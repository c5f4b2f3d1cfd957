"""The closed-loop streaming client, and the arithmetic on what it records.

Standard library only: it runs as a child process that never touches JAX,
so the load generator shares neither the chip nor the interpreter lock with
the server.

    python3 benchmark/clients/closed_loop.py <plan.json> <records.json>

Each client thread POSTs ``/generate`` with ``"stream": true``, stamps every
NDJSON event as it arrives (``time.monotonic()``, one clock for all
processes), and sends its next request when the last token is in.  No new
request starts after ``window_end``; one in flight is read to its end, or
abandoned at ``drain_until``.
"""

import http.client
import json
import sys
import threading
import time


def percentile(values, q):
    """Linear interpolation between order statistics (numpy's default)."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def one_request(host, port, req, drain_until):
    rec = {"prompt_len": len(req["tokens"]), "asked": req["max_new_tokens"],
           "stamps": [], "tokens": None, "error": None}
    body = json.dumps({"tokens": req["tokens"], "stream": True,
                       "max_new_tokens": req["max_new_tokens"],
                       "temperature": 0.0}).encode()
    conn = http.client.HTTPConnection(
        host, port, timeout=max(1.0, drain_until - time.monotonic()))
    try:
        rec["sent"] = time.monotonic()
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json",
                              "Connection": "close"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}: {resp.read()[:200]!r}"
            return rec
        while True:
            line = resp.readline()
            now = time.monotonic()
            if now > drain_until:
                rec["error"] = "abandoned at the drain limit"
                break
            if not line:
                rec["error"] = rec["error"] or "stream ended without done"
                break
            event = json.loads(line)
            if "token" in event:
                rec["stamps"].append(now)
            elif "error" in event:
                rec["error"] = str(event["error"])
                break
            elif event.get("done"):
                rec["tokens"] = event.get("tokens")
                rec["done"] = now
                break
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


def client(host, port, requests, plan, out):
    delay = plan["start_at"] - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    i = 0
    while time.monotonic() < plan["window_end"]:
        out.append(one_request(host, port, requests[i % len(requests)],
                               plan["drain_until"]))
        i += 1


def main(plan_path, records_path):
    with open(plan_path) as f:
        plan = json.load(f)
    host, _, port = plan["url"].split("//", 1)[1].partition(":")
    outs = [[] for _ in plan["clients"]]
    threads = [threading.Thread(target=client, daemon=True,
                                args=(host, int(port), reqs, plan, out))
               for reqs, out in zip(plan["clients"], outs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(1.0, plan["drain_until"] + 5 - time.monotonic()))
    hung = sum(t.is_alive() for t in threads)
    with open(records_path, "w") as f:
        json.dump({"records": [list(o) for o in outs], "hung": hung}, f)
    return 0


# -- arithmetic on the records (used by the driver, tested on synthetic sets) --

def summarize(per_client, window_start, window_end, eos_id):
    """End-to-end numbers of one window from the clients' records.

    tokens/s   token events stamped inside the window / its length
    TTFT       first token - sent, over requests SENT inside the window
               (their first token may arrive in the drain)
    gaps       consecutive token stamps of one stream, the later one inside
               the window
    A request counts as attempted when it was sent inside the window, and
    as failed when it then ended in an error, was abandoned, or returned
    neither the tokens it asked for nor a final ``eos_id``."""
    ws, we = window_start, window_end
    tokens_in = 0
    ttft, gaps, idle = [], [], []
    attempted = failed = 0
    lengths_ok = True
    for recs in per_client:
        prev_done = None
        for r in recs:
            if prev_done is not None and "sent" in r:
                idle.append(r["sent"] - prev_done)
            prev_done = r.get("done")
            st = r["stamps"]
            tokens_in += sum(ws <= t <= we for t in st)
            gaps += [b - a for a, b in zip(st, st[1:]) if ws <= b <= we]
            if not ws <= r.get("sent", -1.0) < we:
                continue
            attempted += 1
            toks = r.get("tokens")
            good = (r["error"] is None and toks is not None and
                    (len(toks) == r["asked"] or
                     (0 < len(toks) < r["asked"] and toks[-1] == eos_id)))
            if not good:
                failed += 1
                lengths_ok = lengths_ok and r["error"] is not None
            if st:
                ttft.append(st[0] - r["sent"])
    window = we - ws
    ms = lambda v: None if v is None else v * 1e3
    return {
        "attempted": attempted, "failed": failed, "lengths_ok": lengths_ok,
        "serve_tokens_per_s": tokens_in / window,
        "ttft_p50_ms": ms(percentile(ttft, 50)),
        "ttft_p90_ms": ms(percentile(ttft, 90)),
        "itl_p50_ms": ms(percentile(gaps, 50)),
        "itl_p95_ms": ms(percentile(gaps, 95)),
        "itl_mean_ms": ms(sum(gaps) / len(gaps)) if gaps else None,
        "n_ttft": len(ttft), "n_gaps": len(gaps), "tokens_in_window": tokens_in,
        "coalesced_share": (100.0 * sum(g < 1e-3 for g in gaps) / len(gaps)
                            if gaps else None),
        "client_idle_max_ms": ms(max(idle)) if idle else None,
    }


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
