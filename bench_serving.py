"""Sustained-load serving bench — the SERVING_r*.json evidence source.

Methodology matches the r05 capture (tests/test_serving_multiproc.py):
the engine + HTTP frontend run in a SUBPROCESS (their own GIL, a real
socket boundary) and N client threads drive sustained load from this
process.  Differences from r05, which are the point of the r08 rebuild:

- clients hold keep-alive HTTP/1.1 connections (the proxy does the same
  per worker now — TCP setup is no longer billed to every request);
- the engine runs CONTINUOUS batching by default (``--fixed`` re-runs the
  legacy fixed-window loop on the same geometry for the A/B);
- the server installs the PR 6 recompile sentinel, warms every predict
  bucket, marks steady, and the bench finishes with a MIXED-SIZE request
  sweep — the run fails unless the sweep triggers ZERO unexpected XLA
  recompiles (bucket padding doing its job).

Output: one JSON row on the last stdout line (the sentinel's
``_load_fresh`` contract) with ``throughput_rps`` / ``p50_ms`` /
``p99_ms`` / ``avg_batch_size`` — the families the perf-regression
sentinel gates against the committed SERVING_r* trajectory.

The ``--decode`` mode is the DECODE_r*.json evidence source
(docs/serving.md §Autoregressive decode): a subprocess LM server runs
the token-level continuous decode engine, keep-alive STREAMING clients
drive a sustained mixed prompt/output-length geometry, and the run
reports aggregate + per-user tokens/s, time-to-first-token, and
inter-token p99.  The A/B baseline is the SAME engine with
``continuous=False`` — whole-batch-restart admission (every slot must
free before the next wave starts), which is exactly what the one-scan
whole-batch decode serving amounted to; ``speedup_vs_static`` is the
continuous engine's tokens/s over that baseline and is sentinel-gated
(≥2x on the committed geometry).  The sustained mixed-length load
doubles as the recompile sweep: the run fails unless the server saw
ZERO unexpected XLA recompiles.

CLI::

    python bench_serving.py                  # full sustained-load run
    python bench_serving.py --fixed          # legacy-engine A/B
    python bench_serving.py --smoke          # CI gate: correctness +
                                             # batching + zero recompiles
    python bench_serving.py --decode         # token-level decode bench
    python bench_serving.py --decode --smoke # CI gate for the decode path
    python bench_serving.py --decode --spec  # speculative decode A/B
                                             # (DECODE_SPEC_r*.json)
    python bench_serving.py --fleet          # disaggregated decode fleet
    python bench_serving.py --fleet --smoke  # CI gate for the fleet path
    python bench_serving.py --out SERVING_r08.json

The ``--fleet`` mode is the DECODE_POOL_r*.json evidence source
(docs/serving.md §Decode fleet): a ``ServingPool`` subprocess runs a
dedicated ``role=prefill`` worker plus decode workers, the proxy's
KV-aware router splits every streaming ``/generate`` (prompt KV pages
cross the serialized handoff channel), and the same mixed-geometry
streaming clients as ``--decode`` drive it — so the TTFT p99 row is
directly comparable to the committed single-host DECODE_r* baseline,
against which it is gated at >= 2x better.
"""

import argparse
import http.client
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# the r05 geometry: Linear(8,16)+ReLU+Linear(16,4), 2-row requests
SERVER = textwrap.dedent("""
    import sys
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    from bigdl_tpu import nn
    from bigdl_tpu.obs.attr import recompile_sentinel
    from bigdl_tpu.optim.metrics import global_metrics
    from bigdl_tpu.serving.inference_model import InferenceModel
    from bigdl_tpu.serving.server import ServingConfig, ServingServer
    from bigdl_tpu.serving.http_frontend import HttpFrontend

    sent = recompile_sentinel().install()
    model = nn.Sequential([nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4)])
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 8), np.float32))
    im = InferenceModel(model, variables)
    im.warmup(np.zeros((8,), np.float32))   # one compile per bucket
    srv = ServingServer(im, ServingConfig(
        batch_size=%(batch_size)d, batch_timeout_s=%(batch_timeout)s,
        queue_capacity=%(queue_capacity)d,
        continuous=%(continuous)s)).start()
    fe = HttpFrontend(srv, port=0).start()
    probe = np.arange(16, dtype=np.float32).reshape(2, 8) / 16.0
    print("REF=" + json.dumps(im.predict(probe).tolist()), flush=True)
    sent.mark_steady()
    print(f"URL={fe.url}", flush=True)
    sys.stdin.readline()        # parent closes stdin to stop us
    fe.stop(); srv.stop()
    m = global_metrics()
    print("RECOMPILES="
          + str(int(m.counter('train.unexpected_recompiles_total'))),
          flush=True)
    print(f"STATS={srv.stats['batches']},{srv.stats['requests']}",
          flush=True)
""").replace("import sys", "import json\nimport sys", 1)


class _Server:
    """The engine subprocess: URL + REF on start, RECOMPILES/STATS on
    stdin close."""

    def __init__(self, continuous: bool, batch_size: int = 16,
                 batch_timeout_s: float = 0.002,
                 queue_capacity: int = 1024):
        code = SERVER % {"batch_size": batch_size,
                         "batch_timeout": repr(batch_timeout_s),
                         "queue_capacity": queue_capacity,
                         "continuous": repr(continuous)}
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
            p for p in [REPO, os.environ.get("PYTHONPATH")] if p))
        env.pop("XLA_FLAGS", None)
        self.proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                                     stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True)
        self.ref = None
        self.url = None
        deadline = time.time() + 180
        while time.time() < deadline and self.url is None:
            line = self.proc.stdout.readline().strip()
            if line.startswith("REF="):
                self.ref = np.asarray(json.loads(line[4:]), np.float32)
            elif line.startswith("URL="):
                self.url = line[4:]
            elif not line and self.proc.poll() is not None:
                raise RuntimeError("bench server died during startup")
        if self.url is None:
            self.proc.kill()
            raise RuntimeError("bench server never printed its URL")
        host, _, port = self.url.split("//", 1)[1].partition(":")
        self.host, self.port = host, int(port)

    def finish(self) -> dict:
        try:
            if not self.proc.stdin.closed:
                self.proc.stdin.close()
        except OSError:
            pass
        out = self.proc.stdout.read()
        self.proc.wait(timeout=60)
        info = {}
        for line in out.splitlines():
            if line.startswith("RECOMPILES="):
                info["unexpected_recompiles"] = int(line.split("=", 1)[1])
            elif line.startswith("STATS="):
                b, r = line.split("=", 1)[1].split(",")
                info["batches"], info["requests"] = int(b), int(r)
            elif line.startswith("SPEC="):
                d, a, rj = line.split("=", 1)[1].split(",")
                info["spec_drafted"] = int(d)
                info["spec_accepted"] = int(a)
                info["spec_rejected"] = int(rj)
        if "batches" not in info:
            raise RuntimeError(f"bench server exited without stats: {out!r}")
        return info


def _post(host: str, port: int, conn, body: bytes, timeout: float = 30.0,
          decode: bool = True):
    """One keep-alive POST /predict; reconnects once on a stale socket.
    Returns (conn, decoded_json) — or (conn, raw_bytes) with
    ``decode=False``, which keeps client-side JSON work out of the timed
    loop (the bench measures the SERVER, and client CPU competes with it
    on a small box)."""
    for attempt in (0, 1):
        if conn is None:
            conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            conn.request("POST", "/predict", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
        except Exception:
            conn.close()
            conn = None
            if attempt:
                raise
            continue
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {data[:200]!r}")
        return conn, (json.loads(data) if decode else data)
    raise RuntimeError("unreachable")


def _sustained_load(server: _Server, clients: int, duration_s: float):
    """N keep-alive client threads posting the r05-geometry request until
    the deadline; returns (completed, latencies_s, wall_s, errors)."""
    rs = np.random.RandomState(0)
    bodies = [json.dumps({"instances":
                          rs.rand(2, 8).astype(np.float32).tolist()}
                         ).encode() for _ in range(16)]
    lats = [[] for _ in range(clients)]
    errors = []
    start = time.time()
    stop_t = start + duration_s

    def client(ci):
        conn = None
        try:
            i = 0
            while time.time() < stop_t:
                t0 = time.perf_counter()
                conn, raw = _post(server.host, server.port,
                                  conn, bodies[(ci + i) % len(bodies)],
                                  decode=False)
                lats[ci].append(time.perf_counter() - t0)
                if i == 0:   # decode once per client: shape sanity only
                    assert len(json.loads(raw)["predictions"]) == 2
                i += 1
        except Exception as e:  # noqa: BLE001 — reported by the caller
            errors.append(e)
        finally:
            if conn is not None:
                conn.close()

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 120)
    wall = time.time() - start
    flat = np.sort(np.concatenate([np.asarray(x) for x in lats if x]))
    return int(flat.size), flat, wall, errors


def _mixed_size_sweep(server: _Server) -> int:
    """Post one request per odd/over-bucket size: every tail shape the
    bucket padding must absorb without a fresh XLA compile."""
    rs = np.random.RandomState(1)
    n = 0
    conn = None
    for rows in (1, 2, 3, 5, 7, 9, 13, 17, 33, 63, 65, 150, 300):
        body = json.dumps({"instances":
                           rs.rand(rows, 8).astype(np.float32).tolist()}
                          ).encode()
        conn, out = _post(server.host, server.port, conn, body)
        assert len(out["predictions"]) == rows, (
            rows, len(out["predictions"]))
        n += 1
    if conn is not None:
        conn.close()
    return n


def run_bench(continuous: bool, clients: int, duration_s: float) -> dict:
    server = _Server(continuous=continuous)
    try:
        # correctness probe against the server's own reference prediction
        conn, out = _post(server.host, server.port, None, json.dumps(
            {"instances": (np.arange(16, dtype=np.float32)
                           .reshape(2, 8) / 16.0).tolist()}).encode())
        conn.close()
        np.testing.assert_allclose(
            np.asarray(out["predictions"], np.float32), server.ref,
            rtol=1e-5, atol=1e-6)
        # brief warm phase (HTTP handler threads, client sockets) that
        # stays out of the measured window
        _sustained_load(server, clients, min(0.5, duration_s))
        completed, lats, wall, errors = _sustained_load(
            server, clients, duration_s)
        if errors:
            raise RuntimeError(f"{len(errors)} client errors: {errors[0]}")
        swept = _mixed_size_sweep(server)
    finally:
        info = server.finish()
    # engine-side stats cover warmup+probe+sweep too; the occupancy ratio
    # is measured over the whole run — continuous assembly must keep it
    # up across all phases, not just the measured window
    avg_batch = round(info["requests"] / max(info["batches"], 1), 2)
    return {
        "engine": "continuous" if continuous else "fixed",
        # sentinel family scope: same-geometry captures gate each other;
        # the untagged r04/r05 light-load rows stay out of this trajectory
        "geometry": f"sustained_c{clients}",
        "requests": completed,
        "concurrent_clients": clients,
        "duration_s": round(wall, 2),
        "batches": info["batches"],
        "avg_batch_size": avg_batch,
        "occupancy": round(avg_batch / 16.0, 4),
        "throughput_rps": round(completed / wall, 1),
        "p50_ms": round(float(lats[int(0.50 * (lats.size - 1))]) * 1e3, 2),
        "p99_ms": round(float(lats[int(0.99 * (lats.size - 1))]) * 1e3, 2),
        "mixed_size_sweep": swept,
        "unexpected_recompiles": info.get("unexpected_recompiles", -1),
        "keep_alive_clients": True,
    }


def _smoke() -> int:
    """CI gate (seconds-scale, machine-independent): both engines answer
    correctly under concurrent keep-alive load, batching actually
    coalesces, and the mixed-size sweep triggers zero unexpected XLA
    recompiles.  Absolute rps is NOT gated here — that is the committed
    SERVING_r*.json trajectory's job via the perf sentinel."""
    failures = []
    rows = {}
    for continuous in (True, False):
        row = run_bench(continuous, clients=8, duration_s=0.8)
        rows[row["engine"]] = row
        if row["requests"] <= 0:
            failures.append(f"{row['engine']}: no requests completed")
        # avg_batch_size is engine-lifetime requests/batches — the same
        # scope on both sides (the client-side "requests" count covers
        # only the measured window, a mismatched denominator)
        if row["avg_batch_size"] < 1.2:
            failures.append(f"{row['engine']}: batching never coalesced "
                            f"(avg batch {row['avg_batch_size']} under "
                            f"8 concurrent clients)")
        if row["unexpected_recompiles"] != 0:
            failures.append(
                f"{row['engine']}: {row['unexpected_recompiles']} "
                "unexpected XLA recompiles across the mixed-size sweep")
    print(json.dumps({"smoke": "ok" if not failures else "fail",
                      "failures": failures,
                      "continuous_rps": rows["continuous"]["throughput_rps"],
                      "fixed_rps": rows["fixed"]["throughput_rps"],
                      "continuous_avg_batch":
                          rows["continuous"]["avg_batch_size"],
                      "fixed_avg_batch": rows["fixed"]["avg_batch_size"]}))
    return 0 if not failures else 1


# ---------------------------------------------------------------------------
# token-level decode bench (--decode): the DECODE_r*.json evidence source
# ---------------------------------------------------------------------------

# tiny LM geometry: vocab 64, hidden 32, 2 heads, 2 layers; slot pool 8,
# 8-token pages, 64-token cap.  Continuous vs whole-batch-restart rides
# the SAME engine code behind DecodeConfig(continuous=).
DECODE_SERVER = textwrap.dedent("""
    import json
    import sys
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    from bigdl_tpu.nn.attention import Transformer
    from bigdl_tpu.obs.attr import recompile_sentinel
    from bigdl_tpu.optim.metrics import global_metrics
    from bigdl_tpu.serving import (DecodeConfig, InferenceModel,
                                   ServingConfig, ServingServer,
                                   SpecConfig)
    from bigdl_tpu.serving.http_frontend import HttpFrontend

    sent = recompile_sentinel().install()
    model = Transformer(vocab_size=64, hidden_size=32, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    variables = model.init(jax.random.PRNGKey(0),
                           np.arange(8, dtype=np.int32)[None])
    im = InferenceModel(model, variables, decode=DecodeConfig(
        slots=%(slots)d, page_size=8, pages_per_slot=16, prompt_chunk=8,
        max_new_tokens=120, eos_id=1, continuous=%(continuous)s,
        kv_dtype=%(kv_dtype)r, speculative=%(speculative)s),
        weight_quant=%(weight_quant)r)
    im.decode_engine.warmup()
    srv = ServingServer(im, ServingConfig(batch_size=8)).start()
    fe = HttpFrontend(srv, port=0).start()
    sent.mark_steady()
    print(f"URL={fe.url}", flush=True)
    sys.stdin.readline()
    fe.stop(); srv.stop(); im.decode_engine.stop()
    m = global_metrics()
    print("RECOMPILES="
          + str(int(m.counter('train.unexpected_recompiles_total'))),
          flush=True)
    st = im.decode_engine.stats
    print("STATS=%%d,%%d" %% (st['steps'], st['completed']), flush=True)
    print("SPEC=%%d,%%d,%%d" %% (st['spec_drafted'], st['spec_accepted'],
                                 st['spec_rejected']), flush=True)
""")


class _DecodeServer(_Server):
    def __init__(self, continuous: bool, slots: int = 8,
                 kv_dtype: str = "float32", weight_quant=None,
                 speculative: str = "None"):
        code = DECODE_SERVER % {"continuous": repr(continuous),
                                "slots": slots, "kv_dtype": kv_dtype,
                                "weight_quant": weight_quant,
                                "speculative": speculative}
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       p for p in [REPO, os.environ.get("PYTHONPATH")]
                       if p))
        env.pop("XLA_FLAGS", None)
        self.proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                                     stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True)
        self.ref = None
        self.url = None
        deadline = time.time() + 240
        while time.time() < deadline and self.url is None:
            line = self.proc.stdout.readline().strip()
            if line.startswith("URL="):
                self.url = line[4:]
            elif not line and self.proc.poll() is not None:
                raise RuntimeError("decode bench server died on startup")
        if self.url is None:
            self.proc.kill()
            raise RuntimeError("decode bench server never printed its URL")
        host, _, port = self.url.split("//", 1)[1].partition(":")
        self.host, self.port = host, int(port)


def _decode_request_mix(rs):
    """One request of the mixed geometry: short prompts, short-heavy
    output lengths (85%) with a long tail (15% near the horizon) — the
    production chat regime, and the one where slot recycling beats
    whole-batch restarts hardest (a wave pays the longest member's
    horizon; the mean request is an order of magnitude shorter)."""
    plen = int(rs.randint(4, 17))
    max_new = int(rs.randint(96, 121) if rs.rand() < 0.15
                  else rs.randint(4, 10))
    prompt = rs.randint(2, 64, (plen,)).tolist()
    return prompt, max_new


def _stream_generate(host, port, conn, body, timeout=60.0):
    """One streaming /generate on a persistent keep-alive connection.
    Returns (conn, t_first_token, token_times, n_tokens)."""
    import http.client as _hc

    for attempt in (0, 1):
        if conn is None:
            conn = _hc.HTTPConnection(host, port, timeout=timeout)
        try:
            conn.request("POST", "/generate", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
        except Exception:
            conn.close()
            conn = None
            if attempt:
                raise
            continue
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: "
                               f"{resp.read()[:200]!r}")
        t_first = None
        times = []
        while True:
            line = resp.readline()
            if not line:
                break
            # the bench measures the SERVER; keep client-side JSON work
            # out of the per-token loop (it competes for the same CPU)
            if line.startswith(b'{"token"'):
                times.append(time.time())
                if t_first is None:
                    t_first = times[-1]
                continue
            line = line.strip()
            if not line:
                continue
            event = json.loads(line)
            if event.get("done") or "error" in event:
                if "error" in event:
                    raise RuntimeError(f"generate error: {event}")
                break
        resp.read()   # drain the terminal chunk so the conn is reusable
        return conn, t_first, times
    raise RuntimeError("unreachable")


def _decode_client_threads(host: str, port: int, clients: int,
                           duration_s: float, seed0: int):
    """The thread-level load loop (one process's worth of clients).
    Token RATE accounting is windowed: only tokens that arrived inside
    the ``duration_s`` window count, so in-flight stragglers drained
    after the deadline neither inflate nor dilute tokens/s.  Latency
    samples (TTFT, inter-token gaps) keep every completed request."""
    ttfts, gaps, errors = [], [], []
    in_window = [0]
    lock = threading.Lock()
    start_t = time.time()
    stop_t = start_t + duration_s

    def client(ci):
        rs = np.random.RandomState(seed0 + ci)
        conn = None
        try:
            while time.time() < stop_t:
                prompt, max_new = _decode_request_mix(rs)
                body = json.dumps({"tokens": prompt,
                                   "max_new_tokens": max_new,
                                   "stream": True}).encode()
                t0 = time.time()
                conn, t_first, times = _stream_generate(
                    host, port, conn, body)
                with lock:
                    if t_first is not None:
                        ttfts.append(t_first - t0)
                    gaps.extend(b - a for a, b in zip(times, times[1:]))
                    in_window[0] += sum(1 for t in times if t <= stop_t)
        except Exception as e:  # noqa: BLE001 — reported by caller
            errors.append(e)
        finally:
            if conn is not None:
                conn.close()

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration_s + 180)
    return ttfts, gaps, in_window[0], errors


def _decode_worker_main(argv) -> int:
    """``--decode-worker host port threads duration seed`` — one load
    PROCESS.  The aggregate token rate of the continuous engine exceeds
    what one Python process's GIL can consume, so the parent fans the
    client threads out over several of these."""
    host, port, threads, duration, seed = (
        argv[0], int(argv[1]), int(argv[2]), float(argv[3]), int(argv[4]))
    # the load generator is the measuring instrument: at the default 5ms
    # GIL switch interval its own thread scheduling shows up in the TTFT
    # and inter-token tails it reports for the server
    sys.setswitchinterval(0.001)
    ttfts, gaps, tokens, errors = _decode_client_threads(
        host, port, threads, duration, seed)
    print(json.dumps({"ttfts": ttfts, "gaps": gaps, "tokens": tokens,
                      "errors": [str(e) for e in errors[:3]]}))
    return 0


def _decode_load(server, clients: int, duration_s: float):
    """Streaming keep-alive load from several worker PROCESSES (a
    single client process saturates its GIL before the server
    saturates) posting mixed-geometry generate requests."""
    procs = max(1, min(4, clients // 8))
    per = clients // procs
    env = dict(os.environ)
    workers = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--decode-worker",
         server.host, str(server.port), str(per), str(duration_s),
         str(1000 + 100 * i)],
        stdout=subprocess.PIPE, text=True, env=env)
        for i in range(procs)]
    ttfts, gaps, errors = [], [], []
    tokens = 0
    for w in workers:
        out, _ = w.communicate(timeout=duration_s + 240)
        row = json.loads(out.strip().splitlines()[-1])
        ttfts.extend(row["ttfts"])
        gaps.extend(row["gaps"])
        tokens += row["tokens"]
        errors.extend(row["errors"])
    # in-window tokens over the nominal window (every worker measures
    # its own); wall returned for the artifact row only
    return ttfts, gaps, [tokens], duration_s, errors


def _pct(xs, q):
    if not xs:
        return 0.0
    xs = np.sort(np.asarray(xs))
    return float(xs[int(q * (xs.size - 1))])


def run_decode_bench(continuous: bool, clients: int,
                     duration_s: float, slots: int = 8,
                     kv_dtype: str = "float32",
                     weight_quant=None,
                     speculative: str = "None") -> dict:
    server = _DecodeServer(continuous=continuous, slots=slots,
                           kv_dtype=kv_dtype, weight_quant=weight_quant,
                           speculative=speculative)
    try:
        # warm phase outside the window: handler threads + client conns
        _decode_load(server, clients, min(0.6, duration_s))
        ttfts, gaps, counts, wall, errors = _decode_load(
            server, clients, duration_s)
        if errors:
            raise RuntimeError(f"{len(errors)} client errors: {errors[0]}")
    finally:
        info = server.finish()
    tokens = int(sum(counts))
    adjud = info.get("spec_accepted", 0) + info.get("spec_rejected", 0)
    return {
        "engine": "continuous" if continuous else "static_batch_restart",
        "spec_drafted": info.get("spec_drafted", 0),
        "spec_accepted": info.get("spec_accepted", 0),
        "spec_accept_rate": (round(info["spec_accepted"] / adjud, 4)
                             if adjud else 0.0),
        "geometry": f"decode_s{slots}_c{clients}",
        "concurrent_clients": clients,
        "duration_s": round(wall, 2),
        "requests": len(ttfts),
        "tokens": tokens,
        "tokens_per_s": round(tokens / wall, 1),
        "tokens_per_s_user": round(tokens / wall / clients, 2),
        "ttft_ms_p50": round(_pct(ttfts, 0.50) * 1e3, 2),
        "ttft_ms_p99": round(_pct(ttfts, 0.99) * 1e3, 2),
        "inter_token_p99_ms": round(_pct(gaps, 0.99) * 1e3, 2),
        "engine_steps": info["batches"],      # STATS first field
        "completed_requests": info["requests"],
        "unexpected_recompiles": info.get("unexpected_recompiles", -1),
        "streaming_clients": True,
    }


def run_decode(clients: int, duration_s: float, out=None,
               smoke: bool = False) -> int:
    """Both arms on the same geometry; the continuous row (plus the
    baseline's tokens/s and the speedup) is the committed artifact."""
    cont = run_decode_bench(True, clients, duration_s)
    static = run_decode_bench(False, clients, duration_s)
    speedup = (round(cont["tokens_per_s"] / static["tokens_per_s"], 2)
               if static["tokens_per_s"] else 0.0)
    row = dict(cont, static_tokens_per_s=static["tokens_per_s"],
               static_ttft_ms_p99=static["ttft_ms_p99"],
               speedup_vs_static=speedup)
    failures = []
    for arm in (cont, static):
        if arm["tokens"] <= 0:
            failures.append(f"{arm['engine']}: no tokens generated")
        if arm["unexpected_recompiles"] != 0:
            failures.append(
                f"{arm['engine']}: {arm['unexpected_recompiles']} "
                "unexpected XLA recompiles under the mixed-length load")
    if not smoke and speedup < 2.0:
        failures.append(f"continuous tokens/s only {speedup}x the "
                        "whole-batch-restart baseline (< 2x)")
    if out:
        with open(out, "w") as f:
            json.dump(row, f, indent=1)
    print(json.dumps(row))
    if failures:
        print("FAIL: " + "; ".join(failures), file=sys.stderr)
        return 1
    return 0


# ---------------------------------------------------------------------------
# quantized decode bench (--decode --quant): the DECODE_QUANT_r*.json
# evidence source (docs/quantization.md §Serving memory hierarchy)
# ---------------------------------------------------------------------------

# Engine-level parity drill run in its own interpreter: builds the SAME
# tiny LM twice — f32 KV + f32 weights vs int8 KV pages + int8 serving
# weights — and greedy-decodes an identical mixed-geometry prompt batch
# through both.  Prints the token-agreement fraction, the per-page HBM
# cost of each KV dtype (the equal-HBM-budget slot math runs on these),
# and the unexpected-recompile counter (both engines warm up BEFORE
# mark_steady, so the int8 programs joining the compile set is expected;
# anything after is not).
QUANT_PARITY = textwrap.dedent("""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    from bigdl_tpu.nn.attention import Transformer
    from bigdl_tpu.obs.attr import recompile_sentinel
    from bigdl_tpu.optim.metrics import global_metrics
    from bigdl_tpu.serving.decode_engine import (DecodeConfig,
                                                 DecodeEngine, LMAdapter)

    sent = recompile_sentinel().install()
    model = Transformer(vocab_size=64, hidden_size=32, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    params = model.init(jax.random.PRNGKey(0),
                        np.arange(8, dtype=np.int32)[None])["params"]
    rs = np.random.RandomState(0)
    prompts = [rs.randint(2, 64, (int(rs.randint(4, 17)),)).tolist()
               for _ in range(8)]

    def build(kv_dtype, weight_quant):
        cfg = DecodeConfig(slots=4, page_size=8, pages_per_slot=16,
                           prompt_chunk=8, max_new_tokens=32, eos_id=1,
                           kv_dtype=kv_dtype)
        eng = DecodeEngine(LMAdapter(model, params, cap=cfg.cap,
                                     weight_quant=weight_quant), cfg)
        eng.warmup()
        return eng

    e32 = build("float32", None)
    e8 = build("int8", "int8")
    sent.mark_steady()
    ref = e32.generate(prompts, max_new_tokens=24)
    qnt = e8.generate(prompts, max_new_tokens=24)
    agree = sum(1 for a, b in zip(ref, qnt)
                if a.tokens.tolist() == b.tokens.tolist()) / len(ref)
    drift = max(abs(a.logp - b.logp) for a, b in zip(ref, qnt))
    print("PARITY=%.4f" % agree, flush=True)
    print("LOGP_DRIFT=%.4f" % drift, flush=True)
    print("BYTES=%d,%d" % (e32.kv_bytes_per_page(),
                           e8.kv_bytes_per_page()), flush=True)
    e32.stop(); e8.stop()
    m = global_metrics()
    print("RECOMPILES="
          + str(int(m.counter('train.unexpected_recompiles_total'))),
          flush=True)
""")


def _run_quant_parity() -> dict:
    """Run the parity drill subprocess; parse its KEY=value lines."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in [REPO, os.environ.get("PYTHONPATH")] if p))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", QUANT_PARITY], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError("quant parity drill died:\n" + proc.stderr[-2000:])
    vals = {}
    for line in proc.stdout.splitlines():
        if "=" in line:
            k, _, v = line.partition("=")
            vals[k.strip()] = v.strip()
    f32_bytes, int8_bytes = (int(x) for x in vals["BYTES"].split(","))
    return {
        "parity": float(vals["PARITY"]),
        "logp_drift": float(vals["LOGP_DRIFT"]),
        "f32_bytes_per_page": f32_bytes,
        "int8_bytes_per_page": int8_bytes,
        "recompiles": int(vals["RECOMPILES"]),
    }


def run_decode_quant(clients: int, duration_s: float, out=None,
                     smoke: bool = False) -> int:
    """The quantized-serving smoke gate (docs/quantization.md §Serving
    memory hierarchy): greedy token parity int8-vs-f32, >= 1.8x slot
    capacity at an EQUAL KV HBM budget, zero unexpected recompiles on
    every arm, and (non-smoke) quantized tokens/s within 10% of the f32
    arm run in the same invocation."""
    par = _run_quant_parity()
    # equal HBM budget: the f32 arm's 8 slots of pages, re-spent on
    # int8 pages (per-page scales included in int8_bytes_per_page)
    base_slots = 8
    ratio = par["f32_bytes_per_page"] / par["int8_bytes_per_page"]
    quant_slots = max(1, int(base_slots * ratio))
    f32 = run_decode_bench(True, clients, duration_s, slots=base_slots,
                           kv_dtype="float32")
    quant = run_decode_bench(True, clients, duration_s,
                             slots=quant_slots, kv_dtype="int8",
                             weight_quant="int8")
    row = {
        "bench": "decode_quant",
        "geometry": f"decode_s{base_slots}q{quant_slots}_c{clients}",
        "concurrent_clients": clients,
        "kv_dtype": "int8",
        "weight_quant": "int8",
        "f32_kv_bytes_per_page": par["f32_bytes_per_page"],
        "int8_kv_bytes_per_page": par["int8_bytes_per_page"],
        "f32_slots": base_slots,
        "int8_slots_equal_hbm": quant_slots,
        "slots_per_chip_ratio": round(quant_slots / base_slots, 2),
        "token_parity": par["parity"],
        "logp_drift_max": par["logp_drift"],
        "f32_tokens_per_s": f32["tokens_per_s"],
        "quant_tokens_per_s": quant["tokens_per_s"],
        "quant_ttft_ms_p99": quant["ttft_ms_p99"],
        "unexpected_recompiles": (par["recompiles"]
                                  + f32["unexpected_recompiles"]
                                  + quant["unexpected_recompiles"]),
    }
    failures = []
    if par["parity"] < 1.0:
        failures.append(f"greedy token parity {par['parity']:.2f} < 1.0 "
                        "(int8 KV + int8 weights vs f32)")
    if row["slots_per_chip_ratio"] < 1.8:
        failures.append(f"int8 slots only {row['slots_per_chip_ratio']}x "
                        "f32 at equal HBM budget (< 1.8x)")
    if row["unexpected_recompiles"] != 0:
        failures.append(f"{row['unexpected_recompiles']} unexpected XLA "
                        "recompiles across the quant sweep")
    for arm, name in ((f32, "f32"), (quant, "int8")):
        if arm["tokens"] <= 0:
            failures.append(f"{name} arm: no tokens generated")
    if not smoke and f32["tokens_per_s"] > 0:
        rel = quant["tokens_per_s"] / f32["tokens_per_s"]
        if rel < 0.9:
            failures.append(f"quantized tokens/s only {rel:.2f}x the f32 "
                            "arm (< 0.9x): dequant overhead regressed")
    if out and not failures:
        with open(out, "w") as f:
            json.dump(row, f, indent=1)
    print(json.dumps(row))
    if failures:
        print("FAIL: " + "; ".join(failures), file=sys.stderr)
        return 1
    return 0


# ---------------------------------------------------------------------------
# speculative decode bench (--decode --spec): the DECODE_SPEC_r*.json
# evidence source (docs/serving.md §Speculative decoding)
# ---------------------------------------------------------------------------

# Engine-level parity drill in its own interpreter: the SAME tiny LM
# spec-off vs spec-on (weight-shared block-sparse draft, k tokens per
# iteration, single-call verify), greedy AND seeded-sample over an
# identical mixed-geometry batch.  Speculation must be invisible in the
# output: byte-identical tokens and logp on both legs (the acceptance
# rule emits only target selections).  Prints the agreement fraction,
# the accept rate, and the unexpected-recompile counter (both engines
# warm BEFORE mark_steady — the draft/verify programs joining the
# compile set is expected; anything after is not).
SPEC_PARITY = textwrap.dedent("""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    from bigdl_tpu.nn.attention import Transformer
    from bigdl_tpu.obs.attr import recompile_sentinel
    from bigdl_tpu.optim.metrics import global_metrics
    from bigdl_tpu.serving.decode_engine import (DecodeConfig,
                                                 DecodeEngine, LMAdapter,
                                                 SpecConfig)

    sent = recompile_sentinel().install()
    model = Transformer(vocab_size=64, hidden_size=32, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    params = model.init(jax.random.PRNGKey(0),
                        np.arange(8, dtype=np.int32)[None])["params"]
    rs = np.random.RandomState(0)
    prompts = [rs.randint(2, 64, (int(rs.randint(4, 17)),)).tolist()
               for _ in range(8)]

    def build(spec):
        cfg = DecodeConfig(slots=4, page_size=8, pages_per_slot=16,
                           prompt_chunk=8, max_new_tokens=32, eos_id=1,
                           speculative=spec)
        eng = DecodeEngine(LMAdapter(model, params, cap=cfg.cap), cfg)
        eng.warmup()
        return eng

    off = build(None)
    on = build(SpecConfig(k=%(k)d, sparsity=%(sparsity)r))
    chunk = build(SpecConfig(k=%(k)d, sparsity=%(sparsity)r,
                             verify_impl="chunk"))
    sent.mark_steady()
    agree = chunk_agree = 0
    for kw in ({}, dict(temperature=0.9, top_k=8, top_p=0.9)):
        ref = off.generate(prompts, max_new_tokens=24, **kw)
        spc = on.generate(prompts, max_new_tokens=24, **kw)
        chk = chunk.generate(prompts, max_new_tokens=24, **kw)
        agree += sum(1 for a, b in zip(ref, spc)
                     if a.tokens.tolist() == b.tokens.tolist()
                     and np.float32(a.logp) == np.float32(b.logp))
        # the chunk verify is a different (multi-query) program: token
        # stream still exact, logp pinned to allclose (same math, one
        # batched softmax instead of k+1 single-token ones)
        chunk_agree += sum(1 for a, b in zip(ref, chk)
                           if a.tokens.tolist() == b.tokens.tolist()
                           and np.allclose(a.logp, b.logp,
                                           rtol=2e-5, atol=2e-5))
    st = on.stats
    adjud = st['spec_accepted'] + st['spec_rejected']
    print("PARITY=%%.4f" %% (agree / (2 * len(prompts))), flush=True)
    print("CHUNK_PARITY=%%.4f" %% (chunk_agree / (2 * len(prompts))),
          flush=True)
    print("ACCEPT=%%.4f" %% (st['spec_accepted'] / max(adjud, 1)),
          flush=True)
    off.stop(); on.stop(); chunk.stop()
    m = global_metrics()
    print("RECOMPILES="
          + str(int(m.counter('train.unexpected_recompiles_total'))),
          flush=True)
""")


# The throughput A/B in its own interpreter, at the geometry where
# speculation's physics live: LONG context (768-token cap, 150-250
# token prompts, 480-token decodes).  Per decoded token the spec-off
# engine re-reads the slot's whole KV pool to score ONE position; the
# draft pays that same read k+1 times but the verify scores k+1
# positions in a single pass over it, so the pool traffic per EMITTED
# token drops by the acceptance-weighted chunk length.  Short-context
# geometries hide this (the pool read is too cheap to amortize) — the
# committed artifact says so via the geometry field.  Arms run ABBA
# (off,on,on,off) per wave with a shared warm wave first: on the
# 1-CPU bench host wall-clock drifts +/-30%% run to run, and pairing
# cancels it where back-to-back arms would bake it in.  Both engines
# warm BEFORE mark_steady; every wave after is a zero-recompile gate.
SPEC_AB = textwrap.dedent("""
    import time
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    from bigdl_tpu.nn.attention import Transformer
    from bigdl_tpu.obs.attr import recompile_sentinel
    from bigdl_tpu.optim.metrics import global_metrics
    from bigdl_tpu.serving.decode_engine import (DecodeConfig,
                                                 DecodeEngine,
                                                 DecodeRequest,
                                                 LMAdapter, SpecConfig)

    sent = recompile_sentinel().install()
    model = Transformer(vocab_size=64, hidden_size=32, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    params = model.init(jax.random.PRNGKey(0),
                        np.arange(8, dtype=np.int32)[None])["params"]

    def build(spec):
        cfg = DecodeConfig(slots=%(slots)d, page_size=16,
                           pages_per_slot=%(pps)d, prompt_chunk=64,
                           max_new_tokens=%(horizon)d, eos_id=1,
                           speculative=spec)
        eng = DecodeEngine(LMAdapter(model, params, cap=cfg.cap), cfg)
        eng.warmup()
        return eng

    off = build(None)
    on = build(SpecConfig(k=%(k)d, sparsity=%(sparsity)r,
                          verify_impl=%(verify_impl)r))
    sent.mark_steady()

    def wave(eng, seed):
        rs = np.random.RandomState(seed)
        reqs = [DecodeRequest(
                    tokens=rs.randint(2, 64, (int(rs.randint(
                        %(plo)d, %(phi)d)),)).astype(np.int32),
                    max_new_tokens=%(new)d, seed=seed * 100 + i)
                for i in range(%(conc)d)]
        t0 = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        outs = [r.wait(timeout=600) for r in reqs]
        dt = time.perf_counter() - t0
        toks = sum(len(o.tokens) for o in outs)
        assert toks > 0, "wave produced no tokens"
        return toks / dt / %(conc)d

    wave(off, 0); wave(on, 0)   # shared warm wave, outside the window
    for w in range(1, %(waves)d + 1):
        a1 = wave(off, w); b1 = wave(on, w)
        b2 = wave(on, w + 100); a2 = wave(off, w + 100)
        print("WAVE=%%.4f,%%.4f" %% (a1 + a2, b1 + b2), flush=True)
    st = on.stats
    adjud = st['spec_accepted'] + st['spec_rejected']
    print("ACCEPT=%%.4f" %% (st['spec_accepted'] / max(adjud, 1)),
          flush=True)
    print("DRAFTED=%%d" %% st['spec_drafted'], flush=True)
    off.stop(); on.stop()
    m = global_metrics()
    print("RECOMPILES="
          + str(int(m.counter('train.unexpected_recompiles_total'))),
          flush=True)
""")


def _run_spec_ab(k: int, sparsity: float, verify_impl: str,
                 smoke: bool) -> dict:
    """Run the paired long-context A/B subprocess; parse its lines.
    Smoke collapses the geometry (256-token cap, 48-token decodes, one
    wave) — it exercises the identical wave/pairing machinery and the
    zero-recompile gate, just not the speedup floor."""
    geo = dict(slots=4, conc=4, k=k, sparsity=sparsity,
               verify_impl=verify_impl)
    if smoke:
        geo.update(pps=16, horizon=64, plo=40, phi=80, new=48, waves=1)
    else:
        geo.update(pps=48, horizon=520, plo=150, phi=250, new=480,
                   waves=3)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in [REPO, os.environ.get("PYTHONPATH")] if p))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SPEC_AB % geo], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError("spec A/B died:\n" + proc.stderr[-2000:])
    waves, vals = [], {}
    for line in proc.stdout.splitlines():
        if line.startswith("WAVE="):
            a, _, b = line[5:].partition(",")
            waves.append((float(a), float(b)))
        elif "=" in line:
            key, _, v = line.partition("=")
            vals[key.strip()] = v.strip()
    return {
        "geometry": ("decode_spec_s4_c4_ctx256_smoke" if smoke
                     else "decode_spec_s4_c4_ctx768"),
        "waves": waves,
        "accept_rate": float(vals["ACCEPT"]),
        "drafted": int(vals["DRAFTED"]),
        "recompiles": int(vals["RECOMPILES"]),
    }


def _run_spec_parity(k: int, sparsity: float) -> dict:
    """Run the spec parity drill subprocess; parse its KEY=value lines."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in [REPO, os.environ.get("PYTHONPATH")] if p))
    env.pop("XLA_FLAGS", None)
    code = SPEC_PARITY % {"k": k, "sparsity": sparsity}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError("spec parity drill died:\n" + proc.stderr[-2000:])
    vals = {}
    for line in proc.stdout.splitlines():
        if "=" in line:
            key, _, v = line.partition("=")
            vals[key.strip()] = v.strip()
    return {
        "parity": float(vals["PARITY"]),
        "chunk_parity": float(vals["CHUNK_PARITY"]),
        "accept_rate": float(vals["ACCEPT"]),
        "recompiles": int(vals["RECOMPILES"]),
    }


def run_decode_spec(out=None, smoke: bool = False, k: int = 48,
                    sparsity: float = 0.5,
                    verify_impl: str = "chunk") -> int:
    """The speculative-decoding gate (docs/serving.md §Speculative
    decoding).  Two drills, each its own interpreter:

    1. Parity: spec-on vs spec-off over an identical batch, greedy AND
       seeded sample.  Scan verify must be BYTE-identical (tokens and
       logp); the chunk verify must match tokens exactly with logp
       allclose.
    2. Throughput: ABBA-paired waves at the long-context geometry,
       tokens/s/user spec-on vs spec-off, median-of-waves speedup
       gated >= 1.5x (non-smoke).

    Zero unexpected recompiles across both drills — every draft /
    verify / step / prefill program joins warmup()'s closed bucket
    set before mark_steady."""
    par = _run_spec_parity(k, sparsity)
    ab = _run_spec_ab(k, sparsity, verify_impl, smoke)
    ratios = sorted(b / a for a, b in ab["waves"] if a > 0)
    speedup = (round(ratios[len(ratios) // 2], 2) if ratios else 0.0)
    off_rate = sorted(a for a, _ in ab["waves"])[len(ab["waves"]) // 2]
    on_rate = sorted(b for _, b in ab["waves"])[len(ab["waves"]) // 2]
    row = {
        "bench": "decode_spec",
        "geometry": ab["geometry"],
        "concurrent_clients": 4,
        "spec_k": k,
        "spec_sparsity": sparsity,
        "spec_verify_impl": verify_impl,
        "token_parity": par["parity"],
        "chunk_token_parity": par["chunk_parity"],
        "accept_rate": ab["accept_rate"],
        "parity_accept_rate": par["accept_rate"],
        # median per-wave PAIRED rates (each wave sums its two ABBA
        # runs); the speedup is the median of per-wave ratios, not the
        # ratio of medians — pairing is what cancels host drift
        "spec_tokens_per_s_user": round(on_rate / 2, 2),
        "base_tokens_per_s_user": round(off_rate / 2, 2),
        "wave_speedups": [round(r, 3) for r in ratios],
        "speedup_vs_off": speedup,
        "spec_drafted": ab["drafted"],
        "unexpected_recompiles": (par["recompiles"]
                                  + ab["recompiles"]),
    }
    failures = []
    if par["parity"] < 1.0:
        failures.append(f"token/logp parity {par['parity']:.2f} < 1.0 "
                        "(spec-on vs spec-off must be byte-identical)")
    if par["chunk_parity"] < 1.0:
        failures.append(f"chunk-verify parity {par['chunk_parity']:.2f}"
                        " < 1.0 (tokens exact, logp allclose)")
    if row["unexpected_recompiles"] != 0:
        failures.append(f"{row['unexpected_recompiles']} unexpected XLA "
                        "recompiles across the spec sweep")
    if ab["drafted"] <= 0:
        failures.append("spec-on arm never drafted — speculation "
                        "silently disabled")
    if not smoke and speedup < 1.5:
        failures.append(f"speculative tokens/s/user only {speedup}x the "
                        "spec-off arm (< 1.5x median of paired waves)")
    if out and not failures:
        with open(out, "w") as f:
            json.dump(row, f, indent=1)
    print(json.dumps(row))
    if failures:
        print("FAIL: " + "; ".join(failures), file=sys.stderr)
        return 1
    return 0


# ---------------------------------------------------------------------------
# disaggregated decode-fleet bench (--fleet): the DECODE_POOL_r*.json
# evidence source (docs/serving.md §Decode fleet)
# ---------------------------------------------------------------------------


def _fleet_loader():
    """Worker-side factory (``bench_serving:_fleet_loader`` in the worker
    interpreter): the SAME tiny LM as the single-host decode bench, with
    a SMALLER slot pool (5 vs 8) — on the CPU bench host the decode
    worker is bound by token DELIVERY (callback -> handler write ->
    relay -> client read all timeshare the cores), not step compute, so
    each extra concurrently-streaming slot stretches the inter-token
    tail by a whole delivery burst; 5 slots keeps the burst short while
    the disaggregated prefill worker absorbs the long-prompt admission
    work that would otherwise stall those bursts.  Everything else
    (model, pages, chunking, request mix) matches DECODE_r*.json so the
    TTFT comparison is honest — plus the fleet pieces (prefix cache;
    the handoff path needs no config).  Installs the recompile sentinel
    so the pool's federated /metrics carries every worker's
    ``train_unexpected_recompiles_total``."""
    import jax
    import numpy as np

    from bigdl_tpu.nn.attention import Transformer
    from bigdl_tpu.obs.attr import recompile_sentinel
    from bigdl_tpu.serving import DecodeConfig, InferenceModel

    jax.config.update("jax_platforms", "cpu")
    sent = recompile_sentinel().install()
    model = Transformer(vocab_size=64, hidden_size=32, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    variables = model.init(jax.random.PRNGKey(0),
                           np.arange(8, dtype=np.int32)[None])
    slots = int(os.environ.get("BIGDL_TPU_FLEET_SLOTS", "5"))
    im = InferenceModel(model, variables, decode=DecodeConfig(
        slots=slots, page_size=8, pages_per_slot=16, prompt_chunk=8,
        max_new_tokens=120, eos_id=1, prefix_cache_pages=16))
    eng = im.decode_engine
    eng.warmup()
    # chaos drill only: throttle the decode loop so the tiny CPU model
    # holds streams in flight long enough for the mid-run SIGKILL to
    # land on live slots (both phases get the same throttle — the
    # baseline/chaos throughput comparison stays honest)
    sleep_s = float(os.environ.get("BIGDL_TPU_CHAOS_DECODE_SLEEP",
                                   "0") or 0)
    if sleep_s > 0:
        import time as _time
        orig_step = eng._decode_step

        def _throttled_step():
            _time.sleep(sleep_s)
            return orig_step()

        eng._decode_step = _throttled_step
    sent.mark_steady()
    return im


FLEET_SERVER = textwrap.dedent("""
    import sys, threading, time
    from bigdl_tpu.serving.pool import ServingPool

    pool = ServingPool("bench_serving:_fleet_loader",
                       workers=%(workers)d, batch_size=8,
                       roles=%(roles)r, worker_env=%(env)r,
                       fleet_split_min_tokens=%(split_min)d,
                       supervise_interval_s=0.5,
                       predict_timeout=%(predict_timeout)f)
    pool.start()

    def _chaos_kill(after):
        # chaos drill (--fleet --chaos): once enough client streams are
        # in flight, SIGKILL one decode-capable worker mid-stream — the
        # proxy must fail its streams over with token parity.  Target a
        # worker that is actually HOLDING live generates (the router may
        # have packed the whole first wave on one worker): a kill that
        # lands on an idle peer proves nothing
        while pool.stats["stream_relays"] < after:
            time.sleep(0.02)
        live = [w for w in reversed(pool.worker_list())
                if w.role != "prefill" and w.alive()]
        victim = None
        deadline = time.time() + 30.0
        while victim is None and time.time() < deadline:
            for w in live:
                h = pool._worker_health(w)
                if (h or {}).get("decode", {}).get(
                        "generate_inflight", 0) >= 1:
                    victim = w
                    break
            else:
                time.sleep(0.02)
        if victim is None and live:
            victim = live[0]
        if victim is not None:
            victim.proc.kill()
            print("KILLED=" + victim.name, flush=True)

    if %(kill_after)d:
        threading.Thread(target=_chaos_kill, args=(%(kill_after)d,),
                         daemon=True).start()
    print(f"URL={pool.url}", flush=True)
    sys.stdin.readline()
    pool.stop()
""")


class _FleetServer:
    """The pool subprocess: proxy + role-assigned workers.  Scraping
    (federated /metrics, /health) happens from the PARENT while the pool
    is still up — ``scrape()`` before ``finish()``.  ``kill_after`` > 0
    arms the chaos thread: one decode-capable worker is SIGKILLed once
    that many client streams have started relaying."""

    def __init__(self, workers: int, roles, split_min: int = 0,
                 kill_after: int = 0, predict_timeout: float = 30.0,
                 decode_sleep: float = 0.0):
        env = {"PYTHONPATH": os.pathsep.join(
                   p for p in [REPO, os.environ.get("PYTHONPATH")] if p),
               "JAX_PLATFORMS": "cpu"}
        if os.environ.get("BIGDL_TPU_FLEET_SLOTS"):
            env["BIGDL_TPU_FLEET_SLOTS"] = \
                os.environ["BIGDL_TPU_FLEET_SLOTS"]
        if decode_sleep > 0:
            env["BIGDL_TPU_CHAOS_DECODE_SLEEP"] = str(decode_sleep)
        code = FLEET_SERVER % {"workers": workers, "roles": list(roles),
                               "env": env, "split_min": split_min,
                               "kill_after": kill_after,
                               "predict_timeout": predict_timeout}
        penv = dict(os.environ, JAX_PLATFORMS="cpu",
                    PYTHONPATH=env["PYTHONPATH"])
        penv.pop("XLA_FLAGS", None)
        self.proc = subprocess.Popen([sys.executable, "-c", code],
                                     env=penv, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True)
        self.url = None
        deadline = time.time() + 240 + 60 * workers
        while time.time() < deadline and self.url is None:
            line = self.proc.stdout.readline().strip()
            if line.startswith("URL="):
                self.url = line[4:]
            elif not line and self.proc.poll() is not None:
                raise RuntimeError("fleet pool died on startup")
        if self.url is None:
            self.proc.kill()
            raise RuntimeError("fleet pool never printed its URL")
        host, _, port = self.url.split("//", 1)[1].partition(":")
        self.host, self.port = host, int(port)

    def scrape(self) -> dict:
        """Fleet-level evidence while the workers are alive: the summed
        recompile counter from the federated exposition, KV handoff +
        prefix-cache totals from /health, and the proxy's routing
        counters."""
        from urllib import request as _rq

        with _rq.urlopen(self.url + "/metrics", timeout=30) as r:
            text = r.read().decode()
        recompiles = sum(
            int(float(line.rsplit(None, 1)[1]))
            for line in text.splitlines()
            if line.startswith("train_unexpected_recompiles_total"))
        with _rq.urlopen(self.url + "/health", timeout=30) as r:
            health = json.loads(r.read())
        kv_exports = kv_imports = hits = misses = 0
        for w in health.get("workers", []):
            d = w.get("decode") or {}
            kv_exports += int(d.get("kv_exports", 0))
            kv_imports += int(d.get("kv_imports", 0))
            pc = d.get("prefix_cache") or {}
            hits += int(pc.get("hits", 0))
            misses += int(pc.get("misses", 0))
        return {"unexpected_recompiles": recompiles,
                "kv_exports": kv_exports, "kv_imports": kv_imports,
                "prefix_cache_hits": hits, "prefix_cache_misses": misses,
                "completed_requests": int(health.get("requests", 0)),
                **{k: health["pool"][k] for k in
                   ("fleet_routed", "fleet_split", "stream_relays")}}

    def finish(self) -> None:
        try:
            self.proc.stdin.close()
            self.proc.wait(timeout=120)
        except Exception:  # noqa: BLE001 — a hung pool must not hang CI
            self.proc.kill()


def run_fleet_bench(workers: int, roles, clients: int,
                    duration_s: float, split_min: int = 0) -> dict:
    server = _FleetServer(workers, roles, split_min=split_min)
    try:
        # warm phase outside the window: relay paths, handoff channel,
        # worker handler threads, client conns
        _decode_load(server, clients, min(0.6, duration_s))
        ttfts, gaps, counts, wall, errors = _decode_load(
            server, clients, duration_s)
        if errors:
            raise RuntimeError(f"{len(errors)} client errors: {errors[0]}")
        fleet = server.scrape()
    finally:
        server.finish()
    tokens = int(sum(counts))
    return {
        "engine": "decode_pool",
        "geometry": f"decode_pool_w{workers}_c{clients}",
        "workers": workers,
        "roles": ",".join(roles),
        "concurrent_clients": clients,
        "duration_s": round(wall, 2),
        "requests": len(ttfts),
        "tokens": tokens,
        "tokens_per_s": round(tokens / wall, 1),
        "tokens_per_s_user": round(tokens / wall / clients, 2),
        "ttft_ms_p50": round(_pct(ttfts, 0.50) * 1e3, 2),
        "ttft_ms_p99": round(_pct(ttfts, 0.99) * 1e3, 2),
        "inter_token_p99_ms": round(_pct(gaps, 0.99) * 1e3, 2),
        "streaming_clients": True,
        **fleet,
    }


def _single_host_ttft_baseline() -> float:
    """The committed single-host decode TTFT p99 the fleet must halve
    (ISSUE gate: disaggregation + capacity, not a lucky run)."""
    try:
        with open(os.path.join(REPO, "DECODE_r01.json")) as f:
            return float(json.load(f)["ttft_ms_p99"])
    except Exception:  # noqa: BLE001 — artifact not committed yet
        return 3028.92


def run_fleet(clients: int, duration_s: float, out=None,
              smoke: bool = False) -> int:
    """One fleet row: a dedicated prefill worker feeding decode workers
    over the serialized KV-handoff channel, streaming mixed-geometry
    clients through the pool proxy's relay.  Smoke keeps the split
    live-or-fail gates; the full run adds the TTFT/inter-token gates
    against the committed single-host baseline."""
    workers, roles = 2, ("prefill", "decode")
    # Split threshold: the handoff has a fixed cost (harvest + serialize
    # + HTTP hop + import) that only beats local recompute past a prompt
    # length, so the full run splits only the long tail of the mixed
    # geometry.  Smoke forces split_min=0 — its 1.5 s window must
    # exercise the handoff channel deterministically, not probabilistically.
    split_min = 0 if smoke else 16
    if smoke:
        clients, duration_s = 6, 1.5
    row = run_fleet_bench(workers, roles, clients, duration_s,
                          split_min=split_min)
    failures = []
    if row["tokens"] <= 0:
        failures.append("no tokens generated")
    if row["unexpected_recompiles"] != 0:
        failures.append(f"{row['unexpected_recompiles']} unexpected XLA "
                        "recompiles across the fleet")
    if row["fleet_split"] < 1 or row["kv_imports"] < 1:
        failures.append("the prefill/decode split never happened "
                        f"(fleet_split={row['fleet_split']}, "
                        f"kv_imports={row['kv_imports']})")
    if row["stream_relays"] < 1:
        failures.append("no streams relayed through the proxy")
    if not smoke:
        ttft_gate = _single_host_ttft_baseline() / 2.0
        if row["ttft_ms_p99"] > ttft_gate:
            failures.append(f"TTFT p99 {row['ttft_ms_p99']}ms > "
                            f"{ttft_gate:.0f}ms (2x single-host gate)")
        if row["inter_token_p99_ms"] > 10.0:
            failures.append(f"inter-token p99 "
                            f"{row['inter_token_p99_ms']}ms > 10ms")
    if out:
        with open(out, "w") as f:
            json.dump(row, f, indent=1)
    print(json.dumps(row))
    if failures:
        print("FAIL: " + "; ".join(failures), file=sys.stderr)
        return 1
    return 0


# ---------------------------------------------------------------------------
# decode-fleet chaos drill (--fleet --chaos): the DECODE_CHAOS_r*.json
# evidence source (docs/serving.md §Fleet fault tolerance)
# ---------------------------------------------------------------------------


def _chaos_request_set(clients: int, per_client: int, seed: int = 7):
    """A FIXED, seeded request set — the same list runs in the no-fault
    baseline phase and the chaos phase, so token parity is a strict
    equality check, not a statistic.  Half the requests are greedy
    (temperature 0), half seeded sampling — both must survive a failover
    byte-identically (the engine keys sampling on absolute position, not
    on who computed the prefix).  Each client's FIRST request carries a
    long output so the mid-run kill lands while most of the first wave
    is still streaming."""
    rs = np.random.RandomState(seed)
    reqs = []
    for ci in range(clients):
        for j in range(per_client):
            plen = int(rs.randint(4, 17))
            max_new = int(rs.randint(48, 81) if j == 0
                          else rs.randint(8, 25))
            seeded = bool(rs.rand() < 0.5)
            reqs.append({
                "client": ci, "rid": f"chaos-{ci}-{j}",
                "tokens": rs.randint(2, 64, (plen,)).tolist(),
                "max_new_tokens": max_new,
                "temperature": 0.8 if seeded else 0.0,
                "top_k": 0, "top_p": 1.0,
                "seed": int(rs.randint(0, 2 ** 31 - 1))})
    return reqs


def _chaos_clients(host: str, port: int, reqs, clients: int):
    """The chaos drill's measuring clients: one thread per client, each
    posting its fixed request list sequentially over a keep-alive
    connection.  Unlike the perf loops, EVERY token line is parsed —
    parity is the gate — and each stream's worst inter-token gap is kept
    as the client-visible recovery latency.  Returns
    ``({rid: tokens}, {rid: max_gap_s}, [(rid, error), ...])``."""
    import http.client as _hc

    by_client = {}
    for r in reqs:
        by_client.setdefault(r["client"], []).append(r)
    results, maxgaps, failed = {}, {}, []
    lock = threading.Lock()

    def one(conn, body):
        for attempt in (0, 1):
            if conn is None:
                conn = _hc.HTTPConnection(host, port, timeout=240.0)
            try:
                conn.request("POST", "/generate", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
            except Exception:
                conn.close()
                conn = None
                if attempt:
                    raise
                continue  # stale keep-alive socket: one fresh retry
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: "
                                   f"{resp.read()[:200]!r}")
            toks, times, final = [], [], None
            while True:
                line = resp.readline()
                if not line:
                    break
                line = line.strip()
                if not line:
                    continue
                ev = json.loads(line)
                if "error" in ev:
                    raise RuntimeError(f"stream error: {ev['error']}")
                if "token" in ev:
                    toks.append(int(ev["token"]))
                    times.append(time.time())
                if ev.get("done"):
                    final = [int(t) for t in ev.get("tokens") or []]
                    break
            resp.read()  # drain the terminal chunk: conn stays reusable
            if final is None:
                # a silent truncation — exactly what failover exists to
                # prevent; the orphan path would have sent an error line
                raise RuntimeError("stream ended without a final verdict")
            if toks and toks != final:
                raise RuntimeError("streamed tokens diverge from the "
                                   f"final verdict: {toks} vs {final}")
            return conn, final, times
        raise RuntimeError("unreachable")

    def run(ci):
        conn = None
        try:
            for r in by_client.get(ci, []):
                body = json.dumps({
                    "tokens": r["tokens"],
                    "max_new_tokens": r["max_new_tokens"],
                    "temperature": r["temperature"],
                    "top_k": r["top_k"], "top_p": r["top_p"],
                    "seed": r["seed"], "stream": True,
                    "request_id": r["rid"]}).encode()
                try:
                    conn, final, times = one(conn, body)
                except Exception as e:  # noqa: BLE001 — the gate counts it
                    with lock:
                        failed.append((r["rid"], str(e)))
                    if conn is not None:
                        conn.close()
                    conn = None
                    continue
                gap = max((b - a for a, b in zip(times, times[1:])),
                          default=0.0)
                with lock:
                    results[r["rid"]] = final
                    maxgaps[r["rid"]] = gap
        finally:
            if conn is not None:
                conn.close()

    threads = [threading.Thread(target=run, args=(ci,))
               for ci in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    return results, maxgaps, failed


def _chaos_phase(reqs, clients: int, chaos: bool):
    """One phase of the drill on a FRESH pool (two "both"-role workers,
    so a killed worker's streams have a live peer to fail over to
    immediately — the supervisor's respawn is the backstop, not the
    recovery path).  Returns the client results plus the proxy's stats
    and restart count, scraped while the pool is still up."""
    kill_after = max(2, clients // 3) if chaos else 0
    server = _FleetServer(2, ("both", "both"), split_min=0,
                          kill_after=kill_after, predict_timeout=60.0,
                          decode_sleep=0.008)
    t0 = time.time()
    try:
        results, maxgaps, failed = _chaos_clients(
            server.host, server.port, reqs, clients)
        wall = time.time() - t0
        from urllib import request as _rq

        with _rq.urlopen(server.url + "/health", timeout=30) as r:
            health = json.loads(r.read())
    finally:
        server.finish()
    return results, maxgaps, failed, {
        "stats": health.get("pool", {}),
        "restarts": int(health.get("restarts", 0))}, wall


def run_fleet_chaos(clients: int, out=None, smoke: bool = False) -> int:
    """The DECODE_CHAOS_r*.json drill: the same fixed request set runs
    against a clean pool (baseline) and against a pool where one decode
    worker is SIGKILLed mid-run.  Gates: ZERO failed requests under
    chaos, byte-identical token sequences for every request (greedy and
    seeded), at least one observed failover, no orphaned streams, and a
    bounded client-visible recovery tail."""
    per_client = 2
    if smoke:
        clients = 6
    reqs = _chaos_request_set(clients, per_client)
    base, _, base_failed, _, _ = _chaos_phase(reqs, clients, chaos=False)
    got, maxgaps, failed, fleet, wall = _chaos_phase(reqs, clients,
                                                     chaos=True)
    stats = fleet["stats"]
    mismatched = [r["rid"] for r in reqs
                  if got.get(r["rid"]) != base.get(r["rid"])]
    recovery_ms_p99 = round(_pct(list(maxgaps.values()), 0.99) * 1e3, 2)
    tokens = sum(len(v) for v in got.values())
    row = {
        "bench": "decode_chaos",
        "engine": "decode_pool",
        "geometry": f"decode_chaos_w2_c{clients}",
        "workers": 2,
        "concurrent_clients": clients,
        "requests": len(reqs),
        "duration_s": round(wall, 2),
        "tokens": tokens,
        "chaos_tokens_per_s": round(tokens / wall, 1) if wall else 0.0,
        "failed_requests": len(failed),
        "baseline_failed_requests": len(base_failed),
        "parity_ok": not mismatched,
        "failovers": int(stats.get("fleet_failovers", 0)),
        "migrations": int(stats.get("fleet_migrations", 0)),
        "resumed_tokens": int(stats.get("fleet_resumed_tokens", 0)),
        "orphaned_requests": int(stats.get("fleet_orphans", 0)),
        "worker_restarts": fleet["restarts"],
        "recovery_ms_p99": recovery_ms_p99,
        "streaming_clients": True,
    }
    failures = []
    if base_failed:
        failures.append(f"{len(base_failed)} baseline failures "
                        f"(first: {base_failed[0]})")
    if failed:
        failures.append(f"{len(failed)} failed requests under chaos "
                        f"(first: {failed[0]})")
    if mismatched:
        failures.append(f"token parity broken across the failover for "
                        f"{mismatched[:4]}")
    if row["failovers"] < 1:
        failures.append("no failover observed — the kill missed every "
                        "in-flight stream")
    if row["orphaned_requests"]:
        failures.append(f"{row['orphaned_requests']} streams orphaned")
    bound_ms = 30000.0 if smoke else 20000.0
    if recovery_ms_p99 > bound_ms:
        failures.append(f"recovery p99 {recovery_ms_p99}ms > "
                        f"{bound_ms:.0f}ms")
    if out and not failures:
        with open(out, "w") as f:
            json.dump(row, f, indent=1)
    print(json.dumps(row))
    if failures:
        print("FAIL: " + "; ".join(failures), file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "--decode-worker":
        return _decode_worker_main(argv[1:])
    ap = argparse.ArgumentParser(
        description="sustained-load serving bench (docs/serving.md)")
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--duration", type=float, default=4.0)
    ap.add_argument("--fixed", action="store_true",
                    help="run the legacy fixed-window engine (A/B)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: correctness + batching + zero "
                         "unexpected recompiles on both engines")
    ap.add_argument("--decode", action="store_true",
                    help="token-level decode bench: continuous vs "
                         "whole-batch-restart, streaming clients")
    ap.add_argument("--quant", action="store_true",
                    help="with --decode: int8 KV pages + int8 serving "
                         "weights vs f32 at equal HBM budget — token "
                         "parity, >= 1.8x slots, zero recompiles")
    ap.add_argument("--spec", action="store_true",
                    help="with --decode: speculative decoding with the "
                         "weight-shared block-sparse draft, spec-on vs "
                         "spec-off A/B — byte parity, >= 1.5x tokens/s"
                         "/user, zero recompiles")
    ap.add_argument("--fleet", action="store_true",
                    help="disaggregated decode-fleet bench: prefill/"
                         "decode split over a worker pool, KV-aware "
                         "routing, streaming relay")
    ap.add_argument("--chaos", action="store_true",
                    help="with --fleet: kill a decode worker mid-run and "
                         "gate zero failed requests + token parity + "
                         "bounded recovery")
    ap.add_argument("--out", default=None,
                    help="also write the artifact JSON here")
    args = ap.parse_args(argv)
    if args.fleet and args.chaos:
        out = args.out
        if out is None and os.environ.get("BIGDL_TPU_WRITE_ARTIFACTS"):
            out = os.path.join(REPO, "DECODE_CHAOS_r01.json")
        clients = 24 if args.clients == 32 else args.clients
        return run_fleet_chaos(clients=clients, out=out,
                               smoke=args.smoke)
    if args.fleet:
        if args.smoke:
            return run_fleet(clients=6, duration_s=1.5, smoke=True)
        out = args.out
        if out is None and os.environ.get("BIGDL_TPU_WRITE_ARTIFACTS"):
            out = os.path.join(REPO, "DECODE_POOL_r01.json")
        # the ISSUE geometry: 24 mixed-geometry streaming clients
        clients = 24 if args.clients == 32 else args.clients
        return run_fleet(clients=clients, duration_s=args.duration,
                         out=out)
    if args.decode and args.spec:
        out = args.out
        if out is None and os.environ.get("BIGDL_TPU_WRITE_ARTIFACTS"):
            out = os.path.join(REPO, "DECODE_SPEC_r01.json")
        return run_decode_spec(out=out, smoke=args.smoke)
    if args.decode and args.quant:
        out = args.out
        if out is None and os.environ.get("BIGDL_TPU_WRITE_ARTIFACTS"):
            out = os.path.join(REPO, "DECODE_QUANT_r01.json")
        if args.smoke:
            return run_decode_quant(clients=4, duration_s=1.5, out=out,
                                    smoke=True)
        clients = 24 if args.clients == 32 else args.clients
        return run_decode_quant(clients=clients,
                                duration_s=args.duration, out=out)
    if args.decode:
        clients = args.clients
        if args.smoke:
            return run_decode(clients=4, duration_s=1.5, smoke=True)
        out = args.out
        if out is None and os.environ.get("BIGDL_TPU_WRITE_ARTIFACTS"):
            out = os.path.join(REPO, "DECODE_r01.json")
        return run_decode(clients=clients, duration_s=args.duration,
                          out=out)
    if args.smoke:
        return _smoke()
    row = run_bench(not args.fixed, args.clients, args.duration)
    out = args.out
    if out is None and os.environ.get("BIGDL_TPU_WRITE_ARTIFACTS"):
        out = os.path.join(REPO, "SERVING_r08.json")
    if out:
        with open(out, "w") as f:
            json.dump(row, f, indent=1)
    print(json.dumps(row))
    if row["unexpected_recompiles"] != 0:
        print("FAIL: unexpected XLA recompiles during the mixed-size "
              "sweep", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
