"""Serving through the path a caller reaches: ``InferenceModel`` with a
``DecodeConfig`` -> ``warmup()`` -> ``ServingServer`` + ``HttpFrontend`` on
localhost, driven by a closed loop of streaming clients (a child process,
``benchmark/clients/closed_loop.py``): each client sends its next request
when the last token of the previous one has arrived.  No rate is offered
and none is searched for.

Traffic keys: ``clients``; ``prompt_len`` and ``output_len`` (``lo``,
``hi``, ``dist`` "loguniform" or "uniform"); ``lengths_seed`` and
``requests_per_client``, which fix the SET of request sizes: every
``--seed`` gives the clients the same sizes in another order, with other
token ids; ``ramp_s`` before the window and ``drain_s`` after it;
``logp_tolerance_nats_per_token``.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmark import harness
from benchmark.clients import closed_loop


def draw_lengths(spec, n, rng):
    lo, hi = spec["lo"], spec["hi"]
    if spec["dist"] == "loguniform":
        return np.exp(rng.uniform(np.log(lo), np.log(hi + 1), n)).astype(
            int).clip(lo, hi)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    raise ValueError(f"length distribution {spec['dist']!r}")


def make_plan(traffic, eos_id, seed):
    """Per client, its list of requests.  The sizes come from
    ``lengths_seed``; ``seed`` shuffles them over the clients and draws the
    token ids (uniform on [2, eos_id))."""
    n = traffic["clients"] * traffic["requests_per_client"]
    fixed = np.random.default_rng(traffic["lengths_seed"])
    prompt = draw_lengths(traffic["prompt_len"], n, fixed)
    output = draw_lengths(traffic["output_len"], n, fixed)
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    return [[{"tokens": rng.integers(2, eos_id, int(prompt[j])).tolist(),
              "max_new_tokens": int(output[j])}
             for j in order[c::traffic["clients"]]]
            for c in range(traffic["clients"])]


def check_logp(run, family, eng, params, records, tol):
    """Shortest and longest prompt the window saw, greedy, straight through
    the engine: its summed log-prob against the reference's full forward
    pass at the engine's own tokens."""
    from bigdl_tpu.serving.decode_engine import DecodeRequest

    cfg = run.config
    done = [r for recs in records for r in recs if r.get("tokens")]
    if not done:
        return False
    done.sort(key=lambda r: r["prompt_len"])
    worst = 0.0
    for r in (done[0], done[-1]):
        prompt = np.asarray(r["request_tokens"], np.int32)
        got = eng.submit(DecodeRequest(
            tokens=prompt, max_new_tokens=r["asked"])).wait(timeout=300.0)
        ref = family.reference_answer_logp(
            cfg, params, prompt, np.asarray(got.tokens, np.int32),
            cfg["n_positions"])
        per_token = abs(float(got.logp) - ref) / len(got.tokens)
        worst = max(worst, per_token)
        run.say(f"logp_check prompt_len={len(prompt)} tokens={len(got.tokens)} "
                f"engine_logp={float(got.logp):.4f} reference_logp={ref:.4f} "
                f"per_token_abs_diff={per_token:.2e} tol={tol}")
    return worst <= tol


def drive_clients(run, url, clients, traffic):
    """The child process from ramp to drain, with the window's marks set
    from this side.  Returns (records per client, hung client threads,
    window start, window end)."""
    start_at = time.monotonic() + 1.5     # the child's own start-up
    ws = start_at + traffic["ramp_s"]
    we = ws + run.seconds
    plan = {"url": url, "start_at": start_at, "window_end": we,
            "drain_until": we + traffic["drain_s"], "clients": clients}
    with tempfile.TemporaryDirectory(prefix="bench_serve_") as tmp:
        plan_path = os.path.join(tmp, "plan.json")
        rec_path = os.path.join(tmp, "records.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(closed_loop.__file__),
             plan_path, rec_path])
        try:
            time.sleep(max(0.0, ws - time.monotonic()))
            ws = run.window_start()
            time.sleep(max(0.0, we - time.monotonic()))
            we = run.window_end()
            child.wait(timeout=traffic["drain_s"] + 60.0)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        with open(rec_path) as f:
            got = json.load(f)
    for reqs, recs in zip(clients, got["records"]):  # which prompt each was
        for i, r in enumerate(recs):
            r["request_tokens"] = reqs[i % len(reqs)]["tokens"]
    return got["records"], got["hung"], ws, we


def run(run):
    import jax

    from bigdl_tpu.serving import (DecodeConfig, HttpFrontend, InferenceModel,
                                   ServingConfig, ServingServer)

    cfg, traffic = run.config, run.traffic
    family = harness.load_module("families", cfg["family"])
    s = cfg["serving"]
    t = time.monotonic()
    model = family.build_model(cfg)
    variables = harness.init_variables(model, run.seed, np.zeros((1, 8), np.int32))
    jax.block_until_ready(variables)
    t_build = time.monotonic() - t
    im = InferenceModel(
        model, variables, batch_buckets=(1,),
        decode=DecodeConfig(
            slots=s["slots"], page_size=s["page_size"],
            pages_per_slot=s["pages_per_slot"],
            prompt_chunk=s["prompt_chunk"], prefill_batch=s["prefill_batch"],
            max_new_tokens=traffic["output_len"]["hi"], eos_id=cfg["eos_id"],
            kv_dtype=s["kv_dtype"],
            prefix_cache_pages=s["prefix_cache_pages"]))
    eng = im.decode_engine
    t = time.monotonic()
    im.warmup(np.zeros((8,), np.int32))
    run.say(f"setup: build_s={t_build:.1f} warmup_s={time.monotonic() - t:.1f} "
            f"cap={eng.cfg.cap} length_buckets={eng.cfg.len_buckets()} "
            f"use_flash_decode={eng.cfg.use_flash_decode} (None = the "
            f"engine's own choice: the Pallas kernel on a TPU)")
    srv = ServingServer(im, ServingConfig()).start()
    fe = HttpFrontend(srv, port=0, predict_timeout=300.0).start()
    try:
        clients = make_plan(traffic, cfg["eos_id"], run.seed)
        records, hung, ws, we = drive_clients(run, fe.url, clients, traffic)
        stats = closed_loop.summarize(records, ws, we, cfg["eos_id"])
        run.say("client " + json.dumps(stats) + f" hung={hung}")
        run.say(f"engine_stats={dict(eng.stats)}")
        logp_ok = check_logp(run, family, eng,
                             jax.device_get(variables["params"]), records,
                             traffic["logp_tolerance_nats_per_token"])
    finally:
        fe.stop()
        srv.stop()
        eng.stop()
    checks = {"requests_in_window": stats["attempted"] > 0,
              "none_failed": stats["failed"] == 0 and hung == 0,
              "lengths_as_asked_or_eos": stats["lengths_ok"],
              "logp_matches_reference": logp_ok}
    run.say(f"checks={checks}")
    return {
        "correct": all(checks.values()),
        "attempted": stats["attempted"], "failed": stats["failed"],
        "end_to_end": {k: stats[k] for k in
                       ("serve_tokens_per_s", "ttft_p90_ms", "itl_p95_ms")},
        "evidence": {"client": stats},
    }
