"""Recsys serving bench — the RECSYS_r*.json evidence source
(docs/recsys.md §Bench geometry).

One run covers the full new-workload vertical:

1. **Features + training**: a string-keyed interaction log goes through
   ``ShardedFeatureTable.gen_string_idx`` (4 partitions) and the vocab is
   checked IDENTICAL to the single-host ``FeatureTable`` twin before it
   feeds a TwoTower trained with the in-batch-softmax step; a
   ``TCNForecaster`` trains through the declarative GSPMD driver
   (``fit(parallelism="dp")``) and an ``AutoformerForecaster`` through
   the classic ZeRO-1 path — the Friesian + Chronos pair the BigDL 2.0
   paper ships as flagship workloads.
2. **Sharded-serving parity**: the SAME checkpoint serves through two
   :class:`~bigdl_tpu.friesian.pipeline.RecommendationPipeline`\\ s —
   unsharded and ``layout="fsdp:2,tp:4"`` vocab-sharded — and the run
   FAILS unless recall candidate ids match exactly and ranked scores
   match to float tolerance (the MLP contraction dims are mesh-sharded,
   so score bits may differ in reduction order; ``scores_byte_equal``
   records the measured truth), and unless per-chip embedding-table
   bytes shrink by >= the mesh model-shard factor.
3. **Sustained mixed-tenant load**: keep-alive clients drive
   ``POST /recommend`` (mixed k) against the sharded pipeline through
   :class:`HttpFrontend` with the recompile sentinel STEADY — the run
   fails on any client error or any unexpected XLA recompile.  Reports
   recommend QPS + p50/p99 and the recall stage's raw candidate
   throughput; the per-axis lookup-collective bytes ride the artifact.

Output: one JSON row on the last stdout line (the sentinel
``_load_fresh`` contract) with ``bench="recsys"`` — the
``recsys_qps`` / ``recsys_recommend_p99_ms`` /
``recsys_recall_candidates_per_s`` families the perf-regression
sentinel gates against the committed RECSYS_r* trajectory.

CLI::

    python bench_recsys.py                   # full run
    python bench_recsys.py --smoke           # CI gate: tiny geometry,
                                             # parity + zero recompiles
    python bench_recsys.py --out RECSYS_r01.json
"""

import os

# a CPU bench by construction: 8 virtual CPU devices, set BEFORE jax
# initializes (same discipline as tests/conftest.py)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import sys
import threading
import time

import numpy as np

import jax

jax.config.update("jax_threefry_partitionable", True)

REPO = os.path.dirname(os.path.abspath(__file__))

LAYOUT = "fsdp:2,tp:4"          # 8 chips, model-shard factor 8
SHARD_FACTOR = 8
HIST_LEN = 8
K_CANDIDATES = 64


def _pct(xs, q: float) -> float:
    xs = np.sort(np.asarray(xs, np.float64))
    if xs.size == 0:
        return 0.0
    return float(xs[int(q * (xs.size - 1))])


# ---------------------------------------------------------------------------
# phase 1: sharded feature engineering -> TwoTower; forecasters
# ---------------------------------------------------------------------------


def build_features(n_users: int, n_items: int, n_rows: int):
    """String-keyed interaction log -> (vocab-parity dict, encoded ids,
    per-user histories).  The vocab comes from the SHARDED path and is
    asserted identical to the single-host twin — the distributed feature
    layer feeding the exact same training step."""
    import pandas as pd

    from bigdl_tpu.friesian.sharded import ShardedFeatureTable
    from bigdl_tpu.friesian.table import FeatureTable

    rs = np.random.RandomState(7)
    u_col = [f"u{rs.randint(n_users):04d}" for _ in range(n_rows)]
    i_col = [f"i{int(rs.zipf(1.3)) % n_items:05d}" for _ in range(n_rows)]
    # coverage tail: every user/item string appears at least once, so the
    # vocab sizes are exactly n+1 (OOV slot 0) — chosen divisible by the
    # mesh model-shard factor, a hard requirement for vocab-dim sharding
    tail = max(n_users, n_items)
    u_col += [f"u{j % n_users:04d}" for j in range(tail)]
    i_col += [f"i{j % n_items:05d}" for j in range(tail)]
    df = pd.DataFrame({"user": u_col, "item": i_col})
    sharded = ShardedFeatureTable.partition(df, 4)
    u_idx, i_idx = sharded.gen_string_idx(["user", "item"])
    su_idx, si_idx = FeatureTable(df).gen_string_idx(["user", "item"])
    vocab_parity = {"user": u_idx.mapping == su_idx.mapping,
                    "item": i_idx.mapping == si_idx.mapping}
    users = u_idx.encode(df["user"])
    items = i_idx.encode(df["item"])
    hists = {}
    for u, i in zip(users, items):
        hists.setdefault(int(u), []).append(int(i))
    return vocab_parity, users, items, hists, u_idx.size, i_idx.size


def train_two_tower_sgd(users, items, hists, n_users: int, n_items: int,
                        dim: int, iters: int, batch: int = 64):
    """The in-batch sampled-softmax step over (user, hist, positive item)
    rows — the standard two-tower objective, plain-SGD on the jit'd
    value_and_grad step (the test_friesian_serving training idiom)."""
    import jax.numpy as jnp

    from bigdl_tpu.models.recsys import TwoTower

    tt = TwoTower(n_users=n_users, n_items=n_items, dim=dim, hidden=(32,))
    rng = jax.random.PRNGKey(0)
    hist_mat = np.zeros((n_users, HIST_LEN), np.int64)
    for u, h in hists.items():
        h = h[-HIST_LEN:]
        hist_mat[u, :len(h)] = h
    params, _ = tt.build(rng, np.zeros((2,), np.int32),
                         np.zeros((2, HIST_LEN), np.int32),
                         np.zeros((2,), np.int32))

    @jax.jit
    def step(params, u, h, i):
        def loss_fn(p):
            logits, _ = tt.forward(p, None, u, h, i)
            labels = jnp.arange(logits.shape[0])
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, labels[:, None], axis=1))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params = jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * g, params, grads)
        return params, loss

    rs = np.random.RandomState(1)
    loss = None
    t0 = time.time()
    for _ in range(iters):
        sel = rs.randint(0, len(users), batch)
        u = users[sel].astype(np.int32)
        i = items[sel].astype(np.int32)
        params, loss = step(params, u, hist_mat[u], i)
    return (tt, {k: np.asarray(v) for k, v in params.items()}, hist_mat,
            float(loss), time.time() - t0)


def train_forecasters(smoke: bool) -> dict:
    """TCN through the declarative GSPMD driver (the satellite's
    ``parallelism=`` carry), Autoformer through the classic path."""
    from bigdl_tpu.forecast.forecaster import (
        AutoformerForecaster, TCNForecaster,
    )

    rs = np.random.RandomState(3)
    n, lookback, horizon = (32, 16, 4) if smoke else (64, 24, 4)
    t = np.cumsum(rs.randn(n, lookback + horizon, 1), axis=1) \
        .astype(np.float32)
    x, y = t[:, :lookback], t[:, lookback:]

    out = {}
    tcn = TCNForecaster(lookback, horizon, 1, 1,
                        num_channels=(8, 8), kernel_size=3, dropout=0.0)
    t0 = time.time()
    tcn.fit((x, y), epochs=1, batch_size=16, parallelism="dp")
    out["tcn"] = {
        "parallelism": "dp",
        "train_time_s": round(time.time() - t0, 2),
        "final_loss": round(float(tcn._layout_stats["losses"][-1]), 5),
        "mesh": tcn._layout_stats["mesh"],
        "mse": round(float(tcn.evaluate((x, y))["mse"]), 5),
    }

    af = AutoformerForecaster(lookback, horizon, 1, 1, d_model=16,
                              n_heads=2, e_layers=1, d_layers=1, d_ff=32)
    t0 = time.time()
    af.fit((x, y), epochs=1, batch_size=16)
    out["autoformer"] = {
        "parallelism": None,
        "train_time_s": round(time.time() - t0, 2),
        "mse": round(float(af.evaluate((x, y))["mse"]), 5),
    }
    return out


# ---------------------------------------------------------------------------
# phase 2: pipelines, parity, sustained /recommend load
# ---------------------------------------------------------------------------


def build_pipelines(tt, params, hist_mat, n_users: int):
    from bigdl_tpu.friesian.pipeline import RecommendationPipeline
    from bigdl_tpu.friesian.serving import FeatureService

    pipes = []
    for layout in (None, LAYOUT):
        fs = FeatureService()
        p = RecommendationPipeline(
            tt, params, fs, hist_len=HIST_LEN, k_candidates=K_CANDIDATES,
            layout=layout, batch_buckets=(1, 4, 16, 64))
        for u in range(n_users):
            p.put_user_history(u, hist_mat[u][hist_mat[u] > 0])
        p.start()
        p.warmup()
        pipes.append(p)
    return pipes


def check_parity(plain, sharded, n_probe: int) -> dict:
    ids_equal = True
    byte_equal = True
    max_diff = 0.0
    for u in range(n_probe):
        s1, i1 = plain.recall_only(u)
        s2, i2 = sharded.recall_only(u)
        ids_equal &= bool(np.array_equal(i1, i2))
        byte_equal &= bool(np.array_equal(s1, s2))
        max_diff = max(max_diff, float(np.max(np.abs(s1 - s2))))
        r1 = plain.recommend(u, k=10)
        r2 = sharded.recommend(u, k=10)
        ids_equal &= [i for i, _ in r1] == [i for i, _ in r2]
        byte_equal &= all(a[1] == b[1] for a, b in zip(r1, r2))
        max_diff = max(max_diff, max(
            (abs(a[1] - b[1]) for a, b in zip(r1, r2)), default=0.0))
    unsharded_bytes = plain.param_bytes_per_chip()
    sharded_bytes = sharded.param_bytes_per_chip()
    factor = {k: unsharded_bytes[k] / max(sharded_bytes[k], 1)
              for k in unsharded_bytes}
    return {
        "candidate_ids_equal": ids_equal,
        "scores_byte_equal": byte_equal,
        "score_max_abs_diff": max_diff,
        "param_bytes_unsharded": unsharded_bytes,
        "param_bytes_per_chip": sharded_bytes,
        "embedding_shard_factor": min(factor.values()) if factor else 0.0,
    }


def run_load(pipe, n_users: int, clients: int, duration_s: float):
    """Keep-alive clients drive POST /recommend (mixed k) through the
    HTTP frontend against the mesh-sharded pipeline."""
    from bigdl_tpu.serving.http_frontend import HttpClient, HttpFrontend

    fe = HttpFrontend(pipe.server, port=0,
                      recsys_pipeline=pipe).start()
    lats, errors = [], []
    stop_t = [0.0]

    def client(seed: int):
        c = HttpClient(fe.url, keep_alive=True)
        rs = np.random.RandomState(seed)
        while time.time() < stop_t[0]:
            u = int(rs.randint(n_users))
            k = int(rs.choice([3, 5, 10]))
            t0 = time.time()
            try:
                items = c.recommend(u, k=k)
                if len(items) != k:
                    raise RuntimeError(
                        f"recommend returned {len(items)} items, want {k}")
            except Exception as e:  # noqa: BLE001 — counted, run fails
                errors.append(repr(e))
                return
            lats.append(time.time() - t0)

    try:
        # warm phase outside the window: handler threads + client conns
        stop_t[0] = time.time() + min(0.6, duration_s)
        warm = [threading.Thread(target=client, args=(100 + i,))
                for i in range(clients)]
        for t in warm:
            t.start()
        for t in warm:
            t.join()
        lats.clear()
        t0 = time.time()
        stop_t[0] = t0 + duration_s
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.time() - t0
    finally:
        fe.stop()
    return lats, errors, wall


def measure_recall_throughput(pipe, n_users: int, iters: int) -> float:
    """Raw recall-stage candidate throughput: full-bucket batches through
    the recall InferenceModel (candidates surfaced per second)."""
    rows = np.stack([pipe._user_row(u % n_users) for u in range(64)]) \
        .astype(np.float32)
    pipe.recall_model.predict(rows)  # ensure compiled/placed
    t0 = time.time()
    for _ in range(iters):
        pipe.recall_model.predict(rows)
    dt = time.time() - t0
    return 64 * iters * pipe.k_candidates / dt


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="recsys serving bench (docs/recsys.md)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: tiny geometry, parity + zero "
                         "unexpected recompiles")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--duration", type=float, default=4.0)
    ap.add_argument("--out", default=None,
                    help="also write the artifact JSON here")
    args = ap.parse_args(argv)

    from bigdl_tpu.obs.attr import recompile_sentinel
    from bigdl_tpu.optim.metrics import global_metrics

    sent = recompile_sentinel().install()
    smoke = args.smoke
    # vocab size is n+1 (OOV slot 0) and must divide by the mesh
    # model-shard factor 8 for vocab-dim sharding -> sizes 24/96, 48/256
    n_users, n_items, n_rows = (23, 95, 600) if smoke else (47, 255, 3000)
    clients = 4 if smoke else args.clients
    duration = 1.5 if smoke else args.duration
    failures = []

    # -- phase 1: features + training --------------------------------------
    vocab_parity, users, items, hists, u_size, i_size = build_features(
        n_users, n_items, n_rows)
    if not all(vocab_parity.values()):
        failures.append(f"sharded vocab != single-host vocab: "
                        f"{vocab_parity}")
    # StringIndex ids start at 1 (slot 0 = OOV) -> table sizes come from
    # the vocab, not the raw generator counts
    n_users, n_items = u_size, i_size
    tt, params, hist_mat, tt_loss, tt_time = train_two_tower_sgd(
        users, items, hists, n_users=n_users, n_items=n_items,
        dim=16, iters=30 if smoke else 150)
    forecast = train_forecasters(smoke)

    # -- phase 2: pipelines + parity ---------------------------------------
    plain, sharded = build_pipelines(tt, params, hist_mat, n_users)
    parity = check_parity(plain, sharded, n_probe=4 if smoke else 8)
    if not parity["candidate_ids_equal"]:
        failures.append("sharded vs unsharded recommend returned "
                        "DIFFERENT candidate ids")
    if parity["score_max_abs_diff"] > 1e-4:
        failures.append(
            f"sharded score drift {parity['score_max_abs_diff']} above "
            "float-reduction tolerance 1e-4")
    if parity["embedding_shard_factor"] < SHARD_FACTOR:
        failures.append(
            f"per-chip embedding bytes shrank only "
            f"{parity['embedding_shard_factor']}x "
            f"(< mesh model-shard factor {SHARD_FACTOR})")

    # -- phase 3: sustained mixed-k load, sentinel steady -------------------
    m = global_metrics()
    before = m.counter("train.unexpected_recompiles_total")
    sent.mark_steady()
    try:
        lats, errors, wall = run_load(sharded, n_users, clients, duration)
        cand_per_s = measure_recall_throughput(
            sharded, n_users, iters=5 if smoke else 25)
    finally:
        sent.mark_warmup()
    recompiles = int(m.counter("train.unexpected_recompiles_total")
                     - before)
    if errors:
        failures.append(f"{len(errors)} client errors: {errors[0]}")
    if not lats:
        failures.append("no completed /recommend requests in the window")
    if recompiles != 0:
        failures.append(f"{recompiles} unexpected XLA recompiles under "
                        "the mixed-k recommend load")

    lookup = sharded.lookup_collective_bytes()
    plain.stop()
    sharded.stop()

    row = {
        "bench": "recsys",
        "geometry": f"recsys_c{clients}_{LAYOUT.replace(',', '_').replace(':', '')}",
        "layout": LAYOUT,
        "concurrent_clients": clients,
        "duration_s": round(wall, 2),
        "requests": len(lats),
        "recsys_qps": round(len(lats) / wall, 1) if wall else 0.0,
        "recommend_p50_ms": round(_pct(lats, 0.50) * 1e3, 2),
        "recommend_p99_ms": round(_pct(lats, 0.99) * 1e3, 2),
        "recall_candidates_per_s": round(cand_per_s, 1),
        "k_candidates": K_CANDIDATES,
        "hist_len": HIST_LEN,
        "n_users": n_users,
        "n_items": n_items,
        "unexpected_recompiles": recompiles,
        "vocab_parity": vocab_parity,
        "parity": parity,
        "lookup_collective_bytes": lookup,
        "two_tower": {"iters": 30 if smoke else 150,
                      "final_loss": round(tt_loss, 5),
                      "train_time_s": round(tt_time, 2)},
        "forecast": forecast,
        "keep_alive_clients": True,
    }
    if smoke:
        row["smoke"] = True
    out = args.out
    if out is None and os.environ.get("BIGDL_TPU_WRITE_ARTIFACTS"):
        out = os.path.join(REPO, "RECSYS_r01.json")
    if out and not smoke:
        with open(out, "w") as f:
            json.dump(row, f, indent=1)
    print(json.dumps(row))
    if failures:
        print("FAIL: " + "; ".join(failures), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
