"""Performance-attribution layer specs (docs/observability.md §Step-time
attribution, docs/performance.md §Regression sentinel).

Tier-1 coverage for the tentpole: per-step wall-time decomposition summing
back to the measured wall, the analytic cost model agreeing with bench.py's
ResNet-50 convention within 5%, the live train.mfu / collective-bytes
gauges on a real Optimizer run, the recompilation sentinel (counting,
expected-compile suppression, flight events), straggler stats, and the
perf-regression sentinel flagging a synthetic 20% throughput drop against
the committed trajectory."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bigdl_tpu.obs import attr as obs_attr
from bigdl_tpu.obs import cost as obs_cost
from bigdl_tpu.obs import flight
from bigdl_tpu.obs import sentinel as obs_sentinel
from bigdl_tpu.optim.metrics import Metrics, global_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_perf_obs():
    flight.global_recorder().clear()
    yield
    # a test that marked the process sentinel steady must not leak the
    # armed state into later tests' compiles
    obs_attr.recompile_sentinel().mark_warmup()


# ---------------------------------------------------------------------------
# StepAttribution
# ---------------------------------------------------------------------------

def test_step_attribution_components_sum_to_wall():
    m = Metrics()
    a = obs_attr.StepAttribution(m)
    a.window(steps=4, wall_s=1.0, data_s=0.2, dispatch_s=0.1,
             overhead_s=0.1)
    a.window(steps=4, wall_s=0.8, data_s=0.1, dispatch_s=0.1,
             overhead_s=0.0)
    rep = a.report()
    assert rep["steps"] == 8 and rep["windows"] == 2
    comp_sum = sum(c["total_s"] for c in rep["components"].values())
    assert comp_sum == pytest.approx(rep["wall_s"], rel=1e-9)
    assert rep["components"]["device"]["total_s"] == pytest.approx(1.2)
    fracs = {k: c["fraction"] for k, c in rep["components"].items()}
    assert sum(fracs.values()) == pytest.approx(1.0)
    # per-step samples landed in the train.attr.* histograms
    for name in obs_attr.COMPONENTS:
        assert m.percentile(f"train.attr.{name}_s", 50) >= 0
        assert m.hists[f"train.attr.{name}_s"].n == 2
    table = a.table()
    for name in obs_attr.COMPONENTS:
        assert name in table
    assert "8 steps" in table


def test_step_attribution_device_residual_clamps_at_zero():
    a = obs_attr.StepAttribution(Metrics())
    # host timers overlap the wall (clock skew): device clamps to 0, the
    # report never shows negative time
    a.window(steps=2, wall_s=0.1, data_s=0.08, dispatch_s=0.05,
             overhead_s=0.0)
    rep = a.report()
    assert rep["components"]["device"]["total_s"] == 0.0


def test_step_time_stats():
    s = obs_attr.step_time_stats([0.10, 0.12, 0.11, 0.19])
    assert s["max"] == pytest.approx(0.19)
    assert s["min"] == pytest.approx(0.10)
    assert s["skew"] == pytest.approx(0.09)
    assert s["n_hosts"] == 4
    assert obs_attr.step_time_stats([]) == {}
    # single process: the driver path returns None (nothing to aggregate)
    assert obs_attr.host_step_time_stats(0.1) is None


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_cost_model_linear_mlp_exact():
    import jax

    from bigdl_tpu import nn

    model = nn.Sequential([nn.Linear(32, 64), nn.ReLU(),
                           nn.Linear(64, 8)])
    x = np.zeros((16, 32), np.float32)
    variables = model.init(jax.random.PRNGKey(0), x[:1])
    rep = obs_cost.forward_costs(model, variables, x)
    # 2 * batch * (32*64 + 64*8) matmul flops + 2 flops/elem for the ReLU
    expect = 2 * 16 * (32 * 64 + 64 * 8) + 2 * 16 * 64
    assert rep.flops == pytest.approx(expect)
    assert rep.batch == 16
    assert rep.train_flops() == pytest.approx(3 * expect)
    # scaling to a different batch is linear
    assert obs_cost.train_step_flops(model, variables, (x[:1],), 160) \
        == pytest.approx(3 * expect * 10)
    # the shape-capture walk restored every forward (model still runs)
    y, _ = model.apply(variables, x)
    assert y.shape == (16, 8)


def test_cost_model_resnet50_matches_bench_analytic_within_5pct():
    """Acceptance: the per-layer analytic count on the bench geometry
    (ResNet-50 @224) agrees with bench.py's hardcoded analytic_3x_fwd
    convention (4.09 GMACs forward) within 5% — so the live train.mfu
    gauge and bench.py's analytic MFU agree whenever step time and peak
    agree (they share both other factors by construction)."""
    import jax

    from bigdl_tpu.models.resnet import resnet50

    model = resnet50(classes=1000, stem="conv")
    # init at 64x64: conv/BN/fc param shapes are spatial-size independent,
    # and the real forward that init runs is ~12x cheaper than at 224
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 64, 64, 3), np.float32))
    # the cost trace itself is jax.eval_shape — no FLOP executes at 224
    rep = obs_cost.forward_costs(
        model, variables, np.zeros((1, 224, 224, 3), np.float32))
    bench_fwd_flops = 2 * 4.09e9  # bench.py: ~4.09 GMACs fwd per image
    assert rep.flops == pytest.approx(bench_fwd_flops, rel=0.05)
    # and the training convention matches bench's 3x multiplier exactly
    import bench

    assert rep.train_flops() == pytest.approx(
        bench._RESNET50_TRAIN_FLOPS_PER_IMAGE, rel=0.05)


def test_cost_model_attention_counts_projections_and_scores():
    import jax

    from bigdl_tpu.nn.attention import MultiHeadAttention

    b, t, d = 2, 16, 32
    mha = MultiHeadAttention(hidden_size=d, num_heads=4)
    x = np.zeros((b, t, d), np.float32)
    variables = mha.init(jax.random.PRNGKey(0), x)
    rep = obs_cost.forward_costs(mha, variables, x)
    proj = 4 * 2 * b * t * d * d          # wq/wk/wv/wo
    scores = 4 * b * t * t * d            # qk^T + att@v
    assert rep.flops == pytest.approx(proj + scores)


def test_peak_flops_resolution():
    assert obs_cost.peak_flops("TPU v5 lite") == 197e12
    assert obs_cost.peak_flops("TPU v4") == 275e12
    assert obs_cost.peak_flops("cpu") is None
    # 1e9 flops / 1ms / 2 chips = 5e11 FLOP/s/chip; peak 1e12 -> 50%
    assert obs_cost.mfu(1e9, 0.001, 2, 1e12) == pytest.approx(0.5)
    assert obs_cost.mfu(1e9, 0.001, 1, None) is None


def test_gspmd_collective_bytes_from_specs(mesh8):
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel.gspmd import collective_bytes_for_specs

    params = {"w": np.zeros((4, 2), np.float32),
              "b": np.zeros((2,), np.float32)}
    specs = {"w": P(), "b": P()}
    rep = collective_bytes_for_specs(params, specs, mesh8)
    n_data = rep["n_data_replicas"]
    assert n_data == 8
    # fully replicated: every gradient element allreduces (~2x bytes)
    assert rep["dp_allreduce_bytes_per_step"] == pytest.approx(
        2 * (4 * 2 + 2) * 4)
    # a model-sharded parameter moves only its shard — shard the matrix
    # over the data axis (size 8) to exercise the divisor
    specs2 = {"w": P("data", None), "b": P()}
    rep2 = collective_bytes_for_specs(params, specs2, mesh8)
    assert rep2["grad_shard_bytes"] == pytest.approx((8 / 8 + 2) * 4)


# ---------------------------------------------------------------------------
# live gauges on a real Optimizer run
# ---------------------------------------------------------------------------

def _train(monkeypatch, iterations=12, batch_size=16):
    from bigdl_tpu import nn, optim
    from bigdl_tpu.data import ArrayDataSet

    # CPU has no peak on record (no gauge); give the test mesh one
    monkeypatch.setitem(obs_cost.PEAK_BF16_FLOPS, "cpu", 1e9)
    x = np.random.RandomState(0).rand(64, 4).astype(np.float32)
    y = (x.sum(-1) > 2).astype(np.int32)
    model = nn.Sequential([nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2),
                           nn.LogSoftMax()])
    opt = optim.Optimizer(model, ArrayDataSet(x, y),
                          nn.ClassNLLCriterion(), batch_size=batch_size)
    opt.set_end_when(optim.Trigger.max_iteration(iterations))
    opt.optimize()
    return opt


def test_optimizer_exports_attribution_and_live_mfu(monkeypatch):
    """Acceptance: a real run exports train.mfu / train.flops_per_step /
    train.attr.* / collective-bytes lines, and the attribution components
    sum to within 10% of the measured wall."""
    opt = _train(monkeypatch)
    snap = opt.metrics.snapshot()
    g = snap["gauges"]
    # analytic FLOPs/step: 3 * fwd * batch; fwd(batch=1) covers the two
    # matmuls plus the elementwise ReLU (8 out) and LogSoftMax (2 out)
    fwd1 = 2 * (4 * 8 + 8 * 2) + 2 * 8 + 2 * 2
    assert g["train.flops_per_step"] == pytest.approx(3 * fwd1 * 16)
    # live MFU is the same arithmetic the bench does: achieved/peak
    assert 0 < g["train.mfu"] < 1
    import jax

    assert g["train.mfu"] == pytest.approx(
        g["train.achieved_flops_per_chip"] / 1e9, rel=1e-6)
    assert g["train.achieved_flops_per_chip"] > 0
    # collective ledger: ZeRO-1 scatter+gather of the padded flat vector
    n_pad = 8 * -(-58 // 8)  # 58 params padded to the 8-device data axis
    assert g["train.collective_ici_bytes_per_step"] == n_pad * 4 + n_pad * 4
    assert snap["counters"]["train.collective_ici_bytes_total"] == \
        pytest.approx(g["train.collective_ici_bytes_per_step"] * 12)
    assert g["train.collective_dcn_bytes_per_step"] == 0.0
    # attribution: components sum back to the wall (within the clamp)
    rep = opt.attribution.report()
    assert rep["steps"] == 12
    comp_sum = sum(c["total_s"] for c in rep["components"].values())
    assert comp_sum == pytest.approx(rep["wall_s"], rel=0.10)
    for name in obs_attr.COMPONENTS:
        assert snap["hists"][f"train.attr.{name}_s"]["n"] >= 1
    assert "device" in opt.attribution.table()


def test_optimizer_run_has_no_unexpected_recompiles(monkeypatch):
    """A steady shape-stable run must not trip the recompilation sentinel:
    warmup compiles and bundle/eval builds are expected, and nothing else
    compiles mid-run."""
    g = global_metrics()
    before = g.counter("train.unexpected_recompiles_total")
    compiles_before = g.counter("train.xla_compiles_total")
    _train(monkeypatch, iterations=10)
    assert g.counter("train.xla_compiles_total") > compiles_before
    assert g.counter("train.unexpected_recompiles_total") == before
    assert not any(e["kind"] == "unexpected_recompile"
                   for e in flight.global_recorder().snapshot())


# ---------------------------------------------------------------------------
# recompilation sentinel
# ---------------------------------------------------------------------------

def test_recompile_sentinel_counts_and_flags():
    import jax
    import jax.numpy as jnp

    sent = obs_attr.recompile_sentinel()
    g = global_metrics()
    sent.mark_warmup()
    base_total = g.counter("train.xla_compiles_total")
    base_unexpected = g.counter("train.unexpected_recompiles_total")

    jax.jit(lambda a: a * 3.0 + 17.0)(jnp.ones((5,)))  # warmup compile
    assert g.counter("train.xla_compiles_total") > base_total
    assert g.counter("train.unexpected_recompiles_total") == \
        base_unexpected

    sent.mark_steady(step=42)
    flight.global_recorder().clear()
    jax.jit(lambda a: a * 5.0 - 3.0)(jnp.ones((6,)))  # mid-run cache miss
    # one jit dispatch may emit several backend-compile events (main
    # computation + subcomputations): >= 1, and all attributed
    flagged = g.counter("train.unexpected_recompiles_total")
    assert flagged > base_unexpected
    evt = next(e for e in flight.global_recorder().snapshot()
               if e["kind"] == "unexpected_recompile")
    assert evt["step"] == 42 and evt["duration_s"] > 0

    # an announced compile region is not flagged
    with obs_attr.expected_compile():
        jax.jit(lambda a: a * 7.0 + 1.0)(jnp.ones((7,)))
    assert g.counter("train.unexpected_recompiles_total") == flagged
    assert g.percentile("train.compile_time_s", 50) > 0


# ---------------------------------------------------------------------------
# perf-regression sentinel
# ---------------------------------------------------------------------------

def test_sentinel_history_covers_committed_trajectory():
    history = obs_sentinel.load_history(REPO)
    assert "resnet50_train_throughput" in history
    assert "train_dispatch_overhead_reduction" in history
    assert "loader_pipeline_img_per_sec" in history
    assert "serving_throughput_rps" in history
    assert "serving_p99_ms" in history
    base = obs_sentinel.baseline_for("resnet50_train_throughput", history)
    assert base.value > 0 and base.source.startswith("BENCH_r")
    p99 = obs_sentinel.baseline_for("serving_p99_ms", history)
    assert p99.direction == obs_sentinel.LOWER
    # lower-better baseline is the BEST (smallest) committed latency
    assert p99.value == min(r.value for r in history["serving_p99_ms"])


def test_sentinel_flags_synthetic_20pct_throughput_drop():
    """Acceptance: a synthetic 20% throughput regression against the
    committed trajectory is flagged; a 5% wiggle (inside the 10%
    threshold) passes; a lower-better latency regression is flagged in
    the other direction."""
    history = obs_sentinel.load_history(REPO)
    base = obs_sentinel.baseline_for("resnet50_train_throughput", history)
    verdicts = obs_sentinel.check(
        {"metric": "resnet50_train_throughput", "value": base.value * 0.8},
        history)
    assert len(verdicts) == 1 and verdicts[0].regressed
    assert verdicts[0].ratio == pytest.approx(0.8, abs=0.001)
    ok = obs_sentinel.check(
        {"metric": "resnet50_train_throughput", "value": base.value * 0.95},
        history)
    assert not ok[0].regressed
    p99 = obs_sentinel.baseline_for("serving_p99_ms", history)
    worse = obs_sentinel.check(
        {"requests": 1, "throughput_rps": 1e9, "p50_ms": 0.01,
         "p99_ms": p99.value * 1.25}, history)
    by_family = {v.family: v for v in worse}
    assert by_family["serving_p99_ms"].regressed
    assert not by_family["serving_throughput_rps"].regressed


def test_sentinel_ignores_bad_rows_and_unknown_families():
    history = obs_sentinel.load_history(REPO)
    # an errored/suspect fresh row yields no verdicts (never a false gate)
    assert obs_sentinel.check(
        {"metric": "resnet50_train_throughput", "value": 1.0,
         "error": "tpu unavailable"}, history) == []
    assert obs_sentinel.check(
        {"metric": "resnet50_train_throughput", "value": 1.0,
         "suspect": True}, history) == []
    # unknown family: nothing to regress from
    assert obs_sentinel.check(
        {"metric": "a_brand_new_metric", "value": 1.0}, history) == []
    # wrapped {parsed} round artifacts unwrap
    rows = obs_sentinel.normalize(
        {"n": 5, "rc": 0,
         "parsed": {"metric": "resnet50_train_throughput", "value": 42.0}},
        "wrapped")
    assert rows and rows[0].value == 42.0


def test_sentinel_smoke_cli_gate():
    """The CI step: --smoke proves the gate flags a synthetic regression
    (and passes an on-trajectory row) using only committed artifacts."""
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.obs.sentinel", "--smoke",
         "--root", REPO],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["smoke"] == "ok" and verdict["families"] >= 4


def test_sentinel_cli_fails_on_regressed_fresh_file(tmp_path):
    history = obs_sentinel.load_history(REPO)
    base = obs_sentinel.baseline_for("resnet50_train_throughput", history)
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(
        {"metric": "resnet50_train_throughput", "value": base.value * 0.5}))
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.obs.sentinel", str(fresh),
         "--root", REPO],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["regressed"] is True


# ---------------------------------------------------------------------------
# block-sparse cost model + KERNELS sentinel family (ISSUE 10 satellites)
# ---------------------------------------------------------------------------

def test_cost_model_block_sparse_dense_vs_effective():
    """A pruned BlockSparseLinear reports dense-equivalent flops in
    ``flops`` and density-scaled flops in ``eff_flops`` — so train.mfu
    (dense-equivalent) can't silently inflate: train.effective_mfu sits
    next to it."""
    import jax

    from bigdl_tpu.ops.block_sparse import BlockSparseLinear

    lin = BlockSparseLinear(64, 64, block_shape=(16, 16))
    x = np.zeros((8, 64), np.float32)
    v = lin.init(jax.random.PRNGKey(0), x[:1])
    dense = 2.0 * 8 * 64 * 64
    rep = obs_cost.forward_costs(lin, v, x)
    assert rep.flops == pytest.approx(dense)
    assert rep.eff_flops == pytest.approx(dense)  # unpruned: equal
    lin.prune_to(v["params"], 0.5)
    rep2 = obs_cost.forward_costs(lin, v, x)
    assert rep2.flops == pytest.approx(dense)          # dense-equivalent
    assert rep2.eff_flops == pytest.approx(dense * 0.5)  # executed work
    detail = obs_cost.train_step_flops_detail(lin, v, (x[:1],), 8)
    assert detail["dense"] == pytest.approx(3 * dense)
    # training effective = fwd(eff) + dx(eff) + dw(DENSE — the weight
    # grad is a dense matmul masked on the way out): 2·0.5 + 1 = 2.0
    assert detail["effective"] == pytest.approx(dense * 2.0)


def test_sentinel_kernels_family_normalize_and_gate():
    """KERNELS_r*.json rows gate: per-kernel speedup (higher-better),
    parity_ok rows only, probe_ rows never."""
    doc = {"device_kind": "TPU v5 lite", "all_ok": True, "kernels": {
        "flash_attention_fwd": {"parity_ok": True, "speedup": 1.2,
                                "speedup_amortized": 1.5},
        "fused_layernorm_fwd": {"parity_ok": True, "speedup": 1.0},
        "broken_kernel": {"parity_ok": False, "speedup": 9.9},
        "probe_flash_bq256": {"parity_ok": True, "speedup": 3.0},
    }}
    rows = {r.family: r for r in obs_sentinel.normalize(doc, "t.json")}
    assert rows["kernel_speedup_flash_attention_fwd"].value == 1.5  # amortized preferred
    assert rows["kernel_speedup_fused_layernorm_fwd"].value == 1.0
    assert "kernel_speedup_broken_kernel" not in rows
    assert not any("probe" in f for f in rows)
    assert all(r.direction == obs_sentinel.HIGHER for r in rows.values())


def test_sentinel_kernels_family_in_committed_history_and_gates():
    """The committed KERNELS_r04 rows are in the history, and a 20%
    kernel-speedup regression fails like every other family (the
    `make bench-watch` contract)."""
    history = obs_sentinel.load_history(REPO)
    fam = "kernel_speedup_flash_attention_fwd"
    assert fam in history
    base = obs_sentinel.baseline_for(fam, history)
    assert base.source.startswith("KERNELS_r")
    fresh = {"kernels": {"flash_attention_fwd": {
        "parity_ok": True, "speedup": base.value * 0.8}}}
    verdicts = obs_sentinel.check(fresh, history)
    by_family = {v.family: v for v in verdicts}
    assert by_family[fam].regressed
    ok = obs_sentinel.check({"kernels": {"flash_attention_fwd": {
        "parity_ok": True, "speedup": base.value}}}, history)
    assert not ok[0].regressed


def test_export_help_covers_new_gauges():
    from bigdl_tpu.obs.export import DEFAULT_HELP

    for name in ("train.effective_mfu", "train.effective_flops_per_step",
                 "ops.autotune_trials", "ops.autotune_cache_hits",
                 "ops.autotune_cache_misses"):
        assert name in DEFAULT_HELP and DEFAULT_HELP[name]
