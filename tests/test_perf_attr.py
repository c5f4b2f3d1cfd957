"""Performance-attribution layer specs (docs/observability.md §Step-time
attribution).

Tier-1 coverage: per-step wall-time decomposition summing back to the
measured wall, the analytic cost model agreeing with the benchmark's
ResNet-50 count within 5%, the live train.mfu / collective-bytes gauges
on a real Optimizer run, the recompilation sentinel (counting,
expected-compile suppression, flight events) and straggler stats."""

import json
import os

import numpy as np
import pytest

from bigdl_tpu.obs import attr as obs_attr
from bigdl_tpu.obs import cost as obs_cost
from bigdl_tpu.obs import flight
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
    a.begin(now=10.0)
    for wall, data, dispatch, sync, overhead in (
            (1.0, 0.2, 0.1, 0.55, 0.1), (0.8, 0.1, 0.1, 0.6, 0.0)):
        a.book("data", data)
        a.book("dispatch", dispatch)
        a.book("sync", sync)
        a.book("overhead", overhead)
        a.steps += 4
        a.end_iteration(now=10.0 + a.wall_s + wall)
    rep = a.report()
    assert rep["steps"] == 8 and rep["windows"] == 2
    comp_sum = sum(c["total_s"] for c in rep["components"].values())
    assert comp_sum == pytest.approx(rep["wall_s"], rel=1e-9)
    assert rep["wall_s"] == pytest.approx(1.8)
    assert rep["components"]["sync"]["total_s"] == pytest.approx(1.15)
    # what nobody timed is reported under its own name, not under sync
    assert rep["components"]["other"]["total_s"] == pytest.approx(0.05)
    fracs = {k: c["fraction"] for k, c in rep["components"].items()}
    assert sum(fracs.values()) == pytest.approx(1.0)
    # every occurrence landed in its histogram (data keeps its old name);
    # compile only when a dispatch compiled
    for name in obs_attr.COMPONENTS:
        hist = obs_attr.HISTOGRAMS[name]
        assert hist == ("train.data_wait_s" if name == "data"
                        else f"train.attr.{name}_s")
        assert (hist in m.hists) == (name != "compile")
        if name != "compile":
            assert m.percentile(hist, 50) >= 0
            assert m.hists[hist].n == 2
    table = a.table()
    for name in obs_attr.COMPONENTS:
        assert name in table
    assert "8 steps" in table


def test_step_attribution_has_no_residual_device_component():
    a = obs_attr.StepAttribution(Metrics())
    assert "device" not in obs_attr.COMPONENTS
    # an iteration whose phases fill its wall leaves other at zero: there
    # is no component that soaks up what the others left, so nothing is
    # booked as device work that nobody measured
    a.begin(now=0.0)
    a.book("data", 0.05)
    a.book("dispatch", 0.05)
    a.end_iteration(now=0.1)
    rep = a.report()
    assert rep["components"]["other"]["total_s"] == pytest.approx(0.0,
                                                                  abs=1e-12)
    assert rep["components"]["sync"]["total_s"] == 0.0
    # without begin() an iteration cannot be closed (nothing to measure)
    b = obs_attr.StepAttribution(Metrics())
    b.end_iteration(now=5.0)
    assert b.report()["wall_s"] == 0.0


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


def test_cost_model_resnet50_matches_benchmark_flops_within_5pct():
    """The program's per-layer count for ResNet-50 @224 agrees within 5%
    with the benchmark's own (``benchmark/flops.py``: 8.18 GFLOP forward,
    x3 a training sample), so the in-program ``train.mfu`` gauge and the
    benchmark's ``train.mfu`` are held to one yardstick: they differ only
    where step time or peak differ."""
    import jax

    from benchmark import flops as benchmark_flops

    from bigdl_tpu.models.resnet import resnet50

    model = resnet50(classes=1000, stem="conv")
    # init at 64x64: conv/BN/fc param shapes are spatial-size independent,
    # and the real forward that init runs is ~12x cheaper than at 224
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 64, 64, 3), np.float32))
    # the cost trace itself is jax.eval_shape — no FLOP executes at 224
    rep = obs_cost.forward_costs(
        model, variables, np.zeros((1, 224, 224, 3), np.float32))
    fwd = benchmark_flops.resnet50_forward_flops(224, 1000)
    assert fwd == pytest.approx(8.18e9, rel=0.01)
    assert rep.flops == pytest.approx(fwd, rel=0.05)
    # and the training convention is the same 3x multiplier
    assert rep.train_flops() == pytest.approx(
        benchmark_flops.TRAIN_OVER_FORWARD * fwd, rel=0.05)


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
    sum to the measured wall."""
    opt = _train(monkeypatch)
    snap = opt.metrics.snapshot()
    g = snap["gauges"]
    # analytic FLOPs/step: 3 * fwd * batch; fwd(batch=1) covers the two
    # matmuls plus the elementwise ReLU (8 out) and LogSoftMax (2 out)
    fwd1 = 2 * (4 * 8 + 8 * 2) + 2 * 8 + 2 * 2
    assert g["train.flops_per_step"] == pytest.approx(3 * fwd1 * 16)
    # live MFU is achieved/peak, the benchmark's own arithmetic
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
    # attribution: the measured components sum back to the wall
    rep = opt.attribution.report()
    assert rep["steps"] == 12
    comp_sum = sum(c["total_s"] for c in rep["components"].values())
    assert comp_sum == pytest.approx(rep["wall_s"], rel=1e-6)
    for name in obs_attr.COMPONENTS:
        assert snap["hists"][obs_attr.HISTOGRAMS[name]]["n"] >= 1
    assert "sync" in opt.attribution.table()
    for gone in ("train.attr.device_s", "train.attr.data_s"):
        assert gone not in snap["hists"]


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
# block-sparse cost model
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


def test_export_help_covers_new_gauges():
    from bigdl_tpu.obs.export import DEFAULT_HELP

    for name in ("train.effective_mfu", "train.effective_flops_per_step",
                 "ops.autotune_trials", "ops.autotune_cache_hits",
                 "ops.autotune_cache_misses"):
        assert name in DEFAULT_HELP and DEFAULT_HELP[name]


# ---------------------------------------------------------------------------
# the step loop explained from inside (ISSUE 26): driver phases that close
# on the wall, the data wait split, idle-by-phase, annotations only under
# the program's own profile
# ---------------------------------------------------------------------------

def _hist(opt, name):
    h = opt.metrics.snapshot()["hists"].get(name)
    return (h["n"], h["sum"]) if h else (0, 0.0)


def test_driver_phases_close_on_the_loop_wall(monkeypatch):
    """(a) every measured phase is observed per step (sync per log
    point), the six sums add up to the loop's wall time, other >= 0."""
    import time

    t0 = time.perf_counter()
    opt = _train(monkeypatch, iterations=12)
    outer = time.perf_counter() - t0
    rep = opt.attribution.report()
    totals = {k: c["total_s"] for k, c in rep["components"].items()}
    assert set(totals) == {"data", "dispatch", "compile", "sync",
                           "overhead", "other"}
    assert sum(totals.values()) == pytest.approx(rep["wall_s"], rel=0.02)
    # the loop's wall is inside optimize()'s and is most of it but for
    # set-up (init, cost model) and the final get_variables
    assert 0 < rep["wall_s"] <= outer
    assert all(v >= 0 for v in totals.values()), totals
    assert _hist(opt, "train.attr.other_s")[0] >= 12
    assert opt.metrics.hists["train.attr.other_s"].min >= 0
    assert _hist(opt, "train.data_wait_s")[0] >= 12
    assert _hist(opt, "train.attr.dispatch_s")[0] == 12
    assert _hist(opt, "train.attr.sync_s")[0] == 12  # log_every = 1
    # overhead: the triggers and the log point of every step, and every
    # end_when call (one after each step, one at the head of each epoch)
    assert _hist(opt, "train.attr.overhead_s")[0] >= 3 * 12
    # histogram sums are the report's totals: one observation path
    for name, total in totals.items():
        assert _hist(opt, obs_attr.HISTOGRAMS[name])[1] == \
            pytest.approx(total, rel=1e-9, abs=1e-12)


def test_sync_is_observed_per_log_point(monkeypatch):
    from bigdl_tpu import optim

    orig = optim.Optimizer.__init__

    def init(self, *a, **kw):
        orig(self, *a, **kw)
        self.log_every = 4

    monkeypatch.setattr(optim.Optimizer, "__init__", init)
    opt = _train(monkeypatch, iterations=12)
    # the log points at 4 and 8 are fetched once step 5 and step 9 are
    # queued; the one at 12 by the flush where the loop leaves
    assert _hist(opt, "train.attr.sync_s")[0] == 3
    assert _hist(opt, "train.attr.dispatch_s")[0] == 12
    assert opt.attribution.report()["windows"] == 3
    assert opt.metrics.counter("train.fetch_overlapped") == 2


def test_cold_compile_is_booked_as_compile_not_dispatch():
    """(b) the defect PERF.md named: a first run that compiles books
    those seconds under compile; dispatch per step is then the same cold
    and warm."""
    import time

    import jax
    import jax.numpy as jnp

    obs_attr.recompile_sentinel()  # listener installed
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64)) * 0.01
    runs = []
    for _ in range(2):  # cold (traces, lowers, compiles), then warm
        a = obs_attr.StepAttribution(Metrics())
        a.begin()
        for _ in range(4):
            with a.phase("dispatch", steps=1) as d:
                y = f(x)
            with a.phase("sync"):
                y.block_until_ready()
            a.end_iteration()
        runs.append((a.report(), d))
    (cold, _), (warm, _) = runs
    assert cold["steps"] == warm["steps"] == 4
    c_cold = cold["components"]["compile"]["total_s"]
    assert c_cold > 0
    assert warm["components"]["compile"]["total_s"] == 0.0
    # the cold run's compile dwarfs its dispatch; with it taken out the
    # two runs' dispatch totals are the same order (a cold jit call is
    # 100x a warm one on any backend)
    d_cold = cold["components"]["dispatch"]["total_s"]
    d_warm = warm["components"]["dispatch"]["total_s"]
    assert c_cold > 5 * d_cold
    assert d_cold < 20 * d_warm + 0.02
    for rep in (cold, warm):
        assert sum(c["total_s"] for c in rep["components"].values()) == \
            pytest.approx(rep["wall_s"], rel=1e-6)
        assert rep["components"]["other"]["total_s"] >= 0


def test_compile_seconds_do_not_count_nested_events_twice():
    import time

    before = obs_attr.compile_seconds()
    t0 = time.perf_counter()
    time.sleep(0.02)
    obs_attr._note_compile(0.01)           # an inner trace, 10 ms
    time.sleep(0.01)
    obs_attr._note_compile(time.perf_counter() - t0)  # the outer one
    outer = time.perf_counter() - t0
    got = obs_attr.compile_seconds() - before
    assert got == pytest.approx(outer, abs=2e-3)
    # a later, separate region adds its own length
    time.sleep(0.005)
    obs_attr._note_compile(0.002)
    assert obs_attr.compile_seconds() - before == \
        pytest.approx(outer + 0.002, abs=2e-3)


def test_compile_seconds_are_per_thread():
    import threading

    before = obs_attr.compile_seconds()
    seen = []

    def other():
        obs_attr._note_compile(0.5)
        seen.append(obs_attr.compile_seconds())

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive() and seen and seen[0] >= 0.4
    assert obs_attr.compile_seconds() == before


class _SlowArrays:
    """An in-memory dataset whose producer sleeps per batch."""

    def __new__(cls, x, y, sleep_s):
        import time

        from bigdl_tpu.data import ArrayDataSet

        class Slow(ArrayDataSet):
            def _emit(self, plan):
                for mb in super()._emit(plan):
                    time.sleep(sleep_s)
                    yield mb

        return Slow(x, y)


def _train_on(dataset, iterations, batch_size=16):
    from bigdl_tpu import nn, optim

    model = nn.Sequential([nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2),
                           nn.LogSoftMax()])
    opt = optim.Optimizer(model, dataset, nn.ClassNLLCriterion(),
                          batch_size=batch_size)
    opt.set_end_when(optim.Trigger.max_iteration(iterations))
    opt.optimize()
    return opt


@pytest.fixture(scope="module")
def slow_producer_run():
    """One run whose producer sleeps 30 ms a batch, beside a fast one."""
    from bigdl_tpu.data import ArrayDataSet

    x = np.random.RandomState(0).rand(64, 4).astype(np.float32)
    y = (x.sum(-1) > 2).astype(np.int32)
    return {"fast": _train_on(ArrayDataSet(x, y), 8),
            "slow": _train_on(_SlowArrays(x, y, 0.03), 8)}


@pytest.mark.parametrize("case", ["parts_within_the_wait", "produce",
                                  "batch_wait"])
def test_data_wait_is_split_where_it_is_spent(slow_producer_run, case):
    """(c) batch_wait + put <= data_wait; a slow producer shows in
    produce and batch_wait.  Only what the sleeps guarantee is asserted:
    ratios between two runs on a shared machine are not (PERF.md §7)."""
    slow = slow_producer_run["slow"]
    if case == "parts_within_the_wait":
        for opt in slow_producer_run.values():
            wait = _hist(opt, "data.batch_wait_s")
            put = _hist(opt, "data.put_s")
            total = _hist(opt, "train.data_wait_s")
            assert wait[0] == put[0] == 8
            assert _hist(opt, "data.produce_s")[0] >= 8
            assert wait[1] + put[1] <= total[1]
    elif case == "produce":
        # 8 batches x 30 ms of sleep, in the producer's thread
        assert _hist(slow, "data.produce_s")[1] >= 8 * 0.03
    else:
        # the driver waited for most of it (the first steps compile
        # meanwhile)
        assert _hist(slow, "data.batch_wait_s")[1] >= 4 * 0.03


def test_epoch_first_wait_once_per_epoch():
    """(d) one observation per epoch's iterator, also in data_wait."""
    from bigdl_tpu.data import ArrayDataSet

    x = np.random.RandomState(0).rand(64, 4).astype(np.float32)
    y = (x.sum(-1) > 2).astype(np.int32)
    opt = _train_on(ArrayDataSet(x, y), 12)   # 4 steps an epoch: 3 epochs
    assert opt.final_state["epoch"] == 3
    n, first_sum = _hist(opt, "data.epoch_first_wait_s")
    assert n == 3
    assert first_sum <= _hist(opt, "train.data_wait_s")[1]


def test_timed_batches_closes_upstream_and_skips_the_exhausted_pull():
    from bigdl_tpu.data.pipeline import timed_batches

    closed = []

    class Src:
        def __iter__(self):
            return iter([1, 2, 3])

        def close(self):
            closed.append(True)

    m = Metrics()
    assert list(timed_batches(Src(), "produce", m)) == [1, 2, 3]
    assert m.hists["data.produce_s"].n == 3 and closed == [True]
    g = timed_batches(Src(), "batch_wait", m, name="val")
    assert next(g) == 1
    g.close()                      # abandoned mid-epoch
    assert closed == [True, True]
    assert m.hists["val.batch_wait_s"].n == 1


@pytest.mark.parametrize("case", ["inside_one", "across_two", "uncovered",
                                  "nested", "no_ops"])
def test_idle_by_phase_on_hand_made_intervals(case):
    """(e) the union-and-gaps walk and the division of a gap."""
    dev = [(0, 10), (5, 12), (20, 30), (30, 31), (50, 60)]  # gaps 12-20, 31-50
    if case == "inside_one":
        out = obs_attr.idle_by_phase(dev[:3], [(11, 25, "train/data")])
        assert out["by_phase"] == {"train/data": 8.0, "none": 0.0}
        assert out["busy"] == 22.0 and out["idle"] == 8.0
        assert out["window"] == 30.0
    elif case == "across_two":
        out = obs_attr.idle_by_phase(
            dev[:3], [(0, 15, "train/sync"), (15, 19, "train/data")])
        assert out["by_phase"] == {"train/sync": 3.0, "train/data": 4.0,
                                   "none": 1.0}
    elif case == "uncovered":
        out = obs_attr.idle_by_phase(dev, [(0, 20, "train/sync")])
        assert out["by_phase"] == {"train/sync": 8.0, "none": 19.0}
        assert out["idle"] == 27.0 and out["busy"] == 33.0
        assert out["window"] == 60.0
    elif case == "nested":
        # data/put inside train/data: the innermost interval is named
        out = obs_attr.idle_by_phase(
            dev, [(10, 45, "train/data"), (13, 16, "data/batch_wait"),
                  (16, 40, "data/put"), (45, 55, "train/dispatch")])
        assert out["by_phase"] == {
            "train/data": 1.0 + 5.0, "data/batch_wait": 3.0,
            "data/put": 4.0 + 9.0, "train/dispatch": 5.0, "none": 0.0}
        assert sum(out["by_phase"].values()) == out["idle"]
    else:
        assert obs_attr.idle_by_phase([], [(0, 1, "train/data")]) is None


def test_regions_are_collected_only_under_a_program_owned_profile(
        monkeypatch, tmp_path):
    """(f) on the clock route (PERF.md §6, PR 26: the profiler's host
    tracer costs too much for annotations): the helpers never enter a
    TraceAnnotation, and record their regions for the idle-by-phase table
    only while IterationProfiler's own trace runs."""
    import json

    import jax

    from bigdl_tpu.obs import trace
    from bigdl_tpu.utils.profiling import IterationProfiler

    def no_annotation(name):
        raise AssertionError(f"TraceAnnotation({name!r}) entered")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_annotation)
    trace.disable()
    a = obs_attr.StepAttribution(Metrics())
    prof = IterationProfiler(str(tmp_path / "own"), start_iter=1,
                             num_iters=1)
    with a.phase("data"), trace.timed("data/put"):
        pass
    # somebody else's profiler (the benchmark's): still nothing
    jax.profiler.start_trace(str(tmp_path / "other"))
    try:
        with a.phase("dispatch"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert prof._spans.spans() == [] and trace._collector is None
    prof.step(1)
    with a.phase("sync", step=1), trace.timed("data/put"):
        pass
    prof.step(2)                    # the window is over: the trace stops
    assert trace._collector is None and prof.done
    with a.phase("sync"):
        pass
    names = [s.name for s in prof._spans.spans()]
    assert names == ["data/put", "train/sync"]   # in order of ending
    # exported beside the xplane, on the monotonic clock
    doc = json.load(open(tmp_path / "own" / "driver_spans.json"))
    sync = next(e for e in doc["traceEvents"] if e["name"] == "train/sync")
    assert sync["args"]["step"] == 1
    assert sync["args"]["mono_end_ns"] >= sync["args"]["mono_start_ns"] > 0
    # the CPU backend has no device plane: the table says so
    assert prof.summary() is None


def test_clock_offset_pairs_dispatches_with_step_programs():
    # device clock starts at 0; the host's monotonic clock reads 1e9 more.
    # Each program starts 0.2-0.5 ms after its dispatch began
    dispatch = [1_000_000_000 + k * 150_000_000 for k in range(4)]
    lat = [500_000, 200_000, 300_000, 400_000]
    program = [d - 1_000_000_000 + l for d, l in zip(dispatch, lat)]
    off = obs_attr.clock_offset(dispatch, program)
    # the program that started soonest after its dispatch sets it; the
    # error is that launch latency, not the spread
    assert off == 1_000_000_000 - 200_000
    assert all(p + off >= d for d, p in zip(dispatch, program))
    assert obs_attr.clock_offset(dispatch, program[:-1]) is None
    assert obs_attr.clock_offset([], []) is None


def test_profiler_summary_lays_driver_spans_over_a_recorded_tpu_trace(
        tmp_path):
    """IterationProfiler.summary() on a recorded v5e trace (two ResNet-50
    steps, 47 ms apart) and hand-made driver spans on another clock."""
    import shutil

    from bigdl_tpu.utils.profiling import IterationProfiler, \
        format_idle_table

    recorded = os.path.join(REPO, "benchmark", "tests", "data",
                            "resnet50_steps.xplane.pb")
    if not os.path.isfile(recorded):
        pytest.skip("no recorded trace in this checkout")
    run_dir = tmp_path / "plugins" / "profile" / "2026_09_30"
    run_dir.mkdir(parents=True)
    shutil.copy(recorded, run_dir / "host.xplane.pb")
    prof = IterationProfiler(str(tmp_path))
    # the two step programs start at 177,662,171 and 352,499,444 ns of the
    # trace's clock; the host's clock reads `host` more, and each dispatch
    # began 0.3 / 0.5 ms before its program
    host = 5_000_000_000_000
    p0, p1 = 177_662_171, 352_499_444
    d0, d1 = host + p0 - 300_000, host + p1 - 500_000
    spans = [("train/dispatch", d0, d0 + 2_000_000),
             ("train/sync", d0 + 2_000_000, d0 + 130_000_000),
             ("train/overhead", d0 + 130_000_000, d0 + 131_000_000),
             ("train/data", d0 + 131_000_000, d1 - 100_000),
             ("data/batch_wait", d0 + 131_500_000, d0 + 160_000_000),
             ("data/put", d0 + 160_000_000, d1 - 200_000),
             ("train/dispatch", d1, d1 + 2_000_000),
             ("train/sync", d1 + 2_000_000, d1 + 131_000_000)]
    for name, a, b in spans:
        prof._spans.add_span(name, a, b)
    out = prof.summary()
    assert out["busy"] == pytest.approx(0.2552551, rel=1e-4)  # as recorded
    assert out["window"] == pytest.approx(0.3024647, rel=1e-4)
    by = out["by_phase"]
    assert sum(by.values()) == pytest.approx(out["idle"], rel=1e-9)
    assert out["idle"] == pytest.approx(out["window"] - out["busy"],
                                        rel=1e-6)
    # the 47 ms between the steps: the device went idle inside the first
    # sync (the fetch's tail), through overhead and the data wait; the
    # offset is set by the dispatch that was 0.3 ms ahead of its program
    gap = (p1 - (p0 + 127_636_348)) * 1e-9
    assert by["data/put"] + by["data/batch_wait"] + by["train/data"] + \
        by["train/overhead"] + by["train/sync"] + by["train/dispatch"] \
        == pytest.approx(gap, rel=0.02)
    assert by["data/batch_wait"] == pytest.approx(0.0285, abs=1e-4)
    assert by["data/put"] > 0.010 and by["none"] < 1e-3
    table = format_idle_table(out)
    assert "data/put" in table and "% idle)" in table
    # a trace whose edges cut a step in flight cannot be paired: it says so
    prof._spans.add_span("train/dispatch", d1 + 200_000_000,
                         d1 + 202_000_000)
    cut = prof.summary()
    assert cut["by_phase"] is None and cut["busy"] == out["busy"]
    assert "not aligned" in format_idle_table(cut)


# ---------------------------------------------------------------------------
# stalls (ISSUE 36): the rule on an injected clock, the host's alibi, the
# device's view
# ---------------------------------------------------------------------------

def _feed(watch, intervals, steps=1, in_flight=1, t_ns=0, it=0,
          outside=0.01):
    """Fetch returns ``intervals`` seconds apart, from ``t_ns``, each
    after a wait of all of its interval but ``outside`` seconds."""
    for dt in intervals:
        t_ns += int(round(dt * 1e9))
        it += steps
        watch.fetched(t_ns, max(dt - outside, 0.0), steps, in_flight, it)
    return t_ns, it


def _warm(watch, n=10, step_s=0.2, steps=1):
    """The first fetch (no sample) and ``n`` ordinary intervals."""
    watch.fetched(0, 0.0, steps, 1, steps)
    return _feed(watch, [step_s * steps] * n, steps=steps, it=steps)


def _stall_events():
    return [e for e in flight.global_recorder().snapshot()
            if e["kind"] == "train_stall"]


@pytest.mark.parametrize("after, where, lost, host_s, device_s", [
    # long, near-zero, normal: the device had run ahead; both intervals'
    # excess is the loss
    ([0.01, 0.2], "host", (2.2 - 0.2) + (0.01 - 0.2), True, False),
    # long, normal: step i+1 could only start when i ended
    ([0.2, 0.2], "device", 2.2 - 0.2, False, True),
    # the run ends on the long fetch: nobody saw what came next
    ([], "unknown", 2.2 - 0.2, False, False),
])
def test_stall_rule_and_its_witness(after, where, lost, host_s, device_s):
    m = Metrics()
    w = obs_attr.StallWatch(m)
    t, it = _warm(w)
    assert m.counter("train.stalls") == 0 and not _stall_events()
    t, it = _feed(w, [2.2], t_ns=t, it=it)
    assert m.counter("train.stalls") == 0       # waits for its witness
    _feed(w, after, t_ns=t, it=it)
    w.exclude()                                  # the flush where a run ends
    (e,) = _stall_events()
    assert e["where"] == where and e["iteration"] == 12
    assert e["interval_s"] == pytest.approx(2.2)
    assert e["baseline_s"] == pytest.approx(0.2)
    assert e["lost_s"] == pytest.approx(lost)
    assert e["steps"] == 1 and e["in_flight"] == 1 and e["alibi"] == {}
    assert e["waited_s"] == pytest.approx(2.19)
    assert m.counter("train.stalls") == 1
    assert m.counter("train.stall_s") == pytest.approx(lost)
    assert m.counter("train.stall_host_s") == \
        pytest.approx(lost if host_s else 0.0)
    assert m.counter("train.stall_device_s") == \
        pytest.approx(lost if device_s else 0.0)
    # every interval is in the program's one histogram, the stall too
    assert m.hists["train.step_time_s"].n == 11 + len(after)
    assert m.hists["train.step_time_s"].sum == \
        pytest.approx(10 * 0.2 + 2.2 + sum(after))
    # the run-ahead interval and the stall stay out of the baseline
    assert list(w._recent).count(pytest.approx(0.2)) == len(w._recent)


def test_stall_rule_scales_with_the_steps_a_fetch_covers():
    m = Metrics()
    w = obs_attr.StallWatch(m)
    t, it = _warm(w, steps=4)                    # 0.8 s a fetch of 4
    assert m.hists["train.step_time_s"].sum == pytest.approx(10 * 0.2)
    # 1.0 s for four steps: 0.2 s over, under max(0.25, 0.2); for one
    # step it would have been a stall
    t, it = _feed(w, [1.0], steps=4, t_ns=t, it=it)
    t, it = _feed(w, [0.8], steps=4, t_ns=t, it=it)
    assert m.counter("train.stalls") == 0
    # 1.3 s: half a second over four baselines
    t, it = _feed(w, [1.3], steps=4, in_flight=4, t_ns=t, it=it)
    # the bundle in flight ran ahead: the next comes in four steps short
    _feed(w, [0.05], steps=4, in_flight=4, t_ns=t, it=it)
    (e,) = _stall_events()
    assert e["where"] == "host" and e["steps"] == 4
    assert e["baseline_s"] == pytest.approx(0.2)
    assert e["lost_s"] == pytest.approx((1.3 - 0.8) + (0.05 - 0.8))


def test_witness_counts_the_steps_that_were_in_flight():
    """At a coarser log cadence one bundle of a fetch's four is ahead:
    the next interval is short by that one step, not near zero."""
    for short, where in ((0.8 - 0.19, "host"), (0.8 - 0.05, "device")):
        flight.global_recorder().clear()
        w = obs_attr.StallWatch(Metrics())
        t, it = _warm(w, steps=4)
        t, it = _feed(w, [3.0], steps=4, in_flight=1, t_ns=t, it=it)
        _feed(w, [short], steps=4, in_flight=1, t_ns=t, it=it)
        assert [e["where"] for e in _stall_events()] == [where]
    # nothing in flight (a flush): nothing could have run ahead
    flight.global_recorder().clear()
    w = obs_attr.StallWatch(Metrics())
    t, it = _warm(w)
    t, it = _feed(w, [3.0], in_flight=0, t_ns=t, it=it)
    _feed(w, [0.2], t_ns=t, it=it)
    assert [e["where"] for e in _stall_events()] == ["unknown"]


@pytest.mark.parametrize("after", [[0.2, 0.2], []])
def test_seconds_lost_outside_the_fetch_are_the_hosts(after):
    """The driver froze before it queued the next bundle (the chip showed
    it, PERF.md §6 PR 36): the fetch then returns at once and the next
    interval is an ordinary one, which alone would read `device`."""
    m = Metrics()
    w = obs_attr.StallWatch(m)
    t, it = _warm(w)
    t, it = _feed(w, [0.76], outside=0.755, t_ns=t, it=it)
    _feed(w, after, t_ns=t, it=it)
    w.exclude()
    (e,) = _stall_events()
    assert e["where"] == "host" and e["waited_s"] == pytest.approx(0.005)
    assert e["lost_s"] == pytest.approx(0.56)
    assert m.counter("train.stall_host_s") == pytest.approx(0.56)
    assert m.counter("train.stall_device_s") == 0


def test_no_stall_is_judged_before_eight_samples():
    m = Metrics()
    w = obs_attr.StallWatch(m)
    t, it = _warm(w, n=7)
    t, it = _feed(w, [5.0, 0.2], t_ns=t, it=it)  # the eighth sample
    assert m.counter("train.stalls") == 0 and not _stall_events()
    assert m.hists["train.step_time_s"].n == 9
    # from here the median of those nine judges: 0.2 s
    t, it = _feed(w, [5.0, 0.2], t_ns=t, it=it)
    assert m.counter("train.stalls") == 1


@pytest.mark.parametrize("what", ["compile", "trigger", "recovery",
                                  "first"])
def test_booked_intervals_are_neither_samples_nor_stalls(what):
    m = Metrics()
    w = obs_attr.StallWatch(m)
    if what == "first":
        # the first window: no interval yet, and nothing stands in for it
        assert w.fetched(5_000_000_000, 4.9, 1, 1, 1) is None
        assert "train.step_time_s" not in m.hists
        return
    t, it = _warm(w)
    if what == "compile":
        obs_attr._note_compile(0.5)   # the driver thread compiled
        # wall time for the caller (the log line, MFU), not a sample
        assert w.fetched(t + 3_000_000_000, 2.9, 1, 1, it + 1) == \
            pytest.approx(3.0)
    else:
        w.exclude()                   # a checkpoint was written / a resume
        assert w.fetched(t + 3_000_000_000, 2.9, 1, 1, it + 1) is None
    t, it = _feed(w, [0.2, 0.2], t_ns=t + 3_000_000_000, it=it + 1)
    assert m.counter("train.stalls") == 0 and not _stall_events()
    assert m.hists["train.step_time_s"].n == 12
    assert m.hists["train.step_time_s"].max == pytest.approx(0.2)


def test_an_excluded_interval_is_no_witness():
    m = Metrics()
    w = obs_attr.StallWatch(m)
    t, it = _warm(w)
    t, it = _feed(w, [2.2], t_ns=t, it=it)
    w.exclude()                       # a trigger's work came next
    (e,) = _stall_events()
    assert e["where"] == "unknown"
    assert m.counter("train.stall_s") == pytest.approx(2.0)
    assert m.counter("train.stall_host_s") == 0
    assert m.counter("train.stall_device_s") == 0


def _host_probe_leftovers():
    import gc
    import threading

    from bigdl_tpu.obs.host import HostProbes

    return ([cb for cb in gc.callbacks
             if isinstance(getattr(cb, "__self__", None), HostProbes)],
            [t for t in threading.enumerate() if t.name == "obs-heartbeat"])


@pytest.mark.parametrize("leaves", ["returns", "raises"])
def test_host_probes_live_as_long_as_optimize(leaves):
    """The counters and the gc histogram exist at 0 when optimize() starts,
    a collection inside the run is booked, and the hook and the heartbeat
    are gone when it returns or raises."""
    import gc

    from bigdl_tpu import nn, optim
    from bigdl_tpu.data import ArrayDataSet

    seen = {}

    def end_when(state):
        if not seen:
            snap = opt.metrics.snapshot()
            seen["counters"] = {k: snap["counters"].get(k)
                                for k in obs_attr.STALL_COUNTERS}
            seen["hists"] = set(snap["hists"])
            seen["global"] = set(global_metrics().snapshot()["hists"])
            seen["alive"] = _host_probe_leftovers()
        if state["iteration"] == 3:
            gc.collect()
            if leaves == "raises":
                raise RuntimeError("the run ends here")
        return state["iteration"] >= 6

    x = np.random.RandomState(0).rand(64, 4).astype(np.float32)
    y = (x.sum(-1) > 2).astype(np.int32)
    model = nn.Sequential([nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2),
                           nn.LogSoftMax()])
    opt = optim.Optimizer(model, ArrayDataSet(x, y), nn.ClassNLLCriterion(),
                          batch_size=16)
    opt.set_end_when(optim.Trigger(end_when, "test"))
    if leaves == "raises":
        with pytest.raises(RuntimeError, match="ends here"):
            opt.optimize()
    else:
        opt.optimize()
    assert seen["counters"] == dict.fromkeys(obs_attr.STALL_COUNTERS, 0.0)
    for name in ("host.gc_pause_s", "host.heartbeat_late_s"):
        assert name in seen["hists"] and name in seen["global"]
    hooks, threads = seen["alive"]
    assert len(hooks) == 1 and len(threads) == 1
    assert _host_probe_leftovers() == ([], [])
    n, total = _hist(opt, "host.gc_pause_s")
    assert n >= 1 and total > 0
    assert opt.metrics.counter('host.gc_collections{generation="2"}') >= 1


def test_heartbeat_reports_the_lateness_it_was_given():
    import threading

    from bigdl_tpu.obs.host import BEAT_S, HostProbes

    now = [1_000_000_000]
    lates = [0.0, 0.004, 1.5, 0.0, 0.03]
    done = threading.Event()

    def sleeper(seconds):
        assert seconds == BEAT_S
        if not lates:
            done.set()
            probes._stop.wait(10.0)   # until stop(); nothing sleeps
            return
        now[0] += int((seconds + lates.pop(0)) * 1e9)

    m = Metrics()
    probes = HostProbes(m, sleep=sleeper, clock_ns=lambda: now[0])
    probes.start()
    try:
        assert done.wait(10.0)
        # over 10 ms late is observed; on time and 4 ms are not
        assert m.hists["host.heartbeat_late_s"].n == 2
        assert m.hists["host.heartbeat_late_s"].sum == pytest.approx(1.53)
        # a stall's record carries the largest lateness of a beat that
        # slept into its interval: the third beat woke at 1.0 + 0.05 +
        # 0.054 + 1.55 s
        woke = 1_000_000_000 + 1_654_000_000
        a = probes.alibi(woke - 1_400_000_000, woke - 100_000_000)
        assert a["heartbeat_late_s"] == pytest.approx(1.5)
        assert probes.alibi(0, 900_000_000)["heartbeat_late_s"] == 0.0
        assert a["gc_collections"] == 0 and a["gc_pause_s"] == 0.0
        # what the kernel says, each only where readable: the rusage
        # counts always are
        assert a["major_faults"] >= 0 and a["involuntary_switches"] >= 0
        assert a["kernel_over_s"] >= 0
    finally:
        probes.stop()
    assert not probes._thread.is_alive()
    assert _host_probe_leftovers() == ([], [])


def test_gc_pauses_are_booked_off_the_collecting_thread():
    """The callback only stamps (it may run inside a registry's lock); the
    booking is the heartbeat's or the alibi's."""
    import gc

    from bigdl_tpu.obs.host import HostProbes

    m = Metrics()
    probes = HostProbes(m, sleep=lambda s: probes._stop.wait(10.0))
    probes.start()
    try:
        t0 = probes._clock()
        gc.collect()
        assert len(probes._gc_done) >= 1       # stamped, not yet booked
        a = probes.alibi(t0, probes._clock())
        assert a["gc_collections"] >= 1 and a["gc_pause_s"] > 0
        assert m.hists["host.gc_pause_s"].n >= 1
        assert m.counter('host.gc_collections{generation="2"}') >= 1
    finally:
        probes.stop()
    assert _host_probe_leftovers() == ([], [])


def test_a_jump_of_the_clock_is_one_stall_through_a_real_run(monkeypatch):
    """A real tiny Optimizer run whose clock jumps ahead by two seconds
    once (nothing sleeps): one train_stall event with every field, one
    train/stall span."""
    from bigdl_tpu import optim
    from bigdl_tpu.obs import trace

    real, jump = trace.now_ns, [0]
    monkeypatch.setattr(trace, "now_ns", lambda: real() + jump[0])
    one_bundle = optim.Optimizer._one_bundle

    def jumping(self, step_engine, state, mbs):
        if state["iteration"] == 25:
            jump[0] += 2_000_000_000
        return one_bundle(self, step_engine, state, mbs)

    monkeypatch.setattr(optim.Optimizer, "_one_bundle", jumping)
    spans = trace.Tracer()
    trace.collect_into(spans)
    try:
        opt = _train(monkeypatch, iterations=40)
    finally:
        trace.collect_into(None)
    # a loaded machine may add a stall of its own; the jump is the one of
    # two seconds
    (e,) = [e for e in _stall_events() if e["interval_s"] >= 2.0]
    assert set(e) >= {"iteration", "steps", "in_flight", "interval_s",
                      "waited_s", "baseline_s", "lost_s", "where", "alibi"}
    assert e["iteration"] == 25 and e["steps"] == 1 and e["in_flight"] == 1
    assert 2.0 <= e["interval_s"] < 2.5 and 0 < e["baseline_s"] < 0.25
    assert e["lost_s"] == pytest.approx(e["interval_s"] - e["baseline_s"],
                                        abs=0.3)
    assert e["where"] in ("device", "host")
    # the heartbeat reads the same clock: it woke two seconds late, which
    # on a chip says the interpreter or the process was held
    alibi = e["alibi"]
    assert alibi["heartbeat_late_s"] >= 1.9
    assert {"gc_pause_s", "gc_collections", "kernel_over_s",
            "major_faults", "involuntary_switches"} <= set(alibi)
    (s,) = [s for s in spans.spans() if s.name == "train/stall"
            and s.end_ns - s.start_ns >= 2_000_000_000]
    assert s.attrs["where"] == e["where"] and s.attrs["iteration"] == 25
    assert opt.metrics.counter("train.stalls") >= 1
    assert opt.metrics.counter("train.stall_s") >= 1.5
    # the first window's dispatch-mean proxy is no longer a sample: 39
    # intervals of 40 fetches, less those that held a compile
    assert _hist(opt, "train.step_time_s")[0] <= 39


@pytest.mark.parametrize("case", ["device_late", "device_ran_ahead"])
def test_stall_on_device_on_hand_made_intervals(case):
    host = [(0, 100, "train/sync"), (100, 104, "train/overhead"),
            (104, 110, "train/dispatch"), (110, 140, "train/sync")]
    if case == "device_late":
        # one op of 90 where a step takes 30
        ops = [(0, 10, "fusion.1"), (10, 100, "fusion.2"),
               (100, 130, "fusion.1")]
        v = obs_attr.stall_on_device(ops, host, 0, 100)
        assert v["busy"] == 100 and v["idle"] == 0
        assert v["longest_op"] == ("fusion.2", 90)
        assert v["longest_gap"][0] == 0
    else:
        # both steps done by 60; the chip waits for the host to wake
        ops = [(-5, 30, "fusion.1"), (30, 60, "fusion.1"),
               (108, 138, "fusion.1")]
        v = obs_attr.stall_on_device(ops, host, 0, 100)
        assert v["busy"] == 60 and v["idle"] == 40
        assert v["longest_op"] == ("fusion.1", 30)
        assert v["longest_gap"] == (40, "train/sync")


def test_profiler_summary_reads_a_stall_on_the_devices_clock(tmp_path):
    """A train/stall span over the recorded trace's first step: the
    summary's paragraph for it is on the device's clock."""
    import shutil

    from bigdl_tpu.utils.profiling import IterationProfiler, \
        format_idle_table

    recorded = os.path.join(REPO, "benchmark", "tests", "data",
                            "resnet50_steps.xplane.pb")
    if not os.path.isfile(recorded):
        pytest.skip("no recorded trace in this checkout")
    run_dir = tmp_path / "plugins" / "profile" / "2026_10_04"
    run_dir.mkdir(parents=True)
    shutil.copy(recorded, run_dir / "host.xplane.pb")
    prof = IterationProfiler(str(tmp_path))
    host = 5_000_000_000_000
    p0, p1 = 177_662_171, 352_499_444
    d0, d1 = host + p0 - 300_000, host + p1 - 300_000
    for name, a, b in (("train/dispatch", d0, d0 + 2_000_000),
                       ("train/sync", d0 + 2_000_000, d1 - 1_000_000),
                       ("train/dispatch", d1, d1 + 2_000_000)):
        prof._spans.add_span(name, a, b)
    prof._spans.add_span("train/stall", d0 + 2_000_000, d1 - 1_000_000,
                         where="host", iteration=7, lost_s=0.04)
    out = prof.summary()
    (st,) = out["stalls"]
    assert st["where"] == "host" and st["iteration"] == 7
    assert st["busy"] + st["idle"] == pytest.approx(st["length"])
    assert st["length"] == pytest.approx((p1 - p0 - 3_000_000) * 1e-9)
    # the step's 127.6 ms of ops, then the 47 ms the chip waited, in sync
    assert st["longest_gap"][1] == "train/sync"
    assert st["longest_gap"][0] == pytest.approx(0.046, abs=2e-3)
    assert 0 < st["longest_op"][1] < 0.02
    # the stall is not a driver phase of the idle table
    assert "train/stall" not in out["by_phase"]
    text = format_idle_table(out)
    assert "stall at iteration 7" in text and "under train/sync" in text
