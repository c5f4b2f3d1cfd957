"""The train driver's log point fetches one bundle late (ISSUE 31): after
dispatching bundle n it fetches the results of bundles <= n-1 and leaves n
in flight, so the host queues n+1 while the device still runs n.  A flush
(fetch everything) happens before a validation, checkpoint or histogram
trigger does its work and where the loop leaves.

Counts and values only: nothing here reads a clock.  ``Flushed`` is the
driver as it was before: every log point fetches everything.
"""

import numpy as np
import pytest

import jax

from bigdl_tpu import nn, optim
from bigdl_tpu.data import ArrayDataSet
from bigdl_tpu.resilience.detector import PoisonedStepError, StepWatchdog
from bigdl_tpu.runtime.engine import Engine

from test_step_bundle import _PoisonOnce, loss_curve, mlp, synthetic

GRID = [(1, 1), (1, 3), (4, 1), (4, 3)]
grid = pytest.mark.parametrize("spc,log_every", GRID)


class Flushed(optim.Optimizer):
    """Host and device take turns: the loop as the parent commit ran it."""

    def _log_progress(self, state, flush=False):
        super()._log_progress(state, flush=True)


class Spy(StepWatchdog):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.seen = []

    def observe_loss(self, step, loss):
        self.seen.append((step, loss))
        super().observe_loss(step, loss)


def build(tmp_path, tag, spc, log_every, end_when, cls=optim.Optimizer,
          dataset=None, ckpt=None, watchdog=None):
    Engine.reset()
    Engine.get().config.failure_retry_interval_s = 0.05
    x, y = synthetic()
    opt = cls(mlp(), dataset if dataset is not None else ArrayDataSet(x, y),
              nn.ClassNLLCriterion(), batch_size=32, seed=11)
    opt.steps_per_call = spc
    opt.log_every = log_every
    opt.set_optim_method(optim.SGD(learning_rate=0.1, momentum=0.9))
    opt.set_end_when(end_when)
    opt.set_train_summary(str(tmp_path / tag))
    if ckpt is not None:
        opt.set_checkpoint(str(tmp_path / f"{tag}-ck"), ckpt)
    opt.watchdog = watchdog
    return opt


# -- (a) what a log point fetches ---------

@grid
def test_log_point_leaves_the_newest_bundle_in_flight(
        tmp_path, monkeypatch, spc, log_every):
    handed = []
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get",
        lambda tree: (handed.extend(jax.tree_util.tree_leaves(tree)),
                      real_get(tree))[1])
    # (flush, the newest bundle's size, was it asked for, was anything
    # fetched, the in-flight gauge afterwards)
    log_points = []

    class Watched(optim.Optimizer):
        def _log_progress(self, state, flush=False):
            if not self._pending_losses:
                return super()._log_progress(state, flush)
            newest = self._pending_losses[-1]
            del handed[:]
            super()._log_progress(state, flush)
            mine = jax.tree_util.tree_leaves(
                (newest.losses, newest.gnorms, newest.counted))
            asked = any(a is b for a in mine for b in handed)
            log_points.append((flush, newest.steps, asked, bool(handed),
                               self.metrics.gauges["train.steps_in_flight"]))

    opt = build(tmp_path, "a", spc, log_every,
                optim.Trigger.max_iteration(14), cls=Watched)
    opt.optimize()
    lagged = [p for p in log_points if not p[0]]
    assert len(lagged) >= 14 // max(spc, log_every) - 1
    overlapped = 0
    for _, steps, asked, fetched, in_flight in lagged:
        assert not asked              # no array of the newest bundle
        assert in_flight == steps     # which is what stays in flight
        overlapped += fetched
    assert overlapped >= len(lagged) - 1   # all but a first, empty one
    assert opt.metrics.counter("train.fetch_overlapped") == overlapped
    # the flush where the loop leaves asks for the newest, and none is left
    flush = [p for p in log_points if p[0]]
    assert flush and flush[-1][2] and flush[-1][4] == 0
    assert opt._pending_losses == []
    # every fetch is one observation of the sync phase
    syncs = opt.metrics.snapshot()["hists"]["train.attr.sync_s"]["n"]
    assert syncs == sum(1 for p in log_points if p[3])


# -- (b) nothing lost, nothing reordered ---------

@grid
def test_every_loss_is_logged_once_in_order_and_bit_identical(
        tmp_path, spc, log_every):
    """30 steps over 3 epochs of 10, a checkpoint every 7 iterations (off
    every grid) and one at each epoch's end."""
    end = optim.Trigger.max_epoch(3)
    every = optim.Trigger.or_(optim.Trigger.several_iteration(7),
                              optim.Trigger.every_epoch())
    ref = build(tmp_path, "ref", spc, 1, end, cls=Flushed, ckpt=every,
                watchdog=Spy())
    ref.optimize()
    want = loss_curve(ref)
    assert [s for s, _ in want] == list(range(1, 31))

    opt = build(tmp_path, "lag", spc, log_every, end, ckpt=every,
                watchdog=Spy())
    opt.optimize()
    assert loss_curve(opt) == want
    # the watchdog: each step once, in order, under its own number, and
    # the value the curve has for it
    assert opt.watchdog.seen == [(s - 1, v) for s, v in want]
    assert opt.watchdog.seen == ref.watchdog.seen
    assert opt.final_state["iteration"] == 30
    assert opt.final_state["loss"] == want[-1][1]
    n = opt.metrics.snapshot()["hists"]["train.grad_norm"]["n"]
    assert n == 30


def test_log_line_names_the_fetched_steps_iteration(tmp_path):
    from test_resilience import _LogCapture

    opt = build(tmp_path, "line", 1, 1, optim.Trigger.max_iteration(5))
    with _LogCapture("bigdl_tpu.optim") as logged:
        opt.optimize()
    want = dict(loss_curve(opt))
    lines = [r.getMessage() for r in logged.records
             if "Iteration" in r.getMessage()]
    assert len(lines) == 5
    for it, line in enumerate(lines, start=1):
        assert f"Iteration {it}: loss {want[it]:.4f}" in line


# -- (c) a trigger that reads the loss ---------

@grid
def test_min_loss_stops_where_a_flushing_run_stops(tmp_path, spc, log_every):
    probe = build(tmp_path, "probe", 1, 1, optim.Trigger.max_iteration(24),
                  cls=Flushed)
    probe.optimize()
    curve = [v for _, v in loss_curve(probe)]
    # between two losses of the run, far from both: float32 against
    # float64 cannot decide the comparison
    lo = min(curve[:16])
    below = max(v for v in curve[:16] if v > lo)
    v = (lo + below) / 2
    end = lambda: optim.Trigger.or_(optim.Trigger.min_loss(v),
                                    optim.Trigger.max_iteration(24))
    ref = build(tmp_path, "ref", spc, log_every, end(), cls=Flushed)
    ref.optimize()
    opt = build(tmp_path, "lag", spc, log_every, end())
    opt.optimize()
    stop = opt.final_state["iteration"]
    assert stop == ref.final_state["iteration"] < 24
    got = loss_curve(opt)
    assert got == loss_curve(ref) and len(got) == stop
    # the state ends with the LAST step's loss, the one the trigger read
    assert opt.final_state["loss"] == got[-1][1] < v
    assert isinstance(opt.final_state["loss"], float)


def test_state_loss_is_the_newest_steps_at_every_end_when_call(tmp_path):
    read = []

    def watch(state):
        if state["iteration"] > len(read):
            read.append(float(state["loss"]))
        return state["iteration"] >= 9

    opt = build(tmp_path, "newest", 1, 1, optim.Trigger(watch, "nine"))
    opt.optimize()
    assert read == [v for _, v in loss_curve(opt)]


# -- (d) a NaN, a hang ---------

def _dispatched_at_raise(tmp_path, tag, spc, log_every, cls):
    count = [0]

    class Counting(cls):
        def _one_bundle(self, step_engine, state, mbs):
            super()._one_bundle(step_engine, state, mbs)
            count[0] = state["iteration"]

    _PoisonOnce.fired = False
    x, y = synthetic()
    opt = build(tmp_path, tag, spc, log_every,
                optim.Trigger.max_iteration(20), cls=Counting,
                dataset=_PoisonOnce(x, y), watchdog=Spy(nan_patience=1))
    with pytest.raises(PoisonedStepError):
        opt.optimize()   # no checkpoint to resume from: it escapes
    assert _PoisonOnce.fired
    return count[0], opt


@grid
def test_nan_is_seen_at_most_one_bundle_later(tmp_path, spc, log_every):
    today, _ = _dispatched_at_raise(tmp_path, "t", spc, log_every, Flushed)
    now, opt = _dispatched_at_raise(tmp_path, "n", spc, log_every,
                                    optim.Optimizer)
    assert today <= now <= today + spc
    # the poisoned step (index 5) was the last the watchdog was shown
    assert [s for s, _ in opt.watchdog.seen] == list(range(6))
    assert not np.isfinite(opt.watchdog.seen[-1][1])


@grid
def test_retry_drops_what_was_pending(tmp_path, spc, log_every):
    """NaN at step index 5, checkpoints every 4: the run rewinds to
    iteration 4, and the bundle that was in flight behind the poisoned one
    is never shown to the watchdog or the curve from before the rewind."""
    _PoisonOnce.fired = False
    x, y = synthetic()
    opt = build(tmp_path, "retry", spc, log_every,
                optim.Trigger.max_iteration(12), dataset=_PoisonOnce(x, y),
                ckpt=optim.Trigger.several_iteration(4),
                watchdog=Spy(nan_patience=1))
    opt.optimize()
    assert opt.metrics.counter("recoveries_total") == 1
    assert opt.final_state["iteration"] == 12
    steps = [s for s, _ in opt.watchdog.seen]
    assert steps == list(range(6)) + list(range(4, 12))
    ref = build(tmp_path, "retry-ref", 1, 1,
                optim.Trigger.max_iteration(12), cls=Flushed)
    ref.optimize()
    want = dict(loss_curve(ref))
    assert [v for _, v in opt.watchdog.seen[6:]] == \
        [want[s] for s in range(5, 13)]


def test_watchdog_times_the_step_in_flight():
    now = [0.0]
    dog = StepWatchdog(step_timeout_s=10.0, clock=lambda: now[0])
    dog.step_started(0)
    now[0] = 1.0
    dog.step_started(1)          # queued behind step 0
    now[0] = 3.0
    dog.observe_loss(0, 0.5)     # step 0 done: step 1 has the device now
    assert not dog.hung()
    now[0] = 12.9
    assert not dog.hung()        # 9.9 s on the device
    now[0] = 13.1
    assert dog.hung() and dog.check()
    dog.observe_loss(1, 0.4)     # nothing in flight any more
    now[0] = 100.0
    assert not dog.hung()


# -- (e) counters in donated state ---------

@pytest.mark.parametrize("spc,log_every", [(1, 1), (4, 3)])
def test_state_counters_survive_donation(spc, log_every):
    from benchmark import harness
    from bigdl_tpu.data.dataset import DataSet
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.obs.state_metrics import subtrees
    from bigdl_tpu.optim import optim_method

    from test_mla_moe import CFG_FILE, T, ids_batch

    fam = harness.load_module("families", "mla_moe_lm")
    model = fam.build_model(dict(CFG_FILE, num_hidden_layers=2))
    ids = ids_batch(7, 48)
    x, y = ids[:, :-1], ids[:, 1:]
    donated = []

    def run(cls):
        class Checked(cls):
            def _one_bundle(self, step_engine, state, mbs):
                old = jax.tree_util.tree_leaves(
                    subtrees(step_engine.model_state))
                super()._one_bundle(step_engine, state, mbs)
                donated.append(all(a.is_deleted() for a in old))

        Engine.reset()
        opt = Checked(model, DataSet.array(x, y), CrossEntropyCriterion(),
                      batch_size=8, seed=5)
        opt.steps_per_call = spc
        opt.log_every = log_every
        opt.set_optim_method(optim_method.Adam(learning_rate=1e-3))
        opt.set_initial_variables(
            model.init(jax.random.PRNGKey(13), x[:1]))
        opt.set_end_when(optim.Trigger.max_iteration(11))
        opt.optimize()
        counters = opt.metrics.snapshot()["counters"]
        hist = opt.metrics.snapshot()["hists"]["moe.load_imbalance"]
        return ({k: v for k, v in counters.items() if k.startswith("moe.")},
                hist["n"], hist["sum"])

    want, _, _ = run(Flushed)
    got, n, total = run(optim.Optimizer)
    # the state really is consumed by the next dispatch (but each run's
    # first: on the CPU the booker's own first fetch still refers to it)
    assert len(donated) > 4 and sum(donated) == len(donated) - 2
    assert want["moe.routed_pairs"] == 11 * 8 * T * 2   # one expert layer
    assert got == want
    assert want["moe.dropped_pairs"] == 0 < want["moe.local_pairs"]
    assert n > 0 and total / n >= 1.0
