"""Pod-scale coordinated fault tolerance (``resilience.membership`` /
``resilience.cluster``): membership views, leader failover, partition
heal, gang recovery, peer-shard restore, preemption propagation, and the
elastic re-sharded mid-epoch resume.

The load-bearing specs are the chaos acceptance tests: under injected
``cluster_host_loss`` mid-run, training completes with weights
bit-identical to the fault-free run (the restored trajectory is the
fault-free trajectory), peer-shard restore is verified bit-identical to a
checkpoint restore of the same step, and MTTR + ``cluster.*`` metrics
appear in /metrics and the flight recorder.  Everything runs
single-process under tier-1 (injected clocks, ``memory://``-style shared
dirs); the true multi-process kill/rejoin drill is a ``slow`` mark.
"""

import json
import os
import signal

import numpy as np
import pytest

from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.cluster import (ClusterConfig, ClusterCoordinator,
                                          GangAbortedError, PeerShardStore)
from bigdl_tpu.resilience.detector import Heartbeat
from bigdl_tpu.resilience.faults import FaultSpec, HostLostError
from bigdl_tpu.resilience.membership import MembershipBoard, MembershipView
from bigdl_tpu.resilience.retry import (FailureCause, FailurePolicy,
                                        RetryPolicy, classify)


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    faults.clear()


def _fast_engine(retry_times=3):
    from bigdl_tpu.runtime.engine import EngineConfig, init_engine

    init_engine(EngineConfig(failure_retry_times=retry_times,
                             failure_retry_interval_s=0.01,
                             failure_policy=FailurePolicy(
                                 max_restarts=max(retry_times, 2),
                                 by_cause={c: RetryPolicy(
                                     max_retries=max(retry_times, 2),
                                     base_s=0.0, jitter=0.0)
                                     for c in FailureCause})))


def _coord(directory, rank=0, clock=None, metrics=None, **kw):
    cfg = ClusterConfig(directory=str(directory), process_index=rank,
                        rendezvous_timeout_s=kw.pop("timeout", 10.0),
                        rendezvous_poll_s=0.01, **kw)
    if clock is not None:
        cfg.clock = clock
    return ClusterCoordinator(cfg, metrics=metrics)


def _linreg_optimizer(ckpt_dir, n_iters, cluster_dir=None, seed=3,
                      steps_per_call=None, ckpt_every=2):
    from bigdl_tpu import nn, optim
    from bigdl_tpu.data.dataset import ArrayDataSet

    rs = np.random.RandomState(0)
    x = rs.rand(64, 4).astype(np.float32)
    y = x @ np.asarray([[1.0], [2.0], [-1.0], [0.5]], np.float32)
    opt = (optim.Optimizer(nn.Linear(4, 1), ArrayDataSet(x, y),
                           nn.MSECriterion(), batch_size=16, seed=seed)
           .set_optim_method(optim.SGD(learning_rate=0.2))
           .set_end_when(optim.Trigger.max_iteration(n_iters)))
    opt.set_checkpoint(str(ckpt_dir), optim.Trigger.several_iteration(
        ckpt_every))
    if steps_per_call:
        opt.steps_per_call = steps_per_call
    opt.log_every = 100
    if cluster_dir is not None:
        coord = _coord(cluster_dir, metrics=opt.metrics)
        coord.start()
        opt.set_cluster(coord)
    return opt


# ---------------------------------------------------------------------------
# membership board + views


def test_view_board_highest_epoch_wins(tmp_path):
    board = MembershipBoard(str(tmp_path))
    assert board.current() is None
    board.publish(MembershipView(epoch=1, members=(0, 1), leader=0))
    board.publish(MembershipView(epoch=3, members=(0,), leader=0,
                                 reason="host_loss"))
    board.publish(MembershipView(epoch=2, members=(0, 1), leader=0))
    v = board.current()
    assert v.epoch == 3 and v.members == (0,) and v.reason == "host_loss"


def test_abort_and_preempt_flags_are_epoch_scoped(tmp_path):
    board = MembershipBoard(str(tmp_path))
    board.post_abort(4, rank=1, reason="collective timeout", step=17)
    assert board.abort_posted(4)["rank"] == 1
    assert board.abort_posted(5) is None  # the next epoch is clean
    # first abort wins: a second poster must not overwrite the cause
    board.post_abort(4, rank=0, reason="me too")
    assert board.abort_posted(4)["reason"] == "collective timeout"
    board.post_preempt(4, rank=2)
    assert board.preempt_posted(4) == [2]
    assert board.preempt_posted(5) == []
    board.ack(6, 0)
    board.ack(6, 1)
    assert board.acks(6) == [0, 1]


def test_leader_failover_and_rejoin(tmp_path):
    """The lowest LIVE rank leads: when rank 0 stops beating, rank 1's
    sweep suspects it and publishes the shrink view with itself as
    leader; when rank 0 beats again the view heals with leader 0."""
    now = [100.0]
    clock = lambda: now[0]  # noqa: E731
    c0 = _coord(tmp_path, rank=0, clock=clock)
    c1 = _coord(tmp_path, rank=1, clock=clock)
    c0.start()
    c1.start()
    for _ in range(5):  # build beat history at 1s cadence
        now[0] += 1.0
        c0.sweep()
        c1.sweep()
    v = c1.view
    assert v.members == (0, 1) and v.leader == 0
    epoch0 = v.epoch

    now[0] += 300.0      # rank 0 goes silent
    v = c1.sweep()
    assert v.members == (1,) and v.leader == 1
    assert v.epoch > epoch0 and v.reason == "host_loss"
    assert c1.metrics.counter("cluster.peers_suspected_total") >= 1

    v2 = c0.sweep()      # rank 0 comes back: beats, reclaims leadership
    assert v2.members == (0, 1) and v2.leader == 0
    assert v2.epoch > v.epoch and v2.reason == "rejoin"


def test_partition_blinds_sweep_then_heals(tmp_path):
    """``cluster_partition``: while the spec fires, a sweep sees no peer
    heartbeats (live = self); when max_fires is exhausted the partition
    heals and the full membership is republished."""
    now = [100.0]
    clock = lambda: now[0]  # noqa: E731
    c0 = _coord(tmp_path, rank=0, clock=clock)
    hb1 = Heartbeat(str(tmp_path), process_index=1, clock=clock)
    hb1.beat()
    c0.start()
    v = c0.sweep()
    assert v.members == (0, 1)
    full_epoch = v.epoch

    faults.install([FaultSpec("cluster_partition", every=1, max_fires=2)])
    v = c0.sweep()
    assert v.members == (0,) and v.epoch > full_epoch
    assert v.reason == "host_loss"
    v = c0.sweep()  # still partitioned: view unchanged, no thrash
    assert v.members == (0,)
    hb1.beat()
    healed = c0.sweep()  # fault exhausted: the peer is visible again
    assert healed.members == (0, 1) and healed.reason == "rejoin"
    assert c0.metrics.counter("cluster.peers_suspected_total") >= 1


def test_suspicion_posts_gang_abort_and_unwinds_poster(tmp_path):
    """Heartbeat-detected peer death posts the gang abort (survivors
    wedged in a collective have no local exception to unwind them), and
    the POSTING process's own next bundle edge raises too — then
    recovers onto the shrink view."""
    now = [100.0]
    clock = lambda: now[0]  # noqa: E731
    c0 = _coord(tmp_path, rank=0, clock=clock)
    c1 = _coord(tmp_path, rank=1, clock=clock)
    c0.start()
    c1.start()
    for _ in range(5):
        now[0] += 1.0
        c0.sweep()
        c1.sweep()
    assert c1.view.members == (0, 1)
    epoch0 = c1.view.epoch

    now[0] += 300.0              # rank 0 dies mid-collective
    c1.sweep()
    assert c1.board.abort_posted(epoch0) is not None  # the wedge breaker
    with pytest.raises(GangAbortedError):
        c1.on_step(9)            # the poster's own edge unwinds as well
    view = c1.gang_recover("host loss")
    assert view.members == (1,) and view.epoch > epoch0
    c1.on_step(10)               # the recovered epoch is clean


def test_suspicion_abort_lands_under_freshest_view_epoch(tmp_path):
    """The suspicion abort is posted at the epoch of the view the sweep
    just READ from the board — which may be newer than the
    coordinator's own — so the guard, the flag, and the poster's
    self-unwind marker all agree on one epoch."""
    now = [100.0]
    clock = lambda: now[0]  # noqa: E731
    c0 = _coord(tmp_path, rank=0, clock=clock)
    c1 = _coord(tmp_path, rank=1, clock=clock)
    c0.start()
    c1.start()
    for _ in range(5):
        now[0] += 1.0
        c0.sweep()
        c1.sweep()
    assert c0.view.members == (0, 1)
    # a fresh epoch lands on the board that c1 has NOT adopted yet
    v = c0.sweep(force_publish=True)
    assert v.epoch > c1.view.epoch
    now[0] += 300.0              # rank 0 dies before c1 sweeps again
    c1.sweep()
    assert c1.board.abort_posted(v.epoch) is not None
    with pytest.raises(GangAbortedError):
        c1.on_step(5)


def test_restart_never_reaborts_on_stale_flag(tmp_path):
    """A restarted gang must not re-abort on the previous incarnation's
    abort flag: the leader's start bump retires the old epoch, and the
    restarted members' edge probes scan only from their JOINED epoch."""
    now = [100.0]
    clock = lambda: now[0]  # noqa: E731
    c0 = _coord(tmp_path, rank=0, clock=clock)
    c1 = _coord(tmp_path, rank=1, clock=clock)
    c0.start()
    c1.start()
    for _ in range(5):
        now[0] += 1.0
        c0.sweep()
        c1.sweep()
    epoch0 = c0.view.epoch
    c0.abort("collective timeout", step=3)

    # the whole gang restarts (fresh coordinators over the same board)
    c0b = _coord(tmp_path, rank=0, clock=clock)
    c0b.start()                  # leader start: epoch bump retires flags
    c1b = _coord(tmp_path, rank=1, clock=clock)
    c1b.start()
    assert c0b.view.epoch > epoch0
    c0b.on_step(4)
    c1b.on_step(4)               # stale abort-<epoch0> must not re-fire


def test_abort_probe_covers_epochs_back_to_joined(tmp_path):
    """A view published between two bundle edges must not hide the
    abort: the flag lands under the epoch the member was TRAINING in,
    and its edge probe walks [joined, current] even after a sweep
    adopted a newer view."""
    c0 = _coord(tmp_path, rank=0)
    c1 = _coord(tmp_path, rank=1)
    c0.start()
    c1.start()
    c0.sweep()
    c1.sweep()
    joined = c1.view.epoch
    c0.abort("collective timeout", step=3)   # posted under `joined`
    # the leader's recovery view lands BEFORE c1's next edge, and c1's
    # background sweep adopts it
    v = c0.sweep()
    assert v.epoch > joined
    c1.sweep()
    assert c1.view.epoch == v.epoch
    with pytest.raises(GangAbortedError) as ei:
        c1.on_step(4)
    assert ei.value.epoch == joined
    # recovery rendezvouses on the ALREADY-published post-abort view
    # instead of waiting for yet another epoch
    import threading

    got = {}
    t = threading.Thread(
        target=lambda: got.setdefault("v", c1.gang_recover("late")))
    t.start()
    c0.rendezvous(v)
    t.join(timeout=10)
    assert not t.is_alive()
    assert got["v"].epoch == v.epoch
    c1.on_step(5)                # joined the new epoch: flag retired


def test_edge_probe_is_rate_limited(tmp_path):
    """K=1 training must not pay a board read per step: between probe
    windows on_step serves from the sweep-refreshed cache."""
    now = [100.0]
    clock = lambda: now[0]  # noqa: E731
    c0 = _coord(tmp_path, rank=0, clock=clock)
    c0.start()
    calls = {"n": 0}
    real = c0.board.abort_posted

    def counted(epoch):
        calls["n"] += 1
        return real(epoch)

    c0.board.abort_posted = counted
    c0.on_step(1)
    first = calls["n"]
    assert first > 0
    for s in range(2, 12):       # same second: all served from cache
        c0.on_step(s)
    assert calls["n"] == first
    now[0] += 2.0                # window elapsed: exactly one more probe
    c0.on_step(12)
    assert calls["n"] > first


def test_gang_abort_raises_at_peer_step_edge_and_recovers(tmp_path):
    """A survivor posting the abort flag makes every OTHER member's next
    bundle edge raise GangAbortedError (classified host_lost); both then
    rendezvous on the post-abort view together."""
    import threading

    c0 = _coord(tmp_path, rank=0)
    c1 = _coord(tmp_path, rank=1)
    c0.start()
    c1.start()
    c0.sweep()
    c1.sweep()
    v = c0.sweep()
    assert v.members == (0, 1)

    c1.abort("peer collective timeout", step=7)
    with pytest.raises(GangAbortedError) as ei:
        c0.on_step(8)
    assert classify(ei.value) is FailureCause.HOST_LOST
    assert ei.value.source_rank == 1
    c1.on_step(8)  # the poster's own flag never re-raises on itself
    t = threading.Thread(target=c1.gang_recover, args=("test",))
    t.start()
    view = c0.gang_recover("test")
    t.join(timeout=10)
    assert not t.is_alive()
    assert view.epoch > v.epoch
    assert set(view.members) == {0, 1}
    # the new epoch carries no stale abort: steps run again
    c0.on_step(9)
    c1.on_step(9)


def test_preemption_notice_propagates_to_peers(tmp_path):
    c0 = _coord(tmp_path, rank=0)
    c1 = _coord(tmp_path, rank=1)
    c0.start()
    c1.start()
    c0.sweep()
    c1.sweep()
    c0.sweep()
    c1.notify_preemption(source="signal")
    assert c1.preempt_pending
    c0.sweep()
    assert c0.preempt_pending  # the un-signalled host checkpoints too
    assert c1.metrics.counter("cluster.preempt_notices_total") >= 1


# ---------------------------------------------------------------------------
# peer-shard store


def test_peer_store_completeness_and_gc(tmp_path):
    store = PeerShardStore(str(tmp_path), keep=2)
    sh = {"m@offset": np.asarray(0, np.int64),
          "m": np.arange(4, dtype=np.float32)}
    # step 2: only rank 0 of 2 published — NOT complete (rank 1 died)
    store.publish(0, 2, sh, ranks=2, params=np.ones(3, np.float32))
    assert store.latest_complete_step() is None
    # step 4: both ranks published, params present — complete
    for r in range(2):
        store.publish(r, 4, {"m@offset": np.asarray(4 * r, np.int64),
                             "m": np.full(4, float(r), np.float32)},
                      ranks=2,
                      params=np.ones(3, np.float32) if r == 0 else None,
                      driver_state={"iteration": 4} if r == 0 else None)
    assert store.latest_complete_step() == 4
    got = store.fetch(4)
    assert len(got["payloads"]) == 2
    assert got["driver_state"]["iteration"] == 4
    np.testing.assert_array_equal(got["params"], np.ones(3, np.float32))
    # merge: each rank's slice lands at its offset
    from bigdl_tpu.optim.checkpoint import merge_flat_shards

    merged = merge_flat_shards(got["payloads"],
                               {"m": np.zeros(8, np.float32)})
    np.testing.assert_array_equal(merged["m"],
                                  np.r_[np.zeros(4), np.ones(4)])
    # gc: publishing more complete steps evicts the oldest
    for step in (6, 8):
        for r in range(2):
            store.publish(r, step, sh, ranks=2,
                          params=np.ones(3, np.float32) if r == 0 else None)
    assert store.complete_steps() == [6, 8]
    with pytest.raises(ValueError):
        store.fetch(4)


def test_peer_restore_bit_identical_to_checkpoint_restore(tmp_path):
    """The acceptance parity spec: restoring step N from the peer store
    yields byte-for-byte the state a checkpoint restore of step N yields
    — params, optimizer state, model state, and driver step."""
    from bigdl_tpu.optim import checkpoint as ckpt

    _fast_engine()
    faults.clear()
    opt = _linreg_optimizer(tmp_path / "ck", 4,
                            cluster_dir=tmp_path / "cl")
    trained = opt.optimize()
    eng = trained._engine

    latest = ckpt.latest_checkpoint(str(tmp_path / "ck"))
    assert latest is not None and latest.endswith("ckpt-4")
    c_flat, c_opt, c_ms, c_driver, c_ema = ckpt.load_checkpoint(
        latest, opt_state_template=eng.opt_template,
        model_state_template=eng.model_state_template)

    assert opt.cluster.store.latest_complete_step() == 4
    p_flat, p_opt, p_ms, p_driver, p_ema = opt.cluster.load_peer_state(
        4, eng.opt_template, eng.model_state_template)

    np.testing.assert_array_equal(np.asarray(c_flat), np.asarray(p_flat))
    for a, b in zip(np.asarray(c_ema) if c_ema is not None else [],
                    np.asarray(p_ema) if p_ema is not None else []):
        np.testing.assert_array_equal(a, b)
    import jax

    for a, b in zip(jax.tree_util.tree_leaves(c_opt),
                    jax.tree_util.tree_leaves(p_opt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(c_ms),
                    jax.tree_util.tree_leaves(p_ms)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for key in ("iteration", "epoch", "epoch_batch"):
        assert c_driver[key] == p_driver[key]


# ---------------------------------------------------------------------------
# chaos acceptance: gang recovery end to end


def test_host_loss_recovers_to_fault_free_trajectory(tmp_path):
    """Injected ``cluster_host_loss`` mid-run: the gang aborts, bumps the
    membership epoch, restores from the PEER store, and finishes with
    weights bit-identical to the fault-free run; MTTR and ``cluster.*``
    counters land in /metrics and the flight recorder."""
    _fast_engine()
    faults.clear()
    opt_a = _linreg_optimizer(tmp_path / "ck_a", 8)
    trained_a = opt_a.optimize()

    inj = faults.install([FaultSpec("cluster_host_loss", at_step=5)])
    opt_b = _linreg_optimizer(tmp_path / "ck_b", 8,
                              cluster_dir=tmp_path / "cl_b")
    trained_b = opt_b.optimize()

    assert [p for p, _, _ in inj.events] == ["cluster_host_loss"]
    assert opt_b.final_state["iteration"] == 8
    wa = np.asarray(trained_a.variables["params"]["weight"])
    wb = np.asarray(trained_b.variables["params"]["weight"])
    np.testing.assert_array_equal(wa, wb)

    m = opt_b.metrics
    assert m.counter("cluster.recoveries_total") == 1
    assert m.counter("cluster.recovery_by_path.peer_shard") == 1
    assert m.counter("cluster.recovery_bytes_total") > 0
    assert m.counter("cluster.aborts_total") == 1
    assert m.summary()["cluster.mttr_s.count"] == 1
    assert m.counter("recoveries_total") == 1  # the classic counter too
    assert m.counter("retries_by_cause.host_lost") == 1
    # membership: the recovery bumped the view epoch past the start view
    assert opt_b.cluster.view.epoch >= 2

    from bigdl_tpu.obs.export import render_prometheus

    text = render_prometheus(m)
    assert "cluster_recoveries_total 1.0" in text
    assert "cluster_mttr_s_count 1" in text
    assert any(line.startswith("cluster_recovery_bytes_total")
               for line in text.splitlines())

    from bigdl_tpu.obs import flight

    kinds = [e["kind"] for e in flight.global_recorder().snapshot()]
    for expected in ("cluster_abort", "cluster_view", "cluster_rendezvous",
                     "cluster_restore", "cluster_recover",
                     "cluster_publish"):
        assert expected in kinds, expected


def test_host_loss_falls_back_to_checkpoint_when_no_peer_state(tmp_path):
    """Recovery ladder rung 2: with the peer store emptied (no buddy
    holds the shard), restore comes from the newest shard-complete
    checkpoint and is still exact."""
    _fast_engine()
    faults.clear()
    opt_a = _linreg_optimizer(tmp_path / "ck_a", 8)
    trained_a = opt_a.optimize()

    faults.install([FaultSpec("cluster_host_loss", at_step=5)])
    opt_b = _linreg_optimizer(tmp_path / "ck_b", 8,
                              cluster_dir=tmp_path / "cl_b")
    # sabotage the peer store mid-run: drop every publish before the fault
    real_publish = opt_b.cluster.publish_state
    opt_b.cluster.publish_state = lambda *a, **k: 0
    trained_b = opt_b.optimize()
    opt_b.cluster.publish_state = real_publish

    np.testing.assert_array_equal(
        np.asarray(trained_a.variables["params"]["weight"]),
        np.asarray(trained_b.variables["params"]["weight"]))
    m = opt_b.metrics
    assert m.counter("cluster.recovery_by_path.checkpoint") == 1
    assert m.counter("cluster.recovery_by_path.peer_shard") == 0


def test_supervisor_gang_recovers_with_cluster_dir(tmp_path):
    """FailurePolicy.cluster_dir: the Supervisor builds the coordinator,
    and a failure that escapes optimize() goes through gang recovery
    (abort → new view → rendezvous) before re-entering."""
    from bigdl_tpu.resilience.supervisor import Supervisor

    _fast_engine(retry_times=0)
    faults.install([FaultSpec("step_fail", at_step=5)])
    opt = _linreg_optimizer(tmp_path / "ck", 8)
    policy = FailurePolicy(
        max_restarts=2, cluster_dir=str(tmp_path / "cl"),
        by_cause={FailureCause.STEP_FAILURE: RetryPolicy(
            max_retries=2, base_s=0.0, jitter=0.0)})
    sup = Supervisor(opt, policy=policy, sleep=lambda s: None)
    trained = sup.run()
    assert trained is not None
    assert opt.final_state["iteration"] == 8
    assert sup.restarts_total == 1
    assert opt.cluster is None  # supervisor-owned coordinator detached
    assert opt.metrics.counter("cluster.aborts_total") == 1
    assert opt.metrics.counter("cluster.recoveries_total") == 1
    board = MembershipBoard(str(tmp_path / "cl"))
    assert board.current().epoch >= 2  # start view + abort-recovery view


def test_cluster_preempt_notice_stops_with_checkpoint_and_resumes_exact(
        tmp_path):
    """``cluster_preempt_notice`` at a bundle edge acts as a received
    cluster-wide preemption: the run checkpoints just-in-time and stops;
    a restart resumes step-exact to the uninterrupted trajectory."""
    _fast_engine()
    faults.clear()
    ref = _linreg_optimizer(tmp_path / "ck_ref", 8)
    trained_ref = ref.optimize()

    faults.install([FaultSpec("cluster_preempt_notice", at_step=3)])
    opt1 = _linreg_optimizer(tmp_path / "ck", 8,
                             cluster_dir=tmp_path / "cl")
    opt1.optimize()
    stopped_at = opt1.final_state["iteration"]
    assert stopped_at < 8  # preempted mid-run...
    assert opt1.metrics.counter("cluster.preempt_notices_total") >= 1
    from bigdl_tpu.optim import checkpoint as ckpt

    latest = ckpt.latest_checkpoint(str(tmp_path / "ck"))
    assert latest is not None
    assert latest.endswith(f"ckpt-{stopped_at}")  # just-in-time landed

    faults.clear()
    opt2 = _linreg_optimizer(tmp_path / "ck", 8,
                             cluster_dir=tmp_path / "cl")
    trained2 = opt2.optimize()
    assert opt2.final_state["iteration"] == 8
    np.testing.assert_array_equal(
        np.asarray(trained_ref.variables["params"]["weight"]),
        np.asarray(trained2.variables["params"]["weight"]))


# ---------------------------------------------------------------------------
# elastic re-sharded mid-epoch resume (plan level)


@pytest.mark.parametrize("old_pc,new_pc,trained", [
    (2, 1, 1), (1, 4, 2), (4, 2, 1), (2, 4, 2)])
def test_resharded_plan_covers_each_remaining_example_once(
        old_pc, new_pc, trained):
    from bigdl_tpu.data.dataset import (batch_index_plan,
                                        resharded_batch_index_plan)

    n, bs = 48, 16
    done = set()
    for p in range(old_pc):
        for b, (sel, n_real) in enumerate(batch_index_plan(
                n, bs, seed=3, epoch=1, process_id=p,
                process_count=old_pc)):
            if b >= trained:
                break
            done.update(sel[:n_real].tolist())
    assert len(done) == trained * bs
    rem = []
    for p in range(new_pc):
        for sel, n_real in resharded_batch_index_plan(
                n, bs, trained_batches=trained, old_process_count=old_pc,
                seed=3, epoch=1, process_id=p, process_count=new_pc):
            rem.extend(sel[:n_real].tolist())
    assert len(rem) == len(set(rem))        # nothing trained twice
    assert not (done & set(rem))            # nothing replayed
    assert done | set(rem) == set(range(n))  # nothing lost


# ---------------------------------------------------------------------------
# storage mirror (satellite): bounded retry, accounted


def test_mirror_tree_retries_upload_and_accounts(tmp_path):
    from bigdl_tpu.optim.metrics import Metrics
    from bigdl_tpu.utils import storage

    src = tmp_path / "src"
    src.mkdir()
    (src / "a.bin").write_bytes(b"payload")
    (src / "manifest.json").write_text("{}")
    faults.install([FaultSpec("storage_io_fail", every=1, max_fires=1)])
    m = Metrics()
    n = storage.mirror_tree(str(src), str(tmp_path / "dst"), metrics=m,
                            sleep=lambda s: None)
    assert n == len(b"payload") + 2
    assert (tmp_path / "dst" / "a.bin").read_bytes() == b"payload"
    assert m.counter("retries_by_cause.transient_storage") == 1

    # retries exhausted -> raises (the caller decides severity)
    faults.install([FaultSpec("storage_io_fail", every=1, max_fires=50)])
    with pytest.raises(Exception):
        storage.mirror_tree(str(src), str(tmp_path / "dst2"), metrics=m,
                            sleep=lambda s: None)


def test_checkpoint_mirror_produces_restorable_copy(tmp_path):
    from bigdl_tpu import nn, optim
    from bigdl_tpu.data.dataset import ArrayDataSet
    from bigdl_tpu.optim import checkpoint as ckpt

    _fast_engine()
    faults.clear()
    rs = np.random.RandomState(0)
    x = rs.rand(32, 4).astype(np.float32)
    y = x.sum(1, keepdims=True).astype(np.float32)
    opt = (optim.Optimizer(nn.Linear(4, 1), ArrayDataSet(x, y),
                           nn.MSECriterion(), batch_size=16, seed=1)
           .set_optim_method(optim.SGD(learning_rate=0.1))
           .set_end_when(optim.Trigger.max_iteration(4)))
    opt.set_checkpoint(str(tmp_path / "primary"),
                       optim.Trigger.several_iteration(2),
                       mirror=str(tmp_path / "mirror"))
    opt.log_every = 100
    opt.optimize()

    primary = ckpt.latest_checkpoint(str(tmp_path / "primary"))
    mirrored = ckpt.latest_checkpoint(str(tmp_path / "mirror"))
    assert primary is not None and mirrored is not None
    assert os.path.basename(primary) == os.path.basename(mirrored)
    a = json.load(open(os.path.join(primary, "manifest.json")))
    b = json.load(open(os.path.join(mirrored, "manifest.json")))
    assert a == b


def test_checkpoint_mirror_is_garbage_collected(tmp_path):
    """The mirror root is bounded like the primary: a long
    frequent-checkpoint run must not accumulate every checkpoint ever
    taken in the remote bucket."""
    from bigdl_tpu import nn, optim
    from bigdl_tpu.data.dataset import ArrayDataSet

    _fast_engine()
    faults.clear()
    rs = np.random.RandomState(0)
    x = rs.rand(32, 4).astype(np.float32)
    y = x.sum(1, keepdims=True).astype(np.float32)
    opt = (optim.Optimizer(nn.Linear(4, 1), ArrayDataSet(x, y),
                           nn.MSECriterion(), batch_size=16, seed=1)
           .set_optim_method(optim.SGD(learning_rate=0.1))
           .set_end_when(optim.Trigger.max_iteration(10)))
    opt.set_checkpoint(str(tmp_path / "primary"),
                       optim.Trigger.several_iteration(1),
                       mirror=str(tmp_path / "mirror"))
    opt.log_every = 100
    opt.optimize()

    def ckpts(d):
        return sorted(n for n in os.listdir(str(tmp_path / d))
                      if n.startswith("ckpt-"))

    assert len(ckpts("primary")) <= 3  # save_checkpoint keep_last default
    assert ckpts("mirror") == ckpts("primary")


# ---------------------------------------------------------------------------
# true multi-process membership drill (slow: real processes, real clocks)


@pytest.mark.slow
def test_two_process_kill_and_rejoin_membership(tmp_path):
    """A REAL second process beats into the control dir; kill -9 takes it
    out (the leader publishes the shrink view), a relaunch rejoins (the
    leader publishes the grow view).  No jax collectives involved — this
    drills exactly the membership/failover layer."""
    import subprocess
    import sys
    import time as _time

    beater = ("import sys, time\n"
              "from bigdl_tpu.resilience.detector import Heartbeat\n"
              "hb = Heartbeat(sys.argv[1], process_index=1, "
              "interval_s=0.05)\n"
              "hb.start()\n"
              "time.sleep(60)\n")

    def wait_for(pred, timeout=30.0):
        deadline = _time.time() + timeout
        while _time.time() < deadline:
            if pred():
                return True
            _time.sleep(0.05)
        return False

    c0 = _coord(tmp_path, rank=0, heartbeat_interval_s=0.05,
                phi_threshold=3.0)
    c0.start()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen([sys.executable, "-c", beater, str(tmp_path)],
                         env=env)
    try:
        assert wait_for(lambda: c0.sweep() is not None
                        and c0.view.members == (0, 1)), "peer never joined"
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=10)
        assert wait_for(lambda: c0.sweep() is not None
                        and c0.view.members == (0,)), \
            "dead peer never suspected"
        p = subprocess.Popen([sys.executable, "-c", beater, str(tmp_path)],
                             env=env)
        assert wait_for(lambda: c0.sweep() is not None
                        and c0.view.members == (0, 1)), \
            "restarted peer never rejoined"
    finally:
        p.kill()
        p.wait(timeout=10)
