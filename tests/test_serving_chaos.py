"""Serving chaos suite — the request lifecycle under injected failure.

Deterministic, CPU-only specs for docs/serving.md's lifecycle guarantees:
every ACCEPTED request gets a correct answer or an explicit error (shed /
expired / dropped), never a hang or a silent drop, under worker death,
slow batches, full queues, and shutdown.  Fault injection uses the
``bigdl_tpu.resilience.faults`` points ``serving_predict_fail`` /
``serving_worker_kill`` / ``serving_slow_batch``.

In-process specs run under tier-1; the multi-worker pool chaos tests are
``slow`` (subprocess spawns).
"""

import json
import os
import threading
import time
from urllib import request as urlreq
from urllib.error import HTTPError

import numpy as np
import pytest

from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.faults import FaultSpec
from bigdl_tpu.serving import (DeadlineExceededError, InferenceModel,
                               RequestDroppedError, ServiceUnavailableError,
                               ServingConfig, ServingServer)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _echo(x):
    return np.asarray(x) * 2.0


def _slow(delay):
    def predict(x):
        time.sleep(delay)
        return np.asarray(x) * 2.0
    return predict


# ---------------------------------------------------------------------------
# deadline expiry


def test_deadline_expires_in_queue_before_predict():
    """A slow model backs the queue up; requests whose deadline passes
    while queued are dropped BEFORE predict with an explicit error."""
    calls = []

    def counting_slow(x):
        calls.append(np.asarray(x).shape[0])
        time.sleep(0.2)
        return np.asarray(x)

    srv = ServingServer(InferenceModel(predict_fn=counting_slow),
                        ServingConfig(batch_size=1,
                                      batch_timeout_s=0.0)).start()
    try:
        r1 = srv.enqueue(np.ones((1, 2), np.float32))      # occupies engine
        for _ in range(1000):      # r1 must be IN predict before r2
            if calls:              # arrives, or r2 jumps it (deadline-
                break              # aware ordering) and gets answered
            time.sleep(0.002)
        assert calls, "r1 never reached predict"
        r2 = srv.enqueue(np.ones((1, 2), np.float32), deadline_s=0.05)
        with pytest.raises(DeadlineExceededError):
            srv.query(r2, timeout=10)
        srv.query(r1, timeout=10)                          # unaffected
        assert srv.stats["expired_requests"] == 1
        # the per-tenant SLO surface says WHOSE deadline expired
        from bigdl_tpu.optim.metrics import global_metrics
        assert global_metrics().counter("serving.tenant.default.expired") >= 1
        # the expired request never reached the chip
        assert sum(calls) == 1, calls
    finally:
        srv.stop()


def test_default_deadline_from_config():
    srv = ServingServer(InferenceModel(predict_fn=_slow(0.2)),
                        ServingConfig(batch_size=1, batch_timeout_s=0.0,
                                      default_deadline_s=0.05)).start()
    try:
        srv.enqueue(np.ones((1, 2), np.float32))
        rid = srv.enqueue(np.ones((1, 2), np.float32))     # inherits default
        with pytest.raises(DeadlineExceededError):
            srv.query(rid, timeout=10)
    finally:
        srv.stop()


def test_deadline_expiry_under_injected_slow_batch():
    """serving_slow_batch makes every batch a straggler; a short-deadline
    request behind an IN-FLIGHT straggler expires, a no-deadline request
    survives.  (The in-flight wait matters: a short-deadline request that
    is merely *queued* jumps the window under deadline-aware ordering and
    would be answered in time.)"""
    faults.install([FaultSpec("serving_slow_batch", every=1, delay_s=0.15,
                              max_fires=4)])
    srv = ServingServer(InferenceModel(predict_fn=_echo),
                        ServingConfig(batch_size=1,
                                      batch_timeout_s=0.0)).start()
    try:
        r1 = srv.enqueue(np.ones((1, 2), np.float32))
        time.sleep(0.05)   # r1's straggler batch is now in predict
        r2 = srv.enqueue(np.ones((1, 2), np.float32), deadline_s=0.05)
        r3 = srv.enqueue(np.ones((1, 2), np.float32))
        np.testing.assert_array_equal(srv.query(r1, timeout=10), 2.0)
        with pytest.raises(DeadlineExceededError):
            srv.query(r2, timeout=10)
        np.testing.assert_array_equal(srv.query(r3, timeout=10), 2.0)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# backpressure


def test_enqueue_never_blocks_on_full_queue():
    """The blocking-put bug: a full queue sheds (429 semantics) instead of
    blocking the client thread indefinitely."""
    srv = ServingServer(InferenceModel(predict_fn=_slow(0.3)),
                        ServingConfig(batch_size=1, batch_timeout_s=0.0,
                                      queue_capacity=2)).start()
    try:
        t0 = time.time()
        shed = 0
        for _ in range(10):
            try:
                srv.enqueue(np.ones((1, 2), np.float32))
            except ServiceUnavailableError as e:
                shed += 1
                assert e.retry_after > 0
        # ten admission attempts against a capacity-2 queue returned
        # quickly — nothing blocked for the engine's 0.3s/batch pace
        assert time.time() - t0 < 0.25
        assert shed >= 6
        assert srv.stats["shed_requests"] == shed
    finally:
        srv.stop()


def test_backpressure_http_429_with_retry_after():
    from bigdl_tpu.serving import HttpFrontend

    srv = ServingServer(InferenceModel(predict_fn=_slow(0.3)),
                        ServingConfig(batch_size=1, batch_timeout_s=0.0,
                                      queue_capacity=1,
                                      retry_after_s=2.5)).start()
    fe = HttpFrontend(srv).start()
    try:
        body = json.dumps({"instances": [[1.0, 2.0]]}).encode()
        saw_429 = None
        for _ in range(8):
            req = urlreq.Request(fe.url + "/predict", data=body,
                                 headers={"Content-Type": "application/json"})
            try:
                # short client timeout: we only care about admission
                urlreq.urlopen(req, timeout=0.05)
            except HTTPError as e:
                if e.code == 429:
                    saw_429 = e.headers.get("Retry-After")
                    break
            except Exception:  # noqa: BLE001 — client-side timeout
                pass
        assert saw_429 == "2.5"
    finally:
        fe.stop()
        srv.stop()


def test_oversized_body_rejected_413():
    from bigdl_tpu.serving import HttpFrontend

    srv = ServingServer(InferenceModel(predict_fn=_echo)).start()
    fe = HttpFrontend(srv, max_body_bytes=512).start()
    try:
        req = urlreq.Request(fe.url + "/predict", data=b"x" * 2048,
                             headers={"Content-Type": "application/json"})
        with pytest.raises(HTTPError) as ei:
            urlreq.urlopen(req, timeout=10)
        assert ei.value.code == 413
        # the engine never saw it
        assert srv.stats["requests"] == 0
    finally:
        fe.stop()
        srv.stop()


# ---------------------------------------------------------------------------
# drain vs drop


def test_drain_finishes_queued_requests():
    srv = ServingServer(InferenceModel(predict_fn=_slow(0.05)),
                        ServingConfig(batch_size=4,
                                      batch_timeout_s=0.0)).start()
    rids = [srv.enqueue(np.full((1, 2), i, np.float32)) for i in range(16)]
    report = srv.drain(timeout=30)
    # nothing dropped; whatever had not completed before drain() began
    # was finished inside the budget
    assert report["dropped"] == 0 and report["drained"] >= 1
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(srv.query(rid, timeout=1), 2.0 * i)
    assert srv.stats["requests"] == 16
    # no silent leftovers: queue empty, results consumed
    assert srv._in.empty() and not srv._results
    with pytest.raises(ServiceUnavailableError):
        srv.enqueue(np.ones((1, 2), np.float32))   # draining sheds admission
    assert srv.stats["drained_requests"] == report["drained"]


def test_stop_without_drain_fails_queued_explicitly():
    srv = ServingServer(InferenceModel(predict_fn=_slow(0.3)),
                        ServingConfig(batch_size=1,
                                      batch_timeout_s=0.0)).start()
    r_inflight = srv.enqueue(np.ones((1, 2), np.float32))
    queued = [srv.enqueue(np.ones((1, 2), np.float32)) for _ in range(5)]
    time.sleep(0.05)                      # let the engine pick up the first
    srv.stop()
    # the in-flight batch finished; the queued ones got explicit verdicts
    np.testing.assert_array_equal(srv.query(r_inflight, timeout=1), 2.0)
    for rid in queued:
        with pytest.raises(RequestDroppedError):
            srv.query(rid, timeout=1)
    assert srv.stats["dropped_requests"] == 5


def test_drain_budget_exhausted_drops_remainder_explicitly():
    srv = ServingServer(InferenceModel(predict_fn=_slow(0.2)),
                        ServingConfig(batch_size=1,
                                      batch_timeout_s=0.0)).start()
    rids = [srv.enqueue(np.ones((1, 2), np.float32)) for _ in range(8)]
    report = srv.drain(timeout=0.3)
    assert report["dropped"] >= 1 and report["drained"] >= 1
    verdicts = {"ok": 0, "dropped": 0}
    for rid in rids:
        try:
            srv.query(rid, timeout=1)
            verdicts["ok"] += 1
        except RequestDroppedError:
            verdicts["dropped"] += 1
    assert verdicts["ok"] + verdicts["dropped"] == 8   # nobody hangs
    assert verdicts["dropped"] == report["dropped"]


def test_engine_survives_poison_batch():
    """A batch that fails BEFORE predict (shape-mismatched co-batched
    requests break np.concatenate) must not kill the dispatcher thread:
    its requests get the error, later requests still answer."""
    srv = ServingServer(InferenceModel(predict_fn=_echo),
                        ServingConfig(batch_size=8, batch_timeout_s=0.05))
    # enqueue BEFORE start so both requests land in the same first batch
    r1 = srv.enqueue(np.ones((1, 3), np.float32))
    r2 = srv.enqueue(np.ones((1, 4), np.float32))
    srv.start()
    try:
        verdicts = 0
        for rid in (r1, r2):
            try:
                srv.query(rid, timeout=10)
                verdicts += 1          # answered (split across batches)
            except TimeoutError:
                raise AssertionError("poison batch hung the engine")
            except Exception:  # noqa: BLE001 — explicit error is fine
                verdicts += 1
        assert verdicts == 2
        # the engine survived: a fresh request round-trips
        rid = srv.enqueue(np.ones((1, 3), np.float32))
        np.testing.assert_array_equal(srv.query(rid, timeout=10), 2.0)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# result-table TTL GC


def test_abandoned_results_are_gcd():
    srv = ServingServer(InferenceModel(predict_fn=_echo),
                        ServingConfig(result_ttl_s=0.1,
                                      result_gc_interval_s=0.02)).start()
    try:
        for _ in range(5):
            srv.enqueue(np.ones((1, 2), np.float32))   # never queried
        deadline = time.time() + 5
        while time.time() < deadline and srv.stats["results_gc"] < 5:
            time.sleep(0.02)
        assert srv.stats["results_gc"] == 5
        assert not srv._results and not srv._result_expiry
    finally:
        srv.stop()


def test_queried_results_not_gcd_within_ttl():
    srv = ServingServer(InferenceModel(predict_fn=_echo),
                        ServingConfig(result_ttl_s=30.0)).start()
    try:
        rid = srv.enqueue(np.ones((1, 2), np.float32))
        time.sleep(0.1)
        np.testing.assert_array_equal(srv.query(rid, timeout=5), 2.0)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# degradation + half-open probe race


def test_degraded_half_open_probe_race():
    """N threads hit enqueue on a degraded (no-fallback) server at once:
    exactly ONE probe is admitted per interval, the rest shed — the
    check-then-set race is closed by the probe lock."""

    class _Dying:
        def predict(self, x):
            raise RuntimeError("replica down")

    srv = ServingServer(_Dying(), ServingConfig(
        batch_size=1, batch_timeout_s=0.0, degraded_after_failures=1,
        degraded_probe_interval_s=60.0)).start()
    try:
        rid = srv.enqueue(np.ones((1, 2), np.float32))
        with pytest.raises(RuntimeError, match="replica down"):
            srv.query(rid, timeout=10)
        assert srv.degraded
        srv._last_probe_t = 0.0            # open the probe window once
        admitted, sheds = [], []
        barrier = threading.Barrier(8)

        def worker():
            barrier.wait()
            try:
                admitted.append(srv.enqueue(np.ones((1, 2), np.float32)))
            except ServiceUnavailableError:
                sheds.append(1)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        [t.start() for t in threads]
        [t.join(10) for t in threads]
        assert len(admitted) == 1, f"{len(admitted)} probes admitted"
        assert len(sheds) == 7
        assert srv.stats["shed_requests"] == 7
    finally:
        srv.stop()


def test_injected_predict_fail_drives_degradation_and_recovery():
    """serving_predict_fail (bounded fires) degrades the server; the next
    half-open probe after the plan is exhausted clears degradation."""
    faults.install([FaultSpec("serving_predict_fail", every=1, max_fires=2)])
    srv = ServingServer(InferenceModel(predict_fn=_echo), ServingConfig(
        batch_size=1, batch_timeout_s=0.0, degraded_after_failures=2,
        degraded_probe_interval_s=60.0)).start()
    try:
        for _ in range(2):
            rid = srv.enqueue(np.ones((1, 2), np.float32))
            with pytest.raises(faults.InjectedFault):
                srv.query(rid, timeout=10)
        assert srv.degraded
        srv._last_probe_t = 0.0            # probe window open
        rid = srv.enqueue(np.ones((1, 2), np.float32))
        np.testing.assert_array_equal(srv.query(rid, timeout=10), 2.0)
        assert not srv.degraded
        assert srv.stats["failed_batches"] == 2
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# multi-worker pool chaos (subprocess workers -> slow)


def _post(url, payload, timeout=30.0):
    req = urlreq.Request(url, data=json.dumps(payload).encode(),
                         headers={"Content-Type": "application/json"})
    with urlreq.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _pool_env(extra=None):
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pythonpath = os.pathsep.join(
        p for p in [repo_root, os.environ.get("PYTHONPATH")] if p)
    env = {"PYTHONPATH": pythonpath, "JAX_PLATFORMS": "cpu"}
    env.update(extra or {})
    return env


@pytest.mark.slow
def test_pool_chaos_worker_kill_and_slow_batch():
    """The acceptance spec: a 2-worker pool under injected worker death
    (mid-request) and straggler batches loses ZERO accepted requests —
    every one gets a correct answer or an explicit error, the breaker/
    supervisor machinery respawns the corpse, and the counters are
    visible via /health."""
    from bigdl_tpu.serving.pool import ServingPool

    # each worker process: every batch is a straggler; the 6th _process
    # invocation exits the process mid-request.  Deterministic triggers:
    # the same plan fires at the same invocations in every run (count/
    # hash based, no live RNG).  Respawned workers inherit the plan, so
    # kills recur for as long as traffic flows.
    fault_plan = ("serving_slow_batch:every=1:delay=0.02:max=12;"
                  "serving_worker_kill:every=6:max=1")
    pool = ServingPool("tests.test_serving_multiproc:_pool_loader",
                       workers=2, batch_size=8,
                       worker_env=_pool_env({"BIGDL_TPU_FAULTS": fault_plan}),
                       supervise_interval_s=0.3, breaker_cooldown_s=0.5,
                       predict_timeout=20.0)
    pool.start()
    # ground truth from the same fixed-seed loader in a CLEAN subprocess
    # (the pytest process forces an 8-virtual-device XLA host via
    # conftest, which perturbs init — the workers run without it)
    import subprocess as sp
    import sys as _sys

    rs = np.random.RandomState(0)
    xs = [rs.rand(2, 8).astype(np.float32) for _ in range(18)]
    ref_out = sp.run(
        [_sys.executable, "-c",
         "import json,sys,numpy as np\n"
         "from tests.test_serving_multiproc import _pool_loader\n"
         "xs = np.asarray(json.loads(sys.stdin.read()), np.float32)\n"
         "im = _pool_loader()\n"
         "print(json.dumps([im.predict(x).tolist() for x in xs]))",
         ], input=json.dumps([x.tolist() for x in xs]),
        capture_output=True, text=True, env=dict(_pool_env(), PATH=os.environ["PATH"]),
        check=True)
    expects = [np.asarray(e, np.float32) for e in json.loads(ref_out.stdout)]
    try:
        answered, sheds, hangs = 0, 0, 0
        for i, (x, expect) in enumerate(zip(xs, expects)):
            # a client retries explicit sheds (429/503) — the lifecycle
            # contract is that those are the ONLY failure surface: an
            # accepted request answers correctly, never hangs, never
            # silently drops
            t_end = time.time() + 90
            while True:
                try:
                    out = _post(pool.url + "/predict",
                                {"instances": x.tolist()}, timeout=30.0)
                    preds = np.asarray(out["predictions"], np.float32)
                    np.testing.assert_allclose(preds, expect, rtol=1e-4,
                                               atol=1e-5)
                    answered += 1
                    break
                except HTTPError as e:
                    assert e.code in (429, 503), e.code
                    sheds += 1
                    if time.time() > t_end:
                        raise AssertionError(
                            f"request {i} shed past the retry budget")
                    time.sleep(0.3)
                except (TimeoutError, OSError) as e:
                    hangs += 1
                    raise AssertionError(f"request {i} hung: {e}")
        assert hangs == 0 and answered == 18
        # the injected kills happened and the supervisor recovered them
        assert pool.restarts >= 1, pool.restarts
        deadline = time.time() + 60
        while time.time() < deadline and not all(
                w.alive() for w in pool.workers):
            time.sleep(0.2)
        assert all(w.alive() for w in pool.workers)
        # counters visible via /health after recovery
        with urlreq.urlopen(pool.url + "/health", timeout=10) as r:
            h = json.loads(r.read())
        assert h["restarts"] >= 1
        assert all("breaker" in w for w in h["workers"])
        assert {w["breaker"]["state"] for w in h["workers"]} <= {
            "closed", "open", "half-open"}
        # respawned workers advertise their NEW urls (stale-corpse fix)
        for w, ww in zip(h["workers"], pool.workers):
            assert w["url"] == ww.url and w["alive"]
        print("CHAOS " + json.dumps({"answered": answered, "sheds": sheds,
                                     "restarts": h["restarts"]}))
    finally:
        pool.stop()


@pytest.mark.slow
def test_pool_drain_before_kill_on_stop():
    """stop() drains workers: requests in flight when stop() begins still
    complete (the worker finishes its queue before exiting)."""
    from bigdl_tpu.serving.pool import ServingPool

    # slow batches so the requests are genuinely in flight when stop()
    # lands — without drain they would die with the worker
    slow_env = _pool_env(
        {"BIGDL_TPU_FAULTS": "serving_slow_batch:every=1:delay=0.8:max=2"})
    pool = ServingPool("tests.test_serving_multiproc:_pool_loader",
                       workers=1, batch_size=8, worker_env=slow_env,
                       drain_timeout_s=10.0)
    pool.start()
    results, errors = [], []
    rs = np.random.RandomState(0)

    def client():
        try:
            x = rs.rand(2, 8).astype(np.float32)
            results.append(_post(pool.url + "/predict",
                                 {"instances": x.tolist()}))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(4)]
    [t.start() for t in threads]
    time.sleep(0.3)      # let them reach the worker queue
    pool.stop()
    [t.join(30) for t in threads]
    # drain-before-kill: in-flight work completed rather than dying with
    # the worker
    assert len(results) == 4, errors


@pytest.mark.slow
def test_pool_breaker_opens_and_recovers():
    """A killed worker's breaker opens after connection failures while the
    corpse is still routable-looking (respawn disabled via a huge
    supervise interval), then closes after respawn."""
    from bigdl_tpu.serving.pool import ServingPool

    pool = ServingPool("tests.test_serving_multiproc:_pool_loader",
                       workers=2, batch_size=8, worker_env=_pool_env(),
                       supervise_interval_s=3600.0, breaker_threshold=2,
                       breaker_cooldown_s=0.2)
    pool.start()
    try:
        rs = np.random.RandomState(0)
        _post(pool.url + "/predict",
              {"instances": rs.rand(2, 8).tolist()})
        victim = pool.workers[0]
        victim_url = victim.url
        victim.proc.kill()
        victim.proc.wait(timeout=10)
        # keep the corpse's url so the proxy actually attempts connections
        # (alive() already filters it; simulate the crashed-but-listed
        # window by feeding the breaker directly the way do_POST would)
        for _ in range(2):
            victim.breaker.record_failure()
        assert victim.breaker.state == "open"
        assert not victim.routable()           # the corpse is unroutable
        # an open breaker refuses admission without a connect attempt
        assert not victim.breaker.try_acquire()
        # requests keep flowing through the survivor
        for _ in range(4):
            out = _post(pool.url + "/predict",
                        {"instances": rs.rand(2, 8).tolist()})
            assert np.asarray(out["predictions"]).shape == (2, 4)
        # listing candidates must NOT consume the probe slot: the worker
        # stays plain 'open' until an actual attempt acquires it
        time.sleep(0.25)
        pool._next_workers()
        assert victim.breaker.state == "open"
        # half-open probe admits exactly one attempt after cooldown
        assert victim.breaker.try_acquire()    # the probe
        assert victim.breaker.state == "half-open"
        assert not victim.breaker.try_acquire()  # second caller blocked
        victim.breaker.record_failure()        # probe failed -> re-open
        assert victim.breaker.state == "open"
        time.sleep(0.25)
        assert victim.breaker.try_acquire()
        victim.breaker.record_success()        # probe succeeded -> closed
        assert victim.breaker.state == "closed"
        assert victim.breaker.trips >= 2
        assert victim_url == victim.url        # no respawn happened here
    finally:
        pool.stop()


@pytest.mark.slow
def test_pool_hedged_request_covers_slow_worker():
    """hedge_after_s: a straggling worker (injected slow batches) triggers
    ONE bounded hedge to the other worker; the request still answers fast
    and the hedge is counted."""
    from bigdl_tpu.serving.pool import ServingPool

    # worker-side: every batch sleeps well past the hedge trigger
    fault_plan = "serving_slow_batch:every=1:delay=1.0"
    slow_env = _pool_env({"BIGDL_TPU_FAULTS": fault_plan})
    pool = ServingPool("tests.test_serving_multiproc:_pool_loader",
                       workers=2, batch_size=8, worker_env=slow_env,
                       hedge_after_s=0.15, predict_timeout=20.0)
    pool.start()
    try:
        # both workers are slow (same env), so the hedge does not beat the
        # primary on wall clock — but it must fire, be bounded, and the
        # request must still answer exactly once
        rs = np.random.RandomState(0)
        out = _post(pool.url + "/predict",
                    {"instances": rs.rand(2, 8).tolist()}, timeout=30.0)
        assert np.asarray(out["predictions"]).shape == (2, 4)
        assert pool.stats["hedged_requests"] >= 1
    finally:
        pool.stop()
