"""Continuous batching, multi-tenant registry, autoscaling — the serving
hot-path rebuild (docs/serving.md §Continuous batching).

Tier-1 specs: fixed-vs-continuous batching PARITY (byte-identical
responses for the same request set), event-driven wakeup latency (no
50 ms poll), deadline-aware ordering (near-expiry jumps the queue),
weighted multi-tenant admission + per-tenant SLO metrics + per-tenant
degradation isolation, the queue_wait/occupancy exports, the
zero-recompile mixed-size sweep, the pure autoscaling policy, and the
proxy's keep-alive connection pool.  Pool integration (subprocess
workers: autoscale up/down, conn reuse counters, two models behind one
pool) runs as ``slow``.
"""

import json
import os
import threading
import time
from urllib import request as urlreq

import numpy as np
import pytest

import jax

from bigdl_tpu import nn
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.serving import (InferenceModel, ServiceUnavailableError,
                               ServingConfig, ServingServer)


def _model_and_vars(din=4, dout=2, seed=0):
    model = nn.Sequential([nn.Linear(din, 8), nn.ReLU(), nn.Linear(8, dout)])
    v = model.init(jax.random.PRNGKey(seed), np.zeros((1, din), np.float32))
    return model, v


def _serve_all(srv, xs):
    rids = [srv.enqueue(x) for x in xs]
    return [np.asarray(srv.query(rid, timeout=30)) for rid in rids]


# ---------------------------------------------------------------------------
# batching parity: continuous vs fixed


def test_continuous_matches_fixed_byte_identical_custom_fn():
    """Same request set through both engine modes -> byte-identical
    responses, for arbitrary co-batching (row-wise deterministic fn)."""
    rs = np.random.RandomState(0)
    xs = [rs.rand(rs.randint(1, 5), 3).astype(np.float32)
          for _ in range(24)]

    def run(continuous):
        srv = ServingServer(
            InferenceModel(predict_fn=lambda x: np.asarray(x) * 2.0 + 1.0),
            ServingConfig(batch_size=6, batch_timeout_s=0.002,
                          continuous=continuous)).start()
        try:
            return _serve_all(srv, xs)
        finally:
            srv.stop()

    for a, b in zip(run(True), run(False)):
        assert a.tobytes() == b.tobytes()


def test_continuous_matches_fixed_byte_identical_jitted_model():
    """The jitted path: bucket padding makes per-row results independent
    of co-batching, so the two engines agree to the byte."""
    model, v = _model_and_vars()
    im = InferenceModel(model, v, batch_buckets=(4, 16))
    rs = np.random.RandomState(1)
    xs = [rs.rand(rs.randint(1, 6), 4).astype(np.float32)
          for _ in range(20)]

    def run(continuous):
        srv = ServingServer(im, ServingConfig(
            batch_size=8, batch_timeout_s=0.002,
            continuous=continuous)).start()
        try:
            return _serve_all(srv, xs)
        finally:
            srv.stop()

    for a, b in zip(run(True), run(False)):
        assert a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# event-driven wakeup + deadline-aware ordering


def test_event_driven_wakeup_latency():
    """Sparse traffic pays no polling penalty: a lone request round-trips
    in milliseconds (the old loop polled the queue at 50 ms)."""
    srv = ServingServer(
        InferenceModel(predict_fn=lambda x: np.asarray(x)),
        ServingConfig(batch_size=8, batch_timeout_s=0.0)).start()
    try:
        srv.query(srv.enqueue(np.ones((1, 2), np.float32)), timeout=10)
        lats = []
        for _ in range(20):
            t0 = time.perf_counter()
            srv.query(srv.enqueue(np.ones((1, 2), np.float32)), timeout=10)
            lats.append(time.perf_counter() - t0)
            time.sleep(0.01)   # sparse: every request finds an idle engine
        assert np.median(lats) < 0.02, (
            f"median sparse latency {np.median(lats)*1e3:.1f}ms — the "
            "event-driven wakeup is not waking the assembler")
    finally:
        srv.stop()


def test_near_expiry_request_jumps_queue():
    """Deadline-aware ordering: a later-enqueued request with a deadline
    is predicted BEFORE an earlier no-deadline request."""
    order = []

    def recording(x):
        order.append(float(np.asarray(x).ravel()[0]))
        time.sleep(0.05)
        return np.asarray(x)

    srv = ServingServer(InferenceModel(predict_fn=recording),
                        ServingConfig(batch_size=1,
                                      batch_timeout_s=0.0)).start()
    try:
        r0 = srv.enqueue(np.full((1, 2), 0.0, np.float32))   # occupies engine
        time.sleep(0.02)
        # rA fills the handoff slot, so r1/r2 meet in the HEAP — where
        # deadline ordering decides who goes next
        ra = srv.enqueue(np.full((1, 2), 0.5, np.float32))
        time.sleep(0.02)
        r1 = srv.enqueue(np.full((1, 2), 1.0, np.float32))   # no deadline
        r2 = srv.enqueue(np.full((1, 2), 2.0, np.float32), deadline_s=5.0)
        for rid in (r0, ra, r1, r2):
            srv.query(rid, timeout=10)
        assert order == [0.0, 0.5, 2.0, 1.0], order
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# multi-tenant registry


def test_multi_tenant_routing_and_unknown_model():
    srv = ServingServer(models={
        "double": InferenceModel(predict_fn=lambda x: np.asarray(x) * 2),
        "triple": InferenceModel(predict_fn=lambda x: np.asarray(x) * 3),
    }).start()
    try:
        x = np.ones((1, 2), np.float32)
        np.testing.assert_array_equal(
            srv.query(srv.enqueue(x, model="double"), timeout=10), 2.0)
        np.testing.assert_array_equal(
            srv.query(srv.enqueue(x, model="triple"), timeout=10), 3.0)
        # no "default" key: the FIRST registered model takes unrouted
        # requests
        np.testing.assert_array_equal(
            srv.query(srv.enqueue(x), timeout=10), 2.0)
        with pytest.raises(KeyError, match="unknown model"):
            srv.enqueue(x, model="nope")
        info = srv.models()
        assert set(info) == {"double", "triple"}
        assert info["double"]["default"] and not info["triple"]["default"]
    finally:
        srv.stop()


def test_weighted_admission_shares_engine_by_weight():
    """Stride scheduling: with backlog on both tenants, a weight-3 tenant
    gets ~3x the service of a weight-1 tenant."""
    order = []

    def recorder(tag):
        def predict(x):
            order.append(tag)
            time.sleep(0.002)
            return np.asarray(x)
        return predict

    srv = ServingServer(models={
        "heavy": InferenceModel(predict_fn=recorder("heavy")),
        "light": InferenceModel(predict_fn=recorder("light")),
    }, config=ServingConfig(batch_size=2, batch_timeout_s=0.0))
    srv._tenants["heavy"].weight = 3.0
    rids = []
    for i in range(12):    # backlog BEFORE start: deterministic pops
        rids.append(srv.enqueue(np.ones((1, 2), np.float32), model="heavy"))
        rids.append(srv.enqueue(np.ones((1, 2), np.float32), model="light"))
    srv.start()
    try:
        for rid in rids:
            srv.query(rid, timeout=30)
        first8 = order[:8]
        assert first8.count("heavy") >= 5, (
            f"weight-3 tenant got {first8.count('heavy')}/8 of the first "
            f"batches: {order}")
        assert "light" in order[:8], "weight-1 tenant starved outright"
    finally:
        srv.stop()


def test_tenant_degradation_is_isolated():
    """One tenant's dying model degrades and sheds ONLY that tenant; the
    other keeps answering."""

    class _Dying:
        def predict(self, x):
            raise RuntimeError("replica down")

    srv = ServingServer(models={
        "good": InferenceModel(predict_fn=lambda x: np.asarray(x) * 2),
        "bad": _Dying(),
    }, config=ServingConfig(batch_size=1, batch_timeout_s=0.0,
                            degraded_after_failures=1,
                            degraded_probe_interval_s=60.0)).start()
    try:
        x = np.ones((1, 2), np.float32)
        rid = srv.enqueue(x, model="bad")
        with pytest.raises(RuntimeError, match="replica down"):
            srv.query(rid, timeout=10)
        assert srv._tenants["bad"].degraded
        assert not srv._tenants["good"].degraded
        srv._tenants["bad"].last_probe_t = time.time()  # close the probe
        with pytest.raises(ServiceUnavailableError):
            srv.enqueue(x, model="bad")
        np.testing.assert_array_equal(
            srv.query(srv.enqueue(x, model="good"), timeout=10), 2.0)
    finally:
        srv.stop()


def test_per_tenant_metrics_in_one_scrape():
    """Two tenants' latency histograms land in ONE Prometheus scrape —
    the per-tenant SLO surface."""
    from bigdl_tpu.obs.export import render_prometheus

    reg = Metrics()
    srv = ServingServer(models={
        "alpha": InferenceModel(predict_fn=lambda x: np.asarray(x)),
        "beta": InferenceModel(predict_fn=lambda x: np.asarray(x)),
    }, metrics=reg).start()
    try:
        x = np.ones((1, 2), np.float32)
        srv.query(srv.enqueue(x, model="alpha"), timeout=10)
        srv.query(srv.enqueue(x, model="beta"), timeout=10)
        text = render_prometheus(reg)
        for tenant in ("alpha", "beta"):
            assert f"serving_tenant_{tenant}_latency_s_bucket" in text
            assert f"serving_tenant_{tenant}_queue_wait_s" in text
            assert f"serving_tenant_{tenant}_requests" in text
    finally:
        srv.stop()


def test_register_unregister_live():
    srv = ServingServer(
        InferenceModel(predict_fn=lambda x: np.asarray(x))).start()
    try:
        srv.register_model("extra",
                           InferenceModel(predict_fn=lambda x:
                                          np.asarray(x) * 5))
        x = np.ones((1, 2), np.float32)
        np.testing.assert_array_equal(
            srv.query(srv.enqueue(x, model="extra"), timeout=10), 5.0)
        with pytest.raises(ValueError, match="already registered"):
            srv.register_model("extra", InferenceModel(predict_fn=str))
        with pytest.raises(ValueError, match="default"):
            srv.unregister_model("default")
        srv.unregister_model("extra")
        with pytest.raises(KeyError):
            srv.enqueue(x, model="extra")
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# wait/occupancy exports


def test_queue_wait_and_occupancy_exported():
    from bigdl_tpu.obs.export import render_prometheus

    reg = Metrics()
    srv = ServingServer(
        InferenceModel(predict_fn=lambda x: np.asarray(x)),
        ServingConfig(batch_size=4, batch_timeout_s=0.002),
        metrics=reg).start()
    try:
        rids = [srv.enqueue(np.ones((1, 2), np.float32)) for _ in range(8)]
        for rid in rids:
            srv.query(rid, timeout=10)
        snap = reg.snapshot()
        assert snap["hists"]["serving.queue_wait_s"]["n"] == 8
        occ = snap["gauges"]["serving.batch_occupancy"]
        assert 0.0 < occ <= 1.0
        # occupancy == avg fill / batch_size, from the same stats
        expect = (srv.stats["requests"] / srv.stats["batches"]) / 4
        assert abs(occ - expect) < 1e-9
        text = render_prometheus(reg)
        assert "serving_queue_wait_s_bucket" in text
        assert "serving_batch_occupancy" in text
        # the autoscaling pressure signal rides the same scrape; the
        # engine gauges it after publish, so poll for the drained value
        assert "serving_backlog" in text
        for _ in range(500):
            if reg.snapshot()["gauges"]["serving.backlog"] == 0.0:
                break
            time.sleep(0.002)
        assert reg.snapshot()["gauges"]["serving.backlog"] == 0.0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# bucket padding: zero unexpected recompiles across a mixed-size sweep


def test_mixed_size_sweep_zero_unexpected_recompiles():
    from bigdl_tpu.obs import attr as obs_attr
    from bigdl_tpu.optim.metrics import global_metrics

    model, v = _model_and_vars()
    im = InferenceModel(model, v, batch_buckets=(2, 4, 8))
    im.warmup(np.zeros((4,), np.float32))
    sent = obs_attr.recompile_sentinel()
    before = global_metrics().counter("train.unexpected_recompiles_total")
    sent.mark_steady()
    try:
        srv = ServingServer(im, ServingConfig(
            batch_size=4, batch_timeout_s=0.001)).start()
        try:
            rs = np.random.RandomState(0)
            for rows in (1, 2, 3, 5, 7, 8, 9, 20):   # incl. > max bucket
                rid = srv.enqueue(rs.rand(rows, 4).astype(np.float32))
                out = srv.query(rid, timeout=30)
                assert out.shape == (rows, 2)
        finally:
            srv.stop()
        after = global_metrics().counter(
            "train.unexpected_recompiles_total")
        assert after == before, (
            f"{after - before} unexpected XLA recompiles in a mixed-size "
            "sweep — bucket padding/chunking broke")
    finally:
        sent.mark_warmup()


def test_inference_model_chunks_past_largest_bucket():
    model, v = _model_and_vars()
    im = InferenceModel(model, v, batch_buckets=(2, 4))
    rs = np.random.RandomState(0)
    x = rs.rand(11, 4).astype(np.float32)
    out = im.predict(x)
    assert out.shape == (11, 2)
    ref, _ = model.apply(v, x)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# autoscaling policy (pure function — subprocess integration is slow)


def test_autoscale_decision_policy():
    from bigdl_tpu.serving.pool import ServingPool

    d = ServingPool.autoscale_decision
    base = dict(n_workers=2, min_workers=1, max_workers=4,
                avg_queue_depth=0.0, up_depth=8.0, idle_ticks=0,
                down_after=3, breaker_open=False,
                since_last_scale_s=60.0, cooldown_s=5.0)
    assert d(**base) == "hold"
    assert d(**{**base, "avg_queue_depth": 9.0}) == "up"
    # at the max bound pressure cannot add workers
    assert d(**{**base, "avg_queue_depth": 9.0, "n_workers": 4}) == "hold"
    # cooldown gates BOTH directions
    assert d(**{**base, "avg_queue_depth": 9.0,
                "since_last_scale_s": 1.0}) == "hold"
    assert d(**{**base, "idle_ticks": 3}) == "down"
    assert d(**{**base, "idle_ticks": 2}) == "hold"      # not sustained
    assert d(**{**base, "idle_ticks": 3, "n_workers": 1}) == "hold"
    # an open breaker means load is about to redistribute: never shrink
    assert d(**{**base, "idle_ticks": 3, "breaker_open": True}) == "hold"


# ---------------------------------------------------------------------------
# keep-alive connection pool


def test_conn_pool_reuses_keep_alive_connections():
    from bigdl_tpu.serving import HttpFrontend
    from bigdl_tpu.serving.pool import _ConnPool

    srv = ServingServer(
        InferenceModel(predict_fn=lambda x: np.asarray(x))).start()
    fe = HttpFrontend(srv).start()
    conns = _ConnPool(timeout=10.0)
    try:
        conn, reused = conns.acquire(fe.url)
        assert not reused
        conn.request("GET", "/health")
        assert conn.getresponse().read()
        conns.release(fe.url, conn)
        conn2, reused2 = conns.acquire(fe.url)
        assert reused2 and conn2 is conn   # the parked socket came back
        conn2.request("GET", "/health")
        body = json.loads(conn2.getresponse().read())
        assert body["status"] == "ok"
        conns.release(fe.url, conn2)
        conns.clear(fe.url)
        _, reused3 = conns.acquire(fe.url)
        assert not reused3                 # clear() really dropped it
    finally:
        conns.clear()
        fe.stop()
        srv.stop()


# ---------------------------------------------------------------------------
# frontend surface: /models, model routing, health fields


def test_http_frontend_models_and_health_fields():
    from bigdl_tpu.serving import HttpClient, HttpFrontend

    srv = ServingServer(models={
        "a": InferenceModel(predict_fn=lambda x: np.asarray(x) * 2),
        "b": InferenceModel(predict_fn=lambda x: np.asarray(x) * 3),
    }).start()
    fe = HttpFrontend(srv).start()
    try:
        client = HttpClient(fe.url)
        np.testing.assert_array_equal(
            client.predict(np.ones((1, 2), np.float32), model="b"), 3.0)
        assert set(client.models()) == {"a", "b"}
        h = client.health()
        for key in ("queue_depth", "backlog", "p50_ms", "p99_ms",
                    "occupancy", "models"):
            assert key in h, key
        # unknown model -> 404 with the registry in the error
        from urllib.error import HTTPError
        req = urlreq.Request(
            fe.url + "/predict",
            data=json.dumps({"instances": [[1.0, 2.0]],
                             "model": "nope"}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(HTTPError) as ei:
            urlreq.urlopen(req, timeout=10)
        assert ei.value.code == 404
    finally:
        fe.stop()
        srv.stop()


def test_http_client_keep_alive_roundtrips():
    from bigdl_tpu.serving import HttpClient, HttpFrontend

    srv = ServingServer(
        InferenceModel(predict_fn=lambda x: np.asarray(x) * 2)).start()
    fe = HttpFrontend(srv).start()
    client = HttpClient(fe.url, keep_alive=True)
    try:
        for _ in range(3):
            np.testing.assert_array_equal(
                client.predict(np.ones((1, 2), np.float32)), 2.0)
        assert client._conn is not None    # the socket persisted
    finally:
        client.close()
        fe.stop()
        srv.stop()


# ---------------------------------------------------------------------------
# pool integration (subprocess workers) — slow


def _pool_env(extra=None):
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pythonpath = os.pathsep.join(
        p for p in [repo_root, os.environ.get("PYTHONPATH")] if p)
    env = {"PYTHONPATH": pythonpath, "JAX_PLATFORMS": "cpu"}
    env.update(extra or {})
    return env


def _two_model_loader():
    """Worker-side registry factory: two tenants behind one engine."""
    import numpy as np
    import jax

    from bigdl_tpu import nn
    from bigdl_tpu.serving.inference_model import InferenceModel

    def make(seed):
        model = nn.Sequential([nn.Linear(8, 4)])
        variables = model.init(jax.random.PRNGKey(seed),
                               np.zeros((1, 8), np.float32))
        return InferenceModel(model, variables)

    return {"resnet": make(0), "bert": make(1)}


def _post(url, payload, timeout=30.0):
    req = urlreq.Request(url, data=json.dumps(payload).encode(),
                         headers={"Content-Type": "application/json"})
    with urlreq.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.mark.slow
def test_pool_serves_two_models_with_per_tenant_metrics():
    """The multi-tenant acceptance: two models behind ONE pool, routed by
    the payload's "model" key, with both tenants' latency histograms in
    one worker /metrics scrape."""
    from bigdl_tpu.serving.pool import ServingPool

    pool = ServingPool("tests.test_serving_continuous:_two_model_loader",
                       workers=1, batch_size=8, worker_env=_pool_env())
    pool.start()
    try:
        rs = np.random.RandomState(0)
        outs = {}
        for name in ("resnet", "bert"):
            out = _post(pool.url + "/predict",
                        {"instances": rs.rand(2, 8).tolist(),
                         "model": name})
            outs[name] = np.asarray(out["predictions"], np.float32)
            assert outs[name].shape == (2, 4)
        # different tenants actually hit different weights
        assert not np.array_equal(outs["resnet"], outs["bert"])
        # header-form routing (X-Model) survives the proxy hop: same
        # input via header-bert == payload-bert, != payload-resnet
        x2 = rs.rand(2, 8).tolist()
        req = urlreq.Request(
            pool.url + "/predict",
            data=json.dumps({"instances": x2}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Model": "bert"})
        with urlreq.urlopen(req, timeout=30) as r:
            via_header = np.asarray(json.loads(r.read())["predictions"],
                                    np.float32)
        np.testing.assert_array_equal(
            via_header,
            np.asarray(_post(pool.url + "/predict",
                             {"instances": x2, "model": "bert"}
                             )["predictions"], np.float32))
        assert not np.array_equal(
            via_header,
            np.asarray(_post(pool.url + "/predict",
                             {"instances": x2, "model": "resnet"}
                             )["predictions"], np.float32))
        # proxy relays the registry
        with urlreq.urlopen(pool.url + "/models", timeout=10) as r:
            models = json.loads(r.read())["models"]
        assert set(models) == {"resnet", "bert"}
        # one scrape of the worker shows BOTH tenants' SLO histograms
        with urlreq.urlopen(pool.workers[0].url + "/metrics",
                            timeout=10) as r:
            text = r.read().decode()
        assert "serving_tenant_resnet_latency_s_bucket" in text
        assert "serving_tenant_bert_latency_s_bucket" in text
        # forwards rode the keep-alive pool
        assert pool.stats["conn_reuse"] >= 1
    finally:
        pool.stop()


@pytest.mark.slow
def test_pool_autoscales_up_under_load_and_down_when_idle():
    """Metrics-driven autoscaling end to end: sustained queue pressure
    grows the pool (within max_workers), sustained idle shrinks it back
    (drain-before-kill), both visible in stats/flight."""
    from bigdl_tpu.serving.pool import ServingPool

    # every batch is a straggler -> the queue backs up behind predict
    slow_env = _pool_env(
        {"BIGDL_TPU_FAULTS": "serving_slow_batch:every=1:delay=0.25"})
    pool = ServingPool("tests.test_serving_multiproc:_pool_loader",
                       workers=1, batch_size=4, worker_env=slow_env,
                       min_workers=1, max_workers=2,
                       autoscale_interval_s=0.3,
                       scale_up_queue_depth=2.0, scale_down_after=3,
                       scale_cooldown_s=0.5, predict_timeout=30.0)
    pool.start()
    try:
        rs = np.random.RandomState(0)
        stop_load = threading.Event()
        errors = []

        def hammer():
            while not stop_load.is_set():
                try:
                    _post(pool.url + "/predict",
                          {"instances": rs.rand(1, 8).tolist()},
                          timeout=30.0)
                except Exception:  # noqa: BLE001 — sheds are expected
                    time.sleep(0.05)

        threads = [threading.Thread(target=hammer) for _ in range(8)]
        [t.start() for t in threads]
        deadline = time.time() + 60
        while time.time() < deadline and len(pool.workers) < 2:
            time.sleep(0.2)
        assert len(pool.workers) == 2, "never scaled up under load"
        assert pool.stats["scale_up"] >= 1
        stop_load.set()
        [t.join(30) for t in threads]
        assert not errors
        deadline = time.time() + 60
        while time.time() < deadline and len(pool.workers) > 1:
            time.sleep(0.2)
        assert len(pool.workers) == 1, "never scaled down after idle"
        assert pool.stats["scale_down"] >= 1
        # the survivor still answers (the drained worker left cleanly)
        out = _post(pool.url + "/predict",
                    {"instances": rs.rand(1, 8).tolist()}, timeout=30.0)
        assert np.asarray(out["predictions"]).shape == (1, 4)
        with urlreq.urlopen(pool.url + "/health", timeout=10) as r:
            h = json.loads(r.read())
        assert h["autoscale"]["min"] == 1 and h["autoscale"]["max"] == 2
    finally:
        pool.stop()
