"""Decode-fleet fault tolerance (docs/serving.md §Fleet fault tolerance):
mid-stream failover, live KV migration on drain, chaos-hardened routing.

The load-bearing invariant is the same byte parity test_fleet.py pins,
extended across failures: a stream whose worker dies (or drains away)
mid-generation must finish with EXACTLY the tokens the no-fault run
would have produced — greedy AND seeded — because sampling keys are
counter-based on absolute position, so re-prefilling prompt+delivered
(or adopting the migrated pages) reconstructs the mid-run state bit for
bit.  These tests exercise every recovery path: resume-by-re-prefill,
migration adoption, corrupt-handoff degradation, client-disconnect slot
reclaim, breaker-driven snapshot invalidation, and (slow) a real
SIGKILL / scale-down drain against subprocess pool workers.
"""

import json
import os
import threading
import time
from urllib import request as urlreq

import jax
import numpy as np
import pytest

from bigdl_tpu.nn.attention import Transformer
from bigdl_tpu.resilience import faults
from bigdl_tpu.serving.decode_engine import (DecodeConfig, DecodeEngine,
                                             DecodeRequest, LMAdapter)
from bigdl_tpu.serving.fleet.handoff import (HandoffError, pack_handoff,
                                             unpack_handoff)

BOS, EOS = 0, 1


@pytest.fixture(scope="module")
def lm():
    model = Transformer(vocab_size=32, hidden_size=16, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    v = model.init(jax.random.PRNGKey(0),
                   np.arange(6, dtype=np.int32)[None])
    return model, v


@pytest.fixture(autouse=True)
def _no_faults():
    yield
    faults.clear()


def _engine(lm, **over):
    model, v = lm
    kw = dict(slots=4, page_size=4, pages_per_slot=4, prompt_chunk=4,
              max_new_tokens=16, eos_id=EOS, prefill_batch=2,
              prefix_cache_pages=8)
    kw.update(over)
    cfg = DecodeConfig(**kw)
    return DecodeEngine(LMAdapter(model, v["params"], cap=cfg.cap),
                        cfg).warmup()


def _serving_pair(lm, **decode_over):
    from bigdl_tpu.serving.http_frontend import HttpFrontend
    from bigdl_tpu.serving.inference_model import InferenceModel
    from bigdl_tpu.serving.server import ServingConfig, ServingServer

    model, v = lm
    kw = dict(slots=4, page_size=4, pages_per_slot=4, prompt_chunk=4,
              max_new_tokens=16, eos_id=EOS, prefill_batch=2,
              prefix_cache_pages=8)
    kw.update(decode_over)
    srv = ServingServer(InferenceModel(model, v, decode=DecodeConfig(**kw)),
                        ServingConfig()).start()
    fe = HttpFrontend(srv, port=0).start()
    return srv, fe


def _slow_engine(eng, sleep_s=0.03):
    """Throttle the decode loop so a test can act mid-stream
    deterministically; the wrapper runs inside ``_iter_lock``, so
    ``drain_decode``/``cancel`` still interleave atomically.  The rate
    is re-tunable via ``eng._test_sleep_s`` (fixture-shared engines)."""
    orig = eng._decode_step
    eng._test_sleep_s = sleep_s

    def _step():
        time.sleep(eng._test_sleep_s)
        return orig()

    eng._decode_step = _step


# engine warmup dominates this file's wall time, so the serving pairs
# are module fixtures; tests assert on stat DELTAS, never absolutes


@pytest.fixture(scope="module")
def pair(lm):
    srv, fe = _serving_pair(lm)
    yield srv, fe
    fe.stop()
    srv.stop()


@pytest.fixture(scope="module")
def nocache(lm):
    """A bare exporter engine + an importing pair, prefix cache OFF so
    adoption vs re-prefill is decided by the parked handoff alone."""
    eng_a = _engine(lm, prefix_cache_pages=0)
    srv_b, fe_b = _serving_pair(lm, prefix_cache_pages=0)
    yield eng_a, srv_b, fe_b
    fe_b.stop()
    srv_b.stop()
    eng_a.stop()


@pytest.fixture(scope="module")
def drain_pair(lm):
    """Victim A (decode throttled so tests can act mid-stream) and
    adopting peer B."""
    srv_a, fe_a = _serving_pair(lm)
    srv_b, fe_b = _serving_pair(lm)
    _slow_engine(srv_a.model.decode_engine)
    yield srv_a, fe_a, srv_b, fe_b
    fe_a.stop()
    fe_b.stop()
    srv_a.stop()
    srv_b.stop()


def _prompt(n=4, seed=3):
    rs = np.random.RandomState(seed)
    return np.asarray(rs.randint(2, 32, size=n), np.int32)


def _ref_tokens(eng, prompt, max_new, **kw):
    r = eng.static_generate([DecodeRequest(
        tokens=np.asarray(prompt, np.int32),
        max_new_tokens=max_new, **kw)])[0]
    return [int(t) for t in r.tokens]


SEEDED = dict(temperature=0.8, top_k=8, top_p=0.9, seed=13)


# ---------------------------------------------------------------------------
# proxy relay units: failover bookkeeping without any worker process


def test_track_line_records_and_dedups():
    from bigdl_tpu.serving.pool import _ProxyHandler

    d = []
    track = _ProxyHandler._track_line
    assert track(b'{"token": 7, "index": 0}', d) and d == [7]
    assert track(b'{"token": 9, "index": 1}', d) and d == [7, 9]
    # an adopting worker re-emits the boundary token: dropped, not doubled
    assert not track(b'{"token": 9, "index": 1}', d)
    assert d == [7, 9]
    # final verdicts / non-token lines pass through untouched
    assert track(b'{"done": true, "tokens": [7, 9]}', d)
    assert track(b"not json at all", d)
    assert track(b"[1, 2]", d)
    # blanks are swallowed (keep-alive noise must not be re-framed)
    assert not track(b"   ", d)
    assert d == [7, 9]


def test_resume_body_rebuilds_request():
    from bigdl_tpu.serving.pool import _ProxyHandler

    body = json.dumps({"tokens": [2, 3], "stream": True,
                       "seed": 5}).encode()
    out = _ProxyHandler._resume_body(None, body, [7, 9])
    payload = json.loads(out)
    assert payload["resume_from"] == [7, 9]
    assert payload["seed"] == 5 and payload["stream"] is True
    # nothing delivered yet: a plain fresh re-request, no resume_from
    fresh = json.loads(_ProxyHandler._resume_body(None, body, []))
    assert "resume_from" not in fresh
    # unreconstructable bodies orphan instead of corrupting
    assert _ProxyHandler._resume_body(None, b"\xff\xfe", [1]) is None
    assert _ProxyHandler._resume_body(None, b"[1]", [1]) is None


def test_breaker_open_invalidates_fleet_snapshot():
    from bigdl_tpu.serving.pool import ServingPool

    pool = ServingPool("tests.test_fleet_chaos:_fleet_loader", workers=2)
    try:
        pool._fleet_cache = [("stale", None)]
        pool._fleet_t = time.time()
        pool.invalidate_fleet_snapshot()
        assert pool._fleet_cache is None and pool._fleet_t == 0.0
        # a worker breaker tripping open must evict the routing snapshot
        # (the cached healths still score the dying worker as routable)
        pool._fleet_cache = [("stale", None)]
        pool._fleet_t = time.time()
        w = pool._new_worker()
        for _ in range(pool.breaker_threshold):
            w.breaker.record_failure()
        assert w.breaker.snapshot()["state"] == "open"
        assert pool._fleet_cache is None
    finally:
        pool._httpd.server_close()


def test_fleet_fault_points_registered():
    for point in ("fleet_worker_kill", "fleet_handoff_corrupt",
                  "fleet_stream_sever", "fleet_health_stale"):
        assert point in faults.POINTS
    specs = faults.parse_plan("fleet_stream_sever:every=1;"
                              "fleet_health_stale:every=1")
    faults.install(specs)
    with pytest.raises(faults.StreamSeveredError) as ei:
        faults.fire("fleet_stream_sever")
    # the relay's worker-read try treats it as a connection dying
    assert isinstance(ei.value, ConnectionResetError)
    with pytest.raises(faults.HealthStaleFault):
        faults.fire("fleet_health_stale")


def test_unpack_handoff_hardening_bounds():
    rs = np.random.RandomState(0)
    h = {"tokens": [3, 4, 5], "first_token": 6, "first_logp": -0.5,
         "request_id": "hard-1",
         "k": rs.randn(2, 2, 2, 4, 3).astype(np.float32),
         "v": rs.randn(2, 2, 2, 4, 3).astype(np.float32)}
    blob = pack_handoff(h)
    # request_id rides the wire: what /fleet/import parks by
    assert unpack_handoff(blob)["request_id"] == "hard-1"
    with pytest.raises(HandoffError, match="exceeds"):
        unpack_handoff(blob, max_bytes=16)
    with pytest.raises(HandoffError, match="page"):
        unpack_handoff(blob, max_pages=1)
    with pytest.raises(HandoffError, match="magic"):
        unpack_handoff(b"XXXXXXXX" + blob[8:])
    # HandoffError stays a ValueError: pre-existing callers keep working
    assert issubclass(HandoffError, ValueError)


# ---------------------------------------------------------------------------
# resume_from: the frontend half of mid-stream failover


def test_resume_reprefill_parity_greedy(lm, pair):
    from bigdl_tpu.serving.http_frontend import HttpClient

    srv, fe = pair
    eng = srv.model.decode_engine
    p = _prompt()
    ref = _ref_tokens(eng, p, 8)
    assert len(ref) >= 6  # the split below needs a mid-stream point
    c = HttpClient(fe.url)
    got = c.generate(p, max_new_tokens=8, resume_from=ref[:4],
                     request_id="rg-1")
    assert [int(t) for t in got] == ref


def test_resume_reprefill_parity_seeded(lm, pair):
    from bigdl_tpu.serving.http_frontend import HttpClient

    srv, fe = pair
    eng = srv.model.decode_engine
    p = _prompt()
    ref = _ref_tokens(eng, p, 8, **SEEDED)
    assert len(ref) >= 6
    c = HttpClient(fe.url)
    got = c.generate(p, max_new_tokens=8, resume_from=ref[:4],
                     request_id="rs-1", **SEEDED)
    assert [int(t) for t in got] == ref


def test_resume_stream_indices_continue_past_delivered(lm, pair):
    """A resumed stream must only emit tokens the client does NOT hold,
    indexed where the dead worker stopped — the relay dedups by index."""
    import http.client

    srv, fe = pair
    eng = srv.model.decode_engine
    p = _prompt()
    ref = _ref_tokens(eng, p, 8, **SEEDED)
    assert len(ref) >= 6
    conn = http.client.HTTPConnection(fe.host, fe.port, timeout=30)
    conn.request("POST", "/generate", body=json.dumps(dict(
        tokens=[int(t) for t in p], stream=True, max_new_tokens=8,
        resume_from=ref[:4], request_id="ri-1", **SEEDED)).encode(),
        headers={"Content-Type": "application/json",
                 "Connection": "close"})
    resp = conn.getresponse()
    assert resp.status == 200
    events, final = [], None
    while True:
        line = resp.readline()
        if not line:
            break
        ev = json.loads(line)
        if ev.get("done"):
            final = ev
            break
        events.append((ev["index"], ev["token"]))
    conn.close()
    assert final is not None and "error" not in final
    assert [int(t) for t in final["tokens"]] == ref
    # re-prefill path: generation restarts at index r, never below
    assert events and events[0][0] == 4
    assert [t for _, t in events] == ref[4:]


def test_resume_short_circuits_when_nothing_left(lm, pair):
    """resume_from covering the whole effective budget (or ending at
    EOS) answers immediately with what the client already holds — the
    original run would have stopped exactly there."""
    from bigdl_tpu.serving.http_frontend import HttpClient

    srv, fe = pair
    eng = srv.model.decode_engine
    p = _prompt()
    ref = _ref_tokens(eng, p, 4)
    requests_before = eng.stats["requests"]  # no engine work at all
    c = HttpClient(fe.url)
    got = c.generate(p, max_new_tokens=4, resume_from=ref,
                     request_id="rc-1")
    assert [int(t) for t in got] == ref
    # EOS-terminated delivery short-circuits too
    got = c.generate(p, max_new_tokens=8, resume_from=[5, EOS],
                     request_id="rc-2")
    assert [int(t) for t in got] == [5, EOS]
    assert eng.stats["requests"] == requests_before


def test_resume_reprefill_hits_warm_prefix_cache(lm, pair):
    """Failover re-prefill pays page-aligned prefix-cache hits for the
    prompt the original run already donated — recovery cost is the
    delivered suffix, not the whole prompt."""
    from bigdl_tpu.serving.http_frontend import HttpClient

    srv, fe = pair
    eng = srv.model.decode_engine
    p = _prompt(8, seed=11)  # page-aligned: 2 full pages cacheable
    c = HttpClient(fe.url)
    ref = [int(t) for t in c.generate(p, max_new_tokens=6,
                                      request_id="pc-0")]
    assert len(ref) == 6
    st = eng._prefix_cache.stats()
    assert st["insertions"] >= 1
    hits_before = st["hits"]
    got = c.generate(p, max_new_tokens=6, resume_from=ref[:3],
                     request_id="pc-1")
    assert [int(t) for t in got] == ref
    assert eng._prefix_cache.stats()["hits"] > hits_before


# ---------------------------------------------------------------------------
# migration adoption: parked pages instead of re-prefill


def test_resume_adopts_parked_migration_handoff(lm, nocache):
    """A parked handoff whose state matches prompt+delivered exactly is
    adopted: no re-prefill, the boundary token re-emits at index r-1,
    and the continuation is byte-identical to the no-fault run."""
    import http.client

    eng_a, srv_b, fe_b = nocache
    eng_b = srv_b.model.decode_engine
    imports_before = eng_b.stats["kv_imports"]
    p = _prompt()
    ref = _ref_tokens(eng_b, p, 8, **SEEDED)
    assert len(ref) == 8
    r = 4
    # the state a drained victim would export at r delivered tokens
    # IS a prefill export of prompt + delivered[:-1]: same pages,
    # same pending first token (the byte-parity invariant)
    pre = eng_a.submit(DecodeRequest(
        tokens=np.concatenate([p, np.asarray(ref[:r - 1], np.int32)]),
        max_new_tokens=1, export_kv=True, **SEEDED))
    pre.wait(30)
    assert pre.error is None and pre.kv_export is not None
    h = dict(pre.kv_export)
    h.update(request_id="adopt-1", **SEEDED)
    assert int(h["first_token"]) == ref[r - 1]
    req = urlreq.Request(fe_b.url + "/fleet/import",
                         data=pack_handoff(h),
                         headers={"Content-Type":
                                  "application/octet-stream"})
    with urlreq.urlopen(req, timeout=30) as resp:
        assert json.loads(resp.read())["parked"] == "adopt-1"
    conn = http.client.HTTPConnection(fe_b.host, fe_b.port, timeout=30)
    conn.request("POST", "/generate", body=json.dumps(dict(
        tokens=[int(t) for t in p], stream=True, max_new_tokens=8,
        resume_from=ref[:r], request_id="adopt-1",
        **SEEDED)).encode(),
        headers={"Content-Type": "application/json",
                 "Connection": "close"})
    resp = conn.getresponse()
    assert resp.status == 200
    events, final = [], None
    while True:
        line = resp.readline()
        if not line:
            break
        ev = json.loads(line)
        if ev.get("done"):
            final = ev
            break
        events.append((ev["index"], ev["token"]))
    conn.close()
    assert final is not None and "error" not in final
    assert [int(t) for t in final["tokens"]] == ref
    # adoption, not re-prefill: the pages were IMPORTED, and the
    # boundary token re-emitted at index r-1 (the relay's dedup
    # point) — a re-prefill would have started at index r
    assert eng_b.stats["kv_imports"] == imports_before + 1
    assert events[0] == (r - 1, ref[r - 1])
    # parked state is single-use
    assert srv_b.take_parked("adopt-1") is None


def test_resume_rejects_mismatched_parked_state(lm, nocache):
    """A parked handoff that does not exactly match prompt+delivered
    (here: different sampling seed) must NOT be adopted — byte parity
    is safer served by re-prefill."""
    from bigdl_tpu.serving.http_frontend import HttpClient

    eng_a, srv_b, fe_b = nocache
    eng_b = srv_b.model.decode_engine
    imports_before = eng_b.stats["kv_imports"]
    p = _prompt()
    ref = _ref_tokens(eng_b, p, 8, **SEEDED)
    pre = eng_a.submit(DecodeRequest(
        tokens=np.concatenate([p, np.asarray(ref[:3], np.int32)]),
        max_new_tokens=1, export_kv=True, **SEEDED))
    pre.wait(30)
    h = dict(pre.kv_export)
    h.update(request_id="mism-1", **dict(SEEDED, seed=99))
    req = urlreq.Request(fe_b.url + "/fleet/import",
                         data=pack_handoff(h),
                         headers={"Content-Type":
                                  "application/octet-stream"})
    with urlreq.urlopen(req, timeout=30) as resp:
        assert resp.status == 200
    got = HttpClient(fe_b.url).generate(
        p, max_new_tokens=8, resume_from=ref[:4],
        request_id="mism-1", **SEEDED)
    assert [int(t) for t in got] == ref
    # re-prefilled, no adoption
    assert eng_b.stats["kv_imports"] == imports_before


def test_fleet_import_rejects_corrupt_blob(lm, pair):
    srv, fe = pair
    rs = np.random.RandomState(0)
    blob = pack_handoff({
        "tokens": [3, 4, 5], "first_token": 6, "first_logp": -0.5,
        "request_id": "bad-1",
        "k": rs.randn(2, 2, 2, 4, 3).astype(np.float32),
        "v": rs.randn(2, 2, 2, 4, 3).astype(np.float32)})
    req = urlreq.Request(fe.url + "/fleet/import",
                         data=b"XXXXXXXX" + blob[8:],
                         headers={"Content-Type":
                                  "application/octet-stream"})
    try:
        urlreq.urlopen(req, timeout=10)
        raise AssertionError("expected HTTP 400")
    except Exception as e:  # noqa: BLE001 — urllib HTTPError
        assert getattr(e, "code", None) == 400
    assert srv.take_parked("bad-1") is None  # rejected whole


# ---------------------------------------------------------------------------
# live drain: freeze-export-ship-evict between two real frontends


def _read_stream_until_severed(resp):
    """Collect token events until the stream ends.  ``severed`` means
    it ended WITHOUT a ``done`` verdict — the worker aborted the
    chunked body short of the terminator.  (The pool relay's ``read1``
    sees that as IncompleteRead; ``readline`` here surfaces it as a
    bare EOF because http.client's peek path swallows the exception —
    either way, no verdict is the failover trigger.)"""
    delivered, final, severed = [], None, False
    while True:
        try:
            line = resp.readline()
        except Exception:  # noqa: BLE001 — IncompleteRead: truncation
            severed = True
            break
        if not line:
            severed = final is None
            break
        ev = json.loads(line)
        if ev.get("done"):
            final = ev
            break
        if "token" in ev:
            delivered.append(int(ev["token"]))
    return delivered, final, severed


def test_drain_migrates_live_slot_and_resume_adopts(lm, drain_pair):
    """End-to-end two-phase drain, in process: a live stream on A is
    frozen+exported+shipped to B, evicted (stream aborts WITHOUT a
    terminator — the failover trigger), and the resume on B adopts the
    parked pages; the joined token sequence is byte-identical."""
    import http.client

    from bigdl_tpu.serving.http_frontend import HttpClient

    srv_a, fe_a, srv_b, fe_b = drain_pair
    eng_a = srv_a.model.decode_engine
    eng_b = srv_b.model.decode_engine
    exports_before = eng_a.stats["kv_exports"]
    imports_before = eng_b.stats["kv_imports"]
    cancelled_before = eng_a.stats["cancelled"]
    p = _prompt()
    ref = _ref_tokens(eng_b, p, 10, **SEEDED)
    assert len(ref) == 10
    conn = http.client.HTTPConnection(fe_a.host, fe_a.port,
                                      timeout=30)
    conn.request("POST", "/generate", body=json.dumps(dict(
        tokens=[int(t) for t in p], stream=True, max_new_tokens=10,
        request_id="mig-1", **SEEDED)).encode(),
        headers={"Content-Type": "application/json",
                 "Connection": "close"})
    resp = conn.getresponse()
    assert resp.status == 200
    first = [json.loads(resp.readline()) for _ in range(2)]
    assert all("token" in ev for ev in first)
    # phase 1: freeze + export + ship; the migration map returns
    # BEFORE anything is severed (what the pool records)
    out = srv_a.drain_decode([fe_b.url], evict=False)
    assert out["migrated"] == {"mig-1": fe_b.url}
    assert out["frozen"] == ["mig-1"] and out["failed"] == []
    assert eng_a.stats["kv_exports"] == exports_before + 1
    # phase 2: evict -> the victim-side stream aborts truncated
    srv_a.evict_migrated(out["frozen"])
    rest, final, severed = _read_stream_until_severed(resp)
    conn.close()
    assert severed and final is None
    delivered = [int(ev["token"]) for ev in first] + rest
    # the relay's move: resume on the adopting peer
    got = HttpClient(fe_b.url).generate(
        p, max_new_tokens=10, resume_from=delivered,
        request_id="mig-1", **SEEDED)
    assert [int(t) for t in got] == ref
    # the migrated pages were adopted — no re-prefill on B
    assert eng_b.stats["kv_imports"] == imports_before + 1
    assert eng_a.stats["cancelled"] > cancelled_before  # evicted slot


def test_drain_corrupt_handoff_degrades_to_reprefill(lm, drain_pair):
    """fleet_handoff_corrupt at the export seam: the peer rejects the
    blob whole, drain reports the failure — and the stream STILL
    completes byte-identically via re-prefill failover."""
    import http.client

    from bigdl_tpu.serving.http_frontend import HttpClient

    srv_a, fe_a, srv_b, fe_b = drain_pair
    eng_a = srv_a.model.decode_engine
    eng_b = srv_b.model.decode_engine
    imports_before = eng_b.stats["kv_imports"]
    p = _prompt()
    ref = _ref_tokens(eng_b, p, 10)
    assert len(ref) == 10
    conn = http.client.HTTPConnection(fe_a.host, fe_a.port,
                                      timeout=30)
    conn.request("POST", "/generate", body=json.dumps(dict(
        tokens=[int(t) for t in p], stream=True, max_new_tokens=10,
        request_id="cor-1")).encode(),
        headers={"Content-Type": "application/json",
                 "Connection": "close"})
    resp = conn.getresponse()
    assert resp.status == 200
    first = [json.loads(resp.readline()) for _ in range(2)]
    faults.install([faults.FaultSpec("fleet_handoff_corrupt",
                                     every=1)])
    out = srv_a.drain_decode([fe_b.url], evict=False)
    faults.clear()
    assert out["migrated"] == {} and out["failed"] == ["cor-1"]
    # nothing parked on the peer: the corrupt blob was rejected
    assert srv_b.take_parked("cor-1") is None
    srv_a.evict_migrated(out["frozen"] or ["cor-1"])
    rest, final, severed = _read_stream_until_severed(resp)
    conn.close()
    assert severed and final is None
    delivered = [int(ev["token"]) for ev in first] + rest
    got = HttpClient(fe_b.url).generate(
        p, max_new_tokens=10, resume_from=delivered,
        request_id="cor-1")
    assert [int(t) for t in got] == ref
    # recovered by re-prefill, not adoption
    assert eng_b.stats["kv_imports"] == imports_before


def test_drain_int8_to_f32_degrades_to_reprefill(lm):
    """Mixed-dtype drain (docs/quantization.md §Serving memory
    hierarchy): an int8 victim draining to an f32 peer must NOT ship
    pages the peer can't read — the peer refuses the import naming both
    dtypes, drain reports the failure, and the re-placed stream still
    completes byte-identically via re-prefill failover (int8 greedy
    token parity makes the joined stream exact)."""
    import http.client

    from bigdl_tpu.serving.http_frontend import HttpClient

    srv_a, fe_a = _serving_pair(lm, kv_dtype="int8")
    srv_b, fe_b = _serving_pair(lm)
    _slow_engine(srv_a.model.decode_engine)
    try:
        eng_b = srv_b.model.decode_engine
        imports_before = eng_b.stats["kv_imports"]
        p = _prompt()
        ref = _ref_tokens(eng_b, p, 10)
        assert len(ref) == 10
        conn = http.client.HTTPConnection(fe_a.host, fe_a.port,
                                          timeout=30)
        conn.request("POST", "/generate", body=json.dumps(dict(
            tokens=[int(t) for t in p], stream=True, max_new_tokens=10,
            request_id="dt-1")).encode(),
            headers={"Content-Type": "application/json",
                     "Connection": "close"})
        resp = conn.getresponse()
        assert resp.status == 200
        first = [json.loads(resp.readline()) for _ in range(2)]
        out = srv_a.drain_decode([fe_b.url], evict=False)
        # the peer refused the int8 pages whole: failed, nothing parked
        assert out["migrated"] == {} and out["failed"] == ["dt-1"]
        assert srv_b.take_parked("dt-1") is None
        srv_a.evict_migrated(out["frozen"] or ["dt-1"])
        rest, final, severed = _read_stream_until_severed(resp)
        conn.close()
        assert severed and final is None
        delivered = [int(ev["token"]) for ev in first] + rest
        got = HttpClient(fe_b.url).generate(
            p, max_new_tokens=10, resume_from=delivered,
            request_id="dt-1")
        assert [int(t) for t in got] == ref
        # recovered by re-prefill on the f32 peer, never an adoption
        assert eng_b.stats["kv_imports"] == imports_before
    finally:
        fe_a.stop()
        fe_b.stop()
        srv_a.stop()
        srv_b.stop()


def test_mixed_dtype_parked_handoff_not_adopted(lm):
    """Defense in depth behind the import gate: a parked handoff whose
    page dtype contradicts the engine's is skipped at adoption time —
    the resume re-prefills instead of submitting pages the engine would
    reject."""
    from bigdl_tpu.serving.http_frontend import HttpClient

    eng_a = _engine(lm, kv_dtype="int8", prefix_cache_pages=0)
    srv_b, fe_b = _serving_pair(lm, prefix_cache_pages=0)
    try:
        eng_b = srv_b.model.decode_engine
        imports_before = eng_b.stats["kv_imports"]
        p = _prompt()
        ref = _ref_tokens(eng_b, p, 8)
        pre = eng_a.submit(DecodeRequest(
            tokens=np.concatenate([p, np.asarray(ref[:3], np.int32)]),
            max_new_tokens=1, export_kv=True))
        pre.wait(30)
        h = dict(pre.kv_export, request_id="dtp-1")
        assert h["kv_dtype"] == "int8"
        # park directly (bypassing the /fleet/import dtype gate)
        srv_b.park_handoff(h)
        got = HttpClient(fe_b.url).generate(
            p, max_new_tokens=8, resume_from=ref[:4],
            request_id="dtp-1")
        assert [int(t) for t in got] == ref
        assert eng_b.stats["kv_imports"] == imports_before
    finally:
        fe_b.stop()
        srv_b.stop()
        eng_a.stop()


def test_client_disconnect_frees_slot_mid_stream(lm, drain_pair):
    """A client hanging up mid-stream must free the slot + pages NOW
    (counted as a client_disconnect cancel), not decode to
    max_new_tokens against a dead socket."""
    import http.client

    srv, fe = drain_pair[0], drain_pair[1]
    eng = srv.model.decode_engine
    # slow enough that the whole budget takes seconds: the cancel
    # must land MID-generation, not after a fast run finished
    eng._test_sleep_s = 0.15
    try:
        p = _prompt()
        conn = http.client.HTTPConnection(fe.host, fe.port, timeout=30)
        conn.request("POST", "/generate", body=json.dumps(dict(
            tokens=[int(t) for t in p], stream=True,
            max_new_tokens=14, request_id="gone-1")).encode(),
            headers={"Content-Type": "application/json",
                     "Connection": "close"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert "token" in json.loads(resp.readline())
        before = eng.stats["cancelled"]
        # abrupt client death: shutdown acts on the fd NOW (a bare
        # close() would linger — resp's makefile still holds a ref),
        # and further server writes draw an RST
        import socket as _socket
        conn.sock.shutdown(_socket.SHUT_RDWR)
        conn.sock.close()
        deadline = time.time() + 10
        while time.time() < deadline:
            if eng.stats["cancelled"] > before:
                break
            time.sleep(0.05)
        assert eng.stats["cancelled"] > before
        deadline = time.time() + 5
        while time.time() < deadline:
            if srv.decode_pressure().get("free_slots") == eng.cfg.slots:
                break
            time.sleep(0.05)
        assert srv.decode_pressure().get("free_slots") == eng.cfg.slots
    finally:
        eng._test_sleep_s = 0.03


# ---------------------------------------------------------------------------
# subprocess pool chaos: SIGKILL mid-stream and scale-down drain


def _fleet_loader():
    """Worker-side factory (tests.test_fleet_chaos:_fleet_loader): the
    test_fleet.py tiny-LM worker, plus an optional decode throttle
    (``BIGDL_TPU_TEST_DECODE_SLEEP``) so a kill/drain deterministically
    lands while streams are mid-flight."""
    import os as _os
    import time as _time

    import jax
    import numpy as np

    from bigdl_tpu.nn.attention import Transformer
    from bigdl_tpu.serving.decode_engine import DecodeConfig
    from bigdl_tpu.serving.inference_model import InferenceModel

    jax.config.update("jax_threefry_partitionable", True)
    model = Transformer(vocab_size=32, hidden_size=16, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    v = model.init(jax.random.PRNGKey(0),
                   np.arange(6, dtype=np.int32)[None])
    im = InferenceModel(model, v, decode=DecodeConfig(
        slots=4, page_size=4, pages_per_slot=4, prompt_chunk=4,
        max_new_tokens=16, eos_id=1, prefill_batch=2,
        prefix_cache_pages=8))
    eng = im.decode_engine
    eng.warmup()
    sleep_s = float(_os.environ.get("BIGDL_TPU_TEST_DECODE_SLEEP",
                                    "0") or 0)
    if sleep_s > 0:
        orig = eng._decode_step

        def _slow_step():
            _time.sleep(sleep_s)
            return orig()

        eng._decode_step = _slow_step
    return im


def _chaos_reqs(lm, n=6, max_new=10):
    """n streaming requests (half greedy, half seeded) with their local
    static references — prompts/seeds pinned so every reference runs
    the full max_new (no early EOS: a finished stream cannot fail
    over, and parity against a truncated reference is vacuous)."""
    ref_eng = _engine(lm, max_new_tokens=16)
    rs = np.random.RandomState(17)
    reqs = []
    tries = 0
    while len(reqs) < n and tries < 100:
        tries += 1
        p = np.asarray(rs.randint(2, 32, size=4), np.int32)
        if len(reqs) % 2 == 0:
            kw = dict(temperature=0.0, top_k=0, top_p=1.0, seed=0)
        else:
            kw = dict(temperature=0.8, top_k=8, top_p=0.9,
                      seed=int(rs.randint(0, 2 ** 31 - 1)))
        ref = _ref_tokens(ref_eng, p, max_new, **kw)
        if len(ref) < max_new:
            continue  # early EOS: not a useful chaos stream
        reqs.append({"rid": f"chaos-{len(reqs)}", "ref": ref,
                     "mid": threading.Event(),
                     "payload": dict(tokens=[int(t) for t in p],
                                     stream=True, max_new_tokens=max_new,
                                     **kw)})
    ref_eng.stop()
    assert len(reqs) == n
    return reqs


def _stream_through_pool(pool, req, results, errors):
    import http.client

    conn = http.client.HTTPConnection(pool.host, pool.port, timeout=120)
    try:
        conn.request("POST", "/generate",
                     body=json.dumps(req["payload"]).encode(),
                     headers={"Content-Type": "application/json",
                              "X-Request-Id": req["rid"],
                              "Connection": "close"})
        resp = conn.getresponse()
        if resp.status != 200:
            errors.append((req["rid"], f"HTTP {resp.status}"))
            return
        toks, final = [], None
        while True:
            line = resp.readline()
            if not line:
                break
            ev = json.loads(line)
            if ev.get("done"):
                final = ev
                break
            if "token" in ev:
                toks.append(int(ev["token"]))
                if len(toks) == 2:
                    req["mid"].set()
        if final is None:
            errors.append((req["rid"], "truncated stream"))
        elif "error" in final:
            errors.append((req["rid"], str(final["error"])))
        else:
            results[req["rid"]] = ([int(t) for t in final["tokens"]],
                                   toks)
    except Exception as e:  # noqa: BLE001 — a failed stream IS the bug
        errors.append((req["rid"], repr(e)))
    finally:
        req["mid"].set()
        conn.close()


def _pool_env():
    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    pythonpath = os.pathsep.join(
        p for p in [repo_root, os.environ.get("PYTHONPATH")] if p)
    return {"PYTHONPATH": pythonpath, "JAX_PLATFORMS": "cpu",
            "BIGDL_TPU_TEST_DECODE_SLEEP": "0.05"}


def _run_chaos_streams(pool, reqs):
    results, errors = {}, []
    threads = [threading.Thread(target=_stream_through_pool,
                                args=(pool, r, results, errors))
               for r in reqs]
    for t in threads:
        t.start()
    for r in reqs:
        assert r["mid"].wait(60), f"{r['rid']} never got 2 tokens"
    return threads, results, errors


def _join_and_check_parity(threads, reqs, results, errors):
    for t in threads:
        t.join(timeout=180)
    assert not errors, errors
    for r in reqs:
        final, streamed = results[r["rid"]]
        assert final == r["ref"], \
            f"{r['rid']}: {final} != {r['ref']}"
        # the relay's dedup means streamed events == final, in order
        assert streamed == r["ref"]


@pytest.mark.slow
def test_fleet_pool_failover_on_worker_kill(lm):
    """The chaos acceptance run, in miniature: SIGKILL a decode worker
    with >=4 streams mid-flight; every stream must finish byte-
    identical to its no-fault reference (greedy AND seeded), failovers
    counted, fleet_failover flight events recorded, and the federated
    /metrics scrape exposing the canonical counters."""
    from bigdl_tpu.obs import flight
    from bigdl_tpu.serving.pool import ServingPool

    pool = ServingPool("tests.test_fleet_chaos:_fleet_loader", workers=2,
                       batch_size=8, worker_env=_pool_env(),
                       roles=["both", "both"], supervise_interval_s=0.3,
                       predict_timeout=60.0, fleet_health_max_age_s=0.0)
    pool.start()
    try:
        reqs = _chaos_reqs(lm, n=6)
        threads, results, errors = _run_chaos_streams(pool, reqs)
        # pick a victim that actually holds live streams
        with urlreq.urlopen(pool.url + "/health", timeout=10) as r:
            h = json.loads(r.read())
        victim_name = next(
            w["name"] for w in h["workers"]
            if w.get("decode", {}).get("generate_inflight", 0) >= 1)
        victim = next(w for w in pool.worker_list()
                      if w.name == victim_name)
        victim.proc.kill()  # SIGKILL: no drain, no goodbye
        _join_and_check_parity(threads, reqs, results, errors)
        assert pool.stats["fleet_failovers"] >= 1
        assert pool.stats["fleet_resumed_tokens"] >= 1
        assert pool.stats["fleet_orphans"] == 0
        evs = flight.global_recorder().snapshot()
        assert any(e["kind"] == "fleet_failover" for e in evs)
        with urlreq.urlopen(pool.url + "/metrics", timeout=10) as r:
            scrape = r.read().decode()
        assert "serving_fleet_failovers" in scrape
        assert "serving_fleet_recovery_s" in scrape
    finally:
        pool.stop()


@pytest.mark.slow
def test_fleet_pool_scale_down_drains_live_streams(lm):
    """Scale-down with live streams: the victim's slots migrate to the
    survivor BEFORE its streams abort, the relay resumes each on the
    adopting peer, and no client loses a token — zero dropped, byte
    parity, migrations counted."""
    from bigdl_tpu.serving.pool import ServingPool

    pool = ServingPool("tests.test_fleet_chaos:_fleet_loader", workers=2,
                       batch_size=8, worker_env=_pool_env(),
                       roles=["both", "both"], supervise_interval_s=0.3,
                       predict_timeout=60.0, fleet_health_max_age_s=0.0,
                       min_workers=1, autoscale_interval_s=600.0)
    pool.start()
    try:
        reqs = _chaos_reqs(lm, n=6)
        threads, results, errors = _run_chaos_streams(pool, reqs)
        # _scale_down picks the NEWEST healthy worker; rotate a worker
        # that holds live streams into that position so the drain has
        # real state to migrate
        with urlreq.urlopen(pool.url + "/health", timeout=10) as r:
            h = json.loads(r.read())
        victim_name = next(
            w["name"] for w in h["workers"]
            if w.get("decode", {}).get("generate_inflight", 0) >= 1)
        with pool._workers_lock:
            pool.workers.sort(key=lambda w: w.name == victim_name)
        pool._scale_down(pool.pool_pressure())
        _join_and_check_parity(threads, reqs, results, errors)
        assert pool.stats["scale_down"] == 1
        assert len(pool.worker_list()) == 1
        assert pool.stats["fleet_migrations"] >= 1
        assert pool.stats["fleet_orphans"] == 0
        # every migrated slot was claimed by its resume
        assert pool._migrated == {}
    finally:
        pool.stop()
