"""Decode fleet (docs/serving.md §Decode fleet): KV-aware routing,
prefill/decode handoff, and prefix-cache reuse.

The load-bearing invariant everywhere here is byte parity: fleet-routed
generation — cached-prefix attach, cross-engine (and cross-process)
prefill→decode handoff — must match ``static_generate`` token for token
and logprob for logprob, greedy AND seeded.  The cache/handoff layers
substitute identical bytes for identical work; these tests are the
proof.
"""

import json
import os
import threading
from urllib import error as _urlerr
from urllib import request as urlreq

import jax
import numpy as np
import pytest

from bigdl_tpu.nn.attention import Transformer
from bigdl_tpu.serving.decode_engine import (DecodeConfig, DecodeEngine,
                                             DecodeRequest, LMAdapter)
from bigdl_tpu.serving.fleet import (FleetRouter, PrefixCache,
                                     pack_handoff, unpack_handoff)
from bigdl_tpu.serving.fleet.handoff import HANDOFF_MAGIC, HandoffError

BOS, EOS = 0, 1


@pytest.fixture(scope="module")
def lm():
    model = Transformer(vocab_size=32, hidden_size=16, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    v = model.init(jax.random.PRNGKey(0),
                   np.arange(6, dtype=np.int32)[None])
    return model, v


def _engine(lm, **over):
    model, v = lm
    kw = dict(slots=4, page_size=4, pages_per_slot=4, prompt_chunk=4,
              max_new_tokens=8, eos_id=EOS, prefill_batch=2,
              prefix_cache_pages=8)
    kw.update(over)
    cfg = DecodeConfig(**kw)
    return DecodeEngine(LMAdapter(model, v["params"], cap=cfg.cap),
                        cfg).warmup()


def _shared_prompts():
    rs = np.random.RandomState(0)
    common = rs.randint(2, 32, size=9).tolist()
    p1 = np.asarray(common + [5, 7], np.int32)
    p2 = np.asarray(common + [9, 3, 11], np.int32)
    return p1, p2


# ---------------------------------------------------------------------------
# PrefixCache units


def test_prefix_cache_match_is_page_aligned_and_strict():
    c = PrefixCache(max_pages=8, page_size=4)
    key = list(range(2, 10))            # 8 tokens = 2 pages
    assert c.insert(key, [0, 1])
    # a longer prompt sharing the prefix matches the cached entry
    e = c.match(key + [30])
    assert e is not None and e.pages == [0, 1]
    # STRICT prefix: the exact key must not match itself — the final
    # prefill chunk (first-token selection) always runs locally
    assert c.match(key) is None
    # unrelated prompt misses
    assert c.match([31] * 12) is None
    # longest match wins over a shorter cached prefix
    assert c.insert(key[:4], [2])
    e = c.match(key + [30])
    assert e is not None and len(e.key) == 8


def test_prefix_cache_insert_validation():
    c = PrefixCache(max_pages=4, page_size=4)
    assert not c.insert([2, 3, 4], [0])           # not page-aligned
    assert not c.insert([], [])                   # empty
    assert c.insert([2, 3, 4, 5], [0])
    assert not c.insert([2, 3, 4, 5], [1])        # duplicate key
    assert c.stats()["rejected_insertions"] == 1


def test_prefix_cache_eviction_never_frees_live_pages():
    c = PrefixCache(max_pages=8, page_size=4)
    assert c.insert([2, 3, 4, 5], [0])            # e1: 1 page
    assert c.insert([6, 7, 8, 9], [1, ])          # e2: 1 page
    e1 = c.match([2, 3, 4, 5, 10])
    c.attach(e1)                                  # e1 is LIVE (refs=1)
    freed = c.evict(5)
    # only the idle entry's page comes back; the live entry survives
    assert freed == [1]
    assert c.match([2, 3, 4, 5, 10]) is e1
    # still-live entry survives even direct pressure
    assert c.evict(1) == []
    c.detach(e1)
    assert sorted(c.evict(1)) == [0]
    assert len(c) == 0 and c.pages_held == 0


def test_prefix_cache_evict_protect_shields_pending_attach():
    c = PrefixCache(max_pages=8, page_size=4)
    assert c.insert([2, 3, 4, 5], [0])
    e = c.match([2, 3, 4, 5, 9])
    # refs == 0 until the admission commits, but the pages are spoken
    # for: protect= keeps eviction's hands off
    assert c.evict(4, protect=e) == []
    assert c.match([2, 3, 4, 5, 9]) is e


def test_prefix_cache_budget_bounded_with_lru_turnover():
    c = PrefixCache(max_pages=2, page_size=4)
    assert not c.insert(list(range(2, 14)), [0, 1, 2])  # 3 pages > budget
    assert c.insert([2, 3, 4, 5], [0])
    assert c.insert([6, 7, 8, 9], [1])
    assert c.pages_held == 2
    # a third insert evicts the LRU idle entry to make the budget
    c.attach(c.match([6, 7, 8, 9, 30]))  # freshen + pin e2
    assert c.insert([10, 11, 12, 13], [2])
    assert c.pages_held == 2
    assert c.match([2, 3, 4, 5, 30]) is None  # e1 was the LRU victim
    s = c.stats()
    assert s["evictions"] == 1 and s["evicted_pages"] == 1


# ---------------------------------------------------------------------------
# FleetRouter units


def _health(role="both", slots=2, pages=10, total=16, queued=0,
            inflight=0, prefill_backlog=0, slo=1.0, alive=True):
    return {"alive": alive, "role": role, "slo_health": slo,
            "decode": {"free_slots": slots, "free_pages": pages,
                       "total_pages": total, "queued": queued,
                       "generate_inflight": inflight,
                       "prefill_backlog": prefill_backlog}}


def test_router_picks_decode_headroom():
    r = FleetRouter()
    d, p = r.route([_health(slots=0, pages=0),
                    _health(slots=3, pages=12)])
    assert (d, p) == (1, None)


def test_router_penalizes_backlog_and_slo():
    r = FleetRouter()
    # equal capacity, but worker 0 has queued generate work
    d, _ = r.route([_health(queued=4, inflight=4), _health()])
    assert d == 1
    # equal capacity, worker 1's SLO is burning
    d, _ = r.route([_health(), _health(slo=0.2)])
    assert d == 0


def test_router_skips_dead_and_prefill_workers_for_decode():
    r = FleetRouter()
    d, p = r.route([_health(alive=False), _health(role="prefill"),
                    _health(role="decode")])
    assert d == 2 and p == 1
    # a prefill-only fleet cannot decode
    assert r.route([_health(role="prefill")]) == (None, None)
    assert r.route([]) == (None, None)


def test_router_split_only_with_dedicated_prefill_role():
    r = FleetRouter()
    # no prefill-role workers: decode worker prefills locally
    d, p = r.route([_health(), _health()])
    assert d is not None and p is None
    # least-backlogged prefill worker wins
    d, p = r.route([_health(role="prefill", prefill_backlog=5),
                    _health(role="prefill", prefill_backlog=0),
                    _health(role="decode")])
    assert (d, p) == (2, 1)


def test_router_deterministic_tiebreak():
    r = FleetRouter()
    d1, _ = r.route([_health(), _health()])
    d2, _ = r.route([_health(), _health()])
    assert d1 == d2 == 0  # ties break on the lower index


# ---------------------------------------------------------------------------
# handoff wire format


def _fake_handoff():
    rs = np.random.RandomState(3)
    return {"tokens": [4, 9, 2, 7, 5], "first_token": 12,
            "first_logp": -1.25, "temperature": 0.8, "top_k": 8,
            "top_p": 0.9, "seed": 13, "request_id": "req-1",
            "k": rs.randn(2, 2, 2, 4, 3).astype(np.float32),
            "v": rs.randn(2, 2, 2, 4, 3).astype(np.float32)}


def test_handoff_roundtrip_is_exact():
    h = _fake_handoff()
    out = unpack_handoff(pack_handoff(h))
    assert out["k"].tobytes() == h["k"].tobytes()
    assert out["v"].tobytes() == h["v"].tobytes()
    assert out["tokens"].dtype == np.int32
    assert list(out["tokens"]) == h["tokens"]
    assert out["first_token"] == 12
    assert np.float32(out["first_logp"]) == np.float32(-1.25)
    # extra JSON-serializable keys ride along untouched
    assert out["request_id"] == "req-1" and out["seed"] == 13


def test_handoff_rejects_bad_payloads():
    h = _fake_handoff()
    data = pack_handoff(h)
    with pytest.raises(ValueError, match="magic"):
        unpack_handoff(b"nope" + data)
    with pytest.raises(ValueError, match="truncated"):
        unpack_handoff(data[:-8])
    with pytest.raises(ValueError, match="required"):
        pack_handoff({k: v for k, v in h.items() if k != "first_token"})
    bad = dict(h, v=h["v"][:1])
    with pytest.raises(ValueError, match="5-d page-pool shape"):
        pack_handoff(bad)


def test_handoff_kv_dtype_roundtrip():
    """The kv_dtype header rides the wire for BOTH dtypes: f32 blobs
    stay bit-identical to the pre-kv_dtype format, int8 blobs carry the
    per-(layer, page) scale tables behind the V payload and cut wire
    bytes ~4x (docs/quantization.md §Serving memory hierarchy)."""
    rs = np.random.RandomState(4)
    f32 = unpack_handoff(pack_handoff(_fake_handoff()))
    assert f32["kv_dtype"] == "float32"
    assert f32["k"].dtype == np.float32
    assert "k_scales" not in f32
    h8 = dict(_fake_handoff(), kv_dtype="int8",
              k=rs.randint(-127, 128, (2, 2, 2, 4, 3)).astype(np.int8),
              v=rs.randint(-127, 128, (2, 2, 2, 4, 3)).astype(np.int8),
              k_scales=rs.rand(2, 2).astype(np.float32),
              v_scales=rs.rand(2, 2).astype(np.float32))
    blob = pack_handoff(h8)
    f32_blob = pack_handoff(_fake_handoff())
    assert len(blob) < len(f32_blob) / 2   # int8 pages shrink the wire
    out = unpack_handoff(blob)
    assert out["kv_dtype"] == "int8" and out["k"].dtype == np.int8
    assert out["k"].tobytes() == h8["k"].tobytes()
    assert out["v"].tobytes() == h8["v"].tobytes()
    np.testing.assert_array_equal(out["k_scales"], h8["k_scales"])
    np.testing.assert_array_equal(out["v_scales"], h8["v_scales"])
    # int8 without the scale tables is unserializable, not silently f32
    with pytest.raises(ValueError, match="scale tables"):
        pack_handoff(dict(h8, k_scales=None))


def test_handoff_unknown_kv_dtype_rejected_by_name():
    """A future dtype must be rejected NAMING the dtype — never misread
    as f32 pages — and a legacy 'dtype' field that contradicts
    'kv_dtype' is a corrupt header."""
    import json as _json

    h = _fake_handoff()
    with pytest.raises(ValueError, match="fp4"):
        pack_handoff(dict(h, kv_dtype="fp4"))
    # forge the header of a valid blob to claim an unknown dtype
    data = pack_handoff(h)
    off = len(HANDOFF_MAGIC)
    hlen = int.from_bytes(data[off:off + 8], "big")
    hdr = _json.loads(data[off + 8:off + 8 + hlen].decode())

    def _reforge(hdr):
        enc = _json.dumps(hdr, sort_keys=True).encode()
        return (HANDOFF_MAGIC + len(enc).to_bytes(8, "big") + enc
                + data[off + 8 + hlen:])

    forged = _reforge(dict(hdr, kv_dtype="fp4", dtype="fp4"))
    with pytest.raises(HandoffError, match="fp4"):
        unpack_handoff(forged)
    # legacy decoders keyed on "dtype": a blob where the two fields
    # disagree must not be trusted either way
    forged = _reforge(dict(hdr, kv_dtype="int8", dtype="float32"))
    with pytest.raises(HandoffError, match="contradicts"):
        unpack_handoff(forged)


# ---------------------------------------------------------------------------
# engine-level parity: prefix-cache attach and handoff import


def test_prefix_cache_parity_greedy(lm):
    eng = _engine(lm)
    p1, p2 = _shared_prompts()
    r1 = eng.generate([p1], max_new_tokens=6)[0]          # cold: donates
    r2 = eng.generate([p2], max_new_tokens=6)[0]          # warm: attaches
    s1 = eng.static_generate([DecodeRequest(tokens=p1,
                                            max_new_tokens=6)])[0]
    s2 = eng.static_generate([DecodeRequest(tokens=p2,
                                            max_new_tokens=6)])[0]
    assert r1.tokens.tobytes() == s1.tokens.tobytes()
    assert r2.tokens.tobytes() == s2.tokens.tobytes()
    assert r1.logp == s1.logp and r2.logp == s2.logp
    st = eng._prefix_cache.stats()
    assert st["hits"] >= 1 and st["insertions"] >= 1
    eng.stop()


def test_prefix_cache_parity_seeded(lm):
    eng = _engine(lm)
    p1, p2 = _shared_prompts()
    kw = dict(max_new_tokens=6, temperature=0.8, top_k=8, top_p=0.9,
              seed=13)
    eng.generate([p1], max_new_tokens=6)                  # seed the cache
    assert eng._prefix_cache.stats()["insertions"] >= 1
    r = eng.generate([p2], **kw)[0]
    s = eng.static_generate([DecodeRequest(tokens=p2, **kw)])[0]
    assert r.tokens.tobytes() == s.tokens.tobytes()
    assert r.logp == s.logp
    assert eng._prefix_cache.stats()["hits"] >= 1
    eng.stop()


def test_prefix_cache_page_accounting_exact(lm):
    """Cache-held pages leave the free list and come back on eviction —
    free + cached must always equal the pool when the engine idles."""
    eng = _engine(lm)
    total = eng.cfg.total_pages
    p1, p2 = _shared_prompts()
    for p in (p1, p2):
        eng.generate([p], max_new_tokens=4)
    held = eng._prefix_cache.pages_held
    assert held > 0
    assert len(eng._free_pages) + held == total
    freed = eng._prefix_cache.evict(held)
    eng._free_pages.extend(freed)
    assert len(eng._free_pages) == total
    eng.stop()


def test_handoff_cross_engine_parity(lm):
    """Prefill on engine A, decode on engine B (fresh KV pool): byte-
    identical to static_generate — the invariant the physical
    prefill/decode split rests on."""
    eng_a = _engine(lm, prefix_cache_pages=0)
    eng_b = _engine(lm, prefix_cache_pages=0)
    _, p2 = _shared_prompts()
    kw = dict(temperature=0.8, top_k=8, top_p=0.9, seed=13)
    pre = eng_a.submit(DecodeRequest(tokens=p2, max_new_tokens=1,
                                     export_kv=True, **kw))
    pre.wait(30)
    assert pre.error is None and pre.kv_export is not None
    assert eng_a.stats["kv_exports"] == 1
    # the serialized channel is part of the path under test
    h = unpack_handoff(pack_handoff(pre.kv_export))
    got = eng_b.submit_prefilled(h, max_new_tokens=6).wait(30)
    ref = eng_b.static_generate([DecodeRequest(tokens=p2,
                                               max_new_tokens=6, **kw)])[0]
    assert got.tokens.tobytes() == ref.tokens.tobytes()
    assert got.logp == ref.logp
    assert eng_b.stats["kv_imports"] == 1
    eng_a.stop()
    eng_b.stop()


def test_handoff_greedy_parity_and_first_token(lm):
    eng_a = _engine(lm, prefix_cache_pages=0)
    eng_b = _engine(lm, prefix_cache_pages=0)
    p1, _ = _shared_prompts()
    pre = eng_a.submit(DecodeRequest(tokens=p1, max_new_tokens=1,
                                     export_kv=True))
    pre.wait(30)
    h = unpack_handoff(pack_handoff(pre.kv_export))
    ref = eng_b.static_generate([DecodeRequest(tokens=p1,
                                               max_new_tokens=6)])[0]
    # the first token was selected on the PREFILL engine during its
    # final chunk; the decode engine re-emits, never re-selects
    assert int(h["first_token"]) == int(ref.tokens[0])
    got = eng_b.submit_prefilled(h, max_new_tokens=6).wait(30)
    assert got.tokens.tobytes() == ref.tokens.tobytes()
    eng_a.stop()
    eng_b.stop()


def test_fleet_request_validation(lm):
    eng = _engine(lm)
    _, p2 = _shared_prompts()
    pre = eng.submit(DecodeRequest(tokens=p2, max_new_tokens=1,
                                   export_kv=True))
    pre.wait(30)
    h = pre.kv_export
    # token mismatch between handoff and request must be rejected
    # (submit_prefilled takes its tokens FROM the handoff, so the
    # mismatch can only arrive via a hand-built DecodeRequest)
    other = np.asarray(list(p2[:-1]) + [30], np.int32)
    with pytest.raises(ValueError):
        eng.submit(DecodeRequest(tokens=other, handoff=h,
                                 max_new_tokens=4))
    # K/V shaped for a different geometry must be rejected
    bad = dict(h, k=h["k"][:, :1], v=h["v"][:, :1])
    with pytest.raises(ValueError):
        eng.submit_prefilled(bad, max_new_tokens=4)
    eng.stop()


# ---------------------------------------------------------------------------
# server + frontend: backlog, /health decode block, /fleet/prefill, split


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


def test_backlog_counts_generate_inflight(lm):
    srv, fe = _serving_pair(lm)
    try:
        p1, _ = _shared_prompts()
        hold = threading.Event()
        # the first token's callback parks the engine thread: the
        # request cannot resolve until we release it, so the backlog
        # observation below is deterministic, not a race
        rid = srv.enqueue_generate(p1, max_new_tokens=4,
                                   on_token=lambda r, t, i: hold.wait(10))
        assert srv.backlog() >= 1
        h = json.loads(urlreq.urlopen(fe.url + "/health").read())
        assert h["backlog"] >= 1
        assert h["decode"]["generate_inflight"] >= 1
        hold.set()
        srv.query(rid, timeout=30)
        assert srv.backlog() == 0
    finally:
        fe.stop()
        srv.stop()


def test_health_reports_role_and_decode_pressure(lm):
    srv, fe = _serving_pair(lm)
    try:
        srv.role = "decode"
        h = json.loads(urlreq.urlopen(fe.url + "/health").read())
        assert h["role"] == "decode"
        d = h["decode"]
        for key in ("total_slots", "free_slots", "total_pages",
                    "free_pages", "prefill_backlog", "generate_inflight"):
            assert key in d, key
        assert d["free_slots"] == 4 and d["generate_inflight"] == 0
        assert "prefix_cache" in d
    finally:
        fe.stop()
        srv.stop()


def test_prefix_cache_counters_in_one_metrics_scrape(lm):
    srv, fe = _serving_pair(lm)
    try:
        p1, p2 = _shared_prompts()
        for p in (p1, p2):
            srv.query(srv.enqueue_generate(p, max_new_tokens=4),
                      timeout=30)
        scrape = urlreq.urlopen(fe.url + "/metrics").read().decode()
        # hit AND miss counters land in the same exposition
        assert "serving_fleet_prefix_cache_hits" in scrape
        assert "serving_fleet_prefix_cache_misses" in scrape
        hits = [ln for ln in scrape.splitlines()
                if ln.startswith("serving_fleet_prefix_cache_hits")
                and not ln.startswith("#")]
        assert hits and float(hits[0].split()[-1]) >= 1
    finally:
        fe.stop()
        srv.stop()


def test_fleet_prefill_endpoint_and_split_parity(lm):
    """Two in-process workers — role=prefill and role=decode — split a
    request over HTTP exactly as the pool proxy arranges it (the
    X-Prefill-Url header), byte-identical to a local static decode."""
    srv_p, fe_p = _serving_pair(lm)
    srv_d, fe_d = _serving_pair(lm)
    try:
        srv_p.role, srv_d.role = "prefill", "decode"
        _, p2 = _shared_prompts()
        prompt = [int(t) for t in p2]
        kw = dict(max_new_tokens=8, temperature=0.7, top_k=8, top_p=0.9,
                  seed=21)
        eng = srv_d.model.decode_engine
        ref = eng.static_generate(
            [DecodeRequest(tokens=np.asarray(prompt, np.int32), **kw)])[0]
        body = json.dumps(dict(tokens=prompt, stream=False, **kw)).encode()
        req = urlreq.Request(fe_d.url + "/generate", data=body, headers={
            "Content-Type": "application/json", "X-Prefill-Url": fe_p.url})
        out = json.loads(urlreq.urlopen(req, timeout=30).read())
        got = np.asarray(out["tokens"], np.int32)
        assert got.tobytes() == ref.tokens.tobytes()
        # the prefill ran on the OTHER worker and shipped its pages
        assert srv_p.model.decode_engine.stats["kv_exports"] == 1
        assert eng.stats["kv_imports"] == 1
        # /fleet/prefill error mapping: unknown model is the caller's 404
        try:
            urlreq.urlopen(urlreq.Request(
                fe_p.url + "/fleet/prefill",
                data=json.dumps({"tokens": prompt,
                                 "model": "nope"}).encode(),
                headers={"Content-Type": "application/json"}), timeout=10)
            raise AssertionError("expected HTTP 404")
        except _urlerr.HTTPError as e:
            assert e.code == 404
    finally:
        fe_p.stop()
        fe_d.stop()
        srv_p.stop()
        srv_d.stop()


def test_split_streaming_parity(lm):
    """X-Prefill-Url + stream=true: every token event and the final
    verdict match the local static reference byte for byte."""
    import http.client

    srv_p, fe_p = _serving_pair(lm)
    srv_d, fe_d = _serving_pair(lm)
    try:
        srv_p.role, srv_d.role = "prefill", "decode"
        _, p2 = _shared_prompts()
        prompt = [int(t) for t in p2]
        kw = dict(max_new_tokens=8, temperature=0.7, top_k=8, top_p=0.9,
                  seed=21)
        ref = srv_d.model.decode_engine.static_generate(
            [DecodeRequest(tokens=np.asarray(prompt, np.int32), **kw)])[0]
        conn = http.client.HTTPConnection(fe_d.host, fe_d.port, timeout=30)
        conn.request(
            "POST", "/generate",
            body=json.dumps(dict(tokens=prompt, stream=True, **kw)).encode(),
            headers={"Content-Type": "application/json",
                     "X-Prefill-Url": fe_p.url, "Connection": "close"})
        resp = conn.getresponse()
        assert resp.status == 200
        toks, final = [], None
        while True:
            line = resp.readline()
            if not line:
                break
            ev = json.loads(line)
            if ev.get("done"):
                final = ev
                break
            toks.append(ev["token"])
        conn.close()
        assert final is not None and "error" not in final
        assert np.asarray(final["tokens"],
                          np.int32).tobytes() == ref.tokens.tobytes()
        assert toks == [int(t) for t in ref.tokens]
        assert srv_p.model.decode_engine.stats["kv_exports"] == 1
    finally:
        fe_p.stop()
        fe_d.stop()
        srv_p.stop()
        srv_d.stop()


# ---------------------------------------------------------------------------
# whole-fleet subprocess test: pool proxy + roles + streaming relay


def _fleet_loader():
    """Worker-side factory (resolved as tests.test_fleet:_fleet_loader in
    the worker interpreter): a tiny LM with a fleet-enabled decode
    engine, weights deterministic so every worker — and the in-test
    reference engine — holds identical parameters."""
    import jax
    import numpy as np

    from bigdl_tpu.nn.attention import Transformer
    from bigdl_tpu.serving.decode_engine import DecodeConfig
    from bigdl_tpu.serving.inference_model import InferenceModel

    # conftest.py flips this in the TEST process; the worker must sample
    # from the same threefry variant or seeded parity is vacuously false
    jax.config.update("jax_threefry_partitionable", True)
    model = Transformer(vocab_size=32, hidden_size=16, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    v = model.init(jax.random.PRNGKey(0),
                   np.arange(6, dtype=np.int32)[None])
    im = InferenceModel(model, v, decode=DecodeConfig(
        slots=4, page_size=4, pages_per_slot=4, prompt_chunk=4,
        max_new_tokens=16, eos_id=1, prefill_batch=2,
        prefix_cache_pages=8))
    im.decode_engine.warmup()
    return im


@pytest.mark.slow
def test_fleet_pool_split_streaming_parity(lm):
    """End to end over real worker processes: ServingPool with a
    dedicated prefill worker and a decode worker, a streaming /generate
    through the proxy relay, byte parity against a local reference
    engine built from the same seed."""
    import http.client

    from bigdl_tpu.serving.pool import ServingPool

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pythonpath = os.pathsep.join(
        p for p in [repo_root, os.environ.get("PYTHONPATH")] if p)
    env = {"PYTHONPATH": pythonpath, "JAX_PLATFORMS": "cpu"}
    pool = ServingPool("tests.test_fleet:_fleet_loader", workers=2,
                       batch_size=8, worker_env=env,
                       roles=["prefill", "decode"],
                       supervise_interval_s=0.3)
    pool.start()
    try:
        ref_eng = _engine(lm, max_new_tokens=16, prefix_cache_pages=8)
        _, p2 = _shared_prompts()
        prompt = [int(t) for t in p2]
        kw = dict(max_new_tokens=8, temperature=0.8, top_k=8, top_p=0.9,
                  seed=5)
        ref = ref_eng.static_generate(
            [DecodeRequest(tokens=np.asarray(prompt, np.int32), **kw)])[0]
        ref_eng.stop()

        conn = http.client.HTTPConnection(pool.host, pool.port, timeout=60)
        conn.request(
            "POST", "/generate",
            body=json.dumps(dict(tokens=prompt, stream=True, **kw)).encode(),
            headers={"Content-Type": "application/json",
                     "Connection": "close"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("X-Request-Id")
        toks, final = [], None
        while True:
            line = resp.readline()
            if not line:
                break
            ev = json.loads(line)
            if ev.get("done"):
                final = ev
                break
            toks.append(ev["token"])
        conn.close()
        assert final is not None and "error" not in final, final
        assert np.asarray(final["tokens"],
                          np.int32).tobytes() == ref.tokens.tobytes()
        assert toks == [int(t) for t in ref.tokens]

        # the proxy actually split the request and relayed the stream
        assert pool.stats["stream_relays"] >= 1
        assert pool.stats["fleet_split"] >= 1
        with urlreq.urlopen(pool.url + "/health", timeout=10) as r:
            h = json.loads(r.read())
        roles = sorted(w.get("role") for w in h["workers"])
        assert roles == ["decode", "prefill"]
        # the decode worker imported the prefill worker's pages
        decode_w = next(w for w in h["workers"]
                        if w.get("role") == "decode")
        assert decode_w["decode"]["kv_imports"] >= 1
    finally:
        pool.stop()
