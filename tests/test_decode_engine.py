"""Token-level continuous batching — paged KV-cache decode engine
(docs/serving.md §Autoregressive decode).

Tier-1 specs: continuous-vs-static decode TOKEN PARITY (byte-identical,
greedy AND seeded-sample, including requests inserted mid-flight),
mid-flight insertion/eviction invariants (no page aliasing after slot
reuse, pool accounting restored), the zero-recompile mixed
prompt/generation-length sweep under the PR 6 sentinel, streaming chunk
framing round-trip over the HTTP frontend, the
prefill-never-stalls-decode scheduling spec, per-token deadline
enforcement (an expired streaming request frees its slot immediately,
counted per tenant), the paged single-query flash kernel's parity with
the gathered-jnp path, and the ``serving.decode.*`` metric surface.
"""

import json
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import Transformer
from bigdl_tpu.serving.decode_engine import (DecodeConfig, DecodeEngine,
                                             DecodeRequest, LMAdapter,
                                             Seq2SeqAdapter)

BOS, EOS = 0, 1


@pytest.fixture(scope="module")
def lm():
    model = Transformer(vocab_size=32, hidden_size=16, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    v = model.init(jax.random.PRNGKey(0),
                   np.arange(6, dtype=np.int32)[None])
    return model, v["params"]


def _lm_engine(lm, **over):
    model, params = lm
    kw = dict(slots=4, page_size=4, pages_per_slot=4, prompt_chunk=4,
              max_new_tokens=8, eos_id=EOS, prefill_batch=2)
    kw.update(over)
    cfg = DecodeConfig(**kw)
    return DecodeEngine(LMAdapter(model, params, cap=cfg.cap), cfg)


def _prompts(ns=(3, 5, 9, 2, 7, 11), seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(2, 32, (n,)).astype(np.int32) for n in ns]


def _requests(prompts, temperature=0.0, **kw):
    return [DecodeRequest(tokens=p, temperature=temperature, seed=100 + i,
                          **kw) for i, p in enumerate(prompts)]


def _run(engine, reqs, stagger_at=None):
    split = stagger_at if stagger_at is not None else len(reqs)
    for r in reqs[:split]:
        engine.submit(r)
    if split < len(reqs):
        time.sleep(0.1)
        for r in reqs[split:]:
            engine.submit(r)
    return [r.wait(timeout=120) for r in reqs]


# ---------------------------------------------------------------------------
# continuous vs static whole-sequence parity
# ---------------------------------------------------------------------------

class TestContinuousStaticParity:
    def test_greedy_byte_identical(self, lm):
        eng = _lm_engine(lm)
        try:
            static = eng.static_generate(_requests(_prompts()))
            res = _run(eng, _requests(_prompts()))
            for a, b in zip(res, static):
                assert a.tokens.tobytes() == b.tokens.tobytes()
                assert np.float32(a.logp) == np.float32(b.logp)
                assert a.finish_reason == b.finish_reason
        finally:
            eng.stop()

    def test_seeded_sample_byte_identical(self, lm):
        """Temperature + top-k + top-p sampling: the per-request
        fold_in(key, position) stream makes the draw independent of
        batch composition — continuous == one-scan to the byte."""
        eng = _lm_engine(lm)
        kw = dict(temperature=1.3, top_k=5, top_p=0.9)
        try:
            static = eng.static_generate(_requests(_prompts(), **kw))
            res = _run(eng, _requests(_prompts(), **kw))
            for a, b in zip(res, static):
                assert a.tokens.tobytes() == b.tokens.tobytes()
                assert np.float32(a.logp) == np.float32(b.logp)
        finally:
            eng.stop()

    def test_mid_flight_insertion_parity(self, lm):
        """Requests inserted while others decode claim freed slots at
        step granularity — and still match the static reference, which
        never saw any co-scheduling at all."""
        eng = _lm_engine(lm)
        kw = dict(temperature=1.3, top_k=5, top_p=0.9)
        try:
            static = eng.static_generate(_requests(_prompts(), **kw))
            res = _run(eng, _requests(_prompts(), **kw), stagger_at=3)
            for a, b in zip(res, static):
                assert a.tokens.tobytes() == b.tokens.tobytes()
        finally:
            eng.stop()

    def test_sampling_varies_by_seed_and_position(self, lm):
        eng = _lm_engine(lm)
        try:
            p = _prompts((6,))[0]
            reqs = [DecodeRequest(tokens=p, temperature=2.0, seed=i)
                    for i in range(4)]
            res = _run(eng, reqs)
            streams = {r.tokens.tobytes() for r in res}
            assert len(streams) > 1   # different seeds draw differently
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# slot reuse / page accounting
# ---------------------------------------------------------------------------

class TestSlotAndPageInvariants:
    def test_no_page_aliasing_after_slot_reuse(self, lm):
        """Wave B lands in pages wave A dirtied; results must equal a
        FRESH engine's byte-for-byte (stale K/V is never valid)."""
        wave_a = _requests(_prompts((4, 6, 3, 8), seed=1))
        wave_b = _requests(_prompts((7, 2, 9, 5), seed=2),
                           temperature=1.1, top_k=4)
        dirty = _lm_engine(lm)
        fresh = _lm_engine(lm)
        try:
            _run(dirty, wave_a)
            got = _run(dirty, [DecodeRequest(tokens=r.tokens,
                                             temperature=r.temperature,
                                             top_k=r.top_k, seed=r.seed)
                               for r in wave_b])
            want = _run(fresh, wave_b)
            for a, b in zip(got, want):
                assert a.tokens.tobytes() == b.tokens.tobytes()
        finally:
            dirty.stop()
            fresh.stop()

    def test_pool_accounting_restored(self, lm):
        eng = _lm_engine(lm)
        try:
            _run(eng, _requests(_prompts()))
            deadline = time.time() + 5
            while time.time() < deadline and eng.active_slots():
                time.sleep(0.01)
            assert eng.active_slots() == 0
            assert len(eng._free_pages) == eng.cfg.total_pages
            assert eng._reserved_pages == 0
            assert all(s is None for s in eng._slots)
        finally:
            eng.stop()

    def test_page_reservation_gates_admission(self, lm):
        """With a pool smaller than two worst cases, the second request
        waits for the first's mid-flight release — and both finish."""
        eng = _lm_engine(lm, num_pages=5, max_new_tokens=6)
        try:
            reqs = _requests(_prompts((9, 9), seed=3))
            res = _run(eng, reqs)
            assert all(len(r.tokens) > 0 for r in res)
        finally:
            eng.stop()

    def test_whole_batch_restart_mode_answers(self, lm):
        """continuous=False (the static baseline): gang admission, full
        scan horizon — same answers, just slower seats."""
        eng = _lm_engine(lm, continuous=False, max_new_tokens=6)
        cont = _lm_engine(lm, max_new_tokens=6)
        try:
            res = _run(eng, _requests(_prompts((3, 5, 4, 6, 2), seed=4)))
            want = _run(cont, _requests(_prompts((3, 5, 4, 6, 2),
                                                 seed=4)))
            for a, b in zip(res, want):
                assert a.tokens.tobytes() == b.tokens.tobytes()
        finally:
            eng.stop()
            cont.stop()

    def test_whole_batch_restart_honors_per_request_max_new(self, lm):
        """A wave member asking for MORE than the config default must
        not be truncated by the wave horizon (the horizon is the
        longest member's request)."""
        eng = _lm_engine(lm, continuous=False, max_new_tokens=4)
        cont = _lm_engine(lm, max_new_tokens=4)
        try:
            reqs = lambda: [DecodeRequest(
                tokens=p, temperature=0.0, seed=i, max_new_tokens=10)
                for i, p in enumerate(_prompts((3, 5), seed=6))]
            res = _run(eng, reqs())
            want = _run(cont, reqs())
            for a, b in zip(res, want):
                assert a.tokens.tobytes() == b.tokens.tobytes()
                assert len(a.tokens) > 4 or a.finish_reason == "eos"
        finally:
            eng.stop()
            cont.stop()


# ---------------------------------------------------------------------------
# zero-recompile sweep (the PR 6 closed-set discipline)
# ---------------------------------------------------------------------------

def test_mixed_length_sweep_zero_unexpected_recompiles(lm):
    from bigdl_tpu.obs.attr import recompile_sentinel
    from bigdl_tpu.optim.metrics import global_metrics

    sent = recompile_sentinel()
    eng = _lm_engine(lm, slots=4)
    m = global_metrics()
    try:
        eng.warmup()
        before = m.counter("train.unexpected_recompiles_total")
        sent.mark_steady()
        # every prompt length x generation length the geometry allows
        rs = np.random.RandomState(7)
        reqs = [DecodeRequest(
            tokens=rs.randint(2, 32, (int(rs.randint(1, 12)),)).astype(
                np.int32),
            max_new_tokens=int(rs.randint(1, 9)),
            temperature=float(rs.rand() < 0.5) * 1.2,
            seed=i) for i in range(24)]
        _run(eng, reqs, stagger_at=12)
        after = m.counter("train.unexpected_recompiles_total")
        assert after - before == 0, (
            f"{after - before} unexpected XLA recompiles during the "
            "mixed prompt/generation-length sweep")
    finally:
        sent.mark_warmup()
        eng.stop()


# ---------------------------------------------------------------------------
# scheduling: prefill never stalls decode
# ---------------------------------------------------------------------------

def test_prefill_interleaves_with_decode_steps(lm):
    """While a long prompt chunks through prefill, decode steps for the
    already-active slot keep landing BETWEEN its chunks."""
    eng = _lm_engine(lm, slots=2, pages_per_slot=4, page_size=4,
                     prompt_chunk=4, max_new_tokens=8, prefill_batch=2)
    try:
        short = DecodeRequest(tokens=np.asarray([2, 3], np.int32),
                              max_new_tokens=8, seed=0)
        eng.submit(short)
        # wait until the short request is actively decoding
        deadline = time.time() + 5
        while time.time() < deadline and not eng._active_mask.any():
            time.sleep(0.002)
        long = DecodeRequest(
            tokens=np.arange(2, 15, dtype=np.int32),   # 13 tokens: 4 chunks
            max_new_tokens=2, seed=1)
        eng.submit(long)
        long.wait(30)
        short.wait(30)
        events = list(eng.events)
        chunk_idx = [i for i, e in enumerate(events)
                     if e[0] == "prefill_chunk" and long.rid in e[1]]
        step_idx = [i for i, e in enumerate(events)
                    if e[0] == "decode_step"]
        assert len(chunk_idx) >= 3          # the prompt really chunked
        interleaved = any(
            any(a < s < b for s in step_idx)
            for a, b in zip(chunk_idx, chunk_idx[1:]))
        assert interleaved, (
            "no decode step landed between the long prompt's prefill "
            f"chunks: chunks at {chunk_idx}, steps at {step_idx[:20]}")
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# per-token deadline enforcement
# ---------------------------------------------------------------------------

def test_expired_streaming_request_frees_slot_mid_decode(lm):
    from bigdl_tpu.serving.server import DeadlineExceededError

    eng = _lm_engine(lm, slots=2, max_new_tokens=8)
    try:
        # the stream consumer is slow: the deadline passes mid-decode,
        # long before max_new_tokens would
        seen = []

        def slow_consumer(rid, tok, idx):
            seen.append(tok)
            time.sleep(0.05)

        req = DecodeRequest(tokens=np.asarray([2, 3, 4], np.int32),
                            max_new_tokens=8, seed=0,
                            deadline_t=time.time() + 0.12,
                            on_token=slow_consumer)
        eng.submit(req)
        with pytest.raises(DeadlineExceededError) as ei:
            req.wait(30)
        assert 0 < len(seen) < 8    # streamed some tokens, not all
        assert np.array_equal(
            getattr(ei.value, "partial_tokens", []), seen)
        assert eng.stats["expired"] == 1
        # the slot and its pages freed immediately
        deadline = time.time() + 5
        while time.time() < deadline and any(
                s is not None for s in eng._slots):
            time.sleep(0.01)
        assert len(eng._free_pages) == eng.cfg.total_pages
    finally:
        eng.stop()


def test_empty_prompt_rejected_at_submit(lm):
    """An empty prompt can never prefill, decode, or expire — it must
    be rejected at the door, never parked in a slot forever."""
    eng = _lm_engine(lm)
    try:
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(DecodeRequest(tokens=np.asarray([], np.int32)))
        # the engine still serves afterwards
        res = _run(eng, _requests(_prompts((3,))))
        assert len(res[0].tokens) > 0
        assert eng.active_slots() == 0
    finally:
        eng.stop()


def test_queued_expiry_at_pickup(lm):
    from bigdl_tpu.serving.server import DeadlineExceededError

    eng = _lm_engine(lm)
    try:
        req = DecodeRequest(tokens=np.asarray([2, 3], np.int32),
                            deadline_t=time.time() - 0.01, seed=0)
        eng.submit(req)
        with pytest.raises(DeadlineExceededError):
            req.wait(30)
        assert eng.stats["expired"] == 1
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# seq2seq service: engine vs one-scan reference
# ---------------------------------------------------------------------------

class TestSeq2SeqService:
    @pytest.fixture(scope="class")
    def s2s(self):
        model = Transformer(vocab_size=16, hidden_size=16, num_heads=2,
                            num_layers=1, dropout=0.0, mode="translation")
        src = np.array([[2, 5, 6, 3], [2, 3, 4, 5], [7, 8, 9, 10]],
                       np.int32)
        v = model.init(jax.random.PRNGKey(0), src, src)
        return model, v["params"], src

    @pytest.mark.parametrize("sample", [False, True])
    def test_continuous_matches_one_scan(self, s2s, sample):
        from bigdl_tpu.serving.seq2seq import Seq2SeqService

        model, params, src = s2s
        mk = lambda cont: Seq2SeqService(
            model, params, BOS, EOS, max_len=8, sample=sample,
            temperature=2.0, top_k=6, top_p=0.9, continuous=cont)
        a, b = mk(True), mk(False)
        try:
            ta, sa = a.translate(src)
            tb, sb = b.translate(src)
            assert ta.tobytes() == tb.tobytes()
            assert sa.tobytes() == sb.tobytes()
        finally:
            a.stop()
            b.stop()

    def test_warmup_covers_ctx_write_zero_recompiles(self, s2s):
        """The seq2seq engine's ctx-write program must be COMPILED by
        warmup(), not by the first admission — a cold translate after
        warmup triggers zero unexpected recompiles."""
        from bigdl_tpu.obs.attr import recompile_sentinel
        from bigdl_tpu.optim.metrics import global_metrics
        from bigdl_tpu.serving.seq2seq import Seq2SeqService

        model, params, src = s2s
        sent = recompile_sentinel()
        m = global_metrics()
        svc = Seq2SeqService(model, params, BOS, EOS, max_len=8,
                             src_buckets=(8,))
        try:
            svc.warmup()
            before = m.counter("train.unexpected_recompiles_total")
            sent.mark_steady()
            svc.translate(src)
            assert m.counter("train.unexpected_recompiles_total") \
                == before
        finally:
            sent.mark_warmup()
            svc.stop()

    def test_engine_reused_and_slots_released(self, s2s):
        from bigdl_tpu.serving.seq2seq import Seq2SeqService

        model, params, src = s2s
        svc = Seq2SeqService(model, params, BOS, EOS, max_len=8)
        try:
            t1, _ = svc.translate(src)
            t2, _ = svc.translate(src)
            assert t1.shape == t2.shape == (3, 9)
            assert t1.tobytes() == t2.tobytes()   # greedy deterministic
            assert svc.decode_engine.active_slots() == 0
        finally:
            svc.stop()


# ---------------------------------------------------------------------------
# paged single-query flash decode kernel
# ---------------------------------------------------------------------------

class TestPagedDecodeAttention:
    def _ref(self, q, kp, vp, pt, lengths):
        S, h, hd = q.shape
        nb = pt.shape[1]
        page = kp.shape[2]
        k = kp[pt].transpose(0, 2, 1, 3, 4).reshape(S, h, nb * page, hd)
        v = vp[pt].transpose(0, 2, 1, 3, 4).reshape(S, h, nb * page, hd)
        logits = jnp.einsum("shd,shkd->shk", q, k) / np.sqrt(hd)
        valid = (jnp.arange(nb * page)[None, None, :]
                 <= lengths[:, None, None])
        logits = jnp.where(valid, logits, -1e30)
        w = jax.nn.softmax(logits, -1)
        return jnp.einsum("shk,shkd->shd", w, v)

    def test_kernel_matches_gathered_reference(self):
        from bigdl_tpu.ops.flash_attention import paged_decode_attention

        rs = np.random.RandomState(0)
        S, h, page, hd, nb = 4, 4, 4, 8, 4
        P = S * nb
        q = jnp.asarray(rs.randn(S, h, hd), jnp.float32)
        kp = jnp.asarray(rs.randn(P, h, page, hd), jnp.float32)
        vp = jnp.asarray(rs.randn(P, h, page, hd), jnp.float32)
        pt = jnp.asarray(rs.permutation(P).reshape(S, nb), jnp.int32)
        lengths = jnp.asarray([0, 3, 7, 14], jnp.int32)
        for bh in (1, 2, 4):
            out = paged_decode_attention(q, kp, vp, pt, lengths,
                                         block_h=bh)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(self._ref(q, kp, vp, pt,
                                                      lengths)),
                rtol=1e-5, atol=1e-6)

    def test_bad_block_h_rejected(self):
        from bigdl_tpu.ops.flash_attention import paged_decode_attention

        q = jnp.zeros((2, 4, 8), jnp.float32)
        kp = jnp.zeros((4, 4, 4, 8), jnp.float32)
        pt = jnp.zeros((2, 1), jnp.int32)
        with pytest.raises(ValueError, match="divide"):
            paged_decode_attention(q, kp, kp, pt,
                                   jnp.zeros((2,), jnp.int32), block_h=3)

    def test_registered_in_autotuner(self):
        from bigdl_tpu.ops import autotune

        spec = autotune.REGISTRY["flash_attention_decode"]
        assert "block_h" in spec.space
        key = autotune.decode_attention_key(8, 4, 8, 32, 4, "float32")
        tiles = autotune.resolve("flash_attention_decode", key)
        assert tiles["block_h"] in (1, 2, 4, 8)

    def test_engine_flash_path_greedy_tokens_agree(self, lm):
        jnp_eng = _lm_engine(lm, use_flash_decode=False)
        fl_eng = _lm_engine(lm, use_flash_decode=True)
        try:
            a = _run(jnp_eng, _requests(_prompts((3, 5, 9))))
            b = _run(fl_eng, _requests(_prompts((3, 5, 9))))
            for x, y in zip(a, b):
                assert x.tokens.tolist() == y.tokens.tolist()
        finally:
            jnp_eng.stop()
            fl_eng.stop()


# ---------------------------------------------------------------------------
# serving surface: server routing, HTTP streaming framing, metrics
# ---------------------------------------------------------------------------

class TestServingSurface:
    @pytest.fixture(scope="class")
    def served(self, request):
        from bigdl_tpu.serving import (DecodeConfig, HttpClient,
                                       HttpFrontend, InferenceModel,
                                       ServingConfig, ServingServer)

        model = Transformer(vocab_size=32, hidden_size=16, num_heads=2,
                            num_layers=2, dropout=0.0, mode="lm")
        v = model.init(jax.random.PRNGKey(0),
                       np.arange(6, dtype=np.int32)[None])
        im = InferenceModel(model, v, decode=DecodeConfig(
            slots=4, page_size=4, pages_per_slot=4, prompt_chunk=4,
            max_new_tokens=8, eos_id=EOS))
        srv = ServingServer(im, ServingConfig(batch_size=4)).start()
        fe = HttpFrontend(srv, port=0).start()
        cl = HttpClient(fe.url, keep_alive=True)

        def fin():
            cl.close()
            fe.stop()
            srv.stop()
            im.decode_engine.stop()

        request.addfinalizer(fin)
        return im, srv, fe, cl

    def test_generate_and_stream_framing_round_trip(self, served):
        im, srv, fe, cl = served
        want = im.generate([[2, 3, 4]], temperature=0.0)[0]
        got = cl.generate([2, 3, 4], temperature=0.0)
        assert got.tolist() == want.tolist()
        events = list(cl.generate([2, 3, 4], temperature=0.0,
                                  stream=True))
        tokens = [e["token"] for e in events if "token" in e]
        final = events[-1]
        # framing: per-token events in order, indexed, and the final
        # event re-states the full sequence
        assert tokens == want.tolist()
        assert [e["index"] for e in events if "token" in e] \
            == list(range(len(tokens)))
        assert final["done"] is True
        assert final["tokens"] == want.tolist()

    def test_server_query_path_and_queue_client(self, served):
        from bigdl_tpu.serving import InputQueue, OutputQueue

        im, srv, fe, cl = served
        want = im.generate([[5, 6]], temperature=0.0)[0]
        rid = srv.enqueue_generate(np.asarray([5, 6], np.int32))
        assert np.asarray(srv.query(rid)).tolist() == want.tolist()
        iq, oq = InputQueue(srv), OutputQueue(srv)
        rid = iq.enqueue_generate(tokens=[5, 6], temperature=0.0)
        assert oq.query(rid).tolist() == want.tolist()

    def test_unknown_model_and_no_engine(self, served):
        from bigdl_tpu.serving import InferenceModel

        im, srv, fe, cl = served
        with pytest.raises(KeyError):
            srv.enqueue_generate(np.asarray([2]), model="nope")
        srv.register_model("plain", InferenceModel(
            predict_fn=lambda x: np.asarray(x)))
        try:
            with pytest.raises(TypeError, match="decode engine"):
                srv.enqueue_generate(np.asarray([2]), model="plain")
        finally:
            srv.unregister_model("plain")

    def test_submit_rejection_does_not_poison_request_id(self, served):
        """A submit-time rejection (prompt over the cache cap) must
        clean up _pending so the id stays reusable — and must surface
        as the original error, not a duplicate-id conflict."""
        im, srv, fe, cl = served
        big = np.arange(2, 2 + im.decode_engine.cfg.cap + 2,
                        dtype=np.int32)
        for _ in range(2):   # second attempt must not hit 'in flight'
            with pytest.raises(ValueError, match="cache cap"):
                srv.enqueue_generate(big, request_id="poison-probe")
        want = im.generate([[5, 6]], temperature=0.0)[0]
        rid = srv.enqueue_generate(np.asarray([5, 6], np.int32),
                                   request_id="poison-probe")
        assert np.asarray(srv.query(rid)).tolist() == want.tolist()

    def test_lazy_seq2seq_tenant_serves_generate(self, served):
        """A freshly registered Seq2SeqService (engine built lazily on
        first use) must serve generate requests immediately."""
        from bigdl_tpu.serving.seq2seq import Seq2SeqService

        im, srv, fe, cl = served
        model = Transformer(vocab_size=16, hidden_size=16, num_heads=2,
                            num_layers=1, dropout=0.0,
                            mode="translation")
        src = np.array([[2, 5, 6, 3]], np.int32)
        v = model.init(jax.random.PRNGKey(0), src, src)
        svc = Seq2SeqService(model, v["params"], BOS, EOS, max_len=8)
        srv.register_model("mt", svc)
        try:
            rid = srv.enqueue_generate(src[0], model="mt")
            out = np.asarray(srv.query(rid))
            want, _ = svc.translate(src)
            assert out.tolist() == want[0, 1:1 + len(out)].tolist()
        finally:
            srv.unregister_model("mt")
            svc.stop()

    def test_generate_stream_accepts_deadline(self, served):
        im, srv, fe, cl = served
        toks = list(im.generate_stream([2, 3], temperature=0.0,
                                       max_new_tokens=4, deadline_s=30))
        assert toks == im.generate([[2, 3]], temperature=0.0,
                                   max_new_tokens=4)[0].tolist()

    def test_tenant_expired_counter_on_deadline(self, served):
        im, srv, fe, cl = served
        from bigdl_tpu.serving.server import DeadlineExceededError

        before = srv.metrics.counter("serving.tenant.default.expired")
        rid = srv.enqueue_generate(np.asarray([2, 3], np.int32),
                                   deadline_s=-0.01)
        with pytest.raises(DeadlineExceededError):
            srv.query(rid, timeout=10)
        assert srv.metrics.counter(
            "serving.tenant.default.expired") == before + 1

    def test_decode_metrics_exported_with_help(self, served):
        from bigdl_tpu.obs.export import render_prometheus

        im, srv, fe, cl = served
        im.generate([[2, 3, 4]], temperature=0.0)
        text = render_prometheus(srv.metrics)
        for fam in ("serving_decode_tokens_total",
                    "serving_decode_ttft_s",
                    "serving_decode_slot_occupancy",
                    "serving_decode_page_utilization"):
            assert fam in text, fam
        assert "# HELP serving_decode_ttft_s" in text
