"""Speculative decoding — weight-shared block-sparse draft + single-call
verify (docs/serving.md §Speculative decoding).

Tier-1 specs: spec-on vs spec-off BYTE PARITY (greedy and seeded sample,
including requests admitted mid-flight — the acceptance rule emits only
target selections, so speculation must be invisible in the output), the
dense-twin (sparsity=0.0) acceptance rate pinned at exactly 1.0, the
zero-recompile mixed sweep with the draft/verify/draft-prefill programs
inside warmup()'s closed bucket set, the spec x ``kv_dtype="int8"``
token-parity budget, draft-side pages freed together with target pages
on cancel/disconnect (the page-leak regression spec), ``decode_pressure``
honesty under draft pages, the multi-query verify kernel's parity with
the gathered-jnp reference, and the ``serving.decode.spec_*`` metric
surface.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import Transformer
from bigdl_tpu.serving.decode_engine import (DecodeConfig, DecodeEngine,
                                             DecodeRequest, LMAdapter,
                                             SpecConfig)

BOS, EOS = 0, 1

SAMPLE_KW = dict(temperature=1.3, top_k=5, top_p=0.9)


@pytest.fixture(scope="module")
def lm():
    model = Transformer(vocab_size=32, hidden_size=16, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    v = model.init(jax.random.PRNGKey(0),
                   np.arange(6, dtype=np.int32)[None])
    return model, v["params"]


def _engine(lm, spec=None, **over):
    model, params = lm
    kw = dict(slots=4, page_size=4, pages_per_slot=4, prompt_chunk=4,
              max_new_tokens=8, eos_id=EOS, prefill_batch=2)
    kw.update(over)
    cfg = DecodeConfig(speculative=spec, **kw)
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


def _assert_same(got, want):
    for a, b in zip(got, want):
        assert a.tokens.tobytes() == b.tokens.tobytes()
        assert np.float32(a.logp) == np.float32(b.logp)
        assert a.finish_reason == b.finish_reason


# ---------------------------------------------------------------------------
# spec-on vs spec-off byte parity: speculation must be invisible
# ---------------------------------------------------------------------------

class TestSpecParity:
    def test_greedy_dense_twin_byte_identical_full_acceptance(self, lm):
        """sparsity=0.0 drafts with a bit-identical twin: every drafted
        token must be accepted (rejected == 0 — drafts past an
        eos/length finish are unadjudicated, not rejected) and the
        output must match the spec-off engine to the byte."""
        off = _engine(lm)
        try:
            want = _run(off, _requests(_prompts()))
        finally:
            off.stop()
        on = _engine(lm, spec=SpecConfig(k=3, sparsity=0.0))
        try:
            got = _run(on, _requests(_prompts()))
            _assert_same(got, want)
            st = on.stats
            assert st["spec_drafted"] > 0
            assert st["spec_accepted"] > 0
            assert st["spec_rejected"] == 0, (
                "a dense twin's drafts disagreed with its own target")
        finally:
            on.stop()

    def test_greedy_sparse_draft_byte_identical(self, lm):
        """A REAL sparse draft mispredicts — and the output still
        matches byte-for-byte, because emitted tokens are always the
        verify call's target selections; the draft only gates how many
        land per iteration."""
        off = _engine(lm)
        try:
            want = _run(off, _requests(_prompts()))
        finally:
            off.stop()
        on = _engine(lm, spec=SpecConfig(k=3, sparsity=0.5))
        try:
            got = _run(on, _requests(_prompts()))
            _assert_same(got, want)
        finally:
            on.stop()

    def test_seeded_sample_byte_identical(self, lm):
        """temperature>0: draft and verify share the counter-based
        fold_in(key, position) Gumbel streams, so the accepted stream
        (correction and resampled tail included) is the spec-off
        sampled stream to the byte."""
        off = _engine(lm)
        try:
            want = _run(off, _requests(_prompts(), **SAMPLE_KW))
        finally:
            off.stop()
        on = _engine(lm, spec=SpecConfig(k=3, sparsity=0.5))
        try:
            got = _run(on, _requests(_prompts(), **SAMPLE_KW))
            _assert_same(got, want)
            st = on.stats
            assert st["spec_accepted"] > 0, (
                "shared-Gumbel coupling broke: a 0.5-sparse draft "
                "should still agree sometimes")
        finally:
            on.stop()

    def test_chunk_verify_seeded_routes_to_scan_parity(self, lm):
        """Regression: the chunk verify's last-ulp logit drift is
        harmless under greedy argmax but flips top-k/top-p threshold
        masks (they are discontinuous in the logits), so a sampled
        iteration under verify_impl="chunk" must route to the scan
        tracing — byte parity holds for seeded sampling even on a
        chunk-configured engine, including after a prior greedy round
        reshuffled slot state."""
        off = _engine(lm)
        try:
            want_g = _run(off, _requests(_prompts()))
            want_s = _run(off, _requests(_prompts(), **SAMPLE_KW))
        finally:
            off.stop()
        on = _engine(lm, spec=SpecConfig(k=3, sparsity=0.5,
                                         verify_impl="chunk"))
        try:
            # greedy rides the chunk tracing: tokens exact, logp
            # allclose (the chunk contract)
            got_g = _run(on, _requests(_prompts()))
            for a, b in zip(got_g, want_g):
                assert a.tokens.tobytes() == b.tokens.tobytes()
                assert np.allclose(a.logp, b.logp, rtol=2e-5, atol=2e-5)
            # sampled routes to scan: byte parity, logp included
            _assert_same(_run(on, _requests(_prompts(), **SAMPLE_KW)),
                         want_s)
        finally:
            on.stop()

    def test_mid_flight_admission_parity(self, lm):
        """Requests admitted while earlier ones are mid-speculation
        join the next draft/verify iteration — and still match the
        static target-only reference byte-for-byte."""
        on = _engine(lm, spec=SpecConfig(k=3, sparsity=0.5))
        try:
            want = on.static_generate(_requests(_prompts(), **SAMPLE_KW))
            got = _run(on, _requests(_prompts(), **SAMPLE_KW),
                       stagger_at=3)
            _assert_same(got, want)
        finally:
            on.stop()


# ---------------------------------------------------------------------------
# zero-recompile sweep: draft + verify join the closed bucket set
# ---------------------------------------------------------------------------

def test_spec_sweep_zero_unexpected_recompiles(lm):
    from bigdl_tpu.obs.attr import recompile_sentinel
    from bigdl_tpu.optim.metrics import global_metrics

    sent = recompile_sentinel()
    eng = _engine(lm, spec=SpecConfig(k=3, sparsity=0.5))
    m = global_metrics()
    try:
        eng.warmup()
        before = m.counter("train.unexpected_recompiles_total")
        sent.mark_steady()
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
            "mixed sweep with speculation enabled")
    finally:
        sent.mark_warmup()
        eng.stop()


# ---------------------------------------------------------------------------
# spec x int8 KV pages: the token-parity budget
# ---------------------------------------------------------------------------

def test_spec_int8_token_parity_budget(lm):
    """int8 pages can't promise byte parity under speculation: a
    mismatch has already requantize-written the rejected tokens' K/V,
    and the monotone per-page scale floor remembers their magnitude.
    The budget: identical token streams, logp drift inside the int8
    bound."""
    off = _engine(lm, kv_dtype="int8")
    try:
        want = _run(off, _requests(_prompts()))
    finally:
        off.stop()
    on = _engine(lm, kv_dtype="int8", spec=SpecConfig(k=3, sparsity=0.5))
    try:
        got = _run(on, _requests(_prompts()))
        for a, b in zip(got, want):
            assert a.tokens.tolist() == b.tokens.tolist(), (
                "speculation changed the int8 greedy token stream")
            assert abs(a.logp - b.logp) < 0.15, (
                f"logp drift {abs(a.logp - b.logp):.4f} blows the int8 "
                "budget under speculation")
    finally:
        on.stop()


# ---------------------------------------------------------------------------
# acceptance accounting + the serving.decode.spec_* metric surface
# ---------------------------------------------------------------------------

def test_acceptance_accounting_and_metric_surface(lm):
    from bigdl_tpu.obs.export import DEFAULT_HELP, render_prometheus

    eng = _engine(lm, spec=SpecConfig(k=3, sparsity=0.5))
    try:
        _run(eng, _requests(_prompts()))
        st = eng.stats
        assert st["spec_drafted"] > 0
        # adjudicated tokens never exceed drafted; the remainder is
        # wasted work from eos/length truncation, not rejection
        assert st["spec_accepted"] + st["spec_rejected"] \
            <= st["spec_drafted"]
        # every accepted draft token was emitted (corrections and bonus
        # tokens add more)
        assert st["tokens"] >= st["spec_accepted"]
        text = render_prometheus(eng.metrics)
        for fam in ("serving_decode_spec_drafted_tokens",
                    "serving_decode_spec_accepted_tokens",
                    "serving_decode_spec_rejected_tokens",
                    "serving_decode_spec_accept_rate",
                    "serving_decode_spec_draft_step_s",
                    "serving_decode_spec_verify_step_s"):
            assert fam in text, fam
        for name in ("serving.decode.spec_accept_rate",
                     "serving.decode.spec_drafted_tokens",
                     "serving.decode.spec_accepted_tokens",
                     "serving.decode.spec_rejected_tokens",
                     "serving.decode.spec_draft_step_s",
                     "serving.decode.spec_verify_step_s"):
            assert name in DEFAULT_HELP and DEFAULT_HELP[name], name
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# draft pages free with target pages (the cancel/disconnect regression)
# ---------------------------------------------------------------------------

def test_cancel_frees_draft_pages_with_target_pages(lm):
    """The draft pool is indexed by the SAME page table as the target
    pool — cancel/disconnect releases ONE page list covering both, so
    a mid-stream disconnect under speculation must restore the exact
    free-page count (the PR 17 client-disconnect reclaim, now with
    draft pages in the slot)."""
    eng = _engine(lm, spec=SpecConfig(k=3, sparsity=0.5))
    try:
        eng.warmup()
        total = eng.cfg.total_pages
        assert len(eng._free_pages) == total
        # throttle the loop so the cancel lands MID-generation (the
        # test_fleet_chaos idiom — wrapper runs inside _iter_lock)
        orig_step = eng._decode_step
        eng._decode_step = lambda: (time.sleep(0.15), orig_step())[1]
        req = DecodeRequest(tokens=_prompts()[2], max_new_tokens=200,
                            on_token=lambda rid, tok, idx: None)
        eng.submit(req)
        deadline = time.time() + 30
        while not any(s is not None for s in eng._slots):
            assert time.time() < deadline, "request never took a slot"
            time.sleep(0.01)
        # pages held mid-stream: taken off the free list or reserved
        assert (total - len(eng._free_pages)) + eng._reserved_pages > 0
        eng.cancel(req.rid, reason="client_disconnect")
        eng._decode_step = orig_step
        deadline = time.time() + 30
        while len(eng._free_pages) != total or eng._reserved_pages:
            assert time.time() < deadline, (
                f"draft/target page leak after cancel: "
                f"{total - len(eng._free_pages)} pages out, "
                f"{eng._reserved_pages} reserved")
            time.sleep(0.01)
        # the freed pages (stale draft K/V included) must be safely
        # reusable: a fresh wave through the same slots still matches
        off = _engine(lm)
        try:
            want = _run(off, _requests(_prompts()))
        finally:
            off.stop()
        got = _run(eng, _requests(_prompts()))
        _assert_same(got, want)
    finally:
        eng.stop()


def test_per_token_expiry_frees_draft_pages(lm):
    """A deadline expiry mid-decode rides the same release path: no
    draft-page leak, accounting restored."""
    eng = _engine(lm, spec=SpecConfig(k=3, sparsity=0.5))
    try:
        total = eng.cfg.total_pages
        req = DecodeRequest(tokens=_prompts()[4], max_new_tokens=200,
                            deadline_t=time.time() + 0.2)
        eng.submit(req)
        with pytest.raises(Exception):
            req.wait(timeout=60)
        deadline = time.time() + 30
        while len(eng._free_pages) != total or eng._reserved_pages:
            assert time.time() < deadline, "page leak after expiry"
            time.sleep(0.01)
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# pressure honesty + config validation
# ---------------------------------------------------------------------------

def test_decode_pressure_honest_under_spec(lm):
    on = _engine(lm, spec=SpecConfig(k=3, sparsity=0.5))
    off = _engine(lm)
    try:
        p_on, p_off = on.decode_pressure(), off.decode_pressure()
        assert p_on["speculative"] is True and p_on["spec_k"] == 3
        assert p_off["speculative"] is False and p_off["spec_k"] == 0
        # the draft pool is real HBM: a spec slot's page cost must
        # include the always-f32 draft K/V rows
        assert on.kv_bytes_per_page() > off.kv_bytes_per_page()
    finally:
        on.stop()
        off.stop()


def test_spec_config_validation(lm):
    with pytest.raises(ValueError, match="continuous"):
        _engine(lm, spec=SpecConfig(k=3), continuous=False)
    with pytest.raises(ValueError, match="SpecConfig.k"):
        _engine(lm, spec=SpecConfig(k=0))
    with pytest.raises(ValueError, match="SpecConfig.k"):
        _engine(lm, spec=SpecConfig(k=16))   # >= cap (4*4)
    with pytest.raises(ValueError, match="draft_impl"):
        _engine(lm, spec=SpecConfig(k=2, sparsity=0.5,
                                    draft_impl="magic"))


# ---------------------------------------------------------------------------
# the multi-query verify kernel (ops.flash_attention.paged_verify_attention)
# ---------------------------------------------------------------------------

def _verify_reference(q, kp, vp, pt, pos):
    """Gathered-jnp reference: per-query causal staircase over the
    slot's pages."""
    S, h, C, d = q.shape
    nb, page = pt.shape[1], kp.shape[2]
    K = nb * page
    kb = kp[pt].transpose(0, 2, 1, 3, 4).reshape(S, h, K, d)
    vb = vp[pt].transpose(0, 2, 1, 3, 4).reshape(S, h, K, d)
    sc = jnp.einsum("shcd,shkd->shck", q, kb) / np.sqrt(d)
    key_pos = jnp.arange(K)[None, None, None, :]
    q_lim = (pos[:, None] + jnp.arange(C)[None, :])[:, None, :, None]
    sc = jnp.where(key_pos <= q_lim, sc, -jnp.inf)
    return jnp.einsum("shck,shkd->shcd", jax.nn.softmax(sc, axis=-1), vb)


def test_paged_verify_attention_matches_reference():
    from bigdl_tpu.ops.flash_attention import paged_verify_attention

    rs = np.random.RandomState(3)
    S, h, C, d, P, nb, page = 4, 2, 4, 8, 16, 4, 4
    q = jnp.asarray(rs.randn(S, h, C, d).astype(np.float32))
    kp = jnp.asarray(rs.randn(P, h, page, d).astype(np.float32))
    vp = jnp.asarray(rs.randn(P, h, page, d).astype(np.float32))
    pt = jnp.asarray(rs.permutation(P)[:S * nb].reshape(S, nb), jnp.int32)
    pos = jnp.asarray(rs.randint(0, page * nb - C, (S,)), jnp.int32)
    out = paged_verify_attention(q, kp, vp, pt, pos, block_h=1,
                                 interpret=True)
    ref = _verify_reference(q, kp, vp, pt, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_paged_verify_attention_int8_matches_dequantized():
    from bigdl_tpu.ops.flash_attention import paged_verify_attention
    from bigdl_tpu.ops.quantized import dequantize_pages, quantize_pages

    rs = np.random.RandomState(5)
    S, h, C, d, P, nb, page = 2, 2, 3, 8, 8, 2, 4
    q = jnp.asarray(rs.randn(S, h, C, d).astype(np.float32))
    k32 = jnp.asarray(rs.randn(P, h, page, d).astype(np.float32))
    v32 = jnp.asarray(rs.randn(P, h, page, d).astype(np.float32))
    kq, ks = quantize_pages(k32)
    vq, vs = quantize_pages(v32)
    pt = jnp.asarray(rs.permutation(P)[:S * nb].reshape(S, nb), jnp.int32)
    pos = jnp.asarray(rs.randint(0, page * nb - C, (S,)), jnp.int32)
    ref = paged_verify_attention(q, dequantize_pages(kq, ks),
                                 dequantize_pages(vq, vs), pt, pos,
                                 block_h=1, interpret=True)
    out = paged_verify_attention(q, kq, vq, pt, pos, k_scales=ks,
                                 v_scales=vs, block_h=1, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="k_scales"):
        paged_verify_attention(q, kq, vq, pt, pos, interpret=True)
