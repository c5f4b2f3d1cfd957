"""Lightning (linear) attention, InfLLM-v2 block selection and block-sparse
attention, and the hybrid decoder's two new layer kinds, each against the
plain float32 form or the family's reference
(``benchmark/families/sparse_linear_lm.py``) on seeded weights, at tiny
widths: 4 layers (minicpm4, then three lightning-attn), d 64, 8 published
heads of 16 on 2 key/value heads, 64-position blocks, vocabulary 256."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from bigdl_tpu.models.hybrid_moe_lm import HybridMoEConfig, HybridMoELM
from bigdl_tpu.nn.sparse_linear_attention import (LightningAttention,
                                                  SparseBlockAttention)
from bigdl_tpu.ops.lightning_attention import alibi_slopes, \
    lightning_attention
from bigdl_tpu.ops.sparse_attention import (causal_spans, select_blocks,
                                            sparse_attention, visible_blocks,
                                            walked_spans, work_lists)
from bigdl_tpu.parallel.moe import swiglu

fam = harness.load_module("families", "sparse_linear_lm")

SPARSE = dict(block_size=64, dense_len=128, init_blocks=1, kernel_size=32,
              kernel_stride=16, topk=4, window_size=128)
SELECT = dict(kernel=32, stride=16, block=64, topk=4, init_blocks=1,
              window=128)
MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"]
# one rank's share: heads 4-7 of 8 (all on key/value head 1), half the FFN
TINY = dict(
    family="sparse_linear_lm", hidden_size=64, head_dim=16,
    lightning_head_dim=16, intermediate_size=96, held_ffn_columns=48,
    mixer_types=MIXERS, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=1, lightning_nh=4, lightning_nkv=4,
    held_heads_first=4, vocab_size=256, rms_norm_eps=1e-6, rope_theta=10000,
    scale_emb=12, scale_depth=1.4, mup_denominator=32, dim_model_base=16,
    tie_word_embeddings=False, qk_norm=True, attn_use_rope=False,
    lightning_use_rope=True, lightning_scale="1/sqrt(d)",
    use_output_gate=True, use_output_norm=True, attn_use_output_gate=True,
    sparse_config=SPARSE,
    published=dict(num_attention_heads=8, num_key_value_heads=2,
                   lightning_nh=8, lightning_nkv=8),
    correct={"logits_p90_limit": 1e-4})
T = 512


def close(a, b, tol=2e-5):
    """Both sides are float32 with exact matmuls (tests/conftest.py)."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * max(
        1.0, float(np.abs(b).max())))


def normal(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def value_and_vjp(f, args, g):
    """``f(*args)`` and its VJP of ``g``, in one jitted call (interpret
    mode runs far faster jitted than op by op)."""
    def both(args, g):
        out, vjp = jax.vjp(f, *args)
        return out, vjp(g)
    return jax.jit(both)(args, g)


def ids_batch(seed, batch, length=T):
    return np.random.default_rng(seed).integers(
        2, TINY["vocab_size"], (batch, length + 1), dtype=np.int32)


# -- lightning attention ---------------------------------------------------------


def plain_lightning(q, k, v, slopes, scale):
    t = q.shape[2]
    lag = (jnp.arange(t)[:, None] - jnp.arange(t)[None, :]).astype(
        jnp.float32)
    s = jnp.asarray(slopes)[:, None, None]
    decay = jnp.where(lag >= 0, jnp.exp(-s * jnp.maximum(lag, 0)), 0.0)
    w = jnp.einsum("bhtd,bhsd->bhts", q, k) * decay[None]
    return jnp.einsum("bhts,bhsd->bhtd", w, v) * scale


@pytest.mark.parametrize("b,h,t,d,d_v,chunk", [
    (1, 2, 256, 32, 32, 64),        # whole chunks
    (2, 3, 300, 32, 16, 128),       # padded to a chunk, narrower values
    (1, 4, 512, 64, 64, None),      # the default chunk
])
def test_lightning_kernel_is_the_quadratic_form(b, h, t, d, d_v, chunk):
    slopes = (0.9,) + alibi_slopes(32, 32 - h + 1, h - 1)
    q, k = normal(1, b, h, t, d), normal(2, b, h, t, d)
    v, g = normal(3, b, h, t, d_v), normal(4, b, h, t, d_v)
    mine = lambda q, k, v: lightning_attention(q, k, v, slopes, chunk=chunk)
    ref = lambda q, k, v: plain_lightning(q, k, v, slopes, d ** -0.5)
    (o, grads), (o_ref, grads_ref) = (value_and_vjp(f, (q, k, v), g)
                                      for f in (mine, ref))
    for a, r in zip((o,) + grads, (o_ref,) + grads_ref):
        close(a, r)


def test_lightning_state_carries_across_chunks_and_decays():
    """A key written in the first chunk still reaches the last (slope
    2^-8, the slowest head), by exactly its decay."""
    t, d = 512, 16
    k = jnp.zeros((1, 1, t, d)).at[0, 0, 0, 0].set(1.0)
    v = jnp.zeros((1, 1, t, d)).at[0, 0, 0, 3].set(1.0)
    q = jnp.ones((1, 1, t, d))
    slope = 2.0 ** -8
    o = lightning_attention(q, k, v, (slope,), scale=1.0, chunk=64)
    close(o[0, 0, :, 3], jnp.exp(-slope * jnp.arange(t)))
    assert float(jnp.abs(o[0, 0, :, :3]).max()) == 0.0


def test_alibi_slopes_are_the_published_heads():
    s = alibi_slopes(32)
    assert s[0] == 2.0 ** -0.25 and s[31] == 2.0 ** -8
    assert alibi_slopes(32, 28, 4) == s[28:]


# -- selection ----------------------------------------------------------------------


def rule(q, k, kernel, stride, block, topk, init_blocks, window):
    """The selection rule, a query at a time in numpy: (T, blocks) bool."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    h, t, d = q.shape
    nb = t // block
    wins = [(w * stride, w * stride + kernel) for w in range(t)
            if w * stride + kernel <= t]
    kc = np.stack([k[a:e].mean(0) for a, e in wins])
    out = np.zeros((t, nb), bool)
    for pos in range(t):
        seen = [w for w, (a, e) in enumerate(wins) if e - 1 <= pos]
        p = np.zeros(len(wins))
        if seen:
            z = q[:, pos] @ kc[seen].T / np.sqrt(d)             # (h, n)
            z = np.exp(z - z.max(1, keepdims=True))
            p[seen] = (z / z.sum(1, keepdims=True)).sum(0)
        score = np.full(nb, -1.0)
        for w, (a, _) in enumerate(wins):
            score[a // block] = max(score[a // block], p[w])
        own = pos // block
        forced = {b for b in range(nb) if b < init_blocks and b <= own}
        forced |= {b for b in range(own + 1)
                   if b * block + block - 1 >= pos - window + 1}
        others = sorted((b for b in range(own + 1) if b not in forced),
                        key=lambda b: (-score[b], b))
        for b in sorted(forced) + others[:max(0, topk - len(forced))]:
            out[pos, b] = True
    return out


def as_member(sel, n_blocks):
    sel = np.asarray(sel)
    out = np.zeros(sel.shape[:-1] + (n_blocks,), bool)
    idx = np.nonzero(sel >= 0)
    out[idx[:-1] + (sel[idx],)] = True
    return out


@pytest.mark.parametrize("topk,window,heads", [(4, 128, 3), (6, 64, 1),
                                               (6, 200, 2)])
def test_selection_follows_the_stated_rule(topk, window, heads):
    t = 512
    q, k = normal(5, 1, 1, heads, t, 16), normal(6, 1, 1, t, 16)
    kw = dict(SELECT, topk=topk, window=window)
    sel = np.asarray(select_blocks(q, k, **kw))[0, 0]
    want = rule(q[0, 0], k[0, 0], **kw)
    np.testing.assert_array_equal(as_member(sel, t // 64), want)
    # each block once, then -1 where a query sees fewer than topk blocks
    valid = sel >= 0
    n = valid.sum(1)
    np.testing.assert_array_equal(valid, np.arange(topk) < n[:, None])
    assert all(len(set(row[row >= 0])) == len(row[row >= 0]) for row in sel)
    visible = np.arange(t) // 64 + 1
    np.testing.assert_array_equal(n, np.minimum(visible, topk))
    # the forced blocks: the first, and the query's own
    member = as_member(sel, t // 64)
    assert member[:, 0].all() and member[np.arange(t), np.arange(t) // 64].all()


def test_selection_is_the_references_own_rule():
    """The program's top_k over a ranked score against the reference's
    stable argsort over segment maxima of reduce_window means."""
    t, h = 1024, 4
    c = fam._model_config(dict(TINY, sparse_config=dict(SPARSE, topk=6)))
    q, k = normal(7, h, t, 16), normal(8, t, 16)
    sel = select_blocks(q[None, None], k[None, None], **dict(SELECT, topk=6))
    with jax.default_matmul_precision("highest"):
        ref = np.concatenate([np.asarray(fam._selection(c, q, k, 256, i,
                                                        None))
                              for i in range(t // 256)])
    np.testing.assert_array_equal(as_member(sel[0, 0], t // 64), ref)


def test_selection_refuses_a_topk_the_forced_blocks_overfill():
    q, k = normal(5, 1, 1, 1, 512, 16), normal(6, 1, 1, 512, 16)
    with pytest.raises(ValueError):
        select_blocks(q, k, **dict(SELECT, topk=5, window=200))


def test_visible_blocks_is_the_sum_over_positions():
    for t, blk in ((512, 64), (500, 64), (32768, 64)):
        assert visible_blocks(t, blk) == sum(p // blk + 1 for p in range(t))
    share = fam.selection_share({"sparse_config": dict(SPARSE, topk=64,
                                                       dense_len=8192)},
                                32768)
    assert round(100 * share, 2) == 23.42


# -- sparse attention ------------------------------------------------------------------


def masked_softmax_attention(q, k, v, sel, block):
    t = q.shape[-2]
    member = jnp.asarray(as_member(sel, t // block))          # (b,g,T,nb)
    mask = jnp.repeat(member, block, -1) & jnp.tril(jnp.ones((t, t), bool))
    s = jnp.einsum("bghtd,bgsd->bghts", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(mask[:, :, None], s, -jnp.inf)
    return jnp.einsum("bghts,bgsd->bghtd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("b,g,h,t,d,topk,window,block_q,block_k", [
    (1, 1, 4, 512, 32, 4, 128, 128, 256),     # spans of 4 blocks
    (2, 2, 2, 256, 16, 3, 64, 64, 64),        # one block a step
    (1, 1, 2, 512, 32, 6, 128, 256, 128),     # tiles longer than spans
])
def test_sparse_kernels_are_masked_softmax(b, g, h, t, d, topk, window,
                                           block_q, block_k):
    q, k, v = normal(9, b, g, h, t, d), normal(10, b, g, t, d), normal(
        11, b, g, t, d)
    go = normal(12, b, g, h, t, d)
    sel = select_blocks(q, k, **dict(SELECT, topk=topk, window=window))
    mine = lambda q, k, v: sparse_attention(q, k, v, sel, block_q=block_q,
                                            block_k=block_k)
    ref = lambda q, k, v: masked_softmax_attention(q, k, v, sel, 64)
    (o, grads), (o_ref, grads_ref) = (value_and_vjp(f, (q, k, v), go)
                                      for f in (mine, ref))
    for a, r in zip((o,) + grads, (o_ref,) + grads_ref):
        close(a, r)


@pytest.mark.parametrize("bq,bk", [(128, 64), (64, 128)])
def test_work_lists_visit_each_selected_span_once(bq, bk):
    t = 512
    q, k = normal(13, 1, 1, 2, t, 16), normal(14, 1, 1, t, 16)
    sel = select_blocks(q, k, **SELECT).reshape(1, t, -1)
    fwd, dkv, n = (np.asarray(x) for x in work_lists(sel, 64, bq, bk))
    n = int(n[0])
    member = as_member(np.asarray(sel)[0], t // 64)
    union = {(i, b) for i in range(t // bq) for b in range(t // bk)
             if member[i * bq:(i + 1) * bq, b * bk // 64:
                       (b + 1) * bk // 64].any()}
    pairs = [(x >> 16, x & 0xFFFF) for x in fwd[:n]]
    assert pairs == sorted(union) and len(union) == n
    assert sorted((x & 0xFFFF, x >> 16) for x in dkv[:n]) == sorted(union)
    assert [x >> 16 for x in dkv[:n]] == sorted(x >> 16 for x in dkv[:n])
    # past the count, the last pair again
    assert (fwd[n:] == fwd[n - 1]).all() and (dkv[n:] == dkv[n - 1]).all()


@pytest.mark.parametrize("local", [False, True])
def test_walked_spans_count_the_work_lists_against_the_causal_bound(local):
    """Scored selections that differ from query to query walk most causal
    (tile, span) pairs; local selections walk a band, and the count says
    so."""
    t, bq, bk = 1024, 128, 128
    q, k = normal(22, 2, 1, 2, t, 16), normal(23, 2, 1, t, 16)
    sel = select_blocks(q, k, **SELECT)
    if local:     # block 0, the query's own block and the one before it
        own = jnp.arange(t)[:, None] // 64
        sel = jnp.broadcast_to(jnp.where(
            jnp.arange(4) == 3, -1, jnp.maximum(
                jnp.array([0, -1, 0, 0]) + own * jnp.array([0, 1, 1, 0]),
                0)), sel.shape)
    walked, causal = walked_spans(sel, 64, bq, bk)
    n = work_lists(sel.reshape(2, t, -1), 64, bq, bk)[2]
    assert int(walked) == int(n.sum())
    assert causal == 2 * causal_spans(t, bq, bk) == 2 * 36
    if local:     # tile i walks spans 0, i - 1 and i
        assert int(walked) == 2 * (1 + 2 + 3 * 6)
    else:
        assert 2 * (1 + 2 + 3 * 6) < int(walked) < causal


# -- the decoder -------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    model = fam.build_model(TINY)
    ids = ids_batch(38, 2)
    v = harness.init_variables(model, 3800380000, ids[:1, :-1])
    mine = fam.program_logits(TINY, v["params"], ids[:, :-1])
    return model, ids, v, mine


def test_decoder_logits_are_the_reference(tiny):
    _, ids, v, mine = tiny
    for row, logits in zip(ids, mine):
        close(logits, fam.reference_logits(TINY, v["params"], row[:-1]),
              tol=1e-4)


@pytest.mark.parametrize("ablate", fam.ABLATIONS[1:])
def test_each_ablation_moves_the_logits_past_the_limit(tiny, ablate):
    _, ids, v, mine = tiny
    ref = fam.reference_logits(TINY, v["params"], ids[0, :-1], ablate)
    p90 = np.percentile(fam.token_distances(mine[0], ref), 90)
    assert p90 > TINY["correct"]["logits_p90_limit"]


def test_dense_sequence_is_the_reference_and_counts_every_block():
    model = fam.build_model(TINY)
    ids = ids_batch(39, 1, 128)[:, :-1]
    v = harness.init_variables(model, 7, ids)
    close(fam.program_logits(TINY, v["params"], ids)[0],
          fam.reference_logits(TINY, v["params"], ids[0]), tol=1e-4)
    _, st = jax.jit(model.forward)(v["params"], v["state"], jnp.asarray(ids))
    c = st["layer0"]["sparse"]["metrics"]["counters"]
    assert int(c["sparse.selected_blocks"]) == int(
        c["sparse.visible_blocks"]) == visible_blocks(128, 64)
    assert int(c["sparse.walked_spans"]) == int(
        c["sparse.causal_spans"]) == causal_spans(128)


def test_counters_are_the_analytic_count(tiny):
    model, ids, v, _ = tiny
    _, st = jax.jit(model.forward)(v["params"], v["state"],
                                   jnp.asarray(ids[:, :-1]))
    c = st["layer0"]["sparse"]["metrics"]["counters"]
    visible = np.arange(T) // 64 + 1
    assert int(c["sparse.visible_blocks"]) == 2 * visible.sum()
    assert int(c["sparse.selected_blocks"]) == 2 * np.minimum(
        visible, SPARSE["topk"]).sum()
    # one 512-query tile on one 512-key span a sequence
    assert int(c["sparse.walked_spans"]) == int(
        c["sparse.causal_spans"]) == 2
    assert set(st) == {"layer0"}


def test_reference_loss_is_nan_beyond_the_limit(tiny, capsys):
    _, ids, v, _ = tiny
    x, y = ids[:1, :-1], ids[:1, 1:]
    assert np.isfinite(fam.reference_loss(TINY, v["params"], x, y))
    assert "ok=True" in capsys.readouterr().out


def test_selection_is_saved_across_checkpoint_and_gradients_flow(tiny):
    """Training recomputes each layer, but a sparse layer's block list is
    kept: one top_k in the differentiated step, not two."""
    model, ids, v, _ = tiny
    x = jnp.asarray(ids[:1, :256])          # still past dense_len

    def loss(p):
        logits, _ = model.forward(p, v["state"], x, training=True)
        return jnp.mean(logits ** 2)

    lowered = jax.jit(jax.grad(loss)).lower(v["params"])
    assert lowered.as_text().count("top_k") == 1
    grads = lowered.compile()(v["params"])
    for name in ("wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm"):
        assert float(jnp.abs(grads["layer0"]["sparse"][name]).max()) > 0
        assert float(jnp.abs(grads["layer1"]["lightning"][name]).max()) > 0
    assert float(jnp.abs(grads["head"]).max()) > 0


def test_device_scopes_are_in_the_lowered_step(tiny):
    model, ids, v, _ = tiny
    step = jax.jit(jax.grad(lambda p: jnp.mean(model.forward(
        p, v["state"], jnp.asarray(ids[:1, :-1]), training=True)[0])))
    text = step.lower(v["params"]).as_text(debug_info=True)
    for scope in ("sala/proj", "sala/lightning", "sala/select",
                  "sala/sparse_attn", "lm/dense_ffn", "lm/head"):
        assert scope in text, scope


def test_mup_scales_and_untied_head(tiny):
    model, ids, v, _ = tiny
    c = model.config
    assert (c.scale_emb, c.residual_scale, c.head_divisor) == (
        12, 1.4 / 32 ** 0.5, 4.0)
    assert v["params"]["head"].shape == v["params"]["embed"].shape
    assert not jnp.array_equal(v["params"]["head"], v["params"]["embed"])
    assert "ffn" in v["params"]["layer0"] and "moe" not in v["params"][
        "layer0"]
    assert v["params"]["layer1"]["ffn"]["w_gate"].shape == (64, 48)


# -- the share of a tensor-parallel group ------------------------------------------------


def _split_heads(p, first, count, hd, kv_first=None, kv_count=None):
    cols = slice(first * hd, (first + count) * hd)
    out = dict(p, wq=p["wq"][:, cols], wo=p["wo"][cols], wg=p["wg"][:, cols])
    if kv_first is None:
        out.update(wk=p["wk"][:, cols], wv=p["wv"][:, cols])
        if "o_norm" in p:
            out["o_norm"] = p["o_norm"][cols]
    else:
        kv = slice(kv_first * hd, (kv_first + kv_count) * hd)
        out.update(wk=p["wk"][:, kv], wv=p["wv"][:, kv])
    return out


def test_eight_ranks_of_a_lightning_layer_and_ffn_add_up():
    u = normal(15, 2, 128, 64)
    whole = LightningAttention(64, 8, 16)
    p = whole.init(jax.random.PRNGKey(16), u)["params"]
    total = sum(jax.jit(LightningAttention(64, 8, 16, held=(r, 1)).forward)(
        _split_heads(p, r, 1, 16), {}, u)[0] for r in range(8))
    close(total, jax.jit(whole.forward)(p, {}, u)[0], tol=1e-4)
    ffn = {"w_gate": normal(17, 64, 96), "w_up": normal(18, 64, 96),
           "w_down": normal(19, 96, 64) / 10}
    part = lambda r: {k: (w[:, r * 12:(r + 1) * 12] if k != "w_down"
                          else w[r * 12:(r + 1) * 12]) for k, w in ffn.items()}
    close(sum(swiglu(u, part(r)) for r in range(8)), swiglu(u, ffn),
          tol=1e-4)


def test_eight_ranks_of_a_sparse_layer_add_up_given_the_groups_selection():
    """Eight ranks of one query head each, ranks 0-3 on key/value head 0
    and 4-7 on head 1: every rank is handed its group's selection as the
    whole layer made it (the score exchange over a group's ranks that
    would make it is ROADMAP B8's)."""
    u = normal(20, 1, 256, 64)
    kw = dict(dense_len=128, topk=3, window_size=64)
    whole = SparseBlockAttention(64, 8, 2, 16, **kw)
    p = whole.init(jax.random.PRNGKey(21), u)["params"]
    y, sel = jax.jit(whole.mix)(p, u)
    assert sel.shape == (1, 2, 256, 3) and (sel[..., 192:, :] >= 0).all()
    total = 0
    for r in range(8):
        rank = SparseBlockAttention(64, 8, 2, 16, held=(r, 1), **kw)
        g = r // 4
        total = total + jax.jit(rank.mix)(_split_heads(p, r, 1, 16, g, 1),
                                          u, sel[:, g:g + 1])[0]
    close(total, y, tol=1e-4)


def test_held_heads_must_lie_in_one_group_or_whole_groups():
    SparseBlockAttention(64, 8, 2, 16, held=(4, 4))
    SparseBlockAttention(64, 8, 2, 16, held=(0, 8))
    with pytest.raises(ValueError):
        SparseBlockAttention(64, 8, 2, 16, held=(2, 4))


# -- the configuration ----------------------------------------------------------------------


def test_config_reads_minicpm_names_and_refuses_what_it_cannot_build():
    c = fam._model_config(TINY)
    assert c.layer_types == tuple(MIXERS) and c.norm_eps == 1e-6
    assert c.rope_theta == 10000.0 and c.held_heads == (4, 4)
    assert dict(c.sparse_config)["topk"] == 4
    base = dict(TINY, num_attention_heads=8, num_key_value_heads=2,
                lightning_nh=8, lightning_nkv=8)
    with pytest.raises(ValueError):
        HybridMoEConfig.from_dict(dict(base, lightning_nh=4))
    with pytest.raises(ValueError):
        HybridMoEConfig.from_dict(dict(base, attn_use_rope=True))
    with pytest.raises(ValueError):
        HybridMoEConfig.from_dict(dict(base, lightning_scale="1/d"))


# LFM2's tiny decoder as the parent commit built it: the sha256 of its
# sorted (path, shape, dtype) leaves and a grid of its logits, computed at
# the parent (PR 37), jitted, with tests/conftest.py's settings
LFM2 = dict(vocab_size=512, hidden_size=64, num_hidden_layers=5,
            layer_types=["conv", "full_attention", "conv", "conv", "conv"],
            num_attention_heads=8, num_key_value_heads=2,
            intermediate_size=160, moe_intermediate_size=48, num_experts=8,
            num_experts_per_tok=2, num_dense_layers=1, conv_L_cache=3,
            conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
            routed_scaling_factor=1, use_expert_bias=True,
            rope_parameters={"rope_theta": 1e6, "rope_type": "default"})
LFM2_TREE = "df59282bcc236c3cbd875fd63a3d472cb7a5599031f56b7a7cdd54adc8657940"
LFM2_LOGITS = [
    [[-0.484865, 0.028708, 0.017434, 0.521861],
     [-2.631736, 0.686853, 1.457768, -0.370911],
     [-0.511365, 0.850101, 0.653098, 1.067266],
     [1.140341, 0.775546, -1.582058, 1.250969]],
    [[0.571684, -0.390262, -0.300337, -0.124482],
     [-0.136116, -0.482229, -0.1768, -2.932198],
     [0.48256, -1.851083, 1.52076, 0.664539],
     [0.184328, 0.337018, 0.411555, -0.118572]]]


def test_lfm2_parameter_tree_and_logits_are_as_before():
    m = HybridMoELM(HybridMoEConfig.from_dict(LFM2))
    ids = np.random.default_rng(38).integers(2, 512, (2, 32), dtype=np.int32)
    v = m.init(jax.random.PRNGKey(38), ids[:1])
    leaves = sorted((jax.tree_util.keystr(p), tuple(a.shape), str(a.dtype))
                    for p, a in jax.tree_util.tree_flatten_with_path(v)[0])
    assert hashlib.sha256(repr(leaves).encode()).hexdigest() == LFM2_TREE
    logits, _ = jax.jit(m.forward)(v["params"], v["state"], jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(logits)[:, ::8, ::128],
                               LFM2_LOGITS, atol=2e-6)
    assert not any(k in m.__dict__ for k in ("lightning", "sparse"))
