"""The gated short convolution, grouped-query attention through the flash
kernels, the expert layer with no shared expert and the hybrid decoder
built from them, each against the plain float32 reference
(``benchmark/families/conv_gqa_moe_lm.py``) on seeded weights, at tiny
widths: 1 dense + 4 expert layers (conv, attention, conv, conv, conv), d 64,
8 query heads on 2 key/value heads, 8 experts top-2, vocabulary 512."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from bigdl_tpu.models.hybrid_moe_lm import HybridMoEConfig, HybridMoELM
from bigdl_tpu.nn.attention import GroupedQueryAttention, \
    dot_product_attention
from bigdl_tpu.nn.short_conv import GatedShortConv, causal_taps
from bigdl_tpu.ops.flash_attention import flash_attention
from bigdl_tpu.parallel.moe import HeldMoE, route_sigmoid_topk

fam = harness.load_module("families", "conv_gqa_moe_lm")

TINY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=5,
            layer_types=["conv", "full_attention", "conv", "conv", "conv"],
            num_attention_heads=8, num_key_value_heads=2,
            intermediate_size=160, moe_intermediate_size=48, num_experts=8,
            num_experts_per_tok=2, num_dense_layers=1, conv_L_cache=3,
            conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
            routed_scaling_factor=1, use_expert_bias=True,
            rope_parameters={"rope_theta": 1e6, "rope_type": "default"})
T = 32


def config(**kw):
    return HybridMoEConfig.from_dict(dict(TINY, **kw))


def close(a, b, tol=2e-5):
    """Both sides are float32 with exact matmuls (tests/conftest.py): they
    differ by the order of float32 sums only."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * max(
        1.0, float(np.abs(b).max())))


def ids_batch(seed, batch, length=T):
    return np.random.default_rng(seed).integers(
        2, TINY["vocab_size"], (batch, length + 1), dtype=np.int32)


def leaves_close(got, want, tol=2e-5):
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    ref = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    assert len(flat) == len(ref)
    for path, a in flat:
        assert float(jnp.abs(ref[path]).max()) > 0, path
        close(a, ref[path], tol)


# -- the gated short convolution ------------------------------------------------

def _conv(kernel=3, seed=0, batch=2):
    op = GatedShortConv(64, kernel)
    x = jax.random.normal(jax.random.PRNGKey(seed), (batch, T, 64))
    return op, op.init(jax.random.PRNGKey(seed + 1), x), x


def _ref_conv(params, x, ablate=None):
    return jnp.stack([fam._conv_op(config(), params, u, ablate, False)
                      for u in x])


@pytest.mark.parametrize("kernel", [1, 3, 4])
def test_short_conv_forward_and_gradients(kernel):
    op, v, x = _conv(kernel)
    assert v["params"]["taps"].shape == (kernel, 64)
    y, _ = op.apply(v, x)
    close(y, _ref_conv(v["params"], x))
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    g = jax.grad(lambda p, x: jnp.sum(op.forward(p, {}, x)[0] * cot),
                 (0, 1))(v["params"], x)
    g_ref = jax.grad(lambda p, x: jnp.sum(_ref_conv(p, x) * cot),
                     (0, 1))(v["params"], x)
    leaves_close(g, g_ref)


@pytest.mark.parametrize("ablate", ["taps_reversed", "gate_b"])
def test_short_conv_differs_from_a_wrong_reference(ablate):
    op, v, x = _conv()
    y, _ = op.apply(v, x)
    wrong = _ref_conv(v["params"], x, ablate)
    assert float(jnp.sqrt(jnp.mean((y - wrong) ** 2))) > 0.3 * float(
        jnp.std(y))


@pytest.mark.parametrize("kernel", [1, 3, 4])
def test_short_conv_is_causal(kernel):
    """Position t's output does not change when inputs after t do."""
    op, v, x = _conv(kernel, batch=1)
    t = 11
    later = x.at[:, t + 1:].set(
        jax.random.normal(jax.random.PRNGKey(5), x[:, t + 1:].shape))
    y, y_later = op.apply(v, x)[0], op.apply(v, later)[0]
    assert bool(jnp.array_equal(y[:, :t + 1], y_later[:, :t + 1]))
    assert not bool(jnp.allclose(y[:, t + 1:], y_later[:, t + 1:]))


@pytest.mark.parametrize("kernel", [1, 3, 4])
@pytest.mark.parametrize("length", [2, T])
def test_taps_are_a_depthwise_convolution_padded_on_the_left(kernel,
                                                             length):
    z = jax.random.normal(jax.random.PRNGKey(2), (2, length, 64))
    taps = jax.random.normal(jax.random.PRNGKey(3), (kernel, 64))
    want = jax.lax.conv_general_dilated(
        z, taps[:, None, :], window_strides=(1,),
        padding=[(kernel - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=64)
    close(causal_taps(z, taps), want)


def test_short_conv_refuses_no_tap():
    with pytest.raises(ValueError, match="at least one tap"):
        GatedShortConv(64, 0)


# -- grouped-query heads in the flash kernels -------------------------------------

def _qkv(h, h_kv, sq=48, skv=48, d=16, d_v=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (2, h, sq, d)),
            jax.random.normal(ks[1], (2, h_kv, skv, d)),
            jax.random.normal(ks[2], (2, h_kv, skv, d_v)),
            jax.random.normal(ks[3], (2, h, sq, d_v)))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("h,h_kv", [(4, 4), (8, 2), (4, 1)])
def test_flash_grouped_heads_equal_repeated_heads(h, h_kv, causal):
    """Forward and all three gradients with K/V given once a group equal
    those with K/V repeated for every query head (dk, dv summed over the
    group); blocks of 16 so that several tiles and the diagonal are
    walked."""
    q, k, v, cot = _qkv(h, h_kv)
    group = h // h_kv
    kw = dict(causal=causal, block_q=16, block_k=16, interpret=True)

    def grouped(q, k, v):
        return flash_attention(q, k, v, **kw)

    def repeated(q, k, v):
        return flash_attention(q, jnp.repeat(k, group, 1),
                               jnp.repeat(v, group, 1), **kw)

    out, vjp = jax.vjp(grouped, q, k, v)
    want, vjp_rep = jax.vjp(repeated, q, k, v)
    close(out, want, 1e-6)
    for a, b in zip(vjp(cot), vjp_rep(cot)):
        assert a.shape == b.shape
        close(a, b, 1e-5)
    xla = dot_product_attention(
        q, jnp.repeat(k, group, 1), jnp.repeat(v, group, 1),
        mask=jnp.tril(jnp.ones((48, 48), bool)) if causal else None)
    close(out, xla, 1e-5)


@pytest.mark.parametrize("sq,skv,d,d_v,blocks", [
    (40, 40, 16, 16, (16, 16)),      # padded to the block
    (32, 64, 16, 16, (16, 32)),      # unequal lengths and blocks
    (48, 48, 24, 16, (16, 16)),      # keys wider than values
    (48, 48, 16, 16, (64, 64)),      # one block
])
def test_flash_grouped_heads_at_odd_shapes(sq, skv, d, d_v, blocks):
    q, k, v, cot = _qkv(6, 2, sq, skv, d, d_v, seed=3)
    kw = dict(causal=True, block_q=blocks[0], block_k=blocks[1],
              interpret=True)
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v, **kw),
                       q, k, v)
    want, vjp_rep = jax.vjp(lambda q, k, v: flash_attention(
        q, jnp.repeat(k, 3, 1), jnp.repeat(v, 3, 1), **kw), q, k, v)
    close(out, want, 1e-6)
    for a, b in zip(vjp(cot), vjp_rep(cot)):
        close(a, b, 1e-5)


def test_flash_refuses_heads_that_do_not_divide():
    q, k, v, _ = _qkv(6, 4)
    with pytest.raises(ValueError, match="divides"):
        flash_attention(q, k, v, interpret=True)


def test_flash_trace_counter_names_the_group():
    from bigdl_tpu.optim.metrics import global_metrics, label_key

    m = global_metrics()
    key = lambda direction: label_key(
        "kernel.flash.traces", direction=direction, impl="pallas",
        dtype="float32", kv_group="4")
    before = m.counter(key("fwd")), m.counter(key("bwd"))
    q, k, v, _ = _qkv(8, 2)
    jax.grad(lambda q: flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16,
        interpret=True).sum())(q)
    assert (m.counter(key("fwd")), m.counter(key("bwd"))) == (
        before[0] + 1, before[1] + 1)


def test_block_rule_at_head_size_64_and_8192_positions():
    """The new cell's shape: what the rule picks is written down in
    docs/performance.md; a change of the rule shows here."""
    from bigdl_tpu.ops.flash_attention import default_blocks

    for direction in ("fwd", "bwd"):
        assert default_blocks(direction, 8192, 8192, 64, 2) == {
            "block_q": 1024, "block_k": 1024}


# -- grouped-query attention -----------------------------------------------------

def _gqa(use_flash, seed=0):
    c = config()
    attn = GroupedQueryAttention(64, 8, 2, 8, rope_theta=1e6,
                                 qk_norm_eps=1e-5, use_flash=use_flash)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, T, 64))
    v = attn.init(jax.random.PRNGKey(seed + 1), x)
    # norm weights that are not 1, so that leaving them out shows
    p = dict(v["params"],
             q_norm=1 + 0.3 * jax.random.normal(jax.random.PRNGKey(7), (8,)),
             k_norm=1 + 0.3 * jax.random.normal(jax.random.PRNGKey(8), (8,)))
    return c, attn, p, x


def _ref_gqa(c, p, x, ablate=None):
    return jnp.stack([fam._gqa(c, p, u, ablate, False) for u in x])


@pytest.mark.parametrize("use_flash", [False, True])
def test_gqa_forward_and_gradients(use_flash):
    c, attn, p, x = _gqa(use_flash)
    assert p["wk"].shape == (64, 16) and p["q_norm"].shape == (8,)
    y, _ = attn.forward(p, {}, x)
    close(y, _ref_gqa(c, p, x))
    cot = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    g = jax.grad(lambda p, x: jnp.sum(attn.forward(p, {}, x)[0] * cot),
                 (0, 1))(p, x)
    g_ref = jax.grad(lambda p, x: jnp.sum(_ref_gqa(c, p, x) * cot),
                     (0, 1))(p, x)
    leaves_close(g, g_ref, 1e-4 if use_flash else 2e-5)


@pytest.mark.parametrize("ablate", ["kv_head", "qk_norm", "rope"])
def test_gqa_differs_from_a_wrong_reference(ablate):
    c, attn, p, x = _gqa(False)
    y, _ = attn.forward(p, {}, x)
    wrong = _ref_gqa(c, p, x, ablate)
    assert float(jnp.sqrt(jnp.mean((y - wrong) ** 2))) > 0.1 * float(
        jnp.std(y))


def test_gqa_refuses_heads_that_do_not_divide():
    with pytest.raises(ValueError, match="whole group"):
        GroupedQueryAttention(64, 8, 3, 8)


# -- the expert layer with no shared expert -----------------------------------------

def _moe(held, seed=0):
    c = config(held_experts=held)
    moe = HeldMoE(c.num_experts, c.moe_intermediate_size,
                  c.num_experts_per_tok, held=held, shared_hidden=0,
                  scale=c.routed_scaling_factor, norm_topk=True,
                  norm_eps=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, T, 64))
    return c, moe, moe.init(jax.random.PRNGKey(seed + 1), x), x


def test_topk_sum_epsilon_is_an_argument_and_the_default_is_as_before():
    x = jax.random.normal(jax.random.PRNGKey(0), (T, 64))
    w_r = jax.random.normal(jax.random.PRNGKey(1), (8, 64)) * 0.125
    idx0, w0 = route_sigmoid_topk(x, w_r, jnp.zeros((8,)), 2)
    idx1, w1 = route_sigmoid_topk(x, w_r, jnp.zeros((8,)), 2,
                                  norm_eps=1e-20)
    idx6, w6 = route_sigmoid_topk(x, w_r, jnp.zeros((8,)), 2, norm_eps=1e-6)
    assert bool(jnp.array_equal(w0, w1)) and bool(jnp.array_equal(idx0,
                                                                  idx6))
    s = jnp.take_along_axis(jax.nn.sigmoid(x @ w_r.T), idx6, -1)
    close(w6, s / (s.sum(-1, keepdims=True) + 1e-6), 1e-7)
    assert float(jnp.abs(w6.sum(-1) - 1).max()) < 1e-5
    assert not bool(jnp.array_equal(w0, w6))


def test_no_shared_expert_a_token_with_no_held_expert_gets_zero():
    c, moe, v, x = _moe((2, 2))
    assert "shared" not in v["params"]
    y, st = moe.apply(v, x)
    flat = x.reshape(-1, 64)
    close(y.reshape(flat.shape),
          fam._routed(c, v["params"], flat, False))
    idx, _ = route_sigmoid_topk(flat, v["params"]["w_router"],
                                jnp.zeros((8,)), 2, norm_eps=1e-6)
    none_held = np.asarray(~((idx >= 2) & (idx < 4)).any(-1))
    assert 0.2 * len(flat) < none_held.sum() < 0.8 * len(flat)
    y = np.asarray(y).reshape(flat.shape)
    assert (y[none_held] == 0).all() and (y[~none_held] != 0).any(-1).all()
    # a row that is zero because nobody sent it is not a dropped pair
    assert int(st["metrics"]["counters"]["moe.dropped_pairs"]) == 0
    assert int(st["metrics"]["counters"]["moe.local_pairs"]) == int(
        ((idx >= 2) & (idx < 4)).sum())
    g = jax.grad(lambda x: jnp.sum(moe.apply(v, x)[0] ** 2))(x)
    g = np.asarray(g).reshape(flat.shape)
    assert (g[none_held] == 0).all() and np.isfinite(g).all()


def _ref_layer(c, i, p, x, ablate=None):
    return jnp.stack([fam._layer(c, i, p, u, ablate) for u in x])


@pytest.mark.parametrize("i", [0, 1, 2])
def test_shares_add_up_to_the_uncut_layer(i):
    """Eight chips hold one expert each (the dense layer 0 has none to
    share).  What every chip computes alike, counted once (the operator,
    the residual stream it leaves, the dense FFN), plus the eight shares'
    expert parts, is the uncut reference layer."""
    whole_c = config()
    model = HybridMoELM(whole_c)
    ids = ids_batch(1, 2)[:, :-1]
    v = model.init(jax.random.PRNGKey(3), ids)
    p = v["params"][f"layer{i}"]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, T, 64)) * 0.125
    want = _ref_layer(whole_c, i, p, h)
    if "ffn" in p:
        close(model._layer(i, p, {}, h)[0], want)
        return
    # the stream after the operator: every chip's alike
    no_experts = dict(p, moe=dict(p["moe"], experts=jax.tree_util.tree_map(
        jnp.zeros_like, p["moe"]["experts"])))
    after_op = model._layer(i, no_experts, v["state"][f"layer{i}"], h)[0]
    total = after_op
    for e in range(8):
        share = HybridMoELM(config(held_experts=(e, 1)))
        sp = dict(p, moe=dict(p["moe"], experts={
            k: a[e:e + 1] for k, a in p["moe"]["experts"].items()}))
        out, _ = share._layer(i, sp, v["state"][f"layer{i}"], h)
        total = total + (out - after_op)
    close(total, want)


# -- the whole model ------------------------------------------------------------------

CFG_FILE = dict(TINY, family="conv_gqa_moe_lm", published={"num_experts": 8},
                held_experts_first=2, num_experts=4,
                correct={"logits_p90_limit": 1e-4})


def _ref_loss(c, params, x, y):
    total = 0.0
    for ids, tgt in zip(x, y):
        h = params["embed"][ids]
        for i in range(c.num_hidden_layers):
            h = fam._layer(c, i, params[f"layer{i}"], h, None)
        logp = jax.nn.log_softmax(
            fam._logits(c, params["ln_out"], params["embed"], h, False))
        total = total - jnp.mean(logp[jnp.arange(len(tgt)), tgt])
    return total / len(x)


def test_config_takes_the_source_keys_as_they_are():
    c = fam.build_model(CFG_FILE).config
    assert c.held_experts == (2, 4) and c.num_experts == 8
    assert c.layer_types == tuple(TINY["layer_types"])
    assert c.rope_theta == 1e6 and c.head_dim == 8
    with pytest.raises(ValueError, match="layer_types"):
        config(layer_types=["conv"] * 4)
    with pytest.raises(ValueError, match="one of"):
        config(layer_types=["conv"] * 4 + ["sliding_attention"])
    with pytest.raises(ValueError, match="rotary"):
        config(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"})


def test_model_logits_loss_and_gradients():
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion

    model = fam.build_model(CFG_FILE)
    c = model.config
    ids = ids_batch(0, 2)
    x, y = ids[:, :-1], ids[:, 1:]
    v = model.init(jax.random.PRNGKey(12), x[:1])
    assert "head" not in v["params"]
    logits, _ = model.apply(v, x)
    assert 0.7 < float(jnp.std(logits)) < 1.4      # a loss that can move
    for b in range(2):
        close(logits[b], fam.reference_logits(CFG_FILE, v["params"], x[b]))

    crit = CrossEntropyCriterion()

    def loss(p):
        out, _ = model.forward(p, v["state"], x, training=True)
        return crit.forward(out, y)

    l, g = jax.value_and_grad(loss)(v["params"])
    close(l, fam.reference_loss(CFG_FILE, v["params"], x, y), 1e-6)
    g_ref = jax.grad(lambda p: _ref_loss(c, p, x, y))(v["params"])
    leaves_close(g, g_ref)


def test_init_gives_unit_logits_and_a_first_loss_near_ln_vocabulary():
    """The init's promise with a tied head: logits of standard deviation
    near 1 and no token that owns its own logits, so the first loss is ln
    of the vocabulary plus about half the logits' variance."""
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion

    model = fam.build_model(CFG_FILE)
    ids = ids_batch(2, 4)
    x, y = ids[:, :-1], ids[:, 1:]
    v = model.init(jax.random.PRNGKey(1), x[:1])
    logits, _ = model.apply(v, x)
    assert 0.8 < float(jnp.std(logits)) < 1.2
    own = jnp.take_along_axis(logits, x[..., None], -1)
    assert abs(float(jnp.mean(own))) < 1.0
    loss = float(CrossEntropyCriterion().forward(logits, y))
    assert np.log(512) + 0.2 < loss < np.log(512) + 0.9


def test_tied_head_gradient_is_the_sum_of_its_two_uses():
    """d loss / d Emb = the lookup's gradient + the head's."""
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion

    model = fam.build_model(CFG_FILE)
    ids = ids_batch(5, 2)
    x, y = ids[:, :-1], ids[:, 1:]
    v = model.init(jax.random.PRNGKey(2), x[:1])
    crit = CrossEntropyCriterion()

    def loss(p):
        return crit.forward(model.forward(p, v["state"], x)[0], y)

    def untied(lookup, head):
        """The same model with the two uses of the matrix held apart."""
        c = model.config
        h = jnp.take(lookup, x, axis=0)
        for i in range(c.num_hidden_layers):
            h, _ = model._layer(i, v["params"][f"layer{i}"],
                                v["state"].get(f"layer{i}", {}), h)
        from bigdl_tpu.nn.layers import rms_norm

        h = rms_norm(h, v["params"]["ln_out"], c.norm_eps)
        return crit.forward(jnp.einsum("btd,vd->btv", h, head), y)

    emb = v["params"]["embed"]
    g = jax.grad(loss)(v["params"])["embed"]
    g_lookup, g_head = jax.grad(untied, (0, 1))(emb, emb)
    assert float(jnp.abs(g_lookup).max()) > 0 < float(jnp.abs(g_head).max())
    close(g, g_lookup + g_head)
    # rows never looked up get the head's gradient only
    unused = np.setdiff1d(np.arange(512), np.unique(x))
    assert len(unused) > 300
    assert bool(jnp.all(g_lookup[unused] == 0))
    close(g[unused], g_head[unused])


@pytest.mark.parametrize("ablate", fam.ABLATIONS)
def test_reference_loss_tells_a_wrong_layer_by_the_logits(ablate, capsys):
    """What decides the cell's ``correct``: against a reference with the
    taps reversed, without the B gate, with the query heads on the wrong
    key/value heads, without the per-head norms, RoPE, a routed expert
    layer, or bfloat16's mantissa, the program's logits are too far away
    and ``reference_loss`` is NaN; against the reference as it is, it is
    the loss."""
    model = fam.build_model(CFG_FILE)
    ids = ids_batch(4, 2)
    x, y = ids[:, :-1], ids[:, 1:]
    params = model.init(jax.random.PRNGKey(14), x[:1])["params"]
    # norm weights that are not 1, so that leaving the norm out shows
    attn = params["layer1"]["attn"]
    attn["q_norm"] = 1 + 0.5 * jax.random.normal(jax.random.PRNGKey(1), (8,))
    attn["k_norm"] = 1 + 0.5 * jax.random.normal(jax.random.PRNGKey(2), (8,))
    cfg = dict(CFG_FILE, correct={"logits_p90_limit": 1e-2})
    loss = fam.reference_loss(cfg, params, x, y, ablate)
    assert np.isnan(loss) == (ablate is not None)
    assert f"ok={ablate is None}" in capsys.readouterr().out


def test_training_recomputes_every_layer_and_changes_no_number():
    model = HybridMoELM(config())
    ids = jnp.asarray(ids_batch(3, 1)[:, :-1])
    v = model.init(jax.random.PRNGKey(0), ids)

    def run(p, training):
        return model.forward(p, v["state"], ids, training=training)[0]

    def n_remat(training):
        return str(jax.make_jaxpr(lambda p: run(p, training))(
            v["params"])).count("remat")

    assert n_remat(False) == 0
    assert n_remat(True) == model.config.num_hidden_layers
    close(run(v["params"], True), run(v["params"], False), 1e-6)


def test_device_scopes_are_in_the_lowered_step():
    model = fam.build_model(CFG_FILE)
    ids = jnp.asarray(ids_batch(3, 1)[:, :-1])
    v = model.init(jax.random.PRNGKey(0), ids)
    text = jax.jit(lambda p: model.forward(p, v["state"], ids)[0]).lower(
        v["params"]).as_text(debug_info=True)
    for scope in ("conv/proj", "conv/mix", "gqa/proj", "gqa/attn",
                  "moe/route", "moe/experts", "lm/dense_ffn", "lm/head"):
        assert scope in text, scope
    assert "moe/shared" not in text


# -- through Optimizer.optimize() ---------------------------------------------------------

def test_optimize_first_loss_is_the_reference_and_adam_lowers_it():
    from bigdl_tpu.data.dataset import DataSet
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.optim import optim_method
    from bigdl_tpu.optim.metrics import global_metrics
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.trigger import Trigger

    model = fam.build_model(CFG_FILE)
    ids = ids_batch(3, 8)
    x, y = ids[:, :-1], ids[:, 1:]
    v = model.init(jax.random.PRNGKey(13), x[:1])
    first_params = jax.device_get(v["params"])
    before = global_metrics().snapshot()
    losses = []

    def watch(state):
        if state["iteration"] > len(losses):
            losses.append(float(state["loss"]))
        return state["iteration"] >= 3

    opt = Optimizer(model, DataSet.array(x, y), CrossEntropyCriterion(),
                    batch_size=8, seed=5)
    opt.set_optim_method(optim_method.Adam(learning_rate=1e-3))
    opt.set_initial_variables(v)
    opt.set_end_when(Trigger(watch, "three steps"))
    opt.optimize()

    batch = next(iter(opt.dataset.batches(8, shuffle=True, seed=opt.seed,
                                          epoch=1)))
    bx, by = np.asarray(batch["input"]), np.asarray(batch["target"])
    close(losses[0], fam.reference_loss(CFG_FILE, first_params, bx, by), 1e-5)
    assert losses[2] < losses[1] < losses[0]

    after = global_metrics().snapshot()
    delta = lambda k: after["counters"][k] - before["counters"].get(k, 0)
    # 4 expert layers, 8 sequences of T tokens, 2 choices each, 3 steps
    assert delta("moe.routed_pairs") == 3 * 4 * 8 * T * 2
    assert delta("moe.applies") == delta("moe.short_applies") > 0
    assert delta("moe.dropped_pairs") == 0
    assert 0.3 < delta("moe.local_pairs") / delta("moe.routed_pairs") < 0.7
