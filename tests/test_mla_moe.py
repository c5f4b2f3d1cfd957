"""Latent attention, the held-share no-drop expert layer and the decoder
built from them, each against the plain float32 reference
(``benchmark/families/mla_moe_lm.py``) on seeded weights, at tiny widths:
1 dense + 2 expert layers, d 64, 8 experts top-2, vocabulary 512."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from bigdl_tpu.models.mla_moe_lm import MLAMoEConfig, MLAMoELM
from bigdl_tpu.nn.attention import LatentAttention, rope
from bigdl_tpu.parallel.moe import (HeldMoE, _held_rows_apply, _sort_pairs,
                                    held_capacity, held_experts_apply,
                                    route_sigmoid_topk)

fam = harness.load_module("families", "mla_moe_lm")

TINY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
            intermediate_size=160, moe_intermediate_size=48,
            n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
            first_k_dense_replace=1, routed_scaling_factor=1.8,
            norm_topk_prob=True, rope_theta=1e6, rms_norm_eps=1e-5)
T = 32


def config(**kw):
    return MLAMoEConfig(**dict(TINY, **kw))


def close(a, b, tol=2e-5):
    """Both sides are float32 with exact matmuls (tests/conftest.py): they
    differ by the order of float32 sums only."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * max(
        1.0, float(np.abs(b).max())))


def ids_batch(seed, batch, length=T):
    return np.random.default_rng(seed).integers(
        2, TINY["vocab_size"], (batch, length + 1), dtype=np.int32)


# -- latent attention ----------------------------------------------------------

def test_rope_turns_pairs_and_keeps_norms():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 8))
    y = rope(x, theta=1e6)
    close(y[:, 0], x[:, 0])                       # position 0: no turn
    close(jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1))
    close(y, fam._rope(x, 1e6))
    # a relative encoding: <rope(q)_i, rope(k)_j> depends on i - j only
    q = jnp.broadcast_to(x[0, :1], (5, 8))
    k = jnp.broadcast_to(x[1, :1], (5, 8))
    s = rope(q, 1e4) @ rope(k, 1e4).T
    close(jnp.diagonal(s, 1), jnp.full((4,), s[0, 1]))


@pytest.mark.parametrize("use_flash", [False, True])
def test_mla_forward_and_gradients(use_flash):
    c = config()
    attn = LatentAttention(
        c.hidden_size, c.num_attention_heads, q_rank=c.q_lora_rank,
        kv_rank=c.kv_lora_rank, nope_dim=c.qk_nope_head_dim,
        rope_dim=c.qk_rope_head_dim, v_dim=c.v_head_dim,
        rope_theta=c.rope_theta, eps=c.rms_norm_eps, use_flash=use_flash)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, c.hidden_size))
    p = attn.init(jax.random.PRNGKey(2), x)["params"]
    p = jax.tree_util.tree_map(                  # norms off 1, to be seen
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(3), a.shape),
        p)

    def ours(p, x):
        return attn.forward(p, {}, x)[0]

    def ref(p, x):
        return jnp.stack([fam._mla(c, p, s, True, False) for s in x])

    close(ours(p, x), ref(p, x))
    cot = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    g_ours = jax.grad(lambda p, x: jnp.sum(ours(p, x) * cot), (0, 1))(p, x)
    g_ref = jax.grad(lambda p, x: jnp.sum(ref(p, x) * cot), (0, 1))(p, x)
    # the flash backward sums score tiles in another order
    for a, b in zip(jax.tree_util.tree_leaves(g_ours),
                    jax.tree_util.tree_leaves(g_ref)):
        close(a, b, 1e-4 if use_flash else 2e-5)


def test_mla_without_rope_differs():
    """The reference's rope-less ablation is a different function: what the
    benchmark's tolerance is measured against can be told apart."""
    c = config()
    x = jax.random.normal(jax.random.PRNGKey(1), (T, c.hidden_size))
    p = MLAMoELM(c).attn.init(jax.random.PRNGKey(2), x[None])["params"]
    a, b = fam._mla(c, p, x, True, False), fam._mla(c, p, x, False, False)
    assert float(jnp.abs(a - b).max()) > 1e-2


# -- the router ------------------------------------------------------------------

def _router_case(case):
    s = np.array([[0.9, 0.8, 0.3, 0.2], [0.1, 0.6, 0.7, 0.2]], np.float32)
    logit = np.log(s / (1 - s))
    x = np.eye(2, dtype=np.float32)
    w = logit.T.copy()                       # (E, d): x W^T = logit
    bias = np.zeros(4, np.float32)
    kw = dict(k=2, scale=1.0, norm_topk=False)
    want_idx = [[0, 1], [2, 1]]
    want_w = [[0.9, 0.8], [0.7, 0.6]]
    if case == "bias_moves_choice_not_weight":
        bias[3] = 1.0                        # expert 3 now wins everywhere
        want_idx = [[3, 0], [3, 2]]
        want_w = [[0.2, 0.9], [0.2, 0.7]]    # weights: s alone, without b
    elif case == "normalised":
        kw["norm_topk"] = True
        want_w = [[0.9 / 1.7, 0.8 / 1.7], [0.7 / 1.3, 0.6 / 1.3]]
    elif case == "scaled":
        kw.update(norm_topk=True, scale=1.8)
        want_w = [[1.8 * 0.9 / 1.7, 1.8 * 0.8 / 1.7],
                  [1.8 * 0.7 / 1.3, 1.8 * 0.6 / 1.3]]
    return x, w, bias, kw, want_idx, want_w


@pytest.mark.parametrize("case", ["plain", "bias_moves_choice_not_weight",
                                  "normalised", "scaled"])
def test_router(case):
    x, w, bias, kw, want_idx, want_w = _router_case(case)
    idx, wt = route_sigmoid_topk(jnp.asarray(x), jnp.asarray(w),
                                 jnp.asarray(bias), **kw)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    close(wt, np.asarray(want_w, np.float32), 1e-6)


# -- the expert layer ----------------------------------------------------------------

def _moe(c, held=None, shared=True):
    return HeldMoE(c.n_routed_experts, c.moe_intermediate_size,
                   c.num_experts_per_tok, held=held,
                   shared_hidden=c.moe_intermediate_size if shared else 0,
                   scale=c.routed_scaling_factor,
                   norm_topk=c.norm_topk_prob)


def _ref_moe(c, p, x):
    y = fam._routed(c, p, x, False)
    if "shared" in p:
        y = y + fam._swiglu(x, p["shared"], False)
    return y


# a share whose short buffers are shorter than T*k: 2 of 16 experts, 2 a
# token, 128 tokens: 256 pairs, 32 of them held under uniform routing, C 128
SHARE = dict(n_routed_experts=16, held_experts=(4, 2))
SHARE_T = 64


def _counters(state):
    return {k: int(v) for k, v in state["metrics"]["counters"].items()}


@pytest.mark.parametrize("experts,held,length",
                         [(8, (0, 8), T), (8, (2, 4), T),
                          (16, (4, 2), SHARE_T)])
def test_expert_layer_forward_and_gradients(experts, held, length):
    c = config(n_routed_experts=experts, held_experts=held)
    moe = _moe(c, held)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, length, c.hidden_size))
    v = moe.init(jax.random.PRNGKey(6), x)
    p, st = v["params"], v["state"]
    flat = x.reshape(-1, c.hidden_size)
    # the third case is held to the reference in buffers a quarter the size
    pairs = flat.shape[0] * c.num_experts_per_tok
    assert (held_capacity(pairs, held[1], experts) < pairs) == (experts == 16)
    assert _counters(moe.forward(p, st, x)[1])["moe.short_applies"] == 1

    def ours(p, x):
        return moe.forward(p, st, x)[0].reshape(flat.shape)

    close(ours(p, x), _ref_moe(c, p, flat))
    cot = jax.random.normal(jax.random.PRNGKey(7), flat.shape)
    g_ours = jax.grad(lambda p, x: jnp.sum(ours(p, x) * cot), (0, 1))(p, x)
    g_ref = jax.grad(lambda p, x: jnp.sum(_ref_moe(c, p, x.reshape(
        flat.shape)) * cot), (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_ours),
                    jax.tree_util.tree_leaves(g_ref)):
        close(a, b)


@pytest.mark.parametrize("pairs,count,experts,want", [
    (4096 * 4, 8, 64, 4096),         # the Xing cell: a quarter of 16,384
    (8192 * 4, 8, 64, 8192),         # the GLM cell: a quarter of 32,768
    (64, 8, 8, 64), (64, 4, 8, 64),  # every expert, or half: all the pairs
    (256, 2, 16, 128),               # 64 rounded up to the row tile
    (2048, 3, 64, 256),              # 192 rounded up
    (100, 1, 64, 100),               # never more than the pairs
])
def test_capacity_is_twice_the_uniform_share(pairs, count, experts, want):
    assert held_capacity(pairs, count, experts) == want


def _dense_rebuild(params, x, idx, w, held):
    """Every held expert on every token, weighted where the token chose
    it."""
    want = jnp.zeros_like(x)
    for e in range(held[0], held[0] + held[1]):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        want = want + w_e[:, None] * fam._swiglu(x, params, False,
                                                 e - held[0])
    return want


def test_a_step_that_overflows_the_short_buffers_takes_the_whole_path():
    """Every token's first choice is one held expert: 192 pairs or more
    where the short buffers have 128 rows.  All are computed, none is
    dropped, and the counters say which path ran."""
    c = config(**SHARE)
    moe = _moe(c, (4, 2), shared=False)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 192, c.hidden_size))
    v = moe.init(jax.random.PRNGKey(9), x)
    assert held_capacity(192 * 2, 2, 16) == 128
    _, st = moe.forward(v["params"], v["state"], x)
    assert _counters(st)["moe.applies"] == 1
    assert _counters(st)["moe.short_applies"] == 1
    bias = jnp.zeros((16,)).at[5].set(10.0)
    y, st = moe.forward(v["params"], dict(st, router_bias=bias), x)
    idx, w = route_sigmoid_topk(x[0], v["params"]["w_router"], bias, 2, 1.8)
    close(y[0], _dense_rebuild(v["params"]["experts"], x[0], idx, w, (4, 2)))
    m = _counters(st)
    assert m["moe.applies"] == 2 and m["moe.short_applies"] == 1
    assert m["moe.local_pairs"] > 192 and m["moe.dropped_pairs"] == 0


@pytest.mark.parametrize("n_local", [127, 128, 129, 256])
def test_the_boundary_between_the_paths(n_local):
    """128 tokens, 2 choices, 2 of 16 experts held: C = 128.  ``n_local``
    pairs are routed here: 128 still fit, 129 do not, and either way the
    result is every pair's."""
    held, k, tokens = (4, 2), 2, 128
    c = config(**SHARE)
    x = jax.random.normal(jax.random.PRNGKey(30), (tokens, c.hidden_size))
    p = _moe(c, held, shared=False).init(
        jax.random.PRNGKey(31), x[None])["params"]["experts"]
    pair = np.arange(tokens * k).reshape(tokens, k)
    # held pairs alternate between the two held experts, the rest go to
    # experts 0 and 1
    idx = jnp.asarray(np.where(pair < n_local, 4 + pair % 2, pair % 2),
                      jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(32), (tokens, k)) + 0.5
    y, rows, dropped, short = held_experts_apply(p, x, idx, w, held, 16)
    assert int(rows.sum()) == n_local and int(dropped) == 0
    assert bool(short) == (n_local <= 128)
    close(y, _dense_rebuild(p, x, idx, w, held))


def test_both_paths_give_the_same_result_and_gradients():
    """The two branches of the ``cond``, called directly on one input that
    fits both."""
    held, k = (4, 2), 2
    c = config(**SHARE)
    x = jax.random.normal(jax.random.PRNGKey(33), (128, c.hidden_size))
    v = _moe(c, held, shared=False).init(jax.random.PRNGKey(34), x[None])
    idx, w = route_sigmoid_topk(x, v["params"]["w_router"],
                                v["state"]["router_bias"], k, 1.8)
    order, inv, rows = _sort_pairs(idx, held)
    cap = held_capacity(idx.size, held[1], 16)
    assert 0 < int(rows.sum()) <= cap < idx.size
    cot = jax.random.normal(jax.random.PRNGKey(35), x.shape)

    def path(size):
        def f(p, x, w):
            y, dropped = _held_rows_apply(p, x, w, order, inv, rows, size)
            return jnp.sum(y * cot), (y, dropped)
        return jax.value_and_grad(f, (0, 1, 2), has_aux=True)(
            v["params"]["experts"], x, w)

    (_, (y_s, dropped_s)), g_s = path(cap)
    (_, (y_w, dropped_w)), g_w = path(idx.size)
    assert int(dropped_s) == int(dropped_w) == 0
    close(y_s, y_w, 1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_s),
                    jax.tree_util.tree_leaves(g_w)):
        assert float(jnp.abs(b).max()) > 0
        close(a, b, 1e-6)


@pytest.mark.parametrize("experts,held,conds", [(8, None, 0), (8, (2, 4), 0),
                                                (16, (4, 2), 1)])
def test_a_shard_that_holds_every_expert_has_no_conditional(experts, held,
                                                            conds):
    """C = T*k wherever twice the held share is all the pairs: one path,
    and the program has no ``cond`` to choose with."""
    c = config(n_routed_experts=experts)
    moe = _moe(c, held)
    x = jax.random.normal(jax.random.PRNGKey(36), (2, SHARE_T, c.hidden_size))
    v = moe.init(jax.random.PRNGKey(37), x)
    jaxpr = str(jax.make_jaxpr(lambda p: moe.forward(p, v["state"], x)[0])(
        v["params"]))
    assert jaxpr.count("cond[") == conds


def test_no_pair_dropped_when_every_token_chooses_one_expert():
    """The capacity path would keep 1.25 * T * k / E rows of expert 5; this
    one computes all T, and says so in its counters."""
    c = config(held_experts=(4, 4))
    moe = _moe(c, (4, 4), shared=False)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, T, c.hidden_size))
    v = moe.init(jax.random.PRNGKey(9), x)
    bias = jnp.zeros((8,)).at[5].set(10.0)        # everyone's first choice
    st = dict(v["state"], router_bias=bias)
    y, new = moe.forward(v["params"], st, x)
    flat = x[0]
    idx, w = route_sigmoid_topk(flat, v["params"]["w_router"], bias, 2, 1.8)
    assert (np.asarray(idx) == 5).sum() == T
    _, rows, dropped, _ = held_experts_apply(v["params"]["experts"], flat,
                                             idx, w, (4, 4), 8)
    assert int(rows[1]) == T and int(dropped) == 0
    # every token's expert-5 term is in the result
    close(y[0], _dense_rebuild(v["params"]["experts"], flat, idx, w, (4, 4)))
    m = new["metrics"]["counters"]
    assert int(m["moe.routed_pairs"]) == 2 * T
    assert int(m["moe.local_pairs"]) == int(rows.sum()) >= T
    assert int(m["moe.dropped_pairs"]) == 0


# the layer on each of its paths: a share whose buffers hold all pairs (no
# conditional), one whose routing fits the short buffers, and the same with a
# bias that makes its last held expert every token's first choice: more than
# C pairs and fewer than all, so the whole-size buffers have a tail too
PATHS = {"one_path": (8, (2, 4), T, 0.0), "short": (16, (4, 2), SHARE_T, 0.0),
         "whole": (16, (4, 2), SHARE_T, 10.0)}


def _on_path(path, seed):
    """(moe, params, state, x) for a case of ``PATHS``."""
    experts, held, length, push = PATHS[path]
    c = config(n_routed_experts=experts, held_experts=held)
    moe = _moe(c, held, shared=False)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, length, c.hidden_size))
    v = moe.init(jax.random.PRNGKey(seed + 1), x)
    bias = jnp.zeros((experts,)).at[held[0] + held[1] - 1].set(push)
    st = dict(v["state"], router_bias=bias)
    m = _counters(moe.forward(v["params"], st, x)[1])
    assert m["moe.short_applies"] == (path != "whole")
    assert m["moe.dropped_pairs"] == 0
    assert m["moe.local_pairs"] < m["moe.routed_pairs"]
    return moe, v["params"], st, x


@pytest.mark.parametrize("path", list(PATHS))
def test_rows_a_grouped_product_skips_are_counted_as_dropped(monkeypatch, path):
    """``moe.dropped_pairs`` is read off what the grouped products gave
    back: one that leaves the last held expert's rows unserved (as
    ``ragged_dot`` left rows unwritten on the TPU before they were cut off)
    shows as that expert's rows."""
    moe, p, st, x = _on_path(path, 20)
    real = jax.lax.ragged_dot

    def skips_last_group(a, w, sizes, **kw):
        start, end = jnp.sum(sizes[:-1]), jnp.sum(sizes)
        row = jnp.arange(a.shape[0])[:, None]
        return jnp.where((row >= start) & (row < end), 0.0,
                         real(a, w, sizes, **kw))

    monkeypatch.setattr(jax.lax, "ragged_dot", skips_last_group)
    flat = x.reshape(-1, x.shape[-1])
    idx, w = route_sigmoid_topk(flat, p["w_router"], st["router_bias"], 2,
                                1.8)
    _, rows, dropped, short = held_experts_apply(
        p["experts"], flat, idx, w, moe.held, moe.num_experts)
    assert bool(short) == (path != "whole")
    assert int(rows[-1]) > 0 and int(dropped) == int(rows[-1])
    m = _counters(moe.forward(p, st, x)[1])
    assert m["moe.dropped_pairs"] == int(rows[-1])


def test_shares_add_up_to_the_uncut_layer():
    """Four chips hold two experts each.  Their routed parts, plus the
    shared expert counted once, are the uncut reference layer."""
    c = config(held_experts=(0, 8))
    whole = _moe(c)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, T, c.hidden_size))
    v = whole.init(jax.random.PRNGKey(11), x)
    p = v["params"]
    flat = x.reshape(-1, c.hidden_size)
    total = fam._swiglu(flat, p["shared"], False)
    for first in range(0, 8, 2):
        share = _moe(c, (first, 2), shared=False)
        sp = {"w_router": p["w_router"],
              "experts": {k: a[first:first + 2]
                          for k, a in p["experts"].items()}}
        total = total + share.forward(sp, v["state"], x)[0].reshape(
            flat.shape)
    close(total, _ref_moe(c, p, flat))


# -- the whole model --------------------------------------------------------------------

def _ref_loss(c, params, x, y):
    total = 0.0
    for ids, tgt in zip(x, y):
        h = params["embed"][ids]
        for i in range(c.num_hidden_layers):
            h = fam._layer(c, params[f"layer{i}"], h, True, True, False)
        logp = jax.nn.log_softmax(
            fam._logits(c, params["ln_out"], params["head"], h, False))
        total = total - jnp.mean(logp[jnp.arange(len(tgt)), tgt])
    return total / len(x)


CFG_FILE = dict(TINY, published={"n_routed_experts": 8},
                held_experts_first=2, n_routed_experts=4,
                correct={"logits_p90_limit": 1e-4})


def test_model_logits_loss_and_gradients():
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion

    model = fam.build_model(CFG_FILE)
    c = model.config
    assert c.held_experts == (2, 4) and c.n_routed_experts == 8
    ids = ids_batch(0, 2)
    x, y = ids[:, :-1], ids[:, 1:]
    v = model.init(jax.random.PRNGKey(12), x[:1])
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
    flat, _ = jax.tree_util.tree_flatten_with_path(g)
    ref = dict(jax.tree_util.tree_flatten_with_path(g_ref)[0])
    assert len(flat) == len(ref)
    for path, a in flat:
        assert float(jnp.abs(ref[path]).max()) > 0, path
        close(a, ref[path])


@pytest.mark.parametrize("ablate", fam.ABLATIONS)
def test_reference_loss_tells_a_wrong_layer_by_the_logits(ablate, capsys):
    """What decides the cell's ``correct``: against a reference that lacks a
    routed expert layer, RoPE, or bfloat16's mantissa, the program's logits
    are too far away and ``reference_loss`` is NaN; against the reference as
    it is, it is the loss."""
    model = fam.build_model(CFG_FILE)
    ids = ids_batch(4, 2)
    x, y = ids[:, :-1], ids[:, 1:]
    params = model.init(jax.random.PRNGKey(14), x[:1])["params"]
    cfg = dict(CFG_FILE, correct={"logits_p90_limit": 1e-2})
    loss = fam.reference_loss(cfg, params, x, y, ablate)
    assert np.isnan(loss) == (ablate is not None)
    assert f"ok={ablate is None}" in capsys.readouterr().out


def test_training_recomputes_every_layer_and_changes_no_number():
    model = MLAMoELM(config())
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


def test_cross_entropy_integer_labels_match_one_hot():
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion

    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 7, 11)) * 3
    tgt = jax.random.randint(jax.random.PRNGKey(1), (3, 7), 0, 11)
    crit = CrossEntropyCriterion()
    onehot = jax.nn.one_hot(tgt, 11)
    close(crit.forward(logits, tgt), crit.forward(logits, onehot), 1e-6)
    close(jax.grad(crit.forward)(logits, tgt),
          jax.grad(crit.forward)(logits, onehot), 1e-6)


# -- through Optimizer.optimize() ---------------------------------------------------------

def _reference_routing_counts(c, params, batches):
    """(routed, local) pair counts from the plain router on the reference's
    own activations."""
    first, count = c.held_experts
    routed = local = 0
    for x in batches:
        for ids in x:
            h = jnp.asarray(params["embed"])[ids]
            for i in range(c.num_hidden_layers):
                p = params[f"layer{i}"]
                if "moe" in p:
                    a = h + fam._mla(c, p["attn"], fam._rms(
                        h, p["ln1"], c.rms_norm_eps), True, False)
                    s = jax.nn.sigmoid(fam._rms(a, p["ln2"], c.rms_norm_eps)
                                       @ p["moe"]["w_router"].T)
                    idx = np.asarray(jax.lax.top_k(s, 2)[1])
                    routed += idx.size
                    local += int(((idx >= first)
                                  & (idx < first + count)).sum())
                h = fam._layer(c, p, h, True, True, False)
    return routed, local


def test_optimize_first_loss_is_the_reference_and_adam_lowers_it():
    from bigdl_tpu.data.dataset import DataSet
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.optim import optim_method
    from bigdl_tpu.optim.metrics import global_metrics
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.trigger import Trigger

    model = fam.build_model(CFG_FILE)
    c = model.config
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
    # step 1 ran on the first weights: its counts are the reference router's
    # (all 8 virtual devices' shares of the batch, summed by the train step)
    routed, local = _reference_routing_counts(c, first_params, [bx])
    assert routed == 8 * T * 2 * 2
    assert delta("moe.routed_pairs") == 3 * routed
    assert delta("moe.dropped_pairs") == 0
    assert "moe.dropped_pairs" in after["counters"]
    h = after["hists"]["moe.load_imbalance"]
    n0 = before["hists"].get("moe.load_imbalance", {"n": 0})["n"]
    assert h["n"] - n0 == 3 * 2            # one per expert layer and step
    # steps 2 and 3 route on updated weights: bracket the total by step 1's
    assert 0.8 * 3 * local < delta("moe.local_pairs") < 1.2 * 3 * local

    # the same run, one step: exactly the reference's count (the first
    # run's step donated the state's buffers: make the variables again)
    before = after
    v = model.init(jax.random.PRNGKey(13), x[:1])
    opt = Optimizer(model, DataSet.array(x, y), CrossEntropyCriterion(),
                    batch_size=8, seed=5)
    opt.set_optim_method(optim_method.Adam(learning_rate=1e-3))
    opt.set_initial_variables(v)
    opt.set_end_when(Trigger.max_iteration(1))
    opt.optimize()
    after = global_metrics().snapshot()
    assert delta("moe.local_pairs") == local
    assert delta("moe.routed_pairs") == routed


@pytest.mark.parametrize("path", list(PATHS))
def test_rows_of_no_group_may_hold_anything(monkeypatch, path):
    """On the TPU the grouped product leaves the rows past its groups
    unwritten, in the forward pass and in the gradient alike.  Fill them
    with NaN here: neither the result nor any gradient may see it."""
    moe, params, st, x = _on_path(path, 5)
    cot = jax.random.normal(jax.random.PRNGKey(7), x.shape)

    def loss(p, x):
        return jnp.sum(moe.forward(p, st, x)[0] * cot)

    clean = jax.value_and_grad(loss, (0, 1))(params, x)
    real = jax.lax.ragged_dot

    def tail_nan(a, sizes):
        return jnp.where((jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None],
                         a, jnp.nan)

    @jax.custom_vjp
    def dirty(a, w, sizes):
        return tail_nan(real(a, w, sizes), sizes)

    def fwd(a, w, sizes):
        return dirty(a, w, sizes), (a, w, sizes)

    def bwd(res, g):
        a, w, sizes = res
        # what the kernel's own backward would see: only the groups' rows
        g = jnp.where((jnp.arange(g.shape[0]) < jnp.sum(sizes))[:, None],
                      g, 0.0)
        da, dw = jax.vjp(lambda a, w: real(a, w, sizes), a, w)[1](g)
        return tail_nan(da, sizes), dw, None

    dirty.defvjp(fwd, bwd)
    monkeypatch.setattr(
        jax.lax, "ragged_dot",
        lambda a, w, sizes, preferred_element_type=None: dirty(a, w, sizes))
    got = jax.value_and_grad(loss, (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(clean)):
        assert bool(jnp.isfinite(a).all())
        close(a, b)
