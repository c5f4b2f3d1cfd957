"""The hyper-connection residual path, YaRN rotary frequencies, latent
attention at unequal key and value widths and the decoder built from them as
one rank of a tensor- and expert-parallel group, each against the plain
float32 reference (``benchmark/families/hc_mla_moe_lm.py``) on seeded
weights, at tiny widths: 1 dense + 2 expert layers, d 64, 4 streams, 8
experts top-2, vocabulary 512."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from bigdl_tpu.models.mla_moe_lm import MLAMoEConfig, MLAMoELM
from bigdl_tpu.nn.attention import LatentAttention, rope, yarn_mscale
from bigdl_tpu.nn.hyper_connection import HyperConnection
from bigdl_tpu.parallel.moe import HeldMoE

fam = harness.load_module("families", "hc_mla_moe_lm")

YARN = dict(type="yarn", factor=64, beta_fast=32, beta_slow=1, mscale=1,
            mscale_all_dim=1, original_max_position_embeddings=16)
TINY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=12,
            intermediate_size=160, moe_intermediate_size=48,
            n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
            first_k_dense_replace=1, routed_scaling_factor=2.0,
            norm_topk_prob=True, rope_theta=1e4, rms_norm_eps=1e-6,
            rope_scaling=YARN, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
            mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
# one rank of 4: 1 of 4 heads, 40 of 160 dense-FFN columns, experts 2-3
CFG_FILE = dict(TINY, family="hc_mla_moe_lm", num_attention_heads=1,
                held_ffn_columns=40, n_routed_experts=2,
                published={"n_routed_experts": 8}, held_experts_first=2,
                correct={"logits_p90_limit": 1e-4})
T = 32


def config(**kw):
    return MLAMoEConfig.from_dict(dict(TINY, **kw))


def close(a, b, tol=2e-5):
    """Both sides are float32 with exact matmuls (tests/conftest.py): they
    differ by the order of float32 sums only."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * max(
        1.0, float(np.abs(b).max())))


def ids_batch(seed, batch, length=T):
    return np.random.default_rng(seed).integers(
        2, TINY["vocab_size"], (batch, length + 1), dtype=np.int32)


def _streams(key, n=4, t=T, d=64):
    """Streams that differ, as they do after the first sublayer."""
    return jax.random.normal(key, (n, 2, t, d)) * jnp.asarray(
        [1.0, 0.5, 2.0, 1.5])[:, None, None, None]


def _hc(c=None):
    c = c or config()
    return HyperConnection(
        c.hc_mult, c.hidden_size, sinkhorn_iters=c.hc_sinkhorn_iters,
        eps=c.hc_eps, clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max))


def _perturbed(p, key, by=0.3):
    leaves, tree = jax.tree_util.tree_flatten(p)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        a + by * jax.random.normal(k, a.shape) * (1 if a.ndim < 2 else
                                                  a.shape[-1] ** -0.5)
        for a, k in zip(leaves, keys)])


# -- the mixing module ----------------------------------------------------------

def test_mixing_forward_and_gradients():
    """``pre`` → a sublayer → ``post`` against the reference's equations
    (streams (T, n, d) there, (n, B, T, d) here), and every gradient."""
    c, hc = config(), _hc()
    X = _streams(jax.random.PRNGKey(0))
    p = _perturbed(hc.init(jax.random.PRNGKey(1))["params"],
                   jax.random.PRNGKey(2))
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 64)) / 8
    f = lambda u: jnp.tanh(u @ w)

    def ours(p, X):
        u, co = hc.pre(p, X)
        return hc.post(X, f(u), co)

    def ref(p, X):          # one sequence at a time, streams (T, n, d)
        return jnp.stack([fam._around(c, p, X[:, b].transpose(1, 0, 2), f,
                                      20, 2.0).transpose(1, 0, 2)
                          for b in range(X.shape[1])], 1)

    close(ours(p, X), ref(p, X))
    cot = jax.random.normal(jax.random.PRNGKey(4), X.shape)
    g_ours = jax.grad(lambda p, X: jnp.sum(ours(p, X) * cot), (0, 1))(p, X)
    g_ref = jax.grad(lambda p, X: jnp.sum(ref(p, X) * cot), (0, 1))(p, X)
    flat = jax.tree_util.tree_flatten_with_path(g_ours)[0]
    want = dict(jax.tree_util.tree_flatten_with_path(g_ref)[0])
    for path, a in flat:
        assert float(jnp.abs(want[path]).max()) > 0, path
        close(a, want[path], 1e-4)


def test_mixing_coefficients_are_what_the_equations_say():
    c, hc = config(), _hc()
    X = _streams(jax.random.PRNGKey(5))
    p = _perturbed(hc.init(jax.random.PRNGKey(6))["params"],
                   jax.random.PRNGKey(7))
    u, co = hc.pre(p, X)
    for b in range(2):
        h_pre, h_post, h_res = fam._mixing(
            c, p, X[:, b].transpose(1, 0, 2), 20, 2.0)
        tok = slice(b * T, (b + 1) * T)
        close(co["pre"][:, tok].T, h_pre)
        close(co["post"][:, tok].T, h_post)
        close(co["res"][:, :, tok].transpose(2, 0, 1), h_res)
        close(u[b], jnp.einsum("ti,tid->td", h_pre,
                               X[:, b].transpose(1, 0, 2)))
    assert float(co["pre"].min()) > 0 and float(co["pre"].max()) < 1
    assert float(co["post"].max()) < 2
    # dynamic at seeded weights: the coefficients differ between tokens
    assert float(jnp.std(co["res"], axis=-1).min()) > 1e-3


@pytest.mark.parametrize("alpha_res,iters,within", [
    (0.75, 20, 1e-3),         # the seeded init
    (3.0, 20, None),          # far from uniform: the iteration is slow
    (0.75, 1, None)])
def test_residual_matrix_is_doubly_stochastic_after_20_iterations(
        alpha_res, iters, within):
    c = config(hc_sinkhorn_iters=iters)
    hc = _hc(c)
    X = _streams(jax.random.PRNGKey(8))
    p = hc.init(jax.random.PRNGKey(9))["params"]
    p = dict(p, alpha=p["alpha"].at[2].set(alpha_res))
    _, co = hc.pre(p, X)
    rows = jnp.abs(jnp.sum(co["res"], axis=1) - 1)
    cols = jnp.abs(jnp.sum(co["res"], axis=0) - 1)
    worst = float(jnp.maximum(rows.max(), cols.max()))
    close(co["err"], worst, 1e-6)               # what the histogram reads
    assert float(co["res"].min()) > 0
    if within is not None:
        assert worst < within
    else:       # the columns were normalised last: the rows carry the rest
        assert worst > 1e-3 and float(cols.max()) < 1e-5


def test_clamp_acts_before_exp():
    """A huge ``alpha_res`` cannot overflow: ``A`` is clipped to +-30."""
    hc = _hc()
    X = _streams(jax.random.PRNGKey(10))
    p = hc.init(jax.random.PRNGKey(11))["params"]
    _, co = hc.pre(dict(p, alpha=p["alpha"].at[2].set(1e4)), X)
    assert bool(jnp.isfinite(co["res"]).all())


# -- YaRN ------------------------------------------------------------------------

def _yarn_by_hand(x, theta, sc):
    """The written formula, pair by pair, in numpy."""
    t, dim = x.shape[-2], x.shape[-1]
    c = lambda beta: (dim * math.log(sc["original_max_position_embeddings"]
                                     / (2 * math.pi * beta))
                      / (2 * math.log(theta)))
    low = max(math.floor(c(sc["beta_fast"])), 0)
    high = min(math.ceil(c(sc["beta_slow"])), dim - 1)
    out = np.array(x, np.float64)
    m = ((0.1 * sc["mscale"] * math.log(sc["factor"]) + 1)
         / (0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1))
    for i in range(dim // 2):
        freq = theta ** (-2 * i / dim)
        mask = 1 - min(max((i - low) / (high - low), 0), 1)
        inv = freq / sc["factor"] * (1 - mask) + freq * mask
        for pos in range(t):
            co, si = m * math.cos(pos * inv), m * math.sin(pos * inv)
            a, b = x[..., pos, i], x[..., pos, i + dim // 2]
            out[..., pos, i] = a * co - b * si
            out[..., pos, i + dim // 2] = b * co + a * si
    return out


@pytest.mark.parametrize("scaling", [
    YARN, dict(YARN, original_max_position_embeddings=4096),   # the cell's
    dict(YARN, mscale=0.7, mscale_all_dim=0.3, factor=8)])
def test_yarn_rope_is_the_written_formula(scaling):
    dim = 64 if scaling["original_max_position_embeddings"] == 4096 else 8
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, dim))
    y = rope(x, 1e4, scaling=scaling)
    close(y, _yarn_by_hand(np.asarray(x), 1e4, scaling), 1e-5)
    close(y, fam._rope(x, 1e4, scaling), 1e-6)
    assert float(jnp.abs(y - rope(x, 1e4)).max()) > 1e-2


def test_yarn_at_factor_1_is_plain_rope():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 8))
    one = dict(YARN, factor=1)
    assert yarn_mscale(1, 1) == 1.0 and yarn_mscale(64, 1) == pytest.approx(
        1.4159, abs=1e-4)
    np.testing.assert_array_equal(np.asarray(rope(x, 1e4, scaling=one)),
                                  np.asarray(rope(x, 1e4)))
    with pytest.raises(ValueError, match="yarn"):
        rope(x, 1e4, scaling=dict(YARN, type="linear"))


@pytest.mark.parametrize("use_flash", [False, True])
def test_mla_at_unequal_widths_with_yarn(use_flash):
    """20-wide keys against 12-wide values, YaRN's frequencies and
    ``mscale ** 2`` in the softmax scale: forward and gradients."""
    c = config()
    attn = LatentAttention(
        c.hidden_size, c.num_attention_heads, q_rank=c.q_lora_rank,
        kv_rank=c.kv_lora_rank, nope_dim=c.qk_nope_head_dim,
        rope_dim=c.qk_rope_head_dim, v_dim=c.v_head_dim,
        rope_theta=c.rope_theta, rope_scaling=c.rope_scaling,
        eps=c.rms_norm_eps, use_flash=use_flash)
    assert attn.sm_scale == pytest.approx(20 ** -0.5 * 1.4159 ** 2, rel=1e-4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, c.hidden_size))
    p = _perturbed(attn.init(jax.random.PRNGKey(2), x)["params"],
                   jax.random.PRNGKey(3), 0.1)

    ours = lambda p, x: attn.forward(p, {}, x)[0]
    ref = lambda p, x: jnp.stack([fam._mla(c, p, s, True, False) for s in x])
    close(ours(p, x), ref(p, x))
    plain = jnp.stack([fam._mla(c, p, s, False, False) for s in x])
    assert float(jnp.abs(ref(p, x) - plain).max()) > 1e-2
    cot = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    g_ours = jax.grad(lambda p, x: jnp.sum(ours(p, x) * cot), (0, 1))(p, x)
    g_ref = jax.grad(lambda p, x: jnp.sum(ref(p, x) * cot), (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_ours),
                    jax.tree_util.tree_leaves(g_ref)):
        close(a, b, 1e-4 if use_flash else 2e-5)


def test_flash_trace_counter_is_booked_at_unequal_widths():
    from bigdl_tpu.optim.metrics import global_metrics
    from bigdl_tpu.ops import flash_attention

    def count():
        return sum(v for k, v in global_metrics().snapshot()[
            "counters"].items() if k.startswith("kernel.flash.traces")
            and "pallas" in k)

    before = count()
    q = jnp.ones((1, 2, 16, 24))
    jax.grad(lambda q: flash_attention(
        q, q, q[..., :16], causal=True, block_q=8, block_k=8,
        interpret=True).sum())(q)
    assert count() - before == 2            # forward and backward


# -- the whole model ---------------------------------------------------------------

def _ref_loss(cfg, params, x, y):
    c = fam._model_config(cfg)
    total = 0.0
    for ids, tgt in zip(x, y):
        e = params["embed"][ids]
        X = jnp.broadcast_to(e[:, None], (len(ids), c.hc_mult, e.shape[-1]))
        for i in range(c.num_hidden_layers):
            X = fam._layer(c, params[f"layer{i}"], X, c.hc_sinkhorn_iters,
                           2.0, True, True, False)
        logp = jax.nn.log_softmax(fam._logits(
            c, params["ln_out"], params["head"], jnp.sum(X, 1), False))
        total = total - jnp.mean(logp[jnp.arange(len(tgt)), tgt])
    return total / len(x)


def test_model_logits_loss_and_gradients():
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion

    model = fam.build_model(CFG_FILE)
    c = model.config
    assert (c.held_experts, c.n_routed_experts, c.num_attention_heads,
            c.held_ffn_columns) == ((2, 2), 8, 1, 40)
    ids = ids_batch(0, 2)
    x, y = ids[:, :-1], ids[:, 1:]
    v = model.init(jax.random.PRNGKey(12), x[:1])
    assert v["params"]["layer0"]["ffn"]["w_gate"].shape == (64, 40)
    assert v["params"]["layer1"]["hc_ffn"]["phi"].shape == (24, 256)
    params = _perturbed(v["params"], jax.random.PRNGKey(13), 0.05)
    logits, _ = model.apply(dict(v, params=params), x)
    assert 0.7 < float(jnp.std(logits)) < 1.4      # a loss that can move
    for b in range(2):
        close(logits[b], fam.reference_logits(CFG_FILE, params, x[b]))

    crit = CrossEntropyCriterion()

    def loss(p):
        out, _ = model.forward(p, v["state"], x, training=True)
        return crit.forward(out, y)

    l, g = jax.value_and_grad(loss)(params)
    close(l, fam.reference_loss(CFG_FILE, params, x, y), 1e-6)
    g_ref = jax.grad(lambda p: _ref_loss(CFG_FILE, p, x, y))(params)
    flat = jax.tree_util.tree_flatten_with_path(g)[0]
    ref = dict(jax.tree_util.tree_flatten_with_path(g_ref)[0])
    assert len(flat) == len(ref)
    for path, a in flat:
        assert float(jnp.abs(ref[path]).max()) > 0, path
        close(a, ref[path], 1e-4)


@pytest.mark.parametrize("ablate", fam.ABLATIONS)
def test_reference_loss_tells_each_ablation_apart(ablate, capsys):
    model = fam.build_model(CFG_FILE)
    ids = ids_batch(4, 2)
    x, y = ids[:, :-1], ids[:, 1:]
    params = model.init(jax.random.PRNGKey(14), x[:1])["params"]
    cfg = dict(CFG_FILE, correct={"logits_p90_limit": 5e-3})
    loss = fam.reference_loss(cfg, params, x, y, ablate)
    assert np.isnan(loss) == (ablate is not None)
    assert f"ok={ablate is None}" in capsys.readouterr().out


def test_one_stream_is_the_additive_block_bit_for_bit():
    """``hc_mult`` absent (or 1) leaves GLM's program alone: the same
    parameter and state trees, and logits bit-identical to ``h + f(h)``
    written out with the model's own sublayers."""
    from tests.test_mla_moe import TINY as GLM_TINY
    from bigdl_tpu.nn.layers import rms_norm
    from bigdl_tpu.nn.module import EMPTY
    from bigdl_tpu.parallel.moe import swiglu

    base = MLAMoEConfig(**GLM_TINY)
    assert base.hc_mult == 1 and base.rope_scaling is None
    model, one = MLAMoELM(base), MLAMoELM(MLAMoEConfig(**GLM_TINY, hc_mult=1))
    assert model.hc is None
    ids = jnp.asarray(ids_batch(1, 2)[:, :-1])
    v = model.init(jax.random.PRNGKey(0), ids[:1])
    assert jax.tree_util.tree_structure(v) == jax.tree_util.tree_structure(
        one.init(jax.random.PRNGKey(0), ids[:1]))
    assert set(v["params"]["layer1"]) == {"ln1", "ln2", "attn", "moe"}
    assert set(v["state"]) == {"layer1", "layer2"}
    assert set(v["state"]["layer1"]) == {"router_bias", "metrics"}
    assert set(v["state"]["layer1"]["metrics"]) == {"counters", "means", "n"}

    def additive(params, state, ids):
        h = jnp.take(params["embed"], ids, axis=0)
        for i in range(base.num_hidden_layers):
            p = params[f"layer{i}"]
            a, _ = model.attn.forward(
                p["attn"], EMPTY, rms_norm(h, p["ln1"], base.rms_norm_eps))
            h = h + a
            x = rms_norm(h, p["ln2"], base.rms_norm_eps)
            if i < base.first_k_dense_replace:
                h = h + swiglu(x, p["ffn"])
            else:
                h = h + model.moe.forward(p["moe"], state[f"layer{i}"], x)[0]
        h = rms_norm(h, params["ln_out"], base.rms_norm_eps)
        return jnp.matmul(h, params["head"],
                          preferred_element_type=jnp.float32)

    for training in (False, True):
        got, _ = jax.jit(lambda p, s, i: model.forward(
            p, s, i, training=training))(v["params"], v["state"], ids)
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(jax.jit(additive)(v["params"], v["state"], ids)))


def test_training_recomputes_every_layer_once():
    model = fam.build_model(CFG_FILE)
    ids = jnp.asarray(ids_batch(3, 1)[:, :-1])
    v = model.init(jax.random.PRNGKey(0), ids)
    run = lambda p, training: model.forward(p, v["state"], ids,
                                            training=training)[0]
    n_remat = lambda training: str(jax.make_jaxpr(
        lambda p: run(p, training))(v["params"])).count("remat")
    assert n_remat(False) == 0
    assert n_remat(True) == model.config.num_hidden_layers
    close(run(v["params"], True), run(v["params"], False), 1e-6)


# -- the share and the whole ---------------------------------------------------------

def test_shares_add_up_to_the_uncut_sublayers():
    """Four ranks: one head, 40 dense-FFN columns and two experts each.  For
    each sublayer, what the ranks give through ``W_o``, ``W_down`` and their
    held experts, with the shared expert and the mixing (computed alike on
    every rank) counted once, adds up to the uncut reference's sublayer."""
    whole = config()                                    # 4 heads, 160, 8
    ranks, c = 4, whole
    h, nope, rp, vd = (c.num_attention_heads, c.qk_nope_head_dim,
                       c.qk_rope_head_dim, c.v_head_dim)
    share = MLAMoELM(config(num_attention_heads=h // ranks,
                            held_ffn_columns=c.intermediate_size // ranks))
    full = MLAMoELM(whole)
    X = _streams(jax.random.PRNGKey(20))
    v = full.init(jax.random.PRNGKey(21), np.zeros((2, T), np.int32))
    dense, sparse = v["params"]["layer0"], v["params"]["layer1"]
    hc = full.hc
    per_head = lambda w, width: w.reshape(w.shape[0], h, width)

    def rank_attn(r):
        a = sparse["attn"]
        cut = lambda w, width: per_head(w, width)[:, r:r + 1].reshape(
            w.shape[0], -1)
        return dict(a, wq_b=cut(a["wq_b"], nope + rp),
                    wkv_b=cut(a["wkv_b"], nope + vd),
                    wo=a["wo"].reshape(h, vd, -1)[r])

    def rank_ffn(r):
        cols = slice(r * 40, (r + 1) * 40)
        f = dense["ffn"]
        return {"w_gate": f["w_gate"][:, cols], "w_up": f["w_up"][:, cols],
                "w_down": f["w_down"][cols]}

    def ref_sublayer(hp, f):        # the uncut reference, per sequence
        return jnp.stack([fam._around(
            c, hp, X[:, b].transpose(1, 0, 2), f, 20, 2.0).transpose(1, 0, 2)
            for b in range(2)], 1)

    # attention: heads through W_o
    u, co = hc.pre(sparse["hc_attn"], X)
    parts = sum(share.attn.forward(rank_attn(r), {}, u)[0]
                for r in range(ranks))
    close(hc.post(X, parts, co), ref_sublayer(
        sparse["hc_attn"], lambda u: fam._mla(c, sparse["attn"], u, True,
                                              False)))
    close(parts, full.attn.forward(sparse["attn"], {}, u)[0])
    # dense FFN: columns through W_down
    u, co = hc.pre(dense["hc_ffn"], X)
    parts = sum(fam._swiglu(u, rank_ffn(r), False) for r in range(ranks))
    close(hc.post(X, parts, co), ref_sublayer(
        dense["hc_ffn"], lambda u: fam._swiglu(u, dense["ffn"], False)))
    # expert layer: held experts, the shared expert once
    u, co = hc.pre(sparse["hc_ffn"], X)
    m = sparse["moe"]
    parts = fam._swiglu(u, m["shared"], False)
    for r in range(ranks):
        held = HeldMoE(8, c.moe_intermediate_size, 2, held=(2 * r, 2),
                       scale=c.routed_scaling_factor)
        sp = {"w_router": m["w_router"],
              "experts": {k: a[2 * r:2 * r + 2]
                          for k, a in m["experts"].items()}}
        parts = parts + held.forward(sp, v["state"]["layer1"], u)[0]

    def ref_moe(u):
        return fam._swiglu(u, m["shared"], False) + fam._routed(
            config(held_experts=(0, 8)), m, u, False)

    close(hc.post(X, parts, co), ref_sublayer(sparse["hc_ffn"], ref_moe))


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
    hist = lambda k: (after["hists"][k]["n"]
                      - before["hists"].get(k, {"n": 0})["n"],
                      after["hists"][k]["sum"]
                      - before["hists"].get(k, {"sum": 0.0})["sum"])
    # one observation a sublayer (two a block) and log point
    n, total = hist("hc.doubly_stochastic_err")
    assert n == 3 * 2 * model.config.num_hidden_layers
    assert 0 < total / n < 1e-3
    n, total = hist("hc.stream_gain")
    assert n == 3 and 1.0 < total / n < 10.0
    assert after["counters"]["moe.dropped_pairs"] == before[
        "counters"].get("moe.dropped_pairs", 0)
