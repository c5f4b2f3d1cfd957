"""The SSD (Mamba-2 selective scan) kernels, the Mamba-2 mixer and the hybrid
decoder's ``mamba`` layer kind, each against the plain float32
token-by-token recurrence or the family's reference
(``benchmark/families/mamba_hybrid_lm.py``) on seeded weights, at tiny
widths: 3 layers (mamba, attention, mamba), d 64, 4 Mamba heads of 32 with
a state of 16, 8 published attention heads of 8 on 2 key/value heads (4
held), vocabulary 256.  Granite-4.0-H's keys through the config, and the
other hybrid cells' decoders as they were built before the mamba kind."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from bigdl_tpu.models.hybrid_moe_lm import HybridMoEConfig, HybridMoELM
from bigdl_tpu.nn.attention import GroupedQueryAttention
from bigdl_tpu.nn.mamba2 import Mamba2
from bigdl_tpu.ops.ssd import chunk_carry, ssd
from bigdl_tpu.parallel.moe import swiglu

fam = harness.load_module("families", "mamba_hybrid_lm")

# one rank's share: attention heads 0-3 of 8 (key/value head 0), half the FFN
TINY = dict(
    family="mamba_hybrid_lm", model_type="granitemoehybrid", hidden_size=64,
    intermediate_size=96, shared_intermediate_size=96, held_ffn_columns=48,
    layer_types=["mamba", "attention", "mamba"], num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=1, held_heads_first=0,
    vocab_size=256, rms_norm_eps=1e-5, rope_theta=10000,
    position_embedding_type="nope", attention_multiplier=0.125,
    embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
    tie_word_embeddings=True, mamba_n_heads=4, mamba_d_head=32,
    mamba_d_state=16, mamba_n_groups=1, mamba_expand=2, mamba_d_conv=4,
    mamba_chunk_size=64, mamba_conv_bias=True, mamba_proj_bias=False,
    normalization_function="rmsnorm", num_local_experts=0,
    num_experts_per_tok=0,
    published=dict(num_attention_heads=8, num_key_value_heads=2),
    correct={"logits_p90_limit": 1e-4})
# 160 positions: two whole chunks of 64 and a padded third
T = 160


def close(a, b, tol=2e-5):
    """Both sides are float32 with exact matmuls (tests/conftest.py)."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * max(
        1.0, float(np.abs(b).max())))


def normal(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def ids_batch(seed, batch, length=T):
    return np.random.default_rng(seed).integers(
        2, TINY["vocab_size"], (batch, length + 1), dtype=np.int32)


# -- the SSD kernels ----------------------------------------------------------


def plain_scan(x, dt, a_log, b, c, d):
    """The recurrence one position at a time: S (heads, P, N)."""
    a = -jnp.exp(a_log)

    def one(xs, dts, bs, cs):
        def step(s, inputs):
            xt, dtt, bt, ct = inputs
            s = (jnp.exp(dtt * a)[:, None, None] * s
                 + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
            return s, jnp.einsum("hpn,n->hp", s, ct)

        h, p = xs.shape[1:]
        return jax.lax.scan(step, jnp.zeros((h, p, bs.shape[-1])),
                            (xs, dts, bs, cs))[1]

    return jax.vmap(one)(x, dt, b, c) + d[:, None] * x


def scan_inputs(seed, batch, t, heads, p, n, a_log):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (batch, t, heads, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, t, heads)) - 1),
            jnp.asarray(a_log, jnp.float32),
            jax.random.normal(ks[2], (batch, t, n)),
            jax.random.normal(ks[3], (batch, t, n)),
            jnp.linspace(0.5, 1.5, heads)), jax.random.normal(
        ks[4], (batch, t, heads, p))


@pytest.mark.parametrize("batch,t,p,n,chunk,a_log", [
    (1, 128, 8, 16, 32, (0.0, 1.0)),              # whole chunks
    (2, 100, 8, 16, 32, (-0.5, 0.7, 1.4)),        # padded to a chunk
    # a head whose decay underflows within a chunk (A = -e^5: exp(Δ A)
    # under 1e-30 after a few positions), one with A near 0 (-e^-20)
    (1, 96, 4, 8, 32, (5.0, -20.0)),
    # the TPU's shape rule at lane-shaped blocks: (64, 128) per head and
    # chunk, a state of 128, three chunks with the last one padded
    (1, 320, 64, 128, 128, (0.3, 1.2)),
])
def test_ssd_kernels_are_the_token_recurrence(batch, t, p, n, chunk, a_log):
    """Forward and all six gradients (x, Δ, A_log, B, C, D) against
    autodiff of the plain recurrence, in one jitted call each."""
    args, g = scan_inputs(len(a_log), batch, t, len(a_log), p, n, a_log)
    mine = lambda x, dt, al, b, c, d: ssd(x, dt, -jnp.exp(al), b, c, d,
                                          chunk=chunk)

    def value_and_vjp(f):
        def both(args, g):
            out, vjp = jax.vjp(f, *args)
            return out, vjp(g)
        return jax.jit(both)(args, g)

    (y, grads), (y_ref, grads_ref) = (value_and_vjp(f)
                                      for f in (mine, plain_scan))
    close(y, y_ref)
    for got, want in zip(grads, grads_ref):
        assert np.isfinite(np.asarray(got)).all()
        close(got, want, tol=1e-4)


def test_ssd_state_carries_across_chunks_by_its_decay():
    """One write at position 0 reaches every later chunk, decayed by
    exactly ``exp(Σ_{s=1..t} Δ_s A)`` (Δ = 1, A = −2^-6)."""
    t, p, n = 256, 4, 8
    x = jnp.zeros((1, t, 1, p)).at[0, 0, 0, 2].set(1.0)
    b = jnp.zeros((1, t, n)).at[0, 0, 5].set(1.0)
    c = jnp.zeros((1, t, n)).at[0, :, 5].set(1.0)
    a = -jnp.full((1,), 2.0 ** -6)
    y = ssd(x, jnp.ones((1, t, 1)), a, b, c, chunk=64)
    close(y[0, :, 0, 2], jnp.exp(a[0] * jnp.arange(t)))
    assert float(jnp.abs(y[0, :, 0, :2]).max()) == 0.0


def test_chunk_carry_is_the_mean_surviving_share():
    dt = jnp.full((2, 100, 3), 0.5)
    a = -jnp.asarray([0.0, 0.01, 1.0])
    # chunks of 64: 64 and 36 (+ 28 padded) positions of Δ A each
    want = np.mean([np.exp(0.5 * float(-a_h) * -n) for a_h in a
                    for n in (64, 36)])
    assert float(chunk_carry(dt, a, 64)) == pytest.approx(want, rel=1e-6)


# -- the decoder --------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    model = fam.build_model(TINY)
    ids = ids_batch(40, 2)
    v = harness.init_variables(model, 4000400000, ids[:1, :-1])
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
    # Δ without softplus is negative for most heads: the state grows
    # without bound and the reference's logits are not finite
    assert not p90 <= TINY["correct"]["logits_p90_limit"]


def test_reference_loss_is_finite_within_the_limit(tiny, capsys):
    _, ids, v, _ = tiny
    assert np.isfinite(fam.reference_loss(TINY, v["params"], ids[:1, :-1],
                                          ids[:1, 1:]))
    assert "ok=True" in capsys.readouterr().out


def test_counters_count_scans_and_the_carried_share(tiny):
    model, ids, v, _ = tiny
    x = jnp.asarray(ids[:, :-1])
    _, st = jax.jit(model.forward)(v["params"], v["state"], x)
    assert set(st) == {"layer0", "layer2"}
    for i in (0, 2):
        m = st[f"layer{i}"]["mamba"]["metrics"]
        assert int(m["counters"]["ssm.scans"]) == int(m["n"]) == 1
        carry = int(m["fine"]["ssm.chunk_carry"]) / 2 ** 24
        assert 0.0 < carry < 1.0


def test_gradients_reach_every_mamba_parameter_and_scopes_show(tiny):
    model, ids, v, _ = tiny
    x = jnp.asarray(ids[:1, :-1])

    def loss(p):
        logits, _ = model.forward(p, v["state"], x, training=True)
        return jnp.mean(logits ** 2)

    lowered = jax.jit(jax.grad(loss)).lower(v["params"])
    text = lowered.as_text(debug_info=True)
    for scope in ("mamba/proj", "mamba/conv", "mamba/ssd", "mamba/norm",
                  "gqa/proj", "gqa/attn", "lm/dense_ffn", "lm/head"):
        assert scope in text, scope
    grads = lowered.compile()(v["params"])
    for name in ("w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                 "norm", "w_out"):
        assert float(jnp.abs(grads["layer0"]["mamba"][name]).max()) > 0
    for name in ("wq", "wk", "wv", "wo"):
        assert float(jnp.abs(grads["layer1"]["attn"][name]).max()) > 0


def test_granite_scalars_tied_head_and_nope_attention(tiny):
    model, _, v, _ = tiny
    c = model.config
    assert (c.scale_emb, c.residual_scale, c.head_divisor,
            c.attention_multiplier) == (12, 0.22, 8.0, 0.125)
    assert "head" not in v["params"]
    attn = v["params"]["layer1"]["attn"]
    assert set(attn) == {"wq", "wk", "wv", "wo"}          # no QK-norm
    assert attn["wq"].shape == (64, 4 * 8) and attn["wk"].shape == (64, 8)
    assert model.attn.rope_theta is None
    mamba = v["params"]["layer0"]["mamba"]
    assert {k: a.shape for k, a in mamba.items()} == {
        "w_in": (64, 2 * 128 + 2 * 16 + 4), "conv_w": (4, 160),
        "conv_b": (160,), "dt_bias": (4,), "A_log": (4,), "D": (4,),
        "norm": (128,), "w_out": (128, 64)}
    dt = jax.nn.softplus(mamba["dt_bias"])
    assert float(dt.min()) >= 1e-4 and float(dt.max()) <= 0.1 + 1e-6
    a = jnp.exp(mamba["A_log"])
    assert float(a.min()) >= 1.0 and float(a.max()) <= 16.0


# -- the share of a tensor-parallel group -------------------------------------


def test_four_ranks_of_a_layer_add_up_with_the_mixer_counted_once():
    """Four ranks of a Mamba layer (the FFN's columns a quarter each, the
    mixer whole on every rank) and of the attention layer (2 of 8 query
    heads each, within one group of 4, with its key/value head): the
    ranks' partial sums, with what every rank computes alike counted once,
    add up to the uncut layer."""
    u = normal(41, 2, 96, 64)
    whole = GroupedQueryAttention(64, 8, 2, 8, rope_theta=None,
                                  qk_norm_eps=None, sm_scale=0.125)
    p = whole.init(jax.random.PRNGKey(42), u)["params"]
    parts = []
    for r in range(4):
        cols = slice(r * 16, (r + 1) * 16)
        kv = slice((r // 2) * 8, (r // 2 + 1) * 8)
        rank = GroupedQueryAttention(64, 8, 2, 8, rope_theta=None,
                                     qk_norm_eps=None, sm_scale=0.125,
                                     held=(2 * r, 2))
        parts.append(rank.forward(
            dict(wq=p["wq"][:, cols], wk=p["wk"][:, kv], wv=p["wv"][:, kv],
                 wo=p["wo"][cols]), {}, u)[0])
    close(sum(parts), whole.forward(p, {}, u)[0], tol=1e-4)

    cfg = HybridMoEConfig.from_dict(dict(TINY, num_hidden_layers=1,
                                         layer_types=["mamba"],
                                         held_ffn_columns=None))
    lm = HybridMoELM(cfg)
    layer = lm.init(jax.random.PRNGKey(43), np.zeros((1, 8), np.int32))
    p, st = layer["params"]["layer0"], layer["state"]["layer0"]
    h = normal(44, 1, 96, 64)
    full, _ = jax.jit(lambda p, h: lm._layer(0, p, st, h))(p, h)
    quarter = HybridMoELM(HybridMoEConfig.from_dict(dict(
        TINY, num_hidden_layers=1, layer_types=["mamba"],
        held_ffn_columns=24)))
    rank_layer = jax.jit(lambda p, h: quarter._layer(0, p, st, h)[0])

    def share(r):
        return {k: (w[:, r * 24:(r + 1) * 24] if k != "w_down"
                    else w[r * 24:(r + 1) * 24]) for k, w in p["ffn"].items()}

    # h + r Mix(u): what every rank computes alike (an FFN share of zeros)
    alike = rank_layer(dict(p, ffn=jax.tree_util.tree_map(
        jnp.zeros_like, share(0))), h)
    total = -3 * alike
    for r in range(4):
        total = total + rank_layer(dict(p, ffn=share(r)), h)
    close(total, full, tol=1e-4)
    ffn = p["ffn"]
    close(sum(swiglu(u, {k: (w[:, r * 24:(r + 1) * 24] if k != "w_down"
                             else w[r * 24:(r + 1) * 24])
                         for k, w in ffn.items()}) for r in range(4)),
          swiglu(u, ffn), tol=1e-4)


def test_held_heads_must_lie_in_one_group_or_whole_groups():
    GroupedQueryAttention(64, 8, 2, 8, held=(4, 4))
    GroupedQueryAttention(64, 8, 2, 8, held=(2, 2))
    with pytest.raises(ValueError):
        GroupedQueryAttention(64, 8, 2, 8, held=(2, 4))


# -- the configuration --------------------------------------------------------


def test_config_reads_granite_names_and_refuses_what_it_cannot_build():
    c = fam._model_config(TINY)
    assert c.layer_types == ("mamba", "full_attention", "mamba")
    assert (c.norm_eps, c.held_heads, c.intermediate_size) == (1e-5, (0, 4),
                                                              96)
    assert (c.position_embedding_type, c.qk_norm) == ("nope", False)
    assert (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
            c.mamba_chunk_size) == (4, 32, 16, 64)
    base = dict(TINY, num_attention_heads=8, num_key_value_heads=2)
    for bad in (dict(mamba_n_groups=2), dict(mamba_proj_bias=True),
                dict(mamba_conv_bias=False), dict(mamba_d_head=16),
                dict(position_embedding_type="alibi"),
                dict(normalization_function="layernorm")):
        with pytest.raises(ValueError):
            HybridMoEConfig.from_dict(dict(base, **bad))


def test_mixer_alone_is_the_reference_mixer():
    """The module against the family's own mixer expression, no decoder
    around it, at a length the chunks do not divide."""
    m = Mamba2(64, 4, 32, 16, 4, chunk=64)
    u = normal(45, 1, 100, 64)
    v = m.init(jax.random.PRNGKey(46), u)
    c = fam._model_config(TINY)
    close(jax.jit(m.forward)(v["params"], v["state"], u)[0][0],
          fam._mamba(c, v["params"], u[0], None, False), tol=1e-4)


# MiniCPM-SALA's tiny decoder (tests/test_sparse_linear_lm.py's TINY) as it
# was built before the mamba layer kind came: the sha256 of its sorted
# (path, shape, dtype) leaves and a grid of its logits, computed then,
# jitted, with tests/conftest.py's settings; LFM2's is held there
MINICPM_TREE = \
    "e563f908b0871fb24e9ce8264ff67251518e71730e8e6d1d533a51045c59d9cb"
MINICPM_LOGITS = [
    [[-1.334259, -0.556944, 1.386725, 1.597863],
     [-0.821302, -0.199741, -0.052116, 0.334841],
     [-0.353882, 0.127158, 2.086988, -2.019314],
     [0.896927, 1.687254, 1.263127, -0.216531]],
    [[-0.546607, -1.016157, -1.259636, 0.415170],
     [-1.078808, 1.677778, -0.527698, -0.664920],
     [0.942290, -0.429429, -1.294260, 1.951937],
     [0.658618, -0.494699, -0.022464, 2.069435]]]


def test_minicpm_parameter_tree_and_logits_are_as_before():
    sala = harness.load_module("families", "sparse_linear_lm")
    from tests.test_sparse_linear_lm import TINY as SALA

    m = sala.build_model(SALA)
    ids = np.random.default_rng(40).integers(2, 256, (2, 256),
                                             dtype=np.int32)
    v = m.init(jax.random.PRNGKey(40), ids[:1])
    leaves = sorted((jax.tree_util.keystr(p), tuple(a.shape), str(a.dtype))
                    for p, a in jax.tree_util.tree_flatten_with_path(v)[0])
    assert hashlib.sha256(repr(leaves).encode()).hexdigest() == MINICPM_TREE
    logits, _ = jax.jit(m.forward)(v["params"], v["state"], jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(logits)[:, ::64, ::64],
                               MINICPM_LOGITS, atol=2e-6)
    assert not any(k in m.__dict__ for k in ("attn", "mamba"))
