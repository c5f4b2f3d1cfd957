"""Sparse-expert decoder with latent attention on a hyper-connection
residual path (Xing4.0's ``xing4_0``: the DeepSeek-V2 lineage's block on
``hc_mult`` residual streams, mHC, arXiv:2512.24880): how the program's
model is built from the configuration file, seeded data, the FLOP count, and
the plain reference.

The configuration file holds ONE rank's share of a group that is both
tensor- and expert-parallel (``reduced``): ``num_attention_heads`` counts
the heads HELD here, ``held_ffn_columns`` the dense FFN's columns held of
``intermediate_size``, ``n_routed_experts`` the experts held
(``held_experts_first`` says from which on; the router keeps its published
width ``published.n_routed_experts``), ``vocab_size`` the rank's slice.
What ``W_o`` and ``W_down`` give is this rank's partial sum and goes on
unreduced, as the absent experts' part is left out, in the program and in
the reference alike.  ``make_train_data``, ``program_logits`` and
``token_distances`` are ``families/mla_moe_lm.py``'s, unchanged, and so is
``build_model`` but for one check: a program whose ``MLAMoEConfig`` lacks
the keys this architecture needs cannot run it, and says so at once (it
would otherwise drop them unread and train another model).

The reference is the forward pass written out in ``jax.numpy`` at float32
and matmul precision "highest", one sequence at a time, reading the
program's parameter tree and nothing else of the program.  Streams ``X``
are ``(T, n, d)``, ``n`` = ``hc_mult``; ``X_0[t, i] = Emb[id_t]`` for every
``i``.  Each of a block's two sublayers ``F`` (attention after
``RMSNorm(ln1)``, FFN or expert layer after ``RMSNorm(ln2)``) has mixing
parameters ``phi`` (``(2n + n * n) x n d``, one ROW an output), ``b`` and
``alpha`` (three scalars):

- ``x~ = vec(X[t])``; ``r = sqrt(mean(x~ ** 2) + hc_eps)``; ``m = (phi x~)
  / r``;
- ``H_pre = sigmoid(alpha_0 m[:n] + b[:n])``; ``H_post = 2 sigmoid(alpha_1
  m[n:2n] + b[n:2n])``;
- ``A = clip(alpha_2 m[2n:] + b[2n:], mhc_h_res_clamp_min,
  mhc_h_res_clamp_max)`` as an ``n x n`` matrix, ``M = exp(A)``, then
  ``hc_sinkhorn_iters`` times: every row over its sum, then every column
  over its sum (``hc_eps`` added to each sum); ``H_res = M``;
- ``u = sum_i H_pre[i] X[t, i]``; ``y = F(norm(u))``; ``X'[t, i] = sum_j
  H_res[i, j] X[t, j] + H_post[i] y``.

After the last block ``h = sum_i X[t, i]``, final RMSNorm, untied head over
the vocabulary slice.  ``F`` is ``families/mla_moe_lm.py``'s (its dense
FFN, shared expert, router and held experts are used as they are) but for
attention: keys and queries ``qk_nope_head_dim + qk_rope_head_dim`` wide
against values ``v_head_dim`` wide; rotary frequencies by YaRN as
DeepSeek-V2 publishes it (``inv_freq = freq / factor * (1 - mask) + freq *
mask``, ``mask = 1 - clip((i - low) / (high - low), 0, 1)`` over the rotary
pairs, ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``, ``c(b)
= dim ln(original / (2 pi b)) / (2 ln theta)`` clamped to ``[0, dim - 1]``;
cos and sin times ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)``); softmax scale ``(nope + rope) ** -0.5 * mscale(factor,
mscale_all_dim) ** 2`` with ``mscale(s, m) = 0.1 m ln s + 1``.  Attention
runs one head at a time; one layer's parameters are on the device at a
time.

WHAT ``correct`` COMPARES is what it compares for ``mla_moe_lm``: the
driver holds the step-1 loss to ``reference_loss``, which also holds the
program's logits to the reference's, per sequence the 90th percentile of
``token_distances`` within the configuration's
``correct.logits_p90_limit``, and returns NaN beyond it.

``ablate`` computes a deliberately WRONG reference, for measuring what the
two limits must catch (the configuration file's ``correct.why``):
"sinkhorn1" stops the Sinkhorn iterations after one, "post1" leaves
``H_post`` without its factor 2, "rope_plain" turns by the unscaled
frequencies and leaves ``mscale ** 2`` out of the softmax scale, "routed"
zeroes the routed part of the first expert layer, "float8" rounds every
matmul input to float8_e4m3 (the router and the mixing projection stay
float32, as they do in the program)."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops, harness

_base = harness.load_module("families", "mla_moe_lm")
make_train_data = _base.make_train_data
program_logits = _base.program_logits
token_distances = _base.token_distances
NEEDS = ("hc_mult", "hc_sinkhorn_iters", "rope_scaling", "held_ffn_columns")


def _model_config(cfg):
    from bigdl_tpu.models.mla_moe_lm import MLAMoEConfig

    lacking = sorted(set(NEEDS) - {f.name for f in
                                   dataclasses.fields(MLAMoEConfig)})
    if lacking:
        raise SystemExit(
            f"this program's MLAMoEConfig has no {', '.join(lacking)}: it "
            f"cannot build {cfg.get('model_type', 'this configuration')}")
    return _base._model_config(cfg)


def build_model(cfg):
    _model_config(cfg)
    return _base.build_model(cfg)


_mm, _rms, _swiglu, _routed, _logits = (_base._mm, _base._rms, _base._swiglu,
                                        _base._routed, _base._logits)


def forward_flops_by_block(cfg, seq):
    """Forward FLOPs of one sequence of ``seq`` tokens, by block (a
    multiply-add is 2), of what THIS RANK multiplies: its heads, its
    dense-FFN columns, its experts at their expectation under uniform
    routing, its slice of the head; the mixing whole, as on every rank.
    ``hc_mixing`` = per sublayer the projection (``2n + n * n`` outputs over
    ``n d`` inputs) and the three products (``n d`` for ``u``, ``(n * n + n)
    d`` for ``X'``); the Sinkhorn iterations (``n * n`` values a token) are
    not counted."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rp, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    n = cfg["hc_mult"]
    layers = cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    sparse = layers - dense
    expert = 3 * d * cfg["moe_intermediate_size"]
    proj = (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * (nope + rp)
            + d * (cfg["kv_lora_rank"] + rp)
            + cfg["kv_lora_rank"] * h * (nope + vd) + h * vd * d)
    pairs = (seq * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
             / cfg["published"]["n_routed_experts"])
    mixing = (2 * n + n * n) * n * d + n * d + (n * n + n) * d
    return {
        "mla_proj": layers * 2.0 * seq * proj,
        "attn_scores": layers * 1.0 * seq * seq * h * (nope + rp + vd),
        "dense_ffn": dense * 2.0 * seq * 3 * d * cfg["held_ffn_columns"],
        "shared_expert": sparse * 2.0 * seq * cfg["n_shared_experts"] * expert,
        "router": sparse * 2.0 * seq * d
        * cfg["published"]["n_routed_experts"],
        "routed_experts": sparse * 2.0 * pairs * expert,
        "hc_mixing": 2 * layers * 2.0 * seq * mixing,
        "head": 2.0 * seq * d * cfg["vocab_size"],
    }


def train_flops_per_sample(cfg, traffic):
    return flops.TRAIN_OVER_FORWARD * sum(
        forward_flops_by_block(cfg, traffic["seq_len"]).values())


# -- the plain reference -------------------------------------------------------

def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn(dim, theta, sc):
    """(inv_freq (dim / 2,), the factor on cos and sin) of a ``rope_scaling``
    group of type yarn."""
    def c(beta):
        return (dim * math.log(sc["original_max_position_embeddings"]
                               / (2 * math.pi * beta))
                / (2 * math.log(theta)))

    low = max(math.floor(c(sc["beta_fast"])), 0)
    high = min(math.ceil(c(sc["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * i / dim)
    mask = 1.0 - np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = freq / sc["factor"] * (1.0 - mask) + freq * mask
    return (jnp.asarray(inv_freq, jnp.float32),
            _mscale(sc["factor"], sc["mscale"])
            / _mscale(sc["factor"], sc["mscale_all_dim"]))


def _rope(x, theta, scaling):
    t, dim = x.shape[-2], x.shape[-1]
    half = dim // 2
    if scaling is None:
        freq, on_cos_sin = theta ** (
            -jnp.arange(half, dtype=jnp.float32) * 2.0 / dim), 1.0
    else:
        freq, on_cos_sin = _yarn(dim, theta, scaling)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle) * on_cos_sin, jnp.sin(angle) * on_cos_sin
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(c, a, x, yarn_on, f8):
    t = x.shape[0]
    h, nope, rp, vd = (c.num_attention_heads, c.qk_nope_head_dim,
                       c.qk_rope_head_dim, c.v_head_dim)
    scaling = dict(c.rope_scaling) if c.rope_scaling and yarn_on else None
    cq = _rms(_mm(x, a["wq_a"], f8), a["q_norm"], c.rms_norm_eps)
    q = _mm(cq, a["wq_b"], f8).reshape(t, h, nope + rp).transpose(1, 0, 2)
    kv = _mm(x, a["wkv_a"], f8)
    ckv = _rms(kv[:, :c.kv_lora_rank], a["kv_norm"], c.rms_norm_eps)
    kvb = _mm(ckv, a["wkv_b"], f8).reshape(t, h, nope + vd).transpose(1, 0, 2)
    q_pe = _rope(q[..., nope:], c.rope_theta, scaling)
    k_pe = _rope(kv[:, c.kv_lora_rank:], c.rope_theta, scaling)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = (nope + rp) ** -0.5
    if scaling is not None:
        scale *= _mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2

    def one_head(args):
        q_nope, q_rot, k_nope, v = args
        s = (_mm(q_nope, k_nope.T, f8) + _mm(q_rot, k_pe.T, f8)) * scale
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return _mm(w, v, f8)

    o = jax.lax.map(one_head, (q[..., :nope], q_pe, kvb[..., :nope],
                               kvb[..., nope:]))
    return _mm(o.transpose(1, 0, 2).reshape(t, h * vd), a["wo"], f8)


def _mixing(c, hp, X, iters, post_factor):
    """(H_pre (T, n), H_post (T, n), H_res (T, n, n)) of streams ``X`` (T,
    n, d)."""
    t, n, d = X.shape
    flat = X.reshape(t, n * d)
    r = jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True) + c.hc_eps)
    m = (flat @ hp["phi"].T) / r              # float32, as the router is
    h_pre = jax.nn.sigmoid(hp["alpha"][0] * m[:, :n] + hp["b"][:n])
    h_post = post_factor * jax.nn.sigmoid(
        hp["alpha"][1] * m[:, n:2 * n] + hp["b"][n:2 * n])
    a = jnp.clip(hp["alpha"][2] * m[:, 2 * n:] + hp["b"][2 * n:],
                 c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max)
    mat = jnp.exp(a).reshape(t, n, n)
    for _ in range(iters):
        mat = mat / (jnp.sum(mat, -1, keepdims=True) + c.hc_eps)   # rows
        mat = mat / (jnp.sum(mat, -2, keepdims=True) + c.hc_eps)   # columns
    return h_pre, h_post, mat


def _around(c, hp, X, f, iters, post_factor):
    h_pre, h_post, h_res = _mixing(c, hp, X, iters, post_factor)
    y = f(jnp.einsum("ti,tid->td", h_pre, X))
    return (jnp.einsum("tij,tjd->tid", h_res, X)
            + h_post[:, :, None] * y[:, None, :])


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6, 7))
def _layer(c, p, X, iters, post_factor, yarn_on, routed_on, f8):
    eps = c.rms_norm_eps

    def attn(u):
        return _mla(c, p["attn"], _rms(u, p["ln1"], eps), yarn_on, f8)

    def ffn(u):
        x = _rms(u, p["ln2"], eps)
        if "ffn" in p:
            return _swiglu(x, p["ffn"], f8)
        y = _swiglu(x, p["moe"]["shared"], f8)
        return y + _routed(c, p["moe"], x, f8) if routed_on else y

    X = _around(c, p["hc_attn"], X, attn, iters, post_factor)
    return _around(c, p["hc_ffn"], X, ffn, iters, post_factor)


ABLATIONS = (None, "sinkhorn1", "post1", "rope_plain", "routed", "float8")


def reference_logits(cfg, params, ids, ablate=None):
    """Logits (T, vocabulary slice) float32 of one sequence ``ids`` (T,)."""
    if ablate not in ABLATIONS:
        raise ValueError(f"ablate={ablate!r}")
    c = _model_config(cfg)
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), tree)
    f8 = ablate == "float8"
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(params["embed"])[np.asarray(ids)],
                        jnp.float32)
        X = jnp.broadcast_to(x[:, None, :], (len(x), c.hc_mult, x.shape[-1]))
        for i in range(c.num_hidden_layers):
            X = _layer(c, f32(params[f"layer{i}"]), X,
                       1 if ablate == "sinkhorn1" else c.hc_sinkhorn_iters,
                       1.0 if ablate == "post1" else 2.0,
                       ablate != "rope_plain",
                       not (ablate == "routed"
                            and i == c.first_k_dense_replace), f8)
        return _logits(c, f32(params["ln_out"]), f32(params["head"]),
                       jnp.sum(X, 1), f8)


def reference_loss(cfg, params, x, y, ablate=None):
    """Mean next-token cross-entropy of a batch ``x`` (B, T) against ``y``
    (B, T), one sequence at a time; NaN where the program's logits of a
    sequence lie further from the reference's than the configuration's
    ``correct`` limit allows."""
    limit = cfg["correct"]["logits_p90_limit"]
    total, far, rms = 0.0, [], []
    ours = program_logits(cfg, params, x)
    for ids, target, mine in zip(np.asarray(x), np.asarray(y), ours):
        logits = reference_logits(cfg, params, ids, ablate)
        d = token_distances(mine, logits)
        far.append(float(np.percentile(d, 90)))
        rms.append(float(np.sqrt(np.mean(d * d))))
        logp = jax.nn.log_softmax(logits)
        total += float(-jnp.mean(logp[jnp.arange(len(target)), target]))
    ok = max(far) <= limit
    print(f"[bench] family=hc_mla_moe_lm logits_token_distance_p90="
          f"{[round(d, 5) for d in far]} limit={limit} ok={ok} "
          f"rms_over_sequence={[round(r, 5) for r in rms]}", flush=True)
    return total / len(x) if ok else float("nan")
