"""Sparse-expert decoder with latent attention (the DeepSeek-V2 lineage;
GLM-4.7-Flash's ``glm4_moe_lite``): how the program's model is built from
the configuration file, seeded data, the FLOP count, and the plain reference.

The configuration file holds ONE chip's share of an expert-parallel job
(``reduced``): ``n_routed_experts`` is the number of experts HELD here
(``held_experts_first`` says from which on), the router keeps its published
width ``published.n_routed_experts``; ``vocab_size`` is the chip's slice.

The reference is the forward pass as published, written out in ``jax.numpy``
at float32 and matmul precision "highest", reading the program's parameter
tree and nothing else of the program:

- pre-norm blocks ``h += MLA(RMSNorm(h))``, ``h += FFN(RMSNorm(h))``, final
  RMSNorm, untied head over the vocabulary slice;
- MLA: ``c_q = RMSNorm(h W_qa)``; ``[q_nope | q_pe] = c_q W_qb`` per head;
  ``[c_kv | k_pe] = h W_kva``, ``c_kv = RMSNorm(c_kv)``; ``[k_nope | v] =
  c_kv W_kvb`` per head; RoPE (rotate-half, theta from the file) on ``q_pe``
  and on the ONE ``k_pe`` all heads share; causal softmax(q k^T / sqrt(nope
  + rope)) v, ONE HEAD AT A TIME (a 4096 x 4096 score tile each) so that it
  fits beside what the optimizer still holds; concatenate, ``W_o``;
- dense FFN and shared expert ``W_down(silu(W_gate x) * W_up x)``;
- router ``s = sigmoid(x W_r^T)`` (``W_r`` one row an expert), the k largest of ``s + b``, weights ``s``
  of the chosen over their sum, times the scaling factor; ``y = Shared(x) +
  sum over chosen AND held experts of w_e Expert_e(x)``, written as a loop
  over the held experts, each applied to every token and weighted by the
  router's weight where the token chose it and by 0 where it did not: no
  sort, no grouped product, no capacity.  What the absent experts would add
  is left out, here as in the program.

One layer's parameters are on the device at a time.

WHAT ``correct`` COMPARES.  The train driver holds the step-1 loss to
``reference_loss`` and has no other hook.  At seeded weights the mean of
8,192 cross-entropies hardly moves when a layer is wrong (a missing routed
expert layer or missing RoPE moves it by as little as bfloat16 rounding
does), so ``reference_loss`` also runs the program's own forward pass on the
same weights, takes each token's distance between its logits and the
reference's (``token_distances``), and holds the 90th percentile of them
over the sequence to the configuration file's ``correct.logits_p90_limit``.
Where a sequence is further away it returns NaN, which the driver's
comparison reads as not correct.  A quantile, and not the RMS over the
sequence (printed beside it): the RMS is carried by the few tokens whose
last expert flipped between near-tied router scores (the reference routes
on float32 activations, the program on what bfloat16 products gave it),
while a wrong layer moves a large share of the tokens.

``ablate`` computes a deliberately WRONG reference, for measuring what those
two limits must catch (the configuration file's ``correct.why``): "routed"
zeroes the routed part of the first expert layer, "routed_last" of the last,
"rope" leaves the rotary embedding out, "float8" rounds every matmul input
to float8_e4m3 (the nearest precision below the configuration's
bfloat16)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops


def _model_config(cfg):
    from bigdl_tpu.models.mla_moe_lm import MLAMoEConfig

    return MLAMoEConfig.from_dict(dict(
        cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
        held_experts=(cfg["held_experts_first"], cfg["n_routed_experts"])))


def build_model(cfg):
    from bigdl_tpu.models.mla_moe_lm import MLAMoELM

    return MLAMoELM(_model_config(cfg))


def make_train_data(cfg, traffic, seed):
    """Seeded token ids from the vocabulary slice; the target is the input
    shifted by one."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(2, cfg["vocab_size"], (traffic["examples"],
                                              traffic["seq_len"] + 1),
                       dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def forward_flops_by_block(cfg, seq):
    """Forward FLOPs of one sequence of ``seq`` tokens, by block (a
    multiply-add is 2).  Attention scores count the causal half.  The routed
    experts are counted AT THEIR EXPECTATION UNDER UNIFORM ROUTING: of a
    token's ``num_experts_per_tok`` choices, held / published land on this
    chip, so ``seq * k * held / published`` (token, expert) pairs are
    multiplied; the run's own count is ``moe.local_pairs``."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rp, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    layers = cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    sparse = layers - dense
    expert = 3 * d * cfg["moe_intermediate_size"]
    proj = (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * (nope + rp)
            + d * (cfg["kv_lora_rank"] + rp)
            + cfg["kv_lora_rank"] * h * (nope + vd) + h * vd * d)
    pairs = (seq * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
             / cfg["published"]["n_routed_experts"])
    return {
        "mla_proj": layers * 2.0 * seq * proj,
        "attn_scores": layers * 1.0 * seq * seq * h * (nope + rp + vd),
        "dense_ffn": dense * 2.0 * seq * 3 * d * cfg["intermediate_size"],
        "shared_expert": sparse * 2.0 * seq * cfg["n_shared_experts"] * expert,
        "router": sparse * 2.0 * seq * d
        * cfg["published"]["n_routed_experts"],
        "routed_experts": sparse * 2.0 * pairs * expert,
        "head": 2.0 * seq * d * cfg["vocab_size"],
    }


def train_flops_per_sample(cfg, traffic):
    return flops.TRAIN_OVER_FORWARD * sum(
        forward_flops_by_block(cfg, traffic["seq_len"]).values())


# -- the plain reference -------------------------------------------------------

def _f8(a):
    """Rounded to float8_e4m3's 4 exponent and 3 mantissa bits.  (A pair
    of converts would do the same, but XLA may drop such a pair as excess
    precision it is allowed to keep; this it may not.)"""
    return jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)


def _mm(a, b, f8):
    return (_f8(a) @ _f8(b)) if f8 else a @ b


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    t, dim = x.shape[-2], x.shape[-1]
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, p, f8, e=None):
    pick = (lambda w: w) if e is None else (lambda w: w[e])
    g = _mm(x, pick(p["w_gate"]), f8)
    u = _mm(x, pick(p["w_up"]), f8)
    return _mm(jax.nn.silu(g) * u, pick(p["w_down"]), f8)


def _mla(c, a, x, rope_on, f8):
    t = x.shape[0]
    h, nope, rp, vd = (c.num_attention_heads, c.qk_nope_head_dim,
                       c.qk_rope_head_dim, c.v_head_dim)
    cq = _rms(_mm(x, a["wq_a"], f8), a["q_norm"], c.rms_norm_eps)
    q = _mm(cq, a["wq_b"], f8).reshape(t, h, nope + rp).transpose(1, 0, 2)
    kv = _mm(x, a["wkv_a"], f8)
    ckv = _rms(kv[:, :c.kv_lora_rank], a["kv_norm"], c.rms_norm_eps)
    k_pe = kv[:, c.kv_lora_rank:]
    kvb = _mm(ckv, a["wkv_b"], f8).reshape(t, h, nope + vd).transpose(1, 0, 2)
    q_pe = q[..., nope:]
    if rope_on:
        q_pe, k_pe = _rope(q_pe, c.rope_theta), _rope(k_pe, c.rope_theta)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = (nope + rp) ** -0.5

    def one_head(args):
        q_nope, q_rot, k_nope, v = args
        s = (_mm(q_nope, k_nope.T, f8) + _mm(q_rot, k_pe.T, f8)) * scale
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return _mm(w, v, f8)

    o = jax.lax.map(one_head, (q[..., :nope], q_pe, kvb[..., :nope],
                               kvb[..., nope:]))
    return _mm(o.transpose(1, 0, 2).reshape(t, h * vd), a["wo"], f8)


def _routed(c, m, x, f8):
    first, count = c.held_experts
    s = jax.nn.sigmoid(x @ m["w_router"].T)        # the router stays float32
    _, idx = jax.lax.top_k(s, c.num_experts_per_tok)      # bias b is zero
    w = jnp.take_along_axis(s, idx, -1)
    if c.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * c.routed_scaling_factor
    y = jnp.zeros_like(x)
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)     # (T,)
        y = y + w_e[:, None] * _swiglu(x, m["experts"], f8, e)
    return y


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5))
def _layer(c, p, x, routed_on, rope_on, f8):
    eps = c.rms_norm_eps
    x = x + _mla(c, p["attn"], _rms(x, p["ln1"], eps), rope_on, f8)
    h = _rms(x, p["ln2"], eps)
    if "ffn" in p:
        return x + _swiglu(h, p["ffn"], f8)
    y = _swiglu(h, p["moe"]["shared"], f8)
    if routed_on:
        y = y + _routed(c, p["moe"], h, f8)
    return x + y


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits(c, ln_out, head, x, f8):
    return _mm(_rms(x, ln_out, c.rms_norm_eps), head, f8)


ABLATIONS = (None, "routed", "routed_last", "rope", "float8")


def reference_logits(cfg, params, ids, ablate=None):
    """Logits (T, vocabulary slice) float32 of one sequence ``ids`` (T,)."""
    if ablate not in ABLATIONS:
        raise ValueError(f"ablate={ablate!r}")
    c = _model_config(cfg)
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), tree)
    zeroed = {"routed": c.first_k_dense_replace,
              "routed_last": c.num_hidden_layers - 1}.get(ablate)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(params["embed"])[np.asarray(ids)],
                        jnp.float32)
        for i in range(c.num_hidden_layers):
            x = _layer(c, f32(params[f"layer{i}"]), x, i != zeroed,
                       ablate != "rope", ablate == "float8")
        return _logits(c, f32(params["ln_out"]), f32(params["head"]), x,
                       ablate == "float8")


def program_logits(cfg, params, x):
    """The program's forward pass (``training=True``, the path the train
    step takes) on the weights ``params``, one sequence of ``x`` (B, T) at a
    time: a list of (T, vocabulary slice) float32 arrays on the host."""
    model = build_model(cfg)
    x = np.asarray(x)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), x[:1])
    state = jax.tree_util.tree_map(        # the correction bias is zero
        lambda a: jnp.zeros(a.shape, a.dtype), shapes["state"])
    forward = jax.jit(lambda p, ids: model.forward(
        p, state, ids[None], training=True)[0][0])
    on_device = jax.device_put(params)
    return [np.asarray(forward(on_device, ids)) for ids in x]


def token_distances(program, reference):
    """(T,) per token: RMS over the vocabulary of ``program - reference``,
    over the standard deviation of the reference's logits."""
    program, reference = np.asarray(program), np.asarray(reference)
    return (np.sqrt(np.mean(np.square(program - reference), -1))
            / reference.std())


def reference_loss(cfg, params, x, y, ablate=None):
    """Mean next-token cross-entropy of a batch ``x`` (B, T) against ``y``
    (B, T), one sequence at a time; NaN where the program's logits of a
    sequence lie further from the reference's than the configuration's
    ``correct`` limit allows (the module's docstring says why)."""
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
    print(f"[bench] family=mla_moe_lm logits_token_distance_p90="
          f"{[round(d, 5) for d in far]} limit={limit} ok={ok} "
          f"rms_over_sequence={[round(r, 5) for r in rms]}", flush=True)
    return total / len(x) if ok else float("nan")
