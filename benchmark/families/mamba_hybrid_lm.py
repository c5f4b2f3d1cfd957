"""Mamba-2 / attention hybrid decoder (IBM's ``granitemoehybrid`` with no
experts: Granite-4.0-H-Micro): Mamba-2 mixers beside NoPE grouped-query
attention, a dense SwiGLU FFN in every layer, Granite's four scalar
multipliers, a tied head.  How the program's model is built from the
configuration file, seeded data, the FLOP and byte counts, and the plain
reference.

The configuration file holds ONE rank's share of a 4-way tensor-parallel
group (``reduced``): ``num_attention_heads`` is the query heads HELD in the
attention layers (``held_heads_first`` says from which on),
``num_key_value_heads`` the key/value heads held, ``held_ffn_columns`` the
FFN columns held, ``vocab_size`` the rank's slice of the tied matrix.  The
Mamba-2 mixers are whole.  ``make_train_data`` and ``token_distances`` are
``families/mla_moe_lm.py``'s, unchanged, as are ``_mm``, ``_f8``, ``_rms``,
``_rope``, ``_swiglu``.

The reference is the forward pass written out in ``jax.numpy`` at float32
and matmul precision "highest", one sequence at a time, reading the
program's parameter tree and nothing else of the program.  ``d`` =
``hidden_size``, ``eps`` = ``rms_norm_eps``, ``r`` =
``residual_multiplier``:

- ``h_0 = embedding_multiplier · Emb[id]``; layer ``i``: ``h += r ·
  Mix_i(RMSNorm(h; ln1))``, ``h += r · W_down(silu(W_gate v) ⊙ W_up v)``
  with ``v = RMSNorm(h; ln2)``; ``logits = (RMSNorm(h; ln_out) /
  logits_scaling) Emb^T`` over the vocabulary slice;
- ``mamba``: ``[z, xBC, Δ_raw] = u W_in``; ``xBC = SiLU(Σ_j w_j ⊙
  xBC[t − 3 + j] + b)``; ``[x, B, C] = xBC``; ``Δ = softplus(Δ_raw +
  dt_bias)``, ``A = −exp(A_log)``; the recurrence TOKEN BY TOKEN
  (``lax.scan`` over positions, independent of the kernel's chunks): ``S_t
  = exp(Δ_t A) S_{t−1} + (Δ_t x_t) B_tᵀ``, ``y_t = S_t C_t + D x_t`` with
  S (heads, head_dim, state); ``g = RMSNorm(y ⊙ silu(z); norm)`` over all
  ``d_in`` channels; ``Mix = g W_out``;
- ``attention``: ``q = u W_q``, ``k = u W_k``, ``v = u W_v`` (no norm, no
  positions); ``o_t = softmax_{s ≤ t}(attention_multiplier · q_t · k_s)
  v_s`` over the query head's key/value head, in blocks of
  ``QUERY_BLOCK`` queries; ``Mix = o W_o``.

WHAT ``correct`` COMPARES is what it compares for ``mla_moe_lm``: the
driver holds the step-1 loss to ``reference_loss``, which also runs the
program's own forward pass on the same weights and holds, per sequence, the
90th percentile of ``token_distances`` between its logits and the
reference's to the configuration's ``correct.logits_p90_limit``, returning
NaN beyond it.  ``ablate`` computes a deliberately WRONG reference:
"decay_off" (``A`` = 0), "d_skip_off" (no ``D x``), "gate_off" (no
``silu(z)``), "conv_identity" (``xBC = SiLU(xBC)``), "dt_no_softplus"
(``Δ = Δ_raw + dt_bias``), "rope_on" (RoPE on q and k), "scale_head_dim"
(the softmax scale ``head_dim^-0.5``), "float8" (every matmul input rounded
to float8_e4m3).

THE KERNELS' COUNTS (``layer_metrics/kernel.ssd_roofline.train.json``):
``ssd_flops_per_step`` — the SSD kernels' matrix products, by device op
name, as the compiled step runs them (compiled for a described v5e, PERF.md
section 4): the forward kernel under ``ssd_`` (``C Bᵀ`` once a chunk; a
head's ``(G ⊙ L)(Δ⊙x)``, ``C S`` and the state's update); under ``ssd`` the
same forward kernel again, rerun by the layer's ``jax.checkpoint`` (its
output is not kept: that would add 2.68 GB to the step's temporaries), and
the backward's two (the states kernel's update; ``C Bᵀ``, ``B Cᵀ``, ``Σ dG
B`` and ``Cᵀ Σ dG`` once a chunk, a head's four chunk-square and four
state-sized products).  ``ssd_bytes_per_step`` — the bytes those kernels
read and write."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops, harness

_base = harness.load_module("families", "mla_moe_lm")
make_train_data = _base.make_train_data
token_distances = _base.token_distances
_mm, _f8, _rms, _rope, _swiglu = (_base._mm, _base._f8, _base._rms,
                                  _base._rope, _base._swiglu)
QUERY_BLOCK = 512
COMPUTE_BYTES = 2      # bfloat16, the configuration's compute dtype


def _model_config(cfg):
    try:
        from bigdl_tpu.models.hybrid_moe_lm import HybridMoEConfig, \
            LAYER_TYPES
    except ImportError as e:
        raise SystemExit(
            f"this program has no bigdl_tpu.models.hybrid_moe_lm ({e}): it "
            f"cannot build {cfg.get('model_type', 'this configuration')}")
    if "mamba" not in LAYER_TYPES:
        raise SystemExit(
            f"this program's hybrid decoder has no mamba layer (it has "
            f"{LAYER_TYPES}): it cannot build "
            f"{cfg.get('model_type', 'this configuration')}")
    pub = cfg["published"]
    return HybridMoEConfig.from_dict(dict(
        cfg, num_attention_heads=pub["num_attention_heads"],
        num_key_value_heads=pub["num_key_value_heads"],
        held_heads=(cfg["held_heads_first"], cfg["num_attention_heads"])))


def build_model(cfg):
    c = _model_config(cfg)
    from bigdl_tpu.models.hybrid_moe_lm import HybridMoELM

    return HybridMoELM(c)


def _mamba_dims(cfg):
    """(heads, head_dim, state, chunk, d_in)."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return h, p, cfg["mamba_d_state"], cfg["mamba_chunk_size"], h * p


def _chunks(cfg, seq):
    return -(-seq // cfg["mamba_chunk_size"])


def ssd_forward_flops(cfg, seq):
    """The forward kernel's products, one layer and sequence."""
    h, p, n, q, _ = _mamba_dims(cfg)
    return _chunks(cfg, seq) * (2.0 * q * q * n
                                + h * (2.0 * q * q * p + 4.0 * q * n * p))


def ssd_backward_flops(cfg, seq):
    """The backward's two kernels' products, one layer and sequence."""
    h, p, n, q, _ = _mamba_dims(cfg)
    states = h * 2.0 * q * n * p
    grads = 4 * 2.0 * q * q * n + h * (4.0 * q * q * p + 8.0 * q * n * p)
    return _chunks(cfg, seq) * (states + grads)


def forward_flops_by_block(cfg, seq):
    """Forward FLOPs of one sequence of ``seq`` tokens, by block (a
    multiply-add is 2), at the rank's share.  The SSD kernels count their
    own products (``ssd_forward_flops``); attention scores the causal
    half; the convolution, gates and norms nothing."""
    d = cfg["hidden_size"]
    h, _, n, _, d_in = _mamba_dims(cfg)
    kinds = cfg["layer_types"]
    mamba, attn = kinds.count("mamba"), kinds.count("attention")
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["hidden_size"] // cfg["published"][
                         "num_attention_heads"])
    return {
        "mamba_proj": mamba * 2.0 * seq * (d * (2 * d_in + 2 * n + h)
                                           + d_in * d),
        "ssd": mamba * ssd_forward_flops(cfg, seq),
        "ffn": cfg["num_hidden_layers"] * 2.0 * seq * 3 * d
        * cfg["held_ffn_columns"],
        "head": 2.0 * seq * d * cfg["vocab_size"],
        "attn_proj": attn * 2.0 * seq * d * (2 * heads + 2 * kv) * hd,
        "attn_scores": attn * 2.0 * 2 * (seq * (seq + 1) // 2) * hd * heads,
    }


def train_flops_per_sample(cfg, traffic):
    return flops.TRAIN_OVER_FORWARD * sum(
        forward_flops_by_block(cfg, traffic["seq_len"]).values())


def _per_step(cfg, traffic, of):
    return (of(cfg, traffic["seq_len"]) * cfg["layer_types"].count("mamba")
            * traffic["batch_per_chip"])


def ssd_flops_per_step(cfg, traffic):
    """{device op name: FLOPs a training step} of the SSD kernels (the
    module's docstring)."""
    fwd = _per_step(cfg, traffic, ssd_forward_flops)
    return {"ssd": fwd + _per_step(cfg, traffic, ssd_backward_flops),
            "ssd_": fwd}


def ssd_bytes_per_step(cfg, traffic):
    """{device op name: HBM bytes a training step} the SSD kernels read and
    write, each operand once a call: ``Δ⊙x``, ``y``, ``dy``, ``d(Δ⊙x)`` in
    the compute dtype, B and C (and their transposes) in it too, the
    chunk sums and their cotangent, the chunk states and ``dB``, ``dC`` in
    float32."""
    h, p, n, q, d_in = _mamba_dims(cfg)

    def of(seq):
        x = seq * d_in * COMPUTE_BYTES
        bc = seq * n * COMPUTE_BYTES
        cum = seq * h * 4
        states = _chunks(cfg, seq) * h * n * p * 4
        fwd = 2 * x + 2 * bc + cum
        return {"ssd_": fwd,
                "ssd": fwd + (x + bc + cum + states)
                + (3 * x + 4 * bc + 2 * cum + states + 3 * seq * n * 4)}

    count = cfg["layer_types"].count("mamba") * traffic["batch_per_chip"]
    return {k: float(v) * count for k, v in of(traffic["seq_len"]).items()}


# -- the plain reference ------------------------------------------------------

ABLATIONS = (None, "decay_off", "d_skip_off", "gate_off", "conv_identity",
             "dt_no_softplus", "rope_on", "scale_head_dim", "float8")


def _scan(x, dt, a, b, c):
    """y (T, heads, head_dim) of the token-by-token recurrence."""
    def step(s, inputs):
        xt, dtt, bt, ct = inputs
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return s, jnp.einsum("hpn,n->hp", s, ct)

    h, p = x.shape[1:]
    _, y = jax.lax.scan(step, jnp.zeros((h, p, b.shape[-1]), jnp.float32),
                        (x, dt, b, c))
    return y


def _mamba(c, m, u, ablate, f8):
    t = u.shape[0]
    h, p, n = c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state
    d_in = h * p
    zxbcdt = _mm(u, m["w_in"], f8)
    z, xbc = zxbcdt[:, :d_in], zxbcdt[:, d_in:2 * d_in + 2 * n]
    if ablate != "conv_identity":
        k = m["conv_w"].shape[0]
        padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
        xbc = sum(m["conv_w"][j] * padded[j:j + t] for j in range(k)) \
            + m["conv_b"]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :d_in].reshape(t, h, p)
    b, cc = xbc[:, d_in:d_in + n], xbc[:, d_in + n:]
    dt = zxbcdt[:, 2 * d_in + 2 * n:] + m["dt_bias"]
    if ablate != "dt_no_softplus":
        dt = jax.nn.softplus(dt)
    a = jnp.zeros((h,)) if ablate == "decay_off" else -jnp.exp(m["A_log"])
    y = _scan(x, dt, a, b, cc)
    if ablate != "d_skip_off":
        y = y + m["D"][:, None] * x
    g = y.reshape(t, d_in)
    if ablate != "gate_off":
        g = g * jax.nn.silu(z)
    return _mm(_rms(g, m["norm"], c.norm_eps), m["w_out"], f8)


def _attention(c, w, u, ablate, f8):
    t, hd = u.shape[0], c.head_dim
    n = c.held_heads[1]
    group = c.num_attention_heads // c.num_key_value_heads
    g = max(1, n // group)
    q = _mm(u, w["wq"], f8).reshape(t, n, hd).transpose(1, 0, 2)
    k = _mm(u, w["wk"], f8).reshape(t, g, hd).transpose(1, 0, 2)
    v = _mm(u, w["wv"], f8).reshape(t, g, hd).transpose(1, 0, 2)
    if ablate == "rope_on":
        q, k = _rope(q, c.rope_theta), _rope(k, c.rope_theta)
    scale = hd ** -0.5 if ablate == "scale_head_dim" else \
        c.attention_multiplier
    qb = min(QUERY_BLOCK, t)
    while t % qb:
        qb //= 2
    per = n // g

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(t)
        outs = []
        for j in range(g):
            s = jnp.einsum("nqd,sd->nqs", _f8(qi[j * per:(j + 1) * per])
                           if f8 else qi[j * per:(j + 1) * per],
                           _f8(k[j]) if f8 else k[j]) * scale
            s = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
            outs.append(jnp.einsum("nqs,sd->nqd", _f8(s) if f8 else s,
                                   _f8(v[j]) if f8 else v[j]))
        return jnp.concatenate(outs)

    o = jax.lax.map(block, jnp.arange(t // qb))            # (nb, n, qb, hd)
    o = o.transpose(0, 2, 1, 3).reshape(t, n * hd)
    return _mm(o, w["wo"], f8)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer(c, kind, p, x, ablate):
    f8 = ablate == "float8"
    r = c.residual_scale
    u = _rms(x, p["ln1"], c.norm_eps)
    if kind == "mamba":
        x = x + r * _mamba(c, p["mamba"], u, ablate, f8)
    else:
        x = x + r * _attention(c, p["attn"], u, ablate, f8)
    v = _rms(x, p["ln2"], c.norm_eps)
    return x + r * _swiglu(v, p["ffn"], f8)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits(c, ln_out, embed, x, f8):
    return _mm(_rms(x, ln_out, c.norm_eps) / c.head_divisor, embed.T, f8)


def reference_logits(cfg, params, ids, ablate=None):
    """Logits (T, vocabulary slice) float32 of one sequence ``ids`` (T,)."""
    if ablate not in ABLATIONS:
        raise ValueError(f"ablate={ablate!r}")
    c = _model_config(cfg)
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), tree)
    with jax.default_matmul_precision("highest"):
        x = c.scale_emb * jnp.asarray(
            np.asarray(params["embed"])[np.asarray(ids)], jnp.float32)
        for i in range(c.num_hidden_layers):
            x = _layer(c, c.layer_types[i], f32(params[f"layer{i}"]), x,
                       ablate)
        return _logits(c, f32(params["ln_out"]), f32(params["embed"]), x,
                       ablate == "float8")


def program_logits(cfg, params, x):
    """The program's forward pass (``training=True``, the path the train
    step takes) on the weights ``params``, one sequence of ``x`` (B, T) at a
    time: a list of (T, vocabulary slice) float32 arrays on the host."""
    model = build_model(cfg)
    x = np.asarray(x)
    state = jax.eval_shape(model.init, jax.random.PRNGKey(0), x[:1])["state"]
    state = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype),
                                   state)
    forward = jax.jit(lambda p, ids: model.forward(
        p, state, ids[None], training=True)[0][0])
    on_device = jax.device_put(params)
    return [np.asarray(forward(on_device, ids)) for ids in x]


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
    print(f"[bench] family=mamba_hybrid_lm logits_token_distance_p90="
          f"{[round(d, 5) for d in far]} limit={limit} ok={ok} "
          f"rms_over_sequence={[round(r, 5) for r in rms]}", flush=True)
    return total / len(x) if ok else float("nan")
