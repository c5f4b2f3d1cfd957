"""Hybrid decoder of gated short convolutions and grouped-query attention
over a routed expert layer with no shared expert and a tied head (Liquid
AI's ``lfm2_moe``: LFM2-24B-A2B): how the program's model is built from the
configuration file, seeded data, the FLOP counts, and the plain reference.

The configuration file holds ONE chip's share of an expert-parallel job
(``reduced``): ``num_experts`` is the number of experts HELD here
(``held_experts_first`` says from which on), the router keeps its published
width ``published.num_experts``; ``vocab_size`` is the chip's slice of the
tied matrix.  The operators, the norms, the router and the dense FFN are
whole.  ``make_train_data`` and ``token_distances`` are
``families/mla_moe_lm.py``'s, unchanged, as are the reference's small
parts that know nothing of a block (``_mm``, ``_f8``, ``_rms``, ``_rope``,
``_swiglu``).

The reference is the forward pass written out in ``jax.numpy`` at float32
and matmul precision "highest", one sequence at a time, reading the
program's parameter tree and nothing else of the program.  ``d`` =
``hidden_size``, ``eps`` = ``norm_eps``, ``h_0[t] = Emb[id_t]``; layer ``i``
of kind ``layer_types[i]``:

- ``u = RMSNorm(h; ln1)``; ``h += Op_i(u)``; ``v = RMSNorm(h; ln2)``; ``h
  += FFN_i(v)``;
- ``conv``: ``[B, C, X] = split_3(u W_in)``; ``z = B * X``; ``c[t] = sum_j
  w[j] * z[t - (K - 1) + j]`` with ``z[s] = 0`` for ``s < 0`` (``w`` is
  ``taps`` (K, d), K = ``conv_L_cache``; written as K slices of ``z`` with K
  - 1 rows of zeros put before it); ``Op(u) = (C * c) W_out``;
- ``full_attention``: ``q = u W_q`` as ``num_attention_heads`` heads of
  ``d / num_attention_heads``, ``k = u W_k`` and ``v = u W_v`` as
  ``num_key_value_heads`` heads; RMSNorm over each head's numbers (one
  weight for all query heads, one for all key heads), THEN RoPE
  (rotate-half, theta from ``rope_parameters``) on all dims of q and k;
  causal ``softmax(q k^T / sqrt(head)) v``, ONE KEY/VALUE HEAD AT A TIME
  with the query heads ``j // group == that head``; concatenate, ``W_o``;
- dense FFN (``i < num_dense_layers``) ``W_down(silu(W_gate v) * W_up v)``;
  otherwise ``s = sigmoid(v W_r^T)`` over all experts, the k largest (the
  expert bias is zero), weights ``s`` of the chosen over (their sum + 1e-6)
  times the scaling factor; ``FFN(v) = sum over chosen AND held experts of
  w_e Expert_e(v)``, a loop over the held experts, each applied to every
  token and weighted by the router's weight where the token chose it and by
  0 where it did not.  There is NO shared expert: a token that chose no
  held expert gets exactly zero;
- ``logits = RMSNorm(h; ln_out) Emb^T`` over the vocabulary slice: the head
  is the embedding.

One layer's parameters are on the device at a time.

WHAT ``correct`` COMPARES is what it compares for ``mla_moe_lm``: the
driver holds the step-1 loss to ``reference_loss``, which also runs the
program's own forward pass on the same weights and holds, per sequence, the
90th percentile of ``token_distances`` between its logits and the
reference's to the configuration's ``correct.logits_p90_limit``, returning
NaN beyond it.

``ablate`` computes a deliberately WRONG reference, for measuring what the
two limits must catch (the configuration file's ``correct.why``):
"taps_reversed" applies the taps in the opposite order, "gate_b" leaves the
``B`` gate out, "kv_head" puts query head ``j`` on key/value head ``j %
num_key_value_heads``, "qk_norm" leaves the per-head norms out, "rope" the
rotary embedding, "routed" zeroes the first expert layer's routed part
(with no shared expert: the whole FFN of that layer), "float8" rounds every
matmul input to float8_e4m3 (the router stays float32, as in the
program).

THE FLASH KERNELS' OPERATIONS (``flash_flops_per_step``, read by
``layer_metrics/kernel.flash_roofline.train.json``): the matrix products
the three training kernels run on the unmasked half of the score square,
each ``2 * (T * T / 2) * head * heads`` FLOPs a sequence and attention
layer: 2 in the forward (``q k^T``, ``p v``), 3 in ``dq`` (``q k^T``, ``g
v^T``, ``ds k``), 4 in ``dk/dv`` (``k q^T``, ``p^T g``, ``v g^T``, ``ds^T
q``), and 2 more in the forward that ``jax.checkpoint`` runs again: 11
passes, by the device op that runs them (``FLASH_PASSES``)."""

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
TOPK_SUM_EPS = 1e-6
# matrix products on the unmasked half, by the name the traced device op
# carries: the scope's last word, ``attn_`` for the rematerialised forward
FLASH_PASSES = {"attn": 2 + 3 + 4, "attn_": 2}


def _model_config(cfg):
    try:
        from bigdl_tpu.models.hybrid_moe_lm import HybridMoEConfig
    except ImportError as e:
        raise SystemExit(
            f"this program has no bigdl_tpu.models.hybrid_moe_lm ({e}): it "
            f"cannot build {cfg.get('model_type', 'this configuration')}")
    return HybridMoEConfig.from_dict(dict(
        cfg, num_experts=cfg["published"]["num_experts"],
        held_experts=(cfg["held_experts_first"], cfg["num_experts"])))


def build_model(cfg):
    c = _model_config(cfg)
    from bigdl_tpu.models.hybrid_moe_lm import HybridMoELM

    return HybridMoELM(c)


def forward_flops_by_block(cfg, seq):
    """Forward FLOPs of one sequence of ``seq`` tokens, by block (a
    multiply-add is 2).  A conv operator is its two matmuls (``d x 3d``, ``d
    x d``) and, a token and channel, the two gates and the K taps.
    Attention scores count the causal half.  The routed experts are counted
    AT THEIR EXPECTATION UNDER UNIFORM ROUTING: of a token's
    ``num_experts_per_tok`` choices, held / published land on this chip;
    the run's own count is ``moe.local_pairs``."""
    d, h, h_kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    head = d // h
    kinds = cfg["layer_types"]
    convs, attns = kinds.count("conv"), kinds.count("full_attention")
    dense = cfg["num_dense_layers"]
    sparse = cfg["num_hidden_layers"] - dense
    expert = 3 * d * cfg["moe_intermediate_size"]
    pairs = (seq * cfg["num_experts_per_tok"] * cfg["num_experts"]
             / cfg["published"]["num_experts"])
    return {
        "conv_op": convs * seq * (2.0 * (d * 3 * d + d * d)
                                  + (2 + 2 * cfg["conv_L_cache"]) * d),
        "attn_proj": attns * 2.0 * seq * (2 * d * h * head
                                          + 2 * d * h_kv * head),
        "attn_scores": attns * 1.0 * seq * seq * h * (head + head),
        "dense_ffn": dense * 2.0 * seq * 3 * d * cfg["intermediate_size"],
        "router": sparse * 2.0 * seq * d * cfg["published"]["num_experts"],
        "routed_experts": sparse * 2.0 * pairs * expert,
        "head": 2.0 * seq * d * cfg["vocab_size"],
    }


def train_flops_per_sample(cfg, traffic):
    return flops.TRAIN_OVER_FORWARD * sum(
        forward_flops_by_block(cfg, traffic["seq_len"]).values())


def flash_flops_per_step(cfg, traffic):
    """{device op name: FLOPs a training step} of the flash kernels' matrix
    products on the unmasked half (the module's docstring has the
    count)."""
    seq, h = traffic["seq_len"], cfg["num_attention_heads"]
    one_pass = (2.0 * (seq * seq / 2) * (cfg["hidden_size"] // h) * h
                * cfg["layer_types"].count("full_attention")
                * traffic["batch_per_chip"])
    return {op: n * one_pass for op, n in FLASH_PASSES.items()}


# -- the plain reference -------------------------------------------------------

ABLATIONS = (None, "taps_reversed", "gate_b", "kv_head", "qk_norm", "rope",
             "routed", "float8")


def _conv_op(c, p, u, ablate, f8):
    d, taps = c.hidden_size, p["taps"]
    kernel, t = taps.shape[0], u.shape[0]
    bcx = _mm(u, p["w_in"], f8)
    b, gate_c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = x if ablate == "gate_b" else b * x
    if ablate == "taps_reversed":
        taps = taps[::-1]
    # zp[s + K - 1] = z[s]: rows of zeros stand before the sequence
    zp = jnp.concatenate([jnp.zeros((kernel - 1, d), z.dtype), z], 0)
    conv = sum(taps[j] * zp[j:j + t] for j in range(kernel))
    return _mm(gate_c * conv, p["w_out"], f8)


def _gqa(c, a, u, ablate, f8):
    t = u.shape[0]
    h, h_kv = c.num_attention_heads, c.num_key_value_heads
    head, group = c.hidden_size // h, h // h_kv
    r = _f8 if f8 else (lambda x: x)

    def heads(w, n):
        return _mm(u, w, f8).reshape(t, n, head).transpose(1, 0, 2)

    q, k, v = heads(a["wq"], h), heads(a["wk"], h_kv), heads(a["wv"], h_kv)
    if ablate != "qk_norm":
        q = _rms(q, a["q_norm"], c.norm_eps)
        k = _rms(k, a["k_norm"], c.norm_eps)
    if ablate != "rope":
        q, k = _rope(q, c.rope_theta), _rope(k, c.rope_theta)
    # (h_kv, group, t, head): the query heads of each key/value head
    if ablate == "kv_head":          # head j on key/value head j % h_kv
        q = q.reshape(group, h_kv, t, head).transpose(1, 0, 2, 3)
    else:                            # head j on key/value head j // group
        q = q.reshape(h_kv, group, t, head)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_kv_head(args):
        q_g, k_g, v_g = args
        s = jnp.einsum("gtd,sd->gts", r(q_g), r(k_g)) * head ** -0.5
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return jnp.einsum("gts,sd->gtd", r(w), r(v_g))

    o = jax.lax.map(one_kv_head, (q, k, v))
    if ablate == "kv_head":
        o = o.transpose(1, 0, 2, 3)
    o = o.reshape(h, t, head).transpose(1, 0, 2).reshape(t, h * head)
    return _mm(o, a["wo"], f8)


def _routed(c, m, x, f8):
    first, count = c.held_experts or (0, c.num_experts)
    s = jax.nn.sigmoid(x @ m["w_router"].T)        # the router stays float32
    _, idx = jax.lax.top_k(s, c.num_experts_per_tok)   # expert bias is zero
    w = jnp.take_along_axis(s, idx, -1)
    if c.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + TOPK_SUM_EPS)
    w = w * c.routed_scaling_factor
    y = jnp.zeros_like(x)
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)     # (T,)
        y = y + w_e[:, None] * _swiglu(x, m["experts"], f8, e)
    return y


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer(c, i, p, x, ablate):
    f8 = ablate == "float8"
    u = _rms(x, p["ln1"], c.norm_eps)
    if c.layer_types[i] == "conv":
        x = x + _conv_op(c, p["conv"], u, ablate, f8)
    else:
        x = x + _gqa(c, p["attn"], u, ablate, f8)
    v = _rms(x, p["ln2"], c.norm_eps)
    if "ffn" in p:
        return x + _swiglu(v, p["ffn"], f8)
    if ablate == "routed" and i == c.num_dense_layers:
        return x
    return x + _routed(c, p["moe"], v, f8)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits(c, ln_out, embed, x, f8):
    return _mm(_rms(x, ln_out, c.norm_eps), embed.T, f8)


def reference_logits(cfg, params, ids, ablate=None):
    """Logits (T, vocabulary slice) float32 of one sequence ``ids`` (T,)."""
    if ablate not in ABLATIONS:
        raise ValueError(f"ablate={ablate!r}")
    c = _model_config(cfg)
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), tree)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(np.asarray(params["embed"])[np.asarray(ids)],
                        jnp.float32)
        for i in range(c.num_hidden_layers):
            x = _layer(c, i, f32(params[f"layer{i}"]), x, ablate)
        return _logits(c, f32(params["ln_out"]), f32(params["embed"]), x,
                       ablate == "float8")


def program_logits(cfg, params, x):
    """The program's forward pass (``training=True``, the path the train
    step takes) on the weights ``params``, one sequence of ``x`` (B, T) at a
    time: a list of (T, vocabulary slice) float32 arrays on the host."""
    model = build_model(cfg)
    x = np.asarray(x)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), x[:1])
    state = jax.tree_util.tree_map(        # the expert bias is zero
        lambda a: jnp.zeros(a.shape, a.dtype), shapes["state"])
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
    print(f"[bench] family=conv_gqa_moe_lm logits_token_distance_p90="
          f"{[round(d, 5) for d in far]} limit={limit} ok={ok} "
          f"rms_over_sequence={[round(r, 5) for r in rms]}", flush=True)
    return total / len(x) if ok else float("nan")
