"""Sparse/linear hybrid decoder (OpenBMB's ``minicpm_sala``: MiniCPM-SALA):
lightning (linear) attention layers beside InfLLM-v2 block-sparse
attention layers, a dense SwiGLU FFN in every layer, MiniCPM's muP scales,
an untied head.  How the program's model is built from the configuration
file, seeded data, the FLOP and byte counts, and the plain reference.

The configuration file holds ONE rank's share of an 8-way tensor-parallel
group (``reduced``): ``num_attention_heads`` / ``lightning_nh`` are the
query heads HELD (``held_heads_first`` says from which on: 28-31 of 32),
``num_key_value_heads`` the key/value heads held (1 of 2: the four held
query heads all read head 1), ``held_ffn_columns`` the FFN columns held,
``vocab_size`` the rank's slice.  The published head counts are under
``published``; the decays and the groups are those of the held heads'
published indices.  ``make_train_data`` and ``token_distances`` are
``families/mla_moe_lm.py``'s, unchanged, as are ``_mm``, ``_f8``, ``_rms``,
``_rope``, ``_swiglu``.

The reference is the forward pass written out in ``jax.numpy`` at float32
and matmul precision "highest", one sequence at a time, each mixer in
blocks of ``QUERY_BLOCK`` queries, reading the program's parameter tree and
nothing else of the program.  ``d`` = ``hidden_size``, ``eps`` =
``rms_norm_eps``, ``hd`` = ``head_dim``, ``r = scale_depth /
sqrt(mup_denominator)``:

- ``h_0 = scale_emb · Emb[id]``; layer ``i``: ``u = RMSNorm(h; ln1)``, ``h
  += r · Mix_i(u)``; ``v = RMSNorm(h; ln2)``, ``h += r · W_down(silu(W_gate
  v) ⊙ W_up v)``; ``logits = (RMSNorm(h; ln_out) / (d / dim_model_base))
  W_head^T`` over the vocabulary slice;
- ``lightning-attn``, each held head j of published index j_g: ``q =
  RoPE(RMSNorm_head(u W_q))``, ``k = RoPE(RMSNorm_head(u W_k))``, ``v = u
  W_v``; ``o = ((q k^T) ⊙ D) v / sqrt(hd)`` with ``D[t, s] = exp(−slope
  (t − s))`` for ``s ≤ t``, ``slope = 2^(−8 (j_g + 1) / 32)``; ``Mix =
  W_o(RMSNorm_head(o; o_norm) ⊙ sigmoid(u W_g))``;
- ``minicpm4``: ``q = RMSNorm_head(u W_q)``, ``k = RMSNorm_head(u W_k)``,
  ``v = u W_v``, no RoPE; a sequence of at most ``dense_len`` attends
  densely; a longer one over its selection, written here as its own rule:
  compressed keys by ``lax.reduce_window`` (mean of 32 keys every 16), for
  each query the held heads' softmax over the windows that end at or
  before it, summed; a block's score the ``segment_max`` of the windows
  that start in it; the order a stable ``argsort`` of the block keys (the
  forced blocks first: block 0 and every block meeting ``[t − 2047, t]``;
  then the visible ones by score); the first 64 taken; ``o_t = softmax``
  over keys ``s ≤ t`` in taken blocks of ``q_t · k_s / sqrt(hd)``, times
  ``v``; ``Mix = W_o(o ⊙ sigmoid(u W_g))``.

WHAT ``correct`` COMPARES is what it compares for ``mla_moe_lm``: the
driver holds the step-1 loss to ``reference_loss``, which also runs the
program's own forward pass on the same weights and holds, per sequence, the
90th percentile of ``token_distances`` between its logits and the
reference's to the configuration's ``correct.logits_p90_limit``, returning
NaN beyond it.  ``ablate`` computes a deliberately WRONG reference:
"decay_off" (every slope 0), "dense_select" (the sparse layer attends every
causal key), "local_only" (only the forced blocks), "rope_off" (lightning),
"qk_norm_off" (both mixers), "gate_off" (both output gates),
"out_norm_off" (lightning's output norm), "float8" (every matmul input
rounded to float8_e4m3).

THE KERNELS' COUNTS (``layer_metrics/kernel.*_roofline.train.json``):
``sparse_flops_per_step`` — the sparse kernels' matrix products on the
(query, key) pairs the selection leaves, ``2 · pairs · hd · heads`` a pass:
2 passes in the forward, 3 in ``dq``, 4 in ``dk/dv`` under the device op
``sparse_attn``, the forward's 2 again under ``sparse_attn_`` (recomputed
by ``jax.checkpoint``); ``lightning_bytes_per_step`` — the least HBM bytes
the lightning kernels move: q, k, v read and o written once a forward (4
tensors of ``T · heads · hd`` compute-dtype numbers), q, k, v and ``do``
read and ``dq``, ``dk``, ``dv`` written once a backward (7), under
``lightning`` (4 + 7) and ``lightning_`` (4).  No per-layer metric reads
the second yet: the kernels take ~5 ms of a step, under the tenth name the
reduced trace keeps (PERF.md section 7)."""

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
QUERY_BLOCK = 1024
SPARSE_PASSES = {"sparse_attn": 2 + 3 + 4, "sparse_attn_": 2}
LIGHTNING_TENSORS = {"lightning": 4 + 7, "lightning_": 4}
COMPUTE_BYTES = 2      # bfloat16, the configuration's compute dtype


def _model_config(cfg):
    try:
        from bigdl_tpu.models.hybrid_moe_lm import HybridMoEConfig, \
            LAYER_TYPES
    except ImportError as e:
        raise SystemExit(
            f"this program has no bigdl_tpu.models.hybrid_moe_lm ({e}): it "
            f"cannot build {cfg.get('model_type', 'this configuration')}")
    if "minicpm4" not in LAYER_TYPES:
        raise SystemExit(
            f"this program's hybrid decoder has no minicpm4 or "
            f"lightning-attn layer (it has {LAYER_TYPES}): it cannot build "
            f"{cfg.get('model_type', 'this configuration')}")
    pub = cfg["published"]
    return HybridMoEConfig.from_dict(dict(
        cfg, num_attention_heads=pub["num_attention_heads"],
        num_key_value_heads=pub["num_key_value_heads"],
        lightning_nh=pub["lightning_nh"], lightning_nkv=pub["lightning_nkv"],
        held_heads=(cfg["held_heads_first"], cfg["num_attention_heads"])))


def build_model(cfg):
    c = _model_config(cfg)
    from bigdl_tpu.models.hybrid_moe_lm import HybridMoELM

    return HybridMoELM(c)


def selected_key_pairs(cfg, seq):
    """(query, key) pairs a head of a sparse layer attends in a sequence:
    every causal pair below ``dense_len``, else the keys at or before each
    query in its ``min(topk, visible)`` blocks."""
    s = cfg["sparse_config"]
    if seq <= s["dense_len"]:
        return seq * (seq + 1) // 2
    t = np.arange(seq)
    blk = s["block_size"]
    return int(np.sum(blk * np.minimum(s["topk"], t // blk + 1)
                      - (blk - 1 - t % blk)))


def selection_share(cfg, seq):
    """Selected over visible (query, block) pairs: what
    ``sparse.selected_block_share`` reads at this length."""
    s = cfg["sparse_config"]
    if seq <= s["dense_len"]:
        return 1.0
    visible = np.arange(seq) // s["block_size"] + 1
    return float(np.minimum(visible, s["topk"]).sum() / visible.sum())


def forward_flops_by_block(cfg, seq):
    """Forward FLOPs of one sequence of ``seq`` tokens, by block (a
    multiply-add is 2), at the rank's share.  The lightning recurrence is
    its state form (``k^T v`` into and ``q S`` out of a ``hd x hd`` state a
    token); sparse attention counts the pairs the selection leaves; the
    selection the windows each query sees."""
    d, n, g = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_key_value_heads"])
    hd, lhd = cfg["head_dim"], cfg["lightning_head_dim"]
    kinds = cfg["mixer_types"]
    light, sparse = kinds.count("lightning-attn"), kinds.count("minicpm4")
    s = cfg["sparse_config"]
    windows = 0
    if seq > s["dense_len"]:
        t = np.arange(seq)
        windows = int(np.sum(np.maximum(
            (t - s["kernel_size"] + 1) // s["kernel_stride"] + 1, 0)))
    return {
        "ffn": cfg["num_hidden_layers"] * 2.0 * seq * 3 * d
        * cfg["held_ffn_columns"],
        "head": 2.0 * seq * d * cfg["vocab_size"],
        "lightning_proj": light * 2.0 * seq * 5 * d * n * lhd,
        "lightning_recurrence": light * 2.0 * seq * n * lhd * lhd * 2,
        "sparse_proj": sparse * 2.0 * seq * (3 * d * n * hd
                                             + 2 * d * g * hd),
        "sparse_attn": sparse * 2.0 * 2 * selected_key_pairs(cfg, seq)
        * hd * n,
        "selection": sparse * 2.0 * windows * hd * n,
    }


def train_flops_per_sample(cfg, traffic):
    return flops.TRAIN_OVER_FORWARD * sum(
        forward_flops_by_block(cfg, traffic["seq_len"]).values())


def sparse_flops_per_step(cfg, traffic):
    """{device op name: FLOPs a training step} of the sparse kernels'
    matrix products on the selected pairs (the module's docstring)."""
    one_pass = (2.0 * selected_key_pairs(cfg, traffic["seq_len"])
                * cfg["head_dim"] * cfg["num_attention_heads"]
                * cfg["mixer_types"].count("minicpm4")
                * traffic["batch_per_chip"])
    return {op: n * one_pass for op, n in SPARSE_PASSES.items()}


def lightning_bytes_per_step(cfg, traffic):
    """{device op name: HBM bytes a training step} the lightning kernels
    cannot move less than (the module's docstring)."""
    tensor = (traffic["seq_len"] * cfg["lightning_nh"]
              * cfg["lightning_head_dim"] * COMPUTE_BYTES
              * cfg["mixer_types"].count("lightning-attn")
              * traffic["batch_per_chip"])
    return {op: n * float(tensor) for op, n in LIGHTNING_TENSORS.items()}


# -- the plain reference -------------------------------------------------------

ABLATIONS = (None, "decay_off", "dense_select", "local_only", "rope_off",
             "qk_norm_off", "gate_off", "out_norm_off", "float8")


def _blocks_of_queries(t):
    qb = min(QUERY_BLOCK, t)
    while t % qb:
        qb //= 2
    return qb, t // qb


def _attend(c, q, k, v, weights_of, f8):
    """``o`` (n, t, hd) of q (n, t, hd) against k, v (t, hd), in blocks of
    queries: ``weights_of(block index, scores (n, qb, t))`` gives the
    weights of each query over every key."""
    n, t, hd = q.shape
    qb, nb = _blocks_of_queries(t)

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = jnp.einsum("nqd,sd->nqs", _f8(qi) if f8 else qi,
                       _f8(k) if f8 else k)
        w = weights_of(i, qb, s)
        return jnp.einsum("nqs,sd->nqd", _f8(w) if f8 else w,
                          _f8(v) if f8 else v)

    return jax.lax.map(one, jnp.arange(nb)).transpose(1, 0, 2, 3).reshape(
        n, t, -1)


def _lightning(c, a, u, ablate, f8):
    t, hd = u.shape[0], c.lightning_head_dim or c.head_dim
    first, n = c.held_heads

    def heads(w):
        return _mm(u, w, f8).reshape(t, n, hd).transpose(1, 0, 2)

    q, k, v = heads(a["wq"]), heads(a["wk"]), heads(a["wv"])
    if ablate != "qk_norm_off":
        q, k = _rms(q, a["q_norm"], c.norm_eps), _rms(k, a["k_norm"],
                                                       c.norm_eps)
    if ablate != "rope_off":
        q, k = _rope(q, c.rope_theta), _rope(k, c.rope_theta)
    pos = jnp.arange(t)

    def weights_of(slope):
        def of(i, qb, s):
            lag = (i * qb + jnp.arange(qb))[:, None] - pos[None, :]
            return s * jnp.where(lag >= 0, jnp.exp(
                -slope * jnp.maximum(lag, 0)), 0.0) * hd ** -0.5
        return of

    o = jnp.concatenate([
        _attend(c, q[j:j + 1], k[j], v[j], weights_of(
            0.0 if ablate == "decay_off"
            else 2.0 ** (-8.0 * (first + j + 1) / c.lightning_heads)), f8)
        for j in range(n)])
    o = o.transpose(1, 0, 2)                              # (t, n, hd)
    if ablate != "out_norm_off":
        o = _rms(o, a["o_norm"].reshape(n, hd), c.norm_eps)
    o = o.reshape(t, n * hd)
    if ablate != "gate_off":
        o = o * jax.nn.sigmoid(_mm(u, a["wg"], f8))
    return _mm(o, a["wo"], f8)


def _selection(c, q, k, qb, i, ablate):
    """(qb, blocks) bool: which blocks queries ``i·qb ..`` attend (q: the
    group's held heads, (n, t, hd); k (t, hd))."""
    s = dict(c.sparse_config)
    t, hd = k.shape
    blk, stride, kern = s["block_size"], s["kernel_stride"], s["kernel_size"]
    nblk = t // blk
    pos = i * qb + jnp.arange(qb)
    b = jnp.arange(nblk)
    visible = b[None, :] <= (pos // blk)[:, None]
    forced = visible & ((b[None, :] < s["init_blocks"]) | (
        b[None, :] * blk + blk - 1 >= (pos - s["window_size"] + 1)[:, None]))
    if ablate == "dense_select":
        return visible
    if ablate == "local_only":
        return forced
    kc = jax.lax.reduce_window(k, 0.0, jax.lax.add, (kern, 1), (stride, 1),
                               "VALID") / kern                  # (W, hd)
    w = jnp.arange(kc.shape[0])
    seen = (w * stride + kern - 1)[None, :] <= pos[:, None]     # (qb, W)
    qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
    z = jnp.einsum("nqd,wd->nqw", qi, kc) * hd ** -0.5
    z = jnp.where(seen, z, -jnp.inf)
    p = jnp.where(seen, jax.nn.softmax(z, -1), 0.0).sum(0)     # (qb, W)
    p = jnp.where(seen.any(-1, keepdims=True), p, 0.0)
    score = jax.ops.segment_max(p.T, (w * stride) // blk,
                                num_segments=nblk).T           # (qb, blk)
    key = jnp.where(forced, jnp.inf, jnp.where(visible, score, -jnp.inf))
    order = jnp.argsort(-key, axis=-1, stable=True)[:, :s["topk"]]
    taken = jnp.zeros((qb, nblk), bool).at[
        jnp.arange(qb)[:, None], order].set(True)
    return taken & visible


def _sparse(c, a, u, ablate, f8):
    t, hd = u.shape[0], c.head_dim
    n = c.held_heads[1]
    g = max(1, n // (c.num_attention_heads // c.num_key_value_heads))

    def heads(w, m):
        return _mm(u, w, f8).reshape(t, m, hd).transpose(1, 0, 2)

    q, k, v = heads(a["wq"], n), heads(a["wk"], g), heads(a["wv"], g)
    if ablate != "qk_norm_off":
        q, k = _rms(q, a["q_norm"], c.norm_eps), _rms(k, a["k_norm"],
                                                       c.norm_eps)
    dense = t <= dict(c.sparse_config)["dense_len"]
    blk = dict(c.sparse_config)["block_size"]
    per = n // g
    outs = []
    for j in range(g):                       # one key/value head at a time
        qg = q[j * per:(j + 1) * per]

        def weights_of(i, qb, s, qg=qg, kg=k[j]):
            causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(t)
            if dense:
                mask = causal
            else:
                taken = _selection(c, qg, kg, qb, i, ablate)
                mask = causal & jnp.repeat(taken, blk, axis=1)
            s = jnp.where(mask, s * hd ** -0.5, -jnp.inf)
            return jax.nn.softmax(s, -1)

        outs.append(_attend(c, qg, k[j], v[j], weights_of, f8))
    o = jnp.concatenate(outs).transpose(1, 0, 2).reshape(t, n * hd)
    if ablate != "gate_off":
        o = o * jax.nn.sigmoid(_mm(u, a["wg"], f8))
    return _mm(o, a["wo"], f8)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer(c, kind, p, x, ablate):
    f8 = ablate == "float8"
    r = c.residual_scale
    u = _rms(x, p["ln1"], c.norm_eps)
    if kind == "lightning-attn":
        x = x + r * _lightning(c, p["lightning"], u, ablate, f8)
    else:
        x = x + r * _sparse(c, p["sparse"], u, ablate, f8)
    v = _rms(x, p["ln2"], c.norm_eps)
    return x + r * _swiglu(v, p["ffn"], f8)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits(c, ln_out, head, x, f8):
    return _mm(_rms(x, ln_out, c.norm_eps) / c.head_divisor, head.T, f8)


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
        return _logits(c, f32(params["ln_out"]), f32(params["head"]), x,
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
    print(f"[bench] family=sparse_linear_lm logits_token_distance_p90="
          f"{[round(d, 5) for d in far]} limit={limit} ok={ok} "
          f"rms_over_sequence={[round(r, 5) for r in rms]}", flush=True)
    return total / len(x) if ok else float("nan")
