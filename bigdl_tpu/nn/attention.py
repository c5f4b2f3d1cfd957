"""Attention & Transformer blocks.

Reference analog (unverified — mount empty): ``dllib/nn/Attention.scala``,
``dllib/nn/Transformer.scala`` and the keras-side ``TransformerLayer.scala`` /
``BERT.scala`` (Analytics-Zoo lineage): full O(L²) single-device attention.

TPU-native: attention computed in one fused einsum chain (bf16 in, f32
accumulate), optionally routed through the fused Pallas flash kernel
(``bigdl_tpu.ops.flash_attention``), or — with
``MultiHeadAttention(seq_parallel="ring"|"ulysses")`` traced inside a
shard_map carrying the "seq" axis — through sequence-parallel ring or
all-to-all attention (``bigdl_tpu.parallel``) — capabilities the
reference lacks (SURVEY.md §6.7).
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import init as init_mod
from bigdl_tpu.nn.layers import Dropout, LayerNorm, Linear, rms_norm
from bigdl_tpu.nn.module import EMPTY, Module
from bigdl_tpu.tensor.policy import cast_compute


def _axis_bound(name: str) -> bool:
    """True when ``name`` is a mapped axis in the current trace (i.e. we
    are inside a shard_map/pmap that carries it)."""
    try:
        jax.lax.axis_size(name)
        return True
    except NameError:
        return False


def dot_product_attention(q, k, v, mask=None, dropout_p=0.0, rng=None,
                          training=False, scale=None):
    """q,k,v: (b, heads, len, dim); v's dim may differ.  mask: broadcastable
    to (b, h, lq, lk), True = attend.  ``scale``: what the scores are
    multiplied by (default ``dim ** -0.5``)."""
    d = q.shape[-1]
    qc, kc = cast_compute(q, k)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qc, kc,
                        preferred_element_type=jnp.float32)
    logits = logits / math.sqrt(d) if scale is None else logits * scale
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and training:
        if rng is None:
            raise ValueError(
                "attention dropout needs an rng: pass rng= to forward/apply "
                "when training with dropout_p > 0")
        keep = 1.0 - dropout_p
        weights = weights * jax.random.bernoulli(rng, keep, weights.shape) / keep
    wc, vc = cast_compute(weights, v)
    out = jnp.einsum("bhqk,bhkd->bhqd", wc, vc,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


class MultiHeadAttention(Module):
    """Reference ``nn/Attention.scala`` (multi-head, with q/k/v/out
    projections)."""

    def __init__(self, hidden_size: int, num_heads: int,
                 attn_dropout: float = 0.0, causal: bool = False,
                 weight_init=init_mod.xavier, use_flash=None,
                 seq_parallel: Optional[str] = None,
                 seq_axis: str = "seq", name=None):
        super().__init__(name)
        assert hidden_size % num_heads == 0
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.attn_dropout = attn_dropout
        self.causal = causal
        self.weight_init = weight_init
        # None = auto: the fused Pallas kernel (bigdl_tpu.ops.flash_attention)
        # when on TPU and the mask is none/causal with no attention dropout.
        self.use_flash = use_flash
        # "ring" | "ulysses": run sequence-parallel attention over the
        # mesh's ``seq_axis``.  The module must then be traced INSIDE a
        # shard_map that carries that axis with the sequence dim sharded
        # over it (the parallel/ composition pattern — see
        # tests/test_parallel.py); self-attention only, no extra mask or
        # attention dropout.
        if seq_parallel not in (None, "ring", "ulysses"):
            raise ValueError("seq_parallel: None | 'ring' | 'ulysses'")
        self.seq_parallel = seq_parallel
        self.seq_axis = seq_axis

    def build(self, rng, x, context=None):
        h = self.hidden_size
        d = x.shape[-1]
        dc = d if context is None else context.shape[-1]
        ks = jax.random.split(rng, 4)
        return {
            "wq": self.weight_init(ks[0], (d, h), d, h),
            "wk": self.weight_init(ks[1], (dc, h), dc, h),
            "wv": self.weight_init(ks[2], (dc, h), dc, h),
            "wo": self.weight_init(ks[3], (h, d), h, d),
            "bq": jnp.zeros((h,)), "bk": jnp.zeros((h,)),
            "bv": jnp.zeros((h,)), "bo": jnp.zeros((d,)),
        }, EMPTY

    def _split(self, x):
        b, t, _ = x.shape
        return x.reshape(b, t, self.num_heads, self.head_dim).transpose(
            0, 2, 1, 3)

    def forward(self, params, state, x, context=None, training=False,
                rng=None, mask=None):
        ctx = x if context is None else context
        xc = cast_compute(x)
        cc = cast_compute(ctx)
        q = (jnp.matmul(xc, cast_compute(params["wq"]),
                        preferred_element_type=jnp.float32)
             + params["bq"]).astype(x.dtype)
        k = (jnp.matmul(cc, cast_compute(params["wk"]),
                        preferred_element_type=jnp.float32)
             + params["bk"]).astype(x.dtype)
        v = (jnp.matmul(cc, cast_compute(params["wv"]),
                        preferred_element_type=jnp.float32)
             + params["bv"]).astype(x.dtype)
        q, k, v = self._split(q), self._split(k), self._split(v)

        dropout_active = self.attn_dropout > 0.0 and training
        if self.seq_parallel is not None and _axis_bound(self.seq_axis):
            # outside a shard_map carrying the axis (init's shape-inference
            # forward, single-device inference) the plain path below
            # computes the identical function on the full sequence
            if context is not None or mask is not None or dropout_active:
                raise ValueError(
                    "seq_parallel attention supports self-attention with "
                    "no extra mask and no attention dropout")
            if self.seq_parallel == "ring":
                from bigdl_tpu.parallel.ring_attention import ring_attention

                out = ring_attention(q, k, v, axis_name=self.seq_axis,
                                     causal=self.causal)
            else:
                from bigdl_tpu.parallel.ulysses import ulysses_attention

                out = ulysses_attention(q, k, v, axis_name=self.seq_axis,
                                        causal=self.causal)
            return self._merge_project(params, x, out)
        flash_ok = mask is None and not dropout_active
        if self.use_flash is None:
            from bigdl_tpu.ops.common import on_tpu

            use_flash = flash_ok and on_tpu()
        else:
            use_flash = self.use_flash and flash_ok

        if use_flash:
            from bigdl_tpu.ops.flash_attention import flash_attention

            out = flash_attention(q, k, v, causal=self.causal)
        else:
            attn_mask = mask
            if self.causal:
                lq, lk = q.shape[2], k.shape[2]
                cmask = jnp.tril(jnp.ones((lq, lk), bool))
                attn_mask = cmask if attn_mask is None else (attn_mask & cmask)

            out = dot_product_attention(
                q, k, v, mask=attn_mask, dropout_p=self.attn_dropout, rng=rng,
                training=training)
        return self._merge_project(params, x, out)

    def _merge_project(self, params, x, out):
        b, h, t, dh = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(b, t, h * dh)
        y = (jnp.matmul(cast_compute(out), cast_compute(params["wo"]),
                        preferred_element_type=jnp.float32)
             + params["bo"]).astype(x.dtype)
        return y, EMPTY


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature, ``0.1 * mscale * ln(factor) + 1``
    (1 where nothing is stretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn_frequencies(freq, dim: int, theta: float, scaling: dict):
    """(inv_freq, mscale) from the plain frequencies ``freq`` (dim/2,): YaRN
    (Peng et al., arXiv:2309.00071) as DeepSeek-V2 publishes it.  Pairs
    that turn more than ``beta_fast``
    times over the original context keep their frequency, pairs that turn
    fewer than ``beta_slow`` times are slowed by ``factor``, and a linear
    ramp over the pair index blends the two in between; cos and sin are
    scaled by ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)``."""
    if scaling.get("type", scaling.get("rope_type")) != "yarn":
        raise ValueError(f"rope scaling {scaling!r}: only 'yarn' is known")
    factor = float(scaling["factor"])
    original = scaling["original_max_position_embeddings"]

    def pair_turning(turns):      # the pair that turns ``turns`` times
        return (dim * math.log(original / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(pair_turning(scaling.get("beta_slow", 1))), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = freq / factor * ramp + freq * (1.0 - ramp)
    mscale = (yarn_mscale(factor, scaling.get("mscale", 1.0))
              / yarn_mscale(factor, scaling.get("mscale_all_dim", 0.0)))
    return inv_freq, mscale


def rope(x, theta: float = 10000.0, offset=0, scaling=None):
    """Rotary position embedding over the last axis of ``x`` (..., length,
    dim), positions ``offset .. offset + length - 1`` along axis -2, in
    float32.  Rotate-half pairing: feature ``i`` is rotated with feature
    ``i + dim/2`` by the angle ``pos * theta ** (-2i / dim)``.  (The
    interleaved pairing some checkpoints use is a fixed permutation of the
    projection's columns: same shapes, same work.)  ``scaling``: a
    config's ``rope_scaling`` dict of type "yarn" (the frequencies blended
    by wavelength, :func:`_yarn_frequencies`), or None."""
    length, dim = x.shape[-2], x.shape[-1]
    half = dim // 2
    pos = (jnp.arange(length) + offset).astype(jnp.float32)[:, None]
    freq = jnp.power(float(theta),
                     -jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    mscale = 1
    if scaling is not None:
        freq, mscale = _yarn_frequencies(freq, dim, theta, scaling)
    angle = pos * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if mscale != 1:
        cos, sin = cos * mscale, sin * mscale
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _project(x, w):
    """bf16-in / f32-accumulate matmul under the compute policy."""
    return jnp.matmul(cast_compute(x), cast_compute(w),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _fan_in_normal(key, fan_in, fan_out):
    """A (fan_in, fan_out) matrix N(0, 1/fan_in)."""
    return jax.random.normal(key, (fan_in, fan_out)) * fan_in ** -0.5


# the standard deviation of a NoPE layer's seeded scores without QK-norm
# (GroupedQueryAttention)
NOPE_SCORE_STD = 4.0


def held_share(heads, kv_heads, held):
    """``(first, count, kv_count)`` of a tensor-parallel rank's share
    ``held = (first, count)`` of ``heads`` query heads on ``kv_heads``
    key/value heads (None: all of them): whole groups with their key/value
    heads, or heads of one group with its one."""
    first, count = (0, heads) if held is None else held
    group = heads // kv_heads if kv_heads and heads % kv_heads == 0 else 0
    if not (group and 0 <= first and count > 0 and first + count <= heads) \
            or (first % group or count % group) and (
                first // group != (first + count - 1) // group):
        raise ValueError(f"held heads {held} of {heads} on {kv_heads} "
                         "key/value heads: within one group, or whole "
                         "groups")
    return first, count, max(1, count // group)


def _flash_wanted(use_flash):
    """A module's ``use_flash``: None = the Pallas flash kernels on a TPU,
    XLA's attention elsewhere."""
    if use_flash is None:
        from bigdl_tpu.ops.common import on_tpu

        return on_tpu()
    return use_flash


class LatentAttention(Module):
    """Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434
    §2.1), causal self-attention in its expanded (training) form.

    Queries go through a low-rank pair ``wq_a`` (d, q_rank) → RMSNorm →
    ``wq_b`` (q_rank, heads * (nope + rope)).  Keys and values share one
    joint projection ``wkv_a`` (d, kv_rank + rope): the first ``kv_rank``
    columns are the latent, normalised and expanded by ``wkv_b`` (kv_rank,
    heads * (nope + v_dim)) to per-head no-position keys and values; the
    last ``rope`` columns are ONE rotary key shared by every head.  Only the
    rotary slices of q and k carry positions.  Scores are scaled by
    ``(nope + rope) ** -0.5``, times ``yarn_mscale(factor,
    mscale_all_dim) ** 2`` under a YaRN ``rope_scaling``.  No biases.
    ``v_dim`` need not be ``nope + rope``: the flash kernels take keys and
    values of different widths.

    The latent (``kv_rank + rope`` per token) is what a decode cache would
    hold; this module does not cache — serving through the paged engine
    needs an absorbed decode path that the repo does not have yet."""

    def __init__(self, hidden_size: int, num_heads: int, *, q_rank: int,
                 kv_rank: int, nope_dim: int, rope_dim: int, v_dim: int,
                 rope_theta: float = 10000.0, rope_scaling=None,
                 eps: float = 1e-6, use_flash=None, name=None):
        super().__init__(name)
        self.hidden_size, self.num_heads = hidden_size, num_heads
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.rope_theta, self.eps = rope_theta, eps
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.sm_scale = (nope_dim + rope_dim) ** -0.5
        if self.rope_scaling:
            self.sm_scale *= yarn_mscale(
                self.rope_scaling["factor"],
                self.rope_scaling.get("mscale_all_dim", 0.0)) ** 2
        # None = the Pallas flash kernel on a TPU, XLA attention elsewhere
        self.use_flash = use_flash

    def build(self, rng, x):
        d, h = self.hidden_size, self.num_heads
        qk = self.nope_dim + self.rope_dim
        ks = jax.random.split(rng, 5)
        w = _fan_in_normal
        return {"wq_a": w(ks[0], d, self.q_rank),
                "q_norm": jnp.ones((self.q_rank,)),
                "wq_b": w(ks[1], self.q_rank, h * qk),
                "wkv_a": w(ks[2], d, self.kv_rank + self.rope_dim),
                "kv_norm": jnp.ones((self.kv_rank,)),
                "wkv_b": w(ks[3], self.kv_rank,
                           h * (self.nope_dim + self.v_dim)),
                "wo": w(ks[4], h * self.v_dim, d)}, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        b, t, _ = x.shape
        h, nope, rp, vd = (self.num_heads, self.nope_dim, self.rope_dim,
                           self.v_dim)
        with jax.named_scope("mla/proj"):
            cq = rms_norm(_project(x, params["wq_a"]), params["q_norm"],
                          self.eps)
            q = _project(cq, params["wq_b"]).reshape(
                b, t, h, nope + rp).transpose(0, 2, 1, 3)
            kv = _project(x, params["wkv_a"])
            ckv = rms_norm(kv[..., :self.kv_rank], params["kv_norm"],
                           self.eps)
            turn = functools.partial(rope, theta=self.rope_theta,
                                     scaling=self.rope_scaling)
            k_pe = turn(kv[..., None, :, self.kv_rank:])
            kv = _project(ckv, params["wkv_b"]).reshape(
                b, t, h, nope + vd).transpose(0, 2, 1, 3)
            q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_pe, (b, h, t, rp))], -1)
            v = kv[..., nope:]
        with jax.named_scope("mla/attn"):
            if _flash_wanted(self.use_flash):
                from bigdl_tpu.ops.flash_attention import flash_attention

                out = flash_attention(q, k, v, causal=True,
                                      sm_scale=self.sm_scale)
            else:
                # no scaling: the default's division, bit for bit as before
                out = dot_product_attention(
                    q, k, v, mask=jnp.tril(jnp.ones((t, t), bool)),
                    scale=None if self.rope_scaling is None
                    else self.sm_scale)
        with jax.named_scope("mla/proj"):
            out = out.transpose(0, 2, 1, 3).reshape(b, t, h * vd)
            return _project(out, params["wo"]), EMPTY


class GroupedQueryAttention(Module):
    """Causal self-attention with grouped-query heads (the attention layers
    of a hybrid decoder: LFM2's ``lfm2_moe``, Granite-4.0-H's
    ``granitemoehybrid``).

    ``q = u W_q`` as ``heads`` heads of ``head_dim``, ``k = u W_k`` and ``v
    = u W_v`` as ``kv_heads`` heads, no biases; with ``qk_norm_eps`` every
    query head and every key head is normalised over its ``head_dim``
    numbers (one weight ``q_norm`` for all query heads, one ``k_norm`` for
    all key heads), THEN, with ``rope_theta``, turned by :func:`rope` over
    all ``head_dim`` dims (None: no positions, "NoPE"); query head ``j``
    attends through key/value head ``j // (heads / kv_heads)``; scores
    scaled by ``sm_scale`` (default ``head_dim ** -0.5``); ``W_o`` (heads *
    head_dim, hidden).

    ``held = (first, count)``: a tensor-parallel rank's share of the query
    heads, whole groups with their key/value heads or heads of one group
    with its one; ``W_q``, ``W_k``, ``W_v`` are those heads' columns,
    ``W_o`` their rows, and the output is the rank's partial sum of the
    layer's (docs/parallelism.md §Held heads).  Without QK-norm, ``W_q`` and
    ``W_k`` are drawn so that the scaled scores of unit-variance inputs have
    standard deviation ``NOPE_SCORE_STD``: at N(0, 1/fan_in) and a scale of
    1/64 they would have 0.125, and every query would average v over its
    whole prefix (PERF.md §4).

    On a TPU the Pallas flash kernels take the grouped heads as they are (K
    and V are not repeated); elsewhere K and V are repeated for
    :func:`dot_product_attention`.  Device scopes ``gqa/proj`` and
    ``gqa/attn``.  This module does not cache: the paged decode kernels
    take one K/V head a query head."""

    def __init__(self, hidden_size: int, num_heads: int, kv_heads: int,
                 head_dim: int, *, rope_theta: Optional[float] = 10000.0,
                 qk_norm_eps: Optional[float] = 1e-6,
                 sm_scale: Optional[float] = None, held=None,
                 use_flash=None, name=None):
        super().__init__(name)
        _, count, kv_count = held_share(num_heads, kv_heads, held)
        self.hidden_size, self.num_heads = hidden_size, count
        self.kv_heads, self.head_dim = kv_count, head_dim
        self.rope_theta, self.qk_norm_eps = rope_theta, qk_norm_eps
        self.sm_scale = sm_scale
        # None = the Pallas flash kernel on a TPU, XLA attention elsewhere
        self.use_flash = use_flash

    def build(self, rng, x):
        d, hd = self.hidden_size, self.head_dim
        h, h_kv = self.num_heads, self.kv_heads
        ks = jax.random.split(rng, 4)
        w = _fan_in_normal
        p = {"wq": w(ks[0], d, h * hd), "wk": w(ks[1], d, h_kv * hd),
             "wv": w(ks[2], d, h_kv * hd), "wo": w(ks[3], h * hd, d)}
        if self.qk_norm_eps is None:
            scale = hd ** -0.5 if self.sm_scale is None else self.sm_scale
            gain = (NOPE_SCORE_STD / (scale * hd ** 0.5)) ** 0.5
            p["wq"], p["wk"] = p["wq"] * gain, p["wk"] * gain
        else:
            p["q_norm"], p["k_norm"] = jnp.ones((hd,)), jnp.ones((hd,))
        return p, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        b, t, _ = x.shape
        h, h_kv, hd = self.num_heads, self.kv_heads, self.head_dim

        def heads(y, n):
            return y.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

        with jax.named_scope("gqa/proj"):
            q = heads(_project(x, params["wq"]), h)
            k = heads(_project(x, params["wk"]), h_kv)
            v = heads(_project(x, params["wv"]), h_kv)
            if self.qk_norm_eps is not None:
                q = rms_norm(q, params["q_norm"], self.qk_norm_eps)
                k = rms_norm(k, params["k_norm"], self.qk_norm_eps)
            if self.rope_theta is not None:
                q, k = rope(q, self.rope_theta), rope(k, self.rope_theta)
        with jax.named_scope("gqa/attn"):
            if _flash_wanted(self.use_flash):
                from bigdl_tpu.ops.flash_attention import flash_attention

                out = flash_attention(q, k, v, causal=True,
                                      sm_scale=self.sm_scale)
            else:
                group = h // h_kv
                out = dot_product_attention(
                    q, jnp.repeat(k, group, axis=1),
                    jnp.repeat(v, group, axis=1),
                    mask=jnp.tril(jnp.ones((t, t), bool)),
                    scale=self.sm_scale)
        with jax.named_scope("gqa/proj"):
            out = out.transpose(0, 2, 1, 3).reshape(b, t, h * hd)
            return _project(out, params["wo"]), EMPTY


class PositionwiseFFN(Module):
    """The transformer FFN (two Linears + activation).

    ``ffn_sparsity > 0`` swaps both Linears for
    :class:`~bigdl_tpu.ops.block_sparse.BlockSparseLinear` (BLaST-style,
    docs/performance.md §Block-sparse FFN): they start DENSE (all-ones
    mask — identical math and speed through warmup) until a pruning event
    (``ops.block_sparse.prune_model_to_sparsity`` /
    ``BlockPruningSchedule``) carves the weight into ``sparse_block``
    tiles, after which the forward skips pruned blocks on the MXU."""

    def __init__(self, hidden_size: int, ffn_size: int, activation="gelu",
                 dropout: float = 0.0, ffn_sparsity: float = 0.0,
                 sparse_block=(64, 64), name=None):
        super().__init__(name)
        self.ffn_sparsity = float(ffn_sparsity)
        if ffn_sparsity > 0.0:
            from bigdl_tpu.ops.block_sparse import BlockSparseLinear

            self.l1 = BlockSparseLinear(hidden_size, ffn_size,
                                        block_shape=sparse_block,
                                        target_sparsity=ffn_sparsity)
            self.l2 = BlockSparseLinear(ffn_size, hidden_size,
                                        block_shape=sparse_block,
                                        target_sparsity=ffn_sparsity)
        else:
            self.l1 = Linear(hidden_size, ffn_size)
            self.l2 = Linear(ffn_size, hidden_size)
        self.act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu}[activation]
        self.dropout = Dropout(dropout)

    def init(self, rng, x):
        k1, k2 = jax.random.split(rng)
        v1 = self.l1.init(k1, x)
        h, _ = self.l1.apply(v1, x)
        v2 = self.l2.init(k2, h)
        return {"params": {"l1": v1["params"], "l2": v2["params"]},
                "state": EMPTY}

    def forward(self, params, state, x, training=False, rng=None):
        h, _ = self.l1.forward(params["l1"], EMPTY, x)
        h = self.act(h)
        if rng is not None:
            h, _ = self.dropout.forward(EMPTY, EMPTY, h, training=training,
                                        rng=rng)
        y, _ = self.l2.forward(params["l2"], EMPTY, h)
        return y, EMPTY


class TransformerLayer(Module):
    """Pre-LN transformer encoder block — reference keras
    ``TransformerLayer.scala`` (BERT-style block; pre-LN chosen for training
    stability, documented divergence)."""

    def __init__(self, hidden_size: int, num_heads: int, ffn_size: int = 0,
                 dropout: float = 0.1, causal: bool = False,
                 seq_parallel: Optional[str] = None,
                 ffn_sparsity: float = 0.0, sparse_block=(64, 64),
                 name=None):
        super().__init__(name)
        # seq-parallel kernels don't support attention-weight dropout;
        # keep the residual/FFN dropout and drop only the attn one so the
        # long-sequence TRAINING path (the whole point of seq_parallel)
        # still works
        self.attn = MultiHeadAttention(
            hidden_size, num_heads,
            attn_dropout=0.0 if seq_parallel else dropout,
            causal=causal, seq_parallel=seq_parallel)
        self.ffn = PositionwiseFFN(hidden_size, ffn_size or 4 * hidden_size,
                                   dropout=dropout,
                                   ffn_sparsity=ffn_sparsity,
                                   sparse_block=sparse_block)
        self.ln1 = LayerNorm(hidden_size)
        self.ln2 = LayerNorm(hidden_size)
        self.dropout = Dropout(dropout)

    def init(self, rng, x):
        ks = jax.random.split(rng, 4)
        va = self.attn.init(ks[0], x)
        vl1 = self.ln1.init(ks[1], x)
        vl2 = self.ln2.init(ks[2], x)
        vf = self.ffn.init(ks[3], x)
        return {"params": {"attn": va["params"], "ln1": vl1["params"],
                           "ln2": vl2["params"], "ffn": vf["params"]},
                "state": EMPTY}

    def forward(self, params, state, x, training=False, rng=None, mask=None):
        r1, r2, r3, r4 = (jax.random.split(rng, 4) if rng is not None
                          else (None,) * 4)
        h, _ = self.ln1.forward(params["ln1"], EMPTY, x)
        a, _ = self.attn.forward(params["attn"], EMPTY, h, training=training,
                                 rng=r1, mask=mask)
        if r2 is not None:
            a, _ = self.dropout.forward(EMPTY, EMPTY, a, training=training,
                                        rng=r2)
        x = x + a
        h, _ = self.ln2.forward(params["ln2"], EMPTY, x)
        f, _ = self.ffn.forward(params["ffn"], EMPTY, h, training=training,
                                rng=r3)
        if r4 is not None:
            f, _ = self.dropout.forward(EMPTY, EMPTY, f, training=training,
                                        rng=r4)
        return x + f, EMPTY


def positional_encoding(length: int, dim: int,
                        offset=0) -> jnp.ndarray:
    """Sinusoidal positions — reference ``Transformer.scala`` encoding.
    Handles odd dims (sin gets ceil(dim/2) columns, cos the rest).
    ``offset`` (traceable) shifts the position range: a sequence-parallel
    block at global start ``offset`` gets its TRUE positions."""
    n_sin = (dim + 1) // 2
    pos = (jnp.arange(length) + offset)[:, None].astype(jnp.float32)
    i = jnp.arange(n_sin)[None, :].astype(jnp.float32)
    angle = pos / jnp.power(10000.0, 2 * i / dim)
    pe = jnp.zeros((length, dim))
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    pe = pe.at[:, 1::2].set(jnp.cos(angle[:, : dim // 2]))
    return pe


class PositionalEncoding(Module):
    """Add sinusoidal positions to (batch, seq, dim) activations.

    Sequence-parallel aware: traced inside a shard_map carrying
    ``seq_axis``, each block offsets by ``axis_index * block_len`` so
    positions stay GLOBAL (a plain PE layer would restart every block at
    position 0 and silently break any position-dependent task)."""

    def __init__(self, seq_axis: str = "seq", name=None):
        super().__init__(name)
        self.seq_axis = seq_axis

    def forward(self, params, state, x, training=False, rng=None):
        c, d = x.shape[1], x.shape[2]
        offset = (jax.lax.axis_index(self.seq_axis) * c
                  if _axis_bound(self.seq_axis) else 0)
        return (x + positional_encoding(c, d, offset)[None]
                .astype(x.dtype)), EMPTY


class TransformerDecoderLayer(Module):
    """Pre-LN decoder block: causal self-attention, cross-attention over
    encoder memory, FFN — the decoder half of reference
    ``nn/Transformer.scala``'s translation mode."""

    def __init__(self, hidden_size: int, num_heads: int, ffn_size: int = 0,
                 dropout: float = 0.1, ffn_sparsity: float = 0.0,
                 sparse_block=(64, 64), name=None):
        super().__init__(name)
        self.self_attn = MultiHeadAttention(hidden_size, num_heads,
                                            attn_dropout=dropout, causal=True)
        self.cross_attn = MultiHeadAttention(hidden_size, num_heads,
                                             attn_dropout=dropout)
        self.ffn = PositionwiseFFN(hidden_size, ffn_size or 4 * hidden_size,
                                   dropout=dropout,
                                   ffn_sparsity=ffn_sparsity,
                                   sparse_block=sparse_block)
        self.ln1 = LayerNorm(hidden_size)
        self.ln2 = LayerNorm(hidden_size)
        self.ln3 = LayerNorm(hidden_size)
        self.dropout = Dropout(dropout)

    def init(self, rng, x, memory):
        ks = jax.random.split(rng, 6)
        return {"params": {
            "self_attn": self.self_attn.init(ks[0], x)["params"],
            "cross_attn": self.cross_attn.init(ks[1], x, memory)["params"],
            "ffn": self.ffn.init(ks[2], x)["params"],
            "ln1": self.ln1.init(ks[3], x)["params"],
            "ln2": self.ln2.init(ks[4], x)["params"],
            "ln3": self.ln3.init(ks[5], x)["params"],
        }, "state": EMPTY}

    def forward(self, params, state, x, memory, training=False, rng=None,
                memory_mask=None):
        rs = (jax.random.split(rng, 3) if rng is not None else (None,) * 3)
        h, _ = self.ln1.forward(params["ln1"], EMPTY, x)
        a, _ = self.self_attn.forward(params["self_attn"], EMPTY, h,
                                      training=training, rng=rs[0])
        x = x + a
        h, _ = self.ln2.forward(params["ln2"], EMPTY, x)
        a, _ = self.cross_attn.forward(params["cross_attn"], EMPTY, h,
                                       context=memory, training=training,
                                       rng=rs[1], mask=memory_mask)
        x = x + a
        h, _ = self.ln3.forward(params["ln3"], EMPTY, x)
        f, _ = self.ffn.forward(params["ffn"], EMPTY, h, training=training,
                                rng=rs[2])
        return x + f, EMPTY


class Transformer(Module):
    """Encoder-decoder transformer — reference ``nn/Transformer.scala``
    (tensor2tensor lineage; the WMT Seq2Seq config in BASELINE.json).

    Two modes, like the reference: ``mode="translation"`` —
    ``forward(params, state, src_ids, tgt_ids)`` → (b, t_tgt, vocab)
    logits; ``mode="lm"`` — ``forward(params, state, ids)`` → causal LM
    logits.  Token embedding is scaled by sqrt(d) and shared with the
    output projection (weight tying, as the reference does)."""

    def __init__(self, vocab_size: int, hidden_size: int, num_heads: int,
                 ffn_size: int = 0, num_layers: int = 2,
                 dropout: float = 0.1, mode: str = "translation",
                 ffn_sparsity: float = 0.0, sparse_block=(64, 64),
                 name=None):
        super().__init__(name)
        if mode not in ("translation", "lm"):
            raise ValueError(f"mode {mode!r}: translation | lm")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.mode = mode
        self.ffn_sparsity = float(ffn_sparsity)
        self.dropout = Dropout(dropout)
        mk = (lambda causal=False: TransformerLayer(
            hidden_size, num_heads, ffn_size, dropout, causal=causal,
            ffn_sparsity=ffn_sparsity, sparse_block=sparse_block))
        self.encoder = [mk() for _ in range(num_layers)] \
            if mode == "translation" else []
        if mode == "translation":
            self.decoder = [TransformerDecoderLayer(
                hidden_size, num_heads, ffn_size, dropout,
                ffn_sparsity=ffn_sparsity, sparse_block=sparse_block)
                for _ in range(num_layers)]
        else:
            self.decoder = [mk(causal=True) for _ in range(num_layers)]
        self.ln_out = LayerNorm(hidden_size)

    def _embed(self, params, ids):
        e = jnp.take(params["embedding"], ids.astype(jnp.int32), axis=0)
        e = e * jnp.sqrt(float(self.hidden_size))
        return e + positional_encoding(ids.shape[1],
                                       self.hidden_size)[None].astype(e.dtype)

    def init(self, rng, *ids):
        ks = jax.random.split(rng, 3 + len(self.encoder) + len(self.decoder))
        d = self.hidden_size
        params = {"embedding": jax.random.normal(
            ks[0], (self.vocab_size, d)) * d ** -0.5}
        x = self._embed(params, jnp.asarray(ids[0]))
        ki = 1
        for i, layer in enumerate(self.encoder):
            params[f"enc{i}"] = layer.init(ks[ki], x)["params"]
            ki += 1
        if self.mode == "translation":
            tgt = self._embed(params, jnp.asarray(ids[1]))
            for i, layer in enumerate(self.decoder):
                params[f"dec{i}"] = layer.init(ks[ki], tgt, x)["params"]
                ki += 1
        else:
            for i, layer in enumerate(self.decoder):
                params[f"dec{i}"] = layer.init(ks[ki], x)["params"]
                ki += 1
        params["ln_out"] = self.ln_out.init(ks[ki], x)["params"]
        return {"params": params, "state": EMPTY}

    def forward(self, params, state, src, tgt=None, training=False,
                rng=None):
        n_rngs = len(self.encoder) + len(self.decoder) + 1
        rs = (jax.random.split(rng, n_rngs) if rng is not None
              else (None,) * n_rngs)
        ri = 0
        x = self._embed(params, src)
        if rs[0] is not None:
            x, _ = self.dropout.forward(EMPTY, EMPTY, x, training=training,
                                        rng=rs[0])
        ri = 1
        for i, layer in enumerate(self.encoder):
            x, _ = layer.forward(params[f"enc{i}"], EMPTY, x,
                                 training=training, rng=rs[ri])
            ri += 1
        if self.mode == "translation":
            if tgt is None:
                raise ValueError("translation mode needs (src, tgt)")
            h = self._embed(params, tgt)
            for i, layer in enumerate(self.decoder):
                h, _ = layer.forward(params[f"dec{i}"], EMPTY, h, x,
                                     training=training, rng=rs[ri])
                ri += 1
        else:
            h = x
            for i, layer in enumerate(self.decoder):
                h, _ = layer.forward(params[f"dec{i}"], EMPTY, h,
                                     training=training, rng=rs[ri])
                ri += 1
        h, _ = self.ln_out.forward(params["ln_out"], EMPTY, h)
        # weight-tied output projection
        emb = cast_compute(params["embedding"])
        logits = jnp.matmul(cast_compute(h), emb.T,
                            preferred_element_type=jnp.float32)
        return logits.astype(jnp.float32), EMPTY


# reference ``nn/Attention.scala`` / ``nn/FeedForwardNetwork.scala`` names
Attention = MultiHeadAttention
FeedForwardNetwork = PositionwiseFFN


def transformer_decode(model, params, src, bos_id, eos_id, max_len=32,
                       beam_size: int = 0, length_penalty: float = 0.6):
    """Autoregressive decode for a translation-mode :class:`Transformer` —
    the inference half of reference ``nn/Transformer.scala`` +
    ``nn/SequenceBeamSearch.scala``.

    ``beam_size=0`` → greedy; ``>0`` → beam search with GNMT length
    penalty.  The decoder re-attends over the full static-length prefix
    each step (no KV cache — one ``lax.scan``, static shapes; the buffer
    carries the grown prefix as decode state).  Returns
    ``(tokens, scores)`` with tokens (b, max_len+1) greedy or
    (b, beam, max_len+1) beamed, BOS included.
    """
    from bigdl_tpu.nn.decode import beam_search, greedy_decode

    if model.mode != "translation":
        raise ValueError("decode needs a translation-mode Transformer")
    b = src.shape[0]

    # encode once; memory rides in the decode state (tiled for beams)
    x = model._embed(params, jnp.asarray(src))
    for i, layer in enumerate(model.encoder):
        x, _ = layer.forward(params[f"enc{i}"], EMPTY, x)

    init_state = {
        "memory": x,
        "prefix": jnp.full((b, max_len + 1), bos_id, jnp.int32),
        "pos": jnp.zeros((b,), jnp.int32),
    }

    def step_fn(last_tokens, state):
        pos = state["pos"][0]                       # same for every row
        prefix = state["prefix"].at[:, pos].set(last_tokens)
        h = model._embed(params, prefix)
        for i, layer in enumerate(model.decoder):
            h, _ = layer.forward(params[f"dec{i}"], EMPTY, h,
                                 state["memory"])
        h, _ = model.ln_out.forward(params["ln_out"], EMPTY, h)
        emb = cast_compute(params["embedding"])
        logits = jnp.matmul(cast_compute(h), emb.T,
                            preferred_element_type=jnp.float32)
        lp = logits.astype(jnp.float32)[:, pos]
        return lp, {"memory": state["memory"], "prefix": prefix,
                    "pos": state["pos"] + 1}

    vocab = model.vocab_size
    if beam_size and beam_size > 1:
        res = beam_search(step_fn, init_state, b, vocab, bos_id, eos_id,
                          beam_size=beam_size, max_len=max_len,
                          length_penalty=length_penalty)
        return res.tokens, res.scores
    tokens, log_probs, _lengths = greedy_decode(
        step_fn, init_state, b, bos_id, eos_id, max_len=max_len)
    return tokens, log_probs


def _attn_project(p, x, w, b):
    return (jnp.matmul(cast_compute(x), cast_compute(p[w]),
                       preferred_element_type=jnp.float32)
            + p[b]).astype(x.dtype)


def transformer_decode_cached(model, params, src, bos_id, eos_id,
                              max_len=32, *, rng=None,
                              temperature: float = 1.0, top_k: int = 0,
                              top_p: float = 1.0):
    """Greedy decode with per-layer KV caches — O(L) attention per step
    (O(L²) total) instead of re-running the decoder over the whole prefix
    (O(L³) total).  The serving-path variant of :func:`transformer_decode`;
    numerics match the uncached path (asserted in tests).

    ``rng`` switches to STOCHASTIC decoding (``nn.decode.sample_decode``):
    temperature + top-k + nucleus top-p over the same cached step.

    Cache layout per decoder layer: self-attention K/V buffers
    (b, heads, max_len, head_dim) written at the current position each
    step; cross-attention K/V computed ONCE from the encoder memory.
    """
    from bigdl_tpu.nn.decode import greedy_decode, sample_decode

    if model.mode != "translation":
        raise ValueError("decode needs a translation-mode Transformer")
    b = src.shape[0]
    d = model.hidden_size

    mem = model._embed(params, jnp.asarray(src))
    for i, layer in enumerate(model.encoder):
        mem, _ = layer.forward(params[f"enc{i}"], EMPTY, mem)

    layers = model.decoder
    nh = layers[0].self_attn.num_heads
    hd = layers[0].self_attn.head_dim

    def split_heads(x):                    # (b, t, d) -> (b, h, t, hd)
        return x.reshape(b, -1, nh, hd).transpose(0, 2, 1, 3)

    # cross-attention K/V once per layer
    cross_kv = []
    for i, layer in enumerate(layers):
        p = params[f"dec{i}"]["cross_attn"]
        cross_kv.append((split_heads(_attn_project(p, mem, "wk", "bk")),
                         split_heads(_attn_project(p, mem, "wv", "bv"))))

    pe = positional_encoding(max_len + 1, d)
    scale = jnp.sqrt(float(d))

    init_state = {
        "k": jnp.zeros((b, len(layers), nh, max_len, hd), jnp.float32),
        "v": jnp.zeros((b, len(layers), nh, max_len, hd), jnp.float32),
        "pos": jnp.zeros((b,), jnp.int32),
    }

    def step_fn(last_tokens, state):
        pos = state["pos"][0]
        x = (jnp.take(params["embedding"], last_tokens.astype(jnp.int32),
                      axis=0) * scale + pe[pos])[:, None, :]   # (b, 1, d)
        ks, vs = state["k"], state["v"]
        # valid-position mask over the cache (positions <= pos)
        valid = (jnp.arange(max_len) <= pos)[None, None, None, :]
        for i, layer in enumerate(layers):
            lp = params[f"dec{i}"]
            h, _ = layer.ln1.forward(lp["ln1"], EMPTY, x)
            sp = lp["self_attn"]
            q = split_heads(_attn_project(sp, h, "wq", "bq"))  # (b,h,1,hd)
            k_new = split_heads(_attn_project(sp, h, "wk", "bk"))[:, :, 0]
            v_new = split_heads(_attn_project(sp, h, "wv", "bv"))[:, :, 0]
            ks = ks.at[:, i, :, pos].set(k_new.astype(ks.dtype))
            vs = vs.at[:, i, :, pos].set(v_new.astype(vs.dtype))
            logits = jnp.einsum(
                "bhqd,bhkd->bhqk", q.astype(jnp.float32), ks[:, i],
                preferred_element_type=jnp.float32) / jnp.sqrt(float(hd))
            logits = jnp.where(valid, logits, -1e30)
            w = jax.nn.softmax(logits, axis=-1)
            a = jnp.einsum("bhqk,bhkd->bhqd", w, vs[:, i],
                           preferred_element_type=jnp.float32)
            a = a.transpose(0, 2, 1, 3).reshape(b, 1, nh * hd)
            a = (jnp.matmul(a.astype(x.dtype), cast_compute(sp["wo"]),
                            preferred_element_type=jnp.float32)
                 + sp["bo"]).astype(x.dtype)
            x = x + a
            # cross attention over the fixed memory
            h, _ = layer.ln2.forward(lp["ln2"], EMPTY, x)
            cp = lp["cross_attn"]
            q = split_heads(_attn_project(cp, h, "wq", "bq"))
            ck, cv = cross_kv[i]
            logits = jnp.einsum(
                "bhqd,bhkd->bhqk", q.astype(jnp.float32),
                ck.astype(jnp.float32),
                preferred_element_type=jnp.float32) / jnp.sqrt(float(hd))
            w = jax.nn.softmax(logits, axis=-1)
            a = jnp.einsum("bhqk,bhkd->bhqd", w, cv.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
            a = a.transpose(0, 2, 1, 3).reshape(b, 1, nh * hd)
            a = (jnp.matmul(a.astype(x.dtype), cast_compute(cp["wo"]),
                            preferred_element_type=jnp.float32)
                 + cp["bo"]).astype(x.dtype)
            x = x + a
            h, _ = layer.ln3.forward(lp["ln3"], EMPTY, x)
            f, _ = layer.ffn.forward(lp["ffn"], EMPTY, h)
            x = x + f
        h, _ = model.ln_out.forward(params["ln_out"], EMPTY, x)
        emb = cast_compute(params["embedding"])
        lp_out = jnp.matmul(cast_compute(h), emb.T,
                            preferred_element_type=jnp.float32)
        return lp_out.astype(jnp.float32)[:, 0], \
            {"k": ks, "v": vs, "pos": state["pos"] + 1}

    if rng is not None:
        tokens, log_probs, _lengths = sample_decode(
            step_fn, init_state, b, bos_id, eos_id, rng, max_len=max_len,
            temperature=temperature, top_k=top_k, top_p=top_p)
    else:
        tokens, log_probs, _lengths = greedy_decode(
            step_fn, init_state, b, bos_id, eos_id, max_len=max_len)
    return tokens, log_probs
