"""Blockwise fused (flash) attention — Pallas TPU kernel.

Reference analog: the reference has NO fused attention — its
``nn/Attention.scala`` / Keras ``TransformerLayer`` materialise the full
O(S²) score matrix on one device (SURVEY.md §6.7).  This kernel is the
TPU-native upgrade: online-softmax blockwise attention that keeps exactly
one (block_q × d) query tile and one (block_k × d) key/value tile in VMEM
at a time, so peak on-chip memory is O(block·d) and the matmuls stay on
the MXU.

Forward is a Pallas kernel with grid (batch·heads, q-blocks, k-blocks);
the k dimension is innermost and iterates sequentially on-core, carrying
the online-softmax running (max, denom, accumulator) in VMEM scratch —
the k/v BlockSpecs stream one tile per step from HBM.  Backward is a
custom VJP: the standard flash-attention backward recurrence evaluated
blockwise with a ``lax.scan`` over k/v tiles using the saved logsumexp,
so the O(S²) score matrix is never materialised in either direction
(single-chip long context; cross-chip sequence parallelism lives in
``bigdl_tpu/parallel/ring_attention.py``).

Shapes: q, k, v are (batch, heads, seq, head_dim); output matches q.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.common import default_interpret, round_up

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale, causal, block_q, block_k, kv_len):
    # q_ref: (1, block_q, d); k_ref/v_ref: (1, block_k, d) — one tile each.
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip k-blocks strictly above this q-block's diagonal band
    needed = jnp.bool_(True)
    if causal:
        needed = kj * block_k < (qi + 1) * block_q

    @pl.when(needed)
    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)

        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(kj == num_kb - 1)
    def _finish():
        m = m_scr[:, 0]
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, :, 0] = m + jnp.log(l_safe)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    bq = min(block_q, round_up(sq, 8))
    bk = min(block_k, round_up(skv, 8))
    sq_p, skv_p = round_up(sq, bq), round_up(skv, bk)

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    qp = qp.reshape(b * h, sq_p, d)
    kp = kp.reshape(b * h, skv_p, d)
    vp = vp.reshape(b * h, skv_p, d)

    grid = (b * h, sq_p // bq, skv_p // bk)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_k=bk, kv_len=skv)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            # lse carries a trailing singleton lane dim: a 2-D (1, bq) block
            # would put bq in the lane slot and 1 in the sublane slot, which
            # TPU tiling rejects when batch·heads > 1.
            pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=default_interpret(interpret),
    )(qp, kp, vp)

    out = out.reshape(b, h, sq_p, d)[:, :, :sq]
    lse = lse.reshape(b, h, sq_p)[:, :, :sq]
    return out, lse  # lse: (b, h, sq)


def _blockwise_bwd(q, k, v, out, lse, g, sm_scale, causal, block_k=128):
    """Memory-efficient flash-attention backward: a ``lax.scan`` over k/v
    blocks reconstructs one (sq × block_k) score tile at a time from the
    saved logsumexp — peak memory O(S·block) instead of the O(S²) full
    score matrix.  Recurrence: p = exp(q·kᵀ·scale − lse);
    D = rowsum(g ⊙ out); dS = p ⊙ (g·vᵀ − D)·scale."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    b, h, sq, d = qf.shape
    skv = kf.shape[2]
    bk = min(block_k, round_up(skv, 8))
    skv_p = round_up(skv, bk)
    kp = jnp.pad(kf, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    vp = jnp.pad(vf, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    # (nblocks, b, h, bk, d) scan layout
    kb = kp.reshape(b, h, skv_p // bk, bk, d).transpose(2, 0, 1, 3, 4)
    vb = vp.reshape(b, h, skv_p // bk, bk, d).transpose(2, 0, 1, 3, 4)

    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)  # (b,h,sq)
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, bk), 0)
    k_off = jax.lax.broadcasted_iota(jnp.int32, (sq, bk), 1)

    def step(dq_acc, inp):
        j, k_j, v_j = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_j) * sm_scale
        k_pos = j * bk + k_off
        mask = k_pos < skv
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v_j)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, k_j)
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq_acc, (dk_j, dv_j)

    nb = skv_p // bk
    dq, (dk_b, dv_b) = jax.lax.scan(
        step, jnp.zeros_like(qf), (jnp.arange(nb), kb, vb))
    dk = dk_b.transpose(1, 2, 0, 3, 4).reshape(b, h, skv_p, d)[:, :, :skv]
    dv = dv_b.transpose(1, 2, 0, 3, 4).reshape(b, h, skv_p, d)[:, :, :skv]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, block_k_bwd,
           interpret):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                        interpret)
    return out


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                   block_k_bwd, interpret):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                          interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, block_k_bwd,
                   interpret, res, g):
    q, k, v, out, lse = res
    return _blockwise_bwd(q, k, v, out, lse, g, sm_scale, causal,
                          block_k=block_k_bwd)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _decode_kernel(pt_ref, len_ref, *refs, sm_scale, page_size, quantized):
    """Single-query attention over one slot's paged KV cache.  Grid
    (slots, head-blocks, page-blocks); the page dimension is innermost
    and walks the slot's page table via the scalar-prefetched index map
    — only the slot's own pages are ever touched, so HBM traffic scales
    with the sequence's true length, not the pool size.

    ``quantized`` adds two scalar-prefetched per-page scale tables
    (k/v, one f32 per pool page — docs/quantization.md §Serving memory
    hierarchy): the int8 page block is dequantized IN-REGISTER right
    after the DMA, so HBM reads stay 1 byte/element and the softmax
    math is identical to the f32 kernel."""
    if quantized:
        (ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    s = pl.program_id(0)
    j = pl.program_id(2)
    num_pb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # positions [j*page, (j+1)*page) attend when <= the slot's length
    @pl.when(j * page_size <= len_ref[s])
    def _step():
        q = q_ref[...].astype(jnp.float32) * sm_scale    # (bh, d)
        k = k_ref[0].astype(jnp.float32)                 # (bh, page, d)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            pid = pt_ref[s, j]
            k = k * ks_ref[pid]
            v = v * vs_ref[pid]
        # VPU-friendly batched dot: broadcast-multiply-reduce keeps the
        # per-head contraction off the (batched-dot-averse) MXU path
        sc = jnp.sum(q[:, None, :] * k, axis=-1)         # (bh, page)
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        sc = jnp.where(pos <= len_ref[s], sc, _NEG_INF)
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_scr[:] = acc_scr[:] * alpha[:, None] + jnp.sum(
            p[:, :, None] * v, axis=1)

    @pl.when(j == num_pb - 1)
    def _finish():
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[:] / l_safe[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           k_scales=None, v_scales=None,
                           sm_scale: Optional[float] = None,
                           block_h: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Query-length-1 decode-step attention over a paged KV cache — the
    serving-side sibling of :func:`flash_attention` (docs/serving.md
    §Autoregressive decode).

    ``q``: (slots, heads, head_dim) — one query per sequence slot.
    ``k_pages``/``v_pages``: (num_pages, heads, page_size, head_dim) —
    the page pool ONE layer's cache lives in.  ``page_table``: (slots,
    n_blocks) int32 — each slot's ordered page list (entries past the
    allocated count may be stale; they are masked by ``lengths``).
    ``lengths``: (slots,) int32 — the highest valid cache position per
    slot, INCLUSIVE (the current token's K/V must already be written).

    int8 page pools (docs/quantization.md §Serving memory hierarchy)
    pass ``k_scales``/``v_scales``: (num_pages,) float32 per-page
    abs-max scales, scalar-prefetched alongside the page table so each
    page block is dequantized in-register after its 1-byte/element DMA.

    ``block_h`` tiles the head dimension per program (must divide
    heads); ``None`` consults the autotune cache under the
    ``flash_attention_decode`` registry entry and falls back to the
    largest of {1,2,4,8} that divides ``heads``."""
    S, h, d = q.shape
    P, hk, page, dk = k_pages.shape
    assert (h, d) == (hk, dk), (q.shape, k_pages.shape)
    quantized = k_pages.dtype == jnp.int8
    if quantized and (k_scales is None or v_scales is None):
        raise ValueError("int8 k_pages/v_pages need k_scales/v_scales "
                         "(one f32 abs-max scale per pool page)")
    if not quantized and (k_scales is not None or v_scales is not None):
        raise ValueError("k_scales/v_scales only apply to int8 pages, "
                         f"got {k_pages.dtype} pages")
    nb = page_table.shape[1]
    if sm_scale is None:
        sm_scale = d ** -0.5
    from bigdl_tpu.ops import autotune

    if block_h is None:
        key = autotune.decode_attention_key(S, h, page, d, nb,
                                            q.dtype)
        shape = ((S, h, page, d, nb, q.dtype.name)
                 if autotune.is_concrete(q, k_pages, v_pages) else None)
        bh = int(autotune.resolve("flash_attention_decode", key,
                                  online_shape=shape)["block_h"])
        if h % bh != 0:  # cached winner from another head count
            bh = max(c for c in (1, 2, 4, 8) if h % c == 0)
    else:
        bh = int(block_h)
        if h % bh != 0:
            raise ValueError(f"block_h {bh} must divide heads {h}")

    kernel = functools.partial(_decode_kernel, sm_scale=float(sm_scale),
                               page_size=page, quantized=quantized)
    # scalar-prefetch operands: (page_table, lengths) always; the int8
    # pool adds the two per-page scale tables (index maps then take four
    # trailing scalar refs instead of two — hence the arity split below)
    if quantized:
        def q_map(s, hb, j, pt, ln, ks, vs):
            return (s, hb, 0, 0)

        def kv_map(s, hb, j, pt, ln, ks, vs):
            return (pt[s, j], hb, 0, 0)
    else:
        def q_map(s, hb, j, pt, ln):
            return (s, hb, 0, 0)

        def kv_map(s, hb, j, pt, ln):
            return (pt[s, j], hb, 0, 0)
    # q/out ride as (S, h/bh, bh, d) views with the two leading dims
    # squeezed out of the block: Mosaic tiles the LAST TWO block dims in
    # (8, 128) units unless they span the whole array dim, and a (bh, d)
    # block of the (S, h, d) array (bh=4 of 12 heads) does neither
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quantized else 2,
        grid=(S, h // bh, nb),
        in_specs=[
            pl.BlockSpec((None, None, bh, d), q_map),
            pl.BlockSpec((1, bh, page, d), kv_map),
            pl.BlockSpec((1, bh, page, d), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, bh, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((bh, 1), jnp.float32),    # running max
            pltpu.VMEM((bh, 1), jnp.float32),    # running denom
            pltpu.VMEM((bh, d), jnp.float32),    # output accumulator
        ],
    )
    scalars = [jnp.asarray(page_table, jnp.int32),
               jnp.asarray(lengths, jnp.int32)]
    if quantized:
        scalars += [jnp.asarray(k_scales, jnp.float32),
                    jnp.asarray(v_scales, jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, h // bh, bh, d), q.dtype),
        interpret=default_interpret(interpret),
    )(*scalars, q.reshape(S, h // bh, bh, d), k_pages,
      v_pages).reshape(S, h, d)


def _verify_kernel(pt_ref, pos_ref, *refs, sm_scale, page_size, chunk,
                   quantized):
    """Multi-query (speculative-verify) attention over one slot's paged
    KV cache (docs/serving.md §Speculative decoding).  Identical page
    walk to :func:`_decode_kernel`, but the query block carries the
    whole k+1-token verify chunk: query ``c`` sits at cache position
    ``pos_ref[s] + c`` and attends keys at positions ``<= pos_ref[s] +
    c`` — the per-query causal staircase that makes one program score
    every drafted token."""
    if quantized:
        (ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    s = pl.program_id(0)
    j = pl.program_id(2)
    num_pb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # the LAST query (c = chunk-1) attends the furthest position, so a
    # page participates iff it starts at or below pos + chunk - 1
    @pl.when(j * page_size <= pos_ref[s] + chunk - 1)
    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale      # (bh, C, d)
        k = k_ref[0].astype(jnp.float32)                 # (bh, page, d)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            pid = pt_ref[s, j]
            k = k * ks_ref[pid]
            v = v * vs_ref[pid]
        # (bh, C, page) scores via broadcast-multiply-reduce (VPU path,
        # like the decode kernel — C and page are both small here)
        sc = jnp.sum(q[:, :, None, :] * k[:, None, :, :], axis=-1)
        key_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 2)
        q_lim = pos_ref[s] + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        sc = jnp.where(key_pos <= q_lim, sc, _NEG_INF)
        m_prev = m_scr[:]                                # (bh, C)
        l_prev = l_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_scr[:] = acc_scr[:] * alpha[..., None] + jnp.sum(
            p[..., None] * v[:, None], axis=2)

    @pl.when(j == num_pb - 1)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe[..., None]).astype(o_ref.dtype)


def paged_verify_attention(q, k_pages, v_pages, page_table, positions, *,
                           k_scales=None, v_scales=None,
                           sm_scale: Optional[float] = None,
                           block_h: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Query-length-``k+1`` speculative-VERIFY attention over a paged KV
    cache — the multi-query sibling of :func:`paged_decode_attention`
    (docs/serving.md §Speculative decoding): one call scores the whole
    drafted chunk against the target cache instead of k+1 single-query
    steps.

    ``q``: (slots, heads, chunk, head_dim) — the verify chunk's queries,
    query ``c`` of slot ``s`` sitting at cache position ``positions[s]
    + c``.  ``k_pages``/``v_pages``/``page_table`` exactly as
    :func:`paged_decode_attention`; the chunk's own K/V must already be
    scattered into the pages (positions ``[positions[s], positions[s] +
    chunk)``) before the call.  ``positions``: (slots,) int32 — the
    FIRST query's cache position per slot; the per-query causal
    staircase ``key_pos <= positions[s] + c`` makes each query attend
    its own prefix only, so the outputs match chunk single-query decode
    steps.

    int8 pools pass ``k_scales``/``v_scales`` per-page f32 abs-max
    scales, dequantized in-register like the decode kernel.  ``block_h``
    tiles heads (``None`` = the largest of {1, 2, 4, 8} dividing
    ``heads`` — the verify chunk is not autotuned separately)."""
    S, h, C, d = q.shape
    P, hk, page, dk = k_pages.shape
    assert (h, d) == (hk, dk), (q.shape, k_pages.shape)
    quantized = k_pages.dtype == jnp.int8
    if quantized and (k_scales is None or v_scales is None):
        raise ValueError("int8 k_pages/v_pages need k_scales/v_scales "
                         "(one f32 abs-max scale per pool page)")
    if not quantized and (k_scales is not None or v_scales is not None):
        raise ValueError("k_scales/v_scales only apply to int8 pages, "
                         f"got {k_pages.dtype} pages")
    nb = page_table.shape[1]
    if sm_scale is None:
        sm_scale = d ** -0.5
    if block_h is None:
        bh = max(c for c in (1, 2, 4, 8) if h % c == 0)
    else:
        bh = int(block_h)
        if h % bh != 0:
            raise ValueError(f"block_h {bh} must divide heads {h}")

    kernel = functools.partial(_verify_kernel, sm_scale=float(sm_scale),
                               page_size=page, chunk=C,
                               quantized=quantized)
    if quantized:
        def q_map(s, hb, j, pt, pos, ks, vs):
            return (s, hb, 0, 0)

        def kv_map(s, hb, j, pt, pos, ks, vs):
            return (pt[s, j], hb, 0, 0)
    else:
        def q_map(s, hb, j, pt, pos):
            return (s, hb, 0, 0)

        def kv_map(s, hb, j, pt, pos):
            return (pt[s, j], hb, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quantized else 2,
        grid=(S, h // bh, nb),
        in_specs=[
            pl.BlockSpec((1, bh, C, d), q_map),
            pl.BlockSpec((1, bh, page, d), kv_map),
            pl.BlockSpec((1, bh, page, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bh, C, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((bh, C), jnp.float32),    # running max per query
            pltpu.VMEM((bh, C), jnp.float32),    # running denom
            pltpu.VMEM((bh, C, d), jnp.float32),  # output accumulator
        ],
    )
    scalars = [jnp.asarray(page_table, jnp.int32),
               jnp.asarray(positions, jnp.int32)]
    if quantized:
        scalars += [jnp.asarray(k_scales, jnp.float32),
                    jnp.asarray(v_scales, jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, h, C, d), q.dtype),
        interpret=default_interpret(interpret),
    )(*scalars, q, k_pages, v_pages)


def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Fused blockwise attention.  q, k, v: (batch, heads, seq, head_dim).

    ``block_*=None`` consults the autotune cache for this device/shape
    bucket and falls back to the hand-picked 128 defaults
    (docs/performance.md §Kernel autotuning); explicit kwargs always win.
    ``block_k_bwd`` tiles the backward k/v scan independently of the
    forward."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    from bigdl_tpu.ops import autotune

    key = autotune.attention_key(q.shape, k.shape[2], q.dtype)
    # online mode tunes on a cache miss, but only on EAGER calls —
    # inside a jit trace the args are tracers and we must not run timing
    # trials mid-trace
    shape = (tuple(q.shape) + (q.dtype.name,)
             if autotune.is_concrete(q, k, v) else None)
    fwd = autotune.resolve("flash_attention_fwd", key,
                           explicit={"block_q": block_q,
                                     "block_k": block_k},
                           online_shape=shape)
    if block_k_bwd is None:
        if block_k is not None:
            # an explicit forward block_k also pins the backward (the
            # legacy single-knob contract) — no bwd lookup, no online
            # tuning run whose winner would be discarded
            block_k_bwd = block_k
        else:
            # cache/defaults only — no online_shape: a forward-only eager
            # call must not pay a jax.grad tuning sweep for a backward it
            # may never run (the offline CLI tunes flash_attention_bwd)
            block_k_bwd = autotune.resolve("flash_attention_bwd",
                                           key)["block_k"]
    return _flash(q, k, v, float(sm_scale), bool(causal),
                  int(fwd["block_q"]), int(fwd["block_k"]),
                  int(block_k_bwd), interpret)
