"""Blockwise fused (flash) attention — Pallas TPU kernels.

Reference analog: the reference has NO fused attention — its
``nn/Attention.scala`` / Keras ``TransformerLayer`` materialise the full
O(S²) score matrix on one device (SURVEY.md §6.7).  These kernels are the
TPU-native upgrade: online-softmax blockwise attention that keeps one
(block_q × d) query tile and one (block_k × d) key/value tile in VMEM at a
time, so peak on-chip memory is O(block·d) and the matmuls stay on the
MXU.

Training path (``flash_attention``): three Pallas kernels under one custom
VJP.  The forward (grid batch·heads × q-blocks × k-blocks, k innermost)
carries the online-softmax running (max, denom, accumulator) in VMEM
scratch and saves the logsumexp; the backward is the standard pair that
rebuilds ``p`` from it: a dk/dv kernel (grid over k-blocks, q-blocks
innermost) and a dq kernel (grid over q-blocks, k-blocks innermost), with
``delta = rowsum(g ⊙ out)`` computed once outside.  All three:

- take their operands in the policy's compute dtype
  (``tensor.policy.cast_compute``, as ``nn.attention.
  dot_product_attention`` does) and multiply the tiles as they come with
  float32 accumulation; scores, softmax statistics, ``lse``, ``delta``
  and every accumulator are float32, ``p``/``ds`` are rounded to the
  compute dtype only as a second product's operand;
- visit only the tiles the causal mask leaves: a tile strictly above the
  diagonal is neither multiplied nor fetched (its index map is clamped to
  the nearest tile the row/column does visit, so the skipped grid step
  names a tile already resident), and the element mask is applied only on
  tiles the diagonal (or the key padding) crosses;
- take their blocks from :func:`default_blocks`, a rule on the shape the
  call can see (docs/performance.md §Kernel autotuning).

Cross-chip sequence parallelism lives in
``bigdl_tpu/parallel/ring_attention.py``; the paged decode/verify kernels
below are the serving-side siblings.

Shapes: q is (batch, heads, seq, head_dim), k (batch, kv_heads, seq,
head_dim), v (batch, kv_heads, seq, v_dim); the output has q's heads and
length and v's width.  Grouped-query heads: ``kv_heads`` divides ``heads``
and query head ``j`` reads key/value head ``j // (heads / kv_heads)``.  K
and V are never repeated in HBM: the forward and the dq kernel send program
``bh`` to K/V row ``bh // group`` in their index maps, and the dk/dv kernel
runs over the key/value heads and walks its group's query heads in its
inner grid axis, accumulating all of them in its scratch.  ``kv_heads =
heads`` traces the program it traced before there were groups.
"""

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.common import (ATTN_LSE, ATTN_OUT, default_interpret,
                                  round_up)
from bigdl_tpu.tensor.policy import cast_compute
from bigdl_tpu.utils.log import get_logger

log = get_logger(__name__)

_NEG_INF = -1e30
# contract the last dim of both operands: a @ b.T without the transpose
_NT = (((1,), (1,)), ((), ()))

# What a training kernel may hold in VMEM (Mosaic's scoped default is 16 MiB
# of the v5e core's 128): the limit handed to the compiler, and the share of
# it the block rule fills with what it can count (the pipeline's double
# buffers, the scratch accumulators, the float32 score-tile temporaries);
# the rest is the compiler's own.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_VMEM_BLOCK_BUDGET = _VMEM_LIMIT_BYTES // 2
_LANES = 128
# the blocks the rule (and the autotune spaces) choose among: multiples of
# 128 that nest, so a length one of them divides is divided by the smaller
BLOCK_CHOICES = (128, 256, 512, 1024)


def _tile_bytes(rows, cols, itemsize):
    """VMEM bytes of one (rows, cols) buffer: the lane dim pads to 128."""
    return rows * round_up(cols, _LANES) * itemsize


def block_vmem_bytes(direction: str, block_q: int, block_k: int, d: int,
                     itemsize: int, d_v: Optional[int] = None) -> int:
    """What one grid step holds in VMEM at these blocks, ``fwd`` or ``bwd``
    (the larger of the dq and the dk/dv kernel, which share one pair):
    double-buffered operand and result tiles, the per-row statistics
    (lane- or sublane-padded), float32 accumulators, and the float32
    (block_q, block_k) temporaries of the score tile.  ``d`` is the width
    of q and k, ``d_v`` that of v, out and their gradients (default ``d``)."""
    d_v = d if d_v is None else d_v
    q_tile = _tile_bytes(block_q, d, itemsize)
    k_tile = _tile_bytes(block_k, d, itemsize)
    o_tile = _tile_bytes(block_q, d_v, itemsize)
    v_tile = _tile_bytes(block_k, d_v, itemsize)
    col = _tile_bytes(block_q, 1, 4)
    scores = block_q * block_k * 4
    if direction == "fwd":    # q, k, v -> out, lse; m, l, acc; s, p, p~
        return (2 * (q_tile + o_tile + k_tile + v_tile + col) + 2 * col
                + _tile_bytes(block_q, d_v, 4) + 3 * scores)
    # dq: q, g, k, v, lse, delta -> dq; acc.  dk/dv: the same operands
    # (statistics as rows) -> dk, dv; two acc.  Both: s/p, dp, ds, ds~
    dq = (2 * (2 * q_tile + o_tile + k_tile + v_tile + 2 * col)
          + _tile_bytes(block_q, d, 4))
    dkv = (2 * (q_tile + o_tile + 2 * k_tile + 2 * v_tile
                + 2 * _tile_bytes(8, block_q, 4))
           + _tile_bytes(block_k, d, 4) + _tile_bytes(block_k, d_v, 4))
    return max(dq, dkv) + 4 * scores


def default_blocks(direction: str, sq: int, skv: int, d: int,
                   itemsize: int, d_v: Optional[int] = None) -> Dict[str, int]:
    """The block rule: the largest ``(block_q, block_k)`` of
    ``BLOCK_CHOICES``, none longer than the 128-padded sequence, whose
    :func:`block_vmem_bytes` fits ``_VMEM_BLOCK_BUDGET``.  Largest by area
    (fewest grid steps, least re-reading of K/V per query row); between
    equal areas the squarer pair, then the longer ``block_k`` (the
    contraction the accumulator is rescaled over)."""
    best = None
    for bq in BLOCK_CHOICES:
        for bk in BLOCK_CHOICES:
            if (bq > round_up(sq, _LANES) or bk > round_up(skv, _LANES)
                    or block_vmem_bytes(direction, bq, bk, d, itemsize, d_v)
                    > _VMEM_BLOCK_BUDGET):
                continue
            rank = (bq * bk, -abs(bq - bk), bk)
            if best is None or rank > best[0]:
                best = (rank, bq, bk)
    if best is None:  # a head size no 128 x 128 step fits: let Mosaic say so
        return {"block_q": _LANES, "block_k": _LANES}
    return {"block_q": best[1], "block_k": best[2]}


def _clip_blocks(block_q, block_k, sq, skv):
    """Blocks no longer than the (8-padded) sequence, and the padded
    lengths they tile."""
    bq = min(block_q, round_up(sq, 8))
    bk = min(block_k, round_up(skv, 8))
    return bq, bk, round_up(sq, bq), round_up(skv, bk)


def tile_share(sq: int, skv: int, block_q: int, block_k: int,
               causal: bool) -> float:
    """Tiles the kernels visit / tiles in the (q-blocks x k-blocks)
    rectangle: 1.0 non-causal, 36/64 at 8 x 8 blocks causal."""
    bq, bk, sq_p, skv_p = _clip_blocks(block_q, block_k, sq, skv)
    nq, nk = sq_p // bq, skv_p // bk
    if not causal:
        return 1.0
    visited = sum(min(((i + 1) * bq - 1) // bk, nk - 1) + 1
                  for i in range(nq))
    return visited / (nq * nk)


def _mxu(a, b, dims=(((1,), (0,)), ((), ()))):
    """One tile product on the MXU, float32 out.  Operands narrower than
    float32 are multiplied as they are (a session-wide
    ``jax_default_matmul_precision`` of "highest" asks nothing more of a
    bfloat16 tile, and Mosaic refuses it there); float32 operands follow
    that setting."""
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _pad_seq(x, to):
    s = x.shape[2]
    return x if s == to else jnp.pad(
        x, ((0, 0), (0, 0), (0, to - s), (0, 0)))


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _tile_predicates(qi, kj, *, causal, block_q, block_k, kv_len, skv_p):
    """(visited, masked) for tile (qi, kj): whether the causal mask leaves
    anything of it, and whether the diagonal or the key padding crosses it
    (only then is the element mask built).  Python bools where static."""
    visited, masked = True, False
    if causal:
        visited = kj * block_k < (qi + 1) * block_q
        masked = (kj + 1) * block_k - 1 > qi * block_q
    if skv_p != kv_len:
        pad = (kj + 1) * block_k > kv_len
        masked = pad if masked is False else jnp.logical_or(masked, pad)
    return visited, masked


def _for_visited_tiles(visit, visited, masked):
    """Run ``visit(masked=...)`` under the tile's predicates: one
    specialisation with the element mask, one without (``pl.when`` on a
    Python bool is a plain ``if``)."""
    if masked is False:
        pl.when(visited)(lambda: visit(False))
        return
    pl.when(jnp.logical_and(visited, masked))(lambda: visit(True))
    pl.when(jnp.logical_and(visited, jnp.logical_not(masked)))(
        lambda: visit(False))


def _element_mask(q0, k0, shape, q_axis, *, causal, kv_len):
    """True where key position < kv_len (and <= query position when
    causal), for a score tile whose queries run along ``q_axis``."""
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = k_pos < kv_len
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale, causal, block_q, block_k, kv_len, skv_p):
    # q_ref: (1, block_q, d); k_ref: (1, block_k, d); v_ref: (1, block_k,
    # d_v); o_ref: (1, block_q, d_v) — one tile each.
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def visit(masked):
        v = v_ref[0]
        s = _mxu(q_ref[0], k_ref[0], _NT) * sm_scale
        if masked:
            s = jnp.where(_element_mask(
                qi * block_q, kj * block_k, s.shape, 0, causal=causal,
                kv_len=kv_len), s, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _mxu(p.astype(v.dtype), v)

    _for_visited_tiles(visit, *_tile_predicates(
        qi, kj, causal=causal, block_q=block_q, block_k=block_k,
        kv_len=kv_len, skv_p=skv_p))

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] * (1.0 / l_safe)).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l_safe)


def _kv_index_map(causal, bq, bk, nk, group=1):
    """K/V tile of grid step (bh, i, j): query row ``bh`` of ``(b * h, ..)``
    reads K/V row ``bh // group`` of ``(b * h_kv, ..)``.  Causal: steps
    past the last tile q-block ``i`` visits re-name that tile, so nothing is
    fetched for them."""
    row = (lambda bh: bh) if group == 1 else (lambda bh: bh // group)
    if not causal:
        return lambda bh, i, j: (row(bh), j, 0)
    return lambda bh, i, j: (
        row(bh),
        jnp.minimum(j, jnp.minimum(((i + 1) * bq - 1) // bk, nk - 1)), 0)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    b, h, sq, d = q.shape
    h_kv, skv, d_v = k.shape[1], k.shape[2], v.shape[3]
    bq, bk, sq_p, skv_p = _clip_blocks(block_q, block_k, sq, skv)
    qp = _pad_seq(q, sq_p).reshape(b * h, sq_p, d)
    kp = _pad_seq(k, skv_p).reshape(b * h_kv, skv_p, d)
    vp = _pad_seq(v, skv_p).reshape(b * h_kv, skv_p, d_v)

    nq, nk = sq_p // bq, skv_p // bk
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_k=bk, kv_len=skv, skv_p=skv_p)
    q_spec = pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0))
    o_spec = pl.BlockSpec((1, bq, d_v), lambda bh, i, j: (bh, i, 0))
    kv_map = _kv_index_map(causal, bq, bk, nk, h // h_kv)
    # the row statistics carry a trailing singleton lane dim: a 2-D (1, bq)
    # block would put bq in the lane slot and 1 in the sublane slot, which
    # TPU tiling rejects when batch·heads > 1.
    stat_spec = pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0))

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[q_spec, pl.BlockSpec((1, bk, d), kv_map),
                  pl.BlockSpec((1, bk, d_v), kv_map)],
        out_specs=[o_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d_v), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, d_v), jnp.float32),  # output accumulator
        ],
        compiler_params=_compiler_params(),
        interpret=default_interpret(interpret),
    )(qp, kp, vp)

    out = out.reshape(b, h, sq_p, d_v)[:, :, :sq]
    lse = lse.reshape(b, h, sq_p)[:, :, :sq]
    return out, lse  # lse: (b, h, sq)


def _dq_kernel(q_ref, g_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref,
               dq_scr, *, sm_scale, causal, block_q, block_k, kv_len,
               skv_p):
    """dq for one q-block: p rebuilt from lse, ds = p ⊙ (g·vᵀ − delta),
    dq = scale · Σ_k ds·k over the k-blocks at or below the diagonal."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def visit(masked):
        k = k_ref[0]
        s = _mxu(q_ref[0], k, _NT) * sm_scale
        if masked:
            s = jnp.where(_element_mask(
                qi * block_q, kj * block_k, s.shape, 0, causal=causal,
                kv_len=kv_len), s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        dp = _mxu(g_ref[0], v_ref[0], _NT)
        ds = p * (dp - delta_ref[0])
        dq_scr[...] += _mxu(ds.astype(k.dtype), k)

    _for_visited_tiles(visit, *_tile_predicates(
        qi, kj, causal=causal, block_q=block_q, block_k=block_k,
        kv_len=kv_len, skv_p=skv_p))

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, g_ref, lse_ref, delta_ref, k_ref, v_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, sm_scale, causal, block_q,
                block_k, kv_len, skv_p, q_blocks=None):
    """dk, dv for one k-block, over the q-blocks at or below the diagonal.
    The score tile is built TRANSPOSED (keys along rows, k·qᵀ), so both
    accumulating products are plain (block_k, block_q) @ (block_q, d)
    matmuls and the per-query lse/delta broadcast along rows: nothing is
    transposed in the kernel.  Grouped heads (``q_blocks`` set): the inner
    axis walks the ``q_blocks`` blocks of each of the group's query heads
    in turn, and all of them add into the one accumulator."""
    kj = pl.program_id(1)
    step = pl.program_id(2)
    qi = step if q_blocks is None else step % q_blocks

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def visit(masked):
        q, g = q_ref[0], g_ref[0]
        s_t = _mxu(k_ref[0], q, _NT) * sm_scale
        if masked:
            s_t = jnp.where(_element_mask(
                qi * block_q, kj * block_k, s_t.shape, 1, causal=causal,
                kv_len=kv_len), s_t, _NEG_INF)
        p_t = jnp.exp(s_t - lse_ref[0, 0])
        dv_scr[...] += _mxu(p_t.astype(g.dtype), g)
        dp_t = _mxu(v_ref[0], g, _NT)
        ds_t = p_t * (dp_t - delta_ref[0, 0])
        dk_scr[...] += _mxu(ds_t.astype(q.dtype), q)

    _for_visited_tiles(visit, *_tile_predicates(
        qi, kj, causal=causal, block_q=block_q, block_k=block_k,
        kv_len=kv_len, skv_p=skv_p))

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, sm_scale, causal, block_q, block_k,
               interpret):
    """The Pallas backward pair.  Padding rows and columns carry zero
    gradient: padded queries have g = 0 and delta = 0, padded keys are
    masked out of ``p``; both are sliced off the results."""
    b, h, sq, d = q.shape
    h_kv, skv, d_v = k.shape[1], k.shape[2], v.shape[3]
    bq, bk, sq_p, skv_p = _clip_blocks(block_q, block_k, sq, skv)
    nq, nk = sq_p // bq, skv_p // bk
    bh, bkv, group = b * h, b * h_kv, h // h_kv

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), -1)
    qp = _pad_seq(q, sq_p).reshape(bh, sq_p, d)
    gp = _pad_seq(g, sq_p).reshape(bh, sq_p, d_v)
    kp = _pad_seq(k, skv_p).reshape(bkv, skv_p, d)
    vp = _pad_seq(v, skv_p).reshape(bkv, skv_p, d_v)
    stats = [jnp.pad(x.reshape(bh, sq), ((0, 0), (0, sq_p - sq)))
             for x in (lse, delta)]

    static = dict(sm_scale=sm_scale, causal=causal, block_q=bq, block_k=bk,
                  kv_len=skv, skv_p=skv_p)
    interpret = default_interpret(interpret)

    # dq: grid (bh, q-blocks, k-blocks); statistics as (rows, 1) columns
    q_spec = pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0))
    g_spec = pl.BlockSpec((1, bq, d_v), lambda bh, i, j: (bh, i, 0))
    col_spec = pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0))
    kv_map = _kv_index_map(causal, bq, bk, nk, group)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(bh, nq, nk),
        in_specs=[q_spec, g_spec, col_spec, col_spec,
                  pl.BlockSpec((1, bk, d), kv_map),
                  pl.BlockSpec((1, bk, d_v), kv_map)],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qp, gp, *(x[:, :, None] for x in stats), kp, vp)

    # dk/dv: grid (b * h_kv, k-blocks, group * q-blocks): inner step i is
    # q-block i % nq of the group's query head i // nq (one head, and i
    # itself, where there are no groups); statistics as (1, block_q) rows
    # of a (bh, q-blocks, 1, block_q) view, whose last two block dims span
    # the array's: legal at any block_q.  Causal: the q-blocks before the
    # first one k-block j reaches re-name that first tile.
    def q_row(r, i):
        return r if group == 1 else r * group + i // nq

    def q_block(j, i):
        if group > 1:
            i = i % nq
        if causal:
            i = jnp.maximum(i, jnp.minimum((j * bk) // bq, nq - 1))
        return i

    def qd_spec(width):
        return pl.BlockSpec((1, bq, width),
                            lambda r, j, i: (q_row(r, i), q_block(j, i), 0))

    row_spec = pl.BlockSpec(
        (1, 1, 1, bq), lambda r, j, i: (q_row(r, i), q_block(j, i), 0, 0))
    kd_spec = pl.BlockSpec((1, bk, d), lambda r, j, i: (r, j, 0))
    vd_spec = pl.BlockSpec((1, bk, d_v), lambda r, j, i: (r, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **static,
                          q_blocks=None if group == 1 else nq),
        grid=(bkv, nk, group * nq),
        in_specs=[qd_spec(d), qd_spec(d_v), row_spec, row_spec, kd_spec,
                  vd_spec],
        out_specs=[kd_spec, vd_spec],
        out_shape=[jax.ShapeDtypeStruct((bkv, skv_p, d), k.dtype),
                   jax.ShapeDtypeStruct((bkv, skv_p, d_v), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d_v), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qp, gp, *(x.reshape(bh, nq, 1, bq) for x in stats), kp, vp)

    dq = dq.reshape(b, h, sq_p, d)[:, :, :sq]
    dk = dk.reshape(b, h_kv, skv_p, d)[:, :, :skv]
    dv = dv.reshape(b, h_kv, skv_p, d_v)[:, :, :skv]
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, block_q_bwd,
           block_k_bwd, interpret):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                        interpret)
    return out


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                   block_q_bwd, block_k_bwd, interpret):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                          interpret)
    # named for a layer's jax.checkpoint to keep (ops/common.py)
    out, lse = checkpoint_name(out, ATTN_OUT), checkpoint_name(lse, ATTN_LSE)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, block_q_bwd,
                   block_k_bwd, interpret, res, g):
    q, k, v, out, lse = res
    _book_trace("bwd", q.shape, k.shape[1], k.shape[2], q.dtype, causal,
                block_q_bwd, block_k_bwd)
    return _flash_bwd(q, k, v, out, lse, g, sm_scale, causal, block_q_bwd,
                      block_k_bwd, interpret)


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


@functools.lru_cache(maxsize=None)
def _log_blocks_once(direction, q_shape, kv_heads, skv, dtype, causal,
                     block_q, block_k):
    log.info("flash_attention %s q%s kv %d x %d %s causal=%s: blocks %d x "
             "%d", direction, q_shape, kv_heads, skv, dtype, causal, block_q,
             block_k)


def _book_trace(direction, q_shape, kv_heads, skv, dtype, causal, block_q,
                block_k):
    """Trace-time bookkeeping (nothing of it runs inside the step): that
    this direction was lowered to the Pallas kernels, on what operand
    dtype, with how many query heads to a key/value head, how much of the
    tile rectangle it visits, and — once per shape — the blocks chosen."""
    from bigdl_tpu.optim.metrics import global_metrics

    m = global_metrics()
    m.inc("kernel.flash.traces", labels={
        "direction": direction, "impl": "pallas", "dtype": dtype.name,
        "kv_group": str(q_shape[1] // kv_heads)})
    m.gauge("kernel.flash.tile_share",
            tile_share(q_shape[2], skv, block_q, block_k, causal),
            labels={"direction": direction})
    _log_blocks_once(direction, tuple(q_shape), kv_heads, skv, dtype.name,
                     causal, block_q, block_k)


def resolve_blocks(q_shape, skv, dtype, *, d_v=None, block_q=None,
                   block_k=None, block_k_bwd=None, online_shape=None):
    """``(forward, backward)`` blocks of one call on operands of ``dtype``:
    per axis an explicit kwarg, else a cached autotune winner for this
    device/shape bucket, else :func:`default_blocks`' pick.  Explicit
    ``block_q``/``block_k`` also pin the backward pair's; ``block_k_bwd``
    frees its key block again.  ``d_v``: the width of v where it is not
    q's."""
    from bigdl_tpu.ops import autotune

    dtype = jnp.dtype(dtype)
    sq, d = q_shape[2], q_shape[3]
    key = autotune.attention_key(q_shape, skv, dtype, d_v)
    fwd = autotune.resolve(
        "flash_attention_fwd", key,
        explicit={"block_q": block_q, "block_k": block_k},
        online_shape=online_shape,
        defaults=default_blocks("fwd", sq, skv, d, dtype.itemsize, d_v))
    # the backward: cache/defaults only — no online_shape: a forward-only
    # eager call must not pay a jax.grad tuning sweep for a backward it
    # may never run (the offline CLI tunes flash_attention_bwd)
    bwd = autotune.resolve(
        "flash_attention_bwd", key,
        explicit={"block_q": block_q,
                  "block_k": block_k if block_k_bwd is None
                  else block_k_bwd},
        defaults=default_blocks("bwd", sq, skv, d, dtype.itemsize, d_v))
    return fwd, bwd


def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Fused blockwise attention.  q: (batch, heads, seq, head_dim); k:
    (batch, kv_heads, seq, head_dim); v: (batch, kv_heads, seq, v_dim),
    where ``v_dim`` need not be ``head_dim`` (latent attention publishes
    192-wide keys against 128-wide values) and ``kv_heads`` may be any
    divisor of ``heads`` (grouped-query attention: query head ``j`` reads
    key/value head ``j // (heads / kv_heads)``; K and V are not repeated,
    and ``dk``/``dv`` come back ``kv_heads`` wide).  The result has q's
    heads and v's width.  ``sm_scale`` defaults to ``head_dim ** -0.5``.

    The operands are cast to the policy's compute dtype (bfloat16 on a
    TPU, float32 elsewhere) as ``dot_product_attention`` casts its own;
    accumulation and the softmax statistics are float32 and the result
    comes back in ``q``'s dtype.

    ``block_*=None``: :func:`resolve_blocks` — a cached autotune winner if
    there is one, else the block rule's pick for this shape
    (docs/performance.md §Kernel autotuning); explicit kwargs always
    win."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    kv_heads = k.shape[1]
    if v.shape[1] != kv_heads or q.shape[1] % kv_heads:
        raise ValueError(
            f"flash_attention: {q.shape[1]} query heads on {kv_heads} key "
            f"and {v.shape[1]} value heads: k and v share a head count "
            "that divides q's")
    from bigdl_tpu.ops import autotune

    dtype = q.dtype
    q, k, v = cast_compute(q, k, v)
    skv = k.shape[2]
    # online mode tunes on a cache miss, but only on EAGER calls —
    # inside a jit trace the args are tracers and we must not run timing
    # trials mid-trace
    d_v = None if v.shape[-1] == q.shape[-1] else v.shape[-1]
    # the online tuner's bench shape is (b, h, s, d): equal widths and
    # head counts only
    shape = (tuple(q.shape) + (q.dtype.name,)
             if d_v is None and kv_heads == q.shape[1]
             and autotune.is_concrete(q, k, v) else None)
    fwd, bwd = resolve_blocks(q.shape, skv, q.dtype, d_v=d_v,
                              block_q=block_q, block_k=block_k,
                              block_k_bwd=block_k_bwd, online_shape=shape)
    _book_trace("fwd", q.shape, kv_heads, skv, q.dtype, bool(causal),
                int(fwd["block_q"]), int(fwd["block_k"]))
    out = _flash(q, k, v, float(sm_scale), bool(causal),
                 int(fwd["block_q"]), int(fwd["block_k"]),
                 int(bwd["block_q"]), int(bwd["block_k"]), interpret)
    return out.astype(dtype)
