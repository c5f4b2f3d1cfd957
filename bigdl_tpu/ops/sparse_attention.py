"""Attention over a learned selection of key blocks (InfLLM-v2's sparse
stage, arXiv:2506.07900) — block selection in XLA, flash-style Pallas
kernels that visit only the selected blocks, forward and backward.

Shapes: q ``(batch, groups, heads, T, d)`` (``heads`` query heads read each
of the ``groups`` key/value heads), k and v ``(batch, groups, T, d)``.

**Selection** (:func:`select_blocks`; not differentiated).  Keys are cut
into blocks of ``block`` positions.  Compressed keys are the means of
``kernel``-long windows every ``stride`` positions, ``K_c[w] =
mean(k[stride·w : stride·w + kernel])``; query ``t`` sees the windows that
end at or before it.  Its window scores are the group's held heads' softmax
over those windows, summed over the heads; a block's score is the largest
score of the windows that START in it.  ``Sel_t`` is the first
``init_blocks`` blocks, every block that meets ``[t − window + 1, t]``, and
the highest-scored other blocks at or before ``t``'s own, up to ``topk``
blocks in all (ties to the lower block).  The result is an int32 list
``(batch, groups, T, topk)`` of block ids, the forced blocks first, then
the others by score, ``−1`` at the end where a query sees fewer than
``topk`` blocks.

**The kernels** (:func:`sparse_attention`): ``o_t = softmax over keys s ≤ t
whose block is in Sel_t of (q_t · k_s · scale)`` applied to ``v``, every
held head of a group on its group's selection.  Queries are taken in tiles
of ``block_q`` positions (all of a group's heads in one grid step), keys
in spans of ``block_k`` positions (a whole number of blocks).  A tile's
**work list** is the spans that hold a block any of its queries selected,
and the grid is one flat axis over the (tile, span) pairs of all tiles
(``pltpu.PrefetchScalarGridSpec``: the pairs are scalar-prefetched, packed
``tile << 16 | span``, and the K/V index maps read them), so a span no
query of the tile selected from is neither fetched nor multiplied.  Inside
a step every query row is masked to its own selection, a bit a block of
the span (``_chosen_bits``), and to the causal diagonal.  Selections that
differ from query to query make a tile's list most of the causal spans:
the kernels then do the dense causal work under a mask, and the span is
what sets the cost of a grid step against its work (PERF.md §6, PR 38);
:func:`walked_spans` counts the pairs walked against the causal bound.
The forward carries the online softmax and saves the logsumexp; the
backward is the flash pair: ``dq`` walks the same list, ``dk/dv`` the
transposed one (pairs sorted by block), with ``delta = rowsum(g ⊙ out)``
computed outside.  Every tile selects its own diagonal
block, so every tile and every key block is visited at least once.  The
grid's length is the causal bound on the number of pairs (static); the
pairs past the list's true length re-name its last one and do nothing.
Operands in the compute dtype, statistics and accumulators float32.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.common import ATTN_LSE, ATTN_OUT, default_interpret
from bigdl_tpu.tensor.policy import cast_compute

_NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
_PACK = 16
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# queries whose window scores are held at once while selecting (4 heads:
# 33 MB at 32k positions)
_SELECT_QUERIES = 1024
# query and key positions a grid step takes (whole key blocks, lanes)
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


# -- selection -------------------------------------------------------------


def compress_keys(k, kernel: int, stride: int):
    """``(..., T, d)`` → ``(..., W, d)``: the mean of every ``kernel``-long
    window that starts at a multiple of ``stride`` and ends inside the
    sequence."""
    t, d = k.shape[-2], k.shape[-1]
    if kernel % stride or t % stride:
        raise ValueError(f"kernel {kernel}, stride {stride}, T {t}: the "
                         "windows must be whole strides of the sequence")
    r = kernel // stride
    parts = k.reshape(k.shape[:-2] + (t // stride, stride, d)).mean(-2)
    n = t // stride - r + 1
    return sum(parts[..., i:i + n, :] for i in range(r)) / r


def select_blocks(q, k, *, kernel: int, stride: int, block: int, topk: int,
                  init_blocks: int, window: int):
    """Each query's block list, ``(batch, groups, T, topk)`` int32 (the
    module docstring has the rule).  q ``(batch, groups, heads, T, d)``, k
    ``(batch, groups, T, d)``.  Computed ``_SELECT_QUERIES`` queries at a
    time: the window scores of all of T at once are ``heads · T · T /
    stride`` floats."""
    b, g, h, t, d = q.shape
    if t % block or block % stride:
        raise ValueError(f"T {t}, block {block}, stride {stride}: blocks "
                         "must be whole strides and tile the sequence")
    if topk < init_blocks + (window + block - 2) // block + 1:
        raise ValueError(f"topk {topk}: fewer than the {init_blocks} first "
                         f"and the local blocks a {window}-position window "
                         "can meet")
    n_blocks, per_block = t // block, block // stride
    kc = cast_compute(compress_keys(k.astype(jnp.float32), kernel, stride))
    n_win = kc.shape[-2]
    win_end = jnp.arange(n_win) * stride + kernel - 1          # (W,)
    blocks = jnp.arange(n_blocks)
    qc = min(_SELECT_QUERIES, t)
    while t % qc:
        qc //= 2
    scale = d ** -0.5

    def one_chunk(i):
        pos = i * qc + jnp.arange(qc)                             # (qc,)
        qi = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=3)
        s = jnp.einsum("bghtd,bgwd->bghtw", cast_compute(qi), kc,
                       preferred_element_type=jnp.float32) * scale
        seen = win_end[None, :] <= pos[:, None]                   # (qc, W)
        s = jnp.where(seen, s, -jnp.inf)
        m = jnp.max(s, -1, keepdims=True)
        e = jnp.where(seen, jnp.exp(s - jnp.where(seen.any(-1, keepdims=True),
                                                  m, 0.0)), 0.0)
        z = jnp.sum(e, -1, keepdims=True)
        p = jnp.sum(e / jnp.where(z > 0, z, 1.0), 2)            # (b,g,qc,W)
        # a block's score: the largest of the windows that start in it
        pad = n_blocks * per_block - n_win
        p = jnp.pad(p, ((0, 0), (0, 0), (0, 0), (0, pad)))
        score = p.reshape(b, g, qc, n_blocks, per_block).max(-1)
        own = pos // block
        lo = jnp.maximum(pos - window + 1, 0) // block
        forced = ((blocks[None, :] < init_blocks)
                  | ((blocks[None, :] >= lo[:, None])
                     & (blocks[None, :] <= own[:, None])))
        visible = blocks[None, :] <= own[:, None]
        rank = jnp.where(forced, 2.0 + h, jnp.where(visible, score, -1.0))
        top, idx = jax.lax.top_k(rank, min(topk, n_blocks))
        idx = jnp.where(top >= 0, idx, -1)
        pad = [(0, 0)] * (idx.ndim - 1) + [(0, topk - idx.shape[-1])]
        return jnp.pad(idx, pad, constant_values=-1)

    sel = jax.lax.map(one_chunk, jnp.arange(t // qc))    # (n, b, g, qc, K)
    return jnp.moveaxis(sel, 0, 2).reshape(b, g, t, topk).astype(jnp.int32)


def visible_blocks(t: int, block: int) -> int:
    """Σ over the positions of a sequence of the blocks a query can see,
    ``t // block + 1``."""
    n = t // block
    return block * n * (n + 1) // 2 + (t - n * block) * (n + 1)


# -- work lists ----------------------------------------------------------------


def _pairs_bound(n_tiles, n_spans, block_q, block_k):
    """The most (tile, span) pairs the causal mask allows."""
    return sum(min(n_spans, -(-(i + 1) * block_q // block_k))
               for i in range(n_tiles))


def _span_hits(sel, block: int, block_q: int, block_k: int):
    """(BG, tiles, spans) bool of ``sel`` (BG, T, K): where a query of the
    tile selected a block of the span."""
    bg, t, _ = sel.shape
    n_tiles, n_spans = t // block_q, t // block_k
    tile = jnp.arange(t) // block_q
    span = jnp.where(sel >= 0, sel * block // block_k, n_spans)
    return jnp.zeros((bg, n_tiles, n_spans + 1), bool).at[
        jnp.arange(bg)[:, None, None], tile[None, :, None], span].set(
            True)[..., :n_spans]


def work_lists(sel, block: int, block_q: int, block_k: int):
    """``(fwd, dkv, n)``: per row of ``sel`` (BG, T, K), the (tile, span)
    pairs in which a query of the tile selected a block of the span,
    sorted by tile (``fwd``, packed ``tile << 16 | span``) and by span
    (``dkv``, ``span << 16 | tile``), each ``(BG · P,)`` with P the causal
    bound, and their true count ``n`` (BG,).  Entries past the count repeat
    the last one."""
    bg, t, _ = sel.shape
    n_tiles, n_spans = t // block_q, t // block_k
    bound = _pairs_bound(n_tiles, n_spans, block_q, block_k)
    hits = _span_hits(sel, block, block_q, block_k)
    count = hits.sum((1, 2)).astype(jnp.int32)

    def listed(flags, minor):
        def one(f, c):
            (idx,) = jnp.nonzero(f, size=bound, fill_value=0)
            idx = jnp.where(jnp.arange(bound) < c, idx, idx[c - 1])
            return ((idx // minor) << _PACK) | (idx % minor)
        return jax.vmap(one)(flags.reshape(bg, -1), count).reshape(-1)

    fwd = listed(hits, n_spans)
    dkv = listed(jnp.swapaxes(hits, 1, 2), n_tiles)
    return fwd.astype(jnp.int32), dkv.astype(jnp.int32), count


def walked_spans(sel, block: int = 64, block_q: Optional[int] = None,
                 block_k: Optional[int] = None):
    """``(walked, causal)``: the (tile, span) pairs the kernels walk for
    ``sel`` (batch, groups, T, K), summed over its groups (a traced int32),
    and the most the causal mask allows them (a Python int).  What a tile
    costs is its list's length, not its queries' selections: a walk as
    long as the causal bound is dense work under a mask."""
    b, g, t, _ = sel.shape
    block_q, block_k = _tiles(t, block_q, block_k)
    walked = jnp.sum(_span_hits(sel.reshape(b * g, t, -1), block, block_q,
                                block_k), dtype=jnp.int32)
    return walked, b * g * causal_spans(t, block_q, block_k)


def causal_spans(t: int, block_q: Optional[int] = None,
                 block_k: Optional[int] = None) -> int:
    """The (tile, span) pairs the causal mask allows one row of a sequence
    of ``t``, at the kernels' tiles."""
    block_q, block_k = _tiles(t, block_q, block_k)
    return _pairs_bound(t // block_q, t // block_k, block_q, block_k)


def _tiles(t, block_q, block_k):
    return (min(int(block_q or DEFAULT_BLOCK_Q), t),
            min(int(block_k or DEFAULT_BLOCK_K), t))


def _walk(work_ref, n_ref, bound):
    """(major, minor, first, last, live) of this grid step's pair."""
    g, s = pl.program_id(0), pl.program_id(1)
    base = g * bound
    cur = work_ref[base + s]
    major = cur >> _PACK
    prev = work_ref[base + jnp.maximum(s - 1, 0)] >> _PACK
    nxt = work_ref[base + jnp.minimum(s + 1, bound - 1)] >> _PACK
    first = jnp.logical_or(s == 0, prev != major)
    last = jnp.logical_or(s == bound - 1, nxt != major)
    return major, cur & ((1 << _PACK) - 1), first, last, s < n_ref[g]


def _mxu(a, b, dims=_NN):
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _chosen_bits(sel, span, per_span, axis):
    """Bit ``j`` set where a query selected block ``span · per_span + j``:
    ``sel`` is the tile's lists, one query a row (axis 1) or a column
    (axis 0); the bits come back (queries, 1) or (1, queries)."""
    bits = None
    for j in range(per_span):
        hit = jnp.max(jnp.where(sel == span * per_span + j, 1, 0), axis=axis,
                      keepdims=True) << j
        bits = hit if bits is None else bits | hit
    return bits


def _mask(bits, span, tile, block, block_q, block_k, keys_axis):
    """The (queries, keys) mask of a step, or its transpose: a key attends
    where its block's bit is set and it is not after the query."""
    shape = ((block_q, block_k) if keys_axis == 1 else (block_k, block_q))
    k_at = jax.lax.broadcasted_iota(jnp.int32, shape, keys_axis)
    q_at = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - keys_axis)
    chosen = jnp.right_shift(bits, k_at // block) & 1
    return jnp.logical_and(chosen == 1, span * block_k + k_at
                           <= tile * block_q + q_at)


# -- forward -------------------------------------------------------------------


def _fwd_kernel(work_ref, n_ref, q_ref, sel_ref, k_ref, v_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr, *, sm_scale, block, block_q,
                block_k, heads, bound):
    tile, span, first, last, live = _walk(work_ref, n_ref, bound)

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _step():
        bits = _chosen_bits(sel_ref[0], span, block_k // block, 1)
        mask = _mask(bits, span, tile, block, block_q, block_k, 1)
        k, v = k_ref[0], v_ref[0]
        for h in range(heads):
            s = jnp.where(mask, _mxu(q_ref[0, h], k, _NT) * sm_scale,
                          _NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            m_scr[h] = m_new
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + _mxu(p.astype(v.dtype), v)

    @pl.when(last)
    def _finish():
        for h in range(heads):
            l = l_scr[h]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_scr[h] / l).astype(o_ref.dtype)
            lse_ref[0, h] = m_scr[h] + jnp.log(l)


def _specs(heads, block_q, block_k, bound, by_span):
    """Block specs of the operands, for a walk over pairs sorted by tile
    (``by_span`` False) or by span, and the tile a step reads."""
    low = (1 << _PACK) - 1

    def tile_of(g, s, w):
        cur = w[g * bound + s]
        return (cur & low) if by_span else cur >> _PACK

    def span_of(g, s, w):
        cur = w[g * bound + s]
        return cur >> _PACK if by_span else cur & low

    def q_like(width):
        return pl.BlockSpec((1, heads, block_q, width),
                            lambda g, s, w, n: (g, 0, tile_of(g, s, w), 0))

    def kv(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda g, s, w, n: (g, span_of(g, s, w), 0))

    return q_like, kv, tile_of


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _sparse_fwd(q, k, v, sel, fwd, n, sm_scale, block, block_q, block_k,
                interpret):
    bg, heads, t, d = q.shape
    d_v = v.shape[-1]
    bound = fwd.shape[0] // bg
    q_like, kv, tile_of = _specs(heads, block_q, block_k, bound, False)
    stat = pl.BlockSpec((1, heads, block_q, 1),
                        lambda g, s, w, n: (g, 0, tile_of(g, s, w), 0))
    sel_spec = pl.BlockSpec((1, block_q, sel.shape[-1]),
                            lambda g, s, w, n: (g, tile_of(g, s, w), 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(bg, bound),
        in_specs=[q_like(d), sel_spec, kv(d), kv(d_v)],
        out_specs=[q_like(d_v), stat],
        scratch_shapes=[pltpu.VMEM((heads, block_q, 1), jnp.float32),
                        pltpu.VMEM((heads, block_q, 1), jnp.float32),
                        pltpu.VMEM((heads, block_q, d_v), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, block=block,
                          block_q=block_q, block_k=block_k, heads=heads,
                          bound=bound),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bg, heads, t, d_v), q.dtype),
                   jax.ShapeDtypeStruct((bg, heads, t, 1), jnp.float32)],
        compiler_params=_params(),
        interpret=default_interpret(interpret),
    )(fwd, n, q, sel, k, v)


# -- backward ------------------------------------------------------------------


def _dq_kernel(work_ref, n_ref, q_ref, g_ref, lse_ref, delta_ref, sel_ref,
               k_ref, v_ref, dq_ref, dq_scr, *, sm_scale, block, block_q,
               block_k, heads, bound):
    tile, span, first, last, live = _walk(work_ref, n_ref, bound)

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(live)
    def _step():
        bits = _chosen_bits(sel_ref[0], span, block_k // block, 1)
        mask = _mask(bits, span, tile, block, block_q, block_k, 1)
        k, v = k_ref[0], v_ref[0]
        for h in range(heads):
            s = _mxu(q_ref[0, h], k, _NT) * sm_scale
            p = jnp.where(mask, jnp.exp(s - lse_ref[0, h]), 0.0)
            dp = _mxu(g_ref[0, h], v, _NT)
            ds = p * (dp - delta_ref[0, h])
            dq_scr[h] += _mxu(ds.astype(k.dtype), k)

    @pl.when(last)
    def _finish():
        for h in range(heads):
            dq_ref[0, h] = (dq_scr[h] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(work_ref, n_ref, q_ref, g_ref, lse_ref, delta_ref, selt_ref,
                k_ref, v_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale,
                block, block_q, block_k, heads, bound):
    span, tile, first, last, live = _walk(work_ref, n_ref, bound)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(live)
    def _step():
        bits = _chosen_bits(selt_ref[0], span, block_k // block, 0)
        mask = _mask(bits, span, tile, block, block_q, block_k, 0)
        k, v = k_ref[0], v_ref[0]
        for h in range(heads):
            q, g = q_ref[0, h], g_ref[0, h]
            s_t = _mxu(k, q, _NT) * sm_scale
            p_t = jnp.where(mask, jnp.exp(s_t - lse_ref[0, h, 0]), 0.0)
            dv_scr[...] += _mxu(p_t.astype(g.dtype), g)
            dp_t = _mxu(v, g, _NT)
            ds_t = p_t * (dp_t - delta_ref[0, h, 0])
            dk_scr[...] += _mxu(ds_t.astype(q.dtype), q)

    @pl.when(last)
    def _finish():
        dk_ref[0] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _sparse_bwd(q, k, v, sel, out, lse, g, lists, sm_scale, block, block_q,
                block_k, interpret):
    fwd, dkv, n = lists
    bg, heads, t, d = q.shape
    d_v, n_tiles = v.shape[-1], t // block_q
    bound = fwd.shape[0] // bg
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), -1,
                    keepdims=True)
    interpret = default_interpret(interpret)
    static = dict(sm_scale=sm_scale, block=block, block_q=block_q,
                  block_k=block_k, heads=heads, bound=bound)

    q_like, kv, tile_of = _specs(heads, block_q, block_k, bound, False)
    col = pl.BlockSpec((1, heads, block_q, 1),
                       lambda g_, s, w, n_: (g_, 0, tile_of(g_, s, w), 0))
    sel_spec = pl.BlockSpec((1, block_q, sel.shape[-1]),
                            lambda g_, s, w, n_: (g_, tile_of(g_, s, w), 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bg, bound),
            in_specs=[q_like(d), q_like(d_v), col, col, sel_spec, kv(d),
                      kv(d_v)],
            out_specs=q_like(d),
            scratch_shapes=[pltpu.VMEM((heads, block_q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_params(), interpret=interpret,
    )(fwd, n, q, g, lse, delta, sel, k, v)

    q_like, kv, tile_of = _specs(heads, block_q, block_k, bound, True)
    # statistics as (1, block_q) rows of a (BG, heads, tiles, 1, block_q)
    # view; the selection as (K, block_q) columns of its (K, T) transpose
    row = pl.BlockSpec((1, heads, 1, 1, block_q),
                       lambda g_, s, w, n_: (g_, 0, tile_of(g_, s, w), 0, 0))
    selt_spec = pl.BlockSpec((1, sel.shape[-1], block_q),
                             lambda g_, s, w, n_: (g_, 0, tile_of(g_, s, w)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bg, bound),
            in_specs=[q_like(d), q_like(d_v), row, row, selt_spec, kv(d),
                      kv(d_v)],
            out_specs=[kv(d), kv(d_v)],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d_v), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params(), interpret=interpret,
    )(dkv, n, q, g, *(x.reshape(bg, heads, n_tiles, 1, block_q)
                      for x in (lse, delta)),
      jnp.swapaxes(sel, 1, 2), k, v)
    return dq, dk, dv


# -- the differentiated call -----------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _sparse(q, k, v, sel, sm_scale, block, block_q, block_k, interpret):
    fwd, _, n = work_lists(sel, block, block_q, block_k)
    return _sparse_fwd(q, k, v, sel, fwd, n, sm_scale, block, block_q,
                       block_k, interpret)[0]


def _sparse_vjp_fwd(q, k, v, sel, sm_scale, block, block_q, block_k,
                    interpret):
    lists = work_lists(sel, block, block_q, block_k)
    out, lse = _sparse_fwd(q, k, v, sel, lists[0], lists[2], sm_scale,
                           block, block_q, block_k, interpret)
    # named for a layer's jax.checkpoint to keep (ops/common.py)
    out, lse = checkpoint_name(out, ATTN_OUT), checkpoint_name(lse, ATTN_LSE)
    return out, (q, k, v, sel, out, lse, lists)


def _sparse_vjp_bwd(sm_scale, block, block_q, block_k, interpret, res, g):
    q, k, v, sel, out, lse, lists = res
    dq, dk, dv = _sparse_bwd(q, k, v, sel, out, lse, g.astype(q.dtype),
                             lists, sm_scale, block, block_q, block_k,
                             interpret)
    return dq, dk, dv, None


_sparse.defvjp(_sparse_vjp_fwd, _sparse_vjp_bwd)


def sparse_attention(q, k, v, sel, *, block: int = 64,
                     sm_scale: Optional[float] = None,
                     block_q: Optional[int] = None,
                     block_k: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Attention of every query over the key blocks its group selected
    (``sel`` from :func:`select_blocks`), causal inside its own block.
    q ``(batch, groups, heads, T, d)``, k ``(batch, groups, T, d)``, v
    ``(batch, groups, T, d_v)``, sel ``(batch, groups, T, K)`` int32.  The
    result has q's heads, v's width and q's dtype; ``sel`` is not
    differentiated.  ``block_q`` and ``block_k`` (defaults
    ``DEFAULT_BLOCK_Q`` / ``DEFAULT_BLOCK_K``, no longer than T) are the
    query and key positions of a grid step: whole key blocks, at most 32
    blocks to a span, and multiples of 128 where Mosaic compiles them."""
    b, g, h, t, d = q.shape
    if (k.shape != (b, g, t, d) or v.shape[:3] != (b, g, t)
            or sel.shape[:3] != (b, g, t)):
        raise ValueError(f"sparse_attention: q{q.shape} k{k.shape} "
                         f"v{v.shape} sel{sel.shape}")
    block_q, block_k = _tiles(t, block_q, block_k)
    if (t % block_q or t % block_k or block_q % block or block_k % block
            or block_k > 32 * block):
        raise ValueError(f"T {t}, block_q {block_q}, block_k {block_k}, "
                         f"block {block}: tiles and spans of whole blocks "
                         "that divide the sequence, 32 blocks a span at most")
    if (block_q % 128 or block_k % 128) and not default_interpret(interpret):
        raise ValueError(f"block_q {block_q}, block_k {block_k}: Mosaic "
                         "takes both as lanes somewhere, 128 at least")
    sm_scale = d ** -0.5 if sm_scale is None else float(sm_scale)
    dtype = q.dtype
    q, k, v = cast_compute(q, k, v)
    out = _sparse(q.reshape(b * g, h, t, d), k.reshape(b * g, t, d),
                  v.reshape(b * g, t, v.shape[-1]),
                  sel.reshape(b * g, t, sel.shape[-1]).astype(jnp.int32),
                  sm_scale, int(block), block_q, block_k, interpret)
    return out.reshape(b, g, h, t, v.shape[-1]).astype(dtype)
