"""Mamba-2's state-space duality (SSD, arXiv:2405.21060): a selective scan
with a decay a token and a head — Pallas TPU kernels, forward and backward.

For each head ``h``, with ``x_t`` (P,), ``Δ_t ≥ 0``, ``A_h ≤ 0`` and
``B_t``, ``C_t`` (N,) shared by every head (one group)::

    S_t = exp(Δ_t A_h) S_{t−1} + B_t (Δ_t x_t)ᵀ        S is (N, P), S_{−1} = 0
    y_t = S_tᵀ C_t + D_h x_t

which is causal linear attention whose decay is ``exp(cum_t − cum_s)``
between positions ``s ≤ t``, ``cum`` the running sum of ``Δ A``.  The
kernel walks the sequence in chunks of ``Q`` positions and carries every
head's float32 state in VMEM from one chunk to the next.  Inside a chunk,
with ``cum`` restarted at the chunk's start (``c_i``, inclusive)::

    y  = ((C Bᵀ) ⊙ L) (Δ⊙x) + exp(c) ⊙ (C S)
    S' = exp(c_last) S + (B ⊙ exp(c_last − c))ᵀ (Δ⊙x)

with ``L[i, j] = exp(c_i − c_j)`` for ``j ≤ i`` and 0 above the diagonal.

**The sequence is on the lanes**, in the T-minor layout XLA gives the
Mamba-2 mixer's activations (``W_in``'s output, the convolution's kernels,
the gate and the norm).  A head's chunk of ``Δ⊙x``, ``y`` and their
cotangents is a ``(P, Q)`` block read through the index map (64 sublanes by
256 lanes at Granite's shape, so no tile is padded): of a ``(batch, heads,
P, T)`` array for ``Δ⊙x`` (XLA fuses Δ's broadcast over P into the product
in that shape, and writes it out as an array of its own in a ``(batch,
heads·P, T)`` one) and of a ``(batch, heads·P, T)`` array for ``y`` (which
XLA fuses into the mixer's gate and norm).  ``Bᵀ`` and ``Cᵀ`` are ``(batch,
N, T)`` float32, one ``(N, Q)`` block a chunk rounded to the compute dtype
in VMEM (rounded by XLA, all of the convolution's output would be rounded
to cut them out); the chunk sums ``c`` are one ``(1, Q)`` row a chunk and
head.  :func:`ssd` keeps the ``(batch, T, heads, P)`` interface, and its
swaps to and from these arrays are bitcasts wherever XLA holds them
T-minor.  So every product runs transposed, with the same FLOPs, and the
state is carried as ``Sᵀ`` ``(P, N)``::

    yᵀ  = (Δ⊙x)ᵀ (Gᵀ ⊙ Lᵀ) + exp(c) ⊙ (Sᵀ Cᵀ)          G = C Bᵀ
    S'ᵀ = exp(c_last) Sᵀ + (Δ⊙x)ᵀ (Bᵀ ⊙ w)ᵀ             w = exp(c_last − c)

``exp(c)`` and ``w`` are rows here, broadcast over sublanes.  Every decay
is ``exp`` of a number at or below zero: a head that forgets fast
underflows to 0 and nothing overflows.  The grid is (batch, chunks,
heads), heads innermost: ``Gᵀ`` is one product a chunk for all heads, and
``Bᵀ``, ``Cᵀ`` are fetched once a chunk.  ``D x`` is added outside the
kernels, in ``jax.numpy`` (the Mamba-2 mixer adds it itself, beside its
gate).

The backward pass is two kernels.  The first walks forward in time and
writes the state each chunk starts from (``Sᵀ``, float32, ``(P, N)`` a
chunk and a head).  The second walks backward in time and carries each
head's state cotangent ``dSᵀ`` in VMEM::

    d(Δ⊙x)ᵀ = dyᵀ (G ⊙ L) + w ⊙ (dSᵀ Bᵀ)
    dG      = (dy (Δ⊙x)ᵀ) ⊙ L                         summed over heads
    dCᵀ     = Bᵀ (Σ_h dG)ᵀ + exp(c) ⊙ (S dyᵀ)
    dBᵀ     = Cᵀ Σ_h dG + w ⊙ (dS (Δ⊙x)ᵀ)
    dc      = rows(dG ⊙ G) − columns(dG ⊙ G) + exp(c) ⊙ columns(Cᵀ ⊙ S dyᵀ)
              − w ⊙ r + [at c_last] (Σ w ⊙ r + exp(c_last) Σ dS ⊙ S)
    dS_inᵀ  = exp(c_last) dSᵀ + (exp(c) ⊙ dyᵀ) C

with ``r = columns(Bᵀ ⊙ dS (Δ⊙x)ᵀ)`` (``rows`` sums along a row,
``columns`` down a column).  Three of these contract over P or N on the
sublanes of both operands; their small tiles are transposed in VMEM, never
in HBM: the ``(N, Q)`` tiles of ``Bᵀ`` (forward) and ``Cᵀ`` (backward) once
a chunk, ``dyᵀ`` ``(P, Q)`` and the states ``Sᵀ``, ``dSᵀ`` ``(P, N)`` once a
chunk and head.  ``c``'s column form is the diagonal's lane sums of a (Q, Q)
mask, exact, as the cotangent's row sums are turned into a row (a (Q, 1)
operand in HBM would take a 128-lane tile a position).  ``Δ``, ``A`` and
``x`` reach the kernels only through ``Δ⊙x`` and ``c``, so autodiff of
those two expressions gives ``dx``, ``dΔ`` and ``dA``; ``dD`` is
autodiff's.  Operands in the policy's compute dtype
(``tensor.policy.cast_compute``) with float32 accumulation; ``Δ``, the
decays, the states and their cotangents float32.

**The shape rule.**  On the TPU, P a multiple of 16 (a ``(P, Q)`` block of
the compute dtype is whole sublane tiles) and the chunk a multiple of 128
lanes or the whole padded sequence; any other shape raises ``ValueError``.
Off the TPU the same kernels run in interpret mode at any shape.  T is
padded at the end to a whole chunk: ``Δ`` = 0 there, which neither writes
the state nor decays it.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.common import default_interpret, round_up
from bigdl_tpu.tensor.policy import cast_compute

# Mamba-2's published ``chunk_size`` (``mamba_chunk_size`` of Granite-4.0-H)
DEFAULT_CHUNK = 256
_NT = (((1,), (1,)), ((), ()))
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _mxu(a, b, dims=(((1,), (0,)), ((), ()))):
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _t(tile):
    """A VMEM tile transposed, through float32 and back to its dtype."""
    return tile.astype(jnp.float32).T.astype(tile.dtype)


def _iota(chunk, axis):
    return jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), axis)


def _decay(col, row, chunk, lower):
    """``exp(col − row)`` where the row index is at or past the column
    index (``lower``; else at or before it), 0 elsewhere.  ``col`` (Q, 1),
    ``row`` (1, Q), or the other way round for the transpose."""
    keep = (_iota(chunk, 0) >= _iota(chunk, 1)) if lower else (
        _iota(chunk, 0) <= _iota(chunk, 1))
    return jnp.where(keep, jnp.exp(jnp.minimum(col - row, 0.0)), 0.0)


def _column(row, chunk):
    """(Q, 1) of a (1, Q) row: the diagonal's lane sums, exact (a (Q, 1)
    operand in HBM would take a 128-lane tile a row)."""
    return jnp.sum(jnp.where(_iota(chunk, 0) == _iota(chunk, 1), row, 0.0),
                   axis=1, keepdims=True)


def _row(col, chunk):
    """(1, Q) of a (Q, 1) column, as ``_column`` does the other way."""
    return jnp.sum(jnp.where(_iota(chunk, 0) == _iota(chunk, 1), col, 0.0),
                   axis=0, keepdims=True)


def _last(row, chunk):
    """(1, 1): the chunk's last entry of a (1, Q) row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    return jnp.sum(jnp.where(lane == chunk - 1, row, 0.0), axis=1,
                   keepdims=True)


def _advance(state, xd, bt, cr, last):
    """``S'ᵀ = exp(c_last) Sᵀ + (Δ⊙x)ᵀ (Bᵀ ⊙ w)ᵀ``: (P, N)."""
    return jnp.exp(last) * state + _mxu(
        xd, (bt * jnp.exp(last - cr)).astype(xd.dtype), _NT)


def _fwd_kernel(xd_ref, cr_ref, bt_ref, ct_ref, y_ref, s_scr, gt_scr, *,
                chunk):
    k, h = pl.program_id(1), pl.program_id(2)
    dtype = xd_ref.dtype
    bt, ct = bt_ref[0].astype(dtype), ct_ref[0].astype(dtype)

    @pl.when(h == 0)
    def _products():
        gt_scr[...] = _mxu(_t(bt), ct)

    @pl.when(k == 0)
    def _init():
        s_scr[h] = jnp.zeros(s_scr.shape[1:], s_scr.dtype)

    xd, cr = xd_ref[0, 0], cr_ref[0, 0]
    cc, last = _column(cr, chunk), _last(cr, chunk)
    state = s_scr[h]
    mt = gt_scr[...] * _decay(cr, cc, chunk, False)
    y = _mxu(xd, mt.astype(xd.dtype)) + jnp.exp(cr) * _mxu(
        state.astype(xd.dtype), ct)
    y_ref[0] = y.astype(y_ref.dtype)
    s_scr[h] = _advance(state, xd, bt, cr, last)


def _states_kernel(xd_ref, cr_ref, bt_ref, st_ref, s_scr, *, chunk):
    k, h = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        s_scr[h] = jnp.zeros(s_scr.shape[1:], s_scr.dtype)

    cr = cr_ref[0, 0]
    state = s_scr[h]
    st_ref[0, 0, 0] = state
    s_scr[h] = _advance(state, xd_ref[0, 0], bt_ref[0].astype(xd_ref.dtype),
                        cr, _last(cr, chunk))


def _bwd_kernel(xd_ref, dy_ref, cr_ref, bt_ref, ct_ref, st_ref, dxd_ref,
                dcum_ref, dbt_ref, dct_ref, ds_scr, c_scr, g_scr, dg_scr, *,
                chunk, heads):
    k, h = pl.program_id(1), pl.program_id(2)
    dtype = xd_ref.dtype
    bt, ct = bt_ref[0].astype(dtype), ct_ref[0].astype(dtype)

    @pl.when(h == 0)
    def _products():
        c_scr[...] = _t(ct)
        g_scr[...] = _mxu(c_scr[...], bt)
        dg_scr[...] = jnp.zeros_like(dg_scr)
        dbt_ref[0] = jnp.zeros(dbt_ref.shape[1:], dbt_ref.dtype)
        dct_ref[0] = jnp.zeros(dct_ref.shape[1:], dct_ref.dtype)

    @pl.when(k == 0)
    def _init():
        ds_scr[h] = jnp.zeros(ds_scr.shape[1:], ds_scr.dtype)

    xd, dy, cr = xd_ref[0, 0], dy_ref[0], cr_ref[0, 0]
    cc, last = _column(cr, chunk), _last(cr, chunk)
    e, w = jnp.exp(cr), jnp.exp(last - cr)
    state, dstate = st_ref[0, 0, 0], ds_scr[h]
    lower = _decay(cc, cr, chunk, True)
    g = g_scr[...]
    dxd = _mxu(dy, (g * lower).astype(dy.dtype)) + w * _mxu(
        dstate.astype(bt.dtype), bt)
    dxd_ref[0, 0] = dxd.astype(dxd_ref.dtype)
    dg = _mxu(_t(dy), xd) * lower
    dg_scr[...] += dg
    z = dg * g
    dyh = _mxu(state.T.astype(dy.dtype), dy)
    xdh = _mxu(dstate.T.astype(xd.dtype), xd)
    dct_ref[0] += e * dyh
    dbt_ref[0] += w * xdh
    de = e * jnp.sum(ct.astype(jnp.float32) * dyh, axis=0, keepdims=True)
    dw = w * jnp.sum(bt.astype(jnp.float32) * xdh, axis=0, keepdims=True)
    d_last = (jnp.sum(dw, axis=1, keepdims=True)
              + jnp.exp(last) * jnp.sum(dstate * state, keepdims=True))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    dcum_ref[0, 0] = (_row(jnp.sum(z, axis=1, keepdims=True), chunk)
                      - jnp.sum(z, axis=0, keepdims=True) + de - dw
                      + jnp.where(lane == chunk - 1, d_last, 0.0))
    ds_scr[h] = jnp.exp(last) * dstate + _mxu((e * dy).astype(dy.dtype),
                                              c_scr[...])

    @pl.when(h == heads - 1)
    def _shared():
        dgs = dg_scr[...].astype(bt.dtype)
        dct_ref[0] += _mxu(bt, dgs, _NT)
        dbt_ref[0] += _mxu(ct, dgs)


def _specs(p, chunk, n_chunks, reverse):
    """BlockSpecs by kind for grid (batch, chunk step, head): a head's
    ``(P, Q)`` block of ``Δ⊙x`` (batch, heads, P, T) and of ``y`` (batch,
    heads·P, T), its ``(1, Q)`` row of ``c``, a chunk's ``(N, Q)`` block of
    ``Bᵀ`` or ``Cᵀ``, a head's ``(P, N)`` state at a chunk's start."""
    ci = (lambda k: n_chunks - 1 - k) if reverse else (lambda k: k)
    return {
        "x": pl.BlockSpec((1, 1, p, chunk),
                          lambda b, k, h: (b, h, 0, ci(k))),
        "y": pl.BlockSpec((1, p, chunk), lambda b, k, h: (b, h, ci(k))),
        "row": pl.BlockSpec((1, 1, 1, chunk),
                            lambda b, k, h: (b, h, 0, ci(k))),
        "nt": lambda n: pl.BlockSpec((1, n, chunk),
                                     lambda b, k, h: (b, 0, ci(k))),
        "state": lambda n: pl.BlockSpec(
            (1, 1, 1, p, n), lambda b, k, h: (b, h, ci(k), 0, 0)),
    }


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=default_interpret(interpret))


def _dims(xd, bt, chunk):
    """(batch, heads, P, N, chunks) of ``xd`` (batch, heads, P, T) and
    ``bt`` (batch, N, T)."""
    batch, heads, p, t = xd.shape
    return batch, heads, p, bt.shape[1], t // chunk


def _forward(xd, cr, bt, ct, chunk, interpret):
    batch, heads, p, n, n_chunks = _dims(xd, bt, chunk)
    s = _specs(p, chunk, n_chunks, False)
    return _call(functools.partial(_fwd_kernel, chunk=chunk),
                 (batch, n_chunks, heads),
                 [s["x"], s["row"], s["nt"](n), s["nt"](n)], s["y"],
                 jax.ShapeDtypeStruct((batch, heads * p, n_chunks * chunk),
                                      xd.dtype),
                 [pltpu.VMEM((heads, p, n), jnp.float32),
                  pltpu.VMEM((chunk, chunk), jnp.float32)], interpret,
                 )(xd, cr, bt, ct)


def _states(xd, cr, bt, chunk, interpret):
    """The state each chunk starts from, transposed: (batch, heads,
    chunks, P, N)."""
    batch, heads, p, n, n_chunks = _dims(xd, bt, chunk)
    s = _specs(p, chunk, n_chunks, False)
    return _call(functools.partial(_states_kernel, chunk=chunk),
                 (batch, n_chunks, heads), [s["x"], s["row"], s["nt"](n)],
                 s["state"](n),
                 jax.ShapeDtypeStruct((batch, heads, n_chunks, p, n),
                                      jnp.float32),
                 [pltpu.VMEM((heads, p, n), jnp.float32)], interpret,
                 )(xd, cr, bt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd(xd, cr, bt, ct, chunk, interpret):
    return _forward(xd, cr, bt, ct, chunk, interpret)


def _ssd_fwd(xd, cr, bt, ct, chunk, interpret):
    return _forward(xd, cr, bt, ct, chunk, interpret), (xd, cr, bt, ct)


def _ssd_bwd(chunk, interpret, res, dy):
    xd, cr, bt, ct = res
    batch, heads, p, n, n_chunks = _dims(xd, bt, chunk)
    states = _states(xd, cr, bt, chunk, interpret)
    s = _specs(p, chunk, n_chunks, True)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    dxd, dcum, dbt, dct = _call(
        functools.partial(_bwd_kernel, chunk=chunk, heads=heads),
        (batch, n_chunks, heads),
        [s["x"], s["y"], s["row"], s["nt"](n), s["nt"](n), s["state"](n)],
        [s["x"], s["row"], s["nt"](n), s["nt"](n)],
        [jax.ShapeDtypeStruct(xd.shape, xd.dtype), f32(*cr.shape),
         f32(*bt.shape), f32(*bt.shape)],
        [pltpu.VMEM((heads, p, n), jnp.float32),
         pltpu.VMEM((chunk, n), xd.dtype)]
        + [pltpu.VMEM((chunk, chunk), jnp.float32)] * 2, interpret,
    )(xd, dy.astype(xd.dtype), cr, bt, ct, states)
    return dxd, dcum, dbt, dct


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def _chunked(dt, a, chunk):
    """``Δ A`` (batch, T_p, heads), T padded to whole chunks with zeros."""
    t = dt.shape[1]
    la = dt.astype(jnp.float32) * a.astype(jnp.float32)
    t_p = round_up(t, chunk)
    return la if t_p == t else jnp.pad(la, ((0, 0), (0, t_p - t), (0, 0)))


def _chunk(t, chunk):
    return min(int(chunk or DEFAULT_CHUNK), round_up(t, 8))


def ssd(x, dt, a, b, c, d=None, *, chunk: Optional[int] = None,
        interpret: Optional[bool] = None):
    """The selective scan of the module docstring.  x: (batch, T, heads,
    P); dt: (batch, T, heads), at or above 0; a: (heads,), at or below 0;
    b, c: (batch, T, N), one group shared by every head; d: (heads,) or
    None.  ``chunk`` defaults to 256 (a sequence shorter than that is one
    chunk).  The result has x's shape and dtype; operands go to the
    policy's compute dtype.  Raises where the kernels compile for the TPU
    and the shape rule refuses P or the chunk."""
    batch, t, heads, p = x.shape
    n = b.shape[-1]
    if dt.shape != (batch, t, heads) or a.shape != (heads,) or \
            b.shape != (batch, t, n) or c.shape != b.shape:
        raise ValueError(f"ssd: x{x.shape} dt{dt.shape} a{a.shape} "
                         f"b{b.shape} c{c.shape}")
    chunk = _chunk(t, chunk)
    la = _chunked(dt, a, chunk)
    t_p = la.shape[1]
    if not default_interpret(interpret) and (
            p % 16 or (chunk % 128 and chunk != t_p)):
        raise ValueError(
            f"ssd: head dimension {p} and chunk {chunk} of {t_p} padded "
            "positions: the TPU kernels take P a multiple of 16 and a chunk "
            "a multiple of 128 or the whole padded sequence")
    cum = jnp.cumsum(la.transpose(0, 2, 1).reshape(batch, heads, -1, chunk),
                     axis=-1).reshape(batch, heads, 1, t_p)

    def lanes(v):
        """T last, padded to T_p: (batch, T, ...) → (batch, ..., T_p)."""
        v = jnp.moveaxis(v, 1, -1)
        return v if t_p == t else jnp.pad(
            v, [(0, 0)] * (v.ndim - 1) + [(0, t_p - t)])

    xd = cast_compute(lanes(x.astype(jnp.float32))
                      * lanes(dt.astype(jnp.float32))[:, :, None, :])
    bt, ct = (lanes(v.astype(jnp.float32)) for v in (b, c))
    y = _ssd(xd, cum, bt, ct, chunk, interpret)
    y = jnp.swapaxes(y[..., :t], 1, 2).reshape(batch, t, heads, p).astype(
        jnp.float32)
    if d is not None:
        y = y + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return y.astype(x.dtype)


def chunk_carry(dt, a, chunk: Optional[int] = None):
    """The mean over batch, heads and chunks of ``exp(Σ_{t ∈ chunk} Δ_t
    A_h)``: the share of the carried state that survives one chunk."""
    chunk = _chunk(dt.shape[1], chunk)
    la = _chunked(dt, a, chunk)
    batch, t_p, heads = la.shape
    return jnp.mean(jnp.exp(jnp.sum(
        la.reshape(batch, t_p // chunk, chunk, heads), axis=2)))
