"""Mamba-2's state-space duality (SSD, arXiv:2405.21060): a selective scan
with a decay a token and a head — Pallas TPU kernels, forward and backward.

For each head ``h``, with ``x_t`` (P,), ``Δ_t ≥ 0``, ``A_h ≤ 0`` and
``B_t``, ``C_t`` (N,) shared by every head (one group)::

    S_t = exp(Δ_t A_h) S_{t−1} + B_t (Δ_t x_t)ᵀ        S is (N, P), S_{−1} = 0
    y_t = S_tᵀ C_t + D_h x_t

which is causal linear attention whose decay is ``exp(cum_t − cum_s)``
between positions ``s ≤ t``, ``cum`` the running sum of ``Δ A``.  The
kernel walks the sequence in chunks of ``Q`` positions and carries every
head's (N, P) float32 state in VMEM from one chunk to the next.  Inside a
chunk, with ``cum`` restarted at the chunk's start (``c_i``, inclusive)::

    y  = ((C Bᵀ) ⊙ L) (Δ⊙x) + exp(c) ⊙ (C S)
    S' = exp(c_last) S + (B ⊙ exp(c_last − c))ᵀ (Δ⊙x)

with ``L[i, j] = exp(c_i − c_j)`` for ``j ≤ i`` and 0 above the diagonal.

Every decay is ``exp`` of a number at or below zero: a head that forgets
fast underflows to 0 and nothing overflows.  The grid is (batch, chunks,
heads), heads innermost: ``C Bᵀ`` is one product a chunk for all heads,
and B and C are fetched once a chunk.  ``D x`` is added outside the
kernels, in ``jax.numpy``.

The backward pass is two kernels.  The first walks forward in time and
writes the state each chunk starts from (float32, ``(N, P)`` a chunk and a
head).  The second walks backward in time and carries each head's state
cotangent ``dS`` in VMEM::

    d(Δ⊙x) = ((B Cᵀ) ⊙ Lᵀ) dy + (B ⊙ w) dS          w = exp(c_last − c)
    dG     = (dy (Δ⊙x)ᵀ) ⊙ L                         summed over heads
    dC     = Σ_h dG B + exp(c) ⊙ (dy Sᵀ)
    dB     = Σ_h dGᵀ C + w ⊙ ((Δ⊙x) dSᵀ)
    dc     = rows(dG ⊙ G) − columns(dG ⊙ G) + exp(c) ⊙ rows(C ⊙ dy Sᵀ)
             − w ⊙ r + [at c_last] (Σ w ⊙ r + exp(c_last) Σ dS ⊙ S)
    dS_in  = exp(c_last) dS + Cᵀ (exp(c) ⊙ dy)

with ``r = rows(B ⊙ (Δ⊙x) dSᵀ)``.  ``c`` goes in as a row, one (1, Q)
block a chunk and head; its column form is the diagonal's lane sums of a
(Q, Q) mask, exact, as the cotangent's row form is (a (Q, 1) operand in
HBM would take a 128-lane tile a position).  ``Δ``, ``A`` and ``x`` reach
the kernels only through ``Δ⊙x`` and ``c``, so autodiff of those two
expressions gives ``dx``, ``dΔ`` and ``dA``; ``dD`` is autodiff's.
Operands in the policy's compute dtype
(``tensor.policy.cast_compute``) with float32 accumulation; ``Δ``, the
decays, the states and their cotangents float32.  Off the TPU the same
kernels run in interpret mode.  T is padded at the end to a whole chunk:
``Δ`` = 0 there, which neither writes the state nor decays it.
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


def _iota(chunk, axis):
    return jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), axis)


def _decay(col, row, chunk, lower):
    """``exp(col − row)`` where the row index is at or past the column
    index (``lower``; else at or before it), 0 elsewhere.  ``col`` (Q, 1),
    ``row`` (1, Q)."""
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


def _fwd_kernel(xd_ref, cr_ref, bt_ref, c_ref, y_ref, s_scr, g_scr, *,
                chunk):
    k, h = pl.program_id(1), pl.program_id(2)

    @pl.when(h == 0)
    def _products():
        g_scr[...] = _mxu(c_ref[0], bt_ref[0])

    @pl.when(k == 0)
    def _init():
        s_scr[h] = jnp.zeros(s_scr.shape[1:], s_scr.dtype)

    xd, cr, c = xd_ref[0], cr_ref[0], c_ref[0]
    cc, last = _column(cr, chunk), _last(cr, chunk)
    state = s_scr[h]
    m = g_scr[...] * _decay(cc, cr, chunk, True)
    y = _mxu(m.astype(xd.dtype), xd) + jnp.exp(cc) * _mxu(
        c, state.astype(xd.dtype))
    y_ref[0] = y.astype(y_ref.dtype)
    s_scr[h] = jnp.exp(last) * state + _mxu(
        (bt_ref[0] * jnp.exp(last - cr)).astype(xd.dtype), xd)


def _states_kernel(xd_ref, cr_ref, bt_ref, st_ref, s_scr, *, chunk):
    k, h = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        s_scr[h] = jnp.zeros(s_scr.shape[1:], s_scr.dtype)

    xd, cr = xd_ref[0], cr_ref[0]
    last = _last(cr, chunk)
    state = s_scr[h]
    st_ref[0, 0] = state
    s_scr[h] = jnp.exp(last) * state + _mxu(
        (bt_ref[0] * jnp.exp(last - cr)).astype(xd.dtype), xd)


def _bwd_kernel(xd_ref, dy_ref, cr_ref, b_ref, bt_ref, c_ref, ct_ref, st_ref,
                dxd_ref, dcum_ref, db_ref, dbt_ref, dc_ref, ds_scr, g_scr,
                gt_scr, dg_scr, *, chunk, heads):
    k, h = pl.program_id(1), pl.program_id(2)
    b, c, ct = b_ref[0], c_ref[0], ct_ref[0]

    @pl.when(h == 0)
    def _products():
        g_scr[...] = _mxu(c, bt_ref[0])
        gt_scr[...] = _mxu(b, ct)
        dg_scr[...] = jnp.zeros_like(dg_scr)
        db_ref[0] = jnp.zeros(db_ref.shape[1:], db_ref.dtype)
        dc_ref[0] = jnp.zeros(dc_ref.shape[1:], dc_ref.dtype)

    @pl.when(k == 0)
    def _init():
        ds_scr[h] = jnp.zeros(ds_scr.shape[1:], ds_scr.dtype)

    xd, dy, cr = xd_ref[0], dy_ref[0], cr_ref[0]
    cc, last = _column(cr, chunk), _last(cr, chunk)
    e, w = jnp.exp(cc), jnp.exp(last - cc)
    state, dstate = st_ref[0, 0], ds_scr[h]
    mt = gt_scr[...] * _decay(cr, cc, chunk, False)
    dxd = _mxu(mt.astype(dy.dtype), dy) + w * _mxu(b, dstate.astype(b.dtype))
    dxd_ref[0] = dxd.astype(dxd_ref.dtype)
    dg = _mxu(dy, xd, _NT) * _decay(cc, cr, chunk, True)
    dg_scr[...] += dg
    z = dg * g_scr[...]
    dyh = _mxu(dy, state.astype(dy.dtype), _NT)
    xdh = _mxu(xd, dstate.astype(xd.dtype), _NT)
    dc_ref[0] += e * dyh
    db_ref[0] += w * xdh
    de = e * jnp.sum(c.astype(jnp.float32) * dyh, axis=1, keepdims=True)
    dw = w * jnp.sum(b.astype(jnp.float32) * xdh, axis=1, keepdims=True)
    d_last = (jnp.sum(dw, axis=0, keepdims=True)
              + jnp.exp(last) * jnp.sum(dstate * state, keepdims=True))
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    dcc = (jnp.sum(z, axis=1, keepdims=True) + de - dw
           + jnp.where(row == chunk - 1, d_last, 0.0))
    dcum_ref[0] = _row(dcc, chunk) - jnp.sum(z, axis=0, keepdims=True)
    ds_scr[h] = jnp.exp(last) * dstate + _mxu(ct, (e * dy).astype(ct.dtype))

    @pl.when(h == heads - 1)
    def _shared():
        dgs = dg_scr[...].astype(b.dtype)
        dc_ref[0] += _mxu(dgs, b)
        dbt_ref[0] = _mxu(ct, dgs)


def _specs(heads, chunk, n_chunks, reverse):
    """BlockSpecs by kind for grid (batch, chunk step, head)."""
    ci = (lambda k: n_chunks - 1 - k) if reverse else (lambda k: k)
    row = lambda b, h: b * heads + h

    def per_head(width):
        return pl.BlockSpec((1, chunk, width),
                            lambda b, k, h: (row(b, h), ci(k), 0))

    return {
        "x": per_head,
        "row": pl.BlockSpec((1, 1, chunk),
                            lambda b, k, h: (row(b, h), 0, ci(k))),
        "tn": lambda n: pl.BlockSpec((1, chunk, n),
                                     lambda b, k, h: (b, ci(k), 0)),
        "nt": lambda n: pl.BlockSpec((1, n, chunk),
                                     lambda b, k, h: (b, 0, ci(k))),
        "state": lambda n, p: pl.BlockSpec(
            (1, 1, n, p), lambda b, k, h: (row(b, h), ci(k), 0, 0)),
    }


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=default_interpret(interpret))


def _forward(xd, cr, bt, c, chunk, interpret):
    bh, t, p = xd.shape
    batch, n = c.shape[0], c.shape[2]
    heads, n_chunks = bh // batch, t // chunk
    s = _specs(heads, chunk, n_chunks, False)
    return _call(functools.partial(_fwd_kernel, chunk=chunk),
                 (batch, n_chunks, heads),
                 [s["x"](p), s["row"], s["nt"](n), s["tn"](n)],
                 s["x"](p), jax.ShapeDtypeStruct(xd.shape, xd.dtype),
                 [pltpu.VMEM((heads, n, p), jnp.float32),
                  pltpu.VMEM((chunk, chunk), jnp.float32)], interpret,
                 )(xd, cr, bt, c)


def _states(xd, cr, bt, chunk, interpret):
    """The state each chunk starts from: (batch · heads, chunks, N, P)."""
    bh, t, p = xd.shape
    batch, n = bt.shape[0], bt.shape[1]
    heads, n_chunks = bh // batch, t // chunk
    s = _specs(heads, chunk, n_chunks, False)
    return _call(functools.partial(_states_kernel, chunk=chunk),
                 (batch, n_chunks, heads), [s["x"](p), s["row"], s["nt"](n)],
                 s["state"](n, p),
                 jax.ShapeDtypeStruct((bh, n_chunks, n, p), jnp.float32),
                 [pltpu.VMEM((heads, n, p), jnp.float32)], interpret,
                 )(xd, cr, bt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd(xd, cr, bt, c, chunk, interpret):
    return _forward(xd, cr, bt, c, chunk, interpret)


def _ssd_fwd(xd, cr, bt, c, chunk, interpret):
    return _forward(xd, cr, bt, c, chunk, interpret), (xd, cr, bt, c)


def _ssd_bwd(chunk, interpret, res, dy):
    xd, cr, bt, c = res
    bh, t, p = xd.shape
    batch, n = c.shape[0], c.shape[2]
    heads, n_chunks = bh // batch, t // chunk
    states = _states(xd, cr, bt, chunk, interpret)
    s = _specs(heads, chunk, n_chunks, True)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    dxd, dcum, db, dbt, dc = _call(
        functools.partial(_bwd_kernel, chunk=chunk, heads=heads),
        (batch, n_chunks, heads),
        [s["x"](p), s["x"](p), s["row"], s["tn"](n), s["nt"](n), s["tn"](n),
         s["nt"](n), s["state"](n, p)],
        [s["x"](p), s["row"], s["tn"](n), s["nt"](n), s["tn"](n)],
        [jax.ShapeDtypeStruct(xd.shape, xd.dtype), f32(bh, 1, t),
         f32(batch, t, n), f32(batch, n, t), f32(batch, t, n)],
        [pltpu.VMEM((heads, n, p), jnp.float32)]
        + [pltpu.VMEM((chunk, chunk), jnp.float32)] * 3, interpret,
    )(xd, dy.astype(xd.dtype), cr, jnp.swapaxes(bt, 1, 2), bt, c,
      jnp.swapaxes(c, 1, 2), states)
    return (dxd, dcum, (dbt + jnp.swapaxes(db, 1, 2)).astype(bt.dtype),
            dc.astype(c.dtype))


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
    policy's compute dtype."""
    batch, t, heads, p = x.shape
    n = b.shape[-1]
    if dt.shape != (batch, t, heads) or a.shape != (heads,) or \
            b.shape != (batch, t, n) or c.shape != b.shape:
        raise ValueError(f"ssd: x{x.shape} dt{dt.shape} a{a.shape} "
                         f"b{b.shape} c{c.shape}")
    chunk = _chunk(t, chunk)
    la = _chunked(dt, a, chunk)
    t_p = la.shape[1]
    cum = jnp.cumsum(la.transpose(0, 2, 1).reshape(batch, heads, -1, chunk),
                     axis=-1).reshape(batch * heads, t_p)
    pad = lambda v: v if t_p == t else jnp.pad(
        v, [(0, 0), (0, t_p - t)] + [(0, 0)] * (v.ndim - 2))
    xd = cast_compute(pad(x.astype(jnp.float32)
                          * dt.astype(jnp.float32)[..., None]))
    xd = xd.transpose(0, 2, 1, 3).reshape(batch * heads, t_p, p)
    bt, cc = cast_compute(jnp.swapaxes(pad(b), 1, 2), pad(c))
    y = _ssd(xd, cum[:, None, :], bt, cc, chunk, interpret)
    y = y.reshape(batch, heads, t_p, p)[:, :, :t].transpose(
        0, 2, 1, 3).astype(jnp.float32)
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
