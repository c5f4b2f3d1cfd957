"""The backward of a per-token mixture of streams, in one pass.

A hyper-connection (``nn/hyper_connection.py``) mixes residual streams with
per-token scalars: ``out[i] = sum_q C[i, q] * P[q]`` over ``p`` primal
streams ``P[q]`` of ``(tokens, d)`` and ``m x p`` coefficients ``C[i, q]``
of ``(tokens,)``.  The write-back is ``m = n`` outputs over the ``n``
streams and the sublayer's output (``p = n + 1``, ``C = [H_res | H_post]``);
the read-out is one output over the ``n`` streams (``C = H_pre``).  Given
the cotangent ``G`` of ``out``:

- ``dP[q] = sum_i C[i, q] * G[i]``          ``p`` slabs of ``(tokens, d)``;
- ``dC[i, q] = sum_d G[i] * P[q]``          ``m * p`` rows of ``(tokens,)``.

Autodiff writes each of the ``m * p`` products ``G[i] * P[q]`` to HBM as a
``(tokens, d)`` slab before reducing it.  :func:`mix_backward` is one Pallas
kernel over tiles of ``block_t`` tokens by ``block_d`` of ``d``: it loads the
tile of every ``G[i]`` and ``P[q]`` once, writes the tile of every
``dP[q]``, and adds the products, eight tokens by 128 lanes at a time, into
``m * p`` float32 accumulators that are reduced along the lanes when the
token tile's last chunk of ``d`` is done.  No ``(tokens, d)`` product
reaches HBM.  Everything is float32.

The coefficients are held ``(k, tokens)``, tokens along the lanes; a kernel
that scales rows of ``(tokens, d)`` wants them as columns.  The two small
transposes (``tokens x m*p`` values) are XLA's, outside the call.

Tiles come from :func:`mix_blocks`, a rule on the token count, ``d`` and the
number of slabs a grid step holds; a shape it refuses (``None``) is one the
caller differentiates plainly (:func:`can_mix`).
"""

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.common import default_interpret

_LANES, _SUBLANES = 128, 8
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# the double-buffered tiles of one grid step may take this much of it
_VMEM_BLOCK_BUDGET = 16 * 1024 * 1024
# widest first: a tile's rows are ``block_d`` contiguous values in HBM
BLOCK_D_CHOICES = (512, 384, 256, 128)
BLOCK_T_CHOICES = (512, 256, 128, 64, 32, 16, 8)


def mix_blocks(tokens: int, d: int, slabs: int,
               itemsize: int = 4) -> Optional[Dict[str, int]]:
    """The tile rule: ``block_d`` the widest of ``BLOCK_D_CHOICES`` that
    divides ``d``, ``block_t`` the longest of ``BLOCK_T_CHOICES`` that
    divides ``tokens`` and keeps ``slabs`` double-buffered tiles (every
    ``(tokens, d)`` operand and result of a grid step) within
    ``_VMEM_BLOCK_BUDGET``.  ``None`` where nothing fits: ``d`` not a
    multiple of 128 lanes, a token count not a multiple of 8 sublanes, or
    operands that are not 4 bytes wide (the kernel walks float32 tiles of
    (8, 128))."""
    if itemsize != 4:
        return None
    block_d = next((b for b in BLOCK_D_CHOICES if d % b == 0), None)
    if block_d is None:
        return None
    for block_t in BLOCK_T_CHOICES:
        if (tokens % block_t == 0 and 2 * slabs * block_t * block_d
                * itemsize <= _VMEM_BLOCK_BUDGET):
            return {"block_t": block_t, "block_d": block_d}
    return None


def can_mix(n: int, tokens: int, d: int) -> bool:
    """Whether the rule tiles both backward passes of a mixing of ``n``
    streams (the write-back holds the most slabs: ``g``, ``X``, ``dX``,
    ``y``, ``dy``)."""
    return mix_blocks(tokens, d, 3 * n + 2) is not None


def _mix_bwd_kernel(*refs, m, n, has_y, has_add, block_t, block_d):
    """One (token tile, chunk of ``d``) step.  ``refs``: the coefficient
    columns ``(block_t, m * p)``, ``G`` ``(m, block_t, block_d)``, ``X``
    ``(n, ...)``, [``y`` ``(block_t, block_d)``], [``add`` ``(n, ...)``];
    then ``dX`` ``(n, ...)``, [``dy``], the scalars' columns
    ``(block_t, m * p)``; then the accumulators ``(m * p, block_t, 128)``."""
    refs = list(refs)
    c_ref, g_ref, x_ref = refs[:3]
    del refs[:3]
    y_ref = refs.pop(0) if has_y else None
    add_ref = refs.pop(0) if has_add else None
    dx_ref = refs.pop(0)
    dy_ref = refs.pop(0) if has_y else None
    dc_ref, acc_ref = refs
    p = n + int(has_y)
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def rows(r, carry):
        rs = pl.ds(pl.multiple_of(r * _SUBLANES, _SUBLANES), _SUBLANES)
        cols = c_ref[rs, :]
        coef = [jnp.broadcast_to(cols[:, k:k + 1], (_SUBLANES, _LANES))
                for k in range(m * p)]
        acc = [acc_ref[k, rs, :] for k in range(m * p)]
        for c in range(block_d // _LANES):
            ls = slice(c * _LANES, (c + 1) * _LANES)
            g = [g_ref[i, rs, ls] for i in range(m)]
            prim = [x_ref[q, rs, ls] for q in range(n)]
            if has_y:
                prim.append(y_ref[rs, ls])
            for q in range(p):
                dq = coef[q] * g[0]
                for i in range(1, m):
                    dq = dq + coef[i * p + q] * g[i]
                if q == n:
                    dy_ref[rs, ls] = dq
                else:
                    dx_ref[q, rs, ls] = (dq + add_ref[q, rs, ls] if has_add
                                         else dq)
                for i in range(m):
                    acc[i * p + q] = acc[i * p + q] + g[i] * prim[q]
        for k in range(m * p):
            acc_ref[k, rs, :] = acc[k]
        return carry

    jax.lax.fori_loop(0, block_t // _SUBLANES, rows, 0)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        for k in range(m * p):
            dc_ref[:, k:k + 1] = jnp.sum(acc_ref[k], axis=-1, keepdims=True)


def mix_backward(coeffs, g, x, y=None, add=None, *,
                 block_t: Optional[int] = None, block_d: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """``coeffs`` (m, p, T), ``g`` (m, T, d), ``x`` (n, T, d), ``y`` (T, d)
    or None (``p = n + 1`` with it), all float32; ``add`` (n, T, d) or None:
    a cotangent of ``x`` that arrived another way and is added in the same
    pass.  Returns ``dx`` (n, T, d), ``dy`` (T, d) or None, ``dcoeffs``
    (m, p, T).  ``block_t`` / ``block_d`` ``None``: :func:`mix_blocks`'
    pick for the slabs this call holds (a shape it refuses raises)."""
    m, p, tokens = coeffs.shape
    n, _, d = x.shape
    if p != n + (y is not None) or g.shape != (m, tokens, d):
        raise ValueError(f"mix_backward: coefficients {coeffs.shape} on "
                         f"{n} streams{' and y' if y is not None else ''}, "
                         f"cotangent {g.shape}")
    if any(a.dtype != jnp.float32 for a in (coeffs, g, x)):
        raise ValueError("mix_backward: float32 operands only")
    slabs = m + 2 * n + 2 * (y is not None) + n * (add is not None)
    rule = mix_blocks(tokens, d, slabs) or {"block_t": 0, "block_d": 0}
    block_t, block_d = block_t or rule["block_t"], block_d or rule["block_d"]
    if not (block_t and block_d) or tokens % block_t or d % block_d \
            or block_t % _SUBLANES or block_d % _LANES:
        raise ValueError(f"mix_backward: ({tokens}, {d}) is not tiled by "
                         f"({block_t}, {block_d})")
    k = m * p
    slab = lambda lead: pl.BlockSpec((lead, block_t, block_d),
                                     lambda t, c: (0, t, c))
    row = pl.BlockSpec((block_t, block_d), lambda t, c: (t, c))
    cols = pl.BlockSpec((block_t, k), lambda t, c: (t, 0))
    operands = [coeffs.reshape(k, tokens).T, g, x]
    in_specs = [cols, slab(m), slab(n)]
    out_specs = [slab(n)]
    out_shape = [jax.ShapeDtypeStruct(x.shape, jnp.float32)]
    if y is not None:
        operands.append(y)
        in_specs.append(row)
        out_specs.append(row)
        out_shape.append(jax.ShapeDtypeStruct(y.shape, jnp.float32))
    if add is not None:
        operands.append(add)
        in_specs.append(slab(n))
    out_specs.append(cols)
    out_shape.append(jax.ShapeDtypeStruct((tokens, k), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_mix_bwd_kernel, m=m, n=n, has_y=y is not None,
                          has_add=add is not None, block_t=block_t,
                          block_d=block_d),
        grid=(tokens // block_t, d // block_d),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((k, block_t, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=4 * k * tokens * d, transcendentals=0,
            bytes_accessed=4 * tokens * (slabs * d + 2 * k)),
        interpret=default_interpret(interpret),
    )(*operands)
    dx, dc = outs[0], outs[-1]
    return (dx, outs[1] if y is not None else None,
            dc.T.reshape(m, p, tokens))
