"""Mamba-2's causal depthwise convolution with its bias and SiLU, one Pallas
pass forward and one backward, reading a channel window of a wider array in
place.

For a window of ``width`` channels starting at ``offset`` of ``u`` (B, T,
W), taps ``w`` (K, width) and a bias ``b`` (width,)::

    pre[t] = w[K−1] ⊙ u[t] + Σ_{j=1}^{K−1} w[K−1−j] ⊙ u[t−j] + b   (u[s<0] = 0)
    out[t] = SiLU(pre[t])

which is ``SiLU(nn.short_conv.causal_taps(u[..., window], w) + b)``, the
same four products summed in the same order, in float32.  The plain
expression shifts the window by each of the ``K − 1`` delays: XLA writes
every shifted copy to HBM, forward, in the rerun forward under a layer's
``jax.checkpoint``, and again for the backward's products.

The kernels work on the sequence along the lanes: ``u`` goes in as its
``(B, W, T)`` transpose, which is free where XLA holds ``u`` with T minor,
as it holds the float32 output of the Mamba-2 mixer's input projection
(8,512 channels, a multiple of 8 but not of 128; T a multiple of 128), and
the result comes back the same way, so nothing around the kernels changes
its layout.  The grid is (batch, channel blocks, T tiles); a step reads one
``(block_c, block_t)`` tile of the window straight out of the wide array
through its index map, and the 128 positions before it as a second block of
the same array (zeros at the sequence's start): the delays are lane shifts
of a VMEM scratch.  Nothing but the result is written.

The backward takes the same input, not the forward's result: it rebuilds
``pre`` for the tile and the 128 positions after it (from the tile, the
positions before and the positions after), forms ``g' = dout ⊙
SiLU'(pre)`` and writes

    du[t]       = Σ_{j=0}^{K−1} w[K−1−j] ⊙ g'[t+j]      (the anti-causal taps)

once; each tap's ``Σ_t g'[t] ⊙ u[t−j]`` and the bias's ``Σ_t g'[t]`` leave
as one column a tile, summed over the tiles outside (``(B, tiles, width,
K)`` and ``(B, tiles, width, 1)``: a few hundred KB).  Positions at or past
``T`` of a partial last tile (and after the last tile) are masked to 0.

Tiles come from :func:`conv_blocks`, a rule on ``T``, the window's offset
and width and ``K``; a shape it refuses (``None``) is one the caller
computes with the plain expression.  Off the TPU the kernels run in
interpret mode.
"""

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.common import cdiv, default_interpret

# positions of history a tile reads before it (and, backward, after it),
# one block of 128 lanes: K − 1 of them are used
HALO = 128
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# widest first; a block must divide the window's offset and its width
BLOCK_C_CHOICES = (256, 128, 64, 32, 16, 8)
BLOCK_T = 1024


def conv_blocks(length: int, offset: int, width: int,
                kernel: int) -> Optional[Dict[str, int]]:
    """The tile rule: ``block_c`` the widest of ``BLOCK_C_CHOICES`` that
    divides both ``offset`` and ``width``, ``block_t`` ``BLOCK_T`` or the
    whole sequence if shorter.  ``None`` where the kernels do not apply:
    ``T`` not a multiple of 128 lanes, a window not on 8-sublane blocks, or
    more than ``HALO`` positions of history (``K > 129``)."""
    if length <= 0 or length % HALO or not 1 <= kernel <= HALO + 1:
        return None
    block_c = next((b for b in BLOCK_C_CHOICES
                    if offset % b == 0 and width % b == 0), None)
    if block_c is None:
        return None
    return {"block_t": min(BLOCK_T, length), "block_c": block_c}


def _silu(pre):
    return pre / (1.0 + jnp.exp(-pre))


def _pre(ext_ref, w, b, start, n, kernel):
    """``pre`` at ``n`` positions whose own input sits at lane ``start`` of
    the scratch ``ext_ref`` (their history before it), summed as
    ``causal_taps`` sums.  ``w`` (block_c, K), ``b`` (block_c, 1)."""
    acc = w[:, kernel - 1:kernel] * ext_ref[:, start:start + n]
    for back in range(1, kernel):
        acc = acc + w[:, kernel - 1 - back:kernel - back] * ext_ref[
            :, start - back:start - back + n]
    return acc + b


def _fwd_kernel(u_ref, prev_ref, w_ref, b_ref, o_ref, ext_ref, *, kernel,
                block_t):
    first = pl.program_id(2) == 0
    ext_ref[:, :HALO] = jnp.where(first, 0.0, prev_ref[0])
    ext_ref[:, HALO:] = u_ref[0]
    o_ref[0] = _silu(_pre(ext_ref, w_ref[...], b_ref[...], HALO, block_t,
                          kernel))


def _bwd_kernel(u_ref, prev_ref, next_ref, g_ref, gnext_ref, w_ref, b_ref,
                du_ref, dw_ref, db_ref, ext_ref, gp_ref, *, kernel, block_t,
                length):
    """Scratch ``ext_ref`` (block_c, HALO + block_t + HALO): the positions
    before, the tile, the positions after; ``gp_ref`` (block_c, block_t +
    HALO): ``g'`` of the tile and the positions after."""
    i = pl.program_id(2)
    t0 = i * block_t

    def live(v, at):
        pos = t0 + at + jax.lax.broadcasted_iota(jnp.int32, (1, v.shape[1]),
                                                 1)
        return jnp.where(pos < length, v, 0.0)

    ext_ref[:, :HALO] = jnp.where(i == 0, 0.0, prev_ref[0])
    ext_ref[:, HALO:HALO + block_t] = live(u_ref[0], 0)
    ext_ref[:, HALO + block_t:] = live(next_ref[0], block_t)
    w = w_ref[...]
    pre = _pre(ext_ref, w, b_ref[...], HALO, block_t + HALO, kernel)
    sig = 1.0 / (1.0 + jnp.exp(-pre))
    gp_ref[:, :block_t] = live(g_ref[0], 0)
    gp_ref[:, block_t:] = live(gnext_ref[0], block_t)
    gp_ref[...] = gp_ref[...] * (sig * (1.0 + pre * (1.0 - sig)))
    du = w[:, kernel - 1:kernel] * gp_ref[:, :block_t]
    for ahead in range(1, kernel):
        du = du + w[:, kernel - 1 - ahead:kernel - ahead] * gp_ref[
            :, ahead:ahead + block_t]
    du_ref[0] = du
    gp = gp_ref[:, :block_t]
    for k in range(kernel):
        back = kernel - 1 - k
        dw_ref[0, 0, :, k:k + 1] = jnp.sum(
            gp * ext_ref[:, HALO - back:HALO - back + block_t], axis=1,
            keepdims=True)
    db_ref[0, 0] = jnp.sum(gp, axis=1, keepdims=True)


def _specs(length, offset, block_t, block_c):
    """BlockSpecs by kind for grid (batch, channel block, T tile) over
    (B, channels, T) arrays: a tile of the window, the 128 positions
    before a tile, the 128 after it (clamped at the end: masked there),
    and the same over an array of the window alone."""
    c0 = offset // block_c
    last = cdiv(length, HALO) - 1
    per = block_t // HALO
    tile = lambda base: pl.BlockSpec(
        (1, block_c, block_t), lambda b, c, i: (b, base + c, i))
    before = lambda base: pl.BlockSpec(
        (1, block_c, HALO),
        lambda b, c, i: (b, base + c, jnp.maximum(i * per - 1, 0)))
    after = lambda base: pl.BlockSpec(
        (1, block_c, HALO),
        lambda b, c, i: (b, base + c, jnp.minimum((i + 1) * per, last)))
    return {"tile": tile(c0), "before": before(c0), "after": after(c0),
            "own_tile": tile(0), "own_after": after(0)}


def _params_specs(kernel, block_c):
    return [pl.BlockSpec((block_c, kernel), lambda b, c, i: (c, 0)),
            pl.BlockSpec((block_c, 1), lambda b, c, i: (c, 0))]


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _forward(ut, w, b, offset, block_t, block_c, interpret):
    """``ut`` (B, W, T); returns (B, width, T)."""
    batch, _, length = ut.shape
    kernel, width = w.shape
    s = _specs(length, offset, block_t, block_c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, kernel=kernel, block_t=block_t),
        grid=(batch, width // block_c, cdiv(length, block_t)),
        in_specs=[s["tile"], s["before"]] + _params_specs(kernel, block_c),
        out_specs=s["own_tile"],
        out_shape=jax.ShapeDtypeStruct((batch, width, length), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_c, HALO + block_t), jnp.float32)],
        compiler_params=_compiler_params(),
        cost_estimate=pl.CostEstimate(
            flops=(2 * kernel + 4) * batch * length * width,
            transcendentals=batch * length * width,
            bytes_accessed=8 * batch * length * width),
        interpret=default_interpret(interpret),
    )(ut, ut, w.T, b.reshape(width, 1))


def _backward(ut, w, b, gt, offset, block_t, block_c, interpret):
    """``ut`` (B, W, T), ``gt`` (B, width, T); returns ``du`` (B, width,
    T), ``dw`` (K, width), ``db`` (width,)."""
    batch, _, length = ut.shape
    kernel, width = w.shape
    s = _specs(length, offset, block_t, block_c)
    tiles = cdiv(length, block_t)
    per_tile = lambda cols: pl.BlockSpec((1, 1, block_c, cols),
                                         lambda b_, c, i: (b_, i, c, 0))
    du, dw, db = pl.pallas_call(
        functools.partial(_bwd_kernel, kernel=kernel, block_t=block_t,
                          length=length),
        grid=(batch, width // block_c, tiles),
        in_specs=[s["tile"], s["before"], s["after"], s["own_tile"],
                  s["own_after"]] + _params_specs(kernel, block_c),
        out_specs=[s["own_tile"], per_tile(kernel), per_tile(1)],
        out_shape=[jax.ShapeDtypeStruct((batch, width, length), jnp.float32),
                   jax.ShapeDtypeStruct((batch, tiles, width, kernel),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((batch, tiles, width, 1),
                                        jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_c, 2 * HALO + block_t), jnp.float32),
            pltpu.VMEM((block_c, HALO + block_t), jnp.float32)],
        compiler_params=_compiler_params(),
        cost_estimate=pl.CostEstimate(
            flops=(6 * kernel + 10) * batch * length * width,
            transcendentals=batch * length * width,
            bytes_accessed=12 * batch * length * width),
        interpret=default_interpret(interpret),
    )(ut, ut, ut, gt, gt, w.T, b.reshape(width, 1))
    return du, jnp.sum(dw, axis=(0, 1)).T, jnp.sum(db, axis=(0, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _conv(u, w, b, offset, block_t, block_c, interpret):
    return jnp.swapaxes(_forward(jnp.swapaxes(u, 1, 2), w, b, offset,
                                 block_t, block_c, interpret), 1, 2)


def _conv_fwd(u, w, b, offset, block_t, block_c, interpret):
    return _conv(u, w, b, offset, block_t, block_c, interpret), (u, w, b)


def _conv_bwd(offset, block_t, block_c, interpret, res, g):
    u, w, b = res
    du, dw, db = _backward(jnp.swapaxes(u, 1, 2), w, b, jnp.swapaxes(g, 1, 2),
                           offset, block_t, block_c, interpret)
    wide, width = u.shape[-1], w.shape[1]
    du = jnp.pad(jnp.swapaxes(du, 1, 2),
                 ((0, 0), (0, 0), (offset, wide - offset - width)))
    return du, dw, db


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv(u, w, b, *, offset: int = 0,
                interpret: Optional[bool] = None):
    """``SiLU(causal_taps(u[..., offset:offset + width], w) + b)`` of the
    module docstring.  ``u`` (B, T, W) float32, the window read in place;
    ``w`` (K, width) and ``b`` (width,) go to float32.  Returns (B, T,
    width) float32.  Raises where :func:`conv_blocks` refuses the shape."""
    batch, length, wide = u.shape
    kernel, width = w.shape
    if u.dtype != jnp.float32 or b.shape != (width,) or \
            offset + width > wide:
        raise ValueError(f"causal_conv: u {u.shape} {u.dtype}, taps "
                         f"{w.shape}, bias {b.shape}, offset {offset}")
    rule = conv_blocks(length, offset, width, kernel)
    if rule is None:
        raise ValueError(f"causal_conv: T {length}, channels [{offset}, "
                         f"{offset + width}), {kernel} taps are not tiled")
    return _conv(u, w.astype(jnp.float32), b.astype(jnp.float32), offset,
                 rule["block_t"], rule["block_c"], interpret)
