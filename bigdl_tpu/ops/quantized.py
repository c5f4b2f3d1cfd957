"""Int8 quantized matmul + calibration — Pallas TPU kernels.

Reference analog: BigDL's post-training int8 inference path —
``nn/quantized/{Quantizer,Linear,SpatialConvolution}.scala`` backed by the
``bigdl-core`` native int8 gemm with abs-max calibration (SURVEY.md
§3.1/§3.2).  TPU-native redesign: symmetric per-channel weight
quantization + dynamic per-row activation quantization feeding an
int8×int8→int32 MXU matmul kernel, rescaled to float on the way out.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.ops.common import default_interpret, round_up


def abs_max_scales(x, axis) -> jnp.ndarray:
    """Symmetric abs-max calibration: scale s.t. x/scale fits int8."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=False)
    return jnp.maximum(amax, 1e-8) / 127.0


def quantize_int8(w, axis: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-output-channel symmetric int8 quantization of a weight.

    ``axis`` is the reduction axis (the one contracted in the matmul); for a
    (in, out) Linear weight use axis=0 → per-out-channel scales (out,)."""
    scales = abs_max_scales(w, axis=axis)
    q = jnp.clip(jnp.round(w / jnp.expand_dims(scales, axis)), -127, 127)
    return q.astype(jnp.int8), scales.astype(jnp.float32)


def dequantize_int8(q, scales, axis: int = 0) -> jnp.ndarray:
    return q.astype(jnp.float32) * jnp.expand_dims(scales, axis)


def quantize_blockwise(x, block: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise symmetric int8 quantization along the LAST dimension.

    ``x`` is ``(..., L)`` with ``L % block == 0``; every length-``block``
    run gets its own abs-max scale, so one outlier only costs its own
    block's mantissa (the EQuARX-style gradient-compression granularity —
    PAPERS.md arXiv 2506.17615).  Returns ``(q int8 (..., L), scales f32
    (..., L // block))``.  Pure jnp — safe inside jit/shard_map."""
    lead, L = x.shape[:-1], x.shape[-1]
    if L % block != 0:
        raise ValueError(f"last dim {L} not a multiple of block {block}")
    xb = x.reshape(*lead, L // block, block)
    scales = abs_max_scales(xb, axis=-1)
    q = jnp.clip(jnp.round(xb / scales[..., None]), -127, 127)
    return q.astype(jnp.int8).reshape(*lead, L), scales.astype(jnp.float32)


def dequantize_blockwise(q, scales) -> jnp.ndarray:
    """Inverse of :func:`quantize_blockwise`: ``q (..., L)`` int8 +
    ``scales (..., L // block)`` → f32 ``(..., L)``.  The block size is
    implied by the shapes."""
    lead, L = q.shape[:-1], q.shape[-1]
    nb = scales.shape[-1]
    block = L // nb
    xb = q.astype(jnp.float32).reshape(*lead, nb, block)
    return (xb * scales[..., None]).reshape(*lead, L)


def quantize_pages(pages, floor_scales=None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-page symmetric int8 quantization of KV page images.

    ``pages`` is ``(..., heads, page_size, head_dim)`` float32 — every
    trailing-3-dim page image gets ONE abs-max scale (the page-table
    granularity of docs/quantization.md §Serving memory hierarchy), so
    the scales ride the page table as a flat ``(..., )`` float32 array.
    Without ``floor_scales`` this is :func:`quantize_blockwise` with one
    block per page.

    ``floor_scales`` (shape = the returned scales) makes the scale
    MONOTONE within a page's occupancy: ``new = max(floor, amax/127)``.
    A page whose contents did not change since the last quantization
    requantizes EXACTLY under a monotone scale (``round(q·s / s) == q``),
    which is what makes the decode engine's whole-row write-back safe.
    A floor of 0.0 marks a freshly allocated page: until something is
    written, dequantize yields zeros regardless of the stale int8
    payload left by the page's previous owner."""
    lead, elems = pages.shape[:-3], int(
        pages.shape[-3] * pages.shape[-2] * pages.shape[-1])
    flat = pages.reshape(*lead, elems)
    if floor_scales is None:
        q, scales = quantize_blockwise(flat, elems)
        return q.reshape(pages.shape), scales[..., 0]
    amax = jnp.max(jnp.abs(flat), axis=-1)
    scales = jnp.maximum(amax / 127.0,
                         jnp.asarray(floor_scales, jnp.float32))
    safe = jnp.maximum(scales, 1e-12)[..., None]
    q = jnp.clip(jnp.round(flat / safe), -127, 127).astype(jnp.int8)
    return q.reshape(pages.shape), scales.astype(jnp.float32)


def dequantize_pages(q, scales) -> jnp.ndarray:
    """Inverse of :func:`quantize_pages`: int8 pages ``(..., h, p, hd)``
    + per-page scales ``(...,)`` → float32 pages."""
    return q.astype(jnp.float32) * scales[..., None, None, None]


def _int8_mm_kernel(x_ref, w_ref, o_ref):
    # x: (bm, bk) int8, w: (bk, bn) int8 → o: (bm, bn) int32; the K grid
    # dimension is innermost (sequential on-core), so the output block stays
    # resident and accumulates across K tiles.
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        o_ref[:] = jnp.zeros_like(o_ref)

    # precision pinned to DEFAULT: a global jax_default_matmul_precision of
    # "highest" would stamp an fp32 contract precision onto this integer
    # matmul, which Mosaic rejects ("Bad lhs type").
    o_ref[:] += jax.lax.dot_general(
        x_ref[:], w_ref[:], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
        precision=jax.lax.Precision.DEFAULT)


# (block_m, block_n, block_k) where a call leaves an axis as None
TILES = (256, 256, 512)


def int8_matmul(x_q, w_q, *, block_m: Optional[int] = None,
                block_n: Optional[int] = None,
                block_k: Optional[int] = None,
                interpret: Optional[bool] = None):
    """int8 (M,K) × int8 (K,N) → int32 (M,N) on the MXU, tiled on all
    three dimensions (one (bm,bk) + (bk,bn) tile pair in VMEM per step).

    ``block_*=None`` takes that axis's tile from :data:`TILES`; explicit
    kwargs always win."""
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2, (x_q.shape, w_q.shape)
    block_m, block_n, block_k = (
        tile if b is None else int(b)
        for b, tile in zip((block_m, block_n, block_k), TILES))
    bm = min(block_m, round_up(m, 32))
    bn = min(block_n, round_up(n, 128))
    bk = min(block_k, round_up(k, 128))
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)
    xp = jnp.pad(x_q, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))

    out = pl.pallas_call(
        _int8_mm_kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=default_interpret(interpret),
    )(xp, wp)
    return out[:m, :n]


def quantized_linear(x, w_q, w_scales, bias=None, act_scale=None,
                     interpret: Optional[bool] = None):
    """Dense layer with a pre-quantized (in, out) int8 weight.

    Activation quantization is **dynamic** per-row abs-max
    (``act_scale=None``), **static per-tensor** with a calibrated scalar
    scale (the reference's min/max-calibration path, SURVEY.md §3.2 —
    values beyond ±127·scale saturate), or **static per-channel** with a
    calibrated (K,) scale vector.  In the per-channel case the caller must
    have FOLDED the activation scales into the weight before quantizing it
    (``w'[k,n] = w[k,n]·s[k]``): then ``x_q·w'_q ≈ Σₖ (x/s)·(w·s)/sw`` and
    the output rescale is the weight scale alone.  The matmul always runs
    int8×int8→int32."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    per_channel_act = (act_scale is not None
                      and jnp.ndim(act_scale) == 1)
    if act_scale is None:
        sx = abs_max_scales(x2, axis=1)[:, None]  # (M, 1) dynamic
    elif per_channel_act:
        sx = jnp.asarray(act_scale, jnp.float32)[None, :]   # (1, K)
    else:
        sx = jnp.asarray(act_scale, jnp.float32)  # scalar, calibrated
    x_q = jnp.clip(jnp.round(x2 / sx), -127, 127).astype(jnp.int8)
    acc = int8_matmul(x_q, w_q, interpret=interpret)
    if per_channel_act:   # act scales already folded into w_q's rows
        y = acc.astype(jnp.float32) * w_scales[None, :]
    else:
        y = acc.astype(jnp.float32) * sx * w_scales[None, :]
    if bias is not None:
        y = y + bias
    return y.reshape(*lead, w_q.shape[1]).astype(x.dtype)
