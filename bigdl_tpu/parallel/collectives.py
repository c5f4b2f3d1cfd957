"""Wire-efficient gradient collectives — blockwise-int8 + bucketed sync.

The ZeRO-1 shard cycle (``optim/train_step.py``) moves the FULL flat
gradient through ``psum_scatter`` and the updated params back through
``all_gather`` every step: for DP ResNet-50 on a dcn_data=2 x data=4 mesh
the ledger counts ~204 MB ICI + 51 MB DCN per step, full-precision bytes
on every hop.
This module is the bandwidth layer under that cycle:

- **Blockwise int8 reduce-scatter** (EQuARX recipe, PAPERS.md arXiv
  2506.17615): each rank quantizes its flat-gradient chunk per
  ``block``-length run (symmetric abs-max, ``ops.quantized``
  primitives), exchanges int8 payloads + f32 per-block scales with ONE
  ``all_to_all``, and sums the dequantized chunks in a widened f32
  accumulator.  The wire carries 1 byte/element + 4/block scale bytes
  (~4x less than f32); int8 values are never summed in int8, so the
  reduction cannot overflow, and per-SOURCE scales keep every replica's
  own mantissa (a shared scale would round the small replicas toward
  the largest one).
- **Quantized hierarchical psum** for the cross-slice (DCN) hop:
  all_to_all-scatter the quantized slice over the ``dcn_data`` axis,
  sum dequantized, re-quantize the summed sub-chunk, all_gather it
  back.  Every rank gathers the SAME int8 payload, so the dequantized
  result is bit-identical across slices — the invariant the ZeRO cycle
  relies on (each slice computes the identical update; parameters
  never cross DCN).
- **Bucketing** (``bucket_columns``): split the shard width into
  contiguous column buckets so the step issues one collective per
  bucket instead of one monolithic transfer — bucket *k*'s optimizer
  update and param all_gather depend only on bucket *k*'s
  reduce-scatter, which is the dependence structure XLA's
  latency-hiding scheduler needs to overlap communication with the
  neighbouring buckets' compute (the DDP gradient-bucket discipline).
  Column bucketing keeps shard OWNERSHIP monolithic: bucket ``[c0,c1)``
  of the ``(ndev, shard_size)`` gradient view scatters to exactly the
  monolithic slice's ``[c0,c1)`` range, so optimizer state layout —
  and therefore every existing checkpoint — is identical for any
  bucket size.

Byte estimators at the bottom are THE source of truth for the
collective-bytes ledger (``obs/cost.collective_ledger`` /
``train.collective_{ici,dcn}_bytes_per_step``): they count the actual
wire dtype including quantization scales and block padding, so
before/after comparisons are honest.  Convention matches the original
ledger: one reduce-scatter or all_gather of an n-elem vector counts
the full vector's bytes (a ring moves (n-1)/n ≈ 1x).
"""

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.ops.common import round_up as _round_up
from bigdl_tpu.ops.quantized import dequantize_blockwise, quantize_blockwise

# gradient-sync wire formats for the ZeRO-1 cycle (train_step.grad_comm)
GRAD_COMM_MODES = ("fp32", "bf16", "int8")

# updated-param all_gather wire formats (train_step.param_comm): fp32 is
# the original full-precision gather; int8 gathers the blockwise-
# quantized UPDATE DELTA and reconstructs against the replicated base
# params — no bf16 mode (a bf16 param wire would round the master
# params themselves; the delta trick only works because the base is
# already replicated bit-identically)
PARAM_COMM_MODES = ("fp32", "int8")

# default quantization block: 1024 elements per scale keeps the scale
# overhead at 4/1024 ≈ 0.4% of the payload while isolating outliers to
# ~4 KB runs of the flat gradient
DEFAULT_QUANT_BLOCK = 1024

_SCALE_BYTES = 4  # f32 per-block scales


def wire_itemsize(mode: str) -> float:
    """Bytes per gradient element on the wire (payload only; scale bytes
    are accounted separately by the estimators below)."""
    return {"fp32": 4.0, "bf16": 2.0, "int8": 1.0}[mode]


def _pad_last(x, mult: int):
    w = x.shape[-1]
    wq = _round_up(w, mult)
    if wq == w:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, wq - w)]
    return jnp.pad(x, pad)


# ---------------------------------------------------------------------------
# collectives (shard_map axis-name based; pure jnp + lax)
# ---------------------------------------------------------------------------

def reduce_scatter_quantized(g2d, axis: str, *,
                             block: int = DEFAULT_QUANT_BLOCK):
    """Reduce-scatter one flat-gradient segment with int8 wire bytes.

    ``g2d`` is this rank's ``(n, w)`` view of the segment — row ``r`` is
    the chunk destined to axis rank ``r`` (exactly
    ``flat.reshape(n, w)`` for a tiled ``psum_scatter`` layout).
    Returns this rank's ``(w,)`` f32 chunk of the cross-replica SUM.

    Wire protocol: blockwise-quantize every row (int8 payload + f32
    per-block scales), ONE ``all_to_all`` each for payload and scales,
    then dequantize the ``n`` received source chunks and sum in a
    widened f32 accumulator.  Per-source scales are kept (not pmax'd to
    a shared scale): each replica's gradient is rounded against its OWN
    magnitude, and the f32 accumulation cannot overflow."""
    n, w = g2d.shape
    # clamp the scale granularity to the chunk width: a tiny shard must
    # not pad up to a full default block (which would INFLATE the wire
    # past fp32) — the byte estimators below apply the same clamp
    block = max(1, min(block, w))
    gp = _pad_last(g2d.astype(jnp.float32), block)
    q, scales = quantize_blockwise(gp, block)
    # all_to_all(split=0, concat=0): row r goes to rank r; received row j
    # is rank j's chunk for me — the scatter half of a reduce-scatter,
    # with the reduction deferred to the local widened accumulator
    q = jax.lax.all_to_all(q, axis, 0, 0)
    scales = jax.lax.all_to_all(scales, axis, 0, 0)
    summed = jnp.sum(dequantize_blockwise(q, scales), axis=0)
    return summed[:w]


def psum_quantized(vec, axis: str, n: int, *,
                   block: int = DEFAULT_QUANT_BLOCK):
    """SUM a 1-D f32 vector over ``axis`` (size ``n``) with int8 wire
    bytes — the hierarchical DCN hop of the ZeRO-1 cycle.

    Two quantized phases: all_to_all-scatter (sum dequantized per
    sub-chunk, as :func:`reduce_scatter_quantized`), then re-quantize
    the SUMMED sub-chunk and ``all_gather`` it.  Every rank gathers the
    same int8 payload + scales, so the dequantized result is
    bit-identical on every rank — required so each slice computes the
    identical parameter update and no parameter bytes cross DCN.  The
    summed values pass through a second quantization; that is the
    documented accuracy cost of ``grad_comm="int8"`` on multislice
    meshes (docs/parallelism.md)."""
    w = vec.shape[0]
    block = max(1, min(block, -(-w // n)))  # per-chunk clamp (see above)
    chunk = _round_up(-(-w // n), block)
    gp = jnp.pad(vec.astype(jnp.float32), (0, n * chunk - w))
    part = reduce_scatter_quantized(gp.reshape(n, chunk), axis, block=block)
    q, scales = quantize_blockwise(part, block)
    q = jax.lax.all_gather(q, axis, tiled=True)
    scales = jax.lax.all_gather(scales, axis, tiled=True)
    return dequantize_blockwise(q, scales)[:w]


def all_gather_delta_quantized(delta, base_rows, axis: str, *,
                               block: int = DEFAULT_QUANT_BLOCK):
    """All-gather one bucket's updated-param chunk with int8 wire bytes
    — the ``param_comm="int8"`` leg of the ZeRO-1 cycle.

    ZeRO-1 keeps the flat f32 params REPLICATED; only the optimizer
    update is sharded.  So instead of gathering each rank's f32 updated
    chunk (4 bytes/elem), gather the blockwise-int8 UPDATE DELTA
    ``np_b - p_b`` plus f32 per-block scales (~4x fewer ICI bytes) and
    reconstruct ``base + dequantize(delta)`` locally.  The gathered
    payload+scales are identical bytes on every rank and the base rows
    come from the replicated ``flat_p``, so the reconstructed params
    stay bit-identical replicated — the invariant the whole cycle rests
    on.  Quantizing the DELTA (small against its own abs-max, reset
    every step — rounding does not accumulate in the master params'
    magnitude) is what makes int8 survive the loss-parity gate where
    quantizing the params themselves would not.

    ``delta``: this rank's ``(w,)`` f32 update delta.  ``base_rows``:
    ``(n, w)`` f32 — EVERY rank's base param chunk at these columns
    (``flat_p.reshape(n, shard)[:, c0:c1]``, replicated).  Returns the
    ``(n, w)`` f32 new param rows."""
    n, w = base_rows.shape
    block = max(1, min(block, w))   # same clamp as reduce_scatter
    dp = _pad_last(delta.astype(jnp.float32)[None], block)[0]
    q, scales = quantize_blockwise(dp, block)
    q = jax.lax.all_gather(q, axis)                  # (n, wq) int8
    scales = jax.lax.all_gather(scales, axis)        # (n, wq/block) f32
    return base_rows + dequantize_blockwise(q, scales)[:, :w]


def reduce_scatter_wire(g2d, axis: str, mode: str, *,
                        block: int = DEFAULT_QUANT_BLOCK):
    """Mode-dispatched reduce-scatter of ONE bucket — the single wire
    protocol shared by the train step and the overlap probe (they must
    issue byte-identical collectives or the audit times a different
    wire than the step runs).  ``g2d`` is ``(n, w)`` chunk-per-rank;
    returns this rank's reduced ``(w,)`` chunk, f32 for int8 / the wire
    dtype otherwise."""
    if mode == "int8":
        return reduce_scatter_quantized(g2d, axis, block=block)
    flat = g2d.reshape(-1)
    if mode == "bf16":
        flat = flat.astype(jnp.bfloat16)
    return jax.lax.psum_scatter(flat, axis, scatter_dimension=0,
                                tiled=True)


def psum_wire(vec, axis: str, n: int, mode: str, *,
              block: int = DEFAULT_QUANT_BLOCK):
    """Mode-dispatched hierarchical (DCN) psum of one reduced slice —
    shared by the train step and the overlap probe.  bf16 slices psum in
    bf16 (the half-bytes hop); int8 runs the two-phase quantized
    exchange; fp32 is a plain psum."""
    if mode == "int8":
        return psum_quantized(vec.astype(jnp.float32), axis, n,
                              block=block)
    return jax.lax.psum(vec, axis)


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def bucket_columns(shard_size: int, ndev: int,
                   bucket_bytes: Optional[int] = None,
                   wire_bytes: float = 4.0,
                   block: Optional[int] = None) -> List[Tuple[int, int]]:
    """Split the per-rank shard width into contiguous column buckets.

    ``bucket_bytes`` bounds each bucket's FULL flat-gradient segment
    (``ndev * cols * wire_bytes`` payload — the DDP bucket convention);
    ``None`` keeps today's single monolithic transfer.  Bucket widths
    align to ``block`` (the int8 quantization granularity) so only the
    final bucket ever pads.  Returns ``[(c0, c1), ...]`` covering
    ``[0, shard_size)``."""
    if shard_size <= 0 or not bucket_bytes or bucket_bytes <= 0:
        return [(0, max(shard_size, 0))]
    cols = max(1, int(bucket_bytes / max(wire_bytes, 1e-9)) // max(ndev, 1))
    if block:
        # round DOWN to the quantization granularity (at least one
        # block) so only the final bucket ever pads
        cols = max(block, (cols // block) * block)
    out = []
    c0 = 0
    while c0 < shard_size:
        c1 = min(shard_size, c0 + cols)
        out.append((c0, c1))
        c0 = c1
    return out


# ---------------------------------------------------------------------------
# wire-byte estimators — the ledger's source of truth
# ---------------------------------------------------------------------------

def rs_wire_bytes(w: int, n: int, mode: str,
                  block: int = DEFAULT_QUANT_BLOCK) -> int:
    """Per-step wire bytes to reduce-scatter ONE bucket of per-rank
    width ``w`` over ``n`` ranks.  Full-vector convention (ring moves
    (n-1)/n ≈ 1x); int8 counts the padded payload plus f32 scales."""
    if n <= 1 or w <= 0:
        return 0
    if mode == "int8":
        block = max(1, min(block, w))  # same clamp as the collective
        wq = _round_up(w, block)
        return n * wq + n * (wq // block) * _SCALE_BYTES
    return int(n * w * wire_itemsize(mode))


def ag_wire_bytes(w: int, n: int, mode: str,
                  block: int = DEFAULT_QUANT_BLOCK) -> int:
    """Per-step wire bytes to all_gather ONE bucket of per-rank width
    ``w`` over ``n`` ranks — the updated-param leg.  ``"fp32"`` is the
    plain f32 gather (``n * w * 4``, summing to the classic
    ``n_pad * 4``); ``"int8"`` prices the delta gather's padded int8
    payload plus f32 per-block scales."""
    if n <= 1 or w <= 0:
        return 0
    if mode == "int8":
        block = max(1, min(block, w))  # same clamp as the collective
        wq = _round_up(w, block)
        return n * wq + n * (wq // block) * _SCALE_BYTES
    return int(n * w * 4)


def psum_wire_bytes(w: int, n: int, mode: str,
                    block: int = DEFAULT_QUANT_BLOCK) -> int:
    """Per-step wire bytes for the hierarchical psum of a ``w``-elem
    slice over ``n`` ranks (the DCN hop): scatter + gather phases, each
    ~ the slice's wire bytes (+ scales for int8)."""
    if n <= 1 or w <= 0:
        return 0
    if mode == "int8":
        block = max(1, min(block, -(-w // n)))  # same clamp as psum
        chunk = _round_up(-(-w // n), block)
        per_phase = n * chunk + n * (chunk // block) * _SCALE_BYTES
        return 2 * per_phase
    return int(2 * w * wire_itemsize(mode))


def layout_ledger(n_params: int, ndev: int, dcn: int = 1,
                  mode: str = "fp32",
                  bucket_bytes: Optional[int] = None,
                  block: int = DEFAULT_QUANT_BLOCK,
                  param_comm: str = "fp32") -> Dict[str, float]:
    """Pure layout math: the per-step collective-bytes ledger of a ZeRO-1
    cycle over ``n_params`` parameters WITHOUT building a step engine (no
    devices touched), so a geometry can be priced on any host.  Mirrors
    ``ShardedParameterStep``'s properties exactly (same bucket table,
    same estimators).  ``param_comm`` prices the updated-param gather in
    its actual wire dtype — fp32 stays the classic ``n_pad * 4``."""
    if mode not in GRAD_COMM_MODES:
        raise ValueError(f"grad_comm {mode!r}: one of {GRAD_COMM_MODES}")
    if param_comm not in PARAM_COMM_MODES:
        raise ValueError(f"param_comm {param_comm!r}: one of "
                         f"{PARAM_COMM_MODES}")
    n_pad = _round_up(n_params, ndev)
    shard = n_pad // ndev
    cols = bucket_columns(shard, ndev, bucket_bytes,
                          wire_itemsize(mode),
                          block if mode == "int8" else None)
    grad_ici = sum(rs_wire_bytes(c1 - c0, ndev, mode, block)
                   for c0, c1 in cols)
    param_ici = (sum(ag_wire_bytes(c1 - c0, ndev, param_comm, block)
                     for c0, c1 in cols) if ndev > 1 else 0)
    dcn_bytes = sum(psum_wire_bytes(c1 - c0, dcn, mode, block)
                    for c0, c1 in cols)
    return {
        "grad_comm": mode,
        "param_comm": param_comm,
        "n_params": float(n_params),
        "n_params_padded": float(n_pad),
        "comm_buckets": float(len(cols)),
        "grad_sync_ici_bytes_per_step": float(grad_ici),
        "param_sync_ici_bytes_per_step": float(param_ici),
        "ici_bytes_per_step": float(grad_ici + param_ici),
        "grad_sync_dcn_bytes_per_step": float(dcn_bytes),
        "dcn_bytes_per_step": float(dcn_bytes),
    }
