"""Ulysses-style sequence parallelism — all-to-all head redistribution.

The second of the two canonical long-context strategies (the first, ring
attention, lives in ``parallel/ring_attention.py``; the reference —
SURVEY.md §6.7, mount empty/unverified — has neither: its attention is
single-device O(L²)).  Where ring attention rotates K/V blocks around the
"seq" axis and never materializes the full sequence anywhere, Ulysses
(DeepSpeed-Ulysses, arXiv:2309.14509 — PAPERS.md) re-shards with two
``all_to_all`` collectives:

    in:   q/k/v sharded over SEQUENCE  (each device: full heads, L/P tokens)
    a2a:  q/k/v sharded over HEADS     (each device: h/P heads, FULL L)
    ...plain full attention per head group (XLA's fused attention path —
       no custom accumulation loop needed)...
    a2a:  output back to SEQUENCE sharding

Trade-off vs ring: Ulysses moves ``2 x (q + k + v + o)/P`` bytes in two
dense all-to-alls (bisection-bandwidth friendly on a TPU torus) and runs
the unmodified attention kernel; ring moves K/V in P-1 neighbor hops and
never needs the full L on one chip.  Ulysses requires ``heads % P == 0``;
ring has no head constraint.  Both are exact.
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp


def _a2a(x, axis_name: str, split_axis: int, concat_axis: int):
    """all_to_all that splits ``split_axis`` over the mesh axis and
    concatenates the incoming shards along ``concat_axis``."""
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      scale: Optional[float] = None):
    """Sequence-parallel exact attention via head redistribution.  Call
    inside ``shard_map`` with the sequence dimension sharded over
    ``axis_name``.

    q, k, v: (batch, heads, block_len, head_dim) — the LOCAL sequence
    block with ALL heads (same convention as :func:`ring_attention`).
    ``heads`` must be divisible by the axis size.  ``scale`` overrides
    the default ``1/sqrt(head_dim)`` logit scale.  Returns the local
    output block, same shape/dtype as q.
    """
    from bigdl_tpu.nn.attention import dot_product_attention

    b, h, c, d = q.shape
    p = jax.lax.axis_size(axis_name)
    if h % p != 0:
        raise ValueError(
            f"ulysses needs heads ({h}) divisible by the seq axis ({p}); "
            "use ring_attention for head counts below the axis size")
    if scale is not None:
        # dot_product_attention applies 1/sqrt(d); fold the override in
        q = q * (scale * math.sqrt(d))

    # seq-sharded (b, h, c, d) -> head-sharded (b, h/p, c*p, d): split the
    # head dim across devices, concatenate the sequence blocks
    qh = _a2a(q, axis_name, split_axis=1, concat_axis=2)
    kh = _a2a(k, axis_name, split_axis=1, concat_axis=2)
    vh = _a2a(v, axis_name, split_axis=1, concat_axis=2)

    mask = None
    if causal:
        L = qh.shape[2]
        mask = jnp.tril(jnp.ones((L, L), bool))[None, None]
    out = dot_product_attention(qh, kh, vh, mask=mask)

    # head-sharded output back to sequence sharding
    return _a2a(out.astype(q.dtype), axis_name, split_axis=2,
                concat_axis=1)


def ulysses_attention_sharded(mesh, q, k, v, axis_name: str = "seq",
                              causal: bool = False):
    """Convenience: apply Ulysses attention to GLOBAL (b, h, L, d) arrays
    by shard_map-ping over the mesh's ``axis_name``."""
    from bigdl_tpu.parallel.ring_attention import seq_sharded_call

    return seq_sharded_call(ulysses_attention, mesh, q, k, v, axis_name,
                            causal)
