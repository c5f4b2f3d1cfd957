"""Ring attention — exact sequence-parallel attention over the "seq" axis.

New capability vs the reference (SURVEY.md §6.7: the reference's
``nn/Transformer.scala``/``nn/Attention.scala`` are single-device full O(L²)
attention).  TPU-native design: every device holds one sequence block of
Q/K/V; K/V blocks rotate around the ring via ``jax.lax.ppermute`` (maps to
ICI neighbor exchanges) while each device folds the visiting block into a
flash-style online-softmax accumulator.  Compute of step *i* overlaps the
transfer of step *i+1* under XLA's latency-hiding scheduler because the
``ppermute`` result is only consumed next iteration.

Exact (bitwise-stable masked softmax), causal-aware: fully-masked blocks are
skipped numerically (their contribution is exp(-inf)=0) without NaNs.
"""

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


NEG_INF = -1e30


def _block_attend(q, k, v, q_pos, k_pos, m, l, acc, causal, scale):
    """Fold one visiting K/V block into the online-softmax accumulator.

    q: (b, h, cq, d); k/v: (b, h, ck, d); q_pos: (cq,), k_pos: (ck,) global
    positions; m/l: (b, h, cq); acc: (b, h, cq, d) f32.
    """
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k,
        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = (k_pos[None, :] <= q_pos[:, None])  # (cq, ck)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m_blk = jnp.max(logits, axis=-1)                       # (b,h,cq)
    m_new = jnp.maximum(m, m_blk)
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None])                 # (b,h,cq,ck)
    l_new = alpha * l + jnp.sum(p, axis=-1)
    acc_new = alpha[..., None] * acc + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None):
    """Sequence-parallel exact attention.  Call inside ``shard_map`` with the
    sequence dimension sharded over ``axis_name``.

    q, k, v: (batch, heads, block_len, head_dim) — the LOCAL sequence block.
    Returns the local attention output block, same shape/dtype as q.
    """
    b, h, c, d = q.shape
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    n_blocks = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    q32 = q.astype(jnp.float32)
    q_pos = my_idx * c + jnp.arange(c)

    def body(carry, step):
        k_blk, v_blk, m, l, acc = carry
        # block currently held started at its owner: (my_idx - step) mod S
        src = jnp.mod(my_idx - step, n_blocks)
        k_pos = src * c + jnp.arange(c)
        m, l, acc = _block_attend(
            q32, k_blk.astype(jnp.float32), v_blk, q_pos, k_pos,
            m, l, acc, causal, scale)
        # rotate K/V to the next device (ring over ICI); the permuted block
        # is consumed only on the next step, so XLA overlaps it with compute
        perm = [(j, (j + 1) % n_blocks) for j in range(n_blocks)]
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, m, l, acc), ()

    m0 = jnp.full((b, h, c), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, c), jnp.float32)
    acc0 = jnp.zeros((b, h, c, d), jnp.float32)
    (k_f, v_f, m, l, acc), _ = jax.lax.scan(
        body, (k, v, m0, l0, acc0), jnp.arange(n_blocks))
    del k_f, v_f
    # fully-masked rows (causal, first block positions with nothing visible
    # never happen since a token sees itself; keep the guard for safety)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def seq_sharded_call(kernel, mesh, q, k, v, axis_name: str,
                     causal: bool):
    """Shared wrapper for sequence-parallel attention kernels: shard GLOBAL
    (b, h, L, d) arrays over the mesh's ``axis_name`` (sequence dim) and
    run ``kernel(q, k, v, axis_name=..., causal=...)`` under shard_map.
    Used by both ring and Ulysses attention."""
    from bigdl_tpu.runtime.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)
    fn = shard_map(
        partial(kernel, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def ring_attention_sharded(mesh, q, k, v, axis_name: str = "seq",
                           causal: bool = False):
    """Convenience: apply ring attention to GLOBAL (b, h, L, d) arrays by
    shard_map-ping over the mesh's ``axis_name``."""
    return seq_sharded_call(ring_attention, mesh, q, k, v, axis_name,
                            causal)
