"""Chunked causal linear attention with a per-head decay ("lightning
attention", arXiv:2401.04658) — Pallas TPU kernels, forward and backward.

For each head, with ``λ = exp(−slope)`` of that head::

    o_t = scale · Σ_{s ≤ t} λ^{t−s} (q_t · k_s) v_s

which is the recurrence ``S_t = λ S_{t−1} + k_tᵀ v_t``, ``o_t = scale ·
q_t S_t`` with ``S_{−1} = 0``, and equally the quadratic form ``((q kᵀ) ⊙
D) v`` with ``D[t, s] = λ^{t−s}`` on and below the diagonal.  The kernel
walks the sequence in chunks of ``C`` positions, one grid step a chunk,
and carries the ``(d, d_v)`` float32 state in VMEM from one chunk to the
next (the chunk axis is sequential, ``"arbitrary"``).  Inside a chunk it
takes the quadratic form on the ``(C, C)`` tile and adds what the state
holds of everything before it::

    o  = scale · [((q kᵀ) ⊙ D_C) v + λ^{i+1} ⊙ (q S)]
    S' = λ^C S + (k ⊙ λ^{C−1−j})ᵀ v

Nothing is ever raised to a negative power: every decay is ``exp`` of a
number at or below zero, so a head that forgets quickly underflows to 0
and never overflows.

The backward pass is three runs of the same kernel (no transposed product
inside any of them: ``k`` comes in transposed, ``(d, T)``)::

    dq = LA(g, v, k)              forward in time
    dk = LA_rev(v, g, q)          backward in time: s ≤ t swapped for t ≥ s
    dv = LA_rev(k, q, g)

where ``LA(a, b, c)_t = scale Σ_{s ≤ t} λ^{t−s} (a_t · b_s) c_s`` and
``LA_rev`` sums over ``s ≥ t`` (the grid walks the chunks from the last,
the chunk's decay mask is its transpose).  Operands in the policy's compute
dtype (``tensor.policy.cast_compute``), products with float32
accumulation, the state and every decay float32; the state is rounded to
the compute dtype only as a product's operand, as ``p`` is in the flash
kernels.  Shapes: q, k ``(batch, heads, T, d)``, v ``(batch, heads, T,
d_v)``; ``slopes`` one float a head (static); T is padded at the end to a
whole chunk (zeros there add nothing, in either direction).
"""

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.common import default_interpret, round_up
from bigdl_tpu.tensor.policy import cast_compute

# positions a grid step takes: a multiple of the sparse layer's 64-token
# block, long enough that the (C, C) products fill the MXU
DEFAULT_CHUNK = 256


def alibi_slopes(heads: int, first: int = 0, count: Optional[int] = None):
    """ALiBi's slopes (arXiv:2108.12409) of heads ``first .. first + count
    − 1`` of ``heads``: ``2 ** (−8 (j + 1) / heads)``, as Lightning
    Attention-2 and MiniMax-01 give a head its decay."""
    count = heads - first if count is None else count
    return tuple(2.0 ** (-8.0 * (j + 1) / heads)
                 for j in range(first, first + count))


def _mxu(a, b):
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _la_kernel(slope_ref, q_ref, kt_ref, v_ref, o_ref, s_scr, *, scale,
               chunk, reverse):
    # q_ref (1, C, d); kt_ref (1, d, C); v_ref (1, C, d_v); s_scr (d, d_v)
    bh = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    slope = slope_ref[bh]
    q, kt, v = q_ref[0], kt_ref[0], v_ref[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lag = (cols - rows) if reverse else (rows - cols)
    decay = jnp.where(lag >= 0, jnp.exp(
        -slope * jnp.maximum(lag, 0).astype(jnp.float32)), 0.0)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0).astype(
        jnp.float32)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1).astype(
        jnp.float32)
    # what the state holds reaches position i of the chunk after i + 1
    # steps (C − i backwards); a key at j reaches the chunk's far edge
    # after C − 1 − j (j)
    q_decay = jnp.exp(-slope * ((chunk - i) if reverse else (i + 1.0)))
    k_decay = jnp.exp(-slope * (j if reverse else (chunk - 1.0 - j)))
    state = s_scr[...]
    p = _mxu(q, kt) * decay
    o = _mxu(p.astype(v.dtype), v) + q_decay * _mxu(q, state.astype(q.dtype))
    o_ref[0] = (o * scale).astype(o_ref.dtype)
    carry = jnp.exp(-slope * jnp.full((1, 1), float(chunk), jnp.float32))
    s_scr[...] = carry * state + _mxu((kt * k_decay).astype(kt.dtype), v)


def _la(a, bt, c, slopes, scale, chunk, reverse, interpret):
    """``(a (BH, T, d), bᵀ (BH, d, T), c (BH, T, d_v))`` → (BH, T, d_v)
    in ``a``'s dtype; ``slopes`` (BH,) float32.  T is a whole number of
    chunks here."""
    bh, t, d = a.shape
    d_v = c.shape[-1]
    n = t // chunk
    ci = (lambda k: n - 1 - k) if reverse else (lambda k: k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n),
        in_specs=[pl.BlockSpec((1, chunk, d), lambda h, k, s: (h, ci(k), 0)),
                  pl.BlockSpec((1, d, chunk), lambda h, k, s: (h, 0, ci(k))),
                  pl.BlockSpec((1, chunk, d_v),
                               lambda h, k, s: (h, ci(k), 0))],
        out_specs=pl.BlockSpec((1, chunk, d_v),
                               lambda h, k, s: (h, ci(k), 0)),
        scratch_shapes=[pltpu.VMEM((d, d_v), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_la_kernel, scale=scale, chunk=chunk,
                          reverse=reverse),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d_v), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=default_interpret(interpret),
    )(slopes, a, bt, c)


def _t(x):
    return jnp.swapaxes(x, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _lightning(q, k, v, slopes, scale, chunk, interpret):
    return _la(q, _t(k), v, jnp.asarray(slopes, jnp.float32), scale, chunk,
               False, interpret)


def _lightning_fwd(q, k, v, slopes, scale, chunk, interpret):
    return _lightning(q, k, v, slopes, scale, chunk, interpret), (q, k, v)


def _lightning_bwd(slopes, scale, chunk, interpret, res, g):
    q, k, v = res
    g = g.astype(q.dtype)
    s = jnp.asarray(slopes, jnp.float32)
    dq = _la(g, _t(v), k, s, scale, chunk, False, interpret)
    dk = _la(v, _t(g), q, s, scale, chunk, True, interpret)
    dv = _la(k, _t(q), g, s, scale, chunk, True, interpret)
    return dq, dk, dv


_lightning.defvjp(_lightning_fwd, _lightning_bwd)


def lightning_attention(q, k, v, slopes: Sequence[float], *,
                        scale: Optional[float] = None,
                        chunk: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """Causal linear attention with decay ``exp(−slopes[h])`` a step for
    head ``h`` (the module docstring has the equations).  q, k: (batch,
    heads, T, d); v: (batch, heads, T, d_v); ``slopes``: ``heads`` floats.
    ``scale`` defaults to ``d ** −0.5``.  The result has v's width and q's
    dtype; operands go to the policy's compute dtype."""
    b, h, t, d = q.shape
    d_v = v.shape[-1]
    if len(slopes) != h or k.shape != q.shape or v.shape[:3] != (b, h, t):
        raise ValueError(f"lightning_attention: q{q.shape} k{k.shape} "
                         f"v{v.shape} with {len(slopes)} slopes")
    scale = d ** -0.5 if scale is None else float(scale)
    chunk = int(chunk or min(DEFAULT_CHUNK, round_up(t, 64)))
    t_p = round_up(t, chunk)
    dtype = q.dtype
    q, k, v = cast_compute(q, k, v)

    def flat(x):
        x = x.reshape(b * h, t, x.shape[-1])
        return x if t_p == t else jnp.pad(x, ((0, 0), (0, t_p - t), (0, 0)))

    per_row = tuple(float(s) for s in slopes) * b
    out = _lightning(flat(q), flat(k), flat(v), per_row, scale, chunk,
                     interpret)
    return out[:, :t].reshape(b, h, t, d_v).astype(dtype)
