"""Gated short convolution: the token mixer of a hybrid decoder's ``conv``
layers (LFM2's ``lfm2_moe``; the Hyena / H3 lineage's short filter between
two multiplicative gates), in place of attention.

For ``u`` (..., T, d):

- ``[B, C, X] = split_3(u W_in)`` (``W_in`` d x 3d, no bias; the three
  d-wide thirds in that order);
- ``z = B ⊙ X``;
- ``c[t] = sum_j w[j] ⊙ z[t - (K - 1) + j]``, ``z[s] = 0`` for ``s < 0``:
  a depthwise CAUSAL convolution of ``K`` taps (``kernel``), one filter a
  channel, no bias: position ``t`` sees itself and the ``K - 1`` before it;
- ``Op(u) = (C ⊙ c) W_out`` (d x d, no bias).

The two matmuls take their inputs in the policy's compute dtype with
float32 accumulation (device scope ``conv/proj``); the gates and the taps
are float32 (``conv/mix``): memory-bound work on ``(T, d)`` slabs.  The
convolution is ``K`` shifted multiply-adds along the sequence axis, written
for any ``K >= 1``: a ``lax.conv`` with d feature groups would be lowered
as a convolution of one channel, d times.  The taps are held ``(K, d)``,
channels along the lanes.

This module mixes a whole sequence (training, prefill).  A decode step
needs the last ``K - 1`` values of ``z`` of every layer as per-slot state
beside the paged K/V of the attention layers, which the serving engine does
not have yet."""

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import EMPTY, Module
from bigdl_tpu.tensor.policy import cast_compute


def causal_taps(z, taps):
    """``c[..., t, :] = sum_j taps[j] * z[..., t - (K - 1) + j, :]`` with
    zeros before the sequence's start.  z: (..., T, d); taps: (K, d)."""
    kernel, length = taps.shape[0], z.shape[-2]
    out = taps[kernel - 1] * z
    for back in range(1, min(kernel, length)):
        # z delayed by ``back`` positions: zeros in, the tail out
        pad = [(0, 0)] * z.ndim
        pad[-2] = (back, 0)
        delayed = jnp.pad(z[..., :length - back, :], pad)
        out = out + taps[kernel - 1 - back] * delayed
    return out


class GatedShortConv(Module):
    """``forward(params, state, u)`` → ``(C ⊙ conv_K(B ⊙ X)) W_out`` and no
    state.  Parameters ``w_in`` (d, 3d), ``taps`` (K, d), ``w_out``
    (d, d)."""

    def __init__(self, hidden: int, kernel: int = 3,
                 name: Optional[str] = None):
        super().__init__(name)
        if kernel < 1:
            raise ValueError(f"kernel={kernel}: at least one tap")
        self.hidden, self.kernel = hidden, kernel

    def build(self, rng, x):
        d, k = self.hidden, self.kernel
        k_in, k_taps, k_out = jax.random.split(rng, 3)
        # unit-variance inputs give unit-variance thirds, a unit-variance
        # z, and with taps of variance 1/K a unit-variance c
        return {"w_in": jax.random.normal(k_in, (d, 3 * d)) * d ** -0.5,
                "taps": jax.random.normal(k_taps, (k, d)) * k ** -0.5,
                "w_out": jax.random.normal(k_out, (d, d)) * d ** -0.5}, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        d = self.hidden
        with jax.named_scope("conv/proj"):
            bcx = jnp.matmul(cast_compute(x), cast_compute(params["w_in"]),
                             preferred_element_type=jnp.float32)
        with jax.named_scope("conv/mix"):
            b, c, xx = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
            y = c * causal_taps(b * xx, params["taps"].astype(jnp.float32))
        with jax.named_scope("conv/proj"):
            out = jnp.matmul(cast_compute(y), cast_compute(params["w_out"]),
                             preferred_element_type=jnp.float32)
        return out.astype(x.dtype), EMPTY
