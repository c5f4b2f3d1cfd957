"""The Mamba-2 mixer (arXiv:2405.21060 §7; the ``mamba`` layers of a hybrid
decoder such as IBM's ``granitemoehybrid``), one group of ``B`` and ``C``
shared by every head.

For ``u`` (..., T, d), with ``d_in = heads · head_dim``:

- ``[z, xBC, Δ_raw] = u W_in`` (widths ``d_in``, ``d_in + 2N``, ``heads``;
  no bias);
- ``xBC ← SiLU(conv_K(xBC) + b_conv)``: a depthwise causal convolution of
  ``K`` taps, one filter a channel (``ops/causal_conv.py``);
- ``[x, B, C] = xBC``, x viewed as (T, heads, head_dim), B and C (T, N);
- ``Δ = softplus(Δ_raw + dt_bias)``, ``A = −exp(A_log)``, one of each a
  head;
- ``y = SSD(x, Δ, A, B, C) + D ⊙ x`` (``ops/ssd.py``);
- ``g = y ⊙ silu(z)``, then ``g / sqrt(mean(g²) + ε) ⊙ w_norm`` over all
  ``d_in`` channels at once (the norm comes after the gate, over the one
  group);
- ``Mix(u) = g W_out`` (``d_in`` x d, no bias).

The two matmuls take their inputs in the policy's compute dtype with
float32 accumulation (device scope ``mamba/proj``); the convolution with
its bias and SiLU float32, one Pallas pass each way that reads ``xBC`` in
place out of ``u W_in`` (``mamba/causal_conv``: ``ops/causal_conv.py``)
wherever its tile rule takes the widths, else the plain expression
(``nn.short_conv.causal_taps``, in ``mamba/conv``); the gate's split and
``Δ`` float32 (``mamba/conv``); the scan (``mamba/ssd``); the gate and the
norm float32 (``mamba/norm``).  Around the scan nothing changes layout:
the SSD kernels take ``Δ⊙x`` and give ``y`` with T on the lanes, as XLA
holds every activation of the mixer; x reaches them as a slice of
``xBC``'s view as rows of ``head_dim`` (``_heads``), which XLA fuses into
``Δ⊙x``.  ``D ⊙ x`` is added here, not by ``ssd``, in the mixer's
``(…, T, d_in)`` layout, where XLA fuses it with the gate into the norm's
passes (an add of its own in the scan's layout otherwise).  The mixer is
whole on every rank of a tensor-parallel group: its heads share ``B``,
``C`` and the norm's statistic (docs/parallelism.md §A whole Mamba-2 mixer
beside held attention heads).

It counts, in its model state, each scan it applies (``ssm.scans``), each
convolution traced through the kernels (``ssm.fused_convs``) and, as a
fine mean, the share of the carried state that survives one chunk
(``ssm.chunk_carry``: the mean over heads and chunks of ``exp(Σ_chunk Δ
A)``) (``obs/state_metrics.py``).  This module mixes a whole sequence
(training, prefill); a decode step would need each layer's scan state and
its last ``K − 1`` convolution inputs as per-slot state beside the paged
K/V, which the serving engine does not have yet."""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.layers import rms_norm
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.short_conv import causal_taps
from bigdl_tpu.obs.state_metrics import bump_state_metrics, new_state_metrics
from bigdl_tpu.ops.causal_conv import causal_conv, conv_blocks
from bigdl_tpu.ops.ssd import DEFAULT_CHUNK, chunk_carry, ssd
from bigdl_tpu.tensor.policy import cast_compute

SCANS, FUSED_CONVS = "ssm.scans", "ssm.fused_convs"
SSM_COUNTERS = (SCANS, FUSED_CONVS)
SSM_FINE = ("ssm.chunk_carry",)
# Mamba-2's initialisation of the step size and the decay (the published
# config gives none): Δ log-uniform in [DT_RANGE], floored; A in [A_RANGE]
DT_RANGE, DT_FLOOR, A_RANGE = (1e-3, 1e-1), 1e-4, (1.0, 16.0)


def _mm(x, w):
    return jnp.matmul(cast_compute(x), cast_compute(w),
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _heads(xbc, heads, head_dim):
    """x of ``x‖B‖C`` (batch, T, W) as (batch, T, heads, head_dim), taken
    from the view of ``xbc`` as rows of ``head_dim``: XLA fuses that slice
    into ``Δ⊙x``, where it writes a slice of the (batch, T, W) array out
    first.  The cotangent comes back as a pad of the (batch, T, W) array,
    which XLA fuses into the convolution's cotangent (a pad of the view it
    writes out)."""
    whole = xbc.shape[-1] // head_dim * head_dim
    return xbc[..., :whole].reshape(*xbc.shape[:2], -1, head_dim)[:, :, :heads]


def _heads_fwd(xbc, heads, head_dim):
    return _heads(xbc, heads, head_dim), xbc.shape[-1]


def _heads_bwd(heads, head_dim, wide, g):
    g = g.reshape(*g.shape[:2], heads * head_dim)
    return (jnp.pad(g, ((0, 0), (0, 0), (0, wide - heads * head_dim))),)


_heads.defvjp(_heads_fwd, _heads_bwd)


class Mamba2(Module):
    """``forward(params, state, u)`` → ``Mix(u)`` and the new counters.
    Parameters ``w_in`` (d, 2 d_in + 2N + heads), ``conv_w`` (K, d_in +
    2N), ``conv_b``, ``dt_bias``, ``A_log``, ``D`` (heads,), ``norm``
    (d_in,), ``w_out`` (d_in, d)."""

    def __init__(self, hidden: int, heads: int, head_dim: int, state: int,
                 kernel: int = 4, *, eps: float = 1e-5,
                 chunk: int = DEFAULT_CHUNK, name: Optional[str] = None):
        super().__init__(name)
        self.hidden, self.heads, self.head_dim = hidden, heads, head_dim
        self.d_state, self.kernel, self.eps, self.chunk = (state, kernel,
                                                           eps, chunk)
        self.d_in = heads * head_dim
        self.conv_dim = self.d_in + 2 * state

    def build(self, rng, x):
        d, h, k = self.hidden, self.heads, self.kernel
        ks = jax.random.split(rng, 6)
        # the taps and their bias as torch's Conv1d draws them, U(±K^-1/2)
        bound = k ** -0.5
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(ks[3], (h,), minval=lo,
                                                    maxval=hi)), DT_FLOOR)
        return {
            "w_in": jax.random.normal(
                ks[0], (d, 2 * self.d_in + 2 * self.d_state + h)) * d ** -0.5,
            "conv_w": jax.random.uniform(ks[1], (k, self.conv_dim),
                                         minval=-bound, maxval=bound),
            "conv_b": jax.random.uniform(ks[2], (self.conv_dim,),
                                         minval=-bound, maxval=bound),
            # softplus(dt_bias) = dt
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(
                ks[4], (h,), minval=A_RANGE[0], maxval=A_RANGE[1])),
            "D": jnp.ones((h,)),
            "norm": jnp.ones((self.d_in,)),
            "w_out": jax.random.normal(ks[5], (self.d_in, d))
            * self.d_in ** -0.5,
        }, {"metrics": new_state_metrics(counters=SSM_COUNTERS,
                                         fine=SSM_FINE)}

    def forward(self, params, state, u, training=False, rng=None):
        batch, t, _ = u.shape
        d_in, n, h = self.d_in, self.d_state, self.heads
        with jax.named_scope("mamba/proj"):
            zxbcdt = _mm(u, params["w_in"])
        fused = conv_blocks(t, d_in, self.conv_dim, self.kernel) is not None
        if fused:
            with jax.named_scope("mamba/causal_conv"):
                xbc = causal_conv(zxbcdt, params["conv_w"], params["conv_b"],
                                  offset=d_in)
        else:
            with jax.named_scope("mamba/conv"):
                xbc = jax.nn.silu(causal_taps(
                    zxbcdt[..., d_in:d_in + self.conv_dim],
                    params["conv_w"].astype(jnp.float32)) + params["conv_b"])
        with jax.named_scope("mamba/conv"):
            z, x = zxbcdt[..., :d_in], xbc[..., :d_in]
            xh = _heads(xbc, h, self.head_dim)
            b, c = xbc[..., d_in:d_in + n], xbc[..., d_in + n:]
            dt = jax.nn.softplus(zxbcdt[..., d_in + self.conv_dim:]
                                 + params["dt_bias"])
            a = -jnp.exp(params["A_log"])
        with jax.named_scope("mamba/ssd"):
            y = ssd(xh, dt, a, b, c, chunk=self.chunk).reshape(
                batch, t, d_in) + jnp.repeat(params["D"], self.head_dim) * x
        with jax.named_scope("mamba/norm"):
            g = rms_norm(y * jax.nn.silu(z), params["norm"], self.eps)
        with jax.named_scope("mamba/proj"):
            out = _mm(g, params["w_out"]).astype(u.dtype)
        carry = jax.lax.stop_gradient(chunk_carry(dt, a, self.chunk))
        return out, {"metrics": bump_state_metrics(
            state["metrics"], {SCANS: 1, FUSED_CONVS: int(fused)},
            {"ssm.chunk_carry": carry})}
