"""Fused elementwise kernels.

Reference analog: the MKL VML batch calls (``vsExp/vsAdd/...`` through
``com.intel.analytics.bigdl.mkl.MKL`` — SURVEY.md §3.2) that the reference
uses to avoid per-element JNI overhead.  On TPU, XLA already fuses most
elementwise chains into the surrounding matmuls; the kernel here covers the
remaining normalisation pattern where a hand-rolled single-pass kernel
keeps the row resident in VMEM across both reduction and scale steps.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bigdl_tpu.ops.common import default_interpret, round_up

# rows per grid step where a call leaves ``block_rows`` as None
BLOCK_ROWS = 256

def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps, d):
    x = x_ref[:].astype(jnp.float32)
    mask = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < d
    xm = jnp.where(mask, x, 0.0)
    mean = jnp.sum(xm, axis=-1, keepdims=True) / d
    var = jnp.sum(jnp.where(mask, (x - mean) ** 2, 0.0), axis=-1,
                  keepdims=True) / d
    inv = jax.lax.rsqrt(var + eps)
    y = (x - mean) * inv * g_ref[0][None, :] + b_ref[0][None, :]
    o_ref[:] = y.astype(o_ref.dtype)


def fused_layernorm(x, gamma, beta, *, eps: float = 1e-5,
                    block_rows: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Single-pass LayerNorm over the last axis.  Differentiable: backward
    is the closed-form LayerNorm VJP evaluated with jnp (XLA fuses it).

    ``block_rows=None`` takes :data:`BLOCK_ROWS`; an explicit value
    always wins."""
    if block_rows is None:
        block_rows = BLOCK_ROWS
    return _fused_ln(x, gamma, beta, eps, int(block_rows), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ln(x, gamma, beta, eps, block_rows, interpret):
    return _ln_forward(x, gamma, beta, eps, block_rows, interpret)


def _ln_forward(x, gamma, beta, eps, block_rows, interpret):
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    m = x2.shape[0]
    br = min(block_rows, round_up(m, 8))
    mp = round_up(m, br)
    dp = round_up(d, 128)
    xp = jnp.pad(x2, ((0, mp - m), (0, dp - d)))
    gp = jnp.pad(gamma.astype(jnp.float32), (0, dp - d))[None, :]
    bp = jnp.pad(beta.astype(jnp.float32), (0, dp - d))[None, :]

    kernel = functools.partial(_ln_kernel, eps=eps, d=d)
    out = pl.pallas_call(
        kernel,
        grid=(mp // br,),
        in_specs=[
            pl.BlockSpec((br, dp), lambda i: (i, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, dp), x.dtype),
        interpret=default_interpret(interpret),
    )(xp, gp, bp)
    return out[:m, :d].reshape(*lead, d)


def _ln_vjp_fwd(x, gamma, beta, eps, block_rows, interpret):
    out = _ln_forward(x, gamma, beta, eps, block_rows, interpret)
    return out, (x, gamma, beta)


def _ln_vjp_bwd(eps, block_rows, interpret, res, g):
    x, gamma, beta = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    d = x.shape[-1]
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (xf - mean) * inv
    dgamma = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    dbeta = jnp.sum(gf, axis=tuple(range(x.ndim - 1)))
    gy = gf * gamma.astype(jnp.float32)
    dx = inv * (gy - jnp.mean(gy, axis=-1, keepdims=True)
                - xhat * jnp.mean(gy * xhat, axis=-1, keepdims=True))
    # gradients match each primal's dtype (f32 master params keep f32 grads
    # even when activations are bf16)
    return (dx.astype(x.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(beta.dtype))


_fused_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)
