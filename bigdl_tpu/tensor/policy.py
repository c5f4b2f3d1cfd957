"""Mixed-precision policy.

TPU-first stance: params live in float32, matmuls/convs run with bfloat16
inputs and float32 accumulation (native MXU mode) — so the DEFAULT compute
dtype is bfloat16 on TPU and float32 elsewhere (CPU test meshes keep full
precision for golden comparisons).  The reference has no such policy (MKL
float32 everywhere); this replaces the engineType ``mklblas|mkldnn`` switch
(dllib/utils/Engine.scala, unverified) as the "which compute path" knob.
"""

from contextlib import contextmanager

import jax.numpy as jnp

# None = resolve lazily from the platform on first use (importing jax.devices
# at module import time would initialize the backend too early).
_COMPUTE_DTYPE = [None]


def _platform_default():
    from bigdl_tpu.ops.common import on_tpu

    return jnp.dtype(jnp.bfloat16) if on_tpu() else jnp.dtype(jnp.float32)


def set_compute_dtype(dtype) -> None:
    _COMPUTE_DTYPE[0] = None if dtype is None else jnp.dtype(dtype)


def get_compute_dtype():
    if _COMPUTE_DTYPE[0] is None:
        _COMPUTE_DTYPE[0] = _platform_default()
    return _COMPUTE_DTYPE[0]


@contextmanager
def compute_dtype(dtype):
    old = _COMPUTE_DTYPE[0]
    set_compute_dtype(dtype)
    try:
        yield
    finally:
        _COMPUTE_DTYPE[0] = old


def cast_compute(*arrays):
    """Cast op inputs to the compute dtype (no-op when already matching)."""
    dt = get_compute_dtype()
    out = tuple(a.astype(dt) if a.dtype != dt else a for a in arrays)
    return out if len(out) > 1 else out[0]
