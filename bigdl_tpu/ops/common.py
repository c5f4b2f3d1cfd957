"""Shared helpers for the Pallas kernel layer."""

import functools

import jax


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    """Whether the default backend is a TPU.  A backend that fails to
    initialize raises here (and is not cached): "no backend" must never
    read as "not a TPU" — that would quietly put every kernel in interpret
    mode and every matmul in float32."""
    return jax.devices()[0].platform == "tpu"


def default_interpret(interpret=None) -> bool:
    """Kernels compile with Mosaic on TPU, interpret everywhere else so the
    same code path is exercised by the CPU-simulated-mesh test suite."""
    if interpret is None:
        return not on_tpu()
    return bool(interpret)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
