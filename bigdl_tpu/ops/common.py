"""Shared helpers for the Pallas kernel layer."""

import functools

import jax

# The attention kernels' forward residuals, named inside their custom VJPs'
# forward rules (``ops/flash_attention.py``, ``ops/sparse_attention.py``):
# a ``jax.checkpoint`` whose policy saves these names hands the forward
# kernel's results to the backward instead of running the kernel again.
# Outside such a checkpoint a name is the identity.
ATTN_OUT = "attn_out"
ATTN_LSE = "attn_lse"


def layer_remat_policy(*names):
    """The policy of a decoder layer's ``jax.checkpoint``: keep the
    attention kernels' ``out`` and ``lse`` and any of ``names`` the layer
    holds; recompute everything else from the layer's input.  A layer
    that names none of them keeps only its input."""
    return jax.checkpoint_policies.save_only_these_names(ATTN_OUT, ATTN_LSE,
                                                         *names)


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
