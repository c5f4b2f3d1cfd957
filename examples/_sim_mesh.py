"""Shared example bootstrap: ``import _sim_mesh`` FIRST in every example.

Defaults to the simulated 8-virtual-device CPU mesh (the ``local[N]``
analog) so every example runs anywhere.  Choosing a platform yourself —
``JAX_PLATFORMS=tpu python examples/lenet_mnist.py`` — leaves everything
untouched and the example runs on the real chips.
"""

import os

# both are read when jax is first imported / first initializes a backend
if os.environ.setdefault("JAX_PLATFORMS", "cpu") == "cpu":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")


def _on(v: str) -> bool:
    return v.strip().lower() not in ("", "0", "false", "no")


def tiny() -> bool:
    """CI tiny-size mode: ``BIGDL_TPU_EXAMPLES_TINY=1`` shrinks every
    example's epochs/steps/data so the whole set runs in minutes (the
    reference's nightly example runs, SURVEY.md §5, scaled for CI)."""
    return _on(os.environ.get("BIGDL_TPU_EXAMPLES_TINY", ""))


def tiny_int(normal: int, small: int) -> int:
    return small if tiny() else normal
