"""Test bootstrap: simulate an 8-device TPU mesh on CPU.

This is the analog of the reference's ``local[N]`` / local-cluster Spark tests
(SURVEY.md §5): distribution is exercised for real (XLA collectives run) inside
one process with 8 virtual devices.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)
# Golden-parity tests need exact f32 matmuls; production keeps the fast
# TPU-native default (bf16 passes on MXU).
jax.config.update("jax_default_matmul_precision", "highest")
# Hermetic: Engine/InferenceModel point the persistent compile cache at the
# checkout's .jax_cache (runtime.engine.enable_compile_cache); the suite
# neither reads nor fills it.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from bigdl_tpu.runtime.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec())


@pytest.fixture(autouse=True)
def _reset_engine():
    yield
    from bigdl_tpu.runtime.engine import Engine

    Engine.reset()
