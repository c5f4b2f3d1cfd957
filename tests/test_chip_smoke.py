"""The chip path must not hide the device (ISSUE 22): ``chip_smoke.py``
refuses a box with no TPU before it builds anything, the compile cache has
one owner that the environment can place, and a backend that fails to
initialize — or a TPU nobody has a peak for — is an error, not "CPU".

No model is compiled here: every spec is seconds, not minutes.
"""

import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_a_box_without_a_tpu(tmp_path):
    """Under JAX_PLATFORMS=cpu: non-zero, no phase started, no pass line —
    from any working directory."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(tmp_path),
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "phase=" not in out.stdout          # nothing was built
    assert '"ok"' not in out.stdout
    assert "not a TPU" in out.stderr


def test_compile_cache_has_one_owner(monkeypatch, tmp_path):
    from bigdl_tpu.runtime.engine import enable_compile_cache

    # unset: <checkout>/.jax_cache, whatever the cwd
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache")
    # set from outside: JAX has read it; the code sets nothing
    jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    try:
        assert enable_compile_cache() == str(tmp_path / "cc")
        assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"
    finally:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))


def test_cache_dir_is_set_in_exactly_one_place():
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("tests", "__pycache__")]
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    src = fh.read()
                if '"jax_compilation_cache_dir"' in src \
                        or "JAX_COMPILATION_CACHE_DIR\"] =" in src \
                        or 'setdefault("JAX_COMPILATION_CACHE_DIR"' in src:
                    hits.append(os.path.relpath(os.path.join(root, f),
                                                REPO))
    assert hits == [os.path.join("bigdl_tpu", "runtime", "engine.py")]


def test_backend_error_is_not_read_as_not_a_tpu(monkeypatch):
    from bigdl_tpu.ops import common
    from bigdl_tpu.tensor import policy

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    common.on_tpu.cache_clear()
    monkeypatch.setattr(jax, "devices", no_backend)
    try:
        with pytest.raises(RuntimeError, match="initialize backend"):
            common.on_tpu()
        with pytest.raises(RuntimeError, match="initialize backend"):
            common.default_interpret()
        with pytest.raises(RuntimeError, match="initialize backend"):
            policy._platform_default()
        # an explicit choice never asks the backend
        assert common.default_interpret(True) is True
    finally:
        monkeypatch.undo()
        common.on_tpu.cache_clear()
    assert common.on_tpu() is False            # the real (CPU) answer again


def test_peak_table_is_exact_and_unknown_tpu_raises():
    from bigdl_tpu.obs.cost import peak_flops

    assert peak_flops("TPU v5 lite") == 197e12
    assert peak_flops("cpu") is None           # test meshes: no gauge
    with pytest.raises(ValueError, match="TPU v5 mega"):
        peak_flops("TPU v5 mega")              # no substring guess (v5p)


def test_one_chip_holder_per_host():
    from bigdl_tpu.runtime.engine import require_one_chip_holder

    require_one_chip_holder(1, {})
    require_one_chip_holder(4, {"JAX_PLATFORMS": "cpu"})
    with pytest.raises(RuntimeError, match="claim every local"):
        require_one_chip_holder(2, {})
    with pytest.raises(RuntimeError, match="claim every local"):
        require_one_chip_holder(2, {"JAX_PLATFORMS": "tpu"})


def test_block_sparse_selector_keeps_untileable_blocks_off_mosaic(
        monkeypatch):
    """On TPU a (8, 8)-block layer must take the masked-dense path: Mosaic
    refuses blocks that are not multiples of 128."""
    import numpy as np

    from bigdl_tpu.ops import block_sparse as bs

    assert bs.mosaic_tileable(128, 256)
    assert not bs.mosaic_tileable(64, 64)
    calls = []
    monkeypatch.setattr(bs, "default_interpret", lambda interpret=None: False)
    monkeypatch.setattr(
        bs, "block_sparse_matmul",
        lambda x, w, mask, **kw: calls.append(kw) or x @ w)
    for block, n_calls in (((8, 8), 0), ((128, 128), 1)):
        layer = bs.BlockSparseLinear(256, 256, block_shape=block)
        v = layer.init(jax.random.PRNGKey(0), np.zeros((2, 256), np.float32))
        mask = np.ones_like(layer.mask)
        mask[0, 0] = False
        layer.set_mask(mask)
        layer.apply(v, np.ones((2, 256), np.float32))
        assert len(calls) == n_calls, (block, calls)
