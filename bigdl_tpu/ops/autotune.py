"""Kernel tile autotuner — searchable tile spaces over the ``automl``
Searchers (docs/performance.md §Kernel autotuning).

KERNELS_r04 showed the flagship Pallas kernels running at ~1.0x XLA: the
hand-picked ``block_q``/``block_k``/``block_rows`` tiles were guessed, not
searched.  TVM (PAPERS.md: arXiv 1802.04799) is the precedent — treat op
scheduling as a search problem.  Here each kernel declares a discrete tile
space; trials time the REAL kernel on synthetic inputs of the caller's
shape (median wall over ``block_until_ready`` repeats, compile excluded by
a warm call) driven by the existing :mod:`bigdl_tpu.automl.search`
machinery — :class:`GridSearcher` when the space is small enough to
enumerate, :class:`TPESearcher` above that — and the winner is cached on
disk keyed by ``(device_kind, kernel, shape-bucket, dtype)``.

Guarantees:

- **Never slower than the defaults**: the default tiles are always
  measured under the same protocol, and the tuner returns them unless a
  candidate beat them.  A config Mosaic rejects (bad tiling, VMEM OOM)
  scores ``inf`` via the Searcher's failure handling and cannot win.
- **Cache-hit determinism**: a second process with the same key loads the
  winner from disk and runs ZERO timing trials.
- **Explicit kwargs win**: ``flash_attention(..., block_q=256)`` bypasses
  the cache entirely for that axis.

Resolution order at kernel call time (``resolve``): explicit kwarg >
cached winner > registry default (for flash attention a rule on the shape
the call sees, ``KernelSpec.default_rule``).  Online tuning (measure on first miss)
only ever happens on CONCRETE arrays — inside a ``jit`` trace the kernel
sees tracers and falls back to cache/defaults, so the offline CLI is how
the training path gets tuned tiles::

    python -m bigdl_tpu.ops.autotune                 # tune all kernels
    python -m bigdl_tpu.ops.autotune --kernel flash_attention_fwd \
        --small --trials 8

Knobs: ``BIGDL_TPU_AUTOTUNE`` = ``0``/``off`` (defaults only), ``cache``
(consult the cache, never measure — the default), ``1``/``online``
(measure-and-cache on miss, eager calls only).  The env var is read at
call time by this module (its single owner);
``EngineConfig.kernel_autotune`` is the in-process fallback when the env
var is unset.  ``BIGDL_TPU_AUTOTUNE_CACHE`` overrides the cache directory
(default ``.autotune_cache`` in the checkout, beside the package — tiles
tuned on one machine never follow the user's home directory onto another).
"""

import argparse
import dataclasses
import functools
import json
import os
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from bigdl_tpu.automl import hp as hp_mod
from bigdl_tpu.automl.search import GridSearcher, TPESearcher
from bigdl_tpu.ops.flash_attention import BLOCK_CHOICES, default_blocks
from bigdl_tpu.utils.log import get_logger

log = get_logger(__name__)

# grid spaces at or under this many points enumerate exhaustively; larger
# spaces sample with TPE under the trial budget
GRID_LIMIT = 16
DEFAULT_TRIALS = 12
DEFAULT_REPEATS = 10


def _metrics():
    from bigdl_tpu.optim.metrics import global_metrics

    return global_metrics()


# ---------------------------------------------------------------------------
# mode / cache-dir resolution
# ---------------------------------------------------------------------------

def autotune_mode() -> str:
    """``off`` | ``cache`` | ``online``.  Env var wins; the Engine's
    ``kernel_autotune`` config is the in-process fallback; default is
    ``cache`` (a populated cache is consulted, nothing is ever measured
    behind the caller's back)."""
    raw = os.environ.get("BIGDL_TPU_AUTOTUNE")
    if raw is None:
        try:
            from bigdl_tpu.runtime.engine import Engine

            if Engine._instance is not None:
                raw = Engine._instance.config.kernel_autotune
        except Exception:  # pragma: no cover — engine import cycles
            raw = None
    if raw is None:
        return "cache"
    raw = str(raw).strip().lower()
    if raw in ("0", "off", "false", "none"):
        return "off"
    if raw in ("1", "online", "tune", "true"):
        return "online"
    return "cache"


def cache_dir() -> str:
    from bigdl_tpu.runtime.engine import CHECKOUT

    return os.environ.get("BIGDL_TPU_AUTOTUNE_CACHE") or os.path.join(
        CHECKOUT, ".autotune_cache")


def device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def is_concrete(*arrays) -> bool:
    """True when no argument is a tracer — i.e. we are NOT inside a jit
    trace and may legally run timing trials right now."""
    import jax

    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


# ---------------------------------------------------------------------------
# disk cache
# ---------------------------------------------------------------------------

class AutotuneCache:
    """One JSON file of ``key -> {tiles, best_ms, default_ms, trials}``.

    Reads are memoized; writes are read-merge-replace under a lock with an
    atomic rename, so concurrent tuners on one host lose at most their own
    last write, never the file."""

    def __init__(self, directory: Optional[str] = None):
        self.dir = directory or cache_dir()
        self.path = os.path.join(self.dir, "tiles.json")
        self._mem: Optional[Dict[str, Any]] = None
        self._lock = threading.Lock()

    def _load(self) -> Dict[str, Any]:
        if self._mem is None:
            try:
                with open(self.path) as f:
                    self._mem = json.load(f)
            except (OSError, json.JSONDecodeError):
                self._mem = {}
        return self._mem

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        return self._load().get(key)

    def put(self, key: str, entry: Dict[str, Any]) -> None:
        with self._lock:
            # merge-on-write: pick up entries other processes landed since
            # our last read, then replace atomically
            try:
                with open(self.path) as f:
                    disk = json.load(f)
            except (OSError, json.JSONDecodeError):
                disk = {}
            disk[key] = entry
            os.makedirs(self.dir, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(disk, f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            finally:
                if os.path.exists(tmp):  # pragma: no cover — replace raced
                    os.unlink(tmp)
            self._mem = disk


_cache: Optional[AutotuneCache] = None
_cache_lock = threading.Lock()


def get_cache() -> AutotuneCache:
    global _cache
    with _cache_lock:
        if _cache is None or _cache.dir != cache_dir():
            _cache = AutotuneCache()
        return _cache


def reset_cache() -> None:
    """Drop the in-memory cache handle (tests; env-var redirects)."""
    global _cache
    with _cache_lock:
        _cache = None


# ---------------------------------------------------------------------------
# kernel registry
# ---------------------------------------------------------------------------

def _pow2_bucket(n: int) -> int:
    """Round up to a power of two so nearby shapes share one cache entry
    (tile choice is driven by tiling granularity, not exact size)."""
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass
class KernelSpec:
    """One tunable kernel: its tile space, defaults, and a builder that
    turns ``(shape_key, config)`` into a timable thunk on synthetic
    inputs."""

    name: str
    space: Dict[str, hp_mod.Sampler]
    defaults: Dict[str, int]
    # (shape_key) -> (config -> zero-arg jitted thunk)
    builder: Callable[[Tuple], Callable[[Dict[str, int]], Callable[[], Any]]]
    # shape_key tuple -> the SAME bucketed key string the kernel computes
    # at call time — tune()/the CLI key cache entries through this, so an
    # offline-tuned winner is exactly what flash_attention/fused_layernorm/
    # int8_matmul/block_sparse_matmul look up
    key_fn: Callable[[Tuple], str] = None
    # CLI bench shapes: {label: shape_key}; "small" labels run under --small
    bench_shapes: Dict[str, Tuple] = dataclasses.field(default_factory=dict)
    # defaults that follow the shape: (shape_key) -> tiles.  Where set, the
    # fixed ``defaults`` above are only the answer when no shape is known
    default_rule: Optional[Callable[[Tuple], Dict[str, int]]] = None

    def defaults_for(self, shape_key: Optional[Tuple] = None
                     ) -> Dict[str, int]:
        """The untuned tiles at ``shape_key``: the rule's pick where the
        kernel has one, else the fixed defaults."""
        if self.default_rule is None or shape_key is None:
            return dict(self.defaults)
        return dict(self.default_rule(tuple(shape_key)))


def _flash_inputs(shape_key):
    import jax.numpy as jnp

    b, h, s, d, dtype = shape_key
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, h, s, d), dtype)
    k = jnp.asarray(rs.randn(b, h, s, d), dtype)
    v = jnp.asarray(rs.randn(b, h, s, d), dtype)
    return q, k, v


def _flash_rule(direction):
    """``flash_attention``'s block rule (``ops.flash_attention.
    default_blocks``) at a ``(b, h, s, d, dtype)`` bench shape."""
    def rule(shape_key):
        _, _, s, d, dtype = shape_key
        return default_blocks(direction, s, s, d, np.dtype(dtype).itemsize)

    return rule


def _flash_fwd_builder(shape_key):
    import jax

    from bigdl_tpu.ops.flash_attention import flash_attention

    q, k, v = _flash_inputs(shape_key)

    def make(cfg):
        return jax.jit(lambda: flash_attention(
            q, k, v, causal=True, block_q=cfg["block_q"],
            block_k=cfg["block_k"]))

    return make


def _flash_bwd_builder(shape_key):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.flash_attention import flash_attention

    q, k, v = _flash_inputs(shape_key)

    def make(cfg):
        def loss(qq):
            # an explicit block_q is the backward pair's too (and the
            # forward's, whose block_k stays its own default)
            return flash_attention(
                qq, k, v, causal=True, block_q=cfg["block_q"],
                block_k_bwd=cfg["block_k"]).astype(jnp.float32).sum()

        return jax.jit(lambda: jax.grad(loss)(q))

    return make


def _flash_decode_builder(shape_key):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.flash_attention import paged_decode_attention

    S, h, page, d, nb, dtype = shape_key
    rs = np.random.RandomState(0)
    P = S * nb
    q = jnp.asarray(rs.randn(S, h, d), dtype)
    kp = jnp.asarray(rs.randn(P, h, page, d), dtype)
    vp = jnp.asarray(rs.randn(P, h, page, d), dtype)
    pt = jnp.asarray(rs.permutation(P)[: S * nb].reshape(S, nb), jnp.int32)
    lengths = jnp.asarray(rs.randint(0, nb * page, (S,)), jnp.int32)

    def make(cfg):
        if h % cfg["block_h"] != 0:
            raise ValueError(f"block_h {cfg['block_h']} does not divide "
                             f"heads {h}")
        return jax.jit(lambda: paged_decode_attention(
            q, kp, vp, pt, lengths, block_h=cfg["block_h"]))

    return make


def _ln_builder(shape_key):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.fused import fused_layernorm

    rows, cols, dtype = shape_key
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(rows, cols), dtype)
    g = jnp.asarray(rs.randn(cols), jnp.float32)
    b = jnp.asarray(rs.randn(cols), jnp.float32)

    def make(cfg):
        return jax.jit(lambda: fused_layernorm(
            x, g, b, block_rows=cfg["block_rows"]))

    return make


def _int8_builder(shape_key):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.quantized import int8_matmul

    m, k, n = shape_key
    rs = np.random.RandomState(0)
    a = jnp.asarray(rs.randint(-127, 128, (m, k)), jnp.int8)
    w = jnp.asarray(rs.randint(-127, 128, (k, n)), jnp.int8)

    def make(cfg):
        return jax.jit(lambda: int8_matmul(
            a, w, block_m=cfg["block_m"], block_n=cfg["block_n"],
            block_k=cfg["block_k"]))

    return make


def _bs_builder(shape_key):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.block_sparse import block_sparse_matmul
    from bigdl_tpu.ops.common import cdiv

    m, k, n, bk, bn, dtype = shape_key
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(m, k), dtype)
    w = jnp.asarray(rs.randn(k, n), dtype)
    # half-density mask: the regime where block skipping starts to pay
    mask = rs.rand(cdiv(k, bk), cdiv(n, bn)) < 0.5
    mask[0, :] = True  # no empty columns in the bench mask

    def make(cfg):
        return jax.jit(lambda: block_sparse_matmul(
            x, w, mask, block_k=bk, block_n=bn, block_m=cfg["block_m"]))

    return make


_TILE_CHOICES = [64, 128, 256, 512]
# what flash_attention's block rule chooses among: the spaces hold its picks
_FLASH_BLOCKS = list(BLOCK_CHOICES)

REGISTRY: Dict[str, KernelSpec] = {
    "flash_attention_fwd": KernelSpec(
        name="flash_attention_fwd",
        space={"block_q": hp_mod.choice(_FLASH_BLOCKS),
               "block_k": hp_mod.choice(_FLASH_BLOCKS)},
        defaults={"block_q": 128, "block_k": 128},
        default_rule=_flash_rule("fwd"),
        builder=_flash_fwd_builder,
        key_fn=lambda sk: attention_key(sk[:4], sk[2], sk[4]),
        bench_shapes={
            "small": (1, 2, 256, 64, "bfloat16"),
            "lm_2k": (4, 8, 2048, 128, "bfloat16"),
        }),
    "flash_attention_bwd": KernelSpec(
        name="flash_attention_bwd",
        space={"block_q": hp_mod.choice(_FLASH_BLOCKS),
               "block_k": hp_mod.choice(_FLASH_BLOCKS)},
        defaults={"block_q": 128, "block_k": 128},
        default_rule=_flash_rule("bwd"),
        builder=_flash_bwd_builder,
        key_fn=lambda sk: attention_key(sk[:4], sk[2], sk[4]),
        bench_shapes={
            "small": (1, 2, 256, 64, "bfloat16"),
            "lm_2k": (4, 8, 2048, 128, "bfloat16"),
        }),
    "flash_attention_decode": KernelSpec(
        name="flash_attention_decode",
        space={"block_h": hp_mod.choice([1, 2, 4, 8])},
        defaults={"block_h": 4},
        builder=_flash_decode_builder,
        key_fn=lambda sk: decode_attention_key(sk[0], sk[1], sk[2],
                                               sk[3], sk[4], sk[5]),
        bench_shapes={
            "small": (8, 4, 8, 32, 4, "float32"),
            "serve_8x8": (16, 8, 16, 64, 8, "bfloat16"),
        }),
    "fused_layernorm": KernelSpec(
        name="fused_layernorm",
        space={"block_rows": hp_mod.choice([64, 128, 256, 512, 1024])},
        defaults={"block_rows": 256},
        builder=_ln_builder,
        key_fn=lambda sk: rows_key(sk[0], sk[1], sk[2]),
        bench_shapes={
            "small": (512, 256, "float32"),
            "lm_act": (8192, 1024, "float32"),
        }),
    "int8_matmul": KernelSpec(
        name="int8_matmul",
        space={"block_m": hp_mod.choice(_TILE_CHOICES),
               "block_n": hp_mod.choice(_TILE_CHOICES),
               "block_k": hp_mod.choice([128, 256, 512, 1024])},
        defaults={"block_m": 256, "block_n": 256, "block_k": 512},
        builder=_int8_builder,
        key_fn=lambda sk: matmul_key(sk[0], sk[1], sk[2], "int8"),
        bench_shapes={
            "small": (256, 512, 256),
            "gemm_1k": (1024, 2048, 1024),
        }),
    "block_sparse_matmul": KernelSpec(
        name="block_sparse_matmul",
        space={"block_m": hp_mod.choice(_TILE_CHOICES)},
        defaults={"block_m": 128},
        builder=_bs_builder,
        key_fn=lambda sk: block_sparse_key(sk[0], sk[1], sk[2], sk[3],
                                           sk[4], sk[5]),
        bench_shapes={
            "small": (128, 128, 256, 32, 32, "float32"),
            "ffn_gpt2s": (4096, 768, 3072, 64, 64, "bfloat16"),
        }),
}


def canonical_key(kernel: str, shape_key: Tuple,
                  kind: Optional[str] = None) -> str:
    """THE cache key for one (kernel, concrete shape): the registry's
    ``key_fn`` bucketing under the device kind — identical to what the
    kernel computes at call time, so tune()/CLI winners are exactly what
    call-time resolution finds."""
    return full_key(kernel, REGISTRY[kernel].key_fn(tuple(shape_key)),
                    kind=kind)


# -- shape-bucket keys (one per kernel family) ------------------------------

def _dtype_name(dtype) -> str:
    return np.dtype(dtype).name if not hasattr(dtype, "name") else dtype.name


def attention_key(q_shape, kv_len: int, dtype, d_v=None) -> str:
    """``d_v``: the width of v where it is not ``d`` (q's and k's)."""
    b, h, s, d = q_shape
    width = f"d{d}" if d_v in (None, d) else f"d{d}v{d_v}"
    return (f"bh{_pow2_bucket(b * h)}_q{_pow2_bucket(s)}"
            f"_k{_pow2_bucket(kv_len)}_{width}_{_dtype_name(dtype)}")


def decode_attention_key(slots: int, heads: int, page: int, hd: int,
                         n_blocks: int, dtype) -> str:
    return (f"s{_pow2_bucket(slots)}_h{heads}_p{page}_d{hd}"
            f"_nb{_pow2_bucket(n_blocks)}_{_dtype_name(dtype)}")


def rows_key(rows: int, cols: int, dtype) -> str:
    return f"r{_pow2_bucket(rows)}_c{cols}_{_dtype_name(dtype)}"


def matmul_key(m: int, k: int, n: int, dtype) -> str:
    return f"m{_pow2_bucket(m)}_k{k}_n{n}_{_dtype_name(dtype)}"


def block_sparse_key(m: int, k: int, n: int, bk: int, bn: int,
                     dtype) -> str:
    return (f"m{_pow2_bucket(m)}_k{k}_n{n}_bk{bk}_bn{bn}"
            f"_{_dtype_name(dtype)}")


def full_key(kernel: str, shape_key: str, kind: Optional[str] = None) -> str:
    return f"{kind or device_kind()}|{kernel}|{shape_key}"


# ---------------------------------------------------------------------------
# measurement + search
# ---------------------------------------------------------------------------

def _measure_ms(thunk: Callable[[], Any],
                repeats: int = DEFAULT_REPEATS) -> float:
    """Median wall time of ``thunk`` over ``repeats`` (compile excluded by
    one warm call).  Module-level on purpose: tests monkeypatch it to
    count trials and to make timing deterministic."""
    import jax

    jax.block_until_ready(thunk())  # warm (compile)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def _space_size(space: Dict[str, hp_mod.Sampler]) -> int:
    total = 1
    for v in space.values():
        g = v.grid()
        total *= len(g) if g else GRID_LIMIT + 1
    return total


def tune(kernel: str, shape_key: Tuple, *, key: Optional[str] = None,
         n_trials: int = DEFAULT_TRIALS, repeats: int = DEFAULT_REPEATS,
         cache: Optional[AutotuneCache] = None,
         write_cache: bool = True) -> Dict[str, Any]:
    """Search ``kernel``'s tile space at ``shape_key`` and cache the
    winner.  Returns the cache entry ``{tiles, best_ms, default_ms,
    trials, winner}``.  The default config is timed under the SAME
    protocol and wins ties/regressions — the tuner may return the default,
    it may not regress from it."""
    spec = REGISTRY[kernel]
    make = spec.builder(tuple(shape_key))
    key = key or canonical_key(kernel, shape_key)
    trials = {"n": 0}

    def trial_fn(cfg):
        cfg = {k: v for k, v in cfg.items() if not k.startswith("_")}
        trials["n"] += 1
        _metrics().inc("ops.autotune_trials")
        return _measure_ms(make(cfg), repeats=repeats)

    defaults = spec.defaults_for(shape_key)
    default_ms = trial_fn(dict(defaults))
    if _space_size(spec.space) <= max(GRID_LIMIT, n_trials):
        searcher = GridSearcher(mode="min")
        n = 0  # grid: exhaust the space
    else:
        searcher = TPESearcher(mode="min", seed=0)
        n = n_trials
    best = searcher.run(trial_fn, dict(spec.space), n_sampling=n)
    if best.error is None and best.metric < default_ms:
        tiles, best_ms, winner = dict(best.config), best.metric, "searched"
    else:  # the guarantee: never slower than the hand-picked defaults
        tiles, best_ms, winner = dict(defaults), default_ms, "default"
    tiles = {k: v for k, v in tiles.items() if not k.startswith("_")}
    entry = {"tiles": tiles, "best_ms": round(best_ms, 4),
             "default_ms": round(default_ms, 4), "trials": trials["n"],
             "winner": winner}
    if write_cache:
        (cache or get_cache()).put(key, entry)
    log.info("autotune %s %s: %s %s (%.3f ms vs default %.3f ms, "
             "%d trials)", kernel, key, winner, tiles, best_ms, default_ms,
             trials["n"])
    return entry


def _shape_label(shape_key: Tuple) -> str:
    return "x".join(str(d) for d in shape_key)


# ---------------------------------------------------------------------------
# call-time resolution (the kernels' entry point)
# ---------------------------------------------------------------------------

def resolve(kernel: str, shape_key: str,
            explicit: Optional[Dict[str, Optional[int]]] = None,
            online_shape: Optional[Tuple] = None,
            defaults: Optional[Dict[str, int]] = None) -> Dict[str, int]:
    """Tiles for one kernel call.  Per axis: explicit kwarg (not None) >
    cached winner > default.  ``defaults`` is the caller's pick for the
    shape it sees (a kernel whose registry default is a rule passes the
    rule's answer); without it the registry's fixed defaults apply.  In
    ``online`` mode a cache miss with a concrete ``online_shape`` triggers
    a tuning run first (eager calls only — the kernels never pass
    ``online_shape`` from a trace)."""
    spec = REGISTRY[kernel]
    tiles = dict(spec.defaults if defaults is None else defaults)
    explicit = {k: v for k, v in (explicit or {}).items() if v is not None}
    mode = autotune_mode()
    if mode != "off" and len(explicit) < len(tiles):
        key = full_key(kernel, shape_key)
        entry = get_cache().get(key)
        if entry is None and mode == "online" and online_shape is not None:
            try:
                entry = tune(kernel, online_shape, key=key)
            except Exception as e:  # noqa: BLE001 — tuning must not break
                log.warning("online autotune of %s failed (%s); using "
                            "defaults", kernel, e)
                entry = None
        if entry is not None:
            _metrics().inc("ops.autotune_cache_hits")
            cached = entry.get("tiles", {})
            for k in tiles:
                v = cached.get(k)
                if isinstance(v, (int, float)) and v > 0:
                    tiles[k] = int(v)
        else:
            _metrics().inc("ops.autotune_cache_misses")
    tiles.update({k: int(v) for k, v in explicit.items()})
    return tiles


# ---------------------------------------------------------------------------
# offline CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="bigdl_tpu.ops.autotune",
        description="offline kernel tile tuner (docs/performance.md "
                    "§Kernel autotuning); winners land in the shared "
                    "on-disk cache that flash_attention/fused_layernorm/"
                    "int8_matmul/block_sparse_matmul consult at call time")
    ap.add_argument("--kernel", action="append", default=None,
                    help="kernel(s) to tune (default: all registered)")
    ap.add_argument("--trials", type=int, default=DEFAULT_TRIALS,
                    help="trial budget for TPE spaces (grids enumerate)")
    ap.add_argument("--repeats", type=int, default=DEFAULT_REPEATS,
                    help="timing repeats per trial (median)")
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes only (CPU/CI smoke)")
    ap.add_argument("--cache-dir", default=None,
                    help="override BIGDL_TPU_AUTOTUNE_CACHE")
    args = ap.parse_args(argv)

    if args.cache_dir:
        os.environ["BIGDL_TPU_AUTOTUNE_CACHE"] = args.cache_dir
        reset_cache()
    names = args.kernel or list(REGISTRY)
    rc = 0
    for name in names:
        if name not in REGISTRY:
            print(json.dumps({"kernel": name, "error": "unknown kernel",
                              "known": sorted(REGISTRY)}))
            rc = 1
            continue
        spec = REGISTRY[name]
        shapes = {k: v for k, v in spec.bench_shapes.items()
                  if (k == "small") == bool(args.small)} or spec.bench_shapes
        for label, shape_key in shapes.items():
            key = canonical_key(name, shape_key)
            try:
                entry = tune(name, shape_key, key=key,
                             n_trials=args.trials, repeats=args.repeats)
                print(json.dumps(dict(entry, kernel=name, shape=label,
                                      key=key)), flush=True)
            except Exception as e:  # noqa: BLE001 — keep tuning the rest
                print(json.dumps({"kernel": name, "shape": label,
                                  "error": f"{type(e).__name__}: "
                                           f"{str(e)[:300]}"}), flush=True)
                rc = 1
    print(json.dumps({"cache": get_cache().path, "mode": autotune_mode()}))
    return rc


if __name__ == "__main__":
    sys.exit(main())
