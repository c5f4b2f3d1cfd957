"""Block-sparse matmul + BlockSparseLinear + magnitude block pruning.

Reference analog: none — the reference has no sparse compute path at all.
This is the BLaST-style block-sparse FFN (PAPERS.md: arXiv 2507.03117)
for transformer pretraining and inference: weights are pruned in
``(block_k, block_n)`` tiles after a dense warmup, and the forward matmul
SKIPS pruned blocks entirely instead of multiplying by zeros.

Kernel: ``x (M,K) @ (W ⊙ mask) (K,N)`` with a host-side block mask of
shape ``(ceil(K/bk), ceil(N/bn))``.  The grid is ``(M/bm, N/bn,
max_nnz_per_column)`` and a scalar-prefetched per-column index map
(``pltpu.PrefetchScalarGridSpec``) walks ONLY the nonzero k-blocks of
each output column — compute and k/v HBM traffic scale with the nonzero
block count, not with K.  Columns with fewer nonzero blocks than the
widest column idle via ``pl.when`` on the prefetched per-column count.
``interpret=True`` runs the identical code path on CPU, so tier-1
exercises the real kernel.

The mask is STATIC per compiled program (a hashable host array): pruning
events between training segments retrace — the BLaST schedule prunes a
handful of times per run, and each new mask announces itself via
``obs.attr.expected_compile`` so the recompile sentinel stays quiet.

Backward: ``dx`` reuses the block-sparse kernel on the transposed
problem (same skipping, mask transposed); ``dw`` is a dense XLA matmul
masked on the way out (weight-grad sparsity is future work — it needs an
output-block-skipping variant).
"""

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.nn.layers import Linear
from bigdl_tpu.nn.module import EMPTY, Module
from bigdl_tpu.ops.common import cdiv, default_interpret, round_up
from bigdl_tpu.utils.log import get_logger

log = get_logger(__name__)


class StaticMask:
    """Hashable wrapper around a host bool block mask so it can ride as a
    ``custom_vjp`` nondiff / jit-static argument: two masks with equal
    bytes hash equal, so retraces happen exactly when the mask changes."""

    __slots__ = ("arr", "_hash")

    def __init__(self, arr):
        self.arr = np.ascontiguousarray(np.asarray(arr, bool))
        self._hash = hash((self.arr.shape, self.arr.tobytes()))

    @property
    def shape(self):
        return self.arr.shape

    def density(self) -> float:
        return float(self.arr.mean()) if self.arr.size else 1.0

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return (isinstance(other, StaticMask)
                and self.arr.shape == other.arr.shape
                and bool(np.array_equal(self.arr, other.arr)))

    def __repr__(self):
        return (f"StaticMask({self.arr.shape}, "
                f"density={self.density():.3f})")


def expand_mask(mask, k: int, n: int, block_k: int,
                block_n: int) -> np.ndarray:
    """Block mask -> elementwise (k, n) mask (the dense-reference view)."""
    arr = mask.arr if isinstance(mask, StaticMask) else np.asarray(mask,
                                                                   bool)
    full = np.repeat(np.repeat(arr, block_k, 0), block_n, 1)
    return full[:k, :n]


def _column_plan(arr: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per output-column-block: nonzero k-block count + padded index list.
    Padding indices point at block 0 but never execute (``pl.when`` on the
    count)."""
    nkb, nnb = arr.shape
    counts = arr.sum(0).astype(np.int32)
    maxc = max(1, int(counts.max()) if counts.size else 1)
    idx = np.zeros((nnb, maxc), np.int32)
    for j in range(nnb):
        nz = np.nonzero(arr[:, j])[0]
        idx[j, : len(nz)] = nz
    return counts, idx


def mosaic_tileable(block_k: int, block_n: int) -> bool:
    """Whether Mosaic compiles the kernel for this weight-block shape.
    Its blocks are x ``(bm, block_k)`` and w ``(block_k, block_n)``, and
    the last two dims of a TPU block must be multiples of (8, 128) unless
    they span the array — so both must be multiples of 128 (smaller
    blocks are refused: "last two dimensions of your block shape are
    divisible by 8 and 128").  Interpret mode takes any shape."""
    return block_k % 128 == 0 and block_n % 128 == 0


def _bs_kernel(counts_ref, idx_ref, x_ref, w_ref, o_ref):
    j = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    @pl.when(t < counts_ref[j])
    def _step():
        o_ref[:] += jax.lax.dot_general(
            x_ref[:].astype(jnp.float32), w_ref[:].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _bs_matmul_raw(x, w, smask: StaticMask, block_m: int, block_k: int,
                   block_n: int, interpret: bool):
    """The kernel proper: x (M,K) @ (w ⊙ mask) (K,N) -> f32 (M,N)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    nkb, nnb = smask.shape
    if (nkb, nnb) != (cdiv(k, block_k), cdiv(n, block_n)):
        raise ValueError(
            f"mask {smask.shape} does not tile ({k}, {n}) in "
            f"({block_k}, {block_n}) blocks: want "
            f"({cdiv(k, block_k)}, {cdiv(n, block_n)})")
    bm = min(block_m, round_up(m, 8))
    mp = round_up(m, bm)
    kp, np_ = nkb * block_k, nnb * block_n
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    counts, idx = _column_plan(smask.arr)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(mp // bm, nnb, idx.shape[1]),
        in_specs=[
            pl.BlockSpec((bm, block_k),
                         lambda i, j, t, counts, idx: (i, idx[j, t])),
            pl.BlockSpec((block_k, block_n),
                         lambda i, j, t, counts, idx: (idx[j, t], j)),
        ],
        out_specs=pl.BlockSpec((bm, block_n),
                               lambda i, j, t, counts, idx: (i, j)),
    )
    out = pl.pallas_call(
        _bs_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(counts), jnp.asarray(idx), xp, wp)
    return out[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _bsmm(x, w, smask, block_m, block_k, block_n, interpret):
    out = _bs_matmul_raw(x, w, smask, block_m, block_k, block_n, interpret)
    return out.astype(x.dtype)


def _bsmm_fwd(x, w, smask, block_m, block_k, block_n, interpret):
    out = _bs_matmul_raw(x, w, smask, block_m, block_k, block_n, interpret)
    return out.astype(x.dtype), (x, w)


def _bsmm_bwd(smask, block_m, block_k, block_n, interpret, res, g):
    x, w = res
    k, n = w.shape
    # dx = g @ (w ⊙ mask)ᵀ — the transposed problem keeps the SAME block
    # skipping (mask transposed, block shape swapped)
    tmask = StaticMask(smask.arr.T)
    dx = _bs_matmul_raw(g.astype(jnp.float32), w.T.astype(jnp.float32),
                        tmask, block_m, block_n, block_k, interpret)
    # dw = (xᵀ g) ⊙ mask — dense XLA matmul, masked on the way out
    dw = jnp.matmul(x.T.astype(jnp.float32), g.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    em = jnp.asarray(expand_mask(smask, k, n, block_k, block_n))
    dw = jnp.where(em, dw, 0.0)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_bsmm.defvjp(_bsmm_fwd, _bsmm_bwd)


# rows of x per grid step where a call leaves ``block_m`` as None
BLOCK_M = 128


def block_sparse_matmul(x, w, mask, *, block_k: int, block_n: int,
                        block_m: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """``x (…, K) @ (w ⊙ mask) (K, N)`` skipping pruned weight blocks.

    ``mask`` is a HOST bool array ``(ceil(K/block_k), ceil(N/block_n))``
    (or a :class:`StaticMask`) — it must be concrete; a traced mask cannot
    drive the static index maps.  Differentiable (see module docstring for
    the backward split).  ``block_m=None`` takes :data:`BLOCK_M`;
    explicit wins."""
    if isinstance(mask, jax.core.Tracer):
        raise TypeError(
            "block_sparse_matmul needs a concrete (host) block mask — the "
            "sparsity pattern is static per compiled program")
    smask = mask if isinstance(mask, StaticMask) else StaticMask(mask)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    if block_m is None:
        block_m = BLOCK_M
    out = _bsmm(x2, w, smask, int(block_m), int(block_k), int(block_n),
                default_interpret(interpret))
    return out.reshape(*lead, w.shape[1])


# ---------------------------------------------------------------------------
# BlockSparseLinear module
# ---------------------------------------------------------------------------

class BlockSparseLinear(Linear):
    """Drop-in :class:`~bigdl_tpu.nn.layers.Linear` with a block-prunable
    weight (init/lazy-shape/bias semantics inherited).  Starts DENSE
    (all-ones mask = plain Linear forward, so the warmup phase pays
    nothing); after :meth:`set_mask` / :func:`prune_model_to_sparsity`
    the forward routes through the block-sparse Pallas kernel.

    The mask lives on the MODULE (host numpy), not in the params pytree —
    it is a static compile-time structure, not a trained tensor.  The
    Optimizer's checkpoint path persists masks automatically (driver
    state) and restores them on resume; for custom checkpointing use
    :func:`collect_masks` / :func:`apply_masks`."""

    def __init__(self, in_features: Optional[int] = None,
                 out_features: int = 0,
                 block_shape: Tuple[int, int] = (64, 64),
                 with_bias: bool = True, target_sparsity: float = 0.0,
                 use_kernel: bool = True, name=None, **linear_kwargs):
        super().__init__(in_features, out_features, with_bias=with_bias,
                         name=name, **linear_kwargs)
        self.block_shape = (int(block_shape[0]), int(block_shape[1]))
        # the pruning schedule's end state; the schedule/prune helpers
        # read it, the layer itself only ever applies self.mask
        self.target_sparsity = float(target_sparsity)
        # use_kernel=False routes a pruned mask through a masked DENSE
        # matmul instead of the Pallas kernel: identical math (the mask
        # zeroes the same blocks), no Pallas dispatch — the right trade
        # for the tiny hidden sizes of a speculative draft model on CPU,
        # where a grid launch per FFN costs more than the skipped FLOPs.
        # On TPU the forward also takes it for any block shape Mosaic
        # cannot tile (mosaic_tileable)
        self.use_kernel = bool(use_kernel)
        self.mask: Optional[np.ndarray] = None

    def build(self, rng, x):
        params, state = super().build(rng, x)
        fan_in = int(params["weight"].shape[0])
        self.in_features = fan_in
        bk, bn = self.block_shape
        if self.mask is None:
            self.mask = np.ones((cdiv(fan_in, bk),
                                 cdiv(self.out_features, bn)), bool)
        return params, state

    # -- mask management ----------------------------------------------------
    def set_mask(self, mask) -> None:
        arr = np.asarray(mask, bool)
        bk, bn = self.block_shape
        want = (cdiv(self.in_features or arr.shape[0] * bk, bk),
                cdiv(self.out_features, bn))
        if self.in_features is not None and arr.shape != want:
            raise ValueError(f"mask {arr.shape} != expected {want}")
        self.mask = arr

    def density(self) -> float:
        return float(self.mask.mean()) if self.mask is not None else 1.0

    def sparsity(self) -> float:
        return 1.0 - self.density()

    def prune_to(self, params: Dict[str, Any], sparsity: float) -> float:
        """Magnitude block pruning: keep the highest-L1 weight blocks so
        that ``1 - sparsity`` of ALL blocks survive.  Monotone — only
        currently-kept blocks are candidates, so a pruned block never
        resurrects (the BLaST schedule's invariant).  Returns the achieved
        sparsity."""
        if self.mask is None:
            raise RuntimeError("prune_to before build/init")
        bk, bn = self.block_shape
        w = np.asarray(jax.device_get(params["weight"]), np.float32)
        k, n = w.shape
        nkb, nnb = self.mask.shape
        wp = np.zeros((nkb * bk, nnb * bn), np.float32)
        wp[:k, :n] = np.abs(w)
        scores = wp.reshape(nkb, bk, nnb, bn).sum(axis=(1, 3))
        total = self.mask.size
        n_keep = max(1, int(round((1.0 - float(sparsity)) * total)))
        kept = int(self.mask.sum())
        if n_keep >= kept:
            return self.sparsity()  # already at or past this level
        flat = np.where(self.mask.ravel(), scores.ravel(), -np.inf)
        order = np.argsort(flat)[::-1]
        new = np.zeros(total, bool)
        new[order[:n_keep]] = True
        self.mask = new.reshape(self.mask.shape)
        return self.sparsity()

    def forward(self, params, state, x, training=False, rng=None):
        if self.mask is None or bool(self.mask.all()):
            # dense warmup: exactly Linear (math AND speed)
            return super().forward(params, state, x, training=training,
                                   rng=rng)
        from bigdl_tpu.tensor.policy import cast_compute

        xc, wc = cast_compute(x, params["weight"])
        if self.use_kernel and (default_interpret()
                                or mosaic_tileable(*self.block_shape)):
            y = block_sparse_matmul(
                xc, wc, self.mask, block_k=self.block_shape[0],
                block_n=self.block_shape[1]).astype(jnp.float32)
        else:
            k, n = int(wc.shape[0]), int(wc.shape[1])
            em = jnp.asarray(expand_mask(self.mask, k, n,
                                         self.block_shape[0],
                                         self.block_shape[1]))
            y = jnp.matmul(xc.astype(jnp.float32),
                           jnp.where(em, wc.astype(jnp.float32), 0.0),
                           preferred_element_type=jnp.float32)
        if self.with_bias:
            y = y + params["bias"]
        return y.astype(x.dtype), EMPTY


# ---------------------------------------------------------------------------
# model-level pruning helpers + schedule
# ---------------------------------------------------------------------------

def iter_sparse_modules(model):
    """Every :class:`BlockSparseLinear` in a module tree (depth-first,
    cycle-safe), as ``(path, module)`` pairs."""
    seen = set()

    def walk(mod, path):
        if id(mod) in seen or not isinstance(mod, Module):
            return
        seen.add(id(mod))
        if isinstance(mod, BlockSparseLinear):
            yield path, mod
        for k, v in vars(mod).items():
            children = v if isinstance(v, (list, tuple)) else [v]
            for i, c in enumerate(children):
                if isinstance(c, Module):
                    sub = f"{path}.{k}" if path else k
                    if isinstance(v, (list, tuple)):
                        sub = f"{sub}[{i}]"
                    yield from walk(c, sub)

    yield from walk(model, "")


def _capture_params(model, variables, sample_inputs) -> Dict[int, Any]:
    """EXACT module → params binding: every BlockSparseLinear's forward
    is wrapped to record the params dict it receives, then one real
    forward on the sample batch runs.  Container-layout agnostic (works
    for Sequential keys, keras graph nodes, Transformer's literal dict
    keys alike) — the captured dicts ARE the sub-dicts of ``variables``,
    passed down by reference."""
    captured: Dict[int, Any] = {}
    patched = []

    def _wrap(mod, orig):
        def fwd(params, state, *xs, **kw):
            captured[id(mod)] = params
            return orig(params, state, *xs, **kw)

        return fwd

    try:
        for _, m in iter_sparse_modules(model):
            m.forward = _wrap(m, m.forward)
            patched.append(m)
        model.apply(variables, *sample_inputs)
    finally:
        for m in patched:
            m.__dict__.pop("forward", None)
    return captured


def _params_by_tree_order(variables_params):
    """Fallback binding (no sample inputs): every {"weight": 2-D[,
    "bias"]} leaf dict in depth-first pytree order.  nn/ containers key
    params by child name so this order matches module iteration order for
    the stock layouts; a custom container interleaving a SAME-shaped
    dense Linear ahead of a sparse layer can fool it — pass
    ``sample_inputs`` for the exact capture-based binding instead."""
    found = []

    def walk(node):
        if isinstance(node, dict):
            if set(node) <= {"weight", "bias"} \
                    and getattr(node.get("weight"), "ndim", 0) == 2:
                found.append(node)
            else:
                for v in node.values():
                    walk(v)

    walk(variables_params)
    return found


def prune_model_to_sparsity(model, variables, sparsity: float,
                            sample_inputs: Optional[tuple] = None
                            ) -> Dict[str, float]:
    """One pruning EVENT: every :class:`BlockSparseLinear` whose
    ``target_sparsity`` allows it prunes to ``min(sparsity, target)`` by
    block magnitude.  Mutates module masks (host state); the caller is
    responsible for rebuilding/retracing its compiled step — wrap that
    rebuild in ``obs.attr.expected_compile()`` so the recompile sentinel
    stays quiet.  Returns ``{path: achieved_sparsity}``.

    ``sample_inputs`` (a tuple of sample batch arrays for
    ``model.apply``) enables the EXACT module→params binding via one
    forward pass; without it a tree-order shape-matching heuristic binds
    weights (correct for all stock nn/ layouts, see
    :func:`_params_by_tree_order`)."""
    out: Dict[str, float] = {}
    sparse = list(iter_sparse_modules(model))
    if not sparse:
        return out
    if sample_inputs is not None:
        captured = _capture_params(model, variables, tuple(sample_inputs))
        for path, mod in sparse:
            params = captured.get(id(mod))
            if params is None:
                log.warning("prune: %s never ran in the sample forward; "
                            "skipped", path)
                continue
            goal = min(float(sparsity),
                       mod.target_sparsity or float(sparsity))
            out[path] = mod.prune_to(params, goal)
        return out
    mats = _params_by_tree_order(variables.get("params", variables))
    used: set = set()
    for path, mod in sparse:
        want = ((mod.in_features, mod.out_features)
                if mod.in_features else None)
        params = None
        for i, cand in enumerate(mats):
            if i in used:
                continue
            shape = tuple(int(d) for d in cand["weight"].shape)
            if want is None or shape == want:
                params = cand
                used.add(i)
                break
        if params is None:
            log.warning("prune: no params found for %s; skipped (pass "
                        "sample_inputs for exact binding)", path)
            continue
        goal = min(float(sparsity), mod.target_sparsity or float(sparsity))
        out[path] = mod.prune_to(params, goal)
    return out


def derive_draft_masks(model, params, sparsity: float) -> Dict[str, float]:
    """Derive block masks for a SPECULATIVE DRAFT twin from a SERVED
    checkpoint (docs/serving.md §Speculative decoding): ``model`` is a
    freshly-constructed sparse twin of the target architecture (its
    :class:`BlockSparseLinear` layers carry ctor-known shapes but have
    never been built, so their masks are ``None``); ``params`` is the
    target's trained ``variables["params"]`` tree, which the twin
    consumes verbatim — weight sharing is the whole point.  Seeds every
    sparse layer with the all-ones mask its ``build`` would create, then
    runs one magnitude-pruning event to ``sparsity``.  Returns
    ``{path: achieved_sparsity}``."""
    for path, mod in iter_sparse_modules(model):
        if mod.mask is not None:
            continue
        if not mod.in_features or not mod.out_features:
            raise ValueError(
                f"derive_draft_masks: {path or 'layer'} has no ctor "
                "shapes — construct the draft twin with explicit "
                "in/out features (PositionwiseFFN does)")
        bk, bn = mod.block_shape
        mod.mask = np.ones((cdiv(mod.in_features, bk),
                            cdiv(mod.out_features, bn)), bool)
    return prune_model_to_sparsity(model, {"params": params},
                                   float(sparsity))


def collect_masks(model) -> Dict[str, Any]:
    """Serializable ``{path: mask-as-list}`` snapshot (checkpoint
    sidecar)."""
    return {path: mod.mask.tolist()
            for path, mod in iter_sparse_modules(model)
            if mod.mask is not None}


def apply_masks(model, masks: Dict[str, Any]) -> int:
    """Restore masks captured by :func:`collect_masks`.  Returns how many
    modules matched."""
    n = 0
    for path, mod in iter_sparse_modules(model):
        if path in masks:
            mod.set_mask(np.asarray(masks[path], bool))
            n += 1
    return n


class BlockPruningSchedule:
    """BLaST-style dense-warmup → gradual magnitude pruning.

    ``sparsity_at(step)`` is 0 through ``warmup_steps``, then ramps to
    ``target_sparsity`` over ``ramp_steps`` in ``n_events`` equal jumps
    (cubic ramp, the gradual-pruning standard: early events prune gently
    while the network can still heal).  Monotone non-decreasing by
    construction.  ``prune_steps()`` lists the exact steps where the mask
    changes — the driver/bench retraces only there."""

    def __init__(self, target_sparsity: float, warmup_steps: int,
                 ramp_steps: int, n_events: int = 4):
        if not 0.0 <= target_sparsity < 1.0:
            raise ValueError(f"target_sparsity {target_sparsity}: [0, 1)")
        if warmup_steps < 0 or ramp_steps < 0 or n_events < 1:
            raise ValueError("warmup/ramp steps >= 0, n_events >= 1")
        self.target_sparsity = float(target_sparsity)
        self.warmup_steps = int(warmup_steps)
        self.ramp_steps = int(ramp_steps)
        self.n_events = int(n_events)

    def _ramp(self, frac: float) -> float:
        # cubic: s(t) = target * (1 - (1 - t)^3)
        frac = min(max(frac, 0.0), 1.0)
        return self.target_sparsity * (1.0 - (1.0 - frac) ** 3)

    def sparsity_at(self, step: int) -> float:
        if step < self.warmup_steps or self.target_sparsity == 0.0:
            return 0.0
        if self.ramp_steps == 0:
            return self.target_sparsity
        # quantized to n_events jumps so masks change at a handful of
        # announced steps, not every step
        frac = (step - self.warmup_steps) / self.ramp_steps
        event = min(self.n_events, int(np.floor(frac * self.n_events)) + 1)
        return self._ramp(event / self.n_events)

    def prune_steps(self):
        """Exactly the steps where ``sparsity_at`` increases."""
        if self.target_sparsity == 0.0:
            return []
        if self.ramp_steps == 0:
            return [self.warmup_steps]
        steps, prev = [], 0.0
        for s in range(self.warmup_steps,
                       self.warmup_steps + self.ramp_steps + 1):
            cur = self.sparsity_at(s)
            if cur > prev:
                steps.append(s)
                prev = cur
        return steps
