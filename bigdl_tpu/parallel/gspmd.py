"""GSPMD training — mesh + sharding ANNOTATIONS, XLA inserts collectives.

The manual path (``optim/train_step.py``) re-derives the reference's
AllReduceParameter algorithm with explicit ``shard_map`` collectives.  This
module is the other TPU-native idiom (the scaling-book recipe, and what the
reference could never do): give every parameter a ``PartitionSpec``, jit
the plain train step with those shardings, and let the GSPMD partitioner
place the psums/all-gathers.

Since the declarative-layout refactor (docs/parallelism.md §Declarative
layouts) the specs come from ``parallel.layout`` tables over the named
``(data, fsdp, tp, seq)`` mesh — the old private 2-axis regex table
survives only as the legacy shim behind :func:`tp_spec_for_path`.  Pass a
``parallel.mesh_policy.ResolvedLayout`` (built from a ``parallelism=``
combo string) and the step trains dp / fsdp / tp / any combo with the SAME
model code; :func:`fit_layout` is the driver the Estimator/Keras
``parallelism=`` surface calls.
"""

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.parallel.layout import (
    LEGACY_SPEC_LAYOUT, ModelLayout, TRANSFORMER_RULES,
    path_str as _path_str)
from bigdl_tpu.runtime.mesh import AXIS_DATA, AXIS_DCN
from bigdl_tpu.utils.log import get_logger

log = get_logger("bigdl_tpu.parallel.gspmd")

# the legacy (data x model) transformer table tp_spec_for_path serves —
# built once; its specs are exactly the old regex table's (the layout
# helpers degrade to 2-axis specs when fsdp/seq are None).  Family rules
# only — the generic Linear fallbacks are a layout-mode capability, so
# legacy callers see the old sharding decisions unchanged
_LEGACY_TABLE = ModelLayout(LEGACY_SPEC_LAYOUT, rules=TRANSFORMER_RULES,
                            name="transformer-legacy")


def tp_spec_for_path(path: str, leaf) -> P:
    """Megatron-style PartitionSpec for one parameter path over the legacy
    (data, model) mesh; replicated when no rule matches.  Kept as the
    compatibility surface of the old regex table — new code resolves a
    ``parallelism=`` policy into a layout table instead
    (``parallel.mesh_policy.mesh_and_layout``)."""
    spec, _ = _LEGACY_TABLE.spec_for(path, np.ndim(leaf))
    return spec


def build_param_specs(params, rule_fn=tp_spec_for_path):
    return jax.tree_util.tree_map_with_path(
        lambda p, x: rule_fn(_path_str(p), x), params)


class GSPMDTrainStep:
    """Auto-partitioned train step over a declarative layout.

    ``model.forward`` is written with NO collectives — plain jnp math.
    Sharding constraints on params and batch are the entire parallelism
    story; XLA's SPMD partitioner emits the all-reduces that ``parallel/
    tp.py`` writes by hand.  Loss/params match the single-device program
    bit-for-bit up to reduction order (asserted in tests).

    Two construction modes:

    - ``layout=`` a :class:`~bigdl_tpu.parallel.mesh_policy.
      ResolvedLayout` (or a :class:`~bigdl_tpu.parallel.layout.
      ModelLayout` + explicit mesh): specs come from the per-model layout
      table over the named (data, fsdp, tp, seq) mesh; the batch shards
      over data x fsdp (+ seq for rank>=2 leaves).  Optimizer state
      inherits each parameter's sharding (fsdp Adam moments are sharded).
    - legacy: an explicit ``mesh`` with (data, model) axes and a
      ``rule_fn`` (default :func:`tp_spec_for_path`).

    Either way the layout is AUDITED at construction: parameters that fall
    back to silent replication export the
    ``parallel.layout.replicated_params`` gauge + one flight/log line
    (``parallel.layout.LayoutAudit``)."""

    def __init__(self, model, criterion, optim_method,
                 mesh: Optional[Mesh], variables: Dict[str, Any],
                 rule_fn: Callable[[str, Any], P] = tp_spec_for_path,
                 remat: bool = False, layout=None):
        from bigdl_tpu.parallel.mesh_policy import ResolvedLayout

        self.model = model
        self.criterion = criterion
        self.optim = optim_method
        self._resolved: Optional[ResolvedLayout] = None
        self._table: Optional[ModelLayout] = None
        if isinstance(layout, ResolvedLayout):
            self._resolved = layout
            mesh = mesh if mesh is not None else layout.mesh
            self._table = layout.table_for(model)
        elif isinstance(layout, ModelLayout):
            self._table = layout
        if mesh is None:
            raise ValueError("GSPMDTrainStep needs a mesh (or a "
                             "ResolvedLayout carrying one)")
        self.mesh = mesh

        params = variables["params"]
        if self._table is not None:
            self.specs = self._table.param_specs(params)
            self.audit = self._table.audit(params).export()
        else:
            self.specs = build_param_specs(params, rule_fn)
            # legacy-path visibility (satellite of the layout refactor):
            # the default table audits exactly; a CUSTOM rule_fn gets the
            # coarse audit (every fully-replicated leaf flagged)
            if rule_fn is tp_spec_for_path:
                self.audit = _LEGACY_TABLE.audit(params).export()
            else:
                self.audit = None
        to_sh = lambda spec: NamedSharding(mesh, spec)
        self.param_sh = jax.tree_util.tree_map(
            to_sh, self.specs, is_leaf=lambda x: isinstance(x, P))
        # copy=True: device_put may alias its input as one replica shard,
        # and the jitted step DONATES params — aliasing the caller's
        # buffers would delete them out from under the caller
        self.params = jax.tree_util.tree_map(
            lambda x, sh: jax.device_put(jnp.array(x, copy=True), sh),
            params, self.param_sh)
        # optimizer state: built from the SHARDED params, so zeros_like
        # moments inherit each parameter's sharding (model-parallel /
        # fsdp-sharded Adam state); scalar counters stay replicated
        self.opt_state = self.optim.init_state(self.params)
        # batch sharding: layout mode shards dim 0 over data x fsdp (and
        # dim 1 over seq for rank>=2 leaves); legacy mode shards over
        # every data-parallel axis incl. the multislice dcn_data axis
        axes = dict(mesh.shape)
        if self._resolved is not None:
            self._spec_layout = self._resolved.spec_layout
            self._batch_prod = self._resolved.n_batch_shards
        elif self._table is not None:
            self._spec_layout = self._table.spec_layout
            self._batch_prod = int(np.prod(
                [axes.get(a, 1)
                 for a in self._spec_layout.batch_axes()]))
        else:
            self._spec_layout = None
            batch_axes = ((AXIS_DCN, AXIS_DATA) if AXIS_DCN in axes
                          else (AXIS_DATA,))
            self._legacy_batch_sh = NamedSharding(mesh, P(batch_axes))
            self._batch_prod = int(np.prod(
                [axes.get(a, 1) for a in batch_axes]))
        # the representative (rank-2) batch sharding, public for layout
        # audits; layout mode refines per leaf rank at device_put time
        self.batch_sh = (self._legacy_batch_sh
                         if self._spec_layout is None else NamedSharding(
                             mesh, self._spec_layout.batch_spec(2)))
        self._batch_sh_cache: Dict[int, NamedSharding] = {}
        self._rep = NamedSharding(mesh, P())
        self.ema_flat = None   # layout path has no EMA (TrainedModel probe)
        self._predict_jit = None

        # locals only: the jitted closure must not retain self (and with it
        # the host-side param copy) in the jit cache
        model_, criterion_, optim_ = model, criterion, optim_method
        param_sh = self.param_sh

        def step_fn(params, opt_state, step, rng, x, y):
            def loss_fn(p):
                xs = x if isinstance(x, tuple) else (x,)
                out, _ = model_.forward(p, {}, *xs, training=True, rng=rng)
                return criterion_.forward(out, y)

            if remat:  # recompute activations in the backward (HBM relief)
                loss_fn = jax.checkpoint(loss_fn)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            new_params, new_opt = optim_.update(step, grads, params,
                                                opt_state)
            # pin the result layouts so they never drift between steps
            new_params = jax.lax.with_sharding_constraint(
                new_params, param_sh)
            return new_params, new_opt, loss

        self._step = jax.jit(step_fn, donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    def _batch_sharding(self, a) -> NamedSharding:
        if self._spec_layout is None:
            return self._legacy_batch_sh
        nd = int(jnp.ndim(a))
        sh = self._batch_sh_cache.get(nd)
        if sh is None:
            sh = self._batch_sh_cache[nd] = NamedSharding(
                self.mesh, self._spec_layout.batch_spec(nd))
        return sh

    def _put_batch(self, tree):
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a),
                                     self._batch_sharding(a)), tree)

    def train_step(self, step: int, rng, x, y):
        x = self._put_batch(x)
        y = self._put_batch(y)
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, jnp.asarray(step, jnp.int32),
            rng, x, y)
        return loss

    def get_params(self):
        return jax.device_get(self.params)

    # -- the TrainedModel engine surface (optim.optimizer.TrainedModel
    #    wraps a GSPMDTrainStep exactly like a ShardedParameterStep) ----
    @property
    def n_data_replicas(self) -> int:
        """Batch-dim multiple predict() pads to: the product of the
        data-parallel axes (data x fsdp; dcn x data on a legacy mesh)."""
        return max(1, self._batch_prod)

    def get_variables(self, ema: bool = False) -> Dict[str, Any]:
        # the GSPMD path keeps no EMA; ema=True returns the plain params
        # (TrainedModel.ema_variables guards on ema_flat first)
        return {"params": self.get_params(), "state": {}}

    def set_variables(self, variables: Dict[str, Any]) -> None:
        """Install a loaded params pytree, re-placed under the layout's
        shardings (``TrainedModel.set_variables`` delegates here for
        layout engines)."""
        params = variables["params"]
        if (jax.tree_util.tree_structure(params)
                != jax.tree_util.tree_structure(self.params)):
            raise ValueError(
                "loaded params do not match the model's parameter "
                "structure")
        def put(x, cur, sh):
            if tuple(np.shape(x)) != tuple(cur.shape):
                raise ValueError(
                    f"loaded param shape {np.shape(x)} != model shape "
                    f"{tuple(cur.shape)}")
            return jax.device_put(jnp.asarray(x), sh)

        self.params = jax.tree_util.tree_map(put, params, self.params,
                                             self.param_sh)

    def predict_fn(self):
        """Jitted inference callable over the layout mesh: batch padded to
        the data-shard multiple, params stay sharded on device."""
        if self._predict_jit is None:
            model = self.model

            def raw(params, x):
                xs = x if isinstance(x, tuple) else (x,)
                out, _ = model.forward(params, {}, *xs, training=False)
                return out

            self._predict_jit = jax.jit(raw)
        fwd = self._predict_jit
        k = self.n_data_replicas

        def run(x):
            multi = isinstance(x, tuple)
            xs = tuple(np.asarray(a) for a in x) if multi \
                else (np.asarray(x),)
            n = xs[0].shape[0]
            pad = (-n) % k
            if pad:
                xs = tuple(np.concatenate(
                    [a, np.repeat(a[-1:], pad, 0)]) for a in xs)
            xd = self._put_batch(xs if multi else xs[0])
            out = fwd(self.params, xd)
            return np.asarray(out)[:n]

        return run

    def evaluate(self, methods, batches) -> list:
        """Host-side stat accumulation over the jitted layout forward —
        the TrainedModel.evaluate contract."""
        run = self.predict_fn()
        totals = None
        for mb in batches:
            x = mb["input"]
            out = run(x)
            y = np.asarray(mb["target"])
            n_rows = (x[0] if isinstance(x, tuple) else x).shape[0]
            w = mb.get("weight")
            if w is None:
                w = np.ones((n_rows,), np.float32)
            stats = [m.batch_stats(jnp.asarray(out), jnp.asarray(y),
                                   jnp.asarray(w)) for m in methods]
            pairs = [(float(s), float(c)) for s, c in stats]
            totals = pairs if totals is None else [
                (a + s, b + c) for (a, b), (s, c) in zip(totals, pairs)]
        return [m.fold(s, c) for m, (s, c) in zip(methods, totals or [])]

    # ------------------------------------------------------------------
    def shard_report(self) -> Dict[str, Tuple]:
        """path -> (global shape, spec) for every model-sharded param —
        the profiling aid for layout audits."""
        out = {}

        def visit(path, leaf, spec):
            if any(a is not None for a in spec):
                out[_path_str(path)] = (tuple(leaf.shape), tuple(spec))

        jax.tree_util.tree_map_with_path(
            lambda p, l, s: visit(p, l, s), self.params, self.specs)
        return out

    def collective_bytes_report(self, grad_dtype_bytes: int = 4
                                ) -> Dict[str, float]:
        """Per-step gradient-sync byte estimate from the parameter layout
        (the obs collective-bytes ledger for the GSPMD path).

        Each parameter's gradient is all-reduced over the data axes the
        partitioner left it replicated on; a model-sharded parameter only
        moves its shard.  Convention matches the manual path
        (``ShardedParameterStep``): one allreduce counts ~2x the shard
        bytes (reduce-scatter + all-gather halves of a ring)."""
        return collective_bytes_for_specs(
            self.params, self.specs, self.mesh,
            grad_dtype_bytes=grad_dtype_bytes)

    def collective_bytes_by_axis(self, dtype_bytes: int = 4
                                 ) -> Dict[str, Any]:
        """The per-axis ledger of this step's layout."""
        from bigdl_tpu.parallel.layout import collective_bytes_by_axis

        return collective_bytes_by_axis(self.params, self.specs, self.mesh,
                                        dtype_bytes=dtype_bytes)


def collective_bytes_for_specs(params, specs, mesh: Mesh,
                               grad_dtype_bytes: int = 4
                               ) -> Dict[str, float]:
    """Estimate per-step gradient allreduce bytes from parameter
    PartitionSpecs: per leaf, the locally held gradient shard is
    ``prod(shape) / prod(sharded axis sizes)`` elements, and the
    data-parallel sync moves ~2x its bytes.  Pure layout math — usable
    before anything compiles.  Data-parallel degree counts every batch
    axis present (data, dcn_data, fsdp).  The per-AXIS breakdown lives in
    :func:`bigdl_tpu.parallel.layout.collective_bytes_by_axis`."""
    from bigdl_tpu.parallel.layout import AXIS_FSDP

    axes = dict(mesh.shape)
    n_data = (axes.get(AXIS_DATA, 1) * axes.get(AXIS_DCN, 1)
              * axes.get(AXIS_FSDP, 1))
    total_shard_elems = 0.0
    total_elems = 0.0

    def visit(leaf, spec):
        nonlocal total_shard_elems, total_elems
        elems = float(np.prod(np.shape(leaf))) if np.ndim(leaf) else 1.0
        div = 1.0
        for entry in tuple(spec):
            names = entry if isinstance(entry, tuple) else (entry,)
            for a in names:
                if a is not None:
                    div *= axes.get(a, 1)
        total_elems += elems
        total_shard_elems += elems / max(div, 1.0)

    jax.tree_util.tree_map(
        visit, params, specs, is_leaf=lambda x: isinstance(x, P))
    sync_bytes = (2.0 * total_shard_elems * grad_dtype_bytes
                  if n_data > 1 else 0.0)
    return {
        "dp_allreduce_bytes_per_step": sync_bytes,
        "grad_shard_bytes": total_shard_elems * grad_dtype_bytes,
        "param_elems": total_elems,
        "n_data_replicas": float(n_data),
    }


# ---------------------------------------------------------------------------
# the parallelism= fit driver (Estimator / keras surface)
# ---------------------------------------------------------------------------

def fit_layout(model, criterion, optim_method, dataset, *,
               parallelism, batch_size: int, epochs: int = 1,
               seed: int = 42, log_every: int = 10,
               devices=None, metrics=None):
    """Train ``model`` under a declarative ``parallelism=`` policy and
    return ``(TrainedModel, stats)`` — the driver behind the Estimator /
    Keras ``parallelism=`` config key.

    The policy string resolves against the live device set into a
    (data, fsdp, tp, seq) mesh + per-model layout table
    (``mesh_policy.mesh_and_layout``); the loop itself is the plain GSPMD
    jit — batches keyed by (seed, epoch) exactly like the classic driver,
    so two policies from one seed see IDENTICAL data order and their loss
    trajectories are comparable step for step (the dp-vs-fsdp x tp parity
    acceptance rides on this)."""
    import time

    from bigdl_tpu.parallel.mesh_policy import mesh_and_layout

    if jax.process_count() > 1:
        raise NotImplementedError(
            "parallelism= layout training is single-controller for now: "
            "run multi-host jobs on the classic ZeRO-1 driver "
            "(parallelism=None) — docs/parallelism.md §Declarative "
            "layouts")
    resolved = mesh_and_layout(parallelism, devices)
    log.info("parallelism %s over %d devices", resolved.describe(),
             int(np.prod(list(resolved.sizes.values()))))
    if batch_size % resolved.n_batch_shards != 0:
        raise ValueError(
            f"batch_size {batch_size} not divisible by the "
            f"{resolved.n_batch_shards} batch shards of parallelism "
            f"{parallelism!r} (data x fsdp = "
            f"{resolved.sizes.get('data', 1)} x "
            f"{resolved.sizes.get('fsdp', 1)})")

    sample = next(iter(dataset.batches(batch_size, shuffle=False)), None)
    if sample is None:
        raise ValueError(
            f"dataset yields no batch of size {batch_size} "
            f"({dataset.size()} samples, drop_last) — shrink batch_size")
    sx = sample["input"]
    init_args = tuple(np.asarray(a[:1]) for a in sx) \
        if isinstance(sx, tuple) else (np.asarray(sx[:1]),)
    rng = jax.random.PRNGKey(seed)
    init_vars = model.init(rng, *init_args)
    step = GSPMDTrainStep(model, criterion, optim_method, None, init_vars,
                          layout=resolved)

    # the per-axis ledger + audit ride the process metrics so one scrape
    # answers "what does this layout move, and what did it replicate?"
    if metrics is None:
        from bigdl_tpu.optim.metrics import global_metrics

        metrics = global_metrics()
    ledger = step.collective_bytes_by_axis()
    for axis, b in ledger["per_axis_bytes_per_step"].items():
        metrics.gauge(f"parallel.layout.{axis}_bytes_per_step", float(b))
    metrics.gauge("parallel.layout.param_bytes_per_chip",
                  float(ledger["param_bytes_per_chip"]))

    t0 = time.time()
    it = 0
    losses = []
    for epoch in range(epochs):
        for mb in dataset.batches(batch_size, shuffle=True, seed=seed,
                                  epoch=epoch):
            loss = step.train_step(it, jax.random.fold_in(rng, it),
                                   mb["input"], mb["target"])
            losses.append(float(np.asarray(loss)))
            if log_every and it % log_every == 0:
                log.info("[layout %s] epoch %d iter %d loss %.4f",
                         resolved.parallelism, epoch + 1, it, losses[-1])
            it += 1
    from bigdl_tpu.optim.optimizer import TrainedModel

    trained = TrainedModel(model, step.get_variables(), step)
    stats = {
        "train_time_s": time.time() - t0,
        "epochs": epochs,
        "num_samples": dataset.size(),
        "iterations": it,
        "parallelism": resolved.parallelism,
        "mesh": dict(resolved.sizes),
        "losses": losses,
        "replicated_params": (len(step.audit.fallback_replicated)
                              if step.audit is not None else 0),
        "collective_bytes_by_axis": ledger["per_axis_bytes_per_step"],
        "param_bytes_per_chip": ledger["param_bytes_per_chip"],
    }
    return trained, stats
