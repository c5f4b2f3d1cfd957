"""Optimizer — the training driver.

Reference analog (unverified — mount empty): ``dllib/optim/Optimizer.scala``
(builder API: ``setOptimMethod/setEndWhen/setCheckpoint/setValidation``) and
``DistriOptimizer.optimize()`` (SURVEY.md §4.1 call stack): the per-iteration
loop with trigger-driven validation/checkpoint, per-iteration metrics logging,
and the **driver-side retry loop** that reloads the last checkpoint on
failure (bounded by ``bigdl.failure.retryTimes``).

TPU-native: one iteration is one XLA program (no Spark stages); the loop below
only shards host batches, dispatches the jitted step, and evaluates triggers.
Loss stays on-device between logs so iterations pipeline: a log point
fetches every dispatched bundle but the newest, so the device always holds
the next program while the host does its work for the one after.
"""

import os
import time
import warnings
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import numpy as np

from bigdl_tpu.data import pipeline as pipeline_mod
from bigdl_tpu.data.dataset import DataSet
from bigdl_tpu.data.prefetch import thread_prefetch
from bigdl_tpu.obs import attr as obs_attr
from bigdl_tpu.obs import cost as obs_cost
from bigdl_tpu.obs import flight, trace
from bigdl_tpu.obs.host import HostProbes
from bigdl_tpu.obs.state_metrics import StateMetricsBooker
from bigdl_tpu.optim import checkpoint as ckpt
from bigdl_tpu.optim.metrics import Metrics, SummaryWriter
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.train_step import (
    GradientClipping, ShardedParameterStep, host_fetch, put_sharded,
)
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import ValidationMethod
from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.retry import classify
from bigdl_tpu.runtime.engine import Engine
from bigdl_tpu.utils.log import get_logger

log = get_logger("bigdl_tpu.optim")


class _Dispatched(NamedTuple):
    """One dispatched bundle's results, on the device until a log point
    fetches them."""

    it0: int     # iterations done before its first step
    steps: int
    epoch: int
    losses: Any  # length-``steps`` device vectors
    gnorms: Any
    counted: Any  # the model state's "metrics" subtrees after its last step

    @property
    def end(self) -> int:
        """Iterations done after its last step."""
        return self.it0 + self.steps


class TrainedModel:
    """Returned by ``optimize()`` — the trained module + variables, with
    predict/evaluate conveniences (reference returns the mutated Module)."""

    def __init__(self, model, variables, step_engine: ShardedParameterStep):
        self.model = model
        self.variables = variables
        self._engine = step_engine

    def predict(self, x, batch_size: int = 0) -> np.ndarray:
        run = self._engine.predict_fn()
        multi = isinstance(x, tuple)  # tuple = multi-input pack
        if multi:
            x = tuple(np.asarray(a) for a in x)
        else:
            x = np.asarray(x)
        # multi-host predict runs per-process (no mesh sharding), so padding
        # to the data-axis multiple is only needed single-process
        ndev = (self._engine.n_data_replicas
                if jax.process_count() == 1 else 1)
        n = (x[0] if multi else x).shape[0]

        def pad_to(arrs, k):
            def one(a):
                p = (-a.shape[0]) % k
                return np.concatenate([a, np.repeat(a[-1:], p, 0)]) if p else a
            return tuple(one(a) for a in arrs) if multi else one(arrs)

        if batch_size <= 0:
            return np.asarray(run(pad_to(x, ndev)))[:n]
        outs = []
        for i in range(0, n, batch_size):
            xb = (tuple(a[i:i + batch_size] for a in x) if multi
                  else x[i:i + batch_size])
            outs.append(np.asarray(run(pad_to(xb, ndev)))
                        [:min(batch_size, n - i)])
        return np.concatenate(outs)

    def evaluate(self, dataset: DataSet, methods: Sequence[ValidationMethod],
                 batch_size: int = 128):
        batches = dataset.batches(
            batch_size, shuffle=False, drop_last=False,
            process_id=jax.process_index(), process_count=jax.process_count())
        return self._engine.evaluate(list(methods), batches)

    @property
    def ema_variables(self):
        """EMA weights when the run used ``ema_decay`` (the ImageNet
        EMA-eval recipe), else None.  Evaluate them via
        ``model.apply(trained.ema_variables, x)`` or
        ``trained.set_variables(trained.ema_variables)``."""
        if getattr(self._engine, "ema_flat", None) is None:
            return None
        return self._engine.get_variables(ema=True)

    def set_variables(self, variables: Dict[str, Any]) -> None:
        """Overwrite the engine's weights/state with a loaded variables
        pytree (``Module.loadModule`` analog)."""
        import jax.numpy as jnp
        from jax.flatten_util import ravel_pytree

        eng = self._engine
        if not hasattr(eng, "flat_params"):
            # layout (GSPMD) engines own their sharded placement — they
            # re-device_put the tree under the layout's NamedShardings
            eng.set_variables(variables)
            self.variables = variables
            return
        flat, _ = ravel_pytree(variables["params"])
        if flat.shape[0] != eng.n_real:
            raise ValueError(
                f"loaded params have {flat.shape[0]} elements, model has "
                f"{eng.n_real}")
        eng.flat_params = jax.device_put(
            jnp.pad(flat, (0, eng.n_pad - eng.n_real)), eng._rep)
        eng.model_state = jax.device_put(
            variables.get("state", {}), eng._rep)
        self.variables = variables


class Optimizer:
    """Builder + driver.  Works on a 1-device mesh (the LocalOptimizer case)
    and an N-device/N-host mesh (the DistriOptimizer case) with the same
    code — mesh size is the only difference."""

    def __init__(self, model, dataset: DataSet, criterion,
                 batch_size: int = 32, seed: int = 42):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.batch_size = batch_size
        self.seed = seed
        self.optim_method: OptimMethod = SGD(learning_rate=1e-2)
        self.end_when: Trigger = Trigger.max_epoch(10)
        self.clip: Optional[GradientClipping] = None
        self._ckpt_path: Optional[str] = None
        self._ckpt_trigger: Optional[Trigger] = None
        self._ckpt_sharded = "auto"
        self._ckpt_mirror = None
        self._ckpt_async = None
        self._val_trigger: Optional[Trigger] = None
        self._val_dataset: Optional[DataSet] = None
        self._val_methods: Optional[List[ValidationMethod]] = None
        self._val_batch: int = batch_size
        self._train_summary: Optional[SummaryWriter] = None
        self._val_summary: Optional[SummaryWriter] = None
        self.log_every = 1
        self.prefetch = 2  # device-transfer lookahead depth (1 = no overlap)
        self.host_prefetch = 2  # host-side producer lookahead (batches the
        #                         IO/decode producer runs ahead of dispatch).
        #                         0 = inline production — only right when
        #                         the producer is trivially cheap (in-RAM
        #                         arrays on a starved host); an IO/decode-
        #                         bound producer MUST run ahead or the
        #                         device idles every step (docs/data.md)
        self.streaming = True  # stage-parallel input pipeline when the
        #                        dataset supports it (stream_batches);
        #                        host_prefetch=0 forces inline production
        self.bf16_grads = False  # DEPRECATED: grad_comm = "bf16" spelling
        self.grad_comm = None  # gradient-sync wire format (docs/
        #                        parallelism.md §Gradient compression):
        #                        "fp32" | "bf16" | "int8" (blockwise-
        #                        quantized, ~4x fewer gradient bytes);
        #                        None = inherit EngineConfig.grad_comm
        self.comm_bucket_bytes = None  # max flat-gradient bytes per
        #                                collective (bucketed overlap);
        #                                None = EngineConfig's, which
        #                                defaults to one monolithic sync
        self.param_comm = None  # updated-param all_gather wire format:
        #                         "fp32" | "int8" (blockwise-quantized
        #                         delta gather, ~4x fewer param-gather
        #                         bytes — docs/parallelism.md);
        #                         None = fp32
        self.quant_block = None  # int8 scale granularity (elements per
        #                          f32 scale); None = collectives default
        self.remat = False       # jax.checkpoint the forward (HBM for FLOPs)
        self.remat_policy = None  # None|'nothing'|'dots' (keep MXU outputs)
        self.trainable_mask = None  # bool pytree over params (LoRA/freeze)
        self.accum_steps = 1     # gradient-accumulation microbatches
        self.ema_decay = 0.0     # weight EMA (0 = off); read the result
        #                          via TrainedModel.ema_variables
        self.seq_parallel = False  # shard dim 1 over the mesh "seq" axis
        #                            (long-context; model attention must be
        #                            seq_parallel-aware)
        self.steps_per_call = None  # fused multi-step execution (docs/
        #                             performance.md): compile K train
        #                             steps as ONE XLA program so the host
        #                             re-enters Python once per bundle, not
        #                             once per step.  int K, "auto" (pick K
        #                             from measured dispatch-vs-step time
        #                             after the first log window), or None
        #                             = inherit EngineConfig.steps_per_call
        self.metrics = Metrics()
        self.watchdog = None  # resilience.StepWatchdog (Supervisor installs
        #                       one; set directly for standalone NaN/hang
        #                       detection)
        self.cluster = None  # resilience.ClusterCoordinator (the Supervisor
        #                      installs one when FailurePolicy.cluster_dir is
        #                      set; set_cluster attaches one directly).  The
        #                      driver calls its bundle-edge hook, publishes
        #                      peer-shard state at checkpoints, and prefers
        #                      peer-shard restore in _try_resume
        self.failure_policy = None  # per-Optimizer FailurePolicy override
        #                             (Supervisor propagates its own here so
        #                             the in-run retry loop honors the same
        #                             per-cause bounds); None = engine's
        self._final_state: Optional[Dict[str, Any]] = None
        self._last_val_iter = -1
        self._last_ckpt_iter = -1
        self._preempt_signals: tuple = ()
        self._preempted = False
        self._profiler = None
        self._summary_triggers: Dict[str, Trigger] = {}
        self._last_hist_iter = -1
        # bundle runtime state (resolved per optimize() run)
        self._bundle_k = 1
        self._bundle_auto = False
        self._bundle_picked = False
        self._pending_losses: List[_Dispatched] = []  # not yet fetched
        self._log_due = False  # a log point waits for the next dispatch
        self._last_dispatch_end: Optional[float] = None
        # perf attribution (docs/observability.md §Step-time attribution):
        # the driver thread's time by phase + live MFU/collective-bytes
        # accounting, resolved per optimize() run
        self.attribution = obs_attr.StepAttribution(self.metrics)
        self._flops_per_step: Optional[float] = None
        self._eff_flops_per_step: Optional[float] = None
        self._peak_flops: Optional[float] = None
        self._ici_bytes_step = 0.0
        self._dcn_bytes_step = 0.0
        self._recompile: Optional[obs_attr.RecompileSentinel] = None

    # ---- builder API (reference names, snake_case) -----------------------
    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        return self

    def set_initial_variables(self, variables: Dict[str, Any]) -> "Optimizer":
        """Start training from the given variables pytree instead of a
        fresh ``model.init`` (fine-tuning, e.g. converted torch weights)."""
        self._initial_variables = variables
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       async_write: bool = False,
                       sharded="auto",
                       mirror: Optional[str] = None) -> "Optimizer":
        """``path`` may be a local directory or a remote URI (``gs://…``
        via the optional fsspec+gcsfs — the reference's
        ``setCheckpoint(hdfs://…)`` analog); a preemptible TPU VM must
        checkpoint off-VM to survive.  ``async_write=True`` snapshots to
        host at the trigger and runs the npz serialization on a
        background thread (one in flight) — the cheap-frequent-checkpoint
        posture for preemptible slices.

        ``mirror``: a second (typically remote) checkpoint root every
        completed save is copied to with bounded retry-with-backoff
        (``storage.mirror_tree``) — the off-cluster copy that survives
        the whole pod being reclaimed.  Mirror failures degrade to a
        warning after retries; the primary save already landed.

        ``sharded``: ``"auto"`` (default) writes the ZeRO-1 optimizer
        state as per-process shard files whenever the job is multi-host —
        each host writes 1/n of the state with NO cross-host allgather
        (the Orbax-style pod-scale posture; the path must be visible to
        every process, e.g. ``gs://…``).  ``False`` forces the gathered
        single-writer format; ``True`` forces sharding.  Loading
        reassembles shards for ANY process count, so resharding a resumed
        job is free."""
        self._ckpt_path = path
        self._ckpt_trigger = trigger
        self._ckpt_sharded = sharded
        self._ckpt_mirror = mirror
        self._ckpt_async = (ckpt.AsyncCheckpointer() if async_write
                            else None)
        return self

    def set_validation(self, trigger: Trigger, dataset: DataSet,
                       methods: Sequence[ValidationMethod],
                       batch_size: Optional[int] = None) -> "Optimizer":
        self._val_trigger = trigger
        self._val_dataset = dataset
        self._val_methods = list(methods)
        if batch_size:
            self._val_batch = batch_size
        return self

    def set_gradient_clipping_by_l2_norm(self, norm: float) -> "Optimizer":
        self.clip = self.clip or GradientClipping()
        self.clip.l2_norm = norm
        return self

    def set_constant_gradient_clipping(self, min_v: float, max_v: float
                                       ) -> "Optimizer":
        self.clip = self.clip or GradientClipping()
        self.clip.constant_min = min_v
        self.clip.constant_max = max_v
        return self

    def set_train_summary(self, log_dir: str) -> "Optimizer":
        self._train_summary = SummaryWriter(log_dir, "train")
        return self

    def set_summary_trigger(self, tag: str, trigger: Trigger) -> "Optimizer":
        """Opt-in heavy summary streams — reference
        ``TrainSummary.setSummaryTrigger``.  Supported tag: ``"Parameters"``
        (per-parameter histograms; costs a device→host fetch per firing,
        which is why it is trigger-gated like the reference)."""
        if tag != "Parameters":
            raise ValueError(f"unknown summary tag {tag!r} "
                             "(supported: 'Parameters')")
        self._summary_triggers[tag] = trigger
        return self

    def set_val_summary(self, log_dir: str) -> "Optimizer":
        self._val_summary = SummaryWriter(log_dir, "validation")
        return self

    def set_profile(self, log_dir: str, start_iter: int = 10,
                    num_iters: int = 5) -> "Optimizer":
        """Capture a jax.profiler trace over a warm window of iterations —
        SURVEY.md §6.1 TPU mapping of the reference's per-iteration Metrics
        dump."""
        from bigdl_tpu.utils.profiling import IterationProfiler

        self._profiler = IterationProfiler(log_dir, start_iter, num_iters)
        return self

    def set_cluster(self, coordinator) -> "Optimizer":
        """Attach a :class:`~bigdl_tpu.resilience.cluster.
        ClusterCoordinator` (docs/resilience.md §Multi-host recovery):
        membership/abort checks at every bundle edge, peer-shard
        publishes alongside every checkpoint, and peer-shard-first
        restore.  The Supervisor attaches one automatically when
        ``FailurePolicy.cluster_dir`` is set."""
        self.cluster = coordinator
        return self

    def set_preemption_checkpoint(self, *signals) -> "Optimizer":
        """Save a checkpoint and stop cleanly when the process receives a
        preemption signal (default SIGTERM — what TPU-VM maintenance events
        deliver).  SURVEY.md §6.3 TPU mapping of the reference's
        checkpoint-restart stance; requires ``set_checkpoint``."""
        import signal as _signal

        self._preempt_signals = signals or (_signal.SIGTERM,)
        return self

    def _resolved_grad_comm(self, config) -> str:
        """The run's gradient-sync wire format: the explicit
        ``grad_comm`` attribute, else the deprecated ``bf16_grads=True``
        spelling (warned, mapped to "bf16"), else the engine default."""
        if self.grad_comm is not None:
            mode = str(self.grad_comm).strip().lower()
            if self.bf16_grads and mode != "bf16":
                warnings.warn(
                    "both grad_comm and the deprecated bf16_grads are "
                    f"set; grad_comm={mode!r} wins",
                    DeprecationWarning, stacklevel=2)
            return mode
        if self.bf16_grads:
            warnings.warn(
                "Optimizer.bf16_grads is deprecated: set "
                "grad_comm='bf16' (docs/parallelism.md §Gradient "
                "compression)", DeprecationWarning, stacklevel=2)
            return "bf16"
        return getattr(config, "grad_comm", "fp32") or "fp32"

    # ---- the driver loop --------------------------------------------------
    def optimize(self) -> TrainedModel:
        engine = Engine.get()
        mesh = engine.mesh
        rng = jax.random.PRNGKey(self.seed)
        if self._profiler is None \
                and getattr(engine.config, "profile_dir", None):
            # EngineConfig.profile_dir / BIGDL_TPU_PROFILE_DIR: trace a warm
            # window without touching the builder; the finally below
            # guarantees close() even when training ends inside the window
            self.set_profile(engine.config.profile_dir)

        # init params from one sample batch
        sample = next(iter(self.dataset.batches(
            self.batch_size, shuffle=False, process_count=jax.process_count())))
        sx = sample["input"]
        init_args = tuple(np.asarray(a[:1]) for a in sx) \
            if isinstance(sx, tuple) else (np.asarray(sx[:1]),)
        init_vars = getattr(self, "_initial_variables", None) \
            or self.model.init(rng, *init_args)
        if self.trainable_mask is None:
            # keras-1 layer.trainable=False convention: derive the mask
            # automatically when any module in the tree is frozen
            from bigdl_tpu.nn.freeze import has_frozen, trainable_mask_for

            if has_frozen(self.model):
                self.trainable_mask = trainable_mask_for(
                    self.model, init_vars["params"])
        step_kw = dict(
            grad_comm=self._resolved_grad_comm(engine.config),
            comm_bucket_bytes=(self.comm_bucket_bytes
                               if self.comm_bucket_bytes is not None
                               else getattr(engine.config,
                                            "comm_bucket_bytes", None)))
        if self.quant_block is not None:
            step_kw["quant_block"] = int(self.quant_block)
        if self.param_comm is not None:
            step_kw["param_comm"] = str(self.param_comm)
        step_engine = ShardedParameterStep(
            self.model, self.criterion, self.optim_method, mesh, init_vars,
            clip=self.clip, remat=self.remat,
            remat_policy=self.remat_policy,
            trainable_mask=self.trainable_mask,
            accum_steps=self.accum_steps, ema_decay=self.ema_decay,
            seq_parallel=self.seq_parallel, **step_kw)
        n_params = step_engine.n_real
        log.info("model has %s parameters; mesh data axis = %d; ZeRO shard = %s",
                 f"{n_params:,}", step_engine.ndev,
                 f"{step_engine.shard_size:,}")
        # fused multi-step execution: per-step PRNG derives on device from
        # the step counter (no host PRNGKey/fold_in per step, even at K=1)
        step_engine.set_step_seed(self.seed + 1)
        self._arm_perf_accounting(engine, step_engine, init_vars, init_args)
        if os.environ.get("BIGDL_TPU_MEASURE_OVERLAP", "0") in ("1",
                                                                "true"):
            # opt-in startup audit (two extra compiles): how much of the
            # gradient-sync collective time hides under compute
            try:
                ov = step_engine.measure_overlap(
                    step_engine.shard_batch(sample["input"]),
                    step_engine.shard_batch(
                        np.asarray(sample["target"])))
                self.metrics.gauge("train.comm_overlap_efficiency",
                                   ov["overlap_efficiency"])
                self.metrics.gauge("train.comm_exposed_collective_s",
                                   ov["exposed_collective_s"])
                flight.record("comm_overlap_audit", **ov)
            except Exception as e:  # pragma: no cover — exotic meshes
                log.warning("overlap audit failed (%s); skipped", e)
        spc = self.steps_per_call
        if spc is None:
            spc = getattr(engine.config, "steps_per_call", 1) or 1
        self._bundle_auto = spc == "auto"
        if isinstance(spc, str) and not self._bundle_auto:
            raise ValueError(
                f"steps_per_call {spc!r}: an int >= 1 or 'auto'")
        self._bundle_k = 1 if self._bundle_auto else max(1, int(spc))
        self._bundle_picked = False
        self._pending_losses = []
        self._log_due = False
        self._last_dispatch_end = None

        state: Dict[str, Any] = {
            "epoch": 1, "iteration": 0, "epoch_batch": 0,
            "epoch_finished": False,
            "loss": float("nan"), "score": float("-inf"),
        }

        # resume if a checkpoint exists
        if self._ckpt_path:
            self._try_resume(step_engine, state)
        # counters the model keeps in its state (a router's load, say)
        # are booked at the log points, counted from here on
        self._state_metrics = StateMetricsBooker(step_engine.model_state,
                                                 self.metrics)
        self.metrics.inc("train.fetch_overlapped", 0)  # exists from the start

        # preemption-aware save: flag-based — the handler must not touch jax
        # from signal context, so the loop checkpoints at the next iteration
        old_handlers = []
        self._preempted = False
        if self._preempt_signals:
            import signal as _signal

            if not self._ckpt_path:
                raise ValueError(
                    "set_preemption_checkpoint requires set_checkpoint")

            def _on_preempt(signum, frame):
                self._preempted = True

            for s in self._preempt_signals:
                old_handlers.append((s, _signal.signal(s, _on_preempt)))

        # the host's alibi for a stalled step (obs/host.py), this run long
        stalls = self.attribution.stalls
        stalls.probes = HostProbes(self.metrics).start()
        try:
            return self._optimize_loop(step_engine, state)
        finally:
            stalls.exclude()  # one still waiting for its witness: unknown
            stalls.probes.stop()
            if self._recompile is not None:
                # a later run's warmup compiles must not be flagged
                self._recompile.mark_warmup()
            if self._profiler is not None:
                self._profiler.close()
            if old_handlers:
                import signal as _signal

                for s, h in old_handlers:
                    _signal.signal(s, h)

    def _arm_perf_accounting(self, engine, step_engine, init_vars,
                             init_args) -> None:
        """Resolve the run's performance-attribution state: the analytic
        FLOPs/step (live MFU numerator), the device peak (denominator),
        the collective-bytes ledger, the attribution accumulator, and the
        recompilation sentinel.  Best-effort — a cost-model failure
        degrades observability, never training."""
        self.attribution = obs_attr.StepAttribution(self.metrics)
        self._recompile = obs_attr.recompile_sentinel()
        self._recompile.mark_warmup()
        self._flops_per_step = None
        self._eff_flops_per_step = None
        # kept for _refresh_cost_model: a block-sparse mask restore at
        # resume changes effective FLOPs after this first pass ran
        # (shapes only: the walk runs under eval_shape, and the arrays
        # themselves would pin a second copy of the parameters for the run)
        self._cost_model_args = (jax.eval_shape(lambda v: v, init_vars),
                                 init_args)
        try:
            # shape-capturing walk under eval_shape: no compute, no
            # compile; FLOPs scale linearly from the batch-1 sample to the
            # global batch (the _per_host_batch contract: batch_size IS
            # the global batch)
            detail = obs_cost.train_step_flops_detail(
                self.model, init_vars, init_args, self.batch_size)
            self._flops_per_step = detail["dense"]
            self._eff_flops_per_step = detail["effective"]
            self.metrics.gauge("train.flops_per_step", self._flops_per_step)
            self.metrics.gauge("train.effective_flops_per_step",
                               self._eff_flops_per_step)
        except Exception as e:  # pragma: no cover — exotic custom modules
            log.debug("analytic cost model unavailable (%s); no live MFU "
                      "gauge this run", e)
        self._peak_flops = obs_cost.peak_flops(
            jax.devices()[0].device_kind)
        led = obs_cost.collective_ledger(step_engine)
        self._ici_bytes_step = led["ici_bytes_per_step"]
        self._dcn_bytes_step = led["dcn_bytes_per_step"]
        self.metrics.gauge("train.collective_ici_bytes_per_step",
                           self._ici_bytes_step)
        self.metrics.gauge("train.collective_dcn_bytes_per_step",
                           self._dcn_bytes_step)
        # compression view: the gradient scatter (wire dtype + scales,
        # the compressible half) vs the f32 param gather, and the bucket
        # count the overlap scheduler works with
        self.metrics.gauge("train.collective_grad_ici_bytes_per_step",
                           led["grad_ici_bytes_per_step"])
        self.metrics.gauge("train.collective_param_ici_bytes_per_step",
                           led["param_ici_bytes_per_step"])
        self.metrics.gauge("train.grad_comm_buckets", led["comm_buckets"])

    def _optimize_loop(self, step_engine, state) -> TrainedModel:
        engine = Engine.get()
        retries = 0
        retries_by_cause: Dict[Any, int] = {}
        max_retries = engine.config.failure_retry_times
        attribution = self.attribution
        attribution.begin()
        while not self._end_reached(state):
            if self._preempted:
                # signal landed during epoch-boundary work (validation,
                # triggers) — still honour the save-before-stop contract
                if self.cluster is not None:
                    self.cluster.notify_preemption()
                self._save_checkpoint_once(step_engine, state)
                break
            state["epoch_finished"] = False
            epoch = state["epoch"]
            # exactly-once mid-epoch resume: a checkpoint records how many
            # batches of the current epoch were TRAINED (epoch_batch); the
            # resumed epoch fast-forwards past them instead of replaying
            # the epoch from batch 0.  The skip re-gathers (and discards)
            # at most one epoch of input once per resume — bounded, and
            # the batch plan is deterministic per (seed, epoch).
            # An ELASTIC resume (process_count changed) arrives as a
            # _resume_reshard marker instead: the epoch's remaining
            # examples are re-sharded over the NEW process set
            # (docs/distributed_training.md) — epoch_batch keeps counting
            # GLOBAL steps, which are invariant across process counts.
            skip = int(state.pop("_resume_skip", 0) or 0)
            reshard = state.pop("_resume_reshard", None)
            state["epoch_batch"] = (int(reshard["trained"])
                                    + int(reshard.get("skip", 0) or 0)) \
                if reshard else skip
            batch_iter = self._epoch_batch_iter(step_engine, epoch, skip,
                                                reshard=reshard)
            # observability: time each fetch out of the prefetch pipeline —
            # waiting HERE means the run is input-bound, not device-bound
            batch_iter = self._traced_data(batch_iter)
            # fused multi-step execution: the pipeline lends up to
            # steps_per_call device batches per pull; the span callback
            # clamps each bundle to the per-epoch grid and to trigger
            # edges, and the epoch tail arrives as a remainder bundle
            bundles = pipeline_mod.bundle_batches(
                batch_iter, lambda: self._bundle_span(state))
            try:
                ran_any = False
                for mbs in bundles:
                    ran_any = True
                    prev_it = state["iteration"]
                    self._one_bundle(step_engine, state, mbs)
                    # a log point's fetch comes one bundle late, once the
                    # next bundle is queued behind the one it reads
                    if self._log_due:
                        self._log_progress(state)
                    self._log_due = self._should_log(prev_it,
                                                     state["iteration"])
                    self._fire_triggers(step_engine, state)
                    if self.cluster is not None \
                            and self.cluster.preempt_pending \
                            and not self._preempted:
                        # a PEER host was preempted: the notice propagates
                        # as our own preemption so the whole gang takes
                        # the just-in-time checkpoint, not just the
                        # signalled host
                        log.warning(
                            "cluster preemption notice received: treating "
                            "as local preemption")
                        self._preempted = True
                    if self._preempted:
                        log.warning(
                            "preemption signal received: checkpointing at "
                            "iteration %d and stopping", state["iteration"])
                        if self.cluster is not None:
                            # local SIGTERM → cluster-wide notice (the
                            # handler itself must not touch storage from
                            # signal context; this bundle edge may)
                            self.cluster.notify_preemption()
                        self._save_checkpoint_once(step_engine, state)
                        break
                    done = self._end_reached(state)
                    if done:
                        self._log_progress(state, flush=True)
                    attribution.end_iteration()
                    if done:
                        break
                else:
                    # epoch boundary: fire epoch triggers while `epoch` still
                    # names the epoch that just finished, then advance.
                    # A resume whose skip consumed the WHOLE epoch (the
                    # checkpoint landed on its last batch) advances without
                    # re-firing — those boundary triggers already ran
                    # before the crash, and a duplicate validation event
                    # would double-feed plateau schedules.
                    if ran_any or skip == 0 or reshard is not None:
                        state["epoch_finished"] = True
                        self._fire_triggers(step_engine, state)
                    state["epoch"] += 1
                    # a resharded epoch's plan dies with the epoch: later
                    # epochs use the normal (seed, epoch, process_count)
                    # plan, and later checkpoints must not carry the marker
                    state.pop("reshard_origin", None)
            except Exception as e:  # driver retry loop (§6.3)
                # A failed train_step may have consumed donated buffers, so
                # recovery REQUIRES a checkpoint to restore from; the epoch
                # restarts cleanly from the resumed driver state.
                # latest_checkpoint accepts only SHARD-COMPLETE dirs, so a
                # manifest orphaned by a crashed sharded write is never the
                # resume point.
                retries += 1
                t_fail = time.perf_counter()
                attribution.end_iteration()
                # dispatched-but-unfetched bundle results are part of the
                # rolled-back step chain; drop them so the next log window
                # never feeds pre-failure losses to the watchdog
                self._pending_losses = []
                self._log_due = False
                self._last_dispatch_end = None
                cause = classify(e)
                policy = self.failure_policy \
                    or engine.config.resolved_failure_policy()
                cause_policy = policy.policy_for(cause)
                n_cause = retries_by_cause[cause] = \
                    retries_by_cause.get(cause, 0) + 1
                # in-flight async write may BE the latest checkpoint
                self._ckpt_drain(raise_error=False)
                can_resume = (self._ckpt_path and
                              ckpt.latest_checkpoint(self._ckpt_path))
                # bounded BOTH globally and per cause: a poisoned batch
                # replays the identical plan, so its policy allows far
                # fewer in-run retries than a storage blip — exhausting
                # either bound escapes to the Supervisor (or the caller)
                if retries > max_retries or not can_resume \
                        or n_cause > cause_policy.max_retries:
                    raise
                delay = cause_policy.backoff(n_cause)
                log.warning(
                    "iteration failed (%s: %s); retry %d/%d from checkpoint "
                    "[cause %s] in %.2fs", type(e).__name__, e, retries,
                    max_retries, cause.value, delay)
                flight.record("train_in_run_retry", cause=cause.value,
                              retry=retries, iteration=state["iteration"],
                              error=f"{type(e).__name__}: {e}")
                time.sleep(delay)
                if self.cluster is not None:
                    # coordinated rewind: this process is about to restore
                    # an earlier step, so the GANG must restore with it —
                    # post the abort (peers exit their collectives at the
                    # next bundle edge), rendezvous on the next view, and
                    # only then resume together
                    self.cluster.gang_recover(cause.value)
                with trace.span("resilience/in_run_resume",
                                cause=cause.value, retry=retries):
                    self._try_resume(step_engine, state)
                self._state_metrics.rebase(step_engine.model_state)
                self.metrics.inc("recoveries_total")
                self.metrics.inc(f"retries_by_cause.{cause.value}")
                self.metrics.inc("time_lost_to_recovery_s",
                                 time.perf_counter() - t_fail)
                if self.cluster is not None:
                    # MTTR: failure catch → restored-and-ready wall time
                    self.cluster.note_recovered(
                        time.perf_counter() - t_fail)
                attribution.stalls.exclude()  # recovery is not step time,
                # and not attributable step time either: the failed
                # pass was closed where it failed, the next one starts here
                self.metrics.reset()
                attribution.begin()

        # whatever way the loop left (an epoch-count end_when is seen only
        # at its top), the model handed back has had every loss looked at
        self._log_progress(state, flush=True)
        attribution.end_iteration()  # the tail: the last end_when call
        if self._recompile is not None:
            # the step loop is over: run-tail work (final checkpoint,
            # get_variables' unravel ops) compiles legitimately
            self._recompile.mark_warmup()
        try:
            self._ckpt_drain()
        except Exception as e:
            # training finished and device state is valid — a failed FINAL
            # write must not discard the model; retry once synchronously
            log.warning("final checkpoint write failed (%s); retrying "
                        "synchronously", e)
            try:
                self._save_checkpoint_sync_last(step_engine, state)
            except Exception as e2:
                log.error("synchronous checkpoint retry also failed: %s", e2)
        variables = step_engine.get_variables()
        self._final_state = dict(state)  # observability: final step/epoch
        if attribution.steps:
            # the end-of-run "where did the time go" table; also available
            # programmatically via Optimizer.attribution.report()
            log.info("%s", self.attribution.table())
        return TrainedModel(self.model, variables, step_engine)

    @property
    def final_state(self) -> Optional[Dict[str, Any]]:
        """Driver state at the end of the last completed ``optimize()`` —
        lets callers (tests, the Supervisor) verify e.g. that a faulted
        run reached the same final iteration as a fault-free one."""
        return self._final_state

    # ------------------------------------------------------------------
    def _epoch_batch_iter(self, step_engine, epoch, skip, reshard=None):
        """One epoch's device-ready batch iterator — the streaming input
        pipeline (docs/data.md) when the dataset supports it, the classic
        thread-prefetch path otherwise, both behind the device-dispatch
        lookahead.  ``host_prefetch=0`` forces fully inline production.

        ``reshard`` (an elastic mid-epoch resume marker from
        ``_try_resume``: ``{"process_count": old, "trained": k, "skip":
        extra}``) switches THIS epoch to the re-sharded remainder plan —
        the examples the old process set already trained are excluded and
        the rest re-stride over the new process set
        (``DataSet.resharded_batches``); later epochs revert to the
        normal plan."""
        from bigdl_tpu.data.pipeline import dispatch_to_device, timed_batches

        engine = Engine.get()
        kw = dict(shuffle=True, seed=self.seed, epoch=epoch,
                  process_id=jax.process_index(),
                  process_count=jax.process_count())

        def _skip_closing(inner, n):
            # a bare islice has no close(): abandoning a RESUMED epoch
            # (preemption, end_when, driver retry) must still shut the
            # underlying pipeline's stage threads down, so wrap in a
            # generator whose close propagates
            import itertools

            try:
                yield from itertools.islice(inner, n, None)
            finally:
                close = getattr(inner, "close", None)
                if close is not None:
                    close()

        def _lookahead(batch_iter):
            # the producer thread; data.produce_s is ITS seconds per batch
            return thread_prefetch(
                timed_batches(batch_iter, "produce", self.metrics),
                depth=self.host_prefetch)

        def _dispatch(batch_iter):
            # dispatch lookahead: host→device DMA double-buffers behind
            # the running step (up to 2 transfers in flight); ring slots
            # release only after their own transfer lands
            return dispatch_to_device(
                batch_iter,
                lambda mb: (step_engine.shard_batch(mb["input"]),
                            step_engine.shard_batch(
                                np.asarray(mb["target"]))),
                size=self.prefetch, metrics=self.metrics)

        if reshard is not None:
            rkw = dict(trained_batches=int(reshard["trained"]),
                       old_process_count=int(reshard["process_count"]),
                       **kw)
            stream = (self.streaming and self.host_prefetch > 0
                      and hasattr(self.dataset,
                                  "resharded_stream_batches"))
            if stream:
                # the remainder epoch keeps the stage-parallel sharded
                # feed: each host streams only its slice of the
                # remaining examples (docs/data.md §Multi-host ingest)
                batch_iter = self.dataset.resharded_stream_batches(
                    self.batch_size,
                    workers=getattr(engine.config, "data_workers", None),
                    metrics=self.metrics, **rkw)
            else:
                batch_iter = self.dataset.resharded_batches(
                    self.batch_size, **rkw)
            skip = int(reshard.get("skip", 0) or 0)
            if skip:
                batch_iter = _skip_closing(batch_iter, skip)
            if self.host_prefetch and not stream:
                batch_iter = _lookahead(batch_iter)
            return _dispatch(batch_iter)
        stream = (self.streaming and self.host_prefetch > 0
                  and hasattr(self.dataset, "stream_batches"))
        if stream:
            # stage-parallel read→decode→assemble into the buffer ring;
            # the pipeline's own threads ARE the host lookahead
            batch_iter = self.dataset.stream_batches(
                self.batch_size,
                workers=getattr(engine.config, "data_workers", None),
                metrics=self.metrics, **kw)
        else:
            batch_iter = self.dataset.batches(self.batch_size, **kw)
        if skip:
            batch_iter = _skip_closing(batch_iter, skip)
        if self.host_prefetch and not stream:
            # host-side lookahead: IO/augmentation runs a thread ahead.
            # (Never stacked on the streaming path: buffering RingBatches
            # in a queue would let their slots be recycled under the
            # consumer; the ring provides the lookahead there.)
            batch_iter = _lookahead(batch_iter)
        return _dispatch(batch_iter)

    def _end_reached(self, state) -> bool:
        with self.attribution.phase("overhead"):
            return self.end_when(state)

    def _traced_data(self, batch_iter):
        """The data phase: each ``next()`` on the prefetch pipeline is
        host time the device may spend idle.  Waits land in the
        ``train.data_wait_s`` histogram — the /metrics signal that a run is
        input-bound rather than device-bound; ``data.batch_wait_s`` and
        ``data.put_s`` (data/pipeline.py) say which part of the pipeline.
        The first pull of an epoch's iterator starts its producer and
        refills the lookahead: it is ALSO observed in
        ``data.epoch_first_wait_s``, the epoch-boundary stall."""
        it = iter(batch_iter)
        first = True
        while True:
            with self.attribution.phase("data") as wait:
                try:
                    mb = next(it)
                except StopIteration:
                    return
            if first:
                self.metrics.observe("data.epoch_first_wait_s",
                                     wait.seconds)
                first = False
            yield mb

    def _bundle_span(self, state) -> int:
        """How many steps the NEXT bundle may span.  Bundle edges live on
        the per-epoch grid (epoch_batch multiples of K) so a mid-epoch
        resume re-aligns to the boundaries an uninterrupted run used, and
        iteration-structured triggers (``Trigger.boundary`` hints) shorten
        a bundle so their firing step lands exactly on a bundle edge —
        ``several_iteration(4)`` still checkpoints at iteration 4 under
        ``steps_per_call=8``.  Triggers without iteration structure
        (loss/score/plateau) quantize to bundle granularity."""
        k = self._bundle_k
        if k <= 1:
            return 1
        if self._preempted or (self.cluster is not None
                               and self.cluster.preempt_pending):
            # a preemption is pending: the signal can only be honoured at
            # a bundle edge, so the NEXT bundle shrinks to one step and
            # the just-in-time checkpoint lands ~1 step after the signal
            # instead of up to K steps later
            return 1
        span = k - state.get("epoch_batch", 0) % k
        it = state["iteration"]
        for t in (self.end_when, self._val_trigger, self._ckpt_trigger,
                  self._summary_triggers.get("Parameters")):
            b = getattr(t, "boundary", None) if t is not None else None
            if b is None:
                continue
            edge = b(it)
            if edge is not None and 0 < edge < span:
                span = edge
        return span

    def _one_bundle(self, step_engine, state, mbs):
        """Dispatch ``len(mbs)`` consecutive steps as ONE XLA program.
        Fault injection fires host-side for every step in the range (the
        host only regains control at bundle edges); per-step losses come
        back as a device vector fetched lazily at the next log point."""
        it0 = state["iteration"]
        k = len(mbs)
        now = time.perf_counter()
        if self._last_dispatch_end is not None:
            # host time since the previous dispatch returned — the
            # per-step overhead bundling amortizes (÷ bundle size)
            self.metrics.observe("train.dispatch_gap_s",
                                 now - self._last_dispatch_end)
        with trace.span("train/bundle", step=it0, size=k):
            if self.cluster is not None:
                # cluster hazards first (peer abort flags, propagated
                # preemption notices, injected host loss) — a gang-level
                # condition must win over a local per-step fault
                self.cluster.on_step(it0, k)
            faults.fire_bundle(it0, k)  # slow_host / process_kill /
            #                             step_fail per step in the range
            if self.watchdog is not None:
                self.watchdog.step_started(it0)
            for j in range(k):
                with trace.span("train/step", step=it0 + j):
                    if self._profiler is not None:
                        # starting, stopping and reading back a trace is
                        # driver time: booked, not left to "other"
                        with self.attribution.phase("overhead"):
                            if self._profiler.step(
                                    it0 + j,
                                    settle=lambda: jax.block_until_ready(
                                        state["loss"])):
                                self.attribution.stalls.exclude()
            xs = [mb[0] for mb in mbs]
            ys = [mb[1] for mb in mbs]
            with self.attribution.phase("dispatch", steps=k, step=it0,
                                        size=k) as disp:
                losses, gnorms, counted = step_engine.train_bundle_device(
                    it0, xs, ys)
                last_loss = losses[-1]  # a device op of its own
            # per-step normalized so the mean stays comparable across
            # bundle sizes (the auto-K pick reads it)
            self.metrics.add("step_dispatch", disp.seconds / k)
        self._last_dispatch_end = time.perf_counter()
        # every step dispatched, and those dispatched to a program that
        # carries its state leaf-shaped (train_step.py: one shard)
        self.metrics.inc("train.updates", k)
        self.metrics.inc("train.leaf_updates",
                         k if step_engine.leaf_state else 0)
        if self._recompile is not None:
            self._recompile.note_step(it0 + k)
        # collective-bytes ledger: every dispatched step moves the same
        # sync traffic (the layout is static for the run)
        if self._ici_bytes_step:
            self.metrics.inc("train.collective_ici_bytes_total",
                             self._ici_bytes_step * k)
        if self._dcn_bytes_step:
            self.metrics.inc("train.collective_dcn_bytes_total",
                             self._dcn_bytes_step * k)
        self._pending_losses.append(_Dispatched(
            it0, k, state["epoch"], losses, gnorms, counted))
        self._gauge_in_flight()
        self.metrics.gauge("train.bundle_size", k)
        # the NEWEST step's loss, as a device scalar: a trigger that reads
        # it (min_loss, a plateau on "loss") waits for this bundle, as before
        # the log point stopped doing so; one that does not, does not wait
        state["loss"] = last_loss
        state["iteration"] = it0 + k
        state["epoch_batch"] = state.get("epoch_batch", 0) + k

    def _gauge_in_flight(self) -> None:
        self.metrics.gauge("train.steps_in_flight",
                           sum(b.steps for b in self._pending_losses))

    def _should_log(self, prev_it: int, it: int) -> bool:
        # a log point is any multiple of log_every inside (prev_it, it] —
        # bundles quantize the cadence up to their edges
        return it // self.log_every > prev_it // self.log_every

    def _log_progress(self, state, flush: bool = False):
        """A log point's fetch, made once the NEXT bundle is dispatched: it
        takes the results of every dispatched bundle but that newest one
        and logs them.  The newest stays in flight, so while the device
        runs it the host fires the triggers, pulls the next batch and
        queues the next bundle: the device never waits for the host's
        per-step work, and a NaN or a hang is seen one bundle later than
        it was, no more.  ``flush`` fetches the newest too: where its
        value is needed (before a validation, a checkpoint or a parameter
        histogram) and where the loop leaves."""
        n = len(self._pending_losses) - (0 if flush else 1)
        if flush:
            self._log_due = False
        if n <= 0:
            return
        pending = self._pending_losses[:n]
        del self._pending_losses[:n]
        # fetching the loss VALUES blocks until these bundles have actually
        # executed (they are data-dependent on every bundle before them), so
        # the wall-clock window between two fetches measures real step
        # time — not async dispatch time, which flatters when the in-flight
        # queue hides device latency.  The state's counters come as the
        # bundle's own copy: the state they were in has been donated since.
        with self.attribution.phase("sync", step=pending[-1].end) as sync:
            fetched, counted = jax.device_get((
                [(b.losses, b.gnorms) for b in pending],
                pending[-1].counted))
        if self._pending_losses:
            self.metrics.inc("train.fetch_overlapped")
        with self.attribution.phase("overhead"):
            # the step interval ends where the wait ended, not after the
            # bookkeeping (obs/attr.py StallWatch: sample, rule, witness)
            dt = self.attribution.stalls.fetched(
                sync.end_ns, sync.seconds, sum(b.steps for b in pending),
                sum(b.steps for b in self._pending_losses), pending[-1].end)
            self._state_metrics.book(counted)
            self._record_progress(state, pending, fetched, dt)

    def _record_progress(self, state, pending, fetched, dt):
        """The log point after the fetch: curves, watchdog, step time,
        gauges, the log line, all under the newest FETCHED step's
        iteration number."""
        it = pending[-1].end
        loss = float(np.ravel(fetched[-1][0])[-1])
        if not self._pending_losses:
            # nothing newer in flight: this is the state's device scalar
            state["loss"] = loss
        self._gauge_in_flight()
        # per-step granularity survives bundling: every bundle returned a
        # length-K loss/grad-norm vector — record the full curves first,
        # then feed the NaN watchdog (which may raise PoisonedStepError
        # into the retry loop after nan_patience bad observations; the
        # fetch above already forced the sync, so none of this costs an
        # extra transfer)
        for b, (lv, gv) in zip(pending, fetched):
            lv, gv = np.ravel(lv), np.ravel(gv)
            for j in range(len(lv)):
                self.metrics.observe("train.grad_norm", float(gv[j]))
                if self._train_summary:
                    self._train_summary.add_scalar(
                        "loss", float(lv[j]), b.it0 + j + 1)
        if self.watchdog is not None:
            for b, (lv, _) in zip(pending, fetched):
                lv = np.ravel(lv)
                for j in range(len(lv)):
                    self.watchdog.observe_loss(b.it0 + j, float(lv[j]))
        # dt: the wall time between this fetch's return and the one before
        # over the steps between (exact per step at log_every=1, the
        # window's mean at a coarser cadence); None where that interval
        # held a trigger's work or a recovery, and at the first fetch: the
        # dispatch mean then stands in, for the log line only
        dt_is_wall = dt is not None
        if not dt_is_wall:
            dt = self.metrics.mean("step_dispatch")
        if self._bundle_auto and not self._bundle_picked \
                and dt_is_wall and dt > 0:
            self._pick_bundle_size(dt)
        self._account_window(it, dt, dt_is_wall)
        self.metrics.reset()  # rolling window: throughput reflects recent steps
        lr = float(np.asarray(self.optim_method.get_learning_rate(it - 1)))
        throughput = self.batch_size / max(dt, 1e-9)
        log.info(
            "Epoch %d Iteration %d: loss %.4f, lr %.5g, ~%.0f records/s",
            pending[-1].epoch, it, loss, lr, throughput)
        if self._train_summary:
            self._train_summary.add_scalar("lr", lr, it)
            self._train_summary.add_scalar("throughput", throughput, it)

    def _account_window(self, it: int, dt: float,
                        dt_is_wall: bool) -> None:
        """A log point's gauges: live MFU and (multi-process) straggler
        skew; after two log points the recompile sentinel goes steady.
        ``dt_is_wall=False`` marks the dispatch-mean proxy windows (first
        window, first after recovery): a proxy dt is ~1000x the true wall
        off on real hardware, so MFU/straggler gauges skip those — the
        warmup is symmetric across hosts, so the allgather stays matched."""
        if self._recompile is not None \
                and self.attribution.windows >= 2 \
                and not self._recompile.steady:
            # warmup is over after TWO log points: the first holds the
            # train-program compile, the second flushes the log-point's
            # own eager-op compiles (LR schedule math, summary plumbing).
            # New bundle-size/eval programs announce themselves via
            # expected_compile in the step engine, so from here anything
            # else is a mid-run cache miss
            self._recompile.mark_steady(it)
        if dt_is_wall and dt > 0 and self._flops_per_step:
            achieved = self._flops_per_step / dt / jax.device_count()
            self.metrics.gauge("train.achieved_flops_per_chip", achieved)
            m = obs_cost.mfu(self._flops_per_step, dt, jax.device_count(),
                             self._peak_flops)
            if m is not None:
                self.metrics.gauge("train.mfu", m)
            # effective MFU: nonzero-block work only — under block
            # sparsity train.mfu is the dense-equivalent view and THIS is
            # the honest chip utilization; for dense models they are equal
            if self._eff_flops_per_step:
                em = obs_cost.mfu(self._eff_flops_per_step, dt,
                                  jax.device_count(), self._peak_flops)
                if em is not None:
                    self.metrics.gauge("train.effective_mfu", em)
        if dt_is_wall and dt > 0 and jax.process_count() > 1:
            try:
                stats = obs_attr.host_step_time_stats(dt)
            except Exception as e:  # pragma: no cover — backend quirks
                log.debug("straggler allgather failed: %s", e)
                stats = None
            if stats:
                self.metrics.gauge("train.step_time_max_s", stats["max"])
                self.metrics.gauge("train.step_time_min_s", stats["min"])
                self.metrics.gauge("train.step_time_skew_s", stats["skew"])

    def _pick_bundle_size(self, step_time_s: float) -> None:
        """``steps_per_call="auto"``: after the first full log window
        (compile excluded), compare the measured per-step host dispatch
        time against step wall time and pick K so dispatch amortizes to
        ~2% of wall — small fast steps get deep bundles, big slow steps
        stay at K=1 where bundling only delays triggers."""
        self._bundle_picked = True
        disp = self.metrics.mean("step_dispatch")
        ratio = disp / step_time_s if step_time_s > 0 else 0.0
        k = 1 if ratio < 0.02 else int(min(32, max(2, np.ceil(ratio / 0.02))))
        if k != self._bundle_k:
            log.info(
                "steps_per_call=auto: per-step dispatch %.3f ms vs step "
                "%.3f ms (%.0f%%) -> bundling %d steps per XLA call",
                disp * 1e3, step_time_s * 1e3, 100 * ratio, k)
            flight.record("bundle_auto_pick", k=k, dispatch_s=disp,
                          step_s=step_time_s)
        self._bundle_k = k

    def _fire_triggers(self, step_engine, state):
        """Validation, checkpoint and parameter histograms, each where its
        trigger says so and at most once per iteration (an iteration-count
        trigger would otherwise re-fire at the epoch-boundary call).  One
        that fires first has the bundle in flight fetched: its work reads
        the newest state, and the watchdog must have seen every loss
        before that state is written anywhere."""
        it = state["iteration"]
        phase = self.attribution.phase
        for trigger, wanted, last, work in (
                (self._val_trigger, True, "_last_val_iter",
                 self._run_validation),
                (self._ckpt_trigger, self._ckpt_path, "_last_ckpt_iter",
                 self._save_checkpoint),
                (self._summary_triggers.get("Parameters"),
                 self._train_summary, "_last_hist_iter",
                 self._write_histograms)):
            with phase("overhead"):
                due = (trigger and trigger(state) and wanted
                       and getattr(self, last) != it)
            if not due:
                continue
            setattr(self, last, it)
            self._log_progress(state, flush=True)
            with phase("overhead"):
                work(step_engine, state)
            self.attribution.stalls.exclude()  # trigger work is not step time

    def _write_histograms(self, step_engine, state):
        variables = step_engine.get_variables()
        # ONE batched device→host fetch of the whole params tree — a
        # per-leaf np.asarray would block on a separate transfer per
        # parameter (hundreds of round-trips on a real model)
        host_params = jax.device_get(variables["params"])
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                host_params)[0]:
            tag = "Parameters/" + "/".join(
                str(getattr(k, "key", k)) for k in path)
            self._train_summary.add_histogram(tag, leaf, state["iteration"])

    def _save_checkpoint_once(self, step_engine, state):
        """Checkpoint unless this iteration was already checkpointed (the
        trigger may have fired just before a preemption break)."""
        if self._ckpt_path is None:
            # a cluster-propagated preemption can reach a run that never
            # called set_checkpoint; stopping cleanly is all it can do
            log.warning("preemption stop without set_checkpoint: no "
                        "just-in-time checkpoint to take")
            return
        if self._last_ckpt_iter != state["iteration"]:
            self._last_ckpt_iter = state["iteration"]
            self._log_progress(state, flush=True)
            self._save_checkpoint(step_engine, state)

    def _save_checkpoint(self, step_engine, state):
        state["loss"] = float(state["loss"])
        # Snapshot unconditionally: the async writer serializes driver_state
        # in a background thread while the training loop keeps mutating the
        # live dict, so the manifest could otherwise record a later iteration
        # than the params it accompanies.
        state = dict(state)
        schedule = getattr(self.optim_method, "schedule", None)
        if schedule is not None and hasattr(schedule, "state_dict"):
            state["schedule_state"] = schedule.state_dict()
        kw = self._ckpt_kwargs(step_engine, state,
                               sync_barrier=self._ckpt_async is None)
        if self._ckpt_async is not None:
            self._ckpt_async.submit(self._ckpt_path,
                                    state["iteration"], **kw)
        else:
            ckpt.save_checkpoint(self._ckpt_path, state["iteration"], **kw)
        if self.cluster is not None:
            # peer-shard publish rides the checkpoint trigger: each host
            # pushes its ZeRO-1 shard (leader adds the replicated params)
            # onto the control channel, so a rejoining process can restore
            # from its buddies without touching the checkpoint bucket.
            # Best-effort — a failed publish degrades the recovery ladder
            # (checkpoint rung still holds), never training
            try:
                self.cluster.publish_state(step_engine, state)
            except Exception as e:
                log.warning("peer-shard publish failed: %s", e)

    def _refresh_cost_model(self) -> None:
        """Recompute the live-MFU numerators after a host-side model
        structure change (block-sparse masks restored at resume) — the
        first _arm_perf_accounting pass ran before the masks existed."""
        init_vars, init_args = getattr(self, "_cost_model_args",
                                       (None, None))
        if init_vars is None:
            return
        try:
            detail = obs_cost.train_step_flops_detail(
                self.model, init_vars, init_args, self.batch_size)
            self._flops_per_step = detail["dense"]
            self._eff_flops_per_step = detail["effective"]
            self.metrics.gauge("train.flops_per_step", self._flops_per_step)
            self.metrics.gauge("train.effective_flops_per_step",
                               self._eff_flops_per_step)
        except Exception as e:  # pragma: no cover — cost model optional
            log.debug("cost-model refresh failed (%s)", e)

    def _ckpt_kwargs(self, step_engine, state, sync_barrier: bool):
        """The save_checkpoint argument set: gathered single-writer by
        default, per-process opt-state shards when sharded checkpointing
        is active.  Shards are fetched to host EAGERLY (the async writer
        must never touch live device state), and the cross-process
        barrier is only used on the synchronous path — a barrier inside
        the async writer thread could interleave with the training
        step's own collectives and deadlock; the READER instead verifies
        every shard file exists before trusting a sharded manifest."""
        # the mid-epoch batch plan is keyed by process_count; recording it
        # in every written driver_state lets an elastic resume detect the
        # key changed (see _try_resume) — `state` is already a snapshot on
        # both call paths, so mutating it here is safe
        state["process_count"] = jax.process_count()
        # block-sparse FFN masks are host MODULE state, not params — ride
        # the driver_state so a restarted process resumes the same
        # sparsity pattern instead of silently training dense again
        from bigdl_tpu.ops.block_sparse import collect_masks

        sparse_masks = collect_masks(self.model)
        if sparse_masks:
            state["block_sparse_masks"] = sparse_masks
        kw = dict(model_state=host_fetch(step_engine.model_state),
                  driver_state=state)
        if self._ckpt_mirror:
            kw["mirror"] = self._ckpt_mirror
        sharded = self._ckpt_use_shards(step_engine)
        # params/EMA are replicated: in sharded mode only process 0's copy
        # is ever written, so the other (n-1) hosts skip the full-model
        # device→host materialization entirely
        if not sharded or jax.process_index() == 0:
            kw["flat_params"] = np.asarray(step_engine.flat_params)
            if step_engine.ema_flat is not None:
                kw["ema_flat"] = np.asarray(step_engine.ema_flat)
        if sharded:
            kw["opt_shards"] = ckpt.local_opt_shards(step_engine.opt_state)
            kw["shard_index"] = jax.process_index()
            kw["shard_count"] = jax.process_count()
            kw["attempt"] = self._ckpt_attempt_token(state["iteration"])
            if sync_barrier and jax.process_count() > 1:
                from jax.experimental import multihost_utils

                it = state["iteration"]
                kw["barrier"] = lambda: multihost_utils.sync_global_devices(
                    f"bigdl-tpu-ckpt-{it}")
        else:
            kw["opt_state"] = host_fetch(step_engine.opt_state)
        return kw

    @staticmethod
    def _ckpt_attempt_token(iteration: int) -> str:
        """One uuid per SAVE, agreed by every process: generated on
        process 0 and broadcast on the MAIN thread (a collective here is
        deterministic program order; inside the async writer thread it
        could interleave with the training step's collectives and
        deadlock).  The token makes shard files attempt-unique so a
        manifest can never certify a stale shard from a crashed earlier
        attempt at the same step."""
        import uuid

        if jax.process_count() == 1:
            return uuid.uuid4().hex[:8]
        from jax.experimental import multihost_utils

        tok = np.frombuffer(
            uuid.uuid4().hex[:8].encode(), np.uint8).copy() \
            if jax.process_index() == 0 else np.zeros(8, np.uint8)
        tok = multihost_utils.broadcast_one_to_all(tok)
        return bytes(np.asarray(tok)).decode()

    def _ckpt_use_shards(self, step_engine) -> bool:
        if not step_engine.optim.elementwise:
            return False  # replicated opt state: nothing to shard
        if self._ckpt_sharded == "auto":
            return jax.process_count() > 1
        return bool(self._ckpt_sharded)

    def _save_checkpoint_sync_last(self, step_engine, state):
        ckpt.save_checkpoint(
            self._ckpt_path, state["iteration"],
            **self._ckpt_kwargs(
                step_engine, dict(state, loss=float(state["loss"])),
                sync_barrier=True))

    def _ckpt_drain(self, raise_error: bool = True):
        """Join any in-flight async write (resume and exit paths read
        latest_checkpoint, which must see a completed directory)."""
        if self._ckpt_async is not None:
            self._ckpt_async.wait(raise_error=raise_error)

    def _run_validation(self, step_engine, state):
        batches = self._val_dataset.batches(
            self._val_batch, shuffle=False, drop_last=False,
            process_id=jax.process_index(), process_count=jax.process_count())
        results = step_engine.evaluate(self._val_methods, batches)
        for r in results:
            log.info("validation [%s] epoch %d iter %d: %s",
                     r.name, state["epoch"], state["iteration"], r.result)
            if self._val_summary:
                self._val_summary.add_scalar(r.name, r.result,
                                             state["iteration"])
        if results:
            state["score"] = results[0].result
            # observation counter for event-cadenced triggers
            # (Trigger.plateau counts validation events, not iterations)
            state["n_validations"] = state.get("n_validations", 0) + 1
            # reduce-on-plateau feedback (reference SGD.Plateau): the
            # schedule decides host-side; an LR change needs a recompile
            schedule = getattr(self.optim_method, "schedule", None)
            if schedule is not None and hasattr(schedule, "on_score"):
                monitor = getattr(schedule, "monitor", None)
                picked = results[0]
                if monitor is not None:
                    matches = [r for r in results if r.name == monitor]
                    if not matches:
                        raise ValueError(
                            f"Plateau monitor {monitor!r} not among "
                            f"validation methods {[r.name for r in results]}")
                    picked = matches[0]
                if schedule.on_score(float(picked.result)):
                    log.info("Plateau: reducing LR (factor now %g); "
                             "recompiling train step",
                             schedule.current_factor)
                    step_engine._train = step_engine._build_train()

    def _try_resume(self, step_engine, state):
        """Restore device + driver state from the best available source —
        the recovery LADDER (docs/resilience.md §Multi-host recovery):

        1. **peer-shard store** (cluster attached, complete step at least
           as new as the newest checkpoint): replicated params + the
           ZeRO-1 optimizer shards the peers published on the control
           channel — bit-identical to a checkpoint restore of the same
           step, without touching the checkpoint bucket;
        2. **newest shard-complete checkpoint**;
        3. elastic tail: a ``process_count`` change mid-epoch re-shards
           the epoch's remaining examples over the new process set
           (``DataSet.resharded_batches``), falling back to
           replay-from-epoch-start only when the dataset cannot reshard
           or the process set changed twice in one epoch."""
        from bigdl_tpu.utils import storage as _storage

        latest = ckpt.latest_checkpoint(self._ckpt_path) \
            if self._ckpt_path else None
        ckpt_step = None
        if latest is not None:
            try:
                ckpt_step = int(_storage.basename(latest).split("-")[1])
            except (ValueError, IndexError):
                ckpt_step = None
        loaded = path_used = None
        if self.cluster is not None:
            peer_step = self.cluster.store.latest_complete_step()
            if peer_step is not None and (ckpt_step is None
                                          or peer_step >= ckpt_step):
                try:
                    loaded = self.cluster.load_peer_state(
                        peer_step, step_engine.opt_template,
                        step_engine.model_state_template)
                    path_used = "peer_shard"
                except Exception as e:
                    log.warning(
                        "peer-shard restore of step %d failed (%s: %s); "
                        "falling back to the checkpoint rung", peer_step,
                        type(e).__name__, e)
        if loaded is None:
            if latest is None:
                return
            loaded = ckpt.load_checkpoint(
                latest,
                opt_state_template=step_engine.opt_template,
                model_state_template=step_engine.model_state_template)
            path_used = "checkpoint"
        flat, opt_state, model_state, driver, ema = loaded
        if self.cluster is not None:
            n_bytes = int(
                np.asarray(flat).nbytes
                + sum(np.asarray(a).nbytes for a in
                      jax.tree_util.tree_leaves(opt_state))
                + sum(np.asarray(a).nbytes for a in
                      jax.tree_util.tree_leaves(model_state)))
            self.metrics.inc(f"cluster.recovery_by_path.{path_used}")
            self.metrics.inc("cluster.recovery_bytes_total", n_bytes)
            flight.record("cluster_restore", path=path_used,
                          step=int(driver.get("iteration", 0) or 0),
                          bytes=n_bytes)
        step_engine.flat_params = put_sharded(
            jax.numpy.asarray(flat), step_engine._rep)
        if step_engine.ema_flat is not None:
            # a failed donated step consumed the old EMA buffer too; restore
            # the checkpointed EMA, or re-seed from the restored params when
            # the checkpoint predates EMA
            src = ema if ema is not None else flat
            step_engine.ema_flat = put_sharded(
                jax.numpy.asarray(src).copy(), step_engine._rep)
        opt_sh = (step_engine._sharded_vec if step_engine.optim.elementwise
                  else step_engine._rep)
        step_engine.opt_state = put_sharded(opt_state, opt_sh)
        step_engine.model_state = put_sharded(model_state, step_engine._rep)
        state.update(driver)
        saved_masks = state.pop("block_sparse_masks", None)
        if saved_masks:
            # restore the checkpoint's sparsity pattern; if it differs
            # from the live modules' masks (fresh process: all-ones), the
            # engine's compiled programs traced the WRONG pattern — the
            # mask is a trace-time constant jit cannot see — so drop them
            # and retrace on the next step
            from bigdl_tpu.ops.block_sparse import (apply_masks,
                                                    collect_masks)

            before = collect_masks(self.model)
            n = apply_masks(self.model, saved_masks)
            if n and collect_masks(self.model) != before:
                step_engine.rebuild_programs()
                self._refresh_cost_model()
                log.info("restored block-sparse masks for %d modules; "
                         "programs retrace", n)
                flight.record("block_sparse_masks_restored", modules=n)
        state["epoch_finished"] = False
        # rolled back: trigger bookkeeping beyond the resumed iteration is
        # stale future state — without this reset, a checkpoint/validation
        # trigger that FAILED at iteration N would never re-fire when the
        # replay reaches N again (the run would end missing its last
        # checkpoint).  The resumed iteration itself stays marked: the
        # checkpoint being resumed from IS that iteration's firing.
        it = int(driver.get("iteration", 0) or 0)
        self._last_ckpt_iter = min(self._last_ckpt_iter, it)
        self._last_val_iter = min(self._last_val_iter, it)
        self._last_hist_iter = min(self._last_hist_iter, it)
        # fast-forward the resumed epoch past the batches already trained —
        # from the CHECKPOINT's counter, never the live state's: on the
        # in-run retry path the live epoch_batch reflects rolled-back
        # training (a pre-epoch_batch-era checkpoint must replay, not skip)
        state["epoch_batch"] = int(driver.get("epoch_batch", 0) or 0)
        state["_resume_skip"] = state["epoch_batch"]
        # ELASTIC resume: sharded checkpoints load at any process count,
        # but the per-process batch plan is keyed by (seed, epoch,
        # process_id, process_count) — a skip computed under N processes
        # does not line up with what was trained when resuming under M.
        # When the dataset supports it, the epoch's REMAINING examples are
        # re-sharded deterministically over the new process set (the old
        # plan's trained prefix is reconstructible from (seed, epoch), so
        # shrink/grow loses nothing beyond the post-checkpoint steps);
        # replay-from-epoch-start survives only as the fallback for
        # datasets that cannot reshard or a twice-changed process set.
        saved_pc = driver.get("process_count")
        state["process_count"] = jax.process_count()
        origin = driver.get("reshard_origin")
        pc_changed = (saved_pc is not None
                      and int(saved_pc) != jax.process_count())
        can_reshard = hasattr(self.dataset, "resharded_batches")

        def _replay_epoch(why: str) -> None:
            log.warning(
                "elastic resume: checkpoint written at process_count=%s, "
                "resuming at %d — %s, so epoch %d REPLAYS from its start "
                "(%d mid-epoch batches re-trained rather than silently "
                "dropped)", saved_pc, jax.process_count(), why,
                state["epoch"], state["_resume_skip"])
            state["epoch_batch"] = 0
            state["_resume_skip"] = 0
            state.pop("reshard_origin", None)
            self.metrics.inc("elastic_resumes_total")

        if origin is not None and state["_resume_skip"]:
            # resuming INTO an epoch that already runs on a re-sharded
            # plan: rebuild the same remainder plan and skip the batches
            # of it trained since the reshard point
            if pc_changed or not can_reshard:
                _replay_epoch("the process set changed again mid-epoch")
            else:
                base = int(origin["trained"])
                state["_resume_reshard"] = {
                    "process_count": int(origin["process_count"]),
                    "trained": base,
                    "skip": max(0, state["epoch_batch"] - base)}
                state["_resume_skip"] = 0
        elif pc_changed and state["_resume_skip"]:
            if can_reshard:
                log.warning(
                    "elastic resume: checkpoint written at "
                    "process_count=%d, resuming at %d — epoch %d continues "
                    "on a re-sharded batch plan (the %d already-trained "
                    "global batches are excluded; nothing replays, nothing "
                    "is dropped)", int(saved_pc), jax.process_count(),
                    state["epoch"], state["epoch_batch"])
                state["_resume_reshard"] = {
                    "process_count": int(saved_pc),
                    "trained": state["epoch_batch"], "skip": 0}
                state["reshard_origin"] = {
                    "process_count": int(saved_pc),
                    "trained": state["epoch_batch"]}
                state["_resume_skip"] = 0
                self.metrics.inc("elastic_resumes_total")
                self.metrics.inc("elastic_resharded_total")
                flight.record("elastic_reshard", epoch=state["epoch"],
                              old_pc=int(saved_pc),
                              new_pc=jax.process_count(),
                              trained=state["epoch_batch"])
            else:
                _replay_epoch("the per-process batch plan differs and "
                              "this dataset cannot reshard mid-epoch")
        sched_state = state.pop("schedule_state", None)
        schedule = getattr(self.optim_method, "schedule", None)
        if sched_state is not None and schedule is not None \
                and hasattr(schedule, "load_state_dict"):
            schedule.load_state_dict(sched_state)
            # the restored factor must be baked into the compiled step
            step_engine._train = step_engine._build_train()
        log.info("resumed via %s from %s (iteration %d, epoch %d)",
                 path_used, latest if path_used == "checkpoint"
                 else "peer-shard store",
                 state["iteration"], state["epoch"])


# Reference-parity aliases: the factory in the reference picks the variant by
# dataset type; here the mesh size does, so these are the same class.
DistriOptimizer = Optimizer
LocalOptimizer = Optimizer
