"""Engine: process/topology bootstrap.

Reference analog (all unverified — mount empty): ``dllib/utils/Engine.scala``
reads executor topology from SparkConf, pins MKL threads/affinity, and builds
per-executor thread pools; ``Optimizer`` then refuses to run unless
``Engine.init`` succeeded.  TPU-native replacement: one Python process per
TPU-VM host (multi-controller), ``jax.distributed.initialize`` for rendezvous
(replacing the Spark driver/barrier control plane), and a ``Mesh`` built over
the slice.  There are no thread-pool model clones: per-host multi-chip
parallelism is XLA replication over the mesh.
"""

import dataclasses
import os
from dataclasses import dataclass
from typing import Optional, Union

import jax

from bigdl_tpu.resilience.retry import FailurePolicy
from bigdl_tpu.runtime.mesh import MeshSpec, build_mesh
from bigdl_tpu.utils.log import get_logger

log = get_logger("bigdl_tpu.engine")

# the checkout (the directory holding the ``bigdl_tpu`` package): where the
# run-time caches live, under .gitignore'd names — never the cwd or $HOME
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclass
class EngineConfig:
    """Typed config replacing the reference's three overlapping mechanisms
    (SparkConf props / ``bigdl.*`` sysprops / env soup — SURVEY.md §6.6)."""

    # multi-host rendezvous; None = single-process (or env-configured TPU pod)
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    # logical mesh
    mesh: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    # numerics
    compute_dtype: str = "bfloat16"  # matmul/conv compute dtype on TPU
    param_dtype: str = "float32"
    # failure handling (reference: bigdl.failure.retryTimes ~ 5, unverified).
    # failure_retry_times/interval bound the driver's cheap IN-RUN retry;
    # failure_policy is the full contract (per-cause retries, heartbeats,
    # watchdog) enforced by resilience.Supervisor around optimize().
    failure_retry_times: int = 5
    failure_retry_interval_s: float = 10.0
    failure_policy: Optional[FailurePolicy] = None
    # observability (docs/observability.md): profile_dir arms the
    # IterationProfiler over a warm window of every optimize() run;
    # metrics_port starts a standalone Prometheus /metrics endpoint for
    # jobs with no HTTP surface of their own (0 picks a free port).
    # metrics_host defaults loopback — a fleet scraper needs "0.0.0.0"
    # (set it deliberately: /metrics is unauthenticated)
    profile_dir: Optional[str] = None
    metrics_port: Optional[int] = None
    metrics_host: str = "127.0.0.1"
    # declarative SLOs (docs/observability.md §SLOs & burn rates): a list
    # of spec dicts ({"tenant", "objectives", "window_s"}), inline JSON,
    # or a JSON file path.  The Engine runs an SLOEvaluator over the
    # process registry for the process lifetime; burn rates export as
    # slo.* gauges on /metrics (pair with metrics_port for training
    # jobs).  BIGDL_TPU_SLO_SPECS overrides fleet-wide.
    slo_specs: Optional[object] = None
    # input pipeline (docs/data.md): decode-worker pool width for the
    # streaming batch path; None = one per host core (capped in the
    # adapters).  BIGDL_TPU_DATA_WORKERS overrides fleet-wide.
    data_workers: Optional[int] = None
    # fused multi-step execution (docs/performance.md): compile K train
    # steps as ONE XLA program — the host re-enters Python once per
    # bundle, killing per-step dispatch overhead on small/fast models.
    # int K >= 1, or "auto" (the driver picks K from measured
    # dispatch-vs-step time after its first log window).
    # BIGDL_TPU_STEPS_PER_CALL overrides fleet-wide; the Optimizer's
    # steps_per_call attribute / Estimator "steps_per_call" config key
    # override per run.
    steps_per_call: Union[int, str] = 1
    # gradient-sync wire format (docs/parallelism.md §Gradient
    # compression): "fp32" (full precision), "bf16" (half the gradient
    # bytes), "int8" (blockwise-quantized — int8 payload + per-block
    # scales, ~4x fewer gradient bytes on ICI and DCN).  The Optimizer's
    # grad_comm attribute / Estimator "grad_comm" config key override per
    # run; BIGDL_TPU_GRAD_COMM overrides fleet-wide.
    grad_comm: str = "fp32"
    # gradient-sync bucketing (docs/parallelism.md): max flat-gradient
    # bytes per collective — smaller buckets give XLA's latency-hiding
    # scheduler independent scatter/update/gather chains to overlap;
    # None = one monolithic transfer.  BIGDL_TPU_COMM_BUCKET_BYTES
    # overrides fleet-wide.
    comm_bucket_bytes: Optional[int] = None
    # declarative parallelism policy (docs/parallelism.md §Declarative
    # layouts): a combo string like "dp" | "fsdp" | "tp:8" | "dp:4,tp:2"
    # resolved against the live device set into a named (data, fsdp, tp,
    # seq) mesh + per-model SpecLayout.  The Estimator/Keras
    # "parallelism" config key overrides per run; BIGDL_TPU_PARALLELISM
    # overrides fleet-wide.  None keeps the classic ZeRO-1 data-parallel
    # driver.
    parallelism: Optional[str] = None
    # kernel tile autotuning (docs/performance.md §Kernel autotuning):
    # "off" = hand-picked defaults only, "cache" = consult the on-disk
    # winner cache (default; never measures), "online" = measure-and-
    # cache on a miss (EAGER kernel calls only — jitted paths rely on
    # the offline CLI `python -m bigdl_tpu.ops.autotune`).
    # BIGDL_TPU_AUTOTUNE overrides fleet-wide — resolved at call time by
    # ops.autotune.autotune_mode(), the env var's single owner, so it is
    # NOT parsed into this field by from_env().
    kernel_autotune: str = "cache"

    def resolved_failure_policy(self) -> FailurePolicy:
        """The effective FailurePolicy: the explicit one, else defaults
        seeded from the legacy retry knobs (so BIGDL_TPU_RETRY_TIMES
        keeps meaning what it always did)."""
        if self.failure_policy is not None:
            return self.failure_policy
        from bigdl_tpu.resilience.retry import (FailureCause, RetryPolicy)

        # multiplier=1, no jitter, no cap: the legacy knob meant a FIXED
        # sleep between retries — deriving an exponential-capped policy
        # from it would silently change retry timing for existing
        # configs (e.g. interval_s=120 would hit the 60s cap and retry
        # twice as fast as configured)
        legacy = RetryPolicy(
            max_retries=self.failure_retry_times,
            base_s=self.failure_retry_interval_s,
            multiplier=1.0, jitter=0.0,
            max_s=self.failure_retry_interval_s)
        by_cause = {}
        if (self.failure_retry_times, self.failure_retry_interval_s) \
                != (5, 10.0):
            # the operator TUNED the legacy knobs: they override the
            # static per-cause storage defaults too — storage errors are
            # the dominant real cause on this path, and a tuned 120s
            # interval must not silently become a 0.5s exponential
            by_cause[FailureCause.TRANSIENT_STORAGE] = legacy
        return FailurePolicy(
            max_restarts=self.failure_retry_times,
            default_retry=legacy, by_cause=by_cause)

    @staticmethod
    def from_env() -> "EngineConfig":
        cfg = EngineConfig()
        if os.environ.get("BIGDL_TPU_COORDINATOR"):
            cfg.coordinator_address = os.environ["BIGDL_TPU_COORDINATOR"]
            cfg.num_processes = int(os.environ.get("BIGDL_TPU_NUM_PROCESSES", "1"))
            cfg.process_id = int(os.environ.get("BIGDL_TPU_PROCESS_ID", "0"))
        if os.environ.get("BIGDL_TPU_RETRY_TIMES"):
            cfg.failure_retry_times = int(os.environ["BIGDL_TPU_RETRY_TIMES"])
        if os.environ.get("BIGDL_TPU_HEARTBEAT_DIR"):
            # shared-visibility dir (same requirement as sharded ckpts):
            # enables peer liveness via resilience.detector heartbeats
            cfg.failure_policy = cfg.resolved_failure_policy()
            cfg.failure_policy.heartbeat_dir = \
                os.environ["BIGDL_TPU_HEARTBEAT_DIR"]
        if os.environ.get("BIGDL_TPU_CLUSTER_DIR"):
            # the full cluster control plane (docs/resilience.md
            # §Multi-host recovery): membership views, gang recovery, and
            # peer-shard restore over this shared directory — the
            # Supervisor builds a ClusterCoordinator from it
            cfg.failure_policy = cfg.failure_policy \
                or cfg.resolved_failure_policy()
            cfg.failure_policy.cluster_dir = \
                os.environ["BIGDL_TPU_CLUSTER_DIR"]
        if os.environ.get("BIGDL_TPU_PROFILE_DIR"):
            cfg.profile_dir = os.environ["BIGDL_TPU_PROFILE_DIR"]
        if os.environ.get("BIGDL_TPU_METRICS_PORT"):
            cfg.metrics_port = int(os.environ["BIGDL_TPU_METRICS_PORT"])
        if os.environ.get("BIGDL_TPU_METRICS_HOST"):
            cfg.metrics_host = os.environ["BIGDL_TPU_METRICS_HOST"]
        if os.environ.get("BIGDL_TPU_SLO_SPECS"):
            cfg.slo_specs = os.environ["BIGDL_TPU_SLO_SPECS"]
        if os.environ.get("BIGDL_TPU_DATA_WORKERS"):
            cfg.data_workers = int(os.environ["BIGDL_TPU_DATA_WORKERS"])
        if os.environ.get("BIGDL_TPU_PARALLELISM"):
            # validated lazily at resolve time (the live device count is
            # not known until the backend initializes); bad axis names
            # still fail fast there with the full grammar in the message
            cfg.parallelism = \
                os.environ["BIGDL_TPU_PARALLELISM"].strip().lower()
        if os.environ.get("BIGDL_TPU_GRAD_COMM"):
            cfg.grad_comm = os.environ["BIGDL_TPU_GRAD_COMM"].strip().lower()
        if os.environ.get("BIGDL_TPU_COMM_BUCKET_BYTES"):
            cfg.comm_bucket_bytes = int(
                os.environ["BIGDL_TPU_COMM_BUCKET_BYTES"])
        if os.environ.get("BIGDL_TPU_STEPS_PER_CALL"):
            raw = os.environ["BIGDL_TPU_STEPS_PER_CALL"].strip().lower()
            cfg.steps_per_call = "auto" if raw == "auto" else int(raw)
        if os.environ.get("BIGDL_TPU_DCN_SLICES"):
            # force the cross-slice data-parallel degree where the runtime
            # exposes no slice topology (e.g. multi-host CPU, GKE multislice
            # before the runtime reports slice_index)
            cfg.mesh = dataclasses.replace(
                cfg.mesh, dcn_data=int(os.environ["BIGDL_TPU_DCN_SLICES"]))
        return cfg


class Engine:
    """Singleton runtime: initialized once per process, owns the global mesh."""

    _instance: Optional["Engine"] = None

    _distributed_initialized = False

    def __init__(self, config: EngineConfig):
        self.config = config
        enable_compile_cache()
        if config.coordinator_address is not None and not Engine._distributed_initialized:
            jax.distributed.initialize(
                coordinator_address=config.coordinator_address,
                num_processes=config.num_processes,
                process_id=config.process_id,
            )
            Engine._distributed_initialized = True
        self.mesh = build_mesh(config.mesh)
        self.metrics_server = None
        if config.metrics_port is not None:
            # training jobs have no serving frontend to hang /metrics on;
            # the engine owns the scrape endpoint instead.  A bind failure
            # (port in use — a second job on the host, a pool worker that
            # inherited the env) degrades observability, never compute
            from bigdl_tpu.obs.export import MetricsServer

            try:
                self.metrics_server = MetricsServer(
                    host=config.metrics_host,
                    port=config.metrics_port).start()
            except OSError as e:
                log.error("metrics server failed to bind %s:%s (%s); "
                          "continuing WITHOUT a /metrics endpoint",
                          config.metrics_host, config.metrics_port, e)
        self.slo_evaluator = None
        if config.slo_specs is not None:
            # process-lifetime burn-rate evaluation over the global
            # registry; a bad spec degrades observability, never compute
            from bigdl_tpu.obs.slo import SLOEvaluator

            try:
                self.slo_evaluator = SLOEvaluator(
                    config.slo_specs).start()
            except Exception as e:  # noqa: BLE001
                log.error("SLO specs unusable (%s); SLO evaluation "
                          "disabled", e)
        log.info(
            "Engine initialized: %d devices (%s), %d processes, mesh %s",
            jax.device_count(),
            jax.devices()[0].platform,
            jax.process_count(),
            dict(self.mesh.shape),
        )

    # -- singleton plumbing -------------------------------------------------
    @classmethod
    def get(cls) -> "Engine":
        if cls._instance is None:
            cls._instance = Engine(EngineConfig.from_env())
        return cls._instance

    @classmethod
    def reset(cls) -> None:
        if cls._instance is not None:
            if cls._instance.metrics_server is not None:
                cls._instance.metrics_server.stop()
            if getattr(cls._instance, "slo_evaluator", None) is not None:
                cls._instance.slo_evaluator.stop()
        cls._instance = None

    @property
    def node_number(self) -> int:
        return jax.process_count()

    @property
    def core_number(self) -> int:
        """Devices per process — the analog of coresPerExecutor."""
        return jax.local_device_count()


def init_engine(config: Optional[EngineConfig] = None, **mesh_axes) -> Engine:
    """Initialize (or re-initialize) the global Engine.

    ``init_engine(model=2)`` resizes the logical mesh; the analog of
    ``Engine.init`` + ``spark-bigdl.conf`` in the reference.
    """
    if config is None:
        config = EngineConfig.from_env()
    if mesh_axes:
        config.mesh = dataclasses.replace(config.mesh, **mesh_axes)
    Engine._instance = Engine(config)
    return Engine._instance


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache — THE one place this
    repo sets ``jax_compilation_cache_dir`` (``Engine`` and
    ``InferenceModel`` call it; scripts that compile before building
    either call it first).  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    has already read it and nothing is set in code; otherwise
    ``<checkout>/.jax_cache`` — the path is part of JAX's cache key, so a
    cwd-relative or per-run directory would never hit.  Returns the
    directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_cpu_devices(n_devices: int = 8) -> None:
    """Force the CPU platform with ``n_devices`` virtual devices — the
    ``local[N]`` simulated-mesh bootstrap (SURVEY.md §5) for scripts that
    have already imported jax (the ``JAX_PLATFORMS`` env var is only read
    at import; ``XLA_FLAGS`` must carry the virtual-device count BEFORE
    the backend initializes).  Call before any ``jax.devices()``/array
    op."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # children inherit it
    jax.config.update("jax_platforms", "cpu")


def require_one_chip_holder(n_processes: int, env) -> None:
    """Refuse to start several accelerator-holding JAX processes on one
    host.  A TPU chip belongs to one process at a time and a process
    claims every local chip at backend init, so the second child fails or
    hangs there; nothing in this repo pins one chip per child.  ``env`` is
    the children's environment: only ``JAX_PLATFORMS=cpu`` children may
    be plural.  One process drives all local chips (``Engine``'s default
    mesh); real multi-host jobs run one process per host."""
    if n_processes > 1 and env.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"refusing to start {n_processes} JAX processes on one host "
            "without JAX_PLATFORMS=cpu: each would claim every local "
            "accelerator chip and all but the first fail or hang at "
            "backend init.  Run ONE process per host (it drives all "
            "local chips), or set JAX_PLATFORMS=cpu for CPU workers.")
