"""What every driver shares: the run's context, its clock, its evidence.

A driver gets a :class:`Run`, calls ``window_start()`` when everything is
warm and ``window_end()`` when the measured seconds are over, and returns a
dict (see ``benchmark/README.md``).  Everything a per-layer reader may need
is gathered here under ``run.evidence``: snapshots of the program's metric
registry at the marks, the count of XLA compilations between them, the
reduced device trace.  All times are ``time.monotonic()``, which on Linux
is one clock for every process of the machine.
"""

import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
                "/jax/compilation_cache/cache_hits": "hits"}


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` by file, so that a name may hold a
    dash or a dot and a new file needs no registration."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload, root=ROOT):
    """The cell's manifest entry, configuration, traffic mix and per-layer
    metric files, all found by name."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    here = os.path.join(root, bench["paths"][0])

    def reported(metric):
        return cell["name"] in metric.get("workloads", [cell["name"]])

    return {
        "bench": bench, "cell": cell,
        "config": load_json(root, entry["file"]),
        "traffic": load_json(here, "traffic", cell["traffic"] + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
        "per_layer": [dict(m, **load_json(here, "layer_metrics",
                                          m["name"] + ".json"))
                      for m in bench["per_layer"] if reported(m)],
    }


def init_variables(model, seed, sample):
    """The weights, on the device, in one jitted call from the seed."""
    import jax

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return jax.jit(lambda k: model.init(k, sample))(key)


class Run:
    def __init__(self, cell_name, config, traffic, seed, seconds, trace,
                 t_process_start, device, trace_seconds=4.0):
        self.cell_name = cell_name
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.trace_seconds = min(trace_seconds, self.seconds / 2)
        self.t0 = t_process_start
        self.device = device
        self.marks = {"process_start": t_process_start}
        self.evidence = {"registry": {}, "compiles": {}, "trace": None}
        self.cache = {"requests": 0, "hits": 0}
        self._compiles = 0
        self._trace_dir = None
        self._trace_thread = None

    # -- printing ------------------------------------------------------------
    def say(self, msg):
        d = self.device
        print(f"[bench] cell={self.cell_name} platform={d['platform']} "
              f"device_kind={d['kind']!r} devices={d['count']} {msg}",
              flush=True)

    # -- listeners (installed once, before anything compiles) -----------------
    def install_listeners(self):
        import jax.monitoring

        def on_duration(name, secs, **_):
            if name == COMPILE_EVENT:
                self._compiles += 1

        def on_event(name, **_):
            if name in CACHE_EVENTS:
                self.cache[CACHE_EVENTS[name]] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        # the program's own compile counter and timer live behind this
        from bigdl_tpu.obs.attr import recompile_sentinel

        recompile_sentinel()

    # -- marks ----------------------------------------------------------------
    def mark(self, name):
        """Stamp a moment: the clock, the registry, the compile count."""
        from bigdl_tpu.optim.metrics import global_metrics

        now = time.monotonic()
        snap = global_metrics().snapshot()
        self.marks[name] = now
        self.evidence["registry"][name] = {
            "counters": snap["counters"],
            "hists": {k: {"sum": h["sum"], "n": h["n"]}
                      for k, h in snap["hists"].items()}}
        self.evidence["compiles"][name] = self._compiles
        return now

    def window_start(self):
        now = self.mark("window_start")
        if self.trace:
            self._trace_thread = threading.Thread(
                target=self._profile, name="bench-profile", daemon=True)
            self._trace_thread.start()
        return now

    def window_end(self):
        return self.mark("window_end")

    @property
    def setup_s(self):
        return self.marks["window_start"] - self.t0

    def compiles_in_window(self):
        c = self.evidence["compiles"]
        return c["window_end"] - c["window_start"]

    # -- device trace -----------------------------------------------------------
    def _profile(self):
        """A few seconds from the middle of the window, in a thread of its
        own so that the measured loop is not held up by the profiler's
        start and stop."""
        import jax

        time.sleep(min(2.0, self.seconds / 4))
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # the device's planes are all that is read, so the host and Python
        # tracers are off: with them on (at any level) a 4 s request traced
        # ~12 s, wrote 224 MB, took 120-190 s to serialise and slowed the
        # host it measured (PR 25).  Without them a traced run is as quick
        # as an untraced one
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 0
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self._trace_dir, profiler_options=options)
        time.sleep(self.trace_seconds)
        jax.profiler.stop_trace()

    def finish_trace(self):
        """After the window: wait for the profiler, reduce, delete."""
        if self._trace_thread is None:
            return None
        from benchmark import trace_reduce

        self._trace_thread.join(timeout=240.0)
        if self._trace_thread.is_alive():   # still writing: no trace
            return None
        try:
            summary = trace_reduce.reduce_dir(self._trace_dir)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
        self.evidence["trace"] = summary
        return summary

    # -- device memory ------------------------------------------------------------
    def memory_peak_bytes(self):
        """The peak on the fullest chip.  The TPU runtime counts live
        buffers (``peak_bytes_in_use``) and the scratch it reserves for a
        running program (``peak_bytes_reserved``) apart; the larger of the
        two is a floor of the true peak and is what is reported."""
        import jax

        peak = 0
        for d in jax.devices():
            s = d.memory_stats() or {}
            peak = max(peak, int(s.get("peak_bytes_in_use", 0)),
                       int(s.get("peak_bytes_reserved", 0)))
        return peak
