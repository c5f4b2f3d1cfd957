"""Profiling — TensorBoard trace capture around training iterations.

Reference analog (unverified — mount empty): ``dllib/optim/Metrics.scala``'s
per-iteration timing breakdown + mkldnn perf-dump flags (SURVEY.md §6.1).
TPU mapping per the survey: ``jax.profiler`` traces (XLA op-level timeline,
viewable in TensorBoard's trace viewer / xprof) replace the hand-rolled
counters for device-side visibility; the host-side ``Metrics`` timers stay
for the input-pipeline/dispatch split.
"""

import contextlib
import glob
import os
from typing import Optional

from bigdl_tpu.obs import trace as obs_trace
from bigdl_tpu.utils.log import get_logger

log = get_logger(__name__)


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a jax.profiler trace for the enclosed block."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("profiler trace written to %s", log_dir)


class IterationProfiler:
    """Trace a window of training iterations — the pattern the reference's
    per-iteration Metrics dump serves: profile steps [start, stop) once the
    pipeline is warm (never step 0: that would capture compile, not
    steady state).

    The device's planes are all that is asked of the profiler: with its
    host tracer on, even at the lowest level that records annotations, 5
    ResNet-50 steps wrote 127 MB, took 22 s to stop and ran up to 5x
    slower (PERF.md §6, PR 26).  The host side comes from the program
    instead: while the trace runs, every instrumented region of the
    driver and the input pipeline (``obs.trace.timed``) is recorded on
    the monotonic clock and exported beside the xplane
    (``driver_spans.json``, Chrome trace format).  When the trace stops,
    :meth:`summary` lays the two over each other and the log gets one
    table: device busy / idle share of the traced steps and the idle
    seconds by the driver phase that covered them
    (``obs.attr.idle_by_phase``)."""

    def __init__(self, log_dir: str, start_iter: int = 10,
                 num_iters: int = 5):
        self.log_dir = log_dir
        self.start_iter = max(1, start_iter)
        self.stop_iter = self.start_iter + num_iters
        self._active = False
        self._spans = obs_trace.Tracer()
        self.done = False

    def _start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 0
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        obs_trace.collect_into(self._spans)
        self._active = True

    def _stop(self, why: str) -> None:
        import jax

        obs_trace.collect_into(None)
        jax.profiler.stop_trace()
        self._active = False
        self.done = True
        self._spans.export_chrome_trace(
            os.path.join(self.log_dir, "driver_spans.json"))
        log.info("profiler trace (%s) written to %s", why, self.log_dir)
        try:
            table = format_idle_table(self.summary())
        except Exception as e:  # a reader's fault must not end training
            table = f"could not be read back ({type(e).__name__}: {e})"
        log.info("device idle time by driver phase: %s", table)

    def step(self, iteration: int, settle=None) -> bool:
        """Call once per training iteration (before the step dispatch);
        True when it started or stopped the trace (seconds of driver time).
        ``settle`` is called before the trace starts and before it stops:
        a driver that keeps a step in flight waits for it there, so that
        the trace holds whole steps and as many step programs as
        ``train/dispatch`` spans."""
        if self.done:
            return False
        starting = not self._active and iteration >= self.start_iter
        stopping = self._active and iteration >= self.stop_iter
        if settle is not None and (starting or stopping):
            settle()
        if starting:
            self._start()
        elif stopping:
            self._stop(f"iters {self.start_iter}-{self.stop_iter - 1}")
        return starting or stopping

    def close(self) -> None:
        """Stop a trace the window left open (training ended inside it);
        idempotent — the driver's finally and an explicit close may both
        run."""
        if self._active:
            self._stop("window truncated by end of training")

    def summary(self) -> Optional[dict]:
        """The newest trace under ``log_dir`` reduced to seconds: ``busy``,
        ``idle``, ``window`` of the first chip that ran anything and, where
        the recorded spans can be aligned with it
        (``obs.attr.clock_offset``: the k-th ``train/dispatch`` span and
        the k-th run of the program the chip spent most time in),
        ``by_phase`` — the idle seconds by the innermost region of the
        driver thread that covered them, ``none`` where nothing did — and
        ``stalls``: each ``train/stall`` span of the window on the
        device's clock (``obs.attr.stall_on_device``, seconds).
        None where the trace holds no device plane (the CPU backend)."""
        from jax.profiler import ProfileData

        from bigdl_tpu.obs.attr import (clock_offset, idle_by_phase,
                                        stall_on_device)

        files = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        ops = programs = None
        for plane in (ProfileData.from_file(files[-1]).planes
                      if files else ()):
            if not plane.name.startswith("/device:") or ops:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    # an op's name: "%fusion.1 = (f32[...]) fusion(...)"
                    ops = [(e.start_ns, e.start_ns + e.duration_ns,
                            e.name.split(" = ")[0].lstrip("%"))
                           for e in line.events]
                elif line.name == "XLA Modules":
                    programs = [(e.name.split("(")[0], e.start_ns,
                                 e.duration_ns) for e in line.events]
        if not ops:
            return None
        spent: dict = {}
        for name, _, dur in programs or ():
            spent[name] = spent.get(name, 0.0) + dur
        step_program = max(spent, key=spent.get) if spent else None
        spans = self._spans.spans()
        dispatches = [s for s in spans if s.name == "train/dispatch"]
        offset = clock_offset(
            [s.start_ns for s in dispatches],
            [start for name, start, _ in programs or ()
             if name == step_program])
        driver, stalls = [], []
        if offset is not None:
            tid = dispatches[0]._tid
            driver = [(s.start_ns - offset, s.end_ns - offset, s.name)
                      for s in spans
                      if s._tid == tid and s.name != "train/stall"]
            stalls = [dict(s.attrs, **stall_on_device(
                ops, driver, s.start_ns - offset, s.end_ns - offset, 1e-9))
                for s in spans if s.name == "train/stall"]
        out = idle_by_phase(ops, driver)
        return {"busy": out["busy"] * 1e-9, "idle": out["idle"] * 1e-9,
                "window": out["window"] * 1e-9,
                "by_phase": ({k: v * 1e-9
                              for k, v in out["by_phase"].items()}
                             if driver else None),
                "stalls": stalls}

    def __enter__(self) -> "IterationProfiler":
        return self

    def __exit__(self, *a) -> bool:
        self.close()
        return False


def format_idle_table(summary: Optional[dict]) -> str:
    if summary is None:
        return "no device plane in the trace"
    idle, window = summary["idle"], summary["window"]
    lines = [f"device busy {summary['busy']:.3f}s, idle {idle:.3f}s of "
             f"{window:.3f}s traced ({idle / window:.1%} idle)"]
    if summary["by_phase"] is None:
        lines.append("  phases not aligned: the trace's step programs and "
                     "the train/dispatch spans differ in number (steps in "
                     "flight at its edges)")
    else:
        for name, secs in sorted(summary["by_phase"].items(),
                                 key=lambda kv: -kv[1]):
            lines.append(f"  {name:<18} {secs:>8.3f}s"
                         + (f" {secs / idle:>7.1%}" if idle else ""))
    for st in summary.get("stalls") or ():
        (op, op_s), (gap_s, phase) = st["longest_op"], st["longest_gap"]
        lines.append(
            f"stall at iteration {st.get('iteration')} (the driver says: "
            f"{st.get('where')}), {st['length']:.3f}s on the device's "
            f"clock: busy {st['busy']:.3f}s, idle {st['idle']:.3f}s; "
            f"longest op {op} {op_s:.3f}s; longest gap {gap_s:.3f}s "
            f"under {phase}")
    return "\n".join(lines)


def annotate(name: str):
    """Named region for the trace viewer (jax.profiler.TraceAnnotation)."""
    import jax

    return jax.profiler.TraceAnnotation(name)
