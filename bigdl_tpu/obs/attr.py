"""Step-time attribution — every training run becomes an explained run.

Reference analog (unverified — mount empty): ``dllib/optim/Metrics.scala``
logged per-iteration "computing time average / get weights average / put
gradient" splits; under XLA the iteration is one fused program, so the
meaningful decomposition is host-side: what the DRIVER THREAD was doing.
Six components, each a mutually exclusive interval of that thread, each
measured where the work happens through one helper
(:meth:`StepAttribution.phase`: span and histogram observation of the
same interval):

- **data**     — blocked in ``next()`` on the input pipeline
  (``train.data_wait_s``; split further by ``data.batch_wait_s`` /
  ``data.put_s``, docs/data.md)
- **dispatch** — issuing the jitted bundle, compile seconds taken out
- **compile**  — tracing, lowering and XLA compilation (or the load of a
  cached program) that fell inside a dispatch
- **sync**     — the loss fetch at a log point: the host waiting for the
  device
- **overhead** — triggers (validation, checkpoints, histograms, the
  ``end_when`` calls) and the log point's bookkeeping after the fetch
- **other**    — each loop iteration's wall minus the five above: host time
  nobody timed.  Reported, never hidden; it should stay near zero

Every occurrence lands in its ``train.attr.<name>_s`` histogram on
``/metrics`` (``data`` keeps its older name); the run total is the
end-of-run "where did the time go" table (:meth:`StepAttribution.table`).
:func:`idle_by_phase` carries the same names onto a device trace: idle
seconds of the chip by what the driver was doing meanwhile.

This module also owns two run-health sentinels:

- :class:`RecompileSentinel` — counts XLA cache misses mid-run via
  ``jax.monitoring`` backend-compile events; a compile that fires after
  the run went steady and outside an :func:`expected_compile` region is
  an *unexpected recompile* (shape drift, cache invalidation) — counted
  and flight-recorded.
- :func:`host_step_time_stats` — cross-process aggregation for
  multi-process meshes: allgathers each host's window step time and
  yields max/min/skew (straggler detection).
"""

import statistics
import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from bigdl_tpu.obs import flight, trace
from bigdl_tpu.utils.log import get_logger

log = get_logger("bigdl_tpu.obs")

COMPONENTS = ("data", "dispatch", "compile", "sync", "overhead", "other")
# one name per interval: the data phase IS the data wait
HISTOGRAMS = {c: f"train.attr.{c}_s" for c in COMPONENTS}
HISTOGRAMS["data"] = "train.data_wait_s"


class _Phase:
    """One occurrence of a driver phase (see :meth:`StepAttribution.phase`)."""

    __slots__ = ("_attr", "_name", "_steps", "_timed", "_compile0")

    def __init__(self, attr: "StepAttribution", name: str, steps: int,
                 attrs):
        self._attr = attr
        self._name = name
        self._steps = steps
        self._timed = trace.timed(f"train/{name}", **attrs)

    @property
    def seconds(self) -> float:
        """The whole interval (a dispatch's compile seconds included)."""
        return self._timed.seconds

    @property
    def end_ns(self) -> int:
        """Where the interval ended, on ``obs.trace.now_ns``."""
        return self._timed.end_ns

    def __enter__(self) -> "_Phase":
        if self._name == "dispatch":
            self._compile0 = compile_seconds()
        self._timed.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._timed.__exit__(*exc)
        secs = self._timed.seconds
        if self._name == "dispatch":
            compiled = min(compile_seconds() - self._compile0, secs)
            if compiled > 0:
                self._attr.book("compile", compiled)
                secs -= compiled
        self._attr.book(self._name, secs)
        self._attr.steps += self._steps
        return False


class StepAttribution:
    """Books every occurrence of a driver phase into its histogram and the
    run totals, closes each loop iteration on its wall time (``other`` is
    what the phases left over), and prints the end-of-run table."""

    def __init__(self, metrics=None):
        if metrics is None:
            from bigdl_tpu.optim.metrics import global_metrics

            metrics = global_metrics()
        self.metrics = metrics
        self.steps = 0
        self.wall_s = 0.0
        self.totals: Dict[str, float] = {c: 0.0 for c in COMPONENTS}
        self.windows = 0  # loss fetches seen: log points and flushes
        self.stalls = StallWatch(metrics)
        self._t_iter: Optional[float] = None  # open iteration's start
        self._booked = 0.0  # phase seconds booked since then

    def phase(self, name: str, steps: int = 0, **attrs) -> _Phase:
        """Context manager around one occurrence of phase ``name`` on the
        driver thread: the ``train/<name>`` span (a no-op when the tracer
        is off; ``attrs`` are its attributes; under ``set_profile()``'s
        own trace it is recorded for the idle-by-phase table whatever the
        tracer) and, always, the elapsed seconds into the phase's
        histogram — one interval, so span and counter cannot disagree.
        A ``dispatch`` names the train ``steps`` it issues and books the
        compile seconds that fell inside it under ``compile`` instead."""
        return _Phase(self, name, steps, attrs)

    def book(self, name: str, seconds: float) -> None:
        self.totals[name] += seconds
        self._booked += seconds
        if name == "sync":
            self.windows += 1
        self.metrics.observe(HISTOGRAMS[name], seconds)

    def begin(self, now: Optional[float] = None) -> None:
        """The loop starts (or restarts after a recovery) NOW."""
        self._t_iter = trace.now_ns() * 1e-9 if now is None else now
        self._booked = 0.0

    def end_iteration(self, now: Optional[float] = None) -> None:
        """One pass of the driver loop ends NOW: its wall time minus what
        its phases booked is ``other``.  The phases are disjoint intervals
        inside the iteration, so ``other`` is the host time between them
        and cannot be negative."""
        if self._t_iter is None:
            return
        now = trace.now_ns() * 1e-9 if now is None else now
        wall = now - self._t_iter
        self.book("other", wall - self._booked)
        self.wall_s += wall
        self._t_iter, self._booked = now, 0.0

    def report(self) -> Dict[str, Any]:
        """Run totals + fractions — the machine-readable table."""
        out: Dict[str, Any] = {
            "steps": self.steps, "wall_s": self.wall_s,
            "windows": self.windows, "components": {},
        }
        for name in COMPONENTS:
            t = self.totals[name]
            out["components"][name] = {
                "total_s": t,
                "per_step_s": t / self.steps if self.steps else 0.0,
                "fraction": t / self.wall_s if self.wall_s else 0.0,
            }
        return out

    def table(self) -> str:
        """The end-of-run "where did the time go" table (logged by the
        driver)."""
        rep = self.report()
        lines = [
            f"step-time attribution over {rep['steps']} steps "
            f"({rep['wall_s']:.3f}s wall):",
            f"  {'component':<10} {'total_s':>10} {'per_step_ms':>12} "
            f"{'fraction':>9}",
        ]
        for name in COMPONENTS:
            c = rep["components"][name]
            lines.append(
                f"  {name:<10} {c['total_s']:>10.3f} "
                f"{c['per_step_s'] * 1e3:>12.3f} {c['fraction']:>8.1%}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# stalls: the step intervals judged by the run's own recent ones
# ---------------------------------------------------------------------------

STALL_COUNTERS = ("train.stalls", "train.stall_s", "train.stall_host_s",
                  "train.stall_device_s")


class StallWatch:
    """The program's one per-step interval and the stall rule on it
    (docs/observability.md §Stalls).

    **The interval**: between two consecutive returns of the loss fetch
    (the ``sync`` phase's own end stamps) over the steps the later fetch
    covered, into ``train.step_time_s``.  One that holds a compilation of
    the driver thread's, a trigger's work or a recovery
    (:meth:`exclude`), and the first, is no sample: booked elsewhere.

    **The rule**: baseline = the median of the last 32 samples (stalls and
    run-ahead witnesses left out), judged once 8 exist.  A fetch of *k*
    steps whose interval exceeds *k* baselines by more than max(0.25 s,
    one baseline) opens a stall.

    **The witness** is the next sample.  The fetch comes one bundle late,
    so *a* steps were in flight while the long fetch waited.  Had the
    device's work come late, they could only start when it returned, and
    the next interval is an ordinary one: ``device``, lost = the long
    interval minus its steps' baselines.  Had anything else (interpreter,
    process, the runtime's copy back), the device ran ahead and the next
    interval comes in short by more than *a*/2 baselines (at one step a
    fetch: under half a baseline): ``host``, lost = both intervals minus
    their steps' baselines.  No next sample, or nothing in flight:
    ``unknown``.  And ``host`` whatever came next where more than half of
    the lost seconds fell OUTSIDE the fetch (``interval_s - waited_s``):
    the driver was not waiting for the device, and with the next bundle
    not yet queued nothing could run ahead (the chip showed it, PERF.md
    §6 PR 36).  Booked then: the four counters (at 0 from the start), span
    ``train/stall`` over the long interval, flight event ``train_stall``
    and a warning with the host's alibi (``obs/host.py``)."""

    WINDOW, MIN_SAMPLES, FLOOR_S = 32, 8, 0.25

    def __init__(self, metrics, probes=None):
        self.metrics = metrics
        self.probes = probes
        self._recent: deque = deque(maxlen=self.WINDOW)
        self._last_ns: Optional[int] = None  # the previous fetch's return
        self._compiled = 0.0
        self._open: Optional[Dict[str, Any]] = None  # awaits its witness
        for name in STALL_COUNTERS:
            metrics.inc(name, 0)

    def exclude(self) -> None:
        """What the driver does next is not step time: the interval it
        falls in is no sample, and cannot be a witness either."""
        self._last_ns = None
        self._book("unknown")

    def fetched(self, end_ns: int, waited_s: float, steps: int,
                in_flight: int, iteration: int) -> Optional[float]:
        """A loss fetch covering ``steps`` steps returned at ``end_ns``
        after ``waited_s`` in the fetch, ``in_flight`` steps still
        dispatched.  The per-step wall seconds of the interval it closes:
        None at the first fetch and after :meth:`exclude`; a sample unless
        the driver compiled in it."""
        last, self._last_ns = self._last_ns, end_ns
        compiled, was = compile_seconds(), self._compiled
        self._compiled = compiled
        if last is None or steps <= 0:
            self._book("unknown")
            return None
        interval = (end_ns - last) * 1e-9
        per_step = interval / steps
        if compiled != was:
            self._book("unknown")
            return per_step
        self.metrics.observe("train.step_time_s", per_step)
        o = self._open
        if o is not None:
            short = steps * o["baseline_s"] - interval
            ahead = o["in_flight"] * o["baseline_s"]
            where = ("unknown" if not ahead
                     else "host" if short > ahead / 2 else "device")
            self._book(where, -short)
            if where == "host":  # not an ordinary interval either
                return per_step
        if len(self._recent) >= self.MIN_SAMPLES:
            base = statistics.median(self._recent)
            excess = interval - steps * base
            if excess > max(self.FLOOR_S, base):
                self._open = {
                    "iteration": iteration, "steps": steps,
                    "in_flight": in_flight, "interval_s": interval,
                    "waited_s": waited_s, "baseline_s": base,
                    "lost_s": excess,
                    "start_ns": last, "end_ns": end_ns}
                return per_step
        self._recent.append(per_step)
        return per_step

    def _book(self, where: str, witness_excess: float = 0.0) -> None:
        o, self._open = self._open, None
        if o is None:
            return
        if where == "host":
            o["lost_s"] += witness_excess
        elif o["interval_s"] - o["waited_s"] > o["lost_s"] / 2:
            where = "host"  # lost outside the fetch: nobody waited
        start, end = o.pop("start_ns"), o.pop("end_ns")
        o["where"] = where
        o["alibi"] = self.probes.alibi(start, end) if self.probes else {}
        self.metrics.inc("train.stalls")
        self.metrics.inc("train.stall_s", o["lost_s"])
        if where != "unknown":
            self.metrics.inc(f"train.stall_{where}_s", o["lost_s"])
        trace.record("train/stall", start, end, where=where,
                     lost_s=o["lost_s"], iteration=o["iteration"])
        flight.record("train_stall", **o)
        log.warning(
            "stall at iteration %d: a fetch of %d step(s) took %.3fs (%.3fs "
            "inside the fetch, %d step(s) in flight) where the baseline is "
            "%.3fs a step; %.3fs lost, whose: %s; host's alibi: %s",
            o["iteration"], o["steps"], o["interval_s"], o["waited_s"],
            o["in_flight"], o["baseline_s"], o["lost_s"], where, o["alibi"])


# ---------------------------------------------------------------------------
# device idle time, by what the driver was doing
# ---------------------------------------------------------------------------

Interval = Tuple[float, float, str]


def _innermost(intervals: Iterable[Interval]) -> List[Interval]:
    """Properly nested ``(start, end, name)`` intervals of ONE thread ->
    disjoint segments in time order, each named by the innermost interval
    that covers it (``data/put`` inside ``train/data`` wins there)."""
    out: List[Interval] = []
    stack: List[Tuple[float, str]] = []  # (end, name), outermost first
    cur = 0.0

    def close(t: float) -> None:
        nonlocal cur
        if t > cur:
            out.append((cur, t, stack[-1][1]))
        cur = max(cur, t)

    for s, e, name in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        while stack and stack[-1][0] <= s:
            close(stack[-1][0])
            stack.pop()
        if stack:
            close(s)
        else:
            cur = s
        stack.append((e, name))
    while stack:
        close(stack[-1][0])
        stack.pop()
    return out


def clock_offset(dispatch_starts: Iterable[float],
                 program_starts: Iterable[float]) -> Optional[float]:
    """Host clock minus device-trace clock, from the k-th ``train/dispatch``
    span and the k-th run of the step program on the device: a program
    cannot start before the call that issued it began, so the offset is
    the largest ``dispatch start - program start``.  Its error is the
    launch latency of that one call (well under a dispatch's few ms).
    None unless the two counts agree: steps were in flight when the trace
    started or stopped, and the pairing would be a guess."""
    d, p = list(dispatch_starts), list(program_starts)
    if not d or len(d) != len(p):
        return None
    return max(a - b for a, b in zip(d, p))


def idle_by_phase(device_intervals: Iterable[Tuple[float, float]],
                  host_intervals: Iterable[Interval]) -> Optional[dict]:
    """Union-and-gaps walk over one chip's op intervals ``(start, end)``,
    each idle gap's length divided among the driver-thread intervals
    ``(start, end, name)`` that cover it (nested ones: the innermost);
    what no interval covers goes under ``"none"``.  Both on one clock, in
    one unit, which is the unit of the result: ``{"busy", "idle",
    "window", "by_phase": {name: idle}}``.  Pure; None without ops."""
    dev = sorted((float(iv[0]), float(iv[1])) for iv in device_intervals)
    if not dev:
        return None
    gaps, busy = [], 0.0
    cur_s, cur_e = dev[0]
    for s, e in dev[1:]:
        if s > cur_e:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    busy += cur_e - cur_s
    segs = _innermost(host_intervals)
    by: Dict[str, float] = defaultdict(float)
    i = 0
    for g0, g1 in gaps:
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        covered, j = 0.0, i
        while j < len(segs) and segs[j][0] < g1:
            s, e, name = segs[j]
            overlap = min(g1, e) - max(g0, s)
            by[name] += overlap
            covered += overlap
            j += 1
        by["none"] += (g1 - g0) - covered
    return {"busy": busy, "idle": sum(g1 - g0 for g0, g1 in gaps),
            "window": cur_e - dev[0][0], "by_phase": dict(by)}


def stall_on_device(ops: Iterable[Tuple[float, float, str]],
                    host_intervals: Iterable[Interval],
                    start: float, end: float, scale: float = 1.0) -> dict:
    """One ``train/stall`` span ``[start, end]`` seen from the chip: its
    ``length``, the ``busy`` and ``idle`` time inside it, the
    ``longest_op`` ``(name, length)`` and the ``longest_gap`` ``(length,
    phase)``, the phase being the driver-thread interval that covered most
    of the gap.  Everything on one clock, as in :func:`idle_by_phase`, in
    its unit times ``scale``.  A device that was late shows one long op;
    one that ran ahead, one long gap."""
    inside = sorted((max(s, start), min(e, end), n) for s, e, n in ops
                    if s < end and e > start)
    busy, cur, gap = 0.0, start, (0.0, start)
    for s, e, _ in inside + [(end, end, "")]:
        if s - cur > gap[0]:
            gap = (s - cur, cur)
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    g0, g1 = gap[1], gap[1] + gap[0]
    cover: Dict[str, float] = defaultdict(float)
    for s, e, name in _innermost(host_intervals):
        if s < g1 and e > g0:
            cover[name] += min(g1, e) - max(g0, s)
    op = max(inside, key=lambda o: o[1] - o[0], default=(0.0, 0.0, "none"))
    return {"length": (end - start) * scale, "busy": busy * scale,
            "idle": ((end - start) - busy) * scale,
            "longest_op": (op[2], (op[1] - op[0]) * scale),
            "longest_gap": (gap[0] * scale, max(cover, key=cover.get)
                            if cover else "none")}


# ---------------------------------------------------------------------------
# recompilation sentinel
# ---------------------------------------------------------------------------

_expected = threading.local()


def _expected_depth() -> int:
    return getattr(_expected, "depth", 0)


@contextmanager
def expected_compile():
    """Mark the calling thread's region as an EXPECTED compile site (a new
    bundle size, a fresh eval program, a plateau LR rebake) so the
    recompile sentinel doesn't flag it."""
    _expected.depth = _expected_depth() + 1
    try:
        yield
    finally:
        _expected.depth = _expected_depth() - 1


_compiling = threading.local()  # .total seconds, .spans [(start, end)]


def compile_seconds() -> float:
    """Seconds the CALLING thread has spent tracing, lowering and
    compiling (or loading cached programs) so far: the union of the
    intervals of every ``/jax/core/compile/*_duration`` event it emitted.
    A phase takes the difference around a call to learn how much of the
    call was compilation."""
    return getattr(_compiling, "total", 0.0)


def _note_compile(duration_s: float) -> None:
    # the listener runs on the compiling thread as the timed region ends,
    # so the region is [now - duration, now].  Regions nest (an inner
    # jit's trace inside the outer's) and arrive inner first: a later one
    # swallows the earlier ones it contains, so nothing counts twice
    now = time.perf_counter()
    start = now - duration_s
    spans = _compiling.__dict__.setdefault("spans", [])
    total = compile_seconds()
    while spans and spans[-1][0] >= start:
        a, b = spans.pop()
        total -= b - a
    if spans and spans[-1][1] > start:
        start = spans[-1][1]
    spans.append((start, now))
    del spans[:-64]
    _compiling.total = total + (now - start)


class RecompileSentinel:
    """Counts XLA backend compiles via ``jax.monitoring`` events.

    Every compile increments ``train.xla_compiles_total`` and lands in the
    ``train.compile_time_s`` histogram.  After :meth:`mark_steady` (the
    driver calls it once warmup compiles are done), a compile outside an
    :func:`expected_compile` region additionally increments
    ``train.unexpected_recompiles_total`` and records an
    ``unexpected_recompile`` flight event — the mid-run cache-miss signal
    (shape drift, donation breakage, cache eviction) that silently
    multiplies step time."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    # tracing, lowering and backend compilation: what compile_seconds()
    # totals per thread (the step attribution's ``compile`` phase)
    COMPILE_PREFIX = "/jax/core/compile/"

    def __init__(self):
        self._steady = False
        self._step: Optional[int] = None
        self._registered = False

    # listener plumbing -----------------------------------------------------
    def install(self) -> "RecompileSentinel":
        """Register the jax.monitoring listener once per process (jax has
        no unregister; the listener is a no-op-cheap counter)."""
        if self._registered:
            return self
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._registered = True
        return self

    def _on_event(self, name: str, duration_s: float, **kw) -> None:
        if name.startswith(self.COMPILE_PREFIX):
            _note_compile(float(duration_s))
        if name != self.EVENT:
            return
        try:
            from bigdl_tpu.optim.metrics import global_metrics

            m = global_metrics()
            m.inc("train.xla_compiles_total")
            m.observe("train.compile_time_s", float(duration_s))
            if self._steady and _expected_depth() == 0:
                m.inc("train.unexpected_recompiles_total")
                flight.record("unexpected_recompile",
                              duration_s=float(duration_s),
                              step=self._step)
                log.warning(
                    "unexpected XLA recompile mid-run (%.3fs, step %s): "
                    "input shapes drifted or the compile cache was "
                    "invalidated", duration_s, self._step)
        except Exception:  # a metrics bug must never sink a compile
            pass

    # driver hooks ----------------------------------------------------------
    def mark_steady(self, step: Optional[int] = None) -> None:
        """Warmup is over: from here every unannounced compile is a cache
        miss worth flagging."""
        self._steady = True
        self._step = step

    def note_step(self, step: int) -> None:
        self._step = step

    def mark_warmup(self) -> None:
        """Back to warmup (run ended / new run starting): compiles are
        expected again."""
        self._steady = False
        self._step = None

    @property
    def steady(self) -> bool:
        return self._steady


_sentinel: Optional[RecompileSentinel] = None
_sentinel_lock = threading.Lock()


def recompile_sentinel() -> RecompileSentinel:
    """The process-wide sentinel, listener installed on first use."""
    global _sentinel
    if _sentinel is None:
        with _sentinel_lock:
            if _sentinel is None:
                _sentinel = RecompileSentinel().install()
    return _sentinel


# ---------------------------------------------------------------------------
# cross-process aggregation (straggler skew)
# ---------------------------------------------------------------------------

def step_time_stats(values) -> Dict[str, float]:
    """max/min/skew/mean over per-host step times (pure; unit-testable
    without a multi-process mesh)."""
    vals = np.ravel(np.asarray(values, np.float64))
    if vals.size == 0:
        return {}
    return {"max": float(vals.max()), "min": float(vals.min()),
            "skew": float(vals.max() - vals.min()),
            "mean": float(vals.mean()), "n_hosts": int(vals.size)}


def host_step_time_stats(step_time_s: float) -> Optional[Dict[str, float]]:
    """Allgather this host's window step time and reduce to straggler
    stats.  Multi-process only (None on a single process); every process
    must call at the same cadence (the driver's deterministic log points
    guarantee it).  The caller exports the result as the
    ``train.step_time_{max,min,skew}_s`` gauges."""
    import jax

    if jax.process_count() <= 1:
        return None
    from jax.experimental import multihost_utils

    vals = multihost_utils.process_allgather(
        np.asarray([step_time_s], np.float64))
    return step_time_stats(vals)
