"""Lightweight span tracer — Chrome-trace/Perfetto JSON, no dependencies.

Reference analog (unverified — mount empty): the reference's per-iteration
``Metrics`` breakdown tells you WHERE an iteration's time went on average;
it cannot correlate one serving request (or one training step) across
subsystems.  Spans do: every span has a ``span_id``, a ``parent_id`` (the
context-local current span at creation), a ``trace_id`` shared by the whole
tree, start/end stamps, and free-form attributes.  Serving spans
additionally carry ``request_id`` so the enqueue→batch→predict→publish path
of one request joins across the client thread / engine thread boundary,
where parent links cannot reach (the batch loop serves many requests at
once — correlation there is by attribute, by design).

Export is the Chrome trace-event format (``{"traceEvents": [...]}``, phase
``"X"`` complete events) which Perfetto and ``chrome://tracing`` load
directly; span ids/attributes ride in ``args``.

Clock: spans stamp ``time.monotonic_ns()`` (``start_ns``/``end_ns``) — the
clock the benchmark's marks use (``time.monotonic()``), one clock for
every process of the machine, and one that cannot step.  Each ``Tracer``
keeps a single wall-clock anchor taken at construction; ``start_s`` /
``end_s`` and the Chrome export's ``ts`` are the monotonic stamps carried
to wall-clock seconds through that anchor, and the export also carries the
raw stamps in ``args`` (``mono_start_ns``/``mono_end_ns``).

Cost when disabled: one module-global ``None`` check per ``span()`` call
(the same posture as ``resilience.faults.fire``).  Enable programmatically
(``obs.trace.enable()``) or via ``BIGDL_TPU_TRACE=/path/out.json`` which
also registers an atexit export.
"""

import contextvars
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from bigdl_tpu.utils.log import get_logger

log = get_logger("bigdl_tpu.obs")

_current: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "bigdl_tpu_current_span", default=None)


class Span:
    """One timed region.  Use as a context manager (via ``Tracer.span`` /
    module-level ``span``); ``set_attribute`` adds attributes mid-flight
    (e.g. a request id only known after admission)."""

    __slots__ = ("name", "span_id", "parent_id", "trace_id", "start_ns",
                 "end_ns", "attrs", "_tracer", "_token", "_tid")

    def __init__(self, tracer: "Tracer", name: str, span_id: str,
                 parent_id: Optional[str], trace_id: str,
                 attrs: Dict[str, Any]):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.trace_id = trace_id
        self.attrs = attrs
        self.start_ns = 0  # time.monotonic_ns()
        self.end_ns = 0
        self._tracer = tracer
        self._token = None
        self._tid = threading.get_ident()

    @property
    def start_s(self) -> float:
        """Wall-clock seconds (through the tracer's anchor)."""
        return self._tracer.wall_s(self.start_ns)

    @property
    def end_s(self) -> float:
        return self._tracer.wall_s(self.end_ns) if self.end_ns else 0.0

    def set_attribute(self, key: str, value: Any) -> "Span":
        self.attrs[key] = value
        return self

    def __enter__(self) -> "Span":
        self._token = _current.set(self)
        self.start_ns = time.monotonic_ns()
        return self

    def end(self) -> "Span":
        """Finish the span NOW (idempotent; the context exit becomes a
        no-op).  For handlers whose LAST wire write is what signals
        completion to the client: ending before that write guarantees a
        reader reacting to the completion event sees the span exported,
        instead of racing the handler thread to the context exit."""
        if self.end_ns:
            return self
        self.end_ns = time.monotonic_ns()
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        self._tracer._finish(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.attrs["error"] = f"{exc_type.__name__}: {exc}"
        self.end()
        return False


class _NullSpan:
    """The disabled-tracer stand-in: every operation is a no-op."""

    __slots__ = ()

    def set_attribute(self, key, value):
        return self

    def end(self):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL = _NullSpan()


class Tracer:
    """Collects finished spans in a bounded ring (oldest evicted first —
    a long-running server must not grow without bound) and exports them
    as Chrome-trace JSON."""

    def __init__(self, max_spans: int = 20000):
        self._spans: "deque[Span]" = deque(maxlen=max_spans)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        # the one wall-clock reading: (wall seconds, monotonic ns) of the
        # same moment.  Everything exported as wall time goes through it
        self._anchor = (time.time(), time.monotonic_ns())

    def wall_s(self, mono_ns: int) -> float:
        """A ``time.monotonic_ns()`` stamp as wall-clock seconds."""
        return self._anchor[0] + (mono_ns - self._anchor[1]) * 1e-9

    def _mono_ns(self, wall_s: float) -> int:
        return self._anchor[1] + int(round((wall_s - self._anchor[0]) * 1e9))

    def _next_id(self) -> str:
        with self._lock:
            return f"{next(self._ids):x}"

    def span(self, name: str, **attrs) -> Span:
        parent = _current.get()
        sid = self._next_id()
        if parent is not None:
            return Span(self, name, sid, parent.span_id, parent.trace_id,
                        attrs)
        return Span(self, name, sid, None, sid, attrs)

    def _finish(self, span: Span) -> None:
        with self._lock:
            self._spans.append(span)

    def add_event(self, name: str, start_s: float, end_s: float,
                  **attrs) -> Span:
        """Append an explicitly-timed span (``start_s``/``end_s`` in
        wall-clock ``time.time()`` seconds, carried onto the span clock
        through the anchor) — for call sites that time a
        region themselves (the decode engine's per-token steps span a
        jitted call shared by many requests; each request's event carries
        the same wall window with its own ``request_id``).  No
        contextvars involvement: these events correlate by attribute, not
        by parent link (docs/observability.md §Decode timelines)."""
        return self.add_span(name, self._mono_ns(float(start_s)),
                             self._mono_ns(float(end_s)), **attrs)

    def add_span(self, name: str, start_ns: int, end_ns: int,
                 **attrs) -> Span:
        """:meth:`add_event` for a region stamped on the span clock itself
        (``time.monotonic_ns()``), by the thread that ran it."""
        sid = self._next_id()
        s = Span(self, name, sid, None, sid, attrs)
        s.start_ns = int(start_ns)
        s.end_ns = max(int(end_ns), s.start_ns)
        self._finish(s)
        return s

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def chrome_trace(self) -> Dict[str, Any]:
        """The trace-event dict (phase-X complete events, microsecond
        timestamps) Perfetto/chrome://tracing load as-is."""
        events = []
        pid = os.getpid()
        for s in self.spans():
            args = {"span_id": s.span_id, "trace_id": s.trace_id,
                    "mono_start_ns": s.start_ns, "mono_end_ns": s.end_ns}
            if s.parent_id is not None:
                args["parent_id"] = s.parent_id
            args.update(s.attrs)
            events.append({
                "name": s.name, "cat": s.name.split("/", 1)[0], "ph": "X",
                "ts": s.start_s * 1e6,
                "dur": max(s.end_ns - s.start_ns, 0) * 1e-3,
                "pid": pid, "tid": s._tid, "args": args})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            # default=str: one exotic span attribute (np scalar, enum)
            # must not lose the whole trace at the atexit export
            json.dump(self.chrome_trace(), f, default=str)
        log.info("chrome trace (%d spans) written to %s",
                 len(self._spans), path)
        return path


# -- module-level tracer (what the instrumented sites consult) --------------

_tracer: Optional[Tracer] = None
_env_checked = False
_install_lock = threading.RLock()  # enable() may be re-entered via active()
_atexit_path: Optional[str] = None
_atexit_armed = False

NULL_SPAN = _NULL  # for call sites that build span attributes lazily


def _export_at_exit() -> None:
    # one registered hook reading the CURRENT tracer/path — re-enabling
    # must not stack exporters that overwrite each other's file
    t, p = _tracer, _atexit_path
    if t is not None and p:
        t.export_chrome_trace(p)


def enable(path: Optional[str] = None, max_spans: int = 20000) -> Tracer:
    """Install a process-wide tracer.  ``path`` additionally arms a single
    atexit export (of whatever tracer is current at exit) so a traced run
    needs no explicit teardown."""
    global _tracer, _env_checked, _atexit_path, _atexit_armed
    with _install_lock:
        _tracer = Tracer(max_spans=max_spans)
        _env_checked = True
        if path and not _atexit_armed:
            import atexit

            atexit.register(_export_at_exit)
            _atexit_armed = True
        # pathless enable() clears any leftover path: this tracer was not
        # asked for a file, so exit must not overwrite an earlier run's
        _atexit_path = path
        return _tracer


def disable() -> None:
    global _tracer, _env_checked, _atexit_path
    _tracer = None
    _atexit_path = None
    _env_checked = True  # explicit disable also suppresses the env plan


def get() -> Optional[Tracer]:
    return _tracer


def active() -> Optional[Tracer]:
    """The process tracer, or None when tracing is off — after the lazy
    ``BIGDL_TPU_TRACE`` probe (done once, under a lock: concurrent first
    spans from serving threads must not each install a tracer and split
    the trace between them).  Hot call sites use this to skip building
    span attributes entirely when disabled."""
    global _env_checked
    if _tracer is None:
        if _env_checked:
            return None
        with _install_lock:
            if _tracer is None and not _env_checked:
                path = os.environ.get("BIGDL_TPU_TRACE")
                _env_checked = True
                if path:
                    enable(path)
    return _tracer


def current_span():
    """The context-local active span (None outside any span) — lets call
    sites annotate whatever region they run under without threading a
    span object through every signature."""
    return _current.get()


def span(name: str, **attrs):
    """Instrumented-site entry: near-zero cost when tracing is off (one
    None check after the lazy env probe)."""
    t = active()
    return _NULL if t is None else t.span(name, **attrs)


# -- timed regions -----------------------------------------------------------

_collector: Optional[Tracer] = None  # a program-owned device trace is running
# the one clock of the spans, the driver's phases and the stall rule (and
# of the benchmark's marks); a test may put its own here
now_ns = time.monotonic_ns


def collect_into(tracer: Optional[Tracer]) -> None:
    """``utils.profiling.IterationProfiler`` hands over a tracer of its own
    while ITS ``jax.profiler`` trace runs (None when it stops): every
    :class:`timed` region is then also recorded there, whether or not the
    process tracer is on, so the program's phases can be laid over the
    device's ops.  Otherwise (the default, and under anyone else's
    profiler) a region checks this one variable and does nothing more."""
    global _collector
    _collector = tracer


def record(name: str, start_ns: int, end_ns: int, **attrs) -> None:
    """A region whose two ends were stamped (``time.monotonic_ns()``) by
    different threads — a batch made by a worker pool starts in one
    worker and ends in another, so no ``with`` block can hold it.  Lands
    where a :class:`timed` region would: the process tracer when it is
    on, and the collector of a program-owned device trace."""
    for t in {active(), _collector} - {None}:
        t.add_span(name, start_ns, end_ns, **attrs)


class timed:
    """One instrumented region that is span and stopwatch at once, so the
    two cannot disagree about where it starts and ends: opens
    ``span(name, **attrs)`` (a no-op when the tracer is off), is recorded
    under :func:`collect_into`, and always leaves its end stamp in
    ``.end_ns`` and the elapsed seconds in ``.seconds``, both read off
    :data:`now_ns`."""

    __slots__ = ("name", "attrs", "seconds", "end_ns", "_span", "_ns0",
                 "_into")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.seconds = 0.0
        self.end_ns = 0

    def __enter__(self) -> "timed":
        self._span = span(self.name, **self.attrs).__enter__()
        self._into = _collector
        self._ns0 = now_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = now_ns()
        self.seconds = (self.end_ns - self._ns0) * 1e-9
        c = _collector
        if c is not None and c is self._into:
            c.add_span(self.name, self._ns0, self.end_ns, **self.attrs)
        self._span.__exit__(*exc)
        return False
