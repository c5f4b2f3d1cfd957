"""What the host was doing — the alibi a stalled step's record carries.

``obs.attr.StallWatch`` finds a stall and says whether the device's work
or something else came late; these probes say what the host did meanwhile.
``Optimizer.optimize()`` starts them and stops them where it leaves; none
has an option, and the driver thread reads them only to book a stall.

- every garbage collection's pause (``gc.callbacks``): ``host.gc_pause_s``,
  ``host.gc_collections{generation}``, span ``host/gc`` (attr ``thread``);
- a heartbeat: thread ``obs-heartbeat`` sleeps ``BEAT_S`` and measures how
  late it woke; over ``LATE_S`` goes into ``host.heartbeat_late_s``.  Late
  by a stall's length: the interpreter or the whole process was held.  On
  time: only the driver thread waited, on the runtime;
- once a second the same thread takes a :func:`kernel_reading`, into a
  ring of the last ``RING``.
"""

import gc
import resource
import threading
from collections import deque
from typing import Any, Callable, Dict, Optional

from bigdl_tpu.obs import trace

BEAT_S, LATE_S, KERNEL_EVERY_S, RING = 0.05, 0.01, 1.0, 64


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return ""


def kernel_reading(tid: int) -> Dict[str, float]:
    """Running totals, each only where its file is readable: seconds thread
    ``tid`` waited on a run queue, the process's major faults and
    involuntary context switches, seconds the cgroup was throttled, seconds
    some task stalled on memory (machine-wide pressure)."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {"major_faults": float(ru.ru_majflt),
           "involuntary_switches": float(ru.ru_nivcsw)}
    sched = _read(f"/proc/self/task/{tid}/schedstat").split()
    if len(sched) >= 2:
        out["runq_wait_s"] = int(sched[1]) * 1e-9
    for line in _read("/sys/fs/cgroup/cpu.stat").splitlines():
        if line.startswith("throttled_usec "):
            out["throttled_s"] = int(line.split()[1]) * 1e-6
    some = _read("/proc/pressure/memory").partition("\n")[0]
    if some.startswith("some ") and "total=" in some:
        out["memory_pressure_s"] = int(some.rpartition("total=")[2]) * 1e-6
    return out


class HostProbes:
    """The three probes of one ``optimize()`` call (module docstring).
    ``sleep`` and ``clock_ns`` are the heartbeat's, for a test to put its
    own in."""

    def __init__(self, metrics, sleep: Optional[Callable] = None,
                 clock_ns: Optional[Callable[[], int]] = None):
        self.metrics = metrics
        self._stop = threading.Event()
        self._sleep = sleep or self._stop.wait
        self._clock = clock_ns or (lambda: trace.now_ns())
        self._gc_open = self._beat_at = 0
        self._gc_done: deque = deque()            # not yet booked
        self._gcs: deque = deque(maxlen=RING)     # (start, end, generation)
        self._late: deque = deque(maxlen=RING)    # (woke, seconds late)
        self._kernel: deque = deque(maxlen=RING)  # (read at, reading)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="obs-heartbeat")

    def start(self) -> "HostProbes":
        from bigdl_tpu.optim.metrics import global_metrics

        for m in {self.metrics, global_metrics()}:  # read 0, not nothing
            m.ensure_hist("host.gc_pause_s")
            m.ensure_hist("host.heartbeat_late_s")
        self._tid = threading.get_native_id()  # the driver thread
        self._kernel.append((self._clock(), kernel_reading(self._tid)))
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        self._book_gcs()

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        # runs in whichever thread allocated, possibly inside a registry's
        # or the tracer's lock: stamp and leave, the heartbeat books it
        if phase == "start":
            self._gc_open = self._clock()
        elif self._gc_open:
            self._gc_done.append((self._gc_open, self._clock(),
                                  info.get("generation", -1),
                                  threading.current_thread().name))
            self._gc_open = 0

    def _book_gcs(self) -> None:
        while True:
            try:  # the heartbeat and a stall's booking may both be here
                t0, t1, gen, who = self._gc_done.popleft()
            except IndexError:
                return
            self._gcs.append((t0, t1, gen))
            self.metrics.observe("host.gc_pause_s", (t1 - t0) * 1e-9)
            self.metrics.inc("host.gc_collections",
                             labels={"generation": gen})
            trace.record("host/gc", t0, t1, generation=gen, thread=who)

    def _run(self) -> None:
        read_at = self._clock()
        while not self._stop.is_set():
            t = self._beat_at = self._clock()
            self._sleep(BEAT_S)
            now = self._clock()
            late = (now - t) * 1e-9 - BEAT_S
            if late > LATE_S:
                self._late.append((now, late))
                self.metrics.observe("host.heartbeat_late_s", late)
            self._book_gcs()
            if (now - read_at) * 1e-9 >= KERNEL_EVERY_S:
                read_at = now
                self._kernel.append((now, kernel_reading(self._tid)))

    def alibi(self, start_ns: int, end_ns: int) -> Dict[str, Any]:
        """What the probes saw over ``[start_ns, end_ns]``: the largest
        heartbeat lateness of a beat that slept into it (the one still
        asleep too: the driver may be the first to wake), the collections
        that overlapped it, and the kernel's totals now minus the last
        reading taken before it began (``kernel_over_s`` apart)."""
        self._book_gcs()
        gcs = [(t1 - t0) * 1e-9 for t0, t1, _ in list(self._gcs)
               if t0 < end_ns and t1 > start_ns]
        lates = [late for woke, late in list(self._late)
                 if woke > start_ns and woke - late * 1e9 < end_ns]
        if self._beat_at < end_ns:
            lates.append((self._clock() - self._beat_at) * 1e-9 - BEAT_S)
        out: Dict[str, Any] = {
            "heartbeat_late_s": max(lates + [0.0]),
            "gc_pause_s": sum(gcs), "gc_collections": len(gcs)}
        before = [r for r in list(self._kernel) if r[0] <= start_ns][-1:]
        if before:
            at, was = before[0]
            now = kernel_reading(self._tid)
            out["kernel_over_s"] = (self._clock() - at) * 1e-9
            out.update({k: now[k] - was[k] for k in now if k in was})
        return out
