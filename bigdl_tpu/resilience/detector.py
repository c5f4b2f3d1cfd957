"""Failure detection: multi-process heartbeats + single-process watchdog.

Reference analog (unverified — mount empty): the reference leans on Spark's
executor liveness (driver heartbeat timeouts) to learn a worker died.  The
TPU multi-controller world has no driver, so liveness is peer-observable
state: each process writes a heartbeat file under a shared directory
(checkpoint bucket or shared filesystem — the same visibility requirement
sharded checkpoints already impose), and any process can run a monitor over
the set.

Suspicion is phi-accrual style (Hayashibara et al.; the Akka/Cassandra
detector): instead of a fixed timeout, the monitor keeps a window of
inter-arrival times per peer and reports a CONTINUOUS suspicion level

    phi(elapsed) = -log10( P(a beat takes longer than elapsed) )

under a normal model of the window.  phi ≈ 1 means "this gap would happen
~10% of the time", phi ≥ 8 is practical certainty of death.  The caller
picks the threshold (``FailurePolicy.heartbeat_phi_threshold``) to trade
detection latency against false positives from GC/compile pauses.

The single-process :class:`StepWatchdog` covers the failures heartbeats
cannot see: a HUNG step (the process is alive, the chip is wedged) and a
POISONED step (loss went NaN/Inf — the process is healthy but the model is
dying).  Both are flagged from the driver loop's own observations; the NaN
streak raises :class:`~.retry.PoisonedStepError` so the recovery path
classifies it as data, not infrastructure.

All clocks are injectable (``clock=``) so tests advance time without
sleeping.
"""

import json
import math
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from bigdl_tpu.resilience.retry import PoisonedStepError
from bigdl_tpu.utils import storage
from bigdl_tpu.utils.log import get_logger

log = get_logger("bigdl_tpu.resilience")


def _hb_path(directory: str, index: int) -> str:
    return storage.join(directory, f"hb-{index:05d}.json")


class Heartbeat:
    """Per-process heartbeat writer.  ``beat()`` writes one beat (tests,
    or callers that beat from their own loop); ``start()`` spawns a daemon
    thread beating every ``interval_s``.

    ``directory`` may be local or a remote URI (``gs://…`` — the natural
    choice on a multi-host pod, matching the checkpoint bucket; routed
    through ``utils.storage`` like checkpoints are).  Local writes are
    tmp+replace so a reader never sees a torn file; a remote object PUT
    is already atomic."""

    def __init__(self, directory: str, process_index: Optional[int] = None,
                 interval_s: float = 5.0, clock: Callable[[], float] = time.time):
        if process_index is None:
            import jax

            process_index = jax.process_index()
        self._remote = storage.is_remote(directory)
        storage.makedirs(directory)
        self.path = _hb_path(directory, process_index)
        self.process_index = process_index
        self.interval_s = interval_s
        self._clock = clock
        self._step = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def beat(self, step: Optional[int] = None) -> None:
        if step is not None:
            self._step = step
        rec = {"process_index": self.process_index, "pid": os.getpid(),
               "step": self._step, "time": self._clock()}
        if self._remote:
            storage.write_json(self.path, rec)
            return
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, self.path)

    def start(self) -> "Heartbeat":
        def run():
            while not self._stop.wait(self.interval_s):
                try:
                    self.beat()
                except OSError as e:  # shared dir blipped; next beat retries
                    log.warning("heartbeat write failed: %s", e)

        self.beat()
        self._thread = threading.Thread(
            target=run, name="bigdl-tpu-heartbeat", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.interval_s + 1)
            self._thread = None


class HeartbeatMonitor:
    """Phi-accrual suspicion over every ``hb-*.json`` in a directory."""

    def __init__(self, directory: str, window: int = 32,
                 min_std_s: float = 0.1,
                 clock: Callable[[], float] = time.time):
        self.directory = directory
        self.window = window
        self.min_std_s = min_std_s  # floor: a perfectly regular beat
        #                             history must not make phi explode
        self._clock = clock
        self._last: Dict[int, float] = {}
        self._steps: Dict[int, int] = {}
        self._intervals: Dict[int, deque] = {}

    def poll(self) -> Dict[int, float]:
        """Read the current beat files; returns {process_index: beat_time}.
        Call periodically (or before ``suspects``).  Works on local dirs
        and remote URIs alike (the ``utils.storage`` seam)."""
        seen = {}
        try:
            names = storage.listdir(self.directory)
        except (OSError, ImportError):
            return seen
        for name in names:
            if not (name.startswith("hb-") and name.endswith(".json")):
                continue
            try:
                rec = storage.read_json(
                    storage.join(self.directory, name))
            except (OSError, ValueError):
                continue  # torn/unreadable: count as a missed beat
            idx = int(rec["process_index"])
            t = float(rec["time"])
            seen[idx] = t
            prev = self._last.get(idx)
            if prev is not None and t > prev:
                self._intervals.setdefault(
                    idx, deque(maxlen=self.window)).append(t - prev)
            if prev is None or t > prev:
                self._last[idx] = t
                self._steps[idx] = int(rec.get("step", 0) or 0)
        return seen

    def phi(self, process_index: int, now: Optional[float] = None) -> float:
        """Suspicion level for one peer; 0 when it just beat, +inf when it
        was never seen at all."""
        last = self._last.get(process_index)
        if last is None:
            return float("inf")
        now = self._clock() if now is None else now
        elapsed = max(0.0, now - last)
        ivals = self._intervals.get(process_index)
        if ivals:
            mean = sum(ivals) / len(ivals)
            var = sum((x - mean) ** 2 for x in ivals) / len(ivals)
            std = max(math.sqrt(var), self.min_std_s)
        else:  # single beat so far: assume it meant to beat again soon
            mean, std = 1.0, max(1.0, self.min_std_s)
        # P(interval > elapsed) under N(mean, std): survival via erfc
        z = (elapsed - mean) / (std * math.sqrt(2.0))
        p_later = 0.5 * math.erfc(z)
        if p_later <= 0.0:
            return float("inf")
        return -math.log10(p_later)

    def suspects(self, threshold: float = 8.0,
                 now: Optional[float] = None) -> List[int]:
        """Process indices whose phi exceeds ``threshold`` (poll first)."""
        self.poll()
        return sorted(i for i in self._last
                      if self.phi(i, now=now) > threshold)

    def alive(self, threshold: float = 8.0,
              now: Optional[float] = None) -> List[int]:
        """The complement of ``suspects``: every ever-seen peer whose phi
        is at or under ``threshold`` (poll first) — the live set a
        membership sweep turns into a view (``resilience.cluster``)."""
        self.poll()
        return sorted(i for i in self._last
                      if self.phi(i, now=now) <= threshold)

    def peer_step(self, process_index: int) -> Optional[int]:
        """The training step the peer last reported in its beat — lets a
        monitor (or a postmortem) see not just THAT a peer is alive but
        where its driver loop is."""
        return self._steps.get(process_index)


class StepWatchdog:
    """Single-process step health: hung-step detection + NaN-streak.

    The driver loop reports ``step_started``/``observe_loss``; ``hung()``
    (or the optional background ``start()`` thread) flags a step that has
    been in flight longer than ``step_timeout_s``.  A hang cannot be
    safely interrupted from Python (the thread is blocked in XLA), so the
    watchdog's job is to make the condition VISIBLE — ``on_hang`` may
    escalate (e.g. ``os.kill`` for a supervisor restart)."""

    def __init__(self, step_timeout_s: float = 600.0, nan_patience: int = 3,
                 on_hang: Optional[Callable[[int, float], None]] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.step_timeout_s = step_timeout_s
        self.nan_patience = nan_patience
        self.on_hang = on_hang
        self._clock = clock
        self._step = -1
        self._started: Optional[float] = None
        self._nan_streak = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._hang_reported = False

    def step_started(self, step: int) -> None:
        self._step = step
        self._started = self._clock()
        self._hang_reported = False

    def observe_loss(self, step: int, loss: float) -> None:
        """Feed an OBSERVED (host) loss; raises PoisonedStepError after
        ``nan_patience`` consecutive non-finite values.  The driver loop
        calls this at log points — loss observation already forces a
        device sync there, so the check adds no extra transfer."""
        # the step chain up to here completed.  The driver observes a
        # bundle while the next one runs: a loss from before the newest
        # started step restarts that step's clock (the device has only now
        # reached it) instead of stopping it
        self._started = None if step >= self._step else self._clock()
        if math.isfinite(loss):
            self._nan_streak = 0
            return
        self._nan_streak += 1
        log.warning("non-finite loss %s at step %d (%d/%d before poisoned)",
                    loss, step, self._nan_streak, self.nan_patience)
        if self._nan_streak >= self.nan_patience:
            self._nan_streak = 0
            raise PoisonedStepError(
                f"loss non-finite for {self.nan_patience} consecutive "
                f"observations (last step {step})")

    def hung(self, now: Optional[float] = None) -> bool:
        if self._started is None:
            return False
        now = self._clock() if now is None else now
        return (now - self._started) > self.step_timeout_s

    def check(self) -> bool:
        """One poll: logs (and calls ``on_hang``) the first time a hang is
        seen; returns whether the current step is hung."""
        if not self.hung():
            return False
        if not self._hang_reported:
            self._hang_reported = True
            stuck_for = self._clock() - (self._started or 0.0)
            log.error("step %d appears HUNG (%.0fs > %.0fs timeout)",
                      self._step, stuck_for, self.step_timeout_s)
            if self.on_hang is not None:
                self.on_hang(self._step, stuck_for)
        return True

    def start(self, poll_interval_s: float = 5.0) -> "StepWatchdog":
        def run():
            while not self._stop.wait(poll_interval_s):
                self.check()

        self._thread = threading.Thread(
            target=run, name="bigdl-tpu-watchdog", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=6)
            self._thread = None
