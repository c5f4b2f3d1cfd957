"""``bigdl_tpu.resilience`` — fault-tolerant training.

Six layers (see ``docs/resilience.md`` for the failure model):

- :mod:`.faults`    — deterministic fault injection (tests, chip_smoke.py)
- :mod:`.detector`  — heartbeats (phi-accrual) + step watchdog
- :mod:`.retry`     — retry policies, failure classification, FailurePolicy
- :mod:`.membership`— epoch-numbered views over a shared control channel
- :mod:`.cluster`   — gang recovery + peer-shard restore (pod scale)
- :mod:`.supervisor`— the optimize() retry loop; elastic resume guarantee

``Supervisor``/``supervise`` and the cluster layer import lazily: they
pull in the optimizer and engine layers, which themselves import the leaf
modules above — an eager import here would cycle.
"""

from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.detector import (Heartbeat, HeartbeatMonitor,
                                           StepWatchdog)
from bigdl_tpu.resilience.faults import (FaultInjector, FaultSpec,
                                         HostLostError, InjectedFault,
                                         InjectedPredictError)
from bigdl_tpu.resilience.membership import MembershipBoard, MembershipView
from bigdl_tpu.resilience.retry import (FailureCause, FailurePolicy,
                                        PoisonedStepError, RetryPolicy,
                                        TopologyChangedError, classify)

__all__ = [
    "faults", "FaultInjector", "FaultSpec", "InjectedFault",
    "InjectedPredictError", "HostLostError",
    "Heartbeat", "HeartbeatMonitor", "StepWatchdog",
    "MembershipBoard", "MembershipView",
    "FailureCause", "FailurePolicy", "PoisonedStepError", "RetryPolicy",
    "TopologyChangedError", "classify",
    "Supervisor", "supervise",
    "ClusterConfig", "ClusterCoordinator", "GangAbortedError",
    "PeerShardStore",
]

_CLUSTER = ("ClusterConfig", "ClusterCoordinator", "GangAbortedError",
            "PeerShardStore")


def __getattr__(name):
    if name in ("Supervisor", "supervise"):
        from bigdl_tpu.resilience import supervisor as _sup

        return getattr(_sup, name)
    if name in _CLUSTER:
        from bigdl_tpu.resilience import cluster as _cluster

        return getattr(_cluster, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
