"""Observability — spans, Prometheus export, latency histograms, flight
recorder (docs/observability.md).

The layer every other subsystem reports through:

- :mod:`.trace`  — span tracer (Chrome-trace/Perfetto JSON) correlating a
  serving request or training step across subsystems
- :mod:`.export` — Prometheus text-format exporter over ``Metrics``
  (``GET /metrics`` on serving; :class:`MetricsServer` for training jobs)
- :mod:`.hist`   — bounded log-bucketed histograms (p50/p95/p99 +
  sliding windows)
- :mod:`.slo`    — declarative per-tenant SLOs: sliding-window error
  budgets, multi-window burn-rate alerts, the fleet health score
- :mod:`.flight` — fixed-size ring of notable events, dumped as JSONL on
  crash or SIGTERM
- :mod:`.attr`   — per-step wall-time attribution, the recompilation
  sentinel, and cross-host straggler stats
- :mod:`.cost`   — analytic FLOPs/bytes cost model + device peak table
  (the live ``train.mfu`` gauge)
"""

from bigdl_tpu.obs import attr, cost, flight, slo, trace
from bigdl_tpu.obs.attr import (RecompileSentinel, StepAttribution,
                                expected_compile, recompile_sentinel)
from bigdl_tpu.obs.cost import CostReport, forward_costs, peak_flops
from bigdl_tpu.obs.export import (MetricsServer, federate,
                                  parse_exposition, render_prometheus,
                                  sanitize_metric_name)
from bigdl_tpu.obs.flight import FlightRecorder
from bigdl_tpu.obs.hist import LogHistogram
from bigdl_tpu.obs.slo import SLOEvaluator, SLOSpec
from bigdl_tpu.obs.trace import Span, Tracer

__all__ = [
    "trace", "flight", "attr", "cost", "slo", "Tracer", "Span",
    "FlightRecorder", "LogHistogram", "MetricsServer", "render_prometheus",
    "parse_exposition", "federate", "SLOEvaluator", "SLOSpec",
    "sanitize_metric_name", "StepAttribution", "RecompileSentinel",
    "recompile_sentinel", "expected_compile", "CostReport", "forward_costs",
    "peak_flops",
]
