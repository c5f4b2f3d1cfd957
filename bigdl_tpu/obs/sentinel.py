"""Perf-regression sentinel — fresh bench JSON vs the committed trajectory.

The repo commits perf artifacts per round (``BENCH_r03/r04``,
``BENCH_loader_r06``, ``BENCH_dispatch_r07``, ``SERVING_r04/r05``).  This
module compares a fresh measurement against that trajectory: it normalizes every committed artifact into
``(family, value, direction)`` rows, takes the best good committed value
per family as the baseline, and flags a fresh row that regresses more than
``threshold`` (default 10%).

READ-ONLY by design: the sentinel never writes bench artifacts.

CLI::

    python -m bigdl_tpu.obs.sentinel fresh.json [...]   # exit 1 on regression
    python -m bigdl_tpu.obs.sentinel --smoke            # prove the gate works
                                                        # on synthetic rows

``--smoke`` synthesizes a 20% regressed row and an unregressed row from
the committed history and exits non-zero unless the sentinel flags exactly
the regressed one — the CI step that proves the gate, machine-independent.
"""

import argparse
import glob
import json
import os
import re
import sys
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

HIGHER = "higher"
LOWER = "lower"

DEFAULT_THRESHOLD = 0.10

# committed artifact families: (glob, extractor).  An extractor maps one
# artifact dict onto zero or more normalized rows.
_ARTIFACT_GLOBS = (
    "BENCH_r[0-9]*.json",
    "BENCH_dispatch_r[0-9]*.json",
    "BENCH_loader_r[0-9]*.json",
    "SERVING_r[0-9]*.json",
    # token-level decode serving rounds (bench_serving --decode):
    # aggregate tokens/s and the continuous-vs-static speedup gate
    # higher-better; TTFT and inter-token tails gate lower-better
    "DECODE_r[0-9]*.json",
    # cluster recovery drills (docs/resilience.md §Multi-host recovery):
    # MTTR and restore traffic gate like the latency families — a
    # recovery that got 10% slower or 10% heavier is a regression
    "CLUSTER_r[0-9]*.json",
    # per-kernel Pallas selfcheck rounds (kernels_selfcheck.py): each
    # kernel's speedup-vs-XLA gates higher-better so a kernel regression
    # fails `make bench-watch` like every other family; parity_ok rows
    # only — a broken kernel is caught by the selfcheck exit code, not
    # misread as a perf row
    "KERNELS_r[0-9]*.json",
    # the MULTICHIP family: per-step collective bytes of the ZeRO-1
    # cycle.  The ledger is analytic (pure layout math, machine-
    # independent), so bytes gate exactly — a change that silently
    # re-inflates the wire fails the sentinel.  MULTICHIP_LARGE rounds
    # carry the measured dp_resnet50_multislice cycle; the GRADCOMM
    # rounds (bench_scaling --grad-comm) additionally carry the
    # int8-vs-fp32 gradient-bytes reduction (higher-better — the
    # compression must keep paying)
    "MULTICHIP_LARGE_r[0-9]*.json",
    "MULTICHIP_GRADCOMM_r[0-9]*.json",
    # declarative-layout ledger rounds (bench_scaling --layout): per-axis
    # collective bytes and per-chip param bytes of the dp vs fsdp x tp
    # layouts on the bench geometry.  Analytic (machine-independent), so
    # bytes gate exactly lower-better; the headline per-chip param-bytes
    # reduction rides the generic "metric" row higher-better — a layout-
    # table change that silently re-replicates the big tensors fails
    # bench-watch
    "MULTICHIP_LAYOUT_r[0-9]*.json",
    # SLO burn-rate alert drills (python -m bigdl_tpu.obs.slo --bench):
    # alert latency under an injected hard violation gates lower-better —
    # a PR that silently slows burn detection fails bench-watch; the
    # burn peak gates higher-better (the detector must keep seeing a
    # hard violation as a hard burn)
    "SLO_r[0-9]*.json",
    # decode fleet (bench_serving --fleet): multi-worker pool serving with
    # KV-aware routing — throughput/TTFT/inter-token gate per geometry
    # exactly as the single-host decode rows do (the tokens_per_s
    # normalize branch keys families by the row's geometry)
    "DECODE_POOL_r[0-9]*.json",
    # decode-fleet chaos drills (bench_serving --fleet --chaos): a decode
    # worker is killed mid-run under streaming load; the bench itself
    # hard-gates zero failed requests + token parity, so the committed
    # row only exists for a passing run — the sentinel trends the
    # recovery tail (lower-better) and the under-chaos throughput
    "DECODE_CHAOS_r[0-9]*.json",
    # recsys serving rounds (bench_recsys.py): the feature->recall->
    # ranking pipeline under sustained mixed-tenant load — recommend QPS
    # and recall candidate throughput gate higher-better, the recommend
    # p99 tail lower-better, geometry-scoped like every serving family.
    # The zero-unexpected-recompiles and sharded-parity gates are
    # enforced by the bench before the row is written
    "RECSYS_r[0-9]*.json",
    # quantized decode serving rounds (bench_serving --decode --quant):
    # int8 KV pages vs the f32 pool at EQUAL HBM budget.  The bench
    # hard-gates token parity and zero unexpected recompiles before the
    # row is written; the sentinel trends the slots-per-chip capacity
    # ratio and the quantized engine's tokens/s (both higher-better —
    # the memory win must keep paying and must not cost throughput)
    "DECODE_QUANT_r[0-9]*.json",
    # speculative decode rounds (bench_serving --decode --spec): the
    # weight-shared block-sparse draft + single-call verify vs the same
    # engine spec-off.  Greedy byte parity and zero unexpected
    # recompiles are hard gates inside the bench; the sentinel trends
    # the per-user token rate and the acceptance rate (both higher-
    # better — speculation must keep paying, and a draft that stops
    # agreeing with the target is a silent regression)
    "DECODE_SPEC_r[0-9]*.json",
)

# lower-is-better families (latencies, recovery time/traffic, collective
# bytes); everything else is higher-better
_LOWER_BETTER = frozenset({"serving_p50_ms", "serving_p99_ms",
                           "decode_ttft_ms_p50", "decode_ttft_ms_p99",
                           "decode_inter_token_p99_ms",
                           "cluster_mttr_s", "cluster_recovery_bytes",
                           "chaos_recovery_ms_p99",
                           "recsys_recommend_p99_ms",
                           "slo_alert_latency_s",
                           "multichip_ici_bytes_per_step",
                           "multichip_dcn_bytes_per_step",
                           "multichip_grad_sync_ici_bytes_per_step",
                           "multichip_grad_sync_dcn_bytes_per_step"})


@dataclass
class Row:
    family: str
    value: float
    direction: str
    source: str


@dataclass
class Verdict:
    family: str
    fresh: float
    baseline: float
    baseline_source: str
    direction: str
    ratio: float            # fresh / baseline
    regressed: bool
    threshold: float

    def asdict(self) -> Dict[str, Any]:
        return dict(self.__dict__)


def _good(row: Dict[str, Any]) -> bool:
    """A trustworthy committed row: parsed, no error, not flagged
    suspect."""
    return (isinstance(row, dict) and "error" not in row
            and not row.get("suspect"))


def _unwrap(doc: Any) -> Optional[Dict[str, Any]]:
    """Round artifacts are re-wrapped as {n, cmd, rc, tail, parsed} by the
    round driver — unwrap to the measurement row."""
    if not isinstance(doc, dict):
        return None
    if "parsed" in doc and not doc.get("metric"):
        doc = doc["parsed"]
    return doc if isinstance(doc, dict) else None


def normalize(doc: Any, source: str) -> List[Row]:
    """One artifact dict -> normalized rows (empty when not trustworthy)."""
    row = _unwrap(doc)
    if row is None or not _good(row):
        return []
    out: List[Row] = []

    def add(family: str, value: Any, direction: str = HIGHER) -> None:
        try:
            v = float(value)
        except (TypeError, ValueError):
            return
        if v > 0:
            out.append(Row(family, v, direction, source))

    if "metric" in row:  # bench.py / bench-dispatch rows carry their name
        add(str(row["metric"]), row.get("value"))
    if "pipeline_img_per_sec" in row:
        add("loader_pipeline_img_per_sec", row["pipeline_img_per_sec"])
    if "loader_img_per_sec" in row:
        add("loader_img_per_sec", row["loader_img_per_sec"])
    if "throughput_rps" in row:
        # captures from different load geometries are not comparable: a
        # saturated 32-client p50 includes queue wait a light 8-client
        # probe never pays.  A "geometry" tag scopes the serving families
        # to same-geometry baselines (both directions); legacy untagged
        # rows (r04/r05) keep the plain names and gate each other.
        geo = re.sub(r"[^A-Za-z0-9]+", "_",
                     str(row.get("geometry") or "")).strip("_")
        sfx = f"_{geo}" if geo else ""
        add(f"serving_throughput_rps{sfx}", row["throughput_rps"])
        add(f"serving_p50_ms{sfx}", row.get("p50_ms"), LOWER)
        add(f"serving_p99_ms{sfx}", row.get("p99_ms"), LOWER)
        # batching health: continuous assembly must keep batches FULL —
        # occupancy sliding back toward per-request predicts is the
        # regression the r05->r08 rebuild exists to prevent
        add(f"serving_avg_batch_size{sfx}", row.get("avg_batch_size"))
    if "tokens_per_s" in row:
        # DECODE_r*.json (bench_serving --decode): sustained-generation
        # geometry.  Same geometry-scoping rule as the SERVING family —
        # a saturated decode p99 is not comparable across client counts
        geo = re.sub(r"[^A-Za-z0-9]+", "_",
                     str(row.get("geometry") or "")).strip("_")
        sfx = f"_{geo}" if geo else ""
        add(f"decode_tokens_per_s{sfx}", row["tokens_per_s"])
        add(f"decode_tokens_per_s_user{sfx}", row.get("tokens_per_s_user"))
        add(f"decode_ttft_ms_p50{sfx}", row.get("ttft_ms_p50"), LOWER)
        add(f"decode_ttft_ms_p99{sfx}", row.get("ttft_ms_p99"), LOWER)
        add(f"decode_inter_token_p99_ms{sfx}",
            row.get("inter_token_p99_ms"), LOWER)
        # the reason this engine exists: continuous decode must keep
        # beating the whole-batch-restart baseline
        add(f"decode_speedup_vs_static{sfx}",
            row.get("speedup_vs_static"))
    if row.get("bench") == "decode_quant":
        # DECODE_QUANT_r*.json (bench_serving --decode --quant): int8 KV
        # pages vs f32 at equal HBM budget.  Token parity and the zero-
        # recompile sweep are hard gates inside the bench (a failing run
        # writes no row); the sentinel trends the capacity ratio and the
        # quantized throughput, both higher-better and geometry-scoped
        geo = re.sub(r"[^A-Za-z0-9]+", "_",
                     str(row.get("geometry") or "")).strip("_")
        sfx = f"_{geo}" if geo else ""
        add(f"decode_quant_slots_per_chip{sfx}",
            row.get("slots_per_chip_ratio"))
        add(f"decode_quant_tokens_per_s{sfx}",
            row.get("quant_tokens_per_s"))
    if row.get("bench") == "decode_spec":
        # DECODE_SPEC_r*.json (bench_serving --decode --spec): the
        # block-sparse draft + single-call verify vs the same engine
        # spec-off.  Byte parity, the >=1.5x speedup floor, and the
        # zero-recompile sweep are hard gates inside the bench; the
        # sentinel trends the per-user rate and the acceptance rate —
        # acceptance decaying means the draft stopped earning its keep
        # long before the speedup gate trips.  Geometry-scoped.
        geo = re.sub(r"[^A-Za-z0-9]+", "_",
                     str(row.get("geometry") or "")).strip("_")
        sfx = f"_{geo}" if geo else ""
        add(f"decode_spec_tokens_per_s_user{sfx}",
            row.get("spec_tokens_per_s_user"))
        add(f"decode_spec_accept_rate{sfx}", row.get("accept_rate"))
    if row.get("bench") == "decode_chaos":
        # DECODE_CHAOS_r*.json (bench_serving --fleet --chaos): the
        # pass/fail gates (zero failed requests, byte parity across the
        # mid-run worker kill) are enforced by the bench before the row
        # is written; here we trend what CAN regress gradually — the
        # failover recovery tail and throughput under chaos.  Geometry-
        # scoped like every serving family.
        geo = re.sub(r"[^A-Za-z0-9]+", "_",
                     str(row.get("geometry") or "")).strip("_")
        sfx = f"_{geo}" if geo else ""
        add(f"chaos_recovery_ms_p99{sfx}", row.get("recovery_ms_p99"),
            LOWER)
        add(f"chaos_tokens_per_s{sfx}", row.get("chaos_tokens_per_s"))
    if row.get("bench") == "recsys":
        # RECSYS_r*.json (bench_recsys.py): sustained mixed-tenant load
        # through the feature->recall->ranking pipeline.  The binary
        # gates (zero unexpected recompiles, sharded-vs-unsharded parity,
        # per-chip embedding shrink factor) fail the bench itself; here
        # we trend what can regress gradually.  Geometry-scoped like the
        # SERVING/DECODE families
        geo = re.sub(r"[^A-Za-z0-9]+", "_",
                     str(row.get("geometry") or "")).strip("_")
        sfx = f"_{geo}" if geo else ""
        add(f"recsys_qps{sfx}", row.get("recsys_qps"))
        add(f"recsys_recommend_p99_ms{sfx}",
            row.get("recommend_p99_ms"), LOWER)
        add(f"recsys_recall_candidates_per_s{sfx}",
            row.get("recall_candidates_per_s"))
    if "slo_alert_latency_s" in row:
        # SLO_r*.json burn-rate drills: both values are quantized to the
        # evaluation cadence / a hard injected violation, so they are
        # stable run-to-run (the bench docstring has the reasoning)
        add("slo_alert_latency_s", row["slo_alert_latency_s"], LOWER)
        add("slo_burn_peak", row.get("slo_burn_peak"))
    if "mttr_s" in row:  # CLUSTER_r*.json recovery drills
        add("cluster_mttr_s", row["mttr_s"], LOWER)
        add("cluster_recovery_bytes", row.get("recovery_bytes"), LOWER)
    if "grad_bytes_reduction_vs_fp32" in row:
        # MULTICHIP_GRADCOMM rounds (bench_scaling --grad-comm): the
        # int8-vs-fp32 compression ratio rides the generic "metric" row
        # above (higher-better — the wire must stay shrunk); the shipped
        # mode's absolute gradient bytes gate lower-better here.  All
        # are analytic ledger values — machine-independent, so exact
        add("multichip_grad_sync_ici_bytes_per_step",
            row.get("grad_sync_ici_bytes_per_step"), LOWER)
        add("multichip_grad_sync_dcn_bytes_per_step",
            row.get("grad_sync_dcn_bytes_per_step"), LOWER)
    if isinstance(row.get("layout_modes"), dict):
        # MULTICHIP_LAYOUT rounds (bench_scaling --layout): one family
        # per (layout mode, axis) plus the per-chip param-bytes meter.
        # All analytic ledger values — machine-independent, exact
        for mode, rec in sorted(row["layout_modes"].items()):
            if not isinstance(rec, dict):
                continue
            add(f"multichip_layout_{mode}_param_bytes_per_chip",
                rec.get("param_bytes_per_chip"), LOWER)
            per = rec.get("per_axis_bytes_per_step")
            if isinstance(per, dict):
                for axis, v in sorted(per.items()):
                    add(f"multichip_layout_{mode}_{axis}_bytes_per_step",
                        v, LOWER)
            add(f"multichip_layout_{mode}_tp_activation_bytes_per_step",
                rec.get("tp_activation_bytes_per_step"), LOWER)
    if isinstance(row.get("modes"), dict):
        # MULTICHIP_LARGE rounds: the measured dp_resnet50_multislice
        # ZeRO-1 cycle's per-step collective bytes (fp32 baseline ~204 MB
        # ICI + 51 MB DCN in r05) — a fresh round whose bytes regress
        # >threshold above the best committed value fails the gate
        m = row["modes"].get("dp_resnet50_multislice")
        if isinstance(m, dict):
            add("multichip_ici_bytes_per_step",
                m.get("ici_collective_bytes_per_step"), LOWER)
            add("multichip_dcn_bytes_per_step",
                m.get("dcn_collective_bytes_per_step"), LOWER)
    if "kernels" in row and isinstance(row["kernels"], dict):
        # KERNELS_r*.json: one speedup family per kernel.  Only
        # parity-clean, non-probe rows gate (probe_ entries are tiling
        # experiments, never shipped configs); amortized speedup is
        # preferred when present (single-dispatch numbers can sit on the
        # dispatch floor — KERNELS_r04)
        for name, rec in sorted(row["kernels"].items()):
            if name.startswith("probe_") or not isinstance(rec, dict):
                continue
            if not rec.get("parity_ok"):
                continue
            add(f"kernel_speedup_{name}",
                rec.get("speedup_amortized", rec.get("speedup")))
    return out


def load_history(root: Optional[str] = None) -> Dict[str, List[Row]]:
    """All committed artifact rows, grouped by family."""
    root = root or os.getcwd()
    history: Dict[str, List[Row]] = {}
    for pattern in _ARTIFACT_GLOBS:
        for path in sorted(glob.glob(os.path.join(root, pattern))):
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, json.JSONDecodeError):
                continue
            for row in normalize(doc, os.path.basename(path)):
                history.setdefault(row.family, []).append(row)
    return history


def baseline_for(family: str, history: Dict[str, List[Row]]
                 ) -> Optional[Row]:
    """The committed value to beat: best good row of the family (max for
    higher-better, min for lower-better) — a fresh number must not
    regress >threshold from the trajectory's best."""
    rows = history.get(family)
    if not rows:
        return None
    best = (max if rows[0].direction == HIGHER else min)(
        rows, key=lambda r: r.value)
    return best


def check_row(row: Row, history: Dict[str, List[Row]],
              threshold: float = DEFAULT_THRESHOLD) -> Optional[Verdict]:
    """Compare one fresh row against the committed trajectory.  None when
    the family has no committed history (nothing to regress from)."""
    base = baseline_for(row.family, history)
    if base is None:
        return None
    ratio = row.value / base.value
    if row.direction == HIGHER:
        regressed = ratio < 1.0 - threshold
    else:
        regressed = ratio > 1.0 + threshold
    return Verdict(family=row.family, fresh=row.value, baseline=base.value,
                   baseline_source=base.source, direction=row.direction,
                   ratio=round(ratio, 4), regressed=regressed,
                   threshold=threshold)


def check(fresh: Any, history: Dict[str, List[Row]],
          threshold: float = DEFAULT_THRESHOLD,
          source: str = "fresh") -> List[Verdict]:
    """Normalize a fresh artifact dict and check every family it carries."""
    out = []
    for row in normalize(fresh, source):
        v = check_row(row, history, threshold)
        if v is not None:
            out.append(v)
    return out


def _load_fresh(path: str) -> Optional[Dict[str, Any]]:
    """A fresh artifact: a JSON file, or bench stdout whose LAST line is
    the JSON row (the bench.py contract)."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return None
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        pass
    for line in reversed([ln for ln in text.splitlines() if ln.strip()]):
        try:
            doc = json.loads(line)
            if isinstance(doc, dict):
                return doc
        except json.JSONDecodeError:
            continue
    return None


def _smoke(history: Dict[str, List[Row]], threshold: float) -> int:
    """Prove the gate on synthetic rows: a 20% regression must be flagged,
    an on-trajectory row must pass.  Exit 0 only when both hold."""
    if not history:
        print(json.dumps({"smoke": "fail",
                          "reason": "no committed artifacts found"}))
        return 1
    failures = []
    for family, rows in sorted(history.items()):
        base = baseline_for(family, history)
        drop = 0.8 if base.direction == HIGHER else 1.25
        regressed_row = Row(family, base.value * drop, base.direction,
                            "synthetic-regressed")
        ok_row = Row(family, base.value, base.direction, "synthetic-ok")
        v_bad = check_row(regressed_row, history, threshold)
        v_ok = check_row(ok_row, history, threshold)
        if not (v_bad and v_bad.regressed):
            failures.append(f"{family}: synthetic 20% regression NOT flagged")
        if v_ok and v_ok.regressed:
            failures.append(f"{family}: on-trajectory value falsely flagged")
    verdict = {"smoke": "ok" if not failures else "fail",
               "families": len(history), "threshold": threshold,
               "failures": failures}
    print(json.dumps(verdict))
    return 0 if not failures else 1


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="bigdl_tpu.obs.sentinel",
        description="read-only perf-regression sentinel over committed "
                    "bench artifacts (docs/performance.md §Regression "
                    "sentinel)")
    ap.add_argument("fresh", nargs="*",
                    help="fresh artifact JSON files (bench.py stdout ok)")
    ap.add_argument("--root", default=None,
                    help="repo root holding the committed artifacts "
                         "(default: cwd)")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="relative regression that fails (default 0.10)")
    ap.add_argument("--smoke", action="store_true",
                    help="prove the gate on synthetic regressed rows")
    args = ap.parse_args(argv)

    # default root: the repo checkout this package sits in, falling back
    # to cwd when the package is installed outside a checkout
    repo = args.root or _find_repo_root() or os.getcwd()
    history = load_history(repo)

    if args.smoke:
        return _smoke(history, args.threshold)
    if not args.fresh:
        ap.error("need fresh artifact files (or --smoke)")
    rc = 0
    for path in args.fresh:
        doc = _load_fresh(path)
        if doc is None:
            print(json.dumps({"file": path, "error": "unparseable"}))
            rc = 1
            continue
        verdicts = check(doc, history, args.threshold,
                         source=os.path.basename(path))
        if not verdicts:
            print(json.dumps({"file": path, "checked": 0,
                              "note": "no family overlaps the committed "
                                      "trajectory"}))
            continue
        for v in verdicts:
            print(json.dumps(dict(v.asdict(), file=path)))
            if v.regressed:
                rc = 1
    return rc


def _find_repo_root() -> Optional[str]:
    """Walk up from this file looking for committed BENCH artifacts."""
    d = os.path.dirname(os.path.abspath(__file__))
    for _ in range(6):
        if glob.glob(os.path.join(d, "BENCH_r[0-9]*.json")):
            return d
        d = os.path.dirname(d)
    return None


if __name__ == "__main__":
    sys.exit(main())
