"""One run of one cell of BENCHMARK.json, on the TPU this machine holds.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name; its configuration, traffic mix, driver and
per-layer readers are files named in the manifest (benchmark/README.md).
The last line of standard output is the result, one JSON object.  Without
a TPU, or with fewer chips than the cell asks for, the exit code is 2 and
there is no result: nothing falls back to the CPU.
"""

import time

T_PROCESS_START = time.monotonic()

import argparse
import json
import os
import resource
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness


def read_layer_metrics(resolved, evidence, say):
    """Each per-layer metric through its own reader.  A reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in resolved["per_layer"]:
        reader = harness.load_module("readers", m["reader"])
        value = reader.read(m.get("args", {}), evidence)
        if value is None:
            say(f"per_layer {m['name']}: nothing to read, left out")
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def peaks_of(device_kind):
    return harness.load_json(harness.HERE, "peaks.json")[
        "by_device_kind"].get(device_kind)


def measure(resolved, run):
    """Drive the cell once and build the result line.  ``main`` calls this on
    a TPU; the tests call it with a tiny configuration on the CPU."""
    result = harness.load_module("drivers", run.traffic["driver"]).run(run)
    return assemble(resolved, run, result, run.finish_trace(), run.trace)


def assemble(resolved, run, result, trace, traced):
    """The result line of a run: its end-to-end metrics, or with ``traced``
    its per-layer metrics, the device's busy seconds and the breakdown."""
    import jax

    device = run.device
    evidence = dict(run.evidence, **result.get("evidence", {}))
    evidence["peaks"] = peaks_of(device["kind"])
    evidence["marks"] = run.marks
    end_to_end = dict(result["end_to_end"], setup_s=run.setup_s)
    missing = [m["name"] for m in resolved["end_to_end"]
               if end_to_end.get(m["name"]) is None]
    if missing:  # say so, and let the run count as wrong rather than crash
        run.say(f"end_to_end {missing}: the window gave no sample; reported "
                "as 0 and the run is not correct")
    e2e = {m["name"]: {"value": float(end_to_end.get(m["name"]) or 0.0),
                       "unit": m["unit"]} for m in resolved["end_to_end"]}
    layers = read_layer_metrics(resolved, evidence, run.say)

    correct = (bool(result["correct"]) and not missing
               and run.compiles_in_window() == 0)
    run.say(f"compiles_in_window={run.compiles_in_window()} "
            f"compile_cache_hits={run.cache['hits']}/{run.cache['requests']} "
            f"setup_s={run.setup_s:.3f} "
            f"wall_s={time.monotonic() - run.t0:.1f} host_peak_rss_mb="
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024}")
    run.say("memory_stats " + json.dumps(
        [d.memory_stats() for d in jax.devices()]))
    run.say("end_to_end " + " ".join(
        f"{k}={v['value']:.6g}{v['unit']}" for k, v in e2e.items()))
    run.say("per_layer " + " ".join(
        f"{k}={v['value']:.6g}{v['unit']}" for k, v in layers.items()))
    if trace:
        run.say("trace " + json.dumps(trace))

    dev = dict(device, memory_peak_bytes=run.memory_peak_bytes())
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": layers if traced else e2e, "device": dev}
    if traced and trace:
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    resolved = harness.resolve(args.workload)
    cell, traffic = resolved["cell"], resolved["traffic"]
    seconds = args.seconds or resolved["bench"]["run_seconds"]

    # the compile cache has one owner in the program; the benchmark takes
    # its directory (inside the checkout, or JAX_COMPILATION_CACHE_DIR) and
    # only asks that every program be stored, however quick its compile
    from bigdl_tpu.runtime.engine import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu" or device["count"] < cell["chips"]:
        print(f"benchmark: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {device['count']} device(s) of platform "
              f"{device['platform']!r} ({device['kind']}). No result.",
              file=sys.stderr)
        return 2
    if peaks_of(device["kind"]) is None:
        raise SystemExit(f"no peaks for device kind {device['kind']!r} in "
                         "benchmark/peaks.json: add them with their source")

    run = harness.Run(cell["name"], resolved["config"], traffic, args.seed,
                      seconds, args.trace, T_PROCESS_START, device)
    run.install_listeners()
    run.say(f"jax={jax.__version__} compile_cache={cache_dir} seed={run.seed} "
            f"seconds={run.seconds} trace={int(run.trace)} "
            f"import_s={time.monotonic() - T_PROCESS_START:.1f}")
    line = measure(resolved, run)
    if run.trace and "busy_s" not in line["device"]:
        raise SystemExit("--trace 1, and no operation ran on the device "
                         "inside the profiled span: no result")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
