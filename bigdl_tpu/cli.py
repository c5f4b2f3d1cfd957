"""``bigdl-tpu`` console launcher — the ``bigdl-submit`` / ``spark-submit``
analog (SURVEY.md §2 CLI/launch row).

The reference wraps ``spark-submit`` to place one executor per node with the
right env.  TPU-natively there is no cluster manager to talk to: a job is N
identical processes (one per TPU-VM host) that rendezvous through
``jax.distributed.initialize``.  This launcher covers the two shapes:

- ``bigdl-tpu run script.py``                      one process, all local chips
- ``bigdl-tpu run -n 4 --cpu script.py``           N LOCAL CPU processes (one
  per simulated host) with the coordinator/rank env injected — the
  ``local-cluster`` mode used by the multi-process tests.  Refused
  without ``--cpu``/``JAX_PLATFORMS=cpu``: a chip belongs to one process,
  and every local JAX process would claim all of them
- ``bigdl-tpu run --coordinator host:8476 --num-processes 16
  --process-id 3 script.py``                       one member of a real
  multihost job (run once per host, e.g. from ``gcloud compute tpus ssh
  --worker=all``)

plus ``bigdl-tpu dryrun | doctor`` for the repo harnesses.
"""

import argparse
import os
import subprocess
import sys


def _run(args) -> int:
    env_base = dict(os.environ)
    if args.coordinator and args.process_id is not None:
        # one member of an externally-orchestrated multihost job
        env_base.update(BIGDL_TPU_COORDINATOR=args.coordinator,
                        BIGDL_TPU_NUM_PROCESSES=str(args.num_processes),
                        BIGDL_TPU_PROCESS_ID=str(args.process_id))
        os.environ.update(env_base)
        sys.argv = [args.script] + args.script_args
        with open(args.script) as f:
            code = compile(f.read(), args.script, "exec")
        exec(code, {"__name__": "__main__", "__file__": args.script})
        return 0

    if args.num_processes <= 1:
        return subprocess.call([sys.executable, args.script]
                               + args.script_args, env=env_base)

    # local N-process gang (the local-cluster analog): pick a free port,
    # spawn N children with rank env, fail fast if any member fails
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    if args.cpu:
        env_base["JAX_PLATFORMS"] = "cpu"
        env_base.pop("XLA_FLAGS", None)
    from bigdl_tpu.runtime.engine import require_one_chip_holder

    try:
        require_one_chip_holder(args.num_processes, env_base)
    except RuntimeError as e:
        print(f"bigdl-tpu run: {e}", file=sys.stderr)
        return 2
    procs = []
    for r in range(args.num_processes):
        env = dict(env_base,
                   BIGDL_TPU_COORDINATOR=f"127.0.0.1:{port}",
                   BIGDL_TPU_NUM_PROCESSES=str(args.num_processes),
                   BIGDL_TPU_PROCESS_ID=str(r))
        procs.append(subprocess.Popen(
            [sys.executable, args.script] + args.script_args, env=env))
    # poll ALL children: a crashed rank leaves its peers blocked in the
    # jax.distributed rendezvous, so survivors are killed the moment any
    # member exits nonzero (true fail-fast, not wait-in-order)
    import time as _time

    rc = 0
    live = list(procs)
    while live:
        for p in list(live):
            p_rc = p.poll()
            if p_rc is None:
                continue
            live.remove(p)
            rc = rc or p_rc
        if rc:
            for p in live:
                p.kill()
            for p in live:
                p.wait()
            break
        if live:
            _time.sleep(0.05)
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bigdl-tpu", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="launch a training script")
    run.add_argument("-n", "--num-processes", type=int, default=1,
                     help="local process count (local-cluster mode)")
    run.add_argument("--coordinator", default=None,
                     help="host:port of process 0 (real multihost mode)")
    run.add_argument("--process-id", type=int, default=None,
                     help="this host's rank (real multihost mode)")
    run.add_argument("--cpu", action="store_true",
                     help="force the CPU platform in children")
    run.add_argument("script")
    run.add_argument("script_args", nargs=argparse.REMAINDER)

    sub.add_parser("doctor", help="environment diagnostic: devices, mesh, "
                   "native lib, rendezvous env; non-zero when the backend "
                   "or the mesh cannot be brought up")
    sub.add_parser("dryrun", help="8-virtual-device multichip dry run")

    serve = sub.add_parser(
        "serve", help="multi-worker serving pool: N process-isolated "
        "engines behind one round-robin proxy (serving/pool.py)")
    serve.add_argument("loader", help="module:function returning an "
                       "InferenceModel (imported inside each worker)")
    serve.add_argument("--workers", type=int, default=2)
    serve.add_argument("--port", type=int, default=8000)
    serve.add_argument("--batch-size", type=int, default=32)

    pack = sub.add_parser(
        "pack", help="pack arrays into a BTRECv1 record file "
        "(train-from-disk input, data/records.py)")
    pack.add_argument("src", help=".npz (fields = array names) or .csv "
                      "(fields x=float cols, y=label col)")
    pack.add_argument("out", help="output .btrec path")
    pack.add_argument("--label-col", default=None,
                      help="csv: which column is the label (default: last)")

    args = ap.parse_args(argv)
    if args.cmd == "run":
        return _run(args)
    if args.cmd == "dryrun":
        return subprocess.call([
            sys.executable, "-c",
            "import __graft_entry__ as g; g.dryrun_multichip(8)"])
    if args.cmd == "doctor":
        return _doctor()
    if args.cmd == "serve":
        return subprocess.call([
            sys.executable, "-m", "bigdl_tpu.serving.pool",
            "--loader", args.loader, "--workers", str(args.workers),
            "--port", str(args.port), "--batch-size",
            str(args.batch_size)])
    if args.cmd == "pack":
        return _pack(args)
    return 2


def _doctor() -> int:
    """Environment diagnostic — one JSON report: the backend and devices
    as THIS process sees them (so it takes the chips like any job would —
    do not run it beside one), the mesh ``Engine`` would build, native
    lib, rendezvous env.  Exit 0 only when the backend initialized and
    the mesh was built."""
    import json

    import jax

    from bigdl_tpu.native import lib as nat
    from bigdl_tpu.runtime.engine import EngineConfig
    from bigdl_tpu.runtime.mesh import build_mesh

    report = {"rendezvous_env": {
        k: os.environ.get(k) for k in
        ("BIGDL_TPU_COORDINATOR", "BIGDL_TPU_NUM_PROCESSES",
         "BIGDL_TPU_PROCESS_ID", "BIGDL_TPU_DCN_SLICES", "JAX_PLATFORMS",
         "XLA_FLAGS")
        if os.environ.get(k)}}
    healthy = True
    try:
        ds = jax.devices()
        report["backend"] = {
            "platform": ds[0].platform, "device_kind": ds[0].device_kind,
            "n_devices": len(ds),
            "slices": len({getattr(d, "slice_index", 0) for d in ds})}
        # the SAME mesh Engine would build (env overrides applied)
        try:
            report["mesh"] = dict(
                build_mesh(EngineConfig.from_env().mesh).shape)
        except ValueError as e:
            report["mesh"] = {"error": str(e)}
            healthy = False
    except RuntimeError as e:  # backend init failed: no devices to report
        report["backend"] = {"error": str(e)}
        healthy = False
    report["native_lib"] = {"available": nat.available(),
                            "jpeg": nat.jpeg_available()}
    if os.environ.get("BIGDL_TPU_NUM_PROCESSES"):
        # doctor runs without the rendezvous, so process count comes
        # from the job env, not jax.process_count()
        report["configured_processes"] = int(
            os.environ["BIGDL_TPU_NUM_PROCESSES"])
    print(json.dumps(report, indent=1))
    return 0 if healthy else 1


def _pack(args) -> int:
    import numpy as np

    from bigdl_tpu.data.records import write_records

    if args.src.endswith(".npz"):
        data = np.load(args.src)
        fields = {k: data[k] for k in data.files}
    elif args.src.endswith(".csv"):
        import pandas as pd

        df = pd.read_csv(args.src)
        label = args.label_col or df.columns[-1]
        fields = {
            "x": df.drop(columns=[label]).to_numpy(np.float32),
            "y": df[label].to_numpy(),
        }
    else:
        print(f"pack: unsupported source {args.src!r} (.npz or .csv)",
              file=sys.stderr)
        return 2
    write_records(args.out, fields)
    n = len(next(iter(fields.values())))
    print(f"packed {n} records x {list(fields)} -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
