"""Scaling-efficiency harness on the simulated mesh — prints ONE JSON line.

A CPU harness by construction (it forces 8 virtual CPU devices): it
measures what can be measured without chips — nothing it prints is a
device time.  Several real chips: not measured (ROADMAP A6).

- **strong scaling on the 8-virtual-device CPU mesh** (the ``local[N]``
  analog, SURVEY.md §5): per-step wall time of the ZeRO-1 train step at
  data=1/2/4/8 with the GLOBAL batch fixed.  XLA:CPU runs virtual devices
  on separate host threads, so the mesh delivers real parallel speedup
  until core contention and collective overhead eat it — speedup(n)=t1/tn
  and efficiency=speedup/n are the simulated-mesh proxies for the
  scaling-efficiency curve on a real slice.
- the analytic per-step collective traffic of the dp step (psum_scatter +
  all_gather of the flat parameter vector), for sanity-checking against a
  real profile.
- ``--grad-comm``: the gradient-compression A/B (docs/parallelism.md
  §Gradient compression): prices the MULTICHIP_LARGE dp_resnet50
  geometry (dcn_data=2 x data=4) with the analytic wire-dtype ledger for
  fp32/bf16/int8, then MEASURES int8-vs-fp32 loss parity and bucketed
  overlap efficiency with real train steps of the small bench model —
  the MULTICHIP_GRADCOMM_r*.json artifact the regression sentinel gates.

The real-slice protocol (what to run on a v5e pod and what to record) is
documented in docs/performance.md §"Scaling protocol".
"""

import argparse
import json
import os
import time


def main_real(args):
    """REAL-slice scaling measurement: launch one process per host via
    ``bigdl-tpu run bench_scaling.py -- --real`` (the gang launcher sets the
    rendezvous env).  Measures the full-mesh ZeRO-1 step (dcn_data
    auto-detected from the slice topology) and prints one JSON line from
    rank 0; the 8->256 curve comes from invoking this at each slice size
    (docs/performance.md §Scaling protocol)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models.resnet import resnet50, resnet_cifar
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.train_step import ShardedParameterStep
    from bigdl_tpu.runtime.engine import Engine, init_engine
    from bigdl_tpu.runtime.mesh import detect_slice_count

    engine = init_engine()
    mesh = engine.mesh
    devices = jax.devices()
    n_dev = len(devices)
    per_dev_batch = args.per_device_batch
    global_batch = per_dev_batch * n_dev
    model = (resnet50(classes=1000) if args.model == "resnet50"
             else resnet_cifar(depth=8, classes=10))
    side = 224 if args.model == "resnet50" else 32
    classes = 1000 if args.model == "resnet50" else 10

    rs = np.random.RandomState(0)
    local = global_batch // jax.process_count()
    x = rs.rand(local, side, side, 3).astype(np.float32)
    y = rs.randint(0, classes, (local,)).astype(np.int32)
    rng = jax.random.PRNGKey(0)
    variables = model.init(rng, jnp.asarray(x[:1]))
    # compressed reduce-scatter pays off once the data axis crosses
    # hosts (DCN-bound); over a single slice's ICI f32 is free
    wire = args.wire
    if wire == "auto":
        wire = "bf16" if jax.process_count() > 1 else "fp32"
    step = ShardedParameterStep(
        model, CrossEntropyCriterion(),
        SGD(learning_rate=0.1, momentum=0.9), mesh, variables,
        grad_comm=wire)
    xd, yd = step.shard_batch(x), step.shard_batch(y)
    float(np.asarray(step.train_step_device(0, rng, xd, yd)))  # compile
    t0 = time.perf_counter()
    for i in range(args.steps):
        loss = step.train_step_device(i + 1, rng, xd, yd)
    final = float(np.asarray(loss))
    dt = (time.perf_counter() - t0) / args.steps
    if jax.process_index() == 0:
        print(json.dumps({
            "metric": "real_slice_img_per_s",
            "value": round(global_batch / dt, 1),
            "unit": "img/s",
            "vs_baseline": None,
            "model": args.model,
            "n_devices": n_dev,
            "n_slices": detect_slice_count(devices),
            "n_processes": jax.process_count(),
            "device_kind": devices[0].device_kind,
            "mesh": {k: int(v) for k, v in dict(mesh.shape).items()},
            "global_batch": global_batch,
            "step_time_ms": round(dt * 1e3, 2),
            "grad_comm": wire,
            "ici_bytes_per_step": step.collective_bytes_per_step,
            "grad_sync_ici_bytes_per_step":
                step.grad_sync_ici_bytes_per_step,
            "dcn_bytes_per_step": step.dcn_bytes_per_step,
            "final_loss": round(final, 4),
        }))


def main():
    from bigdl_tpu.runtime.engine import force_cpu_devices

    import jax

    force_cpu_devices(8)

    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models.resnet import resnet_cifar
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.train_step import ShardedParameterStep
    from bigdl_tpu.runtime.mesh import MeshSpec, build_mesh

    devices = jax.devices()
    global_batch = 32          # fixed across mesh sizes (strong scaling)
    steps = 8
    rs = np.random.RandomState(0)
    x = rs.rand(global_batch, 32, 32, 3).astype(np.float32)
    y = rs.randint(0, 10, (global_batch,)).astype(np.int32)

    per_mesh = {}
    for n in (1, 2, 4, 8):
        model = resnet_cifar(depth=8, classes=10)
        mesh = build_mesh(MeshSpec(data=n), devices=devices[:n])
        rng = jax.random.PRNGKey(0)
        variables = model.init(rng, jnp.asarray(x[:1]))
        step = ShardedParameterStep(
            model, CrossEntropyCriterion(),
            SGD(learning_rate=0.1, momentum=0.9), mesh, variables)
        xd, yd = step.shard_batch(x), step.shard_batch(y)
        loss = step.train_step_device(0, rng, xd, yd)
        float(np.asarray(loss))  # compile + warmup
        t0 = time.perf_counter()
        for i in range(steps):
            loss = step.train_step_device(i + 1, rng, xd, yd)
        float(np.asarray(loss))
        dt = (time.perf_counter() - t0) / steps
        per_mesh[str(n)] = {
            "step_time_ms": round(dt * 1e3, 2),
            "collective_bytes_per_step": step.collective_bytes_per_step,
            # the compressible vs fixed halves of the wire (ledger view)
            "grad_sync_bytes_per_step": step.grad_sync_ici_bytes_per_step,
            "param_sync_bytes_per_step":
                step.param_sync_ici_bytes_per_step,
        }

    t1 = per_mesh["1"]["step_time_ms"]
    speedup = {n: round(t1 / v["step_time_ms"], 3)
               for n, v in per_mesh.items()}
    efficiency = {n: round(speedup[n] / int(n), 3) for n in speedup}
    print(json.dumps({
        "metric": "simulated_mesh_strong_scaling_speedup_8dev",
        "value": speedup["8"],
        "unit": "speedup_vs_1dev",
        "vs_baseline": round(speedup["8"] / 8.0, 4),
        # virtual devices are threads of ONE host: with host_cores=1 no
        # real parallel speedup is possible — the artifact then validates
        # the sharded program + collective accounting, not the curve
        "host_cores": os.cpu_count(),
        "global_batch": global_batch,
        "per_mesh": per_mesh,
        "speedup": speedup,
        "efficiency": efficiency,
        "note": "fixed global batch on 8 virtual CPU devices (threads of "
                "one host, NOT chips): speedup saturates at the host's "
                "physical cores; the real-slice protocol is "
                "docs/performance.md §Scaling protocol",
    }))


def main_grad_comm(args):
    """Gradient-compression A/B — ONE JSON line, the
    MULTICHIP_GRADCOMM_r*.json artifact.

    Part 1 (analytic, machine-independent): the wire-dtype ledger of the
    MULTICHIP_LARGE dp_resnet50_multislice geometry (dcn_data=2, data=4)
    for fp32/bf16/int8 — the int8-vs-fp32 gradient-sync byte reduction
    is the sentinel-gated headline (acceptance: >= 3x).

    Part 2 (measured on the 8-virtual-device CPU mesh): the small bench
    model trained the same number of steps under ``grad_comm="fp32"``
    and ``"int8"`` from one seed (loss parity), plus the bucketed-
    overlap audit (exposed collective time vs total)."""
    from bigdl_tpu.runtime.engine import force_cpu_devices

    import jax

    force_cpu_devices(8)

    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models.resnet import resnet50, resnet_cifar
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.train_step import ShardedParameterStep
    from bigdl_tpu.parallel import collectives
    from bigdl_tpu.runtime.mesh import MeshSpec, build_mesh

    # -- analytic ledger on the MULTICHIP_LARGE geometry (no devices) --
    r50 = resnet50(classes=1000)
    shapes = jax.eval_shape(
        lambda r, x: r50.init(r, x), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32))
    n_params = int(sum(int(np.prod(s.shape)) for s in
                       jax.tree_util.tree_leaves(shapes["params"])))
    ledgers = {m: collectives.layout_ledger(
        n_params, ndev=4, dcn=2, mode=m, bucket_bytes=args.bucket_bytes)
        for m in ("fp32", "bf16", "int8")}
    grad_totals = {m: (led["grad_sync_ici_bytes_per_step"]
                       + led["grad_sync_dcn_bytes_per_step"])
                   for m, led in ledgers.items()}
    reduction = grad_totals["fp32"] / grad_totals["int8"]

    # -- measured parity + overlap on the small bench model ------------
    global_batch, steps = 32, args.steps
    rs = np.random.RandomState(0)
    x = rs.rand(global_batch, 32, 32, 3).astype(np.float32)
    y = rs.randint(0, 10, (global_batch,)).astype(np.int32)
    mesh = build_mesh(MeshSpec(data=4, dcn_data=2))  # both hops live
    rng = jax.random.PRNGKey(0)

    def run(mode):
        model = resnet_cifar(depth=8, classes=10)
        variables = model.init(rng, jnp.asarray(x[:1]))
        step = ShardedParameterStep(
            model, CrossEntropyCriterion(),
            SGD(learning_rate=0.1, momentum=0.9), mesh, variables,
            grad_comm=mode, comm_bucket_bytes=args.small_bucket_bytes)
        xd, yd = step.shard_batch(x), step.shard_batch(y)
        loss = None
        for i in range(steps):
            loss = step.train_step_device(i, rng, xd, yd)
        return float(np.asarray(loss)), step, (xd, yd)

    loss_f, _, _ = run("fp32")
    loss_q, step_q, (xd, yd) = run("int8")
    delta = abs(loss_q - loss_f)
    overlap = step_q.measure_overlap(xd, yd, steps=5)

    parity_tol = max(0.05 * abs(loss_f), 0.02)
    print(json.dumps({
        "metric": "multichip_grad_bytes_reduction",
        "value": round(reduction, 3),
        "unit": "x_fewer_grad_sync_bytes_int8_vs_fp32",
        "vs_baseline": None,
        "model": "resnet50",
        "n_params": n_params,
        "mesh": {"dcn_data": 2, "data": 4},
        "grad_bytes_reduction_vs_fp32": round(reduction, 3),
        "grad_sync_ici_bytes_per_step":
            ledgers["int8"]["grad_sync_ici_bytes_per_step"],
        "grad_sync_dcn_bytes_per_step":
            ledgers["int8"]["grad_sync_dcn_bytes_per_step"],
        "ledger": ledgers,
        "loss_parity": {"model": "resnet_cifar8", "steps": steps,
                        "global_batch": global_batch,
                        "fp32": round(loss_f, 4),
                        "int8": round(loss_q, 4),
                        "abs_delta": round(delta, 4),
                        "tolerance": round(parity_tol, 4)},
        "overlap": {k: (round(v, 6) if isinstance(v, float) else v)
                    for k, v in overlap.items()},
        "ok": bool(reduction >= 3.0 and delta <= parity_tol),
    }))
    return 0 if (reduction >= 3.0 and delta <= parity_tol) else 1


def main_layout(args):
    """Declarative-layout ledger A/B — ONE JSON line, the
    MULTICHIP_LAYOUT_r*.json artifact (docs/parallelism.md §Declarative
    layouts).

    Analytic and machine-independent: the 12L GPT-2-small-class
    transformer's parameter shapes (via ``jax.eval_shape`` — nothing
    compiles or computes) are priced under the per-model layout table for
    ``parallelism="dp"`` vs ``"fsdp:2,tp:4"`` on the 8-device bench
    geometry.  Per layout: per-AXIS collective bytes per step
    (``obs.cost.collective_bytes_for_specs`` reading the layout), the tp
    activation-allreduce estimate, and per-chip parameter bytes — the
    headline is the per-chip param-bytes reduction (the models-too-big-
    for-one-chip capability the layout layer exists for).  Exits non-zero
    when the reduction drops below 4x on this geometry or any parameter
    falls back to silent replication."""
    from bigdl_tpu.runtime.engine import force_cpu_devices

    import jax

    force_cpu_devices(8)

    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.nn import Transformer
    from bigdl_tpu.obs.cost import collective_bytes_for_specs
    from bigdl_tpu.parallel.layout import tp_activation_bytes
    from bigdl_tpu.parallel.mesh_policy import mesh_and_layout

    L, D, H, V, S, B = 12, 768, 12, 32768, 1024, 8
    model = Transformer(V, hidden_size=D, num_heads=H, ffn_size=4 * D,
                        num_layers=L, dropout=0.0, mode="lm")
    ids = jax.ShapeDtypeStruct((1, S), jnp.int32)
    shapes = jax.eval_shape(lambda r, x: model.init(r, x),
                            jax.random.PRNGKey(0), ids)["params"]
    n_params = int(sum(int(np.prod(s.shape))
                       for s in jax.tree_util.tree_leaves(shapes)))

    modes = {}
    fallback_total = 0
    for mode, spec in (("dp", "dp"), ("fsdp_tp", "fsdp:2,tp:4")):
        resolved = mesh_and_layout(spec)
        table = resolved.table_for(model)
        audit = table.audit(shapes)
        led = collective_bytes_for_specs(
            shapes, table.param_specs(shapes), resolved.mesh)
        tp = resolved.sizes.get("tp", 1)
        modes[mode] = {
            "parallelism": spec,
            "mesh": {k: int(v) for k, v in resolved.sizes.items()},
            "per_axis_bytes_per_step": {
                k: round(v, 1)
                for k, v in led["per_axis_bytes_per_step"].items()},
            "tp_activation_bytes_per_step": round(tp_activation_bytes(
                B, S, D, n_row_collectives=2 * L, tp=tp), 1),
            "param_bytes_per_chip": round(led["param_bytes_per_chip"], 1),
            "params_sharded": len(audit.sharded),
            "params_replicate_allowlist": len(audit.allowlisted),
            "params_silent_fallback": len(audit.fallback_replicated),
        }
        fallback_total += len(audit.fallback_replicated)

    reduction = (modes["dp"]["param_bytes_per_chip"]
                 / modes["fsdp_tp"]["param_bytes_per_chip"])
    ok = bool(reduction >= 4.0 and fallback_total == 0)
    print(json.dumps({
        "metric": "multichip_layout_param_bytes_reduction",
        "value": round(reduction, 3),
        "unit": "x_smaller_per_chip_params_fsdp_tp_vs_dp",
        "vs_baseline": None,
        "model": f"transformer_{L}L_d{D}_v{V}",
        "n_params": n_params,
        "geometry": "8dev_dp_vs_fsdp2_tp4",
        "global_batch": B,
        "seq_len": S,
        "layout_modes": modes,
        "silent_fallback_params": fallback_total,
        "ok": ok,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--real", action="store_true",
                    help="measure the REAL device mesh (launch via "
                         "`bigdl-tpu run bench_scaling.py -- --real`)")
    ap.add_argument("--grad-comm", action="store_true",
                    help="gradient-compression A/B: analytic wire ledger "
                         "(fp32/bf16/int8) on the MULTICHIP_LARGE "
                         "geometry + measured loss parity and overlap "
                         "efficiency (MULTICHIP_GRADCOMM artifact)")
    ap.add_argument("--layout", action="store_true",
                    help="declarative-layout ledger A/B: per-axis "
                         "collective bytes + per-chip param bytes of "
                         "parallelism='dp' vs 'fsdp:2,tp:4' on the 12L "
                         "transformer bench geometry (MULTICHIP_LAYOUT "
                         "artifact, sentinel-gated)")
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "resnet_cifar"])
    ap.add_argument("--wire", default="auto",
                    choices=["auto", "fp32", "bf16", "int8"],
                    help="--real gradient wire format (auto: bf16 across "
                         "hosts, fp32 within a slice)")
    ap.add_argument("--per-device-batch", type=int, default=96)
    ap.add_argument("--steps", type=int, default=None,
                    help="measured steps (default: 20 for --real, 8 for "
                         "--grad-comm)")
    ap.add_argument("--bucket-bytes", type=int, default=4 << 20,
                    help="--grad-comm ledger bucket size (flat-gradient "
                         "bytes per collective)")
    ap.add_argument("--small-bucket-bytes", type=int, default=32768,
                    help="--grad-comm measured-model bucket size (small "
                         "enough to exercise >1 bucket)")
    cli_args = ap.parse_args()
    if cli_args.steps is None:
        cli_args.steps = 8 if cli_args.grad_comm else 20
    if cli_args.steps < 1:
        ap.error("--steps must be >= 1")
    if cli_args.real:
        main_real(cli_args)
    elif cli_args.grad_comm:
        import sys

        sys.exit(main_grad_comm(cli_args))
    elif cli_args.layout:
        import sys

        sys.exit(main_layout(cli_args))
    else:
        main()
