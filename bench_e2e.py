"""End-to-end HOST-FED training bench — prints ONE JSON line.

bench.py's number is device-resident synthetic input; the claim that
steady-state training overlaps input DMA rested on CPU-only tests of
``data/prefetch.py``.  This runs the REAL
``Optimizer.optimize()`` loop — ``RecordDataSet`` (BTRECv1 mmap gather) →
``thread_prefetch`` (host lookahead) → ``prefetch_to_device`` (DMA
double-buffer) → ``ShardedParameterStep`` — on the actual device and
reports how close host-fed steady state comes to the device-resident step.

Reference analog: ``DistriOptimizer.scala`` measured throughput end-to-end
over ``RDD[Sample]``, never on synthetic device-resident tensors.

Protocol (images ship uint8, normalization runs ON DEVICE in a ``Lambda``
head, so the per-step host→device transfer is 4x smaller than f32):

- steady-state step time by difference: ``T(warm+N) - T(warm)`` over two
  ``optimize()`` runs (both pay init + cached compile; the difference is
  N steady iterations).
- device-resident comparator: same model/batch via ``ShardedParameterStep``
  on a pre-sharded batch (bench.py's measure protocol).
- verdict field ``hostfed_ratio`` = hostfed_step / device_step;
  overlap works when <= ~1.3.
- plus the loader THREAD-SCALING curve on whatever cores exist.

Env knobs: ``E2E_HW`` (default 160), ``E2E_BATCH`` per chip (128),
``E2E_STEPS`` (24), ``E2E_RECORDS`` (2048), ``E2E_TRACE=1`` attaches the
xplane summary of a short host-fed window.
"""

import json
import os
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

HW = int(os.environ.get("E2E_HW", "160"))
BATCH = int(os.environ.get("E2E_BATCH", "128"))
STEPS = int(os.environ.get("E2E_STEPS", "24"))
RECORDS = int(os.environ.get("E2E_RECORDS", "2048"))
WARM = 3
CLASSES = 100


def main():
    import jax

    from bigdl_tpu.runtime.engine import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp

    from bigdl_tpu.data.records import RecordDataSet, write_records
    from bigdl_tpu.models.resnet import resnet50
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.nn.module import Lambda, Sequential
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.train_step import ShardedParameterStep
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.runtime.mesh import MeshSpec, build_mesh

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    n_chips = len(devices)
    hw, batch_chip, steps, records = HW, BATCH, STEPS, RECORDS
    if not on_tpu:  # CPU smoke: harness check only, never evidence
        hw, batch_chip, steps, records = 32, 8, 4, 64
    batch = batch_chip * n_chips

    mean = jnp.asarray([0.485, 0.456, 0.406], jnp.float32) * 255.0
    std = jnp.asarray([0.229, 0.224, 0.225], jnp.float32) * 255.0

    def normalize(x):
        # uint8 NHWC → normalized f32 on device: the host ships 1/4 the
        # bytes and the cast fuses into the stem conv's prologue
        return (x.astype(jnp.float32) - mean) / std

    def make_model():
        return Sequential([Lambda(normalize, name="normalize"),
                           resnet50(classes=CLASSES, stem="conv")])

    criterion = CrossEntropyCriterion()

    rs = np.random.RandomState(0)
    out = {
        "metric": "resnet50_e2e_hostfed_throughput",
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "live": True,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_chips": n_chips,
        "image_size": hw,
        "batch_per_chip": batch_chip,
        "steps": steps,
        "records": records,
        "input_dtype": "uint8",
    }
    if not on_tpu:
        out["tiny_smoke"] = True

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "e2e.btrec")
        xs = rs.randint(0, 255, (records, hw, hw, 3), np.uint8)
        ys = rs.randint(0, CLASSES, (records,)).astype(np.int32)
        write_records(path, {"x": xs, "y": ys})

        def run_optimize(n_iters):
            ds = RecordDataSet(path, feature="x", label="y")
            try:
                opt = Optimizer(make_model(), ds, criterion,
                                batch_size=batch, seed=7)
                opt.set_optim_method(
                    SGD(learning_rate=0.05, momentum=0.9))
                opt.set_end_when(Trigger.max_iteration(n_iters))
                opt.log_every = max(n_iters, 1)
                opt.host_prefetch = 2
                opt.prefetch = 2
                t0 = time.perf_counter()
                opt.optimize()
                return time.perf_counter() - t0
            finally:
                ds.close()

        # untimed prewarm populates the compile caches — WITHOUT it the
        # first timed run pays full compilation while the second hits the
        # cache, and the difference estimator goes negative
        t_compile = run_optimize(1)
        t_warm = run_optimize(WARM)
        t_full = run_optimize(WARM + steps)
        hostfed_step = (t_full - t_warm) / steps
        out["hostfed_step_ms"] = round(hostfed_step * 1e3, 2)
        out["warm_s"] = round(t_warm, 2)
        out["compile_s"] = round(t_compile, 2)
        if hostfed_step <= 0 or not np.isfinite(hostfed_step):
            # difference estimator degenerated (non-steady caches or too
            # few steps): the row must not be publishable evidence
            out["suspect"] = True
            out["value"] = 0.0
        else:
            out["value"] = round(batch / hostfed_step / n_chips, 2)

        # ---- device-resident comparator (bench.py protocol) -------------
        mesh = build_mesh(MeshSpec(), devices=devices)
        model = make_model()
        rng = jax.random.PRNGKey(0)
        xb, yb = xs[:batch], ys[:batch]
        variables = model.init(rng, jnp.asarray(xb[:1]))
        step = ShardedParameterStep(
            model, criterion, SGD(learning_rate=0.05, momentum=0.9),
            mesh, variables)
        x_dev, y_dev = step.shard_batch(xb), step.shard_batch(yb)
        loss = step.train_step_device(0, rng, x_dev, y_dev)
        float(np.asarray(loss))  # warm: compile + value fetch
        t0 = time.perf_counter()
        for i in range(steps):
            loss = step.train_step_device(i + 1, rng, x_dev, y_dev)
        final = float(np.asarray(loss))
        device_step = (time.perf_counter() - t0) / steps
        assert np.isfinite(final), final
        out["device_step_ms"] = round(device_step * 1e3, 2)
        out["img_per_sec_chip_device"] = round(
            batch / device_step / n_chips, 2)
        if hostfed_step > 0:
            out["hostfed_ratio"] = round(hostfed_step / device_step, 3)
            # input-stall estimate: the fraction of host-fed step time the
            # device spent waiting on input (0 when overlap hides it all)
            out["input_stall_fraction"] = round(
                max(0.0, 1.0 - device_step / hostfed_step), 4)
            out["overlap_ok"] = bool(out["hostfed_ratio"] <= 1.3)

        if on_tpu and os.environ.get("E2E_TRACE") == "1":
            try:
                from bench import _trace_summary

                trace_dir = os.path.join(HERE, "profile_e2e_r05")
                ds = RecordDataSet(path, feature="x", label="y")
                try:
                    with jax.profiler.trace(trace_dir):
                        opt = Optimizer(make_model(), ds, criterion,
                                        batch_size=batch, seed=7)
                        opt.set_optim_method(
                            SGD(learning_rate=0.05, momentum=0.9))
                        opt.set_end_when(Trigger.max_iteration(4))
                        opt.log_every = 4
                        opt.host_prefetch = 2
                        opt.prefetch = 2
                        opt.optimize()
                finally:
                    ds.close()
                out["profile"] = _trace_summary(trace_dir)
            except Exception as e:
                out["profile"] = {"error": f"{type(e).__name__}: {e}"[:300]}

    # ---- loader thread-scaling curve (Weak #3) --------------------------
    try:
        from bench_loader import measure_loader

        cores = os.cpu_count() or 1
        threads = sorted(t for t in {1, 2, 4, 8, cores} if t <= cores)
        curve = {}
        for t in threads:
            r = measure_loader(batch=256, n_batches=2, threads=t)
            curve[str(t)] = r.get("loader_img_per_sec")
        out["loader_thread_scaling"] = {"host_cores": cores, "curve": curve}
    except Exception as e:
        out["loader_thread_scaling"] = {
            "error": f"{type(e).__name__}: {e}"[:200]}

    print(json.dumps(out))


if __name__ == "__main__":
    main()
