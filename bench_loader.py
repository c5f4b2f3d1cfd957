"""Input-pipeline sustain bench — prints ONE JSON line (host only).

SURVEY.md §8 hard part #2: at scale the host CPU augmentation pipeline must
sustain the device's consumption rate or training is input-bound.  This
measures the loader-only throughput (no device): the native C++ threaded
pipeline (``native/bigdl_tpu_io.cpp``) running the ResNet-50 training
transform — bilinear resize 256 → crop 224 → hflip → normalize — on
batch-768 geometry, plus the pure-python fallback for comparison.

Since PR 4 it also measures the END-TO-END path the optimizer actually
runs (docs/data.md): record read → decode/augment → batch-assemble, both
serial (the stages in one thread, the pre-PR-4 posture) and through the
stage-parallel streaming pipeline (``data/pipeline.py``: mmap gather on a
read thread, the fused native transform fanned over decode workers into
buffer-ring slots).  ``pipeline_img_per_sec`` vs ``serial_e2e_img_per_sec``
is the PR's headline; per-stage ``data.*`` counters/gauges land in the
process-wide registry exactly as a ``/metrics`` scrape would see them.

``loader_img_per_sec`` must exceed what the chip consumes (PERF.md §5,
``resnet50.train-hostfed``) for that rate to be sustainable host-fed.
``--smoke`` runs a seconds-scale geometry and fails loudly on any pipeline
error — the CI guard against silent loader regressions.
"""

import json
import sys
import time

import numpy as np


def measure_pipeline(batch: int = 768, n_records: int = 1536,
                     epochs: int = 2, src_hw: int = 300, out_hw: int = 224,
                     workers=None, threads=None, seed: int = 0):
    """End-to-end read→decode→assemble throughput over a real record file:
    serial stages vs the streaming pipeline, same geometry and plan."""
    import os
    import tempfile

    from bigdl_tpu.data.records import write_records
    from bigdl_tpu.data.vision import AugmentedRecordImages
    from bigdl_tpu.optim.metrics import global_metrics

    rs = np.random.RandomState(seed)
    mean = (0.485 * 255, 0.456 * 255, 0.406 * 255)
    std = (0.229 * 255, 0.224 * 255, 0.225 * 255)
    out = {"e2e_batch": batch, "e2e_records": n_records, "src_hw": src_hw}
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "bench_imgs.btrec")
        # distinct random source images; labels ride along like training
        xs = rs.randint(0, 255, (n_records, src_hw, src_hw, 3), np.uint8)
        ys = rs.randint(0, 1000, n_records).astype(np.int32)
        write_records(p, {"image": xs, "label": ys})
        del xs

        def make_ds():
            return AugmentedRecordImages(
                p, (out_hw, out_hw), mean, std, resize_hw=(256, 256),
                random_crop=True, random_flip=True, num_threads=threads)

        # serial: every stage in the caller's thread (pre-PR-4 posture)
        ds = make_ds()
        n_img = 0
        list(ds.batches(batch, shuffle=True, seed=seed, epoch=0))  # warm
        t0 = time.perf_counter()
        for e in range(epochs):
            for mb in ds.batches(batch, shuffle=True, seed=seed, epoch=e):
                n_img += len(mb["input"])
        dt = time.perf_counter() - t0
        out["serial_e2e_img_per_sec"] = round(n_img / dt, 1)
        ds.close()

        # pipelined: stage-parallel with ring assembly
        from bigdl_tpu.data.pipeline import autotune_workers

        ds = make_ds()
        rates = {}
        n_img = 0
        for mb in ds.stream_batches(batch, shuffle=True, seed=seed,
                                    epoch=0, workers=workers):
            pass  # warm
        t0 = time.perf_counter()
        for e in range(epochs):
            sp = ds.stream_batches(batch, shuffle=True, seed=seed, epoch=e,
                                   workers=workers,
                                   metrics=global_metrics())
            for mb in sp:
                n_img += len(mb["input"])
            new = sp.stage_rates()
            rates = new if new.get("read_batches") else rates
        dt = time.perf_counter() - t0
        out["pipeline_img_per_sec"] = round(n_img / dt, 1)
        out["pipeline_workers"] = workers or autotune_workers()
        # per-stage counts + busy seconds + window so the rates are
        # auditable (r06's read_batches_per_s=102595.69 divided 4 batches
        # by a near-zero busy interval; these are measured-window rates)
        out["pipeline_stage_rates"] = {
            k: round(v, 6) for k, v in rates.items()}
        ds.close()

        # pipelined + device dispatch: the optimizer-side path through the
        # double-buffered transfer window.  On the CPU backend the
        # "transfer" is the detach copy, but the window bookkeeping — and
        # the overlap counter the smoke gates on — is identical to the
        # accelerator path.
        import jax

        from bigdl_tpu.data.pipeline import dispatch_to_device

        ds = make_ds()
        m = global_metrics()
        # the registry is process-global and cumulative: gate on the
        # DELTA so a smoke re-measure can't pass from a prior run's
        # counts
        base = m.snapshot()["counters"].get(
            "data.dispatch_overlapped_total", 0)
        sp = ds.stream_batches(batch, shuffle=True, seed=seed, epoch=0,
                               workers=workers, metrics=m)
        n_img = 0
        t0 = time.perf_counter()
        for dev in dispatch_to_device(
                sp, lambda mb: (jax.device_put(mb["input"]),
                                jax.device_put(mb["target"])),
                metrics=m):
            n_img += int(dev[0].shape[0])
        dt = time.perf_counter() - t0
        out["dispatch_img_per_sec"] = round(n_img / dt, 1)
        out["dispatch_overlapped_total"] = m.snapshot()["counters"].get(
            "data.dispatch_overlapped_total", 0) - base
        ds.close()

    snap = global_metrics().snapshot()
    out["pipeline_metrics"] = {
        **{k: round(v, 1) for k, v in snap["counters"].items()
           if k.startswith("data.")},
        **{k: v for k, v in snap["gauges"].items()
           if k.startswith("data.")},
    }
    if out["serial_e2e_img_per_sec"] > 0:
        out["pipeline_vs_serial"] = round(
            out["pipeline_img_per_sec"] / out["serial_e2e_img_per_sec"], 2)
    return out


def measure_loader(batch: int = 768, n_batches: int = 4,
                   src_hw: int = 300, out_hw: int = 224,
                   threads=None, seed: int = 0):
    """Returns dict with native (and python-fallback) loader img/s at the
    ResNet-50 train geometry."""
    from bigdl_tpu.native import lib as nat

    rs = np.random.RandomState(seed)
    # a pool of distinct source images, reused across batches (decode is
    # upstream of this pipeline; geometry is what's being measured)
    pool = rs.randint(0, 255, (64, src_hw, src_hw, 3), np.uint8)
    idx = rs.randint(0, len(pool), batch)
    images = [pool[i] for i in idx]
    mean = (0.485 * 255, 0.456 * 255, 0.406 * 255)
    std = (0.229 * 255, 0.224 * 255, 0.225 * 255)

    import os

    out = {"batch": batch, "out_hw": out_hw, "src_hw": src_hw,
           "native_available": nat.available(),
           # loader scales ~linearly in worker threads; a TPU-VM host has
           # O(100) cores where this sandbox may have 1 — img/s must be
           # read against host_cores
           "host_cores": os.cpu_count()}

    def rand_geom(rng):
        crops = [(rng.randint(0, 256 - out_hw + 1),
                  rng.randint(0, 256 - out_hw + 1)) for _ in range(batch)]
        flips = rng.rand(batch) < 0.5
        return crops, list(flips)

    if nat.available():
        pipe = nat.BatchPipeline(num_threads=threads)
        try:
            crops, flips = rand_geom(rs)
            pipe.process_batch(images, (out_hw, out_hw), mean, std,
                               resize_hw=(256, 256), crops=crops,
                               flips=flips)  # warmup
            t0 = time.perf_counter()
            for b in range(n_batches):
                crops, flips = rand_geom(rs)
                y = pipe.process_batch(images, (out_hw, out_hw), mean, std,
                                       resize_hw=(256, 256), crops=crops,
                                       flips=flips)
            dt = time.perf_counter() - t0
            assert y.shape == (batch, out_hw, out_hw, 3), y.shape
            out["loader_img_per_sec"] = round(batch * n_batches / dt, 1)
        finally:
            pipe.close()

    # JPEG decode+transform: the full ImageNet-style ingest (encoded bytes
    # -> decode -> resize -> crop -> flip -> normalize) in C++ workers
    if nat.available() and nat.jpeg_available():
        try:
            import io

            from PIL import Image

            enc_pool = []
            for i in range(16):
                buf = io.BytesIO()
                Image.fromarray(pool[i]).save(buf, "JPEG", quality=90)
                enc_pool.append(buf.getvalue())
            enc = [enc_pool[i % len(enc_pool)] for i in range(batch)]
            pipe = nat.BatchPipeline(num_threads=threads)
            try:
                crops, flips = rand_geom(rs)
                pipe.decode_batch(enc, (out_hw, out_hw), mean, std,
                                  resize_hw=(256, 256), crops=crops,
                                  flips=flips)  # warmup
                t0 = time.perf_counter()
                for b in range(max(1, n_batches // 2)):
                    crops, flips = rand_geom(rs)
                    y = pipe.decode_batch(enc, (out_hw, out_hw), mean, std,
                                          resize_hw=(256, 256), crops=crops,
                                          flips=flips)
                dt = time.perf_counter() - t0
                out["jpeg_decode_img_per_sec"] = round(
                    batch * max(1, n_batches // 2) / dt, 1)
            finally:
                pipe.close()
        except Exception as e:
            out["jpeg_decode_error"] = f"{type(e).__name__}: {e}"[:160]

    # record-file IO: mmap + threaded gather throughput at the same batch
    # geometry (the native sample-storage read path, data/records.py)
    try:
        import tempfile

        from bigdl_tpu.data.records import RecordDataSet, write_records

        with tempfile.TemporaryDirectory() as d:
            import os as _os

            p = _os.path.join(d, "bench.btrec")
            xs = rs.randint(0, 255, (512, out_hw, out_hw, 3), np.uint8)
            write_records(p, {"x": xs})
            ds = RecordDataSet(p)
            list(ds.batches(batch, shuffle=True, drop_last=False))  # warm
            t0 = time.perf_counter()
            nb = 0
            for _mb in ds.batches(batch, shuffle=True, seed=1,
                                  drop_last=False):
                nb += len(_mb["input"])
            dt = time.perf_counter() - t0
            out["record_read_img_per_sec"] = round(nb / dt, 1)
            out["record_read_mb_per_sec"] = round(
                nb * xs[0].nbytes / dt / 1e6, 1)
            ds.close()
    except Exception as e:  # records bench must not sink the loader bench
        out["record_read_error"] = f"{type(e).__name__}: {e}"[:160]

    # single-thread python reference (1 small batch — it is slow)
    t0 = time.perf_counter()
    small = images[:64]
    for img in small:
        a = nat.resize_bilinear(img, 256, 256) if nat.available() else img
        y0 = rs.randint(0, 256 - out_hw + 1)
        x0 = rs.randint(0, 256 - out_hw + 1)
        c = a[y0:y0 + out_hw, x0:x0 + out_hw]
        if rs.rand() < 0.5:
            c = c[:, ::-1]
        (np.asarray(c, np.float32) - np.asarray(mean)) / np.asarray(std)
    out["python_ref_img_per_sec"] = round(
        len(small) / (time.perf_counter() - t0), 1)
    return out


def smoke() -> int:
    """Seconds-scale pipeline sanity for CI: a small (but not trivial)
    geometry through the serial, streaming, and dispatch end-to-end
    paths, hard-failing on crashes, hangs (the CI step timeout), silently
    empty runs, a pipeline that lost to the serial stages, or a dispatch
    double buffer that never overlapped a transfer.  The geometry is
    sized so decode work dominates stage-threading overhead (the old
    64x64 smoke was too small to gate the ratio on).  Returns a process
    exit code."""
    geo = dict(batch=384, n_records=768, epochs=1, src_hw=256, out_hw=224)
    r = measure_pipeline(**geo)
    if r.get("pipeline_img_per_sec", 0) < r.get("serial_e2e_img_per_sec",
                                                0):
        # one re-measure before failing: the strict >= gate is the
        # design claim, but a single noisy scheduler window on a small
        # shared runner must not fail CI without a second opinion
        r = measure_pipeline(**geo)
        r["smoke_remeasured"] = True
    r["metric"] = "loader_pipeline_smoke"
    checks = {
        "ran": (r.get("pipeline_img_per_sec", 0) > 0
                and r.get("serial_e2e_img_per_sec", 0) > 0
                and r.get("pipeline_metrics", {}).get(
                    "data.read_batches", 0) > 0),
        # stage parallelism must PAY: pipelined beats the same stages run
        # serially in one thread, or the PR-4/PR-15 design regressed
        "pipelined_ge_serial": (r.get("pipeline_img_per_sec", 0)
                                >= r.get("serial_e2e_img_per_sec", 1e9)),
        # the transfer window must actually double-buffer
        "dispatch_overlap": r.get("dispatch_overlapped_total", 0) > 0,
    }
    r["smoke_checks"] = checks
    r["smoke_ok"] = all(checks.values())
    print(json.dumps(r))
    return 0 if r["smoke_ok"] else 1


def main():
    if "--smoke" in sys.argv:
        raise SystemExit(smoke())
    r = measure_loader()
    r.update(measure_pipeline())
    r.update({
        "metric": "resnet50_loader_throughput",
        "value": r.get("pipeline_img_per_sec",
                       r.get("loader_img_per_sec",
                             r["python_ref_img_per_sec"])),
        "unit": "images/sec/host",
        "vs_baseline": None,
    })
    print(json.dumps(r))


if __name__ == "__main__":
    main()
