"""Multi-host sharded ingest specs (ISSUE 15, docs/data.md §Multi-host
ingest): per-host sharded streaming reconstructs the 1-process epoch
byte-identically (no dup / no loss), elastic restart mid-epoch keeps
plan-order determinism (PR 7's resharded ownership math through the
streaming pipeline, augmentation geometry keyed by dataset index), the
double-buffered device dispatch window, worker autosizing, honest
measured-window stage rates, and the backpressure/HELP observability
surface."""

import numpy as np
import pytest

from bigdl_tpu.data.dataset import batch_index_plan, resharded_batch_index_plan
from bigdl_tpu.data.pipeline import autotune_workers, dispatch_to_device
from bigdl_tpu.data.records import RecordDataSet, write_records
from bigdl_tpu.data.vision import AugmentedRecordImages, stream_jpeg_batches
from bigdl_tpu.optim.metrics import Metrics

RS = np.random.RandomState(15)
MEAN = (120.0, 110.0, 100.0)
STD = (60.0, 61.0, 62.0)


@pytest.fixture
def rec(tmp_path):
    x = RS.rand(80, 4, 4, 3).astype(np.float32)
    y = RS.randint(0, 7, 80).astype(np.int32)
    p = str(tmp_path / "train.btrec")
    write_records(p, {"x": x, "y": y})
    return p, x, y


@pytest.fixture
def img_rec(tmp_path):
    xs = RS.randint(0, 255, (96, 36, 36, 3), np.uint8)
    ys = RS.randint(0, 10, 96).astype(np.int32)
    p = str(tmp_path / "imgs.btrec")
    write_records(p, {"image": xs, "label": ys})
    return p, xs, ys


def _snap(mb):
    return {k: np.array(v) for k, v in mb.items()}


def _interleave_check(global_batches, host_batches, pc):
    """Global batch row j must equal host j%pc's row j//pc — the stride-
    shard contract that makes N hosts' streams concatenate to exactly the
    1-process plan order (no dup, no loss, byte-identical)."""
    n_b = len(global_batches)
    assert all(len(hb) == n_b for hb in host_batches)
    for b in range(n_b):
        for key in global_batches[b]:
            g = global_batches[b][key]
            for j in range(len(g)):
                h = host_batches[j % pc][b][key]
                np.testing.assert_array_equal(g[j], h[j // pc])


# ---------------------------------------------------------------------------
# sharded feed parity: no dup / no loss / byte-identical reconstruction
# ---------------------------------------------------------------------------

def test_records_two_host_streams_reconstruct_global_epoch(rec):
    p, _, _ = rec
    ds = RecordDataSet(p)
    ref = [_snap(mb) for mb in ds.batches(20, shuffle=True, seed=9,
                                          epoch=2)]
    hosts = []
    for pid in range(2):
        hosts.append([_snap(mb) for mb in ds.stream_batches(
            20, shuffle=True, seed=9, epoch=2, process_id=pid,
            process_count=2, workers=2)])
    assert len(ref) == 4  # 80 rows / global batch 20
    _interleave_check(ref, hosts, 2)
    ds.close()


def test_augmented_two_host_streams_reconstruct_global_epoch(img_rec):
    """Random crop + flip: geometry is keyed by DATASET INDEX, so each
    host applies exactly the augmentation the 1-process run would —
    sharded streams reconstruct the global epoch byte-identically."""
    p, _, _ = img_rec
    ds = AugmentedRecordImages(p, (24, 24), MEAN, STD, resize_hw=(30, 30),
                               random_crop=True, random_flip=True)
    ref = [_snap(mb) for mb in ds.batches(16, shuffle=True, seed=4,
                                          epoch=1)]
    hosts = []
    for pid in range(2):
        hosts.append([_snap(mb) for mb in ds.stream_batches(
            16, shuffle=True, seed=4, epoch=1, process_id=pid,
            process_count=2, workers=3)])
    assert len(ref) == 6
    _interleave_check(ref, hosts, 2)
    ds.close()


def test_sharded_stream_equals_serial_per_host(img_rec):
    """The per-host invariant the tentpole names: serial
    ``batches(process_id=...)`` and sharded ``stream_batches`` are
    byte-identical from one geometry RNG."""
    p, _, _ = img_rec
    ds = AugmentedRecordImages(p, (24, 24), MEAN, STD, resize_hw=(30, 30),
                               random_crop=True, random_flip=True)
    for pid in range(2):
        ref = [_snap(mb) for mb in ds.batches(
            32, shuffle=True, seed=11, epoch=3, process_id=pid,
            process_count=2)]
        got = [_snap(mb) for mb in ds.stream_batches(
            32, shuffle=True, seed=11, epoch=3, process_id=pid,
            process_count=2, workers=2)]
        assert len(ref) == len(got) == 3  # 48 local rows / 16 per host
        for r, g in zip(ref, got):
            assert set(r) == set(g)
            for k in r:
                np.testing.assert_array_equal(r[k], g[k])
    ds.close()


def test_jpeg_stream_sharded_reconstructs_global_epoch(tmp_path):
    import io

    from PIL import Image

    from bigdl_tpu.native import lib as nat

    if not (nat.available() and nat.jpeg_available()):
        pytest.skip("native libjpeg unavailable")
    srcs = []
    for i in range(24):
        buf = io.BytesIO()
        Image.fromarray(RS.randint(0, 255, (40, 40, 3), np.uint8)).save(
            buf, "JPEG", quality=92)
        srcs.append(buf.getvalue())
    labels = np.arange(24, dtype=np.int32)
    kw = dict(out_hw=(24, 24), mean=MEAN, std=STD, resize_hw=(32, 32),
              random_crop=True, random_flip=True, shuffle=True, seed=6,
              epoch=0, labels=labels)
    ref = [_snap(mb) for mb in stream_jpeg_batches(srcs, 8, **kw)]
    hosts = []
    for pid in range(2):
        hosts.append([_snap(mb) for mb in stream_jpeg_batches(
            srcs, 8, process_id=pid, process_count=2, workers=2, **kw)])
    assert len(ref) == 3
    _interleave_check(ref, hosts, 2)


# ---------------------------------------------------------------------------
# elastic restart mid-epoch: plan-order determinism across a pc change
# ---------------------------------------------------------------------------

def test_resharded_stream_matches_resharded_serial(rec):
    p, _, _ = rec
    ds = RecordDataSet(p)
    kw = dict(trained_batches=2, old_process_count=1, shuffle=True,
              seed=3, epoch=1, process_id=0, process_count=2)
    ref = [_snap(mb) for mb in ds.resharded_batches(20, **kw)]
    got = [_snap(mb) for mb in ds.resharded_stream_batches(
        20, workers=2, **kw)]
    assert len(ref) == len(got) == 2  # (80 - 2*20) remaining / 20 global
    for r, g in zip(ref, got):
        assert set(r) == set(g)
        for k in r:
            np.testing.assert_array_equal(r[k], g[k])
    ds.close()


def test_restart_mid_epoch_determinism_across_process_change(img_rec):
    """The restart-mid-epoch determinism spec: an epoch trained k batches
    by 1 process and finished by 2 re-uses PR 7's resharded ownership
    math — every remaining image is decoded exactly once across the new
    hosts, with BYTE-IDENTICAL pixels to the uninterrupted epoch (the
    index-keyed geometry survives the process-count change)."""
    p, _, _ = img_rec
    n, bs, trained = 96, 16, 2
    ds = AugmentedRecordImages(p, (24, 24), MEAN, STD, resize_hw=(30, 30),
                               random_crop=True, random_flip=True)
    kw = dict(shuffle=True, seed=8, epoch=5)
    # reference: the uninterrupted 1-process epoch, pixels by dataset index
    ref_px = {}
    plan = batch_index_plan(n, bs, **kw)
    for mb, (sel, _) in zip(ds.batches(bs, **kw), plan):
        for j, i in enumerate(sel):
            ref_px[int(i)] = (np.array(mb["input"][j]),
                              int(mb["target"][j]))
    # the examples the interrupted run already covered
    done = {int(i)
            for sel, _ in list(batch_index_plan(n, bs, **kw))[:trained]
            for i in sel}
    remaining = set(ref_px) - done
    # resume under process_count=2: a FRESH dataset object per host (a
    # restart has no in-memory state to lean on)
    seen = {}
    for pid in range(2):
        ds2 = AugmentedRecordImages(p, (24, 24), MEAN, STD,
                                    resize_hw=(30, 30), random_crop=True,
                                    random_flip=True)
        plan2 = resharded_batch_index_plan(
            n, bs, trained_batches=trained, old_process_count=1,
            process_id=pid, process_count=2, **kw)
        stream = ds2.resharded_stream_batches(
            bs, trained_batches=trained, old_process_count=1,
            process_id=pid, process_count=2, workers=2, **kw)
        for mb, (sel, n_real) in zip(stream, plan2):
            for j, i in enumerate(sel[:n_real]):
                assert int(i) not in seen, "duplicate across hosts"
                seen[int(i)] = (np.array(mb["input"][j]),
                                int(mb["target"][j]))
        ds2.close()
    assert set(seen) == remaining, "dup/loss in the resharded remainder"
    for i, (px, lb) in seen.items():
        np.testing.assert_array_equal(px, ref_px[i][0])
        assert lb == ref_px[i][1]
    ds.close()


# ---------------------------------------------------------------------------
# early errors: non-divisible geometries reject at call time
# ---------------------------------------------------------------------------

def test_non_divisible_global_batch_rejected_early(rec, img_rec):
    p, _, _ = rec
    ds = RecordDataSet(p)
    with pytest.raises(ValueError, match=r"10.*3"):
        ds.stream_batches(10, process_id=0, process_count=3)
    with pytest.raises(ValueError, match=r"10.*3"):
        ds.steps_per_epoch(10, process_count=3)
    ds.close()
    ip, _, _ = img_rec
    ids = AugmentedRecordImages(ip, (24, 24), MEAN, STD)
    with pytest.raises(ValueError, match=r"16.*5"):
        ids.stream_batches(16, process_id=0, process_count=5)
    ids.close()
    with pytest.raises(ValueError, match=r"8.*3"):
        stream_jpeg_batches([b"x"] * 24, 8, (24, 24), MEAN, STD,
                            resize_hw=(32, 32), process_id=0,
                            process_count=3)


# ---------------------------------------------------------------------------
# double-buffered dispatch
# ---------------------------------------------------------------------------

def test_dispatch_double_buffer_overlaps_and_stays_correct(rec):
    """The transfer window keeps 2 puts in flight (overlap counter > 0),
    the in-flight gauge drains to 0, and every device batch still matches
    the serial epoch — the slot-reuse aliasing invariant under the new
    release-at-next-issue rule."""
    import jax

    p, _, _ = rec
    ds = RecordDataSet(p)
    m = Metrics()
    stream = ds.stream_batches(10, shuffle=True, seed=2, epoch=0,
                               workers=2, ring_depth=2, raw_depth=1,
                               metrics=m)
    devs = list(dispatch_to_device(
        stream, lambda mb: (jax.device_put(np.asarray(mb["input"])),
                            jax.device_put(np.asarray(mb["target"]))),
        size=2, metrics=m))
    ref = list(ds.batches(10, shuffle=True, seed=2, epoch=0))
    assert len(devs) == len(ref) == 8
    for (xd, yd), mb in zip(devs, ref):
        np.testing.assert_array_equal(np.asarray(xd), mb["input"])
        np.testing.assert_array_equal(np.asarray(yd), mb["target"])
    snap = m.snapshot()
    assert snap["counters"]["data.dispatch_overlapped_total"] > 0
    assert snap["gauges"]["data.dispatch.in_flight"] == 0  # drained
    ds.close()


def test_accelerator_path_defers_slot_release_past_next_pull(rec,
                                                             monkeypatch):
    """On accelerator backends the stream's post-yield auto-release fires
    when the consumer pulls batch k+1 — BEFORE transfer k is synced — so
    the dispatch stage must take ownership of the release
    (``RingBatch.defer_release``) and free slot k only at its drain
    point.  This spec pins the ordering: at the issue of put k, exactly
    max(0, k-1) slots have been released (slot k-1 frees during put k,
    after the sync), never k — which is what the pre-fix auto-release
    would produce."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p, _, _ = rec
    ds = RecordDataSet(p)
    stream = ds.stream_batches(10, shuffle=True, seed=5, epoch=0,
                               workers=2, ring_depth=2, raw_depth=1)
    released = []
    orig = stream.ring.release
    monkeypatch.setattr(stream.ring, "release",
                        lambda s: (released.append(s), orig(s))[1])
    snapshots = []

    def put(mb):
        snapshots.append(len(released))
        # copy before device_put: the real accelerator DMA copies; on the
        # CPU test backend a zero-copy of the (later-recycled) slot would
        # alias — the copy keeps this a pure release-ORDERING spec
        return (jax.device_put(np.array(mb["input"])),
                jax.device_put(np.array(mb["target"])))

    devs = list(dispatch_to_device(stream, put, size=2))
    ref = list(ds.batches(10, shuffle=True, seed=5, epoch=0))
    assert len(devs) == len(ref) == 8
    for (xd, yd), mb in zip(devs, ref):
        np.testing.assert_array_equal(np.asarray(xd), mb["input"])
        np.testing.assert_array_equal(np.asarray(yd), mb["target"])
    assert len(released) == 8  # every slot went back, exactly once
    assert snapshots == [max(0, k - 1) for k in range(8)]
    ds.close()


def test_ring_batch_defer_release_transfers_ownership():
    """defer_release marks the batch released (auto-release no-ops) and
    hands back the one real release; double-defer is inert."""
    from bigdl_tpu.data.pipeline import RingBatch

    calls = []
    mb = RingBatch(lambda: calls.append("freed"), input=np.zeros(2))
    rel = mb.defer_release()
    mb.release()  # the stream's post-yield auto-release
    assert calls == []  # ownership moved: auto-release no longer frees
    assert mb.defer_release()() is None and calls == []  # second defer inert
    rel()
    assert calls == ["freed"]


def test_dispatch_inflight_one_is_the_serial_window(rec):
    """inflight=1 degenerates to the old block-inline behaviour: correct,
    and never more than one transfer in the window."""
    import jax

    p, _, _ = rec
    ds = RecordDataSet(p)
    stream = ds.stream_batches(10, shuffle=True, seed=2, epoch=1,
                               workers=2, ring_depth=2, raw_depth=1)
    devs = list(dispatch_to_device(
        stream, lambda mb: jax.device_put(np.asarray(mb["input"])),
        size=2, inflight=1))
    ref = list(ds.batches(10, shuffle=True, seed=2, epoch=1))
    for xd, mb in zip(devs, ref):
        np.testing.assert_array_equal(np.asarray(xd), mb["input"])
    with pytest.raises(ValueError):
        dispatch_to_device([], lambda mb: mb, inflight=0)
    ds.close()


# ---------------------------------------------------------------------------
# decode-pool autosizing + honest stage rates
# ---------------------------------------------------------------------------

def test_autotune_workers_policy():
    # no rates: the whole ceiling (cores minus reserve), floor of 2 so a
    # 2-core host keeps two workers
    assert autotune_workers(host_cores=24) == 22
    assert autotune_workers(host_cores=2) == 2
    assert autotune_workers(host_cores=1) == 1
    # need-based: enough workers to meet the target at the probed rate
    assert autotune_workers(decode_rate=10.0, target_rate=35.0,
                            host_cores=24) == 4
    assert autotune_workers(decode_rate=10.0, target_rate=1e9,
                            host_cores=24) == 22  # capped at the ceiling
    assert autotune_workers(decode_rate=100.0, target_rate=1.0,
                            host_cores=24) == 1


def test_stage_rates_measured_window(rec):
    """stage_rates reports counts, busy seconds, and rates over the
    MEASURED window — not a count divided by a near-zero busy interval
    (which once reported 102595.69 batches/s)."""
    p, _, _ = rec
    ds = RecordDataSet(p)
    sp = ds.stream_batches(10, shuffle=False, workers=2)
    n = sum(1 for _ in sp)
    r = sp.stage_rates()
    assert r["window_s"] > 0
    assert r["read_batches"] == n == 8
    assert r["read_busy_s"] >= 0
    # windowed rate is count/window by definition...
    assert r["read_batches_per_s"] == pytest.approx(
        r["read_batches"] / r["window_s"], rel=0.25)
    # ...and capacity (count/busy) can only exceed it
    assert r["read_capacity_batches_per_s"] >= r["read_batches_per_s"]
    assert r["decode_capacity_batches_per_s"] >= r["decode_batches_per_s"]
    ds.close()


def test_backpressure_and_shard_rate_gauges_exported(rec):
    p, _, _ = rec
    ds = RecordDataSet(p)
    m = Metrics()
    for _ in ds.stream_batches(10, shuffle=False, workers=2, metrics=m):
        pass
    g = m.snapshot()["gauges"]
    for name in ("data.backpressure.read", "data.backpressure.decode",
                 "data.rate.shard_img_per_s",
                 "data.rate.read_batches_per_s"):
        assert name in g, name
    assert 0.0 <= g["data.backpressure.read"] <= 1.0
    assert 0.0 <= g["data.backpressure.decode"] <= 1.0
    assert g["data.rate.shard_img_per_s"] > 0
    ds.close()


def test_slow_consumer_not_blamed_on_read_stage(rec):
    """Device-bound runs: the consumer holds ring slots, the raw queue
    drains, decode workers idle — but that idleness is NOT read-stage
    backpressure.  decode starvation only accumulates while a ring slot
    was free (read had room to produce), so a slow consumer shows up as
    backpressure.read, never as a read-bound verdict."""
    import time as _time

    p, _, _ = rec
    ds = RecordDataSet(p)
    m = Metrics()
    sp = ds.stream_batches(10, shuffle=False, workers=2, ring_depth=2,
                           raw_depth=1, metrics=m)
    for mb in sp:
        _time.sleep(0.08)  # consumer (device) is the bottleneck
    g = m.snapshot()["gauges"]
    assert g["data.backpressure.read"] > 0.5  # blocked on the full ring
    assert g["data.backpressure.decode"] < 0.3  # ...but read isn't blamed
    ds.close()


def test_host_core_count_is_affinity_aware():
    import os

    from bigdl_tpu.data.pipeline import host_core_count

    n = host_core_count()
    assert n >= 1
    if hasattr(os, "sched_getaffinity"):
        assert n == len(os.sched_getaffinity(0))


def test_export_help_covers_ingest_gauges():
    """Every data.* family the ingest pipeline exports carries a HELP
    string — the HELP-coverage discipline from PR 6."""
    from bigdl_tpu.obs.export import DEFAULT_HELP

    for name in ("data.read_batches", "data.decoded_images",
                 "data.ready_batches", "data.queue_depth.raw",
                 "data.queue_depth.ring", "data.backpressure.read",
                 "data.backpressure.decode", "data.dispatch.in_flight",
                 "data.dispatch_overlapped_total",
                 "data.rate.shard_img_per_s",
                 "data.rate.read_batches_per_s",
                 "data.rate.decode_batches_per_s",
                 "data.rate.read_capacity_batches_per_s",
                 "data.rate.decode_capacity_batches_per_s"):
        assert name in DEFAULT_HELP and DEFAULT_HELP[name], name
