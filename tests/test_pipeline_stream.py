"""Streaming input pipeline specs (docs/data.md): stage-parallel
read→decode→assemble over the buffer ring — determinism for any worker
count, crash propagation (never a hang), ring slot-lending safety, the
prefetch leak fix, and the data.* observability surface."""

import threading
import time

import numpy as np
import pytest

from bigdl_tpu.data import dataset as dataset_mod
from bigdl_tpu.data.dataset import ArrayDataSet, gather_rows
from bigdl_tpu.data.pipeline import (
    BufferRing, PipelineError, RingBatch, StreamingPipeline,
    autotune_depths, autotune_workers, dispatch_to_device,
)
from bigdl_tpu.data.prefetch import prefetch_to_device
from bigdl_tpu.data.records import RecordDataSet, write_records
from bigdl_tpu.data.vision import AugmentedRecordImages
from bigdl_tpu.optim.metrics import Metrics

RS = np.random.RandomState(7)


@pytest.fixture
def rec(tmp_path):
    x = RS.rand(100, 4, 4, 3).astype(np.float32)
    y = RS.randint(0, 5, 100).astype(np.int32)
    p = str(tmp_path / "train.btrec")
    write_records(p, {"x": x, "y": y})
    return p, x, y


@pytest.fixture
def img_rec(tmp_path):
    xs = RS.randint(0, 255, (64, 40, 40, 3), np.uint8)
    ys = RS.randint(0, 10, 64).astype(np.int32)
    p = str(tmp_path / "imgs.btrec")
    write_records(p, {"image": xs, "label": ys})
    return p, xs, ys


def _snap(mb):
    # RingBatch arrays are views over reusable slots: copy before the next
    # pull (the documented consumer contract)
    return {k: np.array(v) for k, v in mb.items()}


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------

def test_stream_matches_serial_any_worker_count(rec):
    """stream_batches is byte-identical to batches() for 1 and N workers —
    geometry and order come from the plan, never worker scheduling."""
    p, x, y = rec
    ds = RecordDataSet(p)
    ref = [_snap(mb) for mb in ds.batches(16, shuffle=True, seed=3,
                                          epoch=1, drop_last=False)]
    for w in (1, 3):
        got = [_snap(mb) for mb in ds.stream_batches(
            16, shuffle=True, seed=3, epoch=1, drop_last=False, workers=w)]
        assert len(got) == len(ref)
        for a, b in zip(ref, got):
            assert set(a) == set(b)
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])
    ds.close()


def test_augmented_epochs_identical_for_1_vs_n_workers(img_rec):
    """Seeded augmentation (random crop + flip) through the fused native
    transform: identical epochs for 1 vs 3 decode workers, and identical
    to the serial stage path."""
    p, xs, ys = img_rec
    mean, std = (0.5 * 255,) * 3, (0.25 * 255,) * 3
    ds = AugmentedRecordImages(p, (24, 24), mean, std, resize_hw=(32, 32),
                               random_crop=True, random_flip=True)
    for epoch in (0, 2):
        ref = [_snap(mb) for mb in ds.batches(16, shuffle=True, seed=5,
                                              epoch=epoch)]
        for w in (1, 3):
            got = [_snap(mb) for mb in ds.stream_batches(
                16, shuffle=True, seed=5, epoch=epoch, workers=w)]
            assert len(got) == len(ref) > 0
            for a, b in zip(ref, got):
                for k in a:
                    np.testing.assert_array_equal(
                        a[k], b[k], err_msg=f"epoch {epoch} workers {w} {k}")
    ds.close()


# ---------------------------------------------------------------------------
# failure propagation
# ---------------------------------------------------------------------------

def test_decode_crash_propagates_not_hangs():
    """A worker exception re-raises at the consumer within a bounded wait
    (the training loop's retry path sees it; the run never wedges)."""
    def bad_decode(item, raw, bufs, lo, hi, slot):
        if item >= 2:
            raise RuntimeError("decoder exploded")
        bufs["x"][lo:hi] = item
        return {"n": 4}

    pl = StreamingPipeline(iter(range(8)), lambda i, s: i, bad_decode,
                           {"x": ((4, 2), np.float32)}, rows=4, workers=2)
    t0 = time.time()
    with pytest.raises(PipelineError) as ei:
        for _ in pl:
            pass
    assert time.time() - t0 < 30
    assert "exploded" in str(ei.value.__cause__)


def test_empty_and_dry_plans_terminate_not_hang(rec):
    """A plan that yields nothing (shard smaller than the batch with
    drop_last) — or runs dry while the consumer is already parked in
    pop() — ends iteration instead of spinning forever."""
    p, _, _ = rec
    ds = RecordDataSet(p)
    t0 = time.time()
    # 100 records, batch 128, drop_last=True -> zero planned batches
    assert list(ds.stream_batches(128, shuffle=False, workers=2)) == []
    assert time.time() - t0 < 30
    ds.close()

    def slow_plan():
        yield 0
        time.sleep(0.3)  # consumer parks in pop(seq=1) before plan ends

    def decode(item, raw, bufs, lo, hi, slot):
        bufs["x"][lo:hi] = item
        return {"n": 2}

    pl = StreamingPipeline(slow_plan(), lambda i, s: i, decode,
                           {"x": ((2,), np.float32)}, rows=2, workers=1)
    t0 = time.time()
    assert len(list(pl)) == 1
    assert time.time() - t0 < 30


def test_fetch_crash_propagates():
    def fetch(item, slot):
        raise OSError("disk fell off")

    pl = StreamingPipeline(iter(range(3)), fetch,
                           lambda *a: None, {"x": ((2,), np.float32)},
                           rows=2, workers=1)
    with pytest.raises(PipelineError) as ei:
        next(iter(pl))
    assert isinstance(ei.value.__cause__, OSError)


def test_abandoned_consumer_stops_stage_threads(rec):
    """Walking away mid-epoch (preemption break, end_when) shuts the read
    and decode threads down instead of leaking them per epoch."""
    p, _, _ = rec
    ds = RecordDataSet(p)
    before = threading.active_count()
    sp = ds.stream_batches(16, workers=2)
    it = iter(sp)
    next(it)
    it.close()  # the driver's generator-close path
    deadline = time.time() + 10
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before
    ds.close()


# ---------------------------------------------------------------------------
# ring safety
# ---------------------------------------------------------------------------

def test_ring_never_lends_slot_in_flight():
    """A slot is never re-assigned while READY or LENT: writers see only
    FREE slots, and the strict state machine rejects protocol violations."""
    ring = BufferRing({"x": ((2,), np.float32)}, depth=2)
    stop = threading.Event()
    s0 = ring.assign(0, 1, stop)
    s1 = ring.assign(1, 1, stop)
    assert {s0, s1} == {0, 1}
    # ring full: a non-blocking probe must find nothing FREE
    got = []
    t = threading.Thread(target=lambda: got.append(
        ring.assign(2, 1, stop, timeout=0.01)))
    stop2 = threading.Event()
    ring.part_done(s0, {"n": 2})
    slot, bufs, meta = ring.pop(0, stop2, lambda: None)
    assert slot == s0 and meta["n"] == 2
    # still LENT: seq-2 assignment can only take the OTHER slot once it
    # becomes free
    ring.part_done(s1)
    t.start()
    ring.pop(1, stop2, lambda: None)
    ring.release(s1)
    t.join(5)
    assert got == [s1]  # never the LENT s0
    # protocol violations raise instead of corrupting
    with pytest.raises(PipelineError):
        ring.release(s1)  # not lent anymore (double release path)
    ring.release(s0)
    with pytest.raises(PipelineError):
        ring.release(s0)  # double release
    with pytest.raises(PipelineError):
        ring.part_done(s0)  # not assigned


def test_ring_reuse_no_allocation_and_no_corruption(rec):
    """Slots recycle (bounded buffer identity set) and in-order delivery
    survives a slow consumer — data read before the next pull is intact."""
    p, x, _ = rec
    ds = RecordDataSet(p)
    seen_ids = set()
    total = 0
    for e in range(3):
        got = []
        for mb in ds.stream_batches(20, shuffle=False, epoch=e, workers=2):
            seen_ids.add(id(mb["input"].base)
                         if mb["input"].base is not None
                         else id(mb["input"]))
            got.append(np.array(mb["input"]))
            total += 1
            time.sleep(0.002)  # let producers run ahead into the ring
        np.testing.assert_array_equal(np.concatenate(got), x)
    # ring buffers are cached on the dataset and reused across epochs:
    # 15 batches flow through at most one ring's worth of arrays
    assert total == 15 and len(seen_ids) <= 8
    ds.close()


def test_ring_batch_release_idempotent():
    calls = []
    rb = RingBatch(lambda: calls.append(1), input=np.zeros(2))
    rb.release()
    rb.release()
    assert calls == [1]


# ---------------------------------------------------------------------------
# dispatch + prefetch satellites
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("source", ["records", "arrays"])
def test_dispatch_to_device_survives_slot_reuse(rec, source):
    """Device arrays keep their batch's data even after the ring slot they
    came from is recycled many times over — the XLA:CPU zero-copy
    device_put alias trap (a released slot refilled under a live device
    array corrupts training silently).  Small ring + many batches forces
    heavy reuse; every device array must still match the serial epoch."""
    import jax

    p, x, y = rec
    ds = RecordDataSet(p) if source == "records" else ArrayDataSet(x, y)
    for epoch in range(3):
        stream = ds.stream_batches(10, shuffle=True, seed=7, epoch=epoch,
                                   workers=2, parts_per_batch=2,
                                   ring_depth=2, raw_depth=1)
        devs = list(dispatch_to_device(
            stream, lambda mb: (jax.device_put(np.asarray(mb["input"])),
                                jax.device_put(np.asarray(mb["target"]))),
            size=2))
        ref = list(ds.batches(10, shuffle=True, seed=7, epoch=epoch))
        assert len(devs) == len(ref) == 10
        for (xd, yd), mb in zip(devs, ref):
            np.testing.assert_array_equal(np.asarray(xd), mb["input"])
            np.testing.assert_array_equal(np.asarray(yd), mb["target"])
    if source == "records":
        ds.close()


class _ClosableIter:
    def __init__(self, n):
        self._it = iter(range(n))
        self.closed = False

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._it)

    def close(self):
        self.closed = True


def test_prefetch_to_device_closes_upstream_on_abandonment():
    """Satellite: prefetch_to_device mirrors thread_prefetch's cleanup —
    abandoning the iterator closes the upstream producer."""
    src = _ClosableIter(100)
    it = prefetch_to_device(src, lambda b: b, size=3)
    assert next(it) == 0
    it.close()  # abandon mid-stream
    assert src.closed

    # ...but a normally-exhausted iterator does NOT re-close its upstream
    src2 = _ClosableIter(3)
    assert list(prefetch_to_device(src2, lambda b: b, size=2)) == [0, 1, 2]
    assert not src2.closed


# ---------------------------------------------------------------------------
# observability + autotune
# ---------------------------------------------------------------------------

def test_stage_metrics_and_gauges_exported(rec):
    """data.* counters and queue-depth gauges land in the registry and
    render as Prometheus lines — the /metrics view of the pipeline."""
    from bigdl_tpu.obs.export import render_prometheus

    p, _, _ = rec
    ds = RecordDataSet(p)
    m = Metrics()
    for _ in ds.stream_batches(20, shuffle=False, metrics=m, workers=2):
        pass
    s = m.summary()
    assert s["data.read_batches"] == 5
    assert s["data.decoded_images"] == 100
    assert "data.queue_depth.ring" in s
    text = render_prometheus(m)
    assert "# TYPE data_read_batches counter" in text
    assert "# TYPE data_queue_depth_ring gauge" in text
    ds.close()


def test_data_wait_histogram_recorded_by_driver(rec):
    """The optimizer's data phase lands waits in train.data_wait_s — the
    input-bound-vs-device-bound verdict metric."""
    from bigdl_tpu import nn, optim

    p, _, _ = rec
    ds = RecordDataSet(p)
    model = nn.Sequential([nn.Flatten(), nn.Linear(48, 5)])
    opt = optim.Optimizer(model, ds, nn.CrossEntropyCriterion(),
                          batch_size=40)
    opt.set_optim_method(optim.Adam(learning_rate=0.05))
    opt.set_end_when(optim.Trigger.max_iteration(4))
    assert opt.host_prefetch == 2  # satellite: lookahead on by default
    trained = opt.optimize()
    assert trained is not None
    snap = opt.metrics.snapshot()
    assert snap["hists"]["train.data_wait_s"]["n"] >= 4
    ds.close()


def test_autotune_depths_tracks_stage_ratio():
    fast_read = autotune_depths(read_rate=100.0, decode_rate=5.0, workers=4)
    assert fast_read["raw_depth"] == 1  # reader far ahead: no lookahead
    slow_read = autotune_depths(read_rate=5.0, decode_rate=100.0, workers=4)
    assert slow_read["raw_depth"] == 4  # reader is the bottleneck
    # sub-batch parts (default): workers share a slot, ring stays small —
    # image-batch slots are hundreds of MB each
    assert slow_read["ring_depth"] == 4
    # whole-batch parts: each worker fills its own slot
    assert autotune_depths(5.0, 100.0, 4,
                           parts_per_batch=1)["ring_depth"] == 7
    assert autotune_depths(0, 0, 2)["ring_depth"] == 4


def test_shared_memory_decode_pool_matches_native(img_rec):
    """The PIL fallback's multiprocess shared-memory decode produces the
    same batches as the native path (same math, same rounding)."""
    import io

    from PIL import Image

    from bigdl_tpu.data.vision import stream_jpeg_batches

    _, xs, ys = img_rec
    enc = []
    for i in range(24):
        buf = io.BytesIO()
        Image.fromarray(xs[i]).save(buf, "JPEG", quality=90)
        enc.append(buf.getvalue())
    mean, std = (0.5 * 255,) * 3, (0.25 * 255,) * 3
    kw = dict(labels=ys[:24], resize_hw=(32, 32), random_crop=True,
              random_flip=True, seed=1, workers=2)
    a = [_snap(mb) for mb in stream_jpeg_batches(
        enc, 8, (24, 24), mean, std, use_processes=False, **kw)]
    b = [_snap(mb) for mb in stream_jpeg_batches(
        enc, 8, (24, 24), mean, std, use_processes=True, **kw)]
    assert len(a) == len(b) == 3
    for x1, x2 in zip(a, b):
        np.testing.assert_array_equal(x1["target"], x2["target"])
        np.testing.assert_allclose(x1["input"], x2["input"], atol=1e-5)


# ---------------------------------------------------------------------------
# in-memory arrays on the ring (docs/data.md §In-memory arrays)
# ---------------------------------------------------------------------------

def _arrays(form, n=103):
    rs = np.random.RandomState(11)
    x = rs.rand(n, 5, 3).astype(np.float32)
    y = rs.randint(0, 5, n).astype(np.int32)
    if form == "one_array":
        return ArrayDataSet(x, y)
    if form == "tuple_of_arrays":
        return ArrayDataSet((x, rs.randint(0, 9, (n, 2))), y)
    return ArrayDataSet(x, y, transform=lambda row: (row * 2).sum(0))


def _snap_any(mb):
    return {k: (tuple(np.array(t) for t in v) if isinstance(v, tuple)
                else np.array(v)) for k, v in mb.items()}


def _assert_same_batches(ref, got):
    assert len(got) == len(ref) > 0
    for a, b in zip(ref, got):
        assert list(a) == list(b)  # the same fields, in the same order
        for k in a:
            for u, v in zip(*[t if isinstance(t, tuple) else (t,)
                              for t in (a[k], b[k])]):
                assert u.dtype == v.dtype and u.shape == v.shape
                assert u.tobytes() == v.tobytes()


FORMS = ["one_array", "tuple_of_arrays", "transform"]


@pytest.mark.parametrize("process_count", [1, 2])
@pytest.mark.parametrize("workers", [1, 3])
@pytest.mark.parametrize("form", FORMS)
def test_array_stream_matches_serial(form, workers, process_count):
    """The ring path is byte-identical to batches(): every field, every
    worker count, every host's share, with a cyclic-padded tail (103 rows
    in batches of 16 leave 7, or 3 and 4 over two hosts)."""
    ds = _arrays(form)
    for pid in range(process_count):
        kw = dict(shuffle=True, seed=3, epoch=2, drop_last=False,
                  process_id=pid, process_count=process_count)
        ref = [_snap_any(mb) for mb in ds.batches(16, **kw)]
        assert "weight" in ref[-1] and "weight" not in ref[0]
        stream = ds.stream_batches(16, workers=workers, parts_per_batch=3,
                                   **kw)
        assert isinstance(stream, StreamingPipeline)
        got = []
        for mb in stream:
            assert isinstance(mb, RingBatch)
            got.append(_snap_any(mb))
        _assert_same_batches(ref, got)


@pytest.mark.parametrize("form", FORMS)
def test_array_resharded_stream_matches_serial(form):
    """The elastic remainder epoch (2 hosts trained 2 batches, 3 finish)
    through the ring equals resharded_batches()."""
    ds = _arrays(form)
    for pid in range(3):
        kw = dict(trained_batches=2, old_process_count=2, shuffle=True,
                  seed=5, epoch=1, drop_last=False, process_id=pid,
                  process_count=3)
        ref = [_snap_any(mb) for mb in ds.resharded_batches(12, **kw)]
        got = [_snap_any(mb) for mb in ds.resharded_stream_batches(
            12, workers=2, parts_per_batch=2, **kw)]
        _assert_same_batches(ref, got)


@pytest.mark.parametrize("sel, ok", [
    ([3, 0, 3, 9], True), ([], True), ([0, -1], False), ([10], False)])
def test_gather_rows_is_the_fancy_index_with_indices_checked(sel, ok):
    src = np.arange(40, dtype=np.float32).reshape(10, 4)
    sel = np.asarray(sel, np.int64)
    out = np.full((len(sel), 4), -1, np.float32)
    if ok:
        assert gather_rows(src, sel, out) is out
        np.testing.assert_array_equal(out, src[sel])
    else:
        with pytest.raises(IndexError):
            gather_rows(src, sel, out)
        assert (out == -1).all()  # checked before a byte moved


def _pool_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith("bigdl-tpu-data-")]


def _optimize_arrays(ds, steps, batch_size=16):
    """A short seeded run; per step, the loss and the pool threads alive."""
    from bigdl_tpu import nn, optim

    model = nn.Sequential([nn.Flatten(), nn.Linear(15, 5), nn.LogSoftMax()])
    opt = optim.Optimizer(model, ds, nn.ClassNLLCriterion(),
                          batch_size=batch_size, seed=4)
    opt.set_optim_method(optim.SGD(learning_rate=0.1))
    seen = {}

    def end(state):
        it = state["iteration"]
        if it and it not in seen:
            seen[it] = (float(state["loss"]), _pool_threads())
        return it >= steps

    opt.set_end_when(optim.Trigger(end, "steps"))
    opt.optimize()
    return opt, seen


@pytest.mark.parametrize("batch", ["large", "small"])
def test_bytes_rule_decides_the_path_of_an_optimizer_run(batch, monkeypatch):
    """A batch worth several parts rides the ring (data.ready_batches
    counts every step, a worker pool runs); a batch worth one keeps the
    one-thread path: counter silent, no pipeline thread ever started.
    Either way data.produce_s is observed once per batch made."""
    ds = _arrays("one_array", n=96)  # a batch is 16 x 64 bytes
    if batch == "large":
        monkeypatch.setattr(dataset_mod, "_PART_BYTES", 256)
    opt, seen = _optimize_arrays(ds, steps=9)  # crosses an epoch's end
    snap = opt.metrics.snapshot()
    made = snap["hists"]["data.produce_s"]["n"]
    ready = snap["counters"].get("data.ready_batches", 0)
    pools = [names for _, names in seen.values()]
    assert sorted(seen) == list(range(1, 10)) and made >= 9
    if batch == "large":
        assert ready == made
        assert any("bigdl-tpu-data-decode-0" in names for names in pools)
    else:
        assert ready == 0 and not any(pools)
    assert not _pool_threads()  # nothing outlives the run


def test_bytes_rule_at_real_sizes():
    """No knob turned: a batch worth three parts is split in three (one
    worker each), the same rows in a batch just under two parts' worth,
    and any subclass that assembles batches its own way, keep the serial
    generator."""
    part = dataset_mod._PART_BYTES
    x = np.zeros((16, part // 16), np.float32)  # rows of a quarter part
    ds = ArrayDataSet(x, np.zeros(16, np.int32))
    sp = ds.stream_batches(12, ring_depth=2)
    try:
        assert isinstance(sp, StreamingPipeline)
        assert sp.parts == sp.workers == min(3, autotune_workers())
    finally:
        sp.close()
    assert not isinstance(ds.stream_batches(7), StreamingPipeline)

    class Own(ArrayDataSet):
        def _emit(self, plan):
            return super()._emit(plan)

    own = Own(x, np.zeros(16, np.int32)).stream_batches(12)
    assert not isinstance(own, StreamingPipeline)
    assert len(list(own)) == 1


def test_loss_trajectory_is_the_same_on_both_paths(monkeypatch):
    ds = _arrays("one_array", n=96)
    _, serial = _optimize_arrays(ds, steps=8)
    monkeypatch.setattr(dataset_mod, "_PART_BYTES", 256)
    opt, ring = _optimize_arrays(ds, steps=8)
    assert opt.metrics.snapshot()["counters"]["data.ready_batches"] >= 8
    assert [l for l, _ in ring.values()] == [l for l, _ in serial.values()]


@pytest.mark.parametrize("path", ["ring", "serial"])
def test_produce_seconds_observed_once_per_batch(path):
    from bigdl_tpu.obs import trace

    ds = _arrays("one_array")
    m = Metrics()
    tracer = trace.enable()
    try:
        n = len(list(ds.stream_batches(
            16, metrics=m, parts_per_batch=3 if path == "ring" else 1)))
        spans = [s for s in tracer.spans() if s.name == "data/produce"]
    finally:
        trace.disable()
    assert n == 6
    assert m.snapshot()["hists"]["data.produce_s"]["n"] == n
    # (one thread's exhausted pull is a span too, and no batch)
    assert len(spans) == n + (path == "serial")
    assert all(s.end_ns >= s.start_ns for s in spans)
    ready = m.snapshot()["counters"].get("data.ready_batches", 0)
    assert ready == (n if path == "ring" else 0)


@pytest.mark.parametrize("state", ["idle", "drained", "abandoned"])
def test_close_costs_no_poll_interval(state):
    """Workers parked in their timed get() are woken, not waited out: an
    epoch's end must not stall the driver (they used to cost up to 100 ms
    a close)."""
    ds = _arrays("one_array")
    took = []
    for _ in range(3):  # a busy test host may delay one join, not three
        sp = ds.stream_batches(16, workers=3, parts_per_batch=3)
        it = iter(sp)
        if state == "drained":
            for _ in it:
                pass
        elif state == "abandoned":
            next(it)
        time.sleep(0.15)  # every worker is back in its get() by now
        t0 = time.perf_counter()
        sp.close()
        took.append(time.perf_counter() - t0)
        assert all(not t.is_alive() for t in sp._threads)
        if took[-1] < 0.02:
            break
    assert min(took) < 0.02, f"close() took {min(took) * 1e3:.1f} ms"


def test_array_slot_not_refilled_before_its_transfer_is_released(
        monkeypatch):
    """On an accelerator the dispatch stage owns a slot's release until
    the batch's transfer has landed: while batch k is being put, batch
    k-1's slot (transfer still in the window) must still hold batch k-1,
    however eager the pool is and however small the ring."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ds = _arrays("one_array")
    kw = dict(shuffle=True, seed=9, epoch=0)
    ref = [np.array(mb["input"]) for mb in ds.batches(8, **kw)]
    lent = []

    def put(mb):
        lent.append(mb["input"])  # a view over the slot
        time.sleep(0.005)         # the pool gets every chance to run on
        for k in (len(lent) - 2, len(lent) - 1):
            if k >= 0:
                np.testing.assert_array_equal(lent[k], ref[k])
        return np.array(mb["input"])

    stream = ds.stream_batches(8, workers=2, parts_per_batch=2,
                               ring_depth=2, raw_depth=1, **kw)
    out = list(dispatch_to_device(stream, put, size=2))
    assert len(out) == len(ref) == 12
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(a, b)
