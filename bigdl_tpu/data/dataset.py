"""DataSet / Sample / MiniBatch — the input pipeline.

Reference analog (unverified — mount empty): ``dllib/feature/dataset/
{DataSet,Sample,MiniBatch,SampleToMiniBatch}.scala``.  There, a
``DistributedDataSet`` is a cached Spark RDD[Sample] re-shuffled per epoch and
batched inside each task.  TPU-native: the dataset is a **per-host sharded
index space** over host arrays (the grain-style recipe) — each process sees
``indices[process_id::process_count]``, shuffled identically per epoch from a
shared seed (so the global permutation is consistent without communication),
then batched to the per-host batch and device_put onto the local devices by
the optimizer.
"""

import math
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np


class Sample:
    """One training example — reference ``Sample.scala`` (feature+label
    tensors)."""

    __slots__ = ("feature", "label")

    def __init__(self, feature, label=None):
        self.feature = np.asarray(feature)
        self.label = None if label is None else np.asarray(label)

    def __repr__(self):
        ls = None if self.label is None else self.label.shape
        return f"Sample(feature={self.feature.shape}, label={ls})"


class MiniBatch(dict):
    """Batch dict with 'input' / 'target' arrays — reference
    ``MiniBatch.scala`` as a plain pytree-able dict."""

    @property
    def input(self):
        return self["input"]

    @property
    def target(self):
        return self.get("target")

    def size(self) -> int:
        x = self["input"]
        return x[0].shape[0] if isinstance(x, (tuple, list)) else x.shape[0]


class DataSet:
    """Base dataset: sized, shardable, epoch-iterable."""

    def size(self) -> int:
        raise NotImplementedError

    def batches(self, batch_size: int, *, shuffle: bool = True, seed: int = 0,
                epoch: int = 0, drop_last: bool = True,
                process_id: int = 0, process_count: int = 1
                ) -> Iterator[MiniBatch]:
        raise NotImplementedError

    # -- factories mirroring the reference DataSet.array / DataSet.rdd -----
    @staticmethod
    def array(data, labels=None) -> "ArrayDataSet":
        return ArrayDataSet(data, labels)

    @staticmethod
    def from_samples(samples: Sequence[Sample]) -> "ArrayDataSet":
        feats = np.stack([s.feature for s in samples])
        labels = (np.stack([s.label for s in samples])
                  if samples and samples[0].label is not None else None)
        return ArrayDataSet(feats, labels)


def _per_host_batch(batch_size: int, process_count: int) -> int:
    """The global-batch contract, in one place: every host feeds
    ``batch_size / process_count`` rows per step."""
    process_count = max(process_count, 1)
    if batch_size % process_count != 0:
        raise ValueError(
            f"global batch {batch_size} not divisible by "
            f"{process_count} hosts")
    return batch_size // process_count


def _epoch_permutation(n: int, shuffle: bool, seed: int,
                       epoch: int) -> np.ndarray:
    """The shared global permutation of one (seed, epoch) — every host
    derives the same one, which is what makes both the normal stride plan
    and the elastic re-shard plan reconstructible without communication."""
    idx = np.arange(n)
    if shuffle:
        rng = np.random.RandomState((seed * 1_000_003 + epoch) % (2 ** 31))
        rng.shuffle(idx)
    return idx


def batch_index_plan(n: int, batch_size: int, *, shuffle=True, seed=0,
                     epoch=0, drop_last=True, process_id=0, process_count=1):
    """Yield ``(sel, n_real)`` index batches with the framework's sharding
    contract: same global permutation on every host (shared seed), each
    process takes its stride slice, step count computed from GLOBAL sizes
    (so every process dispatches the same number of collective-bearing
    steps), short tails cyclic-padded to the static batch size with
    ``n_real`` marking how many rows are genuine."""
    idx = _epoch_permutation(n, shuffle, seed, epoch)
    local = idx[process_id::process_count]
    per_host = _per_host_batch(batch_size, process_count)
    min_local = n // process_count
    max_local = min_local + (1 if n % process_count else 0)
    n_batches = (min_local // per_host if drop_last
                 else math.ceil(max_local / per_host))
    filler = local if len(local) else idx[:1]
    for b in range(n_batches):
        sel = local[b * per_host:(b + 1) * per_host]
        n_real = len(sel)
        if n_real < per_host:
            sel = np.concatenate([sel, np.resize(filler, per_host - n_real)])
        yield sel, n_real


def resharded_batch_index_plan(n: int, batch_size: int, *,
                               trained_batches: int,
                               old_process_count: int, shuffle=True,
                               seed=0, epoch=0, drop_last=True,
                               process_id=0, process_count=1):
    """The elastic mid-epoch resume plan (docs/distributed_training.md):
    after a ``process_count`` change, finish the epoch on its REMAINING
    examples instead of replaying it from the start.

    The old plan's coverage is a pure function of (seed, epoch,
    old_process_count): each old process trained the first
    ``trained_batches * per_host_old`` entries of its stride slice of the
    shared permutation.  Those permutation positions are excluded; the
    remainder keeps permutation order and re-strides over the NEW process
    set with the same global-batch contract (step count from global
    sizes, cyclic-padded tails).  Every remaining example is yielded
    exactly once across processes — shrink/grow loses nothing beyond the
    sub-global-batch tail that ``drop_last`` always drops."""
    idx = _epoch_permutation(n, shuffle, seed, epoch)
    old_per_host = _per_host_batch(batch_size, old_process_count)
    take = max(0, int(trained_batches)) * old_per_host
    done = np.zeros(n, bool)  # over PERMUTATION POSITIONS
    for p in range(old_process_count):
        done[np.arange(p, n, old_process_count)[:take]] = True
    remaining = idx[~done]
    local = remaining[process_id::process_count]
    per_host = _per_host_batch(batch_size, process_count)
    n_rem = len(remaining)
    min_local = n_rem // process_count
    max_local = min_local + (1 if n_rem % process_count else 0)
    n_batches = (min_local // per_host if drop_last
                 else math.ceil(max_local / per_host))
    filler = local if len(local) else idx[:1]
    for b in range(n_batches):
        sel = local[b * per_host:(b + 1) * per_host]
        n_real = len(sel)
        if n_real < per_host:
            sel = np.concatenate([sel, np.resize(filler, per_host - n_real)])
        yield sel, n_real


def gather_rows(src: np.ndarray, sel: np.ndarray,
                out: np.ndarray) -> np.ndarray:
    """Rows ``sel`` of ``src`` into the preallocated ``out`` (a ring slot,
    a staging buffer), with no temporary.  ``np.take(..., out=)`` at its
    default ``mode="raise"`` buffers the WHOLE output so that a bad index
    leaves ``out`` untouched (PERF.md §7: 258 ms against 15 for 154 MB), so
    the indices are checked here, once, and the copy runs unchecked."""
    sel = np.asarray(sel)
    if len(sel) and not 0 <= sel.min() <= sel.max() < len(src):
        raise IndexError(
            f"row indices span [{sel.min()}, {sel.max()}] but the source "
            f"has {len(src)} rows")
    return np.take(src, sel, axis=0, out=out, mode="clip")


# An in-memory batch is gathered in one part per this many bytes, each part
# by its own worker, and a batch that makes a single part keeps the serial
# path (ArrayDataSet._stream).  Measured on the v5e's host (13 cores, PR 27,
# PERF.md §7) for a 154 MB batch of 256 float32 images: one thread copies
# into a reused buffer at 10.4 GB/s (3 ms for 32 MB), 2 threads reach 18,
# 4 reach 27 and 8 to 13 the plateau of 30-33 GB/s; in the training loop
# every worker beyond 4 bought nothing (the batch is made in 6-8 ms of a
# 137 ms step either way) and cost the driver's thread 0.3-0.5 ms a step:
# 4 parts of 38 MB ran the step in 137.2 ms, 7 of 22 MB in 138.8, 11 of
# 14 MB in 140.6-141.0.
_PART_BYTES = 32 << 20


class ArrayDataSet(DataSet):
    """In-memory (host RAM) dataset over numpy arrays, with optional
    per-sample transform applied at batch time (the Transformer chain hook)."""

    def __init__(self, data, labels=None,
                 transform: Optional[Callable[[np.ndarray], np.ndarray]] = None):
        if isinstance(data, (tuple, list)) and labels is None and len(data) == 2:
            data, labels = data
        # multi-input models: data is a TUPLE of per-input arrays (labels
        # must be given, else the 2-tuple means (x, y) above).  Plain lists
        # keep their historical meaning of list-of-samples -> one array.
        self.multi = isinstance(data, tuple)
        if self.multi:
            self.data = tuple(np.asarray(a) for a in data)
            n = len(self.data[0])
            if any(len(a) != n for a in self.data):
                raise ValueError("multi-input arrays differ in length: "
                                 + str([len(a) for a in self.data]))
            if transform is not None:
                raise ValueError("transform not supported for multi-input data")
        else:
            self.data = np.asarray(data)
        self.labels = None if labels is None else np.asarray(labels)
        if self.labels is not None and len(self.labels) != self.size():
            raise ValueError(
                f"data/labels length mismatch: {self.size()} vs {len(self.labels)}")
        self.transform = transform
        self._slot_cache: Dict = {}  # ring buffers reused across epochs
        self._row_spec: Optional[tuple] = None  # transform's output, probed

    def size(self) -> int:
        return len(self.data[0]) if self.multi else len(self.data)

    def transformed(self, fn) -> "ArrayDataSet":
        prev = self.transform
        chain = fn if prev is None else (lambda x: fn(prev(x)))
        return ArrayDataSet(self.data, self.labels, chain)

    def _emit(self, plan):
        """Assemble MiniBatches from an index plan of ``(sel, n_real)``
        pairs — shared by the normal and resharded epoch paths."""
        for sel, n_real in plan:
            x = (tuple(a[sel] for a in self.data) if self.multi
                 else self.data[sel])
            if self.transform is not None:
                x = np.stack([self.transform(s) for s in x])
            mb = MiniBatch(input=x)
            if self.labels is not None:
                mb["target"] = self.labels[sel]
            if len(sel) != n_real:
                # padded rows carry weight 0 so metrics stay exact
                w = np.zeros(len(sel), np.float32)
                w[:n_real] = 1.0
                mb["weight"] = w
            yield mb

    def batches(self, batch_size, *, shuffle=True, seed=0, epoch=0,
                drop_last=True, process_id=0, process_count=1):
        return self._emit(batch_index_plan(
            self.size(), batch_size, shuffle=shuffle, seed=seed,
            epoch=epoch, drop_last=drop_last, process_id=process_id,
            process_count=process_count))

    def resharded_batches(self, batch_size, *, trained_batches,
                          old_process_count, shuffle=True, seed=0, epoch=0,
                          drop_last=True, process_id=0, process_count=1):
        """Finish an epoch interrupted under a DIFFERENT process count:
        batches over the epoch's remaining examples, re-strided over the
        new process set (:func:`resharded_batch_index_plan`).  The driver
        uses this for elastic mid-epoch resume; datasets without the
        method fall back to replay-from-epoch-start."""
        return self._emit(resharded_batch_index_plan(
            self.size(), batch_size, trained_batches=trained_batches,
            old_process_count=old_process_count, shuffle=shuffle,
            seed=seed, epoch=epoch, drop_last=drop_last,
            process_id=process_id, process_count=process_count))

    def stream_batches(self, batch_size, *, shuffle=True, seed=0, epoch=0,
                       drop_last=True, process_id=0, process_count=1,
                       workers=None, parts_per_batch=None,
                       raw_depth=None, ring_depth=None, metrics=None):
        """:meth:`batches` behind a host-side lookahead (docs/data.md
        §In-memory arrays), byte-identical to it for every argument.  How
        follows from the batch's bytes (:data:`_PART_BYTES`): a batch
        worth several parts is gathered by a worker pool straight into
        reused slots of a :class:`~bigdl_tpu.data.pipeline.BufferRing`
        and comes out as a ``RingBatch``; a batch that makes one part — a
        few KB of features, nearly every caller — comes from one
        ``thread_prefetch`` thread running :meth:`batches`, the path such
        a dataset always took.  ``workers`` overrides the pool's width,
        ``parts_per_batch`` the bytes rule.  Ring slots are cached on the
        dataset: at most one stream of a dataset may be live at a time."""
        kw = dict(shuffle=shuffle, seed=seed, epoch=epoch,
                  drop_last=drop_last, process_id=process_id,
                  process_count=process_count)
        return self._stream("batches", batch_index_plan, batch_size, kw,
                            workers, parts_per_batch, raw_depth, ring_depth,
                            metrics)

    def resharded_stream_batches(self, batch_size, *, trained_batches,
                                 old_process_count, shuffle=True, seed=0,
                                 epoch=0, drop_last=True, process_id=0,
                                 process_count=1, workers=None,
                                 parts_per_batch=None, raw_depth=None,
                                 ring_depth=None, metrics=None):
        """:meth:`resharded_batches` the way :meth:`stream_batches` serves
        :meth:`batches`: an elastic mid-epoch resume keeps its feed."""
        kw = dict(trained_batches=trained_batches,
                  old_process_count=old_process_count, shuffle=shuffle,
                  seed=seed, epoch=epoch, drop_last=drop_last,
                  process_id=process_id, process_count=process_count)
        return self._stream(
            "resharded_batches", resharded_batch_index_plan, batch_size, kw,
            workers, parts_per_batch, raw_depth, ring_depth, metrics)

    def _ring_spec(self, rows: int) -> Dict[str, tuple]:
        """Full-batch shape and dtype of every buffer of a ring slot.  A
        transform's output row is probed once, from a copy of row 0."""
        if self.multi:
            spec = {f"input:{i}": ((rows,) + a.shape[1:], a.dtype)
                    for i, a in enumerate(self.data)}
        elif self.transform is None:
            spec = {"input": ((rows,) + self.data.shape[1:],
                              self.data.dtype)}
        else:
            if self._row_spec is None:
                row = np.asarray(self.transform(self.data[[0]][0]))
                self._row_spec = (row.shape, row.dtype)
            shape, dtype = self._row_spec
            spec = {"input": ((rows,) + shape, dtype)}
        if self.labels is not None:
            spec["target"] = ((rows,) + self.labels.shape[1:],
                              self.labels.dtype)
        spec["weight"] = ((rows,), np.float32)
        return spec

    def _stream(self, serial, planner, batch_size, kw, workers, parts,
                raw_depth, ring_depth, metrics):
        """``serial`` names the method whose batches are wanted, ``planner``
        is the index plan behind it, ``kw`` the arguments of both."""
        from bigdl_tpu.data.pipeline import (
            StreamingPipeline, autotune_depths, autotune_workers,
            cached_slots, fill_pad_weights, timed_batches,
        )
        from bigdl_tpu.data.prefetch import thread_prefetch

        rows = _per_host_batch(batch_size, kw["process_count"])
        # a subclass that assembles batches its own way is not second-
        # guessed: only this class's own _emit is what the ring reproduces
        own = all(getattr(type(self), m) is getattr(ArrayDataSet, m)
                  for m in ("_emit", serial))
        inputs = ([(f"input:{i}", a) for i, a in enumerate(self.data)]
                  if self.multi else [("input", self.data)])
        sources = inputs + ([("target", self.labels)]
                            if self.labels is not None else [])
        if not own or not self.size():
            parts = 1
        elif parts is None:
            # the bytes a batch copies out of the source
            nbytes = rows * sum(a.nbytes // len(a) for _, a in sources)
            parts = max(1, min(nbytes // _PART_BYTES, autotune_workers(),
                               rows))
        if parts == 1:
            batches = getattr(self, serial)(batch_size, **kw)
            if metrics is not None:
                batches = timed_batches(batches, "produce", metrics)
            return thread_prefetch(batches)
        if workers is None:
            workers = min(parts, autotune_workers())
        if raw_depth is None or ring_depth is None:
            tuned = autotune_depths(0, 0, workers, parts_per_batch=parts)
            raw_depth = raw_depth or tuned["raw_depth"]
            ring_depth = ring_depth or tuned["ring_depth"]
        spec = self._ring_spec(rows)

        def decode(item, raw, buffers, lo, hi, slot):
            sel, n_real = item
            part = sel[lo:hi]
            for name, src in sources:
                if name == "input" and self.transform is not None:
                    # _emit's rows are copies: a transform may write to its
                    # argument, never to the source
                    for i, row in enumerate(src[part], lo):
                        buffers[name][i] = self.transform(row)
                else:
                    gather_rows(src, part, buffers[name][lo:hi])
            fill_pad_weights(buffers["weight"], n_real, lo, hi)
            return {"n_real": n_real}

        def finalize(buffers, meta):
            fields = {"input": (tuple(buffers[k] for k, _ in inputs)
                                if self.multi else buffers["input"])}
            if self.labels is not None:
                fields["target"] = buffers["target"]
            if meta["n_real"] < rows:
                fields["weight"] = buffers["weight"]
            return fields

        return StreamingPipeline(
            planner(self.size(), batch_size, **kw),
            lambda item, slot: None,  # the source is already in memory
            decode, spec, rows=rows, workers=workers, parts_per_batch=parts,
            raw_depth=raw_depth, ring_depth=ring_depth,
            slots=cached_slots(self._slot_cache, spec, ring_depth),
            finalize=finalize, metrics=metrics)

    def steps_per_epoch(self, batch_size: int, process_count: int = 1,
                        drop_last: bool = True) -> int:
        per_host = _per_host_batch(batch_size, process_count)
        n = self.size()
        min_local = n // process_count
        max_local = min_local + (1 if n % process_count else 0)
        return (min_local // per_host if drop_last
                else math.ceil(max_local / per_host))


class SampleToMiniBatch:
    """Kept for reference-API parity: batches an iterator of Samples."""

    def __init__(self, batch_size: int):
        self.batch_size = batch_size

    def __call__(self, samples: Iterator[Sample]) -> Iterator[MiniBatch]:
        buf: List[Sample] = []
        for s in samples:
            buf.append(s)
            if len(buf) == self.batch_size:
                yield self._make(buf)
                buf = []
        if buf:
            yield self._make(buf)

    @staticmethod
    def _make(buf: List[Sample]) -> MiniBatch:
        mb = MiniBatch(input=np.stack([s.feature for s in buf]))
        if buf[0].label is not None:
            mb["target"] = np.stack([s.label for s in buf])
        return mb


class ProcessLocalDataSet(DataSet):
    """Wrap a dataset of rows that are ALREADY this process's disjoint
    share (XShards ``owned_concat`` — the Spark-executor posture), so the
    driver's ``process_id``/``process_count`` sharding must NOT slice it
    again.

    Every process must dispatch the SAME number of collective-bearing
    steps per epoch, so the per-epoch batch count is agreed once from the
    allgathered local sizes (min over processes, cyclic-padded tails keep
    short processes in step)."""

    def __init__(self, local: DataSet):
        self.local = local
        self._global_min: Optional[int] = None

    def size(self) -> int:
        # local rows; the GLOBAL dataset is the union over processes
        return self.local.size()

    def _agreed_size(self) -> int:
        if self._global_min is None:
            import jax

            if jax.process_count() == 1:
                self._global_min = self.local.size()
            else:
                from bigdl_tpu.friesian.sharded import _allgather_objects

                self._global_min = min(_allgather_objects(
                    self.local.size()))
        return self._global_min

    def batches(self, batch_size, *, shuffle=True, seed=0, epoch=0,
                drop_last=True, process_id=0, process_count=1):
        per_host = _per_host_batch(batch_size, process_count)
        agreed = self._agreed_size()
        n_batches = (agreed // per_host if drop_last
                     else math.ceil(agreed / per_host))
        it = self.local.batches(per_host, shuffle=shuffle, seed=seed,
                                epoch=epoch, drop_last=False,
                                process_id=0, process_count=1)
        for b, mb in enumerate(it):
            if b >= n_batches:
                break
            yield mb
