"""``data.ring_batch_share``: of the batches the driver dispatched in the
window (observations of ``data.put_s``), the share a ``StreamingPipeline``
made in a buffer-ring slot (``data.ready_batches``).  Rehearsed on
hand-made registries: a program whose every batch rides the ring, one
whose batches are too small to, and one that has no such counter (the
parent of the PR that put in-memory arrays on the ring)."""

import pytest

from benchmark import harness

CELL = "resnet50.train-hostfed"
NAME = "data.ring_batch_share"


def _evidence(ready_start, ready_end, puts=(36, 276)):
    """Two marks 40 s apart; ``data.put_s`` counted 36 warm-up batches
    before the window and 240 more in it.  ``None``: no such counter."""
    snaps = []
    for ready, n in zip((ready_start, ready_end), puts):
        counters = {} if ready is None else {"data.ready_batches": ready}
        snaps.append({"counters": counters,
                      "hists": {"data.put_s": {"sum": 0.012 * n, "n": n}}})
    return {"registry": {"window_start": snaps[0], "window_end": snaps[1]},
            "marks": {"process_start": 0.0, "window_start": 100.0,
                      "window_end": 140.0}}


def _metric():
    (m,) = [m for m in harness.resolve(CELL)["per_layer"]
            if m["name"] == NAME]
    return m


def test_metric_resolves_as_the_manifest_says():
    m = _metric()
    assert m["reader"] == "registry_delta"
    assert m["source"] == "program_counter" and m["unit"] == "%"
    assert m["layer"] == "input pipeline" and m["better"] == "higher"
    assert m["moves"] == "train_throughput" and m["workloads"] == [CELL]


@pytest.mark.parametrize("ready, expected", [
    ((36, 276), 100.0),      # the counter follows data.put_s's count
    ((38, 277), 99.5833),    # a batch ahead at one edge, two at the other
    ((0, 0), 0.0),           # a pipeline ran once, not for these batches
    ((36, 156), 50.0),
    ((None, None), None),    # the parent: nothing to read, no error
])
def test_metric_reads_the_share_of_ring_batches(ready, expected):
    m = _metric()
    read = harness.load_module("readers", m["reader"]).read
    got = read(m["args"], _evidence(*ready))
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected, abs=1e-3)


def test_metric_reads_nothing_when_no_batch_was_dispatched():
    m = _metric()
    read = harness.load_module("readers", m["reader"]).read
    assert read(m["args"], _evidence(36, 36, puts=(36, 36))) is None
