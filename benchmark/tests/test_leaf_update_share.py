"""``train.leaf_update_share``: of the train steps the driver dispatched in
the window (``train.updates``), the share that went to a step program
carrying its state leaf-shaped (``train.leaf_updates``): the one-shard
layout of ``optim/train_step.py``.  The file resolves as the manifest
says, names counters the driver books, and reads 100, 0, a share and
nothing on hand-made registries: the change on one chip, the flat cycle
on several, a run that changed engines, and the parent, which has no such
counter.  The manifest entry is found by its NAME, wherever later PRs
leave it in the list."""

import pytest

from benchmark import harness

CELLS = ["resnet50.train-hostfed", "glm-4.7-flash.train-packed4k",
         "xing4.0-29b-a4b.train-tp8-packed4k",
         "lfm2-24b-a2b.train-ep8-packed8k"]
NAME = "train.leaf_update_share"


def _evidence(leaf, updates=(8, 204)):
    """Two marks 40 s apart: 8 warm-up steps before the window, 196 more
    in it.  ``None``: no such counters."""
    snaps = []
    for f, n in zip(leaf, updates):
        counters = ({} if f is None
                    else {"train.leaf_updates": f, "train.updates": n})
        snaps.append({"counters": counters, "hists": {}})
    return {"registry": {"window_start": snaps[0], "window_end": snaps[1]},
            "marks": {"process_start": 0.0, "window_start": 100.0,
                      "window_end": 140.0}}


def _metric(cell):
    (m,) = [m for m in harness.resolve(cell)["per_layer"]
            if m["name"] == NAME]
    return m


@pytest.mark.parametrize("cell", CELLS)
def test_metric_resolves_as_the_manifest_says(cell):
    m = _metric(cell)
    assert m["reader"] == "registry_delta"
    assert m["source"] == "program_counter" and m["unit"] == "%"
    assert m["layer"] == "train step" and m["better"] == "higher"
    assert m["moves"] == "train_throughput" and m["workloads"] == CELLS
    (entry,) = [e for e in harness.resolve(cell)["bench"]["per_layer"]
                if e["name"] == NAME]
    assert entry["workloads"] == CELLS


def test_metric_names_counters_the_driver_books():
    import inspect

    from bigdl_tpu.optim import optimizer

    args = _metric(CELLS[0])["args"]
    assert args["over"] == ["window_start", "window_end"]
    assert args["num"] == {"counter": "train.leaf_updates"}
    assert args["den"] == {"counter": "train.updates"}
    booked = inspect.getsource(optimizer.Optimizer._one_bundle)
    for spec in (args["num"], args["den"]):
        assert f'"{spec["counter"]}"' in booked


@pytest.mark.parametrize("leaf, expected", [
    ((8, 204), 100.0),       # both counters advanced together
    ((0, 0), 0.0),           # several shards: the flat cycle
    ((8, 106), 50.0),        # half of the window's steps
    ((None, None), None),    # the parent: nothing to read, no error
])
def test_metric_reads_the_share_of_leaf_updates(leaf, expected):
    m = _metric(CELLS[0])
    read = harness.load_module("readers", m["reader"]).read
    got = read(m["args"], _evidence(leaf))
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected, abs=1e-9)


def test_metric_reads_nothing_when_no_step_was_dispatched():
    m = _metric(CELLS[0])
    read = harness.load_module("readers", m["reader"]).read
    assert read(m["args"], _evidence((8, 8), updates=(8, 8))) is None
