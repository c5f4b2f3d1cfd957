"""``train.stall_share``, ``train.stall_host_share``, ``host.gc_pause_share``
and ``train.step_time_ms`` (ISSUE 36): the train driver's own record of its
stalls, read through ``registry_delta`` as it is.  The four files resolve
for every cell as the manifest says, name what the program books, read the
expected value from a hand-made registry at two marks, and read nothing
from a registry without the counters (the parent's)."""

import pytest

from benchmark import harness

NAMES = ("train.stall_share", "train.stall_host_share",
         "host.gc_pause_share", "train.step_time_ms")
CELLS = ("resnet50.train-hostfed", "glm-4.7-flash.train-packed4k",
         "xing4.0-29b-a4b.train-tp8-packed4k",
         "lfm2-24b-a2b.train-ep8-packed8k")


def _evidence(instrumented=True):
    """Two marks 40 s apart, 8 warm-up steps before the window and 195 in
    it; one stall of 2.4 s in the window, 1.6 s of it with the device ahead
    (and an older one before the window); 60 ms of collections."""
    start = {"counters": {"train.stalls": 1.0, "train.stall_s": 0.9,
                          "train.stall_host_s": 0.9,
                          "train.stall_device_s": 0.0},
             "hists": {"host.gc_pause_s": {"sum": 0.50, "n": 400},
                       "train.step_time_s": {"sum": 1.43, "n": 7},
                       "train.attr.sync_s": {"sum": 1.3, "n": 8}}}
    end = {"counters": {"train.stalls": 2.0, "train.stall_s": 3.3,
                        "train.stall_host_s": 2.5,
                        "train.stall_device_s": 0.0},
           "hists": {"host.gc_pause_s": {"sum": 0.56, "n": 460},
                     "train.step_time_s": {"sum": 41.43, "n": 202},
                     "train.attr.sync_s": {"sum": 39.9, "n": 203}}}
    if not instrumented:
        for snap in (start, end):
            snap["counters"] = {}
            del snap["hists"]["host.gc_pause_s"]
            del snap["hists"]["train.step_time_s"]
    return {"registry": {"window_start": start, "window_end": end},
            "marks": {"process_start": 0.0, "window_start": 100.0,
                      "window_end": 140.0}}


def _metric(cell, name):
    (m,) = [m for m in harness.resolve(cell)["per_layer"]
            if m["name"] == name]
    return m


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("name", NAMES)
def test_metric_resolves_for_every_cell(cell, name):
    m = _metric(cell, name)
    assert m["reader"] == "registry_delta"
    assert m["source"] == "program_span" and m["better"] == "lower"
    assert m["unit"] == ("ms" if name == "train.step_time_ms" else "%")
    assert m["layer"] == "train driver"
    assert m["moves"] == "train_throughput"
    assert m["workloads"] == list(CELLS)
    assert m["args"]["over"] == ["window_start", "window_end"]


def test_the_four_were_appended_together():
    # after everything PR 35 left, in the issue's order; what a later PR
    # appends comes after them and is none of this test's business
    names = [m["name"] for m in harness.resolve(CELLS[0])["bench"]["per_layer"]]
    at = names.index(NAMES[0])
    assert names[at:at + 4] == list(NAMES)
    assert at > names.index("train.unattributed_share")


def test_metrics_name_what_the_program_books():
    from bigdl_tpu.obs import attr, host
    from bigdl_tpu.optim.metrics import Metrics

    m = Metrics()
    attr.StallWatch(m)
    host.HostProbes(m).start().stop()
    booked = set(m.snapshot()["counters"]) | set(m.snapshot()["hists"])
    booked.add("train.step_time_s")   # observed at the second fetch
    for name in NAMES:
        args = _metric(CELLS[0], name)["args"]
        for spec in (args["num"], args["den"]):
            if not spec.get("seconds"):
                assert next(iter(spec.values())) in booked, (name, spec)


@pytest.mark.parametrize("name, expected", [
    ("train.stall_share", (3.3 - 0.9) / 40.0 * 100.0),
    ("train.stall_host_share", (2.5 - 0.9) / 40.0 * 100.0),
    ("host.gc_pause_share", 0.06 / 40.0 * 100.0),
    ("train.step_time_ms", 40.0 / 195 * 1000.0),
])
def test_metric_reads_the_window_from_a_hand_made_registry(name, expected):
    m = _metric(CELLS[3], name)
    read = harness.load_module("readers", m["reader"]).read
    assert read(m["args"], _evidence()) == pytest.approx(expected, rel=1e-9)
    # the parent: no such counters, nothing to read, no error
    assert read(m["args"], _evidence(instrumented=False)) is None


def test_a_window_without_a_stall_reads_zero_not_nothing():
    ev = _evidence()
    for mark in ev["registry"].values():
        for k in mark["counters"]:
            mark["counters"][k] = 0.0
    for name in NAMES[:2]:
        m = _metric(CELLS[0], name)
        read = harness.load_module("readers", m["reader"]).read
        assert read(m["args"], ev) == 0.0
