"""The per-layer metrics that read the train driver's phases and the
split of the data wait: each file resolves through the manifest and reads
a hand-made registry to the number it should, and a registry of a program
that has no such histogram (the parent of the PR that added them) reads as
nothing, not as an error."""

import pytest

from benchmark import harness

CELL = "resnet50.train-hostfed"
# metric -> (histogram it reads, expected value on the registry below)
SHARES = {
    "train.sync_wait_share": ("train.attr.sync_s", 60.0),
    "train.dispatch_share": ("train.attr.dispatch_s", 2.5),
    "train.overhead_share": ("train.attr.overhead_s", 1.5),
    "train.unattributed_share": ("train.attr.other_s", 0.5),
    "data.batch_wait_share": ("data.batch_wait_s", 20.0),
    "data.put_share": ("data.put_s", 7.5),
    "data.epoch_boundary_share": ("data.epoch_first_wait_s", 5.0),
}
EXPECTED = dict({k: v[1] for k, v in SHARES.items()},
                **{"data.produce_ms": 125.0})
WINDOW_S = 40.0


def _evidence():
    """Two marks 40 s apart; every histogram had 10 observations summing
    to 3 s before the window and gains 200 in it."""
    start = {"counters": {}, "hists": {}}
    end = {"counters": {}, "hists": {}}
    for hist, share in SHARES.values():
        start["hists"][hist] = {"sum": 3.0, "n": 10}
        end["hists"][hist] = {"sum": 3.0 + WINDOW_S * share / 100.0,
                              "n": 210}
    start["hists"]["data.produce_s"] = {"sum": 3.0, "n": 10}
    end["hists"]["data.produce_s"] = {"sum": 3.0 + 200 * 0.125, "n": 210}
    return {"registry": {"window_start": start, "window_end": end},
            "marks": {"process_start": 0.0, "window_start": 100.0,
                      "window_end": 100.0 + WINDOW_S}}


def _metric(name):
    (m,) = [m for m in harness.resolve(CELL)["per_layer"]
            if m["name"] == name]
    return m


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_resolves_and_reads_the_expected_number(name):
    m = _metric(name)
    assert m["reader"] == "registry_delta"
    assert m["moves"] == "train_throughput" and m["workloads"] == [CELL]
    assert m["source"] == "program_span"
    assert m["layer"] == ("input pipeline" if name.startswith("data.")
                          else "train driver")
    read = harness.load_module("readers", m["reader"]).read
    assert read(m["args"], _evidence()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reads_nothing_from_a_program_without_the_histogram(name):
    m = _metric(name)
    ev = _evidence()
    for snap in ev["registry"].values():
        snap["hists"] = {"train.data_wait_s": {"sum": 1.0, "n": 1}}
    read = harness.load_module("readers", m["reader"]).read
    assert read(m["args"], ev) is None


def test_phase_shares_close_on_the_window():
    """The five driver-thread shares are disjoint parts of the window."""
    ev = _evidence()
    ev["registry"]["window_start"]["hists"]["train.data_wait_s"] = \
        {"sum": 0.0, "n": 0}
    ev["registry"]["window_end"]["hists"]["train.data_wait_s"] = \
        {"sum": 0.355 * WINDOW_S, "n": 200}
    names = ["train.data_wait_share", "train.sync_wait_share",
             "train.dispatch_share", "train.overhead_share",
             "train.unattributed_share"]
    total = 0.0
    for n in names:
        m = _metric(n)
        total += harness.load_module("readers", m["reader"]).read(
            m["args"], ev)
    assert total == pytest.approx(100.0)
