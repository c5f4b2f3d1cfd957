"""``hc.fused_mix_share``: of the hyper-connection mixings the window's
steps ran (``hc.mixes``, one a sublayer and step), the share traced with the
one-pass backward (``hc.fused_mixes``).  The file resolves as the manifest
says, names counters the program books, and reads 100, a share, 0 and
nothing on hand-made registries: the change on the chip, a program that
took the plain path in some sublayers, one that took it in all (off the
TPU), and the parent, which has no such counter."""

import pytest

from benchmark import harness

CELL = "xing4.0-29b-a4b.train-tp8-packed4k"
NAME = "hc.fused_mix_share"


def _evidence(fused, mixes=(80, 1530)):
    """Two marks 40 s apart: 8 warm-up steps of ten mixings before the
    window, 145 more steps in it.  ``None``: no such counters."""
    snaps = []
    for f, n in zip(fused, mixes):
        counters = {} if f is None else {"hc.fused_mixes": f, "hc.mixes": n}
        snaps.append({"counters": counters, "hists": {}})
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
    assert m["layer"] == "residual path" and m["better"] == "higher"
    assert m["moves"] == "train_throughput" and m["workloads"] == [CELL]
    # the manifest's last entry: nothing that was there moved
    assert harness.resolve(CELL)["bench"]["per_layer"][-1]["name"] == NAME
    for cell in ("glm-4.7-flash.train-packed4k", "resnet50.train-hostfed",
                 "lfm2-24b-a2b.train-ep8-packed8k"):
        assert NAME not in {m["name"]
                            for m in harness.resolve(cell)["per_layer"]}


def test_metric_names_counters_the_program_books():
    import jax

    from bigdl_tpu.nn import hyper_connection as hc

    args = _metric()["args"]
    assert args["num"] == {"counter": hc.FUSED}
    assert args["den"] == {"counter": hc.MIXES}
    state = hc.HyperConnection(4, 128).build(jax.random.PRNGKey(0))
    assert set(state[1]["metrics"]["counters"]) == {hc.FUSED, hc.MIXES}


@pytest.mark.parametrize("fused, expected", [
    ((80, 1530), 100.0),     # every mixing of the window
    ((40, 765), 50.0),
    ((0, 0), 0.0),           # instrumented, and autodiff everywhere
    ((None, None), None),    # the parent: nothing to read, no error
])
def test_metric_reads_the_share_of_fused_mixings(fused, expected):
    m = _metric()
    read = harness.load_module("readers", m["reader"]).read
    got = read(m["args"], _evidence(fused))
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected, abs=1e-9)


def test_metric_reads_nothing_when_no_step_ran():
    m = _metric()
    read = harness.load_module("readers", m["reader"]).read
    assert read(m["args"], _evidence((80, 80), mixes=(80, 80))) is None
