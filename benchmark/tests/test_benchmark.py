import json
import os
import time

import pytest

from benchmark import flops, harness, run as bench_run, trace_reduce
from benchmark.clients import closed_loop

HERE = harness.HERE
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


# -- the manifest resolves every cell's files by name ---------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    r = harness.resolve(cell)
    assert os.path.isfile(os.path.join(
        HERE, "drivers", r["traffic"]["driver"] + ".py"))
    family = harness.load_module("families", r["config"]["family"])
    for fn in ("build_model", "train_flops_per_sample"):
        assert hasattr(family, fn)
    e2e = {m["name"] for m in r["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert r["per_layer"], "every cell reports a per-layer metric"
    for m in r["per_layer"]:
        assert hasattr(harness.load_module("readers", m["reader"]), "read")
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_manifest_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in l and len(l) <= 200 for l in layers)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in BENCH["configs"]:
        on_disk = harness.load_json(harness.ROOT, c["file"])
        assert on_disk["reduced"] == c["reduced"]
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


# -- operation counts --------------------------------------------------------------

def test_resnet50_flops_per_sample():
    train = flops.TRAIN_OVER_FORWARD * flops.resnet50_forward_flops()
    assert train == pytest.approx(24.5e9, rel=0.02)


def test_transformer_flops_match_6n_rule():
    # GPT-2 small, seq 1024: ~6 x (85 M block + 38.6 M tied head) x tokens,
    # plus the attention products
    f = flops.TRAIN_OVER_FORWARD * flops.transformer_lm_forward_flops(
        1024, 12, 768, 3072, 50257)
    n = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 50257
    attention = 3 * 12 * 2.0 * 1024 * 1024 * 768
    assert f == pytest.approx(6.0 * n * 1024 + attention, rel=1e-9)


# -- trace reduction ------------------------------------------------------------------

def test_union_and_gaps_on_known_intervals():
    # two overlapping ops, a gap of 5, one op, a gap of 2, one op
    iv = [(0, 10, "a"), (5, 12, "b"), (17, 20, "c"), (22, 23, "d")]
    busy, gaps = trace_reduce.union_and_gaps(iv)
    assert busy == 12 + 3 + 1
    assert gaps == [(5, "c"), (2, "d")]


def test_short_name_adds_numbered_ops_up():
    assert trace_reduce.short_name("%fusion.123 = bf16[8]") == "fusion"
    assert trace_reduce.short_name("copy-done.4") == "copy-done"
    assert trace_reduce.short_name("custom-call") == "custom-call"


def test_reduce_planes_known_numbers():
    s = 1e9  # one second in ns
    ops = [(0.0, 0.4 * s, "fusion.1"), (0.5 * s, 0.9 * s, "fusion.2"),
           (0.9 * s, 1.0 * s, "copy.3")]
    out = trace_reduce.reduce_planes([
        ("/device:TPU:0", {"XLA Ops": ops,
                           "XLA Modules": [(0.0, 1.0 * s, "jit_step(1)")]}),
        ("/host:CPU", {})])
    assert out["chips"] == 1
    assert out["busy_s"] == pytest.approx(0.9)
    assert out["window_s"] == pytest.approx(1.0)
    assert out["device_ops"][0] == ["fusion", pytest.approx(0.8)]
    assert out["idle_gaps"][0] == ["before fusion.2", pytest.approx(0.1)]
    assert trace_reduce.reduce_planes([("/host:CPU", {})]) is None


RECORDED = os.path.join(HERE, "tests", "data", "resnet50_steps.xplane.pb")


def test_reduce_recorded_trace():
    want = harness.load_json(HERE, "tests", "data",
                             "resnet50_steps.expected.json")
    got = trace_reduce.reduce_file(RECORDED)
    assert got["chips"] == want["chips"] and got["n_ops"] == want["n_ops"]
    for key in ("busy_s", "window_s"):  # ``want``: an independent sweep
        assert got[key] == pytest.approx(want[key], rel=1e-4)
    assert [n for n, _ in got["device_ops"]] == \
        [n for n, _ in want["device_ops"]]
    assert 0.0 < got["busy_s"] < got["window_s"]


# -- the closed-loop client's arithmetic ----------------------------------------------

def test_percentile_is_numpys_default():
    import numpy as np

    v = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0, 50, 90, 95, 100):
        assert closed_loop.percentile(v, q) == pytest.approx(
            float(np.percentile(v, q)))
    assert closed_loop.percentile([], 50) is None


def test_summarize_window_arithmetic():
    eos = 99
    rec = lambda sent, stamps, asked, toks, err=None: {
        "sent": sent, "stamps": stamps, "asked": asked, "tokens": toks,
        "error": err, "done": stamps[-1] if stamps else None,
        "prompt_len": 4}
    per_client = [[
        # sent before the window: its tokens inside count, its TTFT does not
        rec(8.0, [9.5, 10.5, 11.5], 3, [1, 2, 3]),
        # sent inside: TTFT 1.0; gaps 0.5 and 0.0005 (coalesced)
        rec(12.0, [13.0, 13.5, 13.5005], 3, [1, 2, 3]),
    ], [
        # sent inside, first token after the window (drained): TTFT 2.5
        rec(19.0, [21.5, 22.0], 2, [4, 5]),
        # sent inside, ended early on eos: fine
        rec(15.0, [16.0], 5, [eos]),
        # sent inside, error: failed
        rec(17.0, [], 4, None, err="HTTP 500"),
    ]]
    s = closed_loop.summarize(per_client, 10.0, 20.0, eos)
    assert s["attempted"] == 4 and s["failed"] == 1 and s["lengths_ok"]
    assert s["tokens_in_window"] == 2 + 3 + 1
    assert s["serve_tokens_per_s"] == pytest.approx(0.6)
    assert s["n_ttft"] == 3
    assert s["ttft_p50_ms"] == pytest.approx(1000.0)
    assert s["ttft_p90_ms"] == pytest.approx(1000 * (1.0 + 0.8 * 1.5))
    assert s["n_gaps"] == 4            # 1.0, 1.0, 0.5, 0.0005
    assert s["coalesced_share"] == pytest.approx(25.0)
    # wrong length without eos is a failure of the lengths rule
    bad = [[rec(12.0, [13.0], 3, [1])]]
    assert not closed_loop.summarize(bad, 10.0, 20.0, eos)["lengths_ok"]


# -- a tiny rehearsal of each driver, through the command's own line builder -------

SERVE_CELL = "gpt2-small.serve-closed"   # built and rehearsed; PERF.md §7 row 1
SERVE_E2E = ("serve_tokens_per_s", "ttft_p90_ms", "itl_p95_ms", "setup_s")
TINY = {
    "resnet50.train-hostfed": (
        dict(family="resnet", classes=10, image_size=32, stem="s2d",
             train_examples=64),
        dict(driver="train", batch_per_chip=8, warmup_steps=3,
             optimizer={"name": "SGD", "learning_rate": 0.02,
                        "momentum": 0.9}, loss_tolerance=0.05)),
    SERVE_CELL: (
        dict(family="transformer_lm", n_layer=2, n_embd=32, n_head=2,
             n_inner=64, n_positions=64, vocab_size=64, eos_id=63,
             resid_pdrop=0.0,
             serving=dict(slots=4, page_size=4, pages_per_slot=16,
                          prompt_chunk=4, prefill_batch=2,
                          kv_dtype="float32", prefix_cache_pages=0)),
        dict(driver="serve_closed", clients=4,
             prompt_len=dict(dist="loguniform", lo=3, hi=40),
             output_len=dict(dist="uniform", lo=2, hi=8), lengths_seed=1,
             requests_per_client=4, ramp_s=1.0, drain_s=10.0,
             logp_tolerance_nats_per_token=0.01)),
}


@pytest.fixture(scope="module")
def rehearsed():
    """Each cell's driver once, tiny, on the CPU, with the profiler on."""
    import jax

    cache = {}

    def get(cell):
        if cell not in cache:
            if cell in CELLS:
                resolved = harness.resolve(cell)
            else:  # no manifest entry yet: its metric files are there
                names = sorted(f[:-5] for f in os.listdir(os.path.join(
                    HERE, "layer_metrics")) if f.startswith("serve."))
                resolved = {
                    "end_to_end": [{"name": n, "unit": "x"}
                                   for n in SERVE_E2E],
                    "per_layer": [dict(harness.load_json(
                        HERE, "layer_metrics", n + ".json"), name=n,
                        unit="x") for n in names + ["runtime.compile_s"]]}
            resolved["config"], resolved["traffic"] = TINY[cell]
            d = jax.devices()[0]
            device = {"platform": d.platform, "kind": d.device_kind,
                      "count": 1}
            run = harness.Run(cell, *TINY[cell], seed=2 ** 31 + 11,
                              seconds=1.5, trace=1,
                              t_process_start=time.monotonic(),
                              device=device, trace_seconds=0.5)
            run.install_listeners()
            result = harness.load_module(
                "drivers", run.traffic["driver"]).run(run)
            cache[cell] = resolved, run, result, run.finish_trace()
        return cache[cell]

    return get


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", list(TINY))
def test_tiny_rehearsal_prints_the_contracts_line(cell, trace, rehearsed,
                                                  capsys):

    resolved, run, result, summary = rehearsed(cell)
    assert run.device["platform"] == "cpu" and summary is None
    line = json.loads(json.dumps(bench_run.assemble(
        resolved, run, result, summary, bool(trace))))
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    names = {m["name"] for m in
             (resolved["per_layer"] if trace else resolved["end_to_end"])}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
    else:  # what needs the TPU's trace or peaks is left out, not invented
        assert line["metrics"] and not any(
            "idle_share" in k or "mfu" in k for k in line["metrics"])
    for v in line["metrics"].values():
        assert isinstance(v["value"], float) and v["unit"]
    out = capsys.readouterr().out
    assert all("platform=cpu" in l and "device_kind=" in l and "devices=1"
               in l for l in out.splitlines() if l.startswith("[bench]"))


def test_train_driver_takes_the_lm_family_as_data():
    """Open question 1 (the ZeRO-1 LM cell) must be addable as a traffic
    file and manifest entries: the train driver, the LM family's data and
    its reference loss work together, tiny, here."""
    import jax

    cfg = dict(TINY[SERVE_CELL][0])
    traffic = dict(driver="train", batch_per_chip=4, seq_len=16, examples=16,
                   warmup_steps=2, loss_tolerance=0.05,
                   optimizer={"name": "Adam", "learning_rate": 3e-4})
    d = jax.devices()[0]
    run = harness.Run("tiny.lm-train", cfg, traffic, seed=7, seconds=0.5,
                      trace=0, t_process_start=time.monotonic(),
                      device={"platform": d.platform, "kind": d.device_kind,
                              "count": 1})
    run.install_listeners()
    result = harness.load_module("drivers", "train").run(run)
    assert result["correct"] and result["attempted"] > 0
    assert result["end_to_end"]["train_throughput"] > 0
    assert run.compiles_in_window() == 0


def test_same_seed_same_plan_other_seed_same_sizes():
    from benchmark.drivers import serve_closed

    t = TINY[SERVE_CELL][1]
    a, b, c = (serve_closed.make_plan(t, 63, s) for s in (5, 5, 2 ** 31 + 9))
    assert a == b and a != c
    sizes = lambda plan: sorted((len(r["tokens"]), r["max_new_tokens"])
                                for reqs in plan for r in reqs)
    assert sizes(a) == sizes(c)


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    assert bench_run.main(["--workload", CELLS[0], "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) == 2
    cap = capsys.readouterr()
    assert cap.out.strip() == "" and "No result" in cap.err
