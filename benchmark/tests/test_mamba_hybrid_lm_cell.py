"""The Granite-4.0-H-Micro cell: its files resolve by name, no width differs
from the source, its FLOP and parameter counts are the hand counts at the
published cut, the SSD kernels' roofline metric reads a made-up trace and
its arguments are the family's own count, the carried-state share reads a
made-up registry, a program without the mamba layer refuses at once, and a
tiny rehearsal of the cell through ``drivers/train.py`` prints the share
(CPU, counts only).  Every manifest entry is found by name, never by its
place in a list or the list's length."""

import json
import time

import numpy as np
import pytest

from benchmark import harness, run as bench_run

CELL = "granite-4.0-h-micro.train-tp4-16k"
CONFIG = "granite-4.0-h-micro"
NEW = {"kernel.ssd_roofline.train": ("trace_ops", "kernels", "device_trace",
                                     "%"),
       "ssm.chunk_carry": ("registry_delta", "state-space mixer",
                           "program_span", "ratio")}
fam = harness.load_module("families", "mamba_hybrid_lm")

LAYER_TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 9 + ["attention"]
               + ["mamba"] * 9 + ["attention"] + ["mamba"] * 9
               + ["attention"] + ["mamba"] * 4)
# huggingface.co/ibm-granite/granite-4.0-h-micro config.json, as the catalog
# beside the model-configs guide holds it
SOURCE = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": LAYER_TYPES,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}

TINY_CFG = dict(
    family="mamba_hybrid_lm", model_type="granitemoehybrid", hidden_size=64,
    intermediate_size=96, shared_intermediate_size=96, held_ffn_columns=48,
    layer_types=["mamba", "attention", "mamba"], num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=1, held_heads_first=0,
    vocab_size=256, rms_norm_eps=1e-5, rope_theta=10000,
    position_embedding_type="nope", attention_multiplier=0.125,
    embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
    tie_word_embeddings=True, mamba_n_heads=4, mamba_d_head=32,
    mamba_d_state=16, mamba_n_groups=1, mamba_expand=2, mamba_d_conv=4,
    mamba_chunk_size=64, mamba_conv_bias=True, mamba_proj_bias=False,
    normalization_function="rmsnorm", num_local_experts=0,
    num_experts_per_tok=0,
    published=dict(num_attention_heads=8, num_key_value_heads=2),
    correct={"logits_p90_limit": 1e-4})
TINY_TRAFFIC = dict(driver="train", seq_len=256, batch_per_chip=1,
                    examples=8, warmup_steps=2, loss_tolerance=1e-4,
                    optimizer={"name": "Adam", "learning_rate": 1e-4})


def by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


def test_config_file_differs_from_the_source_only_where_it_says():
    r = harness.resolve(CELL)
    cfg, traffic = r["config"], r["traffic"]
    assert set(SOURCE) <= set(cfg)
    changed = {k for k, v in SOURCE.items() if cfg[k] != v}
    reduced = {"num_hidden_layers", "layer_types", "num_attention_heads",
               "num_key_value_heads", "vocab_size"}
    assert changed == reduced
    assert set(cfg["reduced"]) == reduced | {"held_ffn_columns"}
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_why"])
    assert cfg["published"] == dict(
        {k: SOURCE[k] for k in reduced},
        held_ffn_columns=SOURCE["shared_intermediate_size"])
    # no width among them: hidden, FFN, head, state and chunk sizes stay
    assert not set(cfg["reduced"]) & {
        "hidden_size", "intermediate_size", "shared_intermediate_size",
        "mamba_d_head", "mamba_d_state", "mamba_n_heads", "mamba_expand",
        "mamba_d_conv", "mamba_chunk_size"}
    entry = by_name(r["bench"]["configs"], CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    # the cut: layers 0-9, heads 0-7 on key/value heads 0-1, a quarter of
    # the FFN and of the vocabulary
    assert cfg["layer_types"] == LAYER_TYPES[:10]
    assert cfg["held_heads_first"] == 0 and cfg["num_attention_heads"] == 8
    assert cfg["num_key_value_heads"] * 4 == cfg["num_attention_heads"]
    assert cfg["held_ffn_columns"] * 4 == SOURCE["intermediate_size"]
    assert cfg["vocab_size"] * 4 == SOURCE["vocab_size"]
    assert {"mamba_init", "time_step_limit", "gated_norm", "init",
            "seq_len", "compute_dtype", "recompute"} <= set(cfg["assumed"])
    assert (traffic["seq_len"], traffic["batch_per_chip"],
            traffic["examples"], traffic["warmup_steps"]) == (16384, 1, 256, 8)
    assert traffic["optimizer"] == {"name": "Adam", "learning_rate": 1e-4}


def test_flops_are_the_hand_count_at_the_published_cut():
    cfg = harness.resolve(CELL)["config"]
    seq, d = 16384, 2048
    f = fam.forward_flops_by_block(cfg, seq)
    assert f["mamba_proj"] == 9 * 2 * seq * (d * 8512 + 4096 * d)
    assert f["ffn"] == 10 * 2 * seq * 3 * d * 2048
    assert f["head"] == 2 * seq * d * 25088
    assert f["attn_proj"] == 2 * seq * d * (2 * 8 + 2 * 2) * 64
    # a chunk: C Bᵀ once, then per head (G ⊙ L) x, C S and the update
    chunk = 2 * 256 * 256 * 128 + 64 * (2 * 256 * 256 * 64
                                        + 4 * 256 * 128 * 64)
    assert f["ssd"] == 9 * 64 * chunk
    total = sum(f.values())
    assert total / seq == pytest.approx(879.6e6, rel=1e-3)
    assert (f["mamba_proj"] + f["ssd"]) / total == pytest.approx(0.572,
                                                                abs=1e-3)
    assert fam.train_flops_per_sample(cfg, {"seq_len": seq}) == 3 * total


def test_parameter_count_at_the_published_cut():
    import jax

    cfg = harness.resolve(CELL)["config"]
    shapes = jax.eval_shape(fam.build_model(cfg).init,
                            jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))["params"]
    count = lambda t: sum(int(np.prod(a.shape))
                          for a in jax.tree_util.tree_leaves(t))
    assert count(shapes) == 412_498_880
    assert count(shapes["layer0"]["mamba"]) == 25_847_232
    assert count(shapes["layer0"]) == 38_434_240
    assert count(shapes["layer5"]) == 15_208_448
    assert count(shapes["embed"]) == 25088 * 2048
    assert "head" not in shapes


def test_manifest_entries_resolve():
    r = harness.resolve(CELL)
    names = {m["name"] for m in r["per_layer"]}
    sala = {m["name"] for m in harness.resolve(
        "minicpm-sala.train-tp8-32k")["per_layer"]}
    sala_only = {m["name"] for m in r["bench"]["per_layer"]
                 if m.get("workloads") == ["minicpm-sala.train-tp8-32k"]}
    assert names == (sala - sala_only) | set(NEW)
    assert {m["name"] for m in r["end_to_end"]} == {"train_throughput",
                                                     "setup_s"}
    for name, (reader, layer, source, unit) in NEW.items():
        m = by_name(r["per_layer"], name)
        assert (m["reader"], m["layer"], m["source"], m["unit"],
                m["workloads"], m["moves"]) == (reader, layer, source, unit,
                                                [CELL], "train_throughput")
    # every train metric the five accepted train cells report lists it
    trains = {"resnet50.train-hostfed", "glm-4.7-flash.train-packed4k",
              "xing4.0-29b-a4b.train-tp8-packed4k",
              "lfm2-24b-a2b.train-ep8-packed8k", "minicpm-sala.train-tp8-32k"}
    for m in r["bench"]["end_to_end"] + r["bench"]["per_layer"]:
        if trains <= set(m.get("workloads", ())):
            assert CELL in m["workloads"], m["name"]
    assert "workloads" not in by_name(r["bench"]["per_layer"],
                                      "runtime.compile_s")
    assert r["cell"]["chips"] == 1 and r["cell"]["config"] == CONFIG
    assert r["cell"]["traffic"] == "train-lm-16k-b1"
    entry = by_name(r["bench"]["configs"], CONFIG)
    for text in (entry["why"], entry["source"], r["cell"]["why"]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()


def test_roofline_args_are_the_familys_own_count():
    r = harness.resolve(CELL)
    m = by_name(r["per_layer"], "kernel.ssd_roofline.train")
    assert m["args"] == {
        "ops": fam.ssd_flops_per_step(r["config"], r["traffic"]),
        "peak": "bf16_flops_per_s"}
    fwd = fam.ssd_forward_flops(r["config"], 16384) * 9
    bwd = fam.ssd_backward_flops(r["config"], 16384) * 9
    # the forward once under ssd_; rerun by jax.checkpoint with the
    # backward's two kernels under ssd
    assert m["args"]["ops"] == {"ssd_": fwd, "ssd": fwd + bwd}
    assert fwd == pytest.approx(628.1e9, rel=1e-3)
    ratio = sum(m["args"]["ops"].values()) / sum(
        fam.ssd_bytes_per_step(r["config"], r["traffic"]).values())
    assert 150 < ratio < 300                   # near v5e's ridge of 240


def test_roofline_reader_on_a_made_up_trace():
    r = harness.resolve(CELL)
    read = harness.load_module("readers", "trace_ops").read
    m = by_name(r["per_layer"], "kernel.ssd_roofline.train")
    ev = {"trace": {"window_s": 4.0, "busy_s": 3.9, "chips": 1,
                    "device_ops": [["fusion", 2.0], ["ssd", 0.3],
                                   ["ssd_", 0.1]]},
          "window": {"seconds": 40.0, "units": 60.0, "chips": 1},
          "peaks": {"bf16_flops_per_s": 197e12}}
    # 6 steps traced: 66.7 ms a step in the SSD kernels
    ops = m["args"]["ops"]
    assert read(m["args"], ev) == pytest.approx(
        sum(ops.values()) / (0.4 / 6) / 197e12 * 100)
    bare = dict(ev, trace=dict(ev["trace"], device_ops=[["fusion", 2.0]]))
    assert read(m["args"], bare) is None


def test_chunk_carry_reads_the_fine_mean():
    r = harness.resolve(CELL)
    m = by_name(r["per_layer"], "ssm.chunk_carry")
    read = harness.load_module("readers", m["reader"]).read
    snap = lambda s, n: {"counters": {}, "hists": {
        "ssm.chunk_carry": {"sum": s, "n": n}}}
    ev = {"registry": {"window_start": snap(0.5, 9),
                       "window_end": snap(0.5 + 27 * 0.04, 9 + 27)},
          "marks": {"window_start": 0.0, "window_end": 40.0}}
    assert read(m["args"], ev) == pytest.approx(0.04)
    # a program without the mamba layer observes nothing
    empty = {"counters": {}, "hists": {}}
    assert read(m["args"], {"registry": {"window_start": empty,
                                         "window_end": empty},
                            "marks": {}}) is None


def test_a_program_without_the_mamba_layer_refuses_at_once(monkeypatch):
    import bigdl_tpu.models.hybrid_moe_lm as lm

    monkeypatch.setattr(lm, "LAYER_TYPES", ("conv", "full_attention"))
    with pytest.raises(SystemExit):
        fam.build_model(harness.resolve(CELL)["config"])


@pytest.mark.parametrize("trace", (0, 1))
def test_tiny_rehearsal_reports_the_carried_share(trace):
    import jax

    resolved = harness.resolve(CELL)
    resolved["config"], resolved["traffic"] = TINY_CFG, TINY_TRAFFIC
    d = jax.devices()[0]
    run = harness.Run(CELL, TINY_CFG, TINY_TRAFFIC, seed=2 ** 31 + 40,
                      seconds=1.0, trace=0, t_process_start=time.monotonic(),
                      device={"platform": d.platform, "kind": d.device_kind,
                              "count": 1})
    run.install_listeners()
    result = harness.load_module("drivers", "train").run(run)
    line = json.loads(json.dumps(bench_run.assemble(
        resolved, run, result, None, bool(trace))))
    assert line["correct"] is True and line["attempted"] > 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert set(m) == {"train_throughput", "setup_s"}
        return
    assert 0.0 < m["ssm.chunk_carry"] < 1.0
    assert {"train.step_ms", "runtime.compile_s",
            "train.leaf_update_share"} <= set(m)
    assert not any("roofline" in k or "idle_share" in k or "mfu" in k
                   for k in m)
