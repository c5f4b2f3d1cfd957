"""The MiniCPM-SALA cell: its files resolve by name, no width differs from
the source, its FLOP and parameter counts are the hand counts at the
published cut, the sparse kernels' roofline metric reads a made-up trace
and its arguments are the family's own count, the selection and walked
shares read a made-up registry, a program without the new layer kinds
refuses at once, and a tiny rehearsal of the cell through
``drivers/train.py`` prints both shares (CPU, counts only)."""

import json
import time

import numpy as np
import pytest

from benchmark import harness, run as bench_run

CELL = "minicpm-sala.train-tp8-32k"
CONFIG = "minicpm-sala"
NEW = {"sparse.selected_block_share": ("registry_delta", "attention",
                                       "program_counter"),
       "kernel.sparse_attn_roofline.train": ("trace_ops", "kernels",
                                             "device_trace"),
       "sparse.walked_span_share": ("registry_delta", "attention",
                                    "program_counter")}
# the accepted cells' metrics this cell does not report: they read the
# expert layer, the hyper-connection path, the flash kernels or the ring
LFM2_ONLY = {"moe.load_imbalance", "moe.dropped_pairs", "moe.local_pair_share",
             "moe.short_path_share", "kernel.flash_roofline.train"}
fam = harness.load_module("families", "sparse_linear_lm")

MIXERS = (["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"]
          + ["lightning-attn"] * 6 + ["minicpm4"] * 2 + ["lightning-attn"] * 4
          + ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"] * 3)
# huggingface.co/openbmb/MiniCPM-SALA config.json, as the catalog beside
# the model-configs guide holds it
SOURCE = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": MIXERS, "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True}

TINY_CFG = dict(
    family="sparse_linear_lm", hidden_size=64, head_dim=16,
    lightning_head_dim=16, intermediate_size=96, held_ffn_columns=48,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                 "lightning-attn"], num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=1, lightning_nh=4,
    lightning_nkv=4, held_heads_first=4, vocab_size=256, rms_norm_eps=1e-6,
    rope_theta=10000, scale_emb=12, scale_depth=1.4, mup_denominator=32,
    dim_model_base=16, tie_word_embeddings=False, qk_norm=True,
    attn_use_rope=False, lightning_use_rope=True, lightning_scale="1/sqrt(d)",
    use_output_gate=True, use_output_norm=True, attn_use_output_gate=True,
    sparse_config=dict(block_size=64, dense_len=128, init_blocks=1,
                       kernel_size=32, kernel_stride=16, topk=4,
                       window_size=128),
    published=dict(num_attention_heads=8, num_key_value_heads=2,
                   lightning_nh=8, lightning_nkv=8),
    correct={"logits_p90_limit": 1e-4})
TINY_TRAFFIC = dict(driver="train", seq_len=512, batch_per_chip=1,
                    examples=8, warmup_steps=2, loss_tolerance=1e-4,
                    optimizer={"name": "Adam", "learning_rate": 1e-4})


def by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


def test_config_file_differs_from_the_source_only_where_it_says():
    r = harness.resolve(CELL)
    cfg, traffic = r["config"], r["traffic"]
    assert set(SOURCE) <= set(cfg)
    changed = {k for k, v in SOURCE.items() if cfg[k] != v}
    reduced = {"num_hidden_layers", "mixer_types", "num_attention_heads",
               "num_key_value_heads", "lightning_nh", "lightning_nkv",
               "vocab_size"}
    assert changed == reduced
    assert set(cfg["reduced"]) == reduced | {"held_ffn_columns"}
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_why"])
    assert cfg["published"] == dict(
        {k: SOURCE[k] for k in reduced},
        held_ffn_columns=SOURCE["intermediate_size"])
    # no width among them: hidden, FFN, head sizes stay
    assert not set(cfg["reduced"]) & {
        "hidden_size", "intermediate_size", "head_dim", "lightning_head_dim",
        "dim_model_base"}
    entry = by_name(r["bench"]["configs"], CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    # the cut: layers 0-3, heads 28-31 on key/value head 1, an eighth of
    # the FFN and of the vocabulary
    assert cfg["mixer_types"] == MIXERS[:4]
    assert cfg["held_heads_first"] == 28 and cfg["num_attention_heads"] == 4
    assert cfg["held_heads_first"] // 16 == 1 == cfg["num_key_value_heads"]
    assert cfg["held_ffn_columns"] * 8 == SOURCE["intermediate_size"]
    assert cfg["vocab_size"] * 8 == SOURCE["vocab_size"]
    assert cfg["sparse_config"] == dict(
        kernel_size=32, kernel_stride=16, block_size=64, topk=64,
        init_blocks=1, window_size=2048, dense_len=8192)
    assert {"sparse_config", "lightning_decay", "forced_blocks",
            "group_selection", "init", "mup", "seq_len",
            "compute_dtype"} <= set(cfg["assumed"])
    assert (traffic["seq_len"], traffic["batch_per_chip"],
            traffic["examples"], traffic["warmup_steps"]) == (32768, 1, 256, 8)


def test_flops_are_the_hand_count_at_the_published_cut():
    cfg = harness.resolve(CELL)["config"]
    f = fam.forward_flops_by_block(cfg, 32768)
    seq, d = 32768, 4096
    assert f["ffn"] == 4 * 2 * seq * 3 * d * 2048
    assert f["head"] == 2 * seq * d * 9181
    assert f["lightning_proj"] == 3 * 2 * seq * 5 * d * 512
    assert f["sparse_proj"] == 2 * seq * (3 * d * 512 + 2 * d * 128)
    assert fam.selected_key_pairs(cfg, seq) == 124_928_000
    assert f["sparse_attn"] == 2 * 2 * 124_928_000 * 128 * 4
    total = sum(f.values())
    assert total == pytest.approx(11.92e12, rel=1e-3)
    assert f["ffn"] / total == pytest.approx(0.553, abs=1e-3)
    assert fam.train_flops_per_sample(cfg, {"seq_len": seq}) == 3 * total
    assert round(100 * fam.selection_share(cfg, seq), 2) == 23.42


def test_parameter_count_at_the_published_cut():
    import jax

    cfg = harness.resolve(CELL)["config"]
    shapes = jax.eval_shape(fam.build_model(cfg).init,
                            jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))["params"]
    count = lambda t: sum(int(np.prod(a.shape))
                          for a in jax.tree_util.tree_leaves(t))
    assert count(shapes) == 214_710_784
    assert count(shapes["layer0"]) == 32_514_304
    assert count(shapes["layer1"]) == 35_660_544
    assert count(shapes["head"]) == count(shapes["embed"]) == 9181 * 4096


def test_manifest_entries_resolve():
    r = harness.resolve(CELL)
    names = {m["name"] for m in r["per_layer"]}
    lfm2 = {m["name"] for m in harness.resolve(
        "lfm2-24b-a2b.train-ep8-packed8k")["per_layer"]}
    assert names == (lfm2 - LFM2_ONLY) | set(NEW)
    assert {m["name"] for m in r["end_to_end"]} == {"train_throughput",
                                                     "setup_s"}
    for name, (reader, layer, source) in NEW.items():
        m = by_name(r["per_layer"], name)
        assert (m["reader"], m["layer"], m["source"], m["workloads"],
                m["moves"]) == (reader, layer, source, [CELL],
                                "train_throughput")
    # runtime.compile_s has no list: it is read in every cell, this one too
    assert "workloads" not in by_name(r["bench"]["per_layer"],
                                      "runtime.compile_s")
    assert r["cell"]["chips"] == 1 and r["cell"]["config"] == CONFIG
    entry = by_name(r["bench"]["configs"], CONFIG)
    for text in (entry["why"], entry["source"], r["cell"]["why"]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()


def test_roofline_args_are_the_familys_own_count():
    r = harness.resolve(CELL)
    sparse = by_name(r["per_layer"], "kernel.sparse_attn_roofline.train")
    one_pass = 2 * 124_928_000 * 128 * 4
    assert one_pass == pytest.approx(127.9e9, rel=1e-3)
    assert sparse["args"] == {
        "ops": fam.sparse_flops_per_step(r["config"], r["traffic"]),
        "peak": "bf16_flops_per_s"}
    assert sparse["args"]["ops"] == {"sparse_attn": 9 * one_pass,
                                     "sparse_attn_": 2 * one_pass}
    # the lightning kernels' least bytes: no metric reads them yet (a step
    # spends ~5 ms in them, under the trace's tenth name; PERF.md section 7)
    tensor = 32768 * 4 * 128 * 2 * 3
    assert fam.lightning_bytes_per_step(r["config"], r["traffic"]) == {
        "lightning": 11 * tensor, "lightning_": 4 * tensor}
    assert not any("lightning" in m["name"] for m in r["bench"]["per_layer"])


def test_roofline_readers_on_a_made_up_trace():
    r = harness.resolve(CELL)
    read = harness.load_module("readers", "trace_ops").read
    sparse = by_name(r["per_layer"], "kernel.sparse_attn_roofline.train")
    ev = {"trace": {"window_s": 4.0, "busy_s": 3.9, "chips": 1,
                    "device_ops": [["fusion", 2.0], ["sparse_attn", 0.4],
                                   ["lightning", 0.2], ["sparse_attn_", 0.1]]},
          "window": {"seconds": 40.0, "units": 40.0, "chips": 1},
          "peaks": {"bf16_flops_per_s": 197e12}}
    # 4 steps traced: 125 ms a step in the sparse kernels
    ops = sparse["args"]["ops"]
    assert read(sparse["args"], ev) == pytest.approx(
        sum(ops.values()) / 0.125 / 197e12 * 100)
    # the recomputed forward fell out of the ten: its FLOPs go with it
    ten = dict(ev, trace=dict(ev["trace"], device_ops=ev["trace"][
        "device_ops"][:3]))
    assert read(sparse["args"], ten) == pytest.approx(
        ops["sparse_attn"] / 0.1 / 197e12 * 100)
    # a program without the kernels: nothing to read, nothing raised
    bare = dict(ev, trace=dict(ev["trace"], device_ops=[["fusion", 2.0]]))
    assert read(sparse["args"], bare) is None


@pytest.mark.parametrize("name,num,den,per_step,share", [
    # 32,768 positions: 1,968,128 of 8,404,992 (query, block) pairs
    ("sparse.selected_block_share", "sparse.selected_blocks",
     "sparse.visible_blocks", (1_968_128, 8_404_992), 23.416),
    # 64 tiles by 64 spans: 2,080 causal pairs, a band of 3 a tile walked
    ("sparse.walked_span_share", "sparse.walked_spans",
     "sparse.causal_spans", (190, 2_080), 9.135),
])
def test_counter_shares_read_their_two_counters(name, num, den, per_step,
                                                share):
    r = harness.resolve(CELL)
    m = by_name(r["per_layer"], name)
    read = harness.load_module("readers", m["reader"]).read
    snap = lambda a, b: {"counters": {num: a, den: b}, "hists": {}}
    ev = {"registry": {"window_start": snap(10, 20),
                       "window_end": snap(10 + per_step[0] * 3,
                                          20 + per_step[1] * 3)},
          "marks": {"window_start": 0.0, "window_end": 40.0}}
    assert read(m["args"], ev) == pytest.approx(share, abs=1e-3)
    # a program without the sparse layer books neither counter
    assert read(m["args"], {"registry": {
        "window_start": {"counters": {}, "hists": {}},
        "window_end": {"counters": {}, "hists": {}}}, "marks": {}}) is None


def test_a_program_without_the_new_layer_kinds_refuses_at_once(monkeypatch):
    import bigdl_tpu.models.hybrid_moe_lm as lm

    monkeypatch.setattr(lm, "LAYER_TYPES", ("conv", "full_attention"))
    with pytest.raises(SystemExit):
        fam.build_model(harness.resolve(CELL)["config"])


@pytest.mark.parametrize("trace", (0, 1))
def test_tiny_rehearsal_reports_the_selection_share(trace):
    import jax

    resolved = harness.resolve(CELL)
    resolved["config"], resolved["traffic"] = TINY_CFG, TINY_TRAFFIC
    d = jax.devices()[0]
    run = harness.Run(CELL, TINY_CFG, TINY_TRAFFIC, seed=2 ** 31 + 38,
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
    # 512 positions, 8 blocks, top-4: 3,328 of 4,608 (query, block) pairs
    assert m["sparse.selected_block_share"] == pytest.approx(
        100 * 3328 / 4608)
    # one 512-query tile on one 512-key span: the whole triangle
    assert m["sparse.walked_span_share"] == pytest.approx(100)
    assert {"train.step_ms", "runtime.compile_s",
            "train.leaf_update_share"} <= set(m)
    assert not any("roofline" in k or "idle_share" in k or "mfu" in k
                   for k in m)
