"""The LFM2-24B-A2B cell: its files resolve, no width differs from the
source, its FLOP and parameter counts are the hand counts at the published
cut, the flash kernels' roofline metric reads a made-up trace and its
arguments are the family's own count, and a tiny rehearsal of the cell
through ``drivers/train.py`` prints the expert layer's metrics (CPU, counts
only)."""

import json
import time

import numpy as np
import pytest

from benchmark import harness, run as bench_run

CELL = "lfm2-24b-a2b.train-ep8-packed8k"
GLM_CELL = "glm-4.7-flash.train-packed4k"
ROOFLINE = "kernel.flash_roofline.train"
fam = harness.load_module("families", "conv_gqa_moe_lm")

PERIOD = ["conv", "conv", "full_attention", "conv"]
# huggingface.co/LiquidAI/LFM2-24B-A2B config.json, as the catalog beside
# the model-configs guide holds it
SOURCE = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": PERIOD * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}

TINY_CFG = dict(
    family="conv_gqa_moe_lm", vocab_size=512, hidden_size=64,
    num_hidden_layers=5,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    num_attention_heads=8, num_key_value_heads=2, intermediate_size=160,
    moe_intermediate_size=48, num_experts=4, num_experts_per_tok=2,
    num_dense_layers=1, conv_L_cache=3, conv_bias=False, norm_eps=1e-5,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
    rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    published={"num_experts": 8}, held_experts_first=2,
    correct={"logits_p90_limit": 1e-4})
TINY_TRAFFIC = dict(driver="train", seq_len=32, batch_per_chip=4, examples=16,
                    warmup_steps=2, loss_tolerance=1e-4,
                    optimizer={"name": "Adam", "learning_rate": 1e-4})


def test_config_file_differs_from_the_source_only_where_it_says():
    r = harness.resolve(CELL)
    cfg, traffic = r["config"], r["traffic"]
    assert set(SOURCE) <= set(cfg)
    changed = {k for k, v in SOURCE.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"}
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_why"])
    assert cfg["published"] == {k: SOURCE[k] for k in cfg["reduced"]}
    # no width among them
    assert not set(cfg["reduced"]) & {
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok", "conv_L_cache", "num_attention_heads",
        "num_key_value_heads"}
    entry = {c["name"]: c for c in r["bench"]["configs"]}["lfm2-24b-a2b"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    # the guide's floors: one dense layer, one whole period of expert layers
    # (attention to conv 1:3 as published), 8 experts, an eighth of the rows
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] == 4
    assert cfg["layer_types"] == SOURCE["layer_types"][1:6]
    assert sorted(cfg["layer_types"][1:]) == sorted(PERIOD)
    assert cfg["num_experts"] * 8 == SOURCE["num_experts"]
    assert cfg["vocab_size"] * 8 == SOURCE["vocab_size"]
    assert {"tie_embedding", "intermediate_size", "topk_sum_eps",
            "rope_pairing", "expert_bias", "w_in_order", "seq_len", "init",
            "compute_dtype", "recompute"} <= set(cfg["assumed"])
    assert (traffic["seq_len"], traffic["batch_per_chip"],
            traffic["examples"], traffic["warmup_steps"]) == (8192, 1, 256, 8)
    assert traffic["optimizer"] == {"name": "Adam", "learning_rate": 1e-4}
    c = fam.build_model(cfg).config
    assert c.held_experts == (0, 8) and c.num_experts == 64
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.conv_L_cache, c.rope_theta) == (32, 8, 64, 3, 1e6)


def test_flops_are_the_hand_count_at_the_published_cut():
    r = harness.resolve(CELL)
    t, d = 8192, 2048
    # per token, forward, multiply-add = 2 (ISSUE 34's count, by hand)
    conv = 2 * (d * 3 * d + d * d) + (2 + 2 * 3) * d
    proj = 2 * (d * d + 2 * d * 512 + d * d)
    scores = t * 32 * (64 + 64)            # causal half of qk^T and pv
    dense = 2 * 3 * d * 11776
    expert = 2 * 3 * d * 1536
    per_token = (4 * conv + proj + scores + dense
                 + 4 * (2 * d * 64 + 4 * 8 / 64 * expert) + 2 * d * 8192)
    assert per_token == pytest.approx(405.9e6, rel=1e-3)
    assert fam.train_flops_per_sample(r["config"], r["traffic"]) == \
        pytest.approx(3 * t * per_token, rel=1e-12)
    assert 3 * t * per_token == pytest.approx(9.97e12, rel=1e-3)
    by = fam.forward_flops_by_block(r["config"], t)
    share = {k: round(100 * v / sum(by.values()), 1) for k, v in by.items()}
    assert share == dict(conv_op=33.1, attn_proj=5.2, attn_scores=8.3,
                         dense_ffn=35.7, router=0.3, routed_experts=9.3,
                         head=8.3)


def test_parameter_count_at_the_published_cut():
    import jax

    cfg = harness.resolve(CELL)["config"]
    model = fam.build_model(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, np.zeros((1, 8), np.int32)),
        jax.random.PRNGKey(0))["params"]
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree_util.tree_leaves(tree))
    n = count(shapes)
    assert n == 469_284_992                  # 7.51 GB at 16 B a parameter
    assert "469.28 M" in cfg["parameters_here"]
    assert "head" not in shapes and count(shapes["embed"]) == 16_777_216
    assert count(shapes["layer0"]) == 89_139_200
    assert count(shapes["layer0"]["conv"]) == 16_783_360
    assert count(shapes["layer1"]["attn"]) == 10_485_888
    assert count(shapes["layer1"]["moe"]["experts"]) == 75_497_472
    assert "shared" not in shapes["layer1"]["moe"]
    experts = count([shapes[f"layer{i}"]["moe"]["experts"]
                     for i in range(1, 5)])
    assert round(100 * experts / n) == 64
    # no leaf whose minor dimension is under 128 lanes but the vectors
    assert all(a.ndim == 1 or a.shape[-1] >= 128
               for a in jax.tree_util.tree_leaves(shapes))


def test_manifest_entries_resolve():
    r = harness.resolve(CELL)
    names = {m["name"] for m in r["per_layer"]}
    glm = {m["name"] for m in harness.resolve(GLM_CELL)["per_layer"]}
    assert names == glm | {ROOFLINE}
    assert {m["name"] for m in r["end_to_end"]} == {"train_throughput",
                                                     "setup_s"}
    roof = next(m for m in r["per_layer"] if m["name"] == ROOFLINE)
    assert (roof["reader"], roof["layer"], roof["workloads"], roof["unit"],
            roof["source"], roof["moves"]) == (
        "trace_ops", "kernels", [CELL], "%", "device_trace",
        "train_throughput")
    assert r["bench"]["per_layer"][-1]["name"] == ROOFLINE
    assert r["cell"]["chips"] == 1
    entry = {c["name"]: c for c in r["bench"]["configs"]}[r["cell"]["config"]]
    # the manifest's one-line texts: 1 to 200 printable ASCII characters
    for text in (entry["why"], entry["source"], r["cell"]["why"]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()


def test_roofline_args_are_the_familys_own_count():
    r = harness.resolve(CELL)
    roof = next(m for m in r["per_layer"] if m["name"] == ROOFLINE)
    want = fam.flash_flops_per_step(r["config"], r["traffic"])
    assert roof["args"]["ops"] == want
    one_pass = 2 * (8192 * 8192 / 2) * 64 * 32
    assert one_pass == pytest.approx(137.4e9, rel=1e-3)
    assert want == {"attn": 9 * one_pass, "attn_": 2 * one_pass}
    assert sum(want.values()) == pytest.approx(1.51e12, rel=2e-3)
    assert roof["args"]["peak"] == "bf16_flops_per_s"


def _evidence(device_ops, steps_per_s=4.0):
    return {"trace": {"window_s": 4.0, "busy_s": 3.99, "chips": 1,
                      "device_ops": device_ops},
            "window": {"seconds": 40.0, "units": 40.0 * steps_per_s,
                       "chips": 1, "flops_per_unit": 9.97e12},
            "peaks": {"bf16_flops_per_s": 197e12}}


def test_roofline_reader_on_a_made_up_trace():
    read = harness.load_module("readers", "trace_ops").read
    args = {"ops": {"attn": 9e11, "attn_": 2e11}, "peak": "bf16_flops_per_s"}
    ops = [["fusion", 1.6], ["attn", 0.48], ["copy", 0.3], ["attn_", 0.16]]
    # 16 steps traced: attn 30 ms + attn_ 10 ms a step for 1.1 TFLOP
    assert read(args, _evidence(ops)) == pytest.approx(
        1.1e12 / 0.040 / 197e12 * 100)
    # a name that fell out of the ten takes its operations with it
    assert read(args, _evidence(ops[:3])) == pytest.approx(
        9e11 / 0.030 / 197e12 * 100)
    # neither name among the ten, no trace, no window, an older program's
    # evidence: nothing to read, and nothing raised
    assert read(args, _evidence(ops[:1])) is None
    assert read(args, dict(_evidence(ops), trace=None)) is None
    assert read(args, dict(_evidence(ops), window=None)) is None
    assert read(args, {"registry": {}, "marks": {}}) is None
    assert read(args, dict(_evidence(ops), peaks=None)) is None


@pytest.mark.parametrize("ablate", fam.ABLATIONS)
def test_reference_loss_tells_each_ablation_apart(ablate, capsys):
    import jax

    model = fam.build_model(TINY_CFG)
    ids = np.random.default_rng(4).integers(2, 512, (2, 33), dtype=np.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    params = model.init(jax.random.PRNGKey(14), x[:1])["params"]
    attn = params["layer1"]["attn"]    # norm weights that are not 1
    attn["q_norm"] = 1 + 0.5 * jax.random.normal(jax.random.PRNGKey(1), (8,))
    attn["k_norm"] = 1 + 0.5 * jax.random.normal(jax.random.PRNGKey(2), (8,))
    cfg = dict(TINY_CFG, correct={"logits_p90_limit": 5e-3})
    loss = fam.reference_loss(cfg, params, x, y, ablate)
    assert np.isnan(loss) == (ablate is not None)
    assert f"ok={ablate is None}" in capsys.readouterr().out


@pytest.mark.parametrize("trace", (0, 1))
def test_tiny_rehearsal_reports_the_expert_layer(trace, capsys):
    import jax

    resolved = harness.resolve(CELL)
    resolved["config"], resolved["traffic"] = TINY_CFG, TINY_TRAFFIC
    d = jax.devices()[0]
    run = harness.Run(CELL, TINY_CFG, TINY_TRAFFIC, seed=2 ** 31 + 34,
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
    assert m["moe.dropped_pairs"] == 0.0
    assert m["moe.short_path_share"] == 100.0
    # 4 of 8 experts held: about half of the pairs, spread over 4 experts
    assert 35.0 < m["moe.local_pair_share"] < 65.0
    assert 1.0 <= m["moe.load_imbalance"] < 4.0
    assert {"train.step_ms", "train.sync_wait_share",
            "data.produce_ms"} <= set(m)
    # no device trace on the CPU: nothing under a device metric's name
    assert "data.ring_batch_share" not in m and not any(
        "idle_share" in k or "mfu" in k or "roofline" in k for k in m)
