"""The Xing4.0-29B-A4B cell: its files resolve, no width differs from the
source, its FLOP and parameter counts are the hand counts at the published
cut, ``reference_loss`` tells each ablation apart, and a tiny rehearsal of
it through ``drivers/train.py`` prints the residual path's and the expert
layer's metrics (CPU, counts only)."""

import json
import time

import numpy as np
import pytest

from benchmark import harness, run as bench_run

CELL = "xing4.0-29b-a4b.train-tp8-packed4k"
GLM_CELL = "glm-4.7-flash.train-packed4k"
fam = harness.load_module("families", "hc_mla_moe_lm")

TINY_CFG = dict(
    family="hc_mla_moe_lm", vocab_size=512, hidden_size=64,
    num_hidden_layers=3, num_attention_heads=2, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=12,
    intermediate_size=160, held_ffn_columns=40, moe_intermediate_size=48,
    n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1,
    first_k_dense_replace=1, routed_scaling_factor=2, norm_topk_prob=True,
    rope_theta=1e4, rms_norm_eps=1e-6,
    rope_scaling=dict(type="yarn", factor=64, beta_fast=32, beta_slow=1,
                      mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=16),
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, published={"n_routed_experts": 8},
    held_experts_first=2, correct={"logits_p90_limit": 1e-4})
TINY_TRAFFIC = dict(driver="train", seq_len=32, batch_per_chip=4, examples=16,
                    warmup_steps=2, loss_tolerance=1e-4,
                    optimizer={"name": "Adam", "learning_rate": 1e-4})

# huggingface.co/XingChen-AGI/Xing4.0-29B-A4B config.json, as the catalog
# beside the model-configs guide holds it
SOURCE = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 131072}


def test_config_file_differs_from_the_source_only_where_it_says():
    r = harness.resolve(CELL)
    cfg, traffic = r["config"], r["traffic"]
    changed = {k for k, v in SOURCE.items() if cfg[k] != v}
    assert changed | {"held_ffn_columns"} == set(cfg["reduced"])
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_why"])
    assert set(cfg["published"]) == set(cfg["reduced"])
    for k in changed:
        assert cfg["published"][k] == SOURCE[k]
    # no width among them
    assert not any(k.endswith(("_dim", "_rank")) or k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok", "hc_mult") for k in cfg["reduced"])
    entry = {c["name"]: c for c in r["bench"]["configs"]}["xing4.0-29b-a4b"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    # the guide's floors, and one eighth of everything that is shared out
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert cfg["n_routed_experts"] == 8
    for held, whole in ((cfg["n_routed_experts"], 64),
                        (cfg["num_attention_heads"], 32),
                        (cfg["held_ffn_columns"], cfg["intermediate_size"]),
                        (cfg["vocab_size"], 131072)):
        assert held * 8 == whole
    assert (traffic["seq_len"], traffic["batch_per_chip"],
            traffic["examples"]) == (4096, 1, 256)
    c = fam.build_model(cfg).config
    assert c.held_experts == (0, 8) and c.n_routed_experts == 64
    assert (c.num_attention_heads, c.held_ffn_columns, c.hc_mult) == (
        4, 1152, 4)
    assert dict(c.rope_scaling) == SOURCE["rope_scaling"]


def test_flops_are_the_hand_count_at_the_published_cut():
    r = harness.resolve(CELL)
    t, d = 4096, 3584
    # per token, forward, multiply-add = 2 (ISSUE 32's count, by hand)
    proj = 2 * (d * 768 + 768 * 4 * 192 + d * 576 + 512 * 4 * 256
                + 4 * 128 * d)
    scores = t * 4 * (192 + 128)           # causal half of qk^T and pv
    dense = 2 * 3 * d * 1152
    expert = 2 * 3 * d * 1024
    mixing = 2 * (24 * 4 * d + 4 * d + 20 * d)      # a sublayer
    per_token = (5 * (proj + scores + 2 * mixing) + dense
                 + 4 * (expert + 2 * d * 64 + 4 * 8 / 64 * expert)
                 + 2 * d * 16384)
    assert per_token == pytest.approx(388.6e6, rel=1e-3)
    assert fam.train_flops_per_sample(r["config"], r["traffic"]) == \
        pytest.approx(3 * t * per_token, rel=1e-12)
    # a step of one sequence: 4.77 TFLOP (two, as ISSUE 32 counted: 9.55)
    assert 3 * t * per_token == pytest.approx(4.775e12, rel=1e-3)
    by = fam.forward_flops_by_block(r["config"], t)
    share = {k: round(100 * v / sum(by.values())) for k, v in by.items()}
    assert share == dict(mla_proj=20, attn_scores=7, dense_ffn=6,
                         shared_expert=23, router=0, routed_experts=11,
                         hc_mixing=2, head=30)


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
    assert n == 569_422_862                  # 9.11 GB at 16 B a parameter
    assert "569.4 M" in cfg["parameters_here"]
    layer = shapes["layer1"]
    assert count(layer["attn"]) == 7_767_296
    assert count([layer["hc_attn"], layer["hc_ffn"]]) == 2 * (
        24 * 4 * 3584 + 24 + 3)
    assert count(layer["moe"]["experts"]) == 88_080_384
    experts = count([shapes[f"layer{i}"]["moe"]["experts"]
                     for i in range(1, 5)])
    assert round(100 * experts / n) == 62
    # no leaf whose minor dimension is under 128 lanes but the vectors
    assert all(a.ndim == 1 or a.shape[-1] >= 128
               for a in jax.tree_util.tree_leaves(shapes))


def test_manifest_entries_resolve():
    r = harness.resolve(CELL)
    names = {m["name"] for m in r["per_layer"]}
    glm = {m["name"] for m in harness.resolve(GLM_CELL)["per_layer"]}
    assert names == glm | {"hc.doubly_stochastic_err"}
    assert "data.ring_batch_share" not in names
    assert {m["name"] for m in r["end_to_end"]} == {"train_throughput",
                                                     "setup_s"}
    hc = next(m for m in r["per_layer"]
              if m["name"] == "hc.doubly_stochastic_err")
    assert (hc["reader"], hc["layer"], hc["workloads"]) == (
        "registry_delta", "residual path", [CELL])
    assert r["cell"]["chips"] == 1 and len(r["cell"]["why"]) <= 200
    assert len(r["bench"]["workloads"]) == 3
    # the manifest's one-line texts: 1 to 200 printable ASCII characters
    entry = r["bench"]["configs"][-1]
    assert entry["name"] == r["cell"]["config"]
    for text in (entry["why"], entry["source"], r["cell"]["why"]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()
    # where the program has no such histogram (the parent), the reader
    # returns nothing and does not raise
    reader = harness.load_module("readers", "registry_delta")
    empty = {"registry": {m: {"counters": {}, "hists": {}}
                          for m in ("window_start", "window_end")},
             "marks": {"window_start": 0.0, "window_end": 1.0}}
    assert reader.read(hc["args"], empty) is None


@pytest.mark.parametrize("ablate", fam.ABLATIONS)
def test_reference_loss_tells_each_ablation_apart(ablate, capsys):
    import jax

    model = fam.build_model(TINY_CFG)
    ids = np.random.default_rng(4).integers(2, 512, (2, 33), dtype=np.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    params = model.init(jax.random.PRNGKey(14), x[:1])["params"]
    cfg = dict(TINY_CFG, correct={"logits_p90_limit": 5e-3})
    loss = fam.reference_loss(cfg, params, x, y, ablate)
    assert np.isnan(loss) == (ablate is not None)
    assert f"ok={ablate is None}" in capsys.readouterr().out


@pytest.mark.parametrize("trace", (0, 1))
def test_tiny_rehearsal_reports_the_residual_path(trace, capsys):
    import jax

    resolved = harness.resolve(CELL)
    resolved["config"], resolved["traffic"] = TINY_CFG, TINY_TRAFFIC
    d = jax.devices()[0]
    run = harness.Run(CELL, TINY_CFG, TINY_TRAFFIC, seed=2 ** 31 + 32,
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
    assert 0.0 < m["hc.doubly_stochastic_err"] < 1e-3
    assert m["moe.dropped_pairs"] == 0.0
    assert 35.0 < m["moe.local_pair_share"] < 65.0
    assert 1.0 <= m["moe.load_imbalance"] < 4.0
    assert {"train.step_ms", "train.sync_wait_share",
            "data.produce_ms"} <= set(m)
    assert "data.ring_batch_share" not in m and not any(
        "idle_share" in k or "mfu" in k for k in m)
