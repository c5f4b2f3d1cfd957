"""The GLM-4.7-Flash cell: its files resolve, its FLOP count is the hand
count at the published cut, and a tiny rehearsal of it through
``drivers/train.py`` prints the expert layer's metrics (CPU, counts only)."""

import json
import time

import pytest

from benchmark import harness, run as bench_run

CELL = "glm-4.7-flash.train-packed4k"
fam = harness.load_module("families", "mla_moe_lm")

TINY_CFG = dict(
    family="mla_moe_lm", vocab_size=512, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    intermediate_size=160, moe_intermediate_size=48, n_routed_experts=4,
    num_experts_per_tok=2, n_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=1.8, norm_topk_prob=True, rope_theta=1e6,
    rms_norm_eps=1e-5, published={"n_routed_experts": 8},
    held_experts_first=2,
    correct={"logits_p90_limit": 1e-4})
TINY_TRAFFIC = dict(driver="train", seq_len=32, batch_per_chip=4, examples=16,
                    warmup_steps=2, loss_tolerance=1e-4,
                    optimizer={"name": "Adam", "learning_rate": 1e-4})


def test_config_file_keeps_every_published_width():
    r = harness.resolve(CELL)
    cfg, traffic = r["config"], r["traffic"]
    widths = dict(hidden_size=2048, intermediate_size=10240,
                  moe_intermediate_size=1536, num_attention_heads=20,
                  num_key_value_heads=20, q_lora_rank=768, kv_lora_rank=512,
                  qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                  num_experts_per_tok=4, n_shared_experts=1,
                  first_k_dense_replace=1, routed_scaling_factor=1.8,
                  rope_theta=1000000, rms_norm_eps=1e-5, n_group=1,
                  topk_group=1)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["published"] == dict(num_hidden_layers=47, n_routed_experts=64,
                                    vocab_size=154880,
                                    num_nextn_predict_layers=1)
    # the guide's floors: the dense layer and four expert layers, 8 experts
    # held, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert sorted(cfg["reduced"]) == sorted(cfg["reduced_why"])
    assert (traffic["seq_len"], traffic["batch_per_chip"],
            traffic["examples"]) == (4096, 2, 256)
    c = fam.build_model(cfg).config
    assert c.held_experts == (0, 8) and c.n_routed_experts == 64


def test_flops_are_the_hand_count_at_the_published_cut():
    r = harness.resolve(CELL)
    t = 4096
    # per token, forward, multiply-add = 2 (ISSUE 28's count, by hand)
    proj = 2 * (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
                + 20 * 256 * 2048)
    scores = t * 20 * (256 + 256)          # causal half of qk^T and pv
    dense = 2 * 3 * 2048 * 10240
    expert = 2 * 3 * 2048 * 1536
    per_token = (5 * (proj + scores) + dense
                 + 4 * (expert + 2 * 2048 * 64 + 4 * 8 / 64 * expert)
                 + 2 * 2048 * 19360)
    assert per_token == pytest.approx(746.7e6, rel=1e-3)
    assert fam.train_flops_per_sample(r["config"], r["traffic"]) == \
        pytest.approx(3 * t * per_token, rel=1e-12)
    by = fam.forward_flops_by_block(r["config"], t)
    share = {k: round(100 * v / sum(by.values())) for k, v in by.items()}
    assert share == dict(mla_proj=29, attn_scores=28, dense_ffn=17,
                         shared_expert=10, router=0, routed_experts=5,
                         head=11)


def test_parameter_count_at_the_published_cut():
    import jax
    import numpy as np

    cfg = harness.resolve(CELL)["config"]
    model = fam.build_model(cfg)
    shapes = jax.eval_shape(
        lambda k: model.init(k, np.zeros((1, 8), np.int32)),
        jax.random.PRNGKey(0))["params"]
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == 591_294_720                  # 9.46 GB at 16 B a parameter
    experts = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        [shapes[f"layer{i}"]["moe"]["experts"] for i in range(1, 5)]))
    assert round(100 * experts / n) == 51


@pytest.mark.parametrize("trace", (0, 1))
def test_tiny_rehearsal_reports_the_expert_layer(trace, capsys):
    import jax

    resolved = harness.resolve(CELL)
    resolved["config"], resolved["traffic"] = TINY_CFG, TINY_TRAFFIC
    d = jax.devices()[0]
    run = harness.Run(CELL, TINY_CFG, TINY_TRAFFIC, seed=2 ** 31 + 28,
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
    # 4 of 8 experts held: about half of the pairs, spread over 4 experts
    assert 35.0 < m["moe.local_pair_share"] < 65.0
    assert 1.0 <= m["moe.load_imbalance"] < 4.0
    assert {"train.step_ms", "train.sync_wait_share",
            "data.produce_ms"} <= set(m)
    assert "data.ring_batch_share" not in m and not any(
        "idle_share" in k or "mfu" in k for k in m)
