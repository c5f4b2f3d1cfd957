"""Secondary headline benchmark: decoder-only transformer LM training
throughput (tokens/sec/chip) with MFU accounting.

BASELINE.json config 3 is the reference's Seq2Seq/Transformer-on-WMT path;
this measures the same model family on the flagship training engine
(`ShardedParameterStep` ZeRO-1) with the causal flash-attention Pallas
kernel in the layer stack.  Transformers keep the MXU far busier than
ResNet's small convs, so this is the framework's best-MFU evidence.

Model: GPT-2-small-class decoder-only LM — 12 layers, d=768, 12 heads,
ffn 3072, vocab 32k, seq 1024, weight-tied output projection
(`nn/attention.py` Transformer(mode="lm")).

Prints ONE JSON line.  On CPU it runs a tiny smoke (labelled
``tiny_smoke``, no MFU) so the harness is testable without the chip
(BENCH_LM_TINY=1 forces it).
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import _peak_flops


def _analytic_flops_per_token(n_layers, d, seq, vocab):
    """Training FLOPs/token: 3x forward; forward = 2 FLOPs per matmul
    param-use (QKVO 4d^2 + FFN 8d^2 per layer, + vocab projection) plus
    the attention score/value matmuls.  CAUSAL accounting: a token attends
    to seq/2 keys on average, so scores+AV cost 2*(seq/2)*d*2 = 2*seq*d —
    the conservative (undercounting) convention, so MFU is a floor."""
    per_layer = 2 * (12 * d * d) + 2 * seq * d
    return 3 * (n_layers * per_layer + 2 * d * vocab)


def _sparse_ab(b, tiny, n_chips, mesh, crit, rng, V, S, L, D, H, fpt,
               peak):
    """``--sparse``: dense-FFN control vs block-sparse FFN under the
    BLaST schedule, same data/seed/steps.  Prune events rebuild the step
    engine (the mask is static per compiled program) under
    ``expected_compile`` so the recompile sentinel stays quiet; the Adam
    state resets at each event (documented bench simplification — the
    schedule has a handful of events, not one per step)."""
    import jax

    from bigdl_tpu.nn.attention import Transformer
    from bigdl_tpu.obs.attr import expected_compile
    from bigdl_tpu.ops.block_sparse import (BlockPruningSchedule,
                                            prune_model_to_sparsity)
    from bigdl_tpu.optim.optim_method import Adam
    from bigdl_tpu.optim.train_step import ShardedParameterStep

    target = float(os.environ.get("BENCH_LM_SPARSITY", "0.5"))
    block = (16, 16) if tiny else (64, 64)
    warmup, ramp, tail = (2, 4, 3) if tiny else (10, 20, 10)
    n_events = 2 if tiny else 4
    total = warmup + ramp + tail
    sched = BlockPruningSchedule(target, warmup_steps=warmup,
                                 ramp_steps=ramp, n_events=n_events)

    B = b * n_chips
    ids = jax.block_until_ready(jax.jit(
        lambda k: jax.random.randint(k, (B, S), 0, V))(rng))
    tgt = jax.block_until_ready(jax.jit(
        lambda k: jax.random.randint(k, (B, S), 0, V))(
            jax.random.fold_in(rng, 1)))

    def run(mdl, schedule):
        variables = mdl.init(rng, jnp.asarray(ids[:1]))
        prune_at = set(schedule.prune_steps()) if schedule else set()

        def build(vars_):
            step = ShardedParameterStep(mdl, crit,
                                        Adam(learning_rate=1e-4), mesh,
                                        vars_)
            return step, step.shard_batch(ids), step.shard_batch(tgt)

        step, x_dev, y_dev = build(variables)
        trajectory = []  # (sparsity, loss) at each level's last step
        cur_sp = 0.0
        t0 = None
        loss = None
        for i in range(total):
            if i in prune_at:
                trajectory.append((cur_sp, float(np.asarray(loss))))
                cur_sp = schedule.sparsity_at(i)
                v = step.get_variables()
                prune_model_to_sparsity(
                    mdl, v, cur_sp,
                    sample_inputs=(jnp.asarray(ids[:1]),))
                with expected_compile():
                    step, x_dev, y_dev = build(v)
            loss = step.train_step_device(i, rng, x_dev, y_dev)
            if i == total - tail:  # steady-sparsity timing window
                float(np.asarray(loss))  # sync before the clock starts
                t0 = time.perf_counter()
        final = float(np.asarray(loss))
        dt = (time.perf_counter() - t0) / (tail - 1) if tail > 1 else 0.0
        trajectory.append((cur_sp, final))
        assert np.isfinite(final), final
        tps = B * S / dt / n_chips if dt > 0 else None
        return tps, final, trajectory

    dense_model = Transformer(vocab_size=V, hidden_size=D, num_heads=H,
                              ffn_size=4 * D, num_layers=L, dropout=0.0,
                              mode="lm")
    sparse_model = Transformer(vocab_size=V, hidden_size=D, num_heads=H,
                               ffn_size=4 * D, num_layers=L, dropout=0.0,
                               mode="lm", ffn_sparsity=target,
                               sparse_block=block)
    tps_d, loss_d, _ = run(dense_model, None)
    tps_s, loss_s, traj = run(sparse_model, sched)
    rec = {
        "ffn_sparsity": target,
        "sparse_block": list(block),
        "schedule": {"warmup_steps": warmup, "ramp_steps": ramp,
                     "n_events": n_events, "steps": total},
        "tokens_per_sec_chip_dense": round(tps_d, 1) if tps_d else None,
        "tokens_per_sec_chip_sparse": round(tps_s, 1) if tps_s else None,
        # same tokens, same dense-equivalent FLOPs/token: the
        # dense-equivalent MFU ratio IS the throughput ratio
        "mfu_vs_dense": round(tps_s / tps_d, 3) if tps_s and tps_d
        else None,
        "loss_dense": round(loss_d, 5),
        "loss_sparse": round(loss_s, 5),
        "loss_vs_sparsity": [{"sparsity": round(sp, 4),
                              "loss": round(l, 5)}
                             for sp, l in traj],
    }
    if peak and tps_d and tps_s:
        rec["mfu_dense"] = round(tps_d * fpt / peak, 4)
        rec["mfu_sparse_dense_equiv"] = round(tps_s * fpt / peak, 4)
    return rec


def main():
    from bigdl_tpu.nn.attention import Transformer
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import Adam
    from bigdl_tpu.optim.train_step import ShardedParameterStep
    from bigdl_tpu.runtime.engine import enable_compile_cache
    from bigdl_tpu.runtime.mesh import MeshSpec, build_mesh

    enable_compile_cache()
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    tiny = os.environ.get("BENCH_LM_TINY") == "1" or not on_tpu
    n_chips = len(devices)
    mesh = build_mesh(MeshSpec(), devices=devices)

    if tiny:
        L, D, H, V, S, batches, steps = 2, 64, 4, 512, 128, (2,), 2
    else:
        L, D, H, V, S, batches, steps = 12, 768, 12, 32768, 1024, \
            (4, 8, 16), 10

    model = Transformer(vocab_size=V, hidden_size=D, num_heads=H,
                        ffn_size=4 * D, num_layers=L, dropout=0.0,
                        mode="lm")
    crit = CrossEntropyCriterion()
    rng = jax.random.PRNGKey(0)
    n_params = None

    # per-batch lowering handles for cost analysis: the jitted step plus
    # ShapeDtypeStructs of its args — keeps NO device buffers alive, and
    # every sweep point stays analyzable even when the best batch is not
    # the last one measured
    last_build = {}

    def measure(batch_per_chip):
        nonlocal n_params
        B = batch_per_chip * n_chips
        ids = jax.block_until_ready(jax.jit(
            lambda k: jax.random.randint(k, (B, S), 0, V))(rng))
        tgt = jax.block_until_ready(jax.jit(
            lambda k: jax.random.randint(k, (B, S), 0, V))(
                jax.random.fold_in(rng, 1)))
        variables = model.init(rng, jnp.asarray(ids[:1]))
        if n_params is None:
            n_params = int(sum(np.prod(l.shape) for l in
                               jax.tree_util.tree_leaves(
                                   variables["params"])))
        step = ShardedParameterStep(model, crit, Adam(learning_rate=1e-4),
                                    mesh, variables)
        x_dev = step.shard_batch(ids)
        y_dev = step.shard_batch(tgt)

        def sds(t):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                               jnp.asarray(a).dtype), t)

        ema_in = step.ema_flat if step.ema_flat is not None \
            else step._ema_dummy
        last_build[batch_per_chip] = (step._train, (
            sds(step.flat_params), sds(ema_in), sds(step.opt_state),
            sds(step.model_state), sds(jnp.asarray(0, jnp.int32)),
            sds(rng), sds(x_dev), sds(y_dev),
            sds(jnp.asarray(1.0, jnp.float32))))
        loss = step.train_step_device(0, rng, x_dev, y_dev)
        float(np.asarray(loss))  # block on the warm-up VALUE
        t0 = time.perf_counter()
        for i in range(steps):
            loss = step.train_step_device(i + 1, rng, x_dev, y_dev)
        final = float(np.asarray(loss))
        dt = (time.perf_counter() - t0) / steps
        assert np.isfinite(final), final
        return B * S / dt / n_chips, dt

    sweep = {}
    best = (0.0, None, None)
    for b in batches:
        try:
            tps, st = measure(b)
        except Exception as e:
            sweep[str(b)] = f"failed: {type(e).__name__}"
            continue
        sweep[str(b)] = round(tps, 1)
        if tps > best[0]:
            best = (tps, b, st)

    if best[1] is None:
        print(json.dumps({"metric": "transformer_lm_train_throughput",
                          "value": None, "unit": "tokens/sec/chip",
                          "error": "all batch sizes failed",
                          "sweep": sweep}))
        return 1

    tps, b, st = best
    fpt = _analytic_flops_per_token(L, D, S, V)
    flops_source = "analytic_3x_fwd_causal"
    # prefer XLA's own cost analysis of the compiled step (exact,
    # includes the attention/vocab matmuls as lowered)
    try:
        train_fn, abstract_args = last_build[b]
        cost = train_fn.lower(*abstract_args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        f = float(cost.get("flops", -1))
        if f > 0:
            # cost analysis sees the per-device SPMD module: divide by
            # PER-DEVICE tokens (b is already batch-per-chip)
            fpt = f / (b * S)
            flops_source = "xla_cost_analysis"
    except Exception:
        pass
    achieved = tps * fpt
    peak = _peak_flops(devices[0].device_kind) if on_tpu else None
    mfu = round(achieved / peak, 4) if peak else None
    out = {
        "metric": "transformer_lm_train_throughput",
        "value": round(tps, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,  # reference published no transformer numbers
        "model": f"decoder-only L{L} d{D} h{H} vocab{V}",
        "n_params": n_params,
        "seq_len": S,
        "batch_per_chip": b,
        "steps": steps,
        "n_chips": n_chips,
        "step_time_ms": round(st * 1e3, 2),
        "device_kind": devices[0].device_kind,
        "flops_per_token": fpt,
        "flops_source": flops_source,
        "achieved_flops_per_chip": round(achieved, 2),
        "peak_bf16_flops": peak,
        "mfu": mfu,
        "tiny_smoke": tiny,
        "batch_sweep_tokens_per_sec_chip": sweep,
    }
    if mfu is not None and mfu > 1.0:
        out["suspect"] = True

    # headline first: the consumer parses the LAST stdout line, so if the
    # optional A/B below is killed mid-run (timeout/OOM) this line is the
    # row of record — the A/B can only enrich, never sink it
    print(json.dumps(out), flush=True)

    if "--sparse" in sys.argv:
        # block-sparse FFN A/B (docs/performance.md §Block-sparse FFN):
        # dense control vs BLaST schedule (dense warmup -> magnitude
        # block pruning to target sparsity), SAME data/seed/step count.
        # Reports MFU-vs-dense at the final sparsity plus the
        # loss-vs-sparsity trajectory.  Runs on the CPU tiny smoke too —
        # the interpret-mode kernel is the same code path Mosaic compiles.
        try:
            out["sparse"] = _sparse_ab(
                b, tiny, n_chips, mesh, crit, rng, V, S, L, D, H,
                fpt, peak)
        except Exception as e:  # noqa: BLE001 — enrich, never sink
            out["sparse_error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps(out), flush=True)

    prior_flash = os.environ.get("BIGDL_TPU_FLASH")
    if (on_tpu and not tiny and prior_flash != "0"
            and os.environ.get("BENCH_LM_AB", "1") != "0"):
        # flash-vs-XLA A/B at the winning batch: the MHA layers auto-
        # select the Pallas kernel on TPU; BIGDL_TPU_FLASH=0 re-traces
        # through XLA attention.  Records the honest comparison the
        # kernel layer must win to stay the default.
        # Skipped when the operator already demoted the kernel (the
        # headline would itself be the XLA path — nothing to compare).
        try:
            os.environ["BIGDL_TPU_FLASH"] = "0"
            tps_xla, st_xla = measure(b)
            out["tokens_per_sec_chip_xla_attention"] = round(tps_xla, 1)
            out["flash_vs_xla_speedup"] = round(tps / tps_xla, 3)
        except Exception as e:
            out["xla_attention_ab_error"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            if prior_flash is None:
                os.environ.pop("BIGDL_TPU_FLASH", None)
            else:
                os.environ["BIGDL_TPU_FLASH"] = prior_flash
        print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
