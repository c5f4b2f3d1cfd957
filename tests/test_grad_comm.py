"""Quantized + bucketed gradient collectives (docs/parallelism.md
§Gradient compression & bucketed overlap).

Tier-1 on a 2-device CPU mesh (4 devices where the DCN hop needs a
2x2): blockwise-int8 primitives, the all_to_all reduce-scatter vs the
f32 oracle, int8-vs-fp32 LOSS PARITY (the acceptance test), bucketed ==
monolithic trajectories, the honest wire-dtype ledger, the bf16_grads
deprecation shim and the overlap audit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.nn.module import Sequential
from bigdl_tpu.optim.optim_method import SGD, Adam
from bigdl_tpu.optim.train_step import ShardedParameterStep
from bigdl_tpu.parallel import collectives
from bigdl_tpu.runtime.mesh import AXIS_DATA, MeshSpec, build_mesh, \
    shard_map
from jax.sharding import PartitionSpec as P


def _mesh(n):
    return build_mesh(MeshSpec(data=n), devices=jax.devices()[:n])


def _data(n=64, d=8, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, d).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    return x, y


def _step(mesh, x, hidden=16, lr=0.2, seed=0, optim=None, **kw):
    model = Sequential([nn.Linear(x.shape[1], hidden), nn.ReLU(),
                        nn.Linear(hidden, 2)])
    variables = model.init(jax.random.PRNGKey(seed), jnp.asarray(x[:2]))
    return ShardedParameterStep(
        model, nn.CrossEntropyCriterion(),
        optim or SGD(learning_rate=lr, momentum=0.9), mesh, variables,
        **kw)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def test_blockwise_quant_roundtrip_error_bound():
    """Dequantized values sit within half a quantization step of the
    original, per block (symmetric abs-max: step = blockmax/127)."""
    from bigdl_tpu.ops.quantized import (dequantize_blockwise,
                                         quantize_blockwise)

    rs = np.random.RandomState(1)
    x = (rs.randn(3, 256) * np.array([1e-3, 1.0, 50.0])[:, None]) \
        .astype(np.float32)
    q, scales = quantize_blockwise(jnp.asarray(x), 64)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert scales.shape == (3, 4)
    back = np.asarray(dequantize_blockwise(q, scales))
    blockmax = np.abs(x.reshape(3, 4, 64)).max(-1)
    tol = (blockmax / 127.0 * 0.5 + 1e-9).repeat(64, -1).reshape(x.shape)
    assert np.all(np.abs(back - x) <= tol + 1e-6 * np.abs(x))

    with pytest.raises(ValueError, match="not a multiple"):
        quantize_blockwise(jnp.zeros((10,)), 64)


def test_quantized_reduce_scatter_matches_fp32_oracle():
    """The all_to_all int8 cycle equals psum_scatter up to blockwise
    quantization error, on a real 4-device axis."""
    n = 4
    mesh = _mesh(n)
    rs = np.random.RandomState(2)
    # per-device distinct gradients, global shape (n, n*w)
    w = 96
    g = rs.randn(n, n * w).astype(np.float32)

    def body(gl):
        # gl: this device's (1, n*w) row -> flat (n*w,)
        flat = gl.reshape(-1)
        ref = jax.lax.psum_scatter(flat, AXIS_DATA, scatter_dimension=0,
                                   tiled=True)
        quant = collectives.reduce_scatter_quantized(
            flat.reshape(n, w), AXIS_DATA, block=32)
        return ref[None], quant[None]

    fn = jax.jit(shard_map(body, mesh=mesh,
                           in_specs=P(AXIS_DATA),
                           out_specs=(P(AXIS_DATA), P(AXIS_DATA))))
    ref, quant = fn(jnp.asarray(g))
    ref, quant = np.asarray(ref).ravel(), np.asarray(quant).ravel()
    # n sources, each within half a step of its own blockmax (<= global
    # abs max / 127 * 0.5 per source)
    tol = n * (np.abs(g).max() / 127.0)
    np.testing.assert_allclose(quant, ref, atol=tol)
    # and it is a real reduction: matches the numpy sum too
    np.testing.assert_allclose(
        ref, g.sum(0).reshape(n, w).ravel(), rtol=1e-5, atol=1e-5)


def test_quantized_psum_matches_and_replicates():
    """psum_quantized equals the f32 psum within tolerance and returns
    the bit-identical vector on EVERY rank (the no-param-bytes-over-DCN
    invariant)."""
    n = 4
    mesh = _mesh(n)
    rs = np.random.RandomState(3)
    v = rs.randn(n, 70).astype(np.float32)  # 70: not block/n aligned

    def body(vl):
        vec = vl.reshape(-1)
        ref = jax.lax.psum(vec, AXIS_DATA)
        quant = collectives.psum_quantized(vec, AXIS_DATA, n, block=16)
        return ref[None], quant[None]

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P(AXIS_DATA),
                           out_specs=(P(AXIS_DATA), P(AXIS_DATA))))
    out = fn(jnp.asarray(v))
    ref, quant = np.asarray(out[0]), np.asarray(out[1])
    tol = n * (np.abs(v).max() / 127.0) + np.abs(v.sum(0)).max() / 127.0
    for r in range(n):
        np.testing.assert_allclose(quant[r], ref[0], atol=tol)
        # bit-identical across ranks: every rank gathered the same int8
        np.testing.assert_array_equal(quant[r], quant[0])


def test_bucket_columns_and_wire_bytes():
    cols = collectives.bucket_columns(1000, 4, bucket_bytes=None)
    assert cols == [(0, 1000)]
    cols = collectives.bucket_columns(1000, 4, bucket_bytes=1600,
                                      wire_bytes=4.0)
    assert cols[0] == (0, 100) and cols[-1][1] == 1000
    assert all(c1 - c0 <= 100 for c0, c1 in cols)
    # int8 buckets align to the quantization block
    cols = collectives.bucket_columns(1000, 4, bucket_bytes=1600,
                                      wire_bytes=1.0, block=64)
    assert all((c1 - c0) % 64 == 0 for c0, c1 in cols[:-1])
    # estimators: fp32/bf16 payloads, int8 payload + scales + padding
    assert collectives.rs_wire_bytes(100, 4, "fp32") == 1600
    assert collectives.rs_wire_bytes(100, 4, "bf16") == 800
    assert collectives.rs_wire_bytes(100, 4, "int8", block=64) == \
        4 * 128 + 4 * 2 * 4
    assert collectives.rs_wire_bytes(100, 1, "fp32") == 0
    assert collectives.psum_wire_bytes(100, 2, "fp32") == 800
    # per-chunk clamp: block shrinks to ceil(100/2)=50, no padding blowup
    assert collectives.psum_wire_bytes(100, 2, "int8", block=64) == \
        2 * (2 * 50 + 2 * 1 * 4)
    # a tiny shard never pays more wire than fp32 (the clamp invariant)
    assert collectives.rs_wire_bytes(77, 8, "int8", block=1024) < \
        collectives.rs_wire_bytes(77, 8, "fp32")


# ---------------------------------------------------------------------------
# the train-step cycle
# ---------------------------------------------------------------------------

def test_loss_parity_int8_vs_fp32():
    """ACCEPTANCE (ISSUE 11): training with grad_comm="int8" lands within
    tolerance of the fp32 sync on the same data/seed — 2-device CPU
    mesh, both runs converging."""
    mesh = _mesh(2)
    x, y = _data()
    rng = jax.random.PRNGKey(1)
    fp32 = _step(mesh, x)
    int8 = _step(mesh, x, grad_comm="int8", quant_block=64)
    lf = [float(fp32.train_step(i, rng, x, y)) for i in range(30)]
    lq = [float(int8.train_step(i, rng, x, y)) for i in range(30)]
    assert lf[-1] < 0.5 * lf[0], "fp32 baseline failed to converge"
    assert lq[-1] < 0.5 * lq[0], "int8 run failed to converge"
    tol = max(0.05 * abs(lf[-1]), 0.02)
    assert abs(lq[-1] - lf[-1]) <= tol, (lq[-1], lf[-1], tol)


def test_bucketed_matches_monolithic_fp32():
    """Bucketing changes ONLY the collective structure: the fp32
    trajectory and final params match the monolithic sync (shard
    ownership and optimizer-state layout are identical)."""
    mesh = _mesh(2)
    x, y = _data()
    rng = jax.random.PRNGKey(1)
    mono = _step(mesh, x, optim=Adam(learning_rate=0.02))
    buck = _step(mesh, x, optim=Adam(learning_rate=0.02),
                 comm_bucket_bytes=256)
    assert buck.comm_buckets > 1
    lm = [float(mono.train_step(i, rng, x, y)) for i in range(10)]
    lb = [float(buck.train_step(i, rng, x, y)) for i in range(10)]
    np.testing.assert_allclose(lb, lm, rtol=2e-4, atol=1e-6)
    pm = jax.tree_util.tree_leaves(mono.get_variables()["params"])
    pb = jax.tree_util.tree_leaves(buck.get_variables()["params"])
    for a, b in zip(pm, pb):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=1e-6)


def test_int8_bucketed_bundle_with_clip_and_ema():
    """The quantized bucketed cycle composes with the K-step bundle,
    gradient clipping, EMA and accumulation — finite losses, positive
    grad norms, K=1+2 byte-identical to K=3."""
    from bigdl_tpu.optim.train_step import GradientClipping

    mesh = _mesh(2)
    x, y = _data()

    def make():
        return _step(mesh, x, grad_comm="int8", quant_block=32,
                     comm_bucket_bytes=256, ema_decay=0.9, accum_steps=2,
                     clip=GradientClipping(l2_norm=1.0))

    a, b = make(), make()
    a.set_step_seed(3)
    b.set_step_seed(3)
    xd, yd = a.shard_batch(x), a.shard_batch(y)
    la1, g1, _ = a.train_bundle_device(0, [xd], [yd])
    la2, _, _ = a.train_bundle_device(1, [xd, xd], [yd, yd])
    lb, gb, _ = b.train_bundle_device(0, [xd, xd, xd], [yd, yd, yd])
    traj = np.concatenate([np.ravel(la1), np.ravel(la2)])
    np.testing.assert_array_equal(traj.astype(np.float32),
                                  np.ravel(lb).astype(np.float32))
    assert np.all(np.isfinite(np.ravel(lb)))
    assert np.all(np.ravel(gb) > 0)


def test_int8_over_dcn_hop():
    """Multislice: the int8 cycle runs the quantized hierarchical psum
    over the dcn axis, trains in parity with fp32, and the DCN ledger
    shrinks accordingly."""
    mesh = build_mesh(MeshSpec(data=2, dcn_data=2),
                      devices=jax.devices()[:4])
    x, y = _data()
    rng = jax.random.PRNGKey(1)
    fp32 = _step(mesh, x)
    int8 = _step(mesh, x, grad_comm="int8", quant_block=32)
    lf = [float(fp32.train_step(i, rng, x, y)) for i in range(20)]
    lq = [float(int8.train_step(i, rng, x, y)) for i in range(20)]
    assert abs(lq[-1] - lf[-1]) <= max(0.05 * abs(lf[-1]), 0.02)
    assert 0 < int8.dcn_bytes_per_step < fp32.dcn_bytes_per_step
    assert int8.grad_sync_ici_bytes_per_step < \
        fp32.grad_sync_ici_bytes_per_step


def test_ledger_reports_actual_wire_dtype():
    """The collective-bytes ledger counts what actually crosses the wire:
    bf16 halves the gradient bytes, int8 counts payload + per-block f32
    scales (+ padding), and the param gather stays f32 in every mode."""
    from bigdl_tpu.obs.cost import collective_ledger

    mesh = _mesh(2)
    x, _ = _data(d=8)
    fp32 = _step(mesh, x, hidden=256)
    bf16 = _step(mesh, x, hidden=256, grad_comm="bf16")
    int8 = _step(mesh, x, hidden=256, grad_comm="int8", quant_block=64)
    n_pad, shard = fp32.n_pad, fp32.shard_size

    assert fp32.grad_sync_ici_bytes_per_step == n_pad * 4
    assert bf16.grad_sync_ici_bytes_per_step == n_pad * 2
    wq = -(-shard // 64) * 64
    assert int8.grad_sync_ici_bytes_per_step == \
        2 * wq + 2 * (wq // 64) * 4
    for s in (fp32, bf16, int8):
        assert s.param_sync_ici_bytes_per_step == n_pad * 4
        led = collective_ledger(s)
        assert led["grad_comm"] == s.grad_comm
        assert led["grad_ici_bytes_per_step"] == \
            s.grad_sync_ici_bytes_per_step
        assert led["param_ici_bytes_per_step"] == n_pad * 4
        assert led["ici_bytes_per_step"] == \
            led["grad_ici_bytes_per_step"] + led["param_ici_bytes_per_step"]
    # the acceptance ratio on a realistically-sized layer stack: >= 3x
    # fewer gradient-sync bytes than fp32
    assert fp32.grad_sync_ici_bytes_per_step / \
        int8.grad_sync_ici_bytes_per_step >= 3.0


def test_loss_parity_param_comm_int8():
    """ACCEPTANCE (ISSUE 19): the int8 delta param gather
    (``param_comm="int8"``) lands within the same loss-parity tolerance
    as the fp32 gather, alone and composed with the quantized gradient
    wire."""
    mesh = _mesh(2)
    x, y = _data()
    rng = jax.random.PRNGKey(1)
    fp32 = _step(mesh, x)
    q = _step(mesh, x, param_comm="int8", quant_block=64)
    lf = [float(fp32.train_step(i, rng, x, y)) for i in range(30)]
    lq = [float(q.train_step(i, rng, x, y)) for i in range(30)]
    assert lf[-1] < 0.5 * lf[0], "fp32 baseline failed to converge"
    assert lq[-1] < 0.5 * lq[0], "param_comm=int8 failed to converge"
    assert abs(lq[-1] - lf[-1]) <= max(0.05 * abs(lf[-1]), 0.02)
    # the fully-quantized cycle (int8 gradients AND int8 param deltas)
    full = _step(mesh, x, grad_comm="int8", param_comm="int8",
                 quant_block=64)
    lfull = [float(full.train_step(i, rng, x, y)) for i in range(30)]
    assert lfull[-1] < 0.5 * lfull[0], "fully-quantized cycle diverged"
    assert abs(lfull[-1] - lf[-1]) <= max(0.05 * abs(lf[-1]), 0.03)


def test_int8_wire_3x_fewer_grad_bytes_on_two_hop_resnet50():
    """A count from shapes: DP ResNet-50 (25.6 M parameters) on a
    dcn_data=2 x data=4 mesh moves >= 3x fewer gradient-sync bytes over
    both hops (ICI + DCN) under ``grad_comm="int8"`` than under fp32,
    scales and block padding included; bf16 exactly halves them."""
    from bigdl_tpu.models.resnet import resnet50

    model = resnet50(classes=1000)
    shapes = jax.eval_shape(
        lambda r, x: model.init(r, x), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32))["params"]
    n_params = sum(int(np.prod(s.shape))
                   for s in jax.tree_util.tree_leaves(shapes))

    def grad_bytes(mode):
        led = collectives.layout_ledger(n_params, ndev=4, dcn=2, mode=mode,
                                        bucket_bytes=4 << 20)
        assert led["grad_sync_dcn_bytes_per_step"] > 0  # both hops live
        return (led["grad_sync_ici_bytes_per_step"]
                + led["grad_sync_dcn_bytes_per_step"])

    fp32 = grad_bytes("fp32")
    assert fp32 == n_params * 4 + n_params * 4 // 2
    assert grad_bytes("bf16") * 2 == fp32
    assert fp32 / grad_bytes("int8") >= 3.0


def test_param_comm_ledger_and_validation():
    """param_comm="int8" prices the param gather in its actual wire
    dtype (payload + scales), fp32 stays the classic n_pad * 4, the
    pure layout math mirrors the engine, bad modes are rejected."""
    from bigdl_tpu.obs.cost import collective_ledger

    mesh = _mesh(2)
    x, _ = _data(d=8)
    fp32 = _step(mesh, x, hidden=256)
    q = _step(mesh, x, hidden=256, param_comm="int8", quant_block=64)
    n_pad, shard = fp32.n_pad, fp32.shard_size
    assert fp32.param_sync_ici_bytes_per_step == n_pad * 4
    wq = -(-shard // 64) * 64
    assert q.param_sync_ici_bytes_per_step == 2 * wq + 2 * (wq // 64) * 4
    assert q.param_sync_ici_bytes_per_step < \
        fp32.param_sync_ici_bytes_per_step / 3
    led = collective_ledger(q)
    assert led["param_comm"] == "int8"
    assert led["param_ici_bytes_per_step"] == \
        q.param_sync_ici_bytes_per_step
    assert led["ici_bytes_per_step"] == \
        led["grad_ici_bytes_per_step"] + led["param_ici_bytes_per_step"]
    ll = collectives.layout_ledger(fp32.n_real, 2, param_comm="int8",
                                   block=64)
    assert ll["param_comm"] == "int8"
    assert ll["param_sync_ici_bytes_per_step"] == \
        q.param_sync_ici_bytes_per_step
    # estimator: fp32 payload, int8 payload + scales + block padding
    assert collectives.ag_wire_bytes(100, 4, "fp32") == 1600
    assert collectives.ag_wire_bytes(100, 4, "int8", block=64) == \
        4 * 128 + 4 * 2 * 4
    assert collectives.ag_wire_bytes(100, 1, "int8") == 0
    with pytest.raises(ValueError, match="param_comm"):
        _step(mesh, x, param_comm="bf16")
    assert _step(mesh, x, param_comm=" INT8 ").param_comm == "int8"


def test_param_comm_overlap_probe():
    """The comm-only probe mirrors the int8 delta gather's wire shape,
    so the overlap audit times the same collectives the step runs."""
    mesh = _mesh(2)
    x, y = _data()
    s = _step(mesh, x, grad_comm="int8", param_comm="int8",
              quant_block=32)
    xd, yd = s.shard_batch(x), s.shard_batch(y)
    ov = s.measure_overlap(xd, yd, steps=2)
    assert ov["collective_s"] > 0
    assert 0.0 <= ov["overlap_efficiency"] <= 1.0
    assert np.isfinite(float(s.train_step(0, jax.random.PRNGKey(0),
                                          x, y)))


def test_invalid_grad_comm_rejected():
    mesh = _mesh(2)
    x, _ = _data()
    with pytest.raises(ValueError, match="grad_comm"):
        _step(mesh, x, grad_comm="int4")
    # spellings normalize like BIGDL_TPU_GRAD_COMM does, at every entry
    assert _step(mesh, x, grad_comm="INT8").grad_comm == "int8"
    assert _step(mesh, x, grad_comm=" Bf16 ").grad_comm == "bf16"


def test_bucketing_rejects_non_elementwise_state():
    """Per-bucket updates slice every optimizer-state leaf like the param
    slice; an OptimMethod whose state is not strictly per-element must be
    rejected LOUDLY when bucketing is on (it would silently diverge)."""
    from bigdl_tpu.optim.optim_method import OptimMethod

    class ScalarStateSGD(OptimMethod):
        lr = 0.1

        def init_state(self, params):
            return {"gsq_mean": jnp.asarray(0.0, jnp.float32)}

        def update(self, step, grads, params, state):
            s = 0.9 * state["gsq_mean"] + 0.1 * jnp.mean(grads * grads)
            return params - self.lr * grads, {"gsq_mean": s}

    mesh = _mesh(2)
    x, _ = _data()
    with pytest.raises(ValueError, match="per-element"):
        _step(mesh, x, optim=ScalarStateSGD(), comm_bucket_bytes=256)


def test_measure_overlap_audit():
    """The overlap audit returns a sane decomposition: all timings
    positive, exposed <= total collective, efficiency in [0, 1]."""
    mesh = _mesh(2)
    x, y = _data()
    s = _step(mesh, x, grad_comm="int8", quant_block=32,
              comm_bucket_bytes=256)
    xd, yd = s.shard_batch(x), s.shard_batch(y)
    ov = s.measure_overlap(xd, yd, steps=3)
    assert ov["step_s"] > 0 and ov["compute_s"] > 0
    assert ov["collective_s"] > 0
    assert 0.0 <= ov["overlap_efficiency"] <= 1.0
    assert ov["exposed_collective_s"] >= 0.0
    assert ov["grad_comm"] == "int8" and ov["comm_buckets"] >= 1
    # the audit never consumes training state: stepping still works
    assert np.isfinite(float(s.train_step(0, jax.random.PRNGKey(0), x, y)))


# ---------------------------------------------------------------------------
# deprecation shim + config plumbing
# ---------------------------------------------------------------------------

def test_bf16_grads_deprecation_shim():
    """bf16_grads=True keeps working: mapped to grad_comm="bf16" with a
    DeprecationWarning, same halved collective bytes, and the legacy
    .bf16_grads attribute still reads True for old callers."""
    mesh = _mesh(2)
    x, y = _data()
    with pytest.warns(DeprecationWarning, match="bf16_grads"):
        shim = _step(mesh, x, bf16_grads=True)
    assert shim.grad_comm == "bf16" and shim.bf16_grads
    modern = _step(mesh, x, grad_comm="bf16")
    assert shim.collective_bytes_per_step == \
        modern.collective_bytes_per_step
    # explicit grad_comm wins over the legacy flag
    with pytest.warns(DeprecationWarning):
        both = _step(mesh, x, bf16_grads=True, grad_comm="int8")
    assert both.grad_comm == "int8" and not both.bf16_grads
    assert np.isfinite(float(shim.train_step(0, jax.random.PRNGKey(0),
                                             x, y)))


def test_optimizer_grad_comm_resolution():
    """Optimizer-level resolution: explicit grad_comm > deprecated
    bf16_grads (warned) > EngineConfig.grad_comm > fp32."""
    from bigdl_tpu import optim
    from bigdl_tpu.data import ArrayDataSet
    from bigdl_tpu.runtime.engine import EngineConfig

    x, y = _data()
    opt = optim.Optimizer(Sequential([nn.Linear(8, 2)]),
                          ArrayDataSet(x, y), nn.CrossEntropyCriterion())
    cfg = EngineConfig()
    assert opt._resolved_grad_comm(cfg) == "fp32"
    cfg.grad_comm = "int8"
    assert opt._resolved_grad_comm(cfg) == "int8"
    opt.bf16_grads = True
    with pytest.warns(DeprecationWarning, match="bf16_grads"):
        assert opt._resolved_grad_comm(cfg) == "bf16"
    opt.grad_comm = "int8"
    with pytest.warns(DeprecationWarning, match="wins"):
        assert opt._resolved_grad_comm(cfg) == "int8"


def test_engineconfig_grad_comm_env(monkeypatch):
    from bigdl_tpu.runtime.engine import EngineConfig

    monkeypatch.setenv("BIGDL_TPU_GRAD_COMM", "INT8")
    monkeypatch.setenv("BIGDL_TPU_COMM_BUCKET_BYTES", "1048576")
    cfg = EngineConfig.from_env()
    assert cfg.grad_comm == "int8"
    assert cfg.comm_bucket_bytes == 1048576


def test_optimizer_int8_run_exports_gauges(monkeypatch):
    """End-to-end driver run under grad_comm="int8": converges, and one
    /metrics snapshot carries the honest wire ledger (grad vs param
    split, bucket count) plus the overlap-audit gauges when the env
    opts in."""
    from bigdl_tpu import optim
    from bigdl_tpu.data import ArrayDataSet

    monkeypatch.setenv("BIGDL_TPU_MEASURE_OVERLAP", "1")
    x, y = _data(n=64)
    model = Sequential([nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2),
                        nn.LogSoftMax()])
    opt = optim.Optimizer(model, ArrayDataSet(x, y),
                          nn.ClassNLLCriterion(), batch_size=32)
    opt.grad_comm = "int8"
    opt.quant_block = 64
    opt.set_optim_method(optim.Adam(learning_rate=1e-2))
    opt.set_end_when(optim.Trigger.max_iteration(6))
    opt.log_every = 3
    opt.optimize()
    g = opt.metrics.snapshot()["gauges"]
    assert g["train.grad_comm_buckets"] >= 1
    grad_b = g["train.collective_grad_ici_bytes_per_step"]
    param_b = g["train.collective_param_ici_bytes_per_step"]
    assert 0 < grad_b < param_b  # int8 payload < f32 gather
    assert g["train.collective_ici_bytes_per_step"] == grad_b + param_b
    assert 0.0 <= g["train.comm_overlap_efficiency"] <= 1.0
    assert g["train.comm_exposed_collective_s"] >= 0.0
