"""The hyper-connection's stream-wide products under their one-pass backward
(``ops/hc_mix.py``, a Pallas kernel in interpret mode here) against
``jax.vjp`` of the plain expressions (``nn/hyper_connection.py read_out``,
``write_back``): every cotangent, the tile rule and its fallback, the
forward's bits, the pass under ``jax.checkpoint``, a tiny model's gradients,
and the counters that say which way a sublayer was traced."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from bigdl_tpu.nn import hyper_connection as hc_mod
from bigdl_tpu.nn.hyper_connection import (HyperConnection, read_out,
                                           write_back)
from bigdl_tpu.ops.hc_mix import can_mix, mix_backward, mix_blocks
from tests.test_hc_mla_moe import CFG_FILE, close, ids_batch

fam = harness.load_module("families", "hc_mla_moe_lm")
REL = 1e-5      # float32 sums over d in another order


def rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.maximum(jnp.abs(b).max(), 1e-30))


def operands(n, tokens, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    scale = jnp.linspace(0.5, 2.0, n)[:, None, None]
    return dict(
        x=jax.random.normal(ks[0], (n, tokens, d)) * scale,
        y=jax.random.normal(ks[1], (tokens, d)),
        res=jax.random.uniform(ks[2], (n, n, tokens)),
        post=2 * jax.random.uniform(ks[3], (n, tokens)),
        pre=jax.random.uniform(ks[4], (n, tokens)),
        g=jax.random.normal(ks[5], (n, tokens, d)),
        du=jax.random.normal(ks[6], (tokens, d)),
        add=jax.random.normal(ks[7], (n, tokens, d)))


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The module decides by the backend; the kernel itself still asks
    ``ops.common`` and runs in interpret mode here."""
    monkeypatch.setattr(hc_mod, "on_tpu", lambda: True)


# -- the kernel against jax.vjp of the plain expressions ------------------------

@pytest.mark.parametrize("tokens", [64, 200])
@pytest.mark.parametrize("d", [128, 384])
@pytest.mark.parametrize("n", [2, 4])
def test_write_back_cotangents(n, d, tokens):
    o = operands(n, tokens, d)
    want = jax.vjp(write_back, o["x"], o["y"], o["res"], o["post"])[1](o["g"])
    dx, dy, dc = mix_backward(
        jnp.concatenate([o["res"], o["post"][:, None]], 1), o["g"], o["x"],
        o["y"])
    assert dc.shape == (n, n + 1, tokens)       # tokens along the lanes
    for got, ref in zip((dx, dy, dc[:, :n], dc[:, n]), want):
        assert got.dtype == jnp.float32 and rel(got, ref) < REL


@pytest.mark.parametrize("tokens", [64, 200])
@pytest.mark.parametrize("d", [128, 384])
@pytest.mark.parametrize("n", [2, 4])
def test_read_out_cotangents_and_the_cotangent_it_adds(n, d, tokens):
    o = operands(n, tokens, d, seed=1)
    dx_ref, dh_ref = jax.vjp(read_out, o["x"], o["pre"])[1](o["du"])
    dx, dy, dh = mix_backward(o["pre"][None], o["du"][None], o["x"])
    assert dy is None and dh.shape == (1, n, tokens)
    assert rel(dx, dx_ref) < REL and rel(dh[0], dh_ref) < REL
    dx, _, dh = mix_backward(o["pre"][None], o["du"][None], o["x"],
                             add=o["add"])
    assert rel(dx, dx_ref + o["add"]) < REL and rel(dh[0], dh_ref) < REL


def test_more_than_one_chunk_of_d_and_tile_of_tokens():
    """Accumulators carried over 3 chunks of ``d``; 4 token tiles."""
    o = operands(4, 64, 384, seed=2)
    want = jax.vjp(write_back, o["x"], o["y"], o["res"], o["post"])[1](o["g"])
    dx, dy, dc = mix_backward(
        jnp.concatenate([o["res"], o["post"][:, None]], 1), o["g"], o["x"],
        o["y"], block_t=16, block_d=128)
    for got, ref in zip((dx, dy, dc[:, :4], dc[:, 4]), want):
        assert rel(got, ref) < REL


# -- the tile rule ---------------------------------------------------------------

@pytest.mark.parametrize("tokens,d,slabs,expected", [
    (4096, 3584, 14, {"block_t": 256, "block_d": 512}),    # the Xing cell
    (4096, 3584, 13, {"block_t": 256, "block_d": 512}),
    (8192, 2048, 14, {"block_t": 256, "block_d": 512}),
    (200, 384, 14, {"block_t": 8, "block_d": 384}),
    (64, 128, 8, {"block_t": 64, "block_d": 128}),
    (64, 64, 14, None),             # d under a lane tile
    (64, 192, 14, None),            # d not a multiple of 128
    (63, 128, 14, None),            # tokens not a multiple of 8
])
def test_tile_rule(tokens, d, slabs, expected):
    assert mix_blocks(tokens, d, slabs) == expected
    assert mix_blocks(tokens, d, slabs, itemsize=2) is None
    if expected:
        assert (2 * slabs * expected["block_t"] * expected["block_d"] * 4
                <= 16 * 2 ** 20)


def test_kernel_refuses_what_it_cannot_tile():
    o = operands(2, 64, 128)
    with pytest.raises(ValueError, match="not tiled"):
        mix_backward(o["pre"][None], o["du"][None], o["x"], block_t=48,
                     block_d=128)
    with pytest.raises(ValueError, match="float32"):
        mix_backward(o["pre"][None], o["du"][None].astype(jnp.bfloat16),
                     o["x"], block_t=64, block_d=128)
    with pytest.raises(ValueError, match="not tiled"):      # the rule's None
        mix_backward(o["pre"][None, :, :63], o["du"][None, :63], o["x"][:, :63])
    with pytest.raises(ValueError, match="coefficients"):
        mix_backward(o["pre"][None], o["du"][None], o["x"], o["y"],
                     block_t=64, block_d=128)


# -- through HyperConnection --------------------------------------------------------

def _mixing(d, tokens=32, n=4):
    hc = HyperConnection(n, d)
    X = jax.random.normal(jax.random.PRNGKey(0), (n, 2, tokens, d)) \
        * jnp.linspace(0.5, 2.0, n)[:, None, None, None]
    p = hc.init(jax.random.PRNGKey(1))["params"]
    w = jax.random.normal(jax.random.PRNGKey(2), (d, d)) * d ** -0.5
    cot = jax.random.normal(jax.random.PRNGKey(3), X.shape)

    def out(p, X, w):
        u, co = hc.pre(p, X)
        return hc.post(X, jnp.tanh(u @ w), co), co

    loss = lambda p, X, w: jnp.sum(out(p, X, w)[0] * cot)
    return hc, out, loss, (p, X, w)


def _traces():
    from bigdl_tpu.optim.metrics import global_metrics

    return {k: v for k, v in global_metrics().snapshot()["counters"].items()
            if k.startswith("kernel.hc_mix.traces")}


def test_forward_is_bit_equal_and_gradients_agree_under_checkpoint(
        monkeypatch):
    hc, out, loss, args = _mixing(128)
    jit = lambda f: jax.jit(lambda *a: f(*a))   # a trace of its own each
    plain_out, co = jit(out)(*args)
    assert "streams" not in co                  # the CPU: autodiff
    plain = jit(jax.grad(loss, (0, 1, 2)))(*args)
    before = _traces()
    monkeypatch.setattr(hc_mod, "on_tpu", lambda: True)
    fused_out, co = jit(out)(*args)
    assert "streams" in co
    np.testing.assert_array_equal(np.asarray(fused_out),
                                  np.asarray(plain_out))
    fused = jit(jax.grad(jax.checkpoint(loss), (0, 1, 2)))(*args)
    for a, b in zip(jax.tree_util.tree_leaves(fused),
                    jax.tree_util.tree_leaves(plain)):
        assert float(jnp.abs(b).max()) > 0 and rel(a, b) < REL
    new = {k: v - before.get(k, 0) for k, v in _traces().items()}
    assert all(v > 0 for k, v in new.items() if "pallas" in k)
    assert {k.split("{")[1] for k, v in new.items() if v} == {
        'direction="post",impl="pallas"}', 'direction="pre",impl="pallas"}'}


def test_shape_the_rule_refuses_falls_back_and_agrees(as_on_tpu):
    """d = 64: no lane tile.  The TPU's branch takes autodiff there."""
    hc, out, loss, args = _mixing(64)
    _, co = out(*args)
    assert "streams" not in co and not can_mix(4, 64, 64)
    before = _traces()
    got = jax.grad(loss, (0, 1, 2))(*args)
    assert any("autodiff" in k and v > before.get(k, 0)
               for k, v in _traces().items())
    c = fam._model_config(dict(CFG_FILE, hidden_size=64))

    def ref(p, X, w):
        f = lambda u: jnp.tanh(u @ w)
        return jnp.sum(jnp.stack([
            fam._around(c, p, X[:, b].transpose(1, 0, 2), f, 20,
                        2.0).transpose(1, 0, 2)
            for b in range(X.shape[1])], 1) * jax.random.normal(
                jax.random.PRNGKey(3), X.shape))

    want = jax.grad(ref, (0, 1, 2))(*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(a, b, 1e-4)


def test_pre_alone_differentiates_without_a_write_back(as_on_tpu):
    """The pass-through's cotangent is all zeros when nothing reads it."""
    hc, _, _, (p, X, _) = _mixing(128)
    got = jax.grad(lambda X: jnp.sum(hc.pre(p, X)[0] ** 2))(X)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(hc_mod, "on_tpu", lambda: False)
        want = jax.grad(lambda X: jnp.sum(hc.pre(p, X)[0] ** 2))(X)
    assert rel(got, want) < REL


# -- the whole model ------------------------------------------------------------------

WIDE = dict(CFG_FILE, hidden_size=128)      # the narrowest d the rule takes


def _model_grads(x, y, v, model):
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion

    crit = CrossEntropyCriterion()

    def loss(p):
        out, st = model.forward(p, v["state"], x, training=True)
        return crit.forward(out, y), st

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(v["params"])


def test_model_gradients_match_the_plain_path_and_counters_leave(
        monkeypatch):
    model = fam.build_model(WIDE)
    ids = ids_batch(5, 2)
    x, y = ids[:, :-1], ids[:, 1:]
    v = model.init(jax.random.PRNGKey(12), x[:1])
    (l0, st0), g0 = _model_grads(x, y, v, model)
    monkeypatch.setattr(hc_mod, "on_tpu", lambda: True)
    (l1, st1), g1 = _model_grads(x, y, v, model)
    assert float(l0) == float(l1)               # the forward is the same
    flat = jax.tree_util.tree_flatten_with_path(g1)[0]
    ref = dict(jax.tree_util.tree_flatten_with_path(g0)[0])
    assert len(flat) == len(ref)
    for path, a in flat:
        assert float(jnp.abs(ref[path]).max()) > 0, path
        close(a, ref[path], 1e-4)
    layers = model.config.num_hidden_layers
    for st, fused in ((st0, 0), (st1, 1)):
        counters = [st[f"layer{i}"][k]["metrics"]["counters"]
                    for i in range(layers) for k in ("hc_attn", "hc_ffn")]
        assert [int(c["hc.mixes"]) for c in counters] == [1] * (2 * layers)
        assert [int(c["hc.fused_mixes"]) for c in counters] == [
            fused] * (2 * layers)


@pytest.mark.parametrize("fused", [False, True])
def test_counters_are_booked_through_optimize(fused, monkeypatch):
    from bigdl_tpu.data.dataset import DataSet
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.optim import optim_method
    from bigdl_tpu.optim.metrics import global_metrics
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.trigger import Trigger

    if fused:
        monkeypatch.setattr(hc_mod, "on_tpu", lambda: True)
    model = fam.build_model(WIDE)
    ids = ids_batch(6, 8)       # one example a device of the CPU's mesh
    x, y = ids[:, :-1], ids[:, 1:]
    count = lambda: {k: global_metrics().snapshot()["counters"].get(k, 0)
                     for k in ("hc.mixes", "hc.fused_mixes")}
    before = count()
    opt = Optimizer(model, DataSet.array(x, y), CrossEntropyCriterion(),
                    batch_size=8, seed=5)
    opt.set_optim_method(optim_method.Adam(learning_rate=1e-3))
    opt.set_initial_variables(model.init(jax.random.PRNGKey(13), x[:1]))
    opt.set_end_when(Trigger(lambda s: s["iteration"] >= 3, "three steps"))
    opt.optimize()
    after = count()
    # one a sublayer, step and replica (the step sums the data axis)
    mixes = 3 * 2 * model.config.num_hidden_layers * jax.device_count()
    assert after["hc.mixes"] - before["hc.mixes"] == mixes
    assert after["hc.fused_mixes"] - before["hc.fused_mixes"] == (
        mixes if fused else 0)
