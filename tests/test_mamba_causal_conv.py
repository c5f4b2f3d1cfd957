"""Mamba-2's causal convolution with its bias and SiLU as one Pallas pass
each way (``ops/causal_conv.py``, interpret mode here), against autodiff of
the plain expression ``SiLU(causal_taps(u[..., window], w) + b)`` in
float32: the kernels alone at shapes that cross T tiles both ways, end in a
partial tile, are shorter than the taps, span several channel blocks and
read the window inside a wider array; the mixer through the kernels against
the mixer through the plain expression; the tile rule, and the counter the
mixer books and the benchmark's share reads."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from bigdl_tpu.nn import mamba2
from bigdl_tpu.nn.short_conv import causal_taps
from bigdl_tpu.ops.causal_conv import BLOCK_T, causal_conv, conv_blocks

CELL = "granite-4.0-h-micro.train-tp4-16k"
SHARE = "ssm.fused_conv_share"


def close(a, b, tol=2e-5):
    """Both sides are float32: the SiLU's ``exp`` and the order of the
    tap sums differ, nothing else."""
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol * max(
        1.0, float(np.abs(b).max())))


def plain(u, w, b, offset):
    width = w.shape[1]
    return jax.nn.silu(causal_taps(u[..., offset:offset + width], w) + b)


# (batch, T, wide, offset, width, taps)
SHAPES = [
    (1, 2 * BLOCK_T + 128, 640, 128, 384, 4),  # 3 T tiles, the last partial
    (1, 2 * BLOCK_T, 512, 256, 256, 4),        # 2 whole tiles, window inside
    (2, 128, 256, 0, 256, 4),                  # batch 2, one short tile
    (1, 128, 96, 8, 16, 129),                  # T <= K: 128 positions
    (1, 256, 1280, 256, 768, 3),               # 3 channel blocks of 256
]


@pytest.mark.parametrize("batch,t,wide,offset,width,taps", SHAPES)
def test_kernels_are_the_plain_expression(batch, t, wide, offset, width,
                                          taps):
    """Forward, ``du`` (zeros outside the window), ``dw`` and ``db``
    against autodiff of the plain expression, in one jitted call each."""
    ks = jax.random.split(jax.random.PRNGKey(t + width), 4)
    u = jax.random.normal(ks[0], (batch, t, wide))
    w = jax.random.normal(ks[1], (taps, width)) * taps ** -0.5
    b = jax.random.normal(ks[2], (width,))
    g = jax.random.normal(ks[3], (batch, t, width))

    def value_and_vjp(f):
        def both(u, w, b, g):
            out, vjp = jax.vjp(f, u, w, b)
            return out, vjp(g)
        return jax.jit(both)(u, w, b, g)

    (y, grads), (y_ref, grads_ref) = (
        value_and_vjp(f) for f in (
            lambda u, w, b: causal_conv(u, w, b, offset=offset),
            lambda u, w, b: plain(u, w, b, offset)))
    close(y, y_ref)
    for got, want in zip(grads, grads_ref):
        assert got.shape == want.shape
        close(got, want)
    assert not np.asarray(grads[0])[..., :offset].any()
    assert not np.asarray(grads[0])[..., offset + width:].any()


@pytest.mark.parametrize("t,offset,width,taps,want", [
    (16384, 4096, 4352, 4, {"block_t": BLOCK_T, "block_c": 256}),  # Granite
    (128, 0, 1024, 4, {"block_t": 128, "block_c": 256}),
    (256, 128, 160, 4, {"block_t": 256, "block_c": 32}),
    (200, 0, 256, 4, None),         # T not a multiple of 128
    (256, 4, 256, 4, None),         # window not on an 8-sublane block
    (256, 8, 148, 4, None),         # width not a multiple of 8
    (256, 0, 256, 130, None),       # more history than the 128-lane halo
])
def test_tile_rule(t, offset, width, taps, want):
    assert conv_blocks(t, offset, width, taps) == want


def test_refused_shape_raises():
    with pytest.raises(ValueError):
        causal_conv(jnp.zeros((1, 200, 256)), jnp.zeros((4, 256)),
                    jnp.zeros((256,)))


def _mixer(t, **kw):
    """A mixer whose x‖B‖C window tiles (d_in 128, N 64: 256 channels at
    128), or not (``state`` 10: 148 channels)."""
    kw = dict(dict(heads=4, head_dim=32, state=64), **kw)
    m = mamba2.Mamba2(64, chunk=32, **kw)
    u = jax.random.normal(jax.random.PRNGKey(41), (2, t, 64))
    return m, m.init(jax.random.PRNGKey(42), u), u


def _run(m, v, u):
    """Output, gradients of every parameter and of ``u``, and the
    counters, in one jitted call."""
    def loss(p, u):
        out, st = m.forward(p, v["state"], u, training=True)
        return jnp.sum(jnp.square(out)), st

    (_, st), grads = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(
        v["params"], u)
    out = jax.jit(lambda p, u: m.forward(p, v["state"], u)[0])(v["params"], u)
    return out, grads, st["metrics"]["counters"]


def test_mixer_through_the_kernels_is_the_plain_mixer(monkeypatch):
    """Forward and every gradient at widths the rule tiles, against the
    same mixer made to take the plain expression; the counter says which
    way each went."""
    m, v, u = _mixer(128)
    out, grads, counters = _run(m, v, u)
    assert {k: int(c) for k, c in counters.items()} == {
        mamba2.SCANS: 1, mamba2.FUSED_CONVS: 1}
    monkeypatch.setattr(mamba2, "conv_blocks", lambda *a: None)
    out_ref, grads_ref, counters_ref = _run(m, v, u)
    assert int(counters_ref[mamba2.FUSED_CONVS]) == 0
    close(out, out_ref, tol=1e-4)
    for got, want in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(grads_ref)):
        close(got, want, tol=1e-4)


def test_mixer_scope_names_the_kernels():
    m, v, u = _mixer(128)
    text = jax.jit(jax.grad(lambda p: jnp.sum(
        m.forward(p, v["state"], u, training=True)[0]))).lower(
        v["params"]).as_text(debug_info=True)
    assert "mamba/causal_conv" in text


@pytest.mark.parametrize("t,kw", [
    (128, dict(state=10)),          # 148 channels: not on 8 sublanes
    (96, {}),                       # T not a multiple of 128
])
def test_a_shape_the_rule_refuses_takes_the_plain_path(t, kw):
    m, v, u = _mixer(t, **kw)
    ks = jax.random.split(jax.random.PRNGKey(43), 2)
    p = dict(v["params"], conv_w=jax.random.normal(ks[0], (4, m.conv_dim)),
             conv_b=jax.random.normal(ks[1], (m.conv_dim,)))
    out, counters = jax.jit(lambda p, u: m.forward(p, v["state"], u))(p, u)
    assert int(counters["metrics"]["counters"][mamba2.FUSED_CONVS]) == 0
    assert int(counters["metrics"]["counters"][mamba2.SCANS]) == 1
    jaxpr = str(jax.make_jaxpr(lambda p, u: m.forward(p, v["state"], u))(
        p, u))
    # the scan's kernel is the only Pallas call
    assert jaxpr.count("pallas_call") == 1


# -- the benchmark's share -----------------------------------------------


def _share():
    (entry,) = [m for m in harness.resolve(CELL)["per_layer"]
                if m["name"] == SHARE]
    return entry


def test_share_resolves_in_the_granite_cell_only():
    m = _share()
    assert (m["reader"], m["source"], m["unit"], m["better"]) == (
        "registry_delta", "program_counter", "%", "higher")
    assert (m["layer"], m["moves"], m["workloads"]) == (
        "state-space mixer", "train_throughput", [CELL])
    assert m["args"]["num"] == {"counter": mamba2.FUSED_CONVS}
    assert m["args"]["den"] == {"counter": mamba2.SCANS}
    for cell in ("lfm2-24b-a2b.train-ep8-packed8k",
                 "minicpm-sala.train-tp8-32k"):
        assert SHARE not in {e["name"]
                             for e in harness.resolve(cell)["per_layer"]}


@pytest.mark.parametrize("fused,scans,want", [
    ((9, 450), (9, 450), 100.0),      # every mixer of the window
    ((0, 0), (9, 450), 0.0),          # instrumented, the plain path
    ((None, None), (9, 450), None),   # the parent: scans, no such counter
])
def test_share_reads_the_counters(fused, scans, want):
    m = _share()
    snaps = []
    for f, s in zip(fused, scans):
        counters = {mamba2.SCANS: s}
        if f is not None:
            counters[mamba2.FUSED_CONVS] = f
        snaps.append({"counters": counters, "hists": {}})
    ev = {"registry": {"window_start": snaps[0], "window_end": snaps[1]},
          "marks": {"process_start": 0.0, "window_start": 100.0,
                    "window_end": 140.0}}
    got = harness.load_module("readers", m["reader"]).read(m["args"], ev)
    assert got == (None if want is None else pytest.approx(want))
