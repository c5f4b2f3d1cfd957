"""Pallas kernel correctness — flash attention, int8 matmul, fused LN.

Mirrors the reference's layer-correctness spec pattern (SURVEY.md §5:
``nn/LinearSpec.scala``-style golden comparisons): every kernel is checked
against a plain jnp/numpy oracle, on CPU in interpreter mode — the same
code path Mosaic compiles on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.nn.attention import dot_product_attention
from bigdl_tpu.ops import (flash_attention, fused_layernorm, int8_matmul,
                           quantize_int8, quantized_linear)


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


class TestFlashAttention:
    # d_v: the values' width; 24 / 16 is latent attention's 192 / 128
    @pytest.mark.parametrize("d,d_v", [(16, 16), (24, 16), (8, 24)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal, d, d_v):
        rng = np.random.default_rng(0)
        q = _rand(rng, 2, 3, 40, d)
        k = _rand(rng, 2, 3, 40, d)
        v = _rand(rng, 2, 3, 40, d_v)
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                              interpret=True)
        assert out.shape == (2, 3, 40, d_v)
        mask = jnp.tril(jnp.ones((40, 40), bool)) if causal else None
        ref = dot_product_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("d,d_v", [(8, 8), (24, 16)])
    def test_unaligned_and_cross_lengths(self, d, d_v):
        rng = np.random.default_rng(1)
        q = _rand(rng, 1, 2, 37, d)
        k = _rand(rng, 1, 2, 53, d)
        v = _rand(rng, 1, 2, 53, d_v)
        out = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_the_caller_passes_the_softmax_scale(self):
        """``sm_scale`` is the caller's (YaRN's mscale ** 2 rides it)."""
        rng = np.random.default_rng(4)
        q, k, v = (_rand(rng, 1, 2, 24, 24), _rand(rng, 1, 2, 24, 24),
                   _rand(rng, 1, 2, 24, 16))
        mask = jnp.tril(jnp.ones((24, 24), bool))
        out = flash_attention(q, k, v, causal=True, sm_scale=0.37,
                              block_q=8, block_k=8, interpret=True)
        ref = dot_product_attention(q, k, v, mask=mask, scale=0.37)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        assert float(jnp.abs(ref - dot_product_attention(
            q, k, v, mask=mask)).max()) > 1e-2

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match(self, causal):
        rng = np.random.default_rng(2)
        q = _rand(rng, 1, 2, 24, 8)
        k = _rand(rng, 1, 2, 24, 8)
        v = _rand(rng, 1, 2, 24, 8)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, block_q=8, block_k=8,
                interpret=True) ** 2)

        def loss_ref(q, k, v):
            mask = jnp.tril(jnp.ones((24, 24), bool)) if causal else None
            return jnp.sum(dot_product_attention(q, k, v, mask=mask) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)

    def test_jit_compatible(self):
        rng = np.random.default_rng(3)
        q = _rand(rng, 1, 1, 16, 8)
        f = jax.jit(lambda q: flash_attention(q, q, q, interpret=True))
        out = f(q)
        assert out.shape == q.shape


def _attention_grads(att, q, k, v):
    return jax.grad(lambda q, k, v: jnp.sum(att(q, k, v) ** 2),
                    argnums=(0, 1, 2))(q, k, v)


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


# (causal, sq, skv, head size or (q/k width, v width), block_q, block_k)
_BWD_CASES = [
    (False, 32, 32, 16, 8, 8),      # square, every tile visited
    (True, 32, 32, 16, 8, 8),       # square, tiles above the diagonal skipped
    (True, 32, 32, 16, 16, 8),      # block_q != block_k: the clamp's floor
    (True, 32, 32, 16, 8, 16),
    (False, 24, 40, 8, 8, 16),      # cross-attention lengths
    (True, 24, 40, 8, 8, 16),       # sq < skv: trailing key tiles unvisited
    (True, 40, 24, 8, 16, 8),       # sq > skv
    (False, 37, 53, 8, 16, 16),     # neither length a multiple of its block
    (True, 37, 37, 8, 16, 16),
    (True, 21, 21, 8, 128, 128),    # blocks longer than the sequence
    (True, 16, 16, 64, 8, 8),       # gpt2-small's head size
    (True, 16, 16, 256, 8, 8),      # GLM-4.7-Flash's head size
    (True, 16, 16, (192, 128), 8, 8),   # Xing4.0's: keys wider than values
    (False, 32, 32, (24, 16), 8, 8),
    (True, 32, 32, (24, 16), 16, 8),
    (True, 37, 53, (24, 16), 16, 16),   # lengths the blocks do not divide
    (True, 40, 24, (8, 24), 16, 8),     # values wider than keys
]


class TestFlashBackwardKernels:
    """The Pallas backward pair (dq; dk/dv) against ``jax.grad`` of
    ``dot_product_attention``: both policies, padding, cross lengths."""

    @staticmethod
    def _inputs(sq, skv, d, seed=5):
        d, d_v = d if isinstance(d, tuple) else (d, d)
        rng = np.random.default_rng(seed)
        return (_rand(rng, 1, 2, sq, d), _rand(rng, 1, 2, skv, d),
                _rand(rng, 1, 2, skv, d_v))

    @staticmethod
    def _reference(causal, sq, skv):
        # the kernel's causal mask is top-left aligned: key j <= query i
        mask = jnp.tril(jnp.ones((sq, skv), bool)) if causal else None
        return lambda q, k, v: dot_product_attention(q, k, v, mask=mask)

    @pytest.mark.parametrize("policy", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal,sq,skv,d,bq,bk", _BWD_CASES)
    def test_gradients_match_reference(self, causal, sq, skv, d, bq, bk,
                                       policy):
        """float32 policy: 1e-4 of the largest reference gradient (the
        old scan's class; measured 8e-7 at most).  bfloat16 policy: 3e-2
        against the float32 reference, measured 0.53e-2 to 1.4e-2 over
        these cases where XLA's own attention under the same policy reads
        0.51e-2 to 1.1e-2: the operands' rounding sets it, not the kernel.
        The result is float32 as its input."""
        from bigdl_tpu.tensor.policy import compute_dtype

        q, k, v = self._inputs(sq, skv, d)
        want = _attention_grads(self._reference(causal, sq, skv), q, k, v)
        with compute_dtype(policy):
            got = _attention_grads(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    interpret=True), q, k, v)
        tol = 1e-4 if policy == "float32" else 3e-2
        for name, a, b in zip("qkv", got, want):
            assert a.dtype == jnp.float32 and a.shape == b.shape
            assert np.isfinite(np.asarray(a)).all()
            assert _rel_err(a, b) <= tol, (name, _rel_err(a, b))

    def test_padding_rows_and_columns_get_zero_gradient(self):
        """The pair's own outputs BEFORE the slice: rows past sq of dq and
        rows past skv of dk, dv are exactly zero (padded queries carry
        g = 0 and delta = 0; padded keys are masked out of p)."""
        import importlib
        fa = importlib.import_module("bigdl_tpu.ops.flash_attention")

        sq, skv, d = 21, 27, 8
        q, k, v = self._inputs(sq, skv, d)
        g = _rand(np.random.default_rng(6), 1, 2, sq, d)
        out, lse = fa._flash_fwd(q, k, v, 0.3, True, 16, 16, True)
        seen = {}
        real_call = fa.pl.pallas_call

        def spy(kernel, **kw):
            call = real_call(kernel, **kw)

            def run(*args):
                res = call(*args)
                seen[kernel.func.__name__] = res
                return res
            return run

        fa.pl.pallas_call = spy
        try:
            fa._flash_bwd(q, k, v, out, lse, g, 0.3, True, 16, 16, True)
        finally:
            fa.pl.pallas_call = real_call
        dq = np.asarray(seen["_dq_kernel"])
        dk, dv = (np.asarray(x) for x in seen["_dkv_kernel"])
        assert dq.shape[1] == 32 and dk.shape[1] == 32
        assert np.abs(dq[:, :sq]).max() > 0 and np.abs(dk[:, :skv]).max() > 0
        assert (dq[:, sq:] == 0).all()
        assert (dk[:, skv:] == 0).all() and (dv[:, skv:] == 0).all()

    @pytest.mark.parametrize("direction", ["fwd", "bwd"])
    @pytest.mark.parametrize("sq,skv,d,itemsize", [
        (4096, 4096, 256, 2),    # glm-4.7-flash.train-packed4k
        (4096, 4096, 256, 4),
        (1024, 1024, 64, 2),     # gpt2-small, (8, 12, 1024, 64)
        (1024, 1024, 64, 4),
        (8, 8, 64, 4), (16, 16, 64, 4), (32, 32, 64, 2), (64, 64, 64, 2),
        (24, 640, 64, 2),        # the decode engine's buckets and chunks
        (300, 5000, 128, 2),
        (8192, 8192, 512, 4),    # a head size that leaves little room
        (4096, 4096, (192, 128), 2),   # xing4.0-29b-a4b.train-tp8-packed4k
        (4096, 4096, (192, 128), 4),
        (8192, 8192, (512, 64), 4),
    ])
    def test_block_rule_returns_legal_blocks(self, direction, sq, skv, d,
                                             itemsize):
        import importlib
        fa = importlib.import_module("bigdl_tpu.ops.flash_attention")
        from bigdl_tpu.ops.common import round_up

        d, d_v = d if isinstance(d, tuple) else (d, None)
        got = fa.default_blocks(direction, sq, skv, d, itemsize, d_v)
        if d_v is None:     # the values' width left out is the keys'
            assert got == fa.default_blocks(direction, sq, skv, d, itemsize,
                                            d)
        bq, bk = got["block_q"], got["block_k"]
        # multiples of 128 (Mosaic's lane tiling), at most 1024, never
        # longer than the 128-padded length, inside the VMEM budget
        assert bq % 128 == 0 and bk % 128 == 0
        assert 128 <= bq <= min(1024, round_up(sq, 128))
        assert 128 <= bk <= min(1024, round_up(skv, 128))
        assert (fa.block_vmem_bytes(direction, bq, bk, d, itemsize, d_v)
                <= fa._VMEM_BLOCK_BUDGET < fa._VMEM_LIMIT_BYTES)
        # as the kernels use them: a multiple of 8, no longer than the
        # 8-padded sequence, tiling the padded length exactly
        cq, ck, sq_p, skv_p = fa._clip_blocks(bq, bk, sq, skv)
        assert cq % 8 == 0 and ck % 8 == 0
        assert cq <= round_up(sq, 8) and ck <= round_up(skv, 8)
        assert sq_p % cq == 0 and skv_p % ck == 0
        assert sq <= sq_p < sq + cq and skv <= skv_p < skv + ck

    def test_block_rule_follows_the_shape(self):
        """Bigger tiles where they fit, smaller where the head size or the
        itemsize fills the budget; explicit kwargs still win."""
        import importlib
        fa = importlib.import_module("bigdl_tpu.ops.flash_attention")

        area = lambda b: b["block_q"] * b["block_k"]
        # the benchmark's cells: 1024 x 1024 both ways, at GLM's equal and
        # at Xing's unequal widths; narrower values never shrink a block
        for widths in ((256,), (192, 128)):
            for direction in ("fwd", "bwd"):
                assert fa.default_blocks(direction, 4096, 4096, widths[0], 2,
                                         *widths[1:]) == {
                    "block_q": 1024, "block_k": 1024}
        assert area(fa.default_blocks("bwd", 8192, 8192, 512, 4, 64)) >= area(
            fa.default_blocks("bwd", 8192, 8192, 512, 4))
        small = fa.default_blocks("bwd", 8192, 8192, 64, 2)
        big = fa.default_blocks("bwd", 8192, 8192, 512, 4)
        assert area(big) < area(small)
        assert area(fa.default_blocks("bwd", 8192, 8192, 256, 2)) <= area(
            fa.default_blocks("fwd", 8192, 8192, 256, 2))

    @pytest.mark.parametrize("causal,blocks,share", [
        (True, (8, 8), 36 / 64), (False, (8, 8), 1.0),
        (True, (16, 8), 20 / 32), (True, (64, 64), 1.0)])
    def test_trace_counters_read_what_the_call_did(self, causal, blocks,
                                                   share):
        from bigdl_tpu.optim.metrics import global_metrics, label_key

        m = global_metrics()
        q, k, v = self._inputs(64, 64, 8)

        def count(direction):
            return m.counter(label_key(
                "kernel.flash.traces", direction=direction, impl="pallas",
                dtype="float32", kv_group="1"))

        def gauge(direction):
            return m.gauges[label_key("kernel.flash.tile_share",
                                      direction=direction)]

        before = count("fwd"), count("bwd")
        att = lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=blocks[0], block_k=blocks[1],
            interpret=True)
        att(q, k, v)
        assert (count("fwd"), count("bwd")) == (before[0] + 1, before[1])
        assert gauge("fwd") == pytest.approx(share)
        _attention_grads(att, q, k, v)
        assert (count("fwd"), count("bwd")) == (before[0] + 2,
                                                before[1] + 1)
        assert gauge("bwd") == pytest.approx(share)

    def test_autotune_winner_and_explicit_blocks_beat_the_rule(
            self, monkeypatch):
        """Blocks left as None take the rule's pick, forward and backward;
        explicit kwargs win axis by axis."""
        import importlib
        fa = importlib.import_module("bigdl_tpu.ops.flash_attention")

        q, k, v = self._inputs(32, 32, 8)
        seen = []
        real = fa._flash

        def spy(q, k, v, scale, causal, bq, bk, bq_b, bk_b, interpret):
            seen.append((bq, bk, bq_b, bk_b))
            return real(q, k, v, scale, causal, bq, bk, bq_b, bk_b,
                        interpret)

        monkeypatch.setattr(fa, "_flash", spy)
        fa.flash_attention(q, k, v, interpret=True)
        rule_f = fa.default_blocks("fwd", 32, 32, 8, 4)
        rule_b = fa.default_blocks("bwd", 32, 32, 8, 4)
        fq, fk, bq, bk = (rule_f["block_q"], rule_f["block_k"],
                          rule_b["block_q"], rule_b["block_k"])
        assert seen[-1] == (fq, fk, bq, bk)
        fa.flash_attention(q, k, v, block_q=32, interpret=True)
        assert seen[-1] == (32, fk, 32, bk)      # block_q pins both
        fa.flash_attention(q, k, v, block_k=32, interpret=True)
        assert seen[-1] == (fq, 32, bq, 32)      # block_k pins both
        fa.flash_attention(q, k, v, block_k=32, block_k_bwd=8,
                           interpret=True)
        assert seen[-1] == (fq, 32, bq, 8)


class TestInt8Matmul:
    def test_exact_int_arithmetic(self):
        rng = np.random.default_rng(0)
        x = rng.integers(-127, 128, (50, 70), dtype=np.int8)
        w = rng.integers(-127, 128, (70, 30), dtype=np.int8)
        out = int8_matmul(jnp.asarray(x), jnp.asarray(w), block_m=32,
                          block_n=128, block_k=128, interpret=True)
        ref = x.astype(np.int32) @ w.astype(np.int32)
        np.testing.assert_array_equal(np.asarray(out), ref)

    def test_quantize_roundtrip(self):
        rng = np.random.default_rng(1)
        w = _rand(rng, 64, 32)
        w_q, scales = quantize_int8(w, axis=0)
        assert w_q.dtype == jnp.int8 and scales.shape == (32,)
        deq = np.asarray(w_q, np.float32) * np.asarray(scales)[None, :]
        np.testing.assert_allclose(deq, np.asarray(w), atol=float(
            np.max(np.asarray(scales))) * 0.51)

    def test_quantized_linear_close_to_f32(self):
        rng = np.random.default_rng(2)
        x = _rand(rng, 9, 64)
        w = _rand(rng, 64, 48) * 0.1
        b = _rand(rng, 48) * 0.01
        w_q, scales = quantize_int8(w, axis=0)
        y_q = quantized_linear(x, w_q, scales, b, interpret=True)
        y = x @ w + b
        err = np.abs(np.asarray(y_q) - np.asarray(y)).max()
        scale = float(np.abs(np.asarray(y)).max())
        assert err / scale < 0.05, (err, scale)


class TestQuantizedModules:
    def test_quantize_sequential(self):
        from bigdl_tpu.nn.layers import Linear, ReLU
        from bigdl_tpu.nn.module import Sequential
        from bigdl_tpu.nn.quantized import QuantizedLinear, quantize

        rng = np.random.default_rng(3)
        model = Sequential([Linear(32, 16), ReLU(), Linear(16, 4)])
        x = _rand(rng, 5, 32)
        variables = model.init(jax.random.PRNGKey(0), x)
        y_ref, _ = model.apply(variables, x)

        q_model, q_vars = quantize(model, variables)
        assert isinstance(q_model.layers[0], QuantizedLinear)
        assert isinstance(q_model.layers[2], QuantizedLinear)
        y_q, _ = q_model.apply(q_vars, x)
        rel = (np.abs(np.asarray(y_q) - np.asarray(y_ref)).max()
               / (np.abs(np.asarray(y_ref)).max() + 1e-8))
        assert rel < 0.1, rel
        # original untouched
        y_again, _ = model.apply(variables, x)
        np.testing.assert_array_equal(np.asarray(y_again), np.asarray(y_ref))

    def test_quantize_conv(self):
        from bigdl_tpu.nn.layers import Conv2D
        from bigdl_tpu.nn.module import Sequential
        from bigdl_tpu.nn.quantized import QuantizedConv2D, quantize

        rng = np.random.default_rng(4)
        model = Sequential([Conv2D(3, 8, 3, stride=1, padding="SAME")])
        x = _rand(rng, 2, 8, 8, 3)
        variables = model.init(jax.random.PRNGKey(0), x)
        y_ref, _ = model.apply(variables, x)
        q_model, q_vars = quantize(model, variables)
        assert isinstance(q_model.layers[0], QuantizedConv2D)
        y_q, _ = q_model.apply(q_vars, x)
        assert y_q.shape == y_ref.shape
        rel = (np.abs(np.asarray(y_q) - np.asarray(y_ref)).max()
               / (np.abs(np.asarray(y_ref)).max() + 1e-8))
        assert rel < 0.1, rel

    @pytest.mark.parametrize("groups", [2, 4, 8])
    def test_quantize_grouped_conv(self, groups):
        """reference nGroup int8 conv — incl. depthwise (groups == cin)."""
        from bigdl_tpu.nn.layers import Conv2D
        from bigdl_tpu.nn.module import Sequential
        from bigdl_tpu.nn.quantized import QuantizedConv2D, quantize

        rng = np.random.default_rng(5)
        model = Sequential([Conv2D(8, 16, 3, stride=1, padding="SAME",
                                   groups=groups)])
        x = _rand(rng, 2, 8, 8, 8)
        variables = model.init(jax.random.PRNGKey(0), x)
        y_ref, _ = model.apply(variables, x)
        q_model, q_vars = quantize(model, variables)
        assert isinstance(q_model.layers[0], QuantizedConv2D)
        y_q, _ = q_model.apply(q_vars, x)
        assert y_q.shape == y_ref.shape
        rel = (np.abs(np.asarray(y_q) - np.asarray(y_ref)).max()
               / (np.abs(np.asarray(y_ref)).max() + 1e-8))
        assert rel < 0.1, (groups, rel)

    def test_grouped_conv_per_channel_calibration(self):
        """per-input-channel static activation scales fold per group."""
        import jax.numpy as jnp

        from bigdl_tpu.nn.layers import Conv2D
        from bigdl_tpu.nn.quantized import QuantizedConv2D

        rng = np.random.default_rng(6)
        layer = Conv2D(8, 8, 3, padding="SAME", groups=2)
        x = _rand(rng, 2, 8, 8, 8)
        variables = layer.init(jax.random.PRNGKey(1), x)
        y_ref, _ = layer.apply(variables, x)
        # per-channel scales from the actual activation range
        scales = np.abs(np.asarray(x)).max(axis=(0, 1, 2)) / 127.0
        q, qp = QuantizedConv2D.from_conv(layer, variables["params"],
                                          act_scale=scales)
        y_q, _ = q.forward(qp, {}, jnp.asarray(x))
        rel = (np.abs(np.asarray(y_q) - np.asarray(y_ref)).max()
               / (np.abs(np.asarray(y_ref)).max() + 1e-8))
        assert rel < 0.1, rel


class TestFusedLayerNorm:
    def test_matches_reference(self):
        rng = np.random.default_rng(0)
        x = _rand(rng, 7, 33)
        g = _rand(rng, 33)
        b = _rand(rng, 33)
        out = fused_layernorm(x, g, b, interpret=True)
        mean = np.asarray(x).mean(-1, keepdims=True)
        var = np.asarray(x).var(-1, keepdims=True)
        ref = (np.asarray(x) - mean) / np.sqrt(var + 1e-5)
        ref = ref * np.asarray(g) + np.asarray(b)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-4)

    def test_gradients_match(self):
        rng = np.random.default_rng(2)
        x = _rand(rng, 4, 16)
        g = _rand(rng, 16)
        b = _rand(rng, 16)

        def loss_fused(x, g, b):
            return jnp.sum(fused_layernorm(x, g, b, interpret=True) ** 2)

        def loss_ref(x, g, b):
            mean = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            y = (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b
            return jnp.sum(y ** 2)

        g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(x, g, b)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(x, g, b)
        for a, bb in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=1e-3, atol=1e-3)

    def test_3d_input(self):
        rng = np.random.default_rng(1)
        x = _rand(rng, 2, 5, 16)
        g = jnp.ones((16,))
        b = jnp.zeros((16,))
        out = fused_layernorm(x, g, b, interpret=True)
        assert out.shape == x.shape


class TestFlashInMHA:
    def test_mha_flash_path(self):
        from bigdl_tpu.nn.attention import MultiHeadAttention

        rng = np.random.default_rng(5)
        x = _rand(rng, 2, 20, 32)
        mha = MultiHeadAttention(32, 4, causal=True, use_flash=False)
        variables = mha.init(jax.random.PRNGKey(0), x)
        y_ref, _ = mha.apply(variables, x)
        mha_flash = MultiHeadAttention(32, 4, causal=True, use_flash=True)
        y_flash, _ = mha_flash.apply(variables, x)
        np.testing.assert_allclose(np.asarray(y_flash), np.asarray(y_ref),
                                   rtol=2e-3, atol=2e-3)


class TestActivationCalibration:
    """Reference min/max + percentile activation calibration (SURVEY §3.2):
    static per-tensor activation scales from a calibration set, accuracy
    within 1% of float on a trained zoo-style model."""

    def _trained_mlp(self):
        from bigdl_tpu import nn, optim
        from bigdl_tpu.data.dataset import ArrayDataSet
        from bigdl_tpu.runtime.engine import Engine, init_engine

        rs = np.random.RandomState(0)
        x = rs.rand(512, 16).astype(np.float32)
        y = (x[:, :8].sum(1) > x[:, 8:].sum(1)).astype(np.int32)
        Engine.reset()
        init_engine(data=1)
        model = nn.Sequential([nn.Linear(16, 32), nn.ReLU(),
                               nn.Linear(32, 2)])
        opt = optim.Optimizer(model, ArrayDataSet(x, y),
                              nn.CrossEntropyCriterion(), batch_size=64)
        opt.set_optim_method(optim.Adam(learning_rate=5e-3))
        opt.set_end_when(optim.Trigger.max_epoch(20))
        opt.log_every = 10000
        trained = opt.optimize()
        return model, trained.variables, x, y

    def test_calibrated_quantize_accuracy_within_1pct(self):
        from bigdl_tpu.nn.quantized import calibrate, quantize

        model, variables, x, y = self._trained_mlp()

        def top1(variables_, mod):
            out, _ = mod.forward(variables_["params"], variables_["state"],
                                 jnp.asarray(x), training=False)
            return float((np.asarray(out).argmax(1) == y).mean())

        acc_f32 = top1(variables, model)
        calib = calibrate(model, variables,
                          [x[i:i + 64] for i in range(0, 256, 64)],
                          method="percentile", percentile=99.9)
        assert len(calib) == 2  # both Linear leaves calibrated
        q_model, q_vars = quantize(model, variables, calib=calib)
        # calibrated scales recorded as static act_scale params
        flat = str(q_vars["params"])
        assert "act_scale" in flat
        acc_int8 = top1(q_vars, q_model)
        assert acc_f32 - acc_int8 < 0.01, (acc_f32, acc_int8)

    def test_minmax_vs_percentile_scales(self):
        from bigdl_tpu import nn
        from bigdl_tpu.nn.quantized import calibrate

        model = nn.Sequential([nn.Linear(8, 4)])
        rs = np.random.RandomState(1)
        x = rs.randn(64, 8).astype(np.float32)
        x[0, 0] = 100.0  # outlier
        v = model.init(jax.random.PRNGKey(0), jnp.asarray(x))
        mm = calibrate(model, v, [x], method="minmax")
        pc = calibrate(model, v, [x], method="percentile", percentile=99.0)
        (k,) = mm.keys()
        assert mm[k] > 0.5          # dominated by the outlier (100/127)
        assert pc[k] < 0.1 * mm[k]  # percentile clips it away

    def test_nano_quantize_with_calibration(self):
        from bigdl_tpu.nano.inference import InferenceOptimizer

        model, variables, x, y = self._trained_mlp()
        tm = InferenceOptimizer.quantize(
            model, variables, sample=x[:64], precision="int8",
            calib_data=[x[64:128], x[128:192]])
        out = np.asarray(tm(x[:64]))
        acc = (out.argmax(1) == y[:64]).mean()
        assert acc > 0.8

    def test_quantize_and_calibrate_keras_functional_model(self):
        """Regression: quantize/calibrate must descend keras functional
        Models (params keyed by node name), not just Containers."""
        from bigdl_tpu import nn
        from bigdl_tpu.keras.engine import Input, Model
        from bigdl_tpu.nn.quantized import (QuantizedLinear, calibrate,
                                            quantize)

        inp = Input((8,))
        h = nn.Linear(8, 16)(inp)
        h = nn.ReLU()(h)
        out = nn.Linear(16, 3)(h)
        model = Model(inp, out)
        rs = np.random.RandomState(0)
        x = rs.randn(32, 8).astype(np.float32)
        v = model.init(jax.random.PRNGKey(0), jnp.asarray(x))

        calib = calibrate(model, v, [x], method="minmax")
        assert len(calib) == 2

        q_model, q_vars = quantize(model, v, calib=calib)
        qlayers = [n.layer for n in q_model.order
                   if isinstance(n.layer, QuantizedLinear)]
        assert len(qlayers) == 2
        assert "act_scale" in str(q_vars["params"])

        y_f32, _ = model.apply(v, jnp.asarray(x))
        y_q, _ = q_model.apply(q_vars, jnp.asarray(x))
        # int8 with calibrated scales stays close to float
        err = np.abs(np.asarray(y_q) - np.asarray(y_f32)).max()
        assert err < 0.1 * np.abs(np.asarray(y_f32)).max()
        # the ORIGINAL model is untouched
        assert not any(isinstance(n.layer, QuantizedLinear)
                       for n in model.order)

    def test_nano_optimize_with_calibrated_variant(self):
        """Accuracy-vs-speed harness: optimize() ranks fp32 / int8 /
        int8_calibrated under an accuracy budget."""
        from bigdl_tpu.nano.inference import InferenceOptimizer

        model, variables, x, y = self._trained_mlp()

        def acc(outputs):
            return float((outputs.argmax(1) == y[:64]).mean())

        res = InferenceOptimizer.optimize(
            model, variables, x[:64],
            methods=("fp32", "int8", "int8_calibrated"),
            repeats=3, accuracy_fn=acc, accuracy_budget=0.02,
            calib_data=[x[64:192]])
        assert res.results["fp32"]["status"] == "ok"
        assert res.results["int8_calibrated"]["status"] in (
            "ok", "accuracy_drop")
        best, name = res.get_best_model()
        assert name in res.results and best is not None
        assert "int8_calibrated" in res.summary()


class TestPerChannelActivationQuant:
    """VERDICT r3 #6: per-channel calibration — activation scales fold into
    the int8 weight rows, so an outlier input channel no longer dictates
    the whole tensor's quantization resolution."""

    def _outlier_data(self, k=16, n=256):
        rs = np.random.RandomState(0)
        x = rs.randn(n, k).astype(np.float32)
        x[:, 0] *= 60.0          # one outlier channel
        return x

    def test_per_channel_beats_per_tensor_linear(self):
        from bigdl_tpu import nn
        from bigdl_tpu.nn.quantized import calibrate, quantize

        x = self._outlier_data()
        model = nn.Sequential([nn.Linear(16, 8)])
        variables = model.init(jax.random.PRNGKey(0), x[:1])
        ref, _ = model.forward(variables["params"], variables["state"],
                               jnp.asarray(x), training=False)
        errs = {}
        for gran in ("tensor", "channel"):
            calib = calibrate(model, variables, [x], method="minmax",
                              granularity=gran)
            qm, qv = quantize(model, variables, calib=calib)
            out, _ = qm.forward(qv["params"], qv["state"], jnp.asarray(x),
                                training=False)
            errs[gran] = float(np.abs(np.asarray(out)
                                      - np.asarray(ref)).mean())
        assert errs["channel"] < errs["tensor"], errs

    def test_per_channel_beats_per_tensor_conv(self):
        from bigdl_tpu import nn
        from bigdl_tpu.nn.quantized import calibrate, quantize

        rs = np.random.RandomState(1)
        x = rs.randn(8, 8, 8, 6).astype(np.float32)
        x[..., 0] *= 40.0        # outlier input channel
        model = nn.Sequential([nn.Conv2D(6, 4, kernel_size=(3, 3),
                                         padding="same")])
        variables = model.init(jax.random.PRNGKey(0), x[:1])
        ref, _ = model.forward(variables["params"], variables["state"],
                               jnp.asarray(x), training=False)
        errs = {}
        for gran in ("tensor", "channel"):
            calib = calibrate(model, variables, [x], method="minmax",
                              granularity=gran)
            qm, qv = quantize(model, variables, calib=calib)
            out, _ = qm.forward(qv["params"], qv["state"], jnp.asarray(x),
                                training=False)
            errs[gran] = float(np.abs(np.asarray(out)
                                      - np.asarray(ref)).mean())
        assert errs["channel"] < errs["tensor"], errs

    def test_calibration_sweep_all_combos(self):
        """minmax/percentile x tensor/channel all produce working int8
        models (the VERDICT-requested sweep)."""
        from bigdl_tpu import nn
        from bigdl_tpu.nn.quantized import calibrate, quantize

        x = self._outlier_data()
        model = nn.Sequential([nn.Linear(16, 8), nn.ReLU(),
                               nn.Linear(8, 4)])
        variables = model.init(jax.random.PRNGKey(0), x[:1])
        ref, _ = model.forward(variables["params"], variables["state"],
                               jnp.asarray(x), training=False)
        for method in ("minmax", "percentile"):
            for gran in ("tensor", "channel"):
                calib = calibrate(model, variables, [x], method=method,
                                  granularity=gran)
                if gran == "channel":
                    assert all(np.ndim(v) == 1 for v in calib.values())
                qm, qv = quantize(model, variables, calib=calib)
                out, _ = qm.forward(qv["params"], qv["state"],
                                    jnp.asarray(x), training=False)
                err = float(np.abs(np.asarray(out)
                                   - np.asarray(ref)).mean())
                ref_mag = float(np.abs(np.asarray(ref)).mean())
                assert err < 0.25 * ref_mag, (method, gran, err, ref_mag)

    def test_granularity_validation(self):
        from bigdl_tpu import nn
        from bigdl_tpu.nn.quantized import calibrate

        model = nn.Sequential([nn.Linear(4, 2)])
        v = model.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.float32))
        with pytest.raises(ValueError, match="granularity"):
            calibrate(model, v, [np.zeros((2, 4), np.float32)],
                      granularity="row")


class TestQAT:
    """Quantization-aware training: fake-quant fine-tune -> int8 convert
    (beyond the reference's PTQ-only nn/quantized stack)."""

    def _setup(self):
        from bigdl_tpu.nn.layers import Linear, ReLU
        from bigdl_tpu.nn.module import Sequential

        rs = np.random.RandomState(0)
        x = rs.randn(256, 8).astype(np.float32)
        w_true = rs.randn(8, 1).astype(np.float32)
        y = x @ w_true
        model = Sequential([Linear(8, 32), ReLU(), Linear(32, 1)])
        variables = model.init(jax.random.PRNGKey(0), x[:2])
        return model, variables, x, y

    def _train(self, model, variables, x, y, steps=150, lr=0.05):
        import jax.numpy as jnp

        params, state = variables["params"], variables["state"]

        @jax.jit
        def step(p, s):
            def loss_fn(p):
                out, ns = model.forward(p, s, jnp.asarray(x), training=True)
                return jnp.mean((out - jnp.asarray(y)) ** 2), ns

            (l, ns), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
            return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), \
                ns, l

        for _ in range(steps):
            params, state, loss = step(params, state)
        return {"params": params, "state": state}, float(loss)

    def _mse(self, model, variables, x, y):
        import jax.numpy as jnp

        out, _ = model.apply(variables, jnp.asarray(x))
        return float(np.mean((np.asarray(out) - y) ** 2))

    def test_qat_roundtrip_and_conversion(self):
        from bigdl_tpu.nn.qat import QATLinear, convert_qat, prepare_qat
        from bigdl_tpu.nn.quantized import QuantizedLinear

        model, variables, x, y = self._setup()
        variables, _ = self._train(model, variables, x, y)
        fp32_mse = self._mse(model, variables, x, y)

        qat_model, qat_vars = prepare_qat(model, variables)
        # params are reused verbatim: same keys, same arrays
        assert set(qat_vars["params"].keys()) == set(
            variables["params"].keys())
        assert any(isinstance(m, QATLinear) for m in qat_model.layers)

        qat_vars, _ = self._train(qat_model, qat_vars, x, y, steps=80,
                                  lr=0.01)
        # EMA activation ranges were tracked
        amaxes = [float(s["act_amax"]) for s in
                  qat_vars["state"].values() if "act_amax" in s]
        assert amaxes and all(a > 0 for a in amaxes)

        int8_model, int8_vars = convert_qat(qat_model, qat_vars)
        assert any(isinstance(m, QuantizedLinear)
                   for m in int8_model.layers)
        # learned ranges became static calibration scales
        leaf = next(m for m in int8_model.layers
                    if isinstance(m, QuantizedLinear))
        k = int8_model._key(int8_model.layers.index(leaf))
        assert "act_scale" in int8_vars["params"][k]

        int8_mse = self._mse(int8_model, int8_vars, x, y)
        # int8 stays close to the fp32 model it was trained from
        assert int8_mse < max(4 * fp32_mse, 5e-2), (int8_mse, fp32_mse)

    def test_qat_on_keras_functional_model(self):
        """prepare_qat/convert_qat descend keras graphs like quantize."""
        from bigdl_tpu import nn
        from bigdl_tpu.keras.engine import Input, Model
        from bigdl_tpu.nn.qat import QATLinear, convert_qat, prepare_qat
        from bigdl_tpu.nn.quantized import QuantizedLinear

        inp = Input((8,))
        h = nn.Linear(8, 16)(inp)
        h = nn.ReLU()(h)
        out = nn.Linear(16, 3)(h)
        model = Model(inp, out)
        rs = np.random.RandomState(0)
        x = rs.randn(32, 8).astype(np.float32)
        v = model.init(jax.random.PRNGKey(0), jnp.asarray(x))

        qat_model, qat_vars = prepare_qat(model, v)
        assert sum(isinstance(n.layer, QATLinear)
                   for n in qat_model.order) == 2
        # params reused verbatim; a forward in training mode tracks ranges
        y, st = qat_model.forward(qat_vars["params"], qat_vars["state"],
                                  jnp.asarray(x), training=True)
        qat_vars = {"params": qat_vars["params"], "state": st}
        amaxes = [float(s["act_amax"]) for s in st.values()
                  if isinstance(s, dict) and "act_amax" in s]
        assert len(amaxes) == 2 and all(a > 0 for a in amaxes)

        int8_model, int8_vars = convert_qat(qat_model, qat_vars)
        assert sum(isinstance(n.layer, QuantizedLinear)
                   for n in int8_model.order) == 2
        y_f32, _ = model.apply(v, jnp.asarray(x))
        y_q, _ = int8_model.apply(int8_vars, jnp.asarray(x))
        err = np.abs(np.asarray(y_q) - np.asarray(y_f32)).max()
        assert err < 0.15 * np.abs(np.asarray(y_f32)).max()

    def test_qat_eval_before_training_passes_through(self):
        """amax untracked (eval before any train step) must NOT quantize
        with the epsilon floor — that collapses activations to ~0."""
        from bigdl_tpu.nn.qat import prepare_qat

        model, variables, x, y = self._setup()
        qat_model, qat_vars = prepare_qat(model, variables)
        y_fp32, _ = model.apply(variables, jnp.asarray(x))
        y_qat, _ = qat_model.apply(qat_vars, jnp.asarray(x))
        # weights fake-quantize (small error); activations pass through
        rel = (np.abs(np.asarray(y_qat) - np.asarray(y_fp32)).max()
               / (np.abs(np.asarray(y_fp32)).max() + 1e-8))
        assert rel < 0.05, rel

    def test_qat_beats_naive_ptq_on_outlier_activations(self):
        """An input channel with a huge range wrecks per-tensor PTQ's
        activation grid; QAT's fine-tune adapts the weights to it."""
        from bigdl_tpu.nn.layers import Linear
        from bigdl_tpu.nn.module import Sequential
        from bigdl_tpu.nn.qat import convert_qat, prepare_qat
        from bigdl_tpu.nn.quantized import calibrate, quantize

        rs = np.random.RandomState(1)
        x = rs.randn(256, 8).astype(np.float32)
        x[:, 0] *= 60.0  # outlier channel
        y = (x @ rs.randn(8, 1).astype(np.float32) / 60.0)
        model = Sequential([Linear(8, 1)])
        variables = model.init(jax.random.PRNGKey(0), x[:2])
        variables, _ = self._train(model, variables, x, y, steps=400,
                                   lr=2e-4)

        # per-tensor static PTQ (minmax) — the naive reference path
        calib = calibrate(model, variables, [x], method="minmax",
                          granularity="tensor")
        ptq_model, ptq_vars = quantize(model, variables, calib=calib)
        ptq_mse = self._mse(ptq_model, ptq_vars, x, y)

        qat_model, qat_vars = prepare_qat(model, variables)
        qat_vars, _ = self._train(qat_model, qat_vars, x, y, steps=300,
                                  lr=2e-4)
        int8_model, int8_vars = convert_qat(qat_model, qat_vars)
        qat_mse = self._mse(int8_model, int8_vars, x, y)

        assert qat_mse <= ptq_mse * 1.05, (qat_mse, ptq_mse)


class TestGradientChecker:
    """Finite-difference validation of the HAND-WRITTEN custom_vjp
    backwards — reference nn/GradientChecker.scala; autodiff ops don't
    need it, the Pallas kernels' bwd rules do."""

    def test_flash_attention_bwd_matches_finite_differences(self):
        from bigdl_tpu.ops.flash_attention import flash_attention
        from bigdl_tpu.utils.gradcheck import check_grad

        rs = np.random.RandomState(0)
        q = rs.randn(1, 1, 8, 4).astype(np.float32) * 0.5
        kv = jnp.asarray(rs.randn(1, 1, 8, 4), jnp.float32) * 0.5

        def loss(qq):
            o = flash_attention(qq, kv, kv, causal=True, interpret=True)
            # a non-uniform weighting so every grad component matters
            w = jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape)
            return jnp.sum(o * w) / o.size

        check_grad(loss, q, eps=1e-2, samples=16)

    def test_fused_layernorm_bwd_matches_finite_differences(self):
        from bigdl_tpu.ops.fused import fused_layernorm
        from bigdl_tpu.utils.gradcheck import check_grad

        rs = np.random.RandomState(1)
        x = rs.randn(4, 16).astype(np.float32)
        g = jnp.asarray(rs.randn(16), jnp.float32)
        b = jnp.asarray(rs.randn(16), jnp.float32)

        def loss(xx):
            o = fused_layernorm(xx, g, b, interpret=True)
            w = jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape)
            return jnp.sum(o * w) / o.size

        check_grad(loss, x, eps=1e-2, samples=24)

    def test_checker_catches_a_wrong_gradient(self):
        """The checker itself must fail on a broken custom backward."""
        import jax

        from bigdl_tpu.utils.gradcheck import check_grad

        @jax.custom_vjp
        def broken_square(x):
            return jnp.sum(x * x)

        def fwd(x):
            return jnp.sum(x * x), x

        def bwd(res, ct):
            return (3.0 * res * ct,)  # wrong: d(x^2)/dx is 2x, not 3x

        broken_square.defvjp(fwd, bwd)
        x = np.random.RandomState(2).randn(8).astype(np.float32)
        with pytest.raises(AssertionError, match="gradient mismatch"):
            check_grad(broken_square, x, samples=8)


class TestWeightOnly:
    """Weight-only int8 (int8 weights, full-precision compute) — the
    decode-bound serving trade; beyond the reference's always-quantized
    activations."""

    def test_weight_only_closer_than_full_int8(self):
        from bigdl_tpu.nn.layers import Linear, ReLU
        from bigdl_tpu.nn.module import Sequential
        from bigdl_tpu.nn.quantized import WeightOnlyLinear, quantize

        rng = np.random.default_rng(0)
        model = Sequential([Linear(32, 64), ReLU(), Linear(64, 8)])
        x = _rand(rng, 16, 32)
        v = model.init(jax.random.PRNGKey(0), x)
        y_ref, _ = model.apply(v, x)

        wo_model, wo_vars = quantize(model, v, weight_only=True)
        assert isinstance(wo_model.layers[0], WeightOnlyLinear)
        y_wo, _ = wo_model.apply(wo_vars, x)

        full_model, full_vars = quantize(model, v)
        y_full, _ = full_model.apply(full_vars, x)

        err_wo = np.abs(np.asarray(y_wo) - np.asarray(y_ref)).max()
        err_full = np.abs(np.asarray(y_full) - np.asarray(y_ref)).max()
        # no activation-quantization error -> strictly tighter
        assert err_wo <= err_full, (err_wo, err_full)
        assert err_wo < 0.05 * np.abs(np.asarray(y_ref)).max()
        # weights really are int8 on disk
        assert wo_vars["params"][wo_model._key(0)]["weight_q"].dtype == \
            jnp.int8

    def test_weight_only_conv_and_nano_surface(self):
        from bigdl_tpu.nano.inference import InferenceOptimizer
        from bigdl_tpu.nn.layers import Conv2D
        from bigdl_tpu.nn.module import Sequential
        from bigdl_tpu.nn.quantized import WeightOnlyConv2D, quantize

        rng = np.random.default_rng(1)
        model = Sequential([Conv2D(3, 8, 3, padding="SAME", groups=1)])
        x = _rand(rng, 2, 8, 8, 3)
        v = model.init(jax.random.PRNGKey(0), x)
        y_ref, _ = model.apply(v, x)
        wo_model, wo_vars = quantize(model, v, weight_only=True)
        assert isinstance(wo_model.layers[0], WeightOnlyConv2D)
        y_wo, _ = wo_model.apply(wo_vars, x)
        err = np.abs(np.asarray(y_wo) - np.asarray(y_ref)).max()
        assert err < 0.05 * np.abs(np.asarray(y_ref)).max()

        tm = InferenceOptimizer.quantize(model, v, sample=x,
                                         precision="int8_wo")
        out = np.asarray(tm(x))
        assert out.shape == np.asarray(y_ref).shape


class TestBlockSparse:
    """Block-sparse matmul (BLaST FFN path, docs/performance.md
    §Block-sparse FFN) — parity vs a dense-masked jnp reference in
    interpret mode, the exact code path Mosaic compiles on TPU."""

    def _mask(self, rng, nkb, nnb, density=0.6):
        m = rng.random((nkb, nnb)) < density
        m[0, 0] = True  # never a fully-empty mask
        return m

    @pytest.mark.parametrize("shape", [(32, 64, 48), (37, 64, 48),
                                       (16, 96, 32)])
    def test_matmul_parity_vs_dense_masked(self, shape):
        from bigdl_tpu.ops.block_sparse import (block_sparse_matmul,
                                                expand_mask)

        rng = np.random.default_rng(0)
        m, k, n = shape
        bk, bn = 16, 16
        x = _rand(rng, m, k)
        w = _rand(rng, k, n)
        mask = self._mask(rng, -(-k // bk), -(-n // bn))
        out = block_sparse_matmul(x, w, mask, block_k=bk, block_n=bn,
                                  interpret=True)
        ref = np.asarray(x) @ (np.asarray(w)
                               * expand_mask(mask, k, n, bk, bn))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                   atol=2e-4)

    def test_empty_output_column_yields_zeros(self):
        from bigdl_tpu.ops.block_sparse import block_sparse_matmul

        rng = np.random.default_rng(1)
        x = _rand(rng, 16, 32)
        w = _rand(rng, 32, 32)
        mask = np.ones((2, 2), bool)
        mask[:, 1] = False  # second output block-column fully pruned
        out = np.asarray(block_sparse_matmul(x, w, mask, block_k=16,
                                             block_n=16, interpret=True))
        assert np.all(out[:, 16:] == 0.0)
        ref = np.asarray(x) @ np.asarray(w)
        np.testing.assert_allclose(out[:, :16], ref[:, :16], rtol=2e-4,
                                   atol=2e-4)

    def test_gradients_match_dense_masked(self):
        from bigdl_tpu.ops.block_sparse import (block_sparse_matmul,
                                                expand_mask)

        rng = np.random.default_rng(2)
        k, n = 48, 32
        bk, bn = 16, 16
        x = _rand(rng, 8, k)
        w = _rand(rng, k, n)
        mask = self._mask(rng, 3, 2)
        em = jnp.asarray(expand_mask(mask, k, n, bk, bn), jnp.float32)

        def loss_sparse(x, w):
            y = block_sparse_matmul(x, w, mask, block_k=bk, block_n=bn,
                                    interpret=True)
            return jnp.sum(y ** 2)

        def loss_ref(x, w):
            return jnp.sum((x @ (w * em)) ** 2)

        g1 = jax.grad(loss_sparse, argnums=(0, 1))(x, w)
        g2 = jax.grad(loss_ref, argnums=(0, 1))(x, w)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)
        # the weight grad is masked: pruned blocks receive exactly zero
        dw = np.asarray(g1[1])
        assert np.all(dw[np.asarray(em) == 0.0] == 0.0)

    def test_traced_mask_rejected(self):
        from bigdl_tpu.ops.block_sparse import block_sparse_matmul

        x = jnp.ones((8, 16))
        w = jnp.ones((16, 16))

        def f(m):
            return block_sparse_matmul(x, w, m, block_k=16, block_n=16,
                                       interpret=True)

        with pytest.raises(TypeError, match="concrete"):
            jax.jit(f)(jnp.ones((1, 1), bool))

    def test_linear_module_prune_and_parity(self):
        from bigdl_tpu.ops.block_sparse import (BlockSparseLinear,
                                                expand_mask)

        rng = np.random.default_rng(3)
        x = _rand(rng, 9, 64)
        lin = BlockSparseLinear(64, 48, block_shape=(16, 16),
                                target_sparsity=0.5)
        v = lin.init(jax.random.PRNGKey(0), x)
        y_dense, _ = lin.apply(v, x)
        # dense warmup: all-ones mask == plain Linear math
        w = np.asarray(v["params"]["weight"])
        b = np.asarray(v["params"]["bias"])
        np.testing.assert_allclose(np.asarray(y_dense), x @ w + b,
                                   rtol=1e-4, atol=1e-4)
        ach = lin.prune_to(v["params"], 0.5)
        assert ach == pytest.approx(0.5)
        y_sparse, _ = lin.apply(v, x)
        em = expand_mask(lin.mask, 64, 48, 16, 16)
        np.testing.assert_allclose(np.asarray(y_sparse),
                                   x @ (w * em) + b, rtol=1e-3, atol=1e-3)
        # magnitude pruning keeps the heavy blocks: surviving block L1
        # mass >= any pruned block's
        scores = np.abs(w).reshape(4, 16, 3, 16).sum(axis=(1, 3))
        assert scores[lin.mask].min() >= scores[~lin.mask].max()

    def test_prune_is_monotone_no_resurrection(self):
        from bigdl_tpu.ops.block_sparse import BlockSparseLinear

        rng = np.random.default_rng(4)
        x = _rand(rng, 4, 64)
        lin = BlockSparseLinear(64, 64, block_shape=(16, 16))
        v = lin.init(jax.random.PRNGKey(1), x)
        lin.prune_to(v["params"], 0.25)
        kept_25 = lin.mask.copy()
        lin.prune_to(v["params"], 0.5)
        # every survivor of the deeper prune survived the shallow one
        assert np.all(kept_25[lin.mask])
        # and pruning shallower afterwards never resurrects
        lin.prune_to(v["params"], 0.25)
        assert lin.sparsity() == pytest.approx(0.5)

    def test_transformer_ffn_sparsity_end_to_end(self):
        from bigdl_tpu.nn.attention import Transformer
        from bigdl_tpu.ops.block_sparse import (iter_sparse_modules,
                                                prune_model_to_sparsity)

        model = Transformer(vocab_size=64, hidden_size=32, num_heads=2,
                            ffn_size=64, num_layers=2, dropout=0.0,
                            mode="lm", ffn_sparsity=0.5,
                            sparse_block=(16, 16))
        ids = jnp.asarray(np.arange(24).reshape(2, 12) % 64)
        v = model.init(jax.random.PRNGKey(0), ids)
        y_dense, _ = model.apply(v, ids)
        # exact capture-based binding (sample_inputs): one real forward
        # records which params dict each sparse module receives
        achieved = prune_model_to_sparsity(model, v, 0.5,
                                           sample_inputs=(ids,))
        # both FFN linears of both layers pruned
        assert len(achieved) == 4
        assert all(s == pytest.approx(0.5) for s in achieved.values())
        for _, mod in iter_sparse_modules(model):
            assert mod.density() == pytest.approx(0.5)
        y_sparse, _ = model.apply(v, ids)
        assert np.all(np.isfinite(np.asarray(y_sparse)))
        assert not np.allclose(np.asarray(y_sparse), np.asarray(y_dense))
        # training still differentiates through the sparse kernels
        g = jax.grad(lambda p: model.forward(
            p, {}, ids)[0].sum())(v["params"])
        leaves = jax.tree_util.tree_leaves(g)
        assert leaves and all(np.all(np.isfinite(np.asarray(l)))
                              for l in leaves)

    def test_pruning_schedule_monotone(self):
        from bigdl_tpu.ops.block_sparse import BlockPruningSchedule

        sch = BlockPruningSchedule(0.75, warmup_steps=10, ramp_steps=40,
                                   n_events=4)
        vals = [sch.sparsity_at(s) for s in range(80)]
        assert all(b >= a for a, b in zip(vals, vals[1:]))
        assert all(v == 0.0 for v in vals[:10])       # dense warmup
        assert vals[-1] == pytest.approx(0.75)        # reaches target
        steps = sch.prune_steps()
        assert steps and len(steps) <= 4
        # prune_steps are exactly where sparsity_at increases
        for s in steps:
            assert sch.sparsity_at(s) > sch.sparsity_at(s - 1)
        # degenerate schedules
        assert BlockPruningSchedule(0.0, 0, 0).prune_steps() == []
        assert BlockPruningSchedule(0.5, 5, 0).sparsity_at(5) == 0.5

    def test_mask_collect_apply_roundtrip(self):
        from bigdl_tpu.nn.attention import Transformer
        from bigdl_tpu.ops.block_sparse import (apply_masks, collect_masks,
                                                prune_model_to_sparsity)

        mk = lambda: Transformer(vocab_size=32, hidden_size=16,
                                 num_heads=2, ffn_size=32, num_layers=1,
                                 dropout=0.0, mode="lm", ffn_sparsity=0.5,
                                 sparse_block=(16, 16))
        model = mk()
        ids = jnp.asarray(np.arange(8).reshape(1, 8) % 32)
        v = model.init(jax.random.PRNGKey(0), ids)
        prune_model_to_sparsity(model, v, 0.5)
        masks = collect_masks(model)
        fresh = mk()
        fresh.init(jax.random.PRNGKey(0), ids)
        assert apply_masks(fresh, masks) == len(masks) > 0
        y1, _ = model.apply(v, ids)
        y2, _ = fresh.apply(v, ids)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


class TestAutotune:
    """Tiles a call leaves as None (docs/performance.md §Kernel tiles)
    change the tiling, never the math."""

    def test_kernels_consult_resolution_without_breaking_parity(self):
        """Tiles left as None give the output the rule's tiles give when
        passed explicitly."""
        import importlib
        fa = importlib.import_module("bigdl_tpu.ops.flash_attention")
        from bigdl_tpu.ops import fused, quantized

        rng = np.random.default_rng(0)
        q = _rand(rng, 1, 2, 24, 8)
        rule = fa.default_blocks("fwd", 24, 24, 8, 4)
        a = flash_attention(q, q, q, causal=True, interpret=True)
        b = flash_attention(q, q, q, causal=True, interpret=True, **rule)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        x = _rand(rng, 9, 17)
        g2 = _rand(rng, 17)
        b2 = _rand(rng, 17)
        y_auto = fused_layernorm(x, g2, b2, interpret=True)
        y_expl = fused_layernorm(x, g2, b2, block_rows=fused.BLOCK_ROWS,
                                 interpret=True)
        np.testing.assert_array_equal(np.asarray(y_auto), np.asarray(y_expl))
        x8 = jnp.asarray(rng.integers(-127, 128, (40, 70)), jnp.int8)
        w8 = jnp.asarray(rng.integers(-127, 128, (70, 30)), jnp.int8)
        bm, bn, bk = quantized.TILES
        np.testing.assert_array_equal(
            np.asarray(int8_matmul(x8, w8, interpret=True)),
            np.asarray(int8_matmul(x8, w8, block_m=bm, block_n=bn,
                                   block_k=bk, interpret=True)))


class TestKernelTiles:
    """The tiles each kernel takes where a call leaves them as None: one
    rule or constant in the kernel's own module; explicit kwargs win,
    axis by axis.  Read off the kernel's inner call, not timed."""

    @staticmethod
    def _pallas_calls(monkeypatch):
        """Record each ``pallas_call``'s arguments and hand back zeros of
        its result: the tiles are what is read, nothing is run."""
        from jax.experimental import pallas as pl

        seen = []

        def spy(kernel, **kw):
            seen.append(kw)
            out = kw["out_shape"]
            return lambda *args: jnp.zeros(out.shape, out.dtype)

        monkeypatch.setattr(pl, "pallas_call", spy)
        return seen

    def _fused_layernorm(self, monkeypatch, **kw):
        from bigdl_tpu.ops import fused

        seen = []
        monkeypatch.setattr(fused, "_fused_ln",
                            lambda x, g, b, eps, rows, interp:
                            seen.append(rows) or x)
        x = jnp.zeros((1024, 16), jnp.float32)
        fused.fused_layernorm(x, x[0], x[0], **kw)
        return seen[-1]

    def _int8_matmul(self, monkeypatch, **kw):
        from bigdl_tpu.ops import quantized

        seen = self._pallas_calls(monkeypatch)
        x = jnp.zeros((1024, 1024), jnp.int8)
        quantized.int8_matmul(x, x, **kw)
        (bm, bk), (_, bn) = (s.block_shape for s in seen[-1]["in_specs"])
        return bm, bn, bk

    def _block_sparse_matmul(self, monkeypatch, **kw):
        from bigdl_tpu.ops import block_sparse

        seen = []
        monkeypatch.setattr(block_sparse, "_bsmm",
                            lambda x, w, mask, bm, bk, bn, interp:
                            seen.append(bm) or x @ w)
        x = jnp.zeros((512, 64), jnp.float32)
        block_sparse.block_sparse_matmul(
            x, jnp.zeros((64, 64), jnp.float32), np.ones((2, 2), bool),
            block_k=32, block_n=32, **kw)
        return seen[-1]

    def _paged_decode(self, monkeypatch, heads, **kw):
        from bigdl_tpu.ops.flash_attention import paged_decode_attention

        seen = self._pallas_calls(monkeypatch)
        slots, page, d, nb = 2, 8, 16, 2
        pages = jnp.zeros((slots * nb, heads, page, d), jnp.float32)
        paged_decode_attention(
            jnp.zeros((slots, heads, d), jnp.float32), pages, pages,
            jnp.zeros((slots, nb), jnp.int32),
            jnp.zeros((slots,), jnp.int32), **kw)
        return heads // seen[-1]["grid_spec"].grid[1]

    @pytest.mark.parametrize("kernel,kwargs,want", [
        ("fused_layernorm", {}, 256),
        ("fused_layernorm", {"block_rows": 64}, 64),
        ("int8_matmul", {}, (256, 256, 512)),
        ("int8_matmul", {"block_n": 128}, (256, 128, 512)),
        ("block_sparse_matmul", {}, 128),
        ("paged_decode", {"heads": 8}, 4),
        ("paged_decode", {"heads": 6}, 2),
        ("paged_decode", {"heads": 1}, 1),
        ("paged_decode", {"heads": 8, "block_h": 8}, 8),
    ])
    def test_tiles_left_none_take_the_kernel_rule(self, monkeypatch, kernel,
                                                   kwargs, want):
        assert getattr(self, f"_{kernel}")(monkeypatch, **kwargs) == want


class TestBlockSparseServing:
    """The pruned FFN serves through InferenceModel unchanged: the mask
    is a compile-time constant of the jitted forward."""

    def test_inference_model_serves_pruned_transformer(self):
        from bigdl_tpu.nn.attention import Transformer
        from bigdl_tpu.ops.block_sparse import prune_model_to_sparsity
        from bigdl_tpu.serving.inference_model import InferenceModel

        model = Transformer(vocab_size=32, hidden_size=16, num_heads=2,
                            ffn_size=32, num_layers=1, dropout=0.0,
                            mode="lm", ffn_sparsity=0.5,
                            sparse_block=(16, 16))
        ids = np.arange(16).reshape(2, 8).astype(np.int32) % 32
        v = model.init(jax.random.PRNGKey(0), jnp.asarray(ids))
        prune_model_to_sparsity(model, v, 0.5)
        ref, _ = model.apply(v, jnp.asarray(ids))
        im = InferenceModel(model, v, batch_buckets=(2,))
        out = im.predict(ids)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


class TestPruneBinding:
    def test_capture_binding_survives_same_shaped_dense_linear(self):
        """A dense Linear with the SAME (in, out) ahead of the sparse
        layer must not be mistaken for it: capture-based binding prunes
        by the SPARSE layer's own weights."""
        from bigdl_tpu.nn.layers import Linear, ReLU
        from bigdl_tpu.nn.module import Sequential
        from bigdl_tpu.ops.block_sparse import (BlockSparseLinear,
                                                prune_model_to_sparsity)

        rng = np.random.default_rng(7)
        model = Sequential([Linear(32, 32), ReLU(),
                            BlockSparseLinear(32, 32, block_shape=(16, 16))])
        x = _rand(rng, 4, 32)
        v = model.init(jax.random.PRNGKey(0), x)
        # make the sparse layer's block magnitudes unambiguous: one block
        # overwhelmingly heavy
        key = model._key(2)
        w = np.asarray(v["params"][key]["weight"]).copy()
        w[:16, :16] = 100.0
        v["params"][key]["weight"] = jnp.asarray(w)
        achieved = prune_model_to_sparsity(model, v, 0.75,
                                           sample_inputs=(x,))
        assert list(achieved.values()) == [0.75]
        (_, mod), = [pm for pm in __import__(
            "bigdl_tpu.ops.block_sparse",
            fromlist=["iter_sparse_modules"]).iter_sparse_modules(model)]
        assert mod.mask[0, 0] and mod.mask.sum() == 1  # the heavy block


class TestSparseMaskResume:
    def test_masks_ride_checkpoint_and_restore_on_resume(self, tmp_path):
        """A pruned FFN's masks are host module state: they ride the
        checkpoint driver_state, and a FRESH process (dense all-ones
        modules) resuming from that checkpoint gets them back — without
        this, a preempted sparse run silently resumes dense."""
        from bigdl_tpu import nn, optim
        from bigdl_tpu.data.dataset import ArrayDataSet
        from bigdl_tpu.ops.block_sparse import (BlockSparseLinear,
                                                collect_masks,
                                                prune_model_to_sparsity)
        from bigdl_tpu.runtime.engine import Engine, init_engine

        Engine.reset()
        init_engine(data=1)
        rs = np.random.RandomState(0)
        x = rs.rand(64, 32).astype(np.float32)
        y = (x.sum(1) > 16).astype(np.int32)

        def mk():
            return nn.Sequential([
                BlockSparseLinear(32, 32, block_shape=(16, 16),
                                  target_sparsity=0.5),
                nn.ReLU(), nn.Linear(32, 2)])

        def run(model, epochs):
            opt = optim.Optimizer(model, ArrayDataSet(x, y),
                                  nn.CrossEntropyCriterion(),
                                  batch_size=32)
            opt.set_optim_method(optim.Adam(learning_rate=1e-3))
            opt.set_end_when(optim.Trigger.max_epoch(epochs))
            opt.set_checkpoint(str(tmp_path),
                               optim.Trigger.several_iteration(2))
            opt.log_every = 10000
            return opt.optimize()

        m1 = mk()
        v = m1.init(jax.random.PRNGKey(0), x[:1])
        prune_model_to_sparsity(m1, v, 0.5, sample_inputs=(x[:1],))
        masks1 = collect_masks(m1)
        assert any(not np.asarray(m).all() for m in masks1.values())
        run(m1, 1)

        # "fresh process": new modules (all-ones masks), same ckpt dir
        m2 = mk()
        trained = run(m2, 2)  # resumes epoch 1's checkpoint, trains on
        assert collect_masks(m2) == masks1
        out, _ = m2.apply(trained.variables, x)
        assert np.all(np.isfinite(np.asarray(out)))
