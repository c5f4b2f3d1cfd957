"""The attention kernels' forward residuals kept across a layer's
``jax.checkpoint``: ``out`` and ``lse`` are named inside the flash and the
block-sparse kernels' VJP forward rules, both decoders' layer checkpoints
save those names (``ops.common.layer_remat_policy``), and the backward then
reads them instead of running the forward kernel again.  Jaxprs are counted
at tiny shapes, kernels in interpret mode; gradients are compared with the
same layer under a plain ``jax.checkpoint``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.models.hybrid_moe_lm import HybridMoEConfig, HybridMoELM
from bigdl_tpu.models.mla_moe_lm import MLAMoEConfig, MLAMoELM
from bigdl_tpu.nn.sparse_linear_attention import (SELECTION,
                                                  SparseBlockAttention)
from bigdl_tpu.ops.common import layer_remat_policy
from bigdl_tpu.ops.flash_attention import flash_attention
from bigdl_tpu.ops.sparse_attention import select_blocks, sparse_attention

SELECT = dict(kernel=32, stride=16, block=64, topk=4, init_blocks=1,
              window=128)


def normal(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of the jaxprs in its equations'
    parameters (remat, jit, cond, custom rules) included, but not the
    kernels' bodies."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def kernels(fn, *args):
    """The ``pallas_call`` equations of ``fn``'s jaxpr."""
    return [e for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "pallas_call"]


def forward_kernels(fn, *args):
    """The attention forward kernels among them: the calls that give back
    an ``lse`` column, ``(..., 1)``, beside their output."""
    return [e for e in kernels(fn, *args) if len(e.params["out_avals"]) == 2
            and e.params["out_avals"][1].shape[-1] == 1]


def flash_layer(w, x):
    """A projection in front of the kernel, as a decoder layer has."""
    q, k, v = (x @ w[i] for i in range(3))
    return flash_attention(q, k[:, :2], v[:, :2], causal=True, block_q=128,
                           block_k=128)


def sparse_layer(w, x):
    sel = select_blocks(x, x[:, :, 0], **SELECT)
    q, k, v = x @ w[0], x[:, :, 0] @ w[1], x[:, :, 0] @ w[2]
    return sparse_attention(q, k, v, sel, block_q=128, block_k=128)


LAYERS = {"flash": (flash_layer, (1, 4, 256, 32)),
          "sparse": (sparse_layer, (1, 1, 2, 512, 32))}


def _grad(layer, policy):
    f = jax.checkpoint(layer) if policy is None else jax.checkpoint(
        layer, policy=policy)
    return jax.grad(lambda w, x: jnp.sum(f(w, x) ** 2), (0, 1))


@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_kept_residuals_spare_the_forward_kernel(kind):
    layer, shape = LAYERS[kind]
    w, x = normal(1, 3, 32, 32) * 0.2, normal(2, *shape)
    plain, kept = _grad(layer, None), _grad(layer, layer_remat_policy())
    # forward, dq, dk/dv; the plain checkpoint runs the forward again
    assert len(kernels(plain, w, x)) == 4
    assert len(kernels(kept, w, x)) == 3
    assert len(forward_kernels(kept, w, x)) == 1
    for a, b in zip(jax.jit(kept)(w, x), jax.jit(plain)(w, x)):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("names,n_kernels,n_top_k", [
    (None, 4, 2),             # a plain checkpoint: everything again
    ((), 3, 2),               # out and lse kept, the selection recomputed
    ((SELECTION,), 3, 1),     # the hybrid decoder's policy: both kept
])
def test_sparse_mixer_keeps_its_selection_beside_the_residuals(
        names, n_kernels, n_top_k):
    mixer = SparseBlockAttention(32, 2, 1, 16, dense_len=128, kernel_size=32,
                                 kernel_stride=16, block_size=64, topk=4,
                                 window_size=128)
    x = normal(3, 1, 256, 32)
    p = mixer.init(jax.random.PRNGKey(4), x)["params"]
    layer = lambda p, x: mixer.mix(p, x)[0]
    grad = _grad(layer, None if names is None else layer_remat_policy(*names))
    assert len(kernels(grad, p, x)) == n_kernels
    assert str(jax.make_jaxpr(grad)(p, x)).count("top_k") == n_top_k


def test_outside_a_checkpoint_the_programs_are_as_before():
    """The primal kernel carries no name; a gradient outside a checkpoint
    runs each kernel once, as it did before the names."""
    x = normal(5, 1, 4, 256, 32)
    fwd = jax.make_jaxpr(lambda x: flash_attention(x, x, x, causal=True))(x)
    assert [e.primitive.name for e in _eqns(fwd.jaxpr)].count("name") == 0
    assert len(kernels(lambda x: flash_attention(x, x, x), x)) == 1
    assert len(kernels(jax.grad(lambda x: flash_attention(
        x, x, x, causal=True).sum()), x)) == 3


def _hybrid():
    cfg = HybridMoEConfig.from_dict(dict(
        vocab_size=128, hidden_size=32, num_hidden_layers=3,
        layer_types=["full_attention", "minicpm4", "conv"],
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        intermediate_size=48, sparse_config=dict(
            block_size=64, dense_len=128, init_blocks=1, kernel_size=32,
            kernel_stride=16, topk=4, window_size=128)))
    model = HybridMoELM(cfg)
    model.attn.use_flash = True   # the TPU's path, in interpret mode here
    return model, 256, 2


def _mla():
    cfg = MLAMoEConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=3,
        num_attention_heads=2, q_lora_rank=16, kv_lora_rank=16,
        qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
        intermediate_size=64, moe_intermediate_size=16, n_routed_experts=4,
        num_experts_per_tok=2)
    model = MLAMoELM(cfg)
    model.attn.use_flash = True
    return model, 32, 3


@pytest.mark.parametrize("build", [_hybrid, _mla], ids=["hybrid", "mla"])
def test_each_attention_layer_runs_its_forward_kernel_once(build):
    """A training step of the tiny decoder: one forward kernel call per
    attention layer (two before the names: the checkpoint's rerun), and
    the backward pair beside it."""
    model, t, n_attn = build()
    ids = jnp.asarray(np.random.default_rng(39).integers(
        2, 128, (1, t), dtype=np.int32))
    v = model.init(jax.random.PRNGKey(0), ids)

    def loss(p):
        return jnp.mean(model.forward(p, v["state"], ids,
                                      training=True)[0] ** 2)

    grad = jax.grad(loss)
    assert len(forward_kernels(grad, v["params"])) == n_attn
    assert len(kernels(grad, v["params"])) == 3 * n_attn
