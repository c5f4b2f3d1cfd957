"""Mosaic accepts every Pallas kernel at the chip smoke's widths — checked
WITHOUT a chip: libtpu compiles ahead of time for a described v5e topology.

This checks the lowering (block shapes, layouts, scratch), not the numbers —
those need the device (``chip_smoke.py``).  ``slow``: each compile takes
seconds and libtpu start-up is not free, so tier-1 skips it; run it after
touching a kernel: ``pytest -m slow tests/test_mosaic_aot.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chip_smoke import FULL

pytestmark = pytest.mark.slow

_K = FULL["kern"]  # the smoke's widths: the LM's heads, the engine's pages
S, H, D, PAGE, NB, CHUNK = (_K["slots"], _K["heads"], _K["hd"], _K["page"],
                            _K["nb"], _K["chunk"])
FFN_K, FFN_N = _K["ffn"]


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu on this box
        pytest.skip(f"no ahead-of-time TPU topology here: {e}")
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _compile(fn, *args):
    jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("block_h", [None, 1, 4, 12])
def test_paged_decode_attention(v5e, quantized, block_h):
    """block_h=4 of 12 heads: the (1, 4, 64) q/out block of the (S, 12, 64)
    array is what Mosaic refused before the (S, h/bh, bh, d) view."""
    from bigdl_tpu.ops.flash_attention import paged_decode_attention

    pages = v5e((S * NB, H, PAGE, D), jnp.int8 if quantized else jnp.float32)
    scales = [v5e((S * NB,), jnp.float32)] * 2 if quantized else []

    def fn(q, k, v, pt, ln, *sc):
        kw = dict(k_scales=sc[0], v_scales=sc[1]) if sc else {}
        return paged_decode_attention(q, k, v, pt, ln, block_h=block_h,
                                      interpret=False, **kw)

    _compile(fn, v5e((S, H, D), jnp.float32), pages, pages,
             v5e((S, NB), jnp.int32), v5e((S,), jnp.int32), *scales)


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_verify_attention(v5e, quantized):
    from bigdl_tpu.ops.flash_attention import paged_verify_attention

    pages = v5e((S * NB, H, PAGE, D), jnp.int8 if quantized else jnp.float32)
    scales = [v5e((S * NB,), jnp.float32)] * 2 if quantized else []

    def fn(q, k, v, pt, pos, *sc):
        kw = dict(k_scales=sc[0], v_scales=sc[1]) if sc else {}
        return paged_verify_attention(q, k, v, pt, pos, interpret=False,
                                      **kw)

    _compile(fn, v5e((S, H, CHUNK, D), jnp.float32), pages, pages,
             v5e((S, NB), jnp.int32), v5e((S,), jnp.int32), *scales)


@pytest.mark.parametrize("shape,dtype", [
    ((_K["flash_b"], H, _K["flash_s"], D), jnp.float32),   # the smoke's
    ((_K["flash_b"], H, _K["flash_s"], D), jnp.bfloat16),
    ((8, 12, 1024, 64), jnp.bfloat16),     # gpt2-small, batch 8 a chip
    ((8, 12, 1024, 64), jnp.float32),
    ((2, 20, 4096, 256), jnp.bfloat16),    # glm-4.7-flash.train-packed4k
    ((2, 20, 4096, 256), jnp.float32),
    ((2, 4, 4096, 192, 128), jnp.bfloat16),    # xing4.0-29b-a4b: 192-wide
    ((2, 4, 4096, 192, 128), jnp.float32),     # keys, 128-wide values
    ((2, 4, 200, 64), jnp.bfloat16),       # padded: 200 rows, one block
    ((1, 4, 1500, 128), jnp.bfloat16),     # padded to 1536, blocks of 512
])
def test_flash_attention_forward_and_backward(v5e, shape, dtype):
    """The three training kernels (forward, dq, dk/dv) with the block
    rule's picks, operands in ``dtype`` as the policy would hand them: a
    lowering or VMEM refusal shows here, before chip time is spent."""
    from bigdl_tpu.ops.flash_attention import flash_attention
    from bigdl_tpu.tensor.policy import compute_dtype

    q = v5e(shape[:4], jnp.float32)  # activations are float32; the call casts
    v = v5e(shape[:3] + shape[4:], jnp.float32) if len(shape) == 5 else q

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False).sum()

    with compute_dtype(dtype):
        _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, v)


@pytest.mark.parametrize("shape,kv_heads,dtype", [
    ((1, 32, 8192, 64), 8, jnp.bfloat16),  # lfm2-24b-a2b.train-ep8-packed8k
    ((1, 32, 8192, 64), 8, jnp.float32),
    ((2, 8, 200, 64), 2, jnp.bfloat16),    # padded: 200 rows, one block
    ((1, 4, 1500, 128), 1, jnp.bfloat16),  # one key/value head for all
])
def test_flash_attention_grouped_heads(v5e, shape, kv_heads, dtype):
    """Grouped-query heads: the K/V index maps that divide the program id
    and the dk/dv kernel that walks its group's query heads."""
    from bigdl_tpu.ops.flash_attention import flash_attention
    from bigdl_tpu.tensor.policy import compute_dtype

    q = v5e(shape, jnp.float32)
    kv = v5e((shape[0], kv_heads) + shape[2:], jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False).sum()

    with compute_dtype(dtype):
        _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


@pytest.mark.parametrize("block,ok", [((128, 128), True), ((64, 64), False),
                                      ((8, 8), False)])
def test_block_sparse_matmul_needs_128_blocks(v5e, block, ok):
    """The selector's shape test (mosaic_tileable) is Mosaic's own rule."""
    from bigdl_tpu.ops.block_sparse import (block_sparse_matmul,
                                            mosaic_tileable)

    K, N = FFN_K, FFN_N
    bk, bn = block
    mask = np.random.RandomState(0).rand(K // bk, N // bn) < 0.5
    mask[0, :] = True

    def fn(x, w):
        return block_sparse_matmul(x, w, mask, block_k=bk, block_n=bn,
                                   interpret=False)

    args = (v5e((S, K), jnp.bfloat16), v5e((K, N), jnp.bfloat16))
    assert mosaic_tileable(bk, bn) is ok
    if ok:
        _compile(fn, *args)
    else:
        with pytest.raises(ValueError, match="divisible by 8 and 128"):
            _compile(fn, *args)


def test_fused_layernorm_and_int8_matmul(v5e):
    from bigdl_tpu.ops.fused import fused_layernorm
    from bigdl_tpu.ops.quantized import int8_matmul

    g = v5e((FFN_K,), jnp.float32)
    _compile(lambda x, g, b: fused_layernorm(x, g, b, interpret=False),
             v5e((_K["ln_rows"], FFN_K), jnp.float32), g, g)
    m, k, n = _K["mm"]
    _compile(lambda a, w: int8_matmul(a, w, interpret=False),
             v5e((m, k), jnp.int8), v5e((k, n), jnp.int8))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_cross_lengths(v5e, causal):
    """sq != skv, neither a multiple of its block: the clamped index maps
    and the key-padding mask lower too."""
    from bigdl_tpu.ops.flash_attention import flash_attention
    from bigdl_tpu.tensor.policy import compute_dtype

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=256,
                               block_k=512, interpret=False).sum()

    with compute_dtype(jnp.bfloat16):
        _compile(jax.grad(loss, argnums=(0, 1, 2)),
                 v5e((2, 4, 700, 128), jnp.float32),
                 v5e((2, 4, 1300, 128), jnp.float32),
                 v5e((2, 4, 1300, 128), jnp.float32))


@pytest.mark.parametrize("sq,skv", [(8, 8), (24, 24), (24, 640), (5, 37)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_short_lengths(v5e, sq, skv, dtype):
    """Serving's prompt chunks and buckets: blocks clipped to the 8-padded
    sequence (one 24-row block of bf16 tiles) still lower, forward and
    backward."""
    from bigdl_tpu.ops.flash_attention import flash_attention
    from bigdl_tpu.tensor.policy import compute_dtype

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False).sum()

    kv = v5e((2, 4, skv, 64), jnp.float32)
    with compute_dtype(dtype):
        _compile(jax.grad(loss, argnums=(0, 1, 2)),
                 v5e((2, 4, sq, 64), jnp.float32), kv, kv)


def test_grouped_expert_product_at_the_cell_sizes(v5e):
    """The held-share expert layer's grouped products (``jax.lax.
    ragged_dot``: 32,768 pair rows, 8 experts of 2048 x 1536) and their
    gradients compile for the chip."""
    def loss(x, w, sizes):
        return jax.lax.ragged_dot(
            x, w, sizes, preferred_element_type=jnp.float32).sum()

    # production's matmul precision, not the test session's "highest"
    # (tests/conftest.py), which the chip's grouped kernel refuses
    with jax.default_matmul_precision("bfloat16"):
        _compile(jax.grad(loss, argnums=(0, 1)),
                 v5e((32768, 2048), jnp.bfloat16),
                 v5e((8, 2048, 1536), jnp.bfloat16), v5e((8,), jnp.int32))


def test_expert_layer_two_paths_keep_no_more_than_one(v5e):
    """The held-share expert layer at the Xing cell's shape, gradient under
    ``jax.checkpoint`` as the model runs it: the program with the ``cond``
    between buffers of C and of T*k rows needs no more temporaries than the
    whole-size path alone.  (Differentiated without a ``jax.checkpoint`` on
    each branch, the ``cond`` hands both branches' residuals and a copy of
    the weights across: 2.2 GB for 0.66 at this shape.)"""
    from bigdl_tpu.parallel import moe
    from bigdl_tpu.tensor.policy import compute_dtype

    z = FULL["moe"]
    t, d, h, k, held, e = (z["tokens"], z["d"], z["hidden"], z["k"],
                           z["held"], z["experts"])
    w_in, w_out = v5e((held[1], d, h), jnp.float32), v5e((held[1], h, d),
                                                         jnp.float32)
    args = ({"w_gate": w_in, "w_up": w_in, "w_down": w_out},
            v5e((t, d), jnp.float32), v5e((t, k), jnp.float32),
            v5e((t, k), jnp.int32), v5e((t, d), jnp.float32))

    def whole(p, x, idx, w, held, e):
        order, inv, rows = moe._sort_pairs(idx, held)
        return moe._held_rows_apply(p, x, w, order, inv, rows, idx.size)

    def temp_bytes(apply):
        def loss(p, x, w, idx, cot):
            y = jax.checkpoint(lambda p, x, w: apply(p, x, idx, w, held,
                                                     e)[0])(p, x, w)
            return jnp.sum(y * cot)
        # production's matmul precision, not the test session's "highest"
        with compute_dtype(jnp.bfloat16), \
                jax.default_matmul_precision("bfloat16"):
            compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
                *args).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    assert moe.held_capacity(t * k, held[1], e) < t * k
    assert temp_bytes(moe.held_experts_apply) <= 1.1 * temp_bytes(whole)


@pytest.mark.parametrize("n,tokens,d", [
    FULL["kern"]["hc"],         # xing4.0-29b-a4b.train-tp8-packed4k
    (4, 8192, 2048),
    (2, 200, 384),              # tiles of 8 tokens by 384
])
def test_hc_mix_backward_both_ways(v5e, n, tokens, d):
    """The one-pass backward of the hyper-connection's write-back (n + 1
    primal slabs) and of its read-out with the cotangent it adds, at the
    tile rule's picks: per-token columns ``(block_t, m * p)`` and the
    accumulators carried over the chunks of ``d`` are what Mosaic could
    refuse."""
    from bigdl_tpu.ops.hc_mix import mix_backward

    f32 = lambda *shape: v5e(shape, jnp.float32)
    _compile(lambda c, g, x, y: mix_backward(c, g, x, y, interpret=False),
             f32(n, n + 1, tokens), f32(n, tokens, d), f32(n, tokens, d),
             f32(tokens, d))
    _compile(lambda c, g, x, a: mix_backward(c, g, x, add=a,
                                             interpret=False),
             f32(1, n, tokens), f32(1, tokens, d), f32(n, tokens, d),
             f32(n, tokens, d))


@pytest.mark.parametrize("t,chunk", [(32768, None), (1000, 128)])
def test_lightning_attention_forward_and_backward(v5e, t, chunk):
    """The chunked linear-attention kernel, forward in time and backward
    (dq forward, dk and dv walking the chunks from the last), at the
    minicpm-sala cell's per-layer shape (4 held heads of 128, 32k) and at
    a length the chunks do not divide."""
    from bigdl_tpu.ops.lightning_attention import alibi_slopes, \
        lightning_attention
    from bigdl_tpu.tensor.policy import compute_dtype

    x = v5e((1, 4, t, 128), jnp.float32)

    def loss(q, k, v):
        return lightning_attention(q, k, v, alibi_slopes(32, 28, 4),
                                   chunk=chunk, interpret=False).sum()

    with compute_dtype(jnp.bfloat16):
        _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)


@pytest.mark.parametrize("t,block_q,block_k", [(32768, None, None),
                                               (16384, 256, 128),
                                               (16384, 128, 1024)])
def test_sparse_attention_selection_forward_and_backward(v5e, t, block_q,
                                                          block_k):
    """Block selection and the three block-sparse kernels at the cell's
    sparse layer (4 query heads on 1 key/value head of 128, top-64 of
    64-key blocks): the scalar-prefetched (tile, span) list of the causal
    bound is what SMEM could refuse, the span's selection bits and the
    (512, 512) score tiles of four heads what VMEM could."""
    from bigdl_tpu.ops.sparse_attention import select_blocks, \
        sparse_attention
    from bigdl_tpu.tensor.policy import compute_dtype

    def loss(q, k, v):
        sel = select_blocks(q, k, kernel=32, stride=16, block=64, topk=64,
                            init_blocks=1, window=2048)
        return sparse_attention(q, k, v, sel, block_q=block_q,
                                block_k=block_k, interpret=False).sum()

    with compute_dtype(jnp.bfloat16):
        _compile(jax.grad(loss, argnums=(0, 1, 2)),
                 v5e((1, 1, 4, t, 128), jnp.float32),
                 v5e((1, 1, t, 128), jnp.float32),
                 v5e((1, 1, t, 128), jnp.float32))


def test_one_shard_train_step_assembles_no_flat_vector(v5e):
    """A whole train step (forward, backward, Adam) on ONE shard, compiled
    for the described chip in the layout the engine picks there (state
    shaped like the model's leaves) and, steered from here, in the layout
    several shards carry (flat vectors): the leaf-shaped program holds no
    ``concatenate`` and no ``dynamic-update-slice`` as large as a weight
    matrix, anywhere, and needs less scratch than the flat one."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from bigdl_tpu import nn, optim
    from bigdl_tpu.optim.train_step import ShardedParameterStep
    from bigdl_tpu.runtime.mesh import MeshSpec, build_mesh

    chip = next(iter(v5e((), jnp.float32).sharding.device_set))
    chip_mesh = build_mesh(MeshSpec(data=1), devices=[chip])
    rep = NamedSharding(chip_mesh, P())
    widths = (1024, 4096, 4096, 1024)
    layers = []
    for a, b in zip(widths, widths[1:]):
        layers += [nn.Linear(a, b), nn.ReLU()]
    model = nn.Sequential(layers[:-1] + [nn.LogSoftMax()])
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, widths[0]), jnp.float32))
    step = ShardedParameterStep(
        model, nn.ClassNLLCriterion(), optim.Adam(learning_rate=1e-3),
        build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]), variables)
    assert step.leaf_state
    matrix = min(a * b for a, b in zip(widths, widths[1:]))

    def compiled():
        step.mesh = chip_mesh  # the programs are built for this mesh
        args = (step._params, step._ema, step._opt, step.model_state,
                jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0),
                jnp.zeros((64, widths[0]), jnp.float32),
                jnp.zeros((64,), jnp.int32), step._mask)
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
            args)
        return step._build_train().lower(*shapes).compile()

    def assembled(text):
        """``concatenate`` / ``dynamic-update-slice`` instructions whose
        result is at least a weight matrix."""
        found = []
        for m in re.finditer(r"= \w+\[([\d,]*)\]\S* "
                             r"(concatenate|dynamic-update-slice)\(", text):
            if np.prod([int(d) for d in m.group(1).split(",") if d]) \
                    >= matrix:
                found.append(m.group(0))
        return found

    leaf = compiled()
    # the same engine in the other layout: what a one-shard step carried
    # before the layout followed the number of shards
    step.leaf_state = False
    step._init_flat_state(variables["params"], None, jnp.float32)
    flat = compiled()
    assert assembled(flat.as_text()), "the flat form assembles its vector"
    assert not assembled(leaf.as_text())
    temp = [c.memory_analysis().temp_size_in_bytes for c in (leaf, flat)]
    assert temp[0] < temp[1], temp


def _checkpointed_layer_grad(v5e, attend, q_shape, kv_shape, policy):
    """``(tpu_custom_call count, temporaries)`` of the gradient of one
    layer under ``jax.checkpoint(policy=)``, compiled for the described
    chip: a projection in front of ``attend(q, k, v)`` and a loss that
    reads the layer's output, as a decoder's step has them."""
    from bigdl_tpu.tensor.policy import compute_dtype

    d = q_shape[-1]
    f = jax.checkpoint(lambda w, x, k, v: attend(x @ w, k, v), policy=policy)

    def loss(w, x, k, v):
        return jnp.sum(jnp.square(f(w, x, k, v).astype(jnp.float32)))

    with compute_dtype(jnp.bfloat16):
        compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3))).lower(
            v5e((d, d), jnp.float32), v5e(q_shape, jnp.float32),
            v5e(kv_shape, jnp.float32),
            v5e(kv_shape, jnp.float32)).compile()
    return (compiled.as_text().count('custom_call_target="tpu_custom_call"'),
            compiled.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("kind,q_shape,kv_shape", [
    ("sparse", (1, 1, 4, 32768, 128), (1, 1, 32768, 128)),  # minicpm-sala
    ("flash", (2, 20, 4096, 256), (2, 20, 4096, 256)),      # glm-4.7-flash
    ("flash", (1, 32, 8192, 64), (1, 8, 8192, 64)),         # lfm2-24b-a2b
])
def test_checkpointed_layer_keeps_out_and_lse(v5e, kind, q_shape, kv_shape):
    """The decoders' layer policy at the cells' shapes: the gradient holds
    the forward kernel once (forward, dq, dk/dv: three custom calls, four
    under the policy the decoders had before), and its temporaries grow by
    no more than the kept ``out`` (bf16) and ``lse`` (float32)."""
    from jax.ad_checkpoint import checkpoint_name

    from bigdl_tpu.nn.sparse_linear_attention import SELECTION
    from bigdl_tpu.ops.common import layer_remat_policy
    from bigdl_tpu.ops.flash_attention import flash_attention
    from bigdl_tpu.ops.sparse_attention import select_blocks, \
        sparse_attention

    if kind == "sparse":
        def attend(q, k, v):
            sel = checkpoint_name(select_blocks(
                q, k, kernel=32, stride=16, block=64, topk=64,
                init_blocks=1, window=2048), SELECTION)
            return sparse_attention(q, k, v, sel, interpret=False)
        names, before = (SELECTION,), \
            jax.checkpoint_policies.save_only_these_names(SELECTION)
    else:
        def attend(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=False)
        names, before = (), None
    calls, temp = _checkpointed_layer_grad(v5e, attend, q_shape, kv_shape,
                                           layer_remat_policy(*names))
    calls_before, temp_before = _checkpointed_layer_grad(
        v5e, attend, q_shape, kv_shape, before)
    rows = int(np.prod(q_shape[:-1]))
    assert (calls, calls_before) == (3, 4)
    assert temp - temp_before <= rows * kv_shape[-1] * 2 + rows * 4, (
        temp, temp_before)


@pytest.mark.parametrize("t,chunk", [(16384, None), (1000, 256)])
def test_ssd_forward_and_backward(v5e, t, chunk):
    """The SSD kernels (forward; the states and gradient kernels of the
    backward) at the granite-4.0-h-micro cell's Mamba-2 mixer (64 heads of
    64, a state of 128, chunks of 256 over 16k) and at a length the chunks
    do not divide: every head's (128, 64) float32 state in VMEM is what
    Mosaic could refuse."""
    from bigdl_tpu.ops.ssd import ssd
    from bigdl_tpu.tensor.policy import compute_dtype

    def loss(x, dt, a_log, b, c, d):
        return ssd(x, dt, -jnp.exp(a_log), b, c, d, chunk=chunk,
                   interpret=False).sum()

    with compute_dtype(jnp.bfloat16):
        _compile(jax.grad(loss, argnums=tuple(range(6))),
                 v5e((1, t, 64, 64), jnp.float32), v5e((1, t, 64),
                                                      jnp.float32),
                 v5e((64,), jnp.float32), v5e((1, t, 128), jnp.float32),
                 v5e((1, t, 128), jnp.float32), v5e((64,), jnp.float32))


@pytest.mark.parametrize("t,wide,offset,width", [
    (16384, 8512, 4096, 4352),      # granite-4.0-h-micro.train-tp4-16k
    (1152, 640, 128, 384),          # a partial last tile
])
def test_causal_conv_forward_and_backward(v5e, t, wide, offset, width):
    """The Mamba-2 causal convolution's two kernels at the Granite cell's
    mixer (x‖B‖C, 4,352 channels at 4,096 of ``W_in``'s 8,512-wide
    output, 4 taps, 16k) and at a length the tiles do not divide: the
    128-position halo blocks of the same array and the unaligned lane
    slices of the scratch are what Mosaic could refuse."""
    from bigdl_tpu.ops.causal_conv import causal_conv

    f32 = lambda *shape: v5e(shape, jnp.float32)

    def vjp(u, w, b, g):
        return jax.vjp(lambda u, w, b: causal_conv(
            u, w, b, offset=offset, interpret=False), u, w, b)[1](g)

    _compile(vjp, f32(1, t, wide), f32(4, width), f32(width),
             f32(1, t, width))


def _checkpointed_mamba_grad(v5e, t):
    """``jax.checkpoint(Mamba2)``'s gradient at the Granite cell's widths
    (d 2,048, 64 heads of 64, a state of 128, 4 taps), batch 1 x ``t``, in
    bf16, compiled for a described v5e (``on_tpu`` patched by the caller);
    a checkpoint of its own each time: a traced one is cached."""
    from bigdl_tpu.nn import mamba2
    from bigdl_tpu.tensor.policy import compute_dtype

    m = mamba2.Mamba2(2048, 64, 64, 128, 4)
    u = jax.ShapeDtypeStruct((1, t, 2048), jnp.float32)
    v = jax.eval_shape(lambda u: m.init(jax.random.PRNGKey(0), u), u)
    state = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype),
                                   v["state"])
    layer = jax.checkpoint(lambda p, u: m.forward(p, state, u)[0])

    def loss(p, u):
        return jnp.sum(jnp.square(layer(p, u).astype(jnp.float32)))

    shaped = lambda a: v5e(a.shape, a.dtype)
    with compute_dtype(jnp.bfloat16):
        return jax.jit(jax.grad(loss, (0, 1))).lower(
            jax.tree_util.tree_map(shaped, v["params"]), shaped(u)).compile()


def test_checkpointed_mamba_gradient_shifts_nothing_in_hbm(v5e, monkeypatch):
    """``jax.checkpoint(Mamba2)``'s gradient at the Granite cell's widths
    and a short sequence, through the kernels: no top-level ``slice`` in
    the convolution's scope and none shifted along the sequence, no copy
    of ``W_in``'s output in front of the kernels (they read its T-minor
    layout as a (B, W, T) array, a bitcast), and no more temporaries than
    the same layer through the plain expression."""
    import re

    import bigdl_tpu.ops.common as common
    from bigdl_tpu.nn import mamba2

    monkeypatch.setattr(common, "on_tpu", lambda: True)
    t = 1024
    fused = _checkpointed_mamba_grad(v5e, t)
    text = fused.as_text()
    entry = text[text.index("\nENTRY"):]
    assert entry.count('custom_call_target="tpu_custom_call"') >= 6
    assert "causal_conv" in entry
    slices = re.findall(r"= \S+\[([\d,]*)\]\S* slice\(.*", entry)
    assert not [s for s in re.findall(
        r"= \S+ slice\(.*op_name=\"([^\"]*)\"", entry) if "causal_conv" in s]
    assert not [s for s in slices if int(s.split(",")[1]) in
                range(t - 3, t)]
    assert not re.search(rf"= f32\[1,{t},8512\]\S* copy\(", entry)
    monkeypatch.setattr(mamba2, "conv_blocks", lambda *a: None)
    plain = _checkpointed_mamba_grad(v5e, t)
    assert re.search(rf"= f32\[1,{t - 1},4352\]\S* slice\(",
                     plain.as_text()), "the plain expression shifts"
    temp = [c.memory_analysis().temp_size_in_bytes for c in (fused, plain)]
    assert temp[0] <= temp[1], temp


# the temporaries of the same layer gradient at t = 1024 as the SSD kernels
# compiled before they took T on the lanes, with head-major (heads, T, P)
# operands at P = 64 on the lanes (for the same described v5e, libtpu
# 0.0.34)
HEAD_MAJOR_TEMP_BYTES = 85_047_296


def test_checkpointed_mamba_gradient_keeps_the_scan_t_minor(v5e, monkeypatch):
    """The SSD kernels take x, y and their cotangents with T on the lanes,
    the layout XLA gives the mixer around them: in the checkpointed layer's
    gradient no copy or fusion writes the head-major ``(B, T, heads, P)``
    layout, no ``(heads, T, P)`` operand exists, the convolution's output is
    never copied to channels-minor, and the temporaries are no more than
    with the head-major operands."""
    import re

    import bigdl_tpu.ops.common as common

    monkeypatch.setattr(common, "on_tpu", lambda: True)
    t = 1024
    compiled = _checkpointed_mamba_grad(v5e, t)
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    assert "ssd_" in entry
    assert not re.search(
        rf"= \w+\[1,{t},64,64\]{{3,1,2,0\S* (copy|fusion)\(", text)
    assert f"bf16[64,{t},64]" not in text
    assert not re.search(rf"= f32\[1,4352,{t}\]\S* copy\(", entry)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= HEAD_MAJOR_TEMP_BYTES, temp
