"""Core layer catalog.

Reference analog (unverified — mount empty): ``dllib/nn/*.scala`` — ~300 layers
with hand-written forward/backward.  Here each layer is a thin pure-forward
module; backward is ``jax.grad``.  Layout decisions are TPU-first:

- Images are **NHWC** (XLA:TPU's preferred conv layout), not the reference's
  NCHW.  Kernels are HWIO.
- Matmuls/convs run in the global compute dtype (bf16 on TPU) with float32
  accumulation — see ``bigdl_tpu/tensor/policy.py``.
- Reference names are kept as aliases (``SpatialConvolution = Conv2D`` etc.)
  so reference users find their layer catalog.
"""

import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import init as init_mod
from bigdl_tpu.nn.module import EMPTY, Module
from bigdl_tpu.tensor.policy import cast_compute

# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------


class Linear(Module):
    """Fully-connected layer — reference ``nn/Linear.scala``.

    Weight stored as (in, out) so the forward is ``x @ W`` (MXU-friendly, no
    transpose; the reference stores (out, in) for gemv on CPU).
    """

    def __init__(self, in_features: Optional[int] = None, out_features: int = 0,
                 with_bias: bool = True, weight_init=init_mod.xavier,
                 bias_init=init_mod.zeros, name=None):
        super().__init__(name)
        if out_features == 0 and in_features is not None:
            in_features, out_features = None, in_features  # Linear(out) lazy form
        self.in_features = in_features
        self.out_features = out_features
        self.with_bias = with_bias
        self.weight_init = weight_init
        self.bias_init = bias_init

    def build(self, rng, x):
        fan_in = self.in_features or x.shape[-1]
        k1, k2 = jax.random.split(rng)
        params = {"weight": self.weight_init(k1, (fan_in, self.out_features),
                                             fan_in, self.out_features)}
        if self.with_bias:
            params["bias"] = self.bias_init(k2, (self.out_features,), fan_in,
                                            self.out_features)
        return params, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        xc, wc = cast_compute(x, params["weight"])
        y = jnp.matmul(xc, wc, preferred_element_type=jnp.float32)
        if self.with_bias:
            y = y + params["bias"]  # add in f32 accumulation dtype
        return y.astype(x.dtype), EMPTY


Dense = Linear


# ---------------------------------------------------------------------------
# Convolutions (NHWC / HWIO)
# ---------------------------------------------------------------------------

PadLike = Union[str, int, Tuple[int, int]]


def _pair(v) -> Tuple[int, int]:
    return tuple(v) if isinstance(v, (tuple, list)) else (v, v)


def _conv_accum(xc):
    """f32-accumulation kwargs for convs.  With bf16 inputs we must NOT pass
    preferred_element_type: jax's conv transpose rule then builds a mixed
    bf16/f32 conv and fails under grad — and the TPU MXU accumulates conv
    partials in f32 internally regardless, so only the output rounds to
    bf16 (re-widened before the bias add)."""
    return ({"preferred_element_type": jnp.float32}
            if xc.dtype == jnp.float32 else {})


def _conv_padding(pad: PadLike, kh: int, kw: int):
    if isinstance(pad, str):
        return pad.upper()  # "SAME" / "VALID"
    ph, pw = _pair(pad)
    if (ph, pw) == (-1, -1):  # reference convention: -1 = SAME
        return "SAME"
    return [(ph, ph), (pw, pw)]


class Conv2D(Module):
    """2-D convolution — reference ``nn/SpatialConvolution.scala`` (with
    ``nGroup`` group support used by the reference ResNet/AlexNet)."""

    def __init__(self, in_channels: Optional[int], out_channels: int,
                 kernel_size, stride=1, padding: PadLike = 0, dilation=1,
                 groups: int = 1, with_bias: bool = True,
                 weight_init=init_mod.msra, bias_init=init_mod.zeros, name=None):
        super().__init__(name)
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.kernel_size = _pair(kernel_size)
        self.stride = _pair(stride)
        self.padding = padding
        self.dilation = _pair(dilation)
        self.groups = groups
        self.with_bias = with_bias
        self.weight_init = weight_init
        self.bias_init = bias_init

    def build(self, rng, x):
        cin = self.in_channels or x.shape[-1]
        kh, kw = self.kernel_size
        fan_in = cin * kh * kw // self.groups
        fan_out = self.out_channels * kh * kw // self.groups
        k1, k2 = jax.random.split(rng)
        params = {"weight": self.weight_init(
            k1, (kh, kw, cin // self.groups, self.out_channels), fan_in, fan_out)}
        if self.with_bias:
            params["bias"] = self.bias_init(k2, (self.out_channels,), fan_in, fan_out)
        return params, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        kh, kw = self.kernel_size
        xc, wc = cast_compute(x, params["weight"])
        y = jax.lax.conv_general_dilated(
            xc, wc,
            window_strides=self.stride,
            padding=_conv_padding(self.padding, kh, kw),
            rhs_dilation=self.dilation,
            feature_group_count=self.groups,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            **_conv_accum(xc),
        )
        if self.with_bias:
            y = y.astype(jnp.float32) + params["bias"]
        return y.astype(x.dtype), EMPTY


SpatialConvolution = Conv2D


class Conv1D(Module):
    """1-D convolution (NWC) — reference ``nn/TemporalConvolution.scala``.
    Supports causal padding + dilation (the Chronos TCN building block)."""

    def __init__(self, in_channels: Optional[int], out_channels: int,
                 kernel_size: int, stride: int = 1, padding: Union[str, int] = 0,
                 dilation: int = 1, groups: int = 1, with_bias: bool = True,
                 causal: bool = False, weight_init=init_mod.msra,
                 bias_init=init_mod.zeros, name=None):
        super().__init__(name)
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.kernel_size = kernel_size
        self.stride = stride
        self.padding = padding
        self.dilation = dilation
        self.groups = groups
        self.with_bias = with_bias
        self.causal = causal
        self.weight_init = weight_init
        self.bias_init = bias_init

    def build(self, rng, x):
        cin = self.in_channels or x.shape[-1]
        fan_in = cin * self.kernel_size // self.groups
        fan_out = self.out_channels * self.kernel_size // self.groups
        k1, k2 = jax.random.split(rng)
        params = {"weight": self.weight_init(
            k1, (self.kernel_size, cin // self.groups, self.out_channels),
            fan_in, fan_out)}
        if self.with_bias:
            params["bias"] = self.bias_init(k2, (self.out_channels,), fan_in, fan_out)
        return params, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        if self.causal:
            pad = [( (self.kernel_size - 1) * self.dilation, 0 )]
        elif isinstance(self.padding, str):
            pad = self.padding.upper()
        else:
            pad = [(self.padding, self.padding)]
        xc, wc = cast_compute(x, params["weight"])
        y = jax.lax.conv_general_dilated(
            xc, wc, window_strides=(self.stride,), padding=pad,
            rhs_dilation=(self.dilation,), feature_group_count=self.groups,
            dimension_numbers=("NWC", "WIO", "NWC"),
            **_conv_accum(xc),
        )
        if self.with_bias:
            y = y.astype(jnp.float32) + params["bias"]
        return y.astype(x.dtype), EMPTY


TemporalConvolution = Conv1D


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------


class _Pool2D(Module):
    def __init__(self, kernel_size, stride=None, padding: PadLike = 0,
                 ceil_mode: bool = False, name=None):
        super().__init__(name)
        self.kernel_size = _pair(kernel_size)
        self.stride = _pair(stride if stride is not None else kernel_size)
        self.padding = padding
        self.ceil_mode = ceil_mode

    def _pad(self, x):
        if isinstance(self.padding, str):
            if self.ceil_mode:
                raise NotImplementedError("ceil_mode with string padding")
            return self.padding.upper()
        ph, pw = _pair(self.padding)
        pads = [[ph, ph], [pw, pw]]
        if self.ceil_mode:
            # extra right/bottom padding so the last partial window counts
            # (reference SpatialMaxPooling ceil mode)
            for i, (n, k, s) in enumerate(
                    zip(x.shape[1:3], self.kernel_size, self.stride)):
                p = pads[i][0]
                ceil_out = -(-(n + 2 * p - k) // s) + 1
                extra = (ceil_out - 1) * s + k - (n + 2 * p)
                pads[i][1] += max(0, extra)
        (pht, phb), (pwl, pwr) = pads
        return [(0, 0), (pht, phb), (pwl, pwr), (0, 0)]

    def _window(self):
        kh, kw = self.kernel_size
        sh, sw = self.stride
        return (1, kh, kw, 1), (1, sh, sw, 1)


class MaxPool2D(_Pool2D):
    """Reference ``nn/SpatialMaxPooling.scala`` (NHWC)."""

    def forward(self, params, state, x, training=False, rng=None):
        window, strides = self._window()
        y = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, window, strides, self._pad(x))
        return y, EMPTY


class AvgPool2D(_Pool2D):
    """Reference ``nn/SpatialAveragePooling.scala`` (NHWC, count_include_pad
    matching the reference default of averaging over the full window)."""

    def forward(self, params, state, x, training=False, rng=None):
        window, strides = self._window()
        summed = jax.lax.reduce_window(
            x, 0.0, jax.lax.add, window, strides, self._pad(x))
        kh, kw = self.kernel_size
        return summed / (kh * kw), EMPTY


class GlobalAvgPool2D(Module):
    def forward(self, params, state, x, training=False, rng=None):
        return jnp.mean(x, axis=(1, 2)), EMPTY


SpatialMaxPooling = MaxPool2D
SpatialAveragePooling = AvgPool2D


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


class BatchNorm(Module):
    """Batch normalization — reference ``nn/BatchNormalization.scala`` (1-D,
    over (N, C)) and ``nn/SpatialBatchNormalization.scala`` (NHWC here, reduce
    over N,H,W).  Running stats live in ``state`` and are updated functionally
    in training mode (reference mutates ``runningMean/runningVar`` in place).
    Reference defaults: eps 1e-5, momentum 0.1."""

    def __init__(self, num_features: Optional[int] = None, eps: float = 1e-5,
                 momentum: float = 0.1, affine: bool = True, name=None):
        super().__init__(name)
        self.num_features = num_features
        self.eps = eps
        self.momentum = momentum
        self.affine = affine

    def build(self, rng, x):
        c = self.num_features or x.shape[-1]
        params = {}
        if self.affine:
            params = {"weight": jnp.ones((c,)), "bias": jnp.zeros((c,))}
        state = {"running_mean": jnp.zeros((c,)),
                 "running_var": jnp.ones((c,))}
        return params, state

    def forward(self, params, state, x, training=False, rng=None):
        axes = tuple(range(x.ndim - 1))
        if training:
            # single-pass stats: two sibling reductions in ONE read of the
            # activation (XLA fuses them); jnp.var's two-pass
            # mean((x-mean)^2) reads the (often huge, bf16) activation twice.
            # Shifted by the running mean so E[d^2]-E[d]^2 cancellation is
            # benign even when |mean| >> std (unnormalized inputs): with
            # shift ~ mean, E[d] ~ 0 and the subtraction loses no bits.
            xf = x.astype(jnp.float32)
            shift = state["running_mean"].astype(jnp.float32)
            d = xf - shift
            dmean = jnp.mean(d, axis=axes)
            var = jnp.maximum(
                jnp.mean(jnp.square(d), axis=axes) - jnp.square(dmean), 0.0)
            mean = dmean + shift
            m = self.momentum
            new_state = {
                "running_mean": (1 - m) * state["running_mean"] + m * mean,
                "running_var": (1 - m) * state["running_var"] + m * var,
            }
        else:
            mean, var = state["running_mean"], state["running_var"]
            new_state = EMPTY
        inv = jax.lax.rsqrt(var + self.eps)
        y = (x - mean) * inv
        if self.affine:
            y = y * params["weight"] + params["bias"]
        return y.astype(x.dtype), new_state


BatchNormalization = BatchNorm
SpatialBatchNormalization = BatchNorm


class LayerNorm(Module):
    """Reference keras-side ``LayerNorm`` (Analytics-Zoo lineage, unverified).
    Normalizes over the last axis."""

    def __init__(self, num_features: Optional[int] = None, eps: float = 1e-6,
                 name=None):
        super().__init__(name)
        self.num_features = num_features
        self.eps = eps

    def build(self, rng, x):
        c = self.num_features or x.shape[-1]
        return {"weight": jnp.ones((c,)), "bias": jnp.zeros((c,))}, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        return (y * params["weight"] + params["bias"]).astype(x.dtype), EMPTY


def rms_norm(x, weight, eps: float = 1e-6):
    """``x / rms(x) * weight`` over the last axis, computed in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * weight).astype(x.dtype)


class RMSNorm(Module):
    """TPU-era extra (not in reference): RMS normalization for LLM blocks."""

    def __init__(self, num_features: Optional[int] = None, eps: float = 1e-6,
                 name=None):
        super().__init__(name)
        self.num_features = num_features
        self.eps = eps

    def build(self, rng, x):
        c = self.num_features or x.shape[-1]
        return {"weight": jnp.ones((c,))}, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        return rms_norm(x, params["weight"], self.eps), EMPTY


# ---------------------------------------------------------------------------
# Regularization / shape / embedding
# ---------------------------------------------------------------------------


class Dropout(Module):
    """Inverted dropout — reference ``nn/Dropout.scala`` (initP = keep... the
    reference takes initP = drop probability; same here)."""

    def __init__(self, p: float = 0.5, name=None):
        super().__init__(name)
        self.p = p

    def forward(self, params, state, x, training=False, rng=None):
        if not training or self.p == 0.0:
            return x, EMPTY
        if rng is None:
            raise ValueError("Dropout in training mode requires rng")
        keep = 1.0 - self.p
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype), EMPTY


class Reshape(Module):
    """Reference ``nn/Reshape.scala`` — reshape non-batch dims."""

    def __init__(self, shape: Sequence[int], batch_mode: bool = True, name=None):
        super().__init__(name)
        self.shape = tuple(shape)
        self.batch_mode = batch_mode

    def forward(self, params, state, x, training=False, rng=None):
        if self.batch_mode:
            return jnp.reshape(x, (x.shape[0],) + self.shape), EMPTY
        return jnp.reshape(x, self.shape), EMPTY


class View(Reshape):
    pass


class Flatten(Module):
    def forward(self, params, state, x, training=False, rng=None):
        return jnp.reshape(x, (x.shape[0], -1)), EMPTY


class Squeeze(Module):
    def __init__(self, dim=None, name=None):
        super().__init__(name)
        self.dim = dim

    def forward(self, params, state, x, training=False, rng=None):
        return jnp.squeeze(x, axis=self.dim), EMPTY


class Unsqueeze(Module):
    def __init__(self, dim: int, name=None):
        super().__init__(name)
        self.dim = dim

    def forward(self, params, state, x, training=False, rng=None):
        return jnp.expand_dims(x, self.dim), EMPTY


class Transpose(Module):
    def __init__(self, perm: Sequence[int], name=None):
        super().__init__(name)
        self.perm = tuple(perm)

    def forward(self, params, state, x, training=False, rng=None):
        return jnp.transpose(x, self.perm), EMPTY


class Embedding(Module):
    """Reference ``nn/LookupTable.scala``.  NOTE the reference indexes 1-based;
    here indices are 0-based (documented divergence)."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 weight_init=init_mod.random_normal(0.0, 1.0), name=None):
        super().__init__(name)
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.weight_init = weight_init

    def build(self, rng, x):
        w = self.weight_init(rng, (self.num_embeddings, self.embedding_dim),
                             self.num_embeddings, self.embedding_dim)
        return {"weight": w}, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        return jnp.take(params["weight"], x.astype(jnp.int32), axis=0), EMPTY


LookupTable = Embedding


class ZeroPadding2D(Module):
    """Reference ``nn/SpatialZeroPadding.scala`` (NHWC)."""

    def __init__(self, padding, name=None):
        super().__init__(name)
        self.padding = _pair(padding)

    def forward(self, params, state, x, training=False, rng=None):
        ph, pw = self.padding
        return jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0))), EMPTY


# ---------------------------------------------------------------------------
# Activations — reference nn/{ReLU,Tanh,Sigmoid,SoftMax,LogSoftMax,ELU,...}.scala
# ---------------------------------------------------------------------------


def _act(fn, cls_name):
    class _Act(Module):
        def __init__(self, name=None):
            super().__init__(name or cls_name)

        def forward(self, params, state, x, training=False, rng=None):
            return fn(x), EMPTY

    _Act.__name__ = cls_name
    _Act.__qualname__ = cls_name
    return _Act


ReLU = _act(jax.nn.relu, "ReLU")
ReLU6 = _act(jax.nn.relu6, "ReLU6")
Tanh = _act(jnp.tanh, "Tanh")
Sigmoid = _act(jax.nn.sigmoid, "Sigmoid")
GELU = _act(jax.nn.gelu, "GELU")
SiLU = _act(jax.nn.silu, "SiLU")
Swish = SiLU
SoftPlus = _act(jax.nn.softplus, "SoftPlus")
SoftSign = _act(jax.nn.soft_sign, "SoftSign")
HardSigmoid = _act(jax.nn.hard_sigmoid, "HardSigmoid")
HardSwish = _act(jax.nn.hard_swish, "HardSwish")  # x * relu6(x+3)/6


class SoftMax(Module):
    def __init__(self, axis: int = -1, name=None):
        super().__init__(name)
        self.axis = axis

    def forward(self, params, state, x, training=False, rng=None):
        return jax.nn.softmax(x, axis=self.axis), EMPTY


class LogSoftMax(Module):
    def __init__(self, axis: int = -1, name=None):
        super().__init__(name)
        self.axis = axis

    def forward(self, params, state, x, training=False, rng=None):
        return jax.nn.log_softmax(x, axis=self.axis), EMPTY


class LeakyReLU(Module):
    def __init__(self, negval: float = 0.01, name=None):
        super().__init__(name)
        self.negval = negval

    def forward(self, params, state, x, training=False, rng=None):
        return jax.nn.leaky_relu(x, self.negval), EMPTY


class ELU(Module):
    def __init__(self, alpha: float = 1.0, name=None):
        super().__init__(name)
        self.alpha = alpha

    def forward(self, params, state, x, training=False, rng=None):
        return jax.nn.elu(x, self.alpha), EMPTY


class HardTanh(Module):
    def __init__(self, min_value=-1.0, max_value=1.0, name=None):
        super().__init__(name)
        self.min_value, self.max_value = min_value, max_value

    def forward(self, params, state, x, training=False, rng=None):
        return jnp.clip(x, self.min_value, self.max_value), EMPTY


class PReLU(Module):
    def __init__(self, init_alpha: float = 0.25, name=None):
        super().__init__(name)
        self.init_alpha = init_alpha

    def build(self, rng, x):
        return {"alpha": jnp.full((x.shape[-1],), self.init_alpha)}, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        return jnp.where(x >= 0, x, params["alpha"] * x), EMPTY
