"""Hybrid decoder LM: layers that mix tokens by a gated short convolution
beside layers that mix them by grouped-query attention, each followed by a
dense gated-SiLU FFN or a routed expert layer; assembled from a config.

The family Liquid AI publishes as ``lfm2_moe`` (LFM2-8B-A1B, LFM2-24B-A2B):
pre-norm residual blocks ``h += Op_i(RMSNorm(h))``, ``h += FFN_i(
RMSNorm(h))``; ``layer_types[i]`` says whether ``Op_i`` is ``"conv"``
(``nn.short_conv.GatedShortConv``: two gates around a depthwise causal
convolution of ``conv_L_cache`` taps) or ``"full_attention"``
(``nn.attention.GroupedQueryAttention``: RMSNorm on every query and key
head, then RoPE); the first ``num_dense_layers`` layers carry a dense FFN,
the rest ``parallel.moe.HeldMoE`` with NO shared expert (sigmoid scores,
top-k of score + expert bias, the chosen scores over their sum + 1e-6); a
final RMSNorm; the head TIED to the embedding: ``logits = RMSNorm(h)
Emb^T``, and the embedding's gradient is the sum of the lookup's and the
head's.

:class:`HybridMoEConfig` takes the published ``config.json`` keys as they
are (``HybridMoEConfig.from_dict`` ignores the keys that say nothing about
the shape).  ``held_experts=(first, count)`` makes the model ONE chip's
share of an expert-parallel job: every expert layer holds ``count`` of the
``num_experts`` the router scores, the operators, the norms, the router and
the dense FFN are whole, and ``vocab_size`` is then that chip's slice of
the vocabulary (docs/parallelism.md §Held-share expert layer).  With no
shared expert a token none of whose chosen experts is held gets exactly
zero from the layer: the residual stream alone carries it on.

This decoder and ``models/mla_moe_lm.py`` share their parts (``HeldMoE``,
``swiglu``, ``rms_norm``, ``rope``, the state metrics) by import and their
skeleton by shape only.

Precision is the repo's policy: float32 parameters, bfloat16 matmul inputs
on a TPU with float32 accumulation; router, softmax, RMSNorm, RoPE, the
convolution's gates and taps in float32.  When training, every layer is
recomputed in the backward pass (``jax.checkpoint`` per layer; the layers'
inputs are what is kept): there is no switch.

Initialisation (``init``): the tied matrix N(0, 1/hidden), so that the
logits of a normed state have standard deviation near 1; every other matrix
N(0, 1/fan_in), the taps N(0, 1/kernel), norm weights 1: every sublayer
writes unit variance into a stream the embedding starts at variance
1/hidden.  The first layer's normed input is the embedding at full size;
its direct path to the head is faint.  A tied head leaves no other choice
that trains steadily: with sublayers that write as little as the embedding
holds, a position's own input token owns its logits (near sqrt(hidden / 7)
standard deviations) and the first loss, and the first thing every
parameter learns, the routers soonest, is to bury it (PERF.md §6, PR 34)."""

import functools
from dataclasses import dataclass, fields
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import GroupedQueryAttention
from bigdl_tpu.nn.layers import rms_norm
from bigdl_tpu.nn.module import EMPTY, Module
from bigdl_tpu.nn.short_conv import GatedShortConv
from bigdl_tpu.parallel.moe import HeldMoE, swiglu, swiglu_init
from bigdl_tpu.tensor.policy import cast_compute

LAYER_TYPES = ("conv", "full_attention")
# beside the sum of the chosen scores, as the family's published forward
# pass has it (``route_sigmoid_topk``'s default is another family's 1e-20)
TOPK_SUM_EPS = 1e-6


@dataclass(frozen=True)
class HybridMoEConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_dense_layers: int = 1
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # a config's ``rope_parameters`` as sorted (key, value) pairs
    rope_parameters: Tuple[Tuple[str, object], ...] = (
        ("rope_theta", 10000.0), ("rope_type", "default"))
    # this chip's share of the experts, (first, count); None = all of them
    held_experts: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_hidden_layers} layers")
        unknown = sorted(set(self.layer_types) - set(LAYER_TYPES))
        if unknown:
            raise ValueError(f"layer_types {unknown}: one of {LAYER_TYPES}")
        if self.conv_bias:
            raise ValueError("conv_bias: the operator here has no bias")
        if dict(self.rope_parameters).get("rope_type", "default") != "default":
            raise ValueError(f"rope_parameters {self.rope_parameters}: only "
                             "the default (unscaled) rotary table is built")

    @property
    def rope_theta(self) -> float:
        return float(dict(self.rope_parameters)["rope_theta"])

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_dict(cls, cfg: dict) -> "HybridMoEConfig":
        names = {f.name for f in fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        kw["layer_types"] = tuple(kw["layer_types"])
        if kw.get("held_experts") is not None:
            kw["held_experts"] = tuple(kw["held_experts"])
        if kw.get("rope_parameters") is not None:
            kw["rope_parameters"] = tuple(sorted(
                kw["rope_parameters"].items()))
        return cls(**kw)


class HybridMoELM(Module):
    """``forward(params, state, ids)`` → (batch, seq, vocab) float32 logits
    and the new state (each expert layer's bias and routing statistics)."""

    def __init__(self, config: HybridMoEConfig, name=None):
        super().__init__(name)
        c = self.config = config
        self.conv = GatedShortConv(c.hidden_size, c.conv_L_cache)
        self.attn = GroupedQueryAttention(
            c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, rope_theta=c.rope_theta, qk_norm_eps=c.norm_eps)
        self.moe = HeldMoE(
            c.num_experts, c.moe_intermediate_size, c.num_experts_per_tok,
            held=c.held_experts, shared_hidden=0,
            scale=c.routed_scaling_factor, norm_topk=c.norm_topk_prob,
            norm_eps=TOPK_SUM_EPS)

    def _operator(self, i: int):
        """(parameter key, module) of layer ``i``'s token mixer."""
        if self.config.layer_types[i] == "conv":
            return "conv", self.conv
        return "attn", self.attn

    def _is_dense(self, i: int) -> bool:
        return i < self.config.num_dense_layers

    def init(self, rng, ids):
        c = self.config
        d = c.hidden_size
        ks = jax.random.split(rng, c.num_hidden_layers + 1)
        x = jnp.zeros(jnp.shape(ids) + (d,), jnp.float32)
        params = {"embed": jax.random.normal(ks[0], (c.vocab_size, d))
                  * d ** -0.5,
                  "ln_out": jnp.ones((d,))}
        state = {}
        for i in range(c.num_hidden_layers):
            k_op, k_ffn = jax.random.split(ks[i + 1])
            key, op = self._operator(i)
            layer = {"ln1": jnp.ones((d,)), "ln2": jnp.ones((d,)),
                     key: op.init(k_op, x)["params"]}
            if self._is_dense(i):
                layer["ffn"] = swiglu_init(k_ffn, d, c.intermediate_size)
            else:
                v = self.moe.init(k_ffn, x)
                layer["moe"], state[f"layer{i}"] = v["params"], v["state"]
            params[f"layer{i}"] = layer
        return {"params": params, "state": state}

    def _layer(self, i: int, p, st, h):
        eps = self.config.norm_eps
        key, op = self._operator(i)
        y, _ = op.forward(p[key], EMPTY, rms_norm(h, p["ln1"], eps))
        h = h + y
        x = rms_norm(h, p["ln2"], eps)
        if self._is_dense(i):
            with jax.named_scope("lm/dense_ffn"):
                return h + swiglu(x, p["ffn"]), EMPTY
        y, st = self.moe.forward(p["moe"], st, x)
        return h + y, st

    def forward(self, params, state, ids, training=False, rng=None):
        c = self.config
        h = jnp.take(params["embed"], ids.astype(jnp.int32), axis=0)
        new_state = {}
        for i in range(c.num_hidden_layers):
            key = f"layer{i}"
            fn = functools.partial(self._layer, i)
            if training:
                fn = jax.checkpoint(fn)
            h, st = fn(params[key], state.get(key, EMPTY), h)
            if st:
                new_state[key] = st
        h = rms_norm(h, params["ln_out"], c.norm_eps)
        with jax.named_scope("lm/head"):
            logits = jnp.einsum(
                "btd,vd->btv", cast_compute(h), cast_compute(params["embed"]),
                preferred_element_type=jnp.float32)
        return logits, new_state
