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

Two more mixer kinds (MiniCPM-SALA's ``mixer_types``, read as
``layer_types``): ``"lightning-attn"`` (``nn.sparse_linear_attention.
LightningAttention``: causal linear attention with a decay a head) and
``"minicpm4"`` (``SparseBlockAttention``: InfLLM-v2 attention over each
query's selected key blocks; its block list is kept across the layer's
``jax.checkpoint``, not recomputed).  Such a decoder has no experts (every
FFN dense, ``held_ffn_columns`` of them on a tensor-parallel rank), an
untied ``head``, a share of heads ``held_heads`` and MiniCPM's muP scales
(``scale_emb``, ``scale_depth / sqrt(mup_denominator)`` on every branch,
the final state over ``hidden_size / dim_model_base``), all of which
default to what LFM2 had: its parameter tree and logits are as they were.

A fifth (Granite-4.0-H's ``granitemoehybrid``, its ``layer_types``
``"mamba"`` and ``"attention"`` read as ``"mamba"`` and
``"full_attention"``): ``"mamba"`` (``nn.mamba2.Mamba2``: the Mamba-2
mixer, its selective scan through ``ops/ssd.py``, whole on every
tensor-parallel rank).  Granite's scalars are this family's:
``embedding_multiplier`` is ``scale_emb``, ``residual_multiplier`` the
scale of every branch, ``logits_scaling`` divides the normed final state,
``attention_multiplier`` is the softmax scale; its attention layers hold
``held_heads`` of the query heads, have no QK-norm (``qk_norm`` False)
and, with ``position_embedding_type`` ``"nope"``, no positions.

This decoder and ``models/mla_moe_lm.py`` share their parts (``HeldMoE``,
``swiglu``, ``rms_norm``, ``rope``, the state metrics) by import and their
skeleton by shape only.

Precision is the repo's policy: float32 parameters, bfloat16 matmul inputs
on a TPU with float32 accumulation; router, softmax, RMSNorm, RoPE, the
convolution's gates and taps in float32.  When training, every layer is
recomputed in the backward pass (``jax.checkpoint`` per layer; the layers'
inputs are what is kept, and of what a layer holds, the attention kernels'
``out`` and ``lse`` and a sparse layer's block list:
``ops.common.layer_remat_policy``): there is no switch.

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
from bigdl_tpu.nn.mamba2 import Mamba2
from bigdl_tpu.nn.module import EMPTY, Module
from bigdl_tpu.nn.short_conv import GatedShortConv
from bigdl_tpu.nn.sparse_linear_attention import (SELECTION,
                                                  LightningAttention,
                                                  SparseBlockAttention)
from bigdl_tpu.ops.common import layer_remat_policy
from bigdl_tpu.parallel.moe import HeldMoE, swiglu, swiglu_init
from bigdl_tpu.tensor.policy import cast_compute

LAYER_TYPES = ("conv", "full_attention", "lightning-attn", "minicpm4",
               "mamba")
# MiniCPM4-8B's published ``sparse_config`` (InfLLM-v2), the default of a
# ``minicpm4`` layer
SPARSE_CONFIG = (("block_size", 64), ("dense_len", 8192),
                 ("init_blocks", 1), ("kernel_size", 32),
                 ("kernel_stride", 16), ("topk", 64), ("window_size", 2048))
# the source's mixer switches, and the one value each has where the two
# new mixers are built (MiniCPM-SALA's): RoPE, QK-norm, the output gates
# and lightning's output norm always, no RoPE in the sparse layer
MIXER_FIXED = (("attn_use_output_gate", True), ("attn_use_rope", False),
               ("lightning_scale", "1/sqrt(d)"), ("lightning_use_rope", True),
               ("qk_norm", True), ("use_output_gate", True),
               ("use_output_norm", True))
# Granite-4.0-H's names (``granitemoehybrid``) for this family's, and the
# one value each of its switches has where its layers are built: one group,
# no projection bias, a convolution bias, RMSNorm
GRANITE_NAMES = (("embedding_multiplier", "scale_emb"),
                 ("residual_multiplier", "scale_depth"),
                 ("shared_intermediate_size", "intermediate_size"))
GRANITE_FIXED = (("mamba_conv_bias", True), ("mamba_n_groups", 1),
                 ("mamba_proj_bias", False),
                 ("normalization_function", "rmsnorm"))
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
    # no experts (0): every layer's FFN is dense
    moe_intermediate_size: int = 0
    num_experts: int = 0
    num_experts_per_tok: int = 0
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
    # 0: hidden_size // num_attention_heads
    head_dim: int = 0
    # a rank's share of the query heads of every mixer that has heads,
    # (first, count) of ``num_attention_heads``; None = all of them
    held_heads: Optional[Tuple[int, int]] = None
    # a rank's share of the dense FFN's columns; None = intermediate_size
    held_ffn_columns: Optional[int] = None
    tie_word_embeddings: bool = True
    # MiniCPM's muP scales: the embedding times ``scale_emb``, each residual
    # branch times ``scale_depth / sqrt(mup_denominator)``, the normed final
    # state over ``hidden_size / dim_model_base``; 1 where not given
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    mup_denominator: Optional[int] = None
    dim_model_base: Optional[int] = None
    # ``lightning-attn`` (0: as num_attention_heads / head_dim)
    lightning_nh: int = 0
    lightning_nkv: int = 0
    lightning_head_dim: int = 0
    # ``minicpm4``
    sparse_config: Tuple[Tuple[str, int], ...] = SPARSE_CONFIG
    # ``full_attention``: "nope" = no positions; per-head QK-norm or none;
    # the softmax scale (None: head_dim ** -0.5)
    position_embedding_type: str = "rope"
    qk_norm: bool = True
    attention_multiplier: Optional[float] = None
    # the normed final state over this before the head (None: as
    # ``dim_model_base`` says)
    logits_scaling: Optional[float] = None
    # ``mamba`` (Mamba-2, one group of B and C)
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256

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
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.hidden_size // self.num_attention_heads)
        if self.position_embedding_type not in ("rope", "nope"):
            raise ValueError(f"position_embedding_type "
                             f"{self.position_embedding_type!r}: rope or "
                             "nope")
        if "mamba" in self.layer_types and (
                self.mamba_n_heads * self.mamba_d_head
                != self.mamba_expand * self.hidden_size):
            raise ValueError("mamba: mamba_n_heads x mamba_d_head must be "
                             "mamba_expand x hidden_size")
        if "lightning-attn" in self.layer_types and (
                self.lightning_heads != self.num_attention_heads
                or self.lightning_nkv not in (0, self.lightning_heads)):
            raise ValueError("lightning-attn: as many key/value heads as "
                             "query heads, as many as the layer's")

    @property
    def rope_theta(self) -> float:
        return float(dict(self.rope_parameters)["rope_theta"])

    @property
    def lightning_heads(self) -> int:
        return self.lightning_nh or self.num_attention_heads

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / (self.mup_denominator or 1) ** 0.5

    @property
    def head_divisor(self) -> float:
        if self.logits_scaling is not None:
            return float(self.logits_scaling)
        return self.hidden_size / (self.dim_model_base or self.hidden_size)

    @classmethod
    def from_dict(cls, cfg: dict) -> "HybridMoEConfig":
        """The source's keys as they are; MiniCPM's names (``mixer_types``,
        ``rms_norm_eps``, a top-level ``rope_theta``) are read as this
        family's, and its switches (``MIXER_FIXED``) must hold the one
        value the two mixers are built for; so are Granite-4.0-H's
        (``GRANITE_NAMES``, ``layer_types`` ``"attention"`` as
        ``"full_attention"``; ``GRANITE_FIXED``)."""
        cfg = dict(cfg)
        for fixed, kinds in ((MIXER_FIXED, "the lightning-attn and minicpm4 "
                              "mixers"), (GRANITE_FIXED, "the mamba mixer")):
            for key, value in fixed:
                if cfg.get(key, value) != value:
                    raise ValueError(f"{key}={cfg[key]!r}: {kinds} are "
                                     f"built for {value!r} only")
        if cfg.get("model_type") == "granitemoehybrid":
            cfg.update({ours: cfg[theirs] for theirs, ours in GRANITE_NAMES
                        if theirs in cfg})
            cfg["layer_types"] = ["full_attention" if k == "attention" else k
                                  for k in cfg["layer_types"]]
            # its attention layers have no per-head QK-norm
            cfg["qk_norm"] = False
        for theirs, ours in (("mixer_types", "layer_types"),
                             ("rms_norm_eps", "norm_eps")):
            if theirs in cfg:
                cfg.setdefault(ours, cfg[theirs])
        if "rope_theta" in cfg and "rope_parameters" not in cfg:
            cfg["rope_parameters"] = {"rope_theta": cfg["rope_theta"],
                                      "rope_type": "default"}
        names = {f.name for f in fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        kw["layer_types"] = tuple(kw["layer_types"])
        for key in ("held_experts", "held_heads"):
            if kw.get(key) is not None:
                kw[key] = tuple(kw[key])
        for key in ("rope_parameters", "sparse_config"):
            if kw.get(key) is not None:
                kw[key] = tuple(sorted(kw[key].items()))
        return cls(**kw)


class HybridMoELM(Module):
    """``forward(params, state, ids)`` → (batch, seq, vocab) float32 logits
    and the new state (each expert layer's bias and routing statistics,
    each sparse layer's counters)."""

    def __init__(self, config: HybridMoEConfig, name=None):
        super().__init__(name)
        c = self.config = config
        kinds = set(c.layer_types)
        self.conv = GatedShortConv(c.hidden_size, c.conv_L_cache)
        if "full_attention" in kinds:
            self.attn = GroupedQueryAttention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.head_dim, held=c.held_heads,
                rope_theta=(None if c.position_embedding_type == "nope"
                            else c.rope_theta),
                qk_norm_eps=c.norm_eps if c.qk_norm else None,
                sm_scale=c.attention_multiplier)
        if "mamba" in kinds:
            self.mamba = Mamba2(c.hidden_size, c.mamba_n_heads,
                                c.mamba_d_head, c.mamba_d_state,
                                c.mamba_d_conv, eps=c.norm_eps,
                                chunk=c.mamba_chunk_size)
        if "lightning-attn" in kinds:
            self.lightning = LightningAttention(
                c.hidden_size, c.lightning_heads,
                c.lightning_head_dim or c.head_dim, held=c.held_heads,
                rope_theta=c.rope_theta, eps=c.norm_eps)
        if "minicpm4" in kinds:
            self.sparse = SparseBlockAttention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.head_dim, held=c.held_heads, eps=c.norm_eps,
                **dict(c.sparse_config))
        if c.num_experts:
            self.moe = HeldMoE(
                c.num_experts, c.moe_intermediate_size,
                c.num_experts_per_tok, held=c.held_experts, shared_hidden=0,
                scale=c.routed_scaling_factor, norm_topk=c.norm_topk_prob,
                norm_eps=TOPK_SUM_EPS)

    def _operator(self, i: int):
        """(parameter key, module) of layer ``i``'s token mixer."""
        return {"conv": ("conv", self.conv),
                "full_attention": ("attn", getattr(self, "attn", None)),
                "lightning-attn": ("lightning", getattr(
                    self, "lightning", None)),
                "minicpm4": ("sparse", getattr(self, "sparse", None)),
                "mamba": ("mamba", getattr(self, "mamba", None)),
                }[self.config.layer_types[i]]

    def _is_dense(self, i: int) -> bool:
        return i < self.config.num_dense_layers or not self.config.num_experts

    def init(self, rng, ids):
        c = self.config
        d = c.hidden_size
        ks = jax.random.split(rng, c.num_hidden_layers + 1)
        x = jnp.zeros(jnp.shape(ids) + (d,), jnp.float32)
        params = {"embed": jax.random.normal(ks[0], (c.vocab_size, d))
                  * d ** -0.5,
                  "ln_out": jnp.ones((d,))}
        if not c.tie_word_embeddings:
            # logits of a normed state near unit standard deviation after
            # the division by ``head_divisor``
            params["head"] = jax.random.normal(
                jax.random.fold_in(ks[0], 1), (c.vocab_size, d)) * (
                    c.head_divisor * d ** -0.5)
        state = {}
        for i in range(c.num_hidden_layers):
            k_op, k_ffn = jax.random.split(ks[i + 1])
            key, op = self._operator(i)
            v = op.init(k_op, x)
            layer = {"ln1": jnp.ones((d,)), "ln2": jnp.ones((d,)),
                     key: v["params"]}
            if v["state"]:
                state[f"layer{i}"] = {key: v["state"]}
            if self._is_dense(i):
                layer["ffn"] = swiglu_init(
                    k_ffn, d, c.held_ffn_columns or c.intermediate_size)
            else:
                v = self.moe.init(k_ffn, x)
                layer["moe"] = v["params"]
                state[f"layer{i}"] = dict(state.get(f"layer{i}", {}),
                                          **v["state"])
            params[f"layer{i}"] = layer
        return {"params": params, "state": state}

    def _layer(self, i: int, p, st, h):
        c = self.config
        eps, scale = c.norm_eps, c.residual_scale
        key, op = self._operator(i)
        st = dict(st) if st else {}
        y, op_st = op.forward(p[key], st.pop(key, EMPTY),
                              rms_norm(h, p["ln1"], eps))
        h = h + (y if scale == 1.0 else scale * y)
        x = rms_norm(h, p["ln2"], eps)
        if self._is_dense(i):
            with jax.named_scope("lm/dense_ffn"):
                y = swiglu(x, p["ffn"])
            st = EMPTY
        else:
            y, st = self.moe.forward(p["moe"], st, x)
        if op_st:
            st = dict(st or {}, **{key: op_st})
        return h + (y if scale == 1.0 else scale * y), st

    def forward(self, params, state, ids, training=False, rng=None):
        c = self.config
        h = jnp.take(params["embed"], ids.astype(jnp.int32), axis=0)
        if c.scale_emb != 1.0:
            h = h * c.scale_emb
        new_state = {}
        for i in range(c.num_hidden_layers):
            key = f"layer{i}"
            fn = functools.partial(self._layer, i)
            if training:
                # the attention kernels' out and lse, and a sparse layer's
                # block selection, are kept, not recomputed
                fn = jax.checkpoint(fn, policy=layer_remat_policy(SELECTION))
            h, st = fn(params[key], state.get(key, EMPTY), h)
            if st:
                new_state[key] = st
        h = rms_norm(h, params["ln_out"], c.norm_eps)
        if c.head_divisor != 1.0:
            h = h / c.head_divisor
        head = params["embed" if c.tie_word_embeddings else "head"]
        with jax.named_scope("lm/head"):
            logits = jnp.einsum(
                "btd,vd->btv", cast_compute(h), cast_compute(head),
                preferred_element_type=jnp.float32)
        return logits, new_state
