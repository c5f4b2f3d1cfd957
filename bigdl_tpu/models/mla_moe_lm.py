"""Sparse-expert decoder LM with latent attention, assembled from a config.

The family DeepSeek-V2 opened (arXiv:2405.04434) and that GLM-4.7-Flash,
among others, publishes in 2026: pre-norm residual blocks ``h += MLA(
RMSNorm(h))``, ``h += FFN(RMSNorm(h))``; the first ``first_k_dense_replace``
layers carry a dense gated-SiLU FFN, the rest a routed expert layer with a
shared expert (``parallel.moe.HeldMoE``: sigmoid scores, top-k of score +
correction bias, no token dropped); final RMSNorm; an UNTIED output head.
With ``hc_mult`` = n > 1 the residual path is n streams mixed around every
sublayer by manifold-constrained hyper-connections
(``nn.hyper_connection``): the embedding starts every stream, the streams'
sum is read out before the final norm.

:class:`MLAMoEConfig` takes the published ``config.json`` keys as they are
(``MLAMoEConfig.from_dict`` ignores the keys that say nothing about the
shape).  ``held_experts=(first, count)`` makes the model ONE chip's share of
an expert-parallel job: every expert layer holds ``count`` of the
``n_routed_experts`` the router scores (docs/parallelism.md §Held-share
expert layer); ``vocab_size`` is then that chip's slice of the vocabulary —
a sliced vocabulary is a smaller vocabulary.  One rank of a tensor-parallel
group is written the same way (docs/parallelism.md §Tensor-parallel share):
``num_attention_heads`` counts the heads held and ``held_ffn_columns`` the
dense FFN's columns held of ``intermediate_size``; what ``W_o`` and
``W_down`` give is this rank's partial sum and goes on unreduced.

Precision is the repo's policy: float32 parameters, bfloat16 matmul inputs
on a TPU with float32 accumulation; router, softmax, RMSNorm and RoPE in
float32.  When training, every layer is recomputed in the backward pass
(``jax.checkpoint`` per layer; the layers' inputs are what is kept, and the
flash kernels' ``out`` and ``lse``, so the forward kernel is not run again:
``ops.common.layer_remat_policy``): there is no switch."""

import functools
from dataclasses import dataclass, fields
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import LatentAttention
from bigdl_tpu.nn.hyper_connection import HyperConnection
from bigdl_tpu.nn.layers import rms_norm
from bigdl_tpu.nn.module import EMPTY, Module
from bigdl_tpu.obs.state_metrics import (bump_state_metrics,
                                         new_state_metrics)
from bigdl_tpu.ops.common import layer_remat_policy
from bigdl_tpu.parallel.moe import HeldMoE, swiglu, swiglu_init
from bigdl_tpu.tensor.policy import cast_compute

GAIN = "hc.stream_gain"
_STREAM_RMS = "_stream_rms"


def _rms_of(a):
    return jnp.sqrt(jnp.mean(jnp.square(a.astype(jnp.float32))))


@dataclass(frozen=True)
class MLAMoEConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    # a config's ``rope_scaling`` as sorted (key, value) pairs; None = plain
    rope_scaling: Optional[Tuple[Tuple[str, object], ...]] = None
    # residual streams; 1 = ``h + f(h)``
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # this chip's share of the experts, (first, count); None = all of them
    held_experts: Optional[Tuple[int, int]] = None
    # the dense FFN's columns held here; None = all ``intermediate_size``
    held_ffn_columns: Optional[int] = None

    @classmethod
    def from_dict(cls, cfg: dict) -> "MLAMoEConfig":
        names = {f.name for f in fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        if kw.get("held_experts") is not None:
            kw["held_experts"] = tuple(kw["held_experts"])
        if kw.get("rope_scaling") is not None:
            kw["rope_scaling"] = tuple(sorted(kw["rope_scaling"].items()))
        return cls(**kw)


class MLAMoELM(Module):
    """``forward(params, state, ids)`` → (batch, seq, vocab) float32 logits
    and the new state (each expert layer's correction bias and routing
    statistics)."""

    def __init__(self, config: MLAMoEConfig, name=None):
        super().__init__(name)
        c = self.config = config
        self.attn = LatentAttention(
            c.hidden_size, c.num_attention_heads, q_rank=c.q_lora_rank,
            kv_rank=c.kv_lora_rank, nope_dim=c.qk_nope_head_dim,
            rope_dim=c.qk_rope_head_dim, v_dim=c.v_head_dim,
            rope_theta=c.rope_theta, rope_scaling=c.rope_scaling,
            eps=c.rms_norm_eps)
        self.hc = None if c.hc_mult <= 1 else HyperConnection(
            c.hc_mult, c.hidden_size, sinkhorn_iters=c.hc_sinkhorn_iters,
            eps=c.hc_eps,
            clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max))
        self.moe = HeldMoE(
            c.n_routed_experts, c.moe_intermediate_size,
            c.num_experts_per_tok, held=c.held_experts,
            shared_hidden=c.n_shared_experts * c.moe_intermediate_size,
            scale=c.routed_scaling_factor, norm_topk=c.norm_topk_prob)

    def _is_dense(self, i: int) -> bool:
        return i < self.config.first_k_dense_replace

    def init(self, rng, ids):
        c = self.config
        d = c.hidden_size
        ks = jax.random.split(rng, c.num_hidden_layers + 2)
        x = jnp.zeros(jnp.shape(ids) + (d,), jnp.float32)
        params = {"embed": jax.random.normal(ks[0], (c.vocab_size, d)),
                  "ln_out": jnp.ones((d,)),
                  "head": jax.random.normal(ks[1], (d, c.vocab_size))
                  * d ** -0.5}
        state = {}
        if self.hc is not None:
            state["hc"] = {"metrics": new_state_metrics(means=(GAIN,))}
        for i in range(c.num_hidden_layers):
            ka, kf = jax.random.split(ks[i + 2])
            layer = {"ln1": jnp.ones((d,)), "ln2": jnp.ones((d,)),
                     "attn": self.attn.init(ka, x)["params"]}
            st = {}
            if self._is_dense(i):
                layer["ffn"] = swiglu_init(
                    kf, d, c.held_ffn_columns or c.intermediate_size)
            else:
                v = self.moe.init(kf, x)
                layer["moe"], st = v["params"], v["state"]
            if self.hc is not None:
                for j, key in enumerate(("hc_attn", "hc_ffn")):
                    v = self.hc.init(jax.random.fold_in(ks[i + 2], 2 + j))
                    layer[key], st = v["params"], dict(st, **{key: v["state"]})
            if st:
                state[f"layer{i}"] = st
            params[f"layer{i}"] = layer
        return {"params": params, "state": state}

    def _around(self, key, p, st, h, f):
        """One sublayer ``f: input -> (output, its new state)`` on the
        residual path: ``h + f(h)``, or the hyper-connection ``key``'s
        mixing around it."""
        if self.hc is None:
            y, new = f(h)
            return h + y, new
        u, coeffs = self.hc.pre(p[key], h)
        y, new = f(u)
        return (self.hc.post(h, y, coeffs),
                dict(new, **{key: self.hc.book(st[key], coeffs)}))

    def _layer(self, i: int, p, st, h):
        c = self.config
        eps = c.rms_norm_eps
        if self.hc is not None and i == 0:
            # every stream starts as the embedding; broadcast in here, so
            # that the layer's kept input is the embedding and not n copies
            h = jnp.broadcast_to(h, (c.hc_mult,) + h.shape)

        def attn(u):
            return self.attn.forward(p["attn"], EMPTY,
                                     rms_norm(u, p["ln1"], eps))

        def ffn(u):
            x = rms_norm(u, p["ln2"], eps)
            if self._is_dense(i):
                with jax.named_scope("lm/dense_ffn"):
                    return swiglu(x, p["ffn"]), EMPTY
            return self.moe.forward(p["moe"], st, x)

        h, st_attn = self._around("hc_attn", p, st, h, attn)
        h, st_ffn = self._around("hc_ffn", p, st, h, ffn)
        st = {**st_attn, **st_ffn}
        if self.hc is not None and i == c.num_hidden_layers - 1:
            # read out the streams' sum in here too: the layer's cotangent
            # is then one stream wide.  ``_STREAM_RMS`` rides the state out
            # and forward() takes it off again
            st[_STREAM_RMS] = jax.lax.stop_gradient(_rms_of(h))
            h = jnp.sum(h, axis=0)
        return h, st

    def forward(self, params, state, ids, training=False, rng=None):
        c = self.config
        h = jnp.take(params["embed"], ids.astype(jnp.int32), axis=0)
        first_rms = _rms_of(h) if self.hc is not None else None
        new_state = {}
        for i in range(c.num_hidden_layers):
            key = f"layer{i}"
            fn = functools.partial(self._layer, i)
            if training:
                fn = jax.checkpoint(fn, policy=layer_remat_policy())
            h, st = fn(params[key], state.get(key, EMPTY), h)
            if _STREAM_RMS in st:
                new_state["hc"] = {"metrics": bump_state_metrics(
                    state["hc"]["metrics"], {},
                    {GAIN: st.pop(_STREAM_RMS)
                     / jax.lax.stop_gradient(first_rms)})}
            if st:
                new_state[key] = st
        h = rms_norm(h, params["ln_out"], c.rms_norm_eps)
        with jax.named_scope("lm/head"):
            logits = jnp.matmul(cast_compute(h), cast_compute(params["head"]),
                                preferred_element_type=jnp.float32)
        return logits, new_state
