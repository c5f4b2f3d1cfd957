"""The two token mixers of a sparse/linear hybrid decoder (MiniCPM-SALA's
``lightning-attn`` and ``minicpm4`` layers), each holding a share of the
published heads: ``held = (first, count)`` of ``heads``.

:class:`LightningAttention` — causal linear attention with a per-head
decay through ``ops/lightning_attention.py``::

    q = RoPE(RMSNorm_head(u W_q)), k = RoPE(RMSNorm_head(u W_k)), v = u W_v
    o = lightning(q, k, v; slope_j = 2^(−8 (j+1) / heads)) / sqrt(head_dim)
    Mix(u) = W_o(RMSNorm_head(o) ⊙ sigmoid(u W_g))

(RoPE, QK-norm, the output norm and the gate always: the only values the
source's config gives them).  :class:`SparseBlockAttention` — InfLLM-v2
attention through ``ops/sparse_attention.py``: ``q = RMSNorm_head(u
W_q)``, ``k = RMSNorm_head(u W_k)``, ``v = u W_v``, no RoPE; a sequence longer than
``dense_len`` attends over each query's selected key blocks (the selection
shared by the held heads of a group), a shorter one densely (the flash
kernels on a TPU); ``Mix(u) = W_o(o ⊙ sigmoid(u W_g))``.  It counts, in
its model state, the (query, key block) pairs it attended and those the
causal mask left visible (``sparse.selected_blocks``,
``sparse.visible_blocks``: a share the selection rule fixes, min(topk,
visible) a query), and the (query tile, key span) pairs the kernels walk
against the causal bound at the kernels' tiles (``sparse.walked_spans``,
``sparse.causal_spans``: what the kernels pay for; equal where they walk
the whole triangle, as a dense sequence does) (``obs/state_metrics.py``).

A held share of heads is a share of the columns of W_q, W_k, W_v, W_g and
of the rows of W_o: the rank's ``Mix`` is its partial sum of the layer's
output, which goes on unreduced (docs/parallelism.md §Held heads of a
linear or sparse mixer).  Per-head RMSNorm weights: one ``head_dim`` weight
for all query heads and one for all key heads; the output norm's weight is
per channel, each head its own ``head_dim``.  Device scopes ``sala/proj``,
``sala/lightning``, ``sala/select``, ``sala/sparse_attn``,
``sala/dense_attn``."""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bigdl_tpu.nn.attention import (_fan_in_normal, _flash_wanted, _project,
                                    dot_product_attention, held_share, rope)
from bigdl_tpu.nn.layers import rms_norm
from bigdl_tpu.nn.module import EMPTY, Module
from bigdl_tpu.obs.state_metrics import bump_state_metrics, new_state_metrics
from bigdl_tpu.ops.lightning_attention import alibi_slopes, \
    lightning_attention
from bigdl_tpu.ops.sparse_attention import (causal_spans, select_blocks,
                                            sparse_attention, visible_blocks,
                                            walked_spans)

# the name the selection is saved under across ``jax.checkpoint``
SELECTION = "sala_selection"
SPARSE_COUNTERS = ("sparse.selected_blocks", "sparse.visible_blocks",
                   "sparse.walked_spans", "sparse.causal_spans")
# the seeded init's per-head QK-norm weight: at 1 a NoPE query's scores
# over thousands of keys have standard deviation 1 and its attention
# averages v, so which blocks it selected hardly reaches the output; at 2
# they have 4 and its attention lies on a few keys (PERF.md §4, PR 38)
QK_NORM_INIT = 2.0


class _HeldHeads(Module):
    """Projections shared by both mixers: a share of ``heads`` query heads
    of ``head_dim`` with their QK-norm, the output gate and W_o."""

    def __init__(self, hidden_size, heads, head_dim, held, eps, name):
        super().__init__(name)
        self.hidden_size, self.heads, self.head_dim = (hidden_size, heads,
                                                       head_dim)
        self.first, self.count, _ = held_share(heads, heads, held)
        self.eps = eps

    def _build(self, rng, kv_count):
        d, hd, n = self.hidden_size, self.head_dim, self.count
        ks = jax.random.split(rng, 5)
        return {"wq": _fan_in_normal(ks[0], d, n * hd),
                "wk": _fan_in_normal(ks[1], d, kv_count * hd),
                "wv": _fan_in_normal(ks[2], d, kv_count * hd),
                "wo": _fan_in_normal(ks[3], n * hd, d),
                "wg": _fan_in_normal(ks[4], d, n * hd),
                "q_norm": jnp.full((hd,), QK_NORM_INIT),
                "k_norm": jnp.full((hd,), QK_NORM_INIT)}

    def _heads(self, params, u, key, n):
        """(batch, n, T, head_dim) of ``u W_key``, RMSNormed per head for
        q and k."""
        b, t, _ = u.shape
        y = _project(u, params["w" + key]).reshape(b, t, n, self.head_dim)
        if key in "qk":
            y = rms_norm(y, params[key + "_norm"], self.eps)
        return y.transpose(0, 2, 1, 3)

    def _out(self, params, u, o):
        """``W_o(o ⊙ sigmoid(u W_g))`` of o (batch, T, count · head_dim)."""
        return _project(o * jax.nn.sigmoid(_project(u, params["wg"])),
                        params["wo"])


class LightningAttention(_HeldHeads):
    def __init__(self, hidden_size: int, heads: int, head_dim: int, *,
                 held: Optional[Tuple[int, int]] = None,
                 rope_theta: float = 10000.0, eps: float = 1e-6, name=None):
        super().__init__(hidden_size, heads, head_dim, held, eps, name)
        self.rope_theta = rope_theta
        self.slopes = alibi_slopes(heads, self.first, self.count)

    def build(self, rng, x):
        p = self._build(rng, self.count)
        p["o_norm"] = jnp.ones((self.count * self.head_dim,))
        return p, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        b, t, _ = x.shape
        n, hd = self.count, self.head_dim
        with jax.named_scope("sala/proj"):
            q, k, v = (self._heads(params, x, key, n) for key in "qkv")
            q, k = rope(q, self.rope_theta), rope(k, self.rope_theta)
        with jax.named_scope("sala/lightning"):
            o = lightning_attention(q, k, v, self.slopes)
        with jax.named_scope("sala/proj"):
            o = rms_norm(o.transpose(0, 2, 1, 3),
                         params["o_norm"].reshape(n, hd), self.eps)
            return self._out(params, x, o.reshape(b, t, n * hd)), EMPTY


class SparseBlockAttention(_HeldHeads):
    def __init__(self, hidden_size: int, heads: int, kv_heads: int,
                 head_dim: int, *, held: Optional[Tuple[int, int]] = None,
                 eps: float = 1e-6, kernel_size: int = 32,
                 kernel_stride: int = 16, block_size: int = 64,
                 topk: int = 64, init_blocks: int = 1,
                 window_size: int = 2048, dense_len: int = 8192, name=None):
        super().__init__(hidden_size, heads, head_dim, held, eps, name)
        self.kv_count = held_share(heads, kv_heads, held)[2]
        self.select_kw = dict(kernel=kernel_size, stride=kernel_stride,
                              block=block_size, topk=topk,
                              init_blocks=init_blocks, window=window_size)
        self.dense_len = dense_len

    def build(self, rng, x):
        return self._build(rng, self.kv_count), {
            "metrics": new_state_metrics(counters=SPARSE_COUNTERS)}

    def select(self, q, k):
        """The groups' block lists (batch, kv_count, T, topk) of q (batch,
        count, T, hd) and k (batch, kv_count, T, hd)."""
        b, _, t, hd = q.shape
        g = self.kv_count
        with jax.named_scope("sala/select"):
            return select_blocks(
                jax.lax.stop_gradient(q.reshape(b, g, -1, t, hd)),
                jax.lax.stop_gradient(k), **self.select_kw)

    def mix(self, params, x, sel=None):
        """``(Mix(x), sel)``: the layer's partial output and the selection
        it attended through (None for a dense sequence).  A ``sel`` given
        is used as it is (a rank handed its group's selection)."""
        b, t, _ = x.shape
        n, g, hd = self.count, self.kv_count, self.head_dim
        with jax.named_scope("sala/proj"):
            q, k, v = (self._heads(params, x, key, m)
                       for key, m in (("q", n), ("k", g), ("v", g)))
        if t <= self.dense_len and sel is None:
            with jax.named_scope("sala/dense_attn"):
                o = self._dense(q, k, v)
        else:
            if sel is None:
                sel = checkpoint_name(self.select(q, k), SELECTION)
            with jax.named_scope("sala/sparse_attn"):
                o = sparse_attention(
                    q.reshape(b, g, n // g, t, hd), k, v, sel,
                    block=self.select_kw["block"]).reshape(b, n, t, hd)
        with jax.named_scope("sala/proj"):
            o = o.transpose(0, 2, 1, 3).reshape(b, t, n * hd)
            return self._out(params, x, o), sel

    def _dense(self, q, k, v):
        t = q.shape[2]
        if _flash_wanted(None):
            from bigdl_tpu.ops.flash_attention import flash_attention

            return flash_attention(q, k, v, causal=True)
        group = q.shape[1] // k.shape[1]
        return dot_product_attention(
            q, jnp.repeat(k, group, 1), jnp.repeat(v, group, 1),
            mask=jnp.tril(jnp.ones((t, t), bool)))

    def forward(self, params, state, x, training=False, rng=None):
        b, t, _ = x.shape
        y, sel = self.mix(params, x)
        rows = b * self.kv_count
        visible = rows * visible_blocks(t, self.select_kw["block"])
        if sel is None:
            selected, (walked, causal) = visible, (rows * causal_spans(t),) * 2
        else:
            selected = jnp.sum(sel >= 0)
            walked, causal = walked_spans(sel, self.select_kw["block"])
        return y, {"metrics": bump_state_metrics(
            state["metrics"], {"sparse.selected_blocks": selected,
                               "sparse.visible_blocks": visible,
                               "sparse.walked_spans": walked,
                               "sparse.causal_spans": causal}, {})}
