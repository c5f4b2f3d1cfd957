"""Mixture-of-Experts with expert parallelism over the "expert" mesh axis.

New capability vs the reference (SURVEY.md §3.5: expert parallelism absent).
TPU-native design (GShard/Switch formulation): top-k gating builds a
capacity-bounded dispatch tensor; tokens are routed to expert shards with ONE
``jax.lax.all_to_all`` (the canonical EP collective over ICI), each shard runs
its local experts as a single batched einsum (MXU-friendly — no scalar
routing loops), and a second all_to_all brings expert outputs home where they
are combined with the gating weights.  Everything is static-shaped
(capacity-dropped tokens pass through unchanged via the residual), so the
whole layer jits and differentiates cleanly.

Two entry points:
- ``moe_gate`` / ``moe_apply_local``: single-shard (all experts local) — used
  on one device and inside tests as the golden reference.
- ``moe_apply_ep``: expert-parallel functional form, call inside shard_map
  with tokens sharded over data and experts sharded over the expert axis.
- ``MoE``: nn.Module wrapper (local experts) for Sequential/keras use.

Beside that capacity path (which DROPS what overflows an expert's buffer)
stands the **held-share, no-drop layer** a 2026 expert model trains with:
``route_sigmoid_topk`` (sigmoid scores over ALL experts, top-k of score +
correction bias, weights from the scores alone), ``held_experts_apply`` (the
part of the result the experts ``held=(first, count)`` give, for every
token routed to them, whatever the imbalance: sort the (token, choice)
pairs by expert, one grouped matrix product per projection over the held
pairs' rows, sum them into their tokens) and the module ``HeldMoE`` (router
+ held experts + shared expert).  A chip of an expert-parallel job holds
``count`` of the experts and computes its own part; what the absent experts
add arrives by the exchange between chips, which this file does not have
yet — on one chip the layer runs without it and nothing stands in for it
(docs/parallelism.md §Held-share expert layer).
"""

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import Module, EMPTY
from bigdl_tpu.obs.state_metrics import (bump_state_metrics,
                                         new_state_metrics)
from bigdl_tpu.runtime.mesh import AXIS_EXPERT
from bigdl_tpu.tensor.policy import cast_compute


class GateOutput(NamedTuple):
    combine: jnp.ndarray    # (T, E, C) — combine weights (0 where dropped)
    dispatch: jnp.ndarray   # (T, E, C) bool — one-hot dispatch mask
    aux_loss: jnp.ndarray   # scalar load-balancing loss (Switch-style)


def moe_gate(logits: jnp.ndarray, capacity: int, k: int = 2) -> GateOutput:
    """Top-k gating with capacity. logits: (T, E)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    # load-balance aux loss uses the top-1 assignment fractions (Switch eq. 4)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = jnp.sum(frac_tokens * frac_probs) * E

    combine = jnp.zeros((T, E, capacity), jnp.float32)
    dispatch = jnp.zeros((T, E, capacity), bool)
    remaining = probs
    # expert buffer fill level carries across the k rounds so a token's
    # 2nd choice lands after all 1st choices took their slots in that round
    fill = jnp.zeros((E,), jnp.int32)
    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)                    # (T,)
        gate = jnp.take_along_axis(remaining, choice[:, None], -1)[:, 0]
        onehot = jax.nn.one_hot(choice, E, dtype=jnp.int32)        # (T, E)
        pos = jnp.cumsum(onehot, axis=0) - 1 + fill[None, :]       # slot index
        pos_tok = jnp.sum(pos * onehot, axis=-1)                   # (T,)
        keep = pos_tok < capacity
        slot = jax.nn.one_hot(pos_tok, capacity, dtype=jnp.float32)  # (T, C)
        d = (onehot.astype(jnp.float32)[:, :, None] * slot[:, None, :]
             * keep[:, None, None].astype(jnp.float32))
        dispatch = jnp.logical_or(dispatch, d > 0)
        combine = combine + d * gate[:, None, None]
        fill = fill + jnp.sum(onehot, axis=0)
        remaining = remaining * (1.0 - onehot.astype(jnp.float32))

    # renormalize combine weights over the selected experts (GShard style)
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = jnp.where(denom > 0, combine / jnp.maximum(denom, 1e-9), 0.0)
    return GateOutput(combine, dispatch, aux)


def _expert_ffn(w1, b1, w2, b2, x, act):
    # x: (E, C, d); w1: (E, d, h)
    h = act(jnp.einsum("ecd,edh->ech", x, w1,
                       preferred_element_type=jnp.float32).astype(x.dtype)
            + b1[:, None, :])
    return (jnp.einsum("ech,ehd->ecd", h, w2,
                       preferred_element_type=jnp.float32).astype(x.dtype)
            + b2[:, None, :])


def moe_apply_local(params, x, *, capacity_factor: float = 1.25, k: int = 2,
                    act: Callable = jax.nn.gelu
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All experts local. x: (T, d). params: {wg, w1, b1, w2, b2} with
    expert-major leaves (E, ...). Returns (y, aux_loss)."""
    T, d = x.shape
    E = params["w1"].shape[0]
    capacity = max(1, int(np.ceil(T * capacity_factor * k / E)))
    logits = x @ params["wg"]                                     # (T, E)
    gate = moe_gate(logits, capacity, k)
    xe = jnp.einsum("td,tec->ecd", x,
                    gate.dispatch.astype(x.dtype))                # (E, C, d)
    ye = _expert_ffn(params["w1"], params["b1"], params["w2"], params["b2"],
                     xe, act)
    y = jnp.einsum("ecd,tec->td", ye, gate.combine.astype(x.dtype))
    return y, gate.aux_loss


def moe_apply_ep(params, x, *, n_expert_shards: int,
                 capacity_factor: float = 1.25, k: int = 2,
                 act: Callable = jax.nn.gelu,
                 axis_name: str = AXIS_EXPERT
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE — call inside shard_map.

    x: (T_local, d) — this shard's tokens.  params: expert-major leaves
    sharded on the expert axis, so the local block is (E_local, ...).
    Gating weights ``wg`` are (d, E_global) replicated.

    Route: dispatch (T,E,C) → (E_global, C, d) → all_to_all → each shard
    holds (E_local, S*C, d) → batched expert FFN → all_to_all back → combine.
    """
    T, d = x.shape
    E_local = params["w1"].shape[0]
    E = E_local * n_expert_shards
    capacity = max(1, int(np.ceil(T * capacity_factor * k / E)))
    logits = x @ params["wg"]                                     # (T, E)
    gate = moe_gate(logits, capacity, k)
    xe = jnp.einsum("td,tec->ecd", x,
                    gate.dispatch.astype(x.dtype))                # (E, C, d)
    if n_expert_shards > 1:
        # (E, C, d) -> (S, E_local, C, d); all_to_all swaps the shard dim for
        # the token-source dim: each shard receives its experts' tokens from
        # every peer -> (S, E_local, C, d) with S = source shard
        xe = xe.reshape(n_expert_shards, E_local, capacity, d)
        xe = jax.lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=0,
                                tiled=False)
        # (S, E_local, C, d) -> (E_local, S*C, d)
        xe = xe.transpose(1, 0, 2, 3).reshape(E_local,
                                              n_expert_shards * capacity, d)
    ye = _expert_ffn(params["w1"], params["b1"], params["w2"], params["b2"],
                     xe, act)
    if n_expert_shards > 1:
        ye = ye.reshape(E_local, n_expert_shards, capacity, d)
        ye = ye.transpose(1, 0, 2, 3)                 # (S, E_local, C, d)
        ye = jax.lax.all_to_all(ye, axis_name, split_axis=0, concat_axis=0,
                                tiled=False)
        ye = ye.reshape(E, capacity, d)
    y = jnp.einsum("ecd,tec->td", ye, gate.combine.astype(x.dtype))
    return y, gate.aux_loss


class MoE(Module):
    """MoE feed-forward block (local experts) as an nn.Module.

    Reference analog: none (SURVEY.md §3.5 — EP absent from BigDL); this is
    new TPU-native capability.  Expert = 2-layer MLP.
    """

    def __init__(self, num_experts: int, hidden: int, k: int = 2,
                 capacity_factor: float = 1.25, aux_weight: float = 1e-2,
                 act: Callable = jax.nn.gelu, name: Optional[str] = None):
        super().__init__(name)
        self.num_experts = num_experts
        self.hidden = hidden
        self.k = k
        self.capacity_factor = capacity_factor
        self.aux_weight = aux_weight
        self.act = act

    def build(self, rng, x):
        d = x.shape[-1]
        E, H = self.num_experts, self.hidden
        k1, k2, k3 = jax.random.split(rng, 3)
        s1 = 1.0 / np.sqrt(d)
        params = {
            "wg": jax.random.uniform(k1, (d, E), jnp.float32, -s1, s1),
            "w1": jax.random.uniform(k2, (E, d, H), jnp.float32, -s1, s1),
            "b1": jnp.zeros((E, H), jnp.float32),
            "w2": jax.random.uniform(k3, (E, H, d), jnp.float32,
                                     -1.0 / np.sqrt(H), 1.0 / np.sqrt(H)),
            "b2": jnp.zeros((E, d), jnp.float32),
        }
        return params, EMPTY

    def forward(self, params, state, x, training=False, rng=None):
        shape = x.shape
        flat = x.reshape(-1, shape[-1])
        y, aux = moe_apply_local(params, flat,
                                 capacity_factor=self.capacity_factor,
                                 k=self.k, act=self.act)
        # expose aux loss through state so criteria/training can pick it up
        return y.reshape(shape), {"aux_loss": aux * self.aux_weight}


# ---------------------------------------------------------------------------
# Held-share, no-drop expert layer (sigmoid routing, gated experts)
# ---------------------------------------------------------------------------

def route_sigmoid_topk(x, w_router, bias, k: int, scale: float = 1.0,
                       norm_topk: bool = True, norm_eps: float = 1e-20):
    """Aux-loss-free routing (DeepSeek-V3 ``noaux_tc`` with one group), all
    in float32.  x: (T, d); w_router: (E, d), one row an expert (the
    layout checkpoints publish); bias: (E,) correction bias.  ``s =
    sigmoid(x W^T)``; the ``k`` largest of ``s + bias`` are chosen; the
    weights are ``s`` of the chosen (the bias moves the choice, never the
    weight), divided by their sum plus ``norm_eps`` when ``norm_topk`` (a
    published forward pass adds 1e-20, or 1e-6: at float32 beside a sum of
    k sigmoids the first is no number, the second a few ulps), times
    ``scale``.  Returns (idx (T, k) int32, weights (T, k) float32)."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,ed->te", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return idx.astype(jnp.int32), w * scale


@jax.custom_vjp
def _rows_of(x, tok, pos, valid):
    """``x[tok]``: the rows of ``x`` (T, d) a buffer of C sorted rows works
    on.  ``pos`` (T, k) says where in the buffer each of a token's pairs
    sits and ``valid`` (T, k) which of them are held: with them the
    backward is :func:`_tokens_sum` of the cotangent, k gathers a token,
    where autodiff would emit a scatter-add of C rows."""
    return x[tok]


@jax.custom_vjp
def _tokens_sum(buf, tok, pos, valid):
    """The transpose of :func:`_rows_of`: ``buf`` (C, d) summed into its
    tokens (T, d), a token's at most k rows added in float32.  Rows no
    valid pair points at are never read into the sum.  The backward is
    ``g[tok]``."""
    picked = jnp.where(valid[..., None], buf[pos], 0)
    return jnp.sum(picked, axis=1, dtype=jnp.float32).astype(buf.dtype)


_rows_of.defvjp(
    lambda x, tok, pos, valid: (x[tok], (tok, pos, valid)),
    lambda res, g: (_tokens_sum(g, *res), None, None, None))
_tokens_sum.defvjp(
    lambda buf, tok, pos, valid: (_tokens_sum(buf, tok, pos, valid),
                                  (tok, pos, valid)),
    lambda res, g: (_rows_of(g, *res), None, None, None))


def swiglu_init(rng, d: int, hidden: int):
    """Parameters of a gated-SiLU feed-forward block, N(0, 1/fan_in)."""
    kg, ku, kd = jax.random.split(rng, 3)
    return {"w_gate": jax.random.normal(kg, (d, hidden)) * d ** -0.5,
            "w_up": jax.random.normal(ku, (d, hidden)) * d ** -0.5,
            "w_down": jax.random.normal(kd, (hidden, d)) * hidden ** -0.5}


def swiglu(x, p):
    """``W_down(silu(W_gate x) * W_up x)`` with ``p`` = {w_gate, w_up,
    w_down}: bf16-in / f32-accumulate under the compute policy, the gate in
    float32."""
    xc = cast_compute(x)
    g = jnp.matmul(xc, cast_compute(p["w_gate"]),
                   preferred_element_type=jnp.float32)
    u = jnp.matmul(xc, cast_compute(p["w_up"]),
                   preferred_element_type=jnp.float32)
    return jnp.matmul(cast_compute(jax.nn.silu(g) * u),
                      cast_compute(p["w_down"]),
                      preferred_element_type=jnp.float32).astype(x.dtype)


# rows of a grouped product's buffer come in multiples of this: the MXU's
# side on every TPU so far, and a whole number of sublane tiles at 4 bytes (8
# rows) and at 2 (16), so a (C, d) buffer is laid out without padding
_ROW_TILE = 128


def held_capacity(pairs: int, count: int, num_experts: int) -> int:
    """Rows of the buffers the held experts' part is computed in: twice
    what uniform routing sends to ``count`` of ``num_experts`` experts out
    of ``pairs`` (token, choice) pairs, rounded up to the row tile, and
    never more than ``pairs``, which a shard that holds every expert gets.
    A rule on the shapes: nothing sets it."""
    uniform2 = -(-2 * pairs * count // num_experts)
    return min(pairs, -(-uniform2 // _ROW_TILE) * _ROW_TILE)


def _sort_pairs(idx, held: Tuple[int, int]):
    """The T*k (token, choice) pairs by held expert, pairs of absent
    experts last.  Returns ``(order, inv, rows)``: ``order`` (T*k,) int32,
    the pair at each sorted place; ``inv`` its inverse, the place of each
    pair; ``rows`` (count,) int32, the pairs of each held expert."""
    first, count = held
    local = (idx >= first) & (idx < first + count)
    slot = jnp.where(local, idx - first, count).reshape(-1)
    order = jnp.argsort(slot, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(idx.size, dtype=jnp.int32))
    rows = jnp.sum(slot[:, None] == jnp.arange(count)[None, :], axis=0,
                   dtype=jnp.int32)
    return order, inv, rows


def _held_rows_apply(params, x, weights, order, inv, rows, cap: int):
    """``held_experts_apply`` after the sort, in buffers of ``cap`` rows:
    right whenever the held pairs (``sum(rows)``) are at most ``cap``.
    Returns ``(y, dropped)``."""
    t, k = weights.shape
    n_local = jnp.sum(rows)
    head = order[:cap]                      # the pairs the buffer's rows are
    tok = head // k                         # and their tokens
    pos = jnp.minimum(inv, cap - 1).reshape(t, k)
    valid = (inv < n_local).reshape(t, k)
    # rows past the held pairs belong to no group.  The grouped product
    # never writes them, forward or backward (on the TPU they hold whatever
    # the buffer held), so they are cut off on both sides: ``ys`` before it
    # reaches the sum, and ``xs`` so that its cotangent's tail is dropped
    # before it is summed into the tokens' gradient
    held_row = (jnp.arange(cap) < n_local)[:, None]
    xs = jnp.where(held_row,
                   _rows_of(cast_compute(x), tok, pos, valid), 0)

    def grouped(a, w):
        return jax.lax.ragged_dot(a, cast_compute(w), rows,
                                  preferred_element_type=jnp.float32)

    hid = jax.nn.silu(grouped(xs, params["w_gate"])) \
        * grouped(xs, params["w_up"])
    ys = grouped(cast_compute(hid), params["w_down"])      # (cap, d) float32
    ys = jnp.where(held_row, ys, 0.0)
    served = jnp.all(jnp.isfinite(ys), -1) & jnp.any(ys != 0, -1)
    dropped = n_local - jnp.sum(served, dtype=jnp.int32)
    w = _rows_of(weights.reshape(-1, 1), head, pos.reshape(-1, 1),
                 valid.reshape(-1, 1))
    y = _tokens_sum(ys * w, tok, pos, valid)
    return y.astype(x.dtype), dropped


def held_experts_apply(params, x, idx, weights, held: Tuple[int, int],
                       num_experts: int):
    """The held experts' part of a routed layer, dropping nothing.

    x: (T, d); idx, weights: (T, k) from the router, over ALL
    ``num_experts`` experts; ``held=(first, count)``: this shard holds
    experts ``first .. first + count - 1`` as ``params`` {w_gate (count, d,
    h), w_up (count, d, h), w_down (count, h, d)}.  Returns ``(y, rows,
    dropped, short)``: ``y`` (T, d) = sum over a token's chosen AND held
    experts of weight * Expert(x); ``rows`` (count,) int32, the rows each
    held expert was given; ``dropped`` int32, the held pairs whose row came
    back from the grouped products unserved (all zero, or not finite): read
    off the result, not off the sizes, so a product that skips rows shows
    here (a token whose input row is exactly zero would read as unserved
    too; a normed stream has none); ``short`` bool, whether the held pairs
    fitted the short buffers.

    The T*k (token, choice) pairs are sorted by held expert (pairs of
    absent experts last); the first C of them are the rows of the buffers,
    each row its token's input; each projection is ONE grouped matrix
    product (``jax.lax.ragged_dot``) whose group sizes are ``rows``: an
    expert computes exactly the rows routed to it and the tail of absent
    pairs is never multiplied; the rows, times their router weights, are
    summed into their tokens.  C is :func:`held_capacity`: twice the held
    experts' share under uniform routing.  A step that routes more than C
    pairs here (all T*k of them, if every token chooses held experts
    only) runs the same computation in buffers of T*k rows instead, behind
    a ``jax.lax.cond``, so no imbalance overflows anything; a shard that
    holds every expert has C = T*k and no conditional."""
    order, inv, rows = _sort_pairs(idx, held)
    cap = held_capacity(idx.size, held[1], num_experts)
    short = jnp.sum(rows) <= cap

    def path(size):
        return lambda p, x, w: _held_rows_apply(p, x, w, order, inv, rows,
                                                size)

    if cap == idx.size:
        y, dropped = path(cap)(params, x, weights)
    else:
        # each path keeps nothing for its backward but its inputs and
        # computes itself again there.  What a branch of a differentiated
        # ``cond`` keeps, the other has to hand over too, filled with zeros,
        # beside a copy of the weights: 2.2 GB of temporaries for 0.64 at
        # the Xing cell's shape, and a slower step (PERF.md §6, PR 33)
        y, dropped = jax.lax.cond(short, jax.checkpoint(path(cap)),
                                  jax.checkpoint(path(idx.size)),
                                  params, x, weights)
    return y, rows, dropped, short


class HeldMoE(Module):
    """Expert feed-forward layer of a sparse decoder, as ONE shard of an
    expert-parallel job sees it: ``y = Shared(x) + sum over chosen and held
    experts of w_e * Expert_e(x)``.

    ``num_experts`` is the router's width (all experts, wherever they
    live); ``held=(first, count)`` the experts whose weights this shard has
    (default: all).  Routing is :func:`route_sigmoid_topk`; the correction
    bias is model STATE (zero, not trained, not updated here: its update
    rule belongs to a training recipe).  Experts and the ``shared`` expert
    (width ``shared_hidden``, 0 = none) are gated SiLU without biases.

    Routing statistics ride the model state (``state["metrics"]``,
    :func:`bigdl_tpu.optim.metrics.bump_state_metrics`) out of the jitted
    step and are booked into the metric registry at the driver's log point:
    counters ``moe.routed_pairs`` (tokens x k), ``moe.local_pairs`` (those
    whose expert is held), ``moe.dropped_pairs`` (held pairs whose expert
    output came back all zero or not finite: must stay 0),
    ``moe.applies`` (one a forward pass) and ``moe.short_applies`` (those
    whose held pairs fitted the buffers of :func:`held_capacity` rows), and
    the histogram ``moe.load_imbalance`` (rows of the busiest held expert
    over the mean rows of a held expert)."""

    COUNTERS = ("moe.routed_pairs", "moe.local_pairs", "moe.dropped_pairs",
                "moe.applies", "moe.short_applies")
    MEANS = ("moe.load_imbalance",)

    def __init__(self, num_experts: int, hidden: int, k: int, *,
                 held: Optional[Tuple[int, int]] = None,
                 shared_hidden: int = 0, scale: float = 1.0,
                 norm_topk: bool = True, norm_eps: float = 1e-20,
                 name: Optional[str] = None):
        super().__init__(name)
        self.num_experts, self.hidden, self.k = num_experts, hidden, k
        self.held = tuple(held) if held is not None else (0, num_experts)
        if not (0 <= self.held[0]
                and self.held[0] + self.held[1] <= num_experts
                and self.held[1] >= 1):
            raise ValueError(f"held={held}: (first, count) inside "
                             f"[0, {num_experts})")
        self.shared_hidden = shared_hidden
        self.scale, self.norm_topk = scale, norm_topk
        self.norm_eps = norm_eps

    def build(self, rng, x):
        d, h, n = x.shape[-1], self.hidden, self.held[1]
        ks = jax.random.split(rng, 5)

        def w(key, shape, fan_in):
            return jax.random.normal(key, shape) * fan_in ** -0.5

        params = {"w_router": w(ks[0], (self.num_experts, d), d),
                  "experts": {"w_gate": w(ks[1], (n, d, h), d),
                              "w_up": w(ks[2], (n, d, h), d),
                              "w_down": w(ks[3], (n, h, d), h)}}
        if self.shared_hidden:
            params["shared"] = swiglu_init(ks[4], d, self.shared_hidden)
        state = {"router_bias": jnp.zeros((self.num_experts,)),
                 "metrics": new_state_metrics(self.COUNTERS, self.MEANS)}
        return params, state

    def forward(self, params, state, x, training=False, rng=None):
        shape = x.shape
        flat = x.reshape(-1, shape[-1])
        with jax.named_scope("moe/route"):
            idx, w = route_sigmoid_topk(
                flat, params["w_router"], state["router_bias"], self.k,
                self.scale, self.norm_topk, self.norm_eps)
        with jax.named_scope("moe/experts"):
            y, rows, dropped, short = held_experts_apply(
                params["experts"], flat, idx, w, self.held,
                self.num_experts)
        if self.shared_hidden:
            with jax.named_scope("moe/shared"):
                y = y + swiglu(flat, params["shared"])
        with jax.named_scope("moe/route"):
            n_local = jnp.sum(rows)
            mean_rows = jnp.maximum(n_local, 1) / self.held[1]
            metrics = bump_state_metrics(
                state["metrics"],
                {"moe.routed_pairs": idx.size, "moe.local_pairs": n_local,
                 "moe.dropped_pairs": dropped, "moe.applies": 1,
                 "moe.short_applies": short},
                {"moe.load_imbalance": jnp.max(rows) / mean_rows})
        return y.reshape(shape), {"router_bias": state["router_bias"],
                                  "metrics": metrics}
