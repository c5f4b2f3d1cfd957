"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): a residual path of ``n`` streams that
a sublayer reads as a per-token mixture and writes back into through a
per-token doubly stochastic matrix, in place of ``h + f(h)``.

Streams ``X`` are held ``(n, ..., d)``, STREAM INDEX LEADING, so that each
stream is a plain ``(tokens, d)`` slab: with the index beside ``d`` the
(8, 128) tiling of the last two dimensions would pad 4 streams to 8.  Around
one sublayer ``F``, with ``x~ = vec(X[t])`` (the token's ``n * d`` values):

- ``r = sqrt(mean(x~ ** 2) + eps)``, ``m = (phi x~) / r`` (``2n + n * n``);
- ``H_pre = sigmoid(a_pre * m[:n] + b[:n])``;
  ``H_post = 2 * sigmoid(a_post * m[n:2n] + b[n:2n])``;
- ``H_res``: ``exp(clip(a_res * m[2n:] + b[2n:], lo, hi))`` as an ``n x n``
  matrix, then ``sinkhorn_iters`` times: every row over its sum, every
  column over its sum (``eps`` added to each sum);
- ``u = sum_i H_pre[i] X[i]``; ``y = F(norm(u))``;
  ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``.

:class:`HyperConnection` holds one sublayer's ``phi`` (one ROW an output, as
``w_router`` is held), ``b`` and the three scalars ``alpha``, and gives the
two halves: :meth:`~HyperConnection.pre` → ``u`` and the coefficients,
:meth:`~HyperConnection.post` → ``X'``.  The statistic, the projection
(float32 operands at precision "high": three bfloat16 passes, 2e-5 of the
result; the router's top-k wants "highest", a sigmoid does not), the
sigmoids, ``exp`` and the Sinkhorn iterations are float32 whatever the
compute policy; the coefficients are kept ``(k, tokens)``, tokens along
the lanes, so that the 40 small matrices per token autodiff keeps cost what
they hold.  Device scopes ``hc/coeff``, ``hc/pre``, ``hc/post``.

**Two ways to differentiate the stream-wide products.**  The read-out
``u`` (:func:`read_out`) and the write-back ``X'`` (:func:`write_back`) are
plain ``jax.numpy``, and autodiff's backward of each per-token scalar
(``dH_res[i, j] = sum_d g[i] X[j]``: ``n * n + 2n`` of them a sublayer)
writes its ``(tokens, d)`` product to HBM before reducing it.  On a TPU,
where ``ops.hc_mix.can_mix`` says the shape is tiled, the same two
expressions run under a ``jax.custom_vjp`` whose backward is one Pallas
pass over token tiles (``ops/hc_mix.py``): the forward is the expression
itself, its residuals are its inputs, and no product slab is written.  The
read-out hands the streams THROUGH its custom VJP to the write-back
(``coeffs["streams"]``): the write-back's ``dX`` then arrives as the
cotangent of that pass-through and the read-out's one pass adds it to its
own, where two kernels' results would meet in an add of their own over
three slabs of ``n`` streams.  Which way runs is decided at trace time by
the backend and the shape, never by an option; elsewhere (and in the tests'
reference) autodiff differentiates the expressions.

What only the device sees leaves through the model state
(``obs/state_metrics.py``): :meth:`~HyperConnection.book` adds the largest
``|row sum - 1|`` or ``|column sum - 1|`` of ``H_res`` over the batch's
tokens after the last iteration to the fine mean
``hc.doubly_stochastic_err``, one to the counter ``hc.mixes`` and, where
the sublayer was traced with the one-pass backward, one to
``hc.fused_mixes``; the trace itself books
``kernel.hc_mix.traces{direction,impl}`` in the registry."""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Module
from bigdl_tpu.obs.state_metrics import (bump_state_metrics,
                                         new_state_metrics)
from bigdl_tpu.ops.common import on_tpu
from bigdl_tpu.ops.hc_mix import can_mix, mix_backward

ERR = "hc.doubly_stochastic_err"
MIXES, FUSED = "hc.mixes", "hc.fused_mixes"


def sinkhorn(m, iters: int, eps: float):
    """``m``: (n, n, ...) positive; ``iters`` times rows (axis 1 summed)
    then columns (axis 0 summed) over their sums."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def read_out(x32, h_pre):
    """``u = sum_i H_pre[i] X[i]``: ``x32`` (n, T, d), ``h_pre`` (n, T)."""
    return sum(h_pre[i][:, None] * x32[i] for i in range(x32.shape[0]))


def write_back(x32, y32, res, post):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``: ``x32`` (n, T, d),
    ``y32`` (T, d), ``res`` (n, n, T), ``post`` (n, T)."""
    n = x32.shape[0]
    return jnp.stack([
        sum(res[i, j][:, None] * x32[j] for j in range(n))
        + post[i][:, None] * y32 for i in range(n)])


def _book_trace(direction: str, fused: bool) -> None:
    """Trace-time bookkeeping, as the flash kernels book theirs."""
    from bigdl_tpu.optim.metrics import global_metrics

    global_metrics().inc("kernel.hc_mix.traces", labels={
        "direction": direction, "impl": "pallas" if fused else "autodiff"})


@jax.custom_vjp
def _read_out_fused(x32, h_pre):
    """:func:`read_out`, and the streams handed through (module
    docstring)."""
    return read_out(x32, h_pre), x32


def _read_out_fwd(x32, h_pre):
    return (read_out(x32, h_pre), x32), (x32, h_pre)


def _read_out_bwd(kept, cotangents):
    x32, h_pre = kept
    du, through = cotangents
    dx, _, dh = mix_backward(h_pre[None], du[None], x32, add=through)
    return dx, dh[0]


_read_out_fused.defvjp(_read_out_fwd, _read_out_bwd)


@jax.custom_vjp
def _write_back_fused(x32, y32, res, post):
    return write_back(x32, y32, res, post)


def _write_back_fwd(x32, y32, res, post):
    return write_back(x32, y32, res, post), (x32, y32, res, post)


def _write_back_bwd(kept, g):
    x32, y32, res, post = kept
    n = x32.shape[0]
    dx, dy, dc = mix_backward(
        jnp.concatenate([res, post[:, None]], axis=1), g, x32, y32)
    return dx, dy, dc[:, :n], dc[:, n]


_write_back_fused.defvjp(_write_back_fwd, _write_back_bwd)


class HyperConnection(Module):
    """The mixing around ONE sublayer of a block with ``streams`` residual
    streams of width ``hidden``."""

    def __init__(self, streams: int, hidden: int, *, sinkhorn_iters: int = 20,
                 eps: float = 1e-6, clamp: Tuple[float, float] = (-30.0, 30.0),
                 name: Optional[str] = None):
        super().__init__(name)
        if streams < 2:
            raise ValueError(f"streams={streams}: one stream is h + f(h)")
        self.streams, self.hidden = streams, hidden
        self.sinkhorn_iters, self.eps, self.clamp = sinkhorn_iters, eps, clamp

    def build(self, rng, x=None):
        """``phi`` N(0, 1 / (n d)): ``m`` has unit variance and the mixing
        is DYNAMIC at seeded weights (the papers start from ``phi`` = 0, a
        static mixing).  ``alpha`` (1, 1, 0.75); ``b`` zero but for the
        diagonal of the residual matrix, 0.5: ``H_res`` leans to the
        identity the papers start from, one Sinkhorn iteration leaves it 7%
        from where 20 bring it (so the iterations can be told to matter),
        and 20 leave row and column sums within 2e-4 of 1 (80,000 draws;
        with ``alpha_res`` 1 the widest of them is 1e-3 off, with a
        diagonal of 3, 3e-2: the nearer a permutation, the slower the
        iteration)."""
        n, width = self.streams, self.streams * self.hidden
        b = jnp.zeros((2 * n + n * n,)).at[2 * n:].set(
            0.5 * jnp.eye(n).reshape(-1))
        params = {"phi": jax.random.normal(rng, (2 * n + n * n, width))
                  * width ** -0.5,
                  "b": b, "alpha": jnp.asarray([1.0, 1.0, 0.75])}
        return params, {"metrics": new_state_metrics(
            counters=(MIXES, FUSED), fine=(ERR,))}

    def pre(self, params, X):
        """``X``: (n, ..., d) → ``u`` (..., d) in X's dtype, and the
        coefficients {pre (n, T), post (n, T), res (n, n, T), err ()} over
        the ``T`` flattened tokens; on the one-pass backward's path also
        ``streams``, ``X`` as :meth:`post` must read it (module
        docstring)."""
        n, d = self.streams, self.hidden
        flat = X.reshape(n, -1, d)
        with jax.named_scope("hc/coeff"):
            x32 = flat.astype(jnp.float32)
            phi = params["phi"].astype(jnp.float32).reshape(-1, n, d)
            r = jnp.sqrt(jnp.sum(x32 * x32, axis=(0, 2)) / (n * d) + self.eps)
            m = sum(jnp.einsum("od,td->ot", phi[:, i], x32[i],
                               precision=jax.lax.Precision.HIGH)
                    for i in range(n)) / r
            alpha = params["alpha"].astype(jnp.float32)
            b = params["b"].astype(jnp.float32)[:, None]
            h_pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n])
            h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + b[n:2 * n])
            a = jnp.clip(alpha[2] * m[2 * n:] + b[2 * n:], *self.clamp)
            h_res = sinkhorn(jnp.exp(a).reshape(n, n, -1),
                             self.sinkhorn_iters, self.eps)
            err = jnp.maximum(
                jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0)),
                jnp.max(jnp.abs(jnp.sum(h_res, axis=0) - 1.0)))
        coeffs = {"pre": h_pre, "post": h_post, "res": h_res,
                  "err": jax.lax.stop_gradient(err)}
        # a TPU, and a shape the tile rule takes: the one-pass backward
        fused = on_tpu() and can_mix(*x32.shape)
        _book_trace("pre", fused)
        with jax.named_scope("hc/pre"):
            if fused:
                u, coeffs["streams"] = _read_out_fused(x32, h_pre)
            else:
                u = read_out(x32, h_pre)
        return u.astype(X.dtype).reshape(X.shape[1:]), coeffs

    def post(self, X, y, coeffs):
        """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``, in X's dtype.
        ``coeffs`` as :meth:`pre` gave them for this ``X``."""
        n, d = self.streams, self.hidden
        fused = "streams" in coeffs
        _book_trace("post", fused)
        with jax.named_scope("hc/post"):
            y32 = y.reshape(-1, d).astype(jnp.float32)
            if fused:
                out = _write_back_fused(coeffs["streams"], y32,
                                        coeffs["res"], coeffs["post"])
            else:
                out = write_back(X.reshape(n, -1, d).astype(jnp.float32),
                                 y32, coeffs["res"], coeffs["post"])
        return out.astype(X.dtype).reshape(X.shape)

    @staticmethod
    def book(state, coeffs):
        """The sublayer's state after one forward pass."""
        return {"metrics": bump_state_metrics(
            state["metrics"], {MIXES: 1, FUSED: int("streams" in coeffs)},
            {ERR: coeffs["err"]})}
