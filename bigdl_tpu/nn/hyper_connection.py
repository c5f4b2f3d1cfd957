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

What only the device sees leaves through the model state
(``obs/state_metrics.py``): :meth:`~HyperConnection.book` adds the largest
``|row sum - 1|`` or ``|column sum - 1|`` of ``H_res`` over the batch's
tokens after the last iteration to the fine mean
``hc.doubly_stochastic_err``."""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Module
from bigdl_tpu.obs.state_metrics import (bump_state_metrics,
                                         new_state_metrics)

ERR = "hc.doubly_stochastic_err"


def sinkhorn(m, iters: int, eps: float):
    """``m``: (n, n, ...) positive; ``iters`` times rows (axis 1 summed)
    then columns (axis 0 summed) over their sums."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


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
        return params, {"metrics": new_state_metrics(fine=(ERR,))}

    def pre(self, params, X):
        """``X``: (n, ..., d) → ``u`` (..., d) in X's dtype, and the
        coefficients {pre (n, T), post (n, T), res (n, n, T), err ()} over
        the ``T`` flattened tokens."""
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
        with jax.named_scope("hc/pre"):
            u = sum(h_pre[i][:, None] * x32[i] for i in range(n))
        return (u.astype(X.dtype).reshape(X.shape[1:]),
                {"pre": h_pre, "post": h_post, "res": h_res,
                 "err": jax.lax.stop_gradient(err)})

    def post(self, X, y, coeffs):
        """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``, in X's dtype."""
        n, d = self.streams, self.hidden
        with jax.named_scope("hc/post"):
            x32 = X.reshape(n, -1, d).astype(jnp.float32)
            y32 = y.reshape(-1, d).astype(jnp.float32)
            res, post = coeffs["res"], coeffs["post"]
            out = jnp.stack([
                sum(res[i, j][:, None] * x32[j] for j in range(n))
                + post[i][:, None] * y32 for i in range(n)])
        return out.astype(X.dtype).reshape(X.shape)

    @staticmethod
    def book(state, coeffs):
        """The sublayer's state after one forward pass."""
        return {"metrics": bump_state_metrics(
            state["metrics"], {}, {ERR: coeffs["err"]})}
