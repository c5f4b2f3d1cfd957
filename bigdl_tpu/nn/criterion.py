"""Loss functions (criterions).

Reference analog (unverified — mount empty): ``dllib/nn/*Criterion.scala`` —
``AbstractCriterion`` contract ``forward(input, target) -> loss`` +
hand-written ``backward``.  Here: pure scalar functions of (input, target);
gradient via ``jax.grad``.  ``size_average`` (reference default) = mean
reduction.

Label convention: integer class labels are **0-based** (reference is 1-based
Torch convention — documented divergence; the data pipeline keeps labels
0-based end to end).
"""

from typing import Optional

import jax
import jax.numpy as jnp


class Criterion:
    def forward(self, input, target):
        raise NotImplementedError

    def __call__(self, input, target=None):
        # target=None supported for target-free criterions (L1Cost, KLD, ...)
        return self.forward(input, target)


def _reduce(x, size_average: bool):
    return jnp.mean(x) if size_average else jnp.sum(x)


def _as_onehot(target, n_classes):
    if target.ndim >= 1 and target.shape[-1] == n_classes and jnp.issubdtype(
            target.dtype, jnp.floating):
        return target
    return jax.nn.one_hot(target.astype(jnp.int32), n_classes)


class ClassNLLCriterion(Criterion):
    """Negative log-likelihood over **log-probabilities** (pair with
    LogSoftMax) — reference ``nn/ClassNLLCriterion.scala``."""

    def __init__(self, size_average: bool = True, weights: Optional[jnp.ndarray] = None):
        self.size_average = size_average
        self.weights = weights

    def forward(self, input, target):
        tgt = target.astype(jnp.int32).reshape(input.shape[:-1])
        picked = jnp.take_along_axis(input, tgt[..., None], axis=-1)[..., 0]
        if self.weights is not None:
            w = jnp.take(self.weights, tgt)
            return -jnp.sum(picked * w) / (jnp.sum(w) if self.size_average else 1.0)
        return -_reduce(picked, self.size_average)


class CrossEntropyCriterion(Criterion):
    """Softmax cross-entropy over **logits** — reference
    ``nn/CrossEntropyCriterion.scala`` (= LogSoftMax + ClassNLL fused).
    Accepts integer labels or one-hot/soft targets."""

    def __init__(self, size_average: bool = True):
        self.size_average = size_average

    def forward(self, input, target):
        if target.ndim == input.ndim - 1:
            # integer labels: the label's logit is picked by a comparison
            # that fuses into the reduction, so no (..., classes) one-hot
            # and no second log-softmax copy exist beside the logits (a
            # language model's are 8,192 x 19,360 floats a step)
            lse = jax.nn.logsumexp(input, axis=-1)
            classes = jax.lax.broadcasted_iota(jnp.int32, input.shape,
                                               input.ndim - 1)
            picked = jnp.sum(jnp.where(
                classes == target.astype(jnp.int32)[..., None], input, 0.0),
                axis=-1)
            return _reduce(lse - picked, self.size_average)
        logp = jax.nn.log_softmax(input, axis=-1)
        onehot = _as_onehot(target, input.shape[-1])
        return -_reduce(jnp.sum(onehot * logp, axis=-1), self.size_average)


class MSECriterion(Criterion):
    """Reference ``nn/MSECriterion.scala``."""

    def __init__(self, size_average: bool = True):
        self.size_average = size_average

    def forward(self, input, target):
        return _reduce((input - target) ** 2, self.size_average)


class AbsCriterion(Criterion):
    """L1 — reference ``nn/AbsCriterion.scala``."""

    def __init__(self, size_average: bool = True):
        self.size_average = size_average

    def forward(self, input, target):
        return _reduce(jnp.abs(input - target), self.size_average)


class SmoothL1Criterion(Criterion):
    """Huber with delta=1 — reference ``nn/SmoothL1Criterion.scala``."""

    def __init__(self, size_average: bool = True):
        self.size_average = size_average

    def forward(self, input, target):
        d = jnp.abs(input - target)
        loss = jnp.where(d < 1.0, 0.5 * d * d, d - 0.5)
        return _reduce(loss, self.size_average)


class BCECriterion(Criterion):
    """Binary cross-entropy over probabilities — reference
    ``nn/BCECriterion.scala``."""

    def __init__(self, size_average: bool = True, eps: float = 1e-12):
        self.size_average = size_average
        self.eps = eps

    def forward(self, input, target):
        p = jnp.clip(input, self.eps, 1.0 - self.eps)
        loss = -(target * jnp.log(p) + (1.0 - target) * jnp.log1p(-p))
        return _reduce(loss, self.size_average)


class BCEWithLogitsCriterion(Criterion):
    def __init__(self, size_average: bool = True):
        self.size_average = size_average

    def forward(self, input, target):
        loss = jnp.maximum(input, 0) - input * target + jnp.log1p(
            jnp.exp(-jnp.abs(input)))
        return _reduce(loss, self.size_average)


class KLDivCriterion(Criterion):
    """KL divergence, input = log-probs — reference ``nn/DistKLDivCriterion``."""

    def __init__(self, size_average: bool = True):
        self.size_average = size_average

    def forward(self, input, target):
        safe = jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-30))
                                               - input), 0.0)
        return _reduce(safe, self.size_average)


class CosineEmbeddingCriterion(Criterion):
    """Reference ``nn/CosineEmbeddingCriterion.scala`` — input (x1, x2),
    target ±1."""

    def __init__(self, margin: float = 0.0, size_average: bool = True):
        self.margin = margin
        self.size_average = size_average

    def forward(self, input, target):
        x1, x2 = input
        cos = jnp.sum(x1 * x2, -1) / (
            jnp.linalg.norm(x1, axis=-1) * jnp.linalg.norm(x2, axis=-1) + 1e-12)
        loss = jnp.where(target > 0, 1.0 - cos,
                         jnp.maximum(0.0, cos - self.margin))
        return _reduce(loss, self.size_average)


class MarginRankingCriterion(Criterion):
    def __init__(self, margin: float = 1.0, size_average: bool = True):
        self.margin = margin
        self.size_average = size_average

    def forward(self, input, target):
        x1, x2 = input
        return _reduce(jnp.maximum(0.0, -target * (x1 - x2) + self.margin),
                       self.size_average)


class ParallelCriterion(Criterion):
    """Weighted sum of criterions over tuple inputs/targets — reference
    ``nn/ParallelCriterion.scala``."""

    def __init__(self, *pairs):
        # pairs: (criterion, weight)
        self.pairs = [(c, w) for c, w in pairs]

    def forward(self, input, target):
        total = 0.0
        for i, (c, w) in enumerate(self.pairs):
            total = total + w * c(input[i], target[i])
        return total


class TimeDistributedCriterion(Criterion):
    """Apply a criterion per time step — reference
    ``nn/TimeDistributedCriterion.scala``.  With mean reductions the wrapped
    criterion already averages over the time axis; this exists for API parity
    and for ``size_average=False`` per-step sums."""

    def __init__(self, criterion: Criterion, size_average: bool = True):
        self.criterion = criterion
        self.size_average = size_average

    def forward(self, input, target):
        loss = self.criterion(input, target)
        if not self.size_average:
            loss = loss * input.shape[1]
        return loss
