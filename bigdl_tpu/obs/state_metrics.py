"""Counters that a layer keeps INSIDE the jitted train step and the driver
books into the metric registry at its log point.

A layer that wants to report what only the device sees (how a router spread
its tokens, say) cannot call ``Metrics.inc`` from traced code.  It keeps a
small subtree in its model state instead::

    state["metrics"] = new_state_metrics(counters=("moe.local_pairs",),
                                         means=("moe.load_imbalance",))
    ...
    new_state["metrics"] = bump_state_metrics(
        state["metrics"], {"moe.local_pairs": n}, {"moe.load_imbalance": r})

Every leaf is a cumulative ``uint32`` that wraps around: a counter adds its
events; a mean adds its value in 16.16 fixed point and ``n`` counts the
additions; a ``fine`` mean is a mean in 8.24 fixed point, for a quantity
far under 1 (a residual of 1e-6 reads 0 in 16.16).  The train step sums
each replica's additions over the data axes
(``optim/train_step.py``: unsigned leaves are event counters), so the
totals are the job's, and each bundle of steps hands a copy of the subtrees
back beside its losses (:func:`subtrees`: the state itself is donated to
the next bundle).  On the host a :class:`StateMetricsBooker` rides the one
``device_get`` the driver's log point makes anyway, takes the difference
from the last fetch modulo 2**32 (exact as long as fewer than
4.29e9 events, or a summed mean under 65,536, or a summed fine mean under
256, fall between two log points) and books it: ``inc(name, delta)`` for a
counter, one ``observe(name, delta_sum / delta_n)`` per subtree for a
mean.  The one convention: a state
dict with the key ``"metrics"`` built by :func:`new_state_metrics`.
"""

from typing import Dict, Iterable

KEY = "metrics"
# fixed-point scale of each kind of mean
_SCALES = {"means": 2.0 ** 16, "fine": 2.0 ** 24}


def new_state_metrics(counters: Iterable[str] = (),
                      means: Iterable[str] = (), fine: Iterable[str] = ()):
    """``fine`` names means kept in 8.24 fixed point; a subtree without any
    has no ``"fine"`` key (the layers that have none keep their tree)."""
    import jax.numpy as jnp

    zero = lambda: jnp.zeros((), jnp.uint32)
    tree = {"counters": {c: zero() for c in counters},
            "means": {m: zero() for m in means}, "n": zero()}
    if fine := tuple(fine):
        tree["fine"] = {m: zero() for m in fine}
    return tree


def bump_state_metrics(tree, counters: Dict[str, object],
                       means: Dict[str, object]):
    """The subtree after one forward pass: each counter plus its events,
    each mean (``fine`` ones too, by name) plus its value in its fixed
    point, ``n`` plus one."""
    import jax.numpy as jnp

    u32 = lambda v: jnp.asarray(v).astype(jnp.uint32)
    new = {"counters": {k: v + u32(counters[k])
                        for k, v in tree["counters"].items()},
           "n": tree["n"] + jnp.uint32(1)}
    for kind, scale in _SCALES.items():
        if kind in tree:
            new[kind] = {
                k: v + u32(jnp.round(jnp.asarray(means[k], jnp.float32)
                                     * scale))
                for k, v in tree[kind].items()}
    return new


def subtrees(state, path=()) -> Dict[tuple, dict]:
    """The ``"metrics"`` subtrees of a model state by their path in it
    (``{}``, and nothing to fetch, for a model that keeps none)."""
    if not isinstance(state, dict):
        return {}
    found = {}
    for k, v in state.items():
        if k == KEY and isinstance(v, dict) and "counters" in v:
            found[path] = v
        else:
            found.update(subtrees(v, path + (k,)))
    return found


class StateMetricsBooker:
    """Host side: books the differences between one fetch of a model
    state's ``"metrics"`` subtrees and the next."""

    def __init__(self, model_state, metrics):
        self.metrics = metrics
        self.rebase(model_state)
        # a counter exists from the start, at 0: a reader tells "never
        # happened" (0) from "not instrumented" (absent)
        for tree in self._last.values():
            for name in tree["counters"]:
                metrics.inc(name, 0)

    def rebase(self, model_state) -> None:
        """Count from this state's values on (the start of a run, or the
        state a resume restored)."""
        import jax

        self._last = jax.device_get(subtrees(model_state))

    def book(self, fetched) -> None:
        delta = lambda new, old: (int(new) - int(old)) % 2 ** 32
        for path, tree in fetched.items():
            last, self._last[path] = self._last[path], tree
            for name, v in tree["counters"].items():
                self.metrics.inc(name, delta(v, last["counters"][name]))
            n = delta(tree["n"], last["n"])
            for kind, scale in _SCALES.items():
                for name, v in tree.get(kind, {}).items():
                    if n:
                        self.metrics.observe(
                            name, delta(v, last[kind][name]) / scale / n)
