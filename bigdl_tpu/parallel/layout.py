"""Declarative sharding layouts — canonical PartitionSpecs over a named
``(data, fsdp, tp, seq)`` mesh.

This is the SNIPPETS.md [2][3] pattern grown into a subsystem: instead of
every parallelism module plumbing its own mesh (``tp.py``/``pp.py``/
``ulysses.py``/``moe.py``) and ``gspmd.py`` keeping a private 2-axis regex
rule table, ONE frozen :class:`SpecLayout` names the mesh axes and ONE
:class:`ModelLayout` table per model family maps every parameter path to a
canonical spec.  ``jax.jit`` + ``NamedSharding`` then does GSPMD end to
end — the partitioner inserts the collectives, and the same layout object
drives training (``gspmd.GSPMDTrainStep``), serving
(``serving.InferenceModel``/``DecodeEngine``) and the analytic per-axis
collective-bytes ledger (:func:`collective_bytes_by_axis`).

Axis semantics (docs/parallelism.md §Declarative layouts):

- ``data``  — pure data parallelism: batch sharded, params replicated,
  gradients all-reduced.
- ``fsdp``  — data parallelism WITH cross-replica parameter sharding (the
  arXiv 2004.13336 weight-update-sharding recipe): the batch is sharded
  over it like ``data``, but parameters/opt-state are sharded too; the
  partitioner inserts the param all-gathers and gradient reduce-scatter.
- ``tp``    — Megatron tensor parallelism: column-split in-projections,
  row-split out-projections, activations all-reduced once per pair.
- ``seq``   — sequence dimension of activations/batches (long context).

A parameter that matches NO table rule (or whose matching rule is
rank-rejected) is replicated — VISIBLY: :meth:`ModelLayout.audit` exports
the ``parallel.layout.replicated_params`` gauge plus one flight/log line
listing the paths, so a layout that quietly replicates the biggest tensor
is diagnosable from a single scrape.
"""

import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.utils.log import get_logger

log = get_logger("bigdl_tpu.parallel.layout")

# canonical axis names of the layout mesh (mesh_policy builds it; every
# axis is always present — size-1 axes are free in XLA)
AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_TP = "tp"
AXIS_SEQ = "seq"
LAYOUT_AXES = (AXIS_DATA, AXIS_FSDP, AXIS_TP, AXIS_SEQ)

# flight-recorder / log lines cap the path listing at this many entries
_AUDIT_LIST_CAP = 32


def _ps(*dims) -> P:
    """Build a PartitionSpec from axis-name entries where any name may be
    None (axis absent from this layout): Nones inside tuples are dropped,
    single-name tuples collapse to the bare name, and empty entries
    become None — so a layout with ``fsdp=None`` degrades to exactly the
    legacy 2-axis specs (``P(None, "model")`` etc.), spec equality with
    the old rule table holds, and the rank guard keeps its meaning (a
    matrix rule's spec stays rank 2 even when one axis is absent)."""
    out = []
    for d in dims:
        if isinstance(d, tuple):
            names = tuple(n for n in d if n is not None)
            if not names:
                out.append(None)
            elif len(names) == 1:
                out.append(names[0])
            else:
                out.append(names)
        else:
            out.append(d)
    return P(*out)


@dataclass(frozen=True)
class SpecLayout:
    """Canonical PartitionSpecs over the named layout mesh.

    Fields are the mesh axis NAMES (``None`` = the layout has no such
    axis; its entries vanish from every spec).  Frozen: a layout is a
    value, shared by the train step, the serving path and the ledger."""

    data: Optional[str] = AXIS_DATA
    fsdp: Optional[str] = AXIS_FSDP
    tp: Optional[str] = AXIS_TP
    seq: Optional[str] = AXIS_SEQ

    # -- batch / activation specs ---------------------------------------
    def batch_axes(self) -> Tuple[str, ...]:
        """Axes the batch dimension shards over: every data-parallel axis
        (``data`` AND ``fsdp`` — fsdp is data parallelism with sharded
        weight updates, so it carries batch shards too)."""
        return tuple(a for a in (self.data, self.fsdp) if a is not None)

    def batch_spec(self, ndim: int = 2) -> P:
        """Input/target spec: dim 0 over the data axes; dim 1 over ``seq``
        for rank>=2 leaves (a pure layout hint under GSPMD — semantics are
        global, XLA inserts whatever halo/gather the model needs)."""
        if ndim >= 2:
            return _ps(self.batch_axes(), self.seq)
        return _ps(self.batch_axes())

    def activation_spec(self, ndim: int = 3) -> P:
        """Hidden activations: batch over the data axes, sequence over
        ``seq``, features unsharded (the tp all-reduce output form)."""
        if ndim >= 3:
            return _ps(self.batch_axes(), self.seq, None)
        return self.batch_spec(ndim)

    # -- parameter specs (the transformer-family vocabulary) ------------
    def vocab_embedding(self) -> P:
        """(vocab, d) embedding tables — usually the single biggest
        parameter: vocab rows sharded over fsdp x tp jointly."""
        return _ps((self.fsdp, self.tp), None)

    def hidden_in(self) -> P:
        """Column-parallel kernels (wq/wk/wv, ffn-up): outputs split over
        tp, input rows sharded over fsdp."""
        return _ps(self.fsdp, self.tp)

    def hidden_out(self) -> P:
        """Row-parallel kernels (wo, ffn-down): inputs split over tp (the
        pair's single activation all-reduce), output cols over fsdp."""
        return _ps(self.tp, self.fsdp)

    def tower_kernel(self) -> P:
        """Two-tower MLP kernels: pure column-parallel — output columns
        jointly split over tp x fsdp, contraction dim UNSHARDED.  The
        tower input is ``concat([id_emb, pooled_hist])``, and sharding
        the contraction dim of a dot whose operand is a concatenate
        miscompiles on the CPU SPMD partitioner this sim stack runs on
        (outputs off by O(1), verified against the replicated program);
        tower kernels are tiny next to the vocab tables, so keeping the
        contraction local costs nothing and sidesteps the fused
        concat-dot partition entirely."""
        return _ps(None, (self.tp, self.fsdp))

    def tower_bias(self) -> P:
        """Bias of a tower kernel rides the same joint column split."""
        return _ps((self.tp, self.fsdp))

    def col_bias(self) -> P:
        """Bias of a column-parallel kernel rides the tp split."""
        return _ps(self.tp)

    def row_bias(self) -> P:
        """Bias of a row-parallel kernel is replicated across tp (the
        psum output is full-width) but still weight-update-sharded."""
        return _ps(self.fsdp)

    def norm(self) -> P:
        """Norm scales/offsets: tiny, sharded over fsdp only (the 2004.
        13336 weight-update sharding), replicated across tp."""
        return _ps(self.fsdp)

    def replicated(self) -> P:
        return P()


# the legacy 2-axis (data x model) layout gspmd.py's regex table encoded:
# no fsdp, no seq, tp spelled "model" — tp_spec_for_path delegates here
LEGACY_SPEC_LAYOUT = SpecLayout(data="data", fsdp=None, tp="model",
                                seq=None)


@dataclass(frozen=True)
class LayoutRule:
    """One table row: parameter paths matching ``pattern`` get
    ``build(layout)``; a spec whose rank exceeds the leaf's is rejected
    and the search continues (the legacy rank guard, kept).  ``ndim``
    pins a rule to leaves of EXACTLY that rank — how the generic 2-D
    Linear rule and the 4-D conv rule share the ``weight$`` pattern
    without the first shadowing the second."""

    name: str
    pattern: str
    build: Callable[[SpecLayout], P]
    ndim: Optional[int] = None


def _r(name: str, pattern: str, build, ndim: Optional[int] = None
       ) -> LayoutRule:
    return LayoutRule(name, pattern, build, ndim)


# -- the transformer family (12L LM, the translation/seq2seq Transformer,
#    keras graphs built from TransformerLayer/MultiHeadAttention) --------
TRANSFORMER_RULES: Tuple[LayoutRule, ...] = (
    _r("vocab_embedding",
       r"(^|/)(embedding|emb/weight|lookuptable[^/]*/weight|"
       r"embedding[^/]*/weight)$",
       lambda l: l.vocab_embedding()),
    _r("attn_qkv", r"(^|/)(wq|wk|wv)$", lambda l: l.hidden_in()),
    _r("attn_qkv_bias", r"(^|/)(bq|bk|bv)$", lambda l: l.col_bias()),
    _r("attn_out", r"(^|/)wo$", lambda l: l.hidden_out()),
    _r("attn_out_bias", r"(^|/)bo$", lambda l: l.row_bias()),
    _r("ffn_up", r"(^|/)(w1|ffn/l1/weight)$", lambda l: l.hidden_in()),
    _r("ffn_up_bias", r"(^|/)(b1|ffn/l1/bias)$", lambda l: l.col_bias()),
    _r("ffn_down", r"(^|/)(w2|ffn/l2/weight)$", lambda l: l.hidden_out()),
    _r("ffn_down_bias", r"(^|/)(b2|ffn/l2/bias)$",
       lambda l: l.row_bias()),
    _r("norm",
       r"(^|/)(ln\d*|ln_out|ln_f|norm\d*|layernorm[^/]*|rmsnorm[^/]*)"
       r"/(weight|bias)$",
       lambda l: l.norm()),
)

# -- the two-tower recsys family (models.recsys.TwoTower) ----------------
TWO_TOWER_RULES: Tuple[LayoutRule, ...] = (
    _r("tower_embedding", r"(^|/)(user_emb|item_emb)$",
       lambda l: l.vocab_embedding()),
    _r("tower_kernel", r"(^|/)[ui]w\d+$", lambda l: l.tower_kernel()),
    _r("tower_bias", r"(^|/)[ui]b\d+$", lambda l: l.tower_bias()),
    _r("tower_out", r"(^|/)[ui]w_out$", lambda l: l.hidden_out()),
)

# -- generic fallbacks (MLPs, heads, converted models): appended after
#    every family table so plain Linear stacks still shard ---------------
GENERIC_RULES: Tuple[LayoutRule, ...] = (
    _r("linear_kernel", r"(^|/)weight$",
       lambda l: l.hidden_in(), ndim=2),               # (in, out) only
    _r("conv_kernel_cout", r"(^|/)weight$",
       lambda l: _ps(None, None, l.fsdp, l.tp),
       ndim=4),                                        # (kh, kw, cin, cout)
)

# paths DELIBERATELY replicated (tiny, or semantically unshardable):
# a leaf matching these is accounted "replicate-allowlist", never flagged
GENERIC_REPLICATE: Tuple[str, ...] = (
    r"(^|/)bias$",
    r"(^|/)(gamma|beta|scale|offset)$",
    r"(^|/)(running_mean|running_var|moving_mean|moving_var)$",
)


@dataclass
class LayoutAudit:
    """What the table did to one parameter tree — the visibility half of
    the layout (a silently replicated tensor is a perf bug, not an
    error)."""

    model: str
    sharded: Dict[str, Tuple] = field(default_factory=dict)
    allowlisted: List[str] = field(default_factory=list)
    # unmatched + rank-guard-rejected: the SILENT fallbacks made visible
    fallback_replicated: List[str] = field(default_factory=list)
    fallback_elems: int = 0

    def export(self, metrics=None) -> "LayoutAudit":
        """Gauge + one flight/log line for the fallback set.  The gauge
        (``parallel.layout.replicated_params``) is exported even at 0 so
        one scrape answers "is anything silently replicated?"."""
        if metrics is None:
            from bigdl_tpu.optim.metrics import global_metrics

            metrics = global_metrics()
        metrics.gauge("parallel.layout.replicated_params",
                      float(len(self.fallback_replicated)))
        if self.fallback_replicated:
            listed = self.fallback_replicated[:_AUDIT_LIST_CAP]
            extra = len(self.fallback_replicated) - len(listed)
            suffix = f" (+{extra} more)" if extra > 0 else ""
            from bigdl_tpu.obs import flight

            flight.record("layout_replicated_params", model=self.model,
                          count=len(self.fallback_replicated),
                          elems=int(self.fallback_elems),
                          paths=listed)
            log.warning(
                "layout %r replicates %d parameter(s) (%s elements) that "
                "matched no rule: %s%s — add a table rule or an explicit "
                "replicate-allowlist entry (docs/parallelism.md "
                "§Declarative layouts)", self.model,
                len(self.fallback_replicated), f"{self.fallback_elems:,}",
                ", ".join(listed), suffix)
        return self


class ModelLayout:
    """A per-model layout table: ordered rules + an explicit replicate
    allowlist, resolved against one :class:`SpecLayout`."""

    def __init__(self, spec_layout: SpecLayout,
                 rules: Sequence[LayoutRule] = TRANSFORMER_RULES,
                 replicate: Sequence[str] = GENERIC_REPLICATE,
                 name: str = "transformer"):
        self.spec_layout = spec_layout
        self.rules = tuple(rules)
        self.replicate = tuple(replicate)
        self.name = name

    def spec_for(self, path: str, ndim: int) -> Tuple[P, Optional[str]]:
        """(spec, kind) for one parameter path.  ``kind`` is the matching
        rule name, ``"replicate"`` for allowlisted paths, or ``None`` for
        the silent fallback (unmatched / every match rank-rejected)."""
        for rule in self.rules:
            if rule.ndim is not None and rule.ndim != ndim:
                continue
            if re.search(rule.pattern, path):
                s = rule.build(self.spec_layout)
                if len(s) <= ndim:
                    return s, rule.name
                # rank guard: keep searching (a 1-D param matching a
                # matrix rule may still match a later bias/norm rule)
        for pat in self.replicate:
            if re.search(pat, path):
                return P(), "replicate"
        return P(), None

    def param_specs(self, params) -> Any:
        """Pytree of PartitionSpecs matching ``params``."""
        import jax

        return jax.tree_util.tree_map_with_path(
            lambda p, x: self.spec_for(path_str(p), np.ndim(x))[0], params)

    def audit(self, params) -> LayoutAudit:
        """Classify every leaf; call ``.export()`` on the result to emit
        the gauge/flight/log visibility (satellites ride on this)."""
        import jax

        audit = LayoutAudit(model=self.name)

        def visit(p, leaf):
            path = path_str(p)
            spec, kind = self.spec_for(path, np.ndim(leaf))
            if kind is None:
                audit.fallback_replicated.append(path)
                audit.fallback_elems += int(np.prod(np.shape(leaf))) \
                    if np.ndim(leaf) else 1
            elif kind == "replicate" or not any(
                    a is not None for a in tuple(spec)):
                audit.allowlisted.append(path)
            else:
                audit.sharded[path] = (tuple(np.shape(leaf)), tuple(spec))
            return spec

        jax.tree_util.tree_map_with_path(visit, params)
        return audit


def path_str(path) -> str:
    """jax key-path -> the "enc0/attn/wq" strings the tables match."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def transformer_layout(spec_layout: SpecLayout) -> ModelLayout:
    """The transformer-family table: 12L LM, the translation (seq2seq)
    Transformer (enc/dec/cross attention share the same leaf names), and
    keras graphs built from the catalog attention blocks."""
    return ModelLayout(spec_layout,
                       rules=TRANSFORMER_RULES + GENERIC_RULES,
                       name="transformer")


def two_tower_layout(spec_layout: SpecLayout) -> ModelLayout:
    return ModelLayout(spec_layout,
                       rules=TWO_TOWER_RULES + TRANSFORMER_RULES
                       + GENERIC_RULES,
                       name="two_tower")


def generic_layout(spec_layout: SpecLayout) -> ModelLayout:
    return ModelLayout(spec_layout,
                       rules=TRANSFORMER_RULES + GENERIC_RULES,
                       name="generic")


# model class name -> table builder; register_layout extends it
_MODEL_TABLES: Dict[str, Callable[[SpecLayout], ModelLayout]] = {
    "Transformer": transformer_layout,
    "TransformerLayer": transformer_layout,
    "TransformerDecoderLayer": transformer_layout,
    "TwoTower": two_tower_layout,
    "NeuralCF": two_tower_layout,
}


def register_layout(model_cls_name: str,
                    table: Callable[[SpecLayout], ModelLayout]) -> None:
    """Register a layout-table builder for a new model family (docs/
    parallelism.md §Declarative layouts: "how to register a layout for a
    new model").  ``table(spec_layout) -> ModelLayout``."""
    _MODEL_TABLES[model_cls_name] = table


def layout_for_model(model, spec_layout: SpecLayout) -> ModelLayout:
    """Resolve the layout table for ``model``: its own class name first,
    then any registered family found among its sub-modules (a keras graph
    containing TransformerLayers picks the transformer table), else the
    generic table."""
    cls = type(model).__name__
    if cls in _MODEL_TABLES:
        return _MODEL_TABLES[cls](spec_layout)
    try:
        from bigdl_tpu.obs.cost import iter_modules

        for m in iter_modules(model):
            name = type(m).__name__
            if name in _MODEL_TABLES:
                return _MODEL_TABLES[name](spec_layout)
    except Exception:  # pragma: no cover — non-Module callables
        pass
    return generic_layout(spec_layout)


# ---------------------------------------------------------------------------
# the per-axis collective-bytes ledger (pure layout math, no devices)
# ---------------------------------------------------------------------------

def _spec_axes(spec) -> Tuple[str, ...]:
    names: List[str] = []
    for entry in tuple(spec):
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            if a is not None:
                names.append(a)
    return tuple(names)


def collective_bytes_by_axis(params, specs, mesh: Mesh,
                             dtype_bytes: int = 4) -> Dict[str, Any]:
    """Analytic per-step, per-axis collective bytes of a GSPMD layout.

    Conventions (per chip, ring collectives, documented in
    docs/parallelism.md §Declarative layouts):

    - ``data``: each parameter's gradient all-reduces over every
      data-parallel axis it is NOT sharded on — ~2x its LOCAL shard
      bytes (reduce-scatter + all-gather halves), counted once.
    - ``fsdp``: a parameter sharded on fsdp is all-gathered for the
      forward AND the backward and its gradient reduce-scattered — 3
      ring passes of ``elems * (n-1)/n`` bytes (2004.13336 recipe).
    - ``tp``: moves ACTIVATIONS, not parameters — estimate it with
      :func:`tp_activation_bytes` from the model geometry; the param-side
      entry here is 0 by construction.

    Also reports ``param_bytes_per_chip`` (params + same-spec'd Adam-style
    opt state would double it) — the "fits on one chip?" number the fsdp x
    tp layout exists to shrink."""
    import jax

    axes = dict(mesh.shape)
    data_axes = [a for a in (AXIS_DATA, "dcn_data") if axes.get(a, 1) > 1]
    fsdp_axis = AXIS_FSDP if axes.get(AXIS_FSDP, 1) > 1 else None
    per_axis = {a: 0.0 for a in LAYOUT_AXES}
    total_elems = 0.0
    shard_elems_total = 0.0

    def visit(leaf, spec):
        nonlocal total_elems, shard_elems_total
        elems = float(np.prod(np.shape(leaf))) if np.ndim(leaf) else 1.0
        names = _spec_axes(spec)
        div = 1.0
        for a in names:
            div *= axes.get(a, 1)
        shard = elems / max(div, 1.0)
        total_elems += elems
        shard_elems_total += shard
        # gradient allreduce over the data axes the param is replicated on
        n_rep = 1
        for a in data_axes + ([fsdp_axis] if fsdp_axis else []):
            if a not in names:
                n_rep *= axes.get(a, 1)
        if n_rep > 1:
            per_axis[AXIS_DATA] += 2.0 * shard * dtype_bytes
        # fsdp-sharded params: fwd gather + bwd gather + grad scatter
        if fsdp_axis and fsdp_axis in names:
            nf = axes[fsdp_axis]
            per_axis[AXIS_FSDP] += 3.0 * elems * (nf - 1) / nf \
                * dtype_bytes

    jax.tree_util.tree_map(visit, params, specs,
                           is_leaf=lambda x: isinstance(x, P))
    return {
        "per_axis_bytes_per_step": {a: per_axis[a] for a in LAYOUT_AXES},
        "param_elems": total_elems,
        "param_bytes_per_chip": shard_elems_total * dtype_bytes,
        "total_bytes_per_step": float(sum(per_axis.values())),
        "mesh": {k: int(v) for k, v in axes.items()},
    }


def tp_activation_bytes(batch: int, seq: int, d_model: int,
                        n_row_collectives: int, tp: int,
                        dtype_bytes: int = 4) -> float:
    """Analytic tp-axis traffic: each row-parallel matmul's output
    all-reduce moves ~2x(tp-1)/tp of the (batch, seq, d_model) activation
    per chip; x3 for fwd + the backward's two collectives (the standard
    Megatron accounting).  ``n_row_collectives`` = row-parallel matmuls
    per step (2 per transformer layer: attention out + ffn down)."""
    if tp <= 1:
        return 0.0
    one = 2.0 * (tp - 1) / tp * batch * seq * d_model * dtype_bytes
    return 3.0 * n_row_collectives * one


def embedding_lookup_bytes(batch: int, dim: int, sizes: Dict[str, int],
                           n_tables: int = 1,
                           dtype_bytes: int = 4) -> Dict[str, Any]:
    """Analytic per-axis traffic of sparse embedding lookups against a
    vocab-sharded table (the ``vocab_embedding`` spec: rows sharded over
    fsdp x tp).  A gather of ``batch`` rows of width ``dim`` produces
    local partial rows (a chip owns only the ids that hash to its shard);
    serving them whole costs one ring all-gather of the gathered block
    over each vocab-shard axis — ``(n-1)/n`` of ``batch x dim`` per chip,
    the inference-side analog of the weight-update-sharding accounting in
    :func:`collective_bytes_by_axis`.  An unsharded mesh prices to zero,
    keeping the ledger honest for the single-chip baseline."""
    per_axis: Dict[str, float] = {}
    block = float(batch) * float(dim) * float(dtype_bytes) * \
        float(n_tables)
    for axis in (AXIS_FSDP, AXIS_TP):
        n = int(sizes.get(axis, 1) or 1)
        per_axis[axis] = block * (n - 1) / n if n > 1 else 0.0
    return {
        "per_axis_bytes": per_axis,
        "total_bytes": float(sum(per_axis.values())),
        "rows": int(batch),
        "dim": int(dim),
    }
