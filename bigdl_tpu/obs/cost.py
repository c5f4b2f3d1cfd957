"""Analytic FLOPs/bytes cost model + device peak table — the MFU denominator.

Reference analog (unverified — mount empty): the reference reports only
records/s; BigDL 2.0 (arXiv 2204.01715) leaves utilization to offline
TensorBoard summaries.  Here the cost of a model is derived ONCE per run
from the model itself — a shape-capturing walk over the ``nn/`` module tree
under ``jax.eval_shape`` (no compute, no compile) with per-layer FLOP
formulas — so a *running* job can export a live ``train.mfu`` gauge instead
of waiting for an offline measurement.

Conventions (those of ``benchmark/flops.py``, so the live gauge and the
benchmark's ``train.mfu`` agree; ``tests/test_perf_attr.py`` holds the
two ResNet-50 counts within 5%):

- forward FLOPs are *model* flops (2 x MACs for matmul-family layers;
  elementwise layers count one pass over their output), as per-layer
  counts over arbitrary module trees.
- training FLOPs = ``TRAIN_FLOPS_MULTIPLIER`` (3) x forward (fwd +
  input-grad + weight-grad).
- MFU = achieved FLOP/s per chip / the chip's bf16 peak
  (``peak_flops``): an exact ``device_kind`` table.  A TPU that is not in
  it is an error; a non-TPU backend (CPU test meshes) has no peak and
  exports no MFU gauge.
"""

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# fwd + input-grad + weight-grad — the standard training-FLOPs convention
TRAIN_FLOPS_MULTIPLIER = 3.0

# bf16 matmul peak FLOP/s per chip, keyed by the EXACT jax
# ``Device.device_kind`` (the spellings jax's own
# ``_src/pallas/mosaic/tpu_info.py`` matches on).  Values: Google Cloud TPU
# documentation, system-architecture page of each generation ("TPU v5e":
# 197 TFLOP/s bf16 per chip).
PEAK_BF16_FLOPS: Dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e as libtpu reports it
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e (Trillium)
    "TPU v6e": 918e12,
}


def peak_flops(device_kind: str) -> Optional[float]:
    """Peak bf16 FLOP/s for one chip of exactly this ``device_kind``.
    A TPU kind missing from the table raises — a guessed denominator is a
    wrong MFU; add the kind with its source.  Non-TPU kinds (CPU test
    meshes) have no peak: ``None``, and the caller exports no gauge."""
    peak = PEAK_BF16_FLOPS.get(device_kind)
    if peak is None and str(device_kind).upper().startswith("TPU"):
        raise ValueError(
            f"no bf16 peak on record for device_kind {device_kind!r}; add "
            "it to obs.cost.PEAK_BF16_FLOPS with its source")
    return peak


# ---------------------------------------------------------------------------
# per-layer shape capture + FLOP formulas
# ---------------------------------------------------------------------------

@dataclass
class LayerCost:
    """One module's forward cost from its observed shapes."""

    name: str
    kind: str
    flops: float          # forward model-flops (2 x MACs for matmul family)
    param_bytes: int
    out_elems: int
    # effective (executed) flops — differs from ``flops`` only for sparse
    # layers, where ``flops`` stays the DENSE-EQUIVALENT count and this
    # counts only the nonzero-block work the chip actually does
    eff_flops: float = -1.0

    def __post_init__(self):
        if self.eff_flops < 0:
            self.eff_flops = self.flops


@dataclass
class CostReport:
    """Forward-pass cost of one model on one batch shape."""

    layers: List[LayerCost] = field(default_factory=list)
    batch: int = 0

    @property
    def flops(self) -> float:
        """Total forward model-flops for the traced batch
        (dense-equivalent: sparsity does NOT shrink this number)."""
        return float(sum(l.flops for l in self.layers))

    @property
    def eff_flops(self) -> float:
        """Executed forward flops: nonzero-block work only.  Equal to
        ``flops`` for dense models; under block sparsity this is the
        honest MFU numerator (``flops`` would inflate it)."""
        return float(sum(l.eff_flops for l in self.layers))

    @property
    def param_bytes(self) -> int:
        return int(sum(l.param_bytes for l in self.layers))

    def train_flops(self) -> float:
        return TRAIN_FLOPS_MULTIPLIER * self.flops

    def train_eff_flops(self) -> float:
        """Executed training flops: per layer, forward and the input
        gradient run at EFFECTIVE cost (the block-sparse kernel skips
        pruned blocks in both) but the weight gradient is a dense matmul
        masked on the way out (``ops.block_sparse._bsmm_bwd``) — so the
        honest count is ``2·eff + 1·dense`` per layer, which collapses to
        the standard 3x for dense layers (eff == flops)."""
        return float(sum(2.0 * l.eff_flops + l.flops for l in self.layers))

    def per_sample_flops(self) -> float:
        return self.flops / max(self.batch, 1)


def iter_modules(module, seen=None):
    """Walk a module tree (containers, attribute children, lists)."""
    from bigdl_tpu.nn.module import Module

    if seen is None:
        seen = set()
    if id(module) in seen:
        return
    seen.add(id(module))
    yield module
    for v in vars(module).values():
        children = v if isinstance(v, (list, tuple)) else [v]
        for c in children:
            if isinstance(c, Module):
                yield from iter_modules(c, seen)


def _shape(a) -> Optional[Tuple[int, ...]]:
    s = getattr(a, "shape", None)
    if s is None:
        return None
    try:
        return tuple(int(d) for d in s)
    except TypeError:
        return None


def _elems(shape: Optional[Tuple[int, ...]]) -> int:
    if not shape:
        return 0
    return int(np.prod(shape))


def _out_shapes(y) -> List[Tuple[int, ...]]:
    if isinstance(y, (tuple, list)):
        return [s for s in (_shape(a) for a in y) if s is not None]
    s = _shape(y)
    return [s] if s is not None else []


# layers whose cost is one cheap pass over the output (normalization,
# activations, pooling, padding/reshape/dropout); counted as 2 flops/elem
# so they appear in the table without pretending to be matmuls
_ELEMENTWISE_KINDS = frozenset({
    "BatchNorm", "_BN", "LayerNorm", "RMSNorm", "GroupNorm", "ReLU",
    "ReLU6", "GELU", "SiLU", "Sigmoid", "Tanh", "SoftMax", "LogSoftMax",
    "LeakyReLU", "ELU", "HardTanh", "PReLU", "SoftPlus", "SoftSign",
    "Dropout", "MaxPool2D", "AvgPool2D", "MaxPool1D", "AvgPool1D",
    "MaxPool3D", "AvgPool3D", "GlobalAvgPool2D", "GlobalMaxPool2D",
    "GlobalAvgPool1D", "GlobalMaxPool1D", "CAddTable", "CMulTable",
    "Scale", "Power", "Abs", "Clamp", "Sqrt", "Square",
})


def _attention_flops(mod, in_shapes, out_shapes, params) -> float:
    """MultiHeadAttention: q/k/v/out projections + the two attention
    matmuls (qk^T and att@v), 2 flops per MAC."""
    x = in_shapes[0]
    if x is None or len(x) < 3:
        return 0.0
    b, t = x[0], x[1]
    proj = 0.0
    for key in ("wq", "wk", "wv", "wo"):
        w = _shape(params.get(key)) if isinstance(params, dict) else None
        if w is not None:
            proj += 2.0 * b * t * _elems(w)
    h = getattr(mod, "hidden_size", None) or (x[-1] if x else 0)
    # qk^T: b*heads*t*t*head_dim MACs; att@v the same => 4*b*t^2*h flops
    attn = 4.0 * b * t * t * h
    return proj + attn


def _layer_flops(mod, in_shapes, out_shapes, params) -> float:
    kind = type(mod).__name__
    out_e = sum(_elems(s) for s in out_shapes)
    if kind == "MultiHeadAttention":
        return _attention_flops(mod, in_shapes, out_shapes, params)
    if kind == "Embedding":
        return 0.0  # gather, no MACs
    if kind == "DepthwiseConv2D":
        w = _shape(params.get("weight")) if isinstance(params, dict) \
            else None
        if w is not None and len(w) >= 2:
            return 2.0 * out_e * w[0] * w[1]
        return 0.0
    if kind in _ELEMENTWISE_KINDS:
        return 2.0 * out_e
    # matmul family (Linear, Conv1/2/3D, SeparableConv2D pointwise,
    # custom conv-like modules e.g. SpaceToDepthStem): every output
    # element is a dot product over the weight's non-output dims —
    # 2 * out_elems * prod(weight.shape[:-1]) covers (in, out) linears and
    # (kh, kw, cin/groups, cout) convs with one formula
    w = _shape(params.get("weight")) if isinstance(params, dict) else None
    if w is not None and len(w) >= 2 and out_shapes \
            and out_shapes[0] and out_shapes[0][-1] == w[-1]:
        return 2.0 * out_e * _elems(w[:-1])
    # containers / reshapes / unknown glue: children are recorded
    # separately, so counting 0 here avoids double counting
    return 0.0


def _param_bytes(params) -> int:
    if not isinstance(params, dict):
        return 0
    total = 0
    for v in params.values():
        s = _shape(v)
        if s is not None:
            itemsize = getattr(getattr(v, "dtype", None), "itemsize", 4)
            total += _elems(s) * itemsize
        elif isinstance(v, dict):
            # a nested dict is a CHILD module's params — skip just that
            # entry (the child reports its own); the module's direct
            # arrays still count
            continue
    return total


def forward_costs(model, variables: Dict[str, Any], *sample_inputs,
                  training: bool = False) -> CostReport:
    """Per-layer forward cost of ``model`` on ``sample_inputs`` shapes.

    The forward runs under ``jax.eval_shape`` — pure shape propagation, no
    FLOP is executed and nothing compiles — with every module instance's
    ``forward`` wrapped to record its input/output shapes.  Leaf formulas
    turn shapes into FLOPs; container/unknown modules count 0 (their
    children are recorded separately), so the sum never double counts."""
    import jax

    records: List[Tuple[Any, list, list, Any]] = []
    patched: List[Any] = []

    def _wrap(mod, orig):
        def fwd(params, state, *xs, **kw):
            y, st = orig(params, state, *xs, **kw)
            records.append((mod, [_shape(a) for a in xs], _out_shapes(y),
                            params))
            return y, st

        return fwd

    try:
        for m in iter_modules(model):
            _wrap_fn = _wrap(m, m.forward)
            m.forward = _wrap_fn  # instance attr shadows the class method
            patched.append(m)
        jax.eval_shape(
            lambda v, xs: model.apply(v, *xs, training=training),
            variables, tuple(sample_inputs))
    finally:
        for m in patched:
            try:
                del m.__dict__["forward"]
            except KeyError:
                pass

    report = CostReport()
    first = _shape(sample_inputs[0]) if sample_inputs else None
    report.batch = first[0] if first else 1
    for mod, ins, outs, params in records:
        flops = _layer_flops(mod, ins, outs, params)
        out_e = sum(_elems(s) for s in outs)
        # block-sparse layers: ``flops`` stays dense-equivalent (the
        # matmul-family formula above); the EFFECTIVE count scales by the
        # mask's nonzero-block density — so train.mfu vs
        # train.effective_mfu make sparsity's utilization cost visible
        # instead of silently inflating one number
        eff = flops
        if type(mod).__name__ == "BlockSparseLinear":
            try:
                eff = flops * float(mod.density())
            except Exception:  # pragma: no cover — unbuilt module
                pass
        report.layers.append(LayerCost(
            name=getattr(mod, "name", type(mod).__name__),
            kind=type(mod).__name__, flops=flops,
            param_bytes=_param_bytes(params), out_elems=out_e,
            eff_flops=eff))
    return report


def train_step_flops(model, variables: Dict[str, Any], sample_inputs,
                     batch_size: int) -> float:
    """Analytic training FLOPs of ONE global step: 3 x forward, scaled
    from the traced sample batch to ``batch_size`` rows (layer FLOPs are
    linear in the batch dim; sequence lengths come from the sample)."""
    rep = forward_costs(model, variables, *sample_inputs)
    return rep.train_flops() / max(rep.batch, 1) * batch_size


def train_step_flops_detail(model, variables: Dict[str, Any],
                            sample_inputs,
                            batch_size: int) -> Dict[str, float]:
    """Like :func:`train_step_flops` but reports BOTH conventions:
    ``dense`` (dense-equivalent, sparsity-blind — the legacy
    ``train.flops_per_step``/``train.mfu`` numerator) and ``effective``
    (nonzero-block work only — the ``train.effective_mfu`` numerator)."""
    rep = forward_costs(model, variables, *sample_inputs)
    scale = batch_size / max(rep.batch, 1)
    return {"dense": rep.train_flops() * scale,
            "effective": rep.train_eff_flops() * scale}


def mfu(flops_per_step: float, step_time_s: float, n_devices: int,
        peak: Optional[float]) -> Optional[float]:
    """Model-flop utilization: achieved FLOP/s per chip over the chip's
    peak.  None when the peak is unknown (no table entry, no override)."""
    if not peak or step_time_s <= 0 or n_devices <= 0:
        return None
    achieved = flops_per_step / step_time_s / n_devices
    return achieved / peak


def collective_ledger(step_engine) -> Dict[str, Any]:
    """Per-step collective-bytes ledger of a
    :class:`~bigdl_tpu.optim.train_step.ShardedParameterStep`, derived
    from the parameter layout and sync strategy (ZeRO-1 reduce-scatter +
    all_gather; hierarchical DCN hop when the mesh is multislice).

    Bytes are counted in the ACTUAL wire dtype of the configured
    ``grad_comm`` / ``param_comm`` modes — bf16 payloads at 2 B/elem,
    int8 payloads at 1 B/elem PLUS the f32 per-block quantization scales
    and block padding (``parallel.collectives`` estimators) — so
    before/after compression comparisons are honest.  ``grad_ici`` /
    ``param_ici`` split the ICI total into the gradient scatter and the
    param gather (f32, or the int8 delta gather under
    ``param_comm="int8"``)."""
    mode = getattr(step_engine, "grad_comm",
                   "bf16" if getattr(step_engine, "bf16_grads", False)
                   else "fp32")
    param_mode = getattr(step_engine, "param_comm", "fp32")
    grad_ici = float(getattr(step_engine, "grad_sync_ici_bytes_per_step",
                             step_engine.collective_bytes_per_step))
    param_ici = float(getattr(step_engine, "param_sync_ici_bytes_per_step",
                              0))
    from bigdl_tpu.parallel.collectives import wire_itemsize

    return {
        "ici_bytes_per_step": float(step_engine.collective_bytes_per_step),
        "dcn_bytes_per_step": float(step_engine.dcn_bytes_per_step),
        "grad_ici_bytes_per_step": grad_ici,
        "param_ici_bytes_per_step": param_ici,
        "n_data_replicas": float(step_engine.n_data_replicas),
        "grad_comm": mode,
        "param_comm": param_mode,
        # legacy key: payload bytes per gradient element on the wire
        "grad_dtype_bytes": wire_itemsize(mode),
        "comm_buckets": float(getattr(step_engine, "comm_buckets", 1)),
        "n_params_padded": float(step_engine.n_pad),
    }
