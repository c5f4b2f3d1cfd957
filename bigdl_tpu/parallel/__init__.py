"""Parallelism beyond data-parallel — capabilities the reference lacks.

The reference (ram1991/BigDL — SURVEY.md §3.5, mount empty/unverified) is
synchronous data-parallel only (``DistriOptimizer`` + BlockManager allreduce).
This package adds the TPU-native axes on the same ``Mesh``:

- ``ring_attention``: sequence/context parallelism — blockwise attention with
  K/V blocks rotating around the "seq" axis via ``ppermute`` (ICI ring),
  flash-style online-softmax accumulation, exact (not approximate).
- ``ulysses``: the all-to-all sequence-parallel alternative — two dense
  ``all_to_all`` collectives re-shard sequence→heads and back around an
  unmodified full-attention kernel (DeepSpeed-Ulysses recipe).
- ``tp``: tensor parallelism — column/row-parallel Linear pairs with one
  ``psum`` per pair over the "model" axis (Megatron layout, expressed as
  shard_map-friendly functions + GSPMD sharding rules).
- ``sharded_module``: GSPMD partitioning helpers — logical-axis param
  annotations lowered to ``NamedSharding`` on the mesh.
- ``pp``: pipeline parallelism — GPipe schedule as ONE SPMD ``lax.scan``
  over the "pipe" axis, activations rotating via ``ppermute``.
- ``moe``: mixture-of-experts with expert parallelism — capacity-bounded
  top-k dispatch, ONE ``all_to_all`` each way over the "expert" axis; and
  the held-share, no-drop layer (``HeldMoE``: sigmoid routing over all
  experts, this shard's experts by grouped matrix products).
- ``layout`` / ``mesh_policy``: the DECLARATIVE sharding layer (docs/
  parallelism.md §Declarative layouts) — a frozen ``SpecLayout`` of
  canonical PartitionSpecs over a named (data, fsdp, tp, seq) mesh,
  per-model layout tables with an audited replicate fallback, and the
  ``parallelism="dp"|"fsdp"|"tp"|"dp:4,tp:2"`` combo-string policy the
  Estimator/Keras/serving surfaces resolve against the live device set.
"""

from bigdl_tpu.parallel.ring_attention import ring_attention
from bigdl_tpu.parallel.ulysses import (ulysses_attention,
                                        ulysses_attention_sharded)
from bigdl_tpu.parallel.tp import (
    column_parallel, row_parallel, tp_linear_pair,
)
from bigdl_tpu.parallel.pp import (
    microbatch, pipeline_apply, pipeline_apply_circular, spmd_pipeline,
    spmd_pipeline_circular, stack_stage_params,
    stack_stage_params_circular, unmicrobatch,
)
from bigdl_tpu.parallel.moe import (HeldMoE, MoE, held_experts_apply,
                                    moe_apply_ep, moe_apply_local,
                                    route_sigmoid_topk)
from bigdl_tpu.parallel.pp_train import PipelineTrainStep
from bigdl_tpu.parallel.gspmd import (GSPMDTrainStep, build_param_specs,
                                      fit_layout, tp_spec_for_path)
from bigdl_tpu.parallel.layout import (ModelLayout, SpecLayout,
                                       layout_for_model, register_layout)
from bigdl_tpu.parallel.mesh_policy import (ResolvedLayout, mesh_and_layout,
                                            parse_parallelism,
                                            resolve_parallelism)

__all__ = [
    "GSPMDTrainStep",
    "build_param_specs",
    "tp_spec_for_path",
    "fit_layout",
    "SpecLayout",
    "ModelLayout",
    "layout_for_model",
    "register_layout",
    "ResolvedLayout",
    "mesh_and_layout",
    "parse_parallelism",
    "resolve_parallelism",
    "ring_attention",
    "ulysses_attention",
    "ulysses_attention_sharded",
    "column_parallel",
    "row_parallel",
    "tp_linear_pair",
    "microbatch",
    "pipeline_apply",
    "pipeline_apply_circular",
    "spmd_pipeline",
    "spmd_pipeline_circular",
    "stack_stage_params",
    "stack_stage_params_circular",
    "unmicrobatch",
    "MoE",
    "moe_apply_ep",
    "moe_apply_local",
    "HeldMoE",
    "held_experts_apply",
    "route_sigmoid_topk",
    "PipelineTrainStep",
]
