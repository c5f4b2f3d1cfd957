"""Device-mesh construction over ICI/DCN.

Replaces the reference's Spark executor-topology inference
(dllib/utils/Engine.scala, unverified — mount empty): where BigDL asks SparkConf
for node/core counts and hard-fails if it cannot infer them, the TPU runtime
introspects ``jax.devices()`` and lays the requested logical axes
(data / model / seq / expert / pipe) out over the physical slice so that the
heavy-traffic axes ride ICI, not DCN.
"""

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical logical axis names, inner-to-outer traffic intensity.  "data" is
# the WITHIN-SLICE allreduce axis (the AllReduceParameter analog);
# model/seq/expert are the tensor/sequence/expert-parallel axes; pipe is
# pipeline stages; "dcn_data" is the cross-slice (DCN) data axis of a
# multislice job — collectives over it are hierarchical: reduce-scatter
# rides ICI first, only 1/ici_data of the gradient crosses DCN.
AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"
AXIS_PIPE = "pipe"
AXIS_DCN = "dcn_data"


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the replication check off by default (every
    caller here disables it: the train step's donated buffers and
    psum_scatter/all_gather pattern trip false positives)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def detect_slice_count(devices: Sequence) -> int:
    """Number of distinct TPU slices among ``devices`` (1 when the runtime
    exposes no slice topology — CPU sim, single slice)."""
    ids = set()
    for d in devices:
        s = getattr(d, "slice_index", None)
        if s is None:
            return 1
        ids.add(s)
    return max(1, len(ids))


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape.  Any axis set to 1 is still present (size-1 axes
    are free in XLA) so train steps can be written once against all six
    axes (dcn_data, data, model, seq, expert, pipe).

    ``dcn_data``: cross-slice data-parallel degree.  ``0`` (default)
    auto-detects the slice count from the device topology — a multislice
    job hierarchically splits its data axis without config changes;
    single-slice and CPU-sim runs resolve to 1."""

    data: int = -1  # -1: fill with remaining devices
    model: int = 1
    seq: int = 1
    expert: int = 1
    pipe: int = 1
    dcn_data: int = 0  # 0: auto-detect slice count

    def resolve(self, n_devices: int, n_slices: int = 1) -> Dict[str, int]:
        dcn = self.dcn_data if self.dcn_data > 0 else n_slices
        fixed = {
            AXIS_MODEL: self.model,
            AXIS_SEQ: self.seq,
            AXIS_EXPERT: self.expert,
            AXIS_PIPE: self.pipe,
        }
        prod = int(np.prod(list(fixed.values()))) * dcn
        if self.data == -1:
            if n_devices % prod != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by "
                    f"dcn_data*model*seq*expert*pipe={prod}")
            data = n_devices // prod
        else:
            data = self.data
            if data * prod > n_devices:
                raise ValueError(
                    f"mesh {data}x{prod} exceeds device count {n_devices}"
                )
        return {AXIS_DCN: dcn, AXIS_DATA: data, **fixed}


def build_mesh(
    spec: Optional[MeshSpec] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a ``jax.sharding.Mesh`` with the canonical axis names.

    Axis order is (pipe, data, expert, seq, model): the innermost (fastest
    varying over physically-adjacent chips) axes are the ones with the most
    traffic per step — model/seq collectives every layer, data allreduce once
    per step, pipeline edges lightest — so `mesh_utils` places model/seq on
    ICI-adjacent chips.
    """
    spec = spec or MeshSpec()
    devices = list(devices if devices is not None else jax.devices())
    sizes = spec.resolve(len(devices), detect_slice_count(devices))
    order = (AXIS_DCN, AXIS_PIPE, AXIS_DATA, AXIS_EXPERT, AXIS_SEQ,
             AXIS_MODEL)
    shape = tuple(sizes[a] for a in order)
    total = int(np.prod(shape))
    if total < len(devices):
        # a sub-mesh is allowed in single-process runs (tests, debugging) but
        # would strand whole hosts' devices in a multi-process job while the
        # input pipeline still shards by process_count
        if jax.process_count() > 1:
            raise ValueError(
                f"mesh size {total} < device count {len(devices)} is not "
                "supported in multi-process runs")
        devices = devices[:total]
    dev_array = None
    if sizes[AXIS_DCN] > 1 and detect_slice_count(devices) == sizes[AXIS_DCN]:
        # real multislice: let mesh_utils keep each slice's sub-mesh on ICI
        # and put only the dcn axis across slice boundaries
        try:
            from jax.experimental import mesh_utils

            dev_array = mesh_utils.create_hybrid_device_mesh(
                (1,) + shape[1:],
                (shape[0],) + (1,) * (len(shape) - 1),
                devices=devices)
        except Exception:
            dev_array = None
    if dev_array is None:
        try:
            from jax.experimental import mesh_utils

            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        except Exception:
            # jax.devices() orders by process index, so a plain reshape
            # aligns the outermost (dcn) axis with process/slice boundaries
            dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, order)


def mesh_fingerprint(mesh: Optional[Mesh] = None) -> str:
    """Stable identity of the device topology a process is running on —
    device count, platform/kind, and (when a mesh is given) the logical
    axis sizes.  Membership views (``resilience.membership``) carry the
    publisher's fingerprint so a replacement process brought up on
    DIFFERENT hardware (fewer chips, another generation) is rejected at
    rendezvous instead of wedging the first collective it joins."""
    devices = list(mesh.devices.flat) if mesh is not None else jax.devices()
    d0 = devices[0]
    parts = [str(len(devices)), getattr(d0, "platform", "?"),
             getattr(d0, "device_kind", "?")]
    if mesh is not None:
        parts.append("x".join(f"{a}={n}" for a, n in mesh.shape.items()))
    return ":".join(parts)


def data_axis_size(mesh: Mesh) -> int:
    return mesh.shape[AXIS_DATA]


def local_batch_slice(mesh: Mesh, global_batch: int) -> Tuple[int, int]:
    """(per-process batch start, size) for host-sharded input pipelines."""
    n_proc = jax.process_count()
    if global_batch % n_proc != 0:
        raise ValueError(f"global batch {global_batch} % processes {n_proc} != 0")
    per = global_batch // n_proc
    return jax.process_index() * per, per
