"""The distributed train step — heart of the framework.

Reference analog (unverified — mount empty): ``dllib/optim/DistriOptimizer.
scala`` task body + ``optim/parameters/AllReduceParameter.scala``: weights are
flattened into ONE contiguous 1-D storage, gradients are split into
``partitionNum`` chunks pushed through Spark's BlockManager, each partition
owner sums its slice, applies the OptimMethod **on the slice only** (optimizer
state lives sharded — ZeRO-1, 2016 vintage), publishes the updated slice, and
every task gathers all slices next iteration.

TPU-native mapping (this file): the same algorithm as ONE ``shard_map``-ped
XLA program over the mesh's "data" axis —

    flat grads --psum_scatter--> grad slice       (BlockManager put+sum)
    OptimMethod.update(slice)                     (partition-owner update)
    --all_gather--> new flat params               (next-iteration getWeights)

so the BlockManager/netty transport becomes ICI collectives and the two Spark
stages per iteration become zero host round-trips.  Gradient compression
(``FP16CompressedTensor``) maps to the ``grad_comm`` wire-format knob:
``"bf16"`` halves the gradient bytes, ``"int8"`` blockwise-quantizes them
(EQuARX recipe — int8 payload + per-block scales, summed in a widened f32
accumulator; see ``parallel/collectives.py``), and ``comm_bucket_bytes``
splits the sync into buckets XLA can overlap with neighbouring compute.
See PAPERS.md "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" for why this is the native XLA form.
"""

import functools
import warnings
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.obs import state_metrics
from bigdl_tpu.obs.attr import expected_compile
from bigdl_tpu.optim.validation import StatsAccumulator
from bigdl_tpu.parallel import collectives
from bigdl_tpu.runtime.mesh import (AXIS_DATA, AXIS_DCN, AXIS_SEQ,
                                    shard_map)


def as_inputs(x):
    """Model-input convention: a tuple is a multi-input pack, anything else
    is the single input."""
    return x if isinstance(x, tuple) else (x,)


@dataclass
class GradientClipping:
    """Reference ``optim/parameters/ParameterProcessor.scala``:
    ConstantClippingProcessor / L2NormClippingProcessor."""

    constant_min: Optional[float] = None
    constant_max: Optional[float] = None
    l2_norm: Optional[float] = None


def host_fetch(tree):
    """Fetch a (possibly multi-host sharded) pytree to host numpy on every
    process.  Single-process: plain device_get.  Multi-process: allgather the
    non-addressable shards first (checkpoint-time only; not on the hot path)."""
    if jax.process_count() == 1:
        return jax.device_get(tree)
    from jax.experimental import multihost_utils

    return jax.device_get(multihost_utils.process_allgather(tree, tiled=True))


def put_sharded(tree, sharding):
    """Inverse of host_fetch: place full host arrays with ``sharding`` in a
    way that works under multi-controller (each process contributes only its
    addressable shards)."""
    if jax.process_count() == 1:
        return jax.device_put(tree, sharding)

    def put_one(x):
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, sharding, lambda idx: x[idx])

    return jax.tree_util.tree_map(put_one, tree)


def _iter_modules(module, seen=None):
    """Best-effort walk of a module tree (containers, attribute children,
    lists of children)."""
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
                yield from _iter_modules(c, seen)


def _check_seq_parallel_model(model) -> None:
    """Sequence-sharded inputs feed PLAIN attention block-diagonal windows
    (silently wrong numerics), so seq_parallel training demands
    seq-parallel-aware attention layers.  Models with no catalog attention
    at all (hand-written kernels) only get a warning."""
    from bigdl_tpu.nn.attention import MultiHeadAttention
    from bigdl_tpu.utils.log import get_logger

    mhas = [m for m in _iter_modules(model)
            if isinstance(m, MultiHeadAttention)]
    if mhas and not any(m.seq_parallel for m in mhas):
        raise ValueError(
            "seq_parallel=True but none of the model's attention layers "
            "is sequence-parallel-aware — build them with "
            "MultiHeadAttention/TransformerLayer(seq_parallel='ring'|"
            "'ulysses') or plain attention will silently attend only "
            "within each sequence block")
    if not mhas:
        get_logger("bigdl_tpu.optim").warning(
            "seq_parallel=True with no catalog attention layers found: "
            "make sure custom attention uses the seq-axis collectives")


def _flat_layout(params):
    """``(flat vector's shape and dtype, unravel)`` of a parameter pytree
    as ``ravel_pytree`` lays it out, without making the vector."""
    unravel = []

    def ravel(tree):
        flat, fn = ravel_pytree(tree)
        unravel.append(fn)
        return flat

    return jax.eval_shape(ravel, params), unravel[0]


def _host_zeros(tree):
    return jax.tree_util.tree_map(
        lambda x: np.zeros(jnp.shape(x), x.dtype), tree)


def _mask_leaves(params, trainable_mask):
    """``[(parameter leaf, its bool mask as given)]``; a mask leaf is a
    per-leaf scalar or broadcasts to the parameter's shape."""
    leaves_p = jax.tree_util.tree_leaves(params)
    leaves_m = jax.tree_util.tree_leaves(trainable_mask)
    if len(leaves_p) != len(leaves_m):
        raise ValueError(
            "trainable_mask structure does not match params "
            f"({len(leaves_m)} leaves vs {len(leaves_p)})")
    pairs = [(p, np.asarray(m, bool)) for p, m in zip(leaves_p, leaves_m)]
    for p, m in pairs:
        np.broadcast_to(m, np.shape(p))  # raises where it cannot
    return pairs


class ShardedParameterStep:
    """Builds the jitted ZeRO-1 train/eval steps for a model+criterion over a
    mesh.  Owns the flat-parameter layout (the ``AllReduceParameter`` role).

    The layout of the state the step programs carry follows the number of
    shards (``leaf_state``, decided once here from the mesh and the
    ``OptimMethod``; no option).  On several shards, and for layerwise
    methods, parameters / EMA / optimizer state are flat vectors: what
    ``psum_scatter`` and ``all_gather`` need.  Where the parameters live
    on ONE shard (data axis 1, no ``dcn_data`` or ``seq`` axis over 1) and
    the method is elementwise, there is no wire, and the programs carry
    pytrees shaped like the model's parameters: no flat gradient is
    assembled and no flat vector is cut up.  Either way the flat vector
    is the wire and disk format: ``flat_params``, ``ema_flat`` and
    ``opt_state`` read and assign it (docs/parallelism.md)."""

    def __init__(self, model, criterion, optim_method, mesh: Mesh,
                 init_variables: Dict[str, Any],
                 clip: Optional[GradientClipping] = None,
                 bf16_grads: bool = False, remat: bool = False,
                 remat_policy: Optional[str] = None,
                 accum_steps: int = 1, ema_decay: float = 0.0,
                 seq_parallel: bool = False, trainable_mask=None,
                 grad_comm: Optional[str] = None,
                 comm_bucket_bytes: Optional[int] = None,
                 quant_block: int = collectives.DEFAULT_QUANT_BLOCK,
                 param_comm: Optional[str] = None):
        """``grad_comm``: wire format of the gradient sync
        (docs/parallelism.md §Gradient compression) —

        - ``"fp32"`` (default): full-precision reduce-scatter, the
          original cycle.
        - ``"bf16"``: bfloat16 reduce-scatter — halves the gradient's
          collective bytes (the FP16CompressedTensor analog).
        - ``"int8"``: blockwise-quantized reduce-scatter (EQuARX recipe):
          int8 payload + one f32 scale per ``quant_block`` elements over
          an ``all_to_all``, summed in a widened f32 accumulator — ~4x
          fewer gradient bytes on ICI and DCN.  The optimizer update
          always runs on the f32 master params; a single-device data
          axis skips quantization entirely (no wire, no rounding).

        ``param_comm``: wire format of the updated-param all_gather
        (the other half of the ZeRO-1 cycle's ICI bytes) —

        - ``"fp32"`` (default): full-precision gather, the original
          cycle — byte-identical params on every rank by construction.
        - ``"int8"``: gather the blockwise-int8 UPDATE DELTA
          (``new - old`` per shard chunk) + f32 per-block scales and
          reconstruct ``base + dequantized delta`` against the
          replicated flat params — ~4x fewer param-gather ICI bytes.
          The gathered bytes are identical on every rank, so params
          stay bit-identical replicated; the per-step rounding rides
          the small delta, not the param magnitude, and passes the
          same loss-parity gate as ``grad_comm="int8"``
          (tests/test_grad_comm.py).  Master params and the optimizer
          update stay f32.

        ``bf16_grads``: DEPRECATED spelling of ``grad_comm="bf16"``;
        still accepted (with a warning) so existing configs keep working.

        ``comm_bucket_bytes``: split the gradient sync into buckets of at
        most this many flat-gradient bytes, one collective per bucket
        dispatched as its slice of the backward's gradient is consumed —
        bucket *k*'s optimizer update and param gather depend only on
        bucket *k*'s reduce-scatter, the dependence structure XLA's
        latency-hiding scheduler needs to overlap communication with
        neighbouring buckets' compute.  ``None`` keeps one monolithic
        transfer; shard ownership (and therefore optimizer-state layout
        and checkpoints) is identical for every bucket size.

        The optimizer update still runs on the f32 master params.

        ``remat``: wrap the forward in ``jax.checkpoint`` so the backward
        recomputes activations instead of storing them — trades FLOPs for
        HBM on memory-bound models (big batch / long sequence).

        ``accum_steps``: gradient accumulation — each device splits its
        per-step batch into ``accum_steps`` microbatches, runs fwd+bwd per
        microbatch under ``lax.scan`` (activations for ONE microbatch live
        at a time) summing flat gradients in f32, then does a single ZeRO-1
        update.  Numerically the mean gradient of the full batch; the
        per-device batch must be divisible by it.

        ``ema_decay``: keep an exponential moving average of the flat
        params inside the jitted step (``ema = d*ema + (1-d)*params``, the
        ImageNet/TPU recipe); read it with ``get_variables(ema=True)``.

        ``seq_parallel``: additionally shard the SEQUENCE dimension (dim 1
        of every rank>=2 input/target) over the mesh's "seq" axis — the
        long-context training path.  The model's attention layers must be
        sequence-parallel-aware (``MultiHeadAttention(seq_parallel="ring"
        |"ulysses")``); position-wise layers need no change.  Per-block
        gradients are pmean'd over the seq axis before the ZeRO-1 cycle;
        losses/targets must be per-token means so block means compose
        (every block has equal token counts).  The jitted step is built
        lazily on the first batch (leaf ranks decide which dims shard)."""
        self.model = model
        self.criterion = criterion
        self.optim = optim_method
        self.mesh = mesh
        self.clip = clip
        if grad_comm is not None:
            # same normalization as BIGDL_TPU_GRAD_COMM: every entry
            # point (env / Optimizer attr / Estimator config) accepts
            # the same spellings
            grad_comm = str(grad_comm).strip().lower()
        if bf16_grads:
            warnings.warn(
                "bf16_grads is deprecated: use grad_comm='bf16' "
                "(docs/parallelism.md §Gradient compression)",
                DeprecationWarning, stacklevel=2)
            if grad_comm is None:
                grad_comm = "bf16"
        if grad_comm is None:
            grad_comm = "fp32"
        if grad_comm not in collectives.GRAD_COMM_MODES:
            raise ValueError(f"grad_comm {grad_comm!r}: one of "
                             f"{collectives.GRAD_COMM_MODES}")
        self.grad_comm = grad_comm
        if param_comm is not None:
            param_comm = str(param_comm).strip().lower()
        if param_comm is None:
            param_comm = "fp32"
        if param_comm not in collectives.PARAM_COMM_MODES:
            raise ValueError(f"param_comm {param_comm!r}: one of "
                             f"{collectives.PARAM_COMM_MODES}")
        self.param_comm = param_comm
        # legacy readers (benches, old ledgers): True exactly for bf16 wire
        self.bf16_grads = grad_comm == "bf16"
        self.quant_block = int(quant_block)
        self.comm_bucket_bytes = comm_bucket_bytes
        self.remat = remat
        # selective rematerialization: keep the MXU outputs (matmul/conv
        # results — expensive to recompute, cheap to store) and recompute
        # only the fused elementwise tail.  "dots": jax's
        # dots_with_no_batch_dims_saveable policy (the standard long-
        # context recipe); "nothing": recompute everything (max memory
        # savings); None: jax default (= nothing saveable).
        if remat_policy in (None, "nothing"):
            self.remat_policy = None
        elif remat_policy == "dots":
            self.remat_policy = (jax.checkpoint_policies
                                 .dots_with_no_batch_dims_saveable)
        elif callable(remat_policy):
            self.remat_policy = remat_policy
        else:
            raise ValueError(
                f"remat_policy {remat_policy!r}: None | 'nothing' | 'dots' "
                "| a jax.checkpoint_policies callable")
        self.accum_steps = int(accum_steps)
        self.ema_decay = float(ema_decay)
        # ICI (within-slice) data axis: the ZeRO-1 shard denominator.  A
        # multislice mesh adds an outer "dcn_data" axis; gradients
        # reduce-scatter over ICI first and only 1/ndev of the vector
        # crosses DCN (hierarchical allreduce — BASELINE.md 8->256 target).
        axes = dict(mesh.shape)
        self.ndev = axes[AXIS_DATA]
        self.dcn = axes.get(AXIS_DCN, 1)
        self._dcn_axis = AXIS_DCN if self.dcn > 1 else None
        self._batch_axes = ((AXIS_DCN, AXIS_DATA) if AXIS_DCN in axes
                            else (AXIS_DATA,))
        self.n_seq = axes.get(AXIS_SEQ, 1)
        self.seq_parallel = bool(seq_parallel)
        if self.seq_parallel:
            if self.n_seq <= 1:
                raise ValueError(
                    "seq_parallel needs a mesh seq axis > 1 "
                    "(init_engine(seq=N))")
            _check_seq_parallel_model(model)

        # the flat layout (ravel_pytree's leaf order): the wire and disk
        # format on any mesh, and what several shards compute on
        flat_aval, self.unravel = _flat_layout(init_variables["params"])
        self.n_real = flat_aval.shape[0]
        self.n_pad = -(-self.n_real // self.ndev) * self.ndev
        self.shard_size = self.n_pad // self.ndev
        # gradient-sync bucket table: contiguous column ranges of the
        # (ndev, shard_size) gradient view — one collective per bucket,
        # ownership identical to the monolithic layout for any bucketing
        self._bucket_cols = collectives.bucket_columns(
            self.shard_size, self.ndev, comm_bucket_bytes,
            collectives.wire_itemsize(self.grad_comm),
            self.quant_block if self.grad_comm == "int8" else None)

        self._rep = NamedSharding(mesh, P())
        self._sharded_vec = NamedSharding(mesh, P(AXIS_DATA))
        self._batch_sh = NamedSharding(mesh, P(self._batch_axes))

        # the rule: parameters that live on ONE shard have no wire to
        # cross, so the step programs carry them (and the EMA, the
        # optimizer's state, the mask) shaped like the model's leaves;
        # anything else carries flat vectors.  Chosen once, here
        self.leaf_state = (self.ndev == 1 and self.dcn == 1
                           and self.n_seq == 1 and self.optim.elementwise)
        init_state = (self._init_leaf_state if self.leaf_state
                      else self._init_flat_state)
        init_state(init_variables["params"], trainable_mask,
                   flat_aval.dtype)
        self.model_state = jax.device_put(init_variables.get("state", {}),
                                          self._rep)
        # host-side structure templates for checkpoint load (safe to use even
        # when device buffers were consumed by a failed donated step)
        self.model_state_template = _host_zeros(
            init_variables.get("state", {}))

        # seq_parallel specs depend on leaf ranks (which dims shard), so
        # the jitted step is built lazily on the first batch
        self._train = None if self.seq_parallel else self._build_train()
        self._eval_cache: Dict[Any, Callable] = {}
        # fused multi-step programs, one per distinct bundle size (the
        # driver's remainder bundles compile once per K' and are reused)
        self._bundle_cache: Dict[Any, Callable] = {}
        self._base_key = None  # set_step_seed: device-resident PRNG root

    # -- the two layouts of the carried state ---------------------------
    def _init_flat_state(self, params, trainable_mask, dtype) -> None:
        """Several shards (or a layerwise method): ``_params`` / ``_ema``
        are flat vectors replicated over the mesh, ``_opt`` the
        optimizer's state on this shard's slice, ``_mask`` the trainable
        mask as a vector (the scalar 1 when everything trains)."""
        # partial-training mask (LoRA / linear probe / freezing): a pytree
        # matching params with bool leaves (per-leaf scalars, e.g.
        # nn.lora.lora_filter, or per-element arrays).  Frozen entries get
        # zero gradient (optimizer moments stay clean) AND are restored
        # bitwise after the update (weight decay cannot drift them).
        self._mask = jnp.asarray(1.0, jnp.float32)
        if trainable_mask is not None:
            parts = [np.broadcast_to(m, np.shape(p)).reshape(-1)
                     for p, m in _mask_leaves(params, trainable_mask)]
            mask = np.concatenate(parts).astype(np.float32)
            self._mask = jnp.pad(jnp.asarray(mask),
                                 (0, self.n_pad - self.n_real))

        # initial device state.  The flat vector is the only copy of the
        # parameters this object makes (0.6 B parameters are 2.4 GB a
        # copy): padded only if the shards need it, never kept twice
        flat, _ = ravel_pytree(params)
        if self.n_pad != self.n_real:
            flat = jnp.pad(flat, (0, self.n_pad - self.n_real))
        self._params = jax.device_put(flat, self._rep)
        del flat
        # jnp.copy: device_put of an already-placed array is a no-op and
        # would ALIAS ema to the parameters (double donation).  EMA
        # disabled: a distinct 1-element buffer rides the donated slot
        # (donating the parameters twice is an XLA error); it is
        # re-captured from the step output each iteration (donation
        # aliases it through)
        self._ema = jax.device_put(
            jnp.copy(self._params) if self.ema_decay
            else jnp.zeros((1,), dtype), self._rep)
        if self.optim.elementwise:
            # made in place, sharded, by one program: no zeros vector
            # beside the moments and no copy into the sharding
            init_opt = lambda: self.optim.init_state(
                jnp.zeros((self.n_pad,), dtype))
            opt_state = jax.eval_shape(init_opt)
            if len(self._bucket_cols) > 1:
                # per-bucket updates slice every state leaf like the
                # param slice; a leaf that is NOT per-element (scalar
                # running stats, oddly-shaped extras) would be fed whole
                # to every bucket and silently diverge from the
                # monolithic trajectory — fail loudly instead
                bad = [tuple(jnp.shape(l)) for l in
                       jax.tree_util.tree_leaves(opt_state)
                       if tuple(jnp.shape(l)) != (self.n_pad,)]
                if bad:
                    raise ValueError(
                        "comm_bucket_bytes requires per-element "
                        "optimizer state (every leaf shaped "
                        f"({self.n_pad},)); {type(self.optim).__name__} "
                        f"has leaves shaped {bad} — use "
                        "comm_bucket_bytes=None with this OptimMethod")
            self._opt = jax.jit(
                init_opt, out_shardings=self._sharded_vec)()
            self._opt_spec = P(AXIS_DATA)
        else:
            opt_state = self.optim.init_state(params)
            self._opt = jax.device_put(opt_state, self._rep)
            self._opt_spec = P()
        self.opt_template = _host_zeros(opt_state)
        unravel, n_real = self.unravel, self.n_real
        self._params_of = lambda flat_p: unravel(flat_p[:n_real])
        # the carried vectors ARE the wire format
        self._wire = self._carry = self._opt_wire = self._opt_carry = \
            lambda state: state

    def _init_leaf_state(self, params, trainable_mask, dtype) -> None:
        """One shard: ``_params`` / ``_ema`` / ``_opt`` / ``_mask`` are
        pytrees shaped like the model's parameters (no EMA, no mask:
        ``None``).  The flat vector is made only where something reads
        ``flat_params`` / ``ema_flat`` / ``opt_state`` (a checkpoint, a
        peer publish: triggers, never a timed step) and cut up only where
        something assigns them."""
        optim, rep, unravel, n_real = (self.optim, self._rep, self.unravel,
                                       self.n_real)
        tmap = jax.tree_util.tree_map
        # frozen entries: mask leaves stay as given (a per-leaf scalar
        # stays a scalar), as the float the gradient is multiplied by
        self._mask = None
        if trainable_mask is not None:
            self._mask = jax.device_put(jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(params),
                [m.astype(np.float32)
                 for _, m in _mask_leaves(params, trainable_mask)]), rep)
        # own the buffers: the caller may keep ``init_variables`` alive
        # (and the programs donate what they are handed), so every leaf
        # is copied, one at a time: the only copy this object makes
        self._params = jax.device_put(tmap(jnp.copy, params), rep)
        self._ema = (jax.device_put(tmap(jnp.copy, self._params), rep)
                     if self.ema_decay else None)
        shapes = tmap(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
                      self._params)
        # made in place by one program: no zeros beside the moments
        self._opt = jax.jit(
            lambda: optim.init_state(
                tmap(lambda s: jnp.zeros(s.shape, s.dtype), shapes)),
            out_shardings=rep)()
        self._opt_spec = P()
        # the disk format's template: the state of a flat vector
        self.opt_template = _host_zeros(jax.eval_shape(
            lambda: optim.init_state(jnp.zeros((n_real,), dtype))))
        self._params_of = lambda tree: tree
        to_flat = jax.jit(lambda tree: ravel_pytree(tree)[0],
                          out_shardings=rep)
        to_tree = jax.jit(lambda flat: unravel(flat[:n_real]),
                          out_shardings=rep)

        def carry(flat):
            if jnp.ndim(flat) != 1 or flat.shape[0] < n_real:
                raise ValueError(
                    f"a flat vector of shape {jnp.shape(flat)} for a "
                    f"model of {n_real} parameters")
            return to_tree(flat)

        # a leaf of the flat state the size of the vector stands for a
        # whole subtree shaped like the parameters; any other (a scalar
        # statistic) is itself in both formats
        template = self.opt_template

        def per_vector(convert):
            return lambda state: tmap(
                lambda tmpl, sub: (convert(sub)
                                   if tmpl.shape == (n_real,) else sub),
                template, state)

        self._wire, self._carry = to_flat, carry
        self._opt_wire, self._opt_carry = per_vector(to_flat), \
            per_vector(carry)

    @property
    def flat_params(self):
        """The parameters as ONE flat vector (``n_pad`` elements, in
        ``ravel_pytree``'s leaf order, which is ``self.unravel``'s): the
        wire and disk format.  What the step programs carry on several
        shards; raveled on read and cut up on assignment on one."""
        return self._wire(self._params)

    @flat_params.setter
    def flat_params(self, flat):
        self._params = None  # the old copy goes before the new one comes
        self._params = self._carry(flat)

    @property
    def ema_flat(self):
        """The parameters' moving average in the format of
        ``flat_params``; ``None`` without ``ema_decay``."""
        return self._wire(self._ema) if self.ema_decay else None

    @ema_flat.setter
    def ema_flat(self, flat):
        self._ema = None
        self._ema = self._carry(flat)

    @property
    def opt_state(self):
        """The optimizer's state in the wire and disk format: the state
        of the flat vector (this shard's slice of it under ZeRO-1), the
        structure of ``opt_template``."""
        return self._opt_wire(self._opt)

    @opt_state.setter
    def opt_state(self, state):
        self._opt = None
        self._opt = self._opt_carry(state)

    # ------------------------------------------------------------------
    def _leaf_spec(self, a) -> P:
        """Batch sharding spec for one input/target leaf: dim 0 over the
        data axes, dim 1 over the seq axis when sequence-parallel and the
        leaf carries a sequence dimension."""
        if self.seq_parallel and jnp.ndim(a) >= 2:
            return P(self._batch_axes, AXIS_SEQ)
        return P(self._batch_axes)

    def _leaf_sharding(self, a) -> NamedSharding:
        # only two distinct shardings exist; cache them off the hot path
        if self.seq_parallel and jnp.ndim(a) >= 2:
            sh = getattr(self, "_batch_seq_sh", None)
            if sh is None:
                sh = self._batch_seq_sh = NamedSharding(
                    self.mesh, P(self._batch_axes, AXIS_SEQ))
            return sh
        return self._batch_sh

    def _batch_specs(self, tree):
        return jax.tree_util.tree_map(self._leaf_spec, tree)

    # ------------------------------------------------------------------
    def _make_grads(self, form):
        """The forward and backward both layouts share: ``(params, mstate,
        rng, x, y) -> (loss, new_mstate, gradient)``, the gradient in the
        layout's ``form`` (the flat layout ravels the leaves autodiff
        wrote into one vector, the leaf layout takes them as they are).
        With ``accum_steps`` > 1 a scan over the microbatches sums it in
        float32, one microbatch's activations alive at a time."""
        model, criterion = self.model, self.criterion
        remat, remat_policy = self.remat, self.remat_policy
        accum = max(1, self.accum_steps)
        ndev, dcn_axis, seq_par = self.ndev, self._dcn_axis, \
            self.seq_parallel
        tmap = jax.tree_util.tree_map

        def grad_of(p, ms, xs_mb, y_mb, rng_mb):
            def loss_fn(pp):
                out, new_ms = model.forward(
                    pp, ms, *xs_mb, training=True, rng=rng_mb)
                return criterion.forward(out, y_mb), new_ms

            if remat:
                loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)
            return jax.value_and_grad(loss_fn, has_aux=True)(p)

        def grads(params, mstate, rng, x, y):
            replica = jax.lax.axis_index(AXIS_DATA)
            if dcn_axis:
                replica = replica + ndev * jax.lax.axis_index(dcn_axis)
            if seq_par:
                replica = (replica * jax.lax.axis_size(AXIS_SEQ)
                           + jax.lax.axis_index(AXIS_SEQ))
            dev_rng = jax.random.fold_in(rng, replica)
            if accum == 1:
                (loss, new_mstate), g = grad_of(
                    params, mstate, as_inputs(x), y, dev_rng)
                return loss, new_mstate, form(g)

            # microbatch scan: one microbatch's activations live at a
            # time; the f32 gradient accumulates across iterations
            def split(a):
                return a.reshape((accum, a.shape[0] // accum)
                                 + a.shape[1:])

            xs_s = tuple(split(a) for a in as_inputs(x))
            y_s = split(y)

            def micro(carry, inp):
                ms_c, gsum, lsum, k = carry
                xs_mb = inp[:-1]
                y_mb = inp[-1]
                rng_mb = jax.random.fold_in(dev_rng, k)
                (l, new_ms), g = grad_of(params, ms_c, xs_mb, y_mb,
                                         rng_mb)
                gsum = tmap(lambda s, fg: s + fg.astype(jnp.float32),
                            gsum, form(g))
                return (new_ms, gsum, lsum + l, k + 1), None

            gsum0 = tmap(lambda a: jnp.zeros(a.shape, jnp.float32),
                         jax.eval_shape(form, params))
            (new_mstate, gsum, lsum, _), _ = jax.lax.scan(
                micro, (mstate, gsum0, jnp.asarray(0.0, jnp.float32),
                        jnp.asarray(0, jnp.int32)),
                xs_s + (y_s,))
            g = tmap(lambda s: s / accum, gsum)
            return lsum / accum, new_mstate, g

        return grads

    def _make_stats_sync(self):
        """``(loss, mstate, new_mstate) -> (loss, new_mstate)`` as the job
        sees them: what both layouts do with the per-replica loss and
        model state after their update."""
        # axes every per-block statistic (loss, model state, layerwise
        # grads) averages over
        stat_axes = self._batch_axes + ((AXIS_SEQ,)
                                        if self.seq_parallel else ())

        def stats_sync(loss, mstate, new_mstate):
            loss = jax.lax.pmean(loss, stat_axes)
            # model state across replicas: floating leaves (running
            # statistics) are averaged; unsigned leaves are event counters
            # (obs/state_metrics.py), to which every replica added its own
            # events: the job's count is the old value plus the sum of the
            # additions; anything else is each replica's own
            old_leaf = dict(jax.tree_util.tree_flatten_with_path(mstate)[0])

            def sync_state(path, a):
                dtype = jnp.asarray(a).dtype
                if jnp.issubdtype(dtype, jnp.floating):
                    return jax.lax.pmean(a, stat_axes)
                if jnp.issubdtype(dtype, jnp.unsignedinteger) \
                        and path in old_leaf:
                    return old_leaf[path] + jax.lax.psum(
                        a - old_leaf[path], stat_axes)
                return a

            return loss, jax.tree_util.tree_map_with_path(
                sync_state, new_mstate)

        return stats_sync

    def _make_step_shard(self, want_gnorm: bool = False, comm: bool = True):
        """The single-step body of this engine's layout, shared by the
        one-step program and the K-step bundle.  (A method, not a bound
        method kept on the instance: the engine must hold no reference
        cycle, so that dropping it frees its buffers at once.)"""
        make = (self._make_leaf_step if self.leaf_state
                else self._make_flat_step)
        return make(want_gnorm, comm)

    def _make_leaf_step(self, want_gnorm: bool = False, comm: bool = True):
        """The single-step body of the ONE-shard programs: ``(params, ema,
        opt_state, mstate, step, rng, x, y, mask) -> (new_params, new_ema,
        new_opt, new_mstate, loss, gnorm)``, every parameter-sized thing a
        pytree shaped like the model's parameters (``ema`` / ``mask``:
        ``None`` when off).  The gradient's leaves go into
        ``OptimMethod.update`` as autodiff wrote them; the norms are sums
        of per-leaf sums.  There is no wire here, so ``comm`` has nothing
        to leave out."""
        optim, clip, ema_decay = self.optim, self.clip, self.ema_decay
        grads_of = self._make_grads(lambda g: g)
        stats_sync = self._make_stats_sync()
        tmap = jax.tree_util.tree_map

        def sum_sq(tree):
            return sum(jnp.sum(g * g)
                       for g in jax.tree_util.tree_leaves(tree))

        def step_shard(params, ema, opt_state, mstate, step, rng, x, y,
                       mask):
            loss, new_mstate, grads = grads_of(params, mstate, rng, x, y)
            # every leaf is written once, as its weight-gradient product
            # made it.  Left to fuse, XLA:TPU runs the update as that
            # product's epilogue (three more operands, three outputs a
            # tile) at a sixth of the update's own bandwidth (PERF.md §6,
            # PR 37); a barrier a leaf keeps them two programs' worth of
            # work and forces no two leaves alive together
            grads = tmap(jax.lax.optimization_barrier, grads)
            if mask is not None:
                # frozen entries: zero gradient (keeps the moments clean)
                grads = tmap(lambda g, m: g * m.astype(g.dtype), grads,
                             mask)
            grads = tmap(lambda g: g.astype(jnp.float32), grads)
            gnorm = (jnp.sqrt(sum_sq(grads)) if want_gnorm
                     else jnp.asarray(0.0, jnp.float32))
            if clip is not None:
                if (clip.constant_min is not None
                        or clip.constant_max is not None):
                    grads = tmap(lambda g: jnp.clip(
                        g, clip.constant_min, clip.constant_max), grads)
                if clip.l2_norm is not None:
                    scale = jnp.minimum(
                        1.0,
                        clip.l2_norm / (jnp.sqrt(sum_sq(grads)) + 1e-12))
                    grads = tmap(lambda g: g * scale, grads)
            new_params, new_opt = optim.update(step, grads, params,
                                               opt_state)
            if mask is not None:
                # restore frozen entries bitwise: weight decay must not
                # drift parameters that carry no gradient
                new_params = tmap(lambda m, new, old: jnp.where(
                    m > 0, new, old), mask, new_params, params)
            loss, new_mstate = stats_sync(loss, mstate, new_mstate)
            new_ema = (tmap(lambda e, p: ema_decay * e
                            + (1.0 - ema_decay) * p, ema, new_params)
                       if ema_decay else ema)
            return new_params, new_ema, new_opt, new_mstate, loss, gnorm

        return step_shard

    def _make_flat_step(self, want_gnorm: bool = False, comm: bool = True):
        """The single-step body of the programs on several shards (and of
        layerwise methods), shared by the classic one-step program and
        the K-step bundle: (flat_p, ema, opt_state, mstate, step, rng, x,
        y, mask) -> (new_flat, new_ema, new_opt, new_mstate, loss, gnorm).
        ``want_gnorm`` adds the global mean-gradient L2 norm (one extra
        scalar psum on the elementwise path); without it the slot is a
        constant 0 so the classic program's collectives are unchanged.
        ``comm=False`` builds the compute-only overlap-audit variant:
        the gradient scatter / param gather are replaced by same-shaped
        local ops (WRONG numerics; model fwd/bwd and update FLOPs are
        preserved, but the wire codec — int8 quantize/dequantize, bf16
        casts — is elided with the collectives, so the audit attributes
        codec cost to the collective side, matching the comm-only
        probe's denominator) so :meth:`measure_overlap` can time the
        step without its collectives."""
        optim = self.optim
        unravel, n_real = self.unravel, self.n_real
        ndev, shard_size = self.ndev, self.shard_size
        clip = self.clip
        elementwise = optim.elementwise
        grad_comm, quant_block = self.grad_comm, self.quant_block
        param_comm = self.param_comm
        bucket_cols = tuple(self._bucket_cols)
        dcn = self.dcn
        ema_decay = self.ema_decay

        dcn_axis, n_replicas = self._dcn_axis, self.ndev * self.dcn
        batch_axes = self._batch_axes
        seq_par = self.seq_parallel
        grads_of = self._make_grads(lambda g: ravel_pytree(g)[0])
        stats_sync = self._make_stats_sync()

        def step_shard(flat_p, ema, opt_state, mstate, step, rng, x, y,
                       mask):
            # mask: trainable-mask vector (n_pad,) — or the scalar 1.0
            # when everything trains (broadcast no-op)
            params = unravel(flat_p[:n_real])
            loss, new_mstate, flat_g = grads_of(params, mstate, rng, x, y)
            if seq_par:
                # per-sequence-block grads average over the seq axis (the
                # loss is a per-token mean, blocks are equal-sized); params
                # stay replicated across seq so the ZeRO cycle below only
                # spans the data axes
                flat_g = jax.lax.pmean(flat_g, AXIS_SEQ)
            flat_g = jnp.pad(flat_g, (0, flat_p.shape[0] - n_real))
            # frozen entries: zero gradient (keeps optimizer moments clean)
            flat_g = flat_g * mask.astype(flat_g.dtype)

            if elementwise:
                # bucketed reduce-scatter (mean) -> sharded update ->
                # all-gather: exactly AllReduceParameter's
                # put/aggregate/send cycle, one collective per bucket so
                # XLA can overlap a bucket's update/gather with its
                # neighbours' scatter (docs/parallelism.md §Gradient
                # compression & bucketed overlap).  Wire format per
                # grad_comm: f32 / bf16 psum_scatter, or blockwise-int8
                # all_to_all summed in a widened f32 accumulator.
                # Multislice: scatter rides ICI first, then only the
                # 1/ndev slice crosses DCN (quantized again under int8);
                # every slice computes the identical update, so no
                # parameter bytes cross DCN.
                rank = jax.lax.axis_index(AXIS_DATA)
                g2d = (flat_g.reshape(ndev, shard_size) if ndev > 1
                       else None)
                slices = []
                for c0, c1 in bucket_cols:
                    if ndev > 1 and comm:
                        sb = collectives.reduce_scatter_wire(
                            g2d[:, c0:c1], AXIS_DATA, grad_comm,
                            block=quant_block)
                    elif ndev > 1:  # comm=False overlap probe: local chunk
                        sb = jax.lax.dynamic_slice(
                            flat_g, (rank * shard_size + c0,), (c1 - c0,))
                    else:
                        # single-rank data axis: no wire, no quantization
                        sb = flat_g[c0:c1]
                    if dcn_axis and comm:
                        # still in the gradient dtype: with bf16 the DCN
                        # hop carries half the bytes; int8 runs the
                        # two-phase quantized exchange
                        sb = collectives.psum_wire(
                            sb, dcn_axis, dcn, grad_comm,
                            block=quant_block)
                    slices.append(sb.astype(jnp.float32) / n_replicas)
                sq_local = sum(jnp.sum(sb * sb) for sb in slices)
                gnorm = (jnp.sqrt(jax.lax.psum(sq_local, AXIS_DATA))
                         if want_gnorm else jnp.asarray(0.0, jnp.float32))
                if clip is not None:
                    if (clip.constant_min is not None
                            or clip.constant_max is not None):
                        slices = [jnp.clip(sb, clip.constant_min,
                                           clip.constant_max)
                                  for sb in slices]
                    if clip.l2_norm is not None:
                        # global norm over the full (sharded) gradient
                        sq = jax.lax.psum(
                            sum(jnp.sum(sb * sb) for sb in slices),
                            AXIS_DATA)
                        scale = jnp.minimum(
                            1.0, clip.l2_norm / (jnp.sqrt(sq) + 1e-12))
                        slices = [sb * scale for sb in slices]

                def slice_state(leaf, c0, wb):
                    a = jnp.asarray(leaf)
                    if a.ndim >= 1 and a.shape[0] == shard_size:
                        return jax.lax.dynamic_slice_in_dim(a, c0, wb, 0)
                    return a

                new_parts, opt_parts = [], []
                for (c0, c1), sb in zip(bucket_cols, slices):
                    wb = c1 - c0
                    p_b = jax.lax.dynamic_slice(
                        flat_p, (rank * shard_size + c0,), (wb,))
                    o_b = (opt_state if len(bucket_cols) == 1 else
                           jax.tree_util.tree_map(
                               lambda l, c=c0, w=wb: slice_state(l, c, w),
                               opt_state))
                    np_b, no_b = optim.update(step, sb, p_b, o_b)
                    if ndev > 1 and comm:
                        if param_comm == "int8":
                            # delta gather: int8 payload + scales are
                            # identical bytes on every rank, the base
                            # rows come from the replicated flat_p —
                            # params stay bit-identical replicated
                            base = flat_p.reshape(
                                ndev, shard_size)[:, c0:c1]
                            np_b = collectives.all_gather_delta_quantized(
                                np_b - p_b, base, AXIS_DATA,
                                block=quant_block).reshape(-1)
                        else:
                            np_b = jax.lax.all_gather(
                                np_b, AXIS_DATA, tiled=True)
                    elif ndev > 1:  # comm=False probe: same-shape local op
                        np_b = jnp.tile(np_b, ndev)
                    new_parts.append(np_b.reshape(max(ndev, 1), wb))
                    opt_parts.append(no_b)
                # bucket b's gather returns columns [c0,c1) of every
                # rank's chunk; concat along columns rebuilds the
                # monolithic (ndev, shard_size) layout
                new_flat = jnp.concatenate(new_parts, axis=1).reshape(-1)
                if len(opt_parts) == 1:
                    new_opt = opt_parts[0]
                else:
                    def join_state(*parts):
                        a0 = jnp.asarray(parts[0])
                        if a0.ndim >= 1 and sum(
                                jnp.shape(p)[0] for p in parts) \
                                == shard_size:
                            return jnp.concatenate(parts, axis=0)
                        return parts[-1]  # unsliced leaf: buckets agree

                    new_opt = jax.tree_util.tree_map(
                        join_state, *opt_parts)
            else:
                # layerwise methods (LARS): plain psum allreduce + replicated
                # update (matches the reference's treatment pre-slice-
                # sharding); grad_comm is an elementwise-cycle knob, so
                # this path always syncs full precision.  Re-tree the flat
                # (masked) gradient so the trainable_mask reaches this
                # path's optimizer update too
                grads = unravel(flat_g[:n_real].astype(jnp.float32))
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, batch_axes), grads)
                if want_gnorm:
                    fg_n, _ = ravel_pytree(grads)
                    gnorm = jnp.linalg.norm(fg_n)
                else:
                    gnorm = jnp.asarray(0.0, jnp.float32)
                if clip is not None and clip.l2_norm is not None:
                    fg, _ = ravel_pytree(grads)
                    norm = jnp.linalg.norm(fg)
                    scale = jnp.minimum(1.0, clip.l2_norm / (norm + 1e-12))
                    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
                new_params, new_opt = optim.update(step, grads, params, opt_state)
                nf, _ = ravel_pytree(new_params)
                new_flat = jnp.pad(nf, (0, flat_p.shape[0] - n_real))

            # restore frozen entries bitwise: weight decay / bias-corrected
            # moments must not drift parameters that carry no gradient
            new_flat = jnp.where(mask > 0, new_flat, flat_p)
            loss, new_mstate = stats_sync(loss, mstate, new_mstate)
            new_ema = (ema_decay * ema + (1.0 - ema_decay) * new_flat
                       if ema_decay else ema)
            return new_flat, new_ema, new_opt, new_mstate, loss, gnorm

        return step_shard

    def _train_specs(self, x_ex=None, y_ex=None):
        """(opt_spec, x_spec, y_spec) for the train programs — seq_parallel
        specs depend on leaf ranks, so they need example batches."""
        opt_spec = self._opt_spec
        if self.seq_parallel:
            x_spec = self._batch_specs(x_ex)
            y_spec = self._batch_specs(y_ex)
        else:
            x_spec = y_spec = P(self._batch_axes)
        return opt_spec, x_spec, y_spec

    def _build_train(self, x_ex=None, y_ex=None, donate: bool = True,
                     comm: bool = True):
        core = self._make_step_shard(want_gnorm=False, comm=comm)

        def step_shard(flat_p, ema, opt_state, mstate, step, rng, x, y,
                       mask):
            return core(flat_p, ema, opt_state, mstate, step, rng, x, y,
                        mask)[:5]

        opt_spec, x_spec, y_spec = self._train_specs(x_ex, y_ex)
        mapped = shard_map(
            step_shard, mesh=self.mesh,
            in_specs=(P(), P(), opt_spec, P(), P(), P(), x_spec, y_spec,
                      P()),
            out_specs=(P(), P(), opt_spec, P(), P()),
        )
        if not donate:  # overlap-audit probes must not consume live state
            return jax.jit(mapped)
        return jax.jit(mapped, donate_argnums=(0, 1, 2, 3))

    def _build_bundle(self, n_steps: int, x_ex=None, y_ex=None):
        """K consecutive training steps as ONE jitted XLA program: a
        ``lax.scan`` whose body is exactly the single-step shard function,
        loop-carrying (params, EMA, opt-state, model-state, step counter)
        with donation across the whole bundle.  Per-step PRNG derives from
        the ON-DEVICE step counter (``fold_in(base_key, step)``) and the LR
        schedule evaluates on device inside each update, so the host does
        zero per-step work between bundle edges.  Returns length-K loss and
        grad-norm vectors so per-step granularity (NaN-streak detection,
        loss curves) survives bundling, and a copy of the model state's
        ``"metrics"`` subtrees (obs/state_metrics.py): the state itself is
        donated to the next bundle, so the driver, which fetches a bundle's
        results while the next one runs, could not read them there.

        The K input batches arrive as a K-tuple of ordinary per-batch
        device arrays (each sharded exactly like the single-step program's
        batch) and are stacked PER DEVICE inside the shard: the scan xs is
        assembled from local shards, so no host-side super-batch copy and
        no resharding collective ever happens."""
        core = self._make_step_shard(want_gnorm=True)

        def bundle_shard(flat_p, ema, opt_state, mstate, step0, base_key,
                         xs, ys, mask):
            x_stack = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *xs)
            y_stack = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *ys)

            def body(carry, xy):
                fp, em, op, ms, step = carry
                x_k, y_k = xy
                rng = jax.random.fold_in(base_key, step)
                nf, ne, no, nm, loss, gnorm = core(
                    fp, em, op, ms, step, rng, x_k, y_k, mask)
                return (nf, ne, no, nm, step + 1), (loss, gnorm)

            (flat_p, ema, opt_state, mstate, _), (losses, gnorms) = \
                jax.lax.scan(body,
                             (flat_p, ema, opt_state, mstate, step0),
                             (x_stack, y_stack))
            return (flat_p, ema, opt_state, mstate, losses, gnorms,
                    state_metrics.subtrees(mstate))

        opt_spec, x_spec, y_spec = self._train_specs(x_ex, y_ex)
        xs_spec = (tuple(x_spec for _ in range(n_steps))
                   if self.seq_parallel else x_spec)
        ys_spec = (tuple(y_spec for _ in range(n_steps))
                   if self.seq_parallel else y_spec)
        mapped = shard_map(
            bundle_shard, mesh=self.mesh,
            in_specs=(P(), P(), opt_spec, P(), P(), P(), xs_spec, ys_spec,
                      P()),
            out_specs=(P(), P(), opt_spec, P(), P(), P(), P()),
        )
        return jax.jit(mapped, donate_argnums=(0, 1, 2, 3))

    # ------------------------------------------------------------------
    def _build_eval(self, methods: Tuple, x_ex=None, y_ex=None, w_ex=None):
        model, params_of = self.model, self._params_of

        # seq_parallel models MUST see seq-sharded inputs in eval too (their
        # attention layers run seq collectives unconditionally); stats then
        # sum over the seq axis as well — correct for per-token metrics
        stat_axes = self._batch_axes + ((AXIS_SEQ,)
                                        if self.seq_parallel else ())

        def eval_shard(carried_p, mstate, x, y, w):
            params = params_of(carried_p)
            xs = as_inputs(x)
            out, _ = model.forward(params, mstate, *xs, training=False)
            stats = []
            for m in methods:
                s, c = m.batch_stats(out, y, w)
                stats.append((jax.lax.psum(s, stat_axes),
                              jax.lax.psum(c, stat_axes)))
            return tuple(stats)

        if self.seq_parallel:
            x_spec = self._batch_specs(x_ex)
            y_spec = self._batch_specs(y_ex)
            w_spec = self._batch_specs(w_ex)
        else:
            x_spec = y_spec = w_spec = P(self._batch_axes)
        mapped = shard_map(
            eval_shard, mesh=self.mesh,
            in_specs=(P(), P(), x_spec, y_spec, w_spec),
            out_specs=P())
        return jax.jit(mapped)

    @property
    def comm_buckets(self) -> int:
        """Number of gradient-sync buckets (1 = monolithic transfer)."""
        return len(self._bucket_cols)

    @property
    def grad_sync_ici_bytes_per_step(self) -> int:
        """Per-step ICI wire bytes of the GRADIENT reduce-scatter, in the
        actual wire dtype: f32/bf16 payload, or int8 payload + f32
        per-block scales (block padding included) under
        ``grad_comm="int8"`` — the honest before/after meter for
        compression work (``parallel.collectives`` estimators are the
        source of truth)."""
        if self.ndev <= 1:
            return 0
        return sum(collectives.rs_wire_bytes(
            c1 - c0, self.ndev, self.grad_comm, self.quant_block)
            for c0, c1 in self._bucket_cols)

    @property
    def param_sync_ici_bytes_per_step(self) -> int:
        """Per-step ICI wire bytes of the updated-param all_gather, in
        the ACTUAL ``param_comm`` wire dtype: f32 gather bytes
        (``n_pad * 4``) by default; int8 delta payload + f32 per-block
        scales under ``param_comm="int8"``."""
        if self.ndev <= 1:
            return 0
        return sum(collectives.ag_wire_bytes(
            c1 - c0, self.ndev, self.param_comm, self.quant_block)
            for c0, c1 in self._bucket_cols)

    @property
    def collective_bytes_per_step(self) -> int:
        """Per-step ICI traffic of the ZeRO-1 cycle: the gradient
        reduce-scatter (wire dtype per ``grad_comm``, scales included) +
        all_gather of the updated flat f32 params.  Zero on a
        single-device axis — a size-1 collective moves no bytes (matches
        ``gspmd.collective_bytes_for_specs`` for the same topology)."""
        return (self.grad_sync_ici_bytes_per_step
                + self.param_sync_ici_bytes_per_step)

    @property
    def n_data_replicas(self) -> int:
        """Total data-parallel degree (ICI x DCN) — batch dim multiples."""
        return self.ndev * self.dcn

    @property
    def dcn_bytes_per_step(self) -> int:
        """Per-step CROSS-SLICE (DCN) traffic: the hierarchical allreduce
        moves only the 1/ndev gradient slice over DCN (psum ~ 2x slice
        bytes, in the ``grad_comm`` wire dtype — int8 counts payload +
        scales for both quantized phases); parameters never cross
        slices."""
        if self.dcn <= 1:
            return 0
        return sum(collectives.psum_wire_bytes(
            c1 - c0, self.dcn, self.grad_comm, self.quant_block)
            for c0, c1 in self._bucket_cols)

    # -- overlap audit (docs/performance.md §Gradient-comm modes) -------
    def _build_comm_probe(self):
        """Comm-only program: ONLY the bucketed gradient reduce-scatter
        (+ DCN hop) and the bucketed param all_gather, on same-shaped
        vectors — what :meth:`measure_overlap` times as 'total collective
        time'."""
        ndev, shard_size, dcn = self.ndev, self.shard_size, self.dcn
        dcn_axis = self._dcn_axis
        grad_comm, block = self.grad_comm, self.quant_block
        param_comm = self.param_comm
        cols = tuple(self._bucket_cols)
        batch_axes = self._batch_axes

        def comm_shard(flat_g, flat_p):
            rank = jax.lax.axis_index(AXIS_DATA)
            acc = jnp.asarray(0.0, jnp.float32)
            g2d = flat_g.reshape(ndev, shard_size) if ndev > 1 else None
            for c0, c1 in cols:
                wb = c1 - c0
                if ndev > 1:
                    # the SAME wire dispatch the step body uses — the
                    # audit must time exactly the step's collectives
                    sb = collectives.reduce_scatter_wire(
                        g2d[:, c0:c1], AXIS_DATA, grad_comm, block=block)
                else:
                    sb = flat_g[c0:c1]
                if dcn_axis:
                    sb = collectives.psum_wire(sb, dcn_axis, dcn,
                                               grad_comm, block=block)
                acc = acc + jnp.sum(sb.astype(jnp.float32))
                p_b = jax.lax.dynamic_slice(
                    flat_p, (rank * shard_size + c0,), (wb,))
                if ndev > 1 and param_comm == "int8":
                    # same wire shape as the step's delta gather (int8
                    # payload + scales); p_b stands in for the delta —
                    # the probe only needs byte-identical collectives
                    base = flat_p.reshape(ndev, shard_size)[:, c0:c1]
                    p_b = collectives.all_gather_delta_quantized(
                        p_b, base, AXIS_DATA, block=block)
                elif ndev > 1:
                    p_b = jax.lax.all_gather(p_b, AXIS_DATA, tiled=True)
                acc = acc + jnp.sum(p_b)
            # replicate the scalar so the out_spec holds on every rank
            return jax.lax.pmean(acc, batch_axes)

        mapped = shard_map(comm_shard, mesh=self.mesh,
                           in_specs=(P(), P()), out_specs=P())
        return jax.jit(mapped)

    def measure_overlap(self, x_dev, y_dev, *, steps: int = 5,
                        rng=None) -> Dict[str, float]:
        """One-shot overlap audit: how much of the gradient-sync
        collective time does the step structure hide under compute?

        Times three programs on the SAME shapes — the real train step, a
        compute-only variant (collectives replaced by same-shaped local
        ops), and a comm-only probe (just the bucketed scatter/gather
        cycle) — and reports::

            exposed_collective_s = max(0, step_s - compute_s)
            overlap_efficiency   = 1 - exposed / collective_s   (in [0,1])

        Builds two extra non-donating XLA programs, so this is an
        audit call (``BIGDL_TPU_MEASURE_OVERLAP=1``), not a hot-path one.
        Training state is read, never consumed."""
        import time as _time

        if self.seq_parallel:
            raise NotImplementedError(
                "overlap audit under seq_parallel: run it on a "
                "data-parallel mesh")
        if rng is None:
            rng = jax.random.PRNGKey(0)
        full = self._build_train(donate=False)
        nocomm = self._build_train(donate=False, comm=False)
        probe = self._build_comm_probe()
        args = (self._params, self._ema, self._opt,
                self.model_state, jnp.asarray(0, jnp.int32), rng,
                x_dev, y_dev, self._mask)
        flat_p = self.flat_params

        def timed(fn, *a):
            jax.block_until_ready(fn(*a))  # compile + warm
            ts = []
            for _ in range(max(1, steps)):
                t0 = _time.perf_counter()
                jax.block_until_ready(fn(*a))
                ts.append(_time.perf_counter() - t0)
            return float(np.median(ts))

        with expected_compile():
            t_full = timed(full, *args)
            t_nocomm = timed(nocomm, *args)
            t_comm = timed(probe, flat_p, flat_p)
        exposed = max(0.0, t_full - t_nocomm)
        eff = (min(1.0, max(0.0, 1.0 - exposed / t_comm))
               if t_comm > 0 else 0.0)
        return {"step_s": t_full, "compute_s": t_nocomm,
                "collective_s": t_comm, "exposed_collective_s": exposed,
                "overlap_efficiency": eff,
                "comm_buckets": float(len(self._bucket_cols)),
                "grad_comm": self.grad_comm}

    # ------------------------------------------------------------------
    def shard_batch(self, arr):
        """Host numpy (per-process shard) -> global device array on the data
        axis (and the seq axis for rank>=2 leaves when sequence-parallel).
        Accepts a pytree (tuple of arrays for multi-input models)."""
        if jax.process_count() == 1:
            return jax.tree_util.tree_map(
                lambda a: jax.device_put(a, self._leaf_sharding(a)), arr)
        return jax.tree_util.tree_map(
            lambda a: jax.make_array_from_process_local_data(
                self._leaf_sharding(a), a), arr)

    def train_step(self, step: int, rng, x, y):
        return self.train_step_device(
            step, rng, self.shard_batch(x), self.shard_batch(y))

    def train_step_device(self, step: int, rng, x_dev, y_dev):
        """Variant taking already-sharded device arrays (the prefetch path —
        see ``bigdl_tpu.data.prefetch``)."""
        if self._train is None:  # seq_parallel: specs need leaf ranks
            self._train = self._build_train(x_dev, y_dev)
        (self._params, self._ema, self._opt, self.model_state,
         loss) = self._train(
            self._params, self._ema, self._opt, self.model_state,
            jnp.asarray(step, jnp.int32), rng, x_dev, y_dev, self._mask)
        return loss

    # -- fused multi-step execution (docs/performance.md) ---------------
    def set_step_seed(self, seed: int) -> None:
        """Place the per-run PRNG root on device ONCE; every bundled step
        derives its key inside the jitted program from the on-device step
        counter, so no host-side ``PRNGKey``/``fold_in`` runs per step.
        (put_sharded: a bare device_put of a replicated array broadcasts
        under multi-controller, which multi-host CPU meshes cannot do.)"""
        self._base_key = put_sharded(
            np.asarray(jax.random.PRNGKey(seed)), self._rep)

    def train_bundle_device(self, step0: int, xs, ys, base_key=None):
        """Run ``len(xs)`` consecutive training steps as ONE dispatched XLA
        program over already-sharded device batches.  Returns
        ``(losses, grad_norms, state_metrics)`` — two length-K device
        vectors, one entry per step, and the model state's ``"metrics"``
        subtrees after the last step (``{}`` for a model that keeps none),
        all fetched lazily by the caller.

        Numerics are identical for every bundle size: the scan body is the
        same per-step HLO, per-step PRNG is ``fold_in(base_key, step)`` of
        the global step counter, and batches keep their identities — so a
        K=4 trajectory is byte-identical to K=1 (tests/test_step_bundle)."""
        k = len(xs)
        if k == 0 or len(ys) != k:
            raise ValueError(f"bundle needs matching non-empty batch "
                             f"lists, got {k} inputs / {len(ys)} targets")
        if base_key is None:
            base_key = self._base_key
            if base_key is None:
                raise ValueError(
                    "train_bundle_device needs set_step_seed() first "
                    "(or an explicit base_key)")
        key = k
        if self.seq_parallel:
            # baked in_specs depend on leaf ranks
            key = (k, tuple(jnp.ndim(a) for a in
                            jax.tree_util.tree_leaves((xs[0], ys[0]))))
        fn = self._bundle_cache.get(key)
        new_program = fn is None
        if new_program:
            fn = self._bundle_cache[key] = self._build_bundle(
                k, xs[0], ys[0])
        # a first-seen bundle size (epoch-tail remainder, trigger-clamped
        # span) legitimately compiles mid-run: announce it so the
        # recompilation sentinel only flags true cache misses
        with expected_compile() if new_program else nullcontext():
            (self._params, self._ema, self._opt, self.model_state,
             losses, gnorms, counted) = fn(
                self._params, self._ema, self._opt, self.model_state,
                jnp.asarray(step0, jnp.int32), base_key,
                tuple(xs), tuple(ys), self._mask)
        return losses, gnorms, counted

    def evaluate(self, methods, batches) -> list:
        # cache key must be the method *instances* (two Loss() objects with
        # different criteria are different programs); holding them in the
        # cache keeps ids stable
        acc = StatsAccumulator()
        for mb in batches:
            x = mb["input"]
            n_rows = as_inputs(x)[0].shape[0]
            w = mb.get("weight")
            if w is None:
                w = np.ones((n_rows,), np.float32)
            # cache key: method instances AND the spec-relevant batch
            # structure (the baked in_specs depend on leaf ranks)
            ranks = tuple(np.ndim(a) for a in
                          jax.tree_util.tree_leaves((x, mb["target"], w)))
            key = (tuple(id(m) for m in methods), ranks)
            new_program = key not in self._eval_cache
            if new_program:
                # built on the first batch: seq_parallel specs need ranks
                self._eval_cache[key] = (tuple(methods), self._build_eval(
                    tuple(methods), x, mb["target"], w))
            _, fn = self._eval_cache[key]
            # a first validation pass mid-run compiles its eval program —
            # expected, not an XLA cache miss
            with expected_compile() if new_program else nullcontext():
                acc.add(fn(self._params, self.model_state,
                           self.shard_batch(x),
                           self.shard_batch(mb["target"]),
                           self.shard_batch(w)))
        totals = acc.fetch()
        return [m.fold(s, c) for m, (s, c) in zip(methods, totals or [])]

    # ------------------------------------------------------------------
    def rebuild_programs(self) -> None:
        """Drop every compiled program so the next call re-traces the
        model.  Needed after HOST-side model structure changes jit cannot
        see in its input avals — e.g. a block-sparse FFN mask restored
        from a checkpoint or changed by a pruning event: the mask is a
        trace-time constant, so a stale program would keep computing with
        the old sparsity pattern."""
        self._train = None if self.seq_parallel else self._build_train()
        self._eval_cache.clear()
        self._bundle_cache.clear()
        if hasattr(self, "_predict_jit"):
            self._predict_jit = None

    def get_variables(self, ema: bool = False) -> Dict[str, Any]:
        host = jax.device_get(self._ema if (ema and self.ema_decay)
                              else self._params)
        # a flat vector is split on the host: on the device the flat copy,
        # its pieces and their reshapes would stand beside the training
        # state, three more vectors of the model's size where one is wanted
        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            host = jax.device_get(self._params_of(host))
        # fresh arrays: the engine donates its own at the next step
        return {"params": jax.tree_util.tree_map(jnp.asarray, host),
                "state": jax.device_get(self.model_state)}

    def predict_fn(self):
        """Jitted inference callable over the mesh (batch data-sharded).
        The jitted forward is cached on the engine so repeated predict()
        calls don't recompile."""
        fwd = getattr(self, "_predict_jit", None)
        if fwd is None:
            model, params_of = self.model, self._params_of

            def raw(carried_p, mstate, x):
                params = params_of(carried_p)
                xs = as_inputs(x)
                out, _ = model.forward(params, mstate, *xs, training=False)
                return out

            if self.seq_parallel:
                # seq-parallel attention runs seq collectives, so inference
                # too must live inside a shard_map carrying the axis; output
                # leaves must be per-token (batch, seq, ...) — pooled heads
                # are not representable under sequence sharding
                out_spec = P(self._batch_axes, AXIS_SEQ)
                mesh = self.mesh
                _cache: Dict[Any, Callable] = {}

                def fwd(carried_p, mstate, x):
                    key = jax.tree_util.tree_structure(x)
                    if key not in _cache:
                        _cache[key] = jax.jit(shard_map(
                            raw, mesh=mesh,
                            in_specs=(P(), P(), self._batch_specs(x)),
                            out_specs=out_spec))
                    return _cache[key](carried_p, mstate, x)
            else:
                fwd = jax.jit(raw)

            self._predict_jit = fwd

        if jax.process_count() > 1:
            if self.seq_parallel:
                raise NotImplementedError(
                    "multi-host predict with seq_parallel: run evaluate() "
                    "(mesh-wide) or export the params for single-host "
                    "inference")
            # multi-host: predict locally per process (params are replicated,
            # so each host can run inference on its own shard of requests
            # without building a non-addressable global output)
            host_params = np.asarray(self.flat_params)
            host_state = host_fetch(self.model_state)

            def run(x):
                return fwd(jnp.asarray(host_params), host_state,
                           jax.tree_util.tree_map(jnp.asarray, x))
        else:
            def run(x):
                return fwd(self._params, self.model_state,
                           self.shard_batch(x))

        return run
