"""The layout of the state the train step carries follows the number of
shards (``optim/train_step.py``, ``ShardedParameterStep.leaf_state``).

On ONE shard the programs carry parameters, optimizer state, EMA and mask
as pytrees shaped like the model's parameters: the trajectory is the plain
``value_and_grad`` + ``OptimMethod.update`` on the tree, bit for bit, and
no parameter-sized vector is assembled or cut up inside a step.  On
several shards the flat ZeRO-1 cycle is the program it was.  Either way
the flat vector is the wire and disk format: ``flat_params`` /
``opt_state`` / ``ema_flat`` read and assign it, and a checkpoint written
on one device resumes on eight and the reverse.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from bigdl_tpu import nn, optim
from bigdl_tpu.data import ArrayDataSet
from bigdl_tpu.optim import checkpoint as ckpt_mod
from bigdl_tpu.optim.train_step import GradientClipping, ShardedParameterStep
from bigdl_tpu.runtime.engine import Engine, init_engine
from bigdl_tpu.runtime.mesh import MeshSpec, build_mesh

D, CLASSES, BATCH = 16, 8, 16
N_REAL = D * 32 + 32 + 32 * CLASSES + CLASSES  # 808: divides by 8, so
#                      the flat vector has one length on 1 and 8 devices
tmap = jax.tree_util.tree_map


def mlp(dropout=0.1):
    layers = [nn.Linear(D, 32), nn.ReLU()]
    if dropout:
        layers.append(nn.Dropout(dropout))
    return nn.Sequential(layers + [nn.Linear(32, CLASSES), nn.LogSoftMax()])


def batches(n=5, seed=0):
    rs = np.random.RandomState(seed)
    return [(rs.randn(BATCH, D).astype(np.float32),
             rs.randint(0, CLASSES, BATCH).astype(np.int32))
            for _ in range(n)]


def init(model):
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, D), jnp.float32))


def mesh_of(n):
    return build_mesh(MeshSpec(data=n), devices=jax.devices()[:n])


# Every coefficient is a power of two: a product with one is exact, so a
# fused multiply-add and a multiply followed by an add round alike.  The
# CPU compiler contracts the two by where its fusions happen to end, which
# differs between two programs of one expression; with such coefficients
# "bit for bit" tests the expression and not the compiler's mood.
METHODS = {
    "sgd_momentum": lambda: optim.SGD(learning_rate=0.125, momentum=0.5,
                                      weight_decay=2.0 ** -10),
    "adam": lambda: optim.Adam(learning_rate=2.0 ** -6, beta1=0.5,
                               beta2=0.5),
}
EMA_DECAY = 0.5
# frozen: the first layer's bias (a per-leaf scalar) and half the columns
# of the last layer's weight (a per-element array)
_HALF = np.arange(CLASSES) % 2 == 0


def _mask(params):
    keys = sorted(params)
    return {keys[0]: {"weight": True, "bias": False},
            keys[1]: {"weight": np.broadcast_to(_HALF, (32, CLASSES)),
                      "bias": True}}


FEATURES = {
    "plain": {},
    "trainable_mask": {"trainable_mask": _mask},
    "ema_decay": {"ema_decay": EMA_DECAY},
    "accum_steps": {"accum_steps": 2},
    "clip_l2": {"clip": GradientClipping(l2_norm=0.05)},
    "clip_constant": {"clip": GradientClipping(constant_min=-0.01,
                                               constant_max=0.02)},
}


def reference(model, method, variables, feature, data, base_key):
    """The plain tree reference: ``value_and_grad`` of the loss on the
    parameter pytree, then ``method.update`` on the trees.  One jitted
    function a step; returns the trajectory."""
    criterion = nn.ClassNLLCriterion()
    mask = feature.get("trainable_mask")
    if mask is not None:
        mask = tmap(lambda m: jnp.asarray(m, jnp.float32),
                    mask(variables["params"]))
    ema_decay = feature.get("ema_decay", 0.0)
    accum = feature.get("accum_steps", 1)
    clip = feature.get("clip")

    def sum_sq(tree):
        return sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(tree))

    @jax.jit
    def step_fn(params, mstate, opt_state, ema, step, x, y):
        rng = jax.random.fold_in(jax.random.fold_in(base_key, step), 0)

        def grad_of(ms, xb, yb, key):
            def loss_fn(p):
                out, new_ms = model.forward(p, ms, xb, training=True,
                                            rng=key)
                return criterion.forward(out, yb), new_ms
            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        if accum == 1:
            (loss, mstate), grads = grad_of(mstate, x, y, rng)
        else:
            n, gsum, lsum = x.shape[0] // accum, None, 0.0
            for k in range(accum):
                (l, mstate), g = grad_of(
                    mstate, x[k * n:(k + 1) * n], y[k * n:(k + 1) * n],
                    jax.random.fold_in(rng, k))
                gsum = g if gsum is None else tmap(jnp.add, gsum, g)
                lsum = lsum + l
            grads, loss = tmap(lambda s: s / accum, gsum), lsum / accum
        if mask is not None:
            grads = tmap(lambda g, m: g * m, grads, mask)
        gnorm = jnp.sqrt(sum_sq(grads))
        if clip is not None and clip.constant_min is not None:
            grads = tmap(lambda g: jnp.clip(g, clip.constant_min,
                                            clip.constant_max), grads)
        if clip is not None and clip.l2_norm is not None:
            scale = jnp.minimum(
                1.0, clip.l2_norm / (jnp.sqrt(sum_sq(grads)) + 1e-12))
            grads = tmap(lambda g: g * scale, grads)
        new_params, opt_state = method.update(step, grads, params,
                                              opt_state)
        if mask is not None:
            new_params = tmap(lambda m, a, b: jnp.where(m > 0, a, b),
                              mask, new_params, params)
        if ema_decay:
            ema = tmap(lambda e, p: ema_decay * e + (1.0 - ema_decay) * p,
                       ema, new_params)
        return new_params, mstate, opt_state, ema, loss, gnorm

    params, mstate = variables["params"], variables.get("state", {})
    opt_state, ema = method.init_state(params), params
    out = []
    for i, (x, y) in enumerate(data):
        params, mstate, opt_state, ema, loss, gnorm = step_fn(
            params, mstate, opt_state, ema, jnp.asarray(i, jnp.int32),
            jnp.asarray(x), jnp.asarray(y))
        out.append(jax.device_get((params, opt_state, ema, loss, gnorm)))
    return out


def engine(method, n_devices, feature, model=None, variables=None):
    model = model or mlp()
    variables = variables or init(model)
    kw = dict(feature)
    if "trainable_mask" in kw:
        kw["trainable_mask"] = kw["trainable_mask"](variables["params"])
    return ShardedParameterStep(model, nn.ClassNLLCriterion(), method,
                                mesh_of(n_devices), variables, **kw)


def state_of(step):
    """(params tree, moments as trees, EMA tree) read through the wire
    format, whatever the engine carries."""
    n = step.n_real
    cut = lambda flat: jax.device_get(step.unravel(
        jnp.asarray(np.asarray(flat)[:n])))
    opt = {k: cut(v) for k, v in step.opt_state.items()}
    ema = cut(step.ema_flat) if step.ema_flat is not None else None
    return cut(step.flat_params), opt, ema


def assert_trees_equal(a, b, what):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


@pytest.mark.parametrize("bundle", [1, 3], ids=["single_step", "bundle3"])
@pytest.mark.parametrize("feature", sorted(FEATURES))
@pytest.mark.parametrize("method", sorted(METHODS))
def test_one_shard_trajectory_is_the_tree_reference(method, feature, bundle):
    """Five steps on a one-device mesh, as single steps or as bundles of
    3 + 2: parameters, moments, EMA and loss equal the plain tree
    reference BIT FOR BIT.  The gradient norm alone is read to 1e-6
    relative: a sum of per-leaf sums of squares, which XLA may associate
    otherwise in the reference's program than in the step's."""
    feat, data = FEATURES[feature], batches()
    model = mlp()
    variables, base_key = init(model), jax.random.PRNGKey(7)
    want = reference(model, METHODS[method](), variables, feat, data,
                     base_key)
    step = engine(METHODS[method](), 1, feat, model, variables)
    assert step.leaf_state
    step.set_step_seed(0)
    got, i = [], 0
    while i < len(data):
        xs = [step.shard_batch(x) for x, _ in data[i:i + bundle]]
        ys = [step.shard_batch(y) for _, y in data[i:i + bundle]]
        if bundle == 1:
            losses = [step.train_step_device(
                i, jax.random.fold_in(base_key, i), xs[0], ys[0])]
            gnorms = [None]
        else:
            losses, gnorms, _ = step.train_bundle_device(
                i, xs, ys, base_key=base_key)
        got.append((state_of(step), np.asarray(losses), gnorms))
        i += len(xs)
    for (state, losses, gnorms), end in zip(got, (np.cumsum(
            [len(g[1]) for g in got]))):
        params, opt, ema, _, _ = want[end - 1]
        assert_trees_equal(state[0], params, f"params after step {end}")
        assert_trees_equal(state[1], opt, f"moments after step {end}")
        if "ema_decay" in feat:
            assert_trees_equal(state[2], ema, f"EMA after step {end}")
        for j, loss in enumerate(losses):
            ref = want[end - len(losses) + j]
            assert loss == ref[3], f"loss of step {end - len(losses) + j}"
            if gnorms[0] is not None:
                np.testing.assert_allclose(np.asarray(gnorms)[j], ref[4],
                                           rtol=1e-6)


@pytest.mark.parametrize("method", sorted(METHODS))
def test_one_and_eight_devices_agree_at_one_global_batch(method):
    """The same model and the same global batches through the leaf-shaped
    program on one device and the flat ZeRO-1 cycle on eight."""
    data = batches()
    model = mlp(dropout=0.0)  # dropout draws per replica
    variables = init(model)
    feat = {"ema_decay": EMA_DECAY, "clip": GradientClipping(l2_norm=0.5)}
    ends = []
    for n in (1, 8):
        step = engine(METHODS[method](), n, feat, model, variables)
        assert step.leaf_state == (n == 1)
        losses = [float(step.train_step(i, jax.random.PRNGKey(i), x, y))
                  for i, (x, y) in enumerate(data)]
        ends.append((state_of(step), losses))
    (s1, l1), (s8, l8) = ends
    np.testing.assert_allclose(l1, l8, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(s1),
                    jax.tree_util.tree_leaves(s8)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("program", ["train", "bundle"])
def test_one_shard_step_assembles_no_parameter_sized_vector(program):
    """No ``concatenate`` or ``pad`` whose result has ``n_real`` elements,
    and no value of the flat vector's shape at all, in the jaxpr of a
    one-shard step with every feature on."""
    data = batches(2)
    feat = {"ema_decay": EMA_DECAY, "accum_steps": 2,
            "trainable_mask": FEATURES["trainable_mask"]["trainable_mask"],
            "clip": GradientClipping(l2_norm=0.5, constant_max=0.1)}
    step = engine(METHODS["adam"](), 1, feat)
    assert step.n_real == N_REAL
    xd, yd = step.shard_batch(data[0][0]), step.shard_batch(data[0][1])
    carried = (step._params, step._ema, step._opt, step.model_state)
    key = jax.random.PRNGKey(0)
    if program == "train":
        jaxpr = jax.make_jaxpr(step._train)(
            *carried, jnp.asarray(0, jnp.int32), key, xd, yd, step._mask)
    else:
        jaxpr = jax.make_jaxpr(step._build_bundle(2))(
            *carried, jnp.asarray(0, jnp.int32), key, (xd, xd), (yd, yd),
            step._mask)
    seen = set()
    for eqn in _eqns(jaxpr.jaxpr):
        seen.add(eqn.primitive.name)
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            assert shape != (N_REAL,), (eqn.primitive.name, shape)
            if eqn.primitive.name in ("concatenate", "pad"):
                assert int(np.prod(shape)) != N_REAL, eqn
    assert "dot_general" in seen  # the walk did reach the model


# The 8-device programs, lowered at the parent of the PR that gave one
# shard its own layout: the flat cycle must stay the program it was.  A
# PR that means to change that cycle regenerates these (the assertion
# prints what it lowered to).
_FLAT_CYCLE = {
    "sgd_momentum_plain": (
        "sgd_momentum", {}),
    "adam_accum_mask_ema": (
        "adam", {"accum_steps": 2, "ema_decay": EMA_DECAY,
                 "trainable_mask":
                     FEATURES["trainable_mask"]["trainable_mask"]}),
    "sgd_int8_buckets_clip": (
        "sgd_momentum", {"grad_comm": "int8", "param_comm": "int8",
                         "comm_bucket_bytes": 256, "quant_block": 32,
                         "clip": GradientClipping(l2_norm=0.5,
                                                  constant_max=0.1)}),
    "lars_layerwise": ("lars", {"clip": GradientClipping(l2_norm=0.5)}),
}
_FLAT_CYCLE_SHA256 = {
    "sgd_momentum_plain":
        "35880af2f66298ff76ea32c0cbaf1932aa3cae2ce39347807f2ad8142641ae4f",
    "adam_accum_mask_ema":
        "f354d7adbc260992c5db5558cdc83c739872096ee48544bedc3b2f23dfcc1c28",
    "sgd_int8_buckets_clip":
        "4662b4cf36b9e70735c3d8f829a59264892171036f328c05aa2b2f6bd7ecc62d",
    "lars_layerwise":
        "c2c11435b37b964e45c324922e0636c8a58f0253811610bedbd02e6736886eff",
}


@pytest.mark.parametrize("case", sorted(_FLAT_CYCLE))
def test_eight_device_step_lowers_to_the_text_it_lowered_to(case):
    name, feat = _FLAT_CYCLE[case]
    method = (optim.LarsSGD(learning_rate=0.05) if name == "lars"
              else METHODS[name]())
    step = engine(method, 8, feat)
    assert not step.leaf_state
    x, y = batches(1)[0]
    xd, yd = step.shard_batch(x), step.shard_batch(y)
    # by shapes only, through the public attributes: the arguments are
    # the ones the parent's programs took
    ema = (step.ema_flat if step.ema_flat is not None
           else jnp.zeros((1,), jnp.float32))
    mask = (jnp.ones((step.n_pad,), jnp.float32) if "trainable_mask" in feat
            else jnp.asarray(1.0, jnp.float32))
    args = (step.flat_params, ema, step.opt_state, step.model_state,
            jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0))
    text = step._train.lower(*args, xd, yd, mask).as_text()
    text += step._build_bundle(2).lower(
        *args, (xd, xd), (yd, yd), mask).as_text()
    got = hashlib.sha256(text.encode()).hexdigest()
    assert got == _FLAT_CYCLE_SHA256[case], (case, got)


# -- the disk format ---------------------------------------------------

def _fit(ckpt_dir, n_devices, steps):
    """Adam + EMA through the Optimizer on ``n_devices``, checkpointing
    every step into ``ckpt_dir`` (and resuming from it)."""
    Engine.reset()
    init_engine(data=n_devices)
    rs = np.random.RandomState(3)
    x = rs.randn(128, D).astype(np.float32)
    y = rs.randint(0, CLASSES, 128).astype(np.int32)
    opt = optim.Optimizer(mlp(), ArrayDataSet(x, y), nn.ClassNLLCriterion(),
                          batch_size=32, seed=5)
    opt.set_optim_method(optim.Adam(learning_rate=1e-2))
    opt.ema_decay = 0.9
    opt.set_end_when(optim.Trigger.max_iteration(steps))
    opt.set_checkpoint(str(ckpt_dir), optim.Trigger.several_iteration(1))
    return opt, opt.optimize()


@pytest.mark.parametrize("first, then", [(1, 8), (8, 1)],
                         ids=["one_then_eight", "eight_then_one"])
def test_checkpoint_crosses_device_counts(tmp_path, first, then):
    """Saved on ``first`` devices, resumed on ``then``: parameters,
    moments and EMA as saved, and the files hold the flat vector."""
    eng = _fit(tmp_path, first, 3)[1]._engine
    assert eng.leaf_state == (first == 1)
    saved = state_of(eng)
    latest = ckpt_mod.latest_checkpoint(str(tmp_path))
    flat, opt_state, _, driver, ema = ckpt_mod.load_checkpoint(
        latest, opt_state_template=eng.opt_template,
        model_state_template=eng.model_state_template)
    assert driver["iteration"] == 3
    assert flat.shape == ema.shape == (N_REAL,)
    assert {k: v.shape for k, v in opt_state.items()} == {
        "m": (N_REAL,), "v": (N_REAL,)}
    np.testing.assert_array_equal(flat, np.asarray(eng.flat_params))
    # nothing to train: the resumed state is the loaded one
    opt2, trained2 = _fit(tmp_path, then, 3)
    eng2 = trained2._engine
    assert eng2.leaf_state == (then == 1)
    assert opt2.final_state["iteration"] == 3
    assert_trees_equal(state_of(eng2), saved, f"{first} -> {then} devices")


@pytest.mark.parametrize("n_devices", [1, 8])
def test_wire_attributes_read_and_assign(n_devices):
    """``flat_params`` / ``opt_state`` / ``ema_flat``: today's shapes on
    either layout, and what is assigned is what is read and trained on."""
    model = mlp(dropout=0.0)
    variables = init(model)
    a = engine(METHODS["adam"](), n_devices, {"ema_decay": EMA_DECAY}, model,
               variables)
    b = engine(METHODS["adam"](), n_devices, {"ema_decay": EMA_DECAY}, model,
               variables)
    data = batches(4)
    for i, (x, y) in enumerate(data[:2]):
        a.train_step(i, jax.random.PRNGKey(i), x, y)
    assert a.flat_params.shape == a.ema_flat.shape == (a.n_pad,)
    assert tmap(jnp.shape, a.opt_state) == {"m": (a.n_pad,),
                                            "v": (a.n_pad,)}
    assert tmap(np.shape, a.opt_template) == tmap(jnp.shape, a.opt_state)
    want, _ = ravel_pytree(a.get_variables()["params"])
    np.testing.assert_array_equal(np.asarray(a.flat_params)[:a.n_real],
                                  np.asarray(want))
    # through the host, as a checkpoint would carry them
    b.flat_params = np.asarray(a.flat_params)
    b.ema_flat = np.asarray(a.ema_flat)
    b.opt_state = jax.device_get(a.opt_state)
    b.model_state = a.model_state
    assert_trees_equal(state_of(b), state_of(a), "assigned state")
    for i, (x, y) in enumerate(data[2:], start=2):
        la = a.train_step(i, jax.random.PRNGKey(i), x, y)
        lb = b.train_step(i, jax.random.PRNGKey(i), x, y)
        assert float(la) == float(lb)
    assert_trees_equal(state_of(b), state_of(a), "state after two steps")


def test_wire_assignment_refuses_a_vector_too_short():
    step = engine(METHODS["adam"](), 1, {})
    with pytest.raises(ValueError, match="808 parameters"):
        step.flat_params = np.zeros((N_REAL - 1,), np.float32)


def test_set_and_get_variables_on_one_device(tmp_path):
    _, trained = _fit(tmp_path, 1, 2)
    got = trained._engine.get_variables()
    assert tmap(jnp.shape, got["params"]) == tmap(
        jnp.shape, init(mlp())["params"])
    shifted = {"params": tmap(lambda p: p + 1.0, got["params"]),
               "state": got["state"]}
    trained.set_variables(shifted)
    again = trained._engine.get_variables()
    assert_trees_equal(again["params"], shifted["params"], "set -> get")
    assert trained.ema_variables is not None
    x = np.zeros((4, D), np.float32)
    np.testing.assert_allclose(
        np.asarray(trained.predict(x, batch_size=4)),
        np.asarray(mlp()(shifted, jnp.asarray(x))), rtol=1e-6)


def test_donated_step_leaves_the_callers_variables_alive():
    """The engine donates its own copy of the leaves, never the arrays
    the caller handed it (``benchmark/drivers/train.py`` reads them after
    ``optimize()``), nor the ones ``get_variables`` handed out."""
    model = mlp()
    variables = tmap(jnp.asarray, init(model))
    before = jax.device_get(variables["params"])
    step = engine(METHODS["adam"](), 1, {"ema_decay": EMA_DECAY}, model, variables)
    handed = step.get_variables()["params"]
    x, y = batches(1)[0]
    for i in range(2):
        step.train_step(i, jax.random.PRNGKey(i), x, y)
    assert_trees_equal(variables["params"], before, "the caller's leaves")
    assert_trees_equal(handed, before, "get_variables' leaves")
    moved = jax.tree_util.tree_leaves(tmap(
        lambda a, b: bool(np.any(np.asarray(a) != b)),
        step.get_variables()["params"], before))
    assert all(moved)


def test_optimizer_books_the_leaf_updates():
    """``train.updates`` counts every dispatched step, and
    ``train.leaf_updates`` those dispatched to a leaf-shaped program:
    all of them on one device, none on eight."""
    from bigdl_tpu.optim.metrics import global_metrics

    def delta(n_devices, tmp):
        reg = global_metrics()
        start = {k: reg.counters.get(k, 0)
                 for k in ("train.updates", "train.leaf_updates")}
        _fit(tmp, n_devices, 3)
        return {k: reg.counters.get(k, 0) - v for k, v in start.items()}

    import tempfile

    with tempfile.TemporaryDirectory() as t1, \
            tempfile.TemporaryDirectory() as t8:
        assert delta(1, t1) == {"train.updates": 3, "train.leaf_updates": 3}
        assert delta(8, t8) == {"train.updates": 3, "train.leaf_updates": 0}


@pytest.mark.parametrize("n_devices", [1, 8])
def test_dropping_the_engine_frees_it_at_once(n_devices):
    """No reference cycle through the engine: when the last name for it
    goes, its device buffers go with it, without waiting for the cyclic
    collector (a caller that drops a trained model and builds the next
    thing on the chip needs the room then, not a collection later)."""
    import gc
    import weakref

    step = engine(METHODS["adam"](), n_devices, {"ema_decay": EMA_DECAY})
    x, y = batches(1)[0]
    step.train_step(0, jax.random.PRNGKey(0), x, y)
    step.get_variables()
    step.predict_fn()(x)
    leaf = jax.tree_util.tree_leaves(step._params)[0]
    gone = weakref.ref(step)
    gc.collect()
    gc.disable()
    try:
        del step
        assert gone() is None
        del leaf
    finally:
        gc.enable()
