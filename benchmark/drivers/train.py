"""Training through the path a trainer runs: ``Optimizer(...).optimize()``
over ``DataSet.array``, host-fed by the program's own input pipeline.

The run ends on an elapsed-time trigger written here.  The program calls it
after every step; it keeps each step's loss, opens the window when the warm-up steps are done and closes it at the first
step that completes ``seconds`` later.  Both edges wait for the device
(``block_until_ready`` on the step's loss), so the window holds whole steps
and all of their time.

Traffic keys: ``family`` is in the configuration; ``batch_per_chip``,
``examples``, ``warmup_steps``, ``optimizer`` (name and arguments of a
class of ``bigdl_tpu.optim.optim_method``), ``loss_tolerance``; for a
sequence model ``seq_len``.
"""

import time

import numpy as np

from benchmark import harness


class WindowClock:
    """The ``end_when`` trigger: state in, "stop now" out."""

    def __init__(self, run, warmup_steps):
        self.run = run
        self.warmup = warmup_steps
        self.losses = []       # one per step, from step 1
        self.t_start = self.t_end = None
        self.steps = 0
        self._seen = 0

    def __call__(self, state):
        it = state["iteration"]
        if it <= self._seen or self.t_end is not None:
            return self.t_end is not None
        self._seen = it
        loss = state["loss"]
        opening = self.t_start is None and it >= self.warmup
        closing = (self.t_start is not None and
                   time.monotonic() - self.t_start >= self.run.seconds)
        if (opening or closing) and hasattr(loss, "block_until_ready"):
            loss.block_until_ready()
        self.losses.append(loss)
        if opening:
            self.t_start, self._it_start = self.run.window_start(), it
        elif closing:
            self.t_end = self.run.window_end()
            self.steps = it - self._it_start
        return self.t_end is not None


def run(run):
    import jax

    from bigdl_tpu.data.dataset import DataSet
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.optim import optim_method
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.trigger import Trigger

    cfg, traffic = run.config, run.traffic
    family = harness.load_module("families", cfg["family"])
    chips = run.device["count"]
    batch = traffic["batch_per_chip"] * chips

    t = time.monotonic()
    x, y = family.make_train_data(cfg, traffic, run.seed)
    t_data = time.monotonic() - t
    model = family.build_model(cfg)
    variables = harness.init_variables(model, run.seed, x[:1])
    first_params = jax.device_get(variables["params"])
    run.say(f"setup: data_s={t_data:.1f} examples={len(x)} batch={batch} "
            f"build_s={time.monotonic() - t - t_data:.1f}")

    opt = Optimizer(model, DataSet.array(x, y), CrossEntropyCriterion(),
                    batch_size=batch, seed=run.seed % (2 ** 31 - 1))
    method = dict(traffic["optimizer"])
    opt.set_optim_method(getattr(optim_method, method.pop("name"))(**method))
    opt.set_initial_variables(variables)
    clock = WindowClock(run, traffic["warmup_steps"])
    opt.set_end_when(Trigger(clock, "benchmark window"))
    opt.optimize()

    window_s = clock.t_end - clock.t_start
    losses = [float(l) for l in clock.losses]
    throughput = clock.steps * batch / window_s / chips

    # correctness, outside the window: the first step's loss against the
    # plain float32 reference on the same weights and the same batch (the
    # program's own plan says which examples step 1 saw)
    first = next(iter(opt.dataset.batches(batch, shuffle=True, seed=opt.seed,
                                          epoch=1)))
    ref = family.reference_loss(cfg, first_params,
                                np.asarray(first["input"]),
                                np.asarray(first["target"]))
    checks = {"losses_finite": bool(np.isfinite(losses).all()),
              "steps_in_window": clock.steps > 0,
              "first_loss_matches_reference":
                  abs(losses[0] - ref) <= traffic["loss_tolerance"]}
    run.say(f"steps={clock.steps} window_s={window_s:.3f} "
            f"warmup_steps={clock._it_start} loss_first={losses[0]:.6f} "
            f"reference_loss={ref:.6f} abs_diff={abs(losses[0] - ref):.2e} "
            f"tol={traffic['loss_tolerance']} loss_last={losses[-1]:.4f} "
            f"checks={checks}")
    return {
        "correct": all(checks.values()),
        "attempted": clock.steps, "failed": 0,
        "end_to_end": {"train_throughput": throughput},
        "evidence": {"window": {
            "seconds": window_s, "units": clock.steps, "chips": chips,
            "flops_per_unit": family.train_flops_per_sample(cfg, traffic)
            * batch}},
    }
