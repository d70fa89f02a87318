"""Training cells: ``LocalOptimizer`` / ``DistriOptimizer`` ``.optimize()`` as
users call it, one call that holds set-up's first steps and the window.
Iterations are stamped from the train-summary hook, which both loops call
after their own ``float(loss)`` fence; the benchmark's ``end_when`` trigger
closes the window and, at the loop's top, copies out the parameters the
first steps produced, for the comparison with the plain reference."""

import gc
import importlib
import threading
import time

import numpy as np

from benchmark import compare, harness, loadgen

CHECK_STEPS = 3


class FeedLog:
    """Pass-through stage of the input pipeline that notes which of ``items``
    (records, or a pre-built mix's whole batches) the loop was fed, in order,
    for the first steps (nothing after that)."""

    def __init__(self, items, keep):
        self._index = {id(r): i for i, r in enumerate(items)}
        self.keep, self.rows = keep, []

    def __call__(self, it):
        for item in it:
            if len(self.rows) < self.keep:
                self.rows.append(self._index[id(item)])
            yield item


def feed(mix, x, y, batch):
    """What the optimizer's dataset holds, and how the first steps' batches
    are read back from the log of what it was fed. ``"prebuilt": true``: the
    mix's whole batches as ``MiniBatch``es of host arrays, made once here
    (the reference perf harness's way: no stacking on the timed path);
    otherwise one ``Sample`` a record, batched by the optimizer's own
    ``SampleToMiniBatch`` on its producer thread."""
    if mix.get("prebuilt"):
        from bigdl_tpu.dataset.minibatch import MiniBatch

        pairs = loadgen.whole_batches(x, y, batch)
        items = [MiniBatch(xb, yb.reshape(-1, 1)) for xb, yb in pairs]
        return items, CHECK_STEPS, lambda fed: [pairs[i] for i in fed]
    from bigdl_tpu.dataset.sample import Sample

    items = [Sample(x[i], y[i:i + 1]) for i in range(x.shape[0])]
    return items, CHECK_STEPS * batch, lambda fed: [
        (x[idx], y[idx]) for idx in np.asarray(fed).reshape(CHECK_STEPS, batch)]


class Stamps:
    """The train-summary hook: when each iteration ended, its loss, and the
    loop's own reading of its step (dispatch to loss fetched), which it hands
    over as records per second."""

    def __init__(self):
        self.at, self.losses, self.rates = [], [], []

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.at.append(time.monotonic())
            self.losses.append(float(value))
        elif tag == "Throughput":
            self.rates.append(float(value))


class Window:
    """``end_when``: true once the window's seconds have passed since the
    last warm-up iteration ended. At the loop's top it also copies to the
    host the parameters the model was handed after check steps 1 and 3."""

    def __init__(self, stamps, warmup, seconds, model, on_open=None):
        self.stamps, self.warmup, self.seconds = stamps, warmup, seconds
        self.model, self.on_open = model, on_open
        self.snapshots, self.t0 = {}, None

    def wants_params(self, state):
        """The validation trigger: makes the loop hand the model its fresh
        parameters after steps 1 and CHECK_STEPS (no dataset is set, so no
        validation runs)."""
        return state["neval"] - 1 in (1, CHECK_STEPS)

    def __call__(self, state):
        done = state["neval"] - 1
        if done in (1, CHECK_STEPS) and done not in self.snapshots:
            import jax

            self.snapshots[done] = jax.tree.map(
                np.asarray, self.model.params_dict())
        if self.t0 is None and len(self.stamps.at) >= self.warmup:
            self.t0 = self.stamps.at[self.warmup - 1]
            if self.on_open:
                self.on_open(self.t0)
        return self.t0 is not None and \
            time.monotonic() >= self.t0 + self.seconds


def as_trigger(fn):
    from bigdl_tpu.optim import Trigger

    class BenchTrigger(Trigger):
        def __call__(self, state):
            return fn(state)

    return BenchTrigger()


def build_optimizer(config, mix, model, dataset, chips, end_when):
    from bigdl_tpu import nn
    from bigdl_tpu import optim

    o = config["optimizer"]
    common = dict(model=model, dataset=dataset,
                  criterion=getattr(nn, o["criterion"])(),
                  batch_size=int(mix["batch_per_chip"]) * chips,
                  end_when=end_when)
    if o["kind"] == "local":
        opt = optim.LocalOptimizer(**common)
    elif o["kind"] == "distri":
        from bigdl_tpu.parallel import DistriOptimizer, Engine

        opt = DistriOptimizer(**common,
                              mesh=Engine.create_mesh([("data", chips)]),
                              **o.get("kwargs", {}))
    else:
        raise ValueError(o["kind"])
    r = o["recipe"]
    opt.set_optim_method(getattr(optim, r["method"])(
        learning_rate=r["learning_rate"], momentum=r["momentum"],
        dampening=r["dampening"], weight_decay=r["weight_decay"]))
    return opt


def first_steps(p0, after, losses, recipe):
    """What is compared of the first steps, from the parameters before and
    after them (host arrays): each loss, leaf norms of the first gradient as
    the optimizer used it, read back from the first update ((p0 - p1) over
    lr * (1 - dampening): both sides through the same float32 update), and
    leaf norms of the parameters' change after the last step."""
    scale = recipe["learning_rate"] * (1.0 - recipe["dampening"])
    f64 = lambda a: np.asarray(a).astype(np.float64)
    g1 = {k: (f64(p0[k]) - f64(after[0][k])) / scale for k in p0}
    delta = {k: f64(after[-1][k]) - f64(p0[k]) for k in p0}
    return {"losses": list(losses), "grad_norms": compare.leaf_norms(g1),
            "delta_norms": compare.leaf_norms(delta)}


def reference_steps(config, seed, batches, shards, adapter, reference,
                    cast=None):
    """The plain reference through the same first steps. ``cast`` = the
    lower-precision control (by hand or in a test): parameters, inputs and
    activations in it."""
    import jax
    import jax.numpy as jnp

    recipe = config["optimizer"]["recipe"]
    p0 = adapter.weights(config, seed)
    if cast is not None:
        p0 = jax.tree.map(lambda a: a.astype(cast), p0)
        batches = [(jnp.asarray(x).astype(cast), y) for x, y in batches]
    losses, after = reference.follow(p0, batches, recipe, shards, cast)
    return first_steps(p0, [after[0], after[-1]], losses, recipe)


def run(cell, seed, seconds, trace, t_start, trace_dir=None):
    from bigdl_tpu.dataset.dataset import DataSet
    config, mix = cell["config_json"], cell["traffic_json"]
    chips = cell["chips"]
    devs = harness.require_chips(chips)
    compiles = harness.CompileCount()
    adapter = importlib.import_module("benchmark.models." + config["adapter"])
    reference = importlib.import_module(
        "benchmark.reference." + config["reference"])
    x, y = loadgen.synthetic_dataset(mix, seed)
    harness.say(loadgen.describe_dataset(mix, x, chips))
    batch = int(mix["batch_per_chip"]) * chips
    warmup = int(mix["warmup_iterations"])
    items, keep, first_batches = feed(mix, x, y, batch)
    fed = FeedLog(items, keep)

    model = adapter.build(config, seed)
    stamps = Stamps()
    traced = {}

    watch = harness.HostWatch()

    def open_window(t0):
        watch.start()
        if not trace:
            return

        traced["thread"] = threading.Thread(
            target=harness.capture_trace, daemon=True,
            args=(trace_dir, t0, seconds, mix, traced))
        traced["thread"].start()

    window = Window(stamps, warmup, seconds, model, open_window)
    opt = build_optimizer(config, mix, model,
                          DataSet.array(items).transform(fed), chips,
                          as_trigger(window))
    opt.set_train_summary(stamps)
    opt.set_validation(as_trigger(window.wants_params), None, [])
    opt.optimize()
    watch.stop()
    if "thread" in traced:
        traced.pop("thread").join()
    t0, t1 = window.t0, window.t0 + seconds
    setup_s = time.perf_counter() - t_start - (time.monotonic() - t0)
    inside = [t for t in stamps.at if t0 < t <= t1]
    walls = [(b - a) * 1e3 for a, b in zip(stamps.at, stamps.at[1:])
             if t0 <= a and b <= t1]
    compiles_in_window = compiles.between(t0, t1)
    # the window's longest iterations: when each ended, its wall, and the
    # loop's own step inside it; the rest is the wait for data and bookkeeping
    slow = sorted(((round(b - t0, 2), round((b - a) * 1e3, 1),
                    round(batch / stamps.rates[i + 1] * 1e3, 1))
                   for i, (a, b) in enumerate(zip(stamps.at, stamps.at[1:]))
                   if t0 <= a and b <= t1 and i + 1 < len(stamps.rates)),
                  key=lambda r: -r[1])[:5]
    device = harness.device_block(devs)
    prog = first_steps(
        adapter.weights(config, seed),
        [adapter.named(window.snapshots[k], config) for k in (1, CHECK_STEPS)],
        stamps.losses[:CHECK_STEPS], config["optimizer"]["recipe"])
    prog["window_losses"] = stamps.losses[warmup:]
    record = {"programs": config["programs"], "sizes": config["sizes"],
              "scopes": config.get("scopes", {}),
              "batch_per_chip": int(mix["batch_per_chip"]),
              "iteration_ms": walls}
    # the reference needs the device's memory: the program's state goes
    del opt, model, window.model
    gc.collect()
    batches = first_batches(fed.rows)
    ref = reference_steps(config, seed, batches, chips, adapter, reference)
    check_rows = compare.training_rows(prog, ref, compiles_in_window,
                                       config["check"]["limits"])
    for r in check_rows:
        harness.say({"check": r})
    harness.say({"window": {
        "iterations": len(inside), "setup_s": setup_s,
        "iteration_ms_median": harness.median(walls) if walls else None,
        "iteration_ms_median_while_traced": harness.median(
            [(b - a) * 1e3 for a, b in zip(stamps.at, stamps.at[1:])
             if traced["a"] <= a and b <= traced["b"]]) if "b" in traced
        else None,
        "losses_first": stamps.losses[:CHECK_STEPS],
        "reference_losses": ref["losses"],
        "loss_last": stamps.losses[-1], "compiles": compiles.summary()}})
    harness.say({"host": dict(
        watch.summary(t0, t1),
        step_ms_median=harness.median(
            [batch / r * 1e3 for r in stamps.rates[warmup:]]),
        slowest_iterations_end_s_wall_ms_step_ms=slow)})
    out = {"correct": all(r["ok"] for r in check_rows),
           "attempted": len(inside), "failed": 0, "device": device,
           "checks": check_rows,
           "values": {"train_samples_per_s":
                      len(inside) * batch / seconds / chips,
                      "setup_s": setup_s},
           "record": record,
           # for the tools' control readings and the tests
           "compared": {"batches": batches, "reference": ref,
                        "window_losses": prog["window_losses"]}}
    if trace:
        out["trace"] = (traced["b"] - traced["a"]) if "b" in traced else None
    return out


def control_rows(config, seed, chips, compared, cast):
    """The precision control's rows: the reference in ``cast`` put in the
    program's place over a run's own first batches (``out["compared"]``)."""
    adapter = importlib.import_module("benchmark.models." + config["adapter"])
    reference = importlib.import_module(
        "benchmark.reference." + config["reference"])
    low = reference_steps(config, seed, compared["batches"], chips, adapter,
                          reference, cast)
    return compare.training_rows(
        {**low, "window_losses": compared["window_losses"]},
        compared["reference"], 0, config["check"]["limits"])
