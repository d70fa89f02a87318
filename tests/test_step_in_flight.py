"""LocalOptimizer keeps one step in flight: step k+1 is dispatched before step
k's loss is fetched, nothing between two dispatches reads the device, and
whoever reads ``state["Loss"]`` (a trigger, a schedule, an aux point) still
gets the loss of the step just dispatched, at the cost of its own wait."""

import copy
import json
import os
import pickle
from contextlib import contextmanager

import jax
import numpy as np
import pytest

from bigdl_tpu import nn, observability as obs
from bigdl_tpu.dataset.dataset import DataSet
from bigdl_tpu.dataset.minibatch import MiniBatch
from bigdl_tpu.optim import (
    Optimizer, Plateau, SGD, SequentialSchedule, Step, TrainState, Trigger,
    Warmup, make_train_step)
from bigdl_tpu.utils import random as rnd

LR, BATCHES, BATCH = 0.05, 4, 8


# --------------------------------------------------------------- the table
def test_a_deferred_key_is_fetched_once_by_its_first_reader():
    calls = []
    st = TrainState(epoch=1, neval=1)
    st.defer("Loss", lambda: calls.append(1) or 0.5)
    # counters are read without it; the key is there
    assert st["neval"] == 1 and st.get("epoch") == 1 and "Loss" in st
    assert calls == []
    assert st["Loss"] == 0.5 and st.get("Loss") == 0.5 and calls == [1]
    # a later deferral replaces an unread one; a write replaces a deferral
    st.defer("Loss", lambda: calls.append(2) or 0.25)
    st.defer("Loss", lambda: calls.append(3) or 0.125)
    assert st.get("Loss") == 0.125 and calls == [1, 3]
    st.defer("Loss", lambda: calls.append(4) or 9.0)
    st.update(Loss=1.5, score=0.9)
    assert st["Loss"] == 1.5 and st["score"] == 0.9 and calls == [1, 3]
    st.defer("Loss", lambda: calls.append(5) or 9.0)
    st["Loss"] = 2.5
    assert dict(st)["Loss"] == 2.5 and calls == [1, 3]


@pytest.mark.parametrize("read", [
    dict, lambda s: {**s}, lambda s: dict(s.items()), lambda s: s.copy(),
    lambda s: dict(zip(s, s.values())), copy.deepcopy,
    lambda s: pickle.loads(pickle.dumps(s)), lambda s: json.loads(json.dumps(s)),
], ids=["dict", "unpack", "items", "copy", "values", "deepcopy", "pickle",
        "json"])
def test_a_read_of_the_whole_table_settles_it(read):
    st = TrainState(epoch=2, neval=7, Loss=3.0)
    st.defer("Loss", lambda: 0.5)
    assert read(st) == {"epoch": 2, "neval": 7, "Loss": 0.5}
    assert st._deferred == {}


def test_schedules_read_the_loss_only_where_they_use_it():
    read = []
    sgd = SGD(learning_rate=0.1, learning_rate_schedule=SequentialSchedule(2)
              .add(Warmup(0.01), 3).add(Plateau("Loss", epsilon=0.0), 100))
    for n, epoch, loss in [(1, 1, 4.0), (3, 2, 3.0), (4, 2, 2.0), (5, 2, 1.5),
                           (6, 3, 1.0)]:
        sgd.state.update(neval=n, epoch=epoch)
        sgd.state.defer("Loss", lambda loss=loss: read.append(loss) or loss)
        sgd.get_current_rate()
    # Warmup reads no loss; Plateau (its own epochs: two iterations each,
    # from its first) reads it once an epoch, where it compares it
    assert read == [2.0, 1.0]


# ----------------------------------------------------------------- the loop
class Recorder:
    """A train summary that keeps what it is given."""

    def __init__(self, fail_at=None):
        self.scalars, self.fail_at = [], fail_at

    def add_scalar(self, tag, value, step):
        self.scalars.append((tag, value, step))
        if tag == "Loss" and step == self.fail_at:
            raise RuntimeError(f"summary hook failed at step {step}")

    def of(self, tag):
        return [(s, v) for t, v, s in self.scalars if t == tag]


class Fed:
    """Pass-through stage of the input pipeline: which batches the loop was
    fed, in order."""

    def __init__(self):
        self.batches = []

    def __call__(self, it):
        for b in it:
            self.batches.append(b)
            yield b


def trigger(fn):
    class T(Trigger):
        def __call__(self, state):
            return fn(state)

    return T()


def build():
    rnd.set_seed(11)
    rng = np.random.RandomState(0)
    model = nn.Sequential(nn.Linear(6, 3), nn.Tanh(), nn.Linear(3, 2))
    batches = [MiniBatch(rng.randn(BATCH, 6).astype(np.float32),
                         rng.randn(BATCH, 2).astype(np.float32))
               for _ in range(BATCHES)]
    return model, batches


def optimizer(end_when, method=None, summary=None):
    model, batches = build()
    fed = Fed()
    opt = Optimizer(model=model, dataset=DataSet.array(batches).transform(fed),
                    criterion=nn.MSECriterion(), batch_size=BATCH,
                    end_when=end_when)
    opt.set_optim_method(method or SGD(learning_rate=LR))
    opt.set_train_summary(summary or Recorder())
    return opt, fed


def by_hand(fed, steps, method=None):
    """The steps as the parent's loop ran them, one at a time with the loss
    read after each: ``(losses, parameters after each step)``."""
    model, _ = build()
    method = method or SGD(learning_rate=LR)
    ts = make_train_step(model, nn.MSECriterion(), method)
    step = jax.jit(ts.step)
    params = jax.tree.map(jax.numpy.copy, model.params_dict())
    buffers, slots = model.buffers_dict(), ts.init_slots(params)
    losses, after = [], []
    for k, b in enumerate(fed.batches[:steps]):
        method.state["neval"] = k + 1
        loss, params, buffers, slots = step(
            params, buffers, slots, jax.numpy.asarray(b.inputs[0]),
            jax.numpy.asarray(b.targets[0]), ts.current_lrs(), rnd.next_key())
        losses.append(float(loss))
        after.append(jax.tree.map(np.asarray, params))
    return losses, after


@contextmanager
def traced():
    obs.trace.reset()
    out = {}
    yield out
    recs = [r for r in obs.trace.export() if r["name"] != "host/gc"]
    by_id = {r["span_id"]: r for r in recs}
    out["fences"] = {r["attrs"]["neval"]: r for r in recs
                     if r["name"] == "train/fence"}
    out["n_fences"] = sum(r["name"] == "train/fence" for r in recs)
    out["dispatch"] = {by_id[by_id[r["parent_id"]]["parent_id"]]["attrs"]
                       ["neval"]: r for r in recs
                       if r["name"] == "train/dispatch"}
    out["iterations"] = [r for r in recs if r["name"] == "train/iteration"]
    out["records"] = recs


def test_each_step_is_dispatched_before_the_loss_of_the_one_before_is_read():
    summary = Recorder()
    opt, fed = optimizer(Trigger.max_iteration(6), summary=summary)
    with traced() as t:
        opt.optimize()
    assert sorted(t["fences"]) == sorted(t["dispatch"]) == [1, 2, 3, 4, 5, 6]
    assert t["n_fences"] == 6                   # one wait a step
    for k in range(2, 7):
        # step k's enqueue has returned before anybody waits for step k-1,
        # inside step k's iteration
        fence, it = t["fences"][k - 1], t["iterations"][k - 1]
        assert t["dispatch"][k]["end_ns"] <= fence["start_ns"]
        assert fence["attrs"]["behind"] == 1
        assert fence["parent_id"] == it["span_id"] and \
            it["attrs"]["neval"] == k
    # nothing is dispatched behind the last step: it is fenced on the way out
    assert t["fences"][6]["attrs"]["behind"] == 0
    assert t["fences"][6]["parent_id"] is None
    # six losses, once each, in order, under their own step numbers,
    # bit-equal to the steps run one at a time
    losses, after = by_hand(fed, 6)
    assert summary.of("Loss") == list(zip(range(1, 7), losses))
    assert [s for s, _ in summary.of("Throughput")] == list(range(1, 7))
    assert summary.of("LearningRate") == [
        (k, float(np.float32(LR))) for k in range(1, 7)]
    state = opt.optim_method.state
    assert state["Loss"] == losses[-1] and state["neval"] == 7
    assert state._deferred == {}
    for a, b in zip(jax.tree.leaves(opt.model.params_dict()),
                    jax.tree.leaves(after[-1])):
        np.testing.assert_array_equal(np.asarray(a), b)
    # the same seed with the loss read at the top of every iteration: the
    # reader pays each wait before the next dispatch, and gets the same
    summary2 = Recorder()
    opt2, _ = optimizer(trigger(lambda s: (s.get("Loss"), s["neval"] > 6)[1]),
                        summary=summary2)
    with traced() as t2:
        opt2.optimize()
    assert summary2.of("Loss") == summary.of("Loss")
    assert [t2["fences"][k]["attrs"]["behind"] for k in range(1, 7)] == [0] * 6
    for k in range(1, 6):
        assert t2["fences"][k]["end_ns"] <= t2["dispatch"][k + 1]["start_ns"]


def test_min_loss_ends_after_the_step_it_ended_after_when_the_loop_fenced():
    opt, fed = optimizer(Trigger.max_iteration(12))
    opt.optimize()
    losses, _ = by_hand(fed, 12)
    # the first step from the third on whose loss is the lowest so far, and
    # a limit between it and the lowest before it
    j = next(k for k in range(2, 12) if losses[k] < min(losses[:k]))
    limit = (losses[j] + min(losses[:j])) / 2
    summary = Recorder()
    opt, _ = optimizer(Trigger.min_loss(limit), summary=summary)
    with traced() as t:
        opt.optimize()
    assert opt.optim_method.state["neval"] == j + 2     # j + 1 steps ran
    assert summary.of("Loss") == list(zip(range(1, j + 2), losses[:j + 1]))
    assert [t["fences"][k]["attrs"]["behind"]
            for k in range(1, j + 2)] == [0] * (j + 1)
    assert t["n_fences"] == j + 1


@pytest.mark.parametrize("aux", ["validation", "checkpoint"])
def test_an_aux_point_sees_its_own_steps_loss_and_parameters(aux, tmp_path):
    seen = {}

    def at_three(state):
        if state["neval"] - 1 == 3:
            seen["Loss"] = state["Loss"]
            return True
        return False

    def end(state):
        if state["neval"] - 1 == 3:
            # what the loop handed the model at the aux point
            seen["params"] = jax.tree.map(np.asarray, opt.model.params_dict())
        return state["neval"] > 5

    opt, fed = optimizer(trigger(end))
    if aux == "validation":
        opt.set_validation(trigger(at_three), None, [])
    else:
        opt.set_checkpoint(str(tmp_path), trigger(at_three))
    with traced() as t:
        opt.optimize()
    losses, after = by_hand(fed, 5)
    assert seen["Loss"] == losses[2]
    for a, b in zip(jax.tree.leaves(seen["params"]),
                    jax.tree.leaves(after[2])):
        np.testing.assert_array_equal(a, b)
    # that turn of the loop was synchronous, the others ran ahead
    assert [t["fences"][k]["attrs"]["behind"]
            for k in range(1, 6)] == [1, 1, 0, 1, 0]
    assert t["fences"][3]["end_ns"] <= t["dispatch"][4]["start_ns"]
    if aux == "checkpoint":
        from bigdl_tpu.optim import OptimMethod
        from bigdl_tpu.utils import file as bt_file

        method = OptimMethod.load(os.path.join(tmp_path, "optimMethod.3"))
        assert method.state["Loss"] == losses[2] and method.state["neval"] == 4
        saved = bt_file.load_module(os.path.join(tmp_path, "model.3"))
        for a, b in zip(jax.tree.leaves(saved.params_dict()),
                        jax.tree.leaves(after[2])):
            np.testing.assert_array_equal(np.asarray(a), b)


@contextmanager
def host_reads():
    """Every read of a device array's value by the host, with the span the
    reading thread was in."""
    from jax._src import array

    reads, saved = [], {}

    def note():
        cur = obs.trace.current()
        reads.append(cur.name if cur else None)

    def wrap(fn):
        def inner(self, *a, **kw):
            note()
            return fn(self, *a, **kw)
        return inner

    for name in ("__array__", "item"):
        saved[name] = getattr(array.ArrayImpl, name)
        setattr(array.ArrayImpl, name, wrap(saved[name]))
    saved["_value"] = array.ArrayImpl._value
    array.ArrayImpl._value = property(wrap(saved["_value"].fget))
    try:
        yield reads
    finally:
        for name, fn in saved.items():
            setattr(array.ArrayImpl, name, fn)


def test_nothing_between_two_dispatches_reads_the_device():
    method = SGD(learning_rate=LR, learning_rate_schedule=Step(3, 0.5))
    opt, _ = optimizer(Trigger.max_iteration(7), method=method)
    with host_reads() as reads:
        float(jax.numpy.ones(()))               # the patch sees a read
        assert reads == [None]
        opt.optimize()
    in_loop = [r for r in reads[1:] if r is not None]
    assert in_loop and set(in_loop) == {"train/fence"}, set(in_loop)


def test_the_rates_array_is_rebuilt_when_a_rate_changes_and_only_then():
    model, _ = build()
    method = SGD(learning_rate=LR, learning_rate_schedule=Step(3, 0.5))
    ts = make_train_step(model, nn.MSECriterion(), method)
    seen = []
    for n in range(1, 8):
        method.state["neval"] = n
        lrs, lr = ts.lrs_for_step()
        assert lr == float(np.float32(method.get_current_rate()))
        assert float(lrs[0]) == lr == float(ts.current_lrs()[0])
        if not seen or seen[-1] is not lrs:
            seen.append(lrs)
    # the rate halves after iterations 3 and 6
    assert [float(a[0]) for a in seen] == [
        float(np.float32(LR * f)) for f in (1, 0.5, 0.25)]
    # and in the loop the summary is handed the rate each step ran with
    summary = Recorder()
    opt, _ = optimizer(Trigger.max_iteration(7), method=SGD(
        learning_rate=LR, learning_rate_schedule=Step(3, 0.5)),
        summary=summary)
    opt.optimize()
    assert summary.of("LearningRate") == [
        (k, float(np.float32(LR * 0.5 ** ((k - 1) // 3))))
        for k in range(1, 8)]


@pytest.mark.parametrize("fault", ["summary_hook", "next_batch", "next_step"])
def test_an_exception_leaves_every_dispatched_loss_reported_once(
        fault, monkeypatch):
    joined = []
    # step 3's report raises, behind step 4's dispatch; or step 4 is in
    # flight when the fifth batch, or the fifth step's arguments, raise
    summary = Recorder(fail_at=3 if fault == "summary_hook" else None)
    opt, fed = optimizer(Trigger.max_iteration(9), summary=summary)
    if fault == "next_batch":
        def four(it):
            for k, b in enumerate(it):
                if k == 4:
                    raise RuntimeError("no fifth batch")
                yield b

        opt.dataset = opt.dataset.transform(four)
    elif fault == "next_step":
        from bigdl_tpu.optim.optimizer import TrainStep

        calls, lrs_for_step = [], TrainStep.lrs_for_step

        def fifth_fails(self):
            calls.append(1)
            if len(calls) == 5:
                raise RuntimeError("no fifth step")
            return lrs_for_step(self)

        monkeypatch.setattr(TrainStep, "lrs_for_step", fifth_fails)
    join = opt.join_pending_checkpoint
    opt.join_pending_checkpoint = lambda: (joined.append(1), join())[1]
    with pytest.raises(RuntimeError, match="summary hook|no fifth"):
        opt.optimize()
    losses, _ = by_hand(fed, 4)
    assert summary.of("Loss") == list(zip(range(1, 5), losses))
    assert joined == [1]
    state = opt.optim_method.state
    assert state["Loss"] == losses[-1] and state._deferred == {}


def test_fences_are_counted_by_behind():
    reg = obs.MetricRegistry()
    prev = obs.set_default_registry(reg)
    try:
        opt, _ = optimizer(Trigger.max_iteration(6))
        opt.optimize()
    finally:
        obs.set_default_registry(prev)
    fences = reg.get("bigdl_train_fences_total")
    assert fences.labels("1").get() == 5 and fences.labels("0").get() == 1
    assert reg.get("bigdl_train_step_seconds").get()[2] == 6
