"""Optimization methods (SGD family) and learning-rate schedules.

Reference: optim/OptimMethod.scala:28 + one file per method (SGD.scala with
its 10+ nested ``LearningRateSchedule``s at optim/SGD.scala:200-435, Adam,
Adagrad, Adadelta, Adamax, RMSprop, Ftrl, LBFGS). The reference mutates a
flat parameter tensor in place; the TPU-native design splits each method into

- a **pure pytree transform** ``step(params, grads, slots, lr) ->
  (new_params, new_slots)`` — jit/pjit-safe, works on arbitrary pytrees so
  the same code updates replicated params under ``jit`` or a ZeRO-style
  sharded slice under ``shard_map`` (≙ the reference updating only the owned
  partition, optim/DistriOptimizer.scala:343-373);
- a host-side **schedule** computing the scalar learning rate per iteration
  from the state table (epoch/neval/score — the keys of SURVEY.md Appendix
  B.7), passed into the jitted step as an argument so LR changes never
  trigger recompiles.

The flat ``optimize(feval, x)`` API of the reference is kept for parity and
for LBFGS-style line-search methods that must call feval repeatedly.
"""

from __future__ import annotations

import math
import pickle
from collections import ChainMap
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from bigdl_tpu.utils.config_capture import ConfigCaptured


def _tree_map(f, *trees):
    return jax.tree.map(f, *trees)


# ---------------------------------------------------------------------------
# Learning-rate schedules (reference: optim/SGD.scala:200-435)
# ---------------------------------------------------------------------------
class LearningRateSchedule(ConfigCaptured):
    def rate(self, method: "OptimMethod", state: Dict[str, Any]) -> float:
        raise NotImplementedError


class Default(LearningRateSchedule):
    """lr / (1 + neval * learningRateDecay) (SGD.Default)."""

    def rate(self, method, state):
        n = state.get("neval", 1) - 1
        return method.learning_rate / (1 + n * method.learning_rate_decay)


class Poly(LearningRateSchedule):
    """lr * (1 - neval/maxIteration)^power (SGD.Poly)."""

    def __init__(self, power: float, max_iteration: int):
        self.power = power
        self.max_iteration = max_iteration

    def rate(self, method, state):
        n = state.get("neval", 1) - 1
        if n >= self.max_iteration:
            return 0.0
        return method.learning_rate * (1 - n / self.max_iteration) ** self.power


class Step(LearningRateSchedule):
    """lr * gamma^(floor(neval / stepSize)) (SGD.Step)."""

    def __init__(self, step_size: int, gamma: float):
        self.step_size = step_size
        self.gamma = gamma

    def rate(self, method, state):
        n = state.get("neval", 1) - 1
        return method.learning_rate * self.gamma ** (n // self.step_size)


class MultiStep(LearningRateSchedule):
    def __init__(self, step_sizes, gamma: float):
        self.step_sizes = list(step_sizes)
        self.gamma = gamma

    def rate(self, method, state):
        n = state.get("neval", 1) - 1
        k = sum(1 for s in self.step_sizes if n >= s)
        return method.learning_rate * self.gamma ** k


class EpochStep(LearningRateSchedule):
    def __init__(self, step_size: int, gamma: float):
        self.step_size = step_size
        self.gamma = gamma

    def rate(self, method, state):
        e = state.get("epoch", 1)
        return method.learning_rate * self.gamma ** ((e - 1) // self.step_size)


class EpochDecay(LearningRateSchedule):
    def __init__(self, decay_fn):
        self.decay_fn = decay_fn

    def rate(self, method, state):
        e = state.get("epoch", 1)
        return method.learning_rate * 0.1 ** self.decay_fn(e)


class Exponential(LearningRateSchedule):
    def __init__(self, decay_step: int, decay_rate: float, staircase: bool = False):
        self.decay_step = decay_step
        self.decay_rate = decay_rate
        self.staircase = staircase

    def rate(self, method, state):
        n = state.get("neval", 1) - 1
        p = n / self.decay_step
        if self.staircase:
            p = math.floor(p)
        return method.learning_rate * self.decay_rate ** p


class Plateau(LearningRateSchedule):
    """Reduce LR when the monitored score stops improving (SGD.Plateau)."""

    def __init__(self, monitor: str = "score", factor: float = 0.1,
                 patience: int = 10, mode: str = "min", epsilon: float = 1e-4,
                 cooldown: int = 0, min_lr: float = 0.0):
        self.monitor = monitor
        self.factor = factor
        self.patience = patience
        self.mode = mode
        self.epsilon = epsilon
        self.cooldown = cooldown
        self.min_lr = min_lr
        self._lr: Optional[float] = None
        self._best: Optional[float] = None
        self._wait = 0
        self._cooldown_left = 0
        self._last_epoch = -1

    def _improved(self, cur):
        if self._best is None:
            return True
        if self.mode == "min":
            return cur < self._best - self.epsilon
        return cur > self._best + self.epsilon

    def rate(self, method, state):
        if self._lr is None:
            self._lr = method.learning_rate
        epoch = state.get("epoch", 1)
        # the monitored value is read once an epoch, where it is used: a
        # read of ``Loss`` waits for the step in flight (TrainState)
        cur = state.get(self.monitor) if epoch != self._last_epoch else None
        if cur is not None:
            self._last_epoch = epoch
            if self._cooldown_left > 0:
                self._cooldown_left -= 1
                self._wait = 0
            if self._improved(cur):
                self._best = cur
                self._wait = 0
            elif self._cooldown_left <= 0:
                self._wait += 1
                if self._wait >= self.patience:
                    self._lr = max(self._lr * self.factor, self.min_lr)
                    self._cooldown_left = self.cooldown
                    self._wait = 0
        return self._lr


class Warmup(LearningRateSchedule):
    """Linear ramp by delta per iteration (SGD.Warmup); chain via SequentialSchedule."""

    def __init__(self, delta: float):
        self.delta = delta

    def rate(self, method, state):
        n = state.get("neval", 1) - 1
        return method.learning_rate + self.delta * n


class SequentialSchedule(LearningRateSchedule):
    """Concatenate schedules, each active for a number of iterations
    (SGD.SequentialSchedule). The ResNet recipe = Warmup then Poly/MultiStep."""

    def __init__(self, iteration_per_epoch: int = 1):
        self.schedules = []  # (schedule, n_iterations)
        self.iteration_per_epoch = iteration_per_epoch

    def add(self, schedule: LearningRateSchedule, max_iteration: int) -> "SequentialSchedule":
        self.schedules.append((schedule, max_iteration))
        return self

    def rate(self, method, state):
        n = state.get("neval", 1) - 1
        offset = 0
        for sched, cnt in self.schedules:
            if n < offset + cnt or (sched, cnt) == self.schedules[-1]:
                # its own counters over the table's other keys, which are
                # read through and not copied (TrainState: a copy settles)
                sub = ChainMap({
                    "neval": n - offset + 1,
                    "epoch": (n - offset) // max(self.iteration_per_epoch, 1) + 1,
                }, state)
                return sched.rate(method, sub)
            offset += cnt
        return method.learning_rate


class EpochSchedule(LearningRateSchedule):
    """Per-epoch-range regimes (SGD.EpochSchedule / Regime)."""

    def __init__(self, regimes):
        """regimes: list of (start_epoch, end_epoch, lr)."""
        self.regimes = list(regimes)

    def rate(self, method, state):
        e = state.get("epoch", 1)
        for start, end, lr in self.regimes:
            if start <= e <= end:
                return lr
        return method.learning_rate


class CosineDecay(LearningRateSchedule):
    """Cosine annealing to ``min_lr`` over ``decay_iterations`` (the
    modern transformer default; no reference analog — its newest schedule
    era was Poly/MultiStep). Anneals from ``peak_lr`` when given, else
    from the method's base LR. The canonical warmup+cosine::

        peak, w = 1.0, 10
        seq = (SequentialSchedule()
               .add(Warmup((peak - base) / w), w)     # base -> peak
               .add(CosineDecay(990, peak_lr=peak), 990))  # peak -> 0

    (without peak_lr the decay would restart from the BASE lr — a cliff
    at the warmup boundary)."""

    def __init__(self, decay_iterations: int, min_lr: float = 0.0,
                 peak_lr: Optional[float] = None):
        if decay_iterations < 1:
            raise ValueError("decay_iterations must be >= 1")
        self.decay_iterations = decay_iterations
        self.min_lr = min_lr
        self.peak_lr = peak_lr

    def rate(self, method, state):
        n = min(state.get("neval", 1) - 1, self.decay_iterations)
        cos = 0.5 * (1.0 + math.cos(math.pi * n / self.decay_iterations))
        peak = self.peak_lr if self.peak_lr is not None \
            else method.learning_rate
        return self.min_lr + (peak - self.min_lr) * cos


class NaturalExp(LearningRateSchedule):
    def __init__(self, decay_step: int, gamma: float):
        self.decay_step = decay_step
        self.gamma = gamma

    def rate(self, method, state):
        n = state.get("neval", 1) - 1
        return method.learning_rate * math.exp(-self.gamma * (n // self.decay_step))


# ---------------------------------------------------------------------------
# The state table
# ---------------------------------------------------------------------------
class TrainState(dict):
    """The state table of an ``OptimMethod`` (epoch / neval / Loss / score /
    recordsProcessedThisEpoch, Appendix B.7): a dict in which a key can be
    *deferred*. ``defer(key, fetch)`` makes the next read of ``key`` call
    ``fetch()`` and keep what it returns, so a value that is still on the
    device costs its wait only where somebody reads it: the train loop defers
    ``Loss`` of the step it has just dispatched, a trigger or schedule that
    reads it gets that step's loss as it always did, and a loop whose
    triggers read only counters runs a step ahead of the device.

    Reads by key (``[]``, ``get``) settle that key; reads of the whole table
    (``items``, ``values``, ``copy``, iteration into another dict, pickling)
    settle every key; a write through ``[]`` or ``update`` replaces a
    deferred value. It pickles as its settled contents."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._deferred = {}

    def defer(self, key, fetch) -> None:
        self._deferred[key] = fetch
        super().setdefault(key, None)     # the key is there for `in`

    def settle(self, key=None) -> None:
        """Fetch ``key`` if it is deferred; every deferred key for None."""
        keys = list(self._deferred) if key is None else \
            [key] if key in self._deferred else ()
        for k in keys:
            super().__setitem__(k, self._deferred.pop(k)())

    def __getitem__(self, key):
        self.settle(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        self.settle(key)
        return super().get(key, default)

    def __setitem__(self, key, value):
        self._deferred.pop(key, None)
        super().__setitem__(key, value)

    def update(self, *args, **kw):
        for k, v in dict(*args, **kw).items():
            self[k] = v

    def __iter__(self):
        # overridden so that dict(state) and {**state} copy through
        # __getitem__ and not from the raw storage under it
        return super().__iter__()

    def items(self):
        self.settle()
        return super().items()

    def values(self):
        self.settle()
        return super().values()

    def copy(self):
        return TrainState(self.items())

    def __reduce__(self):
        return TrainState, (dict(self.items()),)


# ---------------------------------------------------------------------------
# OptimMethod base
# ---------------------------------------------------------------------------
class OptimMethod(ConfigCaptured):
    """Reference: optim/OptimMethod.scala:28. State-table keys are API
    (epoch/neval/Loss/score/recordsProcessedThisEpoch, Appendix B.7)."""

    def __init__(self, learning_rate: float = 1e-3):
        self.learning_rate = float(learning_rate)
        self.state: Dict[str, Any] = TrainState(epoch=1, neval=1)
        self.schedule: Optional[LearningRateSchedule] = None

    # ---------------------------------------------------------- pure pytree
    def init_slots(self, params) -> Any:
        """Per-parameter optimizer slot pytree (momentum buffers etc.)."""
        return {}

    def step(self, params, grads, slots, lr):
        """Pure update: (new_params, new_slots). lr is a scalar (host-scheduled)."""
        raise NotImplementedError

    # ------------------------------------------------------------ host side
    def get_current_rate(self) -> float:
        if self.schedule is not None:
            return self.schedule.rate(self, self.state)
        return self.learning_rate

    def get_learning_rate(self) -> float:
        return self.get_current_rate()

    def update_state(self, **kv) -> None:
        self.state.update(kv)

    # ------------------------------------------------- flat API (parity)
    def optimize(self, feval, x):
        """Reference-style ``optimize(feval, parameter)`` on a flat tensor.

        feval(x) -> (loss, grad). Returns (new_x, [loss])."""
        loss, grad = feval(x)
        if not hasattr(self, "_flat_slots"):
            self._flat_slots = self.init_slots(x)
        lr = self.get_current_rate()
        x, self._flat_slots = self.step(x, grad, self._flat_slots, lr)
        self.state["neval"] = self.state.get("neval", 1) + 1
        return x, [float(loss)]

    # --------------------------------------------------------- persistence
    def save(self, path: str, overwrite: bool = False) -> "OptimMethod":
        from bigdl_tpu.utils import file as bt_file

        if bt_file.exists(path) and not overwrite:
            raise FileExistsError(path)
        with bt_file.open_file(path, "wb") as f:
            pickle.dump(self, f)
        return self

    @staticmethod
    def load(path: str) -> "OptimMethod":
        from bigdl_tpu.utils import file as bt_file

        with bt_file.open_file(path, "rb") as f:
            return pickle.load(f)

    def clear_history(self) -> None:
        self.state = TrainState(epoch=1, neval=1)
        if hasattr(self, "_flat_slots"):
            del self._flat_slots


def _apply_weight_decay(grads, params, wd: float):
    if wd:
        return _tree_map(lambda g, p: g + wd * p, grads, params)
    return grads


# ---------------------------------------------------------------------------
# Methods
# ---------------------------------------------------------------------------
class SGD(OptimMethod):
    """SGD with momentum/dampening/nesterov/weight decay + schedules
    (reference: optim/SGD.scala)."""

    def __init__(self, learning_rate: float = 1e-3, learning_rate_decay: float = 0.0,
                 weight_decay: float = 0.0, momentum: float = 0.0,
                 dampening: Optional[float] = None, nesterov: bool = False,
                 learning_rate_schedule: Optional[LearningRateSchedule] = None):
        super().__init__(learning_rate)
        self.learning_rate_decay = float(learning_rate_decay)
        self.weight_decay = float(weight_decay)
        self.momentum = float(momentum)
        self.dampening = float(momentum if dampening is None else dampening)
        if nesterov and (momentum <= 0 or self.dampening != 0):
            raise ValueError("nesterov requires momentum > 0 and dampening = 0")
        self.nesterov = nesterov
        self.schedule = learning_rate_schedule or Default()

    def init_slots(self, params):
        if self.momentum:
            return {"velocity": _tree_map(jnp.zeros_like, params)}
        return {}

    def step(self, params, grads, slots, lr):
        grads = _apply_weight_decay(grads, params, self.weight_decay)
        if self.momentum:
            v = _tree_map(
                lambda vel, g: self.momentum * vel + (1 - self.dampening) * g,
                slots["velocity"], grads)
            if self.nesterov:
                upd = _tree_map(lambda g, vel: g + self.momentum * vel, grads, v)
            else:
                upd = v
            new_params = _tree_map(lambda p, u: p - lr * u, params, upd)
            return new_params, {"velocity": v}
        new_params = _tree_map(lambda p, g: p - lr * g, params, grads)
        return new_params, slots


class Adam(OptimMethod):
    """Reference: optim/Adam.scala."""

    def __init__(self, learning_rate: float = 1e-3, learning_rate_decay: float = 0.0,
                 beta1: float = 0.9, beta2: float = 0.999, epsilon: float = 1e-8,
                 weight_decay: float = 0.0):
        super().__init__(learning_rate)
        self.learning_rate_decay = float(learning_rate_decay)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.weight_decay = float(weight_decay)
        self.schedule = Default()

    def init_slots(self, params):
        return {"m": _tree_map(jnp.zeros_like, params),
                "v": _tree_map(jnp.zeros_like, params),
                "t": jnp.zeros((), jnp.int32)}

    def step(self, params, grads, slots, lr):
        grads = _apply_weight_decay(grads, params, self.weight_decay)
        t = slots["t"] + 1
        m = _tree_map(lambda m_, g: self.beta1 * m_ + (1 - self.beta1) * g,
                      slots["m"], grads)
        v = _tree_map(lambda v_, g: self.beta2 * v_ + (1 - self.beta2) * g * g,
                      slots["v"], grads)
        tf = t.astype(jnp.float32)
        c1 = 1 - self.beta1 ** tf
        c2 = 1 - self.beta2 ** tf
        new_params = _tree_map(
            lambda p, m_, v_: p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + self.epsilon),
            params, m, v)
        return new_params, {"m": m, "v": v, "t": t}


class AdamW(Adam):
    """Decoupled weight decay (beyond-parity convenience; decay applied to
    the parameter, not the gradient)."""

    def step(self, params, grads, slots, lr):
        wd = self.weight_decay
        self.weight_decay = 0.0
        try:
            new_params, new_slots = super().step(params, grads, slots, lr)
        finally:
            self.weight_decay = wd
        if wd:
            new_params = _tree_map(lambda np_, p: np_ - lr * wd * p, new_params, params)
        return new_params, new_slots


class Adagrad(OptimMethod):
    """Reference: optim/Adagrad.scala."""

    def __init__(self, learning_rate: float = 1e-3, learning_rate_decay: float = 0.0,
                 weight_decay: float = 0.0, epsilon: float = 1e-10):
        super().__init__(learning_rate)
        self.learning_rate_decay = float(learning_rate_decay)
        self.weight_decay = float(weight_decay)
        self.epsilon = epsilon
        self.schedule = Default()

    def init_slots(self, params):
        return {"accum": _tree_map(jnp.zeros_like, params)}

    def step(self, params, grads, slots, lr):
        grads = _apply_weight_decay(grads, params, self.weight_decay)
        accum = _tree_map(lambda a, g: a + g * g, slots["accum"], grads)
        new_params = _tree_map(
            lambda p, g, a: p - lr * g / (jnp.sqrt(a) + self.epsilon),
            params, grads, accum)
        return new_params, {"accum": accum}


class Adadelta(OptimMethod):
    """Reference: optim/Adadelta.scala (no learning rate; rho/epsilon)."""

    def __init__(self, decay_rate: float = 0.9, epsilon: float = 1e-10):
        super().__init__(1.0)
        self.rho = decay_rate
        self.epsilon = epsilon

    def init_slots(self, params):
        return {"accum": _tree_map(jnp.zeros_like, params),
                "accum_update": _tree_map(jnp.zeros_like, params)}

    def step(self, params, grads, slots, lr):
        rho, eps = self.rho, self.epsilon
        accum = _tree_map(lambda a, g: rho * a + (1 - rho) * g * g,
                          slots["accum"], grads)
        delta = _tree_map(
            lambda au, a, g: jnp.sqrt(au + eps) / jnp.sqrt(a + eps) * g,
            slots["accum_update"], accum, grads)
        accum_update = _tree_map(lambda au, d: rho * au + (1 - rho) * d * d,
                                 slots["accum_update"], delta)
        new_params = _tree_map(lambda p, d: p - lr * d, params, delta)
        return new_params, {"accum": accum, "accum_update": accum_update}


class Adamax(OptimMethod):
    """Reference: optim/Adamax.scala."""

    def __init__(self, learning_rate: float = 2e-3, beta1: float = 0.9,
                 beta2: float = 0.999, epsilon: float = 1e-38):
        super().__init__(learning_rate)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def init_slots(self, params):
        return {"m": _tree_map(jnp.zeros_like, params),
                "u": _tree_map(jnp.zeros_like, params),
                "t": jnp.zeros((), jnp.int32)}

    def step(self, params, grads, slots, lr):
        t = slots["t"] + 1
        m = _tree_map(lambda m_, g: self.beta1 * m_ + (1 - self.beta1) * g,
                      slots["m"], grads)
        u = _tree_map(lambda u_, g: jnp.maximum(self.beta2 * u_, jnp.abs(g) + self.epsilon),
                      slots["u"], grads)
        c1 = 1 - self.beta1 ** t.astype(jnp.float32)
        new_params = _tree_map(lambda p, m_, u_: p - (lr / c1) * m_ / u_, params, m, u)
        return new_params, {"m": m, "u": u, "t": t}


class RMSprop(OptimMethod):
    """Reference: optim/RMSprop.scala."""

    def __init__(self, learning_rate: float = 1e-2, learning_rate_decay: float = 0.0,
                 decay_rate: float = 0.99, epsilon: float = 1e-8):
        super().__init__(learning_rate)
        self.learning_rate_decay = float(learning_rate_decay)
        self.rho = decay_rate
        self.epsilon = epsilon
        self.schedule = Default()

    def init_slots(self, params):
        return {"accum": _tree_map(jnp.zeros_like, params)}

    def step(self, params, grads, slots, lr):
        accum = _tree_map(lambda a, g: self.rho * a + (1 - self.rho) * g * g,
                          slots["accum"], grads)
        new_params = _tree_map(
            lambda p, g, a: p - lr * g / (jnp.sqrt(a) + self.epsilon),
            params, grads, accum)
        return new_params, {"accum": accum}


class Ftrl(OptimMethod):
    """Follow-the-regularized-leader (reference: optim/Ftrl.scala)."""

    def __init__(self, learning_rate: float = 1e-3, learning_rate_power: float = -0.5,
                 initial_accumulator_value: float = 0.1,
                 l1_regularization_strength: float = 0.0,
                 l2_regularization_strength: float = 0.0,
                 l2_shrinkage_regularization_strength: float = 0.0):
        super().__init__(learning_rate)
        self.lr_power = learning_rate_power
        self.init_accum = initial_accumulator_value
        self.l1 = l1_regularization_strength
        self.l2 = l2_regularization_strength
        self.l2_shrinkage = l2_shrinkage_regularization_strength

    def init_slots(self, params):
        return {"accum": _tree_map(lambda p: jnp.full_like(p, self.init_accum), params),
                "linear": _tree_map(jnp.zeros_like, params)}

    def step(self, params, grads, slots, lr):
        lp = self.lr_power

        def upd(p, g, a, l):
            g_shrunk = g + 2 * self.l2_shrinkage * p
            new_a = a + g * g
            sigma = (new_a ** -lp - a ** -lp) / lr
            new_l = l + g_shrunk - sigma * p
            quad = new_a ** -lp / lr + 2 * self.l2
            l_clipped = jnp.clip(new_l, -self.l1, self.l1)
            new_p = (l_clipped - new_l) / quad
            if self.l1 == 0.0:
                new_p = -new_l / quad
            return new_p, new_a, new_l

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        flat_a = jax.tree.leaves(slots["accum"])
        flat_l = jax.tree.leaves(slots["linear"])
        outs = [upd(p, g, a, l) for p, g, a, l in zip(flat_p, flat_g, flat_a, flat_l)]
        new_params = jax.tree.unflatten(treedef, [o[0] for o in outs])
        accum = jax.tree.unflatten(treedef, [o[1] for o in outs])
        linear = jax.tree.unflatten(treedef, [o[2] for o in outs])
        return new_params, {"accum": accum, "linear": linear}


class LBFGS(OptimMethod):
    """Limited-memory BFGS over the flat ``optimize(feval, x)`` API
    (reference: optim/LBFGS.scala + LineSearch.scala). Used for small
    full-batch problems; not part of the jitted minibatch path."""

    def __init__(self, max_iter: int = 20, max_eval: Optional[float] = None,
                 tolerance_fun: float = 1e-5, tolerance_x: float = 1e-9,
                 n_correction: int = 100, learning_rate: float = 1.0,
                 line_search: bool = False):
        super().__init__(learning_rate)
        self.max_iter = max_iter
        self.max_eval = max_eval if max_eval is not None else max_iter * 1.25
        self.tol_fun = tolerance_fun
        self.tol_x = tolerance_x
        self.n_correction = n_correction
        self.line_search = line_search

    def step(self, params, grads, slots, lr):  # pragma: no cover - flat only
        return _tree_map(lambda p, g: p - lr * g, params, grads), slots

    def optimize(self, feval, x):
        x = jnp.asarray(x)
        loss, g = feval(x)
        losses = [float(loss)]
        old_dirs, old_steps = [], []
        hdiag = 1.0
        prev_g = g
        d = -g
        t = min(1.0, 1.0 / float(jnp.sum(jnp.abs(g)) + 1e-10)) * self.learning_rate
        n_eval = 1
        for _ in range(self.max_iter):
            if float(jnp.max(jnp.abs(g))) <= 1e-10:
                break
            # two-loop recursion
            if old_dirs:
                q = -g
                alphas = []
                rhos = [1.0 / float(jnp.dot(yd, sd)) for yd, sd in zip(old_dirs, old_steps)]
                for i in range(len(old_dirs) - 1, -1, -1):
                    a = rhos[i] * float(jnp.dot(old_steps[i], q))
                    alphas.append((i, a))
                    q = q - a * old_dirs[i]
                r = q * hdiag
                for i, a in reversed(alphas):
                    b = rhos[i] * float(jnp.dot(old_dirs[i], r))
                    r = r + (a - b) * old_steps[i]
                d = r
            else:
                d = -g
            gtd = float(jnp.dot(g, d))
            if gtd > -self.tol_x:
                break
            x_new = x + t * d
            new_loss, new_g = feval(x_new)
            n_eval += 1
            y = new_g - prev_g
            s = t * d
            ys = float(jnp.dot(y, s))
            if ys > 1e-10:
                if len(old_dirs) == self.n_correction:
                    old_dirs.pop(0)
                    old_steps.pop(0)
                old_dirs.append(y)
                old_steps.append(s)
                hdiag = ys / float(jnp.dot(y, y))
            if abs(float(new_loss) - losses[-1]) < self.tol_fun:
                x, g = x_new, new_g
                losses.append(float(new_loss))
                break
            x, g, prev_g = x_new, new_g, new_g
            losses.append(float(new_loss))
            t = self.learning_rate
            if n_eval > self.max_eval:
                break
        self.state["neval"] = self.state.get("neval", 1) + 1
        return x, losses


class ParallelAdam(Adam):
    """Reference optim/ParallelAdam.scala shards the Adam update across
    threads; under XLA the same effect comes from sharded params in the
    distributed step, so this is Adam (kept for API parity)."""
