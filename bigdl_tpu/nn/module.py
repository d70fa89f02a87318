"""Core module abstraction.

TPU-native re-design of the reference's ``AbstractModule[A, B, T]``
(reference: nn/abstractnn/AbstractModule.scala:58). The reference threads
hand-written ``updateOutput / updateGradInput / accGradParameters`` through a
mutable module tree backed by MKL JNI. Here the same *user-facing* contract —
a stateful module tree with ``forward`` / ``backward``, ``parameters()``,
train/eval modes, freezing, per-module timing — is kept, but execution is
JAX-native:

- ``forward`` is written once per layer in jax.numpy / lax. Eagerly it runs
  on device; under :func:`pure_apply` the same code is traced into a pure
  function of a params/buffers pytree and jitted/pjitted (SPMD).
- ``backward`` (module-local gradients, needed for parity with the
  reference's 650 layer specs) is derived with ``jax.vjp`` over the pure
  application instead of hand-written ``updateGradInput`` chains
  (SURVEY.md §7 "Hard parts").
- The reference's "all parameters are views into one contiguous storage"
  trick (nn/abstractnn/AbstractModule.scala:963, used for flat-buffer
  all-reduce) becomes "parameters are a pytree"; ``get_parameters()``
  offers the flat view as an explicit copy for API parity.

State model: each Module owns
  _parameters  — trainable jnp arrays (leaves of the grad pytree)
  _gradients   — accumulated gradients, same keys (eager API parity);
                 None until the training path first touches one, so a
                 model built to serve holds its weights and nothing more
  _buffers     — non-trainable state (BN running stats, …)
  _modules     — child modules (ordered; auto-registered on attribute set)
"""

from __future__ import annotations

import functools
import time
from collections import OrderedDict
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.utils import random as bt_random
from bigdl_tpu.utils.table import Table

Activity = Any  # tensor | Table | tuple/list/dict pytree — reference nn/abstractnn/Activity.scala

_PARAMS_KEY = "~params"
_BUFFERS_KEY = "~buffers"

#: >0 while inside a pure bind (trace) — module __call__s then skip recording
#: forward keys, which could be tracers.
_PURE_BIND_DEPTH = 0

# per-instance jitted backward cache (weak: dies with the module, never
# pickled/cloned)
import weakref  # noqa: E402

_VJP_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

#: >0 while constructors run under :func:`abstract_init`
_ABSTRACT_INIT_DEPTH = 0


def scoped(name: str):
    """Decorator: the function's operations are traced under
    ``jax.named_scope(name)`` (a name of
    ``observability.tracing.DEVICE_SCOPES``), through a context manager
    of its own each call. jax's own object used as a decorator is ONE
    manager for every call and thread, and keeps the context it will
    restore on itself."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kw):
            with jax.named_scope(name):
                return fn(*args, **kw)
        return inner
    return wrap


def abstract_init(build: Callable[[], "Module"]) -> "Module":
    """``build()`` with every parameter and buffer a SHAPE: the
    constructors run under ``jax.eval_shape`` (their initialisers are
    traced, never executed, and draw from a scoped key, so the global
    stream does not move) and ``register_parameter`` /
    ``register_buffer`` keep a ``jax.ShapeDtypeStruct`` where they
    would keep an array. The model that comes back allocates nothing;
    ``load_params_dict`` (and ``load_buffers_dict`` where it has
    buffers) then gives it its values in whatever dtype they are
    served in — a model too large to initialise in float32 on the
    device is built this way. A leaf left unloaded fails at first use.
    Constructors that keep ``jnp`` values outside ``register_*``
    cannot be built abstractly (the value would be a leaked tracer)."""
    global _ABSTRACT_INIT_DEPTH
    made = []

    def run():
        global _ABSTRACT_INIT_DEPTH
        _ABSTRACT_INIT_DEPTH += 1
        bt_random.RNG.push_key(jax.random.PRNGKey(0))
        try:
            made.append(build())
        finally:
            bt_random.RNG.pop_key()
            _ABSTRACT_INIT_DEPTH -= 1
        return 0

    jax.eval_shape(run)
    return made[0]


def _registered(value):
    value = jnp.asarray(value)
    if _ABSTRACT_INIT_DEPTH > 0:
        return jax.ShapeDtypeStruct(value.shape, value.dtype)
    return value


def in_pure_bind() -> bool:
    """True while tracing under ``pure_apply`` — layers must then avoid
    stashing per-call values (they would be leaked tracers)."""
    return _PURE_BIND_DEPTH > 0


@contextmanager
def pure_trace():
    """Mark a region as trace-only without binding params (used by shape
    inference): module __call__s skip recording outputs/forward keys."""
    global _PURE_BIND_DEPTH
    _PURE_BIND_DEPTH += 1
    try:
        yield
    finally:
        _PURE_BIND_DEPTH -= 1


class Module:
    """Base class of all layers (reference: nn/abstractnn/AbstractModule.scala:58)."""

    _instance_counters: Dict[str, int] = {}

    def __init_subclass__(cls, **kw):
        # record constructor args for the structured serializer
        # (≙ ModuleSerializer's case-class reflection, SURVEY.md §2.7)
        super().__init_subclass__(**kw)
        from bigdl_tpu.utils.config_capture import capture_init

        capture_init(cls)

    def __init__(self):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_gradients", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_modules", OrderedDict())
        self._frozen = False
        self.training = True
        self.output: Activity = None
        self.grad_input: Activity = None
        self._name: Optional[str] = None
        self._forward_time = 0.0
        self._backward_time = 0.0
        self._forward_key = None
        self._regularizers: Dict[str, Any] = {}
        cls = type(self).__name__
        n = Module._instance_counters.get(cls, 0)
        Module._instance_counters[cls] = n + 1
        self._default_name = f"{cls}{n}"

    # ------------------------------------------------------------------ tree
    def __setattr__(self, name, value):
        if isinstance(value, Module):
            self._modules[name] = value
        object.__setattr__(self, name, value)

    def register_parameter(self, name: str, value, regularizer=None):
        value = _registered(value)
        self._parameters[name] = value
        # allocated by the training path on first use (_grad): a model
        # that only ever serves never pays for a gradient buffer
        self._gradients[name] = None
        object.__setattr__(self, name, value)
        if regularizer is not None:
            self._regularizers[name] = regularizer

    def register_buffer(self, name: str, value):
        self._buffers[name] = _registered(value)
        object.__setattr__(self, name, self._buffers[name])

    def _grad(self, name: str):
        """Parameter ``name``'s accumulated gradient, zeros on first use."""
        g = self._gradients[name]
        if g is None:
            g = self._gradients[name] = jnp.zeros_like(self._parameters[name])
        return g

    def _set_param(self, name: str, value):
        """Rebind a registered parameter (used by bind/load)."""
        self._parameters[name] = value
        object.__setattr__(self, name, value)

    def _set_buffer(self, name: str, value):
        self._buffers[name] = value
        object.__setattr__(self, name, value)

    def modules(self):
        """(name, child) pairs in registration order."""
        return self._modules.items()

    def named_modules(self, prefix=""):
        yield prefix or self.get_name(), self
        for name, child in self._modules.items():
            yield from child.named_modules(f"{prefix}.{name}" if prefix else name)

    # -------------------------------------------------------------- identity
    def set_name(self, name: str) -> "Module":
        self._name = name
        return self

    def get_name(self) -> str:
        return self._name if self._name is not None else self._default_name

    def __repr__(self):
        lines = [type(self).__name__ + self._extra_repr()]
        for name, child in self._modules.items():
            body = repr(child).split("\n")
            lines.append(f"  ({name}): " + body[0])
            lines.extend("  " + l for l in body[1:])
        return "\n".join(lines)

    def _extra_repr(self) -> str:
        return ""

    # ------------------------------------------------------------- execution
    def forward(self, input: Activity) -> Activity:  # ≙ updateOutput
        raise NotImplementedError

    def __call__(self, input: Activity) -> Activity:
        """Forward with timing + output recording (AbstractModule.scala:254-269)."""
        scoped = bt_random.RNG.scoped
        if not scoped:
            bt_random.RNG.push_key(bt_random.next_key())
        # Snapshot the stream state seen by this module's subtree: replaying a
        # pure_apply with this key reproduces the exact stochastic draws
        # (dropout masks, ...) of this forward — see backward(). Skipped under
        # pure binds, where the key may be a tracer that must not outlive the
        # trace.
        if _PURE_BIND_DEPTH == 0:
            self._forward_key = bt_random.RNG.peek_key()
        t0 = time.perf_counter()
        try:
            # the class names this layer's operations, forward and
            # backward, in the compiled program and in a capture of it
            # (observability.tracing.DEVICE_SCOPES)
            with jax.named_scope(type(self).__name__):
                out = self.forward(input)
            # record eagerly only — under a pure bind `out` is a tracer that
            # must not outlive the trace (it would poison clone/checkpoint)
            if _PURE_BIND_DEPTH == 0:
                self.output = out
        finally:
            if not scoped:
                bt_random.RNG.pop_key()
        self._forward_time += time.perf_counter() - t0
        return out

    def _cached_vjp(self, with_params: bool):
        """Jitted module-local backward, cached per instance in a weak map
        (NOT an attribute: jitted callables must never ride along into
        clone/pickle).  jit's own shape-keyed trace cache makes repeated
        eager ``backward()`` calls — e.g. a user training loop on the eager
        API — reuse the compiled program instead of re-tracing a fresh
        ``jax.vjp`` every iteration (VERDICT round-1 weak #5)."""
        cache = _VJP_CACHE.setdefault(self, {})
        # key on the param-tree structure so structural edits (e.g. a
        # Sequential.add after a backward) invalidate the stale trace
        key_ = (with_params, jax.tree.structure(self.params_dict()))
        fn = cache.get(key_)
        if fn is None:
            if with_params:
                def bwd(params, buffers, x, key, g, training):
                    def f(p, xx):
                        out, _ = pure_apply(self)(p, buffers, xx, rng=key,
                                                  training=training)
                        return out

                    _, vjp_fn = jax.vjp(f, params, x)
                    return vjp_fn(g)
            else:
                def bwd(params, buffers, x, key, g, training):
                    def f(xx):
                        out, _ = pure_apply(self)(params, buffers, xx, rng=key,
                                                  training=training)
                        return out

                    _, vjp_fn = jax.vjp(f, x)
                    (dinput,) = vjp_fn(g)
                    return dinput

            fn = jax.jit(bwd, static_argnums=(5,))
            cache[key_] = fn
        return fn

    def backward(self, input: Activity, grad_output: Activity) -> Activity:
        """Module-local backward: gradInput + grad accumulation via jax.vjp.

        Replaces the reference's hand-written updateGradInput /
        accGradParameters chains (AbstractModule.scala:280-317). Dropout-style
        stochastic layers replay the exact rng used by the last ``__call__``.
        """
        t0 = time.perf_counter()
        params = self.params_dict()
        buffers = self.buffers_dict()
        key = self._forward_key if self._forward_key is not None else jax.random.PRNGKey(0)
        dparams, dinput = self._cached_vjp(True)(
            params, buffers, input, key, grad_output, self.training)
        self._acc_grad_dict(dparams)
        self.grad_input = dinput
        self._backward_time += time.perf_counter() - t0
        return dinput

    def update_grad_input(self, input, grad_output):
        """gradInput only — no parameter-grad accumulation."""
        params = self.params_dict()
        buffers = self.buffers_dict()
        key = self._forward_key if self._forward_key is not None else jax.random.PRNGKey(0)
        dinput = self._cached_vjp(False)(
            params, buffers, input, key, grad_output, self.training)
        self.grad_input = dinput
        return dinput

    # ------------------------------------------------------------ parameters
    def parameters(self) -> Tuple[List, List]:
        """(weights, gradWeights) in tree order (AbstractModule.scala:337)."""
        ws, gs = [], []
        for _, m in self.named_modules():
            for k in m._parameters:
                ws.append(m._parameters[k])
                gs.append(m._grad(k))
        return ws, gs

    def get_parameters(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Flat 1-D (weights, grads) copy (≙ getParameters, AbstractModule.scala:963).

        In the reference this returns *views* into one shared storage used for
        flat-buffer all-reduce; functionally that role is played by the params
        pytree + XLA collectives, so this is an explicit copy for parity/tests.
        """
        ws, gs = self.parameters()
        if not ws:
            return jnp.zeros((0,)), jnp.zeros((0,))
        return (
            jnp.concatenate([w.ravel() for w in ws]),
            jnp.concatenate([g.ravel() for g in gs]),
        )

    def params_dict(self) -> Dict:
        """Nested pytree {child: ..., '~params': {name: array}}."""
        d = {}
        if self._parameters:
            d[_PARAMS_KEY] = dict(self._parameters)
        for name, child in self._modules.items():
            sub = child.params_dict()
            if sub:
                d[name] = sub
        return d

    def load_params_dict(self, d: Dict) -> None:
        for k in self._parameters:
            self._set_param(k, d[_PARAMS_KEY][k])
        for name, child in self._modules.items():
            if name in d:
                child.load_params_dict(d[name])

    def buffers_dict(self) -> Dict:
        d = {}
        if self._buffers:
            d[_BUFFERS_KEY] = dict(self._buffers)
        for name, child in self._modules.items():
            sub = child.buffers_dict()
            if sub:
                d[name] = sub
        return d

    def load_buffers_dict(self, d: Dict) -> None:
        for k in self._buffers:
            self._set_buffer(k, d[_BUFFERS_KEY][k])
        for name, child in self._modules.items():
            if name in d:
                child.load_buffers_dict(d[name])

    def grads_dict(self) -> Dict:
        d = {}
        if self._gradients:
            d[_PARAMS_KEY] = {k: self._grad(k) for k in self._gradients}
        for name, child in self._modules.items():
            sub = child.grads_dict()
            if sub:
                d[name] = sub
        return d

    def _acc_grad_dict(self, d: Dict) -> None:
        if _PARAMS_KEY in d:
            for k, g in d[_PARAMS_KEY].items():
                cur = self._gradients[k]
                self._gradients[k] = g if cur is None else cur + g
        for name, child in self._modules.items():
            if name in d:
                child._acc_grad_dict(d[name])

    def load_grads_dict(self, d: Dict) -> None:
        if _PARAMS_KEY in d:
            for k, g in d[_PARAMS_KEY].items():
                self._gradients[k] = g
        for name, child in self._modules.items():
            if name in d:
                child.load_grads_dict(d[name])

    def trainable_dict(self) -> Dict:
        """Pytree of bools mirroring params_dict — False where frozen."""
        d = {}
        if self._parameters:
            d[_PARAMS_KEY] = {k: not self._frozen for k in self._parameters}
        for name, child in self._modules.items():
            sub = child.trainable_dict()
            if sub:
                d[name] = sub
        if self._frozen:
            d = jax.tree.map(lambda _: False, d)
        return d

    def regularization_loss(self, params: Optional[Dict] = None):
        """Sum of per-parameter regularizer penalties (≙ optim/Regularizer.scala,
        applied in the loss instead of inside accGradParameters)."""
        params = params if params is not None else self.params_dict()
        total = 0.0
        if self._parameters and self._regularizers:
            p = params.get(_PARAMS_KEY, {})
            for k, reg in self._regularizers.items():
                if k in p:
                    total = total + reg(p[k])
        for name, child in self._modules.items():
            if name in params:
                total = total + child.regularization_loss(params[name])
        return total

    def copy_parameters_from(self, other: "Module") -> "Module":
        self.load_params_dict(other.params_dict())
        self.load_buffers_dict(other.buffers_dict())
        return self

    def zero_grad_parameters(self) -> None:
        for _, m in self.named_modules():
            for k in m._gradients:
                # a gradient nothing has touched is already zero
                if m._gradients[k] is not None:
                    m._gradients[k] = jnp.zeros_like(m._gradients[k])

    def update_parameters(self, learning_rate: float) -> None:
        """Eager in-place-style SGD step (API parity; real training uses optim/)."""
        for _, m in self.named_modules():
            for k in m._parameters:
                m._set_param(k, m._parameters[k] - learning_rate * m._grad(k))

    # ------------------------------------------------------------ modes/state
    def training_mode(self) -> "Module":  # ≙ training()
        for _, m in self.named_modules():
            m.training = True
        return self

    def evaluate(self) -> "Module":
        for _, m in self.named_modules():
            m.training = False
        return self

    def is_training(self) -> bool:
        return self.training

    def set_training(self, flag: bool) -> "Module":
        for _, m in self.named_modules():
            m.training = flag
        return self

    def freeze(self, *names: str) -> "Module":
        """Stop parameter updates (≙ AbstractModule.freeze :203-252)."""
        if not names:
            self._frozen = True
            for _, child in self._modules.items():
                child.freeze()
        else:
            for _, m in self.named_modules():
                if m.get_name() in names:
                    m.freeze()
        return self

    def unfreeze(self, *names: str) -> "Module":
        if not names:
            self._frozen = False
            for _, child in self._modules.items():
                child.unfreeze()
        else:
            for _, m in self.named_modules():
                if m.get_name() in names:
                    m.unfreeze()
        return self

    def reset(self) -> None:
        """Re-initialize parameters; layers with weights override."""
        for _, child in self._modules.items():
            child.reset()

    # ---------------------------------------------------------------- timing
    def get_times(self):
        """[(module, forward_s, backward_s)] (≙ getTimes, AbstractModule.scala:167)."""
        out = []
        for _, m in self.named_modules():
            out.append((m, m._forward_time, m._backward_time))
        return out

    def get_times_group_by_module_type(self):
        agg: Dict[str, List[float]] = {}
        for m, f, b in self.get_times():
            t = agg.setdefault(type(m).__name__, [0.0, 0.0])
            t[0] += f
            t[1] += b
        return {k: tuple(v) for k, v in agg.items()}

    def reset_times(self) -> None:
        for _, m in self.named_modules():
            m._forward_time = 0.0
            m._backward_time = 0.0

    # ------------------------------------------------------------- inference
    def predict(self, dataset, batch_size: int = 32):
        from bigdl_tpu.optim.predictor import LocalPredictor

        return LocalPredictor(self, batch_size=batch_size).predict(dataset)

    def predict_class(self, dataset, batch_size: int = 32):
        from bigdl_tpu.optim.predictor import LocalPredictor

        return LocalPredictor(self, batch_size=batch_size).predict_class(dataset)

    def evaluate_on(self, dataset, methods, batch_size: int = 32):
        from bigdl_tpu.optim.evaluator import Evaluator

        return Evaluator(self).test(dataset, methods, batch_size=batch_size)

    # ------------------------------------------------------------- utilities
    def inputs(self, *nodes):
        """Wire this module into a dataflow graph; returns its Node
        (≙ AbstractModule.inputs, AbstractModule.scala:785-816)."""
        from bigdl_tpu.nn.graph import Node

        return Node(self).inputs(*nodes)

    def clone_module(self) -> "Module":
        import copy

        return copy.deepcopy(self)

    def is_container(self) -> bool:
        return bool(self._modules)

    def save(self, path: str, overwrite: bool = False) -> "Module":
        """Pickle save (≙ the reference's Java-serialization ``save``,
        AbstractModule.scala:523)."""
        from bigdl_tpu.utils import file as bt_file

        bt_file.save_module(self, path, overwrite=overwrite)
        return self

    def save_module(self, path: str, overwrite: bool = False) -> "Module":
        """Structured save (≙ ``saveModule`` protobuf path,
        AbstractModule.scala:543; format: utils/serializer)."""
        from bigdl_tpu.utils import serializer

        serializer.save_module(self, path, overwrite=overwrite)
        return self

    @staticmethod
    def load(path: str) -> "Module":
        """≙ Module.load (nn/Module.scala:44)."""
        from bigdl_tpu.utils import file as bt_file

        return bt_file.load_module(path)

    @staticmethod
    def load_module(path: str) -> "Module":
        """≙ Module.loadModule (nn/Module.scala:54)."""
        from bigdl_tpu.utils import serializer

        return serializer.load_module(path)

    def quantize(self) -> "Module":
        """Int8-quantized clone for inference (≙ AbstractModule.quantize,
        AbstractModule.scala:895)."""
        from bigdl_tpu.nn.quantized import Quantizer

        return Quantizer.quantize(self)


# --------------------------------------------------------------------------
# Pure (functional) application — the TPU execution path.
# --------------------------------------------------------------------------
@contextmanager
def bind(module: Module, params: Dict, buffers: Dict, training: bool, rng=None):
    """Temporarily bind a params/buffers pytree (possibly tracers) into the
    module tree. Restores original arrays on exit so tracers never leak."""
    old_params = module.params_dict()
    old_buffers = module.buffers_dict()
    old_modes = [m.training for _, m in module.named_modules()]
    if params:
        module.load_params_dict(params)
    if buffers:
        module.load_buffers_dict(buffers)
    module.set_training(training)
    # ALWAYS scope the RNG: without this, module __call__s inside a jit trace
    # would split the global key into tracers and leak them past the trace.
    if rng is None:
        rng = jax.random.PRNGKey(0)
    bt_random.RNG.push_key(rng)
    global _PURE_BIND_DEPTH
    _PURE_BIND_DEPTH += 1
    try:
        yield
    finally:
        _PURE_BIND_DEPTH -= 1
        bt_random.RNG.pop_key()
        if params:
            module.load_params_dict(old_params)
        if buffers:
            module.load_buffers_dict(old_buffers)
        for (_, m), mode in zip(module.named_modules(), old_modes):
            m.training = mode


def pure_apply(module: Module) -> Callable:
    """Extract ``fn(params, buffers, input, rng, training) -> (out, new_buffers)``.

    The returned function is pure and safe to ``jax.jit`` / ``jax.grad`` /
    shard with ``pjit``: module forward code runs once at trace time with
    tracer-bound parameters (the 'compile-phase' that replaces the reference's
    MklDnnContainer.compile, nn/mkldnn/DnnBase.scala:302).
    """

    def apply_fn(params, buffers, input, rng=None, training=False):
        with bind(module, params, buffers, training, rng):
            out = module.forward(input)
            new_buffers = module.buffers_dict()
        return out, new_buffers

    return apply_fn


def jit_inference_fn(module: Module) -> Callable:
    """Jitted eval-mode forward ``fn(params, buffers, input) -> out`` shared
    by the inference facades (LocalPredictor / PredictionService / DLModel):
    one compile per input signature, buffers read-only."""
    import jax

    apply_fn = pure_apply(module)
    return jax.jit(lambda p, b, x: apply_fn(p, b, x, training=False)[0])
