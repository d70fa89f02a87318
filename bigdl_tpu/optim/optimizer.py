"""Optimizer builder API + the local (single-host) training loop.

Reference: optim/Optimizer.scala:47 (builder: setValidation / setCheckpoint /
setTrainSummary / setOptimMethod / setEndWhen / gradient clipping) and
optim/LocalOptimizer.scala:45. The reference runs per-core model replicas
over MKL threads; TPU-native, one jitted train step consumes the whole
per-host batch — thread-level data parallelism is absorbed by XLA's own
parallelism on device, and multi-chip data parallelism lives in
bigdl_tpu.parallel.DistriOptimizer.

The train step is a pure function
    (params, buffers, slots, input, target, lr, rng) ->
    (loss, new_params, new_buffers, new_slots)
compiled once; the loop around it reproduces the reference's semantics:
infinite shuffled iterator, approximate epoch boundary
(recordsProcessedThisEpoch >= numSamples, Appendix B.6), state-table keys
(Appendix B.7), trigger-driven validation/checkpoint/summary, per-iteration
throughput log (optim/DistriOptimizer.scala:390-393 parity). The local
loop keeps one step in flight: a step's loss is fetched and logged behind
the next step's dispatch, unless somebody reads ``state["Loss"]`` sooner.
"""

from __future__ import annotations

import collections
import logging
import os
import time
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset.dataset import AbstractDataSet, LocalDataSet
from bigdl_tpu.dataset.minibatch import MiniBatch
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.dataset.transformer import SampleToMiniBatch
from bigdl_tpu.nn.module import Module, pure_apply, scoped
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.optim_method import OptimMethod, SGD, TrainState
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import ValidationMethod
from bigdl_tpu.utils import random as bt_random

logger = logging.getLogger("bigdl_tpu.optim")


def _clip_constant(grads, min_v, max_v):
    return jax.tree.map(lambda g: jnp.clip(g, min_v, max_v), grads)


def _clip_by_global_norm(grads, max_norm):
    """≙ L2NormClippingProcessor (parameters/ParameterOperations.scala:71-124):
    the reference computes the global grad norm across partitions; here the
    grads pytree is already global under SPMD."""
    sq = sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
    norm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)


def _mask_frozen(new_params, old_params, trainable):
    def pick(new, old, t):
        return new if t else old

    return jax.tree.map(pick, new_params, old_params, trainable,
                        is_leaf=lambda x: isinstance(x, bool))


def _method_groups(model: Module, default_method: OptimMethod, sub_methods):
    """Per-param-leaf optimizer assignment for setOptimMethods
    (optim/Optimizer.scala:377): group 0 = default, one group per named
    submodule. Returns (methods, leaf_group_ids) with ids aligned to
    ``jax.tree.leaves(model.params_dict())`` order (same dict structure)."""
    methods = [default_method]
    name_to_gid = {}
    for name, m in (sub_methods or {}).items():
        name_to_gid[name] = len(methods)
        methods.append(m)

    from bigdl_tpu.nn.module import _PARAMS_KEY

    def walk(module, gid):
        g = name_to_gid.get(module.get_name(), gid)
        d = {}
        if module._parameters:
            d[_PARAMS_KEY] = {k: g for k in module._parameters}
        for child_name, child in module._modules.items():
            sub = walk(child, g)
            if sub:
                d[child_name] = sub
        return d

    unmatched = set(name_to_gid) - {m.get_name() for _, m in model.named_modules()}
    if unmatched:
        raise ValueError(f"setOptimMethods names not found in model: {sorted(unmatched)}")
    return methods, jax.tree.leaves(walk(model, 0))


class TrainStep:
    """The pure train step + grouped optimizer state (shared by Local and
    Distri optimizers). ``step(params, buffers, slots, x, y, lrs, rng)`` is
    jit/pjit-safe; ``lrs`` is one scalar per optimizer group (host-scheduled).

    ``compute_dtype`` enables the mixed-precision master split: params stay
    at their stored dtype (f32 master), are cast once to ``compute_dtype``
    (bf16) for forward+backward, and grads come back f32 through the cast's
    vjp — the TPU-native analog of the reference's FP16 wire format applied
    to compute rather than communication."""

    def __init__(self, model: Module, criterion, optim_method: OptimMethod,
                 grad_clip: Optional[dict] = None, sub_methods=None,
                 compute_dtype=None, grad_accum: int = 1):
        if grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        apply_fn = pure_apply(model)
        trainable = model.trainable_dict()
        any_frozen = not all(
            t for t in jax.tree.leaves(trainable, is_leaf=lambda x: isinstance(x, bool)))
        self.methods, gids = _method_groups(model, optim_method, sub_methods)
        n_groups = len(self.methods)
        idxs_per_group = [[i for i, g in enumerate(gids) if g == k]
                          for k in range(n_groups)]
        self._idxs_per_group = idxs_per_group

        # the scopes name the step's parts in the compiled program
        # (observability.tracing.DEVICE_SCOPES); the model's layers name
        # themselves (Module.__call__)
        @scoped("optim/update")
        def _compute_params(params):
            if compute_dtype is None:
                return params
            return jax.tree.map(
                lambda a: a.astype(compute_dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, params)

        def data_loss_fn(params, buffers, x, y, rng):
            cparams = _compute_params(params)
            out, new_buffers = apply_fn(cparams, buffers, x, rng=rng, training=True)
            with jax.named_scope("optim/loss"):
                return criterion.forward(out, y), new_buffers

        @scoped("optim/loss")
        def reg_loss_fn(params):
            return model.regularization_loss(_compute_params(params))

        def loss_fn(params, buffers, x, y, rng):
            loss, new_buffers = data_loss_fn(params, buffers, x, y, rng)
            return loss + reg_loss_fn(params), new_buffers

        def grad_of_batch(params, buffers, x, y, rng):
            """(loss, new_buffers, grads) — one shot, or accumulated over
            ``grad_accum`` sequential micro-batches via lax.scan: peak
            activation memory drops by the accumulation factor (the TPU
            HBM trade for large effective batches); BN statistics update
            per micro-batch, RNG keys split per micro-batch."""
            if grad_accum == 1:
                (loss, new_buffers), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, buffers, x, y, rng)
                return loss, new_buffers, grads
            batch = jax.tree.leaves(x)[0].shape[0]
            if batch % grad_accum:
                raise ValueError(f"batch size {batch} not divisible by "
                                 f"grad_accum {grad_accum}")

            def split(t):
                return jax.tree.map(
                    lambda a: a.reshape(grad_accum, batch // grad_accum,
                                        *a.shape[1:]), t)

            def micro(carry, xs):
                bufs, g_acc, l_acc = carry
                xm, ym, key = xs
                (loss, nb), g = jax.value_and_grad(
                    data_loss_fn, has_aux=True)(params, bufs, xm, ym, key)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (nb, g_acc, l_acc + loss), None

            keys = (jax.random.split(rng, grad_accum) if rng is not None
                    else jnp.zeros((grad_accum, 2), jnp.uint32))
            zero_g = jax.tree.map(jnp.zeros_like, params)
            (new_buffers, g_sum, l_sum), _ = jax.lax.scan(
                micro, (buffers, zero_g, jnp.float32(0.0)),
                (split(x), split(y), keys))
            # reduction-aware combine: mean criteria (size_average, the
            # default) average the micro results; sum criteria keep the
            # sum. Regularization enters exactly ONCE either way.
            if getattr(criterion, "size_average", True):
                g_sum = jax.tree.map(lambda g: g / grad_accum, g_sum)
                l_sum = l_sum / grad_accum
            reg_val, reg_grads = jax.value_and_grad(reg_loss_fn)(params)
            grads = jax.tree.map(jnp.add, g_sum, reg_grads)
            return l_sum + reg_val, new_buffers, grads

        @scoped("optim/update")
        def update(params, grads, slots, lrs):
            # global pre-clip grad norm for telemetry; callers jitting the
            # plain ``step`` never pay for it — an unused output is dead
            # code to XLA
            gnorm = jnp.sqrt(sum(
                jnp.sum(g.astype(jnp.float32) ** 2)
                for g in jax.tree.leaves(grads)))
            if grad_clip:
                if "constant" in grad_clip:
                    lo, hi = grad_clip["constant"]
                    grads = _clip_constant(grads, lo, hi)
                if "l2norm" in grad_clip:
                    grads = _clip_by_global_norm(grads, grad_clip["l2norm"])
            leaves, treedef = jax.tree.flatten(params)
            g_leaves = jax.tree.leaves(grads)
            new_leaves = list(leaves)
            new_slots = []
            for k, meth in enumerate(self.methods):
                idxs = idxs_per_group[k]
                if not idxs:
                    new_slots.append(slots[k])
                    continue
                p_sub = [leaves[i] for i in idxs]
                gr_sub = [g_leaves[i] for i in idxs]
                np_sub, ns = meth.step(p_sub, gr_sub, slots[k], lrs[k])
                # optimizer math may promote (f32 lr × bf16 param); store
                # back at the parameter's dtype so the step stays stable
                # under jit across iterations
                for i, pv, old in zip(idxs, np_sub, p_sub):
                    new_leaves[i] = pv.astype(old.dtype)
                new_slots.append(ns)
            new_params = jax.tree.unflatten(treedef, new_leaves)
            if any_frozen:
                new_params = _mask_frozen(new_params, params, trainable)
            return gnorm, new_params, tuple(new_slots)

        def _core(params, buffers, slots, x, y, lrs, rng):
            loss, new_buffers, grads = grad_of_batch(params, buffers, x, y,
                                                     rng)
            gnorm, new_params, new_slots = update(params, grads, slots, lrs)
            return loss, gnorm, new_params, new_buffers, new_slots

        def step(params, buffers, slots, x, y, lrs, rng):
            loss, _, new_params, new_buffers, new_slots = _core(
                params, buffers, slots, x, y, lrs, rng)
            return loss, new_params, new_buffers, new_slots

        self.step = step
        #: telemetry variant: same update math, additionally returns the
        #: global pre-clip gradient L2 norm —
        #: (loss, grad_norm, params, buffers, slots)
        self.step_with_stats = _core
        self._rates = self._lrs = None      # lrs_for_step's last answer

    def init_slots(self, params):
        leaves = jax.tree.leaves(params)
        return tuple(
            m.init_slots([leaves[i] for i in idxs])
            for m, idxs in zip(self.methods, self._idxs_per_group))

    def current_rates(self):
        """One host float a group, from each method's schedule."""
        return [m.get_current_rate() for m in self.methods]

    def current_lrs(self):
        return jnp.asarray(self.current_rates(), jnp.float32)

    def lrs_for_step(self):
        """``(lrs, lr)`` for the next step: ``current_lrs()``, made anew
        only when a schedule has changed a rate, and group 0's rate as the
        step will see it (rounded to float32), both without a device read."""
        rates = self.current_rates()
        if rates != self._rates:
            self._rates, self._lrs = rates, jnp.asarray(rates, jnp.float32)
        return self._lrs, float(np.float32(rates[0]))

    def update_states(self, **kv):
        for m in self.methods:
            m.state.update(kv)

    def defer_states(self, key, fetch):
        """``key`` of every method's state table is ``fetch()``, called by
        whoever reads it first (``TrainState.defer``)."""
        for m in self.methods:
            m.state.defer(key, fetch)


class _StepInFlight:
    """A dispatched train step as the host knows it, and its loss, which
    stays on the device until somebody needs it: the loop's report of the
    step, or whoever reads ``state["Loss"]`` first. That wait is the
    ``train/fence`` span, with the step's ``neval`` and ``behind``: 1 when
    a later step was already dispatched (the device has work queued behind
    the one waited for), 0 when the loop was synchronous here."""

    def __init__(self, after, loss, gnorm, start_ns, *, neval, epoch, n, lr,
                 records):
        self.after = after          # the step dispatched before this one
        if after is not None:
            after.overtaken = True
        self._device = (loss, gnorm)
        self.start_ns = start_ns    # its dispatch began
        self.overtaken = False      # a later step has been dispatched
        self.neval, self.epoch, self.n, self.lr = neval, epoch, n, lr
        self.records = records      # recordsProcessedThisEpoch with it

    def loss(self) -> float:
        if self._device is not None:
            from bigdl_tpu.observability import trace

            free_ns = 0     # when the device was free for this step
            if self.after is not None:
                self.after.loss()   # the device ran that one first
                free_ns, self.after = self.after.fetched_ns, None
            loss, gnorm = self._device
            self.behind = int(self.overtaken)
            with trace.span("train/fence", neval=self.neval,
                            behind=self.behind) as fence:
                self.value = float(loss)
                self.grad_norm = None if gnorm is None else float(gnorm)
            self._device = None
            self.fetched_ns = fence.end_ns
            # the step's seconds: from its dispatch, or from the loss of
            # the step before it where the device still ran that one
            self.seconds = (fence.end_ns - max(self.start_ns, free_ns)) / 1e9
        return self.value


def make_train_step(model: Module, criterion, optim_method: OptimMethod,
                    grad_clip: Optional[dict] = None, sub_methods=None,
                    compute_dtype=None, grad_accum: int = 1) -> TrainStep:
    return TrainStep(model, criterion, optim_method, grad_clip, sub_methods,
                     compute_dtype=compute_dtype, grad_accum=grad_accum)


def _named_param_leaves(params):
    """(dotted-name, leaf) pairs over the params pytree."""
    from bigdl_tpu.parallel.tp import tree_paths

    for path, leaf in tree_paths(params):
        yield path.strip("/").replace("/", "."), leaf


def load_latest_checkpoint(path: str):
    """Scan a checkpoint dir for the newest (model, optim_method) snapshot
    (≙ DistriOptimizer.getLatestFile recovery scan,
    optim/DistriOptimizer.scala:1072-1089). Returns (model, method, tag)
    or (None, None, None) when the dir holds no snapshots."""
    from bigdl_tpu.utils import file as bt_file
    from bigdl_tpu.optim.optim_method import OptimMethod

    if not bt_file.is_remote(path) and not os.path.isdir(path):
        return None, None, None
    try:
        names = bt_file.listdir(path)
    except (FileNotFoundError, NotADirectoryError, OSError):
        return None, None, None
    name_set = set(names)  # one listing answers all pairing checks
    tags = []
    for fname in names:
        if fname.startswith("model."):
            suffix = fname[len("model."):]
            if suffix.isdigit() and f"optimMethod.{suffix}" in name_set:
                tags.append(int(suffix))
    if not tags:
        return None, None, None
    tag = max(tags)
    model = bt_file.load_module(os.path.join(path, f"model.{tag}"))
    method = OptimMethod.load(os.path.join(path, f"optimMethod.{tag}"))
    return model, method, tag


class Optimizer:
    """Builder façade (reference: optim/Optimizer.scala:47,655-676). The
    factory picks the local loop for LocalDataSet and the distributed SPMD
    loop for ShardedDataSet / device-sharded data."""

    def __new__(cls, model: Module = None, dataset=None, criterion=None,
                batch_size: Optional[int] = None, end_when: Optional[Trigger] = None,
                training_set=None, **kw):
        dataset = dataset if dataset is not None else training_set
        if cls is Optimizer:
            from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
            from bigdl_tpu.dataset.dataset import ShardedDataSet, dataset_base

            base = dataset_base(dataset)
            if isinstance(base, ShardedDataSet):
                inst = object.__new__(DistriOptimizer)
            else:
                inst = object.__new__(LocalOptimizer)
            return inst
        return object.__new__(cls)

    def __init__(self, model: Module = None, dataset=None, criterion=None,
                 batch_size: Optional[int] = None, end_when: Optional[Trigger] = None,
                 training_set=None, **kw):
        self.model = model
        dataset = dataset if dataset is not None else training_set
        if isinstance(dataset, (list, tuple)) and dataset and isinstance(dataset[0], Sample):
            dataset = LocalDataSet(list(dataset))
        self.dataset: AbstractDataSet = dataset
        self.criterion = criterion
        self.batch_size = batch_size
        self.end_when = end_when or Trigger.max_epoch(1)
        self.optim_method: OptimMethod = SGD()
        self.sub_optim_methods: Dict[str, OptimMethod] = {}
        self.validation_trigger: Optional[Trigger] = None
        self.validation_dataset = None
        self.validation_methods: Optional[Sequence[ValidationMethod]] = None
        self.checkpoint_path: Optional[str] = None
        self.checkpoint_trigger: Optional[Trigger] = None
        self.train_summary = None
        self.validation_summary = None
        self.grad_clip: dict = {}
        self.metrics = Metrics()
        self._dropped_checkpoints = 0

    # -------------------------------------------------------------- builder
    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        return self

    def set_optim_methods(self, methods: Dict[str, OptimMethod]) -> "Optimizer":
        """Per-submodule optim methods (reference: optim/Optimizer.scala:377).
        Keys are module names; parameters under that submodule use its method."""
        self.sub_optim_methods = dict(methods)
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset, methods,
                       batch_size: Optional[int] = None) -> "Optimizer":
        self.validation_trigger = trigger
        if isinstance(dataset, (list, tuple)) and dataset and isinstance(dataset[0], Sample):
            dataset = LocalDataSet(list(dataset))
        self.validation_dataset = dataset
        self.validation_methods = list(methods)
        self.validation_batch_size = batch_size or self.batch_size
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       is_overwrite: bool = True,
                       async_write: bool = False,
                       slots_backend: str = "pickle") -> "Optimizer":
        """``async_write=True`` snapshots synchronously (consistent model +
        optim-method state) but performs serialization/IO in a background
        thread, so the train loop is not stalled by checkpoint writes; at
        most one write is in flight (the next checkpoint joins it first,
        surfacing any write error), and ``optimize()`` joins before
        returning.

        ``slots_backend="orbax"`` (DistriOptimizer only) writes the
        sharded optimizer slots via orbax — shard-wise from their owning
        devices/processes, no host gather (utils/orbax_ckpt.py)."""
        if slots_backend not in ("pickle", "orbax"):
            raise ValueError(f"unknown slots_backend {slots_backend!r}")
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        self.checkpoint_overwrite = is_overwrite
        self.checkpoint_async = async_write
        self.checkpoint_slots_backend = slots_backend
        return self

    def set_gradient_accumulation(self, n_micro_batches: int) -> "Optimizer":
        """Accumulate gradients over ``n_micro_batches`` sequential
        micro-batches per step (batch_size must divide evenly): same
        optimizer math as the full batch, 1/n the activation memory."""
        self.grad_accum = int(n_micro_batches)
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self.train_summary = summary
        return self

    def set_validation_summary(self, summary) -> "Optimizer":
        self.validation_summary = summary
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float) -> "Optimizer":
        self.grad_clip["l2norm"] = float(clip_norm)
        return self

    def set_constant_gradient_clipping(self, min_v: float, max_v: float) -> "Optimizer":
        self.grad_clip["constant"] = (float(min_v), float(max_v))
        return self

    def disable_gradient_clipping(self) -> "Optimizer":
        self.grad_clip = {}
        return self

    # ------------------------------------------------------------- optimize
    def optimize(self) -> Module:
        raise NotImplementedError


class LocalOptimizer(Optimizer):
    """Single-host training loop (reference: optim/LocalOptimizer.scala:45)."""

    def _minibatches(self, dataset, batch_size, train=True):
        it = dataset.data(train=train)
        first = None
        for first in it:
            break
        if first is None:
            return iter(())

        def chain():
            yield first
            yield from it

        if isinstance(first, MiniBatch):
            return chain()
        return SampleToMiniBatch(batch_size)(chain())

    def optimize(self) -> Module:
        model, criterion = self.model, self.criterion
        method = self.optim_method

        # copy once so step-1 donation can never invalidate the model's
        # own arrays (params_dict returns live references); after each aux
        # load_params_dict the model tracks the freshest outputs as before
        params = jax.tree.map(jnp.copy, model.params_dict())
        buffers = jax.tree.map(jnp.copy, model.buffers_dict())
        ga = getattr(self, "grad_accum", 1)
        if ga > 1 and self.batch_size % ga:
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by gradient "
                f"accumulation factor {ga} (checked up front: a ragged "
                "batch would otherwise fail mid-training)")
        ts = make_train_step(model, criterion, method, self.grad_clip,
                             self.sub_optim_methods, grad_accum=ga)
        slots = ts.init_slots(params)
        for m in ts.methods:
            # a table unpickled by an older checkpoint, or assigned
            if not isinstance(m.state, TrainState):
                m.state = TrainState(m.state)
        state = method.state
        state.setdefault("epoch", 1)
        state.setdefault("neval", 1)
        state.setdefault("recordsProcessedThisEpoch", 0)
        # donate params/buffers/slots: the step's outputs reuse their
        # buffers in place of a full params+slots copy every iteration
        # (~2x peak parameter memory otherwise); every consumer of the
        # previous values (histograms, validation, checkpoint) reads the
        # freshest POST-step outputs, which are only donated by the NEXT
        # call, and the async checkpoint thread serializes a deepcopy.
        # With observability on, the stats variant also returns the grad
        # norm (same math; it is fetched with the loss).
        from bigdl_tpu import observability as obs

        self._obs_on = obs.enabled()
        train_step = jax.jit(
            ts.step_with_stats if self._obs_on else ts.step,
            donate_argnums=(0, 1, 2))

        num_samples = self.dataset.size()
        data_iter = self._prepared_batches(queued_steps=1)
        wall_start = time.time()

        # /debug/memory attribution: params and optimizer slots are the
        # training run's two big persistent buffer sets (sizes are
        # shape-derived constants). The context manager unregisters on
        # EVERY exit — including a join_pending_checkpoint re-raise.
        from bigdl_tpu.observability import memory as obs_memory

        with obs_memory.static_pools({
                "train/params": obs_memory.tree_bytes(params),
                "train/optimizer_slots": obs_memory.tree_bytes(slots)}):
            try:
                return self._optimize_loop(
                    model, state, params, buffers, ts, slots, train_step,
                    num_samples, data_iter, wall_start)
            finally:
                # even on an exception mid-training, never abandon an
                # in-flight async checkpoint write (the one run where
                # it matters most)
                self.join_pending_checkpoint()

    def _batch_stream(self):
        """Infinite minibatch stream with PRODUCER-side epoch reshuffles.

        The dataset iterators are deliberately infinite (dataset.py
        ``data(train=True)``), so epochs are counted by records here —
        the same accounting the train loop uses — and ``shuffle()`` fires
        between epochs on this side of the prefetch queue, so the order
        is settled before the next epoch's batches are staged. (The
        iterator reads ``_index`` live; no restart needed.)"""
        if self.dataset.size() == 0:
            raise ValueError("dataset is empty")
        local = getattr(self.dataset, "local_size", self.dataset.size)()
        seen = 0
        for b in self._minibatches(self.dataset, self.batch_size):
            yield b
            seen += b.size()
            if seen >= local:
                seen = 0
                self.dataset.shuffle()

    def _prepare_batch(self, batch):
        """(x, y, n) with device-resident arrays; Table structure preserved
        for multi-input models (jnp.asarray on a Table would stack
        same-shaped features / fail on heterogeneous ones)."""
        x = jax.tree.map(jnp.asarray, batch.get_input())
        y = jax.tree.map(jnp.asarray, batch.get_target())
        return x, y, batch.size()

    def _prepared_batches(self, prepare=None, queued_steps=0):
        """Host batch prep + H2D transfer moved onto a background thread
        (``bigdl.prefetch.buffer`` batches deep, 0 disables) so the input
        pipeline overlaps the device step — ≙ the reference's "io" thread
        pool staging batches per executor (utils/Engine.scala:218-355).
        ``queued_steps``: steps the loop keeps dispatched behind the running
        one. Each holds its batch on the device until it has run, so it
        counts as one of the batches staged ahead and the queue is that
        much shorter: as many batches are alive as with a loop that waits
        for every step."""
        from bigdl_tpu.dataset.prefetch import prefetch
        from bigdl_tpu.utils import config as bt_config

        prepare = prepare or self._prepare_batch
        depth = bt_config.get_int("bigdl.prefetch.buffer", 2)
        stream = self._batch_stream()
        if depth <= 0:
            return (prepare(b) for b in stream)
        return prefetch(stream, buffer_size=max(1, depth - queued_steps),
                        transfer=prepare)

    def _optimize_loop(self, model, state, params, buffers, ts, slots,
                       train_step, num_samples, data_iter, wall_start):
        """One step is kept in flight: step k+1 is dispatched while step k
        runs, and step k's loss is fetched and reported (the log line, the
        summary, the instruments) behind that dispatch, so the device does
        not idle while the host turns round. ``state["Loss"]`` is deferred
        to the step just dispatched (``TrainState``): a trigger, schedule
        or hook that reads it waits for that step and gets its loss, which
        makes that turn of the loop as synchronous as the reader needs; so
        does an aux point (validation, checkpoint, parameter histograms),
        which reads the step's parameters."""
        from bigdl_tpu import observability as obs

        obs_on = getattr(self, "_obs_on", False)
        ins = obs.train_instruments() if obs_on else None
        span = obs.trace.span
        # dispatched steps not yet reported, oldest first: at most the one
        # in flight and the one before it
        flight = collections.deque()

        def report(keep):
            """Fence and report, in order, all but the newest ``keep``."""
            while len(flight) > keep:
                step = flight.popleft()     # off the list first: never twice
                loss = step.loss()
                with span("train/bookkeeping"):
                    dt, n = step.seconds, step.n
                    rate = n / max(dt, 1e-9)
                    self.metrics.add("computing time", dt * 1e9)
                    if obs_on:
                        ins.step_seconds.observe(dt)
                        ins.fences.labels(str(step.behind)).inc()
                        ins.records_total.inc(n)
                        ins.throughput.set(rate)
                        ins.loss.set(loss)
                        ins.learning_rate.set(step.lr)
                        ins.grad_norm.set(step.grad_norm)
                        ins.epoch.set(step.epoch)
                        ins.jit_compiles.set(train_step._cache_size())
                    logger.info(
                        "[Epoch %d %d/%d][Iteration %d][Wall Clock %.3fs] "
                        "Trained %d records in %.4f seconds. Throughput is %.1f records/second. "
                        "Loss is %.4f.",
                        step.epoch, step.records, num_samples, step.neval,
                        time.time() - wall_start, n, dt, rate, loss)
                    if self.train_summary is not None:
                        self.train_summary.add_scalar("Loss", loss, step.neval)
                        self.train_summary.add_scalar("LearningRate", step.lr, step.neval)
                        self.train_summary.add_scalar("Throughput", rate, step.neval)

        # one root an iteration; its children tile it (boundaries touch),
        # so what the loop does between two dispatches has a name
        try:
            while not self.end_when(state):
                with span("train/iteration", neval=state["neval"]):
                    with span("train/data_wait"):
                        x, y, n = next(data_iter)
                    # the step's learning rates and key. Nothing here waits
                    # for the device: the rates are host floats, their array
                    # is remade only when one changes, and the key's split
                    # queues behind the running step
                    with span("train/arguments"):
                        lrs, lr = ts.lrs_for_step()
                        rng = bt_random.next_key()
                    gnorm = None
                    with span("train/step"):
                        # the call into the jitted step: the enqueue
                        with span("train/dispatch") as dispatch:
                            if obs_on:
                                loss, gnorm, params, buffers, slots = train_step(
                                    params, buffers, slots, x, y, lrs, rng)
                            else:
                                loss, params, buffers, slots = train_step(
                                    params, buffers, slots, x, y, lrs, rng)
                    with span("train/bookkeeping"):
                        # what the host knows of this step without its loss
                        state["recordsProcessedThisEpoch"] += n
                        state["LearningRate"] = lr
                        step = _StepInFlight(
                            flight[-1] if flight else None, loss, gnorm,
                            dispatch.start_ns, neval=state["neval"],
                            epoch=state["epoch"], n=n, lr=lr,
                            records=state["recordsProcessedThisEpoch"])
                        flight.append(step)
                        ts.defer_states("Loss", step.loss)
                        # optional parameter histograms, gated on a trigger
                        # (≙ TrainSummary "Parameters" tag, TrainSummary.scala:32)
                        ptrig = getattr(self.train_summary, "get_summary_trigger",
                                        lambda _n: None)("Parameters")
                        hist_now = ptrig is not None and ptrig(state)
                        state["neval"] += 1
                        if state["recordsProcessedThisEpoch"] >= num_samples:
                            state["epoch"] += 1
                            state["recordsProcessedThisEpoch"] = 0
                            # reshuffle + restart happen inside _batch_stream (on the
                            # producer side, ordered ahead of the prefetched batches)
                        ts.update_states(neval=state["neval"], epoch=state["epoch"])
                        aux_now = self._should_fire_aux(state)
                    # the step before: its loss arrives while this one runs.
                    # An aux point reads this step's parameters, so there
                    # this step is fenced too, as every step was
                    report(keep=0 if aux_now or hist_now else 1)
                    if hist_now:
                        with span("train/bookkeeping"):
                            for pname, leaf in _named_param_leaves(params):
                                self.train_summary.add_histogram(
                                    pname, np.asarray(leaf), step.neval)
                # write updated weights back before validation/checkpoint
                if aux_now:
                    model.load_params_dict(params)
                    model.load_buffers_dict(buffers)
                    with span("train/validation"):
                        self._run_validation(state)
                    # only a real checkpoint samples the latency histogram
                    # — the no-op branch would flood it with ~µs entries
                    ck_hist = (ins.checkpoint_seconds
                               if obs_on and self._ckpt_now
                               and self.checkpoint_path is not None else None)
                    with span("train/checkpoint", histogram=ck_hist):
                        self._run_checkpoint(state)
        finally:
            # the step still in flight, also on the way out of an
            # exception: every dispatched step is reported exactly once
            report(keep=0)
            for m in ts.methods:
                m.state.settle()

        model.load_params_dict(params)
        model.load_buffers_dict(buffers)
        return model  # caller's finally joins any pending checkpoint write

    # ------------------------------------------------------------- aux steps
    def _should_fire_aux(self, state) -> bool:
        fire = False
        if self.validation_trigger is not None:
            self._val_now = self.validation_trigger(state)
            fire = fire or self._val_now
        else:
            self._val_now = False
        if self.checkpoint_trigger is not None:
            self._ckpt_now = self.checkpoint_trigger(state)
            fire = fire or self._ckpt_now
        else:
            self._ckpt_now = False
        return fire

    def _run_validation(self, state):
        if not self._val_now or self.validation_dataset is None:
            return
        from bigdl_tpu.optim.evaluator import Evaluator

        results = Evaluator(self.model).test(
            self.validation_dataset, self.validation_methods,
            batch_size=getattr(self, "validation_batch_size", None) or self.batch_size)
        for method, res in results:
            value, _ = res.result()
            logger.info("%s is %s", method.name(), res)
            if method.name() in ("Top1Accuracy", "Top5Accuracy"):
                state["score"] = value
            if self.validation_summary is not None:
                self.validation_summary.add_scalar(method.name(), value, state["neval"] - 1)

    def _run_checkpoint(self, state):
        if not self._ckpt_now or self.checkpoint_path is None:
            return
        from bigdl_tpu.utils import file as bt_file

        bt_file.makedirs(self.checkpoint_path)
        tag = f"{state['neval'] - 1}"

        if not getattr(self, "checkpoint_async", False):
            bt_file.save_module(
                self.model,
                os.path.join(self.checkpoint_path, f"model.{tag}"),
                overwrite=True)
            self.optim_method.save(
                os.path.join(self.checkpoint_path, f"optimMethod.{tag}"),
                overwrite=True)
            return
        import copy
        import threading

        self.join_pending_checkpoint()  # one in flight; surface write errors
        # snapshot NOW (jax arrays are immutable, so deepcopy captures a
        # consistent instant); the thread only serializes and writes
        model_snap = self.model.clone_module()
        method_snap = copy.deepcopy(self.optim_method)
        path = self.checkpoint_path

        def write():
            # write-then-rename: a crash mid-write never leaves a torn
            # model.{tag} as the newest checkpoint on disk. Object stores
            # have atomic single-shot puts, so remote paths write the
            # final names directly.
            try:
                if bt_file.is_remote(path):
                    bt_file.save_module(
                        model_snap, os.path.join(path, f"model.{tag}"),
                        overwrite=True)
                    method_snap.save(
                        os.path.join(path, f"optimMethod.{tag}"),
                        overwrite=True)
                    return
                mtmp = os.path.join(path, f".model.{tag}.tmp")
                otmp = os.path.join(path, f".optimMethod.{tag}.tmp")
                bt_file.save_module(model_snap, mtmp, overwrite=True)
                method_snap.save(otmp, overwrite=True)
                os.replace(mtmp, os.path.join(path, f"model.{tag}"))
                os.replace(otmp, os.path.join(path, f"optimMethod.{tag}"))
            except BaseException as e:  # re-raised at the next join
                self._ckpt_error = e

        t = threading.Thread(target=write, daemon=True, name=f"ckpt-{tag}")
        t.start()
        self._ckpt_thread = t

    def join_pending_checkpoint(self):
        """Wait for an in-flight async checkpoint write and re-raise any
        error it hit (no-op when nothing is pending)."""
        t = getattr(self, "_ckpt_thread", None)
        if t is not None:
            t.join()
            self._ckpt_thread = None
        err = getattr(self, "_ckpt_error", None)
        if err is not None:
            self._ckpt_error = None
            raise RuntimeError("async checkpoint write failed") from err
