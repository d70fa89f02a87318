"""BatchNorm's training pass (``nn/normalization.py _batch_norm_train``: the
statistics from one read, a hand-written two-pass backward) against autodiff
of the plain two-read formula (tests/two_read_batchnorm.py), over what the
one path has to adapt to: the layout, the rank, the affine, the dtype, the
``grad_accum`` scan, a mesh axis. Evaluation mode is bit-equal.

Tolerances: both sides are float32 sums of the same numbers in another order,
so outputs agree to a few float32 steps of the values' size (1e-5 relative to
the largest magnitude; gradients are sums over the batch and get 1e-4). With
bfloat16 input both sides round ``y`` and ``dx`` to 8 bits, and a value on a
rounding edge may land one step apart: 2**-7 relative."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu import nn
from bigdl_tpu.nn.module import pure_apply
from bigdl_tpu.nn.normalization import _batch_norm_train
from two_read_batchnorm import (TwoReadBatchNormalization,
                                TwoReadSpatialBatchNormalization,
                                TwoReadVolumetricBatchNormalization)

PAIRS = {
    2: (nn.BatchNormalization, TwoReadBatchNormalization),
    4: (nn.SpatialBatchNormalization, TwoReadSpatialBatchNormalization),
    5: (nn.VolumetricBatchNormalization, TwoReadVolumetricBatchNormalization),
}
C = 6


def _close(got, want, rel, atol=0.0):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    bound = rel * max(float(np.abs(want).max()), 1e-6) + atol
    assert float(np.abs(got - want).max()) <= bound, (
        float(np.abs(got - want).max()), bound)


def _shape(n_dim, batched, fmt):
    spatial = (5, 4, 3)[:n_dim - 2]
    lead = (8,) if batched else ()
    return (lead + spatial + (C,) if fmt == "NHWC"
            else lead + (C,) + spatial)


def _pair(n_dim, fmt, affine, **kw):
    """The module and its two-read reference with the same random
    parameters and running statistics, both in training mode."""
    rng = np.random.default_rng(0)
    mods = [cls(C, affine=affine, format=fmt, **kw) for cls in PAIRS[n_dim]]
    params = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        mods[0].params_dict())
    bufs = {"~buffers": {
        "running_mean": jnp.asarray(rng.standard_normal(C) * 0.3, jnp.float32),
        "running_var": jnp.asarray(rng.uniform(0.5, 2.0, C), jnp.float32)}}
    for m in mods:
        m.training_mode()
    return mods, params, bufs


def _loss_and_grads(module, params, bufs, x, t):
    fn = pure_apply(module)

    def loss(p, x):
        y, nb = fn(p, bufs, x, training=True)
        return jnp.sum(y.astype(jnp.float32) * t), (y, nb)

    (_, (y, nb)), (gp, gx) = jax.jit(jax.value_and_grad(
        loss, (0, 1), has_aux=True))(params, x)
    return y, nb, gp, gx


CASES = [
    # n_dim, batched, format, affine, dtype
    (2, True, "NCHW", True, "float32"),
    (2, True, "NHWC", False, "float32"),
    (2, False, "NCHW", True, "float32"),
    (4, True, "NCHW", True, "float32"),
    (4, True, "NHWC", True, "float32"),
    (4, True, "NHWC", False, "float32"),
    (4, False, "NCHW", True, "float32"),
    (4, False, "NHWC", True, "float32"),
    (5, True, "NCHW", True, "float32"),
    (5, True, "NHWC", False, "float32"),
    (2, True, "NCHW", True, "bfloat16"),
    (4, True, "NHWC", True, "bfloat16"),
    (4, True, "NCHW", False, "bfloat16"),
    (5, True, "NHWC", True, "bfloat16"),
]


@pytest.mark.parametrize("n_dim,batched,fmt,affine,dtype", CASES)
def test_training_pass_matches_autodiff_of_the_two_read_formula(
        n_dim, batched, fmt, affine, dtype):
    (new, old), params, bufs = _pair(n_dim, fmt, affine)
    rng = np.random.default_rng(1)
    shape = _shape(n_dim, batched, fmt)
    x = jnp.asarray(rng.standard_normal(shape) * 2.0 + 0.7, dtype)
    t = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    got = _loss_and_grads(new, params, bufs, x, t)
    want = _loss_and_grads(old, params, bufs, x, t)
    f32 = dtype == "float32"
    if not f32:
        # autodiff of the bfloat16 forward sums the parameters' gradients
        # IN bfloat16 (2 % off here); the pass sums in float32, so its
        # gradients are held to the two-read formula on the same numbers
        # in float32 (the cotangent of a bfloat16 output is bfloat16)
        want = want[:2] + _loss_and_grads(
            old, params, bufs, x.astype(jnp.float32),
            t.astype(dtype).astype(jnp.float32))[2:]
    y, nb, gp, gx = got
    assert y.dtype == x.dtype and gx.dtype == x.dtype
    _close(y, want[0], 1e-5 if f32 else 2.0 ** -7)
    # one sample a channel (a lone feature vector): the variance is 0, the
    # output is the bias and the true gradients of input and weight are 0;
    # what either side computes there is rounding of terms ~rsqrt(eps) large
    atol = 1e-4 if x.ndim == 1 else 0.0
    _close(gx, want[3], 1e-4 if f32 else 2.0 ** -7, atol)
    for g, w, p in zip(jax.tree.leaves(gp), jax.tree.leaves(want[2]),
                       jax.tree.leaves(params)):
        assert g.dtype == p.dtype
        _close(g, w, 1e-4, atol)
    # the updated running statistics (float32 whatever the input is)
    for g, w in zip(jax.tree.leaves(nb), jax.tree.leaves(want[1])):
        assert g.dtype == w.dtype == jnp.float32
        _close(g, w, 1e-5)


def _two_read_stats(x, weight, bias, ch_ax, eps):
    axes = tuple(i for i in range(x.ndim) if i != ch_ax)
    shape = [1] * x.ndim
    shape[ch_ax] = -1
    mean, var = jnp.mean(x, axis=axes), jnp.var(x, axis=axes)
    y = ((x - mean.reshape(shape)) * jax.lax.rsqrt(var + eps).reshape(shape)
         * weight.reshape(shape) + bias.reshape(shape))
    return y, mean, var


@pytest.mark.parametrize("ch_ax", [1, 3])
def test_cotangents_on_the_mean_and_var_outputs(ch_ax):
    """The function is a VJP for a caller that USES the statistics too."""
    rng = np.random.default_rng(2)
    shape = (4, C, 5, 3) if ch_ax == 1 else (4, 5, 3, C)
    x = jnp.asarray(rng.standard_normal(shape) * 1.5 + 0.5, jnp.float32)
    w, b, pivot, a_m, a_v = (
        jnp.asarray(rng.standard_normal(C), jnp.float32) for _ in range(5))
    t = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def loss(f):
        def fn(x, w, b):
            y, mean, var = f(x, w, b)
            return (jnp.sum(y * t) + jnp.sum(mean * a_m)
                    + jnp.sum(var * var * a_v))
        return jax.jit(jax.grad(fn, (0, 1, 2)))(x, w, b)

    got = loss(lambda x, w, b: _batch_norm_train(x, w, b, pivot, ch_ax,
                                                 1e-3, None))
    want = loss(lambda x, w, b: _two_read_stats(x, w, b, ch_ax, 1e-3))
    for g, r in zip(got, want):
        _close(g, r, 1e-4)


def test_the_pivot_changes_nothing_but_rounding():
    """``var = E[(x - c)^2] - E[x - c]^2`` for any per-channel ``c``."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((8, 5, 4, C)) + 0.5, jnp.float32)
    w, b = jnp.ones(C), jnp.zeros(C)
    outs = [_batch_norm_train(x, w, b, jnp.full((C,), c, jnp.float32),
                              3, 1e-5, None) for c in (0.0, 0.5, -3.0)]
    for other in outs[1:]:
        for g, r in zip(other, outs[0]):
            _close(g, r, 1e-5)


def _float64_truth(x, w, b, t, eps):
    """y and dx of training-mode BatchNorm over NHWC in float64 numpy."""
    x, t = x.astype(np.float64), t.astype(np.float64)
    n = x.size / x.shape[-1]
    inv = 1.0 / np.sqrt(x.var((0, 1, 2)) + eps)
    xh = (x - x.mean((0, 1, 2))) * inv
    dx = w * inv * (t - t.sum((0, 1, 2)) / n - xh * (t * xh).sum((0, 1, 2)) / n)
    return xh * w + b, dx


@pytest.mark.parametrize("pivot,rel", [("running_mean_near", 1e-5),
                                       ("fresh_buffers", 0.05)])
def test_a_channel_whose_mean_is_100x_its_spread(pivot, rel):
    """The cancellation case of the one-read variance, against float64.
    ``E[(x - c)^2] - E[x - c]^2`` loses in float32 the digits that
    ``E[x - c]^2`` has over the variance, times what the length of the sum
    adds (8192 values a channel here, on the CPU's sequential sums).

    With the running mean within a standard deviation of the batch mean (a
    model some dozens of steps into training, or loaded trained) nothing is
    lost: measured 1.3e-6 of the output and 1.6e-7 of the input gradient,
    BETTER than the two-read formula's 2.7e-5 and 1.1e-6 (``jnp.mean`` of
    8192 values near 100 is itself good to 1e-5 only; the pivot makes the
    sums small). Held to 1e-5.

    With fresh buffers (running mean 0) the ratio is 100^2: measured 1.8e-2
    of the output, 5.8e-3 of the input gradient (at 10x: 2.2e-4, at 1x, a
    convolution's output: 3e-6). So the FIRST steps of a model that
    normalises such a channel are good to percents, until the running mean
    has moved toward the channel's (momentum 0.1: 99 % of the way after 44
    steps). That is the price of one read with no prior, and 0.05 is what it
    is held to; the batch mean itself, and so the running mean, is a plain
    sum and good to 1e-5 either way."""
    rng = np.random.default_rng(4)
    sigma = np.array([1.0, 0.1, 3.0, 1.0, 0.5, 2.0], np.float32)
    x = (rng.standard_normal((32, 16, 16, C)) * sigma
         + 100.0 * sigma).astype(np.float32)
    t = rng.standard_normal(x.shape).astype(np.float32)
    (new, _), params, bufs = _pair(4, "NHWC", True)
    start = (100.8 * sigma if pivot == "running_mean_near"
             else np.zeros(C, np.float32))
    bufs = {"~buffers": dict(bufs["~buffers"],
                             running_mean=jnp.asarray(start))}
    y, nb, _, gx = _loss_and_grads(new, params, bufs, jnp.asarray(x),
                                   jnp.asarray(t))
    w, b = (np.asarray(params["~params"][k], np.float64)
            for k in ("weight", "bias"))
    y64, dx64 = _float64_truth(x, w, b, t, new.eps)
    _close(y, y64, rel)
    _close(gx, dx64, rel)
    _close(nb["~buffers"]["running_mean"],
           0.9 * start + 0.1 * x.astype(np.float64).mean((0, 1, 2)), 1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("affine", [True, False])
def test_evaluation_mode_is_bit_equal(affine, dtype):
    (new, old), params, bufs = _pair(4, "NHWC", affine)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((4, 5, 3, C)),
                    dtype)
    outs = []
    for m in (new, old):
        m.evaluate()
        y, nb = jax.jit(lambda p, b, x, f=pure_apply(m): f(
            p, b, x, training=False))(params, bufs, x)
        assert all(np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(nb), jax.tree.leaves(bufs)))
        outs.append(np.asarray(y.astype(jnp.float32)))
    assert np.array_equal(*outs)


def _small_net(bn_cls):
    from bigdl_tpu.utils import random as bt_random

    bt_random.set_seed(11)
    model = nn.Sequential(
        nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1, format="NHWC"),
        bn_cls(8, format="NHWC"), nn.ReLU(),
        nn.SpatialConvolution(8, 8, 3, 3, 2, 2, 1, 1, format="NHWC"),
        bn_cls(8, format="NHWC"), nn.ReLU(),
        nn.Reshape([8 * 4 * 4]), nn.Linear(8 * 4 * 4, 5), nn.LogSoftMax())
    return model.training_mode()


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_train_step_with_grad_accum_matches_the_two_read_model(grad_accum):
    """Through ``make_train_step`` and its ``lax.scan`` over micro-batches
    (a custom_vjp inside a scan body, the statistics per micro-batch)."""
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import make_train_step

    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((8, 8, 8, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(1, 6, (8,)), jnp.float32)
    results = []
    for cls in PAIRS[4]:
        model = _small_net(cls)
        ts = make_train_step(model, nn.ClassNLLCriterion(),
                             SGD(learning_rate=0.1), grad_accum=grad_accum)
        params = model.params_dict()
        results.append(jax.jit(ts.step_with_stats)(
            params, model.buffers_dict(), ts.init_slots(params), x, y,
            ts.current_lrs(), jax.random.PRNGKey(0)))
    # atol: a convolution's bias before a BatchNorm has a true gradient of 0
    for g, w in zip(jax.tree.leaves(results[0]), jax.tree.leaves(results[1])):
        _close(g, w, 1e-4, atol=1e-6)


# ------------------------------------------------------------------ sync-BN
@pytest.fixture(scope="module")
def mesh2():
    return Mesh(np.asarray(jax.devices()[:2]), ("data",))


@pytest.mark.parametrize("grad", ["through_shard_map", "inside_shard_map"])
@pytest.mark.parametrize("check_vma", [True, False])
@pytest.mark.parametrize("sync", [True, False])
def test_batchnorm_under_shard_map_equals_the_unsharded_call(
        mesh2, sync, check_vma, grad):
    """``global_stats_axis`` under ``shard_map`` over two devices: outputs,
    running statistics and every gradient equal the unsharded call over the
    whole batch, with or without the varying-axes typing, differentiated
    from outside the map or inside it (``DistriOptimizer``'s way: each
    shard's own part of the parameters' gradients, summed by the caller).
    ``sync=False`` is the same module with per-shard statistics against the
    unsharded call on each half."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((8, 5, 5, C)) * 2 + 1, jnp.float32)
    t = jnp.asarray(rng.standard_normal((8, 5, 5, C)), jnp.float32)
    (ref, _), params, bufs = _pair(4, "NHWC", True)
    (mod, _), _, _ = _pair(4, "NHWC", True,
                           global_stats_axis="data" if sync else None)
    f_mod = pure_apply(mod)
    data = P("data")

    if sync:
        y0, nb0, gp0, gx0 = _loss_and_grads(ref, params, bufs, x, t)
    else:
        halves = [_loss_and_grads(ref, params, bufs, x[i:i + 4], t[i:i + 4])
                  for i in (0, 4)]
        y0, gx0 = (jnp.concatenate([h[i] for h in halves]) for i in (0, 3))
        gp0 = jax.tree.map(jnp.add, halves[0][2], halves[1][2])
        nb0 = None

    def local(p, x, t):
        y, nb = f_mod(p, bufs, x, training=True)
        return jnp.sum(y * t), (y, nb)

    if grad == "through_shard_map":
        def total(p, x):
            losses, (y, nb) = jax.shard_map(
                lambda p, x, t: jax.tree.map(
                    lambda a: a[None], local(p, x, t)),
                mesh=mesh2, in_specs=(P(), data, data),
                out_specs=(data, (data, data)), check_vma=check_vma)(p, x, t)
            return jnp.sum(losses), (y, nb)

        (_, (y1, nb1)), (gp1, gx1) = jax.jit(jax.value_and_grad(
            total, (0, 1), has_aux=True))(params, x)
        y1 = y1.reshape(x.shape)
        nb1 = jax.tree.map(lambda a: a[0], nb1)
    else:
        def body(p, x, t):
            (_, (y, nb)), (gp, gx) = jax.value_and_grad(
                local, (0, 1), has_aux=True)(p, x, t)
            if not check_vma:
                gp = jax.lax.psum(gp, "data")
            return y, jax.tree.map(lambda a: a[None], nb), gp, gx

        y1, nb1, gp1, gx1 = jax.jit(jax.shard_map(
            body, mesh=mesh2, in_specs=(P(), data, data),
            out_specs=(data, data, P(), data), check_vma=check_vma))(
                params, x, t)
        nb1 = jax.tree.map(lambda a: a[0], nb1)
    _close(y1, y0, 1e-5)
    _close(gx1, gx0, 1e-4)
    for g, w in zip(jax.tree.leaves(gp1), jax.tree.leaves(gp0)):
        _close(g, w, 1e-4)
    if sync:
        for g, w in zip(jax.tree.leaves(nb1), jax.tree.leaves(nb0)):
            _close(g, w, 1e-5)


@pytest.mark.parametrize("check_vma", [True, False])
def test_sharded_statistics_cotangents(mesh2, check_vma):
    """Sync-BN's function with a caller that uses the global statistics:
    their cotangents arrive whole under the typing and in parts without."""
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((8, 5, C)) + 0.5, jnp.float32)
    t = jnp.asarray(rng.standard_normal((8, 5, C)), jnp.float32)
    w, b, a_m, a_v = (jnp.asarray(rng.standard_normal(C), jnp.float32)
                      for _ in range(4))

    def loss(stats, y, t):
        _, mean, var = stats
        return jnp.sum(y * t), jnp.sum(mean * a_m + var * var * a_v)

    def whole(x, w, b):
        out = _two_read_stats(x, w, b, 2, 1e-3)
        return sum(loss(out, out[0], t))

    def sharded(x, w, b):
        def body(x, w, b, t):
            out = _batch_norm_train(x, w, b, jnp.zeros(C), 2, 1e-3, "data")
            own, of_stats = loss(out, out[0], t)
            return own[None], of_stats

        own, of_stats = jax.shard_map(
            body, mesh=mesh2, in_specs=(P("data"), P(), P(), P("data")),
            out_specs=(P("data"), P()), check_vma=check_vma)(x, w, b, t)
        return jnp.sum(own) + of_stats

    got = jax.jit(jax.grad(sharded, (0, 1, 2)))(x, w, b)
    want = jax.jit(jax.grad(whole, (0, 1, 2)))(x, w, b)
    for g, r in zip(got, want):
        _close(g, r, 1e-4)
