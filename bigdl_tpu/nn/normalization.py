"""Normalization layers.

Reference: nn/BatchNormalization.scala (446 LoC), nn/SpatialBatchNormalization.scala,
nn/Normalize.scala, nn/SpatialCrossMapLRN.scala, nn/SpatialWithinChannelLRN.scala,
nn/SpatialContrastive/Divisive/SubtractiveNormalization.scala, nn/NormalizeScale.scala.

BatchNorm running stats are Module *buffers*: under ``pure_apply`` the updated
stats come back as the new-buffers pytree (functional state threading), which
is the jit-safe equivalent of the reference's in-place running-mean updates.
The reference's sync-BN (thread-level ParameterSynchronizer,
utils/ParameterSynchronizer.scala:29) maps to a ``psum`` over the batch axis
when run under shard_map — exposed via ``global_stats_axis``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Module


def _per_channel(v, x, ch_ax):
    shape = [1] * x.ndim
    shape[ch_ax] = x.shape[ch_ax]
    return v.reshape(shape)


def _scale_shift(x, mean, var, weight, bias, ch_ax, eps):
    """``(x - mean) * rsqrt(var + eps) * weight + bias`` folded into one
    per-channel scale and shift. The statistics are float32 (bf16
    accumulations drift), but the output stays in the INPUT dtype: a bf16
    activation must not be promoted to f32 by the f32 running buffers, or
    every downstream matmul/conv silently runs at f32 and the MXU loses
    half its rate."""
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    if weight is not None:
        scale = weight.astype(jnp.float32) * inv
        shift = bias.astype(jnp.float32) - mean * scale
    else:
        scale = inv
        shift = -mean * inv
    return (x * _per_channel(scale, x, ch_ax).astype(x.dtype)
            + _per_channel(shift, x, ch_ax).astype(x.dtype))


def _channel_sums(a, b, ch_ax):
    """``sum(a)`` and ``sum(a * b)`` per channel from ONE read of the
    operands: sibling reductions over the same operands compile to one
    multi-output fusion."""
    axes = tuple(i for i in range(a.ndim) if i != ch_ax)
    return jnp.sum(a, axis=axes), jnp.sum(a * b, axis=axes)


def _count(x, ch_ax, axis_name):
    """Elements per channel, over ``axis_name``'s shards too."""
    n = x.size / x.shape[ch_ax]
    return n if axis_name is None else n * jax.lax.psum(1, axis_name)


def _cotangent_of(primal, ct):
    """``ct`` typed as ``primal``'s cotangent. Under ``shard_map``'s
    varying-axes typing a parameter replicated over a mesh axis takes the
    SUM over that axis of what the shards' rows give it (autodiff does this
    in the transpose of the implicit broadcast; a custom rule has to).
    Without the typing (``check_vma=False``) nothing varies by type, a
    shard hands back its own part and the caller sums, as with autodiff."""
    extra = tuple(jax.typeof(ct).vma - jax.typeof(primal).vma)
    return (jax.lax.psum(ct, extra) if extra else ct).astype(primal.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _batch_norm_train(x, weight, bias, pivot, ch_ax, eps, axis_name):
    """Training-mode batch normalisation as ONE operation: returns
    ``(y, mean, var)`` with the biased batch variance, the statistics over
    every axis but ``ch_ax`` (and over ``axis_name``'s shards: sync-BN).
    ``weight`` / ``bias`` are None without the affine.

    A memory-bound layer costs the passes it makes over the activation, so
    both directions are written for the fewest: the statistics come from
    one read of ``x``, and the backward below makes two passes and keeps
    ``x`` alone, where autodiff of mean / var / rsqrt kept the centred and
    the normalised copy and read them again.

    One read means ``var = E[(x - c)^2] - E[x - c]^2``, the same variance
    for ANY per-channel constant ``c`` = ``pivot`` (no gradient flows to
    it), but in float32 the difference loses the digits that ``E[x - c]^2``
    has over the variance: with ``c = 0`` a channel whose mean is 100x its
    spread keeps 2-3 of 7. The caller passes its running mean, known before
    the read (a pivot taken from ``x`` itself would keep the sums out of
    the fusion that produces ``x``), so the loss is that of a batch mean's
    distance from the running mean, not from zero."""
    return _batch_norm_train_fwd(x, weight, bias, pivot, ch_ax, eps,
                                 axis_name)[0]


def _batch_norm_train_fwd(x, weight, bias, pivot, ch_ax, eps, axis_name):
    c = pivot.astype(jnp.float32)
    xs = x.astype(jnp.float32) - _per_channel(c, x, ch_ax)
    s, ss = _channel_sums(xs, xs, ch_ax)
    if axis_name is not None:
        s, ss = jax.lax.psum((s, ss), axis_name)
    n = _count(x, ch_ax, axis_name)
    shifted_mean = s / n
    mean = c + shifted_mean
    var = jnp.maximum(ss / n - shifted_mean * shifted_mean, 0.0)
    y = _scale_shift(x, mean, var, weight, bias, ch_ax, eps)
    return (y, mean, var), (x, weight, bias, mean, jax.lax.rsqrt(var + eps))


def _batch_norm_train_bwd(ch_ax, eps, axis_name, res, cts):
    x, weight, bias, mean, inv = res
    dy, dmean, dvar = cts
    dy32 = dy.astype(jnp.float32)
    xc = x.astype(jnp.float32) - _per_channel(mean, x, ch_ax)
    # pass 1, one read of dy and x: sum(dy) and sum(dy * (x - mean))
    db_own, dxc_own = _channel_sums(dy32, xc, ch_ax)
    db, dxc = db_own, dxc_own
    if axis_name is not None:
        db, dxc = jax.lax.psum((db_own, dxc_own), axis_name)
        # the statistics are sums over the shards: typed, their cotangents
        # arrive whole; untyped, each shard holds a part (psum's transpose)
        if axis_name not in jax.typeof(jax.lax.axis_index(axis_name)).vma:
            dmean, dvar = jax.lax.psum((dmean, dvar), axis_name)
    n = _count(x, ch_ax, axis_name)
    scale = inv if weight is None else weight.astype(jnp.float32) * inv
    # pass 2: dx = scale * (dy - db/n - xc * inv^2 * dxc/n), and what the
    # mean and var outputs hand back (d mean/dx = 1/n, d var/dx = 2 xc/n)
    k_xc = (2.0 * dvar - scale * inv * inv * dxc) / n
    k_1 = (dmean - scale * db) / n
    dx = _cotangent_of(x, dy32 * _per_channel(scale, x, ch_ax)
                       + xc * _per_channel(k_xc, x, ch_ax)
                       + _per_channel(k_1, x, ch_ax))
    if weight is None:
        return dx, None, None, None
    return (dx, _cotangent_of(weight, dxc_own * inv),
            _cotangent_of(bias, db_own), None)


_batch_norm_train.defvjp(_batch_norm_train_fwd, _batch_norm_train_bwd)


class BatchNormalization(Module):
    """BN over (batch, feat) (reference: nn/BatchNormalization.scala)."""

    n_dim = 2

    def __init__(self, n_output: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True, init_weight=None, init_bias=None,
                 global_stats_axis: str = None, format: str = "NCHW"):
        super().__init__()
        self.n_output = n_output
        self.eps = eps
        self.momentum = momentum
        self.affine = affine
        self.global_stats_axis = global_stats_axis
        from bigdl_tpu.nn.conv import _check_format
        # NHWC puts the channel on the minor axis (DataFormat parity)
        self.format = _check_format(format)
        if affine:
            w = jnp.asarray(init_weight) if init_weight is not None else jnp.ones((n_output,))
            b = jnp.asarray(init_bias) if init_bias is not None else jnp.zeros((n_output,))
            self.register_parameter("weight", w)
            self.register_parameter("bias", b)
        self.register_buffer("running_mean", jnp.zeros((n_output,)))
        self.register_buffer("running_var", jnp.ones((n_output,)))

    def forward(self, input):
        x = input
        # batched input has n_dim dims (channel at 1); unbatched n_dim-1 (channel at 0);
        # NHWC keeps the channel on the minor axis in both cases
        if self.format == "NHWC":
            ch_ax = x.ndim - 1
        else:
            ch_ax = 1 if x.ndim >= self.n_dim else 0
        weight, bias = (self.weight, self.bias) if self.affine else (None, None)
        if not self.training:
            return _scale_shift(x, self.running_mean, self.running_var,
                                weight, bias, ch_ax, self.eps)
        axis = self.global_stats_axis
        y, mean, var = _batch_norm_train(x, weight, bias, self.running_mean,
                                         ch_ax, self.eps, axis)
        n = _count(x, ch_ax, axis)
        unbiased = var * n / max(1.0, n - 1)
        # keep the buffer dtype stable (f32 stats must not flip a bf16
        # buffer to f32 mid-training — that would retrace the jitted step)
        self._set_buffer(
            "running_mean",
            ((1 - self.momentum) * self.running_mean
             + self.momentum * mean).astype(self.running_mean.dtype),
        )
        self._set_buffer(
            "running_var",
            ((1 - self.momentum) * self.running_var
             + self.momentum * unbiased).astype(self.running_var.dtype),
        )
        return y

    def _extra_repr(self):
        return f"({self.n_output}, eps={self.eps}, momentum={self.momentum})"


class SpatialBatchNormalization(BatchNormalization):
    """BN over NCHW per-channel (reference: nn/SpatialBatchNormalization.scala)."""

    n_dim = 4


class VolumetricBatchNormalization(BatchNormalization):
    n_dim = 5


class Normalize(Module):
    """Lp-normalize along the feature dim (reference: nn/Normalize.scala)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10):
        super().__init__()
        self.p = p
        self.eps = eps

    def forward(self, input):
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(input), axis=1 if input.ndim > 1 else 0, keepdims=True)
        else:
            norm = jnp.sum(jnp.abs(input) ** self.p, axis=1 if input.ndim > 1 else 0,
                           keepdims=True) ** (1.0 / self.p)
        return input / (norm + self.eps)


class NormalizeScale(Module):
    """L2-normalize channels then learnable per-channel scale
    (reference: nn/NormalizeScale.scala, used by SSD)."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10, scale: float = 1.0,
                 size=None, w_regularizer=None):
        super().__init__()
        self.p, self.eps = p, eps
        size = tuple(size) if size is not None else (1,)
        self.register_parameter("weight", jnp.full(size, scale), regularizer=w_regularizer)

    def forward(self, input):
        norm = jnp.sum(jnp.abs(input) ** self.p, axis=1, keepdims=True) ** (1.0 / self.p)
        return input / (norm + self.eps) * self.weight


class SpatialCrossMapLRN(Module):
    """Local response normalization across channels
    (reference: nn/SpatialCrossMapLRN.scala)."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75, k: float = 1.0):
        super().__init__()
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k

    def forward(self, input):
        squeeze = input.ndim == 3
        x = input[None] if squeeze else input
        sq = x * x
        half = (self.size - 1) // 2
        # sum over a sliding channel window
        padded = jnp.pad(sq, ((0, 0), (half, self.size - 1 - half), (0, 0), (0, 0)))
        s = jax.lax.reduce_window(
            padded, 0.0, jax.lax.add,
            window_dimensions=(1, self.size, 1, 1),
            window_strides=(1, 1, 1, 1),
            padding="VALID",
        )
        denom = (self.k + self.alpha / self.size * s) ** self.beta
        out = x / denom
        return out[0] if squeeze else out


class SpatialWithinChannelLRN(Module):
    """LRN over a spatial window within each channel
    (reference: nn/SpatialWithinChannelLRN.scala)."""

    def __init__(self, size: int = 5, alpha: float = 1.0, beta: float = 0.75):
        super().__init__()
        self.size, self.alpha, self.beta = size, alpha, beta

    def forward(self, input):
        squeeze = input.ndim == 3
        x = input[None] if squeeze else input
        sq = x * x
        half = (self.size - 1) // 2
        s = jax.lax.reduce_window(
            sq, 0.0, jax.lax.add,
            window_dimensions=(1, 1, self.size, self.size),
            window_strides=(1, 1, 1, 1),
            padding=((0, 0), (0, 0), (half, self.size - 1 - half),
                     (half, self.size - 1 - half)),
        )
        denom = (1.0 + self.alpha / (self.size * self.size) * s) ** self.beta
        out = x / denom
        return out[0] if squeeze else out


class SpatialSubtractiveNormalization(Module):
    """Subtract kernel-weighted local mean (reference:
    nn/SpatialSubtractiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None):
        super().__init__()
        self.n_input_plane = n_input_plane
        if kernel is None:
            kernel = jnp.ones((9, 9))
        kernel = jnp.asarray(kernel, dtype=jnp.float32)
        self.kernel = kernel / jnp.sum(kernel)

    def _local_mean(self, x):
        k = self.kernel
        kh, kw = k.shape
        w = jnp.broadcast_to(k, (1, self.n_input_plane, kh, kw)) / self.n_input_plane
        pad = ((kh - 1) // 2, kh - 1 - (kh - 1) // 2), ((kw - 1) // 2, kw - 1 - (kw - 1) // 2)
        mean = jax.lax.conv_general_dilated(
            x, w, (1, 1), [pad[0], pad[1]], dimension_numbers=("NCHW", "OIHW", "NCHW")
        )
        # normalize by actual window coverage at borders
        ones = jnp.ones_like(x[:, :1])
        w1 = jnp.broadcast_to(k, (1, 1, kh, kw))
        coef = jax.lax.conv_general_dilated(
            ones, w1, (1, 1), [pad[0], pad[1]], dimension_numbers=("NCHW", "OIHW", "NCHW")
        )
        return mean / coef

    def forward(self, input):
        squeeze = input.ndim == 3
        x = input[None] if squeeze else input
        out = x - self._local_mean(x)
        return out[0] if squeeze else out


class SpatialDivisiveNormalization(Module):
    """Divide by local std estimate (reference: nn/SpatialDivisiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None, threshold: float = 1e-4,
                 thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.threshold, self.thresval = threshold, thresval

    def forward(self, input):
        squeeze = input.ndim == 3
        x = input[None] if squeeze else input
        local_sq_mean = self.sub._local_mean(x * x)
        std = jnp.sqrt(jnp.maximum(local_sq_mean, 0.0))
        mean_std = jnp.mean(std, axis=(2, 3), keepdims=True)
        denom = jnp.maximum(std, mean_std)
        denom = jnp.where(denom > self.threshold, denom, self.thresval)
        out = x / denom
        return out[0] if squeeze else out


class SpatialContrastiveNormalization(Module):
    """Subtractive then divisive normalization
    (reference: nn/SpatialContrastiveNormalization.scala)."""

    def __init__(self, n_input_plane: int = 1, kernel=None, threshold: float = 1e-4,
                 thresval: float = 1e-4):
        super().__init__()
        self.sub = SpatialSubtractiveNormalization(n_input_plane, kernel)
        self.div = SpatialDivisiveNormalization(n_input_plane, kernel, threshold, thresval)

    def forward(self, input):
        return self.div(self.sub(input))
