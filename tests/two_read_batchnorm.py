"""BatchNorm written the plain way, kept as the tests' reference: the batch
statistics as ``jnp.mean`` then ``jnp.var`` (two reads of the input) and the
backward left to autodiff. ``nn/normalization.py`` computed its training pass
like this until PR 32; its one-read pass with a hand-written backward is
compared with this one (tests/test_batchnorm_vjp.py) and has to stay cheaper
than it by XLA's byte count (tests/test_chip_compile.py)."""

import jax
import jax.numpy as jnp

from bigdl_tpu import nn


def two_read_forward(self, x):
    """``BatchNormalization.forward`` as autodiff of the textbook formula."""
    if self.format == "NHWC":
        ch_ax = x.ndim - 1
    else:
        ch_ax = 1 if x.ndim >= self.n_dim else 0
    axes = tuple(i for i in range(x.ndim) if i != ch_ax)
    x32 = x.astype(jnp.float32)
    if self.training:
        mean = jnp.mean(x32, axis=axes)
        var = jnp.var(x32, axis=axes)
        n = x.size / x.shape[ch_ax]
        if self.global_stats_axis is not None:
            mean_g = jax.lax.pmean(mean, self.global_stats_axis)
            var = (jax.lax.pmean(var + mean ** 2, self.global_stats_axis)
                   - mean_g ** 2)
            mean = mean_g
            n = n * jax.lax.psum(1, self.global_stats_axis)
        unbiased = var * n / max(1.0, n - 1)
        self._set_buffer(
            "running_mean",
            ((1 - self.momentum) * self.running_mean
             + self.momentum * mean).astype(self.running_mean.dtype))
        self._set_buffer(
            "running_var",
            ((1 - self.momentum) * self.running_var
             + self.momentum * unbiased).astype(self.running_var.dtype))
    else:
        mean, var = self.running_mean, self.running_var
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + self.eps)
    if self.affine:
        scale = self.weight.astype(jnp.float32) * inv
        shift = self.bias.astype(jnp.float32) - mean * scale
    else:
        scale = inv
        shift = -mean * inv
    shape = [1] * x.ndim
    shape[ch_ax] = x.shape[ch_ax]
    return (x * scale.reshape(shape).astype(x.dtype)
            + shift.reshape(shape).astype(x.dtype))


class TwoReadBatchNormalization(nn.BatchNormalization):
    forward = two_read_forward


class TwoReadSpatialBatchNormalization(nn.SpatialBatchNormalization):
    forward = two_read_forward


class TwoReadVolumetricBatchNormalization(nn.VolumetricBatchNormalization):
    forward = two_read_forward
