"""ResNet-50 (He et al. 2015, Table 1; stride on the 3x3 convolution, as the
reference harness's model has it) forward, loss and gradients in plain float32
jax.numpy, and the SGD recipe's update, matmuls and convolutions at precision
"highest". Imports nothing of the program; weights from
``benchmark/weights.py`` (HWIO convolutions with bias).

Training mode throughout: BatchNorm normalises with the batch's own biased
variance, eps 1e-3. Loss = mean cross-entropy + 0.5 * l2 * sum of squares of
every convolution's and the classifier's weight and bias (the model's own L2
regulariser). Each block is rematerialised in backward (``jax.checkpoint``) to
halve the memory; that changes no value.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

BLOCKS = (3, 4, 6, 3)
BN_EPS = 1e-3


def _conv(x, p, name, stride, pad):
    y = lax.conv_general_dilated(
        x, p[name + ".w"], (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p[name + ".b"]


def _bn(x, p, name):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p[name + ".g"] + p[name + ".b"]


def _block(x, p, prefix, stride, project):
    y = jax.nn.relu(_bn(_conv(x, p, prefix + ".c1", 1, 0), p, prefix + ".n1"))
    y = jax.nn.relu(_bn(_conv(y, p, prefix + ".c2", stride, 1), p,
                        prefix + ".n2"))
    y = _bn(_conv(y, p, prefix + ".c3", 1, 0), p, prefix + ".n3")
    if project:
        x = _bn(_conv(x, p, prefix + ".sc", stride, 0), p, prefix + ".sn")
    return jax.nn.relu(x + y)


def logits(p, x, cast=None):
    """(N, 224, 224, 3) -> (N, classes). ``cast`` rounds the activations
    between blocks to a lower type (the precision control only)."""
    keep = (lambda a: a) if cast is None else (lambda a: a.astype(cast))
    x = jax.nn.relu(_bn(_conv(x, p, "conv1", 2, 3), p, "bn1"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for s, n in enumerate(BLOCKS):
        for b in range(n):
            stride = 2 if (s > 0 and b == 0) else 1
            blk = functools.partial(_block, prefix=f"l{s}.b{b}", stride=stride,
                                    project=(b == 0))
            x = keep(jax.checkpoint(blk)(keep(x), p))
    x = jnp.mean(x, axis=(1, 2))
    return x @ p["fc.w"] + p["fc.b"]


def _regularised(name):
    """Convolutions and the classifier (weight and bias); no BatchNorm."""
    stem = name.rsplit(".", 1)[0].rsplit(".", 1)[-1]
    return stem[0] in "cf" or stem == "sc"


def loss_fn(p, x, y, l2, cast=None):
    z = logits(p, x, cast).astype(jnp.float32)
    labels = y.reshape(-1).astype(jnp.int32) - 1          # 1-based labels
    nll = -jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1),
                               labels[:, None], axis=1)
    reg = sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
              for k, v in p.items() if _regularised(k))
    return jnp.mean(nll) + 0.5 * l2 * reg


@functools.partial(jax.jit, static_argnums=(3, 4))
def _shard_loss_and_grads(p, x, y, l2, cast):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(p, x, y, l2, cast)


def loss_and_grads(p, x, y, l2, shards=1, cast=None):
    """Loss and gradient of one step. ``shards`` > 1: the batch in that many
    contiguous parts, each normalised by its own BatchNorm statistics, their
    losses and gradients averaged: synchronous data parallelism. One part
    at a time, one program each, so that four parts need one part's memory."""
    n = x.shape[0] // shards
    total, grads = 0.0, None
    for i in range(shards):
        lo, g = _shard_loss_and_grads(p, x[i * n:(i + 1) * n],
                                      y[i * n:(i + 1) * n], l2, cast)
        total = total + lo / shards
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total, jax.tree.map(lambda g: g / shards, grads)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def sgd_update(p, v, g, lr, momentum, dampening, weight_decay):
    """The recipe's SGD: g += wd * p; v = m * v + (1 - dampening) * g;
    p -= lr * v. Returns (p, v, g as the optimizer used it)."""
    g = jax.tree.map(lambda gi, pi: gi + weight_decay * pi.astype(gi.dtype),
                     g, p)
    v = jax.tree.map(lambda vi, gi: momentum * vi + (1 - dampening) * gi, v, g)
    p = jax.tree.map(lambda pi, vi: (pi - lr * vi).astype(pi.dtype), p, v)
    return p, v, g


def follow(p0, batches, recipe, shards=1, cast=None):
    """Drive the reference through ``batches`` [(x, y), ...] from ``p0``.
    Returns each step's loss and the parameters after each step."""
    p = p0
    v = jax.tree.map(jnp.zeros_like, p0)
    losses, after = [], []
    for x, y in batches:
        loss, g = loss_and_grads(p, jnp.asarray(x), jnp.asarray(y),
                                 float(recipe["l2"]), shards, cast)
        p, v, _ = sgd_update(
            p, v, g, float(recipe["learning_rate"]), float(recipe["momentum"]),
            float(recipe["dampening"]), float(recipe["weight_decay"]))
        losses.append(float(loss))
        after.append(p)
    return losses, after
