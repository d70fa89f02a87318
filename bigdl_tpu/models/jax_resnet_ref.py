"""Raw-JAX ResNet-50 train step — the measured ``vs_baseline`` denominator.

BASELINE.json's north star is ">70% of reference JAX MFU". Round 2 assumed
that constant (50% MFU); this module replaces the assumption with a
measurement: a minimal, framework-free ResNet-50 v1 written directly
against jax.numpy/lax (NHWC, bf16 compute, f32 masters, plain SGD with
momentum), timed by the same loop shape as models/perf.py. Whatever this
step achieves on the current chip IS the reference-JAX number; bench.py
reports our framework's throughput relative to 70% of it.

This file is deliberately independent of bigdl_tpu.nn so the comparison is
framework-vs-raw-JAX, not framework-vs-itself.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax import lax

BLOCKS = (3, 4, 6, 3)  # ResNet-50


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    std = (2.0 / fan_in) ** 0.5  # He normal, matching MSRA init
    return std * jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)


def init_params(key, num_classes: int = 1000):
    params = []

    def conv(kh, kw, cin, cout):
        nonlocal key
        key, sub = jax.random.split(key)
        params.append(_conv_init(sub, kh, kw, cin, cout))
        return len(params) - 1

    def bn(c, zero_gamma=False):
        params.append(jnp.zeros((c,)) if zero_gamma else jnp.ones((c,)))
        params.append(jnp.zeros((c,)))
        return len(params) - 2

    layout = []  # (kind, meta) program: interpreted by forward()
    layout.append(("conv", conv(7, 7, 3, 64), 2, "SAME"))
    layout.append(("bn", bn(64)))
    layout.append(("relu",))
    layout.append(("maxpool",))
    cin = 64
    for stage, n_blocks in enumerate(BLOCKS):
        width = 64 * (2 ** stage)
        for b in range(n_blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            proj = None
            if b == 0:
                proj = (conv(1, 1, cin, width * 4), bn(width * 4), stride)
            layout.append(("block",
                           conv(1, 1, cin, width), bn(width),
                           conv(3, 3, width, width), bn(width),
                           conv(1, 1, width, width * 4), bn(width * 4, True),
                           proj, stride))
            cin = width * 4
    key, sub = jax.random.split(key)
    params.append(0.01 * jax.random.normal(sub, (cin, num_classes), jnp.float32))
    params.append(jnp.zeros((num_classes,)))
    return params, layout


def _conv2d(x, w, stride, padding):
    return lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, gamma, beta, eps=1e-3):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.var(x, axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def forward(params, layout, x):
    p = params

    def block(x, i1, ib1, i2, ib2, i3, ib3, proj, stride):
        y = jax.nn.relu(_bn(_conv2d(x, p[i1], 1, "SAME"), p[ib1], p[ib1 + 1]))
        y = jax.nn.relu(_bn(_conv2d(y, p[i2], stride, "SAME"), p[ib2], p[ib2 + 1]))
        y = _bn(_conv2d(y, p[i3], 1, "SAME"), p[ib3], p[ib3 + 1])
        if proj is not None:
            pc, pb, pstride = proj
            x = _bn(_conv2d(x, p[pc], pstride, "SAME"), p[pb], p[pb + 1])
        return jax.nn.relu(x + y)

    for op in layout:
        if op[0] == "conv":
            x = _conv2d(x, p[op[1]], op[2], op[3])
        elif op[0] == "bn":
            x = _bn(x, p[op[1]], p[op[1] + 1])
        elif op[0] == "relu":
            x = jax.nn.relu(x)
        elif op[0] == "maxpool":
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
        elif op[0] == "block":
            x = block(x, *op[1:])
    x = jnp.mean(x, axis=(1, 2))
    return x @ p[-2].astype(x.dtype) + p[-1].astype(x.dtype)


def make_step(layout, lr=0.01, momentum=0.9):
    def loss_fn(params, x, y):
        cparams = [w.astype(jnp.bfloat16) for w in params]
        logits = forward(cparams, layout, x).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    def step(params, vel, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        vel = [momentum * v + g for v, g in zip(vel, grads)]
        params = [w - lr * v for w, v in zip(params, vel)]
        return loss, params, vel

    return step


def run_ref_perf(batch_size: int = 256, iterations: int = 10, warmup: int = 2,
                 log=print) -> dict:
    """Same timed-loop shape as models/perf.run_perf: jit once, fence the
    timed region with ``block_until_ready``."""
    key = jax.random.PRNGKey(0)
    params, layout = init_params(key)
    vel = [jnp.zeros_like(w) for w in params]
    x = jax.random.normal(key, (batch_size, 224, 224, 3), jnp.bfloat16)
    y = jnp.zeros((batch_size,), jnp.int32)
    step = jax.jit(make_step(layout), donate_argnums=(0, 1))

    t0 = time.perf_counter()
    for _ in range(max(1, warmup)):
        loss, params, vel = step(params, vel, x, y)
    jax.block_until_ready(loss)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(iterations):
        loss, params, vel = step(params, vel, x, y)
    jax.block_until_ready(loss)
    elapsed = time.perf_counter() - t0
    loss_v = float(loss)
    rec_per_sec = batch_size * iterations / elapsed
    out = {"records_per_sec": round(rec_per_sec, 2),
           "ms_per_iter": round(1000.0 * elapsed / iterations, 3),
           "warmup_s": round(compile_s, 3), "loss": loss_v,
           "batch_size": batch_size, "iterations": iterations}
    log(f"[ref-jax] resnet50 batch={batch_size}: {rec_per_sec:.1f} records/s")
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--iterations", type=int, default=10)
    args = ap.parse_args()
    run_ref_perf(args.batch_size, args.iterations)
