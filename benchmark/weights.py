"""Weights from the seed, made on the device in one jitted call, in the
benchmark's own layout. The program's model is loaded from these (through
``benchmark/models/``) and the plain references read them directly, so the
two sides share the seed and nothing the program has made."""

import functools

import jax
import jax.numpy as jnp

RESNET50_BLOCKS = (3, 4, 6, 3)


def _key(seed):
    return jax.random.PRNGKey(int(seed) % (2 ** 31))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def _gpt2(key, vocab, embed, layers, positions, mlp_ratio, dtype, std):
    """GPT-2's published initialisation (Radford et al. 2019, the released
    model code): N(0, 0.02) weights, zero biases, unit LayerNorm gains, the
    two projections into the residual stream scaled by 1/sqrt(2*layers)."""
    res = std / (2.0 * layers) ** 0.5
    hidden = mlp_ratio * embed

    def normal(k, shape, s):
        return (s * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    ones = lambda n: jnp.ones((n,), dtype)
    zeros = lambda n: jnp.zeros((n,), dtype)
    k_tok, k_pos, k_blocks = jax.random.split(key, 3)
    blocks = []
    for i in range(layers):
        k = jax.random.split(jax.random.fold_in(k_blocks, i), 4)
        blocks.append({
            "ln1_g": ones(embed), "ln1_b": zeros(embed),
            # (out, in): q rows, then k rows, then v rows
            "qkv_w": normal(k[0], (3 * embed, embed), std),
            "qkv_b": zeros(3 * embed),
            "proj_w": normal(k[1], (embed, embed), res), "proj_b": zeros(embed),
            "ln2_g": ones(embed), "ln2_b": zeros(embed),
            "fc_w": normal(k[2], (hidden, embed), std), "fc_b": zeros(hidden),
            "fc2_w": normal(k[3], (embed, hidden), res), "fc2_b": zeros(embed),
        })
    return {"wte": normal(k_tok, (vocab, embed), std),
            "wpe": normal(k_pos, (positions, embed), std),
            "blocks": blocks, "lnf_g": ones(embed), "lnf_b": zeros(embed)}


def gpt2_weights(seed, sizes, dtype=jnp.bfloat16):
    """``sizes``: vocab_size, n_embd, n_layer, n_positions (the config file's
    keys; ``initializer_range`` is the published 0.02 unless given). The MLP
    is 4x the width, as published."""
    return _gpt2(_key(seed), int(sizes["vocab_size"]), int(sizes["n_embd"]),
                 int(sizes["n_layer"]), int(sizes["n_positions"]), 4,
                 jnp.dtype(dtype), float(sizes.get("initializer_range", 0.02)))


def rounded(tree, levels=127):
    """Every matrix of ``tree`` rounded to ``2 * levels + 1`` steps per output
    row (symmetric, scale = the row's largest magnitude over ``levels``):
    the values an int8 (127) or int4 (7) copy of the weights would multiply
    with. Vectors stay. Used by the precision control only."""
    def q(a):
        if a.ndim < 2:
            return a
        f = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=-1, keepdims=True) / levels
        return (jnp.round(f / scale) * scale).astype(a.dtype)

    return jax.tree.map(q, tree)


def rounded_fp8(tree):
    """Every matrix of ``tree`` through float8 (e4m3, 3 bits of mantissa),
    each output row scaled to the format's range first. Used by the
    precision control only."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)

    def q(a):
        if a.ndim < 2:
            return a
        f = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=-1, keepdims=True) / top
        return ((f / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * scale).astype(a.dtype)

    return jax.tree.map(q, tree)


def resnet50_layout(classes):
    """[(name, shape, kind)] in architectural order. Convolutions are HWIO
    and carry a bias, as the reference harness's model does."""
    out = []

    def conv(name, k, cin, cout):
        out.append((name + ".w", (k, k, cin, cout), "conv"))
        out.append((name + ".b", (cout,), "zero"))

    def bn(name, c, zero_gain=False):
        out.append((name + ".g", (c,), "zero" if zero_gain else "one"))
        out.append((name + ".b", (c,), "zero"))

    conv("conv1", 7, 3, 64)
    bn("bn1", 64)
    cin = 64
    for s, n_blocks in enumerate(RESNET50_BLOCKS):
        width = 64 * 2 ** s
        for b in range(n_blocks):
            p = f"l{s}.b{b}"
            conv(p + ".c1", 1, cin, width)
            bn(p + ".n1", width)
            conv(p + ".c2", 3, width, width)
            bn(p + ".n2", width)
            conv(p + ".c3", 1, width, width * 4)
            bn(p + ".n3", width * 4, zero_gain=True)
            if b == 0:
                conv(p + ".sc", 1, cin, width * 4)
                bn(p + ".sn", width * 4)
            cin = width * 4
    out.append(("fc.w", (cin, classes), "fc"))
    out.append(("fc.b", (classes,), "zero"))
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _resnet50(key, classes):
    """He et al. 2015 initialisation as the reference harness applies it:
    MSRA-normal convolutions (fan-in), unit BatchNorm gains except a zero
    gain on each block's last BatchNorm (Goyal et al. 2017), N(0, 0.01)
    classifier, zero biases."""
    params = {}
    for i, (name, shape, kind) in enumerate(resnet50_layout(classes)):
        if kind == "conv":
            fan_in = shape[0] * shape[1] * shape[2]
            params[name] = (2.0 / fan_in) ** 0.5 * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        elif kind == "fc":
            params[name] = 0.01 * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        elif kind == "one":
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            params[name] = jnp.zeros(shape, jnp.float32)
    return params


def resnet50_weights(seed, classes=1000):
    return _resnet50(_key(seed), int(classes))
