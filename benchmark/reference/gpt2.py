"""GPT-2 (Radford et al. 2019) forward pass in plain float32 jax.numpy:
learned positions, pre-norm blocks, causal softmax attention, tanh-GELU MLP of
four times the width, final LayerNorm, logits against the tied embedding. No
cache, no batching tricks, no kernel; matmuls at precision "highest". Imports
nothing of the program; its weights come from ``benchmark/weights.py``.

Departure from the release: the vocabulary may be padded (the configuration
says to what), which adds rows to the embedding and columns to the logits.
"""

import functools

import jax
import jax.numpy as jnp

EPS = 1e-5


def _ln(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * g + b


@functools.partial(jax.jit, static_argnums=(2,))
def _block(x, w, heads):
    with jax.default_matmul_precision("highest"):
        b, t, e = x.shape
        d = e // heads
        h = _ln(x, w["ln1_g"], w["ln1_b"])
        qkv = h @ w["qkv_w"].T + w["qkv_b"]
        q, k, v = (qkv[..., i * e:(i + 1) * e].reshape(b, t, heads, d)
                   .transpose(0, 2, 1, 3) for i in range(3))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
        causal = jnp.tril(jnp.ones((t, t), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = jnp.einsum("bhqk,bhkd->bhqd", p, v).transpose(0, 2, 1, 3)
        x = x + a.reshape(b, t, e) @ w["proj_w"].T + w["proj_b"]
        h = _ln(x, w["ln2_g"], w["ln2_b"])
        h = jax.nn.gelu(h @ w["fc_w"].T + w["fc_b"], approximate=True)
        return x + h @ w["fc2_w"].T + w["fc2_b"]


@jax.jit
def _head(x, g, b, wte):
    with jax.default_matmul_precision("highest"):
        return _ln(x, g, b) @ wte.T


def as_float32(weights):
    return jax.tree.map(lambda a: a.astype(jnp.float32), weights)


def forward(weights, ids, config):
    """(batch, time) int ids -> (batch, time, vocab) float32 logits.
    ``weights`` in the layout of ``benchmark.weights.gpt2_weights``, any
    floating type: they are read as float32 values. Of ``config`` (the
    configuration file) only the number of heads is read."""
    heads = int(config["sizes"]["n_head"])
    ids = jnp.asarray(ids, jnp.int32)
    w = as_float32({k: v for k, v in weights.items() if k != "blocks"})
    x = jnp.take(w["wte"], ids, axis=0) + w["wpe"][None, :ids.shape[1]]
    for blk in weights["blocks"]:
        x = _block(x, as_float32(blk), heads)
    return _head(x, w["lnf_g"], w["lnf_b"], w["wte"])
