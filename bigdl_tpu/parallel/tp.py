"""Tensor-parallel sharding rules (GSPMD).

No reference analog — the reference's only parallelism is data parallel
(SURVEY.md §2.5). TPU-native TP is expressed as NamedSharding annotations
on the params pytree: jit/GSPMD then inserts the all-gathers/reduce-
scatters over ICI (scaling-book recipe: pick a mesh, annotate shardings,
let XLA place collectives).

``spec_for_params(params, rules)`` maps dotted param paths to
PartitionSpecs by first-match regex; ``transformer_tp_rules`` implements
the Megatron-style column/row split for the transformer stack:
  qkv / fc1  (out, in)  -> shard dim 0 (column parallel)
  out_proj / fc2        -> shard dim 1 (row parallel)
  tok_embed  (vocab, d) -> shard dim 0
  everything else       -> replicated

``kv_pool_spec`` / ``kv_pool_sharding`` lay out slot-pooled KV caches
(``(rows, H_kv, T, D)``) along the model axis on the heads dimension —
the layout the column-parallel QKV projection writes with ZERO
communication (each device computes exactly its own heads' K/V), used
by the serving engine's SPMD decode loop
(``bigdl_tpu.serving.engine.ContinuousBatchingEngine(mesh=...)``).
``kv_page_pool_spec`` / ``kv_page_pool_sharding`` are the same for the
PAGED engine's pool, whose leaves ``(max_pages, page_size, H_kv * D)``
keep the heads in their last dimension.
"""

from __future__ import annotations

import re
from typing import List, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def tree_paths(params, prefix=""):
    if isinstance(params, dict):
        for k, v in params.items():
            yield from tree_paths(v, f"{prefix}/{k}")
    else:
        yield prefix, params


def spec_for_params(params, rules: List[Tuple[str, P]], default: P = P()):
    """Pytree of PartitionSpec matching ``params``; first regex match wins."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def build(sub, prefix):
        if isinstance(sub, dict):
            return {k: build(v, f"{prefix}/{k}") for k, v in sub.items()}
        for pat, spec in compiled:
            if pat.search(prefix):
                return spec
        return default

    return build(params, "")


def transformer_tp_rules(model_axis: str = "model", data_axis: str = None):
    """Megatron-style rules for TransformerLM param paths. Pass
    ``data_axis`` to ADDITIONALLY shard each weight matrix over that
    axis on the dimension the model split leaves free (the zero-style
    2-D ``fsdp x tp`` layout: qkv/fc1 become ``P(model, data)``,
    out_proj/fc2 ``P(data, model)``), and to shard the otherwise-
    replicated positional table's first dim. Every sharded dimension
    must divide by its mesh-axis size (embed_dim, mlp hidden,
    qkv-out, and — with ``data_axis`` — vocab_size and max_len)."""
    mp, dp = model_axis, data_axis
    rules = [
        (r"attn/qkv/~params/weight$", P(mp, dp)),
        (r"attn/qkv/~params/bias$", P(mp)),
        (r"fc1/~params/weight$", P(mp, dp)),
        (r"fc1/~params/bias$", P(mp)),
        (r"attn/out_proj/~params/weight$", P(dp, mp)),
        (r"fc2/~params/weight$", P(dp, mp)),
        (r"~params/tok_embed$", P(mp, dp)),
        (r"head/~params/weight$", P(mp, dp)),
    ]
    if dp is not None:
        # the learned positional table is the one big replicated leaf
        # left; zero-style, its rows spread over the data axis
        rules.append((r"~params/pos_embed$", P(dp, None)))
    return rules


def shard_params(params, mesh, rules, default=P()):
    """device_put every leaf with its NamedSharding. (Manual walk:
    PartitionSpec is itself a pytree, so jax.tree.map would descend into it.)"""
    specs = spec_for_params(params, rules, default)

    def walk(p, s):
        if isinstance(p, dict):
            return {k: walk(v, s[k]) for k, v in p.items()}
        return jax.device_put(p, NamedSharding(mesh, s))

    return walk(params, specs)


def replicate(tree, mesh):
    """device_put every leaf fully replicated over ``mesh`` — host
    inputs and buffers entering an SPMD program with a committed,
    call-stable layout (one compiled signature, no per-call GSPMD
    resharding guesswork)."""
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def kv_pool_spec(model_axis: str = "model") -> P:
    """PartitionSpec for a slot-pooled KV cache buffer
    ``(rows, H_kv, T, D)``: heads sharded along the model axis,
    rows/time/head-dim replicated — matches the column-parallel QKV
    split, so cache writes need no collective."""
    return P(None, model_axis, None, None)


def kv_page_pool_spec(model_axis: str = "model") -> P:
    """PartitionSpec for a PAGE-pool buffer ``(max_pages, page_size,
    H_kv * D)`` (scale sidecars ``(max_pages, page_size, H_kv)``): the
    last dimension holds a token's heads side by side, so sharding it
    along the model axis gives each device the contiguous run of its
    own heads — the column-parallel QKV split again, and a page write
    needs no collective. Pages and offsets, the dimensions the write
    indexes, stay replicated (and leading: ``nn/attention.py
    _write_kv_paged``)."""
    return P(None, None, model_axis)


def fetch_to_host(tree):
    """One bulk device->host move of a buffer tree: a single blocking
    ``device_get`` per leaf, no per-chunk round trips ("RPC Considered
    Harmful": serialize once, move once). For a mesh-sharded leaf each
    device ships ONLY its own shard — per-link transfer bytes scale
    down with the mesh — and the shards reassemble into one contiguous
    host ndarray, so the host copy is layout-free and can later be
    ``put_from_host`` under ANY sharding. Used by the serving engine
    to demote prefix-KV rows into the host tier."""
    import numpy as np

    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)


def put_from_host(tree, sharding=None):
    """The reverse move: one async ``device_put`` per leaf, started
    immediately and overlapped with whatever the caller does next
    (the engine starts it while the request still waits in the
    admission queue). With ``sharding`` (e.g. the KV pool's heads-
    sharded NamedSharding) each device receives ONLY its shard slice.
    Returns the (possibly still in-flight) device tree."""
    if sharding is None:
        return jax.tree.map(jax.device_put, tree)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def _check_heads_divide(mesh, num_kv_heads: int, model_axis: str):
    """The KV head count must divide the model-axis size: an uneven
    head split would leave ragged shards and break the
    zero-communication cache-write layout."""
    if model_axis not in mesh.axis_names:
        raise ValueError(
            f"mesh axes {tuple(mesh.axis_names)} have no "
            f"{model_axis!r} axis to shard KV heads over")
    shards = int(mesh.shape[model_axis])
    if num_kv_heads % shards != 0:
        raise ValueError(
            f"num_kv_heads ({num_kv_heads}) must divide evenly over "
            f"the {shards}-way {model_axis!r} mesh axis; choose a "
            f"mesh the head count divides or bring more KV heads")


def kv_pool_sharding(mesh, num_kv_heads: int,
                     model_axis: str = "model") -> NamedSharding:
    """NamedSharding for ``TransformerLM.init_cache`` pool buffers
    (heads at dimension 1), validating that the KV head count divides
    the model-axis size."""
    _check_heads_divide(mesh, num_kv_heads, model_axis)
    return NamedSharding(mesh, kv_pool_spec(model_axis))


def kv_page_pool_sharding(mesh, num_kv_heads: int,
                          model_axis: str = "model") -> NamedSharding:
    """NamedSharding for ``TransformerLM.init_page_pool`` buffers
    (heads in the last dimension), with the same validation."""
    _check_heads_divide(mesh, num_kv_heads, model_axis)
    return NamedSharding(mesh, kv_page_pool_spec(model_axis))
