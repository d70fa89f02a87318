"""Module save/load — local AND object-store paths.

Reference: utils/File.scala:68-176 (Java-serialization save/load of any
module, transparently local/HDFS/S3). The pickle-based path is the
analog of the reference's ``save``/``Module.load``; the structured
protobuf-style format (``saveModule``/``loadModule``) lives in
bigdl_tpu.utils.serializer. Device arrays are converted to numpy on save
and restored with jnp.asarray on load, so checkpoints are host-portable.

Remote paths: anything with a URL scheme (``gs://``, ``s3://``, ...) is
routed through ``etils.epath`` (already a dependency via orbax) — the
TPU-pod analog of the reference's Hadoop-FS indirection. The
``open_file``/``exists``/``makedirs``/``listdir`` helpers below are the
single IO seam; checkpoint triggers and TrainSummary event writers go
through them, so both can target a bucket directly.
"""

from __future__ import annotations

import os
import pickle

import jax.numpy as jnp
import numpy as np


def is_remote(path) -> bool:
    """True for URL-style paths (gs://, s3://, ...) that must go through
    epath instead of the local filesystem."""
    return "://" in str(path)


def _epath(path):
    from etils import epath  # ships with orbax; object-store capable

    return epath.Path(path)


def open_file(path, mode: str = "rb"):
    """open() that understands object-store URLs. Append mode on object
    stores degrades to a single streaming write ('ab' -> 'wb'): buckets
    have no append, and every writer here creates fresh files anyway."""
    if is_remote(path):
        return _epath(path).open(mode.replace("ab", "wb"))
    return open(path, mode)


def exists(path) -> bool:
    return _epath(path).exists() if is_remote(path) else os.path.exists(path)


def makedirs(path) -> None:
    if is_remote(path):
        _epath(path).mkdir(parents=True, exist_ok=True)
    else:
        os.makedirs(path, exist_ok=True)


def listdir(path):
    if is_remote(path):
        return [p.name for p in _epath(path).iterdir()]
    return os.listdir(path)


def _to_host(module):
    for _, m in module.named_modules():
        for k in list(m._parameters):
            m._parameters[k] = np.asarray(m._parameters[k])
            object.__setattr__(m, k, m._parameters[k])
        for k in list(m._gradients):
            if m._gradients[k] is not None:
                m._gradients[k] = np.asarray(m._gradients[k])
        for k in list(m._buffers):
            m._buffers[k] = np.asarray(m._buffers[k])
            object.__setattr__(m, k, m._buffers[k])


def _to_device(module):
    for _, m in module.named_modules():
        for k in list(m._parameters):
            m._set_param(k, jnp.asarray(m._parameters[k]))
        for k in list(m._gradients):
            if m._gradients[k] is not None:
                m._gradients[k] = jnp.asarray(m._gradients[k])
        for k in list(m._buffers):
            m._set_buffer(k, jnp.asarray(m._buffers[k]))


def save_module(module, path: str, overwrite: bool = False) -> None:
    if exists(path) and not overwrite:
        raise FileExistsError(f"{path} exists; pass overwrite=True")
    for _, m in module.named_modules():
        # drop recorded activations before deepcopy — they may be large or
        # (if a trace misbehaved) tracers that cannot be copied/pickled
        m.output = None
        m.grad_input = None
        m._forward_key = None
    clone = module.clone_module()
    _to_host(clone)
    with open_file(path, "wb") as f:
        pickle.dump(clone, f)


def load_module(path: str):
    with open_file(path, "rb") as f:
        module = pickle.load(f)
    _to_device(module)
    return module


def save(obj, path: str, overwrite: bool = False) -> None:
    """Generic save for optimizer state / tables (≙ File.save)."""
    if exists(path) and not overwrite:
        raise FileExistsError(f"{path} exists; pass overwrite=True")
    import jax

    host = jax.tree.map(lambda x: np.asarray(x) if hasattr(x, "shape") else x, obj)
    with open_file(path, "wb") as f:
        pickle.dump(host, f)


def load(path: str):
    with open_file(path, "rb") as f:
        return pickle.load(f)
