"""Module tree ↔ (json spec, tensor archive).

Reference: utils/serializer/ (ModuleSerializer reflection +
converters/DataConverter typed attributes + TensorStorageManager spill,
SURVEY.md §2.7). Design here: every Module subclass records its
constructor call (bigdl_tpu.utils.config_capture); the serializer encodes
that config with a small value codec (primitives, containers, tensors,
nested modules, captured objects like regularizers/init methods), plus the
parameter/buffer arrays, plus any children attached after construction
(Container.add). Graphs carry their node topology via
``__serialize_spec__`` / ``__deserialize_spec__`` hooks.

Format: ``path`` is a zip with
  module.json — {"format": 1, "root": id, "records": {id: record}}
  tensors.npz — numpy arrays keyed t0, t1, ...
"""

from __future__ import annotations

import importlib
import io
import json
import os
import zipfile
from typing import Dict

import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import Module
from bigdl_tpu.utils.config_capture import get_init_config
from bigdl_tpu.utils.table import Table


class _Ctx:
    def __init__(self):
        self.records: Dict[str, dict] = {}
        self.mod_ids: Dict[int, str] = {}
        self.tensors: Dict[str, np.ndarray] = {}

    def tensor_key(self, arr) -> str:
        key = f"t{len(self.tensors)}"
        self.tensors[key] = np.asarray(arr)
        return key


def _class_path(obj) -> str:
    cls = type(obj)
    return f"{cls.__module__}.{cls.__qualname__}"


def _resolve_class(path: str):
    mod, _, name = path.rpartition(".")
    target = importlib.import_module(mod)
    for part in name.split("."):
        target = getattr(target, part)
    return target


def _encode(value, ctx: _Ctx):
    if value is None or isinstance(value, (bool, int, str)):
        return value
    if isinstance(value, float):
        return {"t": "f", "v": repr(value)}  # repr round-trips inf/nan via eval-free parse
    if isinstance(value, Module):
        return {"t": "module", "id": _serialize_module(value, ctx)}
    if isinstance(value, Table):
        return {"t": "table", "items": [_encode(v, ctx) for v in value]}
    if isinstance(value, (list, tuple)):
        return {"t": "tuple" if isinstance(value, tuple) else "list",
                "items": [_encode(v, ctx) for v in value]}
    if isinstance(value, dict):
        return {"t": "dict", "items": [[_encode(k, ctx), _encode(v, ctx)]
                                       for k, v in value.items()]}
    if isinstance(value, (np.ndarray, jnp.ndarray)):
        return {"t": "tensor", "key": ctx.tensor_key(value)}
    if np.isscalar(value) and hasattr(value, "item"):  # numpy scalar
        return _encode(value.item(), ctx)
    if hasattr(value, "_init_config"):  # captured object (regularizer, init, ...)
        args, kwargs = get_init_config(value)
        return {"t": "obj", "class": _class_path(value),
                "args": [_encode(a, ctx) for a in args],
                "kwargs": {k: _encode(v, ctx) for k, v in kwargs.items()}}
    if type(value).__name__ == "dtype" or value in (jnp.float32, jnp.bfloat16,
                                                    jnp.float16, jnp.int32):
        return {"t": "dtype", "v": np.dtype(value).name if not hasattr(value, "dtype")
                else np.dtype(value.dtype).name}
    raise TypeError(
        f"cannot serialize constructor argument of type {type(value)!r}: {value!r}")


def _decode(enc, ctx_records, ctx_tensors, memo):
    if enc is None or isinstance(enc, (bool, int, str)):
        return enc
    t = enc["t"]
    if t == "f":
        return float(enc["v"])
    if t == "module":
        return _materialize(enc["id"], ctx_records, ctx_tensors, memo)
    if t == "table":
        return Table(*[_decode(v, ctx_records, ctx_tensors, memo) for v in enc["items"]])
    if t == "tuple":
        return tuple(_decode(v, ctx_records, ctx_tensors, memo) for v in enc["items"])
    if t == "list":
        return [_decode(v, ctx_records, ctx_tensors, memo) for v in enc["items"]]
    if t == "dict":
        return {_decode(k, ctx_records, ctx_tensors, memo):
                _decode(v, ctx_records, ctx_tensors, memo) for k, v in enc["items"]}
    if t == "tensor":
        return jnp.asarray(ctx_tensors[enc["key"]])
    if t == "dtype":
        return jnp.dtype(enc["v"])
    if t == "obj":
        cls = _resolve_class(enc["class"])
        args = [_decode(a, ctx_records, ctx_tensors, memo) for a in enc["args"]]
        kwargs = {k: _decode(v, ctx_records, ctx_tensors, memo)
                  for k, v in enc["kwargs"].items()}
        return cls(*args, **kwargs)
    raise ValueError(f"unknown encoded tag {t!r}")


def _serialize_module(module: Module, ctx: _Ctx) -> str:
    mid = ctx.mod_ids.get(id(module))
    if mid is not None:
        return mid
    mid = f"m{len(ctx.mod_ids)}"
    ctx.mod_ids[id(module)] = mid
    rec: dict = {"class": _class_path(module), "name": module._name}
    ctx.records[mid] = rec  # register before recursing (shared-module cycles)

    if hasattr(module, "__serialize_spec__"):
        rec["custom"] = module.__serialize_spec__(
            lambda m: _serialize_module(m, ctx),
            lambda arr: ctx.tensor_key(arr))
    else:
        args, kwargs = get_init_config(module)
        rec["init"] = {"args": [_encode(a, ctx) for a in args],
                       "kwargs": {k: _encode(v, ctx) for k, v in kwargs.items()}}
        rec["children"] = [[name, _serialize_module(child, ctx)]
                           for name, child in module._modules.items()]
    rec["params"] = {k: ctx.tensor_key(v) for k, v in module._parameters.items()}
    rec["buffers"] = {k: ctx.tensor_key(v) for k, v in module._buffers.items()}
    rec["frozen"] = bool(module._frozen)
    extra = _extra_state(module)
    if extra:
        rec["extra"] = {k: _encode(v, ctx) for k, v in extra.items()}
    return mid


_TRANSIENT_ATTRS = {"output", "grad_input", "training"}


def _is_plain(v) -> bool:
    if v is None or isinstance(v, (bool, int, str)):
        return True
    if isinstance(v, float):
        return np.isfinite(v)  # inf defaults (e.g. max_norm) re-derive from init
    if isinstance(v, (tuple, list)):
        return all(_is_plain(i) for i in v)
    return False


def _extra_state(module: Module) -> dict:
    """Primitive attributes mutated after construction (``.ceil()``,
    ``set_p``...). Restored verbatim on load — constructor args alone don't
    capture builder-style mutations."""
    out = {}
    for k, v in vars(module).items():
        if k.startswith("_") or k in _TRANSIENT_ATTRS:
            continue
        if k in module._parameters or k in module._buffers or k in module._modules:
            continue
        if _is_plain(v):
            out[k] = v
    return out


def _materialize(mid: str, records, tensors, memo) -> Module:
    if mid in memo:
        return memo[mid]
    rec = records[mid]
    cls = _resolve_class(rec["class"])

    if "custom" in rec:
        inst = cls.__deserialize_spec__(
            rec["custom"],
            lambda child_id: _materialize(child_id, records, tensors, memo),
            lambda key: jnp.asarray(tensors[key]))
        memo[mid] = inst
    else:
        init = rec["init"]
        args = [_decode(a, records, tensors, memo) for a in init["args"]]
        kwargs = {k: _decode(v, records, tensors, memo)
                  for k, v in init["kwargs"].items()}
        inst = cls(*args, **kwargs)
        memo[mid] = inst
        for name, child_id in rec["children"]:
            child = _materialize(child_id, records, tensors, memo)
            if name not in inst._modules or inst._modules[name] is not child:
                inst._modules[name] = child
                object.__setattr__(inst, name, child)

    for k, key in rec["params"].items():
        inst._set_param(k, jnp.asarray(tensors[key]))
        inst._gradients[k] = None
    for k, key in rec["buffers"].items():
        inst._set_buffer(k, jnp.asarray(tensors[key]))
    for k, enc in rec.get("extra", {}).items():
        setattr(inst, k, _decode(enc, records, tensors, memo))
    if rec.get("name"):
        inst.set_name(rec["name"])
    if rec.get("frozen"):
        inst._frozen = True
    return inst


def module_to_spec(module: Module):
    """(spec_dict, {tensor_key: np.ndarray}) — the in-memory form."""
    ctx = _Ctx()
    root = _serialize_module(module, ctx)
    return {"format": 1, "root": root, "records": ctx.records}, ctx.tensors


def module_from_spec(spec: dict, tensors) -> Module:
    return _materialize(spec["root"], spec["records"], tensors, {})


def save_module(module: Module, path: str, overwrite: bool = False) -> None:
    """≙ AbstractModule.saveModule (protobuf path, AbstractModule.scala:523)."""
    if os.path.exists(path) and not overwrite:
        raise FileExistsError(f"{path} exists; pass overwrite=True")
    spec, tensors = module_to_spec(module)
    buf = io.BytesIO()
    np.savez(buf, **tensors)
    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr("module.json", json.dumps(spec))
        z.writestr("tensors.npz", buf.getvalue())


def load_module(path: str) -> Module:
    """≙ Module.loadModule (nn/Module.scala:44-94 protobuf path)."""
    with zipfile.ZipFile(path, "r") as z:
        spec = json.loads(z.read("module.json").decode("utf-8"))
        with np.load(io.BytesIO(z.read("tensors.npz"))) as npz:
            tensors = {k: npz[k] for k in npz.files}
    return module_from_spec(spec, tensors)
