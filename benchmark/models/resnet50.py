"""ResNet-50 on the program: ``models.perf.build_model`` (the reference perf
harness's model, NHWC) loaded with the benchmark's seeded weights, and the
way back from the program's parameter tree to the benchmark's names."""

import jax

from benchmark import weights as bw

REFERENCE = "resnet50"


def _paths(classes):
    """benchmark name -> path in the program's ``params_dict()``."""
    out = {}

    def put(name, path, kind):
        a, b = ("weight", "bias")
        out[name + (".w" if kind == "conv" else ".g")] = path + ("~params", a)
        out[name + ".b"] = path + ("~params", b)

    put("conv1", ("m0",), "conv")
    put("bn1", ("m1",), "bn")
    for s, n in enumerate(bw.RESNET50_BLOCKS):
        for b in range(n):
            main = (f"m{4 + s}", f"m{b}", "m0", "m0")
            short = (f"m{4 + s}", f"m{b}", "m0", "m1")
            p = f"l{s}.b{b}"
            for j, (c, bn) in enumerate(((0, 1), (3, 4), (6, 7)), start=1):
                put(f"{p}.c{j}", main + (f"m{c}",), "conv")
                put(f"{p}.n{j}", main + (f"m{bn}",), "bn")
            if b == 0:
                put(p + ".sc", short + ("m0",), "conv")
                put(p + ".sn", short + ("m1",), "bn")
    out["fc.w"] = ("m10", "~params", "weight")
    out["fc.b"] = ("m10", "~params", "bias")
    return out


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _to_program(name, a):
    if name.endswith(".w") and a.ndim == 4:
        return a.transpose(3, 2, 0, 1)       # HWIO -> the program's OIHW
    if name == "fc.w":
        return a.T                            # (in, out) -> (out, in)
    return a


def _from_program(name, a):
    if name.endswith(".w") and a.ndim == 4:
        return a.transpose(2, 3, 1, 0)
    if name == "fc.w":
        return a.T
    return a


def weights(config, seed):
    """The benchmark's seeded weights, under its own names."""
    return bw.resnet50_weights(seed, int(config["sizes"]["classes"]))


def build(config, seed):
    from bigdl_tpu.models.perf import build_model

    classes = int(config["sizes"]["classes"])
    model, _, _ = build_model("resnet50", classes, format="NHWC")
    w = weights(config, seed)
    tree = jax.tree.map(lambda a: a, model.params_dict())   # copy of dicts
    paths = _paths(classes)
    n_leaves = len(jax.tree.leaves(tree))
    if n_leaves != len(paths):
        raise ValueError(f"the program's ResNet-50 has {n_leaves} parameter "
                         f"leaves, benchmark/models/resnet50.py maps "
                         f"{len(paths)}")
    placed = jax.jit(lambda w: {k: _to_program(k, v) for k, v in w.items()})(w)
    for name, path in paths.items():
        node = _get(tree, path[:-1])
        if node[path[-1]].shape != placed[name].shape:
            raise ValueError((name, node[path[-1]].shape, placed[name].shape))
        node[path[-1]] = placed[name]
    model.load_params_dict(tree)
    return model


def named(params_tree, config):
    """The program's parameter tree under the benchmark's names and layout
    (host arrays)."""
    import numpy as np

    return {name: _from_program(name, np.asarray(_get(params_tree, path)))
            for name, path in _paths(int(config["sizes"]["classes"])).items()}
