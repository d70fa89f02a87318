"""Whether two builds lower the pair's decode step to the SAME program.

A program lowered for the TPU with a Mosaic kernel in it never has the same
raw text twice over two checkouts: a kernel's payload is bytecode that
carries, for every operation, the absolute paths and the lines of the whole
Python stack that traced it, from the kernel's body up to the script that
called ``lower`` (PERF.md, PR 49). So the text is compared in two halves:
the StableHLO with every payload cut out, and each kernel's module printed
WITHOUT debug locations. Run on the CPU host in each tree and compare:

    JAX_PLATFORMS=cpu python scripts/mosaic_program_hash.py

prints, for the GPT-2-Large-shaped and the Olmo-Hybrid-shaped decode step
under ``decode_attention="kernel"`` (``tools/export_programs.py``), the
sha256 of both halves and of the raw text.

The parse goes through jax's private MLIR bindings: a jax bump may break
it, which is why it lives here and not in the package
(``tests/test_tpu_lowering.py`` skips its use of it then).
"""

import base64
import hashlib
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mosaic_kernels(text: str):
    """``(text with every Mosaic payload cut out, [(kernel name, the
    kernel's MLIR module without debug locations), ...])`` of a program's
    StableHLO ``text``, in the program's order."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    kernels = []

    def cut(match):
        ctx = mlir.make_ir_context()
        # the payload names Mosaic's dialects by their stable aliases
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            kernels.append(module.operation.get_asm(enable_debug_info=False))
        return r'\22body\22: \22...\22'

    outer = re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]*)\\22', cut, text)
    names = re.findall(r'kernel_name = "([^"]*)"', outer)
    assert len(names) == len(kernels), (names, len(kernels))
    return outer, list(zip(names, kernels))


def main():
    sys.path.insert(0, HERE)
    from bigdl_tpu.tools import export_programs as ep

    sha = lambda s: hashlib.sha256(s.encode()).hexdigest()[:16]
    for name, (fn, args) in (
            ("gpt2-large", ep.paged_decode_step_program(lanes=8)),
            ("olmo-hybrid", ep.hybrid_decode_step_program())):
        text = ep.lower_for_tpu(fn, args)
        outer, kernels = mosaic_kernels(text)
        print(name, "stablehlo", sha(outer), "kernels",
              [(k, sha(module)) for k, module in kernels], "raw", sha(text))


if __name__ == "__main__":
    main()
