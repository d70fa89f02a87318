"""Kernels: median device milliseconds a run of the decode-step program under
the weights' matrix products and what hangs on them (``embed``, ``attn/qkv``,
``attn/out``, ``mlp``, ``norm``, ``head``, ``sample``), self times summed by
scope (``benchmark/program_scopes.py``)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "decode_step", "dense")
