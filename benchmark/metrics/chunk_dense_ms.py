"""Kernels: median device milliseconds a run of the prefill-chunk program
under the weights' matrix products and what hangs on them (``embed``,
``attn/qkv``, ``attn/out``, ``mlp``, ``norm``, ``head``), self times summed by
scope (``benchmark/program_scopes.py``)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "prefill_chunk", "dense")
