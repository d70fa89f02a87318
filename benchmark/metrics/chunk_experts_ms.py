"""Kernels: median device milliseconds a run of the prefill-chunk program
under the routed layers' experts, held and shared (``moe/experts``,
``moe/shared``; the scopes the configuration's file declares under the group
``experts``), self times summed by scope (``benchmark/program_scopes.py``)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "prefill_chunk", "experts")
