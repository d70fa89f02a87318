"""Kernels: median device milliseconds a run of the decode-step program under
the routed layers' routing: scores, top-k, gates and the order of the
assignments that fall on held experts (``moe/route``, which the
configuration's file declares under the group ``route``), self times summed
by scope (``benchmark/program_scopes.py``)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "decode_step", "route")
