"""Kernels: median device milliseconds a run of the decode-step program under
scores, mask, softmax and P.V over what was gathered (``attn/attend`` +
``sparse/attend``), self times summed by scope
(``benchmark/program_scopes.py``)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "decode_step", "attend")
