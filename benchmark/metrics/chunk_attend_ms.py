"""Kernels: median device milliseconds a run of the prefill-chunk program
under scores, mask, running softmax and P.V of the key-block loops
(``attn/attend`` + ``sparse/attend``), self times summed by scope
(``benchmark/program_scopes.py``)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "prefill_chunk", "attend")
