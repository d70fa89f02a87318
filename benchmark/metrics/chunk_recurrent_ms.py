"""Kernels: median device milliseconds a run of the prefill-chunk program
under the recurrent layers' chunked scan and the lanes' read and write
(``gdn/*``, ``lightning/*``), self times summed by scope
(``benchmark/program_scopes.py``)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "prefill_chunk", "recurrent")
