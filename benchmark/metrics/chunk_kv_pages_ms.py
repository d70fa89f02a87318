"""Kernels: median device milliseconds a run of the prefill-chunk program
under the scatter into and the gather out of the page pool (``attn/kv_write``
+ ``attn/kv_gather``), self times summed by scope
(``benchmark/program_scopes.py``)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "prefill_chunk", "kv_pages")
