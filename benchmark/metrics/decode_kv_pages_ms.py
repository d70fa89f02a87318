"""Kernels: median device milliseconds a run of the decode-step program under
the scatter into and the gather out of the page pool (``attn/kv_write`` +
``attn/kv_gather``, quantisation and dequantisation with them), self times
summed by scope (``benchmark/program_scopes.py``)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "decode_step", "kv_pages")
