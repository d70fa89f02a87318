"""Cache manager: median device milliseconds a run of the decode-step program
under the block-sparse layers' selection through the compressed keys
(``sparse/select``), self times summed by scope
(``benchmark/program_scopes.py``)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "decode_step", "select")
