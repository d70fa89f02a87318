"""Kernels: median device milliseconds a run of the train-step program under
the batch-normalisation layers, forward and backward (module classes named
``*BatchNormalization*``), self times summed by scope
(``benchmark/program_scopes.py``)."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.group_ms(run, trace, "train_step", "bn")
