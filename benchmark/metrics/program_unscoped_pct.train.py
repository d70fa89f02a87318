"""Device: of the self time of the operations the cell's step programs ran in
the traced window, the share under no scope of the vocabulary and no module
class (``benchmark/program_scopes.py``): what the by-scope metrics do not see.
100 where the executables carry no scopes."""
from benchmark import program_scopes


def value(run, trace):
    return program_scopes.unscoped_pct(program_scopes.scopes(run, trace))
