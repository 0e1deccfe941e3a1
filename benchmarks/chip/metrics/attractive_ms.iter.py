"""attractive_ms.iter: device milliseconds per iteration in the step's
``attractive`` scope (the P-weighted neighbor term), from the profiler
trace of a steady descent window of a few iterations. Moves ``iter_ms``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_iter(run, ("attractive",))
