"""traversal_ms.iter: device milliseconds per iteration in the step's
``bh_traversal`` scope (the lockstep Barnes-Hut walk and the scatter of its
forces back to point order), from the profiler trace of a steady descent
window of a few iterations. Moves ``iter_ms``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_iter(run, ("bh_traversal",))
