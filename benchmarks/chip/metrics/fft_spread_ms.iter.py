"""fft_spread_ms.iter: device milliseconds per iteration in the step's
``fft_spread`` scope (the FFT repulsion's lattice coordinates, Lagrange
weights and the spread of its 3 charge channels onto the lattice), from the
profiler trace of a steady descent window of a few iterations. Moves
``iter_ms``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_iter(run, ("fft_spread",))
