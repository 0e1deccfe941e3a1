"""fft_gather_ms.iter: device milliseconds per iteration in the step's
``fft_gather`` scope (the FFT repulsion's 4 potentials interpolated back at
the points, and the forces and Z made from them), from the profiler trace of
a steady descent window of a few iterations. Moves ``iter_ms``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_iter(run, ("fft_gather",))
