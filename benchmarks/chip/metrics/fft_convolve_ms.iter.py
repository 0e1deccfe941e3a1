"""fft_convolve_ms.iter: device milliseconds per iteration in the step's
``fft_convolve`` scope (the FFT repulsion's kernel convolution of the
lattice, by FFT), from the profiler trace of a steady descent window of a
few iterations. Moves ``iter_ms``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_iter(run, ("fft_convolve",))
