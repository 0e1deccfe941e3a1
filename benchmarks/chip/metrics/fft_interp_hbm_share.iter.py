"""fft_interp_hbm_share.iter: percent of the HBM roofline that the FFT
repulsion's interpolation reaches: the least bytes its spread and gather
must move in an iteration (``chipbench.roofline.interp_bytes``, at the
cell's N and ``fft_n_boxes``) over the device time per iteration of the
step's ``fft_spread`` and ``fft_gather`` scopes, in the profiler trace of a
steady descent window, times the chip's peak HBM bandwidth
(``chipbench.peaks``). Moves ``iter_ms``."""
from chipbench import peaks, roofline, scopes


def read(run):
    ms = scopes.ms_per_iter(run, ("fft_spread", "fft_gather"))
    config = roofline.cell_config()
    nbytes = roofline.interp_bytes_of(config) if config else None
    if not ms or nbytes is None:
        return None
    import jax
    peak = peaks.peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / (1e-3 * ms * peak)
