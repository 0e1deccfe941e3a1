"""step_device_ms.iter: device milliseconds per iteration of the descent step's
compiled module, from the profiler trace of a steady descent window of a few
iterations. Moves ``iter_ms``."""

# names of the step's module in the trace (XLA names it after the jitted
# function)
MODULES = ("tsne_step",)


def read(run):
    t = run.trace
    if t is None:
        return None
    s = sum(v for k, v in t["summary"].module_s.items()
            if any(m in k for m in MODULES))
    return 1e3 * s / t["iterations"] if s > 0 else None
