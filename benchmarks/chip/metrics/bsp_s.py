"""bsp_s: seconds of the perplexity search (core/bsp) per fit, from each fit's
own synced ``bsp`` span (``TSNE.timings_``), averaged over the window's
fits. Moves ``graph_s``."""


def read(run):
    v = [f.timings["bsp"] for f in run.fits if f.ok and "bsp" in f.timings]
    return sum(v) / len(v) if v else None
