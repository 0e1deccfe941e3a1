"""symmetrize_s: seconds of the host symmetrization of P and its p log p
(core/similarity) per fit, from each fit's own synced ``symmetrize`` span
(``TSNE.timings_``), averaged over the window's fits. Moves ``graph_s``."""


def read(run):
    v = [f.timings["symmetrize"] for f in run.fits if f.ok and "symmetrize" in f.timings]
    return sum(v) / len(v) if v else None
