"""knn_s: seconds of the neighbor search (repro.neighbors, exact blocked scan)
per fit, from each fit's own synced ``knn`` span (``TSNE.timings_``),
averaged over the window's fits. Moves ``graph_s``."""


def read(run):
    v = [f.timings["knn"] for f in run.fits if f.ok and "knn" in f.timings]
    return sum(v) / len(v) if v else None
