"""traversal_lane_use.iter: percent of the lockstep walk's lane-turns that
did work: at each checkpoint the mean walk over points over the longest
(``TSNE.timings_["mean_traversal"]`` / ``["max_traversal"]``), averaged
over every checkpoint of every fit of the window. Moves ``iter_ms``."""


def read(run):
    v = [mean / top for f in run.fits if f.ok
         for top, mean in zip(f.timings.get("max_traversal", ()),
                              f.timings.get("mean_traversal", ()))
         if top > 0]
    return 100.0 * sum(v) / len(v) if v else None
