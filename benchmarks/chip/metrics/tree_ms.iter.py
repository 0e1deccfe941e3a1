"""tree_ms.iter: device milliseconds per iteration in the step's ``bh_tree``
(span radius, Morton encode, sort, quadtree build) and ``bh_summarize``
scopes, from the profiler trace of a steady descent window of a few
iterations. Moves ``iter_ms``."""
from chipbench import scopes


def read(run):
    return scopes.ms_per_iter(run, ("bh_tree", "bh_summarize"))
