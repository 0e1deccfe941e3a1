"""traversal_turns.iter: turns of the lockstep Barnes-Hut walk per step (its
longest point's walk), as the program reports it at each checkpoint
(``TSNE.timings_["max_traversal"]``), averaged over every checkpoint of
every fit of the window: the whole descent that ``iter_ms`` times. Moves
``iter_ms``."""


def read(run):
    v = [t for f in run.fits if f.ok
         for t in f.timings.get("max_traversal", ())]
    return sum(v) / len(v) if v and max(v) > 0 else None
