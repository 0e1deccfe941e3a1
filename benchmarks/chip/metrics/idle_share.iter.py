"""idle_share.iter: percent of the traced window in which no operation ran on
the device, over a steady descent window of a few iterations. Moves
``iter_ms``."""


def read(run):
    t = run.trace
    if t is None:
        return None
    return 100.0 * t["summary"].idle_share
