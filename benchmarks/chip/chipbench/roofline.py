"""The least bytes a layer must move, computed from the cell's
configuration, for the roofline shares among the per-layer metrics.

A share is these bytes over the layer's device time times the chip's peak
(``chipbench.peaks``): the least time the chip could take over the time it
took.  The counts are of what the algorithm must read and write, whatever
implements it, so a change of implementation moves the time and not the
yardstick.
"""
from __future__ import annotations

import argparse
import sys

from chipbench import spec

F32 = 4
# the interpolation lattice: 3 nodes a box, each box's last node the next
# box's first, so n_boxes boxes hold 2 * n_boxes + 1 nodes a dimension
NODES_PER_BOX = 2


def interp_bytes(n: int, n_boxes: int) -> int:
    """Least HBM bytes of one FFT interpolation, spread and gather, of ``n``
    points on a lattice of G = (2 n_boxes + 1)^2 nodes, in float32: the
    spread reads the points (2 N) and writes 3 charge channels (3 G); the
    gather reads 4 potential channels (4 G) and the points (2 N) and writes
    4 potentials a point (4 N): 32 N + 28 G bytes."""
    g = (NODES_PER_BOX * n_boxes + 1) ** 2
    return F32 * ((2 + 2 + 4) * n + (3 + 4) * g)


def interp_bytes_of(config: dict) -> int | None:
    """:func:`interp_bytes` at a configuration's N and ``fft_n_boxes``;
    ``None`` for a configuration that is not of the FFT method or does not
    state its boxes."""
    tsne = config["tsne"]
    n_boxes = tsne.get("backend_options", {}).get("fft_n_boxes")
    if tsne.get("method") != "fft" or n_boxes is None:
        return None
    return interp_bytes(int(config["dataset"]["n"]), int(n_boxes))


def cell_config(argv: list[str] | None = None) -> dict | None:
    """The configuration of the cell that this process runs, by the
    ``--workload`` that ``run.py`` was started with; ``None`` without one."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    args, _ = ap.parse_known_args(sys.argv[1:] if argv is None else argv)
    return None if args.workload is None else \
        spec.load_cell(args.workload).config
