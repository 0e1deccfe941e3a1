#!/usr/bin/env python3
"""Chip benchmark of the t-SNE fit: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload mnist-bh.fit-ee --seed 7 \
        --seconds 30 --trace 0

Set-up (the configuration's points, a warm-up fit that compiles every
program the window runs) is ``setup_s``; then ``TSNE.fit`` runs back to
back, whole passes over the traffic's fits in an order the seed draws,
for at least ``--seconds``, and a fit the seed draws is checked against
the plain reference.  The last line on standard output is one JSON object; with
``--trace 1`` it holds the per-layer metrics of a profiled window instead
of the end-to-end ones.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax

    from chipbench import clock, peaks, runner, spec

    cell = spec.load_cell(args.workload)
    clock.use_checkout_cache(jax)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    peaks.peaks(devices[0].device_kind)
    import repro  # noqa: F401  (fails here when the program is not in the checkout)

    result = runner.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
