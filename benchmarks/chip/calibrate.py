#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 benchmarks/chip/calibrate.py --workload mnist-bh.fit-ee \
        --seeds 11,12,13,14 --controls 3

Each ``--seeds`` seed makes its own point set (the configuration's
generator with the seed as ``data_seed``) and the fit's ``random_state``,
so the limits rest on many datasets, not on the one the benchmark's runs
fit.  For each: one fit of the timed path (``TSNE.fit`` with the cell's
parameters) and the check's numbers against the plain reference; for the
first ``--controls`` seeds also the control, which is the reference
computed in bfloat16, put in the program's place at the same fitted
embedding and probe.  One process; every new dataset compiles its step
anew.  One JSON line per reading on standard output; the benchmark's own
runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def readings(cell, seeds, controls: int, require_tpu=True):
    """Yields (kind, seed, numbers): the program's reading on each seed, and
    the control's on the first ``controls`` of them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import check, data
    from repro.api import TSNE

    if require_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate needs a TPU")
    params = dict(cell.config["tsne"])
    kl_every = int(cell.traffic["kl_every"])
    sched = check.schedule(cell.config)
    for i, seed in enumerate(seeds):
        rng = np.random.default_rng(seed)
        x = data.points({**cell.config["dataset"], "data_seed": seed})
        random_state = int(rng.integers(2**31 - 1))
        kl_path = {}
        est = TSNE(**params, random_state=random_state, kl_every=kl_every,
                   callbacks=(lambda s: kl_path.__setitem__(s.iteration, s.kl),)
                   ).fit(x)
        y, n_iter = est.embedding_, est.n_iter_
        got, probe = check.program_side(est, sched, rng, kl_path)
        del est
        ref = check.reference_side(x, y, cell.config, probe, random_state,
                                   n_iter, jnp.float32)
        yield "program", seed, check.compare(got, ref)
        if i < controls:
            ctl = check.reference_side(x, y, cell.config, probe, random_state,
                                       n_iter, jnp.bfloat16)
            yield "control", seed, check.compare(ctl, ref)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args()
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax

    from chipbench import clock, spec

    cell = spec.load_cell(args.workload)
    clock.use_checkout_cache(jax)
    ints = lambda s: [int(v) for v in s.split(",") if v]  # noqa: E731
    for kind, seed, numbers in readings(cell, ints(args.seeds), args.controls):
        print(json.dumps({"cell": cell.name, "kind": kind, "seed": seed,
                          "t": round(time.perf_counter() - T_START, 3),
                          **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
