"""CPU checks of the FFT cell's ``correct``: at a small size, a sound run of
``mnist-fft.fit-ee`` meets the cell's own limits against the plain
reference, and the bfloat16 control and a fault planted in the FFT
repulsion do not."""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import time

import numpy as np
import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]

from chipbench import spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "mnist-fft.fit-ee"
SMALL_N, SMALL_ITERS = 600, 200


def _small():
    """The cell at 600 points and 200 iterations.  The configuration's
    learning rate is its rule max(N / early_exaggeration, 50) at 70,000
    points; at 600 points the rule gives 50.  Kept at 5,833, the descent
    spreads 600 points to a span of hundreds in a few steps, past what a
    fixed 50-box grid resolves (a departure from FIt-SNE's grid rule, which
    the full-size cell does not reach)."""
    cell = spec.load_cell(CELL, BENCH)
    config = json.loads(json.dumps(cell.config))
    tsne = config["tsne"]
    config["dataset"]["n"] = SMALL_N
    tsne["n_iter"] = SMALL_ITERS
    tsne["learning_rate"] = max(SMALL_N / tsne["early_exaggeration"], 50.0)
    return dataclasses.replace(cell, config=config)


def _run(cell, seed=4000000007):
    from chipbench import runner
    return runner.run(cell, seed, 0.0, False, time.perf_counter())


def test_small_fit_stays_on_the_fixed_grid():
    """The small cut keeps the full cell's regime: the lattice's span stays
    under FIt-SNE's 50 intervals, so its grid rule would not grow the
    grid."""
    from chipbench import data
    from repro.api import TSNE

    cell = _small()
    est = TSNE(**cell.config["tsne"], random_state=7,
               kl_every=50).fit(data.points(cell.config["dataset"]))
    span = est.timings_["fft_span"]
    assert len(span) == SMALL_ITERS // 50 and 0 < max(span) < 50


def test_fft_sound_run_is_correct():
    out = _run(_small())
    assert out["correct"], out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "check"


def test_fft_control_fails_the_limits():
    import calibrate
    from chipbench import check

    cell = _small()
    got = {kind: numbers for kind, _, numbers in
           calibrate.readings(cell, [5], 1, require_tpu=False)}
    assert check.verdict(got["program"], cell.limits)
    assert not check.verdict(got["control"], cell.limits)


def _self_in_z(monkeypatch):
    """Z counts each point's own K1 = 1: its self-interaction is left in
    the sum over pairs."""
    from repro.core import fft_repulsion
    real = fft_repulsion.gather_by_matmul

    def gather(*args):
        return real(*args).at[:, 3].add(1.0)
    monkeypatch.setattr(fft_repulsion, "gather_by_matmul", gather)


def _spacing_off(monkeypatch):
    """The kernel is tabulated at a node spacing 1% off the lattice's, so
    the convolution misreads every pair's distance."""
    from repro.core import fft_repulsion
    real = fft_repulsion.interp_coords

    def coords(y, n_boxes):
        base, wx, wy, h = real(y, n_boxes)
        return base, wx, wy, 1.01 * h
    monkeypatch.setattr(fft_repulsion, "interp_coords", coords)


@pytest.mark.parametrize("fault", [_self_in_z, _spacing_off])
def test_fft_fault_makes_it_incorrect(fault, monkeypatch):
    import jax

    fault(monkeypatch)
    jax.clear_caches()        # the step may be traced already, unpatched
    try:
        out = _run(_small())
    finally:
        monkeypatch.undo()
        jax.clear_caches()    # and no later test may run the patched trace
    assert not out["correct"], out["check"]
    assert np.isfinite([v["value"] for v in out["check"].values()]).all()
