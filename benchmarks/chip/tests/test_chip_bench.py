"""CPU checks of the chip benchmark's harness: every cell loads by name, a
run without a TPU exits non-zero, the trace reduction on a recorded chip
trace, and ``correct`` coming out false for the control and for faults
planted under the timed path."""
from __future__ import annotations

import dataclasses
import gzip
import json
import os
import pathlib
import re
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]

from chipbench import spec, trace  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_by_name(name):
    cell = spec.load_cell(name, BENCH)
    assert cell.chips in (1, 4)
    assert {"dataset", "tsne"} <= set(cell.config)
    assert {"kl_every", "warmup_n_iter", "random_states"} <= set(cell.traffic)
    assert {"dataset", "tsne", "descent"} <= set(cell.config)
    assert cell.limits and all(v > 0 for v in cell.limits.values())
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names, (m["name"], m["moves"])
        assert callable(spec.metric_reader(m["name"]))


def test_benchmark_names_and_files():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    for m in BENCH["per_layer"]:
        assert (BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()


def test_no_tpu_exits_nonzero():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(BENCH_DIR / "run.py"), "--workload", CELLS[0],
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode != 0
    assert "{" not in out.stdout


def _recorded_trace():
    with gzip.open(BENCH_DIR / "tests" / "trace_digits_v5e.json.gz", "rt") as f:
        rows = json.load(f)
    return [trace.Event(*r) for r in rows]


def test_trace_reduction_on_recorded_trace():
    evs = _recorded_trace()
    s = trace.reduce(evs)
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s
    # busy time is a union: never more than the summed op time
    assert s.busy_s <= sum(s.op_s.values()) + 1e-9
    # every idle second is attributed, and idle + busy is the window
    assert sum(s.gaps.values()) + s.busy_s == pytest.approx(s.window_s,
                                                             rel=1e-6)
    steps = [v for k, v in s.module_s.items() if "tsne_step" in k]
    # the slice holds two whole steps and parts of two more
    assert 2 * 0.02 < sum(steps) <= s.window_s


def test_trace_reduction_by_hand():
    host, dev = "/host:CPU", "/device:TPU:0"
    evs = [
        trace.Event(host, "main", "traced", 0, 100_000),
        trace.Event(host, "main", "checkpoint", 40_000, 30_000),
        trace.Event(dev, trace.MODULE_LINE, "jit_tsne_step(1)", 0, 40_000),
        trace.Event(dev, trace.OP_LINE, "fusion.1", 0, 25_000),
        trace.Event(dev, trace.OP_LINE, "fusion.2", 20_000, 20_000),
        trace.Event(dev, trace.OP_LINE, "fusion.3", 80_000, 10_000),
        trace.Event(dev, trace.OP_LINE, "outside", 200_000, 10_000),
    ]
    s = trace.reduce(evs)
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(50e-6)          # [0,40) and [80,90)
    assert s.module_s == {"jit_tsne_step(1)": pytest.approx(40e-6)}
    assert s.gaps["no host event"] == pytest.approx(10e-6)  # [90,100)
    assert s.gaps["checkpoint"] == pytest.approx(40e-6)
    assert s.idle_share == pytest.approx(0.5)


# ------------------------------------------------------------- correct --

SMALL_N, SMALL_ITERS = 600, 200


def _small(name="digits-bh.fit-full"):
    cell = spec.load_cell(name, BENCH)
    config = json.loads(json.dumps(cell.config))
    config["dataset"]["n"] = SMALL_N
    config["tsne"]["n_iter"] = SMALL_ITERS
    return dataclasses.replace(cell, config=config)


def test_control_fails_the_limits():
    import calibrate
    from chipbench import check

    cell = _small()
    got = {kind: numbers for kind, _, numbers in
           calibrate.readings(cell, [5], 1, require_tpu=False)}
    assert check.verdict(got["program"], cell.limits)
    assert not check.verdict(got["control"], cell.limits)


def _run(cell, seed=4000000007):
    from chipbench import runner
    return runner.run(cell, seed, 0.0, False, time.perf_counter())


def _unchanged_state(monkeypatch):
    from repro.core import tsne
    real = tsne.tsne_step

    def step(state, *args, **kw):
        return state, real(state, *args, **kw)[1]
    monkeypatch.setattr(tsne, "tsne_step", step)


def _embedding_unchanged(monkeypatch):
    """The step computes the velocity and gains but leaves y where it was."""
    from repro.core import tsne
    real = tsne.tsne_step

    def step(state, *args, **kw):
        new, stats = real(state, *args, **kw)
        return new._replace(y=state.y), stats
    monkeypatch.setattr(tsne, "tsne_step", step)


def _half_the_points(monkeypatch):
    import jax.numpy as jnp
    from repro.core.knn import knn_query
    from repro.neighbors import exact

    def knn(x, k, **kw):
        n = x.shape[0]
        idx, d2 = knn_query(x, x[: n // 2], k + 1)
        me = idx[:, :1] == jnp.arange(n)[:, None]
        return (jnp.where(me, idx[:, 1:], idx[:, :-1]),
                jnp.where(me, d2[:, 1:], d2[:, :-1]))
    monkeypatch.setattr(exact, "knn", knn)


def _one_row_altered(monkeypatch):
    from repro.core import similarity
    real = similarity.symmetrize_ell

    def symmetrize(cols, cond_p):
        c, v = real(cols, cond_p)
        c[0] = (c[0] + 1) % c.shape[0]
        return c, v
    monkeypatch.setattr(similarity, "symmetrize_ell", symmetrize)


def test_sound_run_is_correct():
    out = _run(_small())
    assert out["correct"], out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("fault", [_unchanged_state, _embedding_unchanged,
                                   _half_the_points, _one_row_altered])
def test_fault_makes_it_incorrect(fault, monkeypatch):
    fault(monkeypatch)
    out = _run(_small())
    assert not out["correct"], out["check"]
    assert np.isfinite([v["value"] for v in out["check"].values()]).all()
