"""CPU checks of the FFT cell's per-layer readers: device time per FFT scope
and the interpolation's share of its HBM roofline, on a hand-built window
and on a recorded v5e window of mnist-fft's step; the least bytes the
interpolation moves; and nothing found where the step has no FFT scopes."""
from __future__ import annotations

import gzip
import json
import pathlib
import sys

import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]

from chipbench import peaks, roofline, runner, scopes, spec, trace  # noqa: E402

FFT_READERS = ("fft_spread_ms.iter", "fft_convolve_ms.iter",
               "fft_gather_ms.iter", "fft_interp_hbm_share.iter")

# the FFT step's entry computation: its three layers, the attractive loop
# and the update
HLO = """\
HloModule jit_tsne_step, is_scheduled=true

ENTRY %main.5 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="state.y"}
  %fusion.1 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%f1, metadata={op_name="jit(tsne_step)/jit(fft_repulsion)/fft_spread/reduce_min"}
  %dot.2 = f32[8]{0} dot(%fusion.1, %Arg_0.1), metadata={op_name="jit(tsne_step)/jit(fft_repulsion)/fft_spread/dot_general"}
  %fft.3 = c64[8]{0} fft(%dot.2), fft_type=RFFT, metadata={op_name="jit(tsne_step)/jit(fft_repulsion)/fft_convolve/jit(fft)"}
  %dot.4 = f32[8]{0} dot(%fft.3, %Arg_0.1), metadata={op_name="jit(tsne_step)/jit(fft_repulsion)/fft_gather/dot_general"}
  %while.5 = f32[8]{0} while(%Arg_0.1), condition=%cond, body=%body, metadata={op_name="jit(tsne_step)/attractive/while"}
  ROOT %fusion.6 = f32[8]{0} fusion(%dot.4, %while.5), kind=kLoop, calls=%f6, metadata={op_name="jit(tsne_step)/update/mul"}
}
"""

HOST, DEV = "/host:CPU", "/device:TPU:0"
V5E_HBM = 819e9


def _events():
    return [
        trace.Event(HOST, "main", "traced", 0, 200_000),
        trace.Event(DEV, trace.MODULE_LINE, "jit_tsne_step(9)", 0, 100_000),
        trace.Event(DEV, trace.OP_LINE, "fusion.1 fusion", 0, 4_000),
        trace.Event(DEV, trace.OP_LINE, "dot.2 dot", 4_000, 16_000),
        trace.Event(DEV, trace.OP_LINE, "fft.3 fft", 20_000, 2_000),
        trace.Event(DEV, trace.OP_LINE, "dot.4 dot", 22_000, 10_000),
        trace.Event(DEV, trace.OP_LINE, "while.5 while", 32_000, 60_000),
        trace.Event(DEV, trace.OP_LINE, "fusion.6 fusion", 92_000, 8_000),
    ]


def _in_the_cell(monkeypatch, table, hbm=V5E_HBM):
    """The readers as ``run.py --workload mnist-fft.fit-ee`` calls them, on
    a step whose layer table is ``table``."""
    monkeypatch.setattr(scopes, "step_tables", lambda: [table])
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload",
                                      "mnist-fft.fit-ee"])
    monkeypatch.setattr(peaks, "peaks",
                        lambda kind: {"hbm_bytes_per_s": hbm})


@pytest.mark.parametrize("n,n_boxes,want", [
    # 32 N + 28 (2 n_boxes + 1)^2
    (70_000, 50, 32 * 70_000 + 28 * 101 ** 2),
    (1_797, 48, 32 * 1_797 + 28 * 97 ** 2),
])
def test_interp_bytes(n, n_boxes, want):
    assert roofline.interp_bytes(n, n_boxes) == want
    config = {"dataset": {"n": n},
              "tsne": {"method": "fft",
                       "backend_options": {"fft_n_boxes": n_boxes}}}
    assert roofline.interp_bytes_of(config) == want
    assert roofline.interp_bytes_of(
        {**config, "tsne": {"method": "barnes_hut"}}) is None


def test_interp_bytes_of_the_fft_cell():
    config = roofline.cell_config(["--workload", "mnist-fft.fit-ee",
                                   "--seed", "7"])
    assert roofline.interp_bytes_of(config) == 2_525_628
    assert roofline.cell_config(["--seed", "7"]) is None


def test_fft_readers_on_a_window(monkeypatch):
    _in_the_cell(monkeypatch, scopes.entry_layers(HLO))
    run = runner.Run(fits=[], trace={"summary": trace.reduce(_events()),
                                     "iterations": 2})
    read = {m: spec.metric_reader(m)(run) for m in FFT_READERS}
    assert read["fft_spread_ms.iter"] == pytest.approx(0.010)
    assert read["fft_convolve_ms.iter"] == pytest.approx(0.001)
    assert read["fft_gather_ms.iter"] == pytest.approx(0.005)
    # 2,525,628 bytes in 15 us an iteration, against 819 GB/s
    assert read["fft_interp_hbm_share.iter"] == pytest.approx(
        100 * 2_525_628 / (15e-6 * V5E_HBM))
    # the Barnes-Hut readers find nothing in the FFT step
    assert spec.metric_reader("traversal_ms.iter")(run) is None


@pytest.mark.parametrize("name", FFT_READERS)
def test_fft_readers_find_nothing_without_the_fft_scopes(name, monkeypatch):
    # a step compiled without the scopes: every op unscoped
    _in_the_cell(monkeypatch,
                 {k: "unscoped" for k in scopes.entry_layers(HLO)})
    read = spec.metric_reader(name)
    summary = trace.reduce(_events())
    assert read(runner.Run(fits=[], trace={"summary": summary,
                                           "iterations": 2})) is None
    assert read(runner.Run(fits=[], trace=None)) is None


def test_fft_readers_on_the_recorded_window(monkeypatch):
    """A v5e window of mnist-fft's step (2 iterations, checkpoints around
    each), with the step's layer table, by ``scope_trace.py``."""
    with gzip.open(BENCH_DIR / "tests" / "trace_mnist_fft_scopes_v5e.json.gz",
                   "rt") as f:
        doc = json.load(f)
    hbm = peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    _in_the_cell(monkeypatch, doc["layers"], hbm)
    run = runner.Run(fits=[], trace={
        "summary": trace.reduce([trace.Event(*r) for r in doc["events"]]),
        "iterations": doc["iterations"]})
    read = {m: spec.metric_reader(m)(run) for m in FFT_READERS}
    step = spec.metric_reader("step_device_ms.iter")(run)
    attractive = spec.metric_reader("attractive_ms.iter")(run)
    fft = sum(read[m] for m in FFT_READERS[:3])
    # the FFT layers and the attractive loop hold the step; at 70,000
    # points the attractive loop takes nearly all of it
    assert 0.99 * step < fft + attractive < step
    assert attractive > 0.95 * step
    assert read["fft_spread_ms.iter"] > read["fft_gather_ms.iter"] > \
        read["fft_convolve_ms.iter"] > 0
    # the interpolation's least bytes over its time, under the roofline
    nbytes = 32 * 70_000 + 28 * 101 ** 2
    assert read["fft_interp_hbm_share.iter"] == pytest.approx(
        100 * nbytes / (1e-3 * (read["fft_spread_ms.iter"]
                                + read["fft_gather_ms.iter"]) * hbm))
    assert 0 < read["fft_interp_hbm_share.iter"] < 100
