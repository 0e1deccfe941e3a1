"""CPU checks of the per-layer readers: device time per program scope from a
profiled window (no loop counted twice), the walk counters the program
reports at its checkpoints, and nothing found where a program has neither."""
from __future__ import annotations

import gzip
import importlib
import json
import pathlib
import sys

import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]

from chipbench import runner, scopes, spec, trace  # noqa: E402

DEVICE_READERS = ("traversal_ms.iter", "attractive_ms.iter", "tree_ms.iter")
WALK_READERS = ("traversal_turns.iter", "traversal_lane_use.iter")

# an entry computation with a loop whose body ops the profiler also shows
HLO = """\
HloModule jit_tsne_step, is_scheduled=true

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %fusion.25 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f25, metadata={op_name="jit(tsne_step)/bh_traversal/jit(bh_repulsion_sorted)/while/body/add"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%p, %fusion.25)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="state.y"}
  %sort.0 = f32[8]{0} sort(%Arg_0.1), dimensions={0}, metadata={op_name="jit(tsne_step)/bh_tree/sort"}
  %while.72 = (s32[], f32[8]{0}) while(%sort.0), condition=%cond, body=%body, metadata={op_name="jit(tsne_step)/bh_traversal/jit(bh_repulsion_sorted)/while"}
  %fusion.80 = f32[8]{0} fusion(%while.72), kind=kLoop, calls=%f80, metadata={op_name="jit(tsne_step)/update/mul"}
  ROOT %copy.3 = f32[8]{0} copy(%fusion.80)
}
"""

HOST, DEV = "/host:CPU", "/device:TPU:0"


def _events():
    return [
        trace.Event(HOST, "main", "traced", 0, 200_000),
        trace.Event(HOST, "main", "step", 0, 5_000),
        trace.Event(HOST, "main", "checkpoint", 160_000, 40_000),
        trace.Event(DEV, trace.MODULE_LINE, "jit_tsne_step(7)", 10_000,
                    130_000),
        trace.Event(DEV, trace.OP_LINE, "sort.0 sort", 10_000, 10_000),
        trace.Event(DEV, trace.OP_LINE, "while.72 while", 20_000, 100_000),
        trace.Event(DEV, trace.OP_LINE, "fusion.25 fusion", 30_000, 30_000),
        trace.Event(DEV, trace.OP_LINE, "fusion.25 fusion", 70_000, 40_000),
        trace.Event(DEV, trace.OP_LINE, "fusion.80 fusion", 120_000, 15_000),
        trace.Event(DEV, trace.OP_LINE, "copy.3 copy", 135_000, 5_000),
    ]


def _fit(timings, ok=True):
    return runner.Fit(random_state=1, start=0.0, first_checkpoint=1.0,
                      first_iteration=1, end=2.0, n_iter=3, timings=timings,
                      kl_path={}, ok=ok)


@pytest.mark.parametrize("path,want", [
    ("jit(tsne_step)/bh_traversal/jit(bh_repulsion_sorted)/while",
     "bh_traversal"),
    ("jit(tsne_step)/jit(main)/attractive/dot_general", "attractive"),
    ("jit(tsne_step)/bh_tree/bh_summarize/add", "bh_summarize"),
    ("jit(tsne_step)/bh_traversal_extra/add", "unscoped"),
    ("", "unscoped"),
])
def test_layer_is_the_innermost_scope(path, want):
    assert scopes.layer(path) == want


def test_entry_layers_keep_the_entry_computation_only():
    assert scopes.entry_layers(HLO) == {
        "Arg_0.1 parameter": "unscoped", "sort.0 sort": "bh_tree",
        "while.72 while": "bh_traversal", "fusion.80 fusion": "update",
        "copy.3 copy": "unscoped"}


def test_scope_seconds_count_a_loop_once():
    s = trace.reduce(_events())
    table = scopes.entry_layers(HLO)
    got = scopes.scope_s(s.op_s, table)
    # the loop's body ops lie inside the while op: counted with it, once
    assert got == {"bh_tree": pytest.approx(10e-6),
                   "bh_traversal": pytest.approx(100e-6),
                   "update": pytest.approx(15e-6),
                   "unscoped": pytest.approx(5e-6)}
    assert sum(got.values()) == pytest.approx(s.module_s["jit_tsne_step(7)"])
    assert scopes.window_layers(s, [{}, table]) == (table, got)


def test_idle_inside_program_spans():
    idle, covered = scopes.idle_in_spans(_events())
    # idle: [0,10) and [140,200) us; the spans cover [0,5) and [160,200)
    assert idle == pytest.approx(70e-6)
    assert covered == pytest.approx(45e-6)


def test_device_readers_on_a_window(monkeypatch):
    monkeypatch.setattr(scopes, "step_tables",
                        lambda: [scopes.entry_layers(HLO)])
    run = runner.Run(fits=[], trace={"summary": trace.reduce(_events()),
                                     "iterations": 2})
    read = {m: spec.metric_reader(m)(run) for m in DEVICE_READERS}
    assert read["traversal_ms.iter"] == pytest.approx(0.05)
    assert read["tree_ms.iter"] == pytest.approx(0.005)
    assert read["attractive_ms.iter"] is None      # no such scope here


def test_walk_readers_average_every_checkpoint():
    run = runner.Run(fits=[
        _fit({"max_traversal": [10, 20], "mean_traversal": [5.0, 5.0]}),
        _fit({"max_traversal": [30], "mean_traversal": [30.0]}),
        _fit({"max_traversal": [1000], "mean_traversal": [1.0]}, ok=False),
    ], trace=None)
    assert spec.metric_reader("traversal_turns.iter")(run) == \
        pytest.approx(20.0)
    assert spec.metric_reader("traversal_lane_use.iter")(run) == \
        pytest.approx(100 * (0.5 + 0.25 + 1.0) / 3)


@pytest.mark.parametrize("name", DEVICE_READERS + WALK_READERS)
def test_new_readers_find_nothing_without_scopes_or_counters(name,
                                                             monkeypatch):
    read = spec.metric_reader(name)
    # a program that reports no walk, and one without a tree
    for timings in ({"knn": 1.0},
                    {"max_traversal": [0, 0], "mean_traversal": [0.0, 0.0]}):
        assert read(runner.Run(fits=[_fit(timings)], trace=None)) is None
    # a step compiled without the scopes: every op unscoped
    unscoped = {k: "unscoped" for k in scopes.entry_layers(HLO)}
    monkeypatch.setattr(scopes, "step_tables", lambda: [unscoped])
    summary = trace.reduce(_events())
    assert read(runner.Run(fits=[_fit({"knn": 1.0})],
                           trace={"summary": summary, "iterations": 2})) \
        is None


def _recorded():
    """A v5e window of digits' step (2 iterations, checkpoints around
    each), with the step's layer table, by ``scope_trace.py``."""
    with gzip.open(BENCH_DIR / "tests" / "trace_digits_scopes_v5e.json.gz",
                   "rt") as f:
        doc = json.load(f)
    return ([trace.Event(*r) for r in doc["events"]], doc["layers"],
            doc["iterations"])


def test_recorded_window_splits_the_step_by_layer():
    evs, table, _ = _recorded()
    s = trace.reduce(evs)
    module = sum(v for k, v in s.module_s.items() if "tsne_step" in k)
    got = scopes.scope_s(s.op_s, table)
    # the layers hold the module's time: little unscoped, nothing twice
    assert got["unscoped"] < 0.03 * module
    assert sum(got.values()) == pytest.approx(module, rel=0.03)
    # the lockstep walk takes most of the step, the attractive loop next
    assert got["bh_traversal"] > got["attractive"] > got["bh_tree"] > \
        got["update"] > 0


def test_recorded_idle_falls_inside_program_spans():
    evs, _, _ = _recorded()
    idle, covered = scopes.idle_in_spans(evs)
    assert idle > 0 and covered >= 0.9 * idle


def test_device_readers_on_the_recorded_window(monkeypatch):
    evs, table, iterations = _recorded()
    monkeypatch.setattr(scopes, "step_tables", lambda: [table])
    run = runner.Run(fits=[], trace={"summary": trace.reduce(evs),
                                     "iterations": iterations})
    layers = sum(spec.metric_reader(m)(run) for m in DEVICE_READERS)
    step = spec.metric_reader("step_device_ms.iter")(run)
    assert 0.95 * step < layers < step


def test_live_step_holds_the_program_scopes():
    from repro.api import TSNE
    from repro.data.datasets import make_dataset

    x, _ = make_dataset("digits", n=150)
    TSNE(perplexity=5.0, n_iter=2, kl_every=1, random_state=0).fit(x)
    layers = set().union(*(t.values() for t in scopes.step_tables()))
    assert {"bh_tree", "bh_traversal", "attractive", "update"} <= layers
    assert set(scopes.SCOPES) >= layers - {"unscoped"}


def test_device_readers_find_nothing_when_another_module_shares_an_op_name(
        monkeypatch):
    monkeypatch.setattr(scopes, "step_tables",
                        lambda: [scopes.entry_layers(HLO)])
    # another module in the window whose op bears a step op's name: its
    # time would land in the step's layers, which then overrun the module
    evs = _events() + [
        trace.Event(DEV, trace.MODULE_LINE, "jit_kl(3)", 140_000, 20_000),
        trace.Event(DEV, trace.OP_LINE, "sort.0 sort", 140_000, 20_000)]
    run = runner.Run(fits=[], trace={"summary": trace.reduce(evs),
                                     "iterations": 2})
    for name in DEVICE_READERS:
        assert spec.metric_reader(name)(run) is None


def test_readers_find_nothing_in_a_program_that_names_no_layers():
    # a program without repro.core.scopes: the harness still loads, every
    # op reads as unscoped, and the device readers report nothing
    saved = sys.modules.get("repro.core.scopes")
    sys.modules["repro.core.scopes"] = None       # import fails
    try:
        importlib.reload(scopes)
        assert scopes.SCOPES == ()
        table = scopes.entry_layers(HLO)
        assert set(table.values()) == {"unscoped"}
        scopes.step_tables = lambda: [table]
        run = runner.Run(fits=[], trace={"summary": trace.reduce(_events()),
                                         "iterations": 2})
        for name in DEVICE_READERS:
            assert spec.metric_reader(name)(run) is None
    finally:
        if saved is None:
            del sys.modules["repro.core.scopes"]
        else:
            sys.modules["repro.core.scopes"] = saved
        importlib.reload(scopes)
    assert "bh_traversal" in scopes.SCOPES
