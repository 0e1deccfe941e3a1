"""Device time per layer of the descent step, and the idle time that the
program's own spans cover.

The program runs each layer of its step under a ``jax.named_scope``
(``repro.core.scopes``); the names land in the ``op_name`` metadata of the
compiled module.  Each operation of a profiled window is read back to a
layer through the step's compiled module: its entry computation names every
top-level operation, and the innermost of :data:`SCOPES` in an operation's
``op_name`` path is its layer.  Only the entry computation counts: a while
loop's time already holds its body's, so counting the body's operations
too would count them twice.  Top-level operations of the step under none of
the scopes go to :data:`UNSCOPED`.

A program without the scopes reads as all ``unscoped``, and a reader of a
layer finds nothing.

The op time comes from ``Summary.op_s``, keyed by ``"<name> <opcode>"``
across every module of the window; an op of another module that shares a
name with one of the step's is counted too.  A reader therefore reports
nothing when the layers add up to more than :data:`MODULE_TOLERANCE` off
the step module's own device time.
"""
from __future__ import annotations

import re
from collections import defaultdict

from chipbench import trace

try:
    from repro.core.scopes import STEP_SCOPES as SCOPES
except ImportError:          # a program that names no layers
    SCOPES = ()
UNSCOPED = "unscoped"
# the largest share by which the layers may miss the step module's time
MODULE_TOLERANCE = 0.03
# the step's compiled module, by the name XLA gives it
STEP_MODULE = "tsne_step"
# the program's host spans around each step's dispatch and each checkpoint
HOST_SPANS = ("step", "checkpoint")

OP_NAME = re.compile(r'op_name="([^"]*)"')


def layer(op_name: str) -> str:
    """The innermost of :data:`SCOPES` in an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def entry_layers(hlo_text: str) -> dict[str, str]:
    """``"<name> <opcode>"`` (the key of ``Summary.op_s``) -> layer, for each
    instruction of the entry computation of an HLO module's text."""
    out = {}
    lines = iter(hlo_text.splitlines())
    for line in lines:
        if line.startswith("ENTRY "):
            break
    for line in lines:
        if line.startswith("}"):
            break
        line = line.strip()
        if line.startswith("ROOT "):
            line = line[len("ROOT "):]
        key = trace.op_name(line)
        if key != line:
            path = OP_NAME.search(line)
            out[key] = layer(path.group(1) if path else "")
    return out


def step_tables() -> list[dict[str, str]]:
    """:func:`entry_layers` of every compiled step this process holds."""
    import jax

    tables = []
    for exe in jax.devices()[0].client.live_executables():
        try:
            modules = exe.hlo_modules()
        except jax.errors.JaxRuntimeError:   # one that keeps no HLO
            continue
        tables.extend(entry_layers(m.to_string()) for m in modules
                      if STEP_MODULE in m.name)
    return tables


def scope_s(op_s: dict[str, float], table: dict[str, str]) -> dict[str, float]:
    """Device seconds per layer: the window's op time of the step's
    top-level operations, summed by layer."""
    out: dict[str, float] = defaultdict(float)
    for op, seconds in op_s.items():
        name = table.get(op)
        if name is not None:
            out[name] += seconds
    return dict(out)


def window_layers(summary: trace.Summary,
                  tables: list[dict[str, str]] | None = None
                  ) -> tuple[dict[str, str], dict[str, float]]:
    """The step module that ran in the window (of those compiled, the one
    whose top-level operations hold the most of the window's op time), and
    its seconds per layer; empty when no step was compiled."""
    best: tuple[dict[str, str], dict[str, float]] = ({}, {})
    for table in step_tables() if tables is None else tables:
        seconds = scope_s(summary.op_s, table)
        if sum(seconds.values()) > sum(best[1].values()):
            best = (table, seconds)
    return best


def module_s(summary: trace.Summary) -> float:
    """Device seconds of the step's compiled module in the window."""
    return sum(v for k, v in summary.module_s.items() if STEP_MODULE in k)


def ms_per_iter(run, layers: tuple[str, ...]) -> float | None:
    """Device ms per traced iteration in ``layers``; ``None`` without a
    trace, when the step has none of those scopes, or when the layers miss
    the step module's time by more than :data:`MODULE_TOLERANCE` (an op of
    another module counted under a step op's name)."""
    t = run.trace
    if t is None:
        return None
    table, seconds = window_layers(t["summary"])
    if not set(layers) & set(table.values()):
        return None
    module = module_s(t["summary"])
    if abs(sum(seconds.values()) - module) > MODULE_TOLERANCE * module:
        return None
    return 1e3 * sum(seconds.get(n, 0.0) for n in layers) / t["iterations"]


def idle_in_spans(evs: list[trace.Event], names: tuple[str, ...] = HOST_SPANS,
                  min_gap_ns: float = 10e3) -> tuple[float, float]:
    """Idle seconds of the device in gaps of at least ``min_gap_ns`` inside
    the ``traced`` window, and how many of them fall inside a host event
    named in ``names``."""
    t0, t1 = next((e.start_ns, e.end_ns) for e in evs
                  if e.plane == trace.HOST_PLANE and e.name == "traced")
    ops = [(max(e.start_ns, t0), min(e.end_ns, t1)) for e in evs
           if trace.DEVICE_PLANE.match(e.plane) and e.line == trace.OP_LINE
           and e.end_ns > t0 and e.start_ns < t1]
    busy = trace._union(ops)
    edges = [t0] + [x for se in busy for x in se] + [t1]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2])
            if e - s >= min_gap_ns]
    spans = trace._union((e.start_ns, e.end_ns) for e in evs
                         if e.plane == trace.HOST_PLANE and e.name in names)
    idle = sum(e - s for s, e in gaps)
    covered = sum(max(0.0, min(e, b) - max(s, a))
                  for s, e in gaps for a, b in spans)
    return idle * 1e-9, covered * 1e-9
