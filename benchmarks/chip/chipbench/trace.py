"""Reduction of a JAX profiler trace to busy and idle time, device time per
compiled module and per operation, and idle gaps named by what the host was
doing.

``events(xplane_path)`` flattens the trace into plain tuples; ``reduce``
works on those alone, so a small recorded trace checks it without a chip.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

# a chip's own plane; on TPU v5e: "/device:TPU:0"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# an op event is named by its whole HLO line; keep "<name> <opcode>"
HLO_LINE = re.compile(r"^%?([^\s=]+) = .*?\s([a-z][a-z0-9-]*)\(")
# the harness's TraceAnnotations ("traced" bounds the window); an idle gap
# is named by the innermost one that covers it, and by the innermost other
# event of that host thread (a dispatch, a transfer).  One opened before the
# profiler started is not in the trace.
ANNOTATIONS = ("fit", "checkpoint")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def op_name(hlo: str) -> str:
    m = HLO_LINE.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo


def events(xplane_path: str) -> list[Event]:
    """Device module and op events, and the events of the host thread that
    holds the harness's annotations, from a ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (MODULE_LINE, OP_LINE):
                    out.extend(Event(plane.name, line.name, op_name(e.name),
                                     float(e.start_ns), float(e.duration_ns))
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = list(line.events)
                if any(e.name == "traced" for e in evs):
                    out.extend(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns))
                               for e in evs)
    return out


@dataclasses.dataclass
class Summary:
    window_s: float                 # the "traced" annotation's length
    busy_s: float                   # mean over chips of the union of op time
    chips: int
    module_s: dict[str, float]      # device seconds per compiled module
    op_s: dict[str, float]          # device seconds per XLA op name
    gaps: dict[str, float]          # idle seconds by what the host was doing

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top(self, table: dict[str, float], n: int = 10) -> list[list]:
        return [[k, v] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host_labels(host: list[Event], times: list[float]) -> list[str]:
    """For each time, the innermost harness annotation and the innermost
    other host event (a dispatch, a transfer) that cover it."""
    import numpy as np

    def innermost(evs):
        if not evs:
            return [None] * len(times)
        s = np.array([e.start_ns for e in evs])
        e = np.array([e.end_ns for e in evs])
        out = []
        for t in times:
            hit = np.nonzero((s <= t) & (t < e))[0]
            out.append(evs[hit[np.argmax(s[hit])]].name if hit.size else None)
        return out

    ann = innermost([e for e in host if e.name in ANNOTATIONS])
    other = innermost([e for e in host if e.name not in ANNOTATIONS
                       and e.name != "traced"])
    return [" / ".join(x for x in (a, o) if x) or "no host event"
            for a, o in zip(ann, other)]


def reduce(evs: list[Event], min_gap_ns: float = 10e3) -> Summary:
    """Busy time, per-module and per-op device time and labelled idle gaps,
    inside the host's ``traced`` annotation."""
    traced = [e for e in evs if e.plane == HOST_PLANE and e.name == "traced"]
    if not traced:
        raise ValueError("trace has no 'traced' annotation")
    t0, t1 = traced[0].start_ns, traced[0].end_ns
    host = [e for e in evs if e.plane == HOST_PLANE and e.end_ns > t0
            and e.start_ns < t1]
    device = defaultdict(list)
    for e in evs:
        if DEVICE_PLANE.match(e.plane) and e.end_ns > t0 and e.start_ns < t1:
            device[e.plane].append(e)
    if not device:
        raise ValueError("no device operation ran inside the traced window")

    module_s = defaultdict(float)
    op_s = defaultdict(float)
    gaps = defaultdict(float)
    busy = 0.0
    for plane, pevs in device.items():
        ops = [e for e in pevs if e.line == OP_LINE] or \
              [e for e in pevs if e.line == MODULE_LINE]
        spans = _union((max(e.start_ns, t0), min(e.end_ns, t1)) for e in ops)
        busy += sum(e - s for s, e in spans)
        for e in pevs:
            d = min(e.end_ns, t1) - max(e.start_ns, t0)
            if e.line == MODULE_LINE:
                module_s[e.name] += d * 1e-9
            else:
                op_s[e.name] += d * 1e-9
        edges = [t0] + [x for se in spans for x in se] + [t1]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        long = [(s, e) for s, e in idle if e - s >= min_gap_ns]
        short = sum(e - s for s, e in idle if e - s < min_gap_ns)
        if short:
            gaps[f"between ops (<{min_gap_ns * 1e-3:g} us)"] += short * 1e-9
        labels = _host_labels(host, [0.5 * (s + e) for s, e in long])
        for label, (s, e) in zip(labels, long):
            gaps[label] += (e - s) * 1e-9
    n = len(device)
    return Summary(
        window_s=(t1 - t0) * 1e-9, busy_s=busy * 1e-9 / n, chips=n,
        module_s={k: v / n for k, v in module_s.items()},
        op_s={k: v / n for k, v in op_s.items()},
        gaps={k: v / n for k, v in gaps.items()},
    )
