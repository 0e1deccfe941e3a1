"""Hierarchical span tracer: the timing substrate of the obs layer.

The source paper's analysis lives on per-step breakdowns (Tables 5/6):
knowing *which* phase dominates is what directed every optimization.  This
module makes that analysis reproducible on our own hot paths — every phase
of a fit (knn / bsp / symmetrize / gradient_descent), a transform batch, or
a service tick opens a :class:`Span`:

    tracer = Tracer()
    with tracer.span("knn") as sp:
        idx, d2 = backend.neighbors(x, k)
        sp.sync(idx)            # block_until_ready at span exit
    tracer.durations()["knn"]   # seconds

Two properties matter on a JAX hot path:

* **device-sync-aware timing** — JAX dispatch is asynchronous, so a naive
  ``perf_counter`` pair around a jitted call times the *dispatch*, not the
  work, and the cost surfaces inside whatever phase blocks next.
  ``sp.sync(arrays)`` registers a pytree whose ``block_until_ready`` runs
  at span exit, *before* the end timestamp is taken, so work is attributed
  to the phase that launched it.
* **near-zero disabled overhead** — a disabled tracer's ``span()`` returns
  one reusable no-op context manager (no allocation, no clock read), so
  instrumentation can stay in production code unconditionally.

While a span is open it is also a ``jax.profiler.TraceAnnotation`` of the
same name (a ``StepTraceAnnotation`` for a span opened with ``step_num``),
so the program's phases appear in any JAX profile on the device trace's
clock, over the idle gaps they cause.  With no profile running an
annotation is one flag check.

Spans nest through a per-thread stack; each completed span records its
parent index and depth, which :meth:`Tracer.to_chrome_trace` turns into
Chrome-trace JSON (``traceEvents`` with ``ph: "X"`` complete events)
loadable in Perfetto / ``chrome://tracing``.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Callable

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation


class _NullSpan:
    """Reusable no-op span: the entire disabled-mode surface."""

    __slots__ = ()
    enabled = False

    def annotate(self, **attrs) -> "_NullSpan":
        return self

    def sync(self, value):
        return value

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class Span:
    """One timed region.  Created by :meth:`Tracer.span` and closed by the
    ``with`` block, which first blocks on every pytree registered through
    :meth:`sync`, so asynchronously dispatched device work lands inside the
    span that launched it."""

    __slots__ = ("name", "t0", "t1", "depth", "index", "parent", "attrs",
                 "_tracer", "_stack", "_annotation", "_sync_targets")
    enabled = True

    def __init__(self, tracer: "Tracer", stack: list, name: str, t0: float,
                 index: int, attrs: dict):
        self.name = name
        self.t0 = t0
        self.t1: float | None = None
        self.depth = len(stack)
        self.index = index
        # index of enclosing span, -1 at root
        self.parent = stack[-1].index if stack else -1
        self.attrs = attrs            # "step_num" makes it a profiler step
        self._tracer = tracer
        self._stack = stack           # the opening thread's span stack
        self._annotation = None
        self._sync_targets: list = []

    def annotate(self, **attrs) -> "Span":
        """Attach key/value metadata (lands in ``args`` of the trace event)."""
        self.attrs.update(attrs)
        return self

    def sync(self, value):
        """Register a pytree to ``block_until_ready`` at span exit; returns
        ``value`` unchanged so it can wrap an expression in place."""
        self._sync_targets.append(value)
        return value

    def __enter__(self) -> "Span":
        step_num = self.attrs.get("step_num")
        if step_num is None:
            self._annotation = TraceAnnotation(self.name)
        else:
            self._annotation = StepTraceAnnotation(self.name,
                                                   step_num=step_num)
        self._annotation.__enter__()
        self._stack.append(self)
        return self

    def __exit__(self, *exc) -> bool:
        try:
            for target in self._sync_targets:
                jax.block_until_ready(target)
        finally:
            self._sync_targets.clear()
            self.t1 = self._tracer._clock()
            if self._stack and self._stack[-1] is self:
                self._stack.pop()
            # list.append is atomic: spans closing on several threads
            # need no lock
            self._tracer.spans.append(self)
            self._annotation.__exit__(None, None, None)
        return False

    @property
    def duration_s(self) -> float:
        if self.t1 is None:
            raise RuntimeError(f"span {self.name!r} is still open")
        return self.t1 - self.t0


class Tracer:
    """Collects nested spans; export through :meth:`to_chrome_trace`,
    aggregate through :meth:`durations`.

    Thread-safe: each thread nests on its own stack (Chrome-trace ``tid``),
    completed spans append to one list.  ``clock`` is injectable for
    deterministic tests.
    """

    def __init__(self, enabled: bool = True,
                 clock: Callable[[], float] = time.perf_counter):
        self.enabled = enabled
        self._clock = clock
        self.spans: list[Span] = []      # completed, in close order
        self._local = threading.local()
        self._indices = itertools.count()
        self.t_epoch = clock()           # ts base for exported traces

    # ------------------------------------------------------------ record --

    def _stack(self) -> list[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def span(self, name: str, step_num: int | None = None, **attrs):
        """Open a span named ``name``; with ``step_num`` it is also a
        profiler step of that number.  Disabled tracers return the shared
        no-op span — zero allocation, no clock read."""
        if not self.enabled:
            return NULL_SPAN
        if step_num is not None:
            attrs["step_num"] = step_num
        # next() on an itertools.count is atomic: indices stay unique
        # across threads
        return Span(self, self._stack(), name, self._clock(),
                    next(self._indices), attrs)

    def clear(self) -> None:
        self.spans.clear()

    def _snapshot(self) -> list[Span]:
        """A copy of the completed spans (worker threads append to the
        list while it is read)."""
        return list(self.spans)

    # ----------------------------------------------------------- inspect --

    def find(self, name: str) -> list[Span]:
        return [s for s in self._snapshot() if s.name == name]

    def last(self, name: str) -> Span | None:
        for s in reversed(self._snapshot()):
            if s.name == name:
                return s
        return None

    def durations(self) -> dict[str, float]:
        """Total seconds per span name (summed over occurrences)."""
        out: dict[str, float] = {}
        for s in self._snapshot():
            out[s.name] = out.get(s.name, 0.0) + s.duration_s
        return out

    # ------------------------------------------------------------ export --

    def chrome_trace(self, process_name: str = "tsne") -> dict:
        """Chrome-trace dict: ``traceEvents`` of complete (``ph: "X"``)
        events, one per span, ts/dur in microseconds.  Nesting is implied by
        time containment per ``tid``, which holds because spans nest on a
        per-thread stack."""
        pid = os.getpid()
        events: list[dict] = [dict(
            name="process_name", ph="M", pid=pid, tid=0,
            args=dict(name=process_name),
        )]
        for s in sorted(self._snapshot(), key=lambda s: s.t0):
            ev = dict(
                name=s.name, ph="X", pid=pid, tid=0, cat="phase",
                ts=round((s.t0 - self.t_epoch) * 1e6, 3),
                dur=round(s.duration_s * 1e6, 3),
            )
            if s.attrs:
                ev["args"] = {k: _jsonable(v) for k, v in s.attrs.items()}
            events.append(ev)
        return dict(traceEvents=events, displayTimeUnit="ms")

    def to_chrome_trace(self, path, process_name: str = "tsne") -> None:
        """Write :meth:`chrome_trace` JSON, loadable in Perfetto."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(process_name), f)


def _jsonable(v: Any):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:
        return float(v)       # numpy / jax scalars
    except (TypeError, ValueError):
        return str(v)
