"""One run of one cell: data, warm-up, the measured window, the check.

The window drives the public entry ``repro.api.TSNE.fit`` with the
configuration's own parameters and times it on the harness's clock.  Its
work is the same in every run: passes over the traffic's set of
``random_states`` (one fit each, in an order drawn from the seed), as many
whole passes as start before ``seconds`` have passed, at least one.  The
fit's checkpoint callback marks when each checkpoint was reported.
"""
from __future__ import annotations

import dataclasses
import glob
import sys
import tempfile
import time
from typing import Any

import numpy as np

from chipbench import check, data, spec, trace


# the traced window: from the first fit's first checkpoint to its third
TRACE_CHECKPOINTS = (1, 3)


@dataclasses.dataclass
class Fit:
    random_state: int
    start: float                  # harness clock at the call
    first_checkpoint: float       # ... when the first checkpoint was reported
    first_iteration: int          # iteration of that checkpoint
    end: float                    # ... when the embedding was on the host
    n_iter: int
    timings: dict
    kl_path: dict                 # iteration -> KL reported at that checkpoint
    ok: bool


@dataclasses.dataclass
class Run:
    """What the per-layer metric readers read."""
    fits: list[Fit]
    trace: dict | None            # summary, iterations traced


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _Profile:
    """The profiler over the first fit's steps between two checkpoints, with
    a ``traced`` annotation whose bounds are the traced window."""

    def __init__(self):
        import jax
        self.jax = jax
        self.dir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        self.ann = None
        self.summary = None

    def start(self):
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # Python frames would slow the host
        self.jax.profiler.start_trace(self.dir.name, profiler_options=opts)
        self.ann = self.jax.profiler.TraceAnnotation("traced")
        self.ann.__enter__()

    def stop(self):
        if self.ann is None:
            return
        ann, self.ann = self.ann, None
        ann.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        try:
            path, = glob.glob(f"{self.dir.name}/plugins/profile/*/*.xplane.pb")
            self.summary = trace.reduce(trace.events(path))
        finally:
            self.dir.cleanup()


def fit_once(make, x, random_state: int,
             profile: _Profile | None = None) -> tuple[Any, Fit]:
    """One ``TSNE.fit`` on the harness's clock."""
    import jax
    marks: list[tuple[float, int]] = []
    kl_path: dict[int, float] = {}

    def on_checkpoint(stats):
        with jax.profiler.TraceAnnotation("checkpoint"):
            marks.append((time.perf_counter(), stats.iteration))
            kl_path[stats.iteration] = stats.kl
            if profile is not None:
                if len(marks) == TRACE_CHECKPOINTS[0]:
                    profile.start()
                elif len(marks) == TRACE_CHECKPOINTS[1]:
                    profile.stop()

    est = make(random_state, callbacks=(on_checkpoint,))
    start = time.perf_counter()
    ok = True
    try:
        with jax.profiler.TraceAnnotation("fit"):
            est.fit(x)
    except Exception as e:           # a failed fit is counted, not fatal
        log(f"fit failed: {type(e).__name__}: {e}")
        ok = False
    end = time.perf_counter()
    if ok:
        emb = est.embedding_
        ok = emb.shape == (x.shape[0], 2) and bool(np.isfinite(emb).all())
    first = marks[0] if marks else (end, 0)
    return est, Fit(random_state=random_state, start=start,
                    first_checkpoint=first[0], first_iteration=first[1],
                    end=end, n_iter=int(getattr(est, "n_iter_", 0)),
                    timings=dict(getattr(est, "timings_", None) or {}),
                    kl_path=kl_path, ok=ok)


def end_to_end(fits: list[Fit]) -> dict[str, float]:
    """The window's timings over all its fits, on the harness's clock."""
    done = [f for f in fits if f.ok]
    out = {"fit_s": (done[-1].end - fits[0].start) / len(done)} if done else {}
    steady = [f for f in done if f.n_iter > f.first_iteration]
    if steady:
        iters = sum(f.n_iter - f.first_iteration for f in steady)
        descent = sum(f.end - f.first_checkpoint for f in steady)
        per_iter = descent / iters
        out["iter_ms"] = 1e3 * per_iter
        # to the first checkpoint, less the iterations before it
        out["graph_s"] = sum(f.first_checkpoint - f.start
                             - f.first_iteration * per_iter
                             for f in steady) / len(steady)
    return out


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_start: float) -> dict:
    import jax

    from chipbench.clock import CompileClock
    from repro.api import TSNE

    devices = jax.devices()
    clock = CompileClock(jax)
    snap = clock.snapshot()
    x = data.points(cell.config["dataset"])
    params = dict(cell.config["tsne"])
    kl_every = int(cell.traffic["kl_every"])
    states = [int(s) for s in cell.traffic["random_states"]]

    def make(random_state, callbacks=(), **over):
        return TSNE(**{**params, **over}, random_state=random_state,
                    kl_every=kl_every, callbacks=callbacks)

    # W, the step's ELL width, is known only after a graph build: warm up
    # with a short fit of the same data
    fit_once(lambda rs, callbacks: make(
        rs, callbacks, n_iter=int(cell.traffic["warmup_n_iter"])),
        x, states[0])
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s: {clock.since(snap)}")

    profile = _Profile() if traced else None
    rng = np.random.default_rng([seed, 1])
    order = [states[i] for i in rng.permutation(len(states))]
    fits: list[Fit] = []
    kept = []                             # the fit the check judges
    snap = clock.snapshot()
    t0 = time.perf_counter()
    while len(fits) % len(order) or (
            not fits or time.perf_counter() - t0 < seconds):
        first = profile is not None and not fits
        est, f = fit_once(make, x, order[len(fits) % len(order)],
                          profile if first else None)
        if first:
            profile.stop()                # if the fit ended before it did
        fits.append(f)
        if rng.random() < 1 / len(fits):  # a uniform draw over the fits
            kept[:] = [(est, f)]
        del est
    log(f"window {time.perf_counter() - t0:.3f}s, {len(fits)} fits: "
        f"{clock.since(snap)}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    result: dict[str, Any] = {"correct": False, "attempted": len(fits),
                              "failed": sum(not f.ok for f in fits)}
    if traced:
        summary = profile.summary
        if summary is None:
            raise RuntimeError("the traced window was not reduced")
        tr = {"summary": summary,
              "iterations": kl_every * (TRACE_CHECKPOINTS[1]
                                        - TRACE_CHECKPOINTS[0])}
        run_ = Run(fits=fits, trace=tr)
        values = {m["name"]: spec.metric_reader(m["name"])(run_)
                  for m in cell.per_layer}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.per_layer if values[m["name"]] is not None}
    else:
        values = {**end_to_end(fits), "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    result["metrics"] = metrics
    d0 = devices[0]
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": len(devices), "memory_peak_bytes": int(peak)}
    if traced:
        result["device"].update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top(summary.op_s),
                               "idle_gaps": summary.top(summary.gaps)}

    t_check = time.perf_counter()
    # judge holds the one reference left, and frees the program's state
    # before the reference runs
    numbers = judge(cell, x, *kept.pop(), rng, clock) if kept else {}
    log(f"check {time.perf_counter() - t_check:.3f}s")
    limits = cell.limits
    for k in sorted(set(numbers) - set(limits)):
        log(f"not compared: {k} = {numbers[k]!r}")
    result["correct"] = bool(result["failed"] == 0
                             and check.verdict(numbers, limits))
    result["check"] = {k: {"value": numbers.get(k), "limit": v}
                       for k, v in limits.items()}
    for k, v in limits.items():
        log(f"check {k} = {numbers.get(k)!r} (limit {v})")
    return result


def judge(cell: spec.Cell, x, est, fit: Fit, rng: np.random.Generator,
          clock) -> dict[str, float]:
    """The check's numbers for one fitted estimator."""
    import jax.numpy as jnp

    t = time.perf_counter()
    snap = clock.snapshot()
    got, probe = check.program_side(est, check.schedule(cell.config), rng,
                                    fit.kl_path)
    y = est.embedding_
    del est
    log(f"check: program side {time.perf_counter() - t:.3f}s "
        f"({clock.since(snap)})")
    t = time.perf_counter()
    descend = bool({"descent_rel", "kl_path_gap"} & set(cell.limits))
    ref = check.reference_side(x, y, cell.config, probe, fit.random_state,
                               fit.n_iter, jnp.float32, descend=descend)
    log(f"check: reference {time.perf_counter() - t:.3f}s")
    return check.compare(got, ref)
