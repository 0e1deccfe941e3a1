#!/usr/bin/env python3
"""Where the descent step's time goes, layer by layer, for one cell's
configuration on the chip.

    python3 benchmarks/chip/scope_trace.py --workload digits-bh.fit-full \
        --profile-from 50 --out chiprun_out/trace.json.gz

It runs, in one process:

1. the cell's own fit (its ``n_iter`` and ``kl_every``): the phase timings,
   and at each checkpoint the seconds since the descent began and the
   Barnes-Hut walk's turns (longest and mean);
2. a short fit with a checkpoint every iteration, the profiler on from
   the checkpoint at ``--profile-from``, a ``traced`` window over the next
   two iterations, and the profiler off one iteration later, so that every
   program span the window touches is whole; the trace reduced: the step
   module's device time, its time per layer (``chipbench.scopes``), and
   the share of the idle time inside the program's ``step`` and
   ``checkpoint`` spans;
3. the same short fit without the profiler: ms per iteration over the
   same window, against the profiled one;
4. the host time of a ``step`` span with no profile running.

The last line on standard output is one JSON object.  ``--out`` writes the
window's events and the step's layer table, gzipped (a test fixture).
``--n`` cuts the points, to rehearse off the chip (the reduction needs a
TPU's trace).
"""
import argparse
import glob
import gzip
import json
import pathlib
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# the short fits' checkpoint interval: one iteration, each step whole
KL_EVERY = 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def cell_fit(make, x, random_state, kl_every, n_iter):
    """One fit on the host clock; its checkpoints as the observer saw them."""
    seen = []

    def on_checkpoint(s):
        seen.append((s.iteration, s.elapsed_s, s.max_traversal,
                     s.mean_traversal))

    est = make(random_state, n_iter=n_iter, kl_every=kl_every,
               callbacks=(on_checkpoint,))
    t = time.perf_counter()
    est.fit(x)
    wall = time.perf_counter() - t
    phases = {k: v for k, v in est.timings_.items()
              if k in ("knn", "bsp", "symmetrize", "gradient_descent")}
    return {"wall_s": wall, "timings": phases,
            "fit_less_phases_s": wall - sum(phases.values()),
            "checkpoints": [
                {"iteration": i, "elapsed_s": e, "max_traversal": m,
                 "mean_traversal": a} for i, e, m, a in seen]}


def short_fit(make, x, random_state, first, profile_dir=None):
    """A fit to the fifth checkpoint from ``first``; with ``profile_dir``
    the profiler runs from the first to the fifth, and a ``traced``
    annotation bounds the second to the fourth."""
    import jax

    marks = [first + KL_EVERY * i for i in range(5)]
    at: dict[int, float] = {}
    ann = []

    def on_checkpoint(s):
        at[s.iteration] = time.perf_counter()
        if profile_dir is None:
            return
        if s.iteration == marks[0]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
        elif s.iteration == marks[1]:
            # made while the profiler runs, or it records nothing
            ann.append(jax.profiler.TraceAnnotation("traced"))
            ann[0].__enter__()
        elif s.iteration == marks[3]:
            ann[0].__exit__(None, None, None)
        elif s.iteration == marks[4]:
            jax.profiler.stop_trace()

    make(random_state, n_iter=marks[4], kl_every=KL_EVERY,
         callbacks=(on_checkpoint,)).fit(x)
    return 1e3 * (at[marks[3]] - at[marks[1]]) / (marks[3] - marks[1])


def reduce_window(path, iterations):
    """The window's numbers, and the events and layer table to keep."""
    from chipbench import scopes, trace

    evs = trace.events(path)
    s = trace.reduce(evs)
    t0, t1 = next((e.start_ns, e.end_ns) for e in evs
                  if e.plane == trace.HOST_PLANE and e.name == "traced")
    module = scopes.module_s(s)
    table, secs = scopes.window_layers(s)
    idle, covered = scopes.idle_in_spans(evs)
    entry = sum(secs.values())
    numbers = {
        "window_s": s.window_s, "busy_s": s.busy_s,
        "step_module_ms_per_iter": 1e3 * module / iterations,
        "layer_ms_per_iter": {k: 1e3 * v / iterations
                              for k, v in sorted(secs.items())},
        "unscoped_share_of_module": secs.get(scopes.UNSCOPED, 0.0) / module,
        "layers_over_module": entry / module,
        "idle_s": idle, "idle_in_program_spans_s": covered,
        "gaps": s.top(s.gaps),
        "top_ops": s.top(s.op_s),
    }
    keep = [e for e in evs if e.end_ns > t0 and e.start_ns < t1]
    return numbers, keep, table


def step_span_us(rounds=200, n=1000):
    """Host microseconds of one ``step`` span with no profile running (a
    fresh tracer per ``n`` spans, as in a fit), less the bare loop's."""
    from repro import obs

    def loop(span):
        tr = obs.Tracer()
        t = time.perf_counter()
        for i in range(n):
            if span:
                with tr.span("step", step_num=i):
                    pass
        return (time.perf_counter() - t) / n * 1e6

    spans = sorted(loop(True) for _ in range(rounds))
    bare = sorted(loop(False) for _ in range(rounds))
    return spans[rounds // 2] - bare[rounds // 2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--profile-from", type=int, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--n", type=int, default=None)
    args = ap.parse_args()

    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax

    from chipbench import clock, data, spec
    from repro.api import TSNE

    clock.use_checkout_cache(jax)
    cell = spec.load_cell(args.workload)
    dataset = dict(cell.config["dataset"])
    if args.n:
        dataset["n"] = args.n
    x = data.points(dataset)
    params = dict(cell.config["tsne"])
    random_state = int(cell.traffic["random_states"][0])

    def make(rs, **over):
        return TSNE(**{**params, **over}, random_state=rs)

    out = {"workload": cell.name, "device": jax.devices()[0].device_kind,
           "step_span_us": step_span_us()}
    t = time.perf_counter()
    out["cell_fit"] = cell_fit(make, x, random_state,
                               int(cell.traffic["kl_every"]),
                               int(params["n_iter"]))
    log(f"cell fit {time.perf_counter() - t:.3f}s")
    iters = 2 * KL_EVERY
    with tempfile.TemporaryDirectory(prefix="scope-trace-") as d:
        out["profiled_ms_per_iter"] = short_fit(
            make, x, random_state, args.profile_from, d)
        out["unprofiled_ms_per_iter"] = short_fit(
            make, x, random_state, args.profile_from)
        path, = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
        try:
            numbers, keep, table = reduce_window(path, iters)
        except ValueError as e:          # no TPU plane off the chip
            log(f"not reduced: {e}")
            numbers, keep, table = {}, [], {}
    out.update(numbers)
    if args.out and keep:
        with gzip.open(args.out, "wt") as f:
            json.dump({"events": [[e.plane, e.line, e.name, e.start_ns,
                                   e.dur_ns] for e in keep],
                       "layers": table, "iterations": iters}, f)
        log(f"wrote {args.out}: {len(keep)} events")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
