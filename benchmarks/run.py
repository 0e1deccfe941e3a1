"""Benchmark harness — one bench per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--bench steps,e2e,accuracy,scaling,knn]
                                            [--quick] [--large] [--n N] [--scale S]
                                            [--out-dir DIR | --no-json]
                                            [--trace [PATH]]
                                            [--compare PREV.json]
                                            [--compare-threshold 0.25]

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit) and
persists the full run — rows + per-phase fit breakdowns (paper Tables 5/6)
+ machine info + git provenance — as the next ``BENCH_<n>.json`` in
``--out-dir`` (default: the repo root), the per-PR perf-trajectory artifact
the ROADMAP calls for.  ``--bench`` names are validated against the known
set; an unknown name (e.g. a typo like ``--bench step``) is an error, not a
silent no-op run.  ``--trace`` enables the process-global span tracer for
the whole run and writes a Perfetto-loadable Chrome-trace JSON (default
``trace_bench.json`` next to the artifact).
``--compare PREV.json`` turns the artifact chain into a *regression gate*:
after the run, every bench present in both runs is diffed
(``benchmarks.common.compare_runs``) and the process exits nonzero if any
matched bench is more than ``--compare-threshold`` (default 25%) slower —
wired into CI against the previous run's uploaded artifact, so the
trajectory accumulates AND regressions fail the build instead of living
silently in commit messages.  The new artifact is still written first:
a regressing run is recorded, then failed.
Paper mapping: steps -> Tables 5/6; e2e -> Table 4 / Fig 4; accuracy ->
Table 3; scaling -> Fig 5/6 (algorithmic form — see bench_scaling docstring).
Roofline reporting lives in benchmarks/roofline.py (dry-run JSON mode plus
``--tsne``, the compiled-HLO hot-path ranking that picks kernel targets).
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

KNOWN_BENCHES = ("steps", "accuracy", "scaling", "e2e", "knn")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default=",".join(KNOWN_BENCHES),
                    help=f"comma-separated subset of {', '.join(KNOWN_BENCHES)}")
    ap.add_argument("--quick", action="store_true", help="small sizes (CI)")
    ap.add_argument("--large", action="store_true",
                    help="scaling bench only: drive the fused sharded+chunked "
                         "pipeline at 100k/500k/1M points (slow — minutes to "
                         "hours; never part of --quick CI)")
    ap.add_argument("--n", type=int, default=None, help="points for step bench")
    ap.add_argument("--scale", type=float, default=None, help="e2e dataset scale")
    ap.add_argument("--out-dir", default=str(REPO_ROOT),
                    help="directory for the BENCH_<n>.json artifact")
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing the BENCH_<n>.json artifact")
    ap.add_argument("--trace", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="enable span tracing; write Chrome-trace JSON to "
                         "PATH (default: <out-dir>/trace_bench.json)")
    ap.add_argument("--compare", default=None, metavar="PREV.json",
                    help="diff this run against a previous BENCH_<n>.json "
                         "and exit nonzero on regression")
    ap.add_argument("--compare-threshold", type=float, default=0.25,
                    help="relative slowdown that fails --compare "
                         "(default: 0.25 = 25%%)")
    args = ap.parse_args()
    benches = [b.strip() for b in args.bench.split(",") if b.strip()]
    unknown = [b for b in benches if b not in KNOWN_BENCHES]
    if not benches:
        ap.error("--bench selected no benchmarks")
    if unknown:
        ap.error(
            f"unknown bench name(s): {', '.join(unknown)} "
            f"(known: {', '.join(KNOWN_BENCHES)})"
        )

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    tracer = None
    if args.trace is not None:
        from repro import obs
        tracer = obs.set_tracer(obs.Tracer())

    t0 = time.time()
    print("name,us_per_call,derived")

    if "steps" in benches:
        from benchmarks import bench_steps
        bench_steps.run(n=args.n or (4000 if args.quick else 20000))
    if "accuracy" in benches:
        from benchmarks import bench_accuracy
        bench_accuracy.run(n=600 if args.quick else 1500,
                           n_iter=120 if args.quick else 300)
    if "scaling" in benches:
        from benchmarks import bench_scaling
        sizes = (1000, 2000, 4000) if args.quick else (2000, 4000, 8000, 16000, 32000)
        bench_scaling.run(sizes=sizes, exact_cap=2000 if args.quick else 8000)
        if args.large and not args.quick:
            bench_scaling.run_large()
        elif args.large:
            print("# --large ignored under --quick", file=sys.stderr)
    if "e2e" in benches:
        from benchmarks import bench_e2e
        bench_e2e.run(n_iter=60 if args.quick else 250,
                      scale=args.scale or (0.15 if args.quick else 1.0))
    if "knn" in benches:
        from benchmarks import bench_knn
        bench_knn.run(sizes=(2000, 5000) if args.quick else (2000, 10000, 50000),
                      k=15 if args.quick else 30)

    wall_s = time.time() - t0
    print(f"# total_bench_wall_s,{wall_s:.1f},", file=sys.stderr)
    if not args.no_json:
        from benchmarks.common import write_bench_json
        path = write_bench_json(
            args.out_dir, benches=benches, argv=sys.argv[1:], wall_s=wall_s
        )
        print(f"# wrote {path}", file=sys.stderr)
    if tracer is not None:
        trace_path = args.trace or str(
            pathlib.Path(args.out_dir) / "trace_bench.json")
        tracer.to_chrome_trace(trace_path, process_name="benchmarks")
        print(f"# wrote Chrome trace ({len(tracer.spans)} spans) to "
              f"{trace_path}", file=sys.stderr)
    if args.compare is not None:
        sys.exit(run_compare_gate(args.compare, args.compare_threshold))


def run_compare_gate(prev_path: str, threshold: float) -> int:
    """Diff the rows of this run against ``prev_path``; 1 on regression.

    Factored out of :func:`main` so the regression-exit path is unit-testable
    without re-running the benches (tests/test_bench_compare.py).
    """
    from benchmarks.common import ROWS, compare_runs, load_bench_json
    prev = load_bench_json(prev_path)
    lines, regressions = compare_runs(prev, ROWS, threshold=threshold)
    print(f"# --compare vs {prev_path} "
          f"(commit {prev.get('git', {}).get('commit', 'unknown')[:12]})",
          file=sys.stderr)
    for line in lines:
        print(line, file=sys.stderr)
    if regressions:
        print(f"# FAIL: {len(regressions)} bench(es) regressed more than "
              f"{threshold:.0%}:", file=sys.stderr)
        for name, p, us, delta in regressions:
            print(f"#   {name}: {p:.1f}us -> {us:.1f}us ({delta:+.1%})",
                  file=sys.stderr)
        return 1
    print("# compare OK: no bench regressed beyond threshold", file=sys.stderr)
    return 0


if __name__ == "__main__":
    main()
