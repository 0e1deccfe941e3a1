"""End-to-end driver matching the paper's headline experiment: t-SNE on the
mouse-brain-cell dataset (1.3M cells x 20 PCA components).

    PYTHONPATH=src python examples/mouse_pipeline.py --n 50000 --iters 1000
    PYTHONPATH=src python examples/mouse_pipeline.py \
        --n 1000000 --shards 4 --chunk-size 100000 --method fft \
        --iters 300 --bench-out .

This is the paper's kind of workload end-to-end: KNN -> BSP -> symmetrize ->
gradient-descent iterations with per-stage timings (paper Fig. 1b /
Table 5).  --n scales the subsample; the full 1291337 points run with
--n 1291337.

Memory envelope: at large --n the pipeline is *chunk/shard-bounded, not
N-bounded*.  The KNN stage defaults to the ``sharded`` backend (per-shard
rp_forest + candidate ring) above ``--n`` 200k, whose transients are
O(block_rows * candidates) per shard; the perplexity search and the ELL
symmetrization stream over ``--chunk-size`` row slices, so beyond the
O(N*K) neighbor graph itself (the product) nothing larger than
O(chunk * K) is ever live.  Nothing in the pipeline materializes anything
O(N^2).  Pass --neighbor_method exact to get the brute-force scan back for
oracle comparisons at small --n.

``--shards S`` rings the KNN over S of the visible devices (0 = all of
them); it fails when fewer than S are visible.
Checkpointing (--ckpt_dir) makes multi-hour full-size runs restartable;
``--bench-out DIR`` records the run as the next ``BENCH_<n>.json`` artifact
with the per-phase breakdown (docs/BENCHMARKS.md schema).
"""
import argparse
import pathlib
import sys
import time


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50000)
    ap.add_argument("--iters", type=int, default=1000)
    ap.add_argument("--perplexity", type=float, default=30.0)
    ap.add_argument("--theta", type=float, default=0.5)
    ap.add_argument("--method", default="barnes_hut",
                    help="gradient backend: barnes_hut | fft | exact | ...")
    ap.add_argument("--neighbor_method", default="auto",
                    help="auto (sharded above 200k points, else rp_forest) | "
                         "exact | rp_forest | nn_descent | sharded | any "
                         "registered name")
    ap.add_argument("--n_neighbors", type=int, default=None,
                    help="KNN degree (default: 3 * perplexity)")
    ap.add_argument("--shards", type=int, default=0,
                    help="device shards for the sharded KNN ring (0 = all "
                         "visible devices)")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="rows per BSP/symmetrize slice (0 = auto: 100k "
                         "chunks above 200k points, unchunked below)")
    ap.add_argument("--kl_every", type=int, default=50)
    ap.add_argument("--ckpt_dir", default="")
    ap.add_argument("--ckpt_every", type=int, default=200)
    ap.add_argument("--out", default="mouse_embedding.npy")
    ap.add_argument("--bench-out", default="",
                    help="directory for a BENCH_<n>.json artifact of this "
                         "run (empty = don't write one)")
    return ap.parse_args()


AUTO_SCALE_N = 200_000      # above this, default to sharded KNN + chunking
AUTO_CHUNK = 100_000


def main():
    args = parse_args()
    import jax.numpy as jnp
    import numpy as np

    from repro.api import make_backend
    from repro.compile_cache import enable_compile_cache
    from repro.core.tsne import TsneConfig, init_state, preprocess, tsne_step
    from repro.data.datasets import make_dataset

    enable_compile_cache()

    neighbor_method = args.neighbor_method
    if neighbor_method == "auto":
        neighbor_method = "sharded" if args.n >= AUTO_SCALE_N else "rp_forest"
    chunk = args.chunk_size or (AUTO_CHUNK if args.n >= AUTO_SCALE_N else None)

    print(f"generating mouse-like dataset: {args.n} cells x 20 components")
    x, _ = make_dataset("mouse_1p3m", n=args.n)
    cfg = TsneConfig(perplexity=args.perplexity, theta=args.theta,
                     n_iter=args.iters, neighbor_method=neighbor_method,
                     n_neighbors=args.n_neighbors,
                     chunk_size=chunk,
                     knn_shards=args.shards or None,
                     method=args.method)

    t0 = time.perf_counter()
    graph, timings = preprocess(jnp.asarray(x), cfg)
    print(f"KNN[{timings['neighbor_method']}, k={timings['n_neighbors']}] "
          f"{timings['knn']:.1f}s  BSP {timings['bsp']:.1f}s  "
          f"symmetrize {timings['symmetrize']:.1f}s  "
          f"(chunk_size={timings['chunk_size']})")

    state = init_state(args.n, cfg)
    ckpt = None
    start = 0
    if args.ckpt_dir:
        from repro.train.checkpoint import CheckpointManager
        ckpt = CheckpointManager(args.ckpt_dir)
        if ckpt.latest_step() is not None:
            state, start = ckpt.restore(state)
            print(f"resumed from iteration {start}")

    lr = cfg.resolve_lr(args.n)
    backend = make_backend(cfg.method, cfg, args.n)
    t_gd = time.perf_counter()
    kl = float("nan")
    for it in range(start, args.iters):
        exag = cfg.early_exaggeration if it < cfg.exaggeration_iters else 1.0
        mom = cfg.momentum_initial if it < cfg.momentum_switch_iter else cfg.momentum_final
        state, stats = tsne_step(
            state, graph, jnp.asarray(exag, jnp.float32),
            jnp.asarray(mom, jnp.float32),
            backend=backend, lr=lr, min_gain=cfg.min_gain)
        if (it + 1) % args.kl_every == 0 or it == args.iters - 1:
            kl = float(stats.kl)
            print(f"iter {it+1:5d}  KL {kl:.4f}  "
                  f"max_traversal {int(stats.max_traversal)}  "
                  f"{(time.perf_counter()-t_gd)/(it+1-start)*1000:.0f} ms/iter")
        if ckpt is not None and (it + 1) % args.ckpt_every == 0:
            ckpt.save(it + 1, state)
    if ckpt is not None:
        ckpt.wait()
    state.y.block_until_ready()
    timings["gradient_descent"] = time.perf_counter() - t_gd
    total_s = time.perf_counter() - t0
    np.save(args.out, np.asarray(state.y))
    print(f"total {total_s:.1f}s; embedding -> {args.out}")

    if args.bench_out:
        # benchmarks/ is a repo-root package, not installed — make it
        # importable no matter where this script was launched from
        sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
        from benchmarks.common import emit, record_phases, write_bench_json
        run_name = f"mouse_pipeline_n{args.n}_{cfg.method}"
        emit(run_name, total_s * 1e6,
             f"kl={kl:.4f} iters={args.iters} knn={timings['neighbor_method']} "
             f"shards={args.shards or 'all'} chunk={timings['chunk_size']}")
        record_phases(run_name, timings)
        path = write_bench_json(args.bench_out, benches=["mouse_pipeline"],
                                argv=sys.argv[1:], wall_s=total_s)
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
