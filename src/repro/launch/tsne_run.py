"""t-SNE launcher — single-device (estimator API) or sharded runs.

    PYTHONPATH=src python -m repro.launch.tsne_run --dataset digits --n 1797
    PYTHONPATH=src python -m repro.launch.tsne_run --method fft --n 4096
    PYTHONPATH=src python -m repro.launch.tsne_run --dataset mnist --n 4096 --devices 4

``--devices`` needs that many visible devices (the four chips of a v5e
2x2 host, or virtual CPU devices that a rehearsal sets up itself with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools

import jax


@functools.partial(jax.jit, static_argnames=(
    "mesh", "theta", "exag", "mom", "lr", "min_gain"))
def _dist_step(state, graph, *, mesh, theta, exag, mom, lr, min_gain):
    """One sharded GD step.  Module-level so the compile cache is shared
    across iterations; the graph's arrays are operands (not closure
    captures baked into the jaxpr as constants).  (exag, mom) take two
    values each over a run — at most 4 traces."""
    from repro.core.distributed import distributed_bh_gradient
    from repro.core.tsne import gd_update

    res = distributed_bh_gradient(mesh, state.y, graph.p_cols, graph.p_vals,
                                  graph.p_logp, theta=theta, exaggeration=exag)
    return gd_update(state, res.grad, lr, mom, min_gain), res.kl


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="digits")
    ap.add_argument("--n", type=int, default=1797)
    ap.add_argument("--iters", type=int, default=500)
    ap.add_argument("--perplexity", type=float, default=30.0)
    ap.add_argument("--theta", type=float, default=0.5)
    ap.add_argument("--method", default="barnes_hut",
                    help="gradient backend: exact | barnes_hut | fft | any registered name")
    ap.add_argument("--devices", type=int, default=1,
                    help=">1: shard points over a data mesh (distributed step)")
    ap.add_argument("--out", default="tsne_out.npy")
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np
    from repro.api import TSNE
    from repro.compile_cache import enable_compile_cache
    from repro.core.tsne import TsneConfig, init_state, preprocess
    from repro.data.datasets import make_dataset

    enable_compile_cache()
    x, _ = make_dataset(args.dataset, n=args.n)
    cfg = TsneConfig(perplexity=args.perplexity, theta=args.theta, n_iter=args.iters)

    if args.devices <= 1:
        est = TSNE(method=args.method, perplexity=args.perplexity,
                   angle=args.theta, n_iter=args.iters, verbose=1)
        emb = est.fit_transform(x)
        np.save(args.out, emb)
        print(f"KL={est.kl_divergence_:.4f} n_iter={est.n_iter_} -> {args.out}")
        return

    # distributed path: points sharded over a 1-D data mesh, the graph
    # built by the exact KNN ring over the same devices
    mesh = jax.make_mesh((args.devices,), ("data",))
    n = args.n - args.n % args.devices
    graph, _ = preprocess(jnp.asarray(x[:n]), dataclasses.replace(
        cfg, neighbor_method="sharded", neighbor_options={"mode": "exact"},
        knn_shards=args.devices))
    state = init_state(n, cfg)
    lr = cfg.resolve_lr(n)

    for it in range(args.iters):
        exag = cfg.early_exaggeration if it < cfg.exaggeration_iters else 1.0
        mom = cfg.momentum_initial if it < cfg.momentum_switch_iter else cfg.momentum_final
        state, kl = _dist_step(state, graph, mesh=mesh, theta=cfg.theta,
                               exag=exag, mom=mom, lr=lr,
                               min_gain=cfg.min_gain)
        if (it + 1) % 100 == 0:
            print(f"iter {it+1} KL {float(kl):.4f}")
    np.save(args.out, np.asarray(state.y))
    print(f"distributed run done -> {args.out}")


if __name__ == "__main__":
    main()
