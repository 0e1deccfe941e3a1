#!/usr/bin/env python3
"""Smoke run of the t-SNE system on a TPU, through the entry points users call.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the sharded paths only

One chip: every registered Pallas kernel against its oracle at the mnist
cell's shapes; three ``TSNE.fit`` runs on the 70,000 x 784 mnist stand-in
(Barnes-Hut, FFT, Barnes-Hut with the Pallas kernels); one gradient of each
approximate backend against the exact one on a 4,096-point subsample; and
32 out-of-sample requests through ``EmbeddingService``.

Four chips: the README's million-point KNN (``neighbor_method="sharded"``
over 4 devices) on the 1,291,337 x 20 mouse stand-in, checked for recall
against brute force, and ``distributed_bh_gradient`` against the
single-device ``bh_gradient``.

Everything runs in this one process (a chip belongs to one process).  Each
phase prints one line with its seconds and the XLA compile seconds inside
them; a phase that fails raises, so the script exits non-zero.  The last
line is ``{"ok": true, "device": {...}}``; without a TPU the script exits
non-zero before any phase and never prints it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

MNIST_N, K, PERPLEXITY = 70_000, 90, 30.0
FIT_ITERS, KL_EVERY = 30, 10
REF_N = 4_096
N_REQUESTS = 32
RECALL_MIN = 0.90
RECALL_QUERIES = 4_096
MOUSE_N = None                  # None: all 1,291,337 points
GRAD_N = 65_536                 # points in the distributed-gradient check
# distributed vs single-device BH gradient: both walk the same tree over the
# same points; only float summation order differs (psum of per-shard Z and
# KL terms).  The CPU with 4 virtual devices gave 7.4e-8 and 0 at 200,000
# points; 1e-4 leaves room for the chip's own reduction orders
DIST_REL_GRAD_MAX = 1e-4
DIST_REL_KL_MAX = 1e-4


class Failure(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise Failure(what)


class CompileClock:
    """Sums XLA's backend-compile seconds and persistent-cache hits."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    try:
        yield
    except BaseException:
        print(f"phase {name}: FAILED after {time.perf_counter() - t0:.3f}s",
              flush=True)
        raise
    print(f"phase {name}: ok {time.perf_counter() - t0:.3f}s "
          f"(compile {clock.seconds - c0:.3f}s, "
          f"cache hits {clock.cache_hits - h0})", flush=True)


def close(out, ref, rtol, atol, what):
    import numpy as np
    out, ref = np.asarray(out), np.asarray(ref)
    check(out.shape == ref.shape, f"{what}: shape {out.shape} != {ref.shape}")
    err = np.abs(out - ref) - (atol + rtol * np.abs(ref))
    check(np.isfinite(out).all() and (err <= 0).all(),
          f"{what}: {int((err > 0).sum())} of {out.size} elements outside "
          f"rtol={rtol} atol={atol} (worst excess {float(err.max()):.3e})")


# ---------------------------------------------------------------- one chip --

def kernel_cases():
    """name -> (pallas kwargs, arguments, compare(out, ref)); the mnist
    cell's shapes, the inputs and tolerances of tests/test_kernels.py."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fft_repulsion, morton

    rng = np.random.default_rng(0)
    n, w = MNIST_N, 397               # symmetric ELL width of the mnist graph
    nodes = 48 * (fft_repulsion.P_ORDER - 1) + 1

    def f32(a):
        return jnp.asarray(a, jnp.float32)

    y = f32(rng.normal(size=(n, 2)) * 10)
    cent, r_span = morton.span_radius(y)
    y5 = f32(rng.normal(size=(n, 2)) * 5)
    base, wx, wy, _ = fft_repulsion.interp_coords(y5, 48)
    charges = jnp.stack([jnp.ones((n,), jnp.float32), y5[:, 0], y5[:, 1]], 1)

    def exact(o, r):
        check((np.asarray(o) == np.asarray(r)).all(), "morton_encode: codes differ")

    def pairwise(o, r):
        close(o, np.maximum(np.asarray(r), 0), 2e-4, 1e-4, "pairwise_sq_dists")

    def attractive(o, r):
        close(o[0], r[0], 1e-5, 1e-7, "attractive_ell force")
        close(o[1], r[1], 1e-5, 0.0, "attractive_ell kl")

    def bsp(o, r):
        close(o[0], r[0], 1e-5, 1e-7, "bsp_search p")
        close(o[1], r[1], 1e-5, 0.0, "bsp_search beta")

    def interp(what):
        return lambda o, r: close(o, r, 1e-4, 1e-5, what)

    return {
        "morton_encode": (dict(depth=16), (y, cent, r_span), exact),
        "pairwise_sq_dists": ({}, (f32(rng.normal(size=(512, 784))),
                                   f32(rng.normal(size=(2048, 784)))), pairwise),
        "attractive_ell": ({}, (
            f32(rng.normal(size=(n, 2))),
            jnp.asarray(rng.integers(0, n, size=(n, w)), jnp.int32),
            f32(rng.uniform(0, 1e-3, size=(n, w)))), attractive),
        "bsp_search": ({}, (f32(np.abs(rng.normal(size=(n, K))) * 4),
                            PERPLEXITY), bsp),
        "fft_spread": (dict(nodes=nodes), (base, wx, wy, charges),
                       interp("fft_spread")),
        "fft_gather": ({}, (f32(rng.normal(size=(nodes, nodes, 4))), base, wx, wy),
                       interp("fft_gather")),
    }


def kernel_phase() -> None:
    import jax

    from repro.kernels import ops

    cases = kernel_cases()
    registry = ops.kernel_registry()
    check(set(cases) == set(registry), f"kernels {sorted(registry)} vs cases "
          f"{sorted(cases)}")
    # as users call them: kernels and oracles set their own matmul precision
    for name, (kwargs, args, compare) in cases.items():
        t0 = time.perf_counter()
        compiled = registry[name]["pallas"].lower(*args, **kwargs).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no tpu_custom_call in the compiled program")
        out = jax.block_until_ready(compiled(*args))
        t1 = time.perf_counter()
        compare(out, registry[name]["ref"](*args, **kwargs))
        print(f"  kernel {name}: compiled and ran {t1 - t0:.3f}s, "
              "matches ref", flush=True)


def fit_phase(x):
    """Three fits of the full mnist stand-in; returns the Barnes-Hut one."""
    import numpy as np

    from repro.api import TSNE

    fits = {}
    for label, method, options in (("barnes_hut", "barnes_hut", {}),
                                   ("fft", "fft", {}),
                                   ("barnes_hut+pallas", "barnes_hut",
                                    {"use_pallas": True})):
        t0 = time.perf_counter()
        est = TSNE(method=method, perplexity=PERPLEXITY, n_iter=FIT_ITERS,
                   kl_every=KL_EVERY, random_state=0, backend_options=options)
        est.fit(x)
        wall = time.perf_counter() - t0
        kl = np.asarray(est.kl_history_)
        timings = {k: (round(v, 3) if isinstance(v, float) else v)
                   for k, v in est.timings_.items()}
        print(f"  fit {label}: {wall:.3f}s timings {json.dumps(timings)}",
              flush=True)
        print(f"  fit {label}: KL checkpoints "
              f"{[(int(i), round(float(v), 5)) for i, v in kl]}", flush=True)
        check(est.embedding_.shape == (x.shape[0], 2), f"{label}: shape")
        check(np.isfinite(est.embedding_).all(), f"{label}: non-finite embedding")
        check(len(kl) == FIT_ITERS // KL_EVERY and np.isfinite(kl[:, 1]).all(),
              f"{label}: KL checkpoints {kl.tolist()}")
        if options.get("use_pallas"):
            check(est.timings_["bsp_impl"] == "pallas", f"{label}: bsp impl")
        fits[label] = est
    return fits["barnes_hut"]


def reference_phase(x) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.api.reference import (
        REFERENCE_BOUNDS, gradient_error, reference_embedding,
    )
    from repro.core.tsne import TsneConfig, preprocess

    rows = np.sort(np.random.default_rng(0).choice(x.shape[0], REF_N,
                                                   replace=False))
    xs = x[rows]
    cfg = TsneConfig(perplexity=PERPLEXITY)
    graph, _ = preprocess(jnp.asarray(xs), cfg)
    y = reference_embedding(xs)
    for method, (overrides, bound) in REFERENCE_BOUNDS.items():
        rel, dkl = gradient_error(method, y, graph, cfg)
        print(f"  reference {method} {overrides}: relative gradient error "
              f"{rel:.4e} (bound {bound.rel_grad}), |dKL| {dkl:.4e} "
              f"(bound {bound.abs_kl})", flush=True)
        check(rel < bound.rel_grad and dkl < bound.abs_kl,
              f"{method} strays from exact: {bound.reason}")


def service_phase(model) -> None:
    import numpy as np

    from repro.data.datasets import make_dataset
    from repro.embed.service import EmbeddingService, TransformRequest

    held_out, _ = make_dataset("mnist", n=N_REQUESTS, seed=1)
    service = EmbeddingService(slots=8)
    service.add_model("mnist", model)
    for i, xi in enumerate(held_out):
        service.submit(TransformRequest(rid=i, dataset="mnist", x=xi))
    done = service.run()
    check(len(done) == N_REQUESTS, f"{len(done)} of {N_REQUESTS} answered")
    check(all(r.y is not None and np.isfinite(r.y).all() for r in done),
          "non-finite answer")
    s = service.stats()
    print(f"  service: {s['completed']} answers, {s['ticks']} ticks, latency "
          f"p50 {s['latency_s_p50']:.4f}s p99 {s['latency_s_p99']:.4f}s",
          flush=True)


def one_chip(clock) -> None:
    from repro.data.datasets import make_dataset

    with phase("kernels", clock):
        kernel_phase()
    with phase("data", clock):
        x, _ = make_dataset("mnist")
        check(x.shape == (MNIST_N, 784), f"mnist shape {x.shape}")
    with phase("fits", clock):
        model = fit_phase(x)
    with phase("reference", clock):
        reference_phase(x)
    with phase("service", clock):
        service_phase(model)


# -------------------------------------------------------------- four chips --

def sharded_knn_phase(x, devices, config):
    """The README's sharded KNN over four devices; recall@K against brute
    force.  Returns (idx, d2)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.knn import knn_query
    from repro.neighbors import make_neighbor_backend

    nb = make_neighbor_backend("sharded", config.resolve_neighbor_options())
    t0 = time.perf_counter()
    idx, d2 = nb.neighbors(jnp.asarray(x), K)
    idx.block_until_ready()
    spread = idx.sharding.device_set
    print(f"  sharded knn: {time.perf_counter() - t0:.3f}s, idx {idx.shape} "
          f"on {len(spread)} device(s)", flush=True)
    check(spread == set(devices), f"KNN output on {sorted(map(str, spread))}")
    q = np.sort(np.random.default_rng(0).choice(x.shape[0], RECALL_QUERIES,
                                                replace=False))
    # brute force over all points; the query itself comes back first
    want, _ = knn_query(jnp.asarray(x[q]), jnp.asarray(x), K + 1)
    want = np.asarray(want)
    got = np.asarray(idx[q])
    hits = [len(set(got[i]) & (set(want[i]) - {q[i]})) for i in range(len(q))]
    recall = float(np.sum(hits)) / (len(q) * K)
    print(f"  recall@{K} on {len(q)} queries: {recall:.5f} "
          f"(bound {RECALL_MIN})", flush=True)
    check(recall >= RECALL_MIN, f"recall@{K} {recall} < {RECALL_MIN}")
    return idx, d2


def distributed_gradient_phase(x, idx, d2, config, devices) -> None:
    """distributed_bh_gradient on four devices against bh_gradient on one.

    On the first GRAD_N points: the compile of both programs grows with N
    (130-146 s each at 262,144 points for a described v5e), and the
    comparison needs no more.  P is the perplexity-calibrated KNN
    graph (p_{j|i} over the sharded KNN's edges, renormalized), with the
    edges into the dropped points zeroed; symmetrizing it is host work that
    four chips would not speed up.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.api.reference import reference_embedding
    from repro.core import bsp
    from repro.core.distributed import distributed_bh_gradient
    from repro.core.tsne import NeighborGraph, bh_gradient

    n = min(GRAD_N, x.shape[0] - x.shape[0] % len(devices))
    cond_p, _ = bsp.binary_search_perplexity_chunked(
        d2[:n], PERPLEXITY, config.resolve_chunk_size(n))
    cols = idx[:n]
    keep = cols < n
    vals = jnp.where(keep, cond_p, 0.0)
    vals = vals / jnp.sum(vals)
    cols = jnp.where(keep, cols, jnp.arange(n, dtype=cols.dtype)[:, None])
    p_logp = jnp.sum(jnp.where(vals > 0, vals * jnp.log(vals), 0.0))
    y = reference_embedding(x[:n])
    kw = dict(theta=0.5, exaggeration=1.0, depth=16)

    graph = NeighborGraph.from_ell(np.asarray(cols), np.asarray(vals),
                                   p_logp, config)
    one = jax.device_put((y, graph), devices[0])
    t0 = time.perf_counter()
    g1 = jax.block_until_ready(jax.jit(
        lambda y, g: bh_gradient(y, g, **kw))(*one))
    t1 = time.perf_counter()
    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
    rows = NamedSharding(mesh, PartitionSpec("data"))
    four = jax.device_put((y, cols, vals), rows) + (
        jax.device_put(p_logp, NamedSharding(mesh, PartitionSpec())),)
    g4 = jax.block_until_ready(jax.jit(
        functools.partial(distributed_bh_gradient, mesh, **kw))(*four))
    t2 = time.perf_counter()
    rel = float(np.linalg.norm(np.asarray(g4.grad) - np.asarray(g1.grad))
                / np.linalg.norm(np.asarray(g1.grad)))
    dkl = abs(float(g4.kl) - float(g1.kl)) / abs(float(g1.kl))
    print(f"  gradient at N={n}: one device {t1 - t0:.3f}s, "
          f"{len(devices)} devices {t2 - t1:.3f}s (compile included); "
          f"relative gradient error {rel:.4e} (bound {DIST_REL_GRAD_MAX}), "
          f"relative |dKL| {dkl:.4e} (bound {DIST_REL_KL_MAX}), "
          f"KL {float(g1.kl):.5f}", flush=True)
    check(rel < DIST_REL_GRAD_MAX and dkl < DIST_REL_KL_MAX,
          "distributed gradient strays from the single-device one")


def four_chips(clock, devices) -> None:
    from repro.core.tsne import TsneConfig
    from repro.data.datasets import make_dataset

    # the README's million-point configuration
    config = TsneConfig(perplexity=PERPLEXITY, neighbor_method="sharded",
                        knn_shards=len(devices), chunk_size=100_000)
    with phase("data", clock):
        x, _ = make_dataset("mouse_1p3m", n=MOUSE_N)
    with phase("sharded_knn", clock):
        idx, d2 = sharded_knn_phase(x, devices, config)
    with phase("distributed_gradient", clock):
        distributed_gradient_phase(x, idx, d2, config, devices)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded KNN and distributed gradient "
                         "on four chips")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"needs {need} TPU device(s); JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 1
    print(f"device {devices[0].device_kind} x{len(devices)}, "
          f"compile cache {cache_dir}", flush=True)
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(clock, devices[:4])
    else:
        one_chip(clock)
    print(f"total {time.perf_counter() - t0:.3f}s (compile {clock.seconds:.3f}s, "
          f"cache hits {clock.cache_hits})", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
