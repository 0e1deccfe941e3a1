"""Paper Tables 5/6: per-step time, daal4py-like naive vs Acc-t-SNE-like
optimized, both executed for real on CPU.

The "naive" column reimplements the baseline's work profile (per-level point
re-partitioning, level-barrier summarization, sequential per-row loops,
uncompressed tree); "optimized" is this framework's Morton pipeline.
Absolute times are this container's single CPU core; the *ratio* is the
algorithmic reproduction of the paper's speedups.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.common import build_tree, emit, time_fn, tsne_fixture
from repro.core import bsp, morton, naive, quadtree
from repro.core.attractive import attractive_forces_ell
from repro.core.repulsive import bh_repulsion_sorted
from repro.core.summarize import summarize
from repro.kernels.ops import attractive_forces_ell as attractive_pallas
from repro.kernels.ops import morton_encode as morton_pallas


@functools.partial(jax.jit, static_argnames=("iters",))
def _bsp_rowloop(d2, perplexity, iters=64):
    """single-thread-like BSP: sequential over rows (lax.map)."""
    def one(row):
        p, b = bsp.binary_search_perplexity(row[None, :], perplexity, iters=iters)
        return p[0]
    return jax.lax.map(one, d2)


@jax.jit
def _morton_pipeline(y):
    cent, r = morton.span_radius(y)
    codes = morton.morton_encode(y, cent, r)
    cs, ys, perm = quadtree.sort_points_by_code(y, codes)
    tree = quadtree.build_quadtree(cs)
    return tree.n_nodes, cs, ys


@jax.jit
def _summarize_only(tree, ys, r):
    return summarize(tree, ys, r)


def run(n: int = 20000, perplexity: float = 30.0, theta: float = 0.5):
    fx = tsne_fixture(n, perplexity=perplexity)
    y = fx["y"]
    # Pallas rows are compiled kernels only on a TPU
    pallas = "(compiled)" if jax.default_backend() == "tpu" else "(interpret mode)"

    # --- BSP (paper: 1.0x single-thread, 17x multithreaded) ---
    # the sequential row loop is timed on a row subsample (it is O(rows) by
    # construction); both variants see the same rows so the ratio is fair
    n_bsp = min(n, 2000)
    d2_sub = fx["d2"][:n_bsp]
    t_naive = time_fn(lambda: _bsp_rowloop(d2_sub, perplexity), iters=2)
    t_opt = time_fn(lambda: bsp.binary_search_perplexity(d2_sub, perplexity))
    t_bsp_pl = time_fn(
        lambda: bsp.binary_search_perplexity(d2_sub, perplexity, impl="pallas"),
        iters=2,
    )
    emit(f"bsp_naive_rowloop_n{n_bsp}", t_naive, "")
    emit(f"bsp_vectorized_n{n_bsp}", t_opt, f"speedup={t_naive / t_opt:.1f}x")
    emit(f"bsp_pallas_n{n_bsp}", t_bsp_pl, pallas)

    # --- Quadtree building (paper: 4.5x single-thread, 14.3x multicore) ---
    t_naive = time_fn(lambda: naive.naive_build_and_summarize(y)[0])
    t_opt = time_fn(lambda: _morton_pipeline(y)[0])
    emit(f"tree_naive_levelwise_n{n}", t_naive, "")
    emit(f"tree_morton_n{n}", t_opt, f"speedup={t_naive / t_opt:.1f}x")

    # --- Summarization (paper: 5.3x / 32.4x) ---
    cent, r, codes, cs, ys, perm, tree = build_tree(y)
    # naive: the level-synchronized segment reduction inside the naive build
    t_naive_sum = time_fn(lambda: naive.naive_build_and_summarize(y)[1][-1])
    t_opt_sum = time_fn(lambda: _summarize_only(tree, ys, r).com)
    emit(f"summarize_levelwise_n{n}", t_naive_sum, "(includes naive build)")
    emit(f"summarize_prefix_n{n}", t_opt_sum, f"speedup={t_naive_sum / t_opt_sum:.1f}x")

    # --- Attractive (paper: 2.2x single-thread) ---
    # every variant timed under jit — the production path (tsne_step) always
    # runs jitted; eager lax.map dispatch would mis-time the blocked kernel
    from repro.core.attractive import attractive_forces_ell_blocked
    ell_j = jax.jit(attractive_forces_ell)
    blk_j = jax.jit(attractive_forces_ell_blocked, static_argnames=("block",))
    t_naive = time_fn(lambda: naive.naive_attractive(y, fx["cols"], fx["vals"]))
    t_opt = time_fn(lambda: ell_j(y, fx["cols"], fx["vals"])[0])
    t_blk = time_fn(lambda: blk_j(y, fx["cols"], fx["vals"])[0])
    t_pallas = time_fn(lambda: attractive_pallas(y, fx["cols"], fx["vals"])[0])
    emit(f"attractive_rowloop_n{n}", t_naive, "")
    emit(f"attractive_vectorized_n{n}", t_opt, f"speedup={t_naive / t_opt:.1f}x")
    emit(f"attractive_blocked_n{n}", t_blk,
         f"speedup={t_naive / t_blk:.1f}x (cache-blocked, default)")
    emit(f"attractive_pallas_n{n}", t_pallas,
         f"speedup={t_naive / t_pallas:.1f}x {pallas}")

    # --- Repulsive (paper: 6.0x single-thread) ---
    summ = _summarize_only(tree, ys, r)
    _, _, _, csu, ysu, permu, tree_u = build_tree(y, compress=False)
    summ_u = _summarize_only(tree_u, ysu, r)
    t_naive = time_fn(lambda: bh_repulsion_sorted(ysu, tree_u, summ_u, theta).force, iters=3)
    t_opt = time_fn(lambda: bh_repulsion_sorted(ys, tree, summ, theta).force, iters=3)
    steps_u = int(jnp.max(bh_repulsion_sorted(ysu, tree_u, summ_u, theta).steps))
    steps_c = int(jnp.max(bh_repulsion_sorted(ys, tree, summ, theta).steps))
    emit(f"repulsive_uncompressed_n{n}", t_naive, f"max_traversal={steps_u}")
    emit(f"repulsive_compressed_n{n}", t_opt,
         f"speedup={t_naive / t_opt:.1f}x max_traversal={steps_c}")

    # --- Morton code formation (Alg. 1) xla vs pallas ---
    cent, r = morton.span_radius(y)
    t_xla = time_fn(lambda: morton.morton_encode(y, cent, r))
    t_pl = time_fn(lambda: morton_pallas(y, cent, r))
    emit(f"morton_xla_n{n}", t_xla, "")
    emit(f"morton_pallas_n{n}", t_pl, pallas)

    # --- FFT-repulsion interpolation spread/gather, xla vs pallas ---
    from repro.core.fft_repulsion import fft_repulsion
    fft_n = min(n, 4000)
    y_fft = y[:fft_n]
    t_fx = time_fn(lambda: fft_repulsion(y_fft, n_boxes=48)[0], iters=3)
    t_fp = time_fn(
        lambda: fft_repulsion(y_fft, n_boxes=48, interp_impl="pallas")[0],
        iters=2,
    )
    emit(f"fft_interp_xla_n{fft_n}", t_fx, "")
    emit(f"fft_interp_pallas_n{fft_n}", t_fp, pallas)
