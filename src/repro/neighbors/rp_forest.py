"""Random-projection tree forest (Annoy / FIt-SNE-style approximate KNN).

Each tree recursively halves the point set ``depth`` times with a median
hyperplane split — expressed as one multi-key ``lax.sort`` per level over
(segment id, projection), so the whole forest build is a handful of sorts
and matmuls, fully jittable with static shapes.  Leaves then hold
``ceil(N / 2^depth)`` points; within each leaf we score all pairs exactly
and keep the top-k, and the per-tree graphs are merged with duplicate
dropping.  Recall grows with ``n_trees`` and ``leaf_size``; an optional
``refine_iters`` polish runs NN-descent over the forest output.

The same forest doubles as an out-of-sample query index: the build records
each level's median split threshold, so a new point routes down every tree
(project, compare, descend — ``depth`` dot products per tree) to a leaf
whose members are scored exactly and merged across trees.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax import lax

from repro.neighbors._candidates import (
    candidate_sq_dists, merge_topk, seed_graph,
)
from repro.neighbors.base import (
    register_neighbor_backend, validate_k, validate_query_k,
)


@functools.partial(jax.jit, static_argnames=("depth", "n_pad"))
def _build_tree(
    x: jax.Array, key: jax.Array, depth: int, n_pad: int
) -> tuple[jax.Array, jax.Array, tuple[jax.Array, ...]]:
    """One tree: leaf membership + the structure needed to route queries.

    Level ``l`` sorts each of the 2^l equal-length segments by the points'
    projection onto that level's random direction; halving sorted segments
    is exactly a median split, so the tree stays perfectly balanced.  Pads
    project to +inf and sink to the high side of every split.

    Returns ``(leaves [2^depth, leaf_size] point indices (pads hold
    idx >= N), dirs [depth, D] hyperplane directions, thrs)`` where
    ``thrs[l] [2^l]`` is the split value of each level-``l`` node — the
    midpoint of the two projections straddling the median, so a query goes
    right iff its projection exceeds it.
    """
    n, d = x.shape
    dirs = jax.random.normal(key, (depth, d), x.dtype)
    # full f32 (as in route_to_leaves): a query routes like a member point
    proj = jnp.matmul(x, dirs.T, precision=lax.Precision.HIGHEST) if depth else None
    order = jnp.arange(n_pad, dtype=jnp.int32)
    big = jnp.asarray(jnp.finfo(x.dtype).max, x.dtype)
    pos = jnp.arange(n_pad, dtype=jnp.int32)
    thrs = []
    for level in range(depth):
        seg_len = n_pad >> level
        seg = pos // seg_len
        p = jnp.where(order < n, proj[jnp.clip(order, 0, n - 1), level], big)
        _, p_s, order = lax.sort((seg, p, order), num_keys=2)
        half = seg_len >> 1
        p2 = p_s.reshape(1 << level, seg_len)
        thrs.append(0.5 * (p2[:, half - 1] + p2[:, half]))
    return order.reshape(1 << depth, n_pad >> depth), dirs, tuple(thrs)


@functools.partial(jax.jit, static_argnames=("k", "n_pad"))
def _leaf_topk(x: jax.Array, leaves: jax.Array, k: int, n_pad: int):
    """Exact top-k within each leaf's candidate set, scattered per point.

    Returns ``(idx [n_pad, kk], d2 [n_pad, kk])`` with ``kk = min(k, S-1)``;
    rows >= N are pad slots the caller slices off.
    """
    n = x.shape[0]
    n_leaves, s = leaves.shape
    kk = min(k, s - 1)
    safe = jnp.clip(leaves, 0, n - 1)
    xb = x[safe]                                     # [L, S, D]
    sqn = jnp.sum(xb * xb, axis=2)
    d2 = sqn[:, :, None] + sqn[:, None, :] - 2.0 * jnp.einsum(
        "lsd,ltd->lst", xb, xb, precision=lax.Precision.HIGHEST
    )
    big = jnp.asarray(jnp.finfo(x.dtype).max, x.dtype)
    pad_col = (leaves >= n)[:, None, :]
    self_col = jnp.eye(s, dtype=bool)[None]
    d2 = jnp.where(pad_col | self_col, big, d2)
    neg_top, argtop = lax.top_k(-d2, kk)             # [L, S, kk]
    glob = jnp.take_along_axis(
        jnp.broadcast_to(leaves[:, None, :], (n_leaves, s, s)), argtop, axis=2
    )
    out_i = jnp.zeros((n_pad, kk), jnp.int32).at[leaves.reshape(-1)].set(
        glob.reshape(-1, kk)
    )
    out_d = jnp.zeros((n_pad, kk), x.dtype).at[leaves.reshape(-1)].set(
        jnp.maximum(-neg_top, 0.0).reshape(-1, kk)
    )
    return out_i, out_d


@functools.partial(
    jax.jit, static_argnames=("k", "n_trees", "depth", "block_rows")
)
def rp_forest_knn(
    x: jax.Array,
    k: int,
    *,
    n_trees: int = 8,
    depth: int = 4,
    seed: int = 0,
    block_rows: int = 512,
) -> tuple[jax.Array, jax.Array]:
    """Approximate KNN via ``n_trees`` RP trees of ``depth`` median splits."""
    n = x.shape[0]
    leaf = -(-n // (1 << depth))                     # ceil(n / 2^depth)
    n_pad = leaf << depth
    key = jax.random.PRNGKey(seed)
    best_i, best_d = seed_graph(x, k, jax.random.fold_in(key, n_trees),
                                block_rows=block_rows)
    # collect every tree's within-leaf top-k, then fold once: a single wide
    # dedup/top-k merge beats n_trees narrow ones (the sort dominates)
    cand_i, cand_d = [], []
    for t in range(n_trees):
        leaves, _, _ = _build_tree(x, jax.random.fold_in(key, t), depth, n_pad)
        ti, td = _leaf_topk(x, leaves, k, n_pad)
        cand_i.append(ti[:n])
        cand_d.append(td[:n])
    return merge_topk(
        best_i, best_d,
        jnp.concatenate(cand_i, axis=1), jnp.concatenate(cand_d, axis=1),
        k, n,
    )


@functools.partial(jax.jit, static_argnames=("n_trees", "depth", "n_pad"))
def build_forest_index(
    x: jax.Array, n_trees: int, depth: int, n_pad: int, seed: int = 0
):
    """Stack every tree's routing structure: the frozen query-side forest.

    Returns ``(leaves [T, 2^depth, leaf_size], dirs [T, depth, D],
    thrs)`` with ``thrs[l] [T, 2^l]`` — the same trees (same PRNG folds)
    ``rp_forest_knn`` builds, so queries descend the forest the fitted
    points were bucketed by.
    """
    key = jax.random.PRNGKey(seed)
    keys = jax.vmap(functools.partial(jax.random.fold_in, key))(
        jnp.arange(n_trees))
    # one batched sort per level for all trees, not n_trees unrolled ones:
    # the TPU compile time grows with the number of large sorts
    return jax.vmap(lambda k: _build_tree(x, k, depth, n_pad))(keys)


def route_to_leaves(
    leaves: jax.Array,
    dirs: jax.Array,
    thrs: tuple[jax.Array, ...],
    q: jax.Array,
) -> jax.Array:
    """Descend every tree with each query point; gather its leaf's members.

    q [M, D] -> cand [M, n_trees * leaf_size] reference-set indices (entries
    >= the fitted N are leaf padding the caller must mask).  This is the
    routing half of :func:`forest_query`, shared with the distributed
    candidate ring (``core/distributed.ring_knn_approx``) where scoring and
    merging happen against a remote shard's running top-k.
    """
    n_trees, _, leaf_size = leaves.shape
    depth = dirs.shape[1]
    m = q.shape[0]
    tree_ids = jnp.arange(n_trees, dtype=jnp.int32)[None, :]      # [1, T]
    node = jnp.zeros((m, n_trees), jnp.int32)
    if depth:
        proj = jnp.einsum("md,tld->mtl", q, dirs,                 # [M, T, depth]
                          precision=lax.Precision.HIGHEST)
        for level in range(depth):
            thr = thrs[level][tree_ids, node]                     # [M, T]
            node = node * 2 + (proj[:, :, level] > thr).astype(jnp.int32)
    return leaves[tree_ids, node].reshape(m, n_trees * leaf_size)


@functools.partial(jax.jit, static_argnames=("k", "block_rows"))
def forest_query(
    x_ref: jax.Array,
    leaves: jax.Array,
    dirs: jax.Array,
    thrs: tuple[jax.Array, ...],
    q: jax.Array,
    k: int,
    block_rows: int = 512,
) -> tuple[jax.Array, jax.Array]:
    """Route queries down every tree, score leaf members exactly, merge.

    q [M, D] -> (idx [M, k] into x_ref, d2 [M, k]).  A deterministic seed
    row (the first k reference points, scored exactly) guarantees k valid
    distinct indices even if the forest candidates collapse to duplicates.
    """
    n = x_ref.shape[0]
    m = q.shape[0]
    cand = route_to_leaves(leaves, dirs, thrs, q)
    cd = candidate_sq_dists(x_ref, cand, block_rows=block_rows, q=q)
    base_i = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32)[None], (m, k))
    base_d = candidate_sq_dists(x_ref, base_i, block_rows=block_rows, q=q)
    return merge_topk(base_i, base_d, cand, cd, k, n, exclude_self=False)


@dataclasses.dataclass(frozen=True, eq=False)
class RPForestIndex:
    """Frozen RP forest over a fitted reference set, ready for queries."""

    x_ref: jax.Array
    leaves: jax.Array                      # [T, 2^depth, leaf_size]
    dirs: jax.Array                        # [T, depth, D]
    thrs: tuple[jax.Array, ...]            # level l: [T, 2^l]
    block_rows: int = 512

    @property
    def n_reference(self) -> int:
        return int(self.x_ref.shape[0])

    def query(self, x_new: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
        validate_query_k(self.n_reference, k)
        return forest_query(
            self.x_ref, self.leaves, self.dirs, self.thrs,
            x_new.astype(self.x_ref.dtype), k, block_rows=self.block_rows,
        )


@dataclasses.dataclass(frozen=True)
class RPForestNeighbors:
    """Forest of random-projection trees; ``refine_iters`` adds NN-descent
    polish passes over the forest graph (see ``nn_descent.py``)."""

    name: ClassVar[str] = "rp_forest"
    n_trees: int = 8
    leaf_size: int = 64
    refine_iters: int = 2
    seed: int = 0
    block_rows: int = 512

    def resolve_depth(self, n: int, k: int) -> int:
        """Deepest split keeping leaves >= max(leaf_size, k+1) points, so a
        single leaf can supply a full top-k row."""
        leaf = max(self.leaf_size, k + 1)
        return max(0, int(math.floor(math.log2(max(1.0, n / leaf)))))

    def neighbors(self, x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
        validate_k(x.shape[0], k)
        idx, d2 = rp_forest_knn(
            x, k,
            n_trees=self.n_trees,
            depth=self.resolve_depth(x.shape[0], k),
            seed=self.seed,
            block_rows=self.block_rows,
        )
        if self.refine_iters > 0:
            from repro.neighbors.nn_descent import nn_descent_knn
            # offset the seed: refine rounds must not replay the PRNG keys
            # that drew the tree hyperplanes (fold_in shares the int domain)
            idx, d2 = nn_descent_knn(
                x, k, init=(idx, d2), n_iters=self.refine_iters,
                seed=self.seed + 1, block_rows=self.block_rows,
            )
        return idx, d2

    def build_index(self, x: jax.Array) -> RPForestIndex:
        """Build (once) the forest a fitted reference set is bucketed by.

        Depth matches the ``neighbors`` heuristic with ``k = leaf_size - 1``
        so leaves keep >= ``leaf_size`` points regardless of later query k;
        ``validate_query_k`` bounds k at query time.
        """
        x = jnp.asarray(x)
        n = int(x.shape[0])
        depth = self.resolve_depth(n, max(1, min(self.leaf_size, n) - 1))
        leaf = -(-n // (1 << depth))
        n_pad = leaf << depth
        leaves, dirs, thrs = build_forest_index(
            x, self.n_trees, depth, n_pad, seed=self.seed
        )
        return RPForestIndex(
            x_ref=x, leaves=leaves, dirs=dirs, thrs=thrs,
            block_rows=self.block_rows,
        )


register_neighbor_backend("rp_forest", RPForestNeighbors)
