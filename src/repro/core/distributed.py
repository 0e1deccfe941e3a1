"""Distributed Barnes-Hut t-SNE (shard_map) + ring KNN.

Distribution strategy (DESIGN.md §5): *points are sharded, the tree is
replicated*.  Y is tiny (N x 2) next to the per-point work, so every shard
all-gathers the embedding, rebuilds the (identical) Morton quadtree, and
traverses only its own point slice — the multi-device generalization of the
paper's thread-parallel repulsion, with the same attractive/BSP row
parallelism.  Z and the KL terms are psum'd.

Two KNN rings live here:

* :func:`ring_knn` — the *exact* oracle: each shard keeps its query slice
  and streams database shards around the ring, merging running top-k per
  hop — the transfer of hop t+1 overlaps the distance matmul of hop t.
  O(N²/S · D) compute per shard; the recall reference.
* :func:`ring_knn_approx` — the scalable path: every shard builds an
  rp-tree forest over its *local* points only, and the ring streams the
  (query block, running global top-k) state instead of database shards.
  At each hop the hosting shard routes the visiting queries down its own
  resident forest, scores just the ``n_trees * leaf_size`` leaf candidates
  exactly, and folds them into the traveling top-k with *global* indices.
  Per-hop compute is O(n_loc · T·leaf · D) — the N²/S distance tile is
  gone — and every merge is row-blocked (``block_rows``), so peak memory
  is bounded by the block size, not the shard size.

:func:`refine_knn` then polishes the approximate ring's graph with
NN-descent rounds across shards.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import attractive, morton, quadtree
from repro.core._pairwise import pairwise_sq_dists
from repro.core.repulsive import bh_repulsion_sorted
from repro.core.summarize import summarize
from repro.core.tsne import GradResult


def _local_bh_gradient(y_loc, p_cols, p_vals, p_logp, *, axis, theta, exaggeration, depth):
    """shard_map body: y_loc [n_loc, 2]; P rows for the local points."""
    n_loc = y_loc.shape[0]
    rank = jax.lax.axis_index(axis)
    y_full = jax.lax.all_gather(y_loc, axis, tiled=True)          # [N, 2]
    n = y_full.shape[0]

    # replicated tree build (steps 3-4)
    cent, r_span = morton.span_radius(y_full)
    codes = morton.morton_encode(y_full, cent, r_span, depth=depth)
    codes_s, y_s, perm = quadtree.sort_points_by_code(y_full, codes)
    tree = quadtree.build_quadtree(codes_s, depth=depth)
    summ = summarize(tree, y_s, r_span)

    # local slice of sorted positions (inverse permutation of our indices)
    inv = jnp.zeros((n,), jnp.int32).at[perm].set(jnp.arange(n, dtype=jnp.int32))
    my_pos = inv[rank * n_loc + jnp.arange(n_loc, dtype=jnp.int32)]

    # repulsion for local points only (step 6)
    theta2 = jnp.asarray(theta, y_loc.dtype) ** 2
    n_nodes = tree.n_nodes
    cap = tree.capacity
    is_leaf = tree.is_leaf

    def traverse(p, yp):
        def cond(state):
            return state[0] < n_nodes

        def body(state):
            ptr, force, z = state
            kk = jnp.minimum(ptr, cap - 1)
            s, e = tree.start[kk], tree.end[kk]
            cnt = summ.count[kk]
            inside = (s <= p) & (p < e)
            cnt_eff = cnt - jnp.where(inside, 1.0, 0.0)
            sum_eff = summ.sum_y[kk] - jnp.where(inside, yp, jnp.zeros_like(yp))
            com = sum_eff / jnp.maximum(cnt_eff, 1.0)
            diff = yp - com
            d2 = jnp.sum(diff * diff)
            side = summ.side[kk]
            open_ = (~is_leaf[kk]) & (side * side >= theta2 * d2)
            w = jnp.where(open_, 0.0, cnt_eff)
            q = 1.0 / (1.0 + d2)
            return (jnp.where(open_, ptr + 1, tree.skip[kk]),
                    force + (w * q * q) * diff, z + w * q)

        init = (jnp.int32(0), jnp.zeros((2,), y_loc.dtype), jnp.asarray(0.0, y_loc.dtype))
        _, force, z = jax.lax.while_loop(cond, body, init)
        return force, z

    f_rep, z_loc = jax.vmap(traverse)(my_pos, y_loc)
    z = jnp.maximum(jax.lax.psum(jnp.sum(z_loc), axis), 1e-30)

    # attractive for local rows (step 5) — cols are global indices
    yj = y_full[p_cols]
    diff = y_loc[:, None, :] - yj
    d2 = jnp.sum(diff * diff, axis=-1)
    pq = p_vals / (1.0 + d2)
    f_attr = jnp.sum(pq[..., None] * diff, axis=1)
    kl_attr = jax.lax.psum(jnp.sum(p_vals * jnp.log1p(d2)), axis)

    grad = 4.0 * (jnp.asarray(exaggeration, y_loc.dtype) * f_attr - f_rep / z)
    kl = p_logp + kl_attr + jnp.log(z)
    return grad, kl, z


def distributed_bh_gradient(mesh, y, p_cols, p_vals, p_logp, *,
                            theta: float, exaggeration: float, depth: int = 16,
                            axis: str = "data") -> GradResult:
    """y [N,2] / p_cols, p_vals [N,K] sharded over ``axis`` (row-wise)."""
    fn = functools.partial(_local_bh_gradient, axis=axis, theta=theta,
                           exaggeration=exaggeration, depth=depth)
    grad, kl, z = shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=(P(axis), P(), P()),
        check_vma=False,
    )(y, p_cols, p_vals, p_logp)
    return GradResult(grad=grad, kl=kl, z=z, max_traversal=jnp.int32(0))


# ---------------------------------------------------------------------------
# ring KNN
# ---------------------------------------------------------------------------

def ring_knn(mesh, x, k: int, axis: str = "data", *, n_valid: int | None = None):
    """Exact distributed KNN: x [N, D] sharded row-wise over ``axis``.

    Returns (idx [N,k] int32 global indices, d2 [N,k]), sharded like x.
    Each hop overlaps the next shard transfer (collective_permute) with the
    current distance tile (MXU matmul + top-k merge).  Rows >= ``n_valid``
    (default: all rows are valid) are padding — never emitted as neighbors.
    """
    n_dev = mesh.shape[axis]
    n_total = x.shape[0] if n_valid is None else int(n_valid)

    def body(xq):
        n_loc = xq.shape[0]
        rank = jax.lax.axis_index(axis)
        big = jnp.asarray(jnp.finfo(xq.dtype).max, xq.dtype)
        q_idx = rank * n_loc + jnp.arange(n_loc, dtype=jnp.int32)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

        def hop(carry, t):
            chunk, owner, best_d, best_i = carry
            # kick off the next transfer, then compute on the current chunk
            nxt = jax.lax.ppermute(chunk, axis, perm)
            nxt_owner = (owner - 1) % n_dev
            d2 = pairwise_sq_dists(xq, chunk)
            col = owner * n_loc + jnp.arange(n_loc, dtype=jnp.int32)
            mask = (col[None, :] == q_idx[:, None]) | (col[None, :] >= n_total)
            d2 = jnp.where(mask, big, d2)
            cat_d = jnp.concatenate([best_d, d2], axis=1)
            cat_i = jnp.concatenate(
                [best_i, jnp.broadcast_to(col[None, :], d2.shape)], axis=1)
            neg, arg = jax.lax.top_k(-cat_d, k)
            return (nxt, nxt_owner, -neg, jnp.take_along_axis(cat_i, arg, axis=1)), None

        init = (xq, rank, jnp.full((n_loc, k), big, xq.dtype),
                jnp.full((n_loc, k), -1, jnp.int32))
        (chunk, _, best_d, best_i), _ = jax.lax.scan(hop, init, jnp.arange(n_dev))
        return best_i, jnp.maximum(best_d, 0.0)

    return shard_map(body, mesh=mesh, in_specs=P(axis),
                     out_specs=(P(axis), P(axis)), check_vma=False)(x)


# ---------------------------------------------------------------------------
# approximate candidate ring (sharded rp_forest)
# ---------------------------------------------------------------------------

def ring_knn_approx(
    mesh, x, k: int, axis: str = "data", *,
    n_valid: int | None = None,
    n_trees: int = 8,
    leaf_size: int = 64,
    block_rows: int = 4096,
    seed: int = 0,
):
    """Sharded approximate KNN: per-shard rp_forest + candidate ring.

    x [N, D] sharded row-wise over ``axis`` (N divisible by the axis size;
    rows >= ``n_valid`` are padding — they are scored as queries but their
    global indices are never emitted as neighbors).  Returns
    ``(idx [N, k] int32 global indices, d2 [N, k])``, sharded like x.

    Memory model: resident per shard is the local forest
    (``n_trees * [2^depth, leaf]`` int32 + thresholds) and the traveling
    state ``[n_loc, D + 2k]``; every hop's routing/scoring/merge runs over
    ``block_rows``-row slices (lax.map), so transients are
    O(block_rows * (n_trees*leaf_size + k)) regardless of N or shard size.
    Each query visits all S shards once (S hops) and comes home with the
    merged global top-k; a per-hop seed block (the host shard's first k+1
    points) guarantees k distinct valid indices even if forest candidates
    collapse to duplicates.
    """
    import math as _math

    from repro.neighbors.rp_forest import build_forest_index, route_to_leaves
    from repro.neighbors._candidates import candidate_sq_dists, merge_topk

    n_dev = mesh.shape[axis]
    n_pad_total, _ = x.shape
    if n_pad_total % n_dev:
        raise ValueError(f"N={n_pad_total} not divisible by {n_dev} shards")
    n_total = n_pad_total if n_valid is None else int(n_valid)
    n_loc = n_pad_total // n_dev
    if n_loc < k + 1:
        raise ValueError(
            f"shard size {n_loc} must exceed k={k}: lower the shard count"
        )
    # deepest split keeping leaves >= max(leaf_size, k+1) local points, the
    # same heuristic as RPForestNeighbors.resolve_depth
    leaf_floor = max(leaf_size, k + 1)
    depth = max(0, int(_math.floor(_math.log2(max(1.0, n_loc / leaf_floor)))))
    leaf = -(-n_loc // (1 << depth))
    n_pad_loc = leaf << depth
    n_seed = min(k + 1, n_loc)
    block = min(block_rows, n_loc)
    m_pad = -(-n_loc // block) * block
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def body(x_loc):
        rank = jax.lax.axis_index(axis)
        big = jnp.asarray(jnp.finfo(x_loc.dtype).max, x_loc.dtype)
        # decorrelate the per-shard forests: each rank draws its own
        # hyperplanes (fold by a prime so shard 1's seed never collides
        # with shard 0's tree-index folds)
        leaves, dirs, thrs = build_forest_index(
            x_loc, n_trees, depth, n_pad_loc, seed=seed + rank * 7919
        )
        base = rank * n_loc                     # global id of local row 0
        seed_cols = jnp.arange(n_seed, dtype=jnp.int32)[None, :]

        def merge_block(args):
            qb, gb, bi, bd = args
            cand = route_to_leaves(leaves, dirs, thrs, qb)     # local ids
            cand = jnp.concatenate(
                [cand, jnp.broadcast_to(seed_cols, (qb.shape[0], n_seed))],
                axis=1,
            )
            cd = candidate_sq_dists(x_loc, cand, block_rows=block, q=qb)
            # leaf pads (>= n_loc) and global pads (>= n_total) must never
            # escape as neighbor ids; -1 is dropped by merge_topk
            cand_g = jnp.where(cand < n_loc, base + cand, -1)
            cand_g = jnp.where(cand_g < n_total, cand_g, -1)
            cd = jnp.where(cand_g == gb[:, None], big, cd)     # self edge
            return merge_topk(bi, bd, cand_g, cd, k, n_total,
                              exclude_self=False)

        def hop(carry, _):
            q, gid, bi, bd = carry
            nb = m_pad // block
            blk = lambda a: a.reshape(nb, block, *a.shape[1:])
            mi, md = jax.lax.map(
                merge_block, (blk(q), blk(gid), blk(bi), blk(bd))
            )
            bi = mi.reshape(m_pad, k)
            bd = md.reshape(m_pad, k)
            # merged state travels on to the next shard's forest
            out = tuple(jax.lax.ppermute(a, axis, perm)
                        for a in (q, gid, bi, bd))
            return out, None

        gid = base + jnp.arange(n_loc, dtype=jnp.int32)
        pad = m_pad - n_loc
        q0 = jnp.pad(x_loc, ((0, pad), (0, 0)))
        gid0 = jnp.pad(gid, (0, pad), constant_values=-1)
        init = (
            q0, gid0,
            jnp.full((m_pad, k), -1, jnp.int32),
            jnp.full((m_pad, k), big, x_loc.dtype),
        )
        (q, gid, bi, bd), _ = jax.lax.scan(hop, init, None, length=n_dev)
        return bi[:n_loc], jnp.maximum(bd[:n_loc], 0.0)

    return shard_map(body, mesh=mesh, in_specs=P(axis),
                     out_specs=(P(axis), P(axis)), check_vma=False)(x)


def refine_knn(
    mesh, x, idx, d2, k: int, axis: str = "data", *,
    n_valid: int | None = None,
    n_iters: int = 5,
    n_sample: int = 24,
    n_reverse: int = 24,
    block_rows: int = 4096,
    seed: int = 0,
):
    """NN-descent rounds over a row-sharded KNN graph (``nn_descent_knn``'s
    rounds, across shards).

    x [N, D], idx/d2 [N, k] (global indices) sharded row-wise over ``axis``;
    rows >= ``n_valid`` are padding and never emitted.  Each round every
    shard all-gathers the graph, takes ``n_sample`` sampled neighbors' sampled
    neighbors as forward candidates plus a bounded sample of reverse edges
    (nominated by every shard, combined with ``pmax``), scores them against
    the all-gathered x and merges them into its rows' top-k.  The ring's
    candidates come from one shard's forest at a time, so its recall falls
    as shards grow; a neighbor of a neighbor may live on any shard.

    Resident per shard: x and the graph in full (``N * (D + k)`` words);
    transients are row-blocked by ``block_rows``.  A round scores
    ``n_sample² + n_reverse`` candidates per row; the defaults take the
    1,291,337 x 20 mouse stand-in over 4 shards from recall@90 0.58 after
    the 8-tree ring to 0.94.
    """
    from repro.neighbors._candidates import candidate_sq_dists, merge_topk

    n_dev = mesh.shape[axis]
    n_pad_total = x.shape[0]
    n_total = n_pad_total if n_valid is None else int(n_valid)
    n_loc = n_pad_total // n_dev
    s = min(n_sample, k)
    block = min(block_rows, n_loc)
    m_pad = -(-n_loc // block) * block

    def body(x_loc, i_loc, d_loc):
        rank = jax.lax.axis_index(axis)
        base = rank * n_loc
        big = jnp.asarray(jnp.finfo(x_loc.dtype).max, x_loc.dtype)
        x_full = jax.lax.all_gather(x_loc, axis, tiled=True)
        gid = base + jnp.arange(n_loc, dtype=jnp.int32)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), rank)
        pad = lambda a, v: jnp.pad(a, ((0, m_pad - n_loc),) + ((0, 0),) * (a.ndim - 1),
                                   constant_values=v)
        blk = lambda a: a.reshape(m_pad // block, block, *a.shape[1:])
        x_blk, gid_blk = blk(pad(x_loc, 0.0)), blk(pad(gid, -1))

        def one_round(it, carry):
            bi, bd = carry
            i_full = jax.lax.all_gather(bi, axis, tiled=True)      # [N, k]
            k1, k2, k3 = jax.random.split(jax.random.fold_in(key, it), 3)
            samp = jnp.take_along_axis(
                bi, jax.random.randint(k1, (n_loc, s), 0, k), axis=1)
            samp = jnp.clip(samp, 0, n_pad_total - 1)
            hop2 = jax.random.randint(k2, (n_loc, s), 0, k)
            # edge i -> samp[i, j] nominates i as a candidate of samp[i, j],
            # wherever that row lives; slot collisions just drop
            slots = jax.random.randint(k3, (n_loc, s), 0, n_reverse)
            rev = jnp.full((n_pad_total, n_reverse), -1, jnp.int32).at[
                samp, slots].set(jnp.broadcast_to(gid[:, None], (n_loc, s)))
            rev = jax.lax.dynamic_slice_in_dim(jax.lax.pmax(rev, axis), base, n_loc)

            def merge_block(args):
                qb, gb, sb, hb, rb, bib, bdb = args
                fwd = i_full[sb[:, :, None], hb[:, None, :]].reshape(-1, s * s)
                cb = jnp.concatenate([fwd, rb], axis=1)
                cd = candidate_sq_dists(x_full, cb, block_rows=block, q=qb)
                cd = jnp.where(cb == gb[:, None], big, cd)       # self edge
                return merge_topk(bib, bdb, cb, cd, k, n_total, exclude_self=False)

            mi, md = jax.lax.map(merge_block, (
                x_blk, gid_blk, blk(pad(samp, 0)), blk(pad(hop2, 0)),
                blk(pad(rev, -1)), blk(pad(bi, -1)), blk(pad(bd, big))))
            return mi.reshape(m_pad, k)[:n_loc], md.reshape(m_pad, k)[:n_loc]

        return jax.lax.fori_loop(0, n_iters, one_round, (i_loc, d_loc))

    return shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
                     out_specs=(P(axis), P(axis)), check_vma=False)(x, idx, d2)
