"""Sparse input-similarity construction (paper §2.2.1).

Produces the symmetric p_ij = (p_{j|i} + p_{i|j}) / 2N over the union of the
directed KNN neighborhoods:

* ``symmetrize_ell`` — host-side (numpy) construction of a regular ELL
  [N, W] matrix, W = max symmetric row degree (<= K + max indegree).  Runs
  once before gradient descent, so host preprocessing is fine; the GD loop
  then runs paper Algorithm 2 over its rows (``core/attractive.py``).

``degree_buckets`` cuts the ELL's rows into a few buckets by degree, so the
attractive loop gathers only up to each row's own bucket's width instead of
the graph's largest degree W.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np


def symmetrize_ell(cols, cond_p):
    """Host-side symmetrization to a regular ELL layout.

    cols   : [N, K] int neighbor indices
    cond_p : [N, K] conditional p_{j|i}
    Returns (sym_cols [N, W] int32, sym_vals [N, W] float) where padding
    entries have col = row-index and val = 0; sum(sym_vals) == 1.
    """
    cols = np.asarray(cols)
    cond_p = np.asarray(cond_p)
    n, k = cols.shape
    rows = np.repeat(np.arange(n, dtype=np.int64), k)
    cs = cols.reshape(-1).astype(np.int64)
    vs = cond_p.reshape(-1).astype(np.float64)
    # both orientations; duplicates (mutual neighbors) sum to p_{j|i}+p_{i|j}
    r2 = np.concatenate([rows, cs])
    c2 = np.concatenate([cs, rows])
    v2 = np.concatenate([vs, vs])
    key = r2 * n + c2
    order = np.argsort(key, kind="stable")
    key, r2, c2, v2 = key[order], r2[order], c2[order], v2[order]
    new_run = np.empty(key.shape, bool)
    new_run[0] = True
    new_run[1:] = key[1:] != key[:-1]
    run_id = np.cumsum(new_run) - 1
    n_runs = run_id[-1] + 1
    val = np.zeros(n_runs, np.float64)
    np.add.at(val, run_id, v2)
    row = r2[new_run]
    col = c2[new_run]
    # rank within row
    row_start = np.zeros(n_runs, np.int64)
    first_of_row = np.empty(n_runs, bool)
    first_of_row[0] = True
    first_of_row[1:] = row[1:] != row[:-1]
    row_first_idx = np.maximum.accumulate(np.where(first_of_row, np.arange(n_runs), 0))
    rank = np.arange(n_runs) - row_first_idx
    w = int(rank.max()) + 1 if n_runs else 1
    sym_cols = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, w))
    sym_vals = np.zeros((n, w), np.float64)
    sym_cols[row, rank] = col.astype(np.int32)
    sym_vals[row, rank] = val / (2.0 * n)
    return sym_cols, sym_vals


def symmetrize_ell_chunked(cols, cond_p, chunk_size: int):
    """Streaming-CSR symmetrization: :func:`symmetrize_ell` in row chunks.

    Bit-identical output to ``symmetrize_ell`` (same [N, W] layout, same
    values — parity-tested), but the 2NK-edge concatenate-and-argsort of
    the reference never materializes.  Memory model:

    * one-shot transpose of the directed graph (incoming edges grouped by
      destination) via a stable integer sort of the NK column indices —
      O(N·K) arrays, the same order as the KNN output itself;
    * per chunk of rows, the reference's key-sort/dedup/rank merge runs
      over that chunk's outgoing + incoming edges only — O(chunk·K)
      transients;
    * the accumulated merged triples total the symmetric nnz (<= 2NK),
      i.e. output-order memory, filled into the ELL planes at the end
      once the global width W is known.

    Nothing here is ever O(N²) or holds more than O(chunk·K) beyond the
    O(N·K) inputs/outputs.
    """
    chunk = int(chunk_size)
    if chunk <= 0:
        raise ValueError(f"chunk_size={chunk_size} must be >= 1")
    cols = np.asarray(cols)
    cond_p = np.asarray(cond_p)
    n, k = cols.shape

    # transpose: incoming edges of row j live at t_order[t_ptr[j]:t_ptr[j+1]]
    flat_cols = cols.reshape(-1).astype(np.int64)
    indeg = np.bincount(flat_cols, minlength=n)
    t_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(indeg, out=t_ptr[1:])
    t_order = np.argsort(flat_cols, kind="stable")
    t_src = (t_order // k).astype(np.int64)          # source row per in-edge
    t_val = cond_p.reshape(-1).astype(np.float64)[t_order]

    parts = []          # (rows, ranks, cols, vals) per chunk — sym nnz total
    w = 1
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        r2 = np.concatenate([
            np.repeat(np.arange(s, e, dtype=np.int64), k),       # outgoing
            np.repeat(np.arange(s, e, dtype=np.int64),           # incoming
                      indeg[s:e]),
        ])
        c2 = np.concatenate([
            cols[s:e].reshape(-1).astype(np.int64),
            t_src[t_ptr[s]:t_ptr[e]],
        ])
        v2 = np.concatenate([
            cond_p[s:e].reshape(-1).astype(np.float64),
            t_val[t_ptr[s]:t_ptr[e]],
        ])
        key = (r2 - s) * n + c2
        order = np.argsort(key, kind="stable")
        key, r2, c2, v2 = key[order], r2[order], c2[order], v2[order]
        new_run = np.empty(key.shape, bool)
        new_run[0] = True
        new_run[1:] = key[1:] != key[:-1]
        run_id = np.cumsum(new_run) - 1
        n_runs = run_id[-1] + 1
        val = np.zeros(n_runs, np.float64)
        np.add.at(val, run_id, v2)
        row = r2[new_run]
        col = c2[new_run]
        first_of_row = np.empty(n_runs, bool)
        first_of_row[0] = True
        first_of_row[1:] = row[1:] != row[:-1]
        row_first_idx = np.maximum.accumulate(
            np.where(first_of_row, np.arange(n_runs), 0))
        rank = np.arange(n_runs) - row_first_idx
        w = max(w, int(rank.max()) + 1 if n_runs else 1)
        parts.append((row.astype(np.int64), rank.astype(np.int32),
                      col.astype(np.int32), val))

    sym_cols = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, w))
    sym_vals = np.zeros((n, w), np.float64)
    for row, rank, col, val in parts:
        sym_cols[row, rank] = col
        sym_vals[row, rank] = val / (2.0 * n)
    return sym_cols, sym_vals


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(x: int, m: int) -> int:
    return _ceil_div(x, m) * m


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DegreeBuckets:
    """The ELL's rows cut by degree (:func:`degree_buckets`).

    Bucket b holds ``rows[b]`` [T, R] (the point of each row, T turns of R
    rows), ``cols[b]`` / ``vals[b]`` [T, R, w] (those ELL rows truncated to
    the bucket's width w).  ``inv`` [N] is each point's place in the
    buckets' rows laid end to end.  Rows past a bucket's last point are
    padding: point 0, col 0, val 0.
    """
    rows: tuple
    cols: tuple
    vals: tuple
    inv: jax.Array

    @property
    def slots(self) -> int:
        """Columns the attractive loop gathers per coordinate."""
        return sum(math.prod(c.shape) for c in self.cols)


# a bucket's width over the next's: about 8 buckets on MNIST's K = 91 graph
LADDER_RATIO = 1.25


def degree_buckets(cols, vals, block: int = 512,
                   max_rows: int | None = None) -> DegreeBuckets:
    """Cut the [N, W] ELL's rows into buckets on a ladder of widths.

    A row's degree is one past its last real entry (padding, ``col ==
    row``, follows the real entries).  The ladder descends from W by
    ``LADDER_RATIO``, each width rounded up to a multiple of 8 (and at least 8
    below the last), down to the smallest degree; each row goes to the
    narrowest width that holds it, so uniform degrees give one bucket, the
    ELL itself.  Rows are sorted by degree, descending (ties in point
    order), and each keeps its entries in their order.

    A bucket of width w runs in turns of at most ``block * W // w`` rows
    (about the indices of one ``block``-row turn over the whole ELL) and at
    most ``max_rows``; its rows are split evenly over as few turns as that
    allows, each turn's rows rounded up to a multiple of 8.
    """
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    n, w_max = cols.shape
    real = cols != np.arange(n, dtype=cols.dtype)[:, None]
    degree = np.where(real.any(axis=1),
                      w_max - np.argmax(real[:, ::-1], axis=1), 0)
    widths = [w_max]
    low = max(int(degree.min()), 1)
    while widths[-1] > 8:
        w = min(_round_up(math.ceil(widths[-1] / LADDER_RATIO), 8),
                widths[-1] - 8)
        if w < low:
            break
        widths.append(w)
    order = np.argsort(-degree, kind="stable")
    # bucket b: widths[b + 1] < degree <= widths[b]
    cut = np.searchsorted(-degree[order], -np.asarray(widths[1:]), side="left")
    bounds = [0, *cut.tolist(), n]
    out_rows, out_cols, out_vals = [], [], []
    inv = np.empty(n, np.int32)
    offset = 0
    for w, lo, hi in zip(widths, bounds[:-1], bounds[1:]):
        if hi == lo:
            continue
        cap = block * w_max // w
        cap = cap // 8 * 8 or cap
        if max_rows is not None:
            cap = min(cap, max_rows)
        cap = max(cap, 1)
        turns = _ceil_div(hi - lo, cap)
        r = min(_round_up(_ceil_div(hi - lo, turns), 8), cap)
        pts = order[lo:hi]
        rows = np.zeros(turns * r, np.int32)
        rows[:hi - lo] = pts
        bc = np.zeros((turns * r, w), cols.dtype)
        bv = np.zeros((turns * r, w), vals.dtype)
        bc[:hi - lo] = cols[pts, :w]
        bv[:hi - lo] = vals[pts, :w]
        inv[pts] = offset + np.arange(hi - lo, dtype=np.int32)
        offset += turns * r
        out_rows.append(rows.reshape(turns, r))
        out_cols.append(bc.reshape(turns, r, w))
        out_vals.append(bv.reshape(turns, r, w))
    return DegreeBuckets(rows=tuple(out_rows), cols=tuple(out_cols),
                         vals=tuple(out_vals), inv=inv)


def dense_p_matrix(cols, cond_p):
    """Dense symmetric P (for the exact oracle / small-N tests)."""
    cols = np.asarray(cols)
    cond_p = np.asarray(cond_p)
    n, k = cols.shape
    p = np.zeros((n, n), np.float64)
    rows = np.repeat(np.arange(n), k)
    p[rows, cols.reshape(-1)] = cond_p.reshape(-1)
    p = (p + p.T) / (2.0 * n)
    np.fill_diagonal(p, 0.0)
    return p
