"""Shared candidate-set machinery for the approximate neighbor backends.

Both ``rp_forest`` and ``nn_descent`` reduce to the same inner loop: gather
a fixed-width candidate set per point, score it with exact squared
distances, and fold it into a running top-k while dropping duplicate /
invalid columns.  Everything here is shape-static and jittable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _big(dtype) -> jax.Array:
    return jnp.asarray(jnp.finfo(dtype).max, dtype)


def merge_topk(
    best_i: jax.Array,
    best_d: jax.Array,
    cand_i: jax.Array,
    cand_d: jax.Array,
    k: int,
    n: int,
    exclude_self: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fold candidate columns into a running top-k, row by row.

    ``best_* [N, K0]`` and ``cand_* [N, C]`` are row-aligned; candidates with
    index outside ``[0, n)`` or equal to the row index are dropped, and
    duplicate indices keep a single copy.  Returns ``(idx [N, k], d2 [N, k])``
    sorted ascending by distance.

    ``exclude_self=False`` skips the row-index drop — the query path, where
    rows are *new* points and candidate index ``i`` in row ``i`` is a
    coincidence, not a self-edge.
    """
    ci = jnp.concatenate([best_i, cand_i], axis=1).astype(jnp.int32)
    cd = jnp.concatenate([best_d, cand_d], axis=1)
    big = _big(cd.dtype)
    invalid = (ci < 0) | (ci >= n)
    if exclude_self:
        rows = jnp.arange(ci.shape[0], dtype=jnp.int32)[:, None]
        invalid = invalid | (ci == rows)
    cd = jnp.where(invalid, big, cd)
    # sort columns by index so duplicates become adjacent, then mask repeats
    order = jnp.argsort(ci, axis=1)
    ci = jnp.take_along_axis(ci, order, axis=1)
    cd = jnp.take_along_axis(cd, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros_like(ci[:, :1], bool), ci[:, 1:] == ci[:, :-1]], axis=1
    )
    cd = jnp.where(dup, big, cd)
    neg_top, argtop = lax.top_k(-cd, k)
    return jnp.take_along_axis(ci, argtop, axis=1), -neg_top


def candidate_sq_dists(
    x: jax.Array, cand: jax.Array, block_rows: int = 512,
    q: jax.Array | None = None,
) -> jax.Array:
    """``d2[i, j] = ||row_i - x[cand[i, j]]||²``, computed in row blocks.

    Rows come from ``q`` when given (out-of-sample queries scored against the
    reference set ``x``), else from ``x`` itself (the self-KNN build path).
    ``cand`` entries are clipped to ``[0, n)`` for the gather; callers mask
    out-of-range columns themselves (merge_topk does).  Row blocking bounds
    the ``[B, C, D]`` gather transient instead of materializing ``[N, C, D]``.
    """
    n, _ = x.shape
    rows = x if q is None else q
    m = rows.shape[0]
    sqn = jnp.sum(x * x, axis=1)
    cand = jnp.clip(cand, 0, n - 1).astype(jnp.int32)

    pad = (-m) % block_rows
    xp = jnp.pad(rows, ((0, pad), (0, 0)))
    candp = jnp.pad(cand, ((0, pad), (0, 0)))
    n_blocks = xp.shape[0] // block_rows

    def one_block(b):
        xb = lax.dynamic_slice_in_dim(xp, b * block_rows, block_rows)
        cb = lax.dynamic_slice_in_dim(candp, b * block_rows, block_rows)
        xc = x[cb]                                   # [B, C, D]
        dots = jnp.einsum("bd,bcd->bc", xb, xc, precision=lax.Precision.HIGHEST)
        d2 = jnp.sum(xb * xb, axis=1)[:, None] + sqn[cb] - 2.0 * dots
        return jnp.maximum(d2, 0.0)

    d2 = lax.map(one_block, jnp.arange(n_blocks))
    return d2.reshape(-1, cand.shape[1])[:m]


@functools.partial(jax.jit, static_argnames=("k", "block_rows"))
def seed_graph(
    x: jax.Array, k: int, key: jax.Array, block_rows: int = 512
) -> tuple[jax.Array, jax.Array]:
    """A valid (if poor) starting graph: k distinct non-self neighbors per row.

    Shared random offsets keep every slot a real point, so backends that
    merge into this state can never emit an invalid index even when their
    candidate generation comes up short.
    """
    n = x.shape[0]
    offsets = 1 + jax.random.choice(
        key, jnp.arange(n - 1, dtype=jnp.int32), (k,), replace=False
    )
    idx = (jnp.arange(n, dtype=jnp.int32)[:, None] + offsets[None, :]) % n
    return idx, candidate_sq_dists(x, idx, block_rows=block_rows)
