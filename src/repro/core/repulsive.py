"""Repulsive force via Barnes-Hut traversal (paper §3.5), TPU formulation.

The CPU implementation does a recursive DFS per point, relying on the
Morton-ordered node layout for cache locality.  The TPU equivalent is a
*rope-linearized* traversal: nodes live in DFS pre-order arrays and each point
walks ``ptr = open ? ptr+1 : skip[ptr]`` inside a ``lax.while_loop``.  vmapping
the loop over points gives lockstep masked execution — the accelerator
analogue of the paper's "structured data locality" DFS (all lanes read from
the same contiguous node table, near its front most of the time, which is
exactly the locality argument of §3.5 restated for VMEM/HBM).

Each node's record is one row of that table (:func:`node_table`), so a turn
is one gather of a row per lane: on a TPU a gather costs about the same per
index whatever its width, so one row costs about what one field would.

Self-interaction is excluded *exactly*: when the current node's point range
contains the query point (known from its position in Morton-sorted order) the
summary is used with the query point subtracted.

Opening criterion (paper eq. 9, van-der-Maaten form): use the summary iff
``side_cell / dist < theta`` — i.e. *open* iff ``side^2 >= theta^2 * d^2``.
Leaves (terminal runs: singletons or max-depth duplicate-code runs) always
contribute their (self-excluded) summary; the Student-t kernel is smooth at
d = 0 so coincident points need no special casing.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.quadtree import LinearQuadtree
from repro.core.summarize import TreeSummary


class RepulsionResult(NamedTuple):
    force: jax.Array       # [N, 2] unnormalized: sum_j (1+d^2)^-2 (y_i - y_j)
    z_per_point: jax.Array  # [N] sum_j (1+d^2)^-1
    steps: jax.Array       # [N] traversal lengths (perf diagnostic)


def node_table(tree: LinearQuadtree, summary: TreeSummary) -> jax.Array:
    """One float32 row per node: everything a walk turn reads, in one gather.

    Columns ``start, end, skip`` (int32, bit-cast so indices stay exact at any
    N), ``sum_y[0], sum_y[1], count`` and ``side2``: the squared cell side,
    or -inf for a leaf, whose opening test then fails at every distance.
    The eighth column is padding.
    """
    as_f32 = lambda a: jax.lax.bitcast_convert_type(a, jnp.float32)
    side2 = jnp.where(tree.is_leaf, -jnp.inf, summary.side * summary.side)
    return jnp.stack(
        [as_f32(tree.start), as_f32(tree.end), as_f32(tree.skip),
         summary.sum_y[:, 0], summary.sum_y[:, 1], summary.count, side2,
         jnp.zeros_like(side2)], axis=1).astype(jnp.float32)


def walk(table: jax.Array, n_nodes: jax.Array, theta: jax.Array | float,
         pos: jax.Array, y_query: jax.Array) -> RepulsionResult:
    """Barnes-Hut repulsion on query points at Morton-sorted positions ``pos``
    (their own point is excluded), one ``table`` row read per turn."""
    dtype = y_query.dtype
    theta2 = jnp.asarray(theta, dtype) ** 2
    cap = table.shape[0]
    as_i32 = lambda a: jax.lax.bitcast_convert_type(a, jnp.int32)

    def traverse(p, yp):
        def cond(state):
            ptr, _, _, _ = state
            return ptr < n_nodes

        def body(state):
            ptr, force, z, steps = state
            row = table[jnp.minimum(ptr, cap - 1)]
            s, e, skip = as_i32(row[0]), as_i32(row[1]), as_i32(row[2])
            inside = (s <= p) & (p < e)
            cnt_eff = row[5] - jnp.where(inside, jnp.asarray(1.0, dtype), 0.0)
            sum_eff = row[3:5] - jnp.where(inside, yp, jnp.zeros_like(yp))
            com = sum_eff / jnp.maximum(cnt_eff, 1.0)
            diff = yp - com
            d2 = jnp.sum(diff * diff)
            open_ = row[6] >= theta2 * d2               # never for a leaf
            w = jnp.where(open_, 0.0, cnt_eff)          # contribute iff accepted
            q = 1.0 / (1.0 + d2)
            z = z + w * q
            force = force + (w * q * q) * diff
            ptr = jnp.where(open_, ptr + 1, skip)
            return ptr, force, z, steps + 1

        init = (jnp.int32(0), jnp.zeros((2,), dtype), jnp.asarray(0.0, dtype), jnp.int32(0))
        _, force, z, steps = jax.lax.while_loop(cond, body, init)
        return force, z, steps

    force, z, steps = jax.vmap(traverse)(pos, y_query)
    return RepulsionResult(force=force, z_per_point=z, steps=steps)


@functools.partial(jax.jit, static_argnames=())
def bh_repulsion_sorted(
    y_sorted: jax.Array,
    tree: LinearQuadtree,
    summary: TreeSummary,
    theta: jax.Array | float,
) -> RepulsionResult:
    """Barnes-Hut repulsion for points in Morton-sorted order."""
    n = y_sorted.shape[0]
    return walk(node_table(tree, summary), tree.n_nodes, theta,
                jnp.arange(n, dtype=jnp.int32), y_sorted)


def bh_repulsion(y: jax.Array, codes: jax.Array, tree_builder, theta):
    """Convenience wrapper operating in original point order (see tsne.py)."""
    raise NotImplementedError("use repro.core.tsne.gradient_step")
