"""Sharded neighbor backend: the distributed KNN rings behind the registry.

Before this backend existed the distributed path (``core/distributed.py``)
predated the ``NeighborBackend`` registry and always rang *exact*
brute-force KNN — O(N²/S · D) per shard, the reason nothing had run past
50k points.  ``ShardedNeighbors`` puts both rings behind the standard
``neighbors(x, k)`` contract:

* ``mode="approx"`` (default) — per-shard rp_forest + candidate ring
  (:func:`repro.core.distributed.ring_knn_approx`): each shard routes the
  visiting query block down its resident forest and merges leaf candidates
  into the traveling global top-k; then ``refine_iters`` neighbor-of-
  neighbor rounds across shards (:func:`repro.core.distributed.refine_knn`)
  find what one shard's forest at a time could not.  Transients are
  bounded by ``block_rows``, not N.
* ``mode="exact"`` — the original exact ring
  (:func:`repro.core.distributed.ring_knn`), kept as the recall oracle.

``shards=None`` uses every visible JAX device: the four chips of a
TPU v5e 2x2 host, say.  On one device the ring degenerates to a single
local forest pass, still row-blocked, so the memory bound holds there too.
Inputs of any N are handled by zero-padding to a shard multiple; pad rows
are masked out of every merge.
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.neighbors.base import register_neighbor_backend, validate_k

MODES = ("approx", "exact")


@dataclasses.dataclass(frozen=True)
class ShardedNeighbors:
    """Distributed KNN over a 1-D device mesh (see module docstring).

    shards     : device count (None = all visible devices, clamped so each
                 shard keeps > k points)
    mode       : "approx" (rp_forest candidate ring) | "exact" (oracle ring)
    n_trees    : forest width per shard (approx mode)
    leaf_size  : leaf occupancy floor per tree (approx mode); the candidate
                 set per hop is n_trees * max(leaf_size, k+1)-ish columns
    refine_iters : NN-descent rounds after the ring (approx mode).  On the
                 1,291,337 x 20 mouse stand-in over 4 shards the ring alone
                 gives recall@90 0.58; 5 rounds bring it to 0.94
    block_rows : rows per routing/scoring/merge slice — the memory knob
    """

    name: ClassVar[str] = "sharded"
    shards: int | None = None
    mode: str = "approx"
    n_trees: int = 8
    leaf_size: int = 64
    refine_iters: int = 5
    block_rows: int = 4096
    seed: int = 0
    axis: str = "knn"

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(
                f"unknown sharded mode {self.mode!r} (known: {', '.join(MODES)})"
            )

    def resolve_shards(self, n: int, k: int) -> int:
        """Devices actually used: requested (or all), bounded by what keeps
        every shard larger than k+1 points."""
        avail = len(jax.devices())
        s = avail if self.shards in (None, 0) else int(self.shards)
        if s > avail:
            raise ValueError(
                f"shards={s} but only {avail} {jax.default_backend()} "
                "device(s) are visible — lower shards or run on a host "
                f"with {s} devices"
            )
        return max(1, min(s, n // (k + 2)))

    def neighbors(self, x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
        from repro.core.distributed import refine_knn, ring_knn, ring_knn_approx

        n = int(x.shape[0])
        validate_k(n, k)
        s = self.resolve_shards(n, k)
        mesh = Mesh(np.asarray(jax.devices()[:s]), (self.axis,))
        pad = (-n) % s
        xp = jnp.pad(jnp.asarray(x), ((0, pad), (0, 0)))
        if self.mode == "exact":
            idx, d2 = ring_knn(mesh, xp, k, self.axis, n_valid=n)
        else:
            idx, d2 = ring_knn_approx(
                mesh, xp, k, self.axis, n_valid=n,
                n_trees=self.n_trees, leaf_size=self.leaf_size,
                block_rows=self.block_rows, seed=self.seed,
            )
            if self.refine_iters > 0:
                # offset the seed: the rounds must not replay the forest's keys
                idx, d2 = refine_knn(
                    mesh, xp, idx, d2, k, self.axis, n_valid=n,
                    n_iters=self.refine_iters, block_rows=self.block_rows,
                    seed=self.seed + 1,
                )
        return idx[:n], d2[:n]


register_neighbor_backend("sharded", ShardedNeighbors)
