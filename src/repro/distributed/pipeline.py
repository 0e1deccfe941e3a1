"""GPipe-style pipeline parallelism over a "pipe" mesh axis (shard_map).

The stage function is replicated code; stage-local weights are sharded over
the pipe axis (leading dim = stage). Microbatches stream through stages via
``collective_permute``; the classic 1F1B-ish schedule is flattened into
n_micro + n_stages - 1 ticks of a ``lax.scan``, so the whole pipeline is a
single SPMD program (bubble fraction = (S-1)/(M+S-1), reported by
``pipeline_bubble``). Used as an optional wrapper for very deep stacks
where FSDP+TP alone would not fit; unit-tested on forced host devices
(tests/test_pipeline.py) against the sequential reference.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def pipeline_bubble(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipelined(stage_fn: Callable, mesh, *, axis: str = "pipe", n_micro: int):
    """Wrap ``stage_fn(stage_params, x) -> x`` into a pipelined apply.

    Returns ``apply(stacked_params, batch)`` where ``stacked_params`` has a
    leading [n_stages, ...] axis (sharded over ``axis``) and ``batch`` is
    [n_micro * micro_b, ...] (replicated across the pipe axis; stage 0
    feeds, the last stage's outputs are collected and re-assembled).
    """
    n_stages = mesh.shape[axis]

    def body(params_local, batch):
        # params_local: [1, ...] this stage's weights; batch replicated
        sp = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        micro = batch.reshape(n_micro, -1, *batch.shape[1:])
        mb_shape = micro.shape[1:]
        ticks = n_micro + n_stages - 1
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if any); others use the buffer
            inject = jnp.where(t < n_micro, jnp.minimum(t, n_micro - 1), 0)
            x_in = jnp.where(stage == 0, micro[inject], buf)
            y = stage_fn(sp, x_in)
            # only compute validity: stage s works on micro (t - s)
            mid = t - stage
            valid = (mid >= 0) & (mid < n_micro)
            y = jnp.where(valid, y, buf)
            # last stage records its finished microbatch
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            record = (stage == n_stages - 1) & valid
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(record, y, outs[out_idx]), out_idx, 0)
            # stream activations forward along the ring
            buf = jax.lax.ppermute(y, axis, fwd_perm)
            return (buf, outs), None

        buf0 = jnp.zeros(mb_shape, batch.dtype)
        outs0 = jnp.zeros((n_micro, *mb_shape), batch.dtype)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
        # every stage returns `outs`, but only the last stage's is real:
        # broadcast it back with a psum of the masked tensor
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs.reshape(-1, *batch.shape[1:])

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
