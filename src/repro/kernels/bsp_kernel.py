"""Pallas TPU kernel for the fused perplexity binary search (paper §3.2).

The XLA formulation in ``core/bsp.py`` runs one ``fori_loop`` over the whole
[N, K] array: every bisection step is a separate pass over HBM (exp + two
reductions + the bounds update), so 64 iterations read the distance matrix
64 times.  Roofline says the step is memory-bound (~6 flops/byte of d2
traffic per iteration) — exactly the shape Pallas fixes: tile the point
axis, keep a [TILE, K] block of d2 resident in VMEM, and run the *entire*
per-row bisection (all iterations + the final normalization) in one grid
step.  d2 is read from HBM once instead of ``iters`` times.

Per grid step: d2 tile [T, K] in, scalar params (log-perplexity, tolerance)
broadcast as a (1, 4) block, outputs cond_p [T, K] and beta [T].  The math
matches ``core/bsp.binary_search_perplexity`` line for line (same
conditioning guards: row-min shift, row-mean scale) so the parity tests in
``tests/test_kernels.py`` can require allclose on both outputs.

Zero padding rows are harmless: d2 = 0 gives a constant entropy row whose
bisection diverges to a large-but-finite beta, and the wrapper slices the
padding off before returning.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

TILE = 256


def _bsp_kernel(d2_ref, par_ref, cond_ref, beta_ref, *, iters: int):
    d2 = d2_ref[...]                     # [T, K]
    dtype = d2.dtype
    log_u = par_ref[0, 0]
    tol = par_ref[0, 1]

    # conditioning guards, identical to the XLA reference: shift by the row
    # min (p_{j|i} is shift-invariant; exp(0)=1 keeps the nearest neighbor
    # alive at large beta) and scale by the row mean so beta ~ O(1).
    d2s = d2 - jnp.min(d2, axis=1, keepdims=True)
    scale = jnp.maximum(jnp.mean(d2s, axis=1, keepdims=True),
                        jnp.asarray(1e-30, dtype))
    d2n = d2s / scale

    def entropy(beta):
        p = jnp.exp(-d2n * beta)
        sum_p = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
        h = jnp.log(sum_p) + beta * jnp.sum(d2n * p, axis=1, keepdims=True) / sum_p
        return h, p, sum_p

    def body(_, state):
        beta, bmin, bmax = state
        h, _, _ = entropy(beta)
        too_high = h > log_u + tol       # entropy too high -> sharpen kernel
        bmin = jnp.where(too_high, beta, bmin)
        bmax = jnp.where(too_high, bmax, beta)
        up = jnp.where(jnp.isinf(bmax), beta * 2.0, 0.5 * (beta + bmax))
        down = jnp.where(bmin <= 0.0, beta * 0.5, 0.5 * (beta + bmin))
        beta = jnp.where(too_high, up, down)
        return beta, bmin, bmax

    t = d2.shape[0]
    state = (jnp.ones((t, 1), dtype), jnp.zeros((t, 1), dtype),
             jnp.full((t, 1), jnp.inf, dtype))
    beta, _, _ = jax.lax.fori_loop(0, iters, body, state)
    _, p, sum_p = entropy(beta)
    cond_ref[...] = p / sum_p
    beta_ref[...] = beta / scale


@functools.partial(jax.jit, static_argnames=("iters", "interpret"))
def binary_search_perplexity_pallas(
    d2: jax.Array,
    perplexity,
    iters: int = 64,
    tol: float = 1e-5,
    interpret: bool | None = None,
):
    """Fused per-tile bisection; same contract as the ``core/bsp`` reference.

    d2 : [N, K] squared neighbor distances (self excluded)
    Returns (cond_p [N, K], beta [N]).
    """
    n, k = d2.shape
    dtype = d2.dtype
    n_pad = (n + TILE - 1) // TILE * TILE
    d2p = jnp.pad(d2, ((0, n_pad - n), (0, 0)))
    par = jnp.stack([
        jnp.log(jnp.asarray(perplexity, dtype)),
        jnp.asarray(tol, dtype),
        jnp.zeros((), dtype), jnp.zeros((), dtype),
    ])[None, :]
    cond_p, beta = pl.pallas_call(
        functools.partial(_bsp_kernel, iters=iters),
        grid=(n_pad // TILE,),
        in_specs=[
            pl.BlockSpec((TILE, k), lambda i: (i, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE, k), lambda i: (i, 0)),
            pl.BlockSpec((TILE, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, k), dtype),
            jax.ShapeDtypeStruct((n_pad, 1), dtype),
        ],
        interpret=interpret_mode(interpret),
    )(d2p, par)
    return cond_p[:n], beta[:n, 0]
