"""Pallas TPU kernel for the attractive-force inner loop (paper Algorithm 2).

The paper hand-vectorizes this loop with AVX-512 (gathers + FMA) and software
prefetch.  TPU adaptation: the pseudo-random y[cols] gather is issued as one
XLA gather *outside* the kernel (TPU's gather path; Pallas' double-buffered
grid pipeline plays the role of software prefetch), and this kernel fuses the
remaining ~10 FLOP/neighbor epilogue over VMEM row tiles:

    pq   = val / (1 + ||y_i - y_j||^2)
    F_i += pq * (y_i - y_j)            and   kl_i += val * log1p(d^2)

Inputs per grid step: y tile [T, 2], the gathered neighbor coordinates as
two lane-dense planes x [T, W] and y [T, W] (a [T, W, 2] block would pad
its size-2 minor axis to 128 lanes and overflow VMEM), values [T, W];
outputs force [T, 2] and per-row KL partials [T, 1].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

TILE = 256


def _attractive_kernel(y_ref, nbx_ref, nby_ref, val_ref, f_ref, kl_ref):
    y = y_ref[...]                       # [T, 2]
    val = val_ref[...]                   # [T, W]
    dx = y[:, 0:1] - nbx_ref[...]        # [T, W]
    dy = y[:, 1:2] - nby_ref[...]
    d2 = dx * dx + dy * dy
    pq = val / (1.0 + d2)
    f_ref[:, 0:1] = jnp.sum(pq * dx, axis=1, keepdims=True)
    f_ref[:, 1:2] = jnp.sum(pq * dy, axis=1, keepdims=True)
    kl_ref[...] = jnp.sum(val * jnp.log1p(d2), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def attractive_forces_ell_pallas(y, cols, vals, interpret: bool | None = None):
    n, w = cols.shape
    n_pad = (n + TILE - 1) // TILE * TILE
    pad = n_pad - n
    yp = jnp.pad(y, ((0, pad), (0, 0)))
    # XLA gathers (outside the kernel), one [N, W] plane per coordinate
    nbx = jnp.pad(y[cols, 0], ((0, pad), (0, 0)))
    nby = jnp.pad(y[cols, 1], ((0, pad), (0, 0)))
    valp = jnp.pad(vals, ((0, pad), (0, 0)))
    force, kl = pl.pallas_call(
        _attractive_kernel,
        grid=(n_pad // TILE,),
        in_specs=[
            pl.BlockSpec((TILE, 2), lambda i: (i, 0)),
            pl.BlockSpec((TILE, w), lambda i: (i, 0)),
            pl.BlockSpec((TILE, w), lambda i: (i, 0)),
            pl.BlockSpec((TILE, w), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE, 2), lambda i: (i, 0)),
            pl.BlockSpec((TILE, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 2), y.dtype),
            jax.ShapeDtypeStruct((n_pad, 1), y.dtype),
        ],
        interpret=interpret_mode(interpret),
    )(yp, nbx, nby, valp)
    return force[:n], jnp.sum(kl[:n, 0])
