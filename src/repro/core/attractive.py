"""Attractive force (paper §3.6, Algorithm 2), TPU formulation.

The paper hand-vectorizes the CSR inner loop with AVX-512 (gather + FMA) and
adds software prefetch for the pseudo-random y_j reads.  On TPU:

* KNN yields exactly K = floor(3u) neighbors per point, so the sparse P is a
  *regular* [N, W] ELL layout — no ragged CSR indirection at all;
* the y[cols] gather is one fused XLA gather (TPU has a hardware gather path;
  Pallas double-buffering plays the role of software prefetch);
* the 10-FLOP epilogue is `kernels/attractive_kernel.py` when enabled.

On the chip the cost of the ELL forms is one scalar gather issue per
column (two per entry, x and y), whatever the bytes: the layer runs far
below the HBM roofline, so what it costs is the count of gathered columns.
``symmetrize_ell`` pads every row to the graph's largest degree W, which is
three times the mean on MNIST.

``attractive_forces_bucketed`` is the path every gradient backend runs: a
loop over the ELL's degree buckets (``similarity.degree_buckets``), each
row gathering only up to its bucket's width.  A loop bounds each gather's
size: the v5e compiler unrolls a single very large gather into code that
grows with it.  The other forms are its oracles:

``attractive_forces_ell_blocked`` — a loop over 512-row blocks of the whole
                                    width W; the bucketed loop's forces to
                                    the bit.
``attractive_forces_ell``         — Algorithm 2 verbatim, one gather over
                                    the whole ELL; the reference of the
                                    Pallas kernel (``kernels/ops.py``).

Each also returns sum_ij p_ij * log(1 + d_ij^2), the attractive half of the
KL-divergence estimate.  ``attractive_forces_frozen`` is ``transform``'s
term against fixed neighbours.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def attractive_forces_ell(y: jax.Array, cols: jax.Array, vals: jax.Array):
    """Algorithm 2: per-row gather + FMA over the symmetric ELL matrix.

    y    : [N, 2]      embedding points
    cols : [N, W] int  neighbor indices (padding: col = row index)
    vals : [N, W]      symmetric p_ij (already / 2N; padding: 0)

    Returns (force [N,2], kl_attr scalar).
    """
    yj = y[cols]                                   # [N, W, 2] one big gather
    diff = y[:, None, :] - yj
    d2 = jnp.sum(diff * diff, axis=-1)
    pq = vals / (1.0 + d2)                         # p_ij * (1+d^2)^-1
    force = jnp.sum(pq[..., None] * diff, axis=1)  # [N, 2]
    kl_attr = jnp.sum(vals * jnp.log1p(d2))
    return force, kl_attr


def _block_terms(yx, yy, x0, y0, cb, vb, width: int | None = None):
    """One row block of Algorithm 2: rows at (x0, y0) [R], their columns
    ``cb`` [R, w] with values ``vb``; the rows' forces and their KL part.

    With ``width`` (> w) each row's force terms are summed as a row of that
    width whose entries past w are zeros: the padding a truncated row left
    out, put back in the sum and not in the gather.
    """
    gx = yx[cb]
    gy = yy[cb]
    dx = x0[:, None] - gx
    dy = y0[:, None] - gy
    d2 = dx * dx + dy * dy
    pq = vb / (1.0 + d2)
    tx, ty = pq * dx, pq * dy
    if width is not None and width > cb.shape[1]:
        pad = ((0, 0), (0, width - cb.shape[1]))
        tx, ty = jnp.pad(tx, pad), jnp.pad(ty, pad)
    return jnp.sum(tx, 1), jnp.sum(ty, 1), jnp.sum(vb * jnp.log1p(d2))


def attractive_forces_ell_blocked(y: jax.Array, cols: jax.Array, vals: jax.Array,
                                  block: int = 512):
    """Algorithm 2 as a loop over ``block``-row blocks of the [N, W] ELL.

    Each turn gathers ``block * W`` columns, so the live transients are
    bounded by the block and not by N.  Every row gathers the whole width
    W, padding included: the oracle of :func:`attractive_forces_bucketed`.
    """
    n, w = cols.shape
    pad = (-n) % block
    cols_p = jnp.pad(cols, ((0, pad), (0, 0)))
    vals_p = jnp.pad(vals, ((0, pad), (0, 0)))
    yx, yy = y[:, 0], y[:, 1]
    x0_p = jnp.pad(yx, (0, pad))
    y0_p = jnp.pad(yy, (0, pad))
    nb = (n + pad) // block

    def one(args):
        cb, vb, x0, y0 = args
        return _block_terms(yx, yy, x0, y0, cb, vb)

    shape = lambda a: a.reshape(nb, block, *a.shape[1:])
    fx, fy, kl = jax.lax.map(one, (shape(cols_p), shape(vals_p), shape(x0_p), shape(y0_p)))
    force = jnp.stack([fx.reshape(-1)[:n], fy.reshape(-1)[:n]], axis=1)
    return force, jnp.sum(kl)


def attractive_forces_bucketed(y: jax.Array, buckets):
    """Algorithm 2 over the ELL's degree buckets (``similarity.degree_buckets``).

    Each bucket is a loop over its turns of [R, w] rows: a turn gathers its
    rows' own points and columns only up to the bucket's width w, so a row
    gathers at most its degree rounded up the ladder, not the graph's
    largest degree.  The per-entry math is :func:`attractive_forces_ell_blocked`'s
    over the same entries in the same order, less padding's zero terms.
    Each row's force is still summed over the ELL's whole width W, with
    zeros for the padding, so it rounds as the whole-ELL loop's does: where
    a backend's row sum does not depend on the block's row count, the
    forces are that loop's to the bit, and a fit's descent does not move.
    Forces come back to point order by one gather through ``buckets.inv``.
    """
    yx, yy = y[:, 0], y[:, 1]
    width = max(c.shape[-1] for c in buckets.cols)      # the ELL's W

    def one(args):
        rb, cb, vb = args
        return _block_terms(yx, yy, yx[rb], yy[rb], cb, vb, width)

    fx, fy, kl = [], [], 0.0
    for rows, cols, vals in zip(buckets.rows, buckets.cols, buckets.vals):
        bx, by, bk = jax.lax.map(one, (rows, cols, vals))
        fx.append(bx.reshape(-1))
        fy.append(by.reshape(-1))
        kl = kl + jnp.sum(bk)
    force = jnp.stack([jnp.concatenate(fx), jnp.concatenate(fy)], axis=1)
    return force[buckets.inv], kl


def attractive_forces_frozen(y: jax.Array, nbr_y: jax.Array, p: jax.Array):
    """Attractive force of free points against *frozen* neighbor coordinates.

    The out-of-sample kernel (FIt-SNE / t-SNE-CUDA style ``transform``):
    each new point ``y [M, 2]`` descends toward its k nearest *fitted*
    points, whose embedding coordinates ``nbr_y [M, K, 2]`` never move, with
    row-normalized similarities ``p [M, K]`` (padding: 0).  Rows are fully
    independent — no cross-point interaction — so the step is embarrassingly
    data-parallel and batches of unrelated requests share one program.

    Returns (force [M, 2], kl_attr [M] — per-point sum p log(1 + d²)).
    """
    diff = y[:, None, :] - nbr_y
    d2 = jnp.sum(diff * diff, axis=-1)
    pq = p / (1.0 + d2)
    force = jnp.sum(pq[..., None] * diff, axis=1)
    kl_attr = jnp.sum(p * jnp.log1p(d2), axis=1)
    return force, kl_attr

