"""Pallas TPU kernel for blocked pairwise squared distances — the KNN hot
loop (paper §3.1; daal4py's KNN is the one step the paper reuses, we build
it).

Output tile [TQ, TC] = |q|^2 + |c|^2 - 2 q c^T: one MXU matmul per tile plus
a rank-1 VPU epilogue.  Tiles are 128-aligned for the MXU; the feature dim D
stays resident per tile (t-SNE inputs are post-PCA, D <= ~1k, well inside
VMEM: 128x1024 f32 = 0.5 MB per operand block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

TQ = 128
TC = 128


def _pairwise_kernel(q_ref, c_ref, qn_ref, cn_ref, out_ref):
    q = q_ref[...]                       # [TQ, D]
    c = c_ref[...]                       # [TC, D]
    # full f32: the TPU default is one bf16 pass, too coarse to rank
    # neighbors
    dots = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                    # [TQ, TC] on the MXU
    out = qn_ref[...] + cn_ref[...] - 2.0 * dots        # [TQ,1] + [1,TC]
    out_ref[...] = jnp.maximum(out, 0.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pairwise_sq_dists_pallas(q, db, q_sqn=None, db_sqn=None, interpret: bool | None = None):
    nq, d = q.shape
    nc = db.shape[0]
    if q_sqn is None:
        q_sqn = jnp.sum(q * q, axis=1)
    if db_sqn is None:
        db_sqn = jnp.sum(db * db, axis=1)
    nq_pad = (nq + TQ - 1) // TQ * TQ
    nc_pad = (nc + TC - 1) // TC * TC
    qp = jnp.pad(q, ((0, nq_pad - nq), (0, 0)))
    cp = jnp.pad(db, ((0, nc_pad - nc), (0, 0)))
    qnp_ = jnp.pad(q_sqn, (0, nq_pad - nq))[:, None]     # [nq_pad, 1]
    cnp_ = jnp.pad(db_sqn, (0, nc_pad - nc))[None, :]     # [1, nc_pad]
    out = pl.pallas_call(
        _pairwise_kernel,
        grid=(nq_pad // TQ, nc_pad // TC),
        in_specs=[
            pl.BlockSpec((TQ, d), lambda i, j: (i, 0)),
            pl.BlockSpec((TC, d), lambda i, j: (j, 0)),
            pl.BlockSpec((TQ, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, TC), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((TQ, TC), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq_pad, nc_pad), q.dtype),
        interpret=interpret_mode(interpret),
    )(qp, cp, qnp_, cnp_)
    return out[:nq, :nc]
