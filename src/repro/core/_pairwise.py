"""Pure-XLA pairwise squared distances (the jnp counterpart of the Pallas
pairwise kernel; also its correctness oracle)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_sq_dists(q, db, q_sqn=None, db_sqn=None):
    """||q_i - db_j||^2 as an MXU matmul + rank-1 epilogue. [Q, D] x [C, D] -> [Q, C]."""
    if q_sqn is None:
        q_sqn = jnp.sum(q * q, axis=1)
    if db_sqn is None:
        db_sqn = jnp.sum(db * db, axis=1)
    # full f32: a TPU's default one-pass bf16 product blurs the gaps
    # between neighbor ranks
    dots = jnp.matmul(q, db.T, precision=jax.lax.Precision.HIGHEST)
    return q_sqn[:, None] + db_sqn[None, :] - 2.0 * dots
