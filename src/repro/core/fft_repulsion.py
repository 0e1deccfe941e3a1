"""FIt-SNE-style FFT-accelerated repulsion (Linderman et al., 2019).

The paper benchmarks Acc-t-SNE against FIt-SNE (its strongest competitor on
one thread — paper Table 4), so the baseline is implemented too: polynomial
interpolation onto a regular grid, kernel convolution via FFT (circulant
embedding), and interpolation back:

    phi_k(x_i) ~= sum_(p^2 nodes) L_p(x_i) * (K * spread(charges))[node]

Charges {1, y_x, y_y} against K2 = (1+d^2)^-2 give the repulsive numerator;
charge {1} against K1 = (1+d^2)^-1 gives Z.  O(N p^2 + M^2 log M) per
iteration instead of O(N log N) BH traversal.  Accuracy is controlled by
the node count (tests: ~1% force error at 128 nodes/dim vs exact O(N^2)).

The interpolation scatter/gather — the O(N p^2) half, which dominates once
N >> nodes^2 — is split into :func:`spread_to_grid` / :func:`gather_from_grid`
so it can dispatch to the Pallas tile kernels in ``kernels/interp_kernel.py``
(``interp_impl="pallas"``; registered as ``fft_spread`` / ``fft_gather`` in
the ``kernels/ops`` registry).  The jnp functions here are the oracles those
kernels are parity-tested against.  The FFT itself stays in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import scopes

P_ORDER = 3  # interpolation nodes per box per dim (cubic-ish accuracy)

# Hard cap on the boxes-per-dim grid resolution.  The Pallas interp kernels
# keep the whole [C, G, G] node lattice VMEM-resident per grid step, so the
# lattice (G = 2*n_boxes+1 padded to the 128-lane boundary) must stay inside
# the ~16 MB budget; `repro.analysis` certifies the BlockSpecs at exactly
# this envelope.  FIt-SNE-style accuracy needs ~50-100 boxes — 128 is head
# room, not a constraint.
MAX_N_BOXES = 128

INTERP_IMPLS = ("xla", "pallas")


def _lagrange_weights(frac: jax.Array) -> jax.Array:
    """Weights of the 3 equispaced nodes {0, .5, 1} for position frac [N]."""
    t = frac
    w0 = 2.0 * (t - 0.5) * (t - 1.0)
    w1 = -4.0 * t * (t - 1.0)
    w2 = 2.0 * t * (t - 0.5)
    return jnp.stack([w0, w1, w2], axis=-1)  # [N, 3]


def interp_coords(y: jax.Array, n_boxes: int):
    """Lattice geometry shared by spread and gather.

    Returns (base [N,2] int32 — the box-start node per dim, wx [N,3],
    wy [N,3] — per-dim Lagrange weights, h — node spacing).
    """
    lo = jnp.min(y, axis=0) - 1e-4
    hi = jnp.max(y, axis=0) + 1e-4
    span = jnp.maximum(jnp.max(hi - lo), 1e-12)
    m = n_boxes * (P_ORDER - 1)            # interior lattice nodes per dim
    h = span / m
    u = (y - lo[None, :]) / h              # fractional lattice coords in [0, m)
    iu = jnp.clip(jnp.floor(u / (P_ORDER - 1)).astype(jnp.int32), 0, n_boxes - 1)
    base = iu * (P_ORDER - 1)              # box start node
    frac = (u - base) / (P_ORDER - 1)      # [N,2] in [0,1]
    wx = _lagrange_weights(frac[:, 0])
    wy = _lagrange_weights(frac[:, 1])
    return base, wx, wy, h


def spread_to_grid(base, wx, wy, charges, nodes: int):
    """Scatter per-point charges onto the node lattice (jnp oracle).

    base [N,2] int32, wx/wy [N,3], charges [N,C] -> grid [nodes, nodes, C]:
    grid[a, b, c] = sum_i wx[i, a - base_x[i]] * wy[i, b - base_y[i]] * charges[i, c]
    (taps outside the 3x3 stencil contribute zero).
    """
    n, c = charges.shape
    gx = base[:, 0, None] + jnp.arange(P_ORDER)[None, :]   # [N,3]
    gy = base[:, 1, None] + jnp.arange(P_ORDER)[None, :]
    w2d = wx[:, :, None] * wy[:, None, :]                  # [N,3,3]
    flat_idx = (gx[:, :, None] * nodes + gy[:, None, :]).reshape(n, -1)
    contrib = w2d.reshape(n, -1)[:, :, None] * charges[:, None, :]  # [N,9,C]
    grid = jnp.zeros((nodes * nodes, c), charges.dtype)
    grid = grid.at[flat_idx.reshape(-1)].add(contrib.reshape(-1, c))
    return grid.reshape(nodes, nodes, c)


def gather_from_grid(pot, base, wx, wy):
    """Interpolate node potentials back at the points (jnp oracle).

    pot [nodes, nodes, C], base [N,2] int32, wx/wy [N,3] -> phi [N, C]:
    the transpose of :func:`spread_to_grid` with unit charges.
    """
    nodes, _, c = pot.shape
    n = base.shape[0]
    gx = base[:, 0, None] + jnp.arange(P_ORDER)[None, :]
    gy = base[:, 1, None] + jnp.arange(P_ORDER)[None, :]
    w2d = (wx[:, :, None] * wy[:, None, :]).reshape(n, -1)  # [N,9]
    flat_idx = (gx[:, :, None] * nodes + gy[:, None, :]).reshape(n, -1)
    vals = pot.reshape(-1, c)[flat_idx]                     # [N,9,C]
    return jnp.sum(vals * w2d[:, :, None], axis=1)          # [N,C]


@functools.partial(jax.jit, static_argnames=("n_boxes", "interp_impl"))
def fft_repulsion(y: jax.Array, n_boxes: int = 48, interp_impl: str = "xla"):
    """Returns (force_unnorm [N,2], z) matching exact_repulsion's contract.

    ``interp_impl`` selects the spread/gather implementation: "xla" (the jnp
    oracles above) or "pallas" (tiled one-hot-matmul kernels, interpret-mode
    on CPU).
    """
    if not 1 <= n_boxes <= MAX_N_BOXES:
        raise ValueError(
            f"n_boxes={n_boxes} outside [1, {MAX_N_BOXES}] — the interp "
            "kernels keep the whole node lattice VMEM-resident (MAX_N_BOXES)"
        )
    if interp_impl == "pallas":
        from repro.kernels.ops import fft_gather, fft_spread
        spread, gather = fft_spread, fft_gather
    elif interp_impl == "xla":
        spread, gather = spread_to_grid, gather_from_grid
    else:
        raise ValueError(
            f"unknown interp impl {interp_impl!r} "
            f"(known: {', '.join(INTERP_IMPLS)})"
        )
    n = y.shape[0]
    dtype = y.dtype
    m = n_boxes * (P_ORDER - 1)
    nodes = m + 1
    with jax.named_scope(scopes.FFT_SPREAD):
        base, wx, wy, h = interp_coords(y, n_boxes)
        # spread charges {1, yx, yy} onto the (m+1)^2 node lattice
        charges = jnp.stack([jnp.ones((n,), dtype), y[:, 0], y[:, 1]], axis=1)
        grid = spread(base, wx, wy, charges, nodes)        # [nodes, nodes, 3]

    # kernel convolution via circulant embedding (size 2*nodes)
    with jax.named_scope(scopes.FFT_CONVOLVE):
        big = 2 * nodes
        dx = jnp.minimum(jnp.arange(big),
                         big - jnp.arange(big)).astype(dtype) * h
        d2 = dx[:, None] ** 2 + dx[None, :] ** 2
        k1 = 1.0 / (1.0 + d2)
        k2 = k1 * k1
        fk1 = jnp.fft.rfft2(k1)
        fk2 = jnp.fft.rfft2(k2)
        gpad = jnp.pad(grid, ((0, big - nodes), (0, big - nodes), (0, 0)))
        fg = jnp.fft.rfft2(gpad, axes=(0, 1))
        pot2 = jnp.fft.irfft2(fg * fk2[:, :, None], s=(big, big),
                              axes=(0, 1))[:nodes, :nodes]
        pot1 = jnp.fft.irfft2(fg[..., 0] * fk1, s=(big, big))[:nodes, :nodes]

    # gather all four potentials back at the points in one pass:
    # channels = {sum K2, sum K2*yx, sum K2*yy, sum K1 (incl self)}
    with jax.named_scope(scopes.FFT_GATHER):
        pot_all = jnp.concatenate([pot2, pot1[:, :, None]], axis=2)
        phi = gather(pot_all, base, wx, wy)                # [N, 4]
        phi2_1, phi2_x, phi2_y, phi1_1 = (phi[:, 0], phi[:, 1], phi[:, 2],
                                          phi[:, 3])
        z = jnp.sum(phi1_1) - n                            # remove self terms
        fx = y[:, 0] * phi2_1 - phi2_x                     # self term cancels
        fy = y[:, 1] * phi2_1 - phi2_y
        return jnp.stack([fx, fy], axis=1), jnp.maximum(z, 1e-30)
