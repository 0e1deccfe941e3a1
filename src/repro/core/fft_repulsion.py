"""FIt-SNE-style FFT-accelerated repulsion (Linderman et al., 2019).

The paper benchmarks Acc-t-SNE against FIt-SNE (its strongest competitor on
one thread — paper Table 4), so the baseline is implemented too: polynomial
interpolation onto a regular grid, kernel convolution via FFT (circulant
embedding), and interpolation back:

    phi_k(x_i) ~= sum_(p^2 nodes) L_p(x_i) * (K * spread(charges))[node]

Charges {1, y_x, y_y} against K2 = (1+d^2)^-2 give the repulsive numerator;
charge {1} against K1 = (1+d^2)^-1 gives Z, less each point's pair with
itself as the lattice interpolates it (:func:`self_k1`).  O(N p^2 +
M^2 log M) per iteration instead of O(N log N) BH traversal.  Accuracy is
controlled by the node count (tests: ~1% force error at 128 nodes/dim vs
exact O(N^2)).

The interpolation scatter/gather — the O(N p^2) half, which dominates once
N >> nodes^2 — is defined by :func:`spread_to_grid` / :func:`gather_from_grid`,
a scatter-add and a gather of the 3x3 taps: the oracles that every
implementation is tested against.  The XLA path (``interp_impl="xla"``) runs
them as matmuls over one-hot tap matrices instead (:func:`spread_by_matmul`
/ :func:`gather_by_matmul`): the TPU compiler unrolls a scatter or gather of
N * 9 taps into code that grows with N (compiling the spread of 70,000
points for a v5e took 110 s and gave 26 MB of code), where a matmul's code
does not grow.  The Pallas tile kernels in ``kernels/interp_kernel.py``
(``interp_impl="pallas"``; registered as ``fft_spread`` / ``fft_gather`` in
the ``kernels/ops`` registry) tile the same matmuls.  The FFT itself stays
in XLA.
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


def lattice_extent(y: jax.Array):
    """The lattice's lower corner [2] and its side ``span`` in embedding
    units: the points' bounding box, 1e-4 wider on each side, squared up.

    FIt-SNE sizes its grid from ``span`` (max(50, span) intervals a
    dimension); here the grid is fixed at ``n_boxes``, and ``span`` is
    reported so a fit shows whether that rule would have grown it.
    """
    lo = jnp.min(y, axis=0) - 1e-4
    hi = jnp.max(y, axis=0) + 1e-4
    return lo, jnp.maximum(jnp.max(hi - lo), 1e-12)


def interp_coords(y: jax.Array, n_boxes: int):
    """Lattice geometry shared by spread and gather.

    Returns (base [N,2] int32 — the box-start node per dim, wx [N,3],
    wy [N,3] — per-dim Lagrange weights, h — node spacing).
    """
    lo, span = lattice_extent(y)
    m = n_boxes * (P_ORDER - 1)            # interior lattice nodes per dim
    h = span / m
    u = (y - lo[None, :]) / h              # fractional lattice coords in [0, m)
    iu = jnp.clip(jnp.floor(u / (P_ORDER - 1)).astype(jnp.int32), 0, n_boxes - 1)
    base = iu * (P_ORDER - 1)              # box start node
    frac = (u - base) / (P_ORDER - 1)      # [N,2] in [0,1]
    wx = _lagrange_weights(frac[:, 0])
    wy = _lagrange_weights(frac[:, 1])
    return base, wx, wy, h


def self_k1(wx, wy, h):
    """[N]: each point's K1 with itself as the lattice interpolates it, the
    point's 3 x 3 taps against each other:
    sum w_a w_b w_c w_d / (1 + h^2 ((a - c)^2 + (b - d)^2)).  Z leaves
    these out, where leaving out 1 a point would leave the interpolation's
    error on the N self-pairs in Z."""
    t = jnp.arange(P_ORDER, dtype=wx.dtype)
    d2 = (t[:, None] - t[None, :]) ** 2 * (h * h)                  # [a, c]
    k1 = 1.0 / (1.0 + d2[:, None, :, None] + d2[None, :, None, :])  # [a,b,c,d]
    wxx = wx[:, :, None] * wx[:, None, :]                           # [N, a, c]
    wyy = wy[:, :, None] * wy[:, None, :]                           # [N, b, d]
    return jnp.sum(wxx[:, :, None, :, None] * wyy[:, None, :, None, :]
                   * k1[None], axis=(1, 2, 3, 4))


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


def _taps(base, w, nodes: int):
    """[N, nodes]: each point's 3 weights at its box's nodes base..base+2
    along one dimension, 0 elsewhere."""
    off = jnp.arange(nodes)[None, :] - base[:, None]
    return sum(jnp.where(off == t, w[:, t, None], 0.0) for t in range(P_ORDER))


def spread_by_matmul(base, wx, wy, charges, nodes: int):
    """:func:`spread_to_grid` as one matmul: grid[a, b, c] =
    sum_i Tx[i, a] * Ty[i, b] * charges[i, c], with T the one-hot taps."""
    n, c = charges.shape
    tx = _taps(base[:, 0], wx, nodes)
    tyc = _taps(base[:, 1], wy, nodes)[:, :, None] * charges[:, None, :]
    grid = jnp.dot(tx.T, tyc.reshape(n, nodes * c),
                   precision=jax.lax.Precision.HIGHEST)
    return grid.reshape(nodes, nodes, c)


def gather_by_matmul(pot, base, wx, wy):
    """:func:`gather_from_grid` as one matmul: phi[i, c] =
    sum_b Ty[i, b] * (Tx @ pot)[i, b, c], with T the one-hot taps."""
    nodes, _, c = pot.shape
    n = base.shape[0]
    rows = jnp.dot(_taps(base[:, 0], wx, nodes), pot.reshape(nodes, nodes * c),
                   precision=jax.lax.Precision.HIGHEST).reshape(n, nodes, c)
    return jnp.sum(_taps(base[:, 1], wy, nodes)[:, :, None] * rows, axis=1)


@functools.partial(jax.jit, static_argnames=("n_boxes", "interp_impl"))
def fft_repulsion(y: jax.Array, n_boxes: int = 48, interp_impl: str = "xla"):
    """Returns (force_unnorm [N,2], z) matching exact_repulsion's contract.

    ``interp_impl`` selects the spread/gather implementation: "xla" (the
    one-hot matmuls above) or "pallas" (the same matmuls in tiled kernels,
    interpret-mode on CPU).
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
        spread, gather = spread_by_matmul, gather_by_matmul
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
        z = jnp.sum(phi1_1) - jnp.sum(self_k1(wx, wy, h))  # self-pairs out
        fx = y[:, 0] * phi2_1 - phi2_x                     # self term cancels
        fy = y[:, 1] * phi2_1 - phi2_y
        return jnp.stack([fx, fy], axis=1), jnp.maximum(z, 1e-30)
