"""Pallas TPU kernels for the FFT-repulsion interpolation spread/gather.

t-SNE-CUDA's profile shows the grid interpolation becoming the bottleneck
once the field solve is an FFT; ours said the same (scatter-add over [N,9]
taps lowers to serialized XLA scatters).  TPUs have no fast scatter at all,
so both directions are reformulated as *matmuls* over one-hot tap matrices,
exploiting that the 3x3 Lagrange stencil is separable:

    spread:  grid[a, b] = sum_i Wx[i, a] * ch[i] * Wy[i, b]
                        = (Wx * ch)^T @ Wy          -- one MXU matmul/channel
    gather:  phi[i]     = sum_{a,b} Wx[i, a] * pot[a, b] * Wy[i, b]
                        = rowsum((Wx @ pot) * Wy)   -- one MXU matmul/channel

where Wx/Wy are [TILE, G] with the 3 Lagrange weights placed at columns
base..base+2 (built in-register from a broadcasted iota — no gather/scatter
anywhere).  The node lattice G is padded to the 128-lane boundary and small
enough (<= ~256 for any practical n_boxes) that the whole grid block stays
VMEM-resident:

* spread — grid over point tiles, every step accumulates its tile's
  contribution into the same [C, G, G] output block (zero-initialized at
  step 0: the sequential-grid revisiting pattern);
* gather — grid over point tiles, the potential block rides along broadcast
  (index_map -> 0) and each step emits its [TILE, C] interpolated values.

Oracles: ``core/fft_repulsion.spread_to_grid`` / ``gather_from_grid``
(exact on planted node-centered points, allclose elsewhere — the matmul
changes only the float summation order).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

P_ORDER = 3      # must match core/fft_repulsion.P_ORDER
TILE = 256
LANE = 128       # node-lattice padding boundary


def _onehot_taps(idx, w, g: int):
    """[T, g] matrix with w[t, tap] at column idx[t] + tap, else 0."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], g), 1)
    out = jnp.zeros((idx.shape[0], g), w.dtype)
    for tap in range(P_ORDER):
        out = out + jnp.where(cols == idx[:, None] + tap, w[:, tap][:, None], 0.0)
    return out


def _spread_kernel(base_ref, wx_ref, wy_ref, ch_ref, out_ref, *, n_ch: int):
    i = pl.program_id(0)
    base = base_ref[...]                 # [T, 2] int32
    ch = ch_ref[...]                     # [T, C]
    g = out_ref.shape[-1]
    w_x = _onehot_taps(base[:, 0], wx_ref[...], g)   # [T, G]
    w_y = _onehot_taps(base[:, 1], wy_ref[...], g)
    acc = jnp.stack([
        jax.lax.dot_general(
            w_x * ch[:, c][:, None], w_y,
            (((0,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)
        for c in range(n_ch)
    ])                                   # [C, G, G]

    @pl.when(i == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(i > 0)
    def _accum():
        out_ref[...] += acc


def _gather_kernel(pot_ref, base_ref, wx_ref, wy_ref, out_ref, *, n_ch: int):
    pot = pot_ref[...]                   # [C, G, G]
    base = base_ref[...]
    g = pot.shape[-1]
    w_x = _onehot_taps(base[:, 0], wx_ref[...], g)   # [T, G]
    w_y = _onehot_taps(base[:, 1], wy_ref[...], g)
    phi = [
        jnp.sum(
            jax.lax.dot_general(
                w_x, pot[c],
                (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            ).astype(out_ref.dtype) * w_y,
            axis=1,
        )
        for c in range(n_ch)
    ]
    out_ref[...] = jnp.stack(phi, axis=1)            # [T, C]


def _pad_points(base, wx, wy, extra=None):
    n = base.shape[0]
    n_pad = (n + TILE - 1) // TILE * TILE
    pad = n_pad - n
    out = [jnp.pad(base, ((0, pad), (0, 0))),
           jnp.pad(wx, ((0, pad), (0, 0))),          # zero weights: no-op rows
           jnp.pad(wy, ((0, pad), (0, 0)))]
    if extra is not None:
        out.append(jnp.pad(extra, ((0, pad), (0, 0))))
    return n_pad, out


@functools.partial(jax.jit, static_argnames=("nodes", "interpret"))
def spread_to_grid_pallas(base, wx, wy, charges, nodes: int, interpret: bool | None = None):
    """Same contract as ``core/fft_repulsion.spread_to_grid``."""
    c = charges.shape[1]
    g = (nodes + LANE - 1) // LANE * LANE
    n_pad, (basep, wxp, wyp, chp) = _pad_points(base, wx, wy, charges)
    grid = pl.pallas_call(
        functools.partial(_spread_kernel, n_ch=c),
        grid=(n_pad // TILE,),
        in_specs=[
            pl.BlockSpec((TILE, 2), lambda i: (i, 0)),
            pl.BlockSpec((TILE, P_ORDER), lambda i: (i, 0)),
            pl.BlockSpec((TILE, P_ORDER), lambda i: (i, 0)),
            pl.BlockSpec((TILE, c), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((c, g, g), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, g, g), charges.dtype),
        interpret=interpret_mode(interpret),
    )(basep, wxp, wyp, chp)
    return jnp.transpose(grid, (1, 2, 0))[:nodes, :nodes, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_from_grid_pallas(pot, base, wx, wy, interpret: bool | None = None):
    """Same contract as ``core/fft_repulsion.gather_from_grid``."""
    nodes, _, c = pot.shape
    n = base.shape[0]
    g = (nodes + LANE - 1) // LANE * LANE
    potp = jnp.pad(jnp.transpose(pot, (2, 0, 1)),
                   ((0, 0), (0, g - nodes), (0, g - nodes)))
    n_pad, (basep, wxp, wyp) = _pad_points(base, wx, wy)
    phi = pl.pallas_call(
        functools.partial(_gather_kernel, n_ch=c),
        grid=(n_pad // TILE,),
        in_specs=[
            pl.BlockSpec((c, g, g), lambda i: (0, 0, 0)),
            pl.BlockSpec((TILE, 2), lambda i: (i, 0)),
            pl.BlockSpec((TILE, P_ORDER), lambda i: (i, 0)),
            pl.BlockSpec((TILE, P_ORDER), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, c), pot.dtype),
        interpret=interpret_mode(interpret),
    )(potp, basep, wxp, wyp)
    return phi[:n]
