"""Pallas TPU kernel for Morton code formation (paper Algorithm 1).

The paper notes the compiler auto-vectorizes this loop with AVX; on TPU the
analogue is a VPU-resident elementwise kernel over point tiles.  One grid
step processes a [TILE, 2] block of embedding points held in VMEM and emits
[TILE] uint32 codes; the root-cell scalars ride along as a (1, 4) block
broadcast to every tile (index_map -> 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

TILE = 1024


def _expand_bits(v):
    v = v & jnp.uint32(0x0000FFFF)
    v = (v | (v << 8)) & jnp.uint32(0x00FF00FF)
    v = (v | (v << 4)) & jnp.uint32(0x0F0F0F0F)
    v = (v | (v << 2)) & jnp.uint32(0x33333333)
    v = (v | (v << 1)) & jnp.uint32(0x55555555)
    return v


def _morton_kernel(y_ref, root_ref, out_ref, *, depth: int):
    y = y_ref[...]                       # [TILE, 2]
    y_root_x = root_ref[0, 0]
    y_root_y = root_ref[0, 1]
    scale = root_ref[0, 2]
    hi = jnp.asarray(float(2**depth) - 1.0, y.dtype)
    mx_f = jnp.clip((y[:, 0:1] - y_root_x) * scale, 0.0, hi)    # [TILE, 1]
    my_f = jnp.clip((y[:, 1:2] - y_root_y) * scale, 0.0, hi)
    # float -> int32 -> uint32: Mosaic has no direct float -> uint32 cast;
    # the clipped values are in [0, 2^16) so both steps are exact
    mx = _expand_bits(jax.lax.bitcast_convert_type(mx_f.astype(jnp.int32), jnp.uint32))
    my = _expand_bits(jax.lax.bitcast_convert_type(my_f.astype(jnp.int32), jnp.uint32))
    code = mx | (my << 1)
    if depth < 16:
        code = code & jnp.uint32((1 << (2 * depth)) - 1)
    out_ref[...] = code


@functools.partial(jax.jit, static_argnames=("depth", "interpret"))
def morton_encode_pallas(y, cent, r_span, depth: int = 16, interpret: bool | None = None):
    n = y.shape[0]
    n_pad = (n + TILE - 1) // TILE * TILE
    yp = jnp.pad(y, ((0, n_pad - n), (0, 0)))
    y_root = cent - r_span
    scale = (2.0 ** (depth - 1)) / r_span
    root = jnp.stack([y_root[0], y_root[1], scale.astype(y.dtype), jnp.zeros((), y.dtype)])[None, :]
    out = pl.pallas_call(
        functools.partial(_morton_kernel, depth=depth),
        grid=(n_pad // TILE,),
        in_specs=[
            pl.BlockSpec((TILE, 2), lambda i: (i, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.uint32),
        interpret=interpret_mode(interpret),
    )(yp, root)
    return out[:n, 0]
