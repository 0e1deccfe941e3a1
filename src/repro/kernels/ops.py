"""Public names of the Pallas kernels + the kernel registry.

Each name is the kernel's own jitted entry point; whether it compiles or
runs in the Pallas interpreter is decided when it is traced
(``repro.kernels.interpret_mode``): compiled on a TPU, interpreted off it
only where asked for.  The kernels target TPU v5e and are checked against
the pure-jnp oracles in ref.py.

``kernel_registry()`` / ``get_kernel()`` form the registry-style dispatch
table the core modules and the roofline tool share: one entry per
kernelized hot path, carrying the pure-jnp oracle (``ref``) and the Pallas
entry point (``pallas``).  Core
call sites (``core/bsp.py``, ``core/fft_repulsion.py``, ``bh_gradient``)
select an implementation from their config flag; ``benchmarks/roofline.py
--tsne`` walks this table to report which hot paths are kernelized and
which are still plain XLA.  See docs/KERNELS.md for the playbook.
"""
from __future__ import annotations

from repro.kernels.attractive_kernel import (
    attractive_forces_ell_pallas as attractive_forces_ell,
)
from repro.kernels.bsp_kernel import (
    binary_search_perplexity_pallas as binary_search_perplexity,
)
from repro.kernels.interp_kernel import (
    gather_from_grid_pallas as fft_gather,
    spread_to_grid_pallas as fft_spread,
)
from repro.kernels.morton_kernel import morton_encode_pallas as morton_encode
from repro.kernels.pairwise_kernel import (
    pairwise_sq_dists_pallas as pairwise_sq_dists,
)


def kernel_registry() -> dict:
    """name -> dict(ref=oracle fn, pallas=kernel entry point, doc).

    Built lazily: the oracles live in ``repro.core`` which must not import
    at ``repro.kernels`` import time (core modules lazily import this module
    for their own dispatch).
    """
    from repro.core import _pairwise, attractive, bsp, fft_repulsion, morton
    return {
        "morton_encode": dict(
            ref=morton.morton_encode, pallas=morton_encode,
            doc="Algorithm 1: Morton code formation"),
        "pairwise_sq_dists": dict(
            ref=_pairwise.pairwise_sq_dists, pallas=pairwise_sq_dists,
            doc="KNN distance tile (MXU matmul + rank-1 epilogue)"),
        "attractive_ell": dict(
            ref=attractive.attractive_forces_ell, pallas=attractive_forces_ell,
            doc="Algorithm 2: attractive-force epilogue over ELL rows"),
        "bsp_search": dict(
            ref=bsp._binary_search_perplexity_xla, pallas=binary_search_perplexity,
            doc="§3.2: fused per-row perplexity bisection over [N, K]"),
        "fft_spread": dict(
            ref=fft_repulsion.spread_to_grid, pallas=fft_spread,
            doc="FFT repulsion: charge scatter onto the node lattice"),
        "fft_gather": dict(
            ref=fft_repulsion.gather_from_grid, pallas=fft_gather,
            doc="FFT repulsion: potential interpolation back at the points"),
    }


def get_kernel(name: str, impl: str = "pallas"):
    """Dispatch helper: the ``impl`` entry point of registered kernel ``name``."""
    table = kernel_registry()
    try:
        entry = table[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel {name!r} (registered: {', '.join(sorted(table))})"
        ) from None
    if impl not in ("ref", "pallas"):
        raise ValueError(f"impl must be 'ref' or 'pallas', got {impl!r}")
    return entry[impl]


def available_kernels() -> tuple[str, ...]:
    return tuple(sorted(kernel_registry()))
