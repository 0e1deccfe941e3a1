"""Pluggable t-SNE gradient backends + string-keyed registry.

A *backend* owns step 3-6 of the pipeline: given the current embedding, the
:class:`~repro.core.tsne.NeighborGraph` and the exaggeration factor, it
returns a :class:`~repro.core.tsne.GradResult` (gradient, KL estimate, Z).
Backends are frozen dataclasses — hashable, so ``tsne_step`` can treat them
as static jit arguments and each backend compiles its own step program.

Three first-class implementations ship with the repo:

* ``exact``       — the O(N^2) oracle (``core/exact.py``)
* ``barnes_hut``  — the paper's Morton/quadtree/summarize/traverse pipeline
* ``fft``         — FIt-SNE-style grid-interpolation repulsion
                    (``core/fft_repulsion.py``, Linderman et al.)

Register your own with :func:`register_backend`; the estimator's ``method=``
and ``TsneConfig.method`` both dispatch through :func:`make_backend`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, ClassVar, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import attractive, exact, scopes
from repro.core.fft_repulsion import fft_repulsion, lattice_extent
from repro.core.tsne import (
    GradResult, NeighborGraph, TsneConfig, bh_gradient, combine_forces,
)


@runtime_checkable
class GradientBackend(Protocol):
    """What ``tsne_step`` needs from a backend.

    Implementations must be hashable (frozen dataclasses are) because the
    backend is passed to ``jax.jit`` as a static argument.
    """

    name: str

    def gradient(
        self, y: jax.Array, graph: NeighborGraph, exaggeration
    ) -> GradResult:
        ...


# --------------------------------------------------------------------------
# First-class backends
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExactBackend:
    """O(N^2) dense gradient — the correctness oracle, feasible to ~5k points."""

    name: ClassVar[str] = "exact"

    def gradient(self, y, graph: NeighborGraph, exaggeration) -> GradResult:
        n = y.shape[0]
        rows = jnp.arange(n, dtype=graph.p_cols.dtype)[:, None]
        # densify the ELL rows; padding entries carry val 0 on the diagonal
        p_dense = jnp.zeros((n, n), y.dtype).at[rows, graph.p_cols].add(graph.p_vals)
        f_attr, kl_attr = exact.exact_attraction(y, p_dense)
        f_rep, z = exact.exact_repulsion(y)
        return combine_forces(f_attr, kl_attr, f_rep, z, exaggeration,
                              graph.p_logp)


@dataclasses.dataclass(frozen=True)
class BarnesHutBackend:
    """The paper's pipeline: Morton encode -> quadtree -> summarize -> traverse."""

    name: ClassVar[str] = "barnes_hut"
    theta: float = 0.5
    depth: int = 16
    compress_tree: bool = True
    use_pallas: bool = False

    def gradient(self, y, graph: NeighborGraph, exaggeration) -> GradResult:
        return bh_gradient(
            y, graph, self.theta, exaggeration, self.depth,
            compress_tree=self.compress_tree, use_pallas=self.use_pallas,
        )


@dataclasses.dataclass(frozen=True)
class FFTBackend:
    """FIt-SNE-style repulsion: interpolate to a grid, convolve via FFT.

    ``interp_impl`` picks the spread/gather implementation: ``"xla"`` (jnp
    scatter/gather oracles) or ``"pallas"`` (tiled one-hot-matmul kernels,
    interpret-mode on CPU) — see ``core/fft_repulsion.py``.
    """

    name: ClassVar[str] = "fft"
    n_boxes: int = 48
    interp_impl: str = "xla"

    def gradient(self, y, graph: NeighborGraph, exaggeration) -> GradResult:
        with jax.named_scope(scopes.ATTRACTIVE):
            f_attr, kl_attr = attractive.attractive_forces_bucketed(
                y, graph.buckets)
        f_rep_unnorm, z = fft_repulsion(y, n_boxes=self.n_boxes,
                                        interp_impl=self.interp_impl)
        with jax.named_scope(scopes.FFT_SPREAD):
            # fft_repulsion's own reduction: XLA computes it once
            _, span = lattice_extent(y)
        return combine_forces(f_attr, kl_attr, f_rep_unnorm, z, exaggeration,
                              graph.p_logp, fft_span=span)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

# factory(config, n_points) -> GradientBackend
BackendFactory = Callable[[TsneConfig, int], GradientBackend]

_REGISTRY: dict[str, BackendFactory] = {}


def register_backend(name: str, factory: BackendFactory | None = None):
    """Register a backend factory under ``name``.

    Usable directly — ``register_backend("mine", make_mine)`` — or as a
    decorator::

        @register_backend("mine")
        def make_mine(config: TsneConfig, n: int) -> GradientBackend:
            return MyBackend(...)
    """
    def _register(fn: BackendFactory) -> BackendFactory:
        _REGISTRY[name] = fn
        return fn

    return _register(factory) if factory is not None else _register


def unregister_backend(name: str) -> None:
    _REGISTRY.pop(name, None)


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def make_backend(method: str, config: TsneConfig, n: int) -> GradientBackend:
    """Instantiate the backend registered under ``method`` for an N-point run."""
    try:
        factory = _REGISTRY[method]
    except KeyError:
        raise ValueError(
            f"unknown t-SNE method {method!r}; registered backends: "
            f"{', '.join(available_backends())}"
        ) from None
    return factory(config, n)


@register_backend("exact")
def _make_exact(config: TsneConfig, n: int) -> ExactBackend:
    return ExactBackend()


@register_backend("barnes_hut")
def _make_barnes_hut(config: TsneConfig, n: int) -> BarnesHutBackend:
    return BarnesHutBackend(
        theta=config.theta,
        depth=config.resolve_depth(n),
        compress_tree=config.compress_tree,
        use_pallas=config.use_pallas,
    )


@register_backend("fft")
def _make_fft(config: TsneConfig, n: int) -> FFTBackend:
    return FFTBackend(n_boxes=config.fft_n_boxes,
                      interp_impl=config.resolve_fft_interp_impl())
