"""How far the approximate gradient backends may stray from the exact one.

Two fits of the same data part ways within a few iterations of early
exaggeration: a different float summation order is enough (a TPU and a CPU
differ there, and so do two backends).  So agreement is judged on one
gradient, at one fixed embedding, against the O(N^2) ``exact`` backend.

Each bound is about twice the largest error measured on the CPU (XLA CPU,
JAX 0.9.0) for 300 digits points at perplexity 12 and for a seeded
4,096-point mnist subsample at perplexity 30, at :func:`reference_embedding`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.backends import ExactBackend, make_backend
from repro.core.tsne import NeighborGraph, TsneConfig


@dataclasses.dataclass(frozen=True)
class Bound:
    rel_grad: float      # ||g - g_exact|| / ||g_exact||
    abs_kl: float        # |KL - KL_exact|
    reason: str


# method -> (TsneConfig overrides, bound)
REFERENCE_BOUNDS = {
    "barnes_hut": (dict(theta=0.5), Bound(
        rel_grad=3e-2, abs_kl=1e-2,
        reason="theta=0.5 is scikit-learn's default opening angle; measured "
               "1.2e-2 (digits) and 8.2e-3 (mnist) gradient error, and "
               "|dKL| 4.1e-3 / 6.6e-3 from the Z estimate (log Z moves by "
               "Z's relative error)")),
    "fft": (dict(fft_n_boxes=48), Bound(
        rel_grad=8e-2, abs_kl=1e-2,
        reason="48 boxes x 3 Lagrange nodes per dimension; at an embedding "
               "std of 10 the node spacing is ~0.6 against a kernel that "
               "varies on a scale of 1; measured 3.7e-2 (digits) and 1.5e-2 "
               "(mnist) gradient error, |dKL| 3.8e-3 / 1.8e-4")),
}


def reference_embedding(x, scale: float = 10.0) -> np.ndarray:
    """The inputs' first two principal components, scaled to std ``scale``.

    A deterministic embedding with the data's cluster structure, at about
    the spread a descent reaches after early exaggeration.
    """
    xc = np.asarray(x, np.float64)
    xc = xc - xc.mean(axis=0)
    _, _, vt = np.linalg.svd(xc, full_matrices=False)
    y = xc @ vt[:2].T
    return (y * (scale / y.std())).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("backend",))
def _gradient(y, graph, *, backend):
    return backend.gradient(y, graph, 1.0)


def gradient_error(method: str, y, graph: NeighborGraph,
                   config: TsneConfig) -> tuple[float, float]:
    """(relative gradient error, |dKL|) of ``method`` against ``exact`` at ``y``,
    with the method configured as in :data:`REFERENCE_BOUNDS`."""
    overrides, _ = REFERENCE_BOUNDS[method]
    backend = make_backend(method, dataclasses.replace(config, **overrides),
                           graph.n)
    y = jnp.asarray(y)
    ex = _gradient(y, graph, backend=ExactBackend())
    res = _gradient(y, graph, backend=backend)
    rel = float(jnp.linalg.norm(res.grad - ex.grad) / jnp.linalg.norm(ex.grad))
    return rel, abs(float(res.kl) - float(ex.kl))
