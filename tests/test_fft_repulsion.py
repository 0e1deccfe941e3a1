"""FIt-SNE baseline (FFT-interpolation repulsion) vs the exact oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.exact import exact_repulsion
from repro.core.fft_repulsion import fft_repulsion


@pytest.mark.parametrize("n,boxes,tol", [(500, 48, 0.05), (2000, 96, 0.01)])
def test_matches_exact(n, boxes, tol):
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32) * 5)
    f, z = fft_repulsion(y, n_boxes=boxes)
    fe, ze = exact_repulsion(y)
    assert abs(float(z) - float(ze)) / float(ze) < tol
    num = np.linalg.norm(np.asarray(f - fe), axis=1)
    den = np.linalg.norm(np.asarray(fe), axis=1) + 1e-9
    assert np.mean(num / den) < tol


def test_clustered_points():
    rng = np.random.default_rng(1)
    c = rng.normal(size=(4, 2)) * 8
    y = jnp.asarray((c[rng.integers(0, 4, 800)] +
                     rng.normal(size=(800, 2)) * 0.3).astype(np.float32))
    f, z = fft_repulsion(y, n_boxes=96)
    fe, ze = exact_repulsion(y)
    assert abs(float(z) - float(ze)) / float(ze) < 0.02
    np.testing.assert_allclose(np.asarray(f).sum(0), np.asarray(fe).sum(0),
                               rtol=0.1, atol=1e-2)


@pytest.mark.parametrize("n,boxes", [(50, 16), (700, 48), (3000, 50)])
def test_matmul_interp_matches_the_scatter_oracles(n, boxes):
    from repro.core import fft_repulsion as fr

    rng = np.random.default_rng(n)
    y = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32) * 5)
    nodes = boxes * (fr.P_ORDER - 1) + 1
    base, wx, wy, _ = fr.interp_coords(y, boxes)
    charges = jnp.stack([jnp.ones((n,), jnp.float32), y[:, 0], y[:, 1]], 1)
    np.testing.assert_allclose(
        np.asarray(fr.spread_by_matmul(base, wx, wy, charges, nodes)),
        np.asarray(fr.spread_to_grid(base, wx, wy, charges, nodes)),
        rtol=1e-5, atol=1e-5)
    pot = jnp.asarray(rng.normal(size=(nodes, nodes, 4)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(fr.gather_by_matmul(pot, base, wx, wy)),
        np.asarray(fr.gather_from_grid(pot, base, wx, wy)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_z_leaves_out_the_interpolated_self_pairs(seed):
    """Z subtracts each point's K1 with itself as the lattice gives it, not
    1: on a 50-box grid over a span of ~35 the interpolation's error on the
    2,000 self-pairs alone is 1.5e-4 of Z."""
    from repro.core import fft_repulsion as fr

    y = np.random.default_rng(seed).normal(size=(2000, 2)) * 5
    d2 = ((y[:, None] - y[None]) ** 2).sum(-1)
    k1 = 1.0 / (1.0 + d2)
    ze = k1.sum() - len(y)
    _, z = fr.fft_repulsion(jnp.asarray(y, jnp.float32), n_boxes=50)
    assert abs(float(z) - ze) / ze < 5e-5
