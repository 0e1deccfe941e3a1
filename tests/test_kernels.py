"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bsp as bsp_ref
from repro.core import fft_repulsion as fft_ref
from repro.core import morton as morton_ref
from repro.core import _pairwise as pairwise_ref
from repro.core import attractive as attractive_ref
from repro.kernels.attractive_kernel import attractive_forces_ell_pallas
from repro.kernels.bsp_kernel import binary_search_perplexity_pallas
from repro.kernels.interp_kernel import (
    gather_from_grid_pallas, spread_to_grid_pallas,
)
from repro.kernels.morton_kernel import morton_encode_pallas
from repro.kernels.pairwise_kernel import pairwise_sq_dists_pallas


@pytest.mark.parametrize("n", [1, 100, 1024, 2500])
@pytest.mark.parametrize("depth", [8, 16])
def test_morton_kernel_matches_ref(n, depth):
    rng = np.random.default_rng(n)
    y = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32) * 10)
    cent, r = morton_ref.span_radius(y)
    ref = morton_ref.morton_encode(y, cent, r, depth=depth)
    out = morton_encode_pallas(y, cent, r, depth=depth)
    assert (np.asarray(out) == np.asarray(ref)).all()


@pytest.mark.parametrize("nq,nc,d", [(64, 64, 8), (128, 256, 20), (300, 500, 64), (1000, 777, 784)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_pairwise_kernel_matches_ref(nq, nc, d, dtype):
    rng = np.random.default_rng(nq + nc)
    q = jnp.asarray(rng.normal(size=(nq, d)), dtype)
    c = jnp.asarray(rng.normal(size=(nc, d)), dtype)
    ref = pairwise_ref.pairwise_sq_dists(q, c)
    out = pairwise_sq_dists_pallas(q, c)
    np.testing.assert_allclose(np.asarray(out), np.maximum(np.asarray(ref), 0), rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("n,w", [(10, 3), (256, 90), (1000, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_attractive_kernel_matches_ref(n, w, dtype):
    rng = np.random.default_rng(n + w)
    y = jnp.asarray(rng.normal(size=(n, 2)), dtype)
    cols = jnp.asarray(rng.integers(0, n, size=(n, w)), jnp.int32)
    vals = jnp.asarray(rng.uniform(0, 1e-3, size=(n, w)), dtype)
    f_ref, kl_ref = attractive_ref.attractive_forces_ell(y, cols, vals)
    f, kl = attractive_forces_ell_pallas(y, cols, vals)
    np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(kl), float(kl_ref), rtol=1e-5)


@pytest.mark.parametrize("n,k", [(1, 5), (65, 20), (500, 45), (1000, 90)])
@pytest.mark.parametrize("perplexity", [8.0, 30.0])
def test_bsp_kernel_matches_ref(n, k, perplexity):
    if k > 3 * perplexity:
        k = int(3 * perplexity)
    rng = np.random.default_rng(n + k)
    d2 = jnp.asarray(np.abs(rng.normal(size=(n, k))).astype(np.float32) * 4)
    p_ref, b_ref = bsp_ref._binary_search_perplexity_xla(d2, perplexity)
    p, b = binary_search_perplexity_pallas(d2, perplexity)
    np.testing.assert_allclose(np.asarray(p), np.asarray(p_ref),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(b), np.asarray(b_ref), rtol=1e-5)
    # and the search itself converged: realized perplexity == target
    if k > perplexity:
        realized = np.asarray(bsp_ref.perplexity_of(p))
        np.testing.assert_allclose(realized, perplexity, rtol=1e-2)


def test_bsp_dispatch_and_validation():
    rng = np.random.default_rng(3)
    d2 = jnp.asarray(np.abs(rng.normal(size=(128, 24))).astype(np.float32))
    p_x, b_x = bsp_ref.binary_search_perplexity(d2, 7.0, impl="xla")
    p_p, b_p = bsp_ref.binary_search_perplexity(d2, 7.0, impl="pallas")
    np.testing.assert_allclose(np.asarray(p_p), np.asarray(p_x),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(b_p), np.asarray(b_x), rtol=1e-5)
    with pytest.raises(ValueError, match="unknown bsp impl"):
        bsp_ref.binary_search_perplexity(d2, 7.0, impl="numba")


def _planted_interp(n_boxes=4, n_ch=3):
    """Points sitting exactly on lattice nodes: one-hot Lagrange weights, so
    spread/gather are exact integer scatters with no float ambiguity."""
    nodes = n_boxes * (fft_ref.P_ORDER - 1) + 1
    rng = np.random.default_rng(0)
    n = 40
    base = rng.integers(0, n_boxes, size=(n, 2)).astype(np.int32) * 2
    taps = rng.integers(0, fft_ref.P_ORDER, size=(n, 2))
    wx = np.zeros((n, 3), np.float32)
    wy = np.zeros((n, 3), np.float32)
    wx[np.arange(n), taps[:, 0]] = 1.0
    wy[np.arange(n), taps[:, 1]] = 1.0
    charges = rng.integers(1, 5, size=(n, n_ch)).astype(np.float32)
    return nodes, jnp.asarray(base), jnp.asarray(wx), jnp.asarray(wy), \
        jnp.asarray(charges), taps


def test_spread_kernel_exact_on_planted_grid():
    nodes, base, wx, wy, charges, taps = _planted_interp()
    expected = np.zeros((nodes, nodes, 3), np.float32)
    b = np.asarray(base)
    for i in range(b.shape[0]):
        expected[b[i, 0] + taps[i, 0], b[i, 1] + taps[i, 1]] += np.asarray(charges)[i]
    ref = fft_ref.spread_to_grid(base, wx, wy, charges, nodes)
    out = spread_to_grid_pallas(base, wx, wy, charges, nodes)
    assert (np.asarray(ref) == expected).all()
    assert (np.asarray(out) == expected).all()


def test_gather_kernel_exact_on_planted_grid():
    nodes, base, wx, wy, _charges, taps = _planted_interp()
    rng = np.random.default_rng(1)
    pot = jnp.asarray(rng.integers(-9, 9, size=(nodes, nodes, 4)).astype(np.float32))
    b = np.asarray(base)
    expected = np.asarray(pot)[b[:, 0] + taps[:, 0], b[:, 1] + taps[:, 1]]
    ref = fft_ref.gather_from_grid(pot, base, wx, wy)
    out = gather_from_grid_pallas(pot, base, wx, wy)
    assert (np.asarray(ref) == expected).all()
    assert (np.asarray(out) == expected).all()


@pytest.mark.parametrize("n,n_boxes", [(50, 16), (700, 48), (1500, 64)])
def test_interp_kernels_match_ref(n, n_boxes):
    rng = np.random.default_rng(n)
    y = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32) * 5)
    nodes = n_boxes * (fft_ref.P_ORDER - 1) + 1
    base, wx, wy, _h = fft_ref.interp_coords(y, n_boxes)
    charges = jnp.stack([jnp.ones((n,), jnp.float32), y[:, 0], y[:, 1]], axis=1)
    g_ref = fft_ref.spread_to_grid(base, wx, wy, charges, nodes)
    g = spread_to_grid_pallas(base, wx, wy, charges, nodes)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)
    pot = jnp.asarray(rng.normal(size=(nodes, nodes, 4)).astype(np.float32))
    ph_ref = fft_ref.gather_from_grid(pot, base, wx, wy)
    ph = gather_from_grid_pallas(pot, base, wx, wy)
    np.testing.assert_allclose(np.asarray(ph), np.asarray(ph_ref),
                               rtol=1e-4, atol=1e-5)


def test_fft_repulsion_pallas_interp_matches_xla():
    rng = np.random.default_rng(9)
    y = jnp.asarray(rng.normal(size=(600, 2)).astype(np.float32) * 8)
    f_x, z_x = fft_ref.fft_repulsion(y, n_boxes=48, interp_impl="xla")
    f_p, z_p = fft_ref.fft_repulsion(y, n_boxes=48, interp_impl="pallas")
    scale = float(jnp.max(jnp.abs(f_x)))
    np.testing.assert_allclose(np.asarray(f_p), np.asarray(f_x),
                               rtol=1e-3, atol=1e-4 * scale)
    np.testing.assert_allclose(float(z_p), float(z_x), rtol=1e-4)
    with pytest.raises(ValueError, match="unknown interp impl"):
        fft_ref.fft_repulsion(y, n_boxes=48, interp_impl="cuda")


def test_kernel_registry_dispatch():
    from repro.kernels import ops
    names = ops.available_kernels()
    assert {"bsp_search", "fft_spread", "fft_gather",
            "attractive_ell", "pairwise_sq_dists", "morton_encode"} <= set(names)
    assert ops.get_kernel("bsp_search", "ref") is bsp_ref._binary_search_perplexity_xla
    assert ops.get_kernel("bsp_search", "pallas") is ops.binary_search_perplexity
    with pytest.raises(ValueError, match="unknown kernel"):
        ops.get_kernel("nope")
    with pytest.raises(ValueError, match="impl must be"):
        ops.get_kernel("bsp_search", "cuda")


def test_knn_with_pallas_pairwise_matches_xla():
    from repro.core.knn import knn
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(500, 16)).astype(np.float32))
    i1, d1 = knn(x, 10, pairwise_fn_name="xla")
    i2, d2 = knn(x, 10, pairwise_fn_name="pallas")
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-4, atol=1e-4)
    same = [set(np.asarray(i1)[r]) == set(np.asarray(i2)[r]) for r in range(500)]
    assert np.mean(same) > 0.99


def test_tsne_with_pallas_path_runs():
    from repro.core.tsne import TsneConfig, run_tsne
    rng = np.random.default_rng(11)
    x = rng.normal(size=(256, 10)).astype(np.float32)
    # use_pallas=True now routes the perplexity search too (bsp_impl="auto")
    cfg = TsneConfig(perplexity=8.0, n_iter=30, exaggeration_iters=10,
                     momentum_switch_iter=10, use_pallas=True, seed=3)
    res = run_tsne(x, cfg, kl_every=30)
    assert np.isfinite(res.y).all() and np.isfinite(res.kl)
    assert res.timings["bsp_impl"] == "pallas"


def test_tsne_fft_backend_with_pallas_interp_runs():
    from repro.core.tsne import TsneConfig, run_tsne
    rng = np.random.default_rng(12)
    x = rng.normal(size=(256, 10)).astype(np.float32)
    cfg = TsneConfig(perplexity=8.0, n_iter=16, exaggeration_iters=6,
                     momentum_switch_iter=6, method="fft", fft_n_boxes=16,
                     fft_interp_impl="pallas", bsp_impl="pallas", seed=3)
    res = run_tsne(x, cfg, kl_every=16)
    assert np.isfinite(res.y).all() and np.isfinite(res.kl)
    assert res.timings["bsp_impl"] == "pallas"


@pytest.mark.parametrize("backend,env,asked,expected", [
    ("tpu", "1", None, False),      # on a TPU the kernels always compile
    ("cpu", "1", None, True),       # the CPU suite asks for the interpreter
    ("cpu", None, None, False),     # nobody asked: request the compiled kernel
    ("cpu", None, True, True),
    ("cpu", "1", False, False),
])
def test_interpret_mode_is_decided_at_call_time(monkeypatch, backend, env,
                                                asked, expected):
    from repro.kernels import INTERPRET_ENV, interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if env is None:
        monkeypatch.delenv(INTERPRET_ENV, raising=False)
    else:
        monkeypatch.setenv(INTERPRET_ENV, env)
    assert interpret_mode(asked) is expected


def test_interpret_mode_is_refused_on_tpu(monkeypatch):
    from repro.kernels import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="never used on a TPU"):
        interpret_mode(True)
