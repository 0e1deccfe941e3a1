"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st
from hypothesis.extra import numpy as hnp

from repro.core import (
    binary_search_perplexity, build_quadtree, morton_encode, perplexity_of,
    sort_points_by_code, span_radius, summarize,
)
from repro.core import exact, similarity
from repro.core.attractive import attractive_forces_bucketed
from repro.core.morton import morton_decode_cell
from repro.core.repulsive import bh_repulsion_sorted

SETTINGS = dict(max_examples=15, deadline=None)


def finite_points(min_n=2, max_n=120):
    return hnp.arrays(
        np.float32,
        st.tuples(st.integers(min_n, max_n), st.just(2)),
        elements=st.floats(-100, 100, width=32, allow_nan=False, allow_infinity=False),
    )


@given(y=finite_points())
@settings(**SETTINGS)
def test_morton_roundtrip_cells(y):
    yj = jnp.asarray(y)
    cent, r = span_radius(yj)
    codes = morton_encode(yj, cent, r)
    cx, cy = morton_decode_cell(codes, level=16)
    # decoded integer cells must equal direct quantization
    y_root = np.asarray(cent) - float(r)
    scale = (2.0**15) / float(r)
    q = np.clip(((y - y_root) * scale), 0, 2**16 - 1).astype(np.uint32)
    assert (np.asarray(cx) == q[:, 0]).all()
    assert (np.asarray(cy) == q[:, 1]).all()


@given(y=finite_points())
@settings(**SETTINGS)
def test_quadtree_laminar_and_partition(y):
    yj = jnp.asarray(y)
    n = y.shape[0]
    cent, r = span_radius(yj)
    codes = morton_encode(yj, cent, r)
    cs, ys, _ = sort_points_by_code(yj, codes)
    tree = build_quadtree(cs)
    nn = int(tree.n_nodes)
    start = np.asarray(tree.start)[:nn]
    end = np.asarray(tree.end)[:nn]
    skip = np.asarray(tree.skip)[:nn]
    assert 1 <= nn <= 2 * n
    assert start[0] == 0 and end[0] == n
    assert (start < end).all()
    # laminar: any two ranges are nested or disjoint
    for k in range(1, min(nn, 40)):
        a = (start[k], end[k])
        b = (start[k - 1], end[k - 1])
        nested = (b[0] <= a[0] and a[1] <= b[1]) or (a[0] <= b[0] and b[1] <= a[1])
        disjoint = a[1] <= b[0] or b[1] <= a[0]
        assert nested or disjoint
    # skip pointers are strictly forward and range-consistent
    ks = np.arange(nn)
    assert (skip > ks).all()
    valid = skip < nn
    assert (start[skip[valid]] >= end[valid]).all()


@given(y=finite_points(min_n=3))
@settings(**SETTINGS)
def test_exact_repulsion_newton_third_law(y):
    f, z = exact.exact_repulsion(jnp.asarray(y))
    assert float(z) >= 0
    np.testing.assert_allclose(np.asarray(f).sum(0), 0.0, atol=1e-3)


@given(y=finite_points(min_n=4, max_n=80))
@settings(**SETTINGS)
def test_bh_matches_exact_at_theta_zero(y):
    # dedup: coincident points are fine but make relative comparison noisy
    yj = jnp.asarray(y)
    cent, r = span_radius(yj)
    codes = morton_encode(yj, cent, r)
    cs, ys, perm = sort_points_by_code(yj, codes)
    tree = build_quadtree(cs)
    summ = summarize(tree, ys, r)
    rep = bh_repulsion_sorted(ys, tree, summ, 0.0)
    f_ex, z_ex = exact.exact_repulsion(ys)
    np.testing.assert_allclose(float(jnp.sum(rep.z_per_point)), float(z_ex), rtol=5e-3, atol=1e-4)
    # float32 prefix-sum noise scales with coordinate magnitude
    atol = 1e-5 * (1.0 + float(np.abs(y).max()))
    np.testing.assert_allclose(np.asarray(rep.force), np.asarray(f_ex), rtol=2e-2, atol=atol)


@given(
    n=st.integers(8, 64),
    k=st.integers(2, 7),
    seed=st.integers(0, 2**31 - 1),
)
@settings(**SETTINGS)
def test_attractive_bucketed_antisymmetry(n, k, seed):
    """Over a symmetric P the bucketed loop's forces sum to zero."""
    rng = np.random.default_rng(seed)
    y = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    cols = (rng.integers(1, n, size=(n, k)) + np.arange(n)[:, None]) % n
    cols, vals = similarity.symmetrize_ell(
        cols, rng.uniform(0, 1, size=(n, k)))
    vals = (vals * 2 * n).astype(np.float32)    # p_{j|i} + p_{i|j}, unscaled
    buckets = similarity.degree_buckets(cols, vals, block=8)
    f, _ = attractive_forces_bucketed(y, jax.tree.map(jnp.asarray, buckets))
    np.testing.assert_allclose(np.asarray(f).sum(0), 0.0, atol=1e-3)


@given(
    n=st.integers(4, 60),
    k=st.integers(3, 16),
    perp=st.floats(2.0, 5.0),
    seed=st.integers(0, 2**31 - 1),
)
@settings(**SETTINGS)
def test_bsp_reaches_target_perplexity(n, k, perp, seed):
    perp = min(perp, k * 0.9)
    rng = np.random.default_rng(seed)
    d2 = jnp.asarray(np.sort(rng.uniform(0.01, 10, size=(n, k)), axis=1).astype(np.float32))
    cond_p, beta = binary_search_perplexity(d2, perp)
    got = np.asarray(perplexity_of(cond_p))
    np.testing.assert_allclose(got, perp, rtol=5e-2)
    assert (np.asarray(beta) > 0).all()


@given(y=finite_points(min_n=10, max_n=100), shift=st.floats(-50, 50))
@settings(**SETTINGS)
def test_bh_translation_invariance(y, shift):
    """BH repulsive forces are invariant to translating the embedding."""
    def forces(yy):
        yj = jnp.asarray(yy)
        cent, r = span_radius(yj)
        codes = morton_encode(yj, cent, r)
        cs, ys, perm = sort_points_by_code(yj, codes)
        tree = build_quadtree(cs)
        summ = summarize(tree, ys, r)
        rep = bh_repulsion_sorted(ys, tree, summ, 0.5)
        out = np.zeros_like(yy)
        out[np.asarray(perm)] = np.asarray(rep.force)
        return out

    f0 = forces(y)
    f1 = forces(y + np.float32(shift))
    # degenerate duplicate clusters amplify one-ulp COM noise by the cluster
    # count, so the absolute tolerance scales with N * |y| * eps
    atol = max(5e-4, 2e-7 * (1.0 + float(np.abs(y).max()) + abs(shift)) * y.shape[0])
    np.testing.assert_allclose(f0, f1, rtol=5e-2, atol=atol)
