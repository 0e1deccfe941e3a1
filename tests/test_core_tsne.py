"""Core BH t-SNE correctness: every step validated against the exact oracle."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DEFAULT_DEPTH, attractive_forces_ell, bh_gradient,
    binary_search_perplexity, build_quadtree, knn, morton_encode,
    perplexity_of, sort_points_by_code, span_radius, summarize,
)
from repro.core import exact, similarity
from repro.core.bsp import binary_search_perplexity as bsp_search
from repro.core.repulsive import RepulsionResult, bh_repulsion_sorted
from repro.core.tsne import NeighborGraph, TsneConfig, run_tsne


def make_points(n, seed=0, clusters=4, dim=2, std=0.2):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(clusters, dim)) * 3.0
    lab = rng.integers(0, clusters, size=n)
    return (centers[lab] + rng.normal(size=(n, dim)) * std).astype(np.float32), lab


# ---------------------------------------------------------------- morton ----
class TestMorton:
    def test_known_example_from_paper(self):
        # paper fig. 2: dim0 = 3 (011b), dim1 = 7 (111b) -> morton 101111b = 47
        from repro.core.morton import expand_bits_u32
        mx = int(expand_bits_u32(jnp.uint32(3)))
        my = int(expand_bits_u32(jnp.uint32(7)))
        assert mx | (my << 1) == 47

    def test_encode_monotone_along_z_order(self):
        # points on a 4x4 grid follow the Z curve ordering of fig. 2
        depth = 2
        xs, ys = np.meshgrid(np.arange(4), np.arange(4), indexing="ij")
        pts = np.stack([xs.ravel(), ys.ravel()], -1).astype(np.float32) + 0.5
        cent = jnp.asarray([2.0, 2.0])
        r = jnp.asarray(2.0)
        codes = np.asarray(morton_encode(jnp.asarray(pts), cent, r, depth=depth))
        expect = np.zeros(16, np.uint32)
        for i, (x, y) in enumerate(pts):
            xi, yi = int(x), int(y)
            code = 0
            for b in range(2):
                code |= ((xi >> b) & 1) << (2 * b)
                code |= ((yi >> b) & 1) << (2 * b + 1)
            expect[i] = code
        assert (codes == expect).all()

    def test_locality(self):
        y, _ = make_points(512, seed=1)
        cent, r = span_radius(jnp.asarray(y))
        codes = morton_encode(jnp.asarray(y), cent, r)
        order = np.argsort(np.asarray(codes))
        ys = y[order]
        # consecutive points in Z order should be close on average
        dz = np.linalg.norm(np.diff(ys, axis=0), axis=1).mean()
        rng = np.random.default_rng(0)
        drand = np.linalg.norm(ys[rng.permutation(512)][:-1] - ys[rng.permutation(512)][1:], axis=1).mean()
        assert dz < 0.5 * drand


# -------------------------------------------------------------- quadtree ----
class TestQuadtree:
    @pytest.mark.parametrize("n", [1, 2, 7, 64, 500])
    @pytest.mark.parametrize("compress", [True, False])
    def test_tree_invariants(self, n, compress):
        y, _ = make_points(n, seed=n)
        yj = jnp.asarray(y)
        cent, r = span_radius(yj)
        codes = morton_encode(yj, cent, r)
        cs, ys, perm = sort_points_by_code(yj, codes)
        tree = build_quadtree(cs, compress=compress)
        nn = int(tree.n_nodes)
        cap = 2 * n + 1 if compress else 17 * n + 1
        assert 1 <= nn <= cap - 1
        start = np.asarray(tree.start)[:nn]
        end = np.asarray(tree.end)[:nn]
        level = np.asarray(tree.level)[:nn]
        skip = np.asarray(tree.skip)[:nn]
        # root covers everything
        assert start[0] == 0 and end[0] == n
        # DFS pre-order: starts non-decreasing; ranges laminar
        assert (np.diff(start) >= 0).all()
        for k in range(nn):
            assert 0 <= start[k] < end[k] <= n
            # skip points to first node at/after our end
            assert skip[k] <= nn
            if skip[k] < nn:
                assert start[skip[k]] >= end[k]
            # children immediately follow and are contained
            if skip[k] != k + 1 and k + 1 < nn:
                assert start[k + 1] >= start[k] and end[k + 1] <= end[k]
                assert level[k + 1] > level[k]

    def test_children_partition_parent(self):
        n = 300
        y, _ = make_points(n, seed=3)
        yj = jnp.asarray(y)
        cent, r = span_radius(yj)
        codes = morton_encode(yj, cent, r)
        cs, ys, _ = sort_points_by_code(yj, codes)
        tree = build_quadtree(cs)
        nn = int(tree.n_nodes)
        start = np.asarray(tree.start)[:nn]
        end = np.asarray(tree.end)[:nn]
        skip = np.asarray(tree.skip)[:nn]
        for k in range(nn):
            if skip[k] == k + 1:
                continue  # leaf
            # walk direct children via skip pointers: they partition [start, end)
            c = k + 1
            covered = start[k]
            while c < nn and start[c] < end[k]:
                assert start[c] == covered
                covered = end[c]
                c = skip[c]
            assert covered == end[k]

    def test_compressed_node_count_bound(self):
        n = 1000
        y, _ = make_points(n, seed=7)
        yj = jnp.asarray(y)
        cent, r = span_radius(yj)
        codes = morton_encode(yj, cent, r)
        cs, _, _ = sort_points_by_code(yj, codes)
        tree = build_quadtree(cs)
        assert int(tree.n_nodes) <= 2 * n - 1

    def test_duplicate_points(self):
        y = np.zeros((16, 2), np.float32)
        y[8:] = 1.0
        yj = jnp.asarray(y)
        cent, r = span_radius(yj)
        codes = morton_encode(yj, cent, r)
        cs, ys, _ = sort_points_by_code(yj, codes)
        tree = build_quadtree(cs)
        nn = int(tree.n_nodes)
        counts = np.asarray(tree.end - tree.start)[:nn]
        leaves = np.asarray(tree.is_leaf)[:nn]
        # two max-depth leaves of 8 coincident points each + root
        assert sorted(counts[leaves].tolist()) == [8, 8]


# -------------------------------------------------------------- summarize ---
class TestSummarize:
    def test_com_matches_bruteforce(self):
        n = 200
        y, _ = make_points(n, seed=5)
        yj = jnp.asarray(y)
        cent, r = span_radius(yj)
        codes = morton_encode(yj, cent, r)
        cs, ys, _ = sort_points_by_code(yj, codes)
        tree = build_quadtree(cs)
        summ = summarize(tree, ys, r)
        nn = int(tree.n_nodes)
        ysn = np.asarray(ys)
        for k in range(0, nn, 7):
            s, e = int(tree.start[k]), int(tree.end[k])
            np.testing.assert_allclose(
                np.asarray(summ.com[k]), ysn[s:e].mean(0), rtol=1e-4, atol=2e-5
            )
            assert float(summ.count[k]) == e - s


# -------------------------------------------------------------- repulsive ---
class TestRepulsive:
    def _bh_forces(self, y, theta):
        yj = jnp.asarray(y)
        cent, r = span_radius(yj)
        codes = morton_encode(yj, cent, r)
        cs, ys, perm = sort_points_by_code(yj, codes)
        tree = build_quadtree(cs)
        summ = summarize(tree, ys, r)
        rep = bh_repulsion_sorted(ys, tree, summ, theta)
        inv = np.empty(y.shape[0], np.int64)
        inv[np.asarray(perm)] = np.arange(y.shape[0])
        return np.asarray(rep.force)[inv], float(jnp.sum(rep.z_per_point))

    def test_theta_zero_is_exact(self):
        y, _ = make_points(150, seed=11)
        f_bh, z_bh = self._bh_forces(y, theta=0.0)
        f_ex, z_ex = exact.exact_repulsion(jnp.asarray(y))
        np.testing.assert_allclose(z_bh, float(z_ex), rtol=1e-4)
        np.testing.assert_allclose(f_bh, np.asarray(f_ex), rtol=2e-3, atol=1e-5)

    @pytest.mark.parametrize("theta", [0.2, 0.5, 0.8])
    def test_bh_approximation_quality(self, theta):
        y, _ = make_points(400, seed=13)
        f_bh, z_bh = self._bh_forces(y, theta)
        f_ex, z_ex = exact.exact_repulsion(jnp.asarray(y))
        f_ex = np.asarray(f_ex)
        rel_z = abs(z_bh - float(z_ex)) / float(z_ex)
        assert rel_z < 0.02 * max(theta, 0.1)
        denom = np.linalg.norm(f_ex, axis=1) + 1e-8
        rel_f = np.linalg.norm(f_bh - f_ex, axis=1) / denom
        # BH guarantee is on aggregate field accuracy; mean relative error
        assert rel_f.mean() < 0.05

    def test_coincident_points_no_nan(self):
        y = np.zeros((32, 2), np.float32)
        f, z = self._bh_forces(y, theta=0.5)
        assert np.isfinite(f).all() and np.isfinite(z)
        np.testing.assert_allclose(f, 0.0, atol=1e-6)
        # z = sum over ordered pairs of (1+0)^-1 = n(n-1)
        np.testing.assert_allclose(z, 32 * 31, rtol=1e-5)

    def test_auto_depth_matches_exact(self):
        from repro.core.morton import auto_depth
        y, _ = make_points(400, seed=211)
        depth = auto_depth(400)
        assert 6 <= depth < 16
        yj = jnp.asarray(y)
        cent, r = span_radius(yj)
        codes = morton_encode(yj, cent, r, depth=depth)
        cs, ys, perm = sort_points_by_code(yj, codes)
        tree = build_quadtree(cs, depth=depth)
        summ = summarize(tree, ys, r)
        rep = bh_repulsion_sorted(ys, tree, summ, 0.0)
        f_ex, z_ex = exact.exact_repulsion(ys)
        np.testing.assert_allclose(float(jnp.sum(rep.z_per_point)), float(z_ex), rtol=1e-3)
        # finite depth merges co-cell points: assert aggregate accuracy
        err = np.linalg.norm(np.asarray(rep.force) - np.asarray(f_ex), axis=1)
        ref = np.linalg.norm(np.asarray(f_ex), axis=1) + 1e-8
        assert np.mean(err / ref) < 0.02
        assert np.quantile(err / ref, 0.99) < 0.2

    def test_uncompressed_tree_same_forces(self):
        y, _ = make_points(200, seed=17)
        yj = jnp.asarray(y)
        cent, r = span_radius(yj)
        codes = morton_encode(yj, cent, r)
        cs, ys, _ = sort_points_by_code(yj, codes)
        f = {}
        for compress in (True, False):
            tree = build_quadtree(cs, compress=compress)
            summ = summarize(tree, ys, r)
            rep = bh_repulsion_sorted(ys, tree, summ, 0.0)
            f[compress] = np.asarray(rep.force)
        np.testing.assert_allclose(f[True], f[False], rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("theta", [0.0, 0.5])
    @pytest.mark.parametrize("case", ["clusters", "coincident", "duplicate_run"])
    def test_packed_walk_matches_field_gathers(self, case, theta):
        """The one-row walk does what a gather per node field did, exactly."""
        y, _ = make_points(203, seed=29)             # N not a multiple of 8
        if case == "coincident":
            y = np.zeros((37, 2), np.float32)
        elif case == "duplicate_run":
            # identical points and points a cell apart at depth 16 share a
            # code: max-depth leaves of many points, the query inside them
            y[40:70] = y[40]
            y[100:120] = y[100] + 1e-7 * np.arange(20, dtype=np.float32)[:, None]
        yj = jnp.asarray(y)
        cent, r = span_radius(yj)
        cs, ys, _ = sort_points_by_code(yj, morton_encode(yj, cent, r))
        tree = build_quadtree(cs)
        summ = summarize(tree, ys, r)
        if case == "duplicate_run":
            leaf_counts = np.asarray(summ.count)[np.asarray(tree.is_leaf)]
            assert leaf_counts.max() >= 20
        got = bh_repulsion_sorted(ys, tree, summ, theta)
        want = _field_gather_walk(ys, tree, summ, theta)
        np.testing.assert_array_equal(np.asarray(got.steps), np.asarray(want.steps))
        np.testing.assert_allclose(np.asarray(got.force), np.asarray(want.force),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(got.z_per_point),
                                   np.asarray(want.z_per_point), rtol=1e-6)

    def test_node_table_int_columns_exact_above_2_24(self):
        from repro.core.quadtree import LinearQuadtree
        from repro.core.repulsive import node_table
        from repro.core.summarize import TreeSummary

        big = np.array([2**24 + 1, 2**30 + 3, 2**31 - 1, 0], np.int32)
        assert int(np.float32(big[0])) != big[0]     # a converted float rounds
        skip = np.array([1, 3, 3, 2**31 - 1], np.int32)  # nodes 0 and 2 leaves
        tree = LinearQuadtree(start=jnp.asarray(big), end=jnp.asarray(big[::-1]),
                              level=jnp.zeros(4, jnp.int32), skip=jnp.asarray(skip),
                              n_nodes=jnp.int32(4), depth=16)
        side = jnp.asarray([1.5, 2.0, 3.0, 0.25], jnp.float32)
        summ = TreeSummary(count=jnp.arange(4, dtype=jnp.float32),
                           sum_y=jnp.ones((4, 2), jnp.float32),
                           com=jnp.ones((4, 2), jnp.float32), side=side)
        table = np.asarray(node_table(tree, summ))
        assert table.shape == (4, 8) and table.dtype == np.float32
        ints = table[:, :3].view(np.int32)
        np.testing.assert_array_equal(ints, np.stack([big, big[::-1], skip], 1))
        np.testing.assert_array_equal(table[:, 6], [-np.inf, 4.0, -np.inf, 0.0625])


def _field_gather_walk(y_sorted, tree, summary, theta):
    """The walk as it read each node field with a gather of its own."""
    n = y_sorted.shape[0]
    dtype = y_sorted.dtype
    theta2 = jnp.asarray(theta, dtype) ** 2
    n_nodes, cap, is_leaf = tree.n_nodes, tree.capacity, tree.is_leaf

    def traverse(p, yp):
        def body(state):
            ptr, force, z, steps = state
            k = jnp.minimum(ptr, cap - 1)
            s = tree.start[k]
            e = tree.end[k]
            cnt = summary.count[k]
            inside = (s <= p) & (p < e)
            cnt_eff = cnt - jnp.where(inside, jnp.asarray(1.0, dtype), 0.0)
            sum_eff = summary.sum_y[k] - jnp.where(inside, yp, jnp.zeros_like(yp))
            com = sum_eff / jnp.maximum(cnt_eff, 1.0)
            diff = yp - com
            d2 = jnp.sum(diff * diff)
            side = summary.side[k]
            open_ = (~is_leaf[k]) & (side * side >= theta2 * d2)
            w = jnp.where(open_, 0.0, cnt_eff)
            q = 1.0 / (1.0 + d2)
            z = z + w * q
            force = force + (w * q * q) * diff
            ptr = jnp.where(open_, ptr + 1, tree.skip[k])
            return ptr, force, z, steps + 1

        init = (jnp.int32(0), jnp.zeros((2,), dtype), jnp.asarray(0.0, dtype),
                jnp.int32(0))
        _, force, z, steps = jax.lax.while_loop(lambda st: st[0] < n_nodes,
                                                body, init)
        return force, z, steps

    force, z, steps = jax.jit(jax.vmap(traverse))(
        jnp.arange(n, dtype=jnp.int32), y_sorted)
    return RepulsionResult(force=force, z_per_point=z, steps=steps)


# -------------------------------------------------------------- attractive --
class TestAttractive:
    def test_ell_vs_dense_oracle(self):
        n, k = 128, 12
        x, _ = make_points(n, seed=19, dim=8)
        idx, d2 = knn(jnp.asarray(x), k)
        cond_p, _ = bsp_search(d2, 5.0)
        sym_cols, sym_vals = similarity.symmetrize_ell(idx, cond_p)
        p_dense = similarity.dense_p_matrix(idx, cond_p)
        y, _ = make_points(n, seed=23)
        f_ell, kl_ell = attractive_forces_ell(
            jnp.asarray(y), jnp.asarray(sym_cols), jnp.asarray(sym_vals, jnp.float32)
        )
        f_ex, kl_ex = exact.exact_attraction(jnp.asarray(y), jnp.asarray(p_dense, jnp.float32))
        np.testing.assert_allclose(np.asarray(f_ell), np.asarray(f_ex), rtol=1e-4, atol=1e-7)
        np.testing.assert_allclose(float(kl_ell), float(kl_ex), rtol=1e-4)


# ---------------------------------------------------------- degree buckets --
def _skewed_ell(seed=5):
    """A graph whose degrees spread widely: a tight cluster beside a wide one."""
    rng = np.random.default_rng(seed)
    x = np.concatenate([rng.normal(size=(260, 8)) * 0.1,
                        rng.normal(size=(240, 8)) * 3.0]).astype(np.float32)
    idx, d2 = knn(jnp.asarray(x), 12)
    cond_p, _ = bsp_search(d2, 5.0)
    sym_cols, sym_vals = similarity.symmetrize_ell(idx, cond_p)
    return x, idx, cond_p, sym_cols, sym_vals.astype(np.float32)


def _layout_leaves(buckets):
    return [np.asarray(a) for a in jax.tree.leaves(buckets)]


class TestDegreeBuckets:
    def test_bucketed_matches_ell_and_dense_oracle(self):
        from repro.core.attractive import attractive_forces_bucketed

        _, idx, cond_p, cols, vals = _skewed_ell()
        buckets = similarity.degree_buckets(cols, vals, block=64)
        assert len(buckets.cols) >= 3
        real = np.count_nonzero(cols != np.arange(len(cols))[:, None])
        assert real / buckets.slots > 1.5 * real / cols.size
        y = jnp.asarray(np.random.default_rng(7).normal(size=(500, 2)),
                        jnp.float32)
        f_b, kl_b = attractive_forces_bucketed(
            y, jax.tree.map(jnp.asarray, buckets))
        f_e, kl_e = attractive_forces_ell(y, jnp.asarray(cols),
                                          jnp.asarray(vals))
        p_dense = similarity.dense_p_matrix(idx, cond_p)
        f_x, kl_x = exact.exact_attraction(y, jnp.asarray(p_dense, jnp.float32))
        for f, kl in ((f_e, kl_e), (f_x, kl_x)):
            np.testing.assert_allclose(np.asarray(f_b), np.asarray(f),
                                       rtol=1e-5, atol=1e-8)
            np.testing.assert_allclose(float(kl_b), float(kl), rtol=1e-5)

    def test_bucketed_forces_are_the_whole_ell_loops_to_the_bit(self):
        """Each row is still summed over the ELL's whole width, so the forces
        round as the whole-ELL loop's do (on this backend, whose row sums do
        not depend on the block's row count)."""
        from repro.core.attractive import (
            attractive_forces_bucketed, attractive_forces_ell_blocked)

        _, _, _, cols, vals = _skewed_ell(seed=11)
        y = jnp.asarray(np.random.default_rng(13).normal(size=(500, 2)),
                        jnp.float32)
        buckets = similarity.degree_buckets(cols, vals, block=64)
        f_b, _ = jax.jit(attractive_forces_bucketed)(
            y, jax.tree.map(jnp.asarray, buckets))
        f_e, _ = jax.jit(attractive_forces_ell_blocked, static_argnums=3)(
            y, jnp.asarray(cols), jnp.asarray(vals), 64)
        np.testing.assert_array_equal(np.asarray(f_b), np.asarray(f_e))

    def test_uniform_degrees_give_one_bucket_equal_to_the_ell(self):
        n, k = 300, 6
        ring = (np.arange(n)[:, None] + np.arange(1, k + 1)) % n
        cols, vals = similarity.symmetrize_ell(ring, np.full((n, k), 1.0 / k))
        assert (cols != np.arange(n)[:, None]).all()       # every row full
        b = similarity.degree_buckets(cols, vals, block=512)
        assert len(b.cols) == 1
        assert b.cols[0].shape == (1, n + (-n) % 8, 2 * k)
        np.testing.assert_array_equal(b.cols[0].reshape(-1, 2 * k)[:n], cols)
        np.testing.assert_array_equal(b.vals[0].reshape(-1, 2 * k)[:n], vals)
        np.testing.assert_array_equal(b.rows[0].reshape(-1)[:n], np.arange(n))
        np.testing.assert_array_equal(b.inv, np.arange(n))

    @pytest.mark.parametrize("chunk", [40, 100])
    def test_turn_rows_respect_chunk_size(self, chunk):
        from repro.core.tsne import preprocess

        x, *_ = _skewed_ell()
        cfg = TsneConfig(perplexity=5.0, n_neighbors=12, chunk_size=chunk)
        graph, timings = preprocess(jnp.asarray(x), cfg)
        w_max = graph.p_cols.shape[1]
        block = cfg.resolve_attractive_block()
        for rows, cols in zip(graph.buckets.rows, graph.buckets.cols):
            t, r, w = cols.shape
            assert rows.shape == (t, r)
            assert r <= chunk and r * w <= block * w_max
        assert timings["attractive_buckets"] == len(graph.buckets.cols) > 1
        assert timings["attractive_slots"] == graph.buckets.slots
        assert 0.5 < timings["attractive_fill"] <= 1.0

    def test_layout_survives_the_chunked_symmetrize(self):
        from repro.core.tsne import preprocess

        x, idx, cond_p, cols, _ = _skewed_ell()
        cfg = TsneConfig(perplexity=5.0, n_neighbors=12, chunk_size=64)
        graph, _ = preprocess(jnp.asarray(x), cfg)
        sym_cols, sym_vals = similarity.symmetrize_ell(idx, cond_p)
        sym_vals = sym_vals / sym_vals.sum()
        ref = NeighborGraph.from_ell(
            sym_cols, sym_vals.astype(np.float32), 0.0, cfg).buckets
        got, want = _layout_leaves(graph.buckets), _layout_leaves(ref)
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)

    def test_layout_survives_save_and_load(self, tmp_path):
        from repro.api import TSNE

        x, *_ = _skewed_ell()
        est = TSNE(perplexity=5.0, n_iter=10, random_state=0).fit(x)
        est.save(tmp_path / "model.npz")
        loaded = TSNE.load(tmp_path / "model.npz")
        got = _layout_leaves(loaded.neighbor_graph_.buckets)
        want = _layout_leaves(est.neighbor_graph_.buckets)
        assert len(got) == len(want) > 3
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("edges_only", [False, True])
    def test_files_with_edge_list_keys_still_load(self, tmp_path, edges_only):
        """Files saved while the graph could carry a directed edge list hold
        its keys (``graph_edge_*``, ``graph_has_edges``).  A file from an
        edges-only fit holds [1, 1] dummy ELL planes: it loads without a
        graph, which ``transform`` does not need."""
        import json

        from repro.api import TSNE

        x, idx, cond_p, *_ = _skewed_ell()
        n, k = idx.shape
        est = TSNE(perplexity=5.0, n_iter=10, random_state=0).fit(x)
        est.save(tmp_path / "model.npz")
        with np.load(tmp_path / "model.npz") as z:
            arrays = dict(z)
        if edges_only:
            params = json.loads(str(arrays["params_json"]))
            params["backend_options"] = {"attractive_impl": "edges"}
            arrays.update(
                params_json=np.array(json.dumps(params)),
                graph_p_cols=np.zeros((1, 1), np.int32),
                graph_p_vals=np.zeros((1, 1), np.float32),
                graph_edge_src=np.repeat(np.arange(n, dtype=np.int32), k),
                graph_edge_dst=np.asarray(idx, np.int32).reshape(-1),
                graph_edge_w=np.asarray(cond_p, np.float32).reshape(-1) / (2 * n))
        else:
            arrays.update(graph_edge_src=np.zeros(1, np.int32),
                          graph_edge_dst=np.zeros(1, np.int32),
                          graph_edge_w=np.zeros(1, np.float32))
        arrays["graph_has_edges"] = np.bool_(edges_only)
        np.savez_compressed(tmp_path / "old.npz", **arrays)

        loaded = TSNE.load(tmp_path / "old.npz")
        if edges_only:
            assert loaded.neighbor_graph_ is None
        else:
            got = _layout_leaves(loaded.neighbor_graph_)
            want = _layout_leaves(TSNE.load(tmp_path / "model.npz")
                                  .neighbor_graph_)
            assert len(got) == len(want) > 5
            for a, b in zip(got, want):
                assert a.dtype == b.dtype and a.shape == b.shape
                np.testing.assert_array_equal(a, b)
        y = loaded.transform(x[:20])
        assert y.shape == (20, 2) and np.isfinite(y).all()

    @pytest.mark.parametrize("method", ["barnes_hut", "fft"])
    def test_step_runs_the_buckets(self, method, monkeypatch):
        """The step reads the buckets, not the padded ELL, and its gradient
        is the whole-ELL loop's."""
        import dataclasses

        from repro.api.backends import make_backend
        from repro.core import attractive
        from repro.core.tsne import init_state, preprocess, tsne_step

        x, *_ = _skewed_ell()
        cfg = TsneConfig(perplexity=5.0, n_neighbors=12, method=method,
                         fft_n_boxes=8)
        graph, _ = preprocess(jnp.asarray(x), cfg)
        args = (init_state(500, cfg), graph, jnp.float32(12.0),
                jnp.float32(0.5))
        kw = dict(backend=make_backend(method, cfg, 500), lr=10.0,
                  min_gain=0.01)
        _, with_buckets = tsne_step(*args, **kw)
        # a graph whose ELL is all padding: the forces come from the buckets
        _, buckets_only = tsne_step(args[0], dataclasses.replace(
            graph, p_vals=jnp.zeros_like(graph.p_vals)), *args[2:], **kw)
        # the same step with the whole-ELL loop in the buckets' place (a
        # new function, so that it is traced anew)
        monkeypatch.setattr(
            attractive, "attractive_forces_bucketed",
            lambda y, _: attractive.attractive_forces_ell_blocked(
                y, graph.p_cols, graph.p_vals))
        _, whole_ell = jax.jit(
            lambda *a: tsne_step.__wrapped__(*a, **kw))(*args)
        for other in (whole_ell, buckets_only):
            np.testing.assert_allclose(float(with_buckets.kl),
                                       float(other.kl), rtol=1e-5)
            np.testing.assert_allclose(float(with_buckets.grad_norm),
                                       float(other.grad_norm), rtol=1e-5)


# --------------------------------------------------------------------- bsp --
class TestBSP:
    @pytest.mark.parametrize("perplexity", [5.0, 15.0, 30.0])
    def test_perplexity_reached(self, perplexity):
        n, k = 256, int(3 * perplexity)
        x, _ = make_points(n, seed=37, dim=10)
        idx, d2 = knn(jnp.asarray(x), k)
        cond_p, beta = binary_search_perplexity(d2, perplexity)
        perp = np.asarray(perplexity_of(cond_p))
        np.testing.assert_allclose(perp, perplexity, rtol=1e-2)
        assert (np.asarray(beta) > 0).all()
        np.testing.assert_allclose(np.asarray(cond_p).sum(1), 1.0, rtol=1e-5)


# --------------------------------------------------------------------- knn --
class TestKNN:
    @pytest.mark.parametrize("n,dim,k", [(100, 4, 5), (1000, 16, 15), (257, 20, 7)])
    def test_matches_bruteforce(self, n, dim, k):
        rng = np.random.default_rng(41)
        x = rng.normal(size=(n, dim)).astype(np.float32)
        idx, d2 = knn(jnp.asarray(x), k)
        d = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
        np.fill_diagonal(d, np.inf)
        ref_idx = np.argsort(d, axis=1)[:, :k]
        ref_d = np.take_along_axis(d, ref_idx, axis=1)
        np.testing.assert_allclose(np.sort(np.asarray(d2), 1), np.sort(ref_d, 1), rtol=1e-3, atol=1e-4)
        # index sets must match (distance ties allowed)
        same = [set(np.asarray(idx)[i]) == set(ref_idx[i]) for i in range(n)]
        assert np.mean(same) > 0.99

    def test_no_self_neighbor(self):
        x = np.random.default_rng(43).normal(size=(300, 8)).astype(np.float32)
        idx, _ = knn(jnp.asarray(x), 10)
        assert not (np.asarray(idx) == np.arange(300)[:, None]).any()


# -------------------------------------------------------- full BH gradient --
class TestGradient:
    def test_bh_gradient_matches_exact(self):
        n, k, perp = 200, 24, 8.0
        x, _ = make_points(n, seed=47, dim=12)
        idx, d2 = knn(jnp.asarray(x), k)
        cond_p, _ = bsp_search(d2, perp)
        sym_cols, sym_vals = similarity.symmetrize_ell(idx, cond_p)
        p_dense = similarity.dense_p_matrix(idx, cond_p)
        y, _ = make_points(n, seed=53)
        graph = NeighborGraph.from_ell(
            sym_cols, sym_vals.astype(np.float32), 0.0, TsneConfig())
        res = bh_gradient(jnp.asarray(y), graph, theta=0.0, exaggeration=1.0,
                          depth=DEFAULT_DEPTH)
        g_ex = exact.exact_gradient(jnp.asarray(y), jnp.asarray(p_dense, jnp.float32))
        np.testing.assert_allclose(np.asarray(res.grad), np.asarray(g_ex), rtol=5e-3, atol=1e-6)

    def test_kl_estimate_matches_exact(self):
        n, k, perp = 150, 15, 5.0
        x, _ = make_points(n, seed=59, dim=12)
        idx, d2 = knn(jnp.asarray(x), k)
        cond_p, _ = bsp_search(d2, perp)
        sym_cols, sym_vals = similarity.symmetrize_ell(idx, cond_p)
        p_dense = similarity.dense_p_matrix(idx, cond_p)
        pv = sym_vals[sym_vals > 0]
        p_logp = float((pv * np.log(pv)).sum())
        y, _ = make_points(n, seed=61)
        graph = NeighborGraph.from_ell(
            sym_cols, sym_vals.astype(np.float32), p_logp, TsneConfig())
        res = bh_gradient(jnp.asarray(y), graph, theta=0.0, exaggeration=1.0,
                          depth=DEFAULT_DEPTH)
        kl_ex = exact.exact_kl(jnp.asarray(y), jnp.asarray(p_dense, jnp.float32))
        np.testing.assert_allclose(float(res.kl), float(kl_ex), rtol=1e-3)


# ------------------------------------------------------------- end-to-end ---
class TestEndToEnd:
    def test_tsne_separates_clusters(self):
        n = 600
        x, lab = make_points(n, seed=67, clusters=3, dim=20, std=0.15)
        cfg = TsneConfig(perplexity=15.0, n_iter=300, exaggeration_iters=100,
                         momentum_switch_iter=100, seed=1)
        res = run_tsne(x, cfg, kl_every=100)
        assert np.isfinite(res.y).all()
        assert np.isfinite(res.kl)
        # KL decreased over the run
        assert res.kl_history[-1, 1] <= res.kl_history[0, 1] + 1e-3
        # cluster separation: mean intra-cluster dist << inter-cluster dist
        y = res.y
        intra, inter = [], []
        for c in range(3):
            m = y[lab == c]
            intra.append(np.linalg.norm(m - m.mean(0), axis=1).mean())
        cents = np.stack([y[lab == c].mean(0) for c in range(3)])
        for i in range(3):
            for j in range(i + 1, 3):
                inter.append(np.linalg.norm(cents[i] - cents[j]))
        assert np.mean(intra) < 0.5 * np.mean(inter)


# --------------------------------------------------- step layers, counters ---
def _numpy_walk(y_s, tree, summ, theta):
    """Each point's turns of the rope walk, step for step in NumPy."""
    start, end = np.asarray(tree.start), np.asarray(tree.end)
    skip, n_nodes = np.asarray(tree.skip), int(tree.n_nodes)
    is_leaf = skip == np.arange(skip.shape[0]) + 1
    count, sum_y = np.asarray(summ.count), np.asarray(summ.sum_y)
    side = np.asarray(summ.side)
    theta2 = np.float32(theta) ** 2
    steps = []
    for p, yp in enumerate(np.asarray(y_s)):
        ptr = turns = 0
        while ptr < n_nodes:
            inside = start[ptr] <= p < end[ptr]
            cnt = count[ptr] - np.float32(inside)
            com = (sum_y[ptr] - (yp if inside else 0)) / max(cnt, 1)
            d2 = np.sum((yp - com) ** 2)
            opened = not is_leaf[ptr] and side[ptr] ** 2 >= theta2 * d2
            ptr = ptr + 1 if opened else skip[ptr]
            turns += 1
        steps.append(turns)
    return np.asarray(steps)


class TestStepLayers:
    @pytest.mark.parametrize("method,scoped", [
        ("barnes_hut", ("bh_tree", "bh_summarize", "bh_traversal",
                        "attractive", "update")),
        ("fft", ("fft_spread", "fft_convolve", "fft_gather", "attractive",
                 "update")),
    ])
    def test_scopes_in_lowered_step(self, method, scoped):
        from repro.api.backends import make_backend
        from repro.core import scopes
        from repro.core.tsne import init_state, preprocess, tsne_step

        x, _ = make_points(120, seed=3, dim=8)
        cfg = TsneConfig(perplexity=8.0, method=method, fft_n_boxes=8)
        graph, _ = preprocess(jnp.asarray(x), cfg)
        text = tsne_step.lower(
            init_state(120, cfg), graph, jnp.float32(12.0), jnp.float32(0.5),
            backend=make_backend(method, cfg, 120), lr=10.0, min_gain=0.01,
        ).as_text(debug_info=True)
        assert set(scoped) <= set(scopes.STEP_SCOPES)
        for name in scopes.STEP_SCOPES:
            # a scope heads or continues an op's name path
            found = re.search(rf'["/]{name}/', text) is not None
            assert found == (name in scoped), name

    @pytest.mark.parametrize("seed", [5, 6])
    def test_walk_counters_match_numpy_walk(self, seed):
        theta = 0.5
        y, _ = make_points(160, seed=seed)
        yj = jnp.asarray(y)
        cent, r = span_radius(yj)
        cs, ys, _ = sort_points_by_code(yj, morton_encode(yj, cent, r))
        tree = build_quadtree(cs)
        turns = _numpy_walk(ys, tree, summarize(tree, ys, r), theta)
        graph = NeighborGraph.from_ell(
            np.arange(160, dtype=np.int32)[:, None],
            np.zeros((160, 1), np.float32), 0.0, TsneConfig())
        res = bh_gradient(yj, graph, theta=theta, exaggeration=1.0,
                          depth=DEFAULT_DEPTH)
        assert int(res.max_traversal) == turns.max()
        np.testing.assert_allclose(float(res.mean_traversal), turns.mean(),
                                   rtol=1e-6)
        assert 0 < float(res.mean_traversal) < int(res.max_traversal)

    def test_checkpoints_report_the_walk(self):
        from repro.api import TSNE

        x, _ = make_points(150, seed=7, dim=8)
        seen = {}
        for method in ("barnes_hut", "fft"):
            stats = []
            est = TSNE(method=method, perplexity=8.0, n_iter=20, kl_every=10,
                       random_state=0, backend_options={"fft_n_boxes": 8},
                       callbacks=(stats.append,)).fit(x)
            assert [s.iteration for s in stats] == [10, 20]
            assert est.timings_["max_traversal"] == \
                [s.max_traversal for s in stats]
            assert est.timings_["mean_traversal"] == \
                [s.mean_traversal for s in stats]
            seen[method] = stats
        assert all(0 < s.mean_traversal <= s.max_traversal
                   for s in seen["barnes_hut"])
        assert all(s.max_traversal == 0 and s.mean_traversal == 0
                   for s in seen["fft"])

    def test_checkpoints_report_the_fft_span(self):
        from repro.api import TSNE

        x, _ = make_points(150, seed=7, dim=8)
        spans = {}
        for method in ("barnes_hut", "fft"):
            stats = []
            est = TSNE(method=method, perplexity=8.0, n_iter=40, kl_every=10,
                       random_state=0, backend_options={"fft_n_boxes": 8},
                       callbacks=(stats.append,)).fit(x)
            assert est.timings_["fft_span"] == [s.fft_span for s in stats]
            spans[method] = est.timings_["fft_span"]
        assert spans["barnes_hut"] == [0.0] * 4
        # the lattice's side: the embedding's widest extent, which early
        # exaggeration spreads out
        fft = spans["fft"]
        assert len(fft) == 4 and 0 < fft[0] < fft[-1]
        assert fft[-1] == pytest.approx(
            float(np.ptp(est.embedding_, axis=0).max()), rel=0.2)
