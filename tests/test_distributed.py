"""Distributed paths on 8 forced host devices — run in a subprocess so the
main pytest process keeps its single-device view."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    return out.stdout


@pytest.mark.slow
def test_distributed_bh_gradient_matches_single_device():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import tsne, similarity, bsp
        from repro.core.knn import knn
        from repro.core.distributed import distributed_bh_gradient
        mesh = jax.make_mesh((8,), ("data",))
        n, k = 512, 12
        rng = np.random.default_rng(0)
        x = rng.normal(size=(n, 10)).astype(np.float32)
        idx, d2 = knn(jnp.asarray(x), k)
        cond_p, _ = bsp.binary_search_perplexity(d2, 4.0)
        cols, vals = similarity.symmetrize_ell(idx, cond_p)
        y = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
        ref = tsne.bh_gradient(y, jnp.asarray(cols), jnp.asarray(vals, jnp.float32),
                               None, theta=0.5, exaggeration=2.0, depth=16, p_logp=0.0)
        got = distributed_bh_gradient(mesh, y, jnp.asarray(cols),
                                      jnp.asarray(vals, jnp.float32), 0.0,
                                      theta=0.5, exaggeration=2.0)
        np.testing.assert_allclose(np.asarray(got.grad), np.asarray(ref.grad),
                                   rtol=2e-3, atol=1e-6)
        np.testing.assert_allclose(float(got.kl), float(ref.kl), rtol=1e-3)
        print("distributed gradient OK")
    """)


def test_ring_knn_matches_local():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.knn import knn
        from repro.core.distributed import ring_knn
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(640, 16)).astype(np.float32))
        i1, d1 = knn(x, 9)
        i2, d2 = ring_knn(mesh, x, 9)
        np.testing.assert_allclose(np.sort(np.asarray(d2), 1), np.sort(np.asarray(d1), 1),
                                   rtol=1e-4, atol=1e-4)
        same = [set(np.asarray(i1)[r]) == set(np.asarray(i2)[r]) for r in range(640)]
        assert np.mean(same) > 0.99
        print("ring knn OK")
    """)


def test_sharded_approx_recall_vs_exact_ring():
    """ISSUE 10 acceptance: the candidate ring's merged top-k must reach
    recall@k >= 0.90 against the exact ring oracle at small N."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.neighbors import make_neighbor_backend, recall_at_k
        rng = np.random.default_rng(7)
        n, k = 3000, 15
        x = jnp.asarray(rng.normal(size=(n, 16)).astype(np.float32))
        exact = make_neighbor_backend("sharded", dict(mode="exact", shards=8))
        ref_idx, ref_d2 = exact.neighbors(x, k)
        approx = make_neighbor_backend(
            "sharded", dict(shards=8, n_trees=8, leaf_size=32, block_rows=256))
        idx, d2 = approx.neighbors(x, k)
        ii = np.asarray(idx)
        assert ii.shape == (n, k)
        assert ((ii >= 0) & (ii < n)).all(), "pad/ghost index leaked"
        assert (ii != np.arange(n)[:, None]).all(), "self returned as neighbor"
        assert all(len(set(r)) == k for r in ii), "duplicate neighbor in a row"
        r = recall_at_k(ref_idx, idx)
        assert r >= 0.90, f"recall@{k} = {r:.3f} < 0.90"
        # exact mode through the same registry entry must agree with itself
        # across a non-dividing N (zero-pad path)
        i3, _ = exact.neighbors(x[: n - 1], k)
        assert np.asarray(i3).shape == (n - 1, k)
        assert (np.asarray(i3) < n - 1).all()
        print(f"sharded approx recall OK ({r:.3f})")
    """)


def test_sharded_refine_recovers_recall():
    """A one-tree ring misses most neighbors on other shards; refine_knn's
    rounds across shards must recover them without leaking pad, self or
    duplicate indices."""
    run_sub("""
        import jax.numpy as jnp, numpy as np
        from repro.neighbors import make_neighbor_backend, recall_at_k
        rng = np.random.default_rng(3)
        n, k = 3001, 15                        # 3001: 7 pad rows over 8 shards
        x = jnp.asarray(rng.normal(size=(n, 8)).astype(np.float32))
        ref_idx, _ = make_neighbor_backend(
            "sharded", dict(mode="exact", shards=8)).neighbors(x, k)
        opts = dict(shards=8, n_trees=1, leaf_size=16, block_rows=256)
        ring, _ = make_neighbor_backend(
            "sharded", dict(opts, refine_iters=0)).neighbors(x, k)
        idx, d2 = make_neighbor_backend(
            "sharded", dict(opts, refine_iters=5)).neighbors(x, k)
        ii = np.asarray(idx)
        assert ((ii >= 0) & (ii < n)).all(), "pad/ghost index leaked"
        assert (ii != np.arange(n)[:, None]).all(), "self returned as neighbor"
        assert all(len(set(r)) == k for r in ii), "duplicate neighbor in a row"
        assert (np.diff(np.asarray(d2), axis=1) >= 0).all(), "rows not sorted"
        r0, r1 = recall_at_k(ref_idx, ring), recall_at_k(ref_idx, idx)
        assert r0 < 0.6 and r1 >= 0.95, f"recall ring {r0:.3f} refined {r1:.3f}"
        print(f"refine recall OK ({r0:.3f} -> {r1:.3f})")
    """)


def test_sharded_preprocess_multi_device():
    """Chunked preprocess on the sharded backend: same graph invariants as
    the single-device path, across 8 forced devices."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.tsne import TsneConfig, preprocess
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.normal(size=(2048, 8)).astype(np.float32))
        cfg = TsneConfig(perplexity=10.0, neighbor_method="sharded",
                         knn_shards=8, chunk_size=500)
        graph, timings = preprocess(x, cfg)
        assert timings["neighbor_method"] == "sharded"
        assert timings["chunk_size"] == 500
        vals = np.asarray(graph.p_vals)
        assert np.isfinite(vals).all() and (vals >= 0).all()
        np.testing.assert_allclose(vals.sum(), 1.0, rtol=1e-4)
        print("sharded preprocess OK")
    """)


@pytest.mark.slow
def test_sharded_pipeline_100k_smoke():
    """Large-N smoke (CI's post-artifact step): ~100k points end-to-end
    through the sharded + chunked preprocessing path on 4 forced devices,
    plus a handful of fft gradient steps."""
    code = """
        import time, jax, jax.numpy as jnp, numpy as np
        from repro.api import make_backend
        from repro.core.tsne import TsneConfig, init_state, preprocess, tsne_step
        from repro.data.datasets import make_dataset
        n = 100_000
        assert len(jax.devices()) == 4, jax.devices()
        x, _ = make_dataset("mouse_1p3m", n=n)
        cfg = TsneConfig(perplexity=30.0, neighbor_method="sharded",
                         knn_shards=4, chunk_size=25_000, method="fft")
        graph, timings = preprocess(jnp.asarray(x), cfg)
        assert timings["neighbor_method"] == "sharded"
        assert timings["chunk_size"] == 25_000
        cols = np.asarray(graph.p_cols)
        assert ((cols >= 0) & (cols < n)).all()
        vals = np.asarray(graph.p_vals)
        assert np.isfinite(vals).all()
        np.testing.assert_allclose(vals.sum(), 1.0, rtol=1e-4)
        backend = make_backend(cfg.method, cfg, n)
        state = init_state(n, cfg)
        for _ in range(3):
            state, stats = tsne_step(
                state, graph, jnp.asarray(12.0, jnp.float32),
                jnp.asarray(0.5, jnp.float32), backend=backend,
                lr=cfg.resolve_lr(n), min_gain=cfg.min_gain)
        assert np.isfinite(np.asarray(state.y)).all()
        assert np.isfinite(float(stats.kl))
        print(f"100k smoke OK  knn={timings['knn']:.0f}s "
              f"bsp={timings['bsp']:.0f}s sym={timings['symmetrize']:.0f}s")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=3600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"


def test_compressed_psum_accuracy():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        from jax import shard_map
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
        exact = np.asarray(x).sum(0)
        got = shard_map(lambda v: compressed_psum(v[0], "data"),
                            mesh=mesh, in_specs=P("data"), out_specs=P(None),
                            check_vma=False)(x)
        scale = np.abs(x).max() / 127.0
        assert np.max(np.abs(np.asarray(got) - exact)) <= 8 * scale
        print("compressed psum OK")
    """)


def test_moe_ep_shard_map_matches_local():
    run_sub("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced_config
        from repro.distributed.sharding import use_mesh_rules, DEFAULT_RULES
        from repro.models.moe import init_moe, moe_block
        cfg = get_reduced_config("deepseek_v2_lite_16b")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        params = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model), jnp.float32)
        out_local, _ = moe_block(params, x, cfg)              # no mesh: local path
        with use_mesh_rules(mesh, DEFAULT_RULES):
            out_ep = jax.jit(lambda p, v: moe_block(p, v, cfg)[0])(params, x)
        np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_local),
                                   rtol=2e-4, atol=2e-5)
        print("moe ep OK")
    """)
