"""Public API surface: backend registry, backend equivalence, sklearn parity."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    TSNE, BarnesHutBackend, ExactBackend, FFTBackend, IterationStats,
    TsneConfig, available_backends, make_backend, preprocess, register_backend,
    run_tsne, unregister_backend,
)
from repro.core.tsne import DEFAULT_ATTRACTIVE_IMPL
from repro.data.datasets import make_dataset


def make_points(n, seed=0, clusters=4, dim=2, std=0.2):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(clusters, dim)) * 3.0
    lab = rng.integers(0, clusters, size=n)
    return (centers[lab] + rng.normal(size=(n, dim)) * std).astype(np.float32), lab


@pytest.fixture(scope="module")
def small_graph():
    x, _ = make_points(256, seed=5, clusters=3, dim=10)
    cfg = TsneConfig(perplexity=10.0)
    graph, _ = preprocess(jnp.asarray(x), cfg)
    return cfg, graph


# ------------------------------------------------------------- registry -----
class TestRegistry:
    def test_builtins_registered(self):
        assert {"exact", "barnes_hut", "fft"} <= set(available_backends())

    def test_unknown_method_raises(self):
        with pytest.raises(ValueError, match="unknown t-SNE method"):
            make_backend("nope", TsneConfig(), 100)
        with pytest.raises(ValueError, match="unknown t-SNE method"):
            TSNE(method="nope", perplexity=5.0).fit(make_points(64)[0])

    def test_config_flows_into_backend(self):
        cfg = TsneConfig(theta=0.3, compress_tree=False, depth="auto",
                         fft_n_boxes=96)
        bh = make_backend("barnes_hut", cfg, 4096)
        assert bh.theta == 0.3 and not bh.compress_tree
        assert isinstance(bh.depth, int) and bh.depth >= 1
        assert make_backend("fft", cfg, 4096).n_boxes == 96

    def test_custom_backend_registration(self):
        class TaggedExact(ExactBackend):
            name = "tagged_exact"

        register_backend("tagged_exact", lambda cfg, n: TaggedExact())
        try:
            assert "tagged_exact" in available_backends()
            x, _ = make_points(96, seed=9, dim=6)
            est = TSNE(method="tagged_exact", perplexity=8.0, n_iter=60,
                       kl_every=30)
            emb = est.fit_transform(x)
            assert emb.shape == (96, 2) and np.isfinite(emb).all()
        finally:
            unregister_backend("tagged_exact")
        assert "tagged_exact" not in available_backends()

    def test_attractive_impl_single_source_of_truth(self):
        # satellite: config and backend defaults must agree
        assert TsneConfig().attractive_impl == DEFAULT_ATTRACTIVE_IMPL
        assert BarnesHutBackend().attractive_impl == DEFAULT_ATTRACTIVE_IMPL
        assert FFTBackend().attractive_impl == DEFAULT_ATTRACTIVE_IMPL
        cfg = TsneConfig()
        assert make_backend("barnes_hut", cfg, 100).attractive_impl \
            == cfg.attractive_impl


# -------------------------------------------------- backend equivalence -----
class TestBackendEquivalence:
    def test_barnes_hut_theta0_matches_exact(self, small_graph):
        cfg, graph = small_graph
        y = jnp.asarray(make_points(graph.n, seed=7)[0])
        ex = ExactBackend().gradient(y, graph, 1.0)
        bh = dataclasses.replace(
            make_backend("barnes_hut", cfg, graph.n), theta=0.0
        ).gradient(y, graph, 1.0)
        np.testing.assert_allclose(np.asarray(bh.grad), np.asarray(ex.grad),
                                   rtol=5e-3, atol=1e-6)
        np.testing.assert_allclose(float(bh.kl), float(ex.kl), rtol=1e-3)
        np.testing.assert_allclose(float(bh.z), float(ex.z), rtol=1e-3)

    def test_fft_close_to_exact(self, small_graph):
        cfg, graph = small_graph
        y = jnp.asarray(make_points(graph.n, seed=7)[0])
        ex = ExactBackend().gradient(y, graph, 1.0)
        ft = FFTBackend(n_boxes=64).gradient(y, graph, 1.0)
        np.testing.assert_allclose(float(ft.z), float(ex.z), rtol=2e-2)
        np.testing.assert_allclose(float(ft.kl), float(ex.kl), rtol=2e-2)
        err = np.linalg.norm(np.asarray(ft.grad) - np.asarray(ex.grad), axis=1)
        ref = np.linalg.norm(np.asarray(ex.grad), axis=1) + 1e-8
        assert np.mean(err / ref) < 0.05

    def test_exaggeration_scales_attractive_only(self, small_graph):
        cfg, graph = small_graph
        y = jnp.asarray(make_points(graph.n, seed=7)[0])
        for backend in (ExactBackend(), make_backend("barnes_hut", cfg, graph.n),
                        FFTBackend()):
            g1 = backend.gradient(y, graph, 1.0)
            g2 = backend.gradient(y, graph, 4.0)
            # grad = 4 (exag * F_attr - F_rep): exag enters affinely
            f_attr = (np.asarray(g2.grad) - np.asarray(g1.grad)) / (4.0 * 3.0)
            assert np.isfinite(f_attr).all()
            assert np.abs(f_attr).max() > 0


# ------------------------------------------------------- sklearn parity -----
class TestEstimator:
    @pytest.mark.parametrize("method", ["exact", "barnes_hut", "fft"])
    def test_fit_transform_digits(self, method):
        x, _ = make_dataset("digits", n=300)
        est = TSNE(method=method, perplexity=12.0, n_iter=120, kl_every=60,
                   random_state=3)
        emb = est.fit_transform(x)
        assert emb.shape == (300, 2)
        assert np.isfinite(emb).all()
        assert np.isfinite(est.kl_divergence_)
        assert est.n_iter_ == 120
        assert est.embedding_ is emb
        assert est.n_features_in_ == x.shape[1]
        # learning_rate='auto' = max(N / early_exaggeration, 50)
        assert est.learning_rate_ == max(300 / 12.0, 50.0)

    def test_methods_agree_on_digits(self):
        # fits part ways with float summation order inside early
        # exaggeration, so agreement is judged on one gradient at a fixed
        # embedding, under the bounds the chip smoke's reference phase uses
        from repro.api.reference import (
            REFERENCE_BOUNDS, gradient_error, reference_embedding,
        )
        x, _ = make_dataset("digits", n=300)
        cfg = TsneConfig(perplexity=12.0)
        graph, _ = preprocess(jnp.asarray(x), cfg)
        y = reference_embedding(x)
        for method, (_, bound) in REFERENCE_BOUNDS.items():
            rel, dkl = gradient_error(method, y, graph, cfg)
            assert rel < bound.rel_grad, (method, rel, bound.reason)
            assert dkl < bound.abs_kl, (method, dkl, bound.reason)

    def test_backend_instance_as_method(self):
        x, _ = make_points(128, seed=21, dim=8)
        est = TSNE(method=FFTBackend(n_boxes=32), perplexity=8.0, n_iter=60,
                   kl_every=30)
        emb = est.fit_transform(x)
        assert emb.shape == (128, 2) and np.isfinite(emb).all()
        # settings that a backend instance would silently ignore must raise
        with pytest.raises(ValueError, match="backend_options have no effect"):
            TSNE(method=FFTBackend(), perplexity=8.0,
                 backend_options={"fft_n_boxes": 96}).fit(x)
        with pytest.raises(ValueError, match="angle= has no effect"):
            TSNE(method=BarnesHutBackend(), perplexity=8.0, angle=0.8).fit(x)

    def test_callbacks_receive_iteration_stats(self):
        x, _ = make_points(200, seed=33, dim=8)
        seen = []
        est = TSNE(perplexity=10.0, n_iter=90, kl_every=30,
                   callbacks=[seen.append])
        est.fit(x)
        assert [s.iteration for s in seen] == [30, 60, 90]
        for s in seen:
            assert isinstance(s, IterationStats)
            assert np.isfinite(s.kl) and np.isfinite(s.grad_norm)
            assert s.z > 0 and s.max_traversal >= 0 and s.elapsed_s >= 0

    def test_min_grad_norm_early_stopping(self):
        x, _ = make_points(200, seed=33, dim=8)
        est = TSNE(perplexity=10.0, n_iter=400, kl_every=25, min_grad_norm=1e9)
        est.fit(x)
        assert est.n_iter_ == 25  # stops at the first convergence check

    def test_validation_errors(self):
        x, _ = make_points(64, seed=1)
        with pytest.raises(ValueError, match="2 dimensions"):
            TSNE(n_components=3, perplexity=5.0).fit(x)
        with pytest.raises(ValueError, match="perplexity"):
            TSNE(perplexity=50.0).fit(x)
        with pytest.raises(ValueError, match="2-D"):
            TSNE(perplexity=5.0).fit(x[:, 0])

    def test_get_set_params_roundtrip(self):
        est = TSNE(perplexity=17.0, method="fft")
        params = est.get_params()
        assert params["perplexity"] == 17.0 and params["method"] == "fft"
        est.set_params(perplexity=9.0)
        assert est.perplexity == 9.0
        with pytest.raises(ValueError, match="invalid parameter"):
            est.set_params(bogus=1)


# ------------------------------------------------------------- run_tsne -----
class TestRunTsne:
    def test_backend_override(self):
        x, _ = make_points(150, seed=41, dim=6)
        cfg = TsneConfig(perplexity=8.0, n_iter=60, exaggeration_iters=30,
                         momentum_switch_iter=30)
        res = run_tsne(x, cfg, backend=ExactBackend(), kl_every=30)
        assert np.isfinite(res.kl) and res.n_iter == 60
        assert res.y.shape == (150, 2)

    def test_method_from_config(self):
        x, _ = make_points(150, seed=43, dim=6)
        cfg = TsneConfig(perplexity=8.0, n_iter=60, exaggeration_iters=30,
                         momentum_switch_iter=30, method="fft")
        res = run_tsne(x, cfg, kl_every=30)
        assert np.isfinite(res.kl) and res.y.shape == (150, 2)
