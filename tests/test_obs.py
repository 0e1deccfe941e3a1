"""Observability layer: span tracer, metrics registry, recompile probe,
export sinks, and the estimator/benchmark integration."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (
    NULL_SPAN, Counter, Gauge, Histogram, MetricsRegistry, RecompileProbe,
    Tracer, env_trace_enabled,
)


# -------------------------------------------------------------- tracer ------
class TestTracer:
    def test_span_nesting(self):
        t = Tracer()
        with t.span("fit") as fit:
            with t.span("knn") as knn:
                pass
            with t.span("gradient_descent") as gd:
                with t.span("early_exaggeration") as ee:
                    pass
        assert [s.name for s in t.spans] == \
            ["knn", "early_exaggeration", "gradient_descent", "fit"]
        assert fit.depth == 0 and fit.parent == -1
        assert knn.depth == 1 and knn.parent == fit.index
        assert gd.depth == 1 and gd.parent == fit.index
        assert ee.depth == 2 and ee.parent == gd.index

    def test_durations_and_containment(self):
        clock = iter(float(i) for i in range(100))
        t = Tracer(clock=lambda: next(clock))
        with t.span("outer"):          # t0=1
            with t.span("inner"):      # t0=2, t1=3
                pass
        outer, inner = t.last("outer"), t.last("inner")
        assert inner.duration_s == pytest.approx(1.0)
        assert outer.duration_s == pytest.approx(3.0)
        assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1
        assert t.durations() == {"outer": 3.0, "inner": 1.0}

    def test_sync_blocks_device_work(self):
        t = Tracer()
        x = jnp.ones((256, 256))
        with t.span("matmul") as sp:
            y = sp.sync(x @ x)
        assert t.last("matmul").duration_s > 0
        assert np.asarray(y).shape == (256, 256)

    def test_annotate_lands_in_attrs(self):
        t = Tracer()
        with t.span("phase", n=10) as sp:
            sp.annotate(kl=1.5)
        assert t.last("phase").attrs == {"n": 10, "kl": 1.5}

    def test_disabled_is_noop(self):
        t = Tracer(enabled=False)
        ctx = t.span("anything", n=3)
        assert ctx is NULL_SPAN              # shared singleton, no alloc
        with ctx as sp:
            sp.annotate(a=1)
            assert sp.sync(42) == 42
        assert t.spans == [] and t.durations() == {}

    def test_chrome_trace_valid_and_nested(self, tmp_path):
        t = Tracer()
        with t.span("fit"):
            with t.span("knn"):
                pass
            with t.span("bsp"):
                pass
        path = tmp_path / "trace.json"
        t.to_chrome_trace(path)
        doc = json.loads(path.read_text())   # valid JSON
        evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        by_name = {e["name"]: e for e in evs}
        assert set(by_name) == {"fit", "knn", "bsp"}
        for e in evs:
            assert e["dur"] >= 0 and e["ts"] >= 0
            assert e["pid"] > 0
        fit, knn = by_name["fit"], by_name["knn"]
        # child interval contained in parent interval (Perfetto nesting rule)
        assert fit["ts"] <= knn["ts"]
        assert knn["ts"] + knn["dur"] <= fit["ts"] + fit["dur"] + 1e-3

    def test_env_gate(self, monkeypatch):
        for v, want in [("", False), ("0", False), ("false", False),
                        ("off", False), ("1", True), ("yes", True)]:
            monkeypatch.setenv("TSNE_TRACE", v)
            assert env_trace_enabled() is want
        monkeypatch.delenv("TSNE_TRACE")
        assert env_trace_enabled() is False


# ------------------------------------------------------------- metrics ------
class TestMetrics:
    def test_counter(self):
        c = Counter("x")
        c.inc()
        c.inc(4)
        assert c.value == 5

    def test_gauge_high_water(self):
        g = Gauge("depth")
        g.set(3)
        g.set(10)
        g.set(2)
        assert g.value == 2 and g.max_value == 10

    def test_histogram_percentiles(self):
        h = Histogram("lat")
        for v in range(1, 101):          # 1..100
            h.observe(v)
        assert h.count == 100 and h.min == 1 and h.max == 100
        assert h.mean == pytest.approx(50.5)
        assert h.percentile(50) == pytest.approx(50.5)
        assert h.percentile(95) == pytest.approx(95.05)
        assert h.percentile(99) == pytest.approx(99.01)
        s = h.summary()
        assert s["p50"] == pytest.approx(50.5)
        assert s["p99"] == pytest.approx(99.01)

    def test_histogram_bounded_retention(self):
        h = Histogram("lat", max_samples=16)
        for v in range(1000):
            h.observe(v)
        assert h.count == 1000 and h.max == 999      # exact aggregates
        assert len(h._samples) == 16                 # bounded reservoir
        assert h.percentile(50) >= 984 - 16          # window = recent values

    def test_empty_histogram(self):
        h = Histogram("lat")
        assert math.isnan(h.percentile(50)) and math.isnan(h.mean)
        assert h.summary() == dict(count=0)

    def test_snapshot_shape(self):
        m = MetricsRegistry()
        m.counter("c").inc()
        m.gauge("g").set(7)
        m.histogram("h").observe(1.0)
        snap = m.snapshot()
        assert snap["c"] == 1
        assert snap["g"] == dict(value=7.0, max=7.0)
        assert snap["h"]["count"] == 1
        json.dumps(snap)                 # JSON-ready

    def test_get_or_create_identity(self):
        m = MetricsRegistry()
        assert m.counter("a") is m.counter("a")
        assert m.histogram("b") is m.histogram("b")


# ------------------------------------------------------ recompile probe -----
class TestRecompileProbe:
    def test_counts_distinct_traces(self):
        reg = MetricsRegistry()
        probe = RecompileProbe("f", registry=reg)

        @jax.jit
        def f(x):
            probe.record(x.shape, x.dtype.name)
            return x * 2

        f(jnp.ones(3))
        f(jnp.ones(3) * 5)               # same shape: cached, no trace
        assert probe.count == 1
        f(jnp.ones((4,)))                # new shape: one more trace
        assert probe.count == 2
        assert probe.calls >= 2
        assert reg.counter("recompiles.f").value == 2

    def test_reset(self):
        probe = RecompileProbe("g", registry=MetricsRegistry())
        probe.record((1, 2))
        probe.reset()
        assert probe.count == 0 and probe.calls == 0


# ---------------------------------------------------------- integration -----
class TestTracedFit:
    @pytest.fixture(scope="class")
    def traced_fit(self, tmp_path_factory):
        from repro.api import TSNE
        from repro.data.datasets import make_dataset

        x, _ = make_dataset("digits", n=260)
        path = tmp_path_factory.mktemp("obs") / "fit_trace.json"
        est = TSNE(perplexity=8.0, n_iter=60, kl_every=30, random_state=0,
                   trace=str(path))
        est.fit(x)
        return est, path

    def test_phase_spans_cover_pipeline(self, traced_fit):
        est, _ = traced_fit
        names = {s.name for s in est.tracer_.spans}
        assert {"fit", "knn", "bsp", "symmetrize", "gradient_descent",
                "early_exaggeration", "checkpoint"} <= names
        fit = est.tracer_.last("fit")
        for child in ("knn", "bsp", "symmetrize", "gradient_descent"):
            sp = est.tracer_.last(child)
            assert sp.parent == fit.index and sp.depth == 1
            assert sp.duration_s > 0

    def test_timings_derived_from_spans(self, traced_fit):
        est, _ = traced_fit
        d = est.tracer_.durations()
        for phase in ("knn", "bsp", "symmetrize", "gradient_descent"):
            assert est.timings_[phase] == pytest.approx(d[phase])
            assert est.timings_[phase] > 0

    def test_step_and_checkpoint_spans(self, traced_fit):
        est, _ = traced_fit
        tr = est.tracer_
        phase = tr.last("early_exaggeration")
        steps = tr.find("step")
        assert [s.attrs["step_num"] for s in steps] == list(range(1, 61))
        assert all(s.parent == phase.index for s in steps)
        ckpts = tr.find("checkpoint")
        assert [c.attrs["iteration"] for c in ckpts] == [30, 60]
        assert all({"kl", "grad_norm", "z", "gain_mean"} <= set(c.attrs)
                   for c in ckpts)

    def test_timings_unchanged_for_normal_fit(self, traced_fit):
        est, _ = traced_fit
        assert set(est.timings_) == {
            "knn", "bsp", "symmetrize", "gradient_descent",
            "neighbor_method", "n_neighbors", "bsp_impl", "chunk_size",
            "knn_mean_d2", "max_traversal", "mean_traversal", "fft_span",
            "attractive_fill", "attractive_slots", "attractive_buckets"}
        d = est.tracer_.durations()
        assert est.timings_["gradient_descent"] == pytest.approx(
            d["early_exaggeration"], rel=0.05)
        assert len(est.timings_["max_traversal"]) == 2
        assert len(est.timings_["mean_traversal"]) == 2
        assert len(est.timings_["fft_span"]) == 2

    def test_chrome_trace_written_and_loadable(self, traced_fit):
        _, path = traced_fit
        doc = json.loads(path.read_text())
        names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
        assert {"fit", "knn", "bsp", "symmetrize", "gradient_descent"} <= names

    def test_fit_metrics_recorded(self, traced_fit):
        est, _ = traced_fit
        snap = est.metrics_.snapshot()
        assert snap["fit.iterations"] == est.n_iter_
        assert snap["fit.grad_norm"]["count"] >= 1
        assert snap["fit.grad_norm"]["p95"] > 0

    def test_untraced_fit_has_timings_but_no_tracer(self):
        from repro.api import TSNE
        from repro.data.datasets import make_dataset

        x, _ = make_dataset("digits", n=200)
        est = TSNE(perplexity=6.0, n_iter=30, kl_every=30, random_state=0)
        est.fit(x)
        assert est.tracer_ is None
        for phase in ("knn", "bsp", "symmetrize", "gradient_descent"):
            assert est.timings_[phase] > 0


class TestSpanLifecycle:
    def test_raising_symmetrize_closes_its_span(self, monkeypatch):
        from repro.core import similarity
        from repro.core.tsne import TsneConfig, preprocess
        from repro.data.datasets import make_dataset

        def symmetrize(*args):
            raise RuntimeError("planted")
        monkeypatch.setattr(similarity, "symmetrize_ell", symmetrize)
        x, _ = make_dataset("digits", n=120)
        t = Tracer()
        with pytest.raises(RuntimeError, match="planted"):
            preprocess(jnp.asarray(x), TsneConfig(perplexity=5.0), tracer=t)
        assert [s.name for s in t.spans] == ["knn", "bsp", "symmetrize"]
        assert t.last("symmetrize").duration_s >= 0
        assert t._stack() == []

    def test_raising_step_closes_every_span(self, monkeypatch):
        from repro.core import tsne

        real = tsne.tsne_step

        def step(state, *args, **kw):
            if int(state.iteration) == 3:
                raise RuntimeError("planted")
            return real(state, *args, **kw)
        monkeypatch.setattr(tsne, "tsne_step", step)
        x = np.random.default_rng(0).normal(size=(80, 5)).astype(np.float32)
        t = Tracer()
        with pytest.raises(RuntimeError, match="planted"):
            tsne.run_tsne(x, tsne.TsneConfig(perplexity=5.0, n_iter=10),
                          tracer=t)
        assert t._stack() == []
        assert [s.attrs["step_num"] for s in t.find("step")] == [1, 2, 3, 4]
        for name in ("fit", "gradient_descent", "early_exaggeration"):
            assert t.last(name).duration_s > 0

    def test_profile_shows_program_spans(self, tmp_path):
        from jax.profiler import ProfileData

        from repro.api import TSNE
        from repro.data.datasets import make_dataset

        x, _ = make_dataset("digits", n=120)

        def fit():
            TSNE(perplexity=5.0, n_iter=4, kl_every=2, random_state=0).fit(x)
        fit()                                   # compiles outside the profile
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            fit()
        finally:
            jax.profiler.stop_trace()
        path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        host = [e for plane in ProfileData.from_file(str(path)).planes
                if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events]
        names = {e.name for e in host}
        assert {"fit", "knn", "bsp", "symmetrize", "gradient_descent",
                "early_exaggeration", "step", "checkpoint"} <= names
        steps = [v for e in host if e.name == "step"
                 for k, v in e.stats if k == "step_num"]
        assert sorted(steps) == [1, 2, 3, 4]


class TestBenchArtifact:
    def test_write_bench_json_phases_and_git(self, tmp_path, monkeypatch):
        from benchmarks import common

        monkeypatch.setattr(common, "ROWS", [("bench_a", 12.5, "")])
        monkeypatch.setattr(common, "PHASES", {})
        common.record_phases("e2e_digits", dict(
            knn=0.5, bsp=0.25, symmetrize=0.1, gradient_descent=1.5,
            neighbor_method="exact",
        ))
        common.record_phases("skipped", None)     # no-op
        path = common.write_bench_json(
            tmp_path, benches=["e2e"], argv=["--quick"], wall_s=3.0)
        doc = json.loads(path.read_text())
        assert doc["phases"]["e2e_digits"]["gradient_descent"] == 1.5
        assert "skipped" not in doc["phases"]
        assert doc["results"][0]["name"] == "bench_a"
        # this repo is a git checkout: commit provenance must be present
        assert len(doc["git"]["commit"]) == 40
        assert isinstance(doc["git"]["dirty"], bool)

    def test_unknown_bench_name_exits_nonzero(self):
        import subprocess
        import sys
        import pathlib

        root = pathlib.Path(__file__).resolve().parent.parent
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--bench", "step",
             "--no-json"],
            cwd=root, capture_output=True, text=True,
            env=dict(PYTHONPATH="src", PATH="/usr/bin:/bin:/usr/local/bin"),
        )
        assert proc.returncode != 0
        assert "unknown bench name" in proc.stderr
