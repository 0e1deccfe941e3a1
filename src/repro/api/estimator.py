"""scikit-learn-compatible ``TSNE`` estimator over pluggable gradient backends.

Drop-in for ``sklearn.manifold.TSNE`` on the parameters that matter for the
paper's benchmark (261x claim): ``fit`` / ``fit_transform``, ``embedding_``,
``kl_divergence_``, ``n_iter_``, ``learning_rate="auto"`` — with ``method=``
extended beyond sklearn's {"exact", "barnes_hut"} to any name in the backend
registry ("fft" ships in-box), or a :class:`GradientBackend` instance.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Iterable, Mapping

import numpy as np

from repro import obs
from repro.core.tsne import (
    IterationStats, NeighborGraph, ObserverFn, TsneConfig, TsneResult,
    run_tsne,
)
from repro.api.backends import GradientBackend, make_backend


class TSNE:
    """t-SNE with a pluggable gradient backend.

    Parameters mirror ``sklearn.manifold.TSNE`` (``angle`` is the BH theta;
    ``random_state`` seeds the embedding init).  ``method`` may also be a
    :class:`GradientBackend` instance, which then carries its own settings
    (``angle`` / ``backend_options`` must be left default).  Extras beyond
    sklearn:

    callbacks : iterable of callables receiving :class:`IterationStats`
        every ``kl_every`` iterations (structured observer API).
    kl_every : int
        iteration period for KL evaluation / callbacks / convergence checks.
    backend_options : mapping
        ``TsneConfig`` field overrides for backend construction (e.g.
        ``{"use_pallas": True}``, ``{"compress_tree": False}``,
        ``{"fft_n_boxes": 96}``).  Kernel dispatch flags ride through here
        too: ``{"bsp_impl": "pallas"}`` routes the perplexity search through
        the fused Pallas kernel, ``{"fft_interp_impl": "pallas"}`` the FFT
        backend's spread/gather; both default to ``"auto"`` (follow
        ``use_pallas``).  See docs/KERNELS.md.
    n_neighbors : int or None
        KNN graph degree; ``None`` = sklearn's ``int(3 * perplexity)``.
        Always clamped to ``n_samples - 1``.
    neighbor_method : str
        registered neighbor backend for the KNN stage
        (``"exact"`` | ``"rp_forest"`` | ``"nn_descent"`` | custom).
    neighbor_options : mapping
        constructor options for the neighbor backend (e.g.
        ``{"n_trees": 16}``, ``{"refine_iters": 3}``).
    trace : bool, str or None
        observability switch.  ``None`` (default) defers to the process
        environment (``TSNE_TRACE=1`` enables the global tracer with
        near-zero overhead otherwise); ``True`` records this estimator's
        fits/transforms on a private tracer exposed as ``tracer_`` (with a
        matching ``metrics_`` registry); a string additionally writes a
        Chrome-trace JSON — loadable in Perfetto — to that path after each
        ``fit``.
    """

    def __init__(
        self,
        n_components: int = 2,
        *,
        perplexity: float = 30.0,
        early_exaggeration: float = 12.0,
        learning_rate: float | str = "auto",
        n_iter: int = 1000,
        min_grad_norm: float = 1e-7,
        method: str | GradientBackend = "barnes_hut",
        angle: float = 0.5,
        verbose: int = 0,
        random_state: int | None = None,
        callbacks: Iterable[ObserverFn] = (),
        kl_every: int = 50,
        backend_options: Mapping | None = None,
        n_neighbors: int | None = None,
        neighbor_method: str = "exact",
        neighbor_options: Mapping | None = None,
        trace: bool | str | None = None,
    ):
        self.n_components = n_components
        self.perplexity = perplexity
        self.early_exaggeration = early_exaggeration
        self.learning_rate = learning_rate
        self.n_iter = n_iter
        self.min_grad_norm = min_grad_norm
        self.method = method
        self.angle = angle
        self.verbose = verbose
        self.random_state = random_state
        self.callbacks = tuple(callbacks)
        self.kl_every = kl_every
        self.backend_options = dict(backend_options or {})
        self.n_neighbors = n_neighbors
        self.neighbor_method = neighbor_method
        self.neighbor_options = dict(neighbor_options or {})
        self.trace = trace

    # -- sklearn plumbing ---------------------------------------------------

    def get_params(self, deep: bool = True) -> dict:
        return {
            "n_components": self.n_components,
            "perplexity": self.perplexity,
            "early_exaggeration": self.early_exaggeration,
            "learning_rate": self.learning_rate,
            "n_iter": self.n_iter,
            "min_grad_norm": self.min_grad_norm,
            "method": self.method,
            "angle": self.angle,
            "verbose": self.verbose,
            "random_state": self.random_state,
            "callbacks": self.callbacks,
            "kl_every": self.kl_every,
            "backend_options": self.backend_options,
            "n_neighbors": self.n_neighbors,
            "neighbor_method": self.neighbor_method,
            "neighbor_options": self.neighbor_options,
            "trace": self.trace,
        }

    def set_params(self, **params) -> "TSNE":
        for k, v in params.items():
            if k not in self.get_params():
                raise ValueError(f"invalid parameter {k!r} for TSNE")
            setattr(self, k, v)
        return self

    # -- core ---------------------------------------------------------------

    def _setup_obs(self) -> tuple:
        """Resolve the ``trace`` knob into ``(tracer, metrics)`` for a run.

        ``trace`` falsy: globals (enabled only under ``TSNE_TRACE``) —
        ``tracer_`` / ``metrics_`` point at them when active, else ``None``.
        ``trace`` truthy: a fresh private tracer + registry per fit, kept on
        the estimator so ``transform`` calls append to the same trace.
        """
        if not self.trace:
            g = obs.get_tracer()
            self.tracer_ = g if g.enabled else None
            self.metrics_ = obs.get_metrics() if g.enabled else None
            return None, None            # run_tsne falls back to the globals
        self.tracer_ = obs.Tracer()
        self.metrics_ = obs.MetricsRegistry()
        return self.tracer_, self.metrics_

    def _build_config(self, n: int) -> TsneConfig:
        cfg = TsneConfig(
            perplexity=self.perplexity,
            n_iter=self.n_iter,
            theta=self.angle,
            learning_rate=self.learning_rate,
            early_exaggeration=self.early_exaggeration,
            min_grad_norm=self.min_grad_norm,
            seed=0 if self.random_state is None else int(self.random_state),
            method=self.method if isinstance(self.method, str)
            else getattr(self.method, "name", "barnes_hut"),
            n_neighbors=self.n_neighbors,
            neighbor_method=self.neighbor_method,
            neighbor_options=self.neighbor_options or None,
        )
        if self.backend_options:
            cfg = dataclasses.replace(cfg, **self.backend_options)
        return cfg

    def fit(self, x, y=None) -> "TSNE":
        """Fit x [n_samples, n_features] into the embedding space."""
        x = np.asarray(x, np.float32)
        if x.ndim != 2:
            raise ValueError(f"expected 2-D input, got shape {x.shape}")
        n = x.shape[0]
        if self.n_components != 2:
            raise ValueError(
                "this implementation embeds into 2 dimensions only "
                f"(n_components={self.n_components})"
            )
        if n <= 3 * self.perplexity:
            raise ValueError(
                f"perplexity {self.perplexity} is too large for n_samples={n} "
                "(need n_samples > 3 * perplexity)"
            )
        config = self._build_config(n)

        if isinstance(self.method, str):
            backend = make_backend(self.method, config, n)
        elif isinstance(self.method, GradientBackend):
            # an instance carries its own settings (theta, grid size, ...);
            # refuse silently-ignored estimator-level overrides
            if self.backend_options:
                raise ValueError(
                    "backend_options have no effect when method= is a "
                    "GradientBackend instance — set them on the instance"
                )
            if self.angle != 0.5 and hasattr(self.method, "theta"):
                raise ValueError(
                    "angle= has no effect when method= is a GradientBackend "
                    "instance — set theta on the instance"
                )
            backend = self.method
        else:
            raise TypeError(
                f"method must be a registered backend name or a GradientBackend "
                f"instance, got {type(self.method).__name__}"
            )

        observers = list(self.callbacks)
        if self.verbose:
            observers.append(
                lambda s: print(
                    f"[t-SNE:{backend.name}] iter {s.iteration:5d}  "
                    f"KL {s.kl:.4f}  |grad| {s.grad_norm:.2e}  {s.elapsed_s:.1f}s"
                )
            )

        def observer(stats: IterationStats) -> None:
            for fn in observers:
                fn(stats)

        tracer, metrics = self._setup_obs()
        result: TsneResult = run_tsne(
            x, config,
            observer=observer if observers else None,
            kl_every=self.kl_every,
            backend=backend,
            tracer=tracer,
            metrics=metrics,
        )
        if isinstance(self.trace, str) and tracer is not None:
            tracer.to_chrome_trace(self.trace, process_name="tsne.fit")
        self.embedding_ = result.y
        self.kl_divergence_ = result.kl
        self.kl_history_ = result.kl_history
        self.n_iter_ = result.n_iter
        self.learning_rate_ = config.resolve_lr(n)
        self.timings_ = result.timings
        self.n_features_in_ = x.shape[1]
        self.neighbor_graph_ = result.graph
        self.n_neighbors_ = config.resolve_n_neighbors(n)
        self._x_fit = x
        self._query_index = None            # built lazily on first transform
        return self

    def fit_transform(self, x, y=None) -> np.ndarray:
        """Fit x and return the [n_samples, 2] embedding."""
        self.fit(x, y)
        return self.embedding_

    # -- out-of-sample ------------------------------------------------------

    def _check_fitted(self) -> None:
        if getattr(self, "embedding_", None) is None:
            raise ValueError("this TSNE instance is not fitted yet — call "
                             "fit / fit_transform (or TSNE.load) first")

    @property
    def query_index_(self):
        """Neighbor-backend query index over the fitted inputs (lazy).

        Built by the same backend that built the fit-time KNN graph
        (``rp_forest`` reuses its forest; backends without a query path fall
        back to exact), then cached until the next ``fit``.
        """
        self._check_fitted()
        if getattr(self, "_query_index", None) is None:
            from repro.neighbors import build_query_index, make_neighbor_backend
            config = self._build_config(self._x_fit.shape[0])
            backend = make_neighbor_backend(
                config.neighbor_method, config.resolve_neighbor_options()
            )
            self._query_index = build_query_index(backend, self._x_fit)
        return self._query_index

    @property
    def query_k_(self) -> int:
        """Neighbor width for out-of-sample queries (the fit-time k)."""
        self._check_fitted()
        return int(self.n_neighbors_)

    def transform(self, x_new, *, transform_config=None,
                  return_stats: bool = False):
        """Embed new points into the *frozen* fitted embedding — no refit.

        Each row of ``x_new [M, n_features]`` finds its ``query_k_`` nearest
        fitted inputs through the fitted neighbor structure, receives
        perplexity-calibrated similarities over them, and descends
        (attractive-only, momentum + gains, per-point early stop) against
        their frozen embedding coordinates, starting from their p-weighted
        mean.  Fixed-shape jitted step: batches of any size share one trace.

        Returns ``y [M, 2]`` (and per-point ``TransformStats`` when
        ``return_stats=True``).
        """
        from repro.embed.transform import TransformConfig, transform_batch

        self._check_fitted()
        x_new = np.asarray(x_new, np.float32)
        if x_new.ndim != 2 or x_new.shape[1] != self.n_features_in_:
            raise ValueError(
                f"expected x_new shaped [m, {self.n_features_in_}], got "
                f"{x_new.shape}"
            )
        cfg = transform_config or TransformConfig()
        perp = cfg.perplexity if cfg.perplexity is not None else self.perplexity
        y, stats = transform_batch(
            x_new, self.query_index_, self.embedding_,
            k=self.query_k_, perplexity=float(perp), config=cfg,
            tracer=getattr(self, "tracer_", None),
        )
        return (y, stats) if return_stats else y

    # -- persistence --------------------------------------------------------

    _SAVE_SCHEMA = 1

    def save(self, path) -> None:
        """Persist the fitted state (npz): embedding, fitted inputs, sparse-P
        neighbor graph, and constructor params — enough for ``load`` to serve
        ``transform`` queries in another process without refitting."""
        self._check_fitted()
        params = self.get_params()
        params.pop("callbacks", None)       # not serializable, fit-only
        if not isinstance(params["method"], str):
            params["method"] = getattr(params["method"], "name", "barnes_hut")
        arrays = dict(
            schema=np.int32(self._SAVE_SCHEMA),
            embedding=np.asarray(self.embedding_, np.float32),
            x_fit=np.asarray(self._x_fit, np.float32),
            kl_divergence=np.float64(self.kl_divergence_),
            kl_history=np.asarray(self.kl_history_, np.float64),
            n_iter_run=np.int32(self.n_iter_),
            learning_rate=np.float64(self.learning_rate_),
            n_neighbors_fit=np.int32(self.n_neighbors_),
            params_json=np.array(json.dumps(params)),
        )
        g = getattr(self, "neighbor_graph_", None)
        if g is not None:
            arrays.update(
                graph_p_cols=np.asarray(g.p_cols, np.int32),
                graph_p_vals=np.asarray(g.p_vals, np.float32),
                graph_p_logp=np.float64(g.p_logp),
            )
        np.savez_compressed(path, **arrays)

    @classmethod
    def load(cls, path) -> "TSNE":
        """Rebuild a fitted estimator persisted with :meth:`save`; the query
        index is rebuilt lazily on the first ``transform``.

        ``timings_`` is ``None`` on a loaded model: no phases ran in this
        process, so there is nothing to report — distinct from the populated
        dict a real ``fit`` leaves behind.  (``{}`` would be indistinguishable
        from a fitted-but-untimed model.)"""
        z = np.load(path, allow_pickle=False)
        if int(z["schema"]) != cls._SAVE_SCHEMA:
            raise ValueError(
                f"unsupported TSNE save schema {int(z['schema'])} "
                f"(expected {cls._SAVE_SCHEMA})"
            )
        params = json.loads(str(z["params_json"]))
        # a file may name backend options that this version no longer has
        fields = {f.name for f in dataclasses.fields(TsneConfig)}
        params["backend_options"] = {
            k: v for k, v in params["backend_options"].items() if k in fields}
        est = cls(**params)
        est.embedding_ = np.asarray(z["embedding"])
        est._x_fit = np.asarray(z["x_fit"])
        est.kl_divergence_ = float(z["kl_divergence"])
        est.kl_history_ = np.asarray(z["kl_history"])
        est.n_iter_ = int(z["n_iter_run"])
        est.learning_rate_ = float(z["learning_rate"])
        est.n_neighbors_ = int(z["n_neighbors_fit"])
        est.n_features_in_ = est._x_fit.shape[1]
        est.timings_ = None         # loaded, not fitted here: no phase ran
        est._query_index = None
        n = est._x_fit.shape[0]
        # files from edges-only fits hold [1, 1] dummy planes: no ELL to load
        if "graph_p_cols" in z.files and len(z["graph_p_cols"]) == n:
            # the attractive loop's degree buckets are rebuilt, not stored
            est.neighbor_graph_ = NeighborGraph.from_ell(
                z["graph_p_cols"], z["graph_p_vals"],
                float(z["graph_p_logp"]), est._build_config(n))
        else:
            est.neighbor_graph_ = None
        return est
