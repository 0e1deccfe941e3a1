"""End-to-end Barnes-Hut t-SNE driver (paper Fig. 1a pipeline).

Pipeline:  KNN -> BSP -> symmetrize P -> gradient descent where every
iteration evaluates the attractive (sparse) + repulsive forces through a
pluggable :class:`~repro.api.backends.GradientBackend` (Barnes-Hut by
default), with early exaggeration, momentum switching and per-dimension
gains exactly as in the reference implementations the paper benchmarks
against (scikit-learn / daal4py).

The preprocessing product is a typed :class:`NeighborGraph` (a JAX pytree),
so the whole descent step — backend gradient + momentum/gains update — jits
as one program regardless of which backend is plugged in.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import attractive, bsp, morton, quadtree, scopes, similarity
from repro.core.summarize import summarize as _summarize
from repro.core.repulsive import bh_repulsion_sorted

# One count per distinct (embedding shape, backend, lr, min_gain) trace of
# the descent step — compile churn shows up in metric snapshots as
# ``recompiles.tsne_step`` instead of being invisible.
TSNE_STEP_RETRACES = obs.RecompileProbe("tsne_step")

# Hard cap on the resolved neighbor width K.  The ELL layouts and the Pallas
# tile budgets ([256, K] blocks resident in ~16 MB VMEM) are sized for this
# envelope, and `repro.analysis` certifies the kernel contracts exactly at
# it.  K = 3*perplexity, so this admits perplexity up to ~341 — far beyond
# any published t-SNE setting.
MAX_N_NEIGHBORS = 1024


@dataclasses.dataclass(frozen=True)
class TsneConfig:
    perplexity: float = 30.0
    n_iter: int = 1000
    theta: float = 0.5
    learning_rate: float | str = "auto"   # 'auto' = max(N / early_exaggeration, 50)
    early_exaggeration: float = 12.0
    exaggeration_iters: int = 250
    momentum_initial: float = 0.5
    momentum_final: float = 0.8
    momentum_switch_iter: int = 250
    min_gain: float = 0.01
    min_grad_norm: float = 1e-7           # early stop when ||grad|| drops below
    init_std: float = 1e-4
    depth: int | str = morton.DEFAULT_DEPTH   # "auto" = morton.auto_depth(N)
    seed: int = 0
    dtype: Any = jnp.float32
    n_neighbors: int | None = None        # None = int(3 * perplexity); clamped to n-1
    # registered neighbor backend ('exact' | 'rp_forest' | 'nn_descent' | ...)
    neighbor_method: str = "exact"
    # accepts a mapping; normalized to a sorted item tuple so the config
    # stays hashable (backends may embed it as a static jit argument)
    neighbor_options: Mapping[str, Any] | tuple | None = None
    knn_block_q: int = 512
    knn_block_db: int = 2048
    # rows per preprocessing slice: the BSP search and the ELL
    # symmetrization stream over [chunk_size, K] blocks instead of whole
    # [N, K] passes (None = unchunked).  The memory knob for million-point
    # runs — peak preprocessing transients are O(chunk_size * K).
    chunk_size: int | None = None
    # device count for the 'sharded' neighbor backend (None = all visible)
    knn_shards: int | None = None
    use_pallas: bool = False              # route hot loops through Pallas kernels
    # perplexity-search implementation: 'auto' follows use_pallas;
    # 'xla' | 'pallas' force one (core/bsp.py dispatch)
    bsp_impl: str = "auto"
    # FFT-repulsion spread/gather implementation, same semantics
    # (core/fft_repulsion.py dispatch, used by the 'fft' backend)
    fft_interp_impl: str = "auto"
    compress_tree: bool = True            # False = daal4py-like uncompressed tree
    method: str = "barnes_hut"            # registered gradient backend name
    fft_n_boxes: int = 48                 # grid boxes/dim for the 'fft' backend

    def __post_init__(self):
        if isinstance(self.neighbor_options, Mapping):
            object.__setattr__(
                self, "neighbor_options",
                tuple(sorted(self.neighbor_options.items())),
            )

    def resolve_lr(self, n: int) -> float:
        if self.learning_rate == "auto":
            return max(n / self.early_exaggeration, 50.0)
        return float(self.learning_rate)

    def resolve_n_neighbors(self, n: int) -> int:
        k = int(3.0 * self.perplexity) if self.n_neighbors is None \
            else int(self.n_neighbors)
        return max(1, min(k, n - 1, MAX_N_NEIGHBORS))

    def resolve_neighbor_options(self) -> dict:
        """Backend options with config-level defaults folded in."""
        opts = dict(self.neighbor_options or {})
        if self.neighbor_method == "exact":
            opts.setdefault("block_q", self.knn_block_q)
            opts.setdefault("block_db", self.knn_block_db)
            opts.setdefault("pairwise", "pallas" if self.use_pallas else "xla")
        elif self.neighbor_method in ("rp_forest", "nn_descent"):
            opts.setdefault("seed", self.seed)
        elif self.neighbor_method == "sharded":
            opts.setdefault("seed", self.seed)
            opts.setdefault("shards", self.knn_shards)
        return opts

    def resolve_chunk_size(self, n: int) -> int | None:
        """Preprocessing chunk: None = unchunked, else clamped to [1, n]."""
        if self.chunk_size is None:
            return None
        return max(1, min(int(self.chunk_size), n))

    def resolve_attractive_block(self) -> int:
        """The attractive loop's row block: a degree bucket's turn gathers
        about this many rows of the whole ELL width (512 by default).  It
        never exceeds the configured preprocessing chunk, so one knob
        bounds live transients end-to-end."""
        if self.chunk_size is not None:
            return max(1, min(512, int(self.chunk_size)))
        return 512

    def resolve_depth(self, n: int) -> int:
        return morton.auto_depth(n) if self.depth == "auto" else int(self.depth)

    def resolve_bsp_impl(self) -> str:
        if self.bsp_impl == "auto":
            return "pallas" if self.use_pallas else "xla"
        return self.bsp_impl

    def resolve_fft_interp_impl(self) -> str:
        if self.fft_interp_impl == "auto":
            return "pallas" if self.use_pallas else "xla"
        return self.fft_interp_impl


class TsneState(NamedTuple):
    y: jax.Array
    velocity: jax.Array
    gains: jax.Array
    iteration: jax.Array


class GradResult(NamedTuple):
    """Common product of every gradient backend (exact / barnes_hut / fft)."""
    grad: jax.Array
    kl: jax.Array          # KL(P||Q) estimate (exact attractive part, backend Z)
    z: jax.Array
    max_traversal: jax.Array  # BH tree-walk depth; 0 for tree-free backends
    # mean BH walk length over points; 0 for tree-free backends.  Over
    # max_traversal it is the share of the lockstep walk's lane-turns that
    # did work.
    mean_traversal: jax.Array | float = 0.0
    # FFT interpolation lattice's side in embedding units; 0 for the others
    fft_span: jax.Array | float = 0.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class NeighborGraph:
    """Sparse symmetric input-similarity graph produced by :func:`preprocess`.

    A JAX pytree: flows straight through ``jax.jit`` as one operand, so any
    backend reads the layout it needs (the ELL rows or their degree
    buckets) inside a jitted step.
    """
    p_cols: jax.Array       # [N, W] int32 ELL neighbor indices (pad: row idx)
    p_vals: jax.Array       # [N, W] symmetric p_ij, sums to 1 (pad: 0)
    p_logp: jax.Array       # exact sum_ij p_ij log p_ij (KL constant)
    # the ELL's rows cut by degree for the attractive loop (from_ell)
    buckets: similarity.DegreeBuckets
    n: int = dataclasses.field(metadata=dict(static=True), default=0)

    @classmethod
    def from_ell(cls, p_cols, p_vals, p_logp, config: TsneConfig):
        """The graph of a host [N, W] ELL, on the device, with the degree
        buckets the attractive loop runs over: the rows cut by degree
        (``similarity.degree_buckets``), each turn within the configured
        row block's indices and the preprocessing chunk's rows."""
        buckets = similarity.degree_buckets(
            p_cols, p_vals, block=config.resolve_attractive_block(),
            max_rows=config.resolve_chunk_size(len(p_cols)))
        return cls(p_cols=jnp.asarray(p_cols), p_vals=jnp.asarray(p_vals),
                   p_logp=jnp.asarray(p_logp, config.dtype), n=len(p_cols),
                   buckets=jax.tree.map(jnp.asarray, buckets))


def combine_forces(
    f_attr, kl_attr, f_rep_unnorm, z, exaggeration, p_logp,
    max_traversal=None, mean_traversal=None, fft_span=None,
) -> GradResult:
    """Shared backend epilogue (eq. 6/7): fold attractive + repulsive terms.

    grad = 4 (exag * F_attr - F_rep / Z);  KL = sum p log p + kl_attr + log Z.
    ``f_rep_unnorm`` is the un-normalized repulsive numerator.
    """
    with jax.named_scope(scopes.UPDATE):
        dtype = f_attr.dtype
        z = jnp.maximum(z, 1e-30)
        grad = 4.0 * (jnp.asarray(exaggeration, dtype) * f_attr
                      - f_rep_unnorm / z)
        kl = p_logp + kl_attr + jnp.log(z)
    if max_traversal is None:
        max_traversal = jnp.zeros((), jnp.int32)
    if mean_traversal is None:
        mean_traversal = jnp.zeros((), dtype)
    if fft_span is None:
        fft_span = jnp.zeros((), dtype)
    return GradResult(grad=grad, kl=kl, z=z, max_traversal=max_traversal,
                      mean_traversal=mean_traversal, fft_span=fft_span)


# ---------------------------------------------------------------------------
# One BH gradient evaluation (steps 3-6 of Fig. 1a)
# ---------------------------------------------------------------------------

def bh_gradient(
    y: jax.Array,
    graph: NeighborGraph,
    theta: float,
    exaggeration: jax.Array | float,
    depth: int,
    compress_tree: bool = True,
    use_pallas: bool = False,
) -> GradResult:
    # --- quadtree building (step 3) ---
    with jax.named_scope(scopes.BH_TREE):
        cent, r_span = morton.span_radius(y)
        if use_pallas:
            from repro.kernels.ops import morton_encode as enc
            codes = enc(y, cent, r_span, depth=depth)
        else:
            codes = morton.morton_encode(y, cent, r_span, depth=depth)
        codes_s, y_s, perm = quadtree.sort_points_by_code(y, codes)
        tree = quadtree.build_quadtree(codes_s, depth=depth,
                                       compress=compress_tree)
    # --- summarization (step 4) ---
    with jax.named_scope(scopes.BH_SUMMARIZE):
        summ = _summarize(tree, y_s, r_span)
    # --- repulsive (step 6) ---
    with jax.named_scope(scopes.BH_TRAVERSAL):
        rep = bh_repulsion_sorted(y_s, tree, summ, theta)
        z = jnp.sum(rep.z_per_point)
        f_rep = jnp.zeros_like(y).at[perm].set(rep.force)
        max_traversal = jnp.max(rep.steps)
        mean_traversal = jnp.mean(rep.steps.astype(y.dtype))
    # --- attractive (step 5) ---
    with jax.named_scope(scopes.ATTRACTIVE):
        if use_pallas:
            from repro.kernels.ops import attractive_forces_ell as attr_ell
            f_attr, kl_attr = attr_ell(y, graph.p_cols, graph.p_vals)
        else:
            f_attr, kl_attr = attractive.attractive_forces_bucketed(
                y, graph.buckets)
    return combine_forces(f_attr, kl_attr, f_rep, z, exaggeration,
                          graph.p_logp,
                          max_traversal=max_traversal,
                          mean_traversal=mean_traversal)


# ---------------------------------------------------------------------------
# Gradient-descent update (momentum + gains, scikit-learn/daal4py-compatible)
# ---------------------------------------------------------------------------

def gd_update(state: TsneState, grad: jax.Array, lr: float, momentum, min_gain: float):
    same_sign = (grad > 0) == (state.velocity > 0)
    gains = jnp.where(same_sign, state.gains * 0.8, state.gains + 0.2)
    gains = jnp.maximum(gains, min_gain)
    velocity = momentum * state.velocity - lr * gains * grad
    y = state.y + velocity
    y = y - jnp.mean(y, axis=0, keepdims=True)
    return TsneState(y=y, velocity=velocity, gains=gains, iteration=state.iteration + 1)


class StepStats(NamedTuple):
    """Device-side per-iteration diagnostics returned by :func:`tsne_step`."""
    kl: jax.Array
    grad_norm: jax.Array
    z: jax.Array
    max_traversal: jax.Array
    mean_traversal: jax.Array | float = 0.0
    fft_span: jax.Array | float = 0.0


@functools.partial(jax.jit, static_argnames=("backend", "lr", "min_gain"))
def tsne_step(
    state: TsneState,
    graph: NeighborGraph,
    exaggeration,
    momentum,
    *,
    backend,
    lr: float,
    min_gain: float,
):
    """One descent iteration: backend gradient + momentum/gains update.

    ``backend`` is any hashable object with a
    ``gradient(y, graph, exaggeration) -> GradResult`` method (see
    ``repro.api.backends``); it is a static argument, so each backend
    compiles its own step program once.
    """
    TSNE_STEP_RETRACES.record(
        state.y.shape, type(backend).__name__, getattr(backend, "name", ""),
        lr, min_gain,
    )
    res = backend.gradient(state.y, graph, exaggeration)
    with jax.named_scope(scopes.UPDATE):
        grad_norm = jnp.linalg.norm(res.grad)
        new_state = gd_update(state, res.grad, lr, momentum, min_gain)
    return new_state, StepStats(kl=res.kl, grad_norm=grad_norm, z=res.z,
                                max_traversal=res.max_traversal,
                                mean_traversal=res.mean_traversal,
                                fft_span=res.fft_span)


# ---------------------------------------------------------------------------
# Full pipeline
# ---------------------------------------------------------------------------

class TsneResult(NamedTuple):
    y: np.ndarray
    kl: float
    kl_history: np.ndarray
    timings: dict
    n_iter: int = 0
    # the fitted sparse-P pytree (kept so estimators can persist / reuse the
    # neighbor structure without re-running KNN + perplexity search)
    graph: "NeighborGraph | None" = None


@dataclasses.dataclass(frozen=True)
class IterationStats:
    """Structured observer payload (replaces the bare ``(it, kl)`` callback)."""
    iteration: int          # 1-based iteration just completed
    kl: float               # KL(P||Q) estimate at this iteration
    grad_norm: float        # ||dC/dY||_F — drives min_grad_norm early stopping
    z: float                # repulsive normalizer estimate
    max_traversal: int      # deepest BH tree walk (0 for exact / fft backends)
    exaggeration: float
    momentum: float
    elapsed_s: float        # wall time since gradient descent started
    mean_traversal: float = 0.0  # mean BH tree walk over points (0 likewise)
    fft_span: float = 0.0   # FFT lattice's side, embedding units (0 likewise)


ObserverFn = Callable[[IterationStats], None]


def preprocess(
    x: jax.Array, config: TsneConfig, tracer: obs.Tracer | None = None,
) -> tuple[NeighborGraph, dict]:
    """KNN + BSP + symmetrization -> (NeighborGraph, stage timings).

    The KNN stage dispatches through the ``repro.neighbors`` registry
    (``config.neighbor_method``); the timings dict records which backend ran
    (``neighbor_method``), the resolved ``n_neighbors``, and ``knn_mean_d2``
    — the mean selected squared distance, directly comparable against the
    exact backend's value on the same data as a recall proxy.

    With ``config.chunk_size`` set, the perplexity search and the ELL
    symmetrization stream over ``[chunk_size, K]`` row slices
    (``bsp.binary_search_perplexity_chunked`` /
    ``similarity.symmetrize_ell_chunked``) — numerically identical to the
    whole-array forms, with preprocessing transients bounded by the chunk
    instead of N.  Pair with ``neighbor_method="sharded"`` for the fully
    memory-bounded million-point pipeline (docs/ARCHITECTURE.md,
    "Scaling to 1M+").

    Each stage is a span on ``tracer`` (default: the process-global tracer)
    with ``block_until_ready`` sync at exit, and the per-stage seconds in
    the timings dict are those spans' durations — one timing source for
    both the Perfetto trace and ``timings_``.  When the tracer is disabled
    a private always-on tracer times the three phases (the spans are
    discarded with it), so timings stay populated at negligible cost.
    """
    from repro.neighbors import make_neighbor_backend  # lazy: builds on core
    if tracer is None:
        tracer = obs.get_tracer()
    timer = tracer if tracer.enabled else obs.Tracer()
    k = config.resolve_n_neighbors(int(x.shape[0]))
    nb = make_neighbor_backend(
        config.neighbor_method, config.resolve_neighbor_options()
    )
    with timer.span("knn", backend=nb.name, k=k, n=int(x.shape[0])) as sp_knn:
        idx, d2 = nb.neighbors(x.astype(config.dtype), k)
        sp_knn.sync((idx, d2))

    bsp_impl = config.resolve_bsp_impl()
    chunk = config.resolve_chunk_size(int(x.shape[0]))
    with timer.span("bsp", perplexity=config.perplexity, impl=bsp_impl,
                    chunk_size=chunk) as sp_bsp:
        if chunk is not None:
            cond_p, _ = bsp.binary_search_perplexity_chunked(
                d2, config.perplexity, chunk, impl=bsp_impl
            )
        else:
            cond_p, _ = bsp.binary_search_perplexity(
                d2, config.perplexity, impl=bsp_impl
            )
        sp_bsp.sync(cond_p)

    n = int(x.shape[0])
    with timer.span("symmetrize", chunk_size=chunk) as sp_sym:
        if chunk is not None:
            sym_cols, sym_vals = similarity.symmetrize_ell_chunked(
                idx, cond_p, chunk
            )
        else:
            sym_cols, sym_vals = similarity.symmetrize_ell(idx, cond_p)
        sym_vals = sym_vals / sym_vals.sum()
        pv = np.asarray(sym_vals)
        p_logp = float((pv[pv > 0] * np.log(pv[pv > 0])).sum())
        graph = NeighborGraph.from_ell(
            sym_cols, np.asarray(sym_vals, np.dtype(config.dtype)), p_logp,
            config)
        # the attractive loop's gathered columns, and the share of them
        # that are real entries (the rest is padding)
        real = np.count_nonzero(sym_cols != np.arange(n)[:, None])
        fill = dict(attractive_fill=real / graph.buckets.slots,
                    attractive_slots=graph.buckets.slots,
                    attractive_buckets=len(graph.buckets.cols))
        sp_sym.annotate(**fill)
        sp_sym.sync((graph.p_vals, graph.buckets))
    return graph, dict(
        knn=sp_knn.duration_s, bsp=sp_bsp.duration_s,
        symmetrize=sp_sym.duration_s,
        neighbor_method=nb.name, n_neighbors=k,
        bsp_impl=bsp_impl,
        chunk_size=chunk,
        knn_mean_d2=float(jnp.mean(d2)),
        **fill,
    )


def init_state(n: int, config: TsneConfig) -> TsneState:
    key = jax.random.PRNGKey(config.seed)
    y0 = config.init_std * jax.random.normal(key, (n, 2), dtype=config.dtype)
    return TsneState(
        y=y0,
        velocity=jnp.zeros_like(y0),
        gains=jnp.ones_like(y0),
        iteration=jnp.zeros((), jnp.int32),
    )


def run_tsne(
    x,
    config: TsneConfig = TsneConfig(),
    observer: ObserverFn | None = None,
    kl_every: int = 50,
    backend=None,
    tracer: obs.Tracer | None = None,
    metrics: obs.MetricsRegistry | None = None,
) -> TsneResult:
    """Full t-SNE run through a pluggable gradient backend.

    ``backend`` defaults to the registered backend named ``config.method``;
    pass any ``GradientBackend`` instance to override.  ``observer`` is
    called with :class:`IterationStats` every ``kl_every`` iterations (and on
    the final one); ``config.min_grad_norm`` stops the descent early at those
    same checkpoints, matching scikit-learn's convergence rule.

    Observability: the run is one ``fit`` span with ``knn`` / ``bsp`` /
    ``symmetrize`` / ``gradient_descent`` children (the descent splits into
    ``early_exaggeration`` / ``main_phase``, each iteration's dispatch is a
    ``step`` span carrying its iteration number, and each KL evaluation a
    ``checkpoint`` span over its host work: the stats pull, the metrics and
    the observer, carrying kl / grad-norm / Z, and the mean gain when the
    caller's tracer is enabled), all on ``tracer`` — default the
    process-global one, a no-op unless enabled.  Every span is also a
    ``jax.profiler`` annotation, so these phases appear in any JAX profile.
    The seconds in the returned ``timings`` dict are *derived from those
    spans*, so the Perfetto trace and ``timings_`` can never disagree on a
    phase's time.  Besides them it lists the Barnes-Hut walk at each
    checkpoint, from the checkpoint's stats and not from a span
    (``max_traversal``: the lockstep walk's turns; ``mean_traversal``: the
    mean over points; 0 without a tree), and the FFT lattice's side
    (``fft_span``, embedding units; 0 for the other backends).  Checkpoint
    stats also land on ``metrics`` (default global registry) as
    ``fit.grad_norm`` / ``fit.gain_mean`` histograms and a ``fit.kl`` gauge.
    """
    x = jnp.asarray(x, config.dtype)
    n = x.shape[0]
    lr = config.resolve_lr(n)
    if tracer is None:
        tracer = obs.get_tracer()
    if metrics is None:
        metrics = obs.get_metrics()
    timer = tracer if tracer.enabled else obs.Tracer()
    n_early = min(config.exaggeration_iters, config.n_iter)
    phases = (("early_exaggeration", 0, n_early, config.early_exaggeration),
              ("main_phase", n_early, config.n_iter, 1.0))

    with timer.span("fit", n=int(n), method=config.method,
                    neighbor_method=config.neighbor_method):
        graph, timings = preprocess(x, config, tracer=timer)
        state = init_state(n, config)

        if backend is None:
            from repro.api.backends import make_backend  # lazy: api builds on core
            backend = make_backend(config.method, config, n)
        step_kw = dict(backend=backend, lr=lr, min_gain=config.min_gain)

        kl_hist = []
        max_walk: list[int] = []
        mean_walk: list[float] = []
        spans: list[float] = []
        kl = float("nan")
        it = 0
        converged = False
        with timer.span("gradient_descent", n_iter=config.n_iter,
                        lr=lr) as sp_gd:
            for phase, start, stop, exag in phases:
                if start >= stop or converged:
                    continue
                with timer.span(phase, start_iter=start,
                                exaggeration=exag) as sp_phase:
                    for it in range(start, stop):
                        mom = config.momentum_initial \
                            if it < config.momentum_switch_iter \
                            else config.momentum_final
                        with timer.span("step", step_num=it + 1):
                            state, stats = tsne_step(
                                state, graph,
                                jnp.asarray(exag, config.dtype),
                                jnp.asarray(mom, config.dtype),
                                **step_kw,
                            )
                        if (it + 1) % kl_every and it != config.n_iter - 1:
                            continue
                        with timer.span("checkpoint",
                                        iteration=it + 1) as sp_ck:
                            got = jax.device_get(stats)     # one pull
                            kl = float(got.kl)
                            grad_norm = float(got.grad_norm)
                            z = float(got.z)
                            kl_hist.append((it + 1, kl))
                            max_walk.append(int(got.max_traversal))
                            mean_walk.append(float(got.mean_traversal))
                            spans.append(float(got.fft_span))
                            metrics.histogram("fit.grad_norm").observe(
                                grad_norm)
                            metrics.gauge("fit.kl").set(kl)
                            sp_ck.annotate(kl=kl, grad_norm=grad_norm, z=z,
                                           exaggeration=exag, momentum=mom)
                            if timer is tracer:
                                # trace-only extra (one more device pull)
                                gain_mean = float(jnp.mean(state.gains))
                                metrics.histogram("fit.gain_mean").observe(
                                    gain_mean)
                                sp_ck.annotate(gain_mean=gain_mean)
                            if observer is not None:
                                observer(IterationStats(
                                    iteration=it + 1, kl=kl,
                                    grad_norm=grad_norm, z=z,
                                    max_traversal=max_walk[-1],
                                    exaggeration=exag, momentum=mom,
                                    elapsed_s=time.perf_counter() - sp_gd.t0,
                                    mean_traversal=mean_walk[-1],
                                    fft_span=spans[-1],
                                ))
                        if grad_norm < config.min_grad_norm:
                            converged = True
                            break
                    sp_phase.sync(state.y)
            sp_gd.sync(state.y)
        timings["gradient_descent"] = sp_gd.duration_s
        timings["max_traversal"] = max_walk
        timings["mean_traversal"] = mean_walk
        timings["fft_span"] = spans
        metrics.counter("fit.iterations").inc(it + 1)
    return TsneResult(
        y=np.asarray(state.y),
        kl=kl,
        kl_history=np.asarray(kl_hist, np.float64) if kl_hist else np.zeros((0, 2)),
        timings=timings,
        n_iter=it + 1,
        graph=graph,
    )
