"""Plain t-SNE reference: exact KNN, perplexity search, symmetric P, and the
exact attractive and repulsive forces at a given embedding.

It imports nothing of the program and takes nothing the program made except
the embedding it is asked to judge.  Everything runs in ``dtype``: float32
(the configuration's precision, matrix products at ``HIGHEST``) for the
reference, bfloat16 for the control.  The O(N^2) parts run in blocks of
rows, so they fit beside nothing else on one chip.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp


def _rows(n: int, block: int):
    return -(-n // block), (-n) % block


@functools.partial(jax.jit, static_argnames=("k", "block", "chunk", "dtype"))
def knn(x, *, k: int, block: int = 512, chunk: int = 4096, dtype):
    """The k nearest neighbors of every row (self excluded): (idx, d2).

    Rows in blocks; for each block the candidates stream past in chunks of
    the points, and the k best so far are kept (a sort over whole rows of
    70,000 distances is slow on a TPU)."""
    x = x.astype(dtype)
    n = x.shape[0]
    nb, pad = _rows(n, block)
    nc, cpad = _rows(n, chunk)
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    sq = jnp.sum(x * x, axis=1)
    xq, sqq = jnp.pad(x, ((0, pad), (0, 0))), jnp.pad(sq, (0, pad))
    xc, sqc = jnp.pad(x, ((0, cpad), (0, 0))), jnp.pad(sq, (0, cpad))
    inf = jnp.asarray(jnp.inf, dtype)

    def one(b):
        q = jax.lax.dynamic_slice_in_dim(xq, b * block, block)
        qn = jax.lax.dynamic_slice_in_dim(sqq, b * block, block)
        me = b * block + jnp.arange(block)

        def scan(best, c):
            db = jax.lax.dynamic_slice_in_dim(xc, c * chunk, chunk)
            dn = jax.lax.dynamic_slice_in_dim(sqc, c * chunk, chunk)
            cols = c * chunk + jnp.arange(chunk)
            d2 = qn[:, None] + dn[None, :] - 2 * jnp.dot(q, db.T, precision=prec)
            d2 = jnp.where((cols[None, :] == me[:, None]) | (cols[None, :] >= n),
                           inf, d2)
            cat_d = jnp.concatenate([best[0], d2], axis=1)
            cat_i = jnp.concatenate(
                [best[1], jnp.broadcast_to(cols[None, :], d2.shape)], axis=1)
            neg, arg = jax.lax.top_k(-cat_d, k)
            return (-neg, jnp.take_along_axis(cat_i, arg, axis=1)), None

        init = (jnp.full((block, k), inf), jnp.full((block, k), -1, jnp.int32))
        (d2, idx), _ = jax.lax.scan(scan, init, jnp.arange(nc))
        return idx, d2

    idx, d2 = jax.lax.map(one, jnp.arange(nb))
    return idx.reshape(-1, k)[:n], jnp.maximum(d2.reshape(-1, k)[:n], 0)


@functools.partial(jax.jit, static_argnames=("dtype", "iters"))
def conditional_p(d2, perplexity, *, dtype, iters: int = 100):
    """p_{j|i} over each row's neighbors, bisected on the Gaussian's
    precision until the row's entropy is log(perplexity) (scikit-learn's
    search; distances shifted by the row minimum, which p does not see)."""
    d2 = d2.astype(dtype)
    d2 = d2 - jnp.min(d2, axis=1, keepdims=True)
    target = jnp.log(jnp.asarray(perplexity, dtype))

    def entropy(beta):
        p = jnp.exp(-d2 * beta)
        s = jnp.sum(p, axis=1, keepdims=True)
        return jnp.log(s) + beta * jnp.sum(d2 * p, axis=1, keepdims=True) / s, p / s

    def body(_, c):
        beta, lo, hi = c
        h, _ = entropy(beta)
        wide = h > target                    # too flat: raise the precision
        lo = jnp.where(wide, beta, lo)
        hi = jnp.where(wide, hi, beta)
        beta = jnp.where(wide,
                         jnp.where(jnp.isinf(hi), beta * 2, (beta + hi) / 2),
                         (beta + lo) / 2)
        return beta, lo, hi

    one = jnp.ones((d2.shape[0], 1), dtype)
    beta, _, _ = jax.lax.fori_loop(
        0, iters, body, (one, 0 * one, jnp.full_like(one, jnp.inf)))
    return entropy(beta)[1]


def joint_p(idx, cond_p) -> sp.csr_matrix:
    """Symmetric P = (p_{j|i} + p_{i|j}) / 2N as a sparse matrix summing to 1."""
    idx = np.asarray(idx)
    n, k = idx.shape
    a = sp.csr_matrix((np.asarray(cond_p, np.float64).ravel(),
                       (np.repeat(np.arange(n), k), idx.ravel())), shape=(n, n))
    p = (a + a.T).tocsr()
    p.eliminate_zeros()
    return p / p.sum()


@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def repulsion(y, *, block: int, dtype):
    """(sum_j q_ij^2 (y_i - y_j) unnormalized, Z = sum_{i!=j} q_ij), with
    q_ij = 1 / (1 + |y_i - y_j|^2)."""
    y = y.astype(dtype)
    n = y.shape[0]
    nb, pad = _rows(n, block)
    yp = jnp.pad(y, ((0, pad), (0, 0)))

    def one(b):
        yb = jax.lax.dynamic_slice_in_dim(yp, b * block, block)
        me = b * block + jnp.arange(block)
        diff = yb[:, None, :] - y[None, :, :]
        w = 1 / (1 + jnp.sum(diff * diff, axis=-1))
        w = jnp.where((me[:, None] == jnp.arange(n)[None, :])
                      | (me[:, None] >= n), 0, w)
        return jnp.sum((w * w)[..., None] * diff, axis=1), jnp.sum(w)

    f, z = jax.lax.map(one, jnp.arange(nb))
    return f.reshape(-1, 2)[:n], jnp.sum(z)


@functools.partial(jax.jit, static_argnames=("dtype",))
def attraction(y, rows, cols, vals, *, dtype):
    """(sum_j p_ij q_ij (y_i - y_j), sum_ij p_ij log(1 + |y_i - y_j|^2))."""
    y = y.astype(dtype)
    vals = vals.astype(dtype)
    diff = y[rows] - y[cols]
    d2 = jnp.sum(diff * diff, axis=1)
    f = jax.ops.segment_sum((vals / (1 + d2))[:, None] * diff, rows,
                            num_segments=y.shape[0])
    return f, jnp.sum(vals * jnp.log1p(d2))


def update(y, velocity, gains, grad, *, lr, momentum, min_gain):
    """One step of the configuration's descent rule (scikit-learn's): the
    gain of a coordinate grows by 0.2 where the gradient's sign differs from
    the velocity's (a sign is that of ``> 0``) and shrinks by 0.8 where it
    agrees, never below ``min_gain``; then momentum, and the embedding
    re-centred on 0."""
    grow = (grad > 0) != (velocity > 0)
    gains = jnp.maximum(jnp.where(grow, gains + 0.2, gains * 0.8), min_gain)
    velocity = momentum * velocity - lr * gains * grad
    y = y + velocity
    return y - jnp.mean(y, axis=0, keepdims=True), velocity, gains


def init(random_state: int, n: int, std: float):
    """The configuration's starting embedding: ``std`` times a standard
    normal draw of ``jax.random`` from the fit's ``random_state``."""
    return std * jax.random.normal(jax.random.PRNGKey(random_state), (n, 2),
                                   jnp.float32)


@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def _descent_step(y, v, g, rows, cols, vals, exaggeration, momentum, lr,
                  min_gain, *, block: int, dtype):
    attr, kl_attr = attraction(y, rows, cols, vals, dtype=dtype)
    f_rep, z = repulsion(y, block=block, dtype=dtype)
    grad = 4 * (exaggeration.astype(dtype) * attr - f_rep / z)
    y, v, g = update(y, v, g, grad, lr=lr.astype(dtype),
                     momentum=momentum.astype(dtype), min_gain=min_gain)
    return y, v, g, kl_attr + jnp.log(z)


@dataclasses.dataclass
class Side:
    """What one side (program, reference or control) says at one embedding."""
    p: sp.csr_matrix        # symmetric P
    attr: np.ndarray        # attractive force sum_j p_ij q_ij (y_i - y_j)
    rep: np.ndarray         # repulsive gradient term -4 F_rep / Z
    z: float
    kl: float
    # one step from the check's probe state: the state it started from, the
    # gradient, and the new embedding, velocity and gains
    step: dict | None = None
    # the embedding after the fit's iterations from the seeded start, and
    # the KL at each iteration's start (1-based)
    descent: np.ndarray | None = None
    kl_path: dict | None = None


def _edges(p: sp.csr_matrix):
    coo = p.tocoo()
    # the edge count moves by a few between seeds (near-tied neighbors);
    # padding it to a multiple of 2**16 with zero edges keeps one program
    pad = (-coo.nnz) % 2**16
    rows, cols, vals = (np.pad(a, (0, pad)) for a in (coo.row, coo.col, coo.data))
    return (jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
            jnp.asarray(vals, jnp.float32))


def graph(x, perplexity: float, k: int, dtype) -> sp.csr_matrix:
    """Symmetric P of points ``x``, every step in ``dtype``."""
    idx, d2 = knn(jnp.asarray(x), k=k, dtype=dtype)
    return joint_p(idx, conditional_p(d2, perplexity, dtype=dtype))


def forces(y, p: sp.csr_matrix, dtype, block: int = 512) -> Side:
    """Attractive force, repulsive term, Z and KL of P at embedding ``y``."""
    y = jnp.asarray(y, jnp.float32)
    attr, kl_attr = attraction(y, *_edges(p), dtype=dtype)
    f_rep, z = repulsion(y, block=block, dtype=dtype)
    z = float(z)
    return Side(p=p, attr=np.asarray(attr, np.float64),
                rep=-4.0 * np.asarray(f_rep, np.float64) / z, z=z,
                kl=p_logp(p) + float(kl_attr) + float(np.log(z)))


def p_logp(p: sp.csr_matrix) -> float:
    return float(np.sum(p.data * np.log(p.data)))


def probe_step(side: Side, y, probe: dict, dtype) -> dict:
    """The side's own step from the probe state (``velocity``, ``gains``,
    ``exaggeration``, ``momentum``, ``lr``, ``min_gain``) at ``y``: its
    gradient there, and the new embedding, velocity and gains, in ``dtype``."""
    grad = 4 * probe["exaggeration"] * side.attr + side.rep
    out = update(jnp.asarray(y, dtype), jnp.asarray(probe["velocity"], dtype),
                 jnp.asarray(probe["gains"], dtype), jnp.asarray(grad, dtype),
                 lr=probe["lr"], momentum=probe["momentum"],
                 min_gain=probe["min_gain"])
    return {"from": np.asarray(y, np.float64), "probe": probe, "grad": grad,
            **{k: np.asarray(a, np.float64)
               for k, a in zip(("y", "velocity", "gains"), out)}}


def descent(p: sp.csr_matrix, y0, schedule: dict, n_iter: int, dtype,
            block: int = 512) -> tuple[np.ndarray, dict]:
    """``n_iter`` steps of exact-gradient descent from ``y0`` under the
    configuration's schedule: the embedding, and the KL at each step's start."""
    edges = _edges(p)
    const = p_logp(p)
    y = jnp.asarray(y0, dtype)
    v, g = jnp.zeros_like(y), jnp.ones_like(y)
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    kls = {}
    for it in range(n_iter):
        early = it < schedule["exaggeration_iters"]
        y, v, g, kl = _descent_step(
            y, v, g, *edges,
            f(schedule["early_exaggeration"] if early else 1.0),
            f(schedule["momentum"][0] if it < schedule["momentum_switch_iter"]
              else schedule["momentum"][1]),
            f(schedule["lr"]), schedule["min_gain"], block=block, dtype=dtype)
        kls[it + 1] = kl
    return (np.asarray(y, np.float64),
            {k: const + float(v) for k, v in kls.items()})


def side(x, y, perplexity: float, k: int, dtype) -> Side:
    """The reference (``dtype`` float32) or the control (bfloat16) for
    points ``x`` at embedding ``y``."""
    return forces(y, graph(x, perplexity, k, dtype), dtype)
