"""What decides ``correct``: one fit of the window, judged against the plain
reference at the state that fit reached.

The program's side is read from the timed path itself: the fit's symmetric
P (``neighbor_graph_``); its gradient at the fitted embedding from the
compiled ``tsne_step`` that the window drove, called again with the same
static arguments (so the same executable) at velocity 0, gains 1 and
momentum 0, where the new velocity is ``-lr * gains' * grad`` (called at
exaggeration 0 it gives the repulsive term alone; at the fit's last
exaggeration E the difference gives the attractive force); one more call of
that step from a probe state drawn from the seed (a velocity and gains that
are not 0 and 1, with E and the fit's last momentum), whose new embedding,
velocity and gains must be the configuration's update rule applied to that
gradient; and the KL at each checkpoint of the fit, which the reference's
exact descent from the same seeded start must follow.

The gradient is held to the reference's forces, and the step to the rule
on the gradient it computed: near convergence the gradient is a small
difference of two large terms, and there Barnes-Hut's error in the
repulsion would swamp an update built on the reference's forces.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from chipbench import reference


def schedule(config: dict) -> dict:
    """The configuration's descent: its ``descent`` block, with the fit's
    exaggeration and learning rate."""
    tsne = config["tsne"]
    return {**config["descent"],
            "early_exaggeration": float(tsne["early_exaggeration"]),
            "lr": float(tsne["learning_rate"])}


def last_phase(sched: dict, n_iter: int) -> tuple[float, float]:
    """Exaggeration and momentum of a fit's last iteration."""
    it = n_iter - 1
    return (sched["early_exaggeration"] if it < sched["exaggeration_iters"]
            else 1.0,
            sched["momentum"][0] if it < sched["momentum_switch_iter"]
            else sched["momentum"][1])


def program_side(est, sched: dict, rng: np.random.Generator,
                 kl_path: dict | None = None) -> tuple[reference.Side, dict]:
    """P, forces, Z, KL, the probe step and the descent of the fitted
    estimator, from its own step; and the probe it was given."""
    import jax.numpy as jnp

    from repro.api import make_backend
    from repro.core.tsne import TsneState, tsne_step

    y = np.asarray(est.embedding_)
    n = y.shape[0]
    config = est._build_config(n)
    backend = make_backend(config.method, config, n)
    lr = est.learning_rate_
    exaggeration, momentum = last_phase(sched, est.n_iter_)
    y0 = jnp.asarray(y, config.dtype)
    f = lambda a: jnp.asarray(a, config.dtype)  # noqa: E731

    def step(velocity, gains, e, m):
        state = TsneState(y=y0, velocity=f(velocity), gains=f(gains),
                          iteration=jnp.zeros((), jnp.int32))
        return tsne_step(state, est.neighbor_graph_, f(e), f(m),
                         backend=backend, lr=lr, min_gain=config.min_gain)

    grads, stats = [], None
    for e in (0.0, exaggeration):
        new, stats = step(np.zeros_like(y), np.ones_like(y), e, 0.0)
        grads.append(-np.asarray(new.velocity, np.float64)
                     / (lr * np.asarray(new.gains, np.float64)))
    # a velocity of the size of a step (of the embedding's, where the
    # gradient reads 0)
    scale = (sched["lr"] * np.sqrt(np.mean(grads[1] ** 2))
             or np.sqrt(np.mean(y ** 2)))
    probe = {"velocity": scale * rng.standard_normal(y.shape).astype(np.float32),
             "gains": rng.uniform(0.1, 3.0, y.shape).astype(np.float32),
             "exaggeration": exaggeration, "momentum": momentum,
             "lr": sched["lr"], "min_gain": sched["min_gain"]}
    new, _ = step(probe["velocity"], probe["gains"], exaggeration, momentum)
    g = est.neighbor_graph_
    cols = np.asarray(g.p_cols)
    p = sp.csr_matrix((np.asarray(g.p_vals, np.float64).ravel(),
                       (np.repeat(np.arange(n), cols.shape[1]), cols.ravel())),
                      shape=(n, n))
    p.eliminate_zeros()
    side = reference.Side(
        p=p, attr=(grads[1] - grads[0]) / (4 * exaggeration), rep=grads[0],
        z=float(stats.z), kl=float(stats.kl),
        step={"from": y, "probe": probe, "grad": grads[1],
              **{k: np.asarray(getattr(new, k), np.float64)
                 for k in ("y", "velocity", "gains")}},
        descent={"y": y}, kl_path=kl_path)
    return side, probe


def reference_side(x, y, cell_config: dict, probe: dict, random_state: int,
                   n_iter: int, dtype, descend: bool = True) -> reference.Side:
    """The reference (``dtype`` float32) or the control (bfloat16) in the
    program's place: its graph and forces at the fitted embedding ``y``, its
    update from the probe, and (with ``descend``) its exact descent from the
    seeded start."""
    tsne = cell_config["tsne"]
    sched = schedule(cell_config)
    p = reference.graph(x, float(tsne["perplexity"]), int(tsne["n_neighbors"]),
                        dtype)
    side = reference.forces(y, p, dtype)
    side.step = reference.probe_step(side, y, probe, dtype)
    if descend:
        y0 = reference.init(random_state, y.shape[0], sched["init_std"])
        yd, kls = reference.descent(p, y0, sched, n_iter, dtype)
        side.descent = {"from": np.asarray(y0, np.float64), "y": yd}
        side.kl_path = kls
    return side


def rule(step: dict) -> dict:
    """The configuration's update from the step's probe state on the step's
    own gradient, in float32 (the configuration's precision)."""
    import jax.numpy as jnp

    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    pr = step["probe"]
    out = reference.update(f(step["from"]), f(pr["velocity"]), f(pr["gains"]),
                           f(step["grad"]), lr=pr["lr"],
                           momentum=pr["momentum"], min_gain=pr["min_gain"])
    return {"from": step["from"], **{k: np.asarray(a, np.float64) for k, a in
                                     zip(("y", "velocity", "gains"), out)}}


def _rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare(got: reference.Side, ref: reference.Side) -> dict[str, float]:
    """The numbers a cell's ``limits/<cell>.json`` may name:

    p_l1          sum |P - P_ref| (each P sums to 1)
    p_row_max     the worst row's sum |P - P_ref| over that row's P_ref mass
    attr_rel      |F_attr - F_attr_ref| / |F_attr_ref|
    rep_rel       |rep - rep_ref| / |rep_ref|, rep = -4 F_rep / Z
    z_rel         |Z - Z_ref| / Z_ref
    kl_gap        |KL - KL_ref|, nats
    step_rel      the probe step's new embedding against the update rule
                  applied to the step's own gradient: |y' - y'_rule| /
                  |y'_rule - y|
    velocity_rel  its new velocity: |v' - v'_rule| / |v'_rule|
    gains_rel     its new gains: |g' - g'_rule| / |g'_rule|
    descent_rel   the fitted embedding against the reference's exact descent
                  from the same seeded start: |Y - Y_ref| / |Y_ref - Y_0|
    kl_path_gap   the widest |KL - KL_ref| over the fit's checkpoints
    """
    d = abs(got.p - ref.p)
    row_ref = np.asarray(ref.p.sum(axis=1)).ravel()
    out = {
        "p_l1": float(d.sum()),
        "p_row_max": float(np.max(np.asarray(d.sum(axis=1)).ravel() / row_ref)),
        "attr_rel": _rel(got.attr, ref.attr),
        "rep_rel": _rel(got.rep, ref.rep),
        "z_rel": abs(got.z - ref.z) / ref.z,
        "kl_gap": abs(got.kl - ref.kl),
    }
    if got.step is not None:
        s, r = got.step, rule(got.step)
        out["step_rel"] = float(np.linalg.norm(s["y"] - r["y"])
                                / np.linalg.norm(r["y"] - r["from"]))
        out["velocity_rel"] = _rel(s["velocity"], r["velocity"])
        out["gains_rel"] = _rel(s["gains"], r["gains"])
    if got.descent is not None and ref.descent is not None:
        out["descent_rel"] = float(
            np.linalg.norm(got.descent["y"] - ref.descent["y"])
            / np.linalg.norm(ref.descent["y"] - ref.descent["from"]))
    if got.kl_path and ref.kl_path:
        common = set(got.kl_path) & set(ref.kl_path)
        if common:
            out["kl_path_gap"] = max(abs(got.kl_path[i] - ref.kl_path[i])
                                     for i in common)
    return out


def verdict(numbers: dict[str, float], limits: dict[str, float]) -> bool:
    """Every limited number present, finite and within its limit."""
    return all(name in numbers and np.isfinite(numbers[name])
               and numbers[name] <= limit for name, limit in limits.items())
