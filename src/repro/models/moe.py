"""Mixture-of-Experts FFN with expert parallelism via shard_map.

EP strategy (DESIGN.md §5): token activations are replicated over the
"model" mesh axis at the MoE boundary, experts are sharded over it.  Each
device gathers (up to capacity C) the tokens routed to *its* local experts —
zero dispatch communication — computes the expert FFNs, scatters back, and a
single psum over "model" combines, i.e. the same collective footprint as a
tensor-parallel FFN.  Shared experts run as an ordinary TP SwiGLU outside
the shard_map.

Routers: 'softmax' (DeepSeek-V2: softmax then top-k, aux load-balance loss)
and 'sigmoid_bias' (DeepSeek-V3: sigmoid scores, bias-adjusted top-k
selection, aux-free; the bias is a non-trainable param updated from expert
load by the trainer).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.configs.base import ArchConfig
from repro.distributed.sharding import active_rules, logical
from repro.models.layers import init_dense, swiglu


def init_moe(key, cfg: ArchConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    shared_ff = m.shared_d_ff or m.n_shared * m.expert_d_ff
    return {
        "router": {"w": init_dense(ks[0], (d, m.n_experts), jnp.float32),
                   "bias": jnp.zeros((m.n_experts,), jnp.float32)},
        "experts": {
            "w1": init_dense(ks[1], (m.n_experts, d, m.expert_d_ff), dtype),
            "w3": init_dense(ks[2], (m.n_experts, d, m.expert_d_ff), dtype),
            "w2": init_dense(ks[3], (m.n_experts, m.expert_d_ff, d), dtype,
                             scale=m.expert_d_ff**-0.5 / (2 * cfg.n_layers) ** 0.5),
        },
        "shared": {
            "w1": init_dense(ks[4], (d, shared_ff), dtype),
            "w3": init_dense(ks[5], (d, shared_ff), dtype),
            "w2": init_dense(ks[6], (shared_ff, d), dtype,
                             scale=shared_ff**-0.5 / (2 * cfg.n_layers) ** 0.5),
        },
    }


def _route(x_flat, router_w, router_bias, cfg: ArchConfig):
    """Full-E routing decision. Returns (weights [t,k], idx [t,k], probs [t,E])."""
    m = cfg.moe
    logits = (x_flat.astype(jnp.float32) @ router_w).astype(jnp.float32)
    if m.router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        sel_scores = scores + router_bias[None, :]
        _, idx = jax.lax.top_k(sel_scores, m.top_k)
        w = jnp.take_along_axis(scores, idx, axis=1)
        if m.norm_topk_prob:
            w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-20)
        probs = scores
    else:
        probs = jax.nn.softmax(logits, axis=1)
        w, idx = jax.lax.top_k(probs, m.top_k)
        if m.norm_topk_prob:
            w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-20)
    return w, idx, probs


def _routed_ffn_local(x_flat, gate, w1, w3, w2, capacity: int):
    """Capacity-C per-expert gather -> SwiGLU -> weighted scatter-add.

    x_flat [t, d]; gate [t, E_loc] (combine weight, 0 if not routed);
    expert weights [E_loc, d, f] / [E_loc, f, d].
    """
    t = x_flat.shape[0]
    c = min(capacity, t)
    sel_w, sel_idx = jax.lax.top_k(gate.T, c)            # [E_loc, C]
    x_sel = x_flat[sel_idx]                              # [E_loc, C, d]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_sel, w1)) * jnp.einsum("ecd,edf->ecf", x_sel, w3)
    y = jnp.einsum("ecf,efd->ecd", h, w2)
    y = y * sel_w[..., None].astype(y.dtype)
    out = jnp.zeros_like(x_flat).at[sel_idx.reshape(-1)].add(y.reshape(-1, x_flat.shape[1]))
    return out


def _gate_matrix(weights, idx, e_offset, e_loc: int):
    """[t, E_loc] combine-weight matrix for this shard's expert range."""
    local = idx[..., None] - e_offset                    # [t, k, 1]
    onehot = (local == jnp.arange(e_loc)[None, None, :]).astype(weights.dtype)
    return jnp.einsum("tk,tke->te", weights, onehot)


def _moe_shard(x, router_w, router_bias, w1, w3, w2, *, cfg: ArchConfig,
               capacity: int, axis: str):
    b, s, d = x.shape
    x_flat = x.reshape(-1, d)
    weights, idx, probs = _route(x_flat, router_w, router_bias, cfg)
    e_loc = w1.shape[0]
    e_offset = jax.lax.axis_index(axis) * e_loc
    gate = _gate_matrix(weights, idx, e_offset, e_loc)
    y = _routed_ffn_local(x_flat, gate, w1, w3, w2, capacity)
    y = jax.lax.psum(y, axis)
    # aux load-balance statistics (global over the data axes happens outside)
    m = cfg.moe
    load = jnp.mean(jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32), axis=(0, 1)) * m.n_experts
    imp = jnp.mean(probs, axis=0)
    aux = m.n_experts * jnp.sum(load / m.n_experts * imp)
    return y.reshape(b, s, d), aux, load


def moe_block(params, x, cfg: ArchConfig):
    """Returns (out, aux) where aux = {'aux_loss', 'expert_load'}."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    rules = active_rules()
    router = params["router"]
    ex = params["experts"]
    shared_out = swiglu(x, **params["shared"])

    ep_axis = rules.axis("experts") if rules is not None else None
    if ep_axis is not None and rules.mesh.shape[ep_axis] > 1 and m.n_experts % rules.mesh.shape[ep_axis] == 0:
        ep = rules.mesh.shape[ep_axis]
        t_local = t // _dp_size(rules)
        capacity = max(1, int(t_local * m.top_k / m.n_experts * m.capacity_factor))
        batch_ax = rules.axis("batch")
        fn = functools.partial(_moe_shard, cfg=cfg, capacity=capacity, axis=ep_axis)
        y, aux, load = shard_map(
            fn,
            mesh=rules.mesh,
            in_specs=(P(batch_ax, None, None), P(), P(),
                      P(ep_axis, None, None), P(ep_axis, None, None), P(ep_axis, None, None)),
            out_specs=(P(batch_ax, None, None), P(), P()),
            check_vma=False,
        )(x, router["w"], router["bias"], ex["w1"], ex["w3"], ex["w2"])
    else:
        capacity = max(1, int(t * m.top_k / m.n_experts * m.capacity_factor))
        x_flat = x.reshape(-1, d)
        weights, idx, probs = _route(x_flat, router["w"], router["bias"], cfg)
        gate = _gate_matrix(weights, idx, 0, m.n_experts)
        y = _routed_ffn_local(x_flat, gate, ex["w1"], ex["w3"], ex["w2"], capacity).reshape(b, s, d)
        load = jnp.mean(jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32), axis=(0, 1)) * m.n_experts
        imp = jnp.mean(probs, axis=0)
        aux = m.n_experts * jnp.sum(load / m.n_experts * imp)

    out = shared_out + y.astype(x.dtype)
    return out, {"aux_loss": aux.astype(jnp.float32), "expert_load": load}


def _dp_size(rules):
    ax = rules.axis("batch")
    if ax is None:
        return 1
    axes = (ax,) if isinstance(ax, str) else ax
    size = 1
    for a in axes:
        size *= rules.mesh.shape[a]
    return size
