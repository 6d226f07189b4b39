"""Mixture-of-Experts FF with expert parallelism (GShard-style capacity).

Routing: softmax router (f32), top-k experts per token, renormalized gates.
Dispatch: per-expert top-capacity token selection — each expert picks its
``capacity`` highest-gate tokens (tokens beyond capacity are dropped, the
standard GShard semantics).  Unrouted slots gather token 0 with gate 0, so
they contribute nothing — no masks needed.

Parallelism: experts are sharded over the ``model`` mesh axis.  Under
``shard_map`` each model shard computes only its local experts against the
(replicated-over-model) token block and the partial outputs are ``psum``-ed —
i.e. expert parallelism with an all-reduce combine.  Without a mesh the same
code runs with all experts local (smoke tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import layers
from repro.models.config import ModelConfig


def init_moe(rng, cfg: ModelConfig, dtype) -> dict:
    d, E, fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    r = jax.random.split(rng, 5)
    p = {
        "router": layers.init_dense(r[0], d, E, jnp.float32),
        "gate": (jax.random.normal(r[1], (E, d, fe)) * d**-0.5).astype(dtype),
        "up": (jax.random.normal(r[2], (E, d, fe)) * d**-0.5).astype(dtype),
        "down": (jax.random.normal(r[3], (E, fe, d)) * fe**-0.5).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = layers.init_mlp(
            r[4], d, cfg.n_shared_experts * fe, dtype
        )
    return p


def _route(cfg: ModelConfig, router_w, x_flat):
    """x_flat: (T, d) -> gates (T, E) f32 with top-k renormalized weights."""
    logits = (x_flat.astype(jnp.float32) @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_v, top_i = jax.lax.top_k(probs, cfg.top_k)             # (T, k)
    top_v = top_v / jnp.maximum(jnp.sum(top_v, -1, keepdims=True), 1e-9)
    gates = jnp.zeros_like(probs)
    T = probs.shape[0]
    gates = gates.at[jnp.arange(T)[:, None], top_i].set(top_v)
    return gates                                               # (T, E)


def _expert_compute(cfg: ModelConfig, gates_loc, x_flat, gate_w, up_w, down_w):
    """gates_loc: (T, E_loc) f32; x_flat: (T, d); weights (E_loc, d|fe, ...).

    Each local expert selects its top-``capacity`` tokens by gate weight and
    runs a SwiGLU FF on the gathered block; results scatter-add back.
    """
    T = x_flat.shape[0]
    E_loc = gates_loc.shape[1]
    cap = min(
        T,
        max(8, int(T * cfg.top_k * cfg.capacity_factor / max(cfg.n_experts, 1))),
    )
    w_sel, idx = jax.lax.top_k(gates_loc.T, cap)               # (E_loc, cap)
    xe = x_flat[idx.reshape(-1)].reshape(E_loc, cap, -1)       # (E_loc, cap, d)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, gate_w))
    h = g * jnp.einsum("ecd,edf->ecf", xe, up_w)
    out_e = jnp.einsum("ecf,efd->ecd", h, down_w)              # (E_loc, cap, d)
    out_e = out_e * w_sel[..., None].astype(out_e.dtype)
    out = jnp.zeros_like(x_flat)
    return out.at[idx.reshape(-1)].add(out_e.reshape(E_loc * cap, -1))


def _moe_local(cfg: ModelConfig, params: dict, x: jax.Array, axis: Optional[str]):
    """Runs on one model shard (or the whole device when axis is None)."""
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    gates = _route(cfg, params["router"], x_flat)              # (T, E) global

    if axis is None:
        gates_loc = gates
    else:
        n_shards = jax.lax.axis_size(axis)
        e_loc = cfg.n_experts // n_shards
        e0 = jax.lax.axis_index(axis) * e_loc
        gates_loc = jax.lax.dynamic_slice_in_dim(gates, e0, e_loc, axis=1)

    out = _expert_compute(
        cfg, gates_loc, x_flat, params["gate"], params["up"], params["down"]
    )
    if axis is not None:
        out = jax.lax.psum(out, axis)
    return out.reshape(B, S, d)


def moe_ff(
    cfg: ModelConfig,
    params: dict,
    x: jax.Array,
    mesh: Optional[Mesh] = None,
    dp_axes: tuple = (),
) -> jax.Array:
    """(B, S, d) -> (B, S, d) MoE feed-forward (+ shared experts)."""
    if mesh is not None and "model" in mesh.axis_names:
        routed = jax.shard_map(
            lambda p, xx: _moe_local(cfg, p, xx, "model"),
            mesh=mesh,
            in_specs=(
                {
                    "router": P(),
                    "gate": P("model", None, None),
                    "up": P("model", None, None),
                    "down": P("model", None, None),
                },
                P(dp_axes, None, None),
            ),
            out_specs=P(dp_axes, None, None),
            check_vma=False,
        )({k: params[k] for k in ("router", "gate", "up", "down")}, x)
    else:
        routed = _moe_local(
            cfg, {k: params[k] for k in ("router", "gate", "up", "down")}, x, None
        )
    if cfg.n_shared_experts:
        routed = routed + layers.mlp(params["shared"], x)
    return routed
