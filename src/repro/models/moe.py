"""Mixture-of-Experts layer: a router over ``n_experts`` routed experts
(SwiGLU of width ``cfg.expert_d_ff``), optionally beside shared experts.

Routing is token-choice top-k. ``router_scoring="softmax"`` (the default)
gates by the router softmax of the chosen experts; the softmax goes
through the plan's softmax slot, the Compute-ACAM dataflow in raceit
mode. ``"sigmoid"`` is deepseek-v3's ``noaux_tc``: the top_k of sigmoid
scores plus a per-expert correction bias (the ``router_bias`` leaf) are
chosen, and gated by their unbiased scores. Either way the chosen gates
are normalized to sum 1 and scaled by ``routed_scaling_factor``. The sigmoid router's logits, selection and
gates stay float32 (``Precision.HIGHEST``) in every mode: a flipped
choice is not a rounding error, so the selection is kept out of the
analog softmax and tables.

One device (no mesh) runs the **dropless** layer: every row routed to an
expert is computed by it, whatever the load, so a request gives the same
result served among others as alone. The layer holds the experts
``cfg.held_experts`` (one expert-parallel rank's share; all by default)
and adds only their part of each row's result: the router still scores
all ``n_experts``, and a choice of an expert held elsewhere adds nothing
here. Each held expert's three matmuls run through the plan's matmul slot
(int8 codes when its stacks are resident `QuantizedWeight`s) and its SiLU
through the activation slot, over the rows masked to those routed to it.
Shared experts (``n_shared_experts``) are one SwiGLU of ``n x
expert_d_ff`` on every row.

On a mesh (shard_map) two distribution modes keep a static-capacity
dispatch, C = ceil(k * T_local * capacity_factor / E), where overflow
choices drop (their residual passes through) and underflow slots compute
on zeros, until a four-chip cell needs them dropless:

* **TP-in-expert** (default, any expert count): expert weights are
  sharded on the expert width over "model"; every shard routes and
  dispatches its local tokens, computes partial expert outputs, combines
  locally, and one psum over "model" finishes the row-parallel matmul.
* **EP** (``expert_parallel=True``, experts % model_size == 0): experts
  are sharded over "model"; capacity-dispatched token blocks are
  exchanged with two all_to_alls (dispatch and return), no psum.

The mesh modes hold every expert and count no routed rows.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ExecConfig, ModelConfig
from repro.dist.sharding import MeshContext, shard_map
from repro.exec.plan import ExecPlan, as_plan
from jax.sharding import PartitionSpec as P

from . import layers

Params = dict


def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 5)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    n = len(cfg.held_experts)
    p = {
        "router": layers._dense_init(ks[0], (D, E), jnp.float32),
        "w1": layers._dense_init(ks[1], (n, D, F), dtype),
        "w2": layers._dense_init(ks[2], (n, F, D), dtype, fan_in=F),
    }
    if cfg.glu:
        p["w3"] = layers._dense_init(ks[3], (n, D, F), dtype)
    if cfg.router_scoring == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = layers.init_ffn(
            ks[4], cfg.replace(d_ff=cfg.n_shared_experts * F), dtype)
    return p


def route(p: Params, xf: jax.Array, cfg: ModelConfig, plan: ExecPlan):
    """(expert ids, gates), each (T, top_k), of the rows ``xf`` (T, D)."""
    K = cfg.top_k
    if cfg.router_scoring == "sigmoid":
        logits = jnp.dot(xf.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, expert = jax.lax.top_k(scores + p["router_bias"], K)
        gate = jnp.take_along_axis(scores, expert, axis=-1)
        total = gate.sum(-1, keepdims=True) + 1e-20
    else:
        logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
        gate, expert = jax.lax.top_k(plan.softmax(logits, axis=-1), K)
        total = jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return expert, gate / total * cfg.routed_scaling_factor


def _moe_dropless(p, x, cfg: ModelConfig, plan: ExecPlan, live):
    """One device's dropless layer over its held experts. ``live`` (B, S)
    bool marks the rows that carry a token (None: all); others are routed
    nowhere. Returns (y, the (row, choice) pairs routed to held experts)."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    expert, gate = route(p, xf, cfg, plan)
    held = jnp.asarray(cfg.held_experts, jnp.int32)
    hit = expert[:, :, None] == held[None, None, :]      # (T, K, held)
    if live is not None:
        hit = hit & live.reshape(-1, 1, 1)
    weight = jnp.sum(jnp.where(hit, gate[:, :, None], 0.0), axis=1)
    routed = jnp.any(hit, axis=1)                         # (T, held)
    y = jnp.zeros((B * S, D), jnp.float32)
    for e in range(held.shape[0]):
        w = jax.tree.map(lambda a: a[e],
                         {k: p[k] for k in ("w1", "w2", "w3") if k in p})
        xe = jnp.where(routed[:, e:e + 1], xf, 0).astype(x.dtype)
        h = plan.activation(layers._linear(xe, w["w1"], plan), cfg.activation)
        if "w3" in w:
            h = h * layers._linear(xe, w["w3"], plan)
        ye = layers._linear(h, w["w2"], plan)
        y = y + ye.astype(jnp.float32) * weight[:, e:e + 1]
    y = y.astype(x.dtype).reshape(B, S, D)
    if "shared" in p:
        y = y + layers.ffn(p["shared"], x, cfg, plan)
    return y, jnp.sum(hit, dtype=jnp.int32)


def _moe_local(p, x, cfg: ModelConfig, plan: ExecPlan, axis: Optional[str],
               tp_size: int):
    """Per-shard MoE body. x: (B_l, S, D). axis: model axis name (or None)."""
    Bl, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = Bl * S
    xf = x.reshape(T, D)

    expert, gate = route(p, xf, cfg, plan)

    # --- capacity-based dispatch (static C) ---
    C = max(1, int(-(-K * T * cfg.capacity_factor // E)))
    e_flat = expert.reshape(-1)  # (T*K,) token-major
    # rank of each (token, k) within its expert, via stable sort
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    rank_sorted = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]
    rank = jnp.zeros((T * K,), jnp.int32).at[order].set(rank_sorted)
    keep = rank < C
    slot = jnp.where(keep, e_flat * C + rank, E * C)  # E*C = drop bin

    token_id = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    disp = jnp.zeros((E * C + 1, D), x.dtype).at[slot].set(xf[token_id])
    disp = disp[:-1].reshape(E, C, D)

    if cfg.expert_parallel and axis is not None and tp_size > 1:
        # EP: exchange expert-blocks so each shard holds its own experts' tokens.
        disp = jax.lax.all_to_all(disp, axis, split_axis=0, concat_axis=1, tiled=True)
    # resident int8 stacks run here as their dequantized values
    w1, w2, w3 = (layers.dequantize(p[k]) if k in p else None
                  for k in ("w1", "w2", "w3"))
    h = jnp.einsum("ecd,edf->ecf", disp, w1.astype(disp.dtype),
                   preferred_element_type=jnp.float32).astype(disp.dtype)
    h = plan.activation(h, cfg.activation)
    if w3 is not None:
        h = h * jnp.einsum("ecd,edf->ecf", disp, w3.astype(disp.dtype),
                           preferred_element_type=jnp.float32).astype(disp.dtype)
    y_e = jnp.einsum("ecf,efd->ecd", h, w2.astype(h.dtype),
                     preferred_element_type=jnp.float32).astype(disp.dtype)
    if cfg.expert_parallel and axis is not None and tp_size > 1:
        y_e = jax.lax.all_to_all(y_e, axis, split_axis=1, concat_axis=0, tiled=True)

    # --- combine: gather each (token, k) slot's output, weight, and sum ---
    y_pad = jnp.concatenate([y_e.reshape(E * C, D),
                             jnp.zeros((1, D), y_e.dtype)], 0)
    per_choice = y_pad[slot] * (gate.reshape(-1) * keep)[:, None].astype(y_e.dtype)
    y = per_choice.reshape(T, K, D).sum(axis=1)

    if (not cfg.expert_parallel) and axis is not None and tp_size > 1:
        y = jax.lax.psum(y, axis)  # finish the row-parallel (d_ff-sharded) matmul
    return y.reshape(Bl, S, D)


def moe(p: Params, x: jax.Array, cfg: ModelConfig,
        plan: "ExecPlan | ExecConfig",
        mesh_ctx: Optional[MeshContext],
        live: Optional[jax.Array] = None):
    """The layer's output and the (row, choice) pairs routed to held
    experts: dropless on one device, shard_map'ed capacity dispatch on a
    mesh (whose count is None)."""
    plan = as_plan(cfg, plan)
    if mesh_ctx is None or mesh_ctx.mesh is None:
        return _moe_dropless(p, x, cfg, plan, live)
    if cfg.experts_held is not None or "shared" in p:
        raise NotImplementedError(
            "mesh MoE dispatch holds every expert and has no shared expert")

    mesh = mesh_ctx.mesh
    model = mesh_ctx.model_axis if mesh_ctx.model_size > 1 else None
    dp = mesh_ctx.present_dp_axes
    batch_spec = dp if (dp and x.shape[0] % mesh_ctx.dp_size == 0) else None

    if cfg.expert_parallel and model is not None:
        # EP: also shard the sequence over "model" so each shard dispatches a
        # distinct token slice (otherwise the exchanged blocks are replicas and
        # expert FFNs run model_size-times redundantly — decode S=1 accepts it).
        seq_spec = model if x.shape[1] % mesh_ctx.model_size == 0 else None
        x_spec = P(batch_spec, seq_spec, None)
        w_specs = {"router": P(None, None), "w1": P(model, None, None),
                   "w2": P(model, None, None)}
        if "w3" in p:
            w_specs["w3"] = P(model, None, None)
    else:
        x_spec = P(batch_spec, None, None)
        w_specs = {"router": P(None, None), "w1": P(None, None, model),
                   "w2": P(None, model, None)}
        if "w3" in p:
            w_specs["w3"] = P(None, None, model)
    if "router_bias" in p:
        w_specs["router_bias"] = P(None)

    fn = partial(_moe_local, cfg=cfg, plan=plan, axis=model,
                 tp_size=mesh_ctx.model_size)
    return shard_map(
        fn, mesh=mesh, in_specs=(w_specs, x_spec), out_specs=x_spec,
        check_vma=False,
    )(p, x), None
