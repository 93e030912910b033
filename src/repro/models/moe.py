"""Mixture-of-experts FFN.

Two execution paths share the same routing math:

* ``apply_moe_local`` — single-shard sort-based dispatch (capacity-bounded
  scatter into an ``(E, C, D)`` buffer, batched expert matmul, gather back).
  Used for CPU smoke tests and whenever no mesh context is active.

* ``apply_moe_ep`` — expert-parallel ``shard_map`` path for production meshes:
  tokens sharded over the data axis, experts sharded over the data axis (EP),
  expert weights tensor-parallel over the model axis. Dispatch crosses the
  data axis with one ``all_to_all`` each way; the TP contraction is closed
  with one ``psum_scatter``+``all_gather`` pair (psum in the baseline). The
  pod axis never carries an all-to-all — EP stays inside a pod (DCN only sees
  the gradient all-reduce; DESIGN.md §5).

Experts are padded to a multiple of 16 (``padded_num_experts``) so the expert
axis always divides the production data axis; the router masks padded experts
to -inf so they are never selected.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.distributed.context import DistContext, get_context
from repro.models.common import ArrayFactory, Params

EP_MULTIPLE = 16  # production data-axis size; experts pad to a multiple


def padded_num_experts(m: MoEConfig) -> int:
    e = m.num_experts
    if e > EP_MULTIPLE and e % EP_MULTIPLE != 0:
        return -(-e // EP_MULTIPLE) * EP_MULTIPLE
    return e


def make_moe_params(f: ArrayFactory, cfg: ModelConfig) -> Params:
    m = cfg.moe
    assert m is not None
    d, fe = cfg.d_model, m.expert_d_ff
    e_pad = padded_num_experts(m)
    p: Params = {
        "router": f.normal((d, e_pad), dtype=jnp.float32),
        "w_gate": f.normal((e_pad, d, fe)),
        "w_up": f.normal((e_pad, d, fe)),
        "w_down": f.normal((e_pad, fe, d)),
    }
    if m.num_shared_experts > 0:
        shared_ff = m.num_shared_experts * (m.shared_d_ff or m.expert_d_ff)
        p["shared"] = {
            "w_gate": f.normal((d, shared_ff)),
            "w_up": f.normal((d, shared_ff)),
            "w_down": f.normal((shared_ff, d)),
            # qwen2-moe gates the shared expert output per token
            "gate": f.normal((d, 1)),
        }
    return p


# ---------------------------------------------------------------------------
# Routing (shared by both paths)
# ---------------------------------------------------------------------------

def _route(p: Params, m: MoEConfig, x2d: jax.Array
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (weights (T,k) f32, expert_idx (T,k) i32, router_probs (T,E))."""
    e_pad = p["router"].shape[-1]
    logits = x2d.astype(jnp.float32) @ p["router"]  # (T, E_pad) f32
    if e_pad > m.num_experts:  # mask padded experts
        mask = jnp.arange(e_pad) < m.num_experts
        logits = jnp.where(mask, logits, -1e30)
    if m.norm_topk_prob:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = jax.lax.top_k(probs, m.top_k)
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    else:
        # llama4-style: sigmoid of the selected logits
        top_logits, idx = jax.lax.top_k(logits, m.top_k)
        weights = jax.nn.sigmoid(top_logits)
        probs = jax.nn.softmax(logits, axis=-1)
    return weights, idx, probs


def aux_load_balance_loss(probs: jax.Array, idx: jax.Array,
                          num_experts: int) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e f_e * p_e (f = token fraction,
    p = mean router prob). Encourages uniform expert load."""
    t = probs.shape[0]
    onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32)  # (T,k,E)
    f = jnp.sum(onehot, axis=(0, 1)) / jnp.maximum(t * idx.shape[-1], 1)
    pmean = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * pmean)


def _capacity(tokens: int, k: int, e: int, factor: float) -> int:
    c = int(-(-tokens * k * factor // e))
    c = max(c, 8)
    c = -(-c // 8) * 8  # multiple of 8 (TPU sublane)
    return min(c, max(tokens, 8))


def _dispatch_indices(expert_idx: jax.Array, e_pad: int, capacity: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """Sort-based dispatch. expert_idx (T, k) -> (dest (T*k,), src_token (T*k,)).

    ``dest`` is the flat slot ``expert * C + position_in_expert`` for kept
    entries and ``e_pad * C`` (out of range -> dropped) for overflow.
    """
    t, k = expert_idx.shape
    flat = expert_idx.reshape(t * k)
    order = jnp.argsort(flat, stable=True)  # (T*k,)
    sorted_expert = flat[order]
    group_start = jnp.searchsorted(sorted_expert, jnp.arange(e_pad),
                                   side="left")
    pos = jnp.arange(t * k) - group_start[sorted_expert]
    keep = pos < capacity
    dest_sorted = jnp.where(keep, sorted_expert * capacity + pos,
                            e_pad * capacity)
    # scatter dest back to unsorted (token-major) order
    dest = jnp.zeros((t * k,), jnp.int32).at[order].set(
        dest_sorted.astype(jnp.int32))
    src_token = jnp.arange(t * k) // k
    return dest, src_token


def _expert_ffn(buf: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array, activation: str) -> jax.Array:
    """Batched per-expert SwiGLU. buf (E, C, D) -> (E, C, D)."""
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    h = act(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _shared_expert(p: Params, x2d: jax.Array, activation: str) -> jax.Array:
    sp = p["shared"]
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    h = act(x2d @ sp["w_gate"]) * (x2d @ sp["w_up"])
    out = h @ sp["w_down"]
    gate = jax.nn.sigmoid((x2d.astype(jnp.float32) @ sp["gate"].astype(
        jnp.float32)))
    return out * gate.astype(out.dtype)


# ---------------------------------------------------------------------------
# Local (single-shard) path
# ---------------------------------------------------------------------------

def apply_moe_local(p: Params, cfg: ModelConfig, x2d: jax.Array,
                    capacity_factor: float = 1.25
                    ) -> Tuple[jax.Array, jax.Array]:
    """x2d (T, D) -> (y (T, D), aux_loss scalar)."""
    m = cfg.moe
    e_pad = p["router"].shape[-1]
    t = x2d.shape[0]
    weights, idx, probs = _route(p, m, x2d)
    cap = _capacity(t, m.top_k, m.num_experts, capacity_factor)
    dest, src_token = _dispatch_indices(idx, e_pad, cap)

    buf = jnp.zeros((e_pad * cap, x2d.shape[-1]), x2d.dtype)
    buf = buf.at[dest].set(x2d[src_token], mode="drop", unique_indices=True)
    out = _expert_ffn(buf.reshape(e_pad, cap, -1), p["w_gate"], p["w_up"],
                      p["w_down"], cfg.activation)
    out_flat = jnp.take(out.reshape(e_pad * cap, -1), dest, axis=0,
                        mode="fill", fill_value=0)
    contrib = out_flat * weights.reshape(-1)[:, None].astype(out_flat.dtype)
    y = jnp.zeros_like(x2d).at[src_token].add(contrib)
    if m.num_shared_experts > 0:
        y = y + _shared_expert(p, x2d, cfg.activation)
    return y, aux_load_balance_loss(probs, idx, m.num_experts)


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path
# ---------------------------------------------------------------------------

def _moe_ep_body(x_loc: jax.Array, router: jax.Array, w_gate: jax.Array,
                 w_up: jax.Array, w_down: jax.Array, *, cfg: ModelConfig,
                 data_axis: str, model_axis: str, capacity_factor: float,
                 e_pad: int) -> Tuple[jax.Array, jax.Array]:
    """Per-device body. x_loc (T_loc, D); w_* local expert blocks
    (E_loc, D, F_loc). Returns (y_loc (T_loc, D), aux scalar)."""
    m = cfg.moe
    t_loc = x_loc.shape[0]
    ep = jax.lax.axis_size(data_axis)
    p_route = {"router": router}
    weights, idx, probs = _route(p_route, m, x_loc)
    cap = _capacity(t_loc, m.top_k, m.num_experts, capacity_factor)
    dest, src_token = _dispatch_indices(idx, e_pad, cap)

    buf = jnp.zeros((e_pad * cap, x_loc.shape[-1]), x_loc.dtype)
    buf = buf.at[dest].set(x_loc[src_token], mode="drop", unique_indices=True)
    buf = buf.reshape(e_pad, cap, -1)
    # data axis a2a: (E, C, D) -> (E/ep, ep*C, D); my expert shard receives
    # its experts' tokens from every data shard
    buf = jax.lax.all_to_all(buf, data_axis, split_axis=0, concat_axis=1,
                             tiled=True)
    out = _expert_ffn(buf, w_gate, w_up, w_down, cfg.activation)
    # close the TP contraction (w_down F dim is model-sharded -> partial sums)
    out = jax.lax.psum(out, model_axis)
    out = jax.lax.all_to_all(out, data_axis, split_axis=1, concat_axis=0,
                             tiled=True)
    out_flat = jnp.take(out.reshape(e_pad * cap, -1), dest, axis=0,
                        mode="fill", fill_value=0)
    contrib = out_flat * weights.reshape(-1)[:, None].astype(out_flat.dtype)
    y = jnp.zeros_like(x_loc).at[src_token].add(contrib)
    aux = aux_load_balance_loss(probs, idx, m.num_experts)
    aux = jax.lax.pmean(aux, data_axis)
    return y, aux


def apply_moe_ep(p: Params, cfg: ModelConfig, x2d: jax.Array,
                 ctx: DistContext, capacity_factor: float = 1.25
                 ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE over ctx.mesh. x2d (T, D) with T sharded over the
    data axes; experts sharded over the (innermost) data axis; F over model."""
    m = cfg.moe
    e_pad = p["router"].shape[-1]
    P = jax.sharding.PartitionSpec
    data_axis = ctx.ep_axis  # innermost data axis (never 'pod')
    model_axis = ctx.model_axis

    # Respect an enclosing manual region (e.g. the pod-manual compressed-grad
    # train step): reuse the ambient abstract mesh and only manualise axes
    # that are not already manual — specs must not mention manual axes.
    ambient = jax.sharding.get_abstract_mesh()
    if not ambient.empty:
        mesh = ambient
        already_manual = set(mesh.manual_axes)
    else:
        mesh = ctx.mesh
        already_manual = set()
    batch_axes = tuple(a for a in ctx.batch_axes if a not in already_manual)
    manual_now = set(batch_axes) | {model_axis}

    body = functools.partial(
        _moe_ep_body, cfg=cfg, data_axis=data_axis, model_axis=model_axis,
        capacity_factor=capacity_factor, e_pad=e_pad)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, None),            # tokens
                  P(None, None),                  # router (replicated)
                  P(data_axis, None, model_axis),  # w_gate
                  P(data_axis, None, model_axis),  # w_up
                  P(data_axis, model_axis, None)),  # w_down
        out_specs=(P(batch_axes, None), P()),
        axis_names=manual_now, check_vma=False,
    )(x2d, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if m.num_shared_experts > 0:
        y = y + _shared_expert(p, x2d, cfg.activation)
    return y, aux


def apply_moe(p: Params, cfg: ModelConfig, x: jax.Array,
              capacity_factor: float = 1.25) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (y (B, S, D), aux scalar). Dispatches to the EP path
    when a distribution context with a mesh is active."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    ctx = get_context()
    use_ep = ctx is not None and ctx.mesh is not None and ctx.use_ep
    if use_ep:
        # shard_map needs the token dim to tile the batch axes exactly
        # (e.g. batch-1 decode cannot); GSPMD handles the local path then.
        div = 1
        for a in ctx.batch_axes:
            div *= ctx.axis_size(a)
        use_ep = (b * s) % div == 0 and (b * s) // div > 0
    if use_ep:
        y, aux = apply_moe_ep(p, cfg, x2d, ctx, capacity_factor)
    else:
        y, aux = apply_moe_local(p, cfg, x2d, capacity_factor)
    return y.reshape(b, s, d), aux
