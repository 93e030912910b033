"""Train-step factory: loss + grads + AdamW, with

* activation rematerialisation (scan-over-layers body checkpointing),
* gradient accumulation over microbatches (``jax.lax.scan``),
* optional int8-compressed gradient all-reduce across the 'pod' (DCN) axis —
  in-pod reduction stays bf16/f32 on ICI; only the inter-pod exchange is
  quantised (per-tensor symmetric int8), halving DCN traffic vs bf16.

The factory returns a pure function suitable for ``jax.jit`` with donated
(params, opt_state).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.context import DistContext, get_context, use_context
from repro.models import model as model_lib
from repro.training.optimizer import AdamWConfig, adamw_update

Pytree = Any


@dataclass(frozen=True)
class TrainStepConfig:
    remat: bool = True
    remat_policy: str = "full"  # "full" | "dots" (save matmul outputs)
    num_microbatches: int = 1
    # int8-quantised gradient exchange over the pod axis (multi-pod only)
    compress_pod_grads: bool = False
    aux_loss_coef: float = 0.01


# ---------------------------------------------------------------------------
# int8 pod-axis gradient exchange
# ---------------------------------------------------------------------------

def _compressed_pod_allreduce_leaf(g: jax.Array, axis: str) -> jax.Array:
    """Mean over the pod axis with int8 on the wire (manual-axis code)."""
    npods = jax.lax.axis_size(axis)
    gf = g.astype(jnp.float32)
    scale = jnp.max(jnp.abs(gf)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    # every pod contributes its int8 block; sum of dequantised blocks
    q_all = jax.lax.all_gather(q, axis)            # (npods, ...) int8 on DCN
    s_all = jax.lax.all_gather(scale, axis)        # (npods,) f32
    deq = q_all.astype(jnp.float32) * s_all.reshape(
        (npods,) + (1,) * g.ndim)
    return (jnp.sum(deq, axis=0) / npods).astype(g.dtype)


def compressed_pod_allreduce(grads: Pytree, mesh: jax.sharding.Mesh,
                             pod_axis: str = "pod") -> Pytree:
    """Apply the compressed exchange leaf-wise. Grads enter replicated over
    the pod axis? No — they enter as *local-pod* gradients (loss averaged over
    the in-pod batch only) and leave as the cross-pod mean."""
    def body(*leaves):
        return tuple(_compressed_pod_allreduce_leaf(l, pod_axis)
                     for l in leaves)

    flat, treedef = jax.tree.flatten(grads)
    specs = tuple(P() for _ in flat)  # manual over pod only; auto elsewhere
    out = jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=specs,
                        axis_names={pod_axis}, check_vma=False)(*flat)
    return jax.tree.unflatten(treedef, list(out))


# ---------------------------------------------------------------------------
# Factory
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    ts_cfg: TrainStepConfig = TrainStepConfig()):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    When ``compress_pod_grads`` is on and the ambient mesh has a 'pod' axis,
    the loss is averaged per pod (shard_map manual over 'pod'), gradients are
    exchanged int8 over DCN, and the optimizer sees the cross-pod mean. In
    every other configuration the grad reduction is XLA's own (bf16/f32).
    """

    def loss_fn(params, batch):
        loss, metrics = model_lib.train_loss(
            params, cfg, batch, remat=ts_cfg.remat,
            aux_coef=ts_cfg.aux_loss_coef,
            remat_policy=ts_cfg.remat_policy)
        return loss, metrics

    def grads_of(params, batch):
        if ts_cfg.num_microbatches <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, grads

        n = ts_cfg.num_microbatches

        def reshape_mb(x):
            b = x.shape[0]
            assert b % n == 0, f"batch {b} % microbatches {n} != 0"
            return x.reshape((n, b // n) + x.shape[1:])

        mb_batch = jax.tree.map(reshape_mb, batch)

        def acc_step(carry, mb):
            loss_acc, grads_acc = carry
            (loss, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grads_acc, grads)
            return (loss_acc + loss, grads_acc), None

        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, grads), _ = jax.lax.scan(
            acc_step, (jnp.zeros((), jnp.float32), zero_grads), mb_batch)
        loss = loss_sum / n
        grads = jax.tree.map(lambda g: g / n, grads)
        return loss, {"ce": loss, "aux_loss": jnp.zeros((), jnp.float32)}, \
            grads

    def train_step(params, opt_state, batch
                   ) -> Tuple[Pytree, Pytree, Dict[str, jax.Array]]:
        ctx = get_context()
        use_compress = (ts_cfg.compress_pod_grads and ctx is not None
                        and ctx.mesh is not None
                        and "pod" in ctx.mesh.axis_names)
        if use_compress:
            # per-pod grads: shard_map manual over 'pod'; XLA (auto axes)
            # still reduces over the in-pod data axis on ICI. Inside the
            # manual region, sharding constraints must not mention 'pod'.
            inner_ctx = DistContext(
                mesh=ctx.mesh,
                batch_axes=tuple(a for a in ctx.batch_axes if a != "pod"),
                model_axis=ctx.model_axis, use_ep=ctx.use_ep)

            def local_grads(params, batch):
                with use_context(inner_ctx):
                    loss, metrics, grads = grads_of(params, batch)
                return loss, metrics, grads

            flat_params, ptree = jax.tree.flatten(params)
            loss, metrics, grads = jax.shard_map(
                local_grads, mesh=ctx.mesh,
                in_specs=(jax.tree.unflatten(ptree,
                                             [P()] * len(flat_params)),
                          # each pod sees its own slice of the global batch
                          jax.tree.map(lambda _: P("pod"), batch)),
                out_specs=(P(), jax.tree.map(lambda _: P(), {
                    "ce": 0, "aux_loss": 0}),
                    jax.tree.unflatten(ptree, [P()] * len(flat_params))),
                axis_names={"pod"}, check_vma=False)(params, batch)
            grads = compressed_pod_allreduce(grads, ctx.mesh)
            loss = jax.shard_map(
                lambda l: jax.lax.pmean(l, "pod"), mesh=ctx.mesh,
                in_specs=P(), out_specs=P(),
                axis_names={"pod"}, check_vma=False)(loss)
        else:
            loss, metrics, grads = grads_of(params, batch)

        new_params, new_opt, opt_metrics = adamw_update(
            params, grads, opt_state, opt_cfg)
        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics.update(opt_metrics)
        return new_params, new_opt, metrics

    return train_step
