"""Full model assembly for all ten assigned architectures.

One code path covers dense / moe / ssm / hybrid / vlm / audio via a *block
pattern*: the layer stack is a repetition of a short period of ``LayerSpec``s
(mixer x ffn kind). Parameters for each position in the period are stacked
over repetitions and the stack is driven by ``jax.lax.scan`` — this keeps the
HLO (and SPMD partitioning time) bounded for 48-64 layer models on 512-device
meshes.

Entry points (pure functions over explicit param pytrees):
  init_params  — real arrays (init) or ShapeDtypeStructs (dry-run specs)
  forward      — full-sequence logits (+ MoE aux loss): train / scoring
  train_loss   — causal-LM CE + aux, with optional remat
  prefill      — full-sequence + returns the decode cache
  decode_step  — one token against the cache
  init_cache   — cache pytree (real or spec)
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import mamba as ssm
from repro.models import moe as moe_lib
from repro.models.common import (ArrayFactory, Params, apply_norm,
                                 cross_entropy_loss, embed_tokens, lm_logits,
                                 make_embed_params, make_ffn_params,
                                 make_norm_params, apply_ffn)

AUX_LOSS_COEF = 0.01


# ---------------------------------------------------------------------------
# Block pattern
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LayerSpec:
    mixer: str            # "attn" | "ssm"
    ffn: str              # "dense" | "moe" | "none"
    cross: bool = False   # decoder cross-attention (enc-dec archs)


def block_pattern(cfg: ModelConfig) -> Tuple[LayerSpec, ...]:
    period = 1
    if cfg.hybrid is not None:
        period = math.lcm(period, cfg.hybrid.attn_every_n)
    if cfg.moe is not None:
        period = math.lcm(period, cfg.moe.moe_every_n)
    if cfg.num_layers % period != 0:
        raise ValueError(
            f"{cfg.name}: num_layers={cfg.num_layers} not divisible by the "
            f"block period {period}")
    specs = []
    for i in range(period):
        mixer = "attn" if cfg.layer_is_attention(i) else "ssm"
        if cfg.layer_is_moe(i):
            ffn = "moe"
        elif cfg.d_ff > 0 and cfg.family != "ssm":
            ffn = "dense"
        else:
            ffn = "none"
        specs.append(LayerSpec(mixer, ffn, cross=cfg.is_encoder_decoder))
    return tuple(specs)


def num_reps(cfg: ModelConfig) -> int:
    return cfg.num_layers // len(block_pattern(cfg))


class _StackedFactory:
    """ArrayFactory adapter that prepends a (n_reps,) leading dim."""

    def __init__(self, base: ArrayFactory, n: int):
        self._base, self._n = base, n
        self.spec_only = base.spec_only
        self.dtype = base.dtype

    def __getattr__(self, name):
        fn = getattr(self._base, name)

        def wrapped(shape, *args, **kw):
            return fn((self._n,) + tuple(shape), *args, **kw)
        return wrapped


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

def _make_block_params(f, cfg: ModelConfig, spec: LayerSpec) -> Params:
    p: Params = {"norm1": make_norm_params(f, cfg.norm_type, cfg.d_model)}
    if spec.mixer == "attn":
        p["attn"] = attn.make_attention_params(f, cfg)
    else:
        p["mamba"] = ssm.make_mamba_params(f, cfg)
    if spec.cross:
        p["cross_norm"] = make_norm_params(f, cfg.norm_type, cfg.d_model)
        p["cross"] = attn.make_cross_attention_params(f, cfg)
    if spec.ffn != "none":
        p["norm2"] = make_norm_params(f, cfg.norm_type, cfg.d_model)
        if spec.ffn == "dense":
            p["ffn"] = make_ffn_params(f, cfg.d_model, cfg.d_ff)
        else:
            p["moe"] = moe_lib.make_moe_params(f, cfg)
    return p


def init_params(cfg: ModelConfig, rng: Optional[jax.Array] = None,
                spec_only: bool = False, dtype=jnp.bfloat16) -> Params:
    if not spec_only and rng is None:
        rng = jax.random.PRNGKey(0)
    f = ArrayFactory(rng, spec_only, dtype)
    pattern = block_pattern(cfg)
    reps = num_reps(cfg)
    params: Params = {
        "embed": make_embed_params(f, cfg.vocab_size, cfg.d_model,
                                   cfg.tie_embeddings),
    }
    sf = _StackedFactory(f, reps)
    params["blocks"] = [_make_block_params(sf, cfg, s) for s in pattern]
    params["final_norm"] = make_norm_params(f, cfg.norm_type, cfg.d_model)
    if cfg.frontend.kind != "none" and cfg.frontend.frontend_dim:
        params["frontend_proj"] = f.normal(
            (cfg.frontend.frontend_dim, cfg.d_model))
    if cfg.is_encoder_decoder:
        enc = cfg.encdec
        esf = _StackedFactory(f, enc.num_encoder_layers)
        enc_spec = LayerSpec("attn", "dense", cross=False)
        params["encoder"] = {
            "blocks": [_make_block_params(esf, cfg, enc_spec)],
            "final_norm": make_norm_params(f, cfg.norm_type, cfg.d_model),
        }
    return params


# ---------------------------------------------------------------------------
# Block application (one position within the period)
# ---------------------------------------------------------------------------

def _apply_block(spec: LayerSpec, p: Params, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array, mode: str,
                 cache: Optional[Params], cross_kv: Optional[Params],
                 cache_index: Optional[jax.Array], cache_len: int,
                 is_causal: bool = True, layer: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (x, new_cache_or_None, aux_loss). In decode, ``layer`` marks
    ``cache`` as the layer-stacked attention cache the scan carries."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, jax.Array] = {}
    h = apply_norm(p["norm1"], x, cfg.norm_type, cfg.norm_eps)
    if spec.mixer == "attn":
        if mode == "full":
            mix = attn.attention_forward(p["attn"], cfg, h, positions,
                                         is_causal=is_causal)
        elif mode == "prefill":
            mix, kv = attn.prefill_attention(p["attn"], cfg, h, positions,
                                             cache_len)
            new_cache.update(kv)
        else:  # decode
            from repro.distributed.context import get_context
            ctx = get_context()
            # the shard_map flash body indexes one global slot; ragged
            # (B,) cache_index batches keep the dense per-row path
            use_flash = (ctx is not None and ctx.mesh is not None
                         and ctx.flash_decode and cfg.sliding_window == 0
                         and jnp.ndim(cache_index) == 0
                         and cache["k"].shape[1]
                         % ctx.axis_size(ctx.model_axis) == 0)
            if use_flash:
                mix, kv = attn.decode_attention_sharded(
                    p["attn"], cfg, h, cache, cache_index, ctx)
            else:
                mix, kv = attn.decode_attention(p["attn"], cfg, h, cache,
                                                cache_index, layer)
            new_cache.update(kv)
    else:  # ssm mixer
        if mode == "full":
            mix = ssm.mamba_forward(p["mamba"], cfg, h)
        elif mode == "prefill":
            mix, st = ssm.mamba_prefill(p["mamba"], cfg, h)
            new_cache.update(st)
        else:
            mix, st = ssm.mamba_decode(p["mamba"], cfg, h, cache)
            new_cache.update(st)
    x = x + mix
    x = constrain(x, "batch", None, None)

    if spec.cross:
        hc = apply_norm(p["cross_norm"], x, cfg.norm_type, cfg.norm_eps)
        assert cross_kv is not None
        x = x + attn.cross_attention_cached(p["cross"], cfg, hc,
                                            cross_kv["ck"], cross_kv["cv"])

    if spec.ffn != "none":
        h2 = apply_norm(p["norm2"], x, cfg.norm_type, cfg.norm_eps)
        if spec.ffn == "dense":
            out = apply_ffn(p["ffn"], h2, cfg.activation)
        else:
            out, aux = moe_lib.apply_moe(p["moe"], cfg, h2)
        x = x + out
        x = constrain(x, "batch", None, None)
    return x, (new_cache or None), aux


# ---------------------------------------------------------------------------
# Scan over repetitions
# ---------------------------------------------------------------------------

def _run_blocks(blocks: List[Params], cfg: ModelConfig, x: jax.Array,
                positions: jax.Array, mode: str,
                caches: Optional[List[Params]] = None,
                cross_kv: Optional[List[Params]] = None,
                cache_index: Optional[jax.Array] = None,
                cache_len: int = 0, is_causal: bool = True,
                remat: bool = False, remat_policy: str = "full",
                pattern: Optional[Tuple[LayerSpec, ...]] = None
                ) -> Tuple[jax.Array, Optional[List[Params]], jax.Array]:
    """Scan the super-block over repetitions.

    blocks: list (per period position) of rep-stacked param pytrees.
    caches: list (per period position) of rep-stacked cache pytrees (decode).
    cross_kv: list (per position) of rep-stacked {'ck','cv'} (enc-dec decode).

    On one device, decode carries the attention caches through the scan
    and each layer writes its new rows into the stack in place: as scan
    ``xs``/``ys`` the stack came back in fresh buffers, copied whole into
    the donated output (DESIGN.md §14). With a mesh set they stay in
    ``xs``/``ys``, as does SSM state, which each layer rewrites whole.
    """
    pattern = pattern or block_pattern(cfg)
    reps = jax.tree.leaves(blocks[0])[0].shape[0]
    kv = None
    if mode == "decode" and not attn._mesh_set():
        kv = [c if s.mixer == "attn" else {} for s, c in zip(pattern, caches)]
        caches = [{} if s.mixer == "attn" else c
                  for s, c in zip(pattern, caches)]

    def body(carry, xs):
        x, aux, kv = carry
        kv = None if kv is None else list(kv)
        block_ps, cache_in, rep_idx = xs
        new_caches = []
        for pos, spec in enumerate(pattern):
            ckv = None
            if spec.cross and cross_kv is not None:
                ckv = jax.tree.map(lambda a: a[rep_idx], cross_kv[pos])
            carried = kv is not None and spec.mixer == "attn"
            c_in = kv[pos] if carried else (
                cache_in[pos] if cache_in is not None else None)
            x, c_out, a = _apply_block(
                spec, block_ps[pos], cfg, x, positions, mode, c_in, ckv,
                cache_index, cache_len, is_causal,
                layer=rep_idx if carried else None)
            if carried:
                kv[pos], c_out = c_out, None
            aux = aux + a
            new_caches.append(c_out if c_out is not None else {})
        return (x, aux, kv), new_caches

    if remat:
        if remat_policy == "dots":
            # keep matmul outputs, recompute the cheap elementwise chains
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)
        else:
            body = jax.checkpoint(body)

    xs = (blocks, caches, jnp.arange(reps))
    (x, aux, kv), caches_out = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), kv), xs)
    if mode == "full":
        caches_out = None
    elif kv is not None:
        caches_out = [k or c for k, c in zip(kv, caches_out)]
    return x, caches_out, aux


# ---------------------------------------------------------------------------
# Input embedding (incl. modality frontend stubs)
# ---------------------------------------------------------------------------

def _embed_inputs(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array]
                  ) -> Tuple[jax.Array, jax.Array, int]:
    """Returns (x (B, S_tot, D), positions (B, S_tot), prefix_len)."""
    tokens = batch["tokens"]
    x = embed_tokens(params["embed"], tokens, cfg.d_model)
    prefix_len = 0
    if "prefix_embeddings" in batch:
        pe = batch["prefix_embeddings"].astype(x.dtype) \
            @ params["frontend_proj"]
        x = jnp.concatenate([pe, x], axis=1)
        prefix_len = pe.shape[1]
    b, s_tot = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s_tot)[None], (b, s_tot))
    x = constrain(x, "batch", None, None)
    return x, positions, prefix_len


def encode(params: Params, cfg: ModelConfig, source: jax.Array,
           remat: bool = False) -> jax.Array:
    """Encoder forward (enc-dec archs). source (B, S_src, frontend_dim) —
    precomputed frames per the frontend-stub assignment."""
    enc = params["encoder"]
    if "frontend_proj" in params and \
            source.shape[-1] == cfg.frontend.frontend_dim:
        x = source.astype(params["frontend_proj"].dtype) \
            @ params["frontend_proj"]
    else:
        x = source
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    pattern = (LayerSpec("attn", "dense", cross=False),)
    x, _, _ = _run_blocks(enc["blocks"], cfg, x, positions, "full",
                          is_causal=False, remat=remat, pattern=pattern)
    return apply_norm(enc["final_norm"], x, cfg.norm_type, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            remat: bool = False, logits_dtype=None,
            remat_policy: str = "full") -> Tuple[jax.Array, jax.Array]:
    """Full-sequence logits. Returns (logits (B, S_tot, V), aux_loss)."""
    cross_kv = None
    if cfg.is_encoder_decoder:
        memory = encode(params, cfg, batch["source_frames"], remat)
        cross_kv = _precompute_cross_kv(params, cfg, memory)
    x, positions, _ = _embed_inputs(params, cfg, batch)
    x, _, aux = _run_blocks(params["blocks"], cfg, x, positions, "full",
                            cross_kv=cross_kv, remat=remat,
                            remat_policy=remat_policy)
    x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    logits = lm_logits(params["embed"], x, cfg.tie_embeddings)
    if logits_dtype is not None:
        logits = logits.astype(logits_dtype)
    logits = constrain(logits, "batch", None, "vocab")
    return logits, aux


def train_loss(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
               remat: bool = True, aux_coef: float = AUX_LOSS_COEF,
               remat_policy: str = "full"
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward(params, cfg, batch, remat=remat,
                          logits_dtype=jnp.bfloat16,
                          remat_policy=remat_policy)
    prefix_len = logits.shape[1] - batch["labels"].shape[1]
    if prefix_len:
        logits = logits[:, prefix_len:]
    ce = cross_entropy_loss(logits, batch["labels"])
    total = ce + aux_coef * aux
    return total, {"ce": ce, "aux_loss": aux}


def _precompute_cross_kv(params: Params, cfg: ModelConfig, memory: jax.Array
                         ) -> List[Params]:
    """Per-position rep-stacked {'ck','cv'} from encoder memory."""
    pattern = block_pattern(cfg)
    out = []
    for pos, spec in enumerate(pattern):
        if not spec.cross:
            out.append({})
            continue
        block_p = params["blocks"][pos]

        def one_rep(p_cross):
            ck, cv = attn.make_cross_kv(p_cross, cfg, memory)
            return {"ck": ck, "cv": cv}
        out.append(jax.vmap(one_rep)(block_p["cross"]))
    return out


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            cache_len: Optional[int] = None) -> Tuple[jax.Array, Params]:
    """Process the prompt; returns (last-position logits (B, V) f32, cache).

    cache_len is the KV-cache capacity in tokens; ``None`` (the default)
    means "capacity = prompt length" (no decode headroom). An explicit
    cache_len must cover the prompt: cache_len >= prompt_len.
    """
    cross_kv = None
    if cfg.is_encoder_decoder:
        memory = encode(params, cfg, batch["source_frames"])
        cross_kv = _precompute_cross_kv(params, cfg, memory)
    x, positions, _ = _embed_inputs(params, cfg, batch)
    if cache_len is None:
        cache_len = x.shape[1]
    elif cache_len < x.shape[1]:
        raise ValueError(
            f"prefill: cache_len={cache_len} is smaller than the prompt "
            f"({x.shape[1]} tokens incl. any modality prefix); the cache "
            f"would drop prompt positions")
    x, caches, _ = _run_blocks(params["blocks"], cfg, x, positions, "prefill",
                               cross_kv=cross_kv, cache_len=cache_len)
    x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    last = x[:, -1]
    logits = lm_logits(params["embed"], last[:, None],
                       cfg.tie_embeddings)[:, 0]
    logits = constrain(logits, "batch", "vocab")
    cache: Params = {"blocks": caches}
    if cross_kv is not None:
        cache["cross"] = cross_kv
    return logits, cache


def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                cache: Params, cache_index: jax.Array
                ) -> Tuple[jax.Array, Params]:
    """One-token decode. tokens (B, 1); cache from ``prefill``/``init_cache``;
    cache_index = number of tokens already in context — a scalar (whole
    batch at one depth) or ``(B,)`` (ragged batch: per-request depths, the
    continuous-batching case). Returns (logits (B, V) f32, new cache)."""
    x = embed_tokens(params["embed"], tokens, cfg.d_model)
    x = constrain(x, "batch", None, None)
    b = x.shape[0]
    positions = jnp.broadcast_to(cache_index, (b,)).reshape(b, 1)
    x, caches, _ = _run_blocks(
        params["blocks"], cfg, x, positions, "decode",
        caches=cache["blocks"], cross_kv=cache.get("cross"),
        cache_index=cache_index)
    x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    logits = lm_logits(params["embed"], x, cfg.tie_embeddings)[:, 0]
    logits = constrain(logits, "batch", "vocab")
    new_cache: Params = {"blocks": caches}
    if "cross" in cache:
        new_cache["cross"] = cache["cross"]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Device-resident decode loop entry points (DESIGN.md §14)
# ---------------------------------------------------------------------------

def decode_fused_steps(params: Params, cfg: ModelConfig, tokens: jax.Array,
                       cache: Params, positions: jax.Array,
                       active: jax.Array, fold_state: Dict[str, jax.Array],
                       *, k: int = 1, beta: float = 0.35,
                       mode: str = "ewma"
                       ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                                  Params, jax.Array, Dict[str, jax.Array]]:
    """``k`` greedy decode steps fused into one executable.

    The per-step greedy argmax, the top-2-gap reduction
    (``kernels.top2gap.argmax_gap``) and the streaming-certainty fold
    (``core.certainty.device_fold_*``) run inside the jit, and at k > 1
    the whole loop is a ``lax.scan`` whose carry — tokens, KV cache,
    positions, fold state — never leaves the device. Each call transfers
    O(k·B) scalars to the host instead of k·(B, V) logits.

    tokens (B,) i32     — each row's next input token (the previous argmax)
    positions (B,) i32  — per-row context depth; inactive rows are pinned
                          to position 0 (their lanes are scratch, fully
                          overwritten at the next prefill scatter)
    active (B,) bool    — resident-request mask; inactive rows neither
                          advance positions nor feed their sampled token
                          forward
    fold_state          — ``device_fold_init`` pytree of (B,) arrays

    Returns (token trace (k, B) i32, gap trace (k, B) f32, certainty trace
    (k, B) f32, next input tokens (B,), cache, positions, fold state).
    """
    from repro.core import certainty as _cert
    from repro.kernels.top2gap import argmax_gap

    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    active_i = active.astype(positions.dtype)

    def body(carry, _):
        toks, cache, pos, st = carry
        pos_eff = jnp.where(active, pos, 0)
        logits, cache = decode_step(params, cfg, toks[:, None], cache,
                                    pos_eff)
        nxt, gap = argmax_gap(logits)
        st = _cert.device_fold_update(st, gap, beta)
        cert = _cert.device_fold_value(st, mode)
        toks = jnp.where(active, nxt, toks)
        pos = pos + active_i
        return (toks, cache, pos, st), (nxt, gap, cert)

    init = (tokens, cache, positions, fold_state)
    if k == 1:
        carry, (tt, gt, ct) = body(init, None)
        tt, gt, ct = tt[None], gt[None], ct[None]
    else:
        carry, (tt, gt, ct) = jax.lax.scan(body, init, None, length=k)
    toks, cache, pos, st = carry
    return tt, gt, ct, toks, cache, pos, st


def bucketed_prefill_supported(cfg: ModelConfig) -> bool:
    """Whether right-padded batched prefill is EXACT for this config.

    Right padding is invisible to a row's true positions only when every
    per-position computation is causal and row-independent: attention
    masks pad keys out (and the pad K/V beyond the true length is masked
    until overwritten during decode). It is NOT exact for

    * SSM mixers — ``mamba_prefill`` returns the recurrent state after
      the FULL padded sequence (conv tail + scan carry), which pads
      corrupt;
    * MoE FFNs — capacity-based routing drops tokens as a function of the
      whole flattened batch, so co-batched rows perturb each other;
    * enc-dec / modality-frontend archs — the prompt is not a plain token
      sequence.

    Those fall back to exact-length batch-1 prefill.
    """
    if cfg.is_encoder_decoder or cfg.moe is not None:
        return False
    if cfg.frontend.kind != "none" and cfg.frontend.frontend_dim:
        return False
    return all(s.mixer == "attn" for s in block_pattern(cfg))


def prefill_bucketed(params: Params, cfg: ModelConfig, tokens: jax.Array,
                     true_lens: jax.Array, cache_len: int
                     ) -> Tuple[jax.Array, Params]:
    """Batched prefill over right-padded prompts.

    tokens (B, Lb) i32 — prompts padded to a shared length bucket;
    true_lens (B,) i32 — each row's real prompt length (1..Lb). Returns
    (per-row logits at position ``true_lens - 1`` (B, V) f32, cache).

    The returned cache rows hold pad K/V at positions >= true_len; those
    slots are masked by every decode step (``idx <= cache_index``) until
    the decode stream overwrites them one position at a time, so they are
    unobservable. Sliding-window ring caches re-home slots modulo the
    window, which WOULD fold pads into the live window — callers must
    keep ``Lb < kv_cache_len`` (enforced here).
    """
    if not bucketed_prefill_supported(cfg):
        raise ValueError(
            f"{cfg.name}: bucketed prefill needs an attention-only decoder "
            f"(no SSM state, no MoE capacity routing, no enc-dec/frontend)")
    x, positions, _ = _embed_inputs(params, cfg, {"tokens": tokens})
    b, s = x.shape[0], x.shape[1]
    if cache_len < s:
        raise ValueError(
            f"prefill_bucketed: cache_len={cache_len} < padded prompt "
            f"length {s}")
    if cfg.sliding_window > 0 and s >= attn.kv_cache_len(cfg, cache_len):
        raise ValueError(
            f"prefill_bucketed: padded length {s} does not fit the "
            f"sliding-window ring ({attn.kv_cache_len(cfg, cache_len)}); "
            f"pads would alias live window slots")
    x, caches, _ = _run_blocks(params["blocks"], cfg, x, positions,
                               "prefill", cache_len=cache_len)
    x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    last = jnp.take_along_axis(
        x, jnp.clip(true_lens - 1, 0, s - 1)[:, None, None]
        .astype(jnp.int32), axis=1)                   # (B, 1, D)
    logits = lm_logits(params["embed"], last, cfg.tie_embeddings)[:, 0]
    logits = constrain(logits, "batch", "vocab")
    return logits, {"blocks": caches}


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               spec_only: bool = False, dtype=jnp.bfloat16,
               source_len: int = 0) -> Params:
    """Decode cache pytree (zeros or ShapeDtypeStructs)."""
    f = ArrayFactory(None if spec_only else jax.random.PRNGKey(0), spec_only,
                     dtype)
    pattern = block_pattern(cfg)
    reps = num_reps(cfg)
    sf = _StackedFactory(f, reps)
    blocks, cross = [], []
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    for spec in pattern:
        c: Params = {}
        if spec.mixer == "attn":
            c_len = attn.kv_cache_len(cfg, cache_len)
            c["k"] = sf.zeros((batch, c_len, kv, hd))
            c["v"] = sf.zeros((batch, c_len, kv, hd))
        else:
            s = cfg.ssm
            d_inner = s.expand * cfg.d_model
            c["conv"] = sf.zeros((batch, s.d_conv - 1, d_inner))
            c["ssm"] = sf.zeros((batch, d_inner, s.d_state), jnp.float32)
        blocks.append(c)
        if spec.cross:
            cross.append({"ck": sf.zeros((batch, source_len, kv, hd)),
                          "cv": sf.zeros((batch, source_len, kv, hd))})
        else:
            cross.append({})
    cache: Params = {"blocks": blocks}
    if cfg.is_encoder_decoder:
        cache["cross"] = cross
    return cache
