"""GQA attention: parameter creation, full-sequence (train/prefill) and
single-token decode against a KV cache (flat or sliding-window ring buffer).

Pure-jnp math by default (XLA fuses this well and it lowers on any backend);
``repro.kernels`` holds the Pallas TPU versions validated against these
semantics.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import ArrayFactory, Params, apply_rope

NEG_INF = -1e30


def make_attention_params(f: ArrayFactory, cfg: ModelConfig) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": f.normal((d, h * hd)),
        "wk": f.normal((d, kv * hd)),
        "wv": f.normal((d, kv * hd)),
        "wo": f.normal((h * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = f.zeros((h * hd,))
        p["bk"] = f.zeros((kv * hd,))
        p["bv"] = f.zeros((kv * hd,))
    if cfg.qk_norm:
        p["q_norm_scale"] = f.ones((hd,), jnp.float32)
        p["k_norm_scale"] = f.ones((hd,), jnp.float32)
    return p


def _project_qkv(p: Params, cfg: ModelConfig, x: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = _head_rmsnorm(q, p["q_norm_scale"], cfg.norm_eps)
        k = _head_rmsnorm(k, p["k_norm_scale"], cfg.norm_eps)
    return q, k, v


def _head_rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _repeat_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, H, hd) by repeating each kv head."""
    b, s, kv, hd = k.shape
    rep = num_heads // kv
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
         mask: Optional[jax.Array]) -> jax.Array:
    """Scaled dot-product attention. q (B,Sq,H,hd), k/v (B,Sk,H,hd),
    mask (Sq,Sk) or (B,1,Sq,Sk) additive-bool (True = keep)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None]
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def sdpa_gqa(q: jax.Array, k: jax.Array, v: jax.Array,
             mask: Optional[jax.Array]) -> jax.Array:
    """Group-structured SDPA: q (B,Sq,H,hd) with k/v at (B,Sk,KV,hd) —
    NEVER materialises the repeated K/V (§Perf H4: the repeat costs
    H/KV x the cache bytes per layer in the XLA lowering). Returns
    (B,Sq,H,hd) with the same head ordering as repeat_kv."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    if g == 1:
        return sdpa(q, k, v, mask)
    qg = q.reshape(b, sq, kv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None, None]
        else:  # (B,1,Sq,Sk) -> (B,1,1,Sq,Sk)
            mask = mask[:, :, None]
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, hd)


def causal_mask(sq: int, sk: int, window: int = 0,
                offset: int = 0) -> jax.Array:
    """(sq, sk) boolean mask; query i attends key j iff
    j <= i + offset and (window == 0 or j > i + offset - window)."""
    qi = jnp.arange(sq)[:, None] + offset
    kj = jnp.arange(sk)[None, :]
    m = kj <= qi
    if window > 0:
        m = m & (kj > qi - window)
    return m


def _mesh_set() -> bool:
    from repro.distributed.context import get_context
    ctx = get_context()
    return ctx is not None and ctx.mesh is not None


def _seq_parallel_attention(cfg: ModelConfig) -> bool:
    """Sequence-parallel full-seq attention when the head count does not
    tile the model axis: left to itself, GSPMD shards the CONTRACTING
    head_dim and all-reduces the full (S x S) score matrix per layer
    (measured 1.4 TB/device on qwen2-0.5b prefill — EXPERIMENTS.md §Perf
    H3). Sharding queries over sequence instead costs one small K/V gather
    and one output gather per layer."""
    from repro.distributed.context import get_context
    ctx = get_context()
    if ctx is None or ctx.mesh is None:
        return False
    return cfg.num_heads % ctx.axis_size(ctx.model_axis) != 0


def attention_forward(p: Params, cfg: ModelConfig, x: jax.Array,
                      positions: jax.Array, *, is_causal: bool = True
                      ) -> jax.Array:
    """Full-sequence self-attention (train / prefill, no cache output)."""
    q, k, v = _project_qkv(p, cfg, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if _seq_parallel_attention(cfg):
        q = constrain(q, "batch", "seq", None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)
    # full-seq paths keep the repeat_kv form: the score matrix dominates
    # traffic at these lengths and the grouped-einsum gradient adds
    # transposes (+8% bytes on qwen3 train — §Perf H4, refuted)
    k = _repeat_kv(k, cfg.num_heads)
    v = _repeat_kv(v, cfg.num_heads)
    s = x.shape[1]
    mask = causal_mask(s, s, cfg.sliding_window) if is_causal else None
    out = sdpa(q, k, v, mask)
    b = x.shape[0]
    return out.reshape(b, s, cfg.num_heads * cfg.head_dim) @ p["wo"]


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def kv_cache_len(cfg: ModelConfig, max_len: int) -> int:
    """Sliding-window archs keep a ring buffer of the window size."""
    if cfg.sliding_window > 0:
        return min(cfg.sliding_window, max_len)
    return max_len


def make_kv_cache(f: ArrayFactory, cfg: ModelConfig, batch: int,
                  max_len: int) -> Params:
    c_len = kv_cache_len(cfg, max_len)
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": f.zeros((batch, c_len, kv, hd)),
        "v": f.zeros((batch, c_len, kv, hd)),
    }


def prefill_attention(p: Params, cfg: ModelConfig, x: jax.Array,
                      positions: jax.Array, max_len: int
                      ) -> Tuple[jax.Array, Params]:
    """Causal attention over the prompt; returns output and the filled cache
    (padded/rolled to the cache length)."""
    q, k, v = _project_qkv(p, cfg, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if _seq_parallel_attention(cfg):
        q = constrain(q, "batch", "seq", None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)
    kr = _repeat_kv(k, cfg.num_heads)
    vr = _repeat_kv(v, cfg.num_heads)
    b, s = x.shape[:2]
    mask = causal_mask(s, s, cfg.sliding_window)
    out = sdpa(q, kr, vr, mask)
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim) @ p["wo"]

    c_len = kv_cache_len(cfg, max_len)
    if s >= c_len:
        cache = {"k": k[:, s - c_len:], "v": v[:, s - c_len:]}
        # ring-buffer alignment: slot i holds position (s - c_len + i); for
        # SWA we store so that slot = pos % c_len
        if cfg.sliding_window > 0:
            shift = (s - c_len) % c_len
            cache = {n: jnp.roll(a, shift, axis=1) for n, a in cache.items()}
    else:
        pad = [(0, 0), (0, c_len - s), (0, 0), (0, 0)]
        cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    return out, cache


def decode_attention(p: Params, cfg: ModelConfig, x: jax.Array,
                     cache: Params, cache_index: jax.Array
                     ) -> Tuple[jax.Array, Params]:
    """One-token decode. x (B,1,D); cache k/v (B,C,KV,hd); cache_index is the
    number of tokens already in context (the new token's position) — a
    scalar, or ``(B,)`` for a ragged batch of requests at different
    generation depths (continuous batching)."""
    b = x.shape[0]
    ragged = jnp.ndim(cache_index) != 0
    positions = jnp.broadcast_to(cache_index, (b,)).reshape(b, 1)
    q, k_new, v_new = _project_qkv(p, cfg, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k_new = apply_rope(k_new, positions, cfg.rope_theta)

    c_len = cache["k"].shape[1]
    slot = jnp.mod(cache_index, c_len) if cfg.sliding_window > 0 else cache_index
    if ragged:
        # per-row scatter: row i writes its own slot[i]
        onehot = jnp.arange(c_len)[None, :] == slot[:, None]      # (B,C)
        sel = onehot[:, :, None, None]
        k = jnp.where(sel, k_new.astype(cache["k"].dtype), cache["k"])
        v = jnp.where(sel, v_new.astype(cache["v"].dtype), cache["v"])
    else:
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot,
                                                axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot,
                                                axis=1)

    idx = jnp.arange(c_len)
    if ragged:
        if cfg.sliding_window > 0:
            valid = (idx[None, :] <= slot[:, None]) \
                | (cache_index[:, None] >= c_len)                 # (B,C)
        else:
            valid = idx[None, :] <= cache_index[:, None]          # (B,C)
        mask = valid[:, None, None, :]  # (B,1,1,C)
    else:
        if cfg.sliding_window > 0:
            # ring buffer: valid once written; all slots valid when full
            valid = (idx <= slot) | (cache_index >= c_len)
        else:
            valid = idx <= cache_index
        mask = valid[None, None, None, :]  # (1,1,1,C)
    if _mesh_set():
        # repeat_kv form: under GSPMD the grouped 5-dim einsum breaks
        # head-dim sharding propagation and replicates the cache (+4.9x
        # bytes measured, §Perf H4 refuted); on a mesh the grouped math
        # lives in the shard_map flash-decode body where layouts are
        # explicit.
        out = sdpa(q, _repeat_kv(k, cfg.num_heads),
                   _repeat_kv(v, cfg.num_heads), mask)
    else:
        # one device: attend grouped over the cache as it stands. The
        # repeat to H heads (upcast to f32 in the fused step) was ~48% of
        # the qwen2 -> danube cascade's device time on a v5e (PERF.md §6).
        out = sdpa_gqa(q, k, v, mask)
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim) @ p["wo"]
    return out, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Sharded flash-decoding (hillclimb H2, EXPERIMENTS.md §Perf)
# ---------------------------------------------------------------------------

def decode_attention_sharded(p: Params, cfg: ModelConfig, x: jax.Array,
                             cache: Params, cache_index: jax.Array,
                             ctx) -> Tuple[jax.Array, Params]:
    """One-token decode with the KV cache SEQUENCE-sharded over the model
    axis (flash-decoding): each shard attends its own cache chunk and the
    partial softmaxes combine with one tiny log-sum-exp reduction. The cache
    never moves — the baseline GSPMD lowering replicates it ("involuntary
    full rematerialization"), reading ~chips x more HBM than necessary.

    Not applicable to sliding-window archs (ring-buffer slots wrap across
    chunks); those keep the dense path.
    """
    import functools as _ft
    from jax.sharding import PartitionSpec as P

    assert cfg.sliding_window == 0, "SWA keeps the ring-buffer path"
    b = x.shape[0]
    positions = jnp.broadcast_to(cache_index, (b, 1))
    q, k_new, v_new = _project_qkv(p, cfg, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k_new = apply_rope(k_new, positions, cfg.rope_theta)

    h, kv_h, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    c_len = cache["k"].shape[1]
    model_axis = ctx.model_axis
    n_shards = ctx.axis_size(model_axis)
    chunk = c_len // n_shards
    # batch sharding only when it tiles exactly (long_500k has batch 1)
    dp_div = 1
    for a in ctx.batch_axes:
        dp_div *= ctx.axis_size(a)
    batch_axes = tuple(ctx.batch_axes) if b % dp_div == 0 else ()

    g = h // kv_h

    def body(q_loc, k_new_loc, v_new_loc, kc, vc, idx):
        # kc/vc: local cache chunk (B_loc, C/n, KV, hd). Precision is kept
        # surgical: the cache stays bf16 end to end (an f32 leak makes XLA
        # round-trip the whole scan-carried stack every layer — measured as
        # the dominant byte term of the first flash-decode iteration).
        shard = jax.lax.axis_index(model_axis)
        start = shard * chunk
        slot = idx - start  # position of the new token within this chunk
        in_range = (slot >= 0) & (slot < chunk)
        slot_c = jnp.clip(slot, 0, chunk - 1)
        # slot-level write: touch (B,1,KV,hd), never the whole chunk
        old_k = jax.lax.dynamic_slice_in_dim(kc, slot_c, 1, 1)
        old_v = jax.lax.dynamic_slice_in_dim(vc, slot_c, 1, 1)
        upd_k = jnp.where(in_range, k_new_loc.astype(kc.dtype), old_k)
        upd_v = jnp.where(in_range, v_new_loc.astype(vc.dtype), old_v)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, upd_k, slot_c, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, upd_v, slot_c, 1)

        # GQA-grouped attention: no kv-head repeat materialisation
        b_loc = q_loc.shape[0]
        qg = q_loc.reshape(b_loc, kv_h, g, hd)
        scores = jnp.einsum("bkgd,bckd->bkgc", qg, kc,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        valid = (jnp.arange(chunk) + start) <= idx            # (C_loc,)
        scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
        m_loc = jnp.max(scores, axis=-1)                      # (B,KV,G)
        p_loc = jnp.exp(scores - m_loc[..., None])
        p_loc = jnp.where(valid[None, None, None, :], p_loc, 0.0)
        l_loc = jnp.sum(p_loc, axis=-1)                       # (B,KV,G)
        # PV in bf16 (flash-style), accumulate f32
        acc = jnp.einsum("bkgc,bckd->bkgd", p_loc.astype(kc.dtype), vc,
                         preferred_element_type=jnp.float32)  # (B,KV,G,hd)
        # combine across shards: one pmax + two psums of tiny tensors
        m_glob = jax.lax.pmax(m_loc, model_axis)
        scale = jnp.exp(m_loc - m_glob)                       # (B,KV,G)
        l_glob = jax.lax.psum(l_loc * scale, model_axis)
        acc = jax.lax.psum(acc * scale[..., None], model_axis)
        out = acc / jnp.maximum(l_glob, 1e-30)[..., None]
        return out.reshape(b_loc, 1, h, hd).astype(q_loc.dtype), kc, vc

    dp = (batch_axes if len(batch_axes) != 1 else batch_axes[0]) or None
    out, k_cache, v_cache = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(dp, None, None, None),   # q (full heads, replicated)
                  P(dp, None, None, None),   # k_new
                  P(dp, None, None, None),   # v_new
                  P(dp, model_axis, None, None),   # cache k
                  P(dp, model_axis, None, None),   # cache v
                  P()),
        out_specs=(P(dp, None, None, None),
                   P(dp, model_axis, None, None),
                   P(dp, model_axis, None, None)),
        axis_names=set(batch_axes) | {model_axis}, check_vma=False,
    )(q, k_new, v_new, cache["k"], cache["v"], cache_index)
    out = out.reshape(b, 1, h * hd) @ p["wo"]
    return out, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# Cross attention (encoder-decoder)
# ---------------------------------------------------------------------------

def make_cross_attention_params(f: ArrayFactory, cfg: ModelConfig) -> Params:
    return make_attention_params(f, cfg)


def cross_attention(p: Params, cfg: ModelConfig, x: jax.Array,
                    memory: jax.Array) -> jax.Array:
    """x (B,Sq,D) attends to encoder memory (B,Sk,D); no causal mask, no rope
    on keys from memory (seamless uses relative/conformer pos in the encoder —
    stubbed out; decoder cross-attn is position-free)."""
    ck, cv = make_cross_kv(p, cfg, memory)
    out = cross_attention_cached(p, cfg, x, ck, cv)
    return out


def make_cross_kv(p: Params, cfg: ModelConfig, memory: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """Project encoder memory -> cached cross K/V (B, Sk, KV, hd)."""
    b, sk, _ = memory.shape
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    ck = (memory @ p["wk"]).reshape(b, sk, kv, hd)
    cv = (memory @ p["wv"]).reshape(b, sk, kv, hd)
    return ck, cv


def cross_attention_cached(p: Params, cfg: ModelConfig, x: jax.Array,
                           ck: jax.Array, cv: jax.Array) -> jax.Array:
    """Cross-attention against precomputed K/V (used at every decode step)."""
    b, sq, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, sq, h, hd)
    k = _repeat_kv(ck, h)
    v = _repeat_kv(cv, h)
    out = sdpa(q, k, v, None)
    return out.reshape(b, sq, h * hd) @ p["wo"]
