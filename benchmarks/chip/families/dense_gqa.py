"""Dense GQA decoders (qwen2-0.5b, h2o-danube-1.8b): their shapes, the
operations and bytes a call of the program needs, and the plain float32
``jax.numpy`` forward, written from the published descriptions and
independent of ``src/repro/models``.

Architecture (Qwen2 arXiv:2407.10671; H2O-Danube arXiv:2401.16818, a
Llama/Mistral decoder):

  x = E[tokens]
  per layer:  h = RMSNorm(x) ; q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
              RoPE (rotate-half, theta) on q, k ; causal GQA softmax
              attention (1/sqrt(head_dim)), sliding window if configured
              x = x + attn Wo ; h = RMSNorm(x)
              x = x + (silu(h Wgate) * (h Wup)) Wdown
  logits = RMSNorm(x) E^T (tied, qwen2) or RMSNorm(x) Whead (danube)

Counts: every count is of what the computation requires, whatever
implements it: a decode call reads the weights once and the live KV of its
active rows, and writes one new KV entry per active row. So the shares of
a roofline built on these counts cannot pass 100% by counting; a reading
above it means the time left out part of the work.

Reference: every product runs in float32 at ``Precision.HIGHEST``.
Departures from the published description: none in the mathematics; the
weights are random (``weights.py``), and the RMSNorm epsilon is the one
the configuration file states for the run. The weights are the arrays the
benchmark made, in the program's serving layout (a dict of rep-stacked
bf16 leaves); the reference only reads them by name, one layer at a time,
and upcasts that layer to float32. ``quant`` turns the same forward into
the precision control (``reference/ops.py``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from costs import KV_BYTES, NORM_BYTES, WEIGHT_BYTES
from reference.ops import HI, NEG, bucket, embed, mm, rms, rope


@dataclass(frozen=True)
class Arch:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    rope_theta: float
    eps: float
    window: int             # 0: full attention


def arch(model: dict) -> Arch:
    """From a configuration file's ``models`` entry (HF key names)."""
    heads = int(model["num_attention_heads"])
    return Arch(
        layers=int(model["num_hidden_layers"]),
        d_model=int(model["hidden_size"]),
        heads=heads,
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model.get("head_dim")
                     or model["hidden_size"] // heads),
        d_ff=int(model["intermediate_size"]),
        vocab=int(model["vocab_size"]),
        tied=bool(model["tie_word_embeddings"]),
        qkv_bias=bool(model.get("attention_bias", False)),
        rope_theta=float(model["rope_theta"]),
        eps=float(model["rms_norm_eps"]),
        window=int(model.get("sliding_window") or 0)
        if model.get("use_sliding_window", True) else 0)


# The shapes the program serves have to be the file's. The norm's epsilon
# is left to the comparison: the reference computes the file's (published)
# value, so a program that serves another one shows in ``correct``.

def stated(a: Arch) -> tuple:
    return (a.layers, a.d_model, a.heads, a.kv_heads, a.head_dim, a.d_ff,
            a.vocab, a.tied, a.qkv_bias, a.rope_theta, a.window)


def served(cfg) -> tuple:
    """The same shapes from the program's model config."""
    return (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size, cfg.tie_embeddings,
            cfg.qkv_bias, cfg.rope_theta, cfg.sliding_window)


# ----------------------------------------------------------------- counts

def layer_matmul_params(a: Arch) -> int:
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    return a.d_model * (q + 2 * kv) + q * a.d_model + 3 * a.d_model * a.d_ff


def matmul_params(a: Arch) -> int:
    """Weights one token multiplies through: every layer and the head."""
    return a.layers * layer_matmul_params(a) + a.vocab * a.d_model


def kv_bytes_per_token(a: Arch) -> int:
    return a.layers * 2 * a.kv_heads * a.head_dim * KV_BYTES


def weight_bytes_read(a: Arch, rows: int) -> int:
    """Bytes of weights one decode step reads: every layer's matrices,
    biases and gains, the final gain, the head, and ``rows`` rows of the
    embedding table (the tied table is read whole as the head)."""
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    per_layer = layer_matmul_params(a) * WEIGHT_BYTES \
        + 2 * a.d_model * NORM_BYTES \
        + (q + 2 * kv) * WEIGHT_BYTES * a.qkv_bias
    head = a.vocab * a.d_model * WEIGHT_BYTES
    gather = 0 if a.tied else rows * a.d_model * WEIGHT_BYTES
    return a.layers * per_layer + a.d_model * NORM_BYTES + head + gather


def token_flops(a: Arch, context: int) -> float:
    """Model FLOPs of one token that attends ``context`` positions (itself
    included): two per multiply-add through the weights, plus QK^T and PV."""
    return 2.0 * matmul_params(a) \
        + 4.0 * a.layers * a.heads * a.head_dim * context


def prefill_flops(a: Arch, prompt_len: int) -> float:
    """A causal prefill of ``prompt_len`` real tokens (pads excluded)."""
    n = prompt_len
    return 2.0 * matmul_params(a) * n \
        + 4.0 * a.layers * a.heads * a.head_dim * n * (n + 1) / 2


def decode_call(a: Arch, call) -> tuple:
    """(FLOPs, bytes) of one decode step over the call's active rows, each
    given by its depth before the step (tokens already in its cache)."""
    depths = [int(d) for d in call.depths]
    flops = sum(token_flops(a, d + 1) for d in depths)
    kv = kv_bytes_per_token(a)
    nbytes = weight_bytes_read(a, len(depths)) + sum(depths) * kv \
        + len(depths) * kv
    return flops, nbytes


def call_flops(a: Arch, call) -> float:
    """Model FLOPs of the real (unpadded) tokens of one call: an admit's
    prompts, or one decode token per active row."""
    if call.kind == "admit":
        return sum(prefill_flops(a, n) for n in call.prompt_lens)
    return sum(token_flops(a, d + 1) for d in call.depths)


# -------------------------------------------------------------- reference

@functools.partial(jax.jit, static_argnames=("a", "quant"))
def layer(x, blocks, i, a: Arch, quant: Optional[str]):
    """Layer ``i`` of the rep-stacked ``blocks`` applied to x (L, d)."""
    p = jax.tree.map(lambda w: w[i].astype(jnp.float32), blocks)
    L = x.shape[0]
    pos = jnp.arange(L)
    at = p["attn"]
    h = rms(x, p["norm1"]["scale"], a.eps)
    q, k, v = mm(h, at["wq"], quant), mm(h, at["wk"], quant), \
        mm(h, at["wv"], quant)
    if a.qkv_bias:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    q = rope(q.reshape(L, a.heads, a.head_dim), pos, a.rope_theta)
    k = rope(k.reshape(L, a.kv_heads, a.head_dim), pos, a.rope_theta)
    v = v.reshape(L, a.kv_heads, a.head_dim)
    rep = a.heads // a.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(a.head_dim)
    keep = pos[None, :] <= pos[:, None]
    if a.window:
        keep &= pos[None, :] > pos[:, None] - a.window
    s = jnp.where(keep[None], s, NEG)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    x = x + mm(o.reshape(L, a.heads * a.head_dim), at["wo"], quant)
    f = p["ffn"]
    h = rms(x, p["norm2"]["scale"], a.eps)
    g = jax.nn.silu(mm(h, f["w_gate"], quant)) * mm(h, f["w_up"], quant)
    return x + mm(g, f["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("a", "quant"))
def head(x, final_gain, table, target, a: Arch, quant: Optional[str]):
    """Per position: (max logit - logit of ``target``, top-1 minus top-2
    gap, argmax). ``table`` is the head: (d, V), or the tied (V, d)
    embedding."""
    h = rms(x, final_gain.astype(jnp.float32), a.eps)
    w = table.astype(jnp.float32)
    logits = mm(h, w.T if a.tied else w, quant)
    top2 = jax.lax.top_k(logits, 2)[0]
    tgt = jnp.take_along_axis(logits, jnp.clip(target, 0)[:, None], 1)[:, 0]
    return top2[:, 0] - tgt, top2[:, 0] - top2[:, 1], \
        jnp.argmax(logits, -1).astype(jnp.int32)


def hidden(a: Arch, params, tokens: np.ndarray, quant=None):
    """Final hidden states (Lb, d) f32 of ``tokens`` padded to a bucket."""
    blocks = params["blocks"]
    if len(blocks) != 1:
        raise ValueError("the reference serves dense decoders (period 1)")
    toks = np.zeros(bucket(tokens.size), np.int32)
    toks[:tokens.size] = tokens
    x = embed(params["embed"]["embedding"], toks)
    for i in range(a.layers):
        x = layer(x, blocks[0], i, a, quant)
    return x


def head_table(a: Arch, params):
    return params["embed"]["embedding"] if a.tied \
        else params["embed"]["lm_head"]


def stats(a: Arch, params, x, target: np.ndarray, quant=None):
    """(shortfall, gap, argmax) per position, as numpy arrays."""
    out = head(x, params["final_norm"]["scale"], head_table(a, params),
               jnp.asarray(target), a, quant)
    return tuple(np.asarray(o) for o in out)
