"""Plain float32 ``jax.numpy`` forward of the dense GQA decoders served
here (qwen2-0.5b, h2o-danube-1.8b), written from their published
descriptions and independent of ``src/repro/models``.

Architecture (Qwen2 arXiv:2407.10671; H2O-Danube arXiv:2401.16818, a
Llama/Mistral decoder):

  x = E[tokens]
  per layer:  h = RMSNorm(x) ; q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
              RoPE (rotate-half, theta) on q, k ; causal GQA softmax
              attention (1/sqrt(head_dim)), sliding window if configured
              x = x + attn Wo ; h = RMSNorm(x)
              x = x + (silu(h Wgate) * (h Wup)) Wdown
  logits = RMSNorm(x) E^T (tied, qwen2) or RMSNorm(x) Whead (danube)

Every product runs in float32 at ``Precision.HIGHEST``. Departures from the
published description: none in the mathematics; the weights are random
(``weights.py``), and the RMSNorm epsilon is the one the configuration file
states for the run.

The weights are the arrays the benchmark made, in the program's serving
layout (a dict of rep-stacked bf16 leaves); this module only reads them by
name, one layer at a time, and upcasts that layer to float32.

``quant`` turns the same forward into the precision control: every weight
matrix and every matmul input is rounded to int8 (symmetric, per output
channel and per token) or to fp8 e4m3 (scaled the same way) first.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from costs import Arch

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
BUCKETS = (256, 512, 1024, 2048, 4096)


def fake_quant(x, axis: int, quant: Optional[str]):
    """Round ``x`` to ``quant`` with one scale per slice along ``axis``."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if quant == "int8":
        s = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control precision {quant!r}")


def mm(x, w, quant):
    """x (L, k) @ w (k, n) in float32 at HIGHEST precision."""
    return jnp.dot(fake_quant(x, -1, quant), fake_quant(w, 0, quant),
                   precision=HI)


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, pos, theta):
    """x (L, H, hd): rotate-half RoPE at positions pos (L,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv          # (L, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(jnp.float32)


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


def bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"sequence of {n} tokens is longer than {BUCKETS[-1]}")


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
