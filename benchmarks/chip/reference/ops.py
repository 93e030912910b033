"""Float32 building blocks of the plain references (``families/``), at
``Precision.HIGHEST``, independent of the program under test.

``quant`` turns a product into the precision control: each matmul input is
rounded to int8 (symmetric, per output channel and per token) or to fp8
e4m3 (scaled the same way) first.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

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


@jax.jit
def embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def bucket(n: int) -> int:
    """The padded length of an ``n``-token sequence: few shapes, few
    compiles."""
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"sequence of {n} tokens is longer than {BUCKETS[-1]}")
