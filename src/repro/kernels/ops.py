"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the kernels lower natively; on any other backend they run
in ``interpret=True`` mode — same kernel body, Python semantics — which is
how the tests validate them against ``ref.py``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.top2gap import top2gap_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_b", "block_v"))
def top2gap(scores: jax.Array, block_b: int = 8, block_v: int = 2048
            ) -> Tuple[jax.Array, jax.Array]:
    """(gap, argmax) over the last axis. scores (B, V)."""
    return top2gap_pallas(scores, block_b=block_b, block_v=block_v,
                          interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q (B,H,S,D), k/v (B,HKV,S,D) -> (B,H,S,D)."""
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_c",))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid_len: jax.Array, block_c: int = 512) -> jax.Array:
    """q (B,H,D), k/v (B,HKV,C,D), valid_len scalar or (B,) -> (B,H,D).

    A (B,) valid_len serves ragged decode batches (continuous batching):
    the kernel's vl BlockSpec already indexes per batch row."""
    return decode_attention_pallas(q, k, v, valid_len, block_c=block_c,
                                   interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk", "block_di"))
def mamba_scan(dt: jax.Array, a: jax.Array, b_mat: jax.Array,
               c_mat: jax.Array, d_vec: jax.Array, x: jax.Array,
               chunk: int = 128, block_di: int = 512) -> jax.Array:
    """Selective scan; see mamba_scan_pallas."""
    return mamba_scan_pallas(dt, a, b_mat, c_mat, d_vec, x, chunk=chunk,
                             block_di=block_di, interpret=_interpret())


# re-export oracles for convenience
top2gap_ref = ref.top2gap_ref
flash_attention_ref = ref.flash_attention_ref
decode_attention_ref = ref.decode_attention_ref
mamba_scan_ref = ref.mamba_scan_ref
