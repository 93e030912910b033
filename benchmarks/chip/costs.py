"""Operations and bytes the algorithm needs, from shapes alone.

Every count is of what the computation requires, whatever implements it:
a decode call reads the weights once and the live KV of its active rows,
and writes one new KV entry per active row. So the shares of a roofline
built on these counts cannot pass 100% by counting; a reading above it
means the time left out part of the work.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable

WEIGHT_BYTES = 2        # bf16, as served
KV_BYTES = 2            # bf16 cache
NORM_BYTES = 4          # f32 norm gains
LOGIT_BYTES = 4         # f32 logits into the top-2-gap kernel
KERNEL_LANES = 128      # the kernel writes one (rows, 128) tile per output


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


def decode_call(a: Arch, depths: Iterable[int]) -> tuple:
    """(FLOPs, bytes) of one decode step over the active rows, each given
    by its depth before the step (tokens already in its cache)."""
    depths = [int(d) for d in depths]
    flops = sum(token_flops(a, d + 1) for d in depths)
    kv = kv_bytes_per_token(a)
    nbytes = weight_bytes_read(a, len(depths)) + sum(depths) * kv \
        + len(depths) * kv
    return flops, nbytes


def top2gap_bytes(rows: int, vocab: int) -> int:
    """One top-2-gap call: read the (rows, vocab) logits, write two
    (rows, 128) tiles."""
    return rows * vocab * LOGIT_BYTES + 2 * rows * KERNEL_LANES * 4


def ideal_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
