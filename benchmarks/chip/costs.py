"""Byte sizes, the top-2-gap kernel's bytes and the roofline's least time:
what no one architecture owns. The operations and bytes of a model's calls
are its family's (``families/<family>.py``)."""
from __future__ import annotations

WEIGHT_BYTES = 2        # bf16, as served
KV_BYTES = 2            # bf16 cache
NORM_BYTES = 4          # f32 norm gains
LOGIT_BYTES = 4         # f32 logits into the top-2-gap kernel
KERNEL_LANES = 128      # the kernel writes one (rows, 128) tile per output


def top2gap_bytes(rows: int, vocab: int) -> int:
    """One top-2-gap call: read the (rows, vocab) logits, write two
    (rows, 128) tiles."""
    return rows * vocab * LOGIT_BYTES + 2 * rows * KERNEL_LANES * 4


def ideal_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
