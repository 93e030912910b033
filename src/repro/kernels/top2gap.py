"""Pallas TPU kernel: top-1 minus top-2 certainty gap (paper Eq. 5).

The paper's certainty estimator is a reduction over the score axis — at
serving scale this is (batch x vocab) with vocab up to 202k (llama4), a
genuine VPU hot spot downstream of the LM head. The kernel streams vocab
blocks HBM->VMEM and keeps running (top1, top2, argmax) accumulators in VMEM
scratch, fusing what would otherwise be two full top-k sorts.

Grid: (B/BB, V/BV), vocab innermost so the scratch carries across blocks.
Block sizes default to (8, 2048) — sublane x lane aligned (8, 128)-multiples.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# per-row results live in one lane-dense (rows, 128) tile, every lane holding
# the same value: Mosaic refuses rank-1 blocks that are not multiples of 128
_LANES = 128


def _top2gap_kernel(x_ref, gap_ref, idx_ref, m1, m2, ai, *, n_vblocks: int,
                    block_v: int, vocab: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m1[...] = jnp.full_like(m1, NEG_INF)
        m2[...] = jnp.full_like(m2, NEG_INF)
        ai[...] = jnp.zeros_like(ai)

    x = x_ref[...].astype(jnp.float32)  # (BB, BV)
    bb, bv = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (bb, bv), 1)
    # mask positions past the vocab (the tail of the last, partial block)
    x = jnp.where(lane + j * block_v < vocab, x, NEG_INF)

    loc1 = jnp.max(x, axis=-1, keepdims=True)                   # (BB, 1)
    # lowest lane holding the block maximum: ties go to the lowest index
    loc_arg = jnp.min(jnp.where(x == loc1, lane, bv), axis=-1,
                      keepdims=True)                            # (BB, 1)
    loc2 = jnp.max(jnp.where(lane == loc_arg, NEG_INF, x), axis=-1,
                   keepdims=True)                               # (BB, 1)

    cur1, cur2, cur_ai = m1[...], m2[...], ai[...]              # (BB, 128)
    # strict: an equal maximum in a later block keeps the earlier index
    better = loc1 > cur1
    # runner-up: best of {loser of (cur1, loc1), cur2, loc2}
    loser = jnp.where(better, cur1, loc1)
    m2[...] = jnp.maximum(loser, jnp.maximum(cur2, loc2))
    m1[...] = jnp.where(better, loc1, cur1)
    ai[...] = jnp.where(better, loc_arg + j * block_v, cur_ai)

    @pl.when(j == n_vblocks - 1)
    def _out():
        gap_ref[...] = m1[...] - m2[...]
        idx_ref[...] = ai[...]


def top2gap_pallas(scores: jax.Array, block_b: int = 8, block_v: int = 2048,
                   interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """scores (B, V) -> (gap (B,) f32, argmax (B,) i32).

    The grid covers (B, V) in (block_b, block_v) tiles without padding the
    input: the partial tiles at the edges are masked inside the kernel
    (vocab) or dropped on write-back (rows)."""
    b, v = scores.shape
    n_vblocks = pl.cdiv(v, block_v)
    kernel = functools.partial(_top2gap_kernel, n_vblocks=n_vblocks,
                               block_v=block_v, vocab=v)
    row_block = pl.BlockSpec((block_b, _LANES), lambda i, j: (i, 0))
    gap, idx = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(b, block_b), n_vblocks),
        in_specs=[pl.BlockSpec((block_b, block_v), lambda i, j: (i, j))],
        out_specs=[row_block, row_block],
        out_shape=[jax.ShapeDtypeStruct((b, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, _LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((block_b, _LANES), jnp.float32),
                        pltpu.VMEM((block_b, _LANES), jnp.float32),
                        pltpu.VMEM((block_b, _LANES), jnp.int32)],
        interpret=interpret,
    )(scores)
    return gap[:, 0], idx[:, 0]


def argmax_gap(scores: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Fused greedy-sampling reduction: scores (B, V) ->
    (argmax (B,) i32, top-1 minus top-2 gap (B,) f32).

    This is the device-resident decode loop's per-step reduction
    (DESIGN.md §14): folded INTO the jitted decode step so the step ships
    (B,) tokens + (B,) certainty values off-device instead of (B, V)
    logits. On a TPU backend it lowers to the Pallas kernel above (one
    HBM pass for both outputs); elsewhere it falls back to
    ``lax.top_k``/``argmax``, which is bit-identical to the host path the
    pre-fusion engine used (``core.certainty.top2_gap`` + ``np.argmax``) —
    both select the same maxima, ties broken to the lowest index.
    """
    if jax.default_backend() == "tpu":
        gap, idx = top2gap_pallas(scores)
        return idx, gap
    top2 = jax.lax.top_k(scores, 2)[0]
    gap = (top2[..., 0] - top2[..., 1]).astype(jnp.float32)
    return jnp.argmax(scores, axis=-1).astype(jnp.int32), gap
