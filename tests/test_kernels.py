"""Pallas kernel validation (interpret mode) against the ref.py oracles —
shape/dtype sweeps per the assignment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.top2gap import top2gap_pallas

RNG = np.random.default_rng(0)


def randf(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# ---------------------------------------------------------------------------
# top2gap
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,v", [(1, 128), (4, 1000), (8, 512), (3, 4097),
                                 (16, 3157), (2, 50304)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_top2gap_sweep(b, v, dtype):
    x = randf((b, v), dtype, 3.0)
    gap, idx = top2gap_pallas(x, interpret=True)
    gref, iref = ops.top2gap_ref(x)
    np.testing.assert_allclose(np.asarray(gap), np.asarray(gref),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(iref))


def test_top2gap_ties_and_blocks():
    # identical top-2 values across block boundaries
    x = np.zeros((2, 1024), np.float32)
    x[0, 5] = 7.0
    x[0, 700] = 7.0  # exact tie in another vocab block
    x[1, 1000] = 3.0
    x[1, 1] = 2.5
    gap, idx = top2gap_pallas(jnp.asarray(x), block_v=512, interpret=True)
    assert abs(float(gap[0])) < 1e-6
    assert abs(float(gap[1]) - 0.5) < 1e-6
    assert int(idx[1]) == 1000


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,hkv,s,d", [
    (2, 4, 2, 64, 32), (1, 8, 8, 96, 16), (2, 4, 1, 160, 64),
    (1, 2, 2, 33, 32),  # ragged seq (padding path)
])
def test_flash_attention_sweep(b, h, hkv, s, d):
    q = randf((b, h, s, d))
    k = randf((b, hkv, s, d))
    v = randf((b, hkv, s, d))
    out = flash_attention_pallas(q, k, v, block_q=32, block_k=32,
                                 interpret=True)
    ref = ops.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("window", [16, 48])
def test_flash_attention_sliding_window(window):
    b, h, s, d = 1, 4, 128, 32
    q, k, v = randf((b, h, s, d)), randf((b, 2, s, d)), randf((b, 2, s, d))
    out = flash_attention_pallas(q, k, v, window=window, block_q=32,
                                 block_k=32, interpret=True)
    ref = ops.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
    b, h, s, d = 2, 4, 64, 32
    q = randf((b, h, s, d), jnp.bfloat16)
    k = randf((b, 2, s, d), jnp.bfloat16)
    v = randf((b, 2, s, d), jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, block_q=32, block_k=32,
                                 interpret=True)
    ref = ops.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,hkv,c,d,vl", [
    (2, 8, 2, 256, 32, 100), (1, 4, 4, 64, 16, 64), (3, 16, 8, 640, 64, 639),
    (2, 4, 1, 100, 32, 1),   # single valid position
])
def test_decode_attention_sweep(b, h, hkv, c, d, vl):
    q = randf((b, h, d))
    k = randf((b, hkv, c, d))
    v = randf((b, hkv, c, d))
    out = decode_attention_pallas(q, k, v, jnp.asarray(vl), block_c=64,
                                  interpret=True)
    ref = ops.decode_attention_ref(q, k, v, jnp.asarray(vl))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("vl", [[100, 7, 256], [1, 64, 33]])
def test_decode_attention_ragged_batch(vl):
    """Per-row (B,) valid_len — the continuous-batching cache layout:
    matches the ref oracle AND per-row scalar calls (row independence)."""
    b, h, hkv, c, d = 3, 8, 2, 256, 32
    q = randf((b, h, d))
    k = randf((b, hkv, c, d))
    v = randf((b, hkv, c, d))
    vl_arr = jnp.asarray(vl, jnp.int32)
    out = decode_attention_pallas(q, k, v, vl_arr, block_c=64,
                                  interpret=True)
    ref = ops.decode_attention_ref(q, k, v, vl_arr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for i in range(b):
        solo = decode_attention_pallas(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                       jnp.asarray(vl[i]), block_c=64,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(solo[0]),
                                   atol=1e-6)


def test_decode_attention_valid_len_masks_garbage():
    b, h, hkv, c, d = 1, 4, 2, 128, 32
    q = randf((b, h, d))
    k = randf((b, hkv, c, d))
    v = randf((b, hkv, c, d))
    # poison the invalid region: result must not change
    k2 = k.at[:, :, 64:].set(1e4)
    v2 = v.at[:, :, 64:].set(-1e4)
    o1 = decode_attention_pallas(q, k, v, jnp.asarray(64), block_c=64,
                                 interpret=True)
    o2 = decode_attention_pallas(q, k2, v2, jnp.asarray(64), block_c=64,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


# ---------------------------------------------------------------------------
# mamba scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,di,n,chunk", [
    (2, 64, 64, 8, 32), (1, 200, 128, 16, 64), (2, 33, 32, 4, 16),
])
def test_mamba_scan_sweep(b, s, di, n, chunk):
    dt = jnp.abs(randf((b, s, di))) * 0.1
    a = -jnp.abs(randf((di, n)))
    bm, cm = randf((b, s, n)), randf((b, s, n))
    dv = randf((di,))
    x = randf((b, s, di))
    y = mamba_scan_pallas(dt, a, bm, cm, dv, x, chunk=chunk, block_di=32,
                          interpret=True)
    yref, _ = ops.mamba_scan_ref(dt, a, bm, cm, dv, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), atol=2e-4)


def test_mamba_scan_state_carries_across_chunks():
    """Same result regardless of chunking — the VMEM state must carry."""
    b, s, di, n = 1, 96, 32, 8
    dt = jnp.abs(randf((b, s, di))) * 0.2
    a = -jnp.abs(randf((di, n)))
    bm, cm = randf((b, s, n)), randf((b, s, n))
    dv = randf((di,))
    x = randf((b, s, di))
    y1 = mamba_scan_pallas(dt, a, bm, cm, dv, x, chunk=96, block_di=32,
                           interpret=True)
    y2 = mamba_scan_pallas(dt, a, bm, cm, dv, x, chunk=16, block_di=32,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


def test_ops_wrappers_jit():
    """The jit'd public wrappers run end to end."""
    gap, idx = ops.top2gap(randf((4, 512)))
    assert gap.shape == (4,)
    out = ops.flash_attention(randf((1, 2, 32, 16)), randf((1, 2, 32, 16)),
                              randf((1, 2, 32, 16)), block_q=16, block_k=16)
    assert out.shape == (1, 2, 32, 16)
