"""Kernel benchmark: per-kernel correctness (vs oracle) + analytic TPU-v5e
roofline terms for the production shapes each kernel serves.

Off the TPU the kernels run in interpret mode, so the correctness checks
hold on any backend; the roofline terms are derived from the kernels' exact
FLOP/byte counts and the v5e constants (these are the numbers the block
sizes were chosen against), not timed."""
from __future__ import annotations

import numpy as np

from benchmarks.common import Results
from repro.profiling import hw


def main(quick: bool = False):
    import jax.numpy as jnp
    from repro.kernels import ops
    res = Results("bench_kernels")
    rng = np.random.default_rng(0)

    # ---- correctness spot checks (full sweeps live in tests) ---------------
    x = jnp.asarray(rng.standard_normal((8, 4096)), jnp.float32)
    gap, _ = ops.top2gap(x)
    gr, _ = ops.top2gap_ref(x)
    res.add("top2gap_max_err", float(np.abs(np.asarray(gap - gr)).max()))

    q = jnp.asarray(rng.standard_normal((1, 4, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    ref = ops.flash_attention_ref(q, k, v)
    res.add("flash_attention_max_err",
            float(np.abs(np.asarray(out - ref)).max()))

    # ---- analytic rooflines at production shapes ---------------------------
    # top2gap on llama4 logits: (B=128, V=202048) bf16, per model shard /16
    b_, v_ = 128, 202048 // 16
    bytes_in = b_ * v_ * 2
    t_mem = bytes_in / hw.HBM_BW
    flops = 3 * b_ * v_  # compare+select ~3 ops/elem
    t_cmp = flops / (hw.PEAK_FLOPS_BF16 / 8)  # VPU ~ 1/8 of MXU peak
    res.add("top2gap_llama4_bound",
            "memory" if t_mem > t_cmp else "compute",
            t_mem_us=round(t_mem * 1e6, 1), t_vpu_us=round(t_cmp * 1e6, 1),
            note="fused into LM-head epilogue saves a full logits round-trip")

    # flash attention prefill qwen3 shard: B=2,H=4(of 64/16),S=32768,D=128
    b_, h_, s_, d_ = 2, 4, 32768, 128
    fl = 4 * b_ * h_ * s_ * s_ * d_ / 2  # causal half
    byt = b_ * h_ * s_ * d_ * 2 * 4  # q,k,v,o bf16-ish traffic
    res.add("flash_prefill_qwen3_intensity", round(fl / byt, 1),
            t_compute_ms=round(fl / hw.PEAK_FLOPS_BF16 * 1e3, 2),
            t_memory_ms=round(byt / hw.HBM_BW * 1e3, 3),
            bound="compute")

    # decode attention llama4 shard: B=8, HKV=8, C=32768, D=128 (C-sharded/16)
    b_, hkv_, c_, d_ = 8, 8, 32768 // 16, 128
    kv_bytes = 2 * b_ * hkv_ * c_ * d_ * 2
    res.add("decode_attention_llama4_bound", "memory",
            kv_read_mb=round(kv_bytes / 2 ** 20, 1),
            t_memory_us=round(kv_bytes / hw.HBM_BW * 1e6, 1),
            note="pure HBM stream; kernel reads each KV block exactly once "
                 "per GQA group")

    # mamba scan falcon shard: B=2, S=32768, Di=512(of 8192/16), N=16
    b_, s_, di_, n_ = 2, 32768, 512, 16
    el = b_ * s_ * di_ * n_
    flops_scan = el * 6  # exp, 2 mul, add, mul, add per (t, di, n)
    byt_scan = b_ * s_ * (di_ * 4 * 3 + n_ * 4 * 2)
    res.add("mamba_scan_falcon_bound",
            "compute(VPU)" if flops_scan / (hw.PEAK_FLOPS_BF16 / 8)
            > byt_scan / hw.HBM_BW else "memory",
            t_vpu_ms=round(flops_scan / (hw.PEAK_FLOPS_BF16 / 8) * 1e3, 3),
            t_memory_ms=round(byt_scan / hw.HBM_BW * 1e3, 3))

    # VMEM working sets (must fit 128 MiB)
    for name, ws in [
        ("flash_attention", (128 * 128 + 2 * 128 * 128 + 128 * 128) * 4),
        ("decode_attention", (8 * 128 + 2 * 512 * 128 + 8 * 128) * 4),
        ("mamba_scan", (128 * 512 * 3 + 512 * 16) * 4),
        ("top2gap", (8 * 2048 + 3 * 8 * 128) * 4),
    ]:
        res.add(f"{name}_vmem_kb", round(ws / 1024, 1),
                fits_vmem=bool(ws < hw.VMEM_BYTES))
    return res.finish()


if __name__ == "__main__":
    main()
