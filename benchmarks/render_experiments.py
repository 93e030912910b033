"""Render EXPERIMENTS.md from the dry-run/benchmark artifacts.

    PYTHONPATH=src python -m benchmarks.render_experiments
"""
from __future__ import annotations

import json
import os

import numpy as np

ART = os.path.join(os.path.dirname(__file__), "artifacts")


def load(name, required=False):
    path = os.path.join(ART, name)
    if not os.path.exists(path):
        if required:
            raise FileNotFoundError(
                f"{path} is missing — EXPERIMENTS.md cannot be rendered "
                f"without it (run `PYTHONPATH=src python -m "
                f"repro.launch.dryrun --all --mesh both --flash-decode` "
                f"first); refusing to emit a report whose tables would "
                f"contradict its narrative")
        return []
    return json.load(open(path))


def bench_rows(name):
    return {r["name"]: r for r in load(f"{name}.json")}


def _multi_tracking_note(single, multi):
    """How closely the 512-chip rows track single-pod, computed from the
    artifacts rather than asserted."""
    s = {(r["arch"], r["shape"]): r["roofline_fraction"]
         for r in single if r["status"] == "ok"}
    m = {(r["arch"], r["shape"]): r["roofline_fraction"]
         for r in multi if r["status"] == "ok"}
    diffs = [abs(m[k] - s[k]) / s[k] for k in s if k in m and s[k] > 0]
    if not diffs:
        return "No paired multi-pod cells."
    return (f"Multi-pod rows (512 chips) track single-pod within "
            f"{np.median(diffs) * 100:.0f}% at the median; serving cells "
            f"whose global batch is fixed lose up to "
            f"{max(diffs) * 100:.0f}% of their fraction at 2x chips "
            "(per-device work halves, fixed per-step costs do not).")


def main() -> None:
    single = load("dryrun_single.json", required=True)
    multi = load("dryrun_multi.json", required=True)
    rows = single + multi
    ok = [r for r in rows if r["status"] == "ok"]
    if not ok:
        raise RuntimeError(
            "dry-run artifacts contain zero ok cells — the sweep failed; "
            "refusing to render a report that would misstate the record")

    out = []
    w = out.append
    w("# EXPERIMENTS — CascadeServe on JAX/TPU\n")
    w("All numbers in this file are regenerated by committed code: the "
      "dry-run tables by `repro.launch.dryrun` (artifacts under "
      "`benchmarks/artifacts/`), the paper-figure numbers by "
      "`python -m benchmarks.run`, and this file by "
      "`python -m benchmarks.render_experiments`. Hardware model: TPU v5e "
      "(197 bf16 TFLOP/s, 819 GB/s HBM, 16 GiB, ~50 GB/s/link ICI); "
      "single-pod mesh (data=16, model=16)=256 chips, multi-pod "
      "(pod=2, data=16, model=16)=512.\n")

    # ------------------------------------------------------------- dry-run
    w("\n## §Dry-run — 80 cells: 10 architectures x 4 input shapes x "
      "{single, multi-pod}\n")
    n_ok = len(ok)
    n_skip = sum(r["status"] == "skip" for r in rows)
    n_err = sum(r["status"] == "error" for r in rows)
    w(f"**{n_ok} compiled OK, {n_skip} documented skips (long_500k on "
      f"pure full-attention archs, DESIGN.md §6), {n_err} errors.** "
      "Every cell is `jax.jit(step).lower(**ShapeDtypeStructs).compile()` "
      "on the production mesh with explicit param/optimizer/cache "
      "shardings; decode cells use the sharded flash-decoding variant "
      "(§Perf H2). Compile wall times measured on this container's single "
      "CPU core.\n")
    w("| arch | shape | mesh | status | compile s | args GiB/dev | "
      "temp GiB/dev |")
    w("|---|---|---|---|---|---|---|")
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        if r["status"] == "ok":
            ma = r.get("memory_analysis", {})
            chips = r["chips"]
            args_g = ma.get("argument_size_in_bytes", 0) / 2 ** 30
            temp_g = ma.get("temp_size_in_bytes", 0) / 2 ** 30
            w(f"| {r['arch']} | {r['shape']} | {r['mesh']} | ok | "
              f"{r['compile_seconds']:.0f} | {args_g:.2f} | {temp_g:.2f} |")
        else:
            w(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
              f"{r['status'].upper()} | - | - | - |")
    w("\nMemory-analysis sizes are per device (the post-SPMD module is the "
      "per-device program). Cells whose temp size exceeds 16 GiB at the "
      "assigned global batch need microbatching on real v5e — measured in "
      "§Perf H1 (qwen3 train_4k: 126 GiB -> 9.3 GiB at 16 microbatches); "
      "the per-cell knob is `TrainStepConfig.num_microbatches`.\n")

    # ------------------------------------------------------------ roofline
    w("\n## §Roofline — per (arch x shape), single-pod 256 chips\n")
    w("Terms from the compiled artifact: FLOPs/bytes/collective bytes per "
      "device from the loop-aware HLO cost analysis "
      "(`repro.profiling.hlo_cost` — XLA's own `cost_analysis()` counts "
      "scan bodies ONCE; ours derives loop trip counts and is validated "
      "exact on known programs in tests/test_roofline.py). "
      "`MODEL_FLOPS` = 6·N_active·D (train) / 2·N_active·D (+ attention "
      "terms); `useful` = MODEL_FLOPS / (HLO_FLOPs x chips). "
      "`roofline` = the useful work's own bound time (max of compute and "
      "memory — decode is legitimately memory-bound) over the achieved "
      "bound time.\n")
    w("| arch | shape | t_compute | t_memory | t_collective | dominant | "
      "useful | roofline | what moves the dominant term |")
    w("|---|---|---|---|---|---|---|---|---|")

    def fix_note(r):
        if r["dominant"] == "memory" and r["shape"] in ("train_4k",
                                                        "prefill_32k"):
            return ("quadratic score materialisation -> Pallas flash-"
                    "attention kernel (VMEM-resident scores)")
        if r["dominant"] == "memory" and ("decode" in r["shape"]
                                          or r["shape"] == "long_500k"):
            return ("KV/weight streaming; bf16->f32 dot shadows are a "
                    "CPU-backend artifact (TPU MXU reads bf16)")
        if r["dominant"] == "collective":
            return "resharding: see §Perf H3 (sequence-parallel attention)"
        return "MXU utilisation (block shapes, fusion)"

    for r in sorted((r for r in single if r["status"] == "ok"),
                    key=lambda r: (r["arch"], r["shape"])):
        w(f"| {r['arch']} | {r['shape']} | {r['t_compute'] * 1e3:.0f} ms | "
          f"{r['t_memory'] * 1e3:.0f} ms | {r['t_collective'] * 1e3:.0f} ms"
          f" | {r['dominant']} | {r['useful_flops_ratio']:.2f} | "
          f"{r['roofline_fraction']:.3f} | {fix_note(r)} |")
    single_ok = [r["roofline_fraction"] for r in single
                 if r["status"] == "ok"]
    if not single_ok:
        raise RuntimeError("no ok single-pod cells — cannot summarise the "
                           "roofline section")
    med = float(np.median(single_ok))
    pair_note = _multi_tracking_note(single, multi)
    w(f"\nMedian roofline fraction {med:.3f}. {pair_note} "
      "The pod axis adds only the DCN gradient all-reduce (train) or "
      "nothing (serving; requests are pod-local by design) — see "
      "dryrun_multi.json.\n")

    w("\n## §Perf — hillclimb log (hypothesis -> change -> before -> after)"
      "\n")
    w(PERF_NARRATIVE)

    # ------------------------------------------------- paper-claim summary
    w("\n## Paper-claim validation (benchmarks, `python -m benchmarks.run`)"
      "\n")
    w(CLAIMS_NARRATIVE)

    # -------------------------------------------------- latency attribution
    attr_path = os.path.join(ART, "telemetry_attribution.json")
    if os.path.exists(attr_path):
        from repro.core.telemetry import Telemetry
        attr = json.load(open(attr_path))
        bench = bench_rows("bench_telemetry")
        w("\n## §Telemetry — latency attribution (DESIGN.md §16)\n")
        oh = bench.get("span_overhead_pct", {}).get("value")
        if oh is not None:
            noise = " (negative sign: ON median beat OFF, i.e. the " \
                    "residual is timing noise, not span cost)" \
                if oh < 0 else ""
            w(f"Span overhead on the scalar DES decision loop: **{oh}%**"
              f"{noise} (target |overhead| <2%, CI smoke gate +/-5%; "
              "`bench_telemetry`). "
              "Attribution decomposes every completed request's "
              "end-to-end latency into telescoping span components — the "
              "per-group sums reconcile with measured latency by "
              "construction "
              f"(worst relative error "
              f"{bench.get('attr_reconcile_worst_rel_err', {}).get('value', 'n/a')}).\n")
        w("Feature trace (cascade escalations + straggler hedges + spot "
          "drain->revoke), per gear and admit window:\n")
        w("```")
        w(Telemetry.render_attribution(attr))
        if attr.get("by_window"):
            comps = sorted(attr["total"]["components"])
            w("")
            w("  ".join(["window"] + [f"{c}_ms" for c in comps]
                        + ["end_to_end_ms", "n"]))
            for wk in sorted(attr["by_window"], key=int):
                g = attr["by_window"][wk]
                w("  ".join(
                    [wk] + [f"{g['components'].get(c, 0.0) * 1e3:.1f}"
                            for c in comps]
                    + [f"{g['end_to_end'] * 1e3:.1f}", str(g["count"])]))
        w("```\n")

    with open("EXPERIMENTS.md", "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"EXPERIMENTS.md written: {len(out)} lines, "
          f"{n_ok} ok / {n_skip} skip / {n_err} err cells")


PERF_NARRATIVE = r"""
Methodology per the assignment: baseline every cell (§Roofline), pick the
three most interesting, iterate hypothesis -> change -> measure -> record.
All numbers are per-device from the compiled single-pod artifact.
A CPU-backend caveat applies to all *byte* terms: XLA-CPU cannot execute
bf16 dots natively, so it materialises f32 copies of bf16 operands; on the
TPU target (MXU consumes bf16 directly) these shadow converts do not
exist. Both baselines and optimised variants carry the artifact equally,
so the relative improvements are conservative.

### H2 — llama4-maverick-400b-a17b x decode_32k (most representative of
the paper's serving technique; memory-bound)

* **Baseline** (cache batch-sharded over data, kv-heads over model — kv=8
  does not tile the 16-way axis): bytes/dev **1.27e12**, collectives
  7.98e9, roofline 0.004. SPMD warns "involuntary full rematerialization":
  the ring-buffer update + attention on the C-sharded cache makes GSPMD
  gather/replicate the whole cache per step (~250x minimum traffic).
* **It. 1 — hypothesis:** a shard_map flash-decoding attention (cache
  sequence-sharded over model, per-shard partial softmax, one
  log-sum-exp combine of (B,H) scalars) removes all cache movement;
  napkin: cache/dev 3.2 GB read once + weights ~2.5 GB => ~6 ms memory
  term. **Change:** `attention.decode_attention_sharded` (+ DistContext
  flash_decode). **After:** bytes 9.87e11 (-22%), collectives 1.54e9
  (-81%). CONFIRMED in direction, magnitude blocked by two artifacts
  found via `hlo_cost.top_contributors`:
  (a) byte accounting charged in-place dynamic-update-slice and fused
  dynamic-slice reads at full-buffer size — fixed in the cost model
  (fusion-aware accounting, validated in tests);
  (b) an f32 shadow of the whole scan-carried cache stack: mixed-precision
  einsums let f32 leak into the cache value path, so XLA round-tripped
  the full stack bf16->f32->bf16 every layer.
* **It. 2 — change:** slot-level cache write (touch (B,1,KV,hd), never the
  chunk), GQA-grouped einsums (no 5x repeat_kv materialisation),
  `preferred_element_type=f32` with bf16 probs for the PV matmul.
  **After:** bytes/dev **3.15e11** (2.1x better than corrected baseline
  6.62e11), collectives 1.54e9 (5.2x better), roofline 0.007 -> **0.016**
  (2.3x). Numerics: exact vs the dense path (4.2e-7 max err, test-covered).
* Remaining gap to the ~6 ms physical floor is the CPU f32-dot shadow on
  the cache read (absent on TPU) — the Pallas `decode_attention` kernel
  (implemented, interpret-validated) IS this loop with VMEM accumulators;
  kernel-adjusted memory term: (3.2 GB cache + 2.5 GB weights + MoE
  dispatch ~0.4 GB) / 819 GB/s = **7.4 ms** => kernel-adjusted roofline
  ~0.8 for this cell.

### H3 — qwen2-0.5b x prefill_32k (most collective-bound cell)

* **Baseline:** collective term **29.1 s/dev** (!), dominant=collective,
  roofline 0.0017. Diagnosis (top_contributors): `all-reduce of
  f32[2,7,32768,32768]` x24 layers = 1.44 TB/dev — 14 heads do not tile
  the 16-way model axis, so GSPMD sharded the CONTRACTING head_dim of
  q·k^T and all-reduced the full 32k x 32k score matrix every layer.
* **Hypothesis:** shard queries over SEQUENCE instead (sequence-parallel
  attention): K/V for GQA kv=2 are tiny (16 MB/layer gather), the output
  gather is (B,S,D) ~112 MB/layer; napkin ~3.5 GB total vs 1.44 TB.
  **Change:** `_seq_parallel_attention` — auto-applied when
  `num_heads % model_axis != 0` (also fires for llama4's 40 heads and
  internvl2). **After:** collectives 1.457e12 -> **1.17e10** (-99.2%),
  dominant flips to memory, roofline 0.0017 -> **0.019** (11x). CONFIRMED.
* The remaining memory term is the materialised (B,H,S/16,S) scores —
  the flash-attention Pallas kernel's job on TPU (same VMEM-residency
  argument as H2; kernel-adjusted memory term ~0.9 s => roofline ~0.09
  for a 0.5B model at 1M tokens on 256 chips, embedding-dominated).

### H1 — qwen3-32b x train_4k (largest dense training cell; memory-bound)

* **Baseline** (full remat, no microbatching): flops/dev 1.126e15
  (useful ratio 0.76 — the remat fwd-recompute +1/3), bytes/dev
  2.91e13 (t_mem 35.6 s), collectives 7.21e11, **temp memory 126
  GiB/device — does NOT fit 16-GiB v5e HBM.**
* **It. 1 — hypothesis:** `dots` remat policy (save matmul outputs) cuts
  recompute flops at some memory cost. **After:** flops -17% (9.4e14)
  but bytes +33% (3.74e13) and temp 242 GiB. REFUTED for this
  memory-bound cell — recorded and reverted.
* **It. 2 — hypothesis:** gradient-accumulation microbatching shrinks
  live activations ~linearly at small traffic cost. **After:**
  microbatches=4: temp 32.8 GiB (bytes +2%); microbatches=16: temp
  **9.3 GiB — fits v5e**, bytes +17% (t_mem 40.4 s), collectives +19%.
  CONFIRMED: mb=16 is the shipping config for this cell (the dry-run
  table reports the no-microbatch baseline; the knob is
  `TrainStepConfig.num_microbatches`).
* Dominant memory remains the (B,H,S,S) attention scores (4.3 GB/layer
  f32 per device-shard, fwd+bwd+remat): the flash-attention kernel keeps
  them VMEM-resident on TPU; kernel-adjusted t_mem ~= 6.1 s => roofline
  ~0.67 with t_useful_c = 4.08 s.

### H4 — GQA grouped-einsum attention (REFUTED, recorded)

* **Hypothesis:** replacing `repeat_kv` + standard SDPA with a group-
  structured einsum (q reshaped (B,S,KV,G,hd), K/V never expanded) should
  cut K/V traffic G x in every attention path. **Measured:** qwen3
  prefill_32k unchanged (score matrix dominates, the bf16 repeat is
  noise); qwen3 train_4k bytes +8% (the 5-dim einsum's GRADIENT adds
  transposes); h2o-danube decode_32k bytes **+4.9x** — under GSPMD the
  grouped einsum breaks head-dim sharding propagation and the partitioner
  replicates the cache. REFUTED for all pjit paths; reverted. The grouped
  math survives only inside the manual shard_map flash-decode body (H2
  it. 2), where layouts are explicit — a crisp lesson that einsum
  *structure* is part of the sharding interface, not just the math.
* **One device, revisited.** The served decode path has no
  mesh, and there a v5e chip trace refuted the refutation. In the
  qwen2-0.5b -> h2o-danube-1.8b cascade (16 slots x 2048 a stage) the
  repeat form's four ops (K/V broadcast to the query heads in f32 and
  bf16, and their two multiply-reduces) took 1.21 s of ~2.53 s busy,
  ~48% of device time (PERF_LEDGER.jsonl). Where no mesh is set,
  `decode_attention` now attends grouped over the bf16 cache
  (`sdpa_gqa`): danube's attention is ~2.9 ms of a 20.5 ms fused call
  on the device, and the cascade's `itl_p95_ms` fell from 105.6 to
  54.9 ms (medians of three runs each side; PERF.md §6). The GSPMD
  result above stands for every path with a mesh, which keeps the
  repeat form.

### Beyond-paper optimisations shipped as defaults

* Expert parallelism for MoE via shard_map (tokens over data, experts over
  data, TP over model; a2a stays on ICI — the pod axis never carries
  dispatch traffic).
* Sharded flash-decoding (H2) and sequence-parallel attention (H3),
  auto-enabled where applicable.
* ZeRO-1 optimizer-state sharding over the pod axis + int8-quantised
  gradient exchange on DCN (halves cross-pod bytes; loss-convergence
  verified in tests/test_multidevice.py).
* Loop-aware, fusion-aware HLO cost analysis (the measurement tool itself;
  XLA's counts scan bodies once and would have hidden ~60x of the above).
"""

CLAIMS_NARRATIVE = r"""
Numbers from `benchmarks/artifacts/*.json` (regenerate:
`PYTHONPATH=src python -m benchmarks.run`); `bench_output.txt` holds the
CSV of the committed run. REAL = measured on trained tiny-transformer
models served by the threaded runtime on this CPU.

| paper claim | our result (final committed run) | where |
|---|---|---|
| Fig. 1: cascades match the big model's accuracy at a fraction of its cost (3.8x) | REAL tiny family: cascade hits t-base accuracy (0.963) at lower efficient-batch cost (factor in bench_profiles.json; the 5-model CPU family has a narrower latency spread than BERT-Tiny->Base, so the factor is smaller than 3.8x) | bench_profiles |
| Fig. 2: placement + batch sizes reorder which cascade is fastest | reproduced: per-cascade p95 shifts across the three configurations at near-capacity load | bench_profiles |
| Figs. 5/6: CascadeServe dominates baselines at fixed devices | BERT-like @2 dev, 20k peak, 400ms SLO: CS acc 0.9525 vs MS+/DynBa lower-or-infeasible, Cocktail+ SLO-infeasible; Llama-like accuracy-SLO (0.55): CS meets it at 82 ms p95 | bench_end_to_end |
| Fig. 7: 2-3x fewer devices across the acc-latency grid | at 400 ms SLO: 1.0-1.67x fewer devices; at 50 ms SLO the tiny CPU family inverts (0.6-0.75x) — with 25 us/sample models, queueing mechanics beat cascade work-saving, an honest boundary of the technique that the paper's 10-450 ms models never enter | bench_cost_grid |
| Figs. 8/9: SLO-driven degradation through spikes | 10x spike: CS holds the 400 ms SLO with ONE device (worst p95 72 ms, acc 0.932) where MS+ needs 3 devices for the same accuracy band and DynBa needs 4; accuracy-SLO llama runs trade latency exactly as Fig. 9 describes (llama_lat2s p95 2.8 s > SLO during spikes) | bench_degradation |
| Fig. 10: planner ~= exhaustive, >> random | planner accuracy within 0.002 of exhaustive at ~300x less time; random sampling with 2x the planner's budget is ~13 pts worse | bench_planner_quality |
| Fig. 11: planning cost grows with n_ranges, minutes at realistic sizes | 2->16 ranges sweep: seconds to ~2 min; submodule-call counts recorded | bench_planner_cost |
| Fig. 12: both gear switching and cascading contribute | full 0.954 @ SLO-ok; No-Switching -1.1 pt accuracy; No-Cascade keeps accuracy but VIOLATES the SLO (p95 2.78 s) — cascading is what makes the load servable | bench_ablation |
| Fig. 13 (App. C): simulator matches the real system | after calibrating the runtime's fixed per-batch overhead (~1.5 ms, measured), sim-vs-real p95 median abs relative error **7.2%** (paper band: +-10-40%) | bench_simulator_fidelity |
| App. A: Algorithm 1 converges | every planner run in the suite converges (errors-resolved counts logged); infeasible SLOs raise the user-facing error | tests/test_planner.py |
| beyond paper | failure rebalance: 90.6% -> 100% completion via a ~2 ms LP re-solve; hedging cuts straggler p99 by 33%; elastic replan 2.1x faster than cold planning | bench_fault_tolerance |
"""


if __name__ == "__main__":
    main()
