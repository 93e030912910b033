"""Benchmark harness: one module per paper table/figure. Emits
``bench,name,value extras`` CSV lines plus, per bench, the historical
``artifacts/<bench>.json`` row dump and a machine-readable
``artifacts/BENCH_<name>.json`` envelope (scenario, metrics, git SHA) —
the unit the perf trajectory and the CI artifact upload consume.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

BENCHES = [
    "bench_profiles",            # Fig. 1/2
    "bench_end_to_end",          # Figs. 5/6
    "bench_cost_grid",           # Fig. 7
    "bench_degradation",         # Figs. 8/9
    "bench_planner_quality",     # Fig. 10
    "bench_planner_cost",        # Fig. 11
    "bench_planner",             # fast-path planner: cold/warm plan timing
    "bench_vecsim",              # lane-batched DES vs scalar + MC certify
    "bench_ablation",            # Fig. 12
    "bench_simulator_fidelity",  # Fig. 13 (REAL tiny models)
    "bench_fidelity",            # Fig. 13 via the ExecutionBackend layer
    "bench_kernels",             # TPU-target kernels
    "bench_roofline",            # §Roofline summary from the dry-run
    "bench_fault_tolerance",     # beyond-paper FT/elasticity
    "bench_replanning",          # beyond-paper online re-planning drift
    "bench_multitenant",         # beyond-paper multi-tenant shared fleet
    "bench_tokens",              # token-level continuous batching vs rebatch
    "bench_elastic",             # elastic fleet $/M-req over a sim week
    "bench_telemetry",           # span overhead + attribution reconcile
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    t0 = time.time()
    failed = []
    for name in BENCHES:
        if args.only:
            # an exact bench name selects just that bench; anything else
            # is a substring filter (bench_planner vs bench_planner_cost)
            if args.only in BENCHES:
                if name != args.only:
                    continue
            elif args.only not in name:
                continue
        print(f"\n=== {name} ===", flush=True)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main(quick=args.quick)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    print(f"\n# all benchmarks done in {time.time() - t0:.0f}s")
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
