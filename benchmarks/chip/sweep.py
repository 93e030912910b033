"""Knee sweep: the highest open-loop rate a configuration sustains.

  python3 benchmarks/chip/sweep.py --workload cascade-chat \\
      --seeds 7,8 --rates 2,2.5,3,3.5,4 --seconds 50 [--out sweep.json]
  python3 benchmarks/chip/sweep.py --knee sweep.json

One process builds the cell's engines and its gear once (the weights and
the calibration are the same in every run). For each seed it serves the
cell's mix at each rate in turn: a lead-in, ``--seconds`` of arrivals, then the engine is
drained with arrivals stopped. For each rate it reports the requests due
and finished, the waiting queue (stage 0, and all stages) in the first and
last third of the window, and the TTFT and inter-token tails.

The knee is the highest rate at which, on every seed, neither that rate
nor a lower one left the waiting queue undrained at the window's end
(``grows``).
The chat mixes run at 0.8 of it: ``--knee`` prints that rate from a
sweep's file (it is written into the mix's file by hand, with the sweep's
table in PERF.md).
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

SHARE_OF_KNEE = 0.8


def grows(row: dict) -> bool:
    """The waiting queue, all stages, did not drain: its mean in the
    window's last third is above one request. A queue that grew, and one
    that stood long from the window's start, both count; a burst that
    cleared before the last third does not."""
    return row["queue_last_third"] > 1.0


def knee(rows) -> float:
    """The highest swept rate at which, and below which, no seed's queue
    grew."""
    rates = sorted({r["rate_rps"] for r in rows})
    best = None
    for rate in rates:
        if any(grows(r) for r in rows if r["rate_rps"] == rate):
            break
        best = rate
    if best is None:
        raise ValueError("the queue grew at every swept rate")
    return best


def sweep_rate(cell, te, seed, rate, seconds):
    import numpy as np
    import harness
    import traffic as T
    from driver import OpenLoop
    mix = copy.deepcopy(cell.traffic)
    mix["rate_rps"] = rate
    arrivals = T.make_requests(mix, seed, seconds)
    t_start = time.perf_counter()
    win0 = t_start + mix["lead_in_s"]
    win1 = win0 + seconds
    loop = OpenLoop(te, arrivals, t_start)
    loop.run(lambda now: now >= win1)
    loop.pending.clear()
    loop.run(lambda now: loop.drained())
    win = [r for r in loop.records.values() if win0 <= r.due < win1]
    third = seconds / 3

    def thirds(q):
        first = [n for t, n in q if win0 <= t < win0 + third]
        last = [n for t, n in q if win1 - third <= t < win1]
        return (float(np.mean(first)) if first else 0.0,
                float(np.mean(last)) if last else 0.0)

    q_all = thirds([(t, sum(w)) for t, w in loop.queue_len])
    q_s0 = thirds([(t, w[0]) for t, w in loop.queue_len])
    done = [r for r in win if r.done is not None]
    ttft = [r.stamps[r.resolver][0] - r.due for r in done]
    itl = [b - a for r in done for a, b in zip(r.stamps[r.resolver],
                                               r.stamps[r.resolver][1:])]
    return {
        "seed": seed, "rate_rps": rate, "due": len(win), "done": len(done),
        "queue_first_third": q_all[0], "queue_last_third": q_all[1],
        "queue_s0_first_third": q_s0[0], "queue_s0_last_third": q_s0[1],
        "ttft_p50_s": harness.nearest_rank(ttft, 0.5) if ttft else None,
        "ttft_p95_s": harness.nearest_rank(ttft, 0.95) if ttft else None,
        "itl_p95_ms": 1e3 * harness.nearest_rank(itl, 0.95) if itl else None,
        "escalated": sum(1 in r.stamps for r in win),
        "drain_s": time.perf_counter() - win1,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seeds")
    ap.add_argument("--rates")
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--knee", default=None,
                    help="print the mix's rate from this sweep's file")
    args = ap.parse_args(argv)
    if args.knee:
        k = knee(json.loads(Path(args.knee).read_text()))
        print(round(SHARE_OF_KNEE * k, 3))
        return 0
    import jax
    import harness
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.token_engine import TokenEngine
    from spec import load_cell
    cell = load_cell(args.workload)
    harness.device_check(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfgs = harness.stage_configs(cell)
    names = [c.name for c in cfgs]
    params = harness.make_weights(cfgs, cell.config["weights_seed"])
    engines = harness.build_engines(cell, cfgs, params)
    harness.warm_up(engines, cell.traffic)
    gear, thr = harness.calibrate(cell, engines, names)
    te = TokenEngine(engines, gear, mode="fused", spec_k=1)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        print(f"sweep: {cell.name} seed {seed}, threshold {thr!r}, "
              f"{time.perf_counter() - T_PROCESS:.1f} s since start",
              flush=True)
        for rate in [float(r) for r in args.rates.split(",")]:
            rows.append(sweep_rate(cell, te, seed, rate, args.seconds))
            print("sweep:", json.dumps(rows[-1]), flush=True)
            if args.out:
                Path(args.out).parent.mkdir(parents=True, exist_ok=True)
                Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
