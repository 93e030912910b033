"""Readings behind the limits of ``correct``, on the chip.

  python3 benchmarks/chip/control.py --workload cascade-chat \\
      --seeds 101,102,... --control-seeds 4 --seconds 8 \\
      [--rate <req/s>] [--out control.json]

One process serves the cell at its own size and load on each seed in turn
(the engines are built and warmed once; the weights and the gear are
the same in every seed, which only orders the mix's requests), with a
short window, and compares what was served with the plain reference of
each stage model's family as every run does: these are the program's
readings, whose largest over a dozen seeds is a limit's lower reading. On
the first ``--control-seeds`` seeds the same sample is also read with the
reference computed in int8 and in fp8 in the program's place (the precision
control), whose smallest reading is a limit's upper one; each control is
also held to the cell's limits in the program's place, and has to come out
not correct. ``--rate`` serves another rate than the mix's file states
(the rate a fresh knee sweep gives, before it is written there). PERF.md
gives the readings and the limits set between them; the benchmark's own
runs never run the control.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

CONTROLS = ("int8", "fp8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import dataclasses
    import jax
    import harness
    from compilelog import CompileLog
    from repro.launch.compile_cache import enable_compile_cache
    from spec import load_cell
    cell = load_cell(args.workload)
    if args.rate is not None:
        cell = dataclasses.replace(
            cell, traffic=dict(cell.traffic, rate_rps=args.rate))
    harness.device_check(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log, engines, rows = CompileLog(), None, []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run_cell(
            cell, seed, args.seconds, log=log, engines=engines,
            keep_engines=True,
            controls=CONTROLS if i < args.control_seeds else (),
            say=lambda s: print(s, flush=True))
        engines = out.pop("engines")
        row = {"seed": seed, "rate_rps": cell.traffic["rate_rps"],
               "correct": out["correct"],
               "control_correct": out["control_correct"],
               "readings": out["readings"],
               "compiles_in_window": out["compiles_in_window"],
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print("control:", json.dumps(row), flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
