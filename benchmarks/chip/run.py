"""The chip benchmark's command.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json`` on the machine it starts on:
the configuration's weights, warm-up of every shape the cell's traffic
reaches,
the gear's calibration, a lead-in, ``--seconds`` of measured open-loop
serving, the drain, and the comparison with the plain float32 reference.
The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, then ``checks``: each number compared beside its limit).
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled sub-window. Without a TPU, or with fewer
chips than the cell needs, it exits non-zero and prints no result.
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


def say(text: str) -> None:
    print(text, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dump", default=None,
                    help="write a look at the trace's busiest operations "
                         "to this JSON file")
    args = ap.parse_args(argv)

    import jax
    import harness
    from spec import load_cell
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        say(f"bench: the program under test is missing ({e})")
        return 2
    cell = load_cell(args.workload)
    try:
        dev = harness.device_check(cell.chips)
        harness.peak_of(dev.device_kind)
    except (harness.NoChip, KeyError) as e:
        say(f"bench: {e}; nothing ran")
        return 3
    say(f"bench: compile cache at {enable_compile_cache()}")
    # every compile, however short, goes to the persistent cache: the
    # join path's small row updates are warmed up like the executables
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_process=T_PROCESS, say=say,
                           trace_dump=args.trace_dump)
    say(f"bench: readings {json.dumps(out['readings'])}")
    print(json.dumps(harness.result_line(out)), flush=True)
    for name, c in out["checks"].items():
        say(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
