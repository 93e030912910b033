"""The program's own phase spans in a run of a cell, beside the device
trace.

``TokenEngine(telemetry=...)`` times each token boundary's phases
(``engine.admit``, ``engine.decode`` and their children, on the host
clock) and annotates each for the profiler with its number ``n``, so a
trace holds them on the device's clock (``tracing.load``'s ``program``).
This module reads them:

* ``reduce_program(events)``: with the device's operations and the
  driver's host phases, each idle gap of the device given to the
  innermost phase that overlaps it most, counting a phase's own time apart
  from its children's (to the driver's phase where no phase overlaps it),
  the phases the trace holds whole, and each device's idle intervals
  (``tracing.reduce`` adds these keys to its summary);
* ``host_gap_ms``, ``prefill_pad_share``,
  ``escalation_wait_ms``: the numbers the per-layer metrics of the stage
  engine and the cascade read (``metrics/``).

As a command it serves a cell as ``run.py`` does, with the engine's
telemetry on, and prints one JSON line: the end-to-end metrics, the
in-program numbers above, the longest phase of each kind, and what one
phase costs the host. ``--trace 1`` also profiles the sub-window that
``run.py --trace 1`` profiles, reduces it, and times each fused decode
call's executable against its dispatch and fetch; ``--slice`` writes a
slice of that trace (at most 50 ms) with the phases' in-memory records
beside it (the recorded data of ``tests/test_spans.py``).
``--telemetry 0`` serves with telemetry off, the same run otherwise, for
the cost of telemetry when on.

  python3 benchmarks/chip/spans.py --workload cascade-chat --seed <n> \\
      --seconds 50 --trace <0|1> [--telemetry 0] [--slice <file>]

The comparison with the reference is ``run.py``'s and is not made here.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from collections import Counter, defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Iterable, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import tracing  # noqa: E402


def _end(e: dict) -> float:
    return e["start_ns"] + e["dur_ns"]


def _window(events: dict):
    host = events["host"]
    return (min(h["start_ns"] for h in host), max(_end(h) for h in host))


def idle_gaps(events: dict) -> Dict[str, list]:
    """Each device's idle intervals (ns) inside the traced window, as
    ``tracing.reduce`` counts them."""
    lo, hi = _window(events)
    ops = tracing.op_events(events["device"])
    out = {}
    for d in sorted({e["plane"] for e in ops}) or ["none"]:
        busy = tracing.union(tracing.clip(
            [(e["start_ns"], _end(e)) for e in ops if e["plane"] == d],
            lo, hi))
        out[d] = tracing.gaps(busy, lo, hi)
    return out


def program_calls(events: dict) -> List[dict]:
    """The program's phases that lie whole inside the traced window."""
    lo, hi = _window(events)
    return [e for e in events["program"]
            if e["start_ns"] >= lo and _end(e) <= hi]


def innermost(spans: List[dict], a: float, b: float) -> Optional[str]:
    """Of the nested ``spans``, the one whose own time (less the phases
    nested in it) overlaps [a, b) most; on a tie the inner one."""
    over = [(e, min(b, _end(e)) - max(a, e["start_ns"])) for e in spans]
    over = [(e, ov) for e, ov in over if ov > 0]
    own = {id(e): ov for e, ov in over}
    for c, ov in over:
        outer = [e for e, _ in over if e is not c
                 and e["start_ns"] <= c["start_ns"] and _end(c) <= _end(e)]
        if outer:
            own[id(max(outer, key=lambda e: (e["start_ns"],
                                             -e["dur_ns"])))] -= ov
    best = max(over, key=lambda eo: (own[id(eo[0])], eo[0]["start_ns"],
                                     -eo[0]["dur_ns"]), default=None)
    return None if best is None else best[0]["name"]


def reduce_program(events: dict) -> dict:
    """``idle_by_program`` (s, per device): each idle gap to the innermost
    program phase that overlaps it most (``innermost``), else to the
    driver's host phase that does (``tracing.attribute``);
    ``program_calls``: the phases that lie whole inside the traced
    window; ``idle_gaps``: each device's idle intervals (ns)."""
    program = sorted(events["program"], key=lambda e: e["start_ns"])
    starts = [e["start_ns"] for e in program]
    longest_ns = max((e["dur_ns"] for e in program), default=0)
    by_device = idle_gaps(events)
    idle: Dict[str, float] = defaultdict(float)
    for gaps in by_device.values():
        for a, b in gaps:
            name = innermost(program[
                bisect.bisect_left(starts, a - longest_ns):
                bisect.bisect_left(starts, b)], a, b)
            if name is not None:
                idle[name] += b - a
            else:
                for k, v in tracing.attribute([(a, b)],
                                              events["host"]).items():
                    idle[k] += v
    n_dev = len(by_device)
    return {"idle_by_program": {k: v / n_dev / 1e9 for k, v in idle.items()},
            "program_calls": program_calls(events),
            "idle_gaps": by_device}


def _overlap(intervals: Iterable, a: float, b: float) -> float:
    return sum(max(0.0, min(y, b) - max(x, a)) for x, y in intervals)


def host_gap_ms(summary: dict, name: str) -> Optional[float]:
    """Mean device idle time (ms, per device) inside one ``name`` phase
    (its children included), over the phases the trace holds whole, from
    a reduced trace (``tracing.reduce``'s ``program_calls`` and
    ``idle_gaps``)."""
    calls = [e for e in summary["program_calls"] if e["name"] == name]
    if not calls:
        return None
    by_device = summary["idle_gaps"]
    idle = sum(_overlap(g, e["start_ns"], _end(e))
               for g in by_device.values() for e in calls)
    return idle / len(by_device) / len(calls) / 1e6


def prefill_pad_share(phases: Iterable, win0: float, win1: float
                      ) -> Optional[float]:
    """1 - real prompt tokens / padded tokens, over the ``slot.prefill``
    phases that end inside [win0, win1)."""
    pre = [p for p in phases
           if p.name == "slot.prefill" and win0 <= p.t1 < win1]
    padded = sum(p.counts["padded"] for p in pre)
    if not padded:
        return None
    return 1.0 - sum(p.counts["tokens"] for p in pre) / padded


def escalation_wait_ms(spans: dict, rids: Iterable[int]) -> List[float]:
    """For each escalation of the requests ``rids`` (folded
    ``Telemetry.spans``): ms from ``escalate`` to the ``fire`` of the next
    stage's admit that takes the request."""
    out = []
    for rid in rids:
        sp = spans.get(rid)
        if sp is None:
            continue
        evs = sp.events
        for i, (kind, t, stage) in enumerate(evs):
            if kind == "escalate":
                fire = next((f for f in evs[i + 1:]
                             if f[0] == "fire" and f[2] == stage + 1), None)
                if fire is not None:
                    out.append(1e3 * (fire[1] - t))
    return out


def longest(phases: Iterable, origin: float) -> Dict[str, dict]:
    """The longest phase of each name, with where and when (s after
    ``origin``) it ran."""
    out: Dict[str, dict] = {}
    for p in phases:
        d = 1e3 * (p.t1 - p.t0)
        if p.name not in out or d > out[p.name]["ms"]:
            out[p.name] = {"ms": d, "stage": p.stage,
                           "boundary": p.boundary, "at_s": p.t0 - origin}
    return out


def decode_alignment(events: dict) -> Dict[str, dict]:
    """For the fused decode calls the trace holds whole, by stage (ms,
    min / median / max): the start of the call's executable on the device
    less the start of ``slot.dispatch`` on the host (a negative least
    bounds how far the device clock runs ahead of the host's), and the
    end of ``slot.fetch`` less the executable's end (how long the host
    waits after the device is done)."""
    by_n = {e["n"]: e for e in events["program"]}
    mods = sorted((e for e in events["device"]
                   if e["line"] == tracing.MODULES_LINE
                   and "fused_decode" in e["name"]),
                  key=lambda e: e["start_ns"])
    if not mods:
        return {}
    starts = [m["start_ns"] for m in mods]
    lag, tail = defaultdict(list), defaultdict(list)
    for e in program_calls(events):
        if e["name"] != "engine.decode":
            continue
        # an engine.decode's first two children: dispatch, then fetch
        dispatch, fetch = by_n[e["n"] + 1], by_n[e["n"] + 2]
        t = _end(dispatch)
        i = bisect.bisect_left(starts, t)
        m = min(mods[max(0, i - 1):i + 1],
                key=lambda m: abs(m["start_ns"] - t))
        lag[e["stage"]].append((m["start_ns"] - dispatch["start_ns"]) / 1e6)
        tail[e["stage"]].append((_end(fetch) - _end(m)) / 1e6)
    spread = lambda v: [min(v), statistics.median(v), max(v)]  # noqa: E731
    return {f"s{k}": {"start_lag_ms": spread(lag[k]),
                      "fetch_tail_ms": spread(tail[k])} for k in lag}


def phase_cost_us(n: int = 20000) -> float:
    """Host microseconds one phase costs (enter and exit, profiler off)."""
    from repro.core.telemetry import Telemetry
    telem = Telemetry()
    t0 = time.perf_counter()
    for _ in range(n):
        with telem.phase("cost", time.perf_counter, 0, 0):
            pass
    return 1e6 * (time.perf_counter() - t0) / n


def cut_slice(events: dict, phases: list, slice_ns: float = 50e6) -> dict:
    """A slice of the trace: the driver's host phases that lie whole in
    ``slice_ns`` from the one that holds an admit of median length (the
    middle host phase if there is none), the program phases inside them,
    the device events that overlap them, the in-memory records of the
    same phases, and what the reductions read from it."""
    host = sorted(events["host"], key=lambda e: e["start_ns"])
    admits = sorted((e for e in events["program"]
                     if e["name"] == "engine.admit"),
                    key=lambda e: e["dur_ns"])
    at = admits[len(admits) // 2]["start_ns"] if admits \
        else host[len(host) // 2]["start_ns"]
    a0 = max(h["start_ns"] for h in host if h["start_ns"] <= at)
    cut = {"host": [h for h in host if a0 <= h["start_ns"]
                    and _end(h) <= a0 + slice_ns]}
    lo, hi = _window(cut)
    cut["program"] = [e for e in events["program"]
                      if lo <= e["start_ns"] and _end(e) <= hi]
    cut["device"] = [e for e in events["device"]
                     if e["start_ns"] < hi and _end(e) > lo]
    ns = {e["n"] for e in cut["program"]}
    summary = tracing.reduce(cut)
    prog = reduce_program(cut)
    return {"events": cut,
            "records": [p.to_dict() for p in phases if p.n in ns],
            "expect": {"busy_s": summary["busy_s"],
                       "window_s": summary["window_s"],
                       "idle_by_host": summary["idle_by_host"],
                       "idle_by_program": prog["idle_by_program"]}}


# ------------------------------------------------------------------ run

def serve(cell, seed: int, seconds: float, trace: bool, telemetry: bool,
          say, slice_path: Optional[str] = None, model_configs=None) -> dict:
    """One run of ``cell``; returns the command's JSON line as a dict."""
    import shutil

    import jax

    import harness
    import traffic as T
    from driver import OpenLoop
    from repro.core.telemetry import Telemetry
    from repro.serving.token_engine import TokenEngine
    mix = cell.traffic
    cfgs = harness.stage_configs(cell, model_configs)
    names = [c.name for c in cfgs]
    params = harness.make_weights(cfgs, cell.config["weights_seed"])
    engines = harness.build_engines(cell, cfgs, params)
    harness.warm_up(engines, mix)
    gear, thr = harness.calibrate(cell, engines, names)
    telem = Telemetry() if telemetry else None
    te = TokenEngine(engines, gear, mode="fused", spec_k=1, telemetry=telem)
    arrivals = T.make_requests(mix, seed, seconds)
    say(f"spans: {cell.name} seed {seed}: {len(arrivals)} requests, "
        f"threshold {thr!r}, telemetry {telemetry}, trace {trace}")
    t_start = time.perf_counter()
    win0 = t_start + mix["lead_in_s"]
    win1 = win0 + seconds
    loop = OpenLoop(te, arrivals, t_start)
    tracer = None
    if trace:
        tracer = harness.Tracer(str(cell.root / ".bench_trace" / "spans"),
                                win0 + min(5.0, 0.25 * seconds),
                                min(3.0, 0.3 * seconds))
        loop.hooks += tracer.hooks()
    try:
        loop.run(lambda now: now >= win1)
        window = [r for r in loop.records.values() if win0 <= r.due < win1]
        if mix["drain_s"] > 0:
            loop.run(lambda now: now >= win1 + mix["drain_s"] or all(
                r.done is not None for r in window))
    finally:
        if tracer is not None:
            tracer.end()
    t_end = time.perf_counter()
    records = sorted(loop.records.values(), key=lambda r: r.arrival.rid)
    unread = [None] * len(names)     # no count is read here
    run = harness.RunData(cell, win0, win1, seconds, records, loop.calls,
                          names, cell.config["n_slots"], unread, unread, {},
                          t_end=t_end)
    out = {"device": jax.devices()[0].device_kind,
           "failed": sum(r.done is None for r in window),
           "end_to_end": harness.end_to_end(run, win0 - T_PROCESS)}
    if telem is None:
        return out
    phases = telem.phases
    telem.finalize()
    waits = sorted(escalation_wait_ms(telem.spans,
                                      [r.arrival.rid for r in window]))
    out.update({
        "escalation_wait_p50_ms": statistics.median(waits) if waits
        else None,
        "escalation_wait_p95_max_ms": [harness.nearest_rank(waits, 0.95),
                                       waits[-1]] if waits else None,
        "escalations": len(waits),
        "prefill_pad_share": prefill_pad_share(phases, win0, win1),
        "phases": len(phases),
        "longest_ms": longest((p for p in phases if win0 <= p.t1 < win1),
                              win0),
        "phase_cost_us": phase_cost_us(),
    })
    if tracer is not None:
        events = tracing.load(tracer.path)
        shutil.rmtree(tracer.path, ignore_errors=True)
        summary = tracing.reduce(events)
        out.update({
            "join_host_gap_ms": host_gap_ms(summary, "engine.admit"),
            "decode_host_gap_ms": host_gap_ms(summary, "engine.decode"),
            "busy_s": summary["busy_s"], "window_s": summary["window_s"],
            "idle_by_host": summary["idle_by_host"],
            "idle_by_program": summary["idle_by_program"],
            "program_calls": dict(Counter(
                e["name"] for e in summary["program_calls"])),
            "profiled_from_s": tracer.t0 - win0,
            "decode_alignment": decode_alignment(events),
        })
        if slice_path:
            Path(slice_path).parent.mkdir(parents=True, exist_ok=True)
            Path(slice_path).write_text(json.dumps(
                cut_slice(events, phases)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--telemetry", type=int, choices=(0, 1), default=1)
    ap.add_argument("--slice", default=None,
                    help="write a slice of the trace with the phases' "
                         "records to this JSON file (with --trace 1)")
    args = ap.parse_args(argv)

    import jax

    import harness
    from repro.launch.compile_cache import enable_compile_cache
    from spec import load_cell
    say = lambda text: print(text, file=sys.stderr, flush=True)  # noqa
    cell = load_cell(args.workload)
    try:
        harness.device_check(cell.chips)
    except harness.NoChip as e:
        say(f"spans: {e}; nothing ran")
        return 3
    say(f"spans: compile cache at {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = serve(cell, args.seed, args.seconds, bool(args.trace),
                bool(args.telemetry), say, args.slice)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
