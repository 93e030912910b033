"""From a profiler trace to the numbers the per-layer metrics read.

``load(trace_dir)`` flattens the ``.xplane.pb`` the JAX profiler wrote into
plain event lists: device operations (with the executable they ran in),
the open-loop driver's host phases (its ``TraceAnnotation`` names) and the
program's own phases (``TokenEngine``'s telemetry, each with its number
``n``, stage and boundary). ``reduce`` works on those lists alone, so it
is checked on small recorded traces (``tests/data``):

* busy: the union of the intervals in which an operation ran on the device,
  within the traced window (the span of the open-loop driver's host phases);
* time by executable: the sum of its events' durations; by operation:
  self time (less the operations nested inside it);
* idle gaps: the complement of busy within the window, each attributed to
  the host phase that overlaps it most;
* the driver's engine calls (``admit.sN``, ``decode.sN``, each tagged with
  its boundary), whose spans the trace holds whole: the device's events
  that start inside such a span belong to that call, since every call
  waits for its outputs before it returns;
* where the events hold the program's phases, ``spans.reduce_program``'s
  keys: the idle gaps by program phase, the phases held whole, and each
  device's idle intervals.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_PHASES = ("arrivals", "wait_arrival", "admit.s0", "admit.s1",
               "decode.s0", "decode.s1", "bookkeeping")
PROGRAM_SPANS = ("engine.admit", "engine.decode", "engine.decide",
                 "slot.prefill", "slot.join", "slot.fetch", "slot.dispatch")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(trace_dir: str) -> dict:
    """{"device": [...], "host": [...], "program": [...]} from the newest
    trace in the dir."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device, host, program = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                for ev in line.events:
                    device.append({
                        "plane": plane.name, "line": line.name,
                        "name": ev.name, "start_ns": ev.start_ns,
                        "dur_ns": ev.duration_ns,
                        "module": str(_stat(ev, "hlo_module") or ""),
                        "long": str(_stat(ev, "long_name") or "")})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_PHASES:
                        host.append({"name": ev.name,
                                     "start_ns": ev.start_ns,
                                     "dur_ns": ev.duration_ns,
                                     "boundary": _stat(ev, "b")})
                    elif ev.name in PROGRAM_SPANS:
                        program.append({
                            "name": ev.name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns,
                            "n": int(_stat(ev, "n")),
                            "stage": int(_stat(ev, "s")),
                            "boundary": int(_stat(ev, "b"))})
    return {"device": device, "host": host,
            "program": sorted(program, key=lambda e: e["n"])}


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float):
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(idle, host) -> Dict[str, float]:
    """Idle nanoseconds by the host phase that overlaps each gap most."""
    spans = sorted((h["start_ns"], h["start_ns"] + h["dur_ns"], h["name"])
                   for h in host)
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in idle:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        best, name, k = 0.0, "host_unannotated", j
        while k < len(spans) and spans[k][0] < b:
            ov = min(b, spans[k][1]) - max(a, spans[k][0])
            if ov > best:
                best, name = ov, spans[k][2]
            k += 1
        out[name] += b - a
    return dict(out)


def op_events(device: List[dict]) -> List[dict]:
    """Operations that ran on the device: the ops line where the trace has
    one, else every line but the executables' and steps'."""
    lines = {e["line"] for e in device}
    if OPS_LINE in lines:
        return [e for e in device if e["line"] == OPS_LINE]
    return [e for e in device if e["line"] not in (MODULES_LINE, "Steps")]


def _fill_modules(ops: List[dict], device: List[dict]) -> None:
    """Give each operation without an ``hlo_module`` stat the executable
    whose event on the same device encloses it."""
    import bisect
    mods: Dict[str, list] = defaultdict(list)
    for e in device:
        if e["line"] == MODULES_LINE:
            mods[e["plane"]].append((e["start_ns"],
                                     e["start_ns"] + e["dur_ns"], e["name"]))
    for v in mods.values():
        v.sort()
    starts = {k: [m[0] for m in v] for k, v in mods.items()}
    for e in ops:
        if e["module"] or e["plane"] not in mods:
            continue
        i = bisect.bisect_right(starts[e["plane"]], e["start_ns"]) - 1
        if i >= 0:
            a, b, name = mods[e["plane"]][i]
            if e["start_ns"] < b:
                e["module"] = name


def short(name: str) -> str:
    """``%copy.7 = bf16[24,16]{...} copy(...)`` -> ``%copy.7 = bf16[24,16]``:
    the operation and its result's shape."""
    return name.split("{")[0].split("(")[0].strip()


def self_times(ops: List[dict], lo: float, hi: float) -> Dict[str, float]:
    """Device seconds by operation, within the window, each less the
    operations nested inside it on the same line (a loop and its body are
    not counted twice)."""
    out: Dict[str, float] = defaultdict(float)
    by_line: Dict[tuple, list] = defaultdict(list)
    for e in ops:
        a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
        if b > a:
            by_line[(e["plane"], e["line"])].append((a, -b, e))
    for evs in by_line.values():
        evs.sort(key=lambda t: (t[0], t[1]))
        stack: list = []            # [end, key, child time]
        for a, nb, e in evs:
            b = -nb
            while stack and stack[-1][0] <= a:
                end, key, child, start = stack.pop()
                out[key] += (end - start - child) / 1e9
            if stack:
                stack[-1][2] += b - a
            key = f"{module_of(e['module'])}/{short(e['name'])}"
            stack.append([b, key, 0.0, a])
        while stack:
            end, key, child, start = stack.pop()
            out[key] += (end - start - child) / 1e9
    return dict(out)


def module_of(name: str) -> str:
    """``jit_fused_decode(123)`` -> ``jit_fused_decode``."""
    return name.split("(")[0]


def reduce(events: dict) -> dict:
    host = events["host"]
    if not host:
        raise ValueError(
            "the trace holds no host phase of the open-loop driver")
    lo = min(h["start_ns"] for h in host)
    hi = max(h["start_ns"] + h["dur_ns"] for h in host)
    ops = op_events(events["device"])
    _fill_modules(ops, events["device"])
    devices = sorted({e["plane"] for e in ops}) or ["none"]
    busy_ns, idle_by = 0.0, defaultdict(float)
    for d in devices:
        busy = union(clip([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                           for e in ops if e["plane"] == d], lo, hi))
        busy_ns += sum(b - a for a, b in busy)
        for k, v in attribute(gaps(busy, lo, hi), host).items():
            idle_by[k] += v
    n_dev = len(devices)
    by_op = self_times(ops, lo, hi)
    by_module, n_module = defaultdict(float), defaultdict(int)
    modules = []
    for e in events["device"]:
        if e["line"] == MODULES_LINE:
            a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
            modules.append({"name": module_of(e["name"]), "start_ns": a,
                            "dur_ns": e["dur_ns"]})
            if min(b, hi) > max(a, lo):
                by_module[module_of(e["name"])] += (min(b, hi)
                                                    - max(a, lo)) / 1e9
                n_module[module_of(e["name"])] += 1
    out = {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "by_op": dict(by_op),
        "by_module": dict(by_module),
        "calls_by_module": dict(n_module),
        "idle_by_host": {k: v / n_dev / 1e9 for k, v in idle_by.items()},
        "ops": ops,
        "modules": modules,
        "host_calls": [h for h in host if h.get("boundary") is not None],
    }
    if "program" in events:
        from spans import reduce_program
        out.update(reduce_program(events))
    return out


def _inside(e: dict, span) -> bool:
    return span is None or span[0] <= e["start_ns"] < span[1]


def kernel_seconds(summary: dict, needles: Sequence[str], span=None
                   ) -> Tuple[float, int]:
    """Device seconds and events of the operations whose name or long name
    holds one of ``needles`` (that start inside ``span``, in ns)."""
    evs = [e for e in summary["ops"] if _inside(e, span)
           and any(n in e["name"] or n in e["long"] for n in needles)]
    return sum(e["dur_ns"] for e in evs) / 1e9, len(evs)


def module_seconds(summary: dict, needle: str, span) -> float:
    """Device seconds of the executables whose name holds ``needle`` and
    that start inside ``span`` (ns)."""
    return sum(e["dur_ns"] for e in summary["modules"]
               if needle in e["name"] and _inside(e, span)) / 1e9


def breakdown(summary: dict, n: int = 10) -> dict:
    """The operations with the most device time (self time, by executable
    and operation) and the host phases behind the most idle time."""
    top = sorted(summary["by_op"].items(), key=lambda kv: -kv[1])[:n]
    idle = sorted(summary["idle_by_host"].items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle]}
