"""One run of one cell: the configuration's weights, warm-up, the gear's
calibration, a lead-in, the measured window, the drain, the comparison
with the plain reference, and the result line's contents.

``run.py`` is the command; ``run_cell`` is the same run as a function, so
that tests and the limit-reading script drive it without the command line.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import tracing
import traffic as T
from compilelog import CompileLog
from driver import Call, OpenLoop, Record
from reference.compare import compare
from spec import Cell
from weights import make_params

MIN_COMPARED_TOKENS = 400      # per stage model, the sample's least
MIN_COMPARED_REQUESTS = 3
MAX_COMPARED_REQUESTS = 8


class NoChip(RuntimeError):
    pass


def device_check(chips: int):
    """The first device, if JAX has at least ``chips`` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has {len(devs)}")
    return devs[0]


# ----------------------------------------------------------------- models

def stage_configs(cell: Cell, model_configs=None):
    from repro.configs import get_config
    cfgs = list(model_configs or [get_config(n)
                                  for n in cell.config["stages"]])
    # the shapes the program serves have to be the file's, as the model's
    # family compares them
    for cfg in cfgs:
        family = cell.family(cfg.name)
        served = family.served(cfg)
        stated = family.stated(family.arch(cell.config["models"][cfg.name]))
        if served != stated:
            raise ValueError(f"{cfg.name}: the program serves {served}, "
                             f"the configuration file states {stated}")
    return cfgs


def make_weights(cfgs, seed: int) -> list:
    from repro.models import model as M
    return [make_params(M.init_params(cfg, spec_only=True), seed, si)
            for si, cfg in enumerate(cfgs)]


def build_engines(cell: Cell, cfgs, params) -> list:
    from repro.serving.token_engine import SlotEngine
    c = cell.config
    return [SlotEngine(cfg.name, p, cfg, n_slots=c["n_slots"],
                       max_len=c["max_len"],
                       min_len_bucket=c["min_len_bucket"])
            for cfg, p in zip(cfgs, params)]


def _join_and_leave(eng, n: int, length: int) -> None:
    slots, _, _ = eng.prefill_batch([np.ones(length, np.int32)] * n)
    for s in slots:
        eng.release(s)


def warm_up(engines, mix: dict) -> None:
    """Every shape the mix can reach: each (batch bucket, length bucket)
    prefill of the mix's prompt range, the join path's row updates for
    every number of joiners, and the fused decode step."""
    lo, hi = int(mix["prompt"]["lo"]), int(mix["prompt"]["hi"])
    for eng in engines:
        for lb in sorted({eng._len_bucket(n) for n in range(lo, hi + 1)}):
            for bb in eng.batch_buckets:
                _join_and_leave(eng, bb, lb)
        for n in range(1, eng.n_slots + 1):
            if n not in eng.batch_buckets:
                _join_and_leave(eng, n, lo)
        slots, _, _ = eng.prefill_batch([np.ones(lo, np.int32)])
        eng.decode_fused()
        eng.release(slots[0])


# ------------------------------------------------------------------ gear

def cascade_gear(names, thresholds):
    from repro.core.cascade import Cascade
    from repro.core.gears import Gear
    return Gear(cascade=Cascade(tuple(names), tuple(thresholds)),
                min_queue_lens={m: 1 for m in names},
                load_fractions={m: {i: 1.0} for i, m in enumerate(names)})


def choose_threshold(gap_streams, gear_for, batcher, target: float) -> float:
    """The stage-0 threshold under which the share ``target`` of the
    streams escalates, replaying the engine's own boundary rule."""
    from repro.core.certainty import StreamingCertainty
    from repro.core.scheduling import CascadeHop

    def n_escalated(thr):
        gear, n = gear_for(thr), 0
        for gaps in gap_streams:
            cert = StreamingCertainty(mode="ewma", beta=0.35)
            cert.update(gaps[0])
            _, hop = batcher.stream_trace_hop(0, cert, gaps[1:], 1,
                                              len(gaps), gear)
            n += isinstance(hop, CascadeHop)
        return n

    finals = []
    for gaps in gap_streams:
        cert = StreamingCertainty(mode="ewma", beta=0.35)
        for g in gaps:
            cert.update(g)
        finals.append(cert.value)
    want = target * len(gap_streams)
    return min(sorted(set(finals)), key=lambda t: abs(n_escalated(t) - want))


def calibrate(cell: Cell, engines, names):
    """The gear: for a cascade, a stage-0 probe on requests of the cell's
    own mix fixes the threshold that escalates ``escalation_target`` of
    them."""
    from repro.core.scheduling import ContinuousBatcher, SchedulerCore
    from repro.serving.token_engine import TokenEngine, TokenRequest
    if len(names) == 1:
        return cascade_gear(names, ()), None
    probe = T.probe_requests(cell.traffic, engines[0].n_slots)
    alone = TokenEngine([engines[0]], cascade_gear(names[:1], ()),
                        mode="fused", spec_k=1)
    out = alone.serve([TokenRequest(a.rid, a.prompt, a.max_new)
                       for a in probe])
    thr = choose_threshold(
        [out[a.rid].gaps for a in probe],
        lambda t: cascade_gear(names, (t,)),
        ContinuousBatcher(SchedulerCore([]), engines[0].n_slots),
        cell.config["escalation_target"])
    return cascade_gear(names, (thr,)), thr


# ------------------------------------------------------------------- run

@dataclass
class RunData:
    """What the per-layer readers read."""
    cell: Cell
    win0: float
    win1: float
    seconds: float
    records: List[Record]
    calls: List[Call]
    stages: List[str]
    n_slots: int
    archs: list                  # each stage model's, from its family
    families: list               # each stage model's family module
    peak: dict
    trace: Optional[dict] = None
    t_end: float = 0.0           # the drain's end
    telemetry: Optional[object] = None   # the engine's, in a traced run

    def window_records(self) -> List[Record]:
        return [r for r in self.records if self.win0 <= r.due < self.win1]

    def window_calls(self, kind: str) -> List[Call]:
        return [c for c in self.calls
                if c.kind == kind and self.win0 <= c.t1 < self.win1]

    def overlap(self, c: Call) -> float:
        return max(0.0, min(c.t1, self.win1) - max(c.t0, self.win0))

    def traced_calls(self, kind: Optional[str] = None
                     ) -> List[Tuple[Call, Tuple[float, float]]]:
        """The calls whose span the trace holds whole, each with that span
        on the trace's clock (ns): the device events that start inside it
        ran for that call."""
        if not self.trace:
            return []
        by_key = {(c.kind, c.stage, c.boundary): c for c in self.calls}
        out = []
        for h in self.trace["host_calls"]:
            name, stage = h["name"].split(".s")
            c = by_key.get((name, int(stage), int(h["boundary"])))
            if c is not None and kind in (None, c.kind):
                out.append((c, (h["start_ns"], h["start_ns"] + h["dur_ns"])))
        return out


class Tracer:
    """Starts the profiler at ``start`` and stops it ``length`` later."""

    def __init__(self, path: str, start: float, length: float):
        self.path, self.start, self.length = path, start, length
        self.t0 = self.t1 = None

    def hooks(self):
        return [(self.start, self.begin),
                (self.start + self.length, self.end)]

    def begin(self):
        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        jax.profiler.start_trace(self.path)
        self.t0 = time.perf_counter()

    def end(self):
        import jax
        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()


def nearest_rank(values: Sequence[float], q: float) -> float:
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def end_to_end(run: RunData, setup_s: float) -> Dict[str, float]:
    win = run.window_records()
    out = {}
    for m in run.cell.end_to_end:
        name = m["name"]
        if name == "setup_s":
            v = setup_s
        elif name == "ttft_p95_s":
            # a request not done by the drain's end counts with the least
            # TTFT it can have: the drain's end (and in ``failed``)
            v = nearest_rank([r.stamps[r.resolver][0] - r.due
                              if r.done is not None else run.t_end - r.due
                              for r in win], 0.95)
        elif name == "itl_p95_ms":
            v = 1e3 * nearest_rank(
                [b - a for r in win if r.done is not None
                 for a, b in zip(r.stamps[r.resolver],
                                 r.stamps[r.resolver][1:])], 0.95)
        else:
            raise KeyError(f"no definition of end-to-end metric {name!r}")
        out[name] = v
    return out


def work_summary(loop: OpenLoop, window: List[Record], win0: float,
                 win1: float) -> dict:
    """What the window's requests asked of the cascade, for the log: the
    ones that escalated (by key, the same in every seed), the TTFT median,
    and the waiting queue (all stages) in the window's first and last
    third."""
    third = (win1 - win0) / 3
    first = [sum(w) for t, w in loop.queue_len if t < win0 + third
             and t >= win0]
    last = [sum(w) for t, w in loop.queue_len if t >= win1 - third
            and t < win1]
    ttft = [r.stamps[r.resolver][0] - r.due for r in window
            if r.done is not None]
    return {
        "escalated": sorted(r.arrival.key for r in window
                            if 1 in r.stamps),
        "ttft_p50_s": nearest_rank(ttft, 0.5) if ttft else None,
        "queue_first_third": float(np.mean(first)) if first else 0.0,
        "queue_last_third": float(np.mean(last)) if last else 0.0,
    }


def limits_checks(cell: Cell, readings: dict, records: List[Record],
                  control: Optional[str] = None):
    """Each number compared, beside its limit. With ``control``, the
    control's readings of the same sample stand in the program's place
    (``control.py`` reads them; a control has to come out not correct)."""
    checks = {}
    lim = cell.config["limits"]
    pre = f"{control}." if control else ""
    for stage, r in readings.items():
        for key in ("logit_shortfall", "gap_error"):
            checks[f"{stage}.{key}"] = (r[pre + key], lim[stage][key])
    done = [r for r in records if r.done is not None]
    checks["short_outputs"] = (
        sum(len(r.tokens) != r.arrival.max_new for r in done), 0)
    checks["requests_not_compared"] = (int(not readings), 0)
    return checks


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool = False, *,
             t_process: Optional[float] = None, model_configs=None,
             log: Optional[CompileLog] = None, engines=None,
             keep_engines: bool = False, controls: Sequence[str] = (),
             fault: Optional[Callable] = None, say=print,
             trace_dump: Optional[str] = None,
             peak: Optional[dict] = None) -> dict:
    """One run; returns the result line's keys plus ``checks``,
    ``readings``, ``compiles_in_window`` and ``engines`` (when kept)."""
    import jax
    from repro.serving.token_engine import TokenEngine
    t_process = time.perf_counter() if t_process is None else t_process
    log = log or CompileLog()
    mix = cell.traffic
    cfgs = stage_configs(cell, model_configs)
    names = [c.name for c in cfgs]
    # the weights are the configuration's, the same in every run: with
    # the requests, they decide which requests escalate (traffic.py)
    params = make_weights(cfgs, cell.config["weights_seed"])
    if engines is None:
        engines = build_engines(cell, cfgs, params)
        warm_up(engines, mix)
    else:
        for eng, p in zip(engines, params):
            eng.params = p
    gear, thr = calibrate(cell, engines, names)
    # the program's phases and request events, read by per-layer metrics;
    # the end-to-end runs serve without them
    telem = None
    if trace:
        from repro.core.telemetry import Telemetry
        telem = Telemetry()
    te = TokenEngine(engines, gear, mode="fused", spec_k=1, telemetry=telem)
    if fault is not None:
        fault(te)
    arrivals = T.make_requests(mix, seed, seconds)
    say(f"bench: {cell.name} seed {seed}: {len(arrivals)} requests, "
        f"threshold {thr!r}, {log.compiles()} compiles and "
        f"{log.cache_hits} cache hits in set-up")

    t_start = time.perf_counter()
    win0 = t_start + mix["lead_in_s"]
    win1 = win0 + seconds
    loop = OpenLoop(te, arrivals, t_start)
    marks = {}
    loop.hooks.append((win0, lambda: marks.update(
        compiles=log.compiles(), hits=log.cache_hits)))
    tracer = None
    if trace:
        path = str(cell.root / ".bench_trace" / cell.name)
        tracer = Tracer(path, win0 + min(5.0, 0.25 * seconds),
                        min(3.0, 0.3 * seconds))
        loop.hooks += tracer.hooks()
    loop.hooks.sort(key=lambda h: h[0])
    try:
        loop.run(lambda now: now >= win1)
        in_window = log.compiles() - marks.get("compiles", log.compiles())
        hits_in_window = log.cache_hits - marks.get("hits", log.cache_hits)
        window = [r for r in loop.records.values() if win0 <= r.due < win1]
        if mix["drain_s"] > 0:
            loop.run(lambda now: now >= win1 + mix["drain_s"] or all(
                r.done is not None for r in window))
    finally:
        if tracer is not None:
            tracer.end()
    t_end = time.perf_counter()
    say(f"bench: compiles inside the window: {in_window} "
        f"(persistent-cache loads {hits_in_window}); boundaries "
        f"{loop.boundary}; {len(window)} requests due in the window")
    say("bench: work " + json.dumps(work_summary(loop, window, win0, win1)))

    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", -1))
    families = [cell.family(n) for n in names]
    archs = [f.arch(cell.config["models"][n]) for f, n in zip(families,
                                                              names)]
    records = sorted(loop.records.values(), key=lambda r: r.arrival.rid)
    if telem is not None:
        telem.finalize()
    run = RunData(cell, win0, win1, seconds, records, loop.calls, names,
                  cell.config["n_slots"], archs, families, {}, t_end=t_end,
                  telemetry=telem)
    metrics, breakdown, device_extra = {}, None, {}
    if trace:
        run.peak = peak or peak_of(jax.devices()[0].device_kind)
        events = tracing.load(tracer.path)
        run.trace = tracing.reduce(events)
        if trace_dump:
            _dump(run.trace, events, trace_dump)
        shutil.rmtree(tracer.path, ignore_errors=True)
        for m in cell.per_layer:
            v = cell.readers[m["name"]](run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = tracing.breakdown(run.trace)
        device_extra = {"busy_s": run.trace["busy_s"],
                        "window_s": run.trace["window_s"]}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        for k, v in end_to_end(run, win0 - t_process).items():
            metrics[k] = {"value": v, "unit": units[k]}

    done_by_stage: Dict[int, List[dict]] = {}
    for r in records:
        if r.done is not None:
            done_by_stage.setdefault(r.resolver, []).append(
                {"rid": r.arrival.rid, "prompt": r.arrival.prompt,
                 "tokens": r.tokens, "gaps": r.gaps})
    attempted = len(window)
    failed = sum(r.done is None for r in window)

    # the program's state goes before the reference runs
    loop = te = None
    if not keep_engines:
        engines = None
    gc.collect()
    t_ref = time.perf_counter()
    readings = compare(names, dict(zip(names, zip(families, archs))),
                       params,
                       done_by_stage, seed, MIN_COMPARED_TOKENS,
                       MAX_COMPARED_REQUESTS, MIN_COMPARED_REQUESTS,
                       controls)
    say(f"bench: drain {t_end - win1:.1f} s, reference "
        f"{time.perf_counter() - t_ref:.1f} s")
    checks = limits_checks(cell, readings, records)
    correct = all(v <= lim for v, lim in checks.values())
    control_correct = {
        q: all(v <= lim for v, lim in
               limits_checks(cell, readings, records, q).values())
        for q in controls}
    dev = jax.devices()[0]
    out = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": mem_peak,
                   **device_extra},
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    out["readings"] = readings
    out["control_correct"] = control_correct
    out["compiles_in_window"] = in_window
    out["threshold"] = thr
    if keep_engines:
        out["engines"] = engines
    return out


def result_line(out: dict) -> dict:
    """The result line: its keys in order, ``checks`` last."""
    line = {k: out[k] for k in ("correct", "attempted", "failed", "metrics",
                                "device")}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    return line


def peak_of(kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; a kind not in it is an
    error."""
    table = json.loads((Path(__file__).resolve().parent
                        / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def _dump(summary: dict, events: dict, path: str,
          slice_ns: float = 30e6) -> None:
    """A short look at the trace: the busiest operations with their
    executable and long name, the planes' lines, and a recorded slice of
    the raw events with what ``tracing.reduce`` reads from it."""
    host = sorted(events["host"], key=lambda e: e["start_ns"])
    mid = host[len(host) // 2]["start_ns"]
    inside = lambda e: mid <= e["start_ns"] < mid + slice_ns  # noqa: E731
    cut = {"device": [e for e in events["device"] if inside(e)],
           "host": [e for e in host if inside(e)]}
    cut_summary = tracing.reduce(cut)
    recorded = {"events": cut, "expect": {
        k: cut_summary[k] for k in ("window_s", "busy_s", "by_module",
                                    "idle_by_host", "calls_by_module")}}
    lines = sorted({(e["plane"], e["line"]) for e in events["device"]})
    kernels = sorted({(e["module"], e["name"][:400], e["long"][:400])
                      for e in summary["ops"]
                      if "custom" in e["name"] or "top2" in e["name"]
                      or "custom" in e["long"]})[:20]
    ops = sorted(summary["ops"], key=lambda e: -e["dur_ns"])
    seen, rows = set(), []
    for e in ops:
        key = (e["module"], e["name"])
        if key not in seen:
            seen.add(key)
            rows.append(e)
        if len(rows) >= 60:
            break
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({k: v for k, v in summary.items() if k != "ops"}
                  | {"top_ops": rows, "lines": lines, "kernels": kernels},
                  f, indent=1, default=str)
    with open(path + ".slice.json", "w") as f:
        json.dump(recorded, f, indent=1)
