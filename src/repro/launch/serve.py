"""Serving driver: plan a gear plan offline, then serve online.

Two workloads, three execution backends (DESIGN.md §9):
* ``--workload tiny``  — the REAL path: the trained tiny-classifier family
  behind an ``EngineBackend`` (profiles measured through the same backend
  via ``profile_backend``), the threaded producer/consumer runtime.
* ``--workload qwen``  — the assigned-architecture family (qwen2-0.5b ->
  qwen3-32b, per DESIGN.md §6) behind a ``CostModelBackend`` (analytic
  TPU-v5e roofline + synthetic validation behaviour), served on the
  discrete-event simulator: its profiles are analytic, not measured.
* ``--stress-replay``  — the threaded WALL-CLOCK runtime over a
  ``ReplayBackend``: no model compute, so the scheduler/queue machinery can
  be stressed at QPS far beyond what real inference allows.

``python -m repro.launch.serve --workload tiny --slo latency:0.2``
"""
from __future__ import annotations

import argparse
import os

import numpy as np

from repro.core import (CostModelBackend, EngineBackend, HardwareSpec,
                        ReplayBackend, SLO, ServingSimulator,
                        optimize_gear_plan, profile_backend)
from repro.core.profiles import ProfileSet
from repro.core.telemetry import Telemetry
from repro.core.traces import azure_like_trace, diurnal_like_trace


def dump_metrics(telem: Telemetry, path: str) -> None:
    """Write the run's telemetry next to ``path``: metrics JSONL at
    ``path``, a Prometheus-style text dump at ``path + '.prom'``, and the
    latency-attribution report at ``path + '.attr.json'``."""
    import json
    telem.finalize()
    with open(path, "w") as f:
        f.write(telem.registry.export_jsonl())
    with open(path + ".prom", "w") as f:
        f.write(telem.registry.prometheus_text())
    with open(path + ".attr.json", "w") as f:
        json.dump(telem.attribution(window_s=10.0), f, sort_keys=True,
                  indent=1)
    cons = telem.conservation()
    print(f"\nmetrics written to {path} (+.prom, +.attr.json): "
          f"spans opened={cons['opened']} completed={cons['completed']} "
          f"shed={cons['shed']} revoked={cons['revoked']} "
          f"open={cons['open']}")
    attr = telem.attribution()
    if attr["total"]["count"]:
        print(Telemetry.render_attribution(attr))


def parse_slo(text: str) -> SLO:
    kind, value = text.split(":")
    if kind == "latency":
        return SLO(kind="latency", latency_p95=float(value))
    return SLO(kind="accuracy", min_accuracy=float(value))


def parse_tenants(text: str):
    """``name:slokind:value:qps_max[:weight]``, comma-separated — e.g.
    ``interactive:latency:0.3:600:2,batch:latency:1.0:600:1``."""
    from repro.core import TenantSpec
    out = []
    for part in text.split(","):
        fields = part.split(":")
        if len(fields) not in (4, 5):
            raise ValueError(f"bad tenant spec {part!r} (want "
                             f"name:slokind:value:qps_max[:weight])")
        name, kind, value, qps_max = fields[:4]
        weight = float(fields[4]) if len(fields) == 5 else 1.0
        out.append(TenantSpec(name, parse_slo(f"{kind}:{value}"),
                              qps_max=float(qps_max), weight=weight,
                              n_ranges=4))
    return out


def serve_multitenant(args, profiles, hw, trace_fn, telem=None) -> None:
    """Multi-tenant mode (DESIGN.md §11): joint plan, per-tenant ladders,
    superposed traces with admission control — on the DES by default, on
    the threaded ``MultiTenantServer`` under ``--stress-replay``."""
    from repro.core import (AdmissionConfig, AdmissionController,
                            plan_multi_tenant)
    tenants = parse_tenants(args.tenants)
    report = plan_multi_tenant(profiles, hw, tenants)
    mt = report.plan
    print(f"\nmulti-tenant plan over {hw.num_devices} shared devices "
          f"({report.wall_seconds:.1f}s):")
    for spec in tenants:
        plan = mt.plans[spec.name]
        print(f"  {spec.name}: qps_max={spec.qps_max:.0f} w={spec.weight} "
              f"top gear {' -> '.join(plan.gears[-1].cascade.models)}")
    traces = {spec.name: trace_fn(seconds=args.trace_seconds,
                                  peak_qps=spec.qps_max)
              for spec in tenants}
    admission = AdmissionController(
        mt, AdmissionConfig(utilization_cap=0.75),
        registry=telem.registry if telem is not None else None)
    if args.stress_replay:
        from repro.serving.runtime import MultiTenantServer, Request
        replay = ReplayBackend(profiles, sleep=True)
        reqs = {n: [Request(rid=i, tokens=np.zeros(1, np.int32), tenant=n)
                    for i in range(int(traces[n].sum()) + 8)]
                for n in mt.names}
        server = MultiTenantServer(mt, backend=replay, admission=admission,
                                   telemetry=telem)
        done = server.run_trace(reqs, traces)
        print("\nREPLAY stress (wall clock, shared fleet):")
        for n in mt.names:
            lats = np.array([r.latency for r in done[n]]) \
                if done[n] else np.zeros(0)
            p95 = np.quantile(lats, .95) * 1e3 if len(lats) else float("nan")
            print(f"  {n}: {len(done[n])} done shed={server.shed_counts[n]} "
                  f"p95={p95:.1f}ms "
                  f"switches={len(server.gear_switches[n])}")
        if telem is not None:
            dump_metrics(telem, args.metrics_out)
        return
    sim_backend = ReplayBackend(profiles)
    sim = ServingSimulator(profiles, mt.replicas, hw.num_devices,
                           backend=sim_backend, telemetry=telem)
    results = sim.run_multi_tenant(mt, traces, admission=admission)
    print("\nsimulated (shared fleet):")
    for spec in tenants:
        r = results[spec.name]
        print(f"  {spec.name}: {r.result.completed}/{r.offered} done "
              f"shed={r.shed} ({100 * r.shed_rate:.1f}%) "
              f"p95={r.p95 * 1e3:.0f}ms acc={r.accuracy:.4f} "
              f"switches={len(r.result.gear_switches)}")
    if telem is not None:
        dump_metrics(telem, args.metrics_out)


def tiny_backend(artifact: str) -> EngineBackend:
    """EngineBackend over the trained tiny family (token/label pools
    attached so any driver can execute from sample ids alone; profiles
    measured via the unified entry point in ``make_engine_backend``)."""
    from repro.serving.tinymodels import make_engine_backend, \
        train_tiny_family
    return make_engine_backend(*train_tiny_family(cache_path=artifact))


def tiny_profiles(artifact: str) -> ProfileSet:
    return tiny_backend(artifact).profiles


def qwen_backend() -> CostModelBackend:
    """CostModelBackend for the assigned big architectures: accuracy/
    certainty structure synthesised, latency/memory analytic (v5e)."""
    from repro.core.profiles import synthetic_family
    names = ["qwen2-0.5b", "internvl2-1b", "qwen2-moe-a2.7b", "qwen3-32b"]
    synth = synthetic_family(names, base_acc=0.55, acc_gain=0.05, seed=11)
    return CostModelBackend(
        {n: n for n in names}, context=2048, kind="decode",
        validation={n: synth[n].validation for n in names})


def qwen_profiles() -> ProfileSet:
    return profile_backend(qwen_backend())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="tiny", choices=["tiny", "qwen"])
    ap.add_argument("--slo", default="latency:0.3",
                    help="latency:<p95 s> | accuracy:<min>")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--mem-per-device", type=float, default=16e9)
    ap.add_argument("--qps-max", type=float, default=0.0)
    ap.add_argument("--n-ranges", type=int, default=8)
    ap.add_argument("--trace", default="diurnal",
                    choices=["diurnal", "azure"])
    ap.add_argument("--trace-seconds", type=int, default=60)
    ap.add_argument("--real", action="store_true",
                    help="tiny workload: threaded runtime, wall clock")
    ap.add_argument("--stress-replay", action="store_true",
                    help="threaded wall-clock runtime over a ReplayBackend "
                         "(no model compute: pure scheduler/queue stress)")
    ap.add_argument("--artifact",
                    default="benchmarks/artifacts/tiny_family.npz")
    ap.add_argument("--plan-out", default="")
    ap.add_argument("--metrics-out", default="",
                    help="write metrics JSONL here (plus .prom Prometheus "
                         "dump and .attr.json latency attribution)")
    ap.add_argument("--tenants", default="",
                    help="multi-tenant mode (DESIGN.md §11): comma-"
                         "separated name:slokind:value:qps_max[:weight]")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.workload == "tiny":
        backend = tiny_backend(args.artifact)
        profiles = backend.profiles
        qps_max = args.qps_max or 2000.0
    else:
        backend = qwen_backend()
        profiles = backend.profiles
        qps_max = args.qps_max or 60.0

    for name, p in profiles.items():
        print(f"  {name:14s} acc={p.accuracy:.3f} "
              f"rt(1)={p.runtime(1) * 1e3:.2f}ms "
              f"slice={p.devices_per_replica}")

    slo = parse_slo(args.slo)
    hw = HardwareSpec(num_devices=args.devices,
                      mem_per_device=args.mem_per_device)

    telem = Telemetry() if args.metrics_out else None

    if args.tenants:
        trace_fn = diurnal_like_trace if args.trace == "diurnal" \
            else azure_like_trace
        serve_multitenant(args, profiles, hw, trace_fn, telem=telem)
        return

    report = optimize_gear_plan(profiles, hw, slo, qps_max=qps_max,
                                n_ranges=args.n_ranges)
    plan = report.plan
    print(f"\ngear plan: {report.submodule_calls} submodule calls, "
          f"{report.errors_resolved} errors resolved, "
          f"{report.wall_seconds:.1f}s")
    for sub, secs in sorted(report.submodule_seconds.items()):
        print(f"  {sub:22s} {secs:7.2f}s")
    for r, g in enumerate(plan.gears):
        print(f"  range {r} (<= {plan.range_width * (r + 1):.0f} qps): "
              f"{' -> '.join(g.cascade.models)} "
              f"acc={g.expected_accuracy:.3f} "
              f"p95={g.expected_p95 * 1e3:.0f}ms")
    if args.plan_out:
        with open(args.plan_out, "w") as f:
            f.write(plan.to_json())
        print(f"plan written to {args.plan_out}")

    trace_fn = diurnal_like_trace if args.trace == "diurnal" \
        else azure_like_trace
    trace = trace_fn(seconds=args.trace_seconds, peak_qps=qps_max)

    if args.stress_replay:
        # real threaded machinery, replayed physics: sleeps for the
        # profiled batch runtime instead of running model compute, so the
        # producer/consumer/queue path is exercised at arbitrary QPS
        from repro.serving.runtime import CascadeServer, Request
        replay = ReplayBackend(profiles, sleep=True)
        n_req = int(trace.sum()) + 8
        reqs = [Request(rid=i, tokens=np.zeros(1, np.int32))
                for i in range(n_req)]
        server = CascadeServer(plan, backend=replay, telemetry=telem)
        done = server.run_trace(reqs, trace)
        lats = np.array([r.latency for r in done])
        print(f"\nREPLAY stress (wall clock): {len(done)}/{n_req} done "
              f"p50={np.quantile(lats, .5) * 1e3:.1f}ms "
              f"p95={np.quantile(lats, .95) * 1e3:.1f}ms "
              f"switches={len(server.gear_switches)}")
        if telem is not None:
            dump_metrics(telem, args.metrics_out)
    elif args.real and args.workload == "tiny":
        import jax
        from repro.serving.runtime import CascadeServer, Request
        from repro.serving.tinymodels import synthetic_classification_data
        # plan device d runs on JAX device d where there are enough of them
        devices = jax.devices()[:plan.num_devices] \
            if len(jax.devices()) >= plan.num_devices else None
        print(f"plan devices -> "
              f"{[str(d) for d in devices] if devices else 'default device'}")
        for d in devices or [None]:
            for e in backend.engines.values():
                e.warmup(32, device=d)
        n_req = int(trace.sum()) + 8
        toks, labels, _ = synthetic_classification_data(n_req, seed=7)
        reqs = [Request(rid=i, tokens=toks[i]) for i in range(n_req)]
        server = CascadeServer(plan, backend=backend, telemetry=telem,
                               devices=devices)
        done = server.run_trace(reqs, trace)
        lats = np.array([r.latency for r in done])
        acc = np.mean([int(r.pred == labels[r.rid]) for r in done])
        print(f"\nREAL runtime: {len(done)}/{n_req} done "
              f"p50={np.quantile(lats, .5) * 1e3:.1f}ms "
              f"p95={np.quantile(lats, .95) * 1e3:.1f}ms acc={acc:.4f} "
              f"switches={len(server.gear_switches)}")
        if telem is not None:
            dump_metrics(telem, args.metrics_out)
    else:
        # replay physics for the DES: the cost-model backend already IS a
        # replay backend over its analytic profiles; engine-measured
        # profiles are wrapped
        sim_backend = backend if isinstance(backend, ReplayBackend) \
            else ReplayBackend(profiles)
        sim = ServingSimulator(profiles, plan.replicas, hw.num_devices,
                               backend=sim_backend, telemetry=telem)
        res = sim.run_trace(plan, trace)
        print(f"\nsimulated ({sim.backend.name} backend): "
              f"{res.completed}/{res.offered} done "
              f"p95={res.p95 * 1e3:.0f}ms acc={res.accuracy:.4f} "
              f"util={res.utilization:.2f} "
              f"switches={len(res.gear_switches)}")
        if telem is not None:
            dump_metrics(telem, args.metrics_out)


if __name__ == "__main__":
    main()
