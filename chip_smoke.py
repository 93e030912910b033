"""Smoke run of model replicas on four TPU chips.

  python chip_smoke.py --chips 4 [--seed N]

The tiny classifier family, trained here from ``--seed``, is planned for
four devices and served by the threaded ``CascadeServer`` twice on the
same requests: with plan device d bound to chip d, and with every plan
device on chip 0. This is all the file does; the one-chip token cascade
is the benchmark's (``python3 benchmarks/chip/run.py --workload
cascade-chat --seed N``).

Lines before the last are smoke output: checks and what a run took, not
benchmark metrics. The last line is one JSON object, ``{"ok": true,
"device": {...}}``. Without four TPU chips, or when a check fails, the
script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_CHIPS = 4


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"smoke check ok: {what}", flush=True)


def say(text: str) -> None:
    print(f"smoke: {text}", flush=True)


def run_replicas(seed: int, devices, trace_qps: float = 200.0,
                 trace_seconds: int = 5) -> None:
    import jax
    from repro.core import SLO, HardwareSpec, optimize_gear_plan
    from repro.core.simulator import trace_to_arrivals
    from repro.serving.runtime import CascadeServer, Request
    from repro.serving.tinymodels import (make_engine_backend,
                                          synthetic_classification_data,
                                          train_tiny_family)
    n_dev = len(devices)
    t0 = time.perf_counter()
    # trained here from the seed: no artifact on disk is read
    backend = make_engine_backend(*train_tiny_family(seed=seed))
    say(f"tiny family trained and profiled in "
        f"{time.perf_counter() - t0:.1f} s on {jax.devices()[0]}")
    plan = optimize_gear_plan(
        backend.profiles, HardwareSpec(num_devices=n_dev,
                                       mem_per_device=16e9),
        SLO(kind="latency", latency_p95=0.3), qps_max=2 * trace_qps,
        n_ranges=4).plan
    for d in range(n_dev):
        say(f"plan device {d}: "
            f"{sorted(r.model for r in plan.replicas if r.device == d)}")

    trace = np.full(trace_seconds, trace_qps)
    n_arr = len(trace_to_arrivals(trace))
    toks, labels, _ = synthetic_classification_data(n_arr + 8, seed=seed + 7)
    models = sorted({r.model for r in plan.replicas})
    engines = [backend.engines[m] for m in models]
    t0 = time.perf_counter()
    # every batch bucket of every planned model, through the serving call,
    # on every chip: the engines and the certainty estimator compile here
    for dev in devices:
        for m in models:
            for b in backend.engines[m].buckets:
                backend.execute(m, range(b), device=dev)
    say(f"planned engines warmed up on {n_dev} devices in "
        f"{time.perf_counter() - t0:.1f} s")

    runs = {}
    for label, bound in (("bound", list(devices)),
                         ("chip0", [devices[0]] * n_dev)):
        for e in engines:
            e.batches_by_device.clear()
        reqs = [Request(rid=i, tokens=toks[i]) for i in range(n_arr + 8)]
        server = CascadeServer(plan, backend=backend, devices=bound)
        done = server.run_trace(reqs, trace, drain=2.0)
        per_dev = defaultdict(int)
        for e in engines:
            for dev, n in e.batches_by_device.items():
                per_dev[dev] += n
        acc = float(np.mean([r.pred == labels[r.rid] for r in done])) \
            if done else float("nan")
        p95 = float(np.quantile([r.latency for r in done], 0.95)) \
            if done else float("nan")
        say(f"{label}: {len(done)}/{n_arr} requests done, accuracy "
            f"{acc!r}, p95 latency {p95:.4f} s, batches per device "
            f"{ {str(d): per_dev.get(d, 0) for d in devices} }")
        check(len(done) == n_arr, f"{label}: every request completed")
        runs[label] = (acc, per_dev)
    diff = abs(runs["bound"][0] - runs["chip0"][0])
    check(diff <= 0.01, f"accuracy bound vs chip 0 differs by {diff!r}")
    check(all(runs["bound"][1].get(d, 0) >= 1 for d in devices),
          f"each of the {n_dev} chips ran batches when bound")
    check(set(runs["chip0"][1]) == {devices[0]},
          "with every plan device on chip 0, only chip 0 ran batches")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=N_CHIPS,
                    choices=(N_CHIPS,), help="the one size this run has")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu":
        print(f"smoke: JAX finds no TPU (platform {dev0.platform!r}); "
              f"nothing ran", file=sys.stderr)
        return 1
    say(f"device {dev0.device_kind}, {len(devices)} devices "
        f"(smoke output below, not benchmark metrics)")
    if len(devices) < N_CHIPS:
        print(f"smoke: needs {N_CHIPS} devices, JAX has {len(devices)}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache at {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        run_replicas(args.seed, devices[:N_CHIPS])
    except SmokeFailure as e:
        print(f"smoke FAILED: {e}", file=sys.stderr)
        return 1
    say(f"done in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
