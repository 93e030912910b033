"""Smoke run of the serving path on TPU chips.

  python chip_smoke.py [--seed N]        # one chip: the token cascade
  python chip_smoke.py --chips 4 [--seed N]   # four chips: model replicas

One chip (default): a qwen2-0.5b -> h2o-danube-1.8b token cascade at
published widths, with random bf16 weights made on the device from
``--seed``. Each stage gets a 16-slot, 2048-token ``SlotEngine``; about two
dozen requests (prompts of 16-1024 tokens, 32 new tokens each) go through
``TokenEngine.serve`` on the fused device loop, once to warm up and once
timed, then once more through the reference host loop for comparison.

Four chips (``--chips 4``): the tiny classifier family, trained here from
``--seed``, is planned for four devices and served by the threaded
``CascadeServer`` twice on the same requests: with plan device d bound to
chip d, and with every plan device on chip 0.

Lines before the last are smoke output: checks and what a run took, not
benchmark metrics. The last line is one JSON object, ``{"ok": true,
"device": {...}}``. Without a TPU, or when a check fails, the script exits
non-zero and prints no such line.
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

STAGES = ("qwen2-0.5b", "h2o-danube-1.8b")
N_SLOTS, MAX_LEN = 16, 2048
N_REQUESTS, MAX_NEW = 24, 32
PROMPT_LENS = (16, 1024)
HBM_LIMIT = 16 * 2 ** 30


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"smoke check ok: {what}", flush=True)


def say(text: str) -> None:
    print(f"smoke: {text}", flush=True)


class CompileLog:
    """Seconds JAX spends compiling (or fetching from the persistent cache),
    by jitted entry point, and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds = defaultdict(float)
        self.count = defaultdict(int)
        self.cache_hits = 0

        def on_duration(name, secs, fun_name="?", **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds[fun_name] += secs
                self.count[fun_name] += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def total(self) -> float:
        return sum(self.seconds.values())

    def report(self, top: int = 8) -> None:
        funs = sorted(self.seconds, key=self.seconds.get, reverse=True)
        for fun in funs[:top]:
            say(f"compile {fun}: {self.seconds[fun]:.2f} s over "
                f"{self.count[fun]} compiles")
        rest = funs[top:]
        say(f"compile, {len(rest)} other functions: "
            f"{sum(self.seconds[f] for f in rest):.2f} s over "
            f"{sum(self.count[f] for f in rest)} compiles")
        say(f"compile total: {self.total():.2f} s, persistent-cache hits "
            f"{self.cache_hits}")


def peak_hbm(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# --------------------------------------------------------------------------
# one chip: the token cascade
# --------------------------------------------------------------------------

def make_requests(seed: int, n: int, lens, id_hi: int, max_new: int):
    """Prompt lengths log-uniform in ``lens``, token ids in [0, id_hi)."""
    from repro.serving.token_engine import TokenRequest
    rng = np.random.default_rng(seed)
    lengths = np.exp(rng.uniform(np.log(lens[0]), np.log(lens[1] + 1), n))
    return [TokenRequest(i, rng.integers(0, id_hi, int(L)).astype(np.int32),
                         max_new)
            for i, L in enumerate(lengths.astype(int))]


def cascade_gear(names, thresholds):
    from repro.core.cascade import Cascade
    from repro.core.gears import Gear
    return Gear(cascade=Cascade(tuple(names), tuple(thresholds)),
                min_queue_lens={m: 1 for m in names},
                load_fractions={m: {i: 1.0} for i, m in enumerate(names)})


def choose_threshold(gap_streams, gear_for, batcher) -> float:
    """The stage-0 threshold that splits the requests most evenly between
    resolving at stage 0 and escalating, replaying the engine's own
    boundary rule over stage-0 gap streams."""
    from repro.core.certainty import StreamingCertainty
    from repro.core.scheduling import CascadeHop

    def n_resolved(thr):
        gear, n = gear_for(thr), 0
        for gaps in gap_streams:
            cert = StreamingCertainty(mode="ewma", beta=0.35)
            cert.update(gaps[0])
            _, hop = batcher.stream_trace_hop(0, cert, gaps[1:], 1,
                                              len(gaps), gear)
            n += not isinstance(hop, CascadeHop)
        return n

    finals = []
    for gaps in gap_streams:
        cert = StreamingCertainty(mode="ewma", beta=0.35)
        for g in gaps:
            cert.update(g)
        finals.append(cert.value)
    half = len(gap_streams) / 2
    return min(sorted(set(finals)), key=lambda t: abs(n_resolved(t) - half))


def run_cascade(seed: int, configs=None, n_slots: int = N_SLOTS,
                max_len: int = MAX_LEN, n_requests: int = N_REQUESTS,
                prompt_lens=PROMPT_LENS, max_new: int = MAX_NEW) -> None:
    import jax
    from repro.configs import get_config
    from repro.core.scheduling import ContinuousBatcher, SchedulerCore
    from repro.kernels.top2gap import top2gap_pallas
    from repro.models import model as M
    from repro.serving.token_engine import SlotEngine, TokenEngine

    device = jax.devices()[0]
    cfgs = configs or [get_config(n) for n in STAGES]
    names = [c.name for c in cfgs]
    log = CompileLog()

    engines = []
    for i, cfg in enumerate(cfgs):
        def init_params(key, cfg=cfg):
            return M.init_params(cfg, key)

        t0 = time.perf_counter()
        params = jax.jit(init_params)(
            jax.random.fold_in(jax.random.PRNGKey(seed), i))
        jax.block_until_ready(params)
        n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
        say(f"{cfg.name}: {n_bytes / 1e9:.3f} GB of random params made on "
            f"{device} in {time.perf_counter() - t0:.1f} s")
        engines.append(SlotEngine(cfg.name, params, cfg, n_slots=n_slots,
                                  max_len=max_len))

    id_hi = min(c.vocab_size for c in cfgs)
    reqs = make_requests(seed, n_requests, prompt_lens, id_hi, max_new)
    say(f"{len(reqs)} requests, prompts {min(r.prompt.size for r in reqs)}-"
        f"{max(r.prompt.size for r in reqs)} tokens, ids < {id_hi}, "
        f"{max_new} new tokens each, {n_slots} slots per stage")

    # stage 0 alone gives each request's gap stream; the threshold is
    # chosen from those so that both stages resolve requests
    alone = TokenEngine([engines[0]], cascade_gear(names[:1], ()),
                        mode="fused", spec_k=1)
    probe = alone.serve(reqs)
    streams = [probe[r.rid].gaps for r in reqs]
    thr = choose_threshold(
        streams, lambda t: cascade_gear(names, (t,)),
        ContinuousBatcher(SchedulerCore([]), n_slots))
    say(f"stage-0 threshold {thr!r} (from the stage-0 gap streams)")
    gear = cascade_gear(names, (thr,))

    fused = TokenEngine(engines, gear, mode="fused", spec_k=1)
    t0 = time.perf_counter()
    fused.serve(reqs)
    say(f"warm-up serve: {time.perf_counter() - t0:.2f} s")
    before = {e.name: e.compile_counts()["total"] for e in engines}
    t0 = time.perf_counter()
    out = fused.serve(reqs)
    wall = time.perf_counter() - t0
    after = {e.name: e.compile_counts()["total"] for e in engines}
    streamed = [sum(len(g) for g in out[r.rid].stage_gaps.values())
                for r in reqs]
    returned = sum(len(out[r.rid].tokens) for r in reqs)
    say(f"timed serve (warm, fused loop): {wall:.3f} s wall, {returned} "
        f"tokens returned, {sum(streamed)} decoded across stages")
    say(f"peak HBM after the fused serves: {peak_hbm(device)} bytes")
    check(before == after,
          f"no compiles during the timed serve (executables {after})")
    check(all(o.resolver in (0, 1) for o in out.values())
          and len(out) == len(reqs), "every request completed at stage 0/1")
    check(all(len(out[r.rid].tokens) == r.max_new for r in reqs),
          f"every request returned {max_new} tokens")
    check(all(0 <= t < cfgs[o.resolver].vocab_size
              for o in out.values() for t in o.tokens),
          "every token lies inside its stage's vocabulary")
    by_stage = [sum(o.resolver == s for o in out.values())
                for s in range(len(cfgs))]
    check(min(by_stage) >= 1,
          f"both stages resolved requests (per stage: {by_stage})")

    for eng in engines:
        check("tpu_custom_call" in eng.fused_step_text(),
              f"{eng.name}: the fused step holds the Pallas top-2-gap "
              f"kernel (tpu_custom_call)")

    # the reference host loop on the same engines: no second cache set
    ref = TokenEngine(engines, gear, mode="reference")
    t0 = time.perf_counter()
    ref_out = ref.serve(reqs)
    say(f"reference serve (cold, host loop): "
        f"{time.perf_counter() - t0:.2f} s")
    same_tok = sum(ref_out[r.rid].tokens == out[r.rid].tokens for r in reqs)
    same_res = sum(ref_out[r.rid].resolver == out[r.rid].resolver
                   for r in reqs)
    first_gap = max(abs(ref_out[r.rid].stage_gaps[0][0]
                        - out[r.rid].stage_gaps[0][0]) for r in reqs)
    say(f"reference vs fused (reported, not gated): tokens identical "
        f"{same_tok}/{len(reqs)}, resolver identical {same_res}/{len(reqs)},"
        f" max |first stage-0 gap difference| {first_gap!r}")

    # the kernel against lax.top_k on real stage-0 logits
    eng = engines[0]
    rng = np.random.default_rng(seed + 1)
    def stage0_logits(p, t, c, i):
        return M.decode_step(p, eng.cfg, t, c, i)[0]

    logits = jax.jit(stage0_logits)(eng.params,
                     rng.integers(0, id_hi, (n_slots, 1)).astype(np.int32),
                     eng.cache,
                     rng.integers(1, max_len // 2, n_slots).astype(np.int32))
    gap, idx = jax.jit(top2gap_pallas)(logits)
    top2, top2_idx = jax.jit(jax.lax.top_k, static_argnums=1)(logits, 2)
    check(np.array_equal(np.asarray(idx), np.asarray(top2_idx[:, 0]))
          and np.array_equal(np.asarray(gap),
                             np.asarray(top2[:, 0] - top2[:, 1])),
          f"top2gap_pallas equals lax.top_k exactly on {logits.shape} "
          f"{logits.dtype} stage-0 logits")

    log.report()
    peak = peak_hbm(device)
    say(f"peak HBM: {peak} bytes ({peak / 2 ** 30:.2f} GiB)")
    check(0 <= peak < HBM_LIMIT, "peak HBM under 16 GiB")


# --------------------------------------------------------------------------
# four chips: replicas behind the router
# --------------------------------------------------------------------------

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
    log = CompileLog()
    t0 = time.perf_counter()
    # every batch bucket of every planned model, through the serving call,
    # on every chip: the engines and the certainty estimator compile here
    for dev in devices:
        for m in models:
            for b in backend.engines[m].buckets:
                backend.execute(m, range(b), device=dev)
    say(f"planned engines warmed up on {n_dev} devices in "
        f"{time.perf_counter() - t0:.1f} s ({sum(log.count.values())} "
        f"compiles)")

    runs = {}
    for label, bound in (("bound", list(devices)),
                         ("chip0", [devices[0]] * n_dev)):
        for e in engines:
            e.batches_by_device.clear()
        reqs = [Request(rid=i, tokens=toks[i]) for i in range(n_arr + 8)]
        server = CascadeServer(plan, backend=backend, devices=bound)
        compiles = sum(log.count.values())
        done = server.run_trace(reqs, trace, drain=2.0)
        compiles = sum(log.count.values()) - compiles
        per_dev = defaultdict(int)
        for e in engines:
            for dev, n in e.batches_by_device.items():
                per_dev[dev] += n
        acc = float(np.mean([r.pred == labels[r.rid] for r in done])) \
            if done else float("nan")
        p95 = float(np.quantile([r.latency for r in done], 0.95)) \
            if done else float("nan")
        say(f"{label}: {len(done)}/{n_arr} requests done, accuracy "
            f"{acc!r}, p95 latency {p95:.4f} s, {compiles} compiles during "
            f"the serve, batches per device "
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
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
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
    if len(devices) < args.chips:
        print(f"smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX has {len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache at {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_replicas(args.seed, devices[:4])
        else:
            run_cascade(args.seed)
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
