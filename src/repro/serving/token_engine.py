"""Token-level serving engine (DESIGN.md §13–14): continuous batching over
the real ``prefill``/``decode_step`` kernels, with a device-resident fused
decode loop.

The one-shot ``InferenceEngine`` (serving/engine.py) treats a request as a
single classify-and-resolve unit. Generation breaks that model: a request
occupies KV-cache memory for its whole lifetime and produces a decision
point at EVERY token. This module adds the token-native execution layer:

* ``SlotEngine`` — one model's resident decode batch. A fixed pool of
  ``n_slots`` KV-cache slots (one ``init_cache`` allocation, batch axis 1
  of the rep-stacked cache arrays) is driven by ONE jitted decode
  executable of static shape ``(n_slots, 1)`` with a per-slot ``(B,)``
  ``cache_index`` — the ragged-decode path. Requests join by prefilling
  and scattering the resulting cache into free slots; rows are independent
  under the ragged per-row masks, so joins are bit-invisible to resident
  requests (pinned by tests/test_token_engine.py).
* ``TokenEngine`` — a cascade of SlotEngines sharing the scheduling
  decision layer with the token DES: ``ContinuousBatcher`` admits waiting
  requests at token boundaries (prefill phase before the next decode
  step — the phase split) and decides mid-stream escalation from a
  ``StreamingCertainty`` fold of per-token top-2 gaps. Escalation carries
  the PROMPT to the next model, never the cache (incompatible layouts
  across architectures; the paper's cascades re-run the larger model from
  scratch for the same reason).

Two execution modes (DESIGN.md §14):

* ``fused`` (default) — the device-resident loop. Greedy argmax, the
  top-2-gap reduction and the streaming-certainty fold run INSIDE the
  jitted step (``models.model.decode_fused_steps``), so each step ships
  ``(B,)`` tokens + ``(B,)`` gaps + ``(B,)`` certainty values to the host
  instead of ``(B, V)`` logits, with the KV cache donated back to the
  executable (no double buffering). When nothing is waiting anywhere and
  no row is near a decision boundary, K steps run inside one ``lax.scan``
  per call; the host replays boundary decisions over the returned
  ``(K, B)`` traces at the SAME token counts a single-step loop would
  have used (``ContinuousBatcher.stream_trace_hop``), discarding at most
  K-1 speculative tokens on an early decision. Joiners prefill in ONE
  right-padded call per boundary, padded to power-of-two (length, batch)
  buckets so the compile set is bounded by the bucket grid, not the
  prompt-length distribution (``models.model.prefill_bucketed``; configs
  where right padding is not exact — SSM state, MoE capacity routing —
  fall back to exact-length prefills, see
  ``bucketed_prefill_supported``).
* ``reference`` — the PR-7 loop, kept verbatim as the parity baseline:
  one jit call per decode step returning full logits, per-joiner batch-1
  prefills, host-side argmax/top-2-gap per row.

Decisions are bit-identical across the two modes and the token DES by
construction: every executor folds the same float64 ``StreamingCertainty``
over the same per-token gap stream and consults the same
``ContinuousBatcher`` at the same token counts. Decisions advance in
deterministic logical steps and read no clock; timing lives in the DES
(``ServingSimulator.run_token_trace``), which stays the decision oracle.

Telemetry (``TokenEngine(telemetry=...)``, DESIGN.md §16) is the one
place the engine reads a clock, the caller's (``clock``, by default
``time.perf_counter``). It stamps the request events and times the
phases of each token boundary (``Telemetry.phase``), each also a
``jax.profiler.TraceAnnotation``:

* ``engine.admit`` (a boundary's joins at one stage, when the batcher
  admits any), holding ``slot.prefill`` (the bucketed prefill's dispatch,
  or the exact-length fallback loop; counts ``rows``, ``batch_bucket``,
  ``len_bucket`` (0: exact length), ``tokens`` and ``padded``),
  ``slot.join`` (the cache scatter, then the fused loop's row updates)
  and ``slot.fetch`` (the host blocked on the first tokens and gaps);
* ``engine.decode`` (one fused decode call; counts ``rows``, ``k``),
  holding ``slot.dispatch`` (the active-mask upload and the executable
  call), ``slot.fetch`` (the host blocked on the traces) and
  ``engine.decide`` (the boundary replay and the leaves; counts
  ``leaves``, ``escalations``).

The reference loop's decode steps are not timed. With ``telemetry=None``
no annotation is made and the clock is never read.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import (Callable, Deque, Dict, List, Optional, Sequence, Set,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.certainty import (StreamingCertainty, device_fold_init,
                                  device_fold_set_rows, top2_gap)
from repro.core.gears import Gear
from repro.core.scheduling import (ContinuousBatcher, SchedulerConfig,
                                   SchedulerCore)
from repro.models import model as model_lib

__all__ = ["SlotEngine", "TokenEngine", "TokenRequest", "TokenResult",
           "SlotEngineStats", "greedy_generate"]


def greedy_generate(params, cfg, prompt: np.ndarray, max_new: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Reference single-request greedy decode: prefill + N x decode_step.

    prompt (L,) int32 -> (tokens (max_new,), per-token top-2 gaps
    (max_new,)). The parity tests pin this position-for-position against
    the full ``forward`` pass.
    """
    toks = np.asarray(prompt, np.int32)[None, :]
    cache_len = toks.shape[1] + max_new
    logits, cache = model_lib.prefill(params, cfg, {"tokens": toks},
                                      cache_len=cache_len)
    out, gaps = [], []
    pos = toks.shape[1]
    for _ in range(max_new):
        nxt = int(np.argmax(np.asarray(logits[0])))
        gaps.append(float(np.asarray(top2_gap(logits))[0]))
        out.append(nxt)
        step = np.full((1, 1), nxt, np.int32)
        logits, cache = model_lib.decode_step(
            params, cfg, step, cache, np.asarray([pos], np.int32))
        pos += 1
    return np.asarray(out, np.int32), np.asarray(gaps, np.float64)


_NO_PHASE = contextlib.nullcontext()


def _phase(telemetry, clock, name: str, stage: Optional[int] = None,
           boundary: Optional[int] = None, **counts: int):
    """``telemetry.phase(...)``, or a no-op context (bound to None) when
    there is no telemetry."""
    if telemetry is None:
        return _NO_PHASE
    return telemetry.phase(name, clock, stage, boundary, **counts)


def _pow2_buckets(lo: int, hi: int) -> List[int]:
    """Powers of two in [lo, hi), then hi itself as the clamp bucket."""
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return out


@dataclass
class SlotEngineStats:
    """Hot-loop instrumentation (bench_decode_loop): executable calls,
    decode steps executed, and the ANALYTIC host-transfer byte counts of
    the step outputs/inputs (what crosses the PCIe/ICI boundary per step,
    not incidental bookkeeping)."""
    prefill_calls: int = 0          # prefill executable invocations
    prefill_prompts: int = 0        # prompts prefetched across those calls
    decode_calls: int = 0           # decode executable invocations
    decode_steps: int = 0           # decode steps executed (sum of K)
    bytes_to_host: int = 0          # step outputs shipped device -> host
    bytes_to_device: int = 0        # step operands shipped host -> device
    prefill_shapes: Set[Tuple[int, int]] = field(default_factory=set)


class SlotEngine:
    """One model's resident decode batch over a fixed KV-slot pool."""

    def __init__(self, name: str, params, cfg, n_slots: int, max_len: int,
                 min_len_bucket: int = 8):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        self.name = name
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.cache = model_lib.init_cache(cfg, n_slots, max_len)
        self.free: List[int] = list(range(n_slots - 1, -1, -1))  # pop -> 0
        # per-slot context depth (tokens already in cache); 0 = idle slot
        self.pos = np.zeros(n_slots, np.int32)
        self.active = np.zeros(n_slots, bool)
        self.stats = SlotEngineStats()
        self._vocab = cfg.vocab_size
        # set by the TokenEngine that drives this pool
        self.telemetry = None
        self.clock: Callable[[], float] = time.perf_counter
        # --- reference executables (PR-7 loop, parity baseline) ---------
        # each jitted entry point is a named function: compile logs and
        # profiler traces report it by that name
        def reference_decode(p, t, c, i):
            return model_lib.decode_step(p, cfg, t, c, i)

        def reference_prefill(p, t):
            return model_lib.prefill(p, cfg, {"tokens": t},
                                     cache_len=max_len)

        # one decode executable, static shape (n_slots, 1) + (n_slots,)
        self._decode = jax.jit(reference_decode)
        self._prefill = jax.jit(reference_prefill)
        # --- fused-loop state (device-resident, DESIGN.md §14) ----------
        self.dev_pos = jnp.zeros((n_slots,), jnp.int32)
        self.dev_tok = jnp.zeros((n_slots,), jnp.int32)
        self.dev_active = jnp.zeros((n_slots,), bool)
        self._active_dirty = False
        self._fold = device_fold_init(n_slots)
        self._fused_fns: Dict[Tuple[str, float], "jax.stages.Wrapped"] = {}
        self.len_buckets = _pow2_buckets(min(min_len_bucket, max_len),
                                         max_len)
        self.batch_buckets = _pow2_buckets(1, n_slots)
        if model_lib.bucketed_prefill_supported(cfg):
            def bucketed_prefill(params, tokens, true_lens):
                """Batched padded prefill + fused greedy/top-2-gap
                reduction: the host receives (B,) tokens + (B,) gaps,
                never (B, V) logits."""
                from repro.kernels.top2gap import argmax_gap
                logits, cache = model_lib.prefill_bucketed(
                    params, cfg, tokens, true_lens, cache_len=max_len)
                tok, gap = argmax_gap(logits)
                return tok, gap, cache

            self._bucketed = jax.jit(bucketed_prefill)
        else:
            self._bucketed = None

    @property
    def n_active(self) -> int:
        return self.n_slots - len(self.free)

    def compile_counts(self) -> Dict[str, int]:
        """Executable-cache sizes per entry point (compile-stability
        regression hook: the bucketed prefill set must stay bounded by the
        bucket grid, while the reference prefill compiles one executable
        per distinct prompt length)."""
        out = {
            "reference_prefill": int(self._prefill._cache_size()),
            "reference_decode": int(self._decode._cache_size()),
            "bucketed_prefill": int(self._bucketed._cache_size())
            if self._bucketed is not None else 0,
            "fused_decode": sum(int(f._cache_size())
                                for f in self._fused_fns.values()),
        }
        out["total"] = sum(out.values())
        return out

    # ------------------------------------------------------------- joins

    def _check_prompt(self, prompt: np.ndarray) -> np.ndarray:
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token array")
        if prompt.size >= self.max_len:
            raise ValueError(
                f"prompt ({prompt.size} tokens) leaves no decode headroom "
                f"in a {self.max_len}-token slot")
        return prompt

    def prefill_into_slot(self, prompt: np.ndarray) -> Tuple[int, np.ndarray]:
        """Prefill one prompt and scatter its cache into a free slot
        (reference path). Returns (slot index, last-position logits (V,)).
        The scatter overwrites the slot's whole cache lane, so stale
        contents from the previous occupant cannot leak.
        """
        if not self.free:
            raise RuntimeError(f"{self.name}: no free decode slot")
        prompt = self._check_prompt(prompt)
        logits, cache1 = self._prefill(self.params, prompt[None, :])
        slot = self.free.pop()
        # rep-stacked cache leaves are (reps, B, ...): batch at axis 1
        self.cache = jax.tree.map(
            lambda pool, new: pool.at[:, slot].set(
                new[:, 0].astype(pool.dtype)), self.cache, cache1)
        self.pos[slot] = prompt.size
        self.active[slot] = True
        self._active_dirty = True
        self.stats.prefill_calls += 1
        self.stats.prefill_prompts += 1
        self.stats.prefill_shapes.add((1, int(prompt.size)))
        self.stats.bytes_to_device += prompt.size * 4
        self.stats.bytes_to_host += self._vocab * 4
        return slot, np.asarray(logits[0])

    def _len_bucket(self, n: int) -> int:
        for b in self.len_buckets:
            if n <= b:
                return b
        return self.len_buckets[-1]

    def _batch_bucket(self, n: int) -> int:
        for b in self.batch_buckets:
            if n <= b:
                return b
        return self.batch_buckets[-1]

    def _join_rows(self, slots: Sequence[int], plens: np.ndarray,
                   toks: np.ndarray, gaps: np.ndarray) -> None:
        """Sync the fused loop's device-resident rows for new joiners:
        positions, next-token feeds, and the certainty fold re-seeded with
        each request's first (prefill) gap."""
        rows = jnp.asarray(np.asarray(slots, np.int32))
        self.dev_pos = self.dev_pos.at[rows].set(
            jnp.asarray(plens.astype(np.int32)))
        self.dev_tok = self.dev_tok.at[rows].set(
            jnp.asarray(toks.astype(np.int32)))
        self._fold = device_fold_set_rows(self._fold, rows,
                                          jnp.asarray(gaps, jnp.float32))
        self._active_dirty = True

    def prefill_batch(self, prompts: Sequence[np.ndarray]
                      ) -> Tuple[List[int], np.ndarray, np.ndarray]:
        """Prefill all of a boundary's joiners (fused path).

        One right-padded call per boundary when the config supports exact
        padded prefill: prompts pad to the smallest power-of-two length
        bucket covering the longest joiner, the batch pads to a batch
        bucket, and the executable returns per-row (first token, gap) —
        so the compile set is (len buckets x batch buckets), invariant to
        the prompt-length distribution. Unsupported configs (SSM / MoE /
        enc-dec) keep exact-length per-prompt prefills.

        Returns (slots, first tokens (n,), first gaps (n,)).
        """
        prompts = [self._check_prompt(p) for p in prompts]
        n = len(prompts)
        if n == 0:
            return [], np.zeros(0, np.int32), np.zeros(0, np.float32)
        if n > len(self.free):
            raise RuntimeError(
                f"{self.name}: {n} joiners for {len(self.free)} free slots")
        lb = self._len_bucket(max(p.size for p in prompts))
        telem, clock = self.telemetry, self.clock
        if self._bucketed is None or (
                self.cfg.sliding_window > 0
                and lb >= min(self.cfg.sliding_window, self.max_len)):
            # exact-length fallback: pads are not semantically invisible
            # here (SSM state / MoE routing / window ring aliasing)
            slots, toks, gaps = [], [], []
            with _phase(telem, clock, "slot.prefill") as ph:
                for p in prompts:
                    slot, logits = self.prefill_into_slot(p)
                    slots.append(slot)
                    toks.append(int(np.argmax(logits)))
                    gaps.append(
                        float(np.asarray(top2_gap(logits[None, :]))[0]))
                if ph is not None:
                    real = sum(p.size for p in prompts)
                    ph.counts.update(rows=n, batch_bucket=1, len_bucket=0,
                                     tokens=real, padded=real)
            toks = np.asarray(toks, np.int32)
            gaps = np.asarray(gaps, np.float32)
            with _phase(telem, clock, "slot.join"):
                self._join_rows(slots, np.asarray([p.size for p in prompts]),
                                toks, gaps)
            return slots, toks, gaps
        with _phase(telem, clock, "slot.prefill") as ph:
            bb = self._batch_bucket(n)
            arr = np.zeros((bb, lb), np.int32)
            lens = np.ones((bb,), np.int32)
            for i, p in enumerate(prompts):
                arr[i, :p.size] = p
                lens[i] = p.size
            tok_d, gap_d, cache1 = self._bucketed(self.params, arr, lens)
            if ph is not None:
                ph.counts.update(rows=n, batch_bucket=bb, len_bucket=lb,
                                 tokens=int(lens[:n].sum()), padded=bb * lb)
        slots = [self.free.pop() for _ in range(n)]
        with _phase(telem, clock, "slot.join"):
            rows = jnp.asarray(np.asarray(slots, np.int32))
            self.cache = jax.tree.map(
                lambda pool, new: pool.at[:, rows].set(
                    new[:, :n].astype(pool.dtype)), self.cache, cache1)
        with _phase(telem, clock, "slot.fetch"):
            toks = np.asarray(tok_d[:n])
            gaps = np.asarray(gap_d[:n])
        plens = lens[:n]
        for slot, plen in zip(slots, plens):
            self.pos[slot] = plen
            self.active[slot] = True
        with _phase(telem, clock, "slot.join"):
            self._join_rows(slots, plens, toks, gaps)
        self.stats.prefill_calls += 1
        self.stats.prefill_prompts += n
        self.stats.prefill_shapes.add((bb, lb))
        self.stats.bytes_to_device += arr.nbytes + lens.nbytes
        self.stats.bytes_to_host += n * 8          # (tok, gap) per joiner
        return slots, toks, gaps

    # ----------------------------------------------------------- leaves

    def release(self, slot: int) -> None:
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self.active[slot] = False
        self.pos[slot] = 0
        self.free.append(slot)
        self._active_dirty = True

    # ------------------------------------------------------ decode steps

    def decode(self, tokens_by_slot: Dict[int, int]) -> Dict[int, np.ndarray]:
        """One ragged decode step over the resident batch (reference
        path: full logits come back to the host).

        tokens_by_slot: {slot: next input token} for every ACTIVE slot.
        Idle slots ride along at position 0 with a zero token (their rows
        are independent under the per-row ragged masks and their lanes are
        fully overwritten at the next prefill scatter). Returns
        {slot: logits (V,)} and advances each active slot's depth.
        """
        if set(tokens_by_slot) != set(np.flatnonzero(self.active)):
            raise ValueError("decode needs exactly the active slots")
        slots = np.fromiter(tokens_by_slot.keys(), np.int64,
                            len(tokens_by_slot))
        vals = np.fromiter(tokens_by_slot.values(), np.int64, len(slots))
        if (self.pos[slots] >= self.max_len).any():
            full = int(slots[np.argmax(self.pos[slots] >= self.max_len)])
            raise ValueError(f"slot {full} is full ({self.max_len} tokens)")
        toks = np.zeros((self.n_slots, 1), np.int32)
        toks[slots, 0] = vals
        logits, self.cache = self._decode(
            self.params, toks, self.cache, self.pos)
        logits = np.asarray(logits)
        self.pos[slots] += 1
        self.stats.decode_calls += 1
        self.stats.decode_steps += 1
        self.stats.bytes_to_device += self.n_slots * 8   # tokens + pos
        self.stats.bytes_to_host += self.n_slots * self._vocab * 4
        return {int(s): logits[s] for s in slots}

    def _get_fused(self, mode: str, beta: float):
        key = (mode, float(beta))
        fn = self._fused_fns.get(key)
        if fn is None:
            cfg = self.cfg

            def fused_decode(params, tokens, cache, positions, active,
                             fold_state, k: int):
                return model_lib.decode_fused_steps(
                    params, cfg, tokens, cache, positions, active,
                    fold_state, k=k, beta=beta, mode=mode)

            # the KV cache (and the small device-resident carries) are
            # donated: the executable writes the new cache into the old
            # buffers instead of double-buffering HBM
            fn = jax.jit(fused_decode, static_argnames=("k",),
                         donate_argnums=(1, 2, 3, 5))
            self._fused_fns[key] = fn
        return fn

    def fused_step_text(self, k: int = 1, mode: str = "ewma",
                        beta: float = 0.35) -> str:
        """The compiled text of the fused decode step at this engine's
        shapes, which shows the kernels the step holds (a Pallas kernel
        appears as a ``tpu_custom_call`` on the TPU). Compiles afresh; the
        executable the loop dispatches is not touched."""
        return self._get_fused(mode, beta).lower(
            self.params, self.dev_tok, self.cache, self.dev_pos,
            self.dev_active, self._fold, k=k).compile().as_text()

    def decode_fused(self, k: int = 1, mode: str = "ewma",
                     beta: float = 0.35
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``k`` fused decode steps over the resident batch (device loop).

        Returns (token trace (k, B) i32, gap trace (k, B) f32, certainty
        trace (k, B) f32) — O(k·B) to the host; all step operands (input
        tokens, positions, certainty fold) stay device-resident between
        calls. Advances every active slot's depth by ``k``.
        """
        if self.n_active == 0:
            raise RuntimeError(f"{self.name}: no active slots to decode")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if int(self.pos[self.active].max()) + k > self.max_len:
            raise ValueError(
                f"{self.name}: a {k}-step scan overruns a "
                f"{self.max_len}-token slot")
        with _phase(self.telemetry, self.clock, "slot.dispatch"):
            if self._active_dirty:
                self.dev_active = jnp.asarray(self.active)
                self._active_dirty = False
            fn = self._get_fused(mode, beta)
            (tt, gt, ct, self.dev_tok, self.cache, self.dev_pos,
             self._fold) = fn(self.params, self.dev_tok, self.cache,
                              self.dev_pos, self.dev_active, self._fold, k=k)
        self.pos[self.active] += k
        self.stats.decode_calls += 1
        self.stats.decode_steps += k
        self.stats.bytes_to_host += k * self.n_slots * 12  # tok+gap+cert
        with _phase(self.telemetry, self.clock, "slot.fetch"):
            return np.asarray(tt), np.asarray(gt), np.asarray(ct)


@dataclass
class TokenRequest:
    rid: int
    prompt: np.ndarray            # (L,) int32
    max_new: int


@dataclass
class TokenResult:
    rid: int
    tokens: List[int] = field(default_factory=list)
    gaps: List[float] = field(default_factory=list)
    resolver: int = -1            # cascade stage that resolved the request
    hops: int = 0                 # mid-stream / end-of-stream escalations
    first_token_step: int = -1    # logical step of the first decode output
    done_step: int = -1
    # per-visited-stage gap stream (the tokens the request REALLY consumed
    # there — speculative tokens never enter); keyed by stage index. This
    # is what the engine-vs-DES decision-parity tests replay.
    stage_gaps: Dict[int, List[float]] = field(default_factory=dict)


@dataclass
class _Active:
    req: TokenRequest
    slot: int
    next_token: int               # greedy argmax fed to the next step
    cert: StreamingCertainty
    res: TokenResult


class TokenEngine:
    """Continuous-batching cascade over per-model ``SlotEngine`` pools.

    Decisions (admission, escalation, resolution) are delegated to the
    same ``ContinuousBatcher``/``SchedulerCore`` layer the token DES uses;
    this class only owns the real-model execution state. ``serve`` runs
    the whole request set to completion in deterministic logical steps —
    one step = (admit + prefill joiners) then one decode phase per stage.

    ``mode='fused'`` (default) drives the device-resident loop;
    ``mode='reference'`` is the PR-7 host loop, kept as the bit-parity
    baseline. ``spec_k`` > 1 enables speculative multi-token scans: K
    decode steps per executable call whenever no request is waiting at ANY
    stage (so admission decisions cannot shift — the K-collapse rule) and
    no resident row is near a decision boundary
    (``ContinuousBatcher.near_boundary`` with ``k_guard_slack``); at most
    K-1 tokens are discarded when a row decides mid-scan, and every
    decision is re-derived from the returned gap trace at the same token
    counts as a K=1 run.

    ``telemetry`` (a ``core.telemetry.Telemetry``, shared with the stage
    engines) observes on ``clock``, and only observes. Its request events
    carry ``clock()`` seconds: ``admit`` when the engine takes custody of
    the request (in ``serve`` its queue entry; for a caller that fills
    ``waiting[0]`` itself and calls ``_admit``, its first stage-0 admit),
    ``fire`` at the start of each stage's admit that takes it,
    ``escalate`` when it enters the next stage's queue, ``close`` when it
    resolves. Its phases time each boundary (module docstring).
    """

    def __init__(self, stages: Sequence[SlotEngine], gear: Gear,
                 cfg: SchedulerConfig = SchedulerConfig(),
                 min_tokens: int = 4, early_margin: float = 0.5,
                 stream_mode: str = "ewma", beta: float = 0.35,
                 mode: str = "fused", spec_k: int = 1,
                 k_guard_slack: float = 1.5, telemetry=None,
                 clock: Callable[[], float] = time.perf_counter):
        if not stages:
            raise ValueError("TokenEngine needs at least one SlotEngine")
        if tuple(e.name for e in stages) != tuple(gear.cascade.models):
            raise ValueError(
                f"stage engines {[e.name for e in stages]} do not match "
                f"the gear cascade {list(gear.cascade.models)}")
        if mode not in ("fused", "reference"):
            raise ValueError(f"mode must be fused|reference, got {mode!r}")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if mode == "reference" and spec_k != 1:
            raise ValueError("speculative scans need mode='fused'")
        self.stages = list(stages)
        self.gear = gear
        self.core = SchedulerCore([], cfg)
        self.batchers = [
            ContinuousBatcher(self.core, e.n_slots, min_tokens=min_tokens,
                              early_margin=early_margin) for e in stages]
        self.stream_mode = stream_mode
        self.beta = beta
        self.mode = mode
        self.spec_k = spec_k
        self.k_guard_slack = k_guard_slack
        self.spec_discarded = 0       # speculative tokens thrown away
        # pure observer (core/telemetry.py): request events and phases on
        # ``clock``, which no decision reads; occupancy gauges and the
        # spec-discard counter live in the shared registry
        self.telemetry = telemetry
        self.clock = clock
        self._traw = telemetry.raw.append if telemetry is not None else None
        for e in self.stages:
            e.telemetry, e.clock = telemetry, clock

    # ------------------------------------------------------------- serve

    def serve(self, requests: Sequence[TokenRequest]
              ) -> Dict[int, TokenResult]:
        """Run all requests through the cascade; returns {rid: result}."""
        waiting: List[Deque[Tuple[TokenRequest, TokenResult]]] = [
            deque() for _ in self.stages]
        act: List[List[_Active]] = [[] for _ in self.stages]
        results: Dict[int, TokenResult] = {}
        for r in requests:
            res = TokenResult(rid=r.rid)
            results[r.rid] = res
            waiting[0].append((r, res))
            if self._traw is not None:
                self._traw(("admit", self.clock(), r.rid, 0, 0, ""))

        step = 0
        while any(waiting) or any(act):
            for si, eng in enumerate(self.stages):
                # admission at the token boundary: prefill phase first
                self._admit(si, eng, waiting, act, step, custody=False)
                if not act[si]:
                    continue
                if self.mode == "reference":
                    self._step_reference(si, eng, waiting, act, step)
                else:
                    self._step_fused(si, eng, waiting, act, step)
            step += 1
        return results

    # ------------------------------------------------------ admit phase

    def _admit(self, si: int, eng: SlotEngine, waiting, act, step: int,
               custody: bool = True) -> None:
        """The boundary's joins at stage ``si``. With ``custody``, stage-0
        joiners enter the engine's custody here (telemetry's ``admit``);
        ``serve`` stamps that at queue entry instead."""
        k = self.batchers[si].admit(eng.n_active, len(waiting[si]))
        if not k:
            return
        pairs = [waiting[si].popleft() for _ in range(k)]
        with _phase(self.telemetry, self.clock, "engine.admit", si,
                    step) as ph:
            if ph is not None:
                rids = tuple(req.rid for req, _ in pairs)
                if custody and si == 0:
                    for rid in rids:
                        self._traw(("admit", ph.t0, rid, 0, 0, ""))
                self._traw(("fire", ph.t0, si, rids))
            self._join(si, eng, pairs, act, step)

    def _join(self, si: int, eng: SlotEngine, pairs, act, step: int
              ) -> None:
        if self.mode == "reference":
            joined = []
            for req, res in pairs:
                slot, logits = eng.prefill_into_slot(req.prompt)
                gap = float(np.asarray(top2_gap(logits[None, :]))[0])
                tok = int(np.argmax(logits))
                joined.append((req, res, slot, tok, gap))
        else:
            slots, toks, gaps = eng.prefill_batch(
                [req.prompt for req, _ in pairs])
            joined = [(req, res, slot, int(tok), float(gap))
                      for (req, res), slot, tok, gap
                      in zip(pairs, slots, toks, gaps)]
        for req, res, slot, tok, gap in joined:
            cert = StreamingCertainty(mode=self.stream_mode, beta=self.beta)
            cert.update(gap)
            res.tokens.append(tok)
            res.gaps.append(gap)
            if res.first_token_step < 0:
                res.first_token_step = step
            act[si].append(_Active(req, slot, tok, cert, res))
        if self.telemetry is not None:
            self.telemetry.registry.gauge(
                "kv_slots_active", model=eng.name).set(eng.n_active)

    # ----------------------------------------------------- decode phase

    def _leave(self, si: int, eng: SlotEngine, a: _Active, hop, waiting,
               act, step: int) -> None:
        eng.release(a.slot)
        act[si].remove(a)
        a.res.stage_gaps[si] = list(a.res.gaps)
        if getattr(hop, "next_stage", None) is not None:
            # escalate: prompt (never the cache) to next model
            a.res.hops += 1
            a.res.tokens.clear()
            a.res.gaps.clear()
            # TTFT re-stamps at the resolving stage (as in the token
            # DES): the user-visible stream restarts
            a.res.first_token_step = -1
            waiting[hop.next_stage].append((a.req, a.res))
            if self._traw is not None:
                self._traw(("escalate", self.clock(), a.req.rid, si))
        else:
            a.res.resolver = si
            a.res.done_step = step
            if self._traw is not None:
                self._traw(("close", self.clock(), a.req.rid, "completed"))
        if self.telemetry is not None:
            self.telemetry.registry.gauge(
                "kv_slots_active", model=eng.name).set(eng.n_active)

    def _step_reference(self, si: int, eng: SlotEngine, waiting, act,
                        step: int) -> None:
        """PR-7 loop: one host round-trip of (B, V) logits per step."""
        out = eng.decode({a.slot: a.next_token for a in act[si]})
        for a in act[si]:
            logits = out[a.slot]
            gap = float(np.asarray(top2_gap(logits[None, :]))[0])
            a.cert.update(gap)
            a.next_token = int(np.argmax(logits))
            a.res.tokens.append(a.next_token)
            a.res.gaps.append(gap)
        # token-boundary decisions (iterate over a copy: leaves mutate
        # the active list)
        for a in list(act[si]):
            hop = self.batchers[si].boundary_hop(
                si, a.cert.value, len(a.res.tokens), a.req.max_new,
                self.gear)
            if hop is not None:
                self._leave(si, eng, a, hop, waiting, act, step)

    def _choose_k(self, si: int, eng: SlotEngine, waiting, act) -> int:
        """The K-collapse rule. K > 1 only when (a) NOTHING is waiting at
        any stage — an admission can then never happen mid-scan, so
        admission decisions are bit-identical to single-stepping — and
        (b) no resident row is near a decision boundary. K is further
        capped so no row crosses its generation end or its slot capacity
        inside the scan."""
        if self.spec_k <= 1:
            return 1
        if any(len(w) for w in waiting):
            return 1
        k = self.spec_k
        for a in act[si]:
            k = min(k, a.req.max_new - len(a.res.tokens),
                    eng.max_len - int(eng.pos[a.slot]))
            if k <= 1:
                return 1
        for a in act[si]:
            if self.batchers[si].near_boundary(
                    si, a.cert.value, len(a.res.tokens), a.req.max_new,
                    self.gear, self.k_guard_slack):
                return 1
        return k

    def _step_fused(self, si: int, eng: SlotEngine, waiting, act,
                    step: int) -> None:
        """Device-resident loop: one executable call covers K decode
        steps; the host sees (K, B) token/gap/certainty traces and
        replays boundary decisions over them at the same token counts."""
        k = self._choose_k(si, eng, waiting, act)
        telem, clock = self.telemetry, self.clock
        with _phase(telem, clock, "engine.decode", si, step,
                    rows=len(act[si]), k=k):
            tok_t, gap_t, _cert_t = eng.decode_fused(
                k, mode=self.stream_mode, beta=self.beta)
            with _phase(telem, clock, "engine.decide") as ph:
                leaves = self._replay(si, act, tok_t, gap_t, k)
                for _, _, a, hop in leaves:
                    self._leave(si, eng, a, hop, waiting, act, step)
                if ph is not None:
                    ph.counts.update(leaves=len(leaves), escalations=sum(
                        getattr(hop, "next_stage", None) is not None
                        for _, _, _, hop in leaves))

    def _replay(self, si: int, act, tok_t: np.ndarray, gap_t: np.ndarray,
                k: int) -> List[Tuple[int, int, _Active, object]]:
        """Append each row's consumed tokens and gaps from the (k, B)
        traces; returns the rows that leave, in (token count, row) order —
        the order a single-step loop would have produced them in."""
        leaves: List[Tuple[int, int, _Active, object]] = []
        for order, a in enumerate(act[si]):
            start = len(a.res.tokens)
            used, hop = self.batchers[si].stream_trace_hop(
                si, a.cert, gap_t[:, a.slot], start, a.req.max_new,
                self.gear)
            for j in range(used):
                a.res.tokens.append(int(tok_t[j, a.slot]))
                a.res.gaps.append(float(gap_t[j, a.slot]))
            a.next_token = int(tok_t[used - 1, a.slot])
            if hop is not None:
                leaves.append((used, order, a, hop))
                self.spec_discarded += k - used
                if self.telemetry is not None and k > used:
                    self.telemetry.registry.counter(
                        "spec_discarded_tokens").inc(k - used)
        leaves.sort(key=lambda e: (e[0], e[1]))
        return leaves

    # ------------------------------------------------------------- stats

    def stats(self) -> Dict[str, object]:
        """Aggregated hot-loop instrumentation across all stages."""
        agg = {"prefill_calls": 0, "prefill_prompts": 0, "decode_calls": 0,
               "decode_steps": 0, "bytes_to_host": 0, "bytes_to_device": 0}
        for eng in self.stages:
            for key in agg:
                agg[key] += getattr(eng.stats, key)
        agg["spec_discarded"] = self.spec_discarded
        agg["compiles"] = {e.name: e.compile_counts() for e in self.stages}
        return agg
