"""Inference engine: bucketed-batch jitted execution of one model.

XLA wants static shapes, so the engine pre-compiles one executable per
power-of-two batch bucket and pads incoming batches up to the bucket
(DESIGN.md §3.2 — the TPU adaptation of the paper's dynamic batching).
``profile_engine`` measures wall-clock batch runtimes — the ModelProfile the
gear planner and simulator consume for real models; it is a thin wrapper
over the unified ``repro.core.execution`` profile entry point.
"""
from __future__ import annotations

import threading
from collections import Counter
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.execution import EngineBackend, profile_backend
from repro.core.profiles import ModelProfile, ValidationRecord


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class InferenceEngine:
    """Wraps apply_fn(params, tokens) -> scores with bucketed compilation.

    A call given a ``device`` runs there, on a copy of the params placed on
    that device at its first use, so the replicas of one model can run on
    different chips. ``batches_by_device`` counts executed batches by the
    device their outputs landed on."""

    def __init__(self, name: str, apply_fn: Callable, params,
                 buckets: Sequence[int] = (1, 2, 4, 8, 16, 32, 64, 128)):
        self.name = name
        self.params = params
        self.buckets = tuple(sorted(buckets))
        self._fn = jax.jit(apply_fn)
        self._placed = {}
        self._lock = threading.Lock()
        self.batches_by_device: Counter = Counter()

    def params_on(self, device=None):
        """The params, on ``device`` (None: as given)."""
        if device is None:
            return self.params
        with self._lock:
            if device not in self._placed:
                self._placed[device] = jax.device_put(self.params, device)
            return self._placed[device]

    def warmup(self, seq_len: int, device=None) -> None:
        params = self.params_on(device)
        for b in self.buckets:
            tok = jax.device_put(jnp.zeros((b, seq_len), jnp.int32), device)
            jax.block_until_ready(self._fn(params, tok))

    def infer(self, tokens: np.ndarray, device=None) -> np.ndarray:
        """tokens (n, L) -> scores (n, C); pads to the bucket internally."""
        n = tokens.shape[0]
        b = _bucket(n, self.buckets)
        if n > self.buckets[-1]:
            # split oversized batches
            out = [self.infer(tokens[i:i + self.buckets[-1]], device)
                   for i in range(0, n, self.buckets[-1])]
            return np.concatenate(out)
        if b != n:
            pad = np.zeros((b - n,) + tokens.shape[1:], tokens.dtype)
            tokens = np.concatenate([tokens, pad])
        scores = self._fn(self.params_on(device),
                          jax.device_put(tokens, device))
        (out_dev,) = scores.devices()
        with self._lock:
            self.batches_by_device[out_dev] += 1
        return np.asarray(jax.block_until_ready(scores))[:n]


def profile_engine(engine: InferenceEngine, seq_len: int,
                   batch_sizes: Sequence[int] = (1, 2, 4, 8, 16, 32, 64),
                   repeats: int = 5, mem_bytes: Optional[float] = None,
                   validation: Optional[ValidationRecord] = None
                   ) -> ModelProfile:
    """Measure wall-clock batch runtimes (median of ``repeats``).

    Thin wrapper over ``profile_backend(EngineBackend(...))`` — the single
    measurement implementation — kept for call-site convenience."""
    backend = EngineBackend({engine.name: engine})
    return profile_backend(backend, engine.name, batch_sizes=batch_sizes,
                           seq_len=seq_len, repeats=repeats,
                           mem_bytes=mem_bytes, validation=validation)
