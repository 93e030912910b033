"""Compiles seen by JAX's monitoring events: backend compiles by jitted
function, and persistent-cache hits. The harness reads the counts at the
window's open and close; the difference is the compiles inside it."""
from __future__ import annotations

from collections import defaultdict


class CompileLog:
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

    def compiles(self) -> int:
        return sum(self.count.values())

    def total_seconds(self) -> float:
        return sum(self.seconds.values())

    def top(self, n: int = 6) -> list:
        funs = sorted(self.seconds, key=self.seconds.get, reverse=True)
        return [(f, round(self.seconds[f], 3), self.count[f])
                for f in funs[:n]]
