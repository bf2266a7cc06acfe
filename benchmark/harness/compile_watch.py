"""What jax itself reports about compilation (copied from ``chip_smoke.py``).

Persistent-cache hits and misses, the time of every backend compile (so that
one landing inside the measured window can be proved absent), and the stage
totals over all the run's functions: jax trace, lowering, backend compile or
cache load.
"""

from __future__ import annotations

import time


class CompileWatch:
    def __init__(self) -> None:
        import jax.monitoring

        self.cache_hits = 0
        self.cache_misses = 0
        self.compile_times: list[float] = []
        self.stage_s: dict[str, float] = {}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event: str, duration: float, **_: object) -> None:
        if not event.startswith("/jax/core/compile/"):
            return
        stage = event.rsplit("/", 1)[1].removesuffix("_duration")
        self.stage_s[stage] = self.stage_s.get(stage, 0.0) + duration
        if stage == "backend_compile":
            self.compile_times.append(time.perf_counter())

    def compiles_after(self, t: float) -> int:
        """Backend compiles (cache loads included) that ended after ``t``."""
        return sum(c > t for c in self.compile_times)
