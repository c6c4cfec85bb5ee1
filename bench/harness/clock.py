"""Compile time and compile counts from jax's monitoring events.

Copied from the program's ``chip_smoke.CompileClock`` and extended with
a count, so that compiles inside the measured window show as a number.
A persistent-cache hit still fires a backend-compile event (the
executable is loaded), so ``compiles`` counts every program jax had to
produce, from the compiler or from the cache."""
from __future__ import annotations


class CompileClock:
    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits

    def since(self, mark) -> dict:
        return {
            "compile_s": self.seconds - mark[0],
            "compiles": self.compiles - mark[1],
            "cache_hits": self.cache_hits - mark[2],
        }


class GcClock:
    """Pauses of Python's garbage collector, by generation, from
    ``gc.callbacks``: a pause stops every thread of the process, the
    load generator's and the server's alike."""

    def __init__(self):
        import gc
        import time

        self._now = time.perf_counter
        self._t0 = None
        self.pauses = {0: [], 1: [], 2: []}
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self._t0 = self._now()
        elif self._t0 is not None:
            self.pauses[info["generation"]].append(self._now() - self._t0)
            self._t0 = None

    def reset(self) -> None:
        for v in self.pauses.values():
            v.clear()

    def summary(self) -> dict:
        return {f"gen{g}": {"count": len(v), "max_ms": 1e3 * max(v, default=0.0),
                            "total_ms": 1e3 * sum(v)} for g, v in self.pauses.items()}
