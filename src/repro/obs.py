"""Program spans and stage counters.

``span(name, **stats)`` marks a stretch of host work in the JAX
profiler's trace: ``with span("fit.steps", steps=150): ...``. It is a
``jax.profiler.TraceAnnotation``, so the event lands in the same trace
as the device ops, on the profiler's clock, and a trace reduction can
put each idle gap of the device down to the stage the host was running.
The profiler is the only switch: with no trace active a span records
nothing and costs about a microsecond to enter and leave.

Rules for placing one: at a layer boundary on the host, never inside a
jitted function (it would fire once, at trace time), never per SGD step
or per request (the device trace has the steps; per-request time is a
``Tally``), and never under the ``bench.`` prefix, which belongs to the
benchmark's own spans.

``Tally`` is the counter beside a span: count, sum and largest of a
stream of numbers, in constant memory, for reports of long-running
servers (``FrontDoor.report()["stages"]``).
"""
from __future__ import annotations

import jax


def span(name: str, **stats):
    """A host span named ``name`` carrying ``stats`` (ints or strings)."""
    return jax.profiler.TraceAnnotation(name, **stats)


class Tally:
    """Count, sum and largest of a stream of numbers, in constant memory."""

    __slots__ = ("count", "total", "max")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.max = 0.0

    def add(self, value: float) -> None:
        self.count += 1
        self.total += value
        if value > self.max:
            self.max = value

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def ms(self) -> dict:
        """A tally of durations in seconds as ``{count, mean_ms, max_ms}``."""
        return {"count": self.count, "mean_ms": self.mean * 1e3, "max_ms": self.max * 1e3}
