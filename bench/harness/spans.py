"""The program's own host spans in the traced window.

The program marks its stages with ``repro.obs.span`` (a
``jax.profiler.TraceAnnotation``), so they land in the trace beside the
device ops. ``of(path)`` reads the trace's host events
(``trace.extract``), clips each to ``bench.window`` and returns, per span
name, how many lie in the window and their mean duration there; a run
reads its trace once (``mean_ms`` keeps the result on the run). A
program without a span of that name (an older commit) gives none.
"""
from __future__ import annotations

import numpy as np


def of(path: str) -> dict:
    """{span name: (count, mean ms)} of the host events inside the window
    of the trace at ``path``."""
    from harness import trace

    ev = trace.extract(path)
    names, start, end = ev.host
    lo, hi = ev.window
    dur = np.clip(end, lo, hi) - np.clip(start, lo, hi)
    keep = np.flatnonzero(dur > 0)
    if not keep.size:
        return {}
    uniq, inv = np.unique(np.asarray(names, object)[keep], return_inverse=True)
    counts = np.bincount(inv)
    ms = np.bincount(inv, weights=dur[keep]) / 1e6
    return {str(n): (int(c), float(t / c)) for n, c, t in zip(uniq, counts, ms)}


def mean_ms(run, name: str):
    """Mean milliseconds of span ``name`` in a traced run's window, or None
    when the window holds none."""
    if getattr(run, "program_spans", None) is None:
        run.program_spans = of(run._trace_path)
    count, ms = run.program_spans.get(name, (0, 0.0))
    return ms if count else None
