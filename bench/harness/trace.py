"""Device trace of the measured window and its reduction to numbers.

``Tracer`` records the window with the JAX profiler (no Python tracer).
``extract`` reads the ``.xplane.pb`` into plain event arrays: device op
intervals, device program (XLA module) intervals, and host spans.
``reduce`` turns those into the per-layer inputs:

  busy_s      union of device op intervals inside the window, averaged
              over the chips traced
  window_s    length of the window (the benchmark's ``bench.window``
              span, or the traced extent when it is missing)
  programs    {jit name: (executions, device seconds)} for the names asked
  device_ops  the device operations that took most time
  idle_gaps   idle device time inside the window, attributed to the host
              span that overlaps each gap most (the shorter of equals)
  longest_gaps  the three longest single idle gaps: (host span, seconds,
              seconds from the window's start), where a host stall shows
"""
from __future__ import annotations

import glob
import gzip
import os
import shutil
from typing import NamedTuple

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
GAPS_ATTRIBUTED = 2000  # the longest gaps get a host span each; the rest are summed


class Events(NamedTuple):
    ops: list  # per chip: (names, start_ns, end_ns)
    modules: list  # per chip: (names, start_ns, end_ns)
    host: tuple  # (names, start_ns, end_ns)
    window: tuple  # (start_ns, end_ns)


class Tracer:
    """Start and stop the profiler into a fixed directory of the checkout."""

    def __init__(self, directory: str):
        self.directory = directory

    def start(self):
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=opts)

    def stop(self) -> str:
        import jax

        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"), recursive=True)
        if not paths:
            raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {self.directory}")
        return max(paths, key=os.path.getmtime)


def _arrays(events):
    names = [e.name for e in events]
    start = np.array([e.start_ns for e in events], np.float64)
    dur = np.array([e.duration_ns for e in events], np.float64)
    return names, start, start + dur


def _is_chip(name: str) -> bool:
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def extract(path: str) -> Events:
    """Read one xplane file (gzipped when its name ends in .gz) into
    ``Events``."""
    import jax

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    ops, modules = [], []
    host_ev = []
    for plane in pd.planes:
        if _is_chip(plane.name):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            ops.append(_arrays(lines.get(OPS_LINE, [])))
            modules.append(_arrays(lines.get(MODULES_LINE, [])))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host_ev.extend(e for e in ln.events if e.duration_ns > 0)
    host = _arrays(host_ev)
    win = [i for i, n in enumerate(host[0]) if n == WINDOW_SPAN]
    if win:
        window = (float(host[1][win[0]]), float(host[2][win[0]]))
    else:
        lo = [s.min() for _, s, _ in ops if len(s)]
        hi = [e.max() for _, _, e in ops if len(e)]
        window = (min(lo), max(hi)) if lo else (0.0, 0.0)
    return Events(ops, modules, host, window)


def _union(start, end, lo, hi) -> np.ndarray:
    """Merged (k, 2) intervals of [start, end) clipped to [lo, hi)."""
    s = np.clip(start, lo, hi)
    e = np.clip(end, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return np.zeros((0, 2))
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    new = np.concatenate([[True], s[1:] > e[:-1]])
    idx = np.flatnonzero(new)
    ends = np.concatenate([e[idx[1:] - 1], [e[-1]]])
    return np.stack([s[idx], ends], 1)


def _attribute(gaps: np.ndarray, host) -> list:
    """A label per gap: the host span overlapping it most, the shorter of
    equals; the window span itself does not count."""
    names, hs, he = host
    keep = np.array([n != WINDOW_SPAN for n in names], bool)
    names = [n for n, k in zip(names, keep) if k]
    hs, he = hs[keep], he[keep]
    if not len(hs):
        return ["no host span"] * len(gaps)
    labels = []
    dur = he - hs
    for chunk in np.array_split(gaps, max(1, len(gaps) // 64)):
        if not len(chunk):
            continue
        overlap = np.minimum(he, chunk[:, 1:2]) - np.maximum(hs, chunk[:, :1])  # (g, H)
        best = overlap.max(axis=1, keepdims=True)
        # the shorter of the events with the largest overlap
        score = np.where(overlap >= best, dur, np.inf)
        pick = np.argmin(score, axis=1)
        for b, i in zip(best[:, 0], pick):
            labels.append(names[i] if b > 0 else "no host span")
    return labels


def _op_labels(names, s, e, modules, idx) -> list:
    """"<op> in <program>" for the ops at ``idx``: the op's name up to its
    HLO text, and the device program whose interval holds it."""
    mnames, ms, me = modules
    order = np.argsort(ms, kind="stable")
    ms, me = ms[order], me[order]
    mnames = [mnames[i] for i in order]
    j = np.searchsorted(ms, s[idx], side="right") - 1
    out = []
    for i, k in zip(idx, j):
        op = names[i].split(" = ")[0].lstrip("%")
        prog = mnames[k].split("(")[0] if k >= 0 and me[k] >= e[i] else "?"
        out.append(f"{op} in {prog}")
    return out


def reduce(ev: Events, program_names=()) -> dict:
    lo, hi = ev.window
    window_s = (hi - lo) / 1e9
    busy, gaps_all, op_time = [], [], {}
    programs = {p: [0, 0.0] for p in program_names}
    for (names, s, e), (mnames, ms, me) in zip(ev.ops, ev.modules, strict=True):
        u = _union(s, e, lo, hi)
        busy.append(float(np.sum(u[:, 1] - u[:, 0])) / 1e9)
        edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
        gaps_all.append(edges[edges[:, 1] > edges[:, 0]])
        inside = np.flatnonzero((s >= lo) & (e <= hi))
        for i, n in zip(inside, _op_labels(names, s, e, (mnames, ms, me), inside)):
            op_time[n] = op_time.get(n, 0.0) + (e[i] - s[i]) / 1e9
        inside = (ms >= lo) & (me <= hi)
        for n, d in zip(np.asarray(mnames, object)[inside], (me - ms)[inside]):
            for p in program_names:
                if p in n:
                    programs[p][0] += 1
                    programs[p][1] += d / 1e9
    chips = max(len(busy), 1)
    gaps = np.concatenate(gaps_all) if gaps_all else np.zeros((0, 2))
    order = np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")
    top = gaps[order[:GAPS_ATTRIBUTED]]
    by_label: dict = {}
    labels = _attribute(top, ev.host)
    for label, (g0, g1) in zip(labels, top):
        by_label[label] = by_label.get(label, 0.0) + (g1 - g0) / 1e9
    longest = [[label, (g1 - g0) / 1e9, (g0 - lo) / 1e9] for label, (g0, g1) in zip(labels[:3], top[:3])]
    rest = float(np.sum(gaps[order[GAPS_ATTRIBUTED:], 1] - gaps[order[GAPS_ATTRIBUTED:], 0])) / 1e9
    if rest > 0:
        by_label["shorter gaps"] = by_label.get("shorter gaps", 0.0) + rest
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / chips,
        "programs": {p: (c, t / chips) for p, (c, t) in programs.items()},
        "device_ops": [[n, t / chips] for n, t in top_ops],
        "idle_gaps": [[n, t / chips] for n, t in top_gaps],
        "longest_gaps": longest,
    }


def program_ms(reduced: dict, program: str):
    """Mean device milliseconds per execution of one program, or None
    when the traced window holds none."""
    count, seconds = reduced["programs"].get(program, (0, 0.0))
    return seconds / count * 1e3 if count else None
