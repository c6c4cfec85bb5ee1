"""Program spans and stage counters (``repro.obs``).

One tiny ``api.refit`` + ``.cache`` and one tiny ``FrontDoor`` session of
a few batching windows run under the JAX profiler (CPU, trace written
into a temporary directory). The trace must hold every span the program
places at its layer boundaries, with its stats, nested where the stages
nest; the span counts must agree with ``FrontDoor.report()``'s own
counters; and the benchmark's span reader (``bench/harness/spans.py``,
loaded by path) must read the same counts from the same file.
"""
import asyncio
import glob
import importlib.util
import os
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import api
from repro.api import frontdoor
from repro.data.spatial import e3sm_like_field
from repro.obs import Tally

BENCH = Path(__file__).resolve().parent.parent / "bench"

# every program span -> the stats it carries
SPANS = {
    "frontdoor.dispatch": {"batch", "rows", "requests"},
    "blend.corners": set(),
    "blend.transfer": set(),
    "blend.launch": set(),
    "frontdoor.collect": {"batch"},
    "server.wait": set(),
    "server.fetch": set(),
    "frontdoor.demux": {"batch"},
    "fit.partition": set(),
    "fit.build": set(),
    "fit.steps": {"steps"},
    "fit.cache": set(),
}
WAVES, PER_WAVE = 3, 4  # FrontDoor windows, concurrent requests in each
REFIT_STEPS = 10


def _points(grid, n, rng):
    lo = (grid.x_edges[0], grid.y_edges[0])
    hi = (grid.x_edges[-1], grid.y_edges[-1])
    return rng.uniform(lo, hi, (n, 2)).astype(np.float32)


async def _waves(server, rng):
    """WAVES batching windows of PER_WAVE concurrent requests each."""
    async with api.FrontDoor(server, api.FrontDoorConfig(max_wait_ms=2.0)) as fd:
        for _ in range(WAVES):
            reqs = [_points(server.fitted.grid, int(rng.integers(1, 9)), rng)
                    for _ in range(PER_WAVE)]
            await asyncio.gather(*(fd.submit(q) for q in reqs))
    return fd.report()


def _program_spans(path):
    """[(name, start_ns, end_ns, stats, thread)] of the program's spans
    on the trace's host planes."""
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in SPANS:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                {key: v for key, v in e.stats}, (plane.name, k)))
    return out


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    directory = tmp_path_factory.mktemp("trace")
    fitted = api.fit(api.FitConfig(grid=2, m=4, train_iters=20, seed=0),
                     e3sm_like_field(n=500, seed=0))
    server = api.Server(fitted)
    rng = np.random.default_rng(0)
    asyncio.run(_waves(server, np.random.default_rng(1)))  # compile off the trace
    jax.profiler.start_trace(str(directory))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            new = api.refit(fitted, e3sm_like_field(n=500, seed=1),
                            api.RefitConfig(train_iters=REFIT_STEPS))
            jax.block_until_ready(new.cache)
            report = asyncio.run(_waves(server, rng))
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    return {"path": path, "events": _program_spans(path), "report": report, "refit": new}


def _named(session, name):
    return [e for e in session["events"] if e[0] == name]


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_is_recorded_with_its_stats(session, name):
    found = _named(session, name)
    assert found, f"no {name} span in the trace"
    for _, start, end, stats, _ in found:
        assert end >= start
        assert set(stats) == SPANS[name]
    if name == "fit.steps":
        assert [e[3]["steps"] for e in found] == [REFIT_STEPS]
    if name == "frontdoor.dispatch":
        batches = sorted(e[3]["batch"] for e in found)
        assert batches == list(range(len(found)))
        assert sum(e[3]["requests"] for e in found) == WAVES * PER_WAVE


@pytest.mark.parametrize("inner, outer", [("blend.", "frontdoor.dispatch"),
                                          ("server.", "frontdoor.collect")])
def test_spans_nest(session, inner, outer):
    parents = _named(session, outer)
    children = [e for e in session["events"] if e[0].startswith(inner)]
    assert children
    for name, start, end, _, thread in children:
        assert any(t == thread and s <= start and end <= e for _, s, e, _, t in parents), name


def test_counts_agree_with_the_report(session):
    rep = session["report"]
    dispatched = len(_named(session, "frontdoor.dispatch"))
    assert dispatched == rep["batches"]["count"] == rep["stages"]["dispatch"]["count"]
    assert len(_named(session, "frontdoor.collect")) == rep["stages"]["collect"]["count"]
    assert len(_named(session, "frontdoor.demux")) == rep["stages"]["demux"]["count"]
    assert rep["stages"]["collect"]["count"] == dispatched
    rows = sum(e[3]["rows"] for e in _named(session, "frontdoor.dispatch"))
    assert rows == rep["batches"]["rows_total"]


def test_queue_wait_counts_every_request(session):
    rep = session["report"]
    wait = rep["stages"]["queue_wait"]
    assert wait["count"] == rep["requests"]["completed"] == WAVES * PER_WAVE
    for stage in rep["stages"].values():
        assert set(stage) == {"count", "mean_ms", "max_ms"}
        assert 0 <= stage["mean_ms"] <= stage["max_ms"]


def test_bench_reader_counts_the_same_spans(session, monkeypatch):
    monkeypatch.syspath_prepend(str(BENCH))  # for its ``harness.trace`` import
    before = set(sys.modules)
    try:
        spec = importlib.util.spec_from_file_location("bench_spans",
                                                      BENCH / "harness" / "spans.py")
        spans = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(spans)
        read = spans.of(session["path"])
    finally:
        for name in set(sys.modules) - before:
            if name.split(".")[0] == "harness":
                del sys.modules[name]
    for name in SPANS:
        count, ms = read[name]
        assert count == len(_named(session, name)), name
        assert ms > 0


def test_refit_seconds_cover_the_phases(session):
    new = session["refit"]
    phases = new.refit_phases
    assert set(phases) == {"partition_s", "build_s", "steps_s"}
    assert all(v >= 0 for v in phases.values())
    assert new.refit_seconds == pytest.approx(sum(phases.values()))
    assert new.train_phases == phases


def test_lifecycle_reports_the_refit_phases(session):
    new = session["refit"]
    server = api.Server(new)
    (version,) = server.lifecycle()["versions"]
    assert version["refit_s"] == pytest.approx(new.refit_seconds)
    assert version["refit_phases"] == new.refit_phases


def test_frontdoor_memory_stays_bounded(session, monkeypatch):
    """The latency record is a ring and the batch counters running sums:
    more requests than the ring holds leave the report complete."""
    monkeypatch.setattr(frontdoor, "LATENCY_RING", 5)
    server = api.Server(session["refit"])
    rng = np.random.default_rng(2)

    async def main():
        async with api.FrontDoor(server, api.FrontDoorConfig(max_wait_ms=1.0)) as fd:
            for _ in range(12):
                await fd.submit(_points(server.fitted.grid, 1, rng))
        return fd, fd.report()

    fd, rep = asyncio.run(main())
    assert len(fd._latency_s) == 5
    assert rep["latency_ms"]["p50_ms"] > 0
    assert rep["requests"]["completed"] == 12
    assert rep["batches"]["count"] == 12 and rep["batches"]["rows_total"] == 12
    assert rep["batches"]["rows_per_batch_max"] == 1
    assert rep["stages"]["queue_wait"]["count"] == 12


def test_tally():
    t = Tally()
    assert t.ms() == {"count": 0, "mean_ms": 0.0, "max_ms": 0.0}
    for v in (0.002, 0.001, 0.003):
        t.add(v)
    assert t.count == 3 and t.total == pytest.approx(0.006) and t.max == 0.003
    assert t.ms() == {"count": 3, "mean_ms": pytest.approx(2.0), "max_ms": pytest.approx(3.0)}
