"""The trace reduction: on hand-made events, and on a small trace
recorded on a TPU v5e (``data/``: a few executions of the blend program
and of the SGD step under the benchmark's host spans)."""
import os

import numpy as np
import pytest
import tiny  # noqa: F401  (puts the bench directory on the path)

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _arr(names, spans):
    s = np.array([a for a, _ in spans], float)
    e = np.array([b for _, b in spans], float)
    return list(names), s, e


def test_union_merges_and_clips():
    u = trace._union(np.array([0., 5., 2., 20.]), np.array([3., 8., 4., 30.]), 1.0, 25.0)
    assert u.tolist() == [[1.0, 4.0], [5.0, 8.0], [20.0, 25.0]]


def test_reduce_by_hand():
    # window 0..100 ns; ops busy 10..30 and 50..60 (one op nested in
    # another); two runs of "jit__blend_eval" and one other module; host
    # spans: the window, a long "bench.refit" over the first gap and a
    # shorter "bench.cache" inside the second
    ops = _arr(["a", "b", "c"], [(10, 30), (15, 20), (50, 60)])
    mods = _arr(["jit__blend_eval(1)", "jit__blend_eval(1)", "jit_other"], [(10, 30), (50, 55), (55, 60)])
    host = _arr(["bench.window", "bench.refit", "bench.cache", "bench.refit"],
                [(0, 100), (0, 12), (35, 45), (30, 55)])
    ev = trace.Events([ops], [mods], host, (0.0, 100.0))
    r = trace.reduce(ev, ["_blend_eval"])
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
    count, seconds = r["programs"]["_blend_eval"]
    assert count == 2 and seconds == pytest.approx(25e-9)
    assert trace.program_ms(r, "_blend_eval") == pytest.approx(12.5e-9 * 1e3)
    assert r["device_ops"][0] == ["a in jit__blend_eval", pytest.approx(20e-9)]
    gaps = dict((n, t) for n, t in r["idle_gaps"])
    # gaps: 0..10 (bench.refit), 30..50 (bench.cache covers 10 of it, the
    # second bench.refit all 20: the larger overlap wins), 60..100 (none)
    assert gaps["bench.refit"] == pytest.approx(10e-9 + 20e-9)
    assert gaps["no host span"] == pytest.approx(40e-9)


def _recorded():
    return sorted(os.path.join(DATA, f) for f in os.listdir(DATA) if ".xplane.pb" in f)


@pytest.mark.parametrize("path", _recorded())
def test_recorded_chip_trace(path):
    ev = trace.extract(path)
    assert len(ev.ops) == 1, "one TPU plane"
    r = trace.reduce(ev, ["_blend_eval", "train_step_gather"])
    assert 0 < r["busy_s"] < r["window_s"]
    counts = {p: c for p, (c, _) in r["programs"].items()}
    assert sum(counts.values()) > 0
    for p, (c, t) in r["programs"].items():
        assert (c == 0) == (t == 0)
    assert r["device_ops"] and r["idle_gaps"]
    # the recording: two blend executions and a two-step refit, whose
    # host spans hold most of the idle time
    assert counts == {"_blend_eval": 2, "train_step_gather": 2}
    assert r["idle_gaps"][0][0] == "bench.refit"
    assert all(" in jit_" in name for name, _ in r["device_ops"])
