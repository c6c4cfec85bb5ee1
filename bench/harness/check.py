"""The numbers that decide ``correct``, and their limits.

Every number is a gap between what the timed path produced and what the
plain reference (``configs/psvgp_reference.py``) computes from the same
seed-made inputs; a run is correct when every number that has a limit in
``limits/<cell>.json`` lies at or under it. Readings and the reasons for
each limit are in PERF.md.
"""
from __future__ import annotations

import json
import os

import numpy as np


def answer_gaps(got_mean, got_var, ref_mean, ref_var) -> dict:
    """Gaps of served answers against the reference's over all points:
    the root mean square of got - ref over that of ref, for the mean
    (``mean_rms``) and the variance (``var_rms``)."""
    out = {}
    for name, got, want in (("mean", got_mean, ref_mean), ("var", got_var, ref_var)):
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        if got.shape != want.shape or not np.isfinite(got).all():
            out[f"{name}_rms"] = float("inf")
            continue
        d = got - want
        out[f"{name}_rms"] = float(np.sqrt(np.mean(d * d) / np.mean(want * want)))
    return out


def leaf_norm_gaps(got: dict, want: dict, *, keep: set | None = None) -> dict:
    """Per leaf, | |got_leaf| - |want_leaf| | / max(|want_leaf|, median
    leaf norm of want): the gap between norms, not the norm of the
    difference. Leaves outside ``keep`` (when given) are left out; a
    leaf of the wrong shape or not finite reads inf."""
    ref_norms = {k: float(np.linalg.norm(np.asarray(want[k], np.float64))) for k in want}
    med = float(np.median(list(ref_norms.values())))
    out = {}
    for k in want:
        if keep is not None and k not in keep:
            continue
        g = np.asarray(got[k], np.float64)
        if g.shape != np.shape(want[k]) or not np.isfinite(g).all():
            out[k] = float("inf")
            continue
        out[k] = abs(float(np.linalg.norm(g)) - ref_norms[k]) / max(ref_norms[k], med)
    return out


def moved_leaves(ref_grad: dict) -> set:
    """Leaves whose reference gradient norm is at least a thousandth of
    the median leaf's: the others move under Adam by round-off alone."""
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in ref_grad.items()}
    med = float(np.median(list(norms.values())))
    return {k for k, n in norms.items() if n >= 1e-3 * med}


def load_limits(bench_dir: str, cell: str) -> dict:
    with open(os.path.join(bench_dir, "limits", f"{cell}.json")) as f:
        return json.load(f)["limits"]


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers with limits.
    A number that is missing or not finite fails."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name, float("inf"))
        value = float(value) if np.isfinite(value) else 1e300
        checks[name] = {"value": value, "limit": float(limit)}
        ok = ok and value <= float(limit)
    return ok, checks
