"""Pass 4 — compiled cost-model gates.

The paper's in-situ claims are quantitative, not just structural: the
sharded cache must occupy O(1/P) bytes per device, and blend work must be
linear in the query block. The HLO pass (pass 1) proves the *shape* of the
program; this pass proves its *cost*, straight from the compiler — no
execution, no benchmark:

  * every distinct device program is AOT-COMPILED at 2-3 scale points per
    axis (grid side for the sharded program, q_max / n_queries for the
    query axis);
  * XLA's ``compiled.cost_analysis()`` (flops, bytes accessed) and
    ``compiled.memory_analysis()`` (argument / output / peak-temp bytes)
    are recorded per point — for an SPMD program these are PER-DEVICE
    numbers, which is exactly what makes the 1/P claim checkable: a
    correctly sharded cache gives a FLAT per-device curve as the mesh
    grows, a replicated one a growing curve;
  * log-log least-squares exponents are fitted per (metric, axis) and
    checked against the declarative budgets in
    ``invariants.COST_BUDGETS`` (COST-FLOP-SUPERLINEAR, COST-MEM-SCALING,
    COST-BUDGET);
  * every point is also diffed against the checked-in baseline
    (``benchmarks/baselines/analysis_costs.json``) so a cost regression
    gates CI the way ``check_bench_regression.py`` gates p50 — but at
    compile time, deterministically. ``--update-baselines`` refreshes the
    file after an intentional change.

Kernel-lane caveat, stated rather than silently capped: on a CPU host the
pallas/fused lanes run interpret-mode (host callbacks), which makes XLA's
cost model meaningless for them — those lanes are recorded as skipped
with this reason, and the ref program bounds the math they implement.

Measurement (jax-touching ``compile_*`` / ``measure_programs``) is kept
separate from judgment (pure ``fit_exponent`` / ``check_*``), so the
gating logic is unit-testable without a mesh.
"""
from __future__ import annotations

import json
import math
import os
import time

from repro.analysis import Finding
from repro.analysis import invariants as inv

# Fixed scale points — independent of the CLI's --grid/--q-max probes so
# the checked-in budgets and baselines always mean the same program.
M = 8
SHARDED_GRID_SIDES = (2, 3, 4)  # P = 4, 9, 16 devices, at q_max = ANCHOR_Q
SHARDED_Q_POINTS = (32, 64, 128)  # at grid side ANCHOR_GRID
ANCHOR_GRID = 4
ANCHOR_Q = 64
REPLICATED_N_POINTS = (128, 256, 512)
REQUIRED_DEVICES = max(s * s for s in SHARDED_GRID_SIDES)

DEFAULT_BASELINE = os.path.join("benchmarks", "baselines", "analysis_costs.json")
# deterministic compiler stats still move across compiler versions; a
# quarter is far above that noise and far below any real regression
DRIFT_TOLERANCE = 1.25

METRICS = ("flops", "bytes_accessed", "arg_bytes", "out_bytes", "temp_bytes")


# --------------------------------------------------------------------------
# Measurement (jax-touching; imports deferred like hlo.py)
# --------------------------------------------------------------------------


def extract(compiled) -> dict:
    """Flatten one compiled program's cost + memory stats to a JSON row."""
    ca = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "arg_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "out_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
    }


def compile_sharded(grid_side: int, q_max: int, *, m: int = M, backend: str = "ref"):
    """AOT-compile the sharded blend on a ``grid_side**2``-device mesh."""
    import jax
    import jax.numpy as jnp

    from repro.analysis import hlo
    from repro.gp.covariances import make_covariance
    from repro.launch import serve_sharded as ss

    grid = hlo.probe_grid(grid_side)
    cache = hlo.abstract_cache(grid.num_partitions, m)
    mesh = ss.mesh_for_grid(grid)
    blend_fn = ss.make_sharded_blend(
        mesh, mesh.axis_names, grid, make_covariance("rbf"), cache, backend=backend
    )
    P = grid.num_partitions

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    return blend_fn.lower(
        cache,
        f32(P, 9, q_max, 2),
        jax.ShapeDtypeStruct((P, q_max, 4), jnp.int32),
        f32(P, q_max, 4),
    ).compile()


def compile_replicated(n_queries: int, *, m: int = M, grid_side: int = ANCHOR_GRID):
    """AOT-compile the replicated blend jit (mesh-free)."""
    import jax
    import jax.numpy as jnp

    from repro.analysis import hlo
    from repro.core import blend
    from repro.gp.covariances import make_covariance

    grid = hlo.probe_grid(grid_side)
    cache = hlo.abstract_cache(grid.num_partitions, m)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    return blend._blend_eval.lower(
        cache,
        make_covariance("rbf"),
        f32(n_queries, 2),
        jax.ShapeDtypeStruct((n_queries, 4), jnp.int64),
        f32(n_queries, 4),
    ).compile()


def measure_programs(*, m: int = M) -> dict:
    """Compile every ref program at its scale points; return per-program
    ``{"points": {label: metrics}, "axes": {axis: {label: value}}}``."""
    sharded_points, sharded_axes = {}, {"devices": {}, "q_max": {}}
    for side in SHARDED_GRID_SIDES:
        label = f"grid={side}/q={ANCHOR_Q}"
        sharded_points[label] = extract(compile_sharded(side, ANCHOR_Q, m=m))
        sharded_axes["devices"][label] = side * side
    for q in SHARDED_Q_POINTS:
        label = f"grid={ANCHOR_GRID}/q={q}"
        if label not in sharded_points:
            sharded_points[label] = extract(compile_sharded(ANCHOR_GRID, q, m=m))
        sharded_axes["q_max"][label] = q

    repl_points, repl_axes = {}, {"n_queries": {}}
    for n in REPLICATED_N_POINTS:
        label = f"n={n}"
        repl_points[label] = extract(compile_replicated(n, m=m))
        repl_axes["n_queries"][label] = n

    return {
        "replicated-blend/ref": {"points": repl_points, "axes": repl_axes},
        "sharded-blend/ref": {"points": sharded_points, "axes": sharded_axes},
    }


# --------------------------------------------------------------------------
# Judgment (pure; unit-testable without jax)
# --------------------------------------------------------------------------


def fit_exponent(xs, ys) -> float:
    """Least-squares slope of log(y) on log(x) — the scaling exponent."""
    if len(xs) != len(ys) or len(xs) < 2:
        raise ValueError("need >= 2 (x, y) points to fit an exponent")
    lx = [math.log(float(x)) for x in xs]
    ly = [math.log(max(float(y), 1e-12)) for y in ys]
    n = len(lx)
    mx, my = sum(lx) / n, sum(ly) / n
    den = sum((a - mx) ** 2 for a in lx)
    if den == 0.0:
        raise ValueError("scale points must differ on the x axis")
    return sum((a - mx) * (b - my) for a, b in zip(lx, ly)) / den


def compute_exponents(record: dict) -> dict:
    """Fitted exponent of every metric along every axis of one program's
    record: ``{"flops_vs_q_max": 1.0, "arg_bytes_vs_devices": 0.0, ...}``."""
    out = {}
    for axis, labels in record["axes"].items():
        xs = [labels[lab] for lab in labels]
        for metric in METRICS:
            ys = [record["points"][lab][metric] for lab in labels]
            out[f"{metric}_vs_{axis}"] = round(fit_exponent(xs, ys), 4)
    return out


def check_budget(name: str, record: dict, budget: "inv.CostBudget") -> list:
    """Apply one program's declarative cost budget to its measured record."""
    exps = record["exponents"]
    where = f"program:{name}"
    findings = []

    flop_key = f"flops_vs_{budget.scale_axis}"
    if exps.get(flop_key, 0.0) > budget.max_flop_exponent:
        findings.append(
            Finding(
                "costs",
                "COST-FLOP-SUPERLINEAR",
                where,
                f"flops scale as {budget.scale_axis}^{exps[flop_key]:.2f}, "
                f"budget is ^{budget.max_flop_exponent} — a quadratic "
                "(pairwise) term crept into the blend",
            )
        )
    if budget.max_device_exponent is not None:
        for metric in ("arg_bytes", "flops"):
            key = f"{metric}_vs_devices"
            if exps.get(key, 0.0) > budget.max_device_exponent:
                findings.append(
                    Finding(
                        "costs",
                        "COST-MEM-SCALING",
                        where,
                        f"per-device {metric} scale as devices^{exps[key]:.2f}, "
                        f"budget is ^{budget.max_device_exponent} — per-device "
                        "state/work must stay FLAT as the mesh grows (the 1/P "
                        "residency claim; a replicated cache in the in_specs "
                        "looks exactly like this)",
                    )
                )
    anchor = record["points"].get(budget.anchor)
    if anchor is None:
        findings.append(
            Finding(
                "costs",
                "COST-BUDGET",
                where,
                f"anchor point {budget.anchor!r} missing from the measured "
                "scale points — the budget manifest and the pass disagree",
            )
        )
        return findings
    for metric, ceiling in (
        ("flops", budget.max_flops),
        ("bytes_accessed", budget.max_bytes_accessed),
        ("arg_bytes", budget.max_arg_bytes),
        ("temp_bytes", budget.max_temp_bytes),
    ):
        if anchor[metric] > ceiling:
            findings.append(
                Finding(
                    "costs",
                    "COST-BUDGET",
                    where,
                    f"{metric} = {anchor[metric]:.0f} at {budget.anchor} "
                    f"exceeds the absolute ceiling {ceiling:.0f}",
                )
            )
    return findings


def check_baseline(name: str, record: dict, baseline_record: dict | None,
                   *, tolerance: float = DRIFT_TOLERANCE) -> list:
    """Diff one program's fresh points against the checked-in baseline.

    Increases beyond ``tolerance`` gate (COST-BASELINE-DRIFT); a point or
    metric the baseline has never seen gates too (COST-BASELINE-MISSING —
    run ``--update-baselines`` after an intentional change). Decreases
    never gate: a cheaper program only deserves a baseline refresh.
    """
    where = f"program:{name}"
    if baseline_record is None:
        return [
            Finding(
                "costs",
                "COST-BASELINE-MISSING",
                where,
                "no baseline for this program — run "
                "`python -m repro.analysis --passes costs --update-baselines` "
                "and commit benchmarks/baselines/analysis_costs.json",
            )
        ]
    findings = []
    base_points = baseline_record.get("points", {})
    for label, metrics in record["points"].items():
        base = base_points.get(label)
        if base is None:
            findings.append(
                Finding(
                    "costs",
                    "COST-BASELINE-MISSING",
                    where,
                    f"scale point {label!r} has no baseline — run "
                    "--update-baselines after an intentional change",
                )
            )
            continue
        for metric in METRICS:
            fresh, ref = float(metrics[metric]), float(base.get(metric, 0.0))
            if fresh > ref * tolerance and fresh - ref > 256:
                findings.append(
                    Finding(
                        "costs",
                        "COST-BASELINE-DRIFT",
                        where,
                        f"{metric} at {label}: {fresh:.0f} vs baseline "
                        f"{ref:.0f} (> {tolerance:.2f}x) — a compiled-cost "
                        "regression; if intentional, refresh with "
                        "--update-baselines",
                    )
                )
    return findings


def lane_cost_records(programs: dict) -> list:
    """Map every serving lane onto its program's cost record (or the
    explicit reason it has none) — the per-lane view ANALYSIS.json ships."""
    records = []
    for lane in inv.LANES:
        name = "/".join(lane.program_key)
        if name in programs:
            rec = programs[name]
            records.append(
                {
                    "lane": lane.name,
                    "program": name,
                    "anchor": inv.COST_BUDGETS[lane.program].anchor,
                    "anchor_cost": rec["points"].get(
                        inv.COST_BUDGETS[lane.program].anchor
                    ),
                    "exponents": rec["exponents"],
                }
            )
        else:
            records.append(
                {
                    "lane": lane.name,
                    "program": name,
                    "skipped": (
                        "kernel lane not cost-modeled: pallas runs "
                        "interpret-mode on this host (host callbacks make "
                        "XLA cost_analysis meaningless); the ref program "
                        "bounds the same math"
                    ),
                }
            )
    return records


# --------------------------------------------------------------------------
# The pass
# --------------------------------------------------------------------------


def load_baseline(path: str) -> dict | None:
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def write_baseline(path: str, programs: dict, *, platform: str) -> None:
    import jax

    payload = {
        "_meta": {
            "platform": platform,
            "jax": jax.__version__,
            "m": M,
            "tolerance": DRIFT_TOLERANCE,
            "note": "deterministic per-device compiled-program costs; "
            "refresh with `python -m repro.analysis --passes costs "
            "--update-baselines` after an intentional change",
        },
        "programs": {
            name: {"points": rec["points"], "exponents": rec["exponents"]}
            for name, rec in programs.items()
        },
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")


def run(
    *,
    m: int = M,
    baseline_path: str = DEFAULT_BASELINE,
    update_baselines: bool = False,
) -> tuple:
    """The full pass. Returns (findings, report)."""
    import jax

    t0 = time.time()
    platform = jax.default_backend()
    findings: list = []
    programs = measure_programs(m=m)
    for name, rec in programs.items():
        rec["exponents"] = compute_exponents(rec)
        findings.extend(check_budget(name, rec, inv.COST_BUDGETS[name.split("/")[0]]))

    baseline = load_baseline(baseline_path)
    baseline_checked = False
    if update_baselines:
        write_baseline(baseline_path, programs, platform=platform)
    elif baseline is not None and baseline.get("_meta", {}).get("platform") != platform:
        # a baseline measured on another platform gates nothing here;
        # stated rather than silently skipped
        pass
    else:
        baseline_checked = True
        base_programs = (baseline or {}).get("programs", {})
        for name, rec in programs.items():
            findings.extend(check_baseline(name, rec, base_programs.get(name)))

    report = {
        "programs": programs,
        "lanes": lane_cost_records(programs),
        "budgets": {
            name: dataclass_dict(b) for name, b in sorted(inv.COST_BUDGETS.items())
        },
        "baseline_path": baseline_path,
        "baseline_checked": baseline_checked,
        "baseline_updated": bool(update_baselines),
        "platform": platform,
        "m": m,
        "seconds": round(time.time() - t0, 3),
    }
    return findings, report


def dataclass_dict(budget) -> dict:
    import dataclasses

    return dataclasses.asdict(budget)
