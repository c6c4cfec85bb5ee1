#!/usr/bin/env python3
"""Readings for the limits that decide ``correct``, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 101 102 ... \
        [--seconds 3] [--control-seeds 3] [--fault half_batch]

For each seed, in one process: the cell's own set-up and a short window
at its own load, then the numbers it compares (the program's readings,
whose largest is a limit's lower reading). On the first
``--control-seeds`` seeds also the control's readings: the plain
reference computed with every matrix product at ``high`` (three
bfloat16 passes) in the program's place, against the reference at the
configurations' ``highest``; for a serving cell also the gaps of the
program, the reference and the control to a float64 witness of the same
blend on the host. ``--fault`` plants a fault in the program
instead and reads it (see ``tests/faults.py``). One JSON line per seed
on stdout. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_numbers(run) -> dict:
    from harness import cells

    return cells.load_kind(run.bench_dir, run.mix["kind"]).control(run)


def blend64(params: dict, grid, pts, jitter: float):
    """The blend of ``psvgp_reference.blend`` in float64 numpy, one
    Cholesky per point and corner, as a witness for the reference."""
    from configs import psvgp_reference as ref

    ids, w = ref.corners(grid, pts)
    x = np.asarray(pts, np.float64)
    means, vars_ = [], []
    for c in range(4):
        p = {k: np.asarray(v, np.float64)[ids[:, c]] for k, v in params.items()}
        ls, var0 = np.exp(p["log_ls"]), np.exp(p["log_var"])
        z = p["z"] / ls[:, None, :]
        d = z[:, :, None, :] - z[:, None, :, :]
        m = z.shape[1]
        kmm = var0[:, None, None] * np.exp(-0.5 * np.sum(d * d, -1)) + jitter * np.eye(m)
        k = var0[:, None] * np.exp(-0.5 * np.sum((x[:, None, :] / ls[:, None, :] - z) ** 2, -1))
        chol = np.linalg.cholesky(kmm)
        v = np.linalg.solve(chol, k[..., None])
        a = np.linalg.solve(np.swapaxes(chol, 1, 2), v)
        s_tril = p["s_tril"]
        chol_s = np.tril(s_tril, -1) + np.eye(m) * np.exp(np.diagonal(s_tril, axis1=1, axis2=2))[:, None, :]
        t = np.swapaxes(chol_s, 1, 2) @ a
        means.append((a[..., 0] * p["m_star"]).sum(-1))
        vars_.append(np.maximum(var0 - (v[..., 0] ** 2).sum(-1) + (t[..., 0] ** 2).sum(-1), 1e-12))
    mu, var = np.stack(means, 1), np.stack(vars_, 1)
    mean = (w * mu).sum(1)
    return mean, np.maximum((w * (var + mu * mu)).sum(1) - mean * mean, 1e-12)


def witness_numbers(run) -> dict:
    """Gaps of the program, the reference and the control to float64."""
    from harness import cells, check

    w, ev = run.world, run.evidence
    truth = blend64(w.params, w.grid, ev["points"], float(run.cfg["jitter"]))
    control = cells.serve_reference(run, mode="high")
    return {name: check.answer_gaps(*got, *truth)
            for name, got in (("program", ev["got"]), ("reference", ev["want"]), ("control", control))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".bench_cache", "jax"))
    sys.path[:0] = [BENCH, os.path.join(BENCH, "tests"), os.path.join(ROOT, "src")]
    import jax

    import run as bench_run
    from harness import cells, device

    device.require_tpu(1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    plant = None
    if args.fault:
        import faults

        plant = faults.PLANTS[args.fault]
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        run = bench_run.Run(bench, args.workload, seed, args.seconds, False, BENCH, ROOT, t)
        run.world = cells.World(run.cfg, run.mix, seed)
        kind = cells.load_kind(BENCH, run.mix["kind"])
        if plant is None:
            kind.run(run)
        else:
            with plant():
                kind.run(run)
        rec = {"workload": args.workload, "seed": seed, "fault": args.fault,
               "program": run.numbers, "metrics": run.metrics, "seconds": time.perf_counter() - t}
        if i < args.control_seeds:
            rec["control"] = control_numbers(run)
            if run.mix["kind"] != "refit" and "points" in run.evidence:
                rec["float64"] = witness_numbers(run)
        print(json.dumps({"calibrate": rec}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
