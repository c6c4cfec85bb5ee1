#!/usr/bin/env python3
"""Find the highest rate an open-loop mix sustains, on the chip, once.

    python3 bench/sweep.py --workload e3sm_m5.small_open --rates 200 400 800 \
        [--seconds 8] [--seed 7] [--max-rows 1024]

One process: the cell's set-up (every batch shape warmed), then the
mix's schedule at each rate in turn through a fresh front door.
``--max-rows`` serves with another front-door ``max_rows`` than the mix
states (every batch size up to it is warmed first; in-window compiles
are counted all the same).
Per rate, one JSON line: offered and achieved rate, p50/p95/p99 latency
from the due time, the p95 of the first and the last fifth of the
requests (a backlog that grows shows as a last fifth far above the
first), how late the generator ran, and the front door's batch counts.
The cell's ``rate_per_s`` is then set, by hand, to about four fifths of
the highest rate whose backlog does not grow. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--max-rows", type=int, default=None)
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".bench_cache", "jax"))
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    import run as bench_run
    from harness import cells, device
    from harness.clock import CompileClock
    from repro import api
    from traffic import generator

    device.require_tpu(1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    run = bench_run.Run(bench, args.workload, args.seed, args.seconds, False, BENCH, ROOT, 0.0)
    kind = cells.load_kind(BENCH, run.mix["kind"])
    if args.max_rows is not None:
        run.mix["frontdoor"] = dict(run.mix.get("frontdoor", {}), max_rows=args.max_rows)
    w = cells.World(run.cfg, run.mix, args.seed)
    server = api.Server(w.fitted, api.ServeConfig(mode="replicated"))
    fd_cfg = cells.frontdoor_config(run.mix)
    cells.warm_shapes(server, w.bounds, range(1, fd_cfg.max_rows + fd_cfg.max_request_rows))
    clock = CompileClock()
    for rate in args.rates:
        mix = dict(run.mix, rate_per_s=rate)
        sched = generator.open_loop(mix, w.bounds, args.seconds, args.seed)
        mark = clock.mark()
        out = asyncio.run(kind.open_loop(server, fd_cfg, sched, args.seconds))
        inside = clock.since(mark)
        lat = out["latency"] * 1e3
        ok = np.isfinite(lat)
        if not ok.any():
            print(json.dumps({"sweep": {"offered_per_s": rate, "answered": 0,
                                        "errors": out["errors"][:3]}}), flush=True)
            continue
        fifth = max(1, len(lat) // 5)

        def p95(part):
            return float(np.percentile(part[np.isfinite(part)], 95)) if np.isfinite(part).any() else None

        rec = {
            "offered_per_s": rate, "requests": len(lat), "answered": int(ok.sum()),
            "achieved_per_s": float(ok.sum() / out["elapsed"]),
            "p50_ms": float(np.percentile(lat[ok], 50)), "p95_ms": float(np.percentile(lat[ok], 95)),
            "p99_ms": float(np.percentile(lat[ok], 99)),
            "p95_first_fifth_ms": p95(lat[:fifth]), "p95_last_fifth_ms": p95(lat[-fifth:]),
            "late_p95_ms": float(np.percentile(out["late"], 95) * 1e3),
            "late_max_ms": float(out["late"].max() * 1e3),
            "batches": out["report"]["batches"],
            "compiles_in_window": inside["compiles"], "compile_s_in_window": inside["compile_s"],
        }
        print(json.dumps({"sweep": rec}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
