"""A benchmark at a size a test run on the host CPU can hold: a copy of
the bench directory's files with a small configuration and small mixes,
and the harness driven without its look for a chip."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = {"n_obs": 20000, "grid": [20, 20], "num_inducing": 5}
MIXES = {
    "small_open": {"rate_per_s": 40, "frontdoor": {"max_rows": 12, "max_request_rows": 6},
                   "size_max": 6, "warm_replay_s": 0.2, "sample_requests": 40},
    "bulk_closed": {"clients": 2, "request_rows": 64, "pool": 4, "warm_replay_s": 0.2,
                    "frontdoor": {"max_request_rows": 64, "max_rows": 128}, "sample_requests": 4},
    "refit": {"train_iters": 5, "slices": 2, "probe_points": 256},
}
CELLS = {"tiny.small_open": "small_open", "tiny.bulk_closed": "bulk_closed", "tiny.refit": "refit"}


def make(tmp: str) -> tuple[dict, str]:
    """Write the tiny benchmark under ``tmp``; return (BENCHMARK dict,
    its bench directory)."""
    bench_dir = os.path.join(tmp, "bench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench_dir, sub), exist_ok=True)
    with open(os.path.join(BENCH, "configs", "e3sm_m5.json")) as f:
        cfg = json.load(f)
    cfg.update(CONFIG, name="tiny")
    with open(os.path.join(bench_dir, "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    for name, over in MIXES.items():
        with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
            mix = json.load(f)
        mix.update(over)
        with open(os.path.join(bench_dir, "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench_dir)
    for sub in ("metrics", "kinds"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench_dir, sub), dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": c, "config": "tiny", "traffic": t, "chips": 1, "why": "test"}
                          for c, t in CELLS.items()]
    kinds = {"small_open": "e3sm_m5.small_open", "bulk_closed": "e3sm_m20.bulk_closed",
             "refit": "e3sm_m5.refit"}
    for cell, traffic in CELLS.items():
        shutil.copy(os.path.join(BENCH, "limits", f"{kinds[traffic]}.json"),
                    os.path.join(bench_dir, "limits", f"{cell}.json"))
    return bench, bench_dir


def run(tmp: str, cell: str, seed: int = 2**31 + 7, seconds: float = 1.0, trace: bool = False) -> dict:
    import run as bench_run

    bench, bench_dir = make(tmp)
    return bench_run.run_cell(bench, cell, seed, seconds, trace, bench_dir=bench_dir, root=tmp)
