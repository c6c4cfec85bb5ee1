#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in the root ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` picks the runner
``bench/kinds/<kind>.py``). The run makes its data and model from ``--seed``,
warms every shape its window will use (that is set-up), measures for
``--seconds``, then checks what the window produced against the plain
reference (``configs/psvgp_reference.py``) under the limits in
``bench/limits/<cell>.json``.

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the window is traced and the result carries the
per-layer metrics, each read by ``bench/metrics/<metric>.py``.

Earlier stdout lines are JSON notes (set-up, generator lateness, front
door counts). The numbers compared for ``correct`` are the last lines on
stderr. The last stdout line is the result. Off a TPU, or with fewer
chips than the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WORK_DIR = os.path.join(ROOT, ".bench_cache")  # compile cache and traces, gitignored


class BenchError(RuntimeError):
    """The cell cannot be run as described (files missing, bad entry)."""


def find_cell(bench: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise BenchError(f"workload {name!r} names an unknown config {cell['config']!r}")
    return cell, configs[cell["config"]]


def metrics_of(entries: list, cell: str) -> list:
    """The metric entries a cell reports: those listing it, or listing none."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


def load_reader(bench_dir: str, metric: str):
    path = os.path.join(bench_dir, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class Run:
    """One run of one cell: its inputs, what it measured and what it
    printed. The runners in ``kinds/`` fill it."""

    def __init__(self, bench: dict, cell: str, seed: int, seconds: float, trace: bool,
                 bench_dir: str, root: str, t_start: float):
        from harness.clock import CompileClock, GcClock
        from traffic import generator

        self.bench = bench
        self.cell, cfg_entry = find_cell(bench, cell)
        self.name = cell
        self.bench_dir = bench_dir
        with open(os.path.join(root, cfg_entry["file"])) as f:
            self.cfg = json.load(f)
        self.mix = generator.load_mix(self.cell["traffic"], os.path.join(bench_dir, "traffic"))
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.clock = CompileClock()
        self.gc_clock = GcClock()
        self.metrics: dict = {}
        self.counters: dict = {}
        self.numbers: dict = {}
        self.evidence: dict = {}  # what the window produced, for the check
        self.attempted = 0
        self.failed = 0
        self.device: dict = {}
        self.reduced: dict | None = None
        self.window_s = 0.0
        self.world = None
        self._mark = None
        self._tracer = None
        self._trace_path = None

    def note(self, what: str, **fields) -> None:
        print(json.dumps({"note": what, **fields}, default=float), flush=True)

    def setup_done(self) -> None:
        import jax

        jax.effects_barrier()
        # set-up's objects (traced programs, data, warm-up garbage) go out of
        # the collector's reach, so a collection in the window walks only
        # what the window makes
        gc.collect()
        gc.freeze()
        self.metrics["setup_s"] = time.perf_counter() - self.t_start
        self.note("setup", setup_s=self.metrics["setup_s"], compile_s=self.clock.seconds,
                  compiles=self.clock.compiles, cache_hits=self.clock.cache_hits)
        self._mark = self.clock.mark()
        self.gc_clock.reset()
        if self.trace:
            from harness.trace import Tracer

            self._tracer = Tracer(os.path.join(WORK_DIR, "trace", self.name))
            self._tracer.start()

    def window_done(self, elapsed: float) -> None:
        from harness import device

        self.window_s = float(elapsed)
        if self._tracer is not None:
            self._trace_path = self._tracer.stop()
        inside = self.clock.since(self._mark)
        self.counters["compiles_in_window"] = inside["compiles"]
        self.note("window", seconds=self.window_s, **inside, gc=self.gc_clock.summary())
        self.device = device.describe(int(self.cell["chips"]))

    def release_program(self) -> None:
        """Free the program's state before the reference runs on the chip."""
        self.world.fitted = None
        gc.collect()

    def per_layer(self) -> dict:
        from harness import trace, work

        names = {"_blend_eval", "train_step_gather"}
        self.reduced = trace.reduce(trace.extract(self._trace_path), sorted(names))
        self.peak = work.peaks(self.device["kind"], self.bench_dir)
        out = {}
        for entry in metrics_of(self.bench["per_layer"], self.name):
            value = load_reader(self.bench_dir, entry["name"])(self)
            if value is not None:
                out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
        return out


def run_cell(bench: dict, cell: str, seed: int, seconds: float, trace: bool, *,
             bench_dir: str = BENCH, root: str = ROOT, t_start: float | None = None) -> dict:
    """Run the cell and return the result line (also printed)."""
    from harness import cells, check

    run = Run(bench, cell, seed, seconds, trace, bench_dir, root,
              time.perf_counter() if t_start is None else t_start)
    run.world = cells.World(run.cfg, run.mix, run.seed)
    cells.load_kind(bench_dir, run.mix["kind"]).run(run)
    correct, checks = check.judge(run.numbers, check.load_limits(bench_dir, cell))
    run.note("numbers", **run.numbers)
    if trace:
        t = time.perf_counter()
        metrics = run.per_layer()
        r = run.reduced
        run.note("per_layer", seconds=time.perf_counter() - t,
                 trace_bytes=os.path.getsize(run._trace_path), longest_gaps=r["longest_gaps"])
        run.device.update(busy_s=r["busy_s"], window_s=r["window_s"])
    else:
        metrics = {}
        for entry in metrics_of(bench["end_to_end"], cell):
            if entry["name"] in run.metrics:
                metrics[entry["name"]] = {"value": float(run.metrics[entry["name"]]),
                                          "unit": entry["unit"]}
    result = {"correct": bool(correct), "attempted": int(run.attempted), "failed": int(run.failed),
              "metrics": metrics, "device": run.device}
    if trace:
        result["breakdown"] = {"device_ops": r["device_ops"], "idle_gaps": r["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cell, _ = find_cell(bench, args.workload)
        src = os.path.join(ROOT, "src")
        if not os.path.isdir(os.path.join(src, "repro")):
            raise BenchError(f"the program (src/repro) is not in {ROOT}")
    except (OSError, KeyError, ValueError, BenchError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    # the compile cache lives at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(WORK_DIR, "jax")
    sys.path[:0] = [BENCH, src]
    from harness import device

    try:
        device.require_tpu(int(cell["chips"]))
    except device.NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    import jax

    from repro.launch import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
