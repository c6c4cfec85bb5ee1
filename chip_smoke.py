#!/usr/bin/env python3
"""Chip smoke: the PSVGP main path, fit -> serve, once on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips (a 2x2 mesh): sharded serving only

One chip, at the paper's E3SM configuration (``configs/psvgp_e3sm.py``:
48,602 observations, a 20x20 grid, m = 5, delta = 0.125, B = 32, lr 0.05),
cut to a few dozen SGD steps:

  fit         ``api.fit``; parameters finite; one more step's loss on the
              chip against the same step on the host CPU backend.
  replicated  ``api.Server`` (mode="replicated") behind ``api.FrontDoor``
              answers requests of 1-64 points and one of 2,048; every
              answer against the uncached, solve-based blend
              (``benchmarks/bench_predict.py``) on the host CPU backend.
  fused       a grid-1 fit served ``mode="sharded"``: the fused Pallas
              slots kernel, compiled for the chip, on the normal serving
              path; against the replicated answer of the same model.

Four chips: a grid-2 fit (one partition per chip) served sharded,
pipelined, two-level routed, backend auto (-> fused), through the
FrontDoor on the 2x2 mesh; against the replicated server on the same
model. Nothing else runs.

Errors are normwise relative: max|got - want| / max|want| over all the
answers of a phase. Any failed or shed request, or error over its
tolerance, ends the run with a non-zero exit. Each phase prints one line;
the last line is the JSON device record. There is no CPU fallback: on
any platform but a TPU the script exits non-zero before doing work.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

FIT_STEPS = 40  # "a few dozen" of the configuration's 2,500
REQUEST_SIZES = (1, 3, 8, 16, 33, 64, 2048, 64, 5)
SEED = 0

# Tolerances, all normwise relative (see module docstring); the figures
# quoted are from a TPU v5 lite at this configuration. Every matmul on
# these paths runs at Precision.HIGHEST: left at DEFAULT, the served
# variance (k** - ||Wk||^2 + ||Uk||^2 cancels) misses the host by 0.12 and
# the fused kernel's by 1.5e-2. At HIGHEST what remains is the chip's
# Cholesky and triangular-inverse, which take no precision setting and
# build the cached factors 1.8e-4 from the float64 answer (the host's:
# 6e-6); the served answers then sit 2.0e-4 (var) / 2.9e-5 (mean) from
# the host's uncached f32 blend.
TOL_LOSS = 1e-4  # one SGD step's mean -ELBO, chip vs host, same batch: 2.1e-6
TOL_REFERENCE = 1e-3  # cached blend on the chip vs uncached blend on the host
# The fused kernel and the jnp path share the cached factors on one chip:
# they agree to 3.1e-6.
TOL_KERNEL = 1e-4  # fused slots kernel vs the jnp replicated path, both on chip


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds spent in XLA compiles (persistent-cache reads included) and
    persistent-cache hits, from jax's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.cache_hits


def report(phase: str, clock: CompileClock, t0: float, mark, **fields) -> None:
    rec = {
        "phase": phase,
        "seconds": round(time.perf_counter() - t0, 3),
        "compile_s": round(clock.seconds - mark[0], 3),
        "cache_hits": clock.cache_hits - mark[1],
        **fields,
    }
    print(json.dumps(rec), flush=True)


def errors(got, want) -> dict:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite answers")
    diff = float(np.abs(got - want).max())
    return {"max_abs": diff, "max_rel": diff / float(np.abs(want).max())}


def compare(phase: str, name: str, got, want, tol: float) -> dict:
    err = errors(got, want)
    err["tol_rel"] = tol
    check(
        err["max_rel"] <= tol,
        f"{phase}: {name} off by {err['max_rel']:.3e} (relative) > {tol:.0e}",
    )
    return err


def request_points(grid, sizes, seed: int) -> list:
    """Uniform query points over the grid's domain, one array per request."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lo = np.array([grid.x_edges[0], grid.y_edges[0]])
    hi = np.array([grid.x_edges[-1], grid.y_edges[-1]])
    return [rng.uniform(lo, hi, (n, 2)).astype(np.float32) for n in sizes]


def serve_through_frontdoor(server, requests) -> tuple:
    """Send every request concurrently through an ``api.FrontDoor``; return
    the concatenated (mean, var) and the front door's report. A shed or
    failed request raises."""
    import numpy as np

    from repro import api

    cfg = api.FrontDoorConfig(max_request_rows=max(len(r) for r in requests), max_rows=4096)

    async def run():
        async with api.FrontDoor(server, cfg) as fd:
            outs = await asyncio.gather(*(fd.submit(r) for r in requests))
        return outs, fd.report()

    outs, rep = asyncio.run(run())
    n = len(requests)
    req = rep["requests"]
    check(req["completed"] == n and req["shed"] == 0, f"front door requests {req}")
    for r, (mean, var) in zip(requests, outs, strict=True):
        check(mean.shape == var.shape == (len(r),), "answer shape")
    mean = np.concatenate([o[0] for o in outs])
    var = np.concatenate([o[1] for o in outs])
    return mean, var, rep


def fit_config(grid: int):
    from repro import api
    from repro.configs.psvgp_e3sm import FULL

    return api.FitConfig(
        grid=grid, m=FULL.num_inducing, delta=FULL.delta,
        batch_size=FULL.batch_size, learning_rate=FULL.learning_rate,
        train_iters=FIT_STEPS, seed=FULL.seed,
    )


def e3sm_data():
    from repro.configs.psvgp_e3sm import FULL
    from repro.data.spatial import e3sm_like_field

    return e3sm_like_field(n=FULL.n_obs, seed=FULL.seed)


def phase_fit(clock, ds):
    import jax
    import numpy as np

    from repro import api
    from repro.configs.psvgp_e3sm import FULL
    from repro.core import psvgp
    from repro.core.partition import partition_data

    t0, mark = time.perf_counter(), clock.mark()
    fitted = api.fit(fit_config(FULL.grid[0]), ds)
    leaves = jax.tree.leaves(fitted.state.params)
    check(all(bool(np.isfinite(np.asarray(a)).all()) for a in leaves), "non-finite parameters")

    # one more step from the fitted state, on the chip and on the host CPU:
    # the training path's projection and ELBO, compared at equal inputs
    pdata = partition_data(ds.x, ds.y, fitted.grid)
    key = jax.random.PRNGKey(FULL.seed)
    _, loss = psvgp.train_step(fitted.static, fitted.state, key, pdata)
    cpu = jax.devices("cpu")[0]
    static_cpu = fitted.static._replace(dist=jax.device_put(fitted.static.dist, cpu))
    _, loss_cpu = psvgp.train_step(
        static_cpu, jax.device_put(fitted.state, cpu), jax.device_put(key, cpu),
        jax.device_put(pdata, cpu),
    )
    loss, loss_cpu = float(loss), float(loss_cpu)
    check(np.isfinite(loss), f"non-finite loss {loss}")
    err = compare("fit", "loss", loss, loss_cpu, TOL_LOSS)
    report(
        "fit", clock, t0, mark,
        partitions=fitted.grid.num_partitions, m=fitted.config.m, n=len(ds.x),
        steps=FIT_STEPS, loss=loss, loss_cpu=loss_cpu, loss_vs_cpu=err,
    )
    return fitted


def phase_replicated(clock, fitted):
    import jax
    import numpy as np

    from benchmarks.bench_predict import _predict_blended_seed
    from repro import api

    t0, mark = time.perf_counter(), clock.mark()
    requests = request_points(fitted.grid, REQUEST_SIZES, SEED + 1)
    server = api.Server(fitted, api.ServeConfig(mode="replicated"))
    mean, var, rep = serve_through_frontdoor(server, requests)

    cpu = jax.devices("cpu")[0]
    pts = np.concatenate(requests)
    with jax.default_device(cpu):
        ref_mean, ref_var = _predict_blended_seed(
            fitted.static, jax.device_put(fitted.state, cpu), fitted.grid, pts
        )
    report(
        "replicated", clock, t0, mark,
        requests=len(requests), points=len(pts), batches=rep["batches"]["count"],
        mean=compare("replicated", "mean", mean, ref_mean, TOL_REFERENCE),
        var=compare("replicated", "var", var, ref_var, TOL_REFERENCE),
    )


def assert_compiled_kernel(server) -> None:
    """The sharded server resolved the fused Pallas lane, and the kernel
    dispatch compiles it for the chip (interpret mode is off)."""
    from repro.kernels import ops

    check(server.backend == "fused", f"backend resolved to {server.backend!r}, not 'fused'")
    check(not ops._interpret_default(), "Pallas kernels would run in interpret mode")


def phase_fused(clock, ds):
    from repro import api

    t0, mark = time.perf_counter(), clock.mark()
    fitted = api.fit(fit_config(1), ds)
    sharded = api.Server(fitted, api.ServeConfig(mode="sharded"))
    assert_compiled_kernel(sharded)
    requests = request_points(fitted.grid, REQUEST_SIZES, SEED + 2)
    mean, var, _ = serve_through_frontdoor(sharded, requests)
    want_mean, want_var, _ = serve_through_frontdoor(
        api.Server(fitted, api.ServeConfig(mode="replicated")), requests
    )
    report(
        "fused", clock, t0, mark,
        backend=sharded.backend, requests=len(requests),
        mean=compare("fused", "mean", mean, want_mean, TOL_KERNEL),
        var=compare("fused", "var", var, want_var, TOL_KERNEL),
    )


def phase_four_chips(clock, ds):
    from repro import api

    t0, mark = time.perf_counter(), clock.mark()
    fitted = api.fit(fit_config(2), ds)
    sharded = api.Server(
        fitted,
        api.ServeConfig(mode="sharded", pipeline="pipelined", router="two-level"),
    )
    assert_compiled_kernel(sharded)
    check(sharded.mesh.size == 4, f"mesh of {sharded.mesh.size} devices, want 4")
    requests = request_points(fitted.grid, REQUEST_SIZES, SEED + 3)
    mean, var, rep = serve_through_frontdoor(sharded, requests)
    want_mean, want_var, _ = serve_through_frontdoor(
        api.Server(fitted, api.ServeConfig(mode="replicated")), requests
    )
    report(
        "four_chips", clock, t0, mark,
        backend=sharded.backend, mesh=dict(sharded.mesh.shape),
        requests=len(requests), batches=rep["batches"]["count"],
        recompiles=rep["recompiles"],
        mean=compare("four_chips", "mean", mean, want_mean, TOL_KERNEL),
        var=compare("four_chips", "var", var, want_var, TOL_KERNEL),
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded path on a 2x2 mesh of four chips",
    )
    args = ap.parse_args()

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no usable jax backend: {e}".splitlines()[0], file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}; refusing to run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}", file=sys.stderr)
        return 2
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}", flush=True)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: the repro package is not at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    sys.path.insert(0, REPO)  # benchmarks/ (the uncached reference)

    from repro.launch import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    clock = CompileClock()
    ds = e3sm_data()
    if args.four_chips:
        phase_four_chips(clock, ds)
    else:
        fitted = phase_fit(clock, ds)
        phase_replicated(clock, fitted)
        phase_fused(clock, ds)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
