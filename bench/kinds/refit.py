"""The in-situ refit: ``api.refit`` on each new field slice, then the
posterior cache, until the model can be served; the window's time over
the cycles it completed.

Set-up makes the model the window starts from (``World.fitted``: the
seed-made parameters and Adam's state mid-fit) and warms the cycle's
programs with one cycle on a slice the window never sends, whose result
is dropped. The window then drives that same model. Its first cycle,
the window's own call with the mix's ``train_iters``, is kept and, after
the window, checked against the reference's run of the same steps: the
change of every parameter leaf and of Adam's second moment of it. The
answers its cache gives at seeded probe points are read too; PERF.md
says why they are printed and not compared.
"""
from __future__ import annotations

import time

import numpy as np

from configs import psvgp_reference as ref
from harness import cells, check, model
from traffic import generator


def run(run) -> None:
    import jax

    from repro import api

    w, mix = run.world, run.mix
    n_slices = int(mix["slices"])
    ys = [w.field.slice(t) for t in range(1, n_slices + 1)]
    cycle = api.RefitConfig(train_iters=int(mix["train_iters"]))
    f0 = w.fitted
    with cells.span("bench.warm"):
        jax.block_until_ready(api.refit(f0, (w.x, w.field.slice(n_slices + 1)), cycle).cache)
    run.setup_done()

    cycles = 0
    f, first = f0, None
    durations = []
    with cells.span("bench.window"):
        t0 = t = time.perf_counter()
        while True:
            with cells.span("bench.refit"):
                f = api.refit(f, (w.x, ys[cycles % n_slices]), cycle)
            with cells.span("bench.cache"):
                jax.block_until_ready(f.cache)
            if first is None:
                first = f
            cycles += 1
            now = time.perf_counter()
            durations.append(now - t)
            t = now
            if now - t0 >= run.seconds:
                break
        elapsed = now - t0
    run.window_done(elapsed=elapsed)
    run.attempted = cycles
    run.failed = 0
    run.metrics["refit_ms"] = elapsed / cycles * 1e3
    run.counters["cycles"] = cycles
    run.counters["steps"] = cycles * int(mix["train_iters"])
    d = np.asarray(durations) * 1e3
    run.note("cycles", n=cycles, first_ms=float(d[0]), median_ms=float(np.median(d)),
             max_ms=float(d.max()), min_ms=float(d.min()))

    probes = generator.points({}, w.bounds, int(mix["probe_points"]),
                              np.random.default_rng([run.seed, 5]))
    run.evidence.update(y=ys[0], probes=probes, after=model.host_params(first.state.params),
                        nu=model.host_params(first.state.opt.nu),
                        got=tuple(np.asarray(a) for a in first.predict(probes)))
    del f, first, f0
    run.release_program()
    want = run.evidence["want"] = reference(run)
    run.numbers.update(numbers(run, run.evidence["after"], run.evidence["nu"], run.evidence["got"],
                               want))
    run.note("reference", first_loss=want[0][0], last_loss=want[0][-1])


def reference(run, mode: str = "highest"):
    """The reference's run of the first cycle's steps from the same
    parameters, optimizer state and slice: (losses, first gradient,
    parameters after, answers at the probes)."""
    w, cfg, ev = run.world, run.cfg, run.evidence
    jitter = float(cfg["jitter"])
    step, mu, nu = w.adam
    losses, grad, after, nu_after = ref.sgd(
        w.params, w.x, ev["y"], w.grid, steps=int(run.mix["train_iters"]), seed=w.fit_seed,
        first_step=step, mu0=mu, nu0=nu, delta=float(cfg["delta"]),
        batch=int(cfg["batch_size"]), lr=float(cfg["learning_rate"]), mode=mode, jitter=jitter)
    answers = ref.blend(after, w.grid, ev["probes"], mode=mode, jitter=jitter)
    return losses, grad, after, nu_after, answers


def numbers(run, after, nu, got, want) -> dict:
    """The change over the cycle of every parameter leaf and of Adam's
    second moment of it (the leaves the reference's gradient moves), by
    the worst leaf and by the median leaf, and the answers at the probes
    after it."""
    params0, nu0 = run.world.params, run.world.adam[2]
    _, ref_grad, ref_after, ref_nu, ref_answers = want
    moved = check.moved_leaves(ref_grad)
    change = check.leaf_norm_gaps({k: after[k] - params0[k] for k in params0},
                                  {k: ref_after[k] - params0[k] for k in params0}, keep=moved)
    moment = check.leaf_norm_gaps({k: nu[k] - nu0[k] for k in nu0},
                                  {k: ref_nu[k] - nu0[k] for k in nu0}, keep=moved)
    out = {"change_gap": max(change.values()), "change_gap_median": float(np.median(list(change.values()))),
           "moment_gap": max(moment.values()), "moment_gap_median": float(np.median(list(moment.values())))}
    out.update({f"probe_{k}": v for k, v in check.answer_gaps(*got, *ref_answers).items()})
    return out


def control(run) -> dict:
    """The reference at ``high`` in the program's place."""
    c = reference(run, mode="high")
    return numbers(run, c[2], c[3], c[4], run.evidence["want"])
