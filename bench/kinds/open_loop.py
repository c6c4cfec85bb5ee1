"""Open loop: seeded arrivals of small requests through ``api.FrontDoor``
over a replicated ``api.Server``; latency from each request's due time,
on the client side. A request that fails, is shed or never comes back
is a miss."""
from __future__ import annotations

import asyncio

import numpy as np

from harness import cells
from traffic import generator


async def open_loop(server, fd_cfg, sched, seconds: float, keep=()) -> dict:
    """Send the schedule; time each request from its due time. Only the
    answers of the requests in ``keep`` are held, so the window's heap
    stays small."""
    from repro import api

    loop = asyncio.get_running_loop()
    n = len(sched.requests)
    latency = np.full(n, np.inf)
    late = np.zeros(n)
    keep = set(keep)
    answers: dict = {}
    errors: list = []
    live: set = set()
    answered: list = []  # when each answer came back

    async def one(i, due):
        try:
            out = await fd.submit(sched.requests[i])
        except Exception as err:  # a shed or failed request is a miss
            errors.append(repr(err))
            return
        answered.append(loop.time())
        latency[i] = loop.time() - due
        if i in keep:
            answers[i] = out

    with cells.span("bench.window"):
        async with api.FrontDoor(server, fd_cfg) as fd:
            t0 = loop.time()
            for i, due in enumerate(sched.due_s):
                due = t0 + float(due)
                wait = due - loop.time()
                if wait > 0:
                    await asyncio.sleep(wait)
                late[i] = loop.time() - due
                task = loop.create_task(one(i, due))
                live.add(task)
                task.add_done_callback(live.discard)
            pending = set()
            if live:
                _, pending = await asyncio.wait(set(live), timeout=seconds + 60.0)
            for t in pending:
                t.cancel()
            t_end = loop.time()
        report = fd.report()
    gaps = np.diff(np.asarray([t0] + answered))
    return {"latency": latency, "late": late, "answers": answers, "errors": errors,
            "report": report, "elapsed": t_end - t0, "unanswered": len(pending),
            "longest_wait_ms": float(gaps.max() * 1e3) if gaps.size else 0.0}


def prepare(run):
    """The server with every batch shape the window can make warmed, and
    the mix replayed once from the warm-up seed."""
    from repro import api

    w, mix = run.world, run.mix
    server = api.Server(w.fitted, api.ServeConfig(mode="replicated"))
    fd_cfg = cells.frontdoor_config(mix)
    cells.warm_shapes(server, w.bounds, range(1, fd_cfg.max_rows + fd_cfg.max_request_rows))
    warm_s = float(mix["warm_replay_s"])
    asyncio.run(open_loop(server, fd_cfg, generator.open_loop(mix, w.bounds, warm_s, cells.WARM_SEED),
                          warm_s))
    return server, fd_cfg


def run(run) -> None:
    w, mix = run.world, run.mix
    server, fd_cfg = prepare(run)
    sched = generator.open_loop(mix, w.bounds, run.seconds, run.seed)
    # the requests whose answers are checked: drawn from the seed before
    # the window, with the largest
    n = len(sched.requests)
    rng = np.random.default_rng([run.seed, 3])
    keep = set(rng.choice(n, size=min(int(mix["sample_requests"]), n), replace=False).tolist())
    keep.add(int(np.argmax([len(r) for r in sched.requests])))
    run.setup_done()

    out = asyncio.run(open_loop(server, fd_cfg, sched, run.seconds, keep))
    run.window_done(elapsed=out["elapsed"])

    lat_ms = out["latency"] * 1e3
    done = np.flatnonzero(np.isfinite(lat_ms))
    # a miss takes the longest latency the run can give it
    lat_ms[~np.isfinite(lat_ms)] = (out["elapsed"] + 60.0) * 1e3
    run.attempted = len(sched.requests)
    run.failed = run.attempted - len(done)
    run.metrics["query_p50_ms"] = float(np.percentile(lat_ms, 50))
    run.metrics["query_p95_ms"] = float(np.percentile(lat_ms, 95))
    run.counters["frontdoor"] = out["report"]
    run.counters["points"] = int(sum(len(sched.requests[i]) for i in done))
    run.note("generator", sends=run.attempted,
             late_max_ms=float(out["late"].max() * 1e3),
             late_p95_ms=float(np.percentile(out["late"], 95) * 1e3),
             errors=out["errors"][:3])
    run.note("frontdoor", requests=out["report"]["requests"], batches=out["report"]["batches"],
             longest_wait_ms=out["longest_wait_ms"])

    cells.keep_sample(run, [(sched.requests[i], out["answers"][i]) for i in sorted(out["answers"])])
    del server, out
    run.release_program()
    cells.serve_check(run)


control = cells.serve_control
