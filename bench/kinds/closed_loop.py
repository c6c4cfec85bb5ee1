"""Closed loop: a few clients, each sending its next request through
``api.FrontDoor`` when the last one is answered; points answered per
second. A seeded reservoir of the answers is checked."""
from __future__ import annotations

import asyncio

import numpy as np

from harness import cells
from traffic import generator


async def closed_loop(server, fd_cfg, pool, clients: int, seconds: float, keep: int, seed: int):
    from repro import api

    loop = asyncio.get_running_loop()
    rng = np.random.default_rng([seed, 4])
    kept: list = []  # reservoir of (pool index, answer)
    state = {"done": 0, "points": 0}
    answered: list = []  # when each answer came back

    async def client(c, fd, stop):
        k = c
        while loop.time() < stop:
            j = k % len(pool)
            out = await fd.submit(pool[j])
            answered.append(loop.time())
            state["done"] += 1
            state["points"] += len(pool[j])
            if len(kept) < keep:
                kept.append((j, out))
            else:
                r = int(rng.integers(state["done"]))
                if r < keep:
                    kept[r] = (j, out)
            k += clients

    with cells.span("bench.window"):
        async with api.FrontDoor(server, fd_cfg) as fd:
            t0 = loop.time()
            await asyncio.gather(*(client(c, fd, t0 + seconds) for c in range(clients)))
            elapsed = loop.time() - t0
        report = fd.report()
    gaps = np.diff(np.asarray([t0] + answered))
    return {"kept": kept, "elapsed": elapsed, "report": report,
            "longest_wait_ms": float(gaps.max() * 1e3) if gaps.size else 0.0, **state}


def run(run) -> None:
    from repro import api

    w, mix = run.world, run.mix
    server = api.Server(w.fitted, api.ServeConfig(mode="replicated"))
    fd_cfg = cells.frontdoor_config(mix)
    rows = int(mix["request_rows"])
    cells.warm_shapes(server, w.bounds, range(rows, fd_cfg.max_rows + 1, rows))
    clients = int(mix["clients"])
    warm_pool = generator.closed_pool(mix, w.bounds, cells.WARM_SEED)
    asyncio.run(closed_loop(server, fd_cfg, warm_pool, clients, float(mix["warm_replay_s"]), 0,
                            cells.WARM_SEED))
    pool = generator.closed_pool(mix, w.bounds, run.seed)
    run.setup_done()

    out = asyncio.run(closed_loop(server, fd_cfg, pool, clients, run.seconds,
                                  int(mix["sample_requests"]), run.seed))
    run.window_done(elapsed=out["elapsed"])
    run.attempted = out["done"]
    run.failed = 0
    run.metrics["points_per_s"] = out["points"] / out["elapsed"]
    run.counters["frontdoor"] = out["report"]
    run.counters["points"] = out["points"]
    run.note("frontdoor", requests=out["report"]["requests"], batches=out["report"]["batches"],
             longest_wait_ms=out["longest_wait_ms"])
    cells.keep_sample(run, [(pool[j], ans) for j, ans in out["kept"]])
    del server, out
    run.release_program()
    cells.serve_check(run)


control = cells.serve_control
