"""``Server`` — one front door for serving a fitted PSVGP.

    server = Server(fitted, ServeConfig(mode="sharded", pipeline="pipelined"))
    mean, var = server.submit(queries)           # one batch, blocking
    report = server.stream(batches)              # a request stream + SLO report
    server.swap(new_fitted, version=t)           # zero-downtime model update

or, from a persisted artifact (no retraining anywhere on this path):

    server = Server.from_artifact("runs/e3sm_t42/", ServeConfig(...))

Internally the config dispatches to the SAME primitives the pre-api
drivers composed by hand — ``blend.predict_blended`` for the replicated
fast path; ``serve_sharded.make_sharded_blend`` + ``make_request_stages``
+ the serial/pipelined request loops for the mesh endpoint, with the
router (``routing.StreamingQMax`` / ``TwoLevelQMax`` / fixed prepass
q_max) and kernel backend chosen by the config — so results are
bitwise-identical to the pre-refactor entry points (gated in
tests/test_api.py). What changed is only who does the wiring: a new
scenario is a ServeConfig field, not a new 600-line driver.

Hot swap (the in-situ lifecycle, docs/lifecycle.md): each model the
server has gone live with is an immutable ``_ServingContext`` — the
fitted model, its (sharded) cache placement, its compiled blend program,
and its route/submit/collect stages, bound together once at build time.
``swap(new_fitted)`` double-buffers the way ``pipelined_request_loop``
double-buffers batches: the ENTIRE new context is built (cache
factorized, sharded onto the mesh, program warmed) while the old context
keeps serving, and going live is one reference flip of ``_active`` under
``_swap_lock``. The stage triple ``request_stages`` hands out never
captures a context: its route stage snapshots ``_active`` exactly once
per request and threads that context through submit and collect — so a
request is answered wholly by the model that was active when it was
routed, never by a mix. That is the atomicity guarantee the swap tests
gate bitwise: pre-flip answers == old model, post-flip == new model,
and in-flight batches are never rejected or corrupted. The streaming
q_max policy is shared ACROSS contexts (the high-water mark is traffic
state, not model state), so a swap does not trigger a q_max recompile
storm.

Device-count contract: sharded mode needs one device per partition. On
CPU those are virtual host devices that must be forced BEFORE the jax
backend initializes — ``Server`` checks and raises with guidance
(``serve_sharded.ensure_host_devices``), but a process that already ran
jax work on too few devices cannot be fixed from here; CLI entry points
call ``ensure_host_devices`` (sized via ``api.peek_fit_config`` for
artifacts) first thing. A swapped-in model must keep the same partition
grid side (same mesh); its grid EDGES may move with the data.
"""
from __future__ import annotations

import threading
import time
from collections.abc import Callable

import jax
import numpy as np

from repro.api.config import ServeConfig
from repro.api.fitted import FittedPSVGP
from repro.obs import span


class _ServingContext:
    """One serving generation: a fitted model bound to its device
    placement and request stages. Immutable after ``Server._build_context``
    returns (the ``requests`` counter is the one mutable field — the
    per-version served count for ``Server.lifecycle``)."""

    __slots__ = (
        "fitted", "version", "route", "submit", "collect",
        "mesh", "cache_bytes", "requests", "build_seconds",
    )

    def __init__(self, fitted: FittedPSVGP, version):
        self.fitted = fitted
        self.version = version
        self.route: Callable | None = None
        self.submit: Callable | None = None
        self.collect: Callable | None = None
        self.mesh = None
        self.cache_bytes: tuple[int, int] | None = None
        self.requests = 0
        self.build_seconds: float | None = None


class Server:
    """Serve a :class:`FittedPSVGP` the way a :class:`ServeConfig` says to.

    Attributes:
      fitted / config: the ACTIVE model (changes on :meth:`swap`) and the
        session config.
      backend: the RESOLVED kernel lane ("ref" | "pallas" | "fused" —
        ``ServeConfig.resolve_backend``).
      policy: the streaming q_max policy routing this server's stream
        (None in replicated mode and in the fixed-q_max lane). Shared
        across swapped model versions — q_max is traffic state.
      mesh / cache_bytes: sharded mode only — the active context's device
        mesh and (total, per-device) cache-factor memory.
    """

    def __init__(self, fitted: FittedPSVGP, config: ServeConfig | None = None):
        self.config = ServeConfig() if config is None else config
        self.backend = self.config.resolve_backend()
        self.policy = (
            self.config.make_policy() if self.config.mode == "sharded" else None
        )
        self._stats = {"requests": 0, "waste_rows": 0, "spilled": 0}
        # the swap flip: _active is written under this lock and snapshotted
        # exactly once per request by the route trampoline (see
        # analysis/asynclint.CONFINEMENT for the safety argument)
        self._swap_lock = threading.Lock()
        self._swaps = 0
        self._retired: list[_ServingContext] = []
        self._active = self._build_context(fitted, version=0)

    # -- construction ------------------------------------------------------

    @classmethod
    def from_artifact(cls, path: str, config: ServeConfig | None = None) -> "Server":
        """``FittedPSVGP.load`` + ``Server`` in one step — the post-hoc
        analysis entry point: serve a persisted artifact without ever
        touching training."""
        return cls(FittedPSVGP.load(path), config)

    @property
    def fitted(self) -> FittedPSVGP:
        """The model currently going live — i.e. the active context's."""
        return self._active.fitted

    @property
    def mesh(self):
        return self._active.mesh

    @property
    def cache_bytes(self) -> tuple[int, int] | None:
        return self._active.cache_bytes

    def _build_context(self, fitted: FittedPSVGP, version) -> _ServingContext:
        """Build one COMPLETE serving generation off the request path:
        factorize/place the cache, compile-memoize the blend program,
        wire the stage triple. Nothing here touches ``_active`` — the
        old context keeps serving until the caller flips."""
        t0 = time.perf_counter()
        ctx = _ServingContext(fitted, version)
        if self.config.mode == "sharded":
            self._build_sharded_stages(ctx)
        else:
            _ = fitted.cache  # factorize up front, off the request path
            self._build_replicated_stages(ctx)
        ctx.build_seconds = time.perf_counter() - t0
        return ctx

    def _build_sharded_stages(self, ctx: _ServingContext) -> None:
        from repro.launch import serve_sharded as ss

        fitted, grid = ctx.fitted, ctx.fitted.grid
        ss.ensure_host_devices(grid.num_partitions)
        cache = fitted._sharded_ctx
        if "mesh" not in cache:
            cache["mesh"] = ss.mesh_for_grid(grid)
            cache_sh = ss.shard_cache(fitted.cache, cache["mesh"])
            jax.block_until_ready(cache_sh)
            cache["cache_sh"] = cache_sh
        if ("blend", self.backend) not in cache:
            cache[("blend", self.backend)] = ss.make_sharded_blend(
                cache["mesh"],
                cache["mesh"].axis_names,
                grid,
                fitted.static.cov_fn,
                cache["cache_sh"],
                backend=self.backend,
            )
        ctx.mesh = cache["mesh"]
        ctx.cache_bytes = ss.cache_memory_bytes(cache["cache_sh"])
        route0, submit0, collect0 = ss.make_request_stages(
            grid,
            cache[("blend", self.backend)],
            cache["cache_sh"],
            policy=self.policy,
            q_max=self.config.q_max,
            pad_multiple=self.config.pad_multiple,
        )

        def route(q):
            table, blocks = route0(q)
            ctx.requests += 1
            self._stats["requests"] += 1
            self._stats["waste_rows"] += table.waste_rows()
            self._stats["spilled"] += table.num_spilled()
            return table, blocks

        ctx.route, ctx.submit, ctx.collect = route, submit0, collect0

    def _build_replicated_stages(self, ctx: _ServingContext) -> None:
        fitted = ctx.fitted

        def route(q):
            return np.asarray(q, np.float32)

        def submit(pts):
            ctx.requests += 1
            self._stats["requests"] += 1
            return fitted.predict(pts)

        def collect(pending):
            with span("server.wait"):
                jax.block_until_ready(pending)
            with span("server.fetch"):
                return np.asarray(pending[0]), np.asarray(pending[1])

        ctx.route, ctx.submit, ctx.collect = route, submit, collect

    # -- lifecycle ---------------------------------------------------------

    def swap(self, new_fitted: FittedPSVGP, *, version=None, warm: bool = True) -> dict:
        """Go live with ``new_fitted`` with zero downtime.

        The new context is fully built FIRST — cache factorized, placed
        on the mesh (sharded), blend program compiled, optionally warmed
        with one tiny query batch — while the current model keeps
        answering every request. Going live is then a single reference
        flip under ``_swap_lock``: requests routed before the flip are
        answered by the old model end-to-end (the stage trampolines
        snapshot the context once, at route time), requests routed after
        it by the new one — bitwise, with no shed, rejected, or corrupted
        batch in between (gated in tests/test_lifecycle.py). The q_max
        policy (and its compiled-shape high-water mark) carries over, so
        a swap never forces a routing recompile by itself.

        Args:
          new_fitted: the replacement model, e.g. from ``api.refit`` or
            ``FittedPSVGP.load(store, step=...)``. Sharded mode requires
            the same partition grid side as the active model (same mesh).
          version: a label for the lifecycle report (artifact step id,
            say); defaults to the swap ordinal.
          warm: run one tiny batch through the new context before the
            flip so the first live request does not pay the compile.

        Returns ``{"version", "build_s", "swaps"}``.
        """
        old = self._active
        if self.config.mode == "sharded":
            og, ng = old.fitted.grid, new_fitted.grid
            if (og.gx, og.gy) != (ng.gx, ng.gy):
                raise ValueError(
                    f"cannot swap a {ng.gx}x{ng.gy} model into a "
                    f"{og.gx}x{og.gy} mesh — the device mesh is one "
                    "partition per device; refit with the same grid side"
                )
        if version is None:
            version = self._swaps + 1
        ctx = self._build_context(new_fitted, version)
        if warm:
            g = new_fitted.grid
            probe = np.array(
                [[np.mean(g.x_edges[[0, -1]]), np.mean(g.y_edges[[0, -1]])]],
                np.float32,
            )
            ctx.collect(ctx.submit(ctx.route(probe)))
            ctx.requests = 0  # the warm probe is not served traffic
        with self._swap_lock:
            self._retired.append(old)
            self._active = ctx
            self._swaps += 1
        return {
            "version": ctx.version,
            "build_s": ctx.build_seconds,
            "swaps": self._swaps,
        }

    def lifecycle(self) -> dict:
        """The lifecycle section of the SLO report: swap count, the active
        version, and per-version history — requests served, refit
        wall-clock (``FittedPSVGP.refit_seconds``: re-partitioning,
        topology build and SGD steps) and its split by phase
        (``refit_phases``: ``partition_s``, ``build_s``, ``steps_s``), and
        context build time (``build_s``: the double-buffered work a swap
        did off the request path).
        """
        versions = [
            {
                "version": c.version,
                "requests": c.requests,
                "refit_s": c.fitted.refit_seconds,
                "refit_phases": c.fitted.refit_phases,
                "build_s": c.build_seconds,
            }
            for c in (*self._retired, self._active)
        ]
        return {
            "swaps": self._swaps,
            "active_version": self._active.version,
            "versions": versions,
        }

    # -- serving -----------------------------------------------------------

    def submit(self, queries) -> tuple[np.ndarray, np.ndarray]:
        """Answer one query batch (N, 2), blocking: (mean (N,), var (N,))."""
        ctx = self._active
        return ctx.collect(ctx.submit(ctx.route(queries)))

    def submit_many(self, requests) -> list[tuple[np.ndarray, np.ndarray]]:
        """Answer many small independent requests as ONE device batch.

        The coalesce seam the async front door (``repro.api.frontdoor``)
        builds on: ``requests`` is a sequence of (n_i, 2) point arrays;
        they are concatenated (``routing.coalesce_requests``), served
        through the same memoized stages as :meth:`submit` — one routing
        pass, one device dispatch — and split back per request
        (``routing.demux_results``). Returns a list of (mean, var) numpy
        pairs, one per request, equal to calling :meth:`submit` on each
        request alone — BITWISE over the sharded path (the fixed-shape
        padded device program makes per-row results independent of batch
        composition), and exact to float32 ULP over the replicated path
        (XLA re-specializes per batch shape). Gated in
        tests/test_frontdoor.py.
        """
        from repro.core import routing

        pts, sizes = routing.coalesce_requests(requests)
        mean, var = self.submit(pts)
        return routing.demux_results(sizes, mean, var)

    def request_stages(self) -> tuple[Callable, Callable, Callable]:
        """The (route, submit, collect) stage triple of this server's
        serving path — the pipelining seam.

        Sharded route is pure numpy; submit is transfer + async dispatch;
        collect is the only sync point. Replicated mode has the same
        three-stage SHAPE around ``fitted.predict`` so a caller that
        overlaps stages — the front door's batching engine,
        ``pipelined_request_loop`` — works against either mode without
        branching.

        The triple survives :meth:`swap`: each stage is a trampoline
        over the ACTIVE context — route snapshots it exactly once and
        threads it through submit and collect, so every request is
        answered end-to-end by the model that was live when it was
        routed (a request never straddles a swap).
        """

        def route(q):
            ctx = self._active  # the one snapshot per request
            return ctx, ctx.route(q)

        def submit(routed):
            ctx, r = routed
            return ctx, ctx.submit(r)

        def collect(pending):
            ctx, p = pending
            return ctx.collect(p)

        return route, submit, collect

    def stream(self, batches, *, warm: bool = True, on_result: Callable | None = None) -> dict:
        """Serve a request stream through the configured loop; return the
        SLO report.

        Dispatch: sharded+pipelined runs the overlapped double-buffered
        driver (``serve_sharded.pipelined_request_loop`` — batch t+1
        routes on the host while the mesh evaluates batch t); everything
        else runs the synchronous ``timed_request_loop``. Results are
        delivered through ``on_result(i, (mean, var))`` in stream order
        (bitwise-identical between the two loops — overlap is scheduling,
        never math).

        ``warm=True`` runs batches[0] once before timing (compile +
        transfer warmup); pass ``warm=False`` when the caller already ran
        a batch (e.g. for an equivalence gate). The warm pass is not
        reported to ``on_result`` and not counted in the latency record.

        Returns ``{"serve_config", "backend", "latency_ms": {p50,p95,p99},
        "points_per_s", "qmax_policy", "lifecycle"}``.
        """
        from repro.launch import serve_sharded as ss

        if self.config.mode == "sharded" and self.config.pipeline == "pipelined":
            route, submit, collect = self.request_stages()
            pct, qps = ss.pipelined_request_loop(
                route, submit, collect, batches, warm=warm, on_result=on_result,
            )
        else:
            if warm:
                self.submit(batches[0])
            if on_result is None:
                answer = self.submit
            else:
                idx = {"i": 0}

                def answer(q):
                    out = self.submit(q)
                    on_result(idx["i"], out)
                    idx["i"] += 1
                    return out

            pct, qps = ss.timed_request_loop(answer, batches, warm=False)
        rec = {
            "serve_config": self.config.to_dict(),
            "backend": self.backend,
            "latency_ms": pct,
            "points_per_s": qps,
            "qmax_policy": (
                {"q_max": int(self.config.q_max), "fixed": True}
                if self.policy is None and self.config.mode == "sharded"
                else self.policy.stats() if self.policy is not None else None
            ),
            "lifecycle": self.lifecycle(),
        }
        return rec

    # -- introspection -----------------------------------------------------

    def stats(self) -> dict:
        """Cumulative serving counters: requests routed, padded-row waste
        and spilled queries (from each request's RoutingTable), plus the
        q_max policy record. Counters span model versions — swap does not
        reset them (``lifecycle()`` has the per-version split).
        ``reset_stats`` zeroes the table counters — benchmark lanes do
        that after their warm pass so the report covers the measured
        stream exactly once."""
        rec = dict(self._stats)
        if self.policy is not None:
            rec["qmax_policy"] = self.policy.stats()
        return rec

    def reset_stats(self) -> None:
        self._stats.update(requests=0, waste_rows=0, spilled=0)
