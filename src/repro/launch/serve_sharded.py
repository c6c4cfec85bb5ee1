"""Sharded multi-host GP serving — the distributed PosteriorCache endpoint.

Replicated serving (``repro.launch.serve --gp``) answers every query from
one host holding ALL P partitions' cached factors. This module completes
the paper's story at serving time: the ``PosteriorCache`` is sharded one
partition per device over the mesh (per-device factor memory = 1/P of
replicated), queries are routed to their owning partition by
``repro.core.routing``, and the 4-corner blend is resolved with a 1-hop
``ppermute`` halo exchange — exactly the training-time communication
pattern of ``repro.core.psvgp_spmd``, and NO all-gather of factors
anywhere.

Per request (the overlapped pipeline; serial mode runs the same stages
back-to-back):

  HOST, overlapped with the mesh evaluating the PREVIOUS request:
  1. route the batch (``routing.build_routing_table``; q_max follows the
     streaming high-water-mark policy ``routing.StreamingQMax``, or its
     two-level variant ``routing.TwoLevelQMax`` — ``--gp-router
     two-level`` — which spills hot-cell overflow onto corner-cell
     neighbors so skewed streams stop padding every device to the
     hottest cell) and stack each device's full 9-slot halo of query
     blocks (``routing.make_halo_stacker``) — queries are host data, so
     the halo ingest rides the dispatch-time host->device transfer and
     costs zero mesh collectives,

  DEVICE (``make_sharded_blend``):
  2. evaluate the LOCAL cached posterior on all 9 stacked blocks at once —
     ``posterior.predict_cached_slots``; with ``use_pallas`` that is ONE
     fused Pallas launch whose grid spans (9 slots x q-blocks) with the
     W/U/c factors resident in VMEM across the whole grid,
  3. return each result block to the query's owner over the COMPOSED
     1-hop reverse halo: a row exchange then a column exchange move all
     8 neighbor results in 4 ppermutes total (diagonals ride the
     composition; the PR-2 program paid 12 query hops out + 24 result
     hops back),
  4. blend the 4 corner evaluations per query on the owning device
     (``routing.blend_slots``),

  HOST:
  5. only when the result is CONSUMED, block on the device values and
     scatter them back to request order (``routing.scatter_results``) —
     jax's async dispatch keeps step 1 of batch t+1 running while the
     mesh is inside steps 2-4 of batch t (``pipelined_request_loop``).

Communication per request per device: 4 nearest-neighbor collectives
carrying 8 result pairs — O(q_max) floats, independent of P. The factors,
like the variational parameters during training, never move.

The CLI at the bottom is a thin shim over ``repro.api``: the flags parse
into a ``FitConfig``/``ServeConfig`` and ``api.Server`` composes the
stages defined here (this module remains the sharded-serving ENGINE —
mesh construction, the shard_map blend program, the request stages and
the serial/pipelined loops). ``--gp-save``/``--gp-artifact`` persist and
reuse the trained artifact (``api.FittedPSVGP``).

Usage (CPU dry-run; the grid is mapped one-partition-per-device onto
gy x gx virtual host devices):

  PYTHONPATH=src python -m repro.launch.serve_sharded \
      --gp-grid 8 --gp-m 10 --gp-train-iters 200 \
      --gp-batch 2048 --gp-requests 50

or equivalently through the main serving driver:

  PYTHONPATH=src python -m repro.launch.serve --gp --sharded --gp-grid 8
"""
from __future__ import annotations

import argparse
import os
import time
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.contracts import contract
from repro.core import posterior, routing
from repro.core.partition import PartitionGrid
from repro.core.psvgp_spmd import grid_matches_mesh, shift_perm
from repro.launch import use_compile_cache
from repro.sharding import gp_stacked_pspecs


def _cpu_platform_in_use() -> bool:
    """Whether this process's jax runs on the CPU backend — decided
    WITHOUT initializing a backend when none is up yet (initializing would
    bind the host device count before the caller could force it)."""
    from jax._src import hardware_utils, xla_bridge

    if xla_bridge.backends_are_initialized():
        return jax.default_backend() == "cpu"
    if jax.config.jax_platforms:
        return jax.config.jax_platforms.split(",")[0] == "cpu"
    # no platform named: jax picks an attached accelerator when there is one
    return (
        hardware_utils.num_available_tpu_chips_and_device_id()[0] == 0
        and not hardware_utils.has_visible_nvidia_gpu()
    )


def ensure_host_devices(n: int) -> None:
    """Make sure one-partition-per-device serving has >= n devices.

    On the CPU platform the devices are virtual: the host-device-count
    flag is written into XLA_FLAGS (an already-present but too-small
    count is rewritten upward), which binds only if the backend has not
    initialized yet. On an accelerator XLA_FLAGS is left alone — the
    devices are the real chips. Raises when fewer than n devices exist,
    with the CPU flag advice only where the flag applies.
    """
    import re

    cpu = _cpu_platform_in_use()
    if cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        flag_re = r"--xla_force_host_platform_device_count=(\d+)"
        m = re.search(flag_re, flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
        elif int(m.group(1)) < n:
            os.environ["XLA_FLAGS"] = re.sub(
                flag_re, f"--xla_force_host_platform_device_count={n}", flags
            )
    have = jax.device_count()
    if have >= n:
        return
    if cpu:
        raise RuntimeError(
            f"need {n} devices for one-partition-per-device serving, have "
            f"{have}. Set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax "
            "initializes (import order matters), or shrink --gp-grid."
        )
    raise RuntimeError(
        f"need {n} devices for one-partition-per-device serving, found "
        f"{have} {jax.default_backend()} device(s) — serve a grid of at "
        f"most {have} partitions here, or run on more chips."
    )


def mesh_for_grid(grid: PartitionGrid) -> Mesh:
    """(gy, gx) device mesh matching the partition grid, axes (data, model)
    — the serving analogue of the training mapping in
    ``repro.core.psvgp_spmd`` (grid x-steps shift along ``model``, y-steps
    along ``data``)."""
    return jax.make_mesh(
        (grid.gy, grid.gx), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


def shard_cache(
    cache: posterior.PosteriorCache, mesh: Mesh
) -> posterior.PosteriorCache:
    """Place the P-stacked cache one partition per device (leading axis
    over all mesh axes via ``sharding.gp_stacked_pspecs``)."""
    specs = gp_stacked_pspecs(cache, mesh)
    return jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        cache, specs,
    )


def _make_shift(axes: Sequence[str], gx: int, gy: int) -> Callable:
    """Build ``shift(tree, dx, dy)`` usable INSIDE a shard_map over ``axes``:
    every device receives the payload of the device at grid offset
    (dx, dy), zeros where that neighbor is off-grid (ppermute's edge
    semantics — routing guarantees off-grid slots are never blended).
    Diagonal offsets compose an x-hop and a y-hop; both are 1-hop
    nearest-neighbor collectives on the ICI torus, exactly like the
    training exchange in ``repro.core.psvgp_spmd``."""
    col_axis = axes[-1]
    row_axes = tuple(axes[:-1])
    row_ax = row_axes if len(row_axes) > 1 else row_axes[0]

    def shift(tree, dx: int, dy: int):
        def sh(a):
            if dx:
                a = jax.lax.ppermute(a, col_axis, shift_perm(gx, up=(dx > 0)))
            if dy:
                a = jax.lax.ppermute(a, row_ax, shift_perm(gy, up=(dy > 0)))
            return a

        return jax.tree.map(sh, tree)

    return shift


def make_halo_gather(mesh: Mesh, axes: Sequence[str], grid: PartitionGrid):
    """Jitted (P, ...) -> (P, 9, ...) halo gather: output slot k on device p
    is device p+OFFSETS[k]'s block (zeros off-grid). The standalone probe of
    the ``shift`` semantics the serving program's reverse halo composes —
    tests assert it resolves corners exactly like ``routing.halo_ids``, and
    that the host-side ``routing.make_halo_stacker`` reproduces it."""
    if not grid_matches_mesh(grid, mesh, axes):
        raise ValueError(
            f"grid {grid.gx}x{grid.gy} must match mesh axes {tuple(axes)}"
        )
    shift = _make_shift(axes, grid.gx, grid.gy)

    def gather(x):
        x = x[0]
        out = [
            x if k == routing.SELF_SLOT else shift(x, dx, dy)
            for k, (dx, dy) in enumerate(routing.OFFSETS)
        ]
        return jnp.stack(out)[None]

    pspec = P(tuple(axes))
    return jax.jit(
        jax.shard_map(
            gather, mesh=mesh, in_specs=(pspec,), out_specs=pspec, check_vma=False
        )
    )


def cache_in_specs(cache_like, pspec) -> posterior.PosteriorCache:
    """shard_map in_specs for a P-stacked cache: every leaf carries
    ``pspec`` on its leading partition axis, DERIVED from the pytree
    structure of the cache actually served. Deriving (rather than
    hand-building a spec literal field by field) means a future
    ``PosteriorCache`` field can never desync the spec tree from the
    value tree — the exact hazard the old literal carried."""
    return jax.tree.map(lambda _: pspec, cache_like)


@contract(
    args={
        "hx": "(P, 9, Q, 2)",
        "corner_slot": "(P, Q, 4)",
        "corner_w": "(P, Q, 4)",
    },
    returns=("(P, Q)", "(P, Q)"),
    invariants=("outputs-f32",),
)
def make_sharded_blend(
    mesh: Mesh,
    axes: Sequence[str],
    grid: PartitionGrid,
    cov_fn: Callable,
    cache_like: posterior.PosteriorCache,
    *,
    use_pallas: bool = False,
    backend: str | None = None,
):
    """Build the jitted shard_map serving program.

    Call signature of the returned function (leading P axis of every array
    sharded one partition per device):

      blend_fn(cache, hx, corner_slot, corner_w) -> (mean, var)

    with cache a P-stacked ``PosteriorCache``, hx (P, 9, q_max, 2) the
    HOST-STACKED halo query blocks (``routing.make_halo_stacker``:
    hx[p, k] = partition p+OFFSETS[k]'s block, zeros off-grid), corner_slot
    (P, q_max, 4) int32, corner_w (P, q_max, 4), and outputs (P, q_max)
    each — padded rows carry garbage (weight-0 blends) and are dropped by
    ``routing.scatter_results``. Math identical to
    ``routing.predict_routed`` and, through it, ``blend.predict_blended``.

    The device program evaluates the local model on all 9 slots at once
    (``posterior.predict_cached_slots`` with the chosen kernel ``backend``
    — "ref" jnp, "pallas" single-block kernel via reshape, "fused" one
    slot-stacked launch; the legacy ``use_pallas`` bool maps True ->
    "fused". Pallas lanes compile to Mosaic on TPU only and are validated
    RBF-only) and returns the results
    over the COMPOSED reverse halo: slot k's evaluation must travel to the
    owner at offset OFFSETS[k], and because a diagonal hop is an x-hop
    then a y-hop, the whole 3x3 neighborhood moves in 4 ppermutes — one
    row exchange (x-+, x+) of the slot-flipped results, one column
    exchange (y-, y+) of the row-exchanged triples.

    ``cache_like``: the cache that will be served; only its pytree
    STRUCTURE is read (``cache_in_specs``) to build the shard_map
    in_specs, so the spec tree can never desync from the cache layout.
    """
    if not grid_matches_mesh(grid, mesh, axes):
        raise ValueError(
            f"grid {grid.gx}x{grid.gy} must match mesh axes {tuple(axes)} "
            f"{[mesh.shape[a] for a in axes]} (one partition per device)"
        )
    if grid.wrap_x:
        raise NotImplementedError("wrapped grids need ring perms for the halo")
    backend = posterior.resolve_slot_backend(use_pallas, backend)
    if backend != "ref":
        from repro.kernels import ops as kops

        kops.require_rbf(cov_fn)  # fail at build time, not trace time
    shift = _make_shift(axes, grid.gx, grid.gy)
    S = routing.NUM_HALO_SLOTS

    def step(cache, hx, corner_slot, corner_w):
        local = jax.tree.map(lambda a: a[0], cache)  # this device's factors
        h = hx[0]  # (9, q, d): slot k = queries owned by the device at offset k
        q = h.shape[1]
        # 1. one slot-stacked local evaluation of all nine blocks
        mean, var = posterior.predict_cached_slots(
            local, cov_fn, h, backend=backend
        )
        ev = jnp.stack([mean, var], axis=1)  # (9, 2, q): one halo payload
        # 2. composed reverse halo. The owner at offset OFFSETS[k] needs MY
        # evaluation of its queries, which sits in my slot 8-k; flipping
        # the slot axis puts "what must travel along offset (dx, dy)" at
        # halo position (dy+1, dx+1):
        f = ev[::-1].reshape(3, 3, 2, q)  # f[dy+1, dx+1] travels along (dx, dy)
        # row exchange: every column of the flipped stack moves its x-hop
        g = jnp.stack(
            [shift(f[:, 0], -1, 0), f[:, 1], shift(f[:, 2], 1, 0)], axis=1
        )
        # column exchange: row-exchanged triples move their y-hop
        res = jnp.concatenate(
            [shift(g[0], 0, -1)[None], g[1][None], shift(g[2], 0, 1)[None]]
        ).reshape(S, 2, q)  # res[k] = model at offset k's evaluation of MY queries
        # 3. 4-corner bilinear blend on the owning device
        bmean, bvar = routing.blend_slots(
            res[:, 0], res[:, 1], corner_slot[0], corner_w[0]
        )
        return bmean[None], bvar[None]

    pspec = P(tuple(axes))
    step_fn = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(cache_in_specs(cache_like, pspec), pspec, pspec, pspec),
        out_specs=(pspec, pspec),
        check_vma=False,
    )
    return jax.jit(step_fn)


# --------------------------------------------------------------------------
# Serving driver
# --------------------------------------------------------------------------


def train_demo_surface(
    *, seed: int, n: int, grid_side: int, m: int, train_iters: int,
    fit_cfg=None,
):
    """The ONE training recipe every serving driver/benchmark demos against
    (``serve --gp``, ``serve --gp --sharded``, ``benchmarks.bench_serve``):
    a PSVGP with the paper-flavored delta=0.25 on the synthetic E3SM-like
    field, trained through ``repro.api.fit``. Keeping it shared is what
    makes the replicated-vs-sharded equivalence checks compare the SAME
    posterior.

    Returns (ds, fitted) — the dataset (for query-domain bounds) and the
    ``repro.api.FittedPSVGP`` serving bundle. An explicit ``fit_cfg`` (the
    ``--config session.json`` lane) replaces the flag-derived FitConfig
    wholesale; the dataset size ``n`` stays a CLI concern either way.
    """
    from repro import api
    from repro.data.spatial import e3sm_like_field

    if fit_cfg is None:
        fit_cfg = api.FitConfig(
            grid=grid_side, m=m, train_iters=train_iters, seed=seed
        )
    ds = e3sm_like_field(n=n, seed=fit_cfg.seed)
    fitted = api.fit(fit_cfg, ds, verbose=True)
    return ds, fitted


@contract(
    route={
        "xq": "(P, Q, D)",
        "stacked": "(P, 9, Q, D)",
        "corner_slot": "(P, Q, 4)",
        "corner_w": "(P, Q, 4)",
    },
    invariants=("q_max-matches-policy", "q_max-aligned"),
)
def make_request_stages(
    grid: PartitionGrid,
    blend_fn: Callable,
    cache_sh: posterior.PosteriorCache,
    *,
    policy: routing.StreamingQMax | None = None,
    q_max: int | None = None,
    pad_multiple: int | None = None,
):
    """Split a request into the three pipeline stages the overlapped driver
    schedules (and the serial driver runs back-to-back):

      route(q)        HOST, pure numpy: bin the batch once
                      (``owning_cells``), fit q_max (streaming policy or
                      the fixed prepass value), build the table REUSING
                      the binning, halo-stack the blocks. Returns
                      (table, blocks). Deliberately NO device_put here: a
                      put targets the same devices the PREVIOUS request is
                      still executing on and serializes behind it, which
                      would stall the overlapped pipeline for a full
                      device window — the transfer happens at dispatch
                      time inside ``submit`` instead.
      submit(routed)  DEVICE: dispatch the shard_map program (host->device
                      transfer + async dispatch) — returns without waiting
                      for the result.
      collect(pending) HOST: block on the device values and scatter them
                      back to request order. The ONLY sync point.

    Exactly one of ``policy`` (live stream) / ``q_max`` (whole-stream
    prepass, ``fixed_q_max``) must be given. ``pad_multiple`` is the
    block-size alignment ``build_routing_table`` applies; it defaults to
    the POLICY's own alignment (so the policy's q_max high-water mark is
    never re-rounded — its compile/overflow counters always describe the
    block shapes actually compiled), or to the table default of 8 in the
    fixed-q_max lane. A
    :class:`routing.TwoLevelQMax` policy routes TWO-LEVEL: hot-cell
    overflow beyond the (post-spill) q_max budget is re-hosted on the
    queries' corner-cell neighbors, so a skewed stream no longer pads
    every device to the hottest cell's peak. The device program is the
    SAME either way — spill rows carry host-relative corner slots like
    any other row — so switching routers never recompiles per se; only
    the q_max trajectory differs. Route stays pure numpy in both modes.
    """
    if (policy is None) == (q_max is None):
        raise ValueError("pass exactly one of policy= (streaming) or q_max= (fixed)")
    if pad_multiple is None:
        pad_multiple = policy.pad_multiple if policy is not None else 8
    stacker = routing.make_halo_stacker(grid)
    two_level = isinstance(policy, routing.TwoLevelQMax)
    if two_level:
        from repro.core.blend import corner_ids_weights

    def route(q):
        pts = np.asarray(q, np.float32)
        cells = routing.owning_cells(grid, pts)
        if two_level:
            own = cells[1] * grid.gx + cells[0]
            corners = corner_ids_weights(grid, pts)
            qm, hosts = policy.fit_spill(grid, own, corners[0])
            table = routing.build_routing_table(
                grid, pts, q_max=qm, cells=cells, corners=corners,
                spill=True, hosts=hosts, pad_multiple=pad_multiple,
            )
        elif policy is not None:
            counts = np.bincount(
                cells[1] * grid.gx + cells[0], minlength=grid.num_partitions
            )
            qm = policy.fit(counts)
            table = routing.build_routing_table(
                grid, pts, q_max=qm, cells=cells, pad_multiple=pad_multiple
            )
        else:
            table = routing.build_routing_table(
                grid, pts, q_max=q_max, cells=cells, pad_multiple=pad_multiple
            )
        return table, (stacker(table.xq), table.corner_slot, table.corner_w)

    def submit(routed):
        table, (hx, cs, cw) = routed
        mean, var = blend_fn(cache_sh, hx, cs, cw)  # transfer + async dispatch
        return table, mean, var

    def collect(pending):
        table, mean, var = pending
        jax.block_until_ready((mean, var))
        return (
            routing.scatter_results(table, np.asarray(mean)),
            routing.scatter_results(table, np.asarray(var)),
        )

    return route, submit, collect


def as_batch_source(batches):
    """Normalize a batch SOURCE into an iterator of query batches.

    The pipelined loop used to demand a pre-built list — fine for
    benchmarks, useless for an endpoint whose batches are formed by live
    coalescing. Accepted shapes:

      * a sequence (list/tuple) — the original contract, replayed as-is;
      * an iterator/generator — consumed once (a live batcher can yield
        batches as its admission window closes);
      * a zero-arg callable — polled per batch; returning None ends the
        stream (the pull-model injection seam: the loop asks for the next
        batch exactly when it has host time to route it).
    """
    if callable(batches):
        def pull():
            while (b := batches()) is not None:
                yield b

        return pull()
    return iter(batches)


def pipelined_request_loop(
    route: Callable,
    submit: Callable,
    collect: Callable,
    batches,
    *,
    warm: bool = True,
    on_result: Callable | None = None,
) -> tuple[dict, float]:
    """The overlapped serving measurement loop (double-buffered).

    Batch t is submitted to the mesh, then batch t+1 is ROUTED ON THE HOST
    while the device program runs — jax's async dispatch means ``submit``
    returns without waiting for the result and the block happens only in
    ``collect``, when the result is consumed. Results are bitwise
    identical to the serial loop — scheduling never touches the math.

    ``batches`` is any :func:`as_batch_source` shape — a pre-built
    sequence (the benchmark lanes), or an INJECTABLE source (iterator /
    generator / zero-arg callable) whose batches may be formed while the
    loop runs; the next batch is pulled exactly at the overlap point,
    while the mesh evaluates the current one. ``warm=True`` needs a
    replayable first batch: it runs the stream's first batch once for
    compile+transfer warmup and then serves it again as batch 0 (the
    sequence semantics the benchmarks rely on).

    Per-request latency is the request's completion-to-completion SERVICE
    interval: the wall time the pipeline spends on it once it reaches the
    head of the queue (dispatch + device evaluation + result scatter).
    Host routing does not appear in it — that is the point of the
    overlap: it ran during the previous request's device window. The
    serial loop (:func:`timed_request_loop`) pays route + dispatch +
    device + scatter per request; the pipelined steady state pays
    max(route, device-window) per request.

    ``on_result(i, (mean, var))`` receives each scattered result (tests
    and the benchmark equivalence gate use it).

    Returns ({p50_ms, p95_ms, p99_ms}, points_per_s).
    """
    src = as_batch_source(batches)
    try:
        first = next(src)
    except StopIteration:
        raise ValueError("pipelined_request_loop needs a non-empty batch source") from None
    if warm:
        collect(submit(route(first)))
    lat = []
    points = 0
    t_all = time.time()
    nxt, nxt_points = route(first), len(first)
    mark = time.time()  # pipeline idle: batch 0's service starts here
    i = 0
    while nxt is not None:
        pending = submit(nxt)  # transfer + async dispatch: mesh starts batch i
        points += nxt_points
        b = next(src, None)
        if b is not None:
            nxt, nxt_points = route(b), len(b)  # host routes i+1 under batch i
        else:
            nxt = None
        out = collect(pending)  # sync point: batch i consumed
        if on_result is not None:
            on_result(i, out)
        now = time.time()
        lat.append(now - mark)
        mark = now
        i += 1
    wall = time.time() - t_all
    ms = np.sort(np.asarray(lat)) * 1e3
    pct = {
        "p50_ms": float(np.percentile(ms, 50)),
        "p95_ms": float(np.percentile(ms, 95)),
        "p99_ms": float(np.percentile(ms, 99)),
    }
    return pct, points / wall


def load_or_train(args, *, ensure_devices: bool = False, fit_cfg=None):
    """The shared fit-or-load front of both GP serving CLIs: returns
    (ds, fitted) where ds is None when serving from a persisted artifact
    (``--gp-artifact``; no retraining on that path). ``--gp-save``
    persists the freshly trained artifact. ``ensure_devices`` (the
    sharded caller) forces one virtual device per artifact partition and
    MUST then run before any other jax work — the artifact's grid side is
    peeked from pure JSON so the count can be forced first. ``fit_cfg``
    (a session file's fit section) replaces the flag-derived training
    config on the training path.
    """
    from repro import api

    if getattr(args, "gp_artifact", None):
        if ensure_devices:
            ensure_host_devices(api.peek_fit_config(args.gp_artifact).num_partitions)
        fitted = api.FittedPSVGP.load(args.gp_artifact)
        print(f"loaded artifact {args.gp_artifact}: grid="
              f"{fitted.grid.gx}x{fitted.grid.gy}, m={fitted.config.m} "
              "(serving without retraining)")
        ds = None
    else:
        ds, fitted = train_demo_surface(
            seed=args.seed, n=args.gp_n, grid_side=args.gp_grid,
            m=args.gp_m, train_iters=args.gp_train_iters, fit_cfg=fit_cfg,
        )
    if getattr(args, "gp_save", None):
        fitted.save(args.gp_save)
        print(f"artifact saved to {args.gp_save}")
    return ds, fitted


def query_batches(
    grid: PartitionGrid, ds=None, *, batch: int, requests: int,
    seed: int = 0, skew: float = 0.0,
) -> list:
    """The demo query stream the GP serving CLIs draw: zipf-skewed over
    cells when ``skew`` > 0 (the ``--gp-skew`` exponent), else uniform
    over the data domain (``ds``) or the grid bounds (``ds=None`` — the
    artifact-serving case, where no dataset exists). Plain parameters, so
    non-CLI callers can reuse it without fabricating an argparse
    namespace."""
    if skew > 0:
        from repro.data.spatial import zipf_query_stream

        return zipf_query_stream(grid, batch, requests, alpha=skew, seed=seed + 1)
    rng = np.random.default_rng(seed + 1)
    if ds is not None:
        lo, hi = ds.x.min(axis=0), ds.x.max(axis=0)
    else:
        lo = np.array([grid.x_edges[0], grid.y_edges[0]], np.float32)
        hi = np.array([grid.x_edges[-1], grid.y_edges[-1]], np.float32)
    return [
        rng.uniform(lo, hi, (batch, 2)).astype(np.float32)
        for _ in range(requests)
    ]


def session_configs(args, *, expect_mode: str):
    """The ``--config session.json`` lane shared by the serving CLIs:
    returns (fit_cfg, serve_cfg, net_cfg) — (None, None, None) without
    the flag. Loading is pure JSON (``api.load_session`` is
    stdlib-only), so the sharded caller can still force virtual devices
    afterwards (and the HTTP caller can read the bind address before
    jax initializes). A serve section whose mode contradicts the
    running entry point is an error, not a silent reroute — and so is
    ``--http`` against a session file with no ``net`` section: a
    recorded session must say where it binds, or the replay is not the
    session."""
    if not getattr(args, "config", None):
        return None, None, None
    from repro.api.config import load_session

    fit_cfg, serve_cfg, net_cfg = load_session(args.config)
    if serve_cfg is not None and serve_cfg.mode != expect_mode:
        raise SystemExit(
            f"--config {args.config}: serve section has mode="
            f"{serve_cfg.mode!r} but this entry point serves "
            f"{expect_mode!r} (pick the matching CLI or fix the session)"
        )
    if getattr(args, "http", False) and net_cfg is None:
        raise SystemExit(
            f"--http with --config {args.config}: the session file has no "
            "'net' section (host/port/max_body_bytes/read_timeout_s/"
            "keepalive — api.NetConfig). Add one, or drop --http to serve "
            "the in-process demo stream."
        )
    return fit_cfg, serve_cfg, net_cfg


def serve_sharded(args) -> dict:
    """Fit (or load) through ``repro.api`` and serve the routed query loop
    from the mesh-sharded cache — this CLI is a thin shim: flags parse
    into a ``ServeConfig`` and ``api.Server`` does the wiring.

    Mirrors ``serve.serve_gp`` (same flags) but serves from the
    distributed cache through the overlapped pipeline (``--gp-serial``
    falls back to the synchronous loop); prints and returns the
    latency/throughput record, including an allclose check against the
    replicated path on the first batch and the streaming-q_max policy
    counters.
    """
    fit_cfg, serve_cfg, _ = session_configs(args, expect_mode="sharded")
    if not getattr(args, "gp_artifact", None):
        grid_side = fit_cfg.grid if fit_cfg is not None else args.gp_grid
        ensure_host_devices(grid_side * grid_side)
    # (the artifact path sizes the device count from the artifact's own
    # grid — load_or_train peeks it from pure JSON before any jax work)

    from repro import api

    ds, fitted = load_or_train(args, ensure_devices=True, fit_cfg=fit_cfg)
    grid = fitted.grid
    if serve_cfg is None:
        serve_cfg = api.ServeConfig(
            mode="sharded",
            pipeline="serial" if getattr(args, "gp_serial", False) else "pipelined",
            router=getattr(args, "gp_router", "single"),
            backend="auto",
        )
    server = api.Server(fitted, serve_cfg)
    total_b, device_b = server.cache_bytes
    print(f"cache sharded over {server.mesh.size} devices: {total_b/1e6:.2f} MB total, "
          f"{device_b/1e3:.1f} kB/device (1/{total_b // max(device_b,1)} of replicated)")

    skew = getattr(args, "gp_skew", 0.0)
    batches = query_batches(
        grid, ds, batch=args.gp_batch, requests=args.gp_requests,
        seed=args.seed, skew=skew,
    )

    # warmup + equivalence check against the replicated path
    m0, v0 = server.submit(batches[0])
    m_rep, v_rep = fitted.predict(jnp.asarray(batches[0]))
    mean_err = float(np.abs(m0 - np.asarray(m_rep)).max())
    var_err = float(np.abs(v0 - np.asarray(v_rep)).max())
    print(f"sharded vs replicated on warmup batch: max|dmean|={mean_err:.2e} "
          f"max|dvar|={var_err:.2e}")

    # already warmed: the equivalence check above compiled and ran batch 0
    report = server.stream(batches, warm=False)
    pct, qps = report["latency_ms"], report["points_per_s"]
    policy = server.policy
    rec = {
        "mesh": f"{grid.gy}x{grid.gx}",
        "devices": server.mesh.size,
        "mode": serve_cfg.pipeline,
        "router": serve_cfg.router,
        "backend": server.backend,
        "serve_config": serve_cfg.to_dict(),
        "skew_alpha": skew,
        "qmax_policy": policy.stats(),
        "waste_rows_last_batch": server.mesh.size * policy.q_max - args.gp_batch,
        "latency_ms": pct,
        "points_per_s": qps,
        "mean_err_vs_replicated": mean_err,
        "var_err_vs_replicated": var_err,
        "cache_bytes_total": total_b,
        "cache_bytes_per_device": device_b,
    }
    print(f"served {args.gp_requests} requests x {args.gp_batch} points "
          f"({rec['mode']}; q_max={policy.q_max}, "
          f"{policy.compiles} compiles, {policy.overflows} overflows)")
    print(f"latency/request ms: p50={pct['p50_ms']:.2f} "
          f"p95={pct['p95_ms']:.2f} p99={pct['p99_ms']:.2f}")
    print(f"throughput: {qps:,.0f} points/s")
    return rec


def timed_request_loop(answer: Callable, batches, *, warm: bool = True) -> tuple[dict, float]:
    """The SERIAL serving measurement loop (shared by ``serve --gp``, the
    ``--gp-serial`` sharded mode and ``benchmarks.bench_serve``'s
    replicated + serial lanes, so their SLO reports stay comparable; the
    overlapped counterpart is :func:`pipelined_request_loop`): warm up on
    batches[0] (compile), then time
    each request end to end. Pass ``warm=False`` when the caller already
    ran a batch through ``answer`` (e.g. for an equivalence check) — the
    program is compiled and a second warmup pass would just burn a
    request's worth of wall clock.

    Returns ({p50_ms, p95_ms, p99_ms}, points_per_s).
    """
    if warm:
        answer(batches[0])
    lat = []
    t_all = time.time()
    for q in batches:
        t0 = time.time()
        answer(q)
        lat.append(time.time() - t0)
    wall = time.time() - t_all
    ms = np.sort(np.asarray(lat)) * 1e3
    pct = {
        "p50_ms": float(np.percentile(ms, 50)),
        "p95_ms": float(np.percentile(ms, 95)),
        "p99_ms": float(np.percentile(ms, 99)),
    }
    return pct, sum(len(q) for q in batches) / wall


def prepass_routing(
    grid: PartitionGrid, batches, *, headroom: float = 1.25, pad_multiple: int = 8
) -> tuple[int, list]:
    """Whole-stream q_max prepass, for streams known up front (benchmarks,
    batch jobs): one q_max covering every batch = single compile, the
    observed max bucket count with headroom, rounded with the SAME
    alignment rule ``routing.build_routing_table`` applies (pass the same
    ``pad_multiple`` to both, or the table re-rounds and recompiles).

    Returns (q_max, cells) where ``cells[i]`` is ``owning_cells`` for
    ``batches[i]`` — pass it into ``build_routing_table(..., cells=...)``
    so the binning this prepass already did is not repeated per request
    (it used to be: the prepass binned every batch, threw the result away,
    and the table re-binned on the serving critical path). Live streams
    should use ``routing.StreamingQMax`` instead — this prepass cannot see
    batches that have not arrived yet.
    """
    need, cells = 1, []
    for q in batches:
        ix, iy = routing.owning_cells(grid, np.asarray(q, np.float32))
        cells.append((ix, iy))
        c = np.bincount(iy * grid.gx + ix, minlength=grid.num_partitions)
        need = max(need, int(c.max()))
    return routing.ceil_to(int(np.ceil(need * headroom)), pad_multiple), cells


def fixed_q_max(
    grid: PartitionGrid, batches, *, headroom: float = 1.25, pad_multiple: int = 8
) -> int:
    """``prepass_routing`` when only the q_max is wanted (the cells are
    discarded — callers on the serving path should take both)."""
    return prepass_routing(
        grid, batches, headroom=headroom, pad_multiple=pad_multiple
    )[0]


def cache_memory_bytes(cache: posterior.PosteriorCache) -> tuple[int, int]:
    """(total, per-device-addressable) bytes of the cache factor leaves."""
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(cache))
    per_dev = 0
    for leaf in jax.tree.leaves(cache):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            per_dev += shards[0].data.nbytes
        else:
            per_dev += leaf.nbytes
    return total, per_dev


def add_gp_args(ap: argparse.ArgumentParser) -> None:
    """The --gp-* serving flags, shared with ``repro.launch.serve`` (which
    defines --seed itself for the LM path, so it is added separately)."""
    ap.add_argument("--gp-n", type=int, default=20_000, help="training observations")
    ap.add_argument("--gp-grid", type=int, default=8, help="partition grid is gp-grid^2")
    ap.add_argument("--gp-m", type=int, default=10, help="inducing points per partition")
    ap.add_argument("--gp-train-iters", type=int, default=200)
    ap.add_argument("--gp-batch", type=int, default=2048, help="query points per request")
    ap.add_argument("--gp-requests", type=int, default=50)
    ap.add_argument("--gp-serial", action="store_true",
                    help="sharded mode: run the synchronous request loop "
                         "instead of the overlapped (double-buffered) pipeline")
    ap.add_argument("--gp-skew", type=float, default=0.0, metavar="ALPHA",
                    help="query stream skew: zipf exponent over cells "
                         "(0 = uniform over the domain, the default)")
    ap.add_argument("--gp-router", choices=("single", "two-level"),
                    default="single",
                    help="q_max routing policy: 'single' pads every device "
                         "block to the hottest cell; 'two-level' spills "
                         "hot-cell overflow onto corner-cell neighbors "
                         "(routing.TwoLevelQMax), capping padded-row waste "
                         "under skewed streams")
    ap.add_argument("--gp-save", metavar="DIR", default=None,
                    help="persist the trained artifact (repro.api "
                         "FittedPSVGP.save: FitConfig + grid + params + "
                         "cached factors) to DIR after training")
    ap.add_argument("--gp-artifact", metavar="DIR", default=None,
                    help="serve from a persisted artifact instead of "
                         "training (repro.api Server.from_artifact); "
                         "ignores the --gp-n/--gp-m/--gp-train-iters "
                         "training flags")
    ap.add_argument("--config", metavar="SESSION_JSON", default=None,
                    help="session file with optional 'fit', 'serve' and "
                         "'net' sections (repro.api load_session). The fit "
                         "section replaces the --gp-grid/--gp-m/"
                         "--gp-train-iters training flags; the serve "
                         "section replaces --gp-serial/--gp-router (its "
                         "mode must match the chosen entry point); the net "
                         "section is required when combined with --http")
    ap.add_argument("--http", action="store_true",
                    help="serve over HTTP (repro.net.server: POST /predict "
                         "+ GET /healthz + GET /slo on the 'net' section's "
                         "or NetConfig's default bind address) instead of "
                         "running the in-process demo query stream")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    add_gp_args(ap)
    args = ap.parse_args()
    if args.gp_requests < 1 or args.gp_batch < 1:
        ap.error("--gp-requests and --gp-batch must be >= 1")
    use_compile_cache()
    if args.http:
        # imports and argparse above never initialize the jax backend, so
        # the HTTP driver can still force the virtual device count.
        from repro.net.server import serve_http

        serve_http(args, expect_mode="sharded")
        return
    serve_sharded(args)


if __name__ == "__main__":
    main()
