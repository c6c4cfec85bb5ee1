"""The overlapped serving pipeline — host-side logic, no device mesh.

The driver (``serve_sharded.pipelined_request_loop``) and the stage
factory are pure host scheduling around an opaque device program, so the
double-buffering contract is testable with stub stages in the default
lane: results bitwise-identical to serial on the same stream and in
order, batch t+1 routed BEFORE batch t's result is collected, the
streaming q_max policy driving recompiles boundedly. The real-mesh half
(shard_map program, collectives) is the slow lane in
tests/test_serve_sharded.py.

Also covers the shard_map in_spec derivation (``cache_in_specs``): specs
must come from the pytree STRUCTURE of the cache being served, never from
a hand-built field-by-field literal that a future PosteriorCache field
would silently desync from.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import posterior, routing, svgp
from repro.gp.covariances import CovarianceParams, make_covariance
from repro.launch import serve_sharded as ss


def _stub_stages(log):
    """Stage callables that tag events instead of touching devices.
    submit 'evaluates' instantly (sum per batch) so collect is a no-op
    unwrap — the loop's scheduling is what is under test."""

    def route(q):
        log.append(("route", int(q[0])))
        return ("table", q)

    def submit(routed):
        _, q = routed
        log.append(("submit", int(q[0])))
        return ("pending", q, q.sum())

    def collect(pending):
        _, q, s = pending
        log.append(("collect", int(q[0])))
        return (q * 2.0, np.full_like(q, s))

    return route, submit, collect


def _stream(n=6, size=5):
    rng = np.random.default_rng(0)
    out = []
    for i in range(n):
        q = rng.normal(size=(size,)).astype(np.float32)
        q[0] = i  # tag each batch with its index for the event log
        out.append(q)
    return out


def test_pipelined_results_bitwise_equal_serial_and_ordered():
    batches = _stream()
    route, submit, collect = _stub_stages([])
    serial = [collect(submit(route(q))) for q in batches]

    got = {}
    pct, qps = ss.pipelined_request_loop(
        route, submit, collect, batches,
        warm=False, on_result=lambda i, out: got.setdefault(i, out),
    )
    assert sorted(got) == list(range(len(batches)))  # every result, in order
    for i, (m_s, v_s) in enumerate(serial):
        np.testing.assert_array_equal(got[i][0], m_s)
        np.testing.assert_array_equal(got[i][1], v_s)
    assert set(pct) == {"p50_ms", "p95_ms", "p99_ms"} and qps > 0


def test_pipelined_loop_overlaps_route_with_inflight_batch():
    """The point of the pipeline: batch t+1 is routed AFTER batch t is
    submitted but BEFORE batch t's result is collected — for every t."""
    batches = _stream()
    log = []
    route, submit, collect = _stub_stages(log)
    ss.pipelined_request_loop(route, submit, collect, batches, warm=False)
    for t in range(len(batches) - 1):
        i_sub = log.index(("submit", t))
        i_rt = log.index(("route", t + 1))
        i_col = log.index(("collect", t))
        assert i_sub < i_rt < i_col, (t, log)


def test_pipelined_warm_runs_batch0_through_all_stages():
    batches = _stream(3)
    log = []
    route, submit, collect = _stub_stages(log)
    ss.pipelined_request_loop(route, submit, collect, batches, warm=True)
    # warm pass + measured pass both start with batch 0
    assert [e for e in log if e[0] == "route"][:2] == [("route", 0), ("route", 0)]


def test_make_request_stages_policy_xor_qmax():
    with pytest.raises(ValueError, match="exactly one"):
        ss.make_request_stages(None, None, None)
    with pytest.raises(ValueError, match="exactly one"):
        ss.make_request_stages(
            None, None, None, policy=routing.StreamingQMax(), q_max=8
        )


def _tiny_cache(key=0, m=5):
    cov_fn = make_covariance("rbf")
    params = svgp.init_svgp_params(
        jax.random.PRNGKey(key), svgp.SVGPConfig(num_inducing=m, input_dim=2)
    )
    return posterior.build_cache(params, cov_fn)


def test_cache_in_specs_derived_from_structure():
    """The spec tree must mirror the cache pytree exactly (same treedef,
    the given spec at every leaf). The expected literal below is the
    regression oracle: if PosteriorCache grows a field, this test fails
    and forces a conscious decision about how the new field shards."""
    cache = jax.tree.map(lambda a: jnp.stack([a, a]), _tiny_cache())
    sentinel = object()
    specs = ss.cache_in_specs(cache, sentinel)
    assert jax.tree.structure(specs) == jax.tree.structure(cache)
    assert all(s is sentinel for s in jax.tree.leaves(specs))
    expected = posterior.PosteriorCache(
        z=sentinel, w=sentinel, u=sentinel, c=sentinel,
        cov=CovarianceParams(log_lengthscale=sentinel, log_variance=sentinel),
        log_beta=sentinel,
    )
    assert jax.tree.structure(specs) == jax.tree.structure(expected), (
        "PosteriorCache grew a field: decide how it shards in the serving "
        "program (cache_in_specs gives it the leading-P spec automatically; "
        "update this oracle once that is confirmed correct)"
    )


def test_two_level_policy_drives_pipeline_shapes():
    """Host half of two-level serving: a skewed stream routed through the
    stage factory with a TwoLevelQMax policy keeps q_max well under the
    hot-cell peak, every table honors the policy's mark, the halo stack
    keeps the (P, 9, q_max, 2) contract, and the scatter inverse recovers
    every batch bitwise."""
    from repro.core.partition import make_grid

    rng = np.random.default_rng(3)
    base = rng.uniform(-1.0, 1.0, size=(1500, 2)).astype(np.float32)
    # hot spot well inside the CENTER cell of the 3x3 grid over [-1, 1]^2
    hot = rng.uniform(-0.25, -0.05, size=(3500, 2)).astype(np.float32)
    pts = np.concatenate([base, hot])
    rng.shuffle(pts)
    grid = make_grid(pts, 3, 3)
    policy = routing.TwoLevelQMax()
    stacker = routing.make_halo_stacker(grid)
    from repro.core.blend import corner_ids_weights

    peak = 0
    for nsz in (800, 800, 5000, 5000):
        q = pts[:nsz]
        cells = routing.owning_cells(grid, q)
        own = cells[1] * grid.gx + cells[0]
        ids, w = corner_ids_weights(grid, q)
        peak = max(peak, int(np.bincount(own, minlength=9).max()))
        qm, hosts = policy.fit_spill(grid, own, ids)
        table = routing.build_routing_table(
            grid, q, q_max=qm, cells=cells, corners=(ids, w),
            spill=True, hosts=hosts,
        )
        assert table.q_max == qm
        assert int(table.counts.max()) <= qm
        np.testing.assert_array_equal(routing.scatter_results(table, table.xq), q)
        hx = stacker(table.xq)
        assert hx.shape == (grid.num_partitions, 9, qm, 2)
    assert policy.q_max < peak  # the budget stayed under the hot peak
    assert policy.compiles <= 2 and policy.spilled > 0


def test_streaming_policy_drives_pipeline_shapes():
    """End-to-end host half: a growing stream recompiles boundedly and
    every batch's table honors the policy's q_max."""
    rng = np.random.default_rng(1)
    pts = rng.uniform(-1.0, 1.0, size=(4000, 2)).astype(np.float32)
    from repro.core.partition import make_grid

    grid = make_grid(pts, 3, 3)
    policy = routing.StreamingQMax()
    stacker = routing.make_halo_stacker(grid)
    sizes = [40, 60, 60, 500, 500, 3000, 3000]
    q_maxes = []
    for nsz in sizes:
        q = pts[:nsz]
        cells = routing.owning_cells(grid, q)
        counts = np.bincount(
            cells[1] * grid.gx + cells[0], minlength=grid.num_partitions
        )
        qm = policy.fit(counts)
        table = routing.build_routing_table(grid, q, q_max=qm, cells=cells)
        assert table.q_max == qm
        hx = stacker(table.xq)
        assert hx.shape == (grid.num_partitions, 9, qm, 2)
        q_maxes.append(qm)
    assert policy.compiles == len(set(q_maxes))  # every shape counted once
    assert policy.compiles <= 4  # 3 growth steps + first on this stream
    assert policy.overflows == policy.compiles - 1


@pytest.mark.parametrize("cpu", [True, False], ids=["cpu", "accelerator"])
def test_ensure_host_devices_flag_only_on_cpu(monkeypatch, cpu):
    """The virtual-device flag is a CPU-platform device: on an accelerator
    XLA_FLAGS stays untouched (the mesh is the real chips), and a
    shortfall names the platform and count found instead of advising the
    CPU flag."""
    monkeypatch.setattr(ss, "_cpu_platform_in_use", lambda: cpu)
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/dev/null")
    have = jax.device_count()
    ss.ensure_host_devices(have)  # enough devices: returns quietly
    with pytest.raises(RuntimeError) as err:
        ss.ensure_host_devices(have + 1)
    flags = os.environ["XLA_FLAGS"]
    if cpu:
        assert f"--xla_force_host_platform_device_count={have + 1}" in flags
        assert "xla_force_host_platform_device_count" in str(err.value)
    else:
        assert flags == "--xla_dump_to=/dev/null"
        assert f"found {have} {jax.default_backend()} device(s)" in str(err.value)
        assert "xla_force_host_platform_device_count" not in str(err.value)


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_use_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache sits at the checkout's fixed .jax_cache/."""
    from repro.launch import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        return
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        assert use_compile_cache() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
