"""The ``repro.api`` front door: config validation + JSON round-trip,
fit -> artifact -> serve, and the GOLDEN equivalence gates of the
api_redesign — the new ``Server`` path must be bitwise-identical to the
pre-refactor driver compositions it replaced.

Three layers:

  * config: frozen dataclasses validate on construction, round-trip
    through JSON, reject unknown fields, and resolve ``backend="auto"``
    to the fastest COMPILED lane (warning once when an explicit Pallas
    backend falls back to interpret mode off-TPU);
  * replicated lifecycle (in-process): ``fit`` reproduces the pre-api
    training recipe bitwise on a fixed seed; ``save``/``load`` restores a
    PosteriorCache whose predictions are bitwise-identical to the
    in-memory model; the replicated ``Server`` answers exactly like
    ``blend.predict_blended``;
  * sharded golden + artifact round-trip (subprocess — the mesh needs
    virtual host devices before jax initializes): ``Server`` results
    bitwise == the pre-refactor ``make_request_stages`` + serial/
    pipelined loop compositions, for single AND two-level routers, plus
    the fixed-q_max prepass lane; ``Server.from_artifact`` serves a
    two-level pipelined stream bitwise == the in-memory server (no
    retraining anywhere on that path); the "pallas"/"fused" kernel
    backends match "ref" to float32 accuracy through the same program.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import api
from repro.api import config as api_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------


def test_fit_config_validation_and_json_round_trip():
    cfg = api.FitConfig(grid=3, m=4, delta=0.5, train_iters=10, seed=7)
    assert api.FitConfig.from_json(cfg.to_json()) == cfg
    assert cfg.num_partitions == 9
    for bad in (
        dict(grid=0),
        dict(m=0),
        dict(delta=1.5),
        dict(delta=-0.1),
        dict(train_iters=-1),
        dict(batch_size=0),
        dict(learning_rate=0.0),
        dict(comm="carrier-pigeon"),
        dict(covariance="linear"),
        dict(jitter=0.0),
    ):
        with pytest.raises(ValueError):
            api.FitConfig(**bad)
    with pytest.raises(ValueError, match="unknown FitConfig fields"):
        api.FitConfig.from_dict({"grid": 3, "banana": 1})


def test_serve_config_validation_and_json_round_trip():
    cfg = api.ServeConfig(
        mode="sharded", pipeline="pipelined", router="two-level",
        backend="fused", headroom=1.5, pad_multiple=16,
    )
    assert api.ServeConfig.from_json(cfg.to_json()) == cfg
    # q_max=None must survive the JSON round trip too
    cfg2 = api.ServeConfig(mode="sharded", q_max=64)
    assert api.ServeConfig.from_json(cfg2.to_json()) == cfg2
    for bad in (
        dict(mode="clustered"),
        dict(pipeline="async"),
        dict(router="three-level"),
        dict(backend="cuda"),
        dict(headroom=0.9),
        dict(pad_multiple=0),
        # replicated mode has no mesh stage / device blocks / kernel lanes
        dict(mode="replicated", pipeline="pipelined"),
        dict(mode="replicated", router="two-level"),
        dict(mode="replicated", backend="fused"),
        dict(mode="replicated", backend="pallas"),
        # fixed q_max is the sharded single-router prepass lane only
        dict(mode="replicated", q_max=8),
        dict(mode="sharded", router="two-level", q_max=8),
        dict(mode="sharded", q_max=0),
    ):
        with pytest.raises(ValueError):
            api.ServeConfig(**bad)
    with pytest.raises(ValueError, match="unknown ServeConfig fields"):
        api.ServeConfig.from_dict({"mode": "sharded", "routerr": "single"})


def test_serve_config_policy_and_backend_resolution():
    import jax

    from repro.core import routing

    on_tpu = jax.default_backend() == "tpu"
    # auto -> the fastest lane that actually compiles here
    auto = api.ServeConfig(mode="sharded", backend="auto").resolve_backend()
    assert auto == ("fused" if on_tpu else "ref")
    # replicated always serves the blend path
    assert api.ServeConfig(mode="replicated").resolve_backend() == "ref"
    # explicit interpret-mode backends are honored but warn ONCE
    if not on_tpu:
        api_config._WARNED_INTERPRET.clear()
        with pytest.warns(RuntimeWarning, match="INTERPRET"):
            got = api.ServeConfig(mode="sharded", backend="fused").resolve_backend()
        assert got == "fused"
        import warnings as _warnings

        with _warnings.catch_warnings():
            _warnings.simplefilter("error")  # a second warning would raise
            assert api.ServeConfig(
                mode="sharded", backend="fused"
            ).resolve_backend() == "fused"
    # the policy factory mirrors the router field
    assert isinstance(
        api.ServeConfig(mode="sharded", router="two-level").make_policy(),
        routing.TwoLevelQMax,
    )
    pol = api.ServeConfig(mode="sharded", headroom=2.0, pad_multiple=4).make_policy()
    assert isinstance(pol, routing.StreamingQMax)
    assert pol.headroom == 2.0 and pol.pad_multiple == 4
    assert api.ServeConfig(mode="sharded", q_max=32).make_policy() is None


# ---------------------------------------------------------------------------
# replicated lifecycle (no mesh needed)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_fitted():
    from repro.data.spatial import e3sm_like_field

    ds = e3sm_like_field(n=700, seed=0)
    cfg = api.FitConfig(grid=3, m=4, train_iters=60, seed=0)
    return ds, api.fit(cfg, ds)


def test_fit_matches_pre_api_recipe_bitwise(tiny_fitted):
    """api.fit is the OLD driver recipe behind a config — same grid, same
    padded partitioning, same init key, same SGD stream — so a fixed seed
    reproduces the pre-refactor trained state bitwise."""
    import jax

    from repro.core import psvgp, svgp
    from repro.core.partition import make_grid, partition_data

    ds, fitted = tiny_fitted
    grid = make_grid(ds.x, 3, 3)
    data = partition_data(ds.x, ds.y, grid)
    pcfg = psvgp.PSVGPConfig(
        svgp=svgp.SVGPConfig(num_inducing=4, input_dim=2),
        delta=0.25, batch_size=32, learning_rate=0.05,
    )
    static = psvgp.build(pcfg, data)
    state = psvgp.init(jax.random.PRNGKey(0), pcfg, data)
    state = psvgp.fit(static, state, data, 60)
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(fitted.state.params), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(grid.x_edges), np.asarray(fitted.grid.x_edges))


def test_artifact_round_trip_replicated_bitwise(tiny_fitted, tmp_path):
    """save -> load restores config, grid and a PosteriorCache whose
    predictions are bitwise-identical — the artifact IS the model."""
    ds, fitted = tiny_fitted
    path = fitted.save(str(tmp_path / "artifact"))
    assert api.peek_fit_config(path) == fitted.config

    loaded = api.FittedPSVGP.load(path)
    assert loaded.config == fitted.config
    np.testing.assert_array_equal(loaded.grid.x_edges, fitted.grid.x_edges)
    np.testing.assert_array_equal(loaded.grid.y_edges, fitted.grid.y_edges)
    import jax

    for a, b in zip(jax.tree.leaves(fitted.cache), jax.tree.leaves(loaded.cache), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    q = ds.x[:128]
    m0, v0 = fitted.predict(q)
    m1, v1 = loaded.predict(q)
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


def test_replicated_server_matches_predict_blended_bitwise(tiny_fitted, tmp_path):
    from repro.core.blend import predict_blended

    ds, fitted = tiny_fitted
    q = ds.x[:96]
    server = api.Server(fitted, api.ServeConfig(mode="replicated"))
    sm, sv = server.submit(q)
    bm, bv = predict_blended(
        fitted.static, fitted.state, fitted.grid, q, cache=fitted.cache
    )
    np.testing.assert_array_equal(sm, np.asarray(bm))
    np.testing.assert_array_equal(sv, np.asarray(bv))

    # from_artifact serves without retraining, bitwise the same answers
    path = fitted.save(str(tmp_path / "a"))
    loaded_server = api.Server.from_artifact(path)
    lm, lv = loaded_server.submit(q)
    np.testing.assert_array_equal(lm, sm)
    np.testing.assert_array_equal(lv, sv)

    got = {}
    report = loaded_server.stream(
        [ds.x[:64], ds.x[64:128]], on_result=lambda i, out: got.setdefault(i, out)
    )
    assert sorted(got) == [0, 1]
    assert set(report["latency_ms"]) == {"p50_ms", "p95_ms", "p99_ms"}
    assert report["points_per_s"] > 0
    assert report["serve_config"] == loaded_server.config.to_dict()
    assert report["backend"] == "ref" and report["qmax_policy"] is None


def test_fit_rejects_bad_data_shapes():
    with pytest.raises(ValueError, match=r"\(N, 2\)"):
        api.fit(api.FitConfig(grid=2, m=2, train_iters=0),
                (np.zeros((10, 3)), np.zeros(10)))


def test_predict_cached_slots_backend_lanes_agree():
    """The three kernel lanes of the device-side hot path compute the same
    numbers (Pallas lanes in interpret mode here): backend='pallas' is the
    single-block kernel through the reshape round-trip, 'fused' the
    slot-stacked launch, 'ref' the jnp oracle."""
    import jax

    from repro.core import posterior, svgp
    from repro.gp.covariances import make_covariance

    cfg = svgp.SVGPConfig(num_inducing=5, input_dim=2)
    params = svgp.init_svgp_params(jax.random.PRNGKey(1), cfg)
    cov_fn = make_covariance("rbf")
    cache = posterior.build_cache(params, cov_fn)
    xslots = np.asarray(
        np.random.default_rng(2).normal(size=(9, 24, 2)), np.float32
    )
    m_ref, v_ref = posterior.predict_cached_slots(cache, cov_fn, xslots)
    for backend in ("pallas", "fused"):
        m_b, v_b = posterior.predict_cached_slots(
            cache, cov_fn, xslots, backend=backend
        )
        np.testing.assert_allclose(np.asarray(m_b), np.asarray(m_ref), atol=1e-5)
        # Relative: this untrained model's Kmm is near-singular (U entries
        # ~2e3 against variances ~1e3, a ~3e4-fold cancellation inside the
        # projection), so every f32 lane sits ~1e-4 (relative) from the
        # float64 answer and two lanes may differ by about twice that.
        np.testing.assert_allclose(np.asarray(v_b), np.asarray(v_ref), rtol=3e-4)
    with pytest.raises(ValueError, match="not both"):
        posterior.predict_cached_slots(
            cache, cov_fn, xslots, use_pallas=True, backend="ref"
        )
    with pytest.raises(ValueError, match="backend"):
        posterior.predict_cached_slots(cache, cov_fn, xslots, backend="mosaic")


def test_request_stages_honor_policy_pad_multiple():
    """A non-default pad_multiple must reach build_routing_table, not just
    the policy — otherwise the table's own default of 8 re-rounds the
    policy's q_max and the policy counters describe block shapes that were
    never compiled. (The route stage is pure host: no mesh needed.)"""
    from repro.core import routing
    from repro.core.partition import make_grid
    from repro.launch import serve_sharded as ss

    rng = np.random.default_rng(0)
    pts = rng.uniform(0.0, 1.0, size=(25, 2)).astype(np.float32)
    grid = make_grid(pts, 3, 3)

    policy = routing.StreamingQMax(headroom=1.0, pad_multiple=4)
    route, _, _ = ss.make_request_stages(
        grid, blend_fn=None, cache_sh=None, policy=policy
    )
    table, _ = route(pts)
    assert table.q_max % 4 == 0
    assert table.q_max == policy.q_max  # counters match the compiled shape

    route_f, _, _ = ss.make_request_stages(
        grid, blend_fn=None, cache_sh=None, q_max=4, pad_multiple=4
    )
    # one point per cell: every bucket fits the fixed q_max=4 budget
    pts_f = np.array(
        [[0.1, 0.1], [0.5, 0.5], [0.9, 0.9], [0.1, 0.9]], np.float32
    )
    table_f, _ = route_f(pts_f)
    assert table_f.q_max == 4


# ---------------------------------------------------------------------------
# sharded golden equivalence + artifact round-trip (subprocess: the mesh
# needs virtual host devices before jax initializes)
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=9"
    import tempfile

    import jax
    import numpy as np

    from repro import api
    from repro.core import psvgp, routing
    from repro.data.spatial import e3sm_like_field, zipf_query_stream
    from repro.launch import serve_sharded as ss

    GS, M, IT = 3, 4, 120
    ds = e3sm_like_field(n=1000, seed=0)
    fitted = api.fit(api.FitConfig(grid=GS, m=M, train_iters=IT, seed=0), ds)
    grid = fitted.grid

    # ---- the PRE-REFACTOR composition, built from the same primitives the
    # old drivers wired by hand ----------------------------------------------
    cache = psvgp.posterior_cache(fitted.static, fitted.state)
    mesh = ss.mesh_for_grid(grid)
    cache_sh = ss.shard_cache(cache, mesh)
    jax.block_until_ready(cache_sh)
    blend_fn = ss.make_sharded_blend(
        mesh, mesh.axis_names, grid, fitted.static.cov_fn, cache_sh
    )

    rng = np.random.default_rng(3)
    lo, hi = ds.x.min(axis=0), ds.x.max(axis=0)
    uni = [rng.uniform(lo, hi, (160, 2)).astype(np.float32) for _ in range(4)]
    zipf = zipf_query_stream(grid, 160, 4, alpha=1.2, seed=5)

    def old_results(batches, router, pipeline, q_max=None):
        if q_max is not None:
            policy = None
        elif router == "two-level":
            policy = routing.TwoLevelQMax()
        else:
            policy = routing.StreamingQMax()
        route, submit, collect = ss.make_request_stages(
            grid, blend_fn, cache_sh, policy=policy, q_max=q_max)
        if pipeline == "serial":
            return [collect(submit(route(q))) for q in batches]
        got = {}
        ss.pipelined_request_loop(route, submit, collect, batches, warm=False,
                                  on_result=lambda i, out: got.setdefault(i, out))
        return [got[i] for i in range(len(batches))]

    def new_results(fitted_, batches, router, pipeline, backend="ref", q_max=None):
        srv = api.Server(fitted_, api.ServeConfig(
            mode="sharded", pipeline=pipeline, router=router,
            backend=backend, q_max=q_max))
        got = {}
        srv.stream(batches, warm=False,
                   on_result=lambda i, out: got.setdefault(i, out))
        return [got[i] for i in range(len(batches))]

    def assert_bitwise(old, new, tag):
        for i, ((mo, vo), (mn, vn)) in enumerate(zip(old, new, strict=True)):
            assert np.array_equal(mo, mn) and np.array_equal(vo, vn), (tag, i)

    # GOLDEN: serial and pipelined, single and two-level router
    for router, batches in (("single", uni), ("two-level", zipf)):
        for pipeline in ("serial", "pipelined"):
            assert_bitwise(
                old_results(batches, router, pipeline),
                new_results(fitted, batches, router, pipeline),
                (router, pipeline),
            )
    print("golden: Server bitwise == pre-refactor loops (2 routers x 2 loops)")

    # GOLDEN: the fixed-q_max whole-stream-prepass lane
    qm, cells = ss.prepass_routing(grid, uni)
    assert_bitwise(
        old_results(uni, "single", "serial", q_max=qm),
        new_results(fitted, uni, "single", "serial", q_max=qm),
        "fixed-q_max",
    )
    print("golden: fixed-q_max prepass lane bitwise OK")

    # kernel backends through the same device program: float32-accurate
    ref = new_results(fitted, uni[:2], "single", "pipelined")
    for backend in ("pallas", "fused"):
        got = new_results(fitted, uni[:2], "single", "pipelined", backend=backend)
        for (mr, vr), (mb, vb) in zip(ref, got, strict=True):
            assert np.abs(mb - mr).max() <= 1e-4, backend
            assert np.abs(vb - vr).max() <= 1e-4, backend
    print("backends: pallas/fused match ref through the sharded program")

    # ARTIFACT round-trip: Server.from_artifact serves the two-level
    # pipelined stream bitwise == the in-memory server, without retraining
    with tempfile.TemporaryDirectory() as td:
        fitted.save(td)
        mem = new_results(fitted, zipf, "two-level", "pipelined")
        srv_art = api.Server.from_artifact(td, api.ServeConfig(
            mode="sharded", pipeline="pipelined", router="two-level",
            backend="ref"))
        got = {}
        srv_art.stream(zipf, warm=False,
                       on_result=lambda i, out: got.setdefault(i, out))
        art = [got[i] for i in range(len(zipf))]
        assert_bitwise(mem, art, "artifact")
        # and the replicated view of the same artifact, also bitwise
        rep_art = api.Server.from_artifact(td)
        m_a, v_a = rep_art.submit(uni[0])
        m_m, v_m = fitted.predict(uni[0])
        assert np.array_equal(m_a, np.asarray(m_m))
        assert np.array_equal(v_a, np.asarray(v_m))
    print("artifact: sharded two-level stream + replicated bitwise OK")
    print("SHARDED-API-OK")
    """
)


@pytest.mark.smoke
def test_sharded_server_golden_and_artifact_round_trip():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=570,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    assert "SHARDED-API-OK" in r.stdout
