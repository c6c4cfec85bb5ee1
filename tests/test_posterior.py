"""PosteriorCache equivalence: cached prediction == uncached SVGP math.

The cache path (repro.core.posterior) must reproduce the solve-based
marginal q(f) of repro.core.svgp.q_f — same mean, same variance — for both
parameterizations, and the fused Pallas prediction kernel must match the
jnp reference through the padding/dispatch layer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import posterior, psvgp, svgp
from repro.core.blend import predict_blended
from repro.core.partition import make_grid, partition_data
from repro.data.spatial import e3sm_like_field
from repro.gp.covariances import make_covariance
from repro.kernels import ops


def _grid_z(m, d, key):
    side = int(np.ceil(m ** (1.0 / d)))
    axes = [jnp.linspace(-2, 2, side)] * d
    zz = jnp.stack(jnp.meshgrid(*axes), -1).reshape(-1, d)[:m]
    return zz + 0.05 * jax.random.normal(key, zz.shape)


def _model(key, m=12, d=2, covariance="rbf"):
    """A converged-looking model: grid-spread z with a matched lengthscale
    (well-conditioned Kmm), SMOOTH m_star, SMALL S.

    A converged posterior has m_star ~ f(z) for a smooth f (so the
    projected mean Kmm^{-1} m_star stays O(1)), S well below I, and
    inducing points its lengthscale can resolve. Random independent
    m_star / clumped z under a long lengthscale / near-init S ~ I make
    every f32 formulation — cached, solve-based, and the f64 oracle cast
    down — disagree at 1e-3 scale through sheer cancellation; serving
    never sees such states."""
    ks = jax.random.split(key, 3)
    cfg = svgp.SVGPConfig(
        num_inducing=m, input_dim=d, covariance=covariance, init_lengthscale=0.5
    )
    params = svgp.init_svgp_params(ks[0], cfg)
    z = _grid_z(m, d, ks[1])
    m_star = jnp.sin(2.0 * z[:, 0]) + 0.5 * jnp.cos(3.0 * z[:, min(1, d - 1)])
    s_tril = 0.05 * jax.random.normal(ks[2], (m, m)) - 2.0 * jnp.eye(m)
    return cfg, params._replace(z=z, m_star=m_star, s_tril=s_tril)


@pytest.mark.parametrize("whitened", [False, True])
@pytest.mark.parametrize("covariance", ["rbf", "matern52"])
def test_predict_cached_matches_qf(whitened, covariance):
    cfg, params = _model(jax.random.PRNGKey(0), covariance=covariance)
    cov_fn = make_covariance(covariance)
    xs = jax.random.uniform(jax.random.PRNGKey(5), (257, 2), minval=-2.5, maxval=2.5)
    mean_u, var_u = svgp.q_f(params, cov_fn, xs, cfg.jitter, whitened)
    cache = posterior.build_cache(params, cov_fn, jitter=cfg.jitter, whitened=whitened)
    mean_c, var_c = posterior.predict_cached(cache, cov_fn, xs)
    np.testing.assert_allclose(np.asarray(mean_c), np.asarray(mean_u), atol=1e-5)
    np.testing.assert_allclose(np.asarray(var_c), np.asarray(var_u), atol=1e-5)


@pytest.mark.parametrize("whitened", [False, True])
def test_svgp_predict_is_cached_path(whitened):
    """svgp.predict == build_cache + predict_cached (it delegates)."""
    cfg, params = _model(jax.random.PRNGKey(1))
    cov_fn = make_covariance("rbf")
    xs = jax.random.uniform(jax.random.PRNGKey(6), (64, 2), minval=-2, maxval=2)
    m_p, v_p = svgp.predict(params, cov_fn, xs, whitened=whitened, include_noise=True)
    cache = posterior.build_cache(params, cov_fn, jitter=cfg.jitter, whitened=whitened)
    m_c, v_c = posterior.predict_cached(cache, cov_fn, xs, include_noise=True)
    np.testing.assert_array_equal(np.asarray(m_p), np.asarray(m_c))
    np.testing.assert_array_equal(np.asarray(v_p), np.asarray(v_c))


@pytest.mark.parametrize("Q,m,d", [(1, 1, 1), (7, 5, 2), (100, 25, 2), (128, 128, 3), (300, 40, 2)])
def test_pallas_prediction_kernel_matches_ref(Q, m, d):
    """Fused kernel vs jnp reference through the padding/dispatch layer,
    including ragged (non-tile-aligned) Q and m."""
    ks = jax.random.split(jax.random.PRNGKey(Q * 1000 + m), 5)
    x = jax.random.uniform(ks[0], (Q, d), minval=-2, maxval=2)
    cfg, params = _model(ks[1], m=m, d=d)
    cov_fn = make_covariance("rbf")
    cache = posterior.build_cache(params, cov_fn)
    args = (x, cache.z, cache.cov.log_lengthscale, cache.cov.log_variance,
            cache.w, cache.u, cache.c)
    mean_k, var_k = ops.posterior_predict(*args)
    mean_r, var_r = ops.posterior_predict_ref(*args)
    np.testing.assert_allclose(np.asarray(mean_k), np.asarray(mean_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(var_k), np.asarray(var_r), atol=1e-5)


def test_predict_cached_pallas_path_matches_jnp():
    cfg, params = _model(jax.random.PRNGKey(2))
    cov_fn = make_covariance("rbf")
    xs = jax.random.uniform(jax.random.PRNGKey(7), (130, 2), minval=-2, maxval=2)
    cache = posterior.build_cache(params, cov_fn)
    m_j, v_j = posterior.predict_cached(cache, cov_fn, xs)
    m_p, v_p = posterior.predict_cached(cache, cov_fn, xs, use_pallas=True)
    np.testing.assert_allclose(np.asarray(m_p), np.asarray(m_j), atol=1e-5)
    np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_j), atol=1e-5)


@pytest.mark.parametrize("S,Q,md", [(1, 8, (5, 2)), (9, 24, (12, 2)),
                                    (9, 130, (25, 2)), (3, 7, (128, 3))])
def test_pallas_slots_kernel_matches_ref(S, Q, md):
    """Slot-stacked fused kernel vs jnp reference through the
    padding/dispatch layer, incl. ragged (non-tile-aligned) S/Q/m."""
    m, d = md
    ks = jax.random.split(jax.random.PRNGKey(S * 100 + Q), 2)
    cfg, params = _model(ks[0], m=m, d=d)
    cov_fn = make_covariance("rbf")
    cache = posterior.build_cache(params, cov_fn)
    hx = jax.random.uniform(ks[1], (S, Q, d), minval=-2, maxval=2)
    args = (hx, cache.z, cache.cov.log_lengthscale, cache.cov.log_variance,
            cache.w, cache.u, cache.c)
    mean_k, var_k = ops.posterior_predict_slots(*args)
    mean_r, var_r = ops.posterior_predict_slots_ref(*args)
    assert mean_k.shape == (S, Q) and var_k.shape == (S, Q)
    np.testing.assert_allclose(np.asarray(mean_k), np.asarray(mean_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(var_k), np.asarray(var_r), atol=1e-5)


def test_slots_kernel_masked_oracle_and_row_independence():
    """The TWO-LEVEL routing contract on the slot-stacked kernel: a block
    may mix owner rows, spilled-in neighbor rows and padded placeholder
    rows, which is only safe because every output row depends on its own
    input row and the resident factors alone. Held two ways: kernel *
    qmask equals the masked oracle (ref.posterior_predict_slots_masked),
    and junk written into the masked rows' INPUTS leaves every valid row
    of the kernel output bitwise unchanged."""
    from repro.kernels import ref as kref

    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    cfg, params = _model(ks[0], m=12, d=2)
    cov_fn = make_covariance("rbf")
    cache = posterior.build_cache(params, cov_fn)
    S, Q = 9, 24
    hx = jax.random.uniform(ks[1], (S, Q, 2), minval=-2, maxval=2)
    qmask = (jax.random.uniform(ks[2], (S, Q)) < 0.6).astype(hx.dtype)
    tail = (cache.z, cache.cov.log_lengthscale, cache.cov.log_variance,
            cache.w, cache.u, cache.c)
    mean_k, var_k = ops.posterior_predict_slots(hx, *tail)
    mean_o, var_o = kref.posterior_predict_slots_masked(hx, qmask, *tail)
    np.testing.assert_allclose(
        np.asarray(mean_k * qmask), np.asarray(mean_o), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(var_k * qmask), np.asarray(var_o), atol=1e-5)

    junk = jnp.where(qmask[..., None] > 0, hx, 1e3 * jnp.ones_like(hx))
    mean_j, var_j = ops.posterior_predict_slots(junk, *tail)
    keep = np.asarray(qmask) > 0
    np.testing.assert_array_equal(np.asarray(mean_k)[keep], np.asarray(mean_j)[keep])
    np.testing.assert_array_equal(np.asarray(var_k)[keep], np.asarray(var_j)[keep])


def test_pallas_slots_kernel_on_halo_stacked_blocks():
    """The kernel's real serving input: halo-stacked blocks from a routing
    table, including edge/corner partitions whose off-grid slots are
    zero-filled, and a ragged q_max."""
    from repro.core import routing
    from repro.core.partition import make_grid

    rng = np.random.default_rng(0)
    pts = rng.uniform(-1.0, 1.0, size=(300, 2)).astype(np.float32)
    grid = make_grid(pts, 4, 3)
    table = routing.build_routing_table(grid, pts)
    hx_all = routing.make_halo_stacker(grid)(table.xq)  # (P, 9, q, 2)

    cfg, params = _model(jax.random.PRNGKey(3), m=10, d=2)
    cov_fn = make_covariance("rbf")
    cache = posterior.build_cache(params, cov_fn)
    # corner (0), edge (1), interior (center of the 4x3 grid)
    for p in (0, 1, grid.index_of(1, 1)):
        hx = jnp.asarray(hx_all[p])
        m_j, v_j = posterior.predict_cached_slots(cache, cov_fn, hx)
        m_p, v_p = posterior.predict_cached_slots(cache, cov_fn, hx, use_pallas=True)
        np.testing.assert_allclose(np.asarray(m_p), np.asarray(m_j), atol=1e-5)
        np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_j), atol=1e-5)


def test_predict_cached_slots_jnp_is_per_slot_predict_cached():
    """The slot stack is a pure batching: slot k's row equals a plain
    predict_cached call on that block. Same math, but the vmapped program
    may order its f32 reductions differently from the unbatched one, so
    the check is a few ulp relative (4 eps), not bitwise."""
    cfg, params = _model(jax.random.PRNGKey(4))
    cov_fn = make_covariance("rbf")
    cache = posterior.build_cache(params, cov_fn)
    hx = jax.random.uniform(jax.random.PRNGKey(8), (9, 16, 2), minval=-2, maxval=2)
    ms, vs = posterior.predict_cached_slots(cache, cov_fn, hx, include_noise=True)
    rtol = 4 * np.finfo(np.float32).eps
    for k in (0, 4, 8):
        m1, v1 = posterior.predict_cached(cache, cov_fn, hx[k], include_noise=True)
        np.testing.assert_allclose(np.asarray(ms[k]), np.asarray(m1), rtol=rtol, atol=1e-7)
        np.testing.assert_allclose(np.asarray(vs[k]), np.asarray(v1), rtol=rtol)


@pytest.mark.parametrize("covariance", ["matern32", "matern52"])
def test_pallas_paths_reject_non_rbf(covariance):
    """use_pallas with a non-RBF covariance must raise, not silently
    return RBF answers — on every cached-prediction entry point."""
    cfg, params = _model(jax.random.PRNGKey(5), covariance=covariance)
    cov_fn = make_covariance(covariance)
    cache = posterior.build_cache(params, cov_fn)
    xs = jax.random.uniform(jax.random.PRNGKey(9), (16, 2), minval=-2, maxval=2)
    with pytest.raises(ValueError, match="rbf"):
        posterior.predict_cached(cache, cov_fn, xs, use_pallas=True)
    with pytest.raises(ValueError, match="rbf"):
        posterior.predict_cached_slots(
            cache, cov_fn, xs[None].repeat(9, axis=0), use_pallas=True
        )
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), cache)
    with pytest.raises(ValueError, match="rbf"):
        posterior.predict_cached_stacked(
            stacked, cov_fn, jnp.stack([xs, xs]), use_pallas=True
        )
    # the jnp path keeps serving every covariance
    m_j, v_j = posterior.predict_cached(cache, cov_fn, xs)
    assert np.isfinite(np.asarray(m_j)).all() and (np.asarray(v_j) > 0).all()


@pytest.fixture(scope="module")
def trained_psvgp():
    ds = e3sm_like_field(n=2500, seed=0)
    grid = make_grid(ds.x, 4, 4)
    data = partition_data(ds.x, ds.y, grid)
    cfg = psvgp.PSVGPConfig(
        svgp=svgp.SVGPConfig(num_inducing=6, input_dim=2),
        delta=0.25, batch_size=16, learning_rate=0.05,
    )
    static = psvgp.build(cfg, data)
    state = psvgp.init(jax.random.PRNGKey(0), cfg, data)
    state = psvgp.fit(static, state, data, 300)
    return ds, grid, data, static, state


def test_prediction_entry_points_share_cache(trained_psvgp):
    """predict_local / predict_at_partitions / predict_blended give the
    same answers with a precomputed cache as without (cache reuse is a pure
    optimization, not a different model)."""
    ds, grid, data, static, state = trained_psvgp
    cache = psvgp.posterior_cache(static, state)

    m0, v0 = psvgp.predict_local(static, state, data.x)
    m1, v1 = psvgp.predict_local(static, state, data.x, cache=cache)
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))

    ids = jnp.asarray([0, 5, 10])
    pts = data.x[:3, :4]
    m0, v0 = psvgp.predict_at_partitions(static, state, ids, pts)
    m1, v1 = psvgp.predict_at_partitions(static, state, ids, pts, cache=cache)
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))

    q = jnp.asarray(ds.x[:500])
    mb0, vb0 = predict_blended(static, state, grid, q)
    mb1, vb1 = predict_blended(static, state, grid, q, cache=cache)
    np.testing.assert_array_equal(np.asarray(mb0), np.asarray(mb1))
    np.testing.assert_array_equal(np.asarray(vb0), np.asarray(vb1))


def test_blended_continuity_preserved_after_rewrite(trained_psvgp):
    """The cached rewrite keeps the bilinear stitch continuous across a
    partition boundary (epsilon probes either side agree)."""
    ds, grid, data, static, state = trained_psvgp
    cache = psvgp.posterior_cache(static, state)
    xb = float(grid.x_edges[2])
    ys = np.linspace(grid.y_edges[1], grid.y_edges[3], 9).astype(np.float32)
    eps = 1e-4
    left = np.stack([np.full_like(ys, xb - eps), ys], -1)
    right = np.stack([np.full_like(ys, xb + eps), ys], -1)
    ml, _ = predict_blended(static, state, grid, jnp.asarray(left), cache=cache)
    mr, _ = predict_blended(static, state, grid, jnp.asarray(right), cache=cache)
    np.testing.assert_allclose(np.asarray(ml), np.asarray(mr), atol=2e-3)


def test_blended_matches_local_model_at_cell_center(trained_psvgp):
    ds, grid, data, static, state = trained_psvgp
    from repro.core.partition import partition_centers

    cache = psvgp.posterior_cache(static, state)
    centers = partition_centers(grid)[[5, 9]]
    ids = jnp.asarray([5, 9])
    mb, _ = predict_blended(static, state, grid, jnp.asarray(centers), cache=cache)
    ml, _ = psvgp.predict_at_partitions(
        static, state, ids, jnp.asarray(centers)[:, None], cache=cache
    )
    np.testing.assert_allclose(np.asarray(mb), np.asarray(ml)[:, 0], atol=1e-4)
