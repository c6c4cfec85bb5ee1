"""SPMD (shard_map + ppermute) PSVGP == single-host simulation, bit-for-bit.

The SPMD program needs multiple XLA host devices, which must be configured
before jax initializes — so the check runs in a subprocess with its own
XLA_FLAGS (tests in this process keep seeing 1 device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax, jax.numpy as jnp, numpy as np
    from repro.data.spatial import e3sm_like_field
    from repro.core.partition import make_grid, partition_data
    from repro.core import psvgp, svgp
    from repro.core.psvgp_spmd import make_spmd_step

    ds = e3sm_like_field(n=2000, seed=0)
    grid = make_grid(ds.x, gx=4, gy=4)
    data = partition_data(ds.x, ds.y, grid)
    cfg = psvgp.PSVGPConfig(
        svgp=svgp.SVGPConfig(num_inducing=8, input_dim=2),
        delta=0.2, batch_size=8, learning_rate=0.05, comm="ppermute")
    static = psvgp.build(cfg, data)
    state = psvgp.init(jax.random.PRNGKey(0), cfg, data)
    mesh = jax.make_mesh((4, 4), ("data", "model"))
    step = make_spmd_step(mesh, ("data", "model"), grid, cfg, static.cov_fn, static.p_dir)

    st_spmd = state
    st_sim = state
    key = jax.random.PRNGKey(42)
    # Two steps: enough to exercise the exchange + update path while staying
    # below Adam's chaotic divergence horizon (the sqrt(nu) normalization
    # amplifies float-reassociation noise exponentially across steps; step-0
    # agreement is ~1e-9, step-4 would be ~1e-3 with identical math).
    with jax.set_mesh(mesh):
        for _ in range(2):
            st_spmd, loss_spmd = step(
                st_spmd, key, data.x, data.y, data.mask,
                static.dist.probs, static.dist.n_eff)
    for _ in range(2):
        st_sim, loss_sim = psvgp.train_step_ppermute(
            st_sim, key, data.x, data.y, data.mask, static.dist,
            static.perms, static.p_dir, cfg, static.cov_fn)

    a = jax.device_get(st_spmd.params)
    b = jax.device_get(st_sim.params)
    # atol covers two Adam steps of float-reassociation noise between the
    # two independently compiled programs (see comment above): the noise is
    # run-to-run nondeterministic on CPU (thread-level reduction order
    # across the 16 virtual devices; measured 1e-5..7e-5 across runs) and
    # each sqrt(nu)-normalized step multiplies it. A real exchange/weight
    # bug shows up at 1e-1 scale (2 x lr sign flips), 3 orders above this.
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_allclose(la, lb, atol=2e-4)

    # the lowered SPMD program must actually contain a collective-permute —
    # the paper's decentralized p2p exchange on the ICI torus.
    lowered = step.lower(state, key, data.x, data.y, data.mask,
                         static.dist.probs, static.dist.n_eff)
    txt = lowered.as_text() + lowered.compile().as_text()
    assert ("collective_permute" in txt) or ("collective-permute" in txt), \
        "no collective-permute in lowered/compiled HLO"
    print("OK")
    """
)


@pytest.mark.slow
def test_spmd_step_matches_simulation():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
