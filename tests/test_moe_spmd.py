"""The shard_map MoE path (§Perf-2) must agree with the local path.

Runs in a subprocess with 16 virtual devices: same params, same tokens —
the manually-partitioned dispatch must reproduce the single-device outputs
(capacity generous so no drops differ; grads checked too).
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
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.models.config import ModelConfig, MoEConfig
    from repro.models.moe import init_moe_params, moe_forward, _moe_forward_local

    cfg = ModelConfig(
        name="t", arch_type="moe", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, d_ff=64, vocab_size=128, dtype="float32",
        moe=MoEConfig(num_experts=8, top_k=2, d_expert=32, num_shared=1,
                      capacity_factor=8.0, dispatch_groups=1),
    ).validate()
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64))

    # local reference (no mesh)
    out_ref, aux_ref = _moe_forward_local(p, cfg, x)
    gref = jax.grad(lambda pp: _moe_forward_local(pp, cfg, x)[0].sum())(p)

    mesh = jax.make_mesh((8, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        out, aux = jax.jit(lambda pp, xx: moe_forward(pp, cfg, xx))(p, x)
        g = jax.jit(jax.grad(lambda pp: moe_forward(pp, cfg, x)[0].sum()))(p)

    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref), atol=2e-5)
    # aux is computed per data shard then averaged (GShard per-group
    # semantics) — close to but not identical with the global statistic
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=0.15)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gref), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)
    print("OK")
    """
)


@pytest.mark.slow
def test_moe_spmd_matches_local():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=900,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK" in r.stdout
