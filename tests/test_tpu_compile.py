"""Compile rehearsal: the main path's kernels and the sharded blend compile
for a DESCRIBED TPU v5e, with no chip attached.

The TPU compiler ships with libtpu, so a program lowered against
``get_topology_desc("v5e:2x2")`` is refused here exactly as the chip's
compiler would refuse it (mis-tiled blocks, too much VMEM, an
unpartitionable kernel) — checks interpret mode cannot make. Nothing runs,
so results and times come only from ``chip_smoke.py`` on the chip.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and every pytest-xdist worker imports
this file. Keep these tests in this one file so one worker holds the lock.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

M_PAD = 128  # the paper's m = 5 pads to one 128-lane tile
D = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or another process holds it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but not read back without one; keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, s):
    """(jitted kernel, abstract args, static kwargs) at the widths
    ``chip_smoke.py`` serves: a 2,048-point request (q_max 2,560 after the
    streaming policy's 1.25 headroom), m = 5 padded to one lane tile, and
    the E3SM training batch B = 32."""
    from repro.kernels.predict import posterior_predict_pallas, posterior_predict_slots_pallas
    from repro.kernels.rbf import rbf_cross_cov_pallas
    from repro.kernels.svgp_proj import svgp_projection_pallas

    factors = (s(M_PAD, D), s(D), s(), s(M_PAD, M_PAD))  # z, log_l, log_var, W
    if name == "slots":
        return posterior_predict_slots_pallas, (
            s(9, 2560, D), *factors, s(M_PAD, M_PAD), s(M_PAD)), {"block_q": 128}
    if name == "predict":
        return posterior_predict_pallas, (
            s(2048, D), *factors, s(M_PAD, M_PAD), s(M_PAD)), {"block_q": 128}
    if name == "projection":
        return svgp_projection_pallas, (s(32, D), *factors), {"block_b": 32}
    return rbf_cross_cov_pallas, (s(128, D), *factors[:3]), {"block_b": 128}


@pytest.mark.parametrize("name", ["slots", "predict", "projection", "rbf"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args, kw = _kernel_case(name, lambda *shape: _sds(one_chip, *shape))
    compiled = fn.lower(*args, **kw, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _op_count(hlo_text: str, op: str) -> int:
    """Instructions of ``op`` (sync or async-start form) in compiled HLO."""
    return len(re.findall(rf"\b{op}(?:-start)?\(", hlo_text))


def test_sharded_fused_blend_compiles_for_v5e_2x2(topo, monkeypatch):
    """The sharded serving program with the fused slots kernel, one
    partition per chip of a described 2x2 mesh: the kernel is in it, the
    reverse halo is at most 8 collective-permutes, and no collective
    gathers the factors."""
    from repro.analysis import hlo
    from repro.gp.covariances import make_covariance
    from repro.kernels import ops
    from repro.launch import serve_sharded as ss

    # the dispatch asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    grid = hlo.probe_grid(2)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    per_device = NamedSharding(mesh, P(mesh.axis_names))
    cache = jax.tree.map(
        lambda a: _sds(per_device, *a.shape, dtype=a.dtype),
        hlo.abstract_cache(grid.num_partitions, 5),
    )
    blend = ss.make_sharded_blend(
        mesh, mesh.axis_names, grid, make_covariance("rbf"), cache, backend="fused"
    )
    q = 64
    text = blend.lower(
        cache,
        _sds(per_device, 4, 9, q, D),
        _sds(per_device, 4, q, 4, dtype=jnp.int32),
        _sds(per_device, 4, q, 4),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert 4 <= _op_count(text, "collective-permute") <= 8
    for gathering in ("all-gather", "all-reduce", "all-to-all"):
        assert _op_count(text, gathering) == 0, gathering


def _computations(hlo_text: str) -> dict:
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, body = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            body = comps[head.group(1)] = []
        elif body is not None:
            body.append(line)
    return comps


def _called(comps: dict, root: str) -> set:
    """``root`` and every computation it calls, transitively."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            for ref in re.findall(r"(?:calls|body|condition|to_apply|branch_computations)=(\{[^}]*\}|%[\w.\-]+)", line):
                todo += re.findall(r"%([\w.\-]+)", ref)
    return seen


# instructions that hand an array on without making a new one
_PASS_THROUGH = {"parameter", "get-tuple-element", "tuple", "bitcast"}


def test_fit_loop_compiles_for_v5e(one_chip):
    """``psvgp.fit_steps`` at the E3SM shapes (400 partitions, the fullest
    of the 48,602 points' holding 248, m = 5, B = 32, comm="gather") is one
    device loop, and no step copies the data: inside the loop body nothing
    the size of x (P x n_max x 2) is made, only passed through."""
    from repro.core import psvgp, svgp
    from repro.core.partition import PartitionedData, make_grid

    P, n_max = 400, 248
    grid = make_grid(np.array([[0.0, 0.0], [1.0, 1.0]], np.float32), 20, 20)
    data = PartitionedData(
        x=jnp.zeros((P, n_max, D)), y=jnp.zeros((P, n_max)), mask=jnp.ones((P, n_max)),
        counts=jnp.full((P,), 122, jnp.int32), grid=grid,
    )
    cfg = psvgp.PSVGPConfig(
        svgp=svgp.SVGPConfig(num_inducing=5, input_dim=D), delta=0.125, batch_size=32,
        learning_rate=0.05, comm="gather",
    )
    static = psvgp.build(cfg, data)
    state = jax.eval_shape(lambda k: psvgp.init(k, cfg, data), jax.random.PRNGKey(0))
    args = jax.tree.map(
        lambda a: _sds(one_chip, *a.shape, dtype=a.dtype),
        (state, jnp.int32(150), jax.random.PRNGKey(0), data.x, data.y, data.mask,
         static.dist, static.perms, static.p_dir),
    )
    text = psvgp.fit_steps.lower(*args, cfg=cfg, cov_fn=static.cov_fn).compile().as_text()
    assert _op_count(text, "while") == 1
    comps = _computations(text)
    (loop,) = [line for lines in comps.values() for line in lines if re.search(r"\bwhile\(", line)]
    body = re.search(r"body=%([\w.\-]+)", loop).group(1)
    x_shape = f"f32[{P},{n_max},{D}]"
    ops = [
        (inst.group(2), inst.group(1), line.strip()[:160])
        for name in _called(comps, body)
        for line in comps[name]
        if (inst := re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([a-z][\w\-]*)\(", line))
    ]
    assert ("get-tuple-element", x_shape) in {(op, t.split("{")[0]) for op, t, _ in ops}
    made = [line for op, t, line in ops if x_shape in t and op not in _PASS_THROUGH]
    assert not made, made
