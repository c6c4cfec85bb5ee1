import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

Proves that EVERY (architecture x input shape) combination lowers AND
compiles on the production meshes — 16x16 single pod and 2x16x16 multi-pod
— with the framework's sharding rules, using ShapeDtypeStruct stand-ins
only (no parameter allocation; a 76B model lowers on a laptop).

Per combination it records memory_analysis() (proves fit), cost_analysis()
(FLOPs/bytes) and the collective-bytes breakdown parsed from the optimized
HLO — the inputs to benchmarks/roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod --out out.json
  PYTHONPATH=src python -m repro.launch.dryrun --psvgp [--multi-pod]
"""
import argparse
import dataclasses
import functools
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, get, input_specs, swa_variant
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.runtime.steps import (
    init_train_state,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)
from repro.sharding import batch_pspec, cache_pspecs, data_axes, state_pspecs


def _data_shardable(n: int, mesh) -> bool:
    import numpy as np

    return n % int(np.prod([mesh.shape[a] for a in data_axes(mesh)])) == 0


def resolve_config(arch: str, shape_name: str):
    """Apply the long_500k SWA variant where the assignment requires it."""
    cfg = get(arch)
    if shape_name == "long_500k":
        cfg = swa_variant(cfg)
    return cfg


def _lower_combo(cfg, shape_name: str, mesh, fsdp: bool = False, microbatches: int = 1):
    """Lower + compile one (config, shape) on a mesh; return compiled module."""
    sh = INPUT_SHAPES[shape_name]
    key = jax.random.PRNGKey(0)

    state_shapes = jax.eval_shape(functools.partial(init_train_state, cfg=cfg), key)
    pspecs = state_pspecs(state_shapes, mesh, fsdp=fsdp)
    bspec = batch_pspec(mesh) if _data_shardable(sh.global_batch, mesh) else P()

    with jax.set_mesh(mesh):
        if sh.kind == "train":
            specs = input_specs(cfg, shape_name)
            batch_specs = {k: bspec if v.ndim >= 2 else P() for k, v in specs.items()}
            step = make_train_step(cfg, microbatches=microbatches)
            jitted = jax.jit(
                step,
                in_shardings=(pspecs, batch_specs),
                out_shardings=(pspecs, None),
            )
            lowered = jitted.lower(state_shapes, specs)
        elif sh.kind == "prefill":
            specs = input_specs(cfg, shape_name)
            step = make_prefill_step(cfg, cache_len=sh.seq_len)
            names = [k for k in ("tokens", "frames", "patches") if k in specs]
            in_sh = [pspecs.params] + [bspec for _ in names]
            jitted = jax.jit(
                lambda params, *args: step(params, **dict(zip(names, args, strict=True))),
                in_shardings=tuple(in_sh),
            )
            lowered = jitted.lower(state_shapes.params, *[specs[k] for k in names])
        else:  # decode
            serve_cfg = dataclasses.replace(cfg, remat=False)
            cache_shapes = jax.eval_shape(
                functools.partial(
                    transformer.init_cache, serve_cfg, sh.global_batch, sh.seq_len,
                    jnp.dtype(serve_cfg.dtype),
                )
            )
            cspecs = cache_pspecs(cache_shapes, mesh, shard_seq=(sh.global_batch == 1))
            step = make_decode_step(cfg)
            tok_spec = jax.ShapeDtypeStruct((sh.global_batch, 1), jnp.int32)
            pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(
                step,
                in_shardings=(pspecs.params, cspecs, P(), bspec),
                out_shardings=(None, cspecs),
            )
            lowered = jitted.lower(state_shapes.params, cache_shapes, pos_spec, tok_spec)

        compiled = lowered.compile()
    return compiled


def _depth_variants(cfg):
    """Reduced-depth UNROLLED configs with 1 and 2 periods (same prelude and
    remainder) for the while-loop cost extrapolation: unrolled bodies are
    counted per period by cost_analysis, so (c2 - c1) = one period's cost."""
    prelude = 1 if (cfg.moe is not None and cfg.moe.first_layer_dense) else 0
    rem = (cfg.num_layers - prelude) % cfg.period
    n1 = prelude + cfg.period + rem
    n2 = n1 + cfg.period
    c1 = dataclasses.replace(cfg, num_layers=n1, unroll=True)
    c2 = dataclasses.replace(cfg, num_layers=n2, unroll=True)
    return c1, c2


def dryrun_one(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    verbose: bool = True,
    extrapolate: bool = True,
    cfg_override=None,
    fsdp: bool = False,
    microbatches: int = 1,
    q_chunk: int = 0,
):
    """Lower + compile one (arch, shape, mesh); return the analysis record.

    XLA's cost_analysis counts a while-loop body ONCE regardless of trip
    count (verified empirically), so the scan-over-periods body cost is
    recovered by lowering 1-period and 2-period variants and extrapolating
    linearly: total = c1 + (n_periods - 1) * (c2 - c1). Exact, because
    every period is identical work. memory_analysis comes from the FULL
    lowering (buffer sizes are trip-count independent).
    """
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = cfg_override if cfg_override is not None else resolve_config(arch, shape_name)
    if q_chunk:
        cfg = dataclasses.replace(cfg, attn_q_chunk=q_chunk)
    sh = INPUT_SHAPES[shape_name]
    if sh.kind != "train":
        # FSDP weight-gathers per decode step would wreck serving latency;
        # microbatching only applies to gradient steps.
        fsdp, microbatches = False, 1
    t0 = time.time()

    compiled = _lower_combo(cfg, shape_name, mesh, fsdp=fsdp, microbatches=microbatches)
    mem = compiled.memory_analysis()
    terms = hlo_analysis.roofline(compiled)

    prelude = 1 if (cfg.moe is not None and cfg.moe.first_layer_dense) else 0
    n_periods = (cfg.num_layers - prelude) // cfg.period
    flops_source = "hlo"
    if extrapolate and n_periods > 1:
        c1, c2 = _depth_variants(cfg)
        # metric variants use microbatches=1: the accumulation scan is a
        # while loop whose body cost_analysis would count once; the full
        # (memory) lowering above keeps the real microbatch count.
        t1 = hlo_analysis.roofline(_lower_combo(c1, shape_name, mesh, fsdp=fsdp))
        t2 = hlo_analysis.roofline(_lower_combo(c2, shape_name, mesh, fsdp=fsdp))
        k = n_periods - 1  # extra periods beyond the 1-period variant

        def ex(a1, a2):
            return a1 + k * (a2 - a1)

        breakdown = {
            key: max(
                int(ex(t1.collective_breakdown.get(key, 0), t2.collective_breakdown.get(key, 0))),
                t1.collective_breakdown.get(key, 0),
            )
            for key in set(t1.collective_breakdown) | set(t2.collective_breakdown)
        }
        flops = ex(t1.flops_per_device, t2.flops_per_device)
        byts = ex(t1.bytes_per_device, t2.bytes_per_device)
        cb = float(sum(breakdown.values()))
        terms = hlo_analysis.RooflineTerms(
            flops_per_device=flops,
            bytes_per_device=byts,
            collective_bytes_per_device=cb,
            collective_breakdown=breakdown,
            compute_s=flops / hlo_analysis.PEAK_FLOPS,
            memory_s=byts / hlo_analysis.HBM_BW,
            collective_s=cb / hlo_analysis.ICI_BW,
        )
        flops_source = "hlo+period-extrapolated"

    if hlo_analysis.has_time_while_loops(cfg):
        # mlstm/slstm scan over TIME: in-loop cost invisible to
        # cost_analysis even unrolled-by-period -> analytical count.
        total = hlo_analysis.analytical_flops_recurrent(
            cfg, sh.seq_len, sh.global_batch, sh.kind
        )
        flops = total / mesh.size
        terms = terms._replace(
            flops_per_device=flops, compute_s=flops / hlo_analysis.PEAK_FLOPS
        )
        flops_source = "analytical(time-scan)"

    if sh.kind == "train":
        mflops = hlo_analysis.model_flops_train(cfg, sh.seq_len, sh.global_batch)
    elif sh.kind == "prefill":
        mflops = hlo_analysis.model_flops_train(cfg, sh.seq_len, sh.global_batch) / 3.0
    else:
        mflops = hlo_analysis.model_flops_decode(cfg, sh.global_batch)
    chips = mesh.size
    total_hlo_flops = terms.flops_per_device * chips

    rec = {
        "arch": arch,
        "config_name": cfg.name,
        "shape": shape_name,
        "kind": sh.kind,
        "fsdp": fsdp,
        "microbatches": microbatches,
        "q_chunk": q_chunk,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "compile_s": round(time.time() - t0, 1),
        "bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "temps": mem.temp_size_in_bytes,
            "peak_est": mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes,
        },
        "flops_per_device": terms.flops_per_device,
        "hlo_bytes_per_device": terms.bytes_per_device,
        "collective_bytes_per_device": terms.collective_bytes_per_device,
        "collective_breakdown": terms.collective_breakdown,
        "roofline_s": {
            "compute": terms.compute_s,
            "memory": terms.memory_s,
            "collective": terms.collective_s,
        },
        "dominant": terms.dominant,
        "flops_source": flops_source,
        "model_flops": mflops,
        "useful_compute_ratio": mflops / total_hlo_flops if total_hlo_flops else 0.0,
    }
    if verbose:
        print(json.dumps(rec, indent=2))
    return rec


def dryrun_psvgp(*, multi_pod: bool = False, comm: str = "ppermute", verbose: bool = True):
    """Lower + compile the PSVGP train step on the production mesh.

    One partition per device: 16x16 grid single-pod, 16x32 multi-pod
    (DESIGN.md §2). The paper's own technique — this record seeds the
    §Perf hillclimb."""
    import numpy as np

    from repro.configs.psvgp_e3sm import DRYRUN_MULTI_POD, DRYRUN_SINGLE_POD
    from repro.core import psvgp
    from repro.core.partition import make_grid
    from repro.core.psvgp_spmd import make_spmd_step
    from repro.core.sampler import slot_distribution
    from repro.core.neighbors import neighbor_table
    from repro.core.svgp import SVGPParams
    from repro.gp.covariances import CovarianceParams, make_covariance
    from repro.optim import AdamState
    from repro.core.psvgp import PSVGPState

    exp = DRYRUN_MULTI_POD if multi_pod else DRYRUN_SINGLE_POD
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = mesh.axis_names  # ("pod","data","model") rows = pod x data
    gx, gy = exp.grid
    grid = make_grid(np.zeros((1, 2), np.float32), gx, gy, bounds=(0.0, 10.0, 0.0, 10.0))
    cfg = exp.psvgp(comm=comm)
    P_ = grid.num_partitions
    n_max = 224  # the paper's max partition size (222), padded to sublane x8
    m, d = cfg.svgp.num_inducing, 2
    t0 = time.time()

    f32 = jnp.float32

    def sds(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt)

    params = SVGPParams(
        m_star=sds((P_, m)), s_tril=sds((P_, m, m)), z=sds((P_, m, d)),
        cov=CovarianceParams(log_lengthscale=sds((P_, d)), log_variance=sds((P_,))),
        log_beta=sds((P_,)),
    )
    state = PSVGPState(
        params=params,
        opt=AdamState(step=sds((), jnp.int32), mu=params, nu=params),
        step=sds((), jnp.int32),
    )
    tbl = jnp.asarray(neighbor_table(grid))
    dist_shapes = jax.eval_shape(
        lambda c: slot_distribution(c, tbl, cfg.delta), sds((P_,), jnp.int32)
    )
    p_dir = jnp.full((5,), 0.2, f32)

    cov_fn = make_covariance(cfg.svgp.covariance)
    with jax.set_mesh(mesh):
        if comm == "ppermute":
            step = make_spmd_step(mesh, axes, grid, cfg, cov_fn, p_dir)
            lowered = step.lower(
                state, sds((2,), jnp.uint32),
                sds((P_, n_max, d)), sds((P_, n_max)), sds((P_, n_max)),
                sds((P_, 5)), sds((P_,)),
            )
        else:  # gather mode under plain pjit
            pspec = P(tuple(axes))
            pl = SVGPParams(
                m_star=pspec, s_tril=pspec, z=pspec,
                cov=CovarianceParams(pspec, pspec), log_beta=pspec,
            )
            sspec = PSVGPState(params=pl, opt=AdamState(P(), pl, pl), step=P())
            from repro.core.sampler import SlotDistribution

            dspec = SlotDistribution(probs=pspec, n_eff=pspec, neighbor_tbl=pspec)
            jitted = jax.jit(
                functools.partial(
                    psvgp.train_step_gather, cfg=cfg, cov_fn=cov_fn
                ),
                in_shardings=(sspec, P(), pspec, pspec, pspec, dspec),
                out_shardings=(sspec, None),
            )
            lowered = jitted.lower(
                state, sds((2,), jnp.uint32),
                sds((P_, n_max, d)), sds((P_, n_max)), sds((P_, n_max)), dist_shapes,
            )
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    terms = hlo_analysis.roofline(compiled)
    rec = {
        "arch": "psvgp-e3sm",
        "config_name": f"psvgp-{comm}",
        "shape": f"grid{gx}x{gy}-m{m}-B{cfg.batch_size}",
        "kind": "train",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": mesh.size,
        "compile_s": round(time.time() - t0, 1),
        "bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "temps": mem.temp_size_in_bytes,
            "peak_est": mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes,
        },
        "flops_per_device": terms.flops_per_device,
        "hlo_bytes_per_device": terms.bytes_per_device,
        "collective_bytes_per_device": terms.collective_bytes_per_device,
        "collective_breakdown": terms.collective_breakdown,
        "roofline_s": {
            "compute": terms.compute_s,
            "memory": terms.memory_s,
            "collective": terms.collective_s,
        },
        "dominant": terms.dominant,
    }
    if verbose:
        print(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=[a.replace("_", "-").replace("-0-", "-0.") for a in ARCH_IDS] + ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true", help="every (arch x shape)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fsdp", action="store_true", help="ZeRO-3 weight/opt sharding over data axes")
    ap.add_argument("--microbatches", type=int, default=1, help="gradient-accumulation chunks (train shapes)")
    ap.add_argument("--q-chunk", type=int, default=0, help="query-chunked attention block size (0=off)")
    ap.add_argument("--psvgp", action="store_true", help="dry-run the paper's PSVGP step")
    ap.add_argument("--comm", default="ppermute", choices=["ppermute", "gather"])
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    records, failures = [], []

    def emit(rec):
        records.append(rec)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")

    if args.psvgp:
        emit(dryrun_psvgp(multi_pod=args.multi_pod, comm=args.comm))
    elif args.all:
        for arch in ARCH_IDS:
            for shape in INPUT_SHAPES:
                try:
                    emit(dryrun_one(arch, shape, multi_pod=args.multi_pod, fsdp=args.fsdp, microbatches=args.microbatches, q_chunk=args.q_chunk))
                except Exception as e:  # noqa: BLE001 — report all failures at end
                    traceback.print_exc()
                    failures.append((arch, shape, repr(e)))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required (or --all / --psvgp)")
        emit(dryrun_one(args.arch, args.shape, multi_pod=args.multi_pod, fsdp=args.fsdp, microbatches=args.microbatches, q_chunk=args.q_chunk))

    print(f"\n{len(records)} dry-runs OK, {len(failures)} failed")
    for f in failures:
        print("FAILED:", f)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
