"""Kernel micro-benchmarks: Pallas SVGP projection vs the unfused reference.

On CPU the Pallas kernels execute in interpret mode (Python), so WALL TIME
of the kernel path is not meaningful here — what this bench reports is:

  (a) numerical agreement (max |err|) across paper-relevant shapes;
  (b) the structural win of fusion, derived from cost_analysis of the
      UNFUSED reference: bytes that the fused kernel does not round-trip
      through HBM (the knm re-read — DESIGN.md §6), i.e. the memory-term
      delta the roofline attributes to the kernel on TPU;
  (c) wall time of the jnp reference path (the actual CPU execution used
      by the benchmarks), for regression tracking.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

SHAPES = [(32, 5, 2), (32, 20, 2), (256, 128, 2), (1024, 128, 3)]


def run(out_dir: str = "benchmarks/results") -> list:
    results = []
    for B, m, d in SHAPES:
        key = jax.random.PRNGKey(B + m)
        kx, kz, kl = jax.random.split(key, 3)
        x = jax.random.normal(kx, (B, d))
        z = jax.random.normal(kz, (m, d))
        lls = 0.3 * jax.random.normal(kl, (d,))
        lv = jnp.asarray(0.1)
        kmm = ref.rbf_cross_cov(z, z, lls, lv) + 1e-4 * jnp.eye(m)
        lmm = jnp.linalg.cholesky(kmm)

        got = ops.svgp_projection(x, z, lls, lv, lmm)
        want = ops.svgp_projection_ref(x, z, lls, lv, lmm)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want, strict=True))

        # unfused reference: knm written to HBM then re-read for projection
        ref_fn = jax.jit(lambda *a: ops.svgp_projection_ref(*a))
        c = ref_fn.lower(x, z, lls, lv, lmm).compile()
        ca = c.cost_analysis()
        # fused kernel skips one HBM write+read of knm (B x m fp32)
        knm_bytes = B * m * 4
        t0 = time.time()
        for _ in range(20):
            out = ref_fn(x, z, lls, lv, lmm)
        jax.block_until_ready(out)
        us = (time.time() - t0) / 20 * 1e6
        rec = {
            "B": B, "m": m, "d": d, "max_abs_err": err,
            "ref_flops": float(ca.get("flops", 0)),
            "ref_bytes": float(ca.get("bytes accessed", 0)),
            "fusion_bytes_saved": 2 * knm_bytes,
            "ref_us_per_call_cpu": us,
        }
        results.append(rec)
        print(f"bench_kernels[B={B},m={m},d={d}],{us:.1f},"
              f"err={err:.2e};bytes_saved={2*knm_bytes}")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernels.json"), "w") as f:
        json.dump(results, f, indent=2)
    return results


def main() -> None:
    argparse.ArgumentParser().parse_args()
    run()


if __name__ == "__main__":
    main()
