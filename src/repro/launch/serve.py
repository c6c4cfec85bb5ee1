"""Serving driver: batched prefill + autoregressive decode — and the GP
serving mode for the stitched PSVGP surface.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --batch 4 --prompt-len 64 --gen 32

GP mode (the paper's E3SM in-situ setting: train the partitioned surface,
then answer query batches at serving rates). A thin shim over
``repro.api``: the flags parse into a ``FitConfig``/``ServeConfig``,
``api.fit`` trains the PSVGP on the synthetic E3SM-like field (all local
posteriors factorized ONCE into a ``PosteriorCache``; ``--gp-save`` /
``--gp-artifact`` persist and reuse the trained artifact), and
``api.Server`` runs the batched query loop with a latency/throughput
report:

  PYTHONPATH=src python -m repro.launch.serve --gp \
      --gp-grid 8 --gp-m 10 --gp-train-iters 200 \
      --gp-batch 2048 --gp-requests 50

``--sharded`` switches the GP mode from the replicated cache to the
distributed endpoint (``repro.launch.serve_sharded``): the PosteriorCache
is sharded one partition per device over a gy x gx mesh, queries are
routed to their owning partition, and corner blending is resolved with a
1-hop ppermute halo exchange. Needs gp-grid^2 devices — on CPU they are
forced as virtual host devices, which must happen before jax initializes,
so --sharded is handled before any other jax work:

  PYTHONPATH=src python -m repro.launch.serve --gp --sharded --gp-grid 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get, get_smoke
from repro.runtime.steps import init_train_state, make_decode_step, make_prefill_step


def serve_gp(args) -> None:
    """Batched query loop over the blended PSVGP surface — a thin shim
    over ``repro.api``: fit (or load) the artifact, then serve the request
    stream through a replicated ``api.Server``."""
    from repro import api
    from repro.launch.serve_sharded import (
        load_or_train,
        query_batches,
        session_configs,
    )

    fit_cfg, serve_cfg, _ = session_configs(args, expect_mode="replicated")
    ds, fitted = load_or_train(args, fit_cfg=fit_cfg)

    t0 = time.time()
    if serve_cfg is None:
        serve_cfg = api.ServeConfig(mode="replicated")
    server = api.Server(fitted, serve_cfg)
    if ds is not None:
        print(f"posterior cache built in {(time.time()-t0)*1e3:.1f} ms "
              f"(one O(P m^3) factorization, reused by every request)")
    else:
        print("posterior cache restored from the artifact "
              "(no factorization at serve time)")

    # synthetic request stream: uniform query batches over the domain
    batches = query_batches(
        fitted.grid, ds, batch=args.gp_batch, requests=args.gp_requests,
        seed=args.seed, skew=getattr(args, "gp_skew", 0.0),
    )
    report = server.stream(batches)
    pct, qps = report["latency_ms"], report["points_per_s"]
    print(f"served {args.gp_requests} requests x {args.gp_batch} points")
    print(f"latency/request ms: p50={pct['p50_ms']:.2f} "
          f"p95={pct['p95_ms']:.2f} p99={pct['p99_ms']:.2f}")
    print(f"throughput: {qps:,.0f} points/s")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--gp", action="store_true", help="serve the stitched PSVGP surface")
    ap.add_argument("--sharded", action="store_true",
                    help="GP mode: serve from the mesh-sharded PosteriorCache "
                         "(repro.launch.serve_sharded) instead of the replicated one")
    # the --gp-* flags are owned by serve_sharded (one definition for both
    # entry points); its import is device-state free, so the virtual-device
    # setup of --sharded still works.
    from repro.launch import use_compile_cache
    from repro.launch.serve_sharded import add_gp_args

    add_gp_args(ap)
    args = ap.parse_args()
    use_compile_cache()

    if args.sharded and not args.gp:
        ap.error("--sharded only applies to the GP serving mode (add --gp)")
    if args.http and not args.gp:
        ap.error("--http only applies to the GP serving mode (add --gp)")
    if args.gp:
        if args.gp_requests < 1 or args.gp_batch < 1:
            ap.error("--gp-requests and --gp-batch must be >= 1")
        if args.http:
            # like --sharded below: nothing above initialized the jax
            # backend, so the HTTP driver can still force virtual devices.
            from repro.net.server import serve_http

            serve_http(
                args, expect_mode="sharded" if args.sharded else "replicated"
            )
            return
        if args.sharded:
            # imports and argparse above never initialize the jax backend,
            # so serve_sharded can still force the virtual device count.
            from repro.launch.serve_sharded import serve_sharded

            serve_sharded(args)
        else:
            serve_gp(args)
        return
    if not args.arch:
        ap.error("--arch required (or --gp for the PSVGP surface)")

    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    key = jax.random.PRNGKey(args.seed)
    state = init_train_state(key, cfg)
    B, S = args.batch, args.prompt_len
    cache_len = S + args.gen + (cfg.vision.num_patches if cfg.vision is not None else 0)

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    kwargs = {}
    if cfg.encoder is not None:
        e = cfg.encoder
        kwargs["frames"] = jnp.asarray(rng.normal(size=(B, e.num_frames, e.frontend_dim)), jnp.float32)
    if cfg.vision is not None:
        v = cfg.vision
        kwargs["patches"] = jnp.asarray(rng.normal(size=(B, v.num_patches, v.vit_dim)), jnp.float32)

    prefill = jax.jit(make_prefill_step(cfg, cache_len=cache_len))
    decode = jax.jit(make_decode_step(cfg))

    t0 = time.time()
    logits, cache = prefill(state.params, prompts, **kwargs)
    logits.block_until_ready()
    t_prefill = time.time() - t0
    print(f"prefill: {B}x{S} tokens in {t_prefill*1e3:.1f} ms "
          f"({B*S/t_prefill:,.0f} tok/s)")

    pos0 = S + (cfg.vision.num_patches if cfg.vision is not None else 0)
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    generated = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = decode(state.params, cache, jnp.asarray(pos0 + i, jnp.int32), tok)
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits / args.temperature)[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        generated.append(tok)
    jax.block_until_ready(generated[-1])
    t_dec = time.time() - t0
    out = jnp.concatenate(generated, axis=1)
    print(f"decode: {args.gen} steps x {B} seqs in {t_dec*1e3:.1f} ms "
          f"({B*(args.gen-1)/max(t_dec,1e-9):,.0f} tok/s)")
    print("sample row 0:", np.asarray(out[0])[:16], "...")


if __name__ == "__main__":
    main()
