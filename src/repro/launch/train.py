"""Training driver for the LM substrate.

Runs REAL steps on whatever devices exist (CPU here, a pod in production —
the same code path; only the mesh differs). Wires data pipeline, sharding
rules, checkpointing and the metrics log together.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_train_state, save_train_state
from repro.configs import get, get_smoke
from repro.data.tokens import synthetic_token_batches
from repro.launch import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.runtime.steps import init_train_state, make_train_step
from repro.sharding import state_pspecs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    mesh = make_host_mesh()
    print(f"arch={cfg.name} devices={jax.device_count()} mesh={dict(mesh.shape)}")

    state = init_train_state(jax.random.PRNGKey(args.seed), cfg)
    if args.ckpt_dir:
        restored = load_train_state(args.ckpt_dir, state)
        if restored is not None:
            state = restored
            print(f"restored checkpoint at step {int(state.step)}")
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(state.params))
    print(f"parameters: {n_params/1e6:.2f}M")

    pspecs = state_pspecs(state, mesh)
    with jax.set_mesh(mesh):
        step_fn = jax.jit(
            make_train_step(cfg, learning_rate=args.lr),
            in_shardings=(pspecs, None),
            out_shardings=(pspecs, None),
        )
        data = synthetic_token_batches(cfg.vocab_size, args.batch, args.seq, seed=args.seed)
        rng = np.random.default_rng(args.seed)
        t0 = time.time()
        for i, (toks, targets) in enumerate(data):
            if i >= args.steps:
                break
            batch = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(targets)}
            if cfg.encoder is not None:
                e = cfg.encoder
                batch["frames"] = jnp.asarray(
                    rng.normal(size=(args.batch, e.num_frames, e.frontend_dim)), jnp.float32
                )
            if cfg.vision is not None:
                v = cfg.vision
                batch["patches"] = jnp.asarray(
                    rng.normal(size=(args.batch, v.num_patches, v.vit_dim)), jnp.float32
                )
            state, metrics = step_fn(state, batch)
            if (i + 1) % args.log_every == 0:
                dt = time.time() - t0
                tok_s = args.batch * args.seq * args.log_every / dt
                print(
                    f"step {i+1:5d}  loss {float(metrics['loss']):.4f}  "
                    f"ce {float(metrics['ce']):.4f}  {tok_s:,.0f} tok/s"
                )
                t0 = time.time()
            if args.ckpt_dir and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
                p = save_train_state(args.ckpt_dir, i + 1, state)
                print(f"checkpoint -> {p}")
    print("done.")


if __name__ == "__main__":
    main()
