"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --mesh 16x16 --steps 100 --ckpt-dir /ckpt/llama3

On real hardware the mesh spans jax.devices(); `--reduced` swaps in the
same-family smoke config so the full path (mesh, shardings, train loop,
checkpointing, restart) can be exercised anywhere, including this CPU
container.  Restart-after-failure = re-running the same command: the
launcher resumes from the newest checkpoint automatically.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ALIASES, get_config
from repro.configs.reduced import reduce_config
from repro.launch.mesh import make_mesh
from repro.models import init_params, set_mesh
from repro.sharding import batch_axes, batch_sharding, tree_shardings
from repro.training import (AdamW, checkpoint, make_train_state,
                            make_train_step, synthetic_batch)


def parse_mesh(spec: str, axis_names=("data", "model")):
    dims = tuple(int(x) for x in spec.split("x"))
    if len(dims) == 3:
        axis_names = ("pod", "data", "model")
    return make_mesh(dims, axis_names[:len(dims)])


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mesh", default=None, help="e.g. 16x16 or 2x16x16")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="same-family smoke config (CPU-sized)")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    args = ap.parse_args(argv)

    cfg = get_config(ALIASES.get(args.arch, args.arch))
    if args.reduced:
        cfg = reduce_config(cfg)
    if args.microbatches:
        cfg = cfg.with_(train_microbatches=args.microbatches)

    if args.mesh:
        mesh = parse_mesh(args.mesh)
    else:
        n = jax.device_count()
        mesh = make_mesh((n, 1), ("data", "model"))
    set_mesh(mesh, batch_axes(mesh))
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.0f}M "
          f"mesh={dict(mesh.shape)} microbatches={cfg.train_microbatches}")

    opt = AdamW(lr=args.lr, warmup=min(100, args.steps // 10 + 1),
                total_steps=args.steps)
    with mesh:
        params = init_params(jax.random.PRNGKey(0), cfg)
        state = make_train_state(params, opt,
                                 compress=args.compress_grads)
        sh = tree_shardings(state, cfg, mesh)
        state = jax.device_put(state, sh)
        start = 0
        if args.ckpt_dir and checkpoint.latest_step(args.ckpt_dir) is not None:
            start = checkpoint.latest_step(args.ckpt_dir)
            state = checkpoint.restore(args.ckpt_dir, state, shardings=sh)
            print(f"resumed from step {start}")
        step_fn = jax.jit(
            make_train_step(cfg, opt,
                            microbatches=cfg.train_microbatches,
                            compress_grads=args.compress_grads,
                            grad_shardings=sh.params),
            in_shardings=(sh, batch_sharding(
                synthetic_batch(cfg, args.batch, args.seq), mesh)),
            out_shardings=(sh, None), donate_argnums=(0,))
        t0 = time.time()
        for i in range(start, args.steps):
            batch = synthetic_batch(cfg, args.batch, args.seq, step=i)
            state, m = step_fn(state, batch)
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:5d} loss={float(m['loss']):.3f} "
                      f"gnorm={float(m['grad_norm']):.2f}")
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                checkpoint.save(args.ckpt_dir, i + 1, state)
        dt = time.time() - t0
        print(f"{args.steps - start} steps in {dt:.1f}s "
              f"({(args.steps - start) / max(dt, 1e-9):.2f} steps/s)")


if __name__ == "__main__":
    main()
