"""Training launcher.

Examples:
  # real CPU run on a reduced config:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --reduced \
      --steps 50 --batch 8 --seq 128
  # production lowering check is launch/dryrun.py (--shape train_4k)
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size the model (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import set_mesh
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import init_params
    from repro.sharding import rules_for
    from repro.training import (AdamWConfig, adamw_init, make_train_step,
                                save_checkpoint, synthetic_batches)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = make_test_mesh()
    rules = rules_for(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw_init(params)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                      total_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, rules, opt))
    data = synthetic_batches(cfg, batch=args.batch, seq=args.seq)

    with set_mesh(mesh):
        t0 = time.time()
        for i in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(data).items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                print(f"step {i:5d}  loss {float(metrics['loss']):.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"({(time.time()-t0)/(i+1):.2f}s/step)", flush=True)
    if args.checkpoint:
        save_checkpoint(args.checkpoint, params, opt_state, args.steps)
        print(f"saved {args.checkpoint}")


if __name__ == "__main__":
    main()
