"""End-to-end training driver: train a ~100M-param dense model for a few
hundred steps on CPU with the full substrate (data pipeline, AdamW +
cosine schedule, checkpointing).

    PYTHONPATH=src python examples/train_tiny.py [--steps 300]
"""
import argparse
import dataclasses
import sys
import time

sys.path.insert(0, "src")

import jax                                                         # noqa: E402
import jax.numpy as jnp                                            # noqa: E402

from jax import set_mesh
from repro.configs import get_config                               # noqa: E402
from repro.launch.mesh import make_test_mesh                       # noqa: E402
from repro.models.model import init_params                         # noqa: E402
from repro.sharding import rules_for                               # noqa: E402
from repro.training import (AdamWConfig, adamw_init,               # noqa: E402
                            make_train_step, save_checkpoint,
                            synthetic_batches)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--checkpoint", default="experiments/tiny_ckpt.npz")
    args = ap.parse_args()

    # ~100M params: a shrunk qwen-family decoder
    cfg = dataclasses.replace(
        get_config("qwen2.5-3b"), n_layers=8, d_model=768, n_heads=12,
        n_kv_heads=4, d_ff=2048, vocab_size=32000, dtype="float32",
        q_block=128)
    n = cfg.num_params()
    print(f"model: {n/1e6:.1f}M params")
    mesh = make_test_mesh()
    rules = rules_for(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw_init(params)
    opt = AdamWConfig(lr=6e-4, warmup_steps=20, total_steps=args.steps)
    step = jax.jit(make_train_step(cfg, rules, opt))
    data = synthetic_batches(cfg, batch=args.batch, seq=args.seq)

    t0 = time.time()
    with set_mesh(mesh):
        for i in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(data).items()}
            params, opt_state, m = step(params, opt_state, batch)
            if i % 20 == 0 or i == args.steps - 1:
                tok_s = args.batch * args.seq * (i + 1) / (time.time() - t0)
                print(f"step {i:4d}  loss {float(m['loss']):.4f}  "
                      f"gnorm {float(m['grad_norm']):.2f}  {tok_s:.0f} tok/s",
                      flush=True)
    save_checkpoint(args.checkpoint, params, opt_state, args.steps)
    print(f"checkpoint saved to {args.checkpoint}")


if __name__ == "__main__":
    main()
