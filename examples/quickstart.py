"""Quickstart: build a reduced model, run a forward pass, prefill + decode
a few tokens, and print the paper's roofline verdict for the full config.

    PYTHONPATH=src python examples/quickstart.py [--arch qwen2.5-3b]
"""
import argparse
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, "src")

from jax import set_mesh
from repro.configs import get_config, reduced                     # noqa: E402
from repro.core import TPU_V5E, decode_step_terms                 # noqa: E402
from repro.launch.mesh import make_test_mesh                      # noqa: E402
from repro.models import model as M                               # noqa: E402
from repro.sharding import rules_for                              # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    args = ap.parse_args()

    full = get_config(args.arch)
    cfg = reduced(full)
    mesh = make_test_mesh()
    rules = rules_for(mesh)
    print(f"arch={full.name} ({full.arch_type}), {full.n_layers}L "
          f"d={full.d_model} params={full.num_params()/1e9:.2f}B "
          f"(active {full.active_params()/1e9:.2f}B)")

    with set_mesh(mesh):
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                 cfg.vocab_size)
        batch = {"tokens": tok}
        if cfg.arch_type == "vlm":
            batch["img_embeds"] = jnp.zeros((2, cfg.n_img_tokens,
                                             cfg.d_model))
        if cfg.embedding_inputs:
            batch = {"embeds": jax.random.normal(
                jax.random.PRNGKey(2), (2, 16, cfg.d_model)) * 0.02}
        logits, aux = M.forward(params, cfg, rules, batch)
        print(f"forward OK: logits {logits.shape}, aux={float(aux):.4f}")

        if cfg.is_decoder:
            last, cache, pos = M.prefill(params, cfg, rules, batch,
                                         cache_len=24)
            toks = [int(jnp.argmax(last[0]))]
            for t in range(16, 22):
                lg, cache = M.decode_step(
                    params, cfg, rules, cache,
                    jnp.asarray([toks[-1]] * 2, jnp.int32), jnp.int32(t))
                toks.append(int(jnp.argmax(lg[0])))
            print(f"decoded tokens: {toks}")

    # the paper's analysis on the FULL config (no allocation needed)
    if full.is_decoder:
        t = decode_step_terms(full, batch=64, ctx=2048, hw=TPU_V5E)
        print("\nTPU v5e single-chip decode step @B=64, ctx=2048:")
        for name, c in t.classes.items():
            bound = "memory" if c["memory_s"] > c["compute_s"] else "compute"
            print(f"  {name:10s} AI={t.ai(name):8.2f} FLOP/B -> {bound}-bound")


if __name__ == "__main__":
    main()
