"""Decode data-path microbenchmark: gather-copy vs zero-copy paged.

Times one steady-state decode step of the REAL engine under both decode
modes across batch sizes, and pairs each timing with the modeled KV-cache
bytes the step moves:

* ``gather``  — materialize the dense ``[B, S_pad, K, hd]`` view (read
  pool + write view), decode against it (read view, write the stacked
  new-cache copy), scatter the new rows back: ~4x the view bytes.
* ``paged``   — block-table attention reads each request's *valid* blocks
  straight from the pool and scatters exactly B new K/V rows per layer.

This is the engine-level evidence for the paper's central claim chain:
decode is DRAM-bound, so halving avoidable KV traffic shows up directly
in us/step — and in ``benchmarks/engine_curves.py`` as lower ITL.

Output follows benchmarks/run.py conventions: ``name,us_per_call,derived``
CSV on stdout plus a JSON artifact in experiments/paper/.

    PYTHONPATH=src python -m benchmarks.decode_datapath [--batches 1,4,16]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List

import numpy as np


def _mk_engine(cfg, params, rules, mode, max_batch, block_size, pool_tokens):
    from repro.serving import ContinuousBatchingEngine, EngineConfig
    from repro.models.model import Model
    ecfg = EngineConfig(max_batch=max_batch, block_size=block_size,
                        kv_pool_tokens=pool_tokens, max_model_len=512,
                        prefill_bucket=32, decode_mode=mode)
    return ContinuousBatchingEngine(Model(cfg, rules), params, ecfg)


def _prefill_batch(engine, B, prompt_len, vocab, seed=0):
    """Admit B requests with identical prompt length, ready to decode."""
    from repro.serving.workload import Request
    rng = np.random.default_rng(seed)
    rids = []
    for i in range(B):
        prompt = rng.integers(0, vocab, size=prompt_len).astype(np.int32)
        req = Request(req_id=i, prompt=prompt, max_new_tokens=1 << 20)
        engine.pool.manager.allocate(i, prompt_len + 1)
        # completion protocol appends to engine.running (max_new_tokens
        # is effectively unbounded, so the request never finishes here)
        engine._complete_prefill(req, engine._prefill(req), now=0.0)
        rids.append(i)
    return rids


def _time_steps(fn, rids, warmup=3, iters=10) -> float:
    for _ in range(warmup):
        fn(rids)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(rids)
    return (time.perf_counter() - t0) / iters * 1e6        # us/step


def _kv_leaf_bytes(cfg) -> int:
    """Bytes of one token's K+V rows across all KV-bearing layers."""
    import jax.numpy as jnp   # resolves bfloat16, which np.dtype can't
    itemsize = jnp.zeros((), cfg.dtype).dtype.itemsize
    return cfg.kv_bytes_per_token(itemsize)


def modeled_bytes(cfg, B, prompt_len, block_size) -> Dict[str, float]:
    # mirror the engine's actual padding policy, not a reimplementation
    from repro.kvcache.paged import BlockManager
    from repro.serving.engine import _bucket
    per_tok = _kv_leaf_bytes(cfg)
    mgr = BlockManager(1, block_size)
    blocks = mgr.blocks_needed(prompt_len + 1)
    s_pad = _bucket(prompt_len + 1, block_size * 4)
    view = B * s_pad * per_tok
    gather = 4.0 * view + B * per_tok          # copy out+in, decode r/w, rows
    paged = B * blocks * block_size * per_tok + B * per_tok
    return {"gather_bytes": gather, "paged_bytes": paged,
            "bytes_ratio": gather / paged}


def sweep(batches=(1, 4, 8, 16), prompt_len: int = 96,
          block_size: int = 16, seed: int = 0) -> Dict:
    import jax
    from jax import set_mesh
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import init_params
    from repro.sharding import rules_for

    cfg = reduced(get_config("opt-1.3b"))
    mesh = make_test_mesh()
    rules = rules_for(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pool_tokens = 1 << 15
    rows: List[Dict] = []
    with set_mesh(mesh):
        for B in batches:
            row: Dict = {"batch": B, "prompt_len": prompt_len}
            row.update(modeled_bytes(cfg, B, prompt_len, block_size))
            for mode in ("gather", "paged"):
                eng = _mk_engine(cfg, params, rules, mode, max_batch=B,
                                 block_size=block_size,
                                 pool_tokens=pool_tokens)
                rids = _prefill_batch(eng, B, prompt_len, cfg.vocab_size,
                                      seed)
                for rid in rids:
                    eng.pool.manager.append_token(rid, eng._pos[rid] + 1)
                # the decode paths consume Request objects now (they
                # carry the per-request SamplingParams the sampler
                # stacks); the batch sits in engine.running
                fn = (eng._decode_paged if mode == "paged"
                      else eng._decode_gather)
                row[f"{mode}_us"] = _time_steps(fn, list(eng.running))
            row["speedup"] = row["gather_us"] / row["paged_us"]
            rows.append(row)
    out = {"rows": rows,
           "zero_copy_wins_at_16": next(
               (r["speedup"] > 1.0 for r in rows if r["batch"] >= 16),
               None)}
    os.makedirs("experiments/paper", exist_ok=True)
    with open("experiments/paper/decode_datapath.json", "w") as f:
        json.dump(out, f, indent=1, default=float)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="1,4,8,16")
    ap.add_argument("--prompt-len", type=int, default=96)
    args = ap.parse_args()
    batches = tuple(int(b) for b in args.batches.split(",") if b.strip())
    if not batches:
        ap.error("--batches needs a comma-separated list, e.g. 1,4,16")
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    out = sweep(batches=batches, prompt_len=args.prompt_len)
    us = (time.perf_counter() - t0) * 1e6
    for r in out["rows"]:
        print(f"decode_datapath_b{r['batch']},{r['paged_us']:.0f},"
              f"gather_us={r['gather_us']:.0f};speedup={r['speedup']:.2f};"
              f"bytes_ratio={r['bytes_ratio']:.2f}")
    print(f"decode_datapath_total,{us:.0f},"
          f"zero_copy_wins_at_16={out['zero_copy_wins_at_16']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
