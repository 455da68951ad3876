"""Prefix-cache reuse benchmark: shared-system-prompt serving, cache
on vs off.

The paper's lever is memory: large-batch decode is DRAM-bound and every
KV block freed is BCA/replication headroom. On a workload of N tenants x
M requests sharing a per-tenant system prompt, the radix prefix cache
should deliver

* >= 2x fewer prefill tokens computed (suffix-only prefill),
* >= 2x fewer KV blocks allocated (shared blocks spliced, not copied),
* bit-identical greedy outputs (reuse must be invisible to the math),

versus the identical engine with the cache off. Default shape is the
acceptance workload: 4 tenants x 32 requests, 256-token shared prefix,
32-token suffix.

Output follows benchmarks/run.py conventions: ``name,us_per_call,derived``
CSV on stdout plus a machine-readable ``experiments/paper/BENCH_prefix.json``
so the perf trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.prefix_reuse [--tenants 4 ...]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict


def run_pair(n_tenants: int = 4, per_tenant: int = 32,
             prefix_len: int = 256, suffix_len: int = 32,
             max_new_tokens: int = 8, max_batch: int = 8,
             block_size: int = 16, kv_pool_tokens: int = 16384,
             seed: int = 0) -> Dict:
    import jax
    from jax import set_mesh
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model, init_params
    from repro.serving import (ContinuousBatchingEngine, EngineConfig,
                               shared_prefix_workload)
    from repro.sharding import rules_for

    cfg = reduced(get_config("opt-1.3b"))
    mesh = make_test_mesh()
    rules = rules_for(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    model = Model(cfg, rules)

    prompt_len = prefix_len + suffix_len
    out: Dict = {"workload": {
        "n_tenants": n_tenants, "per_tenant": per_tenant,
        "prefix_len": prefix_len, "suffix_len": suffix_len,
        "prompt_len": prompt_len, "max_new_tokens": max_new_tokens,
        "max_batch": max_batch, "block_size": block_size,
        "kv_pool_tokens": kv_pool_tokens}}
    tokens: Dict[bool, list] = {}
    with set_mesh(mesh):
        for cache_on in (False, True):
            ecfg = EngineConfig(
                max_batch=max_batch, block_size=block_size,
                kv_pool_tokens=kv_pool_tokens,
                max_model_len=max(256, prompt_len + max_new_tokens + 1),
                prefill_bucket=32, prefix_cache=cache_on)
            engine = ContinuousBatchingEngine(model, params, ecfg)
            if cache_on and engine.prefix is None:
                raise RuntimeError(
                    f"prefix cache unexpectedly disabled: "
                    f"{engine.prefix_disabled_reason}")
            reqs = shared_prefix_workload(
                n_tenants, per_tenant, cfg.vocab_size,
                prefix_len=prefix_len, suffix_len=suffix_len,
                max_new_tokens=max_new_tokens, seed=seed)
            t0 = time.perf_counter()
            m = engine.run(reqs)
            wall = time.perf_counter() - t0
            tokens[cache_on] = [r.output_tokens for r in reqs]
            key = "cache_on" if cache_on else "cache_off"
            out[key] = {
                "wall_s": wall,
                "throughput_tok_s": m.throughput,
                "prefill_tokens_computed": engine.prefill_tokens_computed,
                "kv_blocks_allocated": engine.pool.manager.total_allocations,
                "peak_kv_fraction": m.max_kv_fraction,
                "mean_kv_fraction": m.kv_used_mean,
                "preemptions": engine.preemptions,
            }
            if cache_on:
                st = engine.prefix.stats
                out[key]["prefix"] = {
                    "hit_rate": st.hit_rate,
                    "hit_tokens": st.hit_tokens,
                    "blocks_shared": st.blocks_shared,
                    "blocks_inserted": st.blocks_inserted,
                    "blocks_evicted": st.blocks_evicted,
                    "cached_blocks": engine.prefix.cached_blocks,
                }
    off, on = out["cache_off"], out["cache_on"]
    out["prefill_ratio"] = (off["prefill_tokens_computed"]
                            / max(on["prefill_tokens_computed"], 1))
    out["blocks_ratio"] = (off["kv_blocks_allocated"]
                           / max(on["kv_blocks_allocated"], 1))
    out["tokens_identical"] = tokens[False] == tokens[True]
    out["claim_prefill_2x"] = out["prefill_ratio"] >= 2.0
    out["claim_blocks_2x"] = out["blocks_ratio"] >= 2.0
    out["claim_bit_identical"] = out["tokens_identical"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--per-tenant", type=int, default=32)
    ap.add_argument("--prefix-len", type=int, default=256)
    ap.add_argument("--suffix-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--kv-pool-tokens", type=int, default=16384)
    args = ap.parse_args()

    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    out = run_pair(n_tenants=args.tenants, per_tenant=args.per_tenant,
                   prefix_len=args.prefix_len, suffix_len=args.suffix_len,
                   max_new_tokens=args.max_new, max_batch=args.max_batch,
                   block_size=args.block_size,
                   kv_pool_tokens=args.kv_pool_tokens)
    us = (time.perf_counter() - t0) * 1e6
    print(f"prefix_reuse,{us:.0f},"
          f"prefill_ratio={out['prefill_ratio']:.2f};"
          f"blocks_ratio={out['blocks_ratio']:.2f};"
          f"hit_rate={out['cache_on']['prefix']['hit_rate']:.3f};"
          f"identical={out['tokens_identical']}")
    os.makedirs("experiments/paper", exist_ok=True)
    with open("experiments/paper/BENCH_prefix.json", "w") as f:
        json.dump(out, f, indent=1, default=float)
    failures = [k for k in ("claim_prefill_2x", "claim_blocks_2x",
                            "claim_bit_identical") if not out[k]]
    if failures:
        print(f"FAILED_CLAIMS: {failures}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
