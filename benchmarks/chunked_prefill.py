"""Chunked-prefill benchmark: head-of-line blocking, serial vs mixed steps.

The paper's decode hot loop is memory-bound and its ITL is the SLO input
BCA optimizes — but serial admission-time prefill lets one long prompt
freeze every running decode for its full prefill duration, injecting
multi-hundred-ms stalls that no ``max_batch`` choice can fix. On a mixed
long/short-prompt ShareGPT-like workload the Sarathi-style chunked
scheduler (``EngineConfig.prefill_chunk_tokens``) must deliver

* >= 2x lower p95 ITL (the long-prompt stalls collapse into bounded
  per-step chunks),
* bit-identical greedy outputs (chunking must be invisible to the math),
* total throughput within 10% of the serial baseline,

versus the identical engine with chunking off (``--no-chunking`` runs
only the baseline, for A/B sweeps). Both engines are warmed up on a copy
of the workload first so jit compiles never pollute the latency samples.

Output follows benchmarks/run.py conventions: ``name,us_per_call,derived``
CSV on stdout plus machine-readable ``experiments/paper/BENCH_chunked.json``
so the perf trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.chunked_prefill [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional


def _workload(n_short, n_long, short_len, long_len, short_new, long_new,
              every, vocab, seed):
    from repro.serving import long_short_workload
    return long_short_workload(n_short, n_long, vocab, short_len=short_len,
                               long_len=long_len, short_new=short_new,
                               long_new=long_new, every=every, seed=seed)


def _run_one(model, params, mesh, ecfg_kw: Dict, wl_kw: Dict,
             chunk: Optional[int], repeats: int = 1) -> Dict:
    """Warm up (compiles), then measure ``repeats`` runs and keep the one
    with the lowest p95 ITL — timing claims should compare the modes'
    quiet-box behaviour, not whichever run a noisy host interrupted.
    Outputs must be identical across every repeat (asserted)."""
    from jax import set_mesh
    from repro.serving import ContinuousBatchingEngine, EngineConfig

    with set_mesh(mesh):
        ecfg = EngineConfig(prefill_chunk_tokens=chunk, **ecfg_kw)
        engine = ContinuousBatchingEngine(model, params, ecfg)
        if chunk is not None and not engine.chunking:
            raise RuntimeError(f"chunked prefill unexpectedly disabled: "
                               f"{engine.chunking_disabled_reason}")
        engine.run(_workload(**wl_kw))          # warmup: compile all buckets
        best, outputs = None, None
        for _ in range(max(1, repeats)):
            engine.reset_stats()
            reqs = _workload(**wl_kw)
            t0 = time.perf_counter()
            m = engine.run(reqs)
            wall = time.perf_counter() - t0
            outs = [list(map(int, r.output_tokens)) for r in reqs]
            if outputs is None:
                outputs = outs
            elif outs != outputs:
                raise RuntimeError("outputs changed across repeat runs")
            run = {
                "wall_s": wall,
                "throughput_tok_s": m.throughput,
                "itl_p50_ms": m.itl.p50 * 1e3,
                "itl_p95_ms": m.itl.p95 * 1e3,
                "itl_p99_ms": m.itl.p99 * 1e3,
                "itl_mean_ms": m.itl_s * 1e3,
                "ttft_p95_ms": m.ttft.p95 * 1e3,
                "stall_mean_ms": m.stall_s_mean * 1e3,
                "stall_p95_ms": m.stall.p95 * 1e3,
                "prefill_tokens_per_step": m.prefill_tokens_per_step,
                "decode_tokens_per_step": m.decode_tokens_per_step,
                "preemptions": engine.preemptions,
            }
            if best is None or run["itl_p95_ms"] < best["itl_p95_ms"]:
                best = run
    best["outputs"] = outputs
    return best


def run_pair(n_short: int = 16, n_long: int = 8, short_len: int = 24,
             long_len: int = 768, short_new: int = 24, long_new: int = 6,
             every: int = 2, chunk_tokens: int = 192, max_batch: int = 4,
             block_size: int = 16, kv_pool_tokens: int = 4096,
             seed: int = 0, baseline_only: bool = False,
             repeats: int = 2) -> Dict:
    import jax
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model, init_params
    from repro.sharding import rules_for

    cfg = reduced(get_config("opt-1.3b"))
    mesh = make_test_mesh()
    params = init_params(cfg, jax.random.PRNGKey(0))
    model = Model(cfg, rules_for(mesh))

    ecfg_kw = dict(max_batch=max_batch, block_size=block_size,
                   kv_pool_tokens=kv_pool_tokens,
                   max_model_len=long_len + max(short_new, long_new) + 1,
                   prefill_bucket=32)
    wl_kw = dict(n_short=n_short, n_long=n_long, short_len=short_len,
                 long_len=long_len, short_new=short_new, long_new=long_new,
                 every=every, vocab=cfg.vocab_size, seed=seed)
    out: Dict = {"workload": {**wl_kw, "chunk_tokens": chunk_tokens,
                              "repeats": repeats, **ecfg_kw}}
    out["serial"] = _run_one(model, params, mesh, ecfg_kw, wl_kw, None,
                             repeats=repeats)
    if baseline_only:
        out["serial"].pop("outputs")
        return out
    out["chunked"] = _run_one(model, params, mesh, ecfg_kw, wl_kw,
                              chunk_tokens, repeats=repeats)
    base, chk = out["serial"], out["chunked"]
    out["tokens_identical"] = base.pop("outputs") == chk.pop("outputs")
    out["itl_p95_ratio"] = base["itl_p95_ms"] / max(chk["itl_p95_ms"], 1e-9)
    out["throughput_ratio"] = (chk["throughput_tok_s"]
                               / max(base["throughput_tok_s"], 1e-9))
    out["claim_itl_p95_2x"] = out["itl_p95_ratio"] >= 2.0
    out["claim_bit_identical"] = out["tokens_identical"]
    out["claim_throughput_within_10pct"] = out["throughput_ratio"] >= 0.9
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI shape; hard-fails only on the "
                         "deterministic bit-identity claim (wall-clock "
                         "ratios on shared CI runners are reported, not "
                         "gated — the full shape gates all three)")
    ap.add_argument("--no-chunking", action="store_true",
                    help="run only the serial baseline (no claims)")
    ap.add_argument("--n-short", type=int, default=None)
    ap.add_argument("--n-long", type=int, default=None)
    ap.add_argument("--long-len", type=int, default=None)
    ap.add_argument("--chunk-tokens", type=int, default=None)
    args = ap.parse_args(argv)

    kw: Dict = {}
    if args.smoke:
        kw = dict(n_short=8, n_long=4, short_len=16, long_len=512,
                  short_new=16, long_new=4, every=2, chunk_tokens=128,
                  max_batch=4, kv_pool_tokens=4096, repeats=1)
    for name in ("n_short", "n_long", "long_len", "chunk_tokens"):
        v = getattr(args, name)
        if v is not None:
            kw[name] = v

    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    out = run_pair(baseline_only=args.no_chunking, **kw)
    us = (time.perf_counter() - t0) * 1e6
    if args.no_chunking:
        b = out["serial"]
        print(f"chunked_prefill_baseline,{us:.0f},"
              f"itl_p95_ms={b['itl_p95_ms']:.2f};"
              f"stall_p95_ms={b['stall_p95_ms']:.2f};"
              f"T={b['throughput_tok_s']:.1f}")
        return 0
    print(f"chunked_prefill,{us:.0f},"
          f"itl_p95_ratio={out['itl_p95_ratio']:.2f};"
          f"throughput_ratio={out['throughput_ratio']:.3f};"
          f"identical={out['tokens_identical']};"
          f"serial_p95_ms={out['serial']['itl_p95_ms']:.2f};"
          f"chunked_p95_ms={out['chunked']['itl_p95_ms']:.2f}")
    os.makedirs("experiments/paper", exist_ok=True)
    with open("experiments/paper/BENCH_chunked.json", "w") as f:
        json.dump(out, f, indent=1, default=float)
    # timing claims only gate the full acceptance shape: on noisy shared
    # CI runners the smoke step must stay deterministic (bit-identity),
    # with the perf ratios reported for eyeballs, not exit codes
    gated = ("claim_bit_identical",) if args.smoke else (
        "claim_itl_p95_2x", "claim_bit_identical",
        "claim_throughput_within_10pct")
    failures = [k for k in gated if not out[k]]
    if failures:
        print(f"FAILED_CLAIMS: {failures}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
