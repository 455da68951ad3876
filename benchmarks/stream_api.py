"""Streaming-API benchmark: sampled vs greedy throughput + abort reclaim.

The API redesign claims three things a batch ``run()`` can't show:

* **Sampling costs ~nothing.** The in-jit sampler (temperature / top-k /
  top-p + counter-based RNG) rides the fused decode step; sampled
  throughput must stay within 2x of greedy on the same workload (on the
  tiny reduced config the two [B, V] sorts are a visible fraction of a
  step; on real vocab+model sizes they vanish into the matmuls).
* **Abort reclaims everything, fast.** ``abort()`` on an in-flight
  request — mid-decode *and* mid-PREFILLING (chunked) — must return
  every KV block to the pool immediately (free-block count restored
  exactly) and end the stream with ``finish_reason="abort"``. The
  abort-reclaim latency is the host-side cost of the cancel itself.
* **Streaming is a wrapper, not a fork.** Tokens streamed through
  submit/stream/drain must be identical to the batch ``run()`` path.

Output follows benchmarks/run.py conventions: ``name,us_per_call,derived``
CSV on stdout plus machine-readable ``experiments/paper/BENCH_stream.json``
so the perf trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.stream_api [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict


def _setup():
    import jax
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model, init_params
    from repro.sharding import rules_for

    cfg = reduced(get_config("opt-1.3b"))
    mesh = make_test_mesh()
    rules = rules_for(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, Model(cfg, rules), params, mesh


def _engine(model, params, *, max_batch=8, chunk=None):
    from repro.serving import ContinuousBatchingEngine, EngineConfig
    ecfg = EngineConfig(max_batch=max_batch, block_size=16,
                        kv_pool_tokens=1 << 14, max_model_len=256,
                        prefill_bucket=32, prefill_chunk_tokens=chunk)
    return ContinuousBatchingEngine(model, params, ecfg)


def _throughput_pair(cfg, model, params, mesh, *, n=12, mean_in=24,
                     mean_out=24) -> Dict:
    """Same workload greedy vs sampled (fresh engine each, one warmup run
    so compiles never pollute the timing)."""
    from jax import set_mesh
    from repro.serving import SamplingParams, sharegpt_like

    out: Dict = {}
    with set_mesh(mesh):
        for tag, sampling in (
                ("greedy", None),
                ("sampled", SamplingParams(temperature=0.8, top_k=40,
                                           top_p=0.95, seed=7))):
            eng = _engine(model, params)
            wl = lambda: sharegpt_like(        # noqa: E731
                n, cfg.vocab_size, seed=3, mean_in=mean_in,
                mean_out=mean_out, max_len=96, sigma=0.3,
                sampling=sampling)
            eng.run(wl())                       # warmup (compiles)
            eng.reset_stats()
            m = eng.run(wl())
            out[tag] = {"throughput_tok_s": m.throughput,
                        "itl_mean_ms": m.itl_s * 1e3,
                        "output_tokens": m.output_tokens}
    out["sampled_over_greedy"] = (out["sampled"]["throughput_tok_s"]
                                  / max(out["greedy"]["throughput_tok_s"],
                                        1e-9))
    return out


def _abort_reclaim(cfg, model, params, mesh) -> Dict:
    """Abort mid-decode and mid-prefill; measure reclaim latency and
    verify the pool free-count is restored exactly."""
    from jax import set_mesh
    from repro.serving import SamplingParams, ServingAPI
    import numpy as np

    rng = np.random.default_rng(0)
    out: Dict = {}
    with set_mesh(mesh):
        # --- mid-decode abort (plain engine) ---
        eng = _engine(model, params)
        api = ServingAPI(eng)
        free0 = eng.pool.manager.free_blocks
        victim = api.submit(rng.integers(0, cfg.vocab_size, 48)
                            .astype(np.int32),
                            SamplingParams(max_new_tokens=200))
        for _ in range(4):                      # prefill + a few decodes
            api._backend.pump(api._clock())
        assert victim.request.generated > 1, "victim never started decoding"
        t0 = time.perf_counter()
        assert api.abort(victim)
        abort_us = (time.perf_counter() - t0) * 1e6
        ev = list(api.stream(victim))[-1]
        out["mid_decode"] = {
            "abort_us": abort_us,
            "blocks_restored": eng.pool.manager.free_blocks == free0,
            "finish_reason": ev.finish_reason,
            "tokens_before_abort": len(ev.token_ids)}
        # --- mid-prefill abort (chunked engine, long prompt) ---
        eng = _engine(model, params, chunk=32)
        api = ServingAPI(eng)
        free0 = eng.pool.manager.free_blocks
        victim = api.submit(rng.integers(0, cfg.vocab_size, 200)
                            .astype(np.int32),
                            SamplingParams(max_new_tokens=8))
        api._backend.pump(api._clock())         # one 32-token chunk only
        assert victim.request.req_id in eng._prefilled, \
            "victim should be mid-PREFILLING"
        t0 = time.perf_counter()
        assert api.abort(victim)
        abort_us_pf = (time.perf_counter() - t0) * 1e6
        ev = list(api.stream(victim))[-1]
        out["mid_prefill"] = {
            "abort_us": abort_us_pf,
            "blocks_restored": eng.pool.manager.free_blocks == free0,
            "finish_reason": ev.finish_reason}
    return out


def _stream_equals_run(cfg, model, params, mesh, *, n=6) -> bool:
    """submit/stream/drain must produce the same tokens as batch run()."""
    from jax import set_mesh
    from repro.serving import SamplingParams, sharegpt_like

    sp = SamplingParams(temperature=0.6, top_p=0.9, seed=11)
    wl = lambda: sharegpt_like(n, cfg.vocab_size, seed=5,    # noqa: E731
                               mean_in=16, mean_out=10, max_len=64,
                               sigma=0.3, sampling=sp)
    from repro.serving import ServingAPI
    with set_mesh(mesh):
        eng = _engine(model, params, max_batch=4)
        reqs = wl()
        eng.run(reqs)
        batch_tokens = [list(map(int, r.output_tokens)) for r in reqs]
        eng2 = _engine(model, params, max_batch=4)
        api = ServingAPI(eng2)
        handles = [api.submit(r) for r in wl()]
        outs = api.drain()
        stream_tokens = [list(outs[h.req_id].token_ids) for h in handles]
    return batch_tokens == stream_tokens


def run_suite(smoke: bool = False) -> Dict:
    cfg, model, params, mesh = _setup()
    n = 6 if smoke else 12
    tp = _throughput_pair(cfg, model, params, mesh, n=n)
    ab = _abort_reclaim(cfg, model, params, mesh)
    identical = _stream_equals_run(cfg, model, params, mesh,
                                   n=4 if smoke else 6)
    out = {
        "throughput": tp,
        "abort": ab,
        "stream_equals_run": identical,
        "claim_sampled_within_2x": tp["sampled_over_greedy"] >= 0.5,
        "claim_abort_reclaims_blocks": (
            ab["mid_decode"]["blocks_restored"]
            and ab["mid_prefill"]["blocks_restored"]
            and ab["mid_decode"]["finish_reason"] == "abort"
            and ab["mid_prefill"]["finish_reason"] == "abort"),
        "claim_stream_equals_run": identical,
    }
    os.makedirs("experiments/paper", exist_ok=True)
    with open("experiments/paper/BENCH_stream.json", "w") as f:
        json.dump(out, f, indent=1, default=float)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shape for CI")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    out = run_suite(smoke=args.smoke)
    us = (time.perf_counter() - t0) * 1e6
    tp = out["throughput"]
    print(f"stream_api,{us:.0f},"
          f"sampled_over_greedy={tp['sampled_over_greedy']:.2f};"
          f"abort_us={out['abort']['mid_decode']['abort_us']:.0f};"
          f"abort_prefill_us={out['abort']['mid_prefill']['abort_us']:.0f};"
          f"stream_equals_run={out['stream_equals_run']}")
    ok = (out["claim_sampled_within_2x"]
          and out["claim_abort_reclaims_blocks"]
          and out["claim_stream_equals_run"])
    if not ok:
        print("FAILED_CLAIMS:", {k: v for k, v in out.items()
                                 if k.startswith("claim_")})
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
