"""Host-overlap benchmark: overlapped stepping changes nothing but the
clock.

Under ``EngineConfig.overlap=True`` the engine dispatches plan N+1 while
step N's tokens are still in flight. Its outputs must stay
**bit-identical** to the synchronous loop's across greedy and sampled
decode, chunked prefill, the prefix cache, pool-pressure preemption, and
a kill-1-of-2 replica fault redrive. How much device idle the overlap
removes is read from a profiler trace on the chip (``engine.*`` spans),
not from this CPU benchmark.

Output follows benchmarks/run.py conventions: ``name,us_per_call,derived``
CSV on stdout plus machine-readable ``experiments/paper/BENCH_overlap.json``.

    PYTHONPATH=src python -m benchmarks.host_overlap [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List


def _setup():
    import jax
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model, init_params
    from repro.serving import StepFunctions
    from repro.sharding import rules_for

    cfg = reduced(get_config("opt-1.3b"))
    mesh = make_test_mesh()
    rules = rules_for(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    model = Model(cfg, rules)
    steps = StepFunctions.build(model, 8)
    return cfg, model, params, mesh, steps


def _engine(model, params, steps, **kw):
    from repro.serving import ContinuousBatchingEngine, EngineConfig
    base = dict(max_batch=8, block_size=8, kv_pool_tokens=8192,
                max_model_len=128, prefill_bucket=16)
    base.update(kw)
    return ContinuousBatchingEngine(model, params, EngineConfig(**base),
                                    steps=steps)


def _wl(cfg, n: int, out: int, seed: int = 11, mean_in: int = 14,
        max_len: int = 96, **kw):
    from repro.serving import sharegpt_like
    return sharegpt_like(n, cfg.vocab_size, seed=seed, mean_in=mean_in,
                         mean_out=out, max_len=max_len, sigma=0.3, **kw)


def _record(reqs) -> List:
    return [(list(map(int, r.output_tokens)), r.finish_reason)
            for r in reqs]


# --------------------------------------------------------- bit identity --
def bit_identity(model, params, steps, cfg, mesh, *, n: int,
                 out: int) -> Dict:
    """Every scenario the synchronous loop's tests pin down, replayed
    sync-vs-overlap on fresh engines; one differing token fails it."""
    from jax import set_mesh
    from repro.serving import (FaultInjector, FaultSpec, ReplicatedCluster,
                               SamplingParams, shared_prefix_workload)

    sampled = SamplingParams(temperature=0.9, top_k=40, seed=11)
    res: Dict = {}

    def both(tag, wl_fn, preempt=False, **ecfg_kw):
        outs, preemptions = {}, {}
        with set_mesh(mesh):
            for overlap in (False, True):
                eng = _engine(model, params, steps, overlap=overlap,
                              **ecfg_kw)
                reqs = wl_fn()
                eng.run(reqs)
                outs[overlap] = _record(reqs)
                preemptions[overlap] = eng.preemptions
        ok = outs[True] == outs[False]
        if preempt:
            ok = ok and preemptions[True] > 0
        res[tag] = {"identical": outs[True] == outs[False],
                    "n_requests": len(outs[True]),
                    **({"preemptions": preemptions[True]} if preempt
                       else {})}
        return ok

    res["greedy_ok"] = both("greedy", lambda: _wl(cfg, n, out))
    res["sampled_ok"] = both(
        "sampled", lambda: _wl(cfg, n, out, seed=7, sampling=sampled))
    res["chunked_ok"] = both(
        "chunked", lambda: _wl(cfg, n, out, seed=4),
        prefill_chunk_tokens=16)
    res["prefix_ok"] = both(
        "prefix", lambda: shared_prefix_workload(
            2, 3, cfg.vocab_size, prefix_len=24, suffix_len=8,
            max_new_tokens=out, seed=3),
        prefix_cache=True)
    res["preempt_ok"] = both(
        "preempt", lambda: _wl(cfg, 6, 36, seed=11, sampling=sampled),
        preempt=True, max_batch=6, kv_pool_tokens=256, max_model_len=96)

    # kill 1 of 2: quarantine drops the dead replica's in-flight step,
    # redrive regenerates on the survivor — compare overlapped fault run
    # against the synchronous fault run, same injection point
    outs = {}
    with set_mesh(mesh):
        for overlap in (False, True):
            inj = FaultInjector([FaultSpec("kill", replica=1, step=4)])
            cluster = ReplicatedCluster(
                [_engine(model, params, steps, overlap=overlap)
                 for _ in range(2)],
                mode="sync", faults=inj)
            reqs = _wl(cfg, n, out, seed=9)
            m = cluster.run(reqs)
            outs[overlap] = (_record(reqs), m.redriven > 0,
                             len(inj.fired) == 1)
    res["faults_ok"] = (outs[True][0] == outs[False][0]
                        and outs[True][1] and outs[True][2])
    res["faults"] = {"identical": outs[True][0] == outs[False][0],
                     "redriven": outs[True][1], "fired": outs[True][2]}
    return res


# --------------------------------------------------------------- suite --
def run_suite(smoke: bool = False) -> Dict:
    cfg, model, params, mesh, steps = _setup()
    n = 5 if smoke else 8
    out = 10 if smoke else 16
    ident = bit_identity(model, params, steps, cfg, mesh, n=n, out=out)
    res = {
        "bit_identity": ident,
        "claim_bit_identical_greedy": ident["greedy_ok"],
        "claim_bit_identical_sampled": ident["sampled_ok"],
        "claim_bit_identical_chunked": ident["chunked_ok"],
        "claim_bit_identical_prefix": ident["prefix_ok"],
        "claim_bit_identical_preempt": ident["preempt_ok"],
        "claim_bit_identical_faults": ident["faults_ok"],
    }
    os.makedirs("experiments/paper", exist_ok=True)
    with open("experiments/paper/BENCH_overlap.json", "w") as f:
        json.dump(res, f, indent=1, default=float)
    return res


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shape for CI")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    res = run_suite(smoke=args.smoke)
    us = (time.perf_counter() - t0) * 1e6
    print(f"host_overlap,{us:.0f},"
          + ";".join(f"{k.removeprefix('claim_')}={res[k]}"
                     for k in res if k.startswith("claim_")))
    ok = all(res[k] for k in res if k.startswith("claim_"))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
