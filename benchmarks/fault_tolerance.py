"""Fault-tolerance benchmark: kill 1 of 2 replicas mid-run, measure what
survives.

Replication (paper Sec. VI-B) multiplies failure domains: R replicas is
R chances for a crash to strand every queued and in-flight request. The
recovery layer's claims, checked here end-to-end:

* **Full completion.** With a seeded ``FaultInjector`` killing one of
  two replicas mid-run, every redriven request still completes — the
  stranded work re-enters through the router and recomputes on the
  survivor (its KV is gone; recompute is the recovery currency).
* **Bit-identical outputs.** The redriven requests produce exactly the
  fault-free run's tokens, greedy *and* sampled (counter-based
  per-request RNG replays the same stream positions), in both ``sync``
  and ``thread`` stepping modes.
* **Goodput retention.** Losing half the cluster mid-run costs
  throughput, not requests: served-requests-per-second stays above a
  floor of the fault-free goodput.
* **Graceful overload.** An oversubscribed cluster with bounded queues
  sheds with ``finish_reason="shed"`` — a breakdown visible in
  ``ClusterMetrics`` — and never surfaces an unhandled exception.

Output follows benchmarks/run.py conventions: ``name,us_per_call,derived``
CSV on stdout plus machine-readable ``experiments/paper/BENCH_faults.json``
so the robustness trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.fault_tolerance [--smoke]
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
    base = dict(max_batch=4, block_size=8, kv_pool_tokens=4096,
                max_model_len=128, prefill_bucket=16)
    base.update(kw)
    return ContinuousBatchingEngine(model, params, EngineConfig(**base),
                                    steps=steps)


def _wl(cfg, n, *, sampled=False):
    from repro.serving import SamplingParams, sharegpt_like
    sp = SamplingParams(temperature=0.8, top_k=40, seed=7) if sampled \
        else None
    return sharegpt_like(n, cfg.vocab_size, seed=9, mean_in=14,
                         mean_out=12, max_len=48, sigma=0.4, sampling=sp)


def _outputs(reqs) -> List[List[int]]:
    return [list(map(int, r.output_tokens)) for r in reqs]


def _served(reqs) -> int:
    return sum(1 for r in reqs if r.finish_reason in ("length", "stop"))


def _kill_pair(cfg, model, params, mesh, steps, *, mode: str, n: int,
               sampled: bool, kill_step: int, seed: int) -> Dict:
    """Fault-free vs kill-1-of-2 run of the same workload; compare."""
    from jax import set_mesh
    from repro.serving import FaultInjector, ReplicatedCluster
    from repro.serving.faults import FaultSpec

    with set_mesh(mesh):
        base_cluster = ReplicatedCluster(
            [_engine(model, params, steps) for _ in range(2)], mode=mode)
        baseline = _wl(cfg, n, sampled=sampled)
        bm = base_cluster.run(baseline)

        inj = FaultInjector(
            [FaultSpec("kill", replica=seed % 2, step=kill_step)],
            seed=seed)
        cluster = ReplicatedCluster(
            [_engine(model, params, steps) for _ in range(2)],
            mode=mode, faults=inj)
        reqs = _wl(cfg, n, sampled=sampled)
        t0 = time.perf_counter()
        m = cluster.run(reqs)
        wall = time.perf_counter() - t0

    identical = _outputs(reqs) == _outputs(baseline)
    retention = (m.goodput_rps / max(bm.goodput_rps, 1e-9))
    return {
        "mode": mode,
        "sampled": sampled,
        "n_requests": n,
        "faults": m.faults,
        "redriven": m.redriven,
        "lost": m.lost,
        "served": _served(reqs),
        "completed": m.completed,
        "bit_identical": identical,
        "availability": m.availability,
        "goodput_rps": m.goodput_rps,
        "baseline_goodput_rps": bm.goodput_rps,
        "goodput_retention": retention,
        "wall_s": wall,
    }


def _overload(cfg, model, params, mesh, steps, *, n: int) -> Dict:
    """Oversubscribed bounded-queue cluster: degrade, never die."""
    from jax import set_mesh
    from repro.serving import ReplicatedCluster

    with set_mesh(mesh):
        cluster = ReplicatedCluster(
            [_engine(model, params, steps, max_waiting=2, max_batch=2)
             for _ in range(2)],
            mode="sync")
        reqs = _wl(cfg, n)
        try:
            m = cluster.run(reqs)
            crashed = False
        except Exception:           # the claim is exactly that this
            crashed = True          # never happens
            m = None
    out = {
        "n_requests": n,
        "crashed": crashed,
    }
    if m is not None:
        out.update({
            "served": _served(reqs),
            "shed": m.shed,
            "shed_reasons": dict(cluster.shed_reasons),
            "all_terminal": all(r.t_done is not None for r in reqs),
            "finish_reasons": dict(m.finish_reasons),
        })
    return out


def run_suite(smoke: bool = False) -> Dict:
    cfg, model, params, mesh, steps = _setup()
    n = 6 if smoke else 12
    kill_step = 4 if smoke else 8
    scenarios = [
        _kill_pair(cfg, model, params, mesh, steps, mode="sync", n=n,
                   sampled=False, kill_step=kill_step, seed=1),
        _kill_pair(cfg, model, params, mesh, steps, mode="thread", n=n,
                   sampled=False, kill_step=kill_step, seed=2),
        _kill_pair(cfg, model, params, mesh, steps, mode="sync", n=n,
                   sampled=True, kill_step=kill_step, seed=3),
    ]
    overload = _overload(cfg, model, params, mesh, steps, n=2 * n)
    out = {
        "scenarios": scenarios,
        "overload": overload,
        "claim_full_completion": all(
            s["completed"] == s["n_requests"] and s["lost"] == 0
            for s in scenarios),
        "claim_bit_identical": all(s["bit_identical"] for s in scenarios),
        "claim_redrive_happened": all(
            s["faults"] == 1 and s["redriven"] > 0 for s in scenarios),
        # losing 1 of 2 replicas mid-run may halve throughput; it must
        # not collapse it (recompute on the survivor keeps goodput up)
        "claim_goodput_floor": all(
            s["goodput_retention"] >= 0.2 for s in scenarios),
        "claim_graceful_overload": (
            not overload["crashed"] and overload.get("all_terminal", False)
            and overload.get("shed", 0) > 0),
    }
    os.makedirs("experiments/paper", exist_ok=True)
    with open("experiments/paper/BENCH_faults.json", "w") as f:
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
    ret = min(s["goodput_retention"] for s in out["scenarios"])
    print(f"fault_tolerance,{us:.0f},"
          f"bit_identical={out['claim_bit_identical']};"
          f"full_completion={out['claim_full_completion']};"
          f"min_goodput_retention={ret:.2f};"
          f"graceful_overload={out['claim_graceful_overload']}")
    ok = (out["claim_bit_identical"] and out["claim_full_completion"]
          and out["claim_redrive_happened"]
          and out["claim_graceful_overload"])
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
