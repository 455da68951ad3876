import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")

"""Paper Table IV end-to-end, on real engines: MAX single replica vs
BCA x R replicas.

The H100 paper co-locates replicas with MPS; the TPU-idiomatic adaptation
(core.replication) is spatial — a single "MAX" replica spans the whole
device mesh (paying SPMD partitioning/collective overhead every step),
while BCA-sized replicas each own a disjoint mesh slice and run
concurrently. This benchmark reproduces that comparison on virtual CPU
devices with the reduced model:

1. measure T(B)/ITL(B)/KV(B) curves on a single mesh slice,
2. BCA (Eq. 2) picks B_opt; ReplicationPlanner + the mesh slice count
   pick R (the autoscaler loop),
3. run the SAME workload through (a) one full-mesh engine at the
   pool-limited MAX batch and (b) the R-replica sliced cluster,
4. report aggregate tok/s, the speedup, and tail latencies.

A fixed KV-token budget stands in for HBM: MAX reserves max_model_len per
slot (vLLM-style worst case) so B_MAX = budget / max_model_len; the
cluster splits the same budget across replicas.

    PYTHONPATH=src python benchmarks/replication_throughput.py
"""
import argparse
import dataclasses
import json
import sys

sys.path.insert(0, "src")

import jax                                                         # noqa: E402

from jax import set_mesh                                           # noqa: E402
from repro.launch.mesh import make_mesh                            # noqa: E402
from repro.configs import get_config, reduced                      # noqa: E402
from repro.core.hardware import TPU_V5E                            # noqa: E402
from repro.core.replication import slice_mesh                      # noqa: E402
from repro.models.model import Model, init_params                  # noqa: E402
from repro.serving import (ContinuousBatchingEngine, EngineConfig,  # noqa: E402
                           ReplicatedCluster, StepFunctions, sharegpt_like)
from repro.serving.cluster import decide, measure_curves           # noqa: E402
from repro.sharding import rules_for                               # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--curve-requests", type=int, default=12)
    ap.add_argument("--batches", default="2,4,8,16")
    ap.add_argument("--kv-budget", type=int, default=16384,
                    help="total KV tokens (the 'HBM' both sides share)")
    ap.add_argument("--max-model-len", type=int, default=512)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--mean-in", type=int, default=16)
    ap.add_argument("--mean-out", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policy", default="round-robin")
    ap.add_argument("--out", default="experiments/paper")
    args = ap.parse_args()

    cfg = reduced(get_config("opt-1.3b"))
    n_dev = len(jax.devices())
    if n_dev < 2:
        print(f"[warn] only {n_dev} device(s) — the sliced cluster "
              f"degenerates; run without XLA_FLAGS overrides")
    full_mesh = make_mesh((n_dev, 1), ("data", "model"))
    params = init_params(cfg, jax.random.PRNGKey(0))

    def ecfg(max_batch, pool_tokens):
        return EngineConfig(max_batch=max_batch, block_size=args.block_size,
                            kv_pool_tokens=(pool_tokens // args.block_size)
                            * args.block_size,
                            max_model_len=args.max_model_len,
                            prefill_bucket=32)

    def workload(n, seed):
        return sharegpt_like(n, cfg.vocab_size, seed=seed,
                             mean_in=args.mean_in, mean_out=args.mean_out,
                             max_len=96, sigma=0.3)

    # ---- 1. measured curves on ONE mesh slice (a replica-sized engine) --
    slice0 = slice_mesh(full_mesh, n_dev)[0]
    slice_model = Model(cfg, rules_for(slice0))
    slice_params = jax.device_put(params, slice0.devices.flat[0])
    slice_pool = args.kv_budget // max(n_dev, 2)
    steps = StepFunctions.build(slice_model, args.block_size)
    batches = [int(b) for b in args.batches.split(",")]

    def make_engine(b):
        return ContinuousBatchingEngine(slice_model, slice_params,
                                        ecfg(b, slice_pool), steps=steps)

    with set_mesh(slice0):
        curves = measure_curves(
            make_engine, lambda: workload(args.curve_requests, args.seed + 1),
            batches)

    # ---- 2. BCA + replication plan (the autoscaler decision) -----------
    ctx = args.mean_in + args.mean_out
    decision = decide(curves, hw=TPU_V5E, cfg=cfg, ctx=ctx,
                      slo_factor=2.0, eps=0.05, mesh_slices=n_dev)
    print(decision.summary())
    n_rep = max(decision.n_replicas, 1)

    # ---- 3a. single MAX replica spanning the full mesh -----------------
    b_max = max(args.kv_budget // args.max_model_len, 1)
    single = ContinuousBatchingEngine(Model(cfg, rules_for(full_mesh)),
                                      params, ecfg(b_max, args.kv_budget))
    with set_mesh(full_mesh):
        single.run(workload(args.requests, args.seed))  # warmup/compile
        single.reset_stats()
        m_single = single.run(workload(args.requests, args.seed))
    print(f"[single MAX] B={b_max} full mesh ({n_dev} dev): "
          f"{m_single.row()}")
    print(f"             {m_single.latency_row()}")

    # ---- 3b. BCA x R replicas on mesh slices ---------------------------
    cluster = ReplicatedCluster.sliced(
        cfg, params, ecfg(decision.per_replica_batch, args.kv_budget // n_rep),
        full_mesh, n_rep, policy=args.policy, mode="thread")
    cluster.run(workload(args.requests, args.seed))     # warmup/compile
    cluster.reset_stats()
    m_cluster = cluster.run(workload(args.requests, args.seed))
    print(m_cluster.summary())

    # ---- 4. verdict ----------------------------------------------------
    speedup = m_cluster.output_throughput / max(
        m_single.output_throughput, 1e-9)
    ok = speedup >= 1.3
    print(f"\nBCA x {n_rep} replicas: {m_cluster.output_throughput:.1f} "
          f"out tok/s vs single MAX {m_single.output_throughput:.1f} "
          f"-> {speedup:.2f}x  [{'OK' if ok else 'BELOW 1.3x'}]")

    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "replication_throughput.json")
    with open(path, "w") as f:
        json.dump({
            "curves": {"batches": curves.batches.tolist(),
                       "throughput": curves.throughput.tolist(),
                       "itl_s": curves.itl_s.tolist(),
                       "kv_fraction": curves.kv_fraction.tolist()},
            "bca": decision.bca.summary(),
            "plan": decision.plan.summary(),
            "n_replicas": n_rep,
            "b_opt": decision.per_replica_batch,
            "b_max": b_max,
            "single": dataclasses.asdict(m_single),
            "cluster_out_tok_s": m_cluster.output_throughput,
            "cluster_ttft_p95_s": m_cluster.ttft.p95,
            "cluster_itl_p95_s": m_cluster.itl.p95,
            "speedup": speedup,
            "ok": ok,
        }, f, indent=1, default=float)
    print(f"wrote {path}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
