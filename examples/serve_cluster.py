import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")

"""Serve a bursty workload through the replicated cluster: R engine
replicas (one per mesh slice when >= 2 devices are visible, else
co-located), a pluggable router, and aggregated cluster metrics.

With --autoscale the cluster is sized by the paper's loop instead of
--replicas: sweep measured curves on one replica, solve BCA for B_opt,
cap the ReplicationPlanner's count by the available mesh slices.

    PYTHONPATH=src python examples/serve_cluster.py
    PYTHONPATH=src python examples/serve_cluster.py --autoscale --policy jsq
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax                                                         # noqa: E402

from jax import set_mesh                                           # noqa: E402
from repro.launch.mesh import make_mesh                            # noqa: E402
from repro.configs import get_config, reduced                      # noqa: E402
from repro.core.hardware import TPU_V5E                            # noqa: E402
from repro.models.model import Model, init_params                  # noqa: E402
from repro.serving import (ContinuousBatchingEngine, EngineConfig,  # noqa: E402
                           ReplicatedCluster, sharegpt_like)
from repro.serving.cluster import autoscale                        # noqa: E402
from repro.sharding import rules_for                               # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=2)
    ap.add_argument("--mean-in", type=int, default=12)
    ap.add_argument("--mean-out", type=int, default=8)
    ap.add_argument("--policy", default="round-robin",
                    choices=("round-robin", "jsq", "least-kv"))
    ap.add_argument("--mode", default="thread", choices=("thread", "sync"))
    ap.add_argument("--arrival-rate", type=float, default=4.0)
    ap.add_argument("--pattern", default="burst",
                    choices=("poisson", "burst", "ramp"))
    ap.add_argument("--autoscale", action="store_true")
    args = ap.parse_args()

    cfg = reduced(get_config("opt-1.3b"))
    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev, 1), ("data", "model"))
    params = init_params(cfg, jax.random.PRNGKey(0))

    def ecfg(max_batch):
        return EngineConfig(max_batch=max_batch, block_size=16,
                            kv_pool_tokens=4096, max_model_len=128,
                            prefill_bucket=32)

    def workload(seed, rate=None):
        # offline workloads (no rate — e.g. the autoscale curve sweep)
        # can't carry a non-poisson pattern
        pattern = args.pattern if rate else "poisson"
        return sharegpt_like(args.requests, cfg.vocab_size, seed=seed,
                             mean_in=args.mean_in, mean_out=args.mean_out,
                             max_len=64, sigma=0.3, arrival_rate=rate,
                             arrival_pattern=pattern, burst_size=4)

    n_rep, max_batch = args.replicas, args.max_batch
    if args.autoscale:
        model = Model(cfg, rules_for(mesh))
        with set_mesh(mesh):
            decision = autoscale(
                lambda b: ContinuousBatchingEngine(model, params, ecfg(b)),
                lambda: workload(1), batches=(1, 2), hw=TPU_V5E,
                cfg=cfg, ctx=args.mean_in + args.mean_out,
                eps=0.05, mesh_slices=n_dev)
        print(decision.summary())
        n_rep, max_batch = decision.n_replicas, decision.per_replica_batch

    if n_dev >= n_rep > 1 and n_dev % n_rep == 0:
        print(f"[cluster] {n_rep} replicas on disjoint mesh slices")
        cluster = ReplicatedCluster.sliced(cfg, params, ecfg(max_batch),
                                           mesh, n_rep, policy=args.policy,
                                           mode=args.mode)
    else:
        print(f"[cluster] {n_rep} co-located replicas (shared mesh)")
        model = Model(cfg, rules_for(mesh))
        cluster = ReplicatedCluster.colocated(model, params, ecfg(max_batch),
                                              n_rep, policy=args.policy,
                                              mode=args.mode)
    metrics = cluster.run(workload(0, rate=args.arrival_rate))
    print(metrics.summary())
    assert metrics.completed == args.requests, "cluster dropped requests"


if __name__ == "__main__":
    main()
