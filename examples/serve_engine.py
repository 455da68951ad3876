"""Serve a small model with batched requests through the real
continuous-batching engine (paged KV cache, FCFS admission), sweeping the
BCA-tunable max_batch knob to expose the throughput/latency trade-off.

    PYTHONPATH=src python examples/serve_engine.py
"""
import sys

sys.path.insert(0, "src")

import jax                                                         # noqa: E402

from jax import set_mesh
from repro.configs import get_config, reduced                      # noqa: E402
from repro.launch.mesh import make_test_mesh                       # noqa: E402
from repro.models.model import Model, init_params                  # noqa: E402
from repro.serving import (ContinuousBatchingEngine, EngineConfig,  # noqa: E402
                           sharegpt_like)
from repro.sharding import rules_for                               # noqa: E402


def main():
    cfg = reduced(get_config("opt-1.3b"))
    mesh = make_test_mesh()
    rules = rules_for(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    model = Model(cfg, rules)
    with set_mesh(mesh):
        for mb in (1, 4, 8):
            ecfg = EngineConfig(max_batch=mb, block_size=16,
                                kv_pool_tokens=1 << 14, max_model_len=128,
                                prefill_bucket=32)
            engine = ContinuousBatchingEngine(model, params, ecfg)
            reqs = sharegpt_like(8, cfg.vocab_size, seed=0, mean_in=20,
                                 mean_out=20, max_len=80, sigma=0.3)
            metrics = engine.run(reqs)
            print(f"max_batch={mb}: {metrics.row()}")


if __name__ == "__main__":
    main()
