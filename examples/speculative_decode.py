"""Speculative decoding on the paged KV pool: serve a repetitive
workload twice — plain decode vs prompt-lookup drafting + multi-token
verify — and show that the outputs are bit-identical while the
speculative run commits several tokens per verify step. Also prints the
BCA speculation advisor's break-even recommendation for the batch.

    PYTHONPATH=src python examples/speculative_decode.py
"""
import sys

sys.path.insert(0, "src")

import jax                                                         # noqa: E402

from jax import set_mesh                                           # noqa: E402
from repro.configs import get_config, reduced                      # noqa: E402
from repro.core import H100_PAPER, speculation_advisor             # noqa: E402
from repro.launch.mesh import make_test_mesh                       # noqa: E402
from repro.models.model import Model, init_params                  # noqa: E402
from repro.serving import (ContinuousBatchingEngine, EngineConfig,  # noqa: E402
                           repetitive_workload)
from repro.sharding import rules_for                               # noqa: E402


def main():
    cfg = reduced(get_config("opt-1.3b"))
    full = get_config("opt-1.3b")
    print(speculation_advisor(full, H100_PAPER, batch=4).summary())

    mesh = make_test_mesh()
    rules = rules_for(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    model = Model(cfg, rules)
    outs = {}
    with set_mesh(mesh):
        for spec in (False, True):
            ecfg = EngineConfig(max_batch=4, block_size=8,
                                kv_pool_tokens=1 << 13, max_model_len=256,
                                prefill_bucket=32, speculate=spec,
                                spec_k=4)
            engine = ContinuousBatchingEngine(model, params, ecfg)
            # pure template text (repeat_rate=1.0, one phrase pool) — the
            # prompt-lookup drafter's target shape; wall numbers include
            # first-call compiles, so for the measured warm-engine uplift
            # see benchmarks/speculative.py
            reqs = repetitive_workload(6, cfg.vocab_size, seed=3,
                                       prompt_len=64, max_new_tokens=32,
                                       repeat_rate=1.0, phrase_len=8,
                                       pool_size=1)
            metrics = engine.run(reqs)
            outs[spec] = [list(r.output_tokens) for r in reqs]
            tag = "speculate" if spec else "plain    "
            line = f"{tag}: {metrics.row()}"
            if spec:
                line += f"  {metrics.spec_row()}"
            print(line)
    assert outs[False] == outs[True], "speculation changed the outputs!"
    print("outputs bit-identical with and without speculation")


if __name__ == "__main__":
    main()
