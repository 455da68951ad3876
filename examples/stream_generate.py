"""Streaming quickstart: the online serving facade over the real engine.

Submits three requests with different SamplingParams (greedy, sampled,
and one that will be cancelled), streams the first one token-delta by
token-delta, aborts the third mid-flight, then drains the rest — the
submit/stream/abort/drain surface the README's "Serving API" section
documents.

    PYTHONPATH=src python examples/stream_generate.py
"""
import sys

sys.path.insert(0, "src")

import jax                                                         # noqa: E402
import numpy as np                                                 # noqa: E402

from jax import set_mesh                                           # noqa: E402
from repro.configs import get_config, reduced                      # noqa: E402
from repro.launch.mesh import make_test_mesh                       # noqa: E402
from repro.models.model import Model, init_params                  # noqa: E402
from repro.serving import (ContinuousBatchingEngine, EngineConfig,  # noqa: E402
                           SamplingParams, ServingAPI)
from repro.sharding import rules_for                               # noqa: E402


def main():
    cfg = reduced(get_config("opt-1.3b"))
    mesh = make_test_mesh()
    rules = rules_for(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    model = Model(cfg, rules)
    rng = np.random.default_rng(0)
    prompt = lambda n: rng.integers(0, cfg.vocab_size, n)   # noqa: E731

    with set_mesh(mesh):
        ecfg = EngineConfig(max_batch=4, block_size=16,
                            kv_pool_tokens=1 << 13, max_model_len=128,
                            prefill_bucket=32)
        api = ServingAPI(ContinuousBatchingEngine(model, params, ecfg))

        greedy = api.submit(prompt(24), SamplingParams(max_new_tokens=12))
        sampled = api.submit(
            prompt(24), SamplingParams(temperature=0.8, top_k=40,
                                       top_p=0.95, seed=7,
                                       max_new_tokens=12))
        doomed = api.submit(prompt(24), SamplingParams(max_new_tokens=500))

        print("-- streaming the greedy request (others decode alongside):")
        for ev in api.stream(greedy):
            print(f"   req {ev.req_id}: +{list(ev.new_token_ids)}"
                  + (f"  -> finished ({ev.finish_reason}, "
                     f"{len(ev.token_ids)} tokens)" if ev.finished else ""))

        print(f"-- aborting req {doomed.req_id} mid-flight "
              f"({doomed.request.generated} tokens so far)")
        api.abort(doomed)

        outs = api.drain()
        for rid in sorted(outs):
            o = outs[rid]
            print(f"   req {rid}: {len(o.token_ids)} tokens, "
                  f"finish_reason={o.finish_reason}")
        assert outs[doomed.req_id].finish_reason == "abort"
        assert outs[sampled.req_id].finish_reason == "length"
        m = api.metrics()
        print(f"-- session: {m.row()}")
        print(f"-- session: {m.finish_row()}")


if __name__ == "__main__":
    main()
