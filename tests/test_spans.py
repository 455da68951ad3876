"""The program's spans under ``jax.profiler`` on the CPU backend: they sit
on the thread that drives the facade, nest as the layers do, open one
``engine.decode`` per decode step, and the step programs carry their
names."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, reduced
from repro.models.model import Model, init_params
from repro.models.sampler import positions_array, stack_sampling
from repro.serving import (ContinuousBatchingEngine, EngineConfig,
                           SamplingParams, ServingAPI, StepFunctions)

PROGRAM = ("api.", "engine.", "sched.", "pool.")


@pytest.fixture(scope="module")
def setup(rules):
    cfg = reduced(get_config("opt-1.3b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, StepFunctions.build(Model(cfg, rules), 8)


def _api(setup, overlap: bool) -> ServingAPI:
    _, params, steps = setup
    ecfg = EngineConfig(max_batch=4, block_size=8, kv_pool_tokens=4096,
                        max_model_len=128, prefill_bucket=16,
                        overlap=overlap)
    return ServingAPI(ContinuousBatchingEngine(steps.model, params, ecfg,
                                               steps=steps))


def _submit(api, cfg, n: int = 3):
    rng = np.random.default_rng(0)
    return [api.submit(rng.integers(0, cfg.vocab_size, 10 + 3 * i),
                       SamplingParams(max_new_tokens=5))
            for i in range(n)]


def _profiled(tmp_path, fn):
    """Run ``fn`` under the profiler (its Python tracer off, as the
    benchmark runs it) inside a ``test.run`` span; returns the spans of
    that thread as sorted (start_ns, end_ns, name)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test.run"):
            fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    lines = [[(e.start_ns, e.end_ns, e.name) for e in line.events]
             for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines]
    mine = [evs for evs in lines if any(n == "test.run" for *_, n in evs)]
    assert len(mine) == 1
    for evs in lines:
        if evs is not mine[0]:       # no program span on another thread
            assert not any(n.startswith(PROGRAM) for *_, n in evs)
    return sorted(mine[0])


def _calls(spans, name: str) -> int:
    """Executions of a jitted function: its ``PjitFunction(<name>)``
    events, not counting one nested in another of the same name (the
    runtime opens two per call)."""
    evs = [(a, b) for a, b, n in spans if n == f"PjitFunction({name})"]
    return sum(not any(a0 <= a and b <= b0 and (a0, b0) != (a, b)
                       for a0, b0 in evs) for a, b in evs)


def _inside(spans, inner: str, outer: str) -> bool:
    """Every ``inner`` span lies within some ``outer`` span."""
    outs = [(a, b) for a, b, n in spans if n == outer]
    return all(any(a <= s and e <= b for a, b in outs)
               for s, e, n in spans if n == inner)


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
def test_spans_nest_and_count_decode_steps(setup, tmp_path, overlap):
    cfg = setup[0]
    warm = _api(setup, overlap)            # compile outside the profile
    _submit(warm, cfg)
    warm.drain()
    api = _api(setup, overlap)
    handles = _submit(api, cfg)
    eng = api.backend
    paged = eng._paged_jit
    dispatches = []
    eng._paged_jit = lambda *a: (dispatches.append(1), paged(*a))[1]

    def serve():
        while api.pump():
            pass

    spans = _profiled(tmp_path, serve)
    assert all(h.done and h.finish_reason == "length" for h in handles)
    names = [n for *_, n in spans]
    count = {n: names.count(n) for n in set(names)}
    # one api.round and one engine.step per round
    assert count["api.round"] == count["engine.step"] == eng.step_count
    assert count["sched.plan"] == count["sched.admit"] == eng.step_count
    # the decode steps are the engine.decode spans
    assert count["engine.decode"] == len(dispatches) > 0
    assert _calls(spans, "paged_decode_step") == len(dispatches)
    for inner, outer in [("engine.step", "api.round"),
                         ("sched.plan", "engine.step"),
                         ("sched.admit", "sched.plan"),
                         ("engine.prefill", "sched.admit"),
                         ("pool.write", "sched.admit"),
                         ("engine.decode", "engine.step"),
                         ("engine.fetch", "engine.step"),
                         ("engine.commit", "engine.step"),
                         ("pool.view", "engine.decode"),
                         ("pool.commit", "engine.decode"),
                         ("PjitFunction(paged_decode_step)",
                          "engine.decode")]:
        assert count.get(inner) and _inside(spans, inner, outer), \
            (inner, outer)
    # every decode step's tokens are read back and committed (sync: in
    # the same round; overlap: rounds later, the last ones by the drain)
    assert count["engine.commit"] == len(dispatches)
    assert count["engine.fetch"] == len(dispatches) + len(handles)
    assert _calls(spans, "prefill") == len(handles)
    assert _calls(spans, "sample") == len(handles)
    assert not any(n.startswith("bench.") for n in names)


def test_step_programs_are_named(setup):
    steps = setup[2]
    for field, name in [("prefill", "prefill"), ("decode", "decode_gather"),
                        ("paged", "paged_decode_step"),
                        ("prefix_prefill", "prefix_prefill"),
                        ("chunk_prefill", "chunk_prefill"),
                        ("sample", "sample"), ("spec_verify", "spec_verify")]:
        assert getattr(steps, field).__name__ == name
    # the program's module name, which the device trace shows
    logits = jnp.zeros((2, setup[0].vocab_size), jnp.float32)
    samp = stack_sampling([SamplingParams()] * 2)
    text = steps.sample.lower(logits, *samp,
                              positions_array([1, 2])).as_text()
    assert text.startswith("module @jit_sample ")
