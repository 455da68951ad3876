"""Online serving facade: submit / stream / abort / drain semantics,
finish-reason accounting (stop tokens release blocks the same step), the
frozen-Request/RequestState split, and the engine.run() clock-restore
regression."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models.model import Model, init_params
from repro.serving import (ContinuousBatchingEngine, EngineConfig,
                           ReplicatedCluster, Request, SamplingParams,
                           ServingAPI, sharegpt_like)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("opt-1.3b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(setup, rules, **kw):
    cfg, params = setup
    base = dict(max_batch=4, block_size=8, kv_pool_tokens=4096,
                max_model_len=256, prefill_bucket=16)
    base.update(kw)
    return ContinuousBatchingEngine(Model(cfg, rules), params,
                                    EngineConfig(**base))


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


# ------------------------------------------------------- request model ----
def test_request_input_fields_are_frozen(setup):
    cfg, _ = setup
    req = Request(req_id=0, prompt=_prompt(cfg, 8), max_new_tokens=4)
    for field, value in (("req_id", 1), ("prompt", None),
                         ("arrival_s", 2.0),
                         ("sampling", SamplingParams())):
        with pytest.raises(AttributeError):
            setattr(req, field, value)
    # engine-owned state stays writable through the legacy proxies
    req.t_first_token = 1.0
    req.generated = 3
    req.output_tokens = [1, 2, 3]
    assert req.state.generated == 3 and req.state.output_tokens == [1, 2, 3]
    assert req.max_new_tokens == 4 == req.sampling.max_new_tokens


def test_request_budget_conflict_rejected(setup):
    cfg, _ = setup
    with pytest.raises(TypeError):
        Request(req_id=0, prompt=_prompt(cfg, 8))        # no budget at all
    with pytest.raises(ValueError):
        Request(req_id=0, prompt=_prompt(cfg, 8), max_new_tokens=4,
                sampling=SamplingParams(max_new_tokens=5))
    # agreeing is fine
    req = Request(req_id=0, prompt=_prompt(cfg, 8), max_new_tokens=5,
                  sampling=SamplingParams(max_new_tokens=5))
    assert req.max_new_tokens == 5


# ------------------------------------------------------------ streaming ----
def test_stream_yields_deltas_and_final_reason(setup, rules):
    cfg, _ = setup
    api = ServingAPI(_engine(setup, rules))
    h = api.submit(_prompt(cfg, 10), SamplingParams(max_new_tokens=6))
    events = list(api.stream(h))
    assert events, "stream produced no events"
    assert all(not e.finished for e in events[:-1])
    assert events[-1].finished and events[-1].finish_reason == "length"
    # deltas concatenate to the cumulative ids, which match the request
    cat = [t for e in events for t in e.new_token_ids]
    assert tuple(cat) == events[-1].token_ids
    assert list(events[-1].token_ids) == h.request.output_tokens
    assert len(cat) == 6


def test_stream_equals_batch_run(setup, rules):
    """The facade is a wrapper, not a fork: same tokens as run()."""
    cfg, _ = setup
    sp = SamplingParams(temperature=0.7, top_p=0.9, seed=13)
    wl = lambda: sharegpt_like(4, cfg.vocab_size, seed=5,    # noqa: E731
                               mean_in=12, mean_out=6, max_len=48,
                               sigma=0.3, sampling=sp)
    reqs = wl()
    _engine(setup, rules).run(reqs)
    api = ServingAPI(_engine(setup, rules))
    handles = [api.submit(r) for r in wl()]
    outs = api.drain()
    assert ([list(outs[h.req_id].token_ids) for h in handles]
            == [list(map(int, r.output_tokens)) for r in reqs])
    m = api.metrics()
    assert m.n_completed == 4
    assert m.finish_reasons == {"length": 4}


def test_generate_convenience(setup, rules):
    cfg, _ = setup
    api = ServingAPI(_engine(setup, rules))
    out = api.generate(_prompt(cfg, 9), SamplingParams(max_new_tokens=3))
    assert out.finished and out.finish_reason == "length"
    assert len(out.token_ids) == 3


# ---------------------------------------------------------------- abort ----
def test_abort_mid_decode_reclaims_blocks(setup, rules):
    cfg, _ = setup
    eng = _engine(setup, rules)
    api = ServingAPI(eng)
    free0 = eng.pool.manager.free_blocks
    h = api.submit(_prompt(cfg, 24), SamplingParams(max_new_tokens=100))
    for _ in range(3):
        api._backend.pump(api._clock())
    assert h.request.generated > 1 and not h.done
    assert api.abort(h)
    assert eng.pool.manager.free_blocks == free0
    assert not eng.busy
    ev = list(api.stream(h))
    assert len(ev) >= 1 and ev[-1].finished
    assert ev[-1].finish_reason == "abort"
    assert api.metrics().finish_reasons == {"abort": 1}
    # double-abort and unknown ids are clean no-ops
    assert not api.abort(h)
    assert not api.abort(12345)


def test_abort_mid_prefill_reclaims_blocks(setup, rules):
    """Abort in the PREFILLING phase (chunked): the half-streamed prompt's
    blocks must all return to the pool."""
    cfg, _ = setup
    eng = _engine(setup, rules, prefill_chunk_tokens=16)
    assert eng.chunking
    api = ServingAPI(eng)
    free0 = eng.pool.manager.free_blocks
    h = api.submit(_prompt(cfg, 100), SamplingParams(max_new_tokens=4))
    api._backend.pump(api._clock())          # one 16-token chunk
    assert eng._prefilled.get(h.req_id, 0) > 0, "not mid-PREFILLING"
    assert api.abort(h)
    assert eng.pool.manager.free_blocks == free0
    assert not eng.busy and h.done and h.finish_reason == "abort"
    assert h.request.generated == 0


def test_abort_with_prefix_cache_restores_refcounts(setup, rules):
    """Aborting a request that spliced shared prefix blocks must drop
    exactly its references: cached blocks stay warm at refcount 1."""
    cfg, _ = setup
    eng = _engine(setup, rules, prefix_cache=True)
    api = ServingAPI(eng)
    base = _prompt(cfg, 32, seed=3)
    api.generate(base, SamplingParams(max_new_tokens=2))   # warm the cache
    cached = {n.block for n in eng.prefix._iter_nodes()}
    assert cached, "warmup should have inserted prefix blocks"
    assert all(eng.pool.manager.ref_count(b) == 1 for b in cached)
    # same prefix, longer tail -> splices the cached blocks
    h = api.submit(np.concatenate([base, _prompt(cfg, 16, seed=4)]),
                   SamplingParams(max_new_tokens=50))
    for _ in range(2):
        api._backend.pump(api._clock())
    assert eng.prefix.stats.hits >= 1
    assert api.abort(h)
    assert all(eng.pool.manager.ref_count(b) == 1 for b in cached), \
        "abort must return shared blocks to their cache-only refcount"


def test_abort_future_arrival_never_negative_e2e(setup, rules):
    """Aborting a queued request whose (simulated) arrival hasn't come
    yet must clamp t_done to arrival_s — no negative E2E in collect()."""
    cfg, _ = setup
    api = ServingAPI(_engine(setup, rules))
    h = api.submit(_prompt(cfg, 8), SamplingParams(max_new_tokens=4),
                   arrival_s=1e6)
    assert api.abort(h)
    assert h.request.t_done >= h.request.arrival_s
    m = api.metrics()
    assert m.e2e.p50 >= 0.0


def test_simulated_future_arrivals_keep_timeline_monotonic(setup, rules):
    """Fast-forwarding to a simulated arrival must floor every later
    timestamp: t_done can never land behind the jump (the facade analogue
    of run()'s 'keep now monotonic' guard)."""
    cfg, _ = setup
    api = ServingAPI(_engine(setup, rules))
    near = api.submit(_prompt(cfg, 8), SamplingParams(max_new_tokens=2))
    far = api.submit(_prompt(cfg, 8, seed=1),
                     SamplingParams(max_new_tokens=3), arrival_s=50.0)
    outs = api.drain()
    assert outs[near.req_id].finished and outs[far.req_id].finished
    for h in (near, far):
        r = h.request
        assert r.arrival_s <= r.t_first_token <= r.t_done
    m = api.metrics()
    assert m.e2e.p50 >= 0.0
    # wall is anchored at first submit; the simulated 50 s jump dominates
    assert m.wall_s >= 45.0


def test_release_prunes_finished_handles(setup, rules):
    cfg, _ = setup
    api = ServingAPI(_engine(setup, rules))
    h = api.submit(_prompt(cfg, 8), SamplingParams(max_new_tokens=2))
    assert not api.release(h), "in-flight handles must not be releasable"
    api.drain()
    assert api.release(h)
    assert not api.release(h)
    assert api.metrics().n_completed == 0
    assert h.req_id not in api.drain()


def test_submit_prebuilt_request_rejects_overrides(setup, rules):
    """arrival_s/sampling are frozen on a prebuilt Request — a silently
    ignored override would defer/sample nothing with no indication."""
    cfg, _ = setup
    api = ServingAPI(_engine(setup, rules))
    req = Request(req_id=0, prompt=_prompt(cfg, 8), max_new_tokens=2)
    with pytest.raises(ValueError):
        api.submit(req, arrival_s=5.0)
    with pytest.raises(ValueError):
        api.submit(req, SamplingParams())


def test_abort_queued_request(setup, rules):
    """Abort before admission: nothing was allocated, nothing leaks."""
    cfg, _ = setup
    eng = _engine(setup, rules, max_batch=1)
    api = ServingAPI(eng)
    h1 = api.submit(_prompt(cfg, 12), SamplingParams(max_new_tokens=30))
    api._backend.pump(api._clock())          # h1 occupies the only seat
    h2 = api.submit(_prompt(cfg, 12, seed=9),
                    SamplingParams(max_new_tokens=5))
    assert len(eng.waiting) == 1
    assert api.abort(h2)
    assert not eng.waiting and h2.finish_reason == "abort"
    api.abort(h1)
    assert not eng.busy


# ------------------------------------------------------- stop tokens ----
def test_stop_token_finishes_same_step_and_releases_blocks(setup, rules):
    """A stop-token finish must release KV the same step and account the
    stop token exactly like a length finish (symmetric ITL/decode work);
    the breakdown only differs in finish_reasons."""
    cfg, _ = setup
    wl = lambda sp: sharegpt_like(1, cfg.vocab_size, seed=8,  # noqa: E731
                                  mean_in=12, mean_out=20, max_len=48,
                                  sigma=0.1, sampling=sp)
    reqs = wl(None)
    _engine(setup, rules).run(reqs)
    full = list(map(int, reqs[0].output_tokens))
    assert len(full) >= 4
    stop_tok = full[3]
    cut = full.index(stop_tok)               # first occurrence wins
    sp = SamplingParams(stop_token_ids=(stop_tok,))
    eng = _engine(setup, rules)
    reqs2 = wl(sp)
    m = eng.run(reqs2)
    got = list(map(int, reqs2[0].output_tokens))
    assert got == full[:cut + 1], "stop token itself is emitted, then ends"
    assert reqs2[0].finish_reason == "stop"
    assert m.finish_reasons == {"stop": 1}
    assert m.output_tokens == cut + 1
    assert eng.pool.manager.free_blocks == eng.pool.manager.num_blocks
    # ignore_eos decodes straight through the stop token
    reqs3 = wl(dataclasses.replace(sp, ignore_eos=True))
    _engine(setup, rules).run(reqs3)
    assert list(map(int, reqs3[0].output_tokens)) == full
    assert reqs3[0].finish_reason == "length"


def test_stop_token_on_first_prefill_token(setup, rules):
    """First sampled token is a stop token: finish straight out of
    prefill, one token emitted, reason 'stop'."""
    cfg, _ = setup
    probe = sharegpt_like(1, cfg.vocab_size, seed=8, mean_in=12,
                          mean_out=20, max_len=48, sigma=0.1)
    _engine(setup, rules).run(probe)
    first = int(probe[0].output_tokens[0])
    sp = SamplingParams(stop_token_ids=(first,))
    reqs = sharegpt_like(1, cfg.vocab_size, seed=8, mean_in=12,
                         mean_out=20, max_len=48, sigma=0.1, sampling=sp)
    eng = _engine(setup, rules)
    eng.run(reqs)
    assert list(map(int, reqs[0].output_tokens)) == [first]
    assert reqs[0].finish_reason == "stop"
    assert not eng.busy


# ----------------------------------------------------- clock regression ----
def test_run_restores_clock_for_back_to_back_runs(setup, rules):
    """engine.run() must not leave its epoch installed: a second run — or
    facade/step use after one — stamps on its own timeline."""
    cfg, _ = setup
    eng = _engine(setup, rules)
    assert eng.clock is None
    wl = lambda s: sharegpt_like(3, cfg.vocab_size, seed=s,  # noqa: E731
                                 mean_in=10, mean_out=5, max_len=48,
                                 sigma=0.3)
    m1 = eng.run(wl(2))
    assert eng.clock is None, "run() left its wall clock installed"
    m2 = eng.run(wl(3))
    assert eng.clock is None
    # second run's timestamps live on its own timeline, not offset by the
    # first run's epoch: E2E must be bounded by the second run's wall
    assert m2.n_completed == 3
    assert m2.e2e.p99 <= m2.wall_s + 1e-6
    assert m1.e2e.p99 <= m1.wall_s + 1e-6
    # interleaved facade use after a run stamps small facade-clock times
    api = ServingAPI(eng)
    out = api.generate(_prompt(cfg, 8), SamplingParams(max_new_tokens=2))
    req = api._submitted[0]
    assert out.finished
    assert req.t_done is not None
    assert req.t_done <= api._clock() + 1e-6


def test_cluster_run_restores_clocks(setup, rules):
    cfg, params = setup
    model = Model(cfg, rules)
    ecfg = EngineConfig(max_batch=4, block_size=8, kv_pool_tokens=4096,
                        max_model_len=128, prefill_bucket=16)
    cluster = ReplicatedCluster.colocated(model, params, ecfg, 2,
                                          mode="sync")
    reqs = sharegpt_like(4, cfg.vocab_size, seed=2, mean_in=10,
                         mean_out=5, max_len=48, sigma=0.3)
    m = cluster.run(reqs)
    assert m.completed == 4
    assert all(rep.engine.clock is None for rep in cluster.replicas)


# -------------------------------------------------------- cluster facade ----
def test_facade_over_cluster_routes_and_streams(setup, rules):
    """Router-aware submit + cross-replica streaming + abort through the
    same facade surface."""
    cfg, params = setup
    model = Model(cfg, rules)
    ecfg = EngineConfig(max_batch=2, block_size=8, kv_pool_tokens=4096,
                        max_model_len=128, prefill_bucket=16)
    cluster = ReplicatedCluster.colocated(model, params, ecfg, 2,
                                          policy="round-robin", mode="sync")
    api = ServingAPI(cluster)
    h = [api.submit(_prompt(cfg, 10, seed=i),
                    SamplingParams(max_new_tokens=4 if i < 2 else 100))
         for i in range(3)]
    assert cluster.router.assigned == [2, 1]
    events = list(api.stream(h[1]))          # lives on replica 1
    assert events[-1].finished and len(events[-1].token_ids) == 4
    assert api.abort(h[2])                   # replica 0, mid-flight
    outs = api.drain()
    assert outs[h[0].req_id].finish_reason == "length"
    assert outs[h[2].req_id].finish_reason == "abort"
    m = api.metrics()
    assert m.completed == 3
    assert m.finish_reasons == {"length": 2, "abort": 1}
    for rep in cluster.replicas:
        mgr = rep.engine.pool.manager
        assert mgr.free_blocks == mgr.num_blocks
    # release prunes the replica's routed list too (no phantom rows)
    assert api.release(h[0])
    assert sum(len(rep.requests) for rep in cluster.replicas) == 2
    assert api.metrics().completed == 2


def test_cluster_facade_defers_routing_to_arrival(setup, rules):
    """Future-arrival submits must not be routed against a t=0 snapshot:
    the policy runs when the arrival comes, seeing live load — run()
    parity for queue-aware routers."""
    cfg, params = setup
    model = Model(cfg, rules)
    ecfg = EngineConfig(max_batch=2, block_size=8, kv_pool_tokens=4096,
                        max_model_len=128, prefill_bucket=16)
    cluster = ReplicatedCluster.colocated(model, params, ecfg, 2,
                                          policy="jsq", mode="sync")
    api = ServingAPI(cluster)
    reqs = [Request(req_id=i, prompt=_prompt(cfg, 10, seed=i),
                    arrival_s=5.0 + i, sampling=SamplingParams(
                        max_new_tokens=3)) for i in range(3)]
    handles = [api.submit(r) for r in reqs]
    assert cluster.router.assigned == [0, 0], \
        "future arrivals must not be routed at submit time"
    assert api._backend.pending == reqs
    # abort one while still pending: never routed, nothing allocated
    assert api.abort(handles[2])
    assert handles[2].done and handles[2].finish_reason == "abort"
    assert handles[2].request.t_done >= reqs[2].arrival_s
    outs = api.drain()
    assert sum(cluster.router.assigned) == 2
    assert not api._backend.pending
    for h in handles[:2]:
        assert outs[h.req_id].finish_reason == "length"
        assert h.request.arrival_s <= h.request.t_first_token \
            <= h.request.t_done
    # the never-routed abort still shows up in session metrics, exactly
    # like an engine-backend abort of a queued request would
    m = api.metrics()
    assert m.completed == 3
    assert m.finish_reasons == {"length": 2, "abort": 1}
    # ...and releasing it prunes it from the breakdown again
    assert api.release(handles[2])
    assert api.metrics().completed == 2


def test_metrics_wall_anchored_at_first_submit(setup, rules):
    """Idle time before the first submit must not deflate throughput."""
    cfg, _ = setup
    api = ServingAPI(_engine(setup, rules))
    api._t0 -= 100.0                 # simulate a 100 s idle session head
    out = api.generate(_prompt(cfg, 8), SamplingParams(max_new_tokens=3))
    assert out.finished
    m = api.metrics()
    assert m.wall_s < 100.0, "pre-submit idle counted into wall_s"
    assert m.output_tokens == 3


# ------------------------------------------------------------ one round ---
def test_pump_runs_one_round(setup, rules):
    cfg, _ = setup
    eng = _engine(setup, rules)
    api = ServingAPI(eng)
    assert api.pump() is False                 # nothing submitted: idle
    handles = [api.submit(_prompt(cfg, 12, seed=s),
                          SamplingParams(max_new_tokens=3))
               for s in range(2)]
    rounds, busy = 0, True
    while busy:
        busy = api.pump()
        rounds += 1
        assert eng.step_count == rounds        # one round, one step
    # round 1: admission, prefill (token 1) and a decode step (token 2);
    # round 2: the last token
    assert rounds == 2
    assert all(h.done and h.finish_reason == "length" for h in handles)
    # the same tokens a stream-driven session produces
    again = ServingAPI(_engine(setup, rules))
    outs = [again.generate(_prompt(cfg, 12, seed=s),
                           SamplingParams(max_new_tokens=3))
            for s in range(2)]
    assert [list(h.final_output().token_ids) for h in handles] == \
        [list(o.token_ids) for o in outs]
