"""Zero-copy paged decode: the engine's block-table data path must be
token-for-token identical to the legacy gather fallback, survive pool
exhaustion by preemption instead of crashing, and keep the paged pool's
slot bookkeeping sound."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kvcache.paged import PagedKVCache
from repro.models.model import Model, init_params
from repro.serving import (ContinuousBatchingEngine, EngineConfig,
                           sharegpt_like)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("opt-1.3b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _run(cfg, params, rules, mode, reqs, **ecfg_kw):
    model = Model(cfg, rules)
    ecfg = EngineConfig(decode_mode=mode, **ecfg_kw)
    engine = ContinuousBatchingEngine(model, params, ecfg)
    engine.run(reqs)
    return engine


def test_paged_matches_gather_mixed_lengths(setup, rules):
    """The tentpole acceptance check: zero-copy and gather decode produce
    identical tokens on a mixed-length continuous-batching workload."""
    cfg, params = setup
    kw = dict(max_batch=4, block_size=8, kv_pool_tokens=4096,
              max_model_len=256, prefill_bucket=16)
    outs = {}
    for mode in ("paged", "gather"):
        reqs = sharegpt_like(6, cfg.vocab_size, seed=7, mean_in=14,
                             mean_out=10, max_len=64, sigma=0.6)
        eng = _run(cfg, params, rules, mode, reqs, **kw)
        assert eng.decode_mode == mode
        assert all(r.t_done is not None for r in reqs)
        outs[mode] = [r.output_tokens for r in reqs]
    assert outs["paged"] == outs["gather"]


def test_paged_matches_gather_moe_nonpow2_batch(rules):
    """MoE routing ranks tokens by batch position, so the padding rows the
    paged path appends can never evict a real token's expert slot; with the
    generous serve capacity factor the two modes stay token-identical even
    at a non-power-of-two batch (where expert capacity C differs)."""
    cfg = reduced(get_config("olmoe-1b-7b"))
    params = init_params(cfg, jax.random.PRNGKey(2))
    kw = dict(max_batch=3, block_size=8, kv_pool_tokens=4096,
              max_model_len=128, prefill_bucket=16)
    outs = {}
    for mode in ("paged", "gather"):
        reqs = sharegpt_like(4, cfg.vocab_size, seed=9, mean_in=10,
                             mean_out=6, max_len=40, sigma=0.4)
        _run(cfg, params, rules, mode, reqs, **kw)
        assert all(r.t_done is not None for r in reqs)
        outs[mode] = [r.output_tokens for r in reqs]
    assert outs["paged"] == outs["gather"]


def test_paged_decode_no_dense_gather_on_steady_state(setup, rules):
    """pool.gather / scatter_new_token stay off the paged decode path."""
    cfg, params = setup
    model = Model(cfg, rules)
    engine = ContinuousBatchingEngine(
        model, params, EngineConfig(max_batch=4, block_size=8,
                                    kv_pool_tokens=4096, max_model_len=128,
                                    prefill_bucket=16))
    calls = []
    orig_gather = engine.pool.gather
    orig_scatter = engine.pool.scatter_new_token
    engine.pool.gather = lambda *a, **k: (calls.append("gather"),
                                          orig_gather(*a, **k))[1]
    engine.pool.scatter_new_token = (
        lambda *a, **k: (calls.append("scatter"),
                         orig_scatter(*a, **k))[1])
    reqs = sharegpt_like(4, cfg.vocab_size, seed=5, mean_in=10, mean_out=6,
                         max_len=48, sigma=0.3)
    engine.run(reqs)
    assert calls == []
    assert all(r.t_done is not None for r in reqs)


def test_pool_exhaustion_preempts_instead_of_crashing(setup, rules):
    """Mid-decode block exhaustion must requeue the youngest running
    request (recompute-style), not raise 'KV pool exhausted'."""
    cfg, params = setup
    # pool small enough that admitted requests outgrow it while decoding:
    # admission needs prompt+1 (~3 blocks each), completion needs ~7.
    reqs = sharegpt_like(6, cfg.vocab_size, seed=11, mean_in=20,
                         mean_out=36, max_len=60, sigma=0.1)
    model = Model(cfg, rules)
    engine = ContinuousBatchingEngine(
        model, params, EngineConfig(max_batch=6, block_size=8,
                                    kv_pool_tokens=256, max_model_len=96,
                                    prefill_bucket=16))
    engine.run(reqs)
    assert all(r.t_done is not None for r in reqs)
    assert engine.preemptions > 0, "workload was meant to force preemption"
    # deterministic greedy decode: preempted-and-recomputed requests must
    # emit the same tokens as an undisturbed run with a roomy pool
    reqs2 = sharegpt_like(6, cfg.vocab_size, seed=11, mean_in=20,
                          mean_out=36, max_len=60, sigma=0.1)
    engine2 = ContinuousBatchingEngine(
        model, params, EngineConfig(max_batch=6, block_size=8,
                                    kv_pool_tokens=8192, max_model_len=96,
                                    prefill_bucket=16))
    engine2.run(reqs2)
    assert engine2.preemptions == 0
    for a, b in zip(reqs, reqs2):
        assert a.output_tokens == b.output_tokens, a.req_id


def test_release_without_gather_frees_slot(setup):
    """Regression for the _slot lazy-init hack: release() before any
    gather()/view() must actually free the dense-state slot."""
    cfg, _ = setup
    pool = PagedKVCache(cfg, num_blocks=8, block_size=8, max_batch=2)
    pool.manager.allocate(0, 8)
    pool._slot(0)
    assert len(pool._free_slots) == pool.max_batch - 1
    pool.release(0)
    assert len(pool._free_slots) == pool.max_batch
    assert pool.manager.tables == {}


def test_view_caches_device_tables(setup):
    """Steady-state decode (no allocator change) must not re-upload the
    block table; any allocation must invalidate the cache."""
    cfg, _ = setup
    pool = PagedKVCache(cfg, num_blocks=16, block_size=8, max_batch=2)
    pool.manager.allocate(0, 12)
    v1 = pool.view([0], [12], nb_pad=4, batch_pad=1)
    v2 = pool.view([0], [13], nb_pad=4, batch_pad=1)
    assert v1.tables is v2.tables
    pool.manager.append_token(0, 17)          # crosses a block boundary
    v3 = pool.view([0], [16], nb_pad=4, batch_pad=1)
    assert v3.tables is not v1.tables
    # padding row addresses the trash block and slot, length 0
    v4 = pool.view([0], [16], nb_pad=4, batch_pad=2)
    assert int(v4.lengths[1]) == 0
    assert int(v4.slots[1]) == pool.trash_slot
    assert int(v4.tables[1, 0]) == pool.trash_block


def test_paged_view_is_pytree(setup):
    """PagedCacheView must flow through jit/tree ops unchanged."""
    cfg, _ = setup
    pool = PagedKVCache(cfg, num_blocks=8, block_size=8, max_batch=2)
    pool.manager.allocate(0, 8)
    view = pool.view([0], [8], nb_pad=2, batch_pad=1)
    leaves, treedef = jax.tree.flatten(view)
    view2 = jax.tree.unflatten(treedef, leaves)
    assert view2.block_size == view.block_size
    assert jnp.array_equal(view2.tables, view.tables)
    assert len(jax.tree.leaves(view2.pool)) == len(jax.tree.leaves(pool.pool))


@pytest.mark.parametrize("overlap", [False, True])
def test_kernel_block_counters_hand_counted(setup, rules, overlap):
    """The engine counts the pool blocks the paged kernel copies per
    layer and the blocks of its padded table, per plain decode step.
    Prompts of 5, 8 and 17 tokens in blocks of 8, two decode steps:
    lengths 6, 9, 18 then 7, 10, 19 copy 1 + 2 + 3 blocks each step, of
    a 4 x 4 table (batch and table width bucketed to powers of two)."""
    from repro.serving.workload import Request
    cfg, params = setup
    engine = ContinuousBatchingEngine(
        Model(cfg, rules), params,
        EngineConfig(max_batch=4, block_size=8, kv_pool_tokens=1024,
                     max_model_len=64, prefill_bucket=16, overlap=overlap))
    reqs = [Request(i, np.arange(1, n + 1, dtype=np.int32),
                    max_new_tokens=3) for i, n in enumerate((5, 8, 17))]
    engine.run(reqs)
    assert all(len(r.output_tokens) == 3 for r in reqs)
    assert engine.kernel_live_blocks == 2 * (1 + 2 + 3)
    assert engine.kernel_table_blocks == 2 * 4 * 4
    engine.reset_stats()
    assert engine.kernel_live_blocks == engine.kernel_table_blocks == 0
