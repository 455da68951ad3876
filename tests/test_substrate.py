"""Substrate tests: optimizer, data pipeline, checkpointing, paged cache,
sharding rules, SSM numerics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.configs import get_config, reduced
from repro.models import model as M
from repro.models import ssm as ssm_mod
from repro.sharding import ShardingRules, rules_for
from repro.training import (AdamWConfig, adamw_init, adamw_update,
                            load_checkpoint, make_train_step,
                            save_checkpoint, synthetic_batches)


def test_adamw_reduces_loss(rules):
    cfg = reduced(get_config("llama-2-7b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw_init(params)
    step = jax.jit(make_train_step(
        cfg, rules, AdamWConfig(lr=2e-3, warmup_steps=2, total_steps=40)))
    data = synthetic_batches(cfg, batch=4, seq=32, seed=1)
    losses = []
    for _ in range(10):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses


def test_adamw_grad_clip():
    p = {"w": jnp.ones((4, 4))}
    g = {"w": jnp.full((4, 4), 1e6)}
    opt = AdamWConfig(grad_clip=1.0, lr=1e-2, warmup_steps=1, total_steps=2)
    _, _, gnorm = adamw_update(opt, p, g, adamw_init(p))
    assert float(gnorm) > 1e6  # reported pre-clip


def test_checkpoint_roundtrip(tmp_path, rules):
    cfg = reduced(get_config("olmoe-1b-7b"))
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    opt = adamw_init(params)
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, params, opt, step=42)
    p2, o2, s = load_checkpoint(path, params, opt)
    assert s == 42
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharding_divisibility_fallback():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = rules_for(mesh)
    # 1-device mesh: everything falls back to size-1 axes w/o error
    spec = rules.spec(("batch", "kv_seq", "kv_heads", None), (8, 64, 2, 64))
    assert spec is not None


def test_sharding_no_duplicate_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = rules_for(mesh)
    spec = rules.spec(("d_model", "d_ff"), (64, 64))
    used = [s for s in spec if s is not None]
    flat = []
    for s in used:
        flat.extend(s if isinstance(s, tuple) else (s,))
    assert len(flat) == len(set(flat))


def test_ssm_chunked_matches_stepwise(rules):
    """SSD chunked scan == naive per-token recurrence."""
    cfg = reduced(get_config("mamba2-1.3b"))
    s = cfg.ssm
    key = jax.random.PRNGKey(0)
    B, S, H, P, G, N = 2, 37, 8, s.head_dim, s.ngroups, s.d_state
    ks = jax.random.split(key, 5)
    xs = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    B_ = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    C_ = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    y_chunk, h_chunk = ssm_mod.ssd_chunked(xs, dt, A, B_, C_, cfg, rules)
    # naive recurrence
    rep = H // G
    Bh = jnp.repeat(B_, rep, axis=2)
    Ch = jnp.repeat(C_, rep, axis=2)
    h = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        decay = jnp.exp(dt[:, t] * A)[:, :, None, None]
        upd = dt[:, t][:, :, None, None] * xs[:, t][..., None] * \
            Bh[:, t][:, :, None, :]
        h = h * decay + upd
        ys.append(jnp.einsum("bhpn,bhn->bhp", h, Ch[:, t]))
    y_naive = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_naive),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h_chunk), np.asarray(h),
                               atol=2e-4, rtol=1e-3)


def test_paged_cache_gather_scatter_roundtrip(rules):
    from repro.kvcache.paged import PagedKVCache
    cfg = reduced(get_config("internlm2-1.8b"))
    pool = PagedKVCache(cfg, num_blocks=32, block_size=8, max_batch=4)
    pool.manager.allocate(0, 20)
    pool.manager.allocate(1, 12)
    # write a recognizable prefill for request 0
    cache = M.init_cache(cfg, 1, 24)
    cache = jax.tree.map(lambda x: jnp.full_like(x, 3.0), cache)
    pool.write_prefill(0, cache)
    view = pool.gather([0, 1], pad_blocks=3)
    for leaf in jax.tree.leaves(view):
        if leaf.ndim == 5:            # [L, B, S, K, hd] paged kv leaf
            arr = np.asarray(leaf)
            assert np.allclose(arr[:, 0, :20], 3.0)   # request 0 rows
    # scatter one new token for request 0 at position 20
    pool.manager.append_token(0, 21)
    view2 = pool.gather([0], pad_blocks=3)
    marked = jax.tree.map(
        lambda x: x.at[..., 0, 20, :, :].set(7.0) if x.ndim == 5 else x,
        view2)
    pool.scatter_new_token([0], [20], marked)
    view3 = pool.gather([0], pad_blocks=3)
    for leaf in jax.tree.leaves(view3):
        if leaf.ndim == 5:
            assert np.allclose(np.asarray(leaf)[:, 0, 20], 7.0)


def test_workload_statistics():
    from repro.serving.workload import sharegpt_like
    reqs = sharegpt_like(500, 1000, seed=0)
    lin = np.mean([r.prompt_len for r in reqs])
    lout = np.mean([r.max_new_tokens for r in reqs])
    # lognormal around the ShareGPT means
    assert 100 < lin < 320
    assert 200 < lout < 650
