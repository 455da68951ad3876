"""BlockManager edge cases: exhaustion mid-allocate, release-then-realloc
reuse, ref-count sharing / copy-on-write, and watermark enforcement
(including eviction pressure from the prefix index)."""
import numpy as np
import pytest

from repro.kvcache.paged import BlockManager
from repro.kvcache.prefix import PrefixIndex


def _mk(num_blocks=16, block_size=8, watermark=0.01):
    return BlockManager(num_blocks, block_size, watermark=watermark)


# ------------------------------------------------------------ exhaustion --
def test_exhaustion_mid_allocate_leaves_state_intact():
    bm = _mk(num_blocks=4, block_size=8)
    bm.allocate(0, 24)                       # 3 of 4 blocks
    free_before = list(bm.free)
    tables_before = {k: list(v) for k, v in bm.tables.items()}
    with pytest.raises(RuntimeError, match="exhausted"):
        bm.allocate(1, 17, allow_reserve=True)   # needs 3, only 1 free
    assert bm.free == free_before            # nothing partially popped
    assert {k: list(v) for k, v in bm.tables.items()} == tables_before
    assert 1 not in bm.tables


def test_release_then_realloc_reuses_blocks():
    bm = _mk(num_blocks=8, block_size=8)
    first = bm.allocate(0, 32)
    bm.release(0)
    assert bm.free_blocks == 8
    second = bm.allocate(1, 32)
    assert set(second) <= set(first) | set(range(8))
    assert sorted(first) == sorted(second)   # the freed blocks came back
    # conservation: live refs + free == total
    assert bm.free_blocks + len(bm.refs) == bm.num_blocks


# ------------------------------------------------------------- watermark --
def test_allocate_enforces_watermark():
    bm = _mk(num_blocks=10, block_size=8, watermark=0.2)   # 2 reserved
    assert bm.watermark_blocks == 2
    assert bm.can_allocate(8 * 8)
    assert not bm.can_allocate(9 * 8)
    bm.allocate(0, 8 * 8)                    # down to the reserve
    # admission-style allocation may not drain the reserve...
    with pytest.raises(RuntimeError, match="watermark"):
        bm.allocate(1, 8)
    assert 1 not in bm.tables
    # ...but the in-flight decode path (append_token) may
    assert bm.append_token(0, 8 * 8 + 1) is not None
    assert bm.free_blocks == 1


def test_append_token_only_allocates_on_boundary():
    bm = _mk(num_blocks=8, block_size=8)
    bm.allocate(0, 8)
    assert bm.append_token(0, 8) is None     # still inside the block
    assert bm.append_token(0, 9) is not None  # crosses the boundary
    assert len(bm.tables[0]) == 2


def test_watermark_under_prefix_eviction():
    """Cache-held blocks must be reclaimable: eviction frees them back to
    the free list so admission can proceed without preemption."""
    bm = _mk(num_blocks=8, block_size=4, watermark=0.2)    # 1 reserved
    idx = PrefixIndex(bm)
    toks = np.arange(24)                     # 6 full blocks
    blocks = bm.allocate(0, 24)
    idx.insert(toks, blocks)
    bm.release(0)                            # cache now sole owner
    assert bm.free_blocks == 2               # 6 cached + 2 free
    assert not bm.can_allocate(16)           # 4 needed, watermark 1
    assert idx.evict(3) == 3
    assert bm.free_blocks == 5
    assert bm.can_allocate(16)
    bm.allocate(1, 16)
    assert bm.free_blocks + len(bm.refs) == bm.num_blocks


# ------------------------------------------------------- sharing and COW --
def test_share_refcounts_and_release_order():
    bm = _mk(num_blocks=8, block_size=8)
    blocks = bm.allocate(0, 16)
    bm.share(1, blocks)
    assert all(bm.ref_count(b) == 2 for b in blocks)
    bm.release(0)
    assert all(bm.ref_count(b) == 1 for b in blocks)
    assert bm.free_blocks == 6               # still owned by request 1
    bm.release(1)
    assert bm.free_blocks == 8
    assert bm.refs == {}


def test_incref_decref_pin_blocks():
    bm = _mk(num_blocks=4, block_size=8)
    (b,) = bm.allocate(0, 8)
    bm.incref(b)                             # cache-style pin
    bm.release(0)
    assert bm.ref_count(b) == 1
    assert bm.free_blocks == 3               # pinned, not freed
    assert bm.decref(b)                      # last ref drops -> freed
    assert bm.free_blocks == 4


def test_copy_on_write_forks_shared_tail():
    bm = _mk(num_blocks=8, block_size=8)
    blocks = bm.allocate(0, 16)
    bm.share(1, blocks)                      # both tables end in blocks[1]
    assert bm.needs_cow(1, 12)               # pos 12 -> shared block idx 1
    assert not bm.needs_cow(1, 99)           # beyond the table: new block
    v0 = bm.version
    old, new = bm.copy_on_write(1, 1)
    assert old == blocks[1] and new not in blocks
    assert bm.tables[1][1] == new and bm.tables[0][1] == old
    assert bm.ref_count(old) == 1 and bm.ref_count(new) == 1
    assert bm.version > v0                   # device tables must re-upload
    assert bm.cow_copies == 1
    # private block: no-op
    assert bm.copy_on_write(1, 1) is None
    bm.release(0)
    bm.release(1)
    assert bm.free_blocks == 8


def test_cow_pool_copy_preserves_contents(rules):
    """Pool-level ensure_writable: the forked block must carry the shared
    block's K/V rows so the new owner's reads are unchanged."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.kvcache.paged import PagedKVCache
    from repro.models import model as M

    cfg = reduced(get_config("opt-1.3b"))
    pool = PagedKVCache(cfg, num_blocks=8, block_size=8, max_batch=2)
    pool.manager.allocate(0, 16)
    cache = M.init_cache(cfg, 1, 16)
    cache = jax.tree.map(lambda x: jnp.full_like(x, 5.0), cache)
    pool.write_prefill(0, cache)
    pool.manager.share(1, pool.manager.tables[0])    # share both blocks
    pool.ensure_writable(1, 12)                      # fork the tail block
    assert pool.manager.tables[1][1] != pool.manager.tables[0][1]
    view = pool.gather([1], pad_blocks=2)
    for leaf in jax.tree.leaves(view):
        if leaf.ndim == 5:                   # [L, B, S, K, hd] kv leaf
            assert np.allclose(np.asarray(leaf)[:, 0, :16], 5.0)


def test_pool_writes_donate_the_pool(rules):
    """Every pool write runs in a program that donates the pool: the old
    leaves are deleted, so no write ever holds two copies of the pool."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.kvcache.paged import PagedKVCache
    from repro.models import model as M

    cfg = reduced(get_config("opt-1.3b"))
    pool = PagedKVCache(cfg, num_blocks=8, block_size=8, max_batch=2)
    pool.manager.allocate(0, 16)
    cache = jax.tree.map(lambda x: jnp.full_like(x, 5.0),
                         M.init_cache(cfg, 1, 16))

    def donated(write):
        old = jax.tree.leaves(pool.pool)
        write()
        return all(leaf.is_deleted() for leaf in old)

    assert donated(lambda: pool.write_prefill(0, cache))
    assert donated(lambda: pool.write_prefill(0, cache, start_pos=3,
                                              n_tokens=5))
    assert donated(lambda: pool.scatter_new_token(
        [0], [10], pool.gather([0], pad_blocks=2)))
    pool.manager.share(1, pool.manager.tables[0])
    assert donated(lambda: pool.ensure_writable(1, 12))
    view = pool.gather([1], pad_blocks=2)
    for leaf in jax.tree.leaves(view):
        assert np.allclose(np.asarray(leaf)[:, 0, :16], 5.0)
