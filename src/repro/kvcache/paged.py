"""Paged KV cache (vLLM's PagedAttention, TPU-adapted).

vLLM pages the KV cache with CUDA pointer chasing inside the attention
kernel. TPUs have no in-kernel pointer chasing, so the TPU-native analogue
drives addressing through a *block table*: physical KV blocks live in a
pool tensor and a per-request table of block ids maps logical to physical
positions. Memory accounting (the thing BCA cares about) is identical
to vLLM's: allocation at block granularity, a free list, and admission
control by free-block watermark.

The pool is generic over the model-cache pytree: attention K/V leaves
(which carry a ``kv_seq`` logical axis) are paged; SSM state / cross-attn
leaves are per-slot dense state (they are O(1) in sequence length, there
is nothing to page).

Two consumption modes:

* **zero-copy** (:meth:`PagedKVCache.view`, the steady-state decode path):
  a :class:`~repro.kvcache.view.PagedCacheView` referencing the pool
  leaves directly plus device-resident block tables. The model runs
  block-table attention against the pool in place and writes the new
  token's K/V row at its physical (block, slot) — no ``[B, S_pad]``
  materialization, no full-pytree write-back.
* **gather/scatter** (:meth:`gather` + :meth:`scatter_new_token`, the
  documented fallback): materializes a dense per-request copy. Kept for
  sliding-window models (the ring-buffer layout is not paged) and as the
  reference the equivalence tests compare against.

One extra *trash* physical block (id ``num_blocks``) and one trash dense
slot (id ``max_batch``) absorb the writes of batch-padding rows, so the
engine can pad the running batch to power-of-two buckets without
corrupting live state.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kvcache.view import PagedCacheView
from repro.models import model as model_lib
from repro.models.params import ParamSpec
from repro.sharding import KV_SEQ

# the pool layer's spans (``pool.*``): repro.serving.obs.trace.span, which
# lists the names, taken from JAX directly since the serving package
# imports this module
span = jax.profiler.TraceAnnotation


@jax.jit
def _advance_poslen(pos, lens):
    """Steady-state decode advances every row's write position and
    length by exactly one: one fused device bump of the cached vectors
    replaces two host rebuilds + uploads per step (see
    :meth:`PagedKVCache.view`). Padding rows keep length 0, so the paged
    kernel copies and computes nothing for them."""
    return pos + 1, lens + (lens > 0)


class BlockManager:
    """Ref-counted free-list block allocator with a vLLM-style watermark.

    Physical blocks carry a reference count so they can be *shared* across
    requests (the prefix cache splices one block into many tables):
    :meth:`allocate` hands out fresh blocks with one reference,
    :meth:`share` splices existing blocks into another request's table
    (+1 each), and the prefix index pins cached blocks with its own
    reference via :meth:`incref`/:meth:`decref`. A block returns to the
    free list only when its last reference drops.

    :meth:`allocate` enforces the same watermark :meth:`can_allocate`
    advertises: the last ``watermark_blocks`` blocks are a preemption
    reserve, reachable only with ``allow_reserve=True`` — the engine's
    mid-decode append/COW path, which is backed by preempt-on-exhaustion.
    (Previously ``allocate`` only checked raw exhaustion, so the
    ``append_token`` path could silently drain the reserve that admission
    control was counting on.)
    """

    def __init__(self, num_blocks: int, block_size: int,
                 watermark: float = 0.01):
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.free: List[int] = list(range(num_blocks))
        self.tables: Dict[int, List[int]] = {}
        self.refs: Dict[int, int] = {}           # live block -> ref count
        self.watermark_blocks = max(1, int(num_blocks * watermark))
        # bumped on every table mutation; lets the pool cache device-side
        # block tables and only re-upload when something actually changed
        self.version = 0
        self.total_allocations = 0   # fresh blocks handed out (telemetry)
        self.cow_copies = 0          # copy-on-write forks (telemetry)

    @property
    def free_blocks(self) -> int:
        """Blocks on the free list (including the watermark reserve)."""
        return len(self.free)

    def blocks_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def can_allocate(self, n_tokens: int) -> bool:
        return (len(self.free) - self.blocks_needed(n_tokens)
                >= self.watermark_blocks)

    def allocate(self, req_id: int, n_tokens: int, *,
                 allow_reserve: bool = False) -> List[int]:
        need = self.blocks_needed(n_tokens)
        if need > len(self.free):
            raise RuntimeError("KV pool exhausted")
        if not allow_reserve and len(self.free) - need < self.watermark_blocks:
            raise RuntimeError(
                f"allocation of {need} blocks would drain the watermark "
                f"reserve ({len(self.free)} free, {self.watermark_blocks} "
                f"reserved); check can_allocate first or pass "
                f"allow_reserve=True for the in-flight decode path")
        got = [self.free.pop() for _ in range(need)]
        for b in got:
            self.refs[b] = 1
        self.total_allocations += need
        self.tables.setdefault(req_id, []).extend(got)
        self.version += 1
        return got

    def covered_tokens(self, req_id: int) -> int:
        """Tokens the request's current table can hold (block-granular)."""
        return len(self.tables.get(req_id, ())) * self.block_size

    def can_extend(self, req_id: int, target_tokens: int) -> bool:
        """Could the table grow to cover ``target_tokens`` without
        draining the watermark reserve? (True when it already does.)"""
        short = target_tokens - self.covered_tokens(req_id)
        return short <= 0 or self.can_allocate(short)

    def extend(self, req_id: int, target_tokens: int, *,
               allow_reserve: bool = False) -> List[int]:
        """Grow ``req_id``'s table to cover ``target_tokens`` total tokens.

        The chunked-prefill allocation entry point: each prompt chunk
        extends the table by exactly the blocks it is about to write, so a
        long prompt streams into the pool across steps instead of
        reserving its whole footprint at admission. Enforces the same
        admission watermark as :meth:`allocate` (a chunk must never
        over-allocate past the reserve); returns the new blocks (empty
        when the table already covers the target).
        """
        short = target_tokens - self.covered_tokens(req_id)
        if short <= 0:
            return []
        return self.allocate(req_id, short, allow_reserve=allow_reserve)

    def share(self, req_id: int, blocks: Sequence[int]):
        """Splice existing (cached) blocks into ``req_id``'s table.

        The caller appends them *before* allocating any private suffix
        blocks so logical order is preserved. Each shared block gains one
        reference; the request's :meth:`release` drops it again.
        """
        for b in blocks:
            self.refs[b] += 1
        self.tables.setdefault(req_id, []).extend(blocks)
        self.version += 1

    def incref(self, block: int):
        """Pin a live block (prefix-cache reference, not tied to a table)."""
        self.refs[block] += 1

    def decref(self, block: int) -> bool:
        """Drop one reference; returns True when the block was freed."""
        n = self.refs[block] - 1
        if n > 0:
            self.refs[block] = n
            return False
        del self.refs[block]
        self.free.append(block)
        return True

    def ref_count(self, block: int) -> int:
        return self.refs.get(block, 0)

    def needs_block(self, req_id: int, new_len: int) -> bool:
        """Would extending req_id to new_len tokens require a new block?"""
        return new_len > len(self.tables.get(req_id, ())) * self.block_size

    def needs_cow(self, req_id: int, pos: int) -> bool:
        """Would writing at ``pos`` hit a block shared with other owners?"""
        table = self.tables.get(req_id, ())
        idx = pos // self.block_size
        return idx < len(table) and self.refs.get(table[idx], 0) > 1

    def append_token(self, req_id: int, new_len: int) -> Optional[int]:
        """Ensure capacity for new_len tokens; returns a new block or None.

        May dip into the watermark reserve: a running request must be able
        to take its next token (that is what the reserve is *for*); the
        engine preempts when even the reserve is gone.
        """
        if self.needs_block(req_id, new_len):
            have = len(self.tables.get(req_id, ())) * self.block_size
            return self.allocate(req_id, new_len - have,
                                 allow_reserve=True)[0]
        return None

    def copy_on_write(self, req_id: int,
                      block_idx: int) -> Optional[Tuple[int, int]]:
        """Fork a shared block so ``req_id`` can write into it.

        Returns ``(old, new)`` physical ids when a fork happened (the
        caller must copy the pool contents), or None when the block is
        already private. The fresh block may come from the watermark
        reserve — an in-flight request's write, like ``append_token``.
        """
        table = self.tables[req_id]
        old = table[block_idx]
        if self.refs[old] <= 1:
            return None
        if not self.free:
            raise RuntimeError("KV pool exhausted (copy-on-write)")
        new = self.free.pop()
        self.refs[new] = 1
        self.refs[old] -= 1
        self.total_allocations += 1
        self.cow_copies += 1
        table[block_idx] = new
        self.version += 1
        return old, new

    def truncate(self, req_id: int, keep_blocks: int) -> List[int]:
        """Drop ``req_id``'s table blocks beyond the first ``keep_blocks``.

        The token-granular rollback primitive (speculative decoding
        releases rejected-token KV through it): tail blocks leave the
        table and drop one reference each — a block returns to the free
        list only when no other owner (another request's table or the
        prefix index) still holds it, so prefix-shared blocks are never
        reclaimed out from under their co-owners. Returns the dropped
        physical ids (possibly still live via other references).
        """
        if keep_blocks < 0:
            raise ValueError(f"keep_blocks must be >= 0, got {keep_blocks}")
        table = self.tables.get(req_id)
        if table is None or keep_blocks >= len(table):
            return []
        dropped = table[keep_blocks:]
        del table[keep_blocks:]
        for b in dropped:
            self.decref(b)
        self.version += 1
        return dropped

    def release(self, req_id: int):
        table = self.tables.pop(req_id, [])
        for b in table:
            self.decref(b)
        if table:
            self.version += 1

    @property
    def used_fraction(self) -> float:
        return 1.0 - len(self.free) / self.num_blocks


def _is_kv_leaf(spec: ParamSpec) -> bool:
    return KV_SEQ in spec.logical


def cache_layout(cfg: ArchConfig, block_size: int):
    """(is_kv, bdim) pytrees describing a config's pool layout.

    ``is_kv``: True for paged attention-K/V leaves (vs dense per-slot
    state); ``bdim``: index of the block/slot axis (1 when the leaf is
    layer-stacked). Every program that reads or writes the pool takes
    these facts from here at trace time.
    """
    template = model_lib.abstract_cache(cfg, 1, block_size)
    is_spec = lambda x: isinstance(x, ParamSpec)    # noqa: E731
    is_kv = jax.tree.map(_is_kv_leaf, template, is_leaf=is_spec)
    bdim = jax.tree.map(
        lambda sp: 1 if sp.logical and sp.logical[0] == "layers" else 0,
        template, is_leaf=is_spec)
    return is_kv, bdim


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _rows(x: jax.Array) -> jax.Array:
    """``[..., K, hd]`` -> the pool's lane-dense ``[..., K*hd]`` rows."""
    return x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])


# A layer-stacked K/V leaf [L, N, BS, K*hd] is indexed as its flat
# [L*N, BS, K*hd] form with per-layer offsets, so gathers and scatters
# address its leading dims — as the decode step does. Indexing behind the
# layer dim makes the TPU compiler relayout the whole leaf into a copy,
# which a pool that fills device memory has no room for.
def _layer_ids(leaf, ids):
    """Flat ids of physical blocks ``ids`` in every layer: [L, *ids]."""
    L, N = leaf.shape[:2]
    return (jnp.arange(L) * N).reshape((L,) + (1,) * ids.ndim) + ids


def _flat(leaf):
    return leaf.reshape(-1, *leaf.shape[2:])


def _take_blocks(leaf, bd: int, ids):
    """``leaf[(:,) ids]``: [(L,) *ids.shape, BS, K*hd]."""
    return _flat(leaf)[_layer_ids(leaf, ids)] if bd == 1 else leaf[ids]


def _set_rows(leaf, bd: int, phys, sib, rows):
    """``leaf[(:,) phys, sib] = rows`` with rows ``[(L,) n, K*hd]``."""
    if bd == 0:
        return leaf.at[phys, sib].set(rows)
    ids = _layer_ids(leaf, phys)
    flat = _flat(leaf).at[ids, jnp.broadcast_to(sib, ids.shape)].set(rows)
    return flat.reshape(leaf.shape)


def gather_prefix_jit(pool, cfg: ArchConfig, tables, block_size: int):
    """Materialize dense ``[.., 1, P, K, hd]`` prefix K/V from the pool
    leaves through a trash-padded ``[nb]`` block table (rows past the
    valid prefix length are masked downstream via ``prefix_len``).
    Traceable: it runs fused inside the chunk-prefill jit."""
    is_kv, bdim = cache_layout(cfg, block_size)
    P = tables.shape[0] * block_size
    heads = (cfg.n_kv_heads, cfg.hd)

    def g(leaf, kv, bd):
        if not kv:
            raise NotImplementedError(
                "prefix gather over non-KV (dense-state) leaves: chunked "
                "prefill requires per-token state")
        v = _take_blocks(leaf, bd, tables)         # [(L,) nb, BS, K*hd]
        return v.reshape(*v.shape[:-3], 1, P, *heads)

    return jax.tree.map(g, pool, is_kv, bdim)


def scatter_rows_jit(pool, cache_one, cfg: ArchConfig, tables, start,
                     n_valid, block_size: int, slot=None):
    """Scatter the batch-1 cache's first ``n_valid`` rows (traced) to
    their physical (block, slot) addresses starting at traced position
    ``start`` through the request's trash-padded block table; padding
    rows are routed to the trash block. Dense-state leaves are written
    whole at ``slot`` (None where the caller guarantees there are none).
    Traceable; returns the new pool."""
    is_kv, bdim = cache_layout(cfg, block_size)

    def s(leaf, view, kv, bd):
        v = view[:, 0] if bd == 1 else view[0]     # [L, S, K, hd] / [S,..]
        if not kv:
            if slot is None:
                raise NotImplementedError(
                    "chunk scatter over non-KV (dense-state) leaves: "
                    "chunked prefill requires per-token state")
            return leaf.at[:, slot].set(v) if bd == 1 else leaf.at[slot].set(v)
        S = v.shape[1] if bd == 1 else v.shape[0]
        trash = (leaf.shape[1] if bd == 1 else leaf.shape[0]) - 1
        pos = start + jnp.arange(S)
        idx = jnp.clip(pos // block_size, 0, tables.shape[0] - 1)
        phys = jnp.where(jnp.arange(S) < n_valid, tables[idx], trash)
        return _set_rows(leaf, bd, phys, pos % block_size, _rows(v))

    return jax.tree.map(s, pool, cache_one, is_kv, bdim)


# Reads of the pool run as programs too: an eager reshape or index of a
# pool leaf would materialize a copy of it.
_gather_prefix = jax.jit(gather_prefix_jit,
                         static_argnames=("cfg", "block_size"))


@functools.partial(jax.jit, static_argnames=("cfg", "block_size"))
def _gather(pool, tables, slots, *, cfg: ArchConfig, block_size: int):
    """Dense ``[(L,) B, S_pad, ...]`` cache view of the requests whose
    block tables are the rows of ``tables`` (dense state: their slots)."""
    is_kv, bdim = cache_layout(cfg, block_size)
    B, nb = tables.shape
    heads = (cfg.n_kv_heads, cfg.hd)

    def g(leaf, kv, bd):
        if kv:
            v = _take_blocks(leaf, bd, tables)     # [(L,) B, nb, BS, K*hd]
            return v.reshape(*v.shape[:-4], B, nb * block_size, *heads)
        return jnp.take(leaf, slots, axis=bd)

    return jax.tree.map(g, pool, is_kv, bdim)


# Every program that mutates the pool donates it (argument 0), so the
# update aliases the pool's buffers: no program ever holds two copies of
# a pool that fills device memory.
_donating = functools.partial(jax.jit, static_argnames=("cfg", "block_size"),
                              donate_argnums=0)


@_donating
def _write_rows(pool, cache_one, tables, start, n_valid, slot, *,
                cfg: ArchConfig, block_size: int):
    return scatter_rows_jit(pool, cache_one, cfg, tables, start, n_valid,
                            block_size, slot=slot)


@_donating
def _write_new_tokens(pool, new_cache, phys, sib, pos, slots, *,
                      cfg: ArchConfig, block_size: int):
    """Gather-mode write-back: row ``pos[i]`` of request i's dense view
    goes to physical (phys[i], sib[i]); dense state goes to its slot."""
    is_kv, bdim = cache_layout(cfg, block_size)
    rows = jnp.arange(phys.shape[0])

    def s(leaf, view, kv, bd):
        if kv:
            row = view[:, rows, pos] if bd == 1 else view[rows, pos]
            return _set_rows(leaf, bd, phys, sib, _rows(row))
        return leaf.at[:, slots].set(view) if bd == 1 \
            else leaf.at[slots].set(view)

    return jax.tree.map(s, pool, new_cache, is_kv, bdim)


@_donating
def _copy_block(pool, old, new, *, cfg: ArchConfig, block_size: int):
    """Copy physical block ``old`` into ``new`` in every K/V leaf."""
    is_kv, bdim = cache_layout(cfg, block_size)

    def cp(leaf, kv, bd):
        if not kv:
            return leaf
        if bd == 1:
            return _flat(leaf).at[_layer_ids(leaf, new)].set(
                _flat(leaf)[_layer_ids(leaf, old)]).reshape(leaf.shape)
        return leaf.at[new].set(leaf[old])

    return jax.tree.map(cp, pool, is_kv, bdim)


class PagedKVCache:
    """Physical paged pool mirroring a model cache pytree."""

    def __init__(self, cfg: ArchConfig, *, num_blocks: int, block_size: int,
                 max_batch: int,
                 sharding: Optional[jax.sharding.Sharding] = None):
        self.cfg = cfg
        # where the pool and every array uploaded for it live (None: the
        # default device); a replica passes its own device's sharding
        self.sharding = sharding
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_batch = max_batch
        self.manager = BlockManager(num_blocks, block_size)
        # dense-state slot assignment for non-paged leaves (SSM state,
        # cross-attn K/V); slot ``max_batch`` is the padding trash slot.
        self._slots: Dict[int, int] = {}
        self._free_slots: List[int] = list(range(max_batch))
        self.trash_block = num_blocks          # physical block for padding
        self.trash_slot = max_batch            # dense slot for padding
        # template with batch=1, kv_len=block_size gives per-leaf shapes;
        # is_kv / bdim (the layout facts) come from the shared helper so
        # the jitted chunk-prefill step agrees with the pool byte-for-byte
        template = model_lib.abstract_cache(cfg, 1, block_size)
        is_spec = lambda x: isinstance(x, ParamSpec)
        self._is_kv, self._bdim = cache_layout(cfg, block_size)

        def mk(spec: ParamSpec, is_kv: bool, bdim: int):
            shape = list(spec.shape)
            # +1: trash block / trash slot absorbing padding-row writes
            shape[bdim] = num_blocks + 1 if is_kv else max_batch + 1
            if is_kv:       # [.., BS, K, hd] -> lane-dense [.., BS, K*hd]
                shape[-2:] = [shape[-2] * shape[-1]]
            return jnp.zeros(tuple(shape), spec.dtype)

        # zeros made by a program that runs on the pool's own device:
        # jnp.zeros(device=...) fills on the default device first and
        # copies, which a pool sized to another chip's memory overflows
        self.pool = jax.jit(lambda: jax.tree.map(
            mk, template, self._is_kv, self._bdim, is_leaf=is_spec),
            out_shardings=sharding)()
        # device block-table cache for the zero-copy view
        self._dev_tables: Optional[jax.Array] = None
        self._dev_slots: Optional[jax.Array] = None
        self._dev_tables_key: Optional[Tuple] = None
        self._tables_np: Optional[np.ndarray] = None    # host mirror
        self._tables_snap: Optional[List[Tuple]] = None  # per-row blocks
        # (composition key, positions, dev_pos, dev_lens) of the last
        # view — steady-state steps advance it on device (see view())
        self._poslen: Optional[Tuple] = None
        # --- byte accounting (memory-gap auditor) ---
        # one physical block's bytes summed across every paged KV leaf;
        # each leaf's block axis holds num_blocks+1 rows (incl. trash),
        # so nbytes divides evenly by it
        blk = 0
        dense = 0
        for leaf, kv in zip(jax.tree.leaves(self.pool),
                            jax.tree.leaves(self._is_kv)):
            if kv:
                blk += leaf.nbytes // (num_blocks + 1)
            else:
                dense += leaf.nbytes
        self.block_bytes: int = blk
        self.dense_state_bytes: int = dense     # per-slot state, not paged

    @property
    def pool_bytes(self) -> int:
        """Accountable pool bytes: every real physical block (the trash
        block absorbs padding writes and is excluded — it never holds
        request state, so attributing it would dilute the waste terms)."""
        return self.block_bytes * self.num_blocks

    @property
    def token_bytes(self) -> float:
        """KV bytes one written token occupies (block_bytes/block_size)."""
        return self.block_bytes / self.block_size

    # ------------------------------------------------------------------
    def gather(self, req_ids: Sequence[int], pad_blocks: int):
        """Materialize the logical cache view [B, S_pad, ...] for req_ids."""
        B = len(req_ids)
        table = np.zeros((B, pad_blocks), np.int32)
        for i, rid in enumerate(req_ids):
            blocks = self.manager.tables.get(rid, [])
            table[i, :len(blocks)] = blocks[:pad_blocks]
        slots = np.asarray([self._slot(rid) for rid in req_ids], np.int32)
        return _gather(self.pool, *self.put((table, slots)), cfg=self.cfg,
                       block_size=self.block_size)

    def scatter_new_token(self, req_ids: Sequence[int],
                          positions: Sequence[int], new_cache):
        """Write each request's new KV row (at its position) + state back."""
        B = len(req_ids)
        phys = np.zeros((B,), np.int32)
        slot_in_block = np.zeros((B,), np.int32)
        for i, (rid, pos) in enumerate(zip(req_ids, positions)):
            blocks = self.manager.tables[rid]
            phys[i] = blocks[pos // self.block_size]
            slot_in_block[i] = pos % self.block_size
        self.pool = _write_new_tokens(
            self.pool, new_cache, phys, slot_in_block,
            np.asarray(positions, np.int32),
            np.asarray([self._slot(rid) for rid in req_ids], np.int32),
            cfg=self.cfg, block_size=self.block_size)

    def gather_prefix(self, blocks: Sequence[int], nb_pad: int):
        """Materialize cached prefix K/V for a suffix-only prefill.

        ``blocks`` are full physical blocks (typically spliced from the
        prefix index) holding a prompt's first ``len(blocks)*block_size``
        tokens. Returns a cache-shaped pytree of dense ``[.., 1, P, K,
        hd]`` leaves with ``P = nb_pad * block_size``; table entries past
        ``len(blocks)`` read the trash block and are masked out by the
        attention layer via ``prefix_len``. Only KV (attention) leaves are
        supported — prefix caching is gated to per-token-state configs.
        """
        table = np.full((nb_pad,), self.trash_block, np.int32)
        table[:len(blocks)] = blocks
        return _gather_prefix(self.pool, self.cfg, self.put(table),
                              self.block_size)

    def ensure_writable(self, req_id: int, pos: int):
        """Copy-on-write fork of the block holding ``pos`` if it is shared.

        No-op for private blocks (the common case — a dict lookup). When a
        request is about to write into a block another owner also holds
        (e.g. a partially filled tail block spliced from the prefix
        cache), the block is forked: fresh physical block, contents
        copied, table entry swapped, old block's ref dropped.
        """
        idx = pos // self.block_size
        if not self.manager.needs_cow(req_id, pos):
            return
        old, new = self.manager.copy_on_write(req_id, idx)
        self.pool = _copy_block(self.pool, np.int32(old), np.int32(new),
                                cfg=self.cfg, block_size=self.block_size)

    def write_prefill(self, req_id: int, cache_one, start_pos: int = 0,
                      n_tokens: Optional[int] = None):
        """Store a single request's prefill cache (batch dim == 1).

        ``start_pos`` (block-aligned) writes the view starting at that
        token position — the suffix-only prefill path leaves the cached
        prefix blocks untouched and fills only the request's own blocks.

        With ``n_tokens`` the write is *token-granular*: exactly the view's
        first ``n_tokens`` rows are scattered to their physical
        (block, slot) addresses starting at an arbitrary (not necessarily
        block-aligned) ``start_pos`` — the chunked-prefill path, where a
        chunk may end mid-block and the next chunk picks up inside the
        same physical block. The write refuses to run past the allocated
        table (a chunk must extend the table first, through the
        watermark-checked :meth:`BlockManager.extend`).
        """
        with span("pool.write"):
            table = self.manager.tables.get(req_id, [])
            cap = len(table) * self.block_size
            slot = None             # dense state: whole-prompt writes only
            if n_tokens is None:
                if start_pos % self.block_size:
                    raise ValueError(
                        f"start_pos ({start_pos}) must be block-aligned "
                        f"(block_size={self.block_size}); pass n_tokens "
                        f"for the token-granular chunk path")
                # the view's rows up to the table's end
                S = next((v.shape[-3] for v, kv in zip(
                    jax.tree.leaves(cache_one), jax.tree.leaves(self._is_kv))
                    if kv), 0)
                n_tokens = min(S, cap - start_pos)
                slot = np.int32(self._slot(req_id))
            elif n_tokens < 1:
                raise ValueError(f"n_tokens must be >= 1, got {n_tokens}")
            elif start_pos + n_tokens > cap:
                raise ValueError(
                    f"chunk write [{start_pos}, {start_pos + n_tokens}) "
                    f"over-allocates past req {req_id}'s table ({len(table)} "
                    f"blocks x {self.block_size}); extend() the table first")
            # pow2-padded table: one compiled write per (view width, pad)
            tbl = np.full((_pow2(len(table)),), self.trash_block, np.int32)
            tbl[:len(table)] = table
            self.pool = _write_rows(
                self.pool, cache_one, tbl, np.int32(start_pos),
                np.int32(n_tokens), slot, cfg=self.cfg,
                block_size=self.block_size)

    # ------------------------------------------------------- zero-copy --
    def view(self, req_ids: Sequence[int], positions: Sequence[int],
             nb_pad: int, batch_pad: int) -> PagedCacheView:
        """Zero-copy :class:`PagedCacheView` over the pool for ``req_ids``.

        ``positions[i]`` is the write position of request i's new token
        this step. ``nb_pad``/``batch_pad`` are the bucketed table width /
        batch size (the engine pads both to powers of two so the jit cache
        stays small); padding rows address the trash block/slot and carry
        length 0.

        The host->device traffic here sits on the per-step critical path
        of large-batch decode, so every piece is cached at the right
        granularity:

        * slots and the ``[batch_pad, nb_pad]`` block table are keyed on
          the batch *composition* ``(req_ids, nb_pad, batch_pad)``; an
          allocator ``version`` bump with the composition unchanged (a
          handful of rows crossed a block boundary — at large batch that
          is *most* steps) patches only the changed rows of the cached
          host table instead of rebuilding all of it;
        * positions/lengths advance by exactly one for every row in an
          unchanged composition, so steady-state steps bump the cached
          device vectors with one tiny fused jit instead of two host
          rebuilds + uploads. Padding lanes keep length 0 and their
          positions drift to small nonzero values, which is
          unobservable: pad rows address the trash block/slot, rows are
          independent through the model, and nothing ever reads pad
          outputs or the trash block.
        """
        with span("pool.view"):
            B = len(req_ids)
            assert B <= batch_pad
            ckey = (tuple(req_ids), nb_pad, batch_pad)
            key = ckey + (self.manager.version,)
            if self._dev_tables_key != key:
                if (self._tables_np is not None
                        and self._dev_tables_key is not None
                        and self._dev_tables_key[:3] == ckey):
                    # same rows, allocator moved: patch changed rows only
                    table = self._tables_np
                    snap = self._tables_snap
                    changed = False
                    for i, rid in enumerate(req_ids):
                        blocks = tuple(
                            self.manager.tables.get(rid, [])[:nb_pad])
                        if snap[i] != blocks:
                            table[i, :] = self.trash_block
                            table[i, :len(blocks)] = blocks
                            snap[i] = blocks
                            changed = True
                    if changed:
                        self._dev_tables = self.put(table)
                else:
                    table = np.full((batch_pad, nb_pad), self.trash_block,
                                    np.int32)
                    slots = np.full((batch_pad,), self.trash_slot, np.int32)
                    snap = [()] * batch_pad
                    for i, rid in enumerate(req_ids):
                        blocks = tuple(
                            self.manager.tables.get(rid, [])[:nb_pad])
                        table[i, :len(blocks)] = blocks
                        snap[i] = blocks
                        slots[i] = self._slot(rid)
                    self._tables_np = table
                    self._tables_snap = snap
                    self._dev_tables, self._dev_slots = self.put(
                        (table, slots))
                self._dev_tables_key = key
            pt = tuple(positions)
            cached = self._poslen
            if (cached is not None and cached[0] == ckey
                    and all(p == q + 1 for p, q in zip(pt, cached[1]))):
                dev_pos, dev_lens = _advance_poslen(cached[2], cached[3])
            else:
                pos = np.zeros((batch_pad,), np.int32)
                pos[:B] = np.asarray(positions, np.int32)
                lens = np.zeros((batch_pad,), np.int32)
                lens[:B] = pos[:B] + 1
                dev_pos, dev_lens = self.put((pos, lens))
            self._poslen = (ckey, pt, dev_pos, dev_lens)
            return PagedCacheView(self.pool, self._dev_tables,
                                  dev_lens, dev_pos,
                                  self._dev_slots, self.block_size)

    def put(self, tree):
        """Upload host arrays to the pool's device."""
        with span("pool.put"):
            return jax.device_put(tree, self.sharding)

    def commit(self, new_pool):
        """Adopt the pool pytree returned by a zero-copy decode step."""
        with span("pool.commit"):
            self.pool = new_pool

    # slot assignment for dense (non-paged) state leaves
    def _slot(self, rid: int) -> int:
        if rid not in self._slots:
            self._slots[rid] = self._free_slots.pop()
        return self._slots[rid]

    def rollback(self, rid: int, n_tokens: int) -> List[int]:
        """Shrink ``rid``'s KV to its first ``n_tokens`` tokens.

        Token-granular: the table is truncated to exactly the blocks
        those tokens need; whole blocks past the boundary are released
        (ref-counted — a prefix-shared block survives in its other
        owners' tables and in the prefix index, untouched). Bytes inside
        the kept tail block past ``n_tokens`` are *not* zeroed: the
        attention mask (``lengths``) already hides them, and the next
        write at those positions lands on the same (block, slot)
        addresses. Returns the dropped physical block ids.
        """
        if n_tokens < 0:
            raise ValueError(f"n_tokens must be >= 0, got {n_tokens}")
        return self.manager.truncate(rid,
                                     self.manager.blocks_needed(n_tokens))

    def release(self, rid: int):
        self.manager.release(rid)
        if rid in self._slots:
            self._free_slots.append(self._slots.pop(rid))
