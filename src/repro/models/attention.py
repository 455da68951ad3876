"""Attention blocks: GQA/MHA/MQA self-attention (causal, bidirectional,
sliding-window) and cross-attention, with query-blocked computation so the
score matrix never materializes at [S, S] — the pure-JAX analogue of the
paper's memory-optimized attention kernels (and the lowering path used by
the multi-pod dry-run; the Pallas kernels in ``repro.kernels`` are the TPU
hot path).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.params import pspec
from repro.models.layers import rope
from repro.sharding import (BATCH, HEADS, HEAD_DIM, KV_HEADS, KV_SEQ,
                            D_MODEL, SEQ, W_IN, ShardingRules, constrain)

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def attn_abstract(cfg: ArchConfig):
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    # column-parallel QKV: heads sharded when divisible by the model axis,
    # else the spec dedup falls through to sharding head_dim (e.g. 56-head
    # deepseek/arctic on a 16-wide axis); row-parallel output projection.
    if cfg.attn_row_parallel:
        # §Perf decode variant: shard the d_model (input) dim instead —
        # the post-projection psum moves one token, not layer weights.
        p = {
            "wq": pspec((d, h, hd), (D_MODEL, HEADS, None), cfg.dtype,
                        fan_in=d),
            "wk": pspec((d, k, hd), (D_MODEL, KV_HEADS, None), cfg.dtype,
                        fan_in=d),
            "wv": pspec((d, k, hd), (D_MODEL, KV_HEADS, None), cfg.dtype,
                        fan_in=d),
            "wo": pspec((h, hd, d), (HEADS, None, D_MODEL), cfg.dtype,
                        fan_in=h * hd),
        }
    else:
        p = {
            "wq": pspec((d, h, hd), (W_IN, HEADS, HEAD_DIM), cfg.dtype,
                        fan_in=d),
            "wk": pspec((d, k, hd), (W_IN, KV_HEADS, HEAD_DIM), cfg.dtype,
                        fan_in=d),
            "wv": pspec((d, k, hd), (W_IN, KV_HEADS, HEAD_DIM), cfg.dtype,
                        fan_in=d),
            "wo": pspec((h, hd, d), (HEADS, HEAD_DIM, W_IN), cfg.dtype,
                        fan_in=h * hd),
        }
    if cfg.qkv_bias:
        p["bq"] = pspec((h, hd), (HEADS, None), cfg.dtype, init="zeros")
        p["bk"] = pspec((k, hd), (KV_HEADS, None), cfg.dtype, init="zeros")
        p["bv"] = pspec((k, hd), (KV_HEADS, None), cfg.dtype, init="zeros")
    return p


def _pick_qb(sq: int, want: int) -> int:
    if sq <= 2 * want:
        return sq
    if sq % want == 0:
        return want
    for qb in range(want, 0, -1):
        if sq % qb == 0:
            return qb
    return sq


def _attention_core(q, k, v, mask_fn, q_block: int,
                    q_offset=0, kv_block: int = 1024) -> jax.Array:
    """q: [B,Sq,K,G,hd]; k,v: [B,Skv,K,hd]; mask_fn(q_ids) -> mask or None.

    Flash-pattern two-level blocking in pure JAX: an outer lax.scan over
    query tiles and an inner lax.scan over KV tiles with a running
    (m, l, acc) online softmax. The score matrix never materializes beyond
    one [qb, kv_block] tile, so HBM traffic is O(NQ * |K| + |Q|) instead of
    O(Sq * Skv) — the same memory-hierarchy move as the Pallas kernel in
    repro.kernels, expressed at the XLA level for the SPMD path.
    """
    B, Sq, K, G, hd = q.shape
    Skv = k.shape[1]
    scale = hd ** -0.5

    def one_qblock(qs, q_ids):
        qb_ = qs.shape[1]
        bs = _pick_qb(Skv, kv_block)
        nkv = Skv // bs
        if nkv <= 1:
            with jax.named_scope("attn_core"):
                s = jnp.einsum("bqkgh,bskh->bqkgs", qs, k,
                               preferred_element_type=jnp.float32) * scale
                mask = mask_fn(q_ids)
                if mask is not None:
                    s = jnp.where(mask, s, NEG_INF)
                m = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - jax.lax.stop_gradient(
                    jnp.maximum(m, NEG_INF)))
                denom = jnp.sum(p, axis=-1, keepdims=True)
                p = (p / jnp.maximum(denom, 1e-30)).astype(q.dtype)
                return jnp.einsum("bqkgs,bskh->bqkgh", p, v)

        kr = jnp.moveaxis(k.reshape(B, nkv, bs, K, hd), 1, 0)
        vr = jnp.moveaxis(v.reshape(B, nkv, bs, K, hd), 1, 0)

        def kv_body(carry, inp):
            m_run, l_run, acc = carry
            kc, vc, j = inp
            with jax.named_scope("attn_core"):
                s = jnp.einsum("bqkgh,bskh->bqkgs", qs, kc,
                               preferred_element_type=jnp.float32) * scale
                mask = mask_fn(q_ids, j * bs + jnp.arange(bs))
                if mask is not None:
                    s = jnp.where(mask, s, NEG_INF)
                m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
                m_safe = jax.lax.stop_gradient(m_new)
                p = jnp.exp(s - m_safe[..., None])
                alpha = jnp.exp(m_run - m_safe)
                l_new = alpha * l_run + jnp.sum(p, axis=-1)
                acc = acc * alpha[..., None] + jnp.einsum(
                    "bqkgs,bskh->bqkgh", p.astype(q.dtype), vc,
                    preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), ()

        init = (jnp.full((B, qb_, K, G), NEG_INF, jnp.float32),
                jnp.zeros((B, qb_, K, G), jnp.float32),
                jnp.zeros((B, qb_, K, G, hd), jnp.float32))
        (m_f, l_f, acc), _ = jax.lax.scan(kv_body, init,
                                          (kr, vr, jnp.arange(nkv)))
        return (acc / jnp.maximum(l_f, 1e-30)[..., None]).astype(q.dtype)

    qb = _pick_qb(Sq, q_block)
    if qb == Sq:
        return one_qblock(q, q_offset + jnp.arange(Sq))
    nq = Sq // qb
    qr = jnp.moveaxis(q.reshape(B, nq, qb, K, G, hd), 1, 0)   # [NQ,B,qb,...]

    def body(_, inp):
        qs, i = inp
        out = jax.checkpoint(one_qblock)(
            qs, q_offset + i * qb + jnp.arange(qb))
        return (), out

    _, outs = jax.lax.scan(body, (), (qr, jnp.arange(nq)))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, K, G, hd)


def _expand_mask(mask, b, qb, skv):
    """Normalize mask to [B,QB,1,1,Skv] broadcastable shape."""
    if mask is None:
        return None
    if mask.ndim == 2:       # [QB, Skv]
        mask = mask[None]
    return mask[:, :, None, None, :]


def _mask_builder(*, causal: bool, window: Optional[int],
                  kv_ids: jax.Array, lengths: Optional[jax.Array]):
    """Returns mask_fn(q_ids, kv_sel=None)->bool mask given the kv
    slot->token-id map; kv_sel selects a KV tile (flash inner loop)."""
    def fn(q_ids, kv_sel=None):
        ids = kv_ids if kv_sel is None else kv_ids[kv_sel]
        m = jnp.ones((q_ids.shape[0], ids.shape[0]), bool)
        if causal:
            m &= q_ids[:, None] >= ids[None, :]
        if window is not None:
            m &= q_ids[:, None] - ids[None, :] < window
        m &= ids[None, :] >= 0
        if lengths is not None:   # [B] valid kv length per request
            m = m[None] & (ids[None, None, :] < lengths[:, None, None])
        return _expand_mask(m, None, None, None)
    return fn


def qkv_project(p, x, cfg: ArchConfig, rules: ShardingRules,
                positions: Optional[jax.Array]):
    """x: [B,S,D] -> q [B,S,K,G,hd], k,v [B,S,K,hd] (rope applied)."""
    h, k, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    g = h // k
    with jax.named_scope("qkv_proj"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        kk = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        vv = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if cfg.qkv_bias:
            q = q + p["bq"]
            kk = kk + p["bk"]
            vv = vv + p["bv"]
        if cfg.pos == "rope" and positions is not None:
            q = rope(q, positions, cfg.rope_theta)
            kk = rope(kk, positions, cfg.rope_theta)
        # when heads don't divide the model axis the weights are stored
        # hd-sharded (optimizer memory), but attention math runs with
        # replicated heads — all-gather here, NOT psums of score tensors.
        heads_sharded = rules.assign(HEADS, h) is not None
        hd_ax = HEAD_DIM if heads_sharded else None
        q = constrain(q, rules, (BATCH, SEQ, HEADS, hd_ax))
        q = q.reshape(q.shape[0], q.shape[1], k, g, hd)
        kk = constrain(kk, rules, (BATCH, KV_SEQ, KV_HEADS, hd_ax))
        vv = constrain(vv, rules, (BATCH, KV_SEQ, KV_HEADS, hd_ax))
    return q, kk, vv


def out_project(p, o, cfg: ArchConfig, rules: ShardingRules):
    B, S = o.shape[:2]
    o = o.reshape(B, S, cfg.n_heads, cfg.hd)
    with jax.named_scope("attn_out"):
        y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        return constrain(y, rules, (BATCH, SEQ, D_MODEL))


def self_attn_seq(p, x, cfg: ArchConfig, rules: ShardingRules, *,
                  positions: jax.Array, causal: bool,
                  window: Optional[int] = None,
                  lengths: Optional[jax.Array] = None,
                  prefix_k: Optional[jax.Array] = None,
                  prefix_v: Optional[jax.Array] = None,
                  prefix_len: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence self-attention (train / prefill). Returns (out, (K,V)).

    With ``prefix_k/v`` (``[B, P_pad, K, hd]``, e.g. gathered from cached
    KV-pool blocks) the sequence is treated as the *suffix* of a longer
    prompt: queries attend over the concatenated [prefix || suffix] keys,
    ``positions`` carry the absolute (prefix-offset) token positions, and
    ``prefix_len`` (traced scalar) marks how many prefix rows are valid —
    padding rows past it get kv id -1 and are masked out. ``lengths``
    stays the *total* valid KV length per request. The returned cache
    entry covers only the suffix (the prefix KV is already stored).
    Both the prefix-cache suffix prefill and the engine's chunked prefill
    (each prompt chunk attends over the chunks before it) ride this path;
    ``prefix_len`` may land mid-block — validity is a row mask, not an
    alignment requirement.
    """
    B, S, _ = x.shape
    if prefix_k is not None and window is not None:
        raise NotImplementedError(
            "prefix/chunked prefill over a sliding-window ring cache: "
            "the gathered prefix has no ring arithmetic (the engine "
            "gates these configs to serial prefill)")
    q, k, v = qkv_project(p, x, cfg, rules, positions)
    k_all, v_all, q_off = k, v, 0
    kv_ids = jnp.arange(S)
    if prefix_k is not None:
        P = prefix_k.shape[1]
        pl = jnp.asarray(prefix_len, jnp.int32)
        ids_p = jnp.where(jnp.arange(P) < pl, jnp.arange(P), -1)
        kv_ids = jnp.concatenate([ids_p, pl + jnp.arange(S)])
        k_all = jnp.concatenate([prefix_k.astype(k.dtype), k], axis=1)
        v_all = jnp.concatenate([prefix_v.astype(v.dtype), v], axis=1)
        q_off = pl
    mask_fn = _mask_builder(causal=causal, window=window, kv_ids=kv_ids,
                            lengths=lengths)
    if cfg.attn_kv_repeat and cfg.n_kv_heads < cfg.n_heads:
        # §Perf variant: expand K/V to all H heads (contiguous head shard)
        G = cfg.n_heads // cfg.n_kv_heads
        rep = lambda a: jnp.repeat(a, G, axis=2)
        kr = constrain(rep(k_all), rules, (BATCH, None, HEADS, None))
        vr = constrain(rep(v_all), rules, (BATCH, None, HEADS, None))
        qh = q.reshape(B, S, cfg.n_heads, 1, cfg.hd)
        qh = constrain(qh, rules, (BATCH, None, HEADS, None, None))
        o = _attention_core(qh, kr, vr, mask_fn, cfg.q_block, q_offset=q_off)
    else:
        o = _attention_core(q, k_all, v_all, mask_fn, cfg.q_block,
                            q_offset=q_off)
    o = o.reshape(B, S, cfg.n_heads, cfg.hd).reshape(B, S, -1)
    return out_project(p, o, cfg, rules), (k, v)


def self_attn_decode(p, x, cache_k, cache_v, cfg: ArchConfig,
                     rules: ShardingRules, *, pos: jax.Array,
                     window: Optional[int] = None,
                     lengths: Optional[jax.Array] = None):
    """Single-token decode against a (possibly ring) KV cache.

    x: [B,1,D]; cache_k/v: [B,Smax,K,hd]; pos: scalar position (dry-run /
    aligned batches) or a [B] vector (continuous batching — each request
    sits at its own position; writes become a batched scatter).
    When ``window`` is set the cache is a ring buffer of size Smax=window
    and writes go to ``pos % window`` (scalar pos only).
    """
    B, _, _ = x.shape
    Smax = cache_k.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    ragged = pos.ndim == 1
    positions = pos[:, None] if ragged else jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = qkv_project(p, x, cfg, rules, positions)
    with jax.named_scope("kv_update"):
        if ragged:
            assert window is None, "ragged decode does not support windows"
            barange = jnp.arange(B)
            cache_k = cache_k.at[barange, pos].set(
                k_new[:, 0].astype(cache_k.dtype))
            cache_v = cache_v.at[barange, pos].set(
                v_new[:, 0].astype(cache_v.dtype))
        else:
            slot = pos % Smax if window is not None else pos
            cache_k = jax.lax.dynamic_update_slice(
                cache_k, k_new.astype(cache_k.dtype), (0, slot, 0, 0))
            cache_v = jax.lax.dynamic_update_slice(
                cache_v, v_new.astype(cache_v.dtype), (0, slot, 0, 0))
        cache_k = constrain(cache_k, rules, (BATCH, KV_SEQ, KV_HEADS, None))
        cache_v = constrain(cache_v, rules, (BATCH, KV_SEQ, KV_HEADS, None))
    slots = jnp.arange(Smax)
    if ragged:
        eff_len = lengths if lengths is not None else pos + 1
        mask_fn = _mask_builder(causal=False, window=None, kv_ids=slots,
                                lengths=eff_len)
    else:
        if window is None:
            kv_ids = slots
        else:
            # slot s holds token id pos - ((pos - s) mod W); stale ids go < 0
            kv_ids = pos - jnp.mod(pos - slots, Smax)
        mask_fn = _mask_builder(causal=True, window=window, kv_ids=kv_ids,
                                lengths=lengths)
    # no inner KV tiling at decode: the cache's seq dim may be sharded on
    # the model axis (context parallelism) and must stay whole per-op
    o = _attention_core(q, cache_k.astype(x.dtype), cache_v.astype(x.dtype),
                        mask_fn, cfg.q_block,
                        q_offset=0 if ragged else pos,
                        kv_block=cache_k.shape[1])
    o = o.reshape(B, 1, -1)
    return out_project(p, o, cfg, rules), (cache_k, cache_v)


def paged_self_attn_decode(p, x, k_pool, v_pool, cfg: ArchConfig,
                           rules: ShardingRules, *, tables: jax.Array,
                           lengths: jax.Array, positions: jax.Array,
                           block_size: int):
    """Single-token decode straight against the physical KV pool.

    The zero-copy half of the engine's decode data path: instead of a
    gathered ``[B, S_pad, K, hd]`` cache copy, this takes the pool's
    physical blocks (``k_pool/v_pool: [NB, BS, K*hd]``, lane-dense token
    rows, possibly the layer-flattened ``[L*NB, ...]`` form with layer
    offsets pre-added to ``tables``) plus per-request addressing:

      tables    [B, nb] int32  physical block per logical block
      lengths   [B]     int32  valid tokens incl. the one written now
      positions [B]     int32  write position of the new token

    The new K/V row is scattered into its physical (block, slot) — B rows
    touched, not a pytree — and attention runs via the block-table kernel
    (Pallas on TPU, block-scan JAX elsewhere). Returns
    ``(out [B,1,D], (k_pool', v_pool'))`` with the row written in place
    when the caller threads the pool through a donated jit / scan carry.

    Sliding-window ring caches are not paged; the engine uses the gather
    fallback for those configs.
    """
    from repro.kernels.paged_decode_attention import paged_decode_attention

    B = x.shape[0]
    q, k_new, v_new = qkv_project(p, x, cfg, rules, positions[:, None])
    barange = jnp.arange(B)
    phys = tables[barange, positions // block_size]
    sib = positions % block_size
    with jax.named_scope("kv_update"):
        k_pool = k_pool.at[phys, sib].set(
            k_new[:, 0].reshape(B, -1).astype(k_pool.dtype))
        v_pool = v_pool.at[phys, sib].set(
            v_new[:, 0].reshape(B, -1).astype(v_pool.dtype))
    with jax.named_scope("attn_core"):
        # pools are consumed at their storage dtype — the block-table
        # kernels upcast per tile, so no whole-pool astype copy here
        o = paged_decode_attention(q.reshape(B, cfg.n_heads, cfg.hd),
                                   k_pool, v_pool, tables, lengths)
    o = o.reshape(B, 1, -1).astype(x.dtype)
    return out_project(p, o, cfg, rules), (k_pool, v_pool)


def cross_attn_kv(p, img_embeds, cfg: ArchConfig, rules: ShardingRules):
    """Precompute cross-attention K/V from (stubbed) image embeddings."""
    with jax.named_scope("cross_kv"):
        k = jnp.einsum("bsd,dhk->bshk", img_embeds, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", img_embeds, p["wv"])
        k = constrain(k, rules, (BATCH, None, KV_HEADS, None))
        v = constrain(v, rules, (BATCH, None, KV_HEADS, None))
    return k, v


def cross_attn_apply(p, x, k, v, cfg: ArchConfig, rules: ShardingRules):
    """Cross-attention of text stream x onto fixed image K/V (no mask)."""
    B, S, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    with jax.named_scope("cross_attn"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        q = constrain(q, rules, (BATCH, SEQ, HEADS, None))
        q = q.reshape(B, S, kh, h // kh, hd)
        o = _attention_core(q, k.astype(x.dtype), v.astype(x.dtype),
                            lambda q_ids, kv_sel=None: None, cfg.q_block)
        o = o.reshape(B, S, -1)
    return out_project(p, o, cfg, rules)
