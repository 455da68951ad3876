"""Pallas TPU kernel: tiled causal (optionally sliding-window) prefill
attention — FlashAttention re-tiled for the TPU memory hierarchy.

Grid: (batch, q_heads, Sq/block_q, Skv/block_s) with the KV axis innermost
and sequential; (m, l, acc) online-softmax state lives in VMEM scratch and
carries across KV tiles, the [block_q, hd] output tile is written once on
the last KV step. GQA is handled by mapping query head h to KV head h//G in
the BlockSpec index_map, so no materialized K/V repeat.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_s: int, causal: bool,
                  window: Optional[int], kv_len: int, scale: float):
    qi, si = pl.program_id(2), pl.program_id(3)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_ids = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    kv_ids = si * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_s), 1)

    # whole-tile skip test (static grid, dynamic predicate)
    relevant = jnp.logical_and(
        (not causal) or (si * block_s <= qi * block_q + block_q - 1),
        (window is None) or ((si + 1) * block_s - 1 > qi * block_q - window))

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)             # [BQ, hd]
        k = k_ref[0, :, 0, :].astype(jnp.float32)             # [BS, hd]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = kv_ids < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_ids >= kv_ids)
        if window is not None:
            mask = jnp.logical_and(mask, q_ids - kv_ids < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(si == pl.num_programs(3) - 1)
    def _finish():
        o_ref[0, :, 0, :] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                             ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_s", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_s: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: [B,Sq,H,hd]; k/v: [B,Skv,K,hd] -> [B,Sq,H,hd]."""
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    assert H % K == 0
    G = H // K
    bq, bs = min(block_q, Sq), min(block_s, Skv)
    pq, ps = (-Sq) % bq, (-Skv) % bs
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if ps:
        k = jnp.pad(k, ((0, 0), (0, ps), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, ps), (0, 0), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=bq, block_s=bs,
                          causal=causal, window=window, kv_len=Skv,
                          scale=hd ** -0.5),
        grid=(B, H, (Sq + pq) // bq, (Skv + ps) // bs),
        in_specs=[
            pl.BlockSpec((1, bq, 1, hd), lambda b, h, qi, si: (b, qi, h, 0)),
            pl.BlockSpec((1, bs, 1, hd),
                         lambda b, h, qi, si: (b, si, h // G, 0)),
            pl.BlockSpec((1, bs, 1, hd),
                         lambda b, h, qi, si: (b, si, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, hd),
                               lambda b, h, qi, si: (b, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq + pq, H, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq]
