"""Pallas TPU kernel: GQA decode attention (the paper's bottleneck kernel).

The paper shows decode attention is the DRAM-bandwidth-bound hot spot: every
step streams the whole KV cache from HBM at O(1) FLOP/byte, so batching does
not raise its arithmetic intensity. The TPU-native formulation tiles the KV
cache HBM->VMEM in ``block_s`` chunks along the sequence axis and keeps a
running (m, l, acc) online-softmax state in VMEM scratch — one pass over the
cache, no score matrix in HBM (FlashDecoding adapted to the TPU memory
hierarchy: HBM -> VMEM tiles -> MXU [G,hd]x[hd,BS] matmuls).

Grid: (batch, kv_heads, S/block_s); the sequence axis is the innermost,
sequential ("arbitrary") dimension so the scratch accumulators carry across
KV tiles. All G=H/K query heads of one KV head ride in a single [G, hd]
VMEM tile (GQA packing — the MXU tile is reused across the group).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_s: int, scale: float):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # [G, hd]
    k = k_ref[0, :, 0, :].astype(jnp.float32)             # [BS, hd]
    v = v_ref[0, :, 0, :].astype(jnp.float32)             # [BS, hd]
    length = len_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kv_ids = s_idx * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_s), 1)
    s = jnp.where(kv_ids < length, s, NEG_INF)            # [G, BS]

    m_prev = m_ref[...]                                   # [G, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                # [G, BS]
    alpha = jnp.exp(m_prev - m_new)                       # [G, 1]
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s_idx == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def gqa_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: jax.Array, *, block_s: int = 256,
                         interpret: bool = False) -> jax.Array:
    """q: [B,H,hd]; k/v: [B,S,K,hd]; lengths: [B] int32 -> [B,H,hd]."""
    B, H, hd = q.shape
    S, K = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    G = H // K
    bs = min(block_s, S)
    pad = (-S) % bs
    if pad:
        padkv = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k, v = padkv(k), padkv(v)
    Sp = S + pad
    qg = q.reshape(B, K, G, hd)
    lengths2d = lengths.reshape(B, 1).astype(jnp.int32)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=bs, scale=hd ** -0.5),
        grid=(B, K, Sp // bs),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, kh, s: (b, 0)),
            pl.BlockSpec((1, 1, G, hd), lambda b, kh, s: (b, kh, 0, 0)),
            pl.BlockSpec((1, bs, 1, hd), lambda b, kh, s: (b, s, kh, 0)),
            pl.BlockSpec((1, bs, 1, hd), lambda b, kh, s: (b, s, kh, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, kh, s: (b, kh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),     # running max m
            pltpu.VMEM((G, 1), jnp.float32),     # running denom l
            pltpu.VMEM((G, hd), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths2d, qg, k, v)
    return out.reshape(B, H, hd)
