"""PAGED GQA decode attention: Pallas TPU kernel + block-table JAX path.

vLLM's PagedAttention follows KV block pointers inside the CUDA kernel;
the TPU-native equivalent drives the HBM->VMEM tile fetch through a
*block table* consumed by the BlockSpec index_map (scalar-prefetch
operand). The physical KV pool never gets materialized per request — each
grid step pulls exactly one request's next block from wherever it lives
in the pool.

Layout:
    k_pool/v_pool: [NB, BS, K*hd]    physical blocks, one token per row
    block_table:   [B, nb_max] int32 physical block id per logical block
    lengths:       [B] int32         valid tokens per request

Token rows are lane-dense: head ``kh`` occupies lanes
``[kh*hd, (kh+1)*hd)``. A ``[.., K, hd]`` pool with hd=64 would be padded
to 128 lanes in the layout a Mosaic kernel reads, so the TPU compiler
would copy the whole pool into that layout on every call; the flat row
is read as stored.

Grid: (B, nb_max) with the block axis innermost/sequential; each step
fetches one whole ``[BS, K*hd]`` block (every KV head). Per query group
g, the scores of all heads come from one MXU product of the block with a
block-diagonal query matrix ``Q_g[kh, :] = q_g * [lane in head kh]``
(built once per request in VMEM). The ``[BS, K]`` scores are spread to
the heads' lanes by a product with the 0/1 head-membership matrix
``E [K, K*hd]``, so the online softmax state and the PV update stay
lane-dense ``[1, K*hd]`` elementwise work.

``paged_gqa_decode_attention_jax`` is the same data flow expressed at the
XLA level (a ``lax.scan`` over logical blocks with a per-block take +
online softmax): the serving engine's zero-copy decode path on CPU,
where Pallas-TPU is unavailable. Per scan step only one ``[B, BS, K*hd]``
tile of the pool is gathered, so — like the kernel — it never materializes
a dense ``[B, S_pad, K, hd]`` copy of the cache. ``paged_decode_attention``
dispatches between the two by backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _spread(x, e):
    """``x [R, K] f32 -> [R, K*hd]``: each head's value on its hd lanes,
    as a product with the 0/1 matrix ``e [K, K*hd]`` (bf16). Three bf16
    terms of x carry all 24 bits of its f32 mantissa, so the spread is
    exact to f32 rounding in three single-pass MXU products."""
    out = None
    for _ in range(3):
        t = x.astype(jnp.bfloat16)
        d = jax.lax.dot_general(t, e, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        out = d if out is None else out + d
        x = x - t.astype(jnp.float32)
    return out


def _paged_kernel(table_ref, len_ref, e_ref, q_ref, k_ref, v_ref, o_ref,
                  qm_ref, m_ref, l_ref, acc_ref, *, block_s: int,
                  groups: int, scale: float):
    b, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        e = e_ref[...].astype(jnp.float32)
        for g in range(groups):
            qm_ref[g] = (e * q_ref[0, pl.ds(g, 1), :].astype(jnp.float32)
                         ).astype(qm_ref.dtype)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    # f32 pools (tests) need the multi-pass f32 product; bf16 is one pass
    precision = (None if k_ref.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)

    # tokens covered by this logical block: [i*BS, i*BS+BS)
    @pl.when(i * block_s < length)
    def _compute():
        e = e_ref[...]
        v = v_ref[0].astype(jnp.float32)                   # [BS, K*hd]
        ids = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (block_s, 1), 0)
        for g in range(groups):
            # scores of every head at once: the block times Q_g, in the
            # pool's dtype (bf16 products are exact in the f32 sum)
            s = jax.lax.dot_general(
                k_ref[0], qm_ref[g], (((1,), (1,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32) * scale  # [BS, K]
            # online softmax with every head's state on its own lanes
            s = jnp.where(ids < length, _spread(s, e), NEG_INF)
            m_prev = m_ref[pl.ds(g, 1), :]                 # [1, K*hd]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[pl.ds(g, 1), :] = (alpha * l_ref[pl.ds(g, 1), :]
                                     + jnp.sum(p, axis=0, keepdims=True))
            acc_ref[pl.ds(g, 1), :] = (alpha * acc_ref[pl.ds(g, 1), :]
                                       + jnp.sum(p * v, axis=0,
                                                 keepdims=True))
            m_ref[pl.ds(g, 1), :] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gqa_decode_attention(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, block_table: jax.Array,
                               lengths: jax.Array, *,
                               interpret: bool = False) -> jax.Array:
    """q: [B,H,hd]; k/v_pool: [NB,BS,K*hd]; block_table: [B,nb] int32;
    lengths: [B] int32 -> [B,H,hd]."""
    B, H, hd = q.shape
    NB, BS, W = k_pool.shape
    K = W // hd
    nb = block_table.shape[1]
    G = H // K
    # head h = kh*G + g; row g of qg holds group g of every KV head, laid
    # out like a pool row
    qg = q.reshape(B, K, G, hd).transpose(0, 2, 1, 3).reshape(B, G, W)
    e = (jnp.arange(W)[None, :] // hd == jnp.arange(K)[:, None]
         ).astype(jnp.bfloat16)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((K, W), lambda b, i, tbl, ln: (0, 0)),
            pl.BlockSpec((1, G, W), lambda b, i, tbl, ln: (b, 0, 0)),
            pl.BlockSpec((1, BS, W), lambda b, i, tbl, ln: (tbl[b, i], 0, 0)),
            pl.BlockSpec((1, BS, W), lambda b, i, tbl, ln: (tbl[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, W), lambda b, i, tbl, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, K, W), k_pool.dtype),
            pltpu.VMEM((G, W), jnp.float32),
            pltpu.VMEM((G, W), jnp.float32),
            pltpu.VMEM((G, W), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_s=BS, groups=G,
                          scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, W), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32), e, qg,
      k_pool, v_pool)
    return out.reshape(B, G, K, hd).transpose(0, 2, 1, 3).reshape(B, H, hd)


def paged_gqa_decode_attention_jax(q: jax.Array, k_pool: jax.Array,
                                   v_pool: jax.Array, block_table: jax.Array,
                                   lengths: jax.Array) -> jax.Array:
    """Block-table decode attention in pure JAX (no dense gather).

    Same contract as :func:`paged_gqa_decode_attention` — q: [B,H,hd];
    k/v_pool: [NB,BS,K*hd]; block_table: [B,nb] int32; lengths: [B] int32
    -> [B,H,hd] — but implemented as a ``lax.scan`` over logical block
    index with an online-softmax carry. Each step gathers exactly one
    [B, BS, K*hd] tile from the pool, so peak extra memory is one tile
    per step instead of the full [B, nb*BS, K, hd] logical view.

    Rows with length 0 (batch padding) produce zeros. Table entries past a
    request's last block may point anywhere valid (e.g. a trash block):
    their scores are fully masked by ``lengths``.
    """
    B, H, hd = q.shape
    NB, BS, W = k_pool.shape
    K = W // hd
    nb = block_table.shape[1]
    G = H // K
    scale = hd ** -0.5
    qg = q.reshape(B, K, G, hd).astype(jnp.float32)
    tbl = block_table.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    def tile(pool, i):
        t = jnp.take(pool, tbl[:, i], axis=0)                 # [B,BS,W]
        return t.reshape(B, BS, K, hd).astype(jnp.float32)

    def body(carry, i):
        m_run, l_run, acc = carry
        kb, vb = tile(k_pool, i), tile(v_pool, i)
        s = jnp.einsum("bkgh,bskh->bkgs", qg, kb) * scale     # [B,K,G,BS]
        ids = i * BS + jnp.arange(BS)
        valid = ids[None, :] < lens[:, None]                  # [B,BS]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        # explicit zero (not just exp underflow) so fully-masked rows —
        # batch padding with length 0, where s == m_new == NEG_INF and
        # exp(s - m_new) would be 1 — contribute nothing and output zeros.
        p = jnp.where(valid[:, None, None, :],
                      jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m_run - m_new)
        l_new = alpha * l_run + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgs,bskh->bkgh", p, vb)
        return (m_new, l_new, acc), None

    init = (jnp.full((B, K, G), NEG_INF, jnp.float32),
            jnp.zeros((B, K, G), jnp.float32),
            jnp.zeros((B, K, G, hd), jnp.float32))
    (_, l_f, acc), _ = jax.lax.scan(body, init, jnp.arange(nb))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return out.reshape(B, H, hd).astype(q.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           lengths: jax.Array) -> jax.Array:
    """Backend dispatch: Pallas kernel on TPU, block-scan JAX elsewhere."""
    if jax.default_backend() == "tpu":
        return paged_gqa_decode_attention(q, k_pool, v_pool, block_table,
                                          lengths)
    return paged_gqa_decode_attention_jax(q, k_pool, v_pool, block_table,
                                          lengths)
