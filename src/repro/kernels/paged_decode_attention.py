"""PAGED GQA decode attention: Pallas TPU kernel + block-table JAX path.

vLLM's PagedAttention follows KV block pointers inside the CUDA kernel;
the TPU-native equivalent copies pool blocks HBM->VMEM at addresses read
from a *block table* in SMEM (scalar-prefetch operand). The physical KV
pool never gets materialized per request, and only the blocks that hold
a request's tokens are read.

Layout:
    k_pool/v_pool: [NB, BS, K*hd]    physical blocks, one token per row
    block_table:   [B, nb_max] int32 physical block id per logical block
    lengths:       [B] int32         valid tokens per request

Token rows are lane-dense: head ``kh`` occupies lanes
``[kh*hd, (kh+1)*hd)``. A ``[.., K, hd]`` pool with hd=64 would be padded
to 128 lanes in the layout a Mosaic kernel reads, so the TPU compiler
would copy the whole pool into that layout on every call; the flat row
is read as stored.

Grid: (B,), one step per request, run in order. The pools stay in HBM;
a step loops over the request's *compute blocks* of P pool blocks (P
from the shapes: about 1 MiB of K+V per compute block, at most nb), and
copies each into one of two VMEM buffers with one DMA per live pool
block: blocks at or past ``ceil(length / BS)`` are never copied, and a
row with ``length == 0`` (batch padding) copies and computes nothing and
outputs zeros. The copies are double-buffered: while compute block
``(b, c)`` is computed, those of the next live one are in flight, and
after a request's last compute block that is the first compute block of
the next request with ``length > 0``, so only the kernel's first copy is
exposed. The grid's cost follows the live blocks, not the table width.

Per compute block of R = P*BS rows, the scores of every (group, head)
pair come from one MXU product ``Q [G*K, K*hd] x block^T -> [G*K, R]``,
where ``Q[g*K + kh, :] = q_g * [lane in head kh]`` (built once per
request from the 0/1 head-membership matrix ``E [K, K*hd]``). The online
softmax runs on that ``[G*K, R]`` f32 tile, and the PV update is the
product ``p [G*K, R] x V [R, K*hd]`` (exact in f32: three bf16 terms of
p against a bf16 pool, HIGHEST against an f32 one), accumulated in f32;
``E`` keeps each head's own lanes of its row when the request finishes.
No ``[R, K*hd]`` f32 elementwise work remains.

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

# K+V bytes one compute block copies: enough that its copies outlast
# the block's fixed cost, few enough that two buffers of each fit VMEM
# (on v5e, 0.5 MiB ran 15-27% slower and 2 MiB no faster)
COMPUTE_BLOCK_BYTES = 1 << 20


def _compute_block_pages(block_s: int, width: int, itemsize: int,
                         nb: int) -> int:
    """P: pool blocks per compute block for ``[.., block_s, width]``
    pools of ``itemsize`` bytes and a table ``nb`` blocks wide."""
    per_block = 2 * block_s * width * itemsize
    return max(1, min(nb, COMPUTE_BLOCK_BYTES // per_block))


def _dot_f32(x, y):
    """``x [M, R] f32 @ y [R, N]`` to f32 accuracy. Against bf16 ``y``,
    three bf16 terms of x carry all 24 bits of its mantissa: three
    single-pass MXU products. Against f32 ``y``, one HIGHEST product."""
    if y.dtype != jnp.bfloat16:
        return jax.lax.dot(x, y.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    out = None
    for _ in range(3):
        t = x.astype(jnp.bfloat16)
        d = jax.lax.dot(t, y, preferred_element_type=jnp.float32)
        out = d if out is None else out + d
        x = x - t.astype(jnp.float32)
    return out


def _paged_kernel(table_ref, len_ref, e_ref, q_ref, k_hbm, v_hbm, o_ref,
                  qm_ref, acc_ref, k_buf, v_buf, sems, slot_ref, *,
                  batch: int, nb: int, block_s: int, pages: int,
                  groups: int, scale: float):
    b = pl.program_id(0)
    rows = pages * block_s
    heads = e_ref.shape[0]

    def live_blocks(r):
        return jnp.minimum(pl.cdiv(len_ref[r], block_s), nb)

    def next_live(r):
        """The first request at or after ``r`` with tokens (or batch)."""
        return jax.lax.while_loop(
            lambda r: (r < batch)
            & (len_ref[jnp.minimum(r, batch - 1)] <= 0),
            lambda r: r + 1, r)

    def copies(r, c, slot, act):
        """``act`` (start or wait) on the K and V copies of the live pool
        blocks of compute block ``c`` of request ``r``, into ``slot``."""
        base = r * nb + c * pages

        def one(j, carry):
            blk = table_ref[base + j]
            dst = pl.ds(pl.multiple_of(j * block_s, block_s), block_s)
            for kv, (src, buf) in enumerate(((k_hbm, k_buf),
                                             (v_hbm, v_buf))):
                act(pltpu.make_async_copy(src.at[blk], buf.at[slot, dst],
                                          sems.at[kv, slot]))
            return carry

        n = jnp.minimum(pages, live_blocks(r) - c * pages)
        jax.lax.fori_loop(0, n, one, 0)

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    @pl.when(b == 0)
    def _first_copies():
        # rows past a length meet zero probabilities; uninitialized VMEM
        # could hold NaN, which a zero does not cancel
        v_buf[...] = jnp.zeros_like(v_buf)
        first = next_live(0)

        @pl.when(first < batch)
        def _():
            copies(first, 0, 0, start)

        slot_ref[0] = 0

    length = len_ref[b]

    @pl.when(length > 0)
    def _attend():
        e = e_ref[...].astype(jnp.float32)                 # [K, W]
        q = q_ref[0].astype(jnp.float32)                   # [G, W]
        qm_ref[...] = jnp.concatenate(
            [e * q[g:g + 1] for g in range(groups)], axis=0
        ).astype(qm_ref.dtype)                             # [G*K, W]
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # f32 pools (tests) need the multi-pass f32 product; bf16 is one
        precision = (None if k_buf.dtype == jnp.bfloat16
                     else jax.lax.Precision.HIGHEST)
        n_compute = pl.cdiv(live_blocks(b), pages)
        after = next_live(b + 1)

        def compute_block(c, carry):
            m_prev, l_prev, slot = carry
            last = c + 1 == n_compute
            nxt_r = jnp.where(last, after, b)

            @pl.when(nxt_r < batch)
            def _prefetch():
                copies(nxt_r, jnp.where(last, 0, c + 1), 1 - slot, start)

            copies(b, c, slot, wait)
            # scores of every (group, head) pair: [G*K, R], in the
            # pool's dtype (bf16 products are exact in the f32 sum)
            s = jax.lax.dot_general(
                qm_ref[...], k_buf[slot], (((1,), (1,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32) * scale
            ids = c * rows + jax.lax.broadcasted_iota(
                jnp.int32, (1, rows), 1)
            s = jnp.where(ids < length, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            acc_ref[...] = alpha * acc_ref[...] + _dot_f32(p, v_buf[slot])
            return (m_new, alpha * l_prev + jnp.sum(p, axis=1, keepdims=True),
                    1 - slot)

        init = (jnp.full((groups * heads, 1), NEG_INF, jnp.float32),
                jnp.zeros((groups * heads, 1), jnp.float32), slot_ref[0])
        _, l, slot = jax.lax.fori_loop(0, n_compute, compute_block, init)
        slot_ref[0] = slot
        # row g*K+kh holds head kh's PV over every lane: keep its own
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        o_ref[0] = jnp.concatenate(
            [jnp.sum(out[g * heads:(g + 1) * heads] * e, axis=0,
                     keepdims=True) for g in range(groups)],
            axis=0).astype(o_ref.dtype)

    @pl.when(length <= 0)
    def _padding_row():
        o_ref[...] = jnp.zeros_like(o_ref)


# the TPU interpreter with each DMA done at its wait and fresh scratch
# NaN: a missing wait or a live row left uncopied shows in the output
INTERPRET = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                  uninitialized_memory="nan")


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
    P = _compute_block_pages(BS, W, k_pool.dtype.itemsize, nb)
    # head h = kh*G + g; row g of qg holds group g of every KV head, laid
    # out like a pool row
    qg = q.reshape(B, K, G, hd).transpose(0, 2, 1, 3).reshape(B, G, W)
    e = (jnp.arange(W)[None, :] // hd == jnp.arange(K)[:, None]
         ).astype(jnp.bfloat16)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((K, W), lambda b, tbl, ln: (0, 0)),
            pl.BlockSpec((1, G, W), lambda b, tbl, ln: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, G, W), lambda b, tbl, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G * K, W), k_pool.dtype),
            pltpu.VMEM((G * K, W), jnp.float32),
            pltpu.VMEM((2, P * BS, W), k_pool.dtype),
            pltpu.VMEM((2, P * BS, W), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, batch=B, nb=nb, block_s=BS,
                          pages=P, groups=G, scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, W), q.dtype),
        # the copies run ahead across requests: the grid is one sequence
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=INTERPRET if interpret else False,
        name="paged_decode_attention",
    )(block_table.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      e, qg, k_pool, v_pool)
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
