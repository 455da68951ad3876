"""Paged decode attention vs gathered oracle: the Pallas TPU kernel
(interpret mode) and the pure-JAX block-table reference the serving
engine's zero-copy path uses off-TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.paged_decode_attention import (
    paged_gqa_decode_attention, paged_gqa_decode_attention_jax)


def _flat(pool):
    """[NB, BS, K, hd] -> the kernels' lane-dense [NB, BS, K*hd] rows."""
    return pool.reshape(*pool.shape[:2], -1)


CASES = [
    # B, K, G, hd, BS, nb, NB, dtype, lengths (None: drawn in [1, BS*nb])
    (3, 2, 4, 64, 16, 5, 32, jnp.float32, None),
    (2, 1, 8, 128, 32, 3, 16, jnp.float32, None),
    (4, 4, 1, 64, 16, 4, 24, jnp.bfloat16, None),
    # a table far wider than the longest request, trash past each length
    (4, 2, 2, 64, 16, 32, 24, jnp.float32, (1, 17, 80, 33)),
    # opt-like widths (P = 8): a block, one and two compute blocks, and
    # one token into the second
    (4, 32, 1, 64, 16, 32, 64, jnp.bfloat16, (16, 128, 256, 129)),
    (3, 32, 1, 64, 16, 16, 48, jnp.float32, (128, 16, 200)),
    # internlm2-like widths (P = 16 in bf16, 8 in f32): padding rows
    # between live ones, and a batch whose only live row is the last
    (4, 8, 2, 128, 16, 16, 48, jnp.float32, (0, 200, 0, 37)),
    (4, 8, 2, 128, 16, 32, 48, jnp.bfloat16, (0, 0, 0, 300)),
    # a table narrower than P
    (2, 32, 1, 64, 16, 4, 16, jnp.bfloat16, (64, 5)),
]
IDS = ["-".join(map(str, c[:7])) + f"-{c[7].__name__}"
       + ("" if c[8] is None else "-" + "_".join(map(str, c[8])))
       for c in CASES]


def _case(seed, B, K, G, hd, BS, nb, NB, dtype, lengths):
    """Inputs and the gathered oracle's output. Drawn lengths come with a
    table of distinct blocks; given ones with distinct live blocks and
    the trash block (NB - 1) past each length, as the engine pads."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, K * G, hd), dtype)
    k_pool = jax.random.normal(ks[1], (NB, BS, K, hd), dtype)
    v_pool = jax.random.normal(ks[2], (NB, BS, K, hd), dtype)
    if lengths is None:
        perm = np.random.default_rng(seed + 1).permutation(NB)[:B * nb]
        table = perm.reshape(B, nb)
        lengths = np.random.default_rng(seed + 2).integers(1, BS * nb + 1,
                                                           B)
    else:
        live = [-(-n // BS) for n in lengths]
        ids = iter(np.random.default_rng(seed + 1).permutation(NB - 1))
        table = np.full((B, nb), NB - 1)
        for row, n in zip(table, live):
            row[:n] = [next(ids) for _ in range(n)]
    table = jnp.asarray(table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    kc = k_pool[table].reshape(B, nb * BS, K, hd)
    vc = v_pool[table].reshape(B, nb * BS, K, hd)
    exp = ref.gqa_decode_attention_ref(q, kc, vc, lengths)
    # padding rows (length 0) output zeros
    exp = jnp.where(lengths[:, None, None] > 0, exp, 0.0)
    return (q, _flat(k_pool), _flat(v_pool), table, lengths), exp


@pytest.mark.parametrize("B,K,G,hd,BS,nb,NB,dtype,lengths", CASES, ids=IDS)
def test_paged_decode_vs_gathered_oracle(B, K, G, hd, BS, nb, NB, dtype,
                                         lengths):
    args, exp = _case(0, B, K, G, hd, BS, nb, NB, dtype, lengths)
    out = paged_gqa_decode_attention(*args, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(exp),
                               atol=tol, rtol=1e-2)
    pad = np.asarray(args[4]) == 0
    assert np.all(np.asarray(out)[pad] == 0.0)


@pytest.mark.parametrize("B,K,G,hd,BS,nb,NB,dtype,lengths", CASES, ids=IDS)
def test_paged_jax_path_vs_gathered_oracle(B, K, G, hd, BS, nb, NB, dtype,
                                           lengths):
    """The block-scan pure-JAX path (engine's zero-copy decode on CPU)
    must match the naive gathered oracle bit-for-tolerance."""
    args, exp = _case(3, B, K, G, hd, BS, nb, NB, dtype, lengths)
    out = paged_gqa_decode_attention_jax(*args)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(exp),
                               atol=tol, rtol=1e-2)


def test_paged_jax_path_matches_pallas_interpret():
    """Both backends of the dispatcher agree on the same inputs."""
    B, K, G, hd, BS, nb, NB = 2, 2, 2, 64, 16, 3, 16
    H = K * G
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k_pool = jax.random.normal(ks[1], (NB, BS, K, hd), jnp.float32)
    v_pool = jax.random.normal(ks[2], (NB, BS, K, hd), jnp.float32)
    perm = np.random.default_rng(7).permutation(NB)[:B * nb].reshape(B, nb)
    table = jnp.asarray(perm, jnp.int32)
    lengths = jnp.asarray([BS * nb, 17], jnp.int32)
    a = paged_gqa_decode_attention(q, _flat(k_pool), _flat(v_pool),
                                   table, lengths, interpret=True)
    b = paged_gqa_decode_attention_jax(q, _flat(k_pool), _flat(v_pool),
                                       table, lengths)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                               rtol=1e-5)


def test_paged_jax_path_zero_length_padding_rows():
    """Batch-padding rows (length 0, trash-block table) output zeros —
    the engine relies on this to bucket batch sizes safely."""
    B, K, G, hd, BS, nb, NB = 3, 2, 2, 32, 8, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, K * G, hd), jnp.float32)
    k_pool = jax.random.normal(ks[1], (NB, BS, K, hd), jnp.float32)
    v_pool = jax.random.normal(ks[2], (NB, BS, K, hd), jnp.float32)
    table = jnp.asarray([[0, 1], [2, 3], [7, 7]], jnp.int32)
    lengths = jnp.asarray([10, 4, 0], jnp.int32)
    out = np.asarray(paged_gqa_decode_attention_jax(
        q, _flat(k_pool), _flat(v_pool), table, lengths))
    assert np.all(out[2] == 0.0)
    assert np.all(np.isfinite(out))


def test_paged_result_independent_of_block_placement():
    """The same logical cache in different physical blocks gives identical
    results — the block table fully abstracts placement."""
    B, K, G, hd, BS, nb, NB = 2, 2, 2, 64, 16, 3, 16
    H = K * G
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, nb * BS, K, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, nb * BS, K, hd), jnp.float32)
    lengths = jnp.asarray([nb * BS, 20], jnp.int32)
    outs = []
    for seed in (0, 1):
        perm = np.random.default_rng(seed).permutation(NB)[:B * nb]
        table = jnp.asarray(perm.reshape(B, nb), jnp.int32)
        k_pool = jnp.zeros((NB, BS, K, hd))
        v_pool = jnp.zeros((NB, BS, K, hd))
        k_pool = k_pool.at[table.reshape(-1)].set(
            kc.reshape(B * nb, BS, K, hd))
        v_pool = v_pool.at[table.reshape(-1)].set(
            vc.reshape(B * nb, BS, K, hd))
        outs.append(np.asarray(paged_gqa_decode_attention(
            q, _flat(k_pool), _flat(v_pool), table, lengths,
            interpret=True)))
    np.testing.assert_array_equal(outs[0], outs[1])
