"""Operation and byte counts against figures worked out by hand, and the
peaks table."""
import json
import math

import pytest

from bench.counts import decoder as counts
from bench.models import decoder
from bench.programs import decoder as adapter
from bench.tests.tiny import BENCH


def sizes(name):
    return decoder.sizes(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))


def test_opt_kv_bytes_per_token():
    # 24 layers x (K + V) x 32 heads x 64 x 2 bytes
    assert counts.kv_bytes_per_token(sizes("opt-1.3b")) == 196_608


def test_internlm2_kv_bytes_per_token():
    # 24 layers x (K + V) x 8 heads x 128 x 2 bytes
    assert counts.kv_bytes_per_token(sizes("internlm2-1.8b")) == 98_304


def test_opt_param_count_by_hand():
    # token table at the padded vocabulary 50432 (103,284,736; the head
    # is tied to it), positions 2048 x 2048 (4,194,304), 24 layers of
    # 4 x 2048^2 + 2 x 2048 x 8192 matmul weights and two LayerNorms
    # (24 x 50,339,840), a final LayerNorm (4,096)
    s = sizes("opt-1.3b")
    assert counts.param_count(s, vocab=50432) == 1_315_639_296
    # the served params in bf16: 2,631,278,592 bytes on a v5e
    assert 2 * counts.param_count(s, vocab=50432) == 2_631_278_592


@pytest.mark.parametrize("name", ["opt-1.3b", "internlm2-1.8b"])
def test_param_count_matches_the_program(name):
    from repro.models.model import abstract_params
    s = sizes(name)
    cfg = adapter.arch_config(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()), s)
    leaves = [p for p in __import__("jax").tree.leaves(
        abstract_params(cfg), is_leaf=lambda x: hasattr(x, "logical"))]
    assert sum(math.prod(p.shape) for p in leaves) \
        == counts.param_count(s, vocab=cfg.padded_vocab)


def test_opt_decode_flops_by_hand():
    s = sizes("opt-1.3b")
    matmul = 2 * 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192)   # 2,415,919,104
    logits = 2 * 2048 * 50272                              # 205,914,112
    attn = 4 * 24 * 32 * 64 * 540                          # 106,168,320
    assert counts.decode_flops(s, 540) == matmul + logits + attn \
        == 2_728_001_536


def test_opt_prefill_flops_by_hand():
    s = sizes("opt-1.3b")
    n = 161
    want = (2 * 24 * 50_331_648 * n + 2 * 2048 * 50272
            + 4 * 24 * 32 * 64 * n * (n + 1) // 2)
    assert counts.prefill_flops(s, n) == want


def test_opt_paged_attention_bytes_by_hand():
    # 64 live contexts of 540 tokens: 24 layers x (64 x 540 x K and V rows
    # of 4 KiB + 64 x (q + out) rows of 4 KiB) = 6,807,355,392 bytes
    s = sizes("opt-1.3b")
    assert counts.paged_attn_bytes(s, [540] * 64) == 6_807_355_392
    # G = 1: one operation per byte, far below the v5e ridge
    assert counts.paged_attn_flops(s, [540] * 64) \
        < counts.paged_attn_bytes(s, [540] * 64) * 1.01


def test_peaks_table():
    t = json.loads((BENCH / "peaks.json").read_text())
    v5e = t["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in t["source"]
