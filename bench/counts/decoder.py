"""Operations and bytes the ``decoder`` family's algorithm needs, from a
config's sizes.

Counted from shapes alone, whatever implements them: padding rows,
padded table entries and the padded vocabulary are not work. ``s`` is the
dict of ``bench/models/decoder.py:sizes``. Every layer is the same dense
full-attention layer: one MLP block of ``d x f`` per layer, and each layer
reads the whole live context.
"""
from __future__ import annotations

from typing import Dict, Iterable


def layer_matmul_params(s: Dict) -> int:
    d, H, K, hd, f = s["d"], s["H"], s["K"], s["hd"], s["f"]
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    mlp = (3 if s["act"] == "swiglu" else 2) * d * f
    return attn + mlp


def param_count(s: Dict, vocab: int = None) -> int:
    """Every weight, with the embedding tables at ``vocab`` rows
    (default: the config's vocabulary)."""
    V = vocab or s["V"]
    norm = (2 if s["norm"] == "layernorm" else 1) * s["d"]
    n = s["L"] * (layer_matmul_params(s) + 2 * norm) + norm
    n += V * s["d"] * (1 if s["tie"] else 2)
    if s["pos"] == "learned":
        n += s["max_pos"] * s["d"]
    return n


def kv_bytes_per_token(s: Dict, itemsize: int = 2) -> int:
    return s["L"] * 2 * s["K"] * s["hd"] * itemsize


def attn_flops(s: Dict, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` keys, all
    layers."""
    return 4 * s["L"] * s["H"] * s["hd"] * keys


def decode_flops(s: Dict, context: int) -> int:
    """One output token whose query attends ``context`` keys (itself
    included): every matmul weight once, one row of logits."""
    return (2 * s["L"] * layer_matmul_params(s) + 2 * s["d"] * s["V"]
            + attn_flops(s, context))


def prefill_flops(s: Dict, n: int) -> int:
    """A prompt of ``n`` tokens: every token through every layer, causal
    attention, and the one row of logits that yields the first token."""
    return (2 * s["L"] * layer_matmul_params(s) * n + 2 * s["d"] * s["V"]
            + attn_flops(s, n * (n + 1) // 2))


def paged_attn_bytes(s: Dict, contexts: Iterable[int],
                     itemsize: int = 2) -> int:
    """HBM bytes one decode step's paged attention must move, all layers:
    the K and V rows of every live context, the queries and the outputs."""
    ctx = list(contexts)
    per_layer = (sum(ctx) * 2 * s["K"] * s["hd"] * itemsize
                 + len(ctx) * 2 * s["H"] * s["hd"] * itemsize)
    return s["L"] * per_layer


def paged_attn_flops(s: Dict, contexts: Iterable[int]) -> int:
    return attn_flops(s, sum(contexts))
