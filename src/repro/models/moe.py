"""Mixture-of-Experts FFN with explicit expert parallelism.

Experts are sharded over the data axes (expert parallelism), d_ff over the
model axis (tensor parallelism). The block runs inside ``jax.shard_map`` so
dispatch is plain local scatter/gather and the communication pattern is the
GShard one, written explicitly:

    local top-k route -> capacity-bucketed dispatch buffer [E, C, d]
    -> all_to_all over the expert axis -> per-device expert FFN
    -> psum over the model axis (d_ff partial sums)
    -> all_to_all back -> weighted combine

This keeps the HLO census honest: expert FLOPs are the real active-expert
FLOPs (no one-hot dispatch einsums) and collective bytes are the actual
all-to-all payloads — exactly the quantities the paper's roofline argument
is about.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.params import pspec
from repro.models.layers import mlp_abstract, mlp_apply
from repro.sharding import (BATCH, D_FF, D_MODEL, EXPERTS, SEQ,
                            ShardingRules, constrain)


def moe_abstract(cfg: ArchConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    # logical axes deliberately match the shard_map in_specs of moe_ffn:
    # experts over the data axes (expert parallel), d_ff over the model
    # axis (tensor parallel), d_model replicated.
    p = {
        "router": pspec((d, e), (None, None), "float32"),
        "w1": pspec((e, d, f), (EXPERTS, None, D_FF), cfg.dtype, fan_in=d),
        "w2": pspec((e, f, d), (EXPERTS, D_FF, None), cfg.dtype, fan_in=f),
    }
    if cfg.act == "swiglu":
        p["w3"] = pspec((e, d, f), (EXPERTS, None, D_FF), cfg.dtype, fan_in=d)
    if cfg.moe.dense_residual:
        p["dense"] = mlp_abstract(cfg)
    return p


def _expert_ffn(h, w1, w2, w3, act: str):
    """h: [E_loc, T, d]; w*: [E_loc, d, f] / [E_loc, f, d]."""
    with jax.named_scope("expert_ffn"):
        u = jnp.einsum("etd,edf->etf", h, w1)
        if act == "swiglu":
            u = jax.nn.silu(u.astype(jnp.float32)).astype(h.dtype) * \
                jnp.einsum("etd,edf->etf", h, w3)
        elif act == "gelu":
            u = jax.nn.gelu(u.astype(jnp.float32)).astype(h.dtype)
        else:
            u = jnp.maximum(u, 0)
        return jnp.einsum("etf,efd->etd", u, w2)


def _route(x, router, top_k: int):
    """x: [T,d] -> (probs [T,E] f32, topk weights [T,k], topk idx [T,k])."""
    with jax.named_scope("router"):
        logits = (x.astype(jnp.float32) @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, top_k)
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    return probs, w.astype(x.dtype), idx


def _dispatch_indices(idx, E: int, C: int):
    """idx: [T,k] expert ids -> (slot [T,k] in [0,E*C), keep [T,k])."""
    T, k = idx.shape
    flat = idx.reshape(-1)                               # [T*k]
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)    # [T*k, E]
    pos = jnp.cumsum(onehot, axis=0) - 1                 # rank within expert
    mypos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    keep = mypos < C
    slot = jnp.where(keep, flat * C + mypos, 0)
    return slot.reshape(T, k), keep.reshape(T, k)


MOE_TOKEN_CHUNK = 8192   # max local tokens dispatched per inner step


def moe_ffn(p, x: jax.Array, cfg: ArchConfig, rules: ShardingRules,
            *, capacity_factor: float) -> Tuple[jax.Array, jax.Array]:
    """x: [B,S,d] (or [B,1,d] decode). Returns (out, aux_loss)."""
    B, S, d = x.shape
    moe = cfg.moe
    E, k = moe.num_experts, moe.top_k
    ea = rules.batch_axes            # expert-parallel axes, e.g. ("data",)
    A = rules.axis_size(ea)          # number of expert shards
    ma = rules.model_axis
    M = rules.mesh.shape[ma]
    e_shard = A if E % A == 0 else 1           # fall back to replicated experts
    f_shard = M if cfg.d_ff % M == 0 else 1

    xs = x.reshape(B * S, d)
    tokens_sharded = (B * S) % A == 0 and (B * S) >= A
    T_local = (B * S) // A if tokens_sharded else B * S
    # long sequences are dispatched in chunks so the [E, C, d] buffer stays
    # bounded (one chunk in flight; lax.scan over chunks inside shard_map)
    n_chunks = 1
    while T_local // n_chunks > MOE_TOKEN_CHUNK and T_local % (n_chunks * 2) == 0:
        n_chunks *= 2
    T_chunk = T_local // n_chunks
    C = max(1, math.ceil(capacity_factor * k * T_chunk / E))

    batch_spec = ea if tokens_sharded else None
    w_e = ea if e_shard > 1 else None
    w_f = ma if f_shard > 1 else None

    def local_moe(xt, router, w1, w2, w3):
        # xt: [T,d] local tokens; w1: [E/e_shard, d, f/f_shard]
        if n_chunks > 1:
            chunks = xt.reshape(n_chunks, T_chunk, d)

            def chunk_body(aux_sum, xc):
                out_c, aux_c = _one_chunk(xc, router, w1, w2, w3)
                return aux_sum + aux_c, out_c
            aux, outs = jax.lax.scan(chunk_body,
                                     jnp.zeros((), jnp.float32), chunks)
            return outs.reshape(n_chunks * T_chunk, d), aux / n_chunks
        return _one_chunk(xt, router, w1, w2, w3)

    def _one_chunk(xt, router, w1, w2, w3):
        T = xt.shape[0]
        probs, wts, idx = _route(xt, router, k)
        slot, keep = _dispatch_indices(idx, E, C)
        buf = jnp.zeros((E * C, d), xt.dtype)
        src = jnp.repeat(jnp.arange(T)[:, None], k, 1)
        with jax.named_scope("moe_dispatch"):
            buf = buf.at[slot.reshape(-1)].add(
                (xt[src.reshape(-1)] * keep.reshape(-1)[:, None].astype(xt.dtype)))
            buf = buf.reshape(E, C, d)
        if e_shard > 1:
            with jax.named_scope("moe_all_to_all"):
                # split0/concat0 is self-inverse: its VJP is itself, so the
                # same exchange works under grad without axis gymnastics
                b = buf.reshape(A, E // A, C, d)
                b = jax.lax.all_to_all(b, ea, split_axis=0, concat_axis=0)
                h = jnp.moveaxis(b, 1, 0).reshape(E // A, A * C, d)
        else:
            h = buf
        y = _expert_ffn(h, w1, w2, w3 if w3 is not None else None, cfg.act)
        if f_shard > 1:
            with jax.named_scope("moe_combine_psum"):
                y = jax.lax.psum(y, ma)
        if e_shard > 1:
            with jax.named_scope("moe_all_to_all_back"):
                yb = jnp.moveaxis(y.reshape(E // A, A, C, d), 1, 0)
                yb = jax.lax.all_to_all(yb, ea, split_axis=0, concat_axis=0)
                y = yb.reshape(E * C, d)
        else:
            y = y.reshape(E * C, d)
        with jax.named_scope("moe_gather"):
            picked = y[slot.reshape(-1)].reshape(T, k, d)
            picked = picked * (wts * keep.astype(wts.dtype))[..., None]
            out = jnp.sum(picked.astype(jnp.float32), axis=1).astype(xt.dtype)
        # load-balance aux loss (GShard/Switch): E * sum_e f_e * p_e
        assign = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
        f_e = jnp.mean(assign, axis=0)
        p_e = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(f_e * p_e)
        if batch_spec is not None:
            aux = jax.lax.pmean(aux, ea)
        return out, aux

    in_specs = (P(batch_spec, None),
                P(None, None),
                P(w_e, None, w_f), P(w_e, w_f, None),
                P(w_e, None, w_f) if cfg.act == "swiglu" else P())
    out_specs = (P(batch_spec, None), P())
    w3 = p.get("w3", jnp.zeros((), cfg.activation_dtype))
    fn = jax.shard_map(local_moe, mesh=rules.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out, aux = fn(xs, p["router"], p["w1"], p["w2"], w3)
    out = out.reshape(B, S, d)
    out = constrain(out, rules, (BATCH, SEQ, D_MODEL))
    if moe.dense_residual:
        out = out + mlp_apply(p["dense"], x, cfg, rules)
    return out, aux * moe.aux_loss_weight
