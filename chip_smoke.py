#!/usr/bin/env python3
"""Chip smoke: serve opt-1.3b through the engine's main path on a TPU.

    python chip_smoke.py [--seed N]      # one chip
    python chip_smoke.py --chips 4       # four one-chip replicas

One chip: opt-1.3b at its published widths and depth (24 layers, d_model
2048, 32 heads, d_ff 8192, vocab 50272, bf16) with random weights drawn
from ``--seed``, and the KV pool sized from the device's memory
(``launch/serve.py:kv_pool_tokens``). It

1. runs the Pallas paged decode kernel and the block-scan JAX path on
   the same full-width pool and block table and compares them;
2. serves 16 ShareGPT-like requests through ``ServingAPI`` over a
   ``ContinuousBatchingEngine`` (paged pool, scheduler, zero-copy decode
   step) twice: a cold run that compiles, then a warm run;
3. checks that the compiled decode step contains the kernel.

Four chips (``--chips 4``): the same model and workload, greedy, through
four one-chip replicas from ``ReplicatedCluster.sliced`` behind the
router, compared token for token with one bare engine, both at
``max_batch`` 1. Depth is cut to 4 layers there (widths unchanged) to
bound compile time; placement and token equality do not depend on depth.

Throughput and latency printed here are smoke numbers, not benchmark
numbers. The last line of stdout is ``{"ok": true, "device": {...}}``;
without a TPU, or when any check fails, the script exits nonzero and
prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# kernel vs JAX path on bf16 pools: the tolerance of the bf16 cases in
# tests/test_paged_kernel.py
ATOL, RTOL = 3e-2, 1e-2
N_REQUESTS = 16


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str):
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def workload(cfg, seed: int):
    """16 ShareGPT-like requests, all due at t=0. The narrow lengths
    (prompts of at most 64 tokens: one prefill bucket; about 32 output
    tokens) bound the number of compiled buckets."""
    from repro.serving import sharegpt_like
    return sharegpt_like(N_REQUESTS, cfg.vocab_size, seed=seed, mean_in=48,
                         mean_out=32, sigma=0.2, max_len=128)


def check_outputs(cfg, reqs):
    for r in reqs:
        toks = r.state.output_tokens
        if r.state.finish_reason != "length" \
                or len(toks) != r.sampling.max_new_tokens:
            fail(f"request {r.req_id} finished {r.state.finish_reason!r} "
                 f"with {len(toks)}/{r.sampling.max_new_tokens} tokens")
        bad = [t for t in toks if not 0 <= t < cfg.vocab_size]
        if bad:
            fail(f"request {r.req_id}: tokens outside the vocabulary: {bad}")


def kernel_check(cfg, key) -> float:
    """Max |kernel - JAX path| over one full-width, layer-flattened pool
    of 1024 blocks per layer, for 16 requests of up to 32 blocks."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_decode_attention import (
        paged_gqa_decode_attention, paged_gqa_decode_attention_jax)
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    n_blocks, batch, nb, block_size = cfg.n_layers * 1024, 16, 32, 16
    kq, kk, kv, kt, kl = jax.random.split(key, 5)
    q = jax.random.normal(kq, (batch, H, hd), jnp.bfloat16)
    shape = (n_blocks, block_size, K * hd)
    k_pool = jax.random.normal(kk, shape, jnp.bfloat16)
    v_pool = jax.random.normal(kv, shape, jnp.bfloat16)
    table = jax.random.choice(kt, n_blocks, (batch * nb,),
                              replace=False).reshape(batch, nb)
    lengths = jax.random.randint(kl, (batch,), 1, nb * block_size + 1)
    out = paged_gqa_decode_attention(q, k_pool, v_pool, table, lengths)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_gqa_decode_attention_jax)(q, k_pool, v_pool,
                                                      table, lengths)
    out, ref = (jnp.asarray(x, jnp.float32) for x in (out, ref))
    err = jnp.abs(out - ref)
    if not bool(jnp.all(jnp.isfinite(out))):
        fail("kernel output is not finite")
    diff = float(jnp.max(err))
    log(f"kernel vs JAX path: B={batch} nb={nb} pool={shape} "
        f"max|diff|={diff!r} (tolerance {ATOL} + {RTOL}*|ref|)")
    if not bool(jnp.all(err <= ATOL + RTOL * jnp.abs(ref))):
        fail(f"kernel and JAX path disagree: max|diff|={diff!r}")
    return diff


def make_engine(cfg, params, max_batch: int, pool_tokens: int):
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model
    from repro.serving import ContinuousBatchingEngine, EngineConfig
    from repro.sharding import rules_for
    ecfg = EngineConfig(max_batch=max_batch, kv_pool_tokens=pool_tokens,
                        max_model_len=256)
    return ContinuousBatchingEngine(Model(cfg, rules_for(make_test_mesh())),
                                    params, ecfg)


def decode_step_has_kernel(eng) -> bool:
    """Compile the engine's decode step for a full batch bucket and look
    for the Pallas kernel in the compiled program."""
    from repro.models.sampler import stack_sampling
    import numpy as np
    B = eng.ecfg.max_batch
    view = eng.pool.view([], [], nb_pad=4, batch_pad=B)
    args = (eng.params, view.pool, view.tables, view.lengths,
            view.positions, view.slots,
            *eng.pool.put((np.zeros((B,), np.int32),
                           *stack_sampling([], pad_to=B))))
    return "tpu_custom_call" in eng._paged_jit.lower(*args).compile().as_text()


def one_chip(cfg, seed: int, dev):
    import jax
    from repro.launch.serve import kv_pool_tokens
    from repro.models.model import init_params
    from repro.serving import ServingAPI

    kernel_check(cfg, jax.random.PRNGKey(seed + 1))
    gc.collect()

    params = init_params(cfg, jax.random.PRNGKey(seed))
    pool_tokens = kv_pool_tokens(cfg, params)
    eng = make_engine(cfg, params, max_batch=N_REQUESTS,
                      pool_tokens=pool_tokens)
    log(f"pool: {pool_tokens} tokens, {eng.pool.pool_bytes} bytes")
    api = ServingAPI(eng)
    reqs = workload(cfg, seed)
    t0 = time.perf_counter()
    api.run(reqs)
    cold_s = time.perf_counter() - t0
    check_outputs(cfg, reqs)
    eng.reset_stats()
    reqs = workload(cfg, seed)
    m = api.run(reqs)
    check_outputs(cfg, reqs)
    log(f"cold run {cold_s!r} s, warm run {m.wall_s!r} s; compile seconds "
        f"(cold minus warm) {cold_s - m.wall_s!r}")
    log(f"smoke numbers, not benchmark numbers: output tokens/s "
        f"{m.output_throughput!r}, mean ITL {m.itl_s * 1e3!r} ms, "
        f"{m.output_tokens} output tokens, mean batch {m.avg_batch!r}")
    has_kernel = decode_step_has_kernel(eng)
    log(f"decode step contains tpu_custom_call: {has_kernel}")
    if not has_kernel:
        fail("the compiled decode step does not contain the Pallas kernel")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")


def four_chips(cfg, seed: int):
    import jax
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import kv_pool_tokens
    from repro.models.model import init_params
    from repro.serving import (EngineConfig, ReplicatedCluster, ServingAPI)

    cfg = dataclasses.replace(cfg, n_layers=4)
    # on the TPU a row's greedy tokens depend on the padded batch it
    # decodes in (at max_batch 4 one request in 16 diverged), so both
    # sides decode one request at a time: each request then meets the
    # same programs on the bare engine and on its replica
    max_batch = 1
    params = init_params(cfg, jax.random.PRNGKey(seed))
    pool_tokens = kv_pool_tokens(cfg, params)

    # the comparison: one bare engine on the first chip
    eng = make_engine(cfg, params, max_batch, pool_tokens)
    ref = workload(cfg, seed)
    t0 = time.perf_counter()
    ServingAPI(eng).run(ref)
    log(f"bare engine: {time.perf_counter() - t0!r} s (compiles included)")
    check_outputs(cfg, ref)
    for leaf in jax.tree.leaves(eng.pool.pool):
        leaf.delete()
    del eng
    gc.collect()

    mesh = make_mesh((4, 1), ("data", "model"))
    ecfg = EngineConfig(max_batch=max_batch, kv_pool_tokens=pool_tokens,
                        max_model_len=256)
    cluster = ReplicatedCluster.sliced(cfg, params, ecfg, mesh, 4,
                                       policy="round-robin", mode="thread")
    seen = set()
    for rep in cluster.replicas:
        want = set(np.asarray(rep.mesh.devices).flat)
        for name, tree in (("params", rep.engine.params),
                           ("pool", rep.engine.pool.pool)):
            got = {d for leaf in jax.tree.leaves(tree)
                   for d in leaf.devices()}
            if got != want:
                fail(f"replica {rep.idx} {name} on {got}, expected {want}")
        seen |= want
        log(f"replica {rep.idx}: params and pool on {sorted(want, key=str)}")
    if len(seen) != 4:
        fail(f"replicas share devices: {seen}")
    reqs = workload(cfg, seed)
    t0 = time.perf_counter()
    m = ServingAPI(cluster).run(reqs)
    log(f"4 replicas: {time.perf_counter() - t0!r} s (compiles included); "
        f"requests per replica {[len(r.requests) for r in cluster.replicas]}")
    check_outputs(cfg, reqs)
    want = {r.req_id: list(r.state.output_tokens) for r in ref}
    got = {r.req_id: list(r.state.output_tokens) for r in reqs}
    if got != want:
        bad = [i for i in want if got.get(i) != want[i]]
        fail(f"replicated greedy tokens differ from the bare engine's for "
             f"requests {bad}")
    log(f"greedy tokens equal the bare engine's for all {len(want)} "
        f"requests ({m.output_tokens} output tokens)")
    log("peak_bytes_in_use per device: " + ", ".join(
        f"{d}={(d.memory_stats() or {}).get('peak_bytes_in_use')}"
        for d in jax.devices()))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the workload")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the replicated path and its comparison")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform}")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, "
             f"found {len(devs)}")

    from repro.configs import get_config
    from repro.launch.serve import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config("opt-1.3b")
    if args.chips == 4:
        four_chips(cfg, args.seed)
    else:
        one_chip(cfg, args.seed, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
