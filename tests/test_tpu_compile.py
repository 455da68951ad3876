"""Ahead-of-time compiles for a described TPU v5e chip at the served widths.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse (a block shape off Mosaic's tiling, a program that does not fit
HBM). The paged kernel is compiled on its own at both served widths, the
engine's decode step with depth cut to 2 layers (widths unchanged), and
the pool writes; each must keep the pool in place (aliased, no
pool-sized temporary).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kvcache import paged
from repro.kvcache.paged import PagedKVCache
import repro.kernels.paged_decode_attention as pda
from repro.models.model import Model, abstract_cache, param_sds
from repro.models.params import tree_sds
from repro.serving.engine import StepFunctions
from repro.sharding import rules_for

BS = 16
# no temporary may come near a pool leaf (hundreds of MiB and up here)
MAX_TEMP_BYTES = 64 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_mesh(topo):
    return Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))


@pytest.fixture
def on_chip(chip_mesh):
    """The described chip's mesh in place of the tests' CPU mesh, and the
    persistent cache off: a described-chip compile cannot be read back
    from it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.set_mesh(chip_mesh):
        yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool_sds(sharding, cfg, n_blocks, max_batch):
    pool = jax.eval_shape(lambda: PagedKVCache(
        cfg, num_blocks=n_blocks, block_size=BS, max_batch=max_batch).pool)
    return jax.tree.map(lambda x: _sds(sharding, x.shape, x.dtype), pool)


@pytest.mark.parametrize("arch,batch,nb", [
    pytest.param("opt-1.3b", 8, 32, id="8"),
    pytest.param("opt-1.3b", 256, 32, id="256"),
    pytest.param("opt-1.3b", 64, 128, id="opt-1.3b-64-128"),
    pytest.param("internlm2-1.8b", 128, 128, id="internlm2-1.8b-128-128"),
    # a table narrower than a compute block (P = 16 at these widths)
    pytest.param("internlm2-1.8b", 128, 4, id="internlm2-1.8b-128-4"),
])
def test_paged_kernel_compiles_for_v5e(one_chip, on_chip, arch, batch, nb):
    """Decode attention over a layer-flattened 24 x 2048-block pool of
    lane-dense rows: opt-1.3b's 32 KV heads of 64 lanes ([NB, 16, 2048])
    and internlm2-1.8b's 8 of 128 at G = 2 ([NB, 16, 1024])."""
    cfg = get_config(arch)
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pool = _sds(one_chip, (24 * 2048, BS, K * hd), jnp.bfloat16)
    compiled = pda.paged_gqa_decode_attention.lower(
        _sds(one_chip, (batch, H, hd), jnp.bfloat16), pool, pool,
        _sds(one_chip, (batch, nb), jnp.int32),
        _sds(one_chip, (batch,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < MAX_TEMP_BYTES


def test_paged_decode_step_compiles_for_v5e(chip_mesh, one_chip, on_chip,
                                            monkeypatch):
    """The engine's zero-copy decode step (jitted, pool donated) at
    opt-1.3b widths, 2 layers: the kernel is in it and the pool is
    updated in place."""
    cfg = dataclasses.replace(get_config("opt-1.3b"), n_layers=2)
    B, nb = 16, 32
    steps = StepFunctions.build(Model(cfg, rules_for(chip_mesh)), BS)
    params = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype),
                          param_sds(cfg))
    pool = _pool_sds(one_chip, cfg, 8192, B)
    vec = lambda dt: _sds(one_chip, (B,), dt)              # noqa: E731
    args = (params, pool, _sds(one_chip, (B, nb), jnp.int32),
            vec(jnp.int32), vec(jnp.int32), vec(jnp.int32), vec(jnp.int32),
            vec(jnp.float32), vec(jnp.int32), vec(jnp.float32),
            vec(jnp.uint32))
    # the dispatcher asks which backend this process computes on (the
    # CPU here); steer it to the kernel the chip would run
    monkeypatch.setattr(pda.jax, "default_backend", lambda: "tpu")
    compiled = steps.paged.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < MAX_TEMP_BYTES


def test_pool_writes_compile_in_place_for_v5e(one_chip, on_chip):
    """The prefill write and the copy-on-write block copy donate the pool
    and index it through its leading dims: no pool-sized relayout copy
    (a scatter behind the layer dim costs one)."""
    cfg = dataclasses.replace(get_config("opt-1.3b"), n_layers=2)
    pool = _pool_sds(one_chip, cfg, 8192, 16)
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    cache = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype),
                         tree_sds(abstract_cache(cfg, 1, 64)))
    i32 = lambda *s: _sds(one_chip, s, jnp.int32)          # noqa: E731
    for fn, args in ((paged._write_rows, (pool, cache, i32(8), i32(), i32(),
                                          i32())),
                     (paged._copy_block, (pool, i32(), i32()))):
        mem = fn.lower(*args, cfg=cfg, block_size=BS).compile() \
            .memory_analysis()
        assert mem.alias_size_in_bytes >= pool_bytes
        assert mem.temp_size_in_bytes < MAX_TEMP_BYTES
