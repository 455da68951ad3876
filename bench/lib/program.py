"""The system under test: its weights and its engine.

The benchmark makes the weights (``bench/models/<family>.py``) and hands
the program a copy laid out as the program's parameter tree by the
family's adapter (``bench/programs/<family>.py``), the only place that
knows that layout; this module checks the layout against the program's
own abstract tree before anything runs.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def make_params(cfg, reference, adapter, s: Dict, key):
    """Weights made on the device by one jitted call, the reference's
    ``init`` laid out by the adapter's ``to_program``; the layout is
    checked against ``abstract_params``."""
    from repro.models.model import abstract_params
    fn = jax.jit(lambda k: adapter.to_program(cfg, reference.init(s, k)))
    want = jax.tree.map(lambda p: (p.shape, jnp.dtype(p.dtype)),
                        abstract_params(cfg),
                        is_leaf=lambda x: hasattr(x, "logical"))
    got = jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)),
                       jax.eval_shape(fn, key))
    if want != got:
        raise SystemExit(f"weights layout differs from the program's "
                         f"parameter tree:\n{want}\n{got}")
    return fn(key)


def make_engine(cfg, params, deployment: Dict):
    """One ``ContinuousBatchingEngine`` at the program's default modes,
    pool sized by the program from the device's memory, behind the
    ``ServingAPI`` facade."""
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import kv_pool_tokens
    from repro.models.model import Model
    from repro.serving import (ContinuousBatchingEngine, EngineConfig,
                               ServingAPI)
    from repro.sharding import rules_for
    ecfg = EngineConfig(max_batch=deployment["max_batch"],
                        kv_pool_tokens=kv_pool_tokens(cfg, params),
                        max_model_len=deployment["max_model_len"])
    eng = ContinuousBatchingEngine(Model(cfg, rules_for(make_test_mesh())),
                                   params, ecfg)
    return eng, ServingAPI(eng)
