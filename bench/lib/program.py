"""The system under test: its config, its weights' layout, its engine.

The benchmark makes the weights (``bench/models/<family>.py``) and hands
the program a copy laid out as the program's parameter tree; this module
is the only place that knows that layout, and it checks it against the
program's own abstract tree before anything runs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp


def arch_config(config: Dict, s: Dict):
    """The program's ``ArchConfig`` for a configuration file: the
    registry entry the file names, with every size the file states (the
    file is the configuration as served; the registry gives the rest)."""
    from repro.configs import get_config
    served = dict(n_layers=s["L"], d_model=s["d"], n_heads=s["H"],
                  n_kv_heads=s["K"], d_ff=s["f"], vocab_size=s["V"],
                  head_dim=s["hd"], norm=s["norm"], act=s["act"],
                  pos=s["pos"], tie_embeddings=s["tie"], dtype=s["dtype"],
                  qkv_bias=False)
    if s["pos"] == "learned":
        served["max_position"] = s["max_pos"]
    else:
        served["rope_theta"] = s["theta"]
    return dataclasses.replace(get_config(config["registry"]), **served)


def to_program(cfg, w: Dict) -> Dict:
    """The program's parameter tree from the benchmark's weights (vocab
    rows padded with zeros to the program's padded vocabulary)."""
    pad = cfg.padded_vocab - cfg.vocab_size
    embed = {"tok": jnp.pad(w["tok"], ((0, pad), (0, 0)))}
    if "pos" in w:
        embed["pos"] = w["pos"]
    if "unemb" in w:
        embed["unemb"] = jnp.pad(w["unemb"], ((0, 0), (0, pad)))
    return {"embed": embed, "stack": [w["layers"]], "rem": [],
            "final_norm": w["final_norm"]}


def make_params(cfg, family, s: Dict, key):
    """Weights made on the device by one jitted call, in the program's
    layout; the layout is checked against ``abstract_params``."""
    from repro.models.model import abstract_params
    fn = jax.jit(lambda k: to_program(cfg, family.init(s, k)))
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
