"""The ``decoder`` family's adapter to the program under test.

``arch_config`` maps the sizes of ``bench/models/decoder.py:sizes`` onto
the program's ``ArchConfig``; ``to_program`` lays the reference's weights
out as the program's parameter tree. Every layer is the same dense
full-attention layer without biases, so the tree holds one stacked slot
and no remainder layers.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

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
