"""Measured serving curves from the REAL engine (reduced model on CPU).

Sweeps the engine's ``max_batch`` knob on a fixed workload and reports
T(B)/ITL(B)/KV(B) — the measured-data path into BCA, mirroring the paper's
online-mode evaluation. CPU timings are not H100 timings, but the plateau
SHAPE (throughput saturating while ITL keeps growing) is the phenomenon
under test and emerges from real compute.
"""
from __future__ import annotations

import json
import os
from typing import Dict

import jax
import numpy as np

from jax import set_mesh
from repro.configs import get_config, reduced
from repro.core.bca import BatchingConfigurationAdvisor
from repro.core.perfmodel import ServingCurves
from repro.launch.mesh import make_test_mesh
from repro.models.model import Model, init_params
from repro.serving import ContinuousBatchingEngine, EngineConfig, sharegpt_like
from repro.sharding import rules_for


def measured_curves(batches=(1, 2, 4, 8), n_requests: int = 10,
                    seed: int = 0) -> Dict:
    cfg = reduced(get_config("opt-1.3b"))
    mesh = make_test_mesh()
    rules = rules_for(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    model = Model(cfg, rules)
    rows = []
    with set_mesh(mesh):
        for mb in batches:
            ecfg = EngineConfig(max_batch=mb, block_size=16,
                                kv_pool_tokens=1 << 14, max_model_len=160,
                                prefill_bucket=32)
            engine = ContinuousBatchingEngine(model, params, ecfg)
            reqs = sharegpt_like(n_requests, cfg.vocab_size, seed=seed,
                                 mean_in=24, mean_out=24, max_len=96,
                                 sigma=0.3)
            m = engine.run(reqs)
            rows.append({"max_batch": mb, "throughput": m.throughput,
                         "output_throughput": m.output_throughput,
                         "itl_s": m.itl_s, "avg_batch": m.avg_batch,
                         "kv_fraction": m.max_kv_fraction})
    curves = ServingCurves(
        np.array([r["avg_batch"] for r in rows]),
        np.array([r["output_throughput"] for r in rows]),
        np.array([r["itl_s"] for r in rows]),
        np.array([r["kv_fraction"] for r in rows]))
    slo = float(curves.itl_s.min()) * 3
    bca = BatchingConfigurationAdvisor(curves, slo_s=slo, eps=0.05).solve()
    out = {"rows": rows, "bca_on_measured": bca.summary(),
           "plateau_observed": bool(
               rows[-1]["output_throughput"] <
               rows[-1]["max_batch"] / rows[0]["max_batch"] *
               rows[0]["output_throughput"] * 0.9)}
    os.makedirs("experiments/paper", exist_ok=True)
    with open("experiments/paper/engine_measured_curves.json", "w") as f:
        json.dump(out, f, indent=1, default=float)
    return out
