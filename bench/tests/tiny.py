"""A benchmark root holding one tiny cell, made only of new files: the
code, model families and readers of this checkout, a config, a traffic
mix, a limits file and a manifest of their own. Small enough for the
CPU."""
from __future__ import annotations

import json
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

CELL = "tiny-opt.backlog"
CONFIG = {
    "name": "tiny-opt", "family": "decoder", "registry": "opt-1.3b",
    "source": "test size", "model_type": "opt", "hidden_size": 64,
    "ffn_dim": 128, "num_attention_heads": 4, "num_hidden_layers": 2,
    "vocab_size": 512, "max_position_embeddings": 256,
    "activation_function": "relu", "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "deployment": {"chips": 1, "max_batch": 4, "max_model_len": 128}}
TRAFFIC = {
    "requests": 64, "stratum": 8,
    "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5, "min": 1,
               "max": 60},
    "output": {"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 1,
               "max": 48},
    "arrival": {"pattern": "backlog"},
    "sampling": {"temperature": 0, "ignore_eos": True},
    "window": {"opens_after_finished": "max_batch"}, "check_requests": 8}
# on the CPU, over seeds 1-8, 11 and 2**33 + 9, the bf16 program read at
# most 0.0232 against the float32 reference at this size, and the float8
# control at least 0.154
LIMITS = {"max_logit_gap": {"limit": 0.08}, "min_sampled_tokens": 60}


def make_root(tmp: Path) -> Path:
    """A checkout-like root whose ``bench/`` links this checkout's code,
    model families and readers and adds the tiny cell's own files. The
    families' and readers' directories are the root's own, each file in
    them a link, so that a test can add a family or a reader."""
    b = tmp / "bench"
    for sub in ("configs", "traffic", "cells"):
        (b / sub).mkdir(parents=True)
    for name in ("lib", "peaks.json", "__init__.py"):
        (b / name).symlink_to(BENCH / name)
    for sub in ("models", "programs", "counts", "metrics"):
        (b / sub).mkdir()
        for f in (BENCH / sub).glob("*.py"):
            (b / sub / f.name).symlink_to(f)
    (b / "configs" / "tiny-opt.json").write_text(json.dumps(CONFIG))
    (b / "traffic" / "tiny.json").write_text(json.dumps(TRAFFIC))
    (b / "cells" / f"{CELL}.json").write_text(json.dumps(LIMITS))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": "tiny-opt", "source": "test size",
                            "file": "bench/configs/tiny-opt.json",
                            "reduced": [], "why": "test size"}]
    manifest["workloads"] = [{"name": CELL, "config": "tiny-opt",
                              "traffic": "tiny", "chips": 1,
                              "why": "test size"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp


CACHE_FLAGS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


def run(root: Path, seed: int = 5, control: bool = False):
    """One trace-free run of the tiny cell, without the look for a chip.
    The compilation-cache settings the run makes are undone after it, so
    other tests in the process see JAX as they left it."""
    import jax
    from bench.lib import harness
    saved = {k: getattr(jax.config, k) for k in CACHE_FLAGS}
    try:
        return harness.run_cell(
            root, ["--workload", CELL, "--seed", str(seed), "--seconds",
                   "3", "--trace", "0"], time.perf_counter(),
            require_chip=False, control=control)
    finally:
        from jax.experimental.compilation_cache import compilation_cache
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
