"""A model family joins the benchmark as new files alone: its reference
(``bench/models/<family>.py``), its adapter to the program
(``bench/programs/<family>.py``) and its operation counts
(``bench/counts/<family>.py``), found by the configuration's ``family``.
The family here exists only under a temporary root; its files take the
``decoder`` family's functions, and its counts carry a mark of their own."""
import json
import re
import types

import pytest

from bench.counts import decoder as decoder_counts
from bench.lib import harness, manifest, program
from bench.tests import tiny
from bench.tests.tiny import BENCH

NEW = "tinyfam"
FILES = {
    "models": "from bench.models.decoder import forward, init, sizes"
              "  # noqa: F401\n",
    "programs": "from bench.programs.decoder import arch_config, to_program"
                "  # noqa: F401\n",
    "counts": "from bench.counts.decoder import (  # noqa: F401\n"
              "    decode_flops, paged_attn_bytes, paged_attn_flops,"
              " prefill_flops)\n"
              "MARK = 16.0\n",
}
# a reader that reports which counts module it was handed
MARK_READER = ("def read(ctx):\n"
               "    return getattr(ctx.counts, 'MARK', None)\n")


def new_family_root(tmp_path, leave_out=()):
    root = tiny.make_root(tmp_path)
    for sub, src in FILES.items():
        if sub not in leave_out:
            (root / "bench" / sub / f"{NEW}.py").write_text(src)
    path = root / "bench" / "configs" / "tiny-opt.json"
    config = json.loads(path.read_text())
    config["family"] = NEW
    path.write_text(json.dumps(config))
    return root


def test_new_family_from_files_runs_correct(tmp_path):
    root = new_family_root(tmp_path)
    fam = manifest.family(manifest.resolve(root, tiny.CELL))
    for sub, mod in (("models", fam.reference), ("programs", fam.adapter),
                     ("counts", fam.counts)):
        assert mod.__file__ == str(root / "bench" / sub / f"{NEW}.py")
    assert fam.counts.MARK == 16.0
    res = tiny.run(root, seed=6)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("sub", sorted(FILES))
def test_family_without_a_file_stops_naming_it(sub, tmp_path, monkeypatch):
    root = new_family_root(tmp_path, leave_out=(sub,))

    def no_weights(*a, **k):
        raise AssertionError("weights made for a family that lacks a file")
    monkeypatch.setattr(program, "make_params", no_weights)
    # the decoder family has the file: there is no fallback to it
    assert (BENCH / sub / "decoder.py").is_file()
    with pytest.raises(SystemExit, match=re.escape(f"bench/{sub}/{NEW}.py")):
        tiny.run(root)


def test_readers_get_the_cells_family_counts(tmp_path):
    root = new_family_root(tmp_path)
    (root / "bench" / "metrics" / "counts.mark.py").write_text(MARK_READER)
    cell = manifest.resolve(root, tiny.CELL)
    cell.per_layer = [{"name": n} for n in ("counts.mark", "model.mfu",
                                            "paged_attn_roofline")]
    fam = manifest.family(cell)
    s = fam.reference.sizes(cell.config)
    rounds = [types.SimpleNamespace(steps=1, prompts=[20], pool_used=0.5,
                                    contexts=[40] * 4) for _ in range(3)]
    run = types.SimpleNamespace(t_open=0.0,
                                window_rounds=lambda end=None: rounds)
    red = types.SimpleNamespace(kernel_s={"paged_decode_attention": 1e-3},
                                window_s=1.0, busy_s=0.5)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    got = harness.per_layer(cell, fam.counts, run, s, peaks, red, 1.0)
    assert got["counts.mark"] == 16.0
    # the decoder's module has no mark: its reader finds nothing there
    dense = harness.per_layer(cell, decoder_counts, run, s, peaks, red, 1.0)
    assert "counts.mark" not in dense
    assert got["model.mfu"] == dense["model.mfu"] > 0
    assert got["paged_attn_roofline"] == dense["paged_attn_roofline"] > 0


@pytest.mark.parametrize("sub", ["lib", "metrics"])
def test_harness_and_readers_know_no_family_layout(sub):
    # the dense decoder's layout lives in its adapter and counts alone
    for path in (BENCH / sub).glob("*.py"):
        text = path.read_text()
        for word in ("qkv_bias", '"stack"', "layer_matmul_params",
                     "kv_bytes_per_token"):
            assert word not in text, (path, word)
