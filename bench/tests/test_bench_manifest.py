"""BENCHMARK.json against the contract, and every file it names found."""
import json
import re

import pytest

from bench.lib import manifest
from bench.tests.tiny import BENCH, REPO

M = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = ("hidden", "intermediate", "latent", "state", "proj", "head",
          "expansion", "experts_per_tok", "num_experts_per_tok", "ffn")
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"][1].startswith(M["paths"][0] + "/")
    assert 1 <= M["run_seconds"] <= 51


@pytest.mark.parametrize("entry", M["configs"] + M["workloads"]
                         + M["end_to_end"] + M["per_layer"],
                         ids=lambda e: e["name"])
def test_names_and_units(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for k in ("why", "layer", "source"):
        if k in entry:
            assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k]


def test_bounds():
    for m in M["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in M["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    c = manifest.resolve(REPO, cell)
    assert c.config["name"] == c.config_name
    assert c.traffic["requests"] > 0
    assert 0 < c.limits["max_logit_gap"]["limit"]
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in names
    fam = c.config["family"]
    assert c.family_files == {
        "reference": BENCH / "models" / f"{fam}.py",
        "adapter": BENCH / "programs" / f"{fam}.py",
        "counts": BENCH / "counts" / f"{fam}.py"}
    f = manifest.family(c)
    assert callable(f.reference.sizes) and callable(f.reference.forward)
    assert callable(f.adapter.arch_config) and callable(f.adapter.to_program)
    for fn in ("decode_flops", "prefill_flops", "paged_attn_bytes",
               "paged_attn_flops"):
        assert callable(getattr(f.counts, fn))


def test_moves_are_reported_where_the_metric_is():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    for m in M["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert manifest.reports(e2e[m["moves"]], cell)


@pytest.mark.parametrize("cfg", M["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    f = json.loads((REPO / cfg["file"]).read_text())
    assert f["name"] == cfg["name"] and f["source"] == cfg["source"]
    assert sorted(f["reduced"]) == sorted(cfg["reduced"])
    assert set(f["reduced"]) == set(f.get("published", {}))
    for k in cfg["reduced"]:
        assert NAME.match(k)
        assert not k.endswith(("_dim", "_rank", "_size"))
        assert not any(w in k for w in WIDTHS), k
    assert f["assumed"] and f["deployment"]["chips"] == 1
    assert len({c["file"] for c in M["configs"]}) == len(M["configs"])
