"""A cell added from new files alone (config, traffic mix, limits and
manifest entries; no code) is picked up and runs to a correct result,
every end-to-end metric reported and nothing compiled in the window."""
from bench.lib import manifest
from bench.tests import tiny


def test_new_cell_from_files_runs_correct(tmp_path):
    root = tiny.make_root(tmp_path)
    cell = manifest.resolve(root, tiny.CELL)
    assert cell.config["hidden_size"] == 64
    res = tiny.run(root, seed=2 ** 31 + 77)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}
    assert res["window_compiles"] == 0
    assert res["checks"]["max_logit_gap"]["value"] \
        <= tiny.LIMITS["max_logit_gap"]["limit"]
    assert list(res)[-1] == "checks"
