"""The control: the reference put in the program's place one precision step
below the configuration's (float8 e4m3 under a bfloat16 config), judged by
the harness's own comparison, comes out as not correct, on the same
prompts and served tokens as a sound run of the program, which stays under
the limit."""
import pytest

from bench.tests import tiny


@pytest.mark.parametrize("seed", [4, 2 ** 33 + 9])
def test_control_fails_where_the_program_passes(seed, tmp_path):
    res = tiny.run(tiny.make_root(tmp_path), seed=seed, control=True)
    gap = res["checks"]["max_logit_gap"]
    assert not res["correct"]
    assert gap["value"] > gap["limit"]
    assert res["program_max_logit_gap"] <= gap["limit"]
    assert res["checks"]["short_outputs"]["value"] == 0
