"""The comparison catches a broken timed path: each fault a serving cell can
have, planted in the program underneath an otherwise normal run (without
the look for a chip), makes ``correct`` come out false."""
import functools

import pytest

from bench.tests import tiny


def altered_token(engine):
    """Every sampled token is moved one id up where it is produced."""
    orig = engine.sample_tokens

    @functools.wraps(orig)
    def sample(logits, *a):
        return (orig(logits, *a) + 1) % logits.shape[-1]
    return {"sample_tokens": sample}


def state_unchanged(engine):
    """The decode step returns the pool it was given: no K/V is written."""
    orig = engine._paged_decode_fn

    def step(model, block_size, params, pool, *a):
        tokens, _ = orig(model, block_size, params, pool, *a)
        return tokens, pool
    return {"_paged_decode_fn": step}


def half_batch_left_out(engine):
    """The second half of each decode batch attends to nothing."""
    orig = engine._paged_decode_fn

    def step(model, block_size, params, pool, tables, lengths, *a):
        half = lengths.shape[0] // 2
        return orig(model, block_size, params, pool, tables,
                    lengths.at[half:].set(0), *a)
    return {"_paged_decode_fn": step}


@pytest.mark.parametrize("fault", [altered_token, state_unchanged,
                                   half_batch_left_out])
def test_fault_makes_correct_false(fault, tmp_path, monkeypatch):
    from repro.serving import engine
    for name, fn in fault(engine).items():
        monkeypatch.setattr(engine, name, fn)
    res = tiny.run(tiny.make_root(tmp_path), seed=11)
    assert not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
