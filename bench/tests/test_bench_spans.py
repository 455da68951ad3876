"""Device idle by the program's layers (``bench/lib/spans.py``) on two
small traces recorded on the chip (one TPU v5 lite,
``opt-1.3b.chat_backlog``, batch 64, the profiler's Python tracer off):
the first build's, which has no program spans, and one with the spans.
Also pins what ``bench/lib/trace.py`` reads from the first: the new
reduction leaves it as it was."""
import pytest

from bench.lib import spans, trace
from bench.tests.tiny import BENCH

DATA = BENCH / "tests" / "data"
PLAIN = DATA / "opt-1.3b.chat_backlog.xplane.pb"
SPANS = DATA / "opt-1.3b.chat_backlog.spans.xplane.pb"


@pytest.fixture(scope="module")
def plain():
    return trace.reduce(str(PLAIN), chips=[0]), \
        spans.by_layer(str(PLAIN), chips=[0])


@pytest.fixture(scope="module")
def traced():
    return trace.reduce(str(SPANS), chips=[0]), \
        spans.by_layer(str(SPANS), chips=[0])


def test_reduction_of_the_first_trace_is_unchanged(plain):
    red, _ = plain
    assert red.chips == 1
    assert red.window_s == pytest.approx(0.167324628, abs=1e-9)
    assert red.busy_s == pytest.approx(0.146536216, abs=1e-9)
    assert red.program_count == {
        "jit__unknown": 3, "jit__write_rows": 3, "jit_sample_tokens": 3,
        "jit_dynamic_slice": 6, "jit__unknown+paged_decode_attention": 3,
        "jit__advance_poslen": 1}
    assert red.program_s["jit__unknown+paged_decode_attention"] == \
        pytest.approx(0.131974421, abs=1e-9)
    assert red.kernel_count == {"paged_decode_attention": 72}
    assert red.kernel_s["paged_decode_attention"] == \
        pytest.approx(0.117440245, abs=1e-9)
    assert len(red.op_s) == 45
    assert red.idle_by_host == pytest.approx({
        "bench.pump": 5.2584e-05, "between ops (<10 us)": 2.47e-05,
        "np.asarray(jax.Array)": 0.018964509, "shard_args": 0.001746619},
        abs=1e-9)
    assert red.longest_gaps[0] == ("np.asarray(jax.Array)",
                                   pytest.approx(0.004296459, abs=1e-9))


def test_a_trace_without_program_spans_is_all_client(plain):
    red, lay = plain
    assert lay.span_count == {} and lay.steps() == 0
    assert lay.idle_us_per_step() is None
    assert set(lay.idle_s) == {spans.CLIENT}
    assert lay.idle_s[spans.CLIENT] == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)


def test_layers_and_client_make_up_the_idle_share(traced):
    red, lay = traced
    steps = lay.steps()
    assert steps >= 2
    per_step = lay.idle_us_per_step()
    assert set(per_step) == set(spans.LAYERS) | {spans.CLIENT}
    assert all(v >= 0 for v in per_step.values())
    # device.idle (%) x window / steps, in us
    idle_pct = 100.0 * (1.0 - red.busy_s / red.window_s)
    want = idle_pct / 100.0 * red.window_s / steps * 1e6
    assert sum(per_step.values()) == pytest.approx(want, rel=1e-3)
    # the host works between steps: every layer it passes through holds
    # a part of each gap, the program's layers most of it
    assert all(per_step[k] > 0 for k in spans.LAYERS)
    assert sum(per_step[k] for k in spans.LAYERS) > 10 * per_step["client"]


def test_spans_count_the_decode_steps(traced):
    red, lay = traced
    decode = [k for k in red.program_count if "paged_decode_attention" in k]
    assert decode == ["jit_paged_decode_step+paged_decode_attention"]
    # a step dispatched at the window's edge may run just past it
    assert abs(red.program_count[decode[0]] - lay.steps()) <= 1
    count = lay.span_count
    assert count["api.round"] == count["engine.step"] == \
        count["sched.plan"]
    assert count["engine.fetch"] >= lay.steps()
    assert not any(k.startswith("jit__unknown") for k in red.program_count)


def test_a_gap_is_split_by_the_innermost_span_over_time():
    program = [(0, 100, "api.round"), (10, 50, "engine.step"),
               (20, 30, "pool.put"), (60, 60, "sched.admit")]
    segs = spans._segments(program)
    assert segs == [(0, 10, "api"), (10, 20, "engine"), (20, 30, "pool"),
                    (30, 50, "engine"), (50, 100, "api")]
    idle = {k: 0.0 for k in spans.LAYERS + (spans.CLIENT,)}
    spans._split(5, 120, segs, [s for s, _, _ in segs], idle)
    assert idle == pytest.approx({"api": 55e-9, "engine": 30e-9,
                                  "pool": 10e-9, "sched": 0.0,
                                  "client": 20e-9})
