"""The trace reduction on a small trace recorded on the chip: a few rounds
of ``opt-1.3b.chat_backlog`` (one TPU v5 lite, batch 64, profiler's
Python tracer off)."""
import types

import pytest

from bench.counts import decoder as counts
from bench.lib import manifest, trace
from bench.tests.tiny import BENCH, REPO

TRACE = BENCH / "tests" / "data" / "opt-1.3b.chat_backlog.xplane.pb"
LAYERS = 24
DECODE = "jit__unknown+paged_decode_attention"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(str(TRACE), chips=[0])


def test_busy_inside_window(red):
    assert red.chips == 1
    assert 0 < red.busy_s <= red.window_s
    # leaf ops never overlap on one chip's op line
    assert sum(red.op_s.values()) <= red.busy_s * 1.001


def test_programs_and_kernel(red):
    steps = red.program_count[DECODE]
    assert steps >= 2
    assert red.kernel_count["paged_decode_attention"] == LAYERS * steps
    assert red.kernel_s["paged_decode_attention"] \
        < red.program_s[DECODE]
    # the kernel is the largest leaf op of the decode step
    assert max(red.op_s, key=red.op_s.get) == "paged_decode_attention"


def test_idle_is_named_by_host_spans(red):
    idle = sum(red.idle_by_host.values())
    assert red.busy_s + idle == pytest.approx(red.window_s, rel=1e-6)
    assert all(isinstance(n, str) and n for n in red.idle_by_host)
    assert len(red.longest_gaps) <= 10


def test_op_name():
    assert trace.op_name("%paged_decode_attention.9 = bf16[64] custom-call"
                         "(x)") == "paged_decode_attention"
    assert trace.op_name("%fusion.1.2 = f32[] fusion()") == "fusion"


def test_readers_on_the_recorded_trace(red):
    cell = manifest.resolve(REPO, "opt-1.3b.chat_backlog")
    from bench.models import decoder
    s = decoder.sizes(cell.config)
    steps = red.program_count[DECODE]
    rounds = [types.SimpleNamespace(steps=1, prompts=[], pool_used=0.5,
                                    contexts=[540] * 64)
              for _ in range(steps)]
    ctx = types.SimpleNamespace(
        s=s, peaks=manifest.peaks(REPO, "TPU v5 lite"), counts=counts,
        trace=red, rounds=rounds, window_s=red.window_s)
    read = lambda n: manifest.metric_reader(REPO, n).read(ctx)  # noqa: E731
    assert 0 < read("paged_attn_roofline") <= 100
    assert 0 <= read("device.idle") < 100
    assert read("sched.batch_mean") == 64
    assert read("pool.used") == pytest.approx(50.0)
    assert 0 < read("model.mfu") < 100
