"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Read with ``jax.profiler.ProfileData``. A device plane
(``/device:TPU:<n>``) carries one event per program execution on its
``XLA Modules`` line and one per operation on its ``XLA Ops`` line, where
a loop or conditional op encloses the ops of its body; a Pallas kernel is
an op whose text names ``custom_call_target="tpu_custom_call"``, and is
known by the name it was given (``paged_decode_attention``). The host
plane carries the harness's ``TraceAnnotation`` spans (``bench.*``) and the
runtime's spans on the thread that ran them; host and device events share
one clock.

* busy: the union of the op intervals inside the traced window (first to
  last harness span), averaged over the chips used;
* op time: leaf ops only (an op that encloses others is not counted
  twice), by op name without its ``.N`` suffix;
* program time: by module name without its fingerprint, suffixed with
  ``+<kernel>`` for each kernel the execution ran (the engine's step
  programs are ``functools.partial`` objects, which JAX names
  ``jit__unknown``, so the kernel they run tells them apart);
* idle gaps: each gap between busy intervals is named by the innermost
  host span covering its middle; gaps under 10 us by that fact.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HARNESS_PREFIX = "bench."
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
SHORT_GAP_NS = 10_000


@dataclasses.dataclass
class Reduced:
    window_s: float                       # first to last harness span
    busy_s: float                         # union of op intervals, per chip
    op_s: Dict[str, float]                # leaf-op device time by name
    kernel_s: Dict[str, float]            # Pallas kernel time by name
    kernel_count: Dict[str, int]
    program_s: Dict[str, float]           # device time by program label
    program_count: Dict[str, int]
    idle_by_host: Dict[str, float]        # idle seconds by host span
    longest_gaps: List[Tuple[str, float]]
    chips: int


def start(trace_dir: str):
    """Start the profiler without its Python function tracer (which
    would slow the host it measures)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find(trace_dir: str) -> str:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    return paths[0]


def op_name(text: str) -> str:
    """``%paged_decode_attention.9 = bf16[...] ...`` -> the bare name."""
    head = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _host_spans(pd) -> List[Tuple[float, float, str]]:
    """Spans of the host thread that ran the harness, sorted by start."""
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            if any(n.startswith(HARNESS_PREFIX) for _, _, n in evs):
                return sorted(evs)
    return []


def _innermost(spans, t: float) -> str:
    """Name of the innermost span covering ``t`` (spans of one thread
    nest, so it is the last one started that has not ended)."""
    i = bisect.bisect_right(spans, (t, float("inf"), ""))
    for a, b, n in reversed(spans[:i]):
        if b >= t:
            return n
    return "outside harness spans"


def _device_plane(plane, w0, w1, out, host):
    ops, kernels = [], []
    modules = []
    for line in plane.lines:
        evs = [(e.start_ns, e.end_ns, e.name) for e in line.events
               if min(e.end_ns, w1) > max(e.start_ns, w0)]
        if line.name == OPS_LINE:
            ops = sorted(evs)
        elif line.name == MODULES_LINE:
            modules = sorted(evs)
    if not ops:
        return False
    for i, (a, b, text) in enumerate(ops):
        a, b = max(a, w0), min(b, w1)
        if i + 1 < len(ops) and ops[i + 1][0] < ops[i][1]:
            continue                        # encloses the ops that follow
        name = op_name(text)
        out["op_s"][name] += (b - a) * 1e-9
        if KERNEL_TARGET in text:
            out["kernel_s"][name] += (b - a) * 1e-9
            out["kernel_count"][name] += 1
            kernels.append((a, name))
    for a, b, text in modules:
        lo = bisect.bisect_left(kernels, (a, ""))
        hi = bisect.bisect_right(kernels, (b, "￿"))
        label = "+".join([text.split("(", 1)[0]]
                         + sorted({n for _, n in kernels[lo:hi]}))
        out["program_s"][label] += (min(b, w1) - max(a, w0)) * 1e-9
        out["program_count"][label] += 1
    u = _union([(max(a, w0), min(b, w1)) for a, b, _ in ops])
    out["busy"].append(sum(b - a for a, b in u) * 1e-9)
    edges = [(w0, w0)] + u + [(w1, w1)]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            name = (_innermost(host, (a + b) / 2) if b - a >= SHORT_GAP_NS
                    else "between ops (<10 us)")
            out["idle"][name] += (b - a) * 1e-9
            out["gaps"].append(((b - a) * 1e-9, name))
    return True


def reduce(path: str, chips: Optional[List[int]] = None) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host = _host_spans(pd)
    harness = [(a, b) for a, b, n in host if n.startswith(HARNESS_PREFIX)]
    if not harness:
        raise RuntimeError("no harness spans in the trace")
    w0, w1 = min(a for a, _ in harness), max(b for _, b in harness)
    out = {k: defaultdict(float) for k in ("op_s", "kernel_s", "program_s",
                                           "idle")}
    out.update(kernel_count=defaultdict(int), program_count=defaultdict(int),
               busy=[], gaps=[])
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and (chips is None or int(m.group(1)) in chips):
            _device_plane(plane, w0, w1, out, host)
    if not out["busy"]:
        raise RuntimeError("no device operations in the trace")
    gaps = sorted(out["gaps"], reverse=True)[:10]
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(out["busy"]) / len(out["busy"]),
        op_s=dict(out["op_s"]), kernel_s=dict(out["kernel_s"]),
        kernel_count=dict(out["kernel_count"]),
        program_s=dict(out["program_s"]),
        program_count=dict(out["program_count"]),
        idle_by_host=dict(out["idle"]),
        longest_gaps=[(n, s) for s, n in gaps], chips=len(out["busy"]))
