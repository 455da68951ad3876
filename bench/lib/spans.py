"""Device idle by the program's layers, read from a profiler trace.

The program opens a span at each layer boundary of an engine iteration,
named by layer: ``api.*`` (the facade), ``engine.*`` (the model step),
``sched.*`` (the scheduler) and ``pool.*`` (the KV pool); the names are
listed in ``src/repro/serving/obs/trace.py``. They sit on the harness's
thread, inside its ``bench.*`` spans, on the clock of the device planes.

Each gap in the device's work inside the traced window (as
``bench/lib/trace.py`` finds them) of at least 10 us is split by the
innermost *program* span the host was in over it: each part goes to
that span's layer, and a part under no program span to ``client``, as
does every gap under 10 us. So the layers and ``client`` add up to the
window's idle time, ``(1 - busy / window) x window``, exactly. (A gap
between two decode steps runs from the token fetch through the commit,
the client, the plan and the next step's uploads to its dispatch:
naming it whole by the span at its middle, as ``trace.py`` names gaps,
would hand it to one layer.) Idle seconds are averaged over the chips
used. The ``engine.decode`` spans in the window are the decode steps.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Tuple

from bench.lib import trace

LAYERS = ("api", "engine", "sched", "pool")
PROGRAM_PREFIXES = tuple(f"{layer}." for layer in LAYERS)
CLIENT = "client"
DECODE = "engine.decode"


@dataclasses.dataclass
class Layers:
    window_s: float                  # first to last harness span
    idle_s: Dict[str, float]         # idle seconds by layer and client
    span_count: Dict[str, int]       # program spans started in the window
    chips: int

    def steps(self) -> int:
        return self.span_count.get(DECODE, 0)

    def idle_us_per_step(self) -> Optional[Dict[str, float]]:
        """Microseconds of device idle per decode step, by layer and
        client; None where the trace holds no ``engine.decode`` span."""
        n = self.steps()
        if not n:
            return None
        return {k: self.idle_s.get(k, 0.0) / n * 1e6
                for k in LAYERS + (CLIENT,)}


def layer_of(name: str) -> str:
    return name.split(".", 1)[0] if name.startswith(PROGRAM_PREFIXES) \
        else CLIENT


def _segments(program) -> List[Tuple[float, float, str]]:
    """The layer of the innermost program span over time: sorted,
    disjoint (start, end, layer) pieces (spans of one thread nest)."""
    timed = [(a, b, n) for a, b, n in program if b > a]
    events = sorted([(a, 1, n) for a, _, n in timed]
                    + [(b, 0, n) for _, b, n in timed])
    out, stack, t = [], [], None
    for t1, starts, name in events:
        if stack and t1 > t:
            out.append((t, t1, layer_of(stack[-1])))
        if starts:
            stack.append(name)
        else:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        t = t1
    return out


def _split(a: float, b: float, segs, starts, idle: Dict[str, float]):
    """Add the idle interval [a, b) to ``idle`` by layer."""
    covered = 0.0
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(segs) and segs[i][0] < b:
        s, e, layer = segs[i]
        part = min(e, b) - max(s, a)
        if part > 0:
            idle[layer] += part * 1e-9
            covered += part
        i += 1
    idle[CLIENT] += (b - a - covered) * 1e-9


def by_layer(path: str, chips: Optional[List[int]] = None) -> Layers:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host = trace._host_spans(pd)
    harness = [(a, b) for a, b, n in host
               if n.startswith(trace.HARNESS_PREFIX)]
    if not harness:
        raise RuntimeError("no harness spans in the trace")
    w0, w1 = min(a for a, _ in harness), max(b for _, b in harness)
    program = [s for s in host if s[2].startswith(PROGRAM_PREFIXES)]
    segs = _segments(program)
    starts = [s for s, _, _ in segs]
    idle: Dict[str, float] = defaultdict(float)
    n_chips = 0
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if not m or (chips is not None and int(m.group(1)) not in chips):
            continue
        ops = [(max(e.start_ns, w0), min(e.end_ns, w1))
               for line in plane.lines if line.name == trace.OPS_LINE
               for e in line.events
               if min(e.end_ns, w1) > max(e.start_ns, w0)]
        if not ops:
            continue
        n_chips += 1
        edges = [(w0, w0)] + trace._union(ops) + [(w1, w1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b - a >= trace.SHORT_GAP_NS:
                _split(a, b, segs, starts, idle)
            elif b > a:
                idle[CLIENT] += (b - a) * 1e-9
    if not n_chips:
        raise RuntimeError("no device operations in the trace")
    return Layers(
        window_s=(w1 - w0) * 1e-9,
        idle_s={k: v / n_chips for k, v in idle.items()},
        span_count=dict(Counter(n for a, _, n in program if w0 <= a < w1)),
        chips=n_chips)
