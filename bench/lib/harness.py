"""One run of one cell: set-up, measured window, correctness check.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object; with ``--trace 0``
its metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics (read from a profiler trace of the window).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench.lib import check, manifest, serve, traffic


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def seed_key(seed: int):
    """A PRNG key from any whole number (more than 32 bits included)."""
    import jax
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs traced while ``armed`` (none should be, in the
    window)."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, *_a, **_k):
        if self.armed and event.endswith("jaxpr_trace_duration"):
            self.count += 1

    def close(self):
        import jax
        self.armed = False
        jax.monitoring.unregister_event_duration_listener(self._on)


class GcPauses:
    """The collector's pauses while ``armed`` (the window), by generation."""

    def __init__(self):
        self.armed, self.pauses, self._t = False, [], None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: Dict):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.armed and self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def close(self):
        self.armed = False
        gc.callbacks.remove(self._on)

    def summary(self) -> str:
        longest = max(self.pauses, key=lambda p: p[1], default=(None, 0.0))
        return (f"{len(self.pauses)} collections "
                f"(generation 2: {sum(g == 2 for g, _ in self.pauses)}), "
                f"longest {longest[1]:.4f}s (generation {longest[0]})")


def round_gaps(run: serve.Run, n: int = 3) -> List:
    """The ``n`` longest host-clock gaps between rounds in the window:
    (seconds, offset of the gap's end from the window's open, steps)."""
    ts = [run.t_open] + [r.t for r in run.window_rounds()]
    gaps = [(b - a, b - run.t_open, r.steps) for a, b, r in
            zip(ts, ts[1:], run.window_rounds())]
    return [tuple(round(x, 4) for x in g)
            for g in sorted(gaps, key=lambda g: -g[0])[:n]]


def percentile_ms(x: np.ndarray, q: float) -> Optional[float]:
    return float(np.percentile(x, q) * 1e3) if len(x) else None


def end_to_end(run: serve.Run, seconds: float, setup_s: float) -> Dict:
    return {"output_tok_s": run.tokens_in_window() / seconds,
            "itl_p95_ms": percentile_ms(run.itl_s(), 95),
            "setup_s": setup_s}


def per_layer(cell, counts, run: serve.Run, s: Dict, peaks: Dict, red,
              end: float) -> Dict:
    """Every per-layer metric of the cell its reader finds; ``counts`` is
    the cell's family's (``bench/counts/<family>.py``)."""
    ctx = types.SimpleNamespace(
        s=s, peaks=peaks, counts=counts, trace=red,
        rounds=run.window_rounds(end), window_s=end - run.t_open)
    out = {}
    for m in cell.per_layer:
        v = manifest.metric_reader(cell.root, m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = float(v)
    return out


def run_cell(root: Path, argv: List[str], t_process: float,
             require_chip: bool = True, control: bool = False) -> Dict:
    """Everything but the printing; returns the result line's object."""
    args = parse(argv)
    cell = manifest.resolve(root, args.workload)
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if require_chip and dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform}")
    if len(devs) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips, "
                         f"found {len(devs)}")
    peaks = manifest.peaks(root, dev.device_kind) if require_chip else {
        "bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    log(f"compile cache: {compile_cache(root)}")
    compiles, pauses = CompileCounter(), GcPauses()

    from bench.lib import program
    family = manifest.family(cell)
    s = family.reference.sizes(cell.config)
    cfg = family.adapter.arch_config(cell.config, s)
    dep = cell.config["deployment"]
    key = seed_key(args.seed)
    params = program.make_params(cfg, family.reference, family.adapter, s,
                                 key)
    eng, api = program.make_engine(cfg, params, dep)
    items = traffic.generate(cell.traffic, s["V"], args.seed)
    w = serve.warm_up(api, items, dep["max_batch"], eng.ecfg.block_size,
                      args.seed, s["V"])
    log(f"warm-up: {w}")

    run = serve.Run(api, eng, items)
    k = cell.traffic["window"]["opens_after_finished"]
    k = dep["max_batch"] if k == "max_batch" else int(k)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None

    def on_open():
        # set-up's objects (weights' wrappers, programs, the queued
        # traffic) leave the collector's generations: a full collection
        # in the window walks only what the window made
        gc.freeze()
        compiles.armed = pauses.armed = True
        if trace_dir:
            from bench.lib import trace
            trace.start(trace_dir)

    run.drive(args.seconds, lambda r: len(r.t_done) >= k, on_open)
    end = run.rounds[-1].t
    if trace_dir:
        jax.profiler.stop_trace()
    compiles.close()
    pauses.close()
    gc.unfreeze()
    setup_s = run.t_open - t_process
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": max((d.memory_stats() or {}).get(
                  "peak_bytes_in_use", 0) for d in devs)}
    log(f"window {run.t_open - run.t_start:.3f}s after the traffic "
        f"started; {len(run.window_rounds())} rounds; "
        f"{compiles.count} programs traced in the window; "
        f"preemptions {eng.preemptions}; bytes_limit "
        f"{stats.get('bytes_limit')}")
    log(f"in the window: gc {pauses.summary()}; longest gaps between "
        f"rounds (s, at s, steps) {round_gaps(run)}")
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
              "device": device}
    attempted = run.attempted()
    failed = [i for i in attempted if run.handles[i].done
              and run.finish_reason(i) not in ("length", "stop")]
    result["attempted"], result["failed"] = len(attempted), len(failed)
    if args.trace:
        from bench.lib import trace
        red = trace.reduce(trace.find(trace_dir), chips=list(range(
            cell.chips)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = red.busy_s, red.window_s
        vals = per_layer(cell, family.counts, run, s, peaks, red, end)
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        result["breakdown"] = {
            "device_ops": sorted(([n, v] for n, v in red.op_s.items()),
                                 key=lambda x: -x[1])[:10],
            "idle_gaps": sorted(([n, v] for n, v in red.idle_by_host
                                 .items()), key=lambda x: -x[1])[:10]}
        log(f"programs: {sorted(red.program_s.items(), key=lambda x: -x[1])}"
            f" executions {red.program_count}")
        log(f"kernels: {red.kernel_s} calls {red.kernel_count}")
        log(f"longest idle gaps: {red.longest_gaps}")
    else:
        vals = end_to_end(run, args.seconds, setup_s)
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in vals.items()
                         if k in units and v is not None}
    result["window_compiles"] = compiles.count

    # the correctness comparison: sample, free the program, reference
    finished = run.finished_in_window()
    lengths = {i: len(run.output(i)) for i in finished}
    pick = check.sample(np.random.default_rng([int(args.seed) % (1 << 64),
                                               3]),
                        finished, lengths, int(cell.traffic["check_requests"]))
    reqs, short = [], 0
    for i in pick:
        out = run.output(i)
        if run.finish_reason(i) != "length" \
                or len(out) != items[i].max_new:
            short += 1
        reqs.append((items[i].prompt, out))
    served = sum(len(o) for _, o in reqs)
    for leaf in jax.tree.leaves((eng.pool.pool, params)):
        leaf.delete()
    del api, eng, run, params
    gc.collect()
    gap, gap_ctl = check.logit_gaps(family.reference, s, key, reqs,
                                    dep["max_model_len"], control=control)
    if control:
        # the control in the program's place: its tokens are compared
        result["program_max_logit_gap"] = gap
        gap = gap_ctl
    limits = cell.limits
    checks = {
        "max_logit_gap": {"value": gap,
                          "limit": limits["max_logit_gap"]["limit"]},
        "short_outputs": {"value": short, "limit": 0},
        "sampled_tokens": {"value": served,
                           "limit": limits["min_sampled_tokens"]}}
    result["correct"] = bool(
        pick and gap <= checks["max_logit_gap"]["limit"]
        and short == 0 and served >= limits["min_sampled_tokens"])
    result["checks"] = checks
    return result


def print_result(result: Dict):
    for name, c in result["checks"].items():
        cmp = ">=" if name == "sampled_tokens" else "<="
        log(f"check {name} = {c['value']!r} (must be {cmp} {c['limit']!r})")
    print(json.dumps(result), flush=True)


def main(root: Path, argv: List[str], t_process: float) -> int:
    try:
        result = run_cell(root, argv, t_process)
    except SystemExit as e:
        if e.code in (None, 0):
            raise
        log(f"FAIL: {e.code}")
        return 2
    print_result(result)
    return 0
