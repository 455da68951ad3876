"""Regenerate the data-driven sections of EXPERIMENTS.md from artifacts
(experiments/dryrun/*.json, experiments/perf/*.json, experiments/paper/*)
and consolidate every ``experiments/paper/BENCH_*.json`` into one claim
summary table.

The BENCH consolidation is strict by design: a benchmark artifact that a
PR promised but never wrote, or one carrying NaN fields, fails the
report loudly (exit 1 with the offending paths) instead of producing a
table that silently reads as "all green".

    PYTHONPATH=src python -m benchmarks.report [--skip-experiments]
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
from typing import Dict, List, Tuple

from benchmarks.roofline_table import load_records

# every BENCH_*.json the benchmark suite is expected to have written;
# grows with each PR that adds a benchmarks/<name>.py artifact
REQUIRED_BENCHES = ("BENCH_faults.json", "BENCH_memgap.json",
                    "BENCH_overlap.json", "BENCH_speculative.json")

HISTORY_NAME = "BENCH_history.jsonl"


def fmt_case(r):
    return (f"| {r['arch']} | {r['shape']} | {r.get('variant','baseline')} "
            f"| {r['compute_s']*1e3:.2f} | {r['memory_s']*1e3:.2f} "
            f"| {r['collective_s']*1e3:.2f} | **{r['dominant']}** "
            f"| {r['useful_ratio']:.3f} "
            f"| {r['memory']['peak_bytes']/1e9:.2f} "
            f"| {r['memory'].get('peak_bytes_tpu_adjusted', 0)/1e9:.2f} |")


HEAD = ("| arch | shape | variant | C ms | M ms | X ms | dominant "
        "| useful | GB raw | GB tpu-adj |")
SEP = "|---" * 10 + "|"


def roofline_section() -> str:
    recs = load_records("experiments/dryrun")
    out = ["### Single-pod (16x16 = 256 chips) baseline — all 40 pairs", "",
           HEAD, SEP]
    skips = []
    for r in recs:
        if r["mesh"] != "pod16x16":
            continue
        if r["status"] == "skip":
            skips.append(f"* `{r['arch']} x {r['shape']}`: {r['reason']}")
            continue
        out.append(fmt_case(r))
    out += ["", "Documented skips:", *skips, "",
            "### Multi-pod (2x16x16 = 512 chips) — compile evidence", "",
            "| arch | shape | status | compile s | GB/chip (adj) |", "|---|---|---|---|---|"]
    for r in recs:
        if r["mesh"] != "pod2x16x16":
            continue
        if r["status"] == "skip":
            out.append(f"| {r['arch']} | {r['shape']} | SKIP | — | — |")
        else:
            out.append(
                f"| {r['arch']} | {r['shape']} | {r['status']} "
                f"| {r.get('compile_s','—')} "
                f"| {r['memory'].get('peak_bytes_tpu_adjusted',0)/1e9:.2f} |")
    return "\n".join(out)


def perf_section() -> str:
    recs = []
    for fn in sorted(glob.glob("experiments/perf/*.json")):
        with open(fn) as f:
            recs.append(json.load(f))
    if not recs:
        return "(no variant records yet)"
    out = [HEAD, SEP]
    for r in recs:
        if r["status"] == "ok":
            out.append(fmt_case(r))
    return "\n".join(out)


# ------------------------------------------------- BENCH consolidation --
def _walk_nan(obj, path: str, bad: List[str]):
    """Collect dotted paths of every NaN/Inf number in a JSON tree."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            _walk_nan(v, f"{path}.{k}" if path else str(k), bad)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            _walk_nan(v, f"{path}[{i}]", bad)
    elif isinstance(obj, float) and not math.isfinite(obj):
        bad.append(path)


def load_benches(dirname: str = "experiments/paper",
                 required: Tuple[str, ...] = REQUIRED_BENCHES
                 ) -> Dict[str, Dict]:
    """Load every BENCH_*.json; raise on required-but-missing files and
    on NaN/Inf fields anywhere in an artifact."""
    missing = [fn for fn in required
               if not os.path.exists(os.path.join(dirname, fn))]
    if missing:
        raise FileNotFoundError(
            f"required benchmark artifacts missing from {dirname}: "
            f"{missing} — run the corresponding benchmarks/<name>.py")
    benches: Dict[str, Dict] = {}
    for fn in sorted(glob.glob(os.path.join(dirname, "BENCH_*.json"))):
        with open(fn) as f:
            doc = json.load(f)
        bad: List[str] = []
        _walk_nan(doc, "", bad)
        if bad:
            raise ValueError(f"{fn} has non-finite fields: {bad[:10]}"
                             + (" ..." if len(bad) > 10 else ""))
        name = os.path.basename(fn)[len("BENCH_"):-len(".json")]
        benches[name] = doc
    return benches


def bench_table(benches: Dict[str, Dict]) -> str:
    """One consolidated claims table across every benchmark artifact."""
    lines = ["| bench | claim | pass |", "|---|---|---|"]
    for name, doc in benches.items():
        claims = {k: v for k, v in doc.items() if k.startswith("claim_")}
        if not claims:
            lines.append(f"| {name} | (no claims recorded) | — |")
        for k, v in sorted(claims.items()):
            mark = "PASS" if v else "**FAIL**"
            lines.append(f"| {name} | {k[len('claim_'):]} | {mark} |")
    return "\n".join(lines)


def bench_failures(benches: Dict[str, Dict]) -> List[str]:
    return [f"{name}:{k}" for name, doc in benches.items()
            for k, v in doc.items() if k.startswith("claim_") and not v]


# --------------------------------------------------- cross-run history --
def load_history(dirname: str = "experiments/paper") -> List[Dict]:
    """Read the JSONL trajectory benchmarks/run.py appends to."""
    path = os.path.join(dirname, HISTORY_NAME)
    if not os.path.exists(path):
        return []
    runs: List[Dict] = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                runs.append(json.loads(line))
            except json.JSONDecodeError as e:
                raise ValueError(
                    f"{path}:{i + 1} is not valid JSON ({e}); "
                    "the history file is append-only JSONL") from e
    return runs


def history_table(runs: List[Dict]) -> str:
    """Cross-run trend: one row per recorded benchmark invocation, plus
    a per-claim first/last transition summary so regressions across PRs
    stand out (a claim that was PASS and is now FAIL gets flagged)."""
    if not runs:
        return (f"(no {HISTORY_NAME} yet — benchmarks/run.py appends "
                "one record per invocation)")
    lines = ["| run | ts | suites | benches | claims pass | claims fail |",
             "|---|---|---|---|---|---|"]
    for i, r in enumerate(runs):
        suites = " ".join(a for a in r.get("argv", [])
                          if a.startswith("--")) or "(core)"
        lines.append(f"| {i} | {r.get('ts', '?')} | {suites} "
                     f"| {len(r.get('benches', []))} "
                     f"| {r.get('n_pass', 0)} | {r.get('n_fail', 0)} |")
    # per-claim trajectory: first seen -> latest
    first: Dict[str, bool] = {}
    last: Dict[str, bool] = {}
    for r in runs:
        for k, v in r.get("claims", {}).items():
            first.setdefault(k, bool(v))
            last[k] = bool(v)
    regressed = sorted(k for k in last if first[k] and not last[k])
    fixed = sorted(k for k in last if not first[k] and last[k])
    lines.append("")
    lines.append(f"{len(last)} distinct claims tracked over "
                 f"{len(runs)} run(s)")
    if regressed:
        lines.append("**REGRESSED** (passed earlier, failing latest): "
                     + ", ".join(regressed))
    if fixed:
        lines.append("fixed since first record: " + ", ".join(fixed))
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-experiments", action="store_true",
                    help="only consolidate BENCH_*.json; leave "
                         "EXPERIMENTS.md untouched")
    ap.add_argument("--bench-dir", default="experiments/paper")
    args = ap.parse_args()

    if not args.skip_experiments:
        text = open("EXPERIMENTS.md").read()
        for marker, gen in (("ROOFLINE_TABLE", roofline_section),
                            ("PERF_TABLE", perf_section)):
            begin = f"<!-- BEGIN {marker} -->"
            end = f"<!-- END {marker} -->"
            if begin in text:
                pre, rest = text.split(begin, 1)
                _, post = rest.split(end, 1)
                text = pre + begin + "\n" + gen() + "\n" + end + post
        with open("EXPERIMENTS.md", "w") as f:
            f.write(text)
        print("EXPERIMENTS.md regenerated")

    benches = load_benches(args.bench_dir)      # raises loudly
    print(bench_table(benches))
    print()
    print(history_table(load_history(args.bench_dir)))
    failed = bench_failures(benches)
    if failed:
        print(f"FAILED_CLAIMS: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
