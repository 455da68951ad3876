"""Benchmark driver — one entry per paper table/figure plus the measured
engine curves and the dry-run roofline aggregation.

    PYTHONPATH=src python -m benchmarks.run [--skip-engine]

Prints ``name,us_per_call,derived`` CSV lines and writes JSON artifacts to
experiments/paper/.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HISTORY_PATH = "experiments/paper/BENCH_history.jsonl"


RECORDS: list = []          # every bench row of the current invocation


def _run(name, fn, derive):
    t0 = time.perf_counter()
    out = fn()
    us = (time.perf_counter() - t0) * 1e6
    derived = derive(out)
    print(f"{name},{us:.0f},{derived}", flush=True)
    RECORDS.append({"bench": name, "us_per_call": round(us),
                    "derived": derived})
    return out


def append_history(records, claims, failures,
                   path: str = HISTORY_PATH) -> dict:
    """Append one JSONL record of this run's key claims so the benchmark
    trajectory accretes across PRs instead of being discarded.

    Each line: ``{"ts", "argv", "benches": [{bench, us_per_call,
    derived}], "claims": {name: bool}, "n_pass", "n_fail"}``."""
    rec = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
           "argv": sys.argv[1:],
           "benches": records,
           "claims": claims,
           "n_pass": sum(1 for v in claims.values() if v),
           "n_fail": len(failures)}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-engine", action="store_true",
                    help="skip the slow real-engine sweep")
    ap.add_argument("--datapath", action="store_true",
                    help="also run the decode data-path microbenchmark "
                         "(gather-copy vs zero-copy paged)")
    ap.add_argument("--prefix", action="store_true",
                    help="also run the prefix-cache reuse benchmark "
                         "(shared-system-prompt workload, cache on vs off)")
    ap.add_argument("--chunked", action="store_true",
                    help="also run the chunked-prefill HOL-blocking "
                         "benchmark (mixed long/short workload, chunked "
                         "vs serial prefill)")
    ap.add_argument("--stream", action="store_true",
                    help="also run the streaming-API smoke benchmark "
                         "(sampled vs greedy throughput, abort-reclaim "
                         "latency, stream==run token identity)")
    ap.add_argument("--faults", action="store_true",
                    help="also run the fault-tolerance benchmark (kill "
                         "1 of 2 replicas mid-run: redrive bit-identity, "
                         "goodput retention, graceful overload shedding)")
    ap.add_argument("--memgap", action="store_true",
                    help="also run the memory-gap auditor + SLO monitor "
                         "benchmark (exact pool accounting, reserved-"
                         "unused >= 2x used on worst-case budgets, SLO "
                         "breach/recovery latency, hook overhead)")
    ap.add_argument("--speculative", action="store_true",
                    help="also run the speculative-decoding benchmark "
                         "(prompt-lookup drafts + multi-token verify: "
                         "bit-identity, rollback accounting, small-batch "
                         "uplift)")
    ap.add_argument("--overlap", action="store_true",
                    help="also run the host-overlap benchmark "
                         "(scheduler/executor split: sync-vs-overlap "
                         "bit identity across six scenarios)")
    ap.add_argument("--no-history", action="store_true",
                    help="do not append this run's claims to "
                         + HISTORY_PATH)
    args, _ = ap.parse_known_args()

    from benchmarks import paper_claims as pc
    print("name,us_per_call,derived")
    failures = []
    claims = {}

    def claim(out, key):
        ok = bool(out.get(key, False))
        claims[key] = ok
        if not ok:
            failures.append(key)
        return f"{key}={ok}"

    _run("fig1_intensity", pc.fig1_arithmetic_intensity,
         lambda o: claim(o, "claim_attention_ai_constant") + ";" +
         claim(o, "claim_matmul_ai_grows"))
    _run("fig2_fig3_curves", pc.fig2_fig3_throughput_latency_kv,
         lambda o: claim(o, "claim_kv_knee_below_full_cache") +
         f";opt13b_kv90={o['opt-1.3b']['kv_fraction_for_90pct_T']:.2f}")
    _run("table1_phases", pc.table1_phase_importance,
         lambda o: claim(o, "claim_decode_dominates") +
         f";opt27b_decode_frac={o['opt-2.7b']['decode_fraction']:.3f}")
    _run("table2_roofline", pc.table2_roofline_values,
         lambda o: claim(o, "claim_attention_at_dram_roofline") +
         f";opt13b_bw_ratio={o['opt-1.3b']['bw_ratio']:.2f}")
    _run("fig8_stalls", pc.fig8_memory_stall_fraction,
         lambda o: claim(o, "claim_majority_memory_bound"))
    _run("table4_bca_replication", pc.table4_bca_and_replication,
         lambda o: claim(o, "claim_replication_beats_MAX") +
         f";opt13b_b_opt={o['opt-1.3b']['strict']['b_opt']}" +
         f";opt13b_gain={o['opt-1.3b']['best_gain_vs_MAX']:.2f}")

    if not args.skip_engine:
        from benchmarks.engine_curves import measured_curves
        _run("engine_measured_curves", measured_curves,
             lambda o: f"plateau_observed={o['plateau_observed']};" +
             o["bca_on_measured"].replace(" ", "_"))

    if args.datapath:
        from benchmarks.decode_datapath import sweep

        def _dp_derive(o):
            sp = next((r["speedup"] for r in o["rows"]
                       if r["batch"] >= 16), 0.0)
            return (f"zero_copy_wins_at_16={o['zero_copy_wins_at_16']};"
                    f"speedup_b16={sp:.2f}")

        _run("decode_datapath", sweep, _dp_derive)

    if args.prefix:
        from benchmarks.prefix_reuse import run_pair

        def _pfx_derive(o):
            for key in ("claim_prefill_2x", "claim_blocks_2x",
                        "claim_bit_identical"):
                claim(o, key)
            return (f"prefill_ratio={o['prefill_ratio']:.2f};"
                    f"blocks_ratio={o['blocks_ratio']:.2f};"
                    f"identical={o['tokens_identical']}")

        # reduced shape (the full acceptance run is the module's default)
        _run("prefix_reuse", lambda: run_pair(per_tenant=6), _pfx_derive)

    if args.chunked:
        from benchmarks.chunked_prefill import run_pair as chunked_pair

        def _chk_derive(o):
            for key in ("claim_itl_p95_2x", "claim_bit_identical",
                        "claim_throughput_within_10pct"):
                claim(o, key)
            return (f"itl_p95_ratio={o['itl_p95_ratio']:.2f};"
                    f"throughput_ratio={o['throughput_ratio']:.3f};"
                    f"identical={o['tokens_identical']}")

        # reduced shape (the full acceptance run is the module's default)
        _run("chunked_prefill",
             lambda: chunked_pair(n_short=8, n_long=4, long_len=512,
                                  short_new=16, long_new=4,
                                  chunk_tokens=128), _chk_derive)

    if args.stream:
        from benchmarks.stream_api import run_suite

        def _stream_derive(o):
            for key in ("claim_sampled_within_2x",
                        "claim_abort_reclaims_blocks",
                        "claim_stream_equals_run"):
                claim(o, key)
            return (f"sampled_over_greedy="
                    f"{o['throughput']['sampled_over_greedy']:.2f};"
                    f"abort_us="
                    f"{o['abort']['mid_decode']['abort_us']:.0f}")

        _run("stream_api", lambda: run_suite(smoke=True), _stream_derive)

    if args.faults:
        from benchmarks.fault_tolerance import run_suite as faults_suite

        def _faults_derive(o):
            for key in ("claim_full_completion", "claim_bit_identical",
                        "claim_redrive_happened", "claim_goodput_floor",
                        "claim_graceful_overload"):
                claim(o, key)
            ret = min(s["goodput_retention"] for s in o["scenarios"])
            return (f"min_goodput_retention={ret:.2f};"
                    f"shed={o['overload'].get('shed', 0)}")

        _run("fault_tolerance", lambda: faults_suite(smoke=True),
             _faults_derive)

    if args.memgap:
        from benchmarks.memory_gap import run_suite as memgap_suite

        def _memgap_derive(o):
            for key in ("claim_exact_accounting",
                        "claim_reserved_unused_2x",
                        "claim_slo_within_one_window",
                        "claim_overhead_le_5pct"):
                claim(o, key)
            return (f"resv_over_used="
                    f"{o['reserved_unused']['reserved_over_used']:.1f}x;"
                    f"overhead="
                    f"{o['overhead']['overhead_fraction'] * 100:.1f}%")

        _run("memory_gap", lambda: memgap_suite(smoke=True),
             _memgap_derive)

    if args.speculative:
        from benchmarks.speculative import run_suite as spec_suite

        def _spec_fn():
            out = spec_suite(n=6, prompt_len=64, max_new=32, repeats=1,
                             perf_max_new=64, gate_speedup=False)
            os.makedirs("experiments/paper", exist_ok=True)
            with open("experiments/paper/BENCH_speculative.json", "w") as f:
                json.dump(out, f, indent=1, default=float)
            return out

        def _spec_derive(o):
            # the deterministic claims gate here; the wall-clock speedup
            # gate binds only on the full shape (python -m
            # benchmarks.speculative) — shared runners are too noisy
            for key in ("claim_bit_identical_greedy",
                        "claim_bit_identical_sampled",
                        "claim_exact_accounting"):
                claim(o, key)
            return (f"speedup={o['speedup_x']:.2f}x;"
                    f"accept="
                    f"{o['speculative']['spec_acceptance_rate']:.2f};"
                    f"identical={o['perf_identical']}")

        _run("speculative", _spec_fn, _spec_derive)

    if args.overlap:
        from benchmarks.host_overlap import run_suite as overlap_suite

        def _overlap_derive(o):
            for key in ("claim_bit_identical_greedy",
                        "claim_bit_identical_sampled",
                        "claim_bit_identical_chunked",
                        "claim_bit_identical_prefix",
                        "claim_bit_identical_preempt",
                        "claim_bit_identical_faults"):
                claim(o, key)
            return "identical=" + str(sum(
                v for k, v in o.items() if k.startswith("claim_")))

        _run("host_overlap", lambda: overlap_suite(smoke=True),
             _overlap_derive)

    # §Roofline aggregation from the dry-run artifacts, if present
    from benchmarks.roofline_table import load_records, summary
    recs = load_records()
    if recs:
        s = summary(recs)
        print(f"roofline_table,0,ok={s['ok']};skip={s['skip']};"
              f"error={s['error']};dominant={s['dominant_histogram']}")
    else:
        print("roofline_table,0,no dryrun records yet "
              "(run python -m repro.launch.dryrun --all)")

    if not args.no_history:
        rec = append_history(RECORDS, claims, failures)
        print(f"history,0,appended {rec['n_pass']} pass / "
              f"{rec['n_fail']} fail to {HISTORY_PATH}")

    if failures:
        print(f"FAILED_CLAIMS: {failures}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
