"""Serving launcher — wires the whole paper loop:

    profile T(B)/L(B) -> BCA (Eq. 2) -> replication plan -> serve

With ``--replicas`` > 1 (or ``auto``) the launcher actually runs the
replicated cluster (serving.cluster) instead of a single engine, routing
requests with ``--policy``.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch opt-1.3b --reduced \
      --requests 24 --bca --replicas auto
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

# device memory kept out of the KV pool: the step programs' temporaries
# (logits, prefill activations, gathered prefixes), the compiled code and
# the allocator's fragmentation
POOL_HEADROOM_BYTES = 2 << 30
# pool budget where the device reports no memory limit (the CPU backend)
CPU_POOL_TOKENS = 1 << 16


def start_profile(path: str):
    """Start the JAX profiler writing under ``path``, with its Python
    function tracer off (it would slow the host it measures). The
    program's spans and the device land in one trace on one clock."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, create_perfetto_trace=True,
                             profiler_options=opts)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    other directory is set here; otherwise the cache is ``.jax_cache/`` at
    the checkout root, a fixed path so that later runs hit it."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def kv_pool_tokens(cfg, params, *, n_replicas: int = 1) -> int:
    """KV pool tokens per replica that fit beside ``params`` on the device.

    The pool gets the device's ``bytes_limit`` less the params' bytes and
    ``POOL_HEADROOM_BYTES``, split over the replicas that share the device
    and rounded down to a multiple of 64 tokens (whole blocks of any
    power-of-two size up to 64); all three numbers are printed. A device
    that reports no limit (the CPU) gets ``CPU_POOL_TOKENS``."""
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit is None:
        tokens = CPU_POOL_TOKENS // n_replicas // 64 * 64
        print(f"[pool] {dev.platform}: no device memory limit; fixed budget "
              f"{tokens} tokens per replica")
        return tokens
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    free = limit - param_bytes - POOL_HEADROOM_BYTES
    per_token = cfg.kv_bytes_per_token(jnp.dtype(cfg.dtype).itemsize)
    tokens = free // n_replicas // per_token // 64 * 64
    print(f"[pool] bytes_limit={limit} params={param_bytes} "
          f"headroom={POOL_HEADROOM_BYTES} -> {tokens} tokens "
          f"({tokens * per_token} B) per replica x {n_replicas}")
    if tokens < 64:
        raise SystemExit(f"no room for a KV pool on {dev.device_kind}")
    return tokens


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--bca", action="store_true",
                    help="pick max_batch via the Batching Configuration "
                         "Advisor over modeled curves")
    ap.add_argument("--slo-factor", type=float, default=2.0)
    ap.add_argument("--eps", type=float, default=0.1)
    ap.add_argument("--replicas", default="1",
                    help="'auto' = ReplicationPlanner decides")
    ap.add_argument("--policy", default="round-robin",
                    choices=("round-robin", "jsq", "least-kv",
                             "prefix-affinity"))
    ap.add_argument("--cluster-mode", default="thread",
                    choices=("thread", "sync"))
    ap.add_argument("--ctx", type=int, default=331)
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="N",
                    help="chunked prefill token budget per mixed step "
                         "(0 = serial admission-time prefill; -1 = size "
                         "the budget from the BCA curves' ITL headroom)")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="double-buffered overlapped stepping: dispatch "
                         "decode step N+1 while step N's tokens are in "
                         "flight (scheduler/executor split; outputs are "
                         "bit-identical to --no-overlap)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share KV blocks across prompts with a common "
                         "prefix (radix prefix cache; skips redundant "
                         "prefill and pool footprint)")
    ap.add_argument("--workload", default="sharegpt",
                    choices=("sharegpt", "repetitive"),
                    help="request mix: 'sharegpt' = independent "
                         "ShareGPT-like prompts; 'repetitive' = highly "
                         "self-repetitive template prompts (the "
                         "speculative-decoding target shape — pair with "
                         "--speculate)")
    ap.add_argument("--shared-prefix-tenants", type=int, default=0,
                    metavar="N",
                    help="serve a shared-system-prompt workload (N "
                         "tenants splitting --requests, 128-token shared "
                         "prefix + 24-token suffix each) instead of "
                         "independent ShareGPT-like prompts — the shape "
                         "where --prefix-cache and the prefix-affinity "
                         "policy actually pay off")
    ap.add_argument("--speculate", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="speculative decoding: draft-free prompt-lookup "
                         "drafter + multi-token verify over the paged pool "
                         "(outputs bit-identical to plain decode). Default "
                         "lets the BCA speculation advisor decide from the "
                         "break-even batch; --no-speculate forces it off")
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="max draft tokens per request per verify step "
                         "(0 = advisor's K, or the engine default when "
                         "--speculate was forced on)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax, "
                         "bit-identical to the pre-sampler engine)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus truncation (1.0 = disabled)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base RNG seed; request i samples from stream "
                         "seed+i (bit-reproducible across batch "
                         "composition and replicas)")
    ap.add_argument("--stream", action="store_true",
                    help="serve through the online facade (submit/"
                         "stream/drain) and print per-event token deltas "
                         "for the first request instead of a batch run")
    ap.add_argument("--deadline", type=float, default=0.0, metavar="S",
                    help="per-request end-to-end deadline in seconds "
                         "(0 = none); expired requests finish with "
                         "reason='deadline' and release KV immediately")
    ap.add_argument("--ttft-deadline", type=float, default=0.0,
                    metavar="S",
                    help="per-request time-to-first-token deadline in "
                         "seconds (0 = none); stops binding once the "
                         "first token is out")
    ap.add_argument("--inject-fault", action="append", default=[],
                    metavar="SPEC",
                    help="deterministic fault injection, e.g. "
                         "'replica=1,step=50' (kind defaults to kill; "
                         "also kind=delay,seconds=0.1 or "
                         "kind=alloc-fail); repeatable")
    ap.add_argument("--max-waiting", type=int, default=0, metavar="N",
                    help="bound each replica's arrival queue at N "
                         "requests (0 = unbounded); overflow is shed "
                         "with reason='shed', never an engine crash")
    ap.add_argument("--shed-kv", type=float, default=0.0, metavar="F",
                    help="shed new arrivals while free KV fraction is "
                         "below F and a backlog exists (0 = disabled)")
    ap.add_argument("--watchdog", type=float, default=0.0, metavar="S",
                    help="mark a replica wedged (and route around it) "
                         "when a step exceeds S seconds (0 = disabled; "
                         "cluster mode only)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record request-lifecycle spans and KV/batch "
                         "counters and write Chrome-trace/Perfetto JSON "
                         "to PATH (open in ui.perfetto.dev or "
                         "chrome://tracing)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="record a JAX profiler trace of the serving run "
                         "under DIR: the program's spans (api.*, "
                         "engine.*, sched.*, pool.*) and the device's "
                         "programs and ops on one clock (the .xplane.pb "
                         "for TensorBoard/XProf, perfetto_trace.json.gz "
                         "for ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the final metrics snapshot to PATH — "
                         "Prometheus text exposition if PATH ends in "
                         ".prom, versioned JSON otherwise ('-' = stdout)")
    ap.add_argument("--obs-interval", type=float, default=10.0,
                    metavar="S",
                    help="periodic metrics-emit interval for --metrics-out "
                         "during --stream serving (the batch path emits "
                         "once at the end)")
    ap.add_argument("--audit-memory", action="store_true",
                    help="attribute every KV pool byte per step (used / "
                         "block pad / prefix-held / free, plus the "
                         "reserved-unused and bucket-pad overlays) and "
                         "print the end-of-run memory gap report with the "
                         "BCA sizing cross-check")
    ap.add_argument("--slo-ttft", type=float, default=0.0, metavar="S",
                    help="TTFT objective in seconds: 95% of first tokens "
                         "within S, breach/recovery via multi-window burn "
                         "rates (0 = no TTFT SLO)")
    ap.add_argument("--slo-itl", type=float, default=0.0, metavar="S",
                    help="ITL objective in seconds: 95% of decode steps "
                         "within S (0 = no ITL SLO)")
    ap.add_argument("--dashboard", action="store_true",
                    help="live ANSI terminal dashboard (windowed "
                         "latencies, memory-gap bars, SLO burn rates); "
                         "renders a final frame on batch runs")
    ap.add_argument("--dashboard-html", default=None, metavar="PATH",
                    help="write the dashboard as a self-contained HTML "
                         "report (inline SVG charts) at end of run")
    args = ap.parse_args()

    import jax
    from jax import set_mesh
    from repro.configs import get_config, reduced
    from repro.core import (BatchingConfigurationAdvisor, ReplicationPlanner,
                            decode_curves, local_hardware, max_batch_for,
                            prefill_step_terms, replication_sweep,
                            slo_from_reference)
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model, init_params
    from repro.serving import (ContinuousBatchingEngine, EngineConfig,
                               SamplingParams, ServingAPI, sharegpt_like)
    from repro.sharding import rules_for

    enable_compile_cache()
    full_cfg = get_config(args.arch)
    hw = local_hardware()

    max_batch = args.max_batch
    prefill_chunk = args.prefill_chunk if args.prefill_chunk > 0 else None
    if args.bca:
        mb = max_batch_for(full_cfg, hw, ctx=args.ctx)
        curves = decode_curves(full_cfg, hw, ctx=args.ctx, max_batch=mb)
        slo = slo_from_reference(curves, 32, args.slo_factor)
        # modeled per-prompt-token prefill cost: lets BCA sweep the
        # chunked-prefill budget alongside max_batch (the ITL headroom
        # above the pure-decode step is the prefill time a mixed step
        # may spend)
        pf_tok_s = prefill_step_terms(full_cfg, 1, args.ctx,
                                      hw).step_s / args.ctx
        res = BatchingConfigurationAdvisor(
            curves, slo_s=slo, eps=args.eps,
            prefill_token_s=pf_tok_s).solve()
        print(f"[BCA] {res.summary()}")
        max_batch = res.b_opt
        if args.prefill_chunk < 0:
            prefill_chunk = res.chunk_tokens
            print(f"[BCA] prefill chunk budget: {prefill_chunk} tok/step")
    elif args.prefill_chunk < 0:
        raise SystemExit("--prefill-chunk -1 (auto) requires --bca")

    n_rep = None
    if args.replicas == "auto":
        plan = ReplicationPlanner(hw, full_cfg, ctx=args.ctx).plan(max_batch)
        n_rep = plan.n_replicas
        print(f"[replication] {plan.summary()}")
        for r in replication_sweep(full_cfg, hw, batch=max_batch,
                                   ctx=args.ctx, max_replicas=n_rep):
            print(f"[sim] {r.summary()}")
    else:
        n_rep = int(args.replicas)
    n_rep = max(1, n_rep)

    # speculative decoding: default is advisor-decided — speculate iff the
    # break-even math says the verify compute rides the memory gap at this
    # batch (small B), forced on/off by --speculate/--no-speculate
    speculate, spec_k = args.speculate, args.spec_k
    if speculate is None or (speculate and spec_k <= 0):
        from repro.core import speculation_advisor
        sp = speculation_advisor(full_cfg, hw, batch=max(max_batch, 1))
        print(f"[spec] advisor: {sp.summary()}")
        if speculate is None:
            speculate = sp.enabled
        if spec_k <= 0:
            spec_k = sp.k            # 0 = keep the engine default

    cfg = reduced(full_cfg) if args.reduced else full_cfg
    mesh = make_test_mesh()
    rules = rules_for(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    model = Model(cfg, rules)
    with set_mesh(mesh):
        # co-located replicas split the device's pool evenly
        ecfg = EngineConfig(max_batch=max_batch,
                            kv_pool_tokens=kv_pool_tokens(
                                cfg, params, n_replicas=n_rep),
                            max_model_len=512, prefill_bucket=64,
                            prefix_cache=args.prefix_cache,
                            overlap=args.overlap,
                            prefill_chunk_tokens=prefill_chunk,
                            max_waiting=args.max_waiting or None,
                            shed_kv_fraction=args.shed_kv or None,
                            speculate=bool(speculate),
                            **({"spec_k": spec_k} if spec_k > 0 else {}))
        sampling = SamplingParams(temperature=args.temperature,
                                  top_k=args.top_k, top_p=args.top_p,
                                  seed=args.seed,
                                  deadline_s=args.deadline or None,
                                  ttft_deadline_s=args.ttft_deadline
                                  or None)
        faults = None
        if args.inject_fault:
            from repro.serving import FaultInjector
            faults = FaultInjector.parse(*args.inject_fault)
            print(f"[faults] injecting {len(faults.specs)} fault(s): "
                  + "; ".join(str(s) for s in faults.specs))
        if args.shared_prefix_tenants > 0:
            from repro.serving import shared_prefix_workload
            # round per-tenant count up, then trim so exactly --requests
            # are served (the interleaved tail drops evenly across tenants)
            per = -(-args.requests // args.shared_prefix_tenants)
            reqs = shared_prefix_workload(
                args.shared_prefix_tenants, per, cfg.vocab_size,
                prefix_len=128, suffix_len=24, max_new_tokens=16,
                seed=0, sampling=sampling)[:args.requests]
        elif args.workload == "repetitive":
            from repro.serving import repetitive_workload
            reqs = repetitive_workload(
                args.requests, cfg.vocab_size, prompt_len=64,
                max_new_tokens=32, repeat_rate=1.0, phrase_len=8,
                pool_size=1, seed=0, sampling=sampling)
        else:
            reqs = sharegpt_like(args.requests, cfg.vocab_size, seed=0,
                                 mean_in=24, mean_out=32, max_len=256,
                                 sampling=sampling)
        if n_rep > 1:
            from repro.serving import ReplicatedCluster
            backend = ReplicatedCluster.colocated(
                model, params, ecfg, n_rep, policy=args.policy,
                mode=args.cluster_mode, faults=faults,
                watchdog_s=args.watchdog or None)
        else:
            backend = ContinuousBatchingEngine(model, params, ecfg)
            if faults is not None:
                # single engine = replica 0; kills surface as
                # InjectedFault (no peer to redrive onto)
                backend.faults = faults
        # runtime observability: lifecycle tracing attaches to the
        # backend; metrics snapshots go through the emitter; SLOs + the
        # dashboard ride the windows layer
        obs = emitter = dash = None
        slos = []
        if args.slo_ttft or args.slo_itl:
            from repro.serving import default_slos
            slos = default_slos(ttft_s=args.slo_ttft or None,
                                itl_s=args.slo_itl or None)
        want_dash = args.dashboard or args.dashboard_html
        if args.trace or args.metrics_out or args.audit_memory \
                or want_dash or slos:
            from repro.serving import MetricsEmitter, Observability
            obs = Observability(audit_memory=args.audit_memory,
                                windows=bool(want_dash or slos
                                             or args.audit_memory),
                                slos=slos or None)
            # crash-safe: everything recorded so far survives a replica
            # failure or ^C as a valid trace file
            obs.trace.autosave_path = args.trace
            obs.attach_backend(backend)
            if args.metrics_out:
                path = None if args.metrics_out == "-" else args.metrics_out
                fmt = "prom" if args.metrics_out.endswith(".prom") \
                    else "json"
                emitter = MetricsEmitter(path, fmt=fmt,
                                         interval_s=args.obs_interval)
            if want_dash:
                from repro.serving import Dashboard
                import io
                out = None if args.dashboard else io.StringIO()
                dash = Dashboard(obs, out=out)
        if args.profile:
            start_profile(args.profile)
        try:
            if args.stream:
                # online path: submit everything through the facade,
                # stream the first request's token deltas, drain the rest
                if n_rep > 1 and args.cluster_mode == "thread":
                    print("[stream] note: streaming steps replicas "
                          "cooperatively from the calling thread; "
                          "--cluster-mode thread applies only to the batch "
                          "run() path")
                api = ServingAPI(backend, obs=obs, emitter=emitter,
                                 dashboard=dash)
                handles = [api.submit(r) for r in reqs]
                for ev in api.stream(handles[0]):
                    print(f"[stream] req {ev.req_id} "
                          f"+{len(ev.new_token_ids)} "
                          f"tok {list(ev.new_token_ids)} "
                          f"finished={ev.finished} "
                          f"reason={ev.finish_reason}")
                api.drain()
                metrics = api.metrics()
            elif n_rep == 1 and obs is not None:
                # batch path through the facade so the SLO monitor,
                # emitter and dashboard tick during the run
                metrics = ServingAPI(backend, obs=obs, emitter=emitter,
                                     dashboard=dash).run(reqs)
            else:
                metrics = backend.run(reqs)
        except BaseException:
            # crash path (satellite of the tentpole's exception-safety
            # contract): flush the partial trace + last-known metrics
            # before propagating — the evidence must survive the failure
            if obs is not None:
                obs.trace.flush()
            if emitter is not None:
                try:
                    emitter.close()
                except Exception:
                    pass
            raise
        finally:
            if args.profile:
                jax.profiler.stop_trace()
                print(f"[profile] trace -> {args.profile}")
        if dash is not None:
            dash.close()
        if emitter is not None:
            emitter.emit(metrics)       # final end-of-run snapshot
            if args.metrics_out != "-":
                print(f"[obs] metrics -> {args.metrics_out} "
                      f"({emitter.emits} snapshot(s))")
        if obs is not None:
            if args.trace:
                obs.export_chrome_trace(args.trace)
                print(f"[obs] trace -> {args.trace} "
                      f"({obs.trace.n_events} events; open in "
                      f"ui.perfetto.dev)")
            if args.dashboard_html:
                from repro.serving.obs.dashboard import write_html_report
                write_html_report(obs, obs.trace.now(), args.dashboard_html,
                                  title=f"{args.arch} serving run")
                print(f"[obs] dashboard -> {args.dashboard_html}")
            if obs.slo is not None:
                s = obs.slo.summary()
                print(f"[slo] breaches={s['breaches']} "
                      f"recoveries={s['recoveries']} "
                      f"active={s['active'] or 'none'}")
                for e in obs.slo.events:
                    print(f"[slo] {e.row()}")
            for pid, rep in obs.memory_gap_report().items():
                mb = rep["mean_bytes"]
                pool = max(rep["pool_bytes"], 1)
                print(f"[memgap] replica {pid}: "
                      f"pool={pool / 2**20:.1f}MiB "
                      f"used={100 * mb['used'] / pool:.1f}% "
                      f"blk_pad={100 * mb['block_pad'] / pool:.1f}% "
                      f"pfx_held={100 * mb['prefix_held'] / pool:.1f}% "
                      f"free={100 * mb['free'] / pool:.1f}% | "
                      f"resv_unused={100 * mb['reserved_unused'] / pool:.1f}% "
                      f"worst={rep['worst_term']}")
                from repro.core.bca import audit_sizing
                sa = audit_sizing(
                    full_cfg, hw, args.ctx,
                    observed_tokens_per_req=max(
                        rep["peak_used_tokens_per_req"], 1.0))
                print(f"[memgap] replica {pid}: {sa.summary()}")
        if n_rep > 1:
            print(metrics.summary())
            return
    print(f"[engine] {metrics.row()}")
    print(f"[engine] {metrics.latency_row()}")
    print(f"[engine] {metrics.stall_row()}")
    print(f"[engine] {metrics.finish_row()}")
    if speculate:
        why = getattr(backend, "spec_disabled_reason", None)
        if why is not None:
            print(f"[spec] disabled: {why}")
        else:
            print(f"[spec] {metrics.spec_row()}")


if __name__ == "__main__":
    main()
