"""The observability layer's engine-facing half.

:class:`Observability` is the session-scoped facade: one
:class:`~repro.serving.obs.trace.Tracer` (one timeline), one
:class:`~repro.serving.obs.roofline.StepCensusCache` (co-located replicas
share compiled buckets, so they share censuses), and one
:class:`EngineObserver` per replica. Attach it to a bare engine, a
:class:`~repro.serving.cluster.ReplicatedCluster`, or a
:class:`~repro.serving.api.ServingAPI`; detached engines pay a single
``self.obs is not None`` check per hook site — the always-on default
stays free.

:class:`EngineObserver` is the per-replica hook sink the engine calls:

* lifecycle hooks (``on_submit`` / ``on_admit`` / ``on_prefill`` /
  ``on_first_token`` / ``on_finish`` / ``on_preempt`` / ``on_shed``)
  become request-thread trace spans and instants;
* compute hooks (``on_prefill`` / ``on_decode``) carry the step variant's
  compile-time census plus measured dispatch/device time into
  :class:`~repro.serving.obs.roofline.LiveRoofline`;
* ``end_step`` closes the per-step phase breakdown —
  **schedule** (admission + prefill work before the decode launch, the
  engine's existing stall term), **dispatch** (host time to launch the
  decode jit), **device** (``block_until_ready`` on its outputs), and
  **host** (everything else: token bookkeeping, finish protocol) — and
  emits the replica's step span + KV/batch counter tracks.

Every hook is wrapped in a tight "no observer attached" early return on
the engine side, and the hooks themselves only append to bounded
structures — cheap enough to leave enabled (``benchmarks/observability.py``
pins the decode-step overhead at <= 5%).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Union

from repro.core.hardware import Hardware, local_hardware
from repro.serving.obs.auditor import MemoryGapAuditor
from repro.serving.obs.roofline import (LiveRoofline, StepCensus,
                                        StepCensusCache)
from repro.serving.obs.series import DEFAULT_SERIES_MAXLEN, BoundedSeries
from repro.serving.obs.trace import DEFAULT_MAX_EVENTS, Tracer
from repro.serving.obs.windows import (
    SLO, STREAM_BATCH, STREAM_DEADLINE, STREAM_E2E, STREAM_ITL, STREAM_KV,
    STREAM_TOKENS, STREAM_TTFT, STREAM_WASTE_RESERVED, STREAM_WASTE_USED,
    SLOMonitor, WindowAggregator)
from repro.serving.workload import FINISH_DEADLINE


@dataclasses.dataclass(frozen=True)
class StepPhases:
    """One engine step's time, attributed to its phases (seconds).

    Synchronous steps (``overlapped=False``): ``schedule + dispatch +
    device + host == total`` up to clock granularity; on a decode-less
    (prefill-only) step dispatch and device are zero and the prefill work
    sits inside schedule.

    Overlapped steps (``overlapped=True``, executor commit path): host
    work runs concurrently with device execution, so the phases are
    *attributions*, not a partition of ``total_s``. ``total_s`` is the
    dispatch-call cadence (step N's dispatch to step N+1's dispatch — the
    device-facing step period); ``device_s`` is the estimated span the
    device spent exclusively on this step; ``gap_s`` is the device idle
    time between the previous step's completion and this step's dispatch
    (the host-induced bubble overlap exists to close — the term
    ``host_gap_fraction`` sums for overlapped steps); ``dispatch_ahead_s``
    is how far *before* the previous step completed this one was already
    dispatched (the overlap win, 0 in sync mode by construction).
    """
    step: int
    schedule_s: float
    dispatch_s: float
    device_s: float
    host_s: float
    total_s: float
    overlapped: bool = False
    dispatch_ahead_s: float = 0.0
    gap_s: float = 0.0
    # prefill tokens admitted in the same iteration: 0 marks a pure
    # decode steady-state step (prefill work sits inside schedule_s, so
    # steady-state analyses filter on it)
    n_prefill: int = 0


class EngineObserver:
    """Hook sink for one replica (``engine.obs``)."""

    def __init__(self, parent: "Observability", pid: int,
                 series_maxlen: int = DEFAULT_SERIES_MAXLEN):
        self.parent = parent
        self.pid = pid
        self.trace: Tracer = parent.trace
        self.census: StepCensusCache = parent.census
        self.roofline = LiveRoofline(parent.hw, maxlen=series_maxlen)
        self.phases: BoundedSeries = BoundedSeries(series_maxlen)
        # per-step pool-byte attribution (opt-in: Observability(
        # audit_memory=True)); fed from end_step
        self.auditor: Optional[MemoryGapAuditor] = \
            MemoryGapAuditor(series_maxlen) if parent.audit_memory else None
        # request-thread timeline anchors (tracer seconds)
        self._t_submit: Dict[int, float] = {}
        self._t_decode: Dict[int, float] = {}
        self._named: set = set()
        # the decode compute hook's payload, consumed by end_step
        self._decode_pending = None   # (sc, t0, t1, t2, batch)

    # ------------------------------------------------------------ naming --
    def _tid(self, req) -> int:
        """Request lifecycle rows: tid = req_id + 1 (tid 0 = step track)."""
        rid = req.req_id
        tid = rid + 1
        if rid not in self._named:
            self._named.add(rid)
            self.trace.name_thread(self.pid, tid, f"req {rid}")
        return tid

    # ------------------------------------------------- lifecycle hooks --
    def on_submit(self, req):
        self._t_submit[req.req_id] = self.trace.now()

    def on_admit(self, req):
        t = self.trace.now()
        t0 = self._t_submit.pop(req.req_id, t)
        self.trace.span("queued", t0, t, pid=self.pid, tid=self._tid(req),
                        cat="lifecycle",
                        args={"req": req.req_id,
                              "arrival_s": req.arrival_s,
                              "prompt_len": req.prompt_len})

    def on_first_token(self, req):
        t = self.trace.now()
        self.trace.instant("first_token", t, pid=self.pid,
                           tid=self._tid(req), cat="lifecycle",
                           args={"req": req.req_id})
        self._t_decode[req.req_id] = t
        w = self.parent.windows
        if w is not None and req.state.t_first_token is not None:
            w.push(STREAM_TTFT, t,
                   req.state.t_first_token - req.arrival_s)

    def on_finish(self, req, reason: str):
        t = self.trace.now()
        tid = self._tid(req)
        t0 = self._t_decode.pop(req.req_id, None)
        if t0 is not None:
            self.trace.span("decode", t0, t, pid=self.pid, tid=tid,
                            cat="lifecycle",
                            args={"req": req.req_id,
                                  "generated": req.state.generated})
        self.trace.instant(f"finish:{reason}", t, pid=self.pid, tid=tid,
                           cat="lifecycle", args={"req": req.req_id})
        self._t_submit.pop(req.req_id, None)
        w = self.parent.windows
        if w is not None:
            if req.state.t_done is not None:
                w.push(STREAM_E2E, t, req.state.t_done - req.arrival_s)
            w.push(STREAM_DEADLINE, t,
                   1.0 if reason == FINISH_DEADLINE else 0.0)

    def on_preempt(self, req):
        # recompute-preemption: the decode span (if any) ends here and the
        # request re-enters the queue — the next admit opens a fresh
        # queued span from this instant
        t = self.trace.now()
        tid = self._tid(req)
        t0 = self._t_decode.pop(req.req_id, None)
        if t0 is not None:
            self.trace.span("decode", t0, t, pid=self.pid, tid=tid,
                            cat="lifecycle", args={"req": req.req_id,
                                                   "preempted": True})
        self.trace.instant("preempt", t, pid=self.pid, tid=tid,
                           cat="lifecycle", args={"req": req.req_id})
        self._t_submit[req.req_id] = t

    def on_shed(self, req, reason: str):
        self.trace.instant("shed", self.trace.now(), pid=self.pid,
                           tid=self._tid(req), cat="lifecycle",
                           args={"req": req.req_id, "reason": reason})
        self._t_submit.pop(req.req_id, None)

    def event(self, name: str, args: Optional[dict] = None, *,
              tid: int = 0, cat: str = "cluster"):
        """Generic instant on this replica's track (cluster-level events:
        redrive / quarantine / respawn / watchdog)."""
        self.trace.instant(name, self.trace.now(), pid=self.pid, tid=tid,
                           cat=cat, args=args)

    # --------------------------------------------------- compute hooks --
    def on_prefill(self, req, variant: str, sc: Optional[StepCensus],
                   t0: float, t1: float, t2: float, tokens: int):
        """One prefill compute call (serial / prefix / chunk).

        ``t0``/``t1``/``t2`` are raw ``perf_counter`` stamps: call start,
        dispatch return, outputs ready. Emits the compute span on the
        request's lifecycle row and records a roofline sample (prefill
        variants get attributed exactly like decode steps — the paper's
        compute-bound counterpoint to the memory-bound decode)."""
        e = self.trace.epoch
        self.trace.span(variant, t0 - e, t2 - e, pid=self.pid,
                        tid=self._tid(req), cat="compute",
                        args={"req": req.req_id, "tokens": tokens,
                              "dispatch_us": (t1 - t0) * 1e6})
        self.roofline.record(step=0, sc=sc, device_s=t2 - t1, batch=tokens,
                             variant=variant)

    def on_decode(self, sc: Optional[StepCensus], t0: float, t1: float,
                  t2: float, batch: int, variant: str = "decode"):
        """The decode jit call just ran: stash its census + timing for
        this step's ``end_step`` (which owns the step/roofline emit).
        ``variant`` names the roofline bucket ("decode" for the plain
        step, "spec_verify" for the fused speculative verify)."""
        self._decode_pending = (sc, t0, t1, t2, batch, variant)

    def on_spec(self, eng, *, drafted: int, accepted: int, committed: int):
        """One speculative verify step committed: counter track for the
        acceptance stream (drafted vs accepted vs committed per step —
        committed > batch is the speculation win made visible)."""
        self.trace.counter("speculation", self.trace.now(),
                           {"drafted": drafted, "accepted": accepted,
                            "committed": committed},
                           pid=self.pid)

    # --------------------------------------------------------- end step --
    def end_step(self, eng, t0: float, t_sched_s: float, n_prefill: int,
                 n_decode: int):
        """Close one engine step: phase breakdown, step span, counters,
        and the decode roofline sample. ``t0`` is the raw ``perf_counter``
        stamp the engine's step timer started at; ``t_sched_s`` the
        schedule phase it already measured (its stall term)."""
        t_end = time.perf_counter()
        e = self.trace.epoch
        total_s = t_end - t0
        dispatch_s = device_s = 0.0
        pend = self._decode_pending
        if pend is not None:
            sc, d0, d1, d2, batch, variant = pend
            self._decode_pending = None
            dispatch_s, device_s = d1 - d0, d2 - d1
            self.roofline.record(step=eng.step_count, sc=sc,
                                 device_s=device_s, batch=batch,
                                 variant=variant)
            self.trace.span("dispatch", d0 - e, d1 - e, pid=self.pid,
                            cat="phase")
            self.trace.span("device", d1 - e, d2 - e, pid=self.pid,
                            cat="phase")
            self.trace.span("host", d2 - e, t_end - e, pid=self.pid,
                            cat="phase")
        host_s = max(total_s - t_sched_s - dispatch_s - device_s, 0.0)
        self.phases.append(StepPhases(
            step=eng.step_count, schedule_s=t_sched_s,
            dispatch_s=dispatch_s, device_s=device_s, host_s=host_s,
            total_s=total_s, n_prefill=n_prefill))
        self.trace.span("schedule", t0 - e, t0 - e + t_sched_s,
                        pid=self.pid, cat="phase")
        self.trace.span(f"step {eng.step_count}", t0 - e, t_end - e,
                        pid=self.pid, cat="step",
                        args={"step": eng.step_count, "decode": n_decode,
                              "prefill_tokens": n_prefill})
        t_now = t_end - e
        self.trace.counter("kv_used_fraction", t_now,
                           {"used": eng.pool.manager.used_fraction},
                           pid=self.pid)
        self.trace.counter("batch", t_now,
                           {"decoding": n_decode,
                            "prefilling": len(eng.prefilling),
                            "waiting": len(eng.waiting)},
                           pid=self.pid)
        # memory-gap audit + windowed feed (both opt-in; see windows.py)
        wb = None
        if self.auditor is not None:
            wb = self.auditor.on_step(eng, n_decode=n_decode)
            self.trace.counter("kv_waste_bytes", t_now,
                               {"used": wb.used_bytes,
                                "block_pad": wb.block_pad_bytes,
                                "prefix_held": wb.prefix_held_bytes,
                                "free": wb.free_bytes,
                                "reserved_unused": wb.reserved_unused_bytes},
                               pid=self.pid)
        w = self.parent.windows
        if w is not None:
            if n_decode:
                w.push(STREAM_ITL, t_now, total_s)
            w.push(STREAM_KV, t_now, eng.pool.manager.used_fraction)
            w.push(STREAM_BATCH, t_now, n_decode)
            w.push(STREAM_TOKENS, t_now, n_decode + n_prefill)
            if wb is not None:
                w.push(STREAM_WASTE_USED, t_now, wb.used_bytes)
                w.push(STREAM_WASTE_RESERVED, t_now,
                       wb.reserved_unused_bytes)

    # ----------------------------------------------- end step (overlap) --
    def end_step_overlap(self, eng, *, step: int, t0: float,
                         t_sched_s: float, n_prefill: int, n_decode: int,
                         sc: Optional[StepCensus], batch: int,
                         t_call: float, t_ret: float, dev0: float,
                         dev1: float, gap_s: float,
                         dispatch_ahead_s: float, total_s: float,
                         host_s: float, variant: str = "decode"):
        """Close one *overlapped* engine step, called by the executor at
        commit time (one iteration after the dispatch it describes).

        ``t_call``/``t_ret`` bound the dispatch call; ``dev0``/``dev1``
        bound the estimated exclusive device span (event-estimate based —
        see ``Executor._commit``); ``total_s`` is the dispatch cadence.
        A fully invalidated speculative step commits nothing and emits no
        sample at all (its device time was wasted speculation, already
        visible as a preemption/abort event on the lifecycle track)."""
        e = self.trace.epoch
        device_s = max(dev1 - dev0, 0.0)
        self.roofline.record(step=step, sc=sc, device_s=device_s,
                             batch=batch, variant=variant)
        self.trace.span("schedule", t0 - e, t0 - e + t_sched_s,
                        pid=self.pid, cat="phase")
        self.trace.span("dispatch", t_call - e, t_ret - e, pid=self.pid,
                        cat="phase")
        if device_s > 0:
            self.trace.span("device", dev0 - e, dev1 - e, pid=self.pid,
                            cat="phase")
        if gap_s > 0:
            # device idle between the previous step's completion and this
            # dispatch — the bubble the overlap is meant to close
            self.trace.span("gap", t_call - gap_s - e, t_call - e,
                            pid=self.pid, cat="phase")
        self.phases.append(StepPhases(
            step=step, schedule_s=t_sched_s, dispatch_s=t_ret - t_call,
            device_s=device_s, host_s=host_s, total_s=total_s,
            overlapped=True, dispatch_ahead_s=dispatch_ahead_s,
            gap_s=gap_s, n_prefill=n_prefill))
        t_end = time.perf_counter()
        self.trace.span(f"step {step}", t0 - e, t_end - e, pid=self.pid,
                        cat="step",
                        args={"step": step, "decode": n_decode,
                              "prefill_tokens": n_prefill,
                              "overlapped": True,
                              "dispatch_ahead_us": dispatch_ahead_s * 1e6,
                              "gap_us": gap_s * 1e6})
        t_now = t_end - e
        self.trace.counter("kv_used_fraction", t_now,
                           {"used": eng.pool.manager.used_fraction},
                           pid=self.pid)
        self.trace.counter("batch", t_now,
                           {"decoding": n_decode,
                            "prefilling": len(eng.prefilling),
                            "waiting": len(eng.waiting)},
                           pid=self.pid)
        wb = None
        if self.auditor is not None:
            wb = self.auditor.on_step(eng, n_decode=n_decode)
            self.trace.counter("kv_waste_bytes", t_now,
                               {"used": wb.used_bytes,
                                "block_pad": wb.block_pad_bytes,
                                "prefix_held": wb.prefix_held_bytes,
                                "free": wb.free_bytes,
                                "reserved_unused": wb.reserved_unused_bytes},
                               pid=self.pid)
        w = self.parent.windows
        if w is not None:
            if n_decode:
                w.push(STREAM_ITL, t_now, total_s)
            w.push(STREAM_KV, t_now, eng.pool.manager.used_fraction)
            w.push(STREAM_BATCH, t_now, n_decode)
            w.push(STREAM_TOKENS, t_now, n_decode + n_prefill)
            if wb is not None:
                w.push(STREAM_WASTE_USED, t_now, wb.used_bytes)
                w.push(STREAM_WASTE_RESERVED, t_now,
                       wb.reserved_unused_bytes)

    # ----------------------------------------------------------- views --
    def phase_summary(self) -> dict:
        """Mean seconds per phase over retained steps + the host-gap
        fraction — the paper's host-bottleneck indicator, live. For
        synchronous steps the numerator is host + dispatch time (device
        provably idle while they run); for overlapped steps it is the
        measured device-idle ``gap_s`` (host work that fits under device
        execution no longer counts — that's the point of the overlap)."""
        n = len(self.phases)
        if n == 0:
            return {"steps": 0, "schedule_s": 0.0, "dispatch_s": 0.0,
                    "device_s": 0.0, "host_s": 0.0, "total_s": 0.0,
                    "dispatch_ahead_s": 0.0, "gap_s": 0.0,
                    "host_gap_fraction": 0.0}
        tot = sum(p.total_s for p in self.phases)
        mean = lambda f: sum(f(p) for p in self.phases) / n  # noqa: E731
        host = sum(p.gap_s if p.overlapped else p.host_s + p.dispatch_s
                   for p in self.phases)
        return {"steps": self.phases.appended,
                "schedule_s": mean(lambda p: p.schedule_s),
                "dispatch_s": mean(lambda p: p.dispatch_s),
                "device_s": mean(lambda p: p.device_s),
                "host_s": mean(lambda p: p.host_s),
                "total_s": mean(lambda p: p.total_s),
                "dispatch_ahead_s": mean(lambda p: p.dispatch_ahead_s),
                "gap_s": mean(lambda p: p.gap_s),
                "host_gap_fraction": host / max(tot, 1e-12)}

    def summary(self) -> dict:
        return {"replica": self.pid,
                "phases": self.phase_summary(),
                "roofline": self.roofline.summary(),
                "decode": self.roofline.summary("decode")}


class Observability:
    """Session-scoped observability: tracer + census cache + per-replica
    observers, and the export entry points.

    ::

        obs = Observability()           # peaks of the local device
        obs.attach(engine)              # or obs.attach_cluster(cluster)
        engine.run(reqs)
        obs.export_chrome_trace("trace.json")
        print(obs.summary())
    """

    def __init__(self, hw: Optional[Hardware] = None, *,
                 series_maxlen: int = DEFAULT_SERIES_MAXLEN,
                 max_events: int = DEFAULT_MAX_EVENTS,
                 audit_memory: bool = False,
                 windows: Union[bool, WindowAggregator, None] = None,
                 slos: Optional[Sequence[SLO]] = None):
        self.hw = hw or local_hardware()
        self.trace = Tracer(max_events=max_events)
        self.census = StepCensusCache()
        self.series_maxlen = series_maxlen
        self.observers: Dict[int, EngineObserver] = {}
        # memory-gap auditing: each attached replica gets a
        # MemoryGapAuditor fed from end_step (see obs/auditor.py)
        self.audit_memory = audit_memory
        # windowed telemetry: pass True for a default aggregator, an
        # aggregator to share one, or SLOs (which require windows)
        if isinstance(windows, WindowAggregator):
            self.windows: Optional[WindowAggregator] = windows
        elif windows or slos:
            self.windows = WindowAggregator()
        else:
            self.windows = None
        self.slo: Optional[SLOMonitor] = SLOMonitor(
            list(slos), self.windows, tracer=self.trace) if slos else None

    # ------------------------------------------------------------ attach --
    def attach(self, engine, pid: Optional[int] = None) -> EngineObserver:
        """Attach to one engine (idempotent per replica id): the engine's
        ``obs`` hook slot is pointed at this session's observer for its
        replica, so a respawned engine re-attaches to the same rows."""
        pid = engine.replica_id if pid is None else pid
        ob = self.observers.get(pid)
        if ob is None:
            ob = EngineObserver(self, pid, self.series_maxlen)
            self.observers[pid] = ob
            self.trace.name_process(pid, f"replica{pid}")
            self.trace.name_thread(pid, 0, "engine steps")
        engine.obs = ob
        return ob

    def attach_cluster(self, cluster) -> "Observability":
        cluster.obs = self
        for rep in cluster.replicas:
            self.attach(rep.engine, rep.idx)
        return self

    def attach_backend(self, backend) -> "Observability":
        """Attach to whatever a :class:`~repro.serving.api.ServingAPI`
        wraps (engine or cluster), duck-typed on ``replicas``."""
        if hasattr(backend, "replicas"):
            return self.attach_cluster(backend)
        self.attach(backend)
        return self

    def observer(self, pid: int = 0) -> Optional[EngineObserver]:
        return self.observers.get(pid)

    def replica_event(self, pid: int, name: str,
                      args: Optional[dict] = None):
        """Cluster-level instant on a replica's step track (redrive /
        quarantine / respawn / watchdog / evict) — no-op for a replica
        that was never attached."""
        ob = self.observers.get(pid)
        if ob is not None:
            ob.event(name, args)

    # ----------------------------------------------------------- export --
    def export_chrome_trace(self, path: str) -> str:
        """Write the session trace as Chrome-trace/Perfetto JSON."""
        return self.trace.export_chrome_trace(path)

    def summary(self) -> dict:
        """Per-replica phase + roofline summaries, plus census stats."""
        out = {
            "hardware": self.hw.name,
            "replicas": {pid: ob.summary()
                         for pid, ob in sorted(self.observers.items())},
            "census": {"compiles": self.census.compiles,
                       "errors": len(self.census.errors)},
            "trace": {"events": self.trace.n_events,
                      "dropped": self.trace.dropped},
        }
        if self.slo is not None:
            out["slo"] = self.slo.summary()
        gap = self.memory_gap_report()
        if gap:
            out["memory_gap"] = gap
        return out

    def memory_gap_report(self) -> Dict[int, dict]:
        """Per-replica end-of-run memory gap reports (empty unless
        ``audit_memory=True`` and steps ran)."""
        return {pid: ob.auditor.report()
                for pid, ob in sorted(self.observers.items())
                if ob.auditor is not None and ob.auditor.audits}

    def roofline_rows(self) -> List[str]:
        """Printable per-replica live-roofline lines."""
        out = []
        for pid, ob in sorted(self.observers.items()):
            s = ob.roofline.summary("decode")
            out.append(
                f"replica {pid}: decode steps={s['steps']} "
                f"bw_util={s['bw_util_mean'] * 100:.1f}% "
                f"mfu={s['mfu_mean'] * 100:.2f}% "
                f"ai={s['ai_mean']:.1f} flop/B bound={s['bound']}")
        return out
