"""The observability layer's engine-facing half.

:class:`Observability` is the session-scoped facade: one
:class:`~repro.serving.obs.trace.Tracer` (one timeline) and one
:class:`EngineObserver` per replica. Attach it to a bare engine, a
:class:`~repro.serving.cluster.ReplicatedCluster`, or a
:class:`~repro.serving.api.ServingAPI`; detached engines pay a single
``self.obs is not None`` check per hook site.

:class:`EngineObserver` is the per-replica hook sink the engine calls:

* lifecycle hooks (``on_admit`` / ``on_first_token`` / ``on_finish`` /
  ``on_preempt`` / ``on_shed``) become request-thread trace spans and
  instants;
* ``end_step`` emits the replica's KV/batch counter tracks and feeds the
  memory auditor and the windows.

No hook synchronizes with the device or times a step: where the time of
a step goes is read from a profiler trace of the program's spans
(``repro.serving.obs.trace.span``), on the device's clock.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Union

from repro.serving.obs.auditor import MemoryGapAuditor
from repro.serving.obs.series import DEFAULT_SERIES_MAXLEN
from repro.serving.obs.trace import DEFAULT_MAX_EVENTS, Tracer
from repro.serving.obs.windows import (
    SLO, STREAM_BATCH, STREAM_DEADLINE, STREAM_E2E, STREAM_ITL, STREAM_KV,
    STREAM_TOKENS, STREAM_TTFT, STREAM_WASTE_RESERVED, STREAM_WASTE_USED,
    SLOMonitor, WindowAggregator)
from repro.serving.workload import FINISH_DEADLINE


class EngineObserver:
    """Hook sink for one replica (``engine.obs``)."""

    def __init__(self, parent: "Observability", pid: int,
                 series_maxlen: int = DEFAULT_SERIES_MAXLEN):
        self.parent = parent
        self.pid = pid
        self.trace: Tracer = parent.trace
        # per-step pool-byte attribution (opt-in: Observability(
        # audit_memory=True)); fed from end_step
        self.auditor: Optional[MemoryGapAuditor] = \
            MemoryGapAuditor(series_maxlen) if parent.audit_memory else None
        # request-thread timeline anchors (tracer seconds)
        self._t_decode: Dict[int, float] = {}
        self._named: set = set()

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
    def on_admit(self, req):
        """The ``queued`` span: arrival to ``state.t_admitted`` (both on
        the serving timeline), ending now on the tracer's. After a
        preemption it runs from arrival too, as TTFT does."""
        t = self.trace.now()
        wait = max(req.state.t_admitted - req.arrival_s, 0.0)
        self.trace.span("queued", t - wait, t, pid=self.pid,
                        tid=self._tid(req),
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
        w = self.parent.windows
        if w is not None:
            if req.state.t_done is not None:
                w.push(STREAM_E2E, t, req.state.t_done - req.arrival_s)
            w.push(STREAM_DEADLINE, t,
                   1.0 if reason == FINISH_DEADLINE else 0.0)

    def on_preempt(self, req):
        # recompute-preemption: the decode span (if any) ends here and the
        # request re-enters the queue until its next admit
        t = self.trace.now()
        tid = self._tid(req)
        t0 = self._t_decode.pop(req.req_id, None)
        if t0 is not None:
            self.trace.span("decode", t0, t, pid=self.pid, tid=tid,
                            cat="lifecycle", args={"req": req.req_id,
                                                   "preempted": True})
        self.trace.instant("preempt", t, pid=self.pid, tid=tid,
                           cat="lifecycle", args={"req": req.req_id})

    def on_shed(self, req, reason: str):
        self.trace.instant("shed", self.trace.now(), pid=self.pid,
                           tid=self._tid(req), cat="lifecycle",
                           args={"req": req.req_id, "reason": reason})

    def event(self, name: str, args: Optional[dict] = None, *,
              tid: int = 0, cat: str = "cluster"):
        """Generic instant on this replica's track (cluster-level events:
        redrive / quarantine / respawn / watchdog)."""
        self.trace.instant(name, self.trace.now(), pid=self.pid, tid=tid,
                           cat=cat, args=args)

    # ------------------------------------------------------ step hooks --
    def on_spec(self, eng, *, drafted: int, accepted: int, committed: int):
        """One speculative verify step committed: counter track for the
        acceptance stream (drafted vs accepted vs committed per step —
        committed > batch is the speculation win made visible)."""
        self.trace.counter("speculation", self.trace.now(),
                           {"drafted": drafted, "accepted": accepted,
                            "committed": committed},
                           pid=self.pid)

    def end_step(self, eng, n_prefill: int, n_decode: int,
                 itl_s: Optional[float] = None):
        """One engine step committed ``n_decode`` decode tokens (and ran
        ``n_prefill`` prompt tokens): counter tracks, the memory audit and
        the windows' feed. ``itl_s`` is the step's inter-token time as the
        engine's ``itl_samples`` record it (None on a prefill-only step).
        Overlap mode calls it at commit time."""
        t_now = self.trace.now()
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
            if n_decode and itl_s is not None:
                w.push(STREAM_ITL, t_now, itl_s)
            w.push(STREAM_KV, t_now, eng.pool.manager.used_fraction)
            w.push(STREAM_BATCH, t_now, n_decode)
            w.push(STREAM_TOKENS, t_now, n_decode + n_prefill)
            if wb is not None:
                w.push(STREAM_WASTE_USED, t_now, wb.used_bytes)
                w.push(STREAM_WASTE_RESERVED, t_now,
                       wb.reserved_unused_bytes)


class Observability:
    """Session-scoped observability: tracer + per-replica observers, and
    the export entry points.

    ::

        obs = Observability()
        obs.attach(engine)              # or obs.attach_cluster(cluster)
        engine.run(reqs)
        obs.export_chrome_trace("trace.json")
        print(obs.summary())
    """

    def __init__(self, *, series_maxlen: int = DEFAULT_SERIES_MAXLEN,
                 max_events: int = DEFAULT_MAX_EVENTS,
                 audit_memory: bool = False,
                 windows: Union[bool, WindowAggregator, None] = None,
                 slos: Optional[Sequence[SLO]] = None):
        self.trace = Tracer(max_events=max_events)
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
            self.trace.name_thread(pid, 0, "engine")
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
        """Attached replicas, trace size, SLOs and memory-gap reports."""
        out = {
            "replicas": sorted(self.observers),
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
