"""Scheduler: admission / phase / preemption / deadline logic.

The engine used to be a monolith — ``ServingEngine.step()`` interleaved
admission, chunked-prefill budgeting, capacity preemption, the decode
launch, and the post-decode bookkeeping in one body. This module is the
*decision* half of that split: :class:`Scheduler` owns every piece of
request-phase state (arrival queue, PREFILLING and RUNNING sets, per-
request token/position bookkeeping) and compresses one engine iteration's
worth of decisions into a :class:`StepPlan` — the immutable work order the
:class:`~repro.serving.executor.Executor` dispatches.

Separation of concerns:

* the scheduler decides *what* runs this step (who is admitted, which
  prompt chunks stream in, who gets preempted for blocks, whose deadline
  expired, which requests take a decode token and at which positions);
* the executor decides *when results are fetched* (synchronously, or one
  step behind under double-buffered overlap);
* the engine keeps the compute methods (prefill/chunk jit calls, the
  finish protocol, pool plumbing) both halves call back into.

Overlap-aware planning: under ``EngineConfig.overlap`` a request's next
step is planned while its previous step's tokens are still in flight on
the device, so plans cannot consult token *values*. Everything a plan
needs is host-knowable:

* per-request ``_dispatched`` counts (tokens planned, including in-flight)
  gate length-finishes — a request is planned again iff
  ``dispatched < limit``, so the plan never speculates past the output
  budget;
* stop-token finishes are only discovered when the finishing step
  commits — a stop-finishing request wastes one speculative step per
  step still in flight (at most ``Executor.DEPTH``), whose tokens the
  executor discards (row invalidation) before they can reach
  ``output_tokens``; bit-identity with the synchronous loop holds
  because discarded tokens are never observable;
* write positions (``_pos``) advance at *plan* time under overlap (each
  plan pins the position its token will occupy), and at commit time in
  sync mode — in both modes ``_pos[rid]`` at plan time is the position
  the next dispatched token writes, so block-capacity checks read it
  identically.

Prefill stays scheduler-driven and synchronous in both modes: chunk
selection interleaves with block reservations and completions can free
blocks that change the very next reservation, so the scheduler drives the
engine's chunk compute inline (exact legacy ordering) and only the decode
dispatch is double-buffered.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional, TYPE_CHECKING

import numpy as np

from repro.serving.obs.trace import span
from repro.serving.workload import FINISH_DEADLINE, FINISH_SHED, Request

if TYPE_CHECKING:   # pragma: no cover - import cycle guard (typing only)
    from repro.serving.engine import ContinuousBatchingEngine


@dataclasses.dataclass(frozen=True)
class StepPlan:
    """One engine iteration's decode work order (immutable snapshot).

    ``reqs``/``rids``/``positions`` are parallel: request ``reqs[i]``
    takes one token at write position ``positions[i]``. ``positions``
    are pre-advance (the slot this step's token occupies). A plan with
    no decode rows (``rids == []``) is a prefill-only / idle iteration.

    ``t0``/``t_sched`` carry the step timer anchors so telemetry
    attributes the schedule phase to the right wall-clock span even when
    the plan commits an iteration later (overlap mode).
    """
    step: int                     # engine step_count that produced it
    now: float                    # serving-timeline stamp of the plan
    reqs: List[Request]
    rids: List[int]
    positions: List[int]
    n_prefill: int                # prompt tokens computed this iteration
    t0: float                     # perf_counter at step start
    t_sched: float                # schedule phase (admission + prefill) s
    p0: int                       # engine.preemptions before this step
    # speculative verify plan: per-row draft token arrays, parallel to
    # ``reqs`` (possibly empty per row — a verify batch may mix drafted
    # and undrafted requests). None = plain one-token decode step.
    drafts: Optional[List[np.ndarray]] = None

    @property
    def has_decode(self) -> bool:
        return bool(self.rids)


class Scheduler:
    """Owns request-phase state and produces one StepPlan per iteration.

    All state the engine historically kept on itself lives here now; the
    engine re-exports it through delegating properties so existing tests,
    the cluster's recovery ladder, and router load views keep working
    unchanged (``eng.waiting`` *is* ``eng.sched.waiting``).
    """

    def __init__(self, engine: "ContinuousBatchingEngine"):
        self.eng = engine
        self.waiting: deque = deque()
        self.running: List[Request] = []
        # PREFILLING phase (chunked mode): admitted requests whose prompt
        # is still streaming into the pool, FCFS; _prefilled tracks how
        # many prompt tokens are already written
        self.prefilling: List[Request] = []
        self._prefilled: Dict[int, int] = {}
        self._tokens: Dict[int, int] = {}    # rid -> next input token
        self._pos: Dict[int, int] = {}       # rid -> write position
        # rid -> output tokens planned for dispatch, including in-flight
        # uncommitted ones. In sync mode this equals state.generated after
        # every step; under overlap it runs one ahead while a step is in
        # flight. Length-finishes are gated on it so plans never run past
        # a request's output budget.
        self._dispatched: Dict[int, int] = {}
        # deadlines are only scanned for when at least one admitted
        # request carries one (keeps the deadline-free hot loop unchanged)
        self._has_deadlines = False
        # overlap + speculation: iterations since the last pipeline-drain
        # probe (see plan() — chained rows hide their committed history
        # from the drafter, so the pipeline is periodically drained)
        self._spec_probe = 0

    # ----------------------------------------------- admission control --
    def estimated_queue_delay_s(self) -> float:
        """Rough wait estimate for a newly queued request: tokens already
        committed ahead of it (queued prompts + their output budgets)
        over the recently measured token throughput. Zero until the
        engine has decode samples to estimate from — admission control
        never sheds on a cold start."""
        eng = self.eng
        itl = eng.itl_samples[-32:]
        toks = eng.decode_token_samples[-32:]
        if not itl or not sum(toks):
            return 0.0
        tok_per_s = sum(toks) / max(sum(itl), 1e-9)
        ahead = sum(r.prompt_len + r.sampling.max_new_tokens
                    for r in self.waiting)
        return ahead / tok_per_s

    def shed_check(self, req: Request, now: float) -> Optional[str]:
        """Would admission control reject ``req`` submitted at ``now``?

        Returns the shed reason (``queue_full`` / ``kv_pressure`` /
        ``queue_delay`` / ``deadline_unmeetable``) or None to accept.
        Pure — the caller decides whether to actually shed. All policies
        default off; an engine with no shedding knobs and no deadlines
        accepts everything.
        """
        eng = self.eng
        ecfg = eng.ecfg
        if ecfg.max_waiting is not None \
                and len(self.waiting) >= ecfg.max_waiting:
            return "queue_full"
        if ecfg.shed_kv_fraction is not None and self.waiting \
                and eng.pool.manager.used_fraction >= ecfg.shed_kv_fraction:
            return "kv_pressure"
        if ecfg.shed_queue_delay_s is not None or req.sampling.has_deadline:
            est = self.estimated_queue_delay_s()
            if ecfg.shed_queue_delay_s is not None \
                    and est > ecfg.shed_queue_delay_s:
                return "queue_delay"
            # a request whose queue wait alone already blows its own
            # deadline would only be admitted to expire — reject now so
            # the caller can fail fast / try elsewhere
            dl = req.sampling.ttft_deadline_s
            if dl is None:
                dl = req.sampling.deadline_s
            if dl is not None and max(now, req.arrival_s) + est \
                    > req.arrival_s + dl:
                return "deadline_unmeetable"
        return None

    def shed_request(self, req: Request, now: float, reason: str):
        """Stamp a rejected request (it never entered any queue): KV-free
        by construction, finished with ``finish_reason="shed"``."""
        eng = self.eng
        req.state.finish_reason = FINISH_SHED
        req.state.t_done = max(now, req.arrival_s)
        eng.shed += 1
        eng.shed_reasons[reason] = eng.shed_reasons.get(reason, 0) + 1
        if eng.obs is not None:
            eng.obs.on_shed(req, reason)

    # -------------------------------------------------------- deadlines --
    def expire_deadlines(self, now: float):
        """Finish every request past its SLO, whichever phase it is in:
        queued (never starts), PREFILLING (partial prompt KV released),
        or decoding (partial output kept, blocks + prefix-cache pins
        released this same step — the abort/reclaim path; under overlap
        an already-dispatched step's rows for the victim are invalidated
        so the stale tokens never commit). Gated on ``_has_deadlines``
        so deadline-free serving pays nothing."""
        if not self._has_deadlines:
            return
        eng = self.eng
        for lst in (self.waiting, self.prefilling, self.running):
            expired = [r for r in lst if r.sampling.expired(
                r.arrival_s, now,
                first_token=r.state.t_first_token is not None)]
            for req in expired:
                lst.remove(req)
                self._prefilled.pop(req.req_id, None)
                eng._finish(req, max(now, req.arrival_s),
                            reason=FINISH_DEADLINE)
                eng.deadline_expired += 1

    # -------------------------------------------------------- admission --
    def admit(self, now: float):
        eng = self.eng
        mgr = eng.pool.manager
        if eng.faults is not None and eng.faults.steals_allocation(
                eng.replica_id, eng.step_count):
            # injected transient allocation failure: admission skips a
            # step (requests wait, shed, or expire — never a crash)
            return
        while (self.waiting
               and len(self.running) + len(self.prefilling)
               < eng.ecfg.max_batch
               and self.waiting[0].arrival_s <= now):
            req = self.waiting[0]
            # the prefix cache turns part of the prompt into shared blocks:
            # only the uncached suffix consumes free blocks. Pin the hit
            # with bare increfs *before* any eviction can reclaim the
            # matched nodes — incref doesn't touch tables/version, so a
            # capacity-blocked head request retrying every step does not
            # invalidate the cached device block-table upload.
            hit: List[int] = []
            if eng.prefix is not None:
                hit = eng.prefix.match(req.prompt)
                for b in hit:
                    mgr.incref(b)
            n_cached = len(hit) * eng.ecfg.block_size
            if eng.chunking:
                # chunked admission reserves only the first chunk's
                # blocks — the rest of the prompt streams in chunk by
                # chunk through prefill_step's watermark-checked extends
                first = min(eng.ecfg.prefill_chunk_tokens,
                            req.prompt_len + 1 - n_cached)
                need_new = mgr.blocks_needed(n_cached + first) - len(hit)
            else:
                need_new = mgr.blocks_needed(req.prompt_len + 1) - len(hit)
            short = need_new + mgr.watermark_blocks - mgr.free_blocks
            # only flush warm cache entries when eviction can plausibly
            # close the whole gap (cached_blocks is an upper bound on the
            # evictable count) — an oversized head request must not wipe
            # other tenants' cached prefixes just to stay queued anyway
            if eng.prefix is not None \
                    and 0 < short <= eng.prefix.cached_blocks:
                eng.prefix.evict(short)
            if mgr.free_blocks - need_new < mgr.watermark_blocks:
                for b in hit:               # unpin (cache ref remains)
                    mgr.decref(b)
                if not self.running and not self.prefilling:
                    # nothing in flight will ever free a block: flushing
                    # the whole cache is the only way forward; if even
                    # that cannot fit the head request, fail loudly
                    # instead of spinning forever
                    evictable = (eng.prefix.cached_blocks
                                 if eng.prefix is not None else 0)
                    if (mgr.free_blocks + evictable - need_new
                            < mgr.watermark_blocks):
                        from repro.serving.engine import RequestTooLarge
                        raise RequestTooLarge(
                            f"KV pool exhausted: request {req.req_id} "
                            f"(prompt_len={req.prompt_len}) needs "
                            f"{need_new} blocks but the idle pool has "
                            f"{mgr.free_blocks} free ({mgr.num_blocks} "
                            f"total, {mgr.watermark_blocks} reserved) — "
                            f"raise kv_pool_tokens or lower max_model_len",
                            req.req_id)
                    eng.prefix.evict(need_new + mgr.watermark_blocks
                                     - mgr.free_blocks)
                    continue                # retry the same head request
                break
            self.waiting.popleft()
            req.state.t_admitted = max(now, eng._now(now))
            if eng.obs is not None:
                eng.obs.on_admit(req)
            if hit:
                mgr.share(req.req_id, hit)
                for b in hit:               # table ref replaces the pin
                    mgr.decref(b)
            if eng.prefix is not None:
                eng.prefix.record_admit(req.prompt_len, n_cached)
            if eng.chunking:
                # actually take the blocks the capacity check above was
                # sized for — admission must be a *reservation*, or a
                # second admission in the same loop double-books the
                # same free blocks and forces churny preemption of
                # half-prefilled requests later
                mgr.extend(req.req_id, n_cached + first)
                self._prefilled[req.req_id] = n_cached
                self.prefilling.append(req)
                continue
            mgr.allocate(req.req_id, req.prompt_len + 1 - n_cached)
            # prefill emitted the first output token (int() inside
            # _complete_prefill synced), so TTFT is stamped there, not
            # at the first decode step
            eng._complete_prefill(req, eng._prefill(req, n_cached=n_cached),
                                  now)

    # ------------------------------------------------- chunked prefill --
    def prefill_step(self, now: float) -> int:
        """Run up to ``prefill_chunk_tokens`` prompt tokens of chunked
        prefill, FCFS across PREFILLING requests (leftover budget flows
        to the next request in line). Returns prompt tokens computed.

        This is the prefill half of the mixed step: together with the
        decode batch the engine dispatches right after, one engine
        iteration serves {every running decode} ∪ {<= budget prompt
        tokens}, so a long prompt can never freeze the decode loop for
        longer than one chunk.
        """
        eng = self.eng
        if not eng.chunking or not self.prefilling:
            return 0
        budget = eng.ecfg.prefill_chunk_tokens
        spent = 0
        while budget > 0 and self.prefilling:
            req = self.prefilling[0]
            rid = req.req_id
            done = self._prefilled[rid]
            remaining = req.prompt_len - done
            chunk = min(budget, remaining)
            final = chunk == remaining
            # final chunk also covers the first decode token's slot, the
            # same +1 the serial path allocates at admission
            target = done + chunk + (1 if final else 0)
            if not self._reserve_for_chunk(rid, target):
                break                    # strict FCFS: wait for blocks
            logits = eng._run_chunk(req, done, chunk)
            self._prefilled[rid] = done + chunk
            spent += chunk
            budget -= chunk
            if final:
                self.prefilling.pop(0)
                self._prefilled.pop(rid, None)
                eng._complete_prefill(req, logits, now)
        return spent

    def _reserve_for_chunk(self, rid: int, target_tokens: int) -> bool:
        """Extend ``rid``'s block table to cover ``target_tokens``,
        respecting the admission watermark. Under pressure: reclaim
        cache-only prefix blocks first; if nothing is decoding (so no
        block will free itself), preempt the youngest *other* prefilling
        request; a lone request that cannot fit fails loudly."""
        eng = self.eng
        mgr = eng.pool.manager
        while True:
            short = target_tokens - mgr.covered_tokens(rid)
            if short <= 0:
                return True
            need = mgr.blocks_needed(short)
            gap = need + mgr.watermark_blocks - mgr.free_blocks
            if eng.prefix is not None \
                    and 0 < gap <= eng.prefix.cached_blocks:
                eng.prefix.evict(gap)
            if mgr.can_allocate(short):
                mgr.extend(rid, target_tokens)
                return True
            if self.running:
                return False             # decode completions free blocks
            victims = [r for r in self.prefilling if r.req_id != rid]
            if not victims:
                from repro.serving.engine import RequestTooLarge
                raise RequestTooLarge(
                    "KV pool exhausted: a single request's prompt exceeds "
                    "pool capacity (raise kv_pool_tokens or lower "
                    "max_model_len)", rid)
            self.preempt(victims[-1])

    # ------------------------------------------------------- preemption --
    def preempt(self, req: Request):
        """Recompute-style preemption: release everything, requeue first.

        Works for RUNNING and half-PREFILLED requests alike (the caller
        removes it from ``running``; ``prefilling`` membership and chunk
        progress are cleared here) — re-admission redoes the prefix match
        and restreams the prompt, and greedy decode regenerates identical
        tokens. Under overlap any in-flight step rows for the victim are
        invalidated (the speculative tokens are discarded, never
        committed) and its dispatch counter resets with the rest of its
        state, so the recompute replays from the committed history only.
        """
        eng = self.eng
        rid = req.req_id
        if req in self.prefilling:
            self.prefilling.remove(req)
        self._prefilled.pop(rid, None)
        eng.pool.release(rid)
        self._tokens.pop(rid, None)
        self._pos.pop(rid, None)
        self._dispatched.pop(rid, None)
        eng._executor.invalidate(rid)
        if eng.speculator is not None:
            # drafter context is built from output history the requeue is
            # about to reset — drop it so re-admission starts clean
            eng.speculator.forget(rid)
        req.state.reset_for_requeue()
        self.waiting.appendleft(req)
        eng.preemptions += 1
        if eng.obs is not None:
            eng.obs.on_preempt(req)

    def _needs_step(self, req: Request) -> bool:
        """Does ``req`` take a decode token this step? Sync mode: every
        running request does (finished ones left at commit). Overlap:
        only while its planned output (committed + in-flight) is below
        the length budget — a request at its budget stays in ``running``
        until its final in-flight token commits, but is never planned
        again. A request with a speculative verify step in flight is
        never re-planned until that step commits — its committed length
        (and thus its next write position) depends on how many drafts
        are accepted, which only the commit knows."""
        if req.req_id in self.eng._executor._spec_pending:
            return False
        if not self.eng.ecfg.overlap:
            return True
        return self._dispatched.get(req.req_id, 0) < self.eng._limit(req)

    def ensure_step_capacity(self):
        """Make sure every request decoding this step can take its token.

        ``BlockManager.append_token`` may dip into the admission
        watermark reserve, so a request crossing a block boundary (or
        needing a copy-on-write fork of a shared tail block) with an
        empty free list would raise mid-step. Instead: first reclaim
        cache-only blocks from the prefix index (cold cached prefixes are
        the cheapest memory in the pool), then preempt half-prefilled
        requests youngest-first (no generated tokens lost, only partial
        prompt KV), then the *youngest* running requests (their blocks
        free immediately) until the survivors fit.
        """
        eng = self.eng
        mgr = eng.pool.manager
        while True:
            need = 0
            for r in self.running:
                if not self._needs_step(r):
                    continue
                pos = self._pos[r.req_id]
                if mgr.needs_block(r.req_id, pos + 1) \
                        or mgr.needs_cow(r.req_id, pos):
                    need += 1
            if need <= mgr.free_blocks:
                return
            if eng.prefix is not None \
                    and eng.prefix.evict(need - mgr.free_blocks):
                continue
            if self.prefilling:
                self.preempt(self.prefilling[-1])
                continue
            if len(self.running) <= 1:
                from repro.serving.engine import RequestTooLarge
                raise RequestTooLarge(
                    "KV pool exhausted: a single request exceeds pool "
                    "capacity (raise kv_pool_tokens or lower max_model_len)",
                    self.running[0].req_id)
            self.preempt(self.running.pop())

    # ------------------------------------------------------------- plan --
    def plan(self, now: float) -> StepPlan:
        """One iteration's decisions: deadlines, admission, prefill work,
        capacity preemption, and the decode batch selection — everything
        the monolithic ``step()`` did before launching the decode jit.
        Raises exactly where the legacy step raised (``RequestTooLarge``
        from admission / capacity, injected faults are the engine's to
        raise before calling plan), always *before* any decode dispatch,
        so host bookkeeping stays consistent on the error paths.
        """
        with span("sched.plan"):
            return self._plan(now)

    def _plan(self, now: float) -> StepPlan:
        """The body of :meth:`plan`."""
        eng = self.eng
        t0 = time.perf_counter()
        pf0 = eng.prefill_tokens_computed
        p0 = eng.preemptions
        self.expire_deadlines(now)
        with span("sched.admit"):
            self.admit(now)
        self.prefill_step(now)
        n_prefill = eng.prefill_tokens_computed - pf0
        t_sched = time.perf_counter() - t0
        empty = StepPlan(step=eng.step_count, now=now, reqs=[], rids=[],
                         positions=[], n_prefill=n_prefill, t0=t0,
                         t_sched=t_sched, p0=p0)
        if not self.running:
            return empty
        if (eng.speculator is not None and eng.ecfg.overlap
                and any(r.req_id in eng._executor._chain
                        for r in self.running)):
            # device-chained rows hide their committed history from the
            # drafter (their newest tokens are still in flight), so under
            # overlap speculation could never engage after the first
            # plain dispatch. Probe: every spec_probe_every-th iteration
            # plan nothing — the executor drains the pipeline, and the
            # next plan sees fully committed context. While verify steps
            # run, rows are never chained and the probes cost nothing.
            self._spec_probe += 1
            if self._spec_probe >= eng.ecfg.spec_probe_every:
                self._spec_probe = 0
                return dataclasses.replace(
                    empty, t_sched=time.perf_counter() - t0)
        self.ensure_step_capacity()        # may preempt -> shrink running
        reqs = [r for r in self.running if self._needs_step(r)]
        if not reqs:
            return dataclasses.replace(
                empty, t_sched=time.perf_counter() - t0)
        rids = [r.req_id for r in reqs]
        drafts = (self._plan_drafts(reqs)
                  if eng.speculator is not None else None)
        spec = drafts is not None and any(len(d) for d in drafts)
        positions: List[int] = []
        # ensure capacity for the token being written this step, and fork
        # (copy-on-write) any shared block the write would land in. The
        # COW case is unreachable for engine-spliced prefixes (match()
        # shares only full blocks below prompt_len, and writes start at
        # prompt_len), so this is a two-dict-lookup guard for direct
        # pool.share users and future partial-tail sharing.
        for i, rid in enumerate(rids):
            pos = self._pos[rid]
            eng.pool.manager.append_token(rid, pos + 1)
            eng.pool.ensure_writable(rid, pos)
            positions.append(pos)
            if spec:
                # verify step: reserve the draft span on top of the base
                # token (shrinking the draft if blocks are short), count
                # the worst-case commit against the output budget —
                # corrected down to the actual commit at commit time —
                # and leave _pos alone: the committed length depends on
                # acceptance, which only the commit knows.
                k = self._reserve_span(rid, pos, len(drafts[i]))
                if k < len(drafts[i]):
                    drafts[i] = drafts[i][:k]
                self._dispatched[rid] = \
                    self._dispatched.get(rid, 0) + 1 + k
            else:
                self._dispatched[rid] = self._dispatched.get(rid, 0) + 1
                if eng.ecfg.overlap:
                    # the plan pins this token's position now; the commit
                    # (one iteration later) only appends the token value
                    self._pos[rid] = pos + 1
        return StepPlan(step=eng.step_count, now=now, reqs=reqs, rids=rids,
                        positions=positions, n_prefill=n_prefill, t0=t0,
                        t_sched=t_sched, p0=p0,
                        drafts=drafts if spec else None)

    # ------------------------------------------------------ speculation --
    def _plan_drafts(self, reqs: List[Request]) -> List[np.ndarray]:
        """Ask the drafter for a candidate span per planned request.

        Per-row cap: the verify step commits at least one token and at
        most ``1 + k``, so ``k`` is clipped to keep the worst case inside
        the request's remaining output budget. Under overlap a request
        whose previous plain step is still in flight gets no draft — its
        committed history (the drafter's input) is not host-known yet —
        and rides the verify batch as a draft-free row instead.
        """
        eng = self.eng
        chained = eng._executor._chain if eng.ecfg.overlap else {}
        drafts: List[np.ndarray] = []
        for r in reqs:
            rid = r.req_id
            cap = min(eng.ecfg.spec_k,
                      eng._limit(r) - self._dispatched.get(rid, 0) - 1)
            if cap < 1 or rid in chained:
                drafts.append(np.zeros((0,), np.int32))
            else:
                drafts.append(np.asarray(
                    eng.speculator.propose(r, cap), np.int32))
        return drafts

    def _reserve_span(self, rid: int, pos: int, k: int) -> int:
        """Reserve block capacity for a ``k``-token draft span on top of
        the already-reserved base token: the verify step writes KV at
        positions ``pos .. pos + k``. Speculation is opportunistic, so
        the span *shrinks* rather than dipping into the admission
        watermark reserve (``append_token`` may dip — a running request
        must always take its serial token; drafts must not erode that
        guarantee). Returns the reserved draft length; forks any shared
        block the span writes into (COW) so verify writes never alias
        another owner's data."""
        if k <= 0:
            return 0
        eng = self.eng
        mgr = eng.pool.manager
        while k > 0 and not mgr.can_extend(rid, pos + 1 + k):
            k -= 1
        if k == 0:
            return 0
        mgr.extend(rid, pos + 1 + k)
        bs = eng.ecfg.block_size
        for b in range(pos // bs + 1, (pos + k) // bs + 1):
            eng.pool.ensure_writable(rid, b * bs)
        return k
