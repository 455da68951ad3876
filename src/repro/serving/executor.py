"""Executor: double-buffered overlapped step dispatch.

JAX dispatch is already asynchronous — a jitted call returns device
*futures* immediately and XLA executes in the background. The legacy
engine threw that away: every decode path called ``np.asarray`` /
``jax.block_until_ready`` on its outputs before doing host bookkeeping,
so scheduling, detokenization fan-out, and metrics all serialized with
device execution and the device idled for the whole host phase of every
step.

This module is the *dispatch* half of the scheduler/executor split. Under
``EngineConfig.overlap`` each engine iteration ``i`` runs:

    plan i   -> scheduler decisions on host state only (no token values)
    dispatch i -> launch the decode jit for plan i, non-blocking
    commit i-1 -> fetch step i-1's tokens (usually already on host),
                  run bookkeeping / finish protocol / telemetry

so the device computes step ``i`` while the host commits step ``i-1`` —
steady-state step time approaches ``max(host, device)`` instead of
``host + device``. Up to two steps stay in flight between iterations
(commit runs two behind dispatch): with a single buffered step the
device queue drains whenever one host iteration outruns one device step,
charging the next dispatch's host-side prep (view build, token chain,
sampling stack) as device idle; with two, the device only starves when
the host falls behind by *two* full steps.

Bit-identity with the synchronous loop (the acceptance bar every PR in
this repo holds decode changes to):

* step ``i``'s input token for a chained request is selected *on device*
  from step ``i-1``'s output vector (a tiny jitted ``where``/gather —
  :func:`_chain_tokens_fn`), so the values are the same ones the sync
  loop would have copied through the host;
* plans only consult host-knowable state (positions, dispatch counts,
  block tables) — see :mod:`repro.serving.scheduler`;
* a stop-token finish is discovered at commit time *after* later steps
  were dispatched: the finished request's rows in every still-in-flight
  step (at most two) are invalidated and their tokens discarded without
  ever touching ``output_tokens`` (committed-tokens-only semantics).
  Aborts, deadline expiries, and preemptions funnel through the same
  :meth:`Executor.invalidate`.

Pool safety under speculation: every pool mutation is a dispatched
``.at[].set`` chained through the donated pool pytree, so a discarded
step's KV writes land in blocks its victim owned at dispatch time; by the
time any new owner reads those blocks its own writes (dispatched later)
have been sequenced after them.

Error ordering (the one place overlap changes semantics): injected faults
(``faults.on_step``) and scheduler errors (``RequestTooLarge``) are raised
on the host at *plan* time, before any dispatch — exactly as in sync
mode. A genuine device-side error from step N, however, surfaces at the
deferred fetch during iteration N+1; the executor annotates the exception
with the originating engine step (``err.engine_step = N`` plus an
``add_note`` on Python >= 3.11) so attribution stays unambiguous.

Spans (``repro.serving.obs.trace.span``): ``engine.decode`` around
each dispatch, ``engine.fetch`` around each deferred token readback and
``engine.commit`` around each commit's bookkeeping, all inside the
engine's ``engine.step``.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.sampler import positions_array, stack_sampling
from repro.serving.obs.trace import span
from repro.serving.scheduler import StepPlan


def _chain_tokens_fn(prev, host_tokens, prev_rows, use_prev):
    """Step i's input tokens without a host round-trip: rows chained to
    the in-flight step i-1 gather from its (device) output vector, the
    rest come from host-committed values (prefill first tokens, tokens
    committed before a preemption re-admit)."""
    return jnp.where(use_prev, prev[prev_rows], host_tokens)


_chain_tokens = jax.jit(_chain_tokens_fn)


class InFlightStep:
    """One dispatched-but-uncommitted decode (or verify) step."""
    __slots__ = ("plan", "tokens", "batch", "valid", "oks")

    def __init__(self, plan: StepPlan, tokens, batch: int):
        self.plan = plan
        self.tokens = tokens          # device array; [batch_pad] or [B]
        # verify steps ([batch_pad, K+1] tokens): the acceptance mask
        self.oks = None
        self.batch = batch
        # per-row validity: rows are discarded (never committed) when
        # their request finishes / aborts / expires / is preempted while
        # the step is still in flight
        self.valid = [True] * batch


class Executor:
    """Owns the in-flight window (depth <= 2 between iterations — see
    ``DEPTH``) and the deferred fetch/commit path. Engine-internal: the
    engine's ``step()`` routes here when ``EngineConfig.overlap`` is set;
    sync mode never touches it (beyond the no-op ``invalidate`` calls in
    ``_finish``)."""

    # in-flight steps retained across iterations. 1 = classic double
    # buffering; 2 keeps the device queue non-empty unless the host falls
    # two full device steps behind, hiding the dispatch-prep bubble that
    # otherwise shows up as a ~host-prep-sized gap on every step where
    # the device finished early. Correctness does not depend on the
    # value: commits lag dispatches by DEPTH, row invalidation covers
    # every retained step, and the chain map always points at the newest
    # entry (see _input_tokens).
    DEPTH = 2

    def __init__(self, engine):
        self.eng = engine
        self._inflight: List[InFlightStep] = []
        # (key, device arrays) for the stacked sampling params: they are
        # frozen per request, so the stack only changes when the decode
        # batch's composition does (finish / admit / preempt)
        self._samp_cache: Tuple[Optional[Tuple], Optional[Tuple]] = \
            (None, None)
        # rid -> (entry, row): where an active request's newest
        # uncommitted token lives; consumed by the next dispatch, cleared
        # at commit / invalidation
        self._chain: Dict[int, Tuple[InFlightStep, int]] = {}
        # rids with a speculative verify step in flight: excluded from
        # planning until the commit pins their post-acceptance length
        self._spec_pending: set = set()
        self._t_last_commit: Optional[float] = None
        self._preempt_seen = 0

    # ---------------------------------------------------------- control --
    def reset(self):
        """Drop every in-flight step without committing (cluster
        quarantine: the pool is being rebuilt, the results are garbage)."""
        self._inflight.clear()
        self._chain.clear()
        self._spec_pending.clear()
        self._samp_cache = (None, None)
        self._t_last_commit = None

    def invalidate(self, rid: int):
        """A request left the active set (finish / abort / deadline /
        preempt / evict): discard its uncommitted in-flight rows so the
        speculative tokens never reach ``output_tokens``, and drop any
        step whose rows are now all dead (it commits nothing and emits no
        telemetry)."""
        self._chain.pop(rid, None)
        self._spec_pending.discard(rid)
        if not self._inflight:
            return
        for entry in list(self._inflight):
            hit = False
            for i, r in enumerate(entry.plan.rids):
                if r == rid and entry.valid[i]:
                    entry.valid[i] = False
                    hit = True
            if hit and not any(entry.valid):
                self._inflight.remove(entry)

    # ------------------------------------------------------------- step --
    def step(self, now: float) -> bool:
        """One overlapped iteration: plan i, dispatch i, commit i-1."""
        eng = self.eng
        plan = eng.sched.plan(now)
        if plan.has_decode:
            with span("engine.decode"):
                self._dispatch(plan)
        # commit everything beyond the retained window; with no new
        # dispatch this drains the pipeline (idle / prefill-only /
        # all-at-budget iterations still retire in-flight work)
        keep = self.DEPTH if plan.has_decode else 0
        while len(self._inflight) > keep:
            self._commit(self._inflight.pop(0))
        if not plan.has_decode and plan.n_prefill:
            # prefill-only iteration: same series the sync loop keeps
            eng.stall_samples.append(plan.t_sched)
            eng.prefill_token_samples.append(plan.n_prefill)
            eng.decode_token_samples.append(0)
            delta = max(0, eng.preemptions - self._preempt_seen)
            self._preempt_seen = eng.preemptions
            eng.preemption_samples.append(delta)
            eng.kv_fraction_samples.append(eng.pool.manager.used_fraction)
            eng.max_kv_fraction = max(eng.max_kv_fraction,
                                      eng.pool.manager.used_fraction)
            if eng.obs is not None:
                eng.obs.end_step(eng, n_prefill=plan.n_prefill, n_decode=0)
        return eng.busy or bool(self._inflight)

    # --------------------------------------------------------- dispatch --
    def _input_tokens(self, rids: List[int], pad: int):
        """Build the step's input-token vector ([pad] int32, on device).

        Chained rows (previous token still in flight) never touch the
        host; everything else reads the committed ``_tokens`` value —
        both paths carry the exact value the sync loop would pass."""
        eng = self.eng
        # steady-state fast path: every row chains to the newest in-flight
        # step at the same row index, so its output vector IS this step's
        # input — no host arrays, no chain jit. Padding lanes then carry
        # that step's (valid-vocab) pad samples instead of zeros, which is
        # unobservable: rows are independent through the model, pad rows
        # have length 0 and write to the trash slot, and commits only read
        # valid rows.
        if self._inflight:
            newest = self._inflight[-1]
            if newest.batch == len(rids) and newest.tokens.shape[0] == pad:
                for i, rid in enumerate(rids):
                    ch = self._chain.get(rid)
                    if ch is None or ch[0] is not newest or ch[1] != i:
                        break
                else:
                    return newest.tokens
        host = np.zeros((pad,), np.int32)
        use_prev = np.zeros((pad,), bool)
        prev_rows = np.zeros((pad,), np.int32)
        prev: Optional[InFlightStep] = None
        for i, rid in enumerate(rids):
            ch = self._chain.get(rid)
            if ch is not None:
                # every dispatch re-chains its whole batch to the newest
                # entry, and a rid excluded from a later plan is either at
                # its output budget (never planned again) or invalidated
                # (chain cleared) — so all chained rids share one
                # predecessor even with DEPTH > 1 in flight
                assert prev is None or prev is ch[0], \
                    "chained rows span two in-flight steps"
                prev = ch[0]
                use_prev[i] = True
                prev_rows[i] = ch[1]
            else:
                host[i] = eng._tokens[rid]
        put = self.eng.pool.put
        if prev is None:
            return put(host)
        return _chain_tokens(prev.tokens, *put((host, prev_rows, use_prev)))

    def _dispatch(self, plan: StepPlan):
        eng = self.eng
        if plan.drafts is not None:
            # speculative verify: rows are NOT chained (the committed
            # token count is acceptance-dependent, so no later plan can
            # consume their output positionally) — they sit out planning
            # via _spec_pending until the commit pins their length
            entry = self._dispatch_verify(plan)
            self._inflight.append(entry)
            self._spec_pending.update(plan.rids)
            return
        if eng.decode_mode == "paged":
            entry = self._dispatch_paged(plan)
        else:
            entry = self._dispatch_gather(plan)
        self._inflight.append(entry)
        for row, rid in enumerate(plan.rids):
            self._chain[rid] = (entry, row)

    def _dispatch_paged(self, plan: StepPlan) -> InFlightStep:
        """The zero-copy decode dispatch, fetch deferred: identical args
        to the sync ``_decode_paged`` (same jit, same buckets, same
        sampling stack), minus the ``np.asarray`` — the result stays a
        device future."""
        from repro.serving.engine import _pow2_bucket
        eng = self.eng
        rids, positions = plan.rids, plan.positions
        B = len(rids)
        max_blocks = max(len(eng.pool.manager.tables[rid]) for rid in rids)
        nb_pad = _pow2_bucket(max_blocks, lo=4)
        batch_pad = _pow2_bucket(B)
        view = eng.pool.view(rids, positions, nb_pad, batch_pad)
        tokens = self._input_tokens(rids, batch_pad)
        # sampling params are frozen per request: restack (and re-upload)
        # only when the batch composition changes, not every step
        skey = (tuple(rids), batch_pad)
        if self._samp_cache[0] != skey:
            temp, top_k, top_p, seed = stack_sampling(
                [r.sampling for r in plan.reqs], pad_to=batch_pad)
            self._samp_cache = (skey, eng.pool.put((temp, top_k, top_p,
                                                    seed)))
        next_tokens, new_pool = eng._paged_jit(
            eng.params, view.pool, view.tables, view.lengths,
            view.positions, view.slots, tokens, *self._samp_cache[1])
        eng._count_kernel_blocks(positions, batch_pad, nb_pad)
        if eng.obs is not None:
            eng._stash_buckets(rids, batch_pad, nb_pad)
        eng.pool.commit(new_pool)
        return InFlightStep(plan, next_tokens, batch=B)

    def _dispatch_verify(self, plan: StepPlan) -> InFlightStep:
        """Speculative verify dispatch, fetch deferred: same jit and
        bucketing as the engine's sync ``_verify_paged`` (speculation is
        gated on paged mode). Chained rows (previous plain step still in
        flight) ride draft-free with a device-chained input token."""
        from repro.serving.engine import _pow2_bucket
        from repro.serving.spec import stack_drafts
        eng = self.eng
        rids, positions = plan.rids, plan.positions
        B = len(rids)
        max_blocks = max(len(eng.pool.manager.tables[rid]) for rid in rids)
        nb_pad = _pow2_bucket(max_blocks, lo=4)
        batch_pad = _pow2_bucket(B)
        k_pad = _pow2_bucket(max((len(d) for d in plan.drafts), default=1),
                             lo=1)
        view = eng.pool.view(rids, positions, nb_pad, batch_pad)
        tokens = self._input_tokens(rids, batch_pad)
        draft_mat, draft_len = stack_drafts(plan.drafts, batch_pad, k_pad)
        skey = (tuple(rids), batch_pad)
        if self._samp_cache[0] != skey:
            temp, top_k, top_p, seed = stack_sampling(
                [r.sampling for r in plan.reqs], pad_to=batch_pad)
            self._samp_cache = (skey, eng.pool.put((temp, top_k, top_p,
                                                    seed)))
        ys, oks, new_pool = eng._spec_verify_jit(
            eng.params, view.pool, view.tables, view.lengths,
            view.positions, view.slots, tokens,
            *eng.pool.put((draft_mat, draft_len)), *self._samp_cache[1])
        if eng.obs is not None:
            eng._stash_buckets(rids, batch_pad, nb_pad)
        eng.pool.commit(new_pool)
        entry = InFlightStep(plan, ys, batch=B)
        entry.oks = oks
        return entry

    def _dispatch_gather(self, plan: StepPlan) -> InFlightStep:
        """Dense-copy fallback, fetch deferred: gather, decode, KV row
        scatter, and sampling are all device dispatches (the pool scatter
        is a ``.at[].set`` pytree map), so the whole step pipelines."""
        from repro.serving.engine import _bucket
        eng = self.eng
        rids, positions = plan.rids, plan.positions
        max_pos = max(positions)
        pad_blocks = eng.pool.manager.blocks_needed(
            _bucket(max_pos + 1, eng.ecfg.block_size * 4))
        view = eng.pool.gather(rids, pad_blocks)
        tokens = self._input_tokens(rids, len(rids))
        pos = eng.pool.put(np.asarray(positions, np.int32))
        logits, new_cache = eng._decode_jit(eng.params, view, tokens, pos)
        eng.pool.scatter_new_token(rids, positions, new_cache)
        next_tokens = eng._steps.sample(
            logits, *stack_sampling([r.sampling for r in plan.reqs]),
            positions_array([p + 1 for p in positions]))
        if eng.obs is not None:
            eng._stash_buckets(rids, len(rids), pad_blocks)
        return InFlightStep(plan, next_tokens, batch=len(rids))

    # ----------------------------------------------------------- commit --
    def _fetch(self, entry: InFlightStep, *arrays):
        """Host copies of ``arrays`` (already resident in steady state).
        A deferred device error is attributed to the step that produced
        it: the fetch is one iteration behind the dispatch."""
        plan = entry.plan
        try:
            with span("engine.fetch"):
                return [np.asarray(a) for a in arrays]
        except Exception as err:
            err.engine_step = plan.step
            if hasattr(err, "add_note"):
                kind = ("speculative verify" if plan.drafts is not None
                        else "step")
                err.add_note(
                    f"deferred device error from engine step {plan.step} "
                    f"({kind} dispatched under overlap; surfaced at the "
                    f"next iteration's commit)")
            raise

    def _commit(self, entry: InFlightStep):
        """Retire one in-flight step: fetch its tokens, run the legacy
        bookkeeping + finish protocol for every still-valid row, and
        stamp telemetry with commit-time semantics."""
        eng = self.eng
        plan = entry.plan
        if plan.drafts is not None:
            self._commit_verify(entry)
            return
        host_tokens, = self._fetch(entry, entry.tokens)
        with span("engine.commit"):
            # serving-timeline completion stamp, mirroring sync's
            # ``now + dt``
            t_done = plan.now + (time.perf_counter() - plan.t0)
            n_valid = 0
            for i, r in enumerate(plan.reqs):
                if not entry.valid[i]:
                    continue
                n_valid += 1
                rid = r.req_id
                tok = int(host_tokens[i])
                eng._tokens[rid] = tok
                ch = self._chain.get(rid)
                if ch is not None and ch[0] is entry:
                    del self._chain[rid]
                r.state.generated += 1
                r.state.output_tokens.append(tok)
                # may _finish -> invalidate(rid): the request's
                # speculative row in the step dispatched moments ago dies
                eng._finish_or_run(r, t_done)
            eng.running = [r for r in eng.running
                           if r.state.finish_reason is None]
            if n_valid:
                self._telemetry(plan, n_valid, n_valid)

    def _commit_verify(self, entry: InFlightStep):
        """Retire one in-flight speculative verify step: fetch tokens +
        acceptance mask, release the rows back to planning, and delegate
        the token-by-token commit / rollback to the engine's shared
        ``_spec_commit`` (rows invalidated while the step was in flight
        are skipped — their blocks are already released)."""
        eng = self.eng
        plan = entry.plan
        ys, oks = self._fetch(entry, entry.tokens, entry.oks)
        with span("engine.commit"):
            for rid in plan.rids:
                self._spec_pending.discard(rid)
            t_done = plan.now + (time.perf_counter() - plan.t0)
            n_valid = sum(entry.valid)
            committed = eng._spec_commit(plan, ys, oks, t_done,
                                         valid=entry.valid)
            if n_valid:
                # tokens-per-commit can exceed the batch: the speculation
                # win
                self._telemetry(plan, n_valid, committed)

    def _telemetry(self, plan: StepPlan, n_valid: int, n_tokens: int):
        """The sync loop's per-step series, with commit-time semantics
        (ITL = inter-commit cadence: what a streaming client observes)."""
        eng = self.eng
        t = time.perf_counter()
        dt = t - (self._t_last_commit if self._t_last_commit is not None
                  else plan.t0)
        self._t_last_commit = t
        eng.itl_samples.append(dt)
        eng.stall_samples.append(plan.t_sched)
        eng.prefill_token_samples.append(plan.n_prefill)
        eng.decode_token_samples.append(n_tokens)
        delta = max(0, eng.preemptions - self._preempt_seen)
        self._preempt_seen = eng.preemptions
        eng.preemption_samples.append(delta)
        eng.batch_samples.append(n_valid)
        eng.kv_fraction_samples.append(eng.pool.manager.used_fraction)
        eng.max_kv_fraction = max(eng.max_kv_fraction,
                                  eng.pool.manager.used_fraction)
        if eng.obs is not None:
            eng.obs.end_step(eng, n_prefill=plan.n_prefill,
                             n_decode=n_valid, itl_s=dt)
