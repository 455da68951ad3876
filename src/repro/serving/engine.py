"""Continuous-batching serving engine (Orca/vLLM-style).

Per forward pass the scheduler admits waiting requests into the running
batch (FCFS) subject to two knobs — ``max_batch`` (the quantity BCA tunes)
and free KV blocks (paged pool watermark) — then executes one batched
decode step for every running request at its own position. Prefill runs
per admitted request in padded length buckets (jit-cache friendly).

Two prefill scheduling modes:

* **chunked** (``EngineConfig.prefill_chunk_tokens`` set, Sarathi-style):
  admission only *reserves a seat* — the request enters a ``PREFILLING``
  phase and every engine step assembles one mixed batch: all running
  decodes plus up to ``prefill_chunk_tokens`` of prompt chunks taken FCFS
  from partially-prefilled requests. A chunk attends over the request's
  already-written pool KV through the gathered-prefix path and scatters
  its own KV rows (token-granular, so chunks may end mid-block), and its
  blocks are allocated chunk-by-chunk under the admission watermark — a
  long prompt *streams* into the pool across steps instead of blocking
  the world. Chunk widths are bucketed (``prefill_bucket``) and prefix
  pads are power-of-two, so the jit cache stays bounded. Greedy outputs
  are bit-identical to serial prefill.
* **serial** (default, ``prefill_chunk_tokens=None``): the legacy
  admission-time prefill — the whole prompt runs at batch 1 inside
  ``_admit``. A single long prompt stalls every running request's decode
  for the full prefill duration (head-of-line blocking); the engine step
  timer covers admission + prefill, so the stall is *visible* in ITL and
  in the ``stall`` time series either way.

Chunked prefill requires per-token-addressable KV (the same gate as the
prefix cache); unsupported configs (SSM/cross-attn/MoE/window/embedding
inputs) silently fall back to serial with the reason recorded in
``chunking_disabled_reason``.

Decode data path (the paper's memory-bound hot loop) has two modes:

* ``paged`` (default) — **zero-copy**: one jitted step consumes a
  :class:`~repro.kvcache.view.PagedCacheView` (pool references + device
  block tables), attention reads the physical KV blocks in place via the
  block-table kernel, the new token's K/V row is scattered to its
  physical (block, slot) inside the jit, and the pool buffers are donated
  so the update aliases the input. Per-step host→device traffic is three
  ``[B]`` vectors (plus a table re-upload only when the allocator state
  changes). Batch size and table width are padded to power-of-two buckets
  so the jit cache stays O(log) in both.
* ``gather`` — the legacy fallback: materialize a dense ``[B, S_pad]``
  cache copy per step, decode against it, scatter the new rows back.
  Kept for sliding-window configs (ring caches aren't paged) and as the
  reference the path-equivalence tests compare against.

If the pool runs out of blocks mid-decode, the engine preempts (requeues)
the youngest running requests — recompute-style, like vLLM — instead of
crashing; deterministic greedy decode regenerates identical tokens.

With ``EngineConfig.prefix_cache`` the engine consults a radix
:class:`~repro.kvcache.prefix.PrefixIndex` at admission: a prompt's
longest cached full-block prefix is *spliced* into its block table
(ref-counted shared blocks — no copy) and only the uncached suffix is
prefilled, attending over the gathered prefix K/V. Cached blocks whose
last request released them stay warm in the index and are LRU-evicted
when admission or mid-decode appends need blocks back.

Token selection is the vectorized in-jit sampler
(:mod:`repro.models.sampler`): every decode path consumes stacked
per-request SamplingParams (temperature/top-k/top-p/seed) and
counter-based RNG keyed on ``fold_in(seed, position)``, so sampled
outputs are bit-reproducible across batch composition, bucketing,
preemption, chunked-vs-serial prefill, and replicas — and greedy
(``temperature=0``) stays bit-identical to the pre-sampler argmax.
Requests finish with a ``finish_reason``: ``length`` (budget),
``stop`` (sampled a stop/EOS token — blocks released the same step), or
``abort`` (cancelled via :meth:`ContinuousBatchingEngine.abort`, which
reclaims KV blocks and prefix-cache pins mid-flight, even
mid-PREFILLING).

The engine is the *measured-curves* source for BCA: sweeping ``max_batch``
on a fixed workload yields T(B)/L(B)/KV(B) exactly like the paper's
online-mode evaluation (Sec. IV), with real compute on CPU for reduced
configs and the same code path targeting TPU meshes for full ones.

:meth:`ContinuousBatchingEngine.run` is a thin batch-offline wrapper over
the streaming facade (:mod:`repro.serving.api`) — online callers should
use ``ServingAPI.submit() / stream() / abort()`` directly.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ArchConfig
from repro.kvcache.paged import (PagedKVCache, gather_prefix_jit,
                                 scatter_rows_jit)
from repro.kvcache.prefix import PrefixIndex, PrefixStats, \
    prefix_cache_supported
from repro.kvcache.view import PagedCacheView
from repro.models.model import Model
from repro.models.sampler import (positions_array, sample_tokens,
                                  stack_sampling)
from repro.serving.executor import Executor
from repro.serving.faults import FaultInjector
from repro.serving.metrics import ServingMetrics
from repro.serving.scheduler import Scheduler, StepPlan
from repro.serving.spec import (PromptLookupDrafter, spec_verify_fn,
                                stack_drafts)
from repro.serving.spec.drafter import Drafter
from repro.serving.spec.verify import accepted_prefix
from repro.serving.obs.series import DEFAULT_SERIES_MAXLEN, BoundedSeries
from repro.serving.obs.trace import span
from repro.serving.workload import (FINISH_ABORT, FINISH_DEADLINE,
                                    FINISH_FAILED, FINISH_LENGTH,
                                    FINISH_SHED, FINISH_STOP, Request)


class RequestTooLarge(RuntimeError):
    """A single request can never fit the KV pool (prompt or decode
    footprint exceeds capacity even with everything else evicted).

    Subclasses ``RuntimeError`` with the legacy "KV pool exhausted"
    message, so bare-engine callers see the same hard error as before —
    but carries ``req_id`` so the cluster can *evict that one request*
    (finish it ``failed``) and keep the replica serving instead of
    treating a poison request as a replica death. The lone oversized
    request is the only pool-exhaustion condition that stays a hard
    error; every other one degrades (preemption, shedding, deadlines).
    """

    def __init__(self, msg: str, req_id: int):
        super().__init__(msg)
        self.req_id = req_id


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 16
    block_size: int = 16
    kv_pool_tokens: int = 8192          # total KV token capacity
    max_model_len: int = 1024
    prefill_bucket: int = 64            # pad prompts to multiples of this
    # "paged" = zero-copy block-table decode (default);
    # "gather" = legacy dense-copy fallback (forced for sliding windows)
    decode_mode: str = "paged"
    # radix prefix cache: share full KV blocks across prompts with a
    # common prefix (skips their prefill + their pool footprint). Opt-in;
    # silently downgraded (reason recorded) for configs whose state is not
    # per-token addressable — see kvcache.prefix.prefix_cache_supported.
    prefix_cache: bool = False
    # cap on cached blocks held by the index (None = bounded only by
    # LRU eviction under the pool watermark)
    prefix_cache_blocks: Optional[int] = None
    # double-buffered overlapped stepping (scheduler/executor split):
    # dispatch step N+1 before fetching step N's tokens, so host
    # bookkeeping runs under device execution instead of serializing
    # with it. Outputs are bit-identical to the synchronous loop; the
    # observable differences are timing-only (see serving.executor).
    overlap: bool = False
    # chunked prefill (Sarathi-style mixed steps): per-step token budget
    # for prompt chunks scheduled alongside the running decode batch.
    # None = serial admission-time prefill (the HOL-blocking legacy mode,
    # kept as the baseline for benchmarks/chunked_prefill.py).
    prefill_chunk_tokens: Optional[int] = None
    # --- admission control / load shedding (all off by default) ---
    # bound on the arrival queue: shed_check rejects a submit once this
    # many requests are already waiting (reason "queue_full")
    max_waiting: Optional[int] = None
    # refuse new submits while the KV pool is fuller than this fraction
    # AND requests are already queued behind it (reason "kv_pressure") —
    # occupancy-driven backpressure, the degrade-don't-die alternative
    # to queueing into a pool that preemption is already thrashing
    shed_kv_fraction: Optional[float] = None
    # refuse new submits once the estimated queue delay (queued tokens
    # over the recent measured token throughput) exceeds this bound
    # (reason "queue_delay"); a submit whose own deadline the estimate
    # already blows is shed as "deadline_unmeetable" even without a
    # global bound
    shed_queue_delay_s: Optional[float] = None
    # --- speculative decoding (draft-free prompt-lookup; off by default) ---
    # verify up to spec_k drafted tokens per request per step through the
    # fused multi-token verify jit; accepted outputs stay bit-identical
    # to serial decode. Requires the paged decode path and per-token-
    # addressable KV (same gate as the prefix cache) — unsupported
    # configs silently fall back with the reason in spec_disabled_reason.
    speculate: bool = False
    spec_k: int = 8                     # max draft tokens per step
    spec_ngram: int = 3                 # longest prompt-lookup n-gram
    # overlap mode only: rows with a plain step in flight are device-
    # chained (their committed history is host-unknown, so the drafter
    # cannot run). Every spec_probe_every-th iteration with chained rows
    # the scheduler drains the pipeline so the drafter gets a shot at
    # fully committed context; once speculation engages, verify steps
    # keep rows unchained and the probes stop costing anything.
    spec_probe_every: int = 8
    # bound on every per-step telemetry series (ITL, KV occupancy, stall,
    # token splits, preemptions, the observer's audits):
    # a series reaching this length decimates itself (uniform 1-in-N
    # downsampling over the whole run) instead of growing — soak runs
    # keep O(1) host memory per series. See serving.obs.series.
    series_maxlen: int = DEFAULT_SERIES_MAXLEN

    def __post_init__(self):
        """Fail loudly at construction instead of as a downstream shape
        error three layers into the first decode step."""
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {self.block_size}")
        if self.kv_pool_tokens % self.block_size:
            raise ValueError(
                f"kv_pool_tokens ({self.kv_pool_tokens}) must be divisible "
                f"by block_size ({self.block_size}); the pool is allocated "
                f"in whole blocks")
        if self.kv_pool_tokens < self.block_size:
            raise ValueError(
                f"kv_pool_tokens ({self.kv_pool_tokens}) must hold at least "
                f"one block of {self.block_size} tokens")
        if self.max_model_len > self.kv_pool_tokens:
            raise ValueError(
                f"max_model_len ({self.max_model_len}) exceeds the KV pool "
                f"capacity ({self.kv_pool_tokens} tokens): a single "
                f"max-length request could never be admitted — raise "
                f"kv_pool_tokens or lower max_model_len")
        if self.prefill_bucket < 1:
            raise ValueError(
                f"prefill_bucket must be >= 1, got {self.prefill_bucket}")
        if self.decode_mode not in ("paged", "gather"):
            raise ValueError(
                f"decode_mode must be 'paged' or 'gather', "
                f"got {self.decode_mode!r}")
        if self.prefix_cache_blocks is not None \
                and self.prefix_cache_blocks < 1:
            raise ValueError(
                f"prefix_cache_blocks must be >= 1 (or None for "
                f"unbounded), got {self.prefix_cache_blocks}")
        if self.prefill_chunk_tokens is not None \
                and self.prefill_chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1 (or None for serial "
                f"admission-time prefill), got {self.prefill_chunk_tokens}")
        if self.max_waiting is not None and self.max_waiting < 1:
            raise ValueError(
                f"max_waiting must be >= 1 (or None for an unbounded "
                f"queue), got {self.max_waiting}")
        if self.shed_kv_fraction is not None \
                and not 0.0 < self.shed_kv_fraction <= 1.0:
            raise ValueError(
                f"shed_kv_fraction must be in (0, 1] (or None to "
                f"disable), got {self.shed_kv_fraction}")
        if self.shed_queue_delay_s is not None \
                and self.shed_queue_delay_s <= 0:
            raise ValueError(
                f"shed_queue_delay_s must be > 0 (or None to disable), "
                f"got {self.shed_queue_delay_s}")
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if self.spec_ngram < 1:
            raise ValueError(
                f"spec_ngram must be >= 1, got {self.spec_ngram}")
        if self.spec_probe_every < 1:
            raise ValueError(
                f"spec_probe_every must be >= 1, got "
                f"{self.spec_probe_every}")
        if self.series_maxlen < 2:
            raise ValueError(
                f"series_maxlen must be >= 2, got {self.series_maxlen}")


@dataclasses.dataclass(frozen=True)
class StepFunctions:
    """The engine's three jitted entry points, bundled so co-located
    replicas (serving.cluster) can share one compile cache.

    ``jax.jit`` caches per wrapper object, so two engines that each build
    their own ``jax.jit(partial(...))`` recompile identical programs.
    Replicas of the same model with the same ``block_size`` can pass one
    shared bundle instead and compile each (batch, table) bucket once per
    host.
    """
    model: Model
    block_size: int
    prefill: Callable
    decode: Callable
    paged: Callable
    prefix_prefill: Callable
    chunk_prefill: Callable
    # vectorized sampler for the host-logits paths (prefill first token,
    # gather decode); the zero-copy paged step fuses it in-jit instead
    sample: Callable
    # multi-token speculative verify (serving.spec): K+1 chained serial
    # decode iterations + in-jit acceptance in one program; recompiles
    # per (batch_pad, nb_pad, K_pad) bucket like the paged step
    spec_verify: Callable

    @classmethod
    def build(cls, model: Model, block_size: int) -> "StepFunctions":
        # zero-copy steps: the pool pytree (arg 1) is donated so the K/V
        # row scatters alias the input buffers, on the TPU and the CPU
        # alike. A pool sized to fill HBM has no room for a second copy.
        donate = (1,)
        return cls(
            model=model, block_size=block_size,
            prefill=jax.jit(_named("prefill", _prefill_fn, model),
                            static_argnames=("cache_len",)),
            decode=jax.jit(_named("decode_gather", _decode_fn, model)),
            paged=jax.jit(_named("paged_decode_step", _paged_decode_fn,
                                 model, block_size),
                          donate_argnums=donate),
            prefix_prefill=jax.jit(
                _named("prefix_prefill", _prefix_prefill_fn, model),
                static_argnames=("cache_len",)),
            chunk_prefill=jax.jit(
                _named("chunk_prefill", _chunk_prefill_fn, model,
                       block_size),
                static_argnames=("cache_len", "nb_prefix"),
                donate_argnums=donate),
            sample=jax.jit(_named("sample", sample_tokens)),
            spec_verify=jax.jit(_named("spec_verify", spec_verify_fn,
                                       model, block_size),
                                donate_argnums=donate))


def _named(name: str, fn: Callable, *bound) -> Callable:
    """``partial(fn, *bound)`` named ``name``: JAX names the program
    ``jit_<name>`` (a bare partial is ``jit__unknown``), which is how a
    profile tells the step programs apart."""
    f = partial(fn, *bound)
    f.__name__ = f.__qualname__ = name
    return f


def _bucket(n: int, b: int) -> int:
    return max(b, ((n + b - 1) // b) * b)


def _placement(params) -> jax.sharding.Sharding:
    """The params' devices, replicated: where the pool and uploads go."""
    s = jax.tree.leaves(params)[0].sharding
    if isinstance(s, NamedSharding):
        return NamedSharding(s.mesh, PartitionSpec())
    return s


def _pow2_bucket(n: int, lo: int = 1) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


class ContinuousBatchingEngine:
    def __init__(self, model: Model, params, ecfg: EngineConfig, *,
                 steps: Optional[StepFunctions] = None):
        self.model = model
        self.cfg: ArchConfig = model.cfg
        self.params = params
        self.ecfg = ecfg
        nb = ecfg.kv_pool_tokens // ecfg.block_size
        # the pool and every per-step upload live where the params do (a
        # sliced replica's params sit on its own device)
        self.pool = PagedKVCache(self.cfg, num_blocks=nb,
                                 block_size=ecfg.block_size,
                                 max_batch=ecfg.max_batch,
                                 sharding=_placement(params))
        # ring caches (sliding window) aren't paged — fall back to gather
        self.decode_mode = ("gather" if self.cfg.sliding_window
                            else ecfg.decode_mode)
        # scheduler/executor split: request-phase state (queues, token /
        # position bookkeeping) and all admission / preemption / deadline
        # decisions live on the Scheduler; the Executor owns the
        # overlapped dispatch-ahead window. The engine re-exports the
        # scheduler's state through delegating properties below, so
        # existing callers keep reading ``eng.waiting`` / ``eng.running``
        # / ``eng._pos`` unchanged.
        self.sched = Scheduler(self)
        self._executor = Executor(self)
        # chunked prefill needs the same per-token-addressable KV as the
        # prefix cache (a chunk attends over gathered pool blocks)
        self.chunking = False
        self.chunking_disabled_reason: Optional[str] = None
        if ecfg.prefill_chunk_tokens is not None:
            ok, why = prefix_cache_supported(self.cfg)
            if ok:
                self.chunking = True
            else:
                self.chunking_disabled_reason = why
        # jitted entry points: private by default, shareable across
        # co-located replicas (must agree on model and block_size — the
        # paged step bakes both in, so a mismatch would silently compute
        # wrong physical (block, slot) addresses)
        if steps is not None:
            if steps.model is not model:
                raise ValueError("shared StepFunctions were built for a "
                                 "different Model instance")
            if steps.block_size != ecfg.block_size:
                raise ValueError(
                    f"shared StepFunctions were built for block_size="
                    f"{steps.block_size}, engine uses {ecfg.block_size}")
        self._steps = steps or StepFunctions.build(model, ecfg.block_size)
        self._prefill_jit = self._steps.prefill
        self._decode_jit = self._steps.decode
        self._paged_jit = self._steps.paged
        self._prefix_prefill_jit = self._steps.prefix_prefill
        self._chunk_prefill_jit = self._steps.chunk_prefill
        self._spec_verify_jit = self._steps.spec_verify
        # device-staged sampling stacks keyed on batch composition: the
        # verify step re-dispatches every step but its sampling params
        # are frozen per request, so re-uploading them is pure per-step
        # host overhead (4 device_puts) the small-batch regime can't hide
        self._spec_samp_cache: Dict[tuple, tuple] = {}
        # speculative decoding (serving.spec): the drafter proposes
        # per-request token spans the scheduler turns into draft-carrying
        # plans. Requires the paged pool (token-granular rollback) and
        # per-token-addressable KV (SSM/window state cannot roll back) —
        # same silent-downgrade-with-reason pattern as chunking / prefix.
        self.speculator: Optional[Drafter] = None
        self.spec_disabled_reason: Optional[str] = None
        if ecfg.speculate:
            ok, why = prefix_cache_supported(self.cfg)
            if not ok:
                self.spec_disabled_reason = why
            elif self.decode_mode != "paged":
                self.spec_disabled_reason = (
                    "decode_mode 'gather' (dense-copy fallback has no "
                    "paged block tables to roll back)")
            else:
                self.speculator = PromptLookupDrafter(
                    max_ngram=ecfg.spec_ngram, max_k=ecfg.spec_k)
        # radix prefix cache (opt-in, and only for configs whose KV is
        # per-token addressable — SSM/cross/MoE/window configs downgrade)
        self.prefix: Optional[PrefixIndex] = None
        self.prefix_disabled_reason: Optional[str] = None
        if ecfg.prefix_cache:
            ok, why = prefix_cache_supported(self.cfg)
            if ok:
                self.prefix = PrefixIndex(
                    self.pool.manager, max_blocks=ecfg.prefix_cache_blocks)
            else:
                self.prefix_disabled_reason = why
        # wall clock for request timestamps (seconds since serving start);
        # run() installs one, a cluster driving step() directly installs a
        # shared cluster-wide clock so replica timelines are comparable
        self.clock: Optional[Callable[[], float]] = None
        # fault injection (serving.faults): the cluster installs one
        # injector + this engine's replica id; a bare engine may set them
        # directly. None = no injection hooks consulted.
        self.faults: Optional[FaultInjector] = None
        self.replica_id = 0
        self.step_count = 0          # step() calls, counted from 1
        # observability hook sink (serving.obs): None = detached, every
        # hook site is one attribute check; Observability.attach installs
        # an EngineObserver here
        self.obs = None
        # last decode step's jit-bucketing facts, stashed only when an
        # observer is attached (see _stash_buckets)
        self._last_buckets = None
        # telemetry — every per-step series is bounded (decimating, see
        # serving.obs.series) so soak runs cannot grow host memory
        ml = ecfg.series_maxlen
        self.itl_samples: List[float] = BoundedSeries(ml)
        self.batch_samples: List[int] = BoundedSeries(ml)
        self.kv_fraction_samples: List[float] = BoundedSeries(ml)
        self.max_kv_fraction = 0.0
        self.preemptions = 0
        self.prefill_tokens_computed = 0
        # scheduler-stall series: per-step seconds spent on admission +
        # prefill before the decode launch, and the per-step prefill /
        # decode token split — the observables that make HOL blocking
        # (and the chunked fix) measurable
        self.stall_samples: List[float] = BoundedSeries(ml)
        self.prefill_token_samples: List[int] = BoundedSeries(ml)
        self.decode_token_samples: List[int] = BoundedSeries(ml)
        # per-step recompute re-admissions (preemptions delta): recovery
        # redrives ride the preemption path, so this series is how a
        # thrashing pool — or a redrive storm — becomes visible
        self.preemption_samples: List[int] = BoundedSeries(ml)
        # robustness counters (also broken down in finish_reasons)
        self.deadline_expired = 0
        self.shed = 0
        self.shed_reasons: Dict[str, int] = {}
        self.queued_aborts = 0       # aborts caught in the arrival queue
        # speculative-decoding counters + per-verify-step acceptance rate
        self.spec_steps = 0          # verify steps executed
        self.spec_drafted = 0        # draft tokens proposed
        self.spec_accepted = 0       # draft tokens accepted (committed)
        self.spec_rejected = 0       # draft tokens rejected (rolled back)
        self.spec_acceptance_samples: List[float] = BoundedSeries(ml)
        # paged kernel engagement, per layer, summed over plain paged
        # decode steps (see _count_kernel_blocks)
        self.kernel_live_blocks = 0
        self.kernel_table_blocks = 0

    # -------------------------------------------- scheduler state views --
    # The scheduler owns this state since the scheduler/executor split;
    # these delegating properties keep the engine's historical surface
    # (tests, cluster recovery, router load views all read it). Setters
    # forward too — the sync step still assigns ``self.running``.
    @property
    def waiting(self) -> deque:
        return self.sched.waiting

    @waiting.setter
    def waiting(self, v):
        self.sched.waiting = v

    @property
    def running(self) -> List[Request]:
        return self.sched.running

    @running.setter
    def running(self, v):
        self.sched.running = v

    @property
    def prefilling(self) -> List[Request]:
        return self.sched.prefilling

    @prefilling.setter
    def prefilling(self, v):
        self.sched.prefilling = v

    @property
    def _prefilled(self) -> Dict[int, int]:
        return self.sched._prefilled

    @_prefilled.setter
    def _prefilled(self, v):
        self.sched._prefilled = v

    @property
    def _tokens(self) -> Dict[int, int]:
        return self.sched._tokens

    @_tokens.setter
    def _tokens(self, v):
        self.sched._tokens = v

    @property
    def _pos(self) -> Dict[int, int]:
        return self.sched._pos

    @_pos.setter
    def _pos(self, v):
        self.sched._pos = v

    @property
    def _has_deadlines(self) -> bool:
        return self.sched._has_deadlines

    @_has_deadlines.setter
    def _has_deadlines(self, v):
        self.sched._has_deadlines = v

    # ------------------------------------------------------------- admin --
    @property
    def busy(self) -> bool:
        """Any request still queued, prefilling, or decoding?"""
        return bool(self.waiting or self.prefilling or self.running)

    def add_request(self, req: Request):
        if req.prompt_len + 1 > self.ecfg.max_model_len:
            # previously admitted silently: the decode limit went
            # non-positive and the request "finished" with garbage
            # truncation semantics after one step
            raise ValueError(
                f"request {req.req_id}: prompt_len ({req.prompt_len}) + 1 "
                f"first output token exceeds max_model_len "
                f"({self.ecfg.max_model_len}); reject or truncate the "
                f"prompt upstream")
        if req.sampling.has_deadline:
            self._has_deadlines = True
        self.waiting.append(req)

    # ----------------------------------------------- admission control --
    # (logic lives on the Scheduler since the scheduler/executor split;
    # these thin delegators preserve the engine's public surface)
    def estimated_queue_delay_s(self) -> float:
        """See :meth:`repro.serving.scheduler.Scheduler
        .estimated_queue_delay_s`."""
        return self.sched.estimated_queue_delay_s()

    def shed_check(self, req: Request, now: float) -> Optional[str]:
        """Would admission control reject ``req`` submitted at ``now``?
        See :meth:`repro.serving.scheduler.Scheduler.shed_check`."""
        return self.sched.shed_check(req, now)

    def shed_request(self, req: Request, now: float, reason: str):
        """See :meth:`repro.serving.scheduler.Scheduler.shed_request`."""
        self.sched.shed_request(req, now, reason)

    def try_add_request(self, req: Request, now: float) -> Optional[str]:
        """Admission-controlled enqueue: shed (returning the reason) or
        accept (returning None). The graceful path ``ServingAPI.submit``
        uses — an overloaded engine degrades by rejecting work, it never
        crashes on it."""
        reason = self.shed_check(req, now)
        if reason is not None:
            self.shed_request(req, now, reason)
            return reason
        self.add_request(req)
        return None

    def reset_stats(self):
        """Clear accumulated telemetry (e.g. after a warmup workload) so
        the next run's metrics aren't polluted by compile-time samples.
        The prefix index keeps its *contents* (a warm cache is the point
        of a warmup) — only its counters reset."""
        ml = self.ecfg.series_maxlen
        self.itl_samples = BoundedSeries(ml)
        self.batch_samples = BoundedSeries(ml)
        self.kv_fraction_samples = BoundedSeries(ml)
        self.max_kv_fraction = 0.0
        self.preemptions = 0
        self.prefill_tokens_computed = 0
        self.stall_samples = BoundedSeries(ml)
        self.prefill_token_samples = BoundedSeries(ml)
        self.decode_token_samples = BoundedSeries(ml)
        self.preemption_samples = BoundedSeries(ml)
        self.deadline_expired = 0
        self.shed = 0
        self.shed_reasons = {}
        self.queued_aborts = 0
        self.spec_steps = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_rejected = 0
        self.spec_acceptance_samples = BoundedSeries(ml)
        self.kernel_live_blocks = 0
        self.kernel_table_blocks = 0
        self._last_buckets = None
        self.pool.manager.total_allocations = 0
        self.pool.manager.cow_copies = 0
        if self.prefix is not None:
            self.prefix.stats = PrefixStats()

    def _now(self, fallback: float) -> float:
        return self.clock() if self.clock is not None else fallback

    def _limit(self, req: Request) -> int:
        """Output-token budget: the request's own cap, clipped by model
        length. At least 1 — prefill unconditionally emits the first
        output token (add_request rejects prompts it couldn't hold)."""
        return max(1, min(req.max_new_tokens,
                          self.ecfg.max_model_len - req.prompt_len - 1))

    def _finish(self, req: Request, t_done: float, reason: str):
        # capture peak occupancy before the release drops it — a request
        # can finish straight out of prefill (max_new_tokens=1) without
        # ever reaching the decode-step sampling point
        self.max_kv_fraction = max(self.max_kv_fraction,
                                   self.pool.manager.used_fraction)
        req.state.finish_reason = reason
        req.state.t_done = t_done
        self.pool.release(req.req_id)
        self._tokens.pop(req.req_id, None)
        self._pos.pop(req.req_id, None)
        self.sched._dispatched.pop(req.req_id, None)
        # any still-in-flight speculative token for this request must
        # never commit (no-op in sync mode — nothing is ever in flight)
        self._executor.invalidate(req.req_id)
        if self.speculator is not None:
            self.speculator.forget(req.req_id)
        if self.obs is not None:
            self.obs.on_finish(req, reason)

    def _finish_or_run(self, req: Request, t_done: float) -> bool:
        """Shared finish protocol for the just-produced last token: stop
        tokens end the request the same step (blocks released now, and
        the stop token was already counted in this step's ITL/decode
        accounting exactly like any other token — stop- and
        length-finishes are symmetric); otherwise the length budget
        decides. Returns True when the request finished."""
        tok = req.state.output_tokens[-1]
        if req.sampling.stops_on(tok):
            self._finish(req, t_done, reason=FINISH_STOP)
        elif req.state.generated >= self._limit(req):
            self._finish(req, t_done, reason=FINISH_LENGTH)
        else:
            return False
        return True

    def _post_prefill(self, req: Request, now: float):
        """Prefill just completed (first output token exists): stamp TTFT
        and either finish the request outright — a ``max_new_tokens=1``
        request is already satisfied and must not enter ``running`` (it
        used to decode one extra token because the finish check only ran
        after a decode step), as is one whose very first token was a stop
        token — or move it to the decode batch.

        ``now`` can be ahead of the wall clock when the caller
        fast-forwards idle time to the next arrival; take the max so TTFT
        stays on the same (possibly simulated) timeline as
        arrival_s/t_done and never goes negative."""
        req.state.t_first_token = max(now, self._now(now))
        if self.obs is not None:
            self.obs.on_first_token(req)
        if not self._finish_or_run(req, req.state.t_first_token):
            self.running.append(req)

    def abort(self, req_id: int, now: float = 0.0) -> bool:
        """Cancel a request mid-flight (the API facade's abort path).

        Works in every scheduling phase: queued (nothing allocated yet),
        PREFILLING (partial chunk progress discarded), or decoding.
        Every KV block is released — shared prefix blocks drop back to
        their cache-only refcount, private ones return to the free list —
        and the request finishes with ``finish_reason="abort"``. Returns
        False when the request is unknown or already finished.
        """
        req = next((r for r in self.waiting if r.req_id == req_id), None)
        if req is not None:
            # still in the arrival queue: nothing allocated to reclaim,
            # it just must never start — counted separately so queue
            # churn (clients hanging up before service) is visible
            self.waiting.remove(req)
            self.queued_aborts += 1
        else:
            for lst in (self.prefilling, self.running):
                req = next((r for r in lst if r.req_id == req_id), None)
                if req is not None:
                    lst.remove(req)
                    break
        if req is None:
            return False
        self._prefilled.pop(req_id, None)
        # clamp to arrival_s: aborting a queued request whose (simulated)
        # arrival is still in the future must not produce a negative E2E
        self._finish(req, max(self._now(now), req.arrival_s),
                     reason=FINISH_ABORT)
        return True

    def evict_request(self, req_id: int, now: float = 0.0,
                      reason: str = FINISH_FAILED) -> Optional[Request]:
        """Force-finish one request with an explicit reason, releasing
        its KV blocks — the cluster's surgical response to a
        :class:`RequestTooLarge` poison request (the request dies, the
        replica keeps serving everyone else). Same phase coverage and
        block accounting as :meth:`abort`, but the reason is the
        caller's and queued evictions are not counted as client aborts.
        Returns the request, or None if unknown / already finished."""
        for lst in (self.waiting, self.prefilling, self.running):
            req = next((r for r in lst if r.req_id == req_id), None)
            if req is not None:
                lst.remove(req)
                self._prefilled.pop(req_id, None)
                self._finish(req, max(self._now(now), req.arrival_s),
                             reason=reason)
                return req
        return None

    def _expire_deadlines(self, now: float):
        """See :meth:`repro.serving.scheduler.Scheduler.expire_deadlines`."""
        self.sched.expire_deadlines(now)

    def _admit(self, now: float):
        """See :meth:`repro.serving.scheduler.Scheduler.admit`."""
        self.sched.admit(now)

    def _complete_prefill(self, req: Request, logits, now: float):
        """The one completion protocol both prefill modes share (the
        bit-identity guarantee depends on it staying single-sourced):
        first output token sampled from the final logits (RNG counter =
        ``prompt_len``, the position the token occupies — identical for
        serial, suffix-only, and chunked prefill, so all three produce
        the same first token for the same seed), decode bookkeeping,
        prefix-index registration, TTFT stamp, finish-or-run."""
        rid = req.req_id
        with span("engine.fetch"):
            tok = int(self._steps.sample(
                logits, *stack_sampling([req.sampling]),
                positions_array([req.prompt_len]))[0])
        self._tokens[rid] = tok
        self._pos[rid] = req.prompt_len
        # prefill's token counts as dispatched AND committed (the int()
        # above already fetched it) — the overlap planner's length gate
        # starts from here
        self.sched._dispatched[rid] = 1
        req.generated = 1       # prefill produced the first output token
        req.output_tokens.append(tok)
        if self.prefix is not None:
            # register the prompt's full blocks (prefix + own) for reuse
            self.prefix.insert(req.prompt, self.pool.manager.tables[rid])
        self._post_prefill(req, now)

    def _prefill(self, req: Request, n_cached: int = 0):
        """Serial whole-prompt prefill: compute + write the KV; returns
        the last-position logits for :meth:`_complete_prefill`."""
        rid = req.req_id
        if n_cached:
            # suffix-only prefill: gather the cached prefix K/V once and
            # compute only the uncached tail, writing its KV into the
            # request's own (non-shared) blocks
            sfx_len = req.prompt_len - n_cached
            S = _bucket(sfx_len, self.ecfg.prefill_bucket)
            toks = np.zeros((1, S), np.int32)
            toks[0, :sfx_len] = req.prompt[n_cached:]
            batch = self.pool.put({"tokens": toks, "lengths": np.asarray(
                [sfx_len], np.int32)})
            nb_cached = n_cached // self.ecfg.block_size
            nb_pad = _pow2_bucket(nb_cached, lo=1)
            prefix_kv = self.pool.gather_prefix(
                self.pool.manager.tables[rid][:nb_cached], nb_pad)
            with span("engine.prefill"):
                logits, cache, _ = self._prefix_prefill_jit(
                    self.params, batch, prefix_kv, np.int32(n_cached),
                    cache_len=S)
            self.pool.write_prefill(rid, cache, start_pos=n_cached)
        else:
            S = _bucket(req.prompt_len, self.ecfg.prefill_bucket)
            toks = np.zeros((1, S), np.int32)
            toks[0, :req.prompt_len] = req.prompt
            batch = self.pool.put({"tokens": toks, "lengths": np.asarray(
                [req.prompt_len], np.int32)})
            if self.cfg.arch_type == "vlm":
                batch["img_embeds"] = jnp.zeros(
                    (1, self.cfg.n_img_tokens, self.cfg.d_model),
                    self.cfg.activation_dtype, device=self.pool.sharding)
            with span("engine.prefill"):
                logits, cache, _ = self._prefill_jit(self.params, batch,
                                                     cache_len=S)
            self.pool.write_prefill(rid, cache)
        self.prefill_tokens_computed += req.prompt_len - n_cached
        return logits

    # ------------------------------------------------- chunked prefill --
    def _prefill_step(self, now: float) -> int:
        """See :meth:`repro.serving.scheduler.Scheduler.prefill_step`."""
        return self.sched.prefill_step(now)

    def _reserve_for_chunk(self, rid: int, target_tokens: int) -> bool:
        """See :meth:`repro.serving.scheduler.Scheduler
        ._reserve_for_chunk`."""
        return self.sched._reserve_for_chunk(rid, target_tokens)

    def _run_chunk(self, req: Request, done: int, chunk: int):
        """Prefill prompt positions ``[done, done + chunk)``: attend over
        the already-written pool KV and scatter the chunk's own KV rows,
        all inside one fused jit (``prefix_len`` and the chunk length are
        traced, so chunk progress never recompiles). Returns the chunk's
        last-position logits (only the final chunk's are consumed)."""
        rid = req.req_id
        S = _bucket(chunk, self.ecfg.prefill_bucket)
        toks = np.zeros((1, S), np.int32)
        toks[0, :chunk] = req.prompt[done:done + chunk]
        batch = self.pool.put({"tokens": toks,
                               "lengths": np.asarray([chunk], np.int32)})
        if done == 0:
            # first chunk of an uncached prompt: plain prefill (identical
            # compute to the serial path when the chunk covers the whole
            # prompt — the bit-identity anchor) + token-granular write
            with span("engine.prefill"):
                logits, cache, _ = self._prefill_jit(self.params, batch,
                                                     cache_len=S)
            self.pool.write_prefill(rid, cache, start_pos=0, n_tokens=chunk)
        else:
            blocks = self.pool.manager.tables[rid]
            nb_pad = _pow2_bucket(len(blocks), lo=1)
            table = np.full((nb_pad,), self.pool.trash_block, np.int32)
            table[:len(blocks)] = blocks
            nb_prefix = _pow2_bucket(-(-done // self.ecfg.block_size), lo=1)
            tables = self.pool.put(table)
            with span("engine.prefill"):
                logits, new_pool = self._chunk_prefill_jit(
                    self.params, self.pool.pool, tables, batch,
                    np.int32(done), np.int32(chunk), cache_len=S,
                    nb_prefix=min(nb_prefix, nb_pad))
            self.pool.commit(new_pool)
        self.prefill_tokens_computed += chunk
        return logits

    # -------------------------------------------------------- preemption --
    def _preempt(self, req: Request):
        """See :meth:`repro.serving.scheduler.Scheduler.preempt`."""
        self.sched.preempt(req)

    def _ensure_step_capacity(self):
        """See :meth:`repro.serving.scheduler.Scheduler
        .ensure_step_capacity`."""
        self.sched.ensure_step_capacity()

    # -------------------------------------------------------------- step --
    def step(self, now: float) -> bool:
        """One engine iteration. Returns False when fully idle.

        Since the scheduler/executor split the step body is a thin
        driver: :meth:`Scheduler.plan` makes every decision (admission,
        prefill work, preemption, deadlines, decode batch selection) and
        then either

        * **sync mode** (default): the decode jit runs inline, its
          outputs are fetched immediately, and bookkeeping + telemetry
          run with the exact legacy timing semantics (the step timer
          covers plan start through host bookkeeping), or
        * **overlap mode** (``EngineConfig.overlap``): the
          :class:`~repro.serving.executor.Executor` dispatches this
          plan's decode before committing the *previous* step's results,
          so host work runs under device execution (see
          ``serving/executor.py`` for the full semantics).

        The step timer starts *before* admission, so prefill stalls are
        visible in ITL; the prefill share of each step is recorded
        separately in ``stall_samples``.
        """
        with span("engine.step"):
            self.step_count += 1
            if self.faults is not None:
                # may sleep (delay — the watchdog's trigger) or raise
                # InjectedFault (kill — the cluster's quarantine trigger);
                # raised on the host before any mutation *and before any
                # dispatch*, so injected faults stay ordered even in
                # overlap mode (only genuine device errors defer — see
                # executor)
                self.faults.on_step(self.replica_id, self.step_count)
            if self.ecfg.overlap:
                return self._executor.step(now)
            return self._step_sync(now)

    def _step_sync(self, now: float) -> bool:
        """One synchronous iteration (see :meth:`step`)."""
        plan = self.sched.plan(now)
        t0, t_sched, n_prefill = plan.t0, plan.t_sched, plan.n_prefill
        if not plan.has_decode:
            if n_prefill:          # prefill-only step: keep the series
                self.stall_samples.append(t_sched)
                self.prefill_token_samples.append(n_prefill)
                self.decode_token_samples.append(0)
                self.preemption_samples.append(self.preemptions - plan.p0)
                # KV streamed in without a decode step to sample it
                self.kv_fraction_samples.append(
                    self.pool.manager.used_fraction)
                self.max_kv_fraction = max(self.max_kv_fraction,
                                           self.pool.manager.used_fraction)
                if self.obs is not None:
                    self.obs.end_step(self, n_prefill=n_prefill, n_decode=0)
            return self.busy
        reqs = plan.reqs
        if plan.drafts is not None:
            # speculative verify step: variable tokens-per-request commit,
            # own telemetry stamps (see _spec_step_sync)
            self._spec_step_sync(plan, now)
            return True
        if self.decode_mode == "paged":
            next_tokens = self._decode_paged(reqs)
        else:
            next_tokens = self._decode_gather(reqs)
        with span("engine.commit"):
            dt = time.perf_counter() - t0
            self.itl_samples.append(dt)
            self.stall_samples.append(t_sched)
            self.prefill_token_samples.append(n_prefill)
            self.decode_token_samples.append(len(reqs))
            self.preemption_samples.append(self.preemptions - plan.p0)
            self.batch_samples.append(len(reqs))
            self.kv_fraction_samples.append(self.pool.manager.used_fraction)
            self.max_kv_fraction = max(self.max_kv_fraction,
                                       self.pool.manager.used_fraction)
            # bookkeeping (no TTFT re-stamp here: _post_prefill always
            # stamps t_first_token when prefill emits the first token,
            # and preempted requests get re-stamped on re-admission — a
            # re-stamp on decode could only mis-stamp). Sync mode
            # advances ``_pos`` here, at commit (overlap advances it at
            # plan time — see Scheduler.plan).
            still = []
            for i, r in enumerate(reqs):
                self._pos[r.req_id] += 1
                tok = int(next_tokens[i])
                self._tokens[r.req_id] = tok
                r.state.generated += 1
                r.state.output_tokens.append(tok)
                if not self._finish_or_run(r, now + dt):
                    still.append(r)
            self.running = still
            if self.obs is not None:
                self.obs.end_step(self, n_prefill=n_prefill,
                                  n_decode=len(reqs), itl_s=dt)
        return True

    # ------------------------------------------------------ decode paths --
    def _decode_paged(self, reqs: List[Request]) -> np.ndarray:
        """Zero-copy step: block-table attention directly on the pool,
        next token sampled inside the same jit (per-request params ride
        as traced [B] vectors; padding rows are greedy and discarded)."""
        with span("engine.decode"):
            B = len(reqs)
            rids = [r.req_id for r in reqs]
            positions = [self._pos[rid] for rid in rids]
            max_blocks = max(len(self.pool.manager.tables[rid])
                             for rid in rids)
            nb_pad = _pow2_bucket(max_blocks, lo=4)
            batch_pad = _pow2_bucket(B)
            view = self.pool.view(rids, positions, nb_pad, batch_pad)
            tokens = np.zeros((batch_pad,), np.int32)
            tokens[:B] = [self._tokens[rid] for rid in rids]
            uploads = self.pool.put((tokens, *stack_sampling(
                [r.sampling for r in reqs], pad_to=batch_pad)))
            next_tokens, new_pool = self._paged_jit(
                self.params, view.pool, view.tables, view.lengths,
                view.positions, view.slots, *uploads)
            self.pool.commit(new_pool)
            self._count_kernel_blocks(positions, batch_pad, nb_pad)
            if self.obs is not None:
                self._stash_buckets(rids, batch_pad, nb_pad)
        with span("engine.fetch"):
            return np.asarray(next_tokens)[:B]

    def _count_kernel_blocks(self, positions: List[int], batch_pad: int,
                             nb_pad: int):
        """Add one paged decode step to the kernel's engagement counters:
        the pool blocks it copies per layer (each row's ``ceil(length /
        block_size)`` with ``length = position + 1``, at most ``nb_pad``;
        padding rows have length 0) and the blocks of its padded
        ``[batch_pad, nb_pad]`` table. Host numbers only: no sync."""
        bs = self.ecfg.block_size
        self.kernel_live_blocks += sum(min(p // bs + 1, nb_pad)
                                       for p in positions)
        self.kernel_table_blocks += batch_pad * nb_pad

    def _stash_buckets(self, rids: List[int], batch_pad: int, nb_pad: int):
        """Keep the decode step's jit buckets for the memory-gap
        auditor's bucket-pad overlay: (batch_pad, nb_pad, live table
        entries). Only called while an observer is attached."""
        tables = self.pool.manager.tables
        self._last_buckets = (batch_pad, nb_pad, sum(
            min(len(tables[rid]), nb_pad) for rid in rids))

    def _decode_gather(self, reqs: List[Request]) -> np.ndarray:
        """Legacy dense-copy step (documented fallback); sampling runs as
        a separate jitted call on the returned logits."""
        with span("engine.decode"):
            rids = [r.req_id for r in reqs]
            max_pos = max(self._pos[rid] for rid in rids)
            pad_blocks = self.pool.manager.blocks_needed(
                _bucket(max_pos + 1, self.ecfg.block_size * 4))
            view = self.pool.gather(rids, pad_blocks)
            tokens, pos = self.pool.put((
                np.asarray([self._tokens[rid] for rid in rids], np.int32),
                np.asarray([self._pos[rid] for rid in rids], np.int32)))
            logits, new_cache = self._decode_jit(self.params, view, tokens,
                                                 pos)
            if self.obs is not None:
                self._stash_buckets(rids, len(rids), pad_blocks)
            self.pool.scatter_new_token(rids, [self._pos[r] for r in rids],
                                        new_cache)
            next_tokens = self._steps.sample(
                logits, *stack_sampling([r.sampling for r in reqs]),
                positions_array([self._pos[rid] + 1 for rid in rids]))
        with span("engine.fetch"):
            return np.asarray(next_tokens)

    # ------------------------------------------------ speculative decode --
    def rollback_kv(self, rid: int, n_tokens: int):
        """Token-granular KV rollback (phase-guarded pool.rollback):
        shrink ``rid`` to its first ``n_tokens`` tokens, releasing whole
        tail blocks. Refuses loudly for a PREFILLING request — chunk
        progress (``_prefilled``) tracks the table tail, and rolling the
        table back underneath it would silently desynchronize the two
        (preempt or abort the request instead)."""
        if rid in self._prefilled:
            raise RuntimeError(
                f"KV rollback of request {rid} during PREFILLING "
                f"({self._prefilled[rid]} prompt tokens streamed): chunked "
                f"prefill progress tracks the table tail — preempt or "
                f"abort instead of rolling back mid-prefill")
        return self.pool.rollback(rid, n_tokens)

    def _verify_paged(self, plan: StepPlan):
        """Dispatch one multi-token verify step (sync mode).

        Same bucketing discipline as ``_decode_paged`` plus a pow2 K
        bucket: the jit cache stays O(log batch x log tables x log K).
        Returns the device ``(ys, oks)`` of the padded batch.
        """
        reqs, rids, positions = plan.reqs, plan.rids, plan.positions
        drafts = plan.drafts
        B = len(reqs)
        max_blocks = max(len(self.pool.manager.tables[rid]) for rid in rids)
        nb_pad = _pow2_bucket(max_blocks, lo=4)
        batch_pad = _pow2_bucket(B)
        k_pad = _pow2_bucket(max((len(d) for d in drafts), default=1), lo=1)
        view = self.pool.view(rids, positions, nb_pad, batch_pad)
        tokens = np.zeros((batch_pad,), np.int32)
        tokens[:B] = [self._tokens[rid] for rid in rids]
        draft_mat, draft_len = stack_drafts(drafts, batch_pad, k_pad)
        # sampling params are frozen per request: stage them once per
        # batch composition and replay the device arrays; the per-step
        # payload (input tokens + drafts) goes up in one batched put
        samp_key = (tuple(rids), batch_pad)
        samp = self._spec_samp_cache.get(samp_key)
        if samp is None:
            if len(self._spec_samp_cache) > 64:
                self._spec_samp_cache.clear()
            samp = tuple(self.pool.put(stack_sampling(
                [r.sampling for r in reqs], pad_to=batch_pad)))
            self._spec_samp_cache[samp_key] = samp
        tokens_d, draft_mat_d, draft_len_d = self.pool.put(
            (tokens, draft_mat, draft_len))
        ys, oks, new_pool = self._spec_verify_jit(
            self.params, view.pool, view.tables, view.lengths,
            view.positions, view.slots, tokens_d, draft_mat_d, draft_len_d,
            *samp)
        self.pool.commit(new_pool)
        if self.obs is not None:
            self._stash_buckets(rids, batch_pad, nb_pad)
        return ys, oks

    def _spec_commit(self, plan: StepPlan, ys: np.ndarray, oks: np.ndarray,
                     t_done: float, valid: Optional[List[bool]] = None
                     ) -> int:
        """Commit one verify step's results: per row, the accepted draft
        prefix plus the correction/bonus sample, processed token-by-token
        through the exact serial finish protocol (a stop token ends the
        request mid-span and the tokens after it are discarded — serial
        decode would never have generated them), then the block-table
        tail reserved for uncommitted drafts is rolled back. Shared by
        the sync step and the executor's overlapped commit (``valid``
        masks rows invalidated while the step was in flight). Returns
        the number of committed tokens.
        """
        drafted = accepted = committed = 0
        for i, r in enumerate(plan.reqs):
            if valid is not None and not valid[i]:
                continue
            rid = r.req_id
            dl = len(plan.drafts[i])
            n_ok = accepted_prefix(oks[i], dl)
            drafted += dl
            accepted += n_ok
            if self.speculator is not None:
                self.speculator.observe(rid, n_ok, dl)
            finished = False
            for j in range(n_ok + 1):
                tok = int(ys[i][j])
                self._pos[rid] += 1
                self._tokens[rid] = tok
                r.state.generated += 1
                r.state.output_tokens.append(tok)
                committed += 1
                if self._finish_or_run(r, t_done):
                    finished = True
                    break
            if not finished:
                # release the table tail reserved for rejected drafts;
                # every committed position's K/V is already written and
                # the next input token's slot is re-reserved next plan
                self.rollback_kv(rid, self._pos[rid])
                # plan-time over-reservation (1 + K) corrected to what
                # actually committed — the overlap length gate reads this
                self.sched._dispatched[rid] = r.state.generated
        self.running = [r for r in self.running
                        if r.state.finish_reason is None]
        self.spec_steps += 1
        self.spec_drafted += drafted
        self.spec_accepted += accepted
        self.spec_rejected += drafted - accepted
        if drafted:
            self.spec_acceptance_samples.append(accepted / drafted)
        if self.obs is not None:
            self.obs.on_spec(self, drafted=drafted, accepted=accepted,
                             committed=committed)
        return committed

    def _spec_step_sync(self, plan: StepPlan, now: float):
        """Sync-mode speculative step: verify inline, commit, stamp the
        same telemetry series as the plain decode step (decode-token
        samples count *committed* tokens — tokens-per-step > batch is
        the speculation win made visible)."""
        reqs = plan.reqs
        with span("engine.decode"):
            ys, oks = self._verify_paged(plan)
        with span("engine.fetch"):
            ys, oks = jax.device_get((ys, oks))   # one fetch, one sync
        with span("engine.commit"):
            dt = time.perf_counter() - plan.t0
            committed = self._spec_commit(plan, ys, oks, now + dt)
            self.itl_samples.append(dt)
            self.stall_samples.append(plan.t_sched)
            self.prefill_token_samples.append(plan.n_prefill)
            self.decode_token_samples.append(committed)
            self.preemption_samples.append(self.preemptions - plan.p0)
            self.batch_samples.append(len(reqs))
            self.kv_fraction_samples.append(
                self.pool.manager.used_fraction)
            self.max_kv_fraction = max(self.max_kv_fraction,
                                       self.pool.manager.used_fraction)
            if self.obs is not None:
                self.obs.end_step(self, n_prefill=plan.n_prefill,
                                  n_decode=len(reqs), itl_s=dt)

    # --------------------------------------------------------------- run --
    def run(self, requests: List[Request]) -> ServingMetrics:
        """Batch-offline compatibility wrapper over the streaming facade
        (:class:`repro.serving.api.ServingAPI`): submit everything, drive
        steps to completion with arrival fast-forwarding, collect
        metrics. The wall clock installed for timestamping is restored on
        exit, so back-to-back runs — or facade/step use after a run —
        never stamp timestamps against a stale epoch."""
        from repro.serving.api import ServingAPI
        return ServingAPI(self).run(requests)


def _prefill_fn(model: Model, params, batch, cache_len: int):
    return model.prefill(params, batch, cache_len=cache_len)


def _prefix_prefill_fn(model: Model, params, batch, prefix_kv, prefix_len,
                       cache_len: int):
    """Suffix-only prefill against gathered prefix K/V (jitted; compile
    cache keyed on the bucketed suffix length and prefix-pad width —
    ``prefix_len`` itself is traced, so hit depth doesn't recompile)."""
    return model.prefill(params, batch, cache_len=cache_len,
                         prefix=prefix_kv, prefix_len=prefix_len)


def _decode_fn(model: Model, params, view, tokens, pos):
    return model.decode_step(params, view, tokens, pos, lengths=pos + 1)


def _chunk_prefill_fn(model: Model, block_size: int, params, pool,
                      tables, batch, prefix_len, n_valid, cache_len: int,
                      nb_prefix: int):
    """One fused chunked-prefill step (jitted; ``pool`` donated).

    The prefill analogue of ``_paged_decode_fn``: gather the request's
    already-written prefix K/V from the pool through its (trash-padded)
    block table, run the suffix prefill over the chunk, and scatter the
    chunk's ``n_valid`` KV rows back to their physical (block, slot)
    addresses — one XLA program per (chunk-width bucket, table pad,
    prefix pad) instead of eager per-leaf gathers and writes between two
    jit calls. ``prefix_len``/``n_valid`` are traced, so chunk progress
    never recompiles; ``nb_prefix`` (static) trims the gather to the
    power-of-two block count actually covering the prefix.
    """
    prefix_kv = gather_prefix_jit(pool, model.cfg, tables[:nb_prefix],
                                  block_size)
    logits, cache, _ = model.prefill(params, batch, cache_len=cache_len,
                                     prefix=prefix_kv,
                                     prefix_len=prefix_len)
    new_pool = scatter_rows_jit(pool, cache, model.cfg, tables, prefix_len,
                                n_valid, block_size)
    return logits, new_pool


def _paged_decode_fn(model: Model, block_size: int, params, pool, tables,
                     lengths, positions, slots, tokens, temperature,
                     top_k, top_p, seed):
    """One fused zero-copy decode step (jitted; ``pool`` donated).

    Rebuilds the view from its pytree parts (jit-friendly), runs the
    block-table decode, and samples each request's next token in the same
    program — greedy rows are pure argmax (bit-identical to the
    pre-sampler step), sampled rows draw with the counter-based key
    ``fold_in(seed, positions + 1)`` (the position the new token will
    occupy). Only B token ids cross back to the host.
    """
    view = PagedCacheView(pool, tables, lengths, positions, slots,
                          block_size)
    logits, new_pool = model.decode_step(params, view, tokens, positions,
                                         lengths=lengths)
    next_tokens = sample_tokens(logits, temperature, top_k, top_p, seed,
                                positions + 1)
    return next_tokens, new_pool
