"""The client side of a run: warm-up, submission, token stamps.

The engine is driven through the ``ServingAPI`` facade only: ``submit``
when a request is due, and ``stream(handle)`` of one in-flight request
(the *driver*) to advance scheduling rounds (the facade has no public
"advance one round"; the stream of a decoding request yields once per
round). After every pump the client stamps each new output token of every
in-flight request with the host clock: where the client sees it.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench.lib.traffic import Item, warm_tokens


@dataclasses.dataclass
class Round:
    t: float                    # host clock when its tokens were stamped
    steps: int                  # engine steps the pump ran
    prompts: List[int]          # prompt lengths whose first token came
    contexts: List[int]         # keys attended by each decode token made
    pool_used: float            # share of pool blocks in use afterwards


def sampling(max_new: int):
    from repro.serving import SamplingParams
    return SamplingParams(temperature=0.0, max_new_tokens=int(max_new),
                          ignore_eos=True)


def warm_up(api, items: List[Item], max_batch: int, block: int, seed: int,
            vocab: int) -> Dict:
    """Compile (or load from the cache) every program the traffic uses.

    1. One request per distinct prompt length of the traffic, each
       finishing at its first token: every prefill and pool-write program.
    2. A full batch of ``max_batch`` requests: the traffic's longest
       prompt and the others at its shortest, decoding together until the
       longest context crosses the next power-of-two count of pool blocks
       (the decode step's table width bucket), or reaches the traffic's
       longest context: every decode program a full batch meets.
    """
    plens = sorted({len(it.prompt) for it in items})
    longest = max(len(it.prompt) + it.max_new for it in items)
    toks = warm_tokens(vocab, seed, plens[-1] + 1)
    t0 = time.perf_counter()
    handles = [api.submit(toks[:L], sampling(1)) for L in plens]
    api.drain()
    t1 = time.perf_counter()
    blocks = -(-(plens[-1] + 1) // block)
    boundary = block * (1 << (blocks - 1).bit_length())
    budget = max(1, min(longest, boundary + 4) - plens[-1])
    handles.append(api.submit(toks[:plens[-1]], sampling(budget)))
    handles += [api.submit(toks[:plens[0]], sampling(budget))
                for _ in range(max_batch - 1)]
    api.drain()
    for h in handles:
        api.release(h)
    return {"prefill_warm_s": t1 - t0,
            "decode_warm_s": time.perf_counter() - t1,
            "prompt_lengths": len(plens), "decode_warm_tokens": budget}


class Run:
    """Submits ``items`` when due and stamps every token the client sees."""

    def __init__(self, api, engine, items: List[Item]):
        self.api, self.eng, self.items = api, engine, items
        self.handles: List = []
        self.stamps: List[List[float]] = []
        self.t_done: Dict[int, float] = {}
        self.live: List[int] = []          # started, not finished
        self.unstarted: deque = deque()    # submitted, no token yet
        self.rounds: List[Round] = []
        self._driver = None                # (index, generator)
        self.t_start = self.t_open = self.t_close = None

    # ------------------------------------------------------------ submit --
    def _submit_due(self, now: float):
        while len(self.handles) < len(self.items):
            it = self.items[len(self.handles)]
            if self.t_start + it.due_s > now:
                return
            self.handles.append(self.api.submit(it.prompt,
                                                sampling(it.max_new)))
            self.stamps.append([])
            self.unstarted.append(len(self.handles) - 1)

    # -------------------------------------------------------------- pump --
    def _pump(self):
        idx = self.live[0] if self.live else self.unstarted[0]
        if self._driver is None or self._driver[0] != idx:
            if self._driver is not None:
                self._driver[1].close()
            self._driver = (idx, self.api.stream(self.handles[idx]))
        steps = self.eng.step_count
        with jax.profiler.TraceAnnotation("bench.pump"):
            try:
                next(self._driver[1])
            except StopIteration:
                self._driver = None
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.stamp"):
            self._stamp(t, self.eng.step_count - steps)

    def _take(self, idx: int, t: float, prompts, contexts) -> bool:
        """Stamp request ``idx``'s new tokens; True once it finished."""
        h = self.handles[idx]
        n = len(h.request.state.output_tokens)
        st = self.stamps[idx]
        P = len(self.items[idx].prompt)
        for j in range(len(st), n):
            st.append(t)
            if j == 0:
                prompts.append(P)
            else:
                contexts.append(P + j)
        if h.done:
            self.t_done[idx] = t
            return True
        return False

    def _stamp(self, t: float, steps: int):
        prompts: List[int] = []
        contexts: List[int] = []
        self.live = [i for i in self.live
                     if not self._take(i, t, prompts, contexts)]
        while self.unstarted:
            i = self.unstarted[0]
            h = self.handles[i]
            if not h.request.state.output_tokens and not h.done:
                break                       # admission is first come first served
            self.unstarted.popleft()
            if not self._take(i, t, prompts, contexts):
                self.live.append(i)
        self.rounds.append(Round(t, steps, prompts, contexts,
                                 self.eng.pool.manager.used_fraction))

    # ------------------------------------------------------------ driver --
    def drive(self, seconds: float, opens: Callable[["Run"], bool],
              on_open: Callable[[], None] = lambda: None):
        """Serve until the window has closed. The window opens after the
        first round at which ``opens(run)`` holds; ``on_open`` runs right
        before it opens."""
        self.t_start = time.perf_counter()
        while True:
            now = time.perf_counter()
            if self.t_close is not None and now >= self.t_close:
                return
            self._submit_due(now)
            if not self.live and not self.unstarted:
                raise RuntimeError("the traffic ran out before the window "
                                   "closed")
            self._pump()
            if self.t_open is None and opens(self):
                on_open()
                self.t_open = time.perf_counter()
                self.t_close = self.t_open + seconds

    # ----------------------------------------------------------- reading --
    def in_window(self, t: float, end: Optional[float] = None) -> bool:
        return self.t_open < t <= (self.t_close if end is None else end)

    def window_rounds(self, end: Optional[float] = None) -> List[Round]:
        return [r for r in self.rounds if self.in_window(r.t, end)]

    def tokens_in_window(self) -> int:
        return sum(1 for st in self.stamps for t in st if self.in_window(t))

    def itl_s(self) -> np.ndarray:
        return np.asarray([b - a for st in self.stamps
                           for a, b in zip(st, st[1:])
                           if self.in_window(a) and self.in_window(b)])

    def attempted(self) -> List[int]:
        return [i for i, st in enumerate(self.stamps)
                if any(self.in_window(t) for t in st)]

    def finished_in_window(self) -> List[int]:
        return [i for i, t in self.t_done.items() if self.in_window(t)]

    def output(self, idx: int) -> List[int]:
        return list(self.handles[idx].request.state.output_tokens)

    def finish_reason(self, idx: int) -> Optional[str]:
        return self.handles[idx].finish_reason
