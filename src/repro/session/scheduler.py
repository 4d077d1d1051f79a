"""Continuous-batching serving scheduler.

The static ``generate()`` batch waits for its slowest request: a slot that
finished early keeps burning a decode lane until the whole batch drains.
This module replaces that with the serving-side analogue of the paper's
batching-dominates-utilization observation: a ``RequestQueue`` feeding a
fixed ring of ``n_slots`` cache slots, where

  * every decode step runs at FULL batch width over all active slots, each
    slot at its own position (``stepfn.make_slot_serve_step``);
  * a finished request (stop token / ``max_new_tokens``) frees its slot
    immediately;
  * queued requests are admitted mid-flight: ALL free slots are filled in
    one pass, and requests sharing a prefill width go through ONE batched
    mixed-length prefill call (right-padded rows with per-row lengths) —
    no other slot ever stalls or recompiles;
  * admission prefills are bucketed to power-of-two prompt lengths (pad to
    the bucket, gather logits at ``lengths-1``, invalidate padded cache
    slots) on causal-attention families, so mixed-length workloads compile
    at most log2(max_len) × n_slots prefill shapes instead of one per
    distinct length.

One loop serves two cache layouts: ``SlotLayout`` (contiguous per-slot
caches, every family through the ``ModelFamily.cache_slot_axes`` hook:
ring-buffer KV, SSM/sLSTM states, hybrid lists, cross-KV stacks) and
``kvpool.PagedLayout`` (the block-paged pool, attention stacks only).
Greedy decode here is token-for-token identical to running each request
alone through ``generate()``.  Requests carry token prompts only: for
encdec the slot template is built from the family's stubbed zero encoder
frames, so per-request encoder inputs are a follow-up (the slot mechanics
already cover the cross-KV layout).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.session.kvpool import PagedLayout


@dataclasses.dataclass
class Request:
    """One serving request: a prompt plus its decode budget/stop rule."""
    rid: int
    prompt: np.ndarray                 # (P,) int32 token ids
    max_new_tokens: int
    stop_token: Optional[int] = None
    submit_time: float = 0.0
    admit_time: Optional[float] = None        # admission (prefill) start
    first_token_time: Optional[float] = None  # first token read


@dataclasses.dataclass
class ServingStats:
    """What the serving path actually achieved on a request set."""
    requests: int = 0
    generated_tokens: int = 0
    decode_steps: int = 0
    occupancy: float = 0.0             # mean active-slot fraction per decode step
    admit_s: float = 0.0               # time in admission: prefills and the
    #                                    first-token reads that wait on them
    max_queue_depth: int = 0
    # memory accounting (see repro.session.kvpool): stranded_fraction is the
    # mean over decode steps of 1 - live_tokens / reserved_token_capacity —
    # fixed slots reserve n_active*max_len, the paged pool only mapped pages
    stranded_fraction: float = 0.0
    prompt_tokens: int = 0             # tokens across all admitted prompts
    prefill_tokens: int = 0            # tokens actually prefilled (≤ prompt)
    # paged-pool mode only
    page_size: int = 0
    pool_pages: int = 0                # allocatable pages (excl. trash page)
    pool_occupancy: float = 0.0        # mean allocated-page fraction per step
    prefix_hits: int = 0               # admissions that shared ≥ 1 token
    prefix_hit_rate: float = 0.0       # shared prompt tokens / prompt tokens
    # the run's clock: one stamp per decode step, taken once its tokens are
    # read; per request, in the order they finished, its stamps and the
    # decode steps [first, end) that gave it a token after the first
    step_times: List[float] = dataclasses.field(default_factory=list)
    submit_times: List[float] = dataclasses.field(default_factory=list)
    admit_times: List[float] = dataclasses.field(default_factory=list)
    first_token_times: List[float] = dataclasses.field(default_factory=list)
    token_steps: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list)

    def finished(self, st: "_Slot", steps: int) -> None:
        """Record request ``st`` retiring after ``steps`` decode steps."""
        req = st.req
        self.submit_times.append(req.submit_time)
        self.admit_times.append(req.admit_time)
        self.first_token_times.append(req.first_token_time)
        self.token_steps.append((st.first_step, steps))

    @property
    def mean_queue_wait_s(self) -> float:
        """Submit → admission (prefill start), over finished requests."""
        return _mean_gap(self.submit_times, self.admit_times)

    @property
    def mean_ttft_s(self) -> float:
        """Submit → first token read, over finished requests."""
        return _mean_gap(self.submit_times, self.first_token_times)

    def finish_times(self) -> List[float]:
        """When each finished request's last token was read."""
        return [self.step_times[b - 1] if b > a else t0
                for t0, (a, b) in zip(self.first_token_times,
                                      self.token_steps)]

    def token_gaps(self) -> List[float]:
        """Every finished request's gaps between consecutive tokens."""
        gaps = []
        for t0, (a, b) in zip(self.first_token_times, self.token_steps):
            times = [t0] + self.step_times[a:b]
            gaps += [y - x for x, y in zip(times, times[1:])]
        return gaps

    def __str__(self) -> str:
        latency = _mean_gap(self.submit_times, self.finish_times())
        return (f"ServingStats(requests={self.requests}, "
                f"occupancy={self.occupancy:.2f}, "
                f"stranded={self.stranded_fraction:.2f}, "
                f"steps={self.decode_steps}, "
                f"queue_wait={self.mean_queue_wait_s * 1e3:.1f}ms, "
                f"ttft={self.mean_ttft_s * 1e3:.1f}ms, "
                f"latency={latency * 1e3:.1f}ms)")


def _mean_gap(starts: List[float], ends: List[float]) -> float:
    return float(np.mean(np.subtract(ends, starts))) if starts else 0.0


class RequestQueue:
    """FIFO admission queue; records submit times for queue-wait stats."""

    def __init__(self, clock=time.perf_counter):
        self._q: deque = deque()
        self._next_rid = 0
        self._clock = clock
        self.max_depth = 0

    def submit(self, prompt, max_new_tokens: int,
               stop_token: Optional[int] = None) -> int:
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            # an empty prompt has no position to read first-token logits from:
            # the bucketed prefill would gather at lengths-1 == -1 (wrapping
            # to a padded slot → garbage token) and the unbucketed path would
            # crash on a (1, 0) tokens array — reject at the API edge instead
            raise ValueError("prompt must contain at least one token")
        rid = self._next_rid
        self._next_rid += 1
        self._q.append(Request(rid, prompt, int(max_new_tokens), stop_token,
                               submit_time=self._clock()))
        self.max_depth = max(self.max_depth, len(self._q))
        return rid

    def pop(self) -> Request:
        return self._q.popleft()

    def push_front(self, req: Request) -> None:
        """Return a popped-but-unadmitted request to the head of the queue
        (the paged layout defers admissions under pool pressure)."""
        self._q.appendleft(req)

    def pending(self) -> Tuple[Request, ...]:
        return tuple(self._q)

    def __len__(self) -> int:
        return len(self._q)


@dataclasses.dataclass
class _Slot:
    """Host-side decode state of one occupied cache slot."""
    req: Request
    t: int                             # next decode position (= tokens ingested)
    last: int                          # last emitted token (next step's input)
    out: List[int]                     # prompt + generated so far
    remaining: int                     # new tokens still owed
    first_step: int = 0                # first decode step it takes part in


class SlotLayout:
    """Contiguous per-slot caches, for every family's cache layout
    (``ModelFamily.cache_slot_axes``): each slot holds ``max_len``
    positions, an admitted row is prefilled into a zeroed template and
    written into its slot whole, and a retired slot is zeroed at once.
    ``kvpool.PagedLayout`` answers the same calls from the scheduler."""

    def __init__(self, session, n_slots: int, max_len: int):
        self.session = session
        self.n_slots = n_slots
        self.max_len = max_len
        self.cap = max_len                # widest prefill a row may take
        self._fresh = None                # immutable width-n_slots template
        self.caches = None

    def start(self) -> None:
        self.caches = self.session.init_cache(self.n_slots, self.max_len)

    def check_fits(self, req: Request) -> None:
        """A request within ``max_len`` fits any slot."""

    def admit(self, assignments, held):
        return [(slot, req, 0) for slot, req in assignments], []

    def _fresh_cache(self, width: int):
        """Zeroed width-``width`` prefill template.  Only the full-width
        template is retained; narrower admissions slice it, so the scheduler
        holds at most ONE extra cache's worth of device memory."""
        from repro.core import stepfn
        if self._fresh is None:
            self._fresh = self.session.init_cache(self.n_slots, self.max_len)
        if width == self.n_slots:
            return self._fresh
        return stepfn.cache_slice_slots(self.session.cfg, self._fresh, 0, width)

    def prefill(self, tokens, lengths, hists, slot_ids):
        sess = self.session
        W, L = tokens.shape
        batch = {"tokens": jnp.asarray(tokens)}
        if (lengths != L).any():
            batch["lengths"] = jnp.asarray(lengths)
            # real tokens get segment 0, the pad tail -1: no row ever
            # attends into its padding, on any sdpa path
            batch["segment_ids"] = jnp.asarray(
                np.where(np.arange(L)[None] < lengths[:, None], 0, -1)
                .astype(np.int32))
        return sess.prefill_cache_step(sess.params, batch,
                                       self._fresh_cache(W))

    def commit(self, rows, slot_ids, prompts) -> None:
        """Write each prefilled width-1 cache row into its slot."""
        sess = self.session
        for j, slot in enumerate(slot_ids):
            row = rows if len(slot_ids) == 1 else sess.take_slot(
                rows, jnp.int32(j))
            self.caches = sess.insert_slot(self.caches, row, jnp.int32(slot))

    def step(self, toks, ts, active: List[int]):
        sess = self.session
        nxt, self.caches = sess.slot_step(sess.params, jnp.asarray(toks),
                                          jnp.asarray(ts), self.caches)
        return nxt

    def release(self, slot: int) -> None:
        # reset the freed slot on device (pos → -1, state → 0): stale K/V
        # must be invalid the moment the slot is free, not only after the
        # next admission happens to overwrite it
        self.caches = self.session.zero_slot(self.caches, jnp.int32(slot))

    def capacity(self, active: List[int]) -> int:
        return len(active) * self.max_len

    def stats_fields(self, stats: ServingStats) -> dict:
        return {}


class ContinuousBatchingScheduler:
    """Slot-based continuous batching over an ``InferenceSession``.

    ``n_slots`` is the decode batch width; ``max_len`` the per-slot cache
    length (every admitted request needs prompt + max_new_tokens ≤ max_len).
    ``paged=True`` keeps the KV state in ``kvpool.PagedLayout`` (``n_pages``
    pages of ``page_size`` tokens), else in ``SlotLayout``.
    """

    def __init__(self, session, *, n_slots: int, max_len: int,
                 paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        self.session = session
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        # admission prefills retrace per distinct prompt shape; padding to
        # power-of-two buckets bounds the trace count at log2(max_len) on
        # families whose prefill honors batch["lengths"] (causal attention
        # stacks — see ModelFamily.supports_padded_prefill)
        self.bucket_prefills = session.family.supports_padded_prefill(
            session.cfg)
        self.layout = PagedLayout(session, self.n_slots, self.max_len,
                                  page_size=page_size, n_pages=n_pages) \
            if paged else SlotLayout(session, self.n_slots, self.max_len)

    # ------------------------------------------------------------------
    def _bucket_len(self, P: int) -> int:
        """Power-of-two prefill bucket for a prompt of length ``P``, capped
        at the slot's cache length (position p and p+size would collide in
        the ring past that)."""
        assert P >= 1, "empty prompts are rejected at RequestQueue.submit"
        return min(max(1 << (P - 1).bit_length(), 16), self.max_len)

    def _admit(self, assignments: List[Tuple[int, Request]],
               held: List[Request], clock) -> Tuple:
        """Batched admission next to the requests ``held`` in other slots.
        The layout maps each request, giving the prompt tokens its cache
        already holds (``hist``), or defers it.  Requests sharing a prefill
        width (their suffix's bucket, or exact length when the family cannot
        bucket) are ingested in ONE mixed-length prefill call — shorter
        suffixes ride right-padded with per-row lengths — and their first
        tokens are read once.  Returns ({slot: _Slot}, [deferred requests],
        prefilled tokens)."""
        rows, deferred = self.layout.admit(assignments, held)
        groups: Dict[int, List[Tuple[int, Request, int]]] = {}
        for slot, req, hist in rows:
            n = len(req.prompt) - hist
            L = min(self._bucket_len(n), self.layout.cap - hist) \
                if self.bucket_prefills else n
            groups.setdefault(L, []).append((slot, req, hist))

        states: Dict[int, _Slot] = {}
        prefilled = 0
        for L, items in sorted(groups.items()):
            W = len(items)
            tokens = np.zeros((W, L), np.int32)
            lengths = np.zeros((W,), np.int32)
            hists = np.zeros((W,), np.int32)
            for j, (_, req, hist) in enumerate(items):
                suffix = req.prompt[hist:]
                tokens[j, :len(suffix)] = suffix
                lengths[j] = len(suffix)
                hists[j] = hist
            slot_ids = [slot for slot, _, _ in items]
            logits, written = self.layout.prefill(tokens, lengths, hists,
                                                  slot_ids)
            toks0 = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            first_token_time = clock()
            self.layout.commit(written, slot_ids,
                               [req.prompt for _, req, _ in items])
            prefilled += int(lengths.sum())
            for j, (slot, req, _) in enumerate(items):
                req.first_token_time = first_token_time
                states[slot] = _Slot(
                    req=req, t=len(req.prompt), last=int(toks0[j]),
                    out=list(map(int, req.prompt)) + [int(toks0[j])],
                    remaining=req.max_new_tokens - 1)
        return states, deferred, prefilled

    @staticmethod
    def _finished(state: _Slot) -> bool:
        stop = state.req.stop_token
        return state.remaining <= 0 or (stop is not None and state.last == stop)

    # ------------------------------------------------------------------
    def run(self, queue: RequestQueue,
            clock=time.perf_counter) -> Tuple[Dict[int, np.ndarray], ServingStats]:
        """Drain ``queue``; returns ({rid: prompt+generated token array},
        ``ServingStats``)."""
        layout = self.layout
        B = self.n_slots
        # preflight: reject impossible requests before ANY decode work, so a
        # bad request can't abort a half-drained queue and lose finished
        # outputs (requests are only popped once they fit)
        for req in queue.pending():
            P = len(req.prompt)
            if P + req.max_new_tokens > self.max_len:
                raise ValueError(
                    f"request {req.rid}: prompt {P} + max_new "
                    f"{req.max_new_tokens} exceeds scheduler max_len "
                    f"{self.max_len}")
            layout.check_fits(req)
        layout.start()
        slots: List[Optional[_Slot]] = [None] * B
        outputs: Dict[int, np.ndarray] = {}
        stats = ServingStats()
        steps = occupied = generated = n_requests = 0
        prompt_tokens = prefill_tokens = 0
        stranded = 0.0

        def retire(i: int):
            nonlocal generated
            st = slots[i]
            outputs[st.req.rid] = np.asarray(st.out, np.int32)
            stats.finished(st, steps)
            generated += len(st.out) - len(st.req.prompt)
            layout.release(i)
            slots[i] = None

        while len(queue) or any(s is not None for s in slots):
            # admission: ALL free slots pick up queued requests in one go —
            # same-width prompts share a single batched mixed-length prefill
            free = [i for i in range(B) if slots[i] is None]
            if free and len(queue):
                with TraceAnnotation("serve.admit"):
                    t_admit = clock()
                    assignments = [(i, queue.pop())
                                   for i in free[:min(len(free), len(queue))]]
                    admitted, deferred, prefilled = self._admit(
                        assignments, [s.req for s in slots if s is not None],
                        clock)
                    for req in reversed(deferred):
                        queue.push_front(req)
                    if deferred and not admitted and \
                            all(s is None for s in slots):
                        raise MemoryError(
                            f"{layout} cannot admit request "
                            f"{deferred[0].rid} even with every slot idle — "
                            "grow n_pages or shrink max_len")
                    prefill_tokens += prefilled
                    for i, st in admitted.items():
                        slots[i] = st
                        st.req.admit_time = t_admit
                        st.first_step = steps
                        n_requests += 1
                        prompt_tokens += len(st.req.prompt)
                        if self._finished(st):     # stop token in prefill,
                            retire(i)              # or max_new_tokens == 1
                    stats.admit_s += clock() - t_admit

            active = [i for i in range(B) if slots[i] is not None]
            if not active:
                continue

            # one decode step at full batch width, per-slot positions
            with TraceAnnotation("serve.decode"):
                toks = np.zeros((B,), np.int32)
                ts = np.zeros((B,), np.int32)
                for i in active:
                    toks[i] = slots[i].last
                    ts[i] = slots[i].t
                nxt = layout.step(toks, ts, active)
            with TraceAnnotation("serve.read"):
                nxt = np.asarray(nxt)
            stats.step_times.append(clock())
            with TraceAnnotation("serve.retire"):
                steps += 1
                occupied += len(active)
                live = sum(slots[i].t for i in active)
                stranded += 1.0 - live / layout.capacity(active)

                for i in active:
                    st = slots[i]
                    st.last = int(nxt[i])
                    st.out.append(st.last)
                    st.t += 1
                    st.remaining -= 1
                    if self._finished(st):
                        retire(i)

        stats = dataclasses.replace(
            stats,
            requests=n_requests,
            generated_tokens=generated,
            decode_steps=steps,
            occupancy=occupied / (steps * B) if steps else 0.0,
            max_queue_depth=queue.max_depth,
            stranded_fraction=stranded / steps if steps else 0.0,
            prompt_tokens=prompt_tokens,
            prefill_tokens=prefill_tokens,
        )
        return outputs, dataclasses.replace(stats,
                                            **layout.stats_fields(stats))
