"""Slot scheduler for continuous-batching serving (host-side control plane).

The engine (``serving/engine.py``) holds a fixed number of *slots* — batch
rows of the per-slot tiered KV cache — and decodes all active slots in
lock-free step: each slot is at its own sequence length. This module owns
the host-side bookkeeping around that device state:

  * a bounded admission queue (``submit``; overflow is *shed*, never
    silently grown — the backpressure contract in docs/serving.md),
  * the slot table (which request occupies which slot),
  * admission pairing — either **chunked** (``next_fills``: every free
    slot takes the strongest-claim queued request, any prompt length;
    the engine streams the prompt in as fixed-size chunk dispatches) or
    **grouped** (``next_group``: same-prompt-length requests share one
    whole-prompt prefill dispatch),
  * retirement: freeing a slot once its request is done,
  * preemption support: ``requeue`` puts a victim's request back at the
    head of the queue and ``preempt_victims`` ranks which active slots a
    pressured admission/growth may reclaim (newest-first / fewest-
    tokens-emitted, never a stronger claim than the beneficiary's).

The scheduler never touches device arrays; it only decides *which* slots
the engine should fill or free at each synchronization point. Under
paged serving the admission step additionally consults the refcounted
prefix tree (``serving/paging.py``): a new prompt's longest cached
prefix is adopted by reference (plus a copy-on-write boundary page) and
only the novel suffix is chunk-prefilled. Mid-decode
admission is the point of the design: new prompts prefill into freed slots
while the remaining slots keep decoding, so the decode hot loop stays
saturated instead of draining the whole batch (the seed engine's lock-step
model, where the slowest sequence gated everyone).

Admission order is by *claim* — ``(priority desc, arrival asc)`` — which
degrades to plain FIFO when every request carries the default priority.
Both policies are pad-free (padded prompt tokens would pollute
the causal KV cache; chunked admission masks the final partial chunk by
per-slot valid counts instead). The difference is compilation shape:
grouped admission costs one XLA prefill compilation per (group_size,
prompt_len) pair and makes unequal lengths wait for a shape partner;
chunked admission has exactly one fixed (slots, chunk) dispatch shape,
so any length mix admits immediately (docs/serving.md, "Admission").

docs/serving.md documents the full lifecycle this module drives
(admission -> decode chunks -> retirement/preemption) and the
``sync_every`` semantics of the engine loop around it; the "Degradation
modes" section covers the overload paths (preemption, deadlines,
cancellation, shedding).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Deque, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np


class SchedulerError(RuntimeError):
    """Slot-table misuse (retiring or requeueing an unoccupied slot):
    carries the slot index so the report survives ``python -O``."""

    def __init__(self, msg: str, slot: Optional[int] = None):
        if slot is not None:
            msg = f"{msg} (slot={slot})"
        super().__init__(msg)
        self.slot = slot


@dataclasses.dataclass(eq=False)
class Request:
    """One generation request (identity equality: the queue removes
    requests by object, and field equality would compare prompt arrays).

    ``tokens`` is the prompt (prompt_len,) int32; ``patches`` carries VLM
    image features when the model has a vision frontend. ``deadline`` is
    an absolute time on the engine's clock (``Engine(clock=...)``) after
    which the request is expired instead of served further; ``priority``
    orders admission and bounds preemption (a request may only preempt
    strictly weaker claims — lower priority, or equal priority but later
    arrival).

    The remaining fields are engine-managed preemption bookkeeping: a
    preempted request's already-emitted tokens are folded into ``tokens``
    (so re-admission rides the prefix cache and recomputes only past the
    shared prefix), ``orig_prompt_len`` remembers where the real prompt
    ended, and the carried ledgers accumulate the work the earlier
    attempts already paid for.
    """

    rid: int
    tokens: np.ndarray
    max_new_tokens: int
    patches: Optional[np.ndarray] = None
    deadline: Optional[float] = None
    priority: int = 0
    # -- engine-managed (preemption / accounting) -----------------------
    arrival: Optional[int] = None  # submission order, stamped once
    n_preemptions: int = 0
    orig_prompt_len: Optional[int] = None  # set when emitted tokens fold in
    carry_traffic: Optional[Dict[str, int]] = None  # bytes, prior attempts
    carry_reused: int = 0  # prefix tokens reused by prior attempts
    # speculative-decoding ledger of prior attempts (draft proposals
    # scored / accepted before a preemption), folded into the terminal
    # FinishedRequest so acceptance accounting survives eviction
    carry_drafted: int = 0
    carry_accepted: int = 0
    # engine-clock times of the first admission into a slot and of the
    # sync point that first saw the request past its prefill (its first
    # token); an earlier attempt's stamps survive preemption
    t_admit: Optional[float] = None
    t_first: Optional[float] = None

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.tokens).shape[-1])

    @property
    def claim(self) -> Tuple[int, int]:
        """Admission/preemption strength: lexicographically SMALLER is
        stronger. Arrival breaks priority ties, so the oldest request at
        the top priority can always preempt everyone else — the global-
        progress guarantee preemption liveness rests on."""
        return (-self.priority, self.arrival if self.arrival is not None else 0)


@dataclasses.dataclass
class FinishedRequest:
    """A completed (or terminated) request with its per-sequence DR-traffic
    ledger.

    ``traffic`` is in bytes, split into the four DR-eDRAM categories
    (ondie_read / ext_read / ondie_write / ext_write); it accumulates the
    analytic prompt phase plus the measured per-step decode ledger, so
    ``external_reduction`` reconciles with
    ``dr_edram.closed_form_reduction(seq_len, hot_cap)`` for *this*
    sequence regardless of what other lengths shared the batch. (For a
    preempted-and-resumed request the ledger additionally carries the
    recomputed prefill work of the earlier attempts, so it reports what
    the device actually did, not the unconstrained closed form.)

    ``outcome`` is the terminal state: ``finished`` (full budget or stop
    token), ``cancelled`` (``Engine.cancel`` / ``Router.cancel``),
    ``expired`` (deadline), ``rejected`` (shed by the bounded queue
    before any work ran), or ``failed`` (router-level: the per-request
    retry budget was exhausted across replica failures — single-engine
    serving never emits it). Non-``finished`` outcomes still surface any
    tokens emitted before termination. ``n_preemptions`` counts how many
    times the request was evicted mid-flight and recomputed-from-prefix.
    """

    rid: int
    prompt_len: int
    tokens: np.ndarray  # (n_generated,) int32
    seq_len: int  # prompt + appended decode tokens
    steps: int  # decode dispatches this request was active for
    traffic: Dict[str, int]
    # prompt tokens restored from the shared prefix cache instead of being
    # prefilled (paged serving with prefix sharing; see serving/paging.py).
    # The skipped prefill steps vanish from ``traffic`` — the DR-ledger
    # external-read delta vs an unshared run reconciles with this count.
    prefix_tokens_reused: int = 0
    outcome: str = "finished"
    n_preemptions: int = 0
    # speculative decoding (Engine(spec_k=K)): draft proposals the
    # verifier scored for this request, and how many it accepted. Every
    # round emits 1 + accepted tokens, so the per-request identity
    # ``len(tokens) == accepted + rounds`` reconciles the ledger exactly
    # (asserted in tests/test_speculative.py); both stay 0 on
    # non-speculative engines.
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    # engine-clock timestamps (``Engine(clock=...)``): first admission
    # into a slot, the sync point that first saw the request past its
    # prefill (its first token), and the terminal record; None where the
    # request never got that far
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_finish: Optional[float] = None

    @property
    def acceptance_rate(self) -> float:
        """Fraction of draft proposals the target confirmed (0.0 when
        nothing was drafted — non-speculative runs, empty generations)."""
        return (self.accepted_tokens / self.drafted_tokens
                if self.drafted_tokens else 0.0)

    @property
    def external_reduction(self) -> float:
        from repro.core.kv_cache import external_reduction

        return external_reduction(self.traffic)


def terminal_record(req: Request, outcome: str) -> FinishedRequest:
    """Terminal record for a request that holds no slot (rejected /
    cancelled / expired while queued, or failed at the router after its
    retry budget ran out). A preempted-then-terminated request still
    surfaces the tokens its earlier attempts emitted (folded into
    ``tokens`` past ``orig_prompt_len``) and the work they cost
    (``carry_traffic``). Pure host bookkeeping — both the engine's
    queue sweep and the router's fleet-level terminations route through
    this one constructor so the two layers can never disagree on what a
    slotless terminal looks like."""
    from repro.core.kv_cache import TRAFFIC_KEYS

    if req.orig_prompt_len is not None:
        tokens = np.asarray(req.tokens, np.int32)[req.orig_prompt_len:]
        prompt_len = req.orig_prompt_len
    else:
        tokens = np.zeros((0,), np.int32)
        prompt_len = req.prompt_len
    traffic = (dict(req.carry_traffic) if req.carry_traffic
               else {k: 0 for k in TRAFFIC_KEYS})
    return FinishedRequest(
        rid=req.rid, prompt_len=prompt_len, tokens=tokens,
        seq_len=prompt_len + len(tokens), steps=len(tokens),
        traffic=traffic, prefix_tokens_reused=req.carry_reused,
        outcome=outcome, n_preemptions=req.n_preemptions,
        t_admit=req.t_admit, t_first=req.t_first,
        drafted_tokens=req.carry_drafted,
        accepted_tokens=req.carry_accepted,
    )


class SlotScheduler:
    """Host-side slot table + bounded claim-ordered admission queue (see
    module docstring)."""

    def __init__(self, n_slots: int, max_queue: Optional[int] = None):
        self.n_slots = n_slots
        self.max_queue = max_queue
        self.queue: Deque[Request] = deque()
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self._arrival = 0

    # -- queue ----------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Enqueue ``req``; returns False (shed) when the bounded queue is
        full. The arrival stamp is assigned once and survives preemption
        requeues, so a preempted request keeps its place in claim order."""
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            return False
        if req.arrival is None:
            req.arrival = self._arrival
            self._arrival += 1
        self.queue.append(req)
        return True

    def drop(self, req: Request) -> None:
        """Remove a queued request (cancellation / deadline expiry)."""
        self.queue.remove(req)

    # -- slot table -----------------------------------------------------
    def free_slots(self) -> List[int]:
        """Slot indices with no live request (admission targets)."""
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def active_slots(self) -> List[int]:
        """Slot indices currently holding a live request."""
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    # -- admission ------------------------------------------------------
    def _pop_best(self) -> Request:
        """Remove and return the strongest-claim queued request (plain
        FIFO when priorities are uniform). O(queue) — queues here are
        short host-side structures, not token streams."""
        best = min(self.queue, key=lambda r: r.claim)
        self.queue.remove(best)
        return best

    @staticmethod
    def _group_key(req: Request):
        """Requests may share a prefill dispatch iff their stacked batch is
        homogeneous: same prompt length AND same frontend-feature shape
        (patches present with one shape, or absent)."""
        patches = None if req.patches is None else np.asarray(req.patches).shape
        return (req.prompt_len, patches)

    def next_group(self) -> Tuple[List[int], List[Request]]:
        """Pop the next admissible group: the strongest-claim request plus
        any queued requests sharing its group key (prompt length + patches
        shape), up to the number of free slots. Returns ([], []) when
        nothing can be admitted."""
        free = self.free_slots()
        if not free or not self.queue:
            return [], []
        head = min(self.queue, key=lambda r: r.claim)
        key = self._group_key(head)
        group: List[Request] = []
        for req in sorted(self.queue, key=lambda r: r.claim):
            if len(group) >= len(free):
                break
            if self._group_key(req) == key:
                group.append(req)
        for req in group:
            self.queue.remove(req)
        slots = free[: len(group)]
        for s, req in zip(slots, group):
            self.slot_req[s] = req
        return slots, group

    def next_fills(self) -> List[Tuple[int, Request]]:
        """Chunked-admission pairing: hand each free slot the strongest-
        claim queued request — no length grouping. Chunk streaming makes
        the prompt length irrelevant to compilation (the engine's chunk
        dispatch has one fixed (slots, chunk) shape), so unlike
        ``next_group`` nothing ever waits for a shape partner and there
        is no head-of-line blocking on unusual prompt lengths."""
        out: List[Tuple[int, Request]] = []
        for s in self.free_slots():
            if not self.queue:
                break
            req = self._pop_best()
            self.slot_req[s] = req
            out.append((s, req))
        return out

    # -- retirement / preemption ----------------------------------------
    def retire(self, slot: int) -> Request:
        """Free ``slot`` and return the request that occupied it (the
        engine harvests its outputs before the slot is reused)."""
        req = self.slot_req[slot]
        if req is None:
            raise SchedulerError("retiring free slot", slot=slot)
        self.slot_req[slot] = None
        return req

    def requeue(self, slot: int) -> Request:
        """Preemption / failed admission: free ``slot`` and put its
        request back in the queue (bypassing the bound — the request was
        already accepted; shedding it now would break the admission
        contract). Claim-ordered selection makes the queue position
        irrelevant; appendleft just keeps ``len(queue)`` honest for
        backpressure accounting."""
        req = self.slot_req[slot]
        if req is None:
            raise SchedulerError("requeueing free slot", slot=slot)
        self.slot_req[slot] = None
        self.queue.appendleft(req)
        return req

    def preempt_victims(
        self,
        beneficiary: Request,
        emitted: Mapping[int, int],
        exclude: Sequence[int] = (),
    ) -> List[int]:
        """Active slots the ``beneficiary`` may reclaim pages from, best
        victim first. Eligible victims hold a strictly weaker claim
        (lower priority, or same priority but later arrival) — so the
        strongest claim in the system can preempt every other slot and
        is itself unpreemptable, which is what makes overload *degrade*
        (oldest request always completes) instead of livelock. Among
        eligible victims the order is fewest-tokens-emitted first,
        newest arrival as tie-break: evict the work that is cheapest to
        recompute."""
        ex = set(exclude)
        cands = [
            s
            for s, r in enumerate(self.slot_req)
            if r is not None and s not in ex and beneficiary.claim < r.claim
        ]
        cands.sort(
            key=lambda s: (
                emitted.get(s, 0),
                -(self.slot_req[s].arrival or 0),
            )
        )
        return cands

    def idle(self) -> bool:
        """True when nothing is queued and no slot is occupied — the
        engine's serving-loop exit condition."""
        return not self.queue and all(r is None for r in self.slot_req)
