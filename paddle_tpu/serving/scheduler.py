"""Slot-admission scheduling for continuous batching.

Iteration-level batching (Orca — Yu et al., OSDI 2022; PAPERS.md): the
decode batch is a table of SLOTS, each owning one row of the fused
loop's carry (``inference/generate.DecodeState``). Between chunk
dispatches, rows whose request finished are released and the admission
policy refills them from the queue — one length-bucketed prefill
dispatch per admitted request — so the chip never idles on dead rows
while the single-program decode property (Pope et al., 2211.05102)
stays intact: the batch still runs as ONE device program per chunk.

This module is pure host-side bookkeeping: the request queue (FIFO or
priority), the slot table, and prompt length bucketing. The device-side
state assembly lives in ``serving/engine.py``.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import time
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Request", "Slot", "SlotTable", "Scheduler", "bucket_length"]


def bucket_length(n: int, buckets: Optional[Sequence[int]] = None) -> int:
    """Smallest admission-prefill bucket that fits an ``n``-token prompt:
    the next power of two (floor 8) by default, or the smallest entry of
    an explicit bucket list — ONE compiled prefill program per bucket
    instead of one per distinct prompt length, bounding recompiles under
    arbitrary traffic."""
    if n < 1:
        raise ValueError(f"prompt must have at least 1 token, got {n}")
    if buckets:
        fits = [int(b) for b in buckets if int(b) >= n]
        if not fits:
            raise ValueError(
                f"prompt length {n} exceeds the largest prefill bucket "
                f"{max(int(b) for b in buckets)}")
        return min(fits)
    b = 8
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class Request:
    """One queued generate ask. ``eos_token_id`` is already normalized
    (None = decode to the full budget); ``seed`` keys the row's private
    RNG stream; ``priority`` orders admission under the 'priority'
    policy (lower = sooner), ties broken FIFO. ``submit_time`` is a
    ``time.monotonic()`` stamp — the clock every downstream latency
    subtraction uses (the same discipline ``distributed/elastic.py``
    moved to: wall clocks step under NTP and turn latency math into
    noise); ``Scheduler.push`` stamps it when the caller didn't."""
    id: int
    prompt: np.ndarray            # (S,) token ids
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    temperature: float = 1.0
    seed: int = 0
    priority: int = 0
    submit_time: float = 0.0      # time.monotonic(); 0.0 = unset
    # SLO bucket + targets (None = engine-default for the class, or no
    # target): per-class TTFT / latency violation counters in the
    # engine registry are the groundwork for SLO-aware scheduling
    latency_class: str = "default"
    slo_ttft_s: Optional[float] = None
    slo_latency_s: Optional[float] = None
    # hard deadline (distinct from the SLO targets above, which only
    # count violations): ``deadline_s`` is the budget in seconds from
    # submit, ``deadline_at`` the absolute ``time.monotonic()`` expiry
    # (stamped by ``Scheduler.push`` when unset). The engine enforces it
    # at submit (typed shed), at admission (expired-in-queue shed), at
    # requeue (no zombie retries) and between chunks (the row is frozen
    # like EOS and the partial result flagged ``deadline_expired``).
    deadline_s: Optional[float] = None
    deadline_at: Optional[float] = None
    # prefix-cache grouping key (the prompt's first block-boundary
    # content digest, stamped by the engine when the cache is on):
    # same-priority requests sharing it are admitted together by the
    # cache-aware ordering so their admissions reuse one slab
    prefix_group: Optional[str] = None
    # request-keyed RNG stream inputs (engine ``request_keyed_rng``):
    # the STABLE id the row key folds in (a router's request id survives
    # requeues; None = this engine's own id) and how many generated
    # tokens the prompt already replays — the admission key advances
    # that many steps so a sampled replay resumes the identical stream
    rng_request_id: Optional[int] = None
    rng_tokens_emitted: int = 0
    # multi-tenant LoRA routing (serving/lora): the adapter NAME this
    # request decodes through (None = base model). The engine resolves
    # it to a row index into the stacked delta arrays at admission and
    # pins the resolved (name, revision) so a hot-swap mid-flight is a
    # typed refusal, never a silent tenant mix
    adapter: Optional[str] = None
    # per-request speculative toggle (speculative engines only): False
    # demotes THIS row to plain verify-free decode inside the same
    # speculative chunk program; None = engine default (on)
    speculative: Optional[bool] = None


@dataclasses.dataclass
class Slot:
    """One occupied batch row: the request it serves plus the host-side
    reassembly buffer (per-chunk token pieces) and the per-request
    observability record (queue delay, chunks spanned, resilience events
    that fired while it was in flight)."""
    request: Request
    admitted_at: float = 0.0
    chunks: int = 0
    tokens: List[np.ndarray] = dataclasses.field(default_factory=list)
    events: List[Any] = dataclasses.field(default_factory=list)
    # stamped after the first chunk dispatch the slot rode: the
    # admission->first-token interval is the TTFT instrument's sample
    first_token_at: Optional[float] = None
    # the row's cache position at the start of its next chunk: prompt
    # length + tokens delivered so far, advanced at each harvest (the
    # live-KV counter sums it over the occupied rows per chunk dispatch)
    kv_pos: int = 0
    # prefix-cache admission record (serving/prefix_cache.py): the hit
    # class this admission resolved to (None = cache disabled), the
    # prompt tokens whose prefill it skipped, how many prefill
    # dispatches it issued (0 = full hit, or rode a batched group's
    # dispatch), and the slab pinned for the request's flight — the
    # engine unpins it at release, making the slab evictable again
    prefix_hit: Optional[str] = None
    prefill_tokens_saved: int = 0
    admission_dispatches: int = 0
    pinned_slab: Any = None
    # speculative serving record (engine ``draft_model=``): the row's
    # CUMULATIVE verify rounds / accepted drafts mirrored off the carry
    # after each chunk (the carry's per-row counters reset at admission,
    # so these are exact per-request totals across chunk re-entries),
    # plus the overflow tokens its chunks committed past the chunk
    # boundary (the ``nv``-contract tail the harvest kept)
    spec_rounds: int = 0
    spec_accepted: int = 0
    spec_overflow: int = 0
    # streaming flush cursor (serving/http): how many of this request's
    # reassembled tokens have already been pushed to its stream callback
    # — chunk-boundary harvests emit ``seq[streamed:]`` and advance it
    streamed: int = 0
    # the adapter revision pinned at admission (None = base): hot-swap
    # of THIS adapter while the row is in flight raises the typed
    # AdapterVersionError instead of silently switching tenants mid-seq
    adapter_rev: Optional[int] = None

    def __post_init__(self):
        if not self.kv_pos:
            self.kv_pos = len(self.request.prompt) + sum(
                len(t) for t in self.tokens)


class SlotTable:
    """Which batch row belongs to which in-flight request."""

    def __init__(self, num_slots: int):
        if num_slots < 1:
            raise ValueError(f"need at least 1 slot, got {num_slots}")
        self.entries: List[Optional[Slot]] = [None] * num_slots

    def __len__(self) -> int:
        return len(self.entries)

    def free_slots(self) -> List[int]:
        return [i for i, e in enumerate(self.entries) if e is None]

    def occupied(self) -> List[Tuple[int, Slot]]:
        return [(i, e) for i, e in enumerate(self.entries) if e is not None]

    def occupancy(self) -> float:
        return len(self.occupied()) / len(self.entries)

    def occupy(self, request: Request) -> int:
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slot to occupy")
        i = free[0]
        self.entries[i] = Slot(request=request)
        return i

    def release(self, i: int) -> None:
        if self.entries[i] is None:
            raise RuntimeError(f"slot {i} is already free")
        self.entries[i] = None


class Scheduler:
    """Admission queue + slot table.

    ``policy='fifo'`` admits strictly in submit order; ``'priority'``
    admits by ``Request.priority`` (lower first, FIFO within a class).
    ``admissions()`` implements the between-chunk policy: pop one queued
    request per free slot and occupy it — the engine then prefills each
    admitted request and scatters its row into the decode carry."""

    def __init__(self, num_slots: int, policy: str = "fifo",
                 prompt_buckets: Optional[Sequence[int]] = None,
                 dp_size: int = 1, cache_aware: bool = False):
        if policy not in ("fifo", "priority"):
            raise ValueError(f"policy must be 'fifo' or 'priority', "
                             f"got {policy!r}")
        if dp_size < 1 or num_slots % dp_size:
            raise ValueError(
                f"dp_size {dp_size} must divide num_slots {num_slots} "
                f"(each data-parallel replica owns an equal contiguous "
                f"block of batch rows)")
        self.policy = policy
        self.dp_size = int(dp_size)
        self.prompt_buckets = (sorted(int(b) for b in prompt_buckets)
                               if prompt_buckets else None)
        self.slots = SlotTable(num_slots)
        self._heap: list = []
        self._seq = itertools.count()
        # cache-aware admission ordering (prefix-cache follow-on):
        # among SAME-priority queued requests, admit in an order that
        # maximizes prefix-slab reuse — requests whose digest is already
        # live in the cache (``cache_probe``) lead, and same-digest
        # requests admit together. FIFO is preserved WITHIN a digest
        # group (and across priorities); ``cache_reordered`` counts
        # requests that jumped ahead of an earlier-submitted peer.
        self.cache_aware = bool(cache_aware)
        self.cache_probe = None      # Optional[Callable[[str], bool]]
        self.cache_reordered = 0

    def __len__(self) -> int:
        return len(self._heap)

    def bucket(self, prompt_len: int) -> int:
        return bucket_length(prompt_len, self.prompt_buckets)

    def push(self, request: Request) -> None:
        if not request.submit_time:
            # stamp here, on the monotonic clock, so queue-delay math is
            # sane even for requests built without going through
            # ServingEngine.submit (a 0.0 default subtracted from a
            # monotonic 'now' reported hours of queue delay)
            request.submit_time = time.monotonic()
        if request.deadline_s is not None and request.deadline_at is None:
            request.deadline_at = request.submit_time + request.deadline_s
        pr = request.priority if self.policy == "priority" else 0
        heapq.heappush(self._heap, (pr, next(self._seq), request))

    def push_front(self, request: Request) -> None:
        """Re-queue AHEAD of every same-priority peer — the admission
        backpressure un-admit (engine ring full): the request keeps its
        original ``submit_time`` (queue-delay accounting stays honest)
        and retakes its tier's head via a negative sequence number. Call
        in reverse admission order when re-queuing several, so the
        earliest-admitted lands frontmost."""
        pr = request.priority if self.policy == "priority" else 0
        heapq.heappush(self._heap, (pr, -next(self._seq), request))

    def shed_expired(self, now: float) -> List[Request]:
        """Drop queued requests whose deadline already passed — checked
        every admission round BEFORE slot occupancy, so an expired
        request never wastes a prefill dispatch. Surviving entries keep
        their original sequence numbers (cross-round order stable)."""
        if not self._heap:
            return []
        keep, out = [], []
        for e in self._heap:
            req = e[2]
            if req.deadline_at is not None and now > req.deadline_at:
                out.append(req)
            else:
                keep.append(e)
        if out:
            self._heap = keep
            heapq.heapify(self._heap)
        return out

    def queued(self) -> List[Request]:
        """Non-destructive view of the queue in admission order (the
        snapshot serializer reads it; (priority, seq) keys are unique so
        the sort never compares Requests)."""
        return [e[2] for e in sorted(self._heap,
                                     key=lambda e: (e[0], e[1]))]

    def take_all(self) -> List[Request]:
        """Pop EVERY queued request in admission order (the requeue
        export of a dead replica's queue — the router re-submits them to
        survivors)."""
        out = []
        while self._heap:
            out.append(heapq.heappop(self._heap)[2])
        return out

    def remove(self, ids) -> List[Request]:
        """Pop the queued requests whose ``id`` is in ``ids`` (admission
        order), leaving every other entry in place with its original
        sequence number — the migration export of a SUBSET of a live
        worker's queue (``take_all`` is the everything-must-go case)."""
        want = {int(i) for i in ids}
        keep, out = [], []
        for e in self._heap:
            (out if e[2].id in want else keep).append(e)
        if out:
            self._heap = keep
            heapq.heapify(self._heap)
        return [e[2] for e in sorted(out, key=lambda e: (e[0], e[1]))]

    def admissions(self) -> List[Tuple[int, Request]]:
        """Fill every free slot from the queue; returns the
        ``(slot_index, request)`` pairs admitted this round. With
        ``cache_aware`` the pop order within a priority tier bends
        toward prefix-slab reuse (:meth:`_cache_aware_pops`); plain
        FIFO/priority order otherwise."""
        free_n = len(self.slots.free_slots())
        if not free_n or not self._heap:
            return []
        if self.cache_aware:
            picked = self._cache_aware_pops(free_n)
        else:
            picked = [heapq.heappop(self._heap)[2]
                      for _ in range(min(free_n, len(self._heap)))]
        return [(self.slots.occupy(req), req) for req in picked]

    def _cache_aware_pops(self, free_n: int) -> List[Request]:
        """Choose up to ``free_n`` queued requests, reordering ONLY
        within a priority tier: the tier's head is the earliest request
        whose ``prefix_group`` digest is already live in the cache
        (``cache_probe``) — a guaranteed slab hit — else the FIFO head;
        then same-group followers are pulled forward (FIFO within the
        group) so one slab serves the whole burst. Requests left over
        go back on the heap with their original sequence numbers, so
        nothing is starved and cross-round order stays stable."""
        entries = []
        while self._heap:
            entries.append(heapq.heappop(self._heap))
        chosen: List[Request] = []
        while len(chosen) < free_n and entries:
            p0 = entries[0][0]
            tier_end = next((i for i, e in enumerate(entries)
                             if e[0] != p0), len(entries))
            head_i = 0
            if self.cache_probe is not None:
                for j in range(tier_end):
                    g = entries[j][2].prefix_group
                    if g is not None and self.cache_probe(g):
                        head_i = j
                        break
            if head_i > 0:
                self.cache_reordered += 1
            head = entries.pop(head_i)
            chosen.append(head[2])
            tier_end -= 1
            g = head[2].prefix_group
            if g is not None:
                i = 0
                while i < tier_end and len(chosen) < free_n:
                    if entries[i][2].prefix_group == g:
                        if i > 0:
                            self.cache_reordered += 1
                        chosen.append(entries.pop(i)[2])
                        tier_end -= 1
                    else:
                        i += 1
        for e in entries:
            heapq.heappush(self._heap, e)
        return chosen

    def dp_groups(self) -> List[dict]:
        """How the slot table maps onto the mesh's ``dp`` axis: jax
        shards the batch dim into contiguous equal blocks, so replica i
        of ``dp_size`` owns slots [i*B/dp, (i+1)*B/dp) — each group is
        one data-parallel engine replica's rows. Per-group occupancy is
        the load-balance signal dp-aware admission will read (a replica
        whose block is all free idles its devices through every chunk)."""
        per = len(self.slots) // self.dp_size
        groups = []
        for i in range(self.dp_size):
            idx = list(range(i * per, (i + 1) * per))
            occ = sum(1 for j in idx if self.slots.entries[j] is not None)
            groups.append({"dp": i, "slots": idx, "occupied": occ})
        return groups
