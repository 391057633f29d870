"""Admission control for the serving session (DESIGN.md §13, §14): a
bounded queue with per-request deadlines, explicit load-shedding, and
adaptive micro-batch sizing.

The extractor itself is a pure batch function; what makes a service
survivable under overload is the layer in front of it deciding which
requests to run AT ALL:

  * **bounded queue** — ``submit`` on a full queue raises `QueueFull`
    immediately (the caller's 503/retry-after), instead of buffering
    unbounded work the session can never catch up on;
  * **per-request deadlines** — every admitted request carries an
    absolute deadline; ``drain`` discards requests that expired while
    queued (their caller has already timed out — extracting them would
    spend device time producing an answer nobody reads) and batches the
    live ones through `IVectorExtractor.extract`;
  * **first-response priority** — streaming traffic (DESIGN.md §14)
    has two request kinds: a ``first`` chunk (a user is waiting for
    their first i-vector) and a ``refine`` chunk (an existing session
    getting a better estimate). A full queue sheds the *refinement*
    with the slackest deadline to admit a first-response — dropping a
    refinement costs estimate freshness, dropping a first-response
    costs a user-visible failure;
  * **adaptive micro-batching** — ``batch_budget`` grows the per-drain
    batch with queue depth (power-of-two steps up to the extractor's
    ``max_batch``): near-idle traffic gets minimum-latency singleton
    batches, a burst amortizes fixed per-call cost over bigger ones;
  * **observability** — every shed request is counted by cause
    (``shed_full`` / ``shed_deadline`` / ``shed_refine``) and the
    whole control surface (depth, budget, shed counters, rescore mode)
    surfaces through ``health`` — the readiness-probe payload.

The queue is synchronous and single-threaded by design: it is the
admission policy a real server loop pumps (one ``drain`` per batching
tick), packaged so the chaos drills can exercise overload and deadline
behaviour deterministically via an injectable clock.

Session routing: a request submitted with a ``sid`` and a store
attached is a streaming chunk — ``drain`` routes it through
``SessionStore.update`` (accumulate + incremental solve) instead of the
stateless batch extractor, so one queue fronts both traffic shapes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.serving.extractor import IVectorExtractor, RequestInfo


class QueueFull(RuntimeError):
    """The admission queue is at capacity; the request was load-shed
    before any work happened (the caller should back off and retry)."""


@dataclass
class _Pending:
    id: int
    utterance: np.ndarray
    deadline: float          # absolute, in the queue's clock
    submitted: float
    kind: str = "first"      # "first" | "refine" (shedding priority)
    sid: Optional[str] = None   # streaming session id (store routing)


@dataclass
class RequestResult:
    """Outcome of one admitted request after a ``drain``."""
    id: int
    ivector: Optional[np.ndarray]   # None when expired/preempted
    expired: bool
    wait_s: float                   # from submit to the result
    info: Optional[object] = None   # RequestInfo | session ChunkInfo
    kind: str = "first"
    sid: Optional[str] = None
    preempted: bool = False         # shed to admit a first-response


@dataclass
class AdmissionQueue:
    """Bounded deadline-aware work queue in front of one extractor
    (and, optionally, one streaming `SessionStore`)."""
    extractor: IVectorExtractor
    max_pending: int = 64
    default_timeout: float = 30.0
    clock: Callable[[], float] = time.monotonic
    min_batch: int = 1              # adaptive batch floor (near-idle)
    store: Optional[object] = None  # serving.session.SessionStore
    _pending: List[_Pending] = field(default_factory=list)
    _preempted: List[_Pending] = field(default_factory=list)
    _next_id: int = 0
    stats: Dict[str, int] = field(default_factory=lambda: {
        "submitted": 0, "shed_full": 0, "shed_deadline": 0,
        "shed_refine": 0, "served": 0})

    def __len__(self) -> int:
        return len(self._pending)

    def submit(self, utterance, timeout: Optional[float] = None,
               kind: str = "first", sid: Optional[str] = None) -> int:
        """Admit one request; returns its id or raises `QueueFull`.

        On a full queue a ``first`` request preempts the queued
        ``refine`` with the slackest (latest) deadline — that session
        keeps its last emitted i-vector, the new user gets their first.
        A ``refine`` on a full queue is shed outright."""
        if kind not in ("first", "refine"):
            raise ValueError(f"kind must be 'first'|'refine': {kind!r}")
        if len(self._pending) >= self.max_pending:
            victim = None
            if kind == "first":
                refines = [p for p in self._pending if p.kind == "refine"]
                if refines:
                    victim = max(refines, key=lambda p: p.deadline)
            if victim is None:
                self.stats["shed_full"] += 1
                raise QueueFull(
                    f"admission queue at capacity ({self.max_pending})")
            self._pending.remove(victim)
            self._preempted.append(victim)
            self.stats["shed_refine"] += 1
        now = self.clock()
        rid = self._next_id
        self._next_id += 1
        self._pending.append(_Pending(
            id=rid, utterance=np.asarray(utterance, np.float32),
            deadline=now + (self.default_timeout if timeout is None
                            else timeout),
            submitted=now, kind=kind, sid=sid))
        self.stats["submitted"] += 1
        return rid

    def batch_budget(self) -> int:
        """How many requests the next ``drain`` should serve: grows in
        power-of-two steps with queue depth, from ``min_batch`` (an idle
        queue wants minimum latency, not batching) up to the extractor's
        ``max_batch`` (past which a bigger batch is just a longer
        queue-in-disguise)."""
        depth = len(self._pending)
        cap = self.extractor.serving.max_batch
        b = max(1, self.min_batch)
        while b < depth and b < cap:
            b *= 2
        return min(b, cap)

    def drain(self, budget: Optional[int] = None
              ) -> Dict[int, RequestResult]:
        """Serve up to ``budget`` admissible requests (None = all, the
        batch-serving behaviour; pass ``batch_budget()`` for the
        adaptive streaming loop). Expired requests are shed with no
        device work; preempted refinements surface as shed results.
        Under a budget, first-response chunks are served before
        refinements and earlier deadlines first — the leftovers stay
        queued for the next tick (and shed there if their deadline
        passes: deadline-aware backpressure, not silent drops)."""
        now = self.clock()
        results: Dict[int, RequestResult] = {}
        for p in self._preempted:
            results[p.id] = RequestResult(
                id=p.id, ivector=None, expired=True,
                wait_s=now - p.submitted, kind=p.kind, sid=p.sid,
                preempted=True)
        self._preempted = []
        live: List[_Pending] = []
        for p in self._pending:
            if now > p.deadline:
                self.stats["shed_deadline"] += 1
                results[p.id] = RequestResult(
                    id=p.id, ivector=None, expired=True,
                    wait_s=now - p.submitted, kind=p.kind, sid=p.sid)
            else:
                live.append(p)
        if budget is None:
            serve, self._pending = live, []
        else:
            ranked = sorted(
                live, key=lambda p: (p.kind != "first", p.deadline))
            serve = ranked[:max(0, int(budget))]
            keep = {p.id for p in ranked[max(0, int(budget)):]}
            self._pending = [p for p in live if p.id in keep]
        session = [p for p in serve
                   if p.sid is not None and self.store is not None]
        session_ids = {p.id for p in session}
        batch = [p for p in serve if p.id not in session_ids]
        for p in session:
            iv, cinfo = self.store.update(p.sid, p.utterance)
            results[p.id] = RequestResult(
                id=p.id, ivector=iv, expired=False,
                wait_s=self.clock() - p.submitted, info=cinfo,
                kind=p.kind, sid=p.sid)
            self.stats["served"] += 1
        if batch:
            ivecs, infos = self.extractor.extract(
                [p.utterance for p in batch], return_info=True)
            done = self.clock()
            for p, iv, info in zip(batch, ivecs, infos):
                results[p.id] = RequestResult(
                    id=p.id, ivector=iv, expired=False,
                    wait_s=done - p.submitted, info=info, kind=p.kind)
            self.stats["served"] += len(batch)
        return results

    # -- readiness probe ----------------------------------------------------

    def health(self) -> Dict:
        """The full readiness-probe payload: the extractor's canary
        `health_check` plus the admission-control surface (queue depth,
        adaptive batch budget, shed counters, current rescore mode) and
        the session store's state when one is attached. This is what
        PR 8 left dark: the counters existed but never surfaced."""
        probe = self.extractor.health_check()
        payload = {
            "ok": probe["ok"], "mode": self.extractor.mode,
            "queue": {"depth": len(self._pending),
                      "max_pending": self.max_pending,
                      "batch_budget": self.batch_budget(),
                      "preempted_unreported": len(self._preempted),
                      **dict(self.stats)},
            "extractor": probe,
        }
        if self.store is not None:
            payload["sessions"] = self.store.health()
        return payload
