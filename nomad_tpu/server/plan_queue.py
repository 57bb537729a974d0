"""PlanQueue: leader-side priority queue of pending plans (reference:
nomad/plan_queue.go).

Each enqueued plan carries a future the scheduling worker blocks on; the plan
applier dequeues in priority order and resolves the futures.
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

from nomad_tpu.analysis import guarded_by
from nomad_tpu.structs import Plan, PlanResult
from nomad_tpu.telemetry import metrics


class PendingPlan:
    """A plan + its response future (reference: plan_queue.go:52-93)."""

    def __init__(self, plan: Plan):
        self.plan = plan
        # Made inside enqueue/enqueue_all: the applier samples
        # nomad.plan.queue_wait from this when it takes the plan up.
        self.enqueued = time.monotonic()
        # Made by respond, just before it sets the event: a waiter that
        # was blocked samples nomad.plan.wake from it when it runs again.
        self.responded = 0.0
        self._event = threading.Event()
        self._result: Optional[PlanResult] = None
        self._error: Optional[Exception] = None
        self.cancelled = False

    def wait(self, timeout: Optional[float] = None) -> PlanResult:
        if not self._event.is_set():
            # Blocked: from the applier's set() to this thread holding the
            # interpreter again is the hand-over the waiter pays on top of
            # the apply. A waiter that comes late finds the event set and
            # measures nothing.
            if not self._event.wait(timeout):
                raise TimeoutError("plan response timeout")
            metrics.measure_since(("nomad", "plan", "wake"), self.responded)
        if self._error is not None:
            raise self._error
        return self._result

    def respond(self, result: Optional[PlanResult],
                error: Optional[Exception]) -> None:
        self._result = result
        self._error = error
        self.responded = time.monotonic()
        self._event.set()

    def cancel(self) -> None:
        """Mark a still-queued plan abandoned (a chunked submit whose
        earlier chunk failed): the applier skips it at dequeue instead of
        committing work nobody is waiting on. Best-effort — a plan the
        applier already picked up still lands."""
        self.cancelled = True


class PlanQueue:
    _concurrency = guarded_by("_lock", "_enabled", "_heap", "stats")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._enabled = False
        self._heap: List[Tuple[int, int, PendingPlan]] = []
        self._seq = itertools.count()
        self.stats = {"Depth": 0}

    def enabled(self) -> bool:
        with self._lock:
            return self._enabled

    def set_enabled(self, enabled: bool) -> None:
        with self._lock:
            self._enabled = enabled
        if not enabled:
            self.flush()

    def enqueue(self, plan: Plan) -> PendingPlan:
        """(reference: plan_queue.go:95-124)"""
        with self._lock:
            if not self._enabled:
                raise RuntimeError("plan queue is disabled")
            pending = PendingPlan(plan)
            heapq.heappush(self._heap,
                           (-plan.Priority, next(self._seq), pending))
            self.stats["Depth"] += 1
            self._cond.notify_all()
            return pending

    def enqueue_all(self, plans: List[Plan]) -> List[PendingPlan]:
        """Enqueue a window's plans under ONE lock hold / ONE wakeup.
        A pipelined worker submits its window back-to-back; per-plan lock
        rounds convoy with a second submitting worker and interleave the
        two windows' plans arbitrarily. One critical section keeps each
        window contiguous in arrival order (same-priority plans pop FIFO),
        which is the order the chain dispatched them in."""
        with self._lock:
            if not self._enabled:
                raise RuntimeError("plan queue is disabled")
            out: List[PendingPlan] = []
            for plan in plans:
                pending = PendingPlan(plan)
                heapq.heappush(self._heap,
                               (-plan.Priority, next(self._seq), pending))
                out.append(pending)
            self.stats["Depth"] += len(out)
            self._cond.notify_all()
            return out

    def dequeue_ready(self, max_count: int) -> List[PendingPlan]:
        """Pop up to max_count queued plans under ONE lock hold, without
        waiting (the applier's group drain: per-plan dequeue rounds on
        the serialization point convoy with concurrently submitting
        workers)."""
        out: List[PendingPlan] = []
        with self._lock:
            if not self._enabled:
                raise RuntimeError("plan queue is disabled")
            while self._heap and len(out) < max_count:
                _, _, pending = heapq.heappop(self._heap)
                out.append(pending)
            self.stats["Depth"] -= len(out)
        return out

    def dequeue(self, timeout: Optional[float] = None) -> Optional[PendingPlan]:
        """(reference: plan_queue.go:126-152)"""
        end = None if not timeout else time.monotonic() + timeout
        with self._lock:
            while True:
                if not self._enabled:
                    raise RuntimeError("plan queue is disabled")
                if self._heap:
                    _, _, pending = heapq.heappop(self._heap)
                    self.stats["Depth"] -= 1
                    return pending
                if end is None:
                    self._cond.wait(timeout=0.2)
                else:
                    remaining = end - time.monotonic()
                    if remaining <= 0 or not self._cond.wait(remaining):
                        return None

    def flush(self) -> None:
        with self._lock:
            for _, _, pending in self._heap:
                pending.respond(None, RuntimeError("plan queue flushed"))
            self._heap = []
            self.stats["Depth"] = 0
            self._cond.notify_all()
