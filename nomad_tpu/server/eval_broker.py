"""EvalBroker: leader-side priority queue of evaluations with at-least-once
delivery (reference: nomad/eval_broker.go).

Semantics mirrored: per-scheduler-type priority queues; per-JobID
serialization (one in-flight eval per job, rest held "blocked"); Ack/Nack
with nack-timeout redelivery; delivery-limit overflow into the `_failed`
queue; wait-time deferral; token-gated requeue (a scheduler reblocking its
own eval defers until the outstanding one is Ack'd/Nack'd).

QoS extension (beyond the reference — see README "QoS & SLO serving"):
with a ``QoSConfig``, each ready queue splits into priority TIERS. High
tier drains first; a lower tier's head is promoted one effective tier per
``aging_s`` seconds queued, so saturating high-tier load can delay but
never permanently starve it. The broker also remembers each eval's FIRST
enqueue time across Nack redeliveries and blocked-eval requeues (a
requeued eval must not reset behind fresh arrivals), and converts
(first-enqueue -> ack) wait against the tier deadline into the per-tier
SLO-burn signal admission control sheds on. QoS disabled (the default)
keeps the single-heap path bit-identical to the reference behavior.
"""

from __future__ import annotations

import heapq
import itertools
import random
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

from nomad_tpu.analysis import guarded_by, requires_lock
from nomad_tpu.qos.tiers import N_TIERS, TIER_NAMES, QoSConfig, qos_enabled
from nomad_tpu.structs import Evaluation, generate_uuid
from nomad_tpu.telemetry import metrics, trace
from nomad_tpu.timerwheel import TimerHandle, wheel

FAILED_QUEUE = "_failed"

# Bound on the federation foreign-region park (see _enqueue_locked): a
# safety-net diagnostic for misdirected writes, evicted oldest-first.
FOREIGN_PARK_CAP = 4096


class NotOutstandingError(Exception):
    pass


class TokenMismatchError(Exception):
    pass


class _PriorityQueue:
    """Max-priority heap of evaluations, FIFO within a priority.

    With an enabled QoS config the queue becomes TIERED: one heap per QoS
    tier, served high-first with aging-based promotion (the head of a
    lower tier gains one effective tier per ``aging_s`` waited; effective
    ties go to the longer-waiting head, so progress is guaranteed even
    under a saturating high-tier storm). Without one — the default — the
    single-heap branch is byte-identical to the pre-QoS ordering."""

    _seq = itertools.count()

    def __init__(self, qos: Optional[QoSConfig] = None) -> None:
        self._heap: List[Tuple[int, int, int, Evaluation]] = []
        self._qos = qos if qos_enabled(qos) else None
        self._tiers: Optional[List[list]] = (
            [[] for _ in range(N_TIERS)] if self._qos is not None else None)
        self.promoted = 0  # pops served from an aged-up tier

    def push(self, ev: Evaluation, enq_time: float = 0.0) -> None:
        if self._qos is None:
            heapq.heappush(
                self._heap,
                (-ev.Priority, ev.CreateIndex, next(self._seq), ev))
            return
        tier = self._qos.tier_of(ev.Priority)
        # enq_time rides the entry (never compared: seq is unique) so the
        # aging check reads the head's ORIGINAL enqueue time — preserved
        # across Nack/blocked requeues by the broker's age map.
        heapq.heappush(
            self._tiers[tier],
            (-ev.Priority, ev.CreateIndex, next(self._seq), ev, enq_time))

    def _best_tier(self, now: float) -> Optional[Tuple[int, tuple]]:
        """(tier, sort key) of the entry pop would serve: minimize
        (effective tier, head enqueue time). Aging promotes a head one
        tier per aging_s waited; equal effective tiers go to the OLDER
        head — the anti-starvation guarantee."""
        best = None
        for tier in range(N_TIERS):
            heap = self._tiers[tier]
            if not heap:
                continue
            enq = heap[0][4] or now
            eff = tier
            if self._qos.aging_s > 0:
                eff = max(0, tier - int((now - enq) / self._qos.aging_s))
            key = (eff, enq)
            if best is None or key < best[1]:
                best = (tier, key)
        return best

    def pop(self, now: Optional[float] = None) -> Optional[Evaluation]:
        if self._qos is None:
            if not self._heap:
                return None
            return heapq.heappop(self._heap)[3]
        best = self._best_tier(now if now is not None else time.monotonic())
        if best is None:
            return None
        tier, (eff, _) = best
        if eff < tier:
            self.promoted += 1
        return heapq.heappop(self._tiers[tier])[3]

    def peek(self, now: Optional[float] = None) -> Optional[Evaluation]:
        if self._qos is None:
            if not self._heap:
                return None
            return self._heap[0][3]
        best = self._best_tier(now if now is not None else time.monotonic())
        if best is None:
            return None
        return self._tiers[best[0]][0][3]

    def peek_key(self, now: float) -> Optional[tuple]:
        """Cross-scheduler comparison key for _scan: lower sorts first."""
        if self._qos is None:
            head = self.peek()
            return None if head is None else (-head.Priority,)
        best = self._best_tier(now)
        if best is None:
            return None
        tier, key = best
        return key + (-self._tiers[tier][0][3].Priority,)

    def tier_depths(self) -> List[int]:
        if self._tiers is None:
            return [len(self._heap), 0, 0]
        return [len(h) for h in self._tiers]

    def __len__(self) -> int:
        if self._qos is None:
            return len(self._heap)
        return sum(len(h) for h in self._tiers)


@dataclass
class _Unack:
    eval: Evaluation
    token: str
    nack_timer: TimerHandle


@dataclass
class BrokerStats:
    TotalReady: int = 0
    TotalUnacked: int = 0
    TotalBlocked: int = 0
    TotalWaiting: int = 0
    ByScheduler: Dict[str, Dict[str, int]] = field(default_factory=dict)


class EvalBroker:
    _concurrency = guarded_by(
        "_lock", "_enabled", "_evals", "_job_evals", "_blocked", "_ready",
        "_unack", "_requeue", "_time_wait", "stats", "_ages",
        "_age_slack", "_slo", "_floors", "_foreign", "_region",
        "_index_source", "_ready_at")

    def __init__(self, nack_timeout: float = 60.0, delivery_limit: int = 3,
                 qos: Optional[QoSConfig] = None):
        if nack_timeout < 0:
            raise ValueError("timeout cannot be negative")
        self.nack_timeout = nack_timeout
        self.delivery_limit = delivery_limit
        self.qos = qos
        self._enabled = False
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)

        self._evals: Dict[str, int] = {}          # eval id -> delivery count
        self._job_evals: Dict[str, str] = {}      # job id -> in-flight eval id
        self._blocked: Dict[str, _PriorityQueue] = {}  # job id -> waiting
        self._ready: Dict[str, _PriorityQueue] = {}    # scheduler -> ready
        self._unack: Dict[str, _Unack] = {}
        self._requeue: Dict[str, Evaluation] = {}  # token -> eval
        self._time_wait: Dict[str, TimerHandle] = {}
        # Queue-age memory: eval id -> FIRST enqueue (monotonic). Kept
        # across Nack redeliveries and seeded by blocked-eval requeues
        # (enqueue_all ages=), dropped at Ack/flush — so an aged eval is
        # never reset behind fresh arrivals, and ack-time wait vs the tier
        # deadline feeds the SLO-burn rings below.
        self._ages: Dict[str, float] = {}
        # eval id -> when it entered a ready queue THIS time (monotonic):
        # the one source of the broker's queue wait, sampled at dequeue as
        # nomad.broker.wait and synthesized into the trace's broker.wait.
        self._ready_at: Dict[str, float] = {}
        # Warm-failover witness slack per eval: the first-enqueue seed a
        # new leader derives from the replicated timetable errs OLDER by
        # up to one witness interval (good for ordering — the eval keeps
        # its place — but it must not count as deadline burn the eval
        # may never have suffered). ack subtracts it from the SLO-burn
        # wait, turning the burn sample into a LOWER bound of true wait.
        self._age_slack: Dict[str, float] = {}
        # Per-tier ring of recent completions: True = blew its deadline.
        self._slo: List[Deque[bool]] = [
            deque(maxlen=(qos.burn_window if qos_enabled(qos) else 1))
            for _ in range(N_TIERS)]
        # Federation (set_federation; both None/"" when federation is
        # off, leaving every path below bit-identical to pre-federation
        # behavior):
        # - _floors: eval id -> store index at the moment the eval
        #   became READY (its release point). A follower-snapshot worker
        #   only needs its replica caught up to THIS, not to the
        #   leader's global latest index: per-job serialization means no
        #   plan for the eval's job can commit after its release, so a
        #   snapshot at the floor can never double-place — the Omega
        #   soundness bound that lets a shared snapshot serve a whole
        #   storm burst.
        # - _foreign: evals whose Region differs from the local one,
        #   parked instead of served — a region must never dequeue work
        #   it has no nodes for (ingress forwarding makes these orphans
        #   by construction; parking + the counter is the safety net).
        self._index_source = None
        self._region = ""
        self._floors: Dict[str, int] = {}
        self._foreign: Dict[str, Evaluation] = {}
        self.stats = BrokerStats()

    def set_federation(self, region: str, index_source) -> None:
        """Arm federation routing: evals release-stamp a snapshot floor
        from ``index_source`` (the local store's latest_index) and evals
        of a different region park instead of entering the ready queues."""
        with self._lock:
            self._region = region
            self._index_source = index_source

    def _queue(self) -> _PriorityQueue:
        return _PriorityQueue(self.qos)

    # ------------------------------------------------------------- lifecycle
    def enabled(self) -> bool:
        with self._lock:
            return self._enabled

    def set_enabled(self, enabled: bool) -> None:
        with self._lock:
            self._enabled = enabled
        if not enabled:
            self.flush()

    def flush(self) -> None:
        """(reference: eval_broker.go Flush)"""
        with self._lock:
            for unack in self._unack.values():
                unack.nack_timer.cancel()
            for timer in self._time_wait.values():
                timer.cancel()
            self._evals.clear()
            self._job_evals.clear()
            self._blocked.clear()
            self._ready.clear()
            self._unack.clear()
            self._requeue.clear()
            self._time_wait.clear()
            self._ages.clear()
            self._ready_at.clear()
            self._age_slack.clear()
            self._floors.clear()
            self._foreign.clear()
            self.stats = BrokerStats()
            self._cond.notify_all()

    # --------------------------------------------------------------- enqueue
    def enqueue(self, ev: Evaluation) -> None:
        with self._lock:
            self._process_enqueue(ev, "")

    def enqueue_all(self, evals: Dict[str, Tuple[Evaluation, str]],
                    ages: Optional[Dict[str, float]] = None) -> None:
        """evals: eval.ID -> (eval, token) for token-gated requeues.
        ``ages`` seeds original first-enqueue times (monotonic) for evals
        re-entering from outside the broker — BlockedEvals carries them so
        a capacity-requeued eval keeps its queue age instead of resetting
        behind fresh arrivals."""
        with self._lock:
            if ages:
                for eid, ts in ages.items():
                    if ts:
                        self._ages.setdefault(eid, ts)
            for ev, token in evals.values():
                self._process_enqueue(ev, token)

    @requires_lock("_lock")
    def _process_enqueue(self, ev: Evaluation, token: str) -> None:
        # Tracing: remember the enqueuing context (one dict write when a
        # trace is active, one truthiness check otherwise) so the worker
        # that dequeues this eval — any thread, any time — can resume it,
        # and stamp the hop on the active span.
        trace.link("eval", ev.ID)
        trace.add_event("broker.enqueue", eval=ev.ID, job=ev.JobID)
        if ev.ID in self._evals:
            if token == "":
                return
            unack = self._unack.get(ev.ID)
            if unack is not None and unack.token == token:
                self._requeue[token] = ev
            return
        if self._enabled:
            self._evals[ev.ID] = 0

        if ev.Wait > 0:
            self._time_wait[ev.ID] = wheel.after(
                ev.Wait / 1e9, self._enqueue_waiting, ev)
            self.stats.TotalWaiting += 1
            return
        self._enqueue_locked(ev, ev.Type)

    def _enqueue_waiting(self, ev: Evaluation) -> None:
        with self._lock:
            self._time_wait.pop(ev.ID, None)
            self.stats.TotalWaiting -= 1
            self._enqueue_locked(ev, ev.Type)

    def _enqueue_locked(self, ev: Evaluation, queue: str) -> None:
        if not self._enabled:
            return
        if self._region and ev.Region and ev.Region != self._region:
            # Region-aware routing: this region has no nodes for the
            # eval's job — park it rather than hand it to a local
            # scheduler that can only fail it into a blocked eval no
            # capacity change here will ever unblock. Ingress forwarding
            # keeps these from existing at all; the park is the safety
            # net for pre-federation data and misdirected writes.
            if ev.ID not in self._foreign:
                self._foreign[ev.ID] = ev
                metrics.incr_counter(("nomad", "federation",
                                      "foreign_evals"))
                # The park is a bounded DIAGNOSTIC, not an authority:
                # nothing ever serves these locally, so a leader fed a
                # steady stream of misdirected writes must not grow the
                # dict (and pin dead Evaluations) for its whole term —
                # evict oldest-first past the cap (insertion-ordered).
                while len(self._foreign) > FOREIGN_PARK_CAP:
                    self._foreign.pop(next(iter(self._foreign)))
            return
        # First-enqueue memory: a Nack redelivery or blocked requeue keeps
        # the original timestamp (setdefault), so tier aging and SLO burn
        # see the eval's TRUE queue age, not its latest re-entry.
        enq_time = self._ages.setdefault(ev.ID, time.monotonic())
        pending = self._job_evals.get(ev.JobID, "")
        if pending == "":
            self._job_evals[ev.JobID] = ev.ID
        elif pending != ev.ID:
            self._blocked.setdefault(ev.JobID, _PriorityQueue()).push(ev)
            self.stats.TotalBlocked += 1
            return
        if self._index_source is not None:
            # Release floor (federation): the store index at the moment
            # this eval enters a ready queue. Overwritten on every
            # re-entry (nack redelivery, blocked promotion) — the newest
            # release point is the sound snapshot bound.
            self._floors[ev.ID] = self._index_source()
        self._ready_at[ev.ID] = time.monotonic()
        self._ready.setdefault(queue, self._queue()).push(ev, enq_time)
        self.stats.TotalReady += 1
        sched = self.stats.ByScheduler.setdefault(
            queue, {"Ready": 0, "Unacked": 0})
        sched["Ready"] += 1
        self._cond.notify_all()

    # --------------------------------------------------------------- dequeue
    def dequeue(self, schedulers: List[str], timeout: Optional[float] = None
                ) -> Tuple[Optional[Evaluation], str]:
        """Blocking dequeue of the highest-priority eligible eval.

        timeout is in seconds; None or 0 blocks indefinitely (reference
        semantics: Dequeue with timeout 0 has no timeout channel).
        """
        import time as _time

        end = None if not timeout else _time.monotonic() + timeout
        with self._lock:
            while True:
                if not self._enabled:
                    raise RuntimeError("eval broker disabled")
                got = self._scan(schedulers)
                if got is not None:
                    return got
                if end is None:
                    self._cond.wait()
                else:
                    remaining = end - _time.monotonic()
                    if remaining <= 0 or not self._cond.wait(remaining):
                        return None, ""

    def dequeue_window(self, schedulers: List[str], count: int,
                       timeout: Optional[float] = None,
                       fill_timeout: float = 0.0
                       ) -> List[Tuple[Evaluation, str]]:
        """Batch dequeue of up to `count` evals as ONE window under a
        single lock hold (the N-worker fast path). Blocks like dequeue()
        for the first eligible eval, then drains whatever else is already
        ready; with fill_timeout > 0 it lingers that long for stragglers
        (an enqueue burst still landing) before returning a short window.

        Handing the whole window out inside one critical section gives
        each worker a DISJOINT eval set in one lock round — per-eval
        dequeue loops from two workers interleave-steal each other's
        window fills and convoy on the lock, so both end up dispatching
        half-size windows that each still pay a full device round trip."""
        import time as _time

        out: List[Tuple[Evaluation, str]] = []
        if count <= 0:
            return out
        end = None if not timeout else _time.monotonic() + timeout
        with self._lock:
            while True:
                if not self._enabled:
                    raise RuntimeError("eval broker disabled")
                got = self._scan(schedulers)
                if got is not None:
                    out.append(got)
                    break
                if end is None:
                    self._cond.wait()
                else:
                    remaining = end - _time.monotonic()
                    if remaining <= 0 or not self._cond.wait(remaining):
                        return out
            fill_end = _time.monotonic() + fill_timeout
            while len(out) < count:
                if not self._enabled:
                    break
                got = self._scan(schedulers)
                if got is not None:
                    out.append(got)
                    continue
                remaining = fill_end - _time.monotonic()
                if remaining <= 0 or not self._cond.wait(remaining):
                    break
        return out

    @requires_lock("_lock")
    def _scan(self, schedulers: List[str]
              ) -> Optional[Tuple[Evaluation, str]]:
        if qos_enabled(self.qos):
            # Tier-aware scan: pick the scheduler whose head has the best
            # (effective tier, queue age, priority) key — high tier drains
            # first, aged lower tiers promote, ties go to the oldest.
            now = time.monotonic()
            best_key = None
            eligible: List[str] = []
            for sched in schedulers:
                pending = self._ready.get(sched)
                if pending is None:
                    continue
                key = pending.peek_key(now)
                if key is None:
                    continue
                if best_key is None or key < best_key:
                    best_key = key
                    eligible = [sched]
                elif key == best_key:
                    eligible.append(sched)
            if not eligible:
                return None
            return self._dequeue_for_sched(random.choice(eligible), now=now)
        eligible = []
        eligible_priority = 0
        for sched in schedulers:
            pending = self._ready.get(sched)
            if pending is None:
                continue
            ready = pending.peek()
            if ready is None:
                continue
            if not eligible or ready.Priority > eligible_priority:
                eligible = [sched]
                eligible_priority = ready.Priority
            elif ready.Priority == eligible_priority:
                eligible.append(sched)
        if not eligible:
            return None
        return self._dequeue_for_sched(random.choice(eligible))

    @requires_lock("_lock")
    def _dequeue_for_sched(self, sched: str,
                           now: Optional[float] = None
                           ) -> Tuple[Evaluation, str]:
        ev = self._ready[sched].pop(now)
        ready_at = self._ready_at.pop(ev.ID, None)
        if ready_at is not None:
            metrics.measure_since(("nomad", "broker", "wait"), ready_at)
            # Synthesized queue-wait span, from the same stamp.
            trace.record_span(trace.linked("eval", ev.ID), "broker.wait",
                              ready_at, eval=ev.ID, scheduler=sched)
        token = generate_uuid()
        timer = wheel.after(self.nack_timeout, self.nack, ev.ID, token)
        self._unack[ev.ID] = _Unack(ev, token, timer)
        self._evals[ev.ID] = self._evals.get(ev.ID, 0) + 1
        self.stats.TotalReady -= 1
        self.stats.TotalUnacked += 1
        by = self.stats.ByScheduler[sched]
        by["Ready"] -= 1
        by["Unacked"] += 1
        return ev, token

    # --------------------------------------------------------------- ack/nack
    def outstanding(self, eval_id: str) -> Optional[str]:
        with self._lock:
            unack = self._unack.get(eval_id)
            return unack.token if unack is not None else None

    def outstanding_reset(self, eval_id: str, token: str) -> None:
        """Reset the nack timer mid-flight (reference: OutstandingReset)."""
        with self._lock:
            unack = self._unack.get(eval_id)
            if unack is None:
                raise NotOutstandingError(eval_id)
            if unack.token != token:
                raise TokenMismatchError(eval_id)
            unack.nack_timer.cancel()
            unack.nack_timer = wheel.after(self.nack_timeout, self.nack,
                                           eval_id, token)

    def outstanding_reset_batch(self, pairs: List[Tuple[str, str]]
                                ) -> set:
        """outstanding_reset for a whole window under ONE lock hold (the
        pipelined worker re-arms every live eval's nack deadline at each
        stage entry; per-eval lock rounds from N workers convoy here and
        let deadlines lapse mid-window — the redelivery storm behind the
        `stale` counter). Returns the set of eval ids no longer
        outstanding to this caller (redelivered / token rotated) instead
        of raising — one stale eval must not abort the sweep for the
        rest of the window."""
        stale: set = set()
        with self._lock:
            for eval_id, token in pairs:
                unack = self._unack.get(eval_id)
                if unack is None or unack.token != token:
                    stale.add(eval_id)
                    continue
                unack.nack_timer.cancel()
                unack.nack_timer = wheel.after(self.nack_timeout, self.nack,
                                               eval_id, token)
        return stale

    def ack(self, eval_id: str, token: str) -> None:
        """(reference: eval_broker.go:461-519)"""
        with self._lock:
            self._ack_locked(eval_id, token)

    def ack_batch(self, pairs: List[Tuple[str, str]]
                  ) -> List[Tuple[str, Exception]]:
        """Ack a whole window's evals under ONE lock hold. Per-eval
        broker races (redelivered mid-window, token rotated) are
        returned, not raised — one lost eval must not abort the acks of
        the rest of the window."""
        failures: List[Tuple[str, Exception]] = []
        with self._lock:
            for eval_id, token in pairs:
                try:
                    self._ack_locked(eval_id, token)
                except (NotOutstandingError, TokenMismatchError) as e:
                    failures.append((eval_id, e))
        return failures

    @requires_lock("_lock")
    def _ack_locked(self, eval_id: str, token: str) -> None:
        requeued = self._requeue.pop(token, None)
        unack = self._unack.get(eval_id)
        if unack is None:
            raise NotOutstandingError(f"Evaluation ID not found: {eval_id}")
        if unack.token != token:
            raise TokenMismatchError(eval_id)
        unack.nack_timer.cancel()
        job_id = unack.eval.JobID
        enq_time = self._ages.pop(eval_id, 0.0)
        slack = self._age_slack.pop(eval_id, 0.0)
        self._floors.pop(eval_id, None)
        if qos_enabled(self.qos) and enq_time:
            # SLO burn: did this eval's whole broker residency (first
            # enqueue -> ack, spanning redeliveries) blow its tier
            # deadline? Admission control sheds lower tiers on this.
            # Minus the failover witness slack: a restored eval's seed
            # errs older by up to one timetable interval, and counting
            # that as burn would saturate the rings (and shed tiers)
            # after every election on a long-lived cluster.
            tier = self.qos.tier_of(unack.eval.Priority)
            waited = time.monotonic() - enq_time - slack
            self._slo[tier].append(waited > self.qos.deadlines_s[tier])

        self.stats.TotalUnacked -= 1
        queue = unack.eval.Type
        if self._evals.get(eval_id, 0) > self.delivery_limit:
            queue = FAILED_QUEUE
        by = self.stats.ByScheduler.get(queue)
        if by is not None:
            by["Unacked"] -= 1

        self._unack.pop(eval_id, None)
        self._evals.pop(eval_id, None)
        self._job_evals.pop(job_id, None)

        blocked = self._blocked.get(job_id)
        if blocked is not None and len(blocked):
            ev = blocked.pop()
            if not len(blocked):
                self._blocked.pop(job_id, None)
            self.stats.TotalBlocked -= 1
            self._enqueue_locked(ev, ev.Type)

        if requeued is not None:
            # Token-gated deferred requeue: the SAME logical eval keeps
            # waiting, so it keeps its original queue age (the pop above
            # closed the SLO measurement for the delivery that just
            # acked; without re-seeding, the requeue would reset the
            # aging clock behind fresh arrivals).
            if enq_time:
                self._ages.setdefault(eval_id, enq_time)
            self._process_enqueue(requeued, "")

    def nack(self, eval_id: str, token: str) -> None:
        """(reference: eval_broker.go:520-560)"""
        with self._lock:
            self._requeue.pop(token, None)
            unack = self._unack.get(eval_id)
            if unack is None:
                raise NotOutstandingError(f"Evaluation ID not found: {eval_id}")
            if unack.token != token:
                raise TokenMismatchError(eval_id)
            unack.nack_timer.cancel()
            self._unack.pop(eval_id, None)
            self.stats.TotalUnacked -= 1
            by = self.stats.ByScheduler.get(unack.eval.Type)
            if by is not None:
                by["Unacked"] -= 1
            if self._evals.get(eval_id, 0) >= self.delivery_limit:
                self._enqueue_locked(unack.eval, FAILED_QUEUE)
            else:
                self._enqueue_locked(unack.eval, unack.eval.Type)

    # ------------------------------------------------- federation accessors
    def release_floor(self, eval_id: str) -> Optional[int]:
        """The store index at which this eval entered the ready queue
        (federation snapshot floor), or None when federation is off —
        callers then fall back to the pre-federation global latest
        index, keeping the disabled path bit-identical."""
        with self._lock:
            return self._floors.get(eval_id)

    def foreign_parked(self) -> List[Evaluation]:
        """Evals parked as foreign-region (never served locally)."""
        with self._lock:
            return list(self._foreign.values())

    def foreign_count(self) -> int:
        """len(foreign_parked()) without copying the dict — the stats
        loop and sched-stats endpoint only want the number."""
        with self._lock:
            return len(self._foreign)

    # ------------------------------------------------------ QoS introspection
    def seed_age_slack(self, slack: Dict[str, float]) -> None:
        """Record per-eval witness slack for restored evals (see
        _age_slack). Seeded once per eval — an existing entry (an eval
        that rode TWO elections accumulates only its first, larger
        slack) is kept."""
        with self._lock:
            for eid, s in slack.items():
                if s > 0.0:
                    self._age_slack.setdefault(eid, s)

    def queue_age(self, eval_id: str) -> Optional[float]:
        """Monotonic timestamp of the eval's FIRST enqueue (preserved
        across Nack redeliveries), or None once acked/unknown."""
        with self._lock:
            return self._ages.get(eval_id)

    def tier_depths(self) -> List[int]:
        """Ready-queue depth per QoS tier, summed over scheduler types
        (all zeros except tier 0 totals when QoS is disabled)."""
        with self._lock:
            out = [0] * N_TIERS
            for sched, pending in self._ready.items():
                if sched == FAILED_QUEUE:
                    continue
                for tier, n in enumerate(pending.tier_depths()):
                    out[tier] += n
            return out

    def tier_promotions(self) -> int:
        """Total aged-up pops (anti-starvation promotions served)."""
        with self._lock:
            return sum(q.promoted for q in self._ready.values())

    def slo_burn(self) -> List[float]:
        """Per-tier fraction of recent completions that blew their tier
        deadline (first enqueue -> ack), over the burn_window ring."""
        with self._lock:
            return [(sum(ring) / len(ring)) if ring else 0.0
                    for ring in self._slo]

    def qos_stats(self) -> Dict[str, Dict[str, float]]:
        """Named-tier snapshot for the sched-stats surface."""
        depths = self.tier_depths()
        burn = self.slo_burn()
        return {
            "TierDepths": dict(zip(TIER_NAMES, depths)),
            "SLOBurn": {name: round(b, 4)
                        for name, b in zip(TIER_NAMES, burn)},
            "Promoted": self.tier_promotions(),
        }
