"""Plan applier: THE serialization point (reference: nomad/plan_apply.go).

Dequeues pending plans, verifies every placement against a state snapshot,
computes partial commits + RefreshIndex, applies through the consensus
backend, and responds to the waiting worker.

Two reference optimizations are mirrored here:

- **Overlapped apply** (plan_apply.go:24-33): while plan N's Raft apply is in
  flight, plan N+1 is verified against an OPTIMISTIC snapshot that assumes N
  committed. Productive work happens during consensus latency; the waiter is
  answered asynchronously only after the log really commits.
- **Evaluate pool** (plan_apply_pool.go:38): per-node verification of large
  plans fans out over a thread pool — each node's check is independent.

Verification reads the node tensor: placements without network asks fit-check
as one vector comparison against committed usage (+ the optimistic in-flight
overlay); only nodes needing exact port/bandwidth bitmap accounting
(structs.allocs_fit) take the per-node object path.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from nomad_tpu.resilience import failpoints
from nomad_tpu.tensor.node_table import RES_DIMS, alloc_vec
from nomad_tpu.structs import (
    Allocation,
    Plan,
    PlanResult,
    allocs_fit,
    remove_allocs,
)
from nomad_tpu.structs.structs import NodeStatusReady
from nomad_tpu.telemetry import metrics, trace

from .eval_broker import EvalBroker
from .fsm import DevRaft, MessageType
from .plan_queue import PendingPlan, PlanQueue

logger = logging.getLogger("nomad.plan_apply")

# Below this many touched nodes a plan is verified inline: thread fan-out
# costs more than it saves (reference: pool used unconditionally, but Go
# goroutines are cheaper than pool dispatch here).
_POOL_THRESHOLD = 8

# Max verified plans committed as one consensus entry. Bounds the entry size
# (reference warns at 1MB raft entries, rpc.go:45-47: 16 x 50-alloc plans
# stays well under) and the blast radius of a failed group apply.
_APPLY_BATCH = 16


def _result_allocs(result: "PlanResult") -> List[Allocation]:
    # NodeUpdate (evictions/stops) precede NodeAllocation deliberately:
    # the FSM upserts in list order, so within one commit the state store
    # observes stop-then-place — a preemption's victims are terminal
    # before its placement lands.
    allocs: List[Allocation] = []
    for updates in result.NodeUpdate.values():
        allocs.extend(updates)
    for placed in result.NodeAllocation.values():
        allocs.extend(placed)
    return allocs


def _encode_result(plan: Plan, result: "PlanResult"):
    """One consensus-entry group element for a verified result. A result
    carrying a full-coverage columnar SweepBatch encodes as ONE columnar
    payload (ids + instance names + frozen per-TG templates + per-row
    delta) — not N alloc dicts; its exact-path stops ride the same
    element (`Updates`) so eviction+placement stay one atomic entry.
    Returns (element, is_sweep)."""
    sweep = getattr(result, "_sweep", None)
    if sweep is not None and getattr(sweep, "alloc_ids", None):
        updates: List[Allocation] = []
        for ups in result.NodeUpdate.values():
            updates.extend(ups)
        element = {"Job": plan.Job, "Sweep": sweep.wire()}
        if updates:
            element["Updates"] = updates
        return element, True
    return {"Job": plan.Job, "Alloc": _result_allocs(result)}, False


def _fire_store_commit() -> None:
    """Failure seam: a consensus entry carrying a columnar sweep batch.
    Fires BEFORE raft.apply (like plan.apply.commit), so a killed bulk
    commit never enters the durable log — the waiting workers nack, the
    broker redelivers exactly once, and no replica (or log replay) can
    ever land the killed batch: all rows or none, never torn. Firing
    post-consensus instead would leave the entry in the log and
    duplicate the batch on replay."""
    if failpoints.fire("state.store.commit") == "drop":
        raise failpoints.FailpointError("state.store.commit")


def _fire_preempt_commit(plans) -> None:
    """Failure seam: a consensus commit carrying alloc preemptions. Like
    plan.apply.commit, drop degrades to a failed apply — the waiting
    workers nack, the broker redelivers, and because evictions and their
    placement ride ONE entry, a killed commit loses both or neither."""
    if any(getattr(p, "_preempt", None) for p in plans):
        if failpoints.fire("plan.preempt.commit") == "drop":
            raise failpoints.FailpointError("plan.preempt.commit")


class OptimisticSnapshot:
    """A read view layering not-yet-committed plan results over a state
    snapshot (reference: snap.UpsertAllocs after raft dispatch,
    plan_apply.go:152-158). Supports exactly the reads evaluate_plan needs.

    When built with the node tensor it additionally keeps a per-row usage
    delta of the in-flight result so the vectorized verifier can fit-check
    against (committed usage + in-flight overlay) without re-walking
    allocation objects."""

    def __init__(self, snap, nt=None):
        self.snap = snap
        self.nt = nt
        self._added: Dict[str, List[Allocation]] = {}
        # In-flight results that came with a sweep descriptor, each kept
        # whole (a ColumnarPlacements, or a system sweep's per-node dict)
        # and asked by allocs_by_node_terminal: columns are built into
        # objects only then, and a 10k-node dict is never merged in.
        self._added_columns: List[Any] = []
        self._removed: Set[str] = set()
        self.row_delta: Dict[int, np.ndarray] = {}
        # Dense in-flight usage overlay, allocated lazily by the first
        # SWEEP result (a system sweep's 10k placements would otherwise
        # become 10k per-row dict entries built one _overlay call at a
        # time). Readers treat it as an additive sibling of row_delta.
        self.row_dense: Optional[np.ndarray] = None

    def apply_result(self, result: PlanResult) -> None:
        for updates in result.NodeUpdate.values():
            for a in updates:
                self._removed.add(a.ID)
        sweep = getattr(result, "_sweep", None)
        if (sweep is not None and self.nt is not None
                and sweep.n_rows == self.nt.n_rows
                and sweep.epoch == self.nt.row_epoch):
            # Columnar sweep result: ONE scatter-add replaces the
            # per-alloc row overlay. The descriptor covers every
            # NodeAllocation key (evaluate_plan only attaches it then),
            # so nothing is missed. The exact verify path of a LATER plan
            # in the group reads the placements through
            # allocs_by_node_terminal: kept whole here, asked when read.
            if self.row_dense is None:
                self.row_dense = np.zeros((self.nt.n_rows, RES_DIMS),
                                          dtype=np.float32)
            elif self.row_dense.shape[0] < sweep.n_rows:
                # Table grew since the overlay was allocated; row indices
                # are stable across growth, so zero-extend.
                grown = np.zeros((sweep.n_rows, RES_DIMS), dtype=np.float32)
                grown[:self.row_dense.shape[0]] = self.row_dense
                self.row_dense = grown
            np.add.at(self.row_dense, sweep.rows, sweep.delta)
            self._added_columns.append(result.NodeAllocation)
            return
        for node_id, placed in result.NodeAllocation.items():
            self._added.setdefault(node_id, []).extend(placed)
            for a in placed:
                self._overlay(node_id, a)

    def _overlay(self, node_id: str, alloc: Allocation) -> None:
        """Record an in-flight PLACEMENT in the row overlay. Deliberately
        one-sided: in-flight EVICTIONS are never credited, because the live
        tensor may absorb the in-flight commit mid-verify and crediting the
        eviction twice would understate usage (over-commit). The one-sided
        overlay only ever OVERSTATES usage — worst case a spurious partial
        commit, which the worker resolves through the exact per-eval path."""
        if self.nt is None:
            return
        row = self.nt.row_of.get(node_id)
        if row is None:
            return
        cur = self.row_delta.get(row)
        if cur is None:
            cur = self.row_delta[row] = np.zeros(RES_DIMS, dtype=np.float32)
        cur += alloc_vec(alloc)

    def node_by_id(self, node_id: str):
        return self.snap.node_by_id(node_id)

    def alloc_by_id(self, alloc_id: str):
        return self.snap.alloc_by_id(alloc_id)

    def allocs_by_node_terminal(self, node_id: str, terminal: bool):
        out = [a for a in self.snap.allocs_by_node_terminal(node_id, terminal)
               if a.ID not in self._removed]
        if not terminal:
            out.extend(self._added.get(node_id, ()))
            for placements in self._added_columns:
                if node_id in placements:
                    out.extend(placements[node_id])
        return out

    def get_index(self, table: str) -> int:
        return self.snap.get_index(table)


# allocs_fit's verdicts that come from the NetworkIndex, not from CPU,
# memory, disk or IOPS.
_NETWORK_DIMS = ("reserved port collision", "bandwidth exhausted")


def _alloc_asks_network(alloc: Allocation) -> bool:
    if alloc.Resources is not None and alloc.Resources.Networks:
        return True
    for r in alloc.TaskResources.values():
        if r is not None and r.Networks:
            return True
    return False


def _vector_fit(snap, plan: Plan, nt, node_ids: List[str]
                ) -> Tuple[Dict[str, bool], List[str]]:
    """Vectorized fit pre-pass over the node tensor: nodes whose placements
    ask no network resources fit-check as ONE numpy comparison against
    committed usage (+ the optimistic in-flight overlay) instead of per-alloc
    object math. Returns (decided fits, nodes needing the exact path).

    This is the TPU-framework shape of the applier: commit-side verification
    reads the same tensor mirror the placement kernels run on, so a 50-node
    plan verifies in ~one vector op and the applier stops competing with the
    scheduler for interpreter time. Port/bandwidth-device accounting can't
    vectorize (exact bitmap semantics) — those nodes take the exact path."""
    fits: Dict[str, bool] = {}
    exact: List[str] = []
    rows: List[int] = []
    row_ids: List[str] = []
    deltas: List[np.ndarray] = []
    overlay = getattr(snap, "row_delta", None) or {}
    dense = getattr(snap, "row_dense", None)
    # Row indices are STABLE across table growth (_grow only extends), so
    # a dense overlay allocated before a grow stays valid for its rows;
    # rows beyond its bound were grown later and legitimately carry zero
    # in-flight delta. Reads below bound-check instead of assuming the
    # shapes match.
    n_dense = dense.shape[0] if dense is not None else 0

    sweep = getattr(plan, "_sweep", None)
    if (sweep is not None and len(sweep.rows)
            and sweep.epoch == nt.row_epoch and sweep.n_rows == nt.n_rows):
        # Columnar sweep verify: the whole batch is ONE vectorized
        # capacity check — fresh-UUID, no-network placements with their
        # per-row demand precomputed at emit, so the per-node delta
        # assembly loop below has nothing left to derive. Readiness comes
        # from the tensor mirror, which is updated synchronously at state
        # commit and therefore at least as fresh as any snapshot; a row
        # whose identity moved since emit invalidates the descriptor
        # (epoch guard) and falls back to the per-node walk.
        srows = sweep.rows
        d = sweep.delta.astype(np.float32, copy=True)
        if dense is not None:
            in_bound = srows < n_dense
            if in_bound.all():
                d += dense[srows]
            elif in_bound.any():
                d[in_bound] += dense[srows[in_bound]]
        for row, vec in overlay.items():
            i = int(np.searchsorted(srows, row))
            if i < len(srows) and srows[i] == row:
                d[i] += vec
        usage, capacity = nt.snapshot_rows(srows)
        ok = nt.ready[srows] & np.all(usage + d <= capacity, axis=1)
        for nid, fit in zip(sweep.node_ids, ok.tolist()):
            fits[nid] = fit
        metrics.incr_counter(("nomad", "sched", "system", "bulk_verify"))

    for nid in node_ids:
        if nid in fits:
            continue
        placed = plan.NodeAllocation.get(nid)
        if not placed:
            fits[nid] = True  # evict-only always fits
            continue
        node = snap.node_by_id(nid)
        if node is None or node.Status != NodeStatusReady or node.Drain:
            fits[nid] = False
            continue
        row = nt.row_of.get(nid)
        if row is None:
            exact.append(nid)
            continue
        delta = np.zeros(RES_DIMS, dtype=np.float32)
        simple = True
        for a in placed:
            # Port asks need bitmap accounting; an alloc replacing a live
            # version of itself (in-place update) needs remove-then-add.
            if _alloc_asks_network(a):
                simple = False
                break
            prev = snap.alloc_by_id(a.ID)
            if prev is not None and not prev.terminal_status():
                simple = False
                break
            delta += alloc_vec(a)
        if not simple:
            exact.append(nid)
            continue
        for a in plan.NodeUpdate.get(nid, ()):
            full = snap.alloc_by_id(a.ID) or a
            if not full.terminal_status():
                delta -= alloc_vec(full)
        ov = overlay.get(row)
        if ov is not None:
            delta += ov
        if dense is not None and row < n_dense:
            delta += dense[row]
        rows.append(row)
        row_ids.append(nid)
        deltas.append(delta)
    if rows:
        r = np.asarray(rows, dtype=np.int64)
        d = np.stack(deltas)
        # Row copies under the tensor lock: alloc commits mutate usage rows
        # in place, and a torn row read mid-`+=` could mis-admit a placement.
        usage, capacity = nt.snapshot_rows(r)
        ok = np.all(usage + d <= capacity, axis=1)
        for nid, fit in zip(row_ids, ok):
            fits[nid] = bool(fit)
    # Which half a plan's nodes took: the share of `exact_nodes` is what a
    # network ask costs the applier (a NetworkIndex a node).
    if fits:
        metrics.incr_counter(("nomad", "plan", "verify", "vector_nodes"),
                             len(fits))
    if exact:
        metrics.incr_counter(("nomad", "plan", "verify", "exact_nodes"),
                             len(exact))
    return fits, exact


def evaluate_plan(snap, plan: Plan,
                  pool: Optional[ThreadPoolExecutor] = None,
                  nt=None) -> PlanResult:
    """Per-node fit re-check of a plan (reference: plan_apply.go:194-316).
    With the node tensor, no-port placements verify as one vector op; with a
    pool, remaining exact node checks run in parallel (plan_apply_pool.go)."""
    result = PlanResult()
    node_ids = list(dict.fromkeys(list(plan.NodeUpdate) + list(plan.NodeAllocation)))

    decided: Dict[str, bool] = {}
    exact_ids = node_ids
    if nt is not None:
        decided, exact_ids = _vector_fit(snap, plan, nt, node_ids)

    if pool is not None and len(exact_ids) >= _POOL_THRESHOLD:
        # Chunked fan-out: one pool task per worker, not per node — pool
        # dispatch overhead is comparable to a single node check, so per-node
        # submission would spend more time queueing than verifying.
        workers = getattr(pool, "_max_workers", 4)
        step = max(1, -(-len(exact_ids) // workers))
        chunks = [exact_ids[i:i + step] for i in range(0, len(exact_ids), step)]
        fits_chunks = pool.map(
            lambda chunk: [_evaluate_node_plan(snap, plan, nid)
                           for nid in chunk], chunks)
        for chunk, chunk_fits in zip(chunks, fits_chunks):
            decided.update(zip(chunk, chunk_fits))
    else:
        for nid in exact_ids:
            decided[nid] = _evaluate_node_plan(snap, plan, nid)

    preempt = getattr(plan, "_preempt", None)
    if preempt:
        # Preemption atomicity, belt-and-braces: a preempting node's
        # evictions must NEVER commit without their placement. The
        # per-node verify already drops both sides of a node together;
        # this guards a malformed plan (evictions recorded, placement
        # stripped) from riding the evict-only-always-fits rule — on
        # BOTH the wholesale-admit and the partial paths below.
        for nid in preempt:
            if decided.get(nid) and not plan.NodeAllocation.get(nid):
                decided[nid] = False

    if decided and len(decided) == len(node_ids) \
            and all(decided.values()):
        # Everything fits (the healthy-sweep common case): admit the plan
        # wholesale instead of re-walking 10k node ids to copy dict
        # entries one at a time. A full-coverage sweep descriptor rides
        # the result so the optimistic overlay applies it as one scatter;
        # placements held as columns only are admitted as columns.
        result.NodeUpdate = dict(plan.NodeUpdate)
        result.NodeAllocation = plan.NodeAllocation.copy()
        sweep = getattr(plan, "_sweep", None)
        if sweep is not None \
                and len(sweep.node_ids) == len(plan.NodeAllocation):
            result._sweep = sweep
        return result

    partial_commit = False
    for node_id in node_ids:
        fit = decided[node_id]
        if not fit:
            partial_commit = True
            if plan.AllAtOnce:
                result.NodeUpdate = {}
                result.NodeAllocation = {}
                break
            continue
        if plan.NodeUpdate.get(node_id):
            result.NodeUpdate[node_id] = plan.NodeUpdate[node_id]
        if plan.NodeAllocation.get(node_id):
            result.NodeAllocation[node_id] = plan.NodeAllocation[node_id]

    if partial_commit:
        result.RefreshIndex = max(snap.get_index("nodes"),
                                  snap.get_index("allocs"))
    return result


def _evaluate_node_plan(snap, plan: Plan, node_id: str) -> bool:
    """(reference: plan_apply.go:318-361)"""
    if not plan.NodeAllocation.get(node_id):
        return True  # evict-only always fits
    node = snap.node_by_id(node_id)
    if node is None or node.Status != NodeStatusReady or node.Drain:
        return False
    existing = snap.allocs_by_node_terminal(node_id, False)
    remove: List[Allocation] = list(plan.NodeUpdate.get(node_id, ()))
    remove.extend(plan.NodeAllocation.get(node_id, ()))
    proposed = remove_allocs(list(existing), remove)
    proposed.extend(plan.NodeAllocation.get(node_id, ()))
    try:
        fit, dim, _ = allocs_fit(node, proposed)
    except ValueError:
        return False
    if not fit and dim in _NETWORK_DIMS:
        # Two plans drew the same port blind to each other, or together
        # overcommit the device: the node is refused, the plan is partial.
        metrics.incr_counter(("nomad", "plan", "partial", "ports"))
    return fit


def _join(wait: threading.Thread) -> None:
    """Wait for the previous group's commit. One nomad.plan.join sample a
    group that had a commit before it: how long the applier's loop stood
    behind its own apply thread (~0 where that had already ended)."""
    with metrics.measure(("nomad", "plan", "join")):
        wait.join()


class PlanApplier:
    """The leader's plan-apply loop with verify/apply overlap
    (reference: planApply, plan_apply.go:41-119).

    Concurrency note (why no guarded_by registry here): the applier's
    mutable state is confined by protocol, not by a lock. The run loop
    owns verify-side stats keys; the single in-flight apply thread owns
    apply-side keys (`applied`/`apply_failed`); the run
    loop only reads apply-side keys after `wait.join()`, which is the
    happens-before edge. At most one apply thread exists at a time."""

    def __init__(self, plan_queue: PlanQueue, raft: DevRaft,
                 eval_broker: Optional[EvalBroker] = None,
                 pool_size: Optional[int] = None, tindex=None,
                 qos_counters=None, fed=None):
        self.plan_queue = plan_queue
        self.raft = raft
        self.eval_broker = eval_broker
        self.tindex = tindex
        # FederationConfig (None = federation off): plans stamped with a
        # snapshot birth time (`_fed_born`, worker-side) older than
        # fed.reject_after_s at verify time are rejected outright — the
        # Omega staleness backstop (see federation/snapshots.py).
        self.fed = fed
        # QoS flow counters (qos/tiers.py QoSCounters): preempt_placed /
        # preempt_evictions are counted HERE, at commit, so rejected
        # preemption plans never inflate the "landed" numbers.
        self.qos_counters = qos_counters
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._retired: List[threading.Thread] = []
        self._pool_size = pool_size or max(1, (os.cpu_count() or 2) // 2)
        self._pool: Optional[ThreadPoolExecutor] = None
        # Counters for telemetry/tests. The verify and the apply are timed
        # by the registry: nomad.plan.evaluate (a plan), nomad.plan.apply
        # (a group), each with its thread CPU as `.cpu`.
        self.stats = {"applied": 0, "rejected": 0, "overlapped": 0,
                      "apply_failed": 0}

    def _nt(self):
        return self.tindex.nt if self.tindex is not None else None

    def _count_preempt(self, plan: Plan, result: PlanResult) -> None:
        """Count preemption outcomes that actually COMMITTED: placements
        on preempting nodes that survived verification, and the victim
        evictions that rode them."""
        descriptor = getattr(plan, "_preempt", None)
        if not descriptor:
            return
        counts = getattr(plan, "_preempt_counts", None) or {}
        placed = evicted = 0
        for node_id, victim_ids in descriptor.items():
            landed = result.NodeAllocation.get(node_id)
            if landed:
                # Only the instances placed VIA preemption count — the
                # node may also carry the plan's normal placements.
                placed += min(counts.get(node_id, len(landed)),
                              len(landed))
                committed = {a.ID for a in result.NodeUpdate.get(node_id,
                                                                 ())}
                evicted += sum(1 for v in victim_ids if v in committed)
        if not placed:
            return
        if self.qos_counters is not None:
            self.qos_counters.incr("preempt_placed", placed)
            self.qos_counters.incr("preempt_evictions", evicted)
        metrics.incr_counter(("nomad", "qos", "preempt", "placed"), placed)
        metrics.incr_counter(("nomad", "qos", "preempt", "evictions"),
                             evicted)

    @staticmethod
    def _count_stops(result: PlanResult) -> None:
        """Count the allocations a COMMITTED plan stops (its NodeUpdate:
        a deregistered job's, a preemption's victims)."""
        stopped = sum(len(ups) for ups in result.NodeUpdate.values())
        if stopped:
            metrics.incr_counter(("nomad", "plan", "stop_rows"), stopped)

    def start(self) -> None:
        """Each run gets its OWN stop event, handed to the thread — a
        leadership flap that calls stop();start() must not revive the old
        run by clearing a shared flag (two live appliers would break the
        one-apply-in-flight invariant and could over-commit). The new run
        serializes behind the old thread before consuming the queue, and
        the old thread is retired for join() so shutdown still reaps it."""
        prev = self._thread
        if prev is not None and prev.is_alive():
            self._retired.append(prev)
        self._retired = [t for t in self._retired if t.is_alive()]
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self.run, args=(self._stop, prev), daemon=True,
            name="plan-apply")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def join(self, timeout: float = 30.0) -> None:
        """The apply path commits plan results into the tensor index (JAX
        device arrays); an unjoined thread there at interpreter exit
        aborts XLA teardown. Joins retired (flap-era) runs too."""
        deadline = time.monotonic() + timeout
        for t in [*self._retired, self._thread]:
            if (t is not None and t.is_alive()
                    and t is not threading.current_thread()):
                t.join(max(0.1, deadline - time.monotonic()))

    def run(self, stop: Optional[threading.Event] = None,
            prev: Optional[threading.Thread] = None) -> None:
        stop = stop if stop is not None else self._stop
        if prev is not None and prev.is_alive():
            # One applier at a time: wait out the previous run's last
            # iteration (bounded by its 0.5s dequeue poll + in-flight
            # apply) before touching the queue.
            prev.join(timeout=60.0)
        self._pool = ThreadPoolExecutor(max_workers=self._pool_size,
                                        thread_name_prefix="plan-eval")
        # One in-flight raft apply at a time; while it commits, the NEXT
        # GROUP of plans verifies against `opt`, an optimistic view that
        # assumes it landed. Plans queued back-to-back (a worker window
        # submitting its plans) verify against the chained overlay and
        # commit as ONE log entry / state transaction (fsm Batch shape) —
        # the reference overlaps verify with apply latency
        # (plan_apply.go:24-33); here apply is also CPU on this core, so
        # grouping cuts the work itself, not just the wait.
        wait: Optional[threading.Thread] = None
        opt: Optional[OptimisticSnapshot] = None
        try:
            while not stop.is_set():
                try:
                    pending = self.plan_queue.dequeue(timeout=0.5)
                    batch = [pending] if pending is not None else []
                    if pending is not None and len(batch) < _APPLY_BATCH:
                        # ONE lock hold drains the rest of the group:
                        # workers enqueue whole windows atomically
                        # (PlanQueue.enqueue_all), so the group is either
                        # already there or not coming this iteration —
                        # per-plan timed dequeues only convoyed the lock
                        # against concurrently submitting workers.
                        batch.extend(self.plan_queue.dequeue_ready(
                            _APPLY_BATCH - len(batch)))
                except RuntimeError:
                    return  # queue disabled
                live = []
                for p in batch:
                    metrics.measure_since(("nomad", "plan", "queue_wait"),
                                          p.enqueued)
                    if p.cancelled:
                        # Abandoned chunk (its submitter's earlier chunk
                        # failed): answer the future, commit nothing.
                        p.respond(None, RuntimeError("plan cancelled"))
                    else:
                        live.append(p)
                batch = live
                if not batch:
                    continue

                # Last apply already done? Fall back to a fresh snapshot.
                if wait is not None and not wait.is_alive():
                    _join(wait)
                    wait = None
                    opt = None
                # The optimistic view is only valid WHILE an apply is in
                # flight; with nothing outstanding, always verify against
                # fresh state (matches plan_apply.go:71-79's `waitCh == nil`
                # refresh — an old view could miss a node going down).
                if wait is None or opt is None:
                    opt = OptimisticSnapshot(self.raft.fsm.state.snapshot(),
                         nt=self._nt())

                def resync():
                    # Spurious-partial guard: the one-sided overlay can
                    # double-count the in-flight group once its commit
                    # lands in the live tensor mid-verify. A plan that
                    # verifies PARTIAL while an apply is outstanding gets
                    # one re-verify against settled state — a genuine
                    # overcommit still fails, a double-count victim passes
                    # instead of bouncing its whole eval through the
                    # worker's exact-path fallback (and the chain rebase
                    # stall that follows it). Also reports whether the
                    # joined apply FAILED: verdicts that assumed it landed
                    # (e.g. its evictions) are then stale, and the caller
                    # must re-verify them — setting wait=None here skips
                    # the run loop's own apply_failed re-check.
                    nonlocal wait
                    failed_before = self.stats["apply_failed"]
                    if wait is not None:
                        _join(wait)
                        wait = None
                    return (OptimisticSnapshot(
                                self.raft.fsm.state.snapshot(),
                                nt=self._nt()),
                            self.stats["apply_failed"] != failed_before)

                group, opt = self._verify_group(
                    batch, opt, overlapped=wait is not None, resync=resync)
                if not group:
                    continue

                # One apply in flight at a time: wait for the previous one,
                # then re-snapshot so the optimistic view can't drift more
                # than one group from the log (plan_apply.go:96-103).
                if wait is not None:
                    prev_failed_before = self.stats["apply_failed"]
                    _join(wait)
                    opt = OptimisticSnapshot(self.raft.fsm.state.snapshot(),
                         nt=self._nt())
                    if self.stats["apply_failed"] != prev_failed_before:
                        # The apply this group's verification assumed never
                        # landed (e.g. its evictions); re-verify against the
                        # real state before committing.
                        group, opt = self._verify_group(
                            [p for p, _ in group], opt, overlapped=False)
                        if not group:
                            wait = None
                            continue
                    else:
                        # Fresh snapshot lacks this group's own results:
                        # restore them to the overlay. (When no apply was in
                        # flight, _verify_group already layered them.)
                        for _, result in group:
                            opt.apply_result(result)

                wait = threading.Thread(
                    target=self._apply_group, args=(group,),
                    daemon=True, name="plan-apply-async")
                wait.start()
        finally:
            if wait is not None:
                wait.join()
            # Pool work is synchronous within _verify, so the pool is idle
            # here; wait=True is immediate and leaves no worker for the
            # interpreter-exit join to trip over.
            self._pool.shutdown(wait=True)
            self._pool = None

    def _verify_group(self, batch: List[PendingPlan],
                      opt: OptimisticSnapshot, overlapped: bool,
                      resync=None
                      ) -> Tuple[List[Tuple[PendingPlan, PlanResult]],
                                 OptimisticSnapshot]:
        """Verify plans in queue order against the shared overlay; each
        admitted plan's result is layered into `opt` so the next plan of the
        group sees it (the group analogue of the single-plan chain). No-op
        results respond immediately; rejected plans were answered by
        _verify. A PARTIAL verdict reached while an apply was in flight is
        suspect (the one-sided overlay may have double-counted that commit
        as it landed): `resync` waits the apply out and returns a settled
        snapshot, and the plan gets exactly one clean re-verify. Returns
        (group, opt) — opt is replaced when a resync happened."""
        group: List[Tuple[PendingPlan, PlanResult]] = []
        queue = list(batch)
        i = 0
        while i < len(queue):
            pending = queue[i]
            result = self._verify(pending, opt,
                                  overlapped=overlapped or bool(group))
            if (result is not None and result.RefreshIndex
                    and overlapped and resync is not None):
                # PARTIAL while an apply was in flight: the one-sided
                # overlay may have double-counted — annotate the eval's
                # trace so the re-verify shows up in its timeline.
                trace.add_trace_event(
                    trace.linked("eval", pending.plan.EvalID),
                    "plan.partial_reverify", eval=pending.plan.EvalID)
                opt, in_flight_failed = resync()
                overlapped = False
                if in_flight_failed:
                    # The apply this group's earlier verdicts assumed
                    # never landed (e.g. its evictions): every admitted
                    # plan is stale. Re-verify them all against the
                    # settled state, in order — the run loop's own
                    # apply_failed re-check won't run (wait is None now).
                    queue = [p for p, _ in group] + queue[i:]
                    group = []
                    i = 0
                    continue
                # The settled snapshot lacks this group's own admitted
                # results; restore them so plan ordering is preserved.
                for _, r in group:
                    opt.apply_result(r)
                result = self._verify(pending, opt,
                                      overlapped=bool(group))
            i += 1
            if result is None:
                continue
            if not result.NodeUpdate and not result.NodeAllocation:
                pending.respond(result, None)
                continue
            opt.apply_result(result)
            group.append((pending, result))
        return group, opt

    def _verify(self, pending: PendingPlan, opt: OptimisticSnapshot,
                overlapped: bool) -> Optional[PlanResult]:
        plan = pending.plan
        # Token check: the eval must still be outstanding to its worker
        # (anti split-brain, reference: plan_apply.go:62-78).
        if self.eval_broker is not None:
            token = self.eval_broker.outstanding(plan.EvalID)
            if token is None or (plan.EvalToken and token != plan.EvalToken):
                pending.respond(None, RuntimeError(
                    f"plan for evaluation {plan.EvalID} has stale token"))
                self.stats["rejected"] += 1
                return None
        born = getattr(plan, "_fed_born", None)
        if (born is not None and self.fed is not None
                and self.fed.reject_after_s > 0):
            # Follower-snapshot staleness backstop: a plan built against
            # a snapshot far past the dequeue-side bound (a wedged or
            # deliberately-pinned source) is rejected BEFORE verification
            # — the worker nacks, the broker redelivers the eval exactly
            # once, and the re-run places against a fresh snapshot.
            age = time.monotonic() - born
            if age > self.fed.reject_after_s:
                from nomad_tpu.federation import StaleSnapshotError

                metrics.incr_counter(("nomad", "federation",
                                      "stale_plans"))
                pending.respond(None, StaleSnapshotError(
                    f"plan for evaluation {plan.EvalID} built against a "
                    f"{age * 1e3:.0f}ms-old snapshot (bound "
                    f"{self.fed.reject_after_s * 1e3:.0f}ms)"))
                self.stats["rejected"] += 1
                return None
        try:
            with trace.resume(trace.linked("eval", plan.EvalID),
                              "plan.evaluate", eval=plan.EvalID,
                              overlapped=overlapped):
                with metrics.measure(("nomad", "plan", "evaluate"),
                                     cpu=True):
                    result = evaluate_plan(opt, plan, self._pool,
                                           nt=self._nt())
        # lint: allow(swallow, error is delivered to the plan's waiter)
        except Exception as e:  # verification error: reject the plan
            pending.respond(None, e)
            self.stats["rejected"] += 1
            return None
        if overlapped:
            self.stats["overlapped"] += 1
        return result

    def _apply_group(self, group: List[Tuple[PendingPlan, PlanResult]]
                     ) -> None:
        """Commit a verified group as ONE consensus entry, then answer every
        waiting worker. All plans of the group share the entry's index."""
        # Every plan's trace gets a plan.apply span covering the shared
        # commit (explicit spans: each belongs to its OWN trace); the first
        # live span doubles as the ambient context, so fsm/raft child
        # spans AND failpoint/retry events of the commit land on it.
        spans = [trace.start_from(trace.linked("eval", pending.plan.EvalID),
                                  "plan.apply", eval=pending.plan.EvalID,
                                  batch=len(group))
                 for pending, _ in group]
        primary = next((s for s in spans if s is not None), None)
        try:
            with (primary if primary is not None else trace.attach(None)):
                with metrics.measure(("nomad", "plan", "apply"), cpu=True):
                    if len(group) == 1:
                        pending, result = group[0]
                        index = self._apply(pending.plan, result)
                    else:
                        if failpoints.fire("plan.apply.commit") == "drop":
                            raise failpoints.FailpointError(
                                "plan.apply.commit")
                        _fire_preempt_commit(
                            p.plan for p, _ in group)
                        encoded = [_encode_result(pending.plan, result)
                                   for pending, result in group]
                        # Any columnar member upgrades the whole entry to
                        # the sweep-batch op (its Batch shape is a strict
                        # superset of AllocUpdate's); all-object entries
                        # keep the reference AllocUpdate type.
                        msg = (MessageType.ApplySweepBatch
                               if any(f for _, f in encoded)
                               else MessageType.AllocUpdate)
                        if msg is MessageType.ApplySweepBatch:
                            _fire_store_commit()
                        index = self.raft.apply(msg, {
                            "Batch": [e for e, _ in encoded],
                        })
            for span in spans:
                if span is not None:
                    span.finish()
            for pending, result in group:
                result.AllocIndex = index
                self.stats["applied"] += 1
                self._count_preempt(pending.plan, result)
                self._count_stops(result)
                pending.respond(result, None)
        # lint: allow(swallow, error is delivered to every plan's waiter)
        except Exception as e:
            self.stats["apply_failed"] += 1
            for span in spans:
                if span is not None:
                    span.finish(error=str(e))
            for pending, _ in group:
                pending.respond(None, e)

    def apply_one(self, pending: PendingPlan) -> None:
        """Synchronous single-plan path (tests / dev tools)."""
        opt = OptimisticSnapshot(self.raft.fsm.state.snapshot(),
                         nt=self._nt())
        result = self._verify(pending, opt, overlapped=False)
        if result is None:
            return
        if result.NodeUpdate or result.NodeAllocation:
            with trace.resume(trace.linked("eval", pending.plan.EvalID),
                              "plan.apply", eval=pending.plan.EvalID,
                              batch=1):
                result.AllocIndex = self._apply(pending.plan, result)
            self._count_preempt(pending.plan, result)
            self._count_stops(result)
        pending.respond(result, None)

    def _apply(self, plan: Plan, result: PlanResult) -> int:
        """Commit the verified subset through consensus
        (reference: plan_apply.go:122-164 applyPlan)."""
        # No drop semantics at a consensus commit: a triggered failpoint
        # always surfaces as a failed apply (workers nack + re-evaluate).
        if failpoints.fire("plan.apply.commit") == "drop":
            raise failpoints.FailpointError("plan.apply.commit")
        _fire_preempt_commit((plan,))
        element, is_sweep = _encode_result(plan, result)
        if is_sweep:
            _fire_store_commit()
            return self.raft.apply(MessageType.ApplySweepBatch,
                                   {"Batch": [element]})
        return self.raft.apply(MessageType.AllocUpdate, {
            "Job": plan.Job,
            "Alloc": _result_allocs(result),
        })
