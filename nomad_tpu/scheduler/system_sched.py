"""SystemScheduler: one allocation per eligible node (reference:
scheduler/system_sched.go).

System placement is per-specific-node (the diff pins each placement to its
node), so no scan chain is needed: the whole evaluation is one fused
feasibility/diff mask over the node axis plus a bulk columnar emit
(system_sweep.py). The exact per-node path below — class-memoized
constraint checks plus a numpy fit per pinned node — survives for
network-ask groups (port bitmaps are host state), deregisters, and as the
oracle side of the fixed-seed sweep-equivalence gate.
"""

from __future__ import annotations

import logging
import random
from typing import Dict, List, Optional

import numpy as np

from nomad_tpu.telemetry import metrics
from nomad_tpu.structs import (
    Allocation,
    AllocMetric,
    ColumnarPlacements,
    Evaluation,
    Job,
    Plan,
    PlanResult,
    generate_uuid,
)
from nomad_tpu.structs.structs import (
    AllocClientStatusPending,
    AllocDesiredStatusRun,
    AllocDesiredStatusStop,
    EvalStatusComplete,
    EvalStatusFailed,
    EvalTriggerJobDeregister,
    EvalTriggerJobRegister,
    EvalTriggerNodeUpdate,
    columns_only,
    placed_count,
)
from nomad_tpu.tensor import TensorIndex, alloc_vec

from . import system_sweep
from .context import EvalContext
from .scheduler import Planner, SetStatusError, State
from .stack import SystemStack
from .util import (
    ALLOC_NODE_TAINTED,
    ALLOC_NOT_NEEDED,
    ALLOC_UPDATING,
    attempt_inplace_updates,
    diff_system_allocs,
    progress_made,
    ready_nodes_in_dcs,
    retry_max,
    set_status,
    tainted_nodes,
)

MAX_SYSTEM_SCHEDULE_ATTEMPTS = 5

# A 10k-node system sweep produces one monolithic plan whose verify+apply
# monopolizes the applier for hundreds of ms. Chunking streams it through
# the plan queue so verify(i+1) overlaps apply(i) and other evals' plans
# interleave between chunks (reference anchor: plan_apply.go:41-119's
# verify/apply overlap; the reference commits system sweeps whole, which
# is exactly the latency cliff this avoids).
SYSTEM_PLAN_CHUNK = 2048

_HANDLED = (EvalTriggerJobRegister, EvalTriggerNodeUpdate,
            EvalTriggerJobDeregister)


class SystemScheduler:
    def __init__(self, state: State, planner: Planner,
                 tindex: Optional[TensorIndex], logger: logging.Logger,
                 rng: Optional[random.Random] = None,
                 vectorized: bool = True):
        self.state = state
        self.planner = planner
        self.tindex = tindex
        self.logger = logger
        self.rng = rng or random.Random()
        # Tensor-sweep path switch; False forces the exact per-node path
        # (the equivalence gate's oracle side).
        self.vectorized = vectorized

        self.eval: Optional[Evaluation] = None
        self.job: Optional[Job] = None
        self.plan: Optional[Plan] = None
        self.plan_result: Optional[PlanResult] = None
        self.ctx: Optional[EvalContext] = None
        self.stack: Optional[SystemStack] = None
        self.failed_tg_allocs: Dict[str, AllocMetric] = {}
        self.nodes = []
        self.node_by_dc: Dict[str, int] = {}
        # Memoized ready_nodes_in_dcs result: (state, dcs, node_version,
        # (nodes, dc_map)). Holding the state reference keeps identity
        # comparison sound (no id() reuse).
        self._ready_cache: Optional[tuple] = None

    def process(self, eval: Evaluation) -> None:
        """(reference: system_sched.go:54-102)"""
        self.eval = eval
        if eval.TriggeredBy not in _HANDLED:
            set_status(self.planner, eval, None, None, self.failed_tg_allocs,
                       EvalStatusFailed,
                       f"scheduler cannot handle '{eval.TriggeredBy}' evaluation reason")
            return
        try:
            retry_max(MAX_SYSTEM_SCHEDULE_ATTEMPTS, self._process,
                      lambda: progress_made(self.plan_result))
        except SetStatusError as e:
            set_status(self.planner, eval, None, None, self.failed_tg_allocs,
                       e.eval_status, str(e))
            return
        set_status(self.planner, eval, None, None, self.failed_tg_allocs,
                   EvalStatusComplete, "")

    def _process(self) -> bool:
        """(reference: system_sched.go:105-162)"""
        self.job = self.state.job_by_id(self.eval.JobID)
        self.plan = self.eval.make_plan(self.job)
        self.failed_tg_allocs = {}
        self.ctx = EvalContext(self.state, self.plan, self.logger)
        if self.tindex is None:
            self.tindex = TensorIndex.from_state(self.state)
        self.stack = SystemStack(self.ctx, self.tindex)
        use_sweep = (self.vectorized
                     and system_sweep.sweep_applicable(self.job, self.tindex))
        if self.job is not None:
            if use_sweep:
                # Tensor-sweep wiring: the shared table-wide eligibility
                # replaces set_nodes/set_job's O(cluster) walk; the node
                # set IS the tensor's ready/DC mask.
                self.stack.adopt_shared(
                    self.job, self.tindex.shared_elig(self.state))
            else:
                self.nodes, self.node_by_dc = self._ready_nodes(
                    self.job.Datacenters)
                self.stack.set_nodes(self.nodes)
                self.stack.set_job(self.job)

        self._compute_job_allocs(use_sweep)

        if self.plan.is_no_op():
            return True

        result, new_state = self._submit_chunked(self.plan)
        self.plan_result = result
        if use_sweep:
            # Did any reader on the way need the placements as objects?
            columnar = columns_only(self.plan.NodeAllocation) and (
                result is None or columns_only(result.NodeAllocation))
            metrics.incr_counter(("nomad", "sched", "system",
                                  "plans_columnar" if columnar
                                  else "plans_objects"))
        if new_state is not None:
            self.state = new_state
            if self.tindex is not None and not self.tindex.attached:
                self.tindex = None
            return False
        if result is None:
            # Planner declined (e.g. a cancelled chunk after a wait
            # failure): count as a no-progress attempt, don't deref.
            return False
        full_commit, expected, actual = result.full_commit(self.plan)
        if not full_commit:
            self.logger.debug("eval %s: attempted %d placements, %d placed",
                              self.eval.ID, expected, actual)
            return False
        return True

    def _submit_chunked(self, plan: Plan):
        """Submit the sweep's plan in SYSTEM_PLAN_CHUNK-alloc chunks (node
        boundaries preserved; each node's evictions ride the same chunk as
        its placements) and merge the results. Chunking exists for
        FAIRNESS: with other plans contending for the applier, a 10k-alloc
        sweep would otherwise monopolize it for hundreds of ms while
        interactive evals queue behind it. With an empty queue the
        monolithic submit is strictly cheaper (chunk verify/apply overhead
        buys nothing without contention), so small plans and uncontended
        sweeps take the ordinary path — as do AllAtOnce plans, whose
        all-or-nothing contract the applier enforces per plan and which
        chunking would silently weaken to per-chunk."""
        n_allocs = placed_count(plan.NodeAllocation)
        depth_fn = getattr(self.planner, "plan_queue_depth", None)
        contended = depth_fn is not None and depth_fn() > 0
        if n_allocs <= SYSTEM_PLAN_CHUNK or not contended \
                or plan.AllAtOnce:
            return self.planner.submit_plan(plan)

        if columns_only(plan.NodeAllocation):
            # Columnar chunking: the sweep descriptor lists every placed
            # node in row order with its count, so chunks are slices of
            # it, each with its own columns-only placements and the
            # slice the applier's one-vector-op verify reads.
            sweep = plan._sweep
            starts = sweep.starts
            chunks = []
            i, total = 0, len(sweep.node_ids)
            while i < total:
                j = min(int(np.searchsorted(
                    starts, starts[i] + SYSTEM_PLAN_CHUNK)), total)
                chunk = Plan(EvalID=plan.EvalID, Priority=plan.Priority,
                             Job=plan.Job, AllAtOnce=plan.AllAtOnce)
                chunk._sweep = sweep.slice(i, j)
                chunk.NodeAllocation = ColumnarPlacements.over(chunk._sweep)
                chunks.append(chunk)
                i = j
            chunks[0].Annotations = plan.Annotations
            return self._submit_chunks(chunks, plan)

        chunks: List[Plan] = []
        current = None
        count = 0
        # Each node's evictions travel WITH its placements so the per-node
        # remove-then-add stays atomic in one chunk's verify — an eviction
        # stranded in an earlier chunk would double-count capacity against
        # the replacement under the one-sided optimistic overlay and force
        # spurious partial commits on tight nodes. Evict-only nodes fill
        # chunks like placements do (they count toward the budget, so a
        # fleet-wide destructive update cannot recreate the monolithic
        # plan as "chunk 0").
        node_ids = list(dict.fromkeys(
            list(plan.NodeAllocation) + list(plan.NodeUpdate)))
        for node_id in node_ids:
            if current is None or count >= SYSTEM_PLAN_CHUNK:
                current = Plan(EvalID=plan.EvalID, Priority=plan.Priority,
                               Job=plan.Job, AllAtOnce=plan.AllAtOnce)
                chunks.append(current)
                count = 0
            placed = plan.NodeAllocation.get(node_id)
            if placed:
                current.NodeAllocation[node_id] = placed
                count += len(placed)
            updates = plan.NodeUpdate.get(node_id)
            if updates:
                current.NodeUpdate[node_id] = updates
                count += len(updates)
        chunks[0].Annotations = plan.Annotations
        return self._submit_chunks(chunks, plan)

    def _submit_chunks(self, chunks: List[Plan], plan: Plan):
        """Submit a chunk sequence through the pipelined planner seam and
        merge the per-chunk results. Chunks of a columns-only plan that
        were all admitted as columns merge into the plan's own columns:
        no placement is read as an object to be copied."""
        submit = getattr(self.planner, "submit_plans", None)
        if submit is not None:
            results, new_state = submit(chunks)
        else:  # harness planners: sequential fallback
            results = []
            new_state = None
            for chunk in chunks:
                r, ns = self.planner.submit_plan(chunk)
                results.append(r)
                new_state = ns or new_state

        if None in results:
            return None, new_state  # _process treats None as a retry
        merged = PlanResult()
        for r in results:
            merged.NodeUpdate.update(r.NodeUpdate)
            merged.RefreshIndex = max(merged.RefreshIndex, r.RefreshIndex)
            merged.AllocIndex = max(merged.AllocIndex, r.AllocIndex)
        if columns_only(plan.NodeAllocation) and all(
                columns_only(r.NodeAllocation) for r in results):
            merged.NodeAllocation = plan.NodeAllocation.copy()
        else:
            for r in results:
                merged.NodeAllocation.update(r.NodeAllocation)
        return merged, new_state

    def _ready_nodes(self, dcs) -> tuple:
        """ready_nodes_in_dcs, memoized per (state snapshot, DC list, node
        population): the retry loop re-runs _process up to retry_max times
        per eval, and each attempt re-walked every node in state — twice
        the O(cluster) cost for zero new information. The tensor's
        node_version invalidates the memo when the population actually
        moves (covers live-store harnesses, where the state object is
        mutable); only an attached index sees those moves, so unattached
        ones skip the memo."""
        if self.tindex is None or not self.tindex.attached:
            return ready_nodes_in_dcs(self.state, dcs)
        ver = self.tindex.nt.node_version
        key = (self.state, tuple(dcs), ver)
        cached = self._ready_cache
        if cached is not None and cached[0] is key[0] \
                and cached[1] == key[1] and cached[2] == key[2]:
            return cached[3]
        res = ready_nodes_in_dcs(self.state, dcs)
        self._ready_cache = key + (res,)
        return res

    def _compute_job_allocs(self, use_sweep: bool = False) -> None:
        """(reference: system_sched.go:165-216). The tensor-sweep path
        (system_sweep.compute_job_allocs) computes the same diff + emit as
        row math over the node tensor; the exact per-node path below is
        kept for network-ask groups, deregisters, and as the equivalence
        oracle."""
        if use_sweep:
            with metrics.measure(("nomad", "sched", "system", "sweep"),
                                 cpu=True):
                system_sweep.compute_job_allocs(self)
            metrics.incr_counter(("nomad", "sched", "system", "fast"))
            return
        metrics.incr_counter(("nomad", "sched", "system", "exact"))
        allocs = self.state.allocs_by_job(self.eval.JobID)
        allocs = [a for a in allocs if not a.terminal_status()]
        tainted = tainted_nodes(self.state, allocs)
        diff = diff_system_allocs(self.job, self.nodes, tainted, allocs) \
            if self.job is not None else None
        if diff is None:
            for a in allocs:
                self.plan.append_update(a, AllocDesiredStatusStop,
                                        ALLOC_NOT_NEEDED)
            return

        for tup in diff.stop:
            desc = ALLOC_NODE_TAINTED if tainted.get(tup.Alloc.NodeID) \
                else ALLOC_NOT_NEEDED
            self.plan.append_update(tup.Alloc, AllocDesiredStatusStop, desc)
        # In-place first (non-destructive changes keep the running alloc,
        # reference: system_sched.go computeJobAllocs -> inplaceUpdate);
        # the rest stop + replace on the same node.
        destructive, _ = attempt_inplace_updates(
            self.state, self.plan, self.stack.inner, self.eval.ID, self.ctx,
            diff.update)
        for tup in destructive:
            self.plan.append_update(tup.Alloc, AllocDesiredStatusStop,
                                    ALLOC_UPDATING)
            diff.place.append(tup)

        if not diff.place:
            return
        self._compute_placements(diff.place)

    def _compute_placements(self, place) -> None:
        """(reference: system_sched.go:219-281). Placements group by task
        group and run through the vectorized pinned-node batch select — a
        10k-node system sweep is a few numpy ops, not 10k constraint walks.
        Groups with network asks keep the exact per-node path (port bitmaps
        are host state)."""
        node_by_id = {n.ID: n for n in self.nodes}
        self.ctx.metrics.NodesAvailable = self.node_by_dc

        by_tg: Dict[str, List] = {}
        for tup in place:
            node = node_by_id.get(tup.Alloc.NodeID if tup.Alloc else "")
            if node is None:
                continue
            by_tg.setdefault(tup.TaskGroup.Name, []).append((tup, node))

        for pairs in by_tg.values():
            tg = pairs[0][0].TaskGroup
            options = self.stack.select_batch_on_nodes(
                tg, [node for _, node in pairs])
            if options is None:  # network asks: exact per-node path
                options = [self.stack.select(tup.TaskGroup, node)
                           for tup, node in pairs]
            # One shared metrics snapshot per TG (scoring is done by now;
            # a copy per alloc walks the metric maps P times — the same
            # O(P^2) the generic path's build_placement_allocs avoids).
            # The resource vector is likewise identical for every alloc of
            # a TG: computing it once and pre-seeding the per-instance
            # memo saves a resources_vec walk per alloc in the plan
            # applier, the usage listener, and the optimistic overlay
            # (the memo contract forbids mutation, so sharing is safe).
            shared_metric = None
            shared_vec = None
            for (tup, node), option in zip(pairs, options):
                if option is None:
                    metric = self.failed_tg_allocs.get(tup.TaskGroup.Name)
                    if metric is not None:
                        metric.CoalescedFailures += 1
                    else:
                        self.failed_tg_allocs[tup.TaskGroup.Name] = \
                            self.ctx.metrics.copy()
                    continue
                if shared_metric is None:
                    shared_metric = self.ctx.metrics.copy()
                alloc = Allocation(
                    ID=generate_uuid(),
                    EvalID=self.eval.ID,
                    Name=tup.Name,
                    JobID=self.job.ID,
                    TaskGroup=tup.TaskGroup.Name,
                    Metrics=shared_metric,
                    NodeID=node.ID,
                    TaskResources=option.task_resources,
                    DesiredStatus=AllocDesiredStatusRun,
                    ClientStatus=AllocClientStatusPending,
                )
                if shared_vec is None:
                    shared_vec = alloc_vec(alloc)
                else:
                    alloc._resvec_cache = shared_vec
                self.plan.append_alloc(alloc)
