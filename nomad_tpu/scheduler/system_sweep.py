"""Vectorized system-job sweep: fused feasibility, tensor diff, bulk emit.

A system evaluation places one allocation per feasible node — the most
TPU-shaped workload in the repo (one fused mask over the whole node axis,
no scan chain: each placement is pinned to its node, so no placement's
decision depends on another's winner). The exact path walks Python per
node: `diff_system_allocs` builds an AllocTuple + `Allocation(NodeID=...)`
per node, `_compute_placements` runs a per-pair select and materializes a
SelectedOption per node. At 10k nodes that is tens of thousands of object
constructions per evaluation before a single allocation exists.

This module computes the same decision as row math on the node tensor:

  existing  [name -> rows]   bitmap of rows already carrying the instance
  place     = eligible & feasible & ~existing      (per task-group instance)
  stop      = existing & (tainted | ~required)     (classified per alloc)
  update    = existing & version-changed           (exact in-place attempt)

and emits the placements as a columnar batch — shared per-task-group
task-resource templates, one shared metric snapshot, one shared resource
vector — plus a :class:`SweepBatch` descriptor (node-row indices + per-row
demand) that the plan applier verifies as ONE vectorized capacity check
per chunk instead of a per-node Python walk.

The exact per-node path survives in system_sched.py for network-ask
groups (port bitmaps are host state) and as the oracle for the
fixed-seed equivalence gate (tests/test_system_sweep_equivalence.py).

Semantics contract: bug-for-bug parity with the exact path on a quiesced
state — same stops (with the same descriptions), same placements, same
in-place updates, same FailedTGAllocs metrics. The node set derives from
the live tensor mirror rather than the snapshot's node walk; the mirror
is updated synchronously at state commit, so it is at least as fresh as
any snapshot and the plan applier's re-verification owns the outcome of
any in-flight divergence (the same contract the windowed service path
documents).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from nomad_tpu.resilience import failpoints
from nomad_tpu.structs import Allocation, Resources
from nomad_tpu.structs.structs import (
    AllocClientStatusPending,
    AllocDesiredStatusRun,
    AllocDesiredStatusStop,
    ColumnarPlacements,
    JobTypeBatch,
    generate_uuids,
    stamp_alloc,
)
from nomad_tpu.telemetry import metrics
from nomad_tpu.tensor import alloc_vec, resources_vec
from nomad_tpu.tensor.node_table import DIM_NAMES, RES_DIMS

from .util import (
    ALLOC_NODE_TAINTED,
    ALLOC_NOT_NEEDED,
    ALLOC_UPDATING,
    AllocTuple,
    attempt_inplace_updates,
    materialize_task_groups,
    tainted_nodes,
    task_group_constraints,
)


@dataclass
class SweepBatch:
    """Columnar descriptor of a system sweep's placements, attached to the
    plan as ``plan._sweep`` (an underscore attribute, like
    ``alloc._resvec_cache``, so RPC serialization never sees it — a remote
    applier simply falls back to the per-node verify).

    One entry per UNIQUE placed node row, in row order; ``delta`` is the
    summed demand of every alloc placed on that row (multi-instance task
    groups fold together). Only rows whose node has NO eviction in the
    same plan are included — eviction credit depends on verify-time
    snapshot state, which the per-node path owns. ``epoch``/``n_rows``
    pin the tensor generation: a row that changed identity between emit
    and verify invalidates the whole descriptor (the applier falls back,
    it never mis-verifies).

    The per-ALLOC columns (``alloc_ids``/``alloc_names``/``alloc_tg``,
    row-sorted, with ``starts`` giving each unique row's alloc range)
    carry the batch the rest of the way: the plan applier encodes an
    admitted sweep chunk as ONE ``ApplySweepBatch`` raft entry straight
    from these columns — ids + instance names + a frozen per-TG template
    — and the state store scatter-applies it without ever walking the
    plan's per-alloc objects."""

    rows: np.ndarray        # [U] int64, sorted unique node rows
    node_ids: List[str]     # [U] aligned node IDs
    delta: np.ndarray       # [U, RES_DIMS] float32 summed placed demand
    epoch: int              # nt.row_epoch at emit
    n_rows: int             # nt.n_rows at emit
    counts: np.ndarray = None       # [U] allocs folded into each row
    starts: np.ndarray = None       # [U+1] per-row alloc offsets
    alloc_ids: List[str] = None     # [K] row-sorted alloc UUIDs
    alloc_names: List[str] = None   # [K] instance names (job.tg[i])
    alloc_tg: List[int] = None      # [K] index into templates
    templates: List = None          # per-TG frozen template Allocations
    # Which emit path built the batch: "system" (tensor sweep) or
    # "service" (pipelined service window, stack.WindowCollect.build).
    # Carried through the raft entry into the SweepSegment so operators
    # can see which commit path a storm took (sched-stats `Store` block).
    kind: str = "system"

    def slice(self, lo: int, hi: int) -> "SweepBatch":
        """Chunk view for _submit_chunked: shares the backing arrays."""
        if self.starts is None:
            return SweepBatch(rows=self.rows[lo:hi],
                              node_ids=self.node_ids[lo:hi],
                              delta=self.delta[lo:hi],
                              epoch=self.epoch, n_rows=self.n_rows,
                              kind=self.kind)
        s, e = int(self.starts[lo]), int(self.starts[hi])
        return SweepBatch(rows=self.rows[lo:hi],
                          node_ids=self.node_ids[lo:hi],
                          delta=self.delta[lo:hi],
                          epoch=self.epoch, n_rows=self.n_rows,
                          counts=self.counts[lo:hi],
                          starts=self.starts[lo:hi + 1] - s,
                          alloc_ids=self.alloc_ids[s:e],
                          alloc_names=self.alloc_names[s:e],
                          alloc_tg=self.alloc_tg[s:e],
                          templates=self.templates, kind=self.kind)

    def wire(self) -> dict:
        """The ApplySweepBatch raft entry's `Sweep`: the columns as they
        stand, none copied or converted. Numeric columns stay ndarrays
        and string columns the lists the emit made; every consumer of
        the entry (FSM, digest, event builder) takes either form. An
        array becomes a list where a transport needs one and nowhere
        earlier: `RaftBackend.apply`'s msgpack encoding (templates are
        flattened there too, by to_dict). Per-alloc node ids are NOT
        shipped: they re-expand from (node_ids, counts) on a read."""
        return {
            "Kind": self.kind,
            "Templates": self.templates,
            "TGIdx": self.alloc_tg,
            "AllocIDs": self.alloc_ids,
            "Names": self.alloc_names,
            "RowNodeIDs": self.node_ids,
            "Counts": self.counts,
            "Rows": self.rows,
            "Delta": self.delta,
            "Epoch": self.epoch,
            "NRows": self.n_rows,
        }


# Escape hatch for the equivalence tests' oracle runs: True routes every
# system eval onto the exact per-node path regardless of applicability.
FORCE_EXACT = False


def sweep_applicable(job, tindex) -> bool:
    """The tensor-sweep path serves every system eval except: no job (a
    deregister's stop-all walk is O(allocs), not hot) and network asks
    anywhere in the job (port bitmaps are sequential host state — the
    exact per-node path is kept for those, reference: rank.go:150-240's
    network-check-the-winners-only shape)."""
    if FORCE_EXACT or job is None or tindex is None:
        return False
    for tg in job.TaskGroups:
        for task in tg.Tasks:
            if task.Resources is not None and task.Resources.Networks:
                return False
    return True


def compute_job_allocs(sched) -> None:
    """Vectorized body of SystemScheduler._compute_job_allocs for a
    sweep-applicable eval. Mutates sched.plan / sched.failed_tg_allocs
    exactly like the exact path; attaches the plan's SweepBatch. The
    caller guarantees sweep_applicable() and a stack wired via
    adopt_shared (in-place updates run through stack.select_on_node)."""
    t0 = time.monotonic()
    job = sched.job
    state = sched.state
    plan = sched.plan
    ctx = sched.ctx
    nt = sched.tindex.nt
    elig = sched.stack.inner.elig
    m = ctx.metrics

    allocs = [a for a in state.allocs_by_job(sched.eval.JobID)
              if not a.terminal_status()]
    tainted = tainted_nodes(state, allocs)
    required = materialize_task_groups(job)

    # ---- tensor diff: classify existing allocs (O(allocations of this
    # job), the one loop that inherently needs the alloc objects — stops
    # and updates carry them into the plan).
    row_of = nt.row_of
    has_name_rows: Dict[str, List[int]] = {name: [] for name in required}
    updates: List[AllocTuple] = []
    job_mod = job.JobModifyIndex
    for a in allocs:
        name = a.Name
        tg = required.get(name)
        if tg is not None:
            row = row_of.get(a.NodeID)
            if row is not None:
                # Every alloc of a required name marks the row existing —
                # including stopped/updated ones (a tainted stop is not
                # replaced on the same node; an update replaces in place
                # or via the destructive stop+place below).
                has_name_rows[name].append(row)
        if tg is None:
            desc = ALLOC_NODE_TAINTED if tainted.get(a.NodeID) \
                else ALLOC_NOT_NEEDED
            plan.append_update(a, AllocDesiredStatusStop, desc)
            continue
        if tainted.get(a.NodeID, False):
            # Finished batch work stays finished even on a tainted node;
            # system migrations are stops (diff_system_allocs).
            if (a.Job is not None and a.Job.Type == JobTypeBatch
                    and a.ran_successfully()):
                continue
            plan.append_update(a, AllocDesiredStatusStop, ALLOC_NODE_TAINTED)
            continue
        if a.Job is not None and job_mod != a.Job.JobModifyIndex:
            updates.append(AllocTuple(name, tg, a))
        # else: ignore

    # In-place first (non-destructive changes keep the running alloc);
    # the rest stop + replace on the same node (exact per-alloc path —
    # updates are O(existing allocs) and need object-level TG diffs).
    destructive: List[AllocTuple] = []
    if updates:
        destructive, _ = attempt_inplace_updates(
            state, plan, sched.stack.inner, sched.eval.ID, ctx, updates)
        for tup in destructive:
            plan.append_update(tup.Alloc, AllocDesiredStatusStop,
                               ALLOC_UPDATING)

    # ---- fused eligibility: ready & DC membership as one row mask (the
    # sweep's replacement for the ready_nodes_in_dcs state walk).
    dcs = job.Datacenters
    dc_ids = [nt.dc_vocab[d] for d in dcs if d in nt.dc_vocab]
    elig_mask = nt.eligibility_mask(dc_ids, None)

    # One consistent usage snapshot for the whole sweep (alloc commits
    # mutate rows in place; same torn-row hazard snapshot_rows documents).
    with nt._lock:
        usage0 = nt.usage.astype(np.float64, copy=True)
        capacity = nt.capacity.astype(np.float64, copy=True)
    n_snap = usage0.shape[0]
    job_mask, _, _ = elig.job_mask(job.ID, job.Constraints)
    # The table can GROW mid-eval (a node registering crosses a
    # power-of-two boundary), so arrays snapshotted at different moments
    # may disagree on length. Row indices are stable across growth, so
    # clamping to the shortest view just defers newly-grown rows to the
    # next eval (which sees a fresh node_version) — stale-but-safe, never
    # an out-of-bounds gather.
    n0 = min(len(elig_mask), n_snap, len(job_mask))
    if len(elig_mask) > n0:
        elig_mask = elig_mask[:n0]

    # Destructive replacements re-place on their own node even though the
    # name is still "existing" there; dropped when the node is no longer
    # eligible (the exact path's node_by_id miss).
    destructive_rows: Dict[str, List[int]] = {}
    for tup in destructive:
        row = row_of.get(tup.Alloc.NodeID)
        if row is not None and row < n0 and elig_mask[row]:
            destructive_rows.setdefault(tup.Name, []).append(row)

    metrics.measure_since(("nomad", "sched", "system", "diff"), t0)

    # ---- per-TG fused feasibility + bulk emit.
    # No drop semantics at the emit seam: a triggered failpoint always
    # surfaces as a failed sweep (the worker nacks; the broker redelivers
    # the eval exactly once — nothing was submitted).
    if failpoints.fire("sched.system.emit") == "drop":
        raise failpoints.FailpointError("sched.system.emit")
    t1 = time.monotonic()

    # In-plan deltas, whole-table: stops subtract, placements (in-place
    # updates so far, then each TG's winners) add — the batched mirror of
    # select_on_node's per-node plan walk.
    eff_delta = np.zeros((n_snap, RES_DIMS), dtype=np.float64)
    for nid, ups in plan.NodeUpdate.items():
        row = row_of.get(nid)
        if row is None or row >= n_snap:
            continue
        for u in ups:
            full = state.alloc_by_id(u.ID) or u
            eff_delta[row] -= alloc_vec(full)
    for nid, placed in plan.NodeAllocation.items():
        row = row_of.get(nid)
        if row is None or row >= n_snap:
            continue
        for a in placed:
            eff_delta[row] += alloc_vec(a)

    # Group instance names by task group, preserving job.TaskGroups order
    # (the exact path's by_tg first-appearance order).
    by_tg: Dict[str, List[str]] = {}
    tg_obj: Dict[str, object] = {}
    for name, tg in required.items():
        by_tg.setdefault(tg.Name, []).append(name)
        tg_obj[tg.Name] = tg

    any_candidates = bool(destructive_rows) or elig_mask.any()
    if any_candidates and required:
        # NodesAvailable: ready-node count per asked datacenter (the
        # ready_nodes_in_dcs dc_map, computed as one reduction per DC).
        node_by_dc = {dc: 0 for dc in dcs}
        for dc in dcs:
            did = nt.dc_vocab.get(dc)
            if did is not None:
                node_by_dc[dc] = int((nt.ready & (nt.dc_ids == did)).sum())
        m.NodesAvailable = node_by_dc

    node_id_arr = nt.node_id_array()
    nodes_by_row = elig.nodes_by_row
    # One run per (instance name, rows it lands on), in emission order:
    # job.TaskGroups order, then instance order within a group.
    runs: List[tuple] = []  # (name, rows int64 ndarray, template index)
    sweep_templates: List[Allocation] = []

    for tg_name, names in by_tg.items():
        tg = tg_obj[tg_name]
        cons = task_group_constraints(tg)
        tg_mask, _, _ = elig.tg_mask(job.ID, tg.Name, cons.constraints,
                                     cons.drivers)
        # A cached TG mask may predate a table grow; clamp this group's
        # candidate space to the shortest consistent view (see n0 above).
        n_eff = min(n0, len(tg_mask))
        em = elig_mask if n_eff == n0 else elig_mask[:n_eff]
        demand = resources_vec(cons.size).astype(np.float64)
        # Per-dimension exhaustion over the whole axis, float64 like the
        # exact path's fit_lacking; instances of one TG check the same
        # usage (the exact path computes all of a TG's options before
        # appending its allocs), while the NEXT TG sees this one's.
        lacking = (capacity - (usage0 + eff_delta)) < demand[None, :]
        fits = ~lacking.any(axis=1)

        placed_per_name: List[tuple] = []  # (name, ok_rows ndarray)
        n_failed = 0
        for name in names:
            extra = [r for r in destructive_rows.get(name, ()) if r < n_eff]
            named = has_name_rows[name]
            if named or extra:
                cand_mask = em.copy()
                if named:
                    named_arr = np.asarray(named, dtype=np.int64)
                    cand_mask[named_arr[named_arr < n_eff]] = False
                rows = np.flatnonzero(cand_mask)
                if extra:
                    rows = np.concatenate(
                        [rows, np.asarray(extra, dtype=np.int64)])
            else:
                rows = np.flatnonzero(em)
            if not len(rows):
                continue
            # Metrics: the exact counters select_batch_on_nodes
            # accumulates over this instance's candidate pairs.
            m.NodesEvaluated += len(rows)
            job_ok = job_mask[rows]
            tg_ok = tg_mask[rows]
            for sel, label in ((~job_ok, "job constraints"),
                               ((job_ok & ~tg_ok), "group constraints")):
                if sel.any():
                    for r in rows[sel].tolist():
                        m.filter_node(nodes_by_row.get(r), label)
            eligible = job_ok & tg_ok
            ok = eligible & fits[rows]
            exhausted = eligible & ~fits[rows]
            n_ex = int(exhausted.sum())
            if n_ex:
                m.NodesExhausted += n_ex
                per_dim = (lacking[rows] & exhausted[:, None]).sum(axis=0)
                for d, count in enumerate(per_dim.tolist()):
                    if count:
                        dim = DIM_NAMES[d]
                        m.DimensionExhausted[dim] = (
                            m.DimensionExhausted.get(dim, 0) + count)
            ok_rows = rows[ok]
            n_failed += len(rows) - len(ok_rows)
            if len(ok_rows):
                placed_per_name.append((name, ok_rows))

        if n_failed:
            metric = sched.failed_tg_allocs.get(tg.Name)
            if metric is None:
                metric = sched.failed_tg_allocs[tg.Name] = m.copy()
                n_failed -= 1
            metric.CoalescedFailures += n_failed
        if not placed_per_name:
            continue

        # One frozen task-resources template + one metric snapshot + one
        # resource vector shared by every alloc of the TG (the
        # value-frozen contract is alloc._resvec_cache's).
        tr_template: Dict[str, Resources] = {}
        shared_vec = np.zeros(RES_DIMS, dtype=np.float32)
        for task in tg.Tasks:
            r = (task.Resources.copy() if task.Resources is not None
                 else Resources())
            tr_template[task.Name] = r
            shared_vec += resources_vec(r)
        template = Allocation(
            EvalID=sched.eval.ID,
            JobID=job.ID,
            TaskGroup=tg.Name,
            Metrics=m.copy(),
            TaskResources=tr_template,
            DesiredStatus=AllocDesiredStatusRun,
            ClientStatus=AllocClientStatusPending,
        )
        template._resvec_cache = shared_vec
        tpl_idx = len(sweep_templates)
        sweep_templates.append(template)
        for name, ok_rows in placed_per_name:
            # A row freed mid-sweep has no node: the exact path skips it.
            ok_rows = ok_rows[node_id_arr[ok_rows] != None]  # noqa: E711
            if len(ok_rows):
                runs.append((name, ok_rows.astype(np.int64, copy=False),
                             tpl_idx))
                # The next TG's fit sees this one's placements.
                np.add.at(eff_delta, ok_rows, shared_vec.astype(np.float64))

    if runs:
        _emit(plan, nt, node_id_arr, runs, sweep_templates)
    metrics.measure_since(("nomad", "sched", "system", "emit"), t1)


def _emit(plan, nt, node_id_arr, runs, templates) -> None:
    """The sweep's placements as the plan's SweepBatch: unique placed rows
    with their summed demand, and the per-alloc columns sorted into row
    order so a node-range chunk slice maps to a contiguous alloc range
    (starts). Where the plan held nothing before the emit (a fresh
    register: no stops, no in-place updates) the batch covers every
    placed node and the placements exist as those columns only; any other
    plan also gets them as objects, and the batch keeps only the rows it
    fully describes."""
    lens = [len(rows) for _, rows, _ in runs]
    rows_all = np.concatenate([rows for _, rows, _ in runs])
    ur, inv = np.unique(rows_all, return_inverse=True)
    delta = np.zeros((len(ur), RES_DIMS), dtype=np.float32)
    np.add.at(delta, inv, np.repeat(
        np.stack([templates[t]._resvec_cache for _, _, t in runs]),
        lens, axis=0))
    counts = np.bincount(inv, minlength=len(ur))
    order = np.argsort(rows_all, kind="stable")
    names = np.repeat(np.array([n for n, _, _ in runs], dtype=object), lens)
    tg = np.repeat(np.array([t for _, _, t in runs], dtype=np.int64), lens)
    node_ids = node_id_arr[ur].tolist()
    n = len(rows_all)

    alloc_ids = generate_uuids(n)  # random: any order is row order
    as_columns = not plan.NodeUpdate and not plan.NodeAllocation
    if not as_columns:
        tpl = [t.__dict__ for t in templates]
        for alloc_id, name, t, nid in zip(
                alloc_ids, names.tolist(), tg.tolist(),
                node_id_arr[rows_all].tolist()):
            plan.append_alloc(stamp_alloc(tpl[t], alloc_id, name, nid))
        # Descriptor coverage: only rows whose plan state the delta FULLY
        # describes. Rows with stops stay on the per-node verify path
        # (eviction credit is verify-time snapshot state), as do rows
        # whose NodeAllocation carries allocs the sweep didn't emit —
        # in-place updates on a node that also received a fresh instance
        # need the exact remove-then-add accounting.
        keep = np.asarray(
            [nid not in plan.NodeUpdate
             and len(plan.NodeAllocation[nid]) == counts[k]
             for k, nid in enumerate(node_ids)], dtype=bool)
        keep_alloc = keep[inv][order]
        order = order[keep_alloc]
        ur, delta, counts = ur[keep], delta[keep], counts[keep]
        node_ids = [nid for nid, k in zip(node_ids, keep.tolist()) if k]
        alloc_ids = np.asarray(alloc_ids, dtype=object)[order].tolist()
    names, tg = names[order], tg[order]
    starts = np.concatenate(
        [np.zeros(1, dtype=np.int64), np.cumsum(counts, dtype=np.int64)])
    plan._sweep = SweepBatch(rows=ur, node_ids=node_ids, delta=delta,
                             epoch=nt.row_epoch, n_rows=nt.n_rows,
                             counts=counts, starts=starts,
                             alloc_ids=alloc_ids,
                             alloc_names=names.tolist(),
                             alloc_tg=tg.tolist(), templates=templates)
    if as_columns:
        plan.NodeAllocation = ColumnarPlacements.over(plan._sweep)
    metrics.incr_counter(("nomad", "sched", "system", "placed"), n)
