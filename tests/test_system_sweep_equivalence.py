"""Fixed-seed exact-vs-vectorized system sweep equivalence.

The tensor-sweep path (scheduler/system_sweep.py) must produce the SAME
scheduling decision as the exact per-node path it replaced: same stops
with the same descriptions, same placements (node, instance name, task
group, resource values), same in-place updates, same FailedTGAllocs
metrics — across tainted nodes, partially-allocated fleets, destructive
and in-place updates, and infeasible nodes. Network-ask groups must
route onto the exact path on BOTH sides (port bitmaps are host state),
and duplicate node entries must not double-place (the diff's `emitted`
guard, structural in the tensor path).

Both paths run against the SAME store through a capture-only planner
(nothing commits), so the comparison is a pure function of the fixed
seed state.
"""

import copy
import json
import logging
import os
import random

import numpy as np
import pytest

from benchmark.deploy.dev_agent import build_fleet
from nomad_tpu import mock
from nomad_tpu.scheduler import system_sched, system_sweep
from nomad_tpu.scheduler.system_sched import SystemScheduler
from nomad_tpu.scheduler.util import diff_system_allocs, tainted_nodes
from nomad_tpu.state.state_store import StateStore
from nomad_tpu.structs import (
    Allocation,
    Constraint,
    PlanResult,
    columns_only,
    compute_node_class,
    placed_count,
)
from nomad_tpu.structs.structs import (
    EvalStatusComplete,
    EvalStatusPending,
    EvalTriggerJobRegister,
    EvalTriggerNodeUpdate,
)
from nomad_tpu.telemetry import metrics
from nomad_tpu.tensor import TensorIndex, alloc_vec

logger = logging.getLogger("test.sweep")


class CapturePlanner:
    """Planner that records plans and echoes full commits WITHOUT touching
    the store — both paths then schedule against identical state. The
    verdict's placements are a copy of the plan's, as the applier's
    wholesale admit makes it: columns while nobody asked for objects."""

    def __init__(self):
        self.plans = []
        self.evals = []

    def plan_queue_depth(self):
        return 0

    def submit_plan(self, plan):
        self.plans.append(plan)
        r = PlanResult()
        r.NodeUpdate = dict(plan.NodeUpdate)
        r.NodeAllocation = plan.NodeAllocation.copy()
        r.AllocIndex = 1
        return r, None

    def update_eval(self, ev):
        self.evals.append(ev)

    def create_eval(self, ev):
        self.evals.append(ev)

    def reblock_eval(self, ev):
        self.evals.append(ev)


class CopyPlanner(CapturePlanner):
    """CapturePlanner that keeps its verdicts and reports a queue depth:
    above 0 the system scheduler chunks."""

    def __init__(self, depth=0):
        super().__init__()
        self.depth = depth
        self.results = []

    def plan_queue_depth(self):
        return self.depth

    def submit_plan(self, plan):
        self.plans.append(plan)
        r = PlanResult(NodeUpdate=dict(plan.NodeUpdate),
                       NodeAllocation=plan.NodeAllocation.copy(),
                       AllocIndex=1)
        self.results.append(r)
        return r, None

    def submit_plans(self, plans):
        return [self.submit_plan(p)[0] for p in plans], None


def make_node(i, cpu=4000, dc="dc1"):
    n = mock.node()
    n.ID = f"node-{i:04d}"
    n.Name = f"node-{i:04d}"
    n.Datacenter = dc
    n.Resources.CPU = cpu
    compute_node_class(n)
    return n


def sys_job(job_id="sysjob", cpu=100, networks=False):
    job = mock.system_job()
    job.ID = job_id
    job.Name = job_id
    t = job.TaskGroups[0].Tasks[0]
    t.Resources.CPU = cpu
    t.Resources.MemoryMB = 32
    t.Resources.DiskMB = 150
    if not networks:
        t.Resources.Networks = []
    t.Services = []
    job.init_fields()
    return job


def make_eval(job, trigger=EvalTriggerJobRegister):
    ev = mock.eval()
    ev.JobID = job.ID
    ev.Type = job.Type
    ev.TriggeredBy = trigger
    ev.Status = EvalStatusPending
    return ev


def run_path(store, tindex, job, vectorized, trigger=EvalTriggerJobRegister):
    planner = CapturePlanner()
    sched = SystemScheduler(store, planner, tindex, logger,
                            rng=random.Random(7), vectorized=vectorized)
    sched.process(make_eval(job, trigger))
    return planner, sched


def summarize(planner):
    placed = sorted(
        (a.NodeID, a.Name, a.TaskGroup, a.DesiredStatus,
         tuple(alloc_vec(a).tolist()))
        for p in planner.plans for v in p.NodeAllocation.values()
        for a in v)
    stops = sorted(
        (a.ID, a.DesiredStatus, a.DesiredDescription)
        for p in planner.plans for v in p.NodeUpdate.values() for a in v)
    return placed, stops


def failed_metrics(planner):
    out = {}
    for ev in planner.evals:
        for name, m in (ev.FailedTGAllocs or {}).items():
            out[name] = (m.NodesEvaluated, m.NodesFiltered,
                         m.NodesExhausted, m.CoalescedFailures,
                         dict(m.DimensionExhausted))
    return out


def assert_equivalent(store, tindex, job, trigger=EvalTriggerJobRegister):
    pv, sv = run_path(store, tindex, job, True, trigger)
    pe, se = run_path(store, tindex, job, False, trigger)
    # Read before summarize builds them: which plans stayed columns.
    pv.columns = [columns_only(p.NodeAllocation) for p in pv.plans]
    assert not any(columns_only(p.NodeAllocation) for p in pe.plans)
    assert summarize(pv) == summarize(pe)
    assert failed_metrics(pv) == failed_metrics(pe)
    return pv, pe


def assert_descriptor_types(sweep):
    """The SweepBatch's fields and dtypes, whichever path emitted it."""
    assert sweep.kind == "system"
    assert sweep.rows.dtype == np.int64 and sweep.rows.ndim == 1
    assert sweep.delta.dtype == np.float32
    assert sweep.counts.dtype == np.int64 and sweep.starts.dtype == np.int64
    assert len(sweep.starts) == len(sweep.rows) + 1
    assert sweep.starts[-1] == sweep.counts.sum() == len(sweep.alloc_ids)
    assert np.all(np.diff(sweep.rows) > 0)  # unique, in row order
    assert type(sweep.node_ids) is list
    for column, kind in ((sweep.alloc_ids, str), (sweep.alloc_names, str),
                         (sweep.alloc_tg, int)):
        assert type(column) is list
        assert all(type(v) is kind for v in column)
    assert len(set(sweep.alloc_ids)) == len(sweep.alloc_ids)
    assert all(type(t) is Allocation for t in sweep.templates)


with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "sys-10k.json")) as _f:
    SYS_FLEET = json.load(_f)["fleet"]


def fleet_store(fleet):
    """A fresh system job over a 24-node fleet, or over 2,000 nodes of
    sys-10k's template with its arm64 racks (refused here by a job
    constraint), its racks without driver.exec, and its never-ready
    nodes."""
    store = StateStore()
    tindex = TensorIndex.attach(store)
    job = sys_job(cpu=20)
    if fleet == "24 nodes":
        nodes = [make_node(i) for i in range(24)]
    else:
        nodes = build_fleet(SYS_FLEET, 2000, random.Random(40))
        job.Constraints.append(Constraint(
            LTarget="${attr.arch}", RTarget="x86", Operand="="))
    for idx, node in enumerate(nodes, 1):
        compute_node_class(node)
        store.upsert_node(idx, node)
    store.upsert_job(len(nodes) + 1, job)
    return store, tindex, job


def column_rows(plans):
    """(node, name, template index) a placement, over the plans' columns
    in order, and the ids."""
    rows, ids = [], []
    for p in plans:
        sweep = p._sweep
        nodes = np.repeat(np.asarray(sweep.node_ids, dtype=object),
                          sweep.counts).tolist()
        rows += list(zip(nodes, sweep.alloc_names, sweep.alloc_tg))
        ids += sweep.alloc_ids
    return rows, ids


class TestSweepEquivalence:
    def _store(self, n_nodes=24):
        store = StateStore()
        tindex = TensorIndex.attach(store)
        idx = 0
        for i in range(n_nodes):
            idx += 1
            store.upsert_node(idx, make_node(i))
        return store, tindex, idx

    def test_fresh_register_mixed_fleet(self):
        """Infeasible (too-small), drained, and down nodes in one fleet:
        placements land only on the healthy ones and the failed metrics
        (exhaustion dimensions, coalesced counts) match exactly."""
        store, tindex, idx = self._store(12)
        tiny = make_node(100, cpu=60)       # exhausts on cpu
        idx += 1
        store.upsert_node(idx, tiny)
        drained = make_node(101)
        drained.Drain = True
        idx += 1
        store.upsert_node(idx, drained)
        job = sys_job(cpu=100)
        idx += 1
        store.upsert_job(idx, job)

        pv, pe = assert_equivalent(store, tindex, job)
        assert pv.columns == [True]
        placed, _ = summarize(pv)
        assert len(placed) == 12  # the tiny node exhausts, drained skipped
        nodes_placed = {p[0] for p in placed}
        assert drained.ID not in nodes_placed
        assert tiny.ID not in nodes_placed
        assert failed_metrics(pv)  # the exhaustion was recorded

    def test_partially_allocated_fleet(self):
        """Half the fleet already carries the job (a prior sweep), then
        new nodes join: only the missing nodes get placements and the
        existing allocs are untouched on both paths."""
        store, tindex, idx = self._store(8)
        job = sys_job()
        idx += 1
        store.upsert_job(idx, job)
        planner = CapturePlanner()
        sched = SystemScheduler(store, planner, tindex, logger,
                                rng=random.Random(7))
        sched.process(make_eval(job))
        allocs = [a for p in planner.plans
                  for v in p.NodeAllocation.values() for a in v]
        # Commit HALF the sweep: a partially-allocated fleet.
        half = [a for a in allocs if int(a.NodeID.split("-")[1]) % 2 == 0]
        for a in half:
            a.Job = job
        idx += 1
        store.upsert_allocs(idx, half)

        pv, pe = assert_equivalent(store, tindex, job,
                                   EvalTriggerNodeUpdate)
        # Nothing to stop or update: the batch covers every placement.
        assert pv.columns == [True]
        placed, stops = summarize(pv)
        assert stops == []
        assert len(placed) == 8 - len(half)
        assert all(int(p[0].split("-")[1]) % 2 == 1 for p in placed)

    def test_tainted_nodes_stop_with_desc(self):
        """Drained nodes with live allocs: stops carry the tainted
        description; no replacement lands on the drained node."""
        store, tindex, idx = self._store(6)
        job = sys_job()
        idx += 1
        store.upsert_job(idx, job)
        planner = CapturePlanner()
        sched = SystemScheduler(store, planner, tindex, logger,
                                rng=random.Random(7))
        sched.process(make_eval(job))
        allocs = [a for p in planner.plans
                  for v in p.NodeAllocation.values() for a in v]
        for a in allocs:
            a.Job = job
        idx += 1
        store.upsert_allocs(idx, allocs)
        idx += 1
        store.update_node_drain(idx, "node-0002", True)

        pv, pe = assert_equivalent(store, tindex, job,
                                   EvalTriggerNodeUpdate)
        assert pv.columns == [False]
        placed, stops = summarize(pv)
        assert placed == []
        assert len(stops) == 1
        assert "tainted" in stops[0][2]

    def test_destructive_update_replaces_everywhere(self):
        """A changed task config stops + replaces on every node; the
        replacement rides the same plan and both paths agree."""
        store, tindex, idx = self._store(5)
        job = sys_job()
        idx += 1
        store.upsert_job(idx, job)
        planner = CapturePlanner()
        sched = SystemScheduler(store, planner, tindex, logger,
                                rng=random.Random(7))
        sched.process(make_eval(job))
        allocs = [a for p in planner.plans
                  for v in p.NodeAllocation.values() for a in v]
        for a in allocs:
            a.Job = job
        idx += 1
        store.upsert_allocs(idx, allocs)

        update = job.copy()
        update.TaskGroups[0].Tasks[0].Config = {"command": "/bin/other"}
        update.init_fields()
        idx += 1
        store.upsert_job(idx, update)
        update = store.job_by_id(job.ID)

        pv, pe = assert_equivalent(store, tindex, update)
        assert pv.columns == [False]  # stops ride the plan: objects
        placed, stops = summarize(pv)
        assert len(placed) == 5
        assert len(stops) == 5
        assert all("updated" in s[2] for s in stops)

    def test_inplace_update_keeps_allocs(self):
        """A non-destructive change (added constraint) updates in place:
        no stops, the same alloc IDs are re-planned on both paths."""
        store, tindex, idx = self._store(4)
        job = sys_job()
        idx += 1
        store.upsert_job(idx, job)
        planner = CapturePlanner()
        sched = SystemScheduler(store, planner, tindex, logger,
                                rng=random.Random(7))
        sched.process(make_eval(job))
        allocs = [a for p in planner.plans
                  for v in p.NodeAllocation.values() for a in v]
        for a in allocs:
            a.Job = job
        idx += 1
        store.upsert_allocs(idx, allocs)

        update = job.copy()
        update.Constraints = list(update.Constraints) + [Constraint(
            LTarget="${attr.kernel.name}", RTarget="linux", Operand="=")]
        update.init_fields()
        idx += 1
        store.upsert_job(idx, update)
        update = store.job_by_id(job.ID)

        pv, pe = assert_equivalent(store, tindex, update)
        assert pv.columns == [False]  # in-place updates: objects
        placed, stops = summarize(pv)
        assert stops == []
        inplace_ids = sorted(
            a.ID for p in pv.plans
            for v in p.NodeAllocation.values() for a in v)
        assert inplace_ids == sorted(a.ID for a in allocs)

    def test_inplace_update_with_new_node_joining(self):
        """The eval that both updates in place (existing nodes) and
        places fresh (a node that joined since): the sweep agrees with
        the oracle, and the SweepBatch excludes the in-place nodes —
        their remove-then-add accounting belongs to the exact verify."""
        store, tindex, idx = self._store(3)
        job = sys_job()
        idx += 1
        store.upsert_job(idx, job)
        planner = CapturePlanner()
        sched = SystemScheduler(store, planner, tindex, logger,
                                rng=random.Random(7))
        sched.process(make_eval(job))
        allocs = [a for p in planner.plans
                  for v in p.NodeAllocation.values() for a in v]
        for a in allocs:
            a.Job = job
        idx += 1
        store.upsert_allocs(idx, allocs)

        update = job.copy()
        update.Constraints = list(update.Constraints) + [Constraint(
            LTarget="${attr.kernel.name}", RTarget="linux", Operand="=")]
        update.init_fields()
        idx += 1
        store.upsert_job(idx, update)
        update = store.job_by_id(job.ID)
        newcomer = make_node(50)
        idx += 1
        store.upsert_node(idx, newcomer)

        pv, pe = assert_equivalent(store, tindex, update,
                                   EvalTriggerNodeUpdate)
        assert pv.columns == [False]
        placed, stops = summarize(pv)
        assert stops == []
        assert len(placed) == 4  # 3 in-place re-plans + 1 fresh
        fresh = [p for p in placed if p[0] == newcomer.ID]
        assert len(fresh) == 1
        sweep = getattr(pv.plans[0], "_sweep", None)
        assert sweep is not None
        # Only the newcomer's row is bulk-verifiable.
        assert sweep.node_ids == [newcomer.ID]
        assert_descriptor_types(sweep)
        (a,), = [v for nid, v in pv.plans[0].NodeAllocation.items()
                 if nid == newcomer.ID]
        assert sweep.alloc_ids == [a.ID] and sweep.alloc_names == [a.Name]

    def test_multi_instance_group_places_count_per_node(self):
        """A system TG with Count=2 places BOTH instances on every node;
        the descriptor folds them into one per-row demand."""
        store, tindex, idx = self._store(4)
        job = sys_job()
        job.TaskGroups[0].Count = 2
        job.init_fields()
        idx += 1
        store.upsert_job(idx, job)
        pv, pe = assert_equivalent(store, tindex, job)
        assert pv.columns == [True]
        placed, _ = summarize(pv)
        assert len(placed) == 8
        names = {p[1] for p in placed}
        assert len(names) == 2  # tg[0] and tg[1]
        sweep = getattr(pv.plans[0], "_sweep", None)
        assert sweep is not None
        assert len(sweep.node_ids) == 4
        a = next(iter(pv.plans[0].NodeAllocation.values()))[0]
        assert np.allclose(sweep.delta[0], 2 * alloc_vec(a))

    def test_network_ask_group_forces_exact_path(self):
        """A group asking for ports is NOT sweep-applicable: both runs
        take the exact per-node path and still agree (ports are assigned
        host-side on each)."""
        from nomad_tpu.scheduler import system_sweep

        store, tindex, idx = self._store(4)
        job = sys_job(networks=True)
        assert not system_sweep.sweep_applicable(job, tindex)
        idx += 1
        store.upsert_job(idx, job)
        pv, pe = assert_equivalent(store, tindex, job)
        placed, _ = summarize(pv)
        assert len(placed) == 4
        allocs = [a for p in pv.plans
                  for v in p.NodeAllocation.values() for a in v]
        assert all(
            r.Networks for a in allocs for r in a.TaskResources.values())

    def test_duplicate_node_entries_place_once(self):
        """The exact diff's `emitted` guard dedupes a duplicated node
        list; the tensor path is structurally deduped (one row per node).
        Both produce one placement per distinct node."""
        store, tindex, idx = self._store(3)
        job = sys_job()
        idx += 1
        store.upsert_job(idx, job)
        nodes = list(store.nodes())
        dup = nodes + nodes  # duplicated entries
        diff = diff_system_allocs(job, dup, {}, [])
        per_node = {}
        for tup in diff.place:
            per_node.setdefault(tup.Alloc.NodeID, []).append(tup.Name)
        assert all(len(v) == 1 for v in per_node.values())

        pv, _ = run_path(store, tindex, job, True)
        placed, _ = summarize(pv)
        assert len(placed) == 3
        assert len({p[0] for p in placed}) == 3

    def test_deregister_stops_all_on_both_paths(self):
        """Job gone: both paths stop every alloc (the sweep declines —
        job None — and the exact stop-all walk serves both)."""
        store, tindex, idx = self._store(3)
        job = sys_job()
        idx += 1
        store.upsert_job(idx, job)
        planner = CapturePlanner()
        sched = SystemScheduler(store, planner, tindex, logger,
                                rng=random.Random(7))
        sched.process(make_eval(job))
        allocs = [a for p in planner.plans
                  for v in p.NodeAllocation.values() for a in v]
        for a in allocs:
            a.Job = job
        idx += 1
        store.upsert_allocs(idx, allocs)
        store.delete_job(idx + 1, job.ID)

        pv, pe = assert_equivalent(store, tindex, job)
        placed, stops = summarize(pv)
        assert placed == []
        assert len(stops) == 3

    def test_sweep_batch_descriptor_shape(self):
        """The emitted plan carries a SweepBatch covering every placed
        node with the per-row demand the applier fit-checks against."""
        store, tindex, idx = self._store(6)
        job = sys_job()
        idx += 1
        store.upsert_job(idx, job)
        pv, _ = run_path(store, tindex, job, True)
        plan = pv.plans[0]
        sweep = getattr(plan, "_sweep", None)
        assert sweep is not None
        assert len(sweep.node_ids) == len(plan.NodeAllocation) == 6
        assert sweep.rows.shape == (6,)
        assert sweep.delta.shape == (6, 5)
        a = next(iter(plan.NodeAllocation.values()))[0]
        assert np.allclose(sweep.delta[0], alloc_vec(a))
        assert sweep.n_rows == tindex.nt.n_rows
        assert sweep.epoch == tindex.nt.row_epoch
        assert_descriptor_types(sweep)
        assert sweep.counts.tolist() == [1] * 6
        assert sweep.alloc_tg == [0] * 6
        assert sweep.alloc_names == [f"{job.ID}.web[0]"] * 6
        # The stamped objects are the columns' placements, row by row.
        assert [(a.NodeID, a.ID) for nid in sweep.node_ids
                for a in plan.NodeAllocation[nid]] == list(
            zip(sweep.node_ids, sweep.alloc_ids))

    @pytest.mark.parametrize("fleet", ["24 nodes", "2,000 nodes, racks"])
    def test_fresh_register_stays_columns_through_the_verdict(self, fleet):
        """A fresh register's plan is columns only, the verdict admitted by
        copy is too, and neither is built by the time the eval is done;
        what it places is the exact path's decision."""
        store, tindex, job = fleet_store(fleet)
        pv = CopyPlanner()
        sched = SystemScheduler(store, pv, tindex, logger,
                                rng=random.Random(7))
        sched.process(make_eval(job))
        (plan,), (verdict,) = pv.plans, pv.results
        assert verdict is sched.plan_result
        assert columns_only(plan.NodeAllocation)
        assert columns_only(verdict.NodeAllocation)
        assert placed_count(verdict.NodeAllocation) == len(
            plan._sweep.alloc_ids)
        assert pv.evals[-1].Status == EvalStatusComplete
        pe, _ = run_path(store, tindex, job, False)
        assert summarize(pv) == summarize(pe)
        assert failed_metrics(pv) == failed_metrics(pe)
        placed, _ = summarize(pv)
        if fleet != "24 nodes":
            # Six racks of 64 and the never-ready nodes are refused.
            assert 1600 < len(placed) < 2000
            failed = pv.evals[-1].FailedTGAllocs[job.TaskGroups[0].Name]
            assert set(failed.ConstraintFiltered) == {
                "job constraints", "group constraints"}

    def test_contended_chunks_are_slices_of_the_columns(self, monkeypatch):
        """With plans queued at the applier and more placements than a
        chunk, the sweep goes out in columns-only chunks cut at node
        boundaries by the descriptor's counts; their union is the
        monolithic plan's placements (nodes, names, template indexes,
        counts) with unique ids, and the merged verdict stays columns."""
        monkeypatch.setattr(system_sched, "SYSTEM_PLAN_CHUNK", 16)
        store, tindex, idx = self._store(40)
        job = sys_job()
        job.TaskGroups[0].Count = 2
        side = copy.deepcopy(job.TaskGroups[0])
        side.Name = "side"
        side.Count = 1
        side.Tasks[0].Resources.CPU = 50
        job.TaskGroups.append(side)
        job.init_fields()
        idx += 1
        store.upsert_job(idx, job)

        runs = {}
        for depth in (0, 2):
            planner = CopyPlanner(depth)
            sched = SystemScheduler(store, planner, tindex, logger,
                                    rng=random.Random(7))
            sched.process(make_eval(job))
            assert all(columns_only(p.NodeAllocation) for p in planner.plans)
            assert columns_only(sched.plan_result.NodeAllocation)
            assert placed_count(sched.plan_result.NodeAllocation) == 120
            runs[depth] = planner.plans, column_rows(planner.plans)
        (whole,), (rows, ids) = runs[0]
        chunks, (chunk_rows, chunk_ids) = runs[2]
        assert len(chunks) == 7  # six of 6 nodes (18 placements), one of 4
        assert chunk_rows == rows and len(chunk_rows) == 120
        assert len(set(chunk_ids)) == len(chunk_ids) == 120
        seen = [nid for p in chunks for nid in p.NodeAllocation]
        assert seen == whole._sweep.node_ids  # no node split, row order
        for p in chunks[:-1]:
            assert placed_count(p.NodeAllocation) >= 16
            assert placed_count(p.NodeAllocation) - 3 < 16
        assert np.concatenate([p._sweep.counts for p in chunks]).tolist() \
            == whole._sweep.counts.tolist() == [3] * 40

    def test_served_system_job_commits_one_columnar_entry(self,
                                                          monkeypatch):
        """End to end through a live server's applier, FSM and store: a
        system job over 300 nodes commits as one ApplySweepBatch, the
        store reads back the exact path's placements, the plan was never
        built into objects, and the sweep counted one columnar plan."""
        import time

        from nomad_tpu.server import Server, ServerConfig
        from nomad_tpu.server.fsm import MessageType

        counted = []
        incr = metrics.incr_counter
        monkeypatch.setattr(metrics, "incr_counter",
                            lambda key, *a: (counted.append(tuple(key)),
                                             incr(key, *a))[1])

        def serve(exact):
            monkeypatch.setattr(system_sweep, "FORCE_EXACT", exact)
            srv = Server(ServerConfig(num_schedulers=1,
                                      scheduler_window=8,
                                      min_heartbeat_ttl=3600.0,
                                      heartbeat_grace=3600.0))
            srv.establish_leadership()
            entries, plans = [], []
            apply, enqueue = srv.raft.apply, srv.plan_queue.enqueue
            monkeypatch.setattr(
                srv.raft, "apply", lambda msg, payload: (
                    entries.append(msg), apply(msg, payload))[1])
            monkeypatch.setattr(
                srv.plan_queue, "enqueue", lambda plan: (
                    plans.append(plan), enqueue(plan))[1])
            try:
                for node in build_fleet(SYS_FLEET, 300, random.Random(41)):
                    srv.node_register(node)
                job = sys_job(cpu=20)
                eval_id = srv.job_register(job)[0]
                deadline = time.monotonic() + 60
                while (e := srv.state.eval_by_id(eval_id)) is None \
                        or e.Status != EvalStatusComplete:
                    assert time.monotonic() < deadline, "never completed"
                    time.sleep(0.02)
                built = [not columns_only(p.NodeAllocation) for p in plans]
                placed = sorted(
                    (a.NodeID, a.Name, a.TaskGroup,
                     tuple(alloc_vec(a).tolist()))
                    for a in srv.state.allocs_by_job(job.ID))
                return entries, built, placed
            finally:
                srv.shutdown()

        before = counted.count(("nomad", "sched", "system",
                                "plans_columnar"))
        entries, built, placed = serve(False)
        sweep_entries = [m for m in entries
                         if m in (MessageType.ApplySweepBatch,
                                  MessageType.AllocUpdate)]
        assert sweep_entries == [MessageType.ApplySweepBatch]
        assert built == [False]
        assert counted.count(("nomad", "sched", "system",
                              "plans_columnar")) == before + 1
        assert ("nomad", "sched", "system", "plans_objects") not in counted
        assert 250 < len(placed) < 300

        exact_entries, exact_built, exact_placed = serve(True)
        assert exact_built == [True]
        assert MessageType.AllocUpdate in exact_entries
        assert placed == exact_placed
