"""Fixed-seed columnar-vs-object state-store commit equivalence.

The columnar commit path (plan applier -> ApplySweepBatch raft entry ->
SweepSegment scatter-apply -> lazy materialization) must be
indistinguishable from the per-object path it optimizes: identical
allocs_by_node/-job/-eval results, identical alloc_by_id values,
identical client pull maps, identical snapshot->restore state — and any
MUTATION (client status update, stop/preemption eviction, GC) must
promote the row onto the exact object path with the same end state the
object commit would have produced.

One fixed-seed system sweep is generated ONCE (capture-only planner),
then the same verified result is committed twice — once as the columnar
raft entry (through a real msgpack round-trip, the wire shape), once as
the reference AllocUpdate object entry — into two fresh FSMs, and every
read surface is compared as plain data.

TestServiceColumnarEquivalence holds the SERVICE window path (the
pipelined fast path's all-placed build, kind="service") to the same
gate, including the mixed-window exclusions: failed placements, network
asks, and vanished nodes must keep the exact per-object path.
"""

import logging
import random
import types

import msgpack
import pytest

from nomad_tpu import mock
from nomad_tpu.raft.backend import encode_command
from nomad_tpu.resilience import failpoints
from nomad_tpu.scheduler.system_sched import SystemScheduler
from nomad_tpu.server.fsm import FSM, MessageType
from nomad_tpu.server.plan_apply import _encode_result
from nomad_tpu.state.state_store import StateStore
from nomad_tpu.structs import PlanResult, compute_node_class, to_dict
from nomad_tpu.structs.structs import (
    AllocClientStatusRunning,
    AllocDesiredStatusEvict,
    EvalStatusPending,
    EvalTriggerJobRegister,
)
from nomad_tpu.tensor import TensorIndex

logger = logging.getLogger("test.columnar")

APPLY_INDEX = 100


class CapturePlanner:
    def __init__(self):
        self.plans = []
        self.evals = []

    def plan_queue_depth(self):
        return 0

    def submit_plan(self, plan):
        self.plans.append(plan)
        r = PlanResult()
        r.NodeUpdate = dict(plan.NodeUpdate)
        r.NodeAllocation = dict(plan.NodeAllocation)
        r.AllocIndex = 1
        return r, None

    def update_eval(self, ev):
        self.evals.append(ev)

    def create_eval(self, ev):
        self.evals.append(ev)

    def reblock_eval(self, ev):
        self.evals.append(ev)


def make_node(i):
    n = mock.node()
    n.ID = f"node-{i:04d}"
    n.Name = n.ID
    compute_node_class(n)
    return n


def sys_job(count=2):
    job = mock.system_job()
    t = job.TaskGroups[0].Tasks[0]
    t.Resources.CPU = 50
    t.Resources.MemoryMB = 32
    t.Resources.DiskMB = 150
    t.Resources.Networks = []
    t.Services = []
    job.TaskGroups[0].Count = count
    job.init_fields()
    return job


def sweep_plan(n_nodes=8, count=2):
    """One fixed-seed system sweep plan (with its columnar descriptor)
    against a capture-only planner — nothing committed."""
    store = StateStore()
    tindex = TensorIndex.attach(store)
    idx = 0
    for i in range(n_nodes):
        idx += 1
        store.upsert_node(idx, make_node(i))
    job = sys_job(count)
    idx += 1
    store.upsert_job(idx, job)
    ev = mock.eval()
    ev.JobID = job.ID
    ev.Type = job.Type
    ev.TriggeredBy = EvalTriggerJobRegister
    ev.Status = EvalStatusPending
    planner = CapturePlanner()
    sched = SystemScheduler(store, planner, tindex, logger,
                            rng=random.Random(7))
    sched.process(ev)
    [plan] = planner.plans
    assert getattr(plan, "_sweep", None) is not None
    assert plan._sweep.alloc_ids  # per-alloc columns present
    return job, plan


def commit_columnar(plan, transport="msgpack"):
    """Commit the sweep through the REAL columnar entry: by default with
    a msgpack round-trip (the consensus wire shape, lists all), or as
    DevRaft hands it to the FSM (`transport="devraft"`: the applier's own
    arrays and lists, nothing encoded)."""
    result = PlanResult(NodeUpdate=dict(plan.NodeUpdate),
                        NodeAllocation=dict(plan.NodeAllocation))
    result._sweep = plan._sweep
    element, is_sweep = _encode_result(plan, result)
    assert is_sweep
    msg, payload = MessageType.ApplySweepBatch, {"Batch": [element]}
    if transport == "msgpack":
        # The replicated backend's own encoding: where arrays become lists.
        msg, payload = msgpack.unpackb(encode_command(msg, payload),
                                       raw=False)
    fsm = FSM()
    fsm.apply(APPLY_INDEX, MessageType(msg), payload)
    assert fsm.state._col_segments, "sweep did not commit columnar"
    return fsm


def commit_objects(plan):
    """The reference per-object commit of the SAME result."""
    blob = msgpack.packb(
        (int(MessageType.AllocUpdate),
         to_dict({"Job": plan.Job,
                  "Alloc": [a for placed in plan.NodeAllocation.values()
                            for a in placed]})),
        use_bin_type=True)
    msg, payload = msgpack.unpackb(blob, raw=False)
    fsm = FSM()
    fsm.apply(APPLY_INDEX, MessageType(msg), payload)
    assert not fsm.state._col_segments
    return fsm


def visible(state, job, plan):
    """Every read surface as plain data, sorted for comparison."""
    def dump(allocs):
        return sorted((to_dict(a) for a in allocs), key=lambda d: d["ID"])

    eval_id = plan.EvalID
    node_ids = sorted(plan.NodeAllocation)
    out = {
        "all": dump(state.allocs()),
        "by_job": dump(state.allocs_by_job(job.ID)),
        "by_eval": dump(state.allocs_by_eval(eval_id)),
        "by_node": {nid: dump(state.allocs_by_node(nid))
                    for nid in node_ids},
        "by_node_live": {nid: dump(state.allocs_by_node_terminal(nid,
                                                                 False))
                         for nid in node_ids},
        "index": state.get_index("allocs"),
    }
    out["by_id"] = {d["ID"]: d for d in out["all"]}
    if hasattr(state, "client_alloc_map"):
        out["client"] = {nid: state.client_alloc_map(nid)
                         for nid in node_ids}
    return out


def assert_same_state(fsm_col, fsm_obj, job, plan):
    vc = visible(fsm_col.state, job, plan)
    vo = visible(fsm_obj.state, job, plan)
    assert vc == vo


def roundtrip(fsm):
    blob = msgpack.packb(fsm.snapshot(), use_bin_type=True)
    out = FSM()
    out.restore(msgpack.unpackb(blob, raw=False))
    return out


@pytest.fixture(autouse=True)
def _heal_failpoints():
    failpoints.disarm_all()
    yield
    failpoints.disarm_all()


class TestColumnarEquivalence:
    def test_commit_reads_identical(self):
        """The same sweep committed columnar and per-object is
        indistinguishable through every read surface."""
        job, plan = sweep_plan()
        fsm_col = commit_columnar(plan)
        fsm_obj = commit_objects(plan)
        assert_same_state(fsm_col, fsm_obj, job, plan)
        # And the columnar side really stayed lazy at commit: no chain
        # entries were created for the sweep's allocs.
        assert not fsm_col.state._tables["allocs"].current

    def test_snapshot_restore_identical(self):
        """snapshot->restore round-trips the columnar tables columnar and
        lands byte-identical client-visible state."""
        job, plan = sweep_plan()
        fsm_col = commit_columnar(plan)
        fsm_obj = commit_objects(plan)
        snap = fsm_col.snapshot()
        assert snap["columnar_allocs"] and not snap["allocs"]
        r_col = roundtrip(fsm_col)
        r_obj = roundtrip(fsm_obj)
        assert r_col.state._col_segments  # still columnar after restore
        assert_same_state(r_col, r_obj, job, plan)
        # Restored-columnar == live-object too (transitivity check).
        assert visible(r_col.state, job, plan)["by_id"] \
            == visible(fsm_obj.state, job, plan)["by_id"]

    def test_client_update_promotes_row(self):
        """A client status update on a sweep-committed alloc promotes the
        row onto the exact object path; both stores converge to the same
        mutated state and the row leaves the columnar table."""
        job, plan = sweep_plan()
        fsm_col = commit_columnar(plan)
        fsm_obj = commit_objects(plan)
        target = plan._sweep.alloc_ids[3]
        seg = fsm_col.state._col_segments[0]
        live_before = seg.n_live
        for fsm in (fsm_col, fsm_obj):
            running = fsm.state.alloc_by_id(target).copy()
            running.ClientStatus = AllocClientStatusRunning
            running.ClientDescription = "started"
            fsm.apply(APPLY_INDEX + 1, MessageType.AllocClientUpdate,
                      {"Alloc": [running]})
        assert seg.n_live == live_before - 1
        assert fsm_col.state._tables["allocs"].current[target] is not None
        assert_same_state(fsm_col, fsm_obj, job, plan)
        got = fsm_col.state.alloc_by_id(target)
        assert got.ClientStatus == AllocClientStatusRunning
        assert got.CreateIndex == APPLY_INDEX  # promotion kept identity
        # Snapshot/restore still identical after a promotion.
        assert_same_state(roundtrip(fsm_col), roundtrip(fsm_obj), job, plan)

    def test_preemption_eviction_promotes_and_matches(self):
        """A preemption-style eviction (stop upsert of a columnar row)
        promotes the victim and commits the same terminal state the
        object path produces — including the terminal/live split reads."""
        job, plan = sweep_plan()
        fsm_col = commit_columnar(plan)
        fsm_obj = commit_objects(plan)
        victim_id = plan._sweep.alloc_ids[0]
        for fsm in (fsm_col, fsm_obj):
            victim = fsm.state.alloc_by_id(victim_id).copy()
            victim.DesiredStatus = AllocDesiredStatusEvict
            victim.DesiredDescription = "preempted"
            fsm.apply(APPLY_INDEX + 2, MessageType.AllocUpdate,
                      {"Job": None, "Alloc": [victim]})
        assert_same_state(fsm_col, fsm_obj, job, plan)
        got = fsm_col.state.alloc_by_id(victim_id)
        assert got.terminal_status()
        node = got.NodeID
        assert victim_id not in {
            a.ID for a in fsm_col.state.allocs_by_node_terminal(node,
                                                                False)}

    def test_gc_delete_matches(self):
        """delete_eval GC of columnar rows promotes + tombstones exactly
        like the object path."""
        job, plan = sweep_plan()
        fsm_col = commit_columnar(plan)
        fsm_obj = commit_objects(plan)
        doomed = list(plan._sweep.alloc_ids[:3])
        for fsm in (fsm_col, fsm_obj):
            fsm.apply(APPLY_INDEX + 3, MessageType.EvalDelete,
                      {"Evals": [], "Allocs": list(doomed)})
        assert_same_state(fsm_col, fsm_obj, job, plan)
        for aid in doomed:
            assert fsm_col.state.alloc_by_id(aid) is None

    def test_killed_commit_is_atomic(self):
        """An injected kill at the bulk-commit seam fires BEFORE the
        entry is proposed to consensus (like plan.apply.commit): the
        raft log never carries the batch, so no replica — and no log
        replay after the redelivered eval commits fresh UUIDs — can ever
        land it. No torn batch: zero rows visible, zero segments, log
        index unmoved."""
        from nomad_tpu.server.fsm import DevRaft
        from nomad_tpu.server.plan_apply import PlanApplier
        from nomad_tpu.server.plan_queue import PlanQueue

        job, plan = sweep_plan()
        fsm = FSM()
        raft = DevRaft(fsm)
        # The applier verifies against real state: give the store the
        # same (deterministic-ID) node fleet the plan targets.
        for i in range(8):
            fsm.state.upsert_node(i + 1, make_node(i))
        index_before = raft.last_index
        failpoints.arm_from_spec("state.store.commit=error:count=1")
        queue = PlanQueue()
        queue.set_enabled(True)
        applier = PlanApplier(queue, raft)
        queue.enqueue(plan)
        with pytest.raises(failpoints.FailpointError):
            applier.apply_one(queue.dequeue(timeout=1))
        assert raft.last_index == index_before  # never entered the log
        assert not fsm.state._col_segments
        assert not fsm.state.allocs_by_job(job.ID)
        queue.set_enabled(False)

    def test_tensor_listener_epoch_fallback(self):
        """The usage listener's row-addressed scatter must decline on an
        epoch mismatch and fall back to the id-addressed path — same
        final usage either way (regression: the fallback once executed
        orphaned per-event code and raised NameError)."""
        import numpy as np
        from nomad_tpu.tensor.node_table import RES_DIMS

        store = StateStore()
        tindex = TensorIndex.attach(store)
        node = make_node(0)
        store.upsert_node(1, node)
        row = tindex.nt.row_of[node.ID]
        base = tindex.nt.usage[row].copy()
        delta = np.ones((1, RES_DIMS), dtype=np.float32)
        # Current epoch: row-addressed path.
        tindex.on_sweep_batch([node.ID], np.asarray([row]), delta,
                              tindex.nt.row_epoch)
        assert np.allclose(tindex.nt.usage[row], base + 1)
        # Stale epoch: id-addressed fallback, same result.
        tindex.on_sweep_batch([node.ID], np.asarray([row]), delta,
                              tindex.nt.row_epoch - 1)
        assert np.allclose(tindex.nt.usage[row], base + 2)
        # And the ordinary per-event batch listener is still wired (the
        # store's _emit prefers it).
        assert callable(getattr(tindex, "on_change_batch"))

    def test_entry_with_updates_is_one_transaction(self):
        """A sweep element carrying exact-path stops (Updates) commits
        stops AND placements in the same entry; afterwards both are
        visible together (stop-then-place order inside one
        transaction)."""
        job, plan = sweep_plan()
        fsm = commit_columnar(plan)
        # Build a second sweep entry for the same job whose element also
        # carries a stop of one previously committed alloc.
        victim = fsm.state.alloc_by_id(plan._sweep.alloc_ids[0]).copy()
        victim.DesiredStatus = AllocDesiredStatusEvict
        victim.DesiredDescription = "preempted"
        job2, plan2 = sweep_plan()
        result = PlanResult(NodeUpdate={victim.NodeID: [victim]},
                            NodeAllocation=dict(plan2.NodeAllocation))
        result._sweep = plan2._sweep
        element, is_sweep = _encode_result(plan2, result)
        assert is_sweep and "Updates" in element
        fsm.apply(APPLY_INDEX + 5, MessageType.ApplySweepBatch,
                  {"Batch": [element]})
        got = fsm.state.alloc_by_id(victim.ID)
        assert got.terminal_status()
        assert len(fsm.state.allocs_by_job(job2.ID)) \
            == len(plan2._sweep.alloc_ids)

    def test_chunk_slices_cover_batch(self):
        """Descriptor slices (the chunked submit path) partition the
        per-alloc columns exactly: committing the slices equals
        committing the whole batch."""
        job, plan = sweep_plan(n_nodes=9, count=2)
        sweep = plan._sweep
        mid = len(sweep.node_ids) // 2
        parts = [sweep.slice(0, mid),
                 sweep.slice(mid, len(sweep.node_ids))]
        assert sum(len(p.alloc_ids) for p in parts) == len(sweep.alloc_ids)
        assert [i for p in parts for i in p.alloc_ids] == sweep.alloc_ids
        fsm_whole = commit_columnar(plan)
        fsm_parts = FSM()
        for k, part in enumerate(parts):
            chunk = PlanResult(NodeAllocation={
                nid: plan.NodeAllocation[nid] for nid in part.node_ids})
            chunk._sweep = part
            element, is_sweep = _encode_result(plan, chunk)
            assert is_sweep
            fsm_parts.apply(APPLY_INDEX + k, MessageType.ApplySweepBatch,
                            {"Batch": [element]})
        whole = {a.ID for a in fsm_whole.state.allocs_by_job(job.ID)}
        split = {a.ID for a in fsm_parts.state.allocs_by_job(job.ID)}
        assert whole == split == set(sweep.alloc_ids)


# --------------------------------------------------- service window path
def svc_job(count=5, cpu=50, networks=False):
    """Service job for the window harness: small asks, no networks by
    default (the storm shape); networks=True keeps mock.job's dynamic
    port ask so the window must take the exact per-object path."""
    job = mock.job()
    tg = job.TaskGroups[0]
    tg.Count = count
    t = tg.Tasks[0]
    t.Resources.CPU = cpu
    t.Resources.MemoryMB = 32
    t.Resources.DiskMB = 10
    if not networks:
        t.Resources.Networks = []
    t.Services = []
    if t.LogConfig is not None:
        t.LogConfig.MaxFiles = 1
        t.LogConfig.MaxFileSizeMB = 1
    job.init_fields()
    return job


def service_window(job, n_nodes=6, seed=7, vanish=False):
    """One fixed-seed service eval through the pipelined fast path's
    build — prepare_batch -> host placement kernel -> compact ->
    collect_build — the exact recipe _try_dispatch_fast/_finish_fast run,
    minus the stage threads. Returns a namespace with the plan (carrying
    its service SweepBatch when the window stayed columnar), the build
    verdict, and the store/tensor the window ran against."""
    import numpy as np

    from nomad_tpu.scheduler import kernels
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.stack import GenericStack, WindowAccumulator
    from nomad_tpu.scheduler.util import (
        diff_allocs,
        materialize_task_groups,
        ready_nodes_in_dcs,
    )
    from nomad_tpu.tensor import ClassEligibility

    store = StateStore()
    tindex = TensorIndex.attach(store)
    idx = 0
    for i in range(n_nodes):
        idx += 1
        store.upsert_node(idx, make_node(i))
    idx += 1
    store.upsert_job(idx, job)
    ev = mock.eval()
    ev.JobID = job.ID
    ev.Type = job.Type
    ev.TriggeredBy = EvalTriggerJobRegister
    snap = store.snapshot()
    plan = ev.make_plan(job, copy_job=False)
    ctx = EvalContext(snap, plan, logger)
    stack = GenericStack(ctx, tindex, batch=False, rng=random.Random(seed))
    diff = diff_allocs(job, {}, materialize_task_groups(job), [])
    nodes, by_dc = ready_nodes_in_dcs(snap, job.Datacenters)
    nt = tindex.nt
    nodes_by_id = {n.ID: n for n in nodes}
    cand_mask = np.zeros(nt.n_rows, dtype=bool)
    for n in nodes:
        row = nt.row_of.get(n.ID)
        if row is not None:
            cand_mask[row] = True
    stack.job = job
    stack.adopt_nodes(nodes_by_id, cand_mask, ClassEligibility(nt, nodes))
    ctx.metrics.NodesAvailable = by_dc
    prep = stack.prepare_batch([t.TaskGroup for t in diff.place])
    res = stack.dispatch_host(prep)
    cr = kernels.compact_host(np.asarray(res.packed), prep.n_valid)
    if vanish:
        # A node vanishing between dispatch and build: the window-level
        # lookup must fail and route the eval onto the exact path.
        nodes_by_id.pop(nt.node_id_array()[cr.chosen[0]])
    failed = {}
    ok = stack.collect_build(prep, cr, ev.ID, job, diff.place, plan,
                             failed, WindowAccumulator(nt.n_rows))
    return types.SimpleNamespace(job=job, plan=plan, ok=ok, failed=failed,
                                 store=store, tindex=tindex)


COMMIT_SHAPES = {
    # one allocation a node row; several a row (the per-allocation node
    # column is a repeat of the row ids); a service window's rows.
    "system-one-a-row": lambda: sweep_plan(n_nodes=8, count=1),
    "system-two-a-row": lambda: sweep_plan(n_nodes=8, count=2),
    "service-window": lambda: _service_plan(svc_job()),
    "service-rows-folded": lambda: _service_plan(svc_job(count=5), n_nodes=2),
}


def _service_plan(job, **kw):
    ns = service_window(job, **kw)
    assert ns.ok and not ns.failed
    return ns.job, ns.plan


@pytest.mark.parametrize("transport", ["devraft", "msgpack"])
@pytest.mark.parametrize("shape", sorted(COMMIT_SHAPES))
def test_reads_after_a_columnar_commit_are_the_object_paths(shape, transport):
    """ISSUE 35: the entry's columns reach the store as they are (arrays
    and the emit's lists from DevRaft, lists from a decoded entry), and the
    segment keeps (row node ids, counts) in place of a per-allocation node
    column until a read needs one. Every read surface, a snapshot and its
    restore are those of the per-object commit all the same."""
    job, plan = COMMIT_SHAPES[shape]()
    fsm_col = commit_columnar(plan, transport)
    fsm_obj = commit_objects(plan)
    [seg] = fsm_col.state._col_segments
    sweep = plan._sweep
    # The commit expanded nothing and copied no string column.
    assert seg._node_ids is None
    assert list(seg.row_node_ids) == list(sweep.node_ids)
    assert seg.touched_node_ids() is seg.row_node_ids
    if transport == "devraft":
        assert seg.alloc_ids is sweep.alloc_ids
        assert seg.names is sweep.alloc_names
        assert seg.row_node_ids is sweep.node_ids
    by_node = {nid: [a.ID for a in placed]
               for nid, placed in plan.NodeAllocation.items()}
    want = [nid for nid in sweep.node_ids for _ in by_node[nid]]
    assert_same_state(fsm_col, fsm_obj, job, plan)
    assert seg.node_ids == want and seg.row_node_ids is None
    if len(want) == len(sweep.node_ids):  # one a row: no copy at all
        assert seg.node_ids is (sweep.node_ids if transport == "devraft"
                                else seg._node_ids)
    for nid, placed in by_node.items():
        assert fsm_col.state.client_alloc_map(nid) \
            == ({aid: APPLY_INDEX for aid in placed}, APPLY_INDEX)
        for aid in placed:
            assert fsm_col.state.alloc_by_id(aid).NodeID == nid
    snap = fsm_col.snapshot()
    assert snap["columnar_allocs"] and not snap["allocs"]
    assert snap["columnar_allocs"][0]["NodeIDs"] == want
    assert_same_state(roundtrip(fsm_col), roundtrip(fsm_obj), job, plan)


@pytest.mark.parametrize("watched", ["a-placed-node", "a-placed-alloc",
                                     "the-job", "another-node",
                                     "another-alloc"])
def test_a_columnar_commit_wakes_who_watches_what_it_touched(watched):
    """The commit hands the watch scope its columns as they are; a field's
    column is hashed only once a waiter is registered on that field."""
    import threading

    from nomad_tpu.state.watch import Item

    job, plan = sweep_plan(n_nodes=6, count=2)
    sweep = plan._sweep
    item, fires = {
        "a-placed-node": (Item(alloc_node=sweep.node_ids[3]), True),
        "a-placed-alloc": (Item(alloc=sweep.alloc_ids[-1]), True),
        "the-job": (Item(alloc_job=job.ID), True),
        "another-node": (Item(alloc_node="node-9999"), False),
        "another-alloc": (Item(alloc="no-such-alloc"), False),
    }[watched]
    fsm = FSM()
    woke = threading.Event()
    fsm.state.watch([item], woke)
    result = PlanResult(NodeUpdate={}, NodeAllocation=dict(plan.NodeAllocation))
    result._sweep = sweep
    element, _ = _encode_result(plan, result)
    fsm.apply(APPLY_INDEX, MessageType.ApplySweepBatch, {"Batch": [element]})
    assert woke.is_set() is fires


def test_a_snapshot_taken_before_any_read_holds_the_node_column():
    """The per-allocation node column is first asked for by the snapshot's
    own serialisation here: no read came between commit and persist."""
    job, plan = sweep_plan(n_nodes=6, count=2)
    fsm_col = commit_columnar(plan, "devraft")
    assert fsm_col.state._col_segments[0]._node_ids is None
    restored = roundtrip(fsm_col)
    assert_same_state(restored, commit_objects(plan), job, plan)


class TestServiceColumnarEquivalence:
    def test_service_commit_reads_identical(self):
        """A service window committed columnar and per-object is
        indistinguishable through every read surface, and the columnar
        side stays fully lazy at commit."""
        ns = service_window(svc_job())
        assert ns.ok and not ns.failed
        sweep = ns.plan._sweep
        assert sweep is not None and sweep.kind == "service"
        assert sweep.alloc_ids and sorted(sweep.node_ids) \
            == sorted(ns.plan.NodeAllocation)
        fsm_col = commit_columnar(ns.plan)
        fsm_obj = commit_objects(ns.plan)
        assert fsm_col.state._col_segments[0].kind == "service"
        assert_same_state(fsm_col, fsm_obj, ns.job, ns.plan)
        assert not fsm_col.state._tables["allocs"].current

    def test_service_snapshot_restore_identical(self):
        """snapshot->restore keeps service segments columnar (Kind
        round-trips) and lands identical client-visible state."""
        ns = service_window(svc_job())
        fsm_col = commit_columnar(ns.plan)
        fsm_obj = commit_objects(ns.plan)
        snap = fsm_col.snapshot()
        assert snap["columnar_allocs"] and not snap["allocs"]
        r_col = roundtrip(fsm_col)
        assert r_col.state._col_segments[0].kind == "service"
        assert_same_state(r_col, roundtrip(fsm_obj), ns.job, ns.plan)

    def test_service_client_update_promotes_row(self):
        """A client status update on a service-window row promotes it
        onto the object chain; both stores converge and the promotion
        shows in the operator counters."""
        ns = service_window(svc_job())
        fsm_col = commit_columnar(ns.plan)
        fsm_obj = commit_objects(ns.plan)
        target = ns.plan._sweep.alloc_ids[2]
        for fsm in (fsm_col, fsm_obj):
            running = fsm.state.alloc_by_id(target).copy()
            running.ClientStatus = AllocClientStatusRunning
            running.ClientDescription = "started"
            fsm.apply(APPLY_INDEX + 1, MessageType.AllocClientUpdate,
                      {"Alloc": [running]})
        assert_same_state(fsm_col, fsm_obj, ns.job, ns.plan)
        got = fsm_col.state.alloc_by_id(target)
        assert got.ClientStatus == AllocClientStatusRunning
        assert got.CreateIndex == APPLY_INDEX
        stats = fsm_col.state.columnar_stats()
        assert stats["PromotedRows"] == 1
        assert stats["Batches"] == {"service": 1}

    def test_service_descriptor_bulk_verifies(self):
        """The applier's vectorized verify admits a full-coverage service
        descriptor wholesale and attaches it to the result — the
        precondition for the columnar raft encode."""
        from nomad_tpu.server.plan_apply import (
            OptimisticSnapshot,
            evaluate_plan,
        )

        ns = service_window(svc_job())
        opt = OptimisticSnapshot(ns.store.snapshot(), nt=ns.tindex.nt)
        result = evaluate_plan(opt, ns.plan, None, nt=ns.tindex.nt)
        assert getattr(result, "_sweep", None) is ns.plan._sweep
        full, _, _ = result.full_commit(ns.plan)
        assert full

    def test_service_multi_alloc_rows_fold(self):
        """Count > nodes: several instances land on one node row, so the
        descriptor folds them — counts/starts must partition the
        row-sorted alloc columns exactly, and the commit must still read
        identical to the object path."""
        ns = service_window(svc_job(count=5), n_nodes=2)
        assert ns.ok and not ns.failed
        sweep = ns.plan._sweep
        assert sweep is not None and len(sweep.rows) <= 2
        assert int(sweep.counts.sum()) == 5
        assert sweep.starts[-1] == len(sweep.alloc_ids) == 5
        # Each row's alloc slice really sits on that row's node.
        by_node = {nid: {a.ID for a in v}
                   for nid, v in ns.plan.NodeAllocation.items()}
        for k, nid in enumerate(sweep.node_ids):
            s, e = int(sweep.starts[k]), int(sweep.starts[k + 1])
            assert set(sweep.alloc_ids[s:e]) == by_node[nid]
        assert_same_state(commit_columnar(ns.plan),
                          commit_objects(ns.plan), ns.job, ns.plan)

    def test_service_mixed_window_stays_object(self):
        """Failed placements route the whole eval through the exact
        per-object build: no descriptor, the placed rows commit as plain
        objects, and the failures coalesce into FailedTGAllocs."""
        ns = service_window(svc_job(count=4, cpu=2000), n_nodes=2)
        assert ns.ok and ns.failed  # built exact, with coalesced failures
        assert getattr(ns.plan, "_sweep", None) is None
        placed = sum(len(v) for v in ns.plan.NodeAllocation.values())
        assert placed == 2  # one 2000-CPU alloc fits per 3900-free node
        element, is_sweep = _encode_result(
            ns.plan, PlanResult(NodeAllocation=dict(ns.plan.NodeAllocation)))
        assert not is_sweep and "Alloc" in element
        fsm = commit_objects(ns.plan)
        assert len(fsm.state.allocs_by_job(ns.job.ID)) == placed

    def test_service_network_asks_stay_object(self):
        """Port asks keep the exact per-object path (offers are
        sequential host state): no descriptor even when fully placed."""
        ns = service_window(svc_job(count=3, networks=True))
        assert ns.ok and not ns.failed
        assert getattr(ns.plan, "_sweep", None) is None
        placed = [a for v in ns.plan.NodeAllocation.values() for a in v]
        assert len(placed) == 3
        # The exact build really assigned ports.
        assert any(r.Networks for a in placed
                   for r in a.TaskResources.values())

    def test_service_vanished_node_falls_back(self):
        """A winner row whose node vanished mid-window fails the build —
        the caller re-runs the eval on the exact path — and never leaves
        a descriptor on the abandoned plan."""
        ns = service_window(svc_job(), vanish=True)
        assert ns.ok is False
        assert getattr(ns.plan, "_sweep", None) is None

    def test_served_service_storm_commits_columnar(self):
        """End to end through a live server: a service storm commits as
        service-kind segments (no chain objects), every read surface and
        the client pull map serve the placements, and the sched-stats
        Store counters record the path taken."""
        import time

        from nomad_tpu.server import Server, ServerConfig
        from nomad_tpu.structs.structs import EvalStatusComplete

        srv = Server(ServerConfig(num_schedulers=1, scheduler_window=8,
                                  min_heartbeat_ttl=3600.0,
                                  heartbeat_grace=3600.0))
        srv.establish_leadership()
        try:
            for _ in range(6):
                srv.node_register(mock.node())
            eval_ids = [srv.job_register(svc_job())[0] for _ in range(4)]
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if all((e := srv.state.eval_by_id(eid)) is not None
                       and e.Status == EvalStatusComplete
                       for eid in eval_ids):
                    break
                time.sleep(0.02)
            else:
                raise AssertionError("service storm never completed")
            state = srv.state
            stats = state.columnar_stats()
            assert stats["Batches"].get("service", 0) >= 1
            assert not stats["Batches"].get("system")
            placed = [a for eid in eval_ids
                      for a in state.allocs_by_eval(eid)]
            assert len(placed) == 4 * 5
            assert len({a.ID for a in placed}) == len(placed)
            # The pull signal answers from the columns.
            pulled = {}
            for node in state.nodes():
                pulled.update(state.client_alloc_map(node.ID)[0])
            assert set(pulled) == {a.ID for a in placed}
        finally:
            srv.shutdown()


class TestChunkedSnapshotAtomicity:
    """Streaming-snapshot coverage (ISSUE 13): the chunked persist path
    must be read-equivalent to the monolithic snapshot — including the
    row-slicing of over-large columnar segments — and a restore killed
    at ANY chunk boundary must leave the store bit-identical to its
    pre-restore state (the Restore's staging tables only land at the
    single atomic commit())."""

    def _mutated_fsm(self):
        """A store with every shape a snapshot carries: a columnar
        segment, a promoted row (object chain), and a client update."""
        job, plan = sweep_plan()
        fsm = commit_columnar(plan)
        target = plan._sweep.alloc_ids[3]
        running = fsm.state.alloc_by_id(target).copy()
        running.ClientStatus = AllocClientStatusRunning
        fsm.apply(APPLY_INDEX + 1, MessageType.AllocClientUpdate,
                  {"Alloc": [running]})
        fsm.timetable.witness(APPLY_INDEX + 1, 1000.0)
        return job, plan, fsm

    def test_chunked_roundtrip_identical_to_monolithic(self):
        """snapshot_chunks -> restore_chunks == snapshot -> restore, at a
        chunk size small enough to force BOTH the multi-chunk table path
        and the columnar segment row-slicing path."""
        job, plan, fsm = self._mutated_fsm()
        chunks = list(fsm.snapshot_chunks(chunk_items=3))
        assert len(chunks) > 4  # really streamed
        # The 16-row segment must have been sliced into several.
        seg_chunks = [c for c in chunks if c["kind"] == "columnar_allocs"]
        assert sum(len(c["items"]) for c in seg_chunks) > 1
        # Through the wire shape: msgpack each chunk independently.
        wire = [msgpack.packb(c, use_bin_type=True) for c in chunks]
        r_chunked = FSM()
        r_chunked.restore_chunks(
            msgpack.unpackb(b, raw=False) for b in wire)
        r_mono = roundtrip(fsm)
        assert visible(r_chunked.state, job, plan) \
            == visible(r_mono.state, job, plan)
        assert r_chunked.timetable.serialize() \
            == fsm.timetable.serialize()
        # Sliced segments re-snapshot to the same visible state again
        # (idempotent round-trip, not just one hop).
        r2 = FSM()
        r2.restore_chunks(r_chunked.snapshot_chunks(chunk_items=3))
        assert visible(r2.state, job, plan) \
            == visible(r_mono.state, job, plan)

    def test_restore_killed_at_every_chunk_boundary_keeps_state(self):
        """Kill the chunk stream after k chunks, for EVERY k: the live
        store (and timetable) must stay bit-identical to its pre-restore
        state; only the complete stream lands."""
        job_a, plan_a, fsm_a = self._mutated_fsm()
        chunks = list(fsm_a.snapshot_chunks(chunk_items=3))

        # The victim store has its OWN different prior state.
        job_b, plan_b = sweep_plan(n_nodes=4, count=1)
        fsm_b = commit_columnar(plan_b)
        fsm_b.timetable.witness(APPLY_INDEX, 500.0)
        before_vis = visible(fsm_b.state, job_b, plan_b)
        before_snap = fsm_b.snapshot()
        before_tt = fsm_b.timetable.serialize()

        class Torn(Exception):
            pass

        def torn_stream(n):
            for c in chunks[:n]:
                yield c
            raise Torn(f"stream killed after chunk {n}")

        for k in range(len(chunks)):
            with pytest.raises(Torn):
                fsm_b.restore_chunks(torn_stream(k))
            assert visible(fsm_b.state, job_b, plan_b) == before_vis, \
                f"store mutated by a stream torn after {k} chunks"
            assert fsm_b.snapshot() == before_snap
            assert fsm_b.timetable.serialize() == before_tt

        # The complete stream still installs (the torn attempts left no
        # wedged staging state behind).
        fsm_b.restore_chunks(iter(chunks))
        assert visible(fsm_b.state, job_a, plan_a) \
            == visible(roundtrip(fsm_a).state, job_a, plan_a)
        assert fsm_b.timetable.serialize() == fsm_a.timetable.serialize()
