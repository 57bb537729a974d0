"""Fixed-seed event-stream oracle: the stream IS the state.

Folding every published event into shadow state must reproduce the
StateStore the same applies built — on the object commit path AND the
columnar sweep path (where one AllocationBatch event's row/count
descriptor must expand to exactly the committed placements). The gate
is bidirectional: an `events.publish` drop keeps the FSM perfectly
healthy (NEVER FSM-visible) but must surface here as a fold-vs-store
mismatch — subscriber-visible loss the ring-integrity check cannot see,
because coverage still advances.

Events disabled (`event_buffer_size=0`) must be free: the same storm
produces bit-identical placements and the FSM carries no broker at all
(the disarmed cost is one attribute check on the apply path).
"""

import time
import types

import msgpack
import pytest

from nomad_tpu import mock
from nomad_tpu.events import EventBroker, expand_batch
from nomad_tpu.raft.backend import encode_command
from nomad_tpu.resilience import failpoints
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.fsm import FSM, MessageType
from nomad_tpu.server.plan_apply import _encode_result
from nomad_tpu.structs import PlanResult, to_dict
from nomad_tpu.structs.structs import EvalStatusComplete

from helpers import wait_for  # noqa: E402
from test_columnar_store_equivalence import (  # noqa: E402
    make_node,
    service_window,
    svc_job,
    sweep_plan,
    sys_job,
)

APPLY_INDEX = 100


@pytest.fixture(autouse=True)
def _heal_failpoints():
    failpoints.disarm_all()
    yield
    failpoints.disarm_all()


# ------------------------------------------------------------- plumbing

def fsm_with_broker(size=4096):
    fsm = FSM()
    fsm.events = EventBroker(size=size)
    return fsm


def columnar_entry(plan):
    """The sweep's real wire shape (msgpack round-trip included)."""
    result = PlanResult(NodeUpdate=dict(plan.NodeUpdate),
                        NodeAllocation=dict(plan.NodeAllocation))
    result._sweep = plan._sweep
    element, is_sweep = _encode_result(plan, result)
    assert is_sweep
    # The replicated backend's own encoding: where arrays become lists.
    blob = encode_command(MessageType.ApplySweepBatch, {"Batch": [element]})
    return msgpack.unpackb(blob, raw=False)


def object_entry(plan):
    blob = msgpack.packb(
        (int(MessageType.AllocUpdate),
         to_dict({"Job": plan.Job,
                  "Alloc": [a for placed in plan.NodeAllocation.values()
                            for a in placed]})),
        use_bin_type=True)
    return msgpack.unpackb(blob, raw=False)


def drain(sub, idle=0.3, timeout=15):
    """Pop frames until the stream goes idle. The callers quiesce the
    workload first, so idle == drained."""
    frames = []
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        frame = sub.next(timeout=idle)
        if frame is None:
            if frames or sub.status()[0]:
                break
            continue
        assert "Dropped" not in frame, "oracle subscriber overflowed"
        frames.append(frame)
    return frames


def fold(frames):
    """Fold an event stream into shadow state: the consumer contract —
    summaries carry enough to reconstruct membership and placement."""
    s = types.SimpleNamespace(nodes={}, jobs={}, evals={}, allocs={},
                              services={}, batch_events=0)
    last = 0
    for frame in frames:
        assert frame["Index"] > last, "frames out of raft-index order"
        last = frame["Index"]
        for ev in frame["Events"]:
            _fold_one(s, ev)
    return s


def _fold_one(s, ev):
    t, p = ev["Type"], ev["Payload"]
    if t in ("NodeRegistered", "NodeStatusUpdated"):
        s.nodes[p["ID"]] = p["Status"]
    elif t == "NodeDeregistered":
        s.nodes.pop(p["ID"], None)
    elif t == "NodeDrainUpdated":
        pass
    elif t in ("JobRegistered", "PeriodicLaunchUpserted"):
        if t == "JobRegistered":
            s.jobs[p["ID"]] = p
    elif t in ("JobDeregistered", "PeriodicLaunchDeleted"):
        if t == "JobDeregistered":
            s.jobs.pop(p["ID"], None)
    elif t == "EvalUpdated":
        s.evals[p["ID"]] = p["Status"]
    elif t == "EvalDeleted":
        s.evals.pop(p["ID"], None)
    elif t in ("AllocUpdated", "AllocPlaced"):
        cur = s.allocs.setdefault(p["ID"], {})
        cur.update({k: v for k, v in p.items() if v != ""})
    elif t == "AllocClientUpdated":
        cur = s.allocs.get(p["ID"])
        if cur is not None:
            cur["ClientStatus"] = p["ClientStatus"]
            cur["DesiredStatus"] = p["DesiredStatus"]
    elif t == "AllocDeleted":
        s.allocs.pop(p["ID"], None)
    elif t == "AllocationBatchCommitted":
        s.batch_events += 1
        for row in expand_batch(ev):
            _fold_one(s, row)
    elif t == "ServiceRegistered":
        s.services[p["ID"]] = p
    elif t == "ServiceDeregistered":
        s.services.pop(p["ID"], None)
    else:
        raise AssertionError(f"fold has no rule for event type {t!r}")


def placement_map(state):
    return {a.ID: (a.JobID, a.NodeID) for a in state.allocs()}


# ----------------------------------------------------------------- gates

class TestCommitPathParity:
    def test_sweep_publishes_one_batch_event_matching_columns(self):
        """A 16-alloc system sweep is ONE AllocationBatch event whose
        descriptor names exactly the committed rows — no per-alloc
        materialization on the publish path."""
        job, plan = sweep_plan()
        fsm = fsm_with_broker()
        sub = fsm.events.subscribe(from_index=0)
        msg, payload = columnar_entry(plan)
        fsm.apply(APPLY_INDEX, MessageType(msg), payload)
        frame = sub.next(timeout=1)
        batch = [e for e in frame["Events"]
                 if e["Topic"] == "AllocationBatch"]
        assert len(batch) == 1
        p = batch[0]["Payload"]
        sweep = plan._sweep
        assert p["Count"] == len(sweep.alloc_ids) == sum(p["Counts"])
        assert p["AllocIDs"] == list(sweep.alloc_ids)
        assert p["Kind"] == "system"
        assert set(p["AllocIDs"]) \
            == {a.ID for a in fsm.state.allocs_by_job(job.ID)}

    def test_fanout_fold_matches_store_on_both_paths(self):
        """The same sweep committed columnar (fan-out expanded at read
        time) and per-object folds to the SAME shadow placements, and
        both match their stores exactly."""
        job, plan = sweep_plan()
        folds = {}
        for path, entry in (("columnar", columnar_entry(plan)),
                            ("object", object_entry(plan))):
            fsm = fsm_with_broker()
            sub = fsm.events.subscribe(from_index=0, fanout=True)
            msg, payload = entry
            fsm.apply(APPLY_INDEX, MessageType(msg), payload)
            shadow = fold(drain(sub, idle=0.05, timeout=2))
            got = {aid: (d["JobID"], d["NodeID"])
                   for aid, d in shadow.allocs.items()}
            assert got == placement_map(fsm.state), path
            folds[path] = got
        assert folds["columnar"] == folds["object"]

    def test_service_window_batch_event_is_service_kind(self):
        """The pipelined service fast path's columnar commit publishes
        its batch event with Kind=service and the same descriptor
        parity."""
        ns = service_window(svc_job())
        assert ns.ok and not ns.failed
        fsm = fsm_with_broker()
        sub = fsm.events.subscribe(from_index=0, fanout=True)
        raw = fsm.events.subscribe(from_index=0)
        msg, payload = columnar_entry(ns.plan)
        fsm.apply(APPLY_INDEX, MessageType(msg), payload)
        shadow = fold(drain(sub, idle=0.05, timeout=2))
        # The un-expanded stream carries exactly ONE batch event...
        frame = raw.next(timeout=1)
        assert [e["Payload"]["Kind"] for e in frame["Events"]
                if e["Topic"] == "AllocationBatch"] == ["service"]
        # ...and its fan-out expansion folds to the store's placements.
        got = {aid: (d["JobID"], d["NodeID"])
               for aid, d in shadow.allocs.items()}
        assert got == placement_map(fsm.state)
        assert all(d["Kind"] == "service" for d in shadow.allocs.values())


def _storm_server(event_buffer_size=4096):
    return Server(ServerConfig(num_schedulers=1, scheduler_window=8,
                               event_buffer_size=event_buffer_size,
                               min_heartbeat_ttl=3600.0,
                               heartbeat_grace=3600.0))


def _wait_complete(srv, eval_ids, timeout=30):
    wait_for(lambda: all(
        (e := srv.state.eval_by_id(eid)) is not None
        and e.Status == EvalStatusComplete for eid in eval_ids),
        timeout=timeout, msg="storm evals never completed")


class TestLiveStormOracle:
    @pytest.mark.parametrize("columnar", [True, False])
    def test_storm_fold_matches_store(self, columnar):
        """A live service storm through a real server — placements, a
        deregister's evictions, eval lifecycle — folds from the event
        stream into exactly the store's membership, on BOTH service
        commit paths (columnar batch events vs per-object updates). What
        selects the per-object path is the job: a network ask (ports are
        per-placement offers) keeps its plans as objects."""
        srv = _storm_server()
        srv.establish_leadership()
        try:
            broker = srv.fsm.events
            sub = broker.subscribe(from_index=0, fanout=True,
                                   queue_size=100_000)
            for i in range(6):
                srv.node_register(make_node(i))
            jobs = [svc_job(networks=not columnar) for _ in range(4)]
            eval_ids = [srv.job_register(j)[0] for j in jobs]
            _wait_complete(srv, eval_ids)
            # Deregister one job: its evictions must stream as
            # per-object updates on either path.
            dereg_eval, _ = srv.job_deregister(jobs[0].ID)
            _wait_complete(srv, [dereg_eval])
            state = srv.state
            wait_for(lambda: broker.stats()["Tail"]
                     >= state.latest_index(), timeout=10)
            shadow = fold(drain(sub))

            assert set(shadow.nodes) == {n.ID for n in state.nodes()}
            assert set(shadow.jobs) == {j.ID for j in state.jobs()}
            assert {aid: v[1] for aid, v in placement_map(state).items()} \
                == {aid: d["NodeID"] for aid, d in shadow.allocs.items()}
            store_evals = {e.ID: e.Status for e in state.evals()}
            assert shadow.evals == store_evals
            # Desired-status agreement: the deregistered job's allocs
            # fold to the same terminal intent the store holds.
            for a in state.allocs():
                if a.JobID == jobs[0].ID:
                    assert shadow.allocs[a.ID]["DesiredStatus"] \
                        == a.DesiredStatus
            # Path check: batch-expanded rows (they alone carry the
            # descriptor's Kind marker) iff the columnar path committed.
            batch_rows = [aid for aid, d in shadow.allocs.items()
                          if "Kind" in d]
            batches = state.columnar_stats()["Batches"]
            if columnar:
                assert batch_rows
                assert batches.get("service", 0) >= 1
            else:
                assert not batch_rows
                assert not batches
        finally:
            srv.shutdown()

    def test_events_disabled_is_free_and_bit_identical(self):
        """The same fixed system storm with the broker off: NO broker
        object exists (the apply path pays one attribute check), and
        placements are bit-identical to the armed run."""
        def run(event_buffer_size):
            srv = _storm_server(event_buffer_size=event_buffer_size)
            srv.establish_leadership()
            try:
                for i in range(6):
                    srv.node_register(make_node(i))
                eval_ids = []
                for k in range(3):
                    job = sys_job(count=1)  # system jobs validate count=1
                    job.ID = f"ev-storm-{k}"
                    job.Name = job.ID
                    job.init_fields()
                    eval_ids.append(srv.job_register(job)[0])
                _wait_complete(srv, eval_ids)
                placements = sorted((a.JobID, a.Name, a.NodeID)
                                    for a in srv.state.allocs())
                return placements, srv.fsm.events
            finally:
                srv.shutdown()

        armed, broker = run(4096)
        disarmed, no_broker = run(0)
        assert broker is not None and no_broker is None
        assert armed == disarmed
        assert armed  # the storm really placed

    def test_publish_drop_is_fsm_invisible_but_fold_visible(self):
        """The events.publish failpoint's drop mode: state commits
        perfectly (never FSM-visible), stream coverage advances with no
        gap error — and the ONLY detector is this fold, which comes up
        short exactly one entry."""
        fsm = fsm_with_broker()
        sub = fsm.events.subscribe(from_index=0)
        failpoints.arm_from_spec("events.publish=drop:count=1")
        lost, kept = make_node(0), make_node(1)
        fsm.apply(1, MessageType.NodeRegister, {"Node": to_dict(lost)})
        fsm.apply(2, MessageType.NodeRegister, {"Node": to_dict(kept)})
        shadow = fold(drain(sub, idle=0.05, timeout=2))
        store_nodes = {n.ID for n in fsm.state.nodes()}
        assert store_nodes == {lost.ID, kept.ID}  # FSM never saw it
        assert set(shadow.nodes) == {kept.ID}  # the fold did
        assert fsm.events.stats()["Tail"] == 2  # coverage advanced
        # And a late subscriber replays without a gap error — the loss
        # is silent at the ring level, by design.
        late = fsm.events.subscribe(from_index=0)
        assert late.next(timeout=1)["Index"] == 2
