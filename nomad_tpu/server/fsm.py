"""FSM: the replicated state machine (reference: nomad/fsm.go).

Every cluster mutation is a typed message applied through the FSM. In a
replicated deployment messages flow through the Raft log; in dev mode the
DevRaft backend assigns indexes and applies directly. Either way the FSM is
the single write path into the state store, and the hook point where the
leader's eval broker / blocked-evals tracker observe state transitions
(reference: fsm.go:99-144, 158-164, 320-328).
"""

from __future__ import annotations

import enum
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from nomad_tpu.analysis.replica_digest import chaos_corrupt, effect_of
from nomad_tpu.events.builders import build_events
from nomad_tpu.resilience import failpoints
from nomad_tpu.server.timetable import TimeTable
from nomad_tpu.state.state_store import StateStore, SweepSegment
from nomad_tpu.telemetry import metrics, trace
from nomad_tpu.structs import (
    Allocation,
    Evaluation,
    Job,
    Node,
    PeriodicLaunch,
    ServiceRegistration,
    column_list,
    from_dict,
    to_dict,
)
from nomad_tpu.structs.structs import (
    EvalStatusBlocked,
    JobStatusRunning,
    NodeStatusReady,
)


logger = logging.getLogger("nomad.fsm")

# Streaming-snapshot chunk bound: objects (or columnar rows) per chunk.
# Small enough that one chunk's encode/persist never stalls the apply
# loop noticeably; large enough that a 1M-row store is ~hundreds of
# chunks, not tens of thousands.
SNAPSHOT_CHUNK_ITEMS = 2048


def _slice_segment(seg: Dict[str, Any], lo: int, hi: int) -> Dict[str, Any]:
    """Row-slice one serialized SweepSegment. Each slice restores as its
    own segment; every read surface (by id/node/job/eval, dumps, client
    maps) is the union over segments, so the split is read-equivalent."""
    out = dict(seg)
    for key in ("AllocIDs", "Names", "NodeIDs"):
        out[key] = seg[key][lo:hi]
    if seg.get("TGIdx"):
        out["TGIdx"] = seg["TGIdx"][lo:hi]
    return out


class MessageType(enum.IntEnum):
    """(reference: structs.go:40-57 MessageType constants)"""

    NodeRegister = 0
    NodeDeregister = 1
    NodeUpdateStatus = 2
    NodeUpdateDrain = 3
    JobRegister = 4
    JobDeregister = 5
    EvalUpdate = 6
    EvalDelete = 7
    AllocUpdate = 8
    AllocClientUpdate = 9
    PeriodicLaunchType = 10
    PeriodicLaunchDelete = 11
    ServiceSync = 12
    # Columnar sweep-batch commit (beyond reference v0.4): one entry
    # carries a whole admitted system-sweep chunk as columnar arrays
    # (alloc ids, instance names, per-TG frozen templates, per-row usage
    # delta) instead of N per-alloc payloads.
    ApplySweepBatch = 13


# Metric leaf names per message type (reference: the MeasureSince keys in
# each fsm.go apply handler, fsm.go:147-430).
_MSG_METRIC = {
    MessageType.NodeRegister: "register_node",
    MessageType.NodeDeregister: "deregister_node",
    MessageType.NodeUpdateStatus: "node_status_update",
    MessageType.NodeUpdateDrain: "node_drain_update",
    MessageType.JobRegister: "register_job",
    MessageType.JobDeregister: "deregister_job",
    MessageType.EvalUpdate: "update_eval",
    MessageType.EvalDelete: "delete_eval",
    MessageType.AllocUpdate: "alloc_update",
    MessageType.AllocClientUpdate: "alloc_client_update",
    MessageType.PeriodicLaunchType: "periodic_launch",
    MessageType.PeriodicLaunchDelete: "periodic_launch_delete",
    MessageType.ServiceSync: "service_sync",
    MessageType.ApplySweepBatch: "sweep",
}


class FSM:
    """Applies typed messages to the state store."""

    def __init__(self, state: Optional[StateStore] = None):
        self.state = state or StateStore()
        # Every replica witnesses (index, time) on apply so a new leader has
        # a populated index<->time map after failover (reference: fsm.go:147
        # witnesses in Apply; fsm.go:430-551 persists it in the snapshot).
        self.timetable = TimeTable()
        # Event broker (nomad_tpu/events/): attached by the server when
        # the event stream is enabled. None keeps the apply path's event
        # cost at this one attribute check. Fed on EVERY replica, so any
        # server in the region can serve a gapless resume after failover.
        self.events = None
        # Replica state digest (analysis/replica_digest.py): attached by
        # the server when digest verification is enabled. None keeps the
        # apply path's digest cost at this one attribute check.
        self.digest = None
        # Leader-side observers (broker, blocked evals, periodic dispatch)
        # registered by the server when it holds leadership.
        self.on_eval_update: Optional[Callable[[Evaluation], None]] = None
        self.on_node_ready: Optional[Callable[[Node], None]] = None
        self.on_job_upsert: Optional[Callable[[Job], None]] = None
        self.on_job_delete: Optional[Callable[[str], None]] = None
        self.on_alloc_terminal: Optional[Callable[[Allocation], None]] = None

    def apply(self, index: int, msg_type: MessageType, payload: Dict[str, Any]) -> Any:
        """(reference: fsm.go:99-144 Apply dispatch; each handler is timed
        under nomad.fsm.<op> as in fsm.go:147 MeasureSince, and — inside
        an active trace — spanned as fsm.<op>, child-only so background
        applies never mint traces)"""
        # The witness is REPLICA-LOCAL wall time by design (reference:
        # fsm.go:147): each replica records when IT applied the index, for
        # operator time->index queries. It never feeds replicated tables
        # or events; snapshots ship it only as a hint map.
        # lint: allow(apply_pure, replica-local index->time witness map)
        self.timetable.witness(index, time.time())
        handler = _HANDLERS[msg_type]
        leaf = _MSG_METRIC.get(msg_type, msg_type.name.lower())
        broker = self.events
        events = None
        with metrics.measure(("nomad", "fsm", leaf)):
            try:
                with trace.span("fsm." + leaf, index=index):
                    result = handler(self, index, payload)
                    if broker is not None:
                        # Build INSIDE the span so publish stamps this
                        # entry's fsm trace/span ids onto its events.
                        try:
                            events = build_events(self, msg_type, payload)
                        except Exception:
                            # A builder bug must not fail a consensus-
                            # committed entry (the handler already applied);
                            # the entry publishes empty and the loss shows
                            # up in the equivalence fold.
                            logger.exception(
                                "event builder failed at index %d", index)
                # Fold only SUCCESSFUL applies into the digest chain (a
                # handler exception skips this via the raise): every replica
                # applies the same entries, so every replica folds the same
                # sequence.
                if self.digest is not None:
                    self._digest_fold(index, msg_type, payload)
                return result
            finally:
                # Publish in the finally — even a failed handler releases the
                # broker's index reservation (empty batch), so one poisoned
                # entry can never wedge every later subscriber.
                if broker is not None:
                    broker.publish(index, events or ())

    def _digest_fold(self, index: int, msg_type: MessageType,
                     payload: Dict[str, Any]) -> None:
        """Fold this entry's post-apply effect into the replica digest
        chain. Any failure here is CONTAINED: the entry is consensus-
        committed and already applied, so a broken fold must never fail
        it — the digest marks itself unsynced (verification pauses until
        the next snapshot reseed) instead."""
        digest = self.digest
        try:
            if (self.on_eval_update is None
                    and failpoints.fire("fsm.digest.mutate") == "drop"):
                # Silent store corruption, injected BEFORE the effect
                # readback: this replica folds the corrupt value while
                # healthy replicas fold the clean one — the exact
                # divergence the checkpoint exchange exists to catch.
                # NON-leader replicas only (leader-side observers are the
                # leadership tell): the leader's chain is the reference
                # the quarantined follower reinstalls from, so corrupting
                # it would make the corruption authoritative — and the
                # guard comes FIRST so a count-bounded arm is consumed
                # by a replica that will actually corrupt, never burned
                # by a leader-side skip.
                chaos_corrupt(self.state, index, int(msg_type), payload)
            digest.fold(index, int(msg_type),
                        effect_of(self.state, index, int(msg_type),
                                  payload))
        except Exception:
            logger.exception("digest fold failed at index %d", index)
            digest.mark_unsynced(f"fold failed at index {index}")

    # ------------------------------------------------------------- handlers
    def _apply_node_register(self, index: int, req: Dict[str, Any]):
        node = from_dict(Node, req["Node"]) if isinstance(req["Node"], dict) \
            else req["Node"]
        existing = self.state.node_by_id(node.ID)
        self.state.upsert_node(index, node)
        # Re-registration to ready unblocks evals by class (fsm.go:158-164).
        if (node.Status == NodeStatusReady
                and (existing is None or existing.Status != NodeStatusReady)
                and self.on_node_ready is not None):
            self.on_node_ready(node)
        return None

    def _apply_node_deregister(self, index: int, req: Dict[str, Any]):
        self.state.delete_node(index, req["NodeID"])
        return None

    def _apply_node_status_update(self, index: int, req: Dict[str, Any]):
        self.state.update_node_status(index, req["NodeID"], req["Status"])
        if req["Status"] == NodeStatusReady and self.on_node_ready is not None:
            node = self.state.node_by_id(req["NodeID"])
            if node is not None:
                self.on_node_ready(node)
        return None

    def _apply_node_drain_update(self, index: int, req: Dict[str, Any]):
        self.state.update_node_drain(index, req["NodeID"], req["Drain"])
        return None

    def _apply_job_register(self, index: int, req: Dict[str, Any]):
        job = from_dict(Job, req["Job"]) if isinstance(req["Job"], dict) \
            else req["Job"]
        self.state.upsert_job(index, job)
        if self.on_job_upsert is not None:
            self.on_job_upsert(self.state.job_by_id(job.ID))
        return None

    def _apply_job_deregister(self, index: int, req: Dict[str, Any]):
        self.state.delete_job(index, req["JobID"])
        if self.on_job_delete is not None:
            self.on_job_delete(req["JobID"])
        return None

    def _apply_eval_update(self, index: int, req: Dict[str, Any]):
        evals: List[Evaluation] = [
            from_dict(Evaluation, e) if isinstance(e, dict) else e
            for e in req["Evals"]]
        self.state.upsert_evals(index, evals)
        # Leader enqueues runnable evals / blocks blocked ones (fsm.go:320-328).
        if self.on_eval_update is not None:
            for ev in evals:
                self.on_eval_update(ev)
        return None

    def _apply_eval_delete(self, index: int, req: Dict[str, Any]):
        self.state.delete_eval(index, req.get("Evals", []), req.get("Allocs", []))
        return None

    def _apply_alloc_update(self, index: int, req: Dict[str, Any]):
        # Two shapes: {"Job", "Alloc"} for one plan (reference parity,
        # fsm.go:356 applyAllocUpdate), or {"Batch": [{"Job", "Alloc"}, ...]}
        # when the plan applier commits several verified plans as one log
        # entry — the whole group lands in ONE state-store transaction (one
        # lock/commit/notify/job-status pass), which is where the per-plan
        # apply cost goes at storm rates.
        groups = req.get("Batch")
        if groups is None:
            groups = [req]
        allocs: List[Allocation] = []
        for group in groups:
            group_allocs = [
                from_dict(Allocation, a) if isinstance(a, dict) else a
                for a in group["Alloc"]]
            # Attach the shared job if provided (plan apply normalization).
            job = group.get("Job")
            if isinstance(job, dict):
                job = from_dict(Job, job)
            for alloc in group_allocs:
                if alloc.Job is None and job is not None:
                    alloc.Job = job
            allocs.extend(group_allocs)
        self.state.upsert_allocs(index, allocs)
        return None

    def _apply_sweep_batch(self, index: int, req: Dict[str, Any]):
        """Columnar sweep-batch commit: each group is either a per-object
        {"Job","Alloc"} group (the AllocUpdate shape — mixed entries carry
        the window's ordinary plans too) or a {"Job","Sweep","Updates"}
        group whose placements land as ONE SweepSegment scatter. The
        `state.store.commit` failure seam fires in the PLAN APPLIER,
        before raft.apply — an entry that reaches this handler has
        consensus-committed and must apply deterministically on every
        replica (an injected failure here would survive in the durable
        log and duplicate the batch on replay)."""
        groups = req.get("Batch")
        if groups is None:
            groups = [req]
        obj_allocs: List[Allocation] = []
        n_sweep = 0
        n_service = 0
        # One store transaction for the WHOLE entry: a sweep group's
        # stops, its segment, and any object co-groups land in separate
        # write calls below, and a blocking query woken between them
        # could otherwise observe a torn entry (an eviction committed
        # with its replacement not yet visible — exactly what the
        # eviction+placement-one-entry contract forbids). The lock is
        # reentrant; the inner writes re-acquire freely.
        with self.state.transaction():
            for group in groups:
                job = group.get("Job")
                if isinstance(job, dict):
                    job = from_dict(Job, job)
                sweep = group.get("Sweep")
                if sweep is None:
                    group_allocs = [
                        from_dict(Allocation, a) if isinstance(a, dict)
                        else a
                        for a in group.get("Alloc", ())]
                    for alloc in group_allocs:
                        if alloc.Job is None and job is not None:
                            alloc.Job = job
                    obj_allocs.extend(group_allocs)
                    continue
                updates = [
                    from_dict(Allocation, a) if isinstance(a, dict) else a
                    for a in group.get("Updates", ())]
                for alloc in updates:
                    if alloc.Job is None and job is not None:
                        alloc.Job = job
                if updates:
                    # Stop-then-place: the plan's exact-path evictions
                    # commit before its columnar placements, same order
                    # the object path guarantees within one entry.
                    self.state.upsert_allocs(index, updates)
                templates = [
                    t if isinstance(t, Allocation)
                    else from_dict(Allocation, t)
                    for t in sweep["Templates"]]
                for t in templates:
                    if t.Job is None and job is not None:
                        t.Job = job
                # Columns as the entry carries them: arrays and lists
                # from DevRaft, lists from a decoded log entry. Nothing
                # is copied, and the per-allocation node column is left
                # to the segment (it expands on the first read).
                row_node_ids = column_list(sweep["RowNodeIDs"])
                seg = SweepSegment(
                    index=index,
                    job_id=templates[0].JobID,
                    eval_id=templates[0].EvalID,
                    templates=templates,
                    tg_idx=column_list(sweep["TGIdx"]),
                    alloc_ids=column_list(sweep["AllocIDs"]),
                    names=column_list(sweep["Names"]),
                    row_node_ids=row_node_ids,
                    counts=np.asarray(sweep["Counts"], dtype=np.int64),
                    kind=sweep.get("Kind", "system"))
                self.state.apply_sweep_segment(
                    index, seg,
                    rows=np.asarray(sweep["Rows"], dtype=np.int64),
                    delta=np.asarray(sweep["Delta"], dtype=np.float32),
                    row_node_ids=row_node_ids,
                    epoch=int(sweep.get("Epoch", -1)))
                n_sweep += len(seg.alloc_ids)
                if seg.kind == "service":
                    n_service += len(seg.alloc_ids)
            if obj_allocs:
                self.state.upsert_allocs(index, obj_allocs)
        if n_sweep:
            metrics.incr_counter(("nomad", "fsm", "sweep", "allocs"),
                                 n_sweep)
        if n_service:
            # Service-window rows committed columnar, vs the system-sweep
            # rows the total above also counts — the per-path split the
            # sched-stats `Store` block surfaces.
            metrics.incr_counter(("nomad", "fsm", "sweep", "service_allocs"),
                                 n_service)
        return None

    def _apply_alloc_client_update(self, index: int, req: Dict[str, Any]):
        for a in req["Alloc"]:
            alloc = from_dict(Allocation, a) if isinstance(a, dict) else a
            # A client can report status for an alloc the server already
            # GC'd (its sync loop races system-gc). Skip it up front:
            # letting the store raise would poison the whole COALESCED
            # update batch and lose every other client's statuses riding
            # in it. (A pre-check rather than catching KeyError, which
            # would also mask listener bugs downstream of the write.)
            if self.state.alloc_by_id(alloc.ID) is None:
                logger.debug("client update for unknown alloc %s dropped",
                             alloc.ID)
                continue
            self.state.update_alloc_from_client(index, alloc)
            # Terminal client status frees capacity: unblock by node class
            # (reference: fsm.go:395-428).
            updated = self.state.alloc_by_id(alloc.ID)
            if (updated is not None and updated.terminal_status()
                    and self.on_alloc_terminal is not None):
                self.on_alloc_terminal(updated)
        return None

    def _apply_periodic_launch(self, index: int, req: Dict[str, Any]):
        launch = req["Launch"]
        if isinstance(launch, dict):
            launch = from_dict(PeriodicLaunch, launch)
        self.state.upsert_periodic_launch(index, launch)
        return None

    def _apply_periodic_launch_delete(self, index: int, req: Dict[str, Any]):
        self.state.delete_periodic_launch(index, req["JobID"])
        return None

    def _apply_service_sync(self, index: int, req: Dict[str, Any]):
        """Service registry sync: batched upserts + deregistrations from one
        node's service manager (or a server's self-registration)."""
        upserts = [from_dict(ServiceRegistration, r) if isinstance(r, dict)
                   else r for r in req.get("Upserts", ())]
        if upserts:
            self.state.upsert_services(index, upserts)
        deletes = list(req.get("Deletes", ()))
        if deletes:
            self.state.delete_services(index, deletes)
        return None

    # ------------------------------------------------------ snapshot/restore
    def snapshot(self) -> Dict[str, Any]:
        """Serialize the full FSM state (reference: fsm.go:430-551).
        Columnar sweep segments round-trip COLUMNAR ("columnar_allocs"):
        a million sweep-placed rows persist as id/name/node columns plus
        one template per task group, never as per-alloc object dicts."""
        snap = self.state.snapshot()
        chain_allocs, col_segments = snap.alloc_dump()
        return {
            "nodes": [to_dict(n) for n in snap.nodes()],
            "jobs": [to_dict(j) for j in snap.jobs()],
            "evals": [to_dict(e) for e in snap.evals()],
            "allocs": [to_dict(a) for a in chain_allocs],
            "columnar_allocs": col_segments,
            "periodic_launches": [to_dict(p) for p in snap.periodic_launches()],
            "services": [to_dict(s) for s in snap.services()],
            "indexes": {t: snap.get_index(t)
                        for t in ("nodes", "jobs", "evals", "allocs",
                                  "periodic_launch", "services")},
            "timetable": self.timetable.serialize(),
            # Chain value at the snapshot watermark: a replica restoring
            # this snapshot reseeds and keeps the chain canonical.
            "digest": (self.digest.snapshot_state()
                       if self.digest is not None else None),
        }

    def snapshot_chunks(self, chunk_items: int = SNAPSHOT_CHUNK_ITEMS):
        """Stream the FSM state as BOUNDED chunks (the streaming-snapshot
        persist path). The MVCC snapshot is pinned EAGERLY — before this
        returns — so the caller can capture the watermark under the apply
        lock and then iterate entirely off the apply path: chunks resolve
        through the pinned watermark while later raft entries keep
        committing. Each chunk is one small dict (a header, or up to
        `chunk_items` objects of one table); an oversized columnar segment
        is sliced by rows into several read-equivalent segments so no
        single chunk scales with sweep size."""
        snap = self.state.snapshot()
        timetable = self.timetable.serialize()
        # Pinned eagerly with the MVCC snapshot: the caller holds the
        # apply lock here, so the chain value matches the watermark.
        digest_state = (self.digest.snapshot_state()
                        if self.digest is not None else None)

        def batched(kind, items):
            for i in range(0, len(items), chunk_items):
                yield {"kind": kind, "items": items[i:i + chunk_items]}

        def gen():
            yield {
                "kind": "header",
                "indexes": {t: snap.get_index(t)
                            for t in ("nodes", "jobs", "evals", "allocs",
                                      "periodic_launch", "services")},
                "timetable": timetable,
                "digest": digest_state,
            }
            yield from batched("nodes", [to_dict(n) for n in snap.nodes()])
            yield from batched("jobs", [to_dict(j) for j in snap.jobs()])
            yield from batched("evals", [to_dict(e) for e in snap.evals()])
            chain_allocs, col_segments = snap.alloc_dump()
            yield from batched("allocs", [to_dict(a) for a in chain_allocs])
            # Columnar segments: group whole segments up to chunk_items
            # rows per chunk; slice a lone over-large segment by rows
            # (each slice restores as its own segment — identical on
            # every read surface, `alloc_dump` partition included).
            group: list = []
            rows = 0
            for seg in col_segments:
                n = len(seg["AllocIDs"])
                if n > chunk_items:
                    if group:
                        yield {"kind": "columnar_allocs", "items": group}
                        group, rows = [], 0
                    for i in range(0, n, chunk_items):
                        yield {"kind": "columnar_allocs",
                               "items": [_slice_segment(seg, i,
                                                        i + chunk_items)]}
                    continue
                if rows + n > chunk_items and group:
                    yield {"kind": "columnar_allocs", "items": group}
                    group, rows = [], 0
                group.append(seg)
                rows += n
            if group:
                yield {"kind": "columnar_allocs", "items": group}
            yield from batched(
                "periodic_launches",
                [to_dict(p) for p in snap.periodic_launches()])
            yield from batched("services",
                               [to_dict(s) for s in snap.services()])

        return gen()

    def restore_chunks(self, chunks) -> None:
        """Chunk-by-chunk restore with a SINGLE atomic cutover: every chunk
        loads into the Restore's staging tables; only the final commit()
        swaps them in. An iterator that raises (torn stream, injected
        chunk fault, killed install) leaves the live store — and the
        timetable — bit-identical to its pre-restore state."""
        r = self.state.restore()
        timetable = None
        digest_state = None
        loaders = {
            "nodes": (Node, r.node_restore),
            "jobs": (Job, r.job_restore),
            "evals": (Evaluation, r.eval_restore),
            "allocs": (Allocation, r.alloc_restore),
            "periodic_launches": (PeriodicLaunch, r.periodic_launch_restore),
            "services": (ServiceRegistration, r.service_restore),
        }
        for chunk in chunks:
            kind = chunk.get("kind")
            if kind == "header":
                for t, idx in (chunk.get("indexes") or {}).items():
                    r.index_restore(t, idx)
                timetable = chunk.get("timetable")
                digest_state = chunk.get("digest")
            elif kind == "columnar_allocs":
                for seg in chunk.get("items", ()):
                    r.columnar_restore(seg)
            elif kind in loaders:
                cls, load = loaders[kind]
                for item in chunk.get("items", ()):
                    load(from_dict(cls, item) if isinstance(item, dict)
                         else item)
            else:
                raise ValueError(f"unknown snapshot chunk kind {kind!r}")
        r.commit()
        if timetable:
            self.timetable.deserialize(timetable)
        if self.digest is not None:
            if digest_state:
                # Adopt the snapshot's chain value — folding resumes at
                # the watermark and the chain stays canonical.
                self.digest.reseed(digest_state["index"],
                                   digest_state["digest"])
            else:
                # Snapshot predates digests (or is an empty quarantine
                # wipe): fold but never verify until the next reseed —
                # an unverifiable chain must not raise false alarms.
                self.digest.mark_unsynced("restored snapshot without "
                                          "a digest chain value")
        if self.events is not None:
            # Snapshot install: entries below the restored watermark were
            # never applied here, so the ring cannot serve them. Raise
            # the gap floor; resuming subscribers below it re-snapshot.
            self.events.reset(self.state.latest_index())

    def restore(self, data: Dict[str, Any]) -> None:
        """(reference: fsm.go:444-551) One code path with the chunked
        restore: a monolithic snapshot dict is just a stream of
        one-table chunks."""
        def gen():
            yield {"kind": "header", "indexes": data.get("indexes", {}),
                   "timetable": data.get("timetable"),
                   "digest": data.get("digest")}
            for kind in ("nodes", "jobs", "evals", "allocs",
                         "columnar_allocs", "periodic_launches", "services"):
                items = list(data.get(kind, ()))
                if items:
                    yield {"kind": kind, "items": items}

        self.restore_chunks(gen())


_HANDLERS = {
    MessageType.NodeRegister: FSM._apply_node_register,
    MessageType.NodeDeregister: FSM._apply_node_deregister,
    MessageType.NodeUpdateStatus: FSM._apply_node_status_update,
    MessageType.NodeUpdateDrain: FSM._apply_node_drain_update,
    MessageType.JobRegister: FSM._apply_job_register,
    MessageType.JobDeregister: FSM._apply_job_deregister,
    MessageType.EvalUpdate: FSM._apply_eval_update,
    MessageType.EvalDelete: FSM._apply_eval_delete,
    MessageType.AllocUpdate: FSM._apply_alloc_update,
    MessageType.AllocClientUpdate: FSM._apply_alloc_client_update,
    MessageType.PeriodicLaunchType: FSM._apply_periodic_launch,
    MessageType.PeriodicLaunchDelete: FSM._apply_periodic_launch_delete,
    MessageType.ServiceSync: FSM._apply_service_sync,
    MessageType.ApplySweepBatch: FSM._apply_sweep_batch,
}


class DevRaft:
    """Single-node consensus stand-in: assigns monotone indexes and applies
    synchronously. The replicated log implementation plugs in behind the same
    `apply` seam (reference boot path: server.go:608 setupRaft DevMode)."""

    def __init__(self, fsm: FSM):
        self.fsm = fsm
        self._lock = threading.Lock()
        self._index = max(1, fsm.state.latest_index())

    def apply(self, msg_type: MessageType, payload: Dict[str, Any]) -> int:
        """Apply a mutation; returns the index it committed at."""
        with self._lock:
            self._index += 1
            index = self._index
            # Index assignment happens under the lock but the FSM apply
            # below runs outside it, so concurrent dev-mode applies can
            # reach the broker out of index order. Reserving HERE — still
            # in assignment order — lets the broker hold an early batch
            # until its predecessors publish, keeping the stream strictly
            # index-ordered. (The replicated backend applies in order and
            # never reserves.)
            broker = self.fsm.events
            if broker is not None:
                broker.reserve(index)
        self.fsm.apply(index, msg_type, payload)
        return index

    @property
    def last_index(self) -> int:
        return self._index
