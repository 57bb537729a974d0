"""MVCC in-memory state store with secondary indexes, snapshots, and watches.

Equivalent to the reference's go-memdb-backed StateStore (reference:
nomad/state/state_store.go, nomad/state/schema.go) but designed around
per-key version chains instead of immutable radix trees:

  * every write appends (index, value) to the key's version chain and updates
    a live dict; `snapshot()` is O(1) — it just pins the current index as a
    watermark and resolves reads through the chains;
  * secondary indexes (allocs by node/job/eval, evals by job, periodic jobs)
    are ever-membership sets — valid because the relation keys (NodeID, JobID,
    EvalID) are immutable for the life of an object — resolved through the
    primary chains at the snapshot watermark and pruned on compaction;
  * mutations collect watch Items which are notified after commit, powering
    blocking queries (reference: nomad/rpc.go:294-349).

Writes take an externally supplied monotonically increasing `index` (the Raft
log index in a replicated deployment, a local counter in dev mode).
"""

from __future__ import annotations

import threading
import time
import weakref
from bisect import bisect_right
from dataclasses import replace
from typing import Any, Callable, Dict, Iterable, List, Optional, Set, Tuple

import numpy as np

from nomad_tpu.analysis import guarded_by, requires_lock
from nomad_tpu.telemetry import metrics
from nomad_tpu.structs import (
    Allocation,
    Evaluation,
    Job,
    Node,
    PeriodicLaunch,
    from_dict,
    stamp_alloc,
    to_dict,
)
from nomad_tpu.structs.structs import (
    AllocClientStatusFailed,
    AllocClientStatusRunning,
    CheckStatusCritical,
    EvalStatusBlocked,
    JobStatusDead,
    JobStatusPending,
    JobStatusRunning,
    NodeStatusDown,
    NodeStatusReady,
)

from .watch import Item, Items, NotifyGroup


class _Chain:
    """Version chain for one key: parallel arrays of indexes and values."""

    __slots__ = ("indexes", "values")

    def __init__(self) -> None:
        self.indexes: List[int] = []
        self.values: List[Any] = []

    def append(self, index: int, value: Any) -> None:
        self.indexes.append(index)
        self.values.append(value)

    def at(self, watermark: int) -> Any:
        """Latest value with index <= watermark (None if absent/tombstone)."""
        i = bisect_right(self.indexes, watermark)
        if i == 0:
            return None
        return self.values[i - 1]

    def compact(self, min_watermark: int) -> bool:
        """Drop versions superseded before min_watermark; True if chain empty."""
        i = bisect_right(self.indexes, min_watermark)
        if i > 1:
            del self.indexes[: i - 1]
            del self.values[: i - 1]
        return len(self.values) == 1 and self.values[0] is None


class _Table:
    __slots__ = ("chains", "current")

    def __init__(self) -> None:
        self.chains: Dict[str, _Chain] = {}
        self.current: Dict[str, Any] = {}

    def write(self, index: int, key: str, value: Any) -> None:
        chain = self.chains.get(key)
        if chain is None:
            chain = _Chain()
            self.chains[key] = chain
        chain.append(index, value)
        if value is None:
            self.current.pop(key, None)
        else:
            self.current[key] = value


class SweepSegment:
    """Columnar alloc storage for ONE committed sweep batch: per-alloc id /
    instance-name / node columns plus a frozen per-task-group template the
    rows share everything else with. A 10k-alloc system sweep commits as
    one of these — no per-alloc objects, chains, member-set inserts or
    watch items on the apply path. Rows materialize a real Allocation only
    on first read (`materialize`), and any MUTATION promotes the row out
    of the segment into the exact per-object chain path
    (StateStore._col_promote_locked), so write semantics are unchanged.

    Concurrency: all fields are guarded by the owning StateStore's _lock
    (segments are never shared between stores)."""

    __slots__ = ("index", "job_id", "eval_id", "templates", "tg_idx",
                 "alloc_ids", "names", "_node_ids", "row_node_ids",
                 "counts", "live", "n_live", "kind", "_objs")

    def __init__(self, index: int, job_id: str, eval_id: str,
                 templates: List[Allocation], tg_idx: Optional[List[int]],
                 alloc_ids: List[str], names: List[str],
                 node_ids: Optional[List[str]] = None,
                 kind: str = "system",
                 row_node_ids: Optional[List[str]] = None, counts=None):
        self.index = index
        self.job_id = job_id
        self.eval_id = eval_id
        self.templates = templates
        self.tg_idx = tg_idx  # None => single template for every row
        self.alloc_ids = alloc_ids
        self.names = names
        # The node column, per allocation, comes either whole (a restored
        # snapshot) or as the commit's own (row_node_ids, counts): one id
        # a placed node row and the allocations folded into it. The
        # commit never expands it; the first read does (`node_ids`).
        self._node_ids = node_ids
        self.row_node_ids = row_node_ids
        self.counts = counts
        # Which commit path built the batch ("system" sweep / "service"
        # window) — operator observability only, no read-path semantics.
        self.kind = kind
        self.live = [True] * len(alloc_ids)
        self.n_live = len(alloc_ids)
        self._objs: Dict[int, Allocation] = {}  # pos -> materialized

    @property
    def node_ids(self) -> List[str]:
        """Node id of every row, aligned with `alloc_ids`: one vectorised
        repeat of the commit's (row_node_ids, counts), kept; the row ids
        themselves where every row holds one allocation."""
        ids = self._node_ids
        if ids is None:
            ids = self.row_node_ids
            if len(ids) != len(self.alloc_ids):
                ids = np.repeat(np.asarray(ids, dtype=object),
                                self.counts).tolist()
            self._node_ids = ids
            self.row_node_ids = self.counts = None
        return ids

    def touched_node_ids(self):
        """The distinct nodes' ids (with repeats where the per-allocation
        column is all there is): what a commit's watch scope is made of."""
        return (self._node_ids if self.row_node_ids is None
                else self.row_node_ids)

    def materialize(self, pos: int) -> Allocation:
        """Stamp (and cache) the real Allocation for one row. The clone is
        bit-equal to what the per-object commit path would have stored:
        template fields shared (value-frozen contract), identity fields
        and the client-mutable containers fresh, raft indexes = the
        segment's commit index."""
        obj = self._objs.get(pos)
        if obj is not None:
            return obj
        template = self.templates[self.tg_idx[pos] if self.tg_idx else 0]
        obj = stamp_alloc(template.__dict__, self.alloc_ids[pos],
                          self.names[pos], self.node_ids[pos])
        obj.CreateIndex = self.index
        obj.ModifyIndex = self.index
        obj.AllocModifyIndex = self.index
        self._objs[pos] = obj
        return obj

    def serialize(self) -> Dict[str, Any]:
        """Plain-data dump of the LIVE rows for raft snapshot persist.
        No watermark filter is needed: a promoted row's chain version is
        written at this segment's own index, so for every watermark that
        can see this segment the chain dump already carries exactly the
        promoted rows and `live` carries the rest. Shape round-trips
        through msgpack and `deserialize`."""
        keep = [i for i, alive in enumerate(self.live) if alive]
        return {
            "Index": self.index,
            "JobID": self.job_id,
            "EvalID": self.eval_id,
            "Kind": self.kind,
            "Templates": [to_dict(t) for t in self.templates],
            "TGIdx": ([self.tg_idx[i] for i in keep]
                      if self.tg_idx else None),
            "AllocIDs": [self.alloc_ids[i] for i in keep],
            "Names": [self.names[i] for i in keep],
            "NodeIDs": [self.node_ids[i] for i in keep],
        }

    @staticmethod
    def deserialize(data: Dict[str, Any]) -> "SweepSegment":
        templates = [t if isinstance(t, Allocation)
                     else from_dict(Allocation, t)
                     for t in data["Templates"]]
        return SweepSegment(
            index=int(data["Index"]), job_id=data["JobID"],
            eval_id=data["EvalID"], templates=templates,
            tg_idx=(list(data["TGIdx"]) if data.get("TGIdx") else None),
            alloc_ids=list(data["AllocIDs"]), names=list(data["Names"]),
            node_ids=list(data["NodeIDs"]),
            kind=data.get("Kind", "system"))


class _ReadAPI:
    """Read operations shared by StateStore (live view) and StateSnapshot."""

    # Subclasses define _get(table, key) and _iter(table) and _members(...)
    # plus the columnar hooks _col_alloc / _col_members / _col_allocs_all
    # (lazy views over SweepSegment rows).

    # -- nodes --
    def node_by_id(self, node_id: str) -> Optional[Node]:
        return self._get("nodes", node_id)

    def nodes(self) -> List[Node]:
        return self._iter("nodes")

    # -- jobs --
    def job_by_id(self, job_id: str) -> Optional[Job]:
        return self._get("jobs", job_id)

    def jobs(self) -> List[Job]:
        return self._iter("jobs")

    def jobs_by_id_prefix(self, prefix: str) -> List[Job]:
        return [j for j in self._iter("jobs") if j.ID.startswith(prefix)]

    def jobs_by_periodic(self, periodic: bool = True) -> List[Job]:
        return [j for j in self._iter("jobs") if j.is_periodic() == periodic]

    def jobs_by_scheduler(self, scheduler_type: str) -> List[Job]:
        return [j for j in self._iter("jobs") if j.Type == scheduler_type]

    def jobs_by_gc(self, gc: bool = True) -> List[Job]:
        # A job is GC-able when it is batch-type (reference: schema.go jobIsGCable)
        from nomad_tpu.structs.structs import JobTypeBatch

        return [j for j in self._iter("jobs") if (j.Type == JobTypeBatch) == gc]

    # -- evals --
    def eval_by_id(self, eval_id: str) -> Optional[Evaluation]:
        return self._get("evals", eval_id)

    def evals(self) -> List[Evaluation]:
        return self._iter("evals")

    def evals_by_job(self, job_id: str) -> List[Evaluation]:
        return self._members("eval_job", job_id, "evals")

    # -- allocs --
    def alloc_by_id(self, alloc_id: str) -> Optional[Allocation]:
        found = self._get("allocs", alloc_id)
        if found is None:
            found = self._col_alloc(alloc_id)
        return found

    def allocs(self) -> List[Allocation]:
        return self._iter("allocs") + self._col_allocs_all()

    def allocs_by_node(self, node_id: str) -> List[Allocation]:
        return (self._members("alloc_node", node_id, "allocs")
                + self._col_members("node", node_id))

    def allocs_by_node_terminal(self, node_id: str, terminal: bool) -> List[Allocation]:
        return [a for a in self.allocs_by_node(node_id)
                if a.terminal_status() == terminal]

    def allocs_by_job(self, job_id: str) -> List[Allocation]:
        return (self._members("alloc_job", job_id, "allocs")
                + self._col_members("job", job_id))

    def allocs_by_eval(self, eval_id: str) -> List[Allocation]:
        return (self._members("alloc_eval", eval_id, "allocs")
                + self._col_members("eval", eval_id))

    # -- periodic launches --
    def periodic_launch_by_id(self, job_id: str) -> Optional[PeriodicLaunch]:
        return self._get("periodic_launch", job_id)

    def periodic_launches(self) -> List[PeriodicLaunch]:
        return self._iter("periodic_launch")

    # -- service registry --
    def service_by_id(self, reg_id: str):
        return self._get("services", reg_id)

    def services(self) -> List:
        return self._iter("services")

    def services_by_name(self, name: str) -> List:
        return self._members("service_name", name, "services")

    def services_by_node(self, node_id: str) -> List:
        return self._members("service_node", node_id, "services")

    def services_by_alloc(self, alloc_id: str) -> List:
        return self._members("service_alloc", alloc_id, "services")


TABLES = ("nodes", "jobs", "evals", "allocs", "periodic_launch", "services")
_MEMBER_INDEXES = {
    "eval_job": ("evals", lambda e: e.JobID),
    "alloc_node": ("allocs", lambda a: a.NodeID),
    "alloc_job": ("allocs", lambda a: a.JobID),
    "alloc_eval": ("allocs", lambda a: a.EvalID),
    "service_name": ("services", lambda s: s.ServiceName),
    "service_node": ("services", lambda s: s.NodeID),
    "service_alloc": ("services", lambda s: s.AllocID),
}


class StateStore(_ReadAPI):
    """The authoritative in-memory store behind the FSM."""

    # Columnar alloc tables (SweepSegment) and their lazy secondary
    # indexes: commits append whole segments; the per-row id/node indexes
    # are merged in on first READ (_col_flush_locked), so index
    # maintenance never rides the serialized FSM apply.
    _concurrency = guarded_by(
        "_lock", "_col_segments", "_col_by_job", "_col_by_eval",
        "_col_alloc_index", "_col_node_index", "_col_unindexed",
        "_col_batches", "_col_promoted")

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._tables: Dict[str, _Table] = {t: _Table() for t in TABLES}
        self._member_sets: Dict[str, Dict[str, Set[str]]] = {
            name: {} for name in _MEMBER_INDEXES
        }
        self._table_index: Dict[str, int] = {}
        self._latest_index = 0
        self._notify = NotifyGroup()
        self._watermarks: Dict[int, int] = {}  # snapshot token -> watermark
        self._next_token = 0
        # Columnar alloc tables: one SweepSegment per committed sweep
        # batch, plus segment-level (job/eval) and lazily-merged per-row
        # (alloc id / node) indexes.
        self._col_segments: List[SweepSegment] = []
        self._col_by_job: Dict[str, List[SweepSegment]] = {}
        self._col_by_eval: Dict[str, List[SweepSegment]] = {}
        self._col_alloc_index: Dict[str, Tuple[SweepSegment, int]] = {}
        self._col_node_index: Dict[str, List[Tuple[SweepSegment, int]]] = {}
        self._col_unindexed: List[SweepSegment] = []
        # Operator counters (sched-stats `Store` block): columnar batches
        # committed per kind ("system" sweep / "service" window) and rows
        # promoted onto the object chain by mutations, since boot.
        self._col_batches: Dict[str, int] = {}
        self._col_promoted = 0
        # Relaxed fast-path flag (deliberately OUTSIDE the guarded set):
        # set under the lock when the first segment commits, read lock-free
        # by the columnar hooks so non-sweep deployments never pay an extra
        # lock round per alloc read. Monotonic once a store has seen a
        # sweep; a racing reader at the flip boundary just orders before
        # the commit.
        self._has_col = False
        # Change listeners: cb(kind, old, new) fired post-commit. Used to keep
        # the device-resident node tensor in sync (nomad_tpu/tensor/).
        self._listeners: List[Callable[[str, Any, Any], None]] = []

    def add_change_listener(self, cb: Callable[[str, Any, Any], None]) -> None:
        self._listeners.append(cb)

    def _emit(self, events: List[Tuple[str, Any, Any]]) -> None:
        for cb in self._listeners:
            # Batch-aware listeners (the tensor index) take the whole
            # commit's events in one call — a 50-alloc plan then costs one
            # lock acquisition, not fifty.
            batch = getattr(cb, "on_change_batch", None)
            if batch is not None:
                batch(events)
                continue
            for kind, old, new in events:
                cb(kind, old, new)

    def transaction(self):
        """The store's write lock, for callers that must make SEVERAL
        write calls atomic with respect to readers — the FSM wraps one
        raft entry's groups (a sweep group's stops + its segment, plus
        any object co-groups) in `with state.transaction():` so no
        blocking query can observe a torn entry. Reentrant: the inner
        write methods re-acquire freely."""
        return self._lock

    # ------------------------------------------------------------------ reads
    def _get(self, table: str, key: str):
        return self._tables[table].current.get(key)

    def _iter(self, table: str):
        with self._lock:
            return list(self._tables[table].current.values())

    def _members(self, index_name: str, key: str, table: str):  # type: ignore[override]
        with self._lock:
            ids = self._members_sets(index_name).get(key, ())
            cur = self._tables[table].current
            return [cur[i] for i in ids if i in cur]

    def _members_sets(self, index_name: str) -> Dict[str, Set[str]]:
        return self._member_sets[index_name]

    # ------------------------------------------------- columnar alloc reads
    def _col_flush_locked(self) -> None:
        """Merge freshly committed segments into the per-row indexes.
        Runs on the first read that needs them — off the commit path —
        and costs O(rows) once per segment, amortized."""
        if not self._col_unindexed:
            return
        for seg in self._col_unindexed:
            by_alloc = self._col_alloc_index
            by_node = self._col_node_index
            for pos, (aid, nid) in enumerate(zip(seg.alloc_ids,
                                                 seg.node_ids)):
                if not seg.live[pos]:
                    continue  # promoted before the first index merge
                by_alloc[aid] = (seg, pos)
                bucket = by_node.get(nid)
                if bucket is None:
                    by_node[nid] = [(seg, pos)]
                else:
                    bucket.append((seg, pos))
        self._col_unindexed = []

    def _col_alloc(self, alloc_id: str) -> Optional[Allocation]:
        if not self._has_col:
            return None
        with self._lock:
            self._col_flush_locked()
            hit = self._col_alloc_index.get(alloc_id)
            if hit is None:
                return None
            seg, pos = hit
            if not seg.live[pos]:
                return None
            return seg.materialize(pos)

    def _col_members(self, kind: str, key: str) -> List[Allocation]:
        if not self._has_col:
            return []
        with self._lock:
            if kind == "node":
                self._col_flush_locked()
                return [seg.materialize(pos)
                        for seg, pos in self._col_node_index.get(key, ())
                        if seg.live[pos]]
            segs = (self._col_by_job if kind == "job"
                    else self._col_by_eval).get(key, ())
            return [seg.materialize(pos)
                    for seg in segs for pos in range(len(seg.alloc_ids))
                    if seg.live[pos]]

    def _col_allocs_all(self) -> List[Allocation]:
        if not self._has_col:
            return []
        with self._lock:
            return [seg.materialize(pos)
                    for seg in self._col_segments
                    for pos in range(len(seg.alloc_ids))
                    if seg.live[pos]]

    def client_alloc_map(self, node_id: str) -> Tuple[Dict[str, int], int]:
        """The client pull signal — {alloc_id: AllocModifyIndex} plus the
        blocking-query index — WITHOUT materializing columnar rows: a
        sweep-placed alloc's identity and index live in the segment
        columns, so a node's 30s poll never stamps objects it won't run."""
        with self._lock:
            out: Dict[str, int] = {}
            idx = 0
            ids = self._members_sets("alloc_node").get(node_id, ())
            cur = self._tables["allocs"].current
            for aid in ids:
                a = cur.get(aid)
                if a is not None:
                    out[aid] = a.AllocModifyIndex
                    if a.AllocModifyIndex > idx:
                        idx = a.AllocModifyIndex
            if self._col_segments:
                self._col_flush_locked()
                for seg, pos in self._col_node_index.get(node_id, ()):
                    if seg.live[pos]:
                        out[seg.alloc_ids[pos]] = seg.index
                        if seg.index > idx:
                            idx = seg.index
            if not out:
                idx = self.get_index("allocs")
            return out, idx

    def columnar_stats(self) -> Dict[str, Any]:
        """Operator snapshot of the columnar alloc tables (sched-stats
        `Store` block): live segment/row counts, rows promoted onto the
        object chain, and committed batches split by commit path — the
        "which path did the storm take" answer."""
        with self._lock:
            return {
                "Segments": len(self._col_segments),
                "LiveRows": sum(s.n_live for s in self._col_segments),
                "PromotedRows": self._col_promoted,
                "Batches": dict(self._col_batches),
            }

    def get_index(self, table: str) -> int:
        return self._table_index.get(table, 0)

    def latest_index(self) -> int:
        return self._latest_index

    # ------------------------------------------------------------------ watch
    def watch(self, items: Iterable[Item], event: threading.Event) -> None:
        self._notify.watch(items, event)

    def stop_watch(self, items: Iterable[Item], event: threading.Event) -> None:
        self._notify.stop_watch(items, event)

    # ----------------------------------------------------------------- writes
    def _commit(self, index: int, tables: Iterable[str], watch_items: Items,
                scoped: Optional[Dict[str, Iterable[str]]] = None) -> None:
        # Dedup order is immaterial: every table gets the SAME index and
        # watch items land in a set — no replicated value depends on it.
        # lint: allow(apply_pure, order-independent index assignment)
        for t in set(tables):
            self._table_index[t] = index
            watch_items.add(Item(table=t))
        if index > self._latest_index:
            self._latest_index = index
        self._notify.notify(watch_items, scoped=scoped)

    def _member_add(self, index_name: str, key: str, obj_id: str) -> None:
        self._members_sets(index_name).setdefault(key, set()).add(obj_id)

    # --------------------------------------------------- columnar alloc writes
    def apply_sweep_segment(self, index: int, seg: SweepSegment,
                            rows=None, delta=None, row_node_ids=None,
                            epoch: int = -1) -> None:
        """Commit one columnar sweep batch as ONE scatter: register the
        segment, bump indexes, fire ONE batched trigger set (job/eval/table
        items plus a waiter-intersection over the touched node/alloc keys),
        and hand the per-row usage delta to batch-aware listeners (the
        tensor index) as one scatter-add. No per-alloc work happens here —
        per-row secondary indexes merge lazily on first read, and real
        Allocation objects stamp lazily on first touch."""
        # lint: allow(apply_pure, local metrics timer; never enters state)
        t0 = time.monotonic()
        with self._lock:
            self._col_segments.append(seg)
            self._col_unindexed.append(seg)
            self._col_by_job.setdefault(seg.job_id, []).append(seg)
            self._col_by_eval.setdefault(seg.eval_id, []).append(seg)
            self._col_batches[seg.kind] = \
                self._col_batches.get(seg.kind, 0) + 1
            self._has_col = True
            watch_items = Items([Item(alloc_job=seg.job_id),
                                 Item(alloc_eval=seg.eval_id)])
            # Job status: one live alloc <=> RUNNING, and every segment row
            # is live — skip the O(fleet) derivation when already there.
            jobs: Dict[str, str] = {}
            job = self._get("jobs", seg.job_id)
            if job is not None and job.Status != JobStatusRunning:
                jobs[seg.job_id] = ""
            touched = self._set_job_statuses(index, watch_items, jobs,
                                             eval_delete=False)
            self._commit(index, ["allocs"] + touched, watch_items,
                         scoped={"alloc_node": seg.touched_node_ids(),
                                 "alloc": seg.alloc_ids})
            for cb in self._listeners:
                sweep_cb = getattr(cb, "on_sweep_batch", None)
                if sweep_cb is not None and delta is not None:
                    sweep_cb(row_node_ids, rows, delta, epoch)
                    continue
                # Generic listener fallback: per-event contract needs the
                # objects — correctness over speed for foreign listeners.
                events = [("alloc", None, seg.materialize(pos))
                          for pos in range(len(seg.alloc_ids))]
                batch = getattr(cb, "on_change_batch", None)
                if batch is not None:
                    batch(events)
                else:
                    for kind, old, new in events:
                        cb(kind, old, new)
        metrics.measure_since(("nomad", "state", "scatter"), t0)
        metrics.incr_counter(("nomad", "state", "sweep_allocs"),
                             len(seg.alloc_ids))
        # Per-path segment counter; the trailing segment is dynamic
        # ("system"/"service"), like the per-type fsm keys.
        metrics.incr_counter(("nomad", "state", "segments", seg.kind))

    def _col_promote_locked(self, alloc_id: str) -> Optional[Allocation]:
        """Promote a columnar row into the exact per-object chain path.
        The materialized value is written into the chain AT THE SEGMENT'S
        COMMIT INDEX, so every snapshot watermark keeps seeing exactly what
        it saw before — the row just changes representation. Callers then
        mutate through the ordinary object path. Caller holds _lock."""
        if not self._has_col:
            return None
        self._col_flush_locked()
        hit = self._col_alloc_index.pop(alloc_id, None)
        if hit is None:
            return None
        seg, pos = hit
        if not seg.live[pos]:
            return None
        obj = seg.materialize(pos)
        seg.live[pos] = False
        seg.n_live -= 1
        self._col_promoted += 1
        self._tables["allocs"].write(seg.index, alloc_id, obj)
        self._member_add("alloc_node", obj.NodeID, alloc_id)
        self._member_add("alloc_job", obj.JobID, alloc_id)
        self._member_add("alloc_eval", obj.EvalID, alloc_id)
        metrics.incr_counter(("nomad", "state", "promote"))
        return obj

    def upsert_node(self, index: int, node: Node) -> None:
        """(reference: state_store.go:91 UpsertNode) Preserves CreateIndex and
        keeps drain/status transitions consistent."""
        with self._lock:
            existing = self._get("nodes", node.ID)
            if existing is not None:
                node.CreateIndex = existing.CreateIndex
            else:
                node.CreateIndex = index
            node.ModifyIndex = index
            self._tables["nodes"].write(index, node.ID, node)
            self._commit(index, ["nodes"], Items([Item(node=node.ID)]))
            self._emit([("node", existing, node)])

    def delete_node(self, index: int, node_id: str) -> None:
        with self._lock:
            existing = self._get("nodes", node_id)
            if existing is None:
                raise KeyError(f"node not found: {node_id}")
            self._tables["nodes"].write(index, node_id, None)
            watch_items = Items([Item(node=node_id)])
            # Cascade: a deregistered node's service instances are gone
            # (the reference relies on the node-local Consul agent dying
            # with the node; the replicated registry must prune explicitly).
            tables = ["nodes"]
            for reg in self._members("service_node", node_id, "services"):
                self._tables["services"].write(index, reg.ID, None)
                watch_items.add(Item(service_name=reg.ServiceName))
                tables.append("services")
            self._commit(index, tables, watch_items)
            self._emit([("node", existing, None)])

    # ------------------------------------------------------- service registry
    def upsert_services(self, index: int, regs: List) -> None:
        """Write service registrations (client sync / server self-reg).

        Identical payloads are skipped entirely: clients re-push ALL of
        their registrations every anti-entropy full sync, and rewriting
        an unchanged registration would bump the services table index —
        waking every blocking query on the name and replaying a no-op
        through every watcher — at a cadence of once per 30s per node.
        """
        with self._lock:
            watch_items = Items()
            touched = False
            for reg in regs:
                existing = self._get("services", reg.ID)
                if existing is not None and self._service_equal(existing, reg):
                    continue
                reg.CreateIndex = (existing.CreateIndex if existing is not None
                                   else index)
                reg.ModifyIndex = index
                self._tables["services"].write(index, reg.ID, reg)
                self._member_add("service_name", reg.ServiceName, reg.ID)
                self._member_add("service_node", reg.NodeID, reg.ID)
                self._member_add("service_alloc", reg.AllocID, reg.ID)
                watch_items.add(Item(service_name=reg.ServiceName))
                touched = True
            if touched:
                self._commit(index, ["services"], watch_items)

    @staticmethod
    def _service_equal(a, b) -> bool:
        """Content equality modulo raft indexes (which the store assigns)."""
        return (a.ServiceName == b.ServiceName and a.Tags == b.Tags
                and a.JobID == b.JobID and a.AllocID == b.AllocID
                and a.TaskName == b.TaskName and a.NodeID == b.NodeID
                and a.Address == b.Address and a.Port == b.Port
                and a.Status == b.Status
                # Modulo Timestamp: every check run re-stamps its state, so
                # including it would defeat the dedup for any checked service.
                and [(c.Name, c.Type, c.Status, c.Output) for c in a.Checks]
                == [(c.Name, c.Type, c.Status, c.Output) for c in b.Checks])

    def delete_services(self, index: int, reg_ids: List[str]) -> None:
        with self._lock:
            watch_items = Items()
            touched = False
            for rid in reg_ids:
                existing = self._get("services", rid)
                if existing is None:
                    continue  # idempotent: double-deregister is normal
                self._tables["services"].write(index, rid, None)
                watch_items.add(Item(service_name=existing.ServiceName))
                touched = True
            if touched:
                self._commit(index, ["services"], watch_items)

    def update_node_status(self, index: int, node_id: str, status: str) -> None:
        with self._lock:
            existing = self._get("nodes", node_id)
            if existing is None:
                raise KeyError(f"node not found: {node_id}")
            node = existing.copy()
            node.Status = status
            node.ModifyIndex = index
            self._tables["nodes"].write(index, node_id, node)
            watch_items = Items([Item(node=node_id)])
            tables = ["nodes"]
            # A down node can't run its checks: its service instances must
            # stop being served as healthy (the reference gets this from
            # Consul's serfHealth check; the replicated registry marks them
            # critical explicitly). When the node recovers, its service
            # manager's periodic full sync restores the true statuses
            # (services/manager.py FULL_SYNC_INTERVAL).
            if status == NodeStatusDown:
                for reg in self._members("service_node", node_id, "services"):
                    if reg.Status == CheckStatusCritical:
                        continue
                    down = reg.copy()
                    down.Status = CheckStatusCritical
                    for check in down.Checks:
                        check.Status = CheckStatusCritical
                        check.Output = "node down"
                    down.ModifyIndex = index
                    self._tables["services"].write(index, down.ID, down)
                    watch_items.add(Item(service_name=down.ServiceName))
                    tables.append("services")
            self._commit(index, tables, watch_items)
            self._emit([("node", existing, node)])

    def update_node_drain(self, index: int, node_id: str, drain: bool) -> None:
        with self._lock:
            existing = self._get("nodes", node_id)
            if existing is None:
                raise KeyError(f"node not found: {node_id}")
            node = existing.copy()
            node.Drain = drain
            node.ModifyIndex = index
            self._tables["nodes"].write(index, node_id, node)
            self._commit(index, ["nodes"], Items([Item(node=node_id)]))
            self._emit([("node", existing, node)])

    def upsert_job(self, index: int, job: Job) -> None:
        """(reference: state_store.go:280 UpsertJob) Derives initial status."""
        with self._lock:
            watch_items = Items([Item(job=job.ID)])
            existing = self._get("jobs", job.ID)
            if existing is not None:
                job.CreateIndex = existing.CreateIndex
                job.JobModifyIndex = index
            else:
                job.CreateIndex = index
                job.JobModifyIndex = index
            job.ModifyIndex = index
            job.Status = self._derive_job_status(job, eval_delete=False)
            self._tables["jobs"].write(index, job.ID, job)
            self._commit(index, ["jobs"], watch_items)

    def delete_job(self, index: int, job_id: str) -> None:
        with self._lock:
            if self._get("jobs", job_id) is None:
                raise KeyError(f"job not found: {job_id}")
            self._tables["jobs"].write(index, job_id, None)
            # Also clean the periodic launch entry if any.
            tables = ["jobs"]
            if self._get("periodic_launch", job_id) is not None:
                self._tables["periodic_launch"].write(index, job_id, None)
                tables.append("periodic_launch")
            self._commit(index, tables, Items([Item(job=job_id)]))

    def upsert_evals(self, index: int, evals: List[Evaluation]) -> None:
        """(reference: state_store.go:476 UpsertEvals) Also refreshes the
        status of every touched job."""
        with self._lock:
            watch_items = Items()
            jobs: Dict[str, str] = {}
            for ev in evals:
                existing = self._get("evals", ev.ID)
                if existing is not None:
                    ev.CreateIndex = existing.CreateIndex
                else:
                    ev.CreateIndex = index
                ev.ModifyIndex = index
                self._tables["evals"].write(index, ev.ID, ev)
                self._member_add("eval_job", ev.JobID, ev.ID)
                watch_items.add(Item(eval=ev.ID))
                jobs.setdefault(ev.JobID, "")
            touched = self._set_job_statuses(index, watch_items, jobs,
                                             eval_delete=False)
            self._commit(index, ["evals"] + touched, watch_items)

    def delete_eval(self, index: int, eval_ids: List[str],
                    alloc_ids: List[str]) -> None:
        """GC path: remove evals and allocs together (reference:
        state_store.go DeleteEval)."""
        with self._lock:
            watch_items = Items()
            jobs: Dict[str, str] = {}
            events = []
            for eid in eval_ids:
                existing = self._get("evals", eid)
                if existing is None:
                    continue
                self._tables["evals"].write(index, eid, None)
                watch_items.add(Item(eval=eid))
                jobs.setdefault(existing.JobID, "")
            for aid in alloc_ids:
                existing = self._get("allocs", aid)
                if existing is None:
                    # GC of a columnar row: promote (chain gets the value
                    # at the segment index), then tombstone as usual.
                    existing = self._col_promote_locked(aid)
                if existing is None:
                    continue
                self._tables["allocs"].write(index, aid, None)
                watch_items.add(Item(alloc=aid))
                watch_items.add(Item(alloc_eval=existing.EvalID))
                watch_items.add(Item(alloc_job=existing.JobID))
                watch_items.add(Item(alloc_node=existing.NodeID))
                events.append(("alloc", existing, None))
            touched = self._set_job_statuses(index, watch_items, jobs,
                                             eval_delete=True)
            self._commit(index, ["evals", "allocs"] + touched, watch_items)
            self._emit(events)

    def upsert_allocs(self, index: int, allocs: List[Allocation]) -> None:
        """(reference: state_store.go:792 UpsertAllocs) Used by the plan
        applier; refreshes job statuses."""
        with self._lock:
            watch_items = Items()
            jobs: Dict[str, str] = {}
            events = []
            # Relation watch keys dedupe through cheap string sets first: a
            # 50-placement plan repeats the same eval/job ids per alloc, and
            # hashing a frozen 9-field Item costs ~10x a str.
            evals: set = set()
            nodes: set = set()
            nonterminal_jobs: set = set()
            # Hot loop: a system sweep commits one alloc per node, so the
            # per-alloc work below runs 10k times per chunk; the table and
            # member-set lookups are hoisted out of it.
            alloc_table = self._tables["allocs"]
            alloc_current = alloc_table.current.get
            alloc_write = alloc_table.write
            add_item = watch_items.add
            members_node = self._members_sets("alloc_node")
            members_job = self._members_sets("alloc_job")
            members_eval = self._members_sets("alloc_eval")
            has_col = self._has_col
            for alloc in allocs:
                existing = alloc_current(alloc.ID)
                if existing is None and has_col:
                    # A mutation of a columnar row (eviction, preemption,
                    # in-place replace) first promotes it onto the exact
                    # object path, preserving upsert semantics verbatim.
                    existing = self._col_promote_locked(alloc.ID)
                if existing is None:
                    alloc.CreateIndex = index
                    alloc.ModifyIndex = index
                    alloc.AllocModifyIndex = index
                else:
                    alloc.CreateIndex = existing.CreateIndex
                    alloc.ModifyIndex = index
                    alloc.AllocModifyIndex = index
                    # Keep client-reported state (server-side upsert must not
                    # clobber what the client said).
                    alloc.ClientStatus = existing.ClientStatus
                    alloc.ClientDescription = existing.ClientDescription
                    alloc.TaskStates = existing.TaskStates
                add_item(Item(alloc=alloc.ID))
                alloc_write(index, alloc.ID, alloc)
                members_node.setdefault(alloc.NodeID, set()).add(alloc.ID)
                members_job.setdefault(alloc.JobID, set()).add(alloc.ID)
                members_eval.setdefault(alloc.EvalID, set()).add(alloc.ID)
                evals.add(alloc.EvalID)
                nodes.add(alloc.NodeID)
                jobs.setdefault(alloc.JobID, "")
                if not alloc.terminal_status():
                    nonterminal_jobs.add(alloc.JobID)
                events.append(("alloc", existing, alloc))
            for ev_id in evals:
                watch_items.add(Item(alloc_eval=ev_id))
            for job_id in jobs:
                watch_items.add(Item(alloc_job=job_id))
            for node_id in nodes:
                watch_items.add(Item(alloc_node=node_id))
            # A RUNNING job that just received a non-terminal alloc cannot
            # change status (one live alloc <=> running): skip the
            # derivation, which walks every alloc of the job — O(fleet)
            # per chunk for 10k-alloc system sweeps.
            for job_id in nonterminal_jobs:
                if job_id in jobs:
                    job = self._get("jobs", job_id)
                    if job is not None and job.Status == JobStatusRunning:
                        del jobs[job_id]
            touched = self._set_job_statuses(index, watch_items, jobs,
                                             eval_delete=False)
            self._commit(index, ["allocs"] + touched, watch_items)
            self._emit(events)

    def update_alloc_from_client(self, index: int, alloc: Allocation) -> None:
        """Client status sync (reference: state_store.go UpdateAllocFromClient):
        merges the client-reported fields into the server's copy."""
        with self._lock:
            existing = self._get("allocs", alloc.ID)
            if existing is None:
                # Client status for a sweep-committed row: promote it out
                # of the columnar table, then merge exactly as before.
                existing = self._col_promote_locked(alloc.ID)
            if existing is None:
                raise KeyError(f"alloc not found: {alloc.ID}")
            copy_alloc = existing.copy()
            copy_alloc.ClientStatus = alloc.ClientStatus
            copy_alloc.ClientDescription = alloc.ClientDescription
            copy_alloc.TaskStates = alloc.TaskStates
            copy_alloc.ModifyIndex = index
            self._tables["allocs"].write(index, alloc.ID, copy_alloc)
            watch_items = Items([
                Item(alloc=alloc.ID),
                Item(alloc_eval=copy_alloc.EvalID),
                Item(alloc_job=copy_alloc.JobID),
                Item(alloc_node=copy_alloc.NodeID),
            ])
            touched = self._set_job_statuses(index, watch_items,
                                             {copy_alloc.JobID: ""},
                                             eval_delete=False)
            self._commit(index, ["allocs"] + touched, watch_items)
            self._emit([("alloc", existing, copy_alloc)])

    def upsert_periodic_launch(self, index: int, launch: PeriodicLaunch) -> None:
        with self._lock:
            existing = self._get("periodic_launch", launch.ID)
            if existing is not None:
                launch.CreateIndex = existing.CreateIndex
            else:
                launch.CreateIndex = index
            launch.ModifyIndex = index
            self._tables["periodic_launch"].write(index, launch.ID, launch)
            self._commit(index, ["periodic_launch"], Items())

    def delete_periodic_launch(self, index: int, job_id: str) -> None:
        with self._lock:
            if self._get("periodic_launch", job_id) is None:
                raise KeyError(f"periodic launch not found: {job_id}")
            self._tables["periodic_launch"].write(index, job_id, None)
            self._commit(index, ["periodic_launch"], Items())

    # --------------------------------------------------- derived job statuses
    def _set_job_statuses(self, index: int, watch_items: Items,
                          jobs: Dict[str, str], eval_delete: bool) -> List[str]:
        """Recompute status for touched jobs (reference: state_store.go:1029).
        Returns the list of extra tables touched."""
        touched: List[str] = []
        for job_id, force in jobs.items():
            job = self._get("jobs", job_id)
            if job is None:
                continue
            new_status = force or self._derive_job_status(job, eval_delete)
            if job.Status == new_status:
                continue
            # Committed jobs are value-frozen: share the nested task tree
            # and replace only the scalars that change. A deepcopy here
            # walks the whole job (~1ms) inside the serialized FSM apply,
            # once per eval at storm rates.
            updated = replace(job, Status=new_status, ModifyIndex=index)
            self._tables["jobs"].write(index, job_id, updated)
            watch_items.add(Item(job=job_id))
            touched.append("jobs")
        return touched

    @requires_lock("_lock")
    def _derive_job_status(self, job: Job, eval_delete: bool) -> str:
        """(reference: state_store.go:1097 getJobStatus)"""
        has_alloc = False
        # Columnar rows are live (non-terminal) by construction — any
        # segment row means RUNNING without materializing anything.
        for seg in self._col_by_job.get(job.ID, ()):
            if seg.n_live:
                return JobStatusRunning
            has_alloc = True
        for alloc in self._members("alloc_job", job.ID, "allocs"):
            has_alloc = True
            if not alloc.terminal_status():
                return JobStatusRunning
        has_eval = False
        for ev in self._members("eval_job", job.ID, "evals"):
            has_eval = True
            if not ev.terminal_status():
                return JobStatusPending
        if eval_delete or has_eval or has_alloc:
            return JobStatusDead
        if job.is_periodic():
            return JobStatusRunning
        return JobStatusPending

    # -------------------------------------------------------------- snapshots
    def snapshot(self) -> "StateSnapshot":
        """O(1) point-in-time snapshot pinned at the current index."""
        with self._lock:
            token = self._next_token
            self._next_token += 1
            watermark = self._latest_index
            self._watermarks[token] = watermark
            snap = StateSnapshot(self, watermark, token)
            weakref.finalize(snap, self._release_snapshot, token)
            return snap

    def _release_snapshot(self, token: int) -> None:
        with self._lock:
            self._watermarks.pop(token, None)

    def compact(self) -> None:
        """Drop version history older than the oldest live snapshot."""
        with self._lock:
            min_mark = min(self._watermarks.values(), default=self._latest_index)
            for name, table in self._tables.items():
                dead = [k for k, chain in table.chains.items()
                        if chain.compact(min_mark)]
                for k in dead:
                    del table.chains[k]
            # Prune member sets whose objects are fully gone.
            for index_name, (table_name, _) in _MEMBER_INDEXES.items():
                chains = self._tables[table_name].chains
                sets = self._members_sets(index_name)
                for key in list(sets):
                    sets[key] = {i for i in sets[key] if i in chains}
                    if not sets[key]:
                        del sets[key]
            # Drop fully-promoted segments: every row's value now lives in
            # its chain (written at the segment index), so no watermark can
            # still need the columnar view. Rebuild the per-row indexes
            # without the dead segments' entries.
            dead_segs = [s for s in self._col_segments if s.n_live == 0]
            if dead_segs:
                gone = set(map(id, dead_segs))
                self._col_segments = [s for s in self._col_segments
                                      if id(s) not in gone]
                self._col_unindexed = [s for s in self._col_unindexed
                                       if id(s) not in gone]
                for by in (self._col_by_job, self._col_by_eval):
                    for key in list(by):
                        by[key] = [s for s in by[key] if id(s) not in gone]
                        if not by[key]:
                            del by[key]
                for key in list(self._col_node_index):
                    kept = [(s, p) for s, p in self._col_node_index[key]
                            if id(s) not in gone]
                    if kept:
                        self._col_node_index[key] = kept
                    else:
                        del self._col_node_index[key]

    # ---------------------------------------------------------------- restore
    def restore(self) -> "Restore":
        return Restore(self)


class StateSnapshot(_ReadAPI):
    """Point-in-time read view resolved through the version chains."""

    def __init__(self, store: StateStore, watermark: int, token: int):
        self._store = store
        self.watermark = watermark
        self._token = token

    def _get(self, table: str, key: str):
        chain = self._store._tables[table].chains.get(key)
        if chain is None:
            return None
        return chain.at(self.watermark)

    def _iter(self, table: str):
        with self._store._lock:
            out = []
            for chain in self._store._tables[table].chains.values():
                v = chain.at(self.watermark)
                if v is not None:
                    out.append(v)
            return out

    def _members(self, index_name: str, key: str, table: str):
        with self._store._lock:
            ids = self._store._members_sets(index_name).get(key, ())
            chains = self._store._tables[table].chains
            out = []
            for i in ids:
                chain = chains.get(i)
                if chain is None:
                    continue
                v = chain.at(self.watermark)
                if v is not None:
                    out.append(v)
            return out

    # ----------------------------------------------- columnar (at watermark)
    # A segment is visible iff it committed at or before the watermark;
    # promoted rows left the columnar view FOR EVERY WATERMARK (their chain
    # version is written at the segment's own commit index), so `live` is
    # the only per-row check needed.
    def _col_alloc(self, alloc_id: str):
        store = self._store
        if not store._has_col:
            return None
        with store._lock:
            store._col_flush_locked()
            hit = store._col_alloc_index.get(alloc_id)
            if hit is None:
                return None
            seg, pos = hit
            if seg.index > self.watermark or not seg.live[pos]:
                return None
            return seg.materialize(pos)

    def _col_members(self, kind: str, key: str):
        store = self._store
        if not store._has_col:
            return []
        with store._lock:
            if kind == "node":
                store._col_flush_locked()
                return [seg.materialize(pos)
                        for seg, pos in store._col_node_index.get(key, ())
                        if seg.index <= self.watermark and seg.live[pos]]
            segs = (store._col_by_job if kind == "job"
                    else store._col_by_eval).get(key, ())
            return [seg.materialize(pos)
                    for seg in segs if seg.index <= self.watermark
                    for pos in range(len(seg.alloc_ids))
                    if seg.live[pos]]

    def _col_allocs_all(self):
        store = self._store
        if not store._has_col:
            return []
        with store._lock:
            return [seg.materialize(pos)
                    for seg in store._col_segments
                    if seg.index <= self.watermark
                    for pos in range(len(seg.alloc_ids))
                    if seg.live[pos]]

    def alloc_dump(self):
        """(chain allocs, serialized live columnar segments) read under ONE
        store lock hold — the raft snapshot's alloc state. Two separate
        reads could straddle a promotion and lose the row from both views;
        this can't."""
        store = self._store
        with store._lock:
            chain_allocs = self._iter("allocs")
            segments = [seg.serialize()
                        for seg in store._col_segments
                        if seg.index <= self.watermark and seg.n_live]
            return chain_allocs, segments

    @property
    def store(self) -> StateStore:
        """The store this snapshot reads: part of the identity of anything
        derived from the snapshot and kept (TensorIndex.node_context)."""
        return self._store

    def get_index(self, table: str) -> int:
        # Table indexes are monotone; clamp to the watermark.
        return min(self._store.get_index(table), self.watermark)

    def latest_index(self) -> int:
        return self.watermark


class Restore:
    """Bulk loader used by FSM snapshot restore (reference: state_store.go
    Restore/NodeRestore/JobRestore/...).

    ATOMIC CUTOVER: every *_restore call writes into STAGING structures
    owned by this Restore, never into the live store. `commit()` swaps the
    staged tables in under one lock hold. A restore abandoned mid-stream —
    a torn snapshot chunk, an injected fault, a killed install — therefore
    leaves the live store bit-identical to its pre-restore state; readers
    never observe a half-loaded snapshot."""

    def __init__(self, store: StateStore):
        self._store = store
        self._max_index = 0
        # Staging mirrors of every structure a snapshot populates.
        self._tables: Dict[str, _Table] = {t: _Table() for t in TABLES}
        self._member_sets: Dict[str, Dict[str, Set[str]]] = {
            name: {} for name in _MEMBER_INDEXES}
        self._table_index: Dict[str, int] = {}
        self._col_segments: List[SweepSegment] = []
        self._col_by_job: Dict[str, List[SweepSegment]] = {}
        self._col_by_eval: Dict[str, List[SweepSegment]] = {}
        self._committed = False

    def _bump(self, index: int) -> None:
        self._max_index = max(self._max_index, index)

    def _member_add(self, index_name: str, key: str, obj_id: str) -> None:
        self._member_sets[index_name].setdefault(key, set()).add(obj_id)

    def node_restore(self, node: Node) -> None:
        self._tables["nodes"].write(node.ModifyIndex, node.ID, node)
        self._bump(node.ModifyIndex)

    def job_restore(self, job: Job) -> None:
        self._tables["jobs"].write(job.ModifyIndex, job.ID, job)
        self._bump(job.ModifyIndex)

    def eval_restore(self, ev: Evaluation) -> None:
        self._tables["evals"].write(ev.ModifyIndex, ev.ID, ev)
        self._member_add("eval_job", ev.JobID, ev.ID)
        self._bump(ev.ModifyIndex)

    def alloc_restore(self, alloc: Allocation) -> None:
        self._tables["allocs"].write(alloc.ModifyIndex, alloc.ID, alloc)
        self._member_add("alloc_node", alloc.NodeID, alloc.ID)
        self._member_add("alloc_job", alloc.JobID, alloc.ID)
        self._member_add("alloc_eval", alloc.EvalID, alloc.ID)
        self._bump(alloc.ModifyIndex)

    def columnar_restore(self, seg_data: Dict[str, Any]) -> None:
        """Re-register one serialized columnar segment: the snapshot
        round-trips the columnar tables columnar — a 1M-row restore never
        explodes into per-alloc objects."""
        seg = (seg_data if isinstance(seg_data, SweepSegment)
               else SweepSegment.deserialize(seg_data))
        self._col_segments.append(seg)
        self._col_by_job.setdefault(seg.job_id, []).append(seg)
        self._col_by_eval.setdefault(seg.eval_id, []).append(seg)
        self._bump(seg.index)

    def periodic_launch_restore(self, launch: PeriodicLaunch) -> None:
        self._tables["periodic_launch"].write(launch.ModifyIndex,
                                              launch.ID, launch)
        self._bump(launch.ModifyIndex)

    def service_restore(self, reg) -> None:
        self._tables["services"].write(reg.ModifyIndex, reg.ID, reg)
        self._member_add("service_name", reg.ServiceName, reg.ID)
        self._member_add("service_node", reg.NodeID, reg.ID)
        self._member_add("service_alloc", reg.AllocID, reg.ID)
        self._bump(reg.ModifyIndex)

    def index_restore(self, table: str, index: int) -> None:
        self._table_index[table] = index
        self._bump(index)

    def commit(self) -> None:
        """Swap the staged snapshot in as THE store state, atomically with
        respect to readers, then wake every blocking query (a restore can
        change anything) and tell listeners to rebuild their derived state
        (the device-resident node tensor re-seeds from the store — its
        incremental feed never saw the staged writes)."""
        store = self._store
        if self._committed:
            raise RuntimeError("restore already committed")
        self._committed = True
        with store._lock:
            store._tables = self._tables
            store._member_sets = self._member_sets
            store._table_index = self._table_index
            for t in TABLES:
                store._table_index.setdefault(t, 0)
            store._col_segments = self._col_segments
            store._col_by_job = self._col_by_job
            store._col_by_eval = self._col_by_eval
            store._col_unindexed = list(self._col_segments)
            store._col_alloc_index = {}
            store._col_node_index = {}
            store._has_col = bool(self._col_segments)
            if self._max_index > store._latest_index:
                store._latest_index = self._max_index
            # Every blocking query must re-read. Blocking queries
            # register FINE-GRAINED items only (Item(job=...),
            # Item(alloc_node=...)), so table-level notifies would strand
            # them until their max-wait expiry: wake everyone.
            store._notify.notify_all()
            listeners = list(store._listeners)
        for cb in listeners:
            on_restore = getattr(cb, "on_restore", None)
            if on_restore is not None:
                on_restore(store)
