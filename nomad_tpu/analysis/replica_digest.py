"""Cross-replica state-digest verification (the runtime half of the
replica-determinism contract; the static half is callgraph.py).

Every successful FSM apply folds a canonical encoding of
(index, msg_type, mutation effect) into a rolling blake2b HASH CHAIN:

    chain_i = blake2b(chain_{i-1} || encode(index, type, effect))

A chain VALUE is the whole history in 16 bytes, and — unlike a live
hasher object — it is transferable: snapshots carry the chain value at
their watermark, so a freshly-installed follower reseeds and keeps
folding, and the chain stays CANONICAL (the value at index i is the
same whether a replica replayed the full log from genesis or restored
any intermediate snapshot).

The "effect" is a cheap canonical READBACK of what the entry changed
(node/eval/alloc ids + statuses re-read from the store after the
handler ran) — readback is what makes real store corruption visible,
not just payload divergence. Columnar ApplySweepBatch entries digest
their columns directly, never materializing a row: rows, counts and
delta as dtype/shape/tobytes, the two id columns each as ONE byte
stream (:class:`StrColumn`: the bytes a list of strings folds to, made
by one join and one encode and handed to the hasher in one update).

Every `interval` folds the chain value is recorded as a checkpoint.
The leader piggybacks its latest checkpoint on AppendEntries; a
follower that folded the same index compares and, on mismatch, raises
the typed :class:`ReplicaDivergenceError`, bumps
``nomad.fsm.digest.diverged``, and is quarantined by the raft layer to
snapshot-reinstall recovery. Dev mode folds (sched-stats shows the
chain) but never exchanges — concurrent
dev applies can fold out of index order, which is harmless because
nothing compares the value.

Stats keys: ``nomad.fsm.digest.{folds,exchanged,diverged,verify_ms,
column_folds,row_folds}``.
"""

from __future__ import annotations

import hashlib
import struct
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import numpy as np

from nomad_tpu.telemetry import metrics

# Digest width: 16 bytes is plenty for corruption detection (this is an
# integrity chain, not an adversarial MAC) and halves the snapshot /
# RPC footprint vs blake2b's default 64.
_DIGEST_SIZE = 16
_GENESIS = b"\x00" * _DIGEST_SIZE

# How many recent checkpoints a replica retains for verification. The
# leader only ever piggybacks its newest one; a handful of buckets of
# slack covers followers that lag a few heartbeats behind.
_CHECKPOINT_KEEP = 8


class ReplicaDivergenceError(Exception):
    """A follower's state digest disagrees with the leader's at the same
    applied index: this replica's FSM is no longer a function of the
    log. The raft layer quarantines the replica to snapshot-reinstall
    recovery when this surfaces."""

    def __init__(self, index: int, expected: str, actual: str):
        super().__init__(
            f"replica state digest diverged at index {index}: "
            f"leader={expected} local={actual}")
        self.index = index
        self.expected = expected
        self.actual = actual


# ------------------------------------------------------ canonical encoding
class StrColumn:
    """One string column of a columnar group (alloc ids, node ids),
    folded as the list of its values: the same bytes, no visit per item.

    ``stream`` is what ``_fold_obj`` feeds the hasher for ``list(values)``
    (``L<n>:`` then ``S<len>:<utf-8>`` an item) when every value is an
    ASCII string of one length, so that one join on the constant
    ``S<len>:`` prefix and one encode give it; else None, and the column
    folds item by item as any list does. Lists (the msgpack round trip)
    and object arrays give the same stream."""

    __slots__ = ("values", "stream")

    def __init__(self, values):
        if isinstance(values, np.ndarray):
            values = values.tolist()
        elif not isinstance(values, (list, tuple)):
            values = list(values)
        self.values = values
        self.stream = self._encode(values)

    @staticmethod
    def _encode(values) -> Optional[bytes]:
        n = len(values)
        if n == 0:
            return b"L0:"
        try:
            width = len(values[0])
            if set(map(len, values)) != {width}:
                return None
            prefix = "S%d:" % width
            body = prefix + prefix.join(values)
        except TypeError:  # a value without a length, or not a str
            return None
        if not body.isascii():
            return None
        return b"L%d:" % n + body.encode("ascii")


def _fold_obj(h, obj: Any, tally: Optional[list] = None) -> None:
    """Fold one value with unambiguous type tags. Dicts fold in sorted
    key order; ndarrays fold dtype/shape/raw bytes (no materialization,
    no Python-object hashing — nothing process-local). `tally`, where
    given, counts [string columns folded whole, values of string columns
    folded one by one]."""
    if obj is None:
        h.update(b"N")
    elif obj is True:
        h.update(b"T")
    elif obj is False:
        h.update(b"F")
    elif isinstance(obj, int):
        h.update(b"I" + str(obj).encode())
    elif isinstance(obj, float):
        h.update(b"D" + struct.pack("<d", obj))
    elif isinstance(obj, str):
        b = obj.encode("utf-8")
        h.update(b"S" + str(len(b)).encode() + b":")
        h.update(b)
    elif isinstance(obj, bytes):
        h.update(b"B" + str(len(obj)).encode() + b":")
        h.update(obj)
    elif isinstance(obj, np.ndarray):
        h.update(b"A" + str(obj.dtype).encode() + b"|"
                 + str(obj.shape).encode() + b"|")
        h.update(np.ascontiguousarray(obj).tobytes())
    elif isinstance(obj, StrColumn):
        if obj.stream is not None:
            h.update(obj.stream)
            if tally is not None:
                tally[0] += 1
        else:
            _fold_obj(h, obj.values, tally)
            if tally is not None:
                tally[1] += len(obj.values)
    elif isinstance(obj, (list, tuple)):
        h.update(b"L" + str(len(obj)).encode() + b":")
        for item in obj:
            _fold_obj(h, item, tally)
    elif isinstance(obj, dict):
        h.update(b"M" + str(len(obj)).encode() + b":")
        for key in sorted(obj):
            _fold_obj(h, key, tally)
            _fold_obj(h, obj[key], tally)
    else:
        # Unknown leaf (an already-constructed struct riding a dev-mode
        # payload): fold its type name only — replicated entries are
        # always plain msgpack types, so this never reaches exchange.
        h.update(b"O" + type(obj).__name__.encode())


class ReplicaDigest:
    """Rolling apply-effect hash chain with bounded checkpoints."""

    def __init__(self, interval: int = 64):
        self.interval = max(1, int(interval))
        self._lock = threading.Lock()
        self._chain = _GENESIS
        self._last_index = 0
        self._bucket = 0            # last checkpointed index // interval
        self._checkpoints: "OrderedDict[int, str]" = OrderedDict()
        self._verified_index = 0    # newest index already compared
        self._synced = True         # False: fold but never verify
        self._unsynced_reason = ""
        self._folds = 0
        self._column_folds = 0      # string columns folded whole
        self._row_folds = 0         # their values folded one by one
        self._exchanged = 0
        self._diverged = 0

    # ------------------------------------------------------------- folding
    def fold(self, index: int, msg_type: int, effect: Any) -> None:
        """Fold one applied entry's effect into the chain. Called with
        the apply path serialized (raft's FSM lock / DevRaft callers);
        the internal lock only protects readers on other threads."""
        h = hashlib.blake2b(digest_size=_DIGEST_SIZE)
        tally = [0, 0]
        with self._lock:
            h.update(self._chain)
            _fold_obj(h, index)
            _fold_obj(h, msg_type)
            _fold_obj(h, effect, tally)
            self._chain = h.digest()
            self._last_index = index
            self._folds += 1
            self._column_folds += tally[0]
            self._row_folds += tally[1]
            bucket = index // self.interval
            if bucket > self._bucket:
                self._bucket = bucket
                self._checkpoints[index] = self._chain.hex()
                while len(self._checkpoints) > _CHECKPOINT_KEEP:
                    self._checkpoints.popitem(last=False)
        metrics.incr_counter(("nomad", "fsm", "digest", "folds"))
        if tally[0]:
            metrics.incr_counter(
                ("nomad", "fsm", "digest", "column_folds"), tally[0])
        if tally[1]:
            metrics.incr_counter(
                ("nomad", "fsm", "digest", "row_folds"), tally[1])

    # ------------------------------------------------------------ exchange
    def checkpoint(self) -> Optional[Tuple[int, str]]:
        """Newest (index, chain hex) checkpoint — what the leader
        piggybacks on AppendEntries. None until `interval` applies."""
        with self._lock:
            if not self._checkpoints or not self._synced:
                return None
            index = next(reversed(self._checkpoints))
            return index, self._checkpoints[index]

    def verify(self, index: int, expected_hex: str) -> Optional[bool]:
        """Compare the leader's checkpoint against ours at `index`.

        Returns True on a real match, None when there is nothing to
        compare (not folded that far, checkpoint aged out, already
        verified, or this replica is unsynced) — and raises
        ReplicaDivergenceError on mismatch.
        """
        t0 = time.monotonic()
        with self._lock:
            if not self._synced or index <= self._verified_index:
                return None
            mine = self._checkpoints.get(index)
            if mine is None:
                return None
            self._verified_index = index
            self._exchanged += 1
            ok = mine == expected_hex
            if not ok:
                self._diverged += 1
        metrics.incr_counter(("nomad", "fsm", "digest", "exchanged"))
        metrics.measure_since(("nomad", "fsm", "digest", "verify_ms"), t0)
        if not ok:
            metrics.incr_counter(("nomad", "fsm", "digest", "diverged"))
            raise ReplicaDivergenceError(index, expected_hex, mine)
        return True

    # ----------------------------------------------------- snapshot seams
    def snapshot_state(self) -> Dict[str, Any]:
        """Chain value pinned for a snapshot (capture under the same
        lock discipline as the FSM pin so it matches the watermark)."""
        with self._lock:
            return {"index": self._last_index,
                    "digest": self._chain.hex()}

    def reseed(self, index: int, digest_hex: str) -> None:
        """Adopt a snapshot's chain value: folding resumes from the
        snapshot watermark and the chain stays canonical."""
        with self._lock:
            self._chain = bytes.fromhex(digest_hex)
            self._last_index = int(index)
            self._bucket = int(index) // self.interval
            self._checkpoints.clear()
            self._verified_index = int(index)
            self._synced = True
            self._unsynced_reason = ""

    def reset(self) -> None:
        """Back to genesis (quarantine wiped the FSM; a full log replay
        from index 1 re-derives the canonical chain)."""
        with self._lock:
            self._chain = _GENESIS
            self._last_index = 0
            self._bucket = 0
            self._checkpoints.clear()
            self._verified_index = 0
            self._synced = True
            self._unsynced_reason = ""

    def mark_unsynced(self, reason: str) -> None:
        """Stop verifying (keep folding) — e.g. a restored snapshot
        predates digests, or an injected fold fault broke the chain.
        Prevents false divergence alarms; the next reseed re-syncs."""
        with self._lock:
            self._synced = False
            self._unsynced_reason = reason

    # --------------------------------------------------------------- stats
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "Interval": self.interval,
                "LastIndex": self._last_index,
                "Chain": self._chain.hex(),
                "Checkpoints": dict(self._checkpoints),
                "VerifiedIndex": self._verified_index,
                "Synced": self._synced,
                "UnsyncedReason": self._unsynced_reason,
                "Folds": self._folds,
                "ColumnFolds": self._column_folds,
                "RowFolds": self._row_folds,
                "Exchanged": self._exchanged,
                "Diverged": self._diverged,
            }


# ------------------------------------------------------- effect summaries
def effect_of(state, index: int, msg_type: int,
              payload: Dict[str, Any]) -> Any:
    """Canonical post-apply effect summary for one entry: cheap readbacks
    of the rows the handler touched (ids + the status fields replicas
    must agree on). Message types are matched by INT VALUE so this stays
    import-light; the mapping mirrors fsm.MessageType."""
    if msg_type in (0, 2, 3):      # NodeRegister / status / drain updates
        node = state.node_by_id(payload["NodeID"]) \
            if "NodeID" in payload else state.node_by_id(
                payload["Node"]["ID"] if isinstance(payload["Node"], dict)
                else payload["Node"].ID)
        if node is None:
            return ("node", None)
        return ("node", node.ID, node.Status, bool(node.Drain),
                node.ModifyIndex)
    if msg_type == 1:              # NodeDeregister
        return ("node_del", payload["NodeID"])
    if msg_type == 4:              # JobRegister
        job_id = payload["Job"]["ID"] if isinstance(payload["Job"], dict) \
            else payload["Job"].ID
        job = state.job_by_id(job_id)
        return ("job", job_id, None if job is None else job.Status)
    if msg_type == 5:              # JobDeregister
        return ("job_del", payload["JobID"])
    if msg_type == 6:              # EvalUpdate
        out = []
        for e in payload["Evals"]:
            eid = e["ID"] if isinstance(e, dict) else e.ID
            ev = state.eval_by_id(eid)
            out.append((eid, None if ev is None else ev.Status))
        return ("evals", out)
    if msg_type == 7:              # EvalDelete
        return ("eval_del", sorted(payload.get("Evals", ())),
                sorted(payload.get("Allocs", ())))
    if msg_type == 8:              # AllocUpdate
        return ("allocs", _alloc_effects(state, payload))
    if msg_type == 9:              # AllocClientUpdate
        out = []
        for a in payload["Alloc"]:
            aid = a["ID"] if isinstance(a, dict) else a.ID
            alloc = state.alloc_by_id(aid)
            out.append((aid,
                        None if alloc is None else alloc.ClientStatus))
        return ("client", out)
    if msg_type in (10, 11):       # PeriodicLaunch upsert / delete
        launch = payload.get("Launch")
        if launch is not None:
            return ("launch",
                    launch["ID"] if isinstance(launch, dict) else launch.ID)
        return ("launch_del", payload["JobID"])
    if msg_type == 12:             # ServiceSync
        ups = [(r["ID"] if isinstance(r, dict) else r.ID)
               for r in payload.get("Upserts", ())]
        return ("services", sorted(ups),
                sorted(payload.get("Deletes", ())))
    if msg_type == 13:             # ApplySweepBatch — columns, raw
        return ("sweep", _sweep_effects(state, payload))
    return ("other", msg_type)


def _alloc_effects(state, payload: Dict[str, Any]) -> list:
    groups = payload.get("Batch")
    if groups is None:
        groups = [payload]
    out = []
    for group in groups:
        for a in group.get("Alloc", ()):
            aid = a["ID"] if isinstance(a, dict) else a.ID
            alloc = state.alloc_by_id(aid)
            if alloc is None:
                out.append((aid, None))
            else:
                out.append((aid, alloc.DesiredStatus, alloc.ClientStatus,
                            alloc.ModifyIndex))
    return out


def _sweep_effects(state, payload: Dict[str, Any]) -> list:
    """Columnar groups digest their columns directly — ids, rows,
    counts, usage delta — plus readbacks for any object co-groups. No
    row is ever materialized for the digest, and no id is visited alone
    (StrColumn). Instance names are not folded."""
    groups = payload.get("Batch")
    if groups is None:
        groups = [payload]
    out = []
    for group in groups:
        sweep = group.get("Sweep")
        if sweep is None:
            for a in group.get("Alloc", ()):
                aid = a["ID"] if isinstance(a, dict) else a.ID
                alloc = state.alloc_by_id(aid)
                out.append((aid, None if alloc is None
                            else alloc.DesiredStatus))
            continue
        out.append((
            StrColumn(sweep["AllocIDs"]),
            StrColumn(sweep["RowNodeIDs"]),
            np.asarray(sweep["Counts"], dtype=np.int64),
            np.asarray(sweep["Rows"], dtype=np.int64),
            np.asarray(sweep["Delta"], dtype=np.float32),
            sweep.get("Kind", "system"),
        ))
    return out


def chaos_corrupt(state, index: int, msg_type: int,
                  payload: Dict[str, Any]) -> bool:
    """`fsm.digest.mutate` drop-mode: silently corrupt the row this entry
    just wrote, IN PLACE and bypassing indexes — the exact failure the
    digest exists to catch. The corruption lands BEFORE the effect
    readback, so this replica folds the corrupt value while healthy
    replicas fold the clean one. Returns True when something mutated."""
    if msg_type == 6 and payload.get("Evals"):
        e = payload["Evals"][0]
        ev = state.eval_by_id(e["ID"] if isinstance(e, dict) else e.ID)
        if ev is not None:
            ev.Status = "chaos-diverged"
            return True
    if msg_type in (0, 2) :
        nid = payload.get("NodeID")
        if nid is None and "Node" in payload:
            nid = payload["Node"]["ID"] if isinstance(payload["Node"], dict) \
                else payload["Node"].ID
        node = state.node_by_id(nid) if nid else None
        if node is not None:
            node.Status = "chaos-diverged"
            return True
    if msg_type == 8:
        for aid, _ in ((a["ID"] if isinstance(a, dict) else a.ID, a)
                       for g in (payload.get("Batch") or [payload])
                       for a in g.get("Alloc", ())):
            alloc = state.alloc_by_id(aid)
            if alloc is not None:
                alloc.DesiredStatus = "chaos-diverged"
                return True
    return False
