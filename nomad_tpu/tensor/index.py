"""TensorIndex: keeps the device-resident NodeTensor in sync with the store.

Subscribes to StateStore change events and applies delta updates (node
upserts, alloc usage transitions) to the NodeTensor — the tensor analogue of
go-memdb's indexing, and the mechanism that keeps scheduling from ever
re-shipping the full node table to the device (SURVEY §7.3).

An alloc contributes usage while non-terminal; transitions are derived from
(old, new) pairs so the accounting is exact.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, Optional, Tuple

from nomad_tpu.analysis import guarded_by
from nomad_tpu.state.state_store import StateStore
from nomad_tpu.structs import Allocation, Node

import numpy as np

from .node_table import NodeTensor, alloc_vec, resources_vec

# shared_elig's per-job view caches are unbounded across a long-lived
# server (one entry per job id ever swept); past this many entries the
# views are dropped and rebuilt lazily from the signature cache.
_ELIG_JOB_CACHE_CAP = 8192

# Node contexts kept per index: one per datacenter set, the oldest dropped.
_NODE_CTX_CAP = 8
# Prepared batches kept per node context (a window holds at most its own
# size in distinct signatures), and the signature masks under them: past
# either the oldest batch goes, or the masks are dropped and regenerate.
_NODE_CTX_PREP_CAP = 256
_NODE_CTX_SIG_CAP = 1024


class NodeContext:
    """What a window of service/batch evals places against, as a function
    of the nodes table alone: the ready nodes of one datacenter set, their
    candidate row mask, the class eligibility over them and the count per
    datacenter, plus the prepared batches assembled under them. Lives as
    long as the nodes it was built from (TensorIndex.node_context), so it
    is shared by windows and workers and VALUE-FROZEN by the contract of
    stack._tg_template and alloc._resvec_cache: every consumer reads, a
    change builds a new context."""

    _concurrency = guarded_by("_lock", "_preps")

    def __init__(self, key: tuple, nodes_by_id: dict, cand_mask: np.ndarray,
                 elig, by_dc: Dict[str, int]):
        self.key = key
        self.nodes_by_id = nodes_by_id
        self.cand_mask = cand_mask
        self.elig = elig
        self.by_dc = by_dc
        self._lock = threading.Lock()
        # (prep signature, id of the noise vector embedded) -> PreparedBatch
        self._preps: "OrderedDict[Tuple[tuple, int], object]" = OrderedDict()

    def window_elig(self):
        """The eligibility one window reads: the shared class
        representatives and signature masks under per-job views of its own
        (ClassEligibility.view says why those do not outlive it)."""
        if len(self.elig._sig_cache) > _NODE_CTX_SIG_CAP:
            self.elig._sig_cache.clear()
        return self.elig.view()

    def prep(self, sig: tuple, noise_vec: np.ndarray):
        """The batch prepared under this context for `sig` WITH this very
        noise vector, or None: each worker renews its noise now and then,
        and a batch embeds the vector it was prepared with."""
        key = (sig, id(noise_vec))
        with self._lock:
            prep = self._preps.get(key)
            if prep is None or prep.noise_vec is not noise_vec:
                return None
            self._preps.move_to_end(key)
            return prep

    def keep_prep(self, sig: tuple, prep) -> None:
        with self._lock:
            self._preps[(sig, id(prep.noise_vec))] = prep
            while len(self._preps) > _NODE_CTX_PREP_CAP:
                self._preps.popitem(last=False)

    def drop_noise(self, noise_vec: np.ndarray) -> None:
        with self._lock:
            for key in [k for k, p in self._preps.items()
                        if p.noise_vec is noise_vec]:
                del self._preps[key]


class TensorIndex:
    _concurrency = guarded_by("_ctx_lock", "_node_ctx")

    def __init__(self, nt: Optional[NodeTensor] = None):
        self.nt = nt or NodeTensor()
        # True when subscribed to a store's change feed (stays in sync and
        # must not be discarded on state refresh).
        self.attached = False
        # Mirrors ServerConfig.host_placement: False forces every stack
        # sharing this index onto the device kernels, including the
        # per-eval slow path (the mesh serving tests rely on it).
        self.allow_host_select = True
        # System-sweep eligibility: ONE ClassEligibility over the whole
        # node table, shared by every system evaluation until the node
        # population changes (nt.node_version). Building it walks every
        # node once; without the cache a 50-job system storm pays that
        # O(cluster) walk 50 times.
        self._elig_lock = threading.Lock()
        self._elig_cache: Optional[tuple] = None  # (node_version, elig)
        # Window node contexts (node_context): datacenter tuple -> the
        # newest NodeContext built for it, oldest set first.
        self._ctx_lock = threading.Lock()
        self._node_ctx: "OrderedDict[tuple, NodeContext]" = OrderedDict()

    def shared_elig(self, state):
        """Shared, node-version-keyed ClassEligibility over ALL table rows.

        Safe to share across jobs and DCs: the datacenter is part of the
        computed class (structs/node_class.py), so any class representative
        is exact for every member, and per-job masks AND against the
        caller's ready/DC row mask. Concurrent workers may race to build
        one — the loser's copy is simply dropped (values are identical)."""
        from .constraints import ClassEligibility

        with self._elig_lock:
            ver = self.nt.node_version
            cached = self._elig_cache
            if cached is not None and cached[0] == ver:
                elig = cached[1]
                if len(elig._job_cache) > _ELIG_JOB_CACHE_CAP:
                    # The signature cache holds the actual [n_rows] mask
                    # arrays — clearing only the per-job views would keep
                    # every mask alive; all three regenerate on demand.
                    elig._job_cache.clear()
                    elig._tg_cache.clear()
                    elig._sig_cache.clear()
                return elig
        elig = ClassEligibility(self.nt, list(state.nodes()))
        with self._elig_lock:
            # Re-check: the population may have moved while we built.
            if self.nt.node_version == ver:
                self._elig_cache = (ver, elig)
        return elig

    def _node_ctx_key(self, snap, dc_key: tuple) -> tuple:
        """Everything a NodeContext is a function of, read from the input:
        the store behind the snapshot (a follower's snapshot never meets a
        context built from another store), the snapshot's nodes index
        (clamped to its watermark: equal values mean no nodes write lies
        between two snapshots), the datacenter set, and the table the rows
        and class ids come from: its row identities and shape (row_epoch,
        n_rows: a restore or a grown table remaps or reshapes cand_mask),
        its node population (node_version: class_ids and row_of are read
        live) and its mesh (a prepared batch keeps its device inputs)."""
        nt = self.nt
        with nt._lock:
            table = (nt.row_epoch, nt.n_rows, nt.node_version, nt.mesh)
        return (snap.store, snap.get_index("nodes"), dc_key) + table

    def node_context(self, snap, datacenters) -> Tuple[NodeContext, bool]:
        """(context, hit): the NodeContext for `datacenters` under `snap`,
        kept until the nodes table changes (_node_ctx_key). A miss builds
        what every window used to build for itself; concurrent builders may
        race, the loser's copy is dropped (values are identical), and a
        context whose table moved while it was built is handed to its
        caller but not kept."""
        from nomad_tpu.scheduler.util import ready_nodes_in_dcs

        from .constraints import ClassEligibility

        dc_key = tuple(sorted(datacenters))
        key = self._node_ctx_key(snap, dc_key)
        with self._ctx_lock:
            ctx = self._node_ctx.get(dc_key)
            if ctx is not None and ctx.key == key:
                self._node_ctx.move_to_end(dc_key)
                return ctx, True
        nt = self.nt
        nodes, by_dc = ready_nodes_in_dcs(snap, list(dc_key))
        cand_mask = np.zeros(nt.n_rows, dtype=bool)
        for n in nodes:
            row = nt.row_of.get(n.ID)
            if row is not None:
                cand_mask[row] = True
        ctx = NodeContext(key, {n.ID: n for n in nodes}, cand_mask,
                          ClassEligibility(nt, nodes), by_dc)
        if self._node_ctx_key(snap, dc_key) != key:
            return ctx, False
        with self._ctx_lock:
            kept = self._node_ctx.get(dc_key)
            if kept is not None and kept.key == key:
                return kept, False
            self._node_ctx[dc_key] = ctx
            self._node_ctx.move_to_end(dc_key)
            while len(self._node_ctx) > _NODE_CTX_CAP:
                self._node_ctx.popitem(last=False)
        return ctx, False

    def drop_noise(self, noise_vec: np.ndarray) -> None:
        """A worker renewed its tie-break noise: the batches prepared with
        the old vector go, so none pins it."""
        with self._ctx_lock:
            contexts = list(self._node_ctx.values())
        for ctx in contexts:
            ctx.drop_noise(noise_vec)

    def _seed_from(self, state) -> None:
        """Seed the tensor from any read API: every node a row, usage =
        the non-terminal allocs. The ONE copy of the seeding semantics
        (attach / from_state / on_restore all build through here)."""
        for node in state.nodes():
            self.nt.upsert_node(node)
        for alloc in state.allocs():
            if not alloc.terminal_status():
                self.nt.add_alloc_usage(alloc)

    @staticmethod
    def attach(store: StateStore) -> "TensorIndex":
        """Production mode: subscribe to store changes and stay in sync."""
        idx = TensorIndex()
        idx.attached = True
        idx._seed_from(store)
        # The index object itself is the listener: _emit prefers its
        # on_change_batch; __call__ keeps the per-event contract.
        store.add_change_listener(idx)
        return idx

    @staticmethod
    def from_state(state) -> "TensorIndex":
        """One-shot build from any read API (snapshot) — test/simple mode."""
        idx = TensorIndex()
        idx._seed_from(state)
        return idx

    def on_restore(self, store) -> None:
        """Listener hook fired by Restore.commit() after a snapshot
        restore swapped the store's tables wholesale: the incremental
        change feed never saw the staged writes, so the tensor rebuilds
        from the restored world. Row identities change (row_epoch bumps
        inside reset), forcing in-flight usage chains to rebase."""
        self.nt.reset()
        self._seed_from(store)

    def resync_usage(self, state) -> int:
        """Warm-failover usage re-seed: recompute every node's usage from
        the replicated store (reserved + live alloc vectors), correct any
        row that drifted, and reconcile membership (a node the change
        feed missed is upserted; a departed one is removed). Returns the
        number of corrected rows — a new leader term calls this before
        serving so its placement kernels never start on drifted usage."""
        nt = self.nt
        nodes = list(state.nodes())
        live_by_node = {}
        for alloc in state.allocs():
            if not alloc.terminal_status():
                live_by_node.setdefault(alloc.NodeID, []).append(alloc)
        fixed = 0
        with nt._lock:
            seen = set()
            for node in nodes:
                seen.add(node.ID)
                if node.ID not in nt.row_of:
                    nt.upsert_node(node)
                    fixed += 1
            for node_id in [n for n in nt.row_of if n not in seen]:
                nt.remove_node(node_id)
                fixed += 1
            for node in nodes:
                row = nt.row_of[node.ID]
                expected = resources_vec(node.Reserved).copy()
                for alloc in live_by_node.get(node.ID, ()):
                    expected += alloc_vec(alloc)
                if not np.allclose(nt.usage[row], expected, atol=1e-3):
                    nt.usage[row] = expected
                    nt._usage_dirty.add(row)
                    fixed += 1
        return fixed

    def _on_change(self, kind: str, old, new) -> None:
        if kind == "node":
            self._on_node(old, new)
        elif kind == "alloc":
            self._on_alloc(old, new)

    # Listener protocol: callable per-event, batch-capable via
    # on_change_batch (preferred by state_store._emit).
    __call__ = _on_change

    def on_sweep_batch(self, node_ids, rows, delta, epoch: int) -> None:
        """Columnar sweep-commit listener (state_store.apply_sweep_segment):
        the batch's per-row demand lands as ONE scatter-add. Row-addressed
        when the tensor epoch still matches emit time (no dict lookups at
        all); id-addressed otherwise (rows may have changed identity)."""
        delta = np.asarray(delta, dtype=np.float32)
        if rows is not None and self.nt.apply_row_usage_deltas(
                np.asarray(rows, dtype=np.int64), delta, epoch):
            return
        self.nt.apply_usage_deltas(list(node_ids), delta)

    def on_change_batch(self, events) -> None:
        """Batch form the state store prefers (state_store._emit): alloc
        usage transitions collapse into one scatter-add under one tensor
        lock; node events keep their per-event path (rare)."""
        node_ids = []
        vecs = []
        freed_ids = []
        freed = []
        for kind, old, new in events:
            if kind == "node":
                self._on_node(old, new)
                continue
            if kind != "alloc":
                continue
            was = old is not None and not old.terminal_status()
            now = new is not None and not new.terminal_status()
            if was and not now:
                freed_ids.append(old.NodeID)
                freed.append(alloc_vec(old))
                continue
            if was:
                node_ids.append(old.NodeID)
                vecs.append(-alloc_vec(old))
            if now:
                node_ids.append(new.NodeID)
                vecs.append(alloc_vec(new))
        if node_ids:
            self.nt.apply_usage_deltas(
                node_ids, np.stack(vecs).astype(np.float32))
        if freed_ids:
            self.nt.free_usage(freed_ids,
                               np.stack(freed).astype(np.float32))

    def _on_node(self, old: Optional[Node], new: Optional[Node]) -> None:
        if new is None:
            if old is not None:
                self.nt.remove_node(old.ID)
            return
        self.nt.upsert_node(new)

    def _on_alloc(self, old: Optional[Allocation], new: Optional[Allocation]) -> None:
        was_counted = old is not None and not old.terminal_status()
        now_counted = new is not None and not new.terminal_status()
        if was_counted and not now_counted:
            self.nt.free_usage([old.NodeID], alloc_vec(old)[None, :])
        elif not was_counted and now_counted:
            self.nt.add_alloc_usage(new)
        elif was_counted and now_counted:
            # Resources may have changed (in-place update): re-account.
            self.nt.remove_alloc_usage(old)
            self.nt.add_alloc_usage(new)
