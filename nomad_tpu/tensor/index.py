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
from typing import Optional

from nomad_tpu.state.state_store import StateStore
from nomad_tpu.structs import Allocation, Node

import numpy as np

from .node_table import NodeTensor, alloc_vec, resources_vec

# shared_elig's per-job view caches are unbounded across a long-lived
# server (one entry per job id ever swept); past this many entries the
# views are dropped and rebuilt lazily from the signature cache.
_ELIG_JOB_CACHE_CAP = 8192


class TensorIndex:
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
        for kind, old, new in events:
            if kind == "node":
                self._on_node(old, new)
                continue
            if kind != "alloc":
                continue
            was = old is not None and not old.terminal_status()
            now = new is not None and not new.terminal_status()
            if was:
                node_ids.append(old.NodeID)
                vecs.append(-alloc_vec(old))
            if now:
                node_ids.append(new.NodeID)
                vecs.append(alloc_vec(new))
        if node_ids:
            self.nt.apply_usage_deltas(
                node_ids, np.stack(vecs).astype(np.float32))

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
            self.nt.remove_alloc_usage(old)
        elif not was_counted and now_counted:
            self.nt.add_alloc_usage(new)
        elif was_counted and now_counted:
            # Resources may have changed (in-place update): re-account.
            self.nt.remove_alloc_usage(old)
            self.nt.add_alloc_usage(new)
