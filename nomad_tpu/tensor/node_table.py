"""Device-resident node table with incremental updates.

Columns (float32, resource dims R=5): cpu MHz, memory MB, disk MB, iops,
network mbits. Three persistent arrays:

  capacity  [N, R]  total node resources (the fit bound — reserved counts as
                    usage, matching reference AllocsFit, funcs.go:44-100)
  score_cap [N, 2]  (cpu, mem) minus reserved — the ScoreFit denominator
                    (funcs.go:105-117)
  usage     [N, R]  reserved + sum of non-terminal committed allocs

Rows are stable per node for the node's lifetime (free-list reuse), the array
is padded to power-of-two buckets so jit caches stay warm, and host numpy
mirrors are authoritative: device copies are refreshed by row-scatter of dirty
rows just before a scheduling kernel runs (SURVEY §7.3: keep the node tensor
resident, delta-scatter updates, never re-ship the table).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from nomad_tpu.analysis import guarded_by
from nomad_tpu.structs import Allocation, Node, Resources
from nomad_tpu.structs.structs import NodeStatusReady

RES_DIMS = 5  # cpu, mem, disk, iops, mbits
DIM_NAMES = ("cpu", "memory", "disk", "iops", "bandwidth")
_MIN_CAP = 64
# Dirty-row device refresh chunks (fixed shapes -> bounded compile count:
# trickle, steady, storm, and rebase-after-storm buckets).
_REFRESH_CHUNKS = (8, 128, 2048, 16384)


def resources_vec(r: Optional[Resources]) -> np.ndarray:
    out = np.zeros(RES_DIMS, dtype=np.float32)
    if r is None:
        return out
    out[0] = r.CPU
    out[1] = r.MemoryMB
    out[2] = r.DiskMB
    out[3] = r.IOPS
    out[4] = sum(n.MBits for n in r.Networks)
    return out


def alloc_vec(alloc: Allocation) -> np.ndarray:
    """Resource vector of an allocation, memoized on the instance: the
    commit path reads it three times per alloc (usage listener, vectorized
    plan verify, optimistic overlay). Allocations are value-frozen once
    built — anything that changes resources replaces the object — so the
    memo cannot go stale. Callers must not mutate the returned array."""
    vec = getattr(alloc, "_resvec_cache", None)
    if vec is not None:
        return vec
    if alloc.Resources is not None:
        out = resources_vec(alloc.Resources)
    else:
        out = np.zeros(RES_DIMS, dtype=np.float32)
        for r in alloc.TaskResources.values():
            out += resources_vec(r)
    alloc._resvec_cache = out
    return out


class NodeTensor:
    """Mutable host mirror + lazily synced device arrays of the node table."""

    def __init__(self, capacity_hint: int = _MIN_CAP):
        n = max(_MIN_CAP, _next_pow2(capacity_hint))
        self._lock = threading.RLock()
        self.n_rows = n
        self.capacity = np.zeros((n, RES_DIMS), dtype=np.float32)
        self.score_cap = np.ones((n, 2), dtype=np.float32)  # avoid div-by-0
        self.usage = np.zeros((n, RES_DIMS), dtype=np.float32)
        self.ready = np.zeros(n, dtype=bool)
        self.class_ids = np.zeros(n, dtype=np.int32)
        self.dc_ids = np.full(n, -1, dtype=np.int32)

        self.row_of: Dict[str, int] = {}
        self.node_of: List[Optional[str]] = [None] * n
        # Lazily built object-dtype mirror of node_of for vectorized
        # row->node-ID gathers (the windowed collect maps a whole window's
        # chosen rows in one fancy index instead of a Python lookup per
        # placement). Invalidated whenever a row's identity changes.
        self._node_id_arr: Optional[np.ndarray] = None
        self._free: List[int] = list(range(n - 1, -1, -1))
        self._reserved_cache: Dict[str, np.ndarray] = {}
        # Bumped whenever a row's IDENTITY changes (node removed, row freed
        # for reuse, table grown): a device-side usage chain built against an
        # older epoch may carry a departed node's usage on a reused row and
        # must rebase (shape checks alone miss free-list reuse).
        self.row_epoch = 0
        # Bumped on ANY node-set change (upsert, readiness flip, removal):
        # the invalidation key for caches derived from the node population —
        # the shared sweep eligibility (TensorIndex.shared_elig) and the
        # system scheduler's memoized ready-node list. Coarser than
        # row_epoch, which only tracks identity changes.
        self.node_version = 0

        # Vocabularies
        self.class_vocab: Dict[str, int] = {}
        self.class_names: List[str] = []
        self.dc_vocab: Dict[str, int] = {}
        self.dc_names: List[str] = []

        # Device sync state. Two dirty tiers: rows whose capacity/readiness
        # changed (node upserts — must always refresh) vs rows where only
        # USAGE moved (alloc commits). A caller that overrides usage with a
        # device-side chain can skip the usage tier entirely, turning the
        # steady-state storm refresh (one host->device transfer per
        # window) into zero transfers.
        self._dirty_rows: Set[int] = set()
        self._usage_dirty: Set[int] = set()
        # Allocations that stopped counting (free_usage), and their usage
        # summed by row: a usage chain that skipped the usage tier since
        # an earlier count never saw them (ChainArbiter.acquire).
        self.frees = 0
        self.freed_usage = np.zeros_like(self.usage)
        self._resized = True
        self._device: Optional[dict] = None
        # Multi-chip: when set, device arrays shard their node axis over the
        # mesh (jax.sharding) and every consumer kernel runs SPMD with XLA
        # inserting the ICI collectives (SURVEY §7.1: the node axis IS the
        # sharded tensor axis). None = single-device arrays, byte-identical
        # to the pre-mesh path.
        self.mesh = None
        self._node_sharding = None

    # --------------------------------------------------------------- mesh
    def set_mesh(self, mesh) -> None:
        """Shard the node axis of the device arrays over `mesh` (a 1-D
        jax.sharding.Mesh). Must be a power-of-two device count: rows are
        padded to powers of two (>= 64), so divisibility is guaranteed for
        any pow2 mesh up to 64 devices and preserved across table growth.
        Call before serving traffic; existing device arrays are rebuilt."""
        if mesh is None:
            self.mesh = None
            self._node_sharding = None
            self._device = None
            self._resized = True
            return
        n_dev = mesh.devices.size
        if n_dev & (n_dev - 1):
            raise ValueError(
                f"scheduling mesh needs a power-of-two device count, got "
                f"{n_dev}")
        if self.n_rows % n_dev:
            raise ValueError(
                f"node axis ({self.n_rows}) not divisible by mesh ({n_dev})")
        from jax.sharding import NamedSharding, PartitionSpec

        axis = mesh.axis_names[0]
        self.mesh = mesh
        self._node_sharding = NamedSharding(mesh, PartitionSpec(axis))
        self._device = None  # rebuild sharded on next device_arrays()
        self._resized = True

    def _put(self, arr: np.ndarray):
        """Upload one full array, sharded over the mesh when set."""
        import jax
        import jax.numpy as jnp

        if self._node_sharding is not None:
            return jax.device_put(arr, self._node_sharding)
        return jnp.asarray(arr)

    # ------------------------------------------------------------- vocab
    def class_id(self, computed_class: str) -> int:
        cid = self.class_vocab.get(computed_class)
        if cid is None:
            cid = len(self.class_names)
            self.class_vocab[computed_class] = cid
            self.class_names.append(computed_class)
        return cid

    def dc_id(self, dc: str) -> int:
        did = self.dc_vocab.get(dc)
        if did is None:
            did = len(self.dc_names)
            self.dc_vocab[dc] = did
            self.dc_names.append(dc)
        return did

    # ------------------------------------------------------------ updates
    def upsert_node(self, node: Node) -> None:
        with self._lock:
            row = self.row_of.get(node.ID)
            if row is None:
                row = self._alloc_row()
                self.row_of[node.ID] = row
                self.node_of[row] = node.ID
                self._node_id_arr = None
                self.usage[row] = 0.0
            cap = resources_vec(node.Resources)
            reserved = resources_vec(node.Reserved)
            self.capacity[row] = cap
            # ScoreFit denominator: total minus reserved for cpu/mem. May be
            # zero; the kernel reproduces Go's Inf/NaN division semantics.
            self.score_cap[row] = cap[:2] - reserved[:2]
            # Reserved is baseline usage; preserve the alloc-usage component.
            self.usage[row] = self.usage[row] - self._reserved_of(node.ID) + reserved
            self._reserved_cache[node.ID] = reserved
            self.ready[row] = (node.Status == NodeStatusReady) and not node.Drain
            self.class_ids[row] = self.class_id(node.ComputedClass)
            self.dc_ids[row] = self.dc_id(node.Datacenter)
            self._dirty_rows.add(row)
            self.node_version += 1

    def _reserved_of(self, node_id: str) -> np.ndarray:
        return self._reserved_cache.get(node_id, np.zeros(RES_DIMS, dtype=np.float32))

    def set_node_readiness(self, node_id: str, ready: bool) -> None:
        with self._lock:
            row = self.row_of.get(node_id)
            if row is None:
                return
            self.ready[row] = ready
            self._dirty_rows.add(row)
            self.node_version += 1

    def remove_node(self, node_id: str) -> None:
        with self._lock:
            row = self.row_of.pop(node_id, None)
            if row is None:
                return
            self.node_of[row] = None
            self._node_id_arr = None
            self.capacity[row] = 0.0
            self.score_cap[row] = 1.0
            self.usage[row] = 0.0
            self.ready[row] = False
            self.dc_ids[row] = -1
            self._free.append(row)
            self._dirty_rows.add(row)
            self._reserved_cache.pop(node_id, None)
            self.row_epoch += 1
            self.node_version += 1

    def reset(self) -> None:
        """Drop every row in place (a snapshot restore replaced the world
        and the incremental feed never saw the staged writes). Mirrors are
        zeroed, all rows freed, and BOTH epochs bump so every derived
        consumer — usage chains, shared eligibility, cached row-id arrays
        — rebuilds against the restored population. Mesh/sharding and the
        vocabularies survive: ids are append-only and stay valid."""
        with self._lock:
            self.capacity[:] = 0.0
            self.score_cap[:] = 1.0
            self.usage[:] = 0.0
            self.ready[:] = False
            self.class_ids[:] = 0
            self.dc_ids[:] = -1
            self.row_of.clear()
            self.node_of = [None] * self.n_rows
            self._node_id_arr = None
            self._free = list(range(self.n_rows - 1, -1, -1))
            self._reserved_cache.clear()
            self._dirty_rows.clear()
            self._usage_dirty.clear()
            self.freed_usage[:] = 0.0  # epochs bump: every chain resets
            self._resized = True  # full re-upload on next device_arrays
            self.row_epoch += 1
            self.node_version += 1

    def add_alloc_usage(self, alloc: Allocation) -> None:
        self._apply_usage(alloc, +1.0)

    def remove_alloc_usage(self, alloc: Allocation) -> None:
        self._apply_usage(alloc, -1.0)

    def _apply_usage(self, alloc: Allocation, sign: float) -> None:
        with self._lock:
            row = self.row_of.get(alloc.NodeID)
            if row is None:
                return
            self.usage[row] += sign * alloc_vec(alloc)
            self._usage_dirty.add(row)

    def apply_row_usage_deltas(self, rows: np.ndarray, vecs: np.ndarray,
                               epoch: int) -> bool:
        """Row-addressed batch usage transition: a columnar sweep commit
        carries its node ROWS from emit time, so when no row changed
        identity since (`epoch` still current) the whole batch lands as
        one scatter-add with ZERO per-node dict lookups. Returns False —
        apply nothing — when the epoch moved or rows are out of bounds;
        the caller falls back to the id-addressed path."""
        with self._lock:
            if len(rows) == 0:
                return True
            if epoch != self.row_epoch:
                return False
            if int(rows[-1]) >= self.n_rows:  # rows are sorted ascending
                return False
            np.add.at(self.usage, rows, vecs)
            self._usage_dirty.update(rows.tolist())
            return True

    def apply_usage_deltas(self, node_ids: Sequence[str],
                           vecs: np.ndarray) -> None:
        """Batched usage transitions under ONE lock: a committed plan's 50
        allocs become one scatter-add instead of 50 lock/indexing rounds
        (the plan applier is on the scheduling critical path)."""
        with self._lock:
            rows, keep = self._rows_of(node_ids)
            if not rows:
                return
            rows_arr = np.asarray(rows, dtype=np.int64)
            np.add.at(self.usage, rows_arr, vecs[keep])
            self._usage_dirty.update(rows)

    def free_usage(self, node_ids: Sequence[str], vecs: np.ndarray) -> None:
        """Take the usage of allocations that stopped counting off the
        table (`vecs`: their usage, positive), and keep it: counted
        (`frees`) and summed by row (`freed_usage`) for the usage chains
        that skipped the usage tier since."""
        with self._lock:
            rows, keep = self._rows_of(node_ids)
            if rows:
                rows_arr = np.asarray(rows, dtype=np.int64)
                np.subtract.at(self.usage, rows_arr, vecs[keep])
                np.add.at(self.freed_usage, rows_arr, vecs[keep])
                self._usage_dirty.update(rows)
            self.frees += len(node_ids)

    def freed(self) -> Tuple[int, np.ndarray]:
        """(`frees`, a copy of `freed_usage`), consistent with each other."""
        with self._lock:
            return self.frees, self.freed_usage.copy()

    def _rows_of(self, node_ids: Sequence[str]) -> Tuple[List[int],
                                                           List[int]]:
        """Rows of the nodes that have one, and their positions in
        `node_ids`. Caller holds _lock."""
        rows, keep = [], []
        for k, nid in enumerate(node_ids):
            row = self.row_of.get(nid)
            if row is not None:
                rows.append(row)
                keep.append(k)
        return rows, keep

    # ------------------------------------------------------------ row mgmt
    def _alloc_row(self) -> int:
        if not self._free:
            self._grow()
        return self._free.pop()

    def _grow(self) -> None:
        old = self.n_rows
        new = old * 2
        self.capacity = _grow2(self.capacity, new)
        self.score_cap = _grow2(self.score_cap, new, fill=1.0)
        self.usage = _grow2(self.usage, new)
        self.freed_usage = _grow2(self.freed_usage, new)
        self.ready = _grow1(self.ready, new, fill=False)
        self.class_ids = _grow1(self.class_ids, new, fill=0)
        self.dc_ids = _grow1(self.dc_ids, new, fill=-1)
        self.node_of.extend([None] * (new - old))
        self._free.extend(range(new - 1, old - 1, -1))
        self.n_rows = new
        self._resized = True
        self.row_epoch += 1

    # --------------------------------------------------------- device sync
    def device_arrays(self, skip_usage: bool = False) -> dict:
        """Return jax device arrays, refreshing dirty rows via scatter.

        skip_usage=True refreshes only rows whose capacity/readiness changed
        and leaves usage-only dirty rows queued — valid ONLY for callers that
        override the usage input with their own device-side chain (the
        pipelined worker mid-storm). The queued rows are flushed by the next
        full call."""
        with self._lock:
            pending = (set(self._dirty_rows) if skip_usage
                       else self._dirty_rows | self._usage_dirty)
            if self._device is None or self._resized:
                if (self._node_sharding is not None
                        and self.n_rows % self.mesh.devices.size):
                    raise ValueError(
                        f"node axis ({self.n_rows}) not divisible by mesh "
                        f"({self.mesh.devices.size})")
                self._device = {
                    "capacity": self._put(self.capacity),
                    "score_cap": self._put(self.score_cap),
                    "usage": self._put(self.usage),
                }
                self._resized = False
                self._dirty_rows.clear()
                self._usage_dirty.clear()
            elif pending:
                rows = np.fromiter(pending, dtype=np.int32)
                # Fixed-size scatter chunks (tail padded by repeating the
                # first row — sets are idempotent): ONE compiled refresh
                # program ever, instead of one per distinct dirty-row count.
                # A mid-serving XLA compile blocks the scheduling path for
                # hundreds of ms, which dwarfs any transfer saving.
                d = self._device
                # Smallest bucket that fits: compile count stays bounded
                # without shipping a storm-sized transfer when one heartbeat
                # dirtied one row.
                size = _REFRESH_CHUNKS[-1]
                for candidate in _REFRESH_CHUNKS:
                    if len(rows) <= candidate:
                        size = candidate
                        break
                for i in range(0, len(rows), size):
                    chunk = rows[i:i + size]
                    if len(chunk) < size:
                        chunk = np.concatenate(
                            [chunk, np.full(size - len(chunk),
                                            chunk[0], dtype=np.int32)])
                    # ONE host->device transfer per chunk: rows + all three
                    # column groups ride a single packed array and split
                    # device-side.
                    packed = np.concatenate(
                        [chunk[:, None].astype(np.float32),
                         self.capacity[chunk], self.score_cap[chunk],
                         self.usage[chunk]], axis=1)
                    d["capacity"], d["score_cap"], d["usage"] = \
                        _scatter_refresh(d["capacity"], d["score_cap"],
                                         d["usage"], packed)
                # The scatter writes all three column groups, so refreshed
                # rows are current in BOTH tiers regardless of why they were
                # dirty.
                self._dirty_rows -= pending
                self._usage_dirty -= pending
            return dict(self._device)

    def warm_device(self) -> None:
        """Precompile every dirty-row refresh program for the current table
        size. Each _REFRESH_CHUNKS bucket is a distinct XLA program; the
        first dirty set that lands in a cold bucket otherwise pays its
        compile (hundreds of ms) in the middle of serving. The warm scatter
        rewrites row 0 with its own current values — a no-op — so this is
        safe to call at any time; servers call it once the node table has
        reached steady size (e.g. after initial cluster sync)."""
        with self._lock:
            self.device_arrays()
            d = self._device
            for size in _REFRESH_CHUNKS:
                chunk = np.zeros(size, dtype=np.int32)
                packed = np.concatenate(
                    [chunk[:, None].astype(np.float32),
                     self.capacity[chunk], self.score_cap[chunk],
                     self.usage[chunk]], axis=1)
                d["capacity"], d["score_cap"], d["usage"] = \
                    _scatter_refresh(d["capacity"], d["score_cap"],
                                     d["usage"], packed)

    # ------------------------------------------------------------- queries
    def node_id_array(self) -> np.ndarray:
        """Object-dtype [n_rows] mirror of node_of, rebuilt lazily when a
        row's identity changes. Callers get a SNAPSHOT: a node removed
        after the return may still appear — the same benign race as a live
        node_of read per placement; the plan applier's re-verification
        against committed state owns the outcome either way."""
        with self._lock:
            arr = self._node_id_arr
            if arr is None or len(arr) != self.n_rows:
                arr = np.empty(self.n_rows, dtype=object)
                arr[:] = self.node_of
                self._node_id_arr = arr
            return arr

    def rows_for(self, node_ids: Sequence[str]) -> np.ndarray:
        return np.array([self.row_of[i] for i in node_ids], dtype=np.int32)

    def snapshot_rows(self, rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Consistent (usage, capacity) copies of the given rows, taken under
        the tensor lock. Alloc commits mutate usage rows IN PLACE
        (_apply_usage), so a lock-free reader could see a torn row — half a
        usage vector before an in-flight `+=`, half after. Fancy indexing
        copies, so the returned arrays are immune to later mutation."""
        with self._lock:
            return self.usage[rows], self.capacity[rows]

    def eligibility_mask(self, dc_ids: Sequence[int],
                        class_ok: Optional[np.ndarray]) -> np.ndarray:
        """ready & datacenter-membership & per-class eligibility, as [N] bool."""
        with self._lock:
            mask = self.ready.copy()
            if dc_ids is not None:
                mask &= np.isin(self.dc_ids, np.asarray(list(dc_ids), dtype=np.int32))
            if class_ok is not None:
                mask &= class_ok[self.class_ids]
            return mask


# Force a chain rebase after this many chained windows: the chain misses
# slow-path/fallback commits (undercount — the applier catches any
# oversubscription) and evictions (overcount — spurious blocked evals), so
# its drift is bounded even through a storm that never pauses.
REBASE_WINDOWS = 256


class ChainLease:
    """One window's exclusive hold on the shared device usage chain.

    Returned by :meth:`ChainArbiter.acquire`; carries the usage array the
    window's kernels must chain on (``None`` = committed usage from the
    table), the arbiter's taint sequence at acquire time (windows in
    flight compare it at finish to detect phantom usage raised under
    them), and the node-table row epoch observed at chain-validation time
    (a row changing identity mid-dispatch must still rebase the NEXT
    window). The holder ends the lease with exactly one of
    :meth:`ChainArbiter.publish` (fast evals dispatched — the window is
    now in flight) or :meth:`ChainArbiter.abort` (nothing dispatched)."""

    __slots__ = ("chain", "taint_seq", "epoch", "rebased", "released",
                 "seq", "frees")

    def __init__(self, chain, taint_seq: int, epoch: int, rebased: bool,
                 frees: int):
        self.chain = chain
        self.taint_seq = taint_seq
        self.epoch = epoch
        self.rebased = rebased
        self.frees = frees     # nt.frees the chain has seen: the usage of
        #                        later frees is still in it
        self.released = False  # publish/abort happened (one-shot)
        self.seq = 0           # chain position, assigned at publish


class ChainArbiter:
    """Arbiter of the cross-worker device usage chain.

    N pipelined workers place optimistically against one node table; their
    windows chain each kernel on the previous window's ``usage_after`` so
    every placement sees every placement dispatched before it — regardless
    of which worker dispatched it. Without arbitration, two workers each
    keep a PRIVATE chain from committed usage: neither sees the other's
    in-flight placements, both argmax onto the same best rows, and the
    plan applier bounces half the plans as partial commits (the measured
    2-worker collapse). The arbiter serializes only the chain handoff:

      * ``acquire`` — block until no other window is mid-dispatch, decide
        whether the tail is still valid (taint/epoch/depth/drained checks,
        previously per-worker ``_usage_chain``), and hand the tail out as
        a :class:`ChainLease`.
      * ``publish`` — install the window's ``usage_after`` as the new
        tail and count the window in flight; the next ``acquire`` (any
        worker) chains on it.
      * ``taint`` / ``finish_window`` — a window that ends with stale or
        fallback records left phantom usage in the chain; the taint bumps
        the sequence (in-flight windows quarantine their squeezed evals
        at finish) and marks the tail dirty so the next ``acquire`` drains
        ALL lease holders — across every worker — and rebases onto
        committed state coherently.

    Dispatch serialization is not a scaling loss: the dispatch stage is
    GIL-bound Python, so two workers' dispatches could not run
    concurrently anyway — the win is that their drain fetches (GIL
    released) and build stages interleave on a chain that stays
    coherent.

    On a sharded mesh the tail is a :class:`kernels.MeshChain` — the
    node-sharded usage PLUS a lead-device pending winner ring — not a
    plain array. The arbiter treats it opaquely: ``shape`` drives the
    resize/epoch rebase checks, publish/acquire hand it through, and a
    rebase simply drops it (committed state lives in the node tensor;
    the ring's placements either committed through plans or are being
    redelivered). Consumers that need real rows (eviction overlays,
    the monolithic-scan fallback, numpy readers) call
    ``materialize()``, which folds the ring into the sharded usage."""

    _concurrency = guarded_by(
        "_cond", "_tail", "_tail_epoch", "_holder", "_pending",
        "_windows_since_rebase", "_dirty", "_taint_seq", "_published_seq",
        "_settled_seq", "_chain_frees", "_chain_freed")

    def __init__(self, nt: NodeTensor, rebase_windows: int = REBASE_WINDOWS):
        self.nt = nt
        self.rebase_windows = rebase_windows
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._tail = None            # usage_after of the last dispatched window
        self._tail_epoch = -1        # nt.row_epoch the tail was validated at
        self._holder: Optional[str] = None  # window mid-dispatch (lease out)
        self._pending = 0            # published windows not yet finished
        self._windows_since_rebase = 0
        self._chain_frees = 0        # nt.frees the chain has seen, and
        self._chain_freed = None     # nt.freed_usage then (host tails)
        self._dirty = False          # tail carries phantom usage: rebase next
        self._taint_seq = 0
        # Chain-order finish barrier: windows SETTLE (make their phantom-
        # usage quarantine decision) in publish order, across workers.
        self._published_seq = 0      # windows published so far
        self._settled_seq = 0        # highest contiguously settled window
        self._drained = threading.Event()  # pending == 0 (across all workers)
        self._drained.set()

    # ------------------------------------------------------------- leasing
    def acquire(self, stop: Optional[threading.Event] = None,
                holder: str = "", drain_timeout: float = 60.0) -> ChainLease:
        """Take the window lease, waiting out any other worker's dispatch.

        Rebase decisions (all previously per-worker, now global): a dirty
        or depth-limited tail waits out EVERY in-flight window — any
        worker's — before restarting from committed state; an epoch/shape
        mismatch or a fully drained pipeline rebases immediately
        (committed state is strictly fresher once everything landed).
        The drain wait is bounded: a wedged window must not wedge every
        worker, and rebasing onto committed state early is always safe —
        the plan applier re-verifies every placement."""
        nt = self.nt
        with self._cond:
            while self._holder is not None:
                if stop is not None and stop.is_set():
                    raise RuntimeError("chain arbiter: worker stopping")
                self._cond.wait(0.1)
            self._holder = holder or "window"
            dirty = self._dirty
            self._dirty = False
            chain = self._tail
            if chain is not None and dirty:
                # Phantom usage baked into the tail: wait the in-flight
                # windows out (their commits land in the host mirror),
                # then restart from committed state.
                self._wait_drained_locked(stop, drain_timeout)
                chain = None
            if chain is not None and (chain.shape[0] != nt.n_rows
                                      or self._tail_epoch != nt.row_epoch):
                # Table resized OR a row changed identity (node removed /
                # freed row reused): the chain may carry a departed
                # node's usage on a row that now belongs to someone else.
                chain = None
            if chain is not None \
                    and self._windows_since_rebase >= self.rebase_windows:
                # Bound chain drift: drain the pipeline, then restart.
                self._wait_drained_locked(stop, drain_timeout)
                chain = None
            if chain is not None and self._pending == 0:
                # Pipeline is empty: everything this chain carries has
                # committed into the host mirror, so committed state is
                # strictly fresher (it also includes slow-path/fallback
                # commits the chain missed).
                chain = None
            rebased = self._tail is not None and chain is None
            if chain is None:
                self._tail = None
                self._windows_since_rebase = 0
                # Read before the window reads usage: a free that lands in
                # between is in both, and the applier refuses what that
                # understates (a host tail gives it back twice).
                self._chain_frees, self._chain_freed = nt.freed()
            elif (isinstance(chain, np.ndarray)
                  and nt.frees != self._chain_frees):
                # A host tail gives back the usage freed since it saw the
                # last free: one numpy subtraction. A device tail keeps it
                # (a program and an upload a window); the worker re-runs
                # what it refuses for that (_await_window).
                frees, freed = nt.freed()
                chain = self._tail = chain - (freed - self._chain_freed)
                self._chain_frees, self._chain_freed = frees, freed
            return ChainLease(chain=chain, taint_seq=self._taint_seq,
                              epoch=nt.row_epoch, rebased=rebased,
                              frees=self._chain_frees)

    def publish(self, lease: ChainLease, usage_after) -> None:
        """Install the dispatched window's usage tail and count it in
        flight; releases the dispatch lease."""
        with self._cond:
            if lease.released:
                return
            lease.released = True
            self._published_seq += 1
            lease.seq = self._published_seq
            self._tail = usage_after
            self._tail_epoch = lease.epoch
            self._windows_since_rebase += 1
            self._pending += 1
            self._drained.clear()
            self._holder = None
            self._cond.notify_all()

    def abort(self, lease: ChainLease) -> None:
        """Release the dispatch lease without publishing (the window had
        no fast evals, or dispatch failed before any kernel launched).
        One-shot like publish: a double release must not free a lease
        another worker has since acquired."""
        with self._cond:
            if lease.released:
                return
            lease.released = True
            self._holder = None
            self._cond.notify_all()

    # ------------------------------------------------------ window lifetime
    def finish_window(self) -> bool:
        """A published window fully finished (built, acked or nacked).
        Returns True when that drained the pipeline across ALL workers."""
        with self._cond:
            self._pending = max(0, self._pending - 1)
            drained = self._pending == 0
            if drained:
                self._drained.set()
                self._cond.notify_all()
            return drained

    def taint(self) -> None:
        """A window ended with stale/fallback records: its chained kernel
        placements never commit as dispatched. Windows in flight on the
        tainted tail detect this via the sequence bump; the next acquire
        sees the dirty flag and rebases."""
        with self._cond:
            self._taint_seq += 1
            self._dirty = True

    def taint_changed(self, seq: int) -> bool:
        with self._cond:
            return self._taint_seq != seq

    def wait_turn(self, seq: int, stop: Optional[threading.Event] = None,
                  timeout: float = 60.0) -> bool:
        """Block until every window published BEFORE chain position `seq`
        has SETTLED — made its phantom-usage quarantine decision and
        raised any taint. One build thread per worker settles its own
        windows in order, but with N workers a window chained on another
        worker's tail can otherwise finish first and consult the taint
        sequence before the tail owner raises it — parking squeezed evals
        as blocked on capacity that was never really taken. Bounded: a
        wedged predecessor must not wedge every worker, and proceeding
        early only risks the (rare, logged) missed-quarantine the barrier
        normally closes."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._settled_seq < seq - 1:
                if stop is not None and stop.is_set():
                    return False
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(min(remaining, 0.1))
            return True

    def mark_settled(self, seq: int) -> None:
        """The window at chain position `seq` made its taint decision;
        successors may now make theirs. Idempotent (the build loop's
        finally re-marks windows _finish_fast already settled)."""
        with self._cond:
            if seq > self._settled_seq:
                self._settled_seq = seq
                self._cond.notify_all()

    def wait_drained(self, timeout: Optional[float] = None) -> bool:
        """Block until no window is in flight (PipelinedWorker.quiesce)."""
        return self._drained.wait(timeout)

    def wait_dispatch_idle(self, timeout: float) -> bool:
        """Park until no window is mid-dispatch (the lease is free),
        WITHOUT acquiring: a worker waits its turn BEFORE dequeuing evals
        it could not launch anyway. Dequeue-then-wait holds those evals
        hostage through the other worker's dispatch — their deadlines
        burn and the storm splinters into one-eval windows. The lease is
        only held during dispatch, so a worker parked here still wakes in
        time to dispatch while the previous window's drain/build (the
        device readback and plan-applier wait) run lease-free."""
        with self._cond:
            deadline = time.monotonic() + timeout
            while self._holder is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(min(remaining, 0.05))
            return True

    @property
    def pending(self) -> int:
        with self._cond:
            return self._pending

    @property
    def dirty(self) -> bool:
        with self._cond:
            return self._dirty

    def _wait_drained_locked(self, stop: Optional[threading.Event],
                             timeout: float) -> None:
        """Wait (bounded, stop-aware) for pending == 0 with _lock held.
        Proceeding before fully drained is safe — it only rebases onto
        committed state while windows are still landing, which the plan
        applier's re-verification already tolerates."""
        deadline = time.monotonic() + timeout
        while self._pending > 0:
            if stop is not None and stop.is_set():
                return
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return
            self._cond.wait(min(remaining, 0.1))


@functools.lru_cache(maxsize=None)
def _refresh_program():
    """The jitted split + 3-way row scatter (built on first use so that
    importing this module does not import jax)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def node_table_refresh(cap, sc, us, pk):  # kernels.PROGRAM_NAMES
        rows = pk[:, 0].astype(jnp.int32)
        cap_v = pk[:, 1:1 + RES_DIMS]
        sc_v = pk[:, 1 + RES_DIMS:3 + RES_DIMS]
        us_v = pk[:, 3 + RES_DIMS:]
        return (cap.at[rows].set(cap_v), sc.at[rows].set(sc_v),
                us.at[rows].set(us_v))

    return node_table_refresh


def _scatter_refresh(capacity, score_cap, usage, packed):
    """Row-scatter one packed refresh transfer into the device tables.
    packed: [k, 1 + R + 2 + R] f32 = (row, capacity, score_cap, usage)."""
    # packed stays a host array (uncommitted): jit places it with the other
    # operands, which may be sharded over a mesh — an eager jnp.asarray here
    # would commit it to the default device and conflict.
    return _refresh_program()(capacity, score_cap, usage, packed)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _grow2(a: np.ndarray, n: int, fill: float = 0.0) -> np.ndarray:
    out = np.full((n, a.shape[1]), fill, dtype=a.dtype)
    out[: a.shape[0]] = a
    return out


def _grow1(a: np.ndarray, n: int, fill) -> np.ndarray:
    out = np.full(n, fill, dtype=a.dtype)
    out[: a.shape[0]] = a
    return out
