"""Placement stacks backed by the XLA kernels (reference: scheduler/stack.go).

The reference wires per-node iterator chains; here a stack assembles device
inputs (eligibility masks from the class-constraint compiler, usage deltas
from the plan under construction, anti-affinity counts) and runs ONE
place_batch program for all of an evaluation's placements. Network/port
assignment — inherently sequential, string/random heavy — happens host-side
for the chosen winners only, mirroring the reference's behavior of only
network-checking nodes that survive ranking (reference: rank.go:150-240).
"""

from __future__ import annotations

import hashlib
import random
import threading
import time
from collections import OrderedDict
from contextlib import nullcontext
from itertools import accumulate
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from nomad_tpu.structs import (
    Allocation,
    ColumnarPlacements,
    Job,
    NetworkIndex,
    Node,
    Resources,
    TaskGroup,
)
from nomad_tpu.structs.structs import (
    AllocClientStatusPending,
    AllocDesiredStatusRun,
    ConstraintDistinctHosts,
    JobTypeBatch,
    generate_uuid,
    stamp_alloc,
    uuid_rows,
    uuid_strings,
)
from nomad_tpu.tensor import ClassEligibility, TensorIndex, alloc_vec, resources_vec
from nomad_tpu.tensor.node_table import DIM_NAMES, RES_DIMS

from . import kernels
from .context import EvalContext
from .system_sweep import SweepBatch
from .util import task_group_constraints

# Anti-affinity penalties (reference: stack.go:10-19)
SERVICE_JOB_ANTI_AFFINITY_PENALTY = 10.0
BATCH_JOB_ANTI_AFFINITY_PENALTY = 5.0

_NOISE_SCALE = 1e-3


class _DeviceInputCache:
    """Content-addressed host->device transfer cache.

    A scheduling storm would otherwise re-upload the SAME eligibility
    masks, demand vectors, and zero count/host arrays for every eval (a
    [T, N] mask is N bytes per key per put). Keying on the exact bytes
    (not an identity or semantic key) makes the cache safe under any caller:
    equal content -> same immutable device buffer. Bounded LRU."""

    def __init__(self, cap: int = 256):
        self.cap = cap
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()
        self._lock = threading.Lock()

    def get(self, arr: np.ndarray, sharding=None):
        import jax
        import jax.numpy as jnp

        arr = np.ascontiguousarray(arr)
        # 128-bit content digest as the key: exact-bytes keys would retain a
        # full host copy of every cached array (MBs at large node counts).
        # The sharding is part of the key — the same bytes placed on a mesh
        # and on a single device are different buffers.
        key = (hashlib.blake2b(arr.tobytes(), digest_size=16).digest(),
               arr.dtype.str, arr.shape, sharding)
        with self._lock:
            dev = self._entries.get(key)
            if dev is not None:
                self._entries.move_to_end(key)
                return dev
        dev = (jax.device_put(arr, sharding) if sharding is not None
               else jnp.asarray(arr))
        with self._lock:
            self._entries[key] = dev
            while len(self._entries) > self.cap:
                self._entries.popitem(last=False)
        return dev


_dev_cache = _DeviceInputCache()


def device_input(arr: np.ndarray, sharding=None):
    """Public handle on the content-addressed transfer cache for windowed
    callers outside the stack (the pipelined drain's compaction inputs are
    byte-identical across a storm's windows, so they upload once)."""
    return _dev_cache.get(arr, sharding)


class WindowAccumulator:
    """Deferred window-usage accumulator shared by every eval of a window.

    The chain-replay usage exists ONLY for exhaustion diagnostics
    (_note_exhaustion diffs against the usage the kernel actually saw), so
    an all-placed storm window must not pay a scatter per eval for an
    array nothing reads. Placements queue as (rows, demand-vec) batches;
    the first exhaustion materializes everything queued so far with ONE
    np.add.at — the same values the per-eval eager scatters produced,
    since adds commute and recs are processed in chain order."""

    __slots__ = ("n_rows", "_rows", "_vecs", "_usage")

    def __init__(self, n_rows: int):
        self.n_rows = n_rows
        self._rows: List[np.ndarray] = []
        self._vecs: List[np.ndarray] = []
        self._usage: Optional[np.ndarray] = None

    def add(self, rows: np.ndarray, vecs: np.ndarray) -> None:
        if self._usage is not None:
            np.add.at(self._usage, rows, vecs)
        else:
            self._rows.append(rows)
            self._vecs.append(vecs)

    def usage(self) -> np.ndarray:
        if self._usage is None:
            self._usage = np.zeros((self.n_rows, RES_DIMS), dtype=np.float32)
        if self._rows:
            np.add.at(self._usage,
                      np.concatenate(self._rows),
                      np.concatenate(self._vecs))
            self._rows.clear()
            self._vecs.clear()
        return self._usage


class _Queued(NamedTuple):
    """One record of a window that WindowCollect builds as columns."""

    stack: "GenericStack"
    prep: "PreparedBatch"
    cr: "kernels.CompactResult"
    eval_id: str
    job: Job
    place: Sequence
    plan: object


class WindowCollect:
    """The collect pass of one scheduling window: compacted kernel output
    to plans, for all of the window's evals at once.

    `add` is the ordered walk, one call an eval in chain order. An eval
    whose every placement found a row and whose groups ask for no network
    (the storm case) only queues its rows on the window's accumulator and
    waits for `build`; any other runs the exact per-placement loop there
    and then, so that its exhaustion diagnostics read the usage the
    kernel saw. `build` then makes the queued evals' plans in ONE
    columnar pass: one stable sort on (eval, row), the unique rows,
    counts and summed demand of every eval from the sorted runs, one
    gather of node ids, one id draw, one permutation of the names. What
    is left per eval is what is per eval: the metric snapshot with its
    scores, a template Allocation a task group, and slices of the
    window's columns into the plan's SweepBatch.

    The columns ride each plan as a SweepBatch descriptor
    (kind="service"): the applier bulk-verifies it as one vector op,
    replicates it as one ApplySweepBatch raft entry, and the store
    scatter-applies it as a SweepSegment. plan.NodeAllocation is a
    ColumnarPlacements view over the same descriptor, so the all-fit
    path never holds an object per placement; a reader that wants them
    (partial verdict, refused descriptor, exact verify, serialisation)
    has them stamped from the templates on first ask. The evals may
    belong to different PreparedBatches, stacks and node sets. On a plan
    that already holds placements the objects are stamped at once, beside
    the descriptor."""

    __slots__ = ("nt", "acc", "net_span", "_queued")

    def __init__(self, nt, acc: Optional[WindowAccumulator] = None,
                 net_span=nullcontext):
        self.nt = nt
        self.acc = acc if acc is not None else WindowAccumulator(nt.n_rows)
        # () -> context manager round an eval's network assignments (the
        # window worker's `netassign` stage); entered only by an eval
        # whose groups ask for a network.
        self.net_span = net_span
        self._queued: List[_Queued] = []

    def add(self, stack: "GenericStack", prep: "PreparedBatch", cr,
            eval_id: str, job: Job, place, plan,
            failed_tg_allocs) -> Optional[bool]:
        """The window's next eval, in chain order. None: queued for
        `build`, whose verdicts come in the order of these calls.
        Otherwise the exact build's verdict (False: a winner failed
        host-side network assignment or its node vanished, and the caller
        falls back to the exact per-eval path)."""
        if cr.ok and not prep.has_network_asks:
            n = len(place)
            # A node that turns out to have vanished leaves these rows in
            # the accumulator: the kernel saw them too, and the phantom-
            # usage quarantine re-runs whatever failed behind them.
            self.acc.add(cr.chosen[:n], prep.demands[:n])
            self._queued.append(
                _Queued(stack, prep, cr, eval_id, job, place, plan))
            return None
        return stack._collect_build_exact(prep, cr, eval_id, job, place,
                                          plan, failed_tg_allocs, self.acc,
                                          self.net_span)

    def build(self) -> List[bool]:
        """Plans for the queued evals, one verdict each (False: a chosen
        node vanished mid-window, row freed or reused; that eval alone
        falls back, as when the per-placement lookup fails)."""
        queued, self._queued = self._queued, []
        return self._build(queued) if queued else []

    def _build(self, queued: List[_Queued]) -> List[bool]:
        nt = self.nt
        n_rows = nt.n_rows
        n_evals = len(queued)
        layouts = [q.stack._template_layout(q.prep) for q in queued]
        ns = [len(q.place) for q in queued]
        offs = list(accumulate(ns, initial=0))
        total = offs[-1]

        # One stable sort on (eval, row): an eval's placements stay
        # together, row-sorted, in placement order within a row.
        rows = key = np.concatenate(
            [q.cr.chosen[:n] for q, n in zip(queued, ns)], dtype=np.int64)
        if n_evals > 1:  # a window of one has nothing to offset
            key = rows + np.repeat(
                np.arange(0, n_evals * n_rows, n_rows), ns)
        order = np.argsort(key, kind="stable")
        key = key[order]
        first = np.empty(total, dtype=bool)  # of its run, one a (eval, row)
        first[0] = True
        np.not_equal(key[1:], key[:-1], out=first[1:])
        run_starts = np.flatnonzero(first)
        run_ends = np.append(run_starts, total)
        urows = rows[order[run_starts]]
        run_of = np.searchsorted(run_starts, offs).tolist()

        id_arr = nt.node_id_array()
        epoch = nt.row_epoch
        node_ids = id_arr[urows].tolist()
        verdicts = [q.stack._nodes_by_id.keys() >= set(node_ids[lo:hi])
                    for q, lo, hi in zip(queued, run_of, run_of[1:])]
        if not all(verdicts):
            # Rare: the vanished evals leave, the others' columns are
            # made without them.
            kept = iter(self._build(
                [q for q, ok in zip(queued, verdicts) if ok])
                if any(verdicts) else ())
            return [ok and next(kept) for ok in verdicts]

        # Summed demand per (eval, row) from the template resource
        # vectors: exactly what alloc_vec() yields for every stamped
        # clone, so the applier's bulk verify and the optimistic overlay
        # account the same bytes the object path would.
        delta = np.add.reduceat(
            np.concatenate([lay.placed_vecs for lay in layouts])[order],
            run_starts, axis=0)
        counts = run_ends[1:] - run_starts
        alloc_tg = np.concatenate(
            [lay.alloc_tg for lay in layouts])[order].tolist()

        id_rows = uuid_rows(total)
        alloc_ids = uuid_strings(id_rows[order])
        names = [tup.Name for q in queued for tup in q.place]
        alloc_names = np.asarray(names, dtype=object)[order].tolist()
        placed_ids = id_arr[rows]
        score_keys = (placed_ids + ".binpack").tolist()
        scores = np.concatenate(
            [q.cr.scores[:n] for q, n in zip(queued, ns)]).tolist()

        for k, (q, layout) in enumerate(zip(queued, layouts)):
            a, b = offs[k], offs[k + 1]
            lo, hi = run_of[k], run_of[k + 1]
            metrics_ = q.stack.ctx.metrics
            metrics_.Scores.update(zip(score_keys[a:b], scores[a:b]))
            q.stack._fill_metrics(q.prep, layout.last_ti, q.cr.nf_last)
            # Scoring is final now: one immutable metric snapshot shared
            # by every placed alloc (reference: alloc.Metrics). Templates
            # are per eval (EvalID and metrics are); their task-resource
            # dict and vector come from the shared prep memo.
            shared_metric = metrics_.copy()
            templates = []
            for tg_name, tr, vec in layout.groups:
                template = Allocation(
                    EvalID=q.eval_id,
                    JobID=q.job.ID,
                    TaskGroup=tg_name,
                    TaskResources=tr,
                    Metrics=shared_metric,
                    DesiredStatus=AllocDesiredStatusRun,
                    ClientStatus=AllocClientStatusPending,
                )
                template._resvec_cache = vec
                templates.append(template)
            plan = q.plan
            as_columns = not plan.NodeAllocation
            if not as_columns:
                tpl_dicts = [t.__dict__ for t in templates]
                for tg, alloc_id, name, node_id in zip(
                        layout.alloc_tg.tolist(),
                        uuid_strings(id_rows[a:b]), names[a:b],
                        placed_ids[a:b].tolist()):
                    plan.append_alloc(stamp_alloc(
                        tpl_dicts[tg], alloc_id, name, node_id))
            # Same layout the system sweep emits: unique placed rows with
            # summed demand, and the per-alloc columns in row order so
            # chunk slices stay contiguous.
            plan._sweep = SweepBatch(
                rows=urows[lo:hi], node_ids=node_ids[lo:hi],
                delta=delta[lo:hi], epoch=epoch, n_rows=n_rows,
                counts=counts[lo:hi], starts=run_ends[lo:hi + 1] - a,
                alloc_ids=alloc_ids[a:b], alloc_names=alloc_names[a:b],
                alloc_tg=alloc_tg[a:b], templates=templates,
                kind="service")
            if as_columns:
                plan.NodeAllocation = ColumnarPlacements.over(plan._sweep)
        return [True] * n_evals


# Row-steps (node rows x padded placements) under which an eval places via
# the numpy mirror (kernels.place_batch_host) instead of a device dispatch:
# a shallow window then needs no dispatch, readback or cold compile. The
# host kernel's incremental same-demand caching does one full table pass
# per unique (tg, demand) + O(1) patches per placement. Deep storm windows
# on big tables stay on the device chain. The value predates the directly
# attached chip; PERF.md has the host-sync round trip chip_smoke.py
# measured there, which is what a re-tuning would start from.
HOST_ROW_STEP_BUDGET = 1 << 23

# Candidate-table budget for the keyed kernel (keys x candidates x devices).
# Within it, every device dispatch uses kernels.place_batch_keyed; beyond it
# (degenerate many-key mega-windows) the monolithic scan kernels take over.
KEYED_CAND_BUDGET = 1 << 17


@dataclass
class SelectedOption:
    """A chosen placement (the reference's RankedNode, rank.go:12-45)."""

    node: Node
    score: float
    task_resources: Dict[str, Resources] = field(default_factory=dict)


@dataclass
class PreparedBatch:
    """Host-assembled device inputs for one evaluation's placements.

    Split out of select_batch so the pipelined worker can dispatch many
    evals' kernels chained on device usage before any readback."""

    tgs: List[TaskGroup]
    tg_index: Dict[str, int]      # tg name -> row in tg_masks/tg_demands
    tg_masks: np.ndarray          # [U, N] bool eligibility per unique TG
    tg_demands: np.ndarray        # [U, R]
    demands: np.ndarray           # [P_pad, R]
    tg_ids: np.ndarray            # [P_pad] int32
    valid: np.ndarray             # [P_pad] bool
    p_pad: int
    evict_rows: np.ndarray        # in-plan eviction scatter
    evict_vecs: np.ndarray
    job_counts: np.ndarray        # [N] int32 anti-affinity base
    distinct: bool
    penalty: float
    noise_vec: np.ndarray         # [N] f32 tie-break jitter
    tg_mask_sums: np.ndarray      # [U] eligible-node count per unique TG
    cand_sum: int                 # candidate node count (metrics base)
    # Real (non-padding) placement count — REQUIRED: it bounds the keyed
    # kernel's candidate sets, and an understated value would silently
    # trim true winners out of the candidate table.
    n_valid: int
    # True when any task of any placed group asks for network resources:
    # those evals keep the exact per-placement build (ports are sequential
    # host state); everything else takes the vectorized window build.
    has_network_asks: bool = False
    # Memo of the resolved device-side inputs for the unmodified first
    # dispatch (no bans/placed overlays): a (kernel-kind, tuple) pair so a
    # window re-dispatching an identical prep skips the content-hash
    # lookups entirely.
    dev_inputs: Optional[tuple] = None
    # Lazily built per-unique-TG (task_resources, resource-vec) templates
    # for the vectorized build: every alloc of a TG carries value-identical
    # task resources, so the window shares ONE frozen dict + Resources set
    # per TG instead of copying per alloc (same value-frozen contract as
    # alloc._resvec_cache — anything that changes resources replaces the
    # objects).
    tr_templates: Optional[dict] = None
    # Lazily built template columns of the same build (_TemplateLayout).
    tpl_layout: Optional["_TemplateLayout"] = None


class _TemplateLayout(NamedTuple):
    """What WindowCollect reads of a PreparedBatch: its placements' task
    groups as template indexes, in order of first appearance."""

    alloc_tg: np.ndarray    # [n_valid] int64 template index per placement
    groups: List[tuple]     # per template (task group name, task resources,
    #                         resource vector)
    placed_vecs: np.ndarray  # [n_valid, RES_DIMS] f32 the vector per placement
    last_ti: int            # unique-TG index of the last placement


def _pad_pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def eval_pad(n_evals: int) -> int:
    """Evals a window's run of n_evals same-shaped evals is launched as: a
    run of one as it is (dispatch), two or more (dispatch_multi) padded to
    a power of two, at least 4, so that jit compiles one program per
    bucket and not per window fill."""
    return _pad_pow2(n_evals, floor=4) if n_evals >= 2 else 1


def score_fit_rows(usage2: np.ndarray, score_cap: np.ndarray) -> np.ndarray:
    """BestFit-v3 host-side, in float64 like the Go reference
    (funcs.go:102-137): 20 - 10^freeCpuPct - 10^freeMemPct, clamped [0,18],
    NaN/Inf division edges sanitized. THE single host-side definition —
    select_on_node and the system batch path both call it so the formula
    cannot drift between them (the device twin is kernels._score).

    usage2 [K, 2]: proposed cpu/mem including reserved; score_cap [K, 2]."""
    with np.errstate(divide="ignore", invalid="ignore"):
        free_pct = 1.0 - (usage2.astype(np.float64)
                          / score_cap.astype(np.float64))
        total = (np.power(10.0, free_pct[:, 0])
                 + np.power(10.0, free_pct[:, 1]))
    scores = np.clip(20.0 - total, 0.0, 18.0)
    return np.nan_to_num(scores, nan=0.0, posinf=18.0, neginf=0.0)


def fit_lacking(cap: np.ndarray, usage: np.ndarray,
                demand: np.ndarray) -> np.ndarray:
    """Per-dimension exhaustion mask in float64 (reference AllocsFit,
    funcs.go:44-100): True where free capacity can't cover the demand.
    Shared by the single-node and batched host fit checks."""
    return ((cap.astype(np.float64) - usage.astype(np.float64))
            < demand.astype(np.float64))


def _mesh_shardings(nt):
    """(node_sh, mask_sh, rep_sh) for the table's serving mesh, or Nones
    for single-device serving. Shared by every kernel launch path so the
    fused and per-eval launches can never diverge on sharding."""
    mesh = nt.mesh
    if mesh is None:
        return None, None, None
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = mesh.axis_names[0]
    return (NamedSharding(mesh, P(axis)),
            NamedSharding(mesh, P(None, axis)),
            NamedSharding(mesh, P()))


def _chain_to_device(usage, node_sh):
    """Rejoin the device chain after a host-placed window: one async
    host->device upload (nothing waits on it, unlike a readback)."""
    if not isinstance(usage, np.ndarray):
        return usage
    import jax
    import jax.numpy as jnp

    return jnp.asarray(usage) if node_sh is None else \
        jax.device_put(usage, node_sh)


def make_noise_vec(n_rows: int, rng: random.Random) -> np.ndarray:
    """Per-node tie-break jitter (the load-spreading analogue of the
    reference's node shuffle, stack.go:120-133)."""
    return np.asarray(
        np.random.default_rng(rng.randrange(2**31)).random(n_rows),
        dtype=np.float32) * _NOISE_SCALE


class GenericStack:
    """Stack for service/batch jobs (reference: stack.go:35-173)."""

    def __init__(self, ctx: EvalContext, tindex: TensorIndex, batch: bool,
                 rng: Optional[random.Random] = None):
        self.ctx = ctx
        self.tindex = tindex
        self.batch = batch
        self.rng = rng or random.Random()
        self.job: Optional[Job] = None
        self.elig: Optional[ClassEligibility] = None
        self._cand_mask: Optional[np.ndarray] = None
        self._nodes_by_id: Dict[str, Node] = {}
        self._netidx_cache: Dict[str, NetworkIndex] = {}
        # What the network asks of this stack's eval cost (touched only
        # where a group asks for a network): placements given an offer,
        # NetworkIndexes built (the cache's misses), assignments refused.
        self.net_offers = 0
        self.netidx_builds = 0
        self.net_refused = 0

    # ------------------------------------------------------------- wiring
    def set_job(self, job: Job) -> None:
        self.job = job
        self.elig = ClassEligibility(self.tindex.nt,
                                     list(self._nodes_by_id.values()) or [])

    def set_nodes(self, nodes: Sequence[Node]) -> None:
        nt = self.tindex.nt
        self._nodes_by_id = {n.ID: n for n in nodes}
        mask = np.zeros(nt.n_rows, dtype=bool)
        for n in nodes:
            row = nt.row_of.get(n.ID)
            if row is not None:
                mask[row] = True
        self._cand_mask = mask
        # Rebuild the eligibility cache against the new node set.
        if self.job is not None:
            self.elig = ClassEligibility(nt, nodes)

    def adopt_nodes(self, nodes_by_id: Dict[str, Node], cand_mask: np.ndarray,
                    elig: ClassEligibility) -> None:
        """Share a candidate set + eligibility cache built once for a whole
        scheduling window (pipelined worker): evals against the same snapshot
        need not re-scan the node list per eval."""
        self._nodes_by_id = nodes_by_id
        self._cand_mask = cand_mask
        self.elig = elig

    def tg_eligibility(self, tgs: Sequence[TaskGroup]) -> tuple:
        """The job's and its unique task groups' eligibility, read through
        (and so filling) this job's per-job views of the eligibility cache:
        (unique TGs, TG name -> index, job mask, per unique TG its merged
        constraints and mask). prepare_batch assembles its masks from it;
        a job that adopts another's PreparedBatch (value-identical by
        _prep_sig) calls it alone, so that a blocked eval still reports ITS
        class eligibility and escaped flag, which are read by job id."""
        job = self.job
        unique_tgs: List[TaskGroup] = []
        tg_index: Dict[str, int] = {}
        for tg in tgs:
            if tg.Name not in tg_index:
                tg_index[tg.Name] = len(unique_tgs)
                unique_tgs.append(tg)
        job_mask, _, _ = self.elig.job_mask(job.ID, job.Constraints)
        per_tg = []
        for tg in unique_tgs:
            cons = task_group_constraints(tg)
            m, _, _ = self.elig.tg_mask(job.ID, tg.Name, cons.constraints,
                                        cons.drivers)
            per_tg.append((cons, m))
        return unique_tgs, tg_index, job_mask, per_tg

    def adopt_shared(self, job: Job, elig: ClassEligibility) -> None:
        """Wire the stack for a tensor-sweep evaluation: the job plus the
        table-wide shared eligibility (TensorIndex.shared_elig), WITHOUT
        set_nodes/set_job's O(cluster) node walk. The candidate set is the
        sweep's own ready/DC row mask, so _nodes_by_id/_cand_mask stay
        empty — only the mask-based paths (sweep feasibility,
        select_on_node for in-place updates) are valid on a stack wired
        this way."""
        self.job = job
        self.elig = elig

    # ---------------------------------------------------------- selection
    def select(self, tg: TaskGroup) -> Tuple[Optional[SelectedOption], Resources]:
        opts = self.select_batch([tg])
        size = task_group_constraints(tg).size
        return opts[0], size

    def select_batch(self, tgs: Sequence[TaskGroup]
                     ) -> List[Optional[SelectedOption]]:
        """Place a sequence of task-group instances in order, each seeing the
        previous placements' usage (reference sequencing: context.go:109-140),
        as one lax.scan on device."""
        assert self.job is not None and self.elig is not None
        if self._cand_mask is None or not self._nodes_by_id:
            self.ctx.metrics.NodesEvaluated = 0
            return [None] * len(tgs)

        t0 = time.monotonic()
        nt = self.tindex.nt
        prep = self.prepare_batch(tgs)

        banned_extra = np.zeros(nt.n_rows, dtype=bool)
        results: List[Optional[SelectedOption]] = [None] * len(tgs)
        remaining = list(range(len(tgs)))
        # Effects of winners from earlier attempts of THIS call: their usage,
        # anti-affinity counts, and distinct-hosts occupancy must be visible
        # to re-run placements (they aren't in ctx.plan yet).
        placed_usage = np.zeros((nt.n_rows, RES_DIMS), dtype=np.float32)
        placed_counts = np.zeros(nt.n_rows, dtype=np.int32)
        placed_hosts = np.zeros(nt.n_rows, dtype=bool)

        # The port-collision retry loop runs at most a handful of times: a
        # winner failing host-side network assignment is masked and the
        # remaining placements re-run.
        # Small evals place host-side (no dispatch, readback or cold
        # compile for a modest rows x placements product). Storms and
        # huge evals keep the device path (the budget keeps host work
        # bounded). allow_host_select mirrors ServerConfig.host_placement
        # so that host_placement=False forces the device kernel on the
        # slow path too (the mesh serving tests rely on it).
        use_host = (self.tindex.allow_host_select
                    and nt.n_rows * prep.p_pad <= HOST_ROW_STEP_BUDGET)
        for _attempt in range(8):
            if not remaining:
                break
            if use_host:
                res = self.dispatch_host(prep, banned=banned_extra,
                                         placed_usage=placed_usage,
                                         placed_counts=placed_counts,
                                         placed_hosts=placed_hosts,
                                         keep=remaining)
            else:
                res = self.dispatch(prep, banned=banned_extra,
                                    placed_usage=placed_usage,
                                    placed_counts=placed_counts,
                                    placed_hosts=placed_hosts,
                                    keep=remaining)
            # ONE device->host transfer: results come back packed (free
            # for the host path — already numpy).
            packed = np.asarray(res.packed)
            failed_rows, remaining = self.collect(
                prep, packed, results, remaining,
                placed_usage, placed_counts, placed_hosts)
            if not failed_rows:
                break
            for row in failed_rows:
                banned_extra[row] = True

        self.ctx.metrics.AllocationTime = int((time.monotonic() - t0) * 1e9)
        return results

    def prepare_batch(self, tgs: Sequence[TaskGroup],
                      noise_vec: Optional[np.ndarray] = None) -> PreparedBatch:
        """Assemble the host-side device inputs for one eval's placements.

        noise_vec lets a windowed caller share one tie-break jitter vector
        across many evals so its upload is paid once per window, not per
        eval (the reference's analogue is one node shuffle per scheduling
        pass, stack.go:120-133 — per-eval freshness is not load-bearing)."""
        assert self.job is not None and self.elig is not None
        nt = self.tindex.nt
        job = self.job

        # Per-unique-TG eligibility masks and demand vectors.
        unique_tgs, tg_index, job_mask, per_tg = self.tg_eligibility(tgs)
        tg_masks = np.zeros((len(unique_tgs), nt.n_rows), dtype=bool)
        tg_demands = np.zeros((len(unique_tgs), RES_DIMS), dtype=np.float32)
        for i, (cons, m) in enumerate(per_tg):
            tg_masks[i] = self._cand_mask & job_mask & m
            tg_demands[i] = resources_vec(cons.size)

        # Plan deltas: usage scatter for in-plan evictions; anti-affinity and
        # distinct-hosts state from proposed allocs of this job.
        evict_rows, evict_vecs = self._eviction_deltas()
        job_counts = self._job_alloc_counts()
        distinct = any(c.Operand == ConstraintDistinctHosts
                       for c in job.Constraints)
        penalty = (BATCH_JOB_ANTI_AFFINITY_PENALTY if self.batch
                   else SERVICE_JOB_ANTI_AFFINITY_PENALTY)

        p_pad = _pad_pow2(len(tgs))
        demands = np.zeros((p_pad, RES_DIMS), dtype=np.float32)
        tg_ids = np.zeros(p_pad, dtype=np.int32)
        valid = np.zeros(p_pad, dtype=bool)
        for p, tg in enumerate(tgs):
            ti = tg_index[tg.Name]
            demands[p] = tg_demands[ti]
            tg_ids[p] = ti
            valid[p] = True

        if noise_vec is None:
            noise_vec = make_noise_vec(nt.n_rows, self.rng)

        return PreparedBatch(
            tgs=list(tgs), tg_index=tg_index, tg_masks=tg_masks,
            tg_demands=tg_demands, demands=demands, tg_ids=tg_ids,
            valid=valid, p_pad=p_pad, evict_rows=evict_rows,
            evict_vecs=evict_vecs, job_counts=job_counts, distinct=distinct,
            penalty=penalty, noise_vec=noise_vec,
            tg_mask_sums=tg_masks.sum(axis=1),
            cand_sum=int(self._cand_mask.sum()), n_valid=len(tgs),
            has_network_asks=any(
                t.Resources is not None and t.Resources.Networks
                for tg in unique_tgs for t in tg.Tasks))

    def _device_kind(self, prep: PreparedBatch, n_valid: int) -> str:
        """Pick the device kernel: the keyed-candidate kernel whenever its
        candidate table stays within budget (always, in practice — the
        bound only trips on degenerate many-key mega-windows), else the
        monolithic scan. Keyed is bit-identical and does one score pass
        per unique task group instead of one per placement; on a sharded
        mesh it runs the shard-local pipeline — ZERO collectives per
        window, only winner-candidate rows cross devices (kernels.py:
        'shard-local mesh pipeline') — vs the scan's 2 per placement."""
        nt = self.tindex.nt
        n_dev = nt.mesh.devices.size if nt.mesh is not None else 1
        n_keys = prep.tg_masks.shape[0]
        if n_keys * kernels.keyed_cand_count(n_valid) * n_dev \
                <= KEYED_CAND_BUDGET:
            return "keyed"
        return "scan"

    def replay_resident(self, prep: PreparedBatch, n_valid: int) -> bool:
        """Whether a device launch of n_valid real placements of this
        prepared batch replays as the loop resident on the chip: the keyed
        program on one device, at a shape its builder does not decline
        (kernels.keyed_replay_resident, the builder's own rule). The mesh
        pipeline and the monolithic scans keep their lax.scan."""
        nt = self.tindex.nt
        return (self._device_kind(prep, n_valid) == "keyed"
                and (nt.mesh is None or nt.mesh.devices.size == 1)
                and kernels.keyed_replay_resident(
                    nt.n_rows, RES_DIMS, prep.tg_masks.shape[0],
                    kernels.keyed_cand_count(n_valid)))

    def _launch_device(self, d, usage, kind: str, dev: tuple, n_valid: int):
        nt = self.tindex.nt
        if kind == "keyed":
            mesh = nt.mesh
            if mesh is not None and mesh.devices.size == 1:
                mesh = None  # plain jit; no shard_map needed
            return kernels.place_batch_keyed(
                mesh, d["capacity"], d["score_cap"], usage, *dev,
                n_valid=n_valid)
        if isinstance(usage, kernels.MeshChain):
            # Degenerate mega-window routed to the monolithic scan: fold
            # the chain's pending ring into the sharded usage first.
            usage = usage.materialize()
        return kernels.place_batch(d["capacity"], d["score_cap"], usage,
                                   *dev)

    def _assemble_dev(self, kind: str, prep: PreparedBatch,
                      masks: np.ndarray, counts: np.ndarray,
                      tg_ids: np.ndarray, valid: np.ndarray,
                      hosts: np.ndarray, reset: Optional[np.ndarray],
                      demands: Optional[np.ndarray] = None) -> tuple:
        """THE one assembly of the positional device-input tuple shared by
        dispatch and dispatch_multi: keyed kernels take tg_demands plus a
        reset vector; scan kernels take per-placement demands (reset only
        for the multi-eval scan). Every host array goes through the
        content-addressed transfer cache, so a storm's byte-identical
        masks/demands/zero arrays pay ZERO host->device puts per eval."""
        node_sh, mask_sh, rep_sh = _mesh_shardings(self.tindex.nt)
        mid = prep.tg_demands if kind == "keyed" else demands
        dev = (_dev_cache.get(masks, mask_sh),
               _dev_cache.get(counts, node_sh),
               _dev_cache.get(mid, rep_sh),
               _dev_cache.get(tg_ids, rep_sh),
               _dev_cache.get(valid, rep_sh),
               _dev_cache.get(prep.noise_vec, node_sh),
               _dev_cache.get(np.float32(prep.penalty), rep_sh),
               _dev_cache.get(np.asarray(prep.distinct), rep_sh),
               _dev_cache.get(hosts, node_sh))
        if reset is not None:
            dev = dev + (_dev_cache.get(reset, rep_sh),)
        return dev

    def dispatch(self, prep: PreparedBatch, usage_override=None,
                 banned: Optional[np.ndarray] = None,
                 placed_usage: Optional[np.ndarray] = None,
                 placed_counts: Optional[np.ndarray] = None,
                 placed_hosts: Optional[np.ndarray] = None,
                 keep: Optional[Sequence[int]] = None,
                 tables: Optional[dict] = None):
        """Launch the placement kernel; returns the device-side result without
        forcing a readback. usage_override lets a pipelined caller chain the
        previous eval's usage_after array device-side; tables lets a windowed
        caller fetch the node table's device arrays ONCE per window instead of
        paying the dirty-row refresh per eval."""
        nt = self.tindex.nt
        d = tables if tables is not None else nt.device_arrays()
        # Mesh serving: node-axis inputs shard over the mesh like the table
        # arrays; per-placement inputs replicate. The keyed kernel runs the
        # explicit shard_map program; the scan fallback relies on XLA's
        # SPMD partitioner.
        node_sh, _, _ = _mesh_shardings(nt)
        usage = usage_override if usage_override is not None else d["usage"]
        usage = _chain_to_device(usage, node_sh)
        if isinstance(usage, kernels.MeshChain) and (
                len(prep.evict_rows)
                or (placed_usage is not None and placed_usage.any())):
            # Eviction/overlay math needs a real array; fold the chain's
            # pending winner ring back into the sharded usage first (one
            # scatter dispatch, stays on the mesh).
            usage = usage.materialize()
        if len(prep.evict_rows):
            usage = usage.at[prep.evict_rows].add(-prep.evict_vecs)
        if placed_usage is not None and placed_usage.any():
            # Host accumulator stays numpy (uncommitted): the add places it
            # with `usage`, sharded or not.
            usage = usage + placed_usage

        pristine = (banned is None and placed_usage is None
                    and placed_counts is None and placed_hosts is None
                    and keep is None)
        if pristine and prep.dev_inputs is not None:
            kind, dev = prep.dev_inputs
            return self._launch_device(d, usage, kind, dev, prep.n_valid)

        masks = prep.tg_masks
        if banned is not None and banned.any():
            masks = masks & ~banned[None, :]
        sel_valid = prep.valid
        if keep is not None:
            k = np.zeros(prep.p_pad, dtype=bool)
            k[list(keep)] = True
            sel_valid = sel_valid & k
        counts_now = prep.job_counts
        if placed_counts is not None:
            counts_now = counts_now + placed_counts
        if prep.distinct:
            hosts = counts_now > 0
            if placed_hosts is not None:
                hosts = hosts | placed_hosts
        else:
            hosts = np.zeros(nt.n_rows, dtype=bool)

        n_valid = int(sel_valid.sum()) if keep is not None else prep.n_valid
        kind = self._device_kind(prep, n_valid)
        dev = self._assemble_dev(
            kind, prep, masks, counts_now, prep.tg_ids, sel_valid, hosts,
            reset=(np.zeros(prep.p_pad, dtype=bool) if kind == "keyed"
                   else None),
            demands=prep.demands)
        if pristine:
            prep.dev_inputs = (kind, dev)
        return self._launch_device(d, usage, kind, dev, n_valid)

    def dispatch_multi(self, prep: PreparedBatch, n_evals: int,
                       usage_override=None, tables: Optional[dict] = None):
        """Launch ONE kernel for n_evals same-shaped evaluations sharing
        this PreparedBatch (a storm window after prep dedup): placements
        are concatenated with per-eval resets of the job-local state, so
        the window costs one host->device dispatch and one readback
        instead of one per eval (see kernels.place_batch_multi). Only
        legal for the pristine shared-prep case: no prior allocs, no
        overlays (the fast path's _prep_sig guarantees this).

        Returns (result, e_pad): result.packed is [e_pad * p_pad, 3];
        caller slices per eval. The eval axis pads to a power of two so
        jit compiles one program per bucket, not per window fill."""
        nt = self.tindex.nt
        d = tables if tables is not None else nt.device_arrays()
        node_sh, _, _ = _mesh_shardings(nt)
        usage = usage_override if usage_override is not None else d["usage"]
        usage = _chain_to_device(usage, node_sh)

        e_pad = eval_pad(n_evals)
        p = prep.p_pad
        # Tiled per-placement inputs: byte-identical across a storm's
        # windows, so the content-addressed cache uploads them once.
        tg_ids = np.tile(prep.tg_ids, e_pad)
        valid = np.tile(prep.valid, e_pad)
        valid[n_evals * p:] = False  # padding evals place nothing
        reset = np.zeros(e_pad * p, dtype=bool)
        reset[::p] = True
        hosts = np.zeros(nt.n_rows, dtype=bool)

        n_valid = n_evals * prep.n_valid
        kind = self._device_kind(prep, n_valid)
        dev = self._assemble_dev(
            kind, prep, prep.tg_masks, prep.job_counts, tg_ids, valid,
            hosts, reset=reset,
            demands=(None if kind == "keyed"
                     else np.tile(prep.demands, (e_pad, 1))))
        if kind == "keyed":
            res = self._launch_device(d, usage, kind, dev, n_valid)
        else:
            if isinstance(usage, kernels.MeshChain):
                usage = usage.materialize()
            res = kernels.place_batch_multi(d["capacity"], d["score_cap"],
                                            usage, *dev)
        return res, e_pad

    def dispatch_host(self, prep: PreparedBatch, usage_override=None,
                      banned: Optional[np.ndarray] = None,
                      placed_usage: Optional[np.ndarray] = None,
                      placed_counts: Optional[np.ndarray] = None,
                      placed_hosts: Optional[np.ndarray] = None,
                      keep: Optional[Sequence[int]] = None):
        """Host-side mirror of dispatch() for shallow windows: a near-idle
        broker's evals place as numpy vector ops, with no device dispatch
        or readback (kernels.place_batch_host). The result's packed array
        is already host-side; the pipelined drain recognizes that and
        skips the device fetch entirely."""
        nt = self.tindex.nt
        if usage_override is not None:
            usage = np.asarray(usage_override, np.float32)
            with nt._lock:
                capacity = nt.capacity.copy()
                score_cap = nt.score_cap.copy()
        else:
            # Snapshot under the table lock: alloc commits mutate usage
            # rows in place, and a lock-free copy could capture a torn row
            # (cpu updated, mem not) — the same hazard snapshot_rows
            # documents. The device path gets this via device_arrays().
            with nt._lock:
                usage = nt.usage.astype(np.float32, copy=True)
                capacity = nt.capacity.copy()
                score_cap = nt.score_cap.copy()
        if len(prep.evict_rows):
            usage = usage.copy()
            np.add.at(usage, prep.evict_rows, -prep.evict_vecs)
        if placed_usage is not None and placed_usage.any():
            usage = usage + placed_usage

        masks = prep.tg_masks
        if banned is not None and banned.any():
            masks = masks & ~banned[None, :]
        sel_valid = prep.valid
        if keep is not None:
            k = np.zeros(prep.p_pad, dtype=bool)
            k[list(keep)] = True
            sel_valid = sel_valid & k
        counts_now = prep.job_counts
        if placed_counts is not None:
            counts_now = counts_now + placed_counts
        if prep.distinct:
            hosts = counts_now > 0
            if placed_hosts is not None:
                hosts = hosts | placed_hosts
        else:
            hosts = np.zeros(nt.n_rows, dtype=bool)

        return kernels.place_batch_host(
            capacity, score_cap, usage, masks, counts_now,
            prep.demands, prep.tg_ids, sel_valid, prep.noise_vec,
            prep.penalty, prep.distinct, hosts)

    def collect(self, prep: PreparedBatch, packed: np.ndarray,
                results: List[Optional[SelectedOption]],
                remaining: Sequence[int],
                placed_usage: np.ndarray, placed_counts: np.ndarray,
                placed_hosts: np.ndarray) -> Tuple[set, List[int]]:
        """Materialize winners host-side: node lookup, port assignment,
        metrics. Returns (rows that failed network assignment, placement
        indexes to re-run). Mutates results and the placed_* accumulators."""
        nt = self.tindex.nt
        chosen = packed[:, 0].astype(np.int32)
        scores = packed[:, 1]
        n_feasible = packed[:, 2].astype(np.int32)

        # Hot loop: a storm window runs this for thousands of placements, so
        # locals are hoisted and the accumulator writes are batched into one
        # np.add.at per array after the loop.
        node_of = nt.node_of
        nodes_by_id = self._nodes_by_id
        tg_index = prep.tg_index
        tgs = prep.tgs
        metrics_ = self.ctx.metrics
        score_node = metrics_.score_node
        chosen_list = chosen.tolist()
        scores_list = scores.tolist()

        failed_rows: set = set()
        next_remaining: List[int] = []
        placed_ps: List[int] = []
        placed_rows: List[int] = []
        last_fill = None

        def flush_placed():
            # Exhaustion diagnostics read placed_usage, so the batched
            # accumulator writes must land before any _note_exhaustion.
            if placed_rows:
                rows_arr = np.asarray(placed_rows, dtype=np.int64)
                np.add.at(placed_usage, rows_arr, prep.demands[placed_ps])
                np.add.at(placed_counts, rows_arr, 1)
                placed_hosts[rows_arr] = True
                placed_ps.clear()
                placed_rows.clear()

        for p in remaining:
            row = chosen_list[p]
            ti = tg_index[tgs[p].Name]
            last_fill = (ti, int(n_feasible[p]))
            if row < 0:
                self._fill_metrics(prep, ti, int(n_feasible[p]))
                flush_placed()
                self._note_exhaustion(tgs[p], prep.tg_masks[ti],
                                      prep.tg_demands[ti], prep, placed_usage)
                continue  # infeasible: stays None
            node = nodes_by_id.get(node_of[row])
            if node is None:
                failed_rows.add(row)
                next_remaining.append(p)
                continue
            option = self._assign_networks(node, tgs[p], scores_list[p])
            if option is None:
                failed_rows.add(row)
                next_remaining.append(p)
                continue
            results[p] = option
            score_node(node, "binpack", scores_list[p])
            placed_ps.append(p)
            placed_rows.append(row)
        if last_fill is not None:
            # Metric fields are overwritten per placement, so only the last
            # one's values survive the reference loop — reproduce that state
            # with a single fill.
            self._fill_metrics(prep, *last_fill)
        flush_placed()
        return failed_rows, next_remaining

    def _tg_template(self, prep: PreparedBatch, ti: int) -> tuple:
        """(task_resources, resource-vec) for one unique TG, built once per
        PreparedBatch and shared by every alloc the window places for it.
        Only legal with no network asks anywhere in the group — ports are
        per-alloc offers. The shared dict/Resources are value-frozen by the
        same contract as alloc._resvec_cache (every consumer reads; a
        change replaces the objects)."""
        templates = prep.tr_templates
        if templates is None:
            templates = prep.tr_templates = {}
        ent = templates.get(ti)
        if ent is None:
            # tg_index maps name -> ti; the TG object is the first
            # placement of this ti (prep.tgs is in placement order).
            tg = next(t for t in prep.tgs if prep.tg_index[t.Name] == ti)
            tr = {}
            vec = np.zeros(RES_DIMS, dtype=np.float32)
            for task in tg.Tasks:
                r = (task.Resources.copy() if task.Resources is not None
                     else Resources())
                tr[task.Name] = r
                vec += resources_vec(r)
            ent = templates[ti] = (tr, vec)
        return ent

    def _template_layout(self, prep: PreparedBatch) -> "_TemplateLayout":
        """The template columns of the vectorised build, which depend on
        the PreparedBatch alone: built once, shared by every eval that
        adopts it."""
        layout = prep.tpl_layout
        if layout is None:
            tis: List[int] = []
            local: List[int] = []
            tpl_of: Dict[int, int] = {}
            for ti in prep.tg_ids[:prep.n_valid].tolist():
                k = tpl_of.get(ti)
                if k is None:
                    k = tpl_of[ti] = len(tis)
                    tis.append(ti)
                local.append(k)
            entries = [self._tg_template(prep, ti) for ti in tis]
            names = {ti: name for name, ti in prep.tg_index.items()}
            layout = prep.tpl_layout = _TemplateLayout(
                alloc_tg=np.asarray(local, dtype=np.int64),
                groups=[(names[ti], tr, vec)
                        for ti, (tr, vec) in zip(tis, entries)],
                placed_vecs=np.stack([vec for _, vec in entries])[local],
                last_ti=int(prep.tg_ids[prep.n_valid - 1]))
        return layout

    def collect_build(self, prep: PreparedBatch, cr,
                      eval_id: str, job: Job, place,
                      plan, failed_tg_allocs,
                      acc: "WindowAccumulator") -> bool:
        """Fused collect + build_placement_allocs for one eval of the
        pipelined fast path: a WindowCollect of one record (the window
        worker hands it the whole window at once). Returns False when a
        winner fails host-side network assignment or its node vanished:
        the caller falls back to the exact per-eval path, same as a
        non-empty failed_rows from collect()."""
        window = WindowCollect(self.tindex.nt, acc)
        ok = window.add(self, prep, cr, eval_id, job, place, plan,
                        failed_tg_allocs)
        if ok is None:
            [ok] = window.build()
        return ok

    def _collect_build_exact(self, prep: PreparedBatch, cr,
                             eval_id: str, job: Job, place,
                             plan, failed_tg_allocs,
                             acc: "WindowAccumulator",
                             net_span=nullcontext) -> bool:
        """The exact per-placement build: ONE pass from the compacted
        kernel output (CompactResult: chosen rows, scores, per-eval
        success) to plan allocations, skipping the SelectedOption list and
        the placed_counts/hosts accumulators the windowed caller never
        reads (they exist for the sync path's banned-row retry loop).
        Serves what WindowCollect does not build as columns (failed
        placements, network asks) and is the oracle of what it does.
        Returns False when a winner fails host-side network assignment or
        its node vanished. Where a group asks for a network, the winners'
        ports and bandwidth are assigned first, in placement order, inside
        `net_span` (the window worker's `netassign` stage: one span an
        eval, not one a placement)."""
        nt = self.tindex.nt
        chosen_list = cr.chosen.tolist()
        scores_list = cr.scores.tolist()
        options = None
        if prep.has_network_asks:
            with net_span():
                options = self._assign_placed_networks(
                    prep, chosen_list, scores_list, len(place))
            if options is None:
                return False

        node_of = nt.node_of
        nodes_by_id = self._nodes_by_id
        tg_index = prep.tg_index
        tgs = prep.tgs
        metrics_ = self.ctx.metrics
        score_node = metrics_.score_node

        allocs: List[Allocation] = []
        placed_rows: List[int] = []
        placed_ps: List[int] = []
        failed_counts: Dict[str, int] = {}
        # Every alloc of a task group carries the same resource vector;
        # pre-seeding the per-instance memo (immutable by contract) saves
        # a resources_vec walk per alloc downstream (plan verify, usage
        # listener, optimistic overlay).
        shared_vecs: Dict[int, np.ndarray] = {}
        last_ti = None

        def flush_placed():
            # Exhaustion diagnostics read the window accumulator, so the
            # batched accumulation must land before any _note_exhaustion.
            if placed_rows:
                acc.add(np.asarray(placed_rows, dtype=np.int64),
                        prep.demands[placed_ps])
                placed_rows.clear()
                placed_ps.clear()

        for p, tup in enumerate(place):
            row = chosen_list[p]
            tg = tgs[p]
            ti = tg_index[tg.Name]
            last_ti = ti
            if row < 0:
                # No per-placement _fill_metrics here: intermediate fills
                # are dead stores — nothing snapshots the metrics until
                # after the final fill below, which uses the compacted
                # nf_last (the LAST placement's n_feasible, the only one
                # the reference loop's end state keeps).
                flush_placed()
                self._note_exhaustion(tg, prep.tg_masks[ti],
                                      prep.tg_demands[ti], prep,
                                      acc.usage())
                # Snapshots are deferred to after the final _fill_metrics
                # so FailedTGAllocs carries the same end-state metrics the
                # sync path's build_placement_allocs records.
                failed_counts[tg.Name] = failed_counts.get(tg.Name, 0) + 1
                continue
            if options is not None:
                option = options[p]
                node = option.node
            else:
                node = nodes_by_id.get(node_of[row])
                if node is None:
                    return False
                option = self._assign_networks(node, tg, scores_list[p])
                if option is None:
                    return False
            score_node(node, "binpack", scores_list[p])
            placed_rows.append(row)
            placed_ps.append(p)
            alloc = Allocation(
                ID=generate_uuid(),
                EvalID=eval_id,
                Name=tup.Name,
                JobID=job.ID,
                TaskGroup=tg.Name,
                NodeID=node.ID,
                TaskResources=option.task_resources,
                DesiredStatus=AllocDesiredStatusRun,
                ClientStatus=AllocClientStatusPending,
            )
            vec = shared_vecs.get(ti)
            if vec is None:
                shared_vecs[ti] = alloc_vec(alloc)
            else:
                alloc._resvec_cache = vec
            allocs.append(alloc)
        if last_ti is not None:
            self._fill_metrics(prep, last_ti, cr.nf_last)
        flush_placed()
        for name, count in failed_counts.items():
            metric = failed_tg_allocs.get(name)
            if metric is None:
                metric = failed_tg_allocs[name] = metrics_.copy()
                count -= 1
            metric.CoalescedFailures += count
        if allocs:
            # Scoring is final now: one immutable metric snapshot shared
            # by every placed alloc (reference: alloc.Metrics).
            shared_metric = metrics_.copy()
            append_alloc = plan.append_alloc
            for alloc in allocs:
                alloc.Metrics = shared_metric
                append_alloc(alloc)
        return True

    # ------------------------------------------------------------- helpers
    def _eviction_deltas(self) -> Tuple[np.ndarray, np.ndarray]:
        nt = self.tindex.nt
        rows, vecs = [], []
        for node_id, updates in self.ctx.plan.NodeUpdate.items():
            row = nt.row_of.get(node_id)
            if row is None:
                continue
            for alloc in updates:
                # Look up the full alloc for resource accounting.
                full = self.ctx.state.alloc_by_id(alloc.ID) or alloc
                rows.append(row)
                vecs.append(alloc_vec(full))
        if not rows:
            return np.zeros(0, dtype=np.int32), np.zeros((0, RES_DIMS),
                                                         dtype=np.float32)
        return (np.asarray(rows, dtype=np.int32),
                np.asarray(vecs, dtype=np.float32))

    def _job_alloc_counts(self) -> np.ndarray:
        """Proposed allocs of this job per node row (anti-affinity base)."""
        nt = self.tindex.nt
        counts = np.zeros(nt.n_rows, dtype=np.int32)
        assert self.job is not None
        evicted = {a.ID
                   for updates in self.ctx.plan.NodeUpdate.values()
                   for a in updates}
        for alloc in self.ctx.state.allocs_by_job(self.job.ID):
            if alloc.terminal_status() or alloc.ID in evicted:
                continue
            row = nt.row_of.get(alloc.NodeID)
            if row is not None:
                counts[row] += 1
        for node_id, placed in self.ctx.plan.NodeAllocation.items():
            row = nt.row_of.get(node_id)
            if row is not None:
                counts[row] += sum(1 for a in placed if a.JobID == self.job.ID)
        return counts

    def _assign_placed_networks(self, prep: PreparedBatch, chosen_list,
                                scores_list, n: int
                                ) -> Optional[List[Optional[SelectedOption]]]:
        """_assign_networks for every placement that found a row, in
        placement order (None at a failed placement). None when a node
        vanished or an assignment was refused: the eval falls back."""
        node_of = self.tindex.nt.node_of
        options: List[Optional[SelectedOption]] = [None] * n
        for p in range(n):
            row = chosen_list[p]
            if row < 0:
                continue
            node = self._nodes_by_id.get(node_of[row])
            if node is None:
                return None
            option = options[p] = self._assign_networks(
                node, prep.tgs[p], scores_list[p])
            if option is None:
                return None
        return options

    def _assign_networks(self, node: Node, tg: TaskGroup,
                         score: float) -> Optional[SelectedOption]:
        """Host-side port/bandwidth assignment for a chosen node."""
        if not any(t.Resources is not None and t.Resources.Networks
                   for t in tg.Tasks):
            # No network asks anywhere in the group: nothing to reserve, so
            # skip building the node's port/bandwidth index entirely (the
            # common case in large placement storms).
            option = SelectedOption(node=node, score=score)
            for task in tg.Tasks:
                option.task_resources[task.Name] = (
                    task.Resources.copy() if task.Resources is not None
                    else Resources())
            return option
        netidx = self._netidx_cache.get(node.ID)
        if netidx is None:
            netidx = NetworkIndex()
            netidx.set_node(node)
            netidx.add_allocs(self.ctx.proposed_allocs(node.ID))
            self._netidx_cache[node.ID] = netidx
            self.netidx_builds += 1
        option = SelectedOption(node=node, score=score)
        staged = []
        for task in tg.Tasks:
            resources = (task.Resources.copy() if task.Resources is not None
                         else Resources())
            if task.Resources is not None and task.Resources.Networks:
                ask = task.Resources.Networks[0]
                try:
                    offer = netidx.assign_network(ask, self.rng)
                except ValueError:
                    # Staged reservations from this partial TG poison the
                    # cached index; drop it so the next user rebuilds clean.
                    self._netidx_cache.pop(node.ID, None)
                    self.net_refused += 1
                    return None
                netidx.add_reserved(offer)
                staged.append(offer)
                resources.Networks = [offer]
            option.task_resources[task.Name] = resources
        self.net_offers += 1
        return option

    def _fill_metrics(self, prep: PreparedBatch, ti: int,
                      n_feasible: int) -> None:
        """Metrics from the per-unique-TG sums precomputed in prepare_batch
        (summing the node axis per placement would be O(P*N) per eval)."""
        m = self.ctx.metrics
        n_eligible = int(prep.tg_mask_sums[ti])
        m.NodesEvaluated = n_eligible
        m.NodesFiltered = prep.cand_sum - n_eligible
        m.NodesExhausted = max(0, n_eligible - n_feasible)

    def _note_exhaustion(self, tg: TaskGroup, mask: np.ndarray,
                         demand: np.ndarray,
                         prep: Optional[PreparedBatch] = None,
                         placed_usage: Optional[np.ndarray] = None) -> None:
        """Failed placement: record which dimensions were exhausted, against
        the EFFECTIVE usage the kernel saw (committed usage minus this plan's
        evictions plus this call's earlier placements) — diffing the stale
        host mirror can blame the wrong dimension."""
        nt = self.tindex.nt
        usage = nt.usage
        if (prep is not None and len(prep.evict_rows)) or (
                placed_usage is not None and placed_usage.any()):
            usage = usage.copy()
            if prep is not None and len(prep.evict_rows):
                np.subtract.at(usage, prep.evict_rows, prep.evict_vecs)
            if placed_usage is not None:
                usage += placed_usage
        free = nt.capacity - usage
        lacking = (free < demand[None, :]) & mask[:, None]
        per_dim = lacking.sum(axis=0)
        for d, count in enumerate(per_dim):
            if count > 0:
                name = DIM_NAMES[d]
                m = self.ctx.metrics
                m.DimensionExhausted[name] = (
                    m.DimensionExhausted.get(name, 0) + int(count))

    # -------------------------------------------- single-node host fast path
    def select_on_node(self, tg: TaskGroup, node: Node
                       ) -> Optional[SelectedOption]:
        """Feasibility + fit on one specific node, host-side (used by
        in-place updates, reference: util.go:393-426)."""
        from nomad_tpu.tensor.constraints import (
            node_has_drivers,
            node_meets_constraints,
        )

        assert self.job is not None
        nt = self.tindex.nt
        m = self.ctx.metrics
        row = nt.row_of.get(node.ID)
        if row is None:
            return None
        m.NodesEvaluated += 1
        cons = task_group_constraints(tg)
        if not nt.ready[row]:
            m.NodesFiltered += 1
            return None
        if not node_meets_constraints(node, self.job.Constraints):
            m.filter_node(node, "job constraints")  # increments NodesFiltered
            return None
        if not (node_meets_constraints(node, cons.constraints)
                and node_has_drivers(node, cons.drivers)):
            m.filter_node(node, "group constraints")
            return None
        # Usage: committed minus in-plan evictions on this node.
        usage = nt.usage[row].copy()
        for alloc in self.ctx.plan.NodeUpdate.get(node.ID, ()):
            full = self.ctx.state.alloc_by_id(alloc.ID) or alloc
            usage -= alloc_vec(full)
        for alloc in self.ctx.plan.NodeAllocation.get(node.ID, ()):
            usage += alloc_vec(alloc)
        demand = resources_vec(cons.size)
        lacking = fit_lacking(nt.capacity[row], usage, demand)
        if np.any(lacking):
            m.NodesExhausted += 1
            for d in np.flatnonzero(lacking):
                name = DIM_NAMES[int(d)]
                m.DimensionExhausted[name] = (
                    m.DimensionExhausted.get(name, 0) + 1)
            return None
        util2 = usage[:2] + demand[:2]
        score = float(score_fit_rows(util2[None, :],
                                     nt.score_cap[row][None, :])[0])
        option = SelectedOption(node=node, score=score)
        for task in tg.Tasks:
            option.task_resources[task.Name] = (
                task.Resources.copy() if task.Resources is not None
                else Resources())
        return option


class SystemStack:
    """Stack for the system scheduler: evaluates one specific node at a time
    (reference: stack.go:176-261)."""

    def __init__(self, ctx: EvalContext, tindex: TensorIndex):
        self.inner = GenericStack(ctx, tindex, batch=False)

    def set_nodes(self, nodes: Sequence[Node]) -> None:
        self.inner.set_nodes(nodes)

    def set_job(self, job: Job) -> None:
        self.inner.set_job(job)

    def adopt_shared(self, job: Job, elig) -> None:
        self.inner.adopt_shared(job, elig)

    def select(self, tg: TaskGroup, node: Node) -> Optional[SelectedOption]:
        option = self.inner.select_on_node(tg, node)
        if option is None:
            return None
        return self.inner._assign_networks(node, tg, option.score) or None

    def select_batch_on_nodes(self, tg: TaskGroup, nodes: Sequence[Node]
                              ) -> Optional[List[Optional[SelectedOption]]]:
        """Vectorized per-pinned-node selection for ONE task group: the
        system scheduler's sweep is `for node in all_nodes: select(tg,
        node)`, which at 10k nodes is 10k Python constraint walks. All the
        per-node checks are row math on the node tensor, so they run as a
        handful of numpy ops over the whole batch instead (the TPU-framework
        shape of system_sched.go:219-281's loop; the reference's per-node
        semantics are preserved exactly).

        Returns None when the group asks for network resources — port
        bitmaps are per-node host state, the caller keeps the per-node path.
        """
        inner = self.inner
        assert inner.job is not None and inner.elig is not None
        if any(t.Resources is not None and t.Resources.Networks
               for t in tg.Tasks):
            return None
        nt = inner.tindex.nt
        ctx = inner.ctx
        m = ctx.metrics

        cons = task_group_constraints(tg)
        job_mask, _, _ = inner.elig.job_mask(inner.job.ID,
                                             inner.job.Constraints)
        tg_mask, _, _ = inner.elig.tg_mask(inner.job.ID, tg.Name,
                                           cons.constraints, cons.drivers)
        demand = resources_vec(cons.size).astype(np.float64)

        results: List[Optional[SelectedOption]] = [None] * len(nodes)
        rows = np.empty(len(nodes), dtype=np.int64)
        idxs: List[int] = []
        for i, node in enumerate(nodes):
            row = nt.row_of.get(node.ID)
            if row is not None:
                rows[len(idxs)] = row
                idxs.append(i)
        rows = rows[:len(idxs)]
        if not len(rows):
            return results

        usage_rows, cap_rows = nt.snapshot_rows(rows)
        usage_rows = usage_rows.astype(np.float64)
        # In-plan deltas on these nodes (stops subtract, placements add) —
        # mirrors select_on_node's per-node walk, batched by node id.
        plan = ctx.plan
        if plan.NodeUpdate or plan.NodeAllocation:
            for k, i in enumerate(idxs):
                nid = nodes[i].ID
                for alloc in plan.NodeUpdate.get(nid, ()):
                    full = ctx.state.alloc_by_id(alloc.ID) or alloc
                    usage_rows[k] -= alloc_vec(full)
                for alloc in plan.NodeAllocation.get(nid, ()):
                    usage_rows[k] += alloc_vec(alloc)

        ready = nt.ready[rows]
        job_ok = job_mask[rows]
        tg_ok = tg_mask[rows]
        eligible = ready & job_ok & tg_ok
        lacking = fit_lacking(cap_rows, usage_rows, demand[None, :])
        fits = ~lacking.any(axis=1)
        ok = eligible & fits

        # Metrics: the exact counters select_on_node's per-node walk
        # accumulates (not-ready counts filtered-only; constraint filters
        # also record class + constraint labels via filter_node).
        m.NodesEvaluated += len(rows)
        m.NodesFiltered += int((~ready).sum())
        job_filtered = ready & ~job_ok
        tg_filtered = ready & job_ok & ~tg_ok
        for sel, label in ((job_filtered, "job constraints"),
                           (tg_filtered, "group constraints")):
            for k in np.flatnonzero(sel):
                m.filter_node(nodes[idxs[int(k)]], label)
        exhausted = eligible & ~fits
        m.NodesExhausted += int(exhausted.sum())
        if exhausted.any():
            # Per lacking dimension of each exhausted node, exactly like
            # select_on_node's flatnonzero walk.
            per_dim = (lacking & exhausted[:, None]).sum(axis=0)
            for d, count in enumerate(per_dim.tolist()):
                if count:
                    name = DIM_NAMES[d]
                    m.DimensionExhausted[name] = (
                        m.DimensionExhausted.get(name, 0) + count)

        util2 = usage_rows[:, :2] + demand[None, :2]
        scores = score_fit_rows(util2, nt.score_cap[rows])

        ok_list = ok.tolist()
        score_list = scores.tolist()
        for k, i in enumerate(idxs):
            if not ok_list[k]:
                continue
            node = nodes[i]
            option = SelectedOption(node=node, score=score_list[k])
            for task in tg.Tasks:
                option.task_resources[task.Name] = (
                    task.Resources.copy() if task.Resources is not None
                    else Resources())
            results[i] = option
        return results
