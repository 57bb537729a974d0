"""Pipelined placement: device-resident usage chaining across evaluations.

The TPU-native throughput path. A synchronous per-eval loop waits on one
device->host readback per evaluation; instead the placer chains evaluations
ON DEVICE — eval i+1's usage input is eval i's usage_after array, never
copied back — dispatches asynchronously, and streams packed results home
with copy-ahead, so one host sync covers the whole in-flight window.

This is the tensor re-expression of the reference's optimistic concurrency:
N workers scheduling against snapshots with a serializing applier
(reference: nomad/worker.go:45-49, plan_apply.go:24-33) becomes a device-side
dependency chain with deferred host materialization; the plan applier still
re-verifies every placement before commit.
"""

from __future__ import annotations

import random
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from nomad_tpu.structs import Job, TaskGroup
from nomad_tpu.tensor import TensorIndex
from nomad_tpu.tensor.node_table import RES_DIMS, resources_vec

from . import kernels
from .util import task_group_constraints


@dataclass
class EvalRequest:
    job: Job
    tgs: List[TaskGroup]


@dataclass
class EvalPlacements:
    job: Job
    tgs: List[TaskGroup]
    chosen_rows: np.ndarray   # [P] int32, -1 = infeasible
    scores: np.ndarray        # [P] f32
    n_feasible: np.ndarray    # [P] int32


class PipelinedPlacer:
    """Streams evaluations through the placement kernel with device-resident
    usage state."""

    def __init__(self, tindex: TensorIndex, nodes, batch: bool = False,
                 rng: Optional[random.Random] = None, window: int = 16):
        import jax
        import jax.numpy as jnp

        self.tindex = tindex
        self.nodes = list(nodes)
        self.batch = batch
        self.rng = rng or random.Random()
        self.window = window
        self._jnp = jnp
        self._jax = jax

        nt = tindex.nt
        d = nt.device_arrays()
        self._capacity = d["capacity"]
        self._score_cap = d["score_cap"]
        self._usage = d["usage"]  # device-resident, chained across evals
        self._cand_mask = np.zeros(nt.n_rows, dtype=bool)
        for n in self.nodes:
            row = nt.row_of.get(n.ID)
            if row is not None:
                self._cand_mask[row] = True
        noise = np.asarray(
            np.random.default_rng(self.rng.randrange(2**31)).random(nt.n_rows),
            dtype=np.float32) * 1e-3
        self._noise = jnp.asarray(noise)
        self._zero_counts = jnp.zeros(nt.n_rows, dtype=jnp.int32)
        self._no_banned = jnp.zeros(nt.n_rows, dtype=bool)
        self._mask_cache: Dict[tuple, np.ndarray] = {}
        self._input_cache: Dict[tuple, tuple] = {}
        self._inflight: List[Tuple[EvalRequest, object]] = []
        self.results: List[EvalPlacements] = []
        self._penalty = jnp.float32(5.0 if batch else 10.0)
        self._false = jnp.asarray(False)
        # One representative node per computed class for host constraint
        # evaluation (classes << nodes).
        self._reps: Dict[int, Job] = {}
        for n in self.nodes:
            cid = nt.class_vocab.get(n.ComputedClass)
            if cid is not None and cid not in self._reps:
                self._reps[cid] = n

    # ------------------------------------------------------------- internals
    def _tg_mask(self, job: Job, tg: TaskGroup) -> np.ndarray:
        """Eligibility mask keyed by the constraint SIGNATURE, so distinct
        jobs with identical constraints share one per-class evaluation; the
        node axis is a vectorized gather, never a Python loop."""
        from nomad_tpu.tensor.constraints import (
            node_has_drivers,
            node_meets_constraints,
        )

        nt = self.tindex.nt
        cons = task_group_constraints(tg)
        key = (
            tuple((c.LTarget, c.Operand, c.RTarget) for c in job.Constraints),
            tuple((c.LTarget, c.Operand, c.RTarget) for c in cons.constraints),
            tuple(cons.drivers),
        )
        cached = self._mask_cache.get(key)
        if cached is not None:
            return cached
        table = np.zeros(max(len(nt.class_names), 1), dtype=bool)
        for cid, rep in self._reps.items():
            table[cid] = (node_meets_constraints(rep, job.Constraints)
                          and node_meets_constraints(rep, cons.constraints)
                          and node_has_drivers(rep, cons.drivers))
        mask = table[nt.class_ids] & nt.ready & self._cand_mask
        self._mask_cache[key] = mask
        return mask

    def _device_inputs(self, req: EvalRequest):
        """Device-side (masks, demands, tg_ids, valid) cached by the eval's
        placement signature: repeated workloads pay zero host->device puts."""
        jnp = self._jnp
        tgs = req.tgs
        cons_sig = tuple(
            (tg.Name,
             tuple((c.LTarget, c.Operand, c.RTarget) for c in req.job.Constraints))
            for tg in tgs)
        cached = self._input_cache.get(cons_sig)
        if cached is not None:
            return cached
        p_pad = 8
        while p_pad < len(tgs):
            p_pad *= 2
        demands = np.zeros((p_pad, RES_DIMS), dtype=np.float32)
        valid = np.zeros(p_pad, dtype=bool)
        unique: Dict[str, int] = {}
        masks: List[np.ndarray] = []
        tg_ids = np.zeros(p_pad, dtype=np.int32)
        for p, tg in enumerate(tgs):
            ti = unique.get(tg.Name)
            if ti is None:
                ti = len(masks)
                unique[tg.Name] = ti
                masks.append(self._tg_mask(req.job, tg))
            demands[p] = resources_vec(task_group_constraints(tg).size)
            tg_ids[p] = ti
            valid[p] = True
        out = (jnp.asarray(np.stack(masks)), jnp.asarray(demands),
               jnp.asarray(tg_ids), jnp.asarray(valid))
        self._input_cache[cons_sig] = out
        return out

    def submit(self, req: EvalRequest) -> None:
        """Dispatch one eval's placement program; non-blocking."""
        jnp = self._jnp
        masks, demands, tg_ids, valid = self._device_inputs(req)
        res = kernels.place_batch(
            self._capacity, self._score_cap, self._usage,
            masks, self._zero_counts, demands, tg_ids, valid,
            self._noise, self._penalty, self._false, self._no_banned)
        # Chain: next eval sees this eval's proposed usage, device-side.
        self._usage = res.usage_after
        self._inflight.append((req, res.packed))
        if len(self._inflight) >= self.window:
            self._drain_window()

    def _drain_window(self) -> None:
        """ONE readback for the whole in-flight window: the host sync is
        shared by all of the window's evals."""
        jnp = self._jnp
        window = self._inflight
        self._inflight = []
        if not window:
            return
        by_shape: Dict[tuple, list] = {}
        for i, (req, packed) in enumerate(window):
            by_shape.setdefault(packed.shape, []).append((i, req, packed))
        out: List[Tuple[int, EvalPlacements]] = []
        for shape, group in by_shape.items():
            stacked = np.asarray(jnp.stack([p for _, _, p in group]))
            for (i, req, _), arr in zip(group, stacked):
                arr = arr[: len(req.tgs)]
                out.append((i, EvalPlacements(
                    job=req.job, tgs=req.tgs,
                    chosen_rows=arr[:, 0].astype(np.int32),
                    scores=arr[:, 1],
                    n_feasible=arr[:, 2].astype(np.int32))))
        out.sort(key=lambda t: t[0])
        self.results.extend(r for _, r in out)

    def flush(self) -> List[EvalPlacements]:
        self._drain_window()
        out = self.results
        self.results = []
        return out

    def sync_usage_to_host(self) -> None:
        """Materialize the chained device usage back into the host mirror."""
        nt = self.tindex.nt
        nt.usage[:] = np.asarray(self._usage)
        nt._dirty_rows.clear()
        nt._device["usage"] = self._usage
