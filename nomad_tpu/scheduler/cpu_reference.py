"""CPU reference placement path: the reference's iterator-chain algorithm.

A faithful host-side implementation of the reference Stack semantics
(reference: scheduler/stack.go, feasible.go, rank.go, select.go): Fisher-
Yates node shuffle, computed-class-memoized feasibility with escape hatch,
BinPack scoring over proposed usage, job anti-affinity, and the
max(2, ceil(log2 n)) LimitIterator with MaxScore selection.

Used as the golden model of the placement-quality parity tests
(tests/test_tensor_and_kernels.py, tests/test_mixed_window_equivalence.py).
"""

from __future__ import annotations

import math
import random
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from nomad_tpu.structs import Job, Node, TaskGroup
from nomad_tpu.tensor.constraints import (
    node_has_drivers,
    node_meets_constraints,
)
from nomad_tpu.tensor.node_table import RES_DIMS, resources_vec

from .util import task_group_constraints

SERVICE_PENALTY = 10.0
BATCH_PENALTY = 5.0


def binpack_score(usage: np.ndarray, demand: np.ndarray,
                  score_cap: np.ndarray) -> float:
    """BestFit-v3 over proposed (cpu, mem) utilization: 20 - 10^freeCpuPct -
    10^freeMemPct clamped to [0, 18], with the reference's IEEE Inf/NaN
    division edges (reference: scheduler/rank.go:131-240, funcs.go:102-137).
    score_cap is capacity minus reserved for (cpu, mem)."""
    util2 = usage[:2] + demand[:2]
    with np.errstate(divide="ignore", invalid="ignore"):
        free = 1.0 - util2 / score_cap
        total = 10.0 ** free[0] + 10.0 ** free[1]
    score = float(np.clip(20.0 - total, 0.0, 18.0))
    return 0.0 if np.isnan(score) else score


class CPUReferenceStack:
    """Per-placement iterator walk over node dicts + numpy usage vectors."""

    def __init__(self, nodes: Sequence[Node], batch: bool = False,
                 rng: Optional[random.Random] = None):
        self.nodes = list(nodes)
        self.batch = batch
        self.rng = rng or random.Random()
        # Resource vectors per node.
        self.capacity = {n.ID: resources_vec(n.Resources) for n in self.nodes}
        self.score_cap = {
            n.ID: (resources_vec(n.Resources)[:2]
                   - resources_vec(n.Reserved)[:2])
            for n in self.nodes}
        self.usage: Dict[str, np.ndarray] = {
            n.ID: resources_vec(n.Reserved) for n in self.nodes}
        self.job_allocs: Dict[str, int] = {}
        self.job: Optional[Job] = None
        # Class-level feasibility memo (reference: feasible.go:454-568).
        self._class_memo: Dict[Tuple[str, str], bool] = {}

    def set_job(self, job: Job) -> None:
        self.job = job
        self.job_allocs = {}

    def _feasible(self, node: Node, tg: TaskGroup, constraints, drivers) -> bool:
        key = (node.ComputedClass, tg.Name)
        memo = self._class_memo.get(key)
        if memo is not None:
            return memo
        ok = (node_meets_constraints(node, self.job.Constraints)
              and node_meets_constraints(node, constraints)
              and node_has_drivers(node, drivers))
        self._class_memo[key] = ok
        return ok

    def select(self, tg: TaskGroup) -> Optional[Tuple[str, float]]:
        """One placement: returns (node_id, score) or None."""
        assert self.job is not None
        cons = task_group_constraints(tg)
        demand = resources_vec(cons.size)

        # Random source (Fisher-Yates shuffle, reference: util.go:281-287).
        order = list(range(len(self.nodes)))
        self.rng.shuffle(order)

        # LimitIterator: max(2, ceil(log2 n)) feasible candidates
        # (reference: stack.go:120-133).
        limit = 2
        n = len(self.nodes)
        if not self.batch and n > 0:
            limit = max(2, int(math.ceil(math.log2(n))))

        penalty = BATCH_PENALTY if self.batch else SERVICE_PENALTY
        best: Optional[Tuple[str, float]] = None
        seen = 0
        for i in order:
            node = self.nodes[i]
            if node.Status != "ready" or node.Drain:
                continue
            if not self._feasible(node, tg, cons.constraints, cons.drivers):
                continue
            # BinPack fit + score (reference: rank.go:131-240).
            usage = self.usage[node.ID]
            if np.any(self.capacity[node.ID] - usage < demand):
                continue
            score = binpack_score(usage, demand, self.score_cap[node.ID])
            score -= self.job_allocs.get(node.ID, 0) * penalty
            if best is None or score > best[1]:
                best = (node.ID, score)
            seen += 1
            if seen >= limit:
                break
        if best is None:
            return None
        node_id, score = best
        self.usage[node_id] = self.usage[node_id] + demand
        self.job_allocs[node_id] = self.job_allocs.get(node_id, 0) + 1
        return best

    def select_batch(self, tgs: Sequence[TaskGroup]) -> List[Optional[Tuple[str, float]]]:
        return [self.select(tg) for tg in tgs]


class CPUReferenceServedStack:
    """GenericScheduler-compatible stack running the reference's host-side
    iterator chain against LIVE cluster state (scheduler_impl=
    "cpu-reference"): same broker, plan applier, raft, and status paths
    as the TPU stack, with only the placement engine swapped.

    Semantics mirror CPUReferenceStack (Fisher-Yates shuffle, class-memoized
    feasibility, BinPack scoring, max(2, ceil(log2 n)) candidate limit,
    reference: scheduler/stack.go:120-133, rank.go:131-240); usage derives
    lazily per candidate node from ctx.proposed_allocs, exactly the
    reference BinPackIterator's proposed-allocation walk."""

    elig = None  # no tensorized eligibility: escape/class reporting no-ops

    def __init__(self, ctx, batch: bool, rng: Optional[random.Random] = None):
        self.ctx = ctx
        self.batch = batch
        self.rng = rng or random.Random()
        self.job: Optional[Job] = None
        self.nodes: List[Node] = []
        self._class_memo: Dict[Tuple[str, str], bool] = {}

    def set_job(self, job: Job) -> None:
        self.job = job
        self._class_memo.clear()

    def set_nodes(self, nodes: Sequence[Node]) -> None:
        self.nodes = list(nodes)

    # ------------------------------------------------------------- internals
    def _feasible(self, node: Node, tg: TaskGroup, constraints, drivers) -> bool:
        key = (node.ComputedClass, tg.Name)
        memo = self._class_memo.get(key)
        if memo is not None:
            return memo
        ok = (node_meets_constraints(node, self.job.Constraints)
              and node_meets_constraints(node, constraints)
              and node_has_drivers(node, drivers))
        self._class_memo[key] = ok
        return ok

    def _usage(self, node: Node, cache: Dict[str, np.ndarray],
               counts: Dict[str, int]) -> np.ndarray:
        from nomad_tpu.tensor.node_table import alloc_vec

        vec = cache.get(node.ID)
        if vec is None:
            vec = resources_vec(node.Reserved)
            job_id = self.job.ID if self.job is not None else ""
            n_job = 0
            for a in self.ctx.proposed_allocs(node.ID):
                vec = vec + alloc_vec(a)
                if a.JobID == job_id:
                    n_job += 1
            cache[node.ID] = vec
            counts[node.ID] = n_job
        return vec

    def _option(self, node: Node, tg: TaskGroup, score: float):
        from nomad_tpu.structs import NetworkIndex, Resources

        from .stack import SelectedOption

        option = SelectedOption(node=node, score=score)
        needs_net = any(t.Resources is not None and t.Resources.Networks
                        for t in tg.Tasks)
        netidx = None
        if needs_net:
            netidx = NetworkIndex()
            netidx.set_node(node)
            netidx.add_allocs(self.ctx.proposed_allocs(node.ID))
        for task in tg.Tasks:
            resources = (task.Resources.copy() if task.Resources is not None
                         else Resources())
            if netidx is not None and task.Resources is not None \
                    and task.Resources.Networks:
                try:
                    offer = netidx.assign_network(
                        task.Resources.Networks[0], self.rng)
                except ValueError:
                    return None
                resources.Networks = [offer]
                netidx.add_reserved(offer)
            option.task_resources[task.Name] = resources
        return option

    # -------------------------------------------------------------- selection
    def select_batch(self, tgs: Sequence[TaskGroup]) -> List:
        usage_cache: Dict[str, np.ndarray] = {}
        counts: Dict[str, int] = {}
        return [self._select(tg, usage_cache, counts) for tg in tgs]

    def _select(self, tg: TaskGroup, usage_cache: Dict[str, np.ndarray],
                counts: Dict[str, int]):
        assert self.job is not None
        m = self.ctx.metrics
        cons = task_group_constraints(tg)
        demand = resources_vec(cons.size)

        order = list(range(len(self.nodes)))
        self.rng.shuffle(order)
        limit = 2
        n = len(self.nodes)
        if not self.batch and n > 0:
            limit = max(2, int(math.ceil(math.log2(n))))
        penalty = BATCH_PENALTY if self.batch else SERVICE_PENALTY

        best = None
        best_node = None
        seen = 0
        for i in order:
            node = self.nodes[i]
            m.NodesEvaluated += 1
            if not self._feasible(node, tg, cons.constraints, cons.drivers):
                m.NodesFiltered += 1
                continue
            usage = self._usage(node, usage_cache, counts)
            capacity = resources_vec(node.Resources)
            if np.any(capacity - usage < demand):
                m.NodesExhausted += 1
                continue
            score = binpack_score(
                usage, demand,
                capacity[:2] - resources_vec(node.Reserved)[:2])
            score -= counts.get(node.ID, 0) * penalty
            if best is None or score > best:
                best, best_node = score, node
            seen += 1
            if seen >= limit:
                break
        if best_node is None:
            return None
        option = self._option(best_node, tg, best)
        if option is None:
            return None
        usage_cache[best_node.ID] = usage_cache[best_node.ID] + demand
        counts[best_node.ID] = counts.get(best_node.ID, 0) + 1
        self.ctx.metrics.score_node(best_node, "binpack", best)
        return option

    def select_on_node(self, tg: TaskGroup, node: Node):
        """Feasibility + fit on one specific node (in-place update path)."""
        cons = task_group_constraints(tg)
        if node.Status != "ready" or node.Drain:
            return None
        if not self._feasible(node, tg, cons.constraints, cons.drivers):
            return None
        cache: Dict[str, np.ndarray] = {}
        counts: Dict[str, int] = {}
        usage = self._usage(node, cache, counts)
        capacity = resources_vec(node.Resources)
        demand = resources_vec(cons.size)
        if np.any(capacity - usage < demand):
            return None
        score = binpack_score(usage, demand,
                              capacity[:2] - resources_vec(node.Reserved)[:2])
        return self._option(node, tg, score)
