"""Host-side constraint compiler: per-class evaluation, per-node gather.

Semantics mirror the reference's ConstraintChecker (reference:
scheduler/feasible.go:244-452): target interpolation (${node.*}, ${attr.*},
${meta.*}), operands (= == is, != not, lexical < <= > >=, version, regexp),
and the computed-class memoization with the unique.* escape hatch (reference:
scheduler/feasible.go:454-568, scheduler/context.go:150-331).

Regex/version work is not expressible in XLA; it runs here once per computed
node class (classes << nodes), yielding a [C] bool table that the node axis
gathers through class_ids — the tensorized form of the reference's
EvalEligibility cache.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from nomad_tpu.structs import Constraint, Node, escaped_constraints
from nomad_tpu.structs.structs import ConstraintDistinctHosts
from nomad_tpu.structs.version import check_version_constraint

from .node_table import NodeTensor

_REGEX_CACHE: Dict[str, Optional[re.Pattern]] = {}


def resolve_target(target: str, node: Node):
    """Interpolate a constraint target against a node; returns (value, ok)."""
    if not target.startswith("${"):
        return target, True
    if target == "${node.unique.id}":
        return node.ID, True
    if target == "${node.datacenter}":
        return node.Datacenter, True
    if target == "${node.unique.name}":
        return node.Name, True
    if target == "${node.class}":
        return node.NodeClass, True
    if target.startswith("${attr."):
        attr = target[len("${attr."):]
        attr = attr[:-1] if attr.endswith("}") else attr
        if attr in node.Attributes:
            return node.Attributes[attr], True
        return None, False
    if target.startswith("${meta."):
        meta = target[len("${meta."):]
        meta = meta[:-1] if meta.endswith("}") else meta
        if meta in node.Meta:
            return node.Meta[meta], True
        return None, False
    return None, False


def check_constraint(operand: str, l_val, r_val) -> bool:
    """Operand evaluation (reference: feasible.go:327-350)."""
    if operand == ConstraintDistinctHosts:
        return True  # handled by the placement kernel, not per-node
    if operand in ("=", "==", "is"):
        return l_val == r_val
    if operand in ("!=", "not"):
        return l_val != r_val
    if operand in ("<", "<=", ">", ">="):
        if not isinstance(l_val, str) or not isinstance(r_val, str):
            return False
        return {"<": l_val < r_val, "<=": l_val <= r_val,
                ">": l_val > r_val, ">=": l_val >= r_val}[operand]
    if operand == "version":
        # The reference converts an integer lVal to its decimal string
        # (feasible.go checkVersionConstraint's int fallback).
        if isinstance(l_val, int) and not isinstance(l_val, bool):
            l_val = str(l_val)
        if not isinstance(l_val, str) or not isinstance(r_val, str):
            return False
        return check_version_constraint(l_val, r_val)
    if operand == "regexp":
        if not isinstance(l_val, str) or not isinstance(r_val, str):
            return False
        pat = _REGEX_CACHE.get(r_val, False)
        if pat is False:
            try:
                pat = re.compile(r_val)
            except re.error:
                pat = None
            _REGEX_CACHE[r_val] = pat
        return pat is not None and bool(pat.search(l_val))
    return False


def constraint_sig(constraints: Sequence[Constraint]) -> tuple:
    """Value identity of a constraint list. THE single definition: every
    cache keyed on "same constraints" (class-eligibility masks, shared
    prepared batches) must use this so a future constraint field can't be
    forgotten in one of them."""
    return tuple((c.LTarget, c.Operand, c.RTarget) for c in constraints)


def node_meets_constraints(node: Node, constraints: Sequence[Constraint]) -> bool:
    for c in constraints:
        l_val, l_ok = resolve_target(c.LTarget, node)
        r_val, r_ok = resolve_target(c.RTarget, node)
        if not l_ok or not r_ok:
            return False
        if not check_constraint(c.Operand, l_val, r_val):
            return False
    return True


def node_has_drivers(node: Node, drivers: Sequence[str]) -> bool:
    """DriverChecker (reference: feasible.go:91-143): `driver.<name>` node
    attribute must parse as a true boolean — Go strconv.ParseBool
    semantics, so "1", "t", "T", "true", "TRUE", "True" all pass."""
    for d in drivers:
        raw = node.Attributes.get(f"driver.{d}", "")
        if raw not in ("1", "t", "T", "true", "TRUE", "True"):
            return False
    return True


class ClassEligibility:
    """Per-eval cache of class-level job/TG eligibility (the tensorized
    EvalEligibility, reference: scheduler/context.go:150-331).

    For each computed class we keep one representative node; job- and
    task-group-level constraints are evaluated once per class against the
    representative and cached. Escaped constraints (targets under unique.*)
    are evaluated per node. The result is a [N] bool mask over the node
    tensor's rows.
    """

    def __init__(self, nt: NodeTensor, nodes: Sequence[Node]):
        self.nt = nt
        self.representatives: Dict[int, Node] = {}
        self.nodes_by_row: Dict[int, Node] = {}
        for node in nodes:
            row = nt.row_of.get(node.ID)
            if row is None:
                continue
            self.nodes_by_row[row] = node
            cid = nt.class_vocab.get(node.ComputedClass)
            if cid is not None and cid not in self.representatives:
                self.representatives[cid] = node
        self._job_cache: Dict[str, Tuple[np.ndarray, bool]] = {}
        self._tg_cache: Dict[Tuple[str, str], np.ndarray] = {}
        # Cross-job memo keyed by the constraint SIGNATURE: a registration
        # storm of many jobs with identical constraints (the C1M shape) pays
        # the per-class evaluation once, not once per job. The per-job-id
        # caches above stay — blocked-eval reporting introspects them — but
        # they become views onto these shared entries.
        self._sig_cache: Dict[tuple, Tuple[np.ndarray, np.ndarray, bool]] = {}

    def view(self) -> "ClassEligibility":
        """A fresh set of per-job views over the SAME representatives and
        signature cache: what a holder that outlives one scheduling pass
        (TensorIndex.node_context) hands each pass. The per-job caches are
        keyed by job id alone, so they must not outlive the pass: a job
        re-registered under its id with other constraints would read its
        old mask. The signature cache is keyed by the constraints' value."""
        v = ClassEligibility.__new__(ClassEligibility)
        v.nt = self.nt
        v.representatives = self.representatives
        v.nodes_by_row = self.nodes_by_row
        v._job_cache = {}
        v._tg_cache = {}
        v._sig_cache = self._sig_cache
        return v

    # ---- reporting for blocked evals (reference: Evaluation.ClassEligibility)
    def class_eligibility_report(self, mask_by_class: np.ndarray) -> Dict[str, bool]:
        out = {}
        for cid, ok in enumerate(mask_by_class):
            if cid < len(self.nt.class_names):
                out[self.nt.class_names[cid]] = bool(ok)
        return out

    def _class_table(self, constraints: Sequence[Constraint]) -> np.ndarray:
        """[C] bool: class representative satisfies the memoizable constraints."""
        n_classes = len(self.nt.class_names)
        table = np.zeros(n_classes, dtype=bool)
        for cid, rep in self.representatives.items():
            table[cid] = node_meets_constraints(rep, constraints)
        return table

    def _escaped_mask(self, constraints: Sequence[Constraint]) -> Optional[np.ndarray]:
        """[N] bool over rows for constraints that escape class memoization."""
        if not constraints:
            return None
        mask = np.zeros(self.nt.n_rows, dtype=bool)
        for row, node in self.nodes_by_row.items():
            mask[row] = node_meets_constraints(node, constraints)
        return mask

    @staticmethod
    def _sig(constraints: Sequence[Constraint],
             drivers: Sequence[str] = ()) -> tuple:
        return (constraint_sig(constraints), tuple(drivers))

    def job_mask(self, job_id: str, constraints: Sequence[Constraint],
                 ) -> Tuple[np.ndarray, np.ndarray, bool]:
        """Returns ([N] row mask, [C] class table, escaped?)."""
        cached = self._job_cache.get(job_id)
        if cached is None:
            sig = ("job",) + self._sig(constraints)
            cached = self._sig_cache.get(sig)
            if cached is None:
                esc = escaped_constraints(list(constraints))
                memo = [c for c in constraints if c not in esc]
                table = self._class_table(memo)
                mask = table[self.nt.class_ids]
                esc_mask = self._escaped_mask(esc)
                if esc_mask is not None:
                    mask = mask & esc_mask
                cached = (mask, table, bool(esc))
                self._sig_cache[sig] = cached
            self._job_cache[job_id] = cached
        return cached

    def tg_mask(self, job_id: str, tg_name: str,
                constraints: Sequence[Constraint],
                drivers: Sequence[str]) -> Tuple[np.ndarray, np.ndarray, bool]:
        """Task-group-level mask: constraints + driver availability."""
        key = (job_id, tg_name)
        cached = self._tg_cache.get(key)
        if cached is None:
            sig = ("tg",) + self._sig(constraints, drivers)
            cached = self._sig_cache.get(sig)
            if cached is None:
                esc = escaped_constraints(list(constraints))
                memo = [c for c in constraints if c not in esc]
                n_classes = len(self.nt.class_names)
                table = np.zeros(n_classes, dtype=bool)
                for cid, rep in self.representatives.items():
                    table[cid] = (node_meets_constraints(rep, memo)
                                  and node_has_drivers(rep, drivers))
                mask = table[self.nt.class_ids]
                esc_mask = self._escaped_mask(esc)
                if esc_mask is not None:
                    mask = mask & esc_mask
                cached = (mask, table, bool(esc))
                self._sig_cache[sig] = cached
            self._tg_cache[key] = cached
        return cached
