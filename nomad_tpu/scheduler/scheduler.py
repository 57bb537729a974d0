"""Scheduler factory and the State/Planner seams (reference:
scheduler/scheduler.go:13-96)."""

from __future__ import annotations

import logging
from typing import Callable, Dict, Optional, Protocol, Tuple

from nomad_tpu.structs import Evaluation, Plan, PlanResult


class State(Protocol):
    """Immutable snapshot reads the scheduler needs (reference:
    scheduler.go:55-76). Satisfied by StateStore and StateSnapshot."""

    def nodes(self): ...
    def node_by_id(self, node_id: str): ...
    def job_by_id(self, job_id: str): ...
    def allocs_by_job(self, job_id: str): ...
    def allocs_by_node(self, node_id: str): ...
    def allocs_by_node_terminal(self, node_id: str, terminal: bool): ...


class Planner(Protocol):
    """Write seam owned by the worker (reference: scheduler.go:78-96)."""

    def submit_plan(self, plan: Plan) -> Tuple[Optional[PlanResult], Optional[State]]:
        """Returns (result, refreshed_state_or_None)."""
        ...

    def update_eval(self, eval: Evaluation) -> None: ...
    def create_eval(self, eval: Evaluation) -> None: ...
    def reblock_eval(self, eval: Evaluation) -> None: ...


class Scheduler(Protocol):
    def process(self, eval: Evaluation) -> None: ...


class SetStatusError(Exception):
    """Terminal scheduling failure carrying the eval status to set
    (reference: generic_sched.go:42-50)."""

    def __init__(self, msg: str, eval_status: str):
        super().__init__(msg)
        self.eval_status = eval_status


def new_scheduler(name: str, state: State, planner: Planner,
                  tindex=None, logger: Optional[logging.Logger] = None,
                  impl: str = "tpu") -> Scheduler:
    """(reference: scheduler.go:30-41 NewScheduler)

    tindex is the TensorIndex backing the placement kernels; when None, one is
    built from the state snapshot (simple mode for tests/tools). impl selects
    the placement engine for the generic schedulers: "tpu" (device kernels)
    or "cpu-reference" (host-side iterator chain, the parity tests' golden
    model).
    """
    factory = BUILTIN_SCHEDULERS.get(name)
    if factory is None:
        raise ValueError(f"unknown scheduler '{name}'")
    return factory(state, planner, tindex,
                   logger or logging.getLogger("sched"), impl)


def _service(state, planner, tindex, logger, impl="tpu"):
    from .generic_sched import GenericScheduler

    return GenericScheduler(state, planner, tindex, logger, batch=False,
                            impl=impl)


def _batch(state, planner, tindex, logger, impl="tpu"):
    from .generic_sched import GenericScheduler

    return GenericScheduler(state, planner, tindex, logger, batch=True,
                            impl=impl)


def _system(state, planner, tindex, logger, impl="tpu"):
    # The system scheduler's per-node sweep is host-side already; it has no
    # separate cpu-reference engine, so impl is accepted but moot.
    from .system_sched import SystemScheduler

    return SystemScheduler(state, planner, tindex, logger)


BUILTIN_SCHEDULERS: Dict[str, Callable] = {
    "service": _service,
    "batch": _batch,
    "system": _system,
}
