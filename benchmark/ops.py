"""One operation of a traffic mix as the generators record it, the poll
that every generator shares, and the fill guard's limit that a mix may
state for either loop. Times are time.perf_counter() seconds."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from benchmark.reference.guarantees import capacity_allocs

TERMINAL = ("complete", "failed", "canceled")


@dataclass
class Op:
    template: str
    job_id: str
    asks: int                     # allocations the job asks for
    due: float                    # when the schedule wanted it sent
    sent: float = 0.0             # when the call into the server began
    acked: float = 0.0            # when the call returned (acknowledged)
    eval_id: str = ""
    done: Optional[float] = None  # first read that showed a terminal status
    status: Optional[str] = None  # the last status read


def submit(dep, template, due, clock):
    """Build the job, register it, return its Op. The job is built before
    `sent` is taken: building stands for what a client does, the span from
    sent to acked for what the server's entry does."""
    job = dep.make_job(template)
    asks = sum(g.Count for g in job.TaskGroups)
    op = Op(template=template, job_id=job.ID, asks=asks, due=due)
    op.sent = clock()
    op.eval_id = dep.register(job)
    op.acked = clock()
    return op


def poll(dep, pending, clock):
    """Read each pending op's eval once; returns the ops that finished."""
    finished = []
    for op in pending:
        op.status = dep.eval_status(op.eval_id)
        if op.status in TERMINAL:
            op.done = clock()
            finished.append(op)
    return finished


def pick_template(weights, rng):
    names = sorted(weights)
    if len(names) == 1:
        return names[0]
    return rng.choices(names, [weights[n] for n in names])[0]


def fill_limit(dep, traffic):
    """The allocations a window may ask for before its fill guard ends it:
    the mix's fill_guard (a share of what the eligible nodes hold when
    empty, by the smallest template's room) less what set-up already asked
    for. None where the mix states no guard."""
    if not traffic.get("fill_guard"):
        return None
    nodes = dep.server.state.nodes()
    room = min(capacity_allocs(nodes, dep.make_job(t))
               for t in traffic["templates"])
    return traffic["fill_guard"] * room - dep.asked
