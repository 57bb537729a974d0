"""Check 11: every stop the server acknowledged took effect. For each job
in the deployment's `stopped` list (job id, register eval, deregister
eval): the job is absent from the store, none of its allocations is
non-terminal, and its deregister eval ended `complete`.

Plain Python over the store's public reads (job_by_id, allocs_by_job,
eval_by_id), after the drain; it shares nothing with the tensor path. That
what the stopped allocations held is free again is checks 2 and 6 of
guarantees.py, which recompute each node's usage, and the device's usage
table, from the live allocations alone; check 3 names any live allocation
of a job that is gone. Compared as a count of the jobs that break it,
against 0, like checks 2-5."""

from __future__ import annotations

from benchmark.reference.guarantees import MAX_IDS


def stop_failures(state, stopped):
    """[(job id, what is wrong)] over the acknowledged stops."""
    wrong = []
    for job_id, _, dereg_eval in stopped:
        if state.job_by_id(job_id) is not None:
            wrong.append((job_id, "job still in the store"))
        live = [a.ID for a in state.allocs_by_job(job_id)
                if not a.terminal_status()]
        if live:
            wrong.append((job_id, f"{len(live)} live allocations, e.g. "
                                  f"{live[0]}"))
        ev = state.eval_by_id(dereg_eval)
        status = None if ev is None else ev.Status
        if status != "complete":
            wrong.append((job_id, f"deregister eval {dereg_eval} {status}"))
    return wrong


def check(dep, seed, verdict):
    """Adds check 11's failures to the verdict; returns the facts."""
    state = dep.server.state
    wrong = stop_failures(state, dep.stopped)
    jobs = sorted({job_id for job_id, _ in wrong})
    verdict.require("11_stops", not wrong,
                    f"{len(jobs)} acknowledged stops did not take effect: "
                    + "; ".join(f"{j}: {why}" for j, why in wrong[:MAX_IDS]),
                    jobs)
    stopped_allocs = sum(len(state.allocs_by_job(job_id))
                         for job_id, _, _ in dep.stopped)
    return {"stopped_jobs": len(dep.stopped),
            "stopped_allocations": stopped_allocs,
            "jobs_breaking_it": len(jobs)}
