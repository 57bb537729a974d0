"""End to end: allocations committed per second of the window. Counted are
the allocations asked for by the jobs seen complete inside the window and
not among the failed operations; the recomputation (check 4) holds each of
those jobs to exactly that many live allocations. The time is all of the
window, from its first registration to the read that closed it."""


def read(run):
    t1 = run["window"]["t1"]
    placed = sum(op.asks for op in run["ops"]
                 if op.done is not None and op.done <= t1
                 and op.status == "complete"
                 and op.job_id not in run["failed_jobs"])
    if run["seconds"] <= 0 or placed == 0:
        return None
    return placed / run["seconds"]
