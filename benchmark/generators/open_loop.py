"""Open loop: jobs are due on a schedule drawn from the seed and are sent
whether or not earlier ones have finished. Each is timed from its due time
(not from when it was sent, so a stall's cost to later arrivals counts) to
the first read that shows its eval in a terminal status.

Traffic parameters: arrival ("poisson", "fixed" or "bursts"), rate_per_s,
burst (jobs per burst, for "bursts"), poll_ms, templates (weights),
fill_guard (share of eligible capacity, optional). With a fill guard, as
in closed_loop, no op is sent once the allocations asked for reach it: the
window ends there, the ops in flight are still waited for, and
`progress(asked, limit)`, where the harness gives one, is told once a
registration. Without one the hook is not called.

Every seed gets the same multiset of gaps in another order: a Poisson
process is drawn as blocks of BLOCK gaps at the exponential distribution's
quantile midpoints, scaled so that each block lasts BLOCK / rate seconds,
shuffled by the seed. Run-to-run spread then comes from the system, not
from how many arrivals a seed happened to draw."""

from __future__ import annotations

import math
import time

from benchmark.ops import fill_limit, pick_template, poll, submit

BLOCK = 100
GRACE_S = 60.0  # how long after the window an unfinished op is waited for


def gaps(traffic, rng):
    """An endless iterator of inter-arrival gaps in seconds."""
    rate = float(traffic["rate_per_s"])
    kind = traffic["arrival"]
    if kind == "fixed":
        block = [1.0 / rate]
    elif kind == "bursts":
        n = int(traffic["burst"])
        block = [n / rate] + [0.0] * (n - 1)
    elif kind == "poisson":
        raw = [-math.log(1.0 - (k + 0.5) / BLOCK) for k in range(BLOCK)]
        scale = BLOCK / rate / sum(raw)
        block = [g * scale for g in raw]
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    while True:
        if kind == "poisson":
            rng.shuffle(block)
        yield from block


def run(dep, traffic, rng, seconds, clock=time.perf_counter, progress=None):
    poll_s = traffic["poll_ms"] / 1e3
    limit = fill_limit(dep, traffic)
    gap = gaps(traffic, rng)
    ops, pending, notes = [], [], []
    asked = 0
    t0 = clock()
    t_end = t0 + seconds
    due = t0 + next(gap)
    while True:
        now = clock()
        while due <= t_end and due <= now:
            op = submit(dep, pick_template(traffic["templates"], rng), due,
                        clock)
            ops.append(op)
            pending.append(op)
            due += next(gap)
            if limit is None:
                continue
            asked += op.asks
            if progress is not None:
                progress(asked, limit)
            if asked >= limit:  # the guard ends the window: send no more
                t_end, due = clock(), math.inf
                notes.append(f"fill guard: {asked} allocations asked for "
                             f"reach {traffic['fill_guard']:.0%} of eligible "
                             f"capacity; window ended after "
                             f"{t_end - t0:.3f} s")
        for op in poll(dep, pending, clock):
            pending.remove(op)
        now = clock()
        if due > t_end and (not pending or now > t_end + GRACE_S):
            break
        wake = now + poll_s if pending else due
        if due <= t_end:
            wake = min(wake, due)
        time.sleep(max(0.0, min(wake, t_end + GRACE_S) - clock()))
    return {"t0": t0, "t1": t_end, "gave_up": clock(), "ops": ops,
            "notes": notes}
