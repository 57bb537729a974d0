"""Closed loop: `outstanding` jobs are kept in flight; the next is
registered when one is seen in a terminal status. Completion is polled
every poll_ms. The window ends after `seconds`, or earlier at the fill
guard: when the allocations asked for so far reach fill_guard of what the
eligible nodes can hold, submitting stops and the window ends there, so
that a faster program is not punished with blocked evals.

Traffic parameters: outstanding, poll_ms, templates (weights), fill_guard
(share of eligible capacity, optional).

`progress(asked, limit)`, where the harness gives one, is told once a
registration how far the window has come towards its guard (limit None
without a guard): a traced run may anchor its trace to that
(instruments.Window.progress; the file's trace_guard_share)."""

from __future__ import annotations

import time

from benchmark.ops import fill_limit, pick_template, poll, submit


def run(dep, traffic, rng, seconds, clock=time.perf_counter, progress=None):
    poll_s = traffic["poll_ms"] / 1e3
    target = int(traffic["outstanding"])
    notes = []
    limit = fill_limit(dep, traffic)
    ops, pending = [], []
    asked = 0
    t0 = clock()
    t_end = t0 + seconds
    while True:
        for op in poll(dep, pending, clock):
            pending.remove(op)
        now = clock()
        if now >= t_end:
            break  # every op with `done` set was read before `now`
        if limit is not None and asked >= limit:
            notes.append(f"fill guard: {asked} allocations asked for reach "
                         f"{traffic['fill_guard']:.0%} of eligible capacity; "
                         f"window ended after {now - t0:.3f} s")
            break
        while len(pending) < target and (limit is None or asked < limit):
            op = submit(dep, pick_template(traffic["templates"], rng),
                        clock(), clock)
            ops.append(op)
            pending.append(op)
            asked += op.asks
            if progress is not None:
                progress(asked, limit)
        time.sleep(max(0.0, min(poll_s, t_end - clock())))
    return {"t0": t0, "t1": now, "gave_up": None, "ops": ops, "notes": notes}
