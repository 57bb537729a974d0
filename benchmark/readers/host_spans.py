"""Shares of the in-window traced span in which the device ran no program
while the host had a span of a given name open (benchmark/trace/
host_gaps.py): which stage of the program the idle device was waiting for.

  spans:   names of the program's spans ("nomad.worker.dispatch"); time
           counts while any of them is open on any thread
  without: names whose open time is taken out again, so that two metrics
           split the idle time without counting a moment twice

Read from the trace the harness left under .bench_work/trace/ (parsed once
per process). None in a rehearsal (no device plane) and where the trace is
missing; 0 where the program has no such spans, as before ISSUE 26."""

import functools
import os

from benchmark.trace import host_gaps

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@functools.lru_cache(maxsize=2)
def _trace(path, mtime):
    return host_gaps.load(path)


def read(run, spans, without=()):
    if run["device"] is None:
        return None
    path = host_gaps.newest_trace(ROOT)
    if path is None:
        return None
    trace = _trace(path, os.path.getmtime(path))
    return host_gaps.idle_share(trace, spans, without)
