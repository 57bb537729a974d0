"""The device's idle gaps put down to what the host was doing in them.

benchmark/trace/xplane.py parses a traced run's file (the device's
programs, the harness's markers and the program's stage spans, on one
clock) and holds the interval arithmetic; it sums the whole traced span's
idle time by the stage open in it, which is the `breakdown.idle_gaps` of a
run's line. This file reads two more things off the parsed trace:

  idle_share(..)  the share of the in-window traced span in which the
                  device ran no program AND a span of a given name was
                  open on any thread (and none of another set was): what
                  benchmark/readers/host_spans.py reports
  gaps(..)        the longest in-window gaps, each with the spans open in
                  it: the table this file prints as a tool

As a tool it prints that table for a traced run's file:

    python -m benchmark.trace.host_gaps [--json] [path-to.xplane.pb]

(default: the newest trace under .bench_work/trace/, which a `--trace 1`
run leaves there until the next one). A program without such spans, as
every commit before ISSUE 26, gives gaps with nothing open in them and
shares of 0; a trace without a device plane gives None.
tests/benchmark_suite/test_benchmark_host_spans.py checks this file on
hand-made events and on sample_host.xplane.pb (sample_host.README)."""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

from benchmark.trace import xplane


def window(trace):
    """(start, end) of the part of the trace inside the measured window,
    by the harness's markers; None when one is missing."""
    markers = trace["markers"]
    if "bench.trace_begin" not in markers \
            or "bench.window_end" not in markers:
        return None
    return markers["bench.trace_begin"], markers["bench.window_end"]


def idle_share(trace, spans, without=()):
    """100 x (time in which the device ran no program, a span named in
    `spans` was open and none named in `without` was) / (in-window traced
    span). None without the markers or a device plane: nothing to read."""
    extent = window(trace)
    if extent is None or not trace["devices"]:
        return None
    lo, hi = extent
    if hi <= lo:
        return None
    idle = [(a, b) for a, b, _, _ in
            xplane.idle_intervals(trace["devices"][0]["programs"], lo, hi)]
    held = xplane.intersect(idle, xplane.open_intervals(
        trace["spans"], set(spans), lo, hi))
    if without:
        held = xplane.subtract(held, xplane.open_intervals(
            trace["spans"], set(without), lo, hi))
    return 100.0 * xplane.length(held) / (hi - lo)


def gaps(trace, top=xplane.TOP):
    """The `top` longest in-window idle gaps, longest first: offset from
    the trace's begin, length, the programs on its two sides, and every
    span open in it with the seconds of the gap it covers."""
    extent = window(trace)
    if extent is None or not trace["devices"]:
        return None
    lo, hi = extent
    found = xplane.idle_intervals(trace["devices"][0]["programs"], lo, hi)
    out = []
    for t0, t1, before, after in sorted(found, key=lambda g: g[0] - g[1]
                                        )[:top]:
        inside = []
        for s in trace["spans"]:
            covered = min(s["end_s"], t1) - max(s["start_s"], t0)
            if covered > 0:
                inside.append({"name": s["name"], "covers_s": covered,
                               "worker": s.get("worker"),
                               "window": s.get("window")})
        inside.sort(key=lambda s: -s["covers_s"])
        out.append({"at_s": t0 - lo, "gap_s": t1 - t0, "before": before,
                    "after": after, "open": inside})
    return out


def newest_trace(root):
    paths = glob.glob(os.path.join(root, ".bench_work", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", nargs="?")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--spans", type=int, default=6,
                    help="spans listed per gap, longest cover first")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = args.path or newest_trace(root)
    if path is None:
        print("host_gaps: no trace under .bench_work/trace/",
              file=sys.stderr)
        return 1
    trace = xplane.load(path)
    table = gaps(trace)
    if table is None:
        print("host_gaps: the trace lacks the bench.* markers or a device "
              "plane", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps({"path": path, "gaps": table}))
        return 0
    lo, hi = window(trace)
    print(f"{path}: {len(trace['spans'])} host spans, in-window span "
          f"{hi - lo:.3f} s")
    for gap in table:
        print(f"+{gap['at_s'] * 1e3:9.1f} ms  idle {gap['gap_s'] * 1e3:8.1f}"
              f" ms  {gap['before']} -> {gap['after']}")
        for s in gap["open"][:args.spans]:
            who = ", ".join(f"{k} {s[k]}" for k in ("worker", "window")
                            if s[k] is not None)
            print(f"      {s['covers_s'] * 1e3:8.1f} ms  {s['name']}"
                  + (f"  ({who})" if who else ""))
        if not gap["open"]:
            print("      no nomad.* span open")
    return 0


if __name__ == "__main__":
    sys.exit(main())
