"""The device's idle gaps put down to what the host was doing in them.

Since ISSUE 26 the program's stage timers (`metrics.measure`, the window
worker's `_stage`) open a jax.profiler.TraceAnnotation each, named by the
dotted metric key ("nomad.worker.dispatch", "nomad.plan.apply",
"nomad.fsm.sweep") and carrying `worker` and `window` where they exist.
Those events lie on the host planes of the same .xplane.pb as the device's
"XLA Modules" events, on one nanosecond clock (benchmark/trace/xplane.py
says how the device's side is read). This file reads both sides:

  load(path)      the host's "nomad.*" spans beside xplane.load's programs
                  and markers
  idle_share(..)  the share of the in-window traced span in which the
                  device ran no program AND a span of a given name was
                  open on any thread (and none of another set was): what
                  benchmark/readers/host_spans.py reports
  gaps(..)        the longest in-window gaps, each with the spans open in
                  it: the table PERF.md section 5 keeps

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

SPAN_PREFIX = "nomad."
TOP = 10


def load(path):
    """What xplane.load gives ({"devices", "markers"}, read the same way,
    in one pass over the file) plus "spans": the host planes' events whose
    name starts with "nomad.", as dicts of name, start_s, end_s and the
    span's own attributes (worker, window)."""
    from jax.profiler import ProfileData

    devices, markers, spans = [], {}, []
    for plane in ProfileData.from_file(path).planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            devices.append({"name": plane.name, "programs": [
                (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                for line in plane.lines if line.name == xplane.PROGRAM_LINE
                for e in line.events]})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    start = e.start_ns * 1e-9
                    if e.name.startswith(xplane.MARKER_PREFIX):
                        markers.setdefault(e.name, start)
                    elif e.name.startswith(SPAN_PREFIX):
                        spans.append({
                            "name": e.name, "start_s": start,
                            "end_s": start + e.duration_ns * 1e-9,
                            **{k: _plain(v) for k, v in e.stats}})
    devices.sort(key=lambda d: d["name"])
    spans.sort(key=lambda s: s["start_s"])
    return {"devices": devices, "markers": markers, "spans": spans}


def _plain(value):
    return value if isinstance(value, (int, float)) else str(value)


def window(trace):
    """(start, end) of the part of the trace inside the measured window,
    by the harness's markers; None when one is missing."""
    markers = trace["markers"]
    if "bench.trace_begin" not in markers \
            or "bench.window_end" not in markers:
        return None
    return markers["bench.trace_begin"], markers["bench.window_end"]


def union(intervals):
    """Sorted disjoint (start, end) covering the same points."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def intersect(xs, ys):
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys):
    """xs without ys, both sorted disjoint interval lists."""
    out = []
    for a, b in xs:
        for c, d in ys:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def length(intervals):
    return sum(b - a for a, b in intervals)


def idle_intervals(programs, lo, hi):
    """[(start, end, program before, program after)] of the stretches of
    [lo, hi] in which no program ran on the device."""
    busy = xplane.merge([(xplane.plain(n), s, d) for n, s, d in programs],
                        lo, hi)
    edges = [(lo, "trace_begin")] + [(b, last) for _, b, _, last in busy]
    starts = [(a, first) for a, _, first, _ in busy] + [(hi, "window_end")]
    return [(t0, t1, before, after)
            for (t0, before), (t1, after) in zip(edges, starts) if t1 > t0]


def open_intervals(spans, names, lo, hi):
    """Union over threads of the spans named in `names`, clipped."""
    return union((max(s["start_s"], lo), min(s["end_s"], hi))
                 for s in spans if s["name"] in names)


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
            idle_intervals(trace["devices"][0]["programs"], lo, hi)]
    held = intersect(idle, open_intervals(trace["spans"], set(spans),
                                          lo, hi))
    if without:
        held = subtract(held, open_intervals(trace["spans"], set(without),
                                             lo, hi))
    return 100.0 * length(held) / (hi - lo)


def gaps(trace, top=TOP):
    """The `top` longest in-window idle gaps, longest first: offset from
    the trace's begin, length, the programs on its two sides, and every
    span open in it with the seconds of the gap it covers."""
    extent = window(trace)
    if extent is None or not trace["devices"]:
        return None
    lo, hi = extent
    found = idle_intervals(trace["devices"][0]["programs"], lo, hi)
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
    trace = load(path)
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
