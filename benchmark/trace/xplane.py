"""The reduction from a profiler trace (.xplane.pb) to the benchmark's
device numbers: busy union, idle share, per-program sums, the breakdown.

jax.profiler.ProfileData reads the file with nothing but JAX. A TPU's plane
is named "/device:TPU:<n>"; its line "XLA Modules" has one event per program
run. The line "XLA Ops" has one per operation inside a program: 61,656 events
for one 32-eval window of the keyed kernel, whose loop runs 2,048 times, so
it is not read. On the chip the union of the programs' intervals and the
union of the operations' differ by under 0.01 % (0.47455 s against 0.47452 s
over a 600-job storm; my chip run, PR 24): a program's loop is itself one
operation that spans its body. Device events and the host's TraceAnnotation
events are on one clock (nanoseconds), so the harness's own markers
("bench.trace_begin", "bench.window_end") cut the device's timeline into the
part inside the measured window and the part after it. The program's
stage timers are spans on that clock too (since ISSUE 26 `metrics.measure`
and the window worker's `_stage` open a TraceAnnotation each, named by the
dotted metric key and carrying `worker` and `window` where they exist), so
the idle time is put down to the stage the host had open.

This is the one parser of the file (load) and the one place of the
interval arithmetic (union, intersect, subtract, idle_intervals,
open_intervals): the harness parses a run's trace once, reduces it here and
hands the parsed trace to the readers; trace/host_gaps.py builds the two
idle shares and its table on the same functions. tests/benchmark_suite
checks this file on the small recorded traces kept beside it
(sample.xplane.pb, sample_host.xplane.pb; the READMEs say how they were
made)."""

from __future__ import annotations

import bisect
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
PROGRAM_LINE = "XLA Modules"
MARKER_PREFIX = "bench."
SPAN_PREFIX = "nomad."
DEVICE_READ = "bench.device_read"  # the harness's own span, a marker too
# The precedence by which idle time is put down to a stage (idle_gaps, and
# the two metrics device_idle.dispatch.storm / .planwait.storm).
STAGE_FIRST = ("nomad.worker.dispatch", "nomad.worker.planwait")
STAGE_OTHER = ("nomad.worker.", "nomad.plan.")
TOP = 10


def peaks(device_kind):
    """The chip's published peaks from peaks.json. A device that is not in
    the table is an error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       "add it to benchmark/trace/peaks.json with its source")
    return table["devices"][device_kind]


def load(path):
    """{"devices": [{"name", "programs"}], "markers": {name: start_s},
    "spans": [...]}, in one pass over the file. programs is a list of
    (name, start_s, duration_s). spans are the host planes' events whose
    name starts with "nomad.", and the harness's own "bench.device_read"
    (the post-window read of check 6), as dicts of name, start_s, end_s
    and the span's own attributes (worker, window), by start. A program
    from before ISSUE 26 has none."""
    from jax.profiler import ProfileData

    devices, markers, spans = [], {}, []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            programs = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for line in plane.lines if line.name == PROGRAM_LINE
                        for e in line.events]
            devices.append({"name": plane.name, "programs": programs})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    start = e.start_ns * 1e-9
                    if e.name.startswith(MARKER_PREFIX):
                        markers.setdefault(e.name, start)
                    if e.name.startswith(SPAN_PREFIX) \
                            or e.name == DEVICE_READ:
                        spans.append({
                            "name": e.name, "start_s": start,
                            "end_s": start + e.duration_ns * 1e-9,
                            **{k: _plain(v) for k, v in e.stats}})
    devices.sort(key=lambda d: d["name"])
    spans.sort(key=lambda s: s["start_s"])
    return {"devices": devices, "markers": markers, "spans": spans}


def _plain(value):
    return value if isinstance(value, (int, float)) else str(value)


def plain(name):
    return re.sub(r"\(\d+\)$", "", name)


def merge(events, lo, hi):
    """The union of the events' intervals clipped to [lo, hi], as sorted
    disjoint (start, end, name of the event that opened it, name of the
    event that closed it)."""
    out = []
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        a, b = max(start, lo), min(start + dur, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b, out[-1][2], name)
        else:
            out.append((a, b, name, name))
    return out


def busy_seconds(events, lo, hi):
    return sum(b - a for a, b, _, _ in merge(events, lo, hi))


def program_sums(programs, lo, hi):
    """[(name, runs, seconds)] of the programs that started in [lo, hi),
    longest first. A program's name loses its fingerprint: "jit_f(123)" is
    "jit_f", whatever shape bucket it was compiled for."""
    sums = {}
    for name, start, dur in programs:
        if lo <= start < hi:
            entry = sums.setdefault(plain(name), [0, 0.0])
            entry[0] += 1
            entry[1] += dur
    return sorted(((n, c, s) for n, (c, s) in sums.items()),
                  key=lambda x: -x[2])


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
    busy = merge([(plain(n), s, d) for n, s, d in programs],
                        lo, hi)
    edges = [(lo, "trace_begin")] + [(b, last) for _, b, _, last in busy]
    starts = [(a, first) for a, _, first, _ in busy] + [(hi, "window_end")]
    return [(t0, t1, before, after)
            for (t0, before), (t1, after) in zip(edges, starts) if t1 > t0]


def open_intervals(spans, names, lo, hi):
    """Union over threads of the spans named in `names`, clipped."""
    return union((max(s["start_s"], lo), min(s["end_s"], hi))
                 for s in spans if s["name"] in names)


def idle_gaps(events, lo, hi, window_end, spans=()):
    """The traced span's idle time, all of it, summed by the phase it lay
    in and the stage the host had open: [("window:nomad.worker.dispatch",
    seconds), ...], longest first, at most TOP rows whose seconds add up to
    hi - lo minus the busy union. A stretch in which no program ran is put
    down to one of the program's stage spans (load's `spans`) by a fixed
    precedence, the one the two metrics that stand use
    (device_idle.dispatch.storm, device_idle.planwait.storm): a
    nomad.worker.dispatch span open on any thread; else a
    nomad.worker.planwait; else, gap by gap, the other nomad.worker.* /
    nomad.plan.* span or the harness's bench.device_read that covers most
    of the gap; else "none". Without spans (a program from before PR 26)
    every row is "none". Where more than TOP (phase, stage) pairs occur,
    the shortest are folded into one "<phase>:other" row a phase, so the
    sum still holds."""
    others = {}  # the spans of the third rank, by name
    for s in spans:
        if s["name"] not in STAGE_FIRST and (
                s["name"].startswith(STAGE_OTHER)
                or s["name"] == DEVICE_READ):
            others.setdefault(s["name"], []).append(s)
    sums = {}
    for phase, a, b in (("window", lo, min(window_end, hi)),
                        ("after_window", max(window_end, lo), hi)):
        if b <= a:
            continue
        rest = [(t0, t1) for t0, t1, _, _ in idle_intervals(events, a, b)]
        for name in STAGE_FIRST:
            held = open_intervals(spans, {name}, a, b)
            sums[f"{phase}:{name}"] = length(intersect(rest, held))
            rest = subtract(rest, held)
        starts = [gap[0] for gap in rest]
        held = [{} for _ in rest]  # per gap: seconds covered, by span name
        for name, group in others.items():
            opened = open_intervals(group, {name}, a, b)
            for t0, t1 in intersect(rest, opened):  # each inside one gap
                of = held[bisect.bisect_right(starts, t0) - 1]
                of[name] = of.get(name, 0.0) + t1 - t0
        for (t0, t1), of in zip(rest, held):
            name = max(of, key=of.get) if of else "none"
            key = f"{phase}:{name}"
            sums[key] = sums.get(key, 0.0) + t1 - t0
    rows = sorted(((n, s) for n, s in sums.items() if s > 0.0),
                  key=lambda r: -r[1])
    if len(rows) > TOP:
        kept, folded = rows[:TOP - 2], {}
        for name, secs in rows[TOP - 2:]:
            key = name.split(":", 1)[0] + ":other"
            folded[key] = folded.get(key, 0.0) + secs
        rows = sorted(kept + list(folded.items()), key=lambda r: -r[1])
    return rows


def reduce(trace, window_s, in_window_s):
    """window_s: length of the whole traced span (host clock); in_window_s:
    how much of it lay inside the measured window."""
    markers = trace["markers"]
    if "bench.trace_begin" not in markers:
        raise RuntimeError("the trace has no bench.trace_begin marker")
    if not trace["devices"]:
        raise RuntimeError("the trace has no /device:TPU plane")
    lo = markers["bench.trace_begin"]
    hi = lo + window_s
    cut = markers.get("bench.window_end", lo + in_window_s)
    timeline = trace["devices"][0]["programs"]
    busy = [busy_seconds(d["programs"], lo, hi) for d in trace["devices"]]
    busy_in = busy_seconds(timeline, lo, cut)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "in_window_s": cut - lo,
        "in_window_busy_s": busy_in,
        "in_window_idle_share":
            100.0 * (1.0 - busy_in / (cut - lo)) if cut > lo else None,
        "in_window_programs": program_sums(timeline, lo, cut),
        "breakdown": {
            "device_ops": [[n, s] for n, _, s in
                           program_sums(timeline, lo, hi)[:TOP]],
            "idle_gaps": [[n, s] for n, s in idle_gaps(
                timeline, lo, hi, cut, trace.get("spans", ()))],
        },
    }
