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
part inside the measured window and the part after it.
tests/benchmark_suite checks this file on the small recorded trace kept
beside it (sample.xplane.pb; sample.README says how it was made)."""

from __future__ import annotations

import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
PROGRAM_LINE = "XLA Modules"
MARKER_PREFIX = "bench."
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
    """{"devices": [{"name", "programs"}], "markers": {name: start_s}};
    programs is a list of (name, start_s, duration_s)."""
    from jax.profiler import ProfileData

    devices, markers = [], {}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            programs = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for line in plane.lines if line.name == PROGRAM_LINE
                        for e in line.events]
            devices.append({"name": plane.name, "programs": programs})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(MARKER_PREFIX):
                        markers.setdefault(e.name, e.start_ns * 1e-9)
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "markers": markers}


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


def idle_gaps(events, lo, hi, window_end):
    """The longest gaps in which no program ran, each named by the phase it
    began in and the programs on its two sides: the program has no host
    spans on the device's clock yet (PERF.md, list for the tracing issue),
    so this is all that can be said of what the host was doing."""
    busy = merge([(plain(n), s, d) for n, s, d in events], lo, hi)
    edges = [(lo, "trace_begin")] + [(b, last) for _, b, _, last in busy]
    starts = [(a, first) for a, _, first, _ in busy] + [(hi, "trace_end")]
    gaps = []
    for (t_from, before), (t_to, after) in zip(edges, starts):
        if t_to > t_from:
            phase = "window" if t_from < window_end else "after_window"
            gaps.append((f"{phase}:{before}->{after}", t_to - t_from))
    return sorted(gaps, key=lambda g: -g[1])[:TOP]


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
            "idle_gaps": [[n, s] for n, s in idle_gaps(timeline, lo, hi,
                                                       cut)],
        },
    }
