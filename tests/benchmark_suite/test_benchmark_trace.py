"""The reduction from a profiler trace to the benchmark's device numbers
(benchmark/trace/xplane.py): on hand-made events, where the right answer is
plain, and on the small trace recorded on the chip that is kept beside it."""

import json
import os

import pytest

from benchmark.trace import xplane

HERE = os.path.dirname(os.path.abspath(xplane.__file__))
SAMPLE = os.path.join(HERE, "sample.xplane.pb")

# (name, start_s, duration_s): two overlapping programs, a gap, a nested
# pair, one that straddles the window's end, one after it.
OPS = [("a(1)", 1.0, 0.5), ("b(2)", 1.25, 0.5), ("c(3)", 3.0, 1.0),
       ("a(9)", 3.25, 0.25), ("e(4)", 5.5, 1.0), ("f(5)", 8.25, 0.5)]
TRACE = {"devices": [{"name": "/device:TPU:0", "programs": OPS}],
         "markers": {"bench.trace_begin": 0.0, "bench.window_end": 6.0}}


def test_busy_is_the_union_of_intervals_not_their_sum():
    assert xplane.busy_seconds(OPS, 0.0, 10.0) == pytest.approx(
        0.75 + 1.0 + 1.0 + 0.5)
    # Clipped to the window: op e counts only up to its end.
    assert xplane.busy_seconds(OPS, 0.0, 6.0) == pytest.approx(
        0.75 + 1.0 + 0.5)
    assert xplane.busy_seconds([], 0.0, 6.0) == 0.0


def test_reduce_cuts_the_timeline_at_the_window_end_marker():
    out = xplane.reduce(TRACE, window_s=10.0, in_window_s=6.0)
    assert out["busy_s"] == pytest.approx(3.25)
    assert out["window_s"] == 10.0
    assert out["in_window_busy_s"] == pytest.approx(2.25)
    assert out["in_window_idle_share"] == pytest.approx(
        100.0 * (1 - 2.25 / 6.0))
    # Per-program sums inside the window, fingerprints dropped, longest
    # first; the breakdown has them over the whole traced span.
    assert out["in_window_programs"] == [
        ("c", 1, pytest.approx(1.0)), ("e", 1, pytest.approx(1.0)),
        ("a", 2, pytest.approx(0.75)), ("b", 1, pytest.approx(0.5))]
    ops = dict(map(tuple, out["breakdown"]["device_ops"]))
    assert ops["a"] == pytest.approx(0.75) and len(ops) == 5
    # The idle time, all of it, by the phase it lay in and the host stage
    # open in it (ISSUE 38). This trace has no host span: every idle second
    # is "none", 0-1, 1.75-3, 4-5.5 inside the window (e straddles its end
    # at 6.0), 6.5-8.25 and 8.75-10 after it.
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps == [["window:none", pytest.approx(3.75)],
                    ["after_window:none", pytest.approx(3.0)]]
    assert sum(s for _, s in gaps) + out["busy_s"] == pytest.approx(10.0)


def test_a_window_in_which_no_operation_ran_is_all_idle():
    quiet = {"devices": [{"name": "/device:TPU:0",
                          "programs": [("refresh(1)", 7.0, 0.25)]}],
             "markers": {"bench.trace_begin": 0.0, "bench.window_end": 6.0}}
    out = xplane.reduce(quiet, window_s=8.0, in_window_s=6.0)
    assert out["in_window_idle_share"] == 100.0
    assert out["busy_s"] == pytest.approx(0.25)  # the post-window read


def test_busy_is_averaged_over_the_chips_used():
    two = {"devices": [TRACE["devices"][0],
                       {"name": "/device:TPU:1",
                        "programs": [("x(1)", 1.0, 1.25)]}],
           "markers": TRACE["markers"]}
    assert xplane.reduce(two, 10.0, 6.0)["busy_s"] == pytest.approx(
        (3.25 + 1.25) / 2)


def test_a_trace_without_marker_or_device_plane_is_an_error():
    with pytest.raises(RuntimeError, match="trace_begin"):
        xplane.reduce({"devices": TRACE["devices"], "markers": {}}, 1.0, 1.0)
    with pytest.raises(RuntimeError, match="device"):
        xplane.reduce({"devices": [], "markers": TRACE["markers"]}, 1.0, 1.0)


def test_peaks_are_keyed_by_device_kind_and_an_unknown_device_is_an_error():
    v5e = xplane.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks known"):
        xplane.peaks("TPU v9 imaginary")


# The seven program events of the recorded trace, (start_s, duration_s),
# read off the file once and summed by hand below.
RECORDED = [("jit_local_fn", 0.072201776, 0.024995722),
            ("jit_reshape", 0.097199154, 0.000000779),
            ("jit_compact_window", 0.097201494, 0.000002708),
            ("jit_local_fn", 0.097213041, 0.006147167),
            ("jit_reshape", 0.103361749, 0.000000679),
            ("jit_compact_window", 0.103363896, 0.000002699),
            ("jit_refresh", 0.152989274, 0.000051142)]
MARK = 0.050022817  # where the recorded "bench.mark" annotation begins


def test_the_recorded_trace_loads_with_its_programs_and_marker():
    trace = xplane.load(SAMPLE)
    assert trace["markers"] == {"bench.mark": pytest.approx(MARK, abs=1e-9)}
    assert [d["name"] for d in trace["devices"]] == ["/device:TPU:0"]
    programs = trace["devices"][0]["programs"]
    assert [(xplane.plain(n), pytest.approx(s, abs=1e-9),
             pytest.approx(d, abs=1e-9)) for n, s, d in programs] == RECORDED
    # The names carry the program's fingerprint as the trace gives it.
    assert programs[0][0] == "jit_local_fn(14240725742686897559)"


def test_busy_union_idle_share_and_program_sums_of_the_recorded_trace():
    trace = xplane.load(SAMPLE)
    # The harness's markers, put where a window would have ended: after the
    # two placement windows, before the node-table refresh.
    trace["markers"] = {"bench.trace_begin": MARK, "bench.window_end": 0.12}
    out = xplane.reduce(trace, window_s=0.122666151, in_window_s=0.07)
    # No two programs overlap, so the union is the plain sum: 31.200896 ms,
    # of which all but the refresh (51.142 us) lies inside the window.
    assert out["busy_s"] == pytest.approx(0.031200896, abs=1e-9)
    assert out["in_window_busy_s"] == pytest.approx(0.031149754, abs=1e-9)
    assert out["in_window_s"] == pytest.approx(0.12 - MARK)
    assert out["in_window_idle_share"] == pytest.approx(
        100.0 * (1 - 0.031149754 / 0.069977183), abs=1e-6)
    assert out["in_window_programs"] == [
        ("jit_local_fn", 2, pytest.approx(0.031142889, abs=1e-9)),
        ("jit_compact_window", 2, pytest.approx(0.000005407, abs=1e-9)),
        ("jit_reshape", 2, pytest.approx(0.000001458, abs=1e-9))]
    assert out["breakdown"]["device_ops"][0] == [
        "jit_local_fn", pytest.approx(0.031142889, abs=1e-9)]
    assert ["jit_refresh", pytest.approx(0.000051142, abs=1e-9)] in \
        out["breakdown"]["device_ops"]
    # The longest gap is the host's: between the last compaction and the
    # refresh that the next dispatch asked for; it straddles the marker
    # and is cut there. This trace predates the program's host spans
    # (PR 26), so the breakdown can name no stage: one row a phase, which
    # add up to the span less the busy union.
    gaps = dict(map(tuple, out["breakdown"]["idle_gaps"]))
    assert gaps == {
        "window:none": pytest.approx(0.069977183 - 0.031149754, abs=1e-9),
        "after_window:none": pytest.approx(
            0.122666151 - 0.069977183 - 0.000051142, abs=1e-9)}
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(0.122666151)
