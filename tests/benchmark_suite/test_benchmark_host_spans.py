"""The device's idle gaps put down to the host's spans
(benchmark/trace/host_gaps.py, benchmark/readers/host_spans.py): on
hand-made events, where the right answer is plain, and on the small trace
with host spans recorded on the chip that is kept beside the code
(sample_host.xplane.pb; sample_host.README says how it was made)."""

import json
import os

import pytest

from benchmark.readers import host_spans, worker_stats_opt
from benchmark.trace import host_gaps, xplane

HERE = os.path.dirname(os.path.abspath(host_gaps.__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SAMPLE = os.path.join(HERE, "sample_host.xplane.pb")
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
DISPATCH, PLANWAIT = "nomad.worker.dispatch", "nomad.worker.planwait"


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def _span(name, start, end, **attrs):
    return {"name": name, "start_s": start, "end_s": end, **attrs}


# The device runs a(1) 1.0-2.0 and b(2) 4.0-5.0 in a window of 0.0-8.0, so
# it is idle 0-1, 2-4 and 5-8: 6 of 8 seconds. Two workers' dispatch spans
# overlap each other and the programs; plan waits lie partly under them.
TRACE = {
    "devices": [{"name": "/device:TPU:0",
                 "programs": [("a(1)", 1.0, 1.0), ("b(2)", 4.0, 1.0),
                              ("late(3)", 9.0, 0.5)]}],
    "markers": {"bench.trace_begin": 0.0, "bench.window_end": 8.0},
    "spans": [
        _span(DISPATCH, 0.5, 1.5, worker="w0", window=1),   # idle 0.5-1.0
        _span(DISPATCH, 2.5, 3.5, worker="w0", window=2),   # idle 2.5-3.5
        _span(DISPATCH, 3.0, 4.5, worker="w1", window=1),   # idle 3.0-4.0
        _span(PLANWAIT, 2.0, 3.25, worker="w1", window=0),  # idle 2.0-3.25
        _span(PLANWAIT, 5.5, 7.0, worker="w0", window=2),   # idle 5.5-7.0
        _span("nomad.plan.apply", 5.75, 6.25),
        _span(DISPATCH, 8.5, 9.5, worker="w0", window=3),   # after the window
    ],
}


def test_interval_arithmetic():
    assert xplane.union([(3, 4), (1, 2), (1.5, 2.5), (5, 5)]) == [
        (1, 2.5), (3, 4)]
    assert xplane.intersect([(0, 2), (3, 6)], [(1, 4), (5, 7)]) == [
        (1, 2), (3, 4), (5, 6)]
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 5), (9, 12)]) == [
        (0, 1), (2, 4), (5, 9)]
    assert xplane.subtract([(0, 1), (2, 3)], [(0, 1)]) == [(2, 3)]
    assert xplane.length([(0, 1.5), (2, 2.25)]) == pytest.approx(1.75)


def test_idle_time_is_put_down_to_the_span_open_in_it():
    idle = xplane.reduce({k: TRACE[k] for k in ("devices", "markers")},
                         window_s=10.0, in_window_s=8.0)
    assert idle["in_window_idle_share"] == pytest.approx(100 * 6 / 8)
    # dispatch open and device idle: 0.5-1.0 and 2.5-4.0 (the union of two
    # workers' spans, not their sum) = 2.0 s of 8.
    dispatch = host_gaps.idle_share(TRACE, [DISPATCH])
    assert dispatch == pytest.approx(100 * 2.0 / 8)
    # plan wait open, device idle, no dispatch open: 2.0-2.5 and 5.5-7.0.
    planwait = host_gaps.idle_share(TRACE, [PLANWAIT], without=[DISPATCH])
    assert planwait == pytest.approx(100 * 2.0 / 8)
    # Without the exclusion the moment 2.5-3.25 would count twice.
    assert host_gaps.idle_share(TRACE, [PLANWAIT]) == pytest.approx(
        100 * 2.75 / 8)
    assert dispatch + planwait <= idle["in_window_idle_share"]
    # The remainder (0-0.5, 5-5.5, 7-8) had neither open.
    assert idle["in_window_idle_share"] - dispatch - planwait == \
        pytest.approx(100 * 2.0 / 8)


def test_the_gaps_table_names_the_spans_open_in_each_gap():
    table = host_gaps.gaps(TRACE)
    assert [(g["at_s"], g["gap_s"], g["before"], g["after"])
            for g in table] == [(5.0, 3.0, "b", "window_end"),
                                (2.0, 2.0, "a", "b"),
                                (0.0, 1.0, "trace_begin", "a")]
    longest = table[0]["open"]
    assert [(s["name"], s["covers_s"], s["worker"], s["window"])
            for s in longest] == [(PLANWAIT, 1.5, "w0", 2),
                                  ("nomad.plan.apply", 0.5, None, None)]
    middle = {(s["name"], s["worker"]): s["covers_s"]
              for s in table[1]["open"]}
    assert middle == {(PLANWAIT, "w1"): 1.25, (DISPATCH, "w0"): 1.0,
                      (DISPATCH, "w1"): 1.0}
    assert sum(g["gap_s"] for g in table) == pytest.approx(6.0)


def test_the_breakdown_sums_all_idle_time_by_phase_and_stage():
    """xplane.reduce's breakdown.idle_gaps (ISSUE 38): the traced span's
    idle seconds, all of them, by the phase they lay in and the stage the
    host had open, by one precedence: dispatch, else plan wait, else the
    other span that covers most of the gap, else none."""
    trace = dict(TRACE, spans=TRACE["spans"] + [
        _span("nomad.worker.lease", 0.0, 0.4),      # idle 0.0-0.5: most of
        _span("nomad.worker.drain", 0.35, 0.5),     # it lease (0.4 > 0.15)
        _span("nomad.plan.evaluate", 7.25, 7.5),    # idle 7.0-8.0
        _span("bench.device_read", 9.6, 10.5),      # idle 9.5-10.0
        _span("nomad.fsm.sweep", 7.0, 8.0)])        # not a stage that counts
    out = xplane.reduce(trace, window_s=10.0, in_window_s=8.0)
    rows = out["breakdown"]["idle_gaps"]
    assert rows == sorted(rows, key=lambda r: -r[1])
    assert dict(map(tuple, rows)) == {
        "window:" + DISPATCH: pytest.approx(2.0),   # 0.5-1.0, 2.5-4.0
        "window:" + PLANWAIT: pytest.approx(2.0),   # 2.0-2.5, 5.5-7.0
        "window:nomad.worker.lease": pytest.approx(0.5),
        "window:none": pytest.approx(0.5),          # 5.0-5.5
        # One gap, one name: the whole of 7.0-8.0 goes to the span that
        # covers most of it, not a quarter of it.
        "window:nomad.plan.evaluate": pytest.approx(1.0),
        # After the window: late(3) runs 9.0-9.5; a dispatch is open
        # 8.5-9.5, the read of check 6 from 9.6.
        "after_window:" + DISPATCH: pytest.approx(0.5),
        "after_window:none": pytest.approx(0.5),    # 8.0-8.5
        "after_window:bench.device_read": pytest.approx(0.5)}
    assert sum(s for _, s in rows) == pytest.approx(10.0 - out["busy_s"])
    # The two rows that have a metric of their own agree with it.
    assert 100 * 2.0 / 8 == pytest.approx(
        host_gaps.idle_share(trace, [DISPATCH]))
    assert 100 * 2.0 / 8 == pytest.approx(
        host_gaps.idle_share(trace, [PLANWAIT], without=[DISPATCH]))


def test_more_pairs_than_rows_are_folded_and_the_sum_holds():
    # Twelve stages, each alone in a gap of its own length, in a window
    # the device sits out, and the idle second after it: thirteen pairs.
    # The eight longest keep their names, the rest are one "other" row a
    # phase (here one phase: nine rows).
    spans = [_span(f"nomad.worker.s{i:02d}", float(i), i + 0.25 + i * 0.05)
             for i in range(12)]
    programs = [(f"p({i})", i + 0.25 + i * 0.05,
                 0.75 - i * 0.05) for i in range(12)]
    trace = {"devices": [{"name": "/device:TPU:0", "programs": programs}],
             "markers": {"bench.trace_begin": 0.0, "bench.window_end": 12.0},
             "spans": spans}
    out = xplane.reduce(trace, window_s=13.0, in_window_s=12.0)
    rows = out["breakdown"]["idle_gaps"]
    assert len(rows) == 9 and xplane.TOP == 10
    names = [n for n, _ in rows]
    assert names[:2] == ["window:other", "after_window:none"]
    assert "window:nomad.worker.s11" in names
    assert "window:nomad.worker.s00" not in names  # the shortest: folded
    assert dict(map(tuple, rows))["window:other"] == pytest.approx(
        sum(0.25 + i * 0.05 for i in range(5)))
    assert sum(s for _, s in rows) == pytest.approx(13.0 - out["busy_s"])


def test_no_span_reads_zero_and_no_device_plane_reads_nothing():
    bare = dict(TRACE, spans=[])  # a program from before ISSUE 26
    assert host_gaps.idle_share(bare, [DISPATCH]) == 0.0
    assert all(g["open"] == [] for g in host_gaps.gaps(bare))
    rehearsal = dict(TRACE, devices=[])
    assert host_gaps.idle_share(rehearsal, [DISPATCH]) is None
    assert host_gaps.gaps(rehearsal) is None
    unmarked = dict(TRACE, markers={"bench.trace_begin": 0.0})
    assert host_gaps.idle_share(unmarked, [DISPATCH]) is None


def test_the_reader_reads_nothing_in_a_rehearsal():
    assert host_spans.read({"device": None}, [DISPATCH]) is None
    assert host_spans.read({"device": None, "trace": None},
                           [DISPATCH]) is None


def test_the_reader_reads_the_trace_the_harness_parsed():
    # run["trace"] is what instruments.Window parsed to reduce the run:
    # the reader opens no file of its own.
    run = {"device": {}, "trace": TRACE}
    assert host_spans.read(run, [DISPATCH]) == pytest.approx(
        host_gaps.idle_share(TRACE, [DISPATCH]))
    assert host_spans.read(run, [PLANWAIT], without=[DISPATCH]) \
        == pytest.approx(host_gaps.idle_share(TRACE, [PLANWAIT],
                                              without=[DISPATCH]))
    assert not hasattr(host_spans, "_trace")
    assert not hasattr(host_gaps, "load")  # xplane.load is the one parser


def test_a_stats_key_the_program_lacks_is_left_out_not_raised():
    run = {"stats": {"t_planwait_ms": 30.0, "windows": 3, "fast": 10,
                     "slow": 5}, "ops": [1, 2]}
    assert worker_stats_opt.read(run, "t_fill_ms", per=["fast", "slow"]) \
        is None
    assert worker_stats_opt.read(run, "t_planwait_ms", per="t_new") is None
    assert worker_stats_opt.read(run, "t_planwait_ms", per="windows") == 10.0
    assert worker_stats_opt.read(run, "t_planwait_ms",
                                 per=["fast", "slow"]) == 2.0
    assert worker_stats_opt.read(run, "t_planwait_ms", per="ops") == 15.0


NEW = ["broker_wait_ms.trickle", "broker_wait_ms.rollout", "fill_ms.trickle",
       "stage_wait_ms.storm", "stage_wait_ms.trickle", "plan_queue_ms.storm",
       "plan_queue_ms.rollout", "device_idle.dispatch.storm",
       "device_idle.planwait.storm"]


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_declared_for_its_cell_and_layer(name):
    declared(BENCH, name)


def declared(bench, name):
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    cell = {"storm": "svc-10k.storm", "trickle": "svc-10k.trickle",
            "rollout": "sys-10k.rollout"}[name.rsplit(".", 1)[1]]
    assert entry["workloads"][0] == cell and entry["better"] == "lower"
    # Every other name is a cell of the same traffic family: one that the
    # same generator drives and that reports the end-to-end metric the
    # entry moves (ISSUE 38 put the four .storm entries on all four storm
    # cells).
    moved = next(m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    traffic = {w["name"]: _json("benchmark", "traffic",
                                w["traffic"] + ".json")
               for w in bench["workloads"]}
    for other in entry["workloads"][1:]:
        assert other in moved["workloads"]
        assert traffic[other]["generator"] == traffic[cell]["generator"]
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    assert entry["layer"] in layers  # a layer the benchmark already names
    device = name.startswith("device_idle.")
    assert entry["source"] == ("device_trace" if device else "program_span")
    assert entry["unit"] == ("%" if device else "ms")
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == ("host_spans" if device else
                              "sink_mean" if "sample" in spec["args"]
                              else "worker_stats_opt")


# -------------------------------------------------- the recorded trace
# Eight windows of the keyed placement program in 0.45 s after
# bench.trace_begin (sample_host.README). The harness's window_end marker
# is put where a window could have ended: at 0.40 s, before the seventh.
BEGIN = 0.04444093
CUT = BEGIN + 0.40


@pytest.fixture(scope="module")
def recorded():
    trace = xplane.load(SAMPLE)
    assert trace["markers"] == {"bench.trace_begin": pytest.approx(BEGIN)}
    trace["markers"]["bench.window_end"] = CUT
    return trace


def test_the_recorded_trace_holds_device_programs_and_host_spans(recorded):
    assert [d["name"] for d in recorded["devices"]] == ["/device:TPU:0"]
    programs = recorded["devices"][0]["programs"]
    assert len(programs) == 27
    assert programs[0][0] == "jit_place_batch_keyed(14240725742686897559)"
    names = {s["name"] for s in recorded["spans"]}
    assert len(recorded["spans"]) == 522
    for stage in ("lease", "fill", "dispatch", "refresh", "launch",
                  "drain_stack", "drain", "drain_fetch", "build", "collect",
                  "planwait", "evalupd", "wait_for_index"):
        assert "nomad.worker." + stage in names
    assert {"nomad.plan.evaluate", "nomad.plan.apply", "nomad.fsm.sweep",
            "nomad.fsm.register_job", "nomad.fsm.update_eval"} <= names
    stages = [s for s in recorded["spans"]
              if s["name"] == "nomad.worker.dispatch"]
    assert {s["worker"] for s in stages} == {"worker-0", "worker-1"}
    assert all(isinstance(s["window"], int) for s in stages)


def test_known_gaps_of_the_recorded_trace(recorded):
    table = host_gaps.gaps(recorded)
    assert len(table) == 10
    # The longest: from the end of the fifth window's compaction
    # (0.246599160 + 0.000002690 after the trace began) to the start of
    # the sixth window's program (0.361745580).
    top = table[0]
    assert top["at_s"] == pytest.approx(0.24660185, abs=1e-8)
    assert top["gap_s"] == pytest.approx(0.36174558 - 0.24660185, abs=1e-8)
    assert (top["before"], top["after"]) == ("jit_compact_window",
                                             "jit_place_batch_keyed")
    # What the host was doing in it, read off the span list by hand:
    # worker-0's window 168 waited on the applier 0.262138-0.328882,
    # worker-1's window 174 was in dispatch from 0.303201 to past the gap's
    # end, worker-1's windows 170 and 169 waited 0.284168-0.335774 and
    # until 0.278585.
    assert [(s["name"], round(s["covers_s"], 4), s["worker"], s["window"])
            for s in top["open"][:4]] == [
        (PLANWAIT, 0.0667, "worker-0", 168),
        (DISPATCH, 0.0585, "worker-1", 174),
        (PLANWAIT, 0.0516, "worker-1", 170),
        (PLANWAIT, 0.0320, "worker-1", 169)]
    second = table[1]
    assert second["gap_s"] == pytest.approx(0.050098248, abs=1e-8)
    assert second["open"][0]["name"] == "nomad.worker.lease"
    whole = host_gaps.gaps(recorded, top=100)
    assert sum(g["gap_s"] for g in whole) == pytest.approx(
        0.40 - 0.136653349, abs=1e-8)


def test_known_coverage_of_the_recorded_trace(recorded):
    idle = xplane.reduce({k: recorded[k] for k in ("devices", "markers")},
                         window_s=0.45, in_window_s=0.40)
    assert idle["in_window_idle_share"] == pytest.approx(65.83666, abs=1e-4)
    # Checked once against a count on a 1 us grid (47.650, 14.632, 38.793).
    dispatch = host_gaps.idle_share(recorded, [DISPATCH])
    planwait = host_gaps.idle_share(recorded, [PLANWAIT], without=[DISPATCH])
    assert dispatch == pytest.approx(47.64973, abs=1e-4)
    assert planwait == pytest.approx(14.63233, abs=1e-4)
    assert host_gaps.idle_share(recorded, [PLANWAIT]) == pytest.approx(
        38.79349, abs=1e-4)
    assert dispatch + planwait <= idle["in_window_idle_share"]


def test_the_breakdown_of_the_recorded_trace(recorded):
    out = xplane.reduce(recorded, window_s=0.45, in_window_s=0.40)
    rows = out["breakdown"]["idle_gaps"]
    assert 2 <= len(rows) <= xplane.TOP
    assert all(n.split(":", 1)[0] in ("window", "after_window")
               for n, _ in rows)
    # Its seconds are the span's idle seconds, all of them.
    assert sum(s for _, s in rows) == pytest.approx(0.45 - out["busy_s"],
                                                    abs=1e-9)
    by_name = dict(map(tuple, rows))
    # The rows for dispatch and plan wait are what the two metrics that
    # stand read from the same file, to the microsecond.
    assert 100 * by_name["window:" + DISPATCH] / 0.40 == pytest.approx(
        host_gaps.idle_share(recorded, [DISPATCH]), abs=1e-6)
    assert 100 * by_name["window:" + PLANWAIT] / 0.40 == pytest.approx(
        host_gaps.idle_share(recorded, [PLANWAIT], without=[DISPATCH]),
        abs=1e-6)
    assert rows[0][0] == "window:" + DISPATCH
    assert rows[0][1] == pytest.approx(0.190598935, abs=1e-8)
    in_window = sum(s for n, s in rows if n.startswith("window:"))
    assert 100 * in_window / 0.40 == pytest.approx(
        out["in_window_idle_share"], abs=1e-6)
    # Without the spans (a program from before PR 26) the same reduction
    # names no stage.
    bare = xplane.reduce(dict(recorded, spans=[]), 0.45, 0.40)
    assert {n for n, _ in bare["breakdown"]["idle_gaps"]} == {
        "window:none", "after_window:none"}
    assert bare["breakdown"]["device_ops"] == out["breakdown"]["device_ops"]


def test_the_device_read_is_a_marker_and_a_span(recorded):
    # xplane.load keeps the harness's bench.device_read as a span too
    # (the recorded file was cut before one): nothing else named bench.*.
    assert not [s for s in recorded["spans"]
                if s["name"].startswith("bench.")]
    assert xplane.DEVICE_READ == "bench.device_read"
    assert xplane.STAGE_FIRST == (DISPATCH, PLANWAIT)


def test_the_tool_prints_the_table_or_says_what_the_file_lacks(capsys):
    # As recorded the file has no window_end marker: the tool says so.
    assert host_gaps.main([SAMPLE]) == 1
    assert "markers" in capsys.readouterr().err
