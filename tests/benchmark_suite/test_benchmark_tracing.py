"""The per-layer metrics ISSUE 39 appended behind PR 38's 62: the two seams
of the window worker and the blocked hand-offs, the plan wait's parts, thread
CPU beside the stages' wall, and the runtime's own readings (stalls, CPU
share, full collections), then four stage timers that had a stats key and no
metric. Each stands where the issue put it, is a data file over a reader and
arguments that exist, reads its value from made-up numbers and reads nothing
on a run of a program that lacks what this PR adds (the parent's side). The
one new reader, sink_stat, on hand-made runs."""

import importlib
import inspect
import json
import os

import pytest

from benchmark import cells
from benchmark.readers import sink_stat
from nomad_tpu.server.pipelined_worker import (CPU_STAGES, STATS_COUNTERS,
                                               STATS_TIMERS_MS)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
STORMS = ["svc-10k.storm", "dc-50k.storm", "c1m-5k.fill", "web-10k.storm"]
FIRST = {"storm": STORMS, "trickle": ["svc-10k.trickle"],
         "rollout": ["sys-10k.rollout"]}
WORKER = "Window worker: server/pipelined_worker.py"
DEVICE = "Device: TPU v5e"
APPLY = "Plan apply: server/plan_apply.py, fsm.py, state/"
INTERP = "Interpreter: host process"
SWEEP = "System sweep: scheduler/system_sweep.py"

# What the program has had since before this PR: a parent's run has these.
OLD_STATS = {"windows": 4, "t_lease_ms": 80.0, "t_fill_ms": 4.0,
             "t_dispatch_ms": 200.0, "t_drain_ms": 8.0, "t_build_ms": 72.0,
             "t_planwait_ms": 300.0, "t_evalupd_ms": 12.0, "t_slow_ms": 0.0,
             "t_stagewait_ms": 100.0, "t_diff_ms": 20.0,
             "t_drain_stack_ms": 6.0}
OLD_SAMPLES = {"nomad.plan.evaluate": [0.5, 1.0, 1.5],
               "nomad.plan.apply": [8.0, 12.0],
               "nomad.sched.system.sweep": [70.0]}
# ... and what ISSUE 39 adds to it.
NEW_STATS = {"t_wait_drain_ms": 60.0, "t_wait_build_ms": 40.0,
             "t_handoff_drain_ms": 28.0, "t_handoff_build_ms": 2.0,
             "t_turnwait_ms": 1.0, "t_lease_cpu_ms": 1.0, "t_fill_cpu_ms": 2.0,
             "t_dispatch_cpu_ms": 120.0, "t_drain_cpu_ms": 3.0,
             "t_build_cpu_ms": 36.0, "t_planwait_cpu_ms": 2.0,
             "t_evalupd_cpu_ms": 5.0, "t_slow_cpu_ms": 0.0}
NEW_SAMPLES = {"nomad.plan.evaluate.cpu": [0.25, 0.75],
               "nomad.plan.apply.cpu": [4.0, 6.0],
               "nomad.sched.system.sweep.cpu": [50.0],
               "nomad.plan.join": [0.0, 3.0], "nomad.plan.wake": [2.0, 4.0],
               "nomad.runtime.tick_late": [4.0, 210.0, 6.0],
               "nomad.runtime.cpu_share": [110.0, 130.0],
               "nomad.runtime.gc": [100.0, 150.0]}
OPS = [object()] * 5


def _run(stats, samples):
    return {"stats": stats, "trace_stats": stats, "ops": OPS,
            "samples": samples, "counters": {}, "gc": [], "trace": None}


CHANGE = _run({**OLD_STATS, **NEW_STATS}, {**OLD_SAMPLES, **NEW_SAMPLES})
PARENT = _run(OLD_STATS, OLD_SAMPLES)

# name: (unit, better, source, layer, moves, reader, value on CHANGE,
#        value on PARENT). A device_idle.* metric reads the device's
# timeline, which a made-up run has none of: nothing on either side.
_MS = ("ms", "lower", "program_span")
NEW = {
    "drain_wait_ms.storm": (*_MS, WORKER, "placed_per_s",
                            "worker_stats_opt", 15.0, None),
    "build_wait_ms.storm": (*_MS, WORKER, "placed_per_s",
                            "worker_stats_opt", 10.0, None),
    "handoff_drain_ms.storm": (*_MS, WORKER, "placed_per_s",
                               "worker_stats_opt", 7.0, None),
    "handoff_build_ms.storm": (*_MS, WORKER, "placed_per_s",
                               "worker_stats_opt", 0.5, None),
    "device_idle.handoff.storm": ("%", "lower", "device_trace", DEVICE,
                                  "placed_per_s", "host_spans", None, None),
    "turn_wait_ms.storm": (*_MS, WORKER, "placed_per_s", "worker_stats_opt",
                           0.25, None),
    # The sample exists today: the one metric of the tentpole that a
    # parent's line carries too.
    "plan_verify_ms.storm": (*_MS, APPLY, "placed_per_s", "sink_mean",
                             1.0, 1.0),
    "plan_verify_ms.rollout": (*_MS, APPLY, "rollout_mean_ms", "sink_mean",
                               1.0, 1.0),
    "plan_join_ms.storm": (*_MS, APPLY, "placed_per_s", "sink_mean",
                           1.5, None),
    "plan_wake_ms.storm": (*_MS, APPLY, "placed_per_s", "sink_mean",
                           3.0, None),
    "plan_wake_ms.trickle": (*_MS, APPLY, "eval_p50_ms", "sink_mean",
                             3.0, None),
    "dispatch_cpu_ms.storm": (*_MS, WORKER, "placed_per_s",
                              "worker_stats_opt", 30.0, None),
    "build_cpu_ms.storm": (*_MS, WORKER, "placed_per_s", "worker_stats_opt",
                           9.0, None),
    "planwait_cpu_ms.storm": (*_MS, WORKER, "placed_per_s",
                              "worker_stats_opt", 0.5, None),
    "stage_cpu_share.storm": ("%", "higher", "program_span", INTERP,
                              "placed_per_s", "worker_stats_opt", 25.0,
                              None),
    "verify_cpu_ms.storm": (*_MS, APPLY, "placed_per_s", "sink_mean",
                            0.5, None),
    "apply_cpu_ms.storm": (*_MS, APPLY, "placed_per_s", "sink_mean",
                           5.0, None),
    "apply_cpu_ms.rollout": (*_MS, APPLY, "rollout_mean_ms", "sink_mean",
                             5.0, None),
    "sweep_cpu_ms.rollout": (*_MS, SWEEP, "rollout_mean_ms", "sink_mean",
                             50.0, None),
    "host_cpu_share.storm": ("%", "higher", "program_counter", INTERP,
                             "placed_per_s", "sink_mean", 120.0, None),
    "host_cpu_share.trickle": ("%", "lower", "program_counter", INTERP,
                               "eval_mean_ms", "sink_mean", 120.0, None),
    "host_cpu_share.rollout": ("%", "lower", "program_counter", INTERP,
                               "rollout_mean_ms", "sink_mean", 120.0, None),
    "stall_max_ms.storm": ("ms", "lower", "program_counter", INTERP,
                           "placed_per_s", "sink_stat", 210.0, None),
    "stall_max_ms.trickle": ("ms", "lower", "program_counter", INTERP,
                             "eval_mean_ms", "sink_stat", 210.0, None),
    "stall_max_ms.rollout": ("ms", "lower", "program_counter", INTERP,
                             "rollout_mean_ms", "sink_stat", 210.0, None),
    "gc_pause_ms.storm": (*_MS, INTERP, "placed_per_s", "sink_stat",
                          50.0, None),
    "gc_pause_ms.trickle": (*_MS, INTERP, "eval_mean_ms", "sink_stat",
                            50.0, None),
    "gc_pause_ms.rollout": (*_MS, INTERP, "rollout_mean_ms", "sink_stat",
                            50.0, None),
    "device_idle.gc.storm": ("%", "lower", "device_trace", DEVICE,
                             "placed_per_s", "host_spans", None, None),
    # The satellites: stage timers the program has had all along.
    "evalupd_ms.storm": (*_MS, WORKER, "placed_per_s", "worker_stats_opt",
                         3.0, 3.0),
    "lease_ms.storm": (*_MS, WORKER, "placed_per_s", "worker_stats_opt",
                       20.0, 20.0),
    "diff_ms.storm": (*_MS, WORKER, "placed_per_s", "worker_stats_opt",
                      5.0, 5.0),
    "drain_stack_ms.storm": (*_MS, WORKER, "placed_per_s",
                             "worker_stats_opt", 1.5, 1.5),
}
PR38 = 62


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _read(name, run):
    spec = _spec(name)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(run, **spec["args"])


def test_they_stand_behind_pr_38s_entries_in_the_issues_order():
    assert [m["name"] for m in BENCH["per_layer"][PR38:PR38 + len(NEW)]] \
        == list(NEW)


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_declared_where_the_issue_put_it(name):
    unit, better, source, layer, moves, _, _, _ = NEW[name]
    entry = BENCH["per_layer"][PR38 + list(NEW).index(name)]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": entry["workloads"]}
    # The cells the issue listed come first; a later cell of the family is
    # appended behind them.
    first = FIRST[name.rsplit(".", 1)[1]]
    assert entry["workloads"][:len(first)] == first
    # A layer the benchmark named before, letter for letter.
    assert layer in {m["layer"] for m in BENCH["per_layer"][:PR38]}


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_a_data_file_over_a_reader_and_arguments_that_exist(
        name):
    spec = _spec(name)
    assert set(spec) == {"what", "reader", "args"} and spec["what"]
    assert spec["reader"] == NEW[name][5]
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    accepted = set(inspect.signature(reader.read).parameters) - {"run"}
    assert set(spec["args"]) <= accepted
    # A stats key it names is one the worker declares; a sample it names
    # is one this directory made up above, i.e. one the program emits.
    for arg in ("num", "per"):
        keys = spec["args"].get(arg, [])
        for key in [keys] if isinstance(keys, str) else keys:
            assert key in STATS_COUNTERS + STATS_TIMERS_MS or key == "ops"
    for arg in ("sample", "beside"):
        if arg in spec["args"]:
            assert spec["args"][arg] in {**OLD_SAMPLES, **NEW_SAMPLES}
    for span in spec["args"].get("spans", []) + spec["args"].get(
            "without", []):
        assert span in ("nomad.worker.handoff_drain", "nomad.worker.dispatch",
                        "nomad.runtime.gc")


def test_the_only_new_reader_is_sink_stat():
    named = {_spec(name)["reader"] for name in NEW}
    assert named == {"worker_stats_opt", "sink_mean", "sink_stat",
                     "host_spans"}


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_reads_its_value_from_made_up_numbers(name):
    value = NEW[name][6]
    if value is None:
        assert _read(name, CHANGE) is None  # no trace in a made-up run
    else:
        assert _read(name, CHANGE) == pytest.approx(value)


@pytest.mark.parametrize("name", NEW)
def test_on_the_parents_side_it_reads_nothing_and_breaks_nothing(name):
    """The driver lays these files over the parent's checkout too: where
    the program lacks the key or the sample, the metric is left out of
    the line. Three samples and four stats keys are older than this PR,
    and the metrics over them read on both sides."""
    value = NEW[name][7]
    entry = BENCH["per_layer"][PR38 + list(NEW).index(name)]
    cell = cells.Cell(name=entry["workloads"][0], chips=1, config={},
                      traffic={}, benchmark={"per_layer": [entry]})
    line = cells.read_metrics(cell, "per_layer", PARENT)
    if value is None:
        assert _read(name, PARENT) is None and line == {}
    else:
        assert line == {name: {"value": pytest.approx(value),
                               "unit": entry["unit"]}}


def test_the_cpu_share_is_over_the_eight_stages_that_keep_their_cpu():
    args = _spec("stage_cpu_share.storm")["args"]
    assert args["num"] == [f"t_{s}_cpu_ms" for s in CPU_STAGES]
    assert args["per"] == [f"t_{s}_ms" for s in CPU_STAGES]
    assert args["scale"] == 100.0


def test_the_idle_split_reads_the_hand_off_beside_the_plan_wait():
    """Both take the dispatch spans out again and neither takes out the
    other: a blocked hand-off and a plan wait are open at once, on two
    threads, so the two shares are read side by side and never added."""
    handoff = _spec("device_idle.handoff.storm")["args"]
    planwait = _spec("device_idle.planwait.storm")["args"]
    assert handoff == {"spans": ["nomad.worker.handoff_drain"],
                       "without": ["nomad.worker.dispatch"]}
    assert planwait["without"] == handoff["without"]
    assert _spec("device_idle.gc.storm")["args"] == {
        "spans": ["nomad.runtime.gc"]}


# ------------------------------------------------------------- sink_stat
LATE = "nomad.runtime.tick_late"


@pytest.mark.parametrize("stat,value", [("mean", 4.0), ("max", 9.0),
                                        ("p50", 2.0), ("p95", 9.0),
                                        ("sum", 12.0)])
def test_sink_stat_reads_each_statistic(stat, value):
    run = _run({}, {LATE: [9.0, 1.0, 2.0]})
    assert sink_stat.read(run, LATE, stat) == pytest.approx(value)


def test_sink_stat_divides_by_the_windows_operations():
    run = _run({}, {LATE: [9.0, 1.0, 2.0]})
    assert sink_stat.read(run, LATE, "sum", per="ops") == pytest.approx(2.4)
    assert sink_stat.read(run, LATE, "max", per="ops") == pytest.approx(1.8)
    run["ops"] = []
    assert sink_stat.read(run, LATE, "sum", per="ops") is None


@pytest.mark.parametrize("stat", ["mean", "max", "p95", "sum"])
def test_sink_stat_reads_nothing_where_there_is_no_sample(stat):
    assert sink_stat.read(_run({}, {}), LATE, stat) is None
    assert sink_stat.read(_run({}, {LATE: []}), LATE, stat) is None
    assert sink_stat.read(_run({}, {}), LATE, stat, per="ops") is None


def test_sink_stat_sums_to_zero_beside_a_sample_of_the_same_emitter():
    """A window into which no full collection fell, on a program whose
    collector ran (its once-a-second sample is there): 0.0, so that every
    listed cell prints gc_pause_ms. Without that sample (the parent), and
    for every statistic that is not a sum, nothing."""
    quiet = _run({}, {"nomad.runtime.cpu_share": [101.0]})
    assert sink_stat.read(quiet, "nomad.runtime.gc", "sum", per="ops",
                          beside="nomad.runtime.cpu_share") == 0.0
    assert sink_stat.read(quiet, "nomad.runtime.gc", "max",
                          beside="nomad.runtime.cpu_share") is None
    assert sink_stat.read(_run({}, {}), "nomad.runtime.gc", "sum",
                          per="ops",
                          beside="nomad.runtime.cpu_share") is None
    assert _read("gc_pause_ms.trickle", quiet) == 0.0
    with pytest.raises(ValueError):
        sink_stat.read(_run({}, {LATE: [1.0]}), LATE, "median")
