"""The ten per-layer metrics ISSUE 38 appended for the counters of PRs
33-37, each read through its own file from made-up stats and counters (a
value, 0.0 on a rightly empty denominator, left out where the program
lacks the key), and the registry-counter reader against the harness's sink
fed inside and outside the window."""

import importlib
import json
import os

import pytest

from benchmark import cells, instruments
from benchmark.readers import counters, stats_unused_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
STORMS = ["svc-10k.storm", "dc-50k.storm", "c1m-5k.fill", "web-10k.storm"]
KERNELS = "Kernels: scheduler/kernels.py"
STACK = "Stack: scheduler/stack.py"
APPLY = "Plan apply: server/plan_apply.py, fsm.py, state/"
EXACT, VECTOR = ("nomad.plan.verify.exact_nodes",
                 "nomad.plan.verify.vector_nodes")
PORTS, ROW_FOLDS = "nomad.plan.partial.ports", "nomad.fsm.digest.row_folds"

# name: (index, unit, better, source, layer, reader,
#        run that reads a value, the value,
#        run with an empty denominator (reads 0.0),
#        run of a program or a run without the key (left out))
WEB = {"windows": 4, "launches": 128, "launch_steps": 2048,
       "launch_placements": 1280, "launch_resident": 128, "plan_rows": 1270,
       "plans_columnar": 0, "plans_objects": 127, "t_netassign_ms": 172.0,
       "netidx_builds": 1280, "collect_exact": 128}
IDLE = dict.fromkeys(WEB, 0)
IDLE["windows"] = 3


def _stats(stats, **more):
    return {"stats": stats, "trace_stats": stats, "ops": [], "samples": {},
            "counters": {}, **more}


def _without(key):
    return _stats({k: v for k, v in WEB.items() if k != key})


def _counted(**sums):
    return _stats(WEB, counters=sums)


NEW = {
    "replay_steps_per_window.storm": (
        52, "count", "lower", "program_counter", KERNELS, "worker_stats_opt",
        _stats(WEB), 512.0, None, _without("launch_steps")),
    "replay_pad_share.storm": (
        53, "%", "lower", "program_counter", KERNELS, "stats_unused_share",
        _stats(WEB), 37.5, _stats(IDLE), _without("launch_placements")),
    "resident_launch_share.storm": (
        54, "%", "higher", "program_counter", KERNELS, "worker_stats_zero",
        _stats(WEB), 100.0, _stats(IDLE), _without("launch_resident")),
    "rows_per_plan.storm": (
        55, "count", "higher", "program_counter", APPLY, "worker_stats_zero",
        _stats(WEB), 10.0, _stats(IDLE), _without("plan_rows")),
    "netassign_ms.storm": (
        56, "ms", "lower", "program_span", STACK, "worker_stats_opt",
        _stats(WEB), 43.0, None, _without("t_netassign_ms")),
    "netidx_builds_per_eval.storm": (
        57, "count", "lower", "program_counter", STACK, "worker_stats_zero",
        _stats(WEB), 10.0, _stats(IDLE), _without("netidx_builds")),
    "verify_exact_share.storm": (
        58, "%", "lower", "program_counter", APPLY, "counters",
        _counted(**{EXACT: 30.0, VECTOR: 90.0}), 25.0, _counted(),
        _stats(WEB, counters=None)),
    "port_partial_share.storm": (
        59, "%", "lower", "program_counter", APPLY, "counters",
        _counted(**{EXACT: 1250.0, PORTS: 5.0}), 0.4,
        _counted(**{VECTOR: 90.0}), _stats(WEB, counters=None)),
    "digest_row_folds.storm": (
        60, "count", "lower", "program_counter", APPLY, "counters",
        _counted(**{ROW_FOLDS: 7.0}), 7.0, _counted(**{EXACT: 3.0}),
        _stats(WEB, counters=None)),
    "digest_row_folds.rollout": (
        61, "count", "lower", "program_counter", APPLY, "counters",
        _counted(**{ROW_FOLDS: 7.0}), 7.0, _counted(),
        _stats(WEB, counters=None)),
}


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _read(name, run):
    spec = _spec(name)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(run, **spec["args"])


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_is_declared_where_the_issue_put_it(name):
    declared(BENCH, name)


def declared(bench, name):
    index, unit, better, source, layer, reader = NEW[name][:6]
    entry = bench["per_layer"][index]
    rollout = name.endswith(".rollout")
    assert entry == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer,
        "moves": "rollout_mean_ms" if rollout else "placed_per_s",
        "workloads": entry["workloads"]}
    # The cells the issue listed come first; a later cell of the family is
    # appended behind them.
    first = ["sys-10k.rollout"] if rollout else STORMS
    assert entry["workloads"][:len(first)] == first
    # A layer the benchmark named before, letter for letter.
    assert layer in {m["layer"] for m in bench["per_layer"][:52]}
    assert _spec(name)["reader"] == reader


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_reads_its_value_from_made_up_numbers(name):
    run, value = NEW[name][6:8]
    assert _read(name, run) == pytest.approx(value)


@pytest.mark.parametrize("name", [n for n in NEW if NEW[n][8] is not None])
def test_a_rightly_empty_denominator_reads_zero_not_nothing(name):
    # A rehearsal launches nothing, a cell without a network sends no node
    # through the exact fit, a counter that never moved was never sent to
    # the sink: a float all the same, so that every listed cell prints the
    # metric (test_benchmark_rehearsal wants the printed set to be the
    # listed set).
    value = _read(name, NEW[name][8])
    assert value == 0.0 and isinstance(value, float)


@pytest.mark.parametrize("name", NEW)
def test_a_missing_key_leaves_the_metric_out_of_the_line(name):
    # The program lacks the stats key (a parent commit from before the PR
    # that added it), or the run had no counter sink (untraced).
    assert _read(name, NEW[name][9]) is None
    # The harness prints what it could read and leaves the rest out.
    entry = BENCH["per_layer"][NEW[name][0]]
    cell = cells.Cell(name=entry["workloads"][0], chips=1, config={},
                      traffic={}, benchmark={"per_layer": [entry]})
    assert cells.read_metrics(cell, "per_layer", NEW[name][9]) == {}
    value = NEW[name][7]
    assert cells.read_metrics(cell, "per_layer", NEW[name][6]) == {
        name: {"value": pytest.approx(value), "unit": entry["unit"]}}


def test_the_two_steps_a_window_ratios_are_left_out_without_a_window():
    # worker_stats_opt has no 0.0 for an empty denominator: a span in
    # which no window was dispatched has no steps a window to speak of.
    for name in ("replay_steps_per_window.storm", "netassign_ms.storm"):
        assert _read(name, _stats(dict(WEB, windows=0))) is None


def test_the_unused_share_is_the_complement_of_a_ratio():
    run = _stats({"used": 3000, "of": 4096})
    assert stats_unused_share.read(run, "used", "of") == pytest.approx(
        100 * (1 - 3000 / 4096))
    assert stats_unused_share.read(_stats({"used": 0, "of": 0}),
                                   "used", "of") == 0.0
    assert stats_unused_share.read(_stats({"of": 4096}), "used", "of") is None
    assert stats_unused_share.read(_stats({"used": 1}), "used", "of") is None


def test_the_counter_reader_sums_ratios_and_scales():
    run = {"counters": {"a.b": 3.0, "a.c": 1.0, "d": 8.0}}
    assert counters.read(run, "a.b") == 3.0
    assert counters.read(run, ["a.b", "a.c"]) == 4.0
    assert counters.read(run, "a.b", per=["a.b", "a.c"], scale=100.0) == 75.0
    assert counters.read(run, "never", per="d") == 0.0   # never incremented
    assert counters.read(run, "a.b", per="never") == 0.0  # nothing to share
    assert counters.read({"counters": None}, "a.b") is None
    assert counters.read({}, "a.b") is None  # a run record without the key


# ------------------------------------------- the sink and the window
class _Dep:
    def worker_stats(self):
        return {"windows": 0}


def test_the_sink_keeps_counter_rows_and_the_window_sums_its_own(monkeypatch):
    now = [0.0]
    monkeypatch.setattr(instruments.time, "perf_counter", lambda: now[0])
    sink = instruments.SampleSink()
    feed = [(1.0, ("nomad", "plan", "verify", "exact_nodes"), 10.0),  # before
            (2.0, ("nomad", "plan", "verify", "exact_nodes"), 10.0),  # opens
            (3.0, ("nomad", "plan", "verify", "vector_nodes"), 50.0),
            (4.0, ("nomad", "plan", "verify", "exact_nodes"), 5.0),
            (5.0, ("nomad", "plan", "partial", "ports"), 1.0),        # closes
            (5.5, ("nomad", "plan", "partial", "ports"), 1.0),        # drain
            (6.0, ("nomad", "fsm", "digest", "row_folds"), 99.0)]
    for t, key, step in feed:
        now[0] = t
        sink.incr_counter(key, step)
    now[0] = 2.5
    sink.add_sample(("nomad", "plan", "apply"), 7.0)
    assert sink.counters[0] == (1.0, EXACT, 10.0) and len(sink.counters) == 7
    assert sink.rows == [(2.5, "nomad.plan.apply", 7.0)]  # timers apart

    probe = instruments.Window(_Dep(), compiles=None, traced=True,
                               trace_dir="unused", trace_seconds=3,
                               on_chip=False)
    assert probe.counters() is None  # no sink yet: nothing to read
    probe._sink, probe.t0, probe.t1 = sink, 2.0, 5.0
    assert probe.counters() == {EXACT: 15.0, VECTOR: 50.0, PORTS: 1.0}
    assert probe.samples() == {"nomad.plan.apply": [7.0]}
    run = {"counters": probe.counters()}
    assert _read("verify_exact_share.storm", run) == pytest.approx(
        100 * 15 / 65)
    assert _read("port_partial_share.storm", run) == pytest.approx(
        100 * 1 / 15)
    assert _read("digest_row_folds.storm", run) == 0.0  # it came after t1
    # A traced run in which no counter moved reads zeros, not nothing.
    probe._sink = instruments.SampleSink()
    assert probe.counters() == {}
    assert _read("digest_row_folds.rollout",
                 {"counters": probe.counters()}) == 0.0


def test_the_registry_fans_counters_out_to_the_sink():
    from nomad_tpu.telemetry import metrics

    sink = instruments.SampleSink()
    registry = metrics.MetricsRegistry()
    registry.add_sink(sink)
    registry.incr_counter(("nomad", "plan", "partial", "ports"))
    registry.incr_counter(("nomad", "fsm", "digest", "row_folds"), 3)
    assert [(name, step) for _, name, step in sink.counters] == [
        (PORTS, 1.0), (ROW_FOLDS, 3.0)]
