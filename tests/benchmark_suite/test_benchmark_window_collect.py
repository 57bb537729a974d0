"""`window_collect_share.storm` (ISSUE 31): declared as PR 29's
`columnar_plan_share.storm` was, read from the worker's stats in a
rehearsal of both storm cells, and left out of the line, not raised, where
the program lacks the two counters (the parent commit, on which the driver
runs this PR's benchmark files too)."""

import json
import os

import pytest

from benchmark import cells
from benchmark.readers import worker_stats_opt

from test_benchmark_rehearsal import _run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
with open(os.path.join(ROOT, "tests", "benchmark_suite",
                       "per_layer_at_pr37.json")) as _f:
    AT_PR37 = json.load(_f)  # the 52 entries as PR 37's tree had them
NAME = "window_collect_share.storm"
STORMS = ["svc-10k.storm", "dc-50k.storm"]


def declared(bench):
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Stack: scheduler/stack.py",
        "moves": "placed_per_s", "workloads": entry["workloads"]}
    # The two cells it was declared for come first; the list may have
    # grown behind them (ISSUE 38: every storm-family cell).
    assert entry["workloads"][:2] == STORMS
    assert bench["per_layer"][51] is entry  # it stands where PR 31 put it
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:51]}


def test_it_is_declared_beside_the_share_it_follows():
    declared(BENCH)
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           NAME + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "worker_stats_opt"
    assert spec["args"] == {"num": "collect_windowed",
                            "per": ["collect_windowed", "collect_exact"],
                            "scale": 100.0}


def stands(bench, index):
    """The driver takes a new per-layer entry only at the end of the list
    and calls any other difference a change to a metric that stands. So
    the 52 entries of PR 37 keep their places and every field; only a
    `workloads` list may have grown, behind the cells it had. Whatever
    comes after index 51 is a later PR's to append."""
    assert len(AT_PR37) == 52 and len(bench["per_layer"]) >= 52
    was, now = AT_PR37[index], bench["per_layer"][index]
    assert list(now) == list(was)  # the same keys in the same order
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert now[key] == was[key]
    assert now["workloads"][:len(was["workloads"])] == was["workloads"]
    assert len(set(now["workloads"])) == len(now["workloads"])


@pytest.mark.parametrize("index", range(len(AT_PR37)),
                         ids=[m["name"] for m in AT_PR37])
def test_the_list_of_pr_37_stands_as_a_prefix_entry_for_entry(index):
    stands(BENCH, index)


def names(bench):
    found = [m["name"] for m in bench["per_layer"]]
    assert found[:52] == [m["name"] for m in AT_PR37]
    assert len(set(found)) == len(found)


def test_names_are_unique_and_later_entries_come_after_the_prefix():
    names(BENCH)


def test_the_share_is_read_from_the_two_counters_or_left_out():
    args = {"num": "collect_windowed",
            "per": ["collect_windowed", "collect_exact"], "scale": 100.0}
    run = {"stats": {"collect_windowed": 30, "collect_exact": 10,
                     "windows": 2}, "ops": []}
    assert worker_stats_opt.read(run, **args) == 75.0
    # The parent's stats have neither key; a program with one of the two
    # is read no more than one with none.
    for stats in ({"windows": 2}, {"collect_windowed": 30, "windows": 2}):
        assert worker_stats_opt.read({"stats": stats, "ops": []},
                                     **args) is None


@pytest.mark.parametrize("cell", STORMS)
def test_a_storm_rehearsal_collects_every_eval_in_the_windows_pass(cell):
    proc = _run(["--workload", cell, "--seed", "2147483659", "--seconds",
                 "3", "--trace", "1", "--allow-cpu"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"][NAME] == {"value": 100.0, "unit": "%"}
    run = next(ln for ln in lines if ln.get("note") == "run")
    moved = run["worker_stats_delta"]
    assert moved["collect_windowed"] > 0 and moved["collect_exact"] == 0
    assert moved["collect_windowed"] + moved["stale"] \
        >= moved["fast"] + moved["fallback"]
    # The same line from a program without the counters: the metric is
    # left out, every other one is read as before.
    loaded = cells.load(ROOT, cell)
    stats = {k: v for k, v in moved.items() if not k.startswith("collect_")}
    older = cells.read_metrics(loaded, "per_layer", {
        "cell": loaded, "stats": stats, "trace_stats": stats, "ops": [],
        "samples": {}, "gc": [], "compiles": [], "device": None,
        "window": {"t0": 0.0, "t1": 3.0}, "seconds": 3.0,
        "failed_jobs": {}, "setup_s": 1.0})
    assert NAME not in older and "collect_ms.storm" in older
