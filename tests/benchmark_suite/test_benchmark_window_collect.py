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
NAME = "window_collect_share.storm"
STORMS = ["svc-10k.storm", "dc-50k.storm"]


def test_it_is_declared_beside_the_share_it_follows():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Stack: scheduler/stack.py",
        "moves": "placed_per_s", "workloads": STORMS}
    assert BENCH["per_layer"][-1] is entry  # appended, nothing moved
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"][:-1]}
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           NAME + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "worker_stats_opt"
    assert spec["args"] == {"num": "collect_windowed",
                            "per": ["collect_windowed", "collect_exact"],
                            "scale": 100.0}


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
