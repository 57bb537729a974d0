"""The freedom ISSUE 38 is for, tested: a later PR appends a per-layer
metric, or a configuration with its cell, to BENCHMARK.json and edits no
file under tests/benchmark_suite/. Each check of this directory that holds
what BENCHMARK.json declares is run here on copies grown in the ways the
next issues need: a `.storm` metric on the four storm cells behind the last
entry (the next tracing issue: per-thread CPU, stage_wait_ms by seam), one
on a single cell, a `.rollout` and a `.trickle` metric, and a new storm
cell whose name is appended to every list that web-10k.storm is on (the
next model_config issue). The first review of PR 38 found three asserts
that such an entry failed; a check that pins the list again fails here."""

import copy
import json
import os

import pytest

import test_benchmark_c1m
import test_benchmark_counters
import test_benchmark_host_spans
import test_benchmark_web
import test_benchmark_window_collect

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
STORMS = ["svc-10k.storm", "dc-50k.storm", "c1m-5k.fill", "web-10k.storm"]


def _metric(name, moves, workloads):
    return {"name": name, "unit": "ms", "better": "lower",
            "source": "program_span",
            "layer": "Window worker: server/pipelined_worker.py",
            "moves": moves, "workloads": list(workloads)}


def _with_metrics(*entries):
    bench = copy.deepcopy(BENCH)
    bench["per_layer"].extend(entries)
    return bench


def _with_a_storm_cell():
    """A sixth configuration and a seventh cell, driven by web-10k.storm's
    traffic file, on every list that cell is on."""
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({**bench["configs"][-1], "name": "made-up-10k",
                             "file": "benchmark/configs/made-up-10k.json"})
    bench["workloads"].append({**bench["workloads"][-1],
                               "name": "made-up-10k.storm",
                               "config": "made-up-10k"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "web-10k.storm" in m.get("workloads", ()):
            m["workloads"].append("made-up-10k.storm")
    return bench


GROWN = {
    "a .storm metric on the four storm cells": _with_metrics(
        _metric("stage_cpu_ms.storm", "placed_per_s", STORMS)),
    "a .storm metric on one cell": _with_metrics(
        _metric("stage_wait_ms.drain.storm", "placed_per_s", STORMS[:1])),
    "a metric a family": _with_metrics(
        _metric("stage_cpu_ms.storm", "placed_per_s", STORMS),
        _metric("stage_cpu_ms.trickle", "eval_p50_ms", ["svc-10k.trickle"]),
        _metric("stage_cpu_ms.rollout", "rollout_mean_ms",
                ["sys-10k.rollout"])),
    "a storm cell on every list": _with_a_storm_cell(),
}
CHECKS = {
    "web": lambda bench: test_benchmark_web.declared(bench),
    "c1m": lambda bench: test_benchmark_c1m.declared(bench),
    "window_collect": lambda bench: [
        test_benchmark_window_collect.declared(bench),
        test_benchmark_window_collect.names(bench)] + [
        test_benchmark_window_collect.stands(bench, i) for i in range(52)],
    "host_spans": lambda bench: [
        test_benchmark_host_spans.declared(bench, name)
        for name in test_benchmark_host_spans.NEW],
    "counters": lambda bench: [
        test_benchmark_counters.declared(bench, name)
        for name in test_benchmark_counters.NEW],
}


@pytest.mark.parametrize("check", CHECKS)
@pytest.mark.parametrize("grown", GROWN)
def test_a_later_pr_appends_and_no_check_here_fails(grown, check):
    bench = GROWN[grown]
    assert bench != BENCH and len(bench["per_layer"]) >= len(
        BENCH["per_layer"])
    CHECKS[check](bench)


def _moved():
    bench = copy.deepcopy(BENCH)
    bench["per_layer"].insert(40, bench["per_layer"].pop())
    return bench


def _without(cell, *names):
    bench = copy.deepcopy(BENCH)
    for m in bench["per_layer"]:
        if m["name"] in names:
            m["workloads"].remove(cell)
    return bench


BROKEN = {
    "the last entry moved to 40": _moved(),
    "c1m off digest_row_folds": _without("c1m-5k.fill",
                                         "digest_row_folds.storm"),
    "web off kernel_ms": _without("web-10k.storm", "kernel_ms.storm"),
    "dc off window_collect_share": _without("dc-50k.storm",
                                            "window_collect_share.storm"),
    "svc off plan_queue_ms": _without("svc-10k.storm", "plan_queue_ms.storm"),
}


@pytest.mark.parametrize("broken,check", [
    ("the last entry moved to 40", "window_collect"),
    ("the last entry moved to 40", "counters"),
    ("the last entry moved to 40", "web"),
    ("c1m off digest_row_folds", "c1m"),
    ("c1m off digest_row_folds", "counters"),
    ("web off kernel_ms", "web"),
    ("dc off window_collect_share", "window_collect"),
    ("svc off plan_queue_ms", "host_spans")])
def test_freed_is_not_loosened(broken, check):
    """An entry of PR 38's list that moves, or loses a cell it had, still
    fails the check that holds it."""
    with pytest.raises(AssertionError):
        CHECKS[check](BROKEN[broken])
