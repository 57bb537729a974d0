"""The freedom ISSUE 38 is for, tested: a later PR appends a per-layer
metric, or a configuration with its cell, to BENCHMARK.json and edits no
file under tests/benchmark_suite/. Each check of this directory that holds
what BENCHMARK.json declares is run here on copies grown in the ways the
next issues need: a `.storm` metric on the four storm cells behind the last
entry (the next tracing issue: per-thread CPU, stage_wait_ms by seam), one
on a single cell, a `.rollout` and a `.trickle` metric, and a new storm
cell whose name is appended to every list that web-10k.storm is on (the
next model_config issue). The first review of PR 38 found three asserts
that such an entry failed; a check that pins the list again fails here.
Grown too: an open-loop cell appended last, a configuration of three
replicas with its cell on every storm list, and a metric behind the churn
cell's four; and broken: configurations that state what cannot run."""

import copy
import json
import os

import pytest

import test_benchmark_c1m
import test_benchmark_churn
import test_benchmark_counters
import test_benchmark_files
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


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def _ha_body(name, replicas=3, dev_mode=False, cut_servers=False,
             durability="fsync: a raft entry is on each server's disk log "
                        "before it is acknowledged"):
    """svc-10k.json as three servers would run it: raft over RPC, a log on
    disk, `servers` no longer cut; the arguments can break it."""
    body = copy.deepcopy(_json("benchmark", "configs", "svc-10k.json"))
    body["name"] = name
    if not cut_servers:
        del body["reduced"]["servers"]
    body["server"]["dev_mode"] = dev_mode
    body["guarantees"].update(
        replicas=replicas, durability=durability,
        read_back="every acknowledged job is read back from the state "
        "store of every replica")
    return body


BODIES = {  # by `file`: the made-up configurations' files, not on disk
    "benchmark/configs/made-up-10k.json": {
        **_json("benchmark", "configs", "web-10k.json"),
        "name": "made-up-10k"},
    "benchmark/configs/made-up-svc-10k.json": {
        **_json("benchmark", "configs", "svc-10k.json"),
        "name": "made-up-svc-10k"},
}


def _with_a_cell(like, config_like, config, cell, lists_of, body=None):
    """A configuration copied from `config_like`'s entry, and a cell copied
    from `like` (its traffic file, so its generator), appended last, its
    name appended to every list `lists_of` is on."""
    bench = copy.deepcopy(BENCH)
    conf = {**_named(bench["configs"], config_like), "name": config,
            "file": f"benchmark/configs/{config}.json"}
    if body is not None:
        conf["reduced"] = sorted(body["reduced"])
        BODIES[conf["file"]] = body
    bench["configs"].append(conf)
    bench["workloads"].append({**_named(bench["workloads"], like),
                               "name": cell, "config": config})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if lists_of in m.get("workloads", ()):
            m["workloads"].append(cell)
    return bench


def _with_a_storm_cell():
    """A seventh configuration and an eighth cell, driven by web-10k.storm's
    traffic file, on every list that cell is on: cloned by name, whatever
    stands last."""
    return _with_a_cell("web-10k.storm", "web-10k", "made-up-10k",
                        "made-up-10k.storm", "web-10k.storm")


def _with_an_open_loop_cell():
    """svc-10k.trickle's copy appended last, driven by open_loop
    (trickle.json itself), on eval_p50_ms, eval_mean_ms, setup_s and every
    .trickle list: what svc-10k.paced will be."""
    bench = _with_a_cell("svc-10k.trickle", "svc-10k", "made-up-svc-10k",
                         "made-up-10k.paced", "svc-10k.trickle")
    assert _json("benchmark", "traffic", "trickle.json")[
        "generator"] == "open_loop"
    return bench


def _with_a_replicated_cell(name="made-up-10k-ha", **broken):
    """A configuration of three replicas appended last, its closed-loop
    cell (storm.json) on every list web-10k.storm is on: what svc-10k-ha
    will be."""
    return _with_a_cell("svc-10k.storm", "svc-10k", name, name + ".storm",
                        "web-10k.storm", body=_ha_body(name, **broken))


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
    "an open-loop cell appended last": _with_an_open_loop_cell(),
    "a configuration of three replicas": _with_a_replicated_cell(),
    "a metric behind the churn cell's four": _with_metrics(
        _metric("stop_wait_ms.churn", "placed_per_s", ["svc-10k.churn"])),
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
    "churn": lambda bench: test_benchmark_churn.declared(bench),
    "files": lambda bench: test_benchmark_files.declared(bench, BODIES),
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


def _churn_entry_at(index):
    bench = copy.deepcopy(BENCH)
    bench["per_layer"].insert(index, _metric(
        "stop_wait_ms.churn", "placed_per_s", ["svc-10k.churn"]))
    return bench


BROKEN = {
    "the last entry moved to 40": _moved(),
    "c1m off digest_row_folds": _without("c1m-5k.fill",
                                         "digest_row_folds.storm"),
    "web off kernel_ms": _without("web-10k.storm", "kernel_ms.storm"),
    "dc off window_collect_share": _without("dc-50k.storm",
                                            "window_collect_share.storm"),
    "svc off plan_queue_ms": _without("svc-10k.storm", "plan_queue_ms.storm"),
    "two replicas": _with_a_replicated_cell("made-up-r2", replicas=2),
    "three replicas in dev mode": _with_a_replicated_cell(
        "made-up-dev", dev_mode=True),
    "three replicas with servers cut": _with_a_replicated_cell(
        "made-up-cut", cut_servers=True),
    "three replicas and no durability": _with_a_replicated_cell(
        "made-up-mem", durability="none: in-memory raft log"),
    "a churn entry before 96": _churn_entry_at(95),
}


@pytest.mark.parametrize("broken,check", [
    ("the last entry moved to 40", "window_collect"),
    ("the last entry moved to 40", "counters"),
    ("the last entry moved to 40", "web"),
    ("c1m off digest_row_folds", "c1m"),
    ("c1m off digest_row_folds", "counters"),
    ("web off kernel_ms", "web"),
    ("dc off window_collect_share", "window_collect"),
    ("svc off plan_queue_ms", "host_spans"),
    ("the last entry moved to 40", "churn"),
    ("two replicas", "files"),
    ("three replicas in dev mode", "files"),
    ("three replicas with servers cut", "files"),
    ("three replicas and no durability", "files"),
    ("a churn entry before 96", "churn")])
def test_freed_is_not_loosened(broken, check):
    """An entry of PR 38's list that moves, or loses a cell it had, a
    configuration that states replicas it cannot run, or a churn entry put
    in front of the cell's four still fails the check that holds it."""
    with pytest.raises(AssertionError):
        CHECKS[check](BROKEN[broken])
