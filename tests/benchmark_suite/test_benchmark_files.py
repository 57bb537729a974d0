"""BENCHMARK.json against the files it names, and the pieces of the
yardstick that need no server: the arrival schedule and the percentiles."""

import importlib
import json
import os
import random
import re

import pytest

from benchmark import cells
from benchmark.generators import open_loop
from benchmark.readers import stats

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = [(s, m) for s in ("end_to_end", "per_layer") for m in BENCH[s]]


def test_it_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_a_cell_finds_its_files_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    loaded = cells.load(ROOT, cell["name"])
    assert loaded.config["name"] == cell["config"]
    assert loaded.traffic["name"] == cell["traffic"]
    importlib.import_module("benchmark.deploy." + loaded.config["deploy"])
    importlib.import_module(
        "benchmark.generators." + loaded.traffic["generator"])
    for template in loaded.traffic["templates"]:
        assert template in loaded.config["jobs"]
    for name in loaded.traffic["extra_checks"]:
        importlib.import_module("benchmark.reference." + name)
    reported = {s: [m["name"] for m in cells.metrics_of(loaded, s)]
                for s in ("end_to_end", "per_layer")}
    assert "setup_s" in reported["end_to_end"]
    assert len(reported["end_to_end"]) >= 2 and reported["per_layer"]


def stated(conf, body):
    """A configuration's file against its entry, and what its guarantees
    say runs: one dev-mode server (in-memory log) with `servers` in
    `reduced`, or three or five servers, not in dev mode, `servers` not
    cut, and a durability that is not "none"."""
    assert body["name"] == conf["name"] and body["source"] == conf["source"]
    assert sorted(body["reduced"]) == sorted(conf["reduced"])
    guarantees = body["guarantees"]
    assert {"capacity", "constraints", "identity_and_counts", "read_back",
            "device_usage_table", "consistency", "replicas",
            "durability"} <= set(guarantees)
    replicas, dev_mode = guarantees["replicas"], body["server"]["dev_mode"]
    if replicas == 1:
        assert dev_mode is True and "servers" in conf["reduced"]
    else:
        assert replicas in (3, 5)
        assert dev_mode is False and "servers" not in conf["reduced"]
        assert not guarantees["durability"].startswith("none")
    # As shipped: two workers, 32-eval windows, host placement on.
    assert body["server"]["num_schedulers"] == 2
    assert body["server"]["scheduler_window"] == 32
    assert body["server"]["host_placement"] is True


def declared(bench, bodies=None):
    """Every configuration of a BENCHMARK.json against its file; `bodies`
    gives, by `file`, those of a made-up copy that are not on disk
    (test_benchmark_list_grows.py)."""
    for conf in bench["configs"]:
        body = (bodies or {}).get(conf["file"])
        if body is None:
            with open(os.path.join(ROOT, conf["file"])) as f:
                body = json.load(f)
        stated(conf, body)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configuration_states_what_runs(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert conf["file"].startswith(BENCH["paths"][0] + "/")
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    declared({"configs": [conf]})
    assert any(w["config"] == conf["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("section,metric", METRICS,
                         ids=[m["name"] for _, m in METRICS])
def test_a_metric_is_a_file_of_its_own_with_a_reader(section, metric):
    keys = {"name", "unit", "better", "source"}
    keys |= {"bound"} if section == "end_to_end" else {"layer", "moves"}
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    allowed = ("host_clock", "device_trace")
    if section == "per_layer":
        allowed += ("program_span", "program_counter")
    assert metric["source"] in allowed
    path = os.path.join(ROOT, "benchmark", cells.METRIC_DIRS[section],
                        metric["name"] + ".json")
    with open(path) as f:
        spec = json.load(f)
    assert set(spec) <= {"what", "reader", "args"} and spec["what"]
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert callable(reader.read)
    all_cells = [w["name"] for w in BENCH["workloads"]]
    mine = metric.get("workloads", all_cells)
    assert mine and set(mine) <= set(all_cells)
    if section == "end_to_end":
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        assert set(mine) <= set(moved.get("workloads", all_cells))


def test_every_metric_file_is_named_in_benchmark_json():
    for section, folder in cells.METRIC_DIRS.items():
        on_disk = {f[:-len(".json")] for f in os.listdir(
            os.path.join(ROOT, "benchmark", folder))}
        assert on_disk == {m["name"] for m in BENCH[section]}


def test_at_most_half_the_cells_ask_for_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("arrival,extra", [
    ("poisson", {}), ("fixed", {}), ("bursts", {"burst": 32})])
def test_every_seed_gets_the_same_gaps_in_another_order(arrival, extra):
    traffic = {"arrival": arrival, "rate_per_s": 10, **extra}
    blocks = []
    for seed in (1, 2 ** 31 + 11):
        gap = open_loop.gaps(traffic, random.Random(seed))
        blocks.append([next(gap) for _ in range(3200)])  # whole blocks
    a, b = blocks
    assert sorted(a) == pytest.approx(sorted(b))
    # The offered rate is the file's over every whole block.
    assert sum(a) == pytest.approx(3200 / 10)
    if arrival == "poisson":
        assert a != b
        mean = sum(a) / len(a)
        var = sum((g - mean) ** 2 for g in a) / len(a)
        assert var / mean ** 2 == pytest.approx(1.0, abs=0.1)  # exponential


def test_percentiles_are_nearest_rank_and_means_are_plain():
    values = [float(v) for v in range(1, 101)]
    assert stats.stat(values, "p50") == 50.0
    assert stats.stat(values, "p95") == 95.0
    assert stats.stat(values, "mean") == 50.5
    assert stats.stat([7.0], "p95") == 7.0
    assert stats.stat([], "p95") is None
