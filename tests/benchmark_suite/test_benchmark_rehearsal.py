"""Every cell of BENCHMARK.json rehearsed on the CPU through the one
command the driver runs, plus the two ways the command must refuse to
print a result. A rehearsal proves control flow and the last line's shape;
none of its numbers is a device number.

Each case runs benchmark/run.py in a child process (which needs no chip)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def _run(args, cwd=ROOT, env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["BENCH_RUN"] = "the driver sets this; the benchmark ignores it"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, *BENCH["command"][1].split("/")),
         *args], capture_output=True, text=True, timeout=600, cwd=cwd,
        env=env)


def _reported(section, cell):
    return {m["name"] for m in BENCH[section]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_prints_the_contracts_last_line(cell, trace):
    proc = _run(["--workload", cell, "--seed", "3000000019", "--seconds",
                 "3", "--trace", str(trace), "--allow-cpu"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    last = lines[-1]
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    assert last["correct"] is True, lines[:-1]
    # Every number compared beside its limit: the line's last key, and the
    # last lines on standard error.
    assert list(last)[-1] == "compared"
    compared = last["compared"]
    assert {"2_capacity", "3_constraints", "4_identity", "4_counts",
            "5_read_back", "6_device_usage", "8_platform"} <= set(compared)
    assert all(c["value"] <= c["limit"] for c in compared.values())
    said = [ln for ln in proc.stderr.splitlines() if ln.strip()]
    assert said[-len(compared):] == [
        f"compared {k}: {c['value']!r} (limit {c['limit']!r})"
        for k, c in compared.items()]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                              "memory_peak_bytes": 0}
    section = "per_layer" if trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in BENCH[section]}
    want = _reported(section, cell)
    if trace:
        # No chip, no device trace: those readers find nothing to read.
        want = {n for n in want
                if not n.startswith(("device_idle.", "kernel_ms."))}
    assert set(last["metrics"]) == want
    for name, metric in last["metrics"].items():
        assert metric["unit"] == units[name]
        assert isinstance(metric["value"], float)
    if not trace:
        assert all(m["value"] > 0 for m in last["metrics"].values())
    # A 400-node fleet is placed by the numpy mirror alone (host = fast):
    # a legal execution, so `correct` holds although no device window ran.
    run = next(ln for ln in lines if ln.get("note") == "run")
    stats = run["worker_stats"]
    assert stats["host"] == stats["fast"]
    assert run["compiles_in_window"] == 0
    assert run["deployment"]["heartbeats_sent"] > 0
    assert run["deployment"]["heartbeat_errors"] == []


def test_without_a_tpu_it_fails_and_prints_no_result():
    proc = _run(["--workload", BENCH["workloads"][0]["name"], "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_with_only_its_own_files_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(["--workload", BENCH["workloads"][0]["name"], "--seed", "1",
                 "--seconds", "1", "--trace", "0", "--allow-cpu"],
                cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_an_unknown_workload_is_refused():
    proc = _run(["--workload", "no-such.cell", "--allow-cpu"])
    assert proc.returncode != 0
    assert proc.stdout == ""
