"""chip_smoke.py rehearsed on the CPU: its verdict, its exit code and its
last line. The chip itself is reached only by running the script there.

Each case runs the real script in a child process (which needs no chip)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, devices=1, cache_dir=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    return proc, lines


def test_without_a_tpu_it_fails_and_prints_no_result():
    proc, lines = _run([])
    assert proc.returncode != 0
    assert lines == []
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("args,devices,ok,failed", [
    # Every window of a 300-node fleet is under HOST_ROW_STEP_BUDGET, so
    # numpy places all of it: the smoke must not call that a pass.
    (["--nodes", "300", "--evals", "40"], 1, False,
     ["storm:device_placed", "stats:device_placed"]),
    # 5,000 nodes (8,192 rows): windows over 16 evals reach the device.
    (["--nodes", "5000", "--evals", "64"], 1, True, []),
    (["--chips", "4", "--nodes", "2048", "--evals", "128",
      "--kernel-rows", "65536"], 4, True, []),
], ids=["host-placed-only-is-not-ok", "served-on-device", "mesh-phase"])
def test_cpu_rehearsal_exits_with_the_checks_verdict(tmp_path, args, devices,
                                                     ok, failed):
    cache = tmp_path / "cache" if ok else None
    proc, lines = _run(["--allow-cpu", *args], devices, cache)
    assert proc.returncode == (0 if ok else 1), proc.stderr[-3000:]
    assert lines[-1] == {"ok": ok, "device": {"platform": "cpu",
                                              "kind": "cpu",
                                              "count": devices}}
    by_name = {ln["obs"]: ln for ln in lines[:-1]}
    assert by_name["total"]["failed_checks"] == failed
    assert all("observation" in ln["kind"] for ln in lines[:-1])
    versions = by_name["versions"]
    if cache is not None:
        # The environment's cache directory wins and the code sets none.
        assert versions["compile_cache_dir"] == str(cache)
        assert versions["compile_cache_from_env"] is True
    else:
        assert versions["compile_cache_dir"] == os.path.join(ROOT,
                                                             ".jax_cache")
    if ok and devices == 1:
        stats = by_name["worker_stats"]
        assert stats["fast"] - stats["host"] > 0 and stats["fallback"] == 0
        assert by_name["device_vs_mirror"]["rows_agree"] == \
            by_name["device_vs_mirror"]["placements"]
    if devices == 4:
        mesh = next(ln for ln in lines if ln.get("side") == "mesh")
        assert mesh["worker_stats"]["mesh_windows"] > 0
        assert mesh["worker_stats"]["mesh_shards"] == 4
        kernel = by_name["kernel_mesh_vs_one_device"]
        assert kernel["rows_agree"] == [kernel["placements"]] * 2
