"""Docker driver lifecycle against the stub daemon CLI (fake_docker.py).

The reference's docker suite (client/driver/docker_test.go) gates on a
live daemon; the stub lets start -> log pump -> stats -> wait/kill ->
cleanup run unconditionally, and additionally asserts the daemon
endpoint/TLS options and registry auth reach the CLI invocations.
"""

import json
import os
import stat
import sys
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.client.allocdir import AllocDir
from nomad_tpu.client.driver import new_driver
from nomad_tpu.client.driver.base import DriverContext, ExecContext
from nomad_tpu.client.env import TaskEnv

from helpers import wait_for  # noqa: E402

# Every assertion here rides real subprocess round-trips (the docker shim
# is a python interpreter start per CLI call); on a loaded suite run a
# single invocation can stall past any fixed margin. Same opt-in retry as
# the cluster/chaos suites.
pytestmark = pytest.mark.timing_retry


@pytest.fixture
def fake_docker(tmp_path, monkeypatch):
    """Install the stub `docker` on PATH; returns the state dir."""
    bin_dir = tmp_path / "bin"
    state = tmp_path / "docker-state"
    bin_dir.mkdir()
    state.mkdir()
    shim = bin_dir / "docker"
    fake = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fake_docker.py")
    # -S -E: the stub imports only the standard library, and skipping
    # site keeps each CLI invocation's interpreter start-up short.
    shim.write_text(f"#!/bin/sh\nexec {sys.executable} -S -E {fake} \"$@\"\n")
    shim.chmod(shim.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("PATH", f"{bin_dir}:{os.environ['PATH']}")
    monkeypatch.setenv("FAKE_DOCKER_STATE", str(state))
    return state


def _invocations(state):
    path = state / "invocations.jsonl"
    if not path.exists():
        return []
    return [json.loads(line) for line in path.read_text().splitlines()]


def _task(image, command="", args=(), config=None):
    alloc = mock.alloc()
    task = alloc.Job.TaskGroups[0].Tasks[0]
    task.Driver = "docker"
    task.Config = {"image": image}
    if command:
        task.Config["command"] = command
        task.Config["args"] = list(args)
    task.Config.update(config or {})
    task.Resources.Networks = []
    return alloc, task


def _ctx(tmp_path, alloc, task):
    ad = AllocDir(str(tmp_path / "alloc" / alloc.ID))
    ad.build([task.Name])
    env = TaskEnv(node=mock.node(), task=task, alloc=alloc,
                  alloc_dir=ad.shared_dir,
                  task_dir=ad.task_dirs[task.Name])
    return ExecContext(alloc_dir=ad, alloc_id=alloc.ID, task_env=env)


class _Options:
    def __init__(self, opts=None):
        self.opts = opts or {}

    def read_option(self, key, default=""):
        return self.opts.get(key, default)


def _driver(opts=None):
    d = new_driver("docker", DriverContext())
    d.ctx.config = _Options(opts)
    return d


class TestDockerLifecycle:
    def test_fingerprint_reports_version(self, fake_docker):
        node = mock.node()
        d = _driver()
        assert d.fingerprint(_Options(), node) is True
        assert node.Attributes["driver.docker"] == "1"
        assert node.Attributes["driver.docker.version"] == "1.11.fake"

    def test_start_logs_wait_cleanup(self, fake_docker, tmp_path):
        """The full happy path: run -> log pump into FileRotator files ->
        wait -> exit 0 -> container removed (cleanup.container default)."""
        alloc, task = _task("fake/short", command="echo",
                            args=["${NOMAD_ALLOC_ID}"])
        ctx = _ctx(tmp_path, alloc, task)
        d = _driver()
        handle = d.start(ctx, task)
        res = handle.wait(timeout=10)
        assert res is not None and res.exit_code == 0
        # Log pump: container stdout/stderr landed in the alloc log dir,
        # with env interpolation applied to args.
        log_dir = ctx.alloc_dir.log_dir()

        def _read(kind):
            return b"".join(
                (p := os.path.join(log_dir, f)) and open(p, "rb").read()
                for f in sorted(os.listdir(log_dir))
                if f.startswith(f"{task.Name}.{kind}"))
        assert wait_for(lambda: b"out:fake/short:echo " + alloc.ID.encode()
                        in _read("stdout"), timeout=10)
        assert wait_for(lambda: b"err:fake/short" in _read("stderr"),
                        timeout=10)
        # Cleanup ran after self-exit (the _watch path, not kill).
        state = json.loads(
            (fake_docker / f"{handle.container_id}.json").read_text())
        assert wait_for(lambda: json.loads(
            (fake_docker / f"{handle.container_id}.json").read_text()
        )["removed"], timeout=10)
        assert state["flags"]["memory"] == f"{task.Resources.MemoryMB}m"
        assert state["flags"]["cpu_shares"] == str(task.Resources.CPU)
        assert any(v.endswith(":/alloc") for v in state["flags"]["volumes"])

    def test_kill_stops_container(self, fake_docker, tmp_path):
        alloc, task = _task("fake/long")
        ctx = _ctx(tmp_path, alloc, task)
        d = _driver()
        handle = d.start(ctx, task)
        # Event checks, not wall-clock margins: a long container has no
        # exit to wait out (poll the done event instantaneously), and the
        # stats sample is one subprocess round that can stall under suite
        # load — poll until a sample lands instead of asserting the first.
        assert handle.wait(timeout=0) is None  # still running
        assert wait_for(lambda: handle.stats() is not None, timeout=20,
                        msg="live stats sample")
        handle.kill(kill_timeout=1.0)
        res = handle.wait(timeout=10)
        assert res is not None and res.exit_code == 137
        argvs = [i["argv"] for i in _invocations(fake_docker)]
        assert any(a[0] == "stop" for a in argvs)

    def test_failing_container_reports_exit_code(self, fake_docker,
                                                 tmp_path):
        alloc, task = _task("fake/fail")
        ctx = _ctx(tmp_path, alloc, task)
        handle = _driver().start(ctx, task)
        res = handle.wait(timeout=10)
        assert res is not None and res.exit_code == 7

    def test_run_flags_network_labels_ports(self, fake_docker, tmp_path):
        from nomad_tpu.structs import NetworkResource, Port

        alloc, task = _task("fake/short", config={
            "network_mode": "host",
            "labels": {"team": "infra"},
            "port_map": {"db": 6379},
        })
        task.Resources.Networks = [NetworkResource(
            IP="10.0.0.1", ReservedPorts=[Port(Label="db", Value=21000)])]
        ctx = _ctx(tmp_path, alloc, task)
        handle = _driver().start(ctx, task)
        handle.wait(timeout=10)
        state = json.loads(
            (fake_docker / f"{handle.container_id}.json").read_text())
        assert state["flags"]["network"] == "host"
        assert "team=infra" in state["flags"]["labels"]
        assert "21000:6379" in state["flags"]["ports"]

    def test_endpoint_and_tls_options_reach_cli(self, fake_docker,
                                                tmp_path):
        """client options docker.endpoint / docker.cert.path /
        docker.tls.verify become DOCKER_* env on every CLI call
        (reference: docker.go:258-289 client init)."""
        alloc, task = _task("fake/short")
        ctx = _ctx(tmp_path, alloc, task)
        d = _driver({"docker.endpoint": "tcp://10.0.0.9:2376",
                     "docker.cert.path": "/etc/docker-certs",
                     "docker.tls.verify": "true"})
        handle = d.start(ctx, task)
        handle.wait(timeout=10)
        envs = [i["env"] for i in _invocations(fake_docker)
                if i["argv"][0] == "run"]
        assert envs and envs[0]["DOCKER_HOST"] == "tcp://10.0.0.9:2376"
        assert envs[0]["DOCKER_CERT_PATH"] == "/etc/docker-certs"
        assert envs[0]["DOCKER_TLS_VERIFY"] == "1"

    def test_registry_auth_passed_and_scrubbed(self, fake_docker,
                                               tmp_path):
        """Private-registry auth reaches `docker --config` as a
        credentials file that is deleted right after the run."""
        alloc, task = _task("fake/short", config={
            "auth": {"username": "u", "password": "p",
                     "server_address": "reg.example.com"}})
        ctx = _ctx(tmp_path, alloc, task)
        handle = _driver().start(ctx, task)
        handle.wait(timeout=10)
        auth = json.loads((fake_docker / "last_auth.json").read_text())
        assert "reg.example.com" in auth["auths"]
        # Scrubbed: no credentials at rest in the task dir.
        task_dir = ctx.alloc_dir.task_dirs[task.Name]
        assert not os.path.exists(os.path.join(task_dir, "docker-auth"))

    def test_exec_in_task(self, fake_docker, tmp_path):
        alloc, task = _task("fake/long")
        ctx = _ctx(tmp_path, alloc, task)
        handle = _driver().start(ctx, task)
        code, out = handle.exec_in_task("/bin/check", ["-v"], timeout=5)
        assert code == 0
        assert "exec:/bin/check -v" in out
        handle.kill(1.0)

    def test_reattach_by_handle_id(self, fake_docker, tmp_path):
        """Agent restart: a new handle opened from the persisted id keeps
        watching the same container."""
        alloc, task = _task("fake/long")
        ctx = _ctx(tmp_path, alloc, task)
        d = _driver()
        handle = d.start(ctx, task)
        hid = handle.id()
        re = d.open(ctx, hid)
        assert re.container_id == handle.container_id
        handle.kill(1.0)
        res = re.wait(timeout=10)
        assert res is not None and res.exit_code == 137

    def test_batched_stats_many(self, fake_docker, tmp_path):
        from nomad_tpu.client.driver.docker import DockerHandle

        handles = []
        for _ in range(3):
            alloc, task = _task("fake/long")
            ctx = _ctx(tmp_path, alloc, task)
            handles.append(_driver().start(ctx, task))
        stats = DockerHandle.stats_many(handles)
        assert len(stats) == 3
        for h in handles:
            assert stats[h.container_id]["cpu_percent"] == 5.0
            assert stats[h.container_id]["rss_bytes"] == 10 * 2**20
            h.kill(1.0)
