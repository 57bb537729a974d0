"""CLI surface: every user-facing command driven in-process against a live
dev agent through the HTTP API, exactly as `python -m nomad_tpu.cli` would
(reference style: command/*_test.go run each Command against a test agent).
"""

import json
import os

import pytest

from nomad_tpu.agent import Agent, AgentConfig
from nomad_tpu.cli.commands import main
from nomad_tpu.structs.structs import EvalStatusComplete


from helpers import wait_for  # noqa: E402


@pytest.fixture(scope="module")
def dev_agent():
    a = Agent(AgentConfig(server_enabled=True, client_enabled=True,
                          dev_mode=True, http_port=0, rpc_port=0,
                          serf_port=0, node_name="cli-dev",
                          num_schedulers=1, enable_debug=True,
                          options={"driver.raw_exec.enable": "true"}))
    a.start()
    assert wait_for(lambda: a.server.is_leader() and a.server._leader)
    assert wait_for(lambda: any(n.Status == "ready"
                                for n in a.server.state.nodes()), timeout=30)
    yield a
    a.shutdown()


@pytest.fixture(scope="module")
def address(dev_agent):
    return f"http://127.0.0.1:{dev_agent.http.port}"


def run_cli(capsys, *argv):
    rc = main(list(argv))
    out = capsys.readouterr()
    return rc, out.out, out.err


@pytest.fixture(scope="module")
def jobfile(tmp_path_factory):
    d = tmp_path_factory.mktemp("cli")
    path = d / "example.nomad"
    old = os.getcwd()
    os.chdir(d)
    try:
        rc = main(["init"])
        assert rc == 0
        assert path.exists()
    finally:
        os.chdir(old)
    # Shrink the example so it places on the dev node and finishes fast.
    text = path.read_text()
    return str(path), text


class TestJobLifecycle:
    def test_validate_and_plan_and_run(self, capsys, address, jobfile,
                                       dev_agent):
        path, _ = jobfile
        rc, out, _ = run_cli(capsys, "validate", path)
        assert rc == 0

        rc, out, _ = run_cli(capsys, "plan", "-address", address, path)
        assert rc in (0, 1)  # 1 = changes would be made (job is new)
        assert "+ Job" in out or "Job:" in out or out

        rc, out, _ = run_cli(capsys, "run", "-detach", "-address", address,
                             path)
        assert rc == 0, out
        eval_id = out.strip().splitlines()[-1]
        assert wait_for(lambda: (
            (e := dev_agent.server.state.eval_by_id(eval_id)) is not None
            and e.Status == EvalStatusComplete), timeout=60)

    def test_validate_prints_ignored_driver_key_warnings(self, capsys,
                                                         tmp_path):
        """`validate` is offline, so the ignored-config warnings must be
        computed locally — same contract as the register path."""
        path = tmp_path / "priv.nomad"
        path.write_text('''
job "priv" {
  datacenters = ["dc1"]
  group "g" {
    task "t" {
      driver = "docker"
      config { image = "busybox" privileged = true }
      resources { cpu = 20 memory = 16 disk = 300 }
    }
  }
}
''')
        rc, out, err = run_cli(capsys, "validate", str(path))
        assert rc == 0
        assert "privileged" in err and "ignored" in err

    def test_status_inspect_stop(self, capsys, address, jobfile):
        rc, out, _ = run_cli(capsys, "status", "-address", address)
        assert rc == 0 and "example" in out

        rc, out, _ = run_cli(capsys, "status", "-address", address,
                             "example")
        assert rc == 0 and "example" in out

        rc, out, _ = run_cli(capsys, "inspect", "-address", address,
                             "example")
        assert rc == 0
        assert json.loads(out)["Job"]["ID"] == "example"

        rc, out, _ = run_cli(capsys, "stop", "-detach", "-address", address,
                             "example")
        assert rc == 0

    def test_run_output_mode_emits_json(self, capsys, address, jobfile):
        path, _ = jobfile
        rc, out, _ = run_cli(capsys, "run", "-output", path)
        assert rc == 0
        assert json.loads(out)["Job"]["ID"] == "example"


class TestClusterCommands:
    def test_node_status_and_drain(self, capsys, address, dev_agent):
        rc, out, _ = run_cli(capsys, "node-status", "-address", address)
        assert rc == 0 and "ready" in out
        node_id = dev_agent.server.state.nodes()[0].ID

        rc, out, _ = run_cli(capsys, "node-status", "-address", address,
                             node_id[:8])
        assert rc == 0 and node_id in out

        rc, out, _ = run_cli(capsys, "node-drain", "-address", address,
                             "-enable", node_id)
        assert rc == 0
        assert wait_for(lambda: dev_agent.server.state.node_by_id(
            node_id).Drain)
        rc, out, _ = run_cli(capsys, "node-drain", "-address", address,
                             "-disable", node_id)
        assert rc == 0
        assert wait_for(lambda: not dev_agent.server.state.node_by_id(
            node_id).Drain)

    def test_alloc_and_eval_status(self, capsys, address, dev_agent):
        from nomad_tpu import mock

        job = mock.job()
        tg = job.TaskGroups[0]
        tg.Count = 1
        task = tg.Tasks[0]
        task.Driver = "mock_driver"
        task.Config = {"run_for": 60}
        task.Resources.Networks = []
        task.Services = []
        eval_id, _, _ = dev_agent.server.job_register(job)
        assert wait_for(lambda: (
            (e := dev_agent.server.state.eval_by_id(eval_id)) is not None
            and e.Status == EvalStatusComplete), timeout=30)
        alloc = dev_agent.server.state.allocs_by_job(job.ID)[0]

        rc, out, _ = run_cli(capsys, "alloc-status", "-address", address,
                             alloc.ID[:8])
        assert rc == 0 and alloc.ID[:8] in out

        rc, out, _ = run_cli(capsys, "eval-status", "-address", address,
                             eval_id[:8])
        assert rc == 0

    def test_agent_level_commands(self, capsys, address):
        rc, out, _ = run_cli(capsys, "server-members", "-address", address)
        assert rc == 0

        rc, out, _ = run_cli(capsys, "agent-info", "-address", address)
        assert rc == 0 and "nomad" in out.lower()

        rc, out, _ = run_cli(capsys, "system-gc", "-address", address)
        assert rc == 0

        rc, out, _ = run_cli(capsys, "services", "-address", address)
        assert rc == 0

        rc, out, _ = run_cli(capsys, "client-config", "-address", address)
        assert rc == 0

    def test_faults_list_arm_disarm(self, capsys, address):
        """`nomad-tpu faults` drives the failpoint registry end to end
        through the debug-gated HTTP endpoint."""
        from nomad_tpu.resilience import failpoints

        try:
            rc, out, _ = run_cli(capsys, "faults", "-address", address)
            assert rc == 0 and "raft.fsync" in out

            rc, out, _ = run_cli(capsys, "faults", "-address", address,
                                 "gossip.send=drop:count=3")
            assert rc == 0 and "gossip.send" in out

            rc, out, _ = run_cli(capsys, "faults", "-address", address)
            assert rc == 0
            armed_line = next(ln for ln in out.splitlines()
                              if ln.startswith("gossip.send"))
            assert "drop" in armed_line

            rc, out, _ = run_cli(capsys, "faults", "-address", address,
                                 "--disarm-all")
            assert rc == 0 and "disarmed" in out.lower()
            assert failpoints.fire("gossip.send") is None
        finally:
            failpoints.disarm_all()

    def test_sched_stats_prints_pipeline_timers(self, capsys, address):
        """`nomad-tpu sched-stats` surfaces the pipelined worker's stage
        timers/counters via the debug-gated endpoint."""
        rc, out, _ = run_cli(capsys, "sched-stats", "-address", address)
        assert rc == 0
        assert "PipelinedWorker" in out
        # Flow counters and at least the headline stage timers show up.
        assert "fast=" in out and "windows=" in out
        for key in ("t_dispatch_ms", "t_collect_ms", "t_drain_fetch_ms"):
            assert key in out

        # Replica-digest health rides the same surface.
        assert "Replica digest:" in out
        assert "diverged=0" in out

        rc, out, _ = run_cli(capsys, "sched-stats", "-address", address,
                             "-json")
        assert rc == 0
        payload = json.loads(out)
        assert payload["Workers"][0]["Stats"]["windows"] >= 0
        assert payload["Digest"]["Diverged"] == 0

    def test_trace_enable_list_show_export_disable(self, capsys, address,
                                                   dev_agent, tmp_path):
        """`nomad-tpu trace` drives the tracing surface end to end:
        runtime enable, a traced register, list, span-tree render, the
        Perfetto export, clear, disable."""
        from nomad_tpu.telemetry import trace

        try:
            rc, out, _ = run_cli(capsys, "trace", "-address", address,
                                 "-enable", "-ratio", "1.0")
            assert rc == 0 and "enabled" in out

            # One traced mutation through the HTTP API.
            from nomad_tpu.api import Client as APIClient
            from nomad_tpu.jobspec import parse_job

            api = APIClient(address=address)
            job = parse_job('''
job "clitrace" {
  datacenters = ["dc1"]
  type = "batch"
  group "g" {
    task "t" {
      driver = "raw_exec"
      config { command = "/bin/sh" args = ["-c", "exit 0"] }
      resources { cpu = 20 memory = 16 disk = 300 }
    }
  }
}
''')
            job.init_fields()
            eval_id, _ = api.jobs.register(job)
            assert wait_for(lambda: api.evaluations.info(eval_id)[0]
                            ["Status"] == "complete", timeout=40)

            def listed():
                rc, out, _ = run_cli(capsys, "trace", "-address", address)
                return out if rc == 0 and "rpc.Job.Register" in out else None

            assert wait_for(lambda: listed() is not None, timeout=15,
                            msg="trace list never showed the register")

            rc, out, _ = run_cli(capsys, "trace", "-address", address,
                                 "-json")
            assert rc == 0
            listing = json.loads(out)
            tid = next(t["TraceID"] for t in listing["Traces"]
                       if t["Root"] == "rpc.Job.Register")

            # Span tree by unique id prefix.
            rc, out, _ = run_cli(capsys, "trace", "-address", address,
                                 tid[:12])
            assert rc == 0
            assert "rpc.Job.Register" in out and "broker.wait" in out

            # Perfetto export.
            dest = str(tmp_path / "trace.json")
            rc, out, _ = run_cli(capsys, "trace", "-address", address,
                                 tid, "-export", dest)
            assert rc == 0
            with open(dest) as f:
                payload = json.load(f)
            assert payload["traceEvents"]

            rc, out, _ = run_cli(capsys, "trace", "-address", address,
                                 "-clear")
            assert rc == 0
            rc, out, _ = run_cli(capsys, "trace", "-address", address,
                                 "-disable")
            assert rc == 0 and "disabled" in out
        finally:
            trace.configure(enabled=False)
            trace.clear()
            run_cli(capsys, "stop", "-detach", "-address", address,
                    "clitrace")

    def test_unknown_job_errors_cleanly(self, capsys, address):
        rc, out, err = run_cli(capsys, "status", "-address", address,
                               "no-such-job")
        assert rc != 0


class TestFsAndMonitor:
    def test_fs_ls_stat_cat_on_live_alloc(self, capsys, address, dev_agent):
        """fs drives the client file API end-to-end: a raw_exec task writes
        stdout, and ls/stat/cat read it through the server->client route."""
        from nomad_tpu import mock

        job = mock.job()
        job.ID = job.Name = "fs-job"
        tg = job.TaskGroups[0]
        tg.Count = 1
        task = tg.Tasks[0]
        task.Name = "echoer"
        task.Driver = "raw_exec"
        task.Config = {"command": "/bin/sh",
                       "args": ["-c", "echo fs-cli-test; sleep 60"]}
        task.Resources.Networks = []
        task.Services = []
        eval_id, _, _ = dev_agent.server.job_register(job)
        assert wait_for(lambda: (
            (e := dev_agent.server.state.eval_by_id(eval_id)) is not None
            and e.Status == EvalStatusComplete), timeout=30)
        assert wait_for(lambda: any(
            al.ClientStatus == "running"
            for al in dev_agent.server.state.allocs_by_job(job.ID)),
            timeout=30)
        alloc = dev_agent.server.state.allocs_by_job(job.ID)[0]

        rc, out, err = run_cli(capsys, "fs", "-address", address,
                               alloc.ID[:8], "alloc/logs")
        assert rc == 0 and "echoer" in out, (out, err)

        log = next(l.split()[-1] for l in out.splitlines()
                   if "stdout" in l)
        assert wait_for(lambda: run_cli(
            capsys, "fs", "-address", address, "-cat", alloc.ID,
            f"alloc/logs/{log}")[1].find("fs-cli-test") >= 0, timeout=20)

        rc, out, _ = run_cli(capsys, "fs", "-address", address, "-stat",
                             alloc.ID, f"alloc/logs/{log}")
        assert rc == 0 and log in out

    def test_monitor_follows_eval(self, capsys, address, dev_agent):
        from nomad_tpu import mock

        job = mock.job()
        job.ID = job.Name = "monitor-job"
        tg = job.TaskGroups[0]
        tg.Count = 1
        task = tg.Tasks[0]
        task.Driver = "mock_driver"
        task.Config = {"run_for": 30}
        task.Resources.Networks = []
        task.Services = []
        eval_id, _, _ = dev_agent.server.job_register(job)
        rc, out, _ = run_cli(capsys, "monitor", "-address", address,
                             eval_id)
        assert rc == 0
        assert "complete" in out

    def test_plan_shows_diff_for_new_job(self, capsys, address, dev_agent,
                                         jobfile):
        path, text = jobfile
        import shutil
        import tempfile

        # A renamed copy is guaranteed-new: plan must render a CREATE diff
        # with added fields and the scheduler annotation summary.
        d = tempfile.mkdtemp()
        newpath = os.path.join(d, "planned.nomad")
        shutil.copy(path, newpath)
        new_text = text.replace('"example"', '"planned"')
        with open(newpath, "w") as f:
            f.write(new_text)
        rc, out, _ = run_cli(capsys, "plan", "-address", address, newpath)
        assert rc == 1  # changes would be made
        assert "+ Job" in out or "+ job" in out.lower()
        assert "create" in out.lower() or "+" in out
