"""Agent + HTTP API + api client + jobspec tests (shaped after reference
command/agent/*_test.go and api/*_test.go — black-box dev-mode agent)."""

import threading
import time

import pytest

from nomad_tpu.agent import Agent, AgentConfig
from nomad_tpu.api import APIError, Client as APIClient, QueryOptions
from nomad_tpu.jobspec import parse_duration, parse_job
from nomad_tpu.structs.structs import SECOND, MINUTE


from helpers import wait_for  # noqa: E402


@pytest.fixture(scope="module")
def dev_agent(tmp_path_factory):
    config = AgentConfig.dev()
    config.http_port = 0  # ephemeral
    config.data_dir = str(tmp_path_factory.mktemp("agent"))
    agent = Agent(config)
    agent.start()
    api = APIClient(address=f"http://127.0.0.1:{agent.http.port}")
    yield agent, api
    agent.shutdown()


BATCH_JOB = '''
job "httpjob" {
  datacenters = ["dc1"]
  type = "batch"
  group "g" {
    task "t" {
      driver = "raw_exec"
      config { command = "/bin/sh" args = ["-c", "echo api > ${NOMAD_TASK_DIR}/api.txt; sleep 1"] }
      resources { cpu = 50 memory = 32 disk = 300 }
    }
  }
}
'''


class TestHTTPAPI:
    def test_agent_self_and_members(self, dev_agent):
        agent, api = dev_agent
        self_info = api.agent.self()
        assert self_info["config"]["Server"] is True
        assert self_info["config"]["Client"] is True
        members = api.agent.members()
        assert members[0]["Status"] == "alive"
        assert api.regions.list() == ["global"]

    def test_agent_metrics_endpoint(self, dev_agent):
        agent, api = dev_agent
        # Force one FSM apply into the current collection interval so the
        # assertion is deterministic regardless of interval rotation.
        from nomad_tpu import mock
        node = mock.node()
        agent.server.node_register(node)
        try:
            snap = api.agent.metrics()
            assert set(snap) == {"Timestamp", "Gauges", "Counters",
                                 "Samples"}
            # Entry shapes (reference: go-metrics DisplayMetrics): gauges
            # are {Name, Value}; counters and samples are aggregates.
            for g in snap["Gauges"]:
                assert set(g) == {"Name", "Value"}
            for agg in list(snap["Counters"]) + list(snap["Samples"]):
                assert set(agg) == {"Name", "Count", "Sum", "Min", "Max",
                                    "Mean"}
                assert agg["Count"] >= 1
                assert agg["Min"] <= agg["Mean"] <= agg["Max"]
            # The HTTP snapshot shows the current interval; the sample we
            # just forced may land either side of a rotation boundary, so
            # assert against the sink's retained intervals.
            from nomad_tpu.telemetry import registry
            assert any("nomad.fsm.register_node" in iv["samples"]
                       for iv in registry.inmem._intervals)
        finally:
            # Leave the shared dev agent's node list as we found it.
            agent.server.node_deregister(node.ID)

    def test_nodes_listed(self, dev_agent):
        agent, api = dev_agent
        assert wait_for(lambda: len(api.nodes.list()[0]) == 1)
        nodes, meta = api.nodes.list()
        assert meta.last_index > 0
        node, _ = api.nodes.info(nodes[0]["ID"])
        assert node["Status"] == "ready"
        assert node["Attributes"]["driver.raw_exec"] == "1"

    def test_job_lifecycle_over_http(self, dev_agent):
        agent, api = dev_agent
        job = parse_job(BATCH_JOB)
        job.init_fields()
        eval_id, meta = api.jobs.register(job)
        assert eval_id
        # Eval completes.
        assert wait_for(lambda: api.evaluations.info(eval_id)[0]["Status"]
                        == "complete")
        # Allocation visible via job + eval + node queries.
        allocs, _ = api.jobs.allocations("httpjob")
        assert len(allocs) == 1
        assert wait_for(lambda: api.jobs.allocations("httpjob")[0][0]
                        ["ClientStatus"] == "complete", timeout=40)
        alloc_id = allocs[0]["ID"]
        full, _ = api.allocations.info(alloc_id)
        assert full["Job"]["ID"] == "httpjob"
        # fs API reads the task output through the agent.
        content = api.alloc_fs.cat(alloc_id, "t/local/api.txt")
        assert content.strip() == "api"
        listing = api.alloc_fs.list(alloc_id, "alloc/logs")
        assert any(f["Name"].startswith("t.stdout") for f in listing)
        # Job listing + info.
        jobs, _ = api.jobs.list()
        assert any(j["ID"] == "httpjob" for j in jobs)
        info, _ = api.jobs.info("httpjob")
        assert info.TaskGroups[0].Tasks[0].Driver == "raw_exec"
        # Stop.
        api.jobs.deregister("httpjob")
        with pytest.raises(APIError) as exc:
            api.jobs.info("httpjob")
        assert exc.value.code == 404

    def test_blocking_query_wakes_on_change(self, dev_agent):
        agent, api = dev_agent
        _, meta = api.jobs.list()
        result = {}

        def blocked():
            jobs, m = api.jobs.list(QueryOptions(wait_index=meta.last_index,
                                                 wait_time=10))
            result["jobs"] = jobs
            result["index"] = m.last_index

        t = threading.Thread(target=blocked)
        t.start()
        time.sleep(0.3)
        job = parse_job(BATCH_JOB)
        job.ID = job.Name = "blocker"
        job.TaskGroups[0].Tasks[0].Config = {"command": "/bin/true"}
        api.jobs.register(job)
        t.join(timeout=10)
        assert not t.is_alive(), "blocking query never woke"
        assert result["index"] > meta.last_index
        api.jobs.deregister("blocker")

    def test_job_plan_over_http(self, dev_agent):
        agent, api = dev_agent
        job = parse_job(BATCH_JOB.replace("httpjob", "planjob"))
        job.init_fields()
        resp, _ = api.jobs.plan(job, diff=True)
        assert resp.Diff is not None and resp.Diff.Type == "Added"
        assert resp.JobModifyIndex == 0
        # Dry run must not register the job.
        with pytest.raises(APIError):
            api.jobs.info("planjob")
        updates = resp.Annotations.DesiredTGUpdates["g"]
        assert updates.Place == 1

    def test_error_codes(self, dev_agent):
        agent, api = dev_agent
        with pytest.raises(APIError) as exc:
            api.jobs.info("nonexistent-job")
        assert exc.value.code == 404
        with pytest.raises(APIError) as exc:
            api.request("GET", "/v1/bogus/path")
        assert exc.value.code == 404

    def test_system_gc(self, dev_agent):
        agent, api = dev_agent
        api.system.garbage_collect()  # must not error


class TestJobspec:
    def test_parse_duration(self):
        assert parse_duration("30s") == 30 * SECOND
        assert parse_duration("5m") == 5 * MINUTE
        assert parse_duration("1h30m") == 90 * MINUTE
        assert parse_duration("250ms") == 250 * 1_000_000
        with pytest.raises(ValueError):
            parse_duration("banana")

    def test_constraint_sugar(self):
        job = parse_job('''
job "x" {
  datacenters = ["dc1"]
  constraint { attribute = "${attr.nomad.version}" version = ">= 0.1" }
  constraint { attribute = "${attr.arch}" regexp = "x86.*" }
  constraint { distinct_hosts = true }
  group "g" { task "t" { driver = "raw_exec"
    config { command = "/bin/true" } } }
}''')
        ops = [c.Operand for c in job.Constraints]
        assert ops == ["version", "regexp", "distinct_hosts"]

    def test_multiple_groups_and_tasks(self):
        job = parse_job('''
job "multi" {
  datacenters = ["dc1"]
  group "a" {
    count = 2
    task "t1" { driver = "raw_exec" config { command = "/bin/true" } }
    task "t2" { driver = "raw_exec" config { command = "/bin/true" } }
  }
  group "b" { task "t3" { driver = "raw_exec" config { command = "/bin/true" } } }
}''')
        assert [g.Name for g in job.TaskGroups] == ["a", "b"]
        assert [t.Name for t in job.TaskGroups[0].Tasks] == ["t1", "t2"]
        assert job.TaskGroups[0].Count == 2


def test_debug_stacks(dev_agent):
    """Thread-stack dump endpoint (the pprof-analogue debug hook; enabled
    in dev mode, gated behind enable_debug otherwise)."""
    agent, api = dev_agent
    stacks, _ = api.get("/v1/agent/debug/stacks")
    assert any("MainThread" in k for k in stacks)
    assert all(isinstance(v, list) for v in stacks.values())


def test_agent_monitor_ring(dev_agent):
    """Recent-log endpoint with incremental polling."""
    import logging

    agent, api = dev_agent
    logging.getLogger("nomad.test").warning("monitor-marker-1")
    out, _ = api.get("/v1/agent/monitor")
    assert any("monitor-marker-1" in l for l in out["Lines"])
    seq = out["Seq"]
    logging.getLogger("nomad.test").warning("monitor-marker-2")
    out2, _ = api.get(f"/v1/agent/monitor?after={seq}")
    assert any("monitor-marker-2" in l for l in out2["Lines"])
    assert not any("monitor-marker-1" in l for l in out2["Lines"])


class TestGzip:
    def test_large_responses_gzip_when_accepted(self, dev_agent):
        """(reference: every handler gzip-wrapped, command/agent/http.go:
        70-80) — large list responses compress; clients that don't accept
        gzip get identity; the API client decodes transparently."""
        import gzip
        import json as _json
        import urllib.request

        agent, api = dev_agent
        base = f"http://127.0.0.1:{agent.http.port}"
        # Find an endpoint whose identity payload clears the 1KB gzip
        # floor (metrics accumulates counters; agent/self dumps config).
        fat = None
        for path in ("/v1/agent/metrics", "/v1/agent/self", "/v1/nodes"):
            with urllib.request.urlopen(base + path, timeout=10) as resp:
                if len(resp.read()) >= 1024:
                    fat = path
                    break
        assert fat is not None, "no endpoint over the gzip floor"
        req = urllib.request.Request(base + fat)
        req.add_header("Accept-Encoding", "gzip")
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.headers.get("Content-Encoding") == "gzip"
            body = _json.loads(gzip.decompress(resp.read()))
        assert body

        # Identity for clients that don't ask for gzip.
        req2 = urllib.request.Request(base + "/v1/nodes")
        with urllib.request.urlopen(req2, timeout=10) as resp:
            assert resp.headers.get("Content-Encoding") is None
            _json.loads(resp.read())

        # The API client path round-trips (it sends Accept-Encoding: gzip).
        nodes, _ = api.request("GET", "/v1/nodes")
        assert isinstance(nodes, list)


class TestConfigKnobs:
    def test_server_scheduler_and_tls_blocks_parse(self, tmp_path):
        from nomad_tpu.agent.config import load_config_file

        p = tmp_path / "srv.hcl"
        p.write_text('''
server {
  enabled = true
  scheduler_window = 128
  pipelined_scheduling = true
  scheduler_mesh = "all"
}
''')
        cfg = load_config_file(str(p))
        assert cfg.scheduler_window == 128
        assert cfg.pipelined_scheduling is True
        assert cfg.scheduler_mesh == "all"


def test_debug_profile_returns_loadable_pstats(dev_agent, tmp_path):
    """CPU-profile capture endpoint (the pprof CPU analogue,
    reference http.go:133-139): the body is a pstats-compatible marshal
    blob loadable with pstats.Stats."""
    import pstats
    import urllib.request

    agent, api = dev_agent
    url = (f"http://127.0.0.1:{agent.http.port}"
           "/v1/agent/debug/profile?seconds=0.3")
    with urllib.request.urlopen(url) as resp:
        assert resp.headers["Content-Type"] == "application/octet-stream"
        blob = resp.read()
    path = tmp_path / "profile.pstats"
    path.write_bytes(blob)
    st = pstats.Stats(str(path))
    # The server's own threads were sampled: some known module shows up.
    files = {f for (f, _, _) in st.stats}
    assert any("nomad_tpu" in f or "threading" in f for f in files), files


def test_debug_sched_stats_exports_worker_schema(dev_agent):
    """/v1/agent/debug/sched-stats: the operator surface for the
    pipelined worker's stage timers/counters — every key of the declared
    stats schema must be present (no lazily-created keys that appear only
    after the stage first runs)."""
    from nomad_tpu.server.pipelined_worker import (
        STATS_COUNTERS,
        STATS_TIMERS_MS,
    )

    agent, api = dev_agent
    out = api.agent.sched_stats()
    workers = out["Workers"]
    assert workers, "leader must export its scheduling workers"
    pipelined = [w for w in workers if w["Type"] == "PipelinedWorker"]
    assert pipelined, [w["Type"] for w in workers]
    for w in pipelined:
        assert w["Window"] >= 1
        stats = w["Stats"]
        for key in STATS_COUNTERS + STATS_TIMERS_MS:
            assert key in stats, f"schema key {key} missing from endpoint"
    # Per-worker stats keyed by WORKER NAME (scaling regressions — one
    # worker starved, one convoying on the chain lease — are invisible
    # in the aggregate), names unique.
    assert all(w["Name"] for w in workers)
    assert len({w["Name"] for w in workers}) == len(workers)
    by_worker = out["ByWorker"]
    for w in pipelined:
        assert by_worker[w["Name"]] == w["Stats"]
    totals = out["Totals"]
    assert totals["windows"] == sum(
        w["Stats"]["windows"] for w in pipelined)
    # Columnar-store block: segment/live-row/promotion counts plus the
    # per-commit-path batch counters (service vs system), present even
    # when zero so operators can rely on the shape.
    store = out["Store"]
    for key in ("Segments", "LiveRows", "PromotedRows", "Batches"):
        assert key in store, f"Store key {key} missing from endpoint"
    assert isinstance(store["Batches"], dict)
    # Replica-digest block: chain position / verification watermark /
    # sync mode / flow counters (README "Replica determinism").
    digest = out["Digest"]
    for key in ("Interval", "LastIndex", "Chain", "Synced", "Folds",
                "ColumnFolds", "RowFolds", "Exchanged", "Diverged", "VerifiedIndex"):
        assert key in digest, f"Digest key {key} missing from endpoint"
    assert digest["Diverged"] == 0


def test_debug_profile_rejects_malformed_seconds(dev_agent):
    """Malformed ?seconds must be a client error (400), not an unhandled
    ValueError surfacing as a 500."""
    agent, api = dev_agent
    with pytest.raises(APIError) as ei:
        api.get("/v1/agent/debug/profile?seconds=banana")
    assert ei.value.code == 400
    assert "banana" in str(ei.value)


class TestFaultsEndpoint:
    """/v1/agent/debug/faults: the HTTP arming surface for the failpoint
    registry (debug-gated like stacks/profile)."""

    @pytest.fixture(autouse=True)
    def _heal(self):
        from nomad_tpu.resilience import failpoints

        failpoints.disarm_all()
        yield
        failpoints.disarm_all()

    def test_lists_known_sites_when_disarmed(self, dev_agent):
        agent, api = dev_agent
        sites = api.agent.faults()["Sites"]
        assert "raft.fsync" in sites and "rpc.pool.call" in sites
        assert len(sites) >= 10
        assert all(info["armed"] is None or info["fired"] >= 0
                   for info in sites.values())

    def test_arm_inspect_disarm_round_trip(self, dev_agent):
        agent, api = dev_agent
        out = api.agent.arm_faults("gossip.send=drop:p=0.5;raft.fsync=off")
        assert out["Touched"] == ["gossip.send", "raft.fsync"]
        armed = out["Sites"]["gossip.send"]["armed"]
        assert armed["mode"] == "drop" and armed["probability"] == 0.5
        assert api.agent.disarm_faults()["DisarmedAll"] is True
        assert api.agent.faults()["Sites"]["gossip.send"]["armed"] is None

    def test_malformed_spec_is_a_400(self, dev_agent):
        agent, api = dev_agent
        with pytest.raises(APIError) as ei:
            api.agent.arm_faults("gossip.send=explode")
        assert ei.value.code == 400

    def test_missing_spec_is_a_400(self, dev_agent):
        agent, api = dev_agent
        with pytest.raises(APIError) as ei:
            api.put("/v1/agent/debug/faults", {})
        assert ei.value.code == 400

    def test_non_string_spec_is_a_400(self, dev_agent):
        agent, api = dev_agent
        with pytest.raises(APIError) as ei:
            api.put("/v1/agent/debug/faults", {"Spec": 5})
        assert ei.value.code == 400
        assert "string" in str(ei.value)


class TestTracePagination:
    """/v1/agent/debug/trace list pagination: limit/after cursor over
    the newest-last summary list (the ring is bounded, so stale cursors
    restart from the oldest retained entry instead of erroring)."""

    @pytest.fixture(autouse=True)
    def _traced(self, dev_agent):
        agent, api = dev_agent
        api.agent.configure_trace(enabled=True, sample_ratio=1.0)
        api.agent.clear_traces()
        yield
        api.agent.configure_trace(enabled=False)
        api.agent.clear_traces()

    def _seed_traces(self, agent, api, n=5):
        from nomad_tpu import mock
        from nomad_tpu.structs import to_dict

        for _ in range(n):
            agent.rpc("Node.Register", {"Node": to_dict(mock.node())})
        wait_for(lambda: len(api.agent.traces().get("Traces", ())) >= n,
                 timeout=20, msg="seed traces never retained")

    def test_limit_after_walks_the_full_list(self, dev_agent):
        agent, api = dev_agent
        self._seed_traces(agent, api)
        full = [t["TraceID"] for t in api.agent.traces()["Traces"]]
        page = api.agent.traces(limit=2)
        assert [t["TraceID"] for t in page["Traces"]] == full[:2]
        assert page["NextAfter"] == full[1]
        # Summary schema holds on a paginated response.
        for t in page["Traces"]:
            assert set(t) >= {"TraceID", "Root", "Start", "DurationMs",
                              "Spans", "Complete", "Error"}
        # Cursor-walk the whole list: background traffic may APPEND new
        # traces while we walk, but the captured prefix must come back
        # exactly once, in order.
        seen, after = [], ""
        while True:
            p = api.agent.traces(limit=2, after=after)
            seen.extend(t["TraceID"] for t in p["Traces"])
            after = p.get("NextAfter", "")
            if not after:
                break
        assert seen[:len(full)] == full
        assert len(seen) == len(set(seen))
        # An un-truncated page carries no cursor.
        assert "NextAfter" not in api.agent.traces(limit=10_000)

    def test_stale_cursor_restarts_from_oldest(self, dev_agent):
        agent, api = dev_agent
        self._seed_traces(agent, api)
        full = [t["TraceID"] for t in api.agent.traces()["Traces"]]
        p = api.agent.traces(limit=2, after="f" * 32)
        assert [t["TraceID"] for t in p["Traces"]] == full[:2]

    def test_malformed_limit_is_a_400(self, dev_agent):
        agent, api = dev_agent
        for bad in ("nope", "0", "-3"):
            with pytest.raises(APIError) as ei:
                api.request("GET", "/v1/agent/debug/trace",
                            {"limit": bad})
            assert ei.value.code == 400


def test_register_surfaces_ignored_driver_config_warnings(dev_agent):
    """Accepted-but-unimplemented docker config keys must come back to
    the SUBMITTER as registration warnings, not vanish into a
    once-per-process client log line."""
    from nomad_tpu import mock

    agent, api = dev_agent
    job = mock.job()
    task = job.TaskGroups[0].Tasks[0]
    task.Driver = "docker"
    task.Config = {"image": "busybox", "privileged": True,
                   "dns_servers": ["8.8.8.8"]}
    try:
        eval_id, warnings, meta = api.jobs.register_with_warnings(job)
        assert any("privileged" in w for w in warnings), warnings
        assert any("dns_servers" in w for w in warnings), warnings
        # The plain register keeps its 2-tuple shape for callers that
        # don't care about warnings.
        eval_id2, meta2 = api.jobs.register(job)
        assert eval_id2
    finally:
        api.jobs.deregister(job.ID)
