"""Fixed-seed gates for the shape benchmark/configs/web-10k.json brings
(ISSUE 36): upstream's mock.Job() as published, a service of 10 whose task
asks for 50 MBits and two dynamic ports, the first job shape in the
benchmark that asks for a network.

- the served path (one Server, its two PipelinedWorkers, broker, plan
  applier, FSM) on the published job, in device-mode windows
  (host_placement off) and in host-mode windows, against the exact
  scheduler through the same served path (scheduler_impl "cpu-reference").
  The paths need not choose the same rows or ports; they must agree on
  what holds in every legal execution: the plain recomputation
  (benchmark/reference/guarantees.py) and the ports recomputation
  (benchmark/reference/ports.py) find nothing, every job has exactly its
  Count, the fleet's usage is the same in total.
- what the shape costs the fast path: every eval a launch of its own, the
  exact collect, object plans, the exact half of the applier's fit, a
  NetworkIndex a placement; and that a job without a network ask pays for
  none of it.
- two evals of one window on one node that draw the SAME ports: the
  applier refuses the node, the eval is finished by the exact scheduler,
  no port is doubled.
- a node at its bandwidth limit (the CPU ask lowered so that bandwidth
  binds first): 19 of 50 MBits fit beside the 1 the node reserves, the
  20th is refused, the eval ends short, not over the limit.
- a fleet filled to the guard and past the brim through one dev-mode
  Agent and the benchmark's closed loop: what cannot be placed is a failed
  operation, never a doubled port.
- benchmark/reference/ports.py on states broken in each way it must name.
"""

import copy
import json
import os
import random
import time
import types

import numpy as np
import pytest

from benchmark.deploy import dev_agent_web
from benchmark.deploy.dev_agent import build_fleet, seeded_uuid
from benchmark.generators import closed_loop
from benchmark.reference import guarantees, ports
from nomad_tpu import mock
from nomad_tpu.scheduler import stack as stack_module
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.pipelined_worker import PipelinedWorker
from nomad_tpu.structs import Job, from_dict
from nomad_tpu.telemetry import metrics

from helpers import wait_for  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "web-10k.json")) as _f:
    CONFIG = json.load(_f)
WEB = "web-10"
TERMINAL = ("complete", "failed", "canceled")


def _job(rng, cpu=None, count=None, networks=True):
    job = from_dict(Job, CONFIG["jobs"][WEB])
    job.ID = seeded_uuid(rng)
    job.Name = f"{WEB}-{job.ID[:8]}"
    task = job.TaskGroups[0].Tasks[0]
    if cpu is not None:
        task.Resources.CPU = cpu
    if count is not None:
        job.TaskGroups[0].Count = count
    if not networks:
        task.Resources.Networks = []
        task.Services = []
    return job


def _fleet(n, seed, variants=True):
    fleet = CONFIG["fleet"] if variants \
        else {**CONFIG["fleet"], "rack_variants": []}
    return build_fleet(fleet, n, random.Random(seed))


def _reads(srv):
    state = srv.state
    return {"nodes": state.nodes(), "jobs": state.jobs(),
            "evals": state.evals(), "allocs": state.allocs()}


def _outcome(srv, acknowledged):
    """(verdict with the ports checks in it, ports facts, failed
    operations, live allocations per job, the fleet's usage)."""
    reads = _reads(srv)
    nt = srv.tindex.nt
    failed = guarantees.failed_operations(reads, acknowledged)
    verdict = guarantees.check(reads, acknowledged, failed,
                               np.array(nt.usage, np.float32),
                               dict(nt.row_of))
    facts = ports.judge(reads, verdict)
    counts = [sum(not a.terminal_status()
                  for a in srv.state.allocs_by_job(job_id))
              for job_id, _, _ in acknowledged]
    return verdict, facts, failed, counts, np.array(nt.usage, np.float64)


class Counters:
    """A registry sink that sums the counters."""

    def __init__(self):
        self.sums = {}

    def incr_counter(self, key, value):
        name = ".".join(key)
        self.sums[name] = self.sums.get(name, 0) + value

    def add_sample(self, key, value):
        pass

    def set_gauge(self, key, value):
        pass


@pytest.fixture()
def counters():
    sink = Counters()
    metrics.registry.add_sink(sink)
    yield sink.sums
    with metrics.registry._lock:
        metrics.registry._sinks = [s for s in metrics.registry._sinks
                                   if s is not sink]


# --------------------------------------- the served path, three engines
MODES = {
    # name: (scheduler_impl, host_placement)
    "device-windows": ("tpu", False),
    "host-windows": ("tpu", True),
    "exact-scheduler": ("cpu-reference", True),
}
SERVED_NODES, SERVED_JOBS = 96, 48  # 92 eligible hold 644; 480 asked for


def _serve(impl, host_placement, jobs, nodes):
    """Register `jobs` with the workers parked (so that the windows are
    full), let both go, wait for every eval; returns (server,
    acknowledged). The caller shuts the server down."""
    srv = Server(ServerConfig(num_schedulers=2, pipelined_scheduling=True,
                              scheduler_window=32, scheduler_impl=impl,
                              host_placement=host_placement))
    srv.establish_leadership()
    for node in nodes:
        srv.node_register(node)
    for w in srv.workers:
        w.set_pause(True)
    time.sleep(0.6)  # longer than a parked worker's blocking dequeue
    acknowledged = [(job.ID, srv.job_register(job)[0], WEB) for job in jobs]
    for w in srv.workers:
        w.set_pause(False)
    wait_for(lambda: all(
        getattr(srv.state.eval_by_id(e), "Status", None) in TERMINAL
        for _, e, _ in acknowledged), timeout=120.0, interval=0.01,
        msg="every eval to reach a terminal status")
    for w in srv.workers:
        quiesce = getattr(w, "quiesce", None)
        if quiesce is not None:
            assert quiesce(30.0)
    return srv, acknowledged


@pytest.fixture(scope="module")
def served():
    out = {}
    for mode, (impl, host_placement) in MODES.items():
        rng = random.Random(2 ** 31 + 36)
        jobs = [_job(rng) for _ in range(SERVED_JOBS)]
        srv, acknowledged = _serve(impl, host_placement, jobs,
                                   _fleet(SERVED_NODES, 36))
        try:
            stats = {}
            for w in srv.workers:
                for key, value in getattr(w, "stats", {}).items():
                    stats[key] = stats.get(key, 0) + value
            out[mode] = _outcome(srv, acknowledged) + (stats, _reads(srv))
        finally:
            srv.shutdown()
    return out


@pytest.mark.parametrize("mode", sorted(MODES))
def test_the_published_job_is_served_within_the_guarantees(served, mode):
    verdict, facts, failed, counts, _, stats, _ = served[mode]
    assert verdict.correct, verdict.failures
    assert failed == {}
    assert counts == [10] * SERVED_JOBS
    # Every plan went through the ports recomputation: two ports a
    # placement, 50 MBits each, at most 7 to a node beside the 1 reserved.
    assert facts["ports_checked"] == 2 * 10 * SERVED_JOBS
    assert 0.051 <= facts["max_mbits_share"] <= (7 * 50 + 1) / 1000
    assert facts["nodes_with_ports"] >= 10 * SERVED_JOBS / 7
    if mode == "exact-scheduler":
        assert stats == {}  # plain Workers: no window, no fast path
    else:
        assert stats["fast"] + stats["fallback"] == SERVED_JOBS
        assert stats["slow"] == stats["stale"] == 0
        host = mode == "host-windows"
        assert (stats["host"] == SERVED_JOBS) is host
        assert (stats["launches"] == 0) is host


@pytest.mark.parametrize("mode", ["device-windows", "host-windows"])
def test_the_fast_path_agrees_with_the_exact_scheduler(served, mode):
    fast, exact = served[mode][4], served["exact-scheduler"][4]
    np.testing.assert_allclose(fast.sum(axis=0), exact.sum(axis=0),
                               rtol=0, atol=1e-2)
    # Bandwidth is a column of the usage table: 50 MBits an allocation on
    # top of 1 reserved a node (never-ready and ineligible nodes included).
    assert fast[:, 4].sum() == pytest.approx(
        50 * 10 * SERVED_JOBS + SERVED_NODES)
    # A service spreads over nodes (job anti-affinity): on either path no
    # node holds two allocations of one job while emptier nodes exist.
    for reads in (served[mode][6], served["exact-scheduler"][6]):
        per_node = {}
        for a in reads["allocs"]:
            per_node.setdefault((a.JobID, a.NodeID), []).append(a.ID)
        assert max(len(v) for v in per_node.values()) == 1


def test_what_a_network_ask_costs_the_fast_path(served):
    """The counters the cell's account reads: every eval its own launch,
    the exact collect, object plans, a NetworkIndex a placement."""
    stats = served["device-windows"][5]
    placed = stats["fast"]  # a fallback is collected, then re-run exactly
    assert stats["multi"] == 0
    assert stats["launches"] == stats["launch_evals"] == stats["launch_keys"]
    assert stats["launches"] >= placed
    assert stats["launch_steps"] == 16 * stats["launches"]
    assert stats["launch_placements"] == 10 * stats["launches"]
    assert stats["collect_windowed"] == 0
    assert stats["collect_exact"] >= placed
    assert stats["plans_columnar"] == 0 and stats["plans_objects"] >= placed
    assert stats["net_offers"] == 10 * stats["collect_exact"]
    # Anti-affinity puts a job's ten placements on ten nodes: the eval's
    # index cache never hits.
    assert stats["netidx_builds"] == stats["net_offers"]
    assert stats["net_refused"] == 0
    assert stats["t_netassign_ms"] > 0
    assert stats["t_netassign_ms"] < stats["t_collect_ms"]
    host = served["host-windows"][5]
    assert host["net_offers"] == 10 * host["collect_exact"] > 0
    assert host["launches"] == host["plans_columnar"] == 0


# ---------------------------------- one worker, windows driven by hand
def _server(host_placement, nodes):
    srv = Server(ServerConfig(num_schedulers=0, pipelined_scheduling=True,
                              scheduler_window=32,
                              host_placement=host_placement))
    srv.establish_leadership()
    for node in nodes:
        srv.node_register(node)
    worker = PipelinedWorker(
        srv.raft, srv.eval_broker, srv.plan_queue, srv.blocked_evals,
        srv.tindex, ["service", "batch", "system"], window=32,
        host_placement=host_placement)
    worker.name = "w-web"
    return srv, worker


def _run_window(worker):
    batch = worker._dequeue_window()
    assert batch
    work = worker._dispatch_window(batch)
    assert work is not None and not work.slow
    work.packed = worker._drain_window(work)
    worker._finish_fast(work)
    worker._arbiter.mark_settled(work.chain_seq)
    worker._arbiter.finish_window()
    return work


def test_the_netassign_span_is_one_an_eval_and_none_without_a_network(
        monkeypatch, counters):
    opened = []

    class Span:
        def __init__(self, name, **attrs):
            self.item = (name, attrs)

        def __enter__(self):
            opened.append(self.item)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(metrics, "_annotation", Span)
    srv, worker = _server(False, _fleet(40, 7))
    rng = random.Random(11)
    try:
        for _ in range(3):
            srv.job_register(_job(rng, networks=False))
        work = _run_window(worker)
        plain = dict(worker.stats)
        assert not [s for s in opened if s[0] == "nomad.worker.netassign"]
        assert plain["net_offers"] == plain["netidx_builds"] == 0
        assert plain["t_netassign_ms"] == 0.0
        assert plain["launches"] == plain["multi"] == 1  # shared, fused
        assert plain["collect_windowed"] == plain["plans_columnar"] == 3
        # The applier decided every node in the vector half.
        assert counters["nomad.plan.verify.vector_nodes"] == 30
        assert "nomad.plan.verify.exact_nodes" not in counters
        assert "nomad.plan.partial.ports" not in counters

        opened.clear()
        for _ in range(3):
            srv.job_register(_job(rng))
        work = _run_window(worker)
        spans = [s for s in opened if s[0] == "nomad.worker.netassign"]
        assert spans == [("nomad.worker.netassign",
                          {"worker": "w-web", "window": work.number})] * 3
        # ... nested in the window's collect span.
        names = [s[0] for s in opened]
        assert names.index("nomad.worker.collect") \
            < names.index("nomad.worker.netassign")
        delta = {k: worker.stats[k] - plain[k] for k in plain}
        assert delta["launches"] == 3 and delta["multi"] == 0
        assert delta["net_offers"] == delta["netidx_builds"] == 30
        assert delta["collect_exact"] == delta["plans_objects"] == 3
        assert 0 < delta["t_netassign_ms"] < delta["t_collect_ms"]
        # ... and every node of those plans took the exact half.
        assert counters["nomad.plan.verify.exact_nodes"] == 30
        assert counters["nomad.plan.verify.vector_nodes"] == 30
    finally:
        srv.shutdown()


@pytest.mark.parametrize("host_placement", [False, True],
                         ids=["device-window", "host-window"])
def test_two_evals_that_draw_the_same_ports_on_one_node(
        monkeypatch, counters, host_placement):
    """Every eval's stack gets a port generator of the same seed, so two
    evals of one window that put an allocation on the same node draw the
    same two ports for it, blind to each other (an eval's NetworkIndex and
    proposed allocations are its own). The applier's exact fit refuses
    the node for the later plan, the plan commits in part, and the eval is
    finished by the exact scheduler against the committed ports."""
    real = random.Random
    monkeypatch.setattr(stack_module, "random", types.SimpleNamespace(
        Random=lambda *seed: real(*seed) if seed else real(20000)))
    # 12 nodes, 11 ready and eligible: three jobs of 10 have to share.
    srv, worker = _server(host_placement, _fleet(12, 3, variants=False))
    rng = random.Random(22)
    try:
        acknowledged = []
        for _ in range(3):
            job = _job(rng)
            acknowledged.append((job.ID, srv.job_register(job)[0], WEB))
        _run_window(worker)
        verdict, facts, failed, counts, _ = _outcome(srv, acknowledged)
        stats = worker.stats
    finally:
        srv.shutdown()
    assert verdict.correct, verdict.failures
    assert failed == {} and counts == [10, 10, 10]
    assert facts["ports_checked"] == 60
    assert facts["nodes_with_ports"] in (10, 11)  # shared: 30 on at most 11
    # The first plan commits whole; the two behind it meet its ports.
    assert stats["fast"] == 1 and stats["fallback"] == 2
    assert stats["net_refused"] == 0  # each eval's own index saw no clash
    assert counters["nomad.plan.partial.ports"] >= 2
    assert counters["nomad.plan.verify.exact_nodes"] >= 30


@pytest.mark.parametrize("host_placement", [False, True],
                         ids=["device-window", "host-window"])
def test_a_node_at_its_bandwidth_limit_takes_19_and_refuses_the_20th(
        host_placement):
    """3 nodes, 2 ready. At 100 MHz a node holds 39 by CPU and 31 by
    memory, and (1,000 - 1) // 50 = 19 by bandwidth, which binds: a job of
    45 places 38 and ends short with a blocked follow-up."""
    srv, worker = _server(host_placement, _fleet(3, 5, variants=False))
    rng = random.Random(50)
    try:
        job = _job(rng, cpu=100, count=45)
        acknowledged = [(job.ID, srv.job_register(job)[0], WEB)]
        _run_window(worker)
        verdict, facts, failed, counts, usage = _outcome(srv, acknowledged)
        ev = srv.state.eval_by_id(acknowledged[0][1])
        stats = worker.stats
    finally:
        srv.shutdown()
    assert verdict.correct, verdict.failures
    assert counts == [38]
    assert failed == {job.ID: "eval complete but short: blocked follow-up"}
    assert ev.BlockedEval and ev.FailedTGAllocs["web"].CoalescedFailures == 6
    # ... and says which dimension ran out, on both nodes.
    assert set(ev.FailedTGAllocs["web"].DimensionExhausted) == {"bandwidth"}
    assert ev.FailedTGAllocs["web"].NodesExhausted == 2
    assert facts["max_mbits_share"] == 0.951
    assert facts["nodes_with_ports"] == 2 and facts["ports_checked"] == 76
    assert sorted(usage[:, 4].tolist(), reverse=True)[:3] == [951, 951, 1]
    # The kernel holds bandwidth as the usage table's fifth column: the
    # 20th was never offered to the NetworkIndex.
    assert stats["net_refused"] == 0 and stats["net_offers"] == 38
    assert stats["fast"] == 1 and stats["fallback"] == 0


# ------------------------------------------------- a fill to the brim
def _small_deployment(seed):
    config = copy.deepcopy(CONFIG)
    config["fleet"].update(nodes=40, table_rows=64)
    config["warmup"] = {"kind": "jobs", "template": WEB, "count": 2}
    # dev_agent's Deployment takes the rng the harness seeds; 40 nodes are
    # this configuration's whole fleet, so the job is the published one.
    return dev_agent_web.Deployment(config, random.Random(seed))


@pytest.mark.parametrize("guard,short", [(0.9, False), (1.3, True)],
                         ids=["to-the-guard", "past-the-brim"])
def test_a_fill_ends_in_blocked_evals_never_in_a_doubled_port(guard, short):
    dep = _small_deployment(2 ** 31 + 9)
    assert dep.full_size
    traffic = {"outstanding": 8, "poll_ms": 5, "templates": {WEB: 1},
               "fill_guard": guard}
    try:
        dep.start()
        window = closed_loop.run(dep, traffic, random.Random(1), 60.0)
        assert any("fill guard" in text for text in window["notes"])
        undrained = dep.drain(60.0)
        usage, row_of = dep.device_usage()
        reads = dep.reads()
        room = guarantees.capacity_allocs(reads["nodes"], dep.make_job(WEB))
        verdict, failed = guarantees.judge(reads, dep.acknowledged, usage,
                                           row_of, undrained, "cpu",
                                           rehearsal=True)
        facts = ports.check(dep, 0, verdict)
    finally:
        dep.shutdown()
    assert verdict.correct, verdict.failures
    live = {}
    for a in reads["allocs"]:
        if not a.terminal_status():
            live[a.JobID] = live.get(a.JobID, 0) + 1
    done = [j for j, _, _ in dep.acknowledged if j not in failed]
    assert all(live[j] == 10 for j in done)
    assert facts["ports_checked"] == 2 * sum(live.values())
    eligible = room // 7
    assert 37 <= eligible <= 39 and room == 7 * eligible
    if short:
        # Asked for 1.3 times the fleet: it fills to the brim, 7 to a node
        # by CPU (351 of 1,000 MBits), and the rest are failed operations.
        assert sum(live.values()) == room
        assert facts["max_mbits_share"] == 0.351
        assert len(failed) >= len(dep.acknowledged) - room // 10 > 0
    else:
        assert failed == {}
        assert dep.asked >= guard * room > sum(live.values()) - 10


# ------------------------------------- the ports check on broken states
@pytest.fixture(scope="module")
def packed_state(served):
    """The host-mode run's store: 480 allocations, nodes that hold seven."""
    return served["host-windows"][6]


def _copied(reads):
    return {"nodes": reads["nodes"], "jobs": reads["jobs"],
            "allocs": copy.deepcopy(reads["allocs"])}


def _two_on_one_node(allocs):
    by_node = {}
    for a in allocs:
        by_node.setdefault(a.NodeID, []).append(a)
    return next(v for v in by_node.values() if len(v) >= 2)[:2]


def _net(alloc):
    return alloc.TaskResources["web"].Networks[0]


def _a_doubled_port(reads):
    a, b = _two_on_one_node(reads["allocs"])
    _net(b).DynamicPorts[0].Value = _net(a).DynamicPorts[1].Value
    return "9_ports_unique", f"{a.ID} and {b.ID}"


def _the_nodes_port_22_taken(reads):
    a = reads["allocs"][0]
    _net(a).DynamicPorts[0].Value = 22
    return "9_ports_reserved", f"{a.ID}: 192.168.0.100:22"


def _an_ip_outside_the_cidr(reads):
    a = reads["allocs"][1]
    _net(a).IP = "192.168.0.101"
    return "9_ports_offer", f"{a.ID}/web: eth0 192.168.0.101"


def _another_device(reads):
    a = reads["allocs"][2]
    _net(a).Device = "eth1"
    return "9_ports_offer", f"{a.ID}/web: eth1"


def _a_label_missing(reads):
    a = reads["allocs"][3]
    del _net(a).DynamicPorts[1]
    return "9_ports_labels", f"{a.ID}/web: dynamic port labels differ"


def _a_port_outside_the_dynamic_range(reads):
    a = reads["allocs"][4]
    _net(a).DynamicPorts[1].Value = 60000
    return "9_ports_labels", f"{a.ID}/web: a dynamic port outside"


def _no_offer_at_all(reads):
    a = reads["allocs"][5]
    a.TaskResources["web"].Networks = []
    return "9_ports_labels", f"{a.ID}/web: 0 networks offered, 1 asked for"


def _1001_mbits(reads):
    """Twenty of 50 MBits on one node beside the 1 it reserves."""
    a, _ = _two_on_one_node(reads["allocs"])
    on_node = [x for x in reads["allocs"] if x.NodeID == a.NodeID]
    for k in range(20 - len(on_node)):
        extra = copy.deepcopy(a)
        extra.ID = f"extra-{k}"
        for i, port in enumerate(_net(extra).DynamicPorts):
            port.Value = 30000 + 2 * k + i
        reads["allocs"].append(extra)
    return "10_bandwidth", f"{a.NodeID}/eth0: 1001 MBits of 1000"


BROKEN = [_a_doubled_port, _the_nodes_port_22_taken, _an_ip_outside_the_cidr,
          _another_device, _a_label_missing,
          _a_port_outside_the_dynamic_range, _no_offer_at_all, _1001_mbits]


def test_the_ports_check_passes_the_state_it_is_broken_from(packed_state):
    verdict = guarantees.Verdict()
    facts = ports.judge(_copied(packed_state), verdict)
    assert verdict.correct, verdict.failures
    assert facts == {"ports_checked": 960, "max_mbits_share": 0.351,
                     "nodes_with_ports": len(
                         {a.NodeID for a in packed_state["allocs"]})}
    assert 480 / 7 <= facts["nodes_with_ports"] <= 92


@pytest.mark.parametrize("break_it", BROKEN,
                         ids=lambda f: f.__name__.strip("_"))
def test_the_ports_check_names_what_is_broken(packed_state, break_it):
    reads = _copied(packed_state)
    check, words = break_it(reads)
    verdict = guarantees.Verdict()
    ports.judge(reads, verdict)
    assert not verdict.correct
    named = {f["check"]: f for f in verdict.failures}
    assert check in named, verdict.failures
    assert any(words in text for text in named[check]["ids"]), named[check]
    if break_it in (_a_doubled_port, _1001_mbits, _an_ip_outside_the_cidr,
                    _a_label_missing):
        assert list(named) == [check]  # by one check, not by each


def test_the_ports_check_shares_nothing_with_what_it_checks():
    with open(ports.__file__) as f:
        source = f.read()
    imports = [ln for ln in source.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import ipaddress"]
    for name in ("NetworkIndex(", "Bitmap(", "nomad_tpu"):
        assert name not in source.split('"""', 2)[2]
    from nomad_tpu.structs.structs import MaxDynamicPort, MinDynamicPort
    assert (ports.MIN_DYNAMIC_PORT, ports.MAX_DYNAMIC_PORT) \
        == (MinDynamicPort, MaxDynamicPort)
    assert from_dict(Job, CONFIG["jobs"][WEB]).TaskGroups[0].Tasks[0] \
        .Resources.Networks[0].MBits == mock.job().TaskGroups[0].Tasks[0] \
        .Resources.Networks[0].MBits == 50
