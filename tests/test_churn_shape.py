"""Fixed-seed gates for the deployment benchmark/configs/svc-10k-churn.json
brings: svc-10k's fleet and jobs already running a standing set, every
`nomad run` paired with the `nomad stop` of the oldest live job.

- one dev-mode Agent as the benchmark runs it (two pipelined workers, host
  placement on) on a 200-node fleet with a standing set of 40 services:
  registrations, each after the stop the deployment pairs it with, queued
  while the workers are parked and taken by one worker as ONE window, then
  the cell's closed loop. After the drain the plain recomputation
  (reference/guarantees.py) and check 11 (reference/churn.py) find
  nothing, the device's usage table is the recomputation after the frees,
  a committed stop plan stops the job's 50 allocations
  (`nomad.plan.stop_rows`), every deregistration is one `stop_evals`, and
  every stopped row, placed as a column, reads back as a terminal object
  (`nomad.state.promote`, one a stopped row).
- the deployment's FIFO on a made-up server under the closed loop: every
  registration paired with one stop sent before it, each job stopped as
  many registrations after it started as the standing set holds, and only
  once seen complete; registrations and stops in flight together never
  past the bound; no stop inside the benchmark's span round `register`.
"""

import copy
import json
import os
import random
import time
import types
from collections import deque

import numpy as np
import pytest

from benchmark.deploy import dev_agent_churn
from benchmark.deploy.dev_agent import WORKER_PARK_S
from benchmark.generators import closed_loop
from benchmark.ops import TERMINAL
from benchmark.reference import churn as churn_check
from benchmark.reference import guarantees
from nomad_tpu.structs import Allocation
from nomad_tpu.telemetry import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "svc-10k-churn.json")) as _f:
    CONFIG = json.load(_f)
TEMPLATE = "service-50"
NODES, STANDING, PAIRS = 200, 40, 12
BOUND = 32  # evals in flight, runs and stops: above the parked 2 x PAIRS


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


def small_config():
    """The file at 200 nodes: its own whole fleet, so the standing set is
    the file's count scaled to it (40), and a warm-up of two jobs."""
    config = copy.deepcopy(CONFIG)
    config["fleet"].update(nodes=NODES, table_rows=256)
    config["standing_jobs"].update(count=STANDING, outstanding=BOUND)
    config["warmup"] = {"kind": "jobs", "template": TEMPLATE, "count": 2}
    return config


def _wait(dep, eval_ids, timeout=120.0):
    deadline = time.monotonic() + timeout
    while any(dep.eval_status(e) not in TERMINAL for e in eval_ids):
        assert time.monotonic() < deadline, "evals never ended"
        time.sleep(0.01)


def _stats(dep):
    return dict(dep.worker_stats())


@pytest.fixture(scope="module")
def churned():
    """A standing set, one parked window of PAIRS registrations (each with
    its stop), then two seconds of the cell's closed loop; drained and
    judged."""
    sink = Counters()
    dep = dev_agent_churn.Deployment(small_config(),
                                     random.Random(2 ** 31 + 41))
    try:
        dep.start()
        metrics.registry.add_sink(sink)  # the agent's start sets the sinks
        assert len(dep.standing) == STANDING
        placed_as_columns = _stats(dep)["plans_columnar"]
        workers = dep.server.workers
        for w in workers:
            w.set_pause(True)
        time.sleep(WORKER_PARK_S)
        before = _stats(dep)
        for _ in range(PAIRS):
            dep.register(dep.make_job(TEMPLATE))
        evals = [e for _, r, d in dep.stopped for e in (r, d)]
        workers[0].set_pause(False)  # one worker: the burst is one window
        try:
            _wait(dep, evals + [e for _, e in list(dep.live)[-PAIRS:]])
            workers[0].quiesce(60.0)
        finally:
            for w in workers:
                w.set_pause(False)
        parked = {k: v - before[k] for k, v in _stats(dep).items()}
        parked_stops = list(dep.stopped)
        traffic = {"outstanding": 8, "poll_ms": 5,
                   "templates": {TEMPLATE: 1}}
        window = closed_loop.run(dep, traffic, random.Random(7), 2.0)
        undrained = dep.drain(60.0)
        usage, row_of = dep.device_usage()
        reads = dep.reads()
        verdict, failed = guarantees.judge(reads, dep.acknowledged, usage,
                                           row_of, undrained, "cpu",
                                           rehearsal=True)
        facts = churn_check.check(dep, 0, verdict)
        host_usage = np.array(dep.server.tindex.nt.usage, np.float32)
        stopped_allocs = {job_id: dep.server.state.allocs_by_job(job_id)
                          for job_id, _, _ in dep.stopped}
        out = types.SimpleNamespace(
            dep=dep, parked=parked, parked_stops=parked_stops, window=window,
            undrained=undrained, usage=usage, host_usage=host_usage,
            row_of=row_of, reads=reads, verdict=verdict, failed=failed,
            facts=facts, counters=dict(sink.sums), stats=_stats(dep),
            placed_as_columns=placed_as_columns,
            stopped_allocs=stopped_allocs)
    finally:
        dep.shutdown()
        with metrics.registry._lock:
            metrics.registry._sinks = [s for s in metrics.registry._sinks
                                       if s is not sink]
    return out


def test_registrations_and_stops_share_a_window(churned):
    parked = churned.parked
    # Each registration stopped the oldest standing job, in order.
    standing = [job_id for job_id, _ in churned.dep.standing]
    assert [j for j, _, _ in churned.parked_stops] == standing[:PAIRS]
    assert parked["windows"] == 1
    assert parked["fast"] == PAIRS  # the registrations, placed as ever
    assert parked["slow"] == parked["stop_evals"] == PAIRS
    assert parked["stop_batched"] == PAIRS  # one batch, none re-run
    assert parked["fallback"] == parked["stale"] == 0


def test_after_the_drain_every_guarantee_and_every_stop_holds(churned):
    assert churned.undrained == []
    assert churned.verdict.correct, churned.verdict.failures
    assert churned.failed == {}
    assert "11_stops" in churned.verdict.compared
    runs = churned.window["ops"]
    # One stop a registration, none owed, none left short.
    assert runs and len(churned.dep.stopped) == PAIRS + len(runs)
    assert churned.dep.owed == 0 and churned.dep.unstopped == []
    assert churned.facts["stopped_jobs"] == len(churned.dep.stopped)
    assert churned.facts["stopped_allocations"] == 50 * len(
        churned.dep.stopped)
    # The live set kept its size: the standing set's.
    live = {a.JobID for a in churned.reads["allocs"]
            if not a.terminal_status()}
    assert len(live) == STANDING + 2  # and the warm-up's two jobs


def test_the_device_usage_table_is_the_recomputation_after_the_frees(
        churned):
    """Check 6 with the frees in it: the stopped allocations hold nothing
    on the device, and the table is what the live allocations sum to."""
    assert churned.verdict.compared["6_device_usage"]["value"] <= 1e-2
    np.testing.assert_allclose(churned.usage, churned.host_usage, atol=1e-2)
    want = np.zeros_like(churned.usage)
    nodes = {n.ID: n for n in churned.reads["nodes"]}
    for nid, row in churned.row_of.items():
        want[row] = guarantees.node_reserved(nodes[nid])
    for a in churned.reads["allocs"]:
        if not a.terminal_status():
            want[churned.row_of[a.NodeID]] += guarantees.alloc_ask(a)
    np.testing.assert_allclose(churned.usage, want, atol=1e-2)
    # What the stopped jobs held is gone from the table: the CPU column is
    # the reserve plus 20 MHz a live allocation, none for a stopped one.
    live = sum(1 for a in churned.reads["allocs"] if not a.terminal_status())
    reserved = sum(guarantees.node_reserved(n)[0] for n in nodes.values())
    assert churned.usage[:, 0].sum() == pytest.approx(reserved + 20 * live)
    assert live == 50 * (STANDING + 2)


def test_a_stop_plan_stops_fifty_and_every_stop_is_one_stop_eval(churned):
    stops = len(churned.dep.stopped)
    assert churned.counters["nomad.plan.stop_rows"] == 50 * stops
    assert churned.stats["stop_evals"] == stops
    assert churned.stats["slow"] == stops


def test_every_stopped_columnar_row_reads_back_as_a_terminal_object(churned):
    assert churned.placed_as_columns >= STANDING  # placed as columns
    for job_id, allocs in churned.stopped_allocs.items():
        assert len(allocs) == 50, job_id
        for a in allocs:
            assert isinstance(a, Allocation)
            assert a.terminal_status() and a.DesiredStatus == "stop"
    # One promotion a stopped row: every one of them was a column.
    assert churned.counters["nomad.state.promote"] \
        == churned.counters["nomad.plan.stop_rows"]


# ------------------------------- the pairing on a made-up server
class FakeServer:
    """What the deployment asks of a server once started, over dicts: an
    eval is `complete` from `latency` seconds after it was sent (the j-th
    registration's latency is latencies[j % len]); every send records the
    evals then truly in flight."""

    def __init__(self, latencies=(0.0,)):
        self.latencies = latencies
        self.sent = []     # ("run" | "stop", job id, time) in the order sent
        self.ends = {}     # eval id -> when it completes
        self.flight = []   # evals truly in flight before each send
        self.read_complete = set()  # evals read complete at least once
        self.reads_at_stop = {}  # job id -> its eval read complete by then
        self.state = types.SimpleNamespace(eval_by_id=self._eval)

    def _send(self, kind, job_id, eval_id, latency):
        now = time.perf_counter()
        self.flight.append(sum(1 for t in self.ends.values() if t > now))
        self.sent.append((kind, job_id, now))
        self.ends[eval_id] = now + latency

    def job_register(self, job):
        runs = sum(1 for kind, _, _ in self.sent if kind == "run")
        self._send("run", job.ID, "eval-" + job.ID,
                   self.latencies[runs % len(self.latencies)])
        return "eval-" + job.ID, 1, 1

    def job_deregister(self, job_id):
        self.reads_at_stop[job_id] = "eval-" + job_id in self.read_complete
        self._send("stop", job_id, "stop-" + job_id, self.latencies[0])
        return "stop-" + job_id, 1

    def _eval(self, eval_id):
        end = self.ends.get(eval_id)
        if end is None:  # a standing job
            return types.SimpleNamespace(Status="complete")
        if time.perf_counter() < end:
            return types.SimpleNamespace(Status="pending")
        self.read_complete.add(eval_id)
        return types.SimpleNamespace(Status="complete")


def _paired(standing, seconds, latencies=(0.0,), bound=BOUND):
    """The deployment's pairing under the cell's closed loop (as many
    registrations outstanding as the bound), on a made-up server; returns
    (deployment, server, the window)."""
    config = small_config()
    config["standing_jobs"]["outstanding"] = bound
    dep = dev_agent_churn.Deployment(config, random.Random(3))
    dep.server = server = FakeServer(latencies)
    for job_id in standing:
        dep.acknowledged.append((job_id, "eval-" + job_id, TEMPLATE))
    dep.live = deque((job_id, "eval-" + job_id) for job_id in standing)
    traffic = {"outstanding": bound, "poll_ms": 1, "templates": {TEMPLATE: 1}}
    try:
        window = closed_loop.run(dep, traffic, random.Random(3), seconds)
    finally:
        dep.shutdown()
    return dep, server, window


def test_each_registration_is_paired_with_the_stop_of_the_oldest():
    lag = 20
    standing = [f"standing-{i}" for i in range(lag)]
    _, server, window = _paired(standing, 0.3)
    runs = [j for kind, j, _ in server.sent if kind == "run"]
    stops = [j for kind, j, _ in server.sent if kind == "stop"]
    assert len(runs) > lag and len(runs) == len(stops)
    # A pair at a time, the stop first.
    assert [kind for kind, _, _ in server.sent] == ["stop", "run"] * len(runs)
    # FIFO: the i-th stop is of the i-th job of the live set, so each job
    # is stopped `lag` registrations after it started.
    assert stops == (standing + runs)[:len(stops)]
    assert stops[lag:] == runs[:len(stops) - lag]
    # The generator's operations are the registrations, 50 asked for each.
    assert [op.job_id for op in window["ops"]] == runs
    assert all(op.asks == 50 for op in window["ops"])


def test_registrations_and_stops_in_flight_never_pass_the_bound():
    # Every eval lasts 30 ms: the bound, not the generator, holds the loop.
    dep, server, window = _paired([f"s-{i}" for i in range(64)], 0.4,
                                  latencies=(0.03,), bound=8)
    assert len(window["ops"]) > 16
    assert max(server.flight) < 8
    assert max(server.flight) >= 3  # and the loop fills it
    # What the deployment read in flight each time it waited, for its facts.
    assert dep.flight_reads
    assert all(runs + stops <= 8 for runs, stops in dep.flight_reads)
    assert max(stops for _, stops in dep.flight_reads) > 0


def test_the_registration_span_holds_no_stop():
    """register() is Server.job_register alone: no stop is sent between an
    operation's `sent` and `acked`, so register_ms.storm reads the
    registration."""
    _, server, window = _paired([f"s-{i}" for i in range(40)], 0.2,
                                latencies=(0.002,), bound=8)
    stops = [t for kind, _, t in server.sent if kind == "stop"]
    assert stops and window["ops"]
    for op in window["ops"]:
        assert not any(op.sent <= t <= op.acked for t in stops)


def test_only_a_job_seen_complete_is_stopped():
    # A live set smaller than what is in flight: the head is a job the
    # window registered, and it is stopped only once it reads complete;
    # meanwhile its stop is owed.
    dep, server, window = _paired(["standing-0"], 0.3,
                                  latencies=(0.05, 0.0, 0.0), bound=8)
    runs = [j for kind, j, _ in server.sent if kind == "run"]
    stops = [j for kind, j, _ in server.sent if kind == "stop"]
    assert len(runs) > 4 and len(stops) > 2
    assert server.reads_at_stop.pop("standing-0") is False  # never sent
    assert all(server.reads_at_stop.values())
    # FIFO all the same, and one stop a registration but the ones owed.
    assert stops == (["standing-0"] + runs)[:len(stops)]
    assert len(stops) + dep.owed == len(runs)
