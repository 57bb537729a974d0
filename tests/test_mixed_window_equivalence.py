"""Fixed-seed equivalence gate for windows that mix job shapes (ISSUE 28):
the six templates of benchmark/configs/dc-50k.json on a small fleet in its
four datacenters, placed by the pipelined worker's device program, by its
host mode, by the exact per-object path (GenericScheduler) and by the CPU
reference's iterator chain. The four need not choose the same rows; they
must agree on what holds in every legal execution: every placement
feasible for its own job (datacenters, constraints, drivers, capacity:
the benchmark's plain recomputation judges them), the same counts per job
and task group, and the same usage in total.

And the counters of what a mixed window costs: a window of k distinct
prepared batches makes k device launches, whose evals and keys add up."""

import json
import os
import random

import numpy as np
import pytest

from benchmark.deploy.dev_agent_dcs import build_fleet, seeded_uuid
from benchmark.reference import guarantees
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.pipelined_worker import PipelinedWorker
from nomad_tpu.structs import Job, from_dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "dc-50k.json")) as _f:
    CONFIG = json.load(_f)
NODES = 96  # 38 / 29 / 19 / 10 over dc1..dc4
KEYS = {t: len(j["TaskGroups"]) for t, j in CONFIG["jobs"].items()}

# Two windows: every template, most of them more than once and interleaved
# (the launch groups them), then a second window on the first's usage.
WINDOWS = [
    ["local-dc1", "global-2tg", "local-dc2", "pair-dc1-dc2", "local-dc1",
     "local-dc3", "global-2tg", "local-dc4", "pair-dc1-dc2", "local-dc1"],
    ["global-2tg", "local-dc4", "pair-dc1-dc2", "global-2tg", "local-dc2",
     "global-2tg"],
]


def _server(host_placement):
    srv = Server(ServerConfig(num_schedulers=0, pipelined_scheduling=True,
                              scheduler_window=16,
                              host_placement=host_placement))
    srv.establish_leadership()
    for node in build_fleet(CONFIG["fleet"], NODES, random.Random(28)):
        srv.node_register(node)
    worker = PipelinedWorker(
        srv.raft, srv.eval_broker, srv.plan_queue, srv.blocked_evals,
        srv.tindex, ["service", "batch", "system"], window=16,
        host_placement=host_placement)
    return srv, worker


def _register(srv, templates, rng, acknowledged):
    for template in templates:
        job = from_dict(Job, CONFIG["jobs"][template])
        job.ID = seeded_uuid(rng)
        job.Name = f"{template}-{len(acknowledged)}"
        acknowledged.append((job.ID, srv.job_register(job)[0], template))


def _run_window(worker, exact):
    """One window as the run loop makes it, one stage after the other; with
    `exact`, every eval of it through the per-eval scheduler instead."""
    batch = worker._dequeue_window()
    assert batch
    if exact:
        for ev, token in batch:
            worker._process_slow(ev, token)
        return
    work = worker._dispatch_window(batch)
    assert work is not None and not work.slow
    work.packed = worker._drain_window(work)
    worker._finish_fast(work)
    worker._arbiter.mark_settled(work.chain_seq)
    worker._arbiter.finish_window()


def _outcome(srv, acknowledged):
    """(what the plain recomputation says, counts per job and group, the
    fleet's usage in total)."""
    state = srv.state
    reads = {"nodes": state.nodes(), "jobs": state.jobs(),
             "evals": state.evals(), "allocs": state.allocs()}
    nt = srv.tindex.nt
    failed = guarantees.failed_operations(reads, acknowledged)
    verdict = guarantees.check(reads, acknowledged, failed,
                               np.array(nt.usage, np.float32),
                               dict(nt.row_of))
    counts = {}
    for job_id, _, template in acknowledged:
        per_group = {}
        for a in state.allocs_by_job(job_id):
            assert not a.terminal_status()
            per_group[a.TaskGroup] = per_group.get(a.TaskGroup, 0) + 1
        counts[job_id] = (template, per_group)
    return verdict, failed, counts, nt.usage.sum(axis=0)


@pytest.fixture(scope="module")
def outcomes():
    out = {}
    for mode in ("device", "host", "exact", "cpu-reference"):
        srv, worker = _server(host_placement=mode != "device")
        worker.scheduler_impl = "cpu-reference" if mode == "cpu-reference" \
            else "tpu"
        try:
            rng, acknowledged = random.Random(2028), []
            for templates in WINDOWS:
                _register(srv, templates, rng, acknowledged)
                _run_window(worker, exact=mode in ("exact", "cpu-reference"))
            out[mode] = _outcome(srv, acknowledged) + (dict(worker.stats),)
        finally:
            srv.shutdown()
    return out


@pytest.mark.parametrize("mode", ["device", "host", "exact", "cpu-reference"])
def test_every_path_places_a_mixed_window_within_the_guarantees(outcomes,
                                                                mode):
    verdict, failed, counts, _, stats = outcomes[mode]
    assert verdict.correct, verdict.failures
    assert failed == {}
    want = {t: {g["Name"]: g["Count"] for g in j["TaskGroups"]}
            for t, j in CONFIG["jobs"].items()}
    assert len(counts) == sum(len(w) for w in WINDOWS)
    for template, per_group in counts.values():
        assert per_group == want[template]
    evals = sum(len(w) for w in WINDOWS)
    if mode == "device":
        assert stats["fast"] == evals and stats["host"] == 0
        assert stats["launch_evals"] == evals
    elif mode == "host":
        assert stats["fast"] == stats["host"] == evals
        assert stats["launches"] == 0
    else:
        assert stats["fast"] == 0


@pytest.mark.parametrize("mode", ["host", "exact", "cpu-reference"])
def test_the_paths_agree_on_counts_and_usage(outcomes, mode):
    _, _, counts, usage, _ = outcomes["device"]
    _, _, other_counts, other_usage, _ = outcomes[mode]
    assert list(counts.values()) == list(other_counts.values())
    np.testing.assert_allclose(other_usage, usage, rtol=0, atol=1e-2)


@pytest.mark.parametrize("templates", [
    ["local-dc1"] * 4,
    ["local-dc1", "global-2tg", "local-dc1", "global-2tg", "local-dc1"],
    ["local-dc1", "local-dc2", "pair-dc1-dc2"],
    sorted(KEYS) * 2,
    ["global-2tg"],
], ids=["one-shape", "two-shapes-interleaved", "three-singles",
        "six-shapes-twice", "one-eval"])
def test_a_window_of_k_shapes_makes_k_launches(templates):
    srv, worker = _server(host_placement=False)
    try:
        _register(srv, templates, random.Random(7), [])
        _run_window(worker, exact=False)
        stats = worker.stats
        shapes = sorted(set(templates))
        assert stats["windows"] == 1
        assert stats["launches"] == len(shapes)
        assert stats["launch_evals"] == stats["fast"] == len(templates)
        assert stats["launch_keys"] == sum(KEYS[t] for t in shapes)
        # A run of two or more is one fused launch; a run of one is single.
        assert stats["multi"] == sum(templates.count(t) > 1 for t in shapes)
        assert stats["node_ctx_hit"] + stats["node_ctx_miss"] == len(
            {tuple(CONFIG["jobs"][t]["Datacenters"]) for t in shapes})
    finally:
        srv.shutdown()
