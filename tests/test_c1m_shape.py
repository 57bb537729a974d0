"""Fixed-seed gates for the shape benchmark/configs/c1m-5k.json brings
(ISSUE 33): jobs of 1,000 placements, padded to 1,024 serial steps an eval,
on a table smaller than the window's candidate count.

- the pipelined fast path against the exact per-eval GenericScheduler
  from the same registrations: a full window of fused 1,024-step evals,
  and a host-mode window in which the jobs of 1,000 are deferred to the
  device behind host-placed small ones (pipelined_worker's `<= 256`
  rule). The paths need not choose the same rows; they must agree on what
  holds in every legal execution: the plain recomputation
  (benchmark/reference/guarantees.py) finds nothing, every job has exactly
  its Count, the fleet's usage is the same in total, and since every node
  is the same machine, the same multiset of per-node usage.
- the keyed program at a pad of 1,024 with a candidate count above the
  table's rows (every step scores the whole table, no trim) against the
  scan oracle place_batch_multi, bit for bit on the CPU, and against the
  numpy mirror place_batch_host.
- a fill to the brim through one dev-mode Agent with its two workers and
  the benchmark's closed loop: up to the guard every job completes with
  1,000 live allocations; asked for more than the fleet holds, what cannot
  be placed is a failed operation, never an incorrect state.
- the counters and span attributes the issue adds.
- the rule the configuration's ask rests on: Server.job_register refuses a
  task asking 16 MHz and accepts the file's 20.
"""

import copy
import json
import os
import random

import numpy as np
import pytest

from benchmark.deploy import dev_agent_c1m
from benchmark.deploy.dev_agent import build_fleet, seeded_uuid
from benchmark.generators import closed_loop
from benchmark.reference import guarantees, kernel_mirror_chain
from nomad_tpu.scheduler import kernels
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.pipelined_worker import PipelinedWorker
from nomad_tpu.structs import Job, from_dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "c1m-5k.json")) as _f:
    CONFIG = json.load(_f)
BIG = "c1m-1000"
NODES = 48  # 47 ready: 11,421 allocations of the template


def _job(template, rng, count=None):
    job = from_dict(Job, CONFIG["jobs"][template])
    job.ID = seeded_uuid(rng)
    job.Name = f"{template}-{job.ID[:8]}"
    if count is not None:
        job.TaskGroups[0].Count = count
    return job


def _server(host_placement):
    srv = Server(ServerConfig(num_schedulers=0, pipelined_scheduling=True,
                              scheduler_window=32,
                              host_placement=host_placement))
    srv.establish_leadership()
    for node in build_fleet(CONFIG["fleet"], NODES, random.Random(32)):
        srv.node_register(node)
    worker = PipelinedWorker(
        srv.raft, srv.eval_broker, srv.plan_queue, srv.blocked_evals,
        srv.tindex, ["service", "batch", "system"], window=32,
        host_placement=host_placement)
    return srv, worker


def _run_window(worker, exact):
    batch = worker._dequeue_window()
    assert batch
    if exact:
        for ev, token in batch:
            worker._process_slow(ev, token)
        return None
    work = worker._dispatch_window(batch)
    assert work is not None and not work.slow
    work.packed = worker._drain_window(work)
    worker._finish_fast(work)
    worker._arbiter.mark_settled(work.chain_seq)
    worker._arbiter.finish_window()
    return work


def _outcome(srv, acknowledged):
    state = srv.state
    reads = {"nodes": state.nodes(), "jobs": state.jobs(),
             "evals": state.evals(), "allocs": state.allocs()}
    nt = srv.tindex.nt
    failed = guarantees.failed_operations(reads, acknowledged)
    verdict = guarantees.check(reads, acknowledged, failed,
                               np.array(nt.usage, np.float32),
                               dict(nt.row_of))
    counts = [sum(not a.terminal_status()
                  for a in state.allocs_by_job(job_id))
              for job_id, _, _ in acknowledged]
    return verdict, failed, counts, np.array(nt.usage, np.float64)


# counts of each window's jobs: a full window of the template (here 8:
# the 47 ready nodes hold 11), then a second on the first's usage; and a
# host-mode window with the jobs of 1,000 behind small ones.
SHAPES = {
    "fused-1024-step-evals": [[1000] * 8, [1000] * 2],
    "deferred-behind-host-placed": [[50, 1000, 50, 200, 1000, 50]],
}


@pytest.fixture(scope="module", params=sorted(SHAPES))
def outcomes(request):
    out = {}
    for mode in ("fast", "exact"):
        # The fused case with host placement off (every eval on the
        # device), the deferred case as shipped (host placement on).
        host = request.param == "deferred-behind-host-placed"
        srv, worker = _server(host_placement=host)
        try:
            rng, acknowledged, works = random.Random(1032), [], []
            for window in SHAPES[request.param]:
                for count in window:
                    job = _job(BIG, rng, count)
                    acknowledged.append(
                        (job.ID, srv.job_register(job)[0], BIG))
                works.append(_run_window(worker, exact=mode == "exact"))
            out[mode] = _outcome(srv, acknowledged) + (
                dict(worker.stats), works)
        finally:
            srv.shutdown()
    return request.param, out


@pytest.mark.parametrize("mode", ["fast", "exact"])
def test_jobs_of_1000_are_placed_within_the_guarantees(outcomes, mode):
    shape, out = outcomes
    verdict, failed, counts, _, stats, _ = out[mode]
    assert verdict.correct, verdict.failures
    assert failed == {}
    assert counts == [c for window in SHAPES[shape] for c in window]
    assert stats["fast"] == (len(counts) if mode == "fast" else 0)
    assert stats["fallback"] == 0


def test_the_fast_path_agrees_with_the_exact_scheduler(outcomes):
    _, out = outcomes
    fast, exact = out["fast"][3], out["exact"][3]
    np.testing.assert_allclose(fast.sum(axis=0), exact.sum(axis=0),
                               rtol=0, atol=1e-2)
    # One machine type, so the two runs differ by a permutation of nodes
    # at most (the tie-break noise): the same multiset of per-node usage.
    order = np.lexsort(fast.T[::-1]), np.lexsort(exact.T[::-1])
    np.testing.assert_allclose(fast[order[0]], exact[order[1]],
                               rtol=0, atol=1e-2)


def test_the_windows_launch_as_the_issue_says(outcomes):
    shape, out = outcomes
    stats, works = out["fast"][4], out["fast"][5]
    if shape == "fused-1024-step-evals":
        # 8 evals: one fused launch of 8 x 1,024 steps; then 2 evals: one
        # of 4 x 1,024 (the eval axis pads to at least four).
        assert stats["host"] == 0 and stats["multi"] == stats["launches"] == 2
        assert stats["launch_evals"] == 10
        assert stats["launch_steps"] == 8 * 1024 + 4 * 1024
        assert stats["launch_placements"] == 10 * 1000
        assert stats["plan_rows"] == 10 * 1000
        assert stats["plans_columnar"] + stats["plans_objects"] == 10
    else:
        # The small jobs on the host, in window order; the two of 1,000
        # deferred, fused into one launch, chained behind them.
        assert stats["host"] == 4 and stats["launches"] == stats["multi"] == 1
        assert stats["launch_evals"] == 2
        assert stats["launch_steps"] == 4 * 1024
        assert stats["launch_placements"] == 2000
        assert stats["plan_rows"] == 50 * 3 + 200 + 2000
        chain = [len(rec.place) for rec in works[0].fast]
        assert chain == [50, 50, 200, 50, 1000, 1000]


def test_the_launch_span_carries_steps_and_placements():
    from nomad_tpu.telemetry import metrics

    seen = []
    real = metrics.measure

    def measure(key, **attrs):
        if tuple(key) == ("nomad", "worker", "launch"):
            seen.append(attrs)
        return real(key, **attrs)

    srv, worker = _server(host_placement=False)
    rng = random.Random(5)
    metrics.measure = measure
    try:
        for count in (1000, 1000, 1000, 50):
            srv.job_register(_job(BIG, rng, count))
        _run_window(worker, exact=False)
    finally:
        metrics.measure = real
        srv.shutdown()
    # One run of three (padded to four evals of 1,024) and one of one (64).
    assert [(a["runs"], a["steps"], a["placements"]) for a in seen] == [
        (2, 4 * 1024 + 64, 3050)]
    assert worker.stats["launch_steps"] == 4 * 1024 + 64
    assert worker.stats["launch_placements"] == 3050


def test_job_register_refuses_16_mhz_and_accepts_the_files_20():
    """structs.Resources.meets_min_resources (the reference's
    MeetsMinResources): an ask under 20 MHz never reaches the broker, so
    the configuration asks for the legal minimum on a 4,960 MHz node."""
    srv, _ = _server(host_placement=True)
    rng = random.Random(16)
    try:
        job = _job(BIG, rng, 50)
        job.TaskGroups[0].Tasks[0].Resources.CPU = 16
        with pytest.raises(ValueError, match="minimum CPU value is 20; "
                                             "got 16"):
            srv.job_register(job)
        assert srv.state.job_by_id(job.ID) is None
        assert srv.eval_broker.stats.TotalReady == 0
        legal = _job(BIG, rng, 50)
        assert legal.TaskGroups[0].Tasks[0].Resources.CPU == 20
        assert srv.job_register(legal)[0]
        assert srv.state.job_by_id(legal.ID) is not None
    finally:
        srv.shutdown()


# ---------------------------------------------- the program, bit for bit
@pytest.fixture(scope="module")
def chain_window():
    # 4 evals of 1,000 in pads of 1,024 over 256 rows: the candidate count
    # is 4,096, sixteen times the table.
    inp = kernel_mirror_chain.window_inputs(CONFIG, BIG, 2 ** 31 + 32, 256,
                                            200, 4)
    launch = inp["launches"][0]
    assert kernels.keyed_cand_count(launch["n_valid"]) == 4096 > 256
    assert len(launch["valid"]) == 4 * 1024
    packed, usage_after = kernel_mirror_chain.run_keyed(inp)
    return inp, launch, packed[0], usage_after


def test_the_keyed_program_equals_the_scan_oracle_bit_for_bit(chain_window):
    inp, launch, packed, usage_after = chain_window
    n, p = inp["capacity"].shape[0], len(launch["valid"])
    oracle = kernels.place_batch_multi(
        inp["capacity"], inp["score_cap"], inp["usage"], launch["masks"],
        np.zeros(n, np.int32), np.tile(launch["asks"][0], (p, 1)),
        launch["tg_ids"], launch["valid"], inp["noise"], inp["penalty"],
        np.asarray(False), np.zeros(n, bool), launch["reset"])
    v = launch["valid"]
    want = np.asarray(oracle.packed)
    assert (packed[v, 0] >= 0).all()
    np.testing.assert_array_equal(packed[v], want[v])
    np.testing.assert_array_equal(usage_after,
                                  np.asarray(oracle.usage_after))


def test_the_keyed_program_equals_the_host_mirror(chain_window):
    inp, launch, packed, usage_after = chain_window
    mirror = kernel_mirror_chain.run_mirror(inp)[0]
    v = launch["valid"]
    # Rows and feasible counts are equal; the scores agree to the last
    # place or two (XLA's exp2 and numpy's round differently), as
    # tests/test_tensor_and_kernels.py finds at a pad of 64.
    np.testing.assert_array_equal(packed[v][:, [0, 2]], mirror[v][:, [0, 2]])
    np.testing.assert_allclose(packed[v, 1], mirror[v, 1], rtol=1e-5,
                               atol=1e-4)
    facts = kernel_mirror_chain.chain_facts(inp, [packed])
    assert facts["max_adds_on_a_row"] > 64  # chains no window of 50s has
    assert facts["max_job_count_on_a_row"] > 1


# -------------------------------------------------- a fill to the brim
def _small_deployment(seed):
    config = copy.deepcopy(CONFIG)
    config["fleet"].update(nodes=26, table_rows=64, computed_classes=26)
    config["warmup"]["bursts"] = [1, 2]
    config["warmup"]["programs"] = config["warmup"]["programs"][:2]
    # dev_agent's Deployment takes the rng the harness seeds.
    return dev_agent_c1m.Deployment(config, random.Random(seed))


@pytest.mark.parametrize("guard,failed_ops", [(0.9, 0), (1.3, 2)],
                         ids=["to-the-guard", "past-the-brim"])
def test_a_fill_to_the_brim(guard, failed_ops):
    """25 ready nodes hold 6,075 allocations. The guard (90 %: 5,467) stops
    the loop after the sixth job (the warm-up's three and three more: 6,000
    asked for, 98.8 % of the fleet) and all six complete. Asked for 1.3
    times the fleet (eight jobs), six complete, the seventh places its 75
    and blocks for the rest, the eighth places nothing: two failed
    operations, and no state is incorrect."""
    dep = _small_deployment(2 ** 31 + 7)
    traffic = {"outstanding": 4, "poll_ms": 5, "templates": {BIG: 1},
               "fill_guard": guard}
    try:
        dep.start()
        assert dep.facts()["job_count"] == 1000
        assert [b["launch_evals"] for b in dep.bursts] == [1, 2]
        window = closed_loop.run(dep, traffic, random.Random(1), 60.0)
        assert any("fill guard" in text for text in window["notes"])
        undrained = dep.drain(60.0)
        usage, row_of = dep.device_usage()
        reads = dep.reads()
        stats = dep.worker_stats()
    finally:
        dep.shutdown()
    verdict, failed = guarantees.judge(reads, dep.acknowledged, usage,
                                       row_of, undrained, "cpu",
                                       rehearsal=True)
    assert verdict.correct, verdict.failures
    assert len(failed) == failed_ops
    live = {}
    for a in reads["allocs"]:
        if not a.terminal_status():
            live[a.JobID] = live.get(a.JobID, 0) + 1
    done = [job_id for job_id, _, _ in dep.acknowledged
            if job_id not in failed]
    assert len(done) == 6 and all(live[j] == 1000 for j in done)
    room = guarantees.capacity_allocs(reads["nodes"], dep.make_job(BIG))
    assert room == 25 * 243
    assert sum(live.values()) == (room if failed_ops else 6000)
    # Jobs of 1,000 never place on the host: every one was launched.
    assert stats["host"] == 0 and stats["launch_evals"] >= 6
