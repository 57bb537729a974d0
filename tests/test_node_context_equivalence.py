"""Fixed-seed equivalence gate for the node-context memo (ISSUE 27): the
same sequence of windows placed with the memo, and with the memo emptied
before every window (the TEST empties it; the program has no switch), must
give identical allocations, FailedTGAllocs and blocked-eval class
eligibility — across a node going down and a job re-registered under its
id with another constraint between two windows."""

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.pipelined_worker import PipelinedWorker
from nomad_tpu.structs import Constraint, compute_node_class
from nomad_tpu.structs.structs import EvalStatusBlocked, NodeStatusDown


def _job(count, cpu=500, mem=64, constraint=None):
    job = mock.job()
    tg = job.TaskGroups[0]
    tg.Count = count
    task = tg.Tasks[0]
    task.Resources.Networks = []
    task.Resources.CPU = cpu
    task.Resources.MemoryMB = mem
    task.Services = []
    if constraint is not None:
        job.Constraints.append(Constraint(
            LTarget="${node.class}", RTarget=constraint, Operand="="))
    return job


def _fleet():
    fleet = []
    for i in range(6):
        node = mock.node()
        node.NodeClass = "small" if i < 4 else "large"
        node.Resources.CPU = 2100 if i < 4 else 4100  # 4 or 8 asks of 500
        compute_node_class(node)
        fleet.append(node)
    return fleet


def _run_until_idle(worker, emptied, tindex):
    """Windows as the run loop makes them, one stage after the other."""
    windows = 0
    while True:
        if emptied:
            with tindex._ctx_lock:
                tindex._node_ctx.clear()
        batch = worker._dequeue_window()
        if not batch:
            return windows
        batch.sort(key=lambda p: (p[0].JobID, p[0].TriggeredBy))
        work = worker._dispatch_window(batch)
        windows += 1
        if work is None:
            continue
        if work.fast:
            work.packed = worker._drain_window(work)
            worker._finish_fast(work)
        for ev, token in work.slow:
            worker._process_slow(ev, token)
        if work.published:  # as the build loop leaves a window
            worker._arbiter.mark_settled(work.chain_seq)
            worker._arbiter.finish_window()


def _outcome(srv, jobs):
    out = {}
    for name, job in jobs.items():
        allocs = sorted((a.Name, a.NodeID, a.DesiredStatus)
                        for a in srv.state.allocs_by_job(job.ID))
        evals = []
        for ev in srv.state.evals_by_job(job.ID):
            failed = {
                tg: (m.NodesEvaluated, m.NodesFiltered, m.NodesExhausted,
                     dict(m.NodesAvailable), dict(m.ClassFiltered),
                     dict(m.ConstraintFiltered), dict(m.ClassExhausted),
                     dict(m.DimensionExhausted), m.CoalescedFailures)
                for tg, m in (ev.FailedTGAllocs or {}).items()}
            evals.append((ev.TriggeredBy, ev.Status, failed,
                          dict(ev.ClassEligibility or {}),
                          ev.EscapedComputedClass, bool(ev.BlockedEval)))
        out[name] = (allocs, sorted(evals, key=repr))
    return out


def test_memo_and_emptied_memo_place_identically(monkeypatch):
    # Zero tie-break noise: placements are a pure function of the fleet
    # and the order of the windows.
    monkeypatch.setattr(
        "nomad_tpu.scheduler.stack.make_noise_vec",
        lambda n_rows, rng: np.zeros(n_rows, dtype=np.float32))
    fleet = _fleet()
    jobs = {
        "a": _job(3), "b": _job(3),                # one shape: shared batch
        "c": _job(2, constraint="nowhere"),        # blocks: no such class
        "d": _job(3), "e": _job(2, cpu=300),
        "f": _job(40),                             # more than the fleet holds
        "g": _job(2, constraint="large"),
    }
    c_again = jobs["c"].copy()                     # same id, other constraint
    c_again.Constraints[-1] = Constraint(
        LTarget="${node.class}", RTarget="large", Operand="=")
    results, stats = {}, {}
    for mode in ("memo", "emptied"):
        srv = Server(ServerConfig(num_schedulers=0,
                                  pipelined_scheduling=True,
                                  scheduler_window=16))
        srv.establish_leadership()
        try:
            for node in fleet:
                srv.node_register(node.copy())
            worker = PipelinedWorker(
                srv.raft, srv.eval_broker, srv.plan_queue,
                srv.blocked_evals, srv.tindex,
                ["service", "batch", "system"], window=16)
            emptied = mode == "emptied"

            for name in ("a", "b", "c"):
                srv.job_register(jobs[name].copy())
            assert _run_until_idle(worker, emptied, srv.tindex) == 1

            # Job c comes back under its id asking for another class, in
            # a quiet window: the memo serves it the context (and job d
            # the batch) of the window before.
            srv.job_register(c_again.copy())
            srv.job_register(jobs["d"].copy())
            assert _run_until_idle(worker, emptied, srv.tindex) == 1

            # A node holding allocations goes down (the TTL's path): the
            # jobs on it are re-evaluated, and e lands beside them.
            held = sorted({a.NodeID for a in srv.state.allocs()})
            assert held
            srv.node_update_status(held[0], NodeStatusDown)
            srv.job_register(jobs["e"].copy())
            assert _run_until_idle(worker, emptied, srv.tindex) >= 1

            # Quiet windows again.
            for name in ("f", "g"):
                srv.job_register(jobs[name].copy())
                _run_until_idle(worker, emptied, srv.tindex)

            results[mode] = _outcome(srv, jobs)
            stats[mode] = dict(worker.stats)
            down = held[0]
            live = [a for a in srv.state.allocs()
                    if not a.terminal_status() and a.DesiredStatus == "run"]
            assert all(a.NodeID != down for a in live)
        finally:
            srv.shutdown()

    assert results["memo"] == results["emptied"]
    # Non-vacuous: the memo served windows on one side and none on the
    # other; everything rode the fast path or the per-eval path alike.
    assert stats["memo"]["node_ctx_hit"] >= 2
    assert stats["emptied"]["node_ctx_hit"] == 0
    assert stats["emptied"]["node_ctx_miss"] \
        == stats["memo"]["node_ctx_hit"] + stats["memo"]["node_ctx_miss"]
    for key in ("fast", "slow", "fallback", "windows"):
        assert stats["memo"][key] == stats["emptied"][key], key
    got = results["memo"]
    # c placed nothing at first and was blocked with every class ruled
    # out; under the same id with another constraint it placed on the
    # large class: a per-job view kept across windows would have failed it.
    c_allocs, c_evals = got["c"]
    assert len([a for a in c_allocs if a[2] == "run"]) == 2
    large = {n.ID for n in fleet if n.NodeClass == "large"}
    assert {a[1] for a in c_allocs} <= large
    assert any(ev[2] and ev[5] for ev in c_evals)  # failed and blocked once
    # f ran out of room on a batch prepared for another job's window: its
    # blocked eval still carries ITS class eligibility.
    f_allocs, f_evals = got["f"]
    assert 0 < len(f_allocs) < 40
    blocked = [ev for ev in f_evals if ev[1] == EvalStatusBlocked]
    assert blocked and all(ev[3] for ev in blocked)
    assert all(v for ev in blocked for v in ev[3].values())


@pytest.mark.parametrize("same_window", [True, False])
def test_a_job_that_adopts_a_batch_reports_its_own_class_eligibility(
        same_window):
    """Two jobs of one shape that both run out of room: the second adopts
    the first's prepared batch (in its window, or a later one) and its
    blocked eval carries the same class eligibility as the first's."""
    srv = Server(ServerConfig(num_schedulers=0, pipelined_scheduling=True,
                              scheduler_window=16))
    srv.establish_leadership()
    try:
        for node in _fleet():
            srv.node_register(node)
        worker = PipelinedWorker(
            srv.raft, srv.eval_broker, srv.plan_queue, srv.blocked_evals,
            srv.tindex, ["service", "batch", "system"], window=16)
        first, second = _job(30, constraint="small"), \
            _job(30, constraint="small")
        srv.job_register(first)
        if not same_window:
            _run_until_idle(worker, False, srv.tindex)
        srv.job_register(second)
        _run_until_idle(worker, False, srv.tindex)
        elig = []
        for job in (first, second):
            blocked = [ev for ev in srv.state.evals_by_job(job.ID)
                       if ev.Status == EvalStatusBlocked]
            assert len(blocked) == 1
            elig.append(blocked[0].ClassEligibility)
        assert elig[0] and elig[0] == elig[1]
        assert sorted(elig[0].values()) == [False, True]
    finally:
        srv.shutdown()
