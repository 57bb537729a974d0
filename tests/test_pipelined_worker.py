"""PipelinedWorker: the windowed device-chained served scheduling path.

Covers: burst placement through the fast path (correctness + no
oversubscription), mixed fast/slow windows, blocked-eval creation on
exhaustion through the fast path, and parity of outcomes with the per-eval
GenericScheduler (reference behavior model: nomad/worker.go + the plan
applier's re-verification making optimistic chaining safe)."""


import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs.structs import EvalStatusComplete
from nomad_tpu.tensor.node_table import alloc_vec, resources_vec


from helpers import wait_for  # noqa: E402

def simple_job(count=4, cpu=None, mem=None):
    """mock.job() without networks (ports are host-side; these tests target
    the device placement path) — services referencing ports go with them."""
    job = mock.job()
    tg = job.TaskGroups[0]
    tg.Count = count
    task = tg.Tasks[0]
    task.Resources.Networks = []
    task.Services = []
    if cpu is not None:
        task.Resources.CPU = cpu
    if mem is not None:
        task.Resources.MemoryMB = mem
    return job


def make_server(**overrides):
    cfg = ServerConfig(num_schedulers=1, pipelined_scheduling=True,
                       scheduler_window=16, **overrides)
    srv = Server(cfg)
    srv.establish_leadership()
    return srv


def total_usage_by_node(state):
    usage = {}
    for alloc in state.allocs():
        if alloc.terminal_status():
            continue
        v = usage.setdefault(alloc.NodeID, np.zeros(5, dtype=np.float64))
        v += alloc_vec(alloc)
    return usage


class TestPipelinedBurst:
    def test_burst_of_jobs_all_place_fast_path(self):
        """A registration storm drains through the device-chained window and
        every eval completes with committed allocations."""
        srv = make_server()
        try:
            for _ in range(20):
                srv.node_register(mock.node())
            jobs = [simple_job(count=4) for _ in range(12)]
            eval_ids = [srv.job_register(j)[0] for j in jobs]
            assert wait_for(lambda: all(
                (e := srv.state.eval_by_id(eid)) is not None
                and e.Status == EvalStatusComplete for eid in eval_ids))
            for job in jobs:
                allocs = [a for a in srv.state.allocs_by_job(job.ID)
                          if not a.terminal_status()]
                assert len(allocs) == 4, job.ID
            # The fast path actually ran (not everything fell back).
            stats = srv.workers[0].stats
            assert stats["fast"] > 0
        finally:
            srv.shutdown()

    def test_no_oversubscription_after_burst(self):
        """Optimistic chaining must never commit more than a node's capacity
        (the plan applier re-verifies every placement)."""
        srv = make_server()
        try:
            nodes = []
            for _ in range(4):
                n = mock.node()
                nodes.append(n)
                srv.node_register(n)
            # Enough demand to pack nodes near-full: 4 nodes x 4000 cpu,
            # each alloc asks 500 cpu -> exactly 32 fit.
            jobs = [simple_job(count=4, cpu=500, mem=256)
                    for _ in range(10)]
            eval_ids = [srv.job_register(j)[0] for j in jobs]
            assert wait_for(lambda: all(
                srv.state.eval_by_id(eid) is not None
                and srv.state.eval_by_id(eid).Status not in ("pending",)
                for eid in eval_ids), timeout=20)
            usage = total_usage_by_node(srv.state)
            caps = {n.ID: resources_vec(n.Resources) for n in nodes}
            for node_id, used in usage.items():
                assert np.all(used <= caps[node_id] + 1e-6), (
                    f"node {node_id} oversubscribed: {used} > {caps[node_id]}")
        finally:
            srv.shutdown()

    def test_exhaustion_creates_blocked_eval_via_fast_path(self):
        srv = make_server()
        try:
            n = mock.node()
            n.Resources.CPU = 1000
            srv.node_register(n)
            job = simple_job(count=6, cpu=500)  # 6 x 500 cpu > 1000 cpu
            eval_id, _, _ = srv.job_register(job)
            assert wait_for(lambda: (
                (e := srv.state.eval_by_id(eval_id)) is not None
                and e.Status == EvalStatusComplete))
            ev = srv.state.eval_by_id(eval_id)
            assert ev.FailedTGAllocs, "exhaustion must be recorded"
            assert ev.BlockedEval, "a blocked eval must be spawned"
            blocked = srv.state.eval_by_id(ev.BlockedEval)
            assert blocked is not None
            # Capacity arrives: the blocked eval unblocks and places the rest.
            n2 = mock.node()
            srv.node_register(n2)
            assert wait_for(lambda: len([
                a for a in srv.state.allocs_by_job(job.ID)
                if not a.terminal_status()]) == 6, timeout=20)
        finally:
            srv.shutdown()

    def test_update_takes_slow_path_and_still_works(self):
        """A job update (destructive) is not pure placement: it must route
        through the per-eval GenericScheduler and still converge."""
        srv = make_server()
        try:
            for _ in range(3):
                srv.node_register(mock.node())
            job = simple_job(count=3)
            eval_id, _, _ = srv.job_register(job)
            assert wait_for(lambda: len([
                a for a in srv.state.allocs_by_job(job.ID)
                if not a.terminal_status()]) == 3)
            # Destructive update: change the task command.
            job2 = job.copy()
            job2.TaskGroups[0].Tasks[0].Config = {"command": "/bin/other"}
            srv.job_register(job2)
            assert wait_for(lambda: srv.workers[0].stats["slow"] > 0,
                            timeout=20)
            assert wait_for(lambda: len([
                a for a in srv.state.allocs_by_job(job.ID)
                if not a.terminal_status()
                and a.Job is None or True]) >= 3, timeout=20)
        finally:
            srv.shutdown()

    def test_parity_with_per_eval_worker(self):
        """Same workload through pipelined and per-eval servers lands the
        same number of allocations with the same per-job placement counts."""
        results = {}
        for pipelined in (True, False):
            srv = Server(ServerConfig(num_schedulers=1,
                                      pipelined_scheduling=pipelined))
            srv.establish_leadership()
            try:
                for i in range(8):
                    srv.node_register(mock.node())
                placed = {}
                eval_ids = []
                jobs = []
                for _ in range(6):
                    job = simple_job(count=5)
                    jobs.append(job)
                    eval_ids.append(srv.job_register(job)[0])
                assert wait_for(lambda: all(
                    (e := srv.state.eval_by_id(eid)) is not None
                    and e.Status == EvalStatusComplete
                    for eid in eval_ids), timeout=20)
                for job in jobs:
                    placed[job.ID] = len([
                        a for a in srv.state.allocs_by_job(job.ID)
                        if not a.terminal_status()])
                results[pipelined] = sorted(placed.values())
            finally:
                srv.shutdown()
        assert results[True] == results[False] == [5] * 6


class TestChainRebase:
    def test_row_identity_change_rebases_chain(self):
        """A freed row reused by a new node mid-storm must invalidate the
        device usage chain: shape alone doesn't change on free-list reuse,
        so the worker tracks the table's row_epoch."""
        srv = make_server()
        try:
            nodes = [mock.node() for _ in range(4)]
            for n in nodes:
                srv.node_register(n)
            w = srv.workers[0]
            nt = srv.tindex.nt

            # Simulate a live chain built against the current table: one
            # published window in flight, tail validated at this epoch.
            chain = np.zeros((nt.n_rows, 5), dtype=np.float32)
            arb = w._arbiter
            lease = arb.acquire()
            arb.publish(lease, chain)
            lease = arb.acquire()
            assert lease.chain is not None  # in flight: chain is kept
            arb.publish(lease, chain)

            # Node leaves; its row goes to the free list (no resize).
            nt.remove_node(nodes[0].ID)
            lease = arb.acquire()
            assert lease.chain is None, (
                "chain must rebase after a row identity change")
            arb.abort(lease)
        finally:
            srv.shutdown()


class TestStalePhantomUsage:
    """A record that goes stale/fallback mid-window leaves its chained
    kernel placements as PHANTOM usage: later evals of the window were
    squeezed by capacity that never commits. The worker must re-run those
    evals on the exact path (not park them as blocked evals that no
    capacity event will ever unblock) and rebase the next window's chain.
    """

    def test_redelivered_eval_does_not_phantom_block_the_window(self):
        from nomad_tpu.server.pipelined_worker import PipelinedWorker
        from nomad_tpu.structs.structs import EvalStatusBlocked

        srv = Server(ServerConfig(num_schedulers=0,
                                  pipelined_scheduling=True,
                                  scheduler_window=16))
        srv.establish_leadership()
        try:
            node = mock.node()
            node.Resources.CPU = 1000
            node.Resources.MemoryMB = 4000
            node.Reserved = None
            srv.node_register(node)

            # Two jobs that cannot BOTH fit: the chained window has B see
            # A's (ultimately phantom) 600cpu placement.
            job_a = simple_job(count=1, cpu=600, mem=100)
            job_b = simple_job(count=1, cpu=600, mem=100)
            eval_a, _, _ = srv.job_register(job_a)
            eval_b, _, _ = srv.job_register(job_b)

            w = PipelinedWorker(srv.raft, srv.eval_broker, srv.plan_queue,
                                srv.blocked_evals, srv.tindex,
                                ["service", "batch", "system"], window=16)
            batch = w._dequeue_window()
            assert {ev.ID for ev, _ in batch} == {eval_a, eval_b}
            # Deterministic chain order: A first, then B.
            batch.sort(key=lambda p: 0 if p[0].ID == eval_a else 1)
            work = w._dispatch_window(batch)
            assert work is not None and len(work.fast) == 2

            # Redeliver A between dispatch and build (nack-timeout shape):
            # its token is no longer outstanding, so the build stage must
            # mark it stale at plan-enqueue.
            rec_a = work.fast[0]
            srv.eval_broker.nack(rec_a.ev.ID, rec_a.token)

            work.packed = w._drain_window(work)
            w._finish_fast(work)

            # A was abandoned (stale), not acked, not planned.
            assert rec_a.stale
            assert w.stats.get("stale", 0) == 1
            assert work.published  # fast evals dispatched: window in flight
            # B must NOT be parked as a blocked eval on phantom usage: the
            # node really has 1000 cpu free, so the exact-path re-run
            # places it.
            e_b = srv.state.eval_by_id(eval_b)
            assert e_b is not None and e_b.Status == EvalStatusComplete
            allocs_b = [a for a in srv.state.allocs_by_job(job_b.ID)
                        if not a.terminal_status()]
            assert len(allocs_b) == 1
            assert not [e for e in srv.state.evals_by_job(job_b.ID)
                        if e.Status == EvalStatusBlocked]
            # The next window must rebase off committed state instead of
            # inheriting A's phantom usage (the arbiter is marked dirty,
            # and a fresh lease — what run()'s next dispatch takes after
            # the build stage retires this window — carries no chain).
            assert w._arbiter.dirty
            w._arbiter.finish_window()  # what _build_loop's finally does
            lease = w._arbiter.acquire()
            assert lease.chain is None
            w._arbiter.abort(lease)
        finally:
            srv.shutdown()

    def test_inflight_window_detects_taint_from_earlier_window(self):
        """Pipelining keeps windows in flight: window 2 dispatches chained
        on window 1's device tail BEFORE window 1's build discovers its
        record went stale. Window 2 must detect the taint at finish time
        (taint sequence) and re-run its squeezed evals instead of parking
        them blocked."""
        from nomad_tpu.server.pipelined_worker import PipelinedWorker
        from nomad_tpu.structs.structs import EvalStatusBlocked

        srv = Server(ServerConfig(num_schedulers=0,
                                  pipelined_scheduling=True,
                                  scheduler_window=16))
        srv.establish_leadership()
        try:
            node = mock.node()
            node.Resources.CPU = 1000
            node.Resources.MemoryMB = 4000
            node.Reserved = None
            srv.node_register(node)

            w = PipelinedWorker(srv.raft, srv.eval_broker, srv.plan_queue,
                                srv.blocked_evals, srv.tindex,
                                ["service", "batch", "system"], window=16)

            job_a = simple_job(count=1, cpu=600, mem=100)
            eval_a, _, _ = srv.job_register(job_a)
            batch1 = w._dequeue_window()
            work1 = w._dispatch_window(batch1)
            assert work1 is not None and len(work1.fast) == 1
            assert work1.published  # dispatch published the window's tail

            # Window 2 dispatches on window 1's (soon-phantom) tail.
            job_b = simple_job(count=1, cpu=600, mem=100)
            eval_b, _, _ = srv.job_register(job_b)
            batch2 = w._dequeue_window()
            work2 = w._dispatch_window(batch2)
            assert work2 is not None and len(work2.fast) == 1
            assert work2.chained

            # Window 1's record goes stale (redelivered) before its build.
            rec_a = work1.fast[0]
            srv.eval_broker.nack(rec_a.ev.ID, rec_a.token)
            work1.packed = w._drain_window(work1)
            w._finish_fast(work1)
            assert rec_a.stale

            # Window 2 finishes AFTER the taint: its squeezed eval re-runs
            # on the exact path and places for real.
            work2.packed = w._drain_window(work2)
            w._finish_fast(work2)
            e_b = srv.state.eval_by_id(eval_b)
            assert e_b is not None and e_b.Status == EvalStatusComplete
            assert len([a for a in srv.state.allocs_by_job(job_b.ID)
                        if not a.terminal_status()]) == 1
            assert not [e for e in srv.state.evals_by_job(job_b.ID)
                        if e.Status == EvalStatusBlocked]
        finally:
            srv.shutdown()


class TestCrossWorkerTaintBarrier:
    def test_quarantine_waits_for_predecessor_taint(self):
        """TWO workers share the chain arbiter: worker B's window rides
        worker A's (soon-phantom) tail, and B's build races ahead of A's.
        B must BLOCK at the chain-order barrier until A announces its
        taint — otherwise B reads a stale taint sequence and parks its
        squeezed eval as a blocked eval no capacity event will unblock."""
        import threading

        from nomad_tpu.server.pipelined_worker import PipelinedWorker
        from nomad_tpu.structs.structs import EvalStatusBlocked
        from nomad_tpu.tensor.node_table import ChainArbiter

        srv = Server(ServerConfig(num_schedulers=0,
                                  pipelined_scheduling=True,
                                  scheduler_window=16))
        srv.establish_leadership()
        try:
            node = mock.node()
            node.Resources.CPU = 1000
            node.Resources.MemoryMB = 4000
            node.Reserved = None
            srv.node_register(node)

            arb = ChainArbiter(srv.tindex.nt)
            wa = PipelinedWorker(srv.raft, srv.eval_broker, srv.plan_queue,
                                 srv.blocked_evals, srv.tindex,
                                 ["service", "batch", "system"], window=16,
                                 chain_arbiter=arb)
            wb = PipelinedWorker(srv.raft, srv.eval_broker, srv.plan_queue,
                                 srv.blocked_evals, srv.tindex,
                                 ["service", "batch", "system"], window=16,
                                 chain_arbiter=arb)

            job_a = simple_job(count=1, cpu=600, mem=100)
            eval_a, _, _ = srv.job_register(job_a)
            work_a = wa._dispatch_window(wa._dequeue_window())
            assert work_a is not None and work_a.published

            job_b = simple_job(count=1, cpu=600, mem=100)
            eval_b, _, _ = srv.job_register(job_b)
            work_b = wb._dispatch_window(wb._dequeue_window())
            assert work_b is not None and work_b.chained
            assert work_b.chain_seq == work_a.chain_seq + 1

            # A's record goes stale (redelivered) before either builds.
            rec_a = work_a.fast[0]
            srv.eval_broker.nack(rec_a.ev.ID, rec_a.token)

            # B's build runs FIRST — it must park at the barrier.
            work_b.packed = wb._drain_window(work_b)
            b_done = threading.Event()

            def finish_b():
                wb._finish_fast(work_b)
                b_done.set()

            t = threading.Thread(target=finish_b, daemon=True,
                                 name="test-finish-b")
            t.start()
            assert not b_done.wait(0.5), \
                "B settled before A announced its taint"

            # A's build settles: stale record, taint raised, barrier opens.
            work_a.packed = wa._drain_window(work_a)
            wa._finish_fast(work_a)
            assert rec_a.stale
            assert b_done.wait(10), "B never unblocked from the barrier"
            t.join(5)

            # B detected the external taint and re-ran on the exact path:
            # placed for real, not parked blocked on phantom usage.
            e_b = srv.state.eval_by_id(eval_b)
            assert e_b is not None and e_b.Status == EvalStatusComplete
            assert len([a for a in srv.state.allocs_by_job(job_b.ID)
                        if not a.terminal_status()]) == 1
            assert not [e for e in srv.state.evals_by_job(job_b.ID)
                        if e.Status == EvalStatusBlocked]
        finally:
            srv.shutdown()


class TestFastSlowEquivalence:
    """A fixed-seed window run through _finish_fast must commit the same
    placements (node, scores, ports) as the same evals run through
    _process_slow — the fast path only accelerates evals whose outcome is
    provably identical. One record is force-failed at plan commit
    (plan.apply.commit failpoint) so the fallback/phantom-taint re-run is
    part of the compared window, not a separate test."""

    def _fleet(self, n=6):
        return [mock.node() for _ in range(n)]

    def _jobs(self):
        from nomad_tpu.structs import NetworkResource
        from nomad_tpu.structs.structs import Port

        jobs = [simple_job(count=3, cpu=120 + 10 * i, mem=64)
                for i in range(4)]
        # One group WITH a (static, deterministic) port ask: exercises the
        # exact per-placement network path on both sides.
        pj = simple_job(count=1, cpu=80, mem=32)
        task = pj.TaskGroups[0].Tasks[0]
        task.Resources.Networks = [
            NetworkResource(MBits=1,
                            ReservedPorts=[Port("http", 12345)])]
        jobs.append(pj)
        return jobs

    def _placements(self, srv, jobs):
        out = {}
        for job in jobs:
            allocs = sorted(
                (a for a in srv.state.allocs_by_job(job.ID)
                 if not a.terminal_status()), key=lambda a: a.Name)
            out[job.ID] = [
                (a.Name, a.NodeID,
                 round((a.Metrics.Scores or {}).get(
                     f"{a.NodeID}.binpack", 0.0), 4),
                 sorted((p.Label, p.Value)
                        for r in a.TaskResources.values()
                        for net in r.Networks
                        for p in net.ReservedPorts))
                for a in allocs]
        return out

    def test_window_matches_per_eval_path(self, monkeypatch):
        import numpy as np

        from nomad_tpu.resilience import failpoints
        from nomad_tpu.server.pipelined_worker import PipelinedWorker

        # Zero tie-break noise on BOTH paths: placements become a pure
        # function of the (identical) fleet + submission order.
        monkeypatch.setattr(
            "nomad_tpu.scheduler.stack.make_noise_vec",
            lambda n_rows, rng: np.zeros(n_rows, dtype=np.float32))

        fleet = self._fleet()
        jobs = self._jobs()
        # The forced-fallback eval rides its OWN second window: a commit
        # failure re-runs the record AFTER the rest of its window commits,
        # so window membership is what keeps the usage each eval observes
        # identical between the two paths.
        fallback_job = simple_job(count=2, cpu=90, mem=48)
        results = {}
        try:
            for mode in ("fast", "slow"):
                srv = Server(ServerConfig(num_schedulers=0,
                                          pipelined_scheduling=True,
                                          scheduler_window=16))
                srv.establish_leadership()
                try:
                    for node in fleet:
                        srv.node_register(node.copy())
                    for job in jobs:
                        srv.job_register(job.copy())
                    w = PipelinedWorker(
                        srv.raft, srv.eval_broker, srv.plan_queue,
                        srv.blocked_evals, srv.tindex,
                        ["service", "batch", "system"], window=16)
                    batch = w._dequeue_window()
                    assert len(batch) == len(jobs)
                    batch.sort(key=lambda p: p[0].JobID)
                    if mode == "fast":
                        work = w._dispatch_window(batch)
                        assert work is not None
                        assert len(work.fast) == len(jobs)
                        work.packed = w._drain_window(work)
                        w._finish_fast(work)
                        assert w.stats["fast"] == len(jobs)
                    else:
                        for ev, token in batch:
                            w._process_slow(ev, token)

                    # Second window: ONE record whose plan commit is
                    # forced to fail — _finish_fast must re-run it on the
                    # exact path (the phantom-taint machinery raises
                    # _chain_dirty so the next window rebases).
                    srv.job_register(fallback_job.copy())
                    batch2 = w._dequeue_window()
                    assert len(batch2) == 1
                    if mode == "fast":
                        failpoints.arm("plan.apply.commit", "error",
                                       count=1)
                        work2 = w._dispatch_window(batch2)
                        assert work2 is not None and len(work2.fast) == 1
                        work2.packed = w._drain_window(work2)
                        w._finish_fast(work2)
                        assert w.stats["fallback"] == 1, \
                            "the forced-fallback record never re-ran"
                        assert w._arbiter.dirty, \
                            "fallback must taint the chain for rebase"
                    else:
                        for ev, token in batch2:
                            w._process_slow(ev, token)
                    results[mode] = self._placements(
                        srv, jobs + [fallback_job])
                finally:
                    srv.shutdown()
        finally:
            failpoints.disarm_all()
        assert results["fast"] == results["slow"]
        # Non-vacuous: real scores and the static port came through.
        flat = [t for allocs in results["fast"].values() for t in allocs]
        assert any(score > 0 for _, _, score, _ in flat)
        assert any(ports == [("http", 12345)] for _, _, _, ports in flat)


class TestWorkerScalingEquivalence:
    """ISSUE 5 satellite: the SAME fixed storm run with 1 and with 2
    pipelined workers (sharing one ChainArbiter via the server) must end
    in the same place: no lost evals, no double-placed allocs, and an
    IDENTICAL final placed count. The storm exhausts the fleet with
    uniform demands, so the capacity-limited total is order-independent
    — window splits between workers cannot change it, only break it."""

    N_JOBS = 8
    PER_JOB = 3
    CPU = 100  # 4 nodes x 500 cpu / 100 = 20 slots for 24 asks

    def _fleet(self):
        nodes = []
        for _ in range(4):
            node = mock.node()
            node.Resources.CPU = 500
            node.Resources.MemoryMB = 2000
            node.Reserved = None
            nodes.append(node)
        return nodes

    def test_one_vs_two_workers_same_storm(self):
        from nomad_tpu.structs.structs import EvalStatusBlocked

        placed_totals = {}
        for n_workers in (1, 2):
            srv = Server(ServerConfig(num_schedulers=n_workers,
                                      pipelined_scheduling=True,
                                      scheduler_window=8))
            srv.establish_leadership()
            try:
                for node in self._fleet():
                    srv.node_register(node)
                jobs = [simple_job(count=self.PER_JOB, cpu=self.CPU, mem=10)
                        for _ in range(self.N_JOBS)]
                eval_ids = [srv.job_register(j)[0] for j in jobs]
                # No lost evals: every one of the storm's evals reaches a
                # terminal status even though 4 of the 24 asks exhaust.
                assert wait_for(lambda: all(
                    (e := srv.state.eval_by_id(eid)) is not None
                    and e.Status == EvalStatusComplete
                    for eid in eval_ids), timeout=30)

                live = [a for a in srv.state.allocs()
                        if not a.terminal_status()]
                # No double-placed allocs: unique IDs, nothing over any
                # job's ask, nothing over any node's capacity.
                assert len({a.ID for a in live}) == len(live)
                for job in jobs:
                    per_job = [a for a in live if a.JobID == job.ID]
                    assert len(per_job) <= self.PER_JOB, job.ID
                for node_id, used in total_usage_by_node(srv.state).items():
                    assert used[0] <= 500 + 1e-6, (node_id, used)
                # The overflow is parked blocked, not lost or failed.
                blocked = [e for e in srv.state.evals()
                           if e.Status == EvalStatusBlocked]
                assert blocked, "exhausted asks must park as blocked evals"
                placed_totals[n_workers] = len(live)
            finally:
                srv.shutdown()
        # Identical final placed count, and exactly the capacity bound:
        # 4 nodes x (500 cpu / 100 cpu-per-alloc) = 20.
        assert placed_totals[1] == placed_totals[2] == 20, placed_totals


class TestWindowFusion:
    def test_interleaved_preps_fuse_and_place_correctly(self):
        """A window mixing two job shapes (A,B,A,B...) fuses only
        consecutive shared-prep runs; placements still match totals and
        nothing oversubscribes."""
        srv = Server(ServerConfig(num_schedulers=0,
                                  pipelined_scheduling=True,
                                  scheduler_window=32,
                                  host_placement=False))
        srv.establish_leadership()
        try:
            from nomad_tpu.server.pipelined_worker import PipelinedWorker

            for _ in range(10):
                srv.node_register(mock.node())
            jobs = []
            for i in range(8):
                if i % 2 == 0:
                    job = simple_job(count=2, cpu=100, mem=64)
                else:
                    job = simple_job(count=3, cpu=150, mem=96)
                jobs.append(job)
                srv.job_register(job)
            w = PipelinedWorker(srv.raft, srv.eval_broker, srv.plan_queue,
                                srv.blocked_evals, srv.tindex,
                                ["service", "batch", "system"], window=32,
                                host_placement=False)
            batch = w._dequeue_window()
            assert len(batch) == 8
            work = w._dispatch_window(batch)
            assert work is not None and len(work.fast) == 8
            work.packed = w._drain_window(work)
            w._finish_fast(work)
            for job in jobs:
                want = job.TaskGroups[0].Count
                got = len([a for a in srv.state.allocs_by_job(job.ID)
                           if not a.terminal_status()])
                assert got == want, (job.ID, got, want)
            assert w.stats.get("multi", 0) >= 1  # at least one fused run
        finally:
            srv.shutdown()
