"""TensorIndex.node_context (ISSUE 27): the node context a window places
against lives as long as the nodes table it was built from. The key is
read from the input (store, nodes index, datacenters, the tensor's row
epoch / shape / population); every way the nodes table can change must
miss, and a miss must build what a from-scratch build gives."""

import sys
import threading
import time
import types

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.util import ready_nodes_in_dcs
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.pipelined_worker import PipelinedWorker
from nomad_tpu.structs import Constraint, compute_node_class
from nomad_tpu.structs.structs import NodeStatusReady
from nomad_tpu.tensor import index as tindex_mod
from nomad_tpu.tensor.constraints import ClassEligibility

DCS = ["dc1"]


def simple_job(count=2, cpu=100):
    job = mock.job()
    tg = job.TaskGroups[0]
    tg.Count = count
    task = tg.Tasks[0]
    task.Resources.Networks = []
    task.Resources.CPU = cpu
    task.Services = []
    return job


def make_server(n_nodes=6):
    srv = Server(ServerConfig(num_schedulers=0, pipelined_scheduling=True,
                              scheduler_window=16))
    srv.establish_leadership()
    fleet = []
    for i in range(n_nodes):
        node = mock.node()
        node.NodeClass = f"class-{i % 2}"
        compute_node_class(node)
        fleet.append(node)
        srv.node_register(node)
    return srv, fleet


def make_worker(srv):
    return PipelinedWorker(srv.raft, srv.eval_broker, srv.plan_queue,
                           srv.blocked_evals, srv.tindex,
                           ["service", "batch", "system"], window=16)


def run_window(worker):
    batch = worker._dequeue_window()
    assert batch
    work = worker._dispatch_window(batch)
    assert work is not None and not work.slow
    work.packed = worker._drain_window(work)
    worker._finish_fast(work)
    worker._arbiter.mark_settled(work.chain_seq)  # as the build loop does
    worker._arbiter.finish_window()
    return work


def scratch_build(tindex, snap):
    """What every window built for itself before the memo."""
    nt = tindex.nt
    nodes, by_dc = ready_nodes_in_dcs(snap, DCS)
    cand_mask = np.zeros(nt.n_rows, dtype=bool)
    for n in nodes:
        cand_mask[nt.row_of[n.ID]] = True
    return {n.ID: n for n in nodes}, cand_mask, ClassEligibility(nt, nodes), \
        by_dc


def assert_equals_scratch(ctx, tindex, snap):
    nodes_by_id, cand_mask, elig, by_dc = scratch_build(tindex, snap)
    assert ctx.nodes_by_id == nodes_by_id
    assert np.array_equal(ctx.cand_mask, cand_mask)
    assert ctx.by_dc == by_dc
    assert {c: n.ID for c, n in ctx.elig.representatives.items()} \
        == {c: n.ID for c, n in elig.representatives.items()}
    assert {r: n.ID for r, n in ctx.elig.nodes_by_row.items()} \
        == {r: n.ID for r, n in elig.nodes_by_row.items()}


# Each case changes (or does not change) what the context is a function
# of, and says whether the next lookup may be served from the memo.
def _jobs_write_only(srv, fleet):
    srv.job_register(simple_job())  # jobs/evals tables move, nodes do not


def _node_register(srv, fleet):
    srv.node_register(mock.node())


def _ttl_expiry(srv, fleet):
    srv._invalidate_heartbeat(fleet[0].ID)  # the TTL timer's callback


def _drain(srv, fleet):
    srv.node_update_drain(fleet[1].ID, True)


def _deregister(srv, fleet):
    srv.node_deregister(fleet[2].ID)


def _restore(srv, fleet):
    # Same tables, same indexes: only the tensor's epochs say it happened.
    srv.raft.fsm.restore(srv.raft.fsm.snapshot())


def _grown_table(srv, fleet):
    srv.tindex.nt._grow()  # no nodes write: row epoch and shape alone


CASES = {
    "same_nodes_index": (_jobs_write_only, True),
    "node_register": (_node_register, False),
    "status_down_by_ttl_expiry": (_ttl_expiry, False),
    "node_update_drain": (_drain, False),
    "node_deregister": (_deregister, False),
    "snapshot_restore": (_restore, False),
    "grown_table": (_grown_table, False),
}


@pytest.mark.parametrize("case", sorted(CASES) + ["other_store"])
def test_the_key_follows_the_nodes_table(case):
    srv, fleet = make_server()
    other = None
    try:
        tindex = srv.tindex
        snap0 = srv.state.snapshot()
        ctx0, hit = tindex.node_context(snap0, DCS)
        assert not hit
        assert_equals_scratch(ctx0, tindex, snap0)
        again, hit = tindex.node_context(srv.state.snapshot(), DCS)
        assert hit and again is ctx0

        if case == "other_store":
            # A federation follower's replica: the same fleet under the
            # same indexes in ANOTHER store must never be served a
            # context built from this one (nor the other way round).
            other = Server(ServerConfig(num_schedulers=0))
            other.establish_leadership()
            for node in fleet:
                other.node_register(node.copy())
            snap = other.state.snapshot()
            assert snap.get_index("nodes") == snap0.get_index("nodes")
            want_hit = False
        else:
            event, want_hit = CASES[case]
            event(srv, fleet)
            snap = srv.state.snapshot()
        ctx1, hit = tindex.node_context(snap, DCS)
        assert hit == want_hit
        if want_hit:
            assert ctx1 is ctx0
            assert ctx1.nodes_by_id is ctx0.nodes_by_id
            assert ctx1.cand_mask is ctx0.cand_mask
            assert ctx1.elig is ctx0.elig and ctx1.by_dc is ctx0.by_dc
        else:
            assert ctx1 is not ctx0
            if case == "other_store":
                assert ctx1.key[0] is other.state
            else:
                assert_equals_scratch(ctx1, tindex, snap)
        # An older snapshot still in hand keeps its own context: a window
        # never places against a node set other than its snapshot's.
        if case in ("status_down_by_ttl_expiry", "node_update_drain",
                    "node_deregister"):
            old, hit = tindex.node_context(snap0, DCS)
            assert not hit
            assert set(old.nodes_by_id) == {n.ID for n in fleet}
            assert len(ctx1.nodes_by_id) == len(fleet) - 1
    finally:
        srv.shutdown()
        if other is not None:
            other.shutdown()


def test_another_datacenter_set_is_another_context_and_the_oldest_goes():
    srv, _ = make_server(n_nodes=2)
    try:
        tindex = srv.tindex
        snap = srv.state.snapshot()
        first, _ = tindex.node_context(snap, ["dc1"])
        both, hit = tindex.node_context(snap, ["dc2", "dc1"])
        assert not hit and both is not first
        assert both.by_dc == {"dc1": 2, "dc2": 0}
        assert tindex.node_context(snap, ["dc1", "dc2"])[0] is both
        assert tindex.node_context(snap, ["dc1"]) == (first, True)
        for i in range(tindex_mod._NODE_CTX_CAP):
            tindex.node_context(snap, [f"dc-{i}"])
        with tindex._ctx_lock:
            assert len(tindex._node_ctx) == tindex_mod._NODE_CTX_CAP
            assert ("dc1",) not in tindex._node_ctx
    finally:
        srv.shutdown()


def test_a_prepared_batch_lives_with_its_context_and_its_noise_vector():
    """Windows of one worker share a prepared batch until the worker
    renews its noise; the renewal drops the batches that embed the old
    vector, and another worker's vector never meets them."""
    srv, _ = make_server()
    try:
        a, b = make_worker(srv), make_worker(srv)
        b._arbiter = a._arbiter  # as the server wires its workers
        srv.job_register(simple_job())
        w1 = run_window(a)
        srv.job_register(simple_job())
        w2 = run_window(a)
        assert w2.fast[0].prep is w1.fast[0].prep
        ctx, _ = srv.tindex.node_context(srv.state.snapshot(), DCS)
        noise_a = a._noise
        assert [p.noise_vec is noise_a for p in ctx._preps.values()] == [True]

        srv.job_register(simple_job())
        w3 = run_window(b)
        assert w3.fast[0].prep is not w1.fast[0].prep
        assert w3.fast[0].prep.noise_vec is b._noise
        assert len(ctx._preps) == 2

        a.stats["windows"] = 64  # the renewal is due
        srv.job_register(simple_job())
        w4 = run_window(a)
        assert a._noise is not noise_a
        assert w4.fast[0].prep.noise_vec is a._noise
        assert not any(p.noise_vec is noise_a for p in ctx._preps.values())
        assert len(ctx._preps) == 2  # b's, and a's new one
        assert a.stats["node_ctx_miss"] + b.stats["node_ctx_miss"] == 1
        assert a.stats["node_ctx_hit"] + b.stats["node_ctx_hit"] == 3
    finally:
        srv.shutdown()


def test_views_and_batches_stay_bounded_and_every_window_is_counted(
        monkeypatch):
    """More distinct job ids, shapes and constraints than any cap: the
    shared eligibility never takes a per-job entry (each window has its
    own views), the signature masks and the batches stay under their
    caps, and hit + miss counts the windows that looked a context up."""
    monkeypatch.setattr(tindex_mod, "_NODE_CTX_PREP_CAP", 4)
    monkeypatch.setattr(tindex_mod, "_NODE_CTX_SIG_CAP", 6)
    srv, _ = make_server()
    try:
        worker = make_worker(srv)
        per_window, windows = 3, 8
        for w in range(windows):
            for j in range(per_window):
                job = simple_job(count=1, cpu=50 + 10 * (w * per_window + j))
                job.Constraints.append(Constraint(
                    LTarget="${meta.database}", RTarget=f"not-{w}-{j}",
                    Operand="!="))
                srv.job_register(job)
            work = run_window(worker)
            assert len(work.fast) == per_window
            views = work.fast[0].stack.elig
            assert len(views._job_cache) == per_window
        ctx, hit = srv.tindex.node_context(srv.state.snapshot(), DCS)
        assert hit
        assert ctx.elig._job_cache == {} and ctx.elig._tg_cache == {}
        assert views._sig_cache is ctx.elig._sig_cache
        # One job-level signature a job; the cap is looked at once a window.
        assert len(ctx.elig._sig_cache) <= 6 + per_window + 1
        assert len(ctx._preps) == 4
        stats = worker.stats
        assert stats["windows"] == windows
        assert stats["node_ctx_hit"] + stats["node_ctx_miss"] == windows
        assert stats["node_ctx_miss"] == 1
        assert stats["t_nodectx_ms"] > 0.0
        assert stats["fast"] == per_window * windows
    finally:
        srv.shutdown()


def test_lookups_race_node_writes_and_batches_race_renewals():
    """More threads than cores against one index while the nodes table
    moves: whatever a lookup returns (kept or not) holds exactly its own
    snapshot's ready nodes, and the contexts' batches keep their caps and
    never hand a batch out under another noise vector."""
    srv, fleet = make_server(n_nodes=12)
    tindex = srv.tindex
    deadline = time.monotonic() + 1.5
    wrong, hits = [], [0]

    def reader(k):
        noise = np.zeros(4, dtype=np.float32)
        while time.monotonic() < deadline:
            snap = srv.state.snapshot()
            ctx, hit = tindex.node_context(snap, DCS)
            hits[0] += hit
            want = {n.ID for n in snap.nodes()
                    if n.Status == NodeStatusReady and not n.Drain}
            if set(ctx.nodes_by_id) != want \
                    or int(ctx.cand_mask.sum()) != len(want):
                wrong.append((k, len(want), len(ctx.nodes_by_id)))
            got = ctx.prep(("sig", k % 3), noise)
            if got is not None and got.noise_vec is not noise:
                wrong.append((k, "another vector's batch"))
            ctx.keep_prep(("sig", k % 3),
                          types.SimpleNamespace(noise_vec=noise))
            if hits[0] % 7 == 0:
                tindex.drop_noise(noise)
                noise = np.zeros(4, dtype=np.float32)

    def writer():
        i = 0
        while time.monotonic() < deadline:
            node = fleet[i % len(fleet)]
            srv.node_update_drain(node.ID, (i // len(fleet)) % 2 == 0)
            i += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=reader, args=(k,)) for k in range(16)]
    threads.append(threading.Thread(target=writer))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
        alive = [t for t in threads if t.is_alive()]
        srv.shutdown()
    assert not alive
    assert wrong == []
    assert hits[0] > 0
    with tindex._ctx_lock:
        contexts = list(tindex._node_ctx.values())
    assert 1 <= len(contexts) <= tindex_mod._NODE_CTX_CAP
    for ctx in contexts:
        assert len(ctx._preps) <= tindex_mod._NODE_CTX_PREP_CAP
