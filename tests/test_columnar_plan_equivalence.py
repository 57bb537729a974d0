"""Fixed-seed gate for fast-path plans that carry their placements as
columns only (ISSUE 29).

The all-placed, no-network build of the pipelined worker
(stack.WindowCollect: one columnar pass a window since ISSUE 31) leaves a
plan whose NodeAllocation is a ColumnarPlacements view over its
SweepBatch: no Allocation per placement exists unless a reader asks for
one. The same windows are run twice, once as shipped and once with the
build this replaced (one eval at a time, one cloned object per placement
beside the descriptor: kept here as the reference, fed from the same
seeded entropy), and must agree on
everything a replica or a reader can see: store reads by job and by node,
the ApplySweepBatch payloads, the usage table, the replica digest chain,
and, once materialised, the plan itself field for field.

Then every path that needs the objects (a partial verdict, a descriptor
refused for a moved row epoch, a later plan's exact verify against the
overlay, serialisation, any plain-dict read) is shown to get exactly the
objects it got before, and to be counted under `plans_objects`; the reads
the all-fit path makes are shown to build nothing."""

import copy
import gc
import json
import os
import random
import re
import types

import msgpack
import numpy as np
import pytest

from benchmark.deploy.dev_agent_dcs import build_fleet, seeded_uuid
from nomad_tpu import mock
from nomad_tpu.raft.backend import encode_command
from nomad_tpu.scheduler import stack as stack_mod
from nomad_tpu.scheduler.stack import GenericStack, WindowCollect
from nomad_tpu.scheduler.system_sweep import SweepBatch
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.fsm import MessageType
from nomad_tpu.server.pipelined_worker import PipelinedWorker
from nomad_tpu.server.plan_apply import OptimisticSnapshot, evaluate_plan
from nomad_tpu.structs import (
    Allocation,
    ColumnarPlacements,
    Job,
    Plan,
    PlanResult,
    from_dict,
    generate_uuids,
    placed_count,
    to_dict,
)
from nomad_tpu.structs import structs as structs_mod
from nomad_tpu.structs.structs import (
    AllocClientStatusPending,
    AllocDesiredStatusRun,
    EvalStatusComplete,
)
from nomad_tpu.tensor.node_table import RES_DIMS

from test_columnar_store_equivalence import service_window, svc_job

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "dc-50k.json")) as _f:
    CONFIG = json.load(_f)
UUID4 = re.compile(r"^[0-9a-f]{8}-[0-9a-f]{4}-4[0-9a-f]{3}-[89ab][0-9a-f]{3}"
                   r"-[0-9a-f]{12}$")

# Two windows a shape, the second chained on the first's usage.
SHAPES = {
    "one-task-group": (False, [["local-dc1"] * 4, ["local-dc1"] * 3]),
    "two-task-groups": (False, [["global-2tg"] * 3, ["global-2tg"] * 2]),
    "host-placement": (True, [["local-dc1", "global-2tg", "local-dc2",
                               "global-2tg"], ["global-2tg", "local-dc1"]]),
}


# ------------------------------------------------------------- the harness
def _seed_ids(monkeypatch, seed):
    """Every ID the program mints (structs.generate_uuid[s]) from one
    seeded stream: two runs that ask in the same order get the same IDs."""
    stream = random.Random(seed)
    monkeypatch.setattr(structs_mod, "os", types.SimpleNamespace(
        urandom=lambda n: stream.randbytes(n)))
    structs_mod._UUID_POOL.clear()


def _reference_build(self, prep, cr, eval_id, job, place, plan):
    """The build ISSUE 29 replaced, for one eval: one cloned Allocation per
    placement in plan.NodeAllocation, and the descriptor beside them from
    numpy calls of its own (unique, add.at, argsort, bincount). IDs come
    from a batched draw an eval: the window's one draw spends the seeded
    stream alike, 16 bytes a placement in chain order."""
    nt = self.tindex.nt
    n = len(place)
    rows = cr.chosen[:n]
    id_arr = nt.node_id_array()
    ids_list = id_arr[rows].tolist()
    for nid in set(ids_list):
        if nid is None or nid not in self._nodes_by_id:
            return False
    metrics_ = self.ctx.metrics
    for nid, s in zip(ids_list, cr.scores[:n].tolist()):
        metrics_.Scores[f"{nid}.binpack"] = s
    tg_index, tgs = prep.tg_index, prep.tgs
    self._fill_metrics(prep, tg_index[tgs[n - 1].Name], cr.nf_last)
    rows64 = rows.astype(np.int64, copy=False)
    shared_metric = metrics_.copy()
    templates, tpl_of = [], {}
    alloc_ids, names = generate_uuids(n), []
    alloc_tg = np.empty(n, dtype=np.int64)
    for p, tup in enumerate(place):
        tg = tgs[p]
        ti = tg_index[tg.Name]
        k = tpl_of.get(ti)
        if k is None:
            tr, vec = self._tg_template(prep, ti)
            template = Allocation(
                EvalID=eval_id, JobID=job.ID, TaskGroup=tg.Name,
                TaskResources=tr, Metrics=shared_metric,
                DesiredStatus=AllocDesiredStatusRun,
                ClientStatus=AllocClientStatusPending)
            template._resvec_cache = vec
            k = tpl_of[ti] = len(templates)
            templates.append(template)
        alloc = object.__new__(Allocation)
        alloc.__dict__ = dict(templates[k].__dict__)
        alloc.ID = alloc_ids[p]
        alloc.Name = tup.Name
        alloc.NodeID = ids_list[p]
        alloc.Services = {}
        alloc.TaskStates = {}
        names.append(tup.Name)
        alloc_tg[p] = k
        plan.append_alloc(alloc)
    ur, inv = np.unique(rows64, return_inverse=True)
    delta = np.zeros((len(ur), RES_DIMS), dtype=np.float32)
    np.add.at(delta, inv,
              np.stack([t._resvec_cache for t in templates])[alloc_tg])
    order = np.argsort(rows64, kind="stable")
    counts = np.bincount(inv, minlength=len(ur)).astype(np.int64)
    starts = np.concatenate([np.zeros(1, dtype=np.int64),
                             np.cumsum(counts, dtype=np.int64)])
    plan._sweep = SweepBatch(
        rows=ur, node_ids=id_arr[ur].tolist(), delta=delta,
        epoch=nt.row_epoch, n_rows=nt.n_rows, counts=counts, starts=starts,
        alloc_ids=np.asarray(alloc_ids, dtype=object)[order].tolist(),
        alloc_names=np.asarray(names, dtype=object)[order].tolist(),
        alloc_tg=alloc_tg[order].tolist(), templates=templates,
        kind="service")
    return True


def _reference_window(self, queued):
    """WindowCollect._build as the reference: one eval at a time."""
    return [_reference_build(*q) for q in queued]


def _server(nodes, host_placement=True, window=16):
    srv = Server(ServerConfig(num_schedulers=0, pipelined_scheduling=True,
                              scheduler_window=window,
                              host_placement=host_placement,
                              min_heartbeat_ttl=3600.0,
                              heartbeat_grace=3600.0))
    srv.establish_leadership()
    for node in nodes:
        srv.node_register(node)
    worker = PipelinedWorker(
        srv.raft, srv.eval_broker, srv.plan_queue, srv.blocked_evals,
        srv.tindex, ["service", "batch", "system"], window=window,
        host_placement=host_placement)
    return srv, worker


def _dispatch(worker):
    """A window up to the build stage's door."""
    batch = worker._dequeue_window()
    assert batch
    work = worker._dispatch_window(batch)
    assert work is not None and not work.slow
    work.packed = worker._drain_window(work)
    return work


def _finish(worker, work):
    worker._finish_fast(work)
    worker._arbiter.mark_settled(work.chain_seq)
    worker._arbiter.finish_window()


def _without_clock(plain):
    """to_dict output minus the one field that reads the clock."""
    if isinstance(plain, dict):
        return {k: _without_clock(v) for k, v in plain.items()
                if k != "AllocationTime"}
    if isinstance(plain, list):
        return [_without_clock(v) for v in plain]
    return plain


def _dump(allocs):
    return sorted((_without_clock(to_dict(a)) for a in allocs),
                  key=lambda d: d["ID"])


def _run(monkeypatch, shape, reference):
    host, windows = SHAPES[shape]
    with monkeypatch.context() as patch:
        _seed_ids(patch, 29)
        noise = stack_mod.make_noise_vec
        patch.setattr(stack_mod, "make_noise_vec",
                      lambda n, rng: noise(n, random.Random(29)))
        if reference:
            patch.setattr(WindowCollect, "_build", _reference_window)
        srv, worker = _server(
            build_fleet(CONFIG["fleet"], 96, random.Random(28)), host)
        try:
            entries, plans = [], []
            apply = srv.raft.apply

            def recording_apply(msg_type, payload):
                if msg_type is MessageType.ApplySweepBatch:
                    # As a follower decodes it: the arrays are lists there.
                    entries.append(_without_clock(msgpack.unpackb(
                        encode_command(msg_type, payload), raw=False)[1]))
                return apply(msg_type, payload)

            enqueue_all = srv.plan_queue.enqueue_all

            def recording_enqueue(batch):
                plans.extend(batch)
                return enqueue_all(batch)

            patch.setattr(srv.raft, "apply", recording_apply)
            patch.setattr(srv.plan_queue, "enqueue_all", recording_enqueue)
            rng, jobs = random.Random(2029), []
            for templates in windows:
                for template in templates:
                    job = from_dict(Job, CONFIG["jobs"][template])
                    job.ID = seeded_uuid(rng)
                    job.Name = f"{template}-{len(jobs)}"
                    srv.job_register(job)
                    jobs.append(job)
                _finish(worker, _dispatch(worker))
            state = srv.state
            assert all(e.Status == EvalStatusComplete for e in state.evals())
            nt = srv.tindex.nt
            return types.SimpleNamespace(
                by_job={j.ID: _dump(state.allocs_by_job(j.ID)) for j in jobs},
                by_node={n.ID: _dump(state.allocs_by_node(n.ID))
                         for n in state.nodes()},
                entries=entries, plans=plans, jobs=jobs,
                usage=np.array(nt.usage, np.float32),
                digest=srv.fsm.digest.stats(), stats=dict(worker.stats),
                evals=sum(len(w) for w in windows))
        finally:
            srv.shutdown()


@pytest.fixture(scope="module")
def runs():
    """(shape) -> (as shipped, reference), each made once."""
    patch = pytest.MonkeyPatch()
    made = {}

    def get(shape):
        if shape not in made:
            made[shape] = (_run(patch, shape, reference=False),
                           _run(patch, shape, reference=True))
        return made[shape]

    yield get
    patch.undo()


# --------------------------------------------------------------- the gate
@pytest.mark.parametrize("shape", list(SHAPES))
def test_columns_only_and_per_object_builds_leave_the_same_store(runs, shape):
    columns, objects = runs(shape)
    assert columns.by_job == objects.by_job
    assert columns.by_node == objects.by_node
    want = {t: {g["Name"]: g["Count"] for g in j["TaskGroups"]}
            for t, j in CONFIG["jobs"].items()}
    for job in columns.jobs:
        placed = columns.by_job[job.ID]
        per_group = {}
        for a in placed:
            assert UUID4.match(a["ID"]), a["ID"]
            assert a["JobID"] == job.ID and a["NodeID"] and a["EvalID"]
            assert a["TaskResources"] and a["Metrics"]["NodesEvaluated"] > 0
            assert a["Metrics"]["Scores"]
            per_group[a["TaskGroup"]] = per_group.get(a["TaskGroup"], 0) + 1
        assert per_group == want[job.Name.rsplit("-", 1)[0]]
        assert len({a["ID"] for a in placed}) == len(placed)
        assert len({a["Name"] for a in placed}) == len(placed)


@pytest.mark.parametrize("shape", list(SHAPES))
def test_they_replicate_as_the_same_entries_and_digest(runs, shape):
    columns, objects = runs(shape)
    assert columns.entries and columns.entries == objects.entries
    for entry in columns.entries:
        for element in entry["Batch"]:
            sweep = element["Sweep"]
            assert sweep["Kind"] == "service"
            assert sum(sweep["Counts"]) == len(sweep["AllocIDs"]) \
                == len(sweep["Names"]) == len(sweep["TGIdx"])
    np.testing.assert_array_equal(columns.usage, objects.usage)
    assert columns.usage.sum() > 0
    assert columns.digest == objects.digest
    assert columns.digest["Folds"] > 0


@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_materialised_plan_is_the_object_built_plan(runs, shape):
    columns, objects = runs(shape)
    assert len(columns.plans) == len(objects.plans) == columns.evals
    for plan, built in zip(columns.plans, objects.plans):
        placed = plan.NodeAllocation
        assert isinstance(placed, ColumnarPlacements)
        assert type(built.NodeAllocation) is dict
        # What the all-fit path reads, still from the columns.
        assert not placed.objects_built
        assert set(placed) == set(built.NodeAllocation)
        assert len(placed) == len(built.NodeAllocation)
        assert placed.total() == placed_count(built.NodeAllocation)
        for nid, allocs in built.NodeAllocation.items():
            assert nid in placed and placed.count(nid) == len(allocs)
        assert not placed.objects_built
        # And now as objects, each equal to its twin field for field.
        mine = dict(placed)
        assert placed.objects_built
        templates = {id(t) for t in plan._sweep.templates}
        fresh = set()
        for nid, allocs in built.NodeAllocation.items():
            assert [a.ID for a in mine[nid]] == [a.ID for a in allocs]
            for a, b in zip(mine[nid], allocs):
                assert type(a) is Allocation
                # Stamped after the commit, which gave the templates their
                # job (as the store's own rows have it); the twin was
                # cloned before. Everything else is equal.
                assert a.Job is plan.Job and b.Job is None
                mine_d, twin_d = to_dict(a), to_dict(b)
                del mine_d["Job"], twin_d["Job"]
                assert _without_clock(mine_d) == _without_clock(twin_d)
                np.testing.assert_array_equal(a._resvec_cache,
                                              b._resvec_cache)
                assert set(a.__dict__) == set(b.__dict__)
                template = plan._sweep.templates[
                    plan._sweep.alloc_tg[plan._sweep.alloc_ids.index(a.ID)]]
                assert id(template) in templates
                assert a.TaskResources is template.TaskResources
                assert a.Metrics is template.Metrics
                assert a._resvec_cache is template._resvec_cache
                assert a.Services == {} and a.TaskStates == {}
                fresh.update((id(a.Services), id(a.TaskStates)))
        assert len(fresh) == 2 * placed.total()  # nothing shared there


@pytest.mark.parametrize("count,n_nodes", [(5, 6), (5, 2), (12, 3)])
def test_before_any_commit_the_two_builds_hold_equal_plans(monkeypatch, count,
                                                           n_nodes):
    """One eval through the build alone, nothing committed: the templates
    have no job yet, and the materialised plan equals the object-built one
    in every field, `Job: None` included."""
    _seed_ids(monkeypatch, 31)
    columns = service_window(svc_job(count=count), n_nodes=n_nodes)
    with monkeypatch.context() as patch:
        _seed_ids(patch, 31)
        patch.setattr(WindowCollect, "_build", _reference_window)
        objects = service_window(svc_job(count=count), n_nodes=n_nodes)
    assert isinstance(columns.plan.NodeAllocation, ColumnarPlacements)
    assert type(objects.plan.NodeAllocation) is dict
    assert columns.plan._sweep.wire().keys() \
        == objects.plan._sweep.wire().keys()
    for key, value in columns.plan._sweep.wire().items():
        if key != "Templates":
            twin = objects.plan._sweep.wire()[key]
            assert type(value) is type(twin), key
            np.testing.assert_array_equal(value, twin, err_msg=key)

    def plain(plan):
        out = _without_clock(to_dict(plan))
        # The two runs differ in the job and eval they were given.
        for allocs in out["NodeAllocation"].values():
            for a in allocs:
                assert a.pop("Job") is None
                assert a.pop("JobID") == plan.Job.ID
                assert a.pop("EvalID") == plan.EvalID
                a["Name"] = a["Name"].replace(plan.Job.ID, "job")
        return out["NodeAllocation"]

    assert plain(columns.plan) == plain(objects.plan)
    assert columns.plan.NodeAllocation.objects_built


@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_counters_say_which_build_ran(runs, shape):
    columns, objects = runs(shape)
    assert columns.stats["plans_columnar"] == columns.evals
    assert columns.stats["plans_objects"] == 0
    assert objects.stats["plans_columnar"] == 0
    assert objects.stats["plans_objects"] == objects.evals
    for run in (columns, objects):
        assert run.stats["fast"] == run.evals and run.stats["fallback"] == 0
    host = SHAPES[shape][0]
    assert (columns.stats["host"] == columns.evals) is host


# ------------------------------- a mixed window, against one eval at a time
# (ISSUE 31) What the window's one pass builds for seven evals of four
# kinds, against the same window built one eval at a time, and against the
# reference above. Two preps over different node sets (dc1, dc2) and a
# second eval on the first's, an eval of two task groups over all four
# datacenters, repeated rows (50 placements on ~60 nodes and fewer), a
# record whose placements fail half-way (so the exact loop reads the
# accumulator the pass only queued on), a stale record, and a record whose
# node vanished. The device path chains a prep's evals together, so the
# roles go by job and the short one stands where both orders leave it
# before the stale and the vanished one (behind either, the phantom-usage
# quarantine would re-run it).
MIXED = ["local-dc1", "global-2tg", "local-dc4", "local-dc2", "local-dc1",
         "local-dc3", "local-dc2"]
SHORT, STALE, VANISHED = "local-dc4-2", "local-dc2-3", "local-dc3-5"
ORACLES = {
    "one-eval-at-a-time": lambda patch: patch.setattr(
        WindowCollect, "add", _add_then_build),
    "issue-29-reference": lambda patch: patch.setattr(
        WindowCollect, "_build", _reference_window),
}
_add, _build_queued = WindowCollect.add, WindowCollect.build


def _add_then_build(self, *record):
    ok = _add(self, *record)
    if ok is None:
        [ok] = _build_queued(self)
    return ok


def _mixed_window(monkeypatch, host, oracle=None):
    with monkeypatch.context() as patch:
        _seed_ids(patch, 31)
        noise = stack_mod.make_noise_vec
        patch.setattr(stack_mod, "make_noise_vec",
                      lambda n, rng: noise(n, random.Random(31)))
        if oracle is not None:
            ORACLES[oracle](patch)
        read, usage = [], stack_mod.WindowAccumulator.usage
        patch.setattr(stack_mod.WindowAccumulator, "usage", lambda acc: (
            read.append(float(usage(acc)[:, 0].sum())), usage(acc))[1])
        srv, worker = _server(
            build_fleet(CONFIG["fleet"], 160, random.Random(28)), host)
        try:
            rng = random.Random(2031)
            for i, template in enumerate(MIXED):
                job = from_dict(Job, CONFIG["jobs"][template])
                job.ID = seeded_uuid(rng)
                job.Name = f"{template}-{i}"
                if job.Name == SHORT:  # one a node, and dc4 has not fifty
                    job.TaskGroups[0].Tasks[0].Resources.CPU = 3000
                srv.job_register(job)
            work = _dispatch(worker)
            assert len(work.fast) == len(MIXED)
            chain = [rec.plan.Job.Name for rec in work.fast]
            assert chain.index(SHORT) < min(chain.index(STALE),
                                            chain.index(VANISHED))
            work.fast[chain.index(STALE)].stale = True
            gone = work.fast[chain.index(VANISHED)]
            row = int(work.packed[chain.index(VANISHED)].chosen[0])
            gone.stack._nodes_by_id = {
                nid: node for nid, node in gone.stack._nodes_by_id.items()
                if nid != srv.tindex.nt.node_id_array()[row]}
            _finish(worker, work)
            return types.SimpleNamespace(
                recs=work.fast, chain=chain, stats=dict(worker.stats),
                n_rows=srv.tindex.nt.n_rows, accumulator_cpu=read)
        finally:
            srv.shutdown()


def _metric(m):
    plain = _without_clock(to_dict(m))
    # Insertion order too: a reader that lists the scores sees one order.
    return plain, list(m.Scores.items())


@pytest.fixture(scope="module")
def mixed():
    """(host, oracle or None) -> that run of the mixed window, made once."""
    patch = pytest.MonkeyPatch()
    made = {}

    def get(host, oracle=None):
        if (host, oracle) not in made:
            made[host, oracle] = _mixed_window(patch, host, oracle)
        return made[host, oracle]

    yield get
    patch.undo()


@pytest.mark.parametrize("host", [True, False],
                         ids=["host-placement", "device"])
@pytest.mark.parametrize("oracle", list(ORACLES))
def test_the_windows_one_pass_builds_what_one_eval_at_a_time_builds(
        mixed, host, oracle):
    window, single = mixed(host), mixed(host, oracle)
    assert window.stats["collect_windowed"] == 4
    assert window.stats["collect_exact"] == 2  # the short one, the vanished
    assert window.stats["stale"] == single.stats["stale"] == 1
    assert window.chain == single.chain
    if host:
        assert window.chain == [f"{t}-{i}" for i, t in enumerate(MIXED)]
    minted = []
    for i, (mine, twin) in enumerate(zip(window.recs, single.recs)):
        who = window.chain[i]
        assert (mine.stale, mine.fallback) == (twin.stale, twin.fallback), i
        assert mine.stale is (who == STALE)
        assert mine.fallback is (who == VANISHED)
        assert {k: _metric(v) for k, v in mine.failed_tg_allocs.items()} \
            == {k: _metric(v) for k, v in twin.failed_tg_allocs.items()}
        assert bool(mine.failed_tg_allocs) is (who == SHORT)
        assert _metric(mine.ctx.metrics) == _metric(twin.ctx.metrics)
        a, b = (getattr(r.plan, "_sweep", None) for r in (mine, twin))
        if who in (STALE, SHORT, VANISHED):
            assert a is None and b is None
            if who == SHORT:
                # The exact loop's plan: objects, their metrics filled from
                # the accumulator the three evals before it queued on.
                mine_l, twin_l = ([x for v in r.plan.NodeAllocation.values()
                                   for x in v] for r in (mine, twin))
                assert 0 < len(mine_l) == len(twin_l) < 50
                for x, y in zip(mine_l, twin_l):
                    dx, dy = to_dict(x), to_dict(y)
                    minted.append(dx.pop("ID"))
                    del dy["ID"]
                    assert _without_clock(dx) == _without_clock(dy)
                [m] = mine.failed_tg_allocs.values()
                assert set(m.DimensionExhausted) == {"cpu"}
                assert m.CoalescedFailures == 50 - len(mine_l) - 1
                # Every failure read the chain's usage: the placements
                # queued before this eval, and its own.
                queued = sum(float(r.prep.demands[:50, 0].sum())
                             for r in window.recs[:i])
                assert queued >= 6800.0  # 90 at 20 MHz and 10 at 500
                assert window.accumulator_cpu == single.accumulator_cpu \
                    == [queued + 3000.0 * len(mine_l)] * (50 - len(mine_l))
            continue
        for name in ("rows", "delta", "counts", "starts"):
            x, y = getattr(a, name), getattr(b, name)
            assert x.dtype == y.dtype, (i, name)
            np.testing.assert_array_equal(x, y, err_msg=f"{i} {name}")
        assert a.rows.dtype == a.counts.dtype == a.starts.dtype == np.int64
        assert a.delta.dtype == np.float32
        assert np.all(np.diff(a.rows) > 0)  # unique, ascending
        for name in ("node_ids", "alloc_names", "alloc_tg", "epoch",
                     "n_rows", "kind"):
            assert getattr(a, name) == getattr(b, name), (i, name)
        assert (a.n_rows, a.kind) == (window.n_rows, "service")
        assert all(type(getattr(a, name)) is list
                   for name in ("node_ids", "alloc_ids", "alloc_names",
                                "alloc_tg"))
        assert all(type(t) is int for t in a.alloc_tg)
        assert len(a.alloc_ids) == len(b.alloc_ids) == 50 == a.starts[-1]
        minted.extend(a.alloc_ids)
        # Placement order within a row: the names of one node's
        # placements rise as the job's instance indexes do.
        index = [int(n[n.rindex("[") + 1:-1]) for n in a.alloc_names]
        for lo, hi in zip(a.starts[:-1], a.starts[1:]):
            groups = {}
            for p in range(lo, hi):
                groups.setdefault(a.alloc_tg[p], []).append(index[p])
            assert all(v == sorted(v) for v in groups.values())
        assert len(a.templates) == len(b.templates) \
            == (2 if who.startswith("global-2tg") else 1)
        for t, u in zip(a.templates, b.templates):
            assert _without_clock(to_dict(t)) == _without_clock(to_dict(u))
            assert _metric(t.Metrics) == _metric(u.Metrics)
            assert t.Metrics.Scores and t.Metrics is a.templates[0].Metrics
            np.testing.assert_array_equal(t._resvec_cache, u._resvec_cache)
        np.testing.assert_array_equal(
            a.delta.sum(axis=0),
            sum(a.templates[t]._resvec_cache for t in a.alloc_tg))
        assert isinstance(mine.plan.NodeAllocation, ColumnarPlacements)
        assert list(mine.plan.NodeAllocation) == a.node_ids
    # An eval with repeated rows, and one that took its prep from another.
    by_name = dict(zip(window.chain, window.recs))
    assert len(by_name["local-dc2-6"].plan._sweep.rows) < 50
    assert by_name["local-dc1-0"].prep is by_name["local-dc1-4"].prep
    assert by_name["local-dc1-0"].prep is not by_name["local-dc2-6"].prep
    # The ids: each of generate_uuid's shape (version 4, variant 8-b), and
    # no two of the window's alike.
    assert len(minted) > 200 and all(UUID4.match(x) for x in minted)
    assert len(set(minted)) == len(minted)


@pytest.mark.parametrize("host", [True, False],
                         ids=["host-placement", "device"])
def test_a_vanished_node_refuses_its_eval_alone(mixed, host):
    """The eval whose node vanished leaves the pass and re-runs per eval;
    the window's other plans are the ones made without it."""
    window = mixed(host)
    gone = window.recs[window.chain.index(VANISHED)]
    assert gone.fallback and gone.pending is None
    assert not gone.plan.NodeAllocation
    assert getattr(gone.plan, "_sweep", None) is None
    assert window.stats["fallback"] == 1
    assert window.stats["fast"] == len(MIXED) - 2  # the stale one, and it
    assert window.stats["plans_columnar"] == 4


def test_a_plan_that_already_holds_placements_gets_objects_beside_the_columns(
        monkeypatch):
    """What decides between columns only and stamped objects is what the
    plan holds when its eval is collected: the descriptor is the same."""
    held = mock.alloc()
    collect = GenericStack.collect_build

    def holding_one(self, prep, cr, eval_id, job, place, plan, failed, acc):
        plan.append_alloc(held)
        return collect(self, prep, cr, eval_id, job, place, plan, failed,
                       acc)

    monkeypatch.setattr(GenericStack, "collect_build", holding_one)
    ns = service_window(svc_job(count=7), n_nodes=3)
    assert ns.ok and type(ns.plan.NodeAllocation) is dict
    assert ns.plan.NodeAllocation[held.NodeID] == [held]
    sweep = ns.plan._sweep
    stamped = {a.ID: a for v in ns.plan.NodeAllocation.values() for a in v
               if a is not held}
    assert sorted(stamped) == sorted(sweep.alloc_ids) and len(stamped) == 7
    assert all(UUID4.match(i) for i in stamped)
    # In placement order within a node, as the exact loop appends them.
    for nid, lo, hi in zip(sweep.node_ids, sweep.starts, sweep.starts[1:]):
        assert [a.ID for a in ns.plan.NodeAllocation[nid]] \
            == sweep.alloc_ids[lo:hi]
        for p, a in zip(range(lo, hi), ns.plan.NodeAllocation[nid]):
            template = sweep.templates[sweep.alloc_tg[p]]
            assert (a.Name, a.NodeID) == (sweep.alloc_names[p], nid)
            assert a.Metrics is template.Metrics
            assert a.TaskResources is template.TaskResources
            assert a.EvalID == ns.plan.EvalID and a.JobID == ns.job.ID


# ------------------------------------------- reads that build no object
def _columns_plan(**kwargs):
    ns = service_window(svc_job(**kwargs.pop("job", {})), **kwargs)
    assert ns.ok and not ns.failed
    assert isinstance(ns.plan.NodeAllocation, ColumnarPlacements)
    return ns


def _admit(ns):
    opt = OptimisticSnapshot(ns.store.snapshot(), nt=ns.tindex.nt)
    return opt, evaluate_plan(opt, ns.plan, None, nt=ns.tindex.nt)


def test_the_all_fit_path_reads_only_columns():
    ns = _columns_plan()
    plan, placed, sweep = ns.plan, ns.plan.NodeAllocation, ns.plan._sweep
    assert not plan.is_no_op() and bool(placed)
    assert list(placed) == sweep.node_ids == list(placed.keys())
    assert len(placed) == len(sweep.node_ids)
    assert sweep.node_ids[0] in placed and "no-such-node" not in placed
    assert placed.count("no-such-node") == 0
    assert sum(placed.count(n) for n in placed) == placed.total() == 5
    assert "placements=5" in repr(placed)
    opt, result = _admit(ns)
    assert result._sweep is sweep and result.RefreshIndex == 0
    assert isinstance(result.NodeAllocation, ColumnarPlacements)
    assert result.NodeAllocation is not placed
    assert list(result.NodeAllocation) == sweep.node_ids
    assert result.full_commit(plan) == (True, 5, 5)
    opt.apply_result(result)
    assert opt._added == {} and len(opt._added_columns) == 1
    assert not placed.objects_built
    assert not result.NodeAllocation.objects_built
    # The overlay took the scatter all the same.
    np.testing.assert_allclose(opt.row_dense[sweep.rows], sweep.delta)


def test_an_empty_view_is_an_empty_dict():
    empty = ColumnarPlacements()
    assert not empty and len(empty) == 0 and empty == {}
    assert empty.objects_built and empty.total() == 0
    assert Plan(NodeAllocation=empty).is_no_op()
    empty["n"] = [Allocation(ID="a")]
    assert empty.total() == 1 and empty.count("n") == 1 and "n" in empty
    assert type(empty.copy()) is dict


READERS = {
    "getitem": lambda p: p[next(iter(p))],
    "get": lambda p: p.get(next(iter(p))),
    "values": lambda p: list(p.values()),
    "items": lambda p: list(p.items()),
    "dict": dict,
    "unpack": lambda p: {**p},
    "equals": lambda p: p == {},
    "deepcopy": copy.deepcopy,
    "setdefault": lambda p: p.setdefault(next(iter(p)), []),
    "to_dict": to_dict,
}


@pytest.mark.parametrize("reader", list(READERS))
def test_any_plain_dict_read_sees_the_objects(reader):
    ns = _columns_plan()
    placed, sweep = ns.plan.NodeAllocation, ns.plan._sweep
    admitted = placed.copy()  # a verdict's view, made before the read
    READERS[reader](placed)
    assert placed.objects_built and admitted.objects_built
    by_node = dict(placed)
    assert list(by_node) == sweep.node_ids
    assert [a.ID for v in by_node.values() for a in v] == sweep.alloc_ids
    assert [a.Name for v in by_node.values() for a in v] \
        == sweep.alloc_names
    for nid, allocs in by_node.items():
        assert allocs and all(a.NodeID == nid for a in allocs)
        # One set of objects for the plan and every view of it.
        assert admitted[nid] is allocs
    assert placed.total() == len(sweep.alloc_ids) == admitted.total()
    assert len(placed) == len(sweep.node_ids)
    assert type(placed.copy()) is dict


def test_readers_on_many_threads_get_one_set_of_objects():
    """The applier's exact verify and a worker's read may ask at once: the
    plan and every copy of its view must end up holding the same lists,
    built once (a second build would hand out twins with equal IDs)."""
    import sys
    import threading

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(20):
            ns = _columns_plan(job={"count": 12}, n_nodes=4)
            views = [ns.plan.NodeAllocation] + [
                ns.plan.NodeAllocation.copy() for _ in range(7)]
            start = threading.Barrier(2 * len(views))
            seen, errors = [], []

            def read(view, reader):
                try:
                    start.wait(timeout=10)
                    READERS[reader](view)
                    seen.append({nid: id(view[nid]) for nid in view})
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=read, args=(v, r))
                       for v in views for r in ("values", "getitem")]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=20)
                assert not t.is_alive()
            assert errors == [] and len(seen) == 2 * len(views)
            assert all(s == seen[0] for s in seen)
            ids = [a.ID for v in views[0].values() for a in v]
            assert ids == ns.plan._sweep.alloc_ids
    finally:
        sys.setswitchinterval(interval)


def test_a_serialised_plan_carries_objects_and_no_descriptor():
    """What a remote applier gets (RemoteWorker.submit_plan sends
    to_dict(plan)): every placement, and nothing of `_sweep`."""
    ns = _columns_plan(job={"count": 6}, n_nodes=3)
    twin = service_window(svc_job(count=6), n_nodes=3)
    wire = to_dict(ns.plan)
    assert set(wire) == set(to_dict(twin.plan))
    assert "_sweep" not in wire and ns.plan.NodeAllocation.objects_built
    back = from_dict(Plan, wire)
    assert type(back.NodeAllocation) is dict
    assert getattr(back, "_sweep", None) is None
    sweep = ns.plan._sweep
    assert sorted(back.NodeAllocation) == sorted(sweep.node_ids)
    got = {a.ID: a for v in back.NodeAllocation.values() for a in v}
    assert sorted(got) == sorted(sweep.alloc_ids)
    for p, alloc_id in enumerate(sweep.alloc_ids):
        a = got[alloc_id]
        template = sweep.templates[sweep.alloc_tg[p]]
        assert (a.Name, a.EvalID, a.JobID, a.TaskGroup) == (
            sweep.alloc_names[p], ns.plan.EvalID, ns.job.ID,
            template.TaskGroup)
        assert to_dict(a.TaskResources) == to_dict(template.TaskResources)
        assert to_dict(a.Metrics) == to_dict(template.Metrics)
    # The object-only applier admits it as it admits today's plan.
    result = evaluate_plan(ns.store.snapshot(), back, None, nt=None)
    assert result.full_commit(back) == (True, 6, 6)


# ----------------------------------------- paths that need the objects
def _fill(srv, node_id):
    """Another scheduler's placement lands on `node_id` and takes it all."""
    hog = mock.alloc()
    hog.NodeID = node_id
    hog.JobID = "hog"
    hog.Resources = None
    for r in hog.TaskResources.values():
        r.CPU, r.MemoryMB, r.Networks = 3900, 64, []
    srv.raft.apply(MessageType.AllocUpdate, {"Job": None, "Alloc": [hog]})
    return hog


def _live(srv, job):
    return [a for a in srv.state.allocs_by_job(job.ID)
            if not a.terminal_status()]


def test_a_partial_verdict_materialises_and_falls_back_as_before(monkeypatch):
    srv, worker = _server([mock.node() for _ in range(3)])
    try:
        job = svc_job(count=6, cpu=100)
        srv.job_register(job)
        work = _dispatch(worker)
        [rec] = work.fast
        results = []
        enqueue_all = srv.plan_queue.enqueue_all

        def fill_then_enqueue(plans):
            # Between the build and the applier's verify.
            [plan] = plans
            assert isinstance(plan.NodeAllocation, ColumnarPlacements)
            assert len(plan.NodeAllocation) == 3
            _fill(srv, plan._sweep.node_ids[0])
            pending = enqueue_all(plans)
            respond = pending[0].respond
            pending[0].respond = lambda result, error: (
                results.append(result), respond(result, error))[1]
            return pending

        monkeypatch.setattr(srv.plan_queue, "enqueue_all", fill_then_enqueue)
        _finish(worker, work)
        sweep = rec.plan._sweep
        [result] = results
        # The verdict: the full node's placements refused, the others'
        # admitted as the very objects the plan now holds.
        assert result.RefreshIndex > 0 and type(result.NodeAllocation) is dict
        assert sorted(result.NodeAllocation) == sorted(sweep.node_ids[1:])
        assert getattr(result, "_sweep", None) is None
        for nid, allocs in result.NodeAllocation.items():
            assert allocs is rec.plan.NodeAllocation[nid]
        assert result.full_commit(rec.plan) == (False, 6, 4)
        assert rec.plan.NodeAllocation.objects_built
        assert worker.stats["plans_objects"] == 1
        assert worker.stats["plans_columnar"] == 0
        assert worker.stats["fallback"] == 1 and worker.stats["fast"] == 0
        # As before: what was admitted stays, the exact path places the
        # rest, and the job ends with its six, none twice.
        placed = _live(srv, job)
        assert len(placed) == 6
        assert len({a.Name for a in placed}) == 6
        kept = {a.ID for a in placed} & set(sweep.alloc_ids)
        assert kept == {a.ID for v in result.NodeAllocation.values()
                        for a in v}
        assert sweep.node_ids[0] not in {a.NodeID for a in placed}
    finally:
        srv.shutdown()


def test_a_refused_descriptor_is_verified_per_node_on_the_objects(
        monkeypatch):
    nodes = [mock.node() for _ in range(3)]
    far = mock.node()
    far.Datacenter = "dc9"  # never a candidate: its row only moves the epoch
    srv, worker = _server(nodes + [far])
    try:
        job = svc_job(count=6, cpu=100)
        srv.job_register(job)
        work = _dispatch(worker)
        [rec] = work.fast
        enqueue_all = srv.plan_queue.enqueue_all
        bulk = []

        def move_epoch_then_enqueue(plans):
            epoch = srv.tindex.nt.row_epoch
            srv.node_deregister(far.ID)
            assert srv.tindex.nt.row_epoch > epoch == plans[0]._sweep.epoch
            return enqueue_all(plans)

        monkeypatch.setattr(srv.plan_queue, "enqueue_all",
                            move_epoch_then_enqueue)
        from nomad_tpu.server import plan_apply

        incr = plan_apply.metrics.incr_counter
        monkeypatch.setattr(
            plan_apply.metrics, "incr_counter",
            lambda key, *a: (bulk.append(key), incr(key, *a))[1])
        _finish(worker, work)
        assert ("nomad", "sched", "system", "bulk_verify") not in bulk
        assert rec.plan.NodeAllocation.objects_built
        assert worker.stats["plans_objects"] == 1
        assert worker.stats["plans_columnar"] == 0
        assert worker.stats["fast"] == 1 and worker.stats["fallback"] == 0
        placed = _live(srv, job)
        assert sorted(a.ID for a in placed) == sorted(rec.plan._sweep.alloc_ids)
        for a in placed:
            assert a.Name in rec.plan._sweep.alloc_names
            assert a.EvalID == rec.ev.ID
    finally:
        srv.shutdown()


def test_a_later_plans_exact_verify_sees_an_in_flight_columnar_result():
    """Capacity for one more only if the in-flight placements are missed:
    the exact per-node verify has to find them in the overlay."""
    ns = _columns_plan(job={"count": 4, "cpu": 1900}, n_nodes=2)
    opt, result = _admit(ns)
    opt.apply_result(result)
    assert not ns.plan.NodeAllocation.objects_built
    nid = ns.plan._sweep.node_ids[0]
    later = mock.alloc()  # asks for a port: exact path
    later.NodeID = nid
    for r in later.TaskResources.values():
        r.CPU, r.MemoryMB = 500, 32
    later.Resources = None
    second = Plan(EvalID=later.EvalID, NodeAllocation={nid: [later]})
    # Seen through the overlay: the very objects of the first plan.
    seen = opt.allocs_by_node_terminal(nid, False)
    assert seen and all(a is b for a, b in
                        zip(seen, ns.plan.NodeAllocation[nid]))
    assert opt.allocs_by_node_terminal(nid, True) == []
    assert ns.plan.NodeAllocation.objects_built
    verdict = evaluate_plan(opt, second, None, nt=ns.tindex.nt)
    assert verdict.RefreshIndex > 0 and not verdict.NodeAllocation
    # Without the in-flight result the same plan fits.
    clean = OptimisticSnapshot(ns.store.snapshot(), nt=ns.tindex.nt)
    assert evaluate_plan(clean, second, None,
                         nt=ns.tindex.nt).full_commit(second)[0]


def test_a_group_with_an_exact_plan_counts_both_as_objects():
    """One window, two plans in one applier group: the first columns-only,
    the second with a port ask and so verified on the exact path, which
    reads the first's placements out of the overlay."""
    srv, worker = _server([mock.node() for _ in range(2)])
    try:
        plain = svc_job(count=4, cpu=100)
        ports = svc_job(count=2, cpu=100, networks=True)
        srv.job_register(plain)
        srv.job_register(ports)
        work = _dispatch(worker)
        assert len(work.fast) == 2
        _finish(worker, work)
        first, second = (rec.plan for rec in work.fast)
        assert isinstance(first.NodeAllocation, ColumnarPlacements)
        assert type(second.NodeAllocation) is dict
        assert first.NodeAllocation.objects_built
        assert worker.stats["plans_objects"] == 2
        assert worker.stats["plans_columnar"] == 0
        assert worker.stats["fast"] == 2 and worker.stats["fallback"] == 0
        assert sorted(a.ID for a in _live(srv, plain)) \
            == sorted(first._sweep.alloc_ids)
        assert len(_live(srv, ports)) == 2
    finally:
        srv.shutdown()


@pytest.mark.parametrize("reader", ["to_dict", "values", "dict"])
def test_a_reader_on_the_way_is_counted_and_changes_nothing(monkeypatch,
                                                            reader):
    srv, worker = _server([mock.node() for _ in range(4)])
    try:
        jobs = [svc_job(count=5) for _ in range(3)]
        for job in jobs:
            srv.job_register(job)
        work = _dispatch(worker)
        enqueue_all = srv.plan_queue.enqueue_all

        def read_then_enqueue(plans):
            READERS[reader](plans[1].NodeAllocation)  # the middle one
            return enqueue_all(plans)

        monkeypatch.setattr(srv.plan_queue, "enqueue_all", read_then_enqueue)
        _finish(worker, work)
        assert worker.stats["plans_objects"] == 1
        assert worker.stats["plans_columnar"] == 2
        assert worker.stats["fast"] == 3 and worker.stats["fallback"] == 0
        for job, rec in zip(jobs, work.fast):
            assert sorted(a.ID for a in _live(srv, job)) \
                == sorted(rec.plan._sweep.alloc_ids)
        assert srv.state.columnar_stats()["Batches"] == {"service": 3}
    finally:
        srv.shutdown()


def test_the_object_build_without_the_columnar_commit_is_unchanged():
    """A job with a network ask (ports are per-placement offers, so its
    window takes the exact build): objects at collect, in placement
    order, no descriptor, counted as objects."""
    srv, worker = _server([mock.node() for _ in range(3)])
    try:
        job = svc_job(count=5, networks=True)
        srv.job_register(job)
        work = _dispatch(worker)
        _finish(worker, work)
        [rec] = work.fast
        assert type(rec.plan.NodeAllocation) is dict
        assert getattr(rec.plan, "_sweep", None) is None
        assert worker.stats["plans_objects"] == 1
        assert worker.stats["plans_columnar"] == 0
        placed = [a for v in rec.plan.NodeAllocation.values() for a in v]
        assert sorted(a.Name for a in placed) \
            == sorted(t.Name for t in rec.place)
        assert all(UUID4.match(a.ID) for a in placed)
        assert sorted(a.ID for a in _live(srv, job)) \
            == sorted(a.ID for a in placed)
        assert not srv.state.columnar_stats()["Batches"]
    finally:
        srv.shutdown()


# ------------------------------------------------ nothing is constructed
def _census():
    gc.collect()
    return {id(o) for o in gc.get_objects() if type(o) is Allocation}


def test_a_fast_window_constructs_no_allocation_beyond_its_templates(
        monkeypatch):
    srv, worker = _server(build_fleet(CONFIG["fleet"], 96,
                                      random.Random(28)))
    try:
        templates = ["local-dc1", "global-2tg", "local-dc1", "global-2tg",
                     "pair-dc1-dc2"]
        rng = random.Random(2029)
        for template in templates:
            job = from_dict(Job, CONFIG["jobs"][template])
            job.ID = job.Name = seeded_uuid(rng)
            srv.job_register(job)
        constructed, stamped = [], []
        init = Allocation.__init__
        monkeypatch.setattr(
            Allocation, "__init__",
            lambda self, *a, **kw: (constructed.append(1),
                                    init(self, *a, **kw))[1])
        stamp = structs_mod.stamp_alloc
        for module in (structs_mod, stack_mod):
            monkeypatch.setattr(
                module, "stamp_alloc",
                lambda *a: (stamped.append(1), stamp(*a))[1])
        before = _census()
        work = _dispatch(worker)
        _finish(worker, work)
        plans = [rec.plan for rec in work.fast]  # keep what they hold alive
        task_groups = sum(len(CONFIG["jobs"][t]["TaskGroups"])
                          for t in templates)
        placements = sum(p.NodeAllocation.total() for p in plans)
        assert placements == 50 * len(templates)
        assert len(constructed) == task_groups == 7
        assert stamped == []
        held = {id(t) for p in plans for t in p._sweep.templates}
        assert _census() - before == held and len(held) == task_groups
        assert worker.stats["plans_columnar"] == len(templates)
        assert worker.stats["plans_objects"] == 0
        # The reads a client or an operator makes afterwards build what
        # they ask for, out of the store's own rows and not the plan's.
        assert len(srv.state.allocs()) == placements
        assert not any(p.NodeAllocation.objects_built for p in plans)
    finally:
        srv.shutdown()


# --------------------------------------------------------- the id column
def test_a_batched_draw_has_the_shape_and_the_entropy_of_single_ids(
        monkeypatch):
    ids = generate_uuids(512)
    assert len(set(ids)) == 512 and all(UUID4.match(i) for i in ids)
    assert generate_uuids(0) == []
    # All 122 free bits move: every hex position but the version's takes
    # every value it may.
    for pos in range(36):
        seen = {i[pos] for i in ids}
        want = ({"-"} if pos in (8, 13, 18, 23) else {"4"} if pos == 14
                else set("89ab") if pos == 19 else set("0123456789abcdef"))
        assert seen == want, pos
    # One read of the entropy source for the lot, 16 bytes an ID.
    asked = []
    monkeypatch.setattr(structs_mod, "os", types.SimpleNamespace(
        urandom=lambda n: (asked.append(n), os.urandom(n))[1]))
    generate_uuids(50)
    assert asked == [800]


def test_a_windows_draw_is_a_hundred_thousand_distinct_ids_of_the_one_shape():
    """The window's id column (ISSUE 31): one draw, a byte matrix that is
    permuted and sliced before any string exists."""
    one = structs_mod.generate_uuid()
    assert UUID4.match(one) and one[14] == "4" and one[19] in "89ab"
    ids = generate_uuids(100_000)
    assert len(ids) == 100_000 == len(set(ids))
    assert all(type(i) is str and UUID4.match(i) for i in ids)
    assert generate_uuids(0) == []
    [lone] = generate_uuids(1)
    assert UUID4.match(lone)
    rows = structs_mod.uuid_rows(7)
    assert rows.shape == (7, 37) and rows.dtype == np.uint8
    whole = structs_mod.uuid_strings(rows)
    order = np.array([6, 0, 3, 3])
    assert structs_mod.uuid_strings(rows[order]) == [whole[i] for i in order]
    assert structs_mod.uuid_strings(rows[2:5]) == whole[2:5]
    assert structs_mod.uuid_strings(rows[:0]) == []


def test_the_seeded_stream_is_spent_alike_by_one_draw_or_many(monkeypatch):
    """What lets the gate above compare ids at all: a window's one draw
    reads the bytes that its evals' draws would have read, in order."""
    _seed_ids(monkeypatch, 5)
    together = generate_uuids(120)
    _seed_ids(monkeypatch, 5)
    apart = generate_uuids(50) + generate_uuids(20) + generate_uuids(50)
    assert together == apart
