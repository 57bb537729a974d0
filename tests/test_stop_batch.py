"""The window's stop batch (PipelinedWorker._stop_batch): the evals of a
window whose job is gone, diffed on one snapshot, their plans enqueued in
one round and finished with one EvalUpdate entry and one ack round.

- equivalence: on one snapshot, stop_plan makes the plan GenericScheduler
  makes for the same eval (node keys, alloc ids, desired status and
  description), for a service and a batch job, and the status the batch
  commits is the one the exact scheduler writes;
- no shared write: a stop row is a new top-level object, so the stored
  allocations, and what an older snapshot reads, are unchanged by the
  commit and by a client update after it;
- fallback: a stop plan refused at commit, or for a stale token, re-runs on
  the exact path and the eval still ends complete;
- a chain placed before a stop committed: a host tail gives the freed usage
  back, a device tail's refused placements re-run on the exact path;
- routing: a second eval of one job, a system job's deregistration, a job
  re-registered between dispatch and build and an annotate request all take
  the exact path;
- an eval with nothing to stop completes with no plan.
"""

import logging
import threading

import pytest

from nomad_tpu import mock
from nomad_tpu.resilience import failpoints
from nomad_tpu.scheduler.generic_sched import GenericScheduler
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.pipelined_worker import (PipelinedWorker, _WindowWork,
                                               stop_plan)
from nomad_tpu.structs import PlanResult, generate_uuid, to_dict
from nomad_tpu.structs.structs import (AllocClientStatusComplete,
                                       AllocDesiredStatusStop,
                                       EvalStatusComplete, TaskState)
from nomad_tpu.scheduler.util import ALLOC_NOT_NEEDED
from nomad_tpu.telemetry import metrics

COUNT = 5


def simple_job(kind="service", count=COUNT):
    """mock.job() without networks or services, of `kind`."""
    job = mock.job()
    job.Type = kind
    tg = job.TaskGroups[0]
    tg.Count = count
    task = tg.Tasks[0]
    task.Resources.Networks = []
    task.Services = []
    return job


@pytest.fixture
def served():
    srv = Server(ServerConfig(num_schedulers=0, pipelined_scheduling=True,
                              scheduler_window=16))
    srv.establish_leadership()
    for _ in range(6):
        srv.node_register(mock.node())
    worker = PipelinedWorker(srv.raft, srv.eval_broker, srv.plan_queue,
                             srv.blocked_evals, srv.tindex,
                             ["service", "batch", "system"], window=16)
    worker.name = "w-stop"
    yield srv, worker
    failpoints.disarm_all()
    srv.shutdown()


def live(srv, job_id):
    return [a for a in srv.state.allocs_by_job(job_id)
            if not a.terminal_status()]


def place(srv, worker, jobs, fast=False):
    """Register `jobs` and place them in one window: through the window's
    fast path (columnar rows) or the exact scheduler (objects)."""
    for job in jobs:
        srv.job_register(job)
    batch = worker._dequeue_window()
    assert len(batch) == len(jobs)
    if fast:
        work = worker._dispatch_window(batch)
        assert len(work.fast) == len(jobs)
        work.packed = worker._drain_window(work)
        worker._finish_fast(work)
    else:
        for ev, token in batch:
            worker._process_slow(ev, token)
    for job in jobs:
        assert len(live(srv, job.ID)) == job.TaskGroups[0].Count


def build(worker, work):
    """The build thread's loop over one window, then its shutdown."""
    thread = threading.Thread(target=worker._build_loop)
    thread.start()
    worker._build_q.put(work)
    worker._build_q.put(None)
    thread.join(60.0)
    assert not thread.is_alive()


def stop_window(srv, worker, job_ids):
    """`nomad stop` each job, then one window dispatched and built."""
    evals = [srv.job_deregister(job_id)[0] for job_id in job_ids]
    batch = worker._dequeue_window()
    assert sorted(ev.ID for ev, _ in batch) == sorted(evals)
    work = worker._dispatch_window(batch)
    assert work.fast == [] and len(work.slow) == len(evals)
    build(worker, work)
    return evals


def complete(srv, alloc):
    """A client reports `alloc` finished."""
    done = alloc.copy()
    done.ClientStatus = AllocClientStatusComplete
    done.TaskStates = {"web": TaskState(State="dead")}
    srv.node_update_allocs([done])


def rows(plan):
    return {node: sorted((a.ID, a.DesiredStatus, a.DesiredDescription)
                         for a in allocs)
            for node, allocs in plan.NodeUpdate.items()}


class Recorder:
    """The exact scheduler's planner, committing nothing: every plan
    admitted whole, every status kept."""

    def __init__(self):
        self.plans, self.evals, self.creates = [], [], []

    def submit_plan(self, plan):
        self.plans.append(plan)
        return PlanResult(NodeUpdate=plan.NodeUpdate,
                          NodeAllocation=plan.NodeAllocation), None

    def update_eval(self, ev):
        self.evals.append(ev)

    def create_eval(self, ev):
        self.creates.append(ev)

    reblock_eval = create_eval


STATUS = ("Status", "StatusDescription", "NextEval", "BlockedEval",
          "FailedTGAllocs", "TriggeredBy", "Type", "JobID", "Priority")


def test_the_batch_makes_the_exact_schedulers_plans_and_statuses(served):
    srv, worker = served
    service, batch_job = simple_job("service"), simple_job("batch")
    place(srv, worker, [service, batch_job])
    # One finished allocation each: terminal for a service (not stopped),
    # kept by a batch job's filter (stopped with the rest).
    complete(srv, live(srv, service.ID)[0])
    complete(srv, live(srv, batch_job.ID)[0])
    evals = [srv.job_deregister(job.ID)[0]
             for job in (service, batch_job)]
    window = worker._dequeue_window()
    snap = srv.state.snapshot()
    exact = {}
    for ev, _ in window:
        planner = Recorder()
        GenericScheduler(snap, planner, srv.tindex, logging.getLogger(),
                         batch=ev.Type == "batch").process(ev)
        [plan] = planner.plans
        mine = stop_plan(ev, snap)
        assert rows(mine) == rows(plan) and mine.Job is plan.Job is None
        exact[ev.JobID] = (rows(plan), planner.evals, planner.creates)
    stopped = {job_id: sum(map(len, r.values()))
               for job_id, (r, _, _) in exact.items()}
    assert stopped == {service.ID: COUNT - 1, batch_job.ID: COUNT}

    work = worker._dispatch_window(window)
    build(worker, work)
    assert worker.stats["stop_batched"] == 2
    assert worker.stats["slow"] == worker.stats["stop_evals"] == 2
    for eval_id, job in zip(evals, (service, batch_job)):
        want_rows, [want], creates = exact[job.ID]
        assert creates == []
        got = srv.state.eval_by_id(eval_id)
        assert got.Status == EvalStatusComplete
        assert {k: getattr(got, k) for k in STATUS} \
            == {k: getattr(want, k) for k in STATUS}
        assert srv.eval_broker.outstanding(eval_id) is None  # acked
        assert live(srv, job.ID) == []
        committed = {a.ID: a for a in srv.state.allocs_by_job(job.ID)}
        for node, want_allocs in want_rows.items():
            for alloc_id, status, desc in want_allocs:
                alloc = committed[alloc_id]
                assert (alloc.NodeID, alloc.DesiredStatus,
                        alloc.DesiredDescription) \
                    == (node, AllocDesiredStatusStop, ALLOC_NOT_NEEDED)


NESTED = ("Resources", "TaskResources", "Metrics", "TaskStates", "Services")


@pytest.mark.parametrize("fast", [True, False], ids=["columns", "objects"])
def test_a_stop_row_writes_no_stored_allocation(served, fast):
    srv, worker = served
    job = simple_job()
    place(srv, worker, [job], fast=fast)
    older = srv.state.snapshot()
    before = {a.ID: (a, to_dict(a)) for a in older.allocs_by_job(job.ID)}
    assert len(before) == COUNT
    stop_window(srv, worker, [job.ID])
    assert worker.stats["stop_batched"] == 1
    stopped = {a.ID: a for a in srv.state.allocs_by_job(job.ID)}
    for alloc_id, (old, old_dict) in before.items():
        new = stopped[alloc_id]
        assert new is not old and new.DesiredStatus == AllocDesiredStatusStop
        # The nested values are shared with the version before the stop,
        # and nothing wrote them.
        for name in NESTED:
            assert getattr(new, name) is getattr(old, name), name
    # A client reports every stopped allocation done: the store replaces
    # what it changes, so the older version still reads as it was.
    for new in stopped.values():
        complete(srv, new)
    for alloc in older.allocs_by_job(job.ID):
        old, old_dict = before[alloc.ID]
        assert alloc is old and to_dict(alloc) == old_dict
        assert alloc.DesiredStatus == "run" and alloc.TaskStates == {}
    for alloc in srv.state.allocs_by_job(job.ID):
        assert alloc.ClientStatus == AllocClientStatusComplete
        assert alloc.TaskStates["web"].State == "dead"


def _ends_complete_on_the_exact_path(srv, worker, job, evals):
    assert worker.stats["stop_batched"] == 0
    [eval_id] = evals
    assert srv.state.eval_by_id(eval_id).Status == EvalStatusComplete
    assert srv.eval_broker.outstanding(eval_id) is None
    assert live(srv, job.ID) == []


def test_a_stop_plan_refused_at_commit_re_runs_on_the_exact_path(served):
    srv, worker = served
    job = simple_job()
    place(srv, worker, [job])
    failpoints.arm("plan.apply.commit", "error", count=1)
    evals = stop_window(srv, worker, [job.ID])
    assert failpoints.snapshot()["plan.apply.commit"]["fired"] == 1
    _ends_complete_on_the_exact_path(srv, worker, job, evals)


def test_a_stop_plan_with_a_stale_token_re_runs_on_the_exact_path(
        served, monkeypatch):
    srv, worker = served
    job = simple_job()
    place(srv, worker, [job])
    broker, refused = srv.eval_broker, []
    outstanding = broker.outstanding

    def stale_once(eval_id):
        if not refused:  # the applier's token check, the batch's plan
            refused.append(eval_id)
            return "another-token"
        return outstanding(eval_id)

    monkeypatch.setattr(broker, "outstanding", stale_once)
    evals = stop_window(srv, worker, [job.ID])
    assert refused == evals
    _ends_complete_on_the_exact_path(srv, worker, job, evals)


@pytest.mark.parametrize("case", ["never_placed", "all_complete"])
def test_an_eval_with_nothing_to_stop_completes_with_no_plan(
        served, monkeypatch, case):
    srv, worker = served
    job = simple_job()
    if case == "all_complete":
        place(srv, worker, [job])
        for alloc in live(srv, job.ID):
            complete(srv, alloc)
    else:  # a constraint no node meets: the job holds nothing
        job.Constraints[0].RTarget = "plan9"
        srv.job_register(job)
        for ev, token in worker._dequeue_window():
            worker._process_slow(ev, token)
        assert srv.state.allocs_by_job(job.ID) == []
    enqueued = []
    enqueue_all = srv.plan_queue.enqueue_all
    monkeypatch.setattr(srv.plan_queue, "enqueue_all",
                        lambda plans: enqueued.append(plans)
                        or enqueue_all(plans))
    [eval_id] = stop_window(srv, worker, [job.ID])
    assert enqueued == []
    assert worker.stats["stop_batched"] == 1
    assert srv.state.eval_by_id(eval_id).Status == EvalStatusComplete
    assert srv.eval_broker.outstanding(eval_id) is None


@pytest.mark.parametrize("host", [True, False],
                         ids=["host_tail", "device_tail"])
def test_a_chain_behind_a_stop_gives_its_usage_back_or_re_runs_exactly(
        served, host):
    """A window chained on a tail from before a stop committed: a host tail
    gives the stopped allocations' usage back and places on the fast path;
    a device tail keeps it, so what its kernel refused re-runs on the exact
    path, which reads the committed table, and the chain is marked for a
    rebase. Either way the job places whole, with no blocked eval."""
    srv, worker = served
    if not host:
        worker = PipelinedWorker(srv.raft, srv.eval_broker, srv.plan_queue,
                                 srv.blocked_evals, srv.tindex,
                                 ["service", "batch", "system"], window=16,
                                 host_placement=False)
    jobs = [simple_job(count=6) for _ in range(2)]
    for job in jobs:  # one a node: 3,000 of a node's 3,900 MHz
        job.TaskGroups[0].Tasks[0].Resources.CPU = 3000
    big, again = jobs
    place(srv, worker, [big], fast=True)  # its window stays in flight
    stop_window(srv, worker, [big.ID])
    eval_id = srv.job_register(again)[0]
    work = worker._dispatch_window(worker._dequeue_window())
    assert work.chained  # on the tail that still held `big`
    work.packed = worker._drain_window(work)
    worker._finish_fast(work)
    assert (worker.stats["fast"], worker.stats["fallback"],
            worker._arbiter.dirty) == ((2, 0, False) if host else (1, 1, True))
    ev = srv.state.eval_by_id(eval_id)
    assert ev.Status == EvalStatusComplete and not ev.BlockedEval
    assert len(live(srv, again.ID)) == 6


# ------------------------------------------------------------------ routing
def _route(worker, monkeypatch, slow):
    """What _stop_batch hands the batch, and what it leaves for the exact
    path, for a window whose slow evals are `slow`."""
    taken = []

    def finish(batch, snap):
        taken.extend(batch)
        return {ev.ID for ev, _ in batch}

    monkeypatch.setattr(worker, "_finish_stops", finish)
    rest = worker._stop_batch(_WindowWork(fast=[], slow=slow))
    return [ev.ID for ev, _ in taken], [ev.ID for ev, _ in rest]


def _other(ev, **fields):
    other = ev.copy()
    other.ID = generate_uuid()
    for k, v in fields.items():
        setattr(other, k, v)
    return other


@pytest.mark.parametrize("case", ["second_eval_of_a_job", "annotate",
                                  "unhandled_trigger"])
def test_only_a_jobs_first_stop_eval_with_no_annotate_is_batched(
        served, monkeypatch, case):
    srv, worker = served
    job, other = simple_job(), simple_job()
    place(srv, worker, [job, other])
    for j in (job, other):
        srv.job_deregister(j.ID)
    window = worker._dequeue_window()
    ev, token = window[0]
    odd = {"second_eval_of_a_job": lambda: _other(ev),
           "annotate": lambda: _other(window[1][0], AnnotatePlan=True),
           "unhandled_trigger": lambda: _other(window[1][0],
                                               TriggeredBy="mystery")}[case]()
    slow = [(ev, token), (odd, "t-odd")]
    taken, rest = _route(worker, monkeypatch, slow)
    assert taken == [ev.ID] and rest == [odd.ID]


def test_a_job_registered_again_before_the_build_takes_the_exact_path(
        served, monkeypatch):
    srv, worker = served
    job = simple_job()
    place(srv, worker, [job])
    srv.job_deregister(job.ID)
    window = worker._dequeue_window()
    work = worker._dispatch_window(window)
    srv.job_register(job.copy())  # between dispatch and build
    taken, rest = _route(worker, monkeypatch, work.slow)
    assert taken == [] and rest == [ev.ID for ev, _ in window]


def test_a_system_jobs_deregistration_takes_the_exact_path(served):
    srv, worker = served
    job = mock.system_job()
    resources = job.TaskGroups[0].Tasks[0].Resources
    resources.Networks, resources.DiskMB = [], 150
    srv.job_register(job)
    for ev, token in worker._dequeue_window():
        worker._process_slow(ev, token)
    assert live(srv, job.ID)
    evals = stop_window(srv, worker, [job.ID])
    _ends_complete_on_the_exact_path(srv, worker, job, evals)


class _Spans:
    def __init__(self):
        self.opened = []

    def __call__(self, name, **attrs):
        outer = self

        class _Span:
            def __enter__(self):
                outer.opened.append((name, attrs))

            def __exit__(self, *exc):
                outer.opened.append(("/" + name, {}))

        return _Span()


def test_the_batch_is_one_span_nested_in_the_slow_stage(served,
                                                         monkeypatch):
    srv, worker = served
    jobs = [simple_job() for _ in range(3)]
    place(srv, worker, jobs)
    spans = _Spans()
    monkeypatch.setattr(metrics, "_annotation", spans)
    stop_window(srv, worker, [j.ID for j in jobs])
    names = [n for n, _ in spans.opened if "nomad.worker." in n]
    start = names.index("nomad.worker.slow")
    assert names[start:start + 4] == [
        "nomad.worker.slow", "nomad.worker.stop_batch",
        "/nomad.worker.stop_batch", "/nomad.worker.slow"]
    [attrs] = [a for n, a in spans.opened if n == "nomad.worker.stop_batch"]
    assert attrs["evals"] == 3 and attrs["worker"] == "w-stop"
    assert worker.stats["stop_batched"] == 3
