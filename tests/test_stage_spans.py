"""One stage-span primitive (ISSUE 26): metrics.measure is a registry
sample AND a span on the profiler's timeline; PipelinedWorker._stage adds
the declared stats key from the same call; the broker's and the plan
queue's waits are sampled where the waiting happens, tracing on or off;
the device programs carry the names kernels.PROGRAM_NAMES declares."""

import glob
import json
import os
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler import kernels
from nomad_tpu.server import Server, ServerConfig, pipelined_worker
from nomad_tpu.server.pipelined_worker import (CPU_STAGES, STATS_COUNTERS,
                                               STATS_TIMERS_MS,
                                               PipelinedWorker, new_stats)
from nomad_tpu.structs.structs import EvalStatusComplete
from nomad_tpu.telemetry import metrics, trace
from nomad_tpu.tensor import node_table

from helpers import wait_for  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Spans:
    """Stands where jax.profiler.TraceAnnotation does and keeps what was
    opened and closed, in order."""

    def __init__(self):
        self.opened, self.closed, self.closed_by = [], [], []

    def __call__(self, name, **attrs):
        outer = self

        class _Span:
            def __enter__(self):
                outer.opened.append((name, attrs))

            def __exit__(self, *exc):
                outer.closed.append(name)
                outer.closed_by.append((name, attrs.get("worker")))

        return _Span()


class Samples:
    """A registry sink that keeps every timer sample."""

    def __init__(self):
        self.rows = []

    def add_sample(self, key, value):
        self.rows.append((".".join(key), value))

    def set_gauge(self, key, value):
        pass

    def incr_counter(self, key, value):
        pass

    def of(self, name):
        return [v for n, v in self.rows if n == name]


@pytest.fixture()
def spans(monkeypatch):
    fake = Spans()
    monkeypatch.setattr(metrics, "_annotation", fake)
    return fake


@pytest.fixture()
def samples():
    sink = Samples()
    metrics.registry.add_sink(sink)
    yield sink
    with metrics.registry._lock:
        metrics.registry._sinks = [s for s in metrics.registry._sinks
                                   if s is not sink]


# ------------------------------------------------------------------ measure
def test_measure_is_a_sample_and_a_span_from_one_call(spans, samples):
    with metrics.measure(("nomad", "plan", "apply"), batch=3) as timed:
        time.sleep(0.002)
        assert spans.closed == []
    assert spans.opened == [("nomad.plan.apply", {"batch": 3})]
    assert spans.closed == ["nomad.plan.apply"]
    assert samples.of("nomad.plan.apply") == [timed.ms]
    assert 2.0 <= timed.ms < 500.0


def test_measure_samples_and_closes_the_span_when_the_block_raises(
        spans, samples):
    with pytest.raises(KeyError):
        with metrics.measure(("nomad", "fsm", "boom")):
            raise KeyError("x")
    assert spans.closed == ["nomad.fsm.boom"]
    assert len(samples.of("nomad.fsm.boom")) == 1


def test_measure_lands_on_the_profilers_timeline_with_its_attrs(tmp_path):
    """The real thing: a profiler session sees the span on a host plane,
    named by the dotted key, with the attrs as the event's stats."""
    assert metrics._trace_annotation() is jax.profiler.TraceAnnotation
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with metrics.measure(("nomad", "worker", "dispatch"), worker="w-7",
                             window=41):
            time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                           / "*.xplane.pb"))
    found = [(e.duration_ns, dict(e.stats))
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "nomad.worker.dispatch"]
    assert len(found) == 1
    duration_ns, stats = found[0]
    assert duration_ns >= 3e6
    assert str(stats["worker"]) == "w-7" and int(stats["window"]) == 41


def test_without_jax_measure_is_a_plain_timer_and_imports_nothing():
    code = (
        "import sys\n"
        "from nomad_tpu.telemetry import metrics\n"
        "with metrics.measure(('nomad', 'x', 'y'), a=1) as timed:\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'measure imported jax'\n"
        "assert metrics._trace_annotation() is None\n"
        "[s] = metrics.snapshot()['Samples']\n"
        "assert s['Name'] == 'nomad.x.y' and s['Count'] == 1\n"
        "assert timed.ms >= 0.0\n"
        "print('plain')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "plain"


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.mark.parametrize("block,busy", [(lambda: _spin(0.02), True),
                                        (lambda: time.sleep(0.02), False)],
                         ids=["a-busy-loop", "a-sleep"])
def test_measure_keeps_the_threads_cpu_beside_the_wall(samples, block, busy):
    """With cpu=True, cpu_ms is this thread's CPU inside the block: all of
    a busy loop's wall (never more than it, but for the two clocks' reads)
    and next to nothing of a sleep's."""
    with metrics.measure(("nomad", "x", "cpu"), cpu=True) as timed:
        block()
    assert timed.ms >= 20.0
    assert 0.0 <= timed.cpu_ms <= timed.ms + 1.0
    if busy:
        # A loaded machine may take the core away for part of it.
        assert timed.cpu_ms >= 2.0
    else:
        assert timed.cpu_ms < 5.0


def test_by_default_measure_reads_no_cpu_clock_and_samples_the_wall_alone(
        samples, monkeypatch):
    """The thread's CPU clock is a system call a read (on the chip's host
    two a block cost svc-10k.storm 8 %: PERF.md section 6, PR 39): only a
    caller that keeps the CPU pays for it."""
    def no_cpu_clock():
        raise AssertionError("thread_time read by a default measure")

    monkeypatch.setattr(time, "thread_time", no_cpu_clock)
    with metrics.measure(("nomad", "x", "wall")) as timed:
        pass
    assert timed.cpu_ms == 0.0
    assert [n for n, _ in samples.rows] == ["nomad.x.wall"]


def test_cpu_true_samples_the_cpu_beside_the_wall_sample(spans, samples):
    with metrics.measure(("nomad", "plan", "apply"), cpu=True,
                         batch=3) as timed:
        _spin(0.002)
    assert samples.rows == [("nomad.plan.apply", timed.ms),
                            ("nomad.plan.apply.cpu", timed.cpu_ms)]
    # An argument of the call, never an attribute of the span.
    assert spans.opened == [("nomad.plan.apply", {"batch": 3})]


def _calls(source, opener):
    """The argument text of every `opener(` call in `source`, by matching
    parentheses."""
    out = []
    for m in re.finditer(re.escape(opener) + r"\(", source):
        depth, i = 1, m.end()
        while depth:
            depth += {"(": 1, ")": -1}.get(source[i], 0)
            i += 1
        out.append(source[m.end():i - 1])
    return out


def test_cpu_is_passed_at_three_sites_and_is_no_span_attribute():
    """`cpu=` is taken out of measure's **attrs by name, so no span may
    carry an attribute called cpu: none does, and the three sites that
    pass it are those read through the sinks alone (ISSUE 39)."""
    passed = {}
    for path in glob.glob(os.path.join(ROOT, "nomad_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            source = f.read()
        for opener in ("metrics.measure", "self._stage"):
            for args in _calls(source, opener):
                if re.search(r"\bcpu\s*=", args):
                    key = ".".join(re.findall(r'"([a-z_]+)"', args))
                    passed[key] = (opener, re.search(
                        r"\bcpu\s*=\s*(\w+)", args).group(1))
    assert passed == {
        "nomad.plan.evaluate": ("metrics.measure", "True"),
        "nomad.plan.apply": ("metrics.measure", "True"),
        "nomad.sched.system.sweep": ("metrics.measure", "True")}


# ------------------------------------------------------------ worker stages
with open(pipelined_worker.__file__) as _f:
    WORKER_SOURCE = _f.read()
STAGES = sorted(set(re.findall(r'self\.(?:_stage|_hand_off)\(\s*"([a-z_]+)"',
                               WORKER_SOURCE)))


def _served():
    srv = Server(ServerConfig(num_schedulers=0, pipelined_scheduling=True,
                              scheduler_window=8))
    srv.establish_leadership()
    for _ in range(4):
        srv.node_register(mock.node())
    worker = PipelinedWorker(srv.raft, srv.eval_broker, srv.plan_queue,
                             srv.blocked_evals, srv.tindex,
                             ["service", "batch", "system"], window=8)
    worker.name = "w-test"
    yield srv, worker
    srv.shutdown()


served = pytest.fixture(scope="module")(_served)
# A server of the test's own: a stale or fallback record taints the chain,
# and the next lease would wait for whatever an earlier test left in flight.
served_alone = pytest.fixture()(_served)


def test_the_worker_times_every_stage_the_issue_lists():
    assert set(STAGES) == {"lease", "fill", "dispatch", "refresh", "nodectx",
                           "launch", "drain_stack", "drain", "drain_fetch",
                           "build", "collect", "netassign", "planwait",
                           "evalupd", "slow",
                           # ISSUE 39: the offers at the two seams and the
                           # chain-order barrier inside the plan wait.
                           "handoff_drain", "handoff_build", "turnwait"}
    # The per-eval timers of _try_dispatch_fast are all that is left of
    # the hand-written pairs: they add to `stats` alone, by design.
    assert WORKER_SOURCE.count("perf_counter()") == 5


@pytest.mark.parametrize("stage", STAGES)
def test_a_stage_is_a_stats_key_a_sample_and_a_span(served, spans, samples,
                                                    stage):
    _, worker = served
    key = f"t_{stage}_ms"
    assert key in STATS_TIMERS_MS
    before = worker.stats[key]
    cpu_before = worker.stats.get(f"t_{stage}_cpu_ms")
    with worker._stage(stage, 12):
        time.sleep(0.001)
    [sample] = samples.of(f"nomad.worker.{stage}")
    assert sample >= 1.0
    assert worker.stats[key] - before == pytest.approx(sample)
    # The thread's CPU beside it, for the eight stages that partition
    # their thread's time and for no stage nested in them; never a second
    # registry sample.
    cpu_key = f"t_{stage}_cpu_ms"
    assert (cpu_key in worker.stats) is (stage in CPU_STAGES)
    if stage in CPU_STAGES:
        assert 0.0 <= worker.stats[cpu_key] - cpu_before <= sample + 1.0
    assert samples.of(f"nomad.worker.{stage}.cpu") == []
    assert spans.opened == [(f"nomad.worker.{stage}",
                             {"worker": "w-test", "window": 12})]
    assert spans.closed == [f"nomad.worker.{stage}"]


def test_the_spans_of_one_window_share_its_number(served, spans, samples):
    srv, worker = served
    ready0 = srv.eval_broker.stats.TotalReady
    jobs = [mock.job() for _ in range(3)]
    for job in jobs:
        srv.job_register(job)
    # The fill below lingers FILL_TIMEOUT (2 ms) for stragglers: under a
    # loaded machine that is no time at all, so the three evals have to
    # stand in the ready queue before the first is taken.
    assert wait_for(lambda: srv.eval_broker.stats.TotalReady - ready0 >= 3,
                    interval=0.002)
    fill0, wait0 = worker.stats["t_fill_ms"], worker.stats["t_stagewait_ms"]
    objects0 = worker.stats["plans_objects"]
    rows0 = worker.stats["plan_rows"]
    first = worker._dequeue_first()
    batch = [first]
    work = worker._dispatch_window(batch, fill=True)
    assert len(batch) == 3 and len(work.fast) == 3  # filled in place
    worker._hand_off("handoff_drain", worker._drain_q, work)
    assert worker._drain_q.get() is work
    time.sleep(0.002)
    worker._enter_stage("t_wait_drain_ms", work)
    with worker._stage("drain", work.number):
        work.packed = worker._drain_window(work)
    worker._finish_fast(work)
    assert worker.stats["fast"] >= 3
    # Three submitted plans, each counted once: mock.job asks for a port,
    # so their placements were objects from collect on.
    assert worker.stats["plans_objects"] - objects0 == 3
    assert worker.stats["plans_columnar"] == 0
    # ... and the rows they carried to the applier: what was placed.
    assert worker.stats["plan_rows"] - rows0 == sum(
        len(srv.state.allocs_by_job(job.ID)) for job in jobs) > 0
    # Placed on the host: no serial replay step was launched.
    assert worker.stats["launch_steps"] == 0
    assert worker.stats["launch_placements"] == 0
    assert worker.stats["t_stagewait_ms"] - wait0 >= 2.0
    assert worker.stats["t_fill_ms"] > fill0
    by_name = {}
    for name, attrs in spans.opened:  # another test's worker may still run
        if name.startswith("nomad.worker.") \
                and attrs.get("worker") == "w-test":
            by_name.setdefault(name[len("nomad.worker."):], []).append(attrs)
    # One span a stage a window, none per eval; all carry this window.
    for stage in ("fill", "dispatch", "refresh", "nodectx", "launch",
                  "drain_stack", "drain", "build", "collect", "planwait",
                  "evalupd"):
        # The launch span also says what the window is about to cost: the
        # device launches it makes (none here: three small evals place on
        # the host, so no serial step and no placement is launched) and the
        # node contexts it looked up.
        extra = {"runs": 0, "dc_sets": 1, "steps": 0, "placements": 0} \
            if stage == "launch" else {}
        assert by_name[stage] == [{"worker": "w-test",
                                   "window": work.number, **extra}], stage
    # Nesting as the timeline shows it: refresh, nodectx, launch and
    # drain_stack open and close inside dispatch; collect inside build.
    order = [n for n, a in spans.opened if a.get("worker") == "w-test"]
    closed = [n for n, who in spans.closed_by if who == "w-test"]
    assert order.index("nomad.worker.fill") \
        < order.index("nomad.worker.dispatch") \
        < order.index("nomad.worker.refresh") \
        < order.index("nomad.worker.nodectx")
    assert closed.index("nomad.worker.nodectx") \
        < closed.index("nomad.worker.launch")
    assert closed.index("nomad.worker.drain_stack") \
        < closed.index("nomad.worker.dispatch")
    assert closed.index("nomad.worker.collect") \
        < closed.index("nomad.worker.build")
    # The registry saw the same stages, and the plan applier's own.
    assert samples.of("nomad.worker.planwait")
    assert samples.of("nomad.plan.apply")
    assert any(n.startswith("nomad.fsm.") for n, _ in samples.rows)


def _plain_job():
    job = mock.job()  # the storm's shape: no port asked, nothing to register
    task = job.TaskGroups[0].Tasks[0]
    task.Resources.Networks = []
    task.Resources.CPU, task.Resources.MemoryMB = 20, 32
    task.Services = []
    job.TaskGroups[0].Count = 3
    return job


def _window_of(srv, worker, jobs):
    ready0 = srv.eval_broker.stats.TotalReady
    for job in jobs:
        srv.job_register(job)
    assert wait_for(lambda: srv.eval_broker.stats.TotalReady - ready0
                    >= len(jobs), interval=0.002)
    work = worker._dispatch_window(worker._dequeue_window())
    assert len(work.fast) == len(jobs)
    work.packed = worker._drain_window(work)
    return work


def _settle(worker, work):
    worker._finish_fast(work)
    worker._arbiter.mark_settled(work.chain_seq)
    worker._arbiter.finish_window()


# --------------------------------------------------- the seams and the waits
@pytest.mark.parametrize("seam", ["drain", "build"])
def test_a_blocked_hand_off_is_a_stage_of_the_givers_thread(
        served_alone, spans, samples, seam):
    """A seam holds one window. With its slot taken the giver's put
    blocks: that is the span nomad.worker.handoff_<seam> on the giver's
    thread and stats["t_handoff_<seam>_ms"]; the taker counts the same
    time again, from the stamp to its take, under its seam's key."""
    _, worker = served_alone
    q = worker._drain_q if seam == "drain" else worker._build_q
    other = "build" if seam == "drain" else "drain"
    q.put(pipelined_worker._WindowWork(fast=[], slow=[], number=6))
    work = pipelined_worker._WindowWork(fast=[], slow=[], number=7)
    opened_on = []
    real = spans.__call__

    def on_thread(name, **attrs):
        opened_on.append((name, threading.current_thread().name))
        return real(name, **attrs)

    metrics._annotation = on_thread  # the fixture restores it
    giver = threading.Thread(
        target=worker._hand_off, args=(f"handoff_{seam}", q, work),
        name="the-giver")
    giver.start()
    time.sleep(0.05)
    assert giver.is_alive()  # blocked in put
    assert spans.closed == []
    assert q.get(timeout=5).number == 6  # the test is the taker
    giver.join(timeout=5)
    assert not giver.is_alive()
    assert q.get(timeout=5) is work
    worker._enter_stage(f"t_wait_{seam}_ms", work)
    assert opened_on == [(f"nomad.worker.handoff_{seam}", "the-giver")]
    assert spans.opened == [(f"nomad.worker.handoff_{seam}",
                             {"worker": "w-test", "window": 7})]
    [blocked] = samples.of(f"nomad.worker.handoff_{seam}")
    stats = worker.stats
    assert blocked >= 45.0
    assert stats[f"t_handoff_{seam}_ms"] == pytest.approx(blocked)
    # The taker's wait holds the blocked put and the time in the slot.
    assert stats[f"t_wait_{seam}_ms"] >= blocked
    assert stats[f"t_handoff_{other}_ms"] == 0.0
    assert stats[f"t_wait_{other}_ms"] == 0.0
    assert stats["t_stagewait_ms"] == stats[f"t_wait_{seam}_ms"]


def test_the_two_seams_sum_to_the_stage_wait_after_a_served_storm():
    """The workers as a server runs them, three threads each: every window
    crossed both seams, and t_stagewait_ms (what stage_wait_ms.* reads) is
    the two seams' sum, exactly."""
    srv = Server(ServerConfig(num_schedulers=2, pipelined_scheduling=True,
                              scheduler_window=4))
    srv.establish_leadership()
    try:
        for _ in range(4):
            srv.node_register(mock.node())
        evals = [srv.job_register(_plain_job())[0] for _ in range(24)]
        assert wait_for(lambda: all(
            (e := srv.state.eval_by_id(i)) is not None
            and e.Status == EvalStatusComplete for i in evals), timeout=90)
        workers = list(srv.workers)
    finally:
        srv.shutdown()
    assert sum(w.stats["windows"] for w in workers) >= 2
    for w in workers:
        stats = w.stats
        assert stats["t_stagewait_ms"] \
            == stats["t_wait_drain_ms"] + stats["t_wait_build_ms"]
        if stats["windows"]:
            assert stats["t_wait_drain_ms"] > 0.0
            assert stats["t_wait_build_ms"] > 0.0
        # A blocked put lies inside its taker's wait.
        assert stats["t_handoff_drain_ms"] <= stats["t_wait_drain_ms"]
        assert stats["t_handoff_build_ms"] <= stats["t_wait_build_ms"]
        # What a stage cost is no more than how long it stood open.
        for stage in CPU_STAGES:
            assert 0.0 <= stats[f"t_{stage}_cpu_ms"] \
                <= stats[f"t_{stage}_ms"] + 1.0 * max(1, stats["windows"])


def test_the_turn_wait_nests_in_the_plan_wait_with_its_window(served, spans):
    srv, worker = served
    before = dict(worker.stats)
    work = _window_of(srv, worker, [_plain_job()])
    _settle(worker, work)
    mine = [(n, a) for n, a in spans.opened if a.get("worker") == "w-test"]
    names = [n for n, _ in mine]
    closed = [n for n, who in spans.closed_by if who == "w-test"]
    assert [a for n, a in mine if n == "nomad.worker.turnwait"] \
        == [{"worker": "w-test", "window": work.number}]
    assert names.index("nomad.worker.planwait") \
        < names.index("nomad.worker.turnwait")
    assert closed.index("nomad.worker.turnwait") \
        < closed.index("nomad.worker.planwait")
    turn = worker.stats["t_turnwait_ms"] - before["t_turnwait_ms"]
    assert 0.0 <= turn <= worker.stats["t_planwait_ms"] \
        - before["t_planwait_ms"]


@pytest.mark.parametrize("waiter", ["blocked", "late"])
def test_the_wake_is_sampled_by_a_waiter_that_blocked_and_by_no_other(
        samples, waiter):
    from nomad_tpu.server.plan_queue import PendingPlan

    pending = PendingPlan(mock.plan())
    if waiter == "late":
        pending.respond(None, None)
        time.sleep(0.01)
        pending.wait(timeout=5)
        assert samples.of("nomad.plan.wake") == []
        return
    responder = threading.Timer(0.05, pending.respond, (None, None))
    responder.start()
    t0 = time.monotonic()
    pending.wait(timeout=5)
    waited = (time.monotonic() - t0) * 1e3
    responder.join(timeout=5)
    [wake] = samples.of("nomad.plan.wake")
    # From respond's stamp, not from the wait's start.
    assert 0.0 <= wake < waited - 30.0
    assert pending.responded >= t0 + 0.04


def test_a_plan_that_times_out_samples_no_wake(samples):
    from nomad_tpu.server.plan_queue import PendingPlan

    with pytest.raises(TimeoutError):
        PendingPlan(mock.plan()).wait(timeout=0.01)
    assert samples.of("nomad.plan.wake") == []


def test_the_applier_samples_one_join_a_group_behind_a_commit(dev_server,
                                                              samples):
    """Three plans one after the other are three groups of one: the first
    has no commit before it, each of the other two joins its
    predecessor's apply thread once (already ended here: ~0)."""
    for _ in range(3):
        _run_job(dev_server, _plain_job())
    groups = len(samples.of("nomad.plan.apply"))
    assert groups >= 3
    joins = samples.of("nomad.plan.join")
    assert len(joins) == groups - 1
    assert all(0.0 <= j < 5000.0 for j in joins)
    # The verify and the apply carry their thread CPU (cpu=True).
    assert len(samples.of("nomad.plan.apply.cpu")) == groups
    assert len(samples.of("nomad.plan.evaluate.cpu")) \
        == len(samples.of("nomad.plan.evaluate"))


def test_the_applier_keeps_no_timer_of_its_own(dev_server):
    """PlanApplier.stats["t_verify_ms"] / ["t_apply_ms"] went with ISSUE
    39: nothing read them, and the blocks they timed are the registry's
    nomad.plan.evaluate and nomad.plan.apply."""
    assert set(dev_server.plan_applier.stats) == {
        "applied", "rejected", "overlapped", "apply_failed"}


def test_a_columns_only_window_still_opens_one_collect_span(served, spans):
    srv, worker = served
    before = dict(worker.stats)
    work = _window_of(srv, worker, [_plain_job(), _plain_job()])
    _settle(worker, work)
    assert worker.stats["plans_columnar"] - before["plans_columnar"] == 2
    assert worker.stats["plans_objects"] == before["plans_objects"]
    assert worker.stats["plan_rows"] - before["plan_rows"] == 2 * 3
    assert worker.stats["t_collect_ms"] > before["t_collect_ms"]
    mine = [(n, a) for n, a in spans.opened if a.get("worker") == "w-test"]
    assert [a for n, a in mine if n == "nomad.worker.collect"] \
        == [{"worker": "w-test", "window": work.number}]
    assert [n for n, _ in mine].count("nomad.worker.build") == 1


@pytest.mark.parametrize("case", ["all-placed", "a-port-asked",
                                  "one-of-each", "a-stale-record"])
def test_a_record_is_counted_once_by_the_build_that_made_its_plan(
        served_alone, case):
    """`collect_windowed` / `collect_exact` (ISSUE 31): a storm's evals,
    which place everything and ask for no network, are all built by the
    window's one pass; mock.job's port ask takes the exact loop; a stale
    record is counted by neither."""
    srv, worker = served_alone
    jobs = {"all-placed": [_plain_job(), _plain_job(), _plain_job()],
            "a-port-asked": [mock.job()],
            "one-of-each": [_plain_job(), mock.job(), _plain_job()],
            "a-stale-record": [_plain_job(), _plain_job()]}[case]
    work = _window_of(srv, worker, jobs)
    before = dict(worker.stats)
    if case == "a-stale-record":
        work.fast[0].stale = True
    _settle(worker, work)
    moved = {k: worker.stats[k] - before[k]
             for k in ("collect_windowed", "collect_exact", "stale",
                       "fallback")}
    plain = sum(1 for j in jobs
                if not j.TaskGroups[0].Tasks[0].Resources.Networks)
    if case == "a-stale-record":
        assert moved == {"collect_windowed": 1, "collect_exact": 0,
                         "stale": 1, "fallback": 0}
    else:
        assert moved["collect_windowed"] == plain
        assert moved["collect_exact"] == len(jobs) - plain
        assert moved["stale"] == moved["fallback"] == 0


def test_a_pass_that_raises_sends_its_evals_to_the_exact_path(served_alone,
                                                              monkeypatch):
    """The window's pass is one call for many evals: if it raises, each of
    them re-runs per eval (counted exact, then fallback), none is lost."""
    from nomad_tpu.scheduler.stack import WindowCollect

    srv, worker = served_alone
    jobs = [_plain_job(), _plain_job()]
    work = _window_of(srv, worker, jobs)
    before = dict(worker.stats)

    def boom(self, queued):
        raise RuntimeError("injected")

    with monkeypatch.context() as patch:
        patch.setattr(WindowCollect, "_build", boom)
        _settle(worker, work)
    assert worker.stats["collect_windowed"] == before["collect_windowed"]
    assert worker.stats["collect_exact"] - before["collect_exact"] == 2
    assert worker.stats["fallback"] - before["fallback"] == 2
    for job in jobs:
        placed = [a for a in srv.state.allocs_by_job(job.ID)
                  if not a.terminal_status()]
        assert len(placed) == job.TaskGroups[0].Count


with open(os.path.join(ROOT, "README.md")) as _f:
    README = _f.read()
README_STATS = [ln for ln in README.splitlines() if ln.startswith("| `")]


@pytest.mark.parametrize("key", STATS_COUNTERS + STATS_TIMERS_MS)
def test_a_stats_key_is_seeded_and_the_readme_says_what_it_counts(key):
    """One declared schema: the key is there before anything ran, zero of
    its kind, and README documents it (the mesh's keys under "Mesh
    serving", every other in the stats-key table)."""
    seeded = new_stats()[key]
    assert seeded == 0
    assert isinstance(seeded, float) is key.startswith("t_")
    assert f"`{key}`" in README, key
    if "mesh" not in key:
        assert any(f"`{key}`" in ln.split("|")[1] for ln in README_STATS)


def test_the_schema_counts_how_a_fast_plan_carried_its_placements():
    assert {"plans_columnar", "plans_objects", "plan_rows",
            "collect_windowed", "collect_exact", "launch_steps",
            "launch_placements"} <= set(STATS_COUNTERS)
    [row] = [ln for ln in README_STATS
             if "`collect_windowed`" in ln.split("|")[1]]
    assert "`collect_exact`" in row.split("|")[1]
    assert len(set(STATS_COUNTERS + STATS_TIMERS_MS)) \
        == len(STATS_COUNTERS) + len(STATS_TIMERS_MS)
    [row] = [ln for ln in README_STATS if "`plans_columnar`" in ln]
    assert "`plans_objects`" in row.split("|")[1]


# ---------------------------------------------------- broker and plan queue
@pytest.fixture()
def dev_server():
    srv = Server(ServerConfig(num_schedulers=1, dev_mode=True))
    srv.establish_leadership()
    for _ in range(2):
        srv.node_register(mock.node())
    yield srv
    srv.shutdown()
    trace.configure(enabled=False, sample_ratio=1.0, ring=128)
    trace.clear()


def _system_job():
    job = mock.system_job()
    task = job.TaskGroups[0].Tasks[0]
    task.Resources.DiskMB = 150
    task.Resources.Networks = []
    task.Services = []
    return job


def _run_job(srv, job):
    eval_id, _, _ = srv.job_register(job)
    assert wait_for(lambda: (
        (e := srv.state.eval_by_id(eval_id)) is not None
        and e.Status == EvalStatusComplete))
    return eval_id


@pytest.mark.parametrize("job", [mock.job, _system_job],
                         ids=["service", "system"])
def test_the_waits_are_sampled_with_tracing_off(dev_server, samples, job):
    assert not trace.is_enabled()
    _run_job(dev_server, job())
    [wait] = samples.of("nomad.broker.wait")
    assert 0.0 <= wait < 5000.0
    queued = samples.of("nomad.plan.queue_wait")
    assert queued and all(0.0 <= q < 5000.0 for q in queued)


def test_the_dapper_broker_wait_span_is_the_registrys_sample(dev_server,
                                                             samples):
    trace.configure(enabled=True, sample_ratio=1.0, ring=128)
    with trace.root_span("test.register"):
        eval_id = _run_job(dev_server, mock.job())
    [wait] = samples.of("nomad.broker.wait")
    found = [s for t in trace.traces()
             for s in trace.get_trace(t["TraceID"])["Spans"]
             if s["Name"] == "broker.wait"
             and s["Attrs"].get("eval") == eval_id]
    assert len(found) == 1
    # One stamp, read twice a few microseconds apart.
    assert found[0]["DurationMs"] == pytest.approx(wait, abs=1.0)


def test_a_redelivered_eval_waits_from_its_re_entry(samples):
    from nomad_tpu.server.eval_broker import EvalBroker

    broker = EvalBroker(nack_timeout=5.0)
    broker.set_enabled(True)
    ev = mock.eval()
    broker.enqueue(ev)
    time.sleep(0.02)
    got, token = broker.dequeue([ev.Type], timeout=1.0)
    broker.nack(got.ID, token)
    got, token = broker.dequeue([ev.Type], timeout=1.0)
    first, second = samples.of("nomad.broker.wait")
    assert first >= 20.0 and second < first
    broker.ack(got.ID, token)
    assert broker._ready_at == {}
    broker.set_enabled(False)


# ------------------------------------------------------------ program names
def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _placement_args(n, p, reset):
    args = [_f32(n, 5), _f32(n, 2), _f32(n, 5),
            jax.ShapeDtypeStruct((1, n), jnp.bool_),
            jax.ShapeDtypeStruct((n,), jnp.int32), None,
            jax.ShapeDtypeStruct((p,), jnp.int32),
            jax.ShapeDtypeStruct((p,), jnp.bool_), _f32(n), _f32(),
            jax.ShapeDtypeStruct((), jnp.bool_),
            jax.ShapeDtypeStruct((n,), jnp.bool_)]
    if reset:
        args.append(jax.ShapeDtypeStruct((p,), jnp.bool_))
    return args


def _lowered(name):
    n, p = 16, 8
    if name in ("place_batch", "place_batch_multi"):
        args = _placement_args(n, p, reset=name.endswith("multi"))
        args[5] = _f32(p, 5)
        return getattr(kernels, name).lower(*args)
    if name == "place_batch_keyed":
        args = _placement_args(n, p, reset=True)
        args[5] = _f32(1, 5)
        return kernels._keyed_program(None, 8).lower(*args)
    if name == "compact_window":
        return kernels.compact_window.lower(
            _f32(2, p, 3), jax.ShapeDtypeStruct((2, p), jnp.bool_),
            jax.ShapeDtypeStruct((2,), jnp.int32))
    if name == "node_table_refresh":
        return node_table._refresh_program().lower(
            _f32(n, 5), _f32(n, 2), _f32(n, 5),
            _f32(4, 3 + 2 * node_table.RES_DIMS))
    raise AssertionError(f"no program known for {name!r}: add it here")


@pytest.mark.parametrize("name", kernels.PROGRAM_NAMES)
def test_a_program_carries_its_declared_name(name):
    text = _lowered(name).as_text()
    assert re.search(r"module @jit_%s\b" % re.escape(name), text), \
        text[:120]


def test_the_benchmarks_kernel_metric_still_finds_the_placement_programs():
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "kernel_ms.storm.json")) as f:
        match = json.load(f)["args"]["match"]
    hit = {name for name in kernels.PROGRAM_NAMES
           if any(m in "jit_" + name for m in match)}
    assert {"place_batch_keyed", "place_batch_multi", "place_batch",
            "compact_window"} <= hit
    assert "node_table_refresh" not in hit  # a refresh is not a placement
