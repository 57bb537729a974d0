"""Where a traced run's trace starts (ISSUE 38): at a fixed time after the
window opens, as ever, or, for a traffic file with a fill guard that states
trace_guard_share, when the allocations asked for inside the window come
within that share of the guard's limit, or at the window's end, whichever
comes first. With a timer the test fires itself, a profiler that starts
nothing, and a scripted `asked`; then closed_loop.run's side of it on a
made-up deployment."""

import json
import os
import random

import pytest

from benchmark import instruments
from benchmark.generators import closed_loop, open_loop
from benchmark.reference.guarantees import capacity_allocs
from nomad_tpu import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class _Dep:
    def worker_stats(self):
        return {"windows": 0}


class _Timer:
    """threading.Timer's face; start() of a delay of 0 fires at once, any
    other waits for the test's fire()."""

    made = []

    def __init__(self, delay, fn, args=()):
        self.delay, self.fn, self.args = delay, fn, args
        self.cancelled = self.fired = False
        _Timer.made.append(self)

    def start(self):
        if self.delay == 0.0:
            self.fire()

    def fire(self):
        if not self.cancelled and not self.fired:
            self.fired = True
            self.fn(*self.args)

    def cancel(self):
        self.cancelled = True


@pytest.fixture
def probe_of(monkeypatch, tmp_path):
    import jax

    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: started.append(a[0]))
    now = [100.0]
    monkeypatch.setattr(instruments.time, "perf_counter", lambda: now[0])
    monkeypatch.setattr(instruments.time, "sleep", lambda s: None)
    _Timer.made = []

    def make(share, trace_seconds=3):
        probe = instruments.Window(
            _Dep(), instruments.CompileLog(), traced=True,
            trace_dir=str(tmp_path / "trace"), trace_seconds=trace_seconds,
            on_chip=False, trace_guard_share=share, timer=_Timer)
        probe.begin(30.0)
        return probe

    yield make, now, started
    from nomad_tpu.telemetry import metrics

    import gc
    for cb in list(gc.callbacks):  # a probe that never reached end()
        if getattr(cb, "__self__", None).__class__ is instruments.Window:
            gc.callbacks.remove(cb)
    metrics.registry._sinks = [s for s in metrics.registry._sinks
                               if not isinstance(s, instruments.SampleSink)]


LIMIT = 1_000_000.0


def test_the_guards_approach_starts_the_trace_when_it_comes_first(probe_of):
    make, now, started = probe_of
    probe = make(0.35)
    (clock,) = _Timer.made
    assert clock.delay == 27.0 and clock.args == ("clock",)
    for asked, at in ((256_000, 100.1), (500_000, 102.0), (649_000, 103.4)):
        now[0] = at
        probe.progress(asked, LIMIT)
    assert started == [] and probe.trace_facts() is None
    now[0] = 103.5
    probe.progress(650_000, LIMIT)  # 65 % of the limit: 35 % of it is left
    assert len(started) == 1 and clock.cancelled
    assert probe.trace_facts() == {"started_after_s": pytest.approx(3.5),
                                   "started_by": "guard"}
    # Once: later registrations, the late clock and the window's end find
    # the trace running.
    now[0] = 104.0
    probe.progress(700_000, LIMIT)
    clock.fire()
    now[0] = 105.6
    probe.end()
    assert len(started) == 1 and len(_Timer.made) == 2
    assert probe.trace_facts()["started_after_s"] == pytest.approx(3.5)
    assert probe.t1 - probe._trace_t0 == pytest.approx(2.1)


def test_the_clock_starts_the_trace_when_it_comes_first(probe_of):
    # web-10k.storm today: the 28 s mark comes before 95 % of the limit.
    make, now, started = probe_of
    probe = make(0.05, trace_seconds=2)
    (clock,) = _Timer.made
    assert clock.delay == 28.0
    now[0] = 127.9
    probe.progress(0.93 * LIMIT, LIMIT)
    assert started == []
    now[0] = 128.0
    clock.fire()
    assert probe.trace_facts() == {"started_after_s": pytest.approx(28.0),
                                   "started_by": "clock"}
    now[0] = 129.0
    probe.progress(0.96 * LIMIT, LIMIT)  # past the share: nothing new
    now[0] = 130.0
    probe.end()
    assert len(started) == 1
    assert probe.trace_facts()["started_by"] == "clock"


def test_the_windows_end_starts_the_trace_when_it_comes_first(probe_of):
    make, now, started = probe_of
    probe = make(0.35)
    now[0] = 101.0
    probe.progress(0.5 * LIMIT, LIMIT)
    now[0] = 101.5
    probe.end()  # the generator gave up early: trace the rest
    assert len(started) == 1 and _Timer.made[0].cancelled
    assert probe.trace_facts() == {"started_after_s": pytest.approx(1.5),
                                   "started_by": "window_end"}


@pytest.mark.parametrize("limit", [LIMIT, None], ids=["guard", "no-guard"])
def test_without_the_key_the_clock_decides_as_it_always_did(probe_of, limit):
    # A file that states no trace_guard_share (storm.json, storm-dcs.json)
    # and a window without a guard: progress is told and does nothing,
    # the timer is the one begin() always set, at the same delay.
    make, now, started = probe_of
    probe = make(None if limit else 0.35, trace_seconds=2)
    (clock,) = _Timer.made
    assert clock.delay == 28.0 and clock.args == ("clock",)
    now[0] = 110.0
    probe.progress(0.99 * LIMIT, limit)
    assert started == [] and _Timer.made == [clock] and not clock.cancelled
    now[0] = 128.0
    clock.fire()
    assert probe.trace_facts()["started_by"] == "clock"
    assert probe.trace_facts()["started_after_s"] == pytest.approx(28.0)


def test_an_untraced_probe_takes_no_notice_of_progress(probe_of, tmp_path):
    _, now, started = probe_of
    probe = instruments.Window(
        _Dep(), instruments.CompileLog(), traced=False,
        trace_dir=str(tmp_path / "t"), trace_seconds=3, on_chip=False,
        trace_guard_share=0.35, timer=_Timer)
    probe.begin(30.0)
    probe.progress(LIMIT, LIMIT)
    probe.end()
    assert started == [] and _Timer.made == []
    assert probe.trace_facts() is None and probe.counters() is None


@pytest.mark.parametrize("mix,share", [
    ("fill", 0.35), ("storm-ports", 0.05), ("storm", None),
    ("storm-dcs", None), ("trickle", None), ("rollout", None)])
def test_only_the_two_cells_a_guard_can_end_state_a_share(mix, share):
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           mix + ".json")) as f:
        traffic = json.load(f)
    assert traffic.get("trace_guard_share") == share
    if share is not None:
        assert traffic["fill_guard"] and 0.0 < share < 1.0


# -------------------------------------------------- the generator's side
class _State:
    def __init__(self, nodes):
        self._nodes = nodes

    def nodes(self):
        return self._nodes


class _Loop:
    """A deployment that completes every eval by the next poll."""

    def __init__(self, nodes=4, already=0):
        self.server = type("S", (), {"state": _State(
            [mock.node() for _ in range(nodes)])})()
        self.asked = already
        self.jobs = 0

    def make_job(self, template):
        job = mock.job()
        self.jobs += 1
        job.ID = f"job-{self.jobs}"
        return job

    def register(self, job):
        self.asked += sum(g.Count for g in job.TaskGroups)
        return "eval-" + job.ID

    def eval_status(self, eval_id):
        return "complete"


def _ticking():
    now = [0.0]

    def clock():
        now[0] += 0.001
        return now[0]
    return clock


def test_the_closed_loop_reports_every_registration_against_its_limit(
        monkeypatch):
    monkeypatch.setattr(closed_loop.time, "sleep", lambda s: None)
    dep = _Loop(nodes=40, already=20)
    room = capacity_allocs(dep.server.state.nodes(), mock.job())
    assert room == 40 * 7  # mock.Job() on mock.Node(): seven by CPU
    traffic = {"poll_ms": 20, "outstanding": 4, "templates": {"web": 1},
               "fill_guard": 0.5}
    told = []
    window = closed_loop.run(dep, traffic, random.Random(1), 30.0,
                             clock=_ticking(),
                             progress=lambda a, lim: told.append((a, lim)))
    limit = 0.5 * room - 20  # what the warm-up asked for counts
    assert limit == 120.0
    # One report a registration, in order, each a job of 10 further; the
    # guard ends the window at the report that reaches the limit.
    assert told == [(10 * (i + 1), limit) for i in range(12)]
    assert len(window["ops"]) == 12 and "fill guard" in window["notes"][0]
    # The same window without a listener, and without a guard.
    quiet = closed_loop.run(_Loop(nodes=40, already=20), traffic,
                            random.Random(1), 30.0, clock=_ticking())
    assert len(quiet["ops"]) == 12
    told.clear()
    clock = _ticking()
    free = closed_loop.run(_Loop(), dict(traffic, fill_guard=None),
                           random.Random(1), 0.05, clock=clock,
                           progress=lambda a, lim: told.append((a, lim)))
    assert told and all(lim is None for _, lim in told)
    assert [a for a, _ in told] == [10 * (i + 1)
                                    for i in range(len(free["ops"]))]


def test_an_open_loop_takes_the_hook_and_has_no_use_for_it(monkeypatch):
    # Without a fill guard (trickle.json, rollout.json) the hook is never
    # called, no job is built to size a room, and the window is the clock's.
    monkeypatch.setattr(open_loop.time, "sleep", lambda s: None)
    told = []
    traffic = {"poll_ms": 2, "arrival": "fixed", "rate_per_s": 100,
               "templates": {"web": 1}}
    dep = _Loop()
    clock = _ticking()
    window = open_loop.run(dep, traffic, random.Random(1), 0.1,
                           clock=clock, progress=told.append)
    assert window["ops"] and told == []
    assert dep.jobs == len(window["ops"]) and window["notes"] == []
    assert window["t1"] - window["t0"] == pytest.approx(0.1)
    assert [op.due - window["t0"] for op in window["ops"]] == pytest.approx(
        [0.01 * (i + 1) for i in range(len(window["ops"]))])
    for mix in ("trickle", "rollout"):
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               mix + ".json")) as f:
            assert "fill_guard" not in json.load(f)


def test_an_open_loop_with_a_guard_ends_the_window_at_the_limit(
        monkeypatch):
    monkeypatch.setattr(open_loop.time, "sleep", lambda s: None)
    dep = _Loop(nodes=40, already=20)
    traffic = {"poll_ms": 2, "arrival": "bursts", "burst": 32,
               "rate_per_s": 320, "templates": {"web": 1}, "fill_guard": 0.5}
    told = []
    window = open_loop.run(dep, traffic, random.Random(1), 30.0,
                           clock=_ticking(),
                           progress=lambda a, lim: told.append((a, lim)))
    limit = 0.5 * 40 * 7 - 20  # as closed_loop's: the warm-up's counts
    # One report a registration; the one that reaches the limit is the
    # last op sent, in the middle of the first burst of 32, and the window
    # ends there, long before the clock, with every op waited for.
    assert told == [(10 * (i + 1), limit) for i in range(12)]
    ops = window["ops"]
    assert len(ops) == 12 and all(op.done is not None for op in ops)
    assert len({op.due for op in ops}) == 1  # all of one burst
    assert window["t1"] - window["t0"] < 1.0
    assert "fill guard" in window["notes"][0]
    # The same window without a listener.
    quiet = open_loop.run(_Loop(nodes=40, already=20), traffic,
                          random.Random(1), 30.0, clock=_ticking())
    assert len(quiet["ops"]) == 12
