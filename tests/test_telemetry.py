"""Telemetry tests: sink aggregation, statsd datagrams, and counters
advancing through a real scheduling cycle (reference shapes: go-metrics
inmem/statsd behavior; EmitStats gauges of eval_broker.go:650-662)."""

import pytest

import socket
import time

from nomad_tpu import mock, telemetry
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs.structs import EvalStatusComplete
from nomad_tpu.telemetry.metrics import InMemSink, MetricsRegistry, StatsdSink


from helpers import wait_for  # noqa: E402

pytestmark = pytest.mark.timing_retry  # real timers/sockets: one retry

class TestInMemSink:
    def test_gauge_keeps_last_value(self):
        sink = InMemSink(interval=60.0)
        sink.set_gauge(("a", "b"), 1.0)
        sink.set_gauge(("a", "b"), 5.0)
        snap = sink.snapshot()
        assert snap["Gauges"] == [{"Name": "a.b", "Value": 5.0}]

    def test_samples_aggregate(self):
        sink = InMemSink(interval=60.0)
        for v in (10.0, 20.0, 30.0):
            sink.add_sample(("lat",), v)
        [s] = sink.snapshot()["Samples"]
        assert s["Count"] == 3
        assert s["Sum"] == 60.0
        assert s["Min"] == 10.0 and s["Max"] == 30.0
        assert abs(s["Mean"] - 20.0) < 1e-9

    def test_counters_aggregate(self):
        sink = InMemSink(interval=60.0)
        sink.incr_counter(("hits",), 1)
        sink.incr_counter(("hits",), 1)
        [c] = sink.snapshot()["Counters"]
        assert c["Count"] == 2 and c["Sum"] == 2.0

    def test_interval_rotation_bounded(self):
        sink = InMemSink(interval=1.0, retain=3)
        for i in range(10):
            with sink._lock:
                sink._current(1000.0 + i)  # each stamp its own interval
        assert len(sink._intervals) <= 3

    def test_interval_floored_to_one_second(self):
        # 0 would divide-by-zero inside the swallow-all sink fan-out and
        # silently blank telemetry; sub-second fragments every sample.
        assert InMemSink(interval=0).interval == 1.0
        assert InMemSink(interval=0.001).interval == 1.0

    def test_interval_rollover_starts_fresh_and_retains_past(self):
        """Crossing an interval boundary opens a NEW aggregation window
        (snapshot shows only the current one) while the previous interval
        stays retained for the dump/debug surfaces."""
        sink = InMemSink(interval=10.0, retain=5)
        with sink._lock:
            cur = sink._current(1000.0)
        cur["counters"]["hits"] = object()
        with sink._lock:
            nxt = sink._current(1011.0)  # next 10s bucket
        assert nxt is not cur
        assert nxt["counters"] == {}
        assert len(sink._intervals) == 2
        assert sink._intervals[0]["start"] == 1000.0
        assert sink._intervals[1]["start"] == 1010.0


class TestStatsdSink:
    def test_datagrams_cross_the_socket(self):
        recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        recv.bind(("127.0.0.1", 0))
        recv.settimeout(2.0)
        addr = "127.0.0.1:%d" % recv.getsockname()[1]
        sink = StatsdSink(addr)
        sink.set_gauge(("nomad", "broker", "total_ready"), 4)
        sink.incr_counter(("nomad", "rpc", "request"), 1)
        sink.add_sample(("nomad", "fsm", "register_job"), 1.25)
        got = set()
        for _ in range(3):
            got.add(recv.recv(1024).decode())
        assert "nomad.broker.total_ready:4|g" in got
        assert "nomad.rpc.request:1|c" in got
        assert "nomad.fsm.register_job:1.25|ms" in got
        sink.close()
        recv.close()


class TestRegistry:
    def test_measure_records_milliseconds(self):
        reg = MetricsRegistry()
        with reg.measure(("op",)):
            time.sleep(0.01)
        [s] = reg.snapshot()["Samples"]
        assert s["Name"] == "op"
        assert s["Min"] >= 5.0  # ms, not seconds

    def test_broken_sink_never_breaks_caller(self):
        reg = MetricsRegistry()

        class Bad:
            def set_gauge(self, k, v):
                raise RuntimeError("boom")

        reg.add_sink(Bad())
        reg.set_gauge(("g",), 1)  # must not raise
        assert reg.snapshot()["Gauges"][0]["Value"] == 1

    def test_reconfigure_closes_replaced_statsd_sink(self):
        """SIGHUP reloads swap the sink list; the replaced StatsdSink's
        UDP socket must be closed, not leaked (one socket per reload)."""
        recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        recv.bind(("127.0.0.1", 0))
        addr = "127.0.0.1:%d" % recv.getsockname()[1]
        try:
            reg = MetricsRegistry()
            reg.configure(statsd_addr=addr)
            old = next(s for s in reg._sinks
                       if isinstance(s, StatsdSink))
            reg.configure(statsd_addr=addr)
            new = next(s for s in reg._sinks
                       if isinstance(s, StatsdSink))
            assert new is not old
            assert old._sock.fileno() == -1, "replaced sink not closed"
            assert new._sock.fileno() != -1
            new.close()
        finally:
            recv.close()

    def test_unresolvable_statsd_addr_degrades_not_raises(self):
        """A bad statsd target must not abort agent boot/reload: warn and
        keep the in-memory sink."""
        reg = MetricsRegistry()
        reg.configure(statsd_addr="no-such-host.invalid:8125")
        assert not any(isinstance(s, StatsdSink) for s in reg._sinks)
        reg.set_gauge(("still", "working"), 1.0)
        assert reg.snapshot()["Gauges"][0]["Value"] == 1.0

    def test_fan_survives_concurrent_reconfigure(self):
        """_fan snapshots the sink-list reference under the lock; a storm
        of configure() swaps racing a storm of writes must neither raise
        nor blank telemetry."""
        import threading

        reg = MetricsRegistry()
        stop = threading.Event()
        errors = []

        def reconfigure():
            while not stop.is_set():
                try:
                    reg.configure(collection_interval=60.0)
                except Exception as exc:  # pragma: no cover
                    errors.append(exc)

        t = threading.Thread(target=reconfigure, daemon=True)
        t.start()
        try:
            for i in range(2000):
                reg.incr_counter(("race", "hits"))
        finally:
            stop.set()
            t.join(5.0)
        assert not errors


class TestTelemetryDumpHandler:
    def test_sigusr1_dump_logs_valid_snapshot_json(self, caplog):
        """The SIGUSR1 handler (cli/commands.py dump_telemetry) dumps the
        in-memory snapshot as one parseable JSON log line."""
        import json
        import logging

        from nomad_tpu.cli.commands import dump_telemetry

        telemetry.configure(collection_interval=3600.0)
        telemetry.incr_counter(("dump", "probe"))
        with caplog.at_level(logging.INFO, logger="nomad.agent"):
            dump_telemetry()  # signature-compatible with signal delivery
        [record] = [r for r in caplog.records
                    if "metrics snapshot" in r.getMessage()]
        payload = json.loads(record.getMessage().split(":", 1)[1])
        assert set(payload) == {"Timestamp", "Gauges", "Counters",
                                "Samples"}
        assert any(c["Name"] == "dump.probe"
                   for c in payload["Counters"])


class TestSchedulingCycleMetrics:
    def test_counters_advance_through_a_cycle(self):
        """One job register -> schedule -> commit cycle must leave FSM
        apply timers, plan evaluate/apply timers, and broker gauges in the
        global registry (reference: fsm.go:147, plan_apply.go:168,195,
        eval_broker.go:650)."""
        # Fresh in-mem sink with a huge interval: counts cannot rotate away
        # mid-test and earlier tests' noise is discarded.
        telemetry.configure(collection_interval=3600.0)
        before = telemetry.snapshot()

        def sample_count(snap, name):
            for s in snap["Samples"]:
                if s["Name"] == name:
                    return s["Count"]
            return 0

        srv = Server(ServerConfig(num_schedulers=1, dev_mode=True))
        try:
            srv.establish_leadership()
            for _ in range(2):
                srv.node_register(mock.node())
            job = mock.job()
            eval_id, _, _ = srv.job_register(job)
            assert wait_for(lambda: (
                (e := srv.state.eval_by_id(eval_id)) is not None
                and e.Status == EvalStatusComplete))
            srv._emit_stats()
            snap = telemetry.snapshot()
            assert sample_count(snap, "nomad.fsm.register_job") \
                > sample_count(before, "nomad.fsm.register_job")
            assert sample_count(snap, "nomad.fsm.register_node") \
                > sample_count(before, "nomad.fsm.register_node")
            assert sample_count(snap, "nomad.plan.evaluate") \
                > sample_count(before, "nomad.plan.evaluate")
            assert sample_count(snap, "nomad.plan.apply") \
                > sample_count(before, "nomad.plan.apply")
            gauges = {g["Name"] for g in snap["Gauges"]}
            assert "nomad.broker.total_ready" in gauges
            assert "nomad.plan.queue_depth" in gauges
            assert "nomad.heartbeat.active" in gauges
        finally:
            srv.shutdown()


# ------------------------------------------------- the runtime (ISSUE 39)
import gc  # noqa: E402
import glob  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

from nomad_tpu.telemetry import metrics  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TICKER = "runtime-metrics"


class _Rows:
    """A registry sink that keeps every call with the thread it came on."""

    def __init__(self):
        self.rows = []

    def _keep(self, kind, key, value):
        self.rows.append((kind, ".".join(key), value,
                          threading.current_thread().name))

    def add_sample(self, key, value):
        self._keep("sample", key, value)

    def set_gauge(self, key, value):
        self._keep("gauge", key, value)

    def incr_counter(self, key, value):
        self._keep("counter", key, value)

    def of(self, name):
        return [(v, t) for _, n, v, t in self.rows if n == name]


def _tickers():
    return [t for t in threading.enumerate() if t.name == TICKER]


def _callbacks():
    return [c for c in gc.callbacks
            if getattr(c, "__self__", None) is metrics.runtime]


@pytest.fixture()
def runtime_rows():
    """The process's one collector, held for the test whatever agents
    other tests left running, with the automatic collector off so that
    the test's own gc.collect calls are all there is."""
    sink = _Rows()
    metrics.registry.add_sink(sink)
    was_enabled = gc.isenabled()
    gc.disable()
    metrics.runtime.acquire()
    try:
        yield sink
    finally:
        metrics.runtime.release()
        if was_enabled:
            gc.enable()
        with metrics.registry._lock:
            metrics.registry._sinks = [s for s in metrics.registry._sinks
                                       if s is not sink]


class TestRuntimeCollector:
    def test_many_agents_share_one_and_the_last_stops_it(self):
        from nomad_tpu.agent import Agent
        from nomad_tpu.agent.agent import AgentConfig

        held = len(_tickers())  # 1 if an earlier test left an agent up
        assert held == len(_callbacks()) <= 1
        agents = [Agent(AgentConfig(server_enabled=False,
                                    client_enabled=False, http_port=0,
                                    bind_addr="127.0.0.1"))
                  for _ in range(5)]
        try:
            for agent in agents:
                agent.start()
                assert len(_tickers()) == len(_callbacks()) == 1
            for agent in agents[:-1]:
                agent.shutdown()
                agent.shutdown()  # a second shutdown gives nothing back
            assert len(_tickers()) == len(_callbacks()) == 1
        finally:
            for agent in agents:
                agent.shutdown()
        assert len(_tickers()) == len(_callbacks()) == held

    def test_the_first_agent_sets_the_cadence_and_the_last_gives_it_back(
            self):
        from nomad_tpu.agent import Agent
        from nomad_tpu.agent.agent import AgentConfig

        held = len(_tickers())  # 1 if an earlier test left an agent up
        was_enabled, found = gc.isenabled(), gc.get_threshold()
        gc.disable()  # no automatic collection moves the pace meanwhile
        if not held:
            gc.set_threshold(900, 11, 12)  # what an embedding program set
        before = gc.get_threshold()
        agents = [Agent(AgentConfig(server_enabled=False,
                                    client_enabled=False, http_port=0,
                                    bind_addr="127.0.0.1"))
                  for _ in range(5)]
        try:
            for agent in agents:
                agent.start()
                assert gc.get_threshold()[:2] == (metrics.GC_YOUNG,
                                                  metrics.GC_MIDDLE)
            if not held:
                assert gc.get_threshold()[2] == metrics.GC_FULL
            for agent in agents[:-1]:
                agent.shutdown()
            assert gc.get_threshold()[:2] == (metrics.GC_YOUNG,
                                              metrics.GC_MIDDLE)
        finally:
            for agent in agents:
                agent.shutdown()
            after = gc.get_threshold()
            gc.set_threshold(*found)
            if was_enabled:
                gc.enable()
        assert after == before

    def test_a_full_collection_paces_the_next_by_what_it_reclaimed(self):
        """Doubled after each that reclaimed nothing, up to the cap; back
        to the start after one that reclaimed anything; young collections
        never move it."""
        was_enabled, found = gc.isenabled(), gc.get_threshold()
        gc.disable()  # only the collections this test hands the callback
        mine = metrics.RuntimeCollector(MetricsRegistry())
        mine.acquire()
        try:
            def full(collected):
                info = {"generation": 2, "collected": collected,
                        "uncollectable": 0}
                mine._on_gc("start", info)
                mine._on_gc("stop", info)
                return gc.get_threshold()

            assert gc.get_threshold() == (metrics.GC_YOUNG,
                                          metrics.GC_MIDDLE, metrics.GC_FULL)
            paces = [full(0)[2] for _ in range(8)]
            assert paces == [20, 40, 80, 160, 320, 640, 640, 640]
            mine._on_gc("stop", {"generation": 0, "collected": 0,
                                 "uncollectable": 0})
            mine._on_gc("stop", {"generation": 1, "collected": 0,
                                 "uncollectable": 0})
            assert gc.get_threshold()[2] == 640
            assert full(3) == (metrics.GC_YOUNG, metrics.GC_MIDDLE,
                               metrics.GC_FULL)
            assert full(0)[2] == 2 * metrics.GC_FULL
            assert mine._runs == [1, 1, 10] and mine._reclaimed == 3
        finally:
            mine.release()
            after = gc.get_threshold()
            gc.set_threshold(*found)
            if was_enabled:
                gc.enable()
        assert after == found
        # Released, the callback paces nothing: the thresholds are the
        # program's again.
        mine._on_gc("stop", {"generation": 2, "collected": 0,
                             "uncollectable": 0})
        assert gc.get_threshold() == found

    def test_cyclic_garbage_is_reclaimed_under_the_cadence(self):
        """150,000 two-object cycles, each unreachable once made, with
        no gc.collect: the young collections the cadence brings reclaim
        them (a finalizer on every thousandth pair says which did)."""
        import weakref

        class Pair:
            __slots__ = ("other", "__weakref__")

        mine = metrics.RuntimeCollector(MetricsRegistry())
        was_enabled = gc.isenabled()
        gc.enable()
        mine.acquire()
        dead = []
        try:
            assert gc.get_threshold()[0] == metrics.GC_YOUNG
            for i in range(150_000):
                a, b = Pair(), Pair()
                a.other, b.other = b, a
                if i % 1000 == 0:
                    weakref.finalize(a, dead.append, i)
            del a, b
            first = sorted(dead)
        finally:
            mine.release()
            if not was_enabled:
                gc.disable()
        # The first young collection comes after 100,000 net allocations:
        # 50,000 pairs. At least those are gone.
        assert len(first) >= 50
        assert first[:50] == list(range(0, 50_000, 1000))

    def test_fifty_holders_are_one_thread_and_one_callback(self):
        mine = metrics.RuntimeCollector(MetricsRegistry())
        for _ in range(50):
            mine.acquire()
        try:
            assert [c for c in gc.callbacks
                    if getattr(c, "__self__", None) is mine] == [mine._on_gc]
            assert mine._thread.is_alive()
            thread = mine._thread
            for _ in range(49):
                mine.release()
            assert thread.is_alive() and mine._on_gc in gc.callbacks
        finally:
            mine.release()
        assert not thread.is_alive() and mine._on_gc not in gc.callbacks
        mine.release()  # one too many: nothing to give back, no error

    def test_a_restarted_collector_counts_from_where_it_stands(self):
        """Stopped with the last agent and started again with the next:
        the young collections the first ticker handed over are not handed
        over again."""
        sink = _Rows()
        registry = MetricsRegistry()
        registry.add_sink(sink)
        mine = metrics.RuntimeCollector(registry)
        mine._runs[:] = [40, 4, 0]  # what an earlier run of it had counted
        mine.acquire()
        try:
            gc.collect(0)
            assert wait_for(
                lambda: sink.of("nomad.runtime.gc_runs.gen0"), timeout=5,
                interval=0.02)
        finally:
            mine.release()
        assert 1 <= sum(v for v, _ in sink.of(
            "nomad.runtime.gc_runs.gen0")) < 40

    def test_a_full_collection_is_one_sample_and_one_span(self, runtime_rows,
                                                          tmp_path):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            gc.collect()
        finally:
            jax.profiler.stop_trace()
        assert wait_for(lambda: runtime_rows.of("nomad.runtime.gc"),
                        timeout=5, interval=0.01)
        [(ms, thread)] = runtime_rows.of("nomad.runtime.gc")
        assert 0.0 < ms < 5000.0
        # Kept by the callback, handed to the registry by the ticker: a
        # collection can start under a sink's own lock.
        assert thread == TICKER
        [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                               / "*.xplane.pb"))
        found = [(e.duration_ns, dict(e.stats))
                 for plane in jax.profiler.ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name == "nomad.runtime.gc"]
        assert len(found) == 1
        duration_ns, stats = found[0]
        assert duration_ns == pytest.approx(ms * 1e6, rel=0.5, abs=2e5)
        assert int(stats["generation"]) == 2
        assert int(stats["collected"]) >= 0  # set at the collection's stop

    def test_young_collections_are_counted_and_never_a_call_of_their_own(
            self, runtime_rows):
        me = threading.current_thread().name
        for _ in range(7):
            gc.collect(0)
        for _ in range(3):
            gc.collect(1)
        # Nothing reached the registry from the callback (this thread).
        assert [r for r in runtime_rows.rows
                if r[3] == me and r[1].startswith("nomad.runtime.")] == []
        assert metrics.runtime._full is None
        assert wait_for(lambda: runtime_rows.of("nomad.runtime.gc_runs.gen1"),
                        timeout=5, interval=0.02)
        assert sum(v for v, _ in runtime_rows.of(
            "nomad.runtime.gc_runs.gen0")) == 7
        assert runtime_rows.of("nomad.runtime.gc_runs.gen1") \
            == [(3.0, TICKER)]
        assert runtime_rows.of("nomad.runtime.gc") == []
        # With them, once a second, what the process got and how many
        # threads it runs.
        [(share, _)] = runtime_rows.of("nomad.runtime.cpu_share")[:1]
        assert 0.0 <= share < 100.0 * (os.cpu_count() or 1) + 100.0
        assert runtime_rows.of("nomad.runtime.threads")[0][0] \
            >= len(_tickers()) + 1

    def test_full_collections_what_they_reclaimed_and_the_cadence_are_flushed(
            self, runtime_rows):
        class Ring:
            __slots__ = ("next",)

        for _ in range(1000):
            ring = Ring()
            ring.next = ring
        del ring
        gc.collect()
        assert wait_for(
            lambda: runtime_rows.of("nomad.runtime.gc_reclaimed.gen2"),
            timeout=5, interval=0.02)
        # The ticker's, once a second, with the young counts.
        runs = runtime_rows.of("nomad.runtime.gc_runs.gen2")
        assert sum(v for v, _ in runs) >= 1
        assert {t for _, t in runs} == {TICKER}
        [(reclaimed, thread)] = runtime_rows.of(
            "nomad.runtime.gc_reclaimed.gen2")
        assert reclaimed >= 1000 and thread == TICKER
        # The gauges of the tick that flushed it: a collection that
        # reclaimed something puts the full pace back to its start.
        young = runtime_rows.of("nomad.runtime.gc_threshold.gen0")
        full = runtime_rows.of("nomad.runtime.gc_threshold.gen2")
        assert young[-1] == (float(metrics.GC_YOUNG), TICKER)
        assert full[-1] == (float(metrics.GC_FULL), TICKER)

    def test_a_thread_that_keeps_the_interpreter_shows_as_a_late_tick(
            self, runtime_rows):
        """200 ms of bytecode with the switch interval raised: the ticker,
        like every other thread, cannot run until the loop ends."""
        interval = sys.getswitchinterval()
        sys.setswitchinterval(2.0)
        try:
            end = time.perf_counter() + 0.2
            while time.perf_counter() < end:
                pass
        finally:
            sys.setswitchinterval(interval)
        assert wait_for(
            lambda: [v for v, _ in runtime_rows.of("nomad.runtime.tick_late")
                     if v >= 100.0], timeout=4, interval=0.02)
        late = max(v for v, _ in runtime_rows.of("nomad.runtime.tick_late"))
        assert 100.0 <= late < 2000.0

    def test_without_jax_the_collector_imports_nothing(self):
        code = (
            "import gc, sys, time\n"
            "from nomad_tpu.telemetry import metrics\n"
            "metrics.runtime.acquire()\n"
            "gc.collect()\n"
            "deadline = time.monotonic() + 5\n"
            "seen = []\n"
            "while time.monotonic() < deadline and not seen:\n"
            "    time.sleep(0.02)\n"
            "    seen = [s for s in metrics.snapshot()['Samples']\n"
            "            if s['Name'] == 'nomad.runtime.gc']\n"
            "metrics.runtime.release()\n"
            "assert seen and seen[0]['Count'] >= 1, seen\n"
            "assert 'jax' not in sys.modules, 'the collector imported jax'\n"
            "assert not [t for t in __import__('threading').enumerate()\n"
            "            if t.name == 'runtime-metrics']\n"
            "print('plain')\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout.strip() == "plain"
