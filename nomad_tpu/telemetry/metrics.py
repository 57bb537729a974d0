"""Operational metrics: counters, gauges, and latency samples with pluggable
sinks (reference: the armon/go-metrics surface the reference instruments
through — MeasureSince/IncrCounter/SetGauge calls like nomad/fsm.go:147,
nomad/eval_broker.go:650-662 — with its InmemSink interval aggregation and
statsd push sink, configured from command/agent/command.go:556-580).

Design notes (TPU-first framework, Python runtime): one process-global
registry with a plain lock — every op is a couple of dict writes, far below
the cost of the raft/RPC/scheduler work being measured. Timings are
milliseconds (go-metrics convention). Keys are tuples of path segments,
rendered dotted ("nomad.fsm.apply") for sinks and the HTTP endpoint.
"""

from __future__ import annotations

import collections
import gc
import logging
import socket
import sys
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from nomad_tpu.analysis import guarded_by, requires_lock

logger = logging.getLogger("nomad.telemetry")

Key = Tuple[str, ...]


def _name(key: Iterable[str]) -> str:
    return ".".join(str(p) for p in key)


_annotation: Any = None  # jax.profiler.TraceAnnotation, once JAX is loaded


def _trace_annotation() -> Any:
    """jax.profiler.TraceAnnotation if THIS process has imported JAX, else
    None. Never imports it: a client agent or CLI process that runs no
    kernel stays JAX-free. Looked up in sys.modules until found, then
    kept (a server imports JAX at start-up, before its first measure)."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


class Measure:
    """One timed stage: a registry sample in ms AND a span on the
    profiler's timeline, from one call. The span is a
    jax.profiler.TraceAnnotation named by the dotted key and carrying
    `attrs`: inert while no profiler session runs, and on the same
    nanosecond clock as the device's program events while one does, so a
    trace shows host stages and device programs together. After the block
    `ms` holds the sample. With `cpu` the block's thread CPU
    (time.thread_time: what the block cost, where `ms` is how long it
    stood open; under one interpreter lock the two differ by what the
    thread waited for) is taken too, kept as `cpu_ms` and sampled as
    `<key>.cpu`. Only then: the thread's CPU clock is a system call each
    read, where the wall clock is not."""

    __slots__ = ("_registry", "_key", "_attrs", "_cpu", "_span", "_start",
                 "_cpu_start", "ms", "cpu_ms")

    def __init__(self, registry: Any, key: Key, attrs: Dict[str, Any],
                 cpu: bool = False) -> None:
        self._registry = registry
        self._key = tuple(key)
        self._attrs = attrs
        self._cpu = cpu
        self._span = None
        self.ms = 0.0
        self.cpu_ms = 0.0

    def __enter__(self) -> "Measure":
        annotation = _trace_annotation()
        if annotation is not None:
            self._span = annotation(_name(self._key), **self._attrs)
            self._span.__enter__()
        if self._cpu:
            self._cpu_start = time.thread_time()
        self._start = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.ms = (time.monotonic() - self._start) * 1000.0
        if self._cpu:
            self.cpu_ms = (time.thread_time() - self._cpu_start) * 1000.0
        if self._span is not None:
            self._span.__exit__(*exc)
        self._registry.add_sample(self._key, self.ms)
        if self._cpu:
            self._registry.add_sample(self._key + ("cpu",), self.cpu_ms)
        return False


class _Aggregate:
    """Streaming count/sum/min/max for one metric within one interval
    (reference: go-metrics AggregateSample)."""

    __slots__ = ("count", "sum", "min", "max")

    def __init__(self) -> None:
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")

    def ingest(self, v: float) -> None:
        self.count += 1
        self.sum += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v

    def to_dict(self, name: str) -> Dict[str, Any]:
        mean = self.sum / self.count if self.count else 0.0
        return {"Name": name, "Count": self.count, "Sum": self.sum,
                "Min": self.min if self.count else 0.0,
                "Max": self.max if self.count else 0.0, "Mean": mean}


class InMemSink:
    """Fixed-interval aggregating sink backing /v1/agent/metrics and the
    SIGUSR1-style dump (reference: go-metrics inmem.go — gauges keep last
    value, counters and samples aggregate per interval, a bounded ring of
    past intervals is retained)."""

    _concurrency = guarded_by("_lock", "_intervals")

    def __init__(self, interval: float = 10.0, retain: int = 60):
        # Sub-second intervals make every sample its own interval (and 0
        # would divide by zero inside the swallow-all sink fan-out, silently
        # blanking telemetry) — floor to 1s.
        self.interval = max(float(interval), 1.0)
        self.retain = retain
        self._lock = threading.Lock()
        self._intervals: List[Dict[str, Any]] = []

    @requires_lock("_lock")
    def _current(self, now: float) -> Dict[str, Any]:
        start = now - (now % self.interval)
        cur = self._intervals[-1] if self._intervals else None
        if cur is None or cur["start"] != start:
            cur = {"start": start, "gauges": {}, "counters": {},
                   "samples": {}}
            self._intervals.append(cur)
            if len(self._intervals) > self.retain:
                self._intervals = self._intervals[-self.retain:]
        return cur

    def set_gauge(self, key: Key, value: float) -> None:
        with self._lock:
            self._current(time.time())["gauges"][_name(key)] = value

    def incr_counter(self, key: Key, value: float) -> None:
        with self._lock:
            cur = self._current(time.time())["counters"]
            agg = cur.get(_name(key))
            if agg is None:
                agg = cur[_name(key)] = _Aggregate()
            agg.ingest(value)

    def add_sample(self, key: Key, value: float) -> None:
        with self._lock:
            cur = self._current(time.time())["samples"]
            agg = cur.get(_name(key))
            if agg is None:
                agg = cur[_name(key)] = _Aggregate()
            agg.ingest(value)

    def snapshot(self) -> Dict[str, Any]:
        """Most recent complete-or-current interval, display-formatted
        (reference: go-metrics DisplayMetrics shape behind the agent
        metrics endpoint)."""
        with self._lock:
            if not self._intervals:
                return {"Timestamp": "", "Gauges": [], "Counters": [],
                        "Samples": []}
            cur = self._intervals[-1]
            return {
                "Timestamp": time.strftime(
                    "%Y-%m-%d %H:%M:%S +0000",
                    time.gmtime(cur["start"])),
                "Gauges": [{"Name": n, "Value": v}
                           for n, v in sorted(cur["gauges"].items())],
                "Counters": [agg.to_dict(n) for n, agg in
                             sorted(cur["counters"].items())],
                "Samples": [agg.to_dict(n) for n, agg in
                            sorted(cur["samples"].items())],
            }


class StatsdSink:
    """Push sink emitting statsd datagrams over UDP, best-effort
    (reference: go-metrics statsd.go — gauges as |g, counters as |c,
    timers as |ms). Never raises into the instrumented path."""

    def __init__(self, addr: str, host_label: str = ""):
        host, port = addr.rsplit(":", 1)
        # Resolve once: an unresolved hostname target would pay a DNS
        # lookup on every sendto from instrumented hot paths.
        info = socket.getaddrinfo(host, int(port), socket.AF_INET,
                                  socket.SOCK_DGRAM)
        self._target = info[0][4]
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self._sock.setblocking(False)
        # Shared-aggregator sinks need per-node series (reference: go-metrics
        # hostname key prefix); the in-memory sink is per-agent and stays
        # unprefixed.
        self._prefix = f"{host_label}." if host_label else ""

    def _send(self, payload: str) -> None:
        try:
            self._sock.sendto(payload.encode(), self._target)
        except OSError:
            pass

    def set_gauge(self, key: Key, value: float) -> None:
        self._send(f"{self._prefix}{_name(key)}:{value:g}|g")

    def incr_counter(self, key: Key, value: float) -> None:
        self._send(f"{self._prefix}{_name(key)}:{value:g}|c")

    def add_sample(self, key: Key, value: float) -> None:
        self._send(f"{self._prefix}{_name(key)}:{value:g}|ms")

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


class MetricsRegistry:
    """Fan-out front for all sinks. Always carries one InMemSink so the
    agent metrics endpoint works without configuration."""

    _concurrency = guarded_by("_lock", "_sinks")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.inmem = InMemSink()
        self._sinks: List[Any] = [self.inmem]
        self.host_label: str = ""

    def configure(self, statsd_addr: str = "",
                  collection_interval: float = 10.0,
                  host_label: str = "") -> None:
        """(reference: command/agent/command.go:556-580 setupTelemetry)

        Reload-safe: a SIGHUP reconfigure swaps the sink list atomically
        (``_fan`` snapshots the reference under the lock and the list is
        never mutated in place) and CLOSES any replaced StatsdSink — the
        old UDP socket would otherwise leak once per reload. A statsd
        sink that cannot be constructed (unresolvable address) degrades
        to a logged warning instead of aborting agent boot/reload; the
        in-memory sink always survives."""
        sinks: List[Any] = [InMemSink(interval=collection_interval)]
        if statsd_addr:
            try:
                sinks.append(StatsdSink(statsd_addr, host_label=host_label))
            except (OSError, ValueError) as exc:
                logger.warning(
                    "telemetry: statsd sink %s unavailable (%s); "
                    "keeping in-memory sink only", statsd_addr, exc)
        with self._lock:
            old = self._sinks
            self.inmem = sinks[0]
            self._sinks = sinks
            self.host_label = host_label
        for sink in old:
            if sink in sinks:
                continue
            close = getattr(sink, "close", None)
            if close is not None:
                try:
                    close()
                # lint: allow(swallow, best-effort close of a replaced sink)
                except Exception:
                    pass

    def add_sink(self, sink: Any) -> None:
        with self._lock:
            # Replace, never mutate: _fan iterates its snapshot lock-free.
            self._sinks = self._sinks + [sink]

    def _fan(self, op: str, key: Key, value: float) -> None:
        # Snapshot the list REFERENCE under the lock: configure() swaps
        # whole lists, so a concurrent reload can never tear this walk.
        with self._lock:
            sinks = self._sinks
        for sink in sinks:
            try:
                getattr(sink, op)(key, value)
            # lint: allow(swallow, a broken sink must never break the measured path)
            except Exception:
                pass

    # ------------------------------------------------------------- surface
    def set_gauge(self, key: Key, value: float) -> None:
        self._fan("set_gauge", tuple(key), float(value))

    def incr_counter(self, key: Key, value: float = 1.0) -> None:
        self._fan("incr_counter", tuple(key), float(value))

    def add_sample(self, key: Key, value: float) -> None:
        self._fan("add_sample", tuple(key), float(value))

    def measure_since(self, key: Key, start: float) -> None:
        """`start` is a time.monotonic() stamp; records milliseconds."""
        self.add_sample(tuple(key), (time.monotonic() - start) * 1000.0)

    def measure(self, key: Key, cpu: bool = False, **attrs) -> Measure:
        """Context manager: sample + profiler span (see Measure). `cpu`
        is an argument of this call and no span attribute: with it the
        block's thread CPU is sampled as `<key>.cpu` beside the wall
        sample. It costs two system calls and a second sample (a lock and
        a fan-out; a datagram, for a statsd sink), so only the sites that
        are read through the sinks alone pass it; PipelinedWorker._stage
        keeps the CPU of its eight outer stages in `stats` itself."""
        return Measure(self, key, attrs, cpu)

    def snapshot(self) -> Dict[str, Any]:
        return self.inmem.snapshot()


# Process-global registry: instrumentation sites call these directly, the
# agent configures sinks at boot (reference: go-metrics global metrics
# singleton initialised by setupTelemetry).
registry = MetricsRegistry()

set_gauge = registry.set_gauge
incr_counter = registry.incr_counter
add_sample = registry.add_sample
measure_since = registry.measure_since
measure = registry.measure
snapshot = registry.snapshot
configure = registry.configure


# ------------------------------------------------------------ the runtime
# What the process itself does to every stage at once (reference: go-metrics
# emits runtime.* gauges and runtime.gc_pause_ns samples from inside the
# process, metrics.go:24-61): stalls of the whole interpreter, the CPU the
# process gets, and the collector's pauses.
TICK_S = 0.05    # the ticker's sleep: a stall longer than this shows
FLUSH_S = 1.0    # one tick_late and one cpu_share sample a second, not twenty
_RUNTIME = ("nomad", "runtime")

# The collector's cadence while an agent runs, paced by what it finds
# (reference: the Go runtime collects when the live heap has doubled,
# GOGC=100, and not every so many allocations). CPython 3.12 collects the
# young generation once threshold0 net container allocations have piled up
# since the last, the middle one every threshold1 young collections, and
# the whole heap once threshold2 middle ones have run and a quarter of the
# long-lived heap is new since the last full one.
#
# GC_YOUNG: a storm window holds its evals, prepared batches, plans and
# results for tens to hundreds of ms; at CPython's 700 they outlive two
# young collections and are promoted, and the promotions alone brought a
# full collection every 2-5 s that walked the whole heap (up to 0.9 s on a
# TPU v5e host, every thread stopped) and reclaimed nothing. At 100,000 a
# window's objects die young. The price is the size of what a young and a
# middle collection walk: on that host the largest took 37-48 ms and
# 170-242 ms in a storm of stops or of jobs of 1,000 (at 200,000 the middle
# one reached 405 ms; at 50,000 the middle collections came often enough
# to bring a full one back).
GC_YOUNG = 100_000
GC_MIDDLE = 10  # CPython's threshold1
# threshold2: CPython's 10 to start with and after a full collection that
# reclaimed anything; doubled after each one that reclaimed nothing, up to
# GC_FULL_MAX. At the cap a full collection still comes once 640 x 10 x
# 100,000 = 6.4e8 net container allocations have piled up since the last.
GC_FULL = 10
GC_FULL_MAX = 640


class RuntimeCollector:
    """One for the process, whatever the number of agents in it: a daemon
    ticker and one gc.callbacks entry, started by the first acquire() and
    stopped by the last release(). The first acquire also sets the
    collector's thresholds to (GC_YOUNG, GC_MIDDLE, GC_FULL) and the last
    release puts back those it found, so a program that embeds agents, or
    a test process, gets its own cadence back.

    The ticker sleeps TICK_S and notes how long after its due time it ran:
    a thread that needs the interpreter to wake runs late by as long as
    the interpreter was kept from it (a full collection, a compile or a C
    call that holds it, the host descheduled). Once every FLUSH_S it
    samples the largest lateness as nomad.runtime.tick_late (ms), the
    process's CPU over the wall as nomad.runtime.cpu_share (%, native
    threads included: it may pass 100), sets the gauges
    nomad.runtime.threads and nomad.runtime.gc_threshold.gen0 / .gen2 (the
    cadence in force), and adds the collections since to the counters
    nomad.runtime.gc_runs.gen0 / .gen1 / .gen2 and the objects the full
    ones reclaimed to nomad.runtime.gc_reclaimed.gen2.

    The callback adds one to a count for a collection of any generation,
    and makes no registry call: young ones run many times a second. A
    generation-2 collection is also a Measure opened at `start` and closed
    at `stop`: a span on the profiler's timeline, on the thread that
    triggered it, with the objects it reclaimed as its `collected`, and a
    nomad.runtime.gc sample in ms. The sample reaches the registry through
    the ticker (this object stands where the registry does for that
    Measure and keeps the sample until the next tick): a collection starts
    wherever an object is allocated, also inside a sink's add_sample with
    the sink's plain lock held, and a registry call from the callback
    would then wait for its own thread. At its `stop` the callback paces
    the next full collection by what this one reclaimed (GC_FULL)."""

    def __init__(self, registry: MetricsRegistry) -> None:
        self._registry = registry
        self._lock = threading.Lock()  # acquire/release only
        self._users = 0
        self._stop: Optional[threading.Event] = None
        self._thread: Optional[threading.Thread] = None
        # Collections run one at a time in a process, so the callback is
        # the only writer of these; the ticker reads the counts.
        self._runs = [0, 0, 0]
        self._reclaimed = 0
        self._full: Optional[Measure] = None
        self._closed: "collections.deque[Tuple[Key, float]]" = \
            collections.deque()
        # The thresholds the first acquire found; None while released.
        self._found: Optional[Tuple[int, int, int]] = None

    def acquire(self) -> None:
        with self._lock:
            self._users += 1
            if self._users > 1:
                return
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._tick, args=(self._stop,), daemon=True,
                name="runtime-metrics")
            self._found = gc.get_threshold()
            gc.set_threshold(GC_YOUNG, GC_MIDDLE, GC_FULL)
            gc.callbacks.append(self._on_gc)
            self._thread.start()

    def release(self) -> None:
        with self._lock:
            if self._users == 0:
                return
            self._users -= 1
            if self._users:
                return
            gc.callbacks.remove(self._on_gc)
            found, self._found = self._found, None
            gc.set_threshold(*found)
            self._stop.set()
            thread, self._thread = self._thread, None
        thread.join(timeout=5.0)

    def add_sample(self, key: Key, value: float) -> None:
        """Where the Measure of a full collection samples: kept, and handed
        to the registry by the ticker."""
        self._closed.append((key, value))

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        generation = info["generation"]
        if phase == "start":
            if generation == 2:
                self._full = Measure(self, _RUNTIME + ("gc",),
                                     {"generation": generation})
                self._full.__enter__()
            return
        self._runs[generation] += 1
        if generation < 2:
            return
        collected = info["collected"]
        self._reclaimed += collected
        if self._found is not None:  # not after the last release
            full_after = GC_FULL if collected else \
                min(2 * gc.get_threshold()[2], GC_FULL_MAX)
            gc.set_threshold(GC_YOUNG, GC_MIDDLE, full_after)
        full, self._full = self._full, None
        if full is not None:
            if full._span is not None:
                full._span.set_metadata(collected=collected)
            full.__exit__(None, None, None)

    def _tick(self, stop: threading.Event) -> None:
        registry = self._registry
        # An earlier ticker's counts are out already.
        flushed, reclaimed = list(self._runs), self._reclaimed
        late = 0.0
        wall0, cpu0 = time.monotonic(), time.process_time()
        while True:
            due = time.monotonic() + TICK_S
            stopping = stop.wait(TICK_S)
            now = time.monotonic()
            late = max(late, now - due)
            while self._closed:
                registry.add_sample(*self._closed.popleft())
            if stopping:
                return
            if now - wall0 < FLUSH_S:
                continue
            cpu = time.process_time()
            registry.add_sample(_RUNTIME + ("tick_late",), late * 1e3)
            registry.add_sample(_RUNTIME + ("cpu_share",),
                                100.0 * (cpu - cpu0) / (now - wall0))
            registry.set_gauge(_RUNTIME + ("threads",),
                               threading.active_count())
            young, _, full_after = gc.get_threshold()
            registry.set_gauge(_RUNTIME + ("gc_threshold", "gen0"), young)
            registry.set_gauge(_RUNTIME + ("gc_threshold", "gen2"),
                               full_after)
            for generation, total in enumerate(self._runs):
                if total > flushed[generation]:
                    registry.incr_counter(
                        _RUNTIME + ("gc_runs", f"gen{generation}"),
                        total - flushed[generation])
                    flushed[generation] = total
            if self._reclaimed > reclaimed:
                registry.incr_counter(_RUNTIME + ("gc_reclaimed", "gen2"),
                                      self._reclaimed - reclaimed)
                reclaimed = self._reclaimed
            late, wall0, cpu0 = 0.0, now, cpu


runtime = RuntimeCollector(registry)
