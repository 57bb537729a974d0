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
    `ms` holds the sample."""

    __slots__ = ("_registry", "_key", "_attrs", "_span", "_start", "ms")

    def __init__(self, registry: "MetricsRegistry", key: Key,
                 attrs: Dict[str, Any]) -> None:
        self._registry = registry
        self._key = tuple(key)
        self._attrs = attrs
        self._span = None
        self.ms = 0.0

    def __enter__(self) -> "Measure":
        annotation = _trace_annotation()
        if annotation is not None:
            self._span = annotation(_name(self._key), **self._attrs)
            self._span.__enter__()
        self._start = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.ms = (time.monotonic() - self._start) * 1000.0
        if self._span is not None:
            self._span.__exit__(*exc)
        self._registry.add_sample(self._key, self.ms)
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

    def measure(self, key: Key, **attrs) -> Measure:
        """Context manager: sample + profiler span (see Measure)."""
        return Measure(self, key, attrs)

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
