"""Causal tracing for the evaluation lifecycle (reference technique:
Dapper-style trace/span propagation — Sigelman et al. 2010 — as deployed
in systems like the reference's opentelemetry hooks; here a dependency-free
core sized for the scheduler's needs).

A *trace* is one logical operation (a job register riding through broker,
worker, plan apply, raft, and the client agent); a *span* is one timed
stage of it. Spans carry monotonic durations anchored to a wall-clock
start, free-form attributes, and timestamped events (failpoint triggers,
retry attempts, fallbacks).

Propagation has three legs:

* **Ambient context** — a ``threading.local`` span stack. ``span()``
  opens a child of the current span; synchronous call chains (RPC handler
  -> raft apply -> FSM) need no plumbing.
* **Wire carrier** — ``inject()`` produces a small dict that rides the
  msgpack RPC envelope (rpc/wire.py ``Trace`` field); the receiving
  dispatcher ``attach()``-es it so one trace spans processes.
* **Async links** — queue hops (eval broker, plan queue, client alloc
  pickup) break the thread chain. The enqueueing side calls
  ``link("eval", ev.ID)``; the dequeueing side ``resume()``-s from
  ``linked("eval", ev.ID)``.

Sampling: a head decision at trace creation (``sample_ratio``) plus a
tail rule — a trace that records an error/failpoint/fallback is retained
even when the head coin said no. The tail rule is why sampling bounds
RETENTION and visibility, not recording cost: while tracing is enabled
every trace records its spans (you cannot retroactively keep an
error trace you never recorded), so ``sample_ratio`` is a memory/noise
knob, not a CPU one — enabling tracing is itself the opt-in to the
recording overhead. Disarmed (``enabled=False``, the default) every
entry point is one module-attribute truthiness check and a shared no-op
context manager (tests/test_trace.py holds the disarmed path inert).
"""

from __future__ import annotations

import threading
import time
import uuid
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from . import metrics

__all__ = [
    "Span", "configure", "is_enabled", "root_span", "span", "resume",
    "start_from", "attach", "current", "add_event", "inject", "link",
    "linked", "record_span", "traces", "get_trace",
    "export_chrome", "clear", "status",
]

# Events whose presence retains an otherwise-unsampled trace (tail rule).
_PROMOTE_EVENTS = frozenset({"failpoint", "error", "fallback"})

_LINK_CAP = 4096          # async-hop carrier registry bound
_DEFAULT_RING = 128       # completed/live traces retained


class _NoopSpan:
    """Shared disarmed span: every operation is a no-op."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def event(self, name: str, **attrs) -> None:
        pass

    def set_attr(self, key: str, value) -> None:
        pass

    def finish(self, error: Optional[str] = None) -> None:
        pass


_NOOP = _NoopSpan()


class _Trace:
    __slots__ = ("trace_id", "sampled", "spans", "events", "root_name",
                 "start_wall", "error", "complete")

    def __init__(self, trace_id: str, sampled: bool):
        self.trace_id = trace_id
        self.sampled = sampled
        self.spans: List[Span] = []
        # Trace-level annotations (e.g. a PARTIAL re-verify noticed after
        # the owning span closed): (wall_ts, name, attrs).
        self.events: List[tuple] = []
        self.root_name = ""
        self.start_wall = time.time()
        self.error = False
        self.complete = False

    @property
    def retained(self) -> bool:
        return self.sampled or self.error


class Span:
    """One timed stage. Use as a context manager (ambient) or hold the
    object and call ``finish()`` explicitly (cross-thread stages)."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "start_wall",
                 "start_mono", "duration_ms", "attrs", "events", "thread",
                 "error", "_trace", "_is_root", "_ambient", "_finished")

    def __init__(self, trace: _Trace, name: str, parent_id: Optional[str],
                 attrs: Dict[str, Any], is_root: bool):
        self.trace_id = trace.trace_id
        self.span_id = uuid.uuid4().hex[:16]
        self.parent_id = parent_id
        self.name = name
        self.start_wall = time.time()
        self.start_mono = time.monotonic()
        self.duration_ms: Optional[float] = None
        self.attrs = dict(attrs)
        self.events: List[tuple] = []  # (offset_ms, name, attrs)
        self.thread = threading.current_thread().name
        self.error = False
        self._trace = trace
        self._is_root = is_root
        self._ambient = False
        self._finished = False

    # ------------------------------------------------------------- recording
    def event(self, name: str, **attrs) -> None:
        off = (time.monotonic() - self.start_mono) * 1000.0
        self.events.append((off, name, attrs))
        if name in _PROMOTE_EVENTS:
            self.error = True
            self._trace.error = True

    def set_attr(self, key: str, value) -> None:
        self.attrs[key] = value

    def finish(self, error: Optional[str] = None) -> None:
        if self._finished:
            return
        self._finished = True
        if error:
            self.error = True
            self.attrs.setdefault("error", error)
        self.duration_ms = (time.monotonic() - self.start_mono) * 1000.0
        with _lock:
            self._trace.spans.append(self)
            if self.error:
                self._trace.error = True
            if self._is_root:
                self._trace.complete = True
        # Span durations bridge into the metrics registry under
        # nomad.trace.<span name> so sinks/statsd see trace latencies too.
        metrics.add_sample(("nomad", "trace") + tuple(self.name.split(".")),
                           self.duration_ms)

    # ------------------------------------------------------- context manager
    def __enter__(self) -> "Span":
        stack = _stack()
        stack.append(self)
        self._ambient = True
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._ambient:
            stack = _stack()
            if stack and stack[-1] is self:
                stack.pop()
            self._ambient = False
        if exc_type is not None:
            self.event("error", type=exc_type.__name__)
        self.finish(error=exc_type.__name__ if exc_type else None)
        return False

    def carrier(self) -> Dict[str, Any]:
        return {"TraceID": self.trace_id, "SpanID": self.span_id,
                "Sampled": self._trace.sampled}

    def to_dict(self) -> Dict[str, Any]:
        return {
            "TraceID": self.trace_id,
            "SpanID": self.span_id,
            "ParentID": self.parent_id,
            "Name": self.name,
            "Start": self.start_wall,
            "DurationMs": self.duration_ms,
            "Thread": self.thread,
            "Error": self.error,
            "Attrs": self.attrs,
            "Events": [{"OffsetMs": round(off, 3), "Name": name,
                        "Attrs": attrs}
                       for off, name, attrs in self.events],
        }


class _RemoteCtx:
    """Ambient stack entry for an extracted wire carrier: parents the next
    span under the remote caller's span without opening a local one. Holds
    only the carrier fields — the local _Trace is created LAZILY when a
    span is actually opened, so carrier-bearing frames whose handlers
    never span (raft replication on followers) cannot fill the ring with
    empty traces."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str, sampled: bool):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled


# ------------------------------------------------------------------ state
_lock = threading.Lock()
_enabled = False
_sample_ratio = 1.0
_ring_max = _DEFAULT_RING
_traces: "OrderedDict[str, _Trace]" = OrderedDict()
_links: "OrderedDict[tuple, dict]" = OrderedDict()  # (kind, key) -> carrier
_tls = threading.local()


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def configure(enabled: Optional[bool] = None,
              sample_ratio: Optional[float] = None,
              ring: Optional[int] = None) -> None:
    global _enabled, _sample_ratio, _ring_max
    with _lock:
        if enabled is not None:
            _enabled = bool(enabled)
        if sample_ratio is not None:
            _sample_ratio = min(1.0, max(0.0, float(sample_ratio)))
        if ring is not None:
            _ring_max = max(1, int(ring))


def is_enabled() -> bool:
    return _enabled


def status() -> Dict[str, Any]:
    with _lock:
        return {"Enabled": _enabled, "SampleRatio": _sample_ratio,
                "Ring": _ring_max,
                "Traces": sum(1 for t in _traces.values() if t.retained)}


def clear() -> None:
    with _lock:
        _traces.clear()
        _links.clear()


# ------------------------------------------------------------ trace store
def _new_trace_locked(trace_id: Optional[str] = None,
                      sampled: Optional[bool] = None) -> _Trace:
    if sampled is None:
        import random

        sampled = random.random() < _sample_ratio
    t = _Trace(trace_id or uuid.uuid4().hex, sampled)
    _traces[t.trace_id] = t
    # Bounded at exactly the configured ring: evict unsampled-and-clean
    # traces first (they only exist in case a late error promotes them),
    # then the oldest outright.
    while len(_traces) > _ring_max:
        victim = next((tid for tid, tr in _traces.items()
                       if not tr.retained), None)
        _traces.pop(victim if victim is not None
                    else next(iter(_traces)), None)
    return t


def _trace_for_carrier_locked(carrier: Dict[str, Any]) -> Optional[_Trace]:
    tid = carrier.get("TraceID")
    if not tid:
        return None
    t = _traces.get(tid)
    if t is None:
        t = _new_trace_locked(tid, bool(carrier.get("Sampled", True)))
    return t


# ----------------------------------------------------------- span entries
def root_span(name: str, **attrs):
    """Open a span, creating a NEW trace when no ambient context exists
    (the trace-ingress points: RPC dispatch, service sync). Joins the
    current trace as a child when one is active."""
    if not _enabled:
        return _NOOP
    top = _stack()[-1] if _stack() else None
    if top is not None:
        return _child_of(top, name, attrs)
    with _lock:
        trace = _new_trace_locked()
        trace.root_name = name
    return Span(trace, name, None, attrs, is_root=True)


def span(name: str, **attrs):
    """Open a child span of the ambient context; no-op when there is no
    active trace (background work must not spawn trace spam)."""
    if not _enabled:
        return _NOOP
    top = _stack()[-1] if _stack() else None
    if top is None:
        return _NOOP
    return _child_of(top, name, attrs)


def resume(carrier: Optional[Dict[str, Any]], name: str, **attrs):
    """Open a span continuing from an async-hop/wire carrier. Prefers the
    ambient context when one is active; no-op without either."""
    if not _enabled:
        return _NOOP
    top = _stack()[-1] if _stack() else None
    if top is not None:
        return _child_of(top, name, attrs)
    if not carrier or not isinstance(carrier, dict):
        return _NOOP
    with _lock:
        trace = _trace_for_carrier_locked(carrier)
    if trace is None:
        return _NOOP
    return Span(trace, name, carrier.get("SpanID"), attrs, is_root=False)


def start_from(carrier: Optional[Dict[str, Any]], name: str,
               **attrs) -> Optional[Span]:
    """Explicit (non-ambient) span from a carrier, for stages that cross
    threads: hold the Span and call ``finish()`` when the stage ends.
    Returns None when tracing is off or the carrier is empty."""
    if not _enabled or not carrier or not isinstance(carrier, dict):
        return None
    with _lock:
        trace = _trace_for_carrier_locked(carrier)
    if trace is None:
        return None
    return Span(trace, name, carrier.get("SpanID"), attrs, is_root=False)


def _child_of(top, name: str, attrs: Dict[str, Any]) -> Span:
    if isinstance(top, _RemoteCtx):
        with _lock:
            trace = _trace_for_carrier_locked(
                {"TraceID": top.trace_id, "Sampled": top.sampled})
        return Span(trace, name, top.span_id, attrs, is_root=False)
    return Span(top._trace, name, top.span_id, attrs, is_root=False)


class _Attach:
    """Context manager establishing a remote parent from a wire carrier
    (no local span): the dispatcher's handler spans become its children."""

    __slots__ = ("_ctx",)

    def __init__(self, ctx: Optional[_RemoteCtx]):
        self._ctx = ctx

    def __enter__(self):
        if self._ctx is not None:
            _stack().append(self._ctx)
        return self

    def __exit__(self, *exc) -> bool:
        if self._ctx is not None:
            stack = _stack()
            if stack and stack[-1] is self._ctx:
                stack.pop()
        return False


def attach(carrier: Optional[Dict[str, Any]]) -> _Attach:
    if not _enabled or not carrier or not isinstance(carrier, dict) \
            or not carrier.get("TraceID"):
        return _Attach(None)
    return _Attach(_RemoteCtx(carrier["TraceID"],
                              carrier.get("SpanID", ""),
                              bool(carrier.get("Sampled", True))))


def current() -> Optional[Span]:
    stack = _stack()
    for entry in reversed(stack):
        if isinstance(entry, Span):
            return entry
    return None


def add_event(name: str, **attrs) -> None:
    """Record an event on the active ambient span (failpoint triggers,
    retry attempts). One truthiness check when tracing is disarmed."""
    if not _enabled:
        return
    s = current()
    if s is not None:
        s.event(name, **attrs)


def add_trace_event(carrier: Optional[Dict[str, Any]], name: str,
                    **attrs) -> None:
    """Trace-level annotation via a carrier, for after the owning span
    closed (e.g. the plan applier's PARTIAL re-verify)."""
    if not _enabled or not carrier or not isinstance(carrier, dict):
        return
    with _lock:
        trace = _traces.get(carrier.get("TraceID", ""))
        if trace is None:
            return
        trace.events.append((time.time(), name, attrs))
        if name in _PROMOTE_EVENTS:
            trace.error = True


def inject() -> Optional[Dict[str, Any]]:
    """Carrier for the active context, for the RPC envelope."""
    if not _enabled:
        return None
    stack = _stack()
    if not stack:
        return None
    top = stack[-1]
    if isinstance(top, _RemoteCtx):
        return {"TraceID": top.trace_id, "SpanID": top.span_id,
                "Sampled": top.sampled}
    return top.carrier()


# ------------------------------------------------------------ async links
def link(kind: str, key: str) -> None:
    """Register the active context's carrier under (kind, key) so an
    async consumer (worker, applier, client) can ``resume`` the trace."""
    if not _enabled:
        return
    carrier = inject()
    if carrier is None:
        return
    with _lock:
        _links[(kind, key)] = carrier
        while len(_links) > _LINK_CAP:
            _links.popitem(last=False)


def linked(kind: str, key: str) -> Optional[Dict[str, Any]]:
    if not _enabled:
        return None
    with _lock:
        return _links.get((kind, key))


def record_span(carrier: Optional[Dict[str, Any]], name: str,
                start_mono: float, **attrs) -> None:
    """Synthesize an already-finished span from a measured interval (e.g.
    broker queue wait: enqueue-link time -> dequeue time)."""
    if not _enabled or not carrier or not isinstance(carrier, dict):
        return
    with _lock:
        trace = _trace_for_carrier_locked(carrier)
    if trace is None:
        return
    s = Span(trace, name, carrier.get("SpanID"), attrs, is_root=False)
    now_mono = time.monotonic()
    s.start_mono = start_mono
    s.start_wall = s.start_wall - (now_mono - start_mono)
    s.finish()


# ------------------------------------------------------------- inspection
def traces() -> List[Dict[str, Any]]:
    """Summaries of retained traces, newest last."""
    with _lock:
        kept = [t for t in _traces.values() if t.retained]
        out = []
        for t in kept:
            root = next((s for s in t.spans if s._is_root), None)
            out.append({
                "TraceID": t.trace_id,
                "Root": t.root_name or (root.name if root else ""),
                "Start": t.start_wall,
                "DurationMs": (root.duration_ms if root is not None
                               else None),
                "Spans": len(t.spans),
                "Complete": t.complete,
                "Error": t.error,
            })
        return out


def get_trace(trace_id: str) -> Optional[Dict[str, Any]]:
    with _lock:
        t = _traces.get(trace_id)
        if t is None:
            return None
        return {
            "TraceID": t.trace_id,
            "Root": t.root_name,
            "Start": t.start_wall,
            "Sampled": t.sampled,
            "Error": t.error,
            "Complete": t.complete,
            "Spans": [s.to_dict() for s in t.spans],
            "Events": [{"Time": ts, "Name": name, "Attrs": attrs}
                       for ts, name, attrs in t.events],
        }


def export_chrome(trace_id: Optional[str] = None) -> Dict[str, Any]:
    """Chrome trace-event JSON (the ``chrome://tracing`` / Perfetto
    format): complete ``X`` events per span, instant ``i`` events per span
    event, with process/thread-name metadata. Loadable in Perfetto."""
    with _lock:
        if trace_id is not None:
            picked = [t for t in (_traces.get(trace_id),) if t is not None]
        else:
            picked = [t for t in _traces.values() if t.retained]
        events: List[Dict[str, Any]] = []
        for pid, t in enumerate(picked, start=1):
            tids: Dict[str, int] = {}
            events.append({"name": "process_name", "ph": "M", "ts": 0,
                           "pid": pid, "tid": 0,
                           "args": {"name": f"{t.root_name or 'trace'} "
                                            f"{t.trace_id[:8]}"}})
            for s in t.spans:
                tid = tids.setdefault(s.thread, len(tids) + 1)
                ts_us = s.start_wall * 1e6
                events.append({
                    "name": s.name, "cat": "nomad", "ph": "X",
                    "ts": ts_us,
                    "dur": (s.duration_ms or 0.0) * 1000.0,
                    "pid": pid, "tid": tid,
                    "args": {"span_id": s.span_id,
                             "parent_id": s.parent_id,
                             "error": s.error, **s.attrs},
                })
                for off, name, attrs in s.events:
                    events.append({
                        "name": f"{s.name}:{name}", "cat": "nomad",
                        "ph": "i", "s": "t",
                        "ts": ts_us + off * 1000.0,
                        "pid": pid, "tid": tid, "args": dict(attrs),
                    })
            for ts, name, attrs in t.events:
                events.append({"name": name, "cat": "nomad", "ph": "i",
                               "s": "p", "ts": ts * 1e6, "pid": pid,
                               "tid": 0, "args": dict(attrs)})
            for tname, tid in tids.items():
                events.append({"name": "thread_name", "ph": "M", "ts": 0,
                               "pid": pid, "tid": tid,
                               "args": {"name": tname}})
    return {"displayTimeUnit": "ms", "traceEvents": events}
