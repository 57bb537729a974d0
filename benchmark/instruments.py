"""What the harness records round the measured window, from outside the
program: worker-stat deltas, compile events, and in a traced run also the
telemetry samples, the interpreter's full collections and a profiler trace
of the window's last seconds. Nothing here changes how the server runs: no
gc tuning, no thresholds, no switch interval."""

from __future__ import annotations

import gc
import glob
import os
import shutil
import threading
import time


def cache_every_program(on_chip):
    """Set-up only: let the persistent compile cache keep every program,
    also the ones that compile in under a second (JAX's default skips
    them), so that after a cell's first run in a checkout a run compiles
    nothing. The cache's directory is the program's (tensor/backend.py):
    JAX_COMPILATION_CACHE_DIR if set, else .jax_cache/ in the checkout. A
    CPU rehearsal keeps no cache: its programs are no use to a chip run."""
    import jax

    if on_chip:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    else:
        jax.config.update("jax_enable_compilation_cache", False)


def memory_peak_bytes():
    """Peak bytes in use on the fullest device, 0 where not reported."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class CompileLog:
    """The programs JAX builds (jit cache misses in this process), through
    jax.monitoring. Copied from chip_smoke.CompileLog (PR 21)."""

    def __init__(self):
        import jax

        self.events = []  # (time.perf_counter(), function name, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(),
                                kwargs.get("fun_name", "?"), secs))

    def between(self, t0, t1):
        return [(name, secs) for t, name, secs in self.events
                if t0 <= t <= t1]


class SampleSink:
    """A telemetry sink (MetricsRegistry.add_sink) that keeps every timer
    sample and every counter increment with the time it arrived. Added in
    traced runs only."""

    def __init__(self):
        self.rows = []      # (time.perf_counter(), dotted name, value)
        self.counters = []  # the same for incr_counter: value is the step

    def add_sample(self, key, value):
        self.rows.append((time.perf_counter(), ".".join(key), value))

    def set_gauge(self, key, value):
        pass

    def incr_counter(self, key, value):
        self.counters.append((time.perf_counter(), ".".join(key), value))


class Window:
    """Brackets the measured window: begin() just before the generator
    starts, end() when it returns, read_device() round the post-window read
    of the device's usage table (the one device operation every cell does,
    so that a traced run of a cell the device sits out still shows it)."""

    def __init__(self, dep, compiles, traced, trace_dir, trace_seconds,
                 on_chip, trace_guard_share=None, timer=threading.Timer):
        self.dep = dep
        self.on_chip = on_chip
        self.compile_log = compiles
        self.traced = traced
        self.trace_dir = trace_dir
        self.trace_seconds = trace_seconds
        self.trace_guard_share = trace_guard_share
        self._new_timer = timer  # a test hands in one it fires itself
        self.gc_events = []       # (generation, seconds) inside the window
        self.compiles = []
        self.stats_delta = {}
        self.trace_stats_delta = {}
        self.device = None
        self.trace = None  # the parsed trace (xplane.load), for the readers
        self._sink = None
        self._gc_started = None
        self._timer = None
        self._anchored = False
        self._tracing = threading.Lock()
        self._trace_t0 = None
        self._trace_by = None
        self._trace_stats0 = None

    # ----------------------------------------------------------- window
    def begin(self, seconds):
        self._stats0 = self.dep.worker_stats()
        if self.traced:
            from nomad_tpu.telemetry import metrics

            self._sink = SampleSink()
            metrics.registry.add_sink(self._sink)
            gc.callbacks.append(self._on_gc)
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self._timer = self._new_timer(
                max(0.0, seconds - self.trace_seconds), self._start_trace,
                ("clock",))
            self._timer.daemon = True
            self._timer.start()
        self.t0 = time.perf_counter()

    def progress(self, asked, limit):
        """The generator's report, once a registration, of the allocations
        asked for inside the window against its fill guard's limit. Where
        the traffic file states trace_guard_share, the trace starts when
        `asked` reaches (1 - trace_guard_share) of the limit, if neither
        the clock (begin's timer) nor the window's end came first: a window
        that its guard ends early is traced over its last stretch of work,
        however fast the program gets through it. Without the key, or
        without a guard, nothing happens here and the clock decides as it
        always did."""
        if not self.traced or self.trace_guard_share is None \
                or limit is None or self._anchored:
            return
        if asked >= (1.0 - self.trace_guard_share) * limit:
            self._anchored = True  # once: _start_trace itself runs once
            self._timer.cancel()
            # On a thread of its own, as the clock's timer starts it: the
            # generator goes on registering while the profiler starts.
            self._timer = self._new_timer(0.0, self._start_trace,
                                          ("guard",))
            self._timer.daemon = True
            self._timer.start()

    def end(self):
        self.t1 = time.perf_counter()
        if self.traced:
            gc.callbacks.remove(self._on_gc)
            self._timer.cancel()
            self._start_trace("window_end")  # ended early: trace the rest
            self._mark("bench.window_end")
            self.trace_stats_delta = _delta(self._trace_stats0,
                                            self.dep.worker_stats())
        self.compiles = self.compile_log.between(self.t0, self.t1)

    def read_device(self, read):
        """Worker stats are final only after the drain, so the delta is
        taken here; the trace stops after the device read."""
        self.stats_delta = _delta(self._stats0, self.dep.worker_stats())
        if not self.traced:
            return read()
        import jax

        with jax.profiler.TraceAnnotation("bench.device_read"):
            out = read()
        jax.profiler.stop_trace()
        t_stop = time.perf_counter()
        if self.on_chip:  # a rehearsal's trace has no device plane
            self._reduce(t_stop)
        return out  # the trace stays in .bench_work/ until the next one

    def samples(self):
        out = {}
        if self._sink is not None:
            for t, name, value in self._sink.rows:
                if self.t0 <= t <= self.t1:
                    out.setdefault(name, []).append(value)
        return out

    def trace_facts(self):
        """Where the trace lay, for the run's note: seconds from the
        window's opening to the trace's start and, on the chip, the traced
        span's length and its part inside the window."""
        if self._trace_t0 is None:
            return None
        facts = {"started_after_s": self._trace_t0 - self.t0,
                 "started_by": self._trace_by}
        if self.device is not None:
            facts.update(window_s=self.device["window_s"],
                         in_window_s=self.device["in_window_s"])
        return facts

    def counters(self):
        """In-window sums of the registry's counters by dotted name: {} in
        a traced run in which none was incremented (a counter that never
        moved reads 0), None in an untraced run (no sink: nothing to
        read)."""
        if self._sink is None:
            return None
        out = {}
        for t, name, value in self._sink.counters:
            if self.t0 <= t <= self.t1:
                out[name] = out.get(name, 0.0) + value
        return out

    # ---------------------------------------------------------- private
    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_started = time.perf_counter()
        elif self._gc_started is not None:
            self.gc_events.append((info["generation"],
                                   time.perf_counter() - self._gc_started))
            self._gc_started = None

    def _start_trace(self, by):
        import jax

        with self._tracing:
            if self._trace_t0 is not None:
                return
            self._trace_by = by  # "clock", "guard" or "window_end"
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1  # enough for the bench.* markers
            options.enable_hlo_proto = False
            self._trace_stats0 = self.dep.worker_stats()
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            self._trace_t0 = time.perf_counter()
            self._mark("bench.trace_begin")

    @staticmethod
    def _mark(name):
        import jax

        with jax.profiler.TraceAnnotation(name):
            time.sleep(1e-4)  # a span the trace viewer can show

    def _reduce(self, t_stop):
        from benchmark.trace import xplane

        paths = glob.glob(os.path.join(self.trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no trace under "
                               + self.trace_dir)
        self.trace = xplane.load(paths[0])  # parsed once a run
        self.device = xplane.reduce(self.trace,
                                    window_s=t_stop - self._trace_t0,
                                    in_window_s=self.t1 - self._trace_t0)


def _delta(before, after):
    return {k: after[k] - before.get(k, 0) for k in after}
