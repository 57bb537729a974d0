"""Numbers from the profiler trace of the window's last seconds, reduced by
benchmark/trace/xplane.py.

  idle_share: 100 x (1 - union of device-op intervals / traced span), over
      the part of the trace inside the window; 100 where no op ran there.
  program_ms_per_window: device time of the programs whose name contains
      one of `match`, inside the window, per window the workers dispatched
      while the trace ran. No window, no value."""


def read(run, reading, match=()):
    device = run["device"]
    if device is None:
        return None
    if reading == "idle_share":
        return device["in_window_idle_share"]
    if reading == "program_ms_per_window":
        windows = run["trace_stats"].get("windows", 0)
        if windows <= 0:
            return None
        secs = sum(s for name, _, s in device["in_window_programs"]
                   if any(m in name for m in match))
        return secs * 1e3 / windows
    raise ValueError(f"unknown device-trace reading {reading!r}")
