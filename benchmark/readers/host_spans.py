"""Shares of the in-window traced span in which the device ran no program
while the host had a span of a given name open (benchmark/trace/
host_gaps.py): which stage of the program the idle device was waiting for.

  spans:   names of the program's spans ("nomad.worker.dispatch"); time
           counts while any of them is open on any thread
  without: names whose open time is taken out again, so that two metrics
           split the idle time without counting a moment twice

Read from the run's parsed trace, which the harness parsed once to reduce
it (instruments.Window, run["trace"]). None in a rehearsal and in an
untraced run (no trace, or one without a device plane); 0 where the program
has no such spans, as before ISSUE 26."""

from benchmark.trace import host_gaps


def read(run, spans, without=()):
    trace = run.get("trace")
    if trace is None:
        return None
    return host_gaps.idle_share(trace, spans, without)
