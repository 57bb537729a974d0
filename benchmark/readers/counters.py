"""Sums of the telemetry registry's counters (metrics.incr_counter) that
were incremented inside the window, by dotted name, read from the sink the
harness adds in traced runs (instruments.SampleSink.incr_counter keeps
every increment with the time it arrived; Window.counters sums those of
the window): the sum of the `num` names, over the sum of the `per` names
where given, times `scale`.

A counter that was never incremented reads 0: the program adds to
"nomad.plan.partial.ports" only when a port is refused, and a run in which
none was is a run that counted none. A denominator of 0 reads 0.0: a cell
whose jobs ask for no network sends no node through the exact fit, so no
share of its nodes is refused there. None only where there was no sink
(an untraced run): nothing to read."""


def _sum(counters, names):
    return sum(counters.get(n, 0.0)
               for n in ([names] if isinstance(names, str) else names))


def read(run, num, per=None, scale=1.0):
    counters = run.get("counters")
    if counters is None:
        return None
    top = _sum(counters, num)
    if per is None:
        return top * scale
    bottom = _sum(counters, per)
    if bottom <= 0:
        return 0.0
    return top / bottom * scale
