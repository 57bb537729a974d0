"""One statistic of a telemetry timer's samples (telemetry/metrics.py) that
arrived inside the window, read from the sink the harness adds in traced
runs: what sink_mean does for the mean, for the others.

  sample: the timer's dotted name
  stat:   "mean", "max" or "p<number>" (readers/stats.stat), or "sum"
  per:    "ops" divides by the window's operations
  beside: a sample the same emitter makes whatever happens (the runtime
          collector's once-a-second nomad.runtime.cpu_share beside its
          nomad.runtime.gc, which it makes only when a full collection
          ran). Where `beside` is there and `sample` is not, a sum reads
          0.0: the emitter ran and saw nothing. Every other statistic of
          no samples is nothing to read.

No sample and no `beside` sample either (a program without the emitter, an
untraced run): None, and the metric is left out of the line."""

from benchmark.readers import stats


def read(run, sample, stat, per=None, beside=None):
    values = run["samples"].get(sample) or []
    if not values and not (stat == "sum" and beside is not None
                           and run["samples"].get(beside)):
        return None
    value = sum(values) if stat == "sum" else stats.stat(values, stat)
    if per == "ops":
        if not run["ops"]:
            return None
        value /= len(run["ops"])
    return value
