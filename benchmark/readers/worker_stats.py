"""A ratio of deltas of PipelinedWorker.stats over the window, summed over
the workers: sum of `num` keys over sum of `per` keys (or over the window's
operations when per is "ops"), times `scale`. The stage timers add over 2
workers x 3 stage threads and overlap, so they are read per window or per
eval and never as a share of wall time. No windows, no value."""


def read(run, num, per=None, scale=1.0):
    stats = run["stats"]
    if not stats:
        return None
    top = sum(stats[k] for k in ([num] if isinstance(num, str) else num))
    if per is None:
        return top * scale
    if per == "ops":
        bottom = len(run["ops"])
    else:
        bottom = sum(stats[k]
                     for k in ([per] if isinstance(per, str) else per))
    if bottom <= 0:
        return None
    return top / bottom * scale
