"""worker_stats for a stats key that the program may not have yet: the
driver also runs a PR's new metric files on the parent commit, whose
PipelinedWorker.stats lacks the keys the PR adds (`t_fill_ms`,
`t_stagewait_ms` before ISSUE 26). Where a key is missing there is nothing
to read, and the metric is left out of the line; otherwise this is
worker_stats, argument for argument."""

from benchmark.readers import worker_stats


def read(run, num, per=None, scale=1.0):
    keys = [num] if isinstance(num, str) else list(num)
    if per not in (None, "ops"):
        keys += [per] if isinstance(per, str) else list(per)
    if any(k not in run["stats"] for k in keys):
        return None
    return worker_stats.read(run, num, per=per, scale=scale)
