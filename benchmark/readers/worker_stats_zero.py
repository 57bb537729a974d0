"""worker_stats_opt for a ratio whose denominator can rightly be 0: evals
or keys per device launch in a window span that launched nothing (a CPU
rehearsal's small fleet is placed on the host). The ratio then reads 0.0,
since nothing was launched, where worker_stats_opt would leave it out; a
stats key that the program lacks still leaves the metric out."""

from benchmark.readers import worker_stats_opt


def read(run, num, per, scale=1.0):
    value = worker_stats_opt.read(run, num, per=per, scale=scale)
    if value is None and worker_stats_opt.read(run, num) is not None \
            and worker_stats_opt.read(run, per) == 0:
        return 0.0
    return value
