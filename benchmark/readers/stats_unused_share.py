"""The share of a worker-stats total that was not used, in percent:
100 x (1 - delta of `used` / delta of `of`) over the window, summed over
the workers. Padding of the replay steps is this with `used` the real
placements and `of` the serial steps launched; a ratio reader cannot take
the complement. Where `of` did not move (nothing was launched: a CPU
rehearsal's small fleet is placed on the host) nothing was wasted and the
share reads 0.0; a stats key that the program lacks leaves the metric out
of the line, as worker_stats_opt does."""


def read(run, used, of):
    stats = run["stats"]
    if used not in stats or of not in stats:
        return None
    if stats[of] <= 0:
        return 0.0
    return 100.0 * (1.0 - stats[used] / stats[of])
