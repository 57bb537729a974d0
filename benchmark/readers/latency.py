"""End to end: a statistic of the time from when an operation was due to
the first read that showed its eval in a terminal status, in ms, over ALL
operations of the window. One that never got there counts with the time
until the generator gave up on it, so it cannot hide; it is also in
`failed`."""

from benchmark.readers.stats import stat as _stat


def read(run, stat):
    gave_up = run["window"]["gave_up"]
    values = []
    for op in run["ops"]:
        end = op.done if op.done is not None else gave_up
        if end is not None:
            values.append((end - op.due) * 1e3)
    return _stat(values, stat)
