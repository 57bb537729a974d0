"""Mean of one telemetry timer's samples (telemetry/metrics.py, ms) that
arrived inside the window, read from a sink the harness adds in traced
runs. No sample, no value."""


def read(run, sample):
    values = run["samples"].get(sample)
    if not values:
        return None
    return sum(values) / len(values)
