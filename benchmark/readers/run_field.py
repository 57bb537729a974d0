"""A number the harness took itself, by its key in the run record."""


def read(run, field):
    return run.get(field)
