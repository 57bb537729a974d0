"""Time the interpreter spent in collections of `generation` inside the
window (gc.callbacks, installed by the harness in traced runs), in ms per
operation of the window. The collector is the program's as shipped: the
harness tunes nothing."""


def read(run, generation=2):
    if not run["ops"]:
        return None
    total = sum(secs for gen, secs in run["gc"] if gen == generation)
    return total * 1e3 / len(run["ops"])
