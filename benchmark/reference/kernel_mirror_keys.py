"""Check 7 for a mix of job shapes: the device's keyed placement program
against the numpy mirror and a float64 replay, on one seeded window of the
configuration's templates, outside the measured window.

kernel_mirror builds a window of one template: one launch, one key, one
mask. Here the window holds every template of the configuration, and is
launched as the served path launches it (pipelined_worker._launch_window):
grouped into one run per template, each run its own place_batch_keyed call
in its own (eval-pad, candidate-count) bucket with its own keys (one per
task group: the job's datacenter-set rows under that group's eligibility,
and the group's ask), the usage chained from run to run. A job of two task
groups gives a launch of two keys with mixed tg_ids and a reset at every
eval's first placement.

Judged as kernel_mirror judges (SCORE_TOL is its limit, not a new one):
every device choice feasible *for its own key's mask*, its score within
SCORE_TOL of the best feasible score of that key, the usage after the
whole chain equal to the replay's within USAGE_TOL. Row equality with the
mirror is a fact, never demanded."""

from __future__ import annotations

from benchmark.deploy.dev_agent_dcs import datacenter_sizes
from benchmark.reference import kernel_mirror
from benchmark.reference.kernel_mirror import P_PAD, SCORE_TOL

USAGE_TOL = 1e-2


def _ask(group):
    parts = [t["Resources"] for t in group["Tasks"]]
    return [sum(r["CPU"] for r in parts), sum(r["MemoryMB"] for r in parts),
            sum(r["DiskMB"] for r in parts), 0, 0]


def window_inputs(config, seed, n_rows, n_live, n_evals):
    """A fleet of the file's node shape in the file's datacenters, part
    filled, and one window of n_evals jobs: every template at least once,
    the rest drawn by the seed, grouped into one launch per template."""
    import numpy as np

    # The fleet, part filled in units of the smallest ask so that the
    # larger asks fit on some rows and not on others: kernel_mirror's.
    names = sorted(config["jobs"])
    smallest = min(names, key=lambda t: _ask(
        config["jobs"][t]["TaskGroups"][0])[0])
    base = kernel_mirror.window_inputs(config, smallest, seed, n_rows,
                                       n_live, n_evals)
    rng = np.random.default_rng([seed, 1])  # the window's own stream
    # Rows in registration order: one datacenter after the other.
    rows_of, start = {}, 0
    for name, size in datacenter_sizes(config["fleet"], n_live):
        rows_of[name] = slice(start, start + size)
        start += size
    asks = {(t, g["Name"]): np.array(_ask(g), np.float32)
            for t in names for g in config["jobs"][t]["TaskGroups"]}

    drawn = names + [names[i] for i in
                     rng.integers(0, len(names), max(0, n_evals - len(names)))]
    launches = []
    for template in names:
        n = drawn.count(template)
        job = config["jobs"][template]
        in_dcs = np.zeros(n_rows, bool)
        for dc in job["Datacenters"]:
            in_dcs[rows_of[dc]] = True
        groups = job["TaskGroups"]
        masks = np.stack([in_dcs & (rng.random(n_rows) < 0.9)
                          for _ in groups])
        per_eval = np.concatenate([np.full(g["Count"], k, np.int32)
                                   for k, g in enumerate(groups)])
        tg_ids = np.zeros(P_PAD, np.int32)
        tg_ids[:len(per_eval)] = per_eval
        valid = np.arange(P_PAD) < len(per_eval)
        # As stack.dispatch_multi pads a run of two or more (a power of
        # two, at least four evals, a reset at every eval's start) and
        # stack.dispatch launches a run of one (no reset).
        e_pad = 1
        if n > 1:
            e_pad = 4
            while e_pad < n:
                e_pad *= 2
        valid = np.tile(valid, e_pad)
        valid[n * P_PAD:] = False
        reset = np.zeros(e_pad * P_PAD, bool)
        if n > 1:
            reset[::P_PAD] = True
        launches.append({
            "template": template, "evals": n, "masks": masks,
            "asks": np.stack([asks[(template, g["Name"])] for g in groups]),
            "tg_ids": np.tile(tg_ids, e_pad), "valid": valid,
            "reset": reset, "n_valid": n * len(per_eval)})
    return {**{k: base[k] for k in ("capacity", "score_cap", "usage",
                                    "noise", "penalty")},
            "launches": launches}


def run_keyed(inp):
    """The window through kernels.place_batch_keyed on one device, one call
    a launch, the usage chained on the device. Returns (packed per launch,
    usage after the last)."""
    import numpy as np

    from nomad_tpu.scheduler import kernels

    n = inp["capacity"].shape[0]
    usage, packed = inp["usage"], []
    for launch in inp["launches"]:
        res = kernels.place_batch_keyed(
            None, inp["capacity"], inp["score_cap"], usage, launch["masks"],
            np.zeros(n, np.int32), launch["asks"], launch["tg_ids"],
            launch["valid"], inp["noise"], inp["penalty"], np.asarray(False),
            np.zeros(n, bool), launch["reset"], launch["n_valid"])
        usage = res.usage_after
        packed.append(np.asarray(res.packed))
    return packed, np.asarray(usage)


def run_mirror(inp):
    """The same window through the numpy mirror, one eval at a time with
    the usage chained, as stack.dispatch_host drives it."""
    import numpy as np

    from nomad_tpu.scheduler import kernels

    n = inp["capacity"].shape[0]
    usage, packed = inp["usage"], []
    for launch in inp["launches"]:
        out = []
        for e in range(len(launch["valid"]) // P_PAD):
            sl = slice(P_PAD * e, P_PAD * e + P_PAD)
            res = kernels.place_batch_host(
                inp["capacity"], inp["score_cap"], usage, launch["masks"],
                np.zeros(n, np.int32), launch["asks"][launch["tg_ids"][sl]],
                launch["tg_ids"][sl], launch["valid"][sl], inp["noise"],
                inp["penalty"], False, np.zeros(n, bool))
            usage = res.usage_after
            out.append(res.packed)
        packed.append(np.concatenate(out))
    return packed


def replay_launch(inp, launch, packed, usage):
    """Follow one launch's device choices in float64 with the reference's
    formula (20 - 10^freeCpu - 10^freeMem, clamped, minus the anti-affinity
    penalty, plus noise), each against its own key's mask and ask; `usage`
    (float64) is advanced in place. Returns (infeasible choices as (slot,
    key, row), largest gap to the best feasible score of the key, largest
    |device score - recomputed score|)."""
    import numpy as np

    cap = inp["capacity"].astype(np.float64)
    sc_cap = inp["score_cap"].astype(np.float64)
    noise = inp["noise"].astype(np.float64)
    asks = launch["asks"].astype(np.float64)
    masks = launch["masks"]
    counts = np.zeros(len(cap))
    score = np.full((len(asks), len(cap)), -np.inf)

    def rescore(rows):
        for t, ask in enumerate(asks):
            fits = np.all(cap[rows] - usage[rows] >= ask, axis=1) \
                & masks[t, rows]
            with np.errstate(divide="ignore", invalid="ignore"):
                free = 1.0 - (usage[rows, :2] + ask[:2]) / sc_cap[rows]
                s = np.nan_to_num(np.clip(
                    20.0 - 10.0 ** free[:, 0] - 10.0 ** free[:, 1],
                    0.0, 18.0))
            score[t, rows] = np.where(
                fits, s - counts[rows] * float(inp["penalty"]) + noise[rows],
                -np.inf)

    rescore(np.arange(len(cap)))
    infeasible, gap, err, touched = [], 0.0, 0.0, []
    valid, reset = launch["valid"], launch["reset"]
    for j in np.flatnonzero(valid | reset):
        if reset[j] and touched:
            counts[touched] = 0
            rescore(np.array(touched))
            touched = []
        if not valid[j]:
            continue
        t = int(launch["tg_ids"][j])
        row, best = int(packed[j, 0]), float(score[t].max())
        if row < 0 or score[t, row] == -np.inf:
            # Nothing chosen is right only when nothing was feasible.
            if row >= 0 or best > -np.inf:
                infeasible.append((int(j), t, row))
            continue
        gap = max(gap, best - score[t, row])
        err = max(err, abs(float(packed[j, 1]) - score[t, row]))
        usage[row] += asks[t]
        counts[row] += 1
        touched.append(row)
        rescore(np.array([row]))
    return infeasible, gap, err


def judge(inp, packed, usage_after, verdict):
    """Adds check 7's failures for the device's results (`packed` per
    launch, `usage_after` the chain's end) to the verdict; returns what the
    replay found."""
    import numpy as np

    usage = inp["usage"].astype(np.float64)
    infeasible, gap, err = [], 0.0, 0.0
    for i, (launch, got) in enumerate(zip(inp["launches"], packed)):
        bad, g, e = replay_launch(inp, launch, got, usage)
        infeasible += [f"launch {i} ({launch['template']}) slot {j}: row "
                       f"{row} for key {t}" for j, t, row in bad]
        gap, err = max(gap, g), max(err, e)
    usage_err = float(np.max(np.abs(usage_after - usage)))
    verdict.require("7_kernel_feasible", not infeasible,
                    f"{len(infeasible)} device choices infeasible for "
                    "their own key's mask and ask", infeasible)
    verdict.require("7_kernel_best_fit",
                    gap <= SCORE_TOL and err <= SCORE_TOL,
                    f"gap to the key's best feasible score {gap}, score "
                    f"error against float64 {err}",
                    value=max(gap, err), limit=SCORE_TOL)
    verdict.require("7_kernel_usage_after", usage_err <= USAGE_TOL,
                    "usage after the window differs from the replay's by "
                    f"{usage_err}", value=usage_err, limit=USAGE_TOL)
    return {"infeasible_choices": len(infeasible),
            "max_gap_to_best_feasible": gap,
            "score_max_err_vs_float64": err,
            "usage_after_max_abs_err": usage_err}


def check(dep, seed, verdict):
    """Adds check 7's failures to the verdict; returns the facts."""
    nt = dep.server.tindex.nt
    n_evals = dep.server.config.scheduler_window
    inp = window_inputs(dep.config, seed, nt.n_rows, dep.n_nodes, n_evals)
    packed, usage_after = run_keyed(inp)
    mirror = run_mirror(inp)
    found = judge(inp, packed, usage_after, verdict)
    launches = inp["launches"]
    placed = equal = 0
    for launch, dev, mir in zip(launches, packed, mirror):
        v = launch["valid"]
        placed += int((dev[v, 0] >= 0).sum())
        equal += int((dev[v, 0] == mir[v, 0]).sum())
    return {"rows": int(nt.n_rows), "evals": n_evals,
            "launches": len(launches),
            "keys": sum(len(la["asks"]) for la in launches),
            "keys_per_launch": [len(la["asks"]) for la in launches],
            "evals_per_launch": [la["evals"] for la in launches],
            "placements": sum(la["n_valid"] for la in launches),
            "placed_by_device": placed, "rows_equal_to_mirror": equal,
            **found}
