"""Check 7: the device's keyed placement kernel against the numpy mirror
and a float64 replay, on one seeded window, outside the measured window.

The served path hands windows over between the device kernel and the
mirror (pipelined_worker's host mode), which rests on "any row that is
feasible and best-fit within rounding is acceptable". So that is what is
judged: every device choice feasible, its score within SCORE_TOL of the
best feasible score, the usage it returns equal to the replay's. Row
equality with the mirror is reported as a fact and never demanded: the
chip's divide and exp2 need not round as numpy's do.

Copied from chip_smoke.py (PR 21): window_inputs, run_keyed, run_mirror,
replay_choices. Changed: the node and ask sizes come from the configuration
file, not from literals."""

from __future__ import annotations

SCORE_TOL = 1e-3
P_PAD = 64  # the kernel's placements per eval, padded


def window_inputs(config, template, seed, n_rows, n_live, n_evals):
    """A fleet of the file's node shape, part filled, and one window of
    n_evals jobs of the template (Count placements each, padded to 64)."""
    import numpy as np

    node = config["fleet"]["node"]
    job = config["jobs"][template]
    task = job["TaskGroups"][0]["Tasks"][0]["Resources"]
    per_eval = job["TaskGroups"][0]["Count"]
    res, rsv = node["Resources"], node["Reserved"]
    rng = np.random.default_rng(seed)
    ask = np.array([task["CPU"], task["MemoryMB"], task["DiskMB"], 0, 0],
                   np.float32)
    reserved = np.array([rsv["CPU"], rsv["MemoryMB"], rsv["DiskMB"], 0,
                         sum(n["MBits"] for n in rsv["Networks"])],
                        np.float32)
    capacity = np.zeros((n_rows, 5), np.float32)
    capacity[:n_live] = [res["CPU"], res["MemoryMB"], res["DiskMB"],
                         res["IOPS"],
                         sum(n["MBits"] for n in res["Networks"])]
    score_cap = np.ones((n_rows, 2), np.float32)
    score_cap[:n_live] = capacity[:n_live, :2] - reserved[:2]
    room = int(min((capacity[0, d] - reserved[d]) // ask[d]
                   for d in range(3) if ask[d] > 0))
    usage = np.zeros((n_rows, 5), np.float32)
    usage[:n_live] = reserved + ask * rng.integers(
        0, max(1, room - 5), (n_live, 1)).astype(np.float32)
    mask = np.zeros((1, n_rows), bool)
    mask[0, :n_live] = rng.random(n_live) < 0.9
    p = P_PAD * n_evals
    valid = np.tile(np.arange(P_PAD) < per_eval, n_evals)
    reset = np.zeros(p, bool)
    reset[::P_PAD] = True
    return {"capacity": capacity, "score_cap": score_cap, "usage": usage,
            "mask": mask, "ask": ask,
            "noise": (rng.random(n_rows) * 1e-3).astype(np.float32),
            "tg_ids": np.zeros(p, np.int32), "valid": valid, "reset": reset,
            "penalty": np.float32(10.0), "n_valid": per_eval * n_evals}


def run_keyed(inp):
    """The window through kernels.place_batch_keyed on one device."""
    import numpy as np

    from nomad_tpu.scheduler import kernels

    n = inp["capacity"].shape[0]
    return kernels.place_batch_keyed(
        None, inp["capacity"], inp["score_cap"], inp["usage"], inp["mask"],
        np.zeros(n, np.int32), inp["ask"][None, :], inp["tg_ids"],
        inp["valid"], inp["noise"], inp["penalty"], np.asarray(False),
        np.zeros(n, bool), inp["reset"], inp["n_valid"])


def run_mirror(inp):
    """The same window through the numpy mirror, one eval at a time with
    the usage chained, as stack.dispatch_host drives it."""
    import numpy as np

    from nomad_tpu.scheduler import kernels

    n = inp["capacity"].shape[0]
    usage, out = inp["usage"], []
    for e in range(len(inp["valid"]) // P_PAD):
        sl = slice(P_PAD * e, P_PAD * e + P_PAD)
        res = kernels.place_batch_host(
            inp["capacity"], inp["score_cap"], usage, inp["mask"],
            np.zeros(n, np.int32), np.tile(inp["ask"], (P_PAD, 1)),
            inp["tg_ids"][sl], inp["valid"][sl], inp["noise"],
            inp["penalty"], False, np.zeros(n, bool))
        usage = res.usage_after
        out.append(res.packed)
    return np.concatenate(out)


def replay_choices(inp, packed):
    """Follow the device's choices in float64 with the reference's formula
    (20 - 10^freeCpu - 10^freeMem, clamped, minus the anti-affinity penalty,
    plus noise). Returns (infeasible choices, largest gap to the best
    feasible score, largest |device score - recomputed score|, usage)."""
    import numpy as np

    cap = inp["capacity"].astype(np.float64)
    sc_cap = inp["score_cap"].astype(np.float64)
    usage = inp["usage"].astype(np.float64)
    ask = inp["ask"].astype(np.float64)
    noise = inp["noise"].astype(np.float64)
    mask = inp["mask"][0]
    counts = np.zeros(len(cap))
    score = np.full(len(cap), -np.inf)

    def rescore(rows):
        fits = np.all(cap[rows] - usage[rows] >= ask, axis=1) & mask[rows]
        with np.errstate(divide="ignore", invalid="ignore"):
            free = 1.0 - (usage[rows, :2] + ask[:2]) / sc_cap[rows]
            s = np.nan_to_num(np.clip(
                20.0 - 10.0 ** free[:, 0] - 10.0 ** free[:, 1], 0.0, 18.0))
        score[rows] = np.where(
            fits, s - counts[rows] * float(inp["penalty"]) + noise[rows],
            -np.inf)

    rescore(np.arange(len(cap)))
    infeasible, gap, err, touched = 0, 0.0, 0.0, []
    for j in np.flatnonzero(inp["valid"] | inp["reset"]):
        if inp["reset"][j] and touched:
            counts[touched] = 0
            rescore(np.array(touched))
            touched = []
        if not inp["valid"][j]:
            continue
        row, best = int(packed[j, 0]), float(score.max())
        if row < 0 or score[row] == -np.inf:
            # Nothing chosen is right only when nothing was feasible.
            infeasible += int(row >= 0 or best > -np.inf)
            continue
        gap = max(gap, best - score[row])
        err = max(err, abs(float(packed[j, 1]) - score[row]))
        usage[row] += ask
        counts[row] += 1
        touched.append(row)
        rescore(np.array([row]))
    return infeasible, gap, err, usage


def check(dep, seed, verdict):
    """Adds check 7's failures to the verdict; returns the facts."""
    import numpy as np

    config = dep.config
    template = config["warmup"]["template"]
    nt = dep.server.tindex.nt
    n_evals = dep.server.config.scheduler_window
    inp = window_inputs(config, template, seed, nt.n_rows, dep.n_nodes,
                        n_evals)
    res = run_keyed(inp)
    dev = np.asarray(res.packed)
    mir = run_mirror(inp)
    v = inp["valid"]
    infeasible, gap, err, usage = replay_choices(inp, dev)
    usage_err = float(np.max(np.abs(np.asarray(res.usage_after) - usage)))
    verdict.require("7_kernel_feasible", infeasible == 0,
                    f"{infeasible} infeasible device choices",
                    value=infeasible)
    verdict.require("7_kernel_best_fit", gap <= SCORE_TOL and err <= SCORE_TOL,
                    f"gap to the best feasible score {gap}, score error "
                    f"against float64 {err}",
                    value=max(gap, err), limit=SCORE_TOL)
    verdict.require("7_kernel_usage_after", usage_err <= 1e-2,
                    "usage after the window differs from the replay's by "
                    f"{usage_err}", value=usage_err, limit=1e-2)
    return {"rows": int(nt.n_rows), "evals": n_evals,
            "placements": int(v.sum()),
            "placed_by_device": int((dev[v, 0] >= 0).sum()),
            "rows_equal_to_mirror": int((dev[v, 0] == mir[v, 0]).sum()),
            "infeasible_choices": infeasible,
            "max_gap_to_best_feasible": gap,
            "score_max_err_vs_float64": err,
            "usage_after_max_abs_err": usage_err}
