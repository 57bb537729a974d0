"""Check 7 for long chains: the device's keyed placement program against
the numpy mirror and a float64 replay, on one seeded window of jobs of
1,000 at the running table's rows, outside the measured window.

kernel_mirror's window is 32 evals of 50 placements in pads of 64: 2,048
serial steps over a 2,048-row candidate set. Here an eval is the
configuration's job (1,000 placements in a pad of 1,024), the window is one
fused launch as pipelined_worker._launch_window makes it of a full window
(stack.dispatch_multi: the evals padded to a power of two, a reset of the
job's anti-affinity counts at every eval's first step), and the candidate
count (kernels.keyed_cand_count of the real placements) is above the
table's rows, so every step scores and argmaxes the whole table. The fleet
is the file's node shape, filled as the end of a fill leaves it: most rows
at the brim or up to two asks short of it, a seeded share of open rows
filled anywhere from empty to full, sized so that the open rows hold a
tenth more than the window asks for. The window then packs open rows
to the brim: a row takes up to 243 sequential float32 adds, and as the
open rows run out an eval's 1,000 placements crowd onto fewer and fewer of
them, so the job's anti-affinity count on a row climbs.

Judged as kernel_mirror judges, by kernel_mirror_keys.judge (one launch,
one key) with its limits, not new ones: every device choice feasible, its
score within SCORE_TOL (1e-3) of the best feasible score at its step and of
the float64 score, the usage returned within USAGE_TOL (1e-2) of the
replay's. Row equality with the mirror is a fact, never demanded.

Why those limits still hold over a chain sixteen times longer (32,768
steps against 2,048; twenty times the placements an eval):

- usage. Every ask and every reserved amount is a whole number of MHz and
  MiB and a row's sum stays under 2**24, so each float32 add is exact
  however many a row takes (243 at most: then the row is full). The replay
  in float64 lands on the same numbers; 1e-2 allows for a chip whose adds
  are not IEEE's, not for the chain's length.
- scores. A score is BestFit-v3's 0..18 minus 10 a placement of the same
  job on the row, plus noise under 1e-3. The count on a row cannot pass
  what a row holds, 243, so |score| < 2,448 < 4,096, where a float32's ulp
  is 2.4e-4: the subtraction and the addition round by at most 1.2e-4
  each, the exponentials by ~1e-5 as in kernel_mirror. A device score is
  then within ~2.6e-4 of the float64 one, and the row the device's float32
  argmax picks within twice that of the best in float64: 5.2e-4 < 1e-3.
  With counts as a window of jobs of 50 has them (0 or 1) the same
  arithmetic gives 1e-5; the facts below report both maxima, so a run that
  comes close to the limit shows it.
- scores rounded to bfloat16 (8 bits of mantissa: 3e-2 to 6e-2 at a score
  of 8 to 18, 1 to 2 at the hundreds a crowded row's penalty reaches) fail
  7_kernel_best_fit here as they do for kernel_mirror_keys: on the test's
  window (8 evals over 512 rows, CPU) the score error reads 2.0 rounded
  and 5.1e-5 as computed; on the chip's window it read 3.7e-5 to 3.8e-5 in
  every run (my chip runs, PR 33; PERF.md section 6). The limit of 1e-3
  lies a factor of 26 above the one reading and 2,000 below the other.
"""

from __future__ import annotations

from benchmark.reference import kernel_mirror_keys
from benchmark.reference.kernel_mirror_keys import run_keyed  # noqa: F401

OPEN_ROOM = 1.1  # the open rows hold this many times the window's ask


def _pad_pow2(n, floor):
    p = floor
    while p < n:
        p *= 2
    return p


def window_inputs(config, template, seed, n_rows, n_live, n_evals,
                  count=None):
    """A fleet of the file's node shape filled as the end of a fill leaves
    it, and one window of n_evals jobs of the template (`count` placements
    each, the template's Count by default, padded to a power of two) as one
    fused launch. The shape kernel_mirror_keys.run_keyed and judge take."""
    import numpy as np

    node = config["fleet"]["node"]
    job = config["jobs"][template]
    task = job["TaskGroups"][0]["Tasks"][0]["Resources"]
    count = count or job["TaskGroups"][0]["Count"]
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
    mask = np.zeros((1, n_rows), bool)
    mask[0, :n_live] = rng.random(n_live) < 0.9
    # An open row is half empty on average and nine in ten are eligible.
    open_share = min(1.0, OPEN_ROOM * n_evals * count
                     / (n_live * 0.9 * room / 2))
    is_open = rng.random(n_live) < open_share
    held = np.where(is_open, rng.integers(0, room + 1, n_live),
                    room - rng.integers(0, 3, n_live))
    usage = np.zeros((n_rows, 5), np.float32)
    usage[:n_live] = reserved + ask * held[:, None].astype(np.float32)

    p_pad = _pad_pow2(count, 8)
    e_pad = _pad_pow2(n_evals, 4) if n_evals > 1 else 1
    valid = np.tile(np.arange(p_pad) < count, e_pad)
    valid[n_evals * p_pad:] = False
    reset = np.zeros(e_pad * p_pad, bool)
    if n_evals > 1:
        reset[::p_pad] = True
    launch = {"template": template, "evals": n_evals, "p_pad": p_pad,
              "masks": mask, "asks": ask[None, :],
              "tg_ids": np.zeros(e_pad * p_pad, np.int32), "valid": valid,
              "reset": reset, "n_valid": n_evals * count}
    return {"capacity": capacity, "score_cap": score_cap, "usage": usage,
            "noise": (rng.random(n_rows) * 1e-3).astype(np.float32),
            "penalty": np.float32(10.0), "room": room, "launches": [launch]}


def run_mirror(inp):
    """The same window through the numpy mirror, one eval at a time with
    the usage chained, as stack.dispatch_host drives it."""
    import numpy as np

    from nomad_tpu.scheduler import kernels

    launch = inp["launches"][0]
    n, p = inp["capacity"].shape[0], launch["p_pad"]
    usage, out = inp["usage"], []
    for e in range(len(launch["valid"]) // p):
        sl = slice(p * e, p * e + p)
        res = kernels.place_batch_host(
            inp["capacity"], inp["score_cap"], usage, launch["masks"],
            np.zeros(n, np.int32), np.tile(launch["asks"][0], (p, 1)),
            launch["tg_ids"][sl], launch["valid"][sl], inp["noise"],
            inp["penalty"], False, np.zeros(n, bool))
        usage = res.usage_after
        out.append(res.packed)
    return [np.concatenate(out)]


def chain_facts(inp, packed):
    """How long the chains really were: the most placements one row took
    over the window (sequential float32 adds), and the most one eval put on
    one row (the job anti-affinity count the scores carried)."""
    import numpy as np

    launch = inp["launches"][0]
    n, p = inp["capacity"].shape[0], launch["p_pad"]
    rows = packed[0][:, 0].astype(np.int64)
    placed = launch["valid"] & (rows >= 0)
    per_eval = [np.bincount(rows[p * e:p * e + p][placed[p * e:p * e + p]],
                            minlength=n).max()
                for e in range(launch["evals"])]
    return {"max_adds_on_a_row": int(np.bincount(rows[placed],
                                                 minlength=n).max()),
            "max_job_count_on_a_row": int(max(per_eval))}


def check(dep, seed, verdict):
    """Adds check 7's failures to the verdict; returns the facts."""
    config = dep.config
    template = config["warmup"]["template"]
    nt = dep.server.tindex.nt
    n_evals = dep.server.config.scheduler_window
    inp = window_inputs(config, template, seed, nt.n_rows, dep.n_nodes,
                        n_evals, dep.job_count)
    packed, usage_after = run_keyed(inp)
    mirror = run_mirror(inp)
    found = kernel_mirror_keys.judge(inp, packed, usage_after, verdict)
    launch = inp["launches"][0]
    v = launch["valid"]
    return {"rows": int(nt.n_rows), "evals": n_evals,
            "steps": int(len(v)), "steps_per_eval": launch["p_pad"],
            "placements": launch["n_valid"],
            "placed_by_device": int((packed[0][v, 0] >= 0).sum()),
            "rows_equal_to_mirror":
                int((packed[0][v, 0] == mirror[0][v, 0]).sum()),
            **chain_facts(inp, packed), **found}
