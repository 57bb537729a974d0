"""What the configuration dc-50k brings to the yardstick, without starting
an agent: the fleet builder's datacenter shares and computed classes at
full size, check 7 for several keys (benchmark/reference/kernel_mirror_keys)
on results broken in each way it must name, and the plain recomputation
catching an allocation in a datacenter its job does not name."""

import collections
import json
import os
import random

import numpy as np
import pytest

from benchmark.deploy.dev_agent_dcs import (build_fleet, datacenter_sizes,
                                            seeded_uuid)
from benchmark.readers import worker_stats_zero
from benchmark.reference import guarantees, kernel_mirror_keys
from benchmark.reference.kernel_mirror import P_PAD, SCORE_TOL
from nomad_tpu.structs import (Allocation, Evaluation, Job, Resources,
                               compute_node_class, from_dict)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs", "dc-50k.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "benchmark", "traffic", "storm-dcs.json")) as _f:
    TRAFFIC = json.load(_f)
FLEET = CONFIG["fleet"]


def test_the_full_fleet_has_the_files_shares_and_classes():
    nodes = build_fleet(FLEET, FLEET["nodes"], random.Random(2 ** 31 + 5))
    assert len(nodes) == 50000 and len({n.ID for n in nodes}) == 50000
    by_dc = collections.Counter(n.Datacenter for n in nodes)
    assert by_dc == {"dc1": 20000, "dc2": 15000, "dc3": 10000, "dc4": 5000}
    classes, racks = set(), collections.defaultdict(set)
    for node in nodes:
        compute_node_class(node)
        classes.add(node.ComputedClass)
        racks[(node.Datacenter, node.Meta["rack"])].add(node.ComputedClass)
    assert len(classes) == FLEET["computed_classes"] == 256
    assert len(racks) == 256 and all(len(c) == 1 for c in racks.values())
    # The ineligible shares of svc-10k, over all (datacenter, rack) pairs.
    arm = {(n.Datacenter, n.Meta["rack"]) for n in nodes
           if n.Attributes["arch"] == "arm64"}
    no_exec = {(n.Datacenter, n.Meta["rack"]) for n in nodes
               if "driver.exec" not in n.Attributes}
    assert (len(arm), len(no_exec)) == (16, 8) and not arm & no_exec
    assert sum(n.Status == "initializing" for n in nodes) == 50
    # The smallest power of two that holds them is the file's table.
    assert 32768 < len(nodes) <= FLEET["table_rows"] == 65536


@pytest.mark.parametrize("n,sizes", [
    (50000, [20000, 15000, 10000, 5000]), (2000, [800, 600, 400, 200]),
    (400, [160, 120, 80, 40]), (96, [38, 29, 19, 10]), (7, [3, 2, 1, 1])])
def test_a_smaller_fleet_keeps_the_shares(n, sizes):
    assert [s for _, s in datacenter_sizes(FLEET, n)] == sizes
    assert CONFIG["rehearsal"]["nodes"] == 2000


def test_the_mix_names_the_files_templates_and_both_shape_classes():
    assert sorted(TRAFFIC["templates"]) == sorted(CONFIG["jobs"])
    assert sum(TRAFFIC["templates"].values()) == 20
    warm = CONFIG["warmup"]
    assert sorted(warm["first"]) == sorted(CONFIG["jobs"])
    keys = {len(CONFIG["jobs"][t]["TaskGroups"])
            for t in warm["shape_classes"].values()}
    assert keys == {len(j["TaskGroups"]) for j in CONFIG["jobs"].values()}
    for job in CONFIG["jobs"].values():
        assert sum(g["Count"] for g in job["TaskGroups"]) == 50
        assert all(not t["Resources"]["Networks"]
                   for g in job["TaskGroups"] for t in g["Tasks"])


def test_a_ratio_per_launch_reads_zero_where_nothing_was_launched():
    stats = {"launches": 0, "launch_evals": 0, "launch_keys": 0, "windows": 4}
    run = {"stats": stats, "ops": []}
    assert worker_stats_zero.read(run, "launch_evals", "launches") == 0.0
    stats.update(launches=6, launch_evals=27, launch_keys=7)
    assert worker_stats_zero.read(run, "launch_evals", "launches") == 4.5
    assert worker_stats_zero.read(run, "launch_keys", "launches",
                                  scale=6.0) == 7.0
    # The parent's stats lack the keys: nothing to read, and no error.
    parent = {"stats": {"windows": 4, "fast": 100}, "ops": []}
    assert worker_stats_zero.read(parent, "launch_evals", "launches") is None
    assert worker_stats_zero.read({"stats": {}, "ops": []}, "launch_evals",
                                  "launches") is None


# ------------------------------------------------ check 7, several keys
@pytest.fixture(scope="module")
def window():
    inp = kernel_mirror_keys.window_inputs(CONFIG, 2 ** 31 + 77, 512, 400, 32)
    packed, usage_after = kernel_mirror_keys.run_keyed(inp)
    return inp, packed, usage_after


def _judge(inp, packed, usage_after):
    verdict = guarantees.Verdict()
    found = kernel_mirror_keys.judge(inp, packed, usage_after, verdict)
    return verdict, found


def _launch_of(inp, template):
    return next(i for i, la in enumerate(inp["launches"])
                if la["template"] == template)


def test_the_window_holds_every_template_as_the_served_path_launches_it(
        window):
    inp, packed, usage_after = window
    launches = inp["launches"]
    assert sorted(la["template"] for la in launches) == sorted(CONFIG["jobs"])
    assert sum(la["evals"] for la in launches) == 32
    two = launches[_launch_of(inp, "global-2tg")]
    assert two["masks"].shape == (2, 512) and two["asks"].shape == (2, 5)
    one_eval = two["tg_ids"][:P_PAD][two["valid"][:P_PAD]]
    assert list(one_eval) == [0] * 40 + [1] * 10
    for la in launches:
        evals = len(la["valid"]) // P_PAD
        assert evals == 1 if la["evals"] == 1 else evals >= max(4, la["evals"])
        assert la["reset"].sum() == (0 if la["evals"] == 1 else evals)
        assert la["valid"].sum() == la["n_valid"] == 50 * la["evals"]
    # A local template's keys see their own datacenter's rows alone.
    dc4 = launches[_launch_of(inp, "local-dc4")]["masks"][0]
    assert dc4[:360].sum() == 0 and 0 < dc4[360:400].sum() <= 40
    verdict, found = _judge(inp, packed, usage_after)
    assert verdict.correct, verdict.failures
    assert found["infeasible_choices"] == 0
    assert found["score_max_err_vs_float64"] < 1e-4
    mirror = kernel_mirror_keys.run_mirror(inp)
    assert all((dev[la["valid"], 0] == mir[la["valid"], 0]).all()
               for la, dev, mir in zip(launches, packed, mirror))


def _a_choice_outside_its_keys_datacenters(inp, packed, usage_after):
    i = _launch_of(inp, "local-dc2")
    mask = inp["launches"][i]["masks"][0]
    outside = int(np.flatnonzero(~mask[:160])[0])  # a row of dc1
    packed[i][3, 0] = outside
    return "7_kernel_feasible", f"row {outside} for key 0"


def _a_choice_outside_the_second_keys_mask(inp, packed, usage_after):
    i = _launch_of(inp, "global-2tg")
    launch = inp["launches"][i]
    slot = int(np.flatnonzero(launch["valid"] & (launch["tg_ids"] == 1))[0])
    # Eligible for the first key, not for the second: the one it was for.
    row = int(np.flatnonzero(launch["masks"][0] & ~launch["masks"][1])[0])
    packed[i][slot, 0] = row
    return "7_kernel_feasible", f"row {row} for key 1"


def _a_score_off_by_more_than_the_limit(inp, packed, usage_after):
    packed[_launch_of(inp, "pair-dc1-dc2")][0, 1] += 5 * SCORE_TOL
    return "7_kernel_best_fit", "score error against float64"


def _scores_in_the_precision_below(inp, packed, usage_after):
    import jax.numpy as jnp

    for got in packed:
        got[:, 1] = np.asarray(jnp.asarray(got[:, 1], jnp.bfloat16),
                               np.float32)
    return "7_kernel_best_fit", "score error against float64"


def _a_feasible_row_that_is_not_the_best(inp, packed, usage_after):
    i = _launch_of(inp, "local-dc1")
    launch = inp["launches"][i]
    # The emptiest eligible row: feasible, and far from the best fit.
    rows = np.flatnonzero(launch["masks"][0])
    packed[i][0, 0] = int(rows[np.argmin(inp["usage"][rows, 0])])
    return "7_kernel_best_fit", "gap to the key's best feasible score"


def _a_wrong_usage_row(inp, packed, usage_after):
    usage_after[int(packed[0][0, 0]), 1] += 32.0
    return "7_kernel_usage_after", "differs from the replay's by 32.0"


@pytest.mark.parametrize("break_it", [
    _a_choice_outside_its_keys_datacenters,
    _a_choice_outside_the_second_keys_mask,
    _a_score_off_by_more_than_the_limit, _scores_in_the_precision_below,
    _a_feasible_row_that_is_not_the_best, _a_wrong_usage_row],
    ids=lambda f: f.__name__.strip("_"))
def test_check_7_names_what_is_broken(window, break_it):
    inp, packed, usage_after = window
    packed = [p.copy() for p in packed]
    usage_after = usage_after.copy()
    check, words = break_it(inp, packed, usage_after)
    verdict, _ = _judge(inp, packed, usage_after)
    assert not verdict.correct
    named = {f["check"]: f for f in verdict.failures}
    assert check in named, verdict.failures
    failure = named[check]
    assert words in failure["detail"] or any(words in i
                                             for i in failure["ids"])


# ------------------------- check 3: a datacenter the job does not name
def _committed(template, nodes, rng, on):
    """One acknowledged job of the template with every allocation on a
    node `on` picks from those that satisfy it."""
    job = from_dict(Job, CONFIG["jobs"][template])
    job.ID, job.Name = seeded_uuid(rng), template
    ev = Evaluation(ID=seeded_uuid(rng), JobID=job.ID, Type=job.Type,
                    Status="complete")
    allocs = []
    for group in job.TaskGroups:
        ok = [n for n in nodes if guarantees.node_satisfies(n, job, group)]
        ask = group.Tasks[0].Resources
        for i in range(group.Count):
            allocs.append(Allocation(
                ID=seeded_uuid(rng), EvalID=ev.ID,
                Name=f"{job.Name}.{group.Name}[{i}]", NodeID=on(ok, i).ID,
                JobID=job.ID, TaskGroup=group.Name,
                TaskResources={group.Tasks[0].Name: Resources(
                    CPU=ask.CPU, MemoryMB=ask.MemoryMB, DiskMB=ask.DiskMB)},
                DesiredStatus="run", ClientStatus="pending"))
    return job, ev, allocs


@pytest.mark.parametrize("template,wrong_dc", [
    ("local-dc1", "dc2"), ("local-dc4", "dc1"), ("pair-dc1-dc2", "dc3"),
    ("global-2tg", None)])
def test_an_allocation_in_a_datacenter_the_job_does_not_name_is_caught(
        template, wrong_dc):
    rng = random.Random(11)
    nodes = build_fleet(FLEET, 96, rng)
    row_of = {n.ID: i for i, n in enumerate(nodes)}
    job, ev, allocs = _committed(template, nodes, rng,
                                 on=lambda ok, i: ok[i * 13 % len(ok)])
    assert {n.Datacenter for n in nodes
            if n.ID in {a.NodeID for a in allocs}} \
        == set(CONFIG["jobs"][template]["Datacenters"])

    def verdict():
        usage = np.zeros((len(nodes), 5), np.float32)
        for n in nodes:
            usage[row_of[n.ID]] = guarantees.node_reserved(n)
        for a in allocs:
            usage[row_of[a.NodeID]] += guarantees.alloc_ask(a)
        reads = {"nodes": nodes, "jobs": [job], "evals": [ev],
                 "allocs": allocs}
        return guarantees.check(reads, [(job.ID, ev.ID, template)], {},
                                usage, row_of)

    assert verdict().correct, verdict().failures
    if wrong_dc is None:
        return  # a job over all four datacenters has no wrong one
    # The same node shape, eligible in every other respect, elsewhere.
    group = job.TaskGroups[0]
    everywhere = from_dict(Job, CONFIG["jobs"]["global-2tg"])
    moved_to = next(n for n in nodes if n.Datacenter == wrong_dc
                    and guarantees.node_satisfies(n, everywhere, group))
    allocs[7].NodeID = moved_to.ID
    got = verdict()
    assert [f["check"] for f in got.failures] == ["3_constraints"]
    assert got.failures[0]["ids"] == [allocs[7].ID]
