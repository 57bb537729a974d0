"""What the configuration c1m-5k brings to the yardstick (ISSUE 33), without
starting an agent: the numbers its file states recomputed from the file,
the warm-up's bursts against the programs a window of jobs of 1,000 can
reach (computed, no device) and its cap on the allocations asked for, the
rehearsal's job size applied and refused, check 7 for long chains
(benchmark/reference/kernel_mirror_chain) on results broken in each way it
must name, the new counters on made-up stats, and the cell's metric lists
in BENCHMARK.json."""

import collections
import copy
import importlib
import json
import os
import random
import types

import numpy as np
import pytest

from benchmark.deploy import dev_agent_c1m
from benchmark.deploy.dev_agent import build_fleet
from benchmark.readers import worker_stats_opt, worker_stats_zero
from benchmark.reference import (guarantees, kernel_mirror_chain,
                                 kernel_mirror_keys)
from benchmark.reference.kernel_mirror import SCORE_TOL
from nomad_tpu.scheduler import kernels
from nomad_tpu.scheduler.stack import (HOST_ROW_STEP_BUDGET,
                                       KEYED_CAND_BUDGET, _pad_pow2, eval_pad)
from nomad_tpu.structs import Job, compute_node_class, from_dict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")
CONFIG = _json("benchmark", "configs", "c1m-5k.json")
TRAFFIC = _json("benchmark", "traffic", "fill.json")
FLEET = CONFIG["fleet"]
CELL = "c1m-5k.fill"
TEMPLATE = "c1m-1000"
# Held off the cell until ISSUE 38: five entries that tests/benchmark_suite
# pinned to the cells they had, and two that read the device's timeline
# inside the window, which this window's guard ends long before the clock
# would have started the trace. ISSUE 38 loosened the pins and anchored the
# trace to the guard's approach (the traffic file's trace_guard_share), so
# the cell reports all seven.
PINNED = ["window_collect_share.storm", "stage_wait_ms.storm",
          "plan_queue_ms.storm", "device_idle.dispatch.storm",
          "device_idle.planwait.storm"]
IN_WINDOW_TRACE = ["kernel_ms.storm", "device_idle.storm"]


# ------------------------------------------------- the file's numbers
@pytest.fixture(scope="module")
def fleet():
    return build_fleet(FLEET, FLEET["nodes"], random.Random(2 ** 31 + 32))


def test_the_full_fleet_is_the_sources_5000_hosts_of_one_machine_type(fleet):
    assert len(fleet) == 5000 and len({n.ID for n in fleet}) == 5000
    assert FLEET["rack_variants"] == []
    classes = set()
    for node in fleet:
        compute_node_class(node)
        classes.add(node.ComputedClass)
    assert len(classes) == FLEET["computed_classes"] == FLEET["racks"] == 64
    assert {n.Datacenter for n in fleet} == {"dc1"}
    assert len({(n.Resources.CPU, n.Resources.MemoryMB, n.Resources.DiskMB,
                 tuple(sorted(n.Attributes.items()))) for n in fleet}) == 1
    by_status = collections.Counter(n.Status for n in fleet)
    assert by_status == {"ready": FLEET["ready_nodes"], "initializing": 5}
    # The smallest power of two that holds them is the file's table.
    assert 4096 < len(fleet) <= FLEET["table_rows"] == 8192


def test_capacity_and_guard_are_what_the_file_states(fleet):
    job = from_dict(Job, CONFIG["jobs"][TEMPLATE])
    room = guarantees.capacity_allocs(fleet, job)
    assert FLEET["allocs_per_node"] == 243
    assert guarantees.capacity_allocs(
        [n for n in fleet if n.Status == "ready"][:1], job) == 243
    assert room == FLEET["capacity_allocs"] == 1_213_785 == 4995 * 243
    guard = int(TRAFFIC["fill_guard"] * room)
    assert guard == FLEET["fill_guard_allocs"] == 1_092_406
    # The warm-up counts toward the guard; the window itself asks for
    # about a million, in whole jobs.
    warm = sum(CONFIG["warmup"]["bursts"]) * 1000
    assert warm == 63_000 <= CONFIG["warmup"]["max_allocs"] == 100_000
    assert warm / room < 0.09
    jobs_in_window = -(-(TRAFFIC["fill_guard"] * room - warm) // 1000)
    assert jobs_in_window == 1030


def test_the_job_and_the_traffic_are_the_issues():
    assert list(CONFIG["jobs"]) == [TEMPLATE]
    job = CONFIG["jobs"][TEMPLATE]
    assert job["Type"] == "service" and job["Datacenters"] == ["dc1"]
    assert job["Constraints"] == [{"LTarget": "${attr.kernel.name}",
                                   "RTarget": "linux", "Operand": "="}]
    (group,) = job["TaskGroups"]
    (task,) = group["Tasks"]
    assert group["Count"] == dev_agent_c1m.JOB_COUNT == 1000
    assert task["Driver"] == "exec" and task["Resources"]["Networks"] == []
    # 20 MHz is the smallest ask the entry accepts (`assumed`.ask;
    # tests/test_c1m_shape.py shows job_register refusing 16).
    res = task["Resources"]
    assert (res["CPU"], res["MemoryMB"], res["DiskMB"]) == (20, 32, 10)
    assert from_dict(Job, job).TaskGroups[0].Tasks[0].Resources \
        .meets_min_resources() == []
    node = FLEET["node"]
    assert (node["Resources"]["CPU"], node["Resources"]["MemoryMB"],
            node["Resources"]["DiskMB"]) == (4960, 8192, 102400)
    assert (node["Reserved"]["CPU"], node["Reserved"]["MemoryMB"],
            node["Reserved"]["DiskMB"]) == (100, 256, 4096)
    free = node["Resources"]["CPU"] - node["Reserved"]["CPU"]
    assert free // res["CPU"] == 243
    assert (node["Resources"]["MemoryMB"] - node["Reserved"]["MemoryMB"]) \
        // res["MemoryMB"] == 248
    # mock.Node's 4,000 MHz would hold 195: under C1M's 200 a host.
    assert (4000 - 100) // 20 == 195 < 200 < 243
    assert {k: TRAFFIC[k] for k in (
        "generator", "outstanding", "poll_ms", "fill_guard", "templates",
        "extra_checks", "trace_seconds", "trace_guard_share")} == {
        "generator": "closed_loop", "outstanding": 256, "poll_ms": 20,
        "fill_guard": 0.9, "templates": {TEMPLATE: 1},
        "extra_checks": ["kernel_mirror_chain"], "trace_seconds": 3,
        "trace_guard_share": 0.35}
    # The file says in words where that puts the trace: at a share of the
    # work, since the guard and not the clock ends this window.
    for words in ("65 % of the guard's limit", "whichever comes first",
                  "a share of the work, not a time",
                  "kernel_ms.storm, device_idle.storm"):
        assert words in TRAFFIC["trace_where"]
    svc = _json("benchmark", "configs", "svc-10k.json")
    assert CONFIG["server"] == svc["server"]
    assert CONFIG["guarantees"] == svc["guarantees"]
    assert sorted(CONFIG["reduced"]) == ["clients", "entry", "servers"]


# ---------------------------------------------------------- the warm-up
def _program_of(n, count=1000):
    """What a window's run of n same-shaped evals launches: as
    pipelined_worker._launch_window, stack.dispatch_multi and
    kernels.place_batch_keyed decide it."""
    e_pad = eval_pad(n)
    return ("dispatch_multi" if n >= 2 else "dispatch", e_pad,
            e_pad * _pad_pow2(count), kernels.keyed_cand_count(n * count))


def test_the_bursts_reach_exactly_the_programs_a_window_can_reach():
    warm = CONFIG["warmup"]
    assert warm["kind"] == "parked_bursts" and warm["template"] == TEMPLATE
    assert warm["bursts"] == [1, 2, 4, 8, 16, 32]
    reached = dev_agent_c1m.programs_reached(warm["bursts"], 1000)
    window = CONFIG["server"]["scheduler_window"]
    reachable = {_program_of(n) for n in range(1, window + 1)}
    assert reached == reachable and len(reached) == 6
    stated = {(p["launch"], p["e_pad"], p["steps"], p["k_cand"])
              for p in warm["programs"]}
    assert stated == reached
    # Each burst is one program of its own: none is warmed twice.
    assert len({_program_of(n) for n in warm["bursts"]}) == 6
    assert max(p["steps"] for p in warm["programs"]) == 32768
    # One key of this size stays on the keyed program (ROADMAP D3) ...
    rows = FLEET["table_rows"]
    assert 1 * kernels.keyed_cand_count(32_000) <= KEYED_CAND_BUDGET
    assert 5 * kernels.keyed_cand_count(32_000) > KEYED_CAND_BUDGET
    # ... whose candidate count is above the table from 9 evals on.
    assert kernels.keyed_cand_count(9_000) > rows
    # Host mode holds up to 16 evals a window at these rows, and a job of
    # 1,000 is never placed there: the `<= 256` rule defers it.
    assert 16 * rows * 64 <= HOST_ROW_STEP_BUDGET < 17 * rows * 64


def _deployment(config, nodes=None):
    return dev_agent_c1m.Deployment(config, random.Random(1), nodes=nodes)


def test_the_rehearsals_job_size_is_applied_at_400_nodes_only():
    small = _deployment(CONFIG, nodes=CONFIG["rehearsal"]["nodes"])
    assert not small.full_size
    assert small.make_job(TEMPLATE).TaskGroups[0].Count \
        == CONFIG["rehearsal"]["count"] == 200 <= 256
    assert "host == fast" in CONFIG["rehearsal"]["why"]
    full = _deployment(CONFIG)
    assert full.full_size
    assert full.make_job(TEMPLATE).TaskGroups[0].Count == 1000


def _with_64_classes(dep, classes=64):
    dep.server = types.SimpleNamespace(tindex=types.SimpleNamespace(
        nt=types.SimpleNamespace(class_names=list(range(classes)))))
    return dep


@pytest.mark.parametrize("classes,count,bursts,ok", [
    (64, 1000, None, True), (64, 200, None, False), (63, 1000, None, False),
    (64, 1000, [1, 2, 4, 8, 16], False)],
    ids=["the-files", "rehearsal-count", "a-class-short", "a-program-short"])
def test_at_full_size_another_shape_is_refused(classes, count, bursts, ok):
    config = copy.deepcopy(CONFIG)
    config["jobs"][TEMPLATE]["TaskGroups"][0]["Count"] = count
    if bursts:
        config["warmup"]["bursts"] = bursts
    dep = _with_64_classes(_deployment(config), classes)
    if ok:
        dep._check_shape(config["warmup"])
    else:
        with pytest.raises(RuntimeError, match="the configuration states"):
            dep._check_shape(config["warmup"])


@pytest.mark.parametrize("split,asked,refused", [
    ((), 63_000, None), ((32,), 95_000, None), ((16, 32), 79_000, 32),
    ((1, 2, 4, 8, 16, 32), 94_000, 32)],
    ids=["no-repeat", "one-repeat", "two-repeats", "every-burst-repeats"])
def test_the_warm_up_never_asks_for_more_than_100000(split, asked, refused):
    """A burst that was not one launch of all its evals is repeated once;
    before any burst, a repeat included, the module raises if that burst
    would take the allocations asked for past the file's max_allocs."""
    dep = _with_64_classes(_deployment(CONFIG))
    split = list(split)

    def parked_burst(template, n, turn):
        dep.asked += n * 1000  # as Deployment.register counts them
        if n in split:         # n-1 and 1 between two workers, once
            split.remove(n)
            return {"jobs": n, "launches": 2, "launch_evals": n,
                    "launch_steps": (eval_pad(n - 1) + 1) * 1024}
        return {"jobs": n, "launches": 1, "launch_evals": n,
                "launch_steps": eval_pad(n) * 1024}

    dep._parked_burst = parked_burst
    if refused is None:
        dep._warm_up(CONFIG["warmup"])
        assert [b["jobs"] for b in dep.bursts if b["launches"] == 1] \
            == CONFIG["warmup"]["bursts"]
    else:
        with pytest.raises(RuntimeError,
                           match=f"burst of {refused} would take .* past "
                                 "100000"):
            dep._warm_up(CONFIG["warmup"])
    assert dep.asked == asked <= CONFIG["warmup"]["max_allocs"]


def test_a_burst_that_launches_at_the_wrong_steps_is_not_one_launch():
    """One launch of all the evals but at another program's steps (the
    pad rule changed under the file): repeated, then refused."""
    dep = _with_64_classes(_deployment(CONFIG))
    dep._parked_burst = lambda template, n, turn: {
        "jobs": n, "launches": 1, "launch_evals": n, "launch_steps": 1024}
    with pytest.raises(RuntimeError, match="burst of 2 was not one launch"):
        dep._warm_up(CONFIG["warmup"])
    assert [b["jobs"] for b in dep.bursts] == [1, 2, 2]


# ------------------------------------------------ check 7, long chains
@pytest.fixture(scope="module")
def window():
    # 8 evals of 1,000 in pads of 1,024 over 512 rows: 8,192 serial steps,
    # a candidate count (8,192) sixteen times the table.
    inp = kernel_mirror_chain.window_inputs(CONFIG, TEMPLATE, 2 ** 31 + 77,
                                            512, 400, 8)
    packed, usage_after = kernel_mirror_chain.run_keyed(inp)
    return inp, packed, usage_after


def _judge(inp, packed, usage_after):
    verdict = guarantees.Verdict()
    found = kernel_mirror_keys.judge(inp, packed, usage_after, verdict)
    return verdict, found


def test_the_window_is_a_full_windows_launch_at_a_pad_of_1024(window):
    inp, packed, usage_after = window
    (launch,) = inp["launches"]
    assert launch["p_pad"] == 1024 and launch["evals"] == 8
    assert len(launch["valid"]) == 8 * 1024 and launch["reset"].sum() == 8
    assert launch["valid"].sum() == launch["n_valid"] == 8000
    assert kernels.keyed_cand_count(launch["n_valid"]) == 8192 > 512
    # Filled as the end of a fill leaves it: most rows at the brim.
    held = (inp["usage"][:400, 0] - 100) / 20
    assert inp["room"] == 243 and (held >= 241).mean() > 0.5
    verdict, found = _judge(inp, packed, usage_after)
    assert verdict.correct, verdict.failures
    assert found["infeasible_choices"] == 0
    assert found["score_max_err_vs_float64"] < 1e-4
    assert found["usage_after_max_abs_err"] == 0.0  # whole numbers: exact
    # With this seed the open rows run out inside the window: the tail
    # chooses nothing, which the replay finds right (nothing was feasible).
    placed = packed[0][launch["valid"], 0] >= 0
    assert 7000 < placed.sum() < 8000 and not placed[-1]
    mirror = kernel_mirror_chain.run_mirror(inp)
    assert (packed[0][launch["valid"], 0]
            == mirror[0][launch["valid"], 0]).all()
    facts = kernel_mirror_chain.chain_facts(inp, packed)
    # Chains no window of jobs of 50 has: a row takes a hundred adds and
    # more, an eval puts several of its placements on one row.
    assert facts["max_adds_on_a_row"] >= 100
    assert facts["max_job_count_on_a_row"] >= 5


def _an_ineligible_row(inp, packed, usage_after):
    mask = inp["launches"][0]["masks"][0]
    outside = int(np.flatnonzero(~mask[:400])[0])
    packed[0][1500, 0] = outside
    return "7_kernel_feasible", f"slot 1500: row {outside} for key 0"


def _a_row_that_is_full(inp, packed, usage_after):
    mask = inp["launches"][0]["masks"][0]
    full = int(np.flatnonzero(
        mask & (inp["usage"][:, 0] + 20 > inp["capacity"][:, 0]))[0])
    packed[0][3, 0] = full
    return "7_kernel_feasible", f"slot 3: row {full} for key 0"


def _a_score_off_by_more_than_the_limit(inp, packed, usage_after):
    packed[0][4000, 1] += 5 * SCORE_TOL
    return "7_kernel_best_fit", "score error against float64"


def _scores_in_the_precision_below(inp, packed, usage_after):
    import jax.numpy as jnp

    packed[0][:, 1] = np.asarray(jnp.asarray(packed[0][:, 1], jnp.bfloat16),
                                 np.float32)
    return "7_kernel_best_fit", "score error against float64"


def _a_feasible_row_that_is_not_the_best(inp, packed, usage_after):
    rows = np.flatnonzero(inp["launches"][0]["masks"][0])
    packed[0][0, 0] = int(rows[np.argmin(inp["usage"][rows, 0])])
    return "7_kernel_best_fit", "gap to the key's best feasible score"


def _a_wrong_usage_row(inp, packed, usage_after):
    usage_after[int(packed[0][0, 0]), 1] += 32.0
    return "7_kernel_usage_after", "differs from the replay's by 32.0"


@pytest.mark.parametrize("break_it", [
    _an_ineligible_row, _a_row_that_is_full,
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


def test_bfloat16_scores_miss_the_limit_by_an_order_of_magnitude(window):
    inp, packed, usage_after = window
    packed = [p.copy() for p in packed]
    _scores_in_the_precision_below(inp, packed, usage_after)
    _, found = _judge(inp, packed, usage_after)
    assert found["score_max_err_vs_float64"] > 10 * SCORE_TOL
    assert found["infeasible_choices"] == 0  # the rows are still right


# ------------------------------------------------- the new counters
def _run(stats):
    return {"stats": stats, "trace_stats": stats, "ops": [], "device": None}


def _metric(name, run):
    """The metric as the harness reads it: its file's reader on its
    file's arguments."""
    spec = _json("benchmark", "layer_metrics", name + ".json")
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(run, **spec["args"])


def test_the_new_counters_read_through_their_metric_files():
    # Until ISSUE 38 freed the last place of the per-layer list these three
    # quantities were read by hand with the readers that stood; they are
    # metrics with files of their own now, and read here through those.
    steps, pad, rows = ("replay_steps_per_window.storm",
                        "replay_pad_share.storm", "rows_per_plan.storm")
    # A window of 32 jobs of 1,000; a window of 30 jobs of 50 padded to 32.
    c1m = _run({"windows": 2, "launch_steps": 65536,
                "launch_placements": 64000, "plan_rows": 64000,
                "plans_columnar": 60, "plans_objects": 4})
    svc = _run({"windows": 2, "launch_steps": 4096,
                "launch_placements": 3000, "plan_rows": 3000,
                "plans_columnar": 60, "plans_objects": 0})
    assert _metric(steps, c1m) == 32768.0
    assert _metric(steps, svc) == 2048.0
    assert _metric(pad, c1m) == pytest.approx(2.34375)
    assert _metric(pad, svc) == pytest.approx(26.7578125)
    assert _metric(rows, c1m) == 1000.0
    assert _metric(rows, svc) == 50.0
    # A rehearsal launches nothing: 0 steps a window, none of them padding,
    # and a number all the same; before any plan, 0 rows a plan.
    host = _run({"windows": 3, "launch_steps": 0, "launch_placements": 0,
                 "plan_rows": 0, "plans_columnar": 0, "plans_objects": 0})
    assert _metric(steps, host) == 0.0
    assert _metric(pad, host) == 0.0
    assert _metric(rows, host) == 0.0
    # A program from before PR 33 lacks the keys: nothing to read, and no
    # error.
    parent = _run({"windows": 3, "launches": 3, "plans_columnar": 90,
                   "plans_objects": 0})
    assert _metric(steps, parent) is None
    assert _metric(pad, parent) is None
    assert _metric(rows, parent) is None
    # The readers that stood give the same on the same stats.
    assert worker_stats_opt.read(c1m, "launch_steps", per="windows") \
        == _metric(steps, c1m)
    assert 100 - worker_stats_zero.read(
        svc, "launch_placements", per="launch_steps", scale=100.0) \
        == pytest.approx(_metric(pad, svc))


# ------------------------------------------------------- BENCHMARK.json
def declared(bench):
    """What the cell and its metrics have to be in a BENCHMARK.json: held
    on the 62 per-layer entries that stood at PR 38, and silent about what
    a later PR appends behind them (test_benchmark_list_grows.py runs this
    on grown copies)."""
    (conf,) = [c for c in bench["configs"] if c["name"] == "c1m-5k"]
    assert conf["source"] == CONFIG["source"] and "c1m" in conf["source"]
    (cell,) = [w for w in bench["workloads"] if w["config"] == "c1m-5k"]
    assert cell == {"name": CELL, "config": "c1m-5k", "traffic": "fill",
                    "chips": 1, "why": cell["why"]}
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e >= {"placed_per_s", "setup_s"}
    at_pr38 = {m["name"]: m for m in bench["per_layer"][:62]}
    assert len(at_pr38) == 62
    # Of the entries that stood at PR 38, every .storm metric lists the
    # cell and no other does: PR 33 gave it all but seven, ISSUE 38 those
    # (the five pinned, the two that read the device's timeline inside the
    # window) and nine new ones. A later entry lists the cells in which
    # its reader finds something to read, this one or not.
    for name, metric in at_pr38.items():
        assert (CELL in metric["workloads"]) == name.endswith(".storm"), name
    for name in PINNED + IN_WINDOW_TRACE:
        assert at_pr38[name]["workloads"][:2] == ["svc-10k.storm",
                                                  "dc-50k.storm"]
    # What it reports from the device's timeline are, at the least, the
    # four shares and times every storm cell reports, read from a trace
    # that the guard's approach starts.
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m["workloads"]
            and m["source"] == "device_trace"} >= set(
        IN_WINDOW_TRACE + ["device_idle.dispatch.storm",
                           "device_idle.planwait.storm"])
    assert "kernel_ms.per_1k_placements.storm" not in at_pr38


def test_the_cell_and_its_metrics_are_declared_as_the_issue_says():
    declared(BENCH)
