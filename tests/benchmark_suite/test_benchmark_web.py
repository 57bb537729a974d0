"""What the configuration web-10k brings to the yardstick (ISSUE 36),
without starting an agent: the job template against upstream's mock.Job(),
the numbers its file states recomputed from the file, the warm-up against
the programs a window of such jobs can reach (computed, no device), the
rehearsal's ask applied and refused, and the cell's metric lists in
BENCHMARK.json. The ports check on broken states and the served path are
in tests/test_web_shape.py."""

import copy
import json
import os
import random

import pytest

from benchmark.deploy import dev_agent_web
from benchmark.deploy.dev_agent import build_fleet
from benchmark.reference import guarantees
from nomad_tpu import mock
from nomad_tpu.scheduler import kernels
from nomad_tpu.scheduler.stack import HOST_ROW_STEP_BUDGET, _pad_pow2
from nomad_tpu.server.pipelined_worker import _prep_sig
from nomad_tpu.structs import Job, from_dict, to_dict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")
CONFIG = _json("benchmark", "configs", "web-10k.json")
SVC = _json("benchmark", "configs", "svc-10k.json")
TRAFFIC = _json("benchmark", "traffic", "storm-ports.json")
FLEET = CONFIG["fleet"]
CELL = "web-10k.storm"
TEMPLATE = "web-10"
# The 22 per-layer metrics c1m-5k.fill reports (ISSUE 36, item 3).
REPORTED = {name + ".storm" for name in (
    "register_ms", "window_fill", "dispatch_ms", "build_ms",
    "fallback_share", "prep_ms", "drain_fetch_ms", "compiles", "refresh_ms",
    "rebases", "planwait_ms", "plan_apply_ms", "node_ctx_hit_share",
    "launches_per_window", "evals_per_launch", "keys_per_launch",
    "dc_sets_per_window", "launch_ms", "nodectx_ms", "collect_ms",
    "columnar_plan_share", "gc_full_ms")}
# What ISSUE 38 put the cell on: the seven it was held off, and the nine
# .storm metrics for the counters of PRs 33-37.
GAINED = {name + ".storm" for name in (
    "kernel_ms", "device_idle", "stage_wait_ms", "plan_queue_ms",
    "device_idle.dispatch", "device_idle.planwait", "window_collect_share",
    "replay_steps_per_window", "replay_pad_share", "resident_launch_share",
    "rows_per_plan", "netassign_ms", "netidx_builds_per_eval",
    "verify_exact_share", "port_partial_share", "digest_row_folds")}


# ------------------------------------------------------ the job, as published
def test_the_template_is_mock_job_but_for_ids_and_indexes():
    assert list(CONFIG["jobs"]) == [TEMPLATE]
    published = to_dict(mock.job())
    template = CONFIG["jobs"][TEMPLATE]
    assert set(published) - set(template) == {
        "ID", "Name", "CreateIndex", "ModifyIndex", "JobModifyIndex"}
    assert template == {k: published[k] for k in template}
    assert template == dev_agent_web.published_job()
    # What the issue reads out of it.
    assert template["Type"] == "service" and template["Priority"] == 50
    assert template["Datacenters"] == ["dc1"]
    assert template["Constraints"] == [{"LTarget": "${attr.kernel.name}",
                                        "RTarget": "linux", "Operand": "="}]
    (group,) = template["TaskGroups"]
    (task,) = group["Tasks"]
    assert group["Name"] == "web" and group["Count"] == 10
    assert task["Driver"] == "exec"
    assert task["Config"] == {"command": "/bin/date"}
    res = task["Resources"]
    assert (res["CPU"], res["MemoryMB"], res["DiskMB"]) == (500, 256, 150)
    (net,) = res["Networks"]
    assert net["MBits"] == 50 and net["ReservedPorts"] == []
    assert [p["Label"] for p in net["DynamicPorts"]] == ["http", "admin"]
    assert [s["PortLabel"] for s in task["Services"]] == ["http", "admin"]
    assert task["Services"][0]["Checks"][0]["Type"] == "script"
    # The file round-trips through the structs to the same job.
    assert to_dict(from_dict(Job, template)) \
        == {**published, "ID": "", "Name": "", "CreateIndex": 0,
            "ModifyIndex": 0, "JobModifyIndex": 0}


def test_the_node_is_mock_node_and_the_fleet_is_svc_10ks():
    from nomad_tpu.structs import Node

    node = to_dict(mock.node())
    stated = to_dict(from_dict(Node, FLEET["node"]))
    for key in ("Datacenter", "Attributes", "Resources", "Reserved", "Links",
                "Meta", "NodeClass", "Status"):
        assert stated[key] == node[key], key
    extra = {"allocs_per_node", "eligible_nodes", "capacity_allocs",
             "at_seed"}
    assert {k: v for k, v in FLEET.items() if k not in extra} == SVC["fleet"]
    assert CONFIG["server"] == SVC["server"]
    assert CONFIG["layout"] == SVC["layout"]
    assert CONFIG["reduced"] == SVC["reduced"]
    assert sorted(CONFIG["reduced"]) == ["clients", "entry", "servers"]
    # `assumed` names the fleet and nothing of the job.
    assert sorted(CONFIG["assumed"]) == ["fleet_size", "ineligible_racks",
                                         "never_ready"]
    more = dict(CONFIG["guarantees"])
    assert "same port on the same IP" in more.pop("ports")
    assert "MBits" in more.pop("bandwidth")
    assert more == SVC["guarantees"]
    assert more["replicas"] == 1 and more["durability"].startswith("none")


# ------------------------------------------------- the file's numbers
@pytest.mark.parametrize("seed", [FLEET["at_seed"]["seed"], 0, 3000000019])
def test_capacity_guard_and_warm_up_are_what_the_file_states(seed):
    fleet = build_fleet(FLEET, FLEET["nodes"], random.Random(seed))
    job = from_dict(Job, CONFIG["jobs"][TEMPLATE])
    group = job.TaskGroups[0]
    eligible = [n for n in fleet if guarantees.node_satisfies(n, job, group)]
    room = guarantees.capacity_allocs(fleet, job)
    # 7 a node by CPU (3,900 / 500); 31 by memory, 655 by disk, and 19 by
    # bandwidth, which capacity_allocs does not count: here CPU binds.
    assert guarantees.capacity_allocs(eligible[:1], job) \
        == FLEET["allocs_per_node"] == 7 == (4000 - 100) // 500
    assert (8192 - 256) // 256 == 31 and (1000 - 1) // 50 == 19
    assert room == 7 * len(eligible)
    # The arm64 racks are eligible (the job constrains kernel.name alone),
    # the two racks without driver.exec and the never-ready nodes are not:
    # which racks and nodes those are is the seed's, hence a range.
    lo, hi = FLEET["eligible_nodes"]
    assert lo <= len(eligible) <= hi and (lo, hi) == (9676, 9688)
    assert FLEET["capacity_allocs"] == [7 * lo, 7 * hi] == [67732, 67816]
    warm = CONFIG["warmup"]
    window = CONFIG["server"]["scheduler_window"]
    asked = sum(window + extra for extra in warm["window_plus"]) * 10
    assert asked == FLEET["at_seed"]["warmup_allocs"] == 330
    assert "330 allocations" in warm["why"]
    if seed == FLEET["at_seed"]["seed"]:
        assert FLEET["at_seed"] == {
            "seed": seed, "eligible_nodes": len(eligible),
            "capacity_allocs": room,
            "fill_guard_allocs": int(TRAFFIC["fill_guard"] * room),
            "warmup_allocs": asked}
        assert (len(eligible), room) == (9678, 67746)
    # What the window may ask for before the guard ends it: some 60,600.
    left = TRAFFIC["fill_guard"] * room - asked
    assert 60_600 <= left <= 60_710


def test_the_warm_up_reaches_the_one_program_such_a_window_launches():
    warm = CONFIG["warmup"]
    assert warm == {"kind": "window_buckets", "template": TEMPLATE,
                    "window_plus": [1], "why": warm["why"]}
    job = from_dict(Job, CONFIG["jobs"][TEMPLATE])
    # Whether such an eval gets a signature (a shared prepared batch, a
    # fused run) is the program's to decide; the job without its network
    # has one, so what differs is the network alone ...
    plain = copy.deepcopy(job)
    plain.TaskGroups[0].Tasks[0].Resources.Networks = []
    assert _prep_sig(plain, [type("T", (), {"TaskGroup": plain.TaskGroups[0]})
                             ] * 10, False) is not None
    # ... and every launch is one eval of 10 padded to 16 with 16
    # candidates, whatever the window holds.
    assert _pad_pow2(10) == 16 and kernels.keyed_cand_count(10) == 16
    rows = FLEET["table_rows"]
    window = CONFIG["server"]["scheduler_window"]
    # A burst of 33 is a device-mode window (host mode ends at 8 evals at
    # 16,384 rows) and a remainder; a fallback's exact re-run stays on the
    # numpy mirror, which compiles nothing.
    assert 8 * rows * 64 <= HOST_ROW_STEP_BUDGET < 9 * rows * 64
    assert window + warm["window_plus"][0] == 33
    assert rows * 16 <= HOST_ROW_STEP_BUDGET
    for words in ("32 x 16 x 3", "numpy mirror", "no others to reach"):
        assert words in warm["why"]


# ------------------------------------------------------- the rehearsal
def _deployment(config, nodes=None):
    return dev_agent_web.Deployment(config, random.Random(1), nodes=nodes)


def test_the_rehearsals_ask_is_applied_off_the_files_fleet_only():
    reh = CONFIG["rehearsal"]
    assert CONFIG["deploy"] == "dev_agent_web"
    small = _deployment(CONFIG, nodes=reh["nodes"])
    assert not small.full_size
    res = small.make_job(TEMPLATE).TaskGroups[0].Tasks[0].Resources
    assert res.CPU == reh["cpu"] == 2000 and (4000 - 100) // res.CPU == 1
    assert (res.MemoryMB, res.DiskMB, res.Networks[0].MBits) == (256, 150, 50)
    assert len(res.Networks[0].DynamicPorts) == 2
    for words in ("host == fast", "one allocation a node",
                  "tests/test_web_shape.py"):
        assert words in reh["why"]
    # 4,000 nodes are 4,096 rows: the most at which a window of 32 is
    # placed by the numpy mirror.
    assert reh["nodes"] == 4000 and _pad_pow2(4000) == 4096
    assert 32 * 4096 * 64 == HOST_ROW_STEP_BUDGET
    full = _deployment(CONFIG)
    assert full.full_size
    job = full.make_job(TEMPLATE)
    assert job.TaskGroups[0].Tasks[0].Resources.CPU == 500
    want = to_dict(mock.job())
    have = to_dict(job)
    for key in ("ID", "Name", "CreateIndex", "ModifyIndex",
                "JobModifyIndex"):
        del want[key], have[key]
    assert have == want


@pytest.mark.parametrize("change", [
    lambda t: t["TaskGroups"][0].update(Count=50),
    lambda t: t["TaskGroups"][0]["Tasks"][0]["Resources"].update(CPU=20),
    lambda t: t["TaskGroups"][0]["Tasks"][0]["Resources"].update(Networks=[]),
    lambda t: t["TaskGroups"][0]["Tasks"][0].update(Services=[])],
    ids=["another-count", "another-ask", "no-network", "no-services"])
def test_at_full_size_a_job_that_is_not_the_published_one_is_refused(change):
    config = copy.deepcopy(CONFIG)
    change(config["jobs"][TEMPLATE])
    with pytest.raises(RuntimeError, match="not nomad_tpu.mock.job"):
        _deployment(config)
    _deployment(config, nodes=400)  # a rehearsal states nothing


# ------------------------------------------------------- BENCHMARK.json
def test_the_traffic_is_the_issues():
    assert {k: TRAFFIC[k] for k in (
        "name", "generator", "outstanding", "poll_ms", "fill_guard",
        "templates", "extra_checks", "trace_seconds",
        "trace_guard_share")} == {
        "name": "storm-ports", "generator": "closed_loop",
        "outstanding": 256, "poll_ms": 20, "fill_guard": 0.9,
        "templates": {TEMPLATE: 1},
        "extra_checks": ["kernel_mirror", "ports"], "trace_seconds": 2,
        "trace_guard_share": 0.05}
    # The clock ends this window today and the guard after any gain: the
    # file says where the trace starts either way (ISSUE 38).
    for words in ("95 % of the guard's limit", "whichever comes first",
                  "the 28 s mark comes first",
                  "kernel_ms.storm, device_idle.storm"):
        assert words in TRAFFIC["trace_where"]


def declared(bench):
    """What the cell and its metrics have to be in a BENCHMARK.json: what
    PR 36 and ISSUE 38 gave them, at the places they were given, and
    nothing about what a later PR appends behind (a configuration, a cell,
    a per-layer entry, a cell's name on a `workloads` list):
    test_benchmark_list_grows.py runs this on grown copies."""
    (conf,) = [c for c in bench["configs"] if c["name"] == "web-10k"]
    assert conf is bench["configs"][4]  # where PR 36 appended it
    assert conf["source"] == CONFIG["source"]
    assert "mock.go Job() on Node(), as published" in conf["source"]
    assert conf["file"] == "benchmark/configs/web-10k.json"
    assert conf["reduced"] == ["servers", "entry", "clients"]
    (cell,) = [w for w in bench["workloads"] if w["config"] == "web-10k"]
    assert cell is bench["workloads"][5]
    assert cell == {"name": CELL, "config": "web-10k",
                    "traffic": "storm-ports", "chips": 1,
                    "why": cell["why"]}
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e >= {"placed_per_s", "setup_s"}
    # The cell reports at least what it was put on: the 22 of PR 36 and
    # the 16 of ISSUE 38, which are what c1m-5k.fill reports of these.
    mine = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert REPORTED | GAINED <= mine
    assert len(REPORTED) == 22 and len(REPORTED | GAINED) == 38
    other = {m["name"] for m in bench["per_layer"]
             if "c1m-5k.fill" in m["workloads"]}
    assert REPORTED | GAINED <= other
    # Appended to each list, behind the cells that stood before it (a
    # later cell's name comes behind this one). What it reports from the
    # device's timeline is read from a trace that starts at the clock's
    # mark or at the guard's approach, whichever comes first (the traffic
    # file says so).
    before = {w["name"] for w in bench["workloads"][:5]}
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"].index(CELL) == len(
                before & set(m["workloads"])), m["name"]
    # PR 36 added no metric for its counters (the last place of the list
    # was pinned); ISSUE 38 freed it and appended them behind the entry
    # that held it.
    assert bench["per_layer"][51]["name"] == "window_collect_share.storm"
    assert [m["name"] for m in bench["per_layer"][52:62]] == [
        "replay_steps_per_window.storm", "replay_pad_share.storm",
        "resident_launch_share.storm", "rows_per_plan.storm",
        "netassign_ms.storm", "netidx_builds_per_eval.storm",
        "verify_exact_share.storm", "port_partial_share.storm",
        "digest_row_folds.storm", "digest_row_folds.rollout"]
    assert len(bench["per_layer"]) >= 62


def test_the_cell_and_its_metrics_are_declared_as_the_issue_says():
    declared(BENCH)
