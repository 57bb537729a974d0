"""The churn cell's files: configs/svc-10k-churn.json against the file it
copies, the rehearsal's standing set, the cell's entries in
BENCHMARK.json, the four metrics it adds on made-up numbers and on the
parent's side, and check 11 (reference/churn.py) on states broken in each
way it, or the checks it leans on, must name."""

import copy
import importlib
import inspect
import json
import os
import random
import types
from collections import deque

import pytest

from benchmark import cells
from benchmark.deploy import dev_agent_churn
from benchmark.deploy.dev_agent import build_fleet
from benchmark.generators import closed_loop
from benchmark.reference import churn as churn_check
from benchmark.reference import guarantees
from nomad_tpu.structs import Job, from_dict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONF = "svc-10k.churn", "svc-10k-churn"


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")
CONFIG = _json("benchmark", "configs", "svc-10k-churn.json")
SVC = _json("benchmark", "configs", "svc-10k.json")
NEW = {  # name: (unit, better, source, layer)
    "slow_ms.churn": ("ms", "lower", "program_span",
                      "Window worker: server/pipelined_worker.py"),
    "stop_evals_per_window.churn": (
        "evals", "higher", "program_counter",
        "Window worker: server/pipelined_worker.py"),
    "stop_promote_share.churn": (
        "%", "lower", "program_counter",
        "Plan apply: server/plan_apply.py, fsm.py, state/"),
    "dereg_ms.churn": ("ms", "lower", "program_span",
                       "Entry: server.job_register"),
}


# ------------------------------------------------------ the configuration
def test_the_shared_fields_are_svc_10ks_letter_for_letter():
    for key in ("layout", "fleet", "server", "jobs", "warmup", "reduced",
                "rehearsal"):
        assert CONFIG[key] == SVC[key], key
    assert {k: v for k, v in CONFIG["guarantees"].items()
            if k != "stops"} == SVC["guarantees"]
    assert "stops" in CONFIG["guarantees"]
    assert SVC["assumed"].items() <= CONFIG["assumed"].items()
    assert {"standing_set", "ratio", "lag"} <= set(CONFIG["assumed"])
    for key in ("standing_set", "ratio", "lag"):
        assert "not a published number" in CONFIG["assumed"][key]
    assert CONFIG["deploy"] == "dev_agent_churn"
    assert set(CONFIG) - set(SVC) == {"standing_jobs"}


def test_the_standing_set_is_2000_services_scaled_by_the_fleet():
    spec = CONFIG["standing_jobs"]
    assert spec == {"template": "service-50", "count": 2000,
                    "outstanding": 256}
    nodes = CONFIG["fleet"]["nodes"]
    assert dev_agent_churn.standing_count(CONFIG, nodes) == 2000
    # A rehearsal's fleet stands its share of the set, but never fewer
    # jobs than evals may be in flight: 256 of 400 nodes, not 80, so no
    # stop is owed behind a pending head.
    assert CONFIG["rehearsal"] == {"nodes": 400}
    assert dev_agent_churn.standing_count(CONFIG, 400) == 256
    assert dev_agent_churn.standing_count(CONFIG, 200) == 256
    assert dev_agent_churn.standing_count(CONFIG, 6000) == 1200
    # The deployment's bound on evals in flight is the mix's outstanding.
    traffic = _json("benchmark", "traffic", "churn.json")
    assert spec["outstanding"] == traffic["outstanding"] == 256


def test_the_standing_set_holds_five_to_six_percent_of_eligible_capacity():
    fleet = build_fleet(CONFIG["fleet"], CONFIG["fleet"]["nodes"],
                        random.Random(2 ** 31 + 41))
    job = from_dict(Job, CONFIG["jobs"]["service-50"])
    room = guarantees.capacity_allocs(fleet, job)
    standing = CONFIG["standing_jobs"]["count"] * 50
    assert 0.05 <= standing / room <= 0.06
    ready = sum(1 for n in fleet if n.Status == "ready")
    assert 9.5 <= standing / ready <= 10.5


class _Pairs:
    """What Deployment._stop_owed reads of a running deployment: the live
    FIFO, the stops owed, each register eval's statuses in the order they
    are read (the last one then stays)."""

    def __init__(self, statuses, owed):
        self.reads = {e: list(seq) for e, seq in statuses.items()}
        self.live = deque((f"job-{e}", e) for e in statuses)
        self.owed, self.unstopped, self.stops = owed, [], []
        self.head_waits = 0

    def eval_status(self, eval_id):
        seq = self.reads[eval_id]
        return seq.pop(0) if len(seq) > 1 else seq[0]

    def _wait_for_room(self):
        pass

    def deregister(self, job_id):
        self.stops.append(job_id)


def test_a_pending_head_holds_the_pair_until_it_is_complete(monkeypatch):
    """The pair stops the oldest live job once it is seen complete: a head
    that a worker still holds is waited for, so no stop is owed behind it
    and the registration goes after the stop, FIFO all the same."""
    monkeypatch.setattr(dev_agent_churn, "POLL_S", 0.0)
    dep = _Pairs({"e0": ["pending", "pending", "complete"],
                  "e1": ["failed"], "e2": ["complete"], "e3": ["pending"]},
                 owed=2)
    dev_agent_churn.Deployment._stop_owed(dep)
    assert dep.stops == ["job-e0", "job-e2"] and dep.owed == 0
    assert dep.unstopped == ["job-e1"] and dep.head_waits == 1
    assert list(dep.live) == [("job-e3", "e3")]
    # A head that stays pending past ROOM_TIMEOUT_S leaves its stop owed.
    monkeypatch.setattr(dev_agent_churn, "ROOM_TIMEOUT_S", 0.0)
    dep.owed = 1
    dev_agent_churn.Deployment._stop_owed(dep)
    assert dep.owed == 1 and dep.head_waits == 2
    assert dep.stops == ["job-e0", "job-e2"]


# ----------------------------------------------------- BENCHMARK.json
def declared(bench):
    """The configuration and the cell where they were appended, the four
    metrics at 96-99, and nothing about what a later PR appends behind them
    (a cell, a configuration, a metric that lists this cell)."""
    names = [c["name"] for c in bench["configs"]]
    conf = bench["configs"][names.index(CONF)]
    assert names.index(CONF) == 5
    assert conf == {"name": CONF, "source": CONFIG["source"],
                    "file": "benchmark/configs/svc-10k-churn.json",
                    "reduced": ["servers", "entry", "clients"],
                    "why": conf["why"]}
    cell_names = [w["name"] for w in bench["workloads"]]
    assert cell_names.index(CELL) == 6
    assert bench["workloads"][6] == {"name": CELL, "config": CONF,
                                     "traffic": "churn", "chips": 1,
                                     "why": bench["workloads"][6]["why"]}
    before = set(cell_names[:6])
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"placed_per_s", "setup_s"}
    mine = [m for m in bench["per_layer"] if CELL in m["workloads"]]
    at = {m["name"]: i for i, m in enumerate(bench["per_layer"])}
    for m in mine:
        # Behind the cells that stood before it; a metric of the storm
        # family, one of the four it brings, or one appended behind those.
        assert m["workloads"].index(CELL) == len(before & set(m["workloads"]))
        assert (m["name"].endswith(".storm") or m["name"] in NEW
                or at[m["name"]] > max(at[n] for n in NEW)), m["name"]
        assert m["moves"] == "placed_per_s"
    # register() is the registration alone (the stop is sent before the
    # span opens), so the benchmark's span round it reports here too.
    assert "register_ms.storm" in {m["name"] for m in mine}
    assert [at[n] for n in NEW] == list(range(96, 100))
    for name, (unit, better, source, layer) in NEW.items():
        entry = bench["per_layer"][at[name]]
        assert entry == {"name": name, "unit": unit, "better": better,
                         "source": source, "layer": layer,
                         "moves": "placed_per_s",
                         "workloads": entry["workloads"]}
        assert entry["workloads"][0] == CELL
        assert layer in {m["layer"] for m in bench["per_layer"][:96]}


def test_the_cell_and_its_metrics_are_declared():
    declared(BENCH)
    traffic = _json("benchmark", "traffic", "churn.json")
    # The cell's closed loop, as every storm cell's: the pairing and the
    # bound on registrations and stops together are the deployment's.
    assert traffic["generator"] == "closed_loop"
    assert traffic["outstanding"] == 256 and traffic["poll_ms"] == 20
    assert dev_agent_churn.POLL_S == traffic["poll_ms"] / 1e3
    assert traffic["extra_checks"] == ["kernel_mirror", "churn"]
    assert "fill_guard" not in traffic


def test_the_stop_batch_share_is_appended_behind_the_four():
    """The metric the stop batch's counter waited for: the entry behind the
    last, on this cell alone, reading stop_batched over stop_evals."""
    entry = BENCH["per_layer"][100]
    assert entry == {"name": "stop_batch_share.churn", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "Window worker: server/pipelined_worker.py",
                     "moves": "placed_per_s", "workloads": [CELL]}
    spec = _json("benchmark", "layer_metrics", "stop_batch_share.churn.json")
    assert (spec["reader"], spec["args"]) == ("worker_stats_opt", {
        "num": "stop_batched", "per": "stop_evals", "scale": 100.0})
    # Every stop batched reads 100; a window with no stop reads nothing.
    assert _read("stop_batch_share.churn", {
        "stats": {**STATS, "stop_batched": 28}}) == pytest.approx(100.0)
    assert _read("stop_batch_share.churn", {
        "stats": {**STATS, "stop_evals": 0, "stop_batched": 0}}) is None


def _read(name, run):
    spec = _json("benchmark", "layer_metrics", name + ".json")
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    accepted = set(inspect.signature(reader.read).parameters) - {"run"}
    assert set(spec["args"]) <= accepted
    return reader.read(run, **spec["args"])


STATS = {"t_slow_ms": 300.0, "slow": 30, "stop_evals": 28, "windows": 2,
         "fast": 30, "stop_batched": 21}
CHANGE = {"stats": STATS, "ops": [],
          "counters": {"nomad.state.promote": 1400.0,
                       "nomad.plan.stop_rows": 1400.0},
          "samples": {"nomad.server.job_deregister": [0.25, 0.75]}}
# A program from before the cell lacks the stats keys, the counter and the
# sample the cell's metrics read; it counts promotions all the same.
PARENT = {"stats": {k: v for k, v in STATS.items()
                    if k not in ("stop_evals", "stop_batched")},
          "ops": [], "counters": {"nomad.state.promote": 1400.0},
          "samples": {}}


@pytest.mark.parametrize("name,change,parent", [
    ("slow_ms.churn", 10.0, 10.0),
    ("stop_evals_per_window.churn", 14.0, None),
    ("stop_promote_share.churn", 100.0, 0.0),
    ("dereg_ms.churn", 0.5, None),
    ("stop_batch_share.churn", 75.0, None),
])
def test_a_new_metric_reads_its_number_and_nothing_breaks_at_the_parent(
        name, change, parent):
    assert _read(name, CHANGE) == pytest.approx(change)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    cell = cells.Cell(name=CELL, chips=1, config={}, traffic={},
                      benchmark={"per_layer": [entry]})
    line = cells.read_metrics(cell, "per_layer", PARENT)
    if parent is None:
        assert line == {}
    else:
        assert line[name]["value"] == pytest.approx(parent)
    # An untraced run has no sink: the counter metric reads nothing.
    if name == "stop_promote_share.churn":
        assert _read(name, {**CHANGE, "counters": None}) is None


# ------------------------------------------- check 11 on broken states
class StoreView:
    """The three public reads check 11 makes, over copied reads."""

    def __init__(self, reads):
        self.jobs = {j.ID: j for j in reads["jobs"]}
        self.evals = {e.ID: e for e in reads["evals"]}
        self.by_job = {}
        for a in reads["allocs"]:
            self.by_job.setdefault(a.JobID, []).append(a)

    def job_by_id(self, job_id):
        return self.jobs.get(job_id)

    def eval_by_id(self, eval_id):
        return self.evals.get(eval_id)

    def allocs_by_job(self, job_id):
        return list(self.by_job.get(job_id, ()))


@pytest.fixture(scope="module")
def churned():
    """200 nodes, a standing set of 40, a second of the cell's closed loop;
    drained. Returns (reads, stopped, device usage, row_of, acknowledged)."""
    config = copy.deepcopy(CONFIG)
    config["fleet"].update(nodes=200, table_rows=256)
    config["standing_jobs"].update(count=40, outstanding=16)
    config["warmup"] = {"kind": "jobs", "template": "service-50", "count": 1}
    dep = dev_agent_churn.Deployment(config, random.Random(2 ** 31 + 7))
    try:
        dep.start()
        closed_loop.run(dep, {"outstanding": 8, "poll_ms": 5,
                              "templates": {"service-50": 1}},
                        random.Random(5), 1.0)
        assert dep.drain(60.0) == []
        usage, row_of = dep.device_usage()
        return (dep.reads(), list(dep.stopped), usage, row_of,
                list(dep.acknowledged))
    finally:
        dep.shutdown()


def _judge(reads, stopped, usage, row_of, acknowledged):
    verdict = guarantees.check(reads, acknowledged, {}, usage, row_of)
    facts = churn_check.check(
        types.SimpleNamespace(server=types.SimpleNamespace(
            state=StoreView(reads)), stopped=stopped), 0, verdict)
    return verdict, facts


def _copied(churned):
    reads, stopped, usage, row_of, acknowledged = churned
    return ({k: copy.deepcopy(v) for k, v in reads.items()}, stopped,
            usage.copy(), row_of, acknowledged)


def _a_stopped_jobs_allocation_left_live(state):
    reads, stopped = state[0], state[1]
    job_id = stopped[0][0]
    a = next(a for a in reads["allocs"] if a.JobID == job_id)
    a.DesiredStatus, a.ClientStatus = "run", "pending"
    return job_id, {"11_stops", "3_constraints", "2_capacity",
                    "6_device_usage"}


def _a_stopped_job_still_in_the_store(state):
    reads, stopped = state[0], state[1]
    job = copy.deepcopy(reads["jobs"][0])
    job.ID = stopped[1][0]
    reads["jobs"].append(job)
    return job.ID, {"11_stops"}


def _a_deregister_eval_left_pending(state):
    reads, stopped = state[0], state[1]
    ev = next(e for e in reads["evals"] if e.ID == stopped[2][2])
    ev.Status = "pending"
    return stopped[2][0], {"11_stops"}


def _a_usage_row_not_freed(state):
    reads, stopped, usage, row_of = state[:4]
    a = next(a for a in reads["allocs"] if a.JobID == stopped[3][0])
    usage[row_of[a.NodeID]] += guarantees.alloc_ask(a)
    return None, {"6_device_usage"}


BROKEN = [_a_stopped_jobs_allocation_left_live,
          _a_stopped_job_still_in_the_store, _a_deregister_eval_left_pending,
          _a_usage_row_not_freed]


def test_check_11_passes_the_state_it_is_broken_from(churned):
    verdict, facts = _judge(*_copied(churned))
    assert verdict.correct, verdict.failures
    assert verdict.compared["11_stops"] == {"value": 0.0, "limit": 0.0}
    stopped = churned[1]
    assert len(stopped) >= 4
    assert facts == {"stopped_jobs": len(stopped),
                     "stopped_allocations": 50 * len(stopped),
                     "jobs_breaking_it": 0}


@pytest.mark.parametrize("break_it", BROKEN,
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_stop_is_named(churned, break_it):
    state = _copied(churned)
    job_id, checks = break_it(state)
    verdict, _ = _judge(*state)
    assert not verdict.correct
    named = {f["check"]: f for f in verdict.failures}
    assert set(named) <= checks and named, verdict.failures
    if "11_stops" in checks:
        assert named["11_stops"]["ids"] == [job_id]
        assert verdict.compared["11_stops"]["value"] == 1.0
    else:
        assert verdict.compared["6_device_usage"]["value"] > 1e-2


def test_check_11_shares_nothing_with_what_it_checks():
    with open(churn_check.__file__) as f:
        source = f.read()
    imports = [ln for ln in source.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "from benchmark.reference.guarantees import MAX_IDS"]
    assert "nomad_tpu" not in source
