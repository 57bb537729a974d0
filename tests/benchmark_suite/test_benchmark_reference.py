"""The plain recomputation that decides `correct` (benchmark/reference):
it names the check a bad state breaks, and it passes every legal execution
whatever path placed it."""

import inspect
import json
import os
import random
import types

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.deploy.dev_agent import build_fleet, seeded_uuid
from benchmark.reference import guarantees
from nomad_tpu.structs import Allocation, Evaluation, Job, Resources, from_dict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


class State:
    """A small committed state built by hand: 128 nodes of the svc-10k
    fleet, service jobs of 50 and one system job, every allocation where
    the guarantees want it."""

    def __init__(self):
        rng = random.Random(5)
        svc, sys_ = _config("svc-10k"), _config("sys-10k")
        self.nodes = build_fleet(svc["fleet"], 128, rng)
        self.row_of = {n.ID: i for i, n in enumerate(self.nodes)}
        self.jobs, self.evals, self.allocs, self.acknowledged = [], [], [], []
        self.rng = rng
        for _ in range(3):
            self.add_service(svc["jobs"]["service-50"])
        self.add_system(sys_["jobs"]["system-all"])

    def _job(self, template, name):
        job = from_dict(Job, template)
        job.ID, job.Name = seeded_uuid(self.rng), name
        ev = Evaluation(ID=seeded_uuid(self.rng), JobID=job.ID,
                        Type=job.Type, Status="complete")
        self.jobs.append(job)
        self.evals.append(ev)
        self.acknowledged.append((job.ID, ev.ID, name))
        return job, ev

    def _alloc(self, job, eval_id, node, name):
        ask = job.TaskGroups[0].Tasks[0].Resources
        self.allocs.append(Allocation(
            ID=seeded_uuid(self.rng), EvalID=eval_id, Name=name,
            NodeID=node.ID, JobID=job.ID, TaskGroup="web",
            TaskResources={"web": Resources(CPU=ask.CPU,
                                            MemoryMB=ask.MemoryMB,
                                            DiskMB=ask.DiskMB)},
            DesiredStatus="run", ClientStatus="pending"))

    def feasible(self, job):
        return [n for n in self.nodes
                if guarantees.node_satisfies(n, job, job.TaskGroups[0])]

    def add_service(self, template, eval_ids=None):
        job, ev = self._job(template, "service-50")
        nodes = self.feasible(job)
        for i in range(job.TaskGroups[0].Count):
            eid = ev.ID if eval_ids is None else eval_ids[i % len(eval_ids)]
            self._alloc(job, eid, nodes[i % 4], f"{job.Name}.web[{i}]")
        return job

    def add_system(self, template):
        job, ev = self._job(template, "system-all")
        for node in self.feasible(job):
            self._alloc(job, ev.ID, node, f"{job.Name}.web[0]")
        return job

    def reads(self):
        return {"nodes": self.nodes, "jobs": self.jobs, "evals": self.evals,
                "allocs": self.allocs}

    def device_usage(self):
        usage = np.zeros((128, 5), np.float32)
        for n in self.nodes:
            usage[self.row_of[n.ID]] = guarantees.node_reserved(n)
        for a in self.allocs:
            usage[self.row_of[a.NodeID]] += guarantees.alloc_ask(a)
        return usage

    def judge(self, usage=None, undrained=(), platform="tpu"):
        if usage is None:
            usage = self.device_usage()
        return guarantees.judge(self.reads(), self.acknowledged, usage,
                                self.row_of, list(undrained), platform,
                                rehearsal=False)


def _names(verdict):
    return [f["check"] for f in verdict.failures]


def test_a_sound_state_is_correct_and_nothing_failed():
    verdict, failed = State().judge()
    assert verdict.correct, verdict.failures
    assert failed == {}
    # 128 nodes less two racks of two without the driver, less one never ready
    assert verdict.facts["allocations"] == 3 * 50 + (128 - 4 - 1)


def test_an_oversubscribed_node_a_misplaced_allocation_and_a_short_job():
    s = State()
    job = s.jobs[0]
    # Oversubscribed: 200 more of this ask on one node (3,900 MHz / 20).
    node = s.feasible(job)[0]
    extra = s.add_service(_config("svc-10k")["jobs"]["service-50"])
    extra.TaskGroups[0].Count = 250
    for i in range(50, 250):
        s._alloc(extra, s.evals[-1].ID, node, f"{extra.Name}.web[{i}]")
    # Misplaced: an allocation of an x86 job on an arm64 node.
    arm = next(n for n in s.nodes if n.Attributes["arch"] == "arm64")
    moved = next(a for a in s.allocs if a.JobID == job.ID)
    moved.NodeID = arm.ID
    # Short: a job acknowledged complete with 49 of its 50.
    short = s.jobs[1]
    s.allocs.remove(next(a for a in s.allocs if a.JobID == short.ID))
    verdict, failed = s.judge()
    assert not verdict.correct
    assert failed == {}
    assert {"2_capacity", "3_constraints", "4_counts"} <= set(_names(verdict))
    by_check = {f["check"]: f for f in verdict.failures}
    assert by_check["2_capacity"]["ids"] == [node.ID]
    assert by_check["3_constraints"]["ids"] == [moved.ID]
    assert by_check["4_counts"]["ids"] == [f"{short.ID}: 49 live of 50"]
    # A check that counts breaches compares their count with 0.
    assert [verdict.compared[c] for c in ("2_capacity", "3_constraints",
                                          "4_counts")] == [
        {"value": 1.0, "limit": 0.0}] * 3
    assert list(verdict.compared) == [
        "2_capacity", "3_constraints", "4_identity", "4_counts",
        "5_read_back", "6_device_usage", "8_platform"]


@pytest.mark.parametrize("break_it,check", [
    (lambda s: s.allocs.append(s.allocs[0]), "4_identity"),
    (lambda s: setattr(s.allocs[1], "Name", s.allocs[0].Name), "4_identity"),
    (lambda s: s.jobs.pop(0), "5_read_back"),
    (lambda s: s.allocs.remove(next(
        a for a in s.allocs if a.JobID == s.jobs[-1].ID)), "4_counts"),
], ids=["duplicate-id", "duplicate-name", "job-not-read-back",
        "system-job-misses-a-feasible-node"])
def test_each_breach_names_its_check(break_it, check):
    s = State()
    usage = s.device_usage()
    break_it(s)
    verdict, _ = s.judge(usage)
    assert check in _names(verdict)


def test_the_device_usage_table_and_the_platform_are_checked():
    s = State()
    usage = s.device_usage()
    usage[3, 0] += 20.0
    verdict = s.judge(usage)[0]
    assert _names(verdict) == ["6_device_usage"]
    # The number compared stands beside its limit, sound or not.
    assert verdict.compared["6_device_usage"] == {"value": 20.0,
                                                  "limit": 1e-2}
    assert verdict.compared["2_capacity"] == {"value": 0.0, "limit": 0.0}
    late = s.judge(platform="cpu")[0]
    assert _names(late) == ["8_platform"]
    assert late.compared["8_platform"] == {"value": 1.0, "limit": 0.0}
    assert late.compared["6_device_usage"]["value"] == 0.0


def test_an_eval_still_pending_after_the_drain_is_a_failed_operation():
    """Not an incorrect output. The table and the store were then read at
    different moments of a running system, so check 6 is left out."""
    s = State()
    s.evals[0].Status = "pending"
    usage = s.device_usage()
    usage[3, 0] += 20.0
    verdict, failed = s.judge(usage, undrained=[s.evals[0].ID])
    assert verdict.correct, verdict.failures
    assert failed == {s.jobs[0].ID: "eval pending"}
    assert verdict.facts["device_usage_checked"] is False


def test_failed_blocked_and_short_evals_are_failed_operations_not_errors():
    s = State()
    s.evals[0].Status = "failed"
    for a in [a for a in s.allocs if a.JobID == s.jobs[0].ID][:10]:
        s.allocs.remove(a)
    s.evals[1].BlockedEval = "some-follow-up"
    s.allocs.remove(next(a for a in s.allocs if a.JobID == s.jobs[1].ID))
    verdict, failed = s.judge()
    assert verdict.correct, verdict.failures
    assert set(failed) == {s.jobs[0].ID, s.jobs[1].ID}


def test_a_partial_commit_finished_by_a_follow_up_eval_is_correct():
    """What `fallback` > 0 leaves behind: one job's allocations under two
    eval ids, and terminal allocations beside their replacements. Counted
    per job it is right; chip_smoke's per-eval count called it wrong."""
    s = State()
    s.add_service(_config("svc-10k")["jobs"]["service-50"],
                  eval_ids=["first-plan", "exact-path-rerun"])
    job = s.jobs[-1]
    gone = next(a for a in s.allocs if a.JobID == job.ID)
    usage_before = s.device_usage()
    lost = Allocation(**{**gone.__dict__, "ID": seeded_uuid(s.rng),
                         "DesiredStatus": "stop"})
    s.allocs.append(lost)
    verdict, failed = s.judge(usage_before)
    assert verdict.correct, verdict.failures
    assert failed == {}
    assert verdict.facts["terminal_allocations"] == 1


def test_no_counter_path_or_timing_can_enter_the_verdict():
    """`correct` is judged from the store's reads (every replica's, and how
    many replicas the configuration states), the device's table, the drain
    and the platform; worker stats (fallback, host, fast, stale, rebases),
    row choices and latencies are not among its inputs."""
    params = set(inspect.signature(guarantees.judge).parameters)
    assert params == {"reads", "acknowledged", "device_usage", "row_of",
                      "undrained", "platform", "rehearsal", "replica_reads",
                      "replicas"}


# ------------------------------------------------ check 5 on every replica
ONE_REPLICA = ["2_capacity", "3_constraints", "4_identity", "4_counts",
               "5_read_back", "6_device_usage", "8_platform"]


class Replicated:
    """A deployment of three servers as the harness sees it: reads() is
    the leader's store, replica_reads() one copy a replica (None where a
    replica does not answer)."""

    def __init__(self, state, n=3):
        self.state = state
        self.copies = [{k: list(v) for k, v in state.reads().items()}
                       for _ in range(n)]

    def reads(self):
        return self.state.reads()

    def replica_reads(self):
        return self.copies


def _judge_replicated(dep, replicas=3):
    s = dep.state
    reads = dep.reads()
    return guarantees.judge(reads, s.acknowledged, s.device_usage(),
                            s.row_of, [], "tpu", rehearsal=False,
                            replica_reads=bench_run.replica_reads(dep, reads),
                            replicas=replicas)[0]


def test_three_replicas_that_agree_are_correct():
    verdict = _judge_replicated(Replicated(State()))
    assert verdict.correct, verdict.failures
    assert list(verdict.compared) == ONE_REPLICA[:5] + ["5_replicas"] + \
        ONE_REPLICA[5:]
    assert verdict.compared["5_replicas"] == {"value": 0.0, "limit": 0.0}


def test_a_replica_that_misses_an_acknowledged_job_is_named():
    dep = Replicated(State())
    job_id = dep.state.jobs[1].ID
    dep.copies[2]["jobs"] = [j for j in dep.copies[2]["jobs"]
                             if j.ID != job_id]
    verdict = _judge_replicated(dep)
    assert _names(verdict) == ["5_read_back", "5_replicas"]
    by_check = {f["check"]: f for f in verdict.failures}
    assert by_check["5_read_back"]["ids"] == [f"replica 2: {job_id}"]
    assert by_check["5_replicas"]["ids"] == ["replica 2"]
    assert verdict.compared["5_read_back"]["value"] == 1.0
    assert verdict.compared["5_replicas"] == {"value": 1.0, "limit": 0.0}


@pytest.mark.parametrize("silent", ["none", "short"])
def test_a_replica_that_does_not_answer_reads_one(silent):
    dep = Replicated(State())
    if silent == "none":
        dep.copies[1] = None
    else:
        dep.copies.pop()  # two answers where three replicas are stated
    verdict = _judge_replicated(dep)
    assert not verdict.correct
    assert verdict.compared["5_replicas"] == {"value": 1.0, "limit": 0.0}
    where = 1 if silent == "none" else 2
    assert {f["check"]: f["ids"] for f in verdict.failures}[
        "5_replicas"] == [f"replica {where}"]


def test_one_replica_compares_what_it_always_did():
    """A deployment without replica_reads() is judged on its one reads(),
    and its `compared` has the keys and values it had before replicas were
    read: no 5_replicas."""
    s = State()
    plain = types.SimpleNamespace(reads=s.reads)
    assert bench_run.replica_reads(plain, s.reads()) == [s.reads()]
    for sound in (True, False):
        s = State()
        gone = None if sound else s.jobs.pop(0)
        before, _ = s.judge()
        after = _judge_replicated(
            types.SimpleNamespace(state=s, reads=s.reads), replicas=1)
        assert list(after.compared) == ONE_REPLICA
        assert after.compared == before.compared
        assert after.failures == before.failures
    # The job is named as it always was, with no replica in front.
    assert {f["check"]: f["ids"] for f in after.failures}[
        "5_read_back"] == [gone.ID]
