"""The plain recomputation that decides `correct`: checks 2-6 of the
configuration's guarantees, from the state store's public reads alone.

It shares nothing with the tensor path: plain Python over nodes, jobs,
evals and allocations. It asks only what holds in EVERY execution the
design allows. Which path placed an eval (host mirror, device, exact
fallback), which rows were chosen, how often a plan was rebased or
redelivered, and every latency are per-layer metrics and never enter here.
An eval that ended failed, blocked or short of its count is a failed
operation (counted by the caller), not an incorrect output; the caller
passes those jobs in `failed_jobs` and their counts are not demanded.

Copied from chip_smoke.check_guarantees (PR 21) and changed: counts are per
job and not per eval id (a partial commit finished by a follow-up eval is
legal), terminal allocations are legal and skipped, system jobs are held to
one allocation on every feasible node, and a failure names its check and
the ids that show it."""

from __future__ import annotations

import operator

MAX_IDS = 8
OPERANDS = {"=": operator.eq, "==": operator.eq, "is": operator.eq,
            "!=": operator.ne, "not": operator.ne}


def alloc_ask(alloc):
    """[cpu, memory, disk, iops, mbits] an allocation asks for."""
    parts = ([alloc.Resources] if alloc.Resources is not None
             else list(alloc.TaskResources.values()))
    return [sum(r.CPU for r in parts), sum(r.MemoryMB for r in parts),
            sum(r.DiskMB for r in parts), sum(r.IOPS for r in parts),
            sum(n.MBits for r in parts for n in r.Networks)]


def group_ask(group):
    parts = [t.Resources for t in group.Tasks]
    return [sum(r.CPU for r in parts), sum(r.MemoryMB for r in parts),
            sum(r.DiskMB for r in parts)]


def node_reserved(node):
    rsv = node.Reserved
    if rsv is None:
        return [0, 0, 0, 0, 0]
    return [rsv.CPU, rsv.MemoryMB, rsv.DiskMB, rsv.IOPS,
            sum(n.MBits for n in rsv.Networks)]


def allocatable(node):
    """[cpu, memory, disk] of a node less what it reserves."""
    res, rsv = node.Resources, node_reserved(node)
    return [res.CPU - rsv[0], res.MemoryMB - rsv[1], res.DiskMB - rsv[2]]


def resolve(node, target):
    """The value of a constraint's ${...} target on a node, or None."""
    if not (target.startswith("${") and target.endswith("}")):
        return target
    name = target[2:-1]
    if name.startswith("attr."):
        return node.Attributes.get(name[len("attr."):])
    if name.startswith("meta."):
        return node.Meta.get(name[len("meta."):])
    plain = {"node.datacenter": node.Datacenter, "node.unique.id": node.ID,
             "node.unique.name": node.Name, "node.class": node.NodeClass}
    if name in plain:
        return plain[name]
    raise ValueError(f"the recomputation does not know target {target!r}")


def node_satisfies(node, job, group):
    """Plain evaluation of check 3 for one node and one task group."""
    if node.Status != "ready" or node.Drain:
        return False
    if node.Datacenter not in job.Datacenters:
        return False
    constraints = list(job.Constraints) + list(group.Constraints)
    for task in group.Tasks:
        constraints += list(task.Constraints)
        if node.Attributes.get(f"driver.{task.Driver}") not in ("1", "true"):
            return False
    for c in constraints:
        test = OPERANDS.get(c.Operand)
        if test is None:
            raise ValueError("the recomputation does not know operand "
                             f"{c.Operand!r}")
        left, right = resolve(node, c.LTarget), resolve(node, c.RTarget)
        if left is None or right is None or not test(left, right):
            return False
    return True


def signature(job, group):
    """What check 3 reads of a job and one of its groups: two jobs of one
    signature are satisfied by the same nodes."""
    constraints = list(job.Constraints) + list(group.Constraints)
    for task in group.Tasks:
        constraints += list(task.Constraints)
    return (tuple(job.Datacenters),
            tuple((c.LTarget, c.Operand, c.RTarget) for c in constraints),
            tuple(task.Driver for task in group.Tasks))


class Feasible:
    """Node ids that satisfy a job's group, evaluated once per signature."""

    def __init__(self, nodes):
        self.nodes = list(nodes)
        self._sets = {}

    def __call__(self, job, group):
        sig = signature(job, group)
        ids = self._sets.get(sig)
        if ids is None:
            ids = self._sets[sig] = frozenset(
                n.ID for n in self.nodes if node_satisfies(n, job, group))
        return ids


def capacity_allocs(nodes, job):
    """How many allocations of the job's (first) group the eligible nodes
    hold when empty: the fill guard's denominator."""
    group = job.TaskGroups[0]
    ask = group_ask(group)
    total = 0
    for node in nodes:
        if node_satisfies(node, job, group):
            room = allocatable(node)
            total += min(room[d] // ask[d] for d in range(3) if ask[d] > 0)
    return total


class Verdict:
    """Named failures with the ids that show them, counted facts, and
    every number compared beside its limit (`compared`, by check, in the
    order judged: what a run prints as its last lines and under the result
    line's last key). A check that counts breaches compares the count of
    the ids that show them (1 where it names none) with 0; a check with a
    tolerance hands in its `value` and `limit`."""

    def __init__(self):
        self.failures = []
        self.facts = {}
        self.compared = {}

    def require(self, check, ok, detail, ids=(), value=None, limit=0.0):
        ids = list(ids)
        if value is None:
            value = len(ids) or (0 if ok else 1)
        self.compared[check] = {"value": float(value), "limit": float(limit)}
        if not ok:
            self.failures.append({"check": check, "detail": detail,
                                  "ids": ids[:MAX_IDS]})

    @property
    def correct(self):
        return not self.failures


def failed_operations(reads, acknowledged):
    """Job ids whose operation failed, by the program's own account: the
    job's register eval is missing or not complete, or, for a service or
    batch job, says it could not place everything (a blocked follow-up or
    failed task groups). A system job's eval lists the infeasible nodes it
    skipped under FailedTGAllocs as a matter of course, so there only the
    status counts and check 4 judges the rest."""
    by_id = {e.ID: e for e in reads["evals"]}
    system = {j.ID for j in reads["jobs"] if j.Type == "system"}
    failed = {}
    for job_id, eval_id, _ in acknowledged:
        ev = by_id.get(eval_id)
        if ev is None:
            failed[job_id] = "eval missing from the store"
        elif ev.Status != "complete":
            failed[job_id] = f"eval {ev.Status}"
        elif job_id not in system and (ev.BlockedEval or ev.FailedTGAllocs):
            failed[job_id] = "eval complete but short: blocked follow-up"
    return failed


def check(reads, acknowledged, failed_jobs, device_usage, row_of,
          replica_reads=None, replicas=1):
    """Checks 2-6. reads: {"nodes", "jobs", "evals", "allocs"} lists from
    the store; acknowledged: (job_id, eval_id, template) of every job the
    server acknowledged; failed_jobs: job ids of failed operations.
    replica_reads: one such reads (or None for a replica that did not
    answer) per replica the deployment runs, [reads] where it runs one;
    replicas: how many the configuration states. Check 5 is held on each."""
    import numpy as np

    v = Verdict()
    nodes = {n.ID: n for n in reads["nodes"]}
    jobs = {j.ID: j for j in reads["jobs"]}
    allocs = reads["allocs"]
    used = {nid: [0.0] * 5 for nid in nodes}
    live_by_job = {}
    seen_ids, dup_ids, seen_names, dup_names = set(), [], set(), []
    unknown_node, unknown_job, misplaced, terminal = [], [], [], 0
    feasible = Feasible(nodes.values())
    feasible_for = {}  # (job id, group name) -> node ids that satisfy it
    for a in allocs:
        if a.ID in seen_ids:
            dup_ids.append(a.ID)
        seen_ids.add(a.ID)
        if a.terminal_status():
            terminal += 1
            continue
        node, job = nodes.get(a.NodeID), jobs.get(a.JobID)
        # A system job's allocations share one name, one to a node.
        name = (a.JobID, a.Name, a.NodeID if job is not None
                and job.Type == "system" else None)
        if name in seen_names:
            dup_names.append(a.ID)
        seen_names.add(name)
        if node is None:
            unknown_node.append(a.ID)
            continue
        if job is None:
            unknown_job.append(a.ID)
            continue
        key = (a.JobID, a.TaskGroup)
        ok_nodes = feasible_for.get(key)
        if ok_nodes is None:
            group = next(g for g in job.TaskGroups if g.Name == a.TaskGroup)
            ok_nodes = feasible_for[key] = feasible(job, group)
        if a.NodeID not in ok_nodes:
            misplaced.append(a.ID)
        u = used[a.NodeID]
        for d, ask in enumerate(alloc_ask(a)):
            u[d] += ask
        live_by_job.setdefault(a.JobID, []).append(a.NodeID)

    # 2. capacity, and 6. the device's usage table.
    over = []
    want = np.zeros_like(device_usage)
    free = {}
    for nid, node in nodes.items():
        rsv, u = node_reserved(node), used[nid]
        free[nid] = [room - u[d] for d, room in enumerate(allocatable(node))]
        if min(free[nid]) < 0:
            over.append(nid)
        row = row_of.get(nid)
        if row is not None:
            want[row] = [rsv[d] + u[d] for d in range(5)]
    usage_err = float(np.max(np.abs(device_usage - want), initial=0.0))
    v.require("2_capacity", not over,
              f"{len(over)} nodes hold more than their resources less "
              "reserved", over)
    v.require("3_constraints", not (misplaced or unknown_node or unknown_job),
              f"{len(misplaced)} live allocations on nodes that fail the "
              f"job's constraints, {len(unknown_node)} on unknown nodes, "
              f"{len(unknown_job)} of unknown jobs",
              misplaced + unknown_node + unknown_job)
    v.require("4_identity", not (dup_ids or dup_names),
              f"{len(dup_ids)} allocation ids and {len(dup_names)} "
              "(JobID, Name) pairs appear twice", dup_ids + dup_names)

    # 4. counts per job.
    wrong_count = []
    for job_id, _, _ in acknowledged:
        job = jobs.get(job_id)
        if job is None or job_id in failed_jobs:  # a missing job is 5's
            continue
        on = live_by_job.get(job_id, [])
        if job.Type == "system":
            group = job.TaskGroups[0]
            ask = group_ask(group)
            want_on, have = feasible(job, group), set(on)
            # A feasible node without one is right only where the ask no
            # longer fits (usage only grows under these mixes).
            wrong_count += [f"{job_id}: none on {nid}"
                            for nid in want_on - have
                            if all(free[nid][d] >= ask[d] for d in range(3))]
            wrong_count += [f"{job_id}: one on infeasible {nid}"
                            for nid in have - want_on]
            if len(on) != len(have):
                wrong_count.append(f"{job_id}: two on one node")
        else:
            want_n = sum(g.Count for g in job.TaskGroups)
            if len(on) != want_n:
                wrong_count.append(f"{job_id}: {len(on)} live of {want_n}")
    v.require("4_counts", not wrong_count,
              f"{len(wrong_count)} acknowledged, unfailed jobs without "
              "exactly their count of live allocations", wrong_count)
    read_back(v, acknowledged,
              [reads] if replica_reads is None else replica_reads, replicas)
    v.require("6_device_usage", usage_err <= 1e-2,
              f"largest |device - recomputed| usage = {usage_err}",
              value=usage_err, limit=1e-2)
    v.facts = {"nodes": len(nodes), "jobs": len(jobs),
               "allocations": len(allocs), "terminal_allocations": terminal,
               "acknowledged_jobs": len(acknowledged),
               "nodes_holding_allocations":
                   sum(1 for u in used.values() if u[0] > 0),
               "device_usage_max_abs_err": usage_err}
    return v


def read_back(v, acknowledged, replica_reads, replicas):
    """5. Every acknowledged job is read back, from every replica the
    configuration states. With one replica the failure names the jobs, as
    it always did. With more it names each as "replica <i>: <job id>", a
    replica with no reads as "replica <i>: no answer", and `5_replicas`
    compares the count of replicas that did not answer or miss a job with
    0."""
    acked = [job_id for job_id, _, _ in acknowledged]
    missing, bad = [], []
    for i in range(max(replicas, len(replica_reads))):
        r = replica_reads[i] if i < len(replica_reads) else None
        if r is None:
            missing.append(f"replica {i}: no answer")
            bad.append(f"replica {i}")
            continue
        held = {j.ID for j in r["jobs"]}
        gone = [job_id for job_id in acked if job_id not in held]
        missing += gone if replicas == 1 else [f"replica {i}: {job_id}"
                                               for job_id in gone]
        if gone:
            bad.append(f"replica {i}")
    where = "the store" if replicas == 1 else f"{len(bad)} of {replicas} " \
        "replicas' stores"
    v.require("5_read_back", not missing,
              f"{len(missing)} acknowledged jobs are not in {where}",
              missing)
    if replicas > 1:
        v.require("5_replicas", not bad,
                  f"{len(bad)} of the {replicas} replicas stated did not "
                  "answer or miss an acknowledged job", bad)


def judge(reads, acknowledged, device_usage, row_of, undrained, platform,
          rehearsal, replica_reads=None, replicas=1):
    """Everything `correct` is made of but the cell's extra checks: the
    recomputation (2-6) after the drain (1) and the platform (8). It is
    handed no counter, stage timer, path or latency, so none can enter;
    check 5 reads every replica's reads (`check`).
    Evals still pending when the drain gave up are failed operations, not
    incorrect outputs; but the store and the device's table were then read
    at different moments of a running system, so check 6 is left out and
    the verdict's facts say so. Returns (verdict, failed operations by job
    id)."""
    failed = failed_operations(reads, acknowledged)
    if undrained:
        device_usage = device_usage * 0
        row_of = {}
    verdict = check(reads, acknowledged, failed, device_usage, row_of,
                    replica_reads, replicas)
    verdict.facts["undrained_evals"] = list(undrained)[:MAX_IDS]
    verdict.facts["device_usage_checked"] = not undrained
    verdict.require("8_platform", rehearsal or platform == "tpu",
                    f"ran on {platform!r} and not as a rehearsal")
    return verdict, failed
