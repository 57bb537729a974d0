#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the served scheduling path still
starts, places and commits on the attached TPU.

Default phase (one chip). BASELINE config 3 (the fleet and job shape of
benchmark/configs/svc-10k.json): 10,000 nodes in 64 computed classes, jobs
of Count=50 with the driver checker and the ${attr.arch} constraint, served
by a dev-mode nomad_tpu.agent.Agent with the configuration users get by
default and the HTTP API up. Two seeded departures make the constraint
checks falsifiable without changing the class count: 6 of the 64 racks are
ineligible (4 are arm64, 2 have no exec driver) and one node in a thousand
registers but never turns ready. Nodes go in through node_register and stay
alive through node_heartbeat, the endpoints client agents call. Then: a few
jobs over HTTP, warm-up, a storm of --evals jobs through job_register, one
lone job on the idle broker.

It fails unless JAX runs on a TPU, every eval completes with exactly Count
allocations, the workers report device-placed evals (fast - host > 0) with no
fallback and no failed eval, the recomputed guarantees hold, and the device
kernel's choices on one fixed window are feasible and best-fit within 1e-3.
The recomputation shares no code with the tensor path: plain Python over the
state store's nodes, jobs, evals and allocations.

--chips 4 runs only the mesh phase: a Server with scheduler_mesh="all" on
four chips against a single-device Server on the first, same fleet, jobs and
tie-break noise, plus one keyed window at --kernel-rows rows on the mesh
against one device.

Every line before the last is one JSON object of observations from this one
run; none is a benchmark result. The last line is the verdict. --allow-cpu
is for rehearsal without a chip: the last line then says "cpu".
"""

import argparse
import itertools
import json
import os
import random
import shutil
import statistics
import subprocess
import sys
import threading
import time
import uuid

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

PER_EVAL = 50
N_RACKS = 64
SCORE_TOL = 1e-3
# Jobs per burst of the warm-up: a full agent window (32) plus a remainder
# that lands, chained, in each smaller (eval-pad, candidate-count) bucket of
# the keyed program: 1 eval; 2 -> (4,128); 3 -> (4,256); 5 -> (8,256);
# 6 -> (8,512); 9 -> (16,512); 11 -> (16,1024); 17 -> (32,1024). The full
# window itself is (32,2048).
WARM_REMAINDERS = (1, 2, 3, 5, 6, 9, 11, 17)


def emit(name, **fields):
    print(json.dumps({"obs": name, "kind": "one observation, not a "
                      "benchmark result", **fields}), flush=True)


class Checks:
    """Named pass/fail results; the verdict is their conjunction."""

    def __init__(self):
        self.failed = []

    def require(self, name, ok, detail=None):
        if not ok:
            self.failed.append(name)
            print(f"CHECK FAILED: {name}: {detail}", file=sys.stderr,
                  flush=True)
        return ok


class CompileLog:
    """Counts the programs JAX builds (in-process jit cache misses) and the
    persistent-cache hits among them, through jax.monitoring."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.hits = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs
            self.names.append((kwargs.get("fun_name", "?"), secs))

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.count, self.seconds, self.hits, len(self.names))

    def since(self, mark=(0, 0.0, 0, 0)):
        slowest = sorted(self.names[mark[3]:], key=lambda x: -x[1])[:8]
        return {"compiles": self.count - mark[0],
                "compile_seconds": self.seconds - mark[1],
                "persistent_cache_hits": self.hits - mark[2],
                "slowest": [[n, s] for n, s in slowest]}


# ------------------------------------------------------------------ set-up
def build_native(checks):
    """Build native/bin from the committed sources; report what loaded."""
    have = shutil.which("g++") is not None and shutil.which("make") is not None
    if have:
        proc = subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                              capture_output=True, text=True, timeout=600)
        checks.require("native_build", proc.returncode == 0,
                       proc.stderr[-2000:])
    from nomad_tpu.client.driver.base import native_executor_path
    from nomad_tpu.raft.native_log import load_liblogstore

    raft_log = "native" if load_liblogstore() is not None else "python"
    executor = "native" if native_executor_path() else "python"
    if have:
        checks.require("native_loaded",
                       raft_log == executor == "native",
                       f"raft_log={raft_log} executor={executor}")
    emit("native_backends", toolchain=have, raft_log=raft_log,
         executor=executor,
         note="dev-mode serving uses neither; a replicated server does")


def report_versions(device):
    import importlib.metadata as md

    import jax
    import jaxlib

    from nomad_tpu.tensor.backend import COMPILE_CACHE_DIR

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    emit("versions", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, device=device,
         compile_cache_dir=env_dir or COMPILE_CACHE_DIR,
         compile_cache_from_env=bool(env_dir))


def measure_host_sync(reps=50):
    """Round trip of a tiny device_put + device_get on the host clock."""
    import jax
    import numpy as np

    x = np.arange(8, dtype=np.float32)
    for _ in range(5):
        jax.device_get(jax.device_put(x))
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.device_get(jax.device_put(x))
        ms.append((time.perf_counter() - t0) * 1e3)
    emit("host_sync_round_trip", reps=reps, median_ms=statistics.median(ms),
         worst_ms=max(ms), best_ms=min(ms),
         what="jax.device_get(jax.device_put(8 x f32))")


def seeded_uuid(rng):
    return str(uuid.UUID(int=rng.getrandbits(128), version=4))


def build_fleet(n, rng):
    """BASELINE config 3's fleet (mock.Node in N_RACKS computed classes)
    with seeded IDs, six ineligible racks and a few nodes that never turn
    ready."""
    from nomad_tpu import mock
    from nomad_tpu.structs import compute_node_class
    from nomad_tpu.structs.structs import NodeStatusInit

    racks = list(range(N_RACKS))
    rng.shuffle(racks)
    arm, no_exec = set(racks[:4]), set(racks[4:6])
    never_ready = set(rng.sample(range(n), max(1, n // 1000)))
    nodes = [mock.node() for _ in range(n)]
    for i, node in enumerate(nodes):
        node.ID = seeded_uuid(rng)
        node.Name = f"node-{i}"
        node.Meta["rack"] = f"r{i % N_RACKS}"
        if i % N_RACKS in arm:
            node.Attributes["arch"] = "arm64"
        if i % N_RACKS in no_exec:
            del node.Attributes["driver.exec"]
        if i in never_ready:
            node.Status = NodeStatusInit
        compute_node_class(node)
    return nodes


def build_job(rng, k):
    """BASELINE config 3's job: Count=PER_EVAL, the exec driver checker of
    the mock task plus an attribute constraint, asks small enough that the
    fleet absorbs the storm, no network."""
    from nomad_tpu import mock
    from nomad_tpu.structs import Constraint

    job = mock.job()
    tg = job.TaskGroups[0]
    tg.Count = PER_EVAL
    job.Constraints.append(
        Constraint(LTarget="${attr.arch}", RTarget="x86", Operand="="))
    task = tg.Tasks[0]
    task.Resources.CPU = 20
    task.Resources.MemoryMB = 32
    task.Resources.DiskMB = 10
    task.Resources.Networks = []
    task.Services = []
    if task.LogConfig is not None:
        # Validation: the task's log storage must fit its disk ask.
        task.LogConfig.MaxFiles = 1
        task.LogConfig.MaxFileSizeMB = 1
    job.ID = seeded_uuid(rng)
    job.Name = f"smoke-{k}"
    return job


class Heartbeater(threading.Thread):
    """Keeps registered nodes alive the way client agents do: one
    node_heartbeat per node, again after a third of the shortest TTL the
    server granted."""

    def __init__(self, server):
        super().__init__(daemon=True, name="smoke-heartbeat")
        self.server = server
        self.ids = []
        self.stop = threading.Event()
        self.sweeps = 0

    def run(self):
        wait = 3.0
        while not self.stop.wait(wait):
            ttls = [self.server.node_heartbeat(nid) for nid in list(self.ids)
                    if not self.stop.is_set()]
            self.sweeps += 1
            wait = max(3.0, min(ttls) / 3) if ttls else 3.0


def wait_evals(state, eval_ids, timeout):
    from nomad_tpu.structs.structs import EvalStatusComplete

    deadline = time.monotonic() + timeout
    pending = set(eval_ids)
    while pending and time.monotonic() < deadline:
        pending = {eid for eid in pending
                   if (e := state.eval_by_id(eid)) is None
                   or e.Status != EvalStatusComplete}
        if pending:
            time.sleep(0.02)
    return pending


def run_storm(server, jobs, timeout=600.0):
    """Register jobs back to back, wait for every eval; (eval_ids, secs)."""
    t0 = time.perf_counter()
    eval_ids = [server.job_register(job)[0] for job in jobs]
    pending = wait_evals(server.state, eval_ids, timeout)
    if pending:
        raise RuntimeError(f"{len(pending)} of {len(jobs)} evals never "
                           "completed")
    return eval_ids, time.perf_counter() - t0


def warm_buckets(server, make_job):
    """Compile every shape bucket the storm can hit, so that the storm
    compiles nothing. A window's bucket depends on how many evals the
    broker held when a worker woke, which a live storm leaves to timing;
    here the workers are parked (the switch leadership changes use) while
    each burst queues. A production server has no such warm-up: its first
    storm pays these compiles under the eval nack timeout. Returns the
    eval ids."""
    done = []
    for rem in WARM_REMAINDERS:
        for w in server.workers:
            w.set_pause(True)
        time.sleep(0.6)  # longer than a parked worker's blocking dequeue
        eval_ids = [server.job_register(make_job())[0]
                    for _ in range(32 + rem)]
        for w in server.workers:
            w.set_pause(False)
        pending = wait_evals(server.state, eval_ids, 900.0)
        if pending:
            raise RuntimeError(f"warm-up burst 32+{rem}: {len(pending)} "
                               "evals never completed")
        done += eval_ids
    server.tindex.nt.warm_device()
    return done


def worker_stats(server):
    total = {}
    for w in server.workers:
        w.quiesce(60.0)
        for k, v in w.stats.items():
            total[k] = total.get(k, 0) + v
    return total


# ------------------------------------------------- the plain recomputation
def alloc_ask(alloc):
    """(cpu, memory, disk, iops, mbits) an allocation asks for."""
    parts = ([alloc.Resources] if alloc.Resources is not None
             else list(alloc.TaskResources.values()))
    return [sum(r.CPU for r in parts), sum(r.MemoryMB for r in parts),
            sum(r.DiskMB for r in parts), sum(r.IOPS for r in parts),
            sum(n.MBits for r in parts for n in r.Networks)]


def node_satisfies(node, job, group):
    """Plain evaluation of the constraints these jobs carry: '=' on
    ${attr.*} targets, the datacenter list and each task's driver."""
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
        if c.Operand != "=" or not (c.LTarget.startswith("${attr.")
                                    and c.LTarget.endswith("}")):
            raise ValueError(f"recomputation does not know constraint {c}")
        if node.Attributes.get(c.LTarget[len("${attr."):-1]) != c.RTarget:
            return False
    return True


def check_guarantees(checks, label, state, row_of, device_usage, eval_ids):
    """(a) capacity, (b) constraints, (c) identity and counts, (d) the
    device's usage table — recomputed from the state store alone."""
    import numpy as np

    nodes = {n.ID: n for n in state.nodes()}
    jobs = {j.ID: j for j in state.jobs()}
    allocs = list(state.allocs())
    used = {nid: [0.0] * 5 for nid in nodes}
    ids, names, per_eval = set(), set(), {}
    bad_constraint = unknown_node = terminal = 0
    for a in allocs:
        ids.add(a.ID)
        names.add((a.JobID, a.Name))
        per_eval[a.EvalID] = per_eval.get(a.EvalID, 0) + 1
        node = nodes.get(a.NodeID)
        if node is None:
            unknown_node += 1
            continue
        if a.terminal_status():
            terminal += 1
            continue
        job = jobs[a.JobID]
        group = next(g for g in job.TaskGroups if g.Name == a.TaskGroup)
        if not node_satisfies(node, job, group):
            bad_constraint += 1
        for d, ask in enumerate(alloc_ask(a)):
            used[a.NodeID][d] += ask

    over = 0
    want = np.zeros_like(device_usage)
    for nid, node in nodes.items():
        res, rsv = node.Resources, node.Reserved
        reserved = [rsv.CPU, rsv.MemoryMB, rsv.DiskMB, rsv.IOPS,
                    sum(n.MBits for n in rsv.Networks)]
        cap = [res.CPU, res.MemoryMB, res.DiskMB]
        if any(used[nid][d] > cap[d] - reserved[d] for d in range(3)):
            over += 1
        want[row_of[nid]] = [reserved[d] + used[nid][d] for d in range(5)]
    usage_err = float(np.max(np.abs(device_usage - want)))
    wrong_count = [eid for eid in eval_ids
                   if per_eval.get(eid, 0) != PER_EVAL]
    failed_evals = [e.ID for e in state.evals() if e.Status == "failed"]

    checks.require(f"{label}:a_capacity", over == 0,
                   f"{over} nodes oversubscribed")
    checks.require(f"{label}:b_constraints",
                   bad_constraint == 0 and unknown_node == 0,
                   f"{bad_constraint} allocations on nodes that fail the "
                   f"job's constraints, {unknown_node} on unknown nodes")
    checks.require(f"{label}:c_identity",
                   len(ids) == len(allocs) == len(names) and not wrong_count
                   and terminal == 0,
                   f"{len(allocs)} allocations, {len(ids)} ids, "
                   f"{len(names)} names, {len(wrong_count)} evals without "
                   f"exactly {PER_EVAL}, {terminal} terminal")
    checks.require(f"{label}:d_device_usage", usage_err <= 1e-2,
                   f"largest |device - recomputed| = {usage_err}")
    checks.require(f"{label}:no_failed_eval", not failed_evals,
                   f"{len(failed_evals)} evals failed (delivery limit)")
    busy = sum(1 for u in used.values() if u[0] > 0)
    emit("recomputed_guarantees", phase=label, nodes=len(nodes),
         allocations=len(allocs), evals_checked=len(eval_ids),
         nodes_holding_allocations=busy, oversubscribed_nodes=over,
         constraint_violations=bad_constraint, duplicate_ids=len(allocs)
         - len(ids), evals_with_wrong_count=len(wrong_count),
         failed_evals=len(failed_evals),
         device_usage_max_abs_err=usage_err)


# --------------------------------------------- one fixed window, by kernel
def window_inputs(rng_seed, n_rows, n_live, n_evals):
    """A fleet of mock-node shape, part filled, and one storm window of
    n_evals x 50 placements of build_job's ask (p_pad 64)."""
    import numpy as np

    rng = np.random.default_rng(rng_seed)
    ask = np.array([20, 32, 10, 0, 0], np.float32)
    reserved = np.array([100, 256, 4096, 0, 1], np.float32)
    capacity = np.zeros((n_rows, 5), np.float32)
    capacity[:n_live] = [4000, 8192, 102400, 150, 1000]
    score_cap = np.ones((n_rows, 2), np.float32)
    score_cap[:n_live] = capacity[:n_live, :2] - reserved[:2]
    usage = np.zeros((n_rows, 5), np.float32)
    usage[:n_live] = reserved + ask * rng.integers(
        0, 190, (n_live, 1)).astype(np.float32)
    mask = np.zeros((1, n_rows), bool)
    mask[0, :n_live] = rng.random(n_live) < 0.9
    p = 64 * n_evals
    valid = np.tile(np.arange(64) < PER_EVAL, n_evals)
    reset = np.zeros(p, bool)
    reset[::64] = True
    return {"capacity": capacity, "score_cap": score_cap, "usage": usage,
            "mask": mask, "ask": ask,
            "noise": (rng.random(n_rows) * 1e-3).astype(np.float32),
            "tg_ids": np.zeros(p, np.int32), "valid": valid, "reset": reset,
            "penalty": np.float32(10.0), "n_valid": PER_EVAL * n_evals}


def run_keyed(inp, mesh, chain=None):
    """The window through kernels.place_batch_keyed; device arrays in,
    PlacementResult out (usage may be a previous window's chain)."""
    import numpy as np

    from nomad_tpu.scheduler import kernels

    n = inp["capacity"].shape[0]
    return kernels.place_batch_keyed(
        mesh, inp["capacity"], inp["score_cap"],
        inp["usage"] if chain is None else chain, inp["mask"],
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
    for e in range(len(inp["valid"]) // 64):
        sl = slice(64 * e, 64 * e + 64)
        res = kernels.place_batch_host(
            inp["capacity"], inp["score_cap"], usage, inp["mask"],
            np.zeros(n, np.int32), np.tile(inp["ask"], (64, 1)),
            inp["tg_ids"][sl], inp["valid"][sl], inp["noise"],
            inp["penalty"], False, np.zeros(n, bool))
        usage = res.usage_after
        out.append(res.packed)
    return np.concatenate(out)


def replay_choices(inp, packed, usage0=None):
    """Follow the device's choices in float64 with the reference's formula
    (20 - 10^freeCpu - 10^freeMem, clamped, minus the anti-affinity penalty,
    plus noise). Returns (infeasible choices, largest gap to the best
    feasible score, largest |device score - recomputed score|, usage)."""
    import numpy as np

    cap = inp["capacity"].astype(np.float64)
    sc_cap = inp["score_cap"].astype(np.float64)
    usage = (inp["usage"] if usage0 is None else usage0).astype(np.float64)
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


def compare_kernel_to_mirror(checks, seed, n_rows, n_live, n_evals):
    import numpy as np

    inp = window_inputs(seed, n_rows, n_live, n_evals)
    t0 = time.perf_counter()
    res = run_keyed(inp, None)
    dev = np.asarray(res.packed)
    t_dev = time.perf_counter() - t0
    t0 = time.perf_counter()
    mir = run_mirror(inp)
    t_mir = time.perf_counter() - t0
    v = inp["valid"]
    same = dev[v, 0] == mir[v, 0]
    both = same & (dev[v, 0] >= 0)
    infeasible, gap, err, usage = replay_choices(inp, dev)
    usage_err = float(np.max(np.abs(np.asarray(res.usage_after) - usage)))
    emit("device_vs_mirror", rows=n_rows, live_rows=n_live, evals=n_evals,
         placements=int(v.sum()), rows_agree=int(same.sum()),
         placed_by_device=int((dev[v, 0] >= 0).sum()),
         max_score_diff_where_rows_agree=float(
             np.max(np.abs(dev[v, 1][both] - mir[v, 1][both]), initial=0.0)),
         device_infeasible_choices=infeasible,
         device_max_gap_to_best_feasible=gap,
         device_score_max_err_vs_float64=err,
         device_usage_after_max_abs_err=usage_err,
         device_seconds_with_compile=t_dev, mirror_seconds=t_mir)
    checks.require("kernel:feasible", infeasible == 0,
                   f"{infeasible} infeasible device choices")
    checks.require("kernel:best_fit", gap <= SCORE_TOL and err <= SCORE_TOL,
                   f"gap to best {gap}, score error {err}")
    checks.require("kernel:usage_after", usage_err <= 1e-2, usage_err)


# ----------------------------------------------------------- one-chip phase
def phase_serve(args, checks, compiles):
    import jax
    import numpy as np

    from nomad_tpu.agent import Agent
    from nomad_tpu.agent.agent import AgentConfig
    from nomad_tpu.api.client import Client

    rng = random.Random(args.seed)
    walls = {}
    measure_host_sync()

    t0 = time.perf_counter()
    agent = Agent(AgentConfig(server_enabled=True, dev_mode=True,
                              http_port=0))
    agent.start()
    server = agent.server
    cfg = server.config
    hb = Heartbeater(server)
    hb.start()
    try:
        emit("server_config", pipelined_scheduling=cfg.pipelined_scheduling,
             host_placement=cfg.host_placement,
             scheduler_impl=cfg.scheduler_impl,
             scheduler_window=cfg.scheduler_window,
             num_schedulers=cfg.num_schedulers,
             eval_nack_timeout=cfg.eval_nack_timeout,
             eval_delivery_limit=cfg.eval_delivery_limit,
             http=f"127.0.0.1:{agent.http.port}")
        walls["agent_start"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        for node in build_fleet(args.nodes, rng):
            server.node_register(node)
            hb.ids.append(node.ID)
        walls["register_nodes"] = time.perf_counter() - t0
        nt = server.tindex.nt
        emit("fleet", nodes=args.nodes, table_rows=nt.n_rows,
             computed_classes=len(nt.class_names),
             ready_rows=int(nt.ready.sum()))

        submitted = []
        job_numbers = itertools.count(1)

        def make_job():
            return build_job(rng, next(job_numbers))

        # A few jobs the way a user submits them: over HTTP, read back
        # over HTTP.
        t0 = time.perf_counter()
        api = Client(f"http://127.0.0.1:{agent.http.port}")
        mark = compiles.mark()
        for _ in range(3):
            job = make_job()
            eval_id, _ = api.jobs.register(job)
            deadline = time.monotonic() + 300
            while api.evaluations.info(eval_id)[0]["Status"] != "complete":
                if time.monotonic() > deadline:
                    raise RuntimeError(f"HTTP job {job.ID} never completed")
                time.sleep(0.02)
            back, _ = api.jobs.info(job.ID)
            placed, _ = api.jobs.allocations(job.ID)
            checks.require("http:read_back",
                           back.ID == job.ID and len(placed) == PER_EVAL
                           and back.TaskGroups[0].Count == PER_EVAL,
                           f"job {back.ID}: {len(placed)} allocations")
            submitted.append(eval_id)
        walls["http_jobs"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        warm_ids = warm_buckets(server, make_job)
        warm_ids += run_storm(server, [make_job()
                                       for _ in range(args.evals)])[0]
        walls["warm_up"] = time.perf_counter() - t0
        emit("warm_up", jobs=len(warm_ids), **compiles.since(mark))
        submitted += warm_ids
        before = worker_stats(server)

        mark = compiles.mark()
        storm_ids, secs = run_storm(server, [make_job()
                                             for _ in range(args.evals)])
        walls["storm"] = secs
        after = worker_stats(server)
        storm = {k: after[k] - before[k] for k in after}
        emit("storm", evals=args.evals, placements=args.evals * PER_EVAL,
             seconds=secs, **compiles.since(mark),
             worker_stats_delta=storm)
        checks.require("storm:device_placed",
                       storm["fast"] - storm["host"] > 0, storm)
        submitted += storm_ids

        t0 = time.perf_counter()
        lone_ids, _ = run_storm(server, [make_job()])
        walls["lone_job"] = time.perf_counter() - t0
        submitted += lone_ids

        stats = worker_stats(server)
        emit("worker_stats", **stats)
        checks.require("stats:device_placed",
                       stats["fast"] - stats["host"] > 0, stats)
        checks.require("stats:no_fallback", stats["fallback"] == 0, stats)
        all_ids = [e.ID for e in server.state.evals()]
        checks.require("all_evals_complete",
                       not wait_evals(server.state, all_ids, 60.0),
                       "an eval the server created never completed")

        t0 = time.perf_counter()
        device_usage = np.asarray(nt.device_arrays()["usage"])
        check_guarantees(checks, "serve", server.state, nt.row_of,
                         device_usage, submitted)
        walls["recompute"] = time.perf_counter() - t0
        emit("heartbeats", sweeps=hb.sweeps,
             nodes_down=sum(1 for n in server.state.nodes()
                            if n.Status == "down"))
    finally:
        hb.stop.set()
        t0 = time.perf_counter()
        agent.shutdown()
        walls["shutdown"] = time.perf_counter() - t0
    hb.join(30.0)
    check_threads_joined(checks)

    t0 = time.perf_counter()
    compare_kernel_to_mirror(checks, args.seed, nt.n_rows, args.nodes,
                             cfg.scheduler_window)
    walls["kernel_compare"] = time.perf_counter() - t0
    emit("phase_wall_seconds", **walls)
    stats_dev = jax.devices()[0].memory_stats() or {}
    emit("device_memory", peak_bytes_in_use=stats_dev.get(
        "peak_bytes_in_use", "not reported"))


def check_threads_joined(checks):
    """server.shutdown joins every thread that can be inside an XLA
    dispatch; one left alive at interpreter exit has aborted the process
    before (exit 134)."""
    alive = [t.name for t in threading.enumerate()
             if t is not threading.main_thread() and t.is_alive()]
    left = [n for n in alive if "worker" in n or "plan-apply" in n]
    emit("threads_after_shutdown", alive=alive)
    checks.require("shutdown:threads_joined", not left, left)


# ---------------------------------------------------------- four-chip phase
def fixed_noise(n_rows, rng):
    """Deterministic tie-break noise so two servers place identically
    (as tests/test_mesh_serving.py fixes it)."""
    import numpy as np

    return np.asarray(np.random.default_rng(1234).random(n_rows),
                      dtype=np.float32) * 1e-3


def serve_side(args, checks, mesh_side, nodes_blob, jobs_blob):
    """One Server (mesh or single device), the same fleet and job stream;
    returns job -> sorted (node, score) placements."""
    import pickle

    import jax
    import numpy as np

    from nomad_tpu.server import Server, ServerConfig

    label = "mesh" if mesh_side else "one_device"
    # Nobody heartbeats these nodes: park the TTLs past the run.
    # host_placement=False so that no window is placed by numpy.
    server = Server(ServerConfig(num_schedulers=1,
                                 scheduler_mesh="all" if mesh_side else "",
                                 host_placement=False,
                                 min_heartbeat_ttl=24 * 3600.0,
                                 heartbeat_grace=24 * 3600.0))
    server.establish_leadership()
    try:
        t0 = time.perf_counter()
        for node in pickle.loads(nodes_blob):
            server.node_register(node)
        t_reg = time.perf_counter() - t0
        nt = server.tindex.nt
        jobs = pickle.loads(jobs_blob)
        # Two bursts: the second starts from committed usage (a rebase and
        # a cold mesh window), and within each the windows chain warm.
        half = len(jobs) // 2
        ids1, s1 = run_storm(server, jobs[:half], timeout=1500.0)
        ids2, s2 = run_storm(server, jobs[half:], timeout=1500.0)
        stats = worker_stats(server)
        arrays = nt.device_arrays()
        shards = {k: [[str(s.device), list(s.data.shape)]
                      for s in v.addressable_shards]
                  for k, v in arrays.items()}
        emit("served_side", side=label, nodes=args.nodes,
             table_rows=nt.n_rows, register_seconds=t_reg,
             storm_seconds=[s1, s2], worker_stats=stats, shards=shards,
             memory=[[str(d), (d.memory_stats() or {}).get("bytes_in_use",
                                                           "not reported")]
                     for d in jax.devices()])
        checks.require(f"{label}:no_fallback", stats["fallback"] == 0
                       and stats["host"] == 0 and stats["fast"] > 0, stats)
        if mesh_side:
            n_dev = len(jax.devices())
            checks.require("mesh:windows", stats["mesh_windows"] > 0
                           and stats["mesh_shards"] == n_dev, stats)
            per_dev = {k: sorted(shape[0] for _, shape in v)
                       for k, v in shards.items()}
            checks.require(
                "mesh:quarter_of_rows_each",
                all(rows == [nt.n_rows // n_dev] * n_dev
                    for rows in per_dev.values()), per_dev)
        check_guarantees(checks, label, server.state, nt.row_of,
                         np.asarray(arrays["usage"]), ids1 + ids2)
        return {job.ID: sorted(
            (a.NodeID, a.Metrics.Scores.get(a.NodeID + ".binpack", 0.0)
             if a.Metrics is not None else 0.0)
            for a in server.state.allocs_by_job(job.ID)) for job in jobs}
    finally:
        server.shutdown()


def phase_mesh(args, checks, compiles):
    import pickle

    import jax
    import numpy as np

    from nomad_tpu.parallel import pow2_prefix, scheduling_mesh
    from nomad_tpu.scheduler import stack as stack_mod

    n_dev = len(jax.devices())
    if not checks.require("mesh:four_devices", n_dev == 4,
                          f"{n_dev} devices"):
        return
    walls = {}
    stack_mod.make_noise_vec = fixed_noise
    rng = random.Random(args.seed)
    nodes_blob = pickle.dumps(build_fleet(args.nodes, rng))
    jobs_blob = pickle.dumps([build_job(rng, k) for k in range(args.evals)])
    sides = {}
    for mesh_side in (True, False):
        t0 = time.perf_counter()
        mark = compiles.mark()
        sides[mesh_side] = serve_side(args, checks, mesh_side, nodes_blob,
                                      jobs_blob)
        check_threads_joined(checks)
        walls["served_mesh" if mesh_side else "served_one_device"] = \
            time.perf_counter() - t0
        emit("served_compiles", side="mesh" if mesh_side else "one_device",
             **compiles.since(mark))
    mesh_p, one_p = sides[True], sides[False]
    same_jobs = sum(1 for j in one_p
                    if [n for n, _ in one_p[j]] == [n for n, _ in mesh_p[j]])
    score_gap = float(np.max(np.abs(
        np.sort([s for p in one_p.values() for _, s in p])
        - np.sort([s for p in mesh_p.values() for _, s in p]))))
    emit("served_mesh_vs_one_device", jobs=len(one_p),
         jobs_with_identical_nodes=same_jobs,
         score_multiset_max_abs_diff=score_gap)
    checks.require("served:same_rows_or_scores",
                   same_jobs == len(one_p) or score_gap <= SCORE_TOL,
                   f"{same_jobs}/{len(one_p)} jobs identical, score "
                   f"multisets differ by {score_gap}")

    # One keyed window (then a second, chained warm) at the kernel level.
    t0 = time.perf_counter()
    mark = compiles.mark()
    n = args.kernel_rows
    inp = window_inputs(args.seed, n, n - n // 16, 16)
    mesh = scheduling_mesh(pow2_prefix(jax.devices()))
    out = {}
    for label, m in (("mesh", mesh), ("one_device", None)):
        r1 = run_keyed(inp, m)
        r2 = run_keyed(inp, m, chain=r1.usage_after)
        flag = getattr(r2.usage_after, "flag", None)
        out[label] = (np.asarray(r1.packed), np.asarray(r2.packed),
                      np.asarray(r2.usage_after),
                      0.0 if flag is None else float(flag))
    v = inp["valid"]
    rows_same = [int((out["mesh"][w][v, 0] == out["one_device"][w][v, 0])
                     .sum()) for w in (0, 1)]
    sc_gap = max(float(np.max(np.abs(
        np.sort(out["mesh"][w][v, 1]) - np.sort(out["one_device"][w][v, 1]))))
        for w in (0, 1))
    infeasible, gap, err, usage = replay_choices(inp, out["mesh"][0])
    inf2, gap2, err2, usage = replay_choices(inp, out["mesh"][1], usage)
    usage_err = float(np.max(np.abs(out["mesh"][2] - usage)))
    walls["kernel_window"] = time.perf_counter() - t0
    emit("kernel_mesh_vs_one_device", rows=n, placements=int(v.sum()),
         windows=2, rows_agree=rows_same,
         score_multiset_max_abs_diff=sc_gap,
         warm_certificate_flag=out["mesh"][3],
         mesh_infeasible_choices=infeasible + inf2,
         mesh_max_gap_to_best_feasible=max(gap, gap2),
         mesh_score_max_err_vs_float64=max(err, err2),
         mesh_usage_after_max_abs_err=usage_err, **compiles.since(mark))
    checks.require("kernel_mesh:same_rows_or_scores",
                   rows_same == [int(v.sum())] * 2 or sc_gap <= SCORE_TOL,
                   f"rows agree {rows_same}, scores differ by {sc_gap}")
    checks.require("kernel_mesh:certificate", out["mesh"][3] == 0.0,
                   "warm window's exactness certificate failed")
    checks.require("kernel_mesh:feasible_best_fit",
                   infeasible + inf2 == 0
                   and max(gap, gap2, err, err2) <= SCORE_TOL
                   and usage_err <= 1e-2,
                   f"infeasible {infeasible + inf2}, gap {max(gap, gap2)}, "
                   f"score error {max(err, err2)}, usage error {usage_err}")
    emit("phase_wall_seconds", **walls)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal without a chip; the last line says cpu")
    ap.add_argument("--nodes", type=int,
                    help="fleet size (default 10000; 65536 with --chips 4)")
    ap.add_argument("--evals", type=int,
                    help="storm size in jobs (default 200; 128 with "
                         "--chips 4)")
    ap.add_argument("--kernel-rows", type=int, default=1 << 20,
                    help="rows of the --chips 4 kernel-level window")
    args = ap.parse_args()
    if args.nodes is None:
        args.nodes = 10_000 if args.chips == 1 else 65_536
    if args.evals is None:
        args.evals = 200 if args.chips == 1 else 128

    t_start = time.perf_counter()
    from nomad_tpu.tensor.backend import device_info

    device = device_info()  # raises when the backend cannot initialize
    if device["platform"] != "tpu" and not args.allow_cpu:
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}); not continuing on it",
              file=sys.stderr)
        return 1
    checks = Checks()
    compiles = CompileLog()
    report_versions(device)
    build_native(checks)
    if args.chips == 4:
        phase_mesh(args, checks, compiles)
    else:
        phase_serve(args, checks, compiles)
    emit("total", wall_seconds=time.perf_counter() - t_start,
         failed_checks=checks.failed,
         **compiles.since())
    ok = not checks.failed
    if ok or args.allow_cpu:
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
