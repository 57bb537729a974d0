#!/usr/bin/env python
"""Benchmark: end-to-end scheduling throughput on the SERVED path.

Headline (BASELINE.json config 3): 10k nodes x 5k task-group placements with
driver + attribute constraint checkers, 64 node-meta partitions — measured
END-TO-END through a live server: job_register -> raft apply -> eval broker ->
pipelined worker (device-chained placement windows, server/pipelined_worker.py)
-> plan applier re-verification -> committed allocations in the state store.

Detail additionally reports:
  - the placer-only device-pipeline number (scheduler/pipeline.py) — the
    ceiling the served path is converging to
  - the CPU reference (iterator-chain re-implementation) and the SERVED
    CPU reference (same server, placement engine swapped) for vs_baseline
  - BASELINE.json configs 2 (1k nodes x 500 resource-only placements),
    4 (system scheduler, 10k nodes x 50 jobs), and 5 (50k nodes x 20k
    task groups, multi-DC) — each END-TO-END through the served path

Prints ONE json line: {"metric", "value", "unit", "vs_baseline"}.
"""

import gc
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

N_NODES = int(os.environ.get("BENCH_NODES", 10_000))
# Headline shape stays BASELINE config 3's node/constraint mix (10k nodes,
# 64 node-meta partitions, driver + attribute checkers); each timed rep is a
# 600-eval x 50-placement registration storm (long reps + a 9-rep median:
# host-clock rates vary from rep to rep, so min/median/max are reported
# alongside).
N_PLACEMENTS = int(os.environ.get("BENCH_PLACEMENTS", 30_000))
PER_EVAL = int(os.environ.get("BENCH_PER_EVAL", 50))
N_PARTITIONS = 64
# Pipelined workers share one device usage chain through the ChainArbiter
# (windows interleave coherently; broker/plan-queue rounds are batched), so
# N workers scale instead of collapsing (pre-arbiter: 2 workers ~30 evals/s
# vs 130-230 for 1 — each kept a private chain the plan applier bounced).
# The worker_scaling sweep below records the measured 1-vs-2 ratio in every
# bench JSON so the trajectory is judged on scaling, not just 1-worker rate.
N_WORKERS = int(os.environ.get("BENCH_WORKERS", 1))
# Worker-scaling sweep shapes: ALWAYS smoke-sized — the sweep judges the
# RATIO, not absolute rate, and two extra full-shape server boots would
# double the bench wall clock.
SCALING_NODES = int(os.environ.get("BENCH_SCALING_NODES", 512))
SCALING_EVALS = int(os.environ.get("BENCH_SCALING_EVALS", 60))
SCALING_REPS = int(os.environ.get("BENCH_SCALING_REPS", 4))
# 64-eval windows: deep (256-eval) windows serialize ~4x the scan steps
# per drain on the device chain, while the dispatch-time async host copy
# keeps a small window's readback off the critical path. The choice was
# made on hardware this repo no longer runs on (PERF.md); re-measure
# before relying on it.
WINDOW = int(os.environ.get("BENCH_WINDOW", 64))
# Nine reps: a 9-sample median of host-clock rates is noticeably more
# stable than 7.
N_REPS = int(os.environ.get("BENCH_REPS", 9))
# >= 24 evals through the reference chain stabilizes the served-vs-served
# denominator to a few percent (round 4 ran 8, the noisiest number in the
# file); still ~4-6s of wall per rep at ~6 evals/s.
CPU_REF_EVALS = int(os.environ.get("BENCH_CPU_EVALS", 24))
C5_NODES = int(os.environ.get("BENCH_C5_NODES", 50_000))
C5_PLACEMENTS = int(os.environ.get("BENCH_C5_PLACEMENTS", 20_000))
RUN_C5 = os.environ.get("BENCH_C5", "1") != "0"
RUN_C2 = os.environ.get("BENCH_C2", "1") != "0"
RUN_C4 = os.environ.get("BENCH_C4", "1") != "0"
# Config 4 (system scheduler) shape: 2 small warmups + one full-size warm
# storm (C4_EVALS) + C4_REPS x C4_EVALS timed + 2 probes = 73 system jobs
# at the defaults (BASELINE names the 50-job storm; the extra warm storm
# is the same compile treatment every served config gets).
C4_EVALS = int(os.environ.get("BENCH_C4_EVALS", 23))
C4_REPS = 2
# Placement-parity gate shape (bench_placement_parity).
PARITY_NODES = 1000
PARITY_EVALS = 40
# QoS slo_storm shape (bench_slo_storm): a saturating LOW-tier storm with
# sparse HIGH-tier arrivals, run interleaved A/B qos-off vs qos-on, per-tier
# latency percentiles recorded. The acceptance frame (ISSUE 8): a high-tier
# eval's storm p99 should be bounded near the idle p50 instead of riding the
# whole low-tier backlog.
SLO_NODES = int(os.environ.get("BENCH_SLO_NODES", 2000))
# Enough low-tier submissions that a real backlog exists when the high
# arrivals land behind it (the tail being measured IS queue wait).
SLO_LOW = int(os.environ.get("BENCH_SLO_LOW", 400))
SLO_HIGH = int(os.environ.get("BENCH_SLO_HIGH", 12))
SLO_REPS = int(os.environ.get("BENCH_SLO_REPS", 3))
RUN_SLO = os.environ.get("BENCH_SLO", "1") != "0"
# Service columnar-commit A/B (bench_service_columnar_ab): the same
# service storm served with columnar commits on vs off, servers live
# simultaneously, reps interleaved with ALTERNATING within-pair order
# (the cgroup quota punishes whoever runs second), max-of-reps.
SVC_AB_NODES = int(os.environ.get("BENCH_SVC_NODES", 2000))
SVC_AB_EVALS = int(os.environ.get("BENCH_SVC_EVALS", 60))
SVC_AB_REPS = int(os.environ.get("BENCH_SVC_REPS", 3))
RUN_SVC_AB = os.environ.get("BENCH_SVC_AB", "1") != "0"
# Smoke gate on the store microbench: columnar service-window commit must
# beat the per-object path by at least this factor (parity-style exit 2).
# Measured ~8-15x on a quiet box; 3x leaves noise headroom.
STORE_SVC_GATE = float(os.environ.get("BENCH_STORE_GATE", 3.0))
# config6_mesh_1m (bench_mesh_1m): the ISSUE-12 headline shape — 1M nodes
# x one wide storm window — as a keyed-kernel one-device-vs-mesh A/B with
# per-window latency percentiles, in this process on the devices JAX
# reports (recorded as "not measured: one device" when there is only
# one). Slow-gated: --smoke turns it off (a 1M-node compile alone blows
# the 60s budget; tier-1 covers the mesh path via
# tests/test_mesh_keyed_equivalence.py and the collective audit).
MESH_NODES = int(os.environ.get("BENCH_MESH_NODES", 1_048_576))
MESH_P = int(os.environ.get("BENCH_MESH_P", 1024))
MESH_VALID = int(os.environ.get("BENCH_MESH_VALID", 800))
MESH_WINDOWS = int(os.environ.get("BENCH_MESH_WINDOWS", 6))
MESH_REPS = int(os.environ.get("BENCH_MESH_REPS", 3))
RUN_MESH = os.environ.get("BENCH_MESH", "1") != "0"
# failover_storm (bench_failover_storm, ISSUE 13): a real 3-server
# in-process cluster (raft + gossip + QoS lanes + streaming snapshots)
# rides a mixed-priority storm through an induced LEADER KILL, recording
# placements/s and per-tier e2e percentiles THROUGH the election plus
# the measured leader gap. Parity-style exit-2 gate: zero lost evals,
# zero duplicate allocs. --smoke runs the small variant; the full storm
# is the slow-gated shape.
FAILOVER_NODES = int(os.environ.get("BENCH_FAILOVER_NODES", 96))
FAILOVER_JOBS = int(os.environ.get("BENCH_FAILOVER_JOBS", 90))
FAILOVER_PER_JOB = int(os.environ.get("BENCH_FAILOVER_PER_JOB", 4))
RUN_FAILOVER = os.environ.get("BENCH_FAILOVER", "1") != "0"
# config7_federation (bench_federation_storm, ISSUE 14): a mixed-priority
# storm CONCENTRATED in one region of a real 3-region federated cluster
# (gossip + cross-region forwarding + follower-snapshot workers + per-
# region QoS), A/B'd against the all-on-leader baseline — ONE region
# holding the same total fleet, the same total storm, and the same total
# worker count on a single leader (the pre-federation shape the tentpole
# scales out). Reps interleaved with ALTERNATING within-pair order,
# max-of-reps (this box's cgroup quota punishes whoever runs second).
# Records per-region evals/s, cross-region forward p99, per-region
# high-tier p99. Parity-style exit-2 gate: zero lost evals, no duplicate
# allocs, storm-free regions' high-tier p99 within the high SLO
# deadline, and the federated side actually sharing snapshots.
FED_NODES = int(os.environ.get("BENCH_FED_NODES", 48))    # per region
FED_JOBS = int(os.environ.get("BENCH_FED_JOBS", 48))      # storm region
FED_QUIET_HIGH = int(os.environ.get("BENCH_FED_QUIET_HIGH", 6))
FED_PER_JOB = int(os.environ.get("BENCH_FED_PER_JOB", 4))
FED_REPS = int(os.environ.get("BENCH_FED_REPS", 3))
RUN_FED = os.environ.get("BENCH_FED", "1") != "0"
# event_stream (bench_event_stream, ISSUE 18): the SAME service storm
# served with the cluster event broker ARMED (event_buffer_size=4096 +
# one live subscriber draining fan-out rows the whole run) vs DISARMED
# (event_buffer_size=0: no broker object; the apply path pays one
# attribute check). Interleaved reps, alternating order, max-of-reps.
# Records per-side evals/s, the publish overhead %, and the armed
# broker's nomad.events counters (published / dropped / ring depth).
# Parity-style exit-2 gate: both sides place the full storm every rep,
# the subscriber really consumed the storm, and nothing was dropped.
EVENTS_AB_NODES = int(os.environ.get("BENCH_EVENTS_NODES", 2048))
EVENTS_AB_EVALS = int(os.environ.get("BENCH_EVENTS_EVALS", 40))
EVENTS_AB_REPS = int(os.environ.get("BENCH_EVENTS_REPS", 3))
RUN_EVENTS = os.environ.get("BENCH_EVENTS", "1") != "0"

# Replica-digest A/B (bench_digest): the apply-path hash-chain fold
# (digest_interval=64, the deployed default) vs disarmed
# (digest_interval=0: no digest object; apply pays one attribute
# check). Parity-style exit-2 gate: both sides place the full storm
# every rep, the armed chain really folded every commit, and it never
# flagged a divergence against itself.
DIGEST_AB_NODES = int(os.environ.get("BENCH_DIGEST_NODES", 2048))
DIGEST_AB_EVALS = int(os.environ.get("BENCH_DIGEST_EVALS", 40))
DIGEST_AB_REPS = int(os.environ.get("BENCH_DIGEST_REPS", 3))
RUN_DIGEST = os.environ.get("BENCH_DIGEST", "1") != "0"


def _apply_smoke():
    """--smoke: tiny CPU-safe shapes, <60s end to end. Same code path as
    the full bench — live server, pipelined worker, plan applier, and the
    placement-parity quality gate — so perf-path breakage is caught
    in-tree (tests/test_bench_smoke.py) without a TPU bench run. Numbers
    from a smoke run are NOT comparable to the headline shapes."""
    global N_NODES, N_PLACEMENTS, N_REPS, CPU_REF_EVALS
    global RUN_C2, RUN_C4, RUN_C5, PARITY_NODES, PARITY_EVALS
    global SCALING_NODES, SCALING_EVALS, C4_EVALS
    global SLO_NODES, SLO_LOW, SLO_HIGH, SLO_REPS
    global SVC_AB_NODES, SVC_AB_EVALS, SVC_AB_REPS, RUN_MESH
    global FAILOVER_NODES, FAILOVER_JOBS
    global FED_NODES, FED_JOBS, FED_QUIET_HIGH, FED_REPS
    global EVENTS_AB_NODES, EVENTS_AB_EVALS, EVENTS_AB_REPS
    global DIGEST_AB_NODES, DIGEST_AB_EVALS, DIGEST_AB_REPS
    N_NODES = min(N_NODES, 512)
    N_PLACEMENTS = min(N_PLACEMENTS, 2000)   # 40 evals @ PER_EVAL=50
    N_REPS = min(N_REPS, 3)
    CPU_REF_EVALS = min(CPU_REF_EVALS, 6)
    RUN_C2 = RUN_C5 = False
    # The system config STAYS on at smoke scale (512-node sweeps, 4
    # timed evals): the tensor-sweep path has no other in-tree perf
    # gate, so a system-path regression must surface in every smoke
    # JSON, not just full runs. ~5s of the <60s budget.
    RUN_C4 = True
    C4_EVALS = min(C4_EVALS, 4)
    PARITY_NODES, PARITY_EVALS = 200, 10
    # The scaling sweep is already smoke-shaped; trim the node count and
    # rep length so the whole smoke run stays under its 60s budget. The
    # rep COUNT stays at the default: the max-of-reps ratio needs samples
    # more than the budget needs the ~2s back.
    SCALING_NODES = min(SCALING_NODES, 256)
    SCALING_EVALS = min(SCALING_EVALS, 40)
    # The QoS storm STAYS on at smoke scale (parity-gated: qos-off and
    # qos-on must place identically): the tiered broker / deadline-window
    # path has no other in-tree perf gate. A few seconds of budget.
    SLO_NODES = min(SLO_NODES, 256)
    SLO_LOW = min(SLO_LOW, 24)
    SLO_HIGH = min(SLO_HIGH, 6)
    SLO_REPS = min(SLO_REPS, 2)
    # The service columnar A/B STAYS on at smoke scale: the columnar
    # service commit has its in-tree microbench gate (store section), but
    # the e2e interleave is the only place an A/B parity break (columnar
    # placing differently from object) would surface. A few seconds.
    SVC_AB_NODES = min(SVC_AB_NODES, 256)
    SVC_AB_EVALS = min(SVC_AB_EVALS, 20)
    SVC_AB_REPS = min(SVC_AB_REPS, 2)
    # The failover storm STAYS on at smoke scale (the zero-loss gate is
    # the only bench-side check that an election loses nothing); the
    # full 90-job storm is the slow-gated shape. A few seconds.
    FAILOVER_NODES = min(FAILOVER_NODES, 24)
    FAILOVER_JOBS = min(FAILOVER_JOBS, 24)
    # The federation storm STAYS on at smoke scale: its zero-loss /
    # no-duplicate / quiet-region-p99 gate is the only bench-side check
    # of the cross-region forwarding + follower-snapshot path. A few
    # seconds of budget (4 single-raft servers, tiny storms).
    FED_NODES = min(FED_NODES, 12)
    # >= 4 windows of backlog in the storm region (window=8): snapshot
    # REUSE only exists once dequeues stop chasing fresh registrations,
    # and the gate requires proving it happened.
    FED_JOBS = min(FED_JOBS, 27)
    FED_QUIET_HIGH = min(FED_QUIET_HIGH, 3)
    FED_REPS = min(FED_REPS, 2)
    # The event-stream A/B STAYS on at smoke scale: the broker-armed vs
    # disarmed interleave (plus its zero-drop gate) is the only bench-
    # side check that publishing + one live subscriber costs the apply
    # path nothing measurable. A few seconds of budget.
    EVENTS_AB_NODES = min(EVENTS_AB_NODES, 256)
    EVENTS_AB_EVALS = min(EVENTS_AB_EVALS, 16)
    EVENTS_AB_REPS = min(EVENTS_AB_REPS, 2)
    # The replica-digest A/B STAYS on at smoke scale: the fold is ON the
    # apply path for every deployment (digest_interval defaults to 64),
    # so its overhead and its parity gate must surface in every smoke
    # JSON. A few seconds of budget.
    DIGEST_AB_NODES = min(DIGEST_AB_NODES, 256)
    DIGEST_AB_EVALS = min(DIGEST_AB_EVALS, 16)
    DIGEST_AB_REPS = min(DIGEST_AB_REPS, 2)
    # The 1M mesh A/B is slow-gated OUT of smoke (its compile alone
    # blows the budget); the mesh path's correctness coverage is
    # tier-1 (equivalence gate + collective audit + chaos schedule).
    RUN_MESH = False


def _freeze_heap():
    """Collect, then freeze every survivor out of the collector's view.
    THE one between-rep GC treatment: every timed loop (headline, config
    benches, and the CPU-served denominator) calls this so the
    served-vs-served ratio can never drift onto unequal GC footing."""
    gc.collect()
    gc.freeze()


def _tune_gc():
    """Server-process runtime tuning, applied identically before BOTH
    sides' timed reps (TPU-served and CPU-served): collect, freeze the
    steady-state heap (10k node structs + server machinery) out of the
    collector's view, and raise the gen-0 threshold so a 20k-alloc
    registration storm doesn't trigger full-heap scans mid-rep. The
    analogue of running the Go reference with a tuned GOGC — a deployment
    setting, not a code path. The GIL switch interval rises from its 5ms
    default for the same reason: a scheduling server runs several
    GIL-bound stage threads (N workers x dispatch/drain/build + the plan
    applier), and 200 preemptions/sec of the dispatch loop is measurable
    convoy overhead on a small core count."""
    _freeze_heap()
    gc.set_threshold(50_000, 50, 50)
    sys.setswitchinterval(0.02)


def build_nodes(n, n_dcs=1):
    from nomad_tpu import mock
    from nomad_tpu.structs import compute_node_class

    nodes = []
    for i in range(n):
        node = mock.node()
        node.Meta["rack"] = f"r{i % N_PARTITIONS}"  # 64 computed classes
        if n_dcs > 1:
            node.Datacenter = f"dc{i % n_dcs + 1}"
        compute_node_class(node)
        nodes.append(node)
    return nodes


def build_job(per_eval=PER_EVAL, dcs=None):
    from nomad_tpu import mock
    from nomad_tpu.structs import Constraint

    job = mock.job()
    if dcs:
        job.Datacenters = list(dcs)
    tg = job.TaskGroups[0]
    tg.Count = per_eval
    # Driver checker (exec) is already on the mock task; add an attribute
    # constraint so the full checker chain runs (BASELINE config 3).
    job.Constraints.append(
        Constraint(LTarget="${attr.arch}", RTarget="x86", Operand="="))
    # Small asks so the node pool absorbs the placements without exhaustion.
    task = tg.Tasks[0]
    task.Resources.CPU = 20
    task.Resources.MemoryMB = 32
    task.Resources.DiskMB = 10
    task.Resources.Networks = []
    task.Services = []
    # Keep per-task log storage under the small disk ask (validation:
    # LogConfig total must fit DiskMB).
    if task.LogConfig is not None:
        task.LogConfig.MaxFiles = 1
        task.LogConfig.MaxFileSizeMB = 1
    return job


def _make_storm_runner(srv, job_fn=None):
    """Register `count` jobs and poll until every eval completes — the
    measured unit of work, shared by BOTH sides of the served-vs-served
    ratio so the two benchmarks can never drift apart."""
    from nomad_tpu.structs.structs import EvalStatusComplete

    if job_fn is None:
        job_fn = build_job

    def run(count, poll=0.02, latencies=None):
        t_submit = {}
        eval_ids = []
        for _ in range(count):
            eid = srv.job_register(job_fn())[0]
            t_submit[eid] = time.monotonic()
            eval_ids.append(eid)
        deadline = time.monotonic() + 600
        pending = set(eval_ids)
        while pending and time.monotonic() < deadline:
            now = time.monotonic()
            done = {eid for eid in pending
                    if (e := srv.state.eval_by_id(eid)) is not None
                    and e.Status == EvalStatusComplete}
            if latencies is not None:
                # In-storm per-eval latency, submit -> observed complete.
                # Quantized by the poll period (+poll worst case): fine
                # for storm tails, which sit far above the poll. The
                # windowed design trades tail for throughput — these
                # percentiles are where that trade is visible.
                latencies.extend(now - t_submit[eid] for eid in done)
            pending -= done
            if pending:
                # Coarse poll: the measured path runs in server threads; a
                # hot completion-poll loop would steal interpreter time
                # from the very workers being measured. (Latency probes
                # pass a finer poll so the granularity doesn't dominate.)
                time.sleep(poll)
        if pending:
            raise RuntimeError(f"{len(pending)} evals never completed")
        return eval_ids

    return run


def _pctiles_ms(lats):
    """{p50, p95, p99} in ms from a list of second-latencies."""
    if not lats:
        return {}
    return {f"p{p}": round(float(np.percentile(lats, p)) * 1e3, 2)
            for p in (50, 95, 99)}


def bench_server_e2e(nodes, n_evals):
    """The SERVED path: a live dev-mode server with the pipelined worker.
    Clock runs from first job_register to the last eval completing with its
    allocations committed in the state store."""
    from nomad_tpu.server import Server, ServerConfig

    # Benchmark nodes never heartbeat: park the TTLs out past the run.
    srv = Server(ServerConfig(num_schedulers=N_WORKERS,
                              pipelined_scheduling=True,
                              scheduler_window=WINDOW,
                              min_heartbeat_ttl=24 * 3600.0,
                              heartbeat_grace=24 * 3600.0))
    srv.establish_leadership()
    try:
        for node in nodes:
            srv.node_register(node)

        run = _make_storm_runner(srv)

        # Warmup: two rounds — the first compiles the placement kernels, the
        # second's window observes the first's committed allocs and compiles
        # the dirty-row device refresh program.
        run(3)
        run(3)
        # Compile the remaining dirty-row refresh buckets now: a full rep
        # dirties ~10k usage rows, whose 16384-row refresh program would
        # otherwise compile inside the SECOND timed rep (the first rep rides
        # the chain and skips usage refresh). Compiles are one-time server
        # lifetime costs; the timed reps still pay every refresh TRANSFER.
        srv.tindex.nt.warm_device()
        # One full-size warm storm: deep windows fuse into place_batch_multi
        # at the LARGE eval-pad buckets, whose first compile would otherwise
        # land inside the first timed rep (same one-time-cost rationale).
        run(n_evals)
        _tune_gc()
        # Attribute phase timers to the timed reps only, not warmup compiles.
        # Quiesce first: evals complete (visibly) at the EvalUpdate apply,
        # before the build stage's final stats writes for the window.
        # reset_stats() zeroes the DECLARED schema in place, so this loop
        # cannot drift from the keys the worker actually maintains.
        for w in srv.workers:
            if hasattr(w, "quiesce"):
                w.quiesce(30.0)
            if hasattr(w, "reset_stats"):
                w.reset_stats()

        # Median of N_REPS timed reps: a single host-clock sample can be
        # far off in either direction. Reps accumulate allocations in the
        # cluster
        # (like a real registration storm would); at the default shapes the
        # node pool has >100x headroom, so fill effects are negligible.
        rates = []
        eval_ids = []
        storm_lats: list = []
        for _ in range(N_REPS):
            t0 = time.perf_counter()
            eval_ids = run(n_evals, latencies=storm_lats)
            rates.append(n_evals / (time.perf_counter() - t0))
            # Freeze each rep's ~30k surviving allocs out of the
            # collector's view BETWEEN reps (untimed): without this,
            # later reps pay growing gen1 scans over every prior rep's
            # live heap and the rate decays ~30% from rep 1 to rep 9 —
            # a measurement artifact, not scheduler behavior. Same
            # steady-state-deployment rationale as _tune_gc.
            _freeze_heap()
        # Lower-middle median: never report the faster of an even pair.
        rate = sorted(rates)[(len(rates) - 1) // 2]

        placed = sum(
            1 for eid in eval_ids
            for a in srv.state.allocs_by_eval(eid))
        stats: dict = {}
        for w in srv.workers:
            if hasattr(w, "quiesce"):
                w.quiesce(30.0)
            for k, v in list(w.stats.items()):
                stats[k] = stats.get(k, 0) + v
        # Counters below cover ALL timed reps (N_REPS x n_evals evals).
        stats["timed_reps"] = len(rates)
        stats["rep_rates"] = [round(r, 1) for r in rates]
        stats["rep_min_med_max"] = [round(min(rates), 1), round(rate, 1),
                                    round(max(rates), 1)]
        # Served-path single-eval latency on an idle broker (the number an
        # interactive `nomad run` pays): registration -> placement ->
        # commit, via the host fast path when the window is shallow.
        lats = []
        for _ in range(5):
            t0 = time.perf_counter()
            run(1, poll=0.002)
            lats.append(time.perf_counter() - t0)
        stats["e2e_p50_eval_latency_ms"] = round(
            float(np.percentile(lats, 50)) * 1e3, 2)
        # In-storm percentiles over every timed rep's evals: an eval's
        # latency under load includes waiting for its window slot — the
        # tail the windowed design trades for throughput.
        stats["e2e_storm_latency_ms"] = _pctiles_ms(storm_lats)
        return rate, placed, stats
    finally:
        srv.shutdown()


def bench_served_config(nodes, job_fn, n_evals, reps=2, warm=3,
                        window=None, latency_probes=3, workers=None):
    """Generic SERVED-path benchmark for one BASELINE config: live server,
    pipelined worker, clock from first register to last commit. Returns
    (median evals/sec, total placed, p50 single-eval latency, rep rates)."""
    from nomad_tpu.server import Server, ServerConfig

    srv = Server(ServerConfig(num_schedulers=workers or N_WORKERS,
                              pipelined_scheduling=True,
                              scheduler_window=window or WINDOW,
                              min_heartbeat_ttl=24 * 3600.0,
                              heartbeat_grace=24 * 3600.0))
    srv.establish_leadership()
    try:
        for node in nodes:
            srv.node_register(node)
        run = _make_storm_runner(srv, job_fn)
        run(warm)
        run(warm)
        srv.tindex.nt.warm_device()
        # Same treatment as the headline bench: one full-size warm storm so
        # the large eval-pad place_batch_multi buckets compile before the
        # first timed rep (symmetric warmup keeps the configs comparable).
        run(n_evals)
        _tune_gc()
        rates = []
        eval_ids = []
        storm_lats: list = []
        for _ in range(reps):
            t0 = time.perf_counter()
            eval_ids = run(n_evals, latencies=storm_lats)
            rates.append(n_evals / (time.perf_counter() - t0))
            # Same between-rep GC treatment as the headline bench (and
            # the CPU-served denominator): freeze each rep's survivors
            # out of the collector's view, untimed.
            _freeze_heap()
        placed = sum(1 for eid in eval_ids
                     for _ in srv.state.allocs_by_eval(eid))
        lats = []
        for _ in range(latency_probes):
            t0 = time.perf_counter()
            run(1, poll=0.002)
            lats.append(time.perf_counter() - t0)
        # Lower-middle for even rep counts: upper-middle would report the
        # FASTER of two reps as "the median" (optimistic bias).
        med = sorted(rates)[(len(rates) - 1) // 2]
        return (med, placed,
                float(np.percentile(lats, 50)) if lats else 0.0,
                [round(r, 2) for r in rates],
                _pctiles_ms(storm_lats))
    finally:
        srv.shutdown()


def bench_worker_scaling():
    """1-vs-2-worker scaling of the served path, at smoke shapes. The
    bench JSON records {workers_1, workers_2, ratio} so a scaling
    regression (a second worker making things SLOWER — the pre-arbiter
    state) is caught by trajectory review, not rediscovered by hand.

    Both servers stay up and the timed reps INTERLEAVE (1w, 2w, 1w, 2w,
    ...): short reps on a box with background load wander ±30%, and
    interleaving puts both sides under the same drift instead of handing
    one config a quiet machine. The reported rate is max-of-reps — the
    ratio compares peak capability, and a max over a handful of short
    reps is far less noisy than their median.

    The sweep forces the DEVICE chain (host_placement=False): N-worker
    scaling is a property of the device-chained architecture — async
    kernel dispatches and GIL-releasing fetches are what one worker's
    stages overlap with another's — and at smoke shapes the host-numpy
    fallback would otherwise swallow the whole window into GIL-bound
    Python, where a second worker can only ever tie (measured: host-path
    ratio ~0.97-1.13 pure noise around parity; device-path ratio >1
    consistently on a 2-core CPU box)."""
    from nomad_tpu.server import Server, ServerConfig

    nodes = build_nodes(SCALING_NODES)
    servers = {}
    out: dict = {"nodes": SCALING_NODES, "evals_per_rep": SCALING_EVALS}
    try:
        for n in (1, 2):
            srv = Server(ServerConfig(num_schedulers=n,
                                      pipelined_scheduling=True,
                                      scheduler_window=WINDOW,
                                      host_placement=False,
                                      min_heartbeat_ttl=24 * 3600.0,
                                      heartbeat_grace=24 * 3600.0))
            srv.establish_leadership()
            for node in nodes:
                srv.node_register(node)
            run = _make_storm_runner(srv)
            run(2)
            run(2)
            srv.tindex.nt.warm_device()
            run(SCALING_EVALS)  # full-size warm storm (compiles)
            servers[n] = (srv, run)
        _tune_gc()
        for n in (1, 2):
            # One untimed pair after the GC tuning: the first post-freeze
            # storm pays one-off collector/cache effects that otherwise
            # land inside whichever config runs first.
            servers[n][1](SCALING_EVALS)
            _freeze_heap()
        rates: dict = {1: [], 2: []}
        for _ in range(SCALING_REPS):
            for n in (1, 2):  # interleaved A/B pair
                srv, run = servers[n]
                for w in srv.workers:
                    if hasattr(w, "quiesce"):
                        w.quiesce(30.0)
                t0 = time.perf_counter()
                eval_ids = run(SCALING_EVALS)
                rates[n].append(
                    round(SCALING_EVALS / (time.perf_counter() - t0), 2))
                _freeze_heap()
                # Per-rep placed counts (not just the last rep's): an
                # under-placing rep is exactly the regression class the
                # sweep exists to surface.
                out.setdefault(f"workers_{n}_placed", []).append(sum(
                    1 for eid in eval_ids
                    for _ in srv.state.allocs_by_eval(eid)))
        for n in (1, 2):
            out[f"workers_{n}"] = max(rates[n])
            out[f"workers_{n}_rep_rates"] = rates[n]
        out["ratio"] = round(out["workers_2"] / out["workers_1"], 3) \
            if out["workers_1"] else None
        return out
    finally:
        for srv, _ in servers.values():
            srv.shutdown()


def build_slo_job(priority, per_eval=8):
    """slo_storm job shape: small placement count so the storm is
    QUEUE-bound (the tails under test come from broker wait, not device
    compute), with an explicit priority tier."""
    job = build_job(per_eval)
    job.Priority = priority
    return job


def bench_slo_storm():
    """QoS mixed-priority storm: a saturating LOW-tier burst with sparse
    HIGH-tier arrivals behind it, measured twice — qos-off (today's FIFO
    path) and qos-on (tiered lanes + deadline windows) — with the timed
    reps INTERLEAVED on live servers like the worker-scaling sweep, so
    both sides see the same machine drift. Records per-tier e2e latency
    percentiles, the qos-on/off throughput ratio (the overhead bound),
    admission + preemption probe counts, and a PARITY gate: with ample
    capacity both modes must place every storm alloc.

    The acceptance frame (ISSUE 8): qos-on high-tier storm p99 bounded
    near the idle e2e p50 instead of riding the whole low-tier backlog —
    reported as high_p99_vs_idle_p50 for trajectory review."""
    from nomad_tpu.qos import QoSConfig
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs.structs import EvalStatusComplete

    per_eval = 8
    expect_allocs = (SLO_LOW + SLO_HIGH) * per_eval

    def run_mixed(srv, lats=None):
        """One mixed rep: low burst, then the high arrivals it buries."""
        tiers = {}
        t_submit = {}
        for _ in range(SLO_LOW):
            eid = srv.job_register(build_slo_job(10, per_eval))[0]
            tiers[eid] = "low"
            t_submit[eid] = time.monotonic()
        for _ in range(SLO_HIGH):
            eid = srv.job_register(build_slo_job(90, per_eval))[0]
            tiers[eid] = "high"
            t_submit[eid] = time.monotonic()
        pending = set(tiers)
        deadline = time.monotonic() + 600
        while pending and time.monotonic() < deadline:
            now = time.monotonic()
            done = {eid for eid in pending
                    if (e := srv.state.eval_by_id(eid)) is not None
                    and e.Status == EvalStatusComplete}
            if lats is not None:
                for eid in done:
                    lats[tiers[eid]].append(now - t_submit[eid])
            pending -= done
            if pending:
                # Finer poll than the throughput storms: high-tier
                # latencies are the measurement and can sit near 10ms.
                time.sleep(0.005)
        if pending:
            raise RuntimeError(f"{len(pending)} slo evals never completed")
        return list(tiers)

    nodes = build_nodes(SLO_NODES)
    out = {"nodes": SLO_NODES, "low_jobs": SLO_LOW, "high_jobs": SLO_HIGH,
           "placements_per_eval": per_eval}
    servers = {}
    try:
        for mode in ("qos_off", "qos_on"):
            # burn_shed > 1 disables SLO-burn shedding for the PARITY
            # storm: the gate asserts identical placed counts, so
            # admission must not shed mid-rep on a slow box. The
            # admission probe below exercises shedding deterministically.
            qos = QoSConfig(enabled=mode == "qos_on", burn_shed=2.0)
            srv = Server(ServerConfig(num_schedulers=N_WORKERS,
                                      pipelined_scheduling=True,
                                      scheduler_window=WINDOW,
                                      qos=qos,
                                      min_heartbeat_ttl=24 * 3600.0,
                                      heartbeat_grace=24 * 3600.0))
            srv.establish_leadership()
            for node in nodes:
                srv.node_register(node)
            run_mixed(srv)  # warm (compiles, first snapshots)
            srv.tindex.nt.warm_device()
            servers[mode] = srv
        _tune_gc()
        rates = {"qos_off": [], "qos_on": []}
        lats = {"qos_off": {"high": [], "low": []},
                "qos_on": {"high": [], "low": []}}
        placed = {}
        for _ in range(SLO_REPS):
            for mode in ("qos_off", "qos_on"):  # interleaved A/B pair
                srv = servers[mode]
                for w in srv.workers:
                    if hasattr(w, "quiesce"):
                        w.quiesce(30.0)
                t0 = time.perf_counter()
                eval_ids = run_mixed(srv, lats=lats[mode])
                rates[mode].append(
                    (SLO_LOW + SLO_HIGH) / (time.perf_counter() - t0))
                placed.setdefault(mode, []).append(sum(
                    1 for eid in eval_ids
                    for _ in srv.state.allocs_by_eval(eid)))
                _freeze_heap()
        for mode in ("qos_off", "qos_on"):
            out[mode] = {
                "evals_sec": round(max(rates[mode]), 2),
                "rep_rates": [round(r, 2) for r in rates[mode]],
                "high_ms": _pctiles_ms(lats[mode]["high"]),
                "low_ms": _pctiles_ms(lats[mode]["low"]),
                "placed_per_rep": placed[mode],
            }
        on = servers["qos_on"]
        out["qos_on"]["window_cuts"] = sum(
            w.stats.get("qos_cut", 0) for w in on.workers)
        out["qos_on"]["promoted"] = on.eval_broker.tier_promotions()
        out["throughput_ratio"] = round(
            max(rates["qos_on"]) / max(rates["qos_off"]), 3) \
            if rates["qos_off"] else None
        # Idle-broker single-eval p50 on the qos-on server — the
        # denominator of the tail bound.
        idle = []
        for _ in range(5):
            t0 = time.perf_counter()
            run_mixed_single(on, per_eval)
            idle.append(time.perf_counter() - t0)
        out["idle_p50_ms"] = round(
            float(np.percentile(idle, 50)) * 1e3, 2)
        high_p99 = out["qos_on"]["high_ms"].get("p99")
        out["high_p99_vs_idle_p50"] = round(
            high_p99 / out["idle_p50_ms"], 2) \
            if high_p99 and out["idle_p50_ms"] else None
        off_p99 = out["qos_off"]["high_ms"].get("p99")
        out["high_p99_improvement"] = round(off_p99 / high_p99, 2) \
            if high_p99 and off_p99 else None
        # Parity gate: ample capacity, so BOTH modes must place the full
        # storm every rep — QoS reorders, it must never drop placements.
        out["parity_ok"] = all(
            p == expect_allocs for mode in placed for p in placed[mode])
        out["expected_allocs"] = expect_allocs
    finally:
        for srv in servers.values():
            srv.shutdown()

    out["admission_probe"] = _slo_admission_probe()
    out["preempt_probe"] = _slo_preempt_probe()
    return out


def run_mixed_single(srv, per_eval):
    """One high-tier eval against an idle broker (idle-p50 probe)."""
    from nomad_tpu.structs.structs import EvalStatusComplete

    eid = srv.job_register(build_slo_job(90, per_eval))[0]
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        e = srv.state.eval_by_id(eid)
        if e is not None and e.Status == EvalStatusComplete:
            return [eid]
        time.sleep(0.002)
    raise RuntimeError("idle probe eval never completed")


def _slo_admission_probe():
    """Deterministic admission exercise: a workerless leader (queue depth
    can't drain) with a low-tier depth limit of 1 — the second low-tier
    submission must shed with the typed backpressure error."""
    from nomad_tpu.qos import QoSBackpressureError, QoSConfig
    from nomad_tpu.server import Server, ServerConfig

    srv = Server(ServerConfig(num_schedulers=0,
                              qos=QoSConfig(enabled=True,
                                            admit_depth=(0, 8192, 1)),
                              min_heartbeat_ttl=24 * 3600.0,
                              heartbeat_grace=24 * 3600.0))
    srv.establish_leadership()
    try:
        for node in build_nodes(2):
            srv.node_register(node)
        srv.job_register(build_slo_job(10, 1))
        shed = 0
        try:
            srv.job_register(build_slo_job(10, 1))
        except QoSBackpressureError:
            shed = 1
        counters = srv.qos_counters.snapshot()
        return {"shed": shed, "admitted": counters["admitted"],
                "ok": shed == 1}
    finally:
        srv.shutdown()


def _slo_preempt_probe():
    """Deterministic preemption exercise: two nearly-full nodes of
    low-tier load, then a high-tier job that fits nowhere — it must evict
    exactly one victim and place, atomically."""
    from nomad_tpu.qos import QoSConfig
    from nomad_tpu.server import Server, ServerConfig
    from nomad_tpu.structs.structs import (
        AllocDesiredStatusEvict,
        EvalStatusComplete,
    )

    srv = Server(ServerConfig(num_schedulers=1,
                              qos=QoSConfig(enabled=True),
                              min_heartbeat_ttl=24 * 3600.0,
                              heartbeat_grace=24 * 3600.0))
    srv.establish_leadership()
    try:
        for node in build_nodes(2):
            node.Resources.CPU = 1000
            node.Reserved = None
            srv.node_register(node)

        def fat_job(prio, cpu):
            job = build_slo_job(prio, 1)
            job.TaskGroups[0].Tasks[0].Resources.CPU = cpu
            return job

        def wait(eid):
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                e = srv.state.eval_by_id(eid)
                if e is not None and e.Status == EvalStatusComplete:
                    return True
                time.sleep(0.01)
            return False

        for _ in range(2):
            assert wait(srv.job_register(fat_job(10, 800))[0])
        heid = srv.job_register(fat_job(90, 600))[0]
        ok = wait(heid)
        placed = len(list(srv.state.allocs_by_eval(heid)))
        evicted = sum(1 for a in srv.state.allocs()
                      if a.DesiredStatus == AllocDesiredStatusEvict)
        counters = srv.qos_counters.snapshot()
        return {"placed": placed, "evicted": evicted,
                "preempt_placed": counters["preempt_placed"],
                "preempt_evictions": counters["preempt_evictions"],
                "ok": bool(ok and placed == 1 and evicted >= 1)}
    finally:
        srv.shutdown()


def bench_failover_storm():
    """Zero-downtime gate (ISSUE 13): a mixed-priority storm against a
    REAL 3-server cluster — raft replication, gossip failure detection,
    QoS lanes, streaming snapshots (low threshold so persists run
    mid-storm) — with the leader killed a third of the way in. Records
    placements/s through the whole storm (election included), per-tier
    e2e latency percentiles (the election wait lands in the tails of
    whatever was queued at the kill), the measured kill->new-leader gap,
    and the zero-loss gate: every eval terminal, every job at exactly
    its asked-for live allocs, no duplicate alloc IDs."""
    import random as _random
    import threading as _threading

    from nomad_tpu import mock
    from nomad_tpu.gossip import GossipConfig
    from nomad_tpu.qos import QoSConfig
    from nomad_tpu.raft import RaftConfig
    from nomad_tpu.rpc.cluster import ClusterServer
    from nomad_tpu.server import ServerConfig
    from nomad_tpu.structs import to_dict
    from nomad_tpu.structs.structs import (
        EvalStatusCancelled,
        EvalStatusComplete,
        EvalStatusFailed,
    )

    terminal = (EvalStatusComplete, EvalStatusFailed, EvalStatusCancelled)
    raft_cfg = RaftConfig(heartbeat_interval=0.02,
                          election_timeout_min=0.08,
                          election_timeout_max=0.16, apply_timeout=5.0,
                          snapshot_threshold=30, trailing_logs=32)

    def boot(name, join=None):
        cs = ClusterServer(ServerConfig(
            node_id="", num_schedulers=1, bootstrap_expect=3,
            scheduler_window=8,
            # Election-scale deadlines: the per-tier burn through the
            # kill is the SLO story, not sub-second compute on a loaded
            # bench box.
            qos=QoSConfig(enabled=True,
                          deadlines_s=(10.0, 30.0, 120.0))))
        cs.connect([], raft_config=raft_cfg)
        cs.start()
        ml_join = join
        cs.enable_gossip(name, join=ml_join,
                         gossip_config=GossipConfig.fast())
        return cs

    def leader_of(live):
        for n in live:
            try:
                if n.server is not None and n.server.is_leader() \
                        and n.server._leader:
                    return n
            except Exception:
                pass
        return None

    def rpc(live, method, args, attempts=80, delay=0.1):
        last = None
        for _ in range(attempts):
            targets = [n for n in live if n.endpoints is not None]
            _random.shuffle(targets)
            for cs in targets:
                try:
                    return cs.endpoints.handle(method, dict(args))
                except Exception as e:
                    last = e
            time.sleep(delay)
        raise last if last is not None else RuntimeError("no servers")

    def gaddr(cs):
        ml = cs.membership.memberlist
        return f"{ml.addr}:{ml.port}"

    tiers = (80, 20, 50)
    tier_name = {80: "high", 20: "low", 50: "normal"}
    nodes = [boot("b0")]
    nodes.append(boot("b1", join=[gaddr(nodes[0])]))
    nodes.append(boot("b2", join=[gaddr(nodes[0])]))
    live = list(nodes)
    out = {"nodes": FAILOVER_NODES, "jobs": FAILOVER_JOBS,
           "per_job": FAILOVER_PER_JOB}
    try:
        deadline = time.monotonic() + 30
        while leader_of(live) is None:
            if time.monotonic() > deadline:
                raise RuntimeError("cluster never elected")
            time.sleep(0.05)
        for _ in range(FAILOVER_NODES):
            rpc(live, "Node.Register", {"Node": to_dict(mock.node())})

        jobs, submit_t, eval_of = [], {}, {}
        lat = {}
        watch_stop = _threading.Event()

        def watcher():
            """Record each eval's submit->terminal latency against
            whichever server currently leads."""
            while True:
                ldr = leader_of(live)
                if ldr is not None:
                    state = ldr.server.state
                    now = time.monotonic()
                    for eid in [e for e in list(eval_of) if e not in lat]:
                        ev = state.eval_by_id(eid)
                        if ev is not None and ev.Status in terminal:
                            lat[eid] = now - submit_t[eid]
                if watch_stop.is_set():
                    return
                time.sleep(0.02)

        wt = _threading.Thread(target=watcher, name="failover-watch",
                               daemon=True)
        wt.start()

        kill_at = max(1, FAILOVER_JOBS // 3)
        recovery_s = None
        t0 = time.monotonic()
        for i in range(FAILOVER_JOBS):
            if i == kill_at:
                victim = leader_of(live)
                if victim is not None:
                    live.remove(victim)
                    tk = time.monotonic()
                    victim.shutdown()
                    while leader_of(live) is None:
                        if time.monotonic() - tk > 30:
                            raise RuntimeError("no post-kill leader")
                        time.sleep(0.02)
                    recovery_s = time.monotonic() - tk
            prio = tiers[i % len(tiers)]
            job = build_job(FAILOVER_PER_JOB)
            job.Priority = prio
            jobs.append(job)
            resp = rpc(live, "Job.Register", {"Job": to_dict(job)})
            # submit_t before eval_of: the watcher keys off eval_of.
            submit_t[resp["EvalID"]] = time.monotonic()
            eval_of[resp["EvalID"]] = prio
            time.sleep(0.005)

        drain_deadline = time.monotonic() + 180
        while len(lat) < len(eval_of):
            if time.monotonic() > drain_deadline:
                break
            time.sleep(0.05)
        t_total = time.monotonic() - t0
        watch_stop.set()
        wt.join(timeout=10)

        ldr = leader_of(live)
        end_wait = time.monotonic() + 15
        while ldr is None and time.monotonic() < end_wait:
            # A second election can be mid-flight at sample time.
            time.sleep(0.05)
            ldr = leader_of(live)
        if ldr is None:
            # Emit a failing gate rather than crash: the exit-2 contract
            # is fail-AFTER-emit.
            out["gate"] = {"ok": False, "error": "no leader after drain",
                           "lost_evals": len(eval_of) - len(lat),
                           "duplicate_allocs": None, "placed": None,
                           "expected": len(jobs) * FAILOVER_PER_JOB}
            return out
        state = ldr.server.state
        lost_evals = len(eval_of) - len(lat)
        placed, dup, all_ids = 0, 0, set()
        for job in jobs:
            job_live = [a for a in state.allocs_by_job(job.ID)
                        if not a.terminal_status()]
            placed += len(job_live)
            for a in job_live:
                if a.ID in all_ids:
                    dup += 1
                all_ids.add(a.ID)
            if len(job_live) != FAILOVER_PER_JOB:
                lost_evals = max(lost_evals, 1)  # under/overshoot = loss
        by_tier = {}
        for eid, prio in eval_of.items():
            if eid in lat:
                by_tier.setdefault(tier_name[prio], []).append(lat[eid])
        out.update({
            "placements_sec": round(placed / t_total, 2)
            if t_total > 0 else None,
            "storm_s": round(t_total, 2),
            "recovery_s": round(recovery_s, 3)
            if recovery_s is not None else None,
            "tier_latency_ms": {t: _pctiles_ms(v)
                                for t, v in sorted(by_tier.items())},
            "slo_burn": dict(zip(("high", "normal", "low"),
                                 [round(b, 4) for b in
                                  ldr.server.eval_broker.slo_burn()])),
            "streaming_snapshot": ldr.server.raft.node.log
            .latest_snapshot_chunks() is not None,
            "gate": {
                "ok": lost_evals == 0 and dup == 0
                and placed == len(jobs) * FAILOVER_PER_JOB
                and recovery_s is not None and recovery_s < 30.0,
                "lost_evals": lost_evals,
                "duplicate_allocs": dup,
                "placed": placed,
                "expected": len(jobs) * FAILOVER_PER_JOB,
            },
        })
        return out
    finally:
        for n in nodes:
            try:
                n.shutdown()
            except Exception:
                pass


def bench_federation_storm():
    """config7_federation (ISSUE 14): a mixed-priority storm concentrated
    in ONE region of a real 3-region federated cluster — cross-region
    forwarding at ingress (two thirds of the storm arrives through the
    other regions' edges), follower-snapshot workers, per-region QoS —
    A/B'd against the all-on-leader baseline: the SAME three servers as
    ONE global raft domain (the pre-federation config5_multidc shape),
    where every commit replicates through one consensus group and every
    worker, commit, and watch rides its single leader. Same total
    fleet, same job multiset, same server count — the delta is the
    topology: region-local authority vs global consensus. Reps
    interleaved with ALTERNATING within-pair order, max-of-reps on
    total evals/s.

    Records per-region evals/s, cross-region forward latency
    percentiles, and per-region high-tier submit->terminal p99. Gate
    (exit-2, fail-after-emit like placement parity): zero lost evals,
    zero duplicate allocs, every job at exactly its asked-for live
    allocs in its HOME region only, the storm-free regions' high-tier
    p99 within the high SLO deadline, and the federated side proving it
    actually shared snapshots (SnapshotSource reuse > 0)."""
    from nomad_tpu import mock
    from nomad_tpu.federation import FederationConfig
    from nomad_tpu.gossip import GossipConfig
    from nomad_tpu.qos import QoSConfig
    from nomad_tpu.qos.admission import QoSBackpressureError
    from nomad_tpu.raft import RaftConfig
    from nomad_tpu.rpc.cluster import ClusterServer
    from nomad_tpu.server import ServerConfig
    from nomad_tpu.structs import to_dict
    from nomad_tpu.structs.structs import (
        EvalStatusCancelled,
        EvalStatusComplete,
        EvalStatusFailed,
    )

    terminal = (EvalStatusComplete, EvalStatusFailed, EvalStatusCancelled)
    raft_cfg = RaftConfig(heartbeat_interval=0.02,
                          election_timeout_min=0.08,
                          election_timeout_max=0.16, apply_timeout=5.0)
    # Election-free storm, but a throttled bench box: election-free
    # deadlines would burn the high ring on compute alone. The quiet
    # regions are gated against deadlines_s[0].
    deadlines = (5.0, 15.0, 60.0)
    storm_region = "east"
    quiet_regions = ("west", "north")
    regions = (storm_region,) + quiet_regions
    tiers = (80, 20, 50)

    def gaddr(cs):
        ml = cs.membership.memberlist
        return f"{ml.addr}:{ml.port}"

    def boot(name, region, n_workers, fed, expect=1, join=None):
        cs = ClusterServer(ServerConfig(
            node_id="", region=region, num_schedulers=n_workers,
            scheduler_window=8, bootstrap_expect=expect,
            # Mock nodes never heartbeat; multi-minute A/B reps must not
            # watch the fleet expire mid-rep (same treatment as every
            # standalone served bench).
            min_heartbeat_ttl=24 * 3600.0, heartbeat_grace=24 * 3600.0,
            # DEVICE chain on both sides: N-worker overlap is a property
            # of the device-chained architecture (async dispatch +
            # GIL-releasing fetches); the host-numpy fallback would
            # swallow every window into GIL-bound Python where the
            # leader's 3 workers and the federation's 3 regions can only
            # ever tie (same treatment as the worker_scaling sweep).
            host_placement=False,
            # Tiered queues + per-region SLO tracking ON; burn-shed
            # disarmed (burn can never exceed 1.0): warmup compiles blow
            # tier deadlines and would poison the burn ring into
            # shedding the first timed rep. The shed paths have their
            # own gates (tests/test_federation.py, slo_storm's probes).
            qos=QoSConfig(enabled=True, deadlines_s=deadlines,
                          burn_shed=1.1),
            federation=fed))
        cs.connect([], raft_config=raft_cfg)
        cs.start()
        cs.enable_gossip(name, join=join,
                         gossip_config=GossipConfig.fast())
        return cs

    class _Edge:
        """One federated region server as a submission/read target."""

        def __init__(self, cs):
            self.cs = cs

        def handle(self, method, body):
            return self.cs.endpoints.handle(method, body)

        def eval_by_id(self, eid):
            return self.cs.server.state.eval_by_id(eid)

        def allocs_by_job(self, job_id):
            return self.cs.server.state.allocs_by_job(job_id)

    class _Domain:
        """The baseline's 3-server raft domain as the same target shape:
        submits retry across servers (an election mid-storm is the
        domain's problem, not the client's), reads go to the current
        leader's replicated store."""

        def __init__(self, servers):
            self.servers = servers

        def leader(self):
            for cs in self.servers:
                try:
                    if (cs.server is not None and cs.server.is_leader()
                            and cs.server._leader):
                        return cs
                except Exception:
                    pass
            return None

        def handle(self, method, body, attempts=150, delay=0.05):
            # The failover bench's retry shape: any server may answer;
            # an election or in-flight leader hop retries (backpressure
            # included — submit() counts it via its own layer when the
            # edge is a single region server; here the pooled domain
            # just keeps trying, which is what a real client pool does).
            last = None
            for _ in range(attempts):
                targets = list(self.servers)
                random.shuffle(targets)
                for cs in targets:
                    try:
                        return cs.endpoints.handle(method, dict(body))
                    except Exception as exc:
                        last = exc
                time.sleep(delay)
            raise last if last is not None \
                else RuntimeError("no servers")

        def eval_by_id(self, eid):
            ldr = self.leader()
            return None if ldr is None \
                else ldr.server.state.eval_by_id(eid)

        def allocs_by_job(self, job_id):
            ldr = self.leader()
            return [] if ldr is None \
                else ldr.server.state.allocs_by_job(job_id)

    def submit(edge, job, attempts=40):
        """One registration through a submission target; a QoS/remote-
        shed 429 — raised locally at the edge or crossing the forward
        wire as a typed RPCError — retries like the API client would
        (shed is backpressure, not loss)."""
        from nomad_tpu.rpc.pool import RPCError

        sheds = 0
        for _ in range(attempts):
            try:
                return edge.handle(
                    "Job.Register", {"Job": to_dict(job)}), sheds
            except QoSBackpressureError:
                sheds += 1
            except RPCError as exc:
                if exc.remote_type != "QoSBackpressureError":
                    raise
                sheds += 1
            time.sleep(0.1)
        raise RuntimeError("registration shed past retry budget")

    sides = {}
    all_servers = []
    out = {"regions": list(regions), "nodes_per_region": FED_NODES,
           "storm_jobs": FED_JOBS, "quiet_high_jobs": FED_QUIET_HIGH,
           "per_job": FED_PER_JOB, "reps": FED_REPS,
           "high_deadline_s": deadlines[0]}
    try:
        # ---- boot both sides (live simultaneously, like every A/B here)
        fed_nodes = {}
        # Staleness bound matched to this box's window cadence (~0.3s a
        # window on the throttled CPU, with multi-hundred-ms GC/noise
        # stalls between them): the source must plausibly serve two
        # consecutive windows or the "shared snapshot" side degrades to
        # a fresh pin per window. reject_after_s scales with it.
        fed_cfg = dict(enabled=True, max_staleness_s=1.5,
                       reject_after_s=10.0)
        first = boot("fed-east", storm_region, 1,
                     FederationConfig(**fed_cfg))
        fed_nodes[storm_region] = first
        for r in quiet_regions:
            fed_nodes[r] = boot(f"fed-{r}", r, 1,
                                FederationConfig(**fed_cfg),
                                join=[gaddr(first)])
        all_servers.extend(fed_nodes.values())
        sides["federated"] = {r: _Edge(cs)
                              for r, cs in fed_nodes.items()}
        # The all-on-leader baseline: the SAME THREE SERVERS as one
        # global raft domain — every commit replicates to two followers
        # over real RPC, all workers run on whichever server leads.
        base_servers = [boot("base-0", storm_region, len(regions),
                             None, expect=len(regions))]
        for i in (1, 2):
            base_servers.append(boot(f"base-{i}", storm_region,
                                     len(regions), None,
                                     expect=len(regions),
                                     join=[gaddr(base_servers[0])]))
        all_servers.extend(base_servers)
        domain = _Domain(base_servers)
        sides["leader"] = {storm_region: domain}
        for cs in fed_nodes.values():
            deadline = time.monotonic() + 30
            while not cs.server.is_leader():
                if time.monotonic() > deadline:
                    raise RuntimeError("region never elected")
                time.sleep(0.02)
        deadline = time.monotonic() + 30
        while domain.leader() is None:
            if time.monotonic() > deadline:
                raise RuntimeError("baseline domain never elected")
            time.sleep(0.02)
        # Gossip convergence: every federated region must know the rest
        # before the first cross-region forward.
        deadline = time.monotonic() + 30
        while any(
                not fed_nodes[r].membership.region_servers(other)
                for r in regions for other in regions if other != r):
            if time.monotonic() > deadline:
                raise RuntimeError("regions never converged")
            time.sleep(0.05)
        # ---- fleets: each region its own; the baseline domain ALL of it
        for r in regions:
            for node in build_nodes(FED_NODES):
                fed_nodes[r].endpoints.handle(
                    "Node.Register", {"Node": to_dict(node)})
        for node in build_nodes(FED_NODES * len(regions)):
            domain.handle("Node.Register", {"Node": to_dict(node)})

        def storm_plan(side):
            """The rep's job multiset: (job, home region, edge server).
            Same shapes/priorities on both sides; the baseline's home is
            always its one region and every submit is local."""
            cluster = sides[side]
            fed = side == "federated"
            plan = []
            for i in range(FED_JOBS):
                job = build_job(FED_PER_JOB)
                job.Priority = tiers[i % len(tiers)]
                home = storm_region
                edge = regions[i % len(regions)] if fed else storm_region
                job.Region = home if fed else ""
                plan.append((job, home, cluster[edge], cluster[home]))
            for r in quiet_regions:
                home = r if fed else storm_region
                for _ in range(FED_QUIET_HIGH):
                    job = build_job(FED_PER_JOB)
                    job.Priority = 80
                    job.Region = home if fed else ""
                    plan.append((job, home,
                                 cluster[home], cluster[home]))
            return plan

        def run_rep(side, fwd_lats, tier_lats, shed_count):
            """Submit one full storm CONCURRENTLY (one submitter lane
            per edge server — wire hops overlap scheduling, as real
            clients would — the same lane count on both sides), drain
            it, and return (total_rate, per_region_rate, rep_checks)."""
            import threading as _threading

            plan = storm_plan(side)
            # Same submit concurrency on BOTH sides (3 client lanes);
            # only the fed side's entries carry cross-region edges.
            lanes: dict = {}
            for i, entry in enumerate(plan):
                lanes.setdefault(i % len(regions), []).append(entry)
            submit_t, eval_home, eval_meta = {}, {}, {}
            meta_lock = _threading.Lock()

            def lane(entries):
                for job, home, edge, home_cs in entries:
                    ts = time.monotonic()
                    resp, sheds = submit(edge, job)
                    now = time.monotonic()
                    with meta_lock:
                        shed_count[0] += sheds
                        if edge is not home_cs:
                            fwd_lats.append(now - ts)
                        eid = resp["EvalID"]
                        submit_t[eid] = ts
                        eval_home[eid] = home
                        eval_meta[eid] = (job, home_cs)

            t0 = time.monotonic()
            threads = [_threading.Thread(target=lane, args=(ents,),
                                         name=f"fed-submit-{i}")
                       for i, ents in enumerate(lanes.values())]
            for t in threads:
                t.start()
            lat, done_at = {}, {}
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                now = time.monotonic()
                with meta_lock:
                    pending = [(eid, meta)
                               for eid, meta in eval_meta.items()
                               if eid not in lat]
                for eid, (job, home_cs) in pending:
                    ev = home_cs.eval_by_id(eid)
                    if ev is not None and ev.Status in terminal:
                        lat[eid] = now - submit_t[eid]
                        done_at[eid] = now
                if (not any(t.is_alive() for t in threads)
                        and len(lat) == len(eval_meta)):
                    break
                time.sleep(0.02)
            for t in threads:
                t.join(timeout=10)
            t_total = (max(done_at.values()) - t0) if done_at else 0.0
            lost = len(submit_t) - len(lat)
            per_region = {}
            for eid in lat:
                per_region.setdefault(eval_home[eid], 0)
                per_region[eval_home[eid]] += 1
                job, home_cs = eval_meta[eid]
                tier_lats.setdefault(eval_home[eid], {}).setdefault(
                    job.Priority, []).append(lat[eid])
            placed, dup, misplaced, all_ids = 0, 0, 0, set()
            for eid, (job, home_cs) in eval_meta.items():
                live = [a for a in home_cs.allocs_by_job(job.ID)
                        if not a.terminal_status()]
                placed += len(live)
                for a in live:
                    if a.ID in all_ids:
                        dup += 1
                    all_ids.add(a.ID)
                if side == "federated":
                    for r, cs in fed_nodes.items():
                        if r != eval_home[eid] \
                                and cs.server.state.job_by_id(job.ID):
                            misplaced += 1
            checks = {"lost": lost, "dup": dup, "placed": placed,
                      "expected": len(plan) * FED_PER_JOB,
                      "misplaced": misplaced}
            rate = round(len(lat) / t_total, 2) if t_total else 0.0
            rates_r = {r: round(n / t_total, 2) if t_total else 0.0
                       for r, n in sorted(per_region.items())}
            return rate, rates_r, checks

        # ---- warm both sides (compile/caches), then interleaved reps
        for side in ("federated", "leader"):
            run_rep(side, [], {}, [0])
        _tune_gc()
        rates = {"federated": [], "leader": []}
        region_rates = {"federated": [], "leader": []}
        fwd_lats, shed_count = [], [0]
        tier_lats = {"federated": {}, "leader": {}}
        checks_all = []
        for rep in range(FED_REPS):
            order = (("federated", "leader") if rep % 2 == 0
                     else ("leader", "federated"))
            for side in order:
                rate, rates_r, checks = run_rep(
                    side, fwd_lats if side == "federated" else [],
                    tier_lats[side], shed_count)
                rates[side].append(rate)
                region_rates[side].append(rates_r)
                checks["side"] = side
                checks_all.append(checks)
                _freeze_heap()

        def tier_pct(side):
            name = {80: "high", 20: "low", 50: "normal"}
            return {r: {name[p]: _pctiles_ms(v)
                        for p, v in sorted(by_prio.items())}
                    for r, by_prio in sorted(tier_lats[side].items())}

        fed_srcs = {r: cs.server.fed_source.stats()
                    for r, cs in fed_nodes.items()}
        quiet_p99 = max(
            float(np.percentile(
                tier_lats["federated"].get(r, {}).get(80) or [0.0], 99))
            for r in quiet_regions)
        lost = sum(c["lost"] for c in checks_all)
        dup = sum(c["dup"] for c in checks_all)
        misplaced = sum(c["misplaced"] for c in checks_all)
        placed_ok = all(c["placed"] == c["expected"] for c in checks_all)
        reused = sum(s["Reused"] for s in fed_srcs.values())
        out.update({
            "federated": {
                "evals_sec": max(rates["federated"]),
                "rep_rates": rates["federated"],
                "per_region_evals_sec": region_rates["federated"],
                "tier_latency_ms": tier_pct("federated"),
                "snapshot_sources": fed_srcs,
                "forward_latency_ms": _pctiles_ms(fwd_lats),
                "forwards": len(fwd_lats),
                "backpressure_sheds": shed_count[0],
            },
            "leader": {
                "evals_sec": max(rates["leader"]),
                "rep_rates": rates["leader"],
                "tier_latency_ms": tier_pct("leader"),
            },
            "speedup": (speedup := (round(max(rates["federated"])
                                          / max(rates["leader"]), 3)
                                    if max(rates["leader"]) else None)),
            "quiet_high_p99_ms": round(quiet_p99 * 1e3, 2),
            "gate": {
                "ok": (lost == 0 and dup == 0 and misplaced == 0
                       and placed_ok and reused > 0
                       and quiet_p99 <= deadlines[0]
                       and speedup is not None and speedup >= 1.0),
                "lost_evals": lost,
                "duplicate_allocs": dup,
                "misplaced_jobs": misplaced,
                "placed_ok": placed_ok,
                "snapshot_reuse": reused,
                "quiet_high_p99_within_slo": quiet_p99 <= deadlines[0],
                "beats_all_on_leader": speedup is not None
                and speedup >= 1.0,
            },
        })
        return out
    finally:
        for cs in all_servers:
            try:
                cs.shutdown()
            except Exception:
                pass


def build_plain_job(per_eval=PER_EVAL):
    """BASELINE config 2's shape: resource-only bin-packing, no constraint
    checkers at all."""
    job = build_job(per_eval)
    job.Constraints = []
    for tg in job.TaskGroups:
        tg.Constraints = []
        for task in tg.Tasks:
            task.Constraints = []
    return job


def build_system_job():
    """BASELINE config 4's shape: one alloc per eligible node, full
    feasibility chain (driver + implicit constraints)."""
    from nomad_tpu import mock

    job = mock.system_job()
    task = job.TaskGroups[0].Tasks[0]
    task.Resources.CPU = 20
    task.Resources.MemoryMB = 16
    task.Resources.DiskMB = 150
    task.Resources.Networks = []
    task.Services = []
    if task.LogConfig is not None:
        task.LogConfig.MaxFiles = 1
        task.LogConfig.MaxFileSizeMB = 1
    return job


def _capture_sweep_plan(n_nodes):
    """One fixed-seed system sweep plan (with its columnar descriptor)
    captured WITHOUT committing — the input both store-commit paths
    replay."""
    import logging
    from nomad_tpu import mock
    from nomad_tpu.scheduler.system_sched import SystemScheduler
    from nomad_tpu.state.state_store import StateStore
    from nomad_tpu.structs import PlanResult
    from nomad_tpu.structs.structs import (
        EvalStatusPending,
        EvalTriggerJobRegister,
    )
    from nomad_tpu.tensor import TensorIndex

    class _Capture:
        def __init__(self):
            self.plans = []

        def plan_queue_depth(self):
            return 0

        def submit_plan(self, plan):
            self.plans.append(plan)
            r = PlanResult()
            r.NodeUpdate = dict(plan.NodeUpdate)
            r.NodeAllocation = dict(plan.NodeAllocation)
            r.AllocIndex = 1
            return r, None

        def update_eval(self, ev):
            pass

        def create_eval(self, ev):
            pass

        def reblock_eval(self, ev):
            pass

    store = StateStore()
    tindex = TensorIndex.attach(store)
    idx = 0
    for node in build_nodes(n_nodes):
        idx += 1
        store.upsert_node(idx, node)
    job = build_system_job()
    idx += 1
    store.upsert_job(idx, job)
    ev = mock.eval()
    ev.JobID = job.ID
    ev.Type = job.Type
    ev.TriggeredBy = EvalTriggerJobRegister
    ev.Status = EvalStatusPending
    planner = _Capture()
    SystemScheduler(store, planner, tindex,
                    logging.getLogger("bench.store"),
                    rng=random.Random(7)).process(ev)
    return planner.plans[0]


def bench_store_commit(n_nodes, reps=3):
    """State-store commit microbench (the `store` section): the SAME
    fixed-seed system sweep committed per-object (the pre-columnar path,
    one upsert per alloc) and columnar (one ApplySweepBatch scatter) into
    fresh FSMs. Reports per-alloc commit µs for both paths, the columnar
    batch scatter ms, and the raft entry bytes of both encodings (the
    wire cost of a chunk). Max-of-reps (min time) like the A/B protocol —
    the commit is deterministic CPU, so the best rep is the least-noisy
    one."""
    import msgpack
    from nomad_tpu.server.fsm import FSM, MessageType
    from nomad_tpu.server.plan_apply import _encode_result
    from nomad_tpu.structs import PlanResult, to_dict

    plan = _capture_sweep_plan(n_nodes)
    allocs = [a for placed in plan.NodeAllocation.values() for a in placed]
    n = len(allocs)
    obj_payload = {"Job": plan.Job, "Alloc": allocs}
    result = PlanResult(NodeAllocation=dict(plan.NodeAllocation))
    result._sweep = plan._sweep
    element, is_sweep = _encode_result(plan, result)
    assert is_sweep, "sweep plan lost its columnar descriptor"
    col_payload = {"Batch": [element]}
    # Entry bytes BEFORE any apply mutates the payload objects (the
    # object path stamps Job/indexes into the shared allocs).
    obj_bytes = len(msgpack.packb(
        (int(MessageType.AllocUpdate), to_dict(obj_payload)),
        use_bin_type=True))
    col_bytes = len(msgpack.packb(
        (int(MessageType.ApplySweepBatch), to_dict(col_payload)),
        use_bin_type=True))

    def timed(msg, payload):
        best = float("inf")
        for _ in range(reps):
            fsm = FSM()
            t0 = time.perf_counter()
            fsm.apply(1, msg, payload)
            best = min(best, time.perf_counter() - t0)
        return best

    t_obj = timed(MessageType.AllocUpdate, obj_payload)
    t_col = timed(MessageType.ApplySweepBatch, col_payload)
    return {
        "nodes": n_nodes,
        "allocs": n,
        "object_per_alloc_us": round(t_obj / n * 1e6, 2),
        "columnar_per_alloc_us": round(t_col / n * 1e6, 3),
        "columnar_batch_scatter_ms": round(t_col * 1e3, 3),
        "commit_speedup": round(t_obj / t_col, 1) if t_col else None,
        "raft_entry_bytes": {"object": obj_bytes, "columnar": col_bytes,
                             "ratio": round(obj_bytes / col_bytes, 1)
                             if col_bytes else None},
    }


def _capture_service_plans(n_nodes, per_eval=PER_EVAL, n_plans=1):
    """Fixed-seed service-window plans (each with its columnar service
    descriptor) captured through the pipelined fast path's build —
    prepare_batch -> host placement kernel -> compact -> collect_build —
    nothing committed. One store/tensor boot serves every capture; the
    plans are the input both store-commit paths replay."""
    import logging

    from nomad_tpu import mock
    from nomad_tpu.scheduler import kernels
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.stack import GenericStack, WindowAccumulator
    from nomad_tpu.scheduler.util import (
        diff_allocs,
        materialize_task_groups,
        ready_nodes_in_dcs,
    )
    from nomad_tpu.state.state_store import StateStore
    from nomad_tpu.structs.structs import EvalTriggerJobRegister
    from nomad_tpu.tensor import ClassEligibility, TensorIndex

    store = StateStore()
    tindex = TensorIndex.attach(store)
    idx = 0
    for node in build_nodes(n_nodes):
        idx += 1
        store.upsert_node(idx, node)
    plans = []
    for k in range(n_plans):
        job = build_job(per_eval)
        idx += 1
        store.upsert_job(idx, job)
        ev = mock.eval()
        ev.JobID = job.ID
        ev.Type = job.Type
        ev.TriggeredBy = EvalTriggerJobRegister
        snap = store.snapshot()
        plan = ev.make_plan(job, copy_job=False)
        ctx = EvalContext(snap, plan, logging.getLogger("bench.store"))
        stack = GenericStack(ctx, tindex, batch=False,
                             rng=random.Random(7 + k))
        diff = diff_allocs(job, {}, materialize_task_groups(job), [])
        nodes, _ = ready_nodes_in_dcs(snap, job.Datacenters)
        nt = tindex.nt
        cand_mask = np.zeros(nt.n_rows, dtype=bool)
        for n in nodes:
            row = nt.row_of.get(n.ID)
            if row is not None:
                cand_mask[row] = True
        stack.job = job
        stack.adopt_nodes({n.ID: n for n in nodes}, cand_mask,
                          ClassEligibility(nt, nodes))
        prep = stack.prepare_batch([t.TaskGroup for t in diff.place])
        res = stack.dispatch_host(prep)
        cr = kernels.compact_host(np.asarray(res.packed), prep.n_valid)
        ok = stack.collect_build(prep, cr, ev.ID, job, diff.place, plan,
                                 {}, WindowAccumulator(nt.n_rows))
        assert ok and getattr(plan, "_sweep", None) is not None, \
            "service window lost its columnar descriptor"
        plans.append(plan)
    return plans


def bench_store_commit_window(per_eval=PER_EVAL, reps=5):
    """Commit A/B at the SERVICE window shapes: the SAME fixed-seed
    service-window plans committed per-object (the pre-columnar service
    path, one upsert per alloc) and columnar (ApplySweepBatch scatter)
    into fresh FSMs. Two shapes: one lone plan (the idle-broker commit)
    and the applier's 16-plan group entry (_APPLY_BATCH — what a storm
    window actually commits as; the per-entry fixed costs amortize
    there, which is where the --smoke gate holds the speedup)."""
    import msgpack
    from nomad_tpu.server.fsm import FSM, MessageType
    from nomad_tpu.server.plan_apply import _APPLY_BATCH, _encode_result
    from nomad_tpu.structs import PlanResult, to_dict

    plans = _capture_service_plans(min(N_NODES, 2048), per_eval,
                                   n_plans=_APPLY_BATCH)
    elements = []
    obj_groups = []
    for plan in plans:
        result = PlanResult(NodeAllocation=dict(plan.NodeAllocation))
        result._sweep = plan._sweep
        element, is_sweep = _encode_result(plan, result)
        assert is_sweep, "service plan lost its columnar descriptor"
        elements.append(element)
        obj_groups.append({"Job": plan.Job,
                           "Alloc": [a for v in plan.NodeAllocation.values()
                                     for a in v]})
    obj_bytes = len(msgpack.packb(
        (int(MessageType.AllocUpdate), to_dict(obj_groups[0])),
        use_bin_type=True))
    col_bytes = len(msgpack.packb(
        (int(MessageType.ApplySweepBatch),
         to_dict({"Batch": [elements[0]]})),
        use_bin_type=True))

    def timed(msg, payload):
        best = float("inf")
        for _ in range(reps):
            fsm = FSM()
            t0 = time.perf_counter()
            fsm.apply(1, msg, payload)
            best = min(best, time.perf_counter() - t0)
        return best

    t_obj = timed(MessageType.AllocUpdate, obj_groups[0])
    t_col = timed(MessageType.ApplySweepBatch, {"Batch": [elements[0]]})
    n_storm = per_eval * len(plans)
    ts_obj = timed(MessageType.AllocUpdate, {"Batch": obj_groups})
    ts_col = timed(MessageType.ApplySweepBatch, {"Batch": elements})
    return {
        "allocs": per_eval,
        "object_per_alloc_us": round(t_obj / per_eval * 1e6, 2),
        "columnar_per_alloc_us": round(t_col / per_eval * 1e6, 3),
        "columnar_batch_scatter_ms": round(t_col * 1e3, 3),
        "commit_speedup": round(t_obj / t_col, 1) if t_col else None,
        "raft_entry_bytes": {"object": obj_bytes, "columnar": col_bytes,
                             "ratio": round(obj_bytes / col_bytes, 1)
                             if col_bytes else None},
        "storm_group": {
            "plans": len(plans),
            "allocs": n_storm,
            "object_per_alloc_us": round(ts_obj / n_storm * 1e6, 2),
            "columnar_per_alloc_us": round(ts_col / n_storm * 1e6, 3),
            "commit_speedup": round(ts_obj / ts_col, 1) if ts_col else None,
        },
    }


def bench_service_columnar_ab():
    """Service-path commit A/B end to end: the SAME storm served with
    columnar service commits on (ApplySweepBatch + SweepSegment scatter)
    vs off (per-object upserts, the pre-columnar path). Both servers live
    simultaneously, timed reps interleaved with the within-pair order
    ALTERNATING each rep (this box's cgroup quota punishes whoever runs
    second), max-of-reps compared. Records per-side rates + storm latency
    percentiles, the columnar server's segment counters (the proof the
    storm took the new path), and a parity gate: both sides must place
    the full storm every rep."""
    from nomad_tpu.server import Server, ServerConfig

    nodes = build_nodes(SVC_AB_NODES)
    out = {"nodes": SVC_AB_NODES, "evals_per_rep": SVC_AB_EVALS}
    servers = {}
    try:
        for mode, columnar in (("columnar", True), ("object", False)):
            srv = Server(ServerConfig(num_schedulers=N_WORKERS,
                                      pipelined_scheduling=True,
                                      scheduler_window=WINDOW,
                                      service_columnar=columnar,
                                      min_heartbeat_ttl=24 * 3600.0,
                                      heartbeat_grace=24 * 3600.0))
            srv.establish_leadership()
            for node in nodes:
                srv.node_register(node)
            run = _make_storm_runner(srv)
            run(3)
            run(3)
            srv.tindex.nt.warm_device()
            run(SVC_AB_EVALS)  # full-size warm storm (compiles)
            servers[mode] = (srv, run)
        _tune_gc()
        # Baseline the cumulative segment counter AFTER warmups so the
        # parity gate proves the TIMED reps took the columnar path (a
        # silent fallback-to-object mid-rep would otherwise hide behind
        # warmup segments).
        base_service = servers["columnar"][0].state.columnar_stats()[
            "Batches"].get("service", 0)
        rates = {"columnar": [], "object": []}
        lats = {"columnar": [], "object": []}
        placed = {"columnar": [], "object": []}
        for rep in range(SVC_AB_REPS):
            order = (("columnar", "object") if rep % 2 == 0
                     else ("object", "columnar"))
            for mode in order:
                srv, run = servers[mode]
                for w in srv.workers:
                    if hasattr(w, "quiesce"):
                        w.quiesce(30.0)
                t0 = time.perf_counter()
                eval_ids = run(SVC_AB_EVALS, latencies=lats[mode])
                rates[mode].append(
                    round(SVC_AB_EVALS / (time.perf_counter() - t0), 2))
                _freeze_heap()
                placed[mode].append(sum(
                    1 for eid in eval_ids
                    for _ in srv.state.allocs_by_eval(eid)))
        for mode in ("columnar", "object"):
            out[mode] = {"evals_sec": max(rates[mode]),
                         "rep_rates": rates[mode],
                         "storm_latency_ms": _pctiles_ms(lats[mode]),
                         "placed_per_rep": placed[mode]}
        out["speedup"] = round(max(rates["columnar"])
                               / max(rates["object"]), 3) \
            if rates["object"] else None
        out["columnar_store"] = servers["columnar"][0].state.columnar_stats()
        out["object_store_batches"] = \
            servers["object"][0].state.columnar_stats()["Batches"]
        out["timed_service_batches"] = \
            out["columnar_store"]["Batches"].get("service", 0) - base_service
        want = SVC_AB_EVALS * PER_EVAL
        out["parity_ok"] = bool(
            all(p == want for mode in placed for p in placed[mode])
            and out["timed_service_batches"] >= 1
            and not out["object_store_batches"])
        out["expected_allocs"] = want
        return out
    finally:
        for srv, _ in servers.values():
            srv.shutdown()


def bench_event_stream():
    """Event-broker overhead A/B end to end: the SAME storm served with
    the event stream ARMED (broker on the FSM apply path + ONE live
    subscriber draining fan-out rows for the whole run — the realistic
    deployed shape) vs DISARMED (event_buffer_size=0: no broker object;
    apply pays one attribute check). Both servers live simultaneously,
    timed reps interleaved with ALTERNATING within-pair order,
    max-of-reps compared. Records per-side rates + storm tails, the
    armed broker's counters (published / dropped / ring depth — the
    nomad.events.* stats keys), and a parity gate: both sides place the
    full storm every rep, the subscriber consumed real traffic, and the
    bounded queue never dropped."""
    import threading

    from nomad_tpu.server import Server, ServerConfig

    nodes = build_nodes(EVENTS_AB_NODES)
    out = {"nodes": EVENTS_AB_NODES, "evals_per_rep": EVENTS_AB_EVALS}
    servers = {}
    stop = threading.Event()
    consumed = {"frames": 0, "events": 0}
    drainer = None
    try:
        for mode, buf in (("armed", 4096), ("disarmed", 0)):
            srv = Server(ServerConfig(num_schedulers=N_WORKERS,
                                      pipelined_scheduling=True,
                                      scheduler_window=WINDOW,
                                      event_buffer_size=buf,
                                      min_heartbeat_ttl=24 * 3600.0,
                                      heartbeat_grace=24 * 3600.0))
            srv.establish_leadership()
            for node in nodes:
                srv.node_register(node)
            run = _make_storm_runner(srv)
            run(3)
            run(3)
            srv.tindex.nt.warm_device()
            run(EVENTS_AB_EVALS)  # full-size warm storm (compiles)
            servers[mode] = (srv, run)
        broker = servers["armed"][0].fsm.events
        sub = broker.subscribe(from_index=0, fanout=True,
                               queue_size=262_144)

        def drain_live():
            while not stop.is_set():
                frame = sub.next(timeout=0.2)
                if frame is None:
                    continue
                consumed["frames"] += 1
                consumed["events"] += len(frame["Events"])

        drainer = threading.Thread(target=drain_live,
                                   name="bench-events-sub", daemon=True)
        drainer.start()
        _tune_gc()
        rates = {"armed": [], "disarmed": []}
        lats = {"armed": [], "disarmed": []}
        placed = {"armed": [], "disarmed": []}
        for rep in range(EVENTS_AB_REPS):
            order = (("armed", "disarmed") if rep % 2 == 0
                     else ("disarmed", "armed"))
            for mode in order:
                srv, run = servers[mode]
                for w in srv.workers:
                    if hasattr(w, "quiesce"):
                        w.quiesce(30.0)
                t0 = time.perf_counter()
                eval_ids = run(EVENTS_AB_EVALS, latencies=lats[mode])
                rates[mode].append(
                    round(EVENTS_AB_EVALS / (time.perf_counter() - t0), 2))
                _freeze_heap()
                placed[mode].append(sum(
                    1 for eid in eval_ids
                    for _ in srv.state.allocs_by_eval(eid)))
        # Let the drainer catch the tail of the last rep before the
        # drop/consumption accounting freezes.
        deadline = time.monotonic() + 10
        while (broker.stats()["Tail"] > sub.last_index
               and time.monotonic() < deadline):
            time.sleep(0.05)
        stop.set()
        drainer.join(timeout=5)
        stats = broker.stats()
        for mode in ("armed", "disarmed"):
            out[mode] = {"evals_sec": max(rates[mode]),
                         "rep_rates": rates[mode],
                         "storm_latency_ms": _pctiles_ms(lats[mode]),
                         "placed_per_rep": placed[mode]}
        out["overhead_pct"] = round(
            (1.0 - max(rates["armed"]) / max(rates["disarmed"]))
            * 100.0, 2) if rates["disarmed"] else None
        out["events"] = {"published": stats["Published"],
                         "dropped": stats["Dropped"],
                         "ring_depth": stats["Depth"],
                         "ring_size": stats["Size"],
                         "subscriber_frames": consumed["frames"],
                         "subscriber_events": consumed["events"]}
        want = EVENTS_AB_EVALS * PER_EVAL
        out["parity_ok"] = bool(
            all(p == want for mode in placed for p in placed[mode])
            and stats["Dropped"] == 0
            and consumed["events"] > 0
            and servers["disarmed"][0].fsm.events is None)
        out["expected_allocs"] = want
        return out
    finally:
        stop.set()
        if drainer is not None:
            drainer.join(timeout=5)
        for srv, _ in servers.values():
            srv.shutdown()


def bench_digest():
    """Replica-digest overhead A/B end to end: the SAME storm served
    with the state hash chain ARMED (digest_interval=64, the deployed
    default — every committed entry folds its post-apply readback into
    the blake2b chain, checkpoints on interval buckets) vs DISARMED
    (digest_interval=0: no digest object; apply pays one attribute
    check). Both servers live simultaneously, timed reps interleaved
    with ALTERNATING within-pair order, max-of-reps compared. Records
    per-side rates + storm tails, the armed chain's counters (folds /
    checkpoints / sync mode — the nomad.fsm.digest.* stats keys), and a
    parity gate: both sides place the full storm every rep, the armed
    chain folded every commit, and it never diverged."""
    from nomad_tpu.server import Server, ServerConfig

    nodes = build_nodes(DIGEST_AB_NODES)
    out = {"nodes": DIGEST_AB_NODES, "evals_per_rep": DIGEST_AB_EVALS}
    servers = {}
    try:
        for mode, interval in (("armed", 64), ("disarmed", 0)):
            srv = Server(ServerConfig(num_schedulers=N_WORKERS,
                                      pipelined_scheduling=True,
                                      scheduler_window=WINDOW,
                                      digest_interval=interval,
                                      min_heartbeat_ttl=24 * 3600.0,
                                      heartbeat_grace=24 * 3600.0))
            srv.establish_leadership()
            for node in nodes:
                srv.node_register(node)
            run = _make_storm_runner(srv)
            run(3)
            run(3)
            srv.tindex.nt.warm_device()
            run(DIGEST_AB_EVALS)  # full-size warm storm (compiles)
            servers[mode] = (srv, run)
        _tune_gc()
        rates = {"armed": [], "disarmed": []}
        lats = {"armed": [], "disarmed": []}
        placed = {"armed": [], "disarmed": []}
        for rep in range(DIGEST_AB_REPS):
            order = (("armed", "disarmed") if rep % 2 == 0
                     else ("disarmed", "armed"))
            for mode in order:
                srv, run = servers[mode]
                for w in srv.workers:
                    if hasattr(w, "quiesce"):
                        w.quiesce(30.0)
                t0 = time.perf_counter()
                eval_ids = run(DIGEST_AB_EVALS, latencies=lats[mode])
                rates[mode].append(
                    round(DIGEST_AB_EVALS / (time.perf_counter() - t0), 2))
                _freeze_heap()
                placed[mode].append(sum(
                    1 for eid in eval_ids
                    for _ in srv.state.allocs_by_eval(eid)))
        for mode in ("armed", "disarmed"):
            out[mode] = {"evals_sec": max(rates[mode]),
                         "rep_rates": rates[mode],
                         "storm_latency_ms": _pctiles_ms(lats[mode]),
                         "placed_per_rep": placed[mode]}
        out["overhead_pct"] = round(
            (1.0 - max(rates["armed"]) / max(rates["disarmed"]))
            * 100.0, 2) if rates["disarmed"] else None
        stats = servers["armed"][0].fsm.digest.stats()
        out["digest"] = {"folds": stats["Folds"],
                         "chain_index": stats["LastIndex"],
                         "checkpoints": len(stats["Checkpoints"]),
                         "synced": stats["Synced"],
                         "diverged": stats["Diverged"]}
        want = DIGEST_AB_EVALS * PER_EVAL
        # Folds can trail LastIndex: a handler that RAISES skips its
        # fold by contract (every replica skips the same entry), so the
        # gate checks the chain advanced and stayed healthy, not an
        # exact count.
        out["parity_ok"] = bool(
            all(p == want for mode in placed for p in placed[mode])
            and stats["Folds"] > 0
            and stats["LastIndex"] >= stats["Folds"]
            and stats["Synced"] and stats["Diverged"] == 0
            and servers["disarmed"][0].fsm.digest is None)
        out["expected_allocs"] = want
        return out
    finally:
        for srv, _ in servers.values():
            srv.shutdown()


def bench_placer(nodes, n_evals, per_eval=PER_EVAL, dcs=None):
    """Placer-only device pipeline: the ceiling (no raft/plan-apply)."""
    from nomad_tpu.scheduler.pipeline import EvalRequest, PipelinedPlacer
    from nomad_tpu.tensor import TensorIndex

    tindex = TensorIndex()
    for node in nodes:
        tindex.nt.upsert_node(node)

    window = min(max(n_evals, 1), 128)

    warm = PipelinedPlacer(tindex, nodes, rng=random.Random(1), window=window)
    for _ in range(window + 1):
        job = build_job(per_eval, dcs)
        warm.submit(EvalRequest(job=job, tgs=[job.TaskGroups[0]] * per_eval))
    warm.flush()

    placer = PipelinedPlacer(tindex, nodes, rng=random.Random(42),
                             window=window)
    t0 = time.perf_counter()
    for _ in range(n_evals):
        job = build_job(per_eval, dcs)
        placer.submit(EvalRequest(job=job,
                                  tgs=[job.TaskGroups[0]] * per_eval))
    results = placer.flush()
    elapsed = time.perf_counter() - t0
    total_placed = sum(int((r.chosen_rows >= 0).sum()) for r in results)

    # Synchronous single-eval latency (the p50 plan-latency figure).
    lat_placer = PipelinedPlacer(tindex, nodes, rng=random.Random(7))
    latencies = []
    for _ in range(5):
        job = build_job(per_eval, dcs)
        t1 = time.perf_counter()
        lat_placer.submit(EvalRequest(job=job,
                                      tgs=[job.TaskGroups[0]] * per_eval))
        lat_placer.flush()
        latencies.append(time.perf_counter() - t1)
    return n_evals / elapsed, total_placed, float(np.percentile(latencies, 50))


def bench_cpu_reference(nodes, n_evals):
    from nomad_tpu.scheduler.cpu_reference import CPUReferenceStack

    rng = random.Random(42)
    stack = CPUReferenceStack(nodes, batch=False, rng=rng)
    t0 = time.perf_counter()
    total = 0
    for _ in range(n_evals):
        job = build_job()
        stack.set_job(job)
        for o in stack.select_batch([job.TaskGroups[0]] * PER_EVAL):
            if o is not None:
                total += 1
    elapsed = time.perf_counter() - t0
    return n_evals / elapsed, total


def bench_cpu_served(nodes, n_evals, reps=3):
    """The apples-to-apples denominator: the reference's host-side iterator
    chain served through the SAME server path as the headline number
    (register -> raft -> broker -> worker -> plan applier -> committed),
    with only the placement engine swapped (scheduler_impl)."""
    from nomad_tpu.server import Server, ServerConfig

    srv = Server(ServerConfig(num_schedulers=1, pipelined_scheduling=False,
                              scheduler_impl="cpu-reference",
                              min_heartbeat_ttl=24 * 3600.0,
                              heartbeat_grace=24 * 3600.0))
    srv.establish_leadership()
    try:
        for node in nodes:
            srv.node_register(node)

        run = _make_storm_runner(srv)
        run(2)  # warmup (imports, first snapshots)
        _tune_gc()  # same runtime tuning as the TPU side (honest ratio)
        rates = []
        for _ in range(reps):
            t0 = time.perf_counter()
            eval_ids = run(n_evals)
            rates.append(n_evals / (time.perf_counter() - t0))
            # Identical between-rep GC treatment to the TPU side: the
            # served-vs-served ratio must not hide a GC-decay asymmetry.
            _freeze_heap()
        placed = sum(1 for eid in eval_ids
                     for a in srv.state.allocs_by_eval(eid))
        return sorted(rates)[(len(rates) - 1) // 2], placed, \
            [round(r, 2) for r in rates]
    finally:
        srv.shutdown()


def bench_placement_parity(n_evals=None, n_nodes=None):
    """BASELINE's ratio is defined \"at identical placement quality\": the
    same storm (identical node fleet, identical jobs) runs served through
    the TPU engine and the reference CPU chain, and the committed
    placements' bin-pack scores are compared. The TPU path's global argmax
    must score AT LEAST as well as the reference's sampled max — a drop
    beyond f32/noise tolerance means the fast path is trading placement
    quality for throughput, and the bench fails loudly."""
    from nomad_tpu.server import Server, ServerConfig

    if n_evals is None:
        n_evals = PARITY_EVALS
    if n_nodes is None:
        n_nodes = PARITY_NODES
    out = {}
    for impl in ("tpu", "cpu-reference"):
        nodes = build_nodes(n_nodes)  # same seed => identical fleets
        srv = Server(ServerConfig(num_schedulers=1,
                                  pipelined_scheduling=impl == "tpu",
                                  scheduler_impl=impl,
                                  min_heartbeat_ttl=24 * 3600.0,
                                  heartbeat_grace=24 * 3600.0))
        srv.establish_leadership()
        try:
            for node in nodes:
                srv.node_register(node)
            run = _make_storm_runner(srv)
            eval_ids = run(n_evals)
            scores = []
            placed = 0
            for eid in eval_ids:
                for a in srv.state.allocs_by_eval(eid):
                    placed += 1
                    s = ((a.Metrics.Scores or {}).get(
                        f"{a.NodeID}.binpack")
                        if a.Metrics is not None else None)
                    if s is not None:
                        scores.append(float(s))
            out[impl] = {
                "placed": placed,
                "scored": len(scores),
                "mean_score": round(float(np.mean(scores)), 5)
                if scores else None,
            }
        finally:
            srv.shutdown()
    tpu, cpu = out["tpu"], out["cpu-reference"]
    want = n_evals * PER_EVAL
    delta = (round(tpu["mean_score"] - cpu["mean_score"], 5)
             if tpu["mean_score"] is not None
             and cpu["mean_score"] is not None else None)
    # Noise tie-break adds <=1e-3 to TPU scores; everything else is f32.
    ok = (tpu["placed"] == cpu["placed"] == want
          and delta is not None and delta >= -2e-3)
    return {"tpu": tpu, "cpu_reference": cpu,
            "mean_score_delta": delta, "storm_placements": want,
            "ok": bool(ok)}


def bench_mesh_1m():
    """config6_mesh_1m: the trajectory's millions-of-users shape — 1M
    nodes x a wide storm window — measured as a keyed-kernel A/B, the
    whole mesh against its first device, in this process. The served
    mesh path itself is equivalence- and chaos-gated in tier-1; this
    records the RATE and per-window latency tails at the headline
    scale."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from nomad_tpu.parallel import pow2_prefix, scheduling_mesh
    from nomad_tpu.scheduler import kernels

    n, p, nv = MESH_NODES, MESH_P, min(MESH_VALID, MESH_P)
    w, reps, t = MESH_WINDOWS, MESH_REPS, 1
    devices = pow2_prefix(jax.devices())
    n_dev = len(devices)
    if n_dev < 2:
        return "not measured: one device"

    def setup(devs):
        rng = np.random.default_rng(1)
        mesh = scheduling_mesh(devs)
        axis = mesh.axis_names[0]
        node_sh = NamedSharding(mesh, PartitionSpec(axis))
        mask_sh = NamedSharding(mesh, PartitionSpec(None, axis))
        d = {k: jax.device_put(v, node_sh) for k, v in {
            "capacity": rng.uniform(1000, 4000, (n, 5)).astype(np.float32),
            "score_cap": rng.uniform(800, 3800, (n, 2)).astype(np.float32),
            "usage": rng.uniform(0, 200, (n, 5)).astype(np.float32),
            "job_counts": np.zeros(n, np.int32),
            "noise": (rng.random(n) * 1e-3).astype(np.float32),
            "banned0": np.zeros(n, bool),
        }.items()}
        tg_masks = jax.device_put(rng.random((t, n)) < 0.9, mask_sh)
        kd = rng.uniform(5, 40, (t, 5)).astype(np.float32)
        tg_ids = rng.integers(0, t, p).astype(np.int32)
        valid = np.zeros(p, bool)
        valid[:nv] = True
        reset = np.zeros(p, bool)
        reset[::64] = True
        penalty = np.float32(10.0)
        distinct = np.asarray(False)
        jax.block_until_ready(list(d.values()))

        def fn(u):
            return kernels.place_batch_keyed(
                mesh if len(devs) > 1 else None, d["capacity"],
                d["score_cap"], u, tg_masks, d["job_counts"], kd, tg_ids,
                valid, d["noise"], penalty, distinct, d["banned0"], reset,
                nv)

        res = fn(d["usage"])  # compile + warm (one cold + warm program)
        res = fn(res.usage_after)
        jax.block_until_ready(res.packed)
        return fn, d["usage"]

    def rate_rep(fn, u0):
        t0 = time.perf_counter()
        u, res = u0, None
        for _ in range(w):
            res = fn(u)
            u = res.usage_after
        jax.block_until_ready(res.packed)
        return w / (time.perf_counter() - t0)

    def lat_rep(fn, u0):
        # Per-window latency: each window blocks to the host, the way a
        # lone interactive eval pays it. The chain restarts at u0 first,
        # so index 0 is the COLD window (rebuild + exchange) and the
        # rest are warm — the percentiles honestly mix both, like a
        # served storm does across rebases.
        lats, u = [], u0
        for _ in range(w):
            t0 = time.perf_counter()
            res = fn(u)
            jax.block_until_ready(res.packed)
            lats.append(time.perf_counter() - t0)
            u = res.usage_after
        return lats

    sides = {"one_dev": setup(devices[:1]), "mesh": setup(devices)}
    kernels.mesh_stats_drain()
    rates = {k: [] for k in sides}
    lats = {k: [] for k in sides}
    # Interleaved A/B, alternating within-pair order, max-of-reps (a
    # throttled rep loses a sample, never skews the ratio). Latency reps
    # ride the same alternation.
    for i in range(reps):
        order = list(sides) if i % 2 == 0 else list(reversed(sides))
        for side in order:
            fn, u0 = sides[side]
            rates[side].append(rate_rep(fn, u0))
            lats[side].extend(lat_rep(fn, u0))
    ms = kernels.mesh_stats_drain()
    out = {
        "nodes": n, "window_p": p, "valid_per_window": nv,
        "windows_per_rep": w, "reps": reps, "devices": n_dev,
        "one_dev": {"windows_sec": round(max(rates["one_dev"]), 2),
                    "rep_rates": [round(r, 2) for r in rates["one_dev"]],
                    "window_latency_ms": _pctiles_ms(lats["one_dev"])},
        "mesh": {"windows_sec": round(max(rates["mesh"]), 2),
                 "rep_rates": [round(r, 2) for r in rates["mesh"]],
                 "window_latency_ms": _pctiles_ms(lats["mesh"]),
                 "mesh_windows": ms["windows"],
                 "warm_windows": ms["warm_windows"],
                 "exchange_bytes": ms["candidate_bytes"]},
    }
    out["ratio"] = round(out["mesh"]["windows_sec"]
                         / out["one_dev"]["windows_sec"], 2)
    return out


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        description="nomad-tpu end-to-end served-path benchmark")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU-safe shapes (<60s) with the parity "
                         "gate; for in-tree perf-path regression checks")
    args = ap.parse_args(argv)
    # Before anything compiles: the configured backend initializes or this
    # raises, and every number below is labelled with the device it ran on.
    from nomad_tpu.tensor.backend import device_info

    device = device_info()
    if args.smoke:
        _apply_smoke()
    nodes = build_nodes(N_NODES)
    n_evals = max(1, N_PLACEMENTS // PER_EVAL)

    e2e_evals_sec, e2e_placed, worker_stats = bench_server_e2e(nodes, n_evals)
    placer_evals_sec, _, p50 = bench_placer(nodes, n_evals)
    cpu_evals_sec, _ = bench_cpu_reference(nodes, CPU_REF_EVALS)
    cpu_served_evals_sec, cpu_served_placed, cpu_served_rates = \
        bench_cpu_served(nodes, CPU_REF_EVALS)

    detail = {
        "placements_per_eval": PER_EVAL,
        "e2e_placed": e2e_placed,
        "e2e_worker_stats": worker_stats,
        "e2e_placements_sec": (e2e_psec := round(e2e_evals_sec * PER_EVAL,
                                                 2)),
        "placer_only_evals_sec": round(placer_evals_sec, 2),
        "placer_p50_eval_latency_ms": round(p50 * 1e3, 2),
        # Served-path idle-broker latency (host fast path): what one
        # interactive job registration pays end-to-end.
        "e2e_p50_eval_latency_ms": worker_stats.get(
            "e2e_p50_eval_latency_ms"),
        "cpu_reference_evals_sec": round(cpu_evals_sec, 2),
        # Served-vs-served: the honest apples-to-apples ratio (same server,
        # broker, applier, raft on both sides; only the placement engine
        # differs).
        "cpu_served_evals_sec": round(cpu_served_evals_sec, 2),
        "cpu_served_rep_rates": cpu_served_rates,
        "cpu_served_placed": cpu_served_placed,
        "served_vs_served_ratio": round(
            e2e_evals_sec / cpu_served_evals_sec, 2),
        # Absolute anchor (a RATIO): the reference's C1M challenge
        # sustained ~3,300 placements/sec across a 5,000-host cluster
        # (BASELINE.md). This is ONE chip driving a full commit path vs
        # their whole fleet.
        "e2e_vs_c1m_ratio": round(e2e_psec / 3300.0, 2),
    }

    # The remaining BASELINE configs, each END-TO-END through the served
    # path (register -> raft -> broker -> worker -> plan apply -> commit).
    if RUN_C2:
        c2_nodes = build_nodes(1000)
        rate, placed, p50, rep_rates, storm_pct = bench_served_config(
            c2_nodes, build_plain_job, n_evals=10, reps=3)
        detail["config2_resource_only"] = {
            "path": "served", "nodes": 1000, "placements": 500,
            "evals_sec": round(rate, 2),
            "placements_sec": round(rate * PER_EVAL, 2),
            "placed_per_rep": placed,
            "p50_eval_latency_ms": round(p50 * 1e3, 2),
            "storm_latency_ms": storm_pct,
            "rep_rates": rep_rates,
        }

    if RUN_C4:
        # Reuse the headline node set (same 10k-node shape; 512 at
        # --smoke). 2 warm + 2x23 timed + 2 probes = 50 system jobs
        # total at full shape, per BASELINE.
        rate, placed, p50, rep_rates, storm_pct = bench_served_config(
            nodes, build_system_job, n_evals=C4_EVALS, reps=C4_REPS,
            warm=1, latency_probes=2)
        detail["config4_system"] = {
            "path": "served", "nodes": N_NODES,
            "system_jobs": 2 + C4_REPS * C4_EVALS + 2 + C4_EVALS,
            "evals_sec": round(rate, 2),
            "placements_sec": round(rate * N_NODES, 2),
            "placed_per_rep": placed,
            "p50_eval_latency_ms": round(p50 * 1e3, 2),
            "storm_latency_ms": storm_pct,
            "rep_rates": rep_rates,
        }

    if RUN_C5:
        c5_nodes = build_nodes(C5_NODES, n_dcs=4)
        c5_evals = max(1, C5_PLACEMENTS // PER_EVAL)
        dcs = ["dc1", "dc2", "dc3", "dc4"]
        rate, placed, p50, rep_rates, storm_pct = bench_served_config(
            c5_nodes, lambda: build_job(PER_EVAL, dcs), n_evals=c5_evals,
            reps=2)
        detail["config5_multidc"] = {
            "path": "served", "nodes": C5_NODES,
            "placements": C5_PLACEMENTS,
            "evals_sec": round(rate, 2),
            "placements_sec": round(rate * PER_EVAL, 2),
            "placed_per_rep": placed,
            "p50_eval_latency_ms": round(p50 * 1e3, 2),
            "storm_latency_ms": storm_pct,
            "rep_rates": rep_rates,
        }

    # State-store commit microbench (`store` section): per-alloc commit
    # µs / batch scatter ms / raft entry bytes, object vs columnar at
    # BOTH commit shapes — the sweep shape feeds config4 (and any system
    # storm), the window shape feeds the headline/config2/config5 service
    # configs (columnar service commits since ISSUE 11).
    detail["store"] = (store := {
        "config4_system": bench_store_commit(N_NODES),
        "service_window": bench_store_commit_window(),
    })

    # Service columnar-commit A/B: end-to-end evals/s + storm tails with
    # columnar service commits on vs off, interleaved/alternating reps.
    svc_ab = None
    if RUN_SVC_AB:
        detail["service_columnar"] = (svc_ab := bench_service_columnar_ab())

    # event_stream: broker-armed (+1 live subscriber) vs disarmed A/B,
    # publish overhead % + nomad.events counters, zero-drop/parity
    # exit-2 gated.
    ev_stream = None
    if RUN_EVENTS:
        detail["event_stream"] = (ev_stream := bench_event_stream())

    # digest: replica hash-chain armed (interval 64) vs disarmed A/B,
    # fold overhead % + nomad.fsm.digest counters, parity exit-2 gated.
    digest_ab = None
    if RUN_DIGEST:
        detail["digest"] = (digest_ab := bench_digest())

    # The millions-of-users shape: 1M nodes x a wide storm window,
    # keyed kernel one-device-vs-mesh with latency percentiles
    # (slow-gated out of --smoke).
    if RUN_MESH:
        detail["config6_mesh_1m"] = bench_mesh_1m()

    # Horizontal worker scaling: always recorded (smoke shapes), so every
    # BENCH file carries the 1-vs-2 ratio next to the single-worker rate.
    detail["worker_scaling"] = bench_worker_scaling()

    # QoS slo_storm: per-tier latency tails under mixed-priority load,
    # qos-on vs qos-off interleaved, + admission/preemption probes.
    slo = None
    if RUN_SLO:
        detail["slo_storm"] = (slo := bench_slo_storm())

    # failover_storm: placements/s + per-tier tails through an induced
    # leader election on a real 3-server cluster, zero-loss gated.
    failover = None
    if RUN_FAILOVER:
        detail["failover_storm"] = (failover := bench_failover_storm())

    # config7_federation: 3-region federated storm vs the all-on-leader
    # baseline, zero-loss / no-duplicate / quiet-region-SLO gated.
    fed_storm = None
    if RUN_FED:
        detail["config7_federation"] = (fed_storm :=
                                        bench_federation_storm())

    detail["placement_parity"] = (parity := bench_placement_parity())

    result = {
        "metric": f"end-to-end server evals/sec @{N_NODES} nodes x "
                  f"{N_PLACEMENTS} task-groups (register->broker->worker->"
                  f"plan-apply->committed)",
        "value": round(e2e_evals_sec, 2),
        "unit": "evals/sec",
        # Apples-to-apples: BOTH sides of this ratio run end-to-end through
        # the same served path; only the placement engine differs.
        "vs_baseline": round(e2e_evals_sec / cpu_served_evals_sec, 2),
        "device": device,
        "detail": detail,
    }
    print(json.dumps(result))
    if not parity["ok"]:
        # Quality gate: the ratio above is only meaningful at >= reference
        # placement quality. Fail AFTER emitting the JSON so the metric is
        # still recorded alongside the failure.
        sys.stderr.write(
            f"PLACEMENT PARITY FAILED: {json.dumps(parity)}\n")
        sys.exit(2)
    if slo is not None and not (slo["parity_ok"]
                                and slo["admission_probe"]["ok"]
                                and slo["preempt_probe"]["ok"]):
        # QoS gate: qos-on must place the full storm (reordering never
        # drops work), admission must shed when told to, preemption must
        # place atomically. Same fail-after-emit contract as above.
        sys.stderr.write(f"QOS SLO GATE FAILED: {json.dumps(slo)}\n")
        sys.exit(2)
    if failover is not None and not failover["gate"]["ok"]:
        # Zero-downtime gate: an election may slow the storm but must
        # never lose or duplicate work. Same fail-after-emit contract.
        sys.stderr.write(
            f"FAILOVER STORM GATE FAILED: {json.dumps(failover)}\n")
        sys.exit(2)
    if fed_storm is not None and not fed_storm["gate"]["ok"]:
        # Federation gate: forwarding/routing may add hops but must
        # never lose or duplicate work, a quiet region's high tier must
        # hold its SLO through another region's storm, and the
        # follower-snapshot source must actually be exercised. Same
        # fail-after-emit contract.
        sys.stderr.write(
            f"FEDERATION STORM GATE FAILED: {json.dumps(fed_storm)}\n")
        sys.exit(2)
    svc_store = store["service_window"]
    if (svc_store["storm_group"]["commit_speedup"] or 0) < STORE_SVC_GATE:
        # Columnar-commit gate: at the storm commit unit (the applier's
        # 16-plan group entry) the service-window FSM commit must stay
        # >= STORE_SVC_GATE x faster than the per-object path (the whole
        # point of the columnar service path). Deterministic CPU, so a
        # miss is a regression, not noise. Same fail-after-emit contract.
        sys.stderr.write(
            f"SERVICE COLUMNAR STORE GATE FAILED "
            f"(want >= {STORE_SVC_GATE}x): {json.dumps(svc_store)}\n")
        sys.exit(2)
    if svc_ab is not None and not svc_ab["parity_ok"]:
        # Columnar A/B parity: both commit paths place the full storm and
        # the columnar server really committed service segments.
        sys.stderr.write(
            f"SERVICE COLUMNAR AB GATE FAILED: {json.dumps(svc_ab)}\n")
        sys.exit(2)
    if ev_stream is not None and not ev_stream["parity_ok"]:
        # Event-stream parity: armed and disarmed place identically-sized
        # storms, the live subscriber saw real traffic, and the bounded
        # queue never dropped. Same fail-after-emit contract.
        sys.stderr.write(
            f"EVENT STREAM AB GATE FAILED: {json.dumps(ev_stream)}\n")
        sys.exit(2)
    if digest_ab is not None and not digest_ab["parity_ok"]:
        # Replica-digest parity: armed and disarmed place identically-
        # sized storms, the chain folded every committed entry, and the
        # armed replica never saw itself diverge. Same fail-after-emit
        # contract.
        sys.stderr.write(
            f"DIGEST AB GATE FAILED: {json.dumps(digest_ab)}\n")
        sys.exit(2)


if __name__ == "__main__":
    main()
