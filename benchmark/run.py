#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process. It finds the cell in BENCHMARK.json, and by the names there
its configuration (benchmark/configs/), its traffic mix (benchmark/traffic/)
and its metrics (benchmark/end_to_end/, benchmark/layer_metrics/); the files
name the deploy, generator and reader modules. It deploys the system, warms
that cell's shapes (all of it set-up), measures for --seconds, drains,
recomputes the guarantees, and prints one JSON object as its last line
(its last key, `compared`, holds every number `correct` compared beside
its limit; the same are the last lines on standard error). Lines before it
are notes of this one run. benchmark/README.md says how a later PR adds a
cell, a mix, a configuration or a metric as new files only.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result. --allow-cpu rehearses on the CPU at the configuration's
rehearsal fleet (or --nodes): the last line then says "cpu", and no number
of such a run is a device number.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DRAIN_TIMEOUT_S = 120.0


def note(name, **fields):
    print(json.dumps({"note": name, **fields}, default=str), flush=True)


def replica_reads(dep, reads):
    """One reads() a replica for check 5: the deployment's replica_reads()
    where it runs more than one server (each taken once that replica has
    applied the leader's last committed index), else the one it has."""
    if hasattr(dep, "replica_reads"):
        return dep.replica_reads()
    return [reads]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal without a chip; the last line says cpu")
    ap.add_argument("--nodes", type=int,
                    help="fleet size of a rehearsal (with --allow-cpu only)")
    args = ap.parse_args(argv)
    if args.nodes is not None and not args.allow_cpu:
        ap.error("--nodes changes the configuration: rehearsals only")

    from benchmark import cells, instruments

    cell = cells.load(ROOT, args.workload)
    seconds = args.seconds if args.seconds is not None \
        else cell.benchmark["run_seconds"]

    from nomad_tpu.tensor.backend import device_info

    device = device_info()  # raises when the backend cannot initialize
    if device["platform"] != "tpu" and not args.allow_cpu:
        print(f"benchmark: JAX found no TPU (platform "
              f"{device['platform']!r}); not continuing on it",
              file=sys.stderr)
        return 1
    if device["count"] < cell.chips:
        print(f"benchmark: the cell asks for {cell.chips} chips, JAX found "
              f"{device['count']}", file=sys.stderr)
        return 1
    rehearsal = device["platform"] != "tpu"
    if not rehearsal:
        from benchmark.trace import xplane

        xplane.peaks(device["kind"])  # an unknown chip is an error
    instruments.cache_every_program(on_chip=not rehearsal)
    nodes = args.nodes or (cell.config["rehearsal"]["nodes"]
                           if rehearsal else None)
    rng = random.Random(args.seed)
    deploy = importlib.import_module(
        "benchmark.deploy." + cell.config["deploy"])
    generator = importlib.import_module(
        "benchmark.generators." + cell.traffic["generator"])
    compiles = instruments.CompileLog()
    dep = deploy.Deployment(cell.config, rng, nodes=nodes)
    try:
        dep.start()
        probe = instruments.Window(dep, compiles, traced=bool(args.trace),
                                   trace_dir=os.path.join(
                                       ROOT, ".bench_work", "trace"),
                                   trace_seconds=cell.traffic.get(
                                       "trace_seconds", 3),
                                   on_chip=not rehearsal,
                                   trace_guard_share=cell.traffic.get(
                                       "trace_guard_share"))
        setup_s = time.perf_counter() - T_PROCESS
        probe.begin(seconds)
        window = generator.run(dep, cell.traffic, rng, seconds,
                               progress=probe.progress)
        probe.end()
        for text in window["notes"]:
            note("generator", text=text)

        t0 = time.perf_counter()
        undrained = dep.drain(DRAIN_TIMEOUT_S)
        device_usage, row_of = probe.read_device(dep.device_usage)
        reads = dep.reads()
        from benchmark.reference import guarantees

        verdict, failed = guarantees.judge(
            reads, dep.acknowledged, device_usage, row_of, undrained,
            device["platform"], rehearsal=args.allow_cpu,
            replica_reads=replica_reads(dep, reads),
            replicas=cell.config["guarantees"]["replicas"])
        facts = dict(verdict.facts)
        for name in cell.traffic.get("extra_checks", ()):
            extra = importlib.import_module("benchmark.reference." + name)
            facts[name] = extra.check(dep, args.seed, verdict)
        check_s = time.perf_counter() - t0
        memory_peak = instruments.memory_peak_bytes()
        stats_total = dep.worker_stats()
        dep_facts = dep.facts()
    finally:
        dep.shutdown()

    window_ops = window["ops"]
    failed_ops = [op for op in window_ops if op.job_id in failed]
    run = {
        "cell": cell, "window": window, "seconds": window["t1"] - window["t0"],
        "ops": window_ops, "failed_jobs": failed, "setup_s": setup_s,
        "stats": probe.stats_delta, "trace_stats": probe.trace_stats_delta,
        "samples": probe.samples(), "counters": probe.counters(),
        "gc": probe.gc_events,
        "compiles": probe.compiles, "device": probe.device,
        "trace": probe.trace,
    }
    metrics = cells.read_metrics(
        cell, "per_layer" if args.trace else "end_to_end", run)

    note("run", workload=cell.name, seed=args.seed, seconds=run["seconds"],
         trace=args.trace, ops=len(window_ops), setup_s=setup_s,
         drain_and_check_s=check_s, compiles_in_window=len(probe.compiles),
         worker_stats_delta=probe.stats_delta, worker_stats=stats_total,
         trace_span=probe.trace_facts(),
         failed_operations={j: failed[j] for j in list(failed)[:8]},
         guarantees=facts, deployment=dep_facts)
    for failure in verdict.failures:
        note("check_failed", **failure)
    result = {
        "correct": verdict.correct,
        "attempted": len(window_ops),
        "failed": len(failed_ops),
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": memory_peak},
    }
    if args.trace and probe.device is not None:
        result["device"]["busy_s"] = probe.device["busy_s"]
        result["device"]["window_s"] = probe.device["window_s"]
        result["breakdown"] = probe.device["breakdown"]
    # Every number `correct` compared, beside its limit: the last lines on
    # standard error and the last key of the result line.
    result["compared"] = verdict.compared
    for check, c in verdict.compared.items():
        print(f"compared {check}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
