"""The per-layer metric ISSUE 40 appended for the system sweep's columnar
plans: `columnar_plan_share.rollout`, declared behind the entries that
stood, read through its own file from made-up counters (100 where every
sweep plan stayed columns, 0.0 where the program counts neither, as the
parent does, left out of an untraced run's line), and read from what a
real sweep sends the registry."""

import importlib
import json
import logging
import os
import random

import pytest

from benchmark import cells, instruments
from nomad_tpu import mock
from nomad_tpu.scheduler.system_sched import SystemScheduler
from nomad_tpu.state.state_store import StateStore
from nomad_tpu.structs import PlanResult, compute_node_class
from nomad_tpu.structs.structs import (
    EvalStatusPending,
    EvalTriggerJobRegister,
)
from nomad_tpu.telemetry import metrics
from nomad_tpu.tensor import TensorIndex

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = "columnar_plan_share.rollout"
INDEX = 95  # the per-layer list held 95 entries at PR 39
COLUMNAR = "nomad.sched.system.plans_columnar"
OBJECTS = "nomad.sched.system.plans_objects"


def _spec():
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           NAME + ".json")) as f:
        return json.load(f)


def _read(run):
    spec = _spec()
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(run, **spec["args"])


def test_it_is_declared_behind_the_entries_that_stood():
    entry = BENCH["per_layer"][INDEX]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "System sweep: scheduler/system_sweep.py",
        "moves": "rollout_mean_ms", "workloads": entry["workloads"]}
    # The cell the issue listed first; a later cell may be appended.
    assert entry["workloads"][:1] == ["sys-10k.rollout"]
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"][:INDEX]}
    assert _spec()["reader"] == "counters"
    assert _spec()["args"] == {"num": COLUMNAR, "per": [COLUMNAR, OBJECTS],
                               "scale": 100.0}


@pytest.mark.parametrize("counted,value", [
    ({COLUMNAR: 60.0}, 100.0),
    ({COLUMNAR: 3.0, OBJECTS: 1.0}, 75.0),
    ({OBJECTS: 2.0}, 0.0),
    ({}, 0.0),  # the parent: neither counter exists there
])
def test_it_reads_the_share_from_made_up_counters(counted, value):
    read = _read({"counters": counted})
    assert read == pytest.approx(value) and isinstance(read, float)


def test_an_untraced_run_leaves_it_out_of_the_line():
    assert _read({"counters": None}) is None
    entry = BENCH["per_layer"][INDEX]
    cell = cells.Cell(name="sys-10k.rollout", chips=1, config={},
                      traffic={}, benchmark={"per_layer": [entry]})
    assert cells.read_metrics(cell, "per_layer", {"counters": None}) == {}
    assert cells.read_metrics(cell, "per_layer",
                              {"counters": {COLUMNAR: 4.0}}) == {
        NAME: {"value": 100.0, "unit": "%"}}


class _Planner:
    """Admits every plan as the applier does when everything fits."""

    def submit_plan(self, plan):
        return PlanResult(NodeAllocation=plan.NodeAllocation.copy(),
                          AllocIndex=1), None

    def update_eval(self, ev): ...
    def create_eval(self, ev): ...
    def reblock_eval(self, ev): ...


class _Dep:
    def worker_stats(self):
        return {"windows": 0}


def test_a_fresh_system_job_reads_100_through_the_harness_sink(monkeypatch):
    sink = instruments.SampleSink()
    monkeypatch.setattr(metrics, "incr_counter",
                        lambda key, value=1.0: sink.incr_counter(
                            tuple(key), float(value)))
    store = StateStore()
    tindex = TensorIndex.attach(store)
    for i in range(16):
        node = mock.node()
        compute_node_class(node)
        store.upsert_node(i + 1, node)
    job = mock.system_job()
    job.TaskGroups[0].Tasks[0].Resources.Networks = []
    job.init_fields()
    store.upsert_job(20, job)
    ev = mock.eval()
    ev.JobID, ev.Type = job.ID, job.Type
    ev.TriggeredBy, ev.Status = EvalTriggerJobRegister, EvalStatusPending
    SystemScheduler(store, _Planner(), tindex, logging.getLogger("t"),
                    rng=random.Random(1)).process(ev)

    probe = instruments.Window(_Dep(), compiles=None, traced=True,
                               trace_dir="unused", trace_seconds=3,
                               on_chip=False)
    probe._sink, probe.t0, probe.t1 = sink, float("-inf"), float("inf")
    counted = probe.counters()
    assert counted[COLUMNAR] == 1.0 and OBJECTS not in counted
    assert _read({"counters": counted}) == 100.0
