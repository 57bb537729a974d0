"""Multi-chip SERVING: the pipelined worker's windows run on a sharded mesh.

The node tensor (and every placement-kernel input) shards its node axis over
a jax.sharding.Mesh, and keyed windows run the shard-local mesh pipeline
(kernels._place_batch_keyed_mesh). These tests run on the 8-virtual-CPU
mesh from conftest and assert the mesh-served path is indistinguishable from
single-device serving (reference frame: SURVEY §7.1 — the node axis IS the
sharded tensor axis; the serving semantics come from nomad/worker.go +
plan_apply.go, which don't care where the argmax ran).

The servers run with host_placement=False: at these sizes every window is
under HOST_ROW_STEP_BUDGET, and the numpy mirror would otherwise place them
all without the mesh kernel ever being built.
"""

import random

import numpy as np
import pytest

import jax

from nomad_tpu import mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs.structs import EvalStatusComplete
from nomad_tpu.tensor.node_table import alloc_vec

from helpers import wait_for  # noqa: E402


def _fixed_noise(n_rows, rng):
    """Deterministic tie-break noise so two servers place identically."""
    return np.asarray(
        np.random.default_rng(1234).random(n_rows), dtype=np.float32) * 1e-3


def _make_server(mesh: bool, window: int = 16) -> Server:
    cfg = ServerConfig(num_schedulers=1, pipelined_scheduling=True,
                       scheduler_window=window,
                       scheduler_mesh="all" if mesh else "",
                       host_placement=False,
                       min_heartbeat_ttl=3600.0, heartbeat_grace=3600.0)
    srv = Server(cfg)
    srv.establish_leadership()
    return srv


def _nodes(n, seed=7):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        node = mock.node()
        node.Meta["rack"] = f"r{i % 8}"
        node.Resources.CPU = 2000 + 400 * (i % 3)
        node.Resources.MemoryMB = 4096
        from nomad_tpu.structs import compute_node_class

        compute_node_class(node)
        out.append(node)
    return out


def _job(count=6):
    job = mock.job()
    tg = job.TaskGroups[0]
    tg.Count = count
    task = tg.Tasks[0]
    task.Resources.CPU = 50
    task.Resources.MemoryMB = 64
    task.Resources.Networks = []
    task.Services = []
    return job


def _run_stream(srv, jobs):
    """Submit jobs one at a time (deterministic eval order and window fill),
    wait for each eval, return placements as job -> sorted node IDs."""
    placements = {}
    for job in jobs:
        eval_id = srv.job_register(job)[0]
        wait_for(lambda: (e := srv.state.eval_by_id(eval_id)) is not None
                 and e.Status == EvalStatusComplete, timeout=60)
        placements[job.ID] = sorted(
            a.NodeID for a in srv.state.allocs_by_job(job.ID)
            if not a.terminal_status())
    return placements


def _assert_placed_on_mesh(srv):
    """Every fast eval went through the mesh pipeline, none through numpy."""
    stats = srv.workers[0].stats
    srv.workers[0].quiesce(30.0)
    assert stats["fast"] > 0 and stats["host"] == 0, stats
    assert stats["mesh_windows"] > 0, stats
    assert stats["mesh_shards"] == len(jax.devices()), stats


class TestMeshServing:
    def test_mesh_is_wired_into_the_served_tensor(self):
        srv = _make_server(mesh=True)
        try:
            assert srv.tindex.nt.mesh is not None
            assert srv.tindex.nt.mesh.devices.size == len(jax.devices())
            for node in _nodes(8):
                srv.node_register(node)
            arrays = srv.tindex.nt.device_arrays()
            # The served table's arrays are actually sharded over the mesh.
            sh = arrays["usage"].sharding
            assert getattr(sh, "mesh", None) is not None
            assert sh.spec[0] is not None, "node axis not sharded"
            # ... and a job placed against them runs the mesh kernel.
            placed = _run_stream(srv, [_job()])
            assert [len(v) for v in placed.values()] == [6]
            _assert_placed_on_mesh(srv)
        finally:
            srv.shutdown()

    def test_sharded_serving_places_identically(self, monkeypatch):
        """Same node set, same job stream, same tie-break noise: the mesh
        server and the single-device server commit identical placements."""
        from nomad_tpu.scheduler import stack as stack_mod

        monkeypatch.setattr(stack_mod, "make_noise_vec", _fixed_noise)

        import pickle

        nodes = _nodes(32)
        jobs = [_job() for _ in range(6)]
        results = []
        for mesh in (False, True):
            srv = _make_server(mesh=mesh)
            try:
                for node in pickle.loads(pickle.dumps(nodes)):
                    srv.node_register(node)
                placements = _run_stream(
                    srv, pickle.loads(pickle.dumps(jobs)))
                results.append(placements)
                if mesh:
                    _assert_placed_on_mesh(srv)
            finally:
                srv.shutdown()
        single, sharded = results
        assert single == sharded

    def test_mesh_burst_places_all_without_oversubscription(self):
        """A windowed burst through the mesh-served path: every eval
        completes, every placement commits, and no node oversubscribes."""
        srv = _make_server(mesh=True, window=8)
        try:
            nodes = _nodes(16)
            for node in nodes:
                srv.node_register(node)
            eval_ids = [srv.job_register(_job(count=4))[0]
                        for _ in range(12)]
            wait_for(lambda: all(
                (e := srv.state.eval_by_id(eid)) is not None
                and e.Status == EvalStatusComplete for eid in eval_ids),
                timeout=120)
            total = 0
            for eid in eval_ids:
                allocs = list(srv.state.allocs_by_eval(eid))
                total += len(allocs)
            assert total == 12 * 4
            _assert_placed_on_mesh(srv)
            for node in nodes:
                used = sum(alloc_vec(a)[0]
                           for a in srv.state.allocs_by_node(node.ID)
                           if not a.terminal_status())
                assert used <= node.Resources.CPU
        finally:
            srv.shutdown()
