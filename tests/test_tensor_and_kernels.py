"""Tensor layer, kernel, windowed placement, and multi-chip sharding tests."""

import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.structs import Constraint, compute_node_class
from nomad_tpu.tensor import ClassEligibility, NodeTensor, TensorIndex
from nomad_tpu.tensor.node_table import RES_DIMS, resources_vec


class TestNodeTensor:
    def test_upsert_and_usage(self):
        nt = NodeTensor()
        n = mock.node()
        nt.upsert_node(n)
        row = nt.row_of[n.ID]
        assert nt.capacity[row][0] == 4000
        assert nt.usage[row][0] == 100  # reserved CPU counts as usage
        assert nt.score_cap[row][0] == 3900
        a = mock.alloc()
        a.NodeID = n.ID
        nt.add_alloc_usage(a)
        assert nt.usage[row][0] == 600
        nt.remove_alloc_usage(a)
        assert nt.usage[row][0] == 100

    def test_row_reuse_and_growth(self):
        nt = NodeTensor(capacity_hint=2)
        nodes = [mock.node() for _ in range(100)]
        for n in nodes:
            nt.upsert_node(n)
        assert nt.n_rows >= 100
        rows = {nt.row_of[n.ID] for n in nodes}
        assert len(rows) == 100
        nt.remove_node(nodes[0].ID)
        n_new = mock.node()
        nt.upsert_node(n_new)
        assert nt.row_of[n_new.ID] in range(nt.n_rows)

    def test_device_sync_dirty_rows(self):
        nt = NodeTensor()
        n = mock.node()
        nt.upsert_node(n)
        d1 = nt.device_arrays()
        row = nt.row_of[n.ID]
        a = mock.alloc()
        a.NodeID = n.ID
        nt.add_alloc_usage(a)
        d2 = nt.device_arrays()
        assert float(d2["usage"][row][0]) == nt.usage[row][0]

    def test_reserved_change_preserves_alloc_usage(self):
        nt = NodeTensor()
        n = mock.node()
        nt.upsert_node(n)
        a = mock.alloc()
        a.NodeID = n.ID
        nt.add_alloc_usage(a)
        row = nt.row_of[n.ID]
        before = nt.usage[row].copy()
        # Re-upsert with doubled reservation.
        n2 = n.copy()
        n2.Reserved.CPU = 200
        nt.upsert_node(n2)
        assert nt.usage[row][0] == before[0] + 100


class TestClassEligibility:
    def test_class_memoization_and_escape(self):
        nt = NodeTensor()
        nodes = [mock.node() for _ in range(4)]
        nodes[2].Attributes["kernel.name"] = "windows"
        nodes[3].Attributes["unique.special"] = "yes"
        for n in nodes:
            compute_node_class(n)
            nt.upsert_node(n)
        elig = ClassEligibility(nt, nodes)
        cons = [Constraint(LTarget="${attr.kernel.name}", RTarget="linux",
                           Operand="=")]
        mask, table, escaped = elig.job_mask("j1", cons)
        assert not escaped
        rows = [nt.row_of[n.ID] for n in nodes]
        assert mask[rows[0]] and mask[rows[1]] and mask[rows[3]]
        assert not mask[rows[2]]

    def test_escaped_constraint_per_node(self):
        nt = NodeTensor()
        n1, n2 = mock.node(), mock.node()
        n1.Attributes["unique.network.ip-address"] = "10.0.0.1"
        n2.Attributes["unique.network.ip-address"] = "10.0.0.2"
        for n in (n1, n2):
            compute_node_class(n)
            nt.upsert_node(n)
        # Same computed class (unique.* excluded) but different unique attrs.
        assert n1.ComputedClass == n2.ComputedClass
        elig = ClassEligibility(nt, [n1, n2])
        cons = [Constraint(LTarget="${attr.unique.network.ip-address}",
                           RTarget="10.0.0.1", Operand="=")]
        mask, _, escaped = elig.job_mask("j1", cons)
        assert escaped
        assert mask[nt.row_of[n1.ID]]
        assert not mask[nt.row_of[n2.ID]]


class TestPlaceBatchKernel:
    def _inputs(self, n=64, p=8):
        import jax.numpy as jnp

        capacity = np.full((n, RES_DIMS), 1000, np.float32)
        score_cap = np.full((n, 2), 1000, np.float32)
        usage = np.zeros((n, RES_DIMS), np.float32)
        masks = np.ones((1, n), bool)
        demands = np.full((p, RES_DIMS), 100, np.float32)
        return dict(
            capacity=jnp.asarray(capacity), score_cap=jnp.asarray(score_cap),
            usage=jnp.asarray(usage), tg_masks=jnp.asarray(masks),
            job_counts=jnp.zeros(n, jnp.int32), demands=jnp.asarray(demands),
            tg_ids=jnp.zeros(p, jnp.int32), valid=jnp.ones(p, bool),
            noise=jnp.zeros(n, jnp.float32), penalty=jnp.float32(10.0),
            distinct_hosts=jnp.asarray(False),
            banned0=jnp.zeros(n, bool))

    def test_spreads_with_anti_affinity(self):
        from nomad_tpu.scheduler import kernels

        kw = self._inputs()
        res = kernels.place_batch(**kw)
        chosen = np.asarray(res.chosen)
        assert (chosen >= 0).all()
        # Penalty 10 dominates bin-pack deltas: placements spread.
        assert len(set(chosen.tolist())) == 8

    def test_packs_without_penalty(self):
        import jax.numpy as jnp

        from nomad_tpu.scheduler import kernels

        kw = self._inputs()
        kw["penalty"] = jnp.float32(0.0)
        res = kernels.place_batch(**kw)
        chosen = np.asarray(res.chosen)
        # Bin packing: everything lands on one node until full.
        assert len(set(chosen.tolist())) == 1

    def test_capacity_exhaustion(self):
        import jax.numpy as jnp

        from nomad_tpu.scheduler import kernels

        kw = self._inputs(n=2, p=8)
        kw["tg_masks"] = jnp.ones((1, 2), bool)
        kw["job_counts"] = jnp.zeros(2, jnp.int32)
        kw["noise"] = jnp.zeros(2, jnp.float32)
        kw["banned0"] = jnp.zeros(2, bool)
        # 2 nodes x 1000 cap, 8 placements x 300: only 3 fit per node.
        kw["demands"] = jnp.full((8, RES_DIMS), 300, jnp.float32)
        res = kernels.place_batch(**kw)
        chosen = np.asarray(res.chosen)
        assert (chosen >= 0).sum() == 6
        assert (chosen < 0).sum() == 2

    def test_distinct_hosts(self):
        import jax.numpy as jnp

        from nomad_tpu.scheduler import kernels

        kw = self._inputs(n=4, p=8)
        kw["tg_masks"] = jnp.ones((1, 4), bool)
        kw["job_counts"] = jnp.zeros(4, jnp.int32)
        kw["noise"] = jnp.zeros(4, jnp.float32)
        kw["banned0"] = jnp.zeros(4, bool)
        kw["demands"] = jnp.full((8, RES_DIMS), 10, jnp.float32)
        kw["distinct_hosts"] = jnp.asarray(True)
        res = kernels.place_batch(**kw)
        chosen = np.asarray(res.chosen)
        placed = chosen[chosen >= 0]
        assert len(placed) == 4  # one per host, rest fail
        assert len(set(placed.tolist())) == 4


def _served_stack(nodes, job, seed):
    """A GenericStack over `nodes` as the served path holds one: a state
    store with the node table attached, an eval's plan and context."""
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.stack import GenericStack
    from nomad_tpu.state.state_store import StateStore

    store = StateStore()
    tindex = TensorIndex.attach(store)
    for i, n in enumerate(nodes):
        store.upsert_node(i + 1, n)
    ev = mock.eval()
    ev.JobID = job.ID
    ctx = EvalContext(store.snapshot(), ev.make_plan(job, copy_job=False))
    stack = GenericStack(ctx, tindex, batch=False, rng=random.Random(seed))
    stack.set_nodes(nodes)
    stack.set_job(job)
    return stack


class TestPipelinedPlacer:
    """A window's placements through GenericStack.prepare_batch + dispatch,
    the calls the pipelined worker makes."""

    def test_chained_contention(self):
        """Evals in one window contend for capacity device-side."""
        node = mock.node()  # 3900 usable CPU
        job = mock.job()
        job.TaskGroups[0].Tasks[0].Resources.CPU = 1000
        job.TaskGroups[0].Tasks[0].Resources.Networks = []
        stack = _served_stack([node], job, seed=1)
        prep = stack.prepare_batch([job.TaskGroups[0]])
        # 6 evals x 1 placement x 1000 CPU on one 3900-CPU node: 3 fit.
        # Each eval's usage input is the previous one's usage_after, never
        # copied back (the window's device chain).
        usage, results = None, []
        for _ in range(6):
            res = stack.dispatch(prep, usage_override=usage)
            usage = res.usage_after
            results.append(res.packed)
        placed = sum(int((np.asarray(r)[:prep.n_valid, 0] >= 0).sum())
                     for r in results)
        assert placed == 3

    def test_matches_stack_semantics(self):
        nodes = [mock.node() for _ in range(8)]
        job = mock.job()
        job.TaskGroups[0].Tasks[0].Resources.Networks = []
        stack = _served_stack(nodes, job, seed=1)
        prep = stack.prepare_batch([job.TaskGroups[0]] * 8)
        chosen_rows = np.asarray(stack.dispatch(prep).packed)[:8, 0]
        assert (chosen_rows >= 0).all()
        # Anti-affinity spreads over all 8 nodes.
        assert len(set(chosen_rows.tolist())) == 8


class TestSharding:
    def test_place_batch_sharded_8dev(self):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        from nomad_tpu.parallel import place_batch_sharded, scheduling_mesh

        mesh = scheduling_mesh(jax.devices()[:8])
        n, p = 512, 16
        rng = np.random.default_rng(0)
        res = place_batch_sharded(
            mesh,
            rng.uniform(1000, 4000, (n, 5)).astype(np.float32),
            rng.uniform(800, 3800, (n, 2)).astype(np.float32),
            np.zeros((n, 5), np.float32),
            np.ones((1, n), bool),
            np.zeros(n, np.int32),
            np.full((p, 5), 50, np.float32),
            np.zeros(p, np.int32),
            np.ones(p, bool),
            (rng.random(n) * 1e-3).astype(np.float32),
            np.float32(10.0),
            np.asarray(False),
            np.zeros(n, bool),
        )
        packed = np.asarray(res.packed)
        chosen = packed[:, 0].astype(np.int32)
        assert (chosen >= 0).all()
        assert len(set(chosen.tolist())) == p  # spread

    def test_sharded_matches_single_device(self):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        import jax.numpy as jnp

        from nomad_tpu.parallel import place_batch_sharded, scheduling_mesh
        from nomad_tpu.scheduler import kernels

        n, p = 256, 8
        rng = np.random.default_rng(3)
        args = [
            rng.uniform(1000, 4000, (n, 5)).astype(np.float32),
            rng.uniform(800, 3800, (n, 2)).astype(np.float32),
            np.zeros((n, 5), np.float32),
            np.ones((1, n), bool),
            np.zeros(n, np.int32),
            np.full((p, 5), 50, np.float32),
            np.zeros(p, np.int32),
            np.ones(p, bool),
            (rng.random(n) * 1e-3).astype(np.float32),
            np.float32(10.0),
            np.asarray(False),
            np.zeros(n, bool),
        ]
        single = kernels.place_batch(*[jnp.asarray(a) for a in args])
        mesh = scheduling_mesh(jax.devices()[:8])
        sharded = place_batch_sharded(mesh, *args)
        np.testing.assert_array_equal(np.asarray(single.packed)[:, 0],
                                      np.asarray(sharded.packed)[:, 0])


class TestKeyedKernel:
    """The keyed-candidate kernel (kernels.place_batch_keyed) must be
    bit-identical to the monolithic scan kernels for every valid
    placement, single-device and sharded, with and without
    distinct_hosts and multi-eval resets. Exactness argument in the
    kernel's module comment; these tests are the empirical check."""

    def _inputs(self, n=512, t=3, seed=0):
        rng = np.random.default_rng(seed)
        return rng, dict(
            capacity=rng.uniform(1000, 4000, (n, 5)).astype(np.float32),
            score_cap=rng.uniform(800, 3800, (n, 2)).astype(np.float32),
            usage=rng.uniform(0, 500, (n, 5)).astype(np.float32),
            tg_masks=rng.random((t, n)) < 0.9,
            job_counts=rng.integers(0, 3, n).astype(np.int32),
            key_demands=rng.uniform(10, 100, (t, 5)).astype(np.float32),
            noise=(rng.random(n) * 1e-3).astype(np.float32),
            banned0=rng.random(n) < 0.05,
        )

    @pytest.mark.parametrize(
        "p,n_valid,distinct,multi",
        [(64, 61, False, False), (64, 64, True, False),
         (128, 128, False, True), (256, 250, True, True),
         (8, 5, False, False)])
    def test_bit_identical_to_monolithic(self, p, n_valid, distinct, multi):
        import jax

        from nomad_tpu.parallel import scheduling_mesh
        from nomad_tpu.scheduler import kernels

        rng, d = self._inputs()
        t = d["key_demands"].shape[0]
        tg_ids = rng.integers(0, t, p).astype(np.int32)
        valid = np.zeros(p, bool)
        valid[:n_valid] = True
        demands = d["key_demands"][tg_ids] * valid[:, None]
        reset = np.zeros(p, bool)
        if multi:
            reset[::8] = True
        dd = np.asarray(distinct)
        if multi:
            ref = kernels.place_batch_multi(
                d["capacity"], d["score_cap"], d["usage"], d["tg_masks"],
                d["job_counts"], demands, tg_ids, valid, d["noise"],
                np.float32(10.0), dd, d["banned0"], reset)
        else:
            ref = kernels.place_batch(
                d["capacity"], d["score_cap"], d["usage"], d["tg_masks"],
                d["job_counts"], demands, tg_ids, valid, d["noise"],
                np.float32(10.0), dd, d["banned0"])
        meshes = [None]
        if len(jax.devices()) >= 8:
            meshes.append(scheduling_mesh(jax.devices()[:8]))
        for mesh in meshes:
            res = kernels.place_batch_keyed(
                mesh, d["capacity"], d["score_cap"], d["usage"],
                d["tg_masks"], d["job_counts"], d["key_demands"], tg_ids,
                valid, d["noise"], np.float32(10.0), dd, d["banned0"],
                reset, n_valid)
            rp = np.asarray(ref.packed)
            bp = np.asarray(res.packed)
            np.testing.assert_array_equal(rp[valid], bp[valid])
            # Padding placements: chosen/score contract holds (n_feasible
            # is unspecified there — no consumer reads it).
            assert (bp[~valid, 0] == -1).all()
            assert np.isneginf(bp[~valid, 1]).all()
            np.testing.assert_array_equal(np.asarray(ref.usage_after),
                                          np.asarray(res.usage_after))

    def test_compaction_survives_starved_key_with_duplicates(self):
        """Regression: a key with almost no feasible rows pads its trim
        slots with -inf entries that can be another key's duplicate
        candidate copies; the compaction dedup must rebuild
        first-occurrence from scratch (identical copies are
        interchangeable) instead of carrying the pre-trim keep mask, or
        rows vanish from the feasible table."""
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        from nomad_tpu.parallel import scheduling_mesh
        from nomad_tpu.scheduler import kernels

        n, t, p = 512, 2, 64
        rng = np.random.default_rng(9)
        d = dict(
            capacity=rng.uniform(1000, 4000, (n, 5)).astype(np.float32),
            score_cap=rng.uniform(800, 3800, (n, 2)).astype(np.float32),
            usage=rng.uniform(0, 300, (n, 5)).astype(np.float32),
            job_counts=np.zeros(n, np.int32),
            noise=(rng.random(n) * 1e-3).astype(np.float32),
            banned0=np.zeros(n, bool),
        )
        # Key 0 is eligible on 2 rows only (every shard's top-k for it is
        # mostly -inf padding); key 1 is eligible broadly. With 8 shards
        # of 64 rows and a 64-candidate budget, every row appears in both
        # keys' local candidate sets, so duplicates are guaranteed and
        # compaction (2*64 < 1024) is active.
        tg_masks = np.zeros((t, n), bool)
        tg_masks[0, [3, 200]] = True
        tg_masks[1] = rng.random(n) < 0.95
        kd = np.array([[30, 40, 0, 0, 0], [20, 25, 0, 0, 0]], np.float32)
        tg_ids = np.asarray([0] * 4 + [1] * 60, np.int32)
        valid = np.ones(p, bool)
        demands = kd[tg_ids]
        reset = np.zeros(p, bool)
        ref = kernels.place_batch(
            d["capacity"], d["score_cap"], d["usage"], tg_masks,
            d["job_counts"], demands, tg_ids, valid, d["noise"],
            np.float32(10.0), np.asarray(False), d["banned0"])
        one = kernels.place_batch_keyed(
            None, d["capacity"], d["score_cap"], d["usage"], tg_masks,
            d["job_counts"], kd, tg_ids, valid, d["noise"],
            np.float32(10.0), np.asarray(False), d["banned0"], reset, p)
        mesh = scheduling_mesh(jax.devices()[:8])
        res = kernels.place_batch_keyed(
            mesh, d["capacity"], d["score_cap"], d["usage"], tg_masks,
            d["job_counts"], kd, tg_ids, valid, d["noise"],
            np.float32(10.0), np.asarray(False), d["banned0"], reset, p)
        rp = np.asarray(ref.packed)
        mp = np.asarray(res.packed)
        # The regression under test is candidate SELECTION: a dropped row
        # would flip a chosen index or an n_feasible count. Those (and
        # the chained usage) must match the monolithic scan exactly.
        np.testing.assert_array_equal(rp[:, 0], mp[:, 0])
        np.testing.assert_array_equal(rp[:, 2], mp[:, 2])
        np.testing.assert_array_equal(np.asarray(ref.usage_after),
                                      np.asarray(res.usage_after))
        # Scores: <= 2 ulp vs the scan on XLA:CPU. Environmental, not a
        # selection bug — the replay and the scan are two differently
        # fused compilations of the same f32 ops (`- counts * penalty
        # + noise` may or may not FMA-contract per fusion shape), and
        # this shape's data lands on a boundary (observed: one score of
        # 64 off by ~1e-6, chosen rows and usage bit-identical; the
        # same codegen class as the historical keyed-vs-scan seed
        # failures). On TPU both programs round identically.
        np.testing.assert_array_almost_equal_nulp(
            np.where(np.isfinite(rp[:, 1]), rp[:, 1], 0.0),
            np.where(np.isfinite(mp[:, 1]), mp[:, 1], 0.0), nulp=2)
        # The ISSUE-12 parity bar is exact: the sharded pipeline must
        # match the SINGLE-DEVICE keyed kernel bit-for-bit.
        np.testing.assert_array_equal(np.asarray(one.packed), mp)

    def test_sharded_collective_count_is_per_window(self):
        """The point of the shard-local mesh pipeline: NO compiled
        program contains a collective. The cold stage scores and top-Ks
        only its own shard's rows (shard_map, no cross-shard ops), the
        winner-row exchange is an explicit device_put — a point-to-point
        transfer, not a rendezvous collective — and warm windows run
        entirely on the lead device. The naive SPMD scan pays 2
        collectives PER PLACEMENT inside its scan body; the old
        single-program keyed variant paid 2 per window. Now: zero."""
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        from nomad_tpu.parallel import scheduling_mesh
        from nomad_tpu.scheduler import kernels

        mesh = scheduling_mesh(jax.devices()[:8])
        p = 64
        counts = kernels.mesh_collective_audit(
            mesh, kernels.keyed_cand_count(p), n_rows=512,
            n_keys=self._inputs()[1]["key_demands"].shape[0], p_pad=p)
        assert counts["cold"] == 0, counts
        assert counts["pool_build"] == 0, counts
        assert counts["warm"] == 0, counts
        assert counts["apply"] == 0, counts


class TestPlacementQualityParity:
    def test_tpu_at_least_as_good_as_reference_algorithm(self):
        """Global argmax must reach >= the reference iterator chain's total
        bin-pack score on the same workload."""
        from nomad_tpu.scheduler.cpu_reference import CPUReferenceStack

        nodes = []
        rng = np.random.default_rng(11)
        for i in range(50):
            n = mock.node()
            # Heterogeneous capacity so scores differ meaningfully.
            n.Resources.CPU = int(rng.integers(2000, 8000))
            n.Resources.MemoryMB = int(rng.integers(4096, 16384))
            compute_node_class(n)
            nodes.append(n)

        job = mock.job()
        job.TaskGroups[0].Tasks[0].Resources.Networks = []
        tgs = [job.TaskGroups[0]] * 20

        stack = _served_stack(nodes, job, seed=5)
        prep = stack.prepare_batch(tgs)
        packed = np.asarray(stack.dispatch(prep).packed)[:len(tgs)]
        tpu_scores = packed[packed[:, 0] >= 0, 1]
        # Remove the tie-break noise contribution before comparing.
        tpu_total = float(tpu_scores.sum()) - 1e-3 * len(tpu_scores)

        ref = CPUReferenceStack(nodes, rng=random.Random(5))
        ref.set_job(job)
        ref_results = [r for r in ref.select_batch(tgs) if r is not None]
        ref_total = sum(s for _, s in ref_results)

        assert len(tpu_scores) >= len(ref_results)
        assert tpu_total >= ref_total - 1e-3


class TestHostKernelParity:
    """place_batch_host is the numpy mirror used for shallow windows; on
    XLA's CPU backend its placements must match the device kernel
    exactly on the same inputs (same f32 BestFit-v3 + Inf/NaN edges,
    same anti-affinity and noise tie-break, same in-loop usage
    chaining). chip_smoke.py makes the comparison on the chip."""

    def _inputs(self, seed, n=256, p=48, t=8):
        import numpy.random as nr

        rng = nr.default_rng(seed)
        capacity = rng.uniform(100, 4000, (n, 8)).astype(np.float32)
        usage = (capacity * rng.uniform(0, 0.9, (n, 8))).astype(np.float32)
        score_cap = capacity[:, :2] * rng.uniform(
            0.5, 1.0, (n, 2)).astype(np.float32)
        tg_masks = rng.random((t, n)) < 0.7
        job_counts = rng.integers(0, 3, n).astype(np.int32)
        demands = rng.uniform(1, 500, (p, 8)).astype(np.float32)
        tg_ids = rng.integers(0, t, p).astype(np.int32)
        valid = rng.random(p) < 0.9
        noise = (rng.random(n) * 1e-3).astype(np.float32)
        banned = rng.random(n) < 0.05
        return (capacity, score_cap, usage, tg_masks, job_counts, demands,
                tg_ids, valid, noise, np.float32(10.0), True, banned)

    @pytest.mark.parametrize("seed", [0, 7, 42])
    def test_host_matches_device(self, seed):
        import jax.numpy as jnp

        from nomad_tpu.scheduler import kernels

        args = self._inputs(seed)
        dev = kernels.place_batch(*[jnp.asarray(a) for a in args])
        host = kernels.place_batch_host(*args)
        dev_packed = np.asarray(dev.packed)
        # Same placement decisions row-for-row.
        np.testing.assert_array_equal(dev_packed[:, 0], host.packed[:, 0])
        np.testing.assert_array_equal(dev_packed[:, 2], host.packed[:, 2])
        # Scores agree to f32 tolerance (TPU transcendental approximations
        # may differ in the last ulps from host libm).
        finite = np.isfinite(dev_packed[:, 1])
        np.testing.assert_array_equal(finite, np.isfinite(host.packed[:, 1]))
        np.testing.assert_allclose(dev_packed[finite, 1],
                                   host.packed[finite, 1],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(dev.usage_after),
                                   host.usage_after, rtol=1e-5, atol=1e-3)

    def test_distinct_hosts_off(self):
        import jax.numpy as jnp

        from nomad_tpu.scheduler import kernels

        args = list(self._inputs(3))
        args[10] = False  # distinct_hosts off: banned must be ignored
        dev = kernels.place_batch(*[jnp.asarray(a) for a in args])
        host = kernels.place_batch_host(*args)
        np.testing.assert_array_equal(
            np.asarray(dev.packed)[:, 0], host.packed[:, 0])


class TestMultiKernelParity:
    """place_batch_multi fuses a window of same-shaped evals into one scan
    with per-eval resets of the job-local state; its placements must be
    IDENTICAL to dispatching place_batch per eval chained on usage."""

    def test_multi_matches_sequential_chain(self):
        import jax
        import jax.numpy as jnp

        from nomad_tpu.scheduler import kernels

        rng = np.random.default_rng(11)
        n, p, t, evals = 256, 16, 3, 5
        capacity = rng.uniform(500, 3000, (n, 8)).astype(np.float32)
        score_cap = capacity[:, :2].copy()
        usage0 = (capacity * rng.uniform(0, 0.5, (n, 8))).astype(np.float32)
        tg_masks = rng.random((t, n)) < 0.8
        jc0 = np.zeros(n, np.int32)
        demands = rng.uniform(1, 200, (p, 8)).astype(np.float32)
        tg_ids = rng.integers(0, t, p).astype(np.int32)
        valid = np.ones(p, bool)
        noise = (rng.random(n) * 1e-3).astype(np.float32)
        banned0 = np.zeros(n, bool)

        # Sequential per-eval chain.
        usage = jnp.asarray(usage0)
        seq_packed = []
        for _ in range(evals):
            res = kernels.place_batch(
                jnp.asarray(capacity), jnp.asarray(score_cap), usage,
                jnp.asarray(tg_masks), jnp.asarray(jc0),
                jnp.asarray(demands), jnp.asarray(tg_ids),
                jnp.asarray(valid), jnp.asarray(noise), jnp.float32(10.0),
                jnp.asarray(True), jnp.asarray(banned0))
            seq_packed.append(np.asarray(res.packed))
            usage = res.usage_after
        seq_usage = np.asarray(usage)

        # One multi kernel over the same five evals.
        reset = np.zeros(evals * p, bool)
        reset[::p] = True
        multi = kernels.place_batch_multi(
            jnp.asarray(capacity), jnp.asarray(score_cap),
            jnp.asarray(usage0), jnp.asarray(tg_masks), jnp.asarray(jc0),
            jnp.asarray(np.tile(demands, (evals, 1))),
            jnp.asarray(np.tile(tg_ids, evals)),
            jnp.asarray(np.tile(valid, evals)), jnp.asarray(noise),
            jnp.float32(10.0), jnp.asarray(True), jnp.asarray(banned0),
            jnp.asarray(reset))
        multi_packed = np.asarray(multi.packed)
        for e in range(evals):
            np.testing.assert_array_equal(
                multi_packed[e * p:(e + 1) * p], seq_packed[e],
                err_msg=f"eval {e} diverged")
        np.testing.assert_allclose(np.asarray(multi.usage_after),
                                   seq_usage, rtol=1e-6, atol=1e-3)
