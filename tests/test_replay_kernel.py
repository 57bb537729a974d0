"""Fixed-seed gates for the keyed program's resident replay (ISSUE 37).

Step 3 of kernels._keyed_program runs as one Pallas loop over VMEM-resident
candidate columns (scheduler/replay_kernel.py); the `lax.scan` it replaced
stays in the file as the exact path. Here, on XLA's CPU backend with the
kernel under `interpret=True`:

- the resident loop equals the scan bit for bit (chosen rows, scores,
  feasible counts of valid steps, usage_after) on the shapes the
  benchmark's cells launch and on the edges a window can hold: resets with
  two keys, a chain whose candidate count is clipped to the table,
  distinct_hosts, evals that fail for want of room, padding evals;
- kernels.keyed_replay_resident, the one rule from static shape, agrees
  with the program that was built (a pallas_call in it or not), also for a
  shape the kernel declines;
- a served window counts `launch_resident` once a device launch.
"""

import json
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.deploy.dev_agent import build_fleet, seeded_uuid
from benchmark.reference import kernel_mirror_chain
from nomad_tpu.scheduler import kernels, replay_kernel
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.pipelined_worker import STATS_COUNTERS, PipelinedWorker
from nomad_tpu.structs import Job, from_dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "c1m-5k.json")) as _f:
    C1M = json.load(_f)

SVC_ASK = [20, 32, 10, 0, 0]
WEB_ASK = [500, 256, 150, 0, 50]
CACHE_ASK = [500, 256, 10, 0, 0]


def _window(seed, n, asks, evals, e_pad, count, p_pad, fill=(0.1, 0.3),
            distinct=False, held=0.0):
    """A fleet of mock.Node's shape (4,000 MHz / 8 GiB / 100 GiB / 1,000
    MBits, whole numbers as the served table holds them) filled to a seeded
    share, and one launch as stack.dispatch / dispatch_multi assemble it:
    `evals` real evals of `count` placements in pads of `p_pad`, the eval
    axis padded to `e_pad`, the keys dealt over an eval's placements in
    blocks. `held`: the share of nodes that already hold the job."""
    rng = np.random.default_rng(seed)
    keys = len(asks)
    cap = np.tile(np.float32([4000, 8192, 102400, 0, 1000]), (n, 1))
    rsv = np.float32([100, 256, 4096, 0, 1])
    usage = np.round(rsv + rng.uniform(*fill, (n, 1)) * (cap - rsv))
    one = np.zeros(p_pad, np.int32)
    one[:count] = np.sort(np.arange(count) % keys)
    valid = np.tile(np.arange(p_pad) < count, e_pad)
    valid[evals * p_pad:] = False
    reset = np.zeros(e_pad * p_pad, bool)
    reset[::p_pad] = e_pad > 1
    has = rng.random(n) < held
    args = (cap, cap[:, :2] - rsv[:2], usage.astype(np.float32),
            rng.random((keys, n)) < 0.9, has.astype(np.int32),
            np.float32(asks), np.tile(one, e_pad), valid,
            (rng.random(n) * 1e-3).astype(np.float32), np.float32(10.0),
            np.asarray(distinct), has, reset)
    return args, evals * count


def _chain_window():
    # tests/test_c1m_shape.py's window: 4 evals of 1,000 in pads of 1,024
    # over 256 rows filled to the brim, so the candidate count (4,096) is
    # clipped to the table and a row takes more than 64 adds.
    inp = kernel_mirror_chain.window_inputs(C1M, "c1m-1000", 2 ** 31 + 32,
                                            256, 200, 4)
    launch = inp["launches"][0]
    n = inp["capacity"].shape[0]
    args = (inp["capacity"], inp["score_cap"], inp["usage"], launch["masks"],
            np.zeros(n, np.int32), launch["asks"], launch["tg_ids"],
            launch["valid"], inp["noise"], inp["penalty"], np.asarray(False),
            np.zeros(n, bool), launch["reset"])
    return args, launch["n_valid"]


# name -> (window, candidate count the cell's launch has, minimum share of
# valid placements that must fail, or None where all must be placed)
CASES = {
    # web-10k.storm: a launch an eval, 10 placements in a pad of 16.
    "web-1key-16steps-16cands": (
        lambda: _window(1, 1024, [WEB_ASK], 1, 1, 10, 16), 16, None),
    # svc-10k.storm: a full window, 32 x 50 in pads of 64.
    "svc-1key-2048steps-2048cands": (
        lambda: _window(2, 4096, [SVC_ASK], 32, 32, 50, 64), 2048, None),
    # dc-50k.storm's global-2tg: 40 web + 10 cache, a reset an eval.
    "dc-2keys-with-resets": (
        lambda: _window(3, 2048, [SVC_ASK, CACHE_ASK], 4, 4, 50, 64,
                        held=0.1), 256, None),
    "c1m-chain-clipped-to-the-table": (_chain_window, 4096, None),
    "distinct-hosts": (
        lambda: _window(4, 1024, [SVC_ASK, CACHE_ASK], 3, 4, 50, 64,
                        distinct=True, held=0.2), 256, None),
    # 48 nodes with room for ~2 of the 500 MHz ask each: the last evals of
    # 8 x 50 find no room.
    "last-evals-fail-for-want-of-room": (
        lambda: _window(5, 48, [CACHE_ASK], 8, 8, 50, 64, fill=(0.6, 0.8)),
        512, 0.3),
    # 3 real evals in a launch padded to 8.
    "padding-evals": (
        lambda: _window(6, 512, [SVC_ASK], 3, 8, 50, 64), 256, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_resident_replay_equals_the_scan_bit_for_bit(case):
    make, k_cand, must_fail = CASES[case]
    args, n_valid = make()
    n, keys, valid = args[0].shape[0], args[5].shape[0], args[7]
    assert kernels.keyed_cand_count(n_valid) == k_cand
    assert kernels.keyed_replay_resident(n, 5, keys, k_cand)
    scan = kernels._keyed_program(None, k_cand, "scan")(*args)
    res = kernels.place_batch_keyed(None, *args, n_valid=n_valid)
    want, got = np.asarray(scan[0]), np.asarray(res.packed)
    # Rows and scores of every step; feasible counts of the valid ones
    # (a padding step's is unspecified: kernels.py, "keyed candidates").
    np.testing.assert_array_equal(got[:, :2], want[:, :2])
    np.testing.assert_array_equal(got[valid], want[valid])
    np.testing.assert_array_equal(np.asarray(res.usage_after),
                                  np.asarray(scan[1]))
    placed = got[valid, 0] >= 0
    assert (got[~valid, 0] == -1).all()
    if must_fail is None:
        assert placed.all()
    else:
        assert must_fail <= 1 - placed.mean() < 1
        assert placed[:50].all() and not placed[-50:].any()


def test_the_chain_case_is_the_one_test_c1m_shape_holds_to_the_oracle():
    args, n_valid = _chain_window()
    res = kernels.place_batch_keyed(None, *args, n_valid=n_valid)
    packed = np.asarray(res.packed)
    facts = kernel_mirror_chain.chain_facts(
        {"capacity": args[0], "score_cap": args[1], "usage": args[2],
         "noise": args[8], "penalty": args[9],
         "launches": [{"asks": args[5], "tg_ids": args[6], "valid": args[7],
                       "reset": args[12], "masks": args[3], "p_pad": 1024,
                       "evals": 4, "n_valid": n_valid,
                       "template": "c1m-1000"}]}, [packed])
    assert facts["max_adds_on_a_row"] > 64
    assert kernels.keyed_cand_count(n_valid) > args[0].shape[0]


# ------------------------------------------------ the rule and the program
def _shapes(n, keys, p):
    s = jax.ShapeDtypeStruct
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    return [s((n, 5), f32), s((n, 2), f32), s((n, 5), f32), s((keys, n), b),
            s((n,), i32), s((keys, 5), f32), s((p,), i32), s((p,), b),
            s((n,), f32), s((), f32), s((), b), s((n,), b), s((p,), b)]


@pytest.mark.parametrize("n,keys,k_cand,p,resident", [
    (16384, 1, 16, 16, True),          # web-10k.storm
    (16384, 1, 2048, 2048, True),      # svc-10k.storm
    (65536, 2, 2048, 2048, True),      # dc-50k.storm, global-2tg
    (8192, 1, 32768, 32768, True),     # c1m-5k.fill
    (1 << 17, 16, 8192, 2048, True),   # the keyed budget, 16 keys
    (1 << 17, 64, 2048, 2048, False),  # the keyed budget, 64 keys: declined
], ids=["web", "svc", "dc-2keys", "c1m", "budget-16keys", "budget-64keys"])
def test_the_shape_rule_agrees_with_the_program_that_is_built(
        n, keys, k_cand, p, resident):
    assert kernels.keyed_replay_resident(n, 5, keys, k_cand) is resident
    built = str(jax.make_jaxpr(kernels._keyed_program(None, k_cand))(
        *_shapes(n, keys, p)))
    assert ("pallas_call" in built) is resident
    if not resident:
        assert "scan[" in built
    oracle = str(jax.make_jaxpr(kernels._keyed_program(None, k_cand, "scan"))(
        *_shapes(n, keys, p)))
    assert "pallas_call" not in oracle and "scan[" in oracle


def test_the_rule_is_the_kernels_own_count_of_what_it_keeps_resident():
    # 8,192 candidates of one key: 5 + 6 table columns, usage in and out,
    # one eligibility column, two carried columns, the result block.
    assert replay_kernel.resident_bytes(8192, 1, 5) == (
        4 * 8192 * (11 + 10 + 1 + 2) + 2 * 4 * 1024 * 128)
    assert replay_kernel.resident_bytes(16, 1, 5) == \
        replay_kernel.resident_bytes(1024, 1, 5)  # whole vregs
    assert replay_kernel.fits(1 << 17, 16, 5)
    assert not replay_kernel.fits(1 << 17, 64, 5)
    assert not replay_kernel.fits(1024, 1 << 16, 5)  # the key's 16 bits


# --------------------------------------------------- the counter, served
def _server(nodes):
    srv = Server(ServerConfig(num_schedulers=0, pipelined_scheduling=True,
                              scheduler_window=32, host_placement=False))
    srv.establish_leadership()
    for node in build_fleet(C1M["fleet"], nodes, random.Random(37)):
        srv.node_register(node)
    worker = PipelinedWorker(
        srv.raft, srv.eval_broker, srv.plan_queue, srv.blocked_evals,
        srv.tindex, ["service", "batch", "system"], window=32,
        host_placement=False)
    return srv, worker


def test_a_served_window_counts_launch_resident_once_a_launch():
    assert "launch_resident" in STATS_COUNTERS
    srv, worker = _server(48)
    seen = []
    real = kernels.keyed_replay_resident

    def rule(*shape):
        seen.append(shape)
        return real(*shape)

    kernels.keyed_replay_resident = rule
    try:
        rng = random.Random(137)
        # Two shapes: a fused run of three jobs of 200 and a run of one of
        # 50 are two launches of one window.
        for count in (200, 200, 50, 200):
            job = from_dict(Job, C1M["jobs"]["c1m-1000"])
            job.ID = seeded_uuid(rng)
            job.Name = f"j-{job.ID[:8]}"
            job.TaskGroups[0].Count = count
            srv.job_register(job)
        batch = worker._dequeue_window()
        work = worker._dispatch_window(batch)
        assert work is not None and not work.slow
        work.packed = worker._drain_window(work)
        worker._finish_fast(work)
    finally:
        kernels.keyed_replay_resident = real
        srv.shutdown()
    stats = worker.stats
    assert stats["launches"] == 2 and stats["fast"] == 4
    assert stats["launch_resident"] == stats["launches"]
    # Asked once a launch by the counter, with the shape the builder is
    # asked with when it traces the launch's program: rows, resource
    # columns, keys, candidate count.
    rows = srv.tindex.nt.n_rows
    counted = [s for s in seen if s in ((rows, 5, 1, 1024), (rows, 5, 1, 64))]
    assert {(rows, 5, 1, 1024), (rows, 5, 1, 64)} <= set(counted)
    assert set(seen) == set(counted)


def test_a_launch_the_kernel_declines_is_not_counted():
    srv, worker = _server(16)
    real = replay_kernel.fits
    replay_kernel.fits = lambda *shape: False
    kernels._keyed_program.cache_clear()
    try:
        rng = random.Random(237)
        for _ in range(2):
            job = from_dict(Job, C1M["jobs"]["c1m-1000"])
            job.ID = seeded_uuid(rng)
            job.Name = f"j-{job.ID[:8]}"
            job.TaskGroups[0].Count = 30
            srv.job_register(job)
        work = worker._dispatch_window(worker._dequeue_window())
        work.packed = worker._drain_window(work)
        worker._finish_fast(work)
    finally:
        replay_kernel.fits = real
        kernels._keyed_program.cache_clear()
        srv.shutdown()
    assert worker.stats["launches"] == 1 and worker.stats["fast"] == 2
    assert worker.stats["launch_resident"] == 0
