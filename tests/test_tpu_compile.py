"""The served path's device programs compile for a TPU v5e at served widths.

No chip is attached where the tests run, but the TPU's compiler is
installed and compiles for a chip that is described: what it refuses here
(an op it cannot lower, a program that does not fit) it would refuse on the
machine with the chip, at the cost of chip time. Widths are chip_smoke.py's
deployment: BASELINE config 3's 10,000 nodes in a 16,384-row table, the
agent's 32-eval window of 50-placement jobs, and the 1,048,576-row table of
the four-chip phase.

The topology is described inside a fixture, in this one file, and every
program compiles in the test's own process: only one process at a time may
load the TPU's library, and a pytest-xdist worker that loaded it keeps it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from nomad_tpu.scheduler import kernels
from nomad_tpu.tensor import node_table

ROWS = 16_384            # 10,000 nodes padded to a power of two
DC_ROWS = 65_536         # dc-50k: 50,000 nodes in four datacenters
C1M_ROWS = 8_192         # c1m-5k: 5,000 nodes, jobs of 1,000 padded to 1,024
WINDOW_P = 32 * 64       # 32 evals x 50 placements, each padded to 64
MESH_ROWS = 1 << 20

F32, I32, BOOL = jnp.float32, jnp.int32, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An executable compiled for a described chip can be written to the
    # persistent cache but not read back without one; keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _node_inputs(n, sh, keys=1):
    """capacity, score_cap, usage, tg_masks (a row a key), job_counts."""
    return [_shape((n, 5), F32, sh), _shape((n, 2), F32, sh),
            _shape((n, 5), F32, sh), _shape((keys, n), BOOL, sh),
            _shape((n,), I32, sh)]


def _tail_inputs(n, p, sh, reset):
    """tg_ids, valid, noise, penalty, distinct_hosts, banned0 (+ reset)."""
    tail = [_shape((p,), I32, sh), _shape((p,), BOOL, sh),
            _shape((n,), F32, sh), _shape((), F32, sh), _shape((), BOOL, sh),
            _shape((n,), BOOL, sh)]
    return tail + [_shape((p,), BOOL, sh)] if reset else tail


def _compiles(jitted, *args):
    compiled = jitted.lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("program,p,reset", [
    (kernels.place_batch, 64, False),
    (kernels.place_batch_multi, WINDOW_P, True),
], ids=["place_batch-one-eval", "place_batch_multi-window"])
def test_scan_kernels_compile(one_chip, program, p, reset):
    _compiles(program, *_node_inputs(ROWS, one_chip),
              _shape((p, 5), F32, one_chip),
              *_tail_inputs(ROWS, p, one_chip, reset))


def test_keyed_window_program_compiles(one_chip):
    """The storm's program: lax.top_k with k=2048 over the node axis,
    argsort and in-scan scatters."""
    k = kernels.keyed_cand_count(32 * 50)
    assert k == 2048
    _compiles(kernels._keyed_program(None, k, "mosaic"),
              *_node_inputs(ROWS, one_chip), _shape((1, 5), F32, one_chip),
              *_tail_inputs(ROWS, WINDOW_P, one_chip, reset=True))


@pytest.mark.parametrize("keys", [1, 2], ids=["one-key", "two-keys"])
@pytest.mark.parametrize("evals", [1, 32], ids=["one-eval", "window"])
def test_keyed_program_compiles_at_dc50k_widths(one_chip, keys, evals):
    """benchmark/configs/dc-50k.json: a 65,536-row table, jobs of one or
    two task groups (keys), launched as a run of one eval (stack.dispatch)
    up to a whole window of one shape (stack.dispatch_multi)."""
    k = kernels.keyed_cand_count(evals * 50)
    assert keys * k <= 1 << 17  # stack.KEYED_CAND_BUDGET: the keyed path
    _compiles(kernels._keyed_program(None, k, "mosaic"),
              *_node_inputs(DC_ROWS, one_chip, keys),
              _shape((keys, 5), F32, one_chip),
              *_tail_inputs(DC_ROWS, evals * 64, one_chip, reset=True))


def test_keyed_program_compiles_at_c1m_widths(one_chip):
    """benchmark/configs/c1m-5k.json: a full window of 32 jobs of 1,000 is
    one scan of 32,768 steps whose candidate count (32,768) is clipped to
    the 8,192-row table: lax.top_k over the whole node axis, no trim."""
    k = kernels.keyed_cand_count(32 * 1000)
    assert k == 32768 > C1M_ROWS and k <= 1 << 17
    _compiles(kernels._keyed_program(None, k, "mosaic"),
              *_node_inputs(C1M_ROWS, one_chip), _shape((1, 5), F32, one_chip),
              *_tail_inputs(C1M_ROWS, 32 * 1024, one_chip, reset=True))


def test_keyed_program_compiles_at_web10k_widths(one_chip):
    """benchmark/configs/web-10k.json: a task that asks for a network
    shares no prepared batch, so every eval is a launch of its own
    (stack.dispatch): 10 placements padded to 16 with 16 candidates, one
    key, at 16,384 rows; 32 of them chained make a window."""
    k = kernels.keyed_cand_count(10)
    assert k == 16
    _compiles(kernels._keyed_program(None, k, "mosaic"),
              *_node_inputs(ROWS, one_chip), _shape((1, 5), F32, one_chip),
              *_tail_inputs(ROWS, 16, one_chip, reset=True))


@pytest.mark.parametrize("rows,keys,n_valid,steps", [
    (ROWS, 1, 10, 16), (ROWS, 1, 32 * 50, WINDOW_P),
    (DC_ROWS, 2, 32 * 50, WINDOW_P), (C1M_ROWS, 1, 32 * 1000, 32 * 1024),
], ids=["web-10k", "svc-10k", "dc-50k-two-keys", "c1m-5k"])
def test_the_replay_lowers_to_the_resident_kernel_for_the_chip(
        one_chip, rows, keys, n_valid, steps):
    """Lowered for the described v5e, the keyed program of every storm
    cell holds its replay as the Mosaic kernel (scheduler/replay_kernel.py),
    the scan build of the same bucket does not, and the rule from static
    shape says which. (The compiles above are of these same programs.)"""
    k = kernels.keyed_cand_count(n_valid)
    assert kernels.keyed_replay_resident(rows, 5, keys, k)
    args = (*_node_inputs(rows, one_chip, keys),
            _shape((keys, 5), F32, one_chip),
            *_tail_inputs(rows, steps, one_chip, reset=True))
    served = kernels._keyed_program(None, k, "mosaic").lower(*args).as_text()
    assert "tpu_custom_call" in served and "keyed_replay" in served
    oracle = kernels._keyed_program(None, k, "scan").lower(*args).as_text()
    assert "tpu_custom_call" not in oracle


@pytest.mark.parametrize("p_pad", [16, 64, 1024])
def test_compact_window_compiles(one_chip, p_pad):
    _compiles(kernels.compact_window, _shape((32, p_pad, 3), F32, one_chip),
              _shape((32, p_pad), BOOL, one_chip),
              _shape((32,), I32, one_chip))


def test_refresh_scatter_compiles(one_chip):
    chunk = node_table._REFRESH_CHUNKS[-1]
    assert chunk == ROWS
    _compiles(node_table._refresh_program(), _shape((ROWS, 5), F32, one_chip),
              _shape((ROWS, 2), F32, one_chip),
              _shape((ROWS, 5), F32, one_chip),
              _shape((chunk, 3 + 2 * node_table.RES_DIMS), F32, one_chip))


def test_mesh_cold_stage_compiles_on_four_chips_without_collectives(topo):
    """The shard_map stage of the mesh pipeline at 1M rows over the 2x2
    host: a quarter of the rows per chip, and nothing crosses chips inside
    the program."""
    mesh = Mesh(np.array(topo.devices[:4]), ("nodes",))
    node = NamedSharding(mesh, PartitionSpec("nodes"))
    mask = NamedSharding(mesh, PartitionSpec(None, "nodes"))
    rep = NamedSharding(mesh, PartitionSpec())
    prog = kernels._MeshKeyedProgram(mesh, kernels.keyed_cand_count(800))
    compiled = _compiles(
        prog.a_cold, _shape((MESH_ROWS, 10), F32, node),
        _shape((MESH_ROWS, 5), F32, node), _shape((1, MESH_ROWS), BOOL, mask),
        _shape((1, 5), F32, rep), _shape((), F32, rep),
        _shape((), BOOL, rep), _shape((prog.ring_cap, 6), F32, rep))
    hlo = compiled.as_text()
    assert not [c for c in ("all-gather", "all-reduce", "reduce-scatter",
                            "collective-permute") if c in hlo]
    usage_out = compiled.output_shardings[1]
    assert usage_out.shard_shape((MESH_ROWS, 5)) == (MESH_ROWS // 4, 5)
