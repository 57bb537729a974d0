"""Cross-replica state-digest verification (analysis/replica_digest.py +
the fsm/raft wiring): the chain is canonical and deterministic, readback
effects catch silent store corruption within one checkpoint interval,
snapshots reseed the chain, divergence raises the typed error, and a
replicated 3-node cluster detects an injected follower corruption and
recovers via quarantine + reinstall.
"""

import copy
import hashlib
import random
import struct
import uuid

import msgpack
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.analysis import replica_digest
from nomad_tpu.analysis.replica_digest import (
    ReplicaDigest,
    ReplicaDivergenceError,
    StrColumn,
    chaos_corrupt,
    effect_of,
)
from nomad_tpu.raft.backend import encode_command
from nomad_tpu.resilience import failpoints
from nomad_tpu.scheduler.system_sweep import SweepBatch
from nomad_tpu.server.fsm import FSM, MessageType
from nomad_tpu.structs import to_dict


@pytest.fixture(autouse=True)
def _heal_failpoints():
    failpoints.disarm_all()
    yield
    failpoints.disarm_all()


def _node_payloads(n, prefix="n"):
    out = []
    for i in range(n):
        node = mock.node()
        node.ID = f"{prefix}{i}"
        out.append({"Node": to_dict(node)})
    return out


def _replay(payloads, interval=16):
    fsm = FSM()
    fsm.digest = ReplicaDigest(interval=interval)
    for i, p in enumerate(payloads, start=1):
        fsm.apply(i, MessageType.NodeRegister, copy.deepcopy(p))
    return fsm


# ----------------------------------------------------------- chain basics
def test_chain_is_deterministic_across_replicas():
    payloads = _node_payloads(40)
    a, b = _replay(payloads), _replay(payloads)
    assert a.digest.stats()["Chain"] == b.digest.stats()["Chain"]
    assert a.digest.checkpoint() == b.digest.checkpoint() is not None


def test_chain_differs_when_any_effect_differs():
    payloads = _node_payloads(40)
    a = _replay(payloads)
    mutated = copy.deepcopy(payloads)
    mutated[20]["Node"]["Status"] = "down"
    b = _replay(mutated)
    assert a.digest.stats()["Chain"] != b.digest.stats()["Chain"]


def test_checkpoints_land_on_interval_buckets_and_stay_bounded():
    d = ReplicaDigest(interval=10)
    for i in range(1, 201):
        d.fold(i, 0, ("effect", i))
    cps = d.stats()["Checkpoints"]
    assert len(cps) <= 8
    assert all(idx % 10 == 0 for idx in cps)
    idx, hexv = d.checkpoint()
    assert idx == 200 and cps[200] == hexv


def test_verify_matches_skips_and_diverges():
    payloads = _node_payloads(40)
    a, b = _replay(payloads), _replay(payloads)
    idx, hexv = a.digest.checkpoint()
    assert b.digest.verify(idx, hexv) is True
    # Re-verifying the same checkpoint is a skip, not a second compare.
    assert b.digest.verify(idx, hexv) is None
    # An index we never folded to a checkpoint is a skip.
    assert b.digest.verify(idx + 7, "00" * 16) is None
    c = _replay(payloads)
    with pytest.raises(ReplicaDivergenceError) as exc:
        c.digest.verify(idx, "00" * 16)
    assert exc.value.index == idx
    assert c.digest.stats()["Diverged"] == 1


def test_unsynced_digest_never_alarms():
    d = ReplicaDigest(interval=4)
    for i in range(1, 9):
        d.fold(i, 0, i)
    d.mark_unsynced("test")
    assert d.verify(8, "00" * 16) is None
    assert d.checkpoint() is None  # and never exports one either


# ------------------------------------------------------ canonical encoder
def test_encoder_distinguishes_types_and_orders_dicts():
    def chain(effect):
        d = ReplicaDigest()
        d.fold(1, 0, effect)
        return d.stats()["Chain"]

    assert chain({"a": 1, "b": 2}) == chain({"b": 2, "a": 1})
    assert chain(1) != chain("1") != chain(1.0)
    assert chain(None) != chain(0) != chain(False)
    assert chain([1, 2]) != chain([2, 1])
    arr = np.arange(6, dtype=np.int64)
    assert chain(arr) == chain(arr.copy())
    assert chain(arr) != chain(arr.astype(np.int32))
    assert chain(arr) != chain(arr.reshape(2, 3))


# ------------------------------------------------------- effect readbacks
def test_effect_readback_sees_silent_store_corruption():
    """The digest folds what the STORE says, not what the payload says —
    an in-place corruption lands in the chain within one fold."""
    payloads = _node_payloads(20)
    a, b = _replay(payloads), _replay(payloads)
    ev = mock.eval()
    req = {"Evals": [to_dict(ev)]}
    a.apply(21, MessageType.EvalUpdate, copy.deepcopy(req))
    b.apply(21, MessageType.EvalUpdate, copy.deepcopy(req))
    assert a.digest.stats()["Chain"] == b.digest.stats()["Chain"]
    # Corrupt b's store the way the chaos failpoint does, then apply one
    # more (clean) entry touching the corrupt row on both replicas.
    assert chaos_corrupt(b.state, 22, int(MessageType.EvalUpdate), req)
    follow = {"Evals": [to_dict(ev)]}
    ea = effect_of(a.state, 22, int(MessageType.EvalUpdate), follow)
    eb = effect_of(b.state, 22, int(MessageType.EvalUpdate), follow)
    assert ea != eb  # readback, not payload echo


def test_sweep_effect_digests_columns_without_materializing(monkeypatch):
    fsm = FSM()
    fsm.digest = ReplicaDigest(interval=4)
    node = mock.node()
    fsm.apply(1, MessageType.NodeRegister, {"Node": to_dict(node)})
    job = mock.system_job()
    tmpl = mock.alloc()
    tmpl.NodeID = node.ID
    tmpl.JobID, tmpl.Job = job.ID, job
    sweep = {"Templates": [to_dict(tmpl)], "TGIdx": [0, 0],
             "AllocIDs": ["a1", "a2"], "Names": ["w.g[0]", "w.g[1]"],
             "RowNodeIDs": [node.ID], "Counts": [2], "Rows": [0, 0],
             "Delta": np.zeros((1, 4), dtype=np.float32)}
    calls = []
    monkeypatch.setattr(fsm.state, "alloc_by_id",
                        lambda aid: calls.append(aid))
    effect = effect_of(fsm.state, 2, int(MessageType.ApplySweepBatch),
                       {"Batch": [{"Job": to_dict(job), "Sweep": sweep}]})
    assert calls == []  # columns digested directly, no per-row readback
    assert effect[0] == "sweep"
    d1, d2 = ReplicaDigest(), ReplicaDigest()
    d1.fold(2, 13, effect)
    d2.fold(2, 13, effect_of(fsm.state, 2, 13,
                             {"Batch": [{"Job": to_dict(job),
                                         "Sweep": dict(sweep)}]}))
    assert d1.stats()["Chain"] == d2.stats()["Chain"]


# --------------------------------------------- columns folded whole
# The encoding, kept here as ISSUE 35 found it: one visit per value. The
# chain a replica computes must stay, bit for bit, what this gives.
def _reference_fold(h, obj):
    if obj is None:
        h.update(b"N")
    elif obj is True:
        h.update(b"T")
    elif obj is False:
        h.update(b"F")
    elif isinstance(obj, int):
        h.update(b"I" + str(obj).encode())
    elif isinstance(obj, float):
        h.update(b"D" + struct.pack("<d", obj))
    elif isinstance(obj, str):
        b = obj.encode("utf-8")
        h.update(b"S" + str(len(b)).encode() + b":")
        h.update(b)
    elif isinstance(obj, bytes):
        h.update(b"B" + str(len(obj)).encode() + b":")
        h.update(obj)
    elif isinstance(obj, np.ndarray):
        h.update(b"A" + str(obj.dtype).encode() + b"|"
                 + str(obj.shape).encode() + b"|")
        h.update(np.ascontiguousarray(obj).tobytes())
    elif isinstance(obj, (list, tuple)):
        h.update(b"L" + str(len(obj)).encode() + b":")
        for item in obj:
            _reference_fold(h, item)
    elif isinstance(obj, dict):
        h.update(b"M" + str(len(obj)).encode() + b":")
        for key in sorted(obj):
            _reference_fold(h, key)
            _reference_fold(h, obj[key])
    else:
        h.update(b"O" + type(obj).__name__.encode())


def _reference_sweep_effects(state, payload):
    out = []
    for group in payload["Batch"]:
        sweep = group.get("Sweep")
        if sweep is None:
            for a in group.get("Alloc", ()):
                aid = a["ID"] if isinstance(a, dict) else a.ID
                alloc = state.alloc_by_id(aid)
                out.append((aid, None if alloc is None
                            else alloc.DesiredStatus))
            continue
        out.append((
            list(sweep["AllocIDs"]),
            list(sweep["RowNodeIDs"]),
            np.asarray(sweep["Counts"], dtype=np.int64),
            np.asarray(sweep["Rows"], dtype=np.int64),
            np.asarray(sweep["Delta"], dtype=np.float32),
            sweep.get("Kind", "system"),
        ))
    return out


def _reference_chain(chain_hex, index, msg_type, effect):
    h = hashlib.blake2b(digest_size=16)
    h.update(bytes.fromhex(chain_hex))
    _reference_fold(h, index)
    _reference_fold(h, int(msg_type))
    _reference_fold(h, effect)
    return h.hexdigest()


def _ids(rng, n):
    return [str(uuid.UUID(int=rng.getrandbits(128), version=4))
            for _ in range(n)]


def _sweep_group(rng, job, n_rows, per_row, kind, updates=()):
    """One columnar group as the applier encodes it (`SweepBatch.wire`:
    arrays and the emit's own lists), `per_row` allocations a node row."""
    n = n_rows * per_row
    template = mock.alloc()
    template.JobID, template.Job, template.EvalID = job.ID, None, _ids(rng, 1)[0]
    counts = np.full(n_rows, per_row, dtype=np.int64)
    batch = SweepBatch(
        rows=np.sort(rng.sample(range(4 * n_rows), n_rows)).astype(np.int64),
        node_ids=_ids(rng, n_rows),
        delta=np.full((n_rows, 4), 20.0 * per_row, dtype=np.float32),
        epoch=3, n_rows=4 * n_rows, counts=counts,
        starts=np.concatenate([[0], np.cumsum(counts)]),
        alloc_ids=_ids(rng, n),
        alloc_names=[f"{job.Name}.web[{i}]" for i in range(n)],
        alloc_tg=[0] * n, templates=[template], kind=kind)
    group = {"Job": job, "Sweep": batch.wire()}
    if updates:
        group["Updates"] = list(updates)
    return group


def _stopped(rng, job):
    alloc = mock.alloc()
    alloc.ID, alloc.JobID, alloc.Job = _ids(rng, 1)[0], job.ID, None
    alloc.DesiredStatus = "stop"
    return alloc


def _entries(shape):
    """(msg_type, payload) runs, one a shape ISSUE 35 names."""
    rng = random.Random(35)
    job = mock.job()
    sweep = MessageType.ApplySweepBatch
    if shape == "system-sweep-4000-rows":
        return [(sweep, {"Batch": [
            _sweep_group(rng, mock.system_job(), 4000, 1, "system")]})]
    if shape == "service-window-of-50":
        return [(sweep, {"Batch": [
            _sweep_group(rng, job, 25, 2, "service") for _ in range(16)]})
            for _ in range(2)]
    if shape == "service-window-of-1000":
        return [(sweep, {"Batch": [
            _sweep_group(rng, job, 250, 4, "service") for _ in range(4)]})]
    if shape == "updates-and-an-alloc-co-group":
        placed = _stopped(rng, job)
        placed.DesiredStatus = "run"
        return [(sweep, {"Batch": [
            _sweep_group(rng, job, 40, 1, "system",
                         updates=[_stopped(rng, job), _stopped(rng, job)]),
            {"Job": job, "Alloc": [placed, _stopped(rng, job)]},
            _sweep_group(rng, job, 10, 5, "service")]})]
    assert shape == "not-uniform-ascii"
    odd = _sweep_group(rng, job, 6, 1, "service")
    odd["Sweep"]["AllocIDs"][2] = "short-id"          # another length
    odd["Sweep"]["RowNodeIDs"][4] = "nøde-" + "0" * 31  # 36 chars, 37 bytes
    return [(sweep, {"Batch": [odd,
                               _sweep_group(rng, job, 6, 1, "service")]})]


SHAPES = ["system-sweep-4000-rows", "service-window-of-50",
          "service-window-of-1000", "updates-and-an-alloc-co-group",
          "not-uniform-ascii"]


def _decoded(msg_type, payload):
    """The entry as a follower's log holds it."""
    got_type, got = msgpack.unpackb(encode_command(msg_type, payload),
                                    raw=False)
    assert got_type == int(msg_type)
    return got


def _replica(interval=2):
    fsm = _replay(_node_payloads(3), interval=interval)
    return fsm, 3


@pytest.mark.parametrize("transport", ["devraft", "msgpack"])
@pytest.mark.parametrize("shape", SHAPES)
def test_chain_of_columnar_entries_is_the_reference_chain(shape, transport):
    """Bit for bit what the per-value fold gives, whether the columns
    arrive as DevRaft hands them on (arrays, the emit's lists) or as a
    follower decodes them (lists all)."""
    fsm, index = _replica()
    want = fsm.digest.stats()["Chain"]
    for msg_type, payload in _entries(shape):
        index += 1
        if transport == "msgpack":
            payload = _decoded(msg_type, payload)
            sweeps = [g["Sweep"] for g in payload["Batch"] if "Sweep" in g]
            assert all(type(s[k]) is list for s in sweeps
                       for k in ("Counts", "Rows", "Delta", "AllocIDs"))
        else:
            payload = copy.deepcopy(payload)
            sweeps = [g["Sweep"] for g in payload["Batch"] if "Sweep" in g]
            assert all(type(s[k]) is np.ndarray for s in sweeps
                       for k in ("Counts", "Rows", "Delta"))
        fsm.apply(index, msg_type, payload)
        want = _reference_chain(
            want, index, msg_type,
            ("sweep", _reference_sweep_effects(fsm.state, payload)))
        assert fsm.digest.stats()["Chain"] == want
    stats = fsm.digest.stats()
    assert stats["Synced"]
    columns = 2 * sum("Sweep" in g for _, p in _entries(shape)
                      for g in p["Batch"])
    if shape == "not-uniform-ascii":
        # The two odd columns take the per-value path, and are counted.
        assert stats["ColumnFolds"] == columns - 2
        assert stats["RowFolds"] == 12
    else:
        assert (stats["ColumnFolds"], stats["RowFolds"]) == (columns, 0)


@pytest.mark.parametrize("shape", SHAPES)
def test_follower_fed_the_encoded_entry_reaches_the_leaders_checkpoint(shape):
    leader, index = _replica()
    follower, _ = _replica()
    for msg_type, payload in _entries(shape) * 2:
        index += 1
        wire = _decoded(msg_type, payload)
        leader.apply(index, msg_type, copy.deepcopy(payload))
        follower.apply(index, msg_type, wire)
    at, chain = leader.digest.checkpoint()
    assert at > 3  # a checkpoint made of columnar entries
    assert follower.digest.verify(at, chain) is True
    assert follower.digest.stats()["Chain"] == leader.digest.stats()["Chain"]


def test_a_corrupted_follower_still_diverges_among_columnar_entries():
    """`fsm.digest.mutate` between columnar entries: the follower folds the
    corrupt readback, the whole-column fold hides nothing."""
    leader, index = _replica(interval=4)
    follower, _ = _replica(interval=4)
    ev = mock.eval()
    for msg_type, payload in _entries("service-window-of-50") + [
            (MessageType.EvalUpdate, {"Evals": [to_dict(ev)]})] \
            + _entries("system-sweep-4000-rows") * 3:
        index += 1
        if msg_type is MessageType.EvalUpdate:
            failpoints.arm("fsm.digest.mutate", "drop", count=1)
        leader.apply(index, msg_type, copy.deepcopy(payload))
        follower.apply(index, msg_type, _decoded(msg_type, payload))
    at, chain = leader.digest.checkpoint()
    with pytest.raises(ReplicaDivergenceError):
        follower.digest.verify(at, chain)


@pytest.mark.parametrize("values,whole", [
    ([], True),
    (_ids(random.Random(1), 64), True),
    (np.asarray(_ids(random.Random(2), 8), dtype=object), True),
    (tuple(_ids(random.Random(3), 5)), True),
    (["", "", ""], True),
    (["ab", "cd", "efg"], False),             # lengths differ
    (["abc", "aéc"], False),             # not ASCII: 3 chars, 4 bytes
    (["abc", None], False),
    (["abc", b"abc"], False),
    ([7, 8], False),
], ids=["empty", "uuids", "object-array", "tuple", "empty-strings",
        "odd-length", "non-ascii", "none", "bytes", "ints"])
def test_str_column_streams_what_the_list_folds_to(values, whole):
    column = StrColumn(values)
    assert (column.stream is not None) == whole
    mine, ref = (hashlib.blake2b(digest_size=16) for _ in range(2))
    tally = [0, 0]
    replica_digest._fold_obj(mine, ("x", column, 1), tally)
    as_list = values.tolist() if isinstance(values, np.ndarray) \
        else list(values)
    _reference_fold(ref, ("x", as_list, 1))
    assert mine.digest() == ref.digest()
    assert tally == ([1, 0] if whole else [0, len(values)])


@pytest.mark.parametrize("transport", ["devraft", "msgpack"])
def test_fold_calls_do_not_grow_with_the_rows_of_a_group(monkeypatch,
                                                         transport):
    """The mechanism, not a time: committing a columnar group of n rows
    visits the encoder a number of times that n does not move."""
    fold = replica_digest._fold_obj
    seen = [0]

    def counting(h, obj, tally=None):
        seen[0] += 1
        return fold(h, obj, tally)

    monkeypatch.setattr(replica_digest, "_fold_obj", counting)
    calls = []
    for n_rows in (10, 1000, 5000):
        fsm, index = _replica()
        rng = random.Random(n_rows)
        payload = {"Batch": [_sweep_group(rng, mock.job(), n_rows, 2,
                                          "service")]}
        if transport == "msgpack":
            payload = _decoded(MessageType.ApplySweepBatch, payload)
        before = seen[0]
        fsm.apply(index + 1, MessageType.ApplySweepBatch, payload)
        calls.append(seen[0] - before)
        stats = fsm.digest.stats()
        assert (stats["ColumnFolds"], stats["RowFolds"]) == (2, 0)
        assert len(fsm.state.allocs()) == 2 * n_rows
    assert calls[0] == calls[1] == calls[2] < 20


# ----------------------------------------------------------- fsm wiring
def test_snapshot_reseeds_the_chain_canonically():
    payloads = _node_payloads(50)
    a = _replay(payloads)
    snap = a.snapshot()
    b = FSM()
    b.digest = ReplicaDigest(interval=16)
    b.restore(snap)
    assert b.digest.stats()["Chain"] == a.digest.stats()["Chain"]
    # Folding the same next entry keeps the chains equal: canonical.
    extra = _node_payloads(1, prefix="x")[0]
    a.apply(51, MessageType.NodeRegister, copy.deepcopy(extra))
    b.apply(51, MessageType.NodeRegister, copy.deepcopy(extra))
    assert b.digest.stats()["Chain"] == a.digest.stats()["Chain"]


def test_snapshot_without_digest_enters_unverified_mode():
    a = _replay(_node_payloads(10))
    snap = a.snapshot()
    snap.pop("digest")
    b = FSM()
    b.digest = ReplicaDigest(interval=4)
    b.restore(snap)
    st = b.digest.stats()
    assert not st["Synced"] and "without" in st["UnsyncedReason"]
    assert b.digest.verify(8, "00" * 16) is None


def test_fold_failure_is_contained_and_marks_unsynced():
    failpoints.arm("fsm.digest.mutate", "error", count=1)
    fsm = _replay(_node_payloads(3))
    # All three entries applied despite the injected fold failure...
    assert len(fsm.state.nodes()) == 3
    st = fsm.digest.stats()
    assert not st["Synced"] and st["Folds"] == 2


def test_divergence_detected_within_one_interval():
    """Corruption at index i must surface at the FIRST checkpoint at or
    after i — within `interval` applies, the ISSUE's K bound."""
    interval = 8
    payloads = _node_payloads(32)
    leader = _replay(payloads, interval=interval)
    leader_cps = leader.digest.stats()["Checkpoints"]
    follower = FSM()
    follower.digest = ReplicaDigest(interval=interval)
    corrupt_at = 12
    detected = None
    for i, p in enumerate(payloads, start=1):
        if i == corrupt_at:
            # The armed seam corrupts THIS entry's just-written row
            # before the effect readback (a bare FSM has no leader-side
            # observers, so the non-leader gate passes).
            failpoints.arm("fsm.digest.mutate", "drop", count=1)
        follower.apply(i, MessageType.NodeRegister, copy.deepcopy(p))
        if i in leader_cps:
            try:
                follower.digest.verify(i, leader_cps[i])
                assert i < corrupt_at, \
                    "checkpoint after the corruption verified clean"
            except ReplicaDivergenceError:
                detected = i
                break
    assert detected is not None
    assert detected - corrupt_at <= interval


# ----------------------------------------------------- replicated cluster
def test_cluster_detects_and_recovers_from_follower_corruption():
    """3-node replicated cluster: corrupt one follower's store via the
    armed seam; the digest exchange must detect it (diverged metric),
    quarantine the follower, and reconverge every replica onto the
    leader's verified state."""
    from nomad_tpu.raft import RaftConfig
    from nomad_tpu.raft.node import NotLeaderError
    from nomad_tpu.rpc.cluster import ClusterServer
    from nomad_tpu.server.server import ServerConfig

    from helpers import wait_for

    fast = RaftConfig(heartbeat_interval=0.02, election_timeout_min=0.08,
                      election_timeout_max=0.16, apply_timeout=5.0,
                      snapshot_threshold=30, trailing_logs=32)
    nodes = []
    try:
        for i in range(3):
            cs = ClusterServer(ServerConfig(
                node_id="", num_schedulers=0, digest_interval=16))
            nodes.append(cs)
        addrs = [cs.addr for cs in nodes]
        for cs in nodes:
            cs.connect(addrs, raft_config=fast)
            cs.start()
        assert wait_for(
            lambda: any(cs.server.is_leader() for cs in nodes), timeout=30)
        leader = next(cs for cs in nodes if cs.server.is_leader())

        def apply_nodes(n, prefix):
            # On whoever leads now: with election timeouts this short a
            # loaded machine can move the leadership between two applies.
            nonlocal leader
            for i in range(n):
                node = mock.node()
                node.ID = f"{prefix}{i}"
                for _ in range(50):
                    try:
                        leader.server.raft.apply(MessageType.NodeRegister,
                                                 {"Node": node})
                        break
                    except NotLeaderError:
                        assert wait_for(lambda: any(
                            cs.server.is_leader() for cs in nodes), timeout=30)
                        leader = next(cs for cs in nodes
                                      if cs.server.is_leader())
                else:
                    raise AssertionError("no stable leader")

        def diverged_total():
            return sum(cs.server.fsm.digest.stats()["Diverged"]
                       for cs in nodes)

        apply_nodes(40, "warm")
        assert diverged_total() == 0  # zero false positives warm
        # One corruption on whichever follower applies next.
        failpoints.arm("fsm.digest.mutate", "drop", count=1)
        apply_nodes(40, "storm")
        assert wait_for(lambda: diverged_total() >= 1,
                        timeout=30, msg="divergence never detected")
        failpoints.disarm_all()
        apply_nodes(10, "heal")

        def converged():
            want = {n.ID for n in leader.server.state.nodes()}
            return all(
                {n.ID for n in cs.server.state.nodes()} == want
                for cs in nodes)

        assert wait_for(converged, timeout=60, interval=0.25,
                        msg="replicas reconverged after quarantine")
        # The corruption marker must not survive anywhere.
        for cs in nodes:
            assert all(n.Status != "chaos-diverged"
                       for n in cs.server.state.nodes())
    finally:
        for cs in nodes:
            try:
                cs.shutdown()
            except Exception:
                pass
