"""RaftBackend: adapts a RaftNode to the `raft.apply(msg_type, payload)`
seam the Server writes through (reference: Server.raftApply nomad/rpc.go:262
— msgpack-encode a typed message, feed it through raft, return the index).

Drop-in replacement for fsm.DevRaft: same apply()/last_index surface, plus
leadership notification and barrier/snapshot passthrough.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import msgpack

from nomad_tpu.structs import to_dict

from .log import EntryType, InMemLogStore
from .node import NotLeaderError, RaftConfig, RaftNode


def _pack_default(obj: Any) -> Any:
    tolist = getattr(obj, "tolist", None)  # ndarray, numpy scalar
    if tolist is None:
        raise TypeError(f"cannot serialize {type(obj).__name__}")
    return tolist()


def encode_command(msg_type, payload: Dict[str, Any]) -> bytes:
    """One typed message as the log entry's bytes. This is the boundary at
    which a columnar entry's ndarrays become plain lists (the form every
    follower decodes) and its structs plain dicts, and nowhere earlier."""
    return msgpack.packb((int(msg_type), to_dict(payload)),
                         use_bin_type=True, default=_pack_default)


class RaftBackend:
    """Owns a RaftNode wired to an FSM. The Server calls apply(); followers
    receive the same entries through replication and apply them to their own
    FSM/state store replica."""

    def __init__(self, node_id: str, fsm, peers: List[str],
                 transport, log_store=None,
                 config: Optional[RaftConfig] = None,
                 on_leader_change: Optional[Callable[[bool], None]] = None,
                 electable: bool = True):
        self.fsm = fsm
        self.node = RaftNode(
            node_id=node_id,
            peers=peers,
            log_store=log_store or InMemLogStore(),
            transport=transport,
            apply_fn=self._fsm_apply,
            snapshot_fn=self._fsm_snapshot,
            restore_fn=self._fsm_restore,
            # Streaming snapshots: chunked persist off the apply path,
            # chunked InstallSnapshot, chunk-by-chunk restore with one
            # atomic cutover (README "Failover & streaming snapshots").
            snapshot_stream_fn=self._fsm_snapshot_stream,
            restore_stream_fn=self._fsm_restore_stream,
            # Replica-digest exchange: checkpoint piggyback on
            # AppendEntries, follower verification, and the divergence
            # quarantine's FSM wipe. All no-ops while fsm.digest is None.
            digest_checkpoint_fn=self._digest_checkpoint,
            digest_verify_fn=self._digest_verify,
            digest_quarantine_fn=self._digest_quarantine,
            config=config,
            on_leader_change=on_leader_change,
            electable=electable,
        )

    def start(self) -> None:
        self.node.start()

    def shutdown(self) -> None:
        self.node.shutdown()

    # ------------------------------------------------------------- fsm glue
    def _fsm_apply(self, index: int, etype: int, data: bytes) -> Any:
        """(reference: nomadFSM.Apply dispatch by MessageType, fsm.go:99-144)"""
        from nomad_tpu.server.fsm import MessageType  # avoid import cycle
        msg_type, payload = msgpack.unpackb(data, raw=False)
        return self.fsm.apply(index, MessageType(msg_type), payload)

    def _fsm_snapshot(self) -> bytes:
        return msgpack.packb(self.fsm.snapshot(), use_bin_type=True)

    def _fsm_restore(self, blob: bytes) -> None:
        self.fsm.restore(msgpack.unpackb(blob, raw=False))

    def _fsm_snapshot_stream(self):
        """Chunk-dict generator, MVCC-pinned eagerly (the raft layer calls
        this under its FSM lock so the pin matches the captured index)."""
        return self.fsm.snapshot_chunks()

    def _fsm_restore_stream(self, raw_chunks) -> None:
        """raw_chunks: iterable of msgpack chunk blobs. Decoding stays
        lazy so the atomic-cutover guarantee covers decode faults too."""
        self.fsm.restore_chunks(
            msgpack.unpackb(c, raw=False) for c in raw_chunks)

    # ---------------------------------------------------------- digest glue
    def _digest_checkpoint(self):
        digest = getattr(self.fsm, "digest", None)
        return None if digest is None else digest.checkpoint()

    def _digest_verify(self, index: int, expected_hex: str) -> bool:
        digest = getattr(self.fsm, "digest", None)
        if digest is None:
            return True
        from nomad_tpu.analysis.replica_digest import ReplicaDivergenceError
        try:
            digest.verify(index, expected_hex)
            return True
        except ReplicaDivergenceError:
            return False

    def _digest_quarantine(self) -> None:
        """Divergence recovery: atomic cutover to an EMPTY store (the
        corrupt state must not survive in any read surface) and a digest
        chain back at genesis — the leader's catch-up re-derives both."""
        self.fsm.restore({})
        digest = getattr(self.fsm, "digest", None)
        if digest is not None:
            digest.reset()

    # ----------------------------------------------------------- apply seam
    def apply(self, msg_type, payload: Dict[str, Any]) -> int:
        """Replicate + apply one mutation; returns its raft index. Raises
        NotLeaderError on non-leaders so RPC endpoints can forward
        (reference: rpc.go:177-242 forward + structs.ErrNoLeader)."""
        index, result = self.node.apply_command(
            encode_command(msg_type, payload))
        if isinstance(result, Exception):
            raise result
        return index

    @property
    def last_index(self) -> int:
        return self.node.last_index

    # ------------------------------------------------------------- exposure
    def is_leader(self) -> bool:
        return self.node.is_leader()

    @property
    def leader_id(self) -> Optional[str]:
        return self.node.leader_id

    def barrier(self, timeout: Optional[float] = None) -> int:
        return self.node.barrier(timeout)

    # ----------------------------------------------------- membership seam
    # (driven by the gossip plane, server/membership.py — the reference
    # equivalents are raft.AddPeer/RemovePeer/SetPeers from nomad/leader.go
    # reconcileMember and nomad/serf.go maybeBootstrap)
    def add_peer(self, peer_id: str, timeout: Optional[float] = None) -> None:
        self.node.add_peer(peer_id, timeout)

    def remove_peer(self, peer_id: str,
                    timeout: Optional[float] = None) -> None:
        self.node.remove_peer(peer_id, timeout)

    def bootstrap_cluster(self, peers: List[str]) -> bool:
        return self.node.bootstrap_cluster(peers)

    @property
    def peers(self) -> List[str]:
        return self.node.peers()

    def stats(self) -> Dict[str, Any]:
        return self.node.stats()
