"""Federation knobs: one config shared by the snapshot source, the region
forwarder, the broker's region routing, and the admission controller's
global view (README "Federation").

``enabled=False`` (and ``ServerConfig.federation=None``, the default) must
leave the served path bit-identical to the pre-federation behavior — every
consumer guards on :func:`federation_enabled` before touching federation
logic, the same discipline as QoS and the columnar service commits
(tests/test_federation_equivalence.py holds the line).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass
class FederationConfig:
    """Read-only after boot; shared by broker, workers, applier,
    endpoints, and the admission controller."""

    enabled: bool = False
    # Follower-snapshot scheduling (snapshots.py): False keeps region
    # routing/forwarding/QoS-view on but has every worker pin a fresh
    # live-store watermark per window — the all-on-leader baseline
    # (the snapshot source is the ONLY delta between the two values;
    # nothing measures it today: ROADMAP Named debts, D6).
    follower_snapshots: bool = True
    # Staleness bound (seconds) on the shared scheduling snapshot:
    # enforced at DEQUEUE — a worker asking for a snapshot older than
    # this gets a fresh one; younger snapshots are shared across windows
    # and workers instead of each window pinning its own watermark on
    # the live store. Observed per plan as nomad.federation.staleness_ms.
    max_staleness_s: float = 0.25
    # Applier-side hard bound (seconds): a plan built against a snapshot
    # older than this at VERIFY time is rejected outright
    # (StaleSnapshotError) and its eval redelivered through the normal
    # nack machinery — the Omega backstop for a worker that sat on a
    # pinned/wedged snapshot far past the dequeue bound. Must be several
    # multiples of max_staleness_s (a healthy window legitimately ages
    # its snapshot by the dispatch+drain+build pipeline depth); 0
    # disables the applier check.
    reject_after_s: float = 2.0
    # Cross-region forwarding resilience (rpc/endpoints.py via
    # federation/routing.py): attempts across region peers, and the
    # per-peer circuit breaker that quarantines a dead region server so
    # it costs one connect timeout per reset window, not one per call.
    forward_attempts: int = 3
    forward_breaker_threshold: int = 3
    forward_breaker_reset_s: float = 5.0
    # Shed a cross-region forward at the LOCAL edge when the target
    # region's cached health view shows the submission's tier already
    # being shed there (saves the WAN hop; the submitter gets the same
    # typed 429-retryable backpressure the home region would return).
    remote_shed: bool = True
    # Leader-loop poll period for the per-region health view
    # (Federation.Health RPC over the gossip region table).
    health_interval_s: float = 1.0
    # Cached health entries older than this are ignored (a partitioned
    # region must not be shed forever on a stale verdict).
    health_ttl_s: float = 10.0


def federation_enabled(fed: Optional[FederationConfig]) -> bool:
    """The one guard every consumer uses: federation logic only runs
    behind an explicit opt-in, so the disabled path stays bit-identical
    to the pre-federation behavior."""
    return fed is not None and fed.enabled
