"""Federated QoS: the global admission/SLO-burn view (ISSUE 14 layer 3).

Each region runs its own tier queues (its EvalBroker) and its own
admission controller — that isolation IS the headline property: a storm
saturating region A's low tier burns and sheds in region A's broker,
while region B's high tier keeps draining its own queues untouched.

What federation adds on top is a VIEW: every server answers
``Federation.Health`` with its region's per-tier depths, SLO burn, and
whether admission is currently shedding; the leader polls its gossip
region table on a short interval and caches the answers here. Two
consumers:

- **Remote-shed at the forwarding edge** (qos/admission.py
  ``admit_forward``): a cross-region submission whose HOME region is
  already shedding its tier is shed locally with the same typed
  QoSBackpressureError — the client gets its 429-and-retry without the
  WAN hop, and the storm region's ingress never sees the doomed forward.
- **Operator surface**: the sched-stats endpoint reports the whole
  federation's tier health next to the local broker's.

Entries expire after ``health_ttl_s`` — a partitioned region must not be
shed forever on a stale verdict; an expired entry means "assume healthy,
forward, let the home region decide".
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from nomad_tpu.analysis import guarded_by
from nomad_tpu.qos.tiers import N_TIERS

from .config import FederationConfig


class FederationHealth:
    """Cached per-region QoS health, fed by the leader's poll loop (and
    directly by tests that skip gossip)."""

    _concurrency = guarded_by("_lock", "_regions")

    def __init__(self, fed: Optional[FederationConfig] = None,
                 clock=time.monotonic):
        self.fed = fed or FederationConfig()
        self.clock = clock
        self._lock = threading.Lock()
        # region -> (payload dict, stamped monotonic time)
        self._regions: Dict[str, tuple] = {}

    def update(self, region: str, payload: Dict) -> None:
        with self._lock:
            self._regions[region] = (dict(payload), self.clock())

    def get(self, region: str) -> Optional[Dict]:
        """The region's last health payload, or None when unknown or
        older than the TTL (stale = assume healthy)."""
        with self._lock:
            entry = self._regions.get(region)
            if entry is None:
                return None
            payload, stamped = entry
            if self.clock() - stamped > self.fed.health_ttl_s:
                return None
            return dict(payload)

    def snapshot(self) -> Dict[str, Dict]:
        """All live entries plus their age — the sched-stats view."""
        with self._lock:
            now = self.clock()
            return {
                region: {**payload,
                         "AgeS": round(now - stamped, 2),
                         "Stale": now - stamped > self.fed.health_ttl_s}
                for region, (payload, stamped) in self._regions.items()
            }

    def region_shedding(self, region: str, tier: int) -> Optional[str]:
        """Reason string when the region's cached health says a
        submission of ``tier`` would be shed there, else None. Mirrors
        AdmissionController.admit's two rules (depth + higher-tier burn)
        against the REMOTE numbers, so edge and home agree."""
        h = self.get(region)
        if h is None:
            return None
        depths = h.get("TierDepths") or [0] * N_TIERS
        limits = h.get("AdmitDepth") or [0] * N_TIERS
        if tier < len(limits) and limits[tier] \
                and depths[tier] >= limits[tier]:
            return (f"region {region} tier backlog "
                    f"{depths[tier]} >= {limits[tier]}")
        burn = h.get("SLOBurn") or [0.0] * N_TIERS
        burn_shed = h.get("BurnShed", 1.1)
        for higher in range(min(tier, len(burn))):
            if burn[higher] > burn_shed and depths[higher]:
                return (f"region {region} {higher}-tier burning SLO "
                        f"({burn[higher]:.0%})")
        return None


def health_payload(server) -> Dict:
    """One server's Federation.Health answer: its region's tier state in
    the shape region_shedding() consumes. Cheap — broker introspection
    plus two config tuples — and safe on a follower (the broker is just
    empty there; callers poll whichever region peer answers)."""
    broker = server.eval_broker
    qos = server.qos
    payload = {
        "Region": server.config.region,
        "TierDepths": broker.tier_depths(),
        "SLOBurn": [round(b, 4) for b in broker.slo_burn()],
        "QoSEnabled": bool(qos is not None and qos.enabled),
        "Nodes": len(server.tindex.nt.row_of),
    }
    if qos is not None and qos.enabled:
        payload["AdmitDepth"] = list(qos.admit_depth)
        payload["BurnShed"] = qos.burn_shed
    return payload
