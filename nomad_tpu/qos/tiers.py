"""QoS tier model: priority lanes, deadline budgets, and shared counters.

The served path (broker -> worker windows -> plan queue) orders work by raw
``Priority`` but treats every eval as latency-equivalent. For multi-tenant
serving the product is BOUNDED TAILS, not just throughput: a Priority=100
eval must not wait out a 10k-eval Priority=1 storm. This module defines the
tier mapping the whole QoS subsystem shares:

  high   (Priority >= high_floor)  interactive / SLO-bearing traffic
  normal (in between)              default batch of work
  low    (Priority <= low_ceiling) best-effort / backfill

Three mechanisms hang off it (see README "QoS & SLO serving"):

- **Tiered lanes** in the EvalBroker: high drains first; lower tiers age
  one tier per ``aging_s`` seconds queued, so a saturating high-tier storm
  can delay but never permanently starve them.
- **Deadline-aware windows** in the PipelinedWorker: each window inherits a
  latency budget from its oldest eval's tier deadline and cuts the batch
  fill short rather than blowing it (``window_fill``).
- **Admission control + preemption** (qos/admission.py, qos/preemption.py)
  read the same tier mapping so "low tier" means one thing everywhere.

``enabled=False`` (the default) must leave the served path bit-identical
to the pre-QoS FIFO behavior — every consumer guards on it before touching
tier logic, and the equivalence test in tests/test_qos.py holds the line.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from nomad_tpu.analysis import guarded_by

TIER_HIGH = 0
TIER_NORMAL = 1
TIER_LOW = 2
N_TIERS = 3
TIER_NAMES = ("high", "normal", "low")


@dataclass
class QoSConfig:
    """Knobs for the QoS subsystem. One instance is shared (read-only
    after boot) by the broker, workers, admission controller, scheduler
    preemption, and the sched-stats surface."""

    enabled: bool = False
    # Priority -> tier mapping. JobMaxPriority is 100, default 50.
    high_floor: int = 70
    low_ceiling: int = 30
    # Anti-starvation: a queued eval's EFFECTIVE tier rises one level per
    # aging_s seconds waited, so saturating high-tier load can delay lower
    # tiers but never park them forever. 0 disables aging.
    aging_s: float = 2.0
    # Per-tier end-to-end latency budget (seconds), high -> low. Drives
    # deadline-aware window sizing and the SLO-burn counters.
    deadlines_s: Tuple[float, float, float] = (0.25, 1.0, 5.0)
    # Admission control: shed a tier's submissions once its ready backlog
    # reaches this depth (0 = unlimited). High tier is deliberately
    # unlimited by default — admission exists to protect it.
    admit_depth: Tuple[int, int, int] = (0, 8192, 2048)
    # Shed submissions BELOW a tier once that tier's rolling deadline-miss
    # fraction exceeds this (the SLO-burn signal).
    burn_shed: float = 0.5
    # Rolling window (completions) the per-tier burn fraction is computed
    # over.
    burn_window: int = 128
    # Alloc preemption for high-tier placements that find no feasible
    # capacity (qos/preemption.py).
    preemption: bool = True
    # Most allocs one placement may evict; bounds the blast radius of a
    # single high-tier instance.
    max_victims: int = 8

    def tier_of(self, priority: int) -> int:
        if priority >= self.high_floor:
            return TIER_HIGH
        if priority <= self.low_ceiling:
            return TIER_LOW
        return TIER_NORMAL

    def deadline_s(self, priority: int) -> float:
        return self.deadlines_s[self.tier_of(priority)]

    def window_fill(self, age_s: float, priority: int, max_fill: int,
                    default_fill: float) -> Tuple[int, float]:
        """Deadline-aware window sizing: scale how many more evals a
        window may take and how long it may linger for stragglers by the
        oldest queued eval's REMAINING tier budget. Returns
        ``(fill_count, fill_timeout_s)``.

        A window's oldest eval has already waited ``age_s``; every extra
        eval batched behind it adds dispatch+drain serialization before
        its ack. With the budget nearly spent the window dispatches small
        and immediately — trading batch efficiency for the tier's
        deadline, which is exactly the trade QoS exists to make."""
        deadline = self.deadlines_s[self.tier_of(priority)]
        remaining = deadline - age_s
        if remaining <= 0:
            # Budget blown: dispatch the smallest useful window, now.
            return max(1, max_fill // 8), 0.0
        frac = min(1.0, remaining / deadline)
        # ceil, not floor: a freshly-dequeued eval (age ~ms) must keep the
        # FULL window — flooring would report a 1-eval "cut" on every
        # healthy window and poison the window_cuts signal.
        count = max(1, math.ceil(max_fill * frac))
        return count, min(default_fill, remaining / 4.0)


class QoSCounters:
    """Cross-thread QoS flow counters (admission verdicts, preemption
    outcomes, window cuts), shared by the server's admission controller,
    the scheduler's preemption path, and the workers; read by the
    sched-stats endpoint and the QoS tests."""

    _concurrency = guarded_by("_lock", "_counts")

    FIELDS = ("admitted", "shed", "delayed",
              "preempt_attempts", "preempt_placed", "preempt_evictions",
              "window_cuts", "forward_shed")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {k: 0 for k in self.FIELDS}

    def incr(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)


def qos_enabled(qos: Optional[QoSConfig]) -> bool:
    """The one guard every hot-path consumer uses: QoS logic only runs
    behind an explicit opt-in, so the disabled path stays bit-identical
    to the pre-QoS behavior."""
    return qos is not None and qos.enabled
