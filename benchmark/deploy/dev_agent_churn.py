"""Deployment "dev_agent_churn": dev_agent's one dev-mode Agent on
svc-10k's fleet, already running a standing set of services, where every
`nomad run` of a new service retires the oldest: it is paired with the
`nomad stop` of the oldest live job.

dev_agent.Deployment is reused by import: fleet, heartbeats, the
window_buckets warm-up, the reads. What differs:

- Evals in flight, registrations and stops together, are held to the
  file's `standing_jobs.outstanding` (traffic/churn.json's `outstanding`,
  a test holds them equal): before a registration or a stop is sent, the
  deployment waits until one of those it sent has ended.
- start() places the standing set after the warm-up: `count` jobs of its
  template through the served path under that bound, every one waited to
  `complete` (set-up: `phases["standing_s"]`). A fleet that is not the
  file's stands the file's count scaled to it, but never fewer than the
  bound on evals in flight (256 at a rehearsal's 400 nodes, where the
  scaled count would be 80): a live set shorter than what is in flight
  would put the next stop's head among the evals still pending, and the
  pairs would wait for it (below). The warm-up's jobs stay, as in
  svc-10k.storm.
- Once start() has returned, make_job(template) is the client's side of
  the pair: it stops the oldest live job, then waits for room for the new
  registration, then builds the job. register(job) stays the registration
  alone, so the benchmark's span round it times Server.job_register only.
  The live set is a FIFO of the standing set, oldest first, and then every
  job registered, so it keeps its size and every job, the standing ones
  included, is stopped as many registrations after it started as the
  standing set holds. Only a job seen `complete` is stopped: a pair whose
  head is still pending waits for it (up to ROOM_TIMEOUT_S, its stop owed
  meanwhile) and sends its registration after the stop, so the live set
  never outgrows the standing set behind an eval that a worker holds (one
  can hold the oldest eval unacked while the other worker's windows go
  by); a head that ended otherwise is a failed operation, which stays
  acknowledged and is left running. A generator's closed loop
  (`closed_loop`, traffic/churn.json) therefore drives both halves.
- deregister(job_id) calls Server.job_deregister, the endpoint behind
  `nomad stop` (DELETE /v1/job/<id>). Once the server acknowledged it, the
  job leaves `acknowledged`, so checks 4 and 5 ask nothing of it, and joins
  `stopped` as (job id, register eval, deregister eval), which check 11
  (reference/churn.py) holds to the stop; check 3 still catches any
  allocation it left live.
"""

from __future__ import annotations

import time
from collections import deque

from benchmark.deploy import dev_agent
from benchmark.ops import TERMINAL

POLL_S = 0.02          # the cell's poll period (traffic/churn.json poll_ms)
ROOM_TIMEOUT_S = 120.0  # no eval in flight ended for this long: give up
STANDING_TIMEOUT_S = 600.0


def standing_count(config, n_nodes):
    """The standing set on a fleet of n_nodes: the file's count, scaled to
    the fleet where it is not the file's, and at least the bound on evals
    in flight."""
    spec = config["standing_jobs"]
    return max(spec["count"] * n_nodes // config["fleet"]["nodes"],
               spec["outstanding"])


class Acknowledged:
    """dev_agent's `acknowledged` list with removal by job id: (job_id,
    eval_id, template) of every job registered and not stopped, in the
    order registered."""

    def __init__(self):
        self._by_job = {}

    def append(self, entry):
        self._by_job[entry[0]] = entry

    def pop(self, job_id):
        return self._by_job.pop(job_id)

    def __iter__(self):
        return iter(list(self._by_job.values()))

    def __len__(self):
        return len(self._by_job)


class Deployment(dev_agent.Deployment):
    """dev_agent's running system with a standing set, every registration
    paired with a stop."""

    def __init__(self, config, seed_rng, nodes=None):
        super().__init__(config, seed_rng, nodes=nodes)
        self.acknowledged = Acknowledged()
        self.stopped = []   # (job_id, register eval, deregister eval)
        self.standing = []  # (job_id, register eval) of the standing set
        self.live = None    # the FIFO, once start() has returned
        self.owed = 0       # stops owed to registrations already sent
        self.unstopped = []  # heads that ended short of complete
        self.head_waits = 0  # pairs that waited for a pending head
        self.in_flight = {}  # eval id -> "run" | "stop", not seen ended
        self.flight_reads = []  # (runs, stops) in flight at each full read

    def start(self):
        super().start()
        t0 = time.perf_counter()
        self._place_standing()
        self.phases["standing_s"] = time.perf_counter() - t0
        self.live = deque(self.standing)
        return self

    def _place_standing(self):
        spec = self.config["standing_jobs"]
        for _ in range(standing_count(self.config, self.n_nodes)):
            self._wait_for_room()
            job = self.make_job(spec["template"])
            self.standing.append((job.ID, self.register(job)))
        eval_ids = [eval_id for _, eval_id in self.standing]
        self._wait(eval_ids, STANDING_TIMEOUT_S, "standing set")
        short = [e for e in eval_ids if self.eval_status(e) != "complete"]
        if short:
            raise RuntimeError(f"standing set: {len(short)} of "
                               f"{len(eval_ids)} evals did not complete, "
                               f"e.g. {short[:3]}")
        self.in_flight.clear()
        self.flight_reads.clear()

    def _wait_for_room(self):
        """Until fewer evals than the bound are in flight, registrations and
        stops together: read those sent every POLL_S once the bound is
        reached."""
        bound = self.config["standing_jobs"]["outstanding"]
        deadline = time.monotonic() + ROOM_TIMEOUT_S
        while len(self.in_flight) >= bound:
            if time.monotonic() > deadline:
                raise RuntimeError(f"{len(self.in_flight)} evals in flight "
                                   f"and none ended in {ROOM_TIMEOUT_S} s")
            time.sleep(POLL_S)
            self.in_flight = {e: kind for e, kind in self.in_flight.items()
                              if self.eval_status(e) not in TERMINAL}
            stops = sum(1 for kind in self.in_flight.values()
                        if kind == "stop")
            self.flight_reads.append((len(self.in_flight) - stops, stops))

    # ------------------------------------------------- what generators use
    def make_job(self, template):
        """Once started, the client's side of the pair before the new job is
        built: the stop of the oldest live job, then room for the job."""
        if self.live is not None:
            self.owed += 1
            self._stop_owed()
            self._wait_for_room()
        return super().make_job(template)

    def _stop_owed(self):
        deadline = None
        while self.owed and self.live:
            job_id, eval_id = self.live[0]
            status = self.eval_status(eval_id)
            if status not in TERMINAL:
                if deadline is None:
                    deadline = time.monotonic() + ROOM_TIMEOUT_S
                    self.head_waits += 1
                if time.monotonic() > deadline:
                    return  # still owed: a later pair stops it
                time.sleep(POLL_S)
                continue
            self.live.popleft()
            if status == "complete":
                self._wait_for_room()
                self.deregister(job_id)
                self.owed -= 1
            else:
                self.unstopped.append(job_id)

    def register(self, job):
        """Server.job_register; the job joins the live set once started."""
        eval_id = super().register(job)
        self.in_flight[eval_id] = "run"
        if self.live is not None:
            self.live.append((job.ID, eval_id))
        return eval_id

    def deregister(self, job_id):
        """Server.job_deregister: the endpoint behind `nomad stop`."""
        eval_id = self.server.job_deregister(job_id)[0]
        self.in_flight[eval_id] = "stop"
        _, register_eval, _ = self.acknowledged.pop(job_id)
        self.stopped.append((job_id, register_eval, eval_id))
        return eval_id

    def facts(self):
        reads = self.flight_reads
        flight = None
        if reads:
            flight = {"reads": len(reads),
                      "runs_mean": sum(r for r, _ in reads) / len(reads),
                      "stops_mean": sum(s for _, s in reads) / len(reads),
                      "stops_max": max(s for _, s in reads)}
        return {**super().facts(), "standing_jobs": len(self.standing),
                "stopped_jobs": len(self.stopped), "stops_owed": self.owed,
                "head_waits": self.head_waits,
                "unstopped": self.unstopped[:5], "in_flight": flight}
