"""Deployment "dev_agent": one dev-mode nomad_tpu Agent with the settings
users get by default, a fleet of mock-shaped nodes built from the seed and
kept alive by paced heartbeats.

The configuration file is the source of every size here: the fleet (node
template, racks, ineligible shares), the job templates, the server settings
and the warm-up. The server settings are *checked*, not pushed: the agent
starts with its own defaults, and a file that states another value than the
one the server runs with stops the run, so the file always says what ran.

Copied from chip_smoke.py (PR 21), which stays the smoke: build_fleet,
warm_buckets. Changed: node and job shapes come from the file, not from
bench.py / nomad_tpu.mock, and heartbeats are paced as clients pace theirs
(chip_smoke.Heartbeater renews all nodes in one burst).
"""

from __future__ import annotations

import tempfile
import threading
import time
import uuid

from benchmark.ops import TERMINAL

WORKER_PARK_S = 0.6  # longer than a parked worker's blocking dequeue


def seeded_uuid(rng):
    return str(uuid.UUID(int=rng.getrandbits(128), version=4))


def build_fleet(fleet, n, rng):
    """n nodes of the file's template in fleet["racks"] racks (one computed
    class each); the first rack_variants racks of a seeded shuffle are made
    ineligible as the file says, and never_ready_per_1000 nodes register in
    status "initializing" and stay there."""
    from nomad_tpu.structs import Node, from_dict

    n_racks = fleet["racks"]
    racks = list(range(n_racks))
    rng.shuffle(racks)
    variant_of = {}
    for variant in fleet["rack_variants"]:
        for _ in range(variant["racks"]):
            variant_of[racks.pop()] = variant
    never_ready = set(rng.sample(
        range(n), max(1, n * fleet["never_ready_per_1000"] // 1000)))
    nodes = []
    for i in range(n):
        node = from_dict(Node, fleet["node"])
        node.ID = seeded_uuid(rng)
        node.Name = f"node-{i}"
        node.Meta[fleet["rack_meta_key"]] = f"r{i % n_racks}"
        variant = variant_of.get(i % n_racks)
        if variant is not None:
            node.Attributes.update(variant.get("set_attributes", {}))
            for key in variant.get("delete_attributes", ()):
                del node.Attributes[key]
        if i in never_ready:
            node.Status = "initializing"
        nodes.append(node)
    return nodes


class Heartbeats(threading.Thread):
    """Keeps every registered node alive as ten thousand clients would: one
    node_heartbeat at a time on an even schedule, a whole cycle over the
    fleet lasting half of the shortest TTL the previous cycle was granted.
    Never a burst; a beat that is late is sent at once and the schedule
    keeps its phase."""

    def __init__(self, server, node_ids, first_ttls):
        super().__init__(daemon=True, name="bench-heartbeats")
        self.server = server
        self.ids = list(node_ids)
        self.cycle_s = min(first_ttls) / 2
        self.stop = threading.Event()
        self.sent = 0
        self.errors = []

    def run(self):
        due = time.monotonic()
        while not self.stop.is_set():
            ttls = []
            gap = self.cycle_s / len(self.ids)
            for nid in self.ids:
                due += gap
                wait = due - time.monotonic()
                if wait > 0 and self.stop.wait(wait):
                    return
                try:
                    ttls.append(self.server.node_heartbeat(nid))
                    self.sent += 1
                except KeyError as exc:  # node marked down: TTL was missed
                    self.errors.append(str(exc))
            if ttls:
                self.cycle_s = min(ttls) / 2


class Deployment:
    """The running system and the few calls generators and checks need."""

    def __init__(self, config, seed_rng, nodes=None):
        self.config = config
        self.rng = seed_rng
        self.n_nodes = nodes or config["fleet"]["nodes"]
        self.acknowledged = []  # (job_id, eval_id, template) of every job
        self.asked = 0          # allocations those jobs' groups ask for
        self.phases = {}
        self._job_numbers = 0
        self._templates = {}
        self._tmp = tempfile.TemporaryDirectory(prefix="bench_agent_")
        self.agent = self.server = self.heartbeats = None

    # ------------------------------------------------------------ set-up
    def start(self):
        from nomad_tpu.agent import Agent
        from nomad_tpu.agent.agent import AgentConfig

        t0 = time.perf_counter()
        self.agent = Agent(AgentConfig(server_enabled=True, dev_mode=True,
                                       http_port=0,
                                       data_dir=self._tmp.name))
        self.agent.start()
        self.server = self.agent.server
        self._check_settings()
        self.phases["agent_start_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        fleet = build_fleet(self.config["fleet"], self.n_nodes, self.rng)
        for node in fleet:
            self.server.node_register(node)
        self.phases["register_nodes_s"] = time.perf_counter() - t0
        # The first nodes were granted 10-15 s (the TTL scales with how many
        # timers exist): one full pass now, as a client's first heartbeat
        # follows its registration, then the paced schedule.
        t0 = time.perf_counter()
        ids = [node.ID for node in fleet]
        ttls = [self.server.node_heartbeat(nid) for nid in ids]
        self.phases["first_heartbeats_s"] = time.perf_counter() - t0
        self.heartbeats = Heartbeats(self.server, ids, ttls)
        self.heartbeats.start()

        nt = self.server.tindex.nt
        want_rows = self.config["fleet"]["table_rows"]
        if self.n_nodes == self.config["fleet"]["nodes"] \
                and nt.n_rows != want_rows:
            raise RuntimeError(f"node table has {nt.n_rows} rows, the "
                               f"configuration states {want_rows}")
        t0 = time.perf_counter()
        # What a server does once its table has reached steady size: the
        # device copy and its refresh programs exist before the first job.
        nt.warm_device()
        self._warm_up(self.config["warmup"])
        self.phases["warm_up_s"] = time.perf_counter() - t0
        return self

    def _check_settings(self):
        cfg = self.server.config
        for field, want in self.config["server"].items():
            have = getattr(cfg, field)
            if have != want:
                raise RuntimeError(
                    f"server runs with {field}={have!r}; the configuration "
                    f"file states {want!r}")

    def _warm_up(self, warm):
        """Every shape this configuration's traffic can reach, before the
        window. "window_buckets": chip_smoke.warm_buckets, one full window
        plus a remainder per (eval-pad, candidate-count) bucket of the
        keyed program, queued while the workers are parked so that the
        bucket does not depend on timing. "jobs": a few jobs through the
        served path (nothing to compile; imports and first-use costs)."""
        if warm["kind"] == "jobs":
            for _ in range(warm["count"]):
                self._wait([self.register(self.make_job(warm["template"]))],
                           300.0, "warm-up job")
            return
        if warm["kind"] != "window_buckets":
            raise ValueError(f"unknown warm-up kind {warm['kind']!r}")
        window = self.server.config.scheduler_window
        for extra in warm["window_plus"]:
            for w in self.server.workers:
                w.set_pause(True)
            time.sleep(WORKER_PARK_S)
            eval_ids = [self.register(self.make_job(warm["template"]))
                        for _ in range(window + extra)]
            for w in self.server.workers:
                w.set_pause(False)
            self._wait(eval_ids, 900.0, f"warm-up burst {window}+{extra}")

    def _wait(self, eval_ids, timeout, what):
        deadline = time.monotonic() + timeout
        pending = set(eval_ids)
        while pending:
            pending = {e for e in pending
                       if self.eval_status(e) not in TERMINAL}
            if pending:
                if time.monotonic() > deadline:
                    raise RuntimeError(f"{what}: {len(pending)} evals never "
                                       "reached a terminal status")
                time.sleep(0.01)

    # ------------------------------------------------- what generators use
    def make_job(self, template):
        from nomad_tpu.structs import Job, from_dict

        self._job_numbers += 1
        job = from_dict(Job, self.config["jobs"][template])
        job.ID = seeded_uuid(self.rng)
        job.Name = f"{template}-{self._job_numbers}"
        self._templates[job.ID] = template
        return job

    def register(self, job):
        """Server.job_register: the endpoint behind PUT /v1/jobs."""
        eval_id = self.server.job_register(job)[0]
        self.acknowledged.append((job.ID, eval_id, self._templates[job.ID]))
        self.asked += sum(g.Count for g in job.TaskGroups)
        return eval_id

    def eval_status(self, eval_id):
        ev = self.server.state.eval_by_id(eval_id)
        return None if ev is None else ev.Status

    # --------------------------------------------------- after the window
    def drain(self, timeout):
        """Wait until every eval the server holds is complete, failed,
        cancelled or parked as blocked, then until every dispatched window
        has been built and acknowledged. Returns the ids still pending."""
        deadline = time.monotonic() + timeout
        while True:
            pending = [e.ID for e in self.server.state.evals()
                       if e.Status not in TERMINAL and e.Status != "blocked"]
            if not pending or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        for w in self.server.workers:
            quiesce = getattr(w, "quiesce", None)
            if quiesce is not None:
                quiesce(max(1.0, deadline - time.monotonic()))
        return pending

    def worker_stats(self):
        """PipelinedWorker.stats summed over the workers."""
        total = {}
        for w in self.server.workers:
            for key, value in getattr(w, "stats", {}).items():
                total[key] = total.get(key, 0) + value
        return total

    def device_usage(self):
        """(the device's usage table as numpy, node id -> row)."""
        import numpy as np

        nt = self.server.tindex.nt
        return np.asarray(nt.device_arrays()["usage"]), dict(nt.row_of)

    def reads(self):
        """The state store's public reads the recomputation works from."""
        state = self.server.state
        return {"nodes": state.nodes(), "jobs": state.jobs(),
                "evals": state.evals(), "allocs": state.allocs()}

    def facts(self):
        nt = self.server.tindex.nt
        hb = self.heartbeats
        return {"nodes": self.n_nodes, "table_rows": nt.n_rows,
                "computed_classes": len(nt.class_names),
                "heartbeats_sent": hb.sent, "heartbeat_errors": hb.errors[:5],
                "heartbeat_cycle_s": hb.cycle_s, **self.phases}

    def shutdown(self):
        if self.heartbeats is not None:
            self.heartbeats.stop.set()
        if self.agent is not None:
            self.agent.shutdown()
        if self.heartbeats is not None:
            self.heartbeats.join(30.0)
        self._tmp.cleanup()
