"""Deployment "dev_agent_dcs": dev_agent's one dev-mode Agent over a fleet
laid into several datacenters, under a mix of job shapes.

dev_agent.Deployment is reused by import; what differs is what a fleet of
datacenters and a mixed window need:

- build_fleet lays the file's nodes into its datacenters by their sizes
  (a rehearsal's smaller fleet in the same shares), with racks_per_datacenter
  rack labels in each. The computed class hashes the datacenter, so the
  classes are datacenters x racks. The ineligible racks are drawn by a
  seeded shuffle over all (datacenter, rack) pairs.
- the warm-up covers every program a window of the mix can reach. A window
  is launched as one run per prepared batch, and a run's program is fixed
  by its key count (unique task groups) and its (eval-pad, candidate-count)
  bucket, not by its datacenter set (the masks are inputs). So: first one
  job of every template in one parked burst (a window of single-eval
  launches in both key counts, and every datacenter set's node context
  built), then dev_agent's window_plus bursts once per shape class.
- at full size it raises unless the running table has the file's rows and
  the file's count of computed classes.
"""

from __future__ import annotations

import time

from benchmark.deploy import dev_agent
from benchmark.deploy.dev_agent import WORKER_PARK_S, seeded_uuid


def datacenter_sizes(fleet, n):
    """[(name, nodes)] for a fleet of n: the file's sizes at full size, the
    same shares (largest remainders) at a rehearsal's size."""
    dcs = fleet["datacenters"]
    total = sum(dc["nodes"] for dc in dcs)
    if n == total:
        return [(dc["name"], dc["nodes"]) for dc in dcs]
    exact = [dc["nodes"] * n / total for dc in dcs]
    sizes = [int(x) for x in exact]
    by_remainder = sorted(range(len(dcs)), key=lambda i: sizes[i] - exact[i])
    for i in by_remainder[:n - sum(sizes)]:
        sizes[i] += 1
    return [(dc["name"], size) for dc, size in zip(dcs, sizes)]


def build_fleet(fleet, n, rng):
    """n nodes of the file's template over the file's datacenters, node i of
    a datacenter in its rack i % racks_per_datacenter. The first
    rack_variants (datacenter, rack) pairs of a seeded shuffle are made
    ineligible as the file says, and never_ready_per_1000 nodes register in
    status "initializing" and stay there."""
    from nomad_tpu.structs import Node, from_dict

    n_racks = fleet["racks_per_datacenter"]
    sizes = datacenter_sizes(fleet, n)
    racks = [(name, r) for name, _ in sizes for r in range(n_racks)]
    rng.shuffle(racks)
    variant_of = {}
    for variant in fleet["rack_variants"]:
        for _ in range(variant["racks"]):
            variant_of[racks.pop()] = variant
    never_ready = set(rng.sample(
        range(n), max(1, n * fleet["never_ready_per_1000"] // 1000)))
    nodes = []
    for name, size in sizes:
        for i in range(size):
            node = from_dict(Node, fleet["node"])
            node.ID = seeded_uuid(rng)
            node.Name = f"node-{name}-{i}"
            node.Datacenter = name
            node.Meta[fleet["rack_meta_key"]] = f"r{i % n_racks}"
            variant = variant_of.get((name, i % n_racks))
            if variant is not None:
                node.Attributes.update(variant.get("set_attributes", {}))
                for key in variant.get("delete_attributes", ()):
                    del node.Attributes[key]
            if len(nodes) in never_ready:
                node.Status = "initializing"
            nodes.append(node)
    return nodes


class Deployment(dev_agent.Deployment):
    """dev_agent's running system, over datacenters and a mix of shapes."""

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
        # As dev_agent: one full pass (a client's first heartbeat follows
        # its registration), then the paced schedule.
        t0 = time.perf_counter()
        ids = [node.ID for node in fleet]
        ttls = [self.server.node_heartbeat(nid) for nid in ids]
        self.phases["first_heartbeats_s"] = time.perf_counter() - t0
        self.heartbeats = dev_agent.Heartbeats(self.server, ids, ttls)
        self.heartbeats.start()

        nt = self.server.tindex.nt
        want = self.config["fleet"]
        if self.n_nodes == want["nodes"]:
            have = (nt.n_rows, len(nt.class_names))
            if have != (want["table_rows"], want["computed_classes"]):
                raise RuntimeError(
                    f"node table has {have[0]} rows and {have[1]} computed "
                    f"classes, the configuration states "
                    f"{want['table_rows']} and {want['computed_classes']}")
        t0 = time.perf_counter()
        nt.warm_device()
        self._warm_up(self.config["warmup"])
        self.phases["warm_up_s"] = time.perf_counter() - t0
        return self

    def _warm_up(self, warm):
        if warm["kind"] != "window_buckets_by_shape":
            raise ValueError(f"unknown warm-up kind {warm['kind']!r}")
        self._parked_burst(warm["first"], "warm-up: one of every template")
        window = self.server.config.scheduler_window
        extras = warm["window_plus"]
        if self.n_nodes != self.config["fleet"]["nodes"]:
            # A rehearsal's fleet compiles nothing and cannot hold every
            # burst under the generator's fill guard (its smallest
            # datacenter sets the guard): one burst a shape class.
            extras = extras[:1]
        for shape, template in warm["shape_classes"].items():
            for extra in extras:
                self._parked_burst([template] * (window + extra),
                                   f"warm-up burst {shape} {window}+{extra}")

    def _parked_burst(self, templates, what):
        """Register one job of each of `templates` while the workers are
        parked, so that the windows they make do not depend on timing."""
        for w in self.server.workers:
            w.set_pause(True)
        time.sleep(WORKER_PARK_S)
        eval_ids = [self.register(self.make_job(t)) for t in templates]
        for w in self.server.workers:
            w.set_pause(False)
        self._wait(eval_ids, 900.0, what)
