"""Deployment "dev_agent_web": dev_agent's one dev-mode Agent on svc-10k's
fleet, under upstream's mock.Job() as published: a service of 10 whose task
asks for 50 MBits and two dynamic ports.

dev_agent.Deployment is reused by import: fleet, heartbeats, the
window_buckets warm-up, the reads. What differs:

- at full size the job is the published one, or the run does not start:
  the template has to equal nomad_tpu.mock.job() field for field but for
  ids and indexes (the configuration's claim "as published" is checked on
  every run, not only by a test).
- a rehearsal's ask. Two evals that pack one node draw their dynamic ports
  blind to each other, so now and then the applier's exact fit refuses a
  node for a doubled port and that eval is finished by the exact
  scheduler: legal, counted under `host` and `fallback` and not under
  `fast`. tests/benchmark_suite/test_benchmark_rehearsal.py asserts
  host == fast for every cell, so a fleet that is not the file's gets the
  template with the task's CPU ask raised to rehearsal.cpu (one allocation
  a node: no two share a port space) and its count cut to rehearsal.count
  (so that the guard ends the window after over a second, not a fifth of
  one), and only such a fleet does. The file's `rehearsal.why` has the
  numbers; tests/test_web_shape.py holds the published shape, collisions
  included, on the CPU.
"""

from __future__ import annotations

from benchmark.deploy import dev_agent

NOT_PUBLISHED = ("ID", "Name", "CreateIndex", "ModifyIndex", "JobModifyIndex")


def published_job():
    """mock.job() as a template: every field but ids and indexes."""
    from nomad_tpu import mock
    from nomad_tpu.structs import to_dict

    job = to_dict(mock.job())
    for key in NOT_PUBLISHED:
        del job[key]
    return job


class Deployment(dev_agent.Deployment):
    """dev_agent's running system under jobs that ask for a network."""

    def __init__(self, config, seed_rng, nodes=None):
        super().__init__(config, seed_rng, nodes=nodes)
        self.full_size = self.n_nodes == config["fleet"]["nodes"]
        if self.full_size:
            want = published_job()
            for name, template in config["jobs"].items():
                if template != want:
                    raise RuntimeError(
                        f"template {name!r} is not nomad_tpu.mock.job() as "
                        "published, which the configuration states")

    def make_job(self, template):
        job = super().make_job(template)
        if not self.full_size:
            for group in job.TaskGroups:
                group.Count = self.config["rehearsal"]["count"]
                for task in group.Tasks:
                    task.Resources.CPU = self.config["rehearsal"]["cpu"]
        return job

    def facts(self):
        return {**super().facts(), "full_size": self.full_size,
                "rehearsal_cpu": None if self.full_size
                else self.config["rehearsal"]["cpu"]}
