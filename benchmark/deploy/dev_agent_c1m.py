"""Deployment "dev_agent_c1m": dev_agent's one dev-mode Agent over the
Million Container Challenge's fleet, under jobs of 1,000.

dev_agent.Deployment is reused by import, its build_fleet included (the
file's rack_variants is empty: one machine type). What differs is what jobs
of 1,000 need:

- the warm-up. A job of 1,000 pads to 1,024 placements, and a run of n such
  evals in one window is one device launch whose program is fixed by
  (eval-pad, unclipped candidate count): stack.dispatch for a run of one,
  stack.eval_pad's power of two (at least 4) and
  kernels.keyed_cand_count(n x 1,000) for two or more. Runs of 1, 2, 3-4,
  5-8, 9-16 and 17-32 evals are six programs (`programs_reached`), and the
  file's bursts of 1, 2, 4, 8, 16 and 32 jobs reach one each. dev_agent's
  bursts of 33-49 jobs would ask for 310,000 allocations, a quarter of the
  cluster, before the window opens; these ask for 63,000.
- how a burst stays whole. A worker takes its first eval before it takes
  the chain lease (pipelined_worker.run: wait for the lease to be free,
  dequeue one, acquire, fill the window under the lease), so two idle
  workers let go together split a parked burst of n into n-1 and 1: a
  burst of 2 would be two runs of one and never reach the (4, 2,048)
  program. So a burst is queued while both workers are parked and only ONE
  is let go (they take turns); the other stays parked until the burst's
  evals are terminal. The one worker takes the first eval, then the lease,
  and its fill finds the other n-1 in the broker: one window, one launch.
  At full size the module reads the workers' counters after each burst
  and repeats a burst (once) that was not one launch of all its evals at
  the steps stack.eval_pad gives, e.g. after a redelivery; it raises
  before any burst, a repeat included, that would take the allocations
  asked for past the file's `max_allocs`.
- the rehearsal's job size. A fleet that is not the file's 5,000 nodes gets
  jobs of rehearsal.count (the file says why) and one burst. At full size
  the module raises unless the job's Count is 1,000, the table has the
  file's rows, the nodes fall into the file's computed classes and the
  programs the file states are the ones its bursts reach.

The eval-pad rule is imported from the program at the top of this file, so
a checkout without it (the parent of the PR that added this cell) fails at
the import, before anything starts.
"""

from __future__ import annotations

import time

from benchmark.deploy import dev_agent
from benchmark.deploy.dev_agent import WORKER_PARK_S
from nomad_tpu.scheduler.stack import _pad_pow2, eval_pad

JOB_COUNT = 1000


def programs_reached(bursts, count):
    """{(launch, eval-pad, serial steps, unclipped candidate count)} of one
    window per burst, each a single run of same-shaped evals of `count`
    placements: what stack.prepare_batch, stack.dispatch_multi and
    kernels.place_batch_keyed make of it. Computed, no device."""
    from nomad_tpu.scheduler import kernels

    p_pad = _pad_pow2(count)
    out = set()
    for n in bursts:
        e_pad = eval_pad(n)
        out.add(("dispatch_multi" if n >= 2 else "dispatch", e_pad,
                 e_pad * p_pad, kernels.keyed_cand_count(n * count)))
    return out


class Deployment(dev_agent.Deployment):
    """dev_agent's running system under jobs of 1,000."""

    def __init__(self, config, seed_rng, nodes=None):
        super().__init__(config, seed_rng, nodes=nodes)
        self.full_size = self.n_nodes == config["fleet"]["nodes"]
        self.job_count = JOB_COUNT if self.full_size \
            else config["rehearsal"]["count"]
        self.bursts = []  # what each warm-up burst launched

    def make_job(self, template):
        job = super().make_job(template)
        if not self.full_size:
            for group in job.TaskGroups:
                group.Count = self.job_count
        return job

    def _check_shape(self, warm):
        """At full size the run is the file's, or it does not start (the
        table's rows are dev_agent's to check)."""
        want = self.config["fleet"]
        classes = len(self.server.tindex.nt.class_names)
        counts = {g["Count"] for job in self.config["jobs"].values()
                  for g in job["TaskGroups"]}
        if classes != want["computed_classes"] or counts != {JOB_COUNT}:
            raise RuntimeError(
                f"{classes} computed classes and jobs of {sorted(counts)}; "
                f"the configuration states {want['computed_classes']} and "
                f"jobs of {JOB_COUNT}")
        stated = {(p["launch"], p["e_pad"], p["steps"], p["k_cand"])
                  for p in warm["programs"]}
        if stated != programs_reached(warm["bursts"], JOB_COUNT):
            raise RuntimeError("the warm-up's bursts do not reach the "
                               "programs the configuration states")

    def _warm_up(self, warm):
        if warm["kind"] != "parked_bursts":
            raise ValueError(f"unknown warm-up kind {warm['kind']!r}")
        bursts = warm["bursts"]
        if self.full_size:
            self._check_shape(warm)
        else:
            bursts = bursts[-1:]  # placed on the host: nothing to compile
        for turn, n in enumerate(bursts):
            one_launch = (1, n, eval_pad(n) * _pad_pow2(self.job_count))
            for _ in range(2):
                if self.asked + n * self.job_count > warm["max_allocs"]:
                    raise RuntimeError(
                        f"a warm-up burst of {n} would take the allocations "
                        f"asked for from {self.asked} past "
                        f"{warm['max_allocs']}: {self.bursts}")
                launched = self._parked_burst(warm["template"], n, turn)
                self.bursts.append(launched)
                if not self.full_size or one_launch == (
                        launched["launches"], launched["launch_evals"],
                        launched["launch_steps"]):
                    break
            else:
                raise RuntimeError(f"warm-up burst of {n} was not one "
                                   f"launch, twice: {self.bursts[-2:]}")

    def _parked_burst(self, template, n, turn):
        """Register n jobs while both workers are parked, let one worker go
        (worker `turn` modulo their number), wait for the evals; returns
        what the workers' counters say was launched for it."""
        workers = self.server.workers
        for w in workers:
            w.set_pause(True)
        time.sleep(WORKER_PARK_S)
        before = self.worker_stats()
        eval_ids = [self.register(self.make_job(template)) for _ in range(n)]
        workers[turn % len(workers)].set_pause(False)
        try:
            self._wait(eval_ids, 900.0, f"warm-up burst of {n}")
            workers[0].quiesce(60.0)  # all workers' counters are final then
        finally:
            for w in workers:
                w.set_pause(False)
        after = self.worker_stats()
        return {"jobs": n, **{k: after.get(k, 0) - before.get(k, 0)
                              for k in ("windows", "launches", "launch_evals",
                                        "launch_steps", "host", "fallback")}}

    def facts(self):
        return {**super().facts(), "job_count": self.job_count,
                "warm_up_bursts": self.bursts}
