"""XLA placement kernels: the scheduling hot path as tensor programs.

This replaces the reference's per-node iterator chain (reference:
scheduler/stack.go Select -> select.go MaxScoreIterator -> rank.go
BinPackIterator -> feasible.go checkers) with batched device programs:

  place_batch   lax.scan over the placements of one evaluation; each step is
                a fused feasibility-mask + BestFit-v3 score + argmax over the
                whole node axis, with in-register usage/anti-affinity updates
                so placement k+1 sees placement k's proposed allocation
                (reference semantics: scheduler/context.go:109-140).

Scoring matches reference funcs.go:102-137 (including its Inf/NaN division
edges) with the job anti-affinity penalty applied after clamping (reference:
rank.go:242-304). Selection is a global argmax rather than the reference's
max-over-log2(n)-random-candidates (reference: stack.go:120-133), which can
only improve placement quality; host-supplied per-node noise reproduces the
load-spreading effect of the reference's node shuffle on ties.

All shapes are static per (N_pad, P_pad) bucket: the node axis is padded to a
power of two by NodeTensor and the placement axis by the stack, so jit caches
stay warm. The node axis is the sharding axis for multi-chip meshes
(nomad_tpu/parallel/).
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import replay_kernel


_LOG2_10 = float(np.log2(10.0))

# The names the served path's device programs carry in a profiler trace
# (each as "jit_<name>"): chosen, not inherited from whatever a factory's
# inner function is called, because trace reductions find programs by
# name (benchmark/layer_metrics/kernel_ms.storm.json matches
# "place_batch" and "compact_window"). tests/test_stage_spans.py pins
# every name to its lowered module. node_table_refresh is built in
# tensor/node_table.py, which imports no kernel.
PROGRAM_NAMES = ("place_batch", "place_batch_multi", "place_batch_keyed",
                 "compact_window", "node_table_refresh")


class PlacementResult(NamedTuple):
    packed: jax.Array       # [P, 3] f32: (chosen row or -1, score, n_feasible)
    usage_after: jax.Array  # [N, R] usage including the new placements

    # Packed so that an eval's result is one device->host transfer, not
    # three.
    @property
    def chosen(self):
        return self.packed[:, 0].astype(jnp.int32)

    @property
    def scores(self):
        return self.packed[:, 1]

    @property
    def n_feasible(self):
        return self.packed[:, 2].astype(jnp.int32)


def _score_cols(use_cpu: jax.Array, use_mem: jax.Array,
                cap_cpu: jax.Array, cap_mem: jax.Array) -> jax.Array:
    """BestFit-v3: 20 - 10^freeCpuPct - 10^freeMemPct, clamped to [0, 18].

    use_* is proposed (cpu, mem) utilization including reserved; cap_* is
    capacity minus reserved (broadcastable). Division by zero follows IEEE
    (Inf/NaN) exactly like the Go reference; NaN sanitizes to 0. THE one
    definition of the formula for the device programs (the monolithic
    scan, the keyed kernel's passes and its resident replay loop, which
    holds a resource a column); the numpy mirror (place_batch_host)
    repeats the same f32 operations. Tests assert all of them bit-for-bit
    equal on XLA's CPU backend. A chip's divide and exp2 need not round as
    numpy's do: PERF.md records what was observed on the v5e.
    """
    free_cpu = 1.0 - use_cpu / cap_cpu
    free_mem = 1.0 - use_mem / cap_mem
    # 10^x on the MXU-friendly path: exp2(x * log2 10).
    total = (jnp.exp2(free_cpu * _LOG2_10) + jnp.exp2(free_mem * _LOG2_10))
    score = jnp.clip(20.0 - total, 0.0, 18.0)
    return jnp.nan_to_num(score, nan=0.0, posinf=18.0, neginf=0.0)


def _score(usage2: jax.Array, score_cap: jax.Array) -> jax.Array:
    """`_score_cols` over usage2 [..., 2] (cpu, mem) and score_cap
    [..., 2] (broadcastable)."""
    return _score_cols(usage2[..., 0], usage2[..., 1],
                       score_cap[..., 0], score_cap[..., 1])


def _make_step(capacity, score_cap, tg_masks, noise, penalty,
               distinct_hosts, job_counts0=None, banned0=None):
    """The ONE definition of the per-placement scan step (fused
    feasibility mask + BestFit-v3 score + argmax + in-register state
    updates). place_batch uses the plain (demand, tg_id, valid) input
    tuple; place_batch_multi adds a reset flag that reloads the per-JOB
    state (anti-affinity counts, distinct-hosts bans) at eval boundaries.
    Sharing the body keeps single/multi/chained parity by construction."""

    def step(carry, inputs):
        usage, job_counts, banned = carry
        if len(inputs) == 4:
            demand, tg_id, is_valid, is_reset = inputs
            job_counts = jnp.where(is_reset, job_counts0, job_counts)
            banned = jnp.where(is_reset, banned0, banned)
        else:
            demand, tg_id, is_valid = inputs
        eligible = tg_masks[tg_id]

        fits = jnp.all(capacity - usage >= demand[None, :], axis=1)
        ok = fits & eligible & ~(distinct_hosts & banned)

        util2 = usage[:, :2] + demand[None, :2]
        score = _score(util2, score_cap)
        score = score - job_counts.astype(jnp.float32) * penalty + noise
        masked = jnp.where(ok, score, -jnp.inf)

        idx = jnp.argmax(masked)
        found = ok[idx] & is_valid

        one = found.astype(usage.dtype)
        usage = usage.at[idx].add(demand * one)
        job_counts = job_counts.at[idx].add(found.astype(job_counts.dtype))
        banned = banned.at[idx].set(banned[idx] | found)

        out = jnp.stack([
            jnp.where(found, idx, -1).astype(jnp.float32),
            jnp.where(found, masked[idx], -jnp.inf),
            jnp.sum(ok).astype(jnp.float32),
        ])
        return (usage, job_counts, banned), out

    return step


@functools.partial(jax.jit, donate_argnums=())
def place_batch(
    capacity: jax.Array,    # [N, R] total resources (fit bound)
    score_cap: jax.Array,   # [N, 2] cpu/mem minus reserved (score denominator)
    usage: jax.Array,       # [N, R] reserved + committed allocs (+/- plan deltas)
    tg_masks: jax.Array,    # [T, N] bool per task group: ready & dc & class & escaped
    job_counts: jax.Array,  # [N] int32 proposed allocs of this job per node
    demands: jax.Array,     # [P, R] per-placement resource ask
    tg_ids: jax.Array,      # [P] int32 task-group index into tg_masks
    valid: jax.Array,       # [P] bool: real placement vs padding
    noise: jax.Array,       # [N] f32 tie-break jitter in [0, 1e-3)
    penalty: jax.Array,     # f32 job anti-affinity penalty (10 service / 5 batch)
    distinct_hosts: jax.Array,  # bool: job has a distinct_hosts constraint
    banned0: jax.Array,     # [N] bool: nodes already holding this job's allocs
) -> PlacementResult:
    step = _make_step(capacity, score_cap, tg_masks, noise, penalty,
                      distinct_hosts)
    (usage, _, _), packed = jax.lax.scan(
        step, (usage, job_counts, banned0), (demands, tg_ids, valid))
    return PlacementResult(packed, usage)


@functools.partial(jax.jit, donate_argnums=())
def place_batch_multi(
    capacity: jax.Array,    # [N, R]
    score_cap: jax.Array,   # [N, 2]
    usage: jax.Array,       # [N, R] chain input (window-sequential)
    tg_masks: jax.Array,    # [T, N] shared across the window's evals
    job_counts0: jax.Array,  # [N] per-eval anti-affinity base (shared)
    demands: jax.Array,     # [E*P, R] all evals' placements, concatenated
    tg_ids: jax.Array,      # [E*P]
    valid: jax.Array,       # [E*P]
    noise: jax.Array,       # [N]
    penalty: jax.Array,     # f32
    distinct_hosts: jax.Array,  # bool (shared job shape)
    banned0: jax.Array,     # [N] per-eval distinct-hosts base (shared)
    reset: jax.Array,       # [E*P] bool: True at each eval's first step
) -> PlacementResult:
    """One scan over a WHOLE WINDOW of same-shaped evaluations.

    A registration storm's window is N near-identical evals whose prepared
    inputs dedupe to one PreparedBatch; dispatching place_batch per eval
    pays a host->device launch per eval plus an eager jnp.stack over the
    window at drain (both scale with window size). This kernel
    concatenates the placements and
    resets the per-JOB state (anti-affinity counts, distinct-hosts bans)
    at each eval boundary, so the whole window is ONE dispatch and ONE
    readback while usage chains exactly as the per-eval kernels did
    (reference sequencing semantics: scheduler/context.go:109-140 within
    an eval; optimistic worker chaining across evals)."""
    step = _make_step(capacity, score_cap, tg_masks, noise, penalty,
                      distinct_hosts, job_counts0=job_counts0,
                      banned0=banned0)
    (usage, _, _), packed = jax.lax.scan(
        step, (usage, job_counts0, banned0),
        (demands, tg_ids, valid, reset))
    return PlacementResult(packed, usage)


class CompactResult(NamedTuple):
    """Host-side per-eval view of a compacted window result: exactly the
    arrays the plan build consumes, in the dtypes it consumes them
    (packed's f32 triple forces a cast + tolist per column per eval on
    the host otherwise)."""

    chosen: np.ndarray   # [P_pad] int32 chosen row per placement (-1 = none)
    scores: np.ndarray   # [P_pad] f32 winning score per placement
    nf_last: int         # n_feasible of the eval's LAST valid placement
    ok: bool             # every valid placement found a row


@jax.jit
def compact_window(packed3, valid, last_idx):
    """On-device reduction of a window's packed kernel outputs to the
    minimal arrays the host build actually needs, BEFORE the device->host
    copy: chosen rows as int32, winner scores, the per-eval n_feasible of
    the final valid placement (the only one metrics keep — earlier fills
    are overwritten before anything snapshots them), and a per-eval
    success mask so the host can branch straight into the vectorized
    all-placed build without scanning. Cuts the transfer by ~1/3 against
    the raw [*, 3] f32 layout and moves every cast off the host.

    packed3 [E, P, 3]; valid [E, P] bool; last_idx [E] int32 (index of
    each eval's last valid placement). Returns (chosen [E, P] int32,
    scores [E, P] f32, nf_last [E] int32, ok [E] bool)."""
    chosen = packed3[..., 0].astype(jnp.int32)
    scores = packed3[..., 1]
    nf_last = jnp.take_along_axis(
        packed3[..., 2], last_idx[:, None].astype(jnp.int32), axis=1
    )[:, 0].astype(jnp.int32)
    ok = jnp.all((chosen >= 0) | ~valid, axis=1)
    return chosen, scores, nf_last, ok


def compact_host(packed: np.ndarray, n_valid: int) -> CompactResult:
    """Numpy mirror of compact_window for one already-host-side result
    (host-placed evals and non-jax test arrays skip the device entirely)."""
    packed = np.asarray(packed)
    chosen = packed[:, 0].astype(np.int32)
    return CompactResult(
        chosen=chosen,
        scores=packed[:, 1].astype(np.float32, copy=False),
        nf_last=int(packed[n_valid - 1, 2]),
        ok=bool((chosen[:n_valid] >= 0).all()))


_LOG2_10_F32 = np.float32(_LOG2_10)


def place_batch_host(capacity, score_cap, usage, tg_masks, job_counts,
                     demands, tg_ids, valid, noise, penalty,
                     distinct_hosts, banned0) -> PlacementResult:
    """Numpy mirror of place_batch for SHALLOW windows.

    A lone eval's 50 placements need no device dispatch, readback or
    (when cold) compile: the pipelined worker routes windows under
    stack.HOST_ROW_STEP_BUDGET row-steps here and storms to the device
    chain. Same semantics — the f32 BestFit-v3 formula with its Inf/NaN
    edges (reference funcs.go:102-137), the anti-affinity penalty and
    noise tie-break, the in-loop usage updates so placement k+1 sees
    placement k (reference context semantics,
    scheduler/context.go:109-140). tests/test_tensor_and_kernels.py
    asserts parity against the device kernel on XLA's CPU backend;
    chip_smoke.py compares the two on the chip."""
    capacity = np.asarray(capacity, np.float32)
    score_cap = np.asarray(score_cap, np.float32)
    usage = np.array(usage, np.float32, copy=True)
    job_counts = np.array(job_counts, np.int32, copy=True)
    banned = np.array(banned0, bool, copy=True)
    demands = np.asarray(demands, np.float32)
    tg_ids = np.asarray(tg_ids, np.int32)
    valid = np.asarray(valid, bool)
    noise = np.asarray(noise, np.float32)
    penalty = np.float32(penalty)
    distinct_hosts = bool(distinct_hosts)
    tg_masks = np.asarray(tg_masks, bool)

    p = len(tg_ids)
    packed = np.empty((p, 3), np.float32)
    neg_inf = np.float32(-np.inf)

    def full_scores(demand):
        """Whole-table masked-score pass — the same f32 formula as the
        device kernel's step."""
        util2 = usage[:, :2] + demand[:2]
        free_pct = np.float32(1.0) - util2 / score_cap
        total = (np.exp2(free_pct[:, 0] * _LOG2_10_F32)
                 + np.exp2(free_pct[:, 1] * _LOG2_10_F32))
        score = np.clip(np.float32(20.0) - total,
                        np.float32(0.0), np.float32(18.0))
        score = np.nan_to_num(score, nan=0.0, posinf=18.0, neginf=0.0)
        return score - job_counts.astype(np.float32) * penalty + noise

    def row_score(idx, demand):
        """One row of full_scores, recomputed after the row's usage or
        count changed — bit-identical to the full pass for that row."""
        util2 = usage[idx, :2] + demand[:2]
        free_pct = np.float32(1.0) - util2 / score_cap[idx]
        total = (np.exp2(free_pct[0] * _LOG2_10_F32)
                 + np.exp2(free_pct[1] * _LOG2_10_F32))
        score = np.float32(np.clip(np.float32(20.0) - total,
                                   np.float32(0.0), np.float32(18.0)))
        score = np.nan_to_num(score, nan=0.0, posinf=18.0, neginf=0.0)
        return (score - np.float32(job_counts[idx]) * penalty
                + noise[idx])

    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        # A storm places many copies of the same task group: between two
        # placements of one (tg, demand) key only ONE node row changes, so
        # the masked-score vector is computed once per key and patched at
        # the placed row afterwards — O(rows) once + O(keys) per step
        # instead of O(rows) per step. Exactly the same f32 values as the
        # naive loop (each row's score is a pure function of that row).
        cache: dict = {}  # key -> [masked, ok, n_feasible, demand, tg]
        for k in range(p):
            demand = demands[k]
            tg = int(tg_ids[k])
            key = (tg, demand.tobytes())
            ent = cache.get(key)
            if ent is None:
                eligible = tg_masks[tg]
                fits = np.all(capacity - usage >= demand[None, :], axis=1)
                ok = fits & eligible
                if distinct_hosts:
                    ok &= ~banned
                masked = np.where(ok, full_scores(demand), neg_inf)
                ent = cache[key] = [masked, ok,
                                    np.float32(np.count_nonzero(ok)),
                                    demand, tg]
            masked, ok, n_feasible = ent[0], ent[1], ent[2]
            idx = int(np.argmax(masked))
            found = bool(ok[idx]) and bool(valid[k])
            packed[k, 0] = np.float32(idx) if found else np.float32(-1)
            packed[k, 1] = masked[idx] if found else neg_inf
            packed[k, 2] = n_feasible
            if found:
                usage[idx] += demand
                job_counts[idx] += 1
                banned[idx] = True
                # Patch the changed row into every cached key: a row's
                # score/feasibility is a pure function of that row, so the
                # patched vectors stay identical to a full recompute.
                cap_row = capacity[idx]
                usage_row = usage[idx]
                for cent in cache.values():
                    cmask, cok, cn, cdemand, ctg = cent
                    old_ok = bool(cok[idx])
                    new_ok = (bool(np.all(cap_row - usage_row >= cdemand))
                              and bool(tg_masks[ctg, idx]))
                    if distinct_hosts:
                        new_ok = new_ok and not banned[idx]
                    cok[idx] = new_ok
                    cmask[idx] = (row_score(idx, cdemand) if new_ok
                                  else neg_inf)
                    if new_ok != old_ok:
                        cent[2] = np.float32(
                            cn + (1.0 if new_ok else -1.0))
    # Same result type as the device kernel; both arrays are
    # host-side numpy here — the pipelined drain dispatches on
    # isinstance(packed, np.ndarray) and skips the readback.
    return PlacementResult(packed, usage)


# ----------------------------------------------------- keyed candidates
# Candidate-set placement: the storm kernel for meshes AND single chips.
#
# Every PreparedBatch satisfies demands[p] == tg_demands[tg_ids[p]]
# (stack.prepare), so a window of P placements draws from at most T
# distinct (task-group, demand) KEYS — and the monolithic scan's full
# score pass per placement is P/T-fold redundant. This kernel
# restructures the whole window around candidate sets:
#
#   1. ONE vectorized score pass per KEY over the node rows at window
#      start (masked BestFit-v3, [T, N]) — then top-K candidate rows per
#      key (lax.top_k; ties break to the lowest index, same as argmax),
#      where K = the window's valid placement count.
#   2. Candidates sort by row id (argmax tie parity), dedup, and trim to
#      the top-K per key, bounding the replay size.
#   3. The exact P-step sequential chain — resets, bans, anti-affinity,
#      the same f32 score ops — replays over the candidate table only.
#
# Exactness: at step j, every modified row is a prior winner (in the
# candidate set by induction, and within its key's top-K by this same
# argument). The winner is either such a row, or the best UNMODIFIED
# row — every row ranked above it at window start for its key is
# modified (else it would win now), so its window-start rank is
# <= j <= K and it survives the top-K selection and the trim.
# Feasibility is monotonic within a window (usage only grows, bans only
# appear mid-eval, eligibility is static) and eval-boundary resets
# restore unmodified rows to exactly their window-start scores, so the
# window-start ranking remains valid across resets. The replay
# recomputes scores from shipped row data with the exact same f32 ops as
# the monolithic step, so results are bit-identical for valid
# placements (tests assert this against place_batch/place_batch_multi).
# For padding placements (valid=False) chosen=-1 and score=-inf as
# always, but the n_feasible column is unspecified (the monolithic
# kernels compute it with the padding's zeroed demand; no consumer reads
# it).
#
# On a MESH the window runs as an explicitly shard-local pipeline
# (`_mesh_keyed_program` below): each shard scores and top-Ks only its
# own rows, one small winner-row exchange crosses the interconnect, and
# the merge+replay runs once on the lead device — ZERO collectives in
# any compiled program. The single-device variant here is the parity
# oracle the mesh pipeline is gated against bit-for-bit.


def keyed_replay_resident(n_rows: int, r_dims: int, n_keys: int,
                          k_cand: int) -> bool:
    """Whether the single-device keyed program for a launch of this static
    shape (table rows and resource columns, keys, candidate budget) runs
    its replay as the loop resident on the chip (`replay_kernel`) or as
    the `lax.scan`. THE one rule: the program builder asks it, and so does
    whoever counts such launches (PipelinedWorker's `launch_resident`), so
    the count cannot disagree with what ran. A shape is declined only
    where its candidate columns would not fit the kernel's share of VMEM
    (many keys over a large candidate table; no served cell comes near)."""
    return replay_kernel.fits(n_keys * min(k_cand, n_rows), n_keys, r_dims)


@functools.lru_cache(maxsize=64)
def _keyed_program(mesh, k_cand: int, replay_mode: str | None = None):
    """Build the jitted single-device keyed-candidate program (mesh is
    accepted for cache-key compatibility but must be None; mesh execution
    goes through `_mesh_keyed_program`). `replay_mode` says how step 3 runs
    where the shape allows the resident loop: None, the served path, is
    the kernel as this process's backend runs it (Mosaic on a TPU, Pallas'
    interpreter anywhere else); "mosaic" is the chip's form whatever the
    backend (tests that lower for a described chip from a CPU host);
    "scan" keeps the `lax.scan`, the exact path the resident loop is gated
    against (tests only)."""
    if replay_mode is None:
        replay_mode = ("mosaic" if jax.default_backend() == "tpu"
                       else "interpret")
    assert mesh is None, "mesh windows run the shard-local pipeline"

    def local_fn(capacity, score_cap, usage, tg_masks, job_counts0,
                 key_demands, tg_ids, valid, noise, penalty, distinct,
                 banned0, reset):
        n_loc, r_dims = capacity.shape
        n_keys = key_demands.shape[0]
        row_base = jnp.int32(0)

        # ---- window-start score pass: one per key over local rows.
        fits0 = jnp.all(capacity[None] - usage[None]
                        >= key_demands[:, None, :], axis=-1)
        ok0 = fits0 & tg_masks & ~(distinct & banned0)[None, :]
        util2 = usage[None, :, :2] + key_demands[:, None, :2]
        score = _score(util2, score_cap[None])
        score = (score - job_counts0.astype(jnp.float32)[None, :] * penalty
                 + noise[None, :])
        masked0 = jnp.where(ok0, score, -jnp.inf)        # [T, n_loc]
        nf0_loc = jnp.sum(ok0, axis=1).astype(jnp.int32)  # [T]

        # ---- local top-K candidates per key -> gathered packets.
        kc = min(k_cand, n_loc)
        _, loc_idx = jax.lax.top_k(masked0, kc)          # [T, kc]
        cand = loc_idx.reshape(-1)                       # [T*kc]
        pkt = jnp.concatenate([
            (cand + row_base)[:, None].astype(jnp.float32),
            capacity[cand],
            score_cap[cand],
            usage[cand],
            job_counts0[cand][:, None].astype(jnp.float32),
            banned0[cand][:, None].astype(jnp.float32),
            noise[cand][:, None],
            tg_masks[:, cand].T.astype(jnp.float32),     # [T*kc, T]
        ], axis=1)
        pkt_all = pkt
        nf0 = nf0_loc
        n_cand = pkt_all.shape[0]

        # Ascending global-row order makes every later argmax break ties
        # toward the lowest row — the monolithic kernel's behavior.
        rows_g = pkt_all[:, 0].astype(jnp.int32)
        order = jnp.argsort(rows_g)
        pkt_s = pkt_all[order]
        rows_s = pkt_s[:, 0].astype(jnp.int32)
        keep = jnp.concatenate(
            [jnp.ones((1,), bool), rows_s[1:] != rows_s[:-1]])

        c_cap = pkt_s[:, 1:1 + r_dims]
        c_sc = pkt_s[:, 1 + r_dims:3 + r_dims]
        c_use0 = pkt_s[:, 3 + r_dims:3 + 2 * r_dims]
        c_cnt0 = pkt_s[:, 3 + 2 * r_dims].astype(jnp.int32)
        c_ban0 = pkt_s[:, 4 + 2 * r_dims] > 0.5
        c_noise = pkt_s[:, 5 + 2 * r_dims]
        c_elig = pkt_s[:, 6 + 2 * r_dims:] > 0.5         # [C, T]

        # Window-start ok/score per candidate per key — the n_feasible
        # delta baseline, and the ranking for the global trim. Rows
        # outside the candidate set cannot change feasibility within a
        # window, so deltas over candidates are exact. ok0c_raw is
        # keep-independent: every copy of a row carries identical data,
        # so after compaction re-picks which copy survives, the raw
        # values stay valid for whichever copy that is.
        fits0c = jnp.all(c_cap[:, None, :] - c_use0[:, None, :]
                         >= key_demands[None, :, :], axis=-1)  # [C, T]
        ok0c_raw = fits0c & c_elig & ~(distinct & c_ban0)[:, None]
        util2c = c_use0[:, None, :2] + key_demands[None, :, :2]
        sc0c = _score(util2c, c_sc[:, None, :])
        sc0c = (sc0c - c_cnt0.astype(jnp.float32)[:, None] * penalty
                + c_noise[:, None])
        # Duplicate copies score -inf here so one row cannot occupy two
        # trim slots of the same key.
        masked0c = jnp.where(ok0c_raw & keep[:, None], sc0c, -jnp.inf)

        # Global trim + COMPACT: keep only each key's global top-K
        # candidates and shrink the arrays to that static size, so the
        # replay cost is independent of the device count. Winners
        # provably rank <= K for their key, so the trim is lossless.
        k_trim = min(k_cand, n_cand)
        if n_keys * k_trim < n_cand:
            _, tidx = jax.lax.top_k(masked0c.T, k_trim)  # [T, k_trim]
            sel = tidx.reshape(-1)                       # [T*k_trim]
            # Re-sort the compacted set by global row (argmax tie parity)
            # and rebuild the dedup mask FROM SCRATCH: a key short of
            # feasible candidates pads its trim slots with -inf entries
            # that can be a row's keep=False duplicate, and if that copy
            # sorts first, carrying the old keep forward would AND it
            # with first-occurrence and drop the row entirely. Copies are
            # identical, so first-occurrence alone is the right mask.
            sel = sel[jnp.argsort(rows_s[sel])]
            rows_s = rows_s[sel]
            keep = jnp.concatenate(
                [jnp.ones((1,), bool), rows_s[1:] != rows_s[:-1]])
            c_cap = c_cap[sel]
            c_sc = c_sc[sel]
            c_use0 = c_use0[sel]
            c_cnt0 = c_cnt0[sel]
            c_ban0 = c_ban0[sel]
            c_noise = c_noise[sel]
            c_elig = c_elig[sel]
            ok0c_raw = ok0c_raw[sel]
        ok0c = ok0c_raw & keep[:, None]

        # Per-placement demand, zeroed for padding steps exactly like the
        # monolithic kernels' zero-padded demand rows.
        kd_p = key_demands[tg_ids] * valid[:, None].astype(jnp.float32)

        def replay(carry, xs):
            c_use, c_cnt, c_ban = carry
            t_j, v_j, r_j, d_j = xs
            c_cnt = jnp.where(r_j, c_cnt0, c_cnt)
            c_ban = jnp.where(r_j, c_ban0, c_ban)
            elig_j = jax.lax.dynamic_index_in_dim(
                c_elig, t_j, axis=1, keepdims=False)
            fits_c = jnp.all(c_cap - c_use >= d_j[None, :], axis=1)
            ok_c = fits_c & elig_j & ~(distinct & c_ban) & keep
            sc = _score(c_use[:, :2] + d_j[None, :2], c_sc)
            sc = sc - c_cnt.astype(jnp.float32) * penalty + c_noise
            m = jnp.where(ok_c, sc, -jnp.inf)
            i = jnp.argmax(m)
            found = ok_c[i] & v_j
            one = found.astype(c_use.dtype)
            c_use = c_use.at[i].add(d_j * one)
            c_cnt = c_cnt.at[i].add(found.astype(jnp.int32))
            c_ban = c_ban.at[i].set(c_ban[i] | found)
            ok0_j = jax.lax.dynamic_index_in_dim(
                ok0c, t_j, axis=1, keepdims=False)
            nf0_j = jax.lax.dynamic_index_in_dim(
                nf0, t_j, keepdims=False)
            nf = nf0_j + jnp.sum(ok_c) - jnp.sum(ok0_j)
            out = jnp.stack([
                jnp.where(found, rows_s[i], -1).astype(jnp.float32),
                jnp.where(found, m[i], -jnp.inf),
                nf.astype(jnp.float32),
            ])
            return (c_use, c_cnt, c_ban), out

        def scan_replay():
            (c_use_f, _, _), packed = jax.lax.scan(
                replay, (c_use0, c_cnt0, c_ban0),
                (tg_ids, valid, reset, kd_p))            # [P, 3]
            return packed, c_use_f

        def resident_replay(interpret):
            # The same chain as one loop over VMEM-resident columns: it
            # answers with candidate indexes, mapped to rows here.
            out, c_use_f = replay_kernel.resident_replay(
                _score_cols, c_cap, c_sc, c_use0, c_cnt0, c_ban0, c_noise,
                c_elig & keep[:, None],
                nf0 - jnp.sum(ok0c, axis=0).astype(jnp.int32),
                key_demands, tg_ids, valid, reset, penalty, distinct,
                interpret=interpret)
            i = out[:, 0].astype(jnp.int32)
            row = jnp.where(i >= 0, rows_s[jnp.maximum(i, 0)], -1)
            return (jnp.concatenate(
                [row.astype(jnp.float32)[:, None], out[:, 1:]], axis=1),
                c_use_f)

        if replay_mode == "scan" or not keyed_replay_resident(
                n_loc, r_dims, n_keys, k_cand):
            packed, c_use_f = scan_replay()
        else:
            packed, c_use_f = resident_replay(
                interpret=replay_mode != "mosaic")

        # Publish the replay's FINAL candidate usage into the owning
        # shard's rows by scatter-SET: c_use_f accumulated each row's won
        # demands sequentially in placement order, bit-identical to the
        # monolithic scan's in-register adds — a scatter-ADD of per-
        # placement demands would apply duplicate indices in XLA-defined
        # order and could drift by an ulp when one row wins repeatedly.
        # Untouched candidate rows set their unchanged value (a no-op),
        # and kept rows are unique so the set order is immaterial.
        lr = rows_s - row_base
        mine = keep & (lr >= 0) & (lr < n_loc)
        # Duplicate entries get an out-of-range index and drop — a
        # clipped index could collide with a real winner row and race
        # its write with a stale gathered value.
        usage = usage.at[jnp.where(mine, lr, n_loc)].set(
            c_use_f, mode="drop")
        return packed, usage

    local_fn.__name__ = local_fn.__qualname__ = "place_batch_keyed"
    return jax.jit(local_fn)


def keyed_cand_count(n_valid: int) -> int:
    """Candidate budget for a window with n_valid real placements, padded
    to a power of two so jit compiles one program per bucket."""
    k = 8
    while k < n_valid:
        k *= 2
    return k


def place_batch_keyed(mesh, capacity, score_cap, usage, tg_masks,
                      job_counts0, key_demands, tg_ids, valid, noise,
                      penalty, distinct_hosts, banned0, reset,
                      n_valid: int) -> PlacementResult:
    """place_batch / place_batch_multi semantics via the keyed candidate
    kernel. key_demands is [T, R] with demands[p] == key_demands[tg_ids[p]]
    for every valid placement (stack.prepare's tg_demands). n_valid is the
    window's real placement count (host-known), which bounds the candidate
    sets. mesh=None runs single-device; a multi-device mesh runs the
    shard-local pipeline (`usage` may be the previous window's MeshChain
    to keep the usage chain shard-resident)."""
    if mesh is not None and int(mesh.devices.size) > 1:
        return _place_batch_keyed_mesh(
            mesh, capacity, score_cap, usage, tg_masks, job_counts0,
            key_demands, tg_ids, valid, noise, penalty, distinct_hosts,
            banned0, reset, n_valid)
    if isinstance(usage, MeshChain):
        usage = usage.materialize()
    fn = _keyed_program(None, keyed_cand_count(n_valid))
    packed, usage = fn(capacity, score_cap, usage, tg_masks, job_counts0,
                      key_demands, tg_ids, valid, noise, penalty,
                      distinct_hosts, banned0, reset)
    return PlacementResult(packed, usage)


# ------------------------------------------------ shard-local mesh pipeline
# The mesh window is an explicitly shard-local pipeline rather than one
# SPMD program (ROADMAP item 2: "per-shard local argmax + top-k merge
# instead of full-axis gathers, usage chain kept shard-local with only
# winner rows exchanged"):
#
#   COLD window (chain start / rebuild):
#     stage A (jax.shard_map, NO collectives): each shard applies the
#       pending winner-row ring to its own usage rows, runs ONE full
#       masked BestFit-v3 score pass over only its own rows, and takes
#       its LOCAL top-B candidate rows per key (B = 2k) with the B-th
#       score as its threshold tau_s.
#     exchange: the per-shard candidate packets hop to the lead device
#       as ONE small transfer ([devices, T*B + 2, W] rows — scores via
#       raw row data, global row ids, usage snapshots, per-key nf0/tau
#       tails — independent of the node count). No compiled program
#       contains a collective, so there is no per-window rendezvous
#       barrier: on ICI the packets ride point-to-point DMAs, on the
#       CPU mesh plain buffer copies.
#     pool build (lead device): merge-sort the candidates by ascending
#       global row (argmax tie parity), first-occurrence dedup, and
#       keep the WHOLE merged set as a resident candidate POOL with
#       tau = max_s tau_s and nf0 = sum_s nf0_s per key.
#
#   WARM window (every storm window after the first): runs ENTIRELY on
#     the lead device against the resident pool — zero shard dispatches,
#     zero cross-device transfers. One jitted step rescopes the pool
#     (window-start ok/score over O(devices * T * k) rows), checks the
#     exactness certificate, selects the top-k candidate set per key,
#     replays the exact P-step sequential chain (resets, bans,
#     anti-affinity, the same f32 score ops), scatters the winners'
#     final usage back into the pool, and appends the winner-row delta
#     (O(P) rows: global row + final usage vector) to a pending RING.
#
#   The sharded usage tail is only touched when it must be: the ring
#   applies to the owning shards inside the NEXT cold window's stage A
#   (or on materialize, for rebase paths and tests) as one scatter —
#   last-write-wins per row, deterministic. Winners only ever come from
#   the pool, so the pool's usage view and the sharded tail + ring are
#   always consistent.
#
# Exactness: winners only modify pool rows, so any row OUTSIDE the pool
# is untouched since the rebuild and still scores <= tau (its shard's
# top-B threshold <= the global max). The certificate per key
#     count(pool scores > tau) >= k   OR   pool ⊇ all feasible rows
# therefore proves the true global top-k lives in the pool; selection,
# dedup, and replay then match the single-device keyed kernel
# bit-for-bit (same f32 ops, same ascending-global-row tie parity).
# A failed certificate raises the chain's exactness FLAG and the
# pipelined worker treats the window like a failed drain: nack + chain
# taint + cold redispatch — the exactly-once machinery that already
# covers killed windows. Cold windows are exact unconditionally (the
# pool contains every shard's top-k at window start), so the flag is
# only ever consulted for warm windows.

_MESH_BUF_MULT = 2      # per-shard candidate buffer per key = mult * k
_MESH_RING_MULT = 16    # pending winner-row ring = mult * k_cand rows


class MeshChain:
    """Opaque sharded usage-chain tail for the keyed mesh pipeline.

    `usage` is the node-sharded usage EXCLUDING every window since the
    last rebuild; those winners live in `ring` (on the lead device)
    until the next cold window scatters them into their owning shards.
    `pool`/`pool_use`/`keep`/`tau`/`nf0` are the lead-device resident
    candidate state warm windows run against; `sig` pins the static
    inputs the warm path may assume unchanged (compared by object
    identity — `refs` keeps them alive). `flag` is the warm window's
    exactness certificate (None for cold windows, which are exact by
    construction). Everything is async device state: building a
    MeshChain never blocks the dispatching thread."""

    __slots__ = ("prog", "usage", "ring", "ring_n", "pool", "pool_use",
                 "keep", "tau", "nf0", "flag", "sig", "refs",
                 "exchange_bytes")

    def __init__(self, prog, usage, ring, ring_n, pool, pool_use, keep,
                 tau, nf0, flag, sig, refs, exchange_bytes):
        self.prog = prog
        self.usage = usage
        self.ring = ring
        self.ring_n = ring_n
        self.pool = pool
        self.pool_use = pool_use
        self.keep = keep
        self.tau = tau
        self.nf0 = nf0
        self.flag = flag
        self.sig = sig
        self.refs = refs
        self.exchange_bytes = exchange_bytes

    @property
    def shape(self):
        # ChainArbiter's shape/epoch validation sees the chain like a
        # plain usage array.
        return self.usage.shape

    def materialize(self):
        """Full usage including the pending winner ring, as a sharded
        device array (one scatter dispatch + one small transfer)."""
        import jax

        ring_rep = jax.device_put(self.ring, self.prog.rep_sharding)
        return self.prog.apply_fn(self.usage, ring_rep)

    def __array__(self, dtype=None):
        arr = np.asarray(self.materialize())
        return arr.astype(dtype) if dtype is not None else arr


class _MeshKeyedProgram:
    """Compiled stages + shardings for one (mesh, k_cand) bucket.

    The node-static columns (capacity, score_cap, job_counts, noise,
    banned) ride ONE packed table array so the cold stage's candidate
    gather is two reads (table + usage), not seven. Candidate packet
    rows use the single-device program's column layout:
    [row, capacity(R), score_cap(2), usage(R), counts, banned, noise,
    eligibility(T)]."""

    def __init__(self, mesh, k_cand):
        import jax.sharding as jsh

        self.mesh = mesh
        self.k_cand = k_cand
        self.ring_cap = _MESH_RING_MULT * k_cand
        axis = mesh.axis_names[0]
        self.axis = axis
        self.n_shards = int(mesh.devices.size)
        dev0 = mesh.devices.reshape(-1)[0]
        self.dev0 = dev0
        self.dev0_sharding = jsh.SingleDeviceSharding(dev0)
        self.node_sharding = jsh.NamedSharding(mesh, jsh.PartitionSpec(axis))
        self.mask_sharding = jsh.NamedSharding(
            mesh, jsh.PartitionSpec(None, axis))
        self.rep_sharding = jsh.NamedSharding(mesh, jsh.PartitionSpec())
        node = jsh.PartitionSpec(axis)
        mask2 = jsh.PartitionSpec(None, axis)
        rep = jsh.PartitionSpec()
        self._puts: "OrderedDict[tuple, tuple]" = OrderedDict()

        k = k_cand

        def pack_table(capacity, score_cap, job_counts0, noise, banned0):
            return jnp.concatenate([
                capacity,
                score_cap,
                job_counts0[:, None].astype(jnp.float32),
                noise[:, None],
                banned0[:, None].astype(jnp.float32),
            ], axis=1)

        # Sharded in, sharded out, no cross-shard ops: plain jit, XLA
        # propagates the sharding without collectives.
        self.pack_fn = jax.jit(pack_table)

        def _apply_ring(usage, ring, row_base):
            """Scatter the pending winner ring into this shard's rows.
            Ring entries carry each window's FINAL usage for the row, so
            the LAST entry per row wins; a scatter-max of ring positions
            picks it deterministically (XLA leaves duplicate-index
            scatter-set order undefined)."""
            n_loc = usage.shape[0]
            rc = ring.shape[0]
            drow = ring[:, 0].astype(jnp.int32) - row_base
            own = (ring[:, 0] >= 0) & (drow >= 0) & (drow < n_loc)
            iota = jnp.arange(rc, dtype=jnp.int32)
            pos = jnp.full((n_loc + 1,), -1, jnp.int32).at[
                jnp.where(own, drow, n_loc)].max(iota, mode="drop")
            last = own & (pos[jnp.clip(drow, 0, n_loc - 1)] == iota)
            # Foreign/padding/superseded rows get an out-of-range index
            # and drop.
            return usage.at[jnp.where(last, drow, n_loc)].set(
                ring[:, 1:], mode="drop")

        def a_cold(table, usage, tg_masks, key_demands, penalty, distinct,
                   ring):
            """Cold shard-local stage: apply the pending ring, one full
            score pass over only this shard's rows, emit the top-B
            candidate packet plus per-key nf0/top-score sidecars. No
            collectives — everything leaves via the explicit exchange.
            The top_k VALUES ship whole: slicing them in here breaks
            XLA:CPU's TopK custom-call rewrite and the lowering falls
            back to a full-row sort (measured 310ms vs 5ms per window at
            262k nodes) — pool_build extracts tau on the lead device."""
            n_loc, r_dims = usage.shape
            my = jax.lax.axis_index(axis)
            row_base = (my * n_loc).astype(jnp.int32)
            usage = _apply_ring(usage, ring, row_base)
            capacity = table[:, :r_dims]
            score_cap = table[:, r_dims:r_dims + 2]
            counts = table[:, r_dims + 2]
            noise = table[:, r_dims + 3]
            banned0 = table[:, r_dims + 4] > 0.5
            fits0 = jnp.all(capacity[None] - usage[None]
                            >= key_demands[:, None, :], axis=-1)
            ok0 = fits0 & tg_masks & ~(distinct & banned0)[None, :]
            util2 = usage[None, :, :2] + key_demands[:, None, :2]
            score = _score(util2, score_cap[None])
            score = score - counts[None, :] * penalty + noise[None, :]
            masked0 = jnp.where(ok0, score, -jnp.inf)
            nf0_loc = jnp.sum(ok0, axis=1).astype(jnp.int32)
            b_buf = min(_MESH_BUF_MULT * k, n_loc)
            vals, idx = jax.lax.top_k(masked0, b_buf)    # [T, B]
            flat = idx.reshape(-1)                       # [T*B] local rows
            pkt = jnp.concatenate([
                (flat + row_base)[:, None].astype(jnp.float32),
                capacity[flat],
                score_cap[flat],
                usage[flat],
                counts[flat][:, None],
                banned0[flat][:, None].astype(jnp.float32),
                noise[flat][:, None],
                tg_masks[:, flat].T.astype(jnp.float32),  # [T*B, T]
            ], axis=1)
            return pkt, usage, nf0_loc[None], vals[None]

        def pool_build(cand, nf_all, vals_all, key_demands):
            """Merge the per-shard packets into the resident candidate
            pool, once per rebuild, on the lead device: sort by
            ascending global row (argmax tie parity), first-occurrence
            dedup (copies of a row are identical), tau = max_s tau_s
            (each shard's B-th best window-start score), nf0 =
            sum_s nf0_s."""
            r_dims = key_demands.shape[1]
            nf0 = jnp.sum(nf_all, axis=0)
            tau = jnp.max(vals_all[:, :, vals_all.shape[2] - 1], axis=0)
            rows_g = cand[:, 0].astype(jnp.int32)
            order = jnp.argsort(rows_g)
            pool = cand[order]
            rows_s = rows_g[order]
            keep = jnp.concatenate(
                [jnp.ones((1,), bool), rows_s[1:] != rows_s[:-1]])
            pool_use = pool[:, 3 + r_dims:3 + 2 * r_dims]
            return pool, pool_use, keep, tau, nf0

        def warm_step(pool, pool_use, keep, tau, nf0, ring, ring_n,
                      key_demands, tg_ids, valid, reset, penalty,
                      distinct):
            """One window against the resident pool, entirely on the
            lead device: window-start ok/score pass, exactness
            certificate, top-k candidate selection, the exact P-step
            replay, winner scatter-back, ring append."""
            n_cand, w = pool.shape
            n_keys = key_demands.shape[0]
            r_dims = key_demands.shape[1]
            rows_s = pool[:, 0].astype(jnp.int32)
            c_cap = pool[:, 1:1 + r_dims]
            c_sc = pool[:, 1 + r_dims:3 + r_dims]
            c_cnt0 = pool[:, 3 + 2 * r_dims].astype(jnp.int32)
            c_ban0 = pool[:, 4 + 2 * r_dims] > 0.5
            c_noise = pool[:, 5 + 2 * r_dims]
            c_elig = pool[:, 6 + 2 * r_dims:] > 0.5     # [C, T]

            # Window-start ok/score per pool row per key — the
            # n_feasible delta baseline, the certificate's evidence, and
            # the selection ranking (identical f32 ops to the
            # single-device program's window-start pass).
            fits0 = jnp.all(c_cap[:, None, :] - pool_use[:, None, :]
                            >= key_demands[None, :, :], axis=-1)
            ok0 = (fits0 & c_elig & ~(distinct & c_ban0)[:, None]
                   & keep[:, None])                      # [C, T]
            util2 = pool_use[:, None, :2] + key_demands[None, :, :2]
            sc0 = _score(util2, c_sc[:, None, :])
            sc0 = (sc0 - c_cnt0.astype(jnp.float32)[:, None] * penalty
                   + c_noise[:, None])
            m0 = jnp.where(ok0, sc0, -jnp.inf)           # [C, T]

            # Exactness certificate: rows outside the pool are untouched
            # since rebuild, hence still <= tau; the true top-k is in
            # the pool iff >= k pool rows score strictly above tau, or
            # the pool covers every feasible row of the table.
            k_sel = min(k, n_cand)
            n_fin = jnp.sum(ok0, axis=0)                 # [T]
            exact = ((jnp.sum(m0 > tau[None, :], axis=0) >= k_sel)
                     | (n_fin >= nf0))
            flag = jnp.any(~exact).astype(jnp.float32)

            # Top-k candidate set per key; ascending pool index IS
            # ascending global row (the pool is sorted), so a plain sort
            # of the selected indices restores argmax tie parity, and
            # first-occurrence dedup masks rows two keys both selected.
            _, tidx = jax.lax.top_k(m0.T, k_sel)         # [T, k_sel]
            sel = jnp.sort(tidx.reshape(-1))             # [T*k_sel]
            keep2 = jnp.concatenate(
                [jnp.ones((1,), bool), sel[1:] != sel[:-1]]) & keep[sel]
            rows_sel = rows_s[sel]
            s_cap = c_cap[sel]
            s_sc = c_sc[sel]
            s_use0 = pool_use[sel]
            s_cnt0 = c_cnt0[sel]
            s_ban0 = c_ban0[sel]
            s_noise = c_noise[sel]
            s_elig = c_elig[sel]                         # [S, T]
            ok0c = ok0[sel] & keep2[:, None]
            kd_p = key_demands[tg_ids] * valid[:, None].astype(jnp.float32)

            def replay(carry, xs):
                c_use, c_cnt, c_ban = carry
                t_j, v_j, r_j, d_j = xs
                c_cnt = jnp.where(r_j, s_cnt0, c_cnt)
                c_ban = jnp.where(r_j, s_ban0, c_ban)
                elig_j = jax.lax.dynamic_index_in_dim(
                    s_elig, t_j, axis=1, keepdims=False)
                fits_c = jnp.all(s_cap - c_use >= d_j[None, :], axis=1)
                ok_c = fits_c & elig_j & ~(distinct & c_ban) & keep2
                sc = _score(c_use[:, :2] + d_j[None, :2], s_sc)
                sc = sc - c_cnt.astype(jnp.float32) * penalty + s_noise
                m = jnp.where(ok_c, sc, -jnp.inf)
                i = jnp.argmax(m)
                found = ok_c[i] & v_j
                one = found.astype(c_use.dtype)
                c_use = c_use.at[i].add(d_j * one)
                c_cnt = c_cnt.at[i].add(found.astype(jnp.int32))
                c_ban = c_ban.at[i].set(c_ban[i] | found)
                ok0_j = jax.lax.dynamic_index_in_dim(
                    ok0c, t_j, axis=1, keepdims=False)
                nf0_j = jax.lax.dynamic_index_in_dim(
                    nf0, t_j, keepdims=False)
                nf = nf0_j + jnp.sum(ok_c) - jnp.sum(ok0_j)
                out = jnp.stack([
                    jnp.where(found, rows_sel[i], -1).astype(jnp.float32),
                    jnp.where(found, m[i], -jnp.inf),
                    nf.astype(jnp.float32),
                    i.astype(jnp.float32),
                ])
                return (c_use, c_cnt, c_ban), out

            (c_use_f, _, _), outs = jax.lax.scan(
                replay, (s_use0, s_cnt0, s_ban0),
                (tg_ids, valid, reset, kd_p))            # [P, 4]
            packed = outs[:, :3]

            # Winners' FINAL usage back into the pool by scatter-SET:
            # c_use_f accumulated each row's won demands sequentially in
            # placement order, bit-identical to the monolithic scan's
            # in-register adds. Masked duplicate entries carry a STALE
            # initial value (the replay never touches them), so they get
            # an out-of-range index and drop rather than racing the kept
            # copy's write.
            pool_use2 = pool_use.at[
                jnp.where(keep2, sel, n_cand)].set(c_use_f, mode="drop")

            # Next window's n_feasible baseline: only pool rows changed.
            fits_f = jnp.all(c_cap[:, None, :] - pool_use2[:, None, :]
                             >= key_demands[None, :, :], axis=-1)
            ok_f = (fits_f & c_elig & ~(distinct & c_ban0)[:, None]
                    & keep[:, None])
            nf0_2 = nf0 + (jnp.sum(ok_f, axis=0)
                           - jnp.sum(ok0, axis=0)).astype(jnp.int32)

            # Winner-row delta appended to the pending ring: O(P) rows
            # of (global row or -1, final usage). Duplicate winners
            # carry identical values; cross-window ordering is resolved
            # by the ring-apply's last-write-wins scatter.
            widx = outs[:, 3].astype(jnp.int32)
            delta = jnp.concatenate(
                [outs[:, 0:1], c_use_f[widx]], axis=1)   # [P, 1+R]
            ring2 = jax.lax.dynamic_update_slice(
                ring, delta, (ring_n, jnp.int32(0)))
            return packed, pool_use2, nf0_2, ring2, flag

        def apply_ring_full(usage, ring):
            my = jax.lax.axis_index(axis)
            row_base = (my * usage.shape[0]).astype(jnp.int32)
            return _apply_ring(usage, ring, row_base)

        self.a_cold = jax.jit(jax.shard_map(
            a_cold, mesh=mesh,
            in_specs=(node, node, mask2, rep, rep, rep, rep),
            out_specs=(node, node, node, node), check_vma=False))
        self.pool_build = jax.jit(pool_build)
        self.warm_step = jax.jit(warm_step)
        self.apply_fn = jax.jit(jax.shard_map(
            apply_ring_full, mesh=mesh,
            in_specs=(node, rep), out_specs=node, check_vma=False))

    def table(self, d_cap, d_sc, d_counts, d_noise, d_banned) -> object:
        """Packed node-static table for these committed inputs, memoized
        by identity (one device-side concat per signature change)."""
        key = ("table", id(d_cap), id(d_sc), id(d_counts), id(d_noise),
               id(d_banned))
        hit = self._puts.get(key)
        if hit is not None:
            self._puts.move_to_end(key)
            return hit[1]
        dev = self.pack_fn(d_cap, d_sc, d_counts, d_noise, d_banned)
        self._puts[key] = ((d_cap, d_sc, d_counts, d_noise, d_banned), dev)
        while len(self._puts) > 64:
            self._puts.popitem(last=False)
        return dev

    # --------------------------------------------------------- input plumbing
    def put(self, name: str, arr, sharding) -> object:
        """Commit one input to the mesh, memoized by object identity: a
        chained storm passes the same host arrays every window and must
        not pay a broadcast per window."""
        import jax

        if _is_committed(arr):
            return arr
        key = (name, id(arr))
        hit = self._puts.get(key)
        if hit is not None:
            self._puts.move_to_end(key)
            return hit[1]
        dev = jax.device_put(np.asarray(arr), sharding)
        self._puts[key] = (arr, dev)
        while len(self._puts) > 64:
            self._puts.popitem(last=False)
        return dev

    def dev0_view(self, arr) -> object:
        """Lead-device view of a replicated/committed array (zero-copy
        when the array already has an addressable shard on dev0)."""
        import jax

        if isinstance(arr, np.ndarray) or np.isscalar(arr):
            return arr  # uncommitted: jit places it with the pool on dev0
        try:
            for s in arr.addressable_shards:
                if s.device == self.dev0:
                    return s.data
        except AttributeError:
            pass
        return jax.device_put(arr, self.dev0_sharding)


def _is_committed(arr) -> bool:
    return hasattr(arr, "sharding") and not isinstance(arr, np.ndarray)


@functools.lru_cache(maxsize=16)
def _mesh_keyed_program(mesh, k_cand: int) -> _MeshKeyedProgram:
    return _MeshKeyedProgram(mesh, k_cand)


def _place_batch_keyed_mesh(mesh, capacity, score_cap, usage, tg_masks,
                            job_counts0, key_demands, tg_ids, valid, noise,
                            penalty, distinct_hosts, banned0, reset,
                            n_valid: int) -> PlacementResult:
    """One window of the shard-local mesh pipeline. `usage` may be a
    plain array (cold window) or the previous window's MeshChain (warm
    when the chain's static inputs are identical by object identity and
    the pending ring has room; anything else rebuilds cold, reusing the
    chained usage + ring when the k bucket still matches)."""
    import jax
    import time as _time

    from nomad_tpu.resilience import failpoints

    k_cand = keyed_cand_count(n_valid)
    prog = _mesh_keyed_program(mesh, k_cand)
    p_pad = len(tg_ids)
    ring_cap = prog.ring_cap
    r_dims = key_demands.shape[1]

    refs = (capacity, score_cap, tg_masks, job_counts0, noise, banned0,
            key_demands, penalty, distinct_hosts)
    sig = tuple(map(id, refs)) + (k_cand, id(mesh))

    d_cap = prog.put("capacity", capacity, prog.node_sharding)
    d_sc = prog.put("score_cap", score_cap, prog.node_sharding)
    d_masks = prog.put("tg_masks", tg_masks, prog.mask_sharding)
    d_counts = prog.put("job_counts", job_counts0, prog.node_sharding)
    d_noise = prog.put("noise", noise, prog.node_sharding)
    d_banned = prog.put("banned0", banned0, prog.node_sharding)
    d_kd = prog.put("key_demands", key_demands, prog.rep_sharding)
    d_pen = prog.put("penalty", penalty, prog.rep_sharding)
    d_dist = prog.put("distinct", distinct_hosts, prog.rep_sharding)
    d_table = prog.table(d_cap, d_sc, d_counts, d_noise, d_banned)

    chain = usage if isinstance(usage, MeshChain) else None
    # The warm path may assume nothing changed but usage-via-winners:
    # the signature pins every static input by identity and the pending
    # ring must have room for this window's delta.
    warm = (chain is not None and chain.prog is prog
            and chain.sig == sig
            and chain.ring_n + p_pad <= ring_cap)

    t0 = _time.perf_counter()
    exchange_ms = 0.0
    exchange_bytes = 0
    poisoned = False
    if warm:
        pool, pool_use, keep = chain.pool, chain.pool_use, chain.keep
        tau, nf0 = chain.tau, chain.nf0
        ring, ring_n = chain.ring, chain.ring_n
        usage_tail = chain.usage
    else:
        if chain is not None and chain.prog is prog:
            # Same bucket: the cold stage applies the chain's pending
            # ring while rebuilding, no materialize round trip.
            base, ring0 = chain.usage, jax.device_put(
                chain.ring, prog.rep_sharding)
        elif chain is not None:
            base = chain.materialize()
            ring0 = prog.put("ring0", _ring_zero(ring_cap, r_dims),
                             prog.rep_sharding)
        else:
            base = usage if _is_committed(usage) else \
                prog.put("usage", usage, prog.node_sharding)
            ring0 = prog.put("ring0", _ring_zero(ring_cap, r_dims),
                             prog.rep_sharding)
        pkt, usage_tail, nf_sh, vals_sh = prog.a_cold(
            d_table, base, d_masks, d_kd, d_pen, d_dist, ring0)
        # The winner-row exchange seam: per-shard candidate packets hop
        # to the lead device as one small async device-to-device
        # transfer — never a collective, never a host sync. Warm windows
        # don't cross the interconnect at all. Chaos coverage:
        # tensor.mesh.exchange — `error`/`delay` surface at this dispatch
        # seam (the worker routes the run to the exact path); `drop`
        # simulates a SILENTLY lost/corrupt exchange by poisoning the
        # chain's exactness certificate, so the failure surfaces where a
        # real ICI loss would — at the drain-stage certificate check,
        # which nacks the window, taints the chain, and redelivers
        # exactly once through the ChainArbiter rebase.
        poisoned = failpoints.fire("tensor.mesh.exchange") == "drop"
        pkt0 = jax.device_put(pkt, prog.dev0_sharding)
        nf0_0 = jax.device_put(nf_sh, prog.dev0_sharding)
        vals0 = jax.device_put(vals_sh, prog.dev0_sharding)
        exchange_bytes = int(pkt.nbytes + nf_sh.nbytes + vals_sh.nbytes)
        pool, pool_use, keep, tau, nf0 = prog.pool_build(
            pkt0, nf0_0, vals0, prog.dev0_view(d_kd))
        ring = prog.dev0_view(prog.put(
            "ring0", _ring_zero(ring_cap, r_dims), prog.rep_sharding))
        ring_n = 0
        # Timer stops HERE: exchange_ms is cold rebuild + winner-row
        # exchange dispatch only — warm windows perform no exchange, so
        # their (lead-device) dispatch must not inflate the metric.
        exchange_ms = (_time.perf_counter() - t0) * 1e3

    packed, pool_use2, nf0_2, ring2, flag = prog.warm_step(
        pool, pool_use, keep, tau, nf0, ring, np.int32(ring_n),
        prog.dev0_view(d_kd), prog.dev0_view(tg_ids),
        prog.dev0_view(valid), prog.dev0_view(reset),
        prog.dev0_view(d_pen), prog.dev0_view(d_dist))

    with _MESH_STATS_LOCK:
        _MESH_STATS["exchange_ms"] += exchange_ms
        _MESH_STATS["candidate_bytes"] += exchange_bytes
        _MESH_STATS["windows"] += 1
        _MESH_STATS["warm_windows"] += 1 if warm else 0

    chain_flag = flag if warm else None
    if poisoned:
        chain_flag = np.float32(1.0)
    new_chain = MeshChain(
        prog, usage_tail, ring2, ring_n + p_pad, pool, pool_use2, keep,
        tau, nf0_2, chain_flag, sig, refs, exchange_bytes)
    return PlacementResult(packed, new_chain)


_COLLECTIVE_RE = r"(all-gather|all-reduce|reduce-scatter|collective-permute)"


def mesh_collective_audit(mesh, k_cand: int, n_rows: int = 512,
                          n_keys: int = 4, p_pad: int = 64,
                          r_dims: int = 5) -> dict:
    """Compile every stage of the shard-local mesh pipeline on synthetic
    inputs and count the collectives in each program's HLO — the
    structural claim behind the pipeline (zero: the cold stage is
    shard-local, the exchange is an explicit point-to-point device_put,
    warm windows live on the lead device). Returns per-stage counts plus
    the per-window exchanged bytes at this shape. The tier-1
    collective-count gate (tests/test_tensor_and_kernels.py) fails on a
    regression."""
    import re

    import jax

    prog = _mesh_keyed_program(mesh, k_cand)
    rng = np.random.default_rng(0)
    put = jax.device_put
    d_cap = put(rng.uniform(1e3, 4e3, (n_rows, r_dims)).astype(np.float32),
                prog.node_sharding)
    d_sc = put(rng.uniform(8e2, 4e3, (n_rows, 2)).astype(np.float32),
               prog.node_sharding)
    usage = put(np.zeros((n_rows, r_dims), np.float32), prog.node_sharding)
    d_counts = put(np.zeros(n_rows, np.int32), prog.node_sharding)
    d_noise = put((rng.random(n_rows) * 1e-3).astype(np.float32),
                  prog.node_sharding)
    d_banned = put(np.zeros(n_rows, bool), prog.node_sharding)
    d_masks = put(np.ones((n_keys, n_rows), bool), prog.mask_sharding)
    d_kd = put(np.full((n_keys, r_dims), 20, np.float32), prog.rep_sharding)
    d_pen = put(np.float32(10.0), prog.rep_sharding)
    d_dist = put(np.asarray(False), prog.rep_sharding)
    ring0 = put(_ring_zero(prog.ring_cap, r_dims), prog.rep_sharding)
    table = prog.pack_fn(d_cap, d_sc, d_counts, d_noise, d_banned)

    def count(jitted, *args):
        hlo = jitted.lower(*args).compile().as_text()
        return len(re.findall(_COLLECTIVE_RE, hlo))

    out = {"cold": count(prog.a_cold, table, usage, d_masks, d_kd, d_pen,
                         d_dist, ring0)}
    pkt, usage_tail, nf_sh, vals_sh = prog.a_cold(
        table, usage, d_masks, d_kd, d_pen, d_dist, ring0)
    out["exchange_bytes"] = int(pkt.nbytes + nf_sh.nbytes + vals_sh.nbytes)
    pkt0 = put(pkt, prog.dev0_sharding)
    nf0_0 = put(nf_sh, prog.dev0_sharding)
    vals0 = put(vals_sh, prog.dev0_sharding)
    out["pool_build"] = count(prog.pool_build, pkt0, nf0_0, vals0,
                              prog.dev0_view(d_kd))
    pool, pool_use, keep, tau, nf0 = prog.pool_build(
        pkt0, nf0_0, vals0, prog.dev0_view(d_kd))
    tg_ids = np.zeros(p_pad, np.int32)
    valid = np.ones(p_pad, bool)
    reset = np.zeros(p_pad, bool)
    out["warm"] = count(
        prog.warm_step, pool, pool_use, keep, tau, nf0,
        prog.dev0_view(ring0), np.int32(0), prog.dev0_view(d_kd), tg_ids,
        valid, reset, prog.dev0_view(d_pen), prog.dev0_view(d_dist))
    out["apply"] = count(prog.apply_fn, usage_tail, ring0)
    return out


# Module-level mesh pipeline counters, drained by the pipelined worker
# into its declared stats schema (nomad.mesh.* keys). The lock covers
# the += read-modify-writes against a concurrent drain's copy+reset:
# with N workers, one worker's roll-up runs lease-free while another's
# dispatch is mid-increment.
_MESH_STATS = {"exchange_ms": 0.0, "candidate_bytes": 0, "windows": 0,
               "warm_windows": 0}
_MESH_STATS_LOCK = threading.Lock()


def mesh_stats_drain() -> dict:
    """Return-and-reset the pipeline counters (any worker's stats
    roll-up may drain; totals are preserved across drains)."""
    with _MESH_STATS_LOCK:
        out = dict(_MESH_STATS)
        _MESH_STATS.update(exchange_ms=0.0, candidate_bytes=0, windows=0,
                           warm_windows=0)
    return out


@functools.lru_cache(maxsize=8)
def _ring_zero(cap: int, r_dims: int) -> np.ndarray:
    return np.full((cap, 1 + r_dims), -1.0, dtype=np.float32)


# Note: the system scheduler's per-node sweep and the plan applier's
# re-verification run host-side (numpy / structs.allocs_fit) — they are
# O(nodes-in-one-plan), tiny next to the placement scan, and need exact
# port-level network checks that don't tensorize. Only place_batch is hot.
