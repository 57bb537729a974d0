"""The keyed program's replay as ONE loop resident on the chip.

Step 3 of `kernels._keyed_program` is a serial chain: placement j+1 has to
see placement j. As a `lax.scan` every step is some eighteen XLA operations
over the candidate table, each a launch of its own inside the loop; the
table is a few hundred KB, so a step costs launch overhead and nothing
else (PERF.md section 5: 12.69 us a step at 8,192 candidates on a v5e).
Here the same chain is one Pallas kernel: the candidate columns are loaded
into VMEM once, `c_use`, `c_cnt` and `c_ban` live there for every step of
the window, and a step is vector code over resident data.

The arithmetic is `replay`'s, operation for operation in f32 and in the same
order (`kernels._score_cols` is the one definition of the formula for
both), with the same argmax tie rule (lowest candidate index), the same
resets at eval boundaries and the same n_feasible. On XLA's CPU backend
(`interpret=True`) the two are equal bit for bit, which
tests/test_replay_kernel.py holds; on the chip Mosaic's divide and exp2
need not round as XLA's do (PERF.md has what was read there).

Layout: candidates on the lane axis, a column of C candidates as
[C_pad / 128, 128] f32 with C_pad a multiple of 1,024 (whole vregs).
Padding candidates are ineligible for every key, so they never win and
never count. The per-step words (key, valid, reset) arrive packed one
int32 a step, a chunk of steps a grid iteration through SMEM; the state
is carried across grid iterations in VMEM (the usage columns are the
kernel's second output, whose block never moves, so it is written back
once, after the last step).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LANES = 128
_TILE = 8 * LANES      # candidates are padded to whole (8, 128) f32 vregs
_CHUNK = 1024          # replay steps a grid iteration (an SMEM block)
_VALID_BIT, _RESET_BIT = 16, 17   # step word: key | valid << 16 | reset << 17

# What the kernel may keep resident. A v5e core has 128 MiB of VMEM; the
# compiler's own temporaries (a few columns' worth) come on top of the
# columns counted in `resident_bytes`, so the limit handed to Mosaic is
# twice the count plus a fixed allowance, and a launch whose columns count
# more than this budget is declined (it replays under lax.scan).
_VMEM_BUDGET = 40 << 20
_VMEM_ALLOWANCE = 16 << 20


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def resident_bytes(n_cand: int, n_keys: int, r_dims: int) -> int:
    """VMEM the resident loop holds for a launch of this shape: the table
    columns (capacity, score_cap, noise, cnt0, ban0, candidate index),
    usage in and out, an eligibility column a key, the two carried
    columns, and the double-buffered block of result rows."""
    c_pad = _pad_to(n_cand, _TILE)
    cols = (r_dims + 6) + 2 * r_dims + n_keys + 2
    return 4 * c_pad * cols + 2 * 4 * _CHUNK * LANES


def fits(n_cand: int, n_keys: int, r_dims: int) -> bool:
    """Whether a launch of this static shape runs as the resident loop. A
    key's index has to fit under the step word's flag bits."""
    return (n_keys < (1 << _VALID_BIT)
            and resident_bytes(n_cand, n_keys, r_dims) <= _VMEM_BUDGET)


def _make_kernel(r_dims: int, chunk: int, score_cols):
    from jax.experimental import pallas as pl

    cap, sc, noise, cnt0, ban0, iota = (0, r_dims, r_dims + 2, r_dims + 3,
                                        r_dims + 4, r_dims + 5)

    def all_reduce(op, x):   # [rows, 128] -> [1, 1]
        return op(op(x, axis=0, keepdims=True), axis=1, keepdims=True)

    def kernel(steps_ref, kd_ref, scal_ref, nfb_ref, tab_ref, use0_ref,
               ek_ref, out_ref, use_ref, cnt_ref, ban_ref):
        def reload_job_state():   # an eval's first step: its job's base
            cnt_ref[...] = tab_ref[cnt0]
            ban_ref[...] = tab_ref[ban0]

        @pl.when(pl.program_id(0) == 0)
        def _():
            use_ref[...] = use0_ref[...]
            reload_job_state()

        penalty = scal_ref[0]
        distinct = scal_ref[1] > 0.5
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        def step(j, carry):
            word = steps_ref[j]
            t_j = word & ((1 << _VALID_BIT) - 1)
            v_j = ((word >> _VALID_BIT) & 1) == 1

            pl.when(((word >> _RESET_BIT) & 1) == 1)(reload_job_state)

            # A padding step's demand is zeroed, as kd_p is for the scan.
            vf = v_j.astype(jnp.float32)
            d = [kd_ref[t_j * r_dims + r] * vf for r in range(r_dims)]
            cnt = cnt_ref[...]
            ban = ban_ref[...]
            use = [use_ref[r] for r in range(r_dims)]
            fits_c = (tab_ref[cap] - use[0]) >= d[0]
            for r in range(1, r_dims):
                fits_c = fits_c & ((tab_ref[cap + r] - use[r]) >= d[r])
            ok = fits_c & (ek_ref[t_j] > 0.5) & ~(distinct & (ban > 0.5))
            s = score_cols(use[0] + d[0], use[1] + d[1],
                           tab_ref[sc], tab_ref[sc + 1])
            s = s - cnt * penalty + tab_ref[noise]
            m = jnp.where(ok, s, -jnp.inf)
            # argmax, ties to the lowest candidate index: the maximum,
            # then the least index that holds it.
            mx = all_reduce(jnp.max, m)
            idx_col = tab_ref[iota]
            i = all_reduce(jnp.min, jnp.where(m == mx, idx_col, jnp.inf))
            # ok[i] is "the maximum is a score": a feasible row's is finite.
            found = (mx > -jnp.inf) & v_j
            one = found.astype(jnp.float32)
            at_i = idx_col == i
            for r in range(r_dims):
                use_ref[r] = jnp.where(at_i, use[r] + d[r] * one, use[r])
            cnt_ref[...] = jnp.where(at_i, cnt + one, cnt)
            ban_ref[...] = jnp.where(at_i, jnp.maximum(ban, one), ban)
            nf = nfb_ref[t_j] + all_reduce(jnp.sum, jnp.where(ok, 1.0, 0.0))
            out_ref[pl.ds(j, 1), :] = jnp.where(
                lane == 0, jnp.where(found, i, -1.0),
                jnp.where(lane == 1, jnp.where(found, mx, -jnp.inf), nf))
            return carry

        jax.lax.fori_loop(0, chunk, step, 0)

    return kernel


def resident_replay(score_cols, c_cap, c_sc, c_use0, c_cnt0, c_ban0, c_noise,
                    ek, nf_base, key_demands, tg_ids, valid, reset, penalty,
                    distinct, *, interpret: bool):
    """The replay of `kernels._keyed_program` over C candidates and P steps.

    ek [C, T] bool: eligible for the key AND the kept copy of its row;
    nf_base [T] int32: a key's feasible count at window start less its
    feasible candidates then (the scan's nf0_j - sum(ok0_j)). Returns
    (out [P, 3] f32: winning CANDIDATE index or -1, its score or -inf,
    n_feasible; c_use_f [C, R] f32: the candidates' final usage)."""
    # Imported where a program is built: the shape rule above is asked by
    # processes that never build one.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_c, r_dims = c_cap.shape
    n_keys = key_demands.shape[0]
    p = tg_ids.shape[0]
    c_pad = _pad_to(n_c, _TILE)
    rows = c_pad // LANES
    chunk = min(_CHUNK, _pad_to(p, 8))
    p_pad = _pad_to(p, chunk)

    def cols(x, fill=0.0):   # [C, K] -> [K, rows, 128]
        return jnp.pad(x.astype(jnp.float32).T, ((0, 0), (0, c_pad - n_c)),
                       constant_values=fill).reshape(-1, rows, LANES)

    tab = jnp.concatenate([
        cols(c_cap), cols(c_sc, 1.0),
        cols(jnp.stack([c_noise, c_cnt0.astype(jnp.float32),
                        c_ban0.astype(jnp.float32)], axis=1)),
        jnp.arange(c_pad, dtype=jnp.float32).reshape(1, rows, LANES)])
    use0, ekf = cols(c_use0), cols(ek)
    word = (tg_ids.astype(jnp.int32)
            | (valid.astype(jnp.int32) << _VALID_BIT)
            | (reset.astype(jnp.int32) << _RESET_BIT))
    word = jnp.pad(word, (0, p_pad - p))   # a pad step: invalid, no reset
    scal = jnp.stack([penalty.astype(jnp.float32).reshape(()),
                      distinct.astype(jnp.float32).reshape(())])

    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    out, use_f = pl.pallas_call(
        _make_kernel(r_dims, chunk, score_cols),
        grid=(p_pad // chunk,),
        in_specs=[
            pl.BlockSpec((chunk,), lambda c: (c,), memory_space=pltpu.SMEM),
            smem(), smem(), smem(), vmem(), vmem(), vmem()],
        out_specs=[
            pl.BlockSpec((chunk, LANES), lambda c: (c, 0)),
            pl.BlockSpec((r_dims, rows, LANES), lambda c: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((p_pad, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((r_dims, rows, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32),
                        pltpu.VMEM((rows, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(2 * resident_bytes(n_c, n_keys, r_dims)
                              + _VMEM_ALLOWANCE)),
        interpret=interpret,
        name="keyed_replay",
    )(word, key_demands.astype(jnp.float32).reshape(-1), scal,
      nf_base.astype(jnp.float32), tab, use0, ekf)
    return out[:p, :3], use_f.reshape(r_dims, c_pad)[:, :n_c].T
