"""On-device workload sampling: job sequences and task durations.

A job sequence is `EnvParams.num_init_jobs` jobs at t=0 and Poisson
arrivals after them (gaps Exponential(1/`job_arrival_rate`)), up to the
cap `max_jobs` and the episode's time limit: at 1 (the default) the
streaming sequence of upstream's `TPCHDataSampler`, at the cap the
Decima paper's batched arrivals (every job at t=0, none later).

Replaces reference tpch.py:54-106 (host-side Python sampling of job arrivals
and per-task durations). Everything here is shape-static and traced into the
environment's jitted step/reset."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..config import EnvParams
from .bank import (
    NUM_EXEC_LEVELS,
    WAVE_FIRST,
    WAVE_FRESH,
    WAVE_REST,
    WorkloadBank,
    executor_interval_runs,
)


def sample_job_sequence(
    params: EnvParams, bank: WorkloadBank, rng: jax.Array,
    time_limit: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sample up to `max_jobs` arrivals (reference tpch.py:54-73): the
    first `params.num_init_jobs` jobs arrive at t=0 (1: upstream's
    sampler; more: the Decima paper's batched arrivals, decima-sim's
    `--num_init_dags`), subsequent inter-arrival gaps are
    Exponential(1/rate); arrivals stop at the time limit or the cap. The
    jobs at t=0 are loaded at reset in index order (`job_arrival_seq`).

    Returns (arrival_times[J] with inf padding, templates[J], arrived_cap
    num_jobs scalar, mask[J])."""
    j_cap, n0 = params.max_jobs, params.num_init_jobs
    k_gap, k_tpl = jax.random.split(rng)
    mean_gap = 1.0 / params.job_arrival_rate
    gaps = jax.random.exponential(k_gap, (j_cap,)) * mean_gap
    arrivals = jnp.concatenate(
        [jnp.zeros(n0), jnp.cumsum(gaps)[: j_cap - n0]]
    ).astype(jnp.float32)
    mask = arrivals < time_limit
    # the jobs at t=0 must arrive, whatever the limit drawn
    mask = mask | (jnp.arange(j_cap) < n0)
    # arrivals must be a prefix: a job only exists if all earlier ones do
    mask = jnp.cumprod(mask.astype(jnp.int32)).astype(bool)
    templates = jax.random.randint(
        k_tpl, (j_cap,), 0, bank.num_templates, dtype=jnp.int32
    )
    num_jobs = mask.sum().astype(jnp.int32)
    arrivals = jnp.where(mask, arrivals, jnp.inf)
    return arrivals, templates, num_jobs, mask


# bit offsets of a run's (left value, right value, left index, right
# index) in one packed int32: values are at most 100 (7 bits), indices
# at most 7 (3 bits)
_ITV_SHIFTS = (0, 7, 14, 17)
_ITV_MASKS = (0x7F, 0x7F, 0x7, 0x7)


def executor_interval(
    num_executors: int, num_local: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The executor-level interval of `num_local` executors on a job
    (reference tpch.py:237-262): the bracketing level values (left,
    right) and their indices into `EXEC_LEVEL_VALUES`, row `num_local`
    of `bank._executor_intervals(num_executors)` and `_to_idx` of it
    (a `num_local` outside [0, N] reads the nearest row).

    The table is piecewise constant (`bank.executor_interval_runs`: 3
    runs at 10 executors, 9 at 50, never more than 17) and a function
    of the executor count alone, so it is no array: one chain of
    selects over the runs' literal starts picks the run's four numbers
    packed in one word, and shifts and masks take them apart. No
    gather and no bank operand: four `i32[N+1]` bank leaves cost every
    loop that samples a duration four serialised gathers a step, or,
    where the bank is a jit argument and N small enough for the
    compiler to unroll them, 4 x (N+1) scalar operands carried through
    the loop (a fifth of `sweep_fair`'s device time: PERF.md, PR 47)."""
    starts, rows = executor_interval_runs(num_executors)
    words = [
        sum(v << sh for v, sh in zip(row, _ITV_SHIFTS)) for row in rows
    ]
    assert all(
        0 <= v <= m for row in rows for v, m in zip(row, _ITV_MASKS)
    ), rows
    word = jnp.full_like(num_local, words[0], dtype=jnp.int32)
    for start, w in zip(starts[1:], words[1:]):
        word = jnp.where(num_local >= start, jnp.int32(w), word)
    left_v, right_v, left_i, right_i = (
        (word >> sh) & m for sh, m in zip(_ITV_SHIFTS, _ITV_MASKS)
    )
    return left_v, right_v, left_i, right_i


# a stage's duration facts in one word (`EnvState.duration_facts`):
# bit l is `bank.level_present[t, s, l]`, bit 8 + 8 w + l is
# `bank.cnt[t, s, w, l] > 0` (three waves, eight levels)
_FACTS_BUCKET_SHIFT = NUM_EXEC_LEVELS
assert _FACTS_BUCKET_SHIFT + 3 * NUM_EXEC_LEVELS <= 32


def pack_duration_facts(bank: WorkloadBank) -> jnp.ndarray:
    """u32[T,S]: what the duration sampler reads of a (template, stage)
    that no draw decides, one word each: which executor levels the
    stage has first-wave samples at (`bank.level_present`, bits 0 to
    7) and which of its 3 x 8 (wave, level) buckets hold a sample
    (`bank.cnt > 0`, bit 8 + 8 w + l). `bank.max_present` is the
    highest set bit of the first byte, 0 where none (`pack_bank`).

    Computed IN the program from the bank it is handed, at reset,
    where a job's template is written (`core.reset_from_sequence`
    gathers a job's row into `EnvState.duration_facts`), and no leaf
    of the bank: a caller that rebuilds `cnt` or `level_present`
    (`bank.replace(...)`, as the benchmark's sweep drivers do for
    their comparison) hands the program a bank whose facts follow."""
    t, s = bank.level_present.shape[:2]
    # the bit axis leading and (template, stage) flat and minor-most:
    # every intermediate is whole tiles (with the 3 x 8 buckets minor
    # a [T,S,3,8] boolean pads to 12.6 MB of tiles)
    bits = jnp.concatenate([
        jnp.moveaxis(bank.level_present, -1, 0).reshape(-1, t * s),
        jnp.moveaxis(bank.cnt, (2, 3), (0, 1)).reshape(-1, t * s) > 0,
    ])  # bool[32, T*S]: the levels, then the buckets wave-major
    place = jnp.arange(bits.shape[0], dtype=jnp.uint32)[:, None]
    return (bits.astype(jnp.uint32) << place).sum(
        0, dtype=jnp.uint32
    ).reshape(t, s)


def sample_executor_key(
    params: EnvParams, facts: jnp.ndarray, u: jnp.ndarray,
    num_local: jnp.ndarray
) -> jnp.ndarray:
    """Map the executor count to a trace executor-level index, randomly
    interpolating between the two bracketing levels and falling back to the
    max level present for this stage (reference tpch.py:216-235). The
    bracketing levels are a static function of `params.num_executors`
    (`executor_interval`); the stage's present levels are the first
    byte of its word of duration facts (`pack_duration_facts`), the
    highest of them its highest set bit: no bank table is read.

    `u` is a pre-drawn Uniform[0,1) scalar, NOT a PRNG key: the round-5
    CPU decomposition measured the per-call rng plumbing (fold_in +
    split + uniform + randint per sampled task) at ~31% of the whole
    flat micro-step, while the bank-table gathers were free (on the
    CPU: on the chip the four interval tables were not, PR 47, nor the
    reads of `level_present` and `max_present`, PR 50). Callers
    draw ONE batched uniform array per bulk pass and hand each row's
    slice down (see `sample_task_duration`)."""
    left_v, right_v, left_i, right_i = executor_interval(
        params.num_executors, num_local
    )
    rand_pt = 1 + (u * (right_v - left_v)).astype(jnp.int32)
    use_left = (left_v == right_v) | (rand_pt <= num_local - left_v)
    key_idx = jnp.where(use_left, left_i, right_i)
    key_val = jnp.where(use_left, left_v, right_v)
    # the reference's interval table leaves index num_executors zeroed when
    # num_executors > 100 (tpch.py:258-260 excludes it); a 0 "level" is not
    # a first_wave key there, so it falls through to the max present level
    levels = facts & jnp.uint32((1 << NUM_EXEC_LEVELS) - 1)
    present = ((levels >> key_idx.astype(jnp.uint32)) & 1).astype(bool) & (
        key_val > 0
    )
    max_present = jnp.maximum(31 - lax.clz(levels).astype(jnp.int32), 0)
    return jnp.where(present, key_idx, max_present)


def sample_task_duration(
    params: EnvParams, bank: WorkloadBank, u2: jnp.ndarray,
    facts: jnp.ndarray, template: jnp.ndarray, stage: jnp.ndarray,
    num_local: jnp.ndarray, task_valid: jnp.ndarray,
    same_stage: jnp.ndarray
) -> jnp.ndarray:
    """Sample one task duration, reproducing the reference's wave logic and
    try/except fallback chains (tpch.py:75-106):

    - executor idle (`task_valid` False — it was just sitting or moving):
      fresh_durations, else first_wave + warmup_delay;
    - executor continuing the same stage: rest_wave, else first_wave, else
      fresh_durations;
    - executor new to this stage: first_wave, else fresh_durations.

    A final fallback to the stage's rough mean duration replaces the
    reference's uncaught exception when a bucket is entirely empty.

    `facts` is the stage's word of `EnvState.duration_facts`
    (`pack_duration_facts` of the bank, row `template`, `stage`): the
    executor level and the wave are chosen from it alone, and the bank
    is read for what a draw decides: ONE element of `cnt` (the chosen
    bucket's size), ONE of `dur` (the pick) and the stage's rough
    duration. Until PR 50 the level read `level_present` and
    `max_present` and the wave three elements of `cnt`: on the chip a
    gather is serialised (12 ns an element, 1.5 to 2.2 us for 128
    lanes), and those reads were the heaviest operations of the fused
    bulk pass's early-exit loop (PERF.md, PR 50).

    `u2` is f32[2] of pre-drawn Uniform[0,1) variates (NOT a key):
    u2[0] drives the executor-level interpolation, u2[1] the
    within-bucket pick. Hot callers (`_apply_action` and the three bulk
    passes in env/core.py) draw one batched uniform per pass — the
    per-row key plumbing this replaces was ~31% of the flat micro-step
    on the CPU backend (round-5 ablation), with identical per-row
    distributions (rows were independently keyed before, independent
    uniforms now; `pick = floor(u*n)` matches randint's law)."""
    li = sample_executor_key(params, facts, u2[0], num_local)

    # does the level's bucket of each wave hold a sample (`cnt > 0`)
    at_level = facts >> (_FACTS_BUCKET_SHIFT + li).astype(jnp.uint32)
    has = [
        ((at_level >> (NUM_EXEC_LEVELS * w)) & 1).astype(bool)
        for w in range(3)
    ]
    fresh_i, first_i, rest_i = WAVE_FRESH, WAVE_FIRST, WAVE_REST

    # wave choice + warmup flag per the chains above
    idle_wave = jnp.where(has[fresh_i], fresh_i, first_i)
    idle_warm = ~has[fresh_i]
    same_wave = jnp.where(
        has[rest_i], rest_i, jnp.where(has[first_i], first_i, fresh_i)
    )
    diff_wave = jnp.where(has[first_i], first_i, fresh_i)

    wave = jnp.where(
        ~task_valid, idle_wave, jnp.where(same_stage, same_wave, diff_wave)
    )
    warm = jnp.where(~task_valid, idle_warm, False)

    cnt = bank.cnt[template, stage, wave, li]
    n = jnp.maximum(cnt, 1)
    pick = jnp.minimum((u2[1] * n).astype(jnp.int32), n - 1)
    dur = bank.dur[template, stage, wave, li, pick]
    if dur.dtype != jnp.float32:
        # low-precision bank layout (ISSUE 7): the gather stays narrow,
        # everything downstream accumulates in f32. Integer banks carry
        # a per-template LOG-domain dequantization scale (relative
        # error ~dur_scale/2 uniformly across the heavy tail — see
        # workload.quantize_bank); bf16 banks just upcast.
        dur = dur.astype(jnp.float32)
        if bank.dur_scale is not None:
            dur = jnp.expm1(dur * bank.dur_scale[template])
    dur = jnp.where(cnt > 0, dur, bank.rough_duration[template, stage])
    return dur + jnp.where(warm, params.warmup_delay, 0.0)
