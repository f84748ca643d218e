"""PR 45: what the fused bulk pass (`core._bulk_events_fused`) sets up
before its loop: the frontier bits of the arrivals' destinations, read
from the frontier packed over its stage axis (`core._frontier_at`)
where a gather of one element an executor read them, and the pass's
uniform table, one pair a step where it held a pair a step and
executor. PR 47: the sampler's executor-level interval, computed.
PR 50: a stage's duration facts, one word a (job, stage) of the state
(`EnvState.duration_facts`), against the bank's tables it packs."""

from __future__ import annotations

import types

import numpy as np
import pytest


def _trail(max_jobs: int, init_jobs: int, steps: int):
    """Every `LoopState` along `steps` micro-steps of one dense episode
    at a job axis of `max_jobs`, the first `init_jobs` of them there at
    t=0 (8 executors, a short moving delay, the real duration sampler),
    stacked on a leading axis."""
    import jax

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import init_loop_state, micro_step
    from sparksched_tpu.schedulers import round_robin_policy
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(
        num_executors=8, max_jobs=max_jobs, max_stages=20, max_levels=20,
        moving_delay=700.0, warmup_delay=1000.0,
        job_arrival_rate=4e-5, mean_time_limit=None,
        num_init_jobs=init_jobs,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    @jax.jit
    def trail(s0, key):
        def body(ls, k):
            ls2 = micro_step(
                params, bank, pol, ls, k, auto_reset=False,
                fulfill_bulk=True,
            )
            return ls2, ls

        return jax.lax.scan(
            body, init_loop_state(s0), jax.random.split(key, steps)
        )[1]

    lss = trail(
        core.reset(params, bank, jax.random.PRNGKey(3)),
        jax.random.PRNGKey(0),
    )
    return params, bank, lss


@pytest.fixture(scope="module")
def trail20():
    return _trail(20, 1, 700)


@pytest.fixture(scope="module")
def trail200():
    # a batch of 150 jobs at t=0, so that the jobs in play lie far
    # along the job axis from the trail's first state on
    return _trail(200, 150, 500)


def _gathered(frontier, dj, ds):
    """The reference: the element gather `_frontier_at` replaced."""
    j_cap, s_cap = frontier.shape[-2:]
    djc = np.clip(dj, 0, j_cap - 1)
    dsc = np.clip(ds, 0, s_cap - 1)
    if frontier.ndim == 2:
        return frontier[djc, dsc]
    return frontier[np.arange(frontier.shape[0])[:, None], djc, dsc]


@pytest.mark.parametrize("job_axis", [20, 200])
def test_frontier_at_equals_the_gather_along_an_episode(
    request, job_axis
):
    """`_frontier_at(state, dj, ds)` is `state.frontier[dj, ds]` (both
    clipped) for the destinations the three bulk passes look up, on
    every state along an episode: executors moving to a stage, parked,
    and bound for the common pool (`dj` -1), destinations on and off
    the frontier, the commitment slots' destinations as
    `_bulk_fulfill` reads them; at a job axis of 20 and of 200 (a
    batch of 150 jobs in play)."""
    import jax

    from sparksched_tpu.env import core

    _, _, lss = request.getfixturevalue(f"trail{job_axis}")
    env = lss.env
    at = jax.jit(jax.vmap(core._frontier_at))
    frontier = np.asarray(env.frontier)
    assert frontier.shape[1:] == (job_axis, 20)
    seen = []
    for dj, ds in (
        (env.exec_dst_job, env.exec_dst_stage),
        (env.cm_dst_job, env.cm_dst_stage),
    ):
        got = np.asarray(at(env, dj, ds))
        dj, ds = np.asarray(dj), np.asarray(ds)
        np.testing.assert_array_equal(got, _gathered(frontier, dj, ds))
        seen.append((dj, ds, got))
    dj, ds, got = seen[0]
    moving = np.asarray(env.exec_moving)
    assert (moving & got).sum() > 20 and ((dj >= 0) & ~got).sum() > 20
    assert (dj == -1).any() and (seen[1][0] == -1).any()
    assert np.asarray(env.exec_at_common).any()
    assert (seen[1][2] & np.asarray(env.cm_valid)).sum() > 20
    if job_axis == 200:
        # the policy works the batch in order, so the trail's own
        # destinations lie in the first thirty jobs: ask for the same
        # stages of jobs spread along the whole axis as well
        far = np.where(dj >= 0, (dj * 7 + 3) % job_axis, dj)
        got = np.asarray(at(env, far, ds))
        np.testing.assert_array_equal(got, _gathered(frontier, far, ds))
        assert far.max() > 150 and got.sum() > 200 and (~got).sum() > 200


@pytest.mark.parametrize(
    "j_cap,s_cap", [(4, 20), (7, 40), (3, 33), (5, 64), (2, 96)]
)
def test_frontier_at_equals_the_gather_at_any_stage_axis(j_cap, s_cap):
    """The same on made-up frontiers whose stage axis takes one, two
    and three 32-bit words (W = ceil(S / 32)), full and ragged, with
    every (job, stage) asked for and indices out of range on both
    sides (an index clips)."""
    import jax.numpy as jnp

    from sparksched_tpu.env import core

    rs = np.random.RandomState(s_cap)
    frontier = rs.rand(j_cap, s_cap) < 0.4
    state = types.SimpleNamespace(
        stage_exists=jnp.ones((j_cap, s_cap), bool),
        frontier=jnp.asarray(frontier),
    )
    jj, ss = np.meshgrid(
        np.arange(-2, j_cap + 2), np.arange(-2, s_cap + 2), indexing="ij"
    )
    dj, ds = jj.ravel().astype(np.int32), ss.ravel().astype(np.int32)
    got = np.asarray(
        core._frontier_at(state, jnp.asarray(dj), jnp.asarray(ds))
    )
    assert got.dtype == bool and got.any() and not got.all()
    np.testing.assert_array_equal(got, _gathered(frontier, dj, ds))
    assert core._pack_stage_sets(state.frontier).shape == (
        j_cap, -(-s_cap // 32)
    )


# ---------------------------------------------------------------------
# the uniform table: one pair a step
# ---------------------------------------------------------------------


def _recorded_passes(params, bank, envs, on, max_events):
    """`core._bulk_events_fused` under `jit(vmap)` over stacked states,
    run as the fixed scan over its own `step_fn`, with what each step
    did handed out beside the pass's result: whether it launched a
    task, the duration the launch stored, the pair the step was
    handed, what `sample_task_duration` was asked beside it (the
    stage's word of duration facts, template, stage, executors on the
    job, task valid, same stage), and the table as the pass drew it."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env import core

    real = core.sample_task_duration
    asked, record = [], []

    def sampler(params_, bank_, u2, *args):
        asked.append((u2,) + args)
        return real(params_, bank_, u2, *args)

    def runner(step_fn, carry0, us, lane_axis=None):
        def body(c, u):
            c2 = step_fn(c, u, True)
            launched = c2[12] != c[12]  # the seq counter: one a launch
            spot = c[5] - c2[5]  # the stage whose remaining tasks fell
            dur = (c2[8] * spot).sum()  # the duration stored for it
            return c2, (launched, dur) + asked.pop()

        carry, steps = jax.lax.scan(body, carry0, us)
        record.append(steps + (us,))
        return carry, 0

    def one(env, enabled):
        out = core._bulk_events_fused(
            params, bank, env, enabled, stop_at_limit=True,
            max_events=max_events,
        )
        return out[:4], record.pop()

    saved = core._steps_while_active
    core._steps_while_active, core.sample_task_duration = runner, sampler
    try:
        return jax.jit(jax.vmap(one))(envs, jnp.asarray(on))
    finally:
        core._steps_while_active, core.sample_task_duration = saved, real


def test_pass_hands_a_step_its_own_pair_and_durations_keep_their_law(
    trail20,
):
    """The pass draws ONE table of `[max_events + N, 2]` uniforms from
    the lane's key and hands step i row i, whichever executor's event
    the step takes. So no two steps of a lane and no two lanes share a
    pair; the duration a launch stores is `sample_task_duration` of
    the step's own pair and of what the engine asks for (exactly); and
    over some thousands of launches along an episode those durations
    have the mean and the quartiles of fresh direct draws for the same
    launches: which row a launch reads depends on earlier rows alone,
    so the pairs consumed are i.i.d. U[0,1)^2, as a pair a step and
    executor was."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import M_EVENT

    params, bank, lss = trail20
    reps, max_events = 4, 8
    length = max_events + params.num_executors
    on = np.tile(np.asarray(lss.mode) == M_EVENT, reps)
    envs = jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a] * reps), lss.env
    )
    lanes = on.shape[0]
    # a key of its own for every lane (a trail's states share theirs
    # from one micro-step to the next)
    envs = envs.replace(rng=jax.vmap(jax.random.fold_in)(
        envs.rng, jnp.arange(lanes)
    ))
    (after, k_rel, k_rdy, _), steps = _recorded_passes(
        params, bank, envs, on, max_events
    )
    launched, dur, u, facts, tmpl, stage, nl, tv, ss, table = (
        np.asarray(x) for x in steps
    )
    # the word the step picked by its one-hot over [J,S] is the one of
    # the (template, stage) it asked the bank's tables for
    from sparksched_tpu.workload.sampling import pack_duration_facts

    np.testing.assert_array_equal(
        facts[launched],
        np.asarray(pack_duration_facts(bank))[
            tmpl[launched], stage[launched]],
    )

    # the table: the lane's own, [length, 2], row i to step i
    halves = jax.vmap(jax.random.split)(envs.rng)
    assert table.shape == (lanes, length, 2)
    np.testing.assert_array_equal(table, np.asarray(jax.vmap(
        lambda k: jax.random.uniform(k, (length, 2))
    )(halves[:, 1])))
    np.testing.assert_array_equal(u, table)
    took = (np.asarray(k_rel) + np.asarray(k_rdy)) > 0
    assert took.sum() > lanes // 4 and not took[~on].any()
    np.testing.assert_array_equal(
        np.asarray(after.rng),
        np.where(took[:, None], np.asarray(halves[:, 0]),
                 np.asarray(envs.rng)),
    )

    # launches: a pair each, shared with no other step or lane
    n = int(launched.sum())
    assert n > 2000 and launched.sum(1).max() >= 6, (n, launched.sum(1))
    assert not launched[~on].any()
    pairs = table[launched]
    assert len(np.unique(pairs, axis=0)) == n
    # (single numbers meet by chance: 23-bit draws, a dozen of 15,000)
    assert len(np.unique(pairs.ravel())) > 2 * n - 30

    # the stored duration is the sampler's, of the step's own pair
    def direct(u2):
        return np.asarray(jax.jit(jax.vmap(
            lambda *a: core.sample_task_duration(params, bank, *a)
        ))(jnp.asarray(u2), *(
            jnp.asarray(x[launched])
            for x in (facts, tmpl, stage, nl, tv, ss)
        )))

    np.testing.assert_array_equal(dur[launched], direct(pairs))
    assert (dur[launched] > 0).all() and (dur[~launched] == 0).all()

    # the law: fresh pairs for the same launches, eight times over
    fresh = np.concatenate([
        direct(jax.random.uniform(jax.random.PRNGKey(450 + i), (n, 2)))
        for i in range(8)
    ])
    got, want = np.log(dur[launched]), np.log(fresh)
    assert abs(got.mean() - want.mean()) < 0.03, (got.mean(), want.mean())
    assert abs(got.std() - want.std()) < 0.03, (got.std(), want.std())
    for q in (0.1, 0.25, 0.5, 0.75, 0.9):
        a, b = np.quantile(got, q), np.quantile(want, q)
        assert abs(a - b) < 0.05, (q, a, b)
    # and the pairs consumed are uniform on the unit square
    assert np.abs(pairs.mean(0) - 0.5).max() < 0.02, pairs.mean(0)
    assert abs(np.corrcoef(pairs.T)[0, 1]) < 0.05
    for q in (0.25, 0.5, 0.75):
        assert np.abs(np.quantile(pairs, q, axis=0) - q).max() < 0.03


# ---------------------------------------------------------------------
# PR 47: the sampler's executor-level interval, computed and not read
# ---------------------------------------------------------------------


def _table_executor_key(itv, bank, u, template, stage, num_local):
    """The reference: `sample_executor_key` as it was until PR 47,
    reading row `num_local` of the four `i32[N+1]` interval tables the
    bank then held (`itv`: left value, right value, left index, right
    index)."""
    import jax.numpy as jnp

    left_v, right_v, left_i, right_i = (t[num_local] for t in itv)
    rand_pt = 1 + (u * (right_v - left_v)).astype(jnp.int32)
    use_left = (left_v == right_v) | (rand_pt <= num_local - left_v)
    key_idx = jnp.where(use_left, left_i, right_i)
    key_val = jnp.where(use_left, left_v, right_v)
    present = bank.level_present[template, stage, key_idx] & (key_val > 0)
    return jnp.where(present, key_idx, bank.max_present[template, stage])


def _interval_tables(num_executors: int) -> np.ndarray:
    """The four tables as `pack_bank` built them: `i32[4, N+1]`."""
    from sparksched_tpu.workload.bank import _executor_intervals, _to_idx

    itv = _executor_intervals(num_executors)
    return np.concatenate([itv, _to_idx(itv)], axis=1).T.astype(np.int32)


def _table_task_duration(
    itv, params, bank, u2, template, stage, num_local, task_valid,
    same_stage,
):
    """The reference: `sample_task_duration` as it read the bank until
    PR 50 (and, through `_table_executor_key`, until PR 47): the
    level's presence from `bank.level_present`, the fallback from
    `bank.max_present`, the wave from the three counts
    `bank.cnt[t, s, :, li]`."""
    import jax.numpy as jnp

    from sparksched_tpu.workload.bank import (
        WAVE_FIRST, WAVE_FRESH, WAVE_REST,
    )

    li = _table_executor_key(itv, bank, u2[0], template, stage, num_local)
    cnt = bank.cnt[template, stage, :, li]  # i32[3]
    has = cnt > 0
    idle_wave = jnp.where(has[WAVE_FRESH], WAVE_FRESH, WAVE_FIRST)
    idle_warm = ~has[WAVE_FRESH]
    same_wave = jnp.where(
        has[WAVE_REST], WAVE_REST,
        jnp.where(has[WAVE_FIRST], WAVE_FIRST, WAVE_FRESH),
    )
    diff_wave = jnp.where(has[WAVE_FIRST], WAVE_FIRST, WAVE_FRESH)
    wave = jnp.where(
        ~task_valid, idle_wave, jnp.where(same_stage, same_wave, diff_wave)
    )
    warm = jnp.where(~task_valid, idle_warm, False)
    n = jnp.maximum(cnt[wave], 1)
    pick = jnp.minimum((u2[1] * n).astype(jnp.int32), n - 1)
    dur = bank.dur[template, stage, wave, li, pick]
    if dur.dtype != jnp.float32:
        dur = dur.astype(jnp.float32)
        if bank.dur_scale is not None:
            dur = jnp.expm1(dur * bank.dur_scale[template])
    dur = jnp.where(
        cnt[wave] > 0, dur, bank.rough_duration[template, stage]
    )
    return dur + jnp.where(warm, params.warmup_delay, 0.0)


def table_reading_sampler(num_executors: int):
    """`_table_task_duration` under `sample_task_duration`'s signature
    (the stage's word of duration facts is taken and not read): what a
    test patches in for `core.sample_task_duration` /
    `sampling.sample_task_duration` to run a program as its parent
    sampled."""
    import jax.numpy as jnp

    itv = jnp.asarray(_interval_tables(num_executors))

    def sampler(params, bank, u2, facts, *args):
        assert params.num_executors == num_executors
        sampler.traced += 1
        return _table_task_duration(itv, params, bank, u2, *args)

    sampler.traced = 0  # the calls a trace made: that it WAS patched in
    return sampler


@pytest.mark.parametrize("n", [1, 4, 5, 6, 10, 50, 100, 101, 120])
def test_executor_interval_is_the_reference_table_row_for_row(n):
    """`sampling.executor_interval(N, num_local)` is row `num_local` of
    `_executor_intervals(N)` and `_to_idx` of it, for every num_local
    in 0..N (the zeroed last row above 100 executors included) and,
    like the clamped gather it replaced, the nearest row outside; the
    runs it is computed from tile 0..N, differ from one to the next,
    and are few."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.workload.bank import executor_interval_runs
    from sparksched_tpu.workload.sampling import executor_interval

    want = _interval_tables(n)
    nl = np.arange(-2, n + 3, dtype=np.int32)
    got = np.stack([np.asarray(x) for x in jax.jit(
        lambda a: executor_interval(n, a)
    )(jnp.asarray(nl))])
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want[:, np.clip(nl, 0, n)])
    # a scalar, as the unbatched callers ask
    for k in (0, n // 2, n):
        one = executor_interval(n, jnp.int32(k))
        assert [int(x) for x in one] == list(want[:, k])

    starts, rows = executor_interval_runs(n)
    assert starts[0] == 0 and list(starts) == sorted(set(starts))
    assert starts[-1] <= n and len(starts) == len(rows) <= 17
    assert all(a != b for a, b in zip(rows, rows[1:]))
    for start, end, row in zip(starts, starts[1:] + (n + 1,), rows):
        assert (want[:, start:end] == np.array(row)[:, None]).all()
    if n > 100:
        assert rows[-1] == (0, 0, 0, 0) and starts[-1] == n
    assert len(starts) == {10: 3, 50: 9, 100: 15, 120: 16}.get(
        n, len(starts))


def test_a_wrong_run_list_fails_the_law(monkeypatch):
    """The law test above can fail: with one run's start moved by one
    (the (5, 10) run of a 10-executor cluster starting at 7), the
    lookup parts from the table at num_local 6."""
    import jax.numpy as jnp

    from sparksched_tpu.workload import sampling

    starts, rows = sampling.executor_interval_runs(10)
    assert starts == (0, 6, 10)
    monkeypatch.setattr(
        sampling, "executor_interval_runs", lambda n: ((0, 7, 10), rows)
    )
    got = np.stack([np.asarray(x) for x in sampling.executor_interval(
        10, jnp.arange(11, dtype=jnp.int32)
    )])
    unequal = (got != _interval_tables(10)).any(axis=0)
    assert list(np.flatnonzero(unequal)) == [6]


def _thinned(templates, seed: int):
    """The templates with levels dropped from every stage's waves at
    random: some first-wave levels (a stage in eight keeps none),
    other fresh and rest levels, whole waves now and then. What the
    sampler's fallback chains are for, and what the TPC-H-like bank
    (every level present in every wave) never asks of them."""
    import copy

    rng = np.random.default_rng(seed)
    out = copy.deepcopy(templates)
    for tpl in out:
        for waves in tpl["durations"].values():
            for name in ("fresh_durations", "first_wave", "rest_wave"):
                have = sorted(waves.get(name, {}))
                keep = rng.random(len(have)) < rng.choice(
                    [0.0, 0.3, 0.6, 1.0], p=[0.125, 0.375, 0.375, 0.125])
                waves[name] = {
                    lv: waves[name][lv]
                    for lv, k in zip(have, keep) if k
                }
    return out


def sparse_bank(num_executors: int, max_stages: int = 20):
    """The TPC-H-like templates thinned (`_thinned`), packed: a bank
    with executor levels and whole waves missing."""
    from sparksched_tpu.workload import pack_bank
    from sparksched_tpu.workload.synthetic import make_templates

    return pack_bank(
        _thinned(make_templates(seed=50, bucket_size=16), 50),
        num_executors, max_stages, 16,
    )


@pytest.fixture(scope="module")
def banks():
    """The banks the word is held on: `tpch` (`make_workload_bank`'s
    own: the TPC-H traces where they are on disk, else the TPC-H-like
    bank, 154 templates), `sparse` (its templates thinned, so that
    levels and whole waves are missing), and each rebuilt as the
    benchmark's sweep drivers rebuild theirs for the engine comparison
    (`sweep_chunks.fixed_durations`: `bank.replace(dur, cnt,
    level_present, dur_scale=None)`, `max_present` left as it was)."""
    from benchmarks.drivers.sweep_chunks import fixed_durations
    from sparksched_tpu.workload import make_workload_bank

    tpch = make_workload_bank(10)
    sparse = sparse_bank(10, tpch.max_stages)
    return {
        "tpch": tpch, "sparse": sparse,
        "tpch_fixed": fixed_durations(tpch)[0],
        "sparse_fixed": fixed_durations(sparse)[0],
    }


@pytest.mark.parametrize(
    "name", ["tpch", "sparse", "tpch_fixed", "sparse_fixed"])
def test_duration_facts_are_the_tables_they_pack(banks, name):
    """`sampling.pack_duration_facts(bank)` against the bank's tables,
    for every (template, stage), padding stages too: bits 0 to 7 are
    `level_present` bit for bit, bit 8 + 8 w + l is `cnt[t, s, w, l] >
    0`, and the highest set bit of the first byte (`lax.clz`, as the
    sampler takes it) is `max_present`, 0 where no level is present,
    on a bank as `pack_bank` makes it. On a bank rebuilt by
    `bank.replace(...)` the word follows the rebuilt tables (it is
    computed in the program from the bank handed in), where
    `bank.max_present` is the old bank's: there the derived one is the
    rebuilt presence's highest bit, and every level of a bucket holds
    one duration, so the level is not read (the durations' test
    below)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from sparksched_tpu.workload.sampling import pack_duration_facts

    bank = banks[name]
    facts = np.asarray(jax.jit(pack_duration_facts)(bank))
    present = np.asarray(bank.level_present)
    cnt = np.asarray(bank.cnt)
    assert facts.dtype == np.uint32 and facts.shape == present.shape[:2]
    assert present.shape[2] == 8 and cnt.shape[2:] == (3, 8)
    for lv in range(8):
        np.testing.assert_array_equal(
            (facts >> lv) & 1, present[:, :, lv], err_msg=str(lv))
        for w in range(3):
            np.testing.assert_array_equal(
                (facts >> (8 + 8 * w + lv)) & 1, cnt[:, :, w, lv] > 0,
                err_msg=str((w, lv)))
    derived = np.asarray(jnp.maximum(
        31 - lax.clz(jnp.asarray(facts & 0xFF)).astype(jnp.int32), 0))
    by_hand = np.where(
        present.any(-1), 7 - np.argmax(present[:, :, ::-1], -1), 0)
    np.testing.assert_array_equal(derived, by_hand)
    if not name.endswith("_fixed"):
        np.testing.assert_array_equal(
            derived, np.asarray(bank.max_present))
    real = np.arange(facts.shape[1]) < np.asarray(bank.num_stages)[:, None]
    if name == "sparse":
        # the thinning reaches what it says: stages with no level,
        # with some, with all; every bucket bit both ways
        levels = facts[real] & 0xFF
        assert (levels == 0).sum() > 50 and (levels == 0xFF).sum() > 50
        assert len(np.unique(levels)) > 100
        assert np.bitwise_or.reduce(facts[real]) == 0xFFFFFFFF
        assert np.bitwise_and.reduce(facts[real]) == 0
    if name == "sparse_fixed":
        assert set(np.unique(facts[real] & 0xFF)) == {0xFF}
        assert (np.asarray(bank.max_present)[real] != 7).sum() > 100


@pytest.mark.parametrize("how", ["reset", "reseed"])
def test_a_lane_holds_the_facts_of_its_own_templates(banks, how):
    """`EnvState.duration_facts` is row `job_template[j]` of the pack,
    for every job slot (a slot no job arrives in holds its template's
    too: the tables were read there alike), wherever a job's template
    is written: `core.reset`, and the streaming re-seed, which gives
    the lanes that ended a fresh episode's templates and must give
    them its words, and leave the others' alone."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import _reseed_ended, init_loop_state
    from sparksched_tpu.workload.sampling import pack_duration_facts

    bank = banks["sparse"]
    params = EnvParams(
        num_executors=4, max_jobs=8, max_stages=bank.max_stages,
        max_levels=bank.max_stages, moving_delay=700.0,
        warmup_delay=1000.0, job_arrival_rate=4e-5, mean_time_limit=None,
    )
    pack = np.asarray(pack_duration_facts(bank))

    def holds(envs):
        tpl = np.asarray(envs.job_template)
        assert len(np.unique(tpl)) > 8
        np.testing.assert_array_equal(
            np.asarray(envs.duration_facts), pack[tpl])
        return tpl

    keys = jax.random.split(jax.random.PRNGKey(50), 4)
    envs = jax.vmap(lambda k: core.reset(params, bank, k))(keys)
    tpl0 = holds(envs)
    assert np.asarray(envs.duration_facts).dtype == np.uint32
    if how == "reset":
        return
    ended = jnp.asarray([True, False, True, False])
    ls = jax.vmap(init_loop_state)(envs)
    ls = ls.replace(episodes=ls.episodes + ended.astype(jnp.int32))
    out = jax.jit(jax.vmap(
        lambda l, e, k: _reseed_ended(
            params, bank, l, e, k, None, "lanes"
        ),
        axis_name="lanes",
    ))(ls, ended, jax.random.split(jax.random.PRNGKey(51), 4))
    tpl1 = holds(out.env)
    np.testing.assert_array_equal(
        (tpl1 != tpl0).any(1), np.asarray(ended))


def _duration_grid(bank, n: int, seed: int):
    """(u2, facts, template, stage, num_local, task valid, same stage)
    over EVERY (template, stage) of the bank (padding stages too),
    every num_local in 0..N, both flags both ways and a grid of
    interpolation draws (both ends of [0, 1) among them), a fresh
    pick draw each."""
    import jax.numpy as jnp

    from sparksched_tpu.workload.sampling import pack_duration_facts

    rs = np.random.RandomState(seed)
    u0 = np.concatenate([[0.0, 0.999999], rs.rand(4)]).astype(np.float32)
    tpl, stage, nl, tv, ss, ui = (g.ravel() for g in np.meshgrid(
        np.arange(bank.num_templates), np.arange(bank.max_stages),
        np.arange(n + 1), [False, True], [False, True],
        np.arange(u0.size), indexing="ij",
    ))
    u2 = np.stack([u0[ui], rs.rand(tpl.size).astype(np.float32)], -1)
    facts = np.asarray(pack_duration_facts(bank))[tpl, stage]
    return tuple(jnp.asarray(x) for x in (
        u2, facts, tpl.astype(np.int32), stage.astype(np.int32),
        nl.astype(np.int32), tv, ss,
    ))


@pytest.mark.parametrize("n,name,dtype", [
    (10, "tpch", "f32"), (50, "tpch", "f32"),
    (10, "sparse", "f32"), (50, "sparse", "f32"),
    (120, "sparse", "f32"), (10, "sparse", "int8"),
    (50, "tpch", "int8"), (10, "sparse_fixed", "f32"),
])
def test_sampled_durations_equal_the_table_reading_sampler_s(
    banks, n, name, dtype
):
    """`sample_task_duration`, handed the stage's word of duration
    facts, equals bit for bit the sampler that read the bank's tables
    (`_table_task_duration`: the four interval tables until PR 47,
    `level_present`, `max_present` and the three counts until PR 50),
    over EVERY (template, stage, num_local in 0..N, task valid, same
    stage) on a grid of draws: on the TPC-H(-like) bank, on a bank
    with levels and whole waves missing (every fallback chain, the
    warm-up branch, the rough duration of an empty bucket, the zeroed
    level above 100 executors), float32 and int8 (`quantize_bank`),
    and on a bank rebuilt as the benchmark's engine comparison rebuilds
    it, whose `max_present` is stale and whose levels all hold one
    duration."""
    import jax

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.workload import quantize_bank, sampling

    params = EnvParams(num_executors=n, max_jobs=4)
    bank = quantize_bank(banks[name], dtype)
    assert (bank.dur_scale is not None) == (dtype == "int8")
    args = _duration_grid(bank, n, 47 + n)

    got = np.asarray(jax.jit(jax.vmap(
        lambda *a: sampling.sample_task_duration(params, bank, *a)
    ))(*args))
    reference = table_reading_sampler(n)
    want = np.asarray(jax.jit(jax.vmap(
        lambda *a: reference(params, bank, *a)
    ))(*args))
    np.testing.assert_array_equal(got, want)
    assert got.size == bank.num_templates * bank.max_stages * (n + 1) * 24
    real = np.asarray(args[3]) < np.asarray(bank.num_stages)[
        np.asarray(args[2])]
    assert (got[real] > 0).all() and not real.all()

    keys = np.asarray(jax.jit(jax.vmap(
        lambda u2, f, k: sampling.sample_executor_key(
            params, f, u2[0], k)
    ))(args[0], args[1], args[4]))
    # the grid reaches what it says: several levels, both sides of an
    # interval, the warm-up branch and plain ones
    assert len(np.unique(keys)) >= (2 if n == 10 else 4), np.unique(keys)
    if not name.endswith("_fixed") and dtype == "f32":
        assert len(np.unique(got)) > got.size // 200
    if name == "sparse":
        rough = np.asarray(bank.rough_duration)[
            np.asarray(args[2]), np.asarray(args[3])]
        warm = got == rough + params.warmup_delay
        assert (real & (got == rough)).sum() > 1000  # an empty bucket
        assert (real & warm).sum() > 1000  # ... on an idle executor
        assert len(np.unique(keys)) == 8


@pytest.mark.parametrize("how", ["another stage's word", "a bit off"])
def test_a_wrong_fact_fails_the_durations_test(banks, monkeypatch, how):
    """The test above can fail: handed the word of the NEXT stage, or
    reading the bucket bits one place off (`_FACTS_BUCKET_SHIFT` 9),
    the sampler parts from the table-reading one on the thinned
    bank."""
    import jax

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.workload import sampling

    params = EnvParams(num_executors=10, max_jobs=4)
    bank = banks["sparse"]
    args = _duration_grid(bank, 10, 57)
    reference = table_reading_sampler(10)
    want = np.asarray(jax.jit(jax.vmap(
        lambda *a: reference(params, bank, *a)))(*args))
    if how == "a bit off":
        monkeypatch.setattr(sampling, "_FACTS_BUCKET_SHIFT", 9)
    else:
        args = (args[0], np.roll(np.asarray(args[1]), 11 * 24)) + args[2:]
    got = np.asarray(jax.jit(jax.vmap(
        lambda *a: sampling.sample_task_duration(params, bank, *a)
    ))(*args))
    assert (got != want).mean() > 0.05
