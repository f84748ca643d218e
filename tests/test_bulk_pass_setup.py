"""PR 45: what the fused bulk pass (`core._bulk_events_fused`) sets up
before its loop: the frontier bits of the arrivals' destinations, read
from the frontier packed over its stage axis (`core._frontier_at`)
where a gather of one element an executor read them, and the pass's
uniform table, one pair a step where it held a pair a step and
executor."""

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
    handed, what `sample_task_duration` was asked beside it (template,
    stage, executors on the job, task valid, same stage), and the
    table as the pass drew it."""
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
    launched, dur, u, tmpl, stage, nl, tv, ss, table = (
        np.asarray(x) for x in steps
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
            jnp.asarray(x[launched]) for x in (tmpl, stage, nl, tv, ss)
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


@pytest.mark.parametrize("n", [10, 50])
def test_sampled_durations_equal_the_table_reading_sampler_s(
    monkeypatch, n
):
    """`sample_task_duration` over a grid of (template, stage,
    num_local, task valid, same stage, u2) on the synthetic bank
    equals, bit for bit, the sampler that read the bank's interval
    tables (`_table_executor_key`, the old `sample_executor_key`):
    every num_local in 0..N, every wave chain, stages with and without
    the level asked for, interpolation draws on both sides of every
    interval."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.workload import make_workload_bank, sampling

    params = EnvParams(num_executors=n, max_jobs=4)
    bank = make_workload_bank(n)
    itv = jnp.asarray(_interval_tables(n))

    rs = np.random.RandomState(47 + n)
    u0 = np.concatenate([[0.0, 0.999999], rs.rand(6)]).astype(np.float32)
    tpl, nl, tv, ss, ui = (g.ravel() for g in np.meshgrid(
        np.arange(0, bank.num_templates, 5), np.arange(n + 1),
        [False, True], [False, True], np.arange(u0.size), indexing="ij",
    ))
    stage = rs.randint(0, bank.max_stages, tpl.size)
    stage = stage % np.asarray(bank.num_stages)[tpl]
    u2 = np.stack([u0[ui], rs.rand(tpl.size).astype(np.float32)], -1)
    args = tuple(jnp.asarray(x) for x in (
        u2, tpl.astype(np.int32), stage.astype(np.int32),
        nl.astype(np.int32), tv, ss,
    ))

    def durations():
        return np.asarray(jax.jit(jax.vmap(
            lambda *a: sampling.sample_task_duration(params, bank, *a)
        ))(*args))

    got = durations()
    keys = np.asarray(jax.jit(jax.vmap(
        lambda u, t, s, k: sampling.sample_executor_key(
            params, bank, u, t, s, k)
    ))(args[0][:, 0], *args[1:4]))
    read = []

    def from_tables(params_, *a):
        read.append(params_.num_executors)
        return _table_executor_key(itv, *a)

    monkeypatch.setattr(sampling, "sample_executor_key", from_tables)
    want = durations()
    assert read == [n]
    np.testing.assert_array_equal(got, want)
    assert got.size > 2000 and (got > 0).all()
    # the grid reaches what it says: several levels, both sides of an
    # interval, the warm-up branch and plain ones
    assert len(np.unique(keys)) >= (2 if n == 10 else 4), np.unique(keys)
    assert len(np.unique(got)) > got.size // 20
