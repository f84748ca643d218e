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
