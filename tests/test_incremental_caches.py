"""The env core maintains saturation/frontier/commitment/moving caches
incrementally (updated at mutation points) because recomputing them with
scatters and [J,S,S] reductions on every access dominated TPU time. This
test drives full episodes and asserts every cache equals its golden
recomputation after every step."""

from __future__ import annotations

import numpy as np
import pytest

from .reference_fixtures import (
    make_tpu_env_state,
    parent_sets_by_hand,
    spec_multi_job,
)


def test_incremental_caches_match_golden():
    import jax.numpy as jnp

    from sparksched_tpu.env import core
    from sparksched_tpu.env.observe import observe
    from sparksched_tpu.schedulers import random_policy
    import jax

    spec = spec_multi_job(num_jobs=4, seed=23)
    num_exec = 5
    params, bank, state = make_tpu_env_state(spec, num_exec)
    rng = jax.random.PRNGKey(3)

    for step in range(2000):
        if bool(state.terminated):
            break
        obs = observe(params, state)
        rng, sub = jax.random.split(rng)
        si, ne = random_policy(sub, obs)
        state, _, _, _ = core.step(params, bank, state, si, ne)

        sat = np.asarray(state.stage_saturated)
        ex = np.asarray(state.stage_exists)
        adj = np.asarray(state.adj)
        golden_upc = (adj & (~sat & ex)[:, :, None]).sum(axis=1)
        np.testing.assert_array_equal(
            np.asarray(state.stage_sat), sat,
            err_msg=f"stage_sat diverged at step {step}",
        )
        np.testing.assert_array_equal(
            np.asarray(state.unsat_parent_count), golden_upc,
            err_msg=f"unsat_parent_count diverged at step {step}",
        )
        np.testing.assert_array_equal(
            np.asarray(state.frontier),
            np.asarray(state.frontier_golden),
            err_msg=f"frontier diverged at step {step}",
        )
        np.testing.assert_array_equal(
            np.asarray(state.commit_count),
            np.asarray(state.commit_count_to_stage),
            err_msg=f"commit_count diverged at step {step}",
        )
        np.testing.assert_array_equal(
            np.asarray(state.moving_count),
            np.asarray(state.moving_count_to_stage),
            err_msg=f"moving_count diverged at step {step}",
        )
        np.testing.assert_array_equal(
            np.asarray(state.node_level),
            np.asarray(state.node_level_golden),
            err_msg=f"node_level diverged at step {step}",
        )
        # the observation view of the cache must equal the full
        # [J,S,S] recomputation it replaced (masked to active jobs)
        np.testing.assert_array_equal(
            np.asarray(observe(params, state).node_level),
            np.asarray(core.compute_node_levels(params, state)),
            err_msg=f"observed node_level diverged at step {step}",
        )
    assert bool(state.terminated), "episode did not terminate"


@pytest.mark.parametrize("bulk_events", [2, 8])
@pytest.mark.parametrize("moving_delay", [0.0, 700.0])
def test_saturation_caches_match_golden_through_the_fused_pass(
    moving_delay, bulk_events
):
    """`core.step` above runs the unfused pass pair, so nothing there
    recomputes the caches `core._bulk_events_fused` writes. Here whole
    episodes go through the flat engine with the fused pass on, and
    after EVERY micro-step `unsat_parent_count` and `stage_sat` equal
    their recomputation from the adjacency and the demand: with
    executors that arrive at once (every start is a direct one) and
    with a moving delay (arrivals, parks and relaunches interleave in
    one pass), under a budget most runs exhaust and one few do."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import init_loop_state, micro_step
    from sparksched_tpu.schedulers import round_robin_policy
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(
        num_executors=6, max_jobs=10, max_stages=20, max_levels=20,
        moving_delay=moving_delay, warmup_delay=1000.0,
        job_arrival_rate=4e-5, mean_time_limit=None,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    @jax.jit
    def episode(key):
        k_reset, k_run = jax.random.split(key)

        def body(ls, k):
            ls = micro_step(
                params, bank, pol, ls, k, auto_reset=False,
                bulk_events=bulk_events, fulfill_bulk=True,
                bulk_fused=True,
            )
            env = ls.env
            open_parent = ~env.stage_saturated & env.stage_exists
            golden = (env.adj & open_parent[:, :, None]).sum(1)
            return ls, (
                (env.unsat_parent_count != golden).any(),
                (env.stage_sat != env.stage_saturated).any(),
                ls.bulked, env.stage_sat.sum(),
            )

        ls0 = init_loop_state(core.reset(params, bank, k_reset))
        ls, per_step = jax.lax.scan(
            body, ls0, jax.random.split(k_run, 900)
        )
        return ls.env.all_jobs_complete, per_step

    done, (bad_unsat, bad_sat, bulked, n_sat) = jax.vmap(episode)(
        jax.random.split(jax.random.PRNGKey(11), 3)
    )
    assert not np.asarray(bad_unsat).any(), np.argwhere(bad_unsat)[:3]
    assert not np.asarray(bad_sat).any(), np.argwhere(bad_sat)[:3]
    bulked, n_sat = np.asarray(bulked), np.asarray(n_sat)
    # the fused pass did the work, and stages saturated inside micro-
    # steps in which it consumed events
    took = np.diff(bulked, axis=1) > 0
    assert bulked[:, -1].min() > 100, bulked[:, -1]
    assert (took & (np.diff(n_sat, axis=1) > 0)).sum() > 20
    assert np.asarray(done).all()


@pytest.mark.parametrize("how", ["packed", "reset", "reseed"])
def test_parent_sets_equal_their_recomputation(how):
    """The packed copy of the adjacency the fused pass reads (PR 39) is
    what the adjacency says, wherever one is written: `pack_parents`
    itself on made-up adjacencies of one, two and three words a set;
    `core.reset`; and the streaming re-seed (`_reseed_ended`), which
    gives the lanes that ended a fresh episode's adjacency and must
    give them its parent sets too, and leave the others' alone."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import (
        _reseed_ended,
        init_loop_state,
    )
    from sparksched_tpu.workload import make_workload_bank

    if how == "packed":
        rng = np.random.default_rng(5)
        for j_cap, s_cap in [(3, 1), (4, 20), (2, 32), (3, 33), (2, 70)]:
            adj = rng.random((j_cap, s_cap, s_cap)) < 0.3
            got = np.asarray(jax.jit(core.pack_parents)(jnp.asarray(adj)))
            assert got.dtype == np.uint32
            np.testing.assert_array_equal(
                got, parent_sets_by_hand(adj), err_msg=str(s_cap)
            )
        return

    params = EnvParams(
        num_executors=4, max_jobs=8, max_stages=20, max_levels=20,
        moving_delay=700.0, warmup_delay=1000.0,
        job_arrival_rate=4e-5, mean_time_limit=None,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )
    keys = jax.random.split(jax.random.PRNGKey(21), 4)
    envs = jax.vmap(lambda k: core.reset(params, bank, k))(keys)
    adj0 = np.asarray(envs.adj)
    assert adj0.any((1, 2, 3)).all()  # every lane has edges to pack
    np.testing.assert_array_equal(
        np.asarray(envs.parent_sets), parent_sets_by_hand(adj0)
    )
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
    ))(ls, ended, jax.random.split(jax.random.PRNGKey(22), 4))
    adj1 = np.asarray(out.env.adj)
    changed = (adj1 != adj0).any((1, 2, 3))
    np.testing.assert_array_equal(changed, np.asarray(ended))
    np.testing.assert_array_equal(
        np.asarray(out.env.parent_sets), parent_sets_by_hand(adj1)
    )
