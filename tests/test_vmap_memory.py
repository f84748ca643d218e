"""Regression guard: under `jax.vmap`, the shared workload bank must never
be broadcast across the batch dimension.

jax's cond/switch batching rule broadcasts ALL operands when the predicate
is lane-dependent ("we broadcast the input operands for simplicity",
jax _src/lax/control_flow/conditionals.py) — so any event-loop branch that
closes over the bank's duration tables materializes
batch x [T,S,3,L,K] floats (~38GB at 1024 lanes). The env core is
phase-split specifically to prevent that (env/core.py structural note);
this test fails if a future change reintroduces a bank-closure under a
batched conditional."""

from __future__ import annotations

import re

import pytest


@pytest.fixture(scope="module")
def setup():
    import jax

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(num_executors=10, max_jobs=20, max_stages=20,
                       max_levels=20)
    bank = make_workload_bank(params.num_executors, params.max_stages)
    B = 4
    states = jax.vmap(lambda k: core.reset(params, bank, k))(
        jax.random.split(jax.random.PRNGKey(0), B)
    )
    return params, bank, states, B


def _batched_bank_shapes(txt: str, bank, batch: int) -> list[str]:
    t, s = bank.num_stages.shape[0], bank.max_stages
    suspicious = [
        rf"\[{batch},{t},{s},3,\d+,\d+\]",  # dur
        rf"\[{batch},{t},{s},3,\d+\]",  # cnt
        rf"\[{batch},{t},{s},{s}\]",  # adj
    ]
    return [p for p in suspicious if re.search(p, txt)]


def test_vmapped_step_does_not_broadcast_bank(setup):
    import jax

    from sparksched_tpu.env import core
    from sparksched_tpu.env.observe import observe
    from sparksched_tpu.schedulers.heuristics import round_robin_policy

    params, bank, states, B = setup

    def lane(state):
        obs = observe(params, state)
        si, ne = round_robin_policy(obs, params.num_executors, True)
        nxt, _, _, _ = core.step(params, bank, state, si, ne)
        return nxt

    txt = str(jax.make_jaxpr(jax.vmap(lane))(states))
    assert not _batched_bank_shapes(txt, bank, B)


def test_vmapped_flat_loop_does_not_broadcast_bank(setup):
    """The flat engine's bulk fast paths sample from the bank; they must
    stay hoisted out of the mode switch / decide branches (regression:
    _bulk_fulfill inside decide.finish materialized a per-lane 19.4 GB
    copy of the dur table on the v5e — fixed by running it in the shared
    micro-step tail, commit 81e77fb)."""
    import jax

    from sparksched_tpu.env.flat_loop import init_loop_state, run_flat
    from sparksched_tpu.schedulers.heuristics import round_robin_policy

    params, bank, states, B = setup

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    def lane(ls, rng):
        return run_flat(
            params, bank, pol, rng, 2, auto_reset=False,
            compute_levels=False, event_bulk=True,
            bulk_events=8, fulfill_bulk=True, loop_state=ls,
        )

    ls = jax.vmap(init_loop_state)(states)
    rngs = jax.random.split(jax.random.PRNGKey(2), B)
    txt = str(jax.make_jaxpr(jax.vmap(lane))(ls, rngs))
    assert not _batched_bank_shapes(txt, bank, B)


def test_single_eval_batch_collect_does_not_broadcast_bank(setup):
    """The round-8 single-eval collector drives decide/drain micro-steps
    (lane-batched lax.switch branches + a batched drain while-loop);
    every bank access must stay out of lane-dependent conditionals."""
    import jax

    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.trainers.rollout import collect_flat_sync_batch

    params, bank, states, B = setup

    def bpol(rng, obs):
        # batched heuristic stand-in: vmap the per-lane policy
        def one(o):
            si, ne = round_robin_policy(o, params.num_executors, True)
            return si, ne
        si, ne = jax.vmap(one)(obs)
        return si, ne, {}

    def f(s, r):
        return collect_flat_sync_batch(
            params, bank, bpol, r, 4, s, fulfill_bulk=True
        )

    txt = str(jax.make_jaxpr(f)(states, jax.random.PRNGKey(3)))
    assert not _batched_bank_shapes(txt, bank, B)


def test_vmapped_async_collect_does_not_broadcast_bank(setup):
    import jax

    from sparksched_tpu.env.observe import Observation
    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.trainers.rollout import collect_async

    params, bank, states, B = setup

    def pol(rng, obs: Observation):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    def f(s, r):
        return jax.vmap(
            lambda rr, ss: collect_async(
                params, bank, pol, rr, 4, ss, 1e6
            )
        )(r, s)

    rngs = jax.random.split(jax.random.PRNGKey(1), B)
    txt = str(jax.make_jaxpr(f)(states, rngs))
    assert not _batched_bank_shapes(txt, bank, B)
