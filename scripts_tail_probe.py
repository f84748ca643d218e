"""Decompose the micro-step cost on the real chip.

Variants timed (all 1024 lanes, 512-lane sub-batches, 256 micro-steps
per jit call):
  full        micro_step, auto_reset=True   (bench baseline)
  noreset     micro_step, auto_reset=False  (isolates reset cost;
              trajectories identical while no lane finishes)
  event       event_micro_step only, auto_reset=False (shared-tail cost
              without the DECIDE/FULFILL switch)
  pop         _pop_event + state replace only (lower bound on event cost)

Scratch diagnostic for the round-2 perf push (not part of the package).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from sparksched_tpu.config import EnvParams
from sparksched_tpu.env import core
from sparksched_tpu.env.flat_loop import (
    LoopState,
    _pop_event,
    event_micro_step,
    init_loop_state,
    micro_step,
)
from sparksched_tpu.schedulers.heuristics import round_robin_policy
from sparksched_tpu.workload import make_workload_bank

NUM_ENVS = 1024
SUB = 512
CHUNK = 256


def main() -> None:
    params = EnvParams(
        num_executors=10, max_jobs=50, max_stages=20, max_levels=20,
        moving_delay=2000.0, warmup_delay=1000.0, job_arrival_rate=4e-5,
        mean_time_limit=None,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    if bank.max_stages != params.max_stages:
        params = params.replace(
            max_stages=bank.max_stages, max_levels=bank.max_stages
        )

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    def lane_full(ls, r, auto_reset):
        def body(carry, _):
            ls, k = carry
            k, sub = jax.random.split(k)
            ls = micro_step(
                params, bank, pol, ls, sub, auto_reset,
                compute_levels=False,
            )
            return (ls, k), None

        (ls, _), _ = lax.scan(body, (ls, r), None, length=CHUNK)
        return ls

    def lane_event(ls, r):
        def body(carry, _):
            ls, k = carry
            k, sub = jax.random.split(k)
            ls = event_micro_step(params, bank, ls, sub, False)
            return (ls, k), None

        (ls, _), _ = lax.scan(body, (ls, r), None, length=CHUNK)
        return ls

    def lane_pop(ls, r):
        def body(carry, _):
            ls, k = carry
            st, rk, rj, rs, arg, quirk = _pop_event(
                params, ls.env, ls.mode == 2
            )
            ls = ls.replace(env=st)
            return (ls, k), None

        (ls, _), _ = lax.scan(body, (ls, r), None, length=CHUNK)
        return ls

    @partial(jax.jit, static_argnums=(0,))
    def chunk(which, ls, rngs):
        fns = {
            "full": lambda l, r: lane_full(l, r, True),
            "noreset": lambda l, r: lane_full(l, r, False),
            "event": lane_event,
            "pop": lane_pop,
        }
        fn = fns[which]
        b = rngs.shape[0]
        grp = jax.tree_util.tree_map(
            lambda a: a.reshape(b // SUB, SUB, *a.shape[1:]), (ls, rngs)
        )
        ls2 = lax.map(lambda sr: jax.vmap(fn)(sr[0], sr[1]), grp)
        return jax.tree_util.tree_map(
            lambda a: a.reshape(b, *a.shape[2:]), ls2
        )

    rng = jax.random.PRNGKey(0)
    keys = jax.random.split(rng, NUM_ENVS)
    states = jax.vmap(lambda k: core.reset(params, bank, k))(keys)
    ls0 = jax.vmap(init_loop_state)(states)
    # warm into steady state with the full variant
    ls0 = chunk("full", ls0, jax.random.split(jax.random.PRNGKey(1),
                                              NUM_ENVS))
    jax.block_until_ready(ls0.decisions)

    for which in ("full", "noreset", "event", "pop"):
        ls = chunk(which, ls0,
                   jax.random.split(jax.random.PRNGKey(2), NUM_ENVS))
        jax.block_until_ready(ls.decisions)  # compile
        t0 = time.perf_counter()
        n_timed = 3
        ls = ls0
        for i in range(n_timed):
            ls = chunk(which, ls,
                       jax.random.split(jax.random.PRNGKey(3 + i),
                                        NUM_ENVS))
        jax.block_until_ready(ls.decisions)
        dt = time.perf_counter() - t0
        ms = n_timed * CHUNK * NUM_ENVS
        per = dt / (n_timed * CHUNK) * 1e3
        print(
            f"{which:8s}: {ms / dt:9.0f} micro-steps/s   "
            f"{per:6.2f} ms per 1024-lane micro-step   "
            f"decisions={int(ls.decisions.sum())}"
        )


if __name__ == "__main__":
    from sparksched_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    main()
