"""Measure decisions/s, micro-step mix and bulk efficiency of the flat
engine variants on the real chip.

Scratch diagnostic for the round-2 perf push (not part of the package).
"""

from __future__ import annotations

import time
from functools import partial

import jax
from jax import lax

from sparksched_tpu.config import EnvParams
from sparksched_tpu.env import core
from sparksched_tpu.env.flat_loop import init_loop_state, run_flat
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

    @partial(jax.jit, static_argnums=(0, 1))
    def chunk(bulk, reset, ls, rngs):
        def lane(l, r):
            return run_flat(
                params, bank, pol, r, CHUNK, auto_reset=reset,
                compute_levels=False, event_bulk=bulk, loop_state=l,
            )

        b = rngs.shape[0]
        grp = jax.tree_util.tree_map(
            lambda a: a.reshape(b // SUB, SUB, *a.shape[1:]), (ls, rngs)
        )
        ls2 = lax.map(lambda sr: jax.vmap(lane)(sr[0], sr[1]), grp)
        return jax.tree_util.tree_map(
            lambda a: a.reshape(b, *a.shape[2:]), ls2
        )

    rng = jax.random.PRNGKey(0)
    keys = jax.random.split(rng, NUM_ENVS)
    states = jax.vmap(lambda k: core.reset(params, bank, k))(keys)

    for bulk, reset in ((False, True), (True, True), (True, False)):
        ls = jax.vmap(init_loop_state)(states)
        ls = chunk(bulk, reset, ls,
                   jax.random.split(jax.random.PRNGKey(10), NUM_ENVS))
        jax.block_until_ready(ls.decisions)
        d0, b0 = int(ls.decisions.sum()), int(ls.bulked.sum())
        t0 = time.perf_counter()
        n_timed = 3
        for i in range(n_timed):
            ls = chunk(bulk, reset, ls,
                       jax.random.split(jax.random.PRNGKey(50 + i),
                                        NUM_ENVS))
        jax.block_until_ready(ls.decisions)
        dt = time.perf_counter() - t0
        d1, b1 = int(ls.decisions.sum()), int(ls.bulked.sum())
        msteps = n_timed * CHUNK * NUM_ENVS
        print(
            f"bulk={int(bulk)} reset={int(reset)}: "
            f"{(d1 - d0) / dt:8.0f} decisions/s  "
            f"{msteps / dt:9.0f} micro-steps/s  "
            f"dec/mstep={(d1 - d0) / msteps:.3f}  "
            f"bulked/mstep={(b1 - b0) / msteps:.2f}  "
            f"episodes={int(ls.episodes.sum())}"
        )


if __name__ == "__main__":
    from sparksched_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    main()
