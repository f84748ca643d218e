"""Sweep the event-burst length on the real chip: decisions/s vs K.

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


def main(bursts=(1, 4, 8, 16)):
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
    def chunk(burst, groups, ls, rngs):
        def lane(l, r):
            return run_flat(
                params, bank, pol, r, groups,
                compute_levels=False, event_burst=burst, loop_state=l,
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
    ls0 = jax.vmap(init_loop_state)(states)

    for burst in bursts:
        groups = max(1, 256 // burst)  # ~256 micro-steps per chunk
        # warm into steady state + compile
        ls = ls0
        ls = chunk(burst, groups, ls,
                   jax.random.split(jax.random.PRNGKey(10), NUM_ENVS))
        jax.block_until_ready(ls.decisions)
        d0 = int(ls.decisions.sum())
        t0 = time.perf_counter()
        n_timed = 3
        for i in range(n_timed):
            ls = chunk(burst, groups, ls,
                       jax.random.split(jax.random.PRNGKey(50 + i),
                                        NUM_ENVS))
        jax.block_until_ready(ls.decisions)
        dt = time.perf_counter() - t0
        d1 = int(ls.decisions.sum())
        msteps = n_timed * groups * burst * NUM_ENVS
        print(
            f"burst={burst:2d}: {(d1 - d0) / dt:8.0f} decisions/s  "
            f"{msteps / dt:9.0f} micro-steps/s  "
            f"dec_frac={(d1 - d0) / msteps:.3f}  "
            f"episodes={int(ls.episodes.sum())}"
        )


if __name__ == "__main__":
    from sparksched_tpu.config import enable_compilation_cache

    import sys

    enable_compilation_cache()
    if len(sys.argv) > 1:
        main(tuple(int(b) for b in sys.argv[1:]))
    else:
        main()
