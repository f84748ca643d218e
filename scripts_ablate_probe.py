import time
from functools import partial
import jax
from jax import lax
from sparksched_tpu.config import EnvParams, enable_compilation_cache
from sparksched_tpu.env import core

# ablation: cheap deterministic sampler (one gather, no rng)
def cheap_sampler(params, bank, rng, facts, template, stage, num_local, task_valid, same_stage):
    return bank.rough_duration[template, stage]

import sys
if "cheap" in sys.argv:
    core.sample_task_duration = cheap_sampler
    import sparksched_tpu.env.flat_loop as fl
from sparksched_tpu.env.flat_loop import init_loop_state, run_flat
from sparksched_tpu.schedulers.heuristics import round_robin_policy
from sparksched_tpu.workload import make_workload_bank

NUM_ENVS, SUB, CHUNK = 1024, 512, 256
params = EnvParams(num_executors=10, max_jobs=50, max_stages=20, max_levels=20,
                   moving_delay=2000.0, warmup_delay=1000.0, job_arrival_rate=4e-5,
                   mean_time_limit=None)
bank = make_workload_bank(params.num_executors, params.max_stages)
if bank.max_stages != params.max_stages:
    params = params.replace(max_stages=bank.max_stages, max_levels=bank.max_stages)

def pol(rng, obs):
    si, ne = round_robin_policy(obs, params.num_executors, True)
    return si, ne, {}

@partial(jax.jit, static_argnums=(0,))
def chunk(bulk, ls, rngs):
    def lane(l, r):
        return run_flat(params, bank, pol, r, CHUNK, auto_reset=False,
                        compute_levels=False, event_bulk=bulk, loop_state=l)
    grp = jax.tree_util.tree_map(
        lambda a: a.reshape(NUM_ENVS // SUB, SUB, *a.shape[1:]), (ls, rngs))
    ls2 = lax.map(lambda sr: jax.vmap(lane)(sr[0], sr[1]), grp)
    return jax.tree_util.tree_map(lambda a: a.reshape(NUM_ENVS, *a.shape[2:]), ls2)

rng = jax.random.PRNGKey(0)
states = jax.vmap(lambda k: core.reset(params, bank, k))(jax.random.split(rng, NUM_ENVS))
for bulk in (False, True):
    ls = jax.vmap(init_loop_state)(states)
    ls = chunk(bulk, ls, jax.random.split(jax.random.PRNGKey(10), NUM_ENVS))
    jax.block_until_ready(ls.decisions)
    d0 = int(ls.decisions.sum())
    t0 = time.perf_counter()
    for i in range(3):
        ls = chunk(bulk, ls, jax.random.split(jax.random.PRNGKey(50 + i), NUM_ENVS))
    jax.block_until_ready(ls.decisions)
    dt = time.perf_counter() - t0
    d1 = int(ls.decisions.sum())
    ms = 3 * CHUNK * NUM_ENVS
    print(f"sampler={'cheap' if 'cheap' in sys.argv else 'full '} bulk={int(bulk)}: "
          f"{(d1-d0)/dt:8.0f} dec/s  {ms/dt:9.0f} mstep/s  dec/mstep={(d1-d0)/ms:.3f}")
