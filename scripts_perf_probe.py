"""Perf probe: micro-step composition + per-piece timing on the real chip.

Not part of the package; a scratch diagnostic for the round-2 perf push.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from sparksched_tpu.config import EnvParams
from sparksched_tpu.env import core
from sparksched_tpu.env.flat_loop import init_loop_state, micro_step
from sparksched_tpu.env.observe import observe
from sparksched_tpu.schedulers.heuristics import round_robin_policy
from sparksched_tpu.workload import make_workload_bank

NUM_ENVS = 1024
SUB = 512
CHUNK = 128


def timed(fn, *args, n=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n, out


def main():
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
    print("caps:", params.max_jobs, params.max_stages,
          bank.num_templates, bank.max_stages)

    rng = jax.random.PRNGKey(0)
    keys = jax.random.split(rng, NUM_ENVS)
    states = jax.vmap(lambda k: core.reset(params, bank, k))(keys)
    ls = jax.vmap(init_loop_state)(states)

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    @partial(jax.jit, static_argnums=())
    def run_chunk(ls, rngs):
        def lane(l, r):
            def body(c, _):
                l, k = c
                k, s = jax.random.split(k)
                l = micro_step(params, bank, pol, l, s, True, False)
                return (l, k), None

            (l, _), _ = lax.scan(body, (l, r), None, length=CHUNK)
            return l

        b = rngs.shape[0]
        grp = jax.tree_util.tree_map(
            lambda a: a.reshape(b // SUB, SUB, *a.shape[1:]), (ls, rngs)
        )
        ls2 = lax.map(lambda sr: jax.vmap(lane)(sr[0], sr[1]), grp)
        return jax.tree_util.tree_map(
            lambda a: a.reshape(b, *a.shape[2:]), ls2
        )

    # mode histogram before/after to estimate decision fraction
    keys = jax.random.split(jax.random.PRNGKey(1), NUM_ENVS)
    ls1 = run_chunk(ls, keys)
    jax.block_until_ready(ls1.decisions)
    d0 = int(ls1.decisions.sum())
    t, ls2 = timed(run_chunk, ls1, jax.random.split(
        jax.random.PRNGKey(2), NUM_ENVS))
    d1 = int(ls2.decisions.sum())
    msteps = NUM_ENVS * CHUNK
    dec_per_chunk = (d1 - d0) / 3
    print(f"chunk: {t*1e3:.1f} ms for {msteps} micro-steps "
          f"({t/CHUNK*1e6:.0f} us per {NUM_ENVS}-lane micro-step)")
    print(f"decision fraction: {dec_per_chunk / msteps:.3f}")
    print(f"decisions/s: {dec_per_chunk / t:.0f}")
    print(f"micro-steps/s: {msteps / t:.0f}")
    print(f"episodes: {int(ls2.episodes.sum())}")

    # --- piece timings at 1024 lanes -------------------------------------
    st = ls2.env

    def f_observe(st):
        return jax.vmap(lambda s: observe(params, s, False))(st)

    def f_levels(st):
        return jax.vmap(lambda s: core.compute_node_levels(params, s))(st)

    def f_policy(st):
        obs = f_observe(st)
        return jax.vmap(
            lambda o: round_robin_policy(o, params.num_executors, True)
        )(obs)

    def f_next_event(st):
        return jax.vmap(lambda s: core._next_event(params, s))(st)

    def f_sched(st):
        return jax.vmap(
            lambda s: core.find_schedulable(params, s, s.source_job_id())
        )(st)

    def f_backup(st):
        return jax.vmap(
            lambda s: core._find_backup_stage(
                params, s, jnp.int32(0), s.source_job_id()
            )
        )(st)

    def f_apply(st):
        return jax.vmap(
            lambda s: core._apply_action(
                params, bank, s, jnp.int32(1), jnp.int32(0), jnp.int32(0),
                jnp.int32(0),
            )
        )(st)

    def f_fulfill_a(st):
        return jax.vmap(
            lambda s: core._fulfill_commitment_phase_a(
                s, jnp.int32(0), jnp.int32(0)
            )
        )(st)

    def f_handle_tf(st):
        return jax.vmap(
            lambda s: core._handle_task_finished(s, jnp.int32(0))
        )(st)

    def f_argsorts(st):
        def one(s):
            n = s.exec_job.shape[0]
            idle = s.source_pool_mask() & ~s.exec_executing
            eo = jnp.argsort(jnp.where(idle, jnp.arange(n), 10**9))
            so = jnp.argsort(
                jnp.where(s.cm_valid, s.cm_seq, 10**9), stable=True
            )
            return eo, so

        return jax.vmap(one)(st)

    for name, fn in [
        ("observe(no levels)", f_observe),
        ("node_levels", f_levels),
        ("observe+fair policy", f_policy),
        ("next_event", f_next_event),
        ("find_schedulable", f_sched),
        ("backup_stage", f_backup),
        ("apply_action", f_apply),
        ("fulfill_phase_a", f_fulfill_a),
        ("handle_task_finished", f_handle_tf),
        ("argsort pair", f_argsorts),
    ]:
        jf = jax.jit(fn)
        t, _ = timed(jf, st, n=10)
        print(f"{name:24s} {t*1e6:8.0f} us / call @1024")


if __name__ == "__main__":
    from sparksched_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    main()
