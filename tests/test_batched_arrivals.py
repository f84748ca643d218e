"""The batched-arrivals deployment below the collector: the arrival law
of `workload.sample_job_sequence` with `EnvParams.num_init_jobs`
against a numpy reading of the same draws, its default against the
sequence the sampler gave before the key existed, the field's
validation, the program's configuration file, and the net at a job axis
of 20 (no multiple of the eight jobs the level scan packs to a row)
against the benchmark's plain forward pass."""

import dataclasses
import os.path as osp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparksched_tpu import config
from sparksched_tpu.config import EnvParams, env_params_from_cfg
from sparksched_tpu.env import core
from sparksched_tpu.workload import make_workload_bank
from sparksched_tpu.workload.sampling import sample_job_sequence

ROOT = osp.dirname(osp.dirname(osp.abspath(__file__)))
N_EXEC, CAP = 6, 8


@pytest.fixture(scope="module")
def bank():
    return make_workload_bank(N_EXEC)


def _sequence_before(params, bank, rng, time_limit):
    """`sample_job_sequence` as it was before `num_init_jobs`."""
    j_cap = params.max_jobs
    k_gap, k_tpl = jax.random.split(rng)
    gaps = jax.random.exponential(k_gap, (j_cap,)) * (
        1.0 / params.job_arrival_rate)
    arrivals = jnp.concatenate(
        [jnp.zeros(1), jnp.cumsum(gaps)[: j_cap - 1]]).astype(jnp.float32)
    mask = (arrivals < time_limit).at[0].set(True)
    mask = jnp.cumprod(mask.astype(jnp.int32)).astype(bool)
    templates = jax.random.randint(
        k_tpl, (j_cap,), 0, bank.num_templates, dtype=jnp.int32)
    return (jnp.where(mask, arrivals, jnp.inf), templates,
            mask.sum().astype(jnp.int32), mask)


@pytest.mark.parametrize("limit", [np.inf, 6.0e4, 1.0])
def test_the_default_is_the_sequence_it_was_bit_for_bit(bank, limit):
    params = EnvParams(num_executors=N_EXEC, max_jobs=CAP)
    assert params.num_init_jobs == 1
    for k in range(4):
        key = jax.random.PRNGKey(k)
        now = sample_job_sequence(params, bank, key, jnp.float32(limit))
        was = _sequence_before(params, bank, key, jnp.float32(limit))
        for a, b in zip(now, was):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n0", [1, 3, CAP])
@pytest.mark.parametrize("limit", [np.inf, 6.0e4, 1.0])
def test_the_arrival_law_is_n_zeros_then_cumulative_gaps(bank, n0, limit):
    """N jobs at t=0 whatever the limit, then the prefix of the
    cumulative exponential gaps that lies under the limit; the gaps and
    templates are the draws the default makes from the same key."""
    params = EnvParams(num_executors=N_EXEC, max_jobs=CAP, num_init_jobs=n0)
    for k in range(4):
        key = jax.random.PRNGKey(100 + k)
        arrivals, templates, num_jobs, mask = (
            np.asarray(a) for a in sample_job_sequence(
                params, bank, key, jnp.float32(limit)))
        k_gap, _ = jax.random.split(key)
        gaps = np.asarray(jax.random.exponential(k_gap, (CAP,))) * np.float32(
            1.0 / params.job_arrival_rate)
        want = np.concatenate([
            np.zeros(n0, np.float32),
            np.cumsum(gaps, dtype=np.float32)[: CAP - n0]])
        there = np.arange(CAP) < n0
        for j in range(n0, CAP):  # a prefix: a job only after the one before
            there[j] = there[j - 1] and want[j] < limit
        np.testing.assert_array_equal(mask, there)
        assert num_jobs == there.sum() >= n0
        np.testing.assert_allclose(arrivals[there], want[there], rtol=1e-6)
        assert (arrivals[:n0] == 0.0).all() and np.isinf(arrivals[~there]).all()
        default = sample_job_sequence(
            params.replace(num_init_jobs=1), bank, key, jnp.float32(limit))
        np.testing.assert_array_equal(templates, np.asarray(default[1]))


@pytest.mark.parametrize("bad", [0, -1, CAP + 1, 2.5, True, "3"])
def test_a_count_under_one_over_the_cap_or_not_whole_raises(bad):
    with pytest.raises((ValueError, TypeError)):
        EnvParams(num_executors=N_EXEC, max_jobs=CAP, num_init_jobs=bad)


def test_a_whole_float_is_taken_and_a_yaml_value_that_is_not_whole_raises():
    assert EnvParams(max_jobs=CAP, num_init_jobs=3.0).num_init_jobs == 3
    env = {"num_executors": 5, "job_arrival_cap": 6, "num_init_jobs": 6}
    assert env_params_from_cfg(env).num_init_jobs == 6
    with pytest.raises(ValueError, match="whole"):
        env_params_from_cfg(dict(env, num_init_jobs=2.5))
    # a key the program does not know is skipped, on purpose
    assert env_params_from_cfg(dict(env, render_mode="human")).max_jobs == 6


def test_the_batched_configuration_is_the_flagship_but_for_its_arrivals():
    cfg = config.load(osp.join(ROOT, "config", "decima_tpch_batched.yaml"))
    flagship = config.load(osp.join(ROOT, "config", "decima_tpch.yaml"))
    params = env_params_from_cfg(cfg["env"])
    assert dataclasses.asdict(params) | {
        "num_executors": 50, "max_jobs": 20, "num_init_jobs": 20,
        "mean_time_limit": None, "moving_delay": 2000.0,
        "warmup_delay": 1000.0} == dataclasses.asdict(params)
    for block in ("agent", "health"):
        assert cfg[block] == flagship[block], block
    # the one key more: the counters of episodes that end in the scan
    assert cfg["obs"] == flagship["obs"] | {"episode_counters": True}
    assert cfg["trainer"]["fast_prng"] is False
    same = set(cfg["trainer"]) - {"fast_prng", "rollout_steps"}
    assert {k: cfg["trainer"][k] for k in same} == {
        k: flagship["trainer"][k] for k in same}


def test_every_job_of_a_batch_is_there_at_the_first_decision(bank):
    params = EnvParams(
        num_executors=N_EXEC, max_jobs=CAP, num_init_jobs=CAP,
        max_stages=bank.max_stages, max_levels=bank.max_stages)
    state = core.reset(params, bank, jax.random.PRNGKey(2))
    assert int(state.num_jobs) == CAP and bool(state.job_arrived.all())
    assert np.isinf(float(state.time_limit))
    assert not np.isfinite(np.asarray(state.exec_arrive_time)).any()
    # nothing is left to arrive: the queue holds no job event
    pending = np.where(np.asarray(state.job_arrived), np.inf,
                       np.asarray(state.job_arrival_time))
    assert np.isinf(pending).all()


@pytest.mark.parametrize("job_bucket", [0, 32])
def test_the_net_at_a_job_axis_of_twenty_equals_the_plain_forward_pass(
        job_bucket):
    """A width that is no multiple of eight, with and without
    `job_bucket` (at 32 it is idle: the axis is under it, so the
    scheduler has one width and reports none)."""
    from benchmarks.reference import decima_np
    from sparksched_tpu.env.observe import observe
    from sparksched_tpu.schedulers.decima import DecimaScheduler
    from sparksched_tpu.schedulers.heuristics import round_robin_policy

    agent = dict(
        gnn_mlp_kwargs={"hid_dims": [32, 16], "act_cls": "LeakyReLU",
                        "act_kwargs": {"negative_slope": 0.2}},
        policy_mlp_kwargs={"hid_dims": [64, 64], "act_cls": "Tanh"})
    bank = make_workload_bank(N_EXEC)
    params = EnvParams(
        num_executors=N_EXEC, max_jobs=20, num_init_jobs=20,
        max_stages=bank.max_stages, max_levels=bank.max_stages)
    sched = DecimaScheduler(
        num_executors=N_EXEC, seed=3, job_bucket=job_bucket, **agent)
    weights = jax.tree_util.tree_map(np.asarray, sched.params)
    states = jax.vmap(lambda k: core.reset(params, bank, k))(
        jax.random.split(jax.random.PRNGKey(7), 3))
    compared = 0
    for row in range(4):
        obs = jax.vmap(lambda s: observe(params, s))(states)
        assert (np.asarray(obs.job_mask).sum(-1) >= 19).all()
        si, ne, aux = sched.batch_policy(jax.random.PRNGKey(row), obs)
        assert "full_width" not in aux  # one width
        for lane in range(3):
            one = jax.tree_util.tree_map(lambda a: a[lane], obs)
            ref = decima_np.score_action(
                weights, decima_np.obs_arrays(jax.device_get(one)),
                int(si[lane]), int(aux["num_exec_k"][lane]), N_EXEC)
            assert ref["lgprob"] == pytest.approx(
                float(aux["lgprob"][lane]), abs=2e-5)
            compared += 1
        # a fair step, so that the next row is another observation
        acts = [round_robin_policy(
            jax.tree_util.tree_map(lambda a: a[lane], obs), N_EXEC, True)
            for lane in range(3)]
        states = jax.vmap(
            lambda s, a, n: core.step(params, bank, s, a, n)[0])(
                states, jnp.stack([a[0] for a in acts]),
                jnp.stack([a[1] for a in acts]))
    assert compared == 12
