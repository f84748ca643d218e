"""The benchmark's plain reference against the program at a small size
on the CPU: the numpy Decima forward pass, and the arithmetic that holds
the collector's log-probs to it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import collect_rollout
from benchmarks.reference import decima_np
from sparksched_tpu.config import EnvParams
from sparksched_tpu.env import core
from sparksched_tpu.env.observe import observe
from sparksched_tpu.schedulers.decima import (
    DecimaAction,
    DecimaScheduler,
    evaluate_actions,
    sample_action,
)
from sparksched_tpu.schedulers.heuristics import round_robin_policy
from sparksched_tpu.workload import make_workload_bank

N_EXEC = 6
# the shipped agent block (config/decima_tpch.yaml)
AGENT = dict(
    gnn_mlp_kwargs={"hid_dims": [32, 16], "act_cls": "LeakyReLU",
                    "act_kwargs": {"negative_slope": 0.2}},
    policy_mlp_kwargs={"hid_dims": [64, 64], "act_cls": "Tanh"})


@pytest.fixture(scope="module")
def setup():
    params = EnvParams(num_executors=N_EXEC, max_jobs=5)
    bank = make_workload_bank(N_EXEC, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages)
    sched = DecimaScheduler(num_executors=N_EXEC, seed=3, **AGENT)
    return params, bank, sched


def _np_obs(o) -> dict:
    return decima_np.obs_arrays(jax.device_get(o))


def _observations(params, bank, n: int):
    """Observations along a fair-policy episode (several jobs live)."""
    state = core.reset(params, bank, jax.random.PRNGKey(5))
    out = []
    for _ in range(n):
        obs = observe(params, state)
        out.append(obs)
        si, ne = round_robin_policy(obs, params.num_executors, True)
        state = core.step(params, bank, state, si, ne)[0]
    return out


def test_forward_pass_features_and_log_probs_match_the_program(setup):
    params, bank, sched = setup
    weights = jax.tree_util.tree_map(np.asarray, sched.params)
    compared = 0
    for i, obs in enumerate(_observations(params, bank, 9)):
        f = sched.features(obs)
        ref_f = decima_np.features(_np_obs(obs), N_EXEC)
        np.testing.assert_allclose(ref_f["x"], f.x, rtol=1e-6, atol=1e-6)
        for name in ("stage_mask", "exec_mask", "adj"):
            np.testing.assert_array_equal(ref_f[name], getattr(f, name))
        active = np.asarray(f.node_mask)
        np.testing.assert_array_equal(
            decima_np.node_levels(active, ref_f["adj"])[active],
            np.asarray(f.node_level)[active])
        if not np.asarray(f.stage_mask).any():
            continue
        stage, execs = sched.net.apply(sched.params, f)
        ref_stage, ref_exec = decima_np.forward(weights, ref_f, N_EXEC)
        jobs = np.asarray(f.job_mask)
        np.testing.assert_allclose(
            ref_stage[active], np.asarray(stage)[active], atol=2e-5)
        np.testing.assert_allclose(
            ref_exec[jobs], np.asarray(execs)[jobs], atol=2e-5)
        action, lgprob = sample_action(
            jax.random.PRNGKey(i), stage, execs, f)
        ref = decima_np.score_action(
            weights, _np_obs(obs), int(action.stage_idx),
            int(action.num_exec), N_EXEC)
        assert ref["lgprob"] == pytest.approx(float(lgprob), abs=2e-5)
        again, _ = evaluate_actions(stage, execs, f, DecimaAction(
            action.stage_idx, action.job_idx, action.num_exec), N_EXEC)
        assert ref["lgprob"] == pytest.approx(float(again), abs=2e-5)
        greedy, _ = sample_action(
            jax.random.PRNGKey(0), stage, execs, f, deterministic=True)
        if ref["margin"] > 1e-4:
            assert ref["greedy"] == (
                int(greedy.stage_idx), int(greedy.num_exec))
        assert ref["below_best"] >= -1e-9
        compared += 1
    assert compared >= 8


def test_the_lower_precision_lands_outside_the_references_noise(setup):
    """bfloat16 compute, the step below the stated float32, moves the
    log-probs thousands of times further from the reference than
    float32 does on this backend."""
    params, bank, sched = setup
    low = DecimaScheduler(
        num_executors=N_EXEC, seed=3, compute_dtype="bfloat16", **AGENT)
    weights = jax.tree_util.tree_map(np.asarray, sched.params)
    gaps = {"float32": [], "bfloat16": []}
    for i, obs in enumerate(_observations(params, bank, 9)):
        f = sched.features(obs)
        if not np.asarray(f.stage_mask).any():
            continue
        for name, s in (("float32", sched), ("bfloat16", low)):
            stage, execs = s.net.apply(sched.params, f)
            action, lgprob = sample_action(
                jax.random.PRNGKey(i), stage, execs, f, deterministic=True)
            ref = decima_np.score_action(
                weights, _np_obs(obs), int(action.stage_idx),
                int(action.num_exec), N_EXEC)
            gaps[name].append(abs(ref["lgprob"] - float(lgprob)))
    assert max(gaps["float32"]) < 1e-4
    assert np.mean(gaps["bfloat16"]) > 100 * np.mean(gaps["float32"])


def test_bf16_rounding_is_the_devices_and_the_stated_precision_is_near():
    """`bf16` rounds as a cast to bfloat16 does (nearest, ties to even),
    and the reference at the stated precision (bfloat16 operands) stays
    within a few tenths of the plain one in every score: the distance a
    TPU's default float32 matrix product keeps."""
    rng = np.random.default_rng(0)
    x = np.concatenate([
        rng.normal(size=4096) * 10.0 ** rng.integers(-6, 6, size=4096),
        [0.0, 1.0, -1.0, 1.00390625, 1.01171875, 3.0e38]]).astype(np.float32)
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(decima_np.bf16(x), want.astype(np.float64))
    with pytest.raises(ValueError):
        decima_np._operand(x, "int4")


def test_the_stated_precision_reference_differs_only_slightly(setup):
    params, bank, sched = setup
    weights = jax.tree_util.tree_map(np.asarray, sched.params)
    gaps = []
    for obs in _observations(params, bank, 6):
        f = decima_np.features(_np_obs(obs), N_EXEC)
        if not f["stage_mask"].any():
            continue
        plain = decima_np.forward(weights, f, N_EXEC)
        stated = decima_np.forward(weights, f, N_EXEC,
                                   matmul="bf16_operands")
        mask = f["node_mask"]
        gaps.append(np.abs(plain[0][mask] - stated[0][mask]).max())
    assert 0 < max(gaps) < 0.5


LIMITS = {"logprob_gap_mean": 0.1, "logprob_gap_max": 0.9,
          "logprob_stated_gap_mean": 0.0025, "logprob_stated_gap_max": 0.0075}


@pytest.mark.parametrize("plain, stated, failed", [
    ([0.01, 0.05], [0.001, 0.002], []),
    ([0.01, 1.0], [0.001, 0.002], ["logprob_gap_mean", "logprob_gap_max"]),
    ([0.2, 0.2], [0.001, 0.002], ["logprob_gap_mean"]),
    ([0.01, 0.05], [0.0001, 0.008], ["logprob_stated_gap_mean",
                                     "logprob_stated_gap_max"]),
    ([0.01, 0.05], [0.003, 0.003], ["logprob_stated_gap_mean"]),
    ([], [], ["logprob_gap_mean", "logprob_gap_max",
              "logprob_stated_gap_mean", "logprob_stated_gap_max"]),
    ([0.01, float("inf")], [0.001, float("nan")], [
        "logprob_gap_mean", "logprob_gap_max", "logprob_stated_gap_mean",
        "logprob_stated_gap_max"]),
])
def test_gap_checks_hold_each_gap_to_its_own_limit(plain, stated, failed):
    checks = collect_rollout.gap_checks(
        {"float32": plain, "bf16_operands": stated}, LIMITS)
    assert [c["check"] for c in checks] == list(LIMITS)
    assert [c["check"] for c in checks if not c["ok"]] == failed
