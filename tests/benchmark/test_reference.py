"""The benchmark's plain reference against the program at a small size
on the CPU: the numpy Decima forward pass, and the arithmetic that holds
the collector's log-probs to it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, logprob_check
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


CONFIGS = ("decima_tpch_50x200", "decima_tpch_50x200_stream",
           "decima_tpch_50x200_dp4")
NOT_COMPARED = ("logprob_sample", "logprob_stated_gap_q")
ABSOLUTE = ["logprob_gap_mean", "logprob_stated_gap_mean",
            "logprob_stated_gap_quantile"]
SHARE = ["logprob_gap_mean", "logprob_stated_gap_mean_ratio"]


def _drawn(mean: float, n: int = 256, seed: int = 7) -> np.ndarray:
    """`n` gaps with a long tail, as a run reads them: exponential
    about `mean` (its 0.95 quantile is three times the mean)."""
    return np.random.default_rng(seed).exponential(mean, size=n)


def _with(gaps: np.ndarray, value: float, count: int = 1) -> np.ndarray:
    out = np.sort(gaps).copy()
    out[-count:] = value
    return np.random.default_rng(1).permutation(out)


# the program's gaps against the plain float32 reference and at the
# stated precision, the reference's own gaps a precision down, and the
# checks that fail under limits on the gaps and under a limit on the share
GAP_CASES = {
    "sound": (_drawn(0.01), _drawn(0.0005), _drawn(0.004, seed=8), [], []),
    # what the widest-gap limit of 0.0075 failed on one seed in ten
    "sound_with_one_outlier": (
        _drawn(0.01), _with(_drawn(0.0005), 0.0247), _drawn(0.004, seed=8),
        [], []),
    # a twentieth of the gaps wild: under the quantile, inside the mean
    "sound_with_twelve_outliers": (
        _drawn(0.01), _with(_drawn(0.0002), 0.03, 12),
        _drawn(0.01, seed=8), [], []),
    # seed 3300000517 on the chip: large weights, a sound share of 0.17
    "sound_under_large_weights": (
        _drawn(0.017), _drawn(0.0016), _drawn(0.0095, seed=8),
        ["logprob_stated_gap_quantile"], []),
    # seed 3500000743 under the control: small weights, a share of 0.84
    "the_control_under_small_weights": (
        _drawn(0.0011), _drawn(0.0006), _drawn(0.00072, seed=8),
        [], ["logprob_stated_gap_mean_ratio"]),
    "as_the_control_reads": (
        _drawn(0.012), _drawn(0.003), _drawn(0.003, seed=8),
        ["logprob_stated_gap_mean", "logprob_stated_gap_quantile"],
        ["logprob_stated_gap_mean_ratio"]),
    "every_gap_a_little_wide": (
        _drawn(0.01), np.full(256, 0.003), np.full(256, 0.004),
        ["logprob_stated_gap_mean"], ["logprob_stated_gap_mean_ratio"]),
    "a_tenth_of_the_gaps_wide": (
        _drawn(0.01), _with(_drawn(0.0002), 0.006, 26),
        _drawn(0.004, seed=8), ["logprob_stated_gap_quantile"], []),
    "a_gross_fault": (
        np.full(256, 0.2), _drawn(0.0005), _drawn(0.004, seed=8),
        ["logprob_gap_mean"], ["logprob_gap_mean"]),
    "a_log_prob_that_is_no_number": (
        _with(_drawn(0.01), float("inf")),
        _with(_drawn(0.0005), float("nan")), _drawn(0.004, seed=8),
        ABSOLUTE, SHARE),
    "a_lower_precision_that_reads_nought": (
        _drawn(0.01), _drawn(0.0005), np.zeros(256),
        [], ["logprob_stated_gap_mean_ratio"]),
    "an_empty_sample": ([], [], [], ["logprob_sample"] + ABSOLUTE,
                        ["logprob_sample"] + SHARE),
}


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("case", GAP_CASES)
def test_gap_checks_compare_what_the_configuration_holds_a_limit_for(
        case, config):
    """Each collector cell compares the numbers its own configuration
    file holds a limit for, through the one routine. Under limits on
    the gaps (the streaming and the mesh cell) and under a limit on the
    share of the lower precision's gap (`decima_rollout`): one wild gap
    in 256 fails nothing; a sample shifted as bfloat16 compute shifts
    it fails; large weights fail a sound run and small weights pass a
    control run under limits on the gaps and not under the share; a
    gross fault fails the plain mean; a sample that is empty or holds
    no number fails."""
    limits = harness.load_json("configs", config + ".json")["limits"]
    assert limits["logprob_sample"] == 256
    assert limits["logprob_stated_gap_q"] == 0.95
    assert "logprob_stated_gap_max" not in limits
    compared = [k for k in limits
                if k.startswith("logprob_") and k not in NOT_COMPARED]
    share = logprob_check.wants_lower_precision(limits)
    assert compared == (SHARE if share else ABSOLUTE)
    assert share == (config == "decima_tpch_50x200")
    plain, stated, lower, *failed = GAP_CASES[case]
    gaps = {"float32": plain, "bf16_operands": stated}
    if share:  # the reference is run a precision down only where asked
        gaps["bf16_compute"] = lower
    checks = logprob_check.gap_checks(gaps, limits)
    assert [c["check"] for c in checks] == ["logprob_sample"] + compared
    assert [c["check"] for c in checks if not c["ok"]] == failed[share]


def test_every_number_is_printed_compared_or_not():
    stated = _drawn(0.0005)
    stated[70] = 0.0247  # past the first 64: what the old sample missed
    gaps = {"float32": _drawn(0.01), "bf16_operands": stated,
            "bf16_compute": _drawn(0.004, seed=8)}
    found = logprob_check.widest(gaps)
    assert found["stated_gap_max"] == 0.0247
    assert found["stated_gap_max_first_64"] == stated[:64].max() < 0.0075
    assert found["gap_max"] == _drawn(0.01).max()
    assert logprob_check.widest({"float32": [], "bf16_operands": []}) == {
        "gap_max": None, "gap_max_first_64": None,
        "stated_gap_max": None, "stated_gap_max_first_64": None}
    numbers = logprob_check.gap_numbers(gaps, 0.95)
    assert numbers["logprob_stated_gap_mean_ratio"] == pytest.approx(
        stated.mean() / gaps["bf16_compute"].mean())
    assert numbers["logprob_stated_gap_quantile_ratio"] == pytest.approx(
        np.quantile(stated, 0.95) / np.quantile(gaps["bf16_compute"], 0.95))
    del gaps["bf16_compute"]
    assert set(logprob_check.gap_numbers(gaps, 0.95)) == set(ABSOLUTE)
    # a share asked for of gaps that hold no lower precision: no number
    checks = logprob_check.gap_checks(
        gaps, {"logprob_stated_gap_q": 0.95,
               "logprob_stated_gap_mean_ratio": 0.5})
    assert [c["ok"] for c in checks] == [True, False]


def test_the_reference_a_precision_down_lies_where_bfloat16_compute_does(
        setup):
    """`bf16_compute`, the reference put in the program's place at the
    next precision down, moves the log-probs about as far from the
    stated precision as the program's own bfloat16 path does (on the
    chip 0.82 to 1.14 of it over twelve seeds), and far further than
    float32 round-off: the yardstick of `decima_rollout`'s share."""
    params, bank, sched = setup
    low = DecimaScheduler(
        num_executors=N_EXEC, seed=3, compute_dtype="bfloat16", **AGENT)
    weights = jax.tree_util.tree_map(np.asarray, sched.params)
    program, reference = [], []
    for i, obs in enumerate(_observations(params, bank, 9)):
        f = sched.features(obs)
        if not np.asarray(f.stage_mask).any():
            continue
        stage, execs = low.net.apply(sched.params, f)
        action, lgprob = sample_action(
            jax.random.PRNGKey(i), stage, execs, f, deterministic=True)
        ref = {m: decima_np.score_action(
            weights, _np_obs(obs), int(action.stage_idx),
            int(action.num_exec), N_EXEC, matmul=m)["lgprob"]
            for m in ("bf16_operands", "bf16_compute")}
        program.append(abs(float(lgprob) - ref["bf16_operands"]))
        reference.append(abs(ref["bf16_compute"] - ref["bf16_operands"]))
    assert min(np.mean(program), np.mean(reference)) > 1e-4
    assert 0.25 < np.mean(program) / np.mean(reference) < 4


def test_the_gap_sampling_exists_once():
    """The three collector drivers hold no copy of the comparison: they
    call `benchmarks/logprob_check.py`."""
    import inspect

    from benchmarks.drivers import (
        collect_rollout,
        collect_rollout_dp,
        collect_stream,
    )

    for driver in (collect_rollout, collect_rollout_dp, collect_stream):
        source = inspect.getsource(driver)
        assert "logprob_check.checks(" in source, driver.__name__
        assert "score_action" not in source, driver.__name__
        assert not hasattr(driver, "gap_checks")
        assert not hasattr(driver, "logprob_gaps")
