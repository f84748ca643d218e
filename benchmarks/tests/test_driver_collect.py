import pytest

from benchmarks.drivers import collect_rollout
from benchmarks.tests.tiny import run_tiny


@pytest.fixture(autouse=True)
def _default_prng():
    import jax

    before = jax.config.jax_default_prng_impl
    yield
    jax.config.update("jax_default_prng_impl", before)


def test_the_collector_runs_whole_collections_and_verifies():
    line = run_tiny("tiny_collect")
    assert line["correct"] and line["checks_failed"] == []
    assert line["attempted"] >= 2 and line["failed"] == 0


def test_bfloat16_compute_fails_the_logprob_comparison():
    """On the CPU float32 is exact to 1e-5, so at the tiny size it is
    the plain mean that parts the control (limit 1e-4); the shares at
    the stated precision are the chip's and are left open here: the
    control's gaps are of the size the reference reads when it is
    itself computed a precision down."""
    line = run_tiny("tiny_collect", control="bf16_compute")
    assert not line["correct"]
    assert line["checks_failed"] == ["logprob_gap_mean"]
    assert list(line)[-1] == "checks"  # each number beside its limit
    value, limit = line["checks"]["logprob_gap_mean"]
    assert value > limit == 0.0001
    assert set(line["checks"]) >= {
        "logprob_sample", "logprob_stated_gap_mean_ratio",
        "logprob_stated_gap_quantile_ratio", "compilations_in_window"}
    assert "logprob_stated_gap_max" not in line["checks"]
    share = line["checks"]["logprob_stated_gap_mean_ratio"][0]
    assert 0.2 < share < 5  # the reference a precision down: that size


def _broken(monkeypatch, change):
    real = collect_rollout._call_collector

    def call(trainer, params, i, rng):
        ro, state, telem = real(trainer, params, i, rng)
        return change(ro), state, telem

    monkeypatch.setattr(collect_rollout, "_call_collector", call)
    return run_tiny("tiny_collect")


def test_a_log_prob_altered_where_it_is_produced_is_not_correct(monkeypatch):
    line = _broken(monkeypatch, lambda ro: ro.replace(lgprob=ro.lgprob - 0.01))
    assert not line["correct"]
    assert "logprob_gap_mean" in line["checks_failed"]


def test_a_lane_left_out_of_the_batch_is_not_correct(monkeypatch):
    line = _broken(monkeypatch, lambda ro: ro.replace(
        valid=ro.valid.at[0].set(False)))
    assert not line["correct"]
    assert set(line["checks_failed"]) == {
        "telemetry_decisions_gap", "idle_lanes"}
