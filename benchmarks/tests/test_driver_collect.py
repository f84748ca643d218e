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
    line = run_tiny("tiny_collect", control="bf16_compute")
    assert not line["correct"]
    assert set(line["checks_failed"]) <= {
        "logprob_gap_mean", "logprob_gap_max"}
    assert "logprob_gap_mean" in line["checks_failed"]


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
