"""The batched-arrivals driver's CPU tests, as `test_driver_collect.py`
for `collect_rollout`: the cell at a tiny size (4 lanes x 160 rows, a
batch of 6 jobs on 5 executors) runs, every lane ends by completion and
it verifies; the lower-precision control fails the comparison; a
rollout whose scan is too short for the episodes is not correct. Not
tier-1 (each compiles a collector).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import os.path as osp
import time

import pytest

from benchmarks import harness, run

TINY = osp.join(harness.HERE, "tests", "data", "tiny_batched")


@pytest.fixture(autouse=True)
def _default_prng():
    import jax

    before = jax.config.jax_default_prng_impl
    yield
    jax.config.update("jax_default_prng_impl", before)


def run_tiny(control: str | None = None, rows: int | None = None) -> dict:
    with open(osp.join(TINY, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    cell = harness.load_cell("tiny_batched", bench, base=TINY)
    if rows is not None:
        cell["mix"] = harness.merge(cell["mix"], {
            "rollout_steps": rows,
            "overrides": {"trainer": {"rollout_steps": rows}}})
    overrides = (cell["config_data"]["lower_precision"][control]
                 if control else None)
    return run.run_cell(
        bench, cell, seed=2**31 + 12345, seconds=1.0, trace=False,
        control=overrides,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        t0=time.perf_counter())


def test_the_batched_cell_runs_to_completion_and_verifies():
    line = run_tiny()
    assert line["correct"] and line["checks_failed"] == []
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert line["checks"]["episodes_terminated_share"] == [1.0, 0.9]
    assert line["checks"]["batched_terminated_count_gap"] == [0, 0]
    assert line["checks"]["batched_ended_rows_gap"] == [0, 0]


def test_bfloat16_compute_fails_the_logprob_comparison():
    line = run_tiny(control="bf16_compute")
    assert not line["correct"]
    assert line["checks_failed"] == ["logprob_gap_mean"]


def test_a_scan_too_short_for_the_episodes_is_not_correct():
    line = run_tiny(rows=24)
    assert not line["correct"]
    assert line["checks_failed"] == ["episodes_terminated_share"]
    assert line["checks"]["episodes_terminated_share"][0] < 0.9
