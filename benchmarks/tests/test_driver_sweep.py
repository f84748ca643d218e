"""The sweep driver's CPU tests, as `test_driver_batched.py` for its
driver: the cell at a tiny size (2 blocks of 128 lanes x 8 rows a chunk,
4 jobs an episode on 3 executors) builds, staggers, measures and
verifies, the engine comparison through the two programs the run
compiled and no third; the lower-precision control fails the engine
comparison (in the timed program and in the source block's) and nothing
else; a plain result over the wrong jobs parts from the stored one; a
seed law that gives every lane one key is not correct; the episodes a
window ends are held to the configuration's limit. Not tier-1 (each
compiles the sweep's chunk twice).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import os.path as osp
import time

import pytest

from benchmarks import harness, run

TINY = osp.join(harness.HERE, "tests", "data", "tiny_sweep")


def run_tiny(control: str | None = None, mix: dict | None = None,
             limits: dict | None = None) -> dict:
    with open(osp.join(TINY, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    cell = harness.load_cell("tiny_sweep", bench, base=TINY)
    cell["mix"] = harness.merge(cell["mix"], mix or {})
    cell["config_data"] = harness.merge(
        cell["config_data"], {"limits": limits or {}})
    overrides = (cell["config_data"]["lower_precision"][control]
                 if control else None)
    return run.run_cell(
        bench, cell, seed=2**31 + 12345, seconds=1.0, trace=False,
        control=overrides,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        t0=time.perf_counter())


def test_the_sweep_cell_staggers_measures_and_verifies():
    line = run_tiny()
    assert line["correct"] and line["checks_failed"] == []
    assert line["attempted"] >= 2 and line["failed"] == 0
    checks = line["checks"]
    assert checks["compilations_in_window"] == [0, 0]
    assert checks["policy_mismatches_recorded"] == [0, 0]
    assert checks["policy_mismatches_returned"] == [0, 0]
    assert checks["engine_mismatches"] == [0, 0]
    assert checks["engine_mismatches_source"] == [0, 0]
    assert checks["engine_programs_compiled"] == [0, 0]
    assert checks["engine_ends_compared"][0] >= 1
    assert checks["sweep_sequences_shared"] == [0, 0]
    assert checks["episodes_finished_share"][0] >= 0.03


def test_an_int8_bank_fails_the_engine_comparison_alone():
    line = run_tiny(control="bank_int8")
    assert not line["correct"]
    assert line["checks_failed"] == [
        "engine_mismatches", "engine_mismatches_source"]
    assert line["checks"]["engine_mismatches"][0] > 0
    assert line["checks"]["engine_programs_compiled"] == [0, 0]


def test_a_result_over_the_wrong_jobs_is_not_the_stored_one(monkeypatch):
    """The stored average job completion time is held to the plain
    simulator's at every episode end compared: a plain result that
    leaves one job out parts from it, and from nothing else."""
    from benchmarks.reference import sweep_np

    real = sweep_np.episode_result

    def without_a_job(ep, decisions):
        whole = real(ep, decisions)
        ep.jobs = ep.jobs[1:]
        return dict(whole, avg_jct=real(ep, decisions)["avg_jct"])

    monkeypatch.setattr(sweep_np, "episode_result", without_a_job)
    line = run_tiny()
    assert line["checks_failed"] == [
        "engine_mismatches", "engine_mismatches_source"]
    ends = line["checks"]["engine_ends_compared"][0]
    assert 1 <= line["checks"]["engine_mismatches"][0] <= ends


def test_a_seed_law_that_shares_keys_is_not_correct(monkeypatch):
    """Every lane under lane 0's key: the lanes re-seed into the same
    job sequences."""
    from sparksched_tpu import sweep

    real = sweep.lane_keys
    monkeypatch.setattr(
        sweep, "lane_keys", lambda key, lanes: real(key, lanes * 0))
    line = run_tiny()
    assert not line["correct"]
    assert "sweep_sequences_shared" in line["checks_failed"]


def test_the_finished_episodes_are_held_to_the_configurations_limit():
    """A window that ends fewer episodes than the configuration asks
    for (here more than a window of this length can end) is not
    correct, by that check alone."""
    line = run_tiny(limits={"episodes_finished_share": 50.0})
    assert line["checks_failed"] == ["episodes_finished_share"]
    assert 0.03 <= line["checks"]["episodes_finished_share"][0] < 50.0


def test_lanes_that_are_no_whole_blocks_are_refused():
    with pytest.raises(SystemExit, match="no whole blocks of 128"):
        run_tiny(mix={"lanes": 200})
