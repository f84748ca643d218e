"""The trained-policy sweep driver's CPU tests, as `test_driver_sweep.py`
for its driver: the cell at a tiny size (2 blocks of 128 lanes x 8 rows
a chunk, 6 jobs an episode on 3 executors, seeded random weights) builds,
staggers, measures and verifies, `correct: true`, the engine comparison
through the two programs the run compiled and no third; the bfloat16
control fails the policy's comparison (on the CPU by the plain mean)
and nothing else; a doctored
record (a swapped stage, a shifted time, a result off by 1e-3) fails the
engine's; a doctored observation fails the observation's. Not tier-1
(each compiles the sweep's chunk twice).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import os.path as osp
import time

import numpy as np
import pytest

from benchmarks import harness, run
from benchmarks.drivers import sweep_decima

TINY = osp.join(harness.HERE, "tests", "data", "tiny_sweep_decima")


def run_tiny(control: str | None = None) -> dict:
    with open(osp.join(TINY, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    cell = harness.load_cell("tiny_sweep_decima", bench, base=TINY)
    overrides = (cell["config_data"]["lower_precision"][control]
                 if control else None)
    return run.run_cell(
        bench, cell, seed=2**31 + 12345, seconds=1.0, trace=False,
        control=overrides,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        t0=time.perf_counter())


def test_the_decima_sweep_cell_staggers_measures_and_verifies():
    line = run_tiny()
    assert line["correct"] and line["checks_failed"] == []
    assert line["attempted"] >= 2 and line["failed"] == 0
    checks = line["checks"]
    assert checks["compilations_in_window"] == [0, 0]
    assert checks["logprob_sample"][0] == 64
    assert checks["logprob_gap_mean"][0] < 1e-5  # float32 on the CPU
    assert checks["engine_mismatches"] == [0, 0]
    assert checks["engine_mismatches_source"] == [0, 0]
    assert checks["engine_observation_mismatches"] == [0, 0]
    assert checks["engine_programs_compiled"] == [0, 0]
    assert checks["engine_ends_compared"][0] >= 1
    assert checks["sweep_sequences_shared"] == [0, 0]
    assert checks["episodes_finished_share"][0] >= 0.03


def test_a_net_computed_in_bfloat16_fails_the_policy_check_alone():
    """On the CPU float32 is exact to 1e-5, so at the tiny size it is
    the plain mean that parts the control (limit 1e-4), as in
    `test_driver_collect.py`; the share at the stated precision is the
    chip's. The engine comparison replays what the program decided, so
    it holds whatever the net computes in."""
    line = run_tiny(control="bf16_compute")
    assert not line["correct"]
    assert line["checks_failed"] == ["logprob_gap_mean"]
    value, limit = line["checks"]["logprob_gap_mean"]
    assert value > limit == 0.0001
    assert 0.2 < line["checks"]["logprob_stated_gap_quantile_ratio"][0] < 5
    assert line["checks"]["engine_mismatches"] == [0, 0]


@pytest.mark.parametrize("fault", ["stage", "time", "result"])
def test_a_doctored_record_fails_the_engine_comparison(monkeypatch, fault):
    """The record the engine comparison reads, doctored after the
    program wrote it: the stages of two rows of one lane swapped (the
    simulator cannot take one of them, or goes another way), a
    decision's time shifted by a millisecond, a stored average job
    completion time off by a thousandth."""
    real = sweep_decima.record_arrays

    def doctored(rec):
        out = {k: v.copy() for k, v in real(rec).items()}
        if out["valid"].shape[1] != sweep_decima.BLOCK:
            return out  # the source block's records only
        if fault == "time":
            out["wall_time"][3] += 1.0
        elif fault == "result":
            out["avg_jct"] *= 1.001
        else:
            out["stage"][[2, 3]] = out["stage"][[3, 2]]
            out["job"][[2, 3]] = out["job"][[3, 2]]
        return out

    monkeypatch.setattr(sweep_decima, "record_arrays", doctored)
    line = run_tiny()
    assert "engine_mismatches_source" in line["checks_failed"]
    assert set(line["checks_failed"]) <= {
        "engine_mismatches", "engine_mismatches_source",
        "engine_ends_compared",  # a replay that parted ends elsewhere
        "engine_observation_mismatches"}  # and observes something else
    assert line["checks"]["engine_mismatches_source"][0] > 0
    assert np.isfinite(line["checks"]["logprob_stated_gap_quantile_ratio"][0])


def test_an_observation_the_plain_lane_does_not_make_fails(monkeypatch):
    """What the program observes, doctored where the driver reads it: a
    stage of one copy of a replayed source lane shown as schedulable
    (or hidden). The rows replay as they did; the observation's
    comparison alone fails."""
    real = sweep_decima.stored_observation

    def doctored(ctx, env, lanes):
        stored = real(ctx, env, lanes)
        if len(lanes) == 64:
            return stored  # the policy's sample
        flipped = np.asarray(stored.schedulable).copy()
        flipped[1, 0] = ~flipped[1, 0]
        return stored.replace(schedulable=flipped)

    monkeypatch.setattr(sweep_decima, "stored_observation", doctored)
    line = run_tiny()
    assert line["checks_failed"] == ["engine_observation_mismatches"]
    assert line["checks"]["engine_observation_mismatches"] == [1, 0]
