"""Runs a tiny cell through `run.run_cell` on whatever backend jax has
(the look for a TPU is `run.run`'s, and is skipped)."""

import json
import os.path as osp
import time

from benchmarks import harness, run

TINY = osp.join(harness.HERE, "tests", "data", "tiny")


def run_tiny(name: str, *, control: str | None = None, seconds: float = 1.0,
             seed: int = 2**31 + 12345) -> dict:
    with open(osp.join(TINY, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    cell = harness.load_cell(name, bench, base=TINY)
    overrides = (cell["config_data"]["lower_precision"][control]
                 if control else None)
    return run.run_cell(
        bench, cell, seed=seed, seconds=seconds, trace=False,
        control=overrides,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        t0=time.perf_counter())
