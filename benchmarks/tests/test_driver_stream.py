"""The streaming driver's CPU tests, as `test_driver_collect.py` for
`collect_rollout`: its build, warm-up, measure and verify at a tiny size
(counts and correctness only), the carry threaded from collection to
collection, and the timed path broken underneath. Not tier-1 (they
compile the collector twice).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import os.path as osp
import time

import pytest

from benchmarks import harness, run
from benchmarks.drivers import collect_stream

TINY = osp.join(harness.HERE, "tests", "data", "tiny_stream")


@pytest.fixture(autouse=True)
def _default_prng():
    import jax

    before = jax.config.jax_default_prng_impl
    yield
    jax.config.update("jax_default_prng_impl", before)


def run_tiny(seconds: float = 1.0, seed: int = 2**31 + 12345) -> dict:
    with open(osp.join(TINY, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    cell = harness.load_cell("tiny_stream", bench, base=TINY)
    return run.run_cell(
        bench, cell, seed=seed, seconds=seconds, trace=False, control=None,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        t0=time.perf_counter())


def _broken(monkeypatch, change):
    real = collect_stream._call_collector

    def call(trainer, params, i, rng, carry):
        ro, carry, telem = real(trainer, params, i, rng, carry)
        return change(ro), carry, telem

    monkeypatch.setattr(collect_stream, "_call_collector", call)
    return run_tiny()


def test_the_streaming_collector_runs_and_verifies():
    line = run_tiny()
    assert line["correct"] and line["checks_failed"] == []
    assert line["attempted"] >= 2 and line["failed"] == 0


def test_a_carry_that_is_rebuilt_is_not_correct(monkeypatch):
    """Every collection from reset: the lanes do not persist."""
    real = collect_stream._call_collector
    monkeypatch.setattr(
        collect_stream, "_call_collector",
        lambda trainer, params, i, rng, carry: real(
            trainer, params, i, rng, None))
    line = run_tiny()
    assert not line["correct"]
    assert set(line["checks_failed"]) & {
        "stream_reset_ordinal_not_handed_on",
        "stream_remaining_rose_in_episode",
        "stream_template_moved_without_reseed"}


def test_a_dropped_reset_flag_is_not_correct(monkeypatch):
    line = _broken(
        monkeypatch, lambda ro: ro.replace(resets=ro.resets & False))
    assert not line["correct"]
    assert "stream_reseeds_gap" in line["checks_failed"]


def test_a_missing_configuration_ends_the_run_at_once(monkeypatch):
    with open(osp.join(TINY, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    cell = harness.load_cell("tiny_stream", bench, base=TINY)
    cell["config_data"]["program_config"] = "config/no_such_file.yaml"
    with pytest.raises(SystemExit, match="has no config/no_such_file.yaml"):
        collect_stream.build(cell, 1)
