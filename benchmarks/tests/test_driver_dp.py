"""The mesh driver's CPU tests, as `test_driver_collect.py` for
`collect_rollout`: its build, warm-up, measure and verify end to end at
a tiny size on four virtual devices (8 lanes x 12 rows, the mesh check
over 6 rows), the lower-precision control, and the timed path broken
underneath: two lanes swapped across shards. Not tier-1 (they compile
the collector three times).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import json
import os.path as osp
import time

import pytest

from __graft_entry__ import force_virtual_cpu_devices

# four virtual CPU devices, before jax looks for any: the cell builds
# its dp=4 mesh without chips (a module of the tests' own, so the other
# drivers' tests keep the one device they were written for when run
# alone)
force_virtual_cpu_devices(4)

from benchmarks import harness, run  # noqa: E402
from benchmarks.drivers import collect_rollout  # noqa: E402

TINY = osp.join(harness.HERE, "tests", "data", "tiny_dp4")
# the leaves held to equality up to a lane's parting (the actions and
# the log-prob are held to limits)
MESH_LEAVES = ("obs", "wall_times", "reward", "resets")


@pytest.fixture(autouse=True)
def _default_prng():
    import jax

    before = jax.config.jax_default_prng_impl
    yield
    jax.config.update("jax_default_prng_impl", before)


def run_tiny(control: str | None = None) -> dict:
    with open(osp.join(TINY, "BENCHMARK.json")) as fp:
        bench = json.load(fp)
    cell = harness.load_cell("tiny_dp4", bench, base=TINY)
    overrides = (cell["config_data"]["lower_precision"][control]
                 if control else None)
    return run.run_cell(
        bench, cell, seed=2**31 + 12345, seconds=1.0, trace=False,
        control=overrides,
        device={"platform": "cpu", "kind": "cpu", "count": 4},
        t0=time.perf_counter())


def test_the_mesh_cell_runs_whole_collections_and_verifies(capsys):
    import jax

    assert len(jax.devices()) >= 4
    line = run_tiny()
    assert line["correct"] and line["checks_failed"] == []
    assert line["attempted"] >= 2 and line["failed"] == 0
    said = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    checks = {s["check"]: s for s in said if "check" in s}
    assert {f"mesh_unequal.{k}" for k in MESH_LEAVES} <= set(checks)
    # on the CPU the two programs store the same bits
    assert checks["mesh_lgprob_unequal_share"]["value"] == 0.0
    assert checks["mesh_lgprob_gap_mean"]["value"] == 0.0
    assert checks["mesh_lanes_parted"]["value"] == 0
    assert checks["mesh_slots"]["value"] > 8  # every lane, several slots
    mesh = [s["mesh_check"] for s in said if "mesh_check" in s][0]
    assert mesh["first_parting_slot"] == -1 and mesh["collection"] >= 2
    assert mesh["rows"] == 6
    assert mesh["slots_before_parting"] == mesh["slots"]
    assert mesh["lgprob_unequal_by_chip"] == [0, 0, 0, 0]
    assert {"build", "collect", "compare",
            "backend_compile_duration"} <= set(mesh["seconds"])


def test_bfloat16_compute_fails_the_gaps_and_the_mesh_check(capsys):
    """The control computes the mesh's side in bfloat16; the one-chip
    reference of (c) stays at the stated precision, so (c) reads a
    fault on one side: log-probs that differ at most slots."""
    line = run_tiny(control="bf16_compute")
    assert not line["correct"]
    assert {"logprob_gap_mean", "mesh_lgprob_unequal_share",
            "mesh_lgprob_gap_mean"} <= set(line["checks_failed"])
    said = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    share = {s["check"]: s for s in said if "check" in s}[
        "mesh_lgprob_unequal_share"]["value"]
    assert share > 0.5


def test_two_lanes_swapped_across_shards_are_not_correct(monkeypatch):
    """Lane 1 (the first device's) and lane 6 (the last's) of what the
    timed path returns change places: every count still holds, and the
    mesh check does not."""
    import jax

    real = collect_rollout._call_collector
    swap = [0, 6, 2, 3, 4, 5, 1, 7]

    def call(trainer, params, i, rng):
        ro, state, telem = real(trainer, params, i, rng)
        keep = (ro.final_state, ro.final_reset_count)
        ro = jax.tree_util.tree_map(lambda a: a[jax.numpy.asarray(swap)], ro)
        return ro.replace(final_state=keep[0],
                          final_reset_count=keep[1]), state, telem

    monkeypatch.setattr(collect_rollout, "_call_collector", call)
    line = run_tiny()
    assert not line["correct"]
    failed = set(line["checks_failed"])
    assert failed and failed <= {f"mesh_unequal.{k}" for k in MESH_LEAVES} | {
        "mesh_lanes_parted", "mesh_lgprob_unequal_share",
        "mesh_lgprob_gap_mean"}
    assert "mesh_lanes_parted" in failed  # two lanes, of a limit of none
