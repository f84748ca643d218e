"""The overlay rules (`test_overlay.py`) for what PR 34 added: the
configuration `decima_tpch_50x200_dp4`, the cell `decima_rollout_dp4`,
the driver `collect_rollout_dp`, the reader `trace_value` and seventeen
`dp4.*` metrics (a twin for each of `decima_rollout`'s fourteen, and
three of the mesh's own). The parent's program runs the new cell (it has
`config/decima_tpch_multichip.yaml` and the mesh) and is traced in
every cell with these files laid over it. What is pinned is about what
PR 34 added; what later PRs add to the cell follows it and is held by
the rules of `test_overlay.py`."""

import math

import pytest

from benchmarks import harness, trace_reduce
from tests.benchmark.test_overlay import (
    FIRST_METRICS,
    PARENT_SUMMARY,
    PARENT_WINDOW,
    may_read_nothing,
    metric_spec,
)

BENCH = harness.load_benchmark()
CELL = "decima_rollout_dp4"
DP4 = [m for m in BENCH["per_layer"] if m["name"].startswith("dp4.")]
PR34 = [m for m in DP4 if m["name"] in FIRST_METRICS[CELL]]
# what a four-chip traced window adds to the parent's: the reducer's
# collective keys (it is the benchmark's own file, the same on both
# sides of a comparison)
MESH_TRACE = dict(
    PARENT_WINDOW["trace"], collective_s=0.02, collective_exposed_s=0.015)
# and a window of a program that has every scope a data file names
FULL_TRACE = dict(MESH_TRACE, unscoped_s=0.04, scopes={
    s: 0.01 for s in trace_reduce.scope_names(harness.metric_scopes())})


def entries_hold(bench: dict, base: str = harness.HERE) -> None:
    """PR 34's seventeen lead the `dp4.*` metrics, every one of which
    lists the cell and nothing else; the cell is the first that takes
    four chips, the third in the rate's `workloads`, and its per-layer
    metrics start with the twenty `dp4.*` it read before any other
    family listed it."""
    dp4 = [m for m in bench["per_layer"] if m["name"].startswith("dp4.")]
    pr34 = [m for m in dp4 if m["name"] in FIRST_METRICS[CELL]]
    assert len(pr34) == 17 and dp4[:17] == pr34
    for m in dp4:
        assert m["workloads"] == [CELL], m["name"]
        assert m["moves"] == "rollout_decisions_per_s"
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 4
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4][0] == (
        CELL)
    rate = {m["name"]: m for m in bench["end_to_end"]}[
        "rollout_decisions_per_s"]
    assert rate["workloads"][:3] == ["decima_rollout", "decima_stream", CELL]
    read = [m["name"] for m in harness.metrics_of_cell(
        bench, CELL, "per_layer")]
    assert len(dp4) >= 20 and read[:20] == [m["name"] for m in dp4[:20]]


def test_every_entry_added_lists_the_new_cell_and_nothing_else():
    entries_hold(BENCH)


@pytest.mark.parametrize("name", [m["name"] for m in DP4])
def test_a_dp4_reader_reads_the_parents_window(name):
    """On the parent's window a reader gives a number, or None for the
    one metric over the counter the parent lacks and for a metric over
    a scope the window does not hold; on a window with the mesh's trace
    keys, every scope and the counter, every one gives a number."""
    value = harness.read_layer_metric(name, PARENT_WINDOW)
    if name == "dp4.lane_syncs_per_row":
        assert value is None
    elif value is None:
        assert "scope" in metric_spec(name)
        assert may_read_nothing(name, PARENT_WINDOW)
    else:
        assert isinstance(value, float) and math.isfinite(value)
    summary = dict(PARENT_SUMMARY, row=dict(
        PARENT_SUMMARY["row"], lane_syncs=52000))
    window = dict(PARENT_WINDOW, telemetry=[summary] * 3, trace=FULL_TRACE)
    assert math.isfinite(harness.read_layer_metric(name, window))
    assert harness.read_layer_metric(name, {}) is None


def test_the_mesh_metrics_read_the_reducers_keys():
    window = dict(PARENT_WINDOW, trace=MESH_TRACE, telemetry=[dict(
        PARENT_SUMMARY, row=dict(PARENT_SUMMARY["row"], lane_syncs=52000))])
    read = harness.read_layer_metric
    units = MESH_TRACE["units"]
    assert read("dp4.collective_device_s", window) == pytest.approx(
        0.02 / units)
    assert read("dp4.collective_exposed_share", window) == pytest.approx(3.0)
    assert read("dp4.lane_syncs_per_row", window) == 52000 / 800
    # the keys are the reducer's own
    reduced = trace_reduce.reduce_events(
        {0: [{"name": "all-reduce.1", "start": 0.0, "dur": 0.1,
              "text": "all-reduce.1"}],
         1: [{"name": "fusion.1", "start": 0.0, "dur": 0.3,
              "text": "fusion.1"}]},
        [], window=(0.0, 1.0), chips=2)
    assert {"collective_s", "collective_exposed_s"} <= set(reduced)
    window = {"trace": dict(reduced, units=0.5)}
    assert read("dp4.collective_device_s", window) == pytest.approx(0.2)
    assert read("dp4.collective_exposed_share", window) == pytest.approx(10.0)


def test_the_summary_gains_one_row_key_where_rows_were_counted():
    """`summarize` gives `row.lane_syncs` where the collector counted
    rows, beside every key the parent's `row` block had; over a
    telemetry of zeros the key reads 0 or is absent."""
    import jax.numpy as jnp

    from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros_like

    zeros = telemetry_zeros_like((2,))
    assert not summarize(zeros)["row"].get("lane_syncs")
    counted = summarize(zeros.replace(
        rows=jnp.full((2,), 3), lane_syncs=jnp.full((2,), 40)))
    assert counted["row"]["lane_syncs"] == 40
    assert set(counted["row"]) >= set(PARENT_SUMMARY["row"]) | {
        "lane_rows_frozen", "lane_syncs"}


def test_the_mesh_driver_imports_and_ends_at_once_without_its_config():
    """Rule 3: the driver imports with nothing of the program loaded
    (the parent's program lacks nothing it names), and on a program
    without the multi-chip configuration `build` ends naming it."""
    import sys

    from benchmarks.drivers import collect_rollout_dp

    cell = harness.load_cell(CELL, BENCH)
    assert cell["mix"]["driver"] == "collect_rollout_dp"
    assert cell["config_data"]["overrides"]["parallel"] == {"dp": 4}
    cell["config_data"] = dict(
        cell["config_data"], program_config="config/no_such_mesh.yaml")
    before = set(sys.modules)
    with pytest.raises(SystemExit, match="no config/no_such_mesh.yaml"):
        collect_rollout_dp.build(cell, 1)
    assert not any(m.startswith("sparksched_tpu.trainers")
                   for m in set(sys.modules) - before)


def _rollout(lanes=8, rows=6, t=10, seed=0):
    """A rollout's per-decision leaves by hand: `t` slots a lane, the
    first `rows` - lane % 3 of them decided within `rows` rows."""
    import numpy as np

    rng = np.random.default_rng(seed)
    ndec = np.asarray([rows - (lane % 3) for lane in range(lanes)])
    ro = {
        "obs": {"remaining": rng.integers(0, 9, (lanes, t, 4)),
                "job_mask": rng.random((lanes, t, 3)) > 0.5},
        "stage_idx": rng.integers(0, 5, (lanes, t)),
        "job_idx": rng.integers(0, 5, (lanes, t)),
        "num_exec_k": rng.integers(0, 5, (lanes, t)),
        "lgprob": rng.standard_normal((lanes, t)).astype("float32"),
        "wall_times": rng.random((lanes, t + 1)).astype("float32"),
        "reward": rng.standard_normal((lanes, t)).astype("float32"),
        "resets": rng.random((lanes, t)) > 0.8,
        "valid": np.arange(t)[None, :] < (ndec + 3)[:, None],
    }
    ref = {k: (v if isinstance(v, dict) else v[:, :rows + (
        k == "wall_times")].copy()) for k, v in ro.items()}
    ref["obs"] = {k: v[:, :rows].copy() for k, v in ro["obs"].items()}
    ref["valid"] = np.arange(rows)[None, :] < ndec[:, None]
    return ref, ro, ndec


def _gap(ref, ro):
    import jax

    from benchmarks.drivers.collect_rollout_dp import mesh_gap

    return jax.device_get(jax.jit(mesh_gap)(ref, ro))


def test_the_mesh_check_reads_equal_rollouts_as_equal():
    ref, ro, ndec = _rollout()
    # rows after R added to the latest slot's reward and reset flag
    lanes = range(len(ndec))
    ro["reward"][lanes, ndec - 1] += 1.0
    ro["resets"][lanes, ndec - 1] = True
    found = _gap(ref, ro)
    assert found["slots"] == ndec.sum() == found["slots_before_parting"]
    assert found["valid_lost"] == 0 and found["lanes_parted"] == 0
    assert found["first_parting_slot"] == -1
    assert set(found["unequal"]) == {"obs", "wall_times", "reward", "resets"}
    assert all(n == 0 for n in found["unequal"].values())
    assert found["lgprob_gap_max"] == 0.0
    assert found["lgprob_unequal_per_lane"].sum() == 0


def test_a_lane_whose_action_fell_the_other_way_parts_and_is_no_fault():
    """Lane 2's sampled stage differs at slot 3, and from there on it
    is another history (every leaf differs after the slot; the
    observation AT the slot is still the same): one lane parted, nothing
    unequal. A log-prob that differs before the parting is measured."""
    ref, ro, ndec = _rollout()
    ro["stage_idx"][2, 3] += 1
    for k in ("lgprob", "reward", "resets", "num_exec_k"):
        ro[k][2, 3:] = ro[k][3, 3:]
    ro["wall_times"][2, 4:] += 1.0
    ro["obs"]["remaining"][2, 4:] += 1
    ro["lgprob"][5, 1] += 0.0625
    found = _gap(ref, ro)
    assert found["lanes_parted"] == 1 and found["first_parting_slot"] == 3
    assert all(n == 0 for n in found["unequal"].values())
    assert found["slots_before_parting"] == ndec.sum() - (ndec[2] - 3)
    assert found["lgprob_gap_max"] == 0.0625 == found["lgprob_gap_sum"]
    assert found["lgprob_unequal_per_lane"].tolist() == [
        0, 0, 0, 0, 0, 1, 0, 0]


@pytest.mark.parametrize("leaf, slot", [("obs", 3), ("wall_times", 2),
                                        ("reward", 2), ("resets", 1)])
def test_a_state_that_differs_before_the_actions_part_is_a_fault(leaf, slot):
    """The same lane, with one stored value of the engine's changed
    at or before the slot at which its actions part: the observation
    and the time count up to that slot, the reward and the reset flag
    before it."""
    ref, ro, _ = _rollout()
    ro["stage_idx"][2, 3] += 1
    target = ro["obs"]["remaining"] if leaf == "obs" else ro[leaf]
    target[2, slot] = target[2, slot] + 1 if leaf != "resets" else (
        not target[2, slot])
    found = _gap(ref, ro)
    assert found["unequal"][leaf] == 1 and found["lanes_parted"] == 1
    assert sum(found["unequal"].values()) == 1


@pytest.mark.parametrize("leaf", ["obs", "wall_times", "reward", "all"])
def test_two_lanes_swapped_across_shards_fail_the_mesh_check(leaf):
    """Lane 1 (the first shard of four) and lane 6 (the last) swapped,
    in one leaf of the engine's or in the whole rollout: the
    observation (or the time) differs at slot 0, where no action has
    parted the lanes yet."""
    import jax

    ref, ro, ndec = _rollout()
    swap = [0, 6, 2, 3, 4, 5, 1, 7]
    if leaf == "all":
        ro = jax.tree_util.tree_map(lambda a: a[swap], ro)
    else:
        ro[leaf] = jax.tree_util.tree_map(lambda a: a[swap], ro[leaf])
    found = _gap(ref, ro)
    if leaf == "all":
        assert found["lanes_parted"] == 2 and found["first_parting_slot"] == 0
        assert found["unequal"]["obs"] == found["unequal"]["wall_times"] == 2
    else:
        assert found["lanes_parted"] == 0
        assert found["unequal"][leaf] >= min(ndec[1], ndec[6]) - 1


def test_a_slot_the_sharded_rollout_lost_fails_the_mesh_check():
    ref, ro, ndec = _rollout()
    ro["valid"][3, ndec[3] - 1:] = False
    assert _gap(ref, ro)["valid_lost"] == 1
