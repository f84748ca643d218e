"""The overlay rules (PR 30): what a PR adds to the benchmark is laid
over its PARENT's program too, and a traced run of any cell there must
still end with a result line. So every per-layer reader, given a window
such as the parent's program makes (telemetry summaries with the keys
`obs/telemetry.summarize` gave at commit 7c4421a and nothing newer, a
trace with the reducer's fixed scopes and nothing newer), returns a
number or None and never raises; the metrics of a cell that was there
read what they read; the metrics over counters the parent lacks are left
out; and every entry added since lists its cells."""

import math

import pytest

from benchmarks import harness, trace_reduce

# `summarize` of commit 7c4421a (PR 28), written out by hand: every key
# it returned, the `row` block and `bulk_scan_steps_*` included; the
# counters of PR 30 (`reseeds_total`, `reset_evals_total`,
# `row.lane_rows_frozen`) absent
PARENT_SUMMARY = {
    "lanes": 128, "decisions": 81645, "commit_rounds": 41210,
    "micro_steps": 200438,
    "composition": {"decide": 0.4073, "fulfill": 0.0512, "event": 0.5415},
    "events_by_kind": {"job_arrival": 5120, "task_finished": 801300,
                       "executor_ready": 72270},
    "events_total": 878690, "events_per_decision": 10.762,
    "micro_per_decision": 2.455,
    "bulk": {"relaunch_events": 742000, "ready_events": 61000,
             "fulfill_hits": 90500},
    "fulfillments": 100760,
    "phase_iters": {"decide": 81645, "fulfill": 10260, "event": 108533,
                    "bulk": 99654},
    "bulk_scan_steps_total": 878620, "bulk_scan_steps_per_pass": 8.817,
    "drain_iters_mean": 928.07, "drain_iters_max": 1377,
    "drain_straggler_ratio": 1.484,
    "row": {"rows": 800, "rows_live": 800, "rows_full_width": 782,
            "drain_batch_iters": 5466, "lane_rows": 102400,
            "drain_lane_iters_executed": 699648,
            "drain_iters_total": 118793},
    "health_mask": 0, "health_bits": [], "unhealthy_lanes": 0,
    "loop_iters_mean": 6864.77, "loop_iters_max": 9885,
    "straggler_ratio": 1.44,
}
PARENT_WINDOW = {
    "scalars": [{"collect_seconds": 15.2, "collection": 1,
                 "decisions": 81645}] * 3,
    "telemetry": [PARENT_SUMMARY] * 3,
    "memory_peak_bytes": 4_534_084_608,
    "trace": {"window_s": 0.5, "busy_s": 0.498, "units": 0.5 / 15.2,
              "scopes": {s: 0.01 * (i + 1) for i, s in enumerate(
                  trace_reduce.KNOWN_SCOPES)}},
}
NEW_COUNTER_METRICS = {"stream.reseeds_per_row",
                       "stream.reset_evals_per_reseed",
                       "stream.frozen_lane_row_share"}
# the cells and per-layer metrics of commit 7c4421a
PARENT_CELLS = {"decima_rollout"}
PARENT_METRICS = (
    "rollout.collect_s", "rollout.micro_per_decision",
    "rollout.events_per_decision", "rollout.straggler_ratio",
    "rollout.gnn_device_s", "rollout.scatter_device_s",
    "rollout.idle_share", "rollout.hbm_peak_gb", "rollout.engine_device_s",
    "rollout.drain_iters_per_row", "rollout.drain_batch_tax",
    "rollout.gnn_full_width_share", "rollout.lane_row_occupancy",
    "rollout.live_row_share")

BENCH = harness.load_benchmark()
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


@pytest.mark.parametrize("name", PER_LAYER)
def test_every_reader_reads_the_parents_window_without_raising(name):
    value = harness.read_layer_metric(name, PARENT_WINDOW)
    assert value is None or (
        isinstance(value, (int, float)) and math.isfinite(value)), name
    if name in NEW_COUNTER_METRICS:
        assert value is None  # nothing to read: left out of the line
    else:
        assert value is not None


def test_a_traced_run_of_an_old_cell_reads_the_metrics_it_read():
    """Rule 1: nothing added since lists an old cell or lists none, so
    on the parent and on the change `decima_rollout` reads the same
    fourteen metrics."""
    for cell in PARENT_CELLS:
        read = [m["name"] for m in harness.metrics_of_cell(
            BENCH, cell, "per_layer")]
        assert read == list(PARENT_METRICS)
    for m in BENCH["per_layer"]:
        if m["name"] not in PARENT_METRICS:
            assert m.get("workloads") and not (
                set(m["workloads"]) & PARENT_CELLS), m["name"]


def test_the_streaming_cell_reads_ten_metrics_without_the_new_counters():
    names = [m["name"] for m in harness.metrics_of_cell(
        BENCH, "decima_stream", "per_layer")]
    assert len(names) == 13 and all(n.startswith("stream.") for n in names)
    read = {n for n in names
            if harness.read_layer_metric(n, PARENT_WINDOW) is not None}
    assert read == set(names) - NEW_COUNTER_METRICS


def test_the_streaming_cell_reads_all_thirteen_with_the_new_counters():
    summary = dict(PARENT_SUMMARY, reseeds_total=40,
                   reset_evals_total=200438,
                   row=dict(PARENT_SUMMARY["row"], lane_rows_frozen=9000))
    window = dict(PARENT_WINDOW, telemetry=[summary] * 2)
    read = harness.read_layer_metric
    assert read("stream.reseeds_per_row", window) == 40 / 800
    assert read("stream.reset_evals_per_reseed", window) == 200438 / 40
    assert read("stream.frozen_lane_row_share", window) == 9000 / 102400
    # no re-seed in the window: nothing to divide by, so left out
    window = dict(window, telemetry=[dict(summary, reseeds_total=0)])
    assert read("stream.reset_evals_per_reseed", window) is None


def test_the_summary_written_by_hand_is_the_parents():
    """The fixture's keys are `summarize`'s less what PR 30 added."""
    from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros_like

    now = summarize(telemetry_zeros_like((2,)))
    added = {"reseeds_total", "reset_evals_total"}
    assert set(now) - set(PARENT_SUMMARY) == added
    assert set(PARENT_SUMMARY) - set(now) == set()
    assert set(now["row"]) - set(PARENT_SUMMARY["row"]) == {
        "lane_rows_frozen"}
    assert all(now[k] == 0 for k in added)  # sync mode: printed, and 0


def test_the_streaming_driver_ends_at_once_without_its_configuration():
    """Rule 3: on a program without `config/decima_tpch_stream.yaml`
    `build` ends with a SystemExit naming the file, before it imports
    the program or touches jax."""
    import sys

    from benchmarks.drivers import collect_stream

    cell = harness.load_cell("decima_stream", BENCH)
    cell["config_data"] = dict(
        cell["config_data"], program_config="config/no_such_stream.yaml")
    before = set(sys.modules)
    with pytest.raises(SystemExit, match="no config/no_such_stream.yaml"):
        collect_stream.build(cell, 1)
    assert not any(m.startswith("sparksched_tpu.trainers")
                   for m in set(sys.modules) - before)
