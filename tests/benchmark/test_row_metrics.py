"""The six per-layer metrics of a decision row (PR 27): each over a
window made by hand, and each left out where there is nothing to read:
an empty window, a program whose row counters are all zero, and a
program that has no row counters at all (the parent commit is traced
with this PR's metric files laid over it)."""

import pytest

from benchmarks import harness


def _summary(decisions, drain_total, **row):
    return {"decisions": decisions, "row": dict(
        row, lane_rows=row["rows"] * 4,
        drain_lane_iters_executed=row["drain_batch_iters"] * 4,
        drain_iters_total=drain_total)}


# two collections of 4 lanes x 10 rows
WINDOW = {
    "telemetry": [
        _summary(30, 60, rows=10, rows_live=9, rows_full_width=2,
                 drain_batch_iters=25),
        _summary(26, 40, rows=10, rows_live=8, rows_full_width=0,
                 drain_batch_iters=15)],
    "trace": {"window_s": 0.5, "busy_s": 0.5, "units": 0.025,
              "scopes": {"env/micro_step": 0.3, "decima/gnn": 0.1}},
}
ZERO_ROWS = {"telemetry": [_summary(30, 0, rows=0, rows_live=0,
                                    rows_full_width=0, drain_batch_iters=0)]}
NO_ROW_BLOCK = {
    "telemetry": [{"decisions": 30, "micro_steps": 70}],
    "trace": {"window_s": 0.5, "busy_s": 0.5, "units": 0.025,
              "scopes": {"decima/gnn": 0.1}}}

METRICS = [
    ("rollout.engine_device_s", 0.3 / 0.025),
    ("rollout.drain_iters_per_row", 40 / 20),
    ("rollout.drain_batch_tax", 160 / 100),
    ("rollout.gnn_full_width_share", 2 / 20),
    ("rollout.lane_row_occupancy", 56 / 80),
    ("rollout.live_row_share", 17 / 20),
]


def entries_hold(bench: dict, base: str = harness.HERE) -> None:
    """The six stand where PR 27 put them, after the eight that were
    there; each moves the rate and lists `decima_rollout` first."""
    assert [m["name"] for m in bench["per_layer"][8:14]] == [
        name for name, _ in METRICS]
    for entry in bench["per_layer"][8:14]:
        assert entry["moves"] == "rollout_decisions_per_s"
        assert entry["workloads"][0] == "decima_rollout"


def test_the_six_stand_after_the_eight_that_were_there():
    entries_hold(harness.load_benchmark())


@pytest.mark.parametrize("name, want", METRICS)
def test_row_metric_reads_its_counters_or_its_scope(name, want):
    assert harness.read_layer_metric(name, WINDOW) == pytest.approx(want)


@pytest.mark.parametrize("name", [n for n, _ in METRICS])
@pytest.mark.parametrize("window", [{}, ZERO_ROWS, NO_ROW_BLOCK],
                         ids=["empty", "zero_rows", "no_row_block"])
def test_row_metric_is_left_out_where_there_is_nothing_to_read(name, window):
    assert harness.read_layer_metric(name, window) is None


def test_the_metrics_the_benchmark_had_still_read_a_window_without_rows():
    """The parent's window: the accepted metrics read it as before."""
    read = harness.read_layer_metric
    assert read("rollout.micro_per_decision", NO_ROW_BLOCK) == 70 / 30
    assert read("rollout.gnn_device_s", NO_ROW_BLOCK) == pytest.approx(4.0)
