"""The next cell comes as files alone. Every module under
`tests/benchmark/` that has rules on the entries of `BENCHMARK.json`
keeps them as a function of a benchmark, and here each is held on a
temporary copy to which a later PR's cell was added the way the contract
asks (`test_overlay.a_copy_with_a_cell_appended`: a sixth
configuration, a traffic file, a one-chip cell, its name at the end of
the rate's `workloads`, two twin per-layer metrics at the end of
`per_layer`, no file that was there changed): the rules pass there, and
fail on the same copy with the new entries put FIRST, and with an older
cell's metric that no longer lists its cell, so none was loosened into
nothing. A test that is added with rules on the entries gets a case
here; one that pins "the last entry" or a whole list fails its case."""

import copy
import os.path as osp

import pytest

from benchmarks import harness
from tests.benchmark import (
    test_batched,
    test_harness,
    test_overlay,
    test_overlay_dp4,
    test_program_span,
    test_row_metrics,
    test_sweep,
)

# module -> (its rules, a function of a benchmark and its directory; a
# per-layer metric of an older cell that they watch; that cell)
RULES = {
    "test_batched": (
        test_batched.entries_hold, "batch20.drain_device_s", "decima_batch20"),
    "test_program_span": (
        test_program_span.entries_hold, "setup.trace_s", "decima_stream"),
    "test_overlay_dp4": (
        test_overlay_dp4.entries_hold, "dp4.collective_device_s",
        "decima_rollout_dp4"),
    "test_sweep": (test_sweep.entries_hold, "sweep.chunk_s", "sweep_fair"),
    "test_row_metrics": (
        test_row_metrics.entries_hold, "rollout.drain_batch_tax",
        "decima_rollout"),
    "test_overlay": (
        lambda bench, base: test_overlay.old_cells_read_what_they_read(bench),
        "stream.reseeds_per_row", "decima_stream"),
    "test_harness": (
        lambda bench, base: test_harness.keeps_to_the_contract(
            bench, base=base, root=osp.dirname(base), probe=True,
            parent=harness.load_benchmark()),
        "rollout.collect_s", "decima_rollout"),
}
PROBE_METRICS = {"probe.collect_s": "rollout.collect_s",
                 "probe.engine_device_s": "rollout.engine_device_s"}


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    """The copy: its benchmark and its directory."""
    root = tmp_path_factory.mktemp("next_cell")
    files = {probe: test_overlay.metric_spec(twin)
             for probe, twin in PROBE_METRICS.items()}
    bench, base, before = test_overlay.a_copy_with_a_cell_appended(root, files)
    assert all(p.read_bytes() == b for p, b in before.items())
    return bench, str(base)


def put_first(bench: dict) -> dict:
    """The copy's benchmark with every appended entry at the head of
    its list instead."""
    broken = copy.deepcopy(bench)
    for key, new in (("configs", 1), ("workloads", 1),
                     ("per_layer", len(PROBE_METRICS))):
        broken[key] = broken[key][-new:] + broken[key][:-new]
    for m in broken["end_to_end"]:
        if test_overlay.PROBE_CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"][-1:] + m["workloads"][:-1]
    return broken


def lost_its_cell(bench: dict, metric: str, cell: str) -> dict:
    """The copy's benchmark in which `metric` no longer lists `cell`
    (and, where it listed no other, lists the new cell instead)."""
    broken = copy.deepcopy(bench)
    entry = {m["name"]: m for m in broken["per_layer"]}[metric]
    assert cell in entry["workloads"]
    entry["workloads"] = [w for w in entry["workloads"] if w != cell] or [
        test_overlay.PROBE_CELL]
    return broken


@pytest.mark.parametrize("module", list(RULES))
def test_the_rules_on_the_entries_hold_with_a_sixth_cell_appended(
        appended, module):
    bench, base = appended
    was = harness.load_benchmark()  # the appended entries follow its own
    assert [w["name"] for w in bench["workloads"]].index(
        test_overlay.PROBE_CELL) == len(was["workloads"]) >= 5
    assert [m["name"] for m in bench["per_layer"]].index(
        list(PROBE_METRICS)[0]) == len(was["per_layer"])
    RULES[module][0](copy.deepcopy(bench), base)


@pytest.mark.parametrize("fault", ["put_first", "lost_its_cell"])
@pytest.mark.parametrize("module", list(RULES))
def test_the_rules_fail_where_an_entry_moved_or_a_metric_lost_its_cell(
        appended, module, fault):
    bench, base = appended
    rules, metric, cell = RULES[module]
    broken = (put_first(bench) if fault == "put_first"
              else lost_its_cell(bench, metric, cell))
    with pytest.raises(AssertionError):
        rules(broken, base)
