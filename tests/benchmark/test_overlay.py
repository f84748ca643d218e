"""The overlay rules (PR 30; as rules since PR 41): what a PR adds to the
benchmark is laid over its PARENT's program too, and a traced run of any
cell there must still end with a result line. So every per-layer reader,
given a window such as the parent's program makes (telemetry summaries
with the keys `obs/telemetry.summarize` gave at commit 7c4421a and
nothing newer, a trace with the scopes the reducer knew then and nothing
newer), returns a number or None and never raises; None only where its
own data file says that there may be nothing to read (`may_lack`, or a
`scope` the trace does not hold); the metrics of a cell that was there
are still read, in their order, whatever follows them; and every entry
added since lists its cells.

Each rule is a function of a benchmark (`BENCHMARK.json` as a dict) and
its directory, run here on the repo's own and, in the last test, on a
temporary copy to which one more cell, a counter metric and a scope
metric were added as files and entries alone, under names no real
entry may take (`test_harness.PROBE`): a later PR's new cell, counter
or scope needs no edit of this file (`test_next_cell.py` holds every
module's rules on the entries on such a copy)."""

import json
import math
import os.path as osp
import shutil

import pytest

from benchmarks import harness, trace_reduce

# `summarize` of commit 7c4421a (PR 28), written out by hand: every key
# it returned, the `row` block and `bulk_scan_steps_*` included; the
# counters of PR 30 (`reseeds_total`, `reset_evals_total`,
# `row.lane_rows_frozen`) and of PR 34 (`row.lane_syncs`) absent
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
# the scopes the reducer matched at that commit (its tuple then)
PARENT_SCOPES = (
    "decima/gnn", "env/micro_step", "collect/scatter", "train/ppo_update",
    "serve/decide_batch", "serve/decide", "serve/dispatch", "serve/flush")
PARENT_WINDOW = {
    "scalars": [{"collect_seconds": 15.2, "collection": 1,
                 "decisions": 81645}] * 3,
    "telemetry": [PARENT_SUMMARY] * 3,
    "memory_peak_bytes": 4_534_084_608,
    "trace": {"window_s": 0.5, "busy_s": 0.498, "units": 0.5 / 15.2,
              "scopes": {s: 0.01 * (i + 1)
                         for i, s in enumerate(PARENT_SCOPES)}},
}
# the metrics over counters that window lacks, today (more may follow)
LACKING_TODAY = {"stream.reseeds_per_row", "stream.reset_evals_per_reseed",
                 "stream.frozen_lane_row_share", "dp4.lane_syncs_per_row"}
# the cells that are there, in their order, and the per-layer metrics
# each read when it came (commit 7c4421a; PR 30; PR 34), in their order
CELLS = ("decima_rollout", "decima_stream", "decima_rollout_dp4")
FIRST_METRICS = {
    "decima_rollout": tuple(f"rollout.{k}" for k in (
        "collect_s", "micro_per_decision", "events_per_decision",
        "straggler_ratio", "gnn_device_s", "scatter_device_s",
        "idle_share", "hbm_peak_gb", "engine_device_s",
        "drain_iters_per_row", "drain_batch_tax", "gnn_full_width_share",
        "lane_row_occupancy", "live_row_share")),
    "decima_stream": tuple(f"stream.{k}" for k in (
        "collect_s", "engine_device_s", "gnn_device_s", "scatter_device_s",
        "idle_share", "hbm_peak_gb", "drain_iters_per_row",
        "drain_batch_tax", "gnn_full_width_share", "lane_row_occupancy",
        "reseeds_per_row", "reset_evals_per_reseed",
        "frozen_lane_row_share")),
    "decima_rollout_dp4": tuple(f"dp4.{k}" for k in (
        "collect_s", "engine_device_s", "gnn_device_s", "scatter_device_s",
        "idle_share", "hbm_peak_gb", "drain_iters_per_row",
        "drain_batch_tax", "lane_row_occupancy", "gnn_full_width_share",
        "collective_device_s", "collective_exposed_share",
        "lane_syncs_per_row", "micro_per_decision", "events_per_decision",
        "straggler_ratio", "live_row_share")),
}

BENCH = harness.load_benchmark()
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


def metric_spec(name: str, base: str = harness.HERE) -> dict:
    """A per-layer metric's data file ({} for a reader of its own)."""
    path = osp.join(base, "layer_metrics", name + ".json")
    if not osp.exists(path):
        return {}
    with open(path) as fp:
        return json.load(fp)


def may_read_nothing(name: str, window: dict,
                     base: str = harness.HERE) -> bool:
    """Whether the metric's own data file says that a window such as
    `window` may hold nothing for it: a counter the program may lack,
    or a scope that no operation of the trace ran under."""
    spec = metric_spec(name, base)
    return bool(spec.get("may_lack")) or (
        "scope" in spec and spec["scope"] not in window["trace"]["scopes"])


def reads_the_parents_window(name: str, base: str = harness.HERE):
    """Rule 2: a number or None, never a raise; None only where the
    data file allows it."""
    value = harness.read_layer_metric(name, PARENT_WINDOW, base=base)
    assert value is None or (
        isinstance(value, (int, float)) and math.isfinite(value)), name
    if value is None:
        assert may_read_nothing(name, PARENT_WINDOW, base), name
    if name in LACKING_TODAY:
        assert value is None  # nothing to read: left out of the line
    return value


def old_cells_read_what_they_read(bench: dict) -> None:
    """Rule 1: the cells that are there come first and in their order;
    each still reads the metrics it read when it came, first and in
    their order (none removed, renamed or moved; more may follow); the
    rate lists those cells first; and every entry added since lists its
    cells."""
    assert tuple(w["name"] for w in bench["workloads"])[:3] == CELLS
    rate = {m["name"]: m for m in bench["end_to_end"]}[
        "rollout_decisions_per_s"]
    assert tuple(rate["workloads"][:3]) == CELLS
    for cell, names in FIRST_METRICS.items():
        read = tuple(m["name"] for m in harness.metrics_of_cell(
            bench, cell, "per_layer"))
        assert read[:len(names)] == names, cell
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        if m["name"] not in FIRST_METRICS["decima_rollout"]:
            assert m.get("workloads"), m["name"]
            assert set(m["workloads"]) <= cells, m["name"]


@pytest.mark.parametrize("name", PER_LAYER)
def test_every_reader_reads_the_parents_window_without_raising(name):
    reads_the_parents_window(name)


def test_a_traced_run_of_an_old_cell_reads_the_metrics_it_read():
    old_cells_read_what_they_read(BENCH)


def test_a_counter_metric_without_the_key_in_its_file_raises():
    """`may_lack` is the data file's to say: without it a missing
    counter is an error, so a slip in a key does not read as nothing."""
    assert metric_spec("stream.reseeds_per_row")["may_lack"] is True
    assert "may_lack" not in metric_spec("rollout.micro_per_decision")
    from benchmarks.layer_metrics import telemetry_ratio

    window = {"telemetry": [{"a": 1}]}
    with pytest.raises(KeyError):
        telemetry_ratio.read(window, "a", "no_such")
    assert telemetry_ratio.read(window, "a", "no_such", True) is None


def test_the_streaming_cell_reads_ten_metrics_without_the_new_counters():
    names = FIRST_METRICS["decima_stream"]
    assert len(names) == 13
    read = {n for n in names
            if harness.read_layer_metric(n, PARENT_WINDOW) is not None}
    assert read == set(names) - LACKING_TODAY


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


def _reads_nought(value) -> bool:
    if isinstance(value, dict):
        return all(_reads_nought(v) for v in value.values())
    if isinstance(value, (list, tuple)):
        return all(_reads_nought(v) for v in value)
    return value == 0


def test_the_summary_written_by_hand_is_the_parents():
    """`summarize` over a telemetry of zeros holds every key of the
    fixture, and every further key reads nought or is absent: nothing
    removed or renamed since, additions free."""
    from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros_like

    now = summarize(telemetry_zeros_like((2,)))
    assert set(PARENT_SUMMARY) <= set(now)
    for block in ("composition", "events_by_kind", "bulk", "phase_iters",
                  "row"):
        assert set(PARENT_SUMMARY[block]) <= set(now[block]), block
    for key in set(now) - set(PARENT_SUMMARY):
        assert _reads_nought(now[key]), key
    for key in set(now["row"]) - set(PARENT_SUMMARY["row"]):
        assert _reads_nought(now["row"][key]), key
    assert {"reseeds_total", "reset_evals_total"} <= set(now)


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


PROBE_CELL, PROBE_CONFIG, PROBE_MIX = "probe_cell", "probe_config", "probe_mix"


def a_copy_with_a_cell_appended(tmp_path, metric_files: dict):
    """What the next `model_config` PR does, in a temporary copy of the
    benchmark's directory under `tmp_path`: a configuration, a traffic
    mix and one more one-chip cell, its name at the end of the rate's
    `workloads`, and a per-layer metric for each data file of
    `metric_files` at the end of `per_layer`, as new files and new
    entries, every one under a made-up name (`test_harness.PROBE`).
    Gives the benchmark, its directory and the bytes of every file that
    was there."""
    base = tmp_path / "benchmarks"
    shutil.copytree(harness.HERE, base, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    bench = harness.load_benchmark()
    config = json.loads((base / "configs" / (
        bench["configs"][0]["name"] + ".json")).read_text())
    (base / "configs" / (PROBE_CONFIG + ".json")).write_text(
        json.dumps(dict(config, deployment="made up by a test")))
    mix = json.loads((base / "traffic" / "decima_128x800.json").read_text())
    (base / "traffic" / (PROBE_MIX + ".json")).write_text(json.dumps(mix))
    for name, spec in metric_files.items():
        (base / "layer_metrics" / (name + ".json")).write_text(
            json.dumps(spec))
    bench["configs"].append(dict(
        bench["configs"][0], name=PROBE_CONFIG,
        file=f"benchmarks/configs/{PROBE_CONFIG}.json"))
    bench["workloads"].append({
        "name": PROBE_CELL, "config": PROBE_CONFIG, "traffic": PROBE_MIX,
        "chips": 1, "why": "added as files and entries alone"})
    for m in bench["end_to_end"]:
        if m["name"] == "rollout_decisions_per_s":
            m["workloads"] = m["workloads"] + [PROBE_CELL]
    twin = {m["name"]: m for m in bench["per_layer"]}["rollout.collect_s"]
    for name in metric_files:
        bench["per_layer"].append(dict(
            twin, name=name, workloads=[PROBE_CELL]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench, base, before


def test_a_fourth_cell_a_counter_and_a_scope_come_as_files_alone(tmp_path):
    """A later PR's cell, a metric over a counter the parent lacks and a
    metric over a scope no list names, on a copy
    (`a_copy_with_a_cell_appended`). Every overlay rule and the contract
    hold on the copy, the reducer finds the new scope through the data
    file, and no file that was there changed."""
    from tests.benchmark.test_harness import keeps_to_the_contract

    files = {
        "probe.collect_s": {
            "reader": "scalar_stat", "key": "collect_seconds",
            "stat": "median"},
        "probe.wave_jobs_per_row": {
            "reader": "telemetry_ratio", "num": "row.wave_jobs",
            "den": "row.rows", "may_lack": True},
        "probe.wave_device_s": {
            "reader": "trace_scope", "scope": "env/micro_step/wave"},
    }
    bench, base, before = a_copy_with_a_cell_appended(tmp_path, files)

    here = str(base)
    keeps_to_the_contract(bench, base=here, root=str(tmp_path), probe=True,
                          parent=harness.load_benchmark())
    old_cells_read_what_they_read(bench)
    values = {m["name"]: reads_the_parents_window(m["name"], here)
              for m in bench["per_layer"]}
    assert values["probe.collect_s"] == 15.2
    assert values["probe.wave_jobs_per_row"] is None
    assert values["probe.wave_device_s"] is None
    for m in bench["per_layer"]:  # an empty window: nothing to read
        assert harness.read_layer_metric(m["name"], {}, base=here) is None
    assert [m["name"] for m in harness.metrics_of_cell(
        bench, PROBE_CELL, "per_layer")] == list(files)
    # the program that HAS the counter and the scope: both read
    assert "env/micro_step/wave" in harness.metric_scopes(here)
    assert "env/micro_step/wave" not in harness.metric_scopes()
    text = "jit(_collect)/while/body/vmap(env/micro_step/wave)/select_n"
    trace = trace_reduce.reduce_events(
        {0: [{"name": "fusion.1", "start": 0.0, "dur": 0.2, "text": text},
             {"name": "copy.1", "start": 0.2, "dur": 0.1, "text": "copy.1"}]},
        [], window=(0.0, 1.0), chips=1,
        scopes=trace_reduce.scope_names(harness.metric_scopes(here)))
    assert dict(trace["top_ops"])["env/micro_step/wave:fusion.1"] == (
        pytest.approx(0.2))
    summary = dict(PARENT_SUMMARY, row=dict(
        PARENT_SUMMARY["row"], wave_jobs=1600))
    window = dict(PARENT_WINDOW, telemetry=[summary],
                  trace=dict(trace, units=0.5))
    read = harness.read_layer_metric
    assert read("probe.wave_jobs_per_row", window, base=here) == 2.0
    assert read("probe.wave_device_s", window, base=here) == (
        pytest.approx(0.4))
    assert read("rollout.engine_device_s", window, base=here) == (
        pytest.approx(0.4))  # the parent scope still holds its child
    assert all(p.read_bytes() == b for p, b in before.items())
