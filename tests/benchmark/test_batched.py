"""The batched-arrivals cell (`decima_batch20`): the trainer's sync
collector at 16 lanes under a batch of 6 jobs on 5 executors (ONE
collector compile for this file), what it stores against the plain
reference `benchmarks/reference/batched_np.py`, each of the reference's
checks on a doctored rollout, the driver's refusal of a program without
the field, and the cell's entries in `BENCHMARK.json`."""

import dataclasses
import json
import os.path as osp
import sys

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.drivers import collect_batched
from benchmarks.reference import batched_np

LANES, GROUP, ROWS, BATCH = 16, 4, 128, 6
CELL, CONF = "decima_batch20", "decima_tpch_50x20_batched"
BENCH = harness.load_benchmark()


@pytest.fixture(scope="module")
def collected(tmp_path_factory):
    """One collection of the trainer built from the program's batched
    configuration at a small cluster: the rollout's arrays as the
    reference reads them, the telemetry summary, the trainer."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu import config
    from sparksched_tpu.obs.telemetry import summarize
    from sparksched_tpu.trainers import make_trainer

    before = jax.config.jax_default_prng_impl
    cfg = config.load(
        osp.join(harness.ROOT, "config", "decima_tpch_batched.yaml"))
    cfg = harness.merge(cfg, {
        "env": {"num_executors": 5, "job_arrival_cap": BATCH,
                "num_init_jobs": BATCH},
        "trainer": {"num_sequences": LANES // GROUP, "num_rollouts": GROUP,
                    "rollout_steps": ROWS, "checkpointing_freq": 10**9,
                    "artifacts_dir": str(tmp_path_factory.mktemp("art"))},
        "agent": {"job_bucket": 0}})
    trainer = make_trainer(cfg)
    state = trainer.init_state()
    ro, _, telem = trainer._collect_jit(
        state.params, jnp.int32(0), jax.random.PRNGKey(11), None)
    jax.config.update("jax_default_prng_impl", before)
    return collect_batched.rollout_arrays(ro), summarize(telem), trainer


def _found(col, summary=None):
    return batched_np.check_batched(
        col, batch_jobs=BATCH, rollouts_per_group=GROUP, summary=summary)


def test_every_lane_ends_terminated_and_the_counters_add_up(collected):
    col, summary, trainer = collected
    n = col["valid"].sum(axis=1)
    assert trainer.params_env.num_init_jobs == BATCH
    assert trainer.params_env.mean_time_limit is None
    assert (n > BATCH).all() and (n < ROWS).all()
    for lane in range(LANES):  # the valid rows are a prefix
        assert col["valid"][lane, :n[lane]].all()
    assert col["final_completed"].all()
    assert summary["episodes_terminated_total"] == LANES
    assert summary["decisions"] == int(n.sum())
    row = summary["row"]
    assert row["lane_rows"] == LANES * ROWS
    assert row["lane_rows_ended"] == LANES * ROWS - int(n.sum())
    assert row["lane_rows_frozen"] == 0 and summary["reseeds_total"] == 0
    present = sum(int(col["job_mask"][k, :n[k]].sum()) for k in range(LANES))
    assert summary["jobs_present_total"] == present
    assert 1.0 < summary["jobs_present_per_decision"] <= BATCH
    assert summary["health_mask"] == 0


def test_the_reference_reads_a_sound_rollout_as_sound(collected):
    col, summary, trainer = collected
    found = _found(col, summary)
    assert found.pop("episodes_terminated_share") == 1.0
    assert set(found) >= {
        "batched_terminated_count_gap", "batched_ended_rows_gap"}
    assert all(v == 0 for v in found.values()), found
    checks = collect_batched.batched_checks(
        col, trainer, summary, {"episodes_terminated_share": 0.9})
    assert all(c["ok"] for c in checks)
    assert checks[-1]["check"] == "episodes_terminated_share"
    # a parent's summary has no such counters: those two are left out
    assert set(_found(col, {"row": {}})) == set(found) - {
        "batched_terminated_count_gap", "batched_ended_rows_gap"} | {
        "episodes_terminated_share"}


def _doctor(name, col, summary):
    col = {k: np.array(v) for k, v in col.items() if k != "row_has"} | {
        "row_has": {k: np.array(v) for k, v in col["row_has"].items()}}
    summary = json.loads(json.dumps(summary))
    n = col["valid"].sum(axis=1)
    if name == "batched_first_row_short":  # a job missing at the start
        col["job_mask"][3, 0, 2] = False
    elif name == "batched_batch_size_off":
        col["final_num_jobs"][5] = BATCH - 1
    elif name == "batched_arrival_after_start":
        col["final_arrival_time"][2, 4] = 5000.0
    elif name == "batched_job_entered_later":  # a job arrives mid-episode
        col["job_mask"][1, :4, 5] = False
    elif name == "batched_valid_not_prefix":
        col["valid"][7, 2] = False
    elif name == "batched_end_flag_misplaced":  # flagged a row early
        col["resets"][4, n[4] - 1] = False
        col["resets"][4, n[4] - 2] = True
    elif name == "batched_ended_unfinished":  # ended with a job to run
        col["final_completed"][6, 1] = False
    elif name == "batched_unended_idle":  # stopped deciding, never ended
        col["resets"][9] = False
    elif name == "batched_row_without_job":
        col["job_mask"][8, n[8] - 1] = False
    elif name.startswith("batched_row_without_"):
        # what the chip lost at 1024 lanes (PERF.md, PR 42): a lane's
        # rows zeroed in a wide leaf from some slot on
        leaf = name.removeprefix("batched_row_without_")
        col["row_has"][leaf][10, n[10] // 2:] = False
    elif name == "batched_group_batch_split":  # a lane with its own batch
        col["job_template"][GROUP + 1] = np.roll(
            col["job_template"][GROUP + 1], 1) + 1
    elif name == "batched_batch_repeated":  # two groups, one batch
        col["job_template"][GROUP:2 * GROUP] = col["job_template"][0]
    elif name == "batched_terminated_count_gap":
        summary["episodes_terminated_total"] -= 1
    elif name == "batched_ended_rows_gap":
        summary["row"]["lane_rows_ended"] += ROWS
    return col, summary


@pytest.mark.parametrize("name", [
    "batched_first_row_short", "batched_batch_size_off",
    "batched_arrival_after_start", "batched_job_entered_later",
    "batched_valid_not_prefix", "batched_end_flag_misplaced",
    "batched_ended_unfinished", "batched_unended_idle",
    "batched_row_without_job", "batched_row_without_node",
    "batched_row_without_schedulable", "batched_row_without_remaining",
    "batched_row_without_duration", "batched_group_batch_split",
    "batched_batch_repeated", "batched_terminated_count_gap",
    "batched_ended_rows_gap"])
def test_each_check_fails_on_a_doctored_rollout(collected, name):
    col, summary, _ = collected
    assert name in _found(col, summary)  # every check is exercised here
    found = _found(*_doctor(name, col, summary))
    assert found[name] != 0, found


def test_a_scan_that_cuts_episodes_short_reads_a_low_share(collected):
    col, summary, trainer = collected
    cut = int(np.median(col["valid"].sum(axis=1)))
    short = {k: (v[:, :cut] if k in ("valid", "resets", "job_mask") else v)
             for k, v in col.items()} | {
        "row_has": {k: v[:, :cut] for k, v in col["row_has"].items()}}
    # the lanes the cut stopped have work left in their final state
    stopped = ~short["resets"].any(axis=1)
    short["final_completed"] = short["final_completed"] & ~stopped[:, None]
    found = _found(short)
    assert 0.2 < found["episodes_terminated_share"] < 0.8
    assert found["batched_unended_idle"] == 0  # cut short, not idle
    checks = collect_batched.batched_checks(
        short, trainer, None, {"episodes_terminated_share": 0.9})
    assert [c["check"] for c in checks if not c["ok"]] == [
        "episodes_terminated_share"]


def test_the_driver_ends_at_once_on_a_program_without_the_field(
        monkeypatch):
    """Rule 3 of the overlay (tests/benchmark/test_overlay.py): on the
    parent's program, whose `EnvParams` has no `num_init_jobs` and
    whose loader skips the key, `build` ends with a SystemExit naming
    the field, before it imports the trainers or touches jax; so too
    without the program's configuration file."""
    from sparksched_tpu import config

    cell = harness.load_cell(CELL, BENCH)
    fields = [(f.name, f.type, f) for f in dataclasses.fields(
        config.EnvParams) if f.name != collect_batched.FIELD]
    parents = dataclasses.make_dataclass("EnvParams", fields, frozen=True)
    before = set(sys.modules)
    with monkeypatch.context() as m:
        m.setattr(config, "EnvParams", parents)
        with pytest.raises(SystemExit, match="no field num_init_jobs"):
            collect_batched.build(cell, 1)
    cell["config_data"] = dict(
        cell["config_data"], program_config="config/no_such_batched.yaml")
    with pytest.raises(SystemExit, match="no config/no_such_batched.yaml"):
        collect_batched.build(cell, 1)
    assert not any(m.startswith("sparksched_tpu.trainers")
                   for m in set(sys.modules) - before)


def entries_hold(bench: dict, base: str = harness.HERE) -> None:
    """What PR 42 added, where it was put: the fourth configuration,
    the fourth cell, the fourth place in the rate's `workloads`, and at
    the head of the cell's per-layer metrics the sixteen twins and the
    three over the new counters. What later PRs add follows."""
    config = {c["name"]: c for c in bench["configs"]}[CONF]
    assert bench["configs"][3] is config
    assert set(config["reduced"]) == {
        "num_sequences", "num_rollouts", "rollout_steps"}
    assert "7.2" in config["source"] and "20" in config["source"]
    cell = bench["workloads"][3]
    assert (cell["name"], cell["config"], cell["chips"]) == (CELL, CONF, 1)
    rate = {m["name"]: m for m in bench["end_to_end"]}[
        "rollout_decisions_per_s"]
    assert rate["workloads"][3] == CELL
    read = [m["name"] for m in harness.metrics_of_cell(
        bench, CELL, "per_layer")]
    names = [m["name"] for m in bench["per_layer"]]
    came = min(i for i, n in enumerate(names) if n.startswith("batch20."))
    # a twin of each `rollout.*` metric that was there when the cell came
    twins = [n.replace("rollout.", "batch20.", 1) for n in names[:came]
             if n.startswith("rollout.")
             and n != "rollout.gnn_full_width_share"]
    assert len(twins) == 16 and read[:16] == twins
    assert read[16:19] == [
        "batch20.ended_lane_row_share", "batch20.jobs_present_per_decision",
        "batch20.episodes_terminated_share"]
    for m in bench["per_layer"]:
        if m["name"].startswith("batch20."):
            assert m["workloads"] == [CELL]
    loaded = harness.load_cell(CELL, bench, base=base)
    mix, conf = loaded["mix"], loaded["config_data"]
    assert mix["driver"] == "collect_batched"
    assert mix["lanes"] == (mix["overrides"]["trainer"]["num_sequences"]
                            * mix["overrides"]["trainer"]["num_rollouts"])
    assert conf["env"]["num_init_jobs"] == conf["env"]["job_arrival_cap"] == 20
    assert conf["limits"]["episodes_terminated_share"] == 0.9


def test_the_cells_entries_are_what_the_issue_names():
    entries_hold(harness.load_benchmark())


def test_the_new_counter_metrics_read_the_programs_summary(collected):
    _, summary, _ = collected
    window = {"telemetry": [summary, summary]}
    read = harness.read_layer_metric
    assert read("batch20.episodes_terminated_share", window) == 1.0
    assert read("batch20.ended_lane_row_share", window) == (
        summary["row"]["lane_rows_ended"] / summary["row"]["lane_rows"])
    assert read("batch20.jobs_present_per_decision", window) == (
        pytest.approx(summary["jobs_present_per_decision"], abs=1e-3))


def test_the_traced_window_is_the_flagship_cells(collected):
    """The cell is measured by `collect_rollout.measure` itself, over
    the half second from 4 s of a collection that `decima_rollout`
    traces, and asks the trainer for the episode counters its metrics
    read."""
    from benchmarks.drivers import collect_rollout

    assert collect_batched.measure is collect_rollout.measure
    mix = harness.load_cell(CELL, BENCH)["mix"]
    flagship = harness.load_cell("decima_rollout", BENCH)["mix"]
    for key in ("trace_start_s", "trace_seconds", "warmup_collections",
                "min_collections", "end_to_end"):
        assert mix[key] == flagship[key], key
    assert collected[2].obs_episode_counters
