"""The heuristic-sweep cell (`sweep_fair`) without a chip: the plain fair
policy on hand-made observations, `sweep_np.check_sweep` against
hand-made records with one violation of each kind, every `sweep.*` data
file read from a made-up window, the program at the deployment's own
10 x 50 over the driver's fixed-duration bank against the plain
simulator (ONE chunk compile for this file), the driver's refusal of a
program without the sweep, and the cell's entries in `BENCHMARK.json`."""

import ast
import os.path as osp
import sys

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.drivers import sweep_chunks
from benchmarks.reference import fair_np, sweep_np

CELL, CONF = "sweep_fair", "tpch_demo_10x50_fair"
BENCH = harness.load_benchmark()
SWEEP_METRICS = [m["name"] for m in BENCH["per_layer"]
                 if m["name"].startswith("sweep.")]


# -- the plain fair policy --------------------------------------------------


def observation(jobs=4, stages=3):
    """Jobs 0 to 2 active with every stage schedulable, stage 1 alone
    on the frontier; job 3 not there."""
    sched = np.zeros((jobs, stages), bool)
    sched[:3] = True
    front = np.zeros_like(sched)
    front[:3, 1] = True
    mask = np.array([True, True, True, False])
    return {"schedulable": sched, "frontier": front, "job_mask": mask,
            "exec_supplies": np.zeros(jobs, int), "num_committable": 5,
            "source_job": -1}


def test_the_first_job_under_its_cap_takes_up_to_the_cap():
    # 10 executors over 3 active jobs: a cap of 4; frontier stage first
    assert fair_np.fair(**observation(), num_executors=10) == (0 * 3 + 1, 4)
    obs = dict(observation(), exec_supplies=np.array([4, 3, 0, 0]))
    assert fair_np.fair(**obs, num_executors=10) == (1 * 3 + 1, 1)
    obs["num_committable"] = 1
    obs["exec_supplies"] = np.array([4, 4, 1, 0])
    assert fair_np.fair(**obs, num_executors=10) == (2 * 3 + 1, 1)


def test_the_source_job_comes_first_and_takes_everything():
    obs = dict(observation(), source_job=2,
               exec_supplies=np.array([0, 0, 9, 0]))
    assert fair_np.fair(**obs, num_executors=10) == (2 * 3 + 1, 5)
    # a source job with nothing to schedule is passed over in the loop
    obs["schedulable"] = obs["schedulable"].copy()
    obs["schedulable"][2] = False
    assert fair_np.fair(**obs, num_executors=10) == (0 * 3 + 1, 4)


def test_without_a_frontier_stage_the_first_schedulable_one():
    obs = observation()
    obs["frontier"] = np.zeros_like(obs["frontier"])
    obs["schedulable"][0, 0] = False
    assert fair_np.fair(**obs, num_executors=10) == (0 * 3 + 1, 4)
    obs["schedulable"][0] = [False, False, True]
    assert fair_np.fair(**obs, num_executors=10) == (0 * 3 + 2, 4)


def test_no_stage_gives_minus_one_with_every_committable_executor():
    obs = dict(observation(), exec_supplies=np.array([4, 4, 4, 0]))
    assert fair_np.fair(**obs, num_executors=10) == (-1, 5)
    obs = observation()
    obs["schedulable"][:] = False
    assert fair_np.fair(**obs, num_executors=10) == (-1, 5)


def test_fifo_has_no_per_job_cap():
    obs = dict(observation(), exec_supplies=np.array([4, 0, 0, 0]))
    assert fair_np.fair(**obs, num_executors=10) == (1 * 3 + 1, 4)
    assert fair_np.fair(**obs, num_executors=10,
                        dynamic_partition=False) == (0 * 3 + 1, 5)


@pytest.mark.parametrize("module", ["fair_np", "sweep_np"])
def test_the_reference_imports_nothing_of_the_program(module):
    path = osp.join(harness.HERE, "reference", module + ".py")
    with open(path) as fp:
        tree = ast.parse(fp.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module)
    allowed = {"__future__", "math", "heapq", "numpy",
               "benchmarks.reference.stream_np"}
    assert names <= allowed, names - allowed


# -- check_sweep ------------------------------------------------------------


def sound_record():
    """Two lanes x six rows. Lane 0 ends its episode 3 on row 2 (its
    50th... here 4th decision of a 2-job episode) and starts episode 4
    on row 3; lane 1 stays in episode 0."""
    rows, lanes = 6, 2
    rec = {
        "valid": np.ones((rows, lanes), bool),
        "wall_time": np.array([[70.0, 1.0], [80.0, 2.0], [90.0, 3.0],
                               [0.0, 4.0], [3.0, 5.0], [5.0, 6.0]]),
        "job": np.zeros((rows, lanes), int),
        "stage": np.zeros((rows, lanes), int),
        "num_exec": np.ones((rows, lanes), int),
        "reset": np.zeros((rows, lanes), bool),
        "ordinal": np.array([[3, 0]] * 3 + [[4, 0]] * 3),
        "avg_jct": np.zeros((rows, lanes)),
        "jobs_completed": np.zeros((rows, lanes), int),
        "makespan": np.zeros((rows, lanes)),
        "decisions": np.zeros((rows, lanes), int),
    }
    rec["reset"][2, 0] = True
    rec["avg_jct"][2, 0], rec["makespan"][2, 0] = 60.0, 95.0
    rec["jobs_completed"][2, 0], rec["decisions"][2, 0] = 2, 4
    extra = {
        "lane": np.array([0, 1]), "final_ordinal": np.array([4, 0]),
        "arrivals": np.array([[0.0, 9.0], [0.0, 7.0]]),
        "templates": np.array([[1, 2], [1, 2]]), "jobs": 2,
        "summary": {"decisions": 12, "reseeds_total": 1,
                    "episodes_terminated_total": 1,
                    "episode_decisions_total": 4}}
    return rec, extra


def test_a_sound_record_has_no_violation():
    rec, extra = sound_record()
    found = sweep_np.check_sweep(rec, **extra)
    assert len(found) == 14 and set(found.values()) == {0}
    # a program without the new counter: that one check is left out
    del extra["summary"]["episode_decisions_total"]
    assert "sweep_episode_decisions_total_gap" not in sweep_np.check_sweep(
        rec, **extra)
    extra["summary"] = None
    assert len(sweep_np.check_sweep(rec, **extra)) == 10


def _set(name, at, value):
    def doctor(rec, extra):
        rec[name][at] = value
    return doctor


def _extra(name, value):
    def doctor(rec, extra):
        extra[name] = value
    return doctor


def _summary(name, value):
    def doctor(rec, extra):
        extra["summary"][name] = value
    return doctor


VIOLATIONS = {
    # (ii) an end with a job missing; results that cannot be
    "sweep_ends_incomplete": _set("jobs_completed", (2, 0), 1),
    "sweep_results_unsound": _set("avg_jct", (2, 0), 99.0),
    "sweep_results_too_few_decisions": _set("decisions", (2, 0), 1),
    "sweep_results_off_an_end": _set("makespan", (4, 1), 5.0),
    # (iv) order
    "sweep_reset_on_an_idle_row": _set("valid", (2, 0), False),
    "sweep_time_runs_back": _set("wall_time", (4, 1), 3.5),
    "sweep_new_episode_not_at_zero": _set("wall_time", (3, 0), 2.0),
    "sweep_ordinal_breaks": _set("ordinal", (4, 0), 5),
    # (iii) episodes of their own
    "sweep_lane_ids_shared": _extra("lane", np.array([1, 1])),
    "sweep_sequences_shared": _extra(
        "arrivals", np.array([[0.0, 7.0], [0.0, 7.0]])),
    # (v) counts
    "sweep_decisions_gap": _summary("decisions", 11),
    "sweep_reseeds_total_gap": _summary("reseeds_total", 2),
    "sweep_episodes_terminated_total_gap": _summary(
        "episodes_terminated_total", 0),
    "sweep_episode_decisions_total_gap": _summary(
        "episode_decisions_total", 5),
}


@pytest.mark.parametrize("name", list(VIOLATIONS))
def test_each_violation_is_counted_by_its_own_check(name):
    rec, extra = sound_record()
    VIOLATIONS[name](rec, extra)
    found = sweep_np.check_sweep(rec, **extra)
    assert found[name] > 0
    alone = {"sweep_reset_on_an_idle_row": {"sweep_decisions_gap"},
             "sweep_results_too_few_decisions": {
                 "sweep_episode_decisions_total_gap"}}
    others = {k for k, v in found.items() if v and k != name}
    assert others <= alone.get(name, set()), others


def test_the_final_ordinal_follows_the_last_row():
    rec, extra = sound_record()
    extra["final_ordinal"] = np.array([5, 0])
    assert sweep_np.check_sweep(rec, **extra)["sweep_ordinal_breaks"] == 1
    rec, extra = sound_record()  # an end on the chunk's last row
    rec["reset"][5, 1] = True
    rec["avg_jct"][5, 1], rec["makespan"][5, 1] = 3.0, 6.5
    rec["jobs_completed"][5, 1], rec["decisions"][5, 1] = 2, 6
    extra["summary"] |= {"reseeds_total": 2, "episodes_terminated_total": 2,
                         "episode_decisions_total": 10}
    assert sweep_np.check_sweep(rec, **extra)["sweep_ordinal_breaks"] == 1
    extra["final_ordinal"] = np.array([4, 1])
    assert set(sweep_np.check_sweep(rec, **extra).values()) == {0}


def test_copies_of_a_lane_are_held_to_sequences_of_their_own_once_reseeded():
    rec, extra = sound_record()
    extra["arrivals"] = np.array([[0.0, 7.0], [0.0, 7.0]])
    found = sweep_np.check_sweep(rec, own=np.array([True, False]), **extra)
    assert found["sweep_sequences_shared"] == 0
    found = sweep_np.check_sweep(rec, own=np.array([True, True]), **extra)
    assert found["sweep_sequences_shared"] == 1


# -- the per-layer metrics --------------------------------------------------

SUMMARY = {
    "decisions": 262144, "micro_steps": 700000, "events_total": 2900000,
    "reseeds_total": 480, "reset_evals_total": 46080,
    "jobs_present_total": 6000000, "episode_decisions_total": 254400,
    "episodes_terminated_total": 480, "health_mask": 0,
    "row": {"rows": 16, "lane_rows": 262144, "drain_batch_iters": 160,
            "drain_lane_iters_executed": 2621440,
            "drain_iters_total": 400000},
}
WINDOW = {
    "scalars": [{"collect_seconds": s, "collection": i}
                for i, s in enumerate((9.0, 10.0, 12.0))],
    "telemetry": [SUMMARY, SUMMARY],
    "memory_peak_bytes": 5_200_000_000,
    "trace": {"window_s": 0.5, "busy_s": 0.499, "units": 0.05,
              "unscoped_s": 0.02,
              "scopes": {"env/micro_step": 0.4, "env/micro_step/drain": 0.3,
                         "env/micro_step/decide": 0.08,
                         "env/micro_step/reset": 0.02, "sweep/policy": 0.01,
                         "sweep/record": 0.005, "collect/observe": 0.015}},
}
WANT = {
    "sweep.chunk_s": 10.0,
    "sweep.engine_device_s": 8.0, "sweep.drain_device_s": 6.0,
    "sweep.decide_device_s": 1.6, "sweep.reset_device_s": 0.4,
    "sweep.policy_device_s": 0.2, "sweep.record_device_s": 0.1,
    "sweep.observe_device_s": 0.3, "sweep.unscoped_device_s": 0.4,
    "sweep.idle_share": 0.2, "sweep.hbm_peak_gb": 5.2,
    "sweep.micro_per_decision": 700000 / 262144,
    "sweep.events_per_decision": 2900000 / 262144,
    "sweep.drain_iters_per_row": 10.0,
    "sweep.drain_batch_tax": 2621440 / 400000,
    "sweep.lane_row_occupancy": 1.0,
    "sweep.reseeds_per_row": 30.0,
    "sweep.reset_evals_per_reseed": 96.0,
    "sweep.jobs_present_per_decision": 6000000 / 262144,
    "sweep.decisions_per_episode": 530.0,
}


# The once-a-row scopes: the issue names their metrics, and the cell's
# traced half second (a third of one decision row, mid-drain) holds none
# of them, so `BENCHMARK.json` does not list them: a listed metric has
# to be in the cell's traced line. Their data files stay (they make the
# scopes known to the reducer) for a `benchmark` PR to list.
UNLISTED = {"sweep.decide_device_s", "sweep.policy_device_s",
            "sweep.record_device_s", "sweep.observe_device_s"}


LISTED = [n for n in WANT if n not in UNLISTED]


def test_the_cell_reads_the_metrics_its_traced_line_can_hold():
    """Sixteen of the issue's twenty; `entries_hold` has them at the
    head of the cell's list."""
    assert len(LISTED) == 16 and SWEEP_METRICS[:16] == LISTED


@pytest.mark.parametrize("name", list(WANT))
def test_each_sweep_metric_reads_its_own_source(name):
    assert harness.read_layer_metric(name, WINDOW) == pytest.approx(
        WANT[name])
    assert harness.read_layer_metric(name, {}) is None
    assert (name in SWEEP_METRICS) == (name not in UNLISTED)


def test_the_new_scopes_reach_the_reducer_through_their_data_files():
    scopes = harness.metric_scopes()
    assert {"sweep/policy", "sweep/record"} <= set(scopes)
    # neither name holds a scope the reducer knows, nor a host span's
    from benchmarks import trace_reduce
    from sparksched_tpu.obs import tracing

    for new in ("sweep/policy", "sweep/record"):
        assert not any(k in new or new in k
                       for k in trace_reduce.KNOWN_SCOPES)
        assert "sweep/chunk_call" not in new and new not in "sweep/chunk_call"
        assert new in tracing.__doc__
    assert "sweep/chunk_call" in tracing.__doc__


def test_the_summary_holds_the_new_counter_only_where_asked_for():
    from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros_like

    assert "episode_decisions_total" not in summarize(
        telemetry_zeros_like((2,), episodes=True))
    asked = summarize(telemetry_zeros_like((2,), episodes=True, results=True))
    assert asked["episode_decisions_total"] == 0
    plain = telemetry_zeros_like((2,))
    assert plain.episode_decisions_sum is None  # no leaf of a carry


# -- the cell's entries -----------------------------------------------------


def entries_hold(bench: dict, base: str = harness.HERE) -> None:
    """What PR 46 added, where it was put: the fifth cell, under a
    configuration no older cell uses, the fifth in the rate's
    `workloads`; the sixteen listed `sweep.*` metrics lead their family
    and the cell's per-layer metrics, and every `sweep.*` metric lists
    the cell alone. What later PRs add follows."""
    config = {c["name"]: c for c in bench["configs"]}[CONF]
    assert set(config["reduced"]) == {"lanes", "rows_per_chunk"}
    assert "examples.py:15-23" in config["source"]
    cell = bench["workloads"][4]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONF, "fair_steady", 1)
    assert not any(w["config"] == CONF for w in bench["workloads"][:4])
    rate = {m["name"]: m for m in bench["end_to_end"]}[
        "rollout_decisions_per_s"]
    assert rate["workloads"][4] == CELL
    family = [m for m in bench["per_layer"] if m["name"].startswith("sweep.")]
    assert [m["name"] for m in family[:16]] == LISTED
    for m in family:
        assert m["workloads"] == [CELL], m["name"]
        assert m["moves"] == "rollout_decisions_per_s"
    assert [m["name"] for m in harness.metrics_of_cell(
        bench, CELL, "per_layer")][:16] == LISTED
    loaded = harness.load_cell(CELL, bench, base=base)
    mix, conf = loaded["mix"], loaded["config_data"]
    assert mix["driver"] == "sweep_chunks"
    assert mix["lanes"] % 2048 == 0 and mix["lanes"] <= 32768
    assert (mix["warmup_chunks"], mix["min_chunks"]) == (2, 2)
    assert (mix["trace_start_s"], mix["trace_seconds"]) == (4.0, 0.5)
    assert conf["env"] == {
        "num_executors": 10, "job_arrival_cap": 50,
        "job_arrival_rate": 4e-05, "moving_delay": 2000.0,
        "warmup_delay": 1000.0, "mean_time_limit": None,
        "first_job_at": 0.0}
    assert conf["scheduler"]["agent_cls"] == "RoundRobinScheduler"
    assert conf["scheduler"]["dynamic_partition"] is True
    assert conf["lower_precision"] == {
        "bank_int8": {"env": {"bank_dtype": "int8"}}}
    assert len(conf["guarantees"]) == 7


def test_the_sweep_cells_entries_are_what_the_issue_names():
    entries_hold(BENCH)
    conf = harness.load_cell(CELL, BENCH)["config_data"]
    # the program's own YAML states the same cluster and scheduler
    from sparksched_tpu import config as program_config

    cfg = program_config.load(osp.join(harness.ROOT, conf["program_config"]))
    for key in sweep_chunks.ENV_KEYS:
        assert cfg["env"][key] == conf["env"][key], key
    assert cfg["agent"] == {"agent_cls": "RoundRobinScheduler",
                            "dynamic_partition": True}
    assert "fast_prng" not in cfg.get("sweep", {})  # threefry keys


def test_every_line_of_the_benchmark_fits_200_printable_characters():
    """`test_harness.keeps_to_the_contract` holds a cell's `why` and a
    configuration's `source` to the contract's 200 characters, not a
    configuration's `why`: this PR's first one had 204 and was refused
    before any run."""
    lines = {("command", i): word for i, word in enumerate(BENCH["command"])}
    for c in BENCH["configs"]:
        lines["config", c["name"], "why"] = c["why"]
        lines["config", c["name"], "source"] = c["source"]
    for w in BENCH["workloads"]:
        lines["cell", w["name"], "why"] = w["why"]
    for m in BENCH["per_layer"]:
        lines["metric", m["name"], "layer"] = m["layer"]
    for where, line in lines.items():
        assert 1 <= len(line) <= 200 and line.isprintable(), (where,
                                                              len(line))


def test_no_name_of_the_cell_is_one_the_overlay_test_makes_up():
    """The tests' made-up entries start with `probe_` or `probe.`
    (until PR 48 `test_overlay.py` made up `decima_batched` and
    `batched.*`, which cost PR 42 its cell's name)."""
    from tests.benchmark.test_harness import PROBE

    names = [CELL, CONF, "fair_steady"] + SWEEP_METRICS
    assert not any(n.startswith(PROBE) for n in names)


def test_the_driver_ends_at_once_without_the_programs_sweep(monkeypatch):
    """On a program without `config/sweep_fair_demo.yaml` or
    `sparksched_tpu/sweep.py` (the parent commit of the PR that brought
    the cell) `build` ends with a SystemExit naming the file, before it
    imports the program or touches jax."""
    cell = harness.load_cell(CELL, BENCH)
    cell["config_data"] = dict(
        cell["config_data"], program_config="config/no_such_sweep.yaml")
    before = set(sys.modules)
    with pytest.raises(SystemExit, match="no config/no_such_sweep.yaml"):
        sweep_chunks.build(cell, 1)
    cell = harness.load_cell(CELL, BENCH)
    monkeypatch.setattr(harness, "ROOT", osp.join(harness.ROOT, "config"))
    cell["config_data"] = dict(
        cell["config_data"], program_config="sweep_fair_demo.yaml")
    with pytest.raises(SystemExit, match="no sparksched_tpu/sweep.py"):
        sweep_chunks.build(cell, 1)
    assert not any(m.startswith("sparksched_tpu.sweep")
                   for m in set(sys.modules) - before)


# -- the engine's transitions at the deployment's size ----------------------

LANES, ROWS = 4, 64


@pytest.fixture(scope="module")
def fixed():
    """The deployment's cluster and scheduler over its own bank
    collapsed to one duration a bucket, 4 lanes x 64 rows from reset."""
    import jax

    from sparksched_tpu import config, sweep

    cfg = config.load(osp.join(harness.ROOT, "config", "sweep_fair_demo.yaml"))
    params, bank, sched = sweep.from_config(cfg)
    bank, tables, durations = sweep_chunks.fixed_durations(bank)
    carry = sweep.init(params, bank, jax.random.PRNGKey(7), LANES)
    _, rec, tm = sweep.sweep_chunk(
        params, bank, sched.batch_policy, carry, jax.random.PRNGKey(8), ROWS)
    return (params, bank, sched, tables, durations, carry,
            sweep_chunks.record_arrays(rec), sweep.summarize(tm))


def test_the_collapsed_bank_holds_one_whole_number_a_bucket(fixed):
    _, bank, _, _, durations, _, _, _ = fixed
    dur, cnt = np.asarray(bank.dur), np.asarray(bank.cnt)
    assert set(np.unique(cnt)) <= {0, 1}
    assert (dur == np.rint(dur)).all()
    assert (dur.min(axis=(3, 4)) == dur.max(axis=(3, 4))).all()
    t = 17
    assert durations[t]["first"] == [
        float(x) for x in dur[t, :len(durations[t]["first"]), 1, 0, 0]]
    assert all(v is not None and v >= 1.0 for v in durations[t]["first"])


def _rows(fixed, lane: int, fair: bool = True) -> list[dict]:
    from sparksched_tpu import sweep

    params, bank, _, tables, durations, carry, _, _ = fixed
    return sweep_chunks.simulated(
        sweep, params, bank, tables, durations, carry.key[lane], ROWS,
        dynamic_partition=fair)


_differ = sweep_chunks.rows_differ


def test_the_program_equals_the_plain_simulator_at_10_by_50(fixed):
    rec, summary = fixed[6], fixed[7]
    assert rec["valid"].all() and summary["health_mask"] == 0
    for lane in range(LANES):
        rows = _rows(fixed, lane)
        assert len(rows) == ROWS
        assert _differ(rec, lane, rows) == 0, lane
    assert rec["wall_time"].max() > 25_000  # past the second arrival
    assert summary["jobs_present_per_decision"] > 1.2  # a backlog


def test_a_plain_policy_without_the_cap_parts_from_the_program(fixed):
    assert _differ(fixed[6], 0, _rows(fixed, 0, fair=False)) > 0


def test_a_simulator_without_the_moving_delay_parts_from_the_program(
        fixed, monkeypatch):
    from benchmarks.reference import stream_np

    real = stream_np._Episode.__init__

    def init(self, *a, **kw):
        real(self, *a, **kw)
        self.moving_delay = 0.0

    monkeypatch.setattr(stream_np._Episode, "__init__", init)
    assert _differ(fixed[6], 0, _rows(fixed, 0)) > 0


def test_a_whole_episode_and_the_reseed_after_it_equal_the_simulators(fixed):
    """What the cell's `verify` rests on past the first rows: an episode
    at 10 x 50 runs past 2^24 sim-ms, where float32 no longer holds
    every whole number, and the program's rows still equal the plain
    simulator's to the last bit (it keeps its times in float32 too),
    through the episode's end, its stored result and the re-seed after
    it; `rows_differ` and `results_differ` from a row `start` onward
    (how a staggered copy of a lane is held to its source's rows); a
    stored mean a thousandth off is counted."""
    import jax

    from sparksched_tpu import sweep

    params, bank, sched, tables, durations, carry = fixed[:6]
    lane, calls = 1, 11
    recs = []
    for i in range(calls):
        carry, rec, _ = sweep.sweep_chunk(
            params, bank, sched.batch_policy, carry,
            jax.random.PRNGKey(i), ROWS)
        recs.append(sweep_chunks.record_arrays(rec))
    rec = {k: np.concatenate([r[k] for r in recs]) for k in recs[0]}
    want = sweep_chunks.simulated(
        sweep, params, bank, tables, durations, fixed[5].key[lane],
        calls * ROWS, ordinals=range(3))
    assert len(want) == calls * ROWS
    assert _differ(rec, lane, want) == 0
    differ, ends, worst = sweep_chunks.results_differ(
        rec, lane, want, 0, 1e-5)
    assert (differ, ends) == (0, 1) and worst < 1e-6
    end = int(np.flatnonzero(rec["reset"][:, lane])[0])
    assert rec["makespan"][end, lane] > 2 ** 24
    assert rec["valid"][end + 1:, lane].all()  # the next episode's rows
    assert rec["ordinal"][-1, lane] == 1
    tail = {k: v[end - 5:] for k, v in rec.items()}
    assert _differ(tail, lane, want, end - 5) == 0
    assert _differ(tail, lane, want, end - 4) > 0
    assert sweep_chunks.results_differ(
        tail, lane, want, end - 5, 1e-5)[:2] == (0, 1)
    rec["avg_jct"][end, lane] *= 1.001
    assert sweep_chunks.results_differ(rec, lane, want, 0, 1e-5)[:2] == (1, 1)
