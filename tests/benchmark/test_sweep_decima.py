"""The trained-policy sweep cell (`sweep_decima`, PR 49) without a chip:
its entries in `BENCHMARK.json` as rules on a benchmark (`entries_hold`,
held here on the repo's own and on a temporary copy with a LATER cell
appended, `test_overlay.a_copy_with_a_cell_appended`, where they pass,
and fail with the appended entries put first or with a `dsweep.*` metric
that lost its cell: `test_next_cell`'s two faults), every `dsweep.*` data
file read from a made-up window, the driver importable without jax and
ending at once on a program without the configuration's YAML, and the
program's own YAML stating the cluster and net the configuration
states."""

import copy
import os.path as osp
import subprocess
import sys

import pytest

from benchmarks import harness
from tests.benchmark import test_overlay
from tests.benchmark.test_next_cell import lost_its_cell, put_first

CELL, CONF, MIX = "sweep_decima", "tpch_demo_10x50_decima", "decima_steady"
RATE = "rollout_decisions_per_s"
BENCH = harness.load_benchmark()

SUMMARY = {
    "decisions": 229376, "micro_steps": 560000, "events_total": 2700000,
    "reseeds_total": 320, "reset_evals_total": 40960,
    "jobs_present_total": 6881280, "nodes_present_total": 91750400,
    "episode_decisions_total": 224000, "episodes_terminated_total": 320,
    "health_mask": 0,
    "row": {"rows": 16, "lane_rows": 229376, "drain_batch_iters": 128,
            "drain_lane_iters_executed": 1835008,
            "drain_iters_total": 458752},
}
WINDOW = {
    "scalars": [{"collect_seconds": s, "collection": i}
                for i, s in enumerate((16.0, 17.0, 19.0))],
    "telemetry": [SUMMARY, SUMMARY],
    "memory_peak_bytes": 2_400_000_000,
    "trace": {"window_s": 0.5, "busy_s": 0.495, "units": 0.025,
              "unscoped_s": 0.01,
              "scopes": {"env/micro_step": 0.3, "env/micro_step/drain": 0.25,
                         "env/micro_step/decide": 0.04,
                         "env/micro_step/reset": 0.01, "sweep/policy": 0.15,
                         "decima/gnn": 0.1, "decima/features": 0.02,
                         "decima/sample": 0.025, "collect/observe": 0.005}},
}
WANT = {
    "dsweep.chunk_s": 17.0,
    "dsweep.policy_device_s": 6.0, "dsweep.gnn_device_s": 4.0,
    "dsweep.features_device_s": 0.8, "dsweep.sample_device_s": 1.0,
    "dsweep.observe_device_s": 0.2, "dsweep.decide_device_s": 1.6,
    "dsweep.drain_device_s": 10.0, "dsweep.engine_device_s": 12.0,
    "dsweep.reset_device_s": 0.4, "dsweep.unscoped_device_s": 0.4,
    "dsweep.idle_share": 1.0, "dsweep.hbm_peak_gb": 2.4,
    "dsweep.micro_per_decision": 560000 / 229376,
    "dsweep.events_per_decision": 2700000 / 229376,
    "dsweep.drain_iters_per_row": 8.0,
    "dsweep.drain_batch_tax": 4.0,
    "dsweep.lane_row_occupancy": 1.0,
    "dsweep.reseeds_per_row": 20.0,
    "dsweep.reset_evals_per_reseed": 128.0,
    "dsweep.jobs_present_per_decision": 30.0,
    "dsweep.decisions_per_episode": 700.0,
    "dsweep.nodes_present_per_decision": 400.0,
}
LISTED = list(WANT)


@pytest.mark.parametrize("name", LISTED)
def test_each_dsweep_metric_reads_its_own_source(name):
    assert harness.read_layer_metric(name, WINDOW) == pytest.approx(
        WANT[name])
    assert harness.read_layer_metric(name, {}) is None
    # on a program without the counter or the scope (an older parent,
    # traced with this PR's files): a number or nothing, and no raise
    test_overlay.reads_the_parents_window(name)


def test_the_node_counter_is_in_the_summary_only_where_asked_for():
    from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros_like

    plain = telemetry_zeros_like((2,), episodes=True, results=True)
    assert plain.nodes_present_sum is None  # no leaf of a carry
    assert "nodes_present_total" not in summarize(plain)
    asked = summarize(telemetry_zeros_like(
        (2,), episodes=True, results=True, nodes=True))
    assert asked["nodes_present_total"] == 0
    assert asked["nodes_present_per_decision"] == 0


# -- the cell's entries -----------------------------------------------------


def lines_of(bench: dict) -> dict:
    """Every line of the new entries the contract holds to 200
    printable characters."""
    config = {c["name"]: c for c in bench["configs"]}[CONF]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    lines = {"config.source": config["source"], "config.why": config["why"],
             "cell.why": cell["why"]}
    for m in bench["per_layer"]:
        if m["name"].startswith("dsweep."):
            lines[m["name"] + ".layer"] = m["layer"]
    return lines


def entries_hold(bench: dict, base: str = harness.HERE) -> None:
    """What PR 49 added, where it was put: the sixth cell, under a
    configuration no older cell uses, the sixth in the rate's
    `workloads`; the listed `dsweep.*` metrics lead their family and
    the cell's per-layer metrics, each lists the cell alone and has its
    data file; every line within 200 printable characters; the mix
    names a driver that is there. What later PRs add follows."""
    config = {c["name"]: c for c in bench["configs"]}[CONF]
    assert set(config["reduced"]) == {"lanes", "rows_per_chunk"}
    assert "examples.py:15-23" in config["source"]
    assert "--sched decima" in config["source"]
    assert [c["name"] for c in bench["configs"]].index(CONF) == 5
    cell = bench["workloads"][5]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONF, MIX, 1)
    assert not any(w["config"] == CONF or w["traffic"] == MIX
                   for w in bench["workloads"][:5])
    rate = {m["name"]: m for m in bench["end_to_end"]}[RATE]
    assert rate["workloads"][5] == CELL
    family = [m for m in bench["per_layer"]
              if m["name"].startswith("dsweep.")]
    assert [m["name"] for m in family[:len(LISTED)]] == LISTED
    for m in family:
        assert m["workloads"] == [CELL], m["name"]
        assert m["moves"] == RATE
        assert osp.exists(osp.join(
            base, "layer_metrics", m["name"] + ".json")), m["name"]
    assert [m["name"] for m in harness.metrics_of_cell(
        bench, CELL, "per_layer")][:len(LISTED)] == LISTED
    first = [m["name"] for m in bench["per_layer"]].index(LISTED[0])
    assert not any(m["name"].startswith("dsweep.")
                   for m in bench["per_layer"][:first])
    assert first >= 100  # behind everything the benchmark had
    for where, line in lines_of(bench).items():
        assert 1 <= len(line) <= 200 and line.isprintable(), where
    loaded = harness.load_cell(CELL, bench, base=base)
    mix, conf = loaded["mix"], loaded["config_data"]
    assert mix["driver"] == "sweep_decima"
    driver = harness.load_driver(mix["driver"])
    for part in ("build", "warm_up", "measure", "verify", "close",
                 "HOST_SPANS", "UNATTRIBUTED"):
        assert hasattr(driver, part), part
    assert mix["lanes"] % 2048 == 0 and mix["rows_per_chunk"] == 16
    assert (mix["warmup_chunks"], mix["min_chunks"]) == (2, 2)
    assert (mix["trace_start_s"], mix["trace_seconds"]) == (4.0, 0.5)
    assert mix["end_to_end"] == RATE
    fair = harness.load_cell("sweep_fair", bench, base=base)["config_data"]
    assert conf["env"] == fair["env"]  # the control's cluster, to the digit
    assert conf["program_config"] == "config/sweep_decima_demo.yaml"
    assert conf["scheduler"]["agent_cls"] == "DecimaScheduler"
    assert conf["architecture"] is None and conf["chips"] == 1
    assert conf["lower_precision"] == {
        "bf16_compute": {"agent": {"compute_dtype": "bfloat16"}}}
    assert len(conf["guarantees"]) == 8
    limits = conf["limits"]
    assert limits["engine_source_lanes"] >= 2
    assert limits["engine_ends_compared"] >= 4
    assert 0 < limits["logprob_stated_gap_quantile_ratio"] < 1
    assert (mix["lanes"] // 128) % limits["engine_copy_stride"] == 0


def test_the_cells_entries_are_what_the_issue_names():
    entries_hold(BENCH)
    assert len(LISTED) == 23
    assert len(BENCH["per_layer"]) <= 128


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    """The benchmark with a LATER PR's cell appended, in a copy."""
    root = tmp_path_factory.mktemp("after_sweep_decima")
    files = {"probe.collect_s": test_overlay.metric_spec("dsweep.chunk_s")}
    bench, base, before = test_overlay.a_copy_with_a_cell_appended(
        root, files)
    assert all(p.read_bytes() == b for p, b in before.items())
    return bench, str(base)


def test_the_rules_hold_with_a_later_cell_appended(appended):
    bench, base = appended
    assert bench["workloads"][-1]["name"] == test_overlay.PROBE_CELL
    entries_hold(copy.deepcopy(bench), base)


@pytest.mark.parametrize("fault", ["put_first", "lost_its_cell"])
def test_the_rules_fail_where_an_entry_moved_or_a_metric_lost_its_cell(
        appended, fault):
    bench, base = appended
    broken = (put_first(bench) if fault == "put_first"
              else lost_its_cell(bench, "dsweep.chunk_s", CELL))
    with pytest.raises(AssertionError):
        entries_hold(broken, base)


def test_no_name_of_the_cell_is_one_the_overlay_test_makes_up():
    from tests.benchmark.test_harness import PROBE

    assert not any(n.startswith(PROBE) for n in [CELL, CONF, MIX] + LISTED)


# -- the driver and the program's own configuration -------------------------


def test_the_driver_is_importable_without_jax():
    """`build` has to end on a program without the cell's YAML before
    jax is touched: the module itself imports none of it."""
    code = ("import sys; import benchmarks.drivers.sweep_decima; "
            "sys.exit(any(m == 'jax' or m.startswith(('jax.', "
            "'sparksched_tpu')) for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-400:]


@pytest.mark.parametrize("lanes, sample", [(14336, 512), (256, 64),
                                           (1024, 1000), (14336, 100)])
def test_the_policys_sample_takes_of_every_block_alike(lanes, sample):
    """A row evaluates the policy a block of 128 lanes at a time: the
    seeded sample holds as many lanes of one block as of another, to
    one, each once, and another seed gives other lanes."""
    import numpy as np

    from benchmarks.drivers import sweep_decima

    picked = sweep_decima.sampled_lanes(7, lanes, sample)
    assert len(set(picked.tolist())) == len(picked) == sample
    assert (np.diff(picked) > 0).all() and picked[-1] < lanes
    of_block = np.bincount(picked // sweep_decima.BLOCK,
                           minlength=lanes // sweep_decima.BLOCK)
    assert of_block.max() - of_block.min() <= 1
    assert (sweep_decima.sampled_lanes(7, lanes, sample) == picked).all()
    assert (sweep_decima.sampled_lanes(8, lanes, sample) != picked).any()


def test_the_driver_ends_at_once_without_the_programs_configuration():
    """On a program without `config/sweep_decima_demo.yaml` (the parent
    commit of this PR, run with this PR's files laid over it) `build`
    ends with a SystemExit naming the file, before it imports the
    program."""
    from benchmarks.drivers import sweep_decima

    cell = harness.load_cell(CELL, BENCH)
    cell["config_data"] = dict(
        cell["config_data"], program_config="config/no_such_decima.yaml")
    before = set(sys.modules)
    with pytest.raises(SystemExit, match="no config/no_such_decima.yaml"):
        sweep_decima.build(cell, 2**31 + 5)
    assert not any(m.startswith("sparksched_tpu.sweep")
                   for m in set(sys.modules) - before)


def test_the_programs_yaml_states_the_configurations_cluster_and_net():
    from benchmarks.drivers import sweep_chunks
    from sparksched_tpu import config as program_config

    conf = harness.load_cell(CELL, BENCH)["config_data"]
    cfg = program_config.load(osp.join(harness.ROOT, conf["program_config"]))
    fair = program_config.load(
        osp.join(harness.ROOT, "config", "sweep_fair_demo.yaml"))
    assert cfg["env"] == fair["env"]
    for key in sweep_chunks.ENV_KEYS:
        assert cfg["env"][key] == conf["env"][key], key
    agent, model = cfg["agent"], conf["model"]
    assert agent["agent_cls"] == conf["scheduler"]["agent_cls"]
    assert agent["embed_dim"] == model["embed_dim"] == 16
    assert agent["gnn_mlp_kwargs"] == {
        "hid_dims": model["gnn_hid_dims"], "act_cls": model["gnn_act"],
        "act_kwargs": {"negative_slope": model["gnn_negative_slope"]}}
    assert agent["policy_mlp_kwargs"] == {
        "hid_dims": model["policy_hid_dims"], "act_cls": model["policy_act"]}
    assert agent["job_bucket"] == model["job_bucket"] == 0
    assert "state_dict_path" not in agent and "num_levels" not in agent
    assert "deterministic" not in cfg["sweep"]  # sampled
    assert "fast_prng" not in cfg["sweep"]  # threefry keys
