"""The harness without a chip: `run.py` refuses anything but a TPU it
knows, cells, mixes and per-layer metrics are found by name, and
`BENCHMARK.json` keeps to the contract's limits."""

import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

from benchmarks import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_run_refuses_a_backend_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = harness.load_benchmark()["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "need 1 tpu device" in out.stderr
    assert '"correct"' not in out.stdout


def _fake_devices(monkeypatch, n: int, kind: str, platform: str = "tpu"):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(platform=platform, device_kind=kind)] * n)


def test_device_info_refuses_too_few_chips_and_an_unknown_kind(monkeypatch):
    _fake_devices(monkeypatch, 1, "TPU v5 lite")
    info, peaks = harness.device_info(1)
    assert info == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert peaks["bf16_tflops"] == 197 and peaks["hbm_gbps"] == 819
    with pytest.raises(SystemExit, match="need 4 tpu"):
        harness.device_info(4)
    _fake_devices(monkeypatch, 1, "TPU v9 imaginary")
    with pytest.raises(SystemExit, match="not in benchmarks/peaks.json"):
        harness.device_info(1)
    _fake_devices(monkeypatch, 4, "TPU v5 lite", platform="cpu")
    with pytest.raises(SystemExit, match="need 1 tpu"):
        harness.device_info(1)


def test_new_cell_mix_and_metric_are_found_by_name_with_no_edit(tmp_path):
    """A later PR adds files and entries and edits none that is there:
    a configuration, a mix, a per-layer metric as a data file over an
    existing reader, one as a reader of its own, and a new family."""
    base = tmp_path / "benchmarks"
    shutil.copytree(harness.HERE, base, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    bench = harness.load_benchmark()
    old = bench["workloads"][0]
    (base / "configs" / "new_model.json").write_text(json.dumps(
        {"source": "a later PR", "env": {"num_executors": 3}}))
    (base / "traffic" / "new_mix.json").write_text(json.dumps(
        {"driver": "collect_rollout", "lanes": 2}))
    (base / "layer_metrics" / "new.ratio.json").write_text(json.dumps(
        {"reader": "telemetry_ratio", "num": "a", "den": "b"}))
    (base / "layer_metrics" / "new.own_reader.py").write_text(
        "def read(window):\n    return window['x'] * 2\n")
    (base / "layer_metrics" / "new_family.py").write_text(
        "def read(window, key):\n    return window.get(key)\n")
    (base / "layer_metrics" / "new.by_family.json").write_text(json.dumps(
        {"reader": "new_family", "key": "y"}))
    bench["workloads"].append({"name": "new_cell", "config": "new_model",
                               "traffic": "new_mix", "chips": 1,
                               "why": "added as files and an entry"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("new_cell", base=str(base))
    assert cell["config_data"]["env"] == {"num_executors": 3}
    assert cell["mix"]["driver"] == "collect_rollout"
    assert harness.load_driver(cell["mix"]["driver"]).HOST_SPANS
    window = {"telemetry": [{"a": 4, "b": 3}, {"a": 2, "b": 1}],
              "x": 21, "y": 7}
    read = harness.read_layer_metric
    assert read("new.ratio", window, base=str(base)) == 1.5
    assert read("new.own_reader", window, base=str(base)) == 42
    assert read("new.by_family", window, base=str(base)) == 7
    assert read("new.ratio", {}, base=str(base)) is None  # nothing to read
    # the cell that was there still loads, and no file of it changed
    assert harness.load_cell(old["name"], base=str(base))["mix"]["driver"]
    assert all(p.read_bytes() == b for p, b in before.items())
    with pytest.raises(SystemExit, match="unknown workload"):
        harness.load_cell("no_such_cell", base=str(base))


def test_every_per_layer_reader_returns_nothing_on_an_empty_window():
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        assert harness.read_layer_metric(m["name"], {}) is None, m["name"]


# one collection's worth of what the window records, by hand
WINDOW = {
    "scalars": [{"collect_seconds": 20.0}, {"collect_seconds": 22.0},
                {"collect_seconds": 27.0}],
    "telemetry": [
        {"decisions": 100, "micro_steps": 250, "events_total": 1000,
         "straggler_ratio": 1.4},
        {"decisions": 300, "micro_steps": 650, "events_total": 3400,
         "straggler_ratio": 1.7}],
    "memory_peak_bytes": 4_500_000_000,
    "trace": {"window_s": 2.0, "busy_s": 1.5, "units": 0.1,
              "scopes": {"decima/gnn": 0.4, "collect/scatter": 0.02}},
}


@pytest.mark.parametrize("name, want", [
    ("rollout.collect_s", 22.0),
    ("rollout.micro_per_decision", 900 / 400),
    ("rollout.events_per_decision", 4400 / 400),
    ("rollout.straggler_ratio", 1.7),
    ("rollout.gnn_device_s", 4.0),
    ("rollout.scatter_device_s", 0.2),
    ("rollout.idle_share", 25.0),
    ("rollout.hbm_peak_gb", 4.5),
])
def test_each_per_layer_metric_reads_its_own_source(name, want):
    assert harness.read_layer_metric(name, WINDOW) == pytest.approx(want)
    names = [m["name"] for m in harness.load_benchmark()["per_layer"]]
    assert name in names


def test_a_scope_time_needs_the_traced_share_of_a_unit():
    window = {"trace": dict(WINDOW["trace"], units=None)}
    assert harness.read_layer_metric("rollout.gnn_device_s", window) is None
    assert harness.read_layer_metric("rollout.idle_share", window) == 25.0


def test_stat_and_merge():
    assert harness.stat([3, 1, 2], "median") == 2.0
    assert harness.stat([3, 1, 2], "mean") == 2.0
    assert harness.stat([3, 1, 2], "max") == 3.0
    assert harness.stat([], "median") is None
    with pytest.raises(ValueError):
        harness.stat([1], "p95")
    base = {"a": {"x": 1, "y": 2}, "b": 3}
    assert harness.merge(base, {"a": {"y": 5}, "c": 7}) == {
        "a": {"x": 1, "y": 5}, "b": 3, "c": 7}
    assert base == {"a": {"x": 1, "y": 2}, "b": 3}


def test_checks_fail_on_a_missing_number():
    assert harness.check("a", 1.0, 2.0)["ok"]
    assert not harness.check("a", 3.0, 2.0)["ok"]
    assert not harness.check("a", float("nan"), 2.0)["ok"]
    assert not harness.check("a", None, 2.0)["ok"]
    assert harness.check("a", 0, 0, "==")["ok"]
    assert harness.check("a", 5, 3, ">=")["ok"]
    # and the result line shows it as JSON that any parser reads
    from benchmarks import run

    shown = [run._shown(v) for v in (float("nan"), float("inf"), 0.5, None)]
    assert shown == ["nan", "inf", 0.5, None]
    assert "NaN" not in json.dumps(shown)


def test_seeds_past_31_bits_make_keys_and_config_seeds():
    import jax

    big = 2**31 + 12345
    a, b = harness.key_from_seed(big), harness.key_from_seed(big + 1)
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()
    assert 0 <= harness.seed31(big) < 2**31 - 1


def test_benchmark_json_keeps_to_the_contract():
    keeps_to_the_contract(harness.load_benchmark())


# what the names a test makes up start with (a cell, a configuration, a
# mix: `probe_`; a metric: `probe.`), so that no test's made-up name can
# be one a later PR wants: no entry of a real benchmark starts so
PROBE = ("probe_", "probe.")


def only_adds_to(bench: dict, parent: dict) -> None:
    """What the contract lets a PR that is no `benchmark` PR do to
    `BENCHMARK.json`: add entries at the end of their lists. Every
    entry of `parent` stands where it stood and as it stood, but that a
    metric's `workloads` may have grown at its end."""
    for key in ("command", "paths", "run_seconds"):
        assert bench[key] == parent[key], key
    for key in ("configs", "workloads"):
        assert bench[key][:len(parent[key])] == parent[key], key
    for key in ("end_to_end", "per_layer"):
        assert len(bench[key]) >= len(parent[key]), key
        for was, now in zip(parent[key], bench[key]):
            assert set(now) == set(was), was["name"]
            for field, value in was.items():
                kept = (now[field][:len(value)] if field == "workloads"
                        else now[field])
                assert kept == value, (was["name"], field)


def keeps_to_the_contract(bench: dict, *, base: str = harness.HERE,
                          root: str = harness.ROOT, probe: bool = False,
                          parent: dict | None = None) -> None:
    """The contract's limits on `BENCHMARK.json` (`bench`, at `root`,
    its files under `base`). `probe` says that it is a test's copy with
    made-up entries; any other holds no name that starts as theirs do.
    With the benchmark it was made from as `parent`, it only adds to
    it."""
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    paths = bench["paths"]
    assert all(not p.startswith("/") and ".." not in p for p in paths)
    for word in bench["command"]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in paths), word
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert len(configs) == len(bench["configs"])
    assert len(cells) == len(bench["workloads"])
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert os.path.exists(os.path.join(root, c["file"]))
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and not k.endswith(("_dim", "_rank"))
                   for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = harness.load_cell(w["name"], bench, base=base)  # both exist
        assert harness.load_driver(cell["mix"]["driver"])
        assert cell["config_data"]["lower_precision"]
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 4)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", [])) <= set(cells)
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200
        layers.add(m["layer"])
        reports = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= reports, m["name"]
        spec = os.path.join(base, "layer_metrics", m["name"])
        assert os.path.exists(spec + ".json") or os.path.exists(spec + ".py")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert m.get("workloads", cells), m["name"]  # some cell reports it
    for w in cells:  # set-up, another end-to-end metric, a per-layer one
        assert len(harness.metrics_of_cell(bench, w, "end_to_end")) >= 2
        assert harness.metrics_of_cell(bench, w, "per_layer")
    made_up = [n for n in names + list(configs) + list(cells) + [
        w["traffic"] for w in bench["workloads"]] if n.startswith(PROBE)]
    assert probe or not made_up, made_up
    if parent is not None:
        only_adds_to(bench, parent)
