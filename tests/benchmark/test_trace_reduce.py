"""The reduction from a profiler trace to busy and idle time, scope
times, collective time, top operations and named idle gaps: on interval
arithmetic, on a four-device trace written by hand, and on a small trace
recorded on a TPU v5e."""

import glob
import gzip
import os

import pytest

from benchmarks import harness, trace_reduce as tr

US = 1_000_000  # picoseconds in a microsecond
DATA = os.path.join(harness.HERE, "tests", "data")


def test_interval_arithmetic():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)])
    assert u == [(0, 3), (5, 8)] and tr.total(u) == 6
    assert tr.subtract([(0, 10)], u) == [(3, 5), (8, 10)]
    assert tr.subtract([(0, 2), (6, 9)], [(1, 7)]) == [(0, 1), (7, 9)]
    assert tr.subtract([(0, 2)], []) == [(0, 2)]
    events = [{"start": 0.0, "dur": 10.0}, {"start": 1.0, "dur": 3.0},
              {"start": 2.0, "dur": 1.0}, {"start": 5.0, "dur": 2.0},
              {"start": 20.0, "dur": 1.0}]
    assert tr.self_times(events) == [5.0, 2.0, 1.0, 2.0, 1.0]


def _plane(dev: int, events: list[tuple[str, float, float, str]]) -> str:
    names = sorted({e[0] for e in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    body = "".join(
        f'events {{ metadata_id: {ids[n]} offset_ps: {int(a * US)} '
        f'duration_ps: {int((b - a) * US)} '
        f'stats {{ metadata_id: 1 str_value: "{scope}" }} }}\n'
        for n, a, b, scope in events)
    meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for n, i in ids.items())
    return (f'planes {{ id: {dev + 10} name: "/device:TPU:{dev}"\n'
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{body}}}\n'
            f'lines {{ id: 2 name: "Steps" timestamp_ns: 0\n'
            f'events {{ metadata_id: 1 offset_ps: 0 duration_ps: 1 }} }}\n'
            f'{meta}'
            'stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n}\n')


def _host(events: list[tuple[str, float, float]]) -> str:
    names = sorted({e[0] for e in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    body = "".join(
        f'events {{ metadata_id: {ids[n]} offset_ps: {int(a * US)} '
        f'duration_ps: {int((b - a) * US)} }}\n' for n, a, b in events)
    meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for n, i in ids.items())
    return ('planes { id: 1 name: "/host:CPU"\n'
            f'lines {{ id: 1 name: "main" timestamp_ns: 0\n{body}}}\n'
            f'{meta}}}\n')


@pytest.fixture()
def four_chip_trace(tmp_path):
    """Times in microseconds. Device 0: a while loop 0..100 holding a
    GNN fusion 10..30 and an engine fusion 40..60, an all-reduce
    120..150 of which 120..130 runs under a fusion (device 0's compute)
    and 130..150 is exposed; idle 100..120 (the host in `bench/update`)
    and 150..200 (in no span). Devices 1..3: busy 0..150."""
    gnn = "jit(step)/while/body/decima/gnn/dot_general"
    eng = "jit(step)/while/body/env/micro_step/select_n"
    dev0 = [("while.1", 0, 100, "jit(step)/while"),
            ("fusion.7", 10, 30, gnn), ("fusion.9", 40, 60, eng),
            ("fusion.11", 118, 130, "jit(step)/train/ppo_update/mul"),
            ("all-reduce.3", 120, 150, "jit(step)/train/ppo_update/psum"),
            ("fusion.early", -50, -10, gnn)]  # before the window: cut off
    others = [("fusion.7", 0, 150, gnn)]
    text = _plane(0, dev0) + "".join(_plane(d, others) for d in (1, 2, 3))
    text += _host([("bench/trace_window", 0, 200),
                   ("bench/collect", 0, 100), ("bench/update", 100, 150),
                   ("unrelated", 150, 200)])
    path = tmp_path / "four.textproto"
    path.write_text(text)
    return str(path)


def test_reduce_a_four_device_trace(four_chip_trace):
    r = tr.reduce_file(
        four_chip_trace, chips=4,
        host_spans=("bench/collect", "bench/update"),
        unattributed="train/host_gap", window_span="bench/trace_window")
    us = 1e-6
    assert r["devices"] == 4
    assert r["window_s"] == pytest.approx(200 * us)
    assert r["busy_s_per_device"] == pytest.approx(
        [132 * us, 150 * us, 150 * us, 150 * us])
    assert r["busy_s"] == pytest.approx((132 + 3 * 150) / 4 * us)
    # scope times are averaged over the devices
    assert r["scopes"]["decima/gnn"] == pytest.approx(
        (20 + 3 * 150) / 4 * us)
    assert r["scopes"]["env/micro_step"] == pytest.approx(20 / 4 * us)
    assert r["scopes"]["train/ppo_update"] == pytest.approx(32 / 4 * us)
    # busy under no scope: device 0's loop less what its scoped
    # operations cover (132 - 20 - 20 - 32), none on the other devices
    assert r["unscoped_s"] == pytest.approx(60 / 4 * us)
    # collectives: device 0's, and the part no compute covers
    assert r["collective_s"] == pytest.approx(30 * us)
    assert r["collective_exposed_s"] == pytest.approx(20 * us)
    top = dict(r["top_ops"])
    assert top["jit(step)/while"[:0] + "while.1"] == pytest.approx(60 * us)
    assert top["decima/gnn:fusion.7"] == pytest.approx(20 * us)
    assert top["env/micro_step:fusion.9"] == pytest.approx(20 * us)
    assert top["train/ppo_update:all-reduce.3"] == pytest.approx(30 * us)
    assert "decima/gnn:fusion.early" not in top
    gaps = dict(r["gaps"])
    assert gaps["bench/update"] == pytest.approx(18 * us)
    assert gaps["train/host_gap"] == pytest.approx(50 * us)
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s_per_device"][0])


def test_one_chip_of_a_four_device_trace_and_no_device_plane(
        four_chip_trace, tmp_path):
    r = tr.reduce_file(four_chip_trace, chips=1,
                       window_span="bench/trace_window")
    assert r["devices"] == 1 and r["busy_s"] == pytest.approx(132e-6)
    assert dict(r["gaps"]) == {"host/other": pytest.approx(68e-6)}
    empty = tmp_path / "host_only.textproto"
    empty.write_text(_host([("bench/trace_window", 0, 10)]))
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce_file(str(empty), chips=1)


def test_layer_readers_over_a_reduced_trace(four_chip_trace):
    trace = tr.reduce_file(four_chip_trace, chips=4,
                           window_span="bench/trace_window")
    trace["units"] = 2
    window = {"trace": trace}
    read = harness.read_layer_metric
    assert read("rollout.gnn_device_s", window) == pytest.approx(
        trace["scopes"]["decima/gnn"] / 2)
    assert read("rollout.idle_share", window) == pytest.approx(
        100 * (1 - trace["busy_s"] / trace["window_s"]))
    assert read("rollout.scatter_device_s", window) is None  # no such scope


def test_reduce_a_trace_recorded_on_a_tpu(tmp_path):
    """`tests/data/*.xplane.pb.gz`: two iterations of a tiny flat-engine
    trainer on one TPU v5e (32,453 operation events; recorded by PR 26's
    probe, call 32). Held to what must be true of any trace (busy within
    the window, scopes within busy) and to the scopes the program
    labels there; a TPU's events carry their scope in the metadata."""
    recorded = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb.gz")))
    assert recorded, "the recorded trace is missing"
    for gz in recorded:
        path = tmp_path / os.path.basename(gz)[:-3]
        with gzip.open(gz, "rb") as src:
            path.write_bytes(src.read())
        r = tr.reduce_file(
            str(path), chips=1, host_spans=("bench/collect", "bench/update"),
            unattributed="train/host_gap")
        assert r["devices"] == 1 and r["device_events"] == 32453
        assert 0 < r["busy_s"] <= r["window_s"]
        assert r["collective_s"] == 0.0
        for scope in ("decima/gnn", "collect/scatter", "train/ppo_update"):
            assert 0 < r["scopes"][scope] <= r["busy_s"], scope
        assert len(r["top_ops"]) == 10 and r["top_ops"][0][1] > 0
        gaps = dict(r["gaps"])
        assert sum(gaps.values()) == pytest.approx(
            r["window_s"] - r["busy_s"], rel=1e-6)
        assert set(gaps) <= {"bench/collect", "bench/update",
                             "train/host_gap"}


DRAIN = "jit(c)/while/body/vmap(env/micro_step/drain)/while/body/select_n"
DECIDE = "jit(c)/while/body/vmap(env/micro_step/decide)/scatter"
RESET = "jit(c)/while/body/env/micro_step/reset/cond/branch_1_fun/select_n"
FREEZE = "jit(c)/while/body/collect/freeze/select_n"


def test_a_sub_scope_is_matched_with_its_parent_and_is_the_inner_one():
    scopes = tr.scope_names(harness.metric_scopes())
    assert set(tr.KNOWN_SCOPES) <= set(scopes)
    assert len(scopes) == len(set(scopes))
    for name in ("env/micro_step/drain", "env/micro_step/decide",
                 "env/micro_step/reset", "collect/freeze"):
        assert name in scopes
    assert tr.scopes_in(DRAIN) == ("env/micro_step",)  # bare: the tuple
    assert tr.scopes_in(DRAIN, scopes) == (
        "env/micro_step", "env/micro_step/drain")
    assert tr.scopes_in(RESET, scopes) == (
        "env/micro_step", "env/micro_step/reset")
    # a scope inside another scope: outermost first, whatever the lengths
    nested = "jit(c)/env/micro_step/drain/while/body/decima/gnn/levels/dot"
    assert tr.scopes_in(nested, scopes + ("decima/gnn/levels",)) == (
        "env/micro_step", "env/micro_step/drain", "decima/gnn",
        "decima/gnn/levels")
    assert tr.scopes_in("jit(c)/while/body/copy", scopes) == ()


@pytest.fixture()
def row_trace():
    """One device, one second: a scan `while` under no scope holding a
    decide step 0.1..0.3, a drain loop 0.3..0.7 (a `while` of its own
    with a fusion inside), a re-seed 0.7..0.75, a freeze 0.75..0.8 and
    a copy under no scope 0.8..0.9."""
    events = [("while.1", 0.0, 0.9, "jit(c)/while"),
              ("fusion.1", 0.1, 0.3, DECIDE),
              ("while.2", 0.3, 0.7, DRAIN.rsplit("/body", 1)[0]),
              ("fusion.2", 0.35, 0.65, DRAIN),
              ("fusion.3", 0.7, 0.75, RESET),
              ("fusion.4", 0.75, 0.8, FREEZE),
              ("copy.1", 0.8, 0.9, "copy.1")]
    ops = {0: [{"name": n, "start": a, "dur": b - a, "text": f"{n} {t}"}
               for n, a, b, t in events]}
    reduced = tr.reduce_events(
        ops, [], window=(0.0, 1.0), chips=1,
        scopes=tr.scope_names(harness.metric_scopes()))
    return dict(reduced, units=0.5)


def test_the_engines_sub_scopes_add_up_to_the_engine(row_trace):
    scopes = row_trace["scopes"]
    assert scopes["env/micro_step/decide"] == pytest.approx(0.2)
    assert scopes["env/micro_step/drain"] == pytest.approx(0.4)
    assert scopes["env/micro_step/reset"] == pytest.approx(0.05)
    assert scopes["collect/freeze"] == pytest.approx(0.05)
    assert scopes["env/micro_step"] == pytest.approx(0.65)
    # busy 0.9, of which 0.7 under a scope: the scan's own 0.2
    assert row_trace["unscoped_s"] == pytest.approx(0.2)
    top = dict(row_trace["top_ops"])
    assert top["env/micro_step/drain:fusion.2"] == pytest.approx(0.3)
    assert top["env/micro_step/drain:while.2"] == pytest.approx(0.1)
    assert top["env/micro_step/decide:fusion.1"] == pytest.approx(0.2)
    assert top["while.1"] == pytest.approx(0.1)
    assert top["copy.1"] == pytest.approx(0.1)
    # bare, the reducer reads what it read: the parent scope alone
    bare = tr.reduce_events(
        {0: [{"name": "fusion.2", "start": 0.35, "dur": 0.3,
              "text": DRAIN}]}, [], window=(0.0, 1.0), chips=1)
    assert set(bare["scopes"]) == {"env/micro_step"}
    assert dict(bare["top_ops"]) == {
        "env/micro_step:fusion.2": pytest.approx(0.3)}


@pytest.mark.parametrize("name, want", [
    ("rollout.drain_device_s", 0.8), ("rollout.decide_device_s", 0.4),
    ("rollout.unscoped_device_s", 0.4), ("rollout.engine_device_s", 1.3),
    ("stream.drain_device_s", 0.8), ("stream.decide_device_s", 0.4),
    ("stream.reset_device_s", 0.1), ("stream.freeze_device_s", 0.1),
    ("stream.unscoped_device_s", 0.4), ("stream.engine_device_s", 1.3),
    ("dp4.drain_device_s", 0.8), ("dp4.decide_device_s", 0.4),
    ("dp4.unscoped_device_s", 0.4), ("dp4.engine_device_s", 1.3),
])
def test_a_scope_metric_reads_its_scope_a_collection(row_trace, name, want):
    """Seconds a collection: the traced window holds half a unit."""
    window = {"trace": row_trace}
    assert harness.read_layer_metric(name, window) == pytest.approx(want)
    bench = harness.load_benchmark()
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert entry["source"] == "device_trace" and entry["unit"] == "s"
    assert entry["moves"] == "rollout_decisions_per_s"
    assert len(entry["workloads"]) == 1
    # a trace with no operation under the scope: nothing to read
    empty = dict(row_trace, scopes={})
    if "unscoped" not in name:
        assert harness.read_layer_metric(name, {"trace": empty}) is None
