"""The `setup.*` metrics (PR 44): `layer_metrics/program_span.py` over a
record of host spans and compile events written by hand, each of the
nine values; nothing to read without `T0`, without `scalars`, without a
window's first collection among the record's calls and on a program
that keeps no record; and, on jax's own events, a nested trace counted
once."""

import json
import os.path as osp
import sys

import pytest

from benchmarks import harness
from benchmarks.layer_metrics import program_span

TRACE = program_span.EVENTS["trace_s"]
LOWER = program_span.EVENTS["lower_s"]
COMPILE = program_span.EVENTS["compile_or_load_s"]
MISS = program_span.CACHE_MISSES
T0 = 100.0

# a streaming run's set-up, written by hand: two warm-up collections,
# the window's first is collection 2. Seconds from T0:
#   0    T0
#   6    setup/mesh 6..6.5, setup/trainer_init 7..10 (its children
#        inside), setup/init_state 10.5..11
#   12   collect/call 0: 12..40 (trace 12.5..30 with a helper's trace
#        nested 14..20 and another overlapping its end 29..31; lower
#        31..36; load 36..39), the device's run to 50
#   50   collect/call 1: 50..52 (a second program: trace 50.2..51.2,
#        compile 51.2..51.9 and a cache write), run and reading to 64
#   64   collect/call 2: the window's first, set-up's end
#   70   collect/call 3; 200 a reference trainer, as `verify` builds one
SPANS = [
    ("setup/mesh", 6.0, 6.5, None),
    ("setup/trainer_init", 7.0, 10.0, None),
    ("setup/workload_bank", 7.2, 8.2, 1),
    ("setup/scheduler_init", 8.4, 9.9, 1),
    ("setup/init_state", 10.5, 11.0, None),
    ("collect/call", 12.0, 40.0, None),
    ("collect/call", 50.0, 52.0, None),
    ("collect/call", 64.0, 64.01, None),
    ("collect/call", 70.0, 70.01, None),
    ("setup/trainer_init", 200.0, 203.0, None),
    ("collect/call", 204.0, 240.0, None),
]
EVENTS = [
    (TRACE, "_collect", 12.5, 30.0, 5),
    (TRACE, "reset_pair", 14.0, 20.0, 5),
    (TRACE, "_where", 29.0, 31.0, 5),
    (LOWER, "jit(_collect)", 31.0, 36.0, 5),
    (COMPILE, "jit(_collect)", 36.0, 39.0, 5),
    (TRACE, "_collect", 50.2, 51.2, 6),
    (COMPILE, "jit(_collect)", 51.2, 51.9, 6),
    (MISS, None, 51.9, 51.9, 6),
    (TRACE, "fold_in", 8.5, 8.6, 3),
    (COMPILE, "jit(fold_in)", 8.6, 8.8, 3),
    (TRACE, "_collect", 204.5, 230.0, 10),  # after set-up: not counted
    (MISS, None, 236.0, 236.0, 10),
    (TRACE, "before_t0", -5.0, -1.0, None),
]
WANT = {
    "before_trainer_s": 6.0,
    "trainer_init_s": 0.5 + 3.0 + 0.5,
    "collector_call_s": 28.0 + 2.0,
    "warmup_run_s": 10.0 + 12.0,
    "unattributed_s": 64.0 - 6.0 - 4.0 - 30.0 - 22.0,
    "trace_s": 0.1 + (31.0 - 12.5) + 1.0,  # the union, not 27.6
    "lower_s": 5.0,
    "compile_or_load_s": 0.2 + 3.0 + 0.7,
    "cache_misses": 1,
    "scheduler_init_s": 1.5,  # a span's own seconds: `span_s`
}
WINDOW = {"scalars": [{"collection": 2, "collect_seconds": 12.0},
                      {"collection": 3, "collect_seconds": 12.0}]}


def hand_record():
    """The table above as the program's own kind of record."""
    from sparksched_tpu.obs.tracing import SpanRecord

    record = SpanRecord()
    for i, (name, start, end, parent) in enumerate(SPANS):
        record.add_span({"name": name, "start": T0 + start,
                         "end": T0 + end, "wall": 2e9 + start,
                         "ordinal": i, "parent": parent})
    for event, fun, start, end, under in EVENTS:
        record.add_event({"event": event, "fun_name": fun,
                          "start": T0 + start, "end": T0 + end,
                          "secs": end - start, "span": under})
    return record


@pytest.fixture
def by_hand(monkeypatch):
    """`run.py`'s `T0` and the hand-written record in the program's
    place."""
    from sparksched_tpu.obs import tracing

    monkeypatch.setattr(sys.modules["__main__"], "T0", T0, raising=False)
    monkeypatch.setattr(tracing, "RECORD", hand_record())


PARTS = ("before_trainer_s", "trainer_init_s", "collector_call_s",
         "trace_s", "lower_s", "compile_or_load_s", "cache_misses",
         "warmup_run_s", "unattributed_s")
# the ten entries PR 44 put after the 74 that were there
SETUP_METRICS = harness.load_benchmark()["per_layer"][74:84]


def entries_hold(bench: dict, base: str = harness.HERE) -> None:
    """The nine parts and a tenth (`setup/scheduler_init`, the one child
    of the trainer's start that read over a second on the chip:
    PERF.md, PR 44, by the reader's `span_s`) stand after the 74 that
    were there, none of their family before them; each lists the two
    cells it was added for first (PR 48 appended the two whose tests
    held their lists shut until then). A `setup.*` metric that a later
    PR adds follows them and moves `setup_s`."""
    names = [m["name"] for m in bench["per_layer"]]
    assert names[74:84] == ["setup." + part for part in PARTS] + [
        "setup.scheduler_init_s"]
    assert not any(n.startswith("setup.") for n in names[:74])
    for m in bench["per_layer"]:
        if m["name"].startswith("setup."):
            assert m["moves"] == "setup_s"
    for m in bench["per_layer"][74:84]:
        assert m["workloads"][:2] == ["decima_rollout", "decima_stream"]
        assert m["source"] == "program_span" and m["better"] == "lower"
        with open(osp.join(base, "layer_metrics", m["name"] + ".json")) as fp:
            spec = json.load(fp)
        part = m["name"].removeprefix("setup.")
        assert spec == {"reader": "program_span", "may_lack": True} | (
            {"part": part} if part in PARTS else
            {"part": "span_s", "span": "setup/scheduler_init"})


def test_the_benchmark_has_the_nine_after_the_74_that_were_there():
    entries_hold(harness.load_benchmark())


@pytest.mark.parametrize("name", [m["name"] for m in SETUP_METRICS])
def test_each_setup_metric_reads_its_part_of_the_hand_written_record(
        by_hand, name):
    value = harness.read_layer_metric(name, WINDOW)
    assert value == pytest.approx(WANT[name.removeprefix("setup.")])


def test_the_five_parts_tile_set_up(by_hand):
    read = harness.read_layer_metric
    tiles = ("before_trainer_s", "trainer_init_s", "collector_call_s",
             "warmup_run_s", "unattributed_s")
    assert sum(read("setup." + t, WINDOW) for t in tiles) == (
        pytest.approx(64.0))
    # the end moves with the window's first collection: a rollout cell's
    # one warm-up ends set-up at the second call
    one = {"scalars": [{"collection": 1}]}
    assert sum(read("setup." + t, one) for t in tiles) == (
        pytest.approx(50.0))
    assert read("setup.collector_call_s", one) == pytest.approx(28.0)
    assert read("setup.warmup_run_s", one) == pytest.approx(10.0)
    assert read("setup.cache_misses", one) == 0


@pytest.mark.parametrize("window", [
    {}, {"scalars": []}, {"scalars": [{"collection": 11}]}])
def test_no_scalars_or_no_such_collection_reads_nothing(by_hand, window):
    for m in SETUP_METRICS:
        assert harness.read_layer_metric(m["name"], window) is None


def test_without_t0_every_setup_metric_reads_nothing(by_hand, monkeypatch):
    monkeypatch.delattr(sys.modules["__main__"], "T0")
    for m in SETUP_METRICS:
        assert harness.read_layer_metric(m["name"], WINDOW) is None


def test_a_program_without_the_record_reads_nothing_where_the_file_says_so(
        by_hand, monkeypatch):
    """The parent commit's program: `obs/tracing.py` without `RECORD`.
    The data files say `may_lack`, so the line leaves the metrics out;
    a data file that did not would raise, so a slip does not read as
    nothing."""
    from sparksched_tpu.obs import tracing

    monkeypatch.delattr(tracing, "RECORD")
    for m in SETUP_METRICS:
        assert harness.read_layer_metric(m["name"], WINDOW) is None
    with pytest.raises(LookupError):
        program_span.read(WINDOW, "trace_s")
    monkeypatch.setitem(sys.modules, "sparksched_tpu.obs", None)
    assert program_span.read(WINDOW, "trace_s", may_lack=True) is None


def test_a_slip_in_a_part_or_a_span_name_raises(by_hand):
    with pytest.raises(KeyError):
        program_span.read(WINDOW, "no_such_part", may_lack=True)
    with pytest.raises(KeyError):
        program_span.read(WINDOW, "span_s", "setup/no_such", may_lack=True)
    # a span's seconds are those inside set-up: the reference trainer
    # that `verify` builds later is not in them
    assert program_span.read(
        WINDOW, "span_s", "setup/trainer_init") == pytest.approx(3.0)


def test_union_counts_nested_and_overlapping_intervals_once():
    union = program_span.union_s
    assert union([], 0.0, 10.0) == 0.0
    assert union([(1, 5), (2, 3), (4, 7), (9, 12)], 0.0, 10.0) == (
        pytest.approx(7.0))
    assert union([(-3, 2), (1, 4)], 0.0, 3.0) == pytest.approx(3.0)


def test_a_nested_jit_trace_is_counted_once_on_jaxs_own_events():
    """jax's trace events NEST: the helper jitted inside reports an
    interval of its own inside the outer function's. The reader's union
    over the real events of one call is the outer trace's length (plus
    nothing), where their sum counts the helper twice."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.obs.tracing import RECORD, span

    @jax.jit
    def union_helper(x):
        for _ in range(40):  # a trace long enough to weigh
            x = jnp.tanh(x) * 1.5 + 0.5
        return x

    @jax.jit
    def union_outer(x):
        return union_helper(x).sum() + union_helper(x * 2.0).sum()

    x = jnp.ones((17, 29))
    with span("test/union") as sp:
        jax.block_until_ready(union_outer(x))
    traces = [e for e in RECORD.events()
              if e["span"] == sp.ordinal and e["event"] == TRACE]
    outer = [e for e in traces if e["fun_name"] == "union_outer"]
    inner = [e for e in traces if e["fun_name"] == "union_helper"]
    assert len(outer) == 1 and inner
    total = sum(e["secs"] for e in traces)
    union = program_span.union_s(
        [(e["start"], e["end"]) for e in traces], sp.start, sp.end)
    assert union == pytest.approx(outer[0]["secs"], abs=0.02)
    assert total >= union + inner[0]["secs"] - 1e-6
    assert inner[0]["secs"] > 0
