"""Host spans (`obs/tracing.py`: `span`, `spanned`, `SpanRecord`): the
process's one host timer, jax's compile events beside it, the trainer's
set-up spans and the runlog's view of them (PR 44)."""

from __future__ import annotations

import glob
import json
import threading

import pytest

from sparksched_tpu.obs import RunLog, tracing
from sparksched_tpu.obs.tracing import RECORD, SpanRecord, span, spanned

from .test_obs import _tiny_cfg

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


def spans_from(ordinal: int) -> list[dict]:
    return [s for s in RECORD.spans() if s["ordinal"] >= ordinal]


def test_span_keeps_name_times_parent_and_ordinal_under_nesting():
    with span("test/outer") as outer:
        with span("test/inner") as inner:
            pass
        with span("test/second") as second:
            pass
    got = {s["name"]: s for s in spans_from(outer.ordinal)}
    assert list(got) == ["test/outer", "test/inner", "test/second"]
    assert got["test/outer"]["parent"] is None
    assert got["test/inner"]["parent"] == outer.ordinal
    assert got["test/second"]["parent"] == outer.ordinal
    assert outer.ordinal < inner.ordinal < second.ordinal
    for s in got.values():
        assert s["start"] <= s["end"] and s["wall"] > 1e9
    assert got["test/outer"]["start"] <= got["test/inner"]["start"]
    assert got["test/second"]["end"] <= got["test/outer"]["end"]
    assert outer.elapsed == pytest.approx(
        got["test/outer"]["end"] - got["test/outer"]["start"])


def test_spans_of_two_threads_keep_their_own_parents():
    """The stack of open spans is a thread's own: a span opened on
    another thread while this one has a span open is no child of it."""
    inside = threading.Event()
    release = threading.Event()
    seen = {}

    def work():
        with span("test/thread") as outer:
            with span("test/thread_child") as child:
                seen.update(outer=outer.ordinal, child=child.ordinal)
                inside.set()
                assert release.wait(timeout=10)

    thread = threading.Thread(target=work, name="span-worker")
    with span("test/main") as main:
        thread.start()
        assert inside.wait(timeout=10)
        with span("test/main_child") as main_child:
            pass
        release.set()
        thread.join(timeout=10)
        assert not thread.is_alive()
    got = {s["name"]: s for s in spans_from(main.ordinal)}
    assert got["test/thread"]["parent"] is None
    assert got["test/thread_child"]["parent"] == seen["outer"]
    assert got["test/main_child"]["parent"] == main.ordinal
    assert len({main.ordinal, main_child.ordinal, *seen.values()}) == 4


def test_span_exception_pops_the_stack_and_still_records():
    """As `test_annotate_exception_safe` for `annotate`: a raise inside
    ends the span, records it with the error's name and leaves the
    thread's stack as it was, so the next span is nobody's child."""
    with pytest.raises(RuntimeError, match="boom"):
        with span("test/poisoned") as bad:
            raise RuntimeError("boom")
    with pytest.raises(ValueError):
        with span("test/around") as around:
            with span("test/raises"):
                raise ValueError("x")
    with span("test/after") as after:
        pass
    got = {s["name"]: s for s in spans_from(bad.ordinal)}
    assert got["test/poisoned"]["error"] == "RuntimeError"
    assert got["test/raises"]["error"] == "ValueError"
    assert got["test/raises"]["parent"] == around.ordinal
    assert got["test/after"]["parent"] is None and after.parent is None
    assert "error" not in got["test/after"]
    assert tracing._open_spans() == []


def test_span_as_a_decorator_is_one_span_a_call():
    @span("test/decorated")
    def f(x):
        return x + 1

    first = tracing.mark()
    assert f(1) == 2 and f(2) == 3
    got = [s for s in spans_from(first) if s["name"] == "test/decorated"]
    assert len(got) == 2 and got[0]["ordinal"] < got[1]["ordinal"]


def test_the_ring_evicts_collection_spans_and_never_a_setup_span():
    record = SpanRecord(ring=3, setup_cap=2)

    def rec(name, i):
        return {"name": name, "start": float(i), "end": i + 0.5,
                "wall": 2e9, "ordinal": i, "parent": None}

    record.add_span(rec("setup/trainer_init", 0))
    record.add_span(rec("setup/init_state", 1))
    for i in range(2, 12):
        record.add_span(rec("collect/call", i))
    names = [(s["ordinal"], s["name"]) for s in record.spans()]
    assert names == [(0, "setup/trainer_init"), (1, "setup/init_state"),
                     (9, "collect/call"), (10, "collect/call"),
                     (11, "collect/call")]
    # bounded all the same, by other set-up spans alone: past the cap
    # the OLDEST goes, so a long-lived process holds the trainer it
    # built last and not the one it built first
    record.add_span(rec("setup/trainer_init", 12))
    assert [s["ordinal"] for s in record.spans()] == [1, 9, 10, 11, 12]


def test_sinks_get_announced_spans_and_a_broken_sink_breaks_nothing():
    class Sink:
        def __init__(self):
            self.got = []

        def span_ended(self, rec):
            self.got.append(rec["name"])

    class Broken:
        def span_ended(self, rec):
            raise OSError("closed")

    sink, broken = Sink(), Broken()
    RECORD.span_sinks.add(sink)
    RECORD.span_sinks.add(broken)
    try:
        with span("test/announced"):
            pass
        with span("test/own_sink", announce=False) as quiet:
            pass
    finally:
        RECORD.span_sinks.discard(sink)
        RECORD.span_sinks.discard(broken)
    assert sink.got == ["test/announced"]
    assert [s["name"] for s in spans_from(quiet.ordinal)] == [
        "test/own_sink"]  # recorded all the same


def test_a_jitted_call_inside_a_span_yields_its_events_with_fun_name():
    """Trace, lower and compile of a function jitted inside a span are
    in the record under that span's ordinal with the function's name,
    on the span's clock; a jitted helper traced inside the outer trace
    reports an interval of its own INSIDE the outer one (so a phase's
    tracing time is the union of the intervals, not their sum)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def span_helper(x):
        return jnp.tanh(x) * 3.0

    @jax.jit
    def span_outer(x):
        return span_helper(x).sum() + span_helper(x + 1.0).sum()

    x = jnp.ones((23, 41))  # eager: its own small programs, out here
    with span("test/jit") as sp:
        jax.block_until_ready(span_outer(x))
    mine = [e for e in RECORD.events() if e["span"] == sp.ordinal]
    by_kind = {kind: [e for e in mine if e["event"] == kind]
               for kind in (TRACE, LOWER, COMPILE)}
    outer_trace = [e for e in by_kind[TRACE]
                   if e["fun_name"] == "span_outer"]
    inner_trace = [e for e in by_kind[TRACE]
                   if e["fun_name"] == "span_helper"]
    assert len(outer_trace) == 1 and inner_trace
    assert [e["fun_name"] for e in by_kind[LOWER]] == ["jit(span_outer)"]
    assert [e["fun_name"] for e in by_kind[COMPILE]] == ["jit(span_outer)"]
    rec = [s for s in RECORD.spans() if s["ordinal"] == sp.ordinal][0]
    slack = 0.05  # jax stamps time.time(); the record perf_counter
    for e in mine:
        assert e["start"] <= e["end"]
        assert rec["start"] - slack <= e["start"]
        assert e["end"] <= rec["end"] + slack
    o = outer_trace[0]
    for e in inner_trace:
        assert o["start"] - slack <= e["start"] and e["end"] <= o["end"]
    # the three phases follow each other
    assert o["end"] <= by_kind[LOWER][0]["end"] <= by_kind[COMPILE][0]["end"]


def test_an_event_outside_any_span_is_under_no_span():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def span_free(x):
        return x * 5.0 - 2.0

    tracing.listen_to_jax()
    jax.block_until_ready(span_free(jnp.ones((19, 3))))
    mine = [e for e in RECORD.events()
            if "span_free" in str(e["fun_name"])]
    assert {e["event"] for e in mine} == {TRACE, LOWER, COMPILE}
    assert all(e["span"] is None for e in mine)


def test_spanned_is_the_jit_object_in_every_other_respect():
    import jax
    import jax.numpy as jnp

    def f(x, y):
        return x @ y + 1.0

    bare = jax.jit(f)
    wrapped = spanned("test/call", bare)
    x, y = jnp.ones((4, 6)), jnp.full((6, 3), 2.0)
    first = tracing.mark()
    assert (wrapped(x, y) == bare(x, y)).all()
    assert wrapped.lower(x, y).as_text() == bare.lower(x, y).as_text()
    assert wrapped.eval_shape(x, y) == bare.eval_shape(x, y)
    assert wrapped.trace(x, y).jaxpr is not None
    wrapped.clear_cache()
    with pytest.raises(AttributeError):
        wrapped.no_such_attribute
    calls = [s for s in spans_from(first) if s["name"] == "test/call"]
    assert len(calls) == 1  # the call; `.lower` and the rest open none


# ---------------------------------------------------------------------------
# the trainer's spans
# ---------------------------------------------------------------------------

SETUP_ORDER = ["setup/mesh", "setup/trainer_init", "setup/workload_bank",
               "setup/scheduler_init", "setup/init_state", "collect/call",
               "collect/call"]


@pytest.fixture(scope="module")
def collected_twice(tmp_path_factory):
    """A tiny trainer built as `train.py` builds it and its collector
    called twice, as the benchmark's drivers call it."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.trainers import make_trainer

    first = tracing.mark()
    trainer = make_trainer(_tiny_cfg(tmp_path_factory.mktemp("spans")))
    state = trainer.init_state()
    rollouts = []
    for i in range(2):
        ro, _, _ = trainer._collect_jit(
            state.params, jnp.int32(i), jax.random.PRNGKey(i), None)
        jax.block_until_ready(ro.reward)
        rollouts.append(ro)
    return {"trainer": trainer, "state": state, "first": first,
            "last": tracing.mark(), "rollouts": rollouts}


def trainer_spans(run: dict) -> list[dict]:
    return [s for s in RECORD.spans()
            if run["first"] < s["ordinal"] < run["last"]]


def test_a_trainer_built_and_collected_twice_leaves_its_spans_in_order(
        collected_twice):
    spans = trainer_spans(collected_twice)
    assert [s["name"] for s in spans] == SETUP_ORDER
    by_name = {s["name"]: s for s in spans[:5]}
    init = by_name["setup/trainer_init"]
    for child in ("setup/workload_bank", "setup/scheduler_init"):
        assert by_name[child]["parent"] == init["ordinal"]
        assert init["start"] <= by_name[child]["start"]
        assert by_name[child]["end"] <= init["end"]
    for top in ("setup/mesh", "setup/trainer_init", "setup/init_state"):
        assert by_name[top]["parent"] is None
    for a, b in zip(spans, spans[1:]):
        assert a["start"] <= b["start"]
    # the first call traces, lowers and compiles the collector, all
    # inside its span; the second is a dispatch
    first_call, second_call = spans[5], spans[6]
    events = [e for e in RECORD.events()
              if e["span"] == first_call["ordinal"]]
    for kind in (TRACE, LOWER, COMPILE):
        assert any(e["event"] == kind and "_collect" in e["fun_name"]
                   for e in events), kind
    assert not [e for e in RECORD.events()
                if e["span"] == second_call["ordinal"]
                and e["event"] in (LOWER, COMPILE)]
    assert (second_call["end"] - second_call["start"]
            < first_call["end"] - first_call["start"])


def test_the_spanned_collector_lowers_and_returns_what_the_jit_returns(
        collected_twice):
    import jax
    import jax.numpy as jnp

    trainer, state = collected_twice["trainer"], collected_twice["state"]
    args = (state.params, jnp.int32(1), jax.random.PRNGKey(1), None)
    bare = trainer._collect_jit._fn
    assert type(bare).__name__ == "PjitFunction"
    ro, _, _ = bare(*args)
    again = collected_twice["rollouts"][1]
    for a, b in zip(jax.tree_util.tree_leaves(ro),
                    jax.tree_util.tree_leaves(again)):
        assert (jnp.asarray(a) == jnp.asarray(b)).all()
    text = trainer._collect_jit.lower(*args).as_text(debug_info=True)
    assert text == bare.lower(*args).as_text(debug_info=True)
    # a host span reaches no op_name: the compiled program is the
    # parent's, and so is its cache entry
    assert "collect/observe" in text  # the device scopes are there
    for name in set(SETUP_ORDER) | {"train/update_call"}:
        assert name not in text, name


def test_a_runlog_opened_afterwards_holds_the_spans(collected_twice,
                                                    tmp_path):
    rl = RunLog(str(tmp_path / "after.jsonl"))
    rl.write("run_start")
    # from the trainer's first span on: what other tests of this
    # process left in the record before it stays out
    rl.follow_spans(collected_twice["first"])
    with span("test/later"):
        pass
    with rl.span("test/own"):  # written by the runlog itself, once
        pass
    rl.close()
    with span("test/after_close"):
        pass
    recs = [json.loads(ln) for ln in open(rl.path)]
    assert recs[0]["ev"] == "run_start" and recs[-1]["ev"] == "run_end"
    spans = [r for r in recs if r["ev"] == "span"]
    assert all(r["ordinal"] > collected_twice["first"]
               for r in spans if "ordinal" in r)
    want = {s["ordinal"]: s for s in trainer_spans(collected_twice)}
    held = [r for r in spans if r.get("ordinal") in want]
    assert [r["name"] for r in held] == SETUP_ORDER
    assert spans[:len(held)] == held  # the backlog opens with them
    for r in held:
        s = want[r["ordinal"]]
        assert r["secs"] == pytest.approx(s["end"] - s["start"], abs=1e-3)
        assert r.get("parent") == s["parent"]
        assert r["started"] == pytest.approx(s["wall"], abs=1e-2)
    names = [r["name"] for r in spans]
    assert names.count("test/later") == 1 and names.count("test/own") == 1
    assert "test/after_close" not in names


ITERATIONS = [
    "collect/call", "iter 1 collect", "train/update_call", "iter 1 update",
    "collect/call", "iter 2 collect", "train/update_call", "iter 2 update"]


def test_training_writes_the_start_up_split_and_a_call_an_iteration(
        tmp_path):
    """An operator's `run.jsonl`, nothing asked for: this trainer's
    set-up spans after `run_start` (and no other trainer's, though this
    process has built some), then `collect/call` and
    `train/update_call` inside the iteration's own spans, each once. A
    second `train()` of the same trainer opens its log with what it
    paid itself (a state), not the first run's set-up or iterations."""
    from sparksched_tpu.trainers import make_trainer

    first = tracing.mark()
    trainer = make_trainer(_tiny_cfg(tmp_path, num_iterations=2))
    logs = []
    for _ in range(2):
        trainer.train()
        (path,) = set(glob.glob(str(tmp_path / "runlog" / "*.jsonl"))
                      ) - set(logs)
        logs.append(path)
    recs = [json.loads(ln) for ln in open(logs[0])]
    assert recs[0]["ev"] == "run_start"
    spans = [r for r in recs if r["ev"] == "span"]
    assert [r["name"] for r in spans] == [
        "setup/mesh", "setup/trainer_init", "setup/workload_bank",
        "setup/scheduler_init", "setup/init_state"] + ITERATIONS
    assert spans[0]["ordinal"] == first + 1
    in_record = {s["name"]: s for s in spans_from(first)}
    assert in_record["collect/call"]["parent"] == (
        in_record["iter 2 collect"]["ordinal"])
    # a compile record names the span it fell in: the first iteration's
    # `collect/call` compiled the collector, the second's nothing
    calls = [r["ordinal"] for r in spans if r["name"] == "collect/call"]
    compiled = {r["span"] for r in recs if r["ev"] == "jit_compile"
                and "_collect" in str(r["fun_name"])}
    assert compiled == {calls[0]}
    again = [json.loads(ln) for ln in open(logs[1])]
    assert [r["name"] for r in again if r["ev"] == "span"] == [
        "setup/init_state"] + ITERATIONS


def test_a_span_under_the_profiler_is_on_the_host_plane(tmp_path):
    """Under a running profiler the span lies on the host track of the
    same trace as the device's operations."""
    import jax
    import jax.numpy as jnp

    try:
        from jax.profiler import ProfileData
    except ImportError:
        pytest.skip("this jax reads no profile")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with span("test/profiled_host_span"):
            jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(
        tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    if not paths:
        pytest.skip("the CPU profiler wrote no trace here")
    found = [
        (plane.name, e.duration_ns)
        for plane in ProfileData.from_file(paths[0]).planes
        for line in plane.lines for e in line.events
        if e.name == "test/profiled_host_span"]
    assert found and all(name.startswith("/host:") for name, _ in found)
