"""Observability subsystem (sparksched_tpu/obs): runlog JSONL schema
(incl. the `memory`/`trace`/`metrics` records, size-based rotation and
crash-safe teardown), the streaming-histogram metrics layer (ISSUE 11),
telemetry summaries, trace-annotation and profiler hygiene, and the
TensorBoard fallback. (The no-bare-print lint that used to live here is
now the analyzer's `bare-print` rule — sparksched_tpu/analysis/lint.py,
run by tests/test_static_analysis.py.)"""

from __future__ import annotations

import json
import logging
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest


def _tiny_cfg(tmp_path, **trainer_overrides):
    cfg = {
        "trainer": {
            "trainer_cls": "PPO",
            "num_iterations": 1,
            "num_sequences": 1,
            "num_rollouts": 2,
            "seed": 0,
            "use_tensorboard": False,
            "num_epochs": 1,
            "num_batches": 2,
            "beta_discount": 5.0e-3,
            "opt_kwargs": {"lr": 3.0e-4},
            "max_grad_norm": 0.5,
            "rollout_steps": 30,
            "artifacts_dir": str(tmp_path),
            "checkpointing_freq": 10**9,
        },
        "agent": {
            "agent_cls": "DecimaScheduler",
            "embed_dim": 8,
            "gnn_mlp_kwargs": {
                "hid_dims": [16, 8],
                "act_cls": "LeakyReLU",
                "act_kwargs": {"negative_slope": 0.2},
            },
            "policy_mlp_kwargs": {"hid_dims": [16, 16],
                                  "act_cls": "Tanh"},
        },
        "env": {
            "num_executors": 5,
            "job_arrival_cap": 3,
            "moving_delay": 2000.0,
            "mean_time_limit": 2.0e7,
            "job_arrival_rate": 4.0e-5,
            "warmup_delay": 1000.0,
        },
        "obs": {"runlog": True, "telemetry": True},
    }
    cfg["trainer"].update(trainer_overrides)
    return cfg


# ---------------------------------------------------------------------------
# metrics edge case (satellite): all-false mask
# ---------------------------------------------------------------------------


def test_masked_percentiles_all_false_mask():
    from sparksched_tpu.metrics import PERCENTILE_QS, masked_percentiles

    out = masked_percentiles(
        np.array([1.0, 2.0, 3.0]), np.zeros(3, dtype=bool)
    )
    assert out.shape == (len(PERCENTILE_QS),)
    np.testing.assert_array_equal(out, np.zeros(len(PERCENTILE_QS)))
    # batched (pooled) form with an all-false mask too
    out2 = masked_percentiles(
        np.zeros((4, 3)), np.zeros((4, 3), dtype=bool)
    )
    np.testing.assert_array_equal(out2, np.zeros(len(PERCENTILE_QS)))


# ---------------------------------------------------------------------------
# streaming metrics (ISSUE 11): log-bucketed histogram quantiles,
# merge, the counter/gauge/hist registry and its two exporters
# ---------------------------------------------------------------------------


def test_streaming_histogram_quantiles_merge_and_bounds():
    from sparksched_tpu.obs.metrics import StreamingHistogram

    rng = np.random.default_rng(0)
    xs = rng.lognormal(2.0, 1.0, 20_000)
    h = StreamingHistogram()
    h.add_many(xs)
    # the whole point: quantiles within the documented relative error
    # (half a bucket = sqrt(growth)-1) without retaining any samples
    bound = h.summary()["scheme"]["max_rel_err"] + 0.01
    for q in (0.5, 0.9, 0.99, 0.999):
        exact = float(np.percentile(xs, q * 100))
        assert abs(h.quantile(q) - exact) / exact < bound, q
    assert h.count == xs.size
    np.testing.assert_allclose(h.mean, xs.mean(), rtol=1e-9)
    assert h.min == xs.min() and h.max == xs.max()
    # mergeability: two halves == the whole, bucket-exact
    a, b = StreamingHistogram(), StreamingHistogram()
    a.add_many(xs[:7000])
    b.add_many(xs[7000:])
    a.merge(b)
    assert a.counts == h.counts and a.count == h.count
    # geometry mismatch must fail loudly, not shift quantiles
    with pytest.raises(ValueError, match="geometry"):
        a.merge(StreamingHistogram(growth=1.5))
    # under/overflow land in the clamp buckets, quantiles stay in range
    e = StreamingHistogram(lo=1.0, hi=10.0)
    e.add_many([0.0, 0.5, 100.0, 2.0])
    assert e.count == 4
    assert e.quantile(0.999) <= 100.0


def test_metrics_registry_snapshot_prometheus_and_merge():
    import json

    from sparksched_tpu.obs.metrics import MetricsRegistry

    m = MetricsRegistry()
    m.counter("serve_flush_size")
    m.counter("serve_flush_size")
    m.counter("serve_flush_linger")
    m.gauge("sessions_live", 5)
    for v in (1.0, 2.0, 4.0):
        m.observe("serve_queue_depth", v)
    snap = m.snapshot()
    json.dumps(snap)  # JSON-safe by contract (the JSONL exporter)
    assert snap["counters"]["serve_flush_size"] == 2
    assert snap["hists"]["serve_queue_depth"]["count"] == 3
    txt = m.to_prometheus()
    assert "# TYPE serve_flush_size counter" in txt
    assert "serve_flush_size 2" in txt
    assert "sessions_live 5" in txt
    # histogram exposition: cumulative buckets ending in +Inf, _sum,
    # _count — monotone by construction
    assert 'serve_queue_depth_bucket{le="+Inf"} 3' in txt
    assert "serve_queue_depth_sum 7" in txt
    cums = [
        int(ln.rsplit(" ", 1)[1]) for ln in txt.splitlines()
        if ln.startswith("serve_queue_depth_bucket")
    ]
    assert cums == sorted(cums)
    # cross-worker merge: counters add, hists merge
    m2 = MetricsRegistry()
    m2.counter("serve_flush_size", 3)
    m2.observe("serve_queue_depth", 8.0)
    m.merge(m2)
    assert m.counters["serve_flush_size"] == 5
    assert m.hists["serve_queue_depth"].count == 4


def test_percentile_block_matches_legacy_and_hist_companion():
    """The shared helper IS the r10 latency-row block: identical keys
    and values to the pre-refactor numpy computation, so r10/r11
    artifacts stay comparable; `hist_summary` is the O(buckets)
    companion whose quantiles agree within the documented error."""
    from sparksched_tpu.obs.metrics import hist_summary, percentile_block

    samples = list(np.random.default_rng(3).lognormal(1.0, 0.8, 500))
    block = percentile_block(samples, reps=500)
    assert set(block) == {
        "p50_ms", "p90_ms", "p99_ms", "mean_ms", "max_ms", "reps",
    }
    a = np.asarray(samples)
    assert block["p50_ms"] == round(float(np.percentile(a, 50)), 4)
    assert block["p99_ms"] == round(float(np.percentile(a, 99)), 4)
    hb = hist_summary(samples)
    bound = hb["scheme"]["max_rel_err"] + 0.01
    assert abs(hb["p50_ms"] - block["p50_ms"]) / block["p50_ms"] < bound


# ---------------------------------------------------------------------------
# profiler trace hygiene (satellite): an exception inside a traced block
# must not leave the process-global tracer running
# ---------------------------------------------------------------------------


def test_profiler_stops_trace_on_exception(tmp_path):
    import jax

    from sparksched_tpu.trainers.profiler import Profiler

    with pytest.raises(RuntimeError, match="boom"):
        with Profiler(str(tmp_path / "t1"), quiet=True):
            raise RuntimeError("boom")
    # the tracer must be free again: a fresh capture raises
    # "Only one profile may be run at a time" if __exit__ leaked it
    jax.profiler.start_trace(str(tmp_path / "t2"))
    jax.profiler.stop_trace()


def test_annotate_exception_safe():
    """A raise inside an annotated region must pop the named-scope
    stack — a leaked scope would prefix every LATER trace's labels with
    the dead phase name (the corruption the ISSUE-5 satellite pins)."""
    import jax

    from jax._src import source_info_util

    from sparksched_tpu.obs import annotate

    def stack() -> str:
        return str(source_info_util.current_name_stack())

    assert stack() == ""
    with annotate("live"):
        assert "live" in stack()
    assert stack() == ""
    with pytest.raises(RuntimeError, match="boom"):
        with annotate("poisoned"):
            assert "poisoned" in stack()
            raise RuntimeError("boom")
    assert stack() == "", "exception exit leaked the trace scope"
    # and nested: an inner raise unwinds exactly the inner scope
    with pytest.raises(ValueError):
        with annotate("outer"):
            try:
                with annotate("inner"):
                    raise ValueError("x")
            finally:
                assert "inner" not in stack() and "outer" in stack()
    assert stack() == ""
    # the annotation still functions after all that (tracing sanity)
    with annotate("alive"):
        jax.make_jaxpr(lambda x: x + 1)(1.0)


def test_profiler_sink_receives_span_even_when_quiet():
    from sparksched_tpu.trainers.profiler import Profiler

    got = []
    with Profiler(None, "lbl", quiet=True,
                  sink=lambda n, s: got.append((n, s))):
        pass
    assert got and got[0][0] == "lbl" and got[0][1] >= 0.0


# ---------------------------------------------------------------------------
# tensorboard import guard (satellite): torch is a heavy optional dep —
# absence must degrade to the runlog sink, not crash the trainer
# ---------------------------------------------------------------------------


def test_tensorboard_fallback_without_torch(tmp_path, monkeypatch,
                                            capsys):
    from sparksched_tpu.trainers import make_trainer

    # simulate an environment without torch: a None sys.modules entry
    # makes `from torch.utils.tensorboard import ...` raise ImportError
    for mod in ("torch", "torch.utils", "torch.utils.tensorboard"):
        monkeypatch.setitem(sys.modules, mod, None)
    cfg = _tiny_cfg(tmp_path, use_tensorboard=True)
    t = make_trainer(cfg)
    t._setup(fresh=True)
    assert t._tb is None, "fallback must disable the TB mirror"
    assert "runlog" in capsys.readouterr().out
    # the default sink is live: stats still land in the runlog
    t._write_stats(0, {"x": 1.0})
    t._runlog.close()
    recs = [json.loads(ln) for ln in open(t._runlog.path)]
    assert any(r["ev"] == "scalars" and r["x"] == 1.0 for r in recs)
    t._runlog = None


# ---------------------------------------------------------------------------
# runlog: JIT recompile hooks
# ---------------------------------------------------------------------------


def test_runlog_records_jit_compiles(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.obs import RunLog
    from sparksched_tpu.obs import runlog as runlog_mod

    monkeypatch.setattr(runlog_mod, "JIT_MIN_SECS", 0.0)
    rl = RunLog(str(tmp_path / "r.jsonl"))
    rl.install_jit_hooks()

    @jax.jit
    def f(x):
        return (x * 2.0 + 1.0).sum()

    # an off-pattern shape forces a fresh compile
    jax.block_until_ready(f(jnp.ones((37, 53))))
    rl.close()
    recs = [json.loads(ln) for ln in open(rl.path)]
    compiles = [r for r in recs if r["ev"] == "jit_compile"]
    assert compiles, "no jit_compile events recorded"
    assert all("event" in r and "secs" in r for r in compiles)
    backend = [r for r in compiles
               if r["event"].endswith("backend_compile_duration")]
    assert any(r["fun_name"] == "jit(f)" for r in backend), (
        "the compile records must name the compiled function"
    )
    # the scraper of jax's DEBUG log is gone with its record kind, and
    # the dispatch logger is as jax left it
    assert {r["ev"] for r in recs} == {"jit_compile", "run_end"}
    assert logging.getLogger("jax._src.dispatch").propagate


def test_runlog_span_and_json_safety(tmp_path):
    from sparksched_tpu.obs import RunLog

    rl = RunLog(str(tmp_path / "s.jsonl"))
    with rl.span("phase", iteration=np.int64(3)):
        pass
    with pytest.raises(ValueError):
        with rl.span("failing"):
            raise ValueError("x")
    rl.telemetry({"decisions": np.int32(7)}, iteration=0)
    rl.close()
    recs = [json.loads(ln) for ln in open(rl.path)]
    spans = [r for r in recs if r["ev"] == "span"]
    assert spans[0]["name"] == "phase" and spans[0]["iteration"] == 3
    assert spans[1]["error"] == "ValueError"
    tel = [r for r in recs if r["ev"] == "telemetry"][0]
    assert tel["summary"]["decisions"] == 7
    assert recs[-1]["ev"] == "run_end"


# ---------------------------------------------------------------------------
# CI smoke (satellite): one tiny training iteration with obs: enabled
# produces a valid-JSONL runlog with the expected span/counter keys
# ---------------------------------------------------------------------------


def test_runlog_memory_record_schema(tmp_path):
    from sparksched_tpu.obs import RunLog

    rl = RunLog(str(tmp_path / "m.jsonl"))
    rl.memory({"bytes_in_use": 111, "peak_bytes_in_use": 222},
              iteration=3)
    rl.memory(None, phase="bench_warmup")  # stats-less backends: no-op keys
    rl.close()
    recs = [json.loads(ln) for ln in open(rl.path)]
    mems = [r for r in recs if r["ev"] == "memory"]
    assert mems[0]["bytes_in_use"] == 111
    assert mems[0]["peak_bytes_in_use"] == 222
    assert mems[0]["iteration"] == 3
    assert mems[1]["phase"] == "bench_warmup"


def test_runlog_trace_and_metrics_records(tmp_path):
    """ISSUE 11: the `trace` record kind (per-request span offsets in
    ms from submit, `total_ms` stamped from reply) and the `metrics`
    record kind (a MetricsRegistry snapshot nested under `snapshot`)."""
    from sparksched_tpu.obs import MetricsRegistry, RunLog

    rl = RunLog(str(tmp_path / "t.jsonl"))
    rl.trace(
        "t1-00000001",
        {"submit": 0.0, "batch_admit": 1.5, "dispatch": 1.6,
         "device_compute": 9.0, "scatter_back": 9.4, "reply": 9.5},
        session_id=3, error=None,
    )
    m = MetricsRegistry()
    m.counter("serve_flush_size")
    rl.metrics(m.snapshot(), iteration=4)
    rl.close()
    recs = [json.loads(ln) for ln in open(rl.path)]
    tr = [r for r in recs if r["ev"] == "trace"][0]
    assert tr["trace_id"] == "t1-00000001" and tr["session_id"] == 3
    assert tr["spans"]["device_compute"] == 9.0
    assert tr["total_ms"] == 9.5
    mt = [r for r in recs if r["ev"] == "metrics"][0]
    assert mt["snapshot"]["counters"]["serve_flush_size"] == 1
    assert mt["iteration"] == 4


# ---------------------------------------------------------------------------
# runlog size-based rotation (ISSUE 11 satellite): long open-loop runs
# must never grow one unbounded JSONL, and the crash-safety guarantees
# must hold across rotation
# ---------------------------------------------------------------------------


def test_runlog_rotation_caps_active_file(tmp_path):
    from sparksched_tpu.obs import RunLog

    path = str(tmp_path / "r.jsonl")
    rl = RunLog(path, max_bytes=600)
    for i in range(200):
        rl.write("tick", i=i, pad="x" * 40)
    rl.close()
    segs = sorted(
        tmp_path.glob("r.jsonl.*"),
        key=lambda p: int(p.suffix[1:]),
    )
    assert len(segs) >= 3, "rotation never fired"
    # every segment AND the active file are complete valid JSONL
    all_ticks = []
    for p in [*segs, tmp_path / "r.jsonl"]:
        for ln in open(p):
            rec = json.loads(ln)  # every line parses
            if rec["ev"] == "tick":
                all_ticks.append(rec["i"])
        assert os.path.getsize(p) <= 600 + 200  # cap + one record slop
    assert all_ticks == list(range(200)), "rotation lost records"
    # rotated segments are immutable history; the ACTIVE file carries
    # the run_end and a `rotate` continuation marker at its head
    recs = [json.loads(ln) for ln in open(path)]
    assert recs[0]["ev"] == "rotate"
    assert recs[0]["segment"] == len(segs)
    assert recs[-1]["ev"] == "run_end"


def test_runlog_rotation_numbering_survives_restart(tmp_path):
    """A second run appending to the same path must continue the
    numbered-suffix sequence, not clobber the first run's segments."""
    from sparksched_tpu.obs import RunLog

    path = str(tmp_path / "s.jsonl")
    rl = RunLog(path, max_bytes=300)
    for i in range(40):
        rl.write("tick", run=1, i=i, pad="y" * 30)
    rl.close()
    first_segs = {p.name for p in tmp_path.glob("s.jsonl.*")}
    assert first_segs
    rl = RunLog(path, max_bytes=300)
    for i in range(40):
        rl.write("tick", run=2, i=i, pad="y" * 30)
    rl.close()
    for name in first_segs:
        recs = [json.loads(ln) for ln in open(tmp_path / name)]
        assert all(
            r.get("run", 1) == 1 for r in recs if r["ev"] == "tick"
        ), f"restart clobbered segment {name}"
    assert len(list(tmp_path.glob("s.jsonl.*"))) > len(first_segs)


def test_runlog_latency_record_and_serve_scalars(tmp_path):
    """ISSUE 10: the `latency` record kind (serving-path percentile
    samples, keys top-level and greppable like `memory`) and the
    serve-session `serve_*` per-iteration scalars — written through
    the standard `scalars` record and mirrored verbatim to a
    TensorBoard-style writer, the trainer's `_write_stats` contract."""
    from sparksched_tpu.obs import RunLog

    rl = RunLog(str(tmp_path / "l.jsonl"))
    rl.latency(
        {"p50_ms": 1.5, "p90_ms": 2.0, "p99_ms": 9.9, "mean_ms": 1.8,
         "reps": 100},
        iteration=2, batch=8,
    )
    rl.latency(None, phase="cold_start", cold_start_s=12.5)

    class _TB:
        def __init__(self):
            self.seen = []

        def add_scalar(self, k, v, i):
            self.seen.append((k, v, i))

    tb = _TB()

    class _Store:  # the SessionStore.log_stats surface, storeless
        stats = {"serve_decisions": 7, "serve_quarantines": 1}
        _runlog, _tb = rl, tb
        from sparksched_tpu.serve.session import SessionStore as _S
        log_stats = _S.log_stats

    _Store().log_stats(5, extra={"serve_p50_ms": 1.5})
    rl.close()
    recs = [json.loads(ln) for ln in open(rl.path)]
    lats = [r for r in recs if r["ev"] == "latency"]
    assert lats[0]["p50_ms"] == 1.5 and lats[0]["p99_ms"] == 9.9
    assert lats[0]["iteration"] == 2 and lats[0]["batch"] == 8
    assert lats[1]["phase"] == "cold_start"
    sc = [r for r in recs if r["ev"] == "scalars"][0]
    assert sc["serve_decisions"] == 7 and sc["iteration"] == 5
    # the TB mirror received identical keys/values at the iteration
    assert ("serve_decisions", 7, 5) in tb.seen
    assert ("serve_p50_ms", 1.5, 5) in tb.seen


# ---------------------------------------------------------------------------
# crash-safety (satellite): a watcher-killed run must leave a parseable
# runlog with its partial telemetry — SIGTERM lands a final run_end via
# the teardown hook; even without it, per-write flushing means every
# completed record survives
# ---------------------------------------------------------------------------

_KILLED_RUN = textwrap.dedent("""\
    import sys, time
    from sparksched_tpu.obs import RunLog

    mb = int(sys.argv[2]) if len(sys.argv) > 2 else 0
    rl = RunLog(sys.argv[1], max_bytes=mb or None)
    rl.write("run_start", demo="kill")
    for i in range(10_000):
        rl.write("tick", i=i, pad="z" * 40)
        if i == 30:
            print("READY", flush=True)
        time.sleep(0.002)
""")


def test_sigterm_killed_run_leaves_parseable_runlog(tmp_path):
    path = str(tmp_path / "killed.jsonl")
    env = os.environ | {"JAX_PLATFORMS": "cpu"}
    import pathlib

    p = subprocess.Popen(
        [sys.executable, "-c", _KILLED_RUN, path],
        env=env, stdout=subprocess.PIPE, text=True,
        cwd=pathlib.Path(__file__).resolve().parent.parent,
    )
    try:
        assert p.stdout.readline().strip() == "READY"
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=60)
    finally:
        p.kill()
    # the teardown hook restores the default disposition and re-raises,
    # so the exit status still says "killed by SIGTERM"
    assert rc == -signal.SIGTERM
    recs = [json.loads(ln) for ln in open(path)]  # every line parses
    assert recs[0]["ev"] == "run_start"
    assert any(r["ev"] == "tick" for r in recs)
    assert recs[-1]["ev"] == "run_end"
    assert recs[-1]["teardown"] == "sigterm"


def test_sigterm_killed_rotating_run_keeps_guarantees(tmp_path):
    """Crash-safety ACROSS rotation (ISSUE 11 satellite): a SIGTERMed
    run with a size cap leaves every rotated segment complete and
    parseable, and the teardown run_end stamped in the ACTIVE file —
    the same guarantees the uncapped runlog pins."""
    path = str(tmp_path / "killed_rot.jsonl")
    env = os.environ | {"JAX_PLATFORMS": "cpu"}
    import pathlib

    p = subprocess.Popen(
        [sys.executable, "-c", _KILLED_RUN, path, "500"],
        env=env, stdout=subprocess.PIPE, text=True,
        cwd=pathlib.Path(__file__).resolve().parent.parent,
    )
    try:
        assert p.stdout.readline().strip() == "READY"
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=60)
    finally:
        p.kill()
    assert rc == -signal.SIGTERM
    segs = sorted(
        tmp_path.glob("killed_rot.jsonl.*"),
        key=lambda q: int(q.suffix[1:]),
    )
    assert segs, "the capped run never rotated before the kill"
    ticks = []
    for q in [*segs, tmp_path / "killed_rot.jsonl"]:
        for ln in open(q):
            rec = json.loads(ln)  # every line of every segment parses
            if rec["ev"] == "tick":
                ticks.append(rec["i"])
    assert ticks == list(range(len(ticks))), "rotation lost a tick"
    recs = [json.loads(ln) for ln in open(path)]
    assert recs[-1]["ev"] == "run_end"
    assert recs[-1]["teardown"] == "sigterm"


def test_sigterm_teardown_never_blocks_on_held_lock(tmp_path):
    """The signal-path close must not block on the writer lock: a
    SIGTERM handler runs on the main thread possibly INSIDE a write()
    that holds the (non-reentrant) lock mid-line — blocking would
    deadlock the process, writing anyway would corrupt the line. With
    the lock held, _teardown must return immediately and leave the log
    open; with it free, it stamps run_end."""
    from sparksched_tpu.obs import RunLog

    rl = RunLog(str(tmp_path / "h.jsonl"))
    rl.write("tick", i=0)
    assert rl._lock.acquire(blocking=False)  # simulate interrupted write
    try:
        rl._teardown("sigterm")  # must return, not deadlock
        assert not rl._closed
    finally:
        rl._lock.release()
    rl._teardown("sigterm")  # lock free: closes with the stamp
    assert rl._closed
    recs = [json.loads(ln) for ln in open(rl.path)]
    assert recs[-1] == recs[-1] | {"ev": "run_end",
                                   "teardown": "sigterm"}


def test_obs_config_keys_validated_and_rotation_threaded(tmp_path):
    """The obs: block fails loudly on unknown keys (the health:/serve:
    contract, ISSUE 11) and `runlog_max_bytes` reaches the trainer's
    RunLog as a live rotation cap."""
    from sparksched_tpu.trainers import make_trainer

    with pytest.raises(ValueError, match="unknown obs"):
        cfg = _tiny_cfg(tmp_path)
        cfg["obs"] = {"runlog": True, "telemetri": True}  # typo'd knob
        make_trainer(cfg)
    cfg = _tiny_cfg(tmp_path)
    cfg["obs"]["runlog_max_bytes"] = 4096
    t = make_trainer(cfg)
    t._setup(fresh=True)
    assert t._runlog.max_bytes == 4096
    t._runlog.close()
    t._runlog = None


def test_trainer_stamps_memory_records(tmp_path, monkeypatch):
    """The trainer's per-iteration memory sample: `memory` runlog
    records + mem_* scalars, via the obs: block default. The allocator
    probe is monkeypatched — CPU backends report no stats, and the
    wiring (not the backend) is what this pins."""
    import sparksched_tpu.trainers.trainer as trainer_mod

    from sparksched_tpu.trainers import make_trainer

    monkeypatch.setattr(
        trainer_mod, "device_memory_stats",
        lambda device=None: {"bytes_in_use": 111,
                             "peak_bytes_in_use": 222},
    )
    cfg = _tiny_cfg(tmp_path)
    t = make_trainer(cfg)
    t.train()
    runlogs = list((tmp_path / "runlog").glob("*.jsonl"))
    recs = [json.loads(ln) for ln in open(runlogs[0])]
    start = [r for r in recs if r["ev"] == "run_start"][0]
    assert start["memory"] is True
    mems = [r for r in recs if r["ev"] == "memory"]
    assert mems and mems[-1]["peak_bytes_in_use"] == 222
    assert "iteration" in mems[-1]
    sc = [r for r in recs if r["ev"] == "scalars"][-1]
    assert sc["mem_peak_bytes"] == 222
    assert sc["mem_bytes_in_use"] == 111


def test_training_iteration_writes_runlog(tmp_path):
    from sparksched_tpu.trainers import make_trainer

    cfg = _tiny_cfg(tmp_path)
    t = make_trainer(cfg)
    t.train()
    runlogs = list((tmp_path / "runlog").glob("*.jsonl"))
    assert len(runlogs) == 1
    recs = []
    for ln in open(runlogs[0]):
        recs.append(json.loads(ln))  # every line must parse
    kinds = {r["ev"] for r in recs}
    assert {"run_start", "span", "scalars", "telemetry",
            "run_end"} <= kinds
    spans = {r["name"] for r in recs if r["ev"] == "span"}
    assert any("collect" in s for s in spans)
    assert any("update" in s for s in spans)
    tel = [r for r in recs if r["ev"] == "telemetry"][-1]["summary"]
    for key in ("decisions", "composition", "straggler_ratio",
                "events_by_kind", "micro_per_decision"):
        assert key in tel, f"telemetry summary missing {key}"
    assert tel["decisions"] > 0
    sc = [r for r in recs if r["ev"] == "scalars"][-1]
    for key in ("collect_seconds", "update_seconds",
                "straggler_ratio", "avg_num_jobs"):
        assert key in sc, f"scalars record missing {key}"




# ---------------------------------------------------------------------------
# decision rows of the single-eval batch collectors: the four row
# counters and the trace scopes of the scan body
# ---------------------------------------------------------------------------

# the scopes of the collector's scan body; a nested name holds its
# parent's, so the device trace's substring match reads both
ROW_SCOPES = (
    "collect/observe", "decima/features", "decima/gnn", "decima/sample",
    "env/micro_step/decide", "env/micro_step/drain", "collect/health",
    "collect/freeze", "collect/scatter",
)


def _tiny_decima_rows(monkeypatch, job_bucket: int, lanes: int = 3):
    """The 5-executor, 6-job cluster of the collection-parity tests, a
    small Decima with the given compaction bucket and `lanes` freshly
    reset lanes."""
    import jax

    from sparksched_tpu.env import core

    from .test_flat_loop import _decima_parity_fixture

    params, bank, make_sched = _decima_parity_fixture(monkeypatch)
    states = jax.vmap(lambda k: core.reset(params, bank, k))(
        jax.random.split(jax.random.PRNGKey(3), lanes)
    )
    return params, bank, make_sched(job_bucket=job_bucket), states


def _check_row_counters(tm, steps: int) -> dict:
    """The identities every collection holds; returns the `row` block
    of the summary. A frozen lane sits the drain out (PR 30), so with
    frozen lanes too the batch's iterations are bounded by the sum of
    what the lanes counted."""
    from sparksched_tpu.analysis.contracts import check_telemetry
    from sparksched_tpu.obs.telemetry import summarize

    assert check_telemetry(tm, batch_ndim=1) == []
    rows, live = np.asarray(tm.rows), np.asarray(tm.rows_live)
    drained = np.asarray(tm.drain_iters)
    batch = np.asarray(tm.drain_batch_iters)
    assert rows.tolist() == [steps] * rows.size  # every lane, frozen too
    assert (live == live[0]).all() and live[0] <= steps
    assert (batch == batch[0]).all()
    assert drained.max() <= batch[0]
    assert batch[0] <= drained.sum()
    # the reductions over the lane axis: the same in every lane, and at
    # least the drain `while`'s predicates (its bodies and one a row),
    # one predicate of the fused pass's loop a body that ended it at
    # once, and two of the row's own
    syncs = np.asarray(tm.lane_syncs)
    assert (syncs == syncs[0]).all()
    assert syncs[0] >= 2 * batch[0] + 3 * steps
    row = summarize(tm)["row"]
    assert row == {
        "rows": steps, "rows_live": int(live[0]),
        "rows_full_width": int(np.asarray(tm.rows_full_width).max()),
        "drain_batch_iters": int(batch[0]),
        "lane_syncs": int(syncs[0]),
        "lane_rows": steps * rows.size,
        "drain_lane_iters_executed": int(batch[0]) * rows.size,
        "drain_iters_total": int(drained.sum()),
        "lane_rows_frozen": int(np.asarray(tm.rows_frozen).sum()),
    }
    return row


@pytest.mark.parametrize("job_bucket", [1, 6])
def test_row_counters_of_the_sync_batch_collector(monkeypatch, job_bucket):
    """`rows` is the scan length in every lane, the drain's batch
    iterations lie between the slowest lane's total and the sum of all
    lanes', `rows_full_width` counts exactly the rows in which some lane
    holds more active jobs than the bucket (none when the bucket covers
    the job cap: the net then has one width), and the telemetry carry
    leaves the rollout as it is."""
    import jax

    from sparksched_tpu.obs.telemetry import telemetry_zeros_like
    from sparksched_tpu.trainers.rollout import collect_flat_sync_batch

    params, bank, sched, states = _tiny_decima_rows(monkeypatch, job_bucket)
    lanes, steps = 3, 24
    bpol = sched.flat_batch_policy()
    key = jax.random.PRNGKey(1)
    plain = collect_flat_sync_batch(
        params, bank, bpol, key, steps, states, fulfill_bulk=True)
    ro, tm = collect_flat_sync_batch(
        params, bank, bpol, key, steps, states,
        telemetry_zeros_like((lanes,)), fulfill_bulk=True, health=True)
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(ro)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    row = _check_row_counters(tm, steps)
    valid = np.asarray(ro.valid)
    assert valid.all(), "the fixture's lanes decide in every row"
    assert row["rows_live"] == steps
    # with every lane deciding in every row, stored step t of a lane is
    # what the lane observed in row t
    jobs = np.asarray(ro.obs.job_mask).sum(axis=-1)  # [lanes, steps]
    over = int((jobs > job_bucket).any(axis=0).sum())
    if job_bucket >= params.max_jobs:
        assert sched.full_width(ro.obs) is None
        assert row["rows_full_width"] == 0
    else:
        assert 0 < over and row["rows_full_width"] == over


def test_row_counters_of_the_async_batch_collector_with_frozen_lanes():
    """A lane that has used up its budget is frozen and keeps its own
    counts; the row counters are the batch's, so they go on in every
    lane. A policy that reports no width counts no full-width row."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import init_loop_state
    from sparksched_tpu.obs.telemetry import telemetry_zeros_like
    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.trainers.rollout import collect_flat_async_batch
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(
        num_executors=4, max_jobs=3, max_stages=20, max_levels=20,
        moving_delay=500.0, warmup_delay=200.0,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )

    def bpol(rng, obs):
        si, ne = jax.vmap(
            lambda o: round_robin_policy(o, params.num_executors, True)
        )(obs)
        return si, ne, {}

    lanes, steps = 2, 120
    states = jax.vmap(lambda k: core.reset(params, bank, k))(
        jax.random.split(jax.random.PRNGKey(7), lanes)
    )
    ro, _, tm = collect_flat_async_batch(
        params, bank, bpol, jax.random.PRNGKey(9), steps,
        jax.vmap(init_loop_state)(states), jnp.float32(2.0e6),
        telemetry=telemetry_zeros_like((lanes,)),
    )
    row = _check_row_counters(tm, steps)
    decided = np.asarray(ro.valid).sum(axis=1)
    assert (decided < steps).all(), "the budget froze no lane"
    assert np.asarray(tm.decide_steps).tolist() == decided.tolist()
    assert decided.max() <= row["rows_live"] < steps
    assert row["rows_full_width"] == 0


def test_summarize_reads_batch_counters_as_the_lane_maximum():
    """Every lane holds the same row counts; where a window's lanes
    differ, the maximum is the batch's. But the drain's bodies: a lane
    holds its own block's (PR 43), so the device ran their sum and
    `drain_batch_iters` is a lane's mean."""
    from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros_like

    tm = telemetry_zeros_like((4,))
    assert set(summarize(tm)["row"].values()) == {0}
    tm = tm.replace(
        rows=np.asarray([5, 7, 7, 6], np.int32),
        rows_live=np.asarray([4, 4, 5, 4], np.int32),
        rows_full_width=np.asarray([0, 2, 2, 2], np.int32),
        drain_batch_iters=np.asarray([30, 31, 31, 20], np.int32),
        drain_iters=np.asarray([10, 20, 25, 5], np.int32),
        lane_syncs=np.asarray([200, 260, 260, 240], np.int32),
    )
    assert summarize(tm)["row"] == {
        "rows": 7, "rows_live": 5, "rows_full_width": 2,
        "drain_batch_iters": 28, "lane_syncs": 260, "lane_rows": 28,
        "drain_lane_iters_executed": 112, "drain_iters_total": 60,
        "lane_rows_frozen": 0,
    }


def test_full_width_predicate_is_one_scalar_over_the_batch(monkeypatch):
    import types

    _, _, sched, _ = _tiny_decima_rows(monkeypatch, job_bucket=1, lanes=1)
    two = types.SimpleNamespace(job_mask=np.asarray(
        [[True, False, False, False, False, False],
         [True, True, False, False, False, False]]))
    one = types.SimpleNamespace(job_mask=two.job_mask[:1])
    assert bool(sched.full_width(two)) and not bool(sched.full_width(one))
    assert sched.full_width(two).shape == ()
    sched.job_bucket = 6  # covers the job cap: one width, no predicate
    assert sched.full_width(two) is None
    sched.job_bucket = 0
    assert sched.full_width(two) is None


_SCAN_BODIES: dict = {}


def _collector_scan_body(monkeypatch, mode: str):
    """The jaxpr of a five-row collection by the sync or the streaming
    single-eval collector (health sentinels and telemetry on, as the
    trainer runs it) and its scan body; traced once a mode."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env.flat_loop import init_loop_state
    from sparksched_tpu.obs.telemetry import telemetry_zeros_like
    from sparksched_tpu.trainers.rollout import (
        collect_flat_async_batch,
        collect_flat_sync_batch,
    )

    if mode in _SCAN_BODIES:
        return _SCAN_BODIES[mode]
    params, bank, sched, states = _tiny_decima_rows(monkeypatch, job_bucket=3)
    steps = 5
    bpol = sched.flat_batch_policy()

    def collect(key, states, tm):
        if mode == "stream":
            return collect_flat_async_batch(
                params, bank, bpol, key, steps,
                jax.vmap(init_loop_state)(states), jnp.float32(1.0e6),
                telemetry=tm, fulfill_bulk=True, health=True)
        return collect_flat_sync_batch(
            params, bank, bpol, key, steps, states, tm,
            fulfill_bulk=True, health=True)

    jaxpr = jax.make_jaxpr(collect)(
        jax.random.PRNGKey(1), states, telemetry_zeros_like((3,)))
    body = _collection_scan_body(jaxpr, steps)
    _SCAN_BODIES[mode] = jaxpr, body
    return jaxpr, body


def _collection_scan_body(jaxpr, steps: int):
    """The body of a collector's scan over its `steps` decision rows:
    the one scan of that length under no scope."""
    def scans(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for inner in _inner_jaxprs(eqn):
                yield from scans(inner)

    (body,) = [e.params["jaxpr"].jaxpr for e in scans(jaxpr.jaxpr)
               if e.params["length"] == steps
               and not str(e.source_info.name_stack)]
    return body


def _inner_jaxprs(eqn):
    """The jaxprs an equation holds: a loop's cond and body, a
    conditional's branches (a tuple), a call's callee."""
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def _under(jp, scope: str) -> int:
    """Equations of `jp`, at any depth, with `scope` in their name."""
    return sum(
        (scope in str(eqn.source_info.name_stack))
        + sum(_under(inner, scope) for inner in _inner_jaxprs(eqn))
        for eqn in jp.eqns
    )


def _drain_while(body):
    (drain,) = [e for e in body.eqns if e.primitive.name == "while"
                and "env/micro_step/drain" in str(e.source_info.name_stack)]
    return drain


RESET_SCOPE = "env/micro_step/reset"


@pytest.mark.parametrize("mode", ["sync", "stream"])
def test_every_equation_of_the_collector_scan_body_is_under_a_scope(
    monkeypatch, mode
):
    """The device trace names an operation by the scopes in its
    `op_name`. Code added to the scan body of the single-eval collector
    must not fall outside them silently: every equation of the body
    (health sentinels and telemetry on, as the trainer runs it) carries
    one of `ROW_SCOPES` in its name stack, the handling of the row's
    PRNG keys apart. The streaming collector's body is the same scan
    with ONE thing more, the re-seed of the lanes whose episode ended
    in the row (PR 31): the whole-name scope `env/micro_step/reset`
    appears once, after the drain's `while` and beside it, as one
    conditional on a predicate reduced over the lanes; it is not inside
    the `while`, not in the decide step, and absent from the sync
    body."""
    jaxpr, body = _collector_scan_body(monkeypatch, mode)
    scopes = ROW_SCOPES + ((RESET_SCOPE,) if mode == "stream" else ())
    stacks = [(e.primitive.name, str(e.source_info.name_stack))
              for e in body.eqns]
    assert len(stacks) > 1000  # the whole decision row is in this body
    seen = {s for s in scopes if any(s in st for _, st in stacks)}
    assert seen == set(scopes)
    bare = [p for p, st in stacks if not any(s in st for s in scopes)]
    key_handling = {"random_split", "random_wrap", "random_unwrap",
                    "random_bits", "slice", "squeeze"}
    assert set(bare) <= key_handling and len(bare) <= 24, bare
    # the GNN's sub-scopes are inside the net, below `decima/gnn`
    text = jaxpr.pretty_print(name_stack=True)
    assert (RESET_SCOPE in text) == (mode == "stream")
    for sub in ("levels", "stage_head", "exec_head"):
        assert f"decima/gnn/{sub}" in text

    drain = _drain_while(body)
    assert _under(drain.params["body_jaxpr"].jaxpr, RESET_SCOPE) == 0
    assert _under(drain.params["cond_jaxpr"].jaxpr, RESET_SCOPE) == 0
    at_top = [(i, e) for i, e in enumerate(body.eqns)
              if RESET_SCOPE in str(e.source_info.name_stack)]
    if mode == "sync":
        assert at_top == []
        return
    # in no other engine scope: the decide step holds none of it
    assert not any(s in str(e.source_info.name_stack)
                   for _, e in at_top for s in ROW_SCOPES)
    # one conditional (the lanes' flags reduced to one predicate, so a
    # `cond` and not a select over both branches), after the `while`,
    # and the add of the `reseeds` counter
    assert sorted(e.primitive.name for _, e in at_top) == [
        "add", "cond", "convert_element_type", "convert_element_type",
        "pmax"]
    (where, cond), = [(i, e) for i, e in at_top
                      if e.primitive.name == "cond"]
    assert where > body.eqns.index(drain)
    assert cond.invars[0].aval.shape == ()  # one predicate, not per lane
    assert _under(body, RESET_SCOPE) == len(at_top) + sum(
        _under(b, RESET_SCOPE) for b in _inner_jaxprs(cond))
    # the reset program is in one branch only; the other hands back
    # what it was given
    sizes = sorted(len(b.eqns) for b in _inner_jaxprs(cond))
    assert sizes[0] == 0 and sizes[1] > 50, sizes


def test_the_streaming_drain_while_is_the_sync_one(monkeypatch):
    """What PR 31 is for, held on a CPU: the drain `while` of the
    streaming collector carries, closes over and computes no more than
    the sync collector's. A reset in the body's tail (the shape until
    PR 31) makes the leaves only a reset writes, the adjacency, the
    templates, the task counts, part of the carry the loop selects and
    copies in every iteration: 31 constants and 7,828 equations where
    the sync loop has 16 and 7,505 at this size."""
    def size(jp) -> int:
        return len(jp.eqns) + sum(
            size(inner) for e in jp.eqns for inner in _inner_jaxprs(e))

    facts = {}
    for mode in ("sync", "stream"):
        drain = _drain_while(_collector_scan_body(monkeypatch, mode)[1])
        loop = drain.params["body_jaxpr"].jaxpr
        facts[mode] = {
            "carried": len(loop.outvars),
            "closed over": drain.params["body_nconsts"]
            + drain.params["cond_nconsts"],
            "equations": size(loop)
            + size(drain.params["cond_jaxpr"].jaxpr),
        }
    assert facts["sync"]["equations"] > 5000
    for what, n in facts["stream"].items():
        assert n <= facts["sync"][what], (what, facts)


# ---------------------------------------------------------------------------
# the sweep loop's decision row under a net (PR 49)
# ---------------------------------------------------------------------------

SWEEP_ROW_SCOPES = (
    "collect/observe", "sweep/policy", "env/micro_step/decide",
    "env/micro_step/drain", "env/micro_step/reset", "collect/health",
    "sweep/record",
)
POLICY_SCOPES = ("decima/features", "decima/gnn", "decima/sample")


def test_every_equation_of_the_sweeps_decima_row_is_under_a_scope(
        monkeypatch):
    """The sweep loop's row with the Decima net in it, 256 lanes in two
    blocks: the rows' scan holds ONE loop over the blocks, inside it
    every scope of a block's row (observe, the policy with the net's
    three inside it, decide, the drain with the re-seed under it) and
    after it, over all lanes, health and record; no equation of the
    block's row but the handling of keys and the blocks' slices and
    write-backs is under no scope."""
    import jax

    from sparksched_tpu import sweep

    params, bank, sched, _ = _tiny_decima_rows(monkeypatch, job_bucket=0)
    lanes, rows = 256, 3
    carry = jax.eval_shape(
        lambda: sweep.init(params, bank, jax.random.PRNGKey(0), lanes))
    jaxpr = jax.make_jaxpr(lambda b, c, k, w: sweep._chunk(
        params, b, sched.batch_policy, c, k, rows, w))(
        bank, carry, jax.random.PRNGKey(1), sched.params)
    body = _collection_scan_body(jaxpr, rows)
    (blocks,) = [e for e in body.eqns if e.primitive.name == "scan"]
    assert blocks.params["length"] == lanes // 128
    assert not str(blocks.source_info.name_stack)
    row = blocks.params["jaxpr"].jaxpr

    def stacks(jp):
        for e in jp.eqns:
            yield e.primitive.name, str(e.source_info.name_stack)
            for inner in _inner_jaxprs(e):
                yield from stacks(inner)

    inside = list(stacks(row))
    assert len(inside) > 3000  # a block's whole row is in this body
    in_block = SWEEP_ROW_SCOPES[:5] + ("sweep/record",) + POLICY_SCOPES
    assert {s for s in SWEEP_ROW_SCOPES + POLICY_SCOPES
            if any(s in st for _, st in inside)} == set(in_block)
    for scope in POLICY_SCOPES:  # the net's scopes are the policy's
        assert all("sweep/policy" in st for _, st in inside if scope in st)
    # at the top of the block's row (an inner jaxpr's names are its
    # own): the blocks' slices and write-backs and the keys' handling
    bare = [e.primitive.name for e in row.eqns if not any(
        s in str(e.source_info.name_stack) for s in SWEEP_ROW_SCOPES)]
    moves = {"dynamic_slice", "dynamic_update_slice", "mul", "add", "lt",
             "select_n", "random_wrap", "random_unwrap", "slice", "squeeze",
             "convert_element_type", "broadcast_in_dim"}
    assert set(bare) <= moves, sorted(set(bare) - moves)
    assert len(bare) < len(row.eqns) // 4, (len(bare), len(row.eqns))
    # after the loop, over all the lanes: health and the record
    after = [str(e.source_info.name_stack) for e in body.eqns[
        body.eqns.index(blocks) + 1:]]
    assert any("collect/health" in st for st in after)
    assert any("sweep/record" in st for st in after)
    assert not any(s in st for st in after for s in (
        "sweep/policy", "env/micro_step", "collect/observe"))
