"""Fleet observability plane (ISSUE 17): StreamingHistogram merge
algebra + windowed delta/count_above, the labeled Prometheus
exposition, the FleetCollector scoreboard against fake and store-like
backends, multi-window burn-rate SLO alerting (cooldown, rollback
drive, fail-loud config), the online-loop depth probe, the
perf-regression ledger (full-coverage CLI gate over the repo's real
artifacts with the round-pinned headline rows, seeded-regression
rc 4), and — slow-marked — the real
spawned 2-replica fleet: per-replica scoreboard labels, seeded
quarantine regression tripping a burn-rate `alert` record that drives
a fleet-wide params rollback, and the server's `/fleet` + labeled
`/metrics` endpoints over that same fleet.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from sparksched_tpu.obs.fleet import (
    FleetCollector,
    labeled_prometheus,
    render_status,
)
from sparksched_tpu.obs.metrics import MetricsRegistry, StreamingHistogram
from sparksched_tpu.obs.runlog import RunLog
from sparksched_tpu.obs.slo import (
    OnlineLoopProbe,
    SLOMonitor,
    SLOSpec,
    slo_from_config,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _records(path) -> list[dict]:
    out = []
    with open(path) as fp:
        for line in fp:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def _hist(xs, **kw) -> StreamingHistogram:
    h = StreamingHistogram(**kw)
    h.add_many(float(x) for x in xs)
    return h


# --------------------------------------------------------------------------
# histogram merge algebra (the property the whole fleet plane leans on:
# per-replica hists merge into fleet hists, scrape deltas subtract)
# --------------------------------------------------------------------------


def test_hist_merge_commutative_and_associative():
    rng = np.random.default_rng(7)
    shards = [rng.lognormal(m, 1.0, 400) for m in (0.0, 1.5, 3.0)]
    a, b, c = (_hist(s) for s in shards)

    ab_c = _hist(shards[0]).merge(_hist(shards[1])).merge(_hist(shards[2]))
    a_bc = _hist(shards[0]).merge(_hist(shards[1]).merge(_hist(shards[2])))
    cba = _hist(shards[2]).merge(_hist(shards[1])).merge(_hist(shards[0]))

    for m in (a_bc, cba):
        assert m.counts == ab_c.counts
        assert m.count == ab_c.count
        assert m.min == ab_c.min and m.max == ab_c.max
        np.testing.assert_allclose(m.total, ab_c.total, rtol=1e-12)
    # merge is in-place accumulation: the three originals are intact
    assert a.count == 400 and b.count == 400 and c.count == 400


def test_hist_multiway_merge_keeps_rel_err_bound():
    """An 8-way merge (the fleet case: one shard per replica) answers
    quantiles within the SAME documented bound as a single histogram
    over the pooled samples — merging adds zero estimation error."""
    rng = np.random.default_rng(0)
    shards = [rng.lognormal(2.0, 1.0, 2_000) for _ in range(8)]
    fleet = _hist(shards[0])
    for s in shards[1:]:
        fleet.merge(_hist(s))
    pooled = np.concatenate(shards)
    assert fleet.count == pooled.size
    bound = fleet.summary()["scheme"]["max_rel_err"] + 0.01  # ~5.8%
    for q in (0.5, 0.9, 0.99):
        exact = float(np.percentile(pooled, q * 100))
        assert abs(fleet.quantile(q) - exact) / exact < bound, q
    # bucket-exact vs the pooled single histogram
    assert fleet.counts == _hist(pooled).counts


def test_hist_delta_recovers_window():
    cum = _hist([1.0, 2.0, 4.0])
    snap = cum.copy()
    cum.add_many([100.0, 120.0, 140.0])
    win = cum.delta(snap)
    assert win.count == 3
    assert abs(win.total - 360.0) < 1e-9
    # the window's quantiles see ONLY the new samples
    assert win.quantile(0.5) > 50.0
    # estimated extremes stay inside the window's bucket span
    assert 50.0 < win.min <= win.max <= cum.max
    # snapshot is independent: mutating cum never touches it
    assert snap.count == 3
    # empty window
    none = cum.delta(cum.copy())
    assert none.count == 0
    # geometry mismatch fails loudly
    with pytest.raises(ValueError, match="geometry"):
        cum.delta(StreamingHistogram(growth=1.5))
    # delta(None) is the cumulative view (first scrape)
    assert cum.delta(None).counts == cum.counts


def test_hist_count_above():
    h = _hist([1.0, 5.0, 50.0, 500.0, 5e7])  # 5e7 -> overflow bucket
    assert h.count_above(1e9) == 1  # overflow is always above
    assert h.count_above(200.0) in (2, 3)  # one-bucket tolerance
    assert h.count_above(h.lo / 2) == 5  # below lo counts underflow
    assert StreamingHistogram().count_above(1.0) == 0


# --------------------------------------------------------------------------
# labeled Prometheus exposition (the /metrics satellite)
# --------------------------------------------------------------------------


def test_labeled_prometheus_merged_first_then_per_replica():
    regs = []
    for n in (2, 3):
        r = MetricsRegistry()
        r.counter("serve_decisions_total", n)
        r.observe("serve_span_device_ms", float(n))
        regs.append(r)
    samples = [
        {"replica": "0", "alive": True, "registry": regs[0], "stats": {}},
        {"replica": "1", "alive": True, "registry": regs[1], "stats": {}},
        {"replica": "2", "alive": False, "registry": None, "stats": None},
    ]
    text = labeled_prometheus(samples)
    # merged totals first — byte-compatible with the pre-fleet merge
    merged = MetricsRegistry()
    merged.merge(regs[0])
    merged.merge(regs[1])
    assert text.startswith(merged.to_prometheus())
    assert 'serve_decisions_total{replica="0"} 2' in text
    assert 'serve_decisions_total{replica="1"} 3' in text
    assert 'replica="2"' not in text  # dead replica has no series
    # exactly one TYPE header per metric (labeled blocks are untyped)
    assert text.count("# TYPE serve_decisions_total counter") == 1
    # histogram series carry BOTH labels, le and replica
    assert 'serve_span_device_ms_bucket{replica="1",le="+Inf"} 1' in text


# --------------------------------------------------------------------------
# FleetCollector scoreboard (fake backends, manual clock)
# --------------------------------------------------------------------------


class _FakeFleet:
    """Router-shaped fake: replica_samples() from mutable counters."""

    def __init__(self):
        self.reg = {r: MetricsRegistry() for r in ("0", "1")}
        self.stats_by = {
            r: {
                "serve_decisions": 0, "serve_quarantines": 0,
                "serve_sessions_live": 2, "serve_sessions_hot": 1,
                "serve_page_ins": 0, "serve_page_outs": 0,
                "serve_param_version": 0,
            } for r in ("0", "1")
        }
        self.dead = set()

    def advance(self, rep, decisions=0, quarantines=0, pages=0,
                lat_ms=(), version=None):
        st = self.stats_by[rep]
        st["serve_decisions"] += decisions
        st["serve_quarantines"] += quarantines
        st["serve_page_ins"] += pages
        if version is not None:
            st["serve_param_version"] = version
        for v in lat_ms:
            self.reg[rep].observe("serve_span_device_ms", v)

    def replica_samples(self):
        out = []
        for r in ("0", "1"):
            if r in self.dead:
                out.append({"replica": r, "alive": False,
                            "sessions": 0, "registry": None,
                            "stats": None})
            else:
                out.append({"replica": r, "alive": True, "sessions": 2,
                            "registry": self.reg[r],
                            "stats": dict(self.stats_by[r])})
        return out


def test_fleet_collector_scoreboard_and_runlog(tmp_path):
    fake = _FakeFleet()
    t = [100.0]
    rl = RunLog(str(tmp_path / "fleet.jsonl"))
    col = FleetCollector(fake, period_s=1.0, runlog=rl,
                         clock=lambda: t[0])

    fake.advance("0", decisions=10, lat_ms=[5.0] * 10, version=3)
    fake.advance("1", decisions=10, lat_ms=[5.0] * 10, version=3)
    col.scrape()

    # rate limiting: within period_s, maybe_scrape is a no-op
    t[0] += 0.25
    assert col.maybe_scrape() is None

    # one window of differentiated load: replica 1 slow + quarantining
    # + one params version behind the fleet
    fake.advance("0", decisions=40, pages=4, lat_ms=[5.0] * 40,
                 version=4)
    fake.advance("1", decisions=10, quarantines=5,
                 lat_ms=[400.0] * 10)
    t[0] += 1.75  # 2.0 s since the first scrape
    status = col.maybe_scrape()
    assert status is not None

    r0, r1 = status["replicas"]
    assert (r0["replica"], r1["replica"]) == ("0", "1")
    assert r0["rps"] == pytest.approx(20.0) and r0["alive"]
    assert r1["rps"] == pytest.approx(5.0)
    assert r0["page_churn_per_s"] == pytest.approx(2.0)
    assert r1["quarantine_rate"] == pytest.approx(0.5)
    assert r0["quarantine_rate"] == 0.0
    # windowed p99: replica 1's window is all-400ms even though its
    # cumulative hist is mostly 5ms — the delta is what the row shows
    assert r1["p99_ms"] > 300.0 and r0["p99_ms"] < 10.0
    assert (r0["params_version"], r0["params_lag"]) == (4, 0)
    assert (r1["params_version"], r1["params_lag"]) == (3, 1)
    fl = status["fleet"]
    assert fl["replicas_alive"] == 2 and fl["replicas"] == 2
    assert fl["decisions"] == 50 and fl["quarantines"] == 5
    assert fl["goodput_rps"] == pytest.approx(25.0)
    assert fl["params_version_max"] == 4

    # a dead replica stays ON the scoreboard, alive=False
    fake.dead.add("1")
    t[0] += 1.0
    status = col.scrape()
    assert [r["alive"] for r in status["replicas"]] == [True, False]
    assert status["fleet"]["replicas_alive"] == 1

    rl.close()
    fleet_recs = [r for r in _records(tmp_path / "fleet.jsonl")
                  if r.get("ev") == "fleet"]
    assert len(fleet_recs) == 3
    assert fleet_recs[1]["fleet"]["decisions"] == 50
    assert {r["replica"] for r in fleet_recs[1]["replicas"]} \
        == {"0", "1"}
    # the renderer accepts what the runlog stored (the CLI's
    # post-mortem path)
    table = render_status(fleet_recs[1])
    assert "replica" in table and "fleet: alive 2/2" in table


def test_fleet_collector_store_backend_is_pseudo_replica():
    """Any .stats/.metrics carrier (a SessionStore, here a stub) gets
    the same plane as pseudo-replica "0"."""

    class _Store:
        def __init__(self):
            self.metrics = MetricsRegistry()
            self.stats = {"serve_decisions": 0, "serve_quarantines": 0}

    st = _Store()
    t = [0.0]
    col = FleetCollector(st, period_s=0.0, clock=lambda: t[0])
    col.scrape()
    st.stats["serve_decisions"] += 8
    t[0] += 2.0
    status = col.scrape()
    (row,) = status["replicas"]
    assert row["replica"] == "0" and row["rps"] == pytest.approx(4.0)
    assert col.fleet_status() is status  # cached last scrape


# --------------------------------------------------------------------------
# SLO burn-rate monitor
# --------------------------------------------------------------------------


def _win(decisions=100, quarantines=0, dt=5.0, rps=None, lat=None,
         lag=None):
    return {
        "dt_s": dt, "decisions": decisions, "quarantines": quarantines,
        "goodput_rps": decisions / dt if rps is None else rps,
        "latency_hist": lat, "params_lag_max": lag,
    }


def test_slo_quarantine_burn_fires_and_cooldown_holds(tmp_path):
    rl = RunLog(str(tmp_path / "slo.jsonl"))
    mon = SLOMonitor(
        [SLOSpec("quarantine_rate", "ratio", 0.05)],
        windows=((60.0, 15.0, 2.0),), cooldown_s=100.0, runlog=rl,
        clock=lambda: 0.0,
    )
    # healthy traffic: rate 1% of the 5% bound -> burn 0.2x, silent
    t = 0.0
    for _ in range(12):
        t += 5.0
        assert mon.ingest(_win(quarantines=1), now=t) == []
    # regression: 50% quarantine rate at full load — the long window
    # still holds the healthy history, so this only fires because the
    # bad scrape outweighs it (the dilution is the false-page guard)
    t += 5.0
    alerts = mon.ingest(_win(decisions=1000, quarantines=500), now=t)
    assert len(alerts) == 1
    a = alerts[0]
    assert a["slo"] == "quarantine_rate" and a["action"] == "none"
    assert a["burn_short"] >= a["factor"] == 2.0
    assert a["burn_long"] >= 2.0
    # cooldown: the breach persists but does not re-page every scrape
    t += 5.0
    assert mon.ingest(_win(decisions=1000, quarantines=500),
                      now=t) == []
    assert mon.stats["slo_alerts"] == 1
    # ...and pages again once the cooldown expires
    t += 101.0
    assert len(mon.ingest(_win(decisions=1000, quarantines=500),
                          now=t)) == 1
    rl.close()
    recs = [r for r in _records(tmp_path / "slo.jsonl")
            if r.get("ev") == "alert"]
    assert len(recs) == 2 and recs[0]["slo"] == "quarantine_rate"


def test_slo_short_window_gates_recovered_incident():
    """The multi-window point: a PAST burst still polluting the long
    window must not page once the short window is clean."""
    mon = SLOMonitor(
        [SLOSpec("quarantine_rate", "ratio", 0.05)],
        windows=((60.0, 15.0, 2.0),), cooldown_s=0.0,
        clock=lambda: 0.0,
    )
    assert len(mon.ingest(_win(quarantines=50), now=5.0)) == 1
    # recovered: clean scrapes push the short window under the factor
    # while the long window still remembers the burst
    fired = []
    for t in (21.0, 26.0, 31.0):
        fired += mon.ingest(_win(quarantines=0), now=t)
    assert fired == []
    burn_long, _ = mon._burn("quarantine_rate", 31.0, 60.0, 0.05)
    assert burn_long >= 2.0  # long window alone WOULD still page


def test_slo_latency_spec_counts_hist_tail():
    mon = SLOMonitor(
        [SLOSpec("p99_ms", "latency", 100.0, budget=0.01)],
        windows=((60.0, 15.0, 2.0),), clock=lambda: 0.0,
    )
    # 1% tail at the bound's budget -> burn ~1x, silent
    ok = _hist([5.0] * 99 + [500.0])
    assert mon.ingest(_win(lat=ok), now=5.0) == []
    # 30% tail -> burn 30x
    bad = _hist([5.0] * 70 + [500.0] * 30)
    alerts = mon.ingest(_win(lat=bad), now=10.0)
    assert len(alerts) == 1 and alerts[0]["kind"] == "latency"


def test_slo_floor_and_ceiling_and_idle_windows():
    mon = SLOMonitor(
        [SLOSpec("goodput_rps", "floor", 50.0),
         SLOSpec("params_staleness", "ceiling", 2.0)],
        windows=((60.0, 15.0, 1.0),), cooldown_s=30.0,
        clock=lambda: 0.0,
    )
    # idle service (zero decisions): no signal, never a floor breach
    for t in (5.0, 10.0):
        assert mon.ingest(_win(decisions=0, rps=0.0), now=t) == []
    # goodput collapse breaches the floor (binary violation, budget
    # 0.5 -> burn 2x >= 1x; the cooldown absorbs the second scrape)
    fired = []
    for t in (15.0, 20.0):
        fired += mon.ingest(_win(decisions=10, rps=2.0, dt=5.0), now=t)
    assert [a["slo"] for a in fired] == ["goodput_rps"]
    # staleness ceiling: lag 5 > 2
    fired = []
    for t in (25.0, 30.0):
        fired += mon.ingest(_win(lag=5), now=t)
    assert [a["slo"] for a in fired] == ["params_staleness"]


def test_slo_rollback_drive_and_config():
    class _Bus:
        def __init__(self):
            self.calls = []

        def rollback_params(self, reason=""):
            self.calls.append(reason)
            return 7

    bus = _Bus()
    mon = slo_from_config(
        {"quarantine_rate_max": 0.05, "p99_ms": 200.0,
         "windows": [[60, 15, 2.0]], "rollback_on": ["quarantine_rate"],
         "cooldown_s": 0.0},
        rollback=bus, clock=lambda: 0.0,
    )
    assert [s.name for s in mon.specs] == ["p99_ms", "quarantine_rate"]
    (alert,) = mon.ingest(_win(quarantines=50), now=5.0)
    assert alert["action"] == "rollback"
    assert alert["rolled_back_to_version"] == 7
    assert len(bus.calls) == 1 and "burn" in bus.calls[0]
    assert mon.stats["slo_rollbacks"] == 1

    # fail-loud surfaces
    with pytest.raises(ValueError, match="unknown slo"):
        slo_from_config({"quarantine_rate_mx": 0.05})
    with pytest.raises(ValueError, match="rollback_on"):
        SLOMonitor([SLOSpec("a", "ratio", 0.1)],
                   rollback_on=("nope",))
    with pytest.raises(ValueError, match="kind"):
        SLOSpec("x", "p99", 1.0)
    assert slo_from_config(None) is None
    assert slo_from_config({"cooldown_s": 5.0}) is None  # no specs


def test_server_config_slo_without_collect_fails_loud():
    from sparksched_tpu.serve.server import server_from_config

    with pytest.raises(ValueError, match="collect: true"):
        server_from_config({"slo": {"p99_ms": 100.0}}, None, None, None)


# --------------------------------------------------------------------------
# online-loop depth probe
# --------------------------------------------------------------------------


class _Res:
    def __init__(self, version, reward=None):
        self.params_version = version
        self.reward = reward


def test_online_loop_probe_staleness_swap_latency_rewards():
    class _Inner:
        def __init__(self):
            self.added, self.closed = [], []

        def add(self, res):
            self.added.append(res)

        def on_close(self, sid, quarantined=False):
            self.closed.append((sid, quarantined))

    class _Store:
        stats = {"serve_param_version": 0}

    inner, store = _Inner(), _Store()
    t = [1000.0]
    probe = OnlineLoopProbe(store=store, inner=inner,
                            metrics=MetricsRegistry(),
                            clock=lambda: t[0])

    probe.add(_Res(0, reward=1.0))  # lag 0
    # a swap lands (ParamBus pump event); decisions still on v0 are
    # STALE until the first v1 decision arrives 2.5 s later
    store.stats["serve_param_version"] = 1
    probe.on_bus_event({"event": "swap", "version": 1})
    probe.add(_Res(0, reward=3.0))  # lag 1, still pre-swap params
    t[0] += 2.5
    probe.add(_Res(1, reward=5.0))  # first decision under v1

    s = probe.summary()
    assert s["probe_decisions"] == 3 and s["probe_swaps"] == 1
    assert s["probe_first_decisions"] == 1
    assert s["staleness"]["count"] == 3
    assert s["swap_to_first_decision"]["count"] == 1
    assert s["swap_to_first_decision"]["max_s"] == pytest.approx(
        2.5, rel=0.07)
    assert s["reward_by_version"]["0"] == {"mean": 2.0, "count": 2}
    assert s["reward_by_version"]["1"] == {"mean": 5.0, "count": 1}
    # forwarding: the inner collector saw every decision + the close
    probe.on_close(4, quarantined=True)
    assert len(inner.added) == 3 and inner.closed == [(4, True)]
    # a rollback cancels the pending swap clock (no phantom latency)
    probe.on_bus_event({"event": "swap", "version": 2})
    probe.on_bus_event({"event": "rollback", "from_version": 2,
                        "to_version": 1})
    t[0] += 50.0
    probe.add(_Res(2))
    assert probe.summary()["swap_to_first_decision"]["count"] == 1
    assert probe.stats["probe_rollbacks"] == 1


# --------------------------------------------------------------------------
# perf-regression ledger (the tier-1 gate over the REAL artifacts)
# --------------------------------------------------------------------------


def test_ledger_cli_full_coverage_and_round_pins():
    """The gate the issue pins: `python -m sparksched_tpu.obs.ledger`
    over the repo's own artifacts/ + BENCH_*.json indexes EVERY file
    and holds the round-scoped headline rows (125 rps@SLO in r17, the
    47.27 rps loopback fleet row in r18, and ISSUE 18's ring-drained
    record path: blocked_host_wall per call with record ON, 0.1466 ms
    at r20 — within noise of the 0.1381 record-off floor). rc must be
    0 — coverage failures (2), pin drift (3), and un-waived
    regressions (4) all break tier-1 by design."""
    proc = subprocess.run(
        [sys.executable, "-m", "sparksched_tpu.obs.ledger",
         "--pin", "sustained_rps_slo_continuous@r17=125.0",
         "--pin", "serve_scale_net50rps_loopback@r18=47.27",
         "--pin", "blocked_host_wall_record_on@r20=0.1466"],
        cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "COVERAGE FAIL" not in proc.stdout
    assert "REGRESSION:" not in proc.stdout


def test_ledger_seeded_regression_and_waiver(tmp_path):
    """Verdict protocol on fabricated rounds: a drop outside the
    paired-rep noise bands is rc 4; a waived metric reports WAIVED and
    passes; an in-band wobble never fires."""
    from sparksched_tpu.obs.ledger import Ledger, main as ledger_main

    art = tmp_path / "artifacts"
    art.mkdir()

    def write(rnd, value, reps, wobble):
        (art / f"bench_tpu_r{rnd:02d}_x.json").write_text(json.dumps({
            "rows": [
                {"metric": "decima_steps_per_sec", "value": value,
                 "unit": "steps/s", "value_reps": reps},
                {"metric": "stable_metric", "value": wobble,
                 "unit": "steps/s",
                 "value_reps": [wobble * 0.97, wobble * 1.03]},
            ]
        }))

    write(1, 100.0, [98.0, 102.0], 50.0)
    write(2, 80.0, [79.0, 81.0], 50.4)  # -20%: far outside both bands
    rc = ledger_main(["--root", str(tmp_path)])
    assert rc == 4

    led = Ledger.scan(root=str(tmp_path))
    verdicts = {v["metric"]: v["verdict"] for v in led.verdicts()}
    assert verdicts["decima_steps_per_sec"] == "REGRESSION"
    assert verdicts["stable_metric"] == "STABLE"  # 0.8% in-band wobble
    assert "REGRESSION" in led.trend_report()

    # a waiver downgrades the verdict (the r18 protocol-change path)
    (art / "ledger_waivers.json").write_text(json.dumps(
        {"waivers": {"decima_steps_per_sec": "protocol change"}}))
    assert ledger_main(["--root", str(tmp_path)]) == 0
    led = Ledger.scan(root=str(tmp_path))
    verdicts = {v["metric"]: v["verdict"] for v in led.verdicts()}
    assert verdicts["decima_steps_per_sec"] == "WAIVED"

    # pins: round-scoped value drift is rc 3
    assert ledger_main(
        ["--root", str(tmp_path), "--pin",
         "decima_steps_per_sec@r01=100.0"]) == 0
    assert ledger_main(
        ["--root", str(tmp_path), "--pin",
         "decima_steps_per_sec@r01=120.0"]) == 3
    # unparseable file breaks coverage (rc 2) unless relaxed
    (art / "bench_tpu_r03_broken.json").write_text("{not json")
    assert ledger_main(["--root", str(tmp_path)]) == 2
    assert ledger_main(
        ["--root", str(tmp_path), "--no-strict-coverage"]) == 0


def test_ledger_units_and_round_parsing():
    from sparksched_tpu.obs.ledger import round_of, unit_direction

    assert unit_direction("steps/s") == 1
    assert unit_direction("rps") == 1
    assert unit_direction("ms") == -1
    assert unit_direction("ratio") == 0
    assert round_of("artifacts/bench_tpu_r05_headline.json") == 5
    assert round_of("BENCH_r19.json") == 19
    assert round_of("artifacts/no_round_stamp.json") == -1


# --------------------------------------------------------------------------
# the real thing: spawned 2-replica fleet + seeded regression + HTTP
# --------------------------------------------------------------------------


@pytest.mark.slow  # spawns two serve processes, AOT-boots both stores
def test_fleet_scoreboard_slo_rollback_and_http(tmp_path):
    """ISSUE 17 acceptance path end to end on a REAL fleet: the
    scoreboard carries per-replica labels; a seeded quarantine
    regression (poisoned sessions on both replicas) trips the
    burn-rate rule, lands an `alert` runlog record, and drives a
    fleet-wide params rollback through the Router facade; then the
    same router behind a ServeServer answers /fleet with the
    scoreboard and /metrics with replica-labeled series."""
    import urllib.request

    import jax

    from sparksched_tpu.serve.router import ReplicaSpec, Router
    from sparksched_tpu.serve.server import ServeServer
    from tests.test_serve_net import fleet_builder

    spec = ReplicaSpec(
        builder="tests.test_serve_net:fleet_builder",
        builder_kwargs={"seed": 0},
        serve_cfg={"capacity": 6, "max_batch": 3},
        trace=True,
    )
    router = Router(spec, replicas=2)
    server = None
    try:
        rl = RunLog(str(tmp_path / "fleet.jsonl"))
        mon = SLOMonitor(
            [SLOSpec("quarantine_rate", "ratio", 0.05)],
            windows=((60.0, 15.0, 1.0),), cooldown_s=0.0,
            rollback=router, rollback_on=("quarantine_rate",),
            runlog=rl,
        )
        col = FleetCollector(router, period_s=0.0, runlog=rl, slo=mon)

        # healthy traffic on both replicas, under a swapped-in params
        # version so the later rollback has somewhere to go
        _p, _b, sched = fleet_builder(seed=0)
        bumped = jax.tree_util.tree_map(
            lambda a: a * 1.01, sched.params)
        assert router.set_params(bumped, version=9) == 9
        sids = [router.create(seed=600 + i) for i in range(4)]
        assert {router.replica_of(s) for s in sids} == {0, 1}
        col.scrape()  # baseline snapshot
        for _ in range(2):
            tks = [router.submit(s) for s in sids]
            router.flush()
            assert all(tk.error is None for tk in tks)
        status = col.scrape()
        assert status["alerts"] == []
        rows = {r["replica"]: r for r in status["replicas"]}
        assert set(rows) == {"0", "1"}
        assert all(r["alive"] and r["decisions"] > 0
                   for r in rows.values())
        assert all(r["rps"] > 0 for r in rows.values())
        assert all(r["params_version"] == 9 and r["params_lag"] == 0
                   for r in rows.values())
        assert status["fleet"]["replicas_alive"] == 2

        # the /metrics satellite: per-replica labeled series
        text = labeled_prometheus(router.replica_samples())
        assert 'replica="0"' in text and 'replica="1"' in text

        # seeded regression: poison one session on EACH replica ->
        # the quarantine replies dominate the next scrape window
        for s in sids[:2]:
            router.poison(s)
        tks = [router.submit(s) for s in sids]
        router.flush()
        masked = [tk for tk in tks
                  if tk.result is not None and tk.result.health_mask]
        assert len(masked) == 2
        status = col.scrape()
        (alert,) = status["alerts"]
        assert alert["slo"] == "quarantine_rate"
        assert alert["burn_long"] >= 1.0
        assert alert["action"] == "rollback"
        # the rollback reverted the WHOLE fleet off the v9 params
        assert alert["rolled_back_to_version"] == 0
        assert router.params_version == 0
        rl.close()
        evs = [r["ev"] for r in _records(tmp_path / "fleet.jsonl")]
        assert "fleet" in evs and "alert" in evs

        # HTTP plane over the same fleet
        for s in sids:
            router.close(s)
        server = ServeServer(
            router, router, metrics=MetricsRegistry(), collector=col,
        ).start()
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(base + "/fleet", timeout=30) as r:
            fleet_doc = json.loads(r.read().decode())
        assert {row["replica"] for row in fleet_doc["replicas"]} \
            == {"0", "1"}
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            prom = r.read().decode()
        assert 'replica="0"' in prom and 'replica="1"' in prom
    finally:
        if server is not None:
            server.stop()
        router.stop()
