"""The network serving tier (sparksched_tpu/serve/server.py +
router.py, ISSUE 16): HTTP front round-trips (decision parity vs the
in-process store, wire-bracketed Dapper traces, 429 admission
control, the /metrics exposition), the open-loop client mode with its
rejection-reconciliation pin, and the router invariants against a
REAL spawned 2-replica fleet — session affinity, cross-process param
swap (version stamp pinned in every replica's results), quarantine
isolation, and replica-death-fails-sessions (never rerouted).

The fleet fixture spawns actual processes (the mp.Pipe replica shape),
so it is module-scoped and shared; the death test runs LAST in the
file (tier-1 runs ordered: -p no:randomly) because it kills one
replica of the shared fleet on purpose.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

from sparksched_tpu.config import EnvParams
from sparksched_tpu.obs.metrics import MetricsRegistry
from sparksched_tpu.obs.tracing import SPAN_ORDER
from sparksched_tpu.schedulers import DecimaScheduler
from sparksched_tpu.serve import (
    ContinuousBatcher,
    SessionError,
    SessionQuarantined,
    SessionStore,
    generate_arrivals,
    run_open_loop,
)
from sparksched_tpu.serve.router import ReplicaDied, ReplicaSpec, Router
from sparksched_tpu.serve.server import ServeClient, ServeServer
from sparksched_tpu.workload import make_workload_bank


def fleet_builder(seed: int = 0):
    """The replica-process builder (`ReplicaSpec.builder` target):
    module-level and importable so spawned workers rebuild the same
    tiny stack — seeded, so every replica gets bit-identical initial
    params (the fleet-wide set_params aval contract)."""
    params = EnvParams(
        num_executors=5, max_jobs=6, max_stages=20, max_levels=20,
        mean_time_limit=None,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )
    sched = DecimaScheduler(
        num_executors=params.num_executors, embed_dim=8,
        gnn_mlp_kwargs={"hid_dims": [16]},
        policy_mlp_kwargs={"hid_dims": [16]},
        job_bucket=4, seed=seed,
    )
    return params, bank, sched


@pytest.fixture(scope="module")
def setup():
    return fleet_builder()


@pytest.fixture(scope="module")
def http_stack(setup):
    """One in-process store behind a loopback HTTP front, plus a
    traced client — module-scoped (the compile is the expensive
    part)."""
    params, bank, sched = setup
    reg = MetricsRegistry()
    store = SessionStore(
        params, bank, sched, capacity=6, max_batch=3, metrics=reg,
        trace=True,
    )
    front = ContinuousBatcher(store, metrics=reg, trace=True)
    server = ServeServer(
        store, front, quota_sessions=0, quota_inflight=0,
        metrics=MetricsRegistry(),
    ).start()
    client = ServeClient(
        "127.0.0.1", server.port, metrics=MetricsRegistry(),
        trace=True,
    )
    yield store, front, server, client
    client.stop()
    server.stop()


@pytest.fixture(scope="module")
def fleet():
    """A real 2-replica serve fleet (spawned processes). Shared by
    every router test; the death test (last in the file) kills
    replica 1."""
    spec = ReplicaSpec(
        builder="tests.test_serve_net:fleet_builder",
        builder_kwargs={"seed": 0},
        serve_cfg={"capacity": 6, "max_batch": 3},
        trace=True,
    )
    router = Router(spec, replicas=2)
    yield router
    router.stop()


# --------------------------------------------------------------------------
# HTTP front
# --------------------------------------------------------------------------


def test_http_decisions_match_in_process(setup, http_stack):
    """Byte-parity through the wire: a sequential client driving the
    HTTP front gets the same decision sequence the in-process store
    serves for the same session seed — the network tier adds
    transport, never changes what is computed."""
    params, bank, sched = setup
    _store, _front, _server, client = http_stack
    baseline = SessionStore(
        params, bank, sched, capacity=6, max_batch=3,
    )
    sid_ref = baseline.create(seed=4242)
    ref = [baseline.decide(sid_ref) for _ in range(4)]
    baseline.close(sid_ref)

    sid = client.create(seed=4242)
    try:
        got = []
        for _ in range(4):
            tk = client.submit(sid)
            client.flush()
            assert tk.error is None, tk.error
            got.append(tk.result)
    finally:
        client.close(sid)
    for a, b in zip(ref, got):
        assert (a.stage_idx, a.job_idx, a.num_exec) == (
            b.stage_idx, b.job_idx, b.num_exec)
        assert a.reward == b.reward
        assert a.wall_time == b.wall_time


def test_http_wire_trace_spans_and_runlog(http_stack, tmp_path):
    """The ISSUE-16 satellite: `wire_submit`/`wire_reply` bracket the
    server's submit->...->reply walk, every offset is monotone in
    SPAN_ORDER, and the runlog `trace` record keeps its shape (the
    wire spans are just two more keys in `spans`). Rides the shared
    traced server with its OWN runlogged client — the runlog and
    wire metrics are client-side state."""
    from sparksched_tpu.obs.runlog import RunLog

    _store, _front, server, _client = http_stack
    rl = RunLog(str(tmp_path / "wire.jsonl"))
    with ServeClient(
        "127.0.0.1", server.port, metrics=MetricsRegistry(),
        runlog=rl, trace=True,
    ) as client:
        sid = client.create(seed=7)
        tk = client.submit(sid)
        client.flush()
        assert tk.error is None, tk.error
        spans = tk.trace.spans
        assert {"wire_submit", "submit", "reply",
                "wire_reply"} <= set(spans)
        ordered = [k for k in SPAN_ORDER if k in spans]
        stamps = [spans[k] for k in ordered]
        assert stamps == sorted(stamps), "span order violated"
        # re-anchoring pins server submit AT wire_submit, so the
        # network + serialization residue is reply -> wire_reply
        assert spans["submit"] == spans["wire_submit"]
        assert spans["wire_reply"] >= spans["reply"]
        m = client.metrics
        assert m.hists["serve_span_wire_total_ms"].count == 1
        assert "serve_span_wire_ms" in m.hists
        client.close(sid)
    rl.close()
    recs = [json.loads(ln) for ln in open(rl.path)]
    traces = [r for r in recs if r["ev"] == "trace"]
    assert len(traces) == 1
    spans_ms = traces[0]["spans"]
    assert set(spans_ms) <= set(SPAN_ORDER)
    assert spans_ms["wire_submit"] == 0.0 == spans_ms["submit"]
    offs = [spans_ms[k] for k in SPAN_ORDER if k in spans_ms]
    assert offs == sorted(offs)


@pytest.mark.slow  # builds its own quota'd server stack (~10 s compile)
def test_http_admission_control_429(setup):
    """Per-tenant quotas become 429s: session quota rejects creates
    (RuntimeError at the client — the store-full contract), in-flight
    quota rejects decides, and the server's registry counts both in
    the PR-11 units (per-create `serve_capacity_rejections`,
    per-request `serve_requests_rejected`)."""
    params, bank, sched = setup
    store = SessionStore(params, bank, sched, capacity=4, max_batch=2)
    front = ContinuousBatcher(store)
    reg = MetricsRegistry()
    with ServeServer(
        store, front, quota_sessions=1, quota_inflight=2, metrics=reg,
    ) as server:
        with ServeClient("127.0.0.1", server.port) as client:
            sid = client.create(seed=1, tenant=5)
            with pytest.raises(RuntimeError, match="session quota"):
                client.create(seed=2, tenant=5)
            # a DIFFERENT tenant is not collateral damage
            other = client.create(seed=3, tenant=6)
            assert reg.counters["serve_capacity_rejections"] == 1
            # flood past the in-flight quota: the excess is rejected
            # per-request, the admitted ones are served
            tks = [client.submit(sid) for _ in range(6)]
            client.flush()
            rejected = [t for t in tks if t.error is not None]
            served = [t for t in tks if t.error is None]
            assert served and rejected
            assert all(isinstance(t.error, RuntimeError)
                       and "in-flight quota" in str(t.error)
                       for t in rejected)
            assert (reg.counters["serve_requests_rejected"]
                    == len(rejected))
            client.close(sid)
            client.close(other)
            # closed session: 404 -> SessionError
            tk = client.submit(sid)
            client.flush()
            assert isinstance(tk.error, SessionError)


def test_http_metrics_endpoint_and_healthz(http_stack):
    """/metrics serves the Prometheus text exposition of the
    backend's registry (merged with the server's own HTTP counters);
    /healthz reports liveness + scalar stats."""
    _store, front, _server, client = http_stack
    sid = client.create(seed=11)
    tk = client.submit(sid)
    client.flush()
    assert tk.error is None
    text = client.metrics_text()
    assert "# TYPE" in text and "_count" in text
    assert "serve_requests_total" in text
    assert "serve_http_requests" in text
    h = client.healthz()
    assert h["ok"] is True
    assert h["front"] == front.front_name
    assert h["stats"]["serve_decisions"] >= 1
    client.close(sid)


def test_open_loop_client_mode_reconciles(http_stack):
    """`run_open_loop(client, client, ...)`: the same open-loop driver
    measures the server end-to-end over loopback — summary stamps the
    wire front, and the ISSUE-16 reconcile block pins
    served + rejected == scheduled with the per-request counter in
    lockstep."""
    _store, _front, _server, client = http_stack
    arrivals = generate_arrivals(200.0, 40, 3, seed=5)
    out = run_open_loop(
        client, client, arrivals, slo_ms=1000.0, session_seed=900,
    )
    assert out["front"] == "http"
    assert out["completed"] + out["capacity_rejections"] == 40
    assert out["reconcile"]["requests"] == 40
    assert (out["reconcile"]["served"]
            == out["completed"])
    assert out["errors"] == 0
    assert out["hist"].count == out["completed"]


class _ContendedStore:
    """Store facade where a competing client steals every slot a
    rotation frees — the cross-client contention the single-threaded
    loadgen cannot produce on its own (its close+create pairs are
    slot-atomic, so a solo run's rotation create never fails). After
    `grace` creates, each further create first hands the freed slot to
    a hog session, so the REAL store's create raises (and counts the
    REAL `serve_capacity_rejections`)."""

    def __init__(self, store, grace: int) -> None:
        self.inner, self.grace, self.hogs = store, grace, []

    def create(self, seed=None):
        if self.grace <= 0:
            self.hogs.append(
                self.inner.create(seed=777 + len(self.hogs))
            )
        self.grace -= 1
        return self.inner.create(seed=seed)

    def __getattr__(self, name):
        return getattr(self.inner, name)


@pytest.mark.slow  # builds its own contended store (~10 s compile)
def test_open_loop_reconcile_counters_distinct(setup):
    """The loadgen double-count fix, test-pinned: when a tenant loses
    its slot (rotation create fails under contention), its turned-away
    traffic moves the per-request `serve_requests_rejected` in
    lockstep with the summary while the store's per-create
    `serve_capacity_rejections` counts rotation ATTEMPTS — two
    counters, two units, reconciled in the summary and never
    conflated. Rotation is forced via the health sentinel (poisoned
    clock -> quarantine reply), not episode end, so the test is
    timing-independent."""
    from sparksched_tpu.serve.router import _poison_session

    params, bank, sched = setup
    reg = MetricsRegistry()
    store = SessionStore(
        params, bank, sched, capacity=2, max_batch=2, metrics=reg,
    )
    contended = _ContendedStore(store, grace=2)
    front = ContinuousBatcher(store, metrics=reg)
    poisoned = []

    def poison_once():
        # trip tenant 1's health sentinel early: its reply rotates the
        # session, the hog steals the freed slot, and every later
        # tenant-1 request is turned away per-request
        if not poisoned:
            _poison_session(store, 1)
            poisoned.append(True)

    # slow enough that most of the schedule still lies AHEAD of the
    # first quarantine reply: only post-rotation arrivals can reject
    arrivals = generate_arrivals(50.0, 30, 2, seed=3)
    out = run_open_loop(
        contended, front, arrivals, slo_ms=1000.0, session_seed=300,
        on_poll=poison_once,
    )
    rec = out["reconcile"]
    assert rec["requests"] == 30
    assert rec["served"] + rec["rejected_requests"] == 30
    assert rec["rejected_requests"] > 0
    assert rec["serve_requests_rejected"] == rec["rejected_requests"]
    # distinct units: ONE failed create per lost slot (the rotation
    # attempt), MANY turned-away requests behind it
    assert rec["serve_capacity_rejections"] >= 1
    assert rec["rejected_requests"] > rec["serve_capacity_rejections"]
    assert (reg.counters["serve_requests_rejected"]
            == rec["rejected_requests"])
    assert (reg.counters["serve_capacity_rejections"]
            == rec["serve_capacity_rejections"])


# --------------------------------------------------------------------------
# router invariants (one real spawned fleet, death test LAST)
#
# Marked slow: the shared fixture spawns two real serve processes and
# each one AOT-boots a full store — run with `-m slow` (or no marker
# filter) to exercise them; tier-1 keeps the in-process HTTP tests.
# --------------------------------------------------------------------------


def _stub_replica_main(conn, idx, spec):
    """A replica that owns no store: it answers the pipe protocol from
    host state alone, so the only jax a Router built over it could
    touch is the router's own."""
    conn.send(("ready", idx, {"capacity": 4, "pid": os.getpid(),
                              "front": "stub"}))
    created = 0
    while True:
        msg = conn.recv()
        op, rid = msg[0], msg[1]
        if op == "create":
            conn.send(("reply", rid, {"sid": created}))
            created += 1
        elif op == "submit":
            conn.send(("result", rid, {
                "session_id": msg[2], "decided": True, "replica": idx,
            }, None))
        elif op == "set_params":
            conn.send(("reply", rid, {"version": msg[3]}))
        elif op == "stop":
            conn.send(("reply", rid, {"stopped": idx}))
            conn.close()
            return
        else:
            conn.send(("reply", rid, {}))


def _router_parent_off_jax():
    """Body of the child interpreter in the test below."""
    import numpy as np
    from jax._src import xla_bridge

    import sparksched_tpu.serve.router as router_mod

    router_mod._replica_main = _stub_replica_main
    router = Router(ReplicaSpec(builder="unused:unused"), replicas=2)
    try:
        sids = [router.create(seed=i) for i in range(4)]
        assert sorted(router.replica_of(s) for s in sids) == [0, 0, 1, 1]
        tickets = [router.submit(s) for s in sids]
        router.flush(timeout_s=30.0)
        assert all(t.result is not None for t in tickets)
        w = {"w": np.ones(3, np.float32)}
        assert router.set_params(w, version=7) == 7
        for s in sids:
            router.close(s)
    finally:
        router.stop()
    assert not xla_bridge.backends_are_initialized()
    print("ROUTER_PARENT_OFF_JAX")


def test_router_parent_makes_no_jax_device_call():
    """A chip belongs to one process, so a Router whose replicas hold
    the chips must never claim one itself: a fresh interpreter that
    builds a Router over stub replicas and drives create / submit /
    set_params / close / stop ends with no jax backend initialised."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c",
         "import tests.test_serve_net as t; t._router_parent_off_jax()"],
        cwd=repo, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ROUTER_PARENT_OFF_JAX" in r.stdout


@pytest.mark.slow
def test_router_session_affinity(fleet):
    """A sid always lands on the same replica: placement is encoded
    in the global sid (gsid % n), and every served decision reports
    the replica that owned it."""
    sids = [fleet.create(seed=100 + i) for i in range(4)]
    assert sorted({fleet.replica_of(s) for s in sids}) == [0, 1]
    try:
        for _round in range(3):
            tks = [fleet.submit(s) for s in sids]
            fleet.flush()
            for s, tk in zip(sids, tks):
                assert tk.error is None, tk.error
                assert tk.result.replica == fleet.replica_of(s)
    finally:
        for s in sids:
            fleet.close(s)


@pytest.mark.slow
def test_router_param_swap_reaches_all_replicas(fleet):
    """One `set_params` on the router lands on EVERY replica (the
    ParamBus facade), and the version stamp rides each subsequent
    ServeResult from each replica — the cross-process staleness
    contract."""
    _params, _bank, sched = fleet_builder(seed=0)
    bumped = jax.tree_util.tree_map(lambda a: a * 1.01, sched.params)
    sids = [fleet.create(seed=200 + i) for i in range(2)]
    assert {fleet.replica_of(s) for s in sids} == {0, 1}
    try:
        v = fleet.set_params(bumped, version=41)
        assert v == 41 == fleet.params_version
        tks = [fleet.submit(s) for s in sids]
        fleet.flush()
        assert all(tk.error is None for tk in tks)
        assert {tk.result.params_version for tk in tks} == {41}
        assert {tk.result.replica for tk in tks} == {0, 1}
        # rollback is fleet-wide too
        v2 = fleet.rollback_params(reason="test")
        tks = [fleet.submit(s) for s in sids]
        fleet.flush()
        assert {tk.result.params_version for tk in tks} == {v2}
    finally:
        for s in sids:
            fleet.close(s)


@pytest.mark.slow
def test_router_quarantine_isolated_to_one_replica(fleet):
    """Quarantine/close on one replica never leaks to another: a
    poisoned session trips ITS replica's health sentinel and later
    submits fail with SessionQuarantined, while the other replica's
    sessions keep serving."""
    a = fleet.create(seed=300)
    b = fleet.create(seed=301)
    assert fleet.replica_of(a) != fleet.replica_of(b)
    q0 = fleet.stats["serve_quarantines"]
    fleet.poison(a)
    tk = fleet.submit(a)
    fleet.flush()
    assert tk.error is None and tk.result.health_mask != 0
    assert fleet.stats["serve_quarantines"] == q0 + 1
    tk2 = fleet.submit(a)
    fleet.flush()
    assert isinstance(tk2.error, SessionQuarantined)
    # the OTHER replica's session is untouched
    tk3 = fleet.submit(b)
    fleet.flush()
    assert tk3.error is None and tk3.result.health_mask == 0
    fleet.close(a)  # close reclaims a quarantined session
    fleet.close(b)
    # and close on one replica doesn't invalidate the other's sids
    c = fleet.create(seed=302)
    tk4 = fleet.submit(c)
    fleet.flush()
    assert tk4.error is None
    fleet.close(c)


@pytest.mark.slow
def test_router_replica_death_fails_sessions_not_rerouted(fleet):
    """Replica death marks its sessions FAILED (`ReplicaDied`, a
    SessionError) — never silently rerouted: the device state died
    with the process, so a reroute would be a different episode
    masquerading as the same session. Survivors keep serving, and
    fleet capacity shrinks accordingly. Runs LAST: it kills replica 1
    of the shared fleet."""
    sids = [fleet.create(seed=400 + i) for i in range(4)]
    on_dead = [s for s in sids if fleet.replica_of(s) == 1]
    on_live = [s for s in sids if fleet.replica_of(s) == 0]
    assert on_dead and on_live
    victim = fleet._replicas[1]
    victim.proc.kill()
    victim.proc.join(timeout=10.0)
    deaths0 = fleet.stats["router_replica_deaths"]
    assert deaths0 == 0
    # in-flight + later submits on the dead replica's sessions fail
    tks = [fleet.submit(s) for s in on_dead]
    deadline = 50
    while fleet.stats["router_replica_deaths"] == 0 and deadline:
        fleet.poll()
        deadline -= 1
        import time as _t

        _t.sleep(0.1)
    assert fleet.stats["router_replica_deaths"] == 1
    fleet.poll()
    tks += [fleet.submit(s) for s in on_dead]
    for tk in tks:
        assert tk.ready
        assert isinstance(tk.error, ReplicaDied), tk.error
        assert isinstance(tk.error, SessionError)  # one error family
    assert fleet.stats["router_sessions_failed"] >= len(on_dead)
    # NOT rerouted: the failed sids never resolve to replica 0
    # results; the survivor's own sessions still serve
    tks_ok = [fleet.submit(s) for s in on_live]
    fleet.flush()
    for tk in tks_ok:
        assert tk.error is None, tk.error
        assert tk.result.replica == 0
    # closing a failed session is a no-op reclaim, not an error
    for s in on_dead:
        fleet.close(s)
    for s in on_live:
        fleet.close(s)
    # placement now avoids the dead replica
    fresh = [fleet.create(seed=500 + i) for i in range(2)]
    assert {fleet.replica_of(s) for s in fresh} == {0}
    for s in fresh:
        fleet.close(s)
