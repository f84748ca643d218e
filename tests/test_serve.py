"""AOT decision serving (sparksched_tpu/serve, ISSUE 10/13): AOT-vs-
jit step-exactness, donated-buffer aliasing, the warm-path
zero-recompile pin, session lifecycle + health quarantine, both
batching fronts (the fixed-linger `MicroBatcher` and the ISSUE-13
`ContinuousBatcher` — fairness, starvation bound, quarantine
eviction), the hot/cold pager (bit-exact page round-trip + full
decision parity vs an unpaged store), and the dp-sharded store
(decision parity vs the unsharded layout). Shapes are tiny (6-job
cap, capacity 6) — the serve programs are shape-polymorphic and the
production store differs only in buffer widths — and the expensive
compiles are amortized behind module-scoped fixtures."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparksched_tpu.config import EnvParams
from sparksched_tpu.env import core
from sparksched_tpu.env.flat_loop import init_loop_state, take_slot
from sparksched_tpu.env.health import H_NONFINITE_TIME
from sparksched_tpu.schedulers import DecimaScheduler
from sparksched_tpu.serve import (
    ContinuousBatcher,
    MicroBatcher,
    SessionError,
    SessionQuarantined,
    SessionStore,
    aot_compile,
    serve_decide_fn,
)
from sparksched_tpu.serve.aot import abstract_like
from sparksched_tpu.workload import make_workload_bank

from .reference_fixtures import parent_sets_by_hand

_i32 = jnp.int32


@pytest.fixture(scope="module")
def setup():
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
        job_bucket=4,
    )
    return params, bank, sched


@pytest.fixture(scope="module")
def store(setup):
    params, bank, sched = setup
    return SessionStore(
        params, bank, sched, capacity=6, max_batch=3, seed=0
    )


def _tiny_store_state(params, bank, capacity=2):
    ls = init_loop_state(core.reset(params, bank, jax.random.PRNGKey(7)))
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (capacity,) + a.shape).copy(), ls
    )


def test_create_writes_the_parent_sets_of_its_adjacency(setup):
    """A session's slot holds the packed parent sets of ITS adjacency
    (`EnvState.parent_sets`, what the fused bulk pass of the served
    drain reads since PR 39) and the duration facts of ITS templates
    (`EnvState.duration_facts`, what the duration sampler reads since
    PR 50): after `create` over whatever the slot held, and still
    after decisions served from it."""
    from sparksched_tpu.workload.sampling import pack_duration_facts

    params, bank, sched = setup
    store = SessionStore(params, bank, sched, capacity=3, max_batch=2,
                         seed=0)
    facts = np.asarray(pack_duration_facts(bank))

    def check(sids):
        env = store._stores[0].env
        adj = np.asarray(env.adj)
        np.testing.assert_array_equal(
            np.asarray(env.parent_sets), parent_sets_by_hand(adj)
        )
        np.testing.assert_array_equal(
            np.asarray(env.duration_facts),
            facts[np.asarray(env.job_template)],
        )
        return [adj[s].copy() for s in sids]

    first = check([store.create(seed=10 + i) for i in range(3)])
    for sid in range(3):
        for _ in range(4):
            store.decide(sid)
    check(range(3))
    store.close(1)
    assert store.create(seed=99) == 1  # the freed slot, another episode
    again = check(range(3))
    assert (again[1] != first[1]).any() and (again[0] == first[0]).all()


# ---------------------------------------------------------------------------
# AOT path correctness: exactness, donation, zero recompiles
# ---------------------------------------------------------------------------


def test_aot_step_exact_vs_jit_and_donation_aliasing(setup):
    """The AOT-compiled serve program is bit-identical to the plain
    jit path at fixed seeds (same store, same key => same decision and
    same post-state), AND the donated store is consumed: its input
    leaves are deleted and the output reuses the input buffer (the
    zero-allocation steady state the donation exists for)."""
    params, bank, sched = setup
    # rng-sensitive policy, explicit-params signature (ISSUE 14: the
    # model params are a runtime argument of the compiled program)
    pol, _ = sched.serve_param_policies(deterministic=False)
    fn = serve_decide_fn(params, bank, pol)
    st = _tiny_store_state(params, bank)
    key = jax.random.PRNGKey(3)
    args = (
        sched.params, _i32(1), key, _i32(-1), _i32(0),
        jnp.bool_(False),
    )

    st_jit = jax.tree_util.tree_map(jnp.copy, st)
    out_jit = jax.jit(fn)(st_jit, *args)  # no donation: the reference

    compiled, _secs = aot_compile(
        fn, abstract_like(st), *[abstract_like(a) for a in args],
        donate_store=True,
    )
    leaves_in = jax.tree_util.tree_leaves(st)
    big = max(
        range(len(leaves_in)), key=lambda i: leaves_in[i].nbytes
    )
    ptr_in = leaves_in[big].unsafe_buffer_pointer()
    st_aot, out_aot = compiled(st, *args)

    # step-exactness: decision fields and the full post-call store
    ref_st, ref_out = out_jit
    for a, b in zip(
        jax.tree_util.tree_leaves(ref_out),
        jax.tree_util.tree_leaves(out_aot),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree_util.tree_leaves(ref_st),
        jax.tree_util.tree_leaves(st_aot),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # donation: every donated input leaf is dead, and the largest
    # output leaf lives in the input's buffer (true in-place update)
    assert all(l.is_deleted() for l in leaves_in)
    leaves_out = jax.tree_util.tree_leaves(st_aot)
    assert leaves_out[big].unsafe_buffer_pointer() == ptr_in


def test_warm_path_records_zero_recompiles(store, tmp_path,
                                           monkeypatch):
    """After the constructor's warmup, serving decisions triggers no
    JIT activity at all: with the runlog recompile hooks installed (at
    threshold 0, so even trivial compiles would land), a window of
    warm single + batched decisions writes no jit_compile records."""
    import json

    from sparksched_tpu.obs import runlog as runlog_mod

    monkeypatch.setattr(runlog_mod, "JIT_MIN_SECS", 0.0)
    sids = [store.create(seed=10 + i) for i in range(3)]
    # absorb first-occurrence host glue (fold_in etc.) outside the
    # pinned window
    store.decide(sids[0])
    store.decide_batch(sids)

    rl = runlog_mod.RunLog(str(tmp_path / "serve.jsonl"))
    rl.install_jit_hooks()
    for _ in range(5):
        store.decide(sids[0])
        store.decide_batch(sids)
    rl.close()
    recs = [json.loads(ln) for ln in open(rl.path)]
    compiles = [r for r in recs if r["ev"].startswith("jit_compile")]
    assert compiles == [], compiles
    for s in sids:
        store.close(s)


# ---------------------------------------------------------------------------
# session API
# ---------------------------------------------------------------------------


def test_session_lifecycle_and_batch_consistency(store):
    """create/decide/step/close semantics, and the micro-batched path
    agrees with the unbatched path: two sessions created from the SAME
    seed serve the SAME greedy decision whether they ride the batch=K
    program or the single-session program."""
    a = store.create(seed=42)
    b = store.create(seed=42)
    c = store.create(seed=43)

    ra = store.decide(a)
    assert ra.decided and not ra.batched
    [rb, rc] = store.decide_batch([b, c])
    assert rb.batched and rb.decided
    # equal states, greedy policy => equal decisions across paths
    assert (rb.stage_idx, rb.num_exec) == (ra.stage_idx, ra.num_exec)

    # step: a caller-forced action through the same compiled program
    rs = store.step(c, rc.stage_idx, 1)
    assert rs.decided
    assert rs.lgprob == 0.0  # forced actions carry no policy log-prob

    store.close(a)
    with pytest.raises(SessionError):
        store.decide(a)
    with pytest.raises(ValueError):
        store.decide_batch([b, b])  # duplicate ids in one batch
    # single-session batches fall back to the unbatched program
    calls_before = store.stats["serve_batch_calls"]
    [r1] = store.decide_batch([b])
    assert not r1.batched
    assert store.stats["serve_batch_calls"] == calls_before
    store.close(b)
    store.close(c)


def test_poisoned_session_is_quarantined_not_served(store):
    """The per-decision health sentinel (ISSUE 9 mask) quarantines: a
    poisoned session's decide reports the tripped mask, and every
    later decide/step refuses with SessionQuarantined; close() still
    reclaims the slot."""
    sid = store.create(seed=77)
    ok = store.create(seed=78)
    # poison the persistent per-job completion clock with NaN — the
    # H_NONFINITE_TIME class a corrupted device buffer would show
    env = store._store.env
    store._store = store._store.replace(
        env=env.replace(
            job_t_completed=env.job_t_completed.at[sid].set(jnp.nan)
        )
    )
    r = store.decide(sid)
    assert r.health_mask & H_NONFINITE_TIME
    q_before = store.stats["serve_quarantines"]
    assert q_before >= 1
    with pytest.raises(SessionQuarantined):
        store.decide(sid)
    with pytest.raises(SessionQuarantined):
        store.step(sid, 0, 1)
    with pytest.raises(SessionQuarantined):
        store.decide_batch([ok, sid])
    # the healthy session keeps serving; quarantine didn't spread
    assert store.decide(ok).health_mask == 0
    assert store.stats["serve_quarantines"] == q_before
    store.close(sid)
    store.close(ok)


def test_store_capacity_exhaustion(store):
    sids = []
    while True:
        try:
            sids.append(store.create())
        except RuntimeError:
            break
    assert len(sids) == store.capacity
    for s in sids:
        store.close(s)


# ---------------------------------------------------------------------------
# micro-batching front
# ---------------------------------------------------------------------------


def test_batcher_flushes_on_full_batch_and_linger(store):
    sids = [store.create(seed=90 + i) for i in range(3)]
    mb = MicroBatcher(store, linger_ms=1e6)  # linger effectively off
    t1, t2 = mb.submit(sids[0]), mb.submit(sids[1])
    assert not t1.ready and not t2.ready  # below max_batch: queued
    t3 = mb.submit(sids[2])  # max_batch reached: immediate flush
    assert t1.ready and t2.ready and t3.ready
    assert t1.result.batched

    # bounded linger: a lone request flushes once the window expires
    mb = MicroBatcher(store, linger_ms=0.0)
    tk = mb.submit(sids[0])
    assert not tk.ready  # one pending < max_batch: no flush yet
    assert mb.poll()  # linger (0 ms) already expired
    assert tk.ready and not tk.result.batched  # lone => unbatched path
    for s in sids:
        store.close(s)


def test_batcher_duplicate_ids_take_successive_batch_calls(store):
    """ISSUE 11 satellite (the untested flush path): duplicate session
    ids within one linger window must NOT share a batch call — the
    first flush pass serves the de-duplicated set in ONE batch, each
    remaining duplicate drains through a successive pass (a lone
    leftover takes the unbatched fallback), and every ticket resolves
    with its decisions in submission order."""
    a = store.create(seed=300)
    b = store.create(seed=301)
    c = store.create(seed=302)
    mb = MicroBatcher(store, linger_ms=1e6)
    batch_before = store.stats["serve_batch_calls"]
    dec_before = store.stats["serve_decisions"]
    # [a, b, a]: the third submit reaches max_batch (3) and flushes —
    # the de-dup pass serves [a, b] in one batch, then the leftover [a]
    t1, t2 = mb.submit(a), mb.submit(b)
    assert not (t1.ready or t2.ready)
    t3 = mb.submit(a)
    assert t1.ready and t2.ready and t3.ready
    assert all(t.error is None for t in (t1, t2, t3))
    assert not mb._pending, "flush left a ticket pending"
    # one true batch call ([a, b]); the leftover [a] rode the
    # unbatched fallback; three decisions total
    assert store.stats["serve_batch_calls"] == batch_before + 1
    assert store.stats["serve_decisions"] == dec_before + 3
    assert t1.result.batched and t2.result.batched
    assert not t3.result.batched
    # two decisions for one session are sequential by definition
    assert t3.result.wall_time >= t1.result.wall_time
    for s in (a, b, c):
        store.close(s)


def test_batcher_exception_reserve_fallback_serves_survivors(store):
    """ISSUE 11 satellite (the untested exception re-serve path): when
    the BATCH call raises — a quarantined co-rider, a closed session —
    flush re-serves the batch one by one so only the offending
    ticket(s) carry errors; healthy tickets get real decisions and no
    ticket is ever left unresolved."""
    a = store.create(seed=310)
    bad = store.create(seed=311)
    gone = store.create(seed=312)
    # quarantine `bad` via the ISSUE-9 sentinel (NaN in its slot's
    # persistent clock), exactly as a poisoned device buffer would
    env = store._store.env
    store._store = store._store.replace(
        env=env.replace(
            job_t_completed=env.job_t_completed.at[bad].set(jnp.nan)
        )
    )
    r = store.decide(bad)
    assert r.health_mask != 0
    store.close(gone)  # `gone` is now unknown to the store

    mb = MicroBatcher(store, linger_ms=1e6)
    ta, tb, tg = mb.submit(a), mb.submit(bad), mb.submit(gone)
    # 3 pending == max_batch: auto-flush; decide_batch([a,bad,gone])
    # raises, the fallback serves each alone
    assert ta.ready and tb.ready and tg.ready
    assert not mb._pending
    assert ta.error is None and ta.result.decided
    assert not ta.result.batched  # served by the fallback decide
    assert isinstance(tb.error, SessionQuarantined)
    assert isinstance(tg.error, SessionError)
    assert tb.result is None and tg.result is None
    store.close(bad)
    store.close(a)


def test_batcher_duplicates_and_failures_resolve_every_ticket(store):
    """A duplicate session id in one linger window rides a SUCCESSIVE
    batch call (two decisions for one session are sequential by
    definition), and an unservable request fails only ITS ticket —
    co-batched healthy requests are still served, never orphaned."""
    a = store.create(seed=200)
    b = store.create(seed=201)
    mb = MicroBatcher(store, linger_ms=1e6)
    t1, t2, t3 = mb.submit(a), mb.submit(a), mb.submit(b)
    mb.flush()
    assert t1.ready and t2.ready and t3.ready
    assert all(t.error is None for t in (t1, t2, t3))
    assert t2.result.wall_time >= t1.result.wall_time  # sequential

    store.close(b)  # b is now unservable; a must still be served
    mb = MicroBatcher(store, linger_ms=1e6)
    ta, tb = mb.submit(a), mb.submit(b)
    mb.flush()
    assert ta.ready and ta.error is None and ta.result.decided
    assert tb.ready and isinstance(tb.error, SessionError)
    store.close(a)


# ---------------------------------------------------------------------------
# ISSUE 11: serving observability — admission/occupancy metrics,
# per-request span traces, and the open-loop load generator
# ---------------------------------------------------------------------------


def test_batcher_metrics_reasons_occupancy_and_queue(store):
    from sparksched_tpu.obs.metrics import MetricsRegistry

    sids = [store.create(seed=400 + i) for i in range(3)]
    reg = MetricsRegistry()
    store.metrics = reg
    try:
        mb = MicroBatcher(store, linger_ms=1e6, metrics=reg)
        for s in sids:  # third submit reaches max_batch: size flush
            mb.submit(s)
        assert reg.counters["serve_flush_size"] == 1
        assert reg.hists["serve_batch_occupancy"].max == 3.0
        assert reg.hists["serve_queue_depth"].max == 3.0
        assert reg.counters["serve_requests_total"] == 3

        mb = MicroBatcher(store, linger_ms=0.0, metrics=reg)
        mb.submit(sids[0])
        assert mb.poll()  # expired window: linger flush
        assert reg.counters["serve_flush_linger"] == 1
        assert reg.hists["serve_linger_wait_ms"].count == 4

        mb = MicroBatcher(store, linger_ms=1e6, metrics=reg)
        mb.submit(sids[0])
        mb.flush()  # explicit: forced
        assert reg.counters["serve_flush_forced"] == 1
        # one flush event != one batch call: the reason counts once,
        # occupancy/queue-depth count per batch pass
        assert reg.hists["serve_batch_occupancy"].count == 3
    finally:
        store.metrics = None
        for s in sids:
            store.close(s)


def test_request_trace_spans_ordered_and_runlogged(store, tmp_path):
    """The Dapper walk (ISSUE 11 tentpole): a trace id minted at
    Ticket creation, span stamps monotone in submit -> batch_admit ->
    dispatch -> device_compute -> scatter_back -> reply order, one
    runlog `trace` record per request with offsets from submit."""
    import json

    from sparksched_tpu.obs.runlog import RunLog
    from sparksched_tpu.obs.tracing import SPAN_ORDER

    sids = [store.create(seed=420 + i) for i in range(3)]
    rl = RunLog(str(tmp_path / "traces.jsonl"))
    store.trace = True
    # the in-process walk: everything but the ISSUE-16 wire bracket
    # (`wire_submit`/`wire_reply` are stamped only by the network
    # client — tests/test_serve_net.py pins that side)
    local = [k for k in SPAN_ORDER if not k.startswith("wire_")]
    try:
        mb = MicroBatcher(store, linger_ms=1e6, runlog=rl, trace=True)
        tks = [mb.submit(s) for s in sids]  # full batch: auto-flush
        ids = set()
        for tk in tks:
            assert tk.ready and tk.error is None
            spans = tk.trace.spans
            assert set(local) <= set(spans)
            stamps = [spans[k] for k in local]
            assert stamps == sorted(stamps), "span order violated"
            ids.add(tk.trace.trace_id)
        assert len(ids) == 3, "trace ids must be unique per request"
        rl.close()
        recs = [json.loads(ln) for ln in open(rl.path)]
        traces = [r for r in recs if r["ev"] == "trace"]
        assert {r["trace_id"] for r in traces} == ids
        for r in traces:
            assert r["spans"]["submit"] == 0.0
            assert r["total_ms"] == r["spans"]["reply"] >= 0.0
            offs = [r["spans"][k] for k in local]
            assert offs == sorted(offs)
    finally:
        store.trace = False
        store.last_spans = None
        for s in sids:
            store.close(s)


def test_instrumentation_off_leaves_request_path_bare(store):
    """Zero-cost when off: an uninstrumented batcher mints no trace,
    touches no registry, and the store stamps no spans — byte-for-byte
    the round-13 request path."""
    sid = store.create(seed=440)
    mb = MicroBatcher(store, linger_ms=1e6)
    tk = mb.submit(sid)
    mb.flush()
    assert tk.ready and tk.trace is None
    assert store.last_spans is None
    assert mb.metrics is None and mb.runlog is None
    # turning trace off mid-life clears the stamps: stale spans from a
    # traced window must never merge into a later request's trace
    store.trace = True
    store.decide(sid)
    assert store.last_spans is not None
    store.trace = False
    store.decide(sid)
    assert store.last_spans is None
    store.close(sid)


def test_loadgen_deterministic_schedules_and_rates():
    import numpy as np

    from sparksched_tpu.serve import generate_arrivals

    a1 = generate_arrivals(100.0, 2000, 8, seed=3)
    a2 = generate_arrivals(100.0, 2000, 8, seed=3)
    assert a1 == a2, "seeded schedules must be byte-identical"
    assert a1 != generate_arrivals(100.0, 2000, 8, seed=4)
    times = np.array([t for t, _ in a1])
    tenants = [w for _, w in a1]
    assert (np.diff(times) >= 0).all()
    assert set(tenants) <= set(range(8))
    # long-run offered rate ~= requested (Poisson, n=2000: loose band)
    assert abs(2000 / times[-1] - 100.0) < 15.0
    # MMPP: same long-run mean rate, strictly burstier inter-arrivals
    am = generate_arrivals(
        100.0, 30_000, 8, process="mmpp", seed=3, burst_factor=8.0,
        burst_fraction=0.1, burst_dwell_s=0.5,
    )
    tm = np.array([t for t, _ in am])
    assert abs(30_000 / tm[-1] - 100.0) < 10.0
    dp = np.diff(times)
    dm = np.diff(tm)
    cv2_poisson = dp.var() / dp.mean() ** 2  # ~1 by definition
    cv2_mmpp = dm.var() / dm.mean() ** 2
    assert cv2_mmpp > 1.5 > cv2_poisson * 1.2
    with pytest.raises(ValueError, match="unknown arrival process"):
        generate_arrivals(10.0, 5, 2, process="weibull")


def test_run_open_loop_resolves_every_request(store):
    """Open-loop smoke on the tiny store: every scheduled request is
    submitted, served and accounted; the summary's counters, histogram
    and goodput fields are consistent."""
    from sparksched_tpu.obs.metrics import MetricsRegistry
    from sparksched_tpu.serve import generate_arrivals, run_open_loop

    arrivals = generate_arrivals(150.0, 24, 3, seed=7)
    reg = MetricsRegistry()
    store.metrics = reg
    try:
        mb = MicroBatcher(store, linger_ms=1.0, metrics=reg)
        out = run_open_loop(
            store, mb, arrivals, slo_ms=10_000.0, session_seed=30_000
        )
    finally:
        store.metrics = None
    assert out["requests"] == out["completed"] == 24
    assert out["errors"] == 0
    assert out["good"] == 24  # generous SLO: everything is goodput
    assert out["hist"].count == 24
    assert len(out["samples_ms"]) == 24
    assert out["goodput_rps"] == out["achieved_rps"]
    assert out["capacity_rejections"] == 0
    assert reg.counters["serve_requests_total"] == 24
    # the run closed its tenant sessions behind itself
    assert store.stats["serve_sessions_live"] == 0


# ---------------------------------------------------------------------------
# ISSUE 13: the continuous batcher — occupancy dispatch, admission-
# order fairness, the starvation bound, decision parity vs the
# single-session path, quarantined-lane eviction mid-stream
# ---------------------------------------------------------------------------


def test_continuous_batcher_occupancy_and_decide_parity(store):
    """The continuous front has NO linger timer: a full width-K slot
    dispatches at submit, a partial slot dispatches on the next poll
    (occupancy-driven — padding lanes are free), and its batched
    decisions agree with the single-session `decide` path for
    same-seed sessions (greedy serving)."""
    x = store.create(seed=42)
    y = store.create(seed=42)
    z = store.create(seed=43)
    r_direct = store.decide(x)

    cb = ContinuousBatcher(store)
    ty, tz = cb.submit(y), cb.submit(z)
    assert not ty.ready and not tz.ready  # 2 sessions < K=3: queued
    assert cb.poll()  # occupancy dispatch: no timer to wait out
    assert ty.ready and tz.ready
    assert ty.result.batched and tz.result.batched
    # same state, greedy policy => same decision across paths
    assert (ty.result.stage_idx, ty.result.num_exec) == (
        r_direct.stage_idx, r_direct.num_exec
    )
    assert not cb.poll()  # empty queue: nothing to pump

    # a full width-K slot never waits for a poll
    tx, ty2, tz2 = cb.submit(x), cb.submit(y), cb.submit(z)
    assert tx.ready and ty2.ready and tz2.ready
    for s in (x, y, z):
        store.close(s)


def test_continuous_batcher_fairness_and_starvation_bound(store):
    """Per-tenant FIFO + round-robin admission (ISSUE 13): one
    tenant's flood cannot starve another — a newly backlogged tenant
    is admitted on the FIRST pump after its submit (the structural
    ceil(S/K) bound at S <= K+1), and the flooding tenant's own
    requests resolve in FIFO order (wall clock nondecreasing)."""
    a = store.create(seed=500)
    b = store.create(seed=501)
    c = store.create(seed=502)
    d = store.create(seed=503)
    cb = ContinuousBatcher(store)
    ta = [cb.submit(a) for _ in range(4)]  # a floods: 4 queued
    assert not any(t.ready for t in ta)  # one session: width-1 slot
    tb = cb.submit(b)
    tc = cb.submit(c)  # 3 distinct sessions ready == K: size dispatch
    assert ta[0].ready and tb.ready and tc.ready
    assert not ta[1].ready  # a's flood rides successive batches
    td = cb.submit(d)
    assert cb.pump()
    # the starvation bound: d admitted on the first pump after its
    # submit, co-riding with a's backlog instead of waiting it out
    assert td.ready and td.error is None
    assert ta[1].ready  # round-robin admitted a's next request too
    cb.flush()
    assert all(t.ready and t.error is None for t in ta)
    # per-tenant FIFO: two decisions for one session are sequential
    walls = [t.result.wall_time for t in ta]
    assert walls == sorted(walls)
    for s in (a, b, c, d):
        store.close(s)


def test_continuous_batcher_quarantine_eviction_midstream(store):
    """A session whose decision trips the health sentinel mid-stream
    is EVICTED from the continuous front: its queued followers fail
    their own tickets with `SessionQuarantined` immediately (no later
    batch lane burned on a session that will never be served again),
    while co-queued tenants are unaffected; a later submit of the
    quarantined session fails at dispatch."""
    bad = store.create(seed=510)
    good = store.create(seed=511)
    # poison the persistent per-job completion clock with NaN — the
    # H_NONFINITE_TIME class a corrupted device buffer would show
    env = store._store.env
    store._store = store._store.replace(
        env=env.replace(
            job_t_completed=env.job_t_completed.at[bad].set(jnp.nan)
        )
    )
    cb = ContinuousBatcher(store)
    t1, t2 = cb.submit(bad), cb.submit(bad)
    tg = cb.submit(good)
    assert cb.pump()  # serves [bad, good]; bad's mask trips
    assert t1.ready and t1.error is None
    assert t1.result.health_mask != 0
    # mid-stream eviction: the follower fails NOW, in the same pump
    assert t2.ready and isinstance(t2.error, SessionQuarantined)
    assert tg.ready and tg.error is None and tg.result.decided
    assert cb.pending == 0
    # a post-quarantine submit fails at dispatch, ticket-local
    t3 = cb.submit(bad)
    cb.flush()
    assert isinstance(t3.error, SessionQuarantined)
    store.close(bad)

    # a CLOSED session's backlog is evicted the same way (one dispatch
    # failure fails the whole queue with SessionError, instead of N
    # later pumps each degrading co-riders to the one-by-one fallback)
    gone_tickets = [cb.submit(good) for _ in range(3)]
    store.close(good)
    assert cb.pump()
    assert all(
        isinstance(t.error, SessionError) for t in gone_tickets
    )
    assert cb.pending == 0


# ---------------------------------------------------------------------------
# ISSUE 13: the hot/cold pager and the dp-sharded store
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def plain6(setup):
    """An unpaged, unsharded capacity-6 store — the parity twin the
    pager and sharding tests compare against (each test aligns
    `_calls` so both stores draw the same fold_in key sequence)."""
    params, bank, sched = setup
    return SessionStore(
        params, bank, sched, capacity=6, max_batch=3, seed=0
    )


def test_paged_store_roundtrip_bitexact_and_parity(setup, plain6):
    """The hot/cold pager (ISSUE 13): 6 sessions over 3 device slots.
    (a) page-out -> page-in is BIT-exact on the full LoopState (the
    host copy is the same `take_slot` view the serve programs gather);
    (b) a fully paged serving sequence is decision-for-decision
    IDENTICAL to an unpaged store at the same seeds (rewards, dt and
    wall clock included) — paging is pure placement, never semantics;
    (c) `create` stays O(1) via the maintained free-lists and close
    recycles ids without a scan."""
    params, bank, sched = setup
    paged = SessionStore(
        params, bank, sched, capacity=6, hot_capacity=3, max_batch=3,
        seed=0,
    )
    # align the fold_in counters so both stores draw identical keys
    plain6._calls = paged._calls
    sp = [paged.create(seed=600 + i) for i in range(6)]
    su = [plain6.create(seed=600 + i) for i in range(6)]
    assert paged.stats["serve_page_outs"] >= 3  # creation overflowed

    # (a) bit-exact round trip for a currently-cold session
    cold = next(s for s in sp if int(paged._slot_of[s]) < 0)
    before = jax.tree_util.tree_leaves(paged._cold[cold])
    [slot] = paged._ensure_hot([cold])
    after = jax.tree_util.tree_leaves(
        jax.device_get(take_slot(paged._store, slot))
    )
    for x, y in zip(before, after):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    # (b) decision parity under heavy page traffic: round-robin twice
    # over all 6 sessions (every decide pages someone in), plus one
    # batched call — every field equal, floats bit-for-bit
    for rnd in range(2):
        for i in range(6):
            rp = paged.decide(sp[i])
            ru = plain6.decide(su[i])
            dp_, du = rp.to_dict(), ru.to_dict()
            dp_.pop("session_id"), du.pop("session_id")
            assert dp_ == du, (i, rnd, dp_, du)
    for rp, ru in zip(
        paged.decide_batch(sp[:3]), plain6.decide_batch(su[:3])
    ):
        dp_, du = rp.to_dict(), ru.to_dict()
        dp_.pop("session_id"), du.pop("session_id")
        assert dp_ == du
    assert paged.stats["serve_page_ins"] > 0
    assert paged.stats["serve_sessions_hot"] == 3

    # (c) O(1) create: the free-lists recycle a closed id without a
    # scan, and capacity exhaustion still rejects loudly
    paged.close(sp[2])
    assert paged.create(seed=700) == sp[2]  # LIFO free-list reuse
    with pytest.raises(RuntimeError, match="store full"):
        paged.create()
    for s in sp:
        paged.close(s)
    for s in su:
        plain6.close(s)


def test_sharded_store_decision_parity(setup, plain6):
    """The dp-sharded store (ISSUE 13): the [C] session stack sharded
    P('dp') over a 2-device mesh serves the SAME decisions as the
    unsharded r11 layout at the same seeds — sessions are
    embarrassingly parallel, so sharding is placement, not semantics.
    Decision fields are pinned exactly; float accumulations to within
    reduction-order tolerance. The store's leaves must actually live
    on 2 devices (a silent single-device fallback would make this
    test vacuous), and donation must still hold."""
    from sparksched_tpu.parallel import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (virtual) devices")
    params, bank, sched = setup
    mesh = make_mesh(2)
    sharded = SessionStore(
        params, bank, sched, capacity=6, max_batch=3, seed=0,
        mesh=mesh,
    )
    assert len(
        sharded._store.env.wall_time.sharding.device_set
    ) == 2
    plain6._calls = sharded._calls
    ss = [sharded.create(seed=800 + i) for i in range(3)]
    su = [plain6.create(seed=800 + i) for i in range(3)]
    for rnd in range(2):
        rs = sharded.decide_batch(ss)
        ru = plain6.decide_batch(su)
        for x, y in zip(rs, ru):
            dx, dy = x.to_dict(), y.to_dict()
            for k in ("stage_idx", "num_exec", "job_idx", "decided",
                      "done", "health_mask"):
                assert dx[k] == dy[k], (k, dx, dy)
            for k in ("reward", "dt", "wall_time", "lgprob"):
                np.testing.assert_allclose(
                    dx[k], dy[k], rtol=1e-5, atol=1e-6, err_msg=k
                )
    # the single-session path on the sharded layout too
    r1, r2 = sharded.decide(ss[0]), plain6.decide(su[0])
    assert (r1.stage_idx, r1.num_exec) == (r2.stage_idx, r2.num_exec)
    for s in ss:
        sharded.close(s)
    for s in su:
        plain6.close(s)


# ---------------------------------------------------------------------------
# ISSUE 15: pipelined serve execution — slot groups, dispatch/harvest,
# decision bit-parity vs the synchronous front, zero-recompile +
# param-swap under depth >= 2 / groups >= 2, the starvation bound
# under max_skips exhaustion, prefetch, and the harvester thread
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gstore(setup):
    """A 2-group store (capacity 6, 3 slots per group, unpaged) — the
    pipelined tests' shared subject. One AOT lowering at the [3] group
    shape serves both groups."""
    params, bank, sched = setup
    return SessionStore(
        params, bank, sched, capacity=6, groups=2, max_batch=3, seed=0
    )


def test_grouped_store_dispatch_harvest_parity(gstore, plain6):
    """The tentpole's parity pin (store level): the SAME sequence of
    batches dispatched through the pipelined window (two groups in
    flight at once, harvest deferred) is decision-for-decision
    BIT-IDENTICAL — rewards, dt, wall clock, log-probs included — to
    the synchronous `decide_batch` path at the same seeds and
    admission order. Pipelining moves WHEN the host materializes,
    never what the device computes. Cross-group batches are rejected
    loudly (a batch is ONE compiled call over ONE group buffer)."""
    pipe, sync = gstore, plain6
    sync._calls = pipe._calls
    ps = [pipe.create(seed=900 + i) for i in range(6)]
    ss = [sync.create(seed=900 + i) for i in range(6)]
    g0 = [s for s in ps if pipe.session_group(s) == 0]
    g1 = [s for s in ps if pipe.session_group(s) == 1]
    assert len(g0) == len(g1) == 3  # balanced static assignment
    s0 = [ss[ps.index(s)] for s in g0]
    s1 = [ss[ps.index(s)] for s in g1]
    with pytest.raises(ValueError, match="spans slot groups"):
        pipe.decide_batch([g0[0], g1[0]])
    for rnd in range(3):
        # pipelined arm: both groups dispatched before ANY harvest —
        # the in-flight window is genuinely 2 deep
        c0 = pipe.dispatch_batch(g0)
        c1 = pipe.dispatch_batch(g1)
        assert pipe.inflight == 2
        r0 = sync.decide_batch(s0)
        r1 = sync.decide_batch(s1)
        done = pipe.harvest(wait=True)
        assert [len(c.results) for c in done] == [3, 3]
        assert (c0.results, c1.results) == (
            done[0].results, done[1].results
        )
        for rs, rp in zip(r0 + r1, c0.results + c1.results):
            ds, dp = rs.to_dict(), rp.to_dict()
            ds.pop("session_id"), dp.pop("session_id")
            assert ds == dp, (rnd, ds, dp)
    assert pipe.inflight == 0
    assert pipe.stats["serve_inflight_peak"] >= 2
    # the wall split saw both components move (satellite: the
    # dispatch-vs-blocked split bench_serve_latency reports)
    assert pipe.wall_split["dispatch_s"] > 0.0
    assert pipe.wall_split["blocked_host_s"] > 0.0
    for s in ps:
        pipe.close(s)
    for s in ss:
        sync.close(s)


def test_pipelined_front_parity_vs_synchronous_front(setup):
    """The acceptance pin (front level): the pipelined
    `ContinuousBatcher` (depth 2 over a 2-group store) resolves every
    ticket with results BIT-EQUAL to the synchronous continuous front
    (depth 1) on an identically-configured store under the identical
    submission order — same admission sequence => same compiled calls
    => same fold_in keys => identical rewards."""
    params, bank, sched = setup
    arms = {}
    for depth in (1, 2):
        st = SessionStore(
            params, bank, sched, capacity=6, groups=2, max_batch=3,
            seed=0,
        )
        front = ContinuousBatcher(st, depth=depth)
        assert front.front_name == (
            "pipelined" if depth > 1 else "continuous"
        )
        sids = [st.create(seed=950 + i) for i in range(6)]
        tickets = [front.submit(s) for _ in range(3) for s in sids]
        while front.pending or st.inflight:
            front.flush()
        assert all(t.ready and t.error is None for t in tickets)
        arms[depth] = [t.result.to_dict() for t in tickets]
        for s in sids:
            st.close(s)
    assert arms[1] == arms[2]


def test_pipelined_warm_path_and_param_swap_zero_recompiles(
    gstore, tmp_path
):
    """Acceptance: the zero-recompile guarantees hold under
    pipelining (depth >= 2, groups >= 2). With the runlog jit hooks
    at threshold 0, a warm window of dispatch/harvest cycles across
    BOTH groups — including a hot param swap mid-window — writes no
    jit_compile records; the in-flight call dispatched BEFORE the
    swap keeps its dispatch-time version while the next call carries
    the new one (one params value per compiled call — no torn
    reads)."""
    import json

    from sparksched_tpu.obs import runlog as runlog_mod

    store = gstore
    sids = [store.create(seed=970 + i) for i in range(6)]
    g0 = [s for s in sids if store.session_group(s) == 0]
    g1 = [s for s in sids if store.session_group(s) == 1]
    # warm glue (fold_in, slot padding) AND the swap payload outside
    # the pinned window
    store.harvest(wait=True)
    store.dispatch_batch(g0)
    store.dispatch_batch(g1)
    store.harvest(wait=True)
    new_params = jax.device_get(jax.tree_util.tree_map(
        lambda x: x * 1.01, store.model_params
    ))

    monkey_prev = runlog_mod.JIT_MIN_SECS
    runlog_mod.JIT_MIN_SECS = 0.0
    rl = runlog_mod.RunLog(str(tmp_path / "pipe.jsonl"))
    rl.install_jit_hooks()
    try:
        v0 = store.params_version
        c_pre = store.dispatch_batch(g0)  # in flight across the swap
        v1 = store.set_params(new_params)
        c_post = store.dispatch_batch(g1)
        done = store.harvest(wait=True)
        assert len(done) == 2
        assert {r.params_version for r in c_pre.results} == {v0}
        assert {r.params_version for r in c_post.results} == {v1}
        for _ in range(3):
            store.dispatch_batch(g0)
            store.dispatch_batch(g1)
            store.harvest(wait=True)
    finally:
        runlog_mod.JIT_MIN_SECS = monkey_prev
        rl.close()
        store.rollback_params(reason="test")
        for s in sids:
            store.close(s)
    recs = [json.loads(ln) for ln in open(rl.path)]
    compiles = [r for r in recs if r["ev"].startswith("jit_compile")]
    assert compiles == [], compiles


def test_continuous_batcher_starvation_bound_under_skip_exhaustion(
    setup
):
    """The fairness test gap (ISSUE 15 satellite): adversarial
    hot/cold interleaving on a paged store where `max_skips` exhausts
    repeatedly — 6 backlogged sessions over 4 device slots, width-2
    batches, so the hot-preferring admission passes cold sessions
    over until the valve forces them. The structural bound must hold
    for EVERY request: a session's queue head is admitted within
    ceil(S/K) + max_skips pumps of becoming head, and
    `serve_page_churn` counts exactly the forced (cold) admissions —
    each one a page round-trip, since the hot set stays full."""
    import math

    from sparksched_tpu.obs.metrics import MetricsRegistry

    params, bank, sched = setup
    store = SessionStore(
        params, bank, sched, capacity=12, hot_capacity=4, max_batch=2,
        seed=0,
    )
    S, R = 6, 6  # backlogged sessions x requests each
    max_skips = 2
    bound = math.ceil(S / store.max_batch) + max_skips
    sids = [store.create(seed=1200 + i) for i in range(S)]
    reg = MetricsRegistry()
    front = ContinuousBatcher(
        store, pager_aware=True, max_skips=max_skips, metrics=reg
    )
    # seed the full backlog with auto-pump suppressed, so every pump
    # sees the whole rotation — the regime where the hot preference
    # has a choice and cold sessions CAN starve without the valve
    real_k = store.max_batch
    store.max_batch = 10 ** 6
    tickets = {s: [front.submit(s) for _ in range(R)] for s in sids}
    store.max_batch = real_k
    ins0 = store.stats["serve_page_ins"]

    resolved_at: dict[int, list[int]] = {s: [] for s in sids}
    pumps = 0
    while front.pending or store.inflight:
        assert front.pump(reason="occupancy"), "queue stuck"
        pumps += 1
        assert pumps < S * R + 10, "no forward progress"
        for s in sids:
            n_ready = sum(1 for t in tickets[s] if t.ready)
            while len(resolved_at[s]) < n_ready:
                resolved_at[s].append(pumps)
    for s in sids:
        assert all(
            t.ready and t.error is None for t in tickets[s]
        ), s
        # per-request head-wait: request k becomes its session's
        # queue head when request k-1 resolves (pump 0 for the first)
        prev = 0
        for p in resolved_at[s]:
            assert p - prev <= bound, (
                f"session {s}: head waited {p - prev} pumps "
                f"> ceil(S/K)+max_skips = {bound}"
            )
            prev = p
    # the churn counter counts the forced page-ins: the hot set stayed
    # full, so every cold admission paid a page round-trip
    churn = int(reg.counters.get("serve_page_churn", 0))
    assert churn > 0
    assert store.stats["serve_page_ins"] - ins0 == churn
    for s in sids:
        store.close(s)


def test_pipelined_prefetch_pages_ahead_into_free_slots(setup):
    """The look-ahead prefetch (ISSUE 15): on a paged grouped store
    under a pipelined front, predicted-next cold sessions are paged
    into FREE slots of their group while the current batch computes —
    counted by `serve_prefetches` — and every request still resolves
    with its session's own state (prefetch is placement, never
    semantics). A prediction never evicts: with no free slot the
    prefetch is refused."""
    params, bank, sched = setup
    store = SessionStore(
        params, bank, sched, capacity=8, hot_capacity=4, groups=2,
        max_batch=2, seed=0,
    )
    sids = [store.create(seed=1300 + i) for i in range(8)]
    # a full hot set refuses predictions (free slots only, no
    # eviction for a guess), and a hot session is a no-op
    cold_full = next(s for s in sids if not store.is_hot(s))
    assert not store.has_free_slot(store.session_group(cold_full))
    assert store.prefetch(cold_full) is False
    assert store.prefetch(next(
        s for s in sids if store.is_hot(s)
    )) is False
    # open one free slot per group (the rotation/close traffic real
    # serving produces), leaving cold sessions queued behind hot ones
    for g in (0, 1):
        victim = next(
            s for s in sids
            if store.is_hot(s) and store.session_group(s) == g
        )
        store.close(victim)
        sids.remove(victim)
    front = ContinuousBatcher(store, depth=2, prefetch=True)
    real_k = store.max_batch
    store.max_batch = 10 ** 6
    tickets = [front.submit(s) for _ in range(3) for s in sids]
    store.max_batch = real_k
    while front.pending or store.inflight:
        front.flush()
    assert all(t.ready and t.error is None for t in tickets)
    assert store.stats["serve_prefetches"] > 0
    for s in sids:
        store.close(s)


def test_background_harvester_materializes_inflight(gstore):
    """The `harvester` flag's thread: it materializes the oldest
    in-flight call's outputs off the serving thread (host_out set
    without the caller blocking), `harvest()` consumes the copy, and
    results are the same ServeResults the foreground path builds.
    `stop_harvester` is idempotent."""
    import threading
    import time as _time

    store = gstore
    assert store._harvester is None
    store._harvester_stop = False
    store._harvester = threading.Thread(
        target=store._harvester_loop, daemon=True,
        name="serve-harvester-test",
    )
    store._harvester.start()
    try:
        sids = [store.create(seed=1400 + i) for i in range(3)]
        gsids = [
            s for s in sids
            if store.session_group(s) == store.session_group(sids[0])
        ]
        call = store.dispatch_batch(gsids)
        deadline = _time.monotonic() + 10.0
        while call.host_out is None and _time.monotonic() < deadline:
            _time.sleep(0.005)
        assert call.host_out is not None, "harvester never picked up"
        [done] = store.harvest(wait=True)
        assert done is call and len(done.results) == len(gsids)
        assert all(r.decided for r in done.results)
    finally:
        store.stop_harvester()
        store.stop_harvester()  # idempotent
        for s in sids:
            store.close(s)
    assert store._harvester is None


# ---------------------------------------------------------------------------
# serve: config block + bench row schema helpers
# ---------------------------------------------------------------------------


def test_store_from_config_rejects_unknown_keys(setup, store):
    from sparksched_tpu.config import SERVE_KEYS
    from sparksched_tpu.serve import front_from_config, store_from_config

    params, bank, sched = setup
    with pytest.raises(ValueError, match="unknown serve"):
        store_from_config(
            {"capcity": 4}, params, bank, sched  # typo'd knob
        )
    # the ISSUE-11 instrumentation keys are part of the declared
    # surface (config.SERVE_KEYS is the single source of truth)
    assert {"trace", "metrics"} <= SERVE_KEYS
    # ISSUE 15: the pipelining knobs are declared, and the pipelined
    # front resolves to a depth>1 ContinuousBatcher (depth defaults
    # to the store's group count, floor 2)
    assert {"groups", "depth", "harvester", "prefetch"} <= SERVE_KEYS
    front = front_from_config({"front": "pipelined"}, store)
    assert isinstance(front, ContinuousBatcher)
    assert front.front_name == "pipelined" and front.depth >= 2
    with pytest.raises(ValueError, match="unknown serve front"):
        front_from_config({"front": "warp"}, store)
    # a depth-1 "pipelined" front IS the continuous front and would
    # mislabel every row — rejected loudly, not silently degraded
    with pytest.raises(ValueError, match="depth >= 2"):
        front_from_config({"front": "pipelined", "depth": 1}, store)


def test_latency_row_blocks():
    """A latency row's building block: the percentile block schema
    (PERF_ROUNDS.md round 13)."""
    from sparksched_tpu.obs.metrics import percentile_block

    block = percentile_block([1.0, 2.0, 3.0, 100.0], 4)
    assert set(block) == {
        "p50_ms", "p90_ms", "p99_ms", "mean_ms", "max_ms", "reps",
    }
    assert block["p50_ms"] <= block["p90_ms"] <= block["p99_ms"]
    assert block["max_ms"] == 100.0 and block["reps"] == 4
