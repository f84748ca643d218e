"""Online learning loop (sparksched_tpu/online, ISSUE 14): param-
version semantics (one version per compiled batch — no torn reads;
staleness stamps in runlog/trace records; zero-recompile swap),
trajectory assembly/eviction/staleness accounting, the learner's
health-gated updates + off-policy guard, the bus's probation rollback,
and the pager-aware admission preference (fewer page round-trips at
capacity >> hot_capacity). Shapes are tiny (6-job cap) and the
expensive compiles sit behind module-scoped fixtures, as in
tests/test_serve.py."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparksched_tpu.config import EnvParams
from sparksched_tpu.online import (
    TrajectoryBuffer,
    online_from_config,
)
from sparksched_tpu.schedulers import DecimaScheduler
from sparksched_tpu.serve import ContinuousBatcher, SessionStore
from sparksched_tpu.workload import make_workload_bank

AGENT_CFG = {
    "agent_cls": "DecimaScheduler",
    "embed_dim": 8,
    "gnn_mlp_kwargs": {"hid_dims": [16]},
    "policy_mlp_kwargs": {"hid_dims": [16]},
    "job_bucket": 4,
}


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
        num_executors=params.num_executors,
        **{k: v for k, v in AGENT_CFG.items() if k != "agent_cls"},
    )
    return params, bank, sched


@pytest.fixture(scope="module")
def rstore(setup):
    """The record-on store the online tests share."""
    params, bank, sched = setup
    return SessionStore(
        params, bank, sched, capacity=8, max_batch=3, seed=0,
        record=True,
    )


def _fresh_sessions(store, n, base=100):
    return [store.create(seed=base + i) for i in range(n)]


def _rotate_done(store, sids, base):
    for j, s in enumerate(list(sids)):
        try:
            store._check_sid(s)
        except Exception:
            store.close(s)
            sids[j] = store.create(seed=base + j)
    return sids


# ---------------------------------------------------------------------------
# param-version semantics (satellite: swap-mid-stream / torn reads)
# ---------------------------------------------------------------------------


def test_record_results_carry_obs_and_version(rstore):
    """Record-on decisions hand back the StoredObs record and the
    staleness stamp; batch results of one compiled call all carry the
    SAME version (the params are one argument of the call)."""
    sids = _fresh_sessions(rstore, 3, base=100)
    r = rstore.decide(sids[0])
    assert r.decided and r.obs is not None
    assert r.params_version == rstore.params_version
    # StoredObs shape sanity: the [J, S] = [6, 20] node grid of the
    # serve env, flat and padded to whole 128-wide rows
    assert np.asarray(r.obs.node_mask).shape == (128,)
    rs = rstore.decide_batch(sids)
    assert len({x.params_version for x in rs}) == 1
    for s in sids:
        rstore.close(s)


def test_swap_mid_stream_uses_dispatch_version(rstore, tmp_path):
    """A swap between batch dispatches: tickets queued BEFORE the swap
    but dispatched AFTER carry the NEW version (the version live at
    dispatch time), and every decision of one batch agrees — no torn
    reads. The swap itself triggers zero recompiles (runlog jit hooks
    at threshold 0), and `params_swap` + per-request staleness stamps
    land in the runlog."""
    from sparksched_tpu.obs import runlog as runlog_mod

    sids = _fresh_sessions(rstore, 3, base=200)
    v0 = rstore.params_version
    # warm glue — AND the swap payload — outside the pinned window
    # (the payload arithmetic compiles; the swap itself must not)
    rstore.decide_batch(sids)
    new_params = jax.device_get(jax.tree_util.tree_map(
        lambda x: x * 1.01, rstore.model_params
    ))

    rl = runlog_mod.RunLog(str(tmp_path / "online.jsonl"))
    prev = runlog_mod.JIT_MIN_SECS
    runlog_mod.JIT_MIN_SECS = 0.0
    rl.install_jit_hooks()
    rstore._runlog = rl
    try:
        front = ContinuousBatcher(rstore, runlog=rl, trace=True)
        rstore.trace = True
        tks_pre = [front.submit(s) for s in sids[:2]]
        # queued but not dispatched (2 < max_batch=3); swap now
        v1 = rstore.set_params(new_params)
        assert v1 == v0 + 1
        front.pump()
        for t in tks_pre:
            assert t.ready and t.error is None
        # dispatched after the swap -> the NEW version, uniformly
        assert {t.result.params_version for t in tks_pre} == {v1}
    finally:
        rstore.trace = False
        rstore._runlog = None
        runlog_mod.JIT_MIN_SECS = prev
        for s in sids:
            rstore.close(s)
    rl.close()
    recs = [json.loads(ln) for ln in open(rl.path)]
    compiles = [r for r in recs if r["ev"].startswith("jit_compile")]
    assert compiles == [], compiles
    swaps = [r for r in recs if r["ev"] == "params_swap"]
    assert swaps and swaps[0]["version"] == v1
    assert swaps[0]["prev_version"] == v0
    traces = [r for r in recs if r["ev"] == "trace"]
    assert traces and all(
        t["params_version"] == v1 for t in traces
    )


def test_rollback_restores_last_good(rstore):
    v0 = rstore.params_version
    good = jax.device_get(rstore.model_params)
    rstore.set_params(
        jax.tree_util.tree_map(lambda x: x * 2.0, rstore.model_params)
    )
    v_back = rstore.rollback_params(reason="test")
    assert v_back == v0
    restored = jax.device_get(rstore.model_params)
    for a, b in zip(
        jax.tree_util.tree_leaves(good),
        jax.tree_util.tree_leaves(restored),
    ):
        np.testing.assert_array_equal(a, b)


def test_swap_rejects_structure_change(rstore):
    with pytest.raises(ValueError, match="structure"):
        rstore.set_params({"params": {}})
    # same treedef, different leaf avals (the drifted-architecture
    # publish): must be rejected HERE, not crash the next compiled
    # call mid-traffic
    with pytest.raises(ValueError, match="leaf aval"):
        rstore.set_params(jax.tree_util.tree_map(
            lambda x: np.zeros((3, 3), np.float32),
            rstore.model_params,
        ))


def test_paired_ab_pct_cancels_monotone_drift():
    """The run-granularity A/B statistic: per-pair ratios cancel a
    monotone drift that median-of-arms aliases into overhead."""
    from sparksched_tpu.obs.metrics import paired_ab_pct

    # both arms drift 3 -> 5 over the reps; true overhead is +2%
    offs = [3.0, 3.5, 4.0, 4.5, 5.0]
    ons = [x * 1.02 for x in offs]
    assert paired_ab_pct(offs, ons) == pytest.approx(2.0)
    # median-of-arms on the same data would read the drift, not the
    # overhead, if the arms interleaved off-first each rep
    assert paired_ab_pct(offs, offs) == pytest.approx(0.0)


def test_online_from_config_enabled_false_wires_nothing(rstore):
    prev = rstore.collector
    try:
        rstore.collector = None
        out = online_from_config(
            {"enabled": False, "max_steps": 4}, rstore, AGENT_CFG
        )
        assert out is None
        assert rstore.collector is None  # nothing attached
    finally:
        rstore.collector = prev


# ---------------------------------------------------------------------------
# trajectory buffer (host-only: duck-typed results, no store)
# ---------------------------------------------------------------------------


class _FakeResult:
    def __init__(self, sid, k, *, done=False, decided=True,
                 health_mask=0, version=0):
        self.session_id = sid
        self.stage_idx = k
        self.job_idx = 0
        self.num_exec = 2
        self.lgprob = -0.5
        self.decided = decided
        self.done = done
        self.reward = -float(k)
        self.dt = 1.0
        self.wall_time = float(k + 1)
        self.health_mask = health_mask
        self.params_version = version
        self.obs = {"x": np.full((2, 3), k, np.float32)}


def test_buffer_assembly_segments_and_eviction():
    buf = TrajectoryBuffer(capacity=2, max_steps=3, min_decisions=2)
    # session 10: a 2-step episode ending naturally
    buf.add(_FakeResult(10, 0))
    buf.add(_FakeResult(10, 1, done=True, version=1))
    assert len(buf) == 1
    [tr] = buf.drain(1)
    assert tr.length == 2 and tr.done
    # per-decision staleness stamps + wall-time layout
    np.testing.assert_array_equal(tr.params_version, [0, 1])
    assert tr.wall_times.shape == (3,)
    assert tr.wall_times[0] == pytest.approx(0.0)  # t0 = wall - dt
    np.testing.assert_array_equal(tr.obs["x"][1], np.full((2, 3), 1))
    # max_steps segment cut at 3 decisions
    for k in range(3):
        buf.add(_FakeResult(11, k))
    assert len(buf) == 1 and buf.stats["online_trajectories"] == 2
    # too-short segments drop on close with a counter
    buf.add(_FakeResult(12, 0))
    buf.on_close(12)
    assert buf.stats["online_dropped_short"] == 1
    # a quarantining decision drops the whole open episode
    buf.add(_FakeResult(13, 0))
    buf.add(_FakeResult(13, 1, health_mask=4))
    assert buf.stats["online_dropped_quarantined"] == 1
    assert len(buf) == 1
    # FIFO overflow eviction: capacity 2, oldest evicted + counted
    for sid in (14, 15):
        buf.add(_FakeResult(sid, 0))
        buf.add(_FakeResult(sid, 1, done=True))
    assert len(buf) == 2
    assert buf.stats["online_dropped_overflow"] == 1


def test_buffer_staleness_guard_drops_old_versions():
    buf = TrajectoryBuffer(capacity=8, max_steps=4, min_decisions=1)
    buf.add(_FakeResult(1, 0, version=0))
    buf.add(_FakeResult(1, 1, done=True, version=0))
    buf.add(_FakeResult(2, 0, version=5))
    buf.add(_FakeResult(2, 1, done=True, version=5))
    got = buf.drain(2, current_version=6, max_lag=2)
    assert [tr.session_id for tr in got] == [2]
    assert buf.stats["online_dropped_stale"] == 1


def test_buffer_requires_record_on_results():
    buf = TrajectoryBuffer()
    r = _FakeResult(1, 0)
    r.obs = None
    with pytest.raises(ValueError, match="record-on"):
        buf.add(r)


# ---------------------------------------------------------------------------
# learner + bus over the real store
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def online_triple(rstore):
    buffer, learner, bus = online_from_config(
        {
            "max_steps": 8, "batch_trajectories": 2,
            "min_decisions": 2, "max_param_lag": 4,
            "probation_decisions": 4, "max_quarantine_rate": 0.5,
        },
        rstore, AGENT_CFG,
    )
    return buffer, learner, bus


def test_learner_updates_and_publishes(rstore, online_triple):
    """The closed loop at test scale: served decisions assemble into
    trajectories, the learner's `ppo_update` (health gates on) accepts
    with finite loss, and the accepted version reaches the store
    through the bus on the next pump — params actually change."""
    buffer, learner, bus = online_triple
    sids = _fresh_sessions(rstore, 2, base=300)
    try:
        guard = 0
        while len(buffer) < learner.B and guard < 400:
            guard += 1
            for j, s in enumerate(list(sids)):
                try:
                    r = rstore.decide(s)
                    rotate = r.done or r.health_mask
                except Exception:
                    rotate = True
                if rotate:
                    rstore.close(s)
                    sids[j] = rstore.create(
                        seed=320 + guard * 4 + j
                    )
        assert learner.ready(), buffer.stats
        before = jax.device_get(rstore.model_params)
        v_store0 = rstore.params_version
        assert learner.version == v_store0  # one version axis
        info = learner.step()
        assert info is not None and info["accepted"], info
        assert np.isfinite(info["policy_loss"])
        assert info["health_mask"] == 0
        assert learner.version == v_store0 + 1
        # the bus applies on the serving thread's next pump
        ev = bus.pump()
        assert ev == {"event": "swap", "version": v_store0 + 1}
        assert rstore.params_version == v_store0 + 1
        after = jax.device_get(rstore.model_params)
        diffs = [
            float(np.abs(a - b).max()) for a, b in zip(
                jax.tree_util.tree_leaves(before),
                jax.tree_util.tree_leaves(after),
            )
        ]
        assert max(diffs) > 0.0  # the swap moved real weights
    finally:
        for s in sids:
            try:
                rstore.close(s)
            except Exception:
                pass


def test_bus_probation_rollback_on_quarantine_spike(
    rstore, online_triple, setup
):
    """Quarantine-style swap rollback: after a swap, a probation
    window with a quarantine-rate spike reverts the store to the
    last proven version and writes the rollback `params_swap`
    record."""
    _, _, bus = online_triple
    params, bank, sched = setup
    # close out any probation still open from earlier tests so the
    # CURRENT version is the proven rollback target: serve a window
    # of healthy decisions, then pump
    s0 = rstore.create(seed=450)
    for _ in range(bus.probation_decisions):
        r = rstore.decide(s0)
        if r.done or r.health_mask:
            rstore.close(s0)
            s0 = rstore.create(seed=451)
    bus.pump()
    rstore.close(s0)
    v_good = rstore.params_version
    good = jax.device_get(rstore.model_params)
    bus.publish(
        jax.tree_util.tree_map(lambda x: x * 1.5, good),
        version=v_good + 1,
    )
    bus.pump()
    assert rstore.params_version == v_good + 1
    # trip the sentinel on several sessions (the test_serve poisoning
    # pattern: NaN the per-job completion clock) — probation window
    # is 4 decisions at max rate 0.5
    sids = _fresh_sessions(rstore, 4, base=400)
    try:
        for sid in sids[:3]:
            slot = int(rstore._slot_of[sid])
            env = rstore._store.env
            rstore._store = rstore._store.replace(
                env=env.replace(
                    job_t_completed=env.job_t_completed.at[slot].set(
                        jnp.nan
                    )
                )
            )
        quarantined = 0
        for sid in sids:
            r = rstore.decide(sid)
            quarantined += bool(r.health_mask)
        assert quarantined >= 2  # the spike is real
        ev = bus.pump()
        assert ev is not None and ev["event"] == "rollback", ev
        assert rstore.params_version == v_good
        restored = jax.device_get(rstore.model_params)
        for a, b in zip(
            jax.tree_util.tree_leaves(good),
            jax.tree_util.tree_leaves(restored),
        ):
            np.testing.assert_array_equal(a, b)
        assert bus.stats["bus_rollbacks"] == 1
    finally:
        for sid in sids:
            try:
                rstore.close(sid)
            except Exception:
                pass


# ---------------------------------------------------------------------------
# pager-aware admission (ISSUE 14 satellite / ROADMAP item 2 leftover)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def paged_store(setup):
    """capacity >> hot_capacity: 12 sessions over 4 device slots."""
    params, bank, sched = setup
    return SessionStore(
        params, bank, sched, capacity=12, hot_capacity=4,
        max_batch=2, seed=0,
    )


def test_pager_aware_admission_cuts_page_roundtrips(paged_store):
    """The satellite's acceptance: at capacity >> hot_capacity, the
    hot-preferring admission serves the same workload with FEWER page
    round-trips than strict round-robin, while every request is still
    served (the max_skips valve keeps the starvation bound
    structural). Protocol: 6 backlogged sessions x 6 requests through
    each front; page-ins counted from store stats; both arms run the
    identical submission order on the same store."""
    store = paged_store
    from sparksched_tpu.obs.metrics import MetricsRegistry

    def run_arm(pager_aware, base):
        sids = _fresh_sessions(store, 6, base=base)
        reg = MetricsRegistry()
        front = ContinuousBatcher(
            store, pager_aware=pager_aware, metrics=reg
        )
        ins0 = store.stats["serve_page_ins"]
        # build the steady backlog FIRST (size-pumps suppressed), so
        # every pump sees the full 6-session rotation — the regime
        # where admission has a choice; the synchronous auto-pump
        # would otherwise drain pairs as fast as they are submitted
        real_k = store.max_batch
        store.max_batch = 10 ** 6
        tickets = [
            front.submit(s) for _r in range(6) for s in sids
        ]
        store.max_batch = real_k
        while front.pending:
            front.pump()
        served = sum(
            1 for t in tickets
            if t.ready and (t.result is not None or t.error)
        )
        assert served == len(tickets)  # nothing starved/unresolved
        for s in sids:
            try:
                store.close(s)
            except Exception:
                pass
        return store.stats["serve_page_ins"] - ins0, reg

    ins_off, _ = run_arm(False, base=500)
    ins_on, reg_on = run_arm(True, base=600)
    assert ins_on < ins_off, (ins_on, ins_off)
    # the churn counter is live under the preference
    assert reg_on.counters.get("serve_page_churn", 0) > 0


def test_pager_aware_inert_on_unpaged_store(rstore):
    """On an unpaged store the preference must be a no-op: admission
    order is byte-identical to strict round-robin."""
    sids = _fresh_sessions(rstore, 5, base=700)
    order = {}
    for aware in (True, False):
        front = ContinuousBatcher(rstore, pager_aware=aware)
        for s in sids:
            front._queues.setdefault(s, __import__(
                "collections"
            ).deque()).append(object())
            front._rotation.append(s)
        order[aware] = front._admit_sids()
    assert order[True] == order[False] == sids[:3]
    for s in sids:
        rstore.close(s)
