"""Batched arrivals in the flat engine against the plain event-heap
simulator (`benchmarks/reference/stream_np.replay`), as
`tests/test_stream_replay.py` holds streaming: at 3 executors x 6 jobs,
a bank of one value a bucket and job sequences as data, the SYNC
single-eval collector's stored rows equal the simulator's row for row
(every observation field, the time, the reward, the episode's end) over
three lanes, one episode each: two with all six jobs at t=0 and no time
limit (`EnvParams.num_init_jobs` = the cap), one with three at t=0 and
three later. Every duration, the moving delay, the warm-up delay and
every arrival time is a multiple of 1000 ms, so events TIE in time in
every episode: two tasks that finish together, a task that finishes as
an executor arrives, a job that arrives as a task finishes; the
simulator pops equal times in sequence order, which is what the engine's
"(time, seq)" order has to reproduce. A simulator whose order among
equal times is broken on purpose, or that loads only the first job at
reset, does not agree."""

import types

import numpy as np
import pytest

from benchmarks.reference import stream_np

from .reference_fixtures import spec_multi_job
from .test_stream_replay import _bank, _mismatches

EXECUTORS, JOBS, ROWS = 3, 6, 200
MOVING, WARMUP = 2000.0, 1000.0
# lane k: (arrival times, templates); no time limit anywhere, so every
# episode ends `terminated`, on its last job's completion
EPISODES = [
    ([0, 0, 0, 0, 0, 0], [0, 2, 1, 3, 2, 4]),
    ([0, 0, 0, 0, 0, 0], [4, 1, 0, 2, 3, 1]),
    ([0, 0, 0, 6000, 6000, 14000], [3, 3, 1, 0, 4, 2]),
]


def _templates():
    """Five job templates whose durations are whole thousands (fresh 3k,
    first 2k, rest 1k for a small k), with the empty buckets of
    `tests/test_stream_replay.py`."""
    out = []
    for t, job in enumerate(spec_multi_job(5, seed=21)["jobs"]):
        ns = len(job["num_tasks"])
        k = [1 + (int(r) % 3) for r in job["rest"]]
        out.append({
            "adj": job["adj"], "num_tasks": job["num_tasks"],
            "fresh": [None if (t == 1 and s % 2 == 0) else 3000.0 * k[s]
                      for s in range(ns)],
            "first": [2000.0 * k[s] for s in range(ns)],
            "rest": [None if (t == 3 and s % 2 == 1) else 1000.0 * k[s]
                     for s in range(ns)]})
    return out


def _collect(episode_counters=True):
    """The sync collector's rollout over the three lanes (device
    arrays), its telemetry's summary, and what the simulator needs."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.obs.telemetry import (
        summarize,
        telemetry_zeros_like,
    )
    from sparksched_tpu.trainers.rollout import collect_flat_sync_batch

    templates = _templates()
    max_stages = max(len(t["num_tasks"]) for t in templates)
    params = EnvParams(
        num_executors=EXECUTORS, max_jobs=JOBS, max_stages=max_stages,
        max_levels=max_stages, moving_delay=MOVING, warmup_delay=WARMUP,
        num_init_jobs=JOBS)
    bank = _bank(templates, max_stages)
    lanes = len(EPISODES)
    states = jax.vmap(lambda a, t, key: core.reset_from_sequence(
        params, bank, key, jnp.float32(jnp.inf), a, t, jnp.int32(JOBS),
        jnp.ones((JOBS,), bool)))(
            jnp.asarray([e[0] for e in EPISODES], jnp.float32),
            jnp.asarray([e[1] for e in EPISODES], jnp.int32),
            jax.random.split(jax.random.PRNGKey(5), lanes))

    def policy(rng, obs):
        """A seeded choice among the schedulable stages and of 1 to 3
        executors; never declines while a stage is schedulable."""
        def one(k, o):
            flat = o.schedulable.reshape(-1)
            k1, k2 = jax.random.split(k)
            idx = jax.random.categorical(
                k1, jnp.where(flat, 0.0, -jnp.inf))
            n = jax.random.randint(k2, (), 1, EXECUTORS + 1)
            return jnp.where(flat.any(), idx, -1).astype(jnp.int32), n

        si, ne = jax.vmap(one)(
            jax.random.split(rng, obs.job_mask.shape[0]), obs)
        return si, ne, {}

    ro, telem = collect_flat_sync_batch(
        params, bank, policy, jax.random.PRNGKey(9), ROWS, states,
        telemetry_zeros_like((lanes,), episodes=episode_counters))
    rough = np.asarray(bank.rough_duration)
    tables = {t: {"adj": tpl["adj"], "num_tasks": tpl["num_tasks"],
                  "rough": rough[t]} for t, tpl in enumerate(templates)}
    durations = {t: {w: tpl[w] for w in ("fresh", "first", "rest")}
                 for t, tpl in enumerate(templates)}
    return jax.device_get(ro), summarize(telem), params, tables, durations


@pytest.fixture(scope="module")
def engine_rows():
    return _collect()


def _lane(ro, k):
    import jax

    return jax.tree_util.tree_map(lambda a: a[k], ro)


def _replay(ro, k, params, tables, durations, pops=None):
    """Lane k's stored actions through the simulator; `pops` collects
    (time, kind) of every event popped."""
    n = int(ro.valid.sum())
    s_cap = params.max_stages
    actions = [
        ((None, None, int(m) + 1) if a < 0
         else (int(a) // s_cap, int(a) % s_cap, int(m) + 1))
        for a, m in zip(ro.stage_idx[:n], ro.num_exec_k[:n])]
    jobs = [{"arrivals": list(zip(*EPISODES[k])), "time_limit": np.inf}]
    ep, real = stream_np._Episode, stream_np._Episode.pop_event
    if pops is not None:
        def pop_event(self):
            pops.append((float(self.events[0][0]), self.events[0][2]))
            real(self)

        ep.pop_event = pop_event
    try:
        return stream_np.replay(
            jobs, tables, actions, durations, num_executors=EXECUTORS,
            max_jobs=JOBS, max_stages=s_cap, moving_delay=MOVING,
            warmup_delay=WARMUP)
    finally:
        ep.pop_event = real


def test_every_lane_ends_by_completion_inside_the_scan(engine_rows):
    ro, summary, params, _, _ = engine_rows
    n = ro.valid.sum(axis=1)
    assert (n < ROWS).all() and (n > 20).all()
    for k in range(len(EPISODES)):
        assert ro.valid[k, :n[k]].all()  # the valid rows are a prefix
        assert ro.resets[k, n[k] - 1] and ro.resets[k].sum() == 1
    assert np.isfinite(ro.final_state.job_t_completed).all()
    # the first decision of a batched lane sees all six jobs
    assert ro.obs.job_mask[:2, 0].all() and ro.obs.job_mask[2, 0].sum() == 3
    # the counters add up: a lane's rows are its decisions and the rows
    # it sat out after its own episode ended
    assert summary["episodes_terminated_total"] == len(EPISODES)
    assert summary["row"]["lane_rows"] == ROWS * len(EPISODES)
    assert summary["row"]["lane_rows_ended"] == int((ROWS - n).sum())
    assert summary["decisions"] == int(n.sum())
    present = sum(int(ro.obs.job_mask[k, :n[k]].sum())
                  for k in range(len(EPISODES)))
    assert summary["jobs_present_per_decision"] == round(
        present / int(n.sum()), 3)


@pytest.mark.parametrize("lane", range(len(EPISODES)))
def test_the_engine_equals_the_heap_simulator_row_for_row(
        engine_rows, lane):
    ro, _, params, tables, durations = engine_rows
    ro, pops = _lane(ro, lane), []
    rows = _replay(ro, lane, params, tables, durations, pops)
    assert len(rows) == int(ro.valid.sum())
    assert _mismatches(ro, params, rows) == []
    assert rows[-1]["reset"]  # the simulator's episode ended there too
    # events tied in time, of these kinds, one popped after the other
    tied = {(a[1], b[1]) for a, b in zip(pops, pops[1:]) if a[0] == b[0]}
    assert ("finished", "finished") in tied
    assert {("finished", "ready"), ("ready", "finished")} & tied
    if lane == 2:
        assert {("arrival", "finished"), ("finished", "arrival"),
                ("arrival", "arrival")} & tied


def _heap_with(key):
    """`heapq` with the entries' order changed: `key` maps an event
    `(time, seq, kind, arg)` to what the heap sorts by."""
    import heapq

    return types.SimpleNamespace(
        heappush=lambda h, ev: heapq.heappush(h, (key(ev), ev)),
        heappop=lambda h: heapq.heappop(h)[1])


BROKEN_ORDER = {
    # among equal times the LATEST sequence number first
    "latest_first": lambda ev: (ev[0], -ev[1]),
    # among equal times every arriving executor before every task end
    "ready_first": lambda ev: (ev[0], ev[2] != "ready", ev[1]),
    # among equal times every task end before anything else
    "finished_first": lambda ev: (ev[0], ev[2] != "finished", ev[1]),
}


@pytest.mark.parametrize("variant", [*BROKEN_ORDER, "one_job_at_reset"])
def test_a_simulator_with_a_broken_order_among_equal_times_disagrees(
        engine_rows, variant, monkeypatch):
    ro, _, params, tables, durations = engine_rows
    if variant in BROKEN_ORDER:
        monkeypatch.setattr(
            stream_np, "heapq", _heap_with(BROKEN_ORDER[variant]))
    else:
        # only the first job is there at the first decision: the others
        # come as events at t=0 (upstream's `_load_initial_jobs` undone)
        real = stream_np._Episode.__init__

        def init(self, seq, *a, **kw):
            first, *rest = seq["arrivals"]
            real(self, dict(seq, arrivals=[first] + [
                (max(t, 1e-3), tpl) for t, tpl in rest]), *a, **kw)

        monkeypatch.setattr(stream_np._Episode, "__init__", init)
    disagreed = 0
    for lane in range(len(EPISODES)):
        lane_ro = _lane(ro, lane)
        try:
            rows = _replay(lane_ro, lane, params, tables, durations)
        except Exception:
            disagreed += 1  # it could not even follow the actions
            continue
        disagreed += bool(
            len(rows) != int(lane_ro.valid.sum())
            or _mismatches(lane_ro, params, rows))
    assert disagreed >= 2


def test_the_episode_counters_are_carried_only_where_asked_for(engine_rows):
    """Without `episodes=True` the three counters are no leaf of the
    telemetry, the summary has none of their keys, and everything else
    (the rollout, every other counter) is what it is with them."""
    import jax

    ro, summary = engine_rows[:2]
    bare_ro, bare = _collect(episode_counters=False)[:2]
    same = jax.tree_util.tree_map(
        lambda a, b: bool(np.array_equal(a, b, equal_nan=True)), ro, bare_ro)
    assert all(jax.tree_util.tree_leaves(same)), same
    asked = {"episodes_terminated_total", "jobs_present_total",
             "jobs_present_per_decision"}
    assert asked <= set(summary) and not asked & set(bare)
    assert "lane_rows_ended" not in bare["row"]
    assert bare == {k: v for k, v in summary.items() if k not in asked} | {
        "row": {k: v for k, v in summary["row"].items()
                if k != "lane_rows_ended"}}


def test_a_lanes_rows_after_its_end_are_left_as_they_were(engine_rows):
    """A row is stored by one scatter over (lane, slot) pairs whose slot
    is out of bounds, and dropped, for a lane that does not decide: past
    a lane's last valid row every stored leaf is the buffer's zero,
    while the lanes still running go on storing theirs."""
    import jax

    ro = engine_rows[0]
    n = ro.valid.sum(axis=1)
    assert len(set(n.tolist())) > 1  # the lanes end in different rows
    for leaf in jax.tree_util.tree_leaves(ro.obs) + [
            ro.job_idx, ro.num_exec_k, ro.lgprob, ro.reward, ro.resets]:
        for k in range(len(EPISODES)):
            assert not np.asarray(leaf[k, n[k]:]).any()
    for k in range(len(EPISODES)):  # and every row before it is there
        assert ro.obs.node_mask[k, :n[k]].any(axis=-1).all()
        assert ro.obs.job_mask[k, :n[k]].any(axis=-1).all()


@pytest.mark.parametrize("kind", ["set", "add", "max"])
def test_the_row_store_writes_what_a_one_lane_update_writes(kind):
    """`rollout._row_scatter`, the ONE form of the collector's store,
    against `vmap` of a one-lane `.at[slot]` update with `mode="drop"`
    (the form it replaced, kept here as the reference): every leaf
    shape the rollout has, slots in bounds, out of bounds (dropped) and
    lanes in any mix of the two."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from sparksched_tpu.trainers.rollout import _row_scatter

    scatter = {"set": lax.scatter, "add": lax.scatter_add,
               "max": lax.scatter_max}[kind]
    lanes, rows = 24, 7
    rng = np.random.default_rng(3)
    for trailing, dtype in (((), np.float32), ((), np.int32),
                            ((5,), np.int32), ((128,), np.float32)):
        buf = rng.integers(-9, 9, (lanes, rows) + trailing).astype(dtype)
        new = rng.integers(-9, 9, (lanes,) + trailing).astype(dtype)
        for slot in (rng.integers(0, rows + 1, lanes),  # rows: dropped
                     np.full(lanes, rows), np.arange(lanes) % rows):
            slot = jnp.asarray(slot, jnp.int32)
            want = jax.vmap(lambda b, s, v: getattr(b.at[s], kind)(
                v, mode="drop"))(jnp.asarray(buf), slot, jnp.asarray(new))
            got = jax.jit(_row_scatter, static_argnums=0)(
                scatter, jnp.asarray(buf), slot, jnp.asarray(new))
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
