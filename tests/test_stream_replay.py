"""The flat engine against the plain event-heap simulator
(`benchmarks/reference/stream_np.replay`): at 3 executors x 6 jobs, with
a bank whose every duration bucket holds one value (so no draw can
change a duration) and job sequences given as data, the stored decision
rows of the streaming single-eval collector over three episodes, with
the re-seeds inside the scan, equal the simulator's row for row:
every observation field, the elapsed time and the reward. One episode
ends on its time limit, the others when their jobs are done. A
simulator with one handler broken on purpose does not agree."""

import numpy as np
import pytest

from benchmarks.reference import stream_np

from .reference_fixtures import spec_multi_job

EXECUTORS, JOBS, ROWS = 3, 6, 260
MOVING, WARMUP = 2000.0, 1000.0
# episode k: (arrival times, templates, time limit); whole numbers, so
# every time is exact in float32
EPISODES = [
    ([0, 0, 4000, 9000, 15000, 40000], [0, 2, 1, 3, 2, 4], np.inf),
    ([0, 3000, 3000, 12000, 30000, 31000], [4, 1, 0, 2, 3, 1], 52000.0),
    ([0, 1000, 2000, 3000, 4000, 5000], [3, 3, 1, 0, 4, 2], np.inf),
]


def _templates():
    """Five job templates with distinct whole durations; in two of them
    some stages have no `fresh` bucket (so an idle executor takes the
    first-wave value and the warm-up delay) or no `rest` bucket."""
    out = []
    for t, job in enumerate(spec_multi_job(5, seed=21)["jobs"]):
        ns = len(job["num_tasks"])
        fresh = [None if (t == 1 and s % 2 == 0) else job["fresh"][s]
                 for s in range(ns)]
        rest = [None if (t == 3 and s % 2 == 1) else job["rest"][s]
                for s in range(ns)]
        out.append({"adj": job["adj"], "num_tasks": job["num_tasks"],
                    "fresh": fresh, "first": list(job["first"]),
                    "rest": rest})
    return out


def _bank(templates, max_stages):
    """The program's bank from the same templates, one value a bucket
    at every executor level."""
    from sparksched_tpu.workload.bank import EXEC_LEVEL_VALUES, pack_bank

    packed = []
    for tpl in templates:
        durations = {}
        for s in range(len(tpl["num_tasks"])):
            waves = {"fresh_durations": tpl["fresh"][s],
                     "first_wave": tpl["first"][s],
                     "rest_wave": tpl["rest"][s]}
            durations[s] = {
                name: {lv: ([] if v is None else [v])
                       for lv in EXEC_LEVEL_VALUES}
                for name, v in waves.items()}
        packed.append({"adj": tpl["adj"],
                       "num_tasks": np.array(tpl["num_tasks"]),
                       "durations": durations})
    return pack_bank(packed, EXECUTORS, max_stages, bucket_size=1)


@pytest.fixture(scope="module")
def engine_rows():
    """What the streaming collector stored for one lane, and the data
    the simulator needs."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import init_loop_state
    from sparksched_tpu.trainers.rollout import _flat_collect_single_eval

    templates = _templates()
    max_stages = max(len(t["num_tasks"]) for t in templates)
    params = EnvParams(
        num_executors=EXECUTORS, max_jobs=JOBS, max_stages=max_stages,
        max_levels=max_stages, moving_delay=MOVING, warmup_delay=WARMUP)
    bank = _bank(templates, max_stages)
    arrivals = jnp.asarray([e[0] for e in EPISODES], jnp.float32)
    tpl_ids = jnp.asarray([e[1] for e in EPISODES], jnp.int32)
    limits = jnp.asarray([e[2] for e in EPISODES], jnp.float32)
    mask = jnp.ones((JOBS,), bool)

    def reset_to(k, key):
        k = jnp.minimum(k, len(EPISODES) - 1)
        return core.reset_from_sequence(
            params, bank, key, limits[k], arrivals[k], tpl_ids[k],
            jnp.int32(JOBS), mask)

    def reset_fns(lane):
        return lambda key, episodes: reset_to(episodes + 1, key)

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

    state = reset_to(0, jax.random.PRNGKey(5))
    ls = jax.vmap(init_loop_state)(
        jax.tree_util.tree_map(lambda a: a[None], state))
    ro, _ = jax.jit(lambda ls: _flat_collect_single_eval(
        params, bank, policy, jax.random.PRNGKey(9), ROWS, ls,
        auto_reset=True, event_bulk=True, bulk_events=8, fulfill_bulk=True,
        bulk_cycles=1, reset_fns=reset_fns,
        rollout_duration=jnp.float32(jnp.inf), use_elapsed=True))(ls)
    ro = jax.device_get(jax.tree_util.tree_map(lambda a: a[0], ro))
    rough = np.asarray(bank.rough_duration)
    tables = {t: {"adj": tpl["adj"], "num_tasks": tpl["num_tasks"],
                  "rough": rough[t]} for t, tpl in enumerate(templates)}
    durations = {t: {w: tpl[w] for w in ("fresh", "first", "rest")}
                 for t, tpl in enumerate(templates)}
    return ro, params, tables, durations


def _replay(ro, params, tables, durations, sim=stream_np):
    n = int(ro.valid.sum())
    s_cap = params.max_stages
    actions = [
        ((None, None, int(k) + 1) if a < 0
         else (int(a) // s_cap, int(a) % s_cap, int(k) + 1))
        for a, k in zip(ro.stage_idx[:n], ro.num_exec_k[:n])]
    episodes = [EPISODES[min(k, len(EPISODES) - 1)] for k in range(16)]
    jobs = [{"arrivals": list(zip(a, t)), "time_limit": lim}
            for a, t, lim in episodes]
    return sim.replay(
        jobs, tables, actions, durations, num_executors=EXECUTORS,
        max_jobs=JOBS, max_stages=s_cap, moving_delay=MOVING,
        warmup_delay=WARMUP)


def _mismatches(ro, params, rows) -> list[str]:
    out = []
    j, s = params.max_jobs, params.max_stages
    for t, row in enumerate(rows):
        for name in ("remaining", "duration", "schedulable", "node_mask"):
            got = np.asarray(getattr(ro.obs, name)[t])[: j * s].reshape(j, s)
            if not np.array_equal(got, row[name]):
                out.append(f"row {t}: {name}")
        for name in ("job_mask", "exec_supplies", "job_template"):
            if not np.array_equal(getattr(ro.obs, name)[t], row[name]):
                out.append(f"row {t}: {name}")
        for name in ("num_committable", "source_job"):
            if int(getattr(ro.obs, name)[t]) != row[name]:
                out.append(f"row {t}: {name}")
        if abs(float(ro.wall_times[t]) - row["elapsed"]) > 1e-3:
            out.append(f"row {t}: elapsed")
        if abs(float(ro.reward[t]) - row["reward"]) > 1e-4 * max(
                1.0, abs(row["reward"])):
            out.append(f"row {t}: reward")
        if bool(ro.resets[t]) != row["reset"]:
            out.append(f"row {t}: reset")
    return out


def test_the_collector_covers_what_the_replay_is_for(engine_rows):
    ro, params, _, _ = engine_rows
    n = int(ro.valid.sum())
    assert n == ROWS, "a row without a decision: the policy declined"
    assert int(ro.resets.sum()) >= 3  # three episodes ended in the scan
    ends = np.flatnonzero(ro.resets)
    # the second episode ends on its time limit with jobs unfinished
    assert ro.obs.job_mask[ends[1]].any()
    moved = (np.diff(ro.wall_times[: n + 1]) > 0).sum()
    assert 20 < moved < n  # rounds of several decisions at one time


def test_the_engine_equals_the_heap_simulator_row_for_row(engine_rows):
    ro, params, tables, durations = engine_rows
    rows = _replay(ro, params, tables, durations)
    assert len(rows) == int(ro.valid.sum())
    assert _mismatches(ro, params, rows) == []


BROKEN = {
    # an executor that arrives at another job is not delayed
    "send": ("moving_delay", lambda ep: setattr(ep, "moving_delay", 0.0)),
    # a fresh executor without a fresh bucket skips the warm-up delay
    "warmup": ("warmup_delay", lambda ep: setattr(ep, "warmup_delay", 0.0)),
}


@pytest.mark.parametrize("handler", [
    "task_finished", "ready", "time_limit", "send", "warmup", "backup"])
def test_a_simulator_with_a_broken_handler_does_not_agree(
        engine_rows, handler, monkeypatch):
    ro, params, tables, durations = engine_rows
    ep = stream_np._Episode
    if handler in BROKEN:
        real = ep.__init__

        def init(self, *a, **kw):
            real(self, *a, **kw)
            BROKEN[handler][1](self)

        monkeypatch.setattr(ep, "__init__", init)
    elif handler == "task_finished":
        # a released executor never becomes the source of a new round
        real_tf = ep.task_finished

        def task_finished(self, e, quirk):
            source = self.source
            real_tf(self, e, quirk)
            self.source = source

        monkeypatch.setattr(ep, "task_finished", task_finished)
    elif handler == "ready":
        # an arriving executor is parked instead of starting a task
        monkeypatch.setattr(ep, "frontier", lambda self, j, s: False)
    elif handler == "time_limit":
        monkeypatch.setattr(ep, "over", lambda self: self.all_done())
    elif handler == "backup":
        monkeypatch.setattr(ep, "find_backup", lambda self, e, quirk: None)
    try:
        rows = _replay(ro, params, tables, durations)
    except Exception:
        return  # the broken simulator could not even follow the actions
    assert len(rows) != int(ro.valid.sum()) or _mismatches(ro, params, rows)
