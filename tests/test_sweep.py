"""The sweep loop (`sparksched_tpu/sweep.py`) against the plain reference
of the deployment: upstream's fair policy as its Python reads
(`benchmarks/reference/fair_np.py`) in the loop of the plain event heap
(`benchmarks/reference/sweep_np.simulate`). At 3 executors x 6 jobs with
a bank whose every duration bucket holds one whole-number value (whole
episodes, re-seeds inside the scan), and at the deployment's own 10 x 50
(a few lanes, the first 64 rows: a whole episode there runs past 2^24
sim-ms, where float32 no longer holds every whole number).

(a) the program's `round_robin_policy` equals `fair_np` on every
observation; (b) the chunk's record equals the simulator's rows, row for
row, and each episode's result; a simulator with one handler broken does
not agree; (c) a carry handed back in, and a carry concatenated from two
runs, go on bit for bit as the unbroken run does; (d) every (lane,
ordinal) draws a job sequence of its own, the same one whatever the lane
count; (e) the sweep's per-episode average JCT equals what
`collect_sync` (the `core.step` engine) leaves in its final state; and
every `Scheduler` runs through the loop."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import sweep_chunks
from benchmarks.reference import fair_np, stream_np
from sparksched_tpu import sweep
from sparksched_tpu.config import EnvParams
from sparksched_tpu.env.observe import observe
from sparksched_tpu.schedulers import (
    DecimaScheduler,
    RandomScheduler,
    RoundRobinScheduler,
)

from .test_stream_replay import EXECUTORS, JOBS, MOVING, WARMUP
from .test_stream_replay import _bank as fixed_bank
from .test_stream_replay import _templates as fixed_templates

LANES, ROWS, CHUNK = 4, 224, 32  # ROWS in one chunk, and in 7 of CHUNK
KEY = jax.random.PRNGKey(46)
FIELDS = ("schedulable", "frontier", "job_mask", "exec_supplies",
          "num_committable", "source_job")


@pytest.fixture(scope="module")
def small():
    """The 3 x 6 cluster over the fixed-duration bank: parameters, bank,
    the fair scheduler and the plain simulator's tables."""
    templates = fixed_templates()
    max_stages = max(len(t["num_tasks"]) for t in templates)
    params = EnvParams(
        num_executors=EXECUTORS, max_jobs=JOBS, max_stages=max_stages,
        max_levels=max_stages, moving_delay=MOVING, warmup_delay=WARMUP)
    bank = fixed_bank(templates, max_stages)
    rough = np.asarray(bank.rough_duration)
    tables = {t: {"adj": tpl["adj"], "num_tasks": tpl["num_tasks"],
                  "rough": rough[t]} for t, tpl in enumerate(templates)}
    durations = {t: {w: tpl[w] for w in ("fresh", "first", "rest")}
                 for t, tpl in enumerate(templates)}
    return params, bank, RoundRobinScheduler(EXECUTORS), tables, durations


def sequences(params, bank, lane_key, ordinals) -> list[dict]:
    """The job sequences the seed law gives a lane, as the simulator
    takes them."""
    return sweep_chunks.job_sequences(sweep, params, bank, lane_key, ordinals)


def simulated(small, lane_key, rows, fair: bool = True):
    params, bank, _, tables, durations = small
    return sweep_chunks.simulated(
        sweep, params, bank, tables, durations, lane_key, rows,
        ordinals=range(8), dynamic_partition=fair)


def mismatches(rec, lane: int, rows: list[dict]) -> list[str]:
    """Where a lane's record and the simulator's rows differ: time, job,
    stage, executors, the end of an episode and its result."""
    out = []
    for t, row in enumerate(rows):
        got = {k: getattr(rec, k)[t, lane] for k in (
            "valid", "wall_time", "job", "stage", "num_exec", "reset",
            "ordinal", "avg_jct", "jobs_completed", "makespan", "decisions")}
        if not got["valid"]:
            out.append(f"row {t}: not valid")
        if abs(float(got["wall_time"]) - row["time"]) > 1e-3:
            out.append(f"row {t}: time")
        for name in ("job", "stage", "num_exec", "ordinal"):
            if int(got[name]) != row[name]:
                out.append(f"row {t}: {name}")
        if bool(got["reset"]) != row["reset"]:
            out.append(f"row {t}: reset")
        if row["reset"]:
            res = row["result"]
            for name in ("jobs_completed", "decisions"):
                if int(got[name]) != res[name]:
                    out.append(f"row {t}: {name}")
            for name in ("avg_jct", "makespan"):
                if abs(float(got[name]) - res[name]) > 1e-6 * res[name]:
                    out.append(f"row {t}: {name}")
    return out


@pytest.fixture(scope="module")
def swept(small):
    """One chunk of `ROWS` rows over `LANES` lanes from reset: the carry
    it was handed, the carry and record it returned, its telemetry."""
    params, bank, sched, _, _ = small
    carry0 = sweep.init(params, bank, KEY, LANES)
    carry, rec, tm = sweep.sweep_chunk(
        params, bank, sched.batch_policy, carry0, jax.random.PRNGKey(1), ROWS)
    return carry0, carry, jax.device_get(rec), tm


# -- (a) the policy ---------------------------------------------------------


def policies_differ(params, sched, carry) -> int:
    """The lanes of `carry` on whose observation the program's policy
    and the plain one disagree."""
    obs = jax.device_get(jax.vmap(lambda e: observe(params, e))(carry.ls.env))
    stage_idx, num_exec, _ = jax.device_get(
        sched.batch_policy(jax.random.PRNGKey(0), obs))
    n = 0
    for b in range(carry.lane.shape[0]):
        want = fair_np.fair(
            *(getattr(obs, f)[b] for f in FIELDS),
            num_executors=sched.num_executors,
            dynamic_partition=sched.dynamic_partition)
        n += want != (int(stage_idx[b]), int(num_exec[b]))
    return n


@pytest.mark.parametrize("fair", [True, False], ids=["fair", "fifo"])
def test_the_policy_equals_the_plain_one_on_whole_episodes(small, fair):
    params, bank, _, _, _ = small
    sched = RoundRobinScheduler(EXECUTORS, dynamic_partition=fair)
    carry = sweep.init(params, bank, KEY, LANES)
    seen = wrong = 0
    for i in range(150 if fair else 80):
        wrong += policies_differ(params, sched, carry)
        seen += LANES
        carry, _, _ = sweep.sweep_chunk(
            params, bank, sched.batch_policy, carry, KEY, 1)
    # whole episodes, every lane
    assert int(carry.ordinal.min()) >= (2 if fair else 1)
    assert (seen, wrong) == (600 if fair else 320, 0)


def test_the_policy_equals_the_plain_one_at_the_deployments_size():
    """10 executors x 50 jobs over the program's own bank: 4 lanes, the
    first 64 rows."""
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(num_executors=10, max_jobs=50)
    bank = make_workload_bank(10, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages)
    sched = RoundRobinScheduler(10)
    carry = sweep.init(params, bank, KEY, 4)
    wrong = 0
    for _ in range(64):
        wrong += policies_differ(params, sched, carry)
        carry, rec, _ = sweep.sweep_chunk(
            params, bank, sched.batch_policy, carry, KEY, 1)
        assert bool(rec.valid.all())
    assert wrong == 0
    assert int(carry.ls.env.job_arrived.sum(-1).max()) > 2  # a backlog


# -- (b) the engine under the loop ------------------------------------------


def test_the_record_equals_the_simulators_rows(small, swept):
    _, carry, rec, tm = swept
    assert int(rec.reset.sum(0).min()) >= 3  # re-seeds inside the scan
    for lane in range(LANES):
        rows = simulated(small, np.asarray(carry.key[lane]), ROWS)
        assert len(rows) == ROWS
        assert mismatches(rec, lane, rows) == [], lane
    summary = sweep.summarize(tm)
    assert summary["decisions"] == int(rec.valid.sum()) == LANES * ROWS
    assert summary["reseeds_total"] == int(rec.reset.sum())
    assert summary["episodes_terminated_total"] == int(rec.reset.sum())
    assert summary["episode_decisions_total"] == int(rec.decisions.sum())
    assert summary["health_mask"] == 0


@pytest.mark.parametrize("handler", ["task_finished", "send", "backup"])
def test_a_broken_simulator_does_not_agree(small, swept, handler,
                                           monkeypatch):
    _, carry, rec, _ = swept
    ep = stream_np._Episode
    if handler == "send":  # an executor sent to another job is not delayed
        real = ep.__init__

        def init(self, *a, **kw):
            real(self, *a, **kw)
            self.moving_delay = 0.0

        monkeypatch.setattr(ep, "__init__", init)
    elif handler == "task_finished":
        # a released executor never becomes the source of a new round
        real_tf = ep.task_finished

        def task_finished(self, e, quirk):
            source = self.source
            real_tf(self, e, quirk)
            self.source = source

        monkeypatch.setattr(ep, "task_finished", task_finished)
    else:
        monkeypatch.setattr(ep, "find_backup", lambda self, e, quirk: None)
    try:
        rows = simulated(small, np.asarray(carry.key[0]), ROWS)
    except Exception:
        return  # the broken simulator could not even run the policy
    assert len(rows) != ROWS or mismatches(rec, 0, rows)


def test_a_broken_policy_does_not_agree(small, swept):
    """The plain policy with the per-job cap left out (FIFO) parts from
    the program's fair record."""
    _, carry, rec, _ = swept
    rows = simulated(small, np.asarray(carry.key[0]), ROWS, fair=False)
    assert mismatches(rec, 0, rows)


# -- (c) the carry ----------------------------------------------------------


def leaves_equal(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(jax.device_get(a)),
        jax.tree_util.tree_leaves(jax.device_get(b))))


def test_a_carry_handed_back_goes_on_as_the_unbroken_run(small, swept):
    params, bank, sched, _, _ = small
    carry0, carry, rec, _ = swept
    end, parts = carry0, []
    for i in range(ROWS // CHUNK):
        end, part, _ = sweep.sweep_chunk(
            params, bank, sched.batch_policy, end, jax.random.PRNGKey(7 + i),
            CHUNK)
        parts.append(jax.device_get(part))
    assert leaves_equal(end, carry)
    whole = jax.tree_util.tree_map(lambda *a: np.concatenate(a), *parts)
    assert leaves_equal(whole, rec)


def test_a_concatenated_carry_goes_on_as_its_parts(small, swept):
    """Lanes 2 and 3 after 96 rows beside lanes 0 and 1 from reset: each
    goes on as it does in the unbroken run."""
    params, bank, sched, _, _ = small
    carry0, _, rec, _ = swept
    mid = carry0
    for _ in range(3):
        mid, _, _ = sweep.sweep_chunk(
            params, bank, sched.batch_policy, mid, KEY, CHUNK)
    take = lambda c, lanes: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a[lanes], c)
    mixed = sweep.concat(
        [take(mid, slice(2, 4)), take(carry0, slice(0, 2))])
    assert mixed.lane.tolist() == [2, 3, 0, 1]
    parts = []
    for _ in range(2):
        mixed, part, _ = sweep.sweep_chunk(
            params, bank, sched.batch_policy, mixed, KEY, CHUNK)
        parts.append(jax.device_get(part))
    got = jax.tree_util.tree_map(lambda *a: np.concatenate(a), *parts)
    for name in ("wall_time", "job", "stage", "num_exec", "reset", "ordinal",
                 "avg_jct", "decisions"):
        a, want = getattr(got, name), getattr(rec, name)
        assert np.array_equal(a[:, :2], want[96:160, 2:4]), name
        assert np.array_equal(a[:, 2:], want[:64, :2]), name


@pytest.fixture(scope="module")
def sparse_chunk():
    """A chunk of the sweep (whole episodes, re-seeds inside the scan)
    over a bank with executor levels and whole waves missing:
    `(bank, carry0, chunk, got)`, `chunk()` the program traced anew
    (whatever the engine's helpers are at that moment) and run, `got`
    its result with the helpers as they are."""
    from .test_bulk_pass_setup import sparse_bank

    bank = sparse_bank(EXECUTORS)
    params = EnvParams(
        num_executors=EXECUTORS, max_jobs=JOBS, max_stages=bank.max_stages,
        max_levels=bank.max_stages, moving_delay=MOVING, warmup_delay=WARMUP)
    sched = RoundRobinScheduler(EXECUTORS)
    carry0 = sweep.init(params, bank, KEY, LANES)

    def chunk():
        # a function and a jit of its own a side: the sampler and the
        # pick are no key of a jit's cache
        def program(*args):
            return sweep._chunk(*args)

        return jax.device_get(jax.jit(program, static_argnums=(0, 2, 5))(
            params, bank, sched.batch_policy, carry0, jax.random.PRNGKey(1),
            ROWS))

    return bank, carry0, chunk, chunk()


@pytest.mark.parametrize("replaced", ["tables", "indexed reads"])
def test_a_chunk_equals_the_one_collected_by_the_reads_replaced(
    sparse_chunk, monkeypatch, replaced,
):
    """A chunk of the sweep over a bank with executor levels and whole
    waves missing is, leaf for leaf (carry, record and telemetry, 0
    unequal), the chunk collected (PR 50, "tables") with the sampler
    that read the bank's tables, and (PR 51, "indexed reads") with
    every pick by one-hot of the drain's fixed part, of the decide
    step and of the early-exit loop made as the indexed read it
    replaced. The carry holds each lane's words of its OWN templates,
    the re-seeded ones too."""
    from sparksched_tpu.workload.sampling import pack_duration_facts

    from .test_env_core import swap_in_the_reads_replaced

    bank, carry0, chunk, got = sparse_chunk
    traced = swap_in_the_reads_replaced(monkeypatch, replaced, EXECUTORS)
    want = chunk()
    # the passes sampled by it; the forty-odd reads of a body and a row
    assert traced() >= (3 if replaced == "tables" else 40), traced()
    unequal = [
        jax.tree_util.keystr(path)
        for (path, x), y in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree_util.tree_leaves(want))
        if not np.array_equal(x, y)]
    assert not unequal, unequal
    carry, rec, _ = got
    assert rec.reset.sum() >= LANES and rec.valid.all()
    env = carry.ls.env
    assert (env.job_template != carry0.ls.env.job_template).any()
    np.testing.assert_array_equal(
        env.duration_facts,
        np.asarray(pack_duration_facts(bank))[env.job_template])


def test_init_takes_states_the_caller_made(small):
    """`init(states=...)`: lanes that are not reset states go on from
    where they are, and their later episodes follow the seed law."""
    params, bank, sched, _, _ = small
    carry0 = sweep.init(params, bank, KEY, LANES)
    mid, _, _ = sweep.sweep_chunk(
        params, bank, sched.batch_policy, carry0, KEY, 1)
    assert int(mid.ordinal.max()) == 0
    again = sweep.init(params, bank, KEY, states=mid.ls.env)
    assert leaves_equal(again.key, carry0.key)
    assert leaves_equal(again.ls.env, mid.ls.env)


def test_init_refuses_keys_that_are_not_threefry(small):
    params, bank, _, _, _ = small
    before = jax.config.jax_default_prng_impl
    jax.config.update("jax_default_prng_impl", "rbg")
    try:
        with pytest.raises(ValueError, match="threefry"):
            sweep.init(params, bank, KEY, 2)
    finally:
        jax.config.update("jax_default_prng_impl", before)


# -- (d) the seed law -------------------------------------------------------


def test_every_lane_and_ordinal_draws_a_sequence_of_its_own(small):
    params, bank, _, _, _ = small
    keys = sweep.lane_keys(KEY, jnp.arange(6))
    seen = set()
    for lane in range(6):
        for seq in sequences(params, bank, keys[lane], range(4)):
            seen.add(repr(seq["arrivals"]))
    assert len(seen) == 24


def test_a_lanes_sequences_do_not_depend_on_the_lane_count(small, swept):
    """Lane 2's episodes in a run of 3 lanes are its episodes in the
    run of 4, results included."""
    params, bank, sched, _, _ = small
    _, _, rec, _ = swept
    carry, parts = sweep.init(params, bank, KEY, 3), []
    for _ in range(ROWS // CHUNK):
        carry, part, _ = sweep.sweep_chunk(
            params, bank, sched.batch_policy, carry, jax.random.PRNGKey(3),
            CHUNK)
        parts.append(jax.device_get(part))
    got = jax.tree_util.tree_map(lambda *a: np.concatenate(a), *parts)
    assert int(got.reset[:, 2].sum()) >= 3
    for name in ("wall_time", "job", "stage", "reset", "avg_jct"):
        assert np.array_equal(
            getattr(got, name)[:, 2], getattr(rec, name)[:, 2]), name


# -- (e) the `core.step` engine ---------------------------------------------


def test_the_result_equals_what_collect_sync_leaves(small, swept):
    """From the same reset states, the first episodes' average JCT by
    the sweep (read at the end, before the re-seed) and by the
    per-decision `core.step` loop (`metrics.avg_job_duration` of its
    final state, as `scripts_eval_decima.run_policy` took it)."""
    from sparksched_tpu import metrics
    from sparksched_tpu.trainers.rollout import collect_sync

    params, bank, sched, _, _ = small
    carry0, _, rec, _ = swept
    states = carry0.ls.env
    ro = jax.jit(jax.vmap(lambda s: collect_sync(
        params, bank, sched.policy, jax.random.PRNGKey(0), 160, s)))(states)
    final = ro.final_state
    assert bool(jax.vmap(lambda s: s.all_jobs_complete)(final).all())
    want = np.asarray(jax.vmap(metrics.avg_job_duration)(final))
    res = sweep.results_of(rec, carry0.lane)
    first = res["ordinal"] == 0
    got = res["avg_jct"][first][np.argsort(res["lane"][first])]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert np.array_equal(
        res["decisions"][first][np.argsort(res["lane"][first])],
        np.asarray(ro.valid.sum(-1)))


# -- every scheduler, and the command line ----------------------------------


def test_run_returns_the_episodes_it_was_asked_for(small):
    params, bank, sched, _, _ = small
    out = sweep.run(params, bank, sched, episodes=6, lanes=LANES, seed=3,
                    rows=CHUNK)
    assert out["lane"].tolist() == [0, 1, 2, 3, 0, 1]
    assert out["ordinal"].tolist() == [0, 0, 0, 0, 1, 1]
    assert (out["jobs_completed"] == JOBS).all()
    assert out["mean_avg_jct"] == pytest.approx(out["avg_jct"].mean())
    assert out["telemetry"]["health_mask"] == 0
    with pytest.raises(RuntimeError, match="0 of 6 episodes"):
        sweep.run(params, bank, sched, episodes=6, lanes=LANES, seed=3,
                  rows=8, max_chunks=1)


@pytest.mark.parametrize("name", ["fifo", "random", "decima", "greedy"])
def test_every_scheduler_runs_through_the_loop(small, name):
    params, bank, _, _, _ = small
    policy = None
    if name == "fifo":
        sched = RoundRobinScheduler(EXECUTORS, dynamic_partition=False)
    elif name == "random":
        sched = RandomScheduler()
    else:
        sched = DecimaScheduler(
            num_executors=EXECUTORS, num_levels=params.max_stages)
        if name == "greedy":
            policy = partial(sched.batch_policy, deterministic=True)
    out = sweep.run(params, bank, sched, policy=policy, episodes=3, lanes=3,
                    seed=5, rows=CHUNK, max_chunks=80)
    assert (out["jobs_completed"] == JOBS).all()
    assert (out["avg_jct"] > 0).all() and np.isfinite(out["avg_jct"]).all()
    assert (out["decisions"] >= JOBS).all()
    assert out["telemetry"]["health_mask"] == 0


def test_the_command_line_prints_the_mean_and_writes_the_episodes(
        tmp_path, capsys):
    import importlib.util
    import os.path as osp

    from sparksched_tpu import config

    root = osp.dirname(osp.dirname(osp.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "sweep_cli", osp.join(root, "sweep.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    cfg = config.load(osp.join(root, "config", "sweep_fair_demo.yaml"))
    assert cfg["env"]["num_executors"] == 10
    assert cfg["env"]["job_arrival_cap"] == 50
    assert cfg["agent"] == {"agent_cls": "RoundRobinScheduler",
                            "dynamic_partition": True}
    out = str(tmp_path / "episodes.csv")
    cfg["env"] |= {"num_executors": 3, "job_arrival_cap": 4}
    cfg["sweep"] |= {"episodes": 4, "lanes": 2, "rows_per_chunk": 32,
                     "out": out}
    res = cli.main(cfg)
    said = capsys.readouterr().out
    assert "Fair: mean avg job completion time" in said
    assert "over 4 episodes" in said
    lines = open(out).read().splitlines()
    assert lines[0] == "lane,ordinal,avg_jct,jobs_completed,makespan,decisions"
    assert len(lines) == 5 and (res["jobs_completed"] == 4).all()
