"""Unit tests for the vectorized simulator core: invariants, vmap batching,
and workload bank integrity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparksched_tpu.config import EnvParams
from sparksched_tpu.env.core import reset, step
from sparksched_tpu.env.observe import observe
from sparksched_tpu.workload import make_workload_bank
from sparksched_tpu.workload.bank import topological_levels


@pytest.fixture(scope="module")
def small_setup():
    params = EnvParams(num_executors=10, max_jobs=6, max_stages=20)
    bank = make_workload_bank(10)
    return params, bank


def greedy_episode(params, bank, seed, max_steps=4000):
    """Run one episode with a greedy policy (first schedulable stage,
    all committable executors), advanced in jitted chunked scans with a
    done-freeze — per-call dispatch made the host-loop version one of
    the slowest fast-tier tests. Returns the final state and the
    decision count."""
    @jax.jit
    def chunk(state, steps):
        def body(carry, _):
            state, steps = carry
            done = state.terminated | state.truncated
            obs = observe(params, state)
            flat = obs.schedulable.reshape(-1)
            idx = jnp.where(
                flat.any(), jnp.argmax(flat), -1
            ).astype(jnp.int32)
            s2, _, _, _ = step(
                params, bank, state, idx,
                obs.num_committable.astype(jnp.int32),
            )
            state = jax.tree_util.tree_map(
                lambda frozen, stepped: jnp.where(done, frozen, stepped),
                state, s2,
            )
            return (state, steps + ~done), None

        return jax.lax.scan(body, (state, steps), None, length=100)[0]

    state = reset(params, bank, jax.random.PRNGKey(seed))
    steps = jnp.int32(0)
    for _ in range(-(-max_steps // 100)):  # ceil: honor small budgets
        state, steps = chunk(state, steps)
        if bool(state.terminated | state.truncated):
            return state, int(steps)
    raise AssertionError("episode did not terminate")


def test_episode_terminates_and_completes_jobs(small_setup):
    params, bank = small_setup
    state, steps = greedy_episode(params, bank, seed=0)
    n = int(state.num_jobs)
    assert bool(state.terminated)
    completions = np.asarray(state.job_t_completed)[:n]
    arrivals = np.asarray(state.job_arrival_time)[:n]
    assert np.isfinite(completions).all()
    assert (completions > arrivals).all()
    # all tasks accounted for
    done = np.asarray(state.stage_completed_tasks)
    total = np.asarray(state.stage_num_tasks)
    assert (done == total).all()


def test_invariants_along_episode(small_setup):
    params, bank = small_setup
    state = reset(params, bank, jax.random.PRNGKey(1))
    for t in range(300):
        if bool(state.terminated):
            break
        obs = observe(params, state)
        # executor conservation: every executor is in exactly one of
        # common / attached / moving
        at_common = np.asarray(state.exec_at_common)
        attached = np.asarray(state.exec_job) >= 0
        moving = np.asarray(state.exec_moving)
        states = at_common.astype(int) + attached.astype(int) + moving.astype(int)
        assert (states <= 1).all(), f"step {t}: overlapping exec states"
        # commitment count bound (supply >= demand invariant)
        assert int(np.asarray(state.cm_valid).sum()) <= params.num_executors
        # committable never negative
        assert int(obs.num_committable) >= 0
        # schedulable stages are active and unsaturated
        sched = np.asarray(state.schedulable)
        if sched.any():
            rem = np.asarray(state.stage_remaining)
            assert (rem[sched] > 0).all()
        flat = sched.reshape(-1)
        idx = int(flat.argmax()) if flat.any() else -1
        state, _, _, _ = step(
            params, bank, state, jnp.int32(idx), jnp.int32(1)
        )


@pytest.mark.slow
def test_vmap_batch_runs(small_setup):
    params, bank = small_setup
    batch = 8
    rngs = jax.random.split(jax.random.PRNGKey(42), batch)
    v_reset = jax.vmap(lambda r: reset(params, bank, r))
    states = v_reset(rngs)
    assert states.wall_time.shape == (batch,)

    def greedy_action(obs):
        flat = obs.schedulable.reshape(-1)
        has = flat.any()
        idx = jnp.where(has, jnp.argmax(flat), -1)
        return idx.astype(jnp.int32), jnp.maximum(obs.num_committable, 1)

    def one_step(state):
        obs = observe(params, state)
        idx, n = greedy_action(obs)
        state, rew, term, trunc = step(params, bank, state, idx, n)
        return state, rew

    v_step = jax.jit(jax.vmap(one_step))
    for _ in range(50):
        states, rews = v_step(states)
    assert np.isfinite(np.asarray(rews)).all()
    assert (np.asarray(states.wall_time) > 0).any()


def test_reward_is_negative_jobtime(small_setup):
    params, bank = small_setup
    state, _ = greedy_episode(params, bank, seed=3)
    # total reward equals negative integral of #active jobs over time ==
    # -sum of job durations (every job arrives and completes in-episode)
    n = int(state.num_jobs)
    durations = (
        np.asarray(state.job_t_completed)[:n]
        - np.asarray(state.job_arrival_time)[:n]
    )
    state2 = reset(params, bank, jax.random.PRNGKey(3))
    total_rew = 0.0
    while not bool(state2.terminated):
        obs = observe(params, state2)
        flat = np.asarray(obs.schedulable).reshape(-1)
        idx = int(flat.argmax()) if flat.any() else -1
        state2, r, _, _ = step(
            params, bank, state2, jnp.int32(idx),
            jnp.int32(int(obs.num_committable)),
        )
        total_rew += float(r)
    np.testing.assert_allclose(-total_rew, durations.sum(), rtol=1e-4)


def test_topological_levels():
    adj = np.zeros((4, 4), dtype=bool)
    adj[0, 1] = adj[0, 2] = adj[1, 3] = adj[2, 3] = True
    lv = topological_levels(adj, 4)
    assert lv.tolist() == [0, 1, 1, 2]


def test_bank_shapes(small_setup):
    _, bank = small_setup
    assert bank.num_templates == 154  # 22 queries x 7 sizes
    assert (np.asarray(bank.num_stages) >= 2).all()
    assert (np.asarray(bank.num_stages) <= bank.max_stages).all()
    # every existing stage has all-positive durations and a present level
    ns = np.asarray(bank.num_stages)
    cnt = np.asarray(bank.cnt)
    for t in [0, 50, 153]:
        for s in range(ns[t]):
            assert cnt[t, s].sum() > 0


def test_rank_order_matches_stable_argsort():
    """_rank_order (the hot path's sort-free ordering primitive) must
    reproduce jnp.argsort(stable=True) exactly, including ties (slots
    from one add_commitment share a seq; idle executors share BIG_SEQ
    keys)."""
    import jax.numpy as jnp

    from sparksched_tpu.env.core import _rank_order

    rng = np.random.default_rng(0)
    for n in (1, 4, 10, 16):
        for _ in range(20):
            key = jnp.asarray(
                rng.integers(0, max(2, n // 2), size=n), jnp.int32
            )
            got = np.asarray(_rank_order(key))
            want = np.asarray(jnp.argsort(key, stable=True))
            np.testing.assert_array_equal(got, want)
    # float keys with INF padding (finish-time shaped)
    key = jnp.asarray([3.0, np.inf, 1.0, np.inf, 1.0], jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(_rank_order(key)),
        np.asarray(jnp.argsort(key, stable=True)),
    )


def indexed_pick(oh, x, axis=None):
    """The read `core._pick` stands for, as the engine made it until
    PR 51: `x` at the index the one-hot marks, by an indexed read
    (under `jax.vmap` a gather). A mask that marks nothing reads index
    0, as the reads that clamped a -1 sentinel did. The leaf-for-leaf
    tests of a sweep chunk and of a sync collection put it in
    `_pick`'s place (tests/test_sweep.py, tests/test_trainers.py)."""
    import jax.numpy as jnp

    if axis is None:
        oh = jnp.broadcast_to(oh, x.shape)
        return x.reshape(-1)[jnp.argmax(oh.reshape(-1))]
    assert axis == 0 and oh.shape[1:] == (1,) * (x.ndim - 1), oh.shape
    return x[jnp.argmax(oh.reshape(-1))]


def swap_in_the_reads_replaced(monkeypatch, which, num_executors):
    """Put back what a `perf_opt` took out of the engine, for a
    leaf-for-leaf comparison: "tables", PR 50's sampler that read the
    bank's `level_present`, `max_present` and three counts a duration
    (`tests/test_bulk_pass_setup.py` keeps it); "indexed reads", the
    indexed read of a lane's own state in place of PR 51's pick by
    one-hot (`indexed_pick`, above; a sentinel's
    all-false mask then reads element 0 and not 0 / False, which is
    how this shows that every such value is masked). Returns a
    function that says how often the stand-in was traced."""
    from sparksched_tpu.env import core, flat_loop

    from .test_bulk_pass_setup import table_reading_sampler

    if which == "tables":
        reference = table_reading_sampler(num_executors)
        monkeypatch.setattr(core, "sample_task_duration", reference)
        return lambda: reference.traced
    assert which == "indexed reads"
    traced = []

    def pick(oh, x, axis=None):
        traced.append(x.shape)
        return indexed_pick(oh, x, axis)

    monkeypatch.setattr(core, "_pick", pick)
    monkeypatch.setattr(flat_loop, "_pick", pick)
    return lambda: len(traced)


def _pick_operand(shape, dtype):
    rng = np.random.default_rng(51)
    if dtype == "bool":
        return rng.integers(0, 2, size=shape).astype(bool)
    if dtype == "float32":
        x = rng.normal(size=shape).astype(np.float32) * 1e4
        flat = x.reshape(-1)
        flat[::3] = np.inf  # the engine's pending times are INF-padded
        flat[1] = -0.5
        return x
    hi = {"int32": 2**31 - 1, "uint32": 2**32 - 1}[dtype]
    x = rng.integers(0, hi, size=shape, dtype=np.int64)
    if dtype == "int32":
        x.reshape(-1)[::2] *= -1  # -1 sentinels and below
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", ["int32", "uint32", "float32", "bool"])
@pytest.mark.parametrize(
    "shape", [(6, 20), (6,), (10,)], ids=["JS", "J", "N"])
def test_pick_is_the_indexed_read_at_every_index(shape, dtype):
    """`core._pick` against `x[i]` / `x[j, s]` over EVERY in-range
    index of a `[J,S]`, `[J]` and `[N]` array of each kind the state
    holds (int32 with negatives, the uint32 words, float32 with `inf`,
    flags), under `jax.vmap` as the engine runs it, bit for bit."""
    import jax.numpy as jnp

    from sparksched_tpu.env.core import _onehot, _onehot2, _pick

    x = jnp.asarray(_pick_operand(shape, dtype))
    if len(shape) == 2:
        j, s = (a.reshape(-1) for a in np.indices(shape))
        got = jax.vmap(
            lambda j, s: _pick(_onehot2(*shape, j, s), x))(j, s)
        want = np.asarray(x)[j, s]
    else:
        i = np.arange(shape[0])
        got = jax.vmap(lambda i: _pick(_onehot(shape[0], i), x))(i)
        want = np.asarray(x)[i]
    got = np.asarray(got)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    ref = jax.vmap(
        lambda oh: indexed_pick(oh, x))(jnp.eye(x.size, dtype=bool).reshape(
            (x.size,) + shape))
    np.testing.assert_array_equal(np.asarray(ref), want)


@pytest.mark.parametrize("dtype", ["int32", "uint32", "float32", "bool"])
def test_pick_of_a_row_is_the_indexed_row(dtype):
    """The row form: `_pick(oj[:, None], x, axis=0)` is `x[j]` for every
    job of a `[J,S]` array, and over the `[J,W,S]` packed parent sets
    (`oj[:, None, None]`) a job's `[W,S]` words."""
    import jax.numpy as jnp

    from sparksched_tpu.env.core import _onehot, _pick

    x = jnp.asarray(_pick_operand((6, 20), dtype))
    rows = jax.vmap(
        lambda j: _pick(_onehot(6, j)[:, None], x, axis=0))(np.arange(6))
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(x))
    assert rows.dtype == x.dtype
    ref = jax.vmap(lambda j: indexed_pick(
        _onehot(6, j)[:, None], x, axis=0))(np.arange(6))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(x))
    if dtype == "uint32":
        sets = jnp.asarray(_pick_operand((6, 2, 20), dtype))
        words = jax.vmap(lambda j: _pick(
            _onehot(6, j)[:, None, None], sets, axis=0))(np.arange(6))
        np.testing.assert_array_equal(np.asarray(words), np.asarray(sets))


@pytest.mark.parametrize("index", [-1, 6, 2**31 - 1])
def test_pick_out_of_range_reads_zero_where_the_read_wraps_or_clamps(
    index,
):
    """What an index outside the array gives: the one-hot marks
    nothing, so a pick reads 0 / False (0.0 even beside an `inf`),
    where the indexed read of a traced index wraps a negative one to
    the end and clamps one past it. Every caller of `_pick` whose
    index can be a sentinel masks the value (core.py says where)."""
    import jax.numpy as jnp

    from sparksched_tpu.env.core import _onehot, _onehot2, _pick

    for dtype in ("int32", "uint32", "float32", "bool"):
        x = jnp.asarray(_pick_operand((6,), dtype))
        x = x.at[-1].set(np.asarray(True if dtype == "bool" else 7, dtype))
        i = jnp.int32(index)
        got = jax.jit(lambda i: _pick(_onehot(6, i), x))(i)
        assert got.dtype == x.dtype and not got.any(), (dtype, got)
        assert jax.jit(lambda i: x[i])(i) == x[-1]  # the read it replaced
        g = jnp.asarray(_pick_operand((6, 20), dtype))
        assert not _pick(_onehot2(6, 20, i, jnp.int32(3)), g).any()
        past_s = jnp.int32(20 if index == 6 else index)
        assert not _pick(_onehot2(6, 20, jnp.int32(3), past_s), g).any()
        row = _pick(_onehot(6, i)[:, None], g, axis=0)
        assert row.shape == (20,) and not row.any()


def test_parent_set_reads_are_the_adjacency_s(small_setup):
    """What the drain body's fixed part reads of the adjacency it reads
    off the packed parent sets: `_children(_job_parent_sets(state, oj),
    os_)` is `adj[j, s]` and `_unpack_parents` of a job's sets `adj[j]`,
    for every (job, stage) of a reset state, and at a stage axis over
    one word (S = 40: two words a set)."""
    import jax.numpy as jnp

    from sparksched_tpu.env.core import (
        _children,
        _job_parent_sets,
        _onehot,
        _unpack_parents,
        pack_parents,
    )

    params, bank = small_setup
    state = reset(params, bank, jax.random.PRNGKey(3))
    adj = np.asarray(state.adj)
    assert adj.any()
    j_cap, s_cap = adj.shape[:2]
    j, s = (a.reshape(-1) for a in np.indices((j_cap, s_cap)))

    def reads(state, s_cap):
        return jax.vmap(lambda j, s: (
            _children(_job_parent_sets(state, _onehot(j_cap, j)),
                      _onehot(s_cap, s)),
            _unpack_parents(
                _job_parent_sets(state, _onehot(j_cap, j)), s_cap),
        ))

    rows, blocks = reads(state, s_cap)(j, s)
    np.testing.assert_array_equal(np.asarray(rows), adj[j, s])
    np.testing.assert_array_equal(np.asarray(blocks), adj[j])

    wide = np.zeros((j_cap, 40, 40), bool)
    wide[:, :s_cap, :s_cap] = adj
    wide[:, 33, 39] = wide[:, 2, 35] = wide[:, 31, 32] = True
    wide_state = state.replace(
        adj=jnp.asarray(wide), parent_sets=pack_parents(jnp.asarray(wide)))
    assert wide_state.parent_sets.shape == (j_cap, 2, 40)
    j, s = (a.reshape(-1) for a in np.indices((j_cap, 40)))
    rows, blocks = reads(wide_state, 40)(j, s)
    np.testing.assert_array_equal(np.asarray(rows), wide[j, s])
    np.testing.assert_array_equal(np.asarray(blocks), wide[j])
