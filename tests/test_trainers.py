"""Training-layer tests: returns/baseline math against straightforward
numpy replicas of the reference formulas, the moving-average ring buffer,
and end-to-end PPO/VPG smoke runs."""

from __future__ import annotations

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# returns (reference trainers/utils/returns_calculator.py)
# ---------------------------------------------------------------------------


def _ref_discounted(rewards, dts, beta):
    out = np.zeros(len(rewards))
    R = 0.0
    for k in reversed(range(len(rewards))):
        R = rewards[k] + np.exp(-beta * 1e-3 * dts[k]) * R
        out[k] = R
    return out


def _ref_differential(rewards, dts, avg_num_jobs):
    out = np.zeros(len(rewards))
    R = 0.0
    for k in reversed(range(len(rewards))):
        job_time = -rewards[k]
        R = -(job_time - dts[k] * avg_num_jobs) + R
        out[k] = R
    return out


def test_discounted_returns_matches_reference_formula():
    import jax.numpy as jnp

    from sparksched_tpu.trainers import discounted_returns, step_dts

    rng = np.random.default_rng(0)
    B, T = 3, 17
    walls = np.cumsum(rng.exponential(100, (B, T + 1)), axis=1).astype(
        np.float32
    )
    rewards = -rng.exponential(50, (B, T)).astype(np.float32)
    beta = 5e-3
    got = np.asarray(
        discounted_returns(
            jnp.asarray(rewards), step_dts(jnp.asarray(walls)), beta
        )
    )
    for b in range(B):
        want = _ref_discounted(
            rewards[b], np.diff(walls[b]), beta
        )
        np.testing.assert_allclose(got[b], want, rtol=1e-4)


def test_differential_returns_matches_reference_formula():
    import jax.numpy as jnp

    from sparksched_tpu.trainers import differential_returns

    rng = np.random.default_rng(1)
    B, T = 2, 9
    dts = rng.exponential(100, (B, T)).astype(np.float32)
    rewards = -rng.exponential(50, (B, T)).astype(np.float32)
    avg = 2.37
    got = np.asarray(
        differential_returns(
            jnp.asarray(rewards), jnp.asarray(dts), jnp.float32(avg)
        )
    )
    for b in range(B):
        np.testing.assert_allclose(
            got[b], _ref_differential(rewards[b], dts[b], avg), rtol=1e-4
        )


def test_avg_num_jobs_buffer_matches_circular_array():
    """Ring buffer == reference CircularArray semantics: moving window of
    the last `cap` dt>0 steps, avg = -sum(r)/sum(dt)."""
    import jax.numpy as jnp

    from sparksched_tpu.trainers import AvgNumJobsBuffer

    cap = 16
    buf = AvgNumJobsBuffer.create(cap)
    rng = np.random.default_rng(2)
    window = []  # reference window of (dt, r)
    for _ in range(5):
        m = int(rng.integers(3, 25))
        dts = rng.exponential(10, m)
        dts[rng.random(m) < 0.3] = 0.0  # some zero-duration steps
        rs = -rng.exponential(5, m)
        valid = rng.random(m) < 0.9
        buf = buf.extend(
            jnp.asarray(dts, jnp.float32), jnp.asarray(rs, jnp.float32),
            jnp.asarray(valid),
        )
        kept = [
            (d, r) for d, r, v in zip(dts, rs, valid) if v and d > 0
        ][-cap:]
        window = (window + kept)[-cap:]
        want = -sum(r for _, r in window) / sum(d for d, _ in window)
        np.testing.assert_allclose(
            float(buf.avg_num_jobs()), want, rtol=1e-5
        )


# ---------------------------------------------------------------------------
# baselines (reference trainers/utils/baselines.py)
# ---------------------------------------------------------------------------


def _ref_baseline(ts_list, ys_list):
    ts_unique = np.unique(np.hstack(ts_list))
    y_hats = np.vstack(
        [np.interp(ts_unique, ts, ys) for ts, ys in zip(ts_list, ys_list)]
    )
    baseline = {t: y.mean() for t, y in zip(ts_unique, y_hats.T)}
    return [np.array([baseline[t] for t in ts]) for ts in ts_list]


def test_group_baselines_matches_reference():
    import jax.numpy as jnp

    from sparksched_tpu.trainers import group_baselines

    rng = np.random.default_rng(3)
    G, R, T = 2, 3, 12
    walls = np.sort(
        rng.uniform(0, 1000, (G, R, T)).astype(np.float32), axis=-1
    )
    returns = rng.normal(size=(G, R, T)).astype(np.float32)
    valid = np.ones((G, R, T), bool)
    got = np.asarray(
        group_baselines(
            jnp.asarray(walls), jnp.asarray(returns), jnp.asarray(valid)
        )
    )
    for g in range(G):
        want = _ref_baseline(list(walls[g]), list(returns[g]))
        for r in range(R):
            np.testing.assert_allclose(got[g, r], want[r], rtol=1e-4,
                                       atol=1e-4)


def test_group_baselines_with_unequal_lengths():
    """Lanes of different valid lengths: a longer lane's baseline past a
    shorter lane's end uses the short lane's final return (np.interp
    right-extension), like the reference's unequal episode lengths."""
    import jax.numpy as jnp

    from sparksched_tpu.trainers import group_baselines

    T = 6
    walls = np.array(
        [[[0, 10, 20, 30, 40, 50], [0, 5, 15, 15, 15, 15]]],
        np.float32,
    )
    returns = np.array(
        [[[6, 5, 4, 3, 2, 1], [9, 8, 7, 0, 0, 0]]], np.float32
    )
    valid = np.array(
        [[[1, 1, 1, 1, 1, 1], [1, 1, 1, 0, 0, 0]]], bool
    )
    got = np.asarray(group_baselines(
        jnp.asarray(walls), jnp.asarray(returns), jnp.asarray(valid)
    ))
    want = _ref_baseline(
        [walls[0, 0], walls[0, 1, :3]], [returns[0, 0], returns[0, 1, :3]]
    )
    np.testing.assert_allclose(got[0, 0], want[0], rtol=1e-4)
    np.testing.assert_allclose(got[0, 1, :3], want[1], rtol=1e-4)


# ---------------------------------------------------------------------------
# end-to-end trainer smoke tests
# ---------------------------------------------------------------------------


def _mini_cfg(trainer_overrides=None, env_overrides=None):
    cfg = {
        "trainer": {
            "trainer_cls": "PPO",
            "num_iterations": 1,
            "num_sequences": 1,
            "num_rollouts": 2,
            "seed": 42,
            "artifacts_dir": "/tmp/sparksched_tpu_test_artifacts",
            "checkpointing_freq": 1,
            "use_tensorboard": False,
            "num_epochs": 2,
            "num_batches": 3,
            "clip_range": 0.2,
            "target_kl": 0.01,
            "entropy_coeff": 0.04,
            "beta_discount": 5.0e-3,
            "opt_cls": "Adam",
            "opt_kwargs": {"lr": 3.0e-4},
            "max_grad_norm": 0.5,
            "rollout_steps": 60,
        },
        "agent": {
            "agent_cls": "DecimaScheduler",
            "embed_dim": 8,
            "gnn_mlp_kwargs": {
                "hid_dims": [16, 8],
                "act_cls": "LeakyReLU",
                "act_kwargs": {"negative_slope": 0.2},
            },
            "policy_mlp_kwargs": {"hid_dims": [16, 16], "act_cls": "Tanh"},
        },
        "env": {
            "num_executors": 5,
            "job_arrival_cap": 3,
            "moving_delay": 2000.0,
            "mean_time_limit": 2.0e7,
            "job_arrival_rate": 4.0e-5,
            "warmup_delay": 1000.0,
        },
    }
    cfg["trainer"].update(trainer_overrides or {})
    cfg["env"].update(env_overrides or {})
    return cfg


@pytest.mark.parametrize("key,value", [
    ("rollout_engine", "core"),
    ("rollout_engine", "flat"),
    ("flat_single_eval", False),
    ("flat_micro_per_decision", 4.0),
    ("flat_event_burst", 4),
])
def test_removed_collector_key_fails_at_construction(key, value):
    """A config from before PR 32 that still chooses a collector is
    rejected by name (an old `rollout_engine: core` must not quietly
    train on the other engine), whatever value the key carries."""
    from sparksched_tpu.trainers import make_trainer

    with pytest.raises(ValueError, match=f"'{key}'.*MIGRATION.md"):
        make_trainer(_mini_cfg({key: value}))


@pytest.mark.slow
def test_ppo_trains_and_checkpoints(tmp_path):
    """Mirrors the reference's only test (test/test_train.py): a full
    train() run completes. Additionally asserts parameters changed and a
    checkpoint + resumable train state were written."""
    import os.path as osp

    import jax
    import numpy as np

    from sparksched_tpu.trainers import make_trainer

    cfg = _mini_cfg({"artifacts_dir": str(tmp_path)})
    t = make_trainer(cfg)
    p0 = jax.device_get(t.scheduler.params)
    state = t.train()
    p1 = jax.device_get(state.params)
    changed = any(
        not np.allclose(a, b)
        for a, b in zip(
            jax.tree_util.tree_leaves(p0), jax.tree_util.tree_leaves(p1)
        )
    )
    assert changed, "PPO update did not change any parameter"
    assert osp.isfile(osp.join(str(tmp_path), "checkpoints", "1",
                               "model.msgpack"))
    assert osp.isfile(osp.join(str(tmp_path), "train_state.msgpack"))
    # resume round-trip
    restored = t.load_train_state(
        osp.join(str(tmp_path), "train_state.msgpack")
    )
    assert int(restored.iteration) == 1


@pytest.mark.slow
def test_vpg_async_differential(tmp_path):
    import jax
    import numpy as np

    from sparksched_tpu.trainers import make_trainer

    cfg = _mini_cfg(
        {
            "trainer_cls": "VPG",
            "artifacts_dir": str(tmp_path),
            "rollout_duration": 2.0e6,
            "rollout_steps": 50,
            "reward_buff_cap": 4000,
        }
    )
    del cfg["trainer"]["beta_discount"]
    t = make_trainer(cfg)
    p0 = jax.device_get(t.scheduler.params)
    state = t.train()
    p1 = jax.device_get(state.params)
    changed = any(
        not np.allclose(a, b)
        for a, b in zip(
            jax.tree_util.tree_leaves(p0), jax.tree_util.tree_leaves(p1)
        )
    )
    assert changed


# ---------------------------------------------------------------------------
# async rollouts: group-shared job sequences across mid-scan resets
# (ADVICE r1: reset keys must derive from the group seq key + reset
# ordinal, not the per-lane policy rng chain)
# ---------------------------------------------------------------------------


def test_collect_async_group_shares_sequences_across_resets():
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.trainers.rollout import collect_async
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(
        num_executors=4, max_jobs=3, max_stages=20, max_levels=20,
        moving_delay=500.0, warmup_delay=200.0,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    master = jax.random.PRNGKey(7)
    seq_base = jax.random.fold_in(master, 0)  # one sequence group
    seq0 = jax.random.fold_in(seq_base, 0)  # initial reset ordinal 0

    T = 400
    ros = []
    for r in range(2):  # two lanes of the same group
        lane_salt = 1000 + r
        state = core.reset_pair(
            params, bank, seq0, jax.random.fold_in(seq0, lane_salt)
        )
        ro = collect_async(
            params, bank, pol,
            jax.random.fold_in(master, 100 + r),  # distinct policy chains
            T, state, 1e9, seq_base, lane_salt, 1,
        )
        ros.append(ro)

    # every lane must have auto-reset at least twice for the test to bite
    n_resets = [int(ro.resets.sum()) for ro in ros]
    assert min(n_resets) >= 2, n_resets

    # for equal reset ordinals the job sequence (template ids + arrival
    # count) must be identical across the group, even though the resets
    # happen at different scan steps in each lane
    for ordinal in range(2):
        tmpl = []
        for ro in ros:
            step_after = int(np.flatnonzero(np.asarray(ro.resets))[ordinal]) + 1
            assert step_after < T
            tmpl.append(np.asarray(ro.obs.job_template[step_after]))
        np.testing.assert_array_equal(tmpl[0], tmpl[1])

    # different groups draw different sequences at the same ordinal
    other_base = jax.random.fold_in(master, 1)
    oseq0 = jax.random.fold_in(other_base, 0)
    ostate = core.reset_pair(
        params, bank, oseq0, jax.random.fold_in(oseq0, 1000)
    )
    oro = collect_async(
        params, bank, pol, jax.random.fold_in(master, 200),
        T, ostate, 1e9, other_base, 1000, 1,
    )
    step_after = int(np.flatnonzero(np.asarray(oro.resets))[0]) + 1
    same = np.array_equal(
        np.asarray(oro.obs.job_template[step_after]),
        np.asarray(ros[0].obs.job_template[
            int(np.flatnonzero(np.asarray(ros[0].resets))[0]) + 1
        ]),
    )
    same_arrivals = np.array_equal(
        np.asarray(oro.final_state.job_arrival_time),
        np.asarray(ros[0].final_state.job_arrival_time),
    )
    assert not (same and same_arrivals)


def test_collect_flat_async_batch_group_sequences_budget_and_resume():
    """The streaming collector, `collect_flat_async_batch` (one policy
    evaluation per decision row, per-lane reset closures over
    seq_bases/lane_salts arrays): lanes sharing `seq_base` must replay
    identical job sequences at equal reset ordinals (the group-shared
    `fold_in(seq_base, reset_count + episodes)` scheme the critic-free
    baseline relies on), the sim-time budget must freeze lanes, and a
    second chunk resumed from the returned LoopState must keep
    collecting."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import init_loop_state
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
        def one(o):
            return round_robin_policy(o, params.num_executors, True)

        si, ne = jax.vmap(one)(obs)
        return si, ne, {}

    master = jax.random.PRNGKey(7)
    seq_base = jax.random.fold_in(master, 0)
    seq0 = jax.random.fold_in(seq_base, 0)
    T = 120
    lane_salts = jnp.asarray([1000, 1001], jnp.int32)
    states = jax.vmap(
        lambda salt: core.reset_pair(
            params, bank, seq0, jax.random.fold_in(seq0, salt)
        )
    )(lane_salts)
    ls0 = jax.vmap(init_loop_state)(states)
    seq_bases = jnp.stack([seq_base, seq_base])
    ro, ls = collect_flat_async_batch(
        params, bank, bpol, jax.random.fold_in(master, 100), T, ls0,
        1e9, seq_bases, lane_salts, jnp.asarray([1, 1], jnp.int32),
    )
    n_resets = [int(n) for n in np.asarray(ro.resets).sum(axis=1)]
    assert min(n_resets) >= 2, n_resets
    # lanes in the same group replay the same sequence at each ordinal
    for ordinal in range(2):
        tmpl = []
        for lane in range(2):
            idx = int(
                np.flatnonzero(np.asarray(ro.resets)[lane])[ordinal]
            ) + 1
            assert idx < T
            tmpl.append(np.asarray(ro.obs.job_template)[lane, idx])
        np.testing.assert_array_equal(tmpl[0], tmpl[1])
    np.testing.assert_array_equal(
        np.asarray(ro.final_reset_count),
        1 + np.asarray(n_resets),
    )

    # chunk 2 resumes from the returned LoopState and keeps collecting
    ro2, _ = collect_flat_async_batch(
        params, bank, bpol, jax.random.fold_in(master, 300), T, ls,
        1e9, seq_bases, lane_salts, ro.final_reset_count,
    )
    assert int(np.asarray(ro2.valid).sum()) > 0

    # sim-time budget freezes lanes near the boundary
    budget = 2.0e6
    ro3, _ = collect_flat_async_batch(
        params, bank, bpol, jax.random.fold_in(master, 400), T, ls0,
        jnp.float32(budget), seq_bases, lane_salts,
        jnp.asarray([1, 1], jnp.int32),
    )
    total = float(np.asarray(ro3.wall_times)[0, -1])
    assert total >= budget * 0.5, "budget never approached"
    unbudgeted = float(np.asarray(ro.wall_times)[0, -1])
    assert total < unbudgeted * 0.5, (
        f"budget freeze ineffective: {total} vs {unbudgeted}"
    )


@pytest.mark.slow
def test_stored_observation_roundtrip_is_exact():
    """An Observation rebuilt from a StoredObs must match the live one
    field-for-field on everything the models read (incl. the recomputed
    node_level) — else PPO's epoch-0 importance ratio drifts from 1."""
    import jax

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.observe import observe
    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.trainers.rollout import (
        store_obs,
        stored_to_observation,
    )
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(
        num_executors=4, max_jobs=5, max_stages=20, max_levels=20,
        moving_delay=500.0, warmup_delay=200.0,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )
    state = core.reset(params, bank, jax.random.PRNGKey(2))
    checked = 0
    for i in range(300):
        live = observe(params, state)
        rebuilt = stored_to_observation(bank, store_obs(live, state))
        for name in ("nodes", "node_mask", "job_mask", "schedulable",
                     "node_level", "exec_supplies",
                     "num_committable", "source_job"):
            np.testing.assert_array_equal(
                np.asarray(getattr(rebuilt, name)),
                np.asarray(getattr(live, name)),
                err_msg=f"{name} differs at step {i}",
            )
        # obs.adj is raw template adjacency on the live path (consumers
        # mask it — observe.py field note); compare the model-visible
        # masked form
        nm = np.asarray(live.node_mask)
        live_adj = (
            np.asarray(live.adj) & nm[:, :, None] & nm[:, None, :]
        )
        np.testing.assert_array_equal(
            np.asarray(rebuilt.adj), live_adj,
            err_msg=f"masked adj differs at step {i}",
        )
        checked += 1
        si, ne = round_robin_policy(live, params.num_executors, True)
        state, _, term, trunc = core.step(params, bank, state, si, ne)
        if bool(term) or bool(trunc):
            break
    assert checked > 30


@pytest.mark.parametrize("chunk_samples", [24, 8])
def test_ppo_update_in_chunks_matches_whole_minibatch(
    tmp_path, monkeypatch, chunk_samples
):
    """A minibatch evaluated in chunks of its time slots, with the
    chunks' gradients summed, is the update on the whole minibatch up
    to the order of the sums: the same minibatches reach the optimizer,
    the same losses, and (under SGD, whose step is linear in the
    gradient) the same parameters. The first minibatch, evaluated at
    the collector's own parameters, reproduces its log-probs."""
    import jax

    from sparksched_tpu.trainers import make_trainer, ppo

    cfg = _mini_cfg({
        "artifacts_dir": str(tmp_path), "num_epochs": 2,
        "num_batches": 2, "opt_cls": "SGD", "rollout_steps": 48,
    })

    def update(limit):
        monkeypatch.setattr(ppo, "CHUNK_SAMPLES", limit)
        t = make_trainer(cfg)
        state = t.init_state()
        state = state.replace(rng=jax.random.fold_in(state.rng, 0))
        ro, _, _ = t._collect_jit(
            state.params, state.iteration, state.rng, None
        )
        # 24 slots a minibatch: one chunk, 2 chunks of 12, 6 of 4
        assert ro.reward.shape == (2, 48)
        new, stats = t._update_jit(state, ro)
        return (
            np.concatenate([
                np.asarray(x).ravel()
                for x in jax.tree_util.tree_leaves(new.params)
            ]),
            {k: float(v) for k, v in stats.items() if v is not None},
        )

    whole, want = update(10**9)
    parts, got = update(chunk_samples)
    assert got["minibatches_applied"] == want["minibatches_applied"] == 4
    assert want["approx_kl_first"] < 1e-9 and got["approx_kl_first"] < 1e-9
    for k in ("policy_loss", "entropy", "approx_kl_div"):
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4, atol=1e-8)
    np.testing.assert_allclose(parts, whole, rtol=0, atol=1e-7)


# ---------------------------------------------------------------------------
# streaming counters (PR 30): `reseeds`, `reset_evals`, `rows_frozen`
# ---------------------------------------------------------------------------


def _stream_fixture():
    """Two persistent lanes of one sequence group on a 4-executor,
    3-job cluster under a round-robin policy, as the batch test above."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.schedulers.heuristics import round_robin_policy
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

    seq_base = jax.random.fold_in(jax.random.PRNGKey(7), 0)
    seq0 = jax.random.fold_in(seq_base, 0)
    salts = jnp.asarray([1000, 1001], jnp.int32)
    states = jax.vmap(
        lambda salt: core.reset_pair(
            params, bank, seq0, jax.random.fold_in(seq0, salt)
        )
    )(salts)
    return params, bank, bpol, states, jnp.stack([seq_base] * 2), salts


def _assert_leaf_equal(a, b):
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("budget", [1e9, 2.0e6], ids=["row_cap", "budget"])
def test_streaming_counters_against_a_hand_count(budget):
    """With a telemetry carry the streaming rollout and the carry it
    hands on are leaf-equal to those without; `reseeds` is the lane's
    flagged resets, `rows_frozen` the rows it sat out with the budget
    spent (the scan's rows less the rows it decided in), and
    `reset_evals` a fact of the batch, the same in every lane: the rows
    in which the re-seed after the drain ran, which are the rows in
    which an unfrozen lane flagged a reset (PR 31; until then one per
    micro-step the lane took)."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env.flat_loop import init_loop_state
    from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros_like
    from sparksched_tpu.trainers.rollout import collect_flat_async_batch

    params, bank, bpol, states, bases, salts = _stream_fixture()
    steps, ones = 120, jnp.ones((2,), jnp.int32)
    args = (params, bank, bpol, jax.random.PRNGKey(100), steps,
            jax.vmap(init_loop_state)(states), jnp.float32(budget),
            bases, salts, ones)
    ro0, ls0 = collect_flat_async_batch(*args)
    ro, ls, tm = collect_flat_async_batch(
        *args, telemetry_zeros_like((2,)))
    _assert_leaf_equal((ro0, ls0), (ro, ls))

    decided = np.asarray(ro.valid).sum(axis=1)
    flagged = np.asarray(ro.resets).sum(axis=1)
    frozen = steps - decided  # a live lane decides in every row
    assert np.asarray(tm.reseeds).tolist() == flagged.tolist()
    if budget >= 1e9:
        assert flagged.min() >= 2, "no episode ended in the scan"
    assert np.asarray(tm.rows_frozen).tolist() == frozen.tolist()
    assert (frozen > 0).all() == (budget < 1e9)
    # a lane's resets are stored at the slot of the decision whose span
    # ended the episode: its k-th row, since a live lane decides in
    # every row. Frozen lanes store nothing, so this is the unfrozen
    # lanes' flags by row
    by_row = np.zeros((steps,), bool)
    for lane in np.asarray(ro.resets):
        by_row |= lane
    want = int(by_row.sum())
    assert np.asarray(tm.reset_evals).tolist() == [want, want]
    assert want <= flagged.sum() < np.asarray(tm.drain_iters).min()
    assert (want > 0) == (flagged.sum() > 0)
    assert np.asarray(tm.decide_steps).tolist() == decided.tolist()
    s = summarize(tm)
    assert s["reseeds_total"] == int(flagged.sum())
    assert s["reset_evals_total"] == 2 * want
    assert s["row"]["lane_rows_frozen"] == int(frozen.sum())
    assert s["row"]["lane_rows"] == 2 * steps


def test_reseed_after_the_drain_equals_the_reset_in_every_micro_step():
    """The oracle of PR 31's deferral. Three lanes of the streaming
    fixture, a decision row at a time, two ways. The micro-step way,
    which stays for the loops whose unit is the micro-step: the decide
    step, then `drain_micro_step(auto_reset=True, masked=True)` with the
    collector's reset programs until every lane is ready to decide, the
    tail re-seeding in the body. And the row as the streaming collector
    runs it: `drain_to_decision(auto_reset=True)`, whose loop never
    re-seeds and which re-seeds once, after it, under one predicate for
    the batch. The `LoopState` and the row's `(reward, dt, reset)` are
    leaf-equal after every row, a lane frozen as the collector freezes
    it included, over at least two episode ends a lane."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import (
        M_DECIDE,
        decide_micro_step,
        drain_micro_step,
        drain_to_decision,
        init_loop_state,
    )
    from sparksched_tpu.env.observe import observe
    from sparksched_tpu.trainers.rollout import _group_reset_fns

    params, bank, bpol, _, bases, _ = _stream_fixture()
    lanes, rows, freeze_from = 3, 150, 110
    bases = jnp.stack([bases[0]] * lanes)
    salts = jnp.asarray([1000, 1001, 1002], jnp.int32)
    counts = jnp.asarray([1, 1, 5], jnp.int32)
    seq0 = jax.random.fold_in(bases[0], 0)
    ls = jax.vmap(init_loop_state)(jax.vmap(
        lambda salt: core.reset_pair(
            params, bank, seq0, jax.random.fold_in(seq0, salt))
    )(salts))
    reset_fns = _group_reset_fns(params, bank)
    lane_args = (bases, counts, salts)

    @jax.jit
    def decide(ls, over):
        obs = jax.vmap(lambda e: observe(params, e))(ls.env)
        si, ne, _ = bpol(None, obs)
        ls2, rec = jax.vmap(
            lambda l, s_, n_: decide_micro_step(
                params, bank, l, s_, n_, True, t_ref=l.env.wall_time)
        )(ls, si, ne)
        # the collector's mask: a frozen lane sits the drain out
        return ls2.replace(mode=jnp.where(over, M_DECIDE, ls2.mode)), rec

    @jax.jit
    def drain_step(ls, keys, t_ref):
        return jax.vmap(
            lambda l, k, i, t: drain_micro_step(
                params, bank, l, k, True, reset_fn=reset_fns(i), t_ref=t,
                masked=True)
        )(ls, keys, lane_args, t_ref)

    @jax.jit
    def drain_row(ls, keys, t_ref):
        return jax.vmap(
            lambda l, k, i, t: drain_to_decision(
                params, bank, l, k, True, reset_fn=reset_fns(i), t_ref=t,
                lane_axis="lanes"),
            axis_name="lanes",
        )(ls, keys, lane_args, t_ref)

    def freeze(over, old, new):
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(
                over.reshape(over.shape + (1,) * (a.ndim - 1)), a, b),
            old, new)

    ends = np.zeros((lanes,), int)
    bodies = 0
    for row in range(rows):
        over = jnp.asarray([False, False, row >= freeze_from])
        keys = jax.random.split(jax.random.PRNGKey(row), lanes)
        t_ref = ls.env.wall_time
        ls2, (decided, rw1, dt1, rs1) = decide(ls, over)
        assert not np.asarray(rs1).any()  # a decide step ends no episode
        assert np.asarray(decided).all()

        new_ls, (rw, dt, rs) = drain_row(ls2, keys, t_ref)
        new = (freeze(over, ls, new_ls), rw1 + rw, dt1 + dt, rs1 | rs)

        old_ls = ls2
        rw, dt = jnp.zeros((lanes,)), jnp.zeros((lanes,))
        rs = jnp.zeros((lanes,), bool)
        while (np.asarray(old_ls.mode) != M_DECIDE).any():
            old_ls, (r, d, re) = drain_step(old_ls, keys, t_ref)
            rw, dt, rs = rw + r, dt + d, rs | re
            bodies += 1
            assert bodies < 40 * rows, "a lane is stuck"
        old = (freeze(over, ls, old_ls), rw1 + rw, dt1 + dt, rs1 | rs)

        _assert_leaf_equal(old, new)
        ls = new[0]
        assert (np.asarray(ls.mode) == M_DECIDE).all()
        ends += np.asarray(new[3] & ~over)
    assert ends.min() >= 2, ends
    assert bodies > 3 * rows
    # the frozen lane's state stood still from the row it froze in
    assert np.asarray(ls.episodes).tolist() == ends.tolist()


def test_sync_rollout_is_leaf_equal_under_telemetry_and_counts_no_stream():
    """Sync mode: no reset program in the scan, no budget, so the three
    streaming counters stay 0 (and `summarize` still prints them), and
    the telemetry carry leaves the rollout as it is."""
    import jax

    from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros_like
    from sparksched_tpu.trainers.rollout import collect_flat_sync_batch

    params, bank, bpol, states, _, _ = _stream_fixture()
    args = (params, bank, bpol, jax.random.PRNGKey(100), 60, states)
    ro0 = collect_flat_sync_batch(*args)
    ro, tm = collect_flat_sync_batch(*args, telemetry_zeros_like((2,)))
    _assert_leaf_equal(ro0, ro)
    for name in ("reseeds", "reset_evals", "rows_frozen"):
        assert not np.asarray(getattr(tm, name)).any(), name
    s = summarize(tm)
    assert (s["reseeds_total"], s["reset_evals_total"],
            s["row"]["lane_rows_frozen"]) == (0, 0, 0)
    assert s["decisions"] == int(np.asarray(ro.valid).sum())


@pytest.fixture(scope="module")
def sparse_collection():
    """A sync collection over a bank with executor levels and whole
    waves missing: `(params, bank, collect, got)`, `collect()` the
    collector traced anew (whatever the engine's helpers are at that
    moment) and run, `got` its result with the helpers as they are."""
    import jax

    from sparksched_tpu.env import core
    from sparksched_tpu.trainers.rollout import collect_flat_sync_batch

    from .test_bulk_pass_setup import sparse_bank

    params, _, bpol, _, _, salts = _stream_fixture()
    bank = sparse_bank(params.num_executors, params.max_stages)
    seq0 = jax.random.fold_in(jax.random.PRNGKey(7), 0)
    states = jax.vmap(lambda salt: core.reset_pair(
        params, bank, seq0, jax.random.fold_in(seq0, salt)))(salts)

    def collect():
        # a function and a jit of its own a side: the sampler and the
        # pick are no key of a jit's cache
        def collector(*args):
            return collect_flat_sync_batch.__wrapped__(*args)

        return jax.device_get(jax.jit(collector, static_argnums=(0, 2, 4))(
            params, bank, bpol, jax.random.PRNGKey(100), 60, states))

    return params, bank, collect, collect()


@pytest.mark.parametrize("replaced", ["tables", "indexed reads"])
def test_sync_collection_equals_the_one_collected_by_the_reads_replaced(
    sparse_collection, monkeypatch, replaced,
):
    """A sync collection over a bank with executor levels and whole
    waves missing is, leaf for leaf (every stored observation, action,
    reward and time, and the final state, which holds each lane's
    words of its templates), the one collected (PR 50, "tables") with
    the sampler that read the bank's `level_present`, `max_present`
    and three counts a duration, and (PR 51, "indexed reads") with
    every pick by one-hot of the decide step, the drain body and the
    early-exit loop made as the indexed read it replaced
    (`tests/test_env_core.py` keeps both stand-ins)."""
    from sparksched_tpu.workload.sampling import pack_duration_facts

    from .test_env_core import swap_in_the_reads_replaced

    params, bank, collect, got = sparse_collection
    traced = swap_in_the_reads_replaced(
        monkeypatch, replaced, params.num_executors)
    want = collect()
    # the passes sampled by it; the forty-odd reads of a body and a row
    assert traced() >= (3 if replaced == "tables" else 40), traced()
    _assert_leaf_equal(got, want)
    assert got.valid.sum() > 40 and len(np.unique(got.wall_times)) > 40
    final = got.final_state
    np.testing.assert_array_equal(
        final.duration_facts,
        np.asarray(pack_duration_facts(bank))[final.job_template])