"""Harness-level tests: example episodes, renderer output, config loader,
and the driver entry points."""

from __future__ import annotations

import os.path as osp

import pytest


@pytest.mark.slow
def test_examples_fair_episode(tmp_path, monkeypatch):
    import examples

    monkeypatch.chdir(tmp_path)
    sched = examples.make_scheduler("fair", None)
    avg = examples.run_episode(sched, seed=0, render=True, max_steps=4000)
    assert avg > 0
    assert osp.isfile(osp.join(tmp_path, "screenshot.png"))


def test_renderer_live_mode_refreshes_frame(tmp_path):
    """Live render mode (reference render_frame analog): the on-disk
    frame must exist after `live_every` recorded decisions, well before
    the episode's final render call."""
    import jax

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.renderer import GanttRenderer
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(num_executors=3, max_jobs=2)
    bank = make_workload_bank(params.num_executors)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )
    state = core.reset(params, bank, jax.random.PRNGKey(0))
    frame = osp.join(tmp_path, "live.png")
    r = GanttRenderer(params.num_executors, live_path=frame, live_every=3)
    for _ in range(3):
        r.record(state)
    assert osp.isfile(frame)


def test_config_loader(tmp_path):
    import yaml

    from sparksched_tpu.config import env_params_from_cfg, load

    cfg_path = osp.join("/root/repo", "config", "decima_tpch.yaml")
    with open(cfg_path) as fp:
        cfg = yaml.safe_load(fp)
    # `health:` (ISSUE 9) ships enabled in the flagship config — the
    # self-healing runtime is the default for unattended chip windows
    assert set(cfg) == {"trainer", "agent", "env", "obs", "health"}
    params = env_params_from_cfg(cfg["env"])
    assert params.num_executors == 50
    assert params.max_jobs == 200  # from job_arrival_cap
    assert load(cfg_path) == cfg


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_is_placeable_from_outside(
        tmp_path, monkeypatch, from_env):
    """One cache for every entry point: `<checkout>/.jax_cache` by
    default; where JAX_COMPILATION_CACHE_DIR is set, jax reads it
    itself and the helper sets no directory in code (it would override
    the variable)."""
    import jax

    from sparksched_tpu.config import enable_compilation_cache

    updates: dict = {}
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    if from_env:
        monkeypatch.setenv(
            "JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside")
        )
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    enable_compilation_cache()
    assert updates.pop("jax_persistent_cache_min_compile_time_secs") == 1.0
    if from_env:
        assert updates == {}  # no directory was set in code
    else:
        assert updates == {"jax_compilation_cache_dir": osp.join(
            osp.dirname(osp.dirname(osp.abspath(__file__))), ".jax_cache"
        )}


@pytest.mark.slow
def test_graft_entry_compiles():
    import jax

    import __graft_entry__ as g

    fn, (params, feats) = g.entry()
    out = jax.jit(fn)(params, feats)
    jax.block_until_ready(out)
    stage_scores, exec_scores = out
    assert stage_scores.shape[:1] == exec_scores.shape[:1]


@pytest.mark.slow
def test_dryrun_multichip_8_devices():
    import jax

    import __graft_entry__ as g

    assert len(jax.devices()) >= 8  # conftest forces 8 virtual CPU devices
    g.dryrun_multichip(8)


def test_ppo_smoke_trains_on_flat_collector(tmp_path):
    """End-to-end PPO iteration at a tiny size: trajectories come from
    the flat micro-step engine's decision rows and the update must
    still move the parameters."""
    import jax
    import numpy as np

    from sparksched_tpu.trainers import make_trainer

    cfg = {
        "trainer": {
            "trainer_cls": "PPO",
            "num_iterations": 1,
            "num_sequences": 1,
            "num_rollouts": 2,
            "seed": 42,
            "artifacts_dir": str(tmp_path),
            "checkpointing_freq": 50,
            "use_tensorboard": False,
            "num_epochs": 2,
            "num_batches": 3,
            "clip_range": 0.2,
            "target_kl": 0.01,
            "entropy_coeff": 0.04,
            "beta_discount": 5.0e-3,
            "opt_kwargs": {"lr": 3.0e-4},
            "max_grad_norm": 0.5,
            "rollout_steps": 40,
        },
        "agent": {
            "agent_cls": "DecimaScheduler",
            "embed_dim": 8,
            "gnn_mlp_kwargs": {"hid_dims": [16, 8],
                               "act_cls": "LeakyReLU"},
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
    }
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
    assert changed, "flat-collector PPO update did not change parameters"


@pytest.mark.slow
def test_vector_env_steps_and_autoresets():
    import jax
    import numpy as np

    from sparksched_tpu.env.gym_compat import SparkSchedSimVectorEnv
    from sparksched_tpu.schedulers.heuristics import round_robin_policy

    B = 8
    cfg = {
        "num_executors": 5,
        "job_arrival_cap": 4,
        "moving_delay": 500.0,
        "warmup_delay": 200.0,
        "job_arrival_rate": 4.0e-5,
    }
    venv = SparkSchedSimVectorEnv(B, cfg)
    obs = venv.reset(seed=0)
    assert obs.schedulable.shape[0] == B

    pick = jax.jit(
        jax.vmap(
            lambda o: round_robin_policy(
                o, venv.params.num_executors, True
            )
        )
    )
    t_prev = np.zeros(B)
    completed = np.zeros(B, bool)
    for _ in range(600):
        si, ne = pick(obs)
        obs, r, term, trunc = venv.step(si, ne)
        t = np.asarray(venv.states.wall_time)
        assert np.all(np.isfinite(np.asarray(r)))
        completed |= np.asarray(term) | np.asarray(trunc)
        # auto-reset may rewind wall_time to 0; otherwise time is
        # monotone per lane
        assert np.all((t >= t_prev) | (t == 0.0))
        t_prev = t
        if completed.all():
            break
    # with a 4-job cap every lane finishes (and auto-resets) quickly
    assert completed.all()
