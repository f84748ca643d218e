"""Mesh-path sharding assertions (VERDICT r1 #7).

The dp-mesh path replaces the reference's multi-process rollout fan-out +
pipe scatter/gather (/root/reference/trainers/trainer.py:110-121,264-296).
These tests assert it is *really* distributed, not accidentally
replicated: rollout lanes land sharded across devices, the jitted update
contains cross-device collectives, and mesh-vs-no-mesh training computes
identical parameters (same seeds -> same program, different layout).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparksched_tpu.parallel import (
    DP_AXIS,
    lane_sharding,
    make_mesh,
    shard_lanes,
)


def _tiny_cfg(num_rollouts: int):
    return (
        {
            "agent_cls": "DecimaScheduler",
            "embed_dim": 8,
            "gnn_mlp_kwargs": {
                "hid_dims": [16, 8],
                "act_cls": "LeakyReLU",
                "act_kwargs": {"negative_slope": 0.2},
            },
            "policy_mlp_kwargs": {"hid_dims": [16, 16], "act_cls": "Tanh"},
        },
        {
            "num_executors": 4,
            "job_arrival_cap": 3,
            "moving_delay": 2000.0,
            "job_arrival_rate": 4.0e-5,
            "warmup_delay": 1000.0,
        },
        {
            "trainer_cls": "PPO",
            "num_iterations": 1,
            "num_sequences": 1,
            "num_rollouts": num_rollouts,
            "seed": 0,
            "use_tensorboard": False,
            "num_epochs": 1,
            "num_batches": 2,
            "beta_discount": 5.0e-3,
            "opt_kwargs": {"lr": 3.0e-4},
            "max_grad_norm": 0.5,
            "rollout_steps": 12,
        },
    )


def _make_trainer(num_rollouts: int, mesh=None):
    from sparksched_tpu.trainers.ppo import PPO

    agent, env, tr = _tiny_cfg(num_rollouts)
    return PPO(agent, env, tr, mesh=mesh)


def _lane_axes(spec) -> tuple:
    """Mesh axes the leading (lane) dimension is sharded over.

    `lane_sharding` builds `P(tuple(mesh.axis_names))`; older jax
    releases normalized a 1-tuple partition entry to the bare string,
    newer ones preserve the tuple — accept both spellings."""
    a = spec[0]
    return a if isinstance(a, tuple) else (a,)


@pytest.mark.parametrize(
    "n_dev",
    [2, pytest.param(4, marks=pytest.mark.slow),
     pytest.param(8, marks=pytest.mark.slow)],
)
def test_rollout_lanes_shard_across_devices(n_dev):
    assert len(jax.devices()) >= n_dev
    mesh = make_mesh(n_dev)
    trainer = _make_trainer(num_rollouts=n_dev)
    state = trainer.init_state()

    # _collect returns (rollout, env_states, telemetry) since the
    # observability round; telemetry is None here (obs_telemetry off)
    ro, _, _ = jax.jit(
        trainer._collect, out_shardings=(lane_sharding(mesh), None, None)
    )(state.params, state.iteration, state.rng, None)

    leaf = ro.reward  # [B, T]
    assert leaf.shape[0] == n_dev
    shards = leaf.addressable_shards
    assert len(shards) == n_dev
    # one lane per device, placed on distinct devices
    assert {s.data.shape[0] for s in shards} == {1}
    assert len({s.device.id for s in shards}) == n_dev
    # every leaf with a lane axis carries the dp sharding
    spec = leaf.sharding.spec
    assert DP_AXIS in _lane_axes(spec)


@pytest.mark.slow
def test_update_jaxpr_contains_cross_device_collectives():
    n_dev = 4
    mesh = make_mesh(n_dev)
    trainer = _make_trainer(num_rollouts=n_dev, mesh=mesh)
    state = trainer.init_state()
    ro, _, _ = trainer._collect_jit(
        state.params, state.iteration, state.rng, None
    )
    ro = shard_lanes(ro, mesh)

    lowered = trainer._update_jit.lower(state, ro)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert ("all-reduce" in hlo) or ("all-gather" in hlo), (
        "update program contains no cross-device collectives"
    )


@pytest.mark.slow
def test_mesh_and_single_device_updates_agree():
    n_dev = 4
    mesh = make_mesh(n_dev)

    results = {}
    init = {}
    for name, m in (("mesh", mesh), ("single", None)):
        trainer = _make_trainer(num_rollouts=n_dev, mesh=m)
        state = trainer.init_state()
        init[name] = jax.device_get(state.params)
        ro, _, _ = trainer._collect_jit(
            state.params, state.iteration, state.rng, None
        )
        if m is not None:
            ro = shard_lanes(ro, mesh)
        state, _ = trainer._update_jit(state, ro)
        results[name] = jax.device_get(state.params)

    # the shard-aligned update computes per-shard partial sums + psum
    # (that's what makes its per-device FLOPs scale 1/dp), which
    # reorders float additions vs the single-device program — and the
    # virtual-mesh collectives are not bitwise-deterministic across
    # runs — so elementwise tolerances on near-zero one-element biases
    # are the wrong assertion (Adam's rsqrt amplifies tiny gradient
    # deltas there). Assert the meaningful invariant instead: the two
    # programs take essentially the same optimization STEP — parameter
    # deltas nearly parallel and absolute drift bounded (2e-4, the
    # same class the 2-D mesh test below documents).
    def flat_delta(params, ref):
        return np.concatenate([
            (np.asarray(a) - np.asarray(b)).ravel()
            for a, b in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(ref),
            )
        ])

    d_mesh = flat_delta(results["mesh"], init["mesh"])
    d_single = flat_delta(results["single"], init["single"])
    assert np.abs(d_single).max() > 1e-5, "single-device update was a no-op"
    cos = float(
        (d_mesh @ d_single)
        / (np.linalg.norm(d_mesh) * np.linalg.norm(d_single) + 1e-12)
    )
    assert cos > 0.999, f"update directions diverge: cos={cos}"
    np.testing.assert_array_less(
        np.abs(d_mesh - d_single).max(), 2e-4,
        err_msg="mesh-vs-single parameter drift exceeds the documented "
        "reordering class",
    )


@pytest.mark.slow
def test_host_device_mesh_shards_and_matches_single_device():
    """2-D ("host", "dp") mesh (virtual multi-host): lanes spread over
    all 8 devices of a 2x4 grid, the update still reduces across the
    full mesh, and parameters equal the single-device run."""
    from sparksched_tpu.parallel import make_host_device_mesh

    mesh = make_host_device_mesh(2, 4)
    assert mesh.shape == {"host": 2, "dp": 4}

    trainer = _make_trainer(num_rollouts=8, mesh=mesh)
    state = trainer.init_state()
    ro, _, _ = trainer._collect_jit(
        state.params, state.iteration, state.rng, None
    )
    ro = shard_lanes(ro, mesh)
    leaf = ro.reward
    assert len(leaf.addressable_shards) == 8
    assert len({s.device.id for s in leaf.addressable_shards}) == 8

    state2, _ = trainer._update_jit(state, ro)

    single = _make_trainer(num_rollouts=8, mesh=None)
    sstate = single.init_state()
    sro, _, _ = single._collect_jit(
        sstate.params, sstate.iteration, sstate.rng, None
    )
    sstate, _ = single._update_jit(sstate, sro)

    # hierarchical (host-then-device) reductions reorder float sums
    # relative to the single-device program; after one Adam step with
    # advantage normalization the drift reaches ~6e-5 abs / ~6e-3 rel
    # on a few elements — looser tolerance than the 1-D mesh test
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(state2.params)),
        jax.tree_util.tree_leaves(jax.device_get(sstate.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-4)


def test_shard_lanes_places_every_leaf():
    mesh = make_mesh(8)
    tree = {
        "a": jnp.zeros((16, 3)),
        "b": jnp.ones((16,), jnp.int32),
    }
    out = shard_lanes(tree, mesh)
    for leaf in jax.tree_util.tree_leaves(out):
        assert len(leaf.addressable_shards) == 8
        assert DP_AXIS in _lane_axes(leaf.sharding.spec)


# ---------------------------------------------------------------------------
# ISSUE 6: the sharded flat collector — step-exact, 1/dp work,
# census-pinned collectives
# ---------------------------------------------------------------------------


def _make_flat_trainer(num_rollouts: int, mesh=None):
    from sparksched_tpu.trainers.ppo import PPO

    agent, env, tr = _tiny_cfg(num_rollouts)
    tr = tr | {"rollout_steps": 8}
    return PPO(agent, env, tr, mesh=mesh)


@pytest.fixture(scope="module")
def flat_dp_pair():
    """dp=1 and dp=8 trainers over the same 16-lane config, with their AOT-compiled collect programs and one executed
    rollout each (shared across the parity / FLOPs / census tests —
    the two collect compiles are the expensive part)."""
    out = {}
    for dp in (1, 8):
        t = _make_flat_trainer(16, mesh=make_mesh(dp))
        s = t.init_state()
        comp = t._collect_jit.lower(
            s.params, s.iteration, s.rng, None
        ).compile()
        ro, _, _ = comp(s.params, s.iteration, s.rng, None)
        out[dp] = {"trainer": t, "state": s, "compiled": comp, "ro": ro}
    return out


def test_flat_collect_dp8_step_exact(flat_dp_pair):
    """The lane-sharded single-eval collector is STEP-EXACT vs dp=1 at
    fixed seeds: collection is embarrassingly parallel along lanes (the
    only cross-lane op is the compaction predicate, an integer max), so
    sharding must not change a single recorded bit — same actions,
    log-probs, rewards, wall times, valid mask, same final EnvState."""
    ro1 = jax.device_get(flat_dp_pair[1]["ro"])
    ro8 = jax.device_get(flat_dp_pair[8]["ro"])
    leaves1, treedef1 = jax.tree_util.tree_flatten(ro1)
    leaves8, treedef8 = jax.tree_util.tree_flatten(ro8)
    assert treedef1 == treedef8
    for a, b in zip(leaves1, leaves8):
        np.testing.assert_array_equal(a, b)
    # and at least one lane actually decided something
    assert ro8.valid.any()


def test_flat_collect_flops_scale_1_over_dp(flat_dp_pair):
    """XLA cost-analysis FLOPs are per-device for an SPMD program: the
    dp=8 collect must do <= 1.1x of (dp=1 FLOPs)/8 per device — the
    quantitative scaling claim (ROADMAP item 1), asserted, not
    gate-checked. Also pins that the rollout really landed sharded."""
    from sparksched_tpu.parallel import compiled_flops

    f1 = compiled_flops(flat_dp_pair[1]["compiled"])
    f8 = compiled_flops(flat_dp_pair[8]["compiled"])
    assert f1 > 0 and f8 > 0, "cost_analysis returned no flops"
    assert f8 <= 1.1 * f1 / 8, (
        f"per-device collect FLOPs {f8} exceed 1.1x of dp=1/8 "
        f"({f1 / 8:.0f}) — the sharded collect is doing replicated work"
    )
    leaf = flat_dp_pair[8]["ro"].reward
    assert len(leaf.addressable_shards) == 8
    assert len({s.device.id for s in leaf.addressable_shards}) == 8


def test_update_collective_census_reduction_families_only(flat_dp_pair):
    """The optimized dp=8 update HLO contains ONLY the reduction
    collectives (all-reduce for the gradient psum + advantage
    normalization, all-gather/reduce-scatter re-associations). An
    all-to-all or collective-permute means the minibatch permutation
    stopped being shard-aligned and every grad step now reshuffles the
    rollout across chips — the exact regression the fold_in key
    derivation in trainers/ppo.py exists to prevent."""
    from sparksched_tpu.parallel import (
        EXPECTED_UPDATE_COLLECTIVES,
        FORBIDDEN_UPDATE_COLLECTIVES,
        collective_census,
    )

    t, s = flat_dp_pair[8]["trainer"], flat_dp_pair[8]["state"]
    hlo = t._update_jit.lower(s, flat_dp_pair[8]["ro"]).compile().as_text()
    census = collective_census(hlo)
    assert census, "sharded update lowered with no collectives at all"
    assert set(census) <= EXPECTED_UPDATE_COLLECTIVES, (
        f"unexpected collectives in the update HLO: {census}"
    )
    assert not (set(census) & FORBIDDEN_UPDATE_COLLECTIVES), census


def test_mesh_from_config():
    from sparksched_tpu.parallel import mesh_from_config

    assert mesh_from_config(None) is None
    assert mesh_from_config({}) is None
    assert mesh_from_config({"dp": 1}) is None
    assert mesh_from_config({"dp": 4}).size == 4
    assert mesh_from_config({"dp": "auto"}).size == len(jax.devices())


def test_lane_fit_mesh_answers_per_device_budget():
    """obs/memory.py lane_fit with `mesh`: candidates stay global lane
    counts but the byte model is evaluated per shard against a
    per-chip budget — a width that cannot fit one device fits an
    8-way mesh."""
    from sparksched_tpu.obs.memory import lane_fit

    def fn(x):  # one ~4 MB intermediate per lane
        return jnp.outer(x, x).sum()

    args = (jax.ShapeDtypeStruct((1024,), jnp.float32),)
    budget = 50_000_000
    f1 = lane_fit(fn, args, candidates=(64,), budget_bytes=budget)
    f8 = lane_fit(fn, args, candidates=(64,), budget_bytes=budget,
                  mesh=8)
    assert not f1["candidates"][0]["fits"]
    assert f8["candidates"][0]["fits"]
    assert f8["candidates"][0]["lanes_per_device"] == 8
    assert f8["dp"] == 8 and f8["max_lanes_fit"] == 64
