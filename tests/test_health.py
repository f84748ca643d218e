"""Self-healing runtime (ISSUE 9): in-JIT health sentinels, the PPO
skip gate, checkpoint atomicity/fallback, resume bit-exactness, and
the tier-1 chaos-drill smoke.

The full drill matrix (all six fault classes end-to-end) is the
slow-marked test at the bottom; tier-1 runs the unit sentinels plus the
two recovery paths the ISSUE pins for CI (NaN-grad recovery and
corrupt-checkpoint fallback)."""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from .reference_fixtures import make_tpu_env_state, spec_multi_job

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree_equal(a, b) -> bool:
    import jax

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


# ---------------------------------------------------------------------------
# sentinel units: every bit fires on its seeded corruption, and only then
# ---------------------------------------------------------------------------


def test_state_health_bits_fire_on_seeded_corruptions():
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env import health as H

    params, bank, st = make_tpu_env_state(spec_multi_job(3, 5), 4)
    del params, bank
    assert int(H.state_health(st)) == 0

    cases = {
        H.H_NONFINITE_TIME: st.replace(
            wall_time=jnp.float32(jnp.nan)
        ),
        H.H_COMMIT_CONSERVE: st.replace(
            commit_count=st.commit_count + 1
        ),
        H.H_EXEC_CONSERVE: st.replace(
            exec_moving=st.exec_moving.at[0].set(True),
            exec_at_common=st.exec_at_common.at[0].set(True),
        ),
        H.H_TASK_MONOTONIC: st.replace(
            stage_completed_tasks=jnp.where(
                st.stage_exists, st.stage_num_tasks + 1, 0
            )
        ),
    }
    for bit, bad in cases.items():
        mask = int(H.state_health(bad))
        assert mask & bit, f"bit {bit} did not fire"
    # jit-compatible (the whole point: sentinels run inside the
    # collection program)
    assert int(jax.jit(H.state_health)(st)) == 0


def test_state_health_monotonicity_needs_prev_and_respects_reset():
    import jax.numpy as jnp

    from sparksched_tpu.env import health as H

    _, _, st = make_tpu_env_state(spec_multi_job(3, 5), 4)
    prev = st.replace(stage_completed_tasks=st.stage_completed_tasks + 2)
    assert int(H.state_health(st)) == 0  # no prev: no monotonic check
    assert int(H.state_health(st, prev=prev)) & H.H_TASK_MONOTONIC
    # an auto-reset legitimately restarts the counters
    assert not int(H.state_health(
        st, prev=prev, resetting=jnp.bool_(True)
    )) & H.H_TASK_MONOTONIC


def test_grad_health_bits_and_describe_mask():
    import jax.numpy as jnp

    from sparksched_tpu.env import health as H

    ok = {"w": jnp.ones(3), "b": jnp.zeros(2)}
    bad = {"w": jnp.array([1.0, jnp.nan, 2.0]), "b": jnp.zeros(2)}
    assert int(H.grad_health(loss=jnp.float32(1.0), grads=ok,
                             params=ok)) == 0
    assert int(H.grad_health(loss=jnp.float32(jnp.inf))) == (
        H.H_NONFINITE_LOSS
    )
    assert int(H.grad_health(grads=bad)) == H.H_NONFINITE_GRAD
    assert int(H.grad_health(params=bad)) == H.H_NONFINITE_PARAM
    # integer leaves cannot trip (isfinite is undefined there)
    assert int(H.grad_health(grads={"i": jnp.arange(3)})) == 0
    assert H.describe_mask(
        H.H_NONFINITE_GRAD | H.H_OOM
    ) == ["nonfinite_grad", "oom"]
    # the retry policy: stragglers observe, everything else retries
    assert not H.RETRYABLE_MASK & H.H_STRAGGLER
    assert H.RETRYABLE_MASK & H.H_NONFINITE_GRAD


# ---------------------------------------------------------------------------
# telemetry parity (ISSUE 9 satellite): the health-bitmask field across
# core and flat engines — zero mask on clean episodes, engines agree
# ---------------------------------------------------------------------------


def test_health_mask_parity_core_vs_flat_collectors():
    import jax

    from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros
    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.trainers.rollout import (
        collect_flat_sync_batch,
        collect_sync,
    )

    params, bank, s0 = make_tpu_env_state(spec_multi_job(3, 5), 4)

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    def bpol(rng, obs):
        si, ne = jax.vmap(
            lambda o: round_robin_policy(o, params.num_executors, True)
        )(obs)
        return si, ne, {}

    key = jax.random.PRNGKey(0)
    _, tm_core = collect_sync(
        params, bank, pol, key, 40, s0, telemetry_zeros(), health=True
    )
    states_b = jax.tree_util.tree_map(lambda a: a[None], s0)
    _, tm_batch = collect_flat_sync_batch(
        params, bank, bpol, key, 40, states_b,
        jax.tree_util.tree_map(
            lambda a: a[None], telemetry_zeros()
        ),
        health=True,
    )
    masks = [
        summarize(t)["health_mask"] for t in (tm_core, tm_batch)
    ]
    # clean deterministic episode: zero on both engines, and therefore
    # engines agree — the cross-engine invariant the satellite pins
    assert masks == [0, 0], masks
    for t in (tm_core, tm_batch):
        s = summarize(t)
        assert s["health_bits"] == []
        assert s["unhealthy_lanes"] == 0


def test_health_requires_telemetry_carry():
    import jax

    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.trainers.rollout import collect_sync

    params, bank, s0 = make_tpu_env_state(spec_multi_job(2, 5), 4)

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    with pytest.raises(ValueError, match="telemetry"):
        collect_sync(
            params, bank, pol, jax.random.PRNGKey(0), 5, s0,
            health=True,
        )


# ---------------------------------------------------------------------------
# PPO in-JIT skip gate: a poisoned rollout must not move the params
# ---------------------------------------------------------------------------


def test_ppo_update_skips_poisoned_minibatches_in_jit(tmp_path):
    import jax
    import jax.numpy as jnp
    import scripts_chaos_drill as drill

    from sparksched_tpu.env.health import H_NONFINITE_GRAD
    from sparksched_tpu.trainers import make_trainer

    cfg = drill.drill_cfg(str(tmp_path), num_iterations=1)
    t = make_trainer(cfg)
    state = t.init_state()
    state = state.replace(rng=jax.random.fold_in(state.rng, 0))
    ro, _, _ = t._collect_jit(
        state.params, state.iteration, state.rng, None
    )
    # The gate is per minibatch, so the poison has to be in every one
    # for an all-skipped update. A NaN reward at a lane's LAST step
    # makes that lane's whole return curve NaN (the returns scan runs
    # in reverse), and every minibatch takes steps from every lane.
    poisoned = ro.replace(
        reward=ro.reward.at[0, -1].set(jnp.float32(jnp.nan))
    )
    new_state, stats = t._update_jit(state, poisoned)
    assert int(stats["health_mask"]) & H_NONFINITE_GRAD
    # every minibatch skipped on-device: params and opt state unmoved
    assert _tree_equal(new_state.params, state.params)
    assert _tree_equal(new_state.opt_state, state.opt_state)
    assert int(stats["minibatches_applied"]) == 0
    # and a clean rollout at the same params DOES move them
    moved, stats2 = t._update_jit(state, ro)
    assert int(stats2["health_mask"]) == 0
    assert not _tree_equal(moved.params, state.params)
    # A NaN reward at a lane's FIRST step poisons one sample (its
    # return alone), hence one minibatch of the two: that one is
    # skipped, the clean one is applied (what jax 0.9's random streams
    # turned the [0, 0] poison of this test into), the bit still trips
    # and the host loop rolls the whole update back.
    one = ro.replace(
        reward=ro.reward.at[0, 0].set(jnp.float32(jnp.nan))
    )
    part, stats3 = t._update_jit(state, one)
    assert int(stats3["health_mask"]) & H_NONFINITE_GRAD
    assert (
        int(stats3["minibatches_applied"])
        == int(stats2["minibatches_applied"]) - 1
    )
    assert all(
        bool(jnp.isfinite(leaf).all())
        for leaf in jax.tree_util.tree_leaves(part.params)
    )


# ---------------------------------------------------------------------------
# checkpoint atomicity (ISSUE 9 satellite): torn-write fallback
# ---------------------------------------------------------------------------


def test_torn_checkpoint_write_falls_back_to_previous_generation(
        tmp_path):
    import scripts_chaos_drill as drill

    from sparksched_tpu.trainers import make_trainer

    cfg = drill.drill_cfg(str(tmp_path), num_iterations=1)
    t = make_trainer(cfg)
    path = str(tmp_path / "state.msgpack")
    s1 = t.init_state()
    s2 = s1.replace(iteration=s1.iteration + 1)
    t.save_train_state(s1, path)
    t.save_train_state(s2, path)  # rotates s1 -> path.1
    assert os.path.exists(path + ".1")
    assert os.path.exists(path + ".1.meta.json")
    # intact: newest generation loads
    assert int(t.load_train_state(path).iteration) == 1
    # torn write: truncate the newest; the digest check must reject it
    # and fall back to the previous generation
    data = open(path, "rb").read()
    with open(path, "wb") as fp:
        fp.write(data[: len(data) // 2])
    restored = t.load_train_state(path)
    assert int(restored.iteration) == 0
    assert _tree_equal(restored.params, s1.params)
    # a save AFTER the torn write must not rotate the corrupt file over
    # the intact previous generation (the zero-intact-generations
    # hazard): the torn gen-0 is discarded, .1 keeps the good state
    s3 = s1.replace(iteration=s1.iteration + 2)
    t.save_train_state(s3, path)
    assert int(t.load_train_state(path).iteration) == 2
    assert int(t.load_train_state(path + ".1").iteration) == 0
    # both generations torn: the loader must raise, not return garbage
    with open(path, "wb") as fp:
        fp.write(b"junk")
    with open(path + ".1", "wb") as fp:
        fp.write(b"junk")
    with pytest.raises(ValueError, match="no intact generation"):
        t.load_train_state(path)


# ---------------------------------------------------------------------------
# resume bit-exactness (ISSUE 9 satellite): train N  ==  train k,
# SIGKILL mid-iteration k+1, resume from the atomic checkpoint,
# train N-k — parameters step-exact
# ---------------------------------------------------------------------------

_KILLED_TRAIN = textwrap.dedent("""\
    import sys
    sys.path.insert(0, {repo!r})
    from __graft_entry__ import force_virtual_cpu_devices
    force_virtual_cpu_devices(8)
    from sparksched_tpu.config import enable_compilation_cache
    enable_compilation_cache()
    import scripts_chaos_drill as drill
    from sparksched_tpu.trainers import make_trainer
    cfg = drill.drill_cfg({art!r}, num_iterations=3,
                          chaos={{"sigkill": [1]}})
    make_trainer(cfg).train()
    raise SystemExit("unreachable: chaos sigkill did not fire")
""")


def test_resume_after_sigkill_is_step_exact(tmp_path):
    """The subprocess trains iteration 0 (checkpoint_every=1 writes the
    atomic train state), is SIGKILLed mid-iteration 1, and the parent
    resumes for the remaining 2 iterations — the final params must be
    bit-identical to an uninterrupted 3-iteration run. The subprocess
    pins the same virtual-device topology as the suite so the compiled
    programs match across processes."""
    import scripts_chaos_drill as drill

    from sparksched_tpu.trainers import make_trainer

    art_kill = str(tmp_path / "killed")
    code = _KILLED_TRAIN.format(repo=REPO, art=art_kill)
    r = subprocess.run(
        [sys.executable, "-c", code], timeout=900, cwd=REPO,
        env=os.environ | {"JAX_PLATFORMS": "cpu",
                          "JAX_ENABLE_X64": "0"},
    )
    assert r.returncode == -signal.SIGKILL, r.returncode
    ckpt = os.path.join(art_kill, "train_state.msgpack")
    assert os.path.isfile(ckpt), "no atomic checkpoint survived"

    # resume the remaining N-k iterations
    t_resume = make_trainer(drill.drill_cfg(art_kill, num_iterations=2))
    resumed = t_resume.train(resume_from=ckpt)
    assert int(resumed.iteration) == 3

    # uninterrupted N=3 run with the identical health config
    art_full = str(tmp_path / "full")
    t_full = make_trainer(drill.drill_cfg(art_full, num_iterations=3))
    full = t_full.train()

    assert _tree_equal(resumed.params, full.params), (
        "resumed params diverged from the uninterrupted run"
    )
    assert _tree_equal(resumed.opt_state, full.opt_state)


# ---------------------------------------------------------------------------
# chaos-drill smoke (ISSUE 9 satellite): the tier-1 subset — NaN-grad
# recovery + corrupt-checkpoint fallback; the full matrix is slow-marked
# ---------------------------------------------------------------------------


def test_chaos_smoke_nan_grad_recovery(tmp_path):
    import scripts_chaos_drill as drill

    assert drill.drill_nan_grad(str(tmp_path))


def test_chaos_smoke_corrupt_checkpoint_fallback(tmp_path):
    import scripts_chaos_drill as drill

    assert drill.drill_corrupt_checkpoint(str(tmp_path))


@pytest.mark.slow
def test_chaos_drill_full_matrix(tmp_path, monkeypatch):
    import scripts_chaos_drill as drill

    monkeypatch.setenv("DRILL_ARTIFACTS", str(tmp_path))
    assert drill.main() == 0
