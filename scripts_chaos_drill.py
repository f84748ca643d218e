"""Deterministic fault-injection drill (ISSUE 9 acceptance): exercise
every recovery path of the self-healing training runtime end-to-end on
CPU, asserting that each injected fault class is (a) DETECTED via a
runlog `health`/`recovery` record, (b) RECOVERED within the bounded
retry budget, and (c) leaves finite parameters behind.

Fault matrix (sparksched_tpu/chaos.py):

  nan_grad     NaN reward -> NaN loss/grads; the in-JIT PPO sentinel
               skips the minibatches, the trainer rolls back + retries
  bank_row     NaN observation-duration row (what a corrupted bank row
               produces downstream) -> same detection path; PLUS the
               state-level check: a genuinely corrupted bank driven
               through a health-threaded collector must trip
               H_NONFINITE_TIME in the telemetry mask
  corrupt_ckpt torn train-state write -> digest-verified load falls
               back to the previous generation and the resume completes
  sigkill      SIGKILL mid-iteration (subprocess) -> the atomic
               checkpoint_every write resumes the run, params finite
  straggler    inflated lane loop_iters -> straggler_ratio_max
               quarantine record, run continues (no retry)
  oom          simulated RESOURCE_EXHAUSTED between collect and update
               -> backoff + retry

Usage:
  python scripts_chaos_drill.py          # full matrix
  python scripts_chaos_drill.py --fast   # the tier-1 smoke subset
                                         # (nan_grad + corrupt_ckpt)

Exit code 0 iff every drilled scenario passed. Each scenario prints a
single `[drill] <name>: PASS|FAIL` line; artifacts land under a temp
dir unless DRILL_ARTIFACTS is set.
"""

from __future__ import annotations

import json
import os
import os.path as osp
import pathlib
import signal
import subprocess
import sys
import tempfile

import jax  # noqa: E402
import numpy as np  # noqa: E402

from sparksched_tpu.obs.runlog import emit  # noqa: E402


def drill_cfg(artifacts: str, num_iterations: int = 3,
              health=None, chaos=None) -> dict:
    cfg = {
        "trainer": {
            "trainer_cls": "PPO",
            "num_iterations": num_iterations,
            "num_sequences": 1,
            "num_rollouts": 2,
            "seed": 0,
            "use_tensorboard": False,
            "num_epochs": 1,
            "num_batches": 2,
            "beta_discount": 5.0e-3,
            "opt_kwargs": {"lr": 3.0e-4},
            "max_grad_norm": 0.5,
            "rollout_steps": 30,
            "artifacts_dir": artifacts,
            "checkpointing_freq": 10**9,
        },
        "agent": {
            "agent_cls": "DecimaScheduler",
            "embed_dim": 8,
            "gnn_mlp_kwargs": {
                "hid_dims": [16, 8],
                "act_cls": "LeakyReLU",
                "act_kwargs": {"negative_slope": 0.2},
            },
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
        "obs": {"runlog": True, "telemetry": True},
        "health": {
            "max_retries": 2,
            "backoff_seconds": 0.05,
            "checkpoint_every": 1,
        } | dict(health or {}),
    }
    if chaos is not None:
        cfg["chaos"] = chaos
    return cfg


def runlog_records(artifacts: str) -> list[dict]:
    recs = []
    for p in sorted(pathlib.Path(artifacts, "runlog").glob("*.jsonl")):
        recs.extend(json.loads(ln) for ln in open(p))
    return recs


def params_finite(state) -> bool:
    return all(
        np.isfinite(np.asarray(leaf)).all()
        for leaf in jax.tree_util.tree_leaves(state.params)
        if np.issubdtype(np.asarray(leaf).dtype, np.floating)
    )


def _train(cfg):
    from sparksched_tpu.trainers import make_trainer

    t = make_trainer(cfg)
    return t, t.train()


def drill_nan_grad(root: str) -> bool:
    """NaN gradient at iteration 1: detected (health record with the
    grad/loss bits), recovered (recovery record + run completes), and
    the final params are finite."""
    art = osp.join(root, "nan_grad")
    t, state = _train(drill_cfg(art, chaos={"nan_grad": [1], "seed": 7}))
    recs = runlog_records(art)
    health = [r for r in recs if r["ev"] == "health"]
    rec = [r for r in recs if r["ev"] == "recovery"
           and r.get("action") == "rollback_retry"]
    ok = (
        int(state.iteration) == 3
        and params_finite(state)
        and any("nonfinite_grad" in h.get("bits", ()) for h in health)
        and bool(rec)
    )
    return ok


def drill_bank_row(root: str) -> bool:
    """Corrupted-bank-row class, both halves: (1) the rollout-level
    injection recovers through the trainer; (2) a genuinely corrupted
    bank driven through a health-threaded flat collector trips the
    state-level H_NONFINITE_TIME sentinel in the telemetry mask."""
    art = osp.join(root, "bank_row")
    # the chaos seed picks the (lane, row, job) that is poisoned: a
    # padded row of a flat-engine rollout has no live node, so the
    # fault has to land on a valid row's live job (seed 1: lane 0,
    # row 3, job 0) to reach the update
    t, state = _train(drill_cfg(art, chaos={"bank_row": [1], "seed": 1}))
    recs = runlog_records(art)
    health = [r for r in recs if r["ev"] == "health"]
    trained_ok = (
        int(state.iteration) == 3 and params_finite(state) and health
        and any(r["ev"] == "recovery" for r in recs)
    )

    # state-level detection on a genuinely corrupt bank
    from sparksched_tpu.chaos import corrupt_bank
    from sparksched_tpu.env import core
    from sparksched_tpu.env.health import (
        H_EXEC_CONSERVE,
        H_NONFINITE_TIME,
    )
    from sparksched_tpu.obs.telemetry import (
        summarize,
        telemetry_zeros_like,
    )
    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.trainers.rollout import collect_flat_sync_batch

    params, bank = t.params_env, corrupt_bank(t.bank, seed=5)

    def pol(rng, obs):
        si, ne = jax.vmap(lambda o: round_robin_policy(
            o, params.num_executors, True))(obs)
        return si, ne, {}

    # the trainer's collector over a batch of one lane
    st = jax.vmap(lambda k: core.reset(params, bank, k))(
        jax.random.PRNGKey(0)[None])
    _, tm = collect_flat_sync_batch(
        params, bank, pol, jax.random.PRNGKey(1), 30, st,
        telemetry_zeros_like((1,)), health=True,
    )
    mask = summarize(tm)["health_mask"]
    # a NaN sampled duration first shows as an executing executor with
    # a non-finite finish time (exec-conservation), then as a NaN wall
    # clock once the event pops — either bit is a detection
    state_ok = bool(mask & (H_NONFINITE_TIME | H_EXEC_CONSERVE))
    return trained_ok and state_ok


def drill_corrupt_checkpoint(root: str) -> bool:
    """Torn train-state write: train 2 iterations (two checkpoint
    generations on disk), truncate the newest, and resume — the
    digest-verified loader must fall back to the previous generation
    and the resumed run must complete with finite params."""
    from sparksched_tpu.trainers import make_trainer

    art = osp.join(root, "corrupt_ckpt")
    cfg = drill_cfg(art, num_iterations=2)
    t = make_trainer(cfg)
    t.train()
    path = osp.join(art, "train_state.msgpack")
    data = open(path, "rb").read()
    with open(path, "wb") as fp:  # torn write: half the bytes
        fp.write(data[: len(data) // 2])

    cfg2 = drill_cfg(art, num_iterations=1)
    t2 = make_trainer(cfg2)
    state = t2.train(resume_from=path)
    recs = runlog_records(art)
    fell_back = any(
        r["ev"] == "recovery" and r.get("action") == "checkpoint_fallback"
        for r in recs
    )
    # the intact generation was written after iteration 1 or 2; resume
    # continues from whichever survived and completes one more
    return (
        fell_back and params_finite(state) and int(state.iteration) >= 2
    )


def drill_sigkill(root: str) -> bool:
    """SIGKILL mid-iteration in a subprocess; resume from the atomic
    per-iteration checkpoint and finish. The harder bit-exactness
    claim (resumed params == straight-run params) is test-pinned in
    tests/test_health.py; the drill asserts the operational story."""
    art = osp.join(root, "sigkill")
    code = (
        "import sys; sys.path.insert(0, {repo!r})\n"
        "import scripts_chaos_drill as d\n"
        "from sparksched_tpu.trainers import make_trainer\n"
        "cfg = d.drill_cfg({art!r}, num_iterations=3,\n"
        "                  chaos={{'sigkill': [1]}})\n"
        "make_trainer(cfg).train()\n"
    ).format(repo=osp.dirname(osp.abspath(__file__)), art=art)
    r = subprocess.run(
        [sys.executable, "-c", code], timeout=900,
        env=os.environ | {"JAX_PLATFORMS": "cpu"},
    )
    if r.returncode != -signal.SIGKILL:
        emit(f"[drill] sigkill: subprocess rc={r.returncode}, "
             f"expected {-signal.SIGKILL}")
        return False
    path = osp.join(art, "train_state.msgpack")
    if not osp.isfile(path):
        emit("[drill] sigkill: no checkpoint survived the kill")
        return False
    from sparksched_tpu.trainers import make_trainer

    t2 = make_trainer(drill_cfg(art, num_iterations=2))
    state = t2.train(resume_from=path)
    recs = runlog_records(art)
    resumed = any(r["ev"] == "resume" for r in recs)
    return resumed and params_finite(state) and int(state.iteration) == 3


def drill_straggler(root: str) -> bool:
    """Inflated straggler lane: quarantined via a `health` record with
    the straggler bit, NO retry (it is an observation, not corruption),
    and the run completes."""
    art = osp.join(root, "straggler")
    # with B lanes max/mean is bounded by B; at the drill's 2 lanes the
    # x100 inflation lands the ratio just under 2.0, so the threshold
    # sits below that bound but above any natural 2-lane imbalance
    t, state = _train(drill_cfg(
        art, health={"straggler_ratio_max": 1.9},
        chaos={"straggler": [1], "seed": 11},
    ))
    recs = runlog_records(art)
    health = [r for r in recs if r["ev"] == "health"]
    quarantined = any(
        "straggler" in h.get("bits", ())
        and h.get("action") == "quarantine"
        for h in health
    )
    no_retry = not any(r["ev"] == "recovery" for r in recs)
    return (
        quarantined and no_retry and int(state.iteration) == 3
        and params_finite(state)
    )


def drill_oom(root: str) -> bool:
    """Simulated RESOURCE_EXHAUSTED between collect and update:
    detected (health record with the oom bit), retried with backoff,
    run completes."""
    art = osp.join(root, "oom")
    t, state = _train(drill_cfg(art, chaos={"oom": [1]}))
    recs = runlog_records(art)
    health = [r for r in recs if r["ev"] == "health"]
    return (
        any("oom" in h.get("bits", ()) for h in health)
        and any(r["ev"] == "recovery"
                and r.get("action") == "rollback_retry" for r in recs)
        and int(state.iteration) == 3
        and params_finite(state)
    )


SCENARIOS = {
    "nan_grad": drill_nan_grad,
    "bank_row": drill_bank_row,
    "corrupt_ckpt": drill_corrupt_checkpoint,
    "sigkill": drill_sigkill,
    "straggler": drill_straggler,
    "oom": drill_oom,
}
FAST = ("nan_grad", "corrupt_ckpt")


def main(names=None) -> int:
    root = os.environ.get("DRILL_ARTIFACTS") or tempfile.mkdtemp(
        prefix="chaos_drill_"
    )
    names = tuple(names) if names else tuple(SCENARIOS)
    failed = []
    for name in names:
        try:
            ok = SCENARIOS[name](root)
        except Exception as e:  # a crashed drill is a failed drill
            emit(f"[drill] {name}: EXCEPTION {type(e).__name__}: {e}")
            ok = False
        emit(f"[drill] {name}: {'PASS' if ok else 'FAIL'}")
        if not ok:
            failed.append(name)
    emit(
        f"[drill] {len(names) - len(failed)}/{len(names)} scenarios "
        f"passed (artifacts: {root})"
    )
    return 1 if failed else 0


if __name__ == "__main__":
    picks = FAST if "--fast" in sys.argv[1:] else None
    sys.exit(main(picks))
