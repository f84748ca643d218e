"""Trained-Decima vs fair-scheduler evaluation on held-out seeds.

Evaluates both schedulers on the SAME job sequences (seed-paired
episodes) at the trained checkpoint's scale and reports per-seed and mean
average job completion time — the reference's headline claim is that
Decima beats the fair scheduler on avg JCT (/root/reference/README.md:5-7,
examples.py:49-81). Writes EVAL.md.

Usage: python scripts_eval_decima.py [num_seeds] [ckpt|-] [out_md]
(ckpt "-" keeps the default multi-checkpoint comparison, e.g. to write
it to a non-default out_md.)
"""

from __future__ import annotations

import sys

import jax
import numpy as np

from sparksched_tpu import sweep
from sparksched_tpu.config import EnvParams
from sparksched_tpu.env import core
from sparksched_tpu.schedulers import DecimaScheduler, RoundRobinScheduler
from sparksched_tpu.workload import make_workload_bank

import os

# the checkpoint's training scale (scripts_train_session.py env cfg);
# EVAL_JOBS=50 reruns the table at the reference's demo setting
# (10 executors / 50 jobs, reference examples.py:15-23) with a
# proportionally larger decision cap
_JOBS = int(os.environ.get("EVAL_JOBS", 20))
# EVAL_EXECS=50 reruns the table at the flagship scale of
# config/decima_tpch.yaml (50 executors; reference decima_tpch.yaml)
_EXECS = int(os.environ.get("EVAL_EXECS", 10))
ENV = dict(num_executors=_EXECS, max_jobs=_JOBS, moving_delay=2000.0,
           warmup_delay=1000.0, job_arrival_rate=4.0e-5)
# padded decision cap per episode: decisions scale with both jobs and
# executors (every executor-availability event forces one); the default
# reproduces 600 at the 10-exec/20-job training scale
STEPS = int(os.environ.get("EVAL_STEPS", 3 * _JOBS * _EXECS))
HELD_OUT_BASE = 10_000  # disjoint from training seeds (iteration-indexed)


def episode_states(params, bank, seeds):
    return jax.vmap(
        lambda s: core.reset(params, bank, jax.random.PRNGKey(s))
    )(seeds)


def run_policy(params, bank, scheduler, seeds):
    """Every seed's episode to its end through the sweep loop
    (`sparksched_tpu/sweep.py`, the evaluation path of every
    `Scheduler`; until PR 46 `collect_sync`, the per-decision
    `core.step` loop, under a padded cap of `STEPS` rows): the episodes'
    average job durations in seed order, and whether every job of each
    completed. `STEPS` bounds the rows a run may take."""
    import time

    states = episode_states(params, bank, seeds)
    t0 = time.perf_counter()
    out = sweep.run(
        params, bank, scheduler, states=states, episodes=len(seeds),
        seed=HELD_OUT_BASE, rows=64, max_chunks=-(-4 * STEPS // 64))
    print(f"  ({time.perf_counter() - t0:.0f}s)", flush=True)
    return out["avg_jct"], out["jobs_completed"] == params.max_jobs


def make_decima(params, ckpt):
    return DecimaScheduler(
        num_executors=params.num_executors,
        embed_dim=16,
        gnn_mlp_kwargs={
            "hid_dims": [32, 16],
            "act_cls": "LeakyReLU",
            "act_kwargs": {"negative_slope": 0.2},
        },
        policy_mlp_kwargs={"hid_dims": [64, 64], "act_cls": "Tanh"},
        state_dict_path=ckpt,
    )


CKPTS = {
    "decima (tpu-trained, no warm start)": "models/decima/model_tpu.msgpack",
    "decima (tpu fine-tuned)": "models/decima/model_ft.msgpack",
    "decima (reference ckpt, converted)": (
        "/root/reference/models/decima/model.pt"
    ),
}

# one provenance line per known checkpoint; the report only describes
# checkpoints it actually evaluated
PROVENANCE = {
    "decima (tpu-trained, no warm start)": (
        "from-scratch PPO in this framework: round-3 recipe through "
        "iteration 250 (scripts_scratch_train.py), then the round-4 "
        "plateau continuation with corrected late-training schedules "
        "(scripts_plateau_train.py); best-model checkpoint at curve "
        "iteration ~400, artifacts/decima_plateau/checkpoints/150"
    ),
    "decima (tpu fine-tuned)": (
        "PPO fine-tune in this framework warm-started from the "
        "converted reference weights (scripts_finetune_loop.py — the "
        "reference's own state_dict_path workflow, "
        "decima/scheduler.py:57-59; train state under "
        "artifacts/decima_ft)"
    ),
    "decima (reference ckpt, converted)": (
        "the reference's published models/decima/model.pt through the "
        "torch->flax converter, no training in this framework"
    ),
}


def main():
    num_seeds = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    ckpts = dict(CKPTS)
    # EVAL_CKPTS: comma-separated substrings selecting which of the
    # default checkpoints to evaluate (long 50-job runs need not pay
    # for stale ones)
    sel = os.environ.get("EVAL_CKPTS")
    if sel:
        keys = [s.strip() for s in sel.split(",") if s.strip()]
        ckpts = {
            n: p for n, p in ckpts.items()
            if any(k in n for k in keys)
        }
        assert ckpts, f"EVAL_CKPTS={sel!r} matched nothing"
    if len(sys.argv) > 2 and sys.argv[2] != "-":
        ckpts = {"decima": sys.argv[2]}
    out_md = sys.argv[3] if len(sys.argv) > 3 else "EVAL.md"
    params = EnvParams(**ENV)
    bank = make_workload_bank(params.num_executors, params.max_stages)
    if bank.max_stages != params.max_stages:
        params = params.replace(
            max_stages=bank.max_stages, max_levels=bank.max_stages
        )
    seeds = jax.numpy.arange(
        HELD_OUT_BASE, HELD_OUT_BASE + num_seeds
    )

    fair = RoundRobinScheduler(
        params.num_executors, dynamic_partition=True
    )
    print("evaluating fair...", flush=True)
    ajd_fair, done_fair = run_policy(params, bank, fair, seeds)
    assert done_fair.all(), "unfinished fair episodes"

    results = {}
    for name, ckpt in ckpts.items():
        print(f"evaluating {name}...", flush=True)
        dec = make_decima(params, ckpt)
        ajd, done = run_policy(params, bank, dec, seeds)
        assert done.all(), f"unfinished {name} episodes"
        results[name] = ajd

    header = (
        "| seed | fair avg JCT (s) | "
        + " | ".join(f"{n} (s)" for n in results)
        + " |"
    )
    lines = [
        "# Decima vs fair scheduler — held-out evaluation",
        "",
        "Seed-paired episodes: every scheduler sees the identical job "
        "arrival sequence per seed (the reference's headline claim is "
        "Decima < fair on avg job completion time, "
        "/root/reference/README.md:5-7).",
        f"Env: {ENV['num_executors']} executors, {ENV['max_jobs']} "
        "TPC-H jobs (synthetic bank), held-out seeds "
        f"{HELD_OUT_BASE}..{HELD_OUT_BASE + num_seeds - 1}.",
        "",
        "Checkpoints: "
        + "; ".join(
            f"`{n}` = "
            + PROVENANCE.get(n, f"custom checkpoint {ckpts[n]}")
            for n in results
        )
        + ".",
        "",
        header,
        "|" + "---|" * (2 + len(results)),
    ]
    for i, s in enumerate(np.asarray(seeds)):
        row = f"| {int(s)} | {ajd_fair[i] * 1e-3:.1f} |"
        for ajd in results.values():
            row += f" {ajd[i] * 1e-3:.1f} |"
        lines.append(row)
    lines.append("")
    for name, ajd in results.items():
        wins = int((ajd < ajd_fair).sum())
        lines.append(
            f"**{name}: mean avg JCT {ajd.mean() * 1e-3:.1f}s vs fair "
            f"{ajd_fair.mean() * 1e-3:.1f}s "
            f"({(1 - ajd.mean() / ajd_fair.mean()) * 100:+.1f}%), wins "
            f"{wins}/{num_seeds} seeds.**"
        )
    lines.append("")
    out = "\n".join(lines)
    print(out)
    with open(out_md, "w") as fp:
        fp.write(out)


if __name__ == "__main__":
    main()
