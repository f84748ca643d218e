"""The trainer's collector alone: collection after collection of
sampled Decima decisions over all lanes, with no update between them.

`build` makes the trainer the way `train.py` does (`config.load` of the
program's own YAML, then `make_trainer`), with the overrides the
configuration file and the traffic mix list; the weights and the key the
actions are sampled with come from `--seed`. `warm_up` runs the mix's
warm-up collections (the first compiles the collector or loads it from
the cache). `measure` calls the same compiled program, the trainer's
`_collect_jit`, as `Trainer.train` calls it, the rollout before it freed
first as the trainer frees it, until `--seconds` are used up and the
mix's least number of collections is done (the last collection runs to
its end, so a window is a whole number of collections). The end-to-end
metric is the valid decisions of all the window's collections over the
window's wall time. A traced run traces a few seconds of the window from
a thread of its own.

`verify`, outside the window, holds the window's LAST rollout to the
engine's sentinels and counts (no tripped health bit, as many valid
decisions as the telemetry counted, every lane decides, finite rewards,
times and log-probs), and scores a seeded sample of its stored decisions
with the benchmark's plain numpy forward pass under the parameters that
collected them: the recorded log-probability of the recorded action on
the recorded observation, against the reference in plain float32 and at
the stated precision.
"""

from __future__ import annotations

import os.path as osp
import shutil
import time

import numpy as np

from benchmarks import harness
from benchmarks.reference import decima_np

HOST_SPANS = ("bench/collect",)
UNATTRIBUTED = "rollout/host_gap"  # an idle gap under no host span


def build(cell: dict, seed: int, *, seconds: float = 0.0,
          control: dict | None = None, trace: bool = False) -> dict:
    from sparksched_tpu import config
    from sparksched_tpu.trainers import make_trainer

    conf, mix = cell["config_data"], cell["mix"]
    out = osp.join(harness.OUT_DIR, cell["name"], "train")
    shutil.rmtree(out, ignore_errors=True)
    cfg = config.load(osp.join(harness.ROOT, conf["program_config"]))
    cfg = harness.merge(cfg, conf.get("overrides", {}))
    cfg = harness.merge(cfg, mix.get("overrides", {}))
    if control:
        cfg = harness.merge(cfg, control)
    cfg["trainer"] |= {"artifacts_dir": out, "checkpointing_freq": 10**9}
    # the trainer's own seed stays the config's: the collector holds it
    # as a compile-time constant, so a new value is a new program
    # (PERF.md, Open questions), and it fixes the job sequences
    cfg["agent"] = dict(cfg["agent"], seed=harness.seed31(seed))
    trainer = make_trainer(cfg)
    lanes, steps = trainer.num_envs, trainer.rollout_steps
    if (lanes, steps) != (mix["lanes"], mix["rollout_steps"]):
        raise SystemExit(
            f"the mix states {mix['lanes']} lanes x {mix['rollout_steps']} "
            f"steps, the trainer was built with {lanes} x {steps}")
    return {"cell": cell, "trainer": trainer, "out": out, "seed": seed,
            "state": None, "last": None}


def warm_up(ctx: dict) -> None:
    ctx["state"] = ctx["trainer"].init_state()
    for i in range(int(ctx["cell"]["mix"]["warmup_collections"])):
        _collect_once(ctx, i)
    ctx["last"] = None


def _call_collector(trainer, params, i: int, rng):
    """The trainer's compiled collector, called as `Trainer.train`
    calls it: rollout, final loop state, telemetry."""
    import jax.numpy as jnp

    return trainer._collect_jit(params, jnp.int32(i), rng, None)


def _collect_once(ctx: dict, i: int) -> dict:
    """Collection number `i`: seconds, valid decisions and the
    telemetry summary. The run's seed gives the weights and the key the
    actions are sampled with; the job sequences are the same for every
    seed (the trainer derives them from its own seed and from `i`), so
    every seed simulates the same arrivals under another policy and
    runs one cached program."""
    import jax

    from sparksched_tpu.obs.telemetry import summarize

    trainer, params = ctx["trainer"], ctx["state"].params
    ctx["last"] = None  # frees the rollout before, as the trainer does
    rng = jax.random.fold_in(harness.key_from_seed(ctx["seed"]), i)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(HOST_SPANS[0]):
        ro, _, telem = _call_collector(trainer, params, i, rng)
        jax.block_until_ready(ro.reward)
    seconds = time.perf_counter() - t0
    ctx["last"] = (params, ro)
    return {"collect_seconds": seconds, "collection": i,
            "decisions": int(ro.valid.sum()),
            "telemetry": summarize(telem) if telem is not None else None}


def measure(ctx: dict, seconds: float, tracer) -> dict:
    trainer, mix = ctx["trainer"], ctx["cell"]["mix"]
    warm, least = int(mix["warmup_collections"]), int(mix["min_collections"])
    recs: list[dict] = []
    tracing = harness.trace_for(
        tracer, float(mix.get("trace_start_s", 0)),
        float(mix.get("trace_seconds", 0)))
    t0 = time.perf_counter()
    while True:
        recs.append(_collect_once(ctx, warm + len(recs)))
        now = time.perf_counter()
        if len(recs) >= least and now - t0 >= seconds:
            break
    wall = now - t0
    per_collection = float(np.median([r["collect_seconds"] for r in recs]))
    trace = None
    if tracing is not None:
        tracing.join()
        trace = tracer.reduce()
        # scope times are per collection: the trace covers part of one
        trace["units"] = trace["window_s"] / per_collection
    decisions = sum(r["decisions"] for r in recs)
    return {
        "end_to_end": {mix["end_to_end"]: decisions / wall},
        "samples": {"collections": len(recs), "decisions": decisions,
                    "window_s": wall, "asked_s": seconds,
                    "lanes": trainer.num_envs,
                    "rollout_steps": trainer.rollout_steps,
                    "collect_s_median": per_collection},
        "attempted": len(recs), "failed": 0,
        "scalars": recs,
        "telemetry": [r["telemetry"] for r in recs if r["telemetry"]],
        "trace": trace,
    }


def verify(ctx: dict, window: dict) -> list[dict]:
    import jax

    conf = ctx["cell"]["config_data"]
    params, ro = ctx["last"]
    per_lane = np.asarray(jax.device_get(ro.valid)).sum(axis=1)
    finite = all(bool(np.isfinite(np.asarray(jax.device_get(a))).all())
                 for a in (ro.reward, ro.wall_times, ro.lgprob))
    checks = [
        harness.check("collections", len(window["scalars"]), int(
            ctx["cell"]["mix"]["min_collections"]), ">="),
        harness.check("health_mask", max(
            (t["health_mask"] for t in window["telemetry"]), default=None),
            0, "=="),
        harness.check("telemetry_decisions_gap", sum(
            t["decisions"] for t in window["telemetry"])
            - window["samples"]["decisions"], 0, "=="),
        harness.check("idle_lanes", int((per_lane == 0).sum()), 0, "=="),
        harness.check("rollout_finite", finite, True, "=="),
    ]
    return checks + logprob_checks(
        ctx["trainer"], params, ro, ctx["seed"], conf, conf["limits"])


def logprob_checks(trainer, params, ro, seed: int, conf: dict,
                   limits: dict) -> list[dict]:
    """A seeded sample of the rollout's valid stored decisions against
    the plain forward pass: the gap between the collector's recorded
    log-probability and the reference's, for the recorded action on the
    recorded observation, under the parameters that collected it; the
    reference once in plain float32 and once at the stated precision."""
    import jax

    valid = np.asarray(jax.device_get(ro.valid))
    lanes_t = np.argwhere(valid)
    rng = np.random.default_rng(seed)
    n = min(int(limits["logprob_sample"]), len(lanes_t))
    pick = lanes_t[rng.choice(len(lanes_t), size=n, replace=False)]
    bi, ti = pick[:, 0], pick[:, 1]
    rows = jax.device_get(jax.tree_util.tree_map(
        lambda a: a[bi, ti],
        (ro.obs, ro.stage_idx, ro.num_exec_k, ro.lgprob)))
    so, stage_idx, exec_k, lgprob = rows
    weights = jax.tree_util.tree_map(np.asarray, jax.device_get(params))
    adj_bank = np.asarray(trainer.bank.adj)
    j, s = so.job_mask.shape[1], adj_bank.shape[-1]
    gaps = {"float32": [], "bf16_operands": []}
    for i in range(n):
        obs = {
            name: np.asarray(getattr(so, name)[i])[: j * s].reshape(j, s)
            for name in ("remaining", "duration", "schedulable",
                         "node_mask")}
        obs |= {"job_mask": so.job_mask[i],
                "exec_supplies": so.exec_supplies[i],
                "num_committable": so.num_committable[i],
                "source_job": so.source_job[i],
                "adj": adj_bank[np.asarray(so.job_template[i])]}
        for matmul, out in gaps.items():
            ref = decima_np.score_action(
                weights, obs, int(stage_idx[i]), int(exec_k[i]),
                trainer.params_env.num_executors,
                gnn_slope=conf["model"]["gnn_negative_slope"],
                matmul=matmul)
            out.append(abs(float(lgprob[i]) - ref["lgprob"]))
    return [harness.check("logprob_sample", n, 1, ">=")] + gap_checks(
        gaps, limits)


def gap_checks(gaps: dict, limits: dict) -> list[dict]:
    """The log-probability gaps against the plain float32 reference and
    against the reference at the stated precision (bfloat16 operands),
    mean and widest, each beside its limit."""
    out = []
    for matmul, key in (("float32", "gap"), ("bf16_operands", "stated_gap")):
        g = np.asarray(gaps[matmul])
        for how, value in (("mean", g.mean() if g.size else np.nan),
                           ("max", g.max() if g.size else np.nan)):
            out.append(harness.check(
                f"logprob_{key}_{how}", float(value),
                limits[f"logprob_{key}_{how}"], "<="))
    return out


def close(ctx: dict) -> None:
    shutil.rmtree(ctx["out"], ignore_errors=True)
