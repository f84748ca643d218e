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
times and log-probs: `sentinel_checks`, which the other collector
drivers hold their cells to as well), and scores a seeded sample of its
stored decisions with the benchmark's plain numpy forward pass under the
parameters that collected them (`benchmarks/logprob_check.py`, the one
copy of that comparison): the mean gap and a high quantile of it.
"""

from __future__ import annotations

import os.path as osp
import shutil
import time

import numpy as np

from benchmarks import harness, logprob_check

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


def sentinel_checks(ctx: dict, window: dict) -> list[dict]:
    """The engine's sentinels and counts on the window and its last
    rollout: enough collections, no tripped health bit, as many valid
    decisions as the telemetry counted, every lane decides, finite
    rewards, times and log-probs."""
    import jax

    ro = ctx["last"][1]
    per_lane = np.asarray(jax.device_get(ro.valid)).sum(axis=1)
    finite = all(bool(np.isfinite(np.asarray(jax.device_get(a))).all())
                 for a in (ro.reward, ro.wall_times, ro.lgprob))
    return [
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


def verify(ctx: dict, window: dict) -> list[dict]:
    params, ro = ctx["last"]
    return sentinel_checks(ctx, window) + logprob_check.checks(
        ctx["trainer"], params, ro, ctx["seed"], ctx["cell"]["config_data"])


def close(ctx: dict) -> None:
    shutil.rmtree(ctx["out"], ignore_errors=True)
