"""The trainer's collector in STREAMING mode (upstream's async rollout
mode, `trainer.rollout_duration`): collection after collection over
lanes that persist, each lane collecting until it has simulated its
sim-time budget, episodes re-seeded inside the scan, no update between
collections.

`build` first looks for the program's streaming configuration: a
program that does not have the file cannot run the cell, and the run
ends there, at once, naming it. Then it makes the trainer the way
`train.py` does, as `collect_rollout` does. The persistent carry
(`LoopState`, reset counts: argument 3 of the trainer's `_collect_jit`)
starts as None in the first warm-up collection, which resets every lane,
and is threaded through EVERY collection after it, warm-up and window
alike, and never rebuilt: the first call compiles or loads the program
that resets, the second the one that goes on, so the mix warms up two.
`measure` runs whole collections until `--seconds` are used up, the
rollout before freed first; the end-to-end metric is the valid decisions
of the window's collections over its wall time.

Of the collection before, `measure` keeps what the boundary check needs
and nothing else: the small leaves of its rollout (`valid`, elapsed
times, `resets`, reset counts, job templates: 0.1 GB of the 4.4) and the
lane's last valid row of `remaining` and `node_mask`.

`verify`, outside the window: the sentinels and counts of
`collect_rollout` (its `sentinel_checks`); the four guarantees of
streaming on the window's last two collections by the plain reference
`reference/stream_np.py`
(budget, persistence, group-shared re-seeding, counts; a check on a
counter the program may lack is left out where the summary has no such
key); and the collector's recorded log-probabilities of a seeded sample
of the last rollout's decisions against the plain forward pass
`reference/decima_np.py` (`benchmarks/logprob_check.py`, the one copy
of that comparison), the mean gap and a high quantile of it, which
unlike the widest gap of a few dozen parts a sound run from one computed
in bfloat16 (PERF.md, PR 30).
"""

from __future__ import annotations

import os.path as osp
import shutil
import time

import numpy as np

from benchmarks import harness, logprob_check
from benchmarks.drivers import collect_rollout
from benchmarks.reference import stream_np

HOST_SPANS = ("bench/collect",)
UNATTRIBUTED = "rollout/host_gap"  # an idle gap under no host span


def build(cell: dict, seed: int, *, seconds: float = 0.0,
          control: dict | None = None, trace: bool = False) -> dict:
    conf, mix = cell["config_data"], cell["mix"]
    path = osp.join(harness.ROOT, conf["program_config"])
    if not osp.exists(path):
        raise SystemExit(
            f"this program has no {conf['program_config']}: it cannot run "
            f"the streaming configuration of the cell {cell['name']}")
    from sparksched_tpu import config
    from sparksched_tpu.trainers import make_trainer

    out = osp.join(harness.OUT_DIR, cell["name"], "train")
    shutil.rmtree(out, ignore_errors=True)
    cfg = harness.merge(config.load(path), conf.get("overrides", {}))
    cfg = harness.merge(cfg, mix.get("overrides", {}))
    if control:
        cfg = harness.merge(cfg, control)
    cfg["trainer"] |= {"artifacts_dir": out, "checkpointing_freq": 10**9}
    # the trainer's own seed stays the config's, as in `collect_rollout`:
    # it is a compile-time constant of the collector
    cfg["agent"] = dict(cfg["agent"], seed=harness.seed31(seed))
    trainer = make_trainer(cfg)
    built = (trainer.num_envs, trainer.rollout_steps,
             trainer.rollout_duration)
    stated = (mix["lanes"], mix["rollout_steps"],
              float(mix["rollout_duration"]))
    if built != stated:
        raise SystemExit(
            f"the mix states lanes, steps, rollout_duration {stated}, the "
            f"trainer was built with {built}")
    return {"cell": cell, "trainer": trainer, "out": out, "seed": seed,
            "state": None, "carry": None, "last": None, "tail": None,
            "prev": None}


def warm_up(ctx: dict) -> None:
    ctx["state"] = ctx["trainer"].init_state()
    for i in range(int(ctx["cell"]["mix"]["warmup_collections"])):
        _collect_once(ctx, i)


def _call_collector(trainer, params, i: int, rng, carry):
    """The trainer's compiled collector, called as `Trainer.train`
    calls it: rollout, the carry for the next call, telemetry."""
    import jax.numpy as jnp

    return trainer._collect_jit(params, jnp.int32(i), rng, carry)


def _tail(ro, per_lane) -> dict:
    """What the boundary check needs of a rollout, still on the device:
    references to its small leaves, and each lane's last valid row
    (`per_lane`: its valid rows)."""
    import jax.numpy as jnp

    lanes = jnp.arange(ro.valid.shape[0])
    last = jnp.maximum(per_lane - 1, 0)
    return {"valid": ro.valid, "wall_times": ro.wall_times,
            "resets": ro.resets, "final_reset_count": ro.final_reset_count,
            "job_template": ro.obs.job_template,
            "last_remaining": ro.obs.remaining[lanes, last],
            "last_node_mask": ro.obs.node_mask[lanes, last]}


def _collect_once(ctx: dict, i: int) -> dict:
    """Collection number `i`, from where collection `i - 1` stopped:
    seconds, valid decisions, re-seeds flagged, lanes that ended on the
    budget, and the telemetry summary. The seed gives the weights and
    the key the actions are sampled with; the job sequences are the
    trainer's own seed's, for every seed."""
    import jax

    from sparksched_tpu.obs.telemetry import summarize

    trainer, params = ctx["trainer"], ctx["state"].params
    ctx["last"] = None  # frees the rollout before, as the trainer does
    rng = jax.random.fold_in(harness.key_from_seed(ctx["seed"]), i)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(HOST_SPANS[0]):
        ro, ctx["carry"], telem = _call_collector(
            trainer, params, i, rng, ctx["carry"])
        jax.block_until_ready(ro.reward)
    seconds = time.perf_counter() - t0
    ctx["last"] = (params, ro)
    per_lane = ro.valid.sum(axis=1)
    ctx["prev"], ctx["tail"] = ctx["tail"], _tail(ro, per_lane)
    return {"collect_seconds": seconds, "collection": i,
            "decisions": int(per_lane.sum()),
            "reseeds": int(ro.resets.sum()),
            "lanes_on_budget": int((per_lane < ro.valid.shape[1]).sum()),
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
    lane_rows = len(recs) * trainer.num_envs * trainer.rollout_steps
    return {
        "end_to_end": {mix["end_to_end"]: decisions / wall},
        "samples": {"collections": len(recs), "decisions": decisions,
                    "window_s": wall, "asked_s": seconds,
                    "lanes": trainer.num_envs,
                    "rollout_steps": trainer.rollout_steps,
                    "rollout_duration": trainer.rollout_duration,
                    "collect_s_median": per_collection,
                    "lane_row_occupancy": decisions / lane_rows,
                    "reseeds": [r["reseeds"] for r in recs],
                    "lanes_on_budget": [r["lanes_on_budget"] for r in recs]},
        "attempted": len(recs), "failed": 0,
        "scalars": recs,
        "telemetry": [r["telemetry"] for r in recs if r["telemetry"]],
        "trace": trace,
    }


def verify(ctx: dict, window: dict) -> list[dict]:
    trainer = ctx["trainer"]
    params, ro = ctx["last"]
    telemetry = window["telemetry"]
    checks = collect_rollout.sentinel_checks(ctx, window)
    checks += stream_checks(
        ctx["prev"], ctx["tail"], ro, trainer,
        telemetry[-1] if telemetry else None)
    return checks + logprob_check.checks(
        trainer, params, ro, ctx["seed"], ctx["cell"]["config_data"])


def stream_checks(prev: dict, cur: dict, ro, trainer,
                  summary: dict | None) -> list[dict]:
    """The four guarantees of streaming on the last two collections, by
    the plain reference: each check's violations against 0, and the
    episodes the group check saw against the number of groups."""
    import jax

    prev, cur = jax.device_get((prev, cur))

    def rows(lane: int):
        lane = jax.numpy.asarray(lane)  # traced: one program, not 128
        return jax.device_get(
            (ro.obs.remaining[lane], ro.obs.node_mask[lane]))

    found = stream_np.check_stream(
        prev, dict(cur, rows=rows),
        rollout_duration=trainer.rollout_duration,
        rollouts_per_group=trainer.num_rollouts, summary=summary)
    seen = found.pop("stream_episodes_seen")
    return [harness.check(k, v, 0, "==") for k, v in found.items()] + [
        harness.check("stream_episodes_seen", seen,
                      trainer.num_sequences, ">=")]


def close(ctx: dict) -> None:
    shutil.rmtree(ctx["out"], ignore_errors=True)
