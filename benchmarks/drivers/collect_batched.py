"""The trainer's sync collector under BATCHED ARRIVALS (the Decima
paper's section 7.2): `collect_rollout`'s cell with every job of an
episode at t=0, no time limit, and a scan as long as the longest
episode, so that every lane's episode ends inside it by completion.

`warm_up`, `measure`, `close` and `sentinel_checks` are
`collect_rollout`'s, and so is what `build` does: the configuration file
loads the program's `config/decima_tpch_batched.yaml`, whose
`env.num_init_jobs` puts the whole batch at t=0. `build` first looks for
that file and for the field `num_init_jobs` of the program's
`EnvParams`, and ends at once where either is missing: a program
without the field SKIPS the key (`config.env_params_from_cfg`) and would
run Poisson arrivals at a cap of 20 under this cell's name. Neither look
touches jax.

`verify`, outside the window, on the window's LAST collection: the
sentinels and counts of `collect_rollout`; the batch guarantees by a
plain numpy reading of the rollout and the state it ended in
(`benchmarks/reference/batched_np.py`: the whole batch at the first
decision and nothing later, an end only with every job complete, no
valid row whose stored observation is empty, one batch a sequence
group, the program's counters equal to the reading),
each count against 0 and the share of lanes that ended by completion
against the configuration's `limits.episodes_terminated_share`; and the
seeded sample of stored decisions against the plain forward pass
(`benchmarks/logprob_check.py`).
"""

from __future__ import annotations

import os.path as osp

from benchmarks import harness, logprob_check
from benchmarks.drivers import collect_rollout
from benchmarks.reference import batched_np

HOST_SPANS = collect_rollout.HOST_SPANS
UNATTRIBUTED = collect_rollout.UNATTRIBUTED
FIELD = "num_init_jobs"


def build(cell: dict, seed: int, *, seconds: float = 0.0,
          control: dict | None = None, trace: bool = False) -> dict:
    conf = cell["config_data"]
    if not osp.exists(osp.join(harness.ROOT, conf["program_config"])):
        raise SystemExit(
            f"this program has no {conf['program_config']}: it cannot run "
            f"the batched-arrivals configuration of the cell {cell['name']}")
    import dataclasses

    from sparksched_tpu.config import EnvParams

    if FIELD not in {f.name for f in dataclasses.fields(EnvParams)}:
        raise SystemExit(
            f"this program's EnvParams has no field {FIELD}: it would skip "
            f"the key and run the cell {cell['name']} under Poisson arrivals")
    ctx = collect_rollout.build(
        cell, seed, seconds=seconds, control=control, trace=trace)
    env, stated = ctx["trainer"].params_env, conf["env"]
    built = (env.num_init_jobs, env.max_jobs, env.num_executors,
             env.mean_time_limit)
    want = (stated[FIELD], stated["job_arrival_cap"],
            stated["num_executors"], stated["mean_time_limit"])
    if built != want:
        raise SystemExit(
            f"the configuration states {FIELD}, cap, executors, limit "
            f"{want}; the trainer was built with {built}")
    return ctx


warm_up = collect_rollout.warm_up
measure = collect_rollout.measure
close = collect_rollout.close


def rollout_arrays(ro) -> dict:
    """What the plain reference reads of a rollout, on the host: the
    small leaves of the stored rows and of the state it ended in, and of
    the four wide leaves of a stored observation ([lanes, rows, F]; a
    gigabyte each at size) whether a row holds anything, reduced on the
    device."""
    import jax
    import numpy as np

    final, obs = ro.final_state, ro.obs
    col = jax.device_get({
        "valid": ro.valid, "resets": ro.resets,
        "job_mask": obs.job_mask,
        "row_has": {
            "node": obs.node_mask.any(-1),
            "schedulable": obs.schedulable.any(-1),
            "remaining": (obs.remaining > 0).any(-1),
            "duration": (obs.duration > 0).any(-1)},
        "job_template": obs.job_template[:, 0],
        "final_num_jobs": final.num_jobs,
        "final_arrival_time": final.job_arrival_time,
        "final_completed": final.job_t_completed})
    col["final_completed"] = np.isfinite(col["final_completed"])
    return col


def batched_checks(col: dict, trainer, summary: dict | None,
                   limits: dict) -> list[dict]:
    """The batch guarantees on one rollout's arrays, by the plain
    reference: each count of violations against 0, the share of lanes
    that ended by completion against its limit."""
    found = batched_np.check_batched(
        col, batch_jobs=trainer.params_env.num_init_jobs,
        rollouts_per_group=trainer.num_rollouts, summary=summary)
    share = found.pop("episodes_terminated_share")
    return [harness.check(k, v, 0, "==") for k, v in found.items()] + [
        harness.check("episodes_terminated_share", share,
                      float(limits["episodes_terminated_share"]), ">=")]


def verify(ctx: dict, window: dict) -> list[dict]:
    conf = ctx["cell"]["config_data"]
    params, ro = ctx["last"]
    telemetry = window["telemetry"]
    checks = collect_rollout.sentinel_checks(ctx, window)
    checks += batched_checks(
        rollout_arrays(ro), ctx["trainer"],
        telemetry[-1] if telemetry else None, conf["limits"])
    return checks + logprob_check.checks(
        ctx["trainer"], params, ro, ctx["seed"], conf)
