"""Chip smoke run: the quickest proof that the system still starts on a TPU.

    python chip_smoke.py             # one chip: trainer, serve, parity
    python chip_smoke.py --chips 4   # four chips: the dp=4 trainer step
                                     # against dp=1, and nothing else

One process, one compile cache (`config.enable_compilation_cache`). It
sets no platform: where `jax.devices()[0].platform` is not "tpu" it
prints `"ok": false` and exits 1, and a phase that raises or fails its
check ends the run the same way. The last line of stdout is one JSON
object, `{"ok": ..., "device": {"platform", "kind", "count"}}`; what
each phase measured goes on earlier lines, one JSON object per phase.
A smoke run, not a benchmark: its seconds include compilation and are
taken once.

Phases (one chip):

- trainer: `train.py`'s path (`config.load` -> `make_trainer(cfg)
  .train()`) on config/decima_tpch.yaml as committed, for
  TRAIN_ITERATIONS iterations. Finite stats, no tripped sentinel, no
  recovery, parameters moved, the update's first minibatch reproduces
  the collector's log-probs (`approx_kl_first`), and the second
  iteration compiled nothing (the runlog's jit hooks as the trainer
  installs them).
- serve: `store_from_config` + `front_from_config` + `ServeServer` on
  an ephemeral loopback port with `ServeClient` in this process, at the
  trainer's cluster and model. Every request answered, no quarantine,
  finite rewards, no compile after warm-up (the jit hooks at threshold
  0, the tests/test_serve.py protocol), served + rejected == scheduled,
  and the donated store still on the TPU.
- parity: the flat engine against the `core.step` collection path for
  PARITY_DECISIONS decisions of one fair-policy episode at the
  flagship `params_env` (time limit and all), both on the chip,
  step-exact, with the duration sampler pinned as
  tests/test_flat_loop.py pins it (the two engines' random streams
  legitimately differ). Where the time limit ends the episode inside
  the scan, the last decision is held to what the two engines promise
  there (`compare_rollouts`).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import os.path as osp
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from sparksched_tpu import config
from sparksched_tpu.obs import runlog as runlog_mod
from sparksched_tpu.obs.memory import device_memory_stats
from sparksched_tpu.trainers import make_trainer

REPO = osp.dirname(osp.abspath(__file__))
REQUIRED_PLATFORM = "tpu"
TRAIN_CONFIG = osp.join(REPO, "config", "decima_tpch.yaml")
MULTICHIP_CONFIG = osp.join(REPO, "config", "decima_tpch_multichip.yaml")
TRAIN_ITERATIONS = 2
# The trainer phase runs the committed 16 lanes x 9600 steps. The
# four-chip phase cuts `rollout_steps`, and nothing else, for time
# alone (two arms of one full iteration each would hold four chips for
# eight minutes), and prints the cut on an earlier line.
DP_ROLLOUT_STEPS = 240
# The update's first minibatch is evaluated at the collector's own
# parameters, so its approx-kl says how far the update's recomputed
# log-probs sit from the recorded ones: float noise, or a fault (PR 25
# found 0.0063 at 384 samples per evaluation on the v5e, 1.4e-7 at 96).
FIRST_KL_MAX = 1e-5
# The four-chip comparison takes its step with plain SGD, which is
# linear in the all-reduced gradient, so that the two layouts' float
# noise stays float noise. Under the committed Adam a parameter whose
# gradient is noise moves a full +-lr per step with a random sign, and
# the two arms part ways on one device as well (PERF.md, PR 25). The
# limits are tests/test_parallel.py's for a sharded against an
# unsharded update from one start.
DP_OPTIMIZER = {"opt_cls": "SGD"}
DP_COS_MIN, DP_DRIFT_MAX = 0.999, 2e-4
SERVE_CFG = {
    "capacity": 256, "hot_capacity": 128, "max_batch": 8,
    "front": "continuous", "donate": True, "trace": True,
}
SERVE_SESSIONS = 32
SERVE_WARMUP_REQUESTS = 64
SERVE_REQUESTS = 640
SERVE_RATE_RPS = 200.0
PARITY_DECISIONS = 300
PARITY_MIN_DECISIONS = 200

_compile_secs = [0.0]


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event.endswith("backend_compile_duration"):
        _compile_secs[0] += float(duration)


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


@contextlib.contextmanager
def phase(name: str):
    """Time one phase; the body fills the dict with what it measured."""
    out: dict = {}
    c0, t0 = _compile_secs[0], time.perf_counter()
    try:
        yield out
    finally:  # a phase that fails still prints what it had measured
        mem = device_memory_stats() or {}
        say(phase=name, seconds=round(time.perf_counter() - t0, 3),
            compile_seconds=round(_compile_secs[0] - c0, 3),
            device_bytes_in_use=mem.get("bytes_in_use"),
            device_peak_bytes=mem.get("peak_bytes_in_use"),
            device_peak_bytes_reserved=mem.get("peak_bytes_reserved"),
            **out)


def check(ok, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def read_runlog(path: str) -> list[dict]:
    with open(path) as fp:
        return [json.loads(ln) for ln in fp]


def compiles_in(recs: list[dict]) -> list[dict]:
    """Programs lowered or compiled: a jit cache miss. A bare
    `jaxpr_trace_duration` is not one: under the rbg keys of the
    flagship config, eager `jax.random.fold_in` (the store's per-call
    key) re-traces its threefry helper on the host at every call and
    compiles nothing; `retraced` reports those by name."""
    return [r for r in recs if r["ev"] == "jit_compile"
            and not r["event"].endswith("jaxpr_trace_duration")]


def retraced(recs: list[dict]) -> dict[str, int]:
    names: dict[str, int] = {}
    for r in recs:
        if (r["ev"] == "jit_compile"
                and r["event"].endswith("jaxpr_trace_duration")):
            name = str(r.get("fun_name"))
            names[name] = names.get(name, 0) + 1
    return names


def tree_delta(new, old) -> np.ndarray:
    return np.concatenate([
        (np.asarray(a, np.float64) - np.asarray(b, np.float64)).ravel()
        for a, b in zip(jax.tree_util.tree_leaves(new),
                        jax.tree_util.tree_leaves(old))
    ])


def load_cfg(path: str, out_dir: str,
             rollout_steps: int | None = None) -> dict:
    cfg = config.load(path)
    cfg["trainer"] |= {
        "num_iterations": TRAIN_ITERATIONS,
        "checkpointing_freq": TRAIN_ITERATIONS,
        "artifacts_dir": out_dir,
    }
    if rollout_steps is not None:
        say(cut="rollout_steps", was=cfg["trainer"]["rollout_steps"],
            now=rollout_steps)
        cfg["trainer"]["rollout_steps"] = rollout_steps
    return cfg


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------


def trainer_phase(out_dir: str):
    with phase("trainer") as out:
        cfg = load_cfg(TRAIN_CONFIG, osp.join(out_dir, "train"))
        trainer = make_trainer(cfg)
        before = jax.device_get(trainer.scheduler.params)
        state = trainer.train()
        recs = []
        for name in sorted(os.listdir(
                osp.join(trainer.artifacts_dir, "runlog"))):
            recs += read_runlog(
                osp.join(trainer.artifacts_dir, "runlog", name))
        scalars = [i for i, r in enumerate(recs) if r["ev"] == "scalars"]
        check(len(scalars) == TRAIN_ITERATIONS,
              f"{len(scalars)} scalars records")
        iters = []
        for i in scalars:
            s = recs[i]
            must = ["policy_loss", "entropy", "approx_kl_div",
                    "avg_num_jobs", "episode_length"]
            if s["num_completed_jobs"] > 0:
                must.append("avg_job_duration")
            bad = [k for k in must if not np.isfinite(s[k])]
            check(not bad, f"non-finite {bad} in {s}")
            check(s["health_mask"] == 0, f"health_mask {s}")
            check(s["episode_length"] > 0, "no decision was taken")
            check(s["approx_kl_first"] < FIRST_KL_MAX,
                  f"the update's log-probs left the collector's: {s}")
            check(s["minibatches_applied"] >= 1,
                  f"no minibatch reached the optimizer: {s}")
            iters.append({k: s[k] for k in (
                "policy_loss", "approx_kl_div", "approx_kl_first",
                "minibatches_applied", "avg_num_jobs",
                "episode_length", "num_completed_jobs",
                "collect_seconds", "update_seconds")})
        tripped = [r for r in recs if r["ev"] in ("health", "recovery")]
        check(not tripped, f"recovery ran: {tripped}")
        warm = compiles_in(recs[scalars[0]:scalars[-1]])
        check(not warm, f"iteration 2 compiled: {warm}")
        moved = np.abs(tree_delta(state.params, before)).max()
        check(np.isfinite(moved) and moved > 0, f"params moved {moved}")
        out.update(
            iterations=iters, lanes=trainer.num_envs,
            rollout_steps=trainer.rollout_steps,
            compiles_first_iteration=len(
                compiles_in(recs[:scalars[0]])),
            compiles_later=len(warm), max_param_delta=float(moved),
        )
    return trainer, state


class _Tap:
    """Keeps every ticket `run_open_loop` submits, for the reward check."""

    def __init__(self, client) -> None:
        self._client = client
        self.tickets: list = []

    def submit(self, sid: int):
        tk = self._client.submit(sid)
        self.tickets.append(tk)
        return tk

    def __getattr__(self, name: str):
        return getattr(self._client, name)


def serve_phase(out_dir: str, trainer, state) -> None:
    from sparksched_tpu.obs.metrics import MetricsRegistry
    from sparksched_tpu.serve import (
        ServeClient,
        ServeServer,
        front_from_config,
        generate_arrivals,
        run_open_loop,
        store_from_config,
    )

    with phase("serve") as out:
        trainer.scheduler.params = state.params  # serve what was trained
        runlog_mod.JIT_MIN_SECS = 0.0  # even a trivial compile lands
        rl = runlog_mod.RunLog(osp.join(out_dir, "serve.jsonl"))
        rl.install_jit_hooks()
        reg = MetricsRegistry()
        t0 = time.perf_counter()
        store = store_from_config(
            SERVE_CFG, trainer.params_env, trainer.bank,
            trainer.scheduler, metrics=reg,
        )
        front = front_from_config(
            SERVE_CFG, store, metrics=reg, runlog=rl, trace=True)
        cold_s = time.perf_counter() - t0
        server = ServeServer(
            store, front, port=0, metrics=MetricsRegistry(), runlog=rl,
        ).start()
        client = ServeClient(
            "127.0.0.1", server.port, workers=SERVE_SESSIONS,
            metrics=MetricsRegistry(), trace=True,
        )
        tap = _Tap(client)
        try:
            # warm-up: every op of the measured window once (create,
            # decide at every batch width the front forms, close)
            run_open_loop(tap, tap, generate_arrivals(
                SERVE_RATE_RPS, SERVE_WARMUP_REQUESTS, SERVE_SESSIONS,
                seed=1))
            rl.write("window_start")
            tap.tickets.clear()
            summary = run_open_loop(tap, tap, generate_arrivals(
                SERVE_RATE_RPS, SERVE_REQUESTS, SERVE_SESSIONS, seed=2))
        finally:
            client.stop()
            server.stop()
        rl.close()
        recs = read_runlog(rl.path)
        start = [r["ev"] for r in recs].index("window_start")
        warm = compiles_in(recs[start:])
        results = [tk.result for tk in tap.tickets]
        rec = summary["reconcile"]
        check(summary["tenants"] == SERVE_SESSIONS, f"{summary}")
        check(summary["completed"] == SERVE_REQUESTS
              and summary["errors"] == 0
              and all(r is not None for r in results),
              f"unanswered or failed requests: {summary}")
        check(rec["served"] + rec["rejected_requests"] == rec["requests"],
              f"reconcile {rec}")
        check(store.stats["serve_quarantines"] == 0
              and not any(r.health_mask for r in results),
              f"quarantines: {store.stats}")
        check(all(np.isfinite(r.reward) and np.isfinite(r.wall_time)
                  for r in results), "non-finite reward")
        check(not warm, f"compiled after warm-up: {warm}")
        leaf = jax.tree_util.tree_leaves(store._stores[0])[0]
        check({d.platform for d in leaf.devices()} == {REQUIRED_PLATFORM},
              f"store lives on {leaf.devices()}")
        lat = summary["hist"].summary("_ms")
        out.update(
            requests=summary["completed"],
            decisions=int(sum(r.decided for r in results)),
            store_decisions=store.stats["serve_decisions"],
            cold_start_seconds=round(cold_s, 3),
            achieved_rps=summary["achieved_rps"],
            p50_ms=lat.get("p50_ms"), p99_ms=lat.get("p99_ms"),
            compiles_after_warmup=len(warm),
            host_retraces_after_warmup=retraced(recs[start:]),
            store_devices=sorted(str(d) for d in leaf.devices()),
        )


def compare_rollouts(ro_core, ro_flat, time_limit: float) -> dict:
    """The flat engine's rollout against the `core.step` path's, from
    one start: every decision, its time and its reward equal. One
    difference is by design, and is held to its rule. `core.step`
    looks at the episode's time limit where the reference's wrapper
    does, when it is back at a decision, so a truncated episode's last
    step runs on to the first decision past the limit. The flat engine
    looks after every event (`flat_loop._lane_done`, the bulk passes'
    `stop_at_limit`) and freezes at the first event at or past the
    limit. The last decision's span in the flat engine is therefore a
    prefix of the core path's: it ends no earlier than the limit and no
    later than the core path's, and its reward (job-time over the
    span, never positive) is no larger in size."""
    nv = int(ro_core.valid.sum())
    np.testing.assert_array_equal(ro_core.valid, ro_flat.valid)
    for name in ("stage_idx", "job_idx", "num_exec_k"):
        np.testing.assert_array_equal(
            getattr(ro_core, name)[:nv], getattr(ro_flat, name)[:nv],
            err_msg=name)
    np.testing.assert_allclose(
        ro_core.wall_times[:nv], ro_flat.wall_times[:nv], rtol=1e-6)
    check(np.isfinite(ro_flat.reward).all(), "non-finite reward")
    end_core = float(ro_core.wall_times[nv])
    end_flat = float(ro_flat.wall_times[nv])
    truncated = nv < ro_core.valid.shape[0] and end_core >= time_limit
    whole = nv - 1 if truncated else nv
    np.testing.assert_allclose(
        ro_core.reward[:whole], ro_flat.reward[:whole],
        rtol=1e-4, atol=1e-4)
    if truncated:
        last_core = float(ro_core.reward[nv - 1])
        last_flat = float(ro_flat.reward[nv - 1])
        check(bool(ro_core.final_state.truncated),
              "core path past the limit and not truncated")
        check(time_limit <= end_flat <= end_core * (1 + 1e-6),
              f"flat engine froze at {end_flat}, limit {time_limit}, "
              f"core path's next decision at {end_core}")
        check(last_core * (1 + 1e-4) - 1e-4 <= last_flat <= 0.0,
              f"last reward: flat {last_flat}, core {last_core}")
    else:
        np.testing.assert_allclose(end_core, end_flat, rtol=1e-6)
    return {"decisions": nv, "truncated_at_limit": truncated,
            "time_limit": time_limit, "end_core": end_core,
            "end_flat": end_flat}


def parity_phase(trainer) -> None:
    from sparksched_tpu.env import core
    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.trainers.rollout import (
        collect_flat_sync_batch,
        collect_sync,
    )

    params, bank = trainer.params_env, trainer.bank
    T = PARITY_DECISIONS

    def det_sampler(params, bank, rng, facts, template, stage, num_local,
                    task_valid, same_stage):
        return (bank.rough_duration[template, stage]
                + jnp.where(task_valid & same_stage, 7.0, 131.0)
                + 17.0 * stage.astype(jnp.float32))

    def fair(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    def fair_batch(rng, obs):
        si, ne, _ = jax.vmap(lambda o: fair(rng, o))(obs)
        return si, ne, {}

    with phase("parity") as out:
        sampler = core.sample_task_duration
        core.sample_task_duration = det_sampler
        try:
            state0 = core.reset(params, bank, jax.random.PRNGKey(3))
            ro_core = jax.jit(lambda s, k: collect_sync(
                params, bank, fair, k, T, s))(
                    state0, jax.random.PRNGKey(0))
            # another collector key on purpose: nothing compared may
            # depend on it. The trainer's collector over a batch of one
            # lane, unstacked again for the comparison
            ro_flat = jax.jit(lambda s, k: collect_flat_sync_batch(
                params, bank, fair_batch, k, T, s,
                **trainer.flat_knobs))(
                    jax.tree_util.tree_map(lambda a: a[None], state0),
                    jax.random.PRNGKey(1))
            ro_flat = jax.tree_util.tree_map(lambda a: a[0], ro_flat)
            ro_core, ro_flat = jax.device_get((ro_core, ro_flat))
        finally:
            core.sample_task_duration = sampler
        out.update(compare_rollouts(
            ro_core, ro_flat, float(state0.time_limit)))
        check(out["decisions"] >= PARITY_MIN_DECISIONS,
              f"the episode took {out['decisions']} decisions")


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def dp_phase(out_dir: str) -> None:
    """One PPO iteration (collect + update) at dp=4 against the same at
    dp=1 on device 0, same seed: the rollout's lanes on four distinct
    devices, rollouts step-exact, the same minibatches reaching the
    optimizer, and the updated parameters within the tolerance of
    tests/test_parallel.py (the two moves nearly parallel, cos > 0.999,
    and no parameter further apart than 2e-4). The optimizer is
    DP_OPTIMIZER, printed as an override of the committed config."""
    with phase("dp4_vs_dp1") as out:
        runs = {}
        for dp in (4, 1):
            cfg = load_cfg(MULTICHIP_CONFIG, osp.join(out_dir, f"dp{dp}"),
                           DP_ROLLOUT_STEPS)
            cfg["parallel"] = {"dp": dp}
            say(override=DP_OPTIMIZER,
                was={k: cfg["trainer"].get(k) for k in DP_OPTIMIZER})
            cfg["trainer"] |= DP_OPTIMIZER
            t = make_trainer(cfg)
            state = t.init_state()
            state = state.replace(
                rng=jax.random.fold_in(jax.random.PRNGKey(t.seed), 0))
            t0 = time.perf_counter()
            ro, _, _ = t._collect_jit(
                state.params, state.iteration, state.rng, None)
            jax.block_until_ready(ro.reward)
            t1 = time.perf_counter()
            new, stats = t._update_jit(state, ro)
            jax.block_until_ready(new.params)
            t2 = time.perf_counter()
            stats = {k: float(v) for k, v in stats.items()
                     if v is not None}
            check(all(np.isfinite(v) for v in stats.values())
                  and not stats.get("health_mask"), f"dp={dp} {stats}")
            check(stats["approx_kl_first"] < FIRST_KL_MAX,
                  f"dp={dp}: the update's log-probs left the "
                  f"collector's: {stats}")
            runs[dp] = {
                "ro": ro, "stats": stats,
                "move": tree_delta(jax.device_get(new.params),
                                   jax.device_get(state.params)),
                "collect_seconds": round(t1 - t0, 3),
                "update_seconds": round(t2 - t1, 3),
            }
        devs = runs[4]["ro"].reward.sharding.device_set
        check(len({d.id for d in devs}) == 4
              and all(d.platform == REQUIRED_PLATFORM for d in devs),
              f"dp=4 rollout lives on {devs}")
        check(len(runs[1]["ro"].reward.sharding.device_set) == 1,
              "dp=1 rollout is not on one device")
        l4, tree4 = jax.tree_util.tree_flatten(
            jax.device_get(runs[4]["ro"]))
        l1, tree1 = jax.tree_util.tree_flatten(
            jax.device_get(runs[1]["ro"]))
        check(tree4 == tree1, "rollout structures differ")
        for a, b in zip(l4, l1):
            np.testing.assert_array_equal(a, b)
        check(bool(runs[4]["ro"].valid.any()), "no decision was taken")
        d4, d1 = runs[4]["move"], runs[1]["move"]
        cos = float(d4 @ d1 / (
            np.linalg.norm(d4) * np.linalg.norm(d1) + 1e-30))
        drift = float(np.abs(d4 - d1).max())
        out.update(
            device_set=sorted(str(d) for d in devs),
            decisions=int(runs[4]["ro"].valid.sum()),
            cos=cos, max_drift=drift, max_move=float(np.abs(d1).max()),
            **{f"dp{dp}_{k}": runs[dp][k] for dp in (4, 1)
               for k in ("collect_seconds", "update_seconds", "stats")},
        )
        applied = [runs[dp]["stats"]["minibatches_applied"]
                   for dp in (4, 1)]
        check(applied[0] == applied[1] >= 1,
              f"minibatches applied: dp=4 {applied[0]}, dp=1 {applied[1]}")
        check(cos > DP_COS_MIN and drift < DP_DRIFT_MAX,
              f"updates disagree: cos {cos}, largest gap {drift}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument(
        "--out", default=osp.join(REPO, "chiprun_out", "chip_smoke"),
        help="directory for runlogs and checkpoints of the run")
    args = ap.parse_args(argv)

    config.enable_compilation_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    ok = False
    try:
        check(device["platform"] == REQUIRED_PLATFORM
              and device["count"] == args.chips,
              f"need {args.chips} {REQUIRED_PLATFORM} device(s), "
              f"jax.devices() gives {device}")
        os.makedirs(args.out, exist_ok=True)
        say(start=True, device=device, jax=jax.__version__,
            compile_cache=jax.config.jax_compilation_cache_dir
            or os.environ.get("JAX_COMPILATION_CACHE_DIR"))
        if args.chips == 4:
            dp_phase(args.out)
        else:
            trainer, state = trainer_phase(args.out)
            serve_phase(args.out, trainer, state)
            parity_phase(trainer)
        ok = True
    except Exception:
        traceback.print_exc()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
