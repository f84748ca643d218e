"""One-process demo of the online learning loop (ISSUE 14).

Closes the serve->learn->serve loop end to end and MEASURES it —
nothing here is asserted on faith:

1. a record-on AOT `SessionStore` + `ContinuousBatcher` serves a
   seeded open-loop schedule (`serve/loadgen.py`) while a BACKGROUND
   `OnlineLearner` thread drains served-decision trajectories and runs
   `ppo_update` (health gates on) on them, publishing accepted param
   versions through the `ParamBus`, which the serving thread applies
   between compiled calls (`run_open_loop(on_poll=bus.pump)`);
2. the measured window is pinned ZERO-RECOMPILE via the runlog jit
   hooks at threshold 0 (the tests/test_serve.py warm-path protocol):
   hot swaps land mid-traffic and no serve/learner program retraces;
3. record-on overhead is an interleaved A/B against a record-off
   partner store at the SAME offered load (median-of-reps, arms
   interleaved rep-by-rep — the PR-11 protocol), with a warm
   batch-window A/B alongside as the queueing-free measure.

Since round 20 (ISSUE 18) the record path runs through the
device-resident trajectory ring: decides append their full record
into a donated on-device ring and the host drains ONE batched
transfer per cadence, so the loop's record cost is the drain, not a
per-decision sync. ONLINE_LOOP_RING=0 restores the r16 per-decision
path; the artifact stamps the ring counters (occupancy / drains /
records / dropped — drops are counted, never silent).

Artifact: artifacts/online_loop_r20.json — swap/rollback counts and
the zero-recompile pin, learner steps with losses and the per-update
reward trend, trajectory-buffer accounting (drops are counted, never
silent), the ring drain accounting, and the record-overhead A/B
block. PERF_ROUNDS.md rounds 16/20 document the row schema.

Env knobs: ONLINE_LOOP_REQUESTS (default 240), ONLINE_LOOP_RATE_RPS
(25), ONLINE_LOOP_TENANTS (4), ONLINE_LOOP_AB_REPS (5),
ONLINE_LOOP_SLO_MS (200), ONLINE_LOOP_RING (16; 0 = per-decision
record path).
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax

from sparksched_tpu.config import EnvParams
from sparksched_tpu.obs import runlog as runlog_mod
from sparksched_tpu.obs.metrics import (
    MetricsRegistry,
    interleaved_ab,
    paired_ab_pct,
    percentile_block,
)
from sparksched_tpu.obs.runlog import RunLog, emit
from sparksched_tpu.online import online_from_config
from sparksched_tpu.schedulers import DecimaScheduler
from sparksched_tpu.serve import (
    ContinuousBatcher,
    SessionStore,
    generate_arrivals,
    run_open_loop,
)
from sparksched_tpu.workload import make_workload_bank

ARTIFACT = "artifacts/online_loop_r20.json"

AGENT_CFG = {
    "agent_cls": "DecimaScheduler",
    "embed_dim": 8,
    "gnn_mlp_kwargs": {"hid_dims": [16]},
    "policy_mlp_kwargs": {"hid_dims": [16]},
    "job_bucket": 8,
}

ONLINE_CFG = {
    "max_trajectories": 64,
    "max_steps": 16,
    "batch_trajectories": 4,
    "min_decisions": 2,
    "max_param_lag": 4,
    "swap_every": 1,
    "probation_decisions": 16,
    "max_quarantine_rate": 0.5,
    "learner": {"num_epochs": 2, "num_batches": 2},
    "seed": 7,
}


def _setup():
    # mid scale (16-job cap): large enough that the record path's
    # FIXED per-call host cost (~0.1 ms: extra output bookkeeping +
    # leaf conversion) amortizes against a ~5 ms decision batch — the
    # tiny test-scale env sits right at the 5% bar, production scale
    # well under it (the bench online arm measures that end)
    params = EnvParams(
        num_executors=10, max_jobs=16, max_stages=20, max_levels=20,
        mean_time_limit=None,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )
    sched = DecimaScheduler(
        num_executors=params.num_executors,
        **{k: v for k, v in AGENT_CFG.items() if k != "agent_cls"},
    )
    return params, bank, sched


def _drive(store, front, arrivals, slo_ms, on_poll=None,
           session_seed=30_000):
    summary = run_open_loop(
        store, front, arrivals, slo_ms=slo_ms,
        session_seed=session_seed, on_poll=on_poll,
    )
    samples = summary.pop("samples_ms")
    summary.pop("hist")
    return summary, samples


def main() -> int:
    n_req = int(os.environ.get("ONLINE_LOOP_REQUESTS", 240))
    rate = float(os.environ.get("ONLINE_LOOP_RATE_RPS", 25))
    tenants = int(os.environ.get("ONLINE_LOOP_TENANTS", 4))
    ab_reps = int(os.environ.get("ONLINE_LOOP_AB_REPS", 7))
    slo_ms = float(os.environ.get("ONLINE_LOOP_SLO_MS", 200))
    ring_size = int(os.environ.get("ONLINE_LOOP_RING", 16))
    seed = 11

    params, bank, sched = _setup()
    runlog = RunLog.create("artifacts", name="online_loop")
    reg = MetricsRegistry()
    t0 = time.perf_counter()
    store = SessionStore(
        params, bank, sched, capacity=2 * tenants, max_batch=4,
        seed=0, record=True, ring=ring_size, runlog=runlog,
        metrics=reg,
    )
    cold_s = time.perf_counter() - t0
    buffer, learner, bus = online_from_config(
        ONLINE_CFG, store, AGENT_CFG, runlog=runlog, metrics=reg
    )
    emit(f"[online-loop] store cold start {cold_s:.1f}s; warming up")

    # ---- pre-window warmup: compile the learner update and absorb
    # first-occurrence host glue (fold_in etc.) OUTSIDE the pinned
    # window, exactly like the warm-path test
    warm_secs = learner.warmup()
    warm_front = ContinuousBatcher(store, metrics=reg)
    warm_arrivals = generate_arrivals(
        rate, max(4 * tenants, 24), tenants, seed=seed + 1
    )
    _drive(store, warm_front, warm_arrivals, slo_ms,
           on_poll=bus.pump, session_seed=29_000)
    while learner.ready():
        learner.step()
    bus.pump()
    emit(
        f"[online-loop] warmup done (learner compile {warm_secs:.1f}s,"
        f" version {learner.version}); entering pinned window"
    )

    # ---- the measured window: live traffic + background learner +
    # hot swaps, pinned zero-recompile via the jit hooks at
    # threshold 0
    runlog_mod.JIT_MIN_SECS, prev_thresh = 0.0, runlog_mod.JIT_MIN_SECS
    pin = RunLog("artifacts/online_loop_pin.jsonl")
    pin.install_jit_hooks()
    swaps0 = store.stats["serve_param_swaps"]
    version0 = store.params_version
    steps0 = learner.stats["learner_steps"]
    front = ContinuousBatcher(store, metrics=reg, runlog=runlog,
                              trace=True)
    store.trace = True
    arrivals = generate_arrivals(rate, n_req, tenants, seed=seed)
    learner.start_background()
    try:
        summary, samples = _drive(
            store, front, arrivals, slo_ms, on_poll=bus.pump
        )
    finally:
        learner.stop()
        store.trace = False
    # in-window accounting BEFORE the drain pump: a swap published at
    # the window's tail but applied below landed outside the measured
    # traffic
    swaps_in_window = store.stats["serve_param_swaps"] - swaps0
    steps_in_window = learner.stats["learner_steps"] - steps0
    pin.close()
    bus.pump()
    runlog_mod.JIT_MIN_SECS = prev_thresh
    with open(pin.path) as fp:
        compiles = [
            json.loads(ln) for ln in fp
            if json.loads(ln)["ev"].startswith("jit_compile")
        ]
    lat = percentile_block(samples)
    emit(
        f"[online-loop] window: {summary['completed']} decisions, "
        f"goodput {summary['goodput_rps']} rps, "
        f"{swaps_in_window} hot swaps "
        f"(v{version0} -> v{store.params_version}), "
        f"{steps_in_window} learner steps, "
        f"{len(compiles)} recompiles"
    )

    # ---- record-on vs record-off A/B at the same offered load,
    # arms interleaved rep-by-rep (PR-11 protocol)
    emit("[online-loop] building record-off partner store for the A/B")
    store_off = SessionStore(
        params, bank, sched, capacity=2 * tenants, max_batch=4,
        seed=0, record=False,
    )
    # both A/B arms run bare (no collector, no metrics): the A/B
    # isolates the record PATH's serving cost; trajectory assembly is
    # the loop's cost, measured by the window above
    store.collector, store.metrics = None, None
    ab_arrivals = generate_arrivals(
        rate, n_req, tenants, seed=seed + 2
    )

    def one_arm(st):
        f = ContinuousBatcher(st)
        s, smp = _drive(st, f, ab_arrivals, slo_ms,
                        session_seed=31_000)
        return percentile_block(smp)["mean_ms"]

    runs: dict[str, list[float]] = {"off": [], "on": []}
    for rep in range(max(1, ab_reps)):
        # alternate the within-pair order so ordering bias cancels
        # along with the drift the pairing removes
        order = ("off", "on") if rep % 2 == 0 else ("on", "off")
        for label in order:
            runs[label].append(
                one_arm(store if label == "on" else store_off)
            )
    med = {
        k: sorted(v)[len(v) // 2] for k, v in runs.items()
    }
    # PAIRED per-rep statistic: run-granularity reps are few and
    # expensive, and box drift is monotone across them — the median
    # per-pair ratio cancels it (obs.metrics.paired_ab_pct)
    open_loop_pct = paired_ab_pct(runs["off"], runs["on"])

    # the queueing-free measure: warm full-batch decide windows,
    # interleaved medians (the obs-overhead protocol)
    sids_on = [store.create(seed=40 + i) for i in range(4)]
    sids_off = [store_off.create(seed=40 + i) for i in range(4)]

    def rotate(st, sids):
        for j, s in enumerate(sids):
            try:
                st._check_sid(s)
            except Exception:
                st.close(s)
                sids[j] = st.create(seed=400 + j)

    def win(st, sids):
        t0 = time.perf_counter()
        rs = st.decide_batch(sids)
        dt = time.perf_counter() - t0
        if any(r.done or r.health_mask for r in rs):
            rotate(st, sids)
        return dt

    t_off, t_on, window_pct = interleaved_ab(
        lambda: win(store_off, sids_off),
        lambda: win(store, sids_on),
        warmups=3, reps=max(40, ab_reps),
    )
    store.collector, store.metrics = buffer, reg
    passed = open_loop_pct <= 5.0
    emit(
        f"[online-loop] record overhead: open-loop {open_loop_pct:+.2f}%"
        f" (median mean-latency {med['off']:.2f} -> {med['on']:.2f} "
        f"ms), warm-window {window_pct:+.2f}% — "
        f"{'PASS' if passed else 'FAIL'} vs 5% bar"
    )

    reward_trend = [
        {
            "version": h.get("version"),
            "policy_loss": round(h["policy_loss"], 6),
            "kl": round(h["approx_kl_div"], 6),
            "traj_reward_mean": round(h["traj_reward_mean"], 2),
            "accepted": h["accepted"],
        }
        for h in learner.history
    ]
    artifact = {
        "protocol": {
            "loop": "open-loop seeded schedule through a record-on "
                    "ring-drained ContinuousBatcher store; "
                    "background learner "
                    "thread drains trajectories and publishes via "
                    "ParamBus; swaps applied between compiled calls "
                    "(run_open_loop on_poll)",
            "zero_recompile": "runlog jit hooks at threshold 0 over "
                              "the whole window (warm-path test "
                              "protocol); learner update pre-compiled "
                              "in warmup",
            "record_ab": "record-on vs record-off store at the same "
                         "seeded offered load, arms interleaved "
                         "rep-by-rep, median per-rep mean latency "
                         "compared; warm-window A/B (interleaved "
                         "medians over full-batch decide calls) as "
                         "the queueing-free companion",
            "offered_rps": rate,
            "requests": n_req,
            "tenants": tenants,
            "slo_ms": slo_ms,
            "ab_reps": ab_reps,
            "backend": jax.default_backend(),
            "cold_start_s": round(cold_s, 2),
            "learner_compile_s": round(warm_secs, 2),
        },
        "window": {
            "open_loop": summary,
            "latency": lat,
            "hot_swaps": swaps_in_window,
            "params_version": {
                "start": version0, "end": store.params_version,
            },
            "rollbacks": store.stats["serve_param_rollbacks"],
            "zero_recompile": len(compiles) == 0,
            "jit_compile_records": len(compiles),
            # ISSUE 18: the ring drain accounting for the whole run —
            # records is every decision that rode the device ring,
            # dropped counts overrun losses (must be 0 at the default
            # cadence)
            "ring": {
                "size": ring_size,
                "drain": getattr(store, "ring_drain", None),
                **{
                    k: int(store.stats[k]) for k in (
                        "serve_ring_occupancy", "serve_ring_drains",
                        "serve_ring_records", "serve_ring_dropped",
                    )
                },
            },
        },
        "learner": {
            "steps": learner.stats["learner_steps"],
            "rejected": learner.stats["learner_rejected"],
            "published": learner.stats["learner_published"],
            "health_gates": "enabled (in-JIT minibatch skip + "
                            "post-update mask rollback)",
            "losses_finite": all(
                h["policy_loss"] == h["policy_loss"]
                and abs(h["policy_loss"]) != float("inf")
                for h in learner.history
            ),
            "reward_trend": reward_trend,
        },
        "trajectories": dict(buffer.stats),
        "bus": dict(bus.stats),
        "record_overhead": {
            "open_loop_pct": round(open_loop_pct, 2),
            "open_loop_mean_ms": {
                "off": round(med["off"], 3),
                "on": round(med["on"], 3),
                "reps": runs,
            },
            "window_pct": round(window_pct, 2),
            "window_ms": {
                "off": round(t_off * 1e3, 3),
                "on": round(t_on * 1e3, 3),
            },
            "passed": passed,
            "bar_pct": 5.0,
        },
    }
    os.makedirs(os.path.dirname(ARTIFACT) or ".", exist_ok=True)
    with open(ARTIFACT, "w") as fp:
        json.dump(artifact, fp, indent=1)
    runlog.close()
    emit(f"[online-loop] wrote {ARTIFACT}")

    ok = (
        swaps_in_window >= 1
        and len(compiles) == 0
        and learner.stats["learner_steps"] >= 2
        and artifact["learner"]["losses_finite"]
        and passed
    )
    emit(f"[online-loop] {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
