"""Decima-policy benchmarks (BASELINE.md configs #3/#4).

Prints one JSON line per configuration:

  {"metric": "decima_infer_steps_per_sec_64envs", ...}
  {"metric": "ppo_train_steps_per_sec_1024envs", ...}

Unlike bench.py (the driver's single headline metric), this script
records the Decima-path numbers VERDICT r1 flagged as missing: policy
inference throughput in the rollout loop, and end-to-end PPO training
throughput (collect + update) per decision step. Since round 6 each
measurement runs on a selectable rollout engine — `core` (per-decision
`core.step` scan) or `flat` (the flat micro-step engine,
trainers/rollout.py:collect_flat_sync) — and EVERY emitted row records
`engine` and `backend` in its config so a CPU-fallback artifact can
never be mistaken for a chip number.

Reference anchors: examples.py:64-81 (Decima episode), trainers
rollout/PPO pipeline (trainer.py:85-162); neither publishes numbers
(BASELINE.md) — vs_baseline is against the 50k steps/s north-star.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax

from sparksched_tpu.config import EnvParams
from sparksched_tpu.env import core
from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros_like
from sparksched_tpu.schedulers import DecimaScheduler
from sparksched_tpu.trainers.ppo import PPO
from sparksched_tpu.trainers.rollout import (
    collect_flat_sync,
    collect_flat_sync_batch,
    collect_sync,
    flat_micro_group_budget,
)
from sparksched_tpu.workload import bank_dtype_label, make_workload_bank

TARGET = 50_000.0
# stamp every row with engine-telemetry (micro-step composition,
# straggler ratio — sparksched_tpu/obs/telemetry.py); BENCH_TELEMETRY=0
# turns it off, as in bench.py
TELEMETRY = os.environ.get("BENCH_TELEMETRY", "1") == "1"

# static-analyzer stamp on every row (once per process, CPU-pinned
# subprocess; BENCH_ANALYSIS=0 stamps null, crash/timeout stamps false
# — semantics live in sparksched_tpu/analysis:analysis_clean_stamp)
from sparksched_tpu.analysis import analysis_clean_stamp  # noqa: E402

# `memory` block on every row (ISSUE 5): runtime allocator stats
# (mem_peak_bytes, null off-chip) + the lane-fit prediction for the
# row's own collection program — the per-lane collectors fit via
# vmap-tracing, the batch (fastpath) collector via a batched tracer,
# and the PPO rows via the memoized registry micro_step proxy (their
# collection program is the trainer's own jit). BENCH_MEMFIT=0 skips
# the trace-time predictions; runtime stats are always stamped.
from sparksched_tpu.obs.memory import memory_row_stamp  # noqa: E402

MEMFIT = os.environ.get("BENCH_MEMFIT", "1") == "1"

# ISSUE 17 satellite: resume the headline bench series. Every row any
# bench in this file emits is also collected here, and main() writes
# the lot as a top-level `BENCH_rNN.json` summary (round from
# BENCH_ROUND, default 20 — the ISSUE 18 ring round; the series
# resumed at r19 after stalling at BENCH_r05.json).
# The perf ledger (sparksched_tpu/obs/ledger.py) indexes that file as
# the round's anchor. BENCH_SUMMARY=0 skips the write (sub-benches
# invoked standalone by other harnesses should not stamp a round).
_SUMMARY_ROWS: list[dict] = []


def _emit_row(row: dict) -> None:
    _SUMMARY_ROWS.append(row)
    print(json.dumps(row), flush=True)
    # rewrite the summary artifact after EVERY row: a bench run killed
    # mid-series (box timeout, ^C) still leaves a valid round artifact
    # holding exactly the rows it measured
    _write_bench_summary(quiet=True)


def _write_bench_summary(quiet: bool = False) -> None:
    if os.environ.get("BENCH_SUMMARY", "1") != "1":
        return
    rnd = int(os.environ.get("BENCH_ROUND", "20"))
    # carried headline anchors: the standing in-process serving
    # headlines, restated at this round so the series carries them
    # forward explicitly. `carried: true` + `source` mark them as
    # re-anchored prior measurements, not fresh runs of this round.
    anchors: list[dict] = []

    def _carry(metric: str, value, unit: str, source: str) -> None:
        if value is not None:
            anchors.append({
                "metric": metric, "value": value, "unit": unit,
                "carried": True, "source": source,
            })

    try:
        with open("artifacts/serve_scale_r17.json") as fp:
            slo = json.load(fp)["protocol"]["sustained_rps_slo"]
        _carry("sustained_rps_slo_continuous", slo.get("continuous"),
               "rps", "artifacts/serve_scale_r17.json")
    except (OSError, KeyError, ValueError):
        pass
    try:
        with open("artifacts/serve_scale_r18.json") as fp:
            rows = json.load(fp)["rows"]
        loop = [r for r in rows
                if r.get("metric") == "serve_scale_net50rps_loopback"]
        if loop:
            _carry("serve_scale_net50rps_loopback",
                   loop[-1].get("value"), loop[-1].get("unit", ""),
                   "artifacts/serve_scale_r18.json")
    except (OSError, KeyError, ValueError):
        pass
    out = {
        "n": rnd,
        "round": rnd,
        "schema": "bench_summary_v1",
        "cmd": "python bench_decima.py",
        "rows": _SUMMARY_ROWS,
        "anchors": anchors,
        "env": {k: v for k, v in sorted(os.environ.items())
                if k.startswith(("DEC_BENCH_", "SERVE_BENCH",
                                 "SERVE_SCALE_BENCH", "BENCH_",
                                 "JAX_PLATFORMS"))},
    }
    path = f"BENCH_r{rnd:02d}.json"
    # atomic replace: a run killed mid-write must never leave a
    # truncated artifact for the ledger's coverage gate to trip on
    with open(path + ".tmp", "w") as fp:
        json.dump(out, fp, indent=1)
    os.replace(path + ".tmp", path)
    if not quiet:
        print(f"# wrote {path}: {len(_SUMMARY_ROWS)} rows + "
              f"{len(anchors)} carried anchors", flush=True)


def _registry_proxy_stamp() -> dict:
    """Memory stamp for rows without a per-lane collection program:
    allocator stats + the registry micro_step lane-fit (memoized in
    sparksched_tpu/analysis/memory.py, labeled so the row cannot be
    read as a fit of the trainer's own jit)."""
    out = memory_row_stamp()
    if not MEMFIT:
        return out
    try:
        from sparksched_tpu.analysis.memory import registry_lane_fit

        out["lane_fit"] = {"program": "registry:micro_step"} | (
            registry_lane_fit(("micro_step",))["micro_step"]
        )
    except Exception as e:
        out["lane_fit"] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}
    return out


def _inference_mem_stamp(params, bank, engine, steps, pol, bpol, knobs,
                         micro_groups, states) -> dict:
    """Per-row memory block for the inference benches: the row's own
    collection program, lane-fitted at the production lane range."""
    if not MEMFIT:
        return memory_row_stamp()
    from sparksched_tpu.trainers.rollout import (
        collect_flat_sync,
        collect_flat_sync_batch,
        collect_sync,
    )

    state1 = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), states
    )
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    cands = (64, 256, 1024)
    if engine == "fastpath":
        def tracer(b):
            st_b = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(
                    (b,) + tuple(l.shape), l.dtype
                ),
                state1,
            )
            return jax.make_jaxpr(
                lambda s, k: collect_flat_sync_batch(
                    params, bank, bpol, k, steps, s, None,
                    fulfill_bulk=knobs["fulfill_bulk"],
                    bulk_events=knobs["bulk_events"],
                    bulk_cycles=knobs["bulk_cycles"],
                    bulk_fused=knobs["bulk_fused"],
                )
            )(st_b, key)

        return memory_row_stamp(tracer=tracer, candidates=cands)
    if engine == "flat":
        def fn(r, s):
            return collect_flat_sync(
                params, bank, pol, r, steps, s, None,
                micro_groups=micro_groups, **knobs,
            )
    else:
        def fn(r, s):
            return collect_sync(params, bank, pol, r, steps, s, None)
    return memory_row_stamp(fn, (key, state1), candidates=cands)


def _flat_knobs() -> dict:
    """Flat-engine calibration knobs for the decima_flat rows (same
    env-var override style as bench.py's self-calibration surface)."""
    return {
        "event_burst": int(os.environ.get("DEC_BENCH_FLAT_BURST", 4)),
        "bulk_events": int(os.environ.get("DEC_BENCH_FLAT_EVENTS", 8)),
        # on by default: FULFILL micro-steps only advance in full
        # micro-steps, so with a burst every un-bulked fulfillment costs
        # a whole burst-sized group (PERF_ROUNDS.md round-6 calibration)
        "fulfill_bulk": bool(int(
            os.environ.get("DEC_BENCH_FLAT_FULFILL", 1)
        )),
        "bulk_cycles": int(os.environ.get("DEC_BENCH_FLAT_CYCLES", 1)),
        # ISSUE 7: single fused bulk kernel vs the pass pair (step-
        # exact either way; purely a dispatch-count knob)
        "bulk_fused": bool(int(
            os.environ.get("DEC_BENCH_FLAT_FUSED", 1)
        )),
    }


def _job_cap_candidates() -> list[int]:
    """Compaction-bucket K candidates for the decima_fastpath rows
    (round-8 tentpole): calibrated like bench.py's engine knobs, pinned
    by setting a single value. Every emitted row records the candidate
    list and the bucket it ran with (0 = compaction off)."""
    raw = os.environ.get("BENCH_DECIMA_JOB_CAP", "8,16,32")
    return [int(x) for x in raw.split(",") if x.strip()]


def bench_inference(
    num_envs: int = 64, steps: int = 512,
    compute_dtype: str | None = None, engine: str = "core",
    bank_dtype: str | None = None,
) -> None:
    """Rollout-collection throughput (valid decision steps/s). `engine`
    selects the collector: "core" = per-decision `collect_sync` scan,
    "flat" = `collect_flat_sync` over the flat micro-step engine (the
    decima_flat row; knobs from `_flat_knobs`), "fastpath" = the round-8
    single-eval batch collector (`collect_flat_sync_batch`: one batched
    GNN evaluation per decision row + active-job compaction, bucket K
    calibrated over `BENCH_DECIMA_JOB_CAP` candidates).

    `bank_dtype` (ISSUE 7) quantizes the workload bank's dur table
    ("int16"/"int8"/"bf16") for the low-precision A/B row — the metric
    name carries the layout tag and every row stamps `config.dtype`
    with the bank's actual dur dtype, so the f32-vs-quantized sweep is
    a recorded A/B, not a claim."""
    params = EnvParams(
        num_executors=10, max_jobs=50, max_stages=20, max_levels=20,
        moving_delay=2000.0, warmup_delay=1000.0, job_arrival_rate=4e-5,
        mean_time_limit=None,
    )
    bank = make_workload_bank(
        params.num_executors, params.max_stages, bank_dtype=bank_dtype
    )
    if bank.max_stages != params.max_stages:
        params = params.replace(
            max_stages=bank.max_stages, max_levels=bank.max_stages
        )
    sched = DecimaScheduler(
        num_executors=params.num_executors,
        embed_dim=16,
        gnn_mlp_kwargs={
            "hid_dims": [32, 16],
            "act_cls": "LeakyReLU",
            "act_kwargs": {"negative_slope": 0.2},
        },
        policy_mlp_kwargs={"hid_dims": [64, 64], "act_cls": "Tanh"},
        compute_dtype=compute_dtype,
    )

    pol = sched.flat_policy()
    knobs = _flat_knobs()
    micro_per_dec = float(os.environ.get("DEC_BENCH_FLAT_MICRO", 4.0))
    job_bucket = 0
    job_caps = _job_cap_candidates()

    telem = telemetry_zeros_like((num_envs,)) if TELEMETRY else None
    # one vmapped call covers telemetry on AND off: vmap treats a None
    # argument as an empty pytree, and the collector's return shape
    # switches on the Python-level None check at trace time (the same
    # pattern as trainer._collect)
    if engine == "fastpath":
        def make_run(k):
            # the bucket is read at trace time; a fresh batch-policy
            # closure per candidate forces its own compile
            sched.job_bucket = int(k)
            bpol = sched.flat_batch_policy()

            @jax.jit
            def run(states, key, tm):
                out = collect_flat_sync_batch(
                    params, bank, bpol, key, steps, states, tm,
                    fulfill_bulk=knobs["fulfill_bulk"],
                    bulk_events=knobs["bulk_events"],
                    bulk_cycles=knobs["bulk_cycles"],
                    bulk_fused=knobs["bulk_fused"],
                )
                return out if tm is not None else (out, None)

            return run
    elif engine == "flat":
        micro_groups = flat_micro_group_budget(
            steps, micro_per_dec, knobs["event_burst"]
        )

        @jax.jit
        def run(states, rngs, tm):
            out = jax.vmap(
                lambda r, s, t: collect_flat_sync(
                    params, bank, pol, r, steps, s, t,
                    micro_groups=micro_groups, **knobs,
                )
            )(rngs, states, tm)
            return out if tm is not None else (out, None)
    else:
        @jax.jit
        def run(states, rngs, tm):
            out = jax.vmap(
                lambda r, s, t: collect_sync(
                    params, bank, pol, r, steps, s, t
                )
            )(rngs, states, tm)
            return out if tm is not None else (out, None)

    keys = jax.random.split(jax.random.PRNGKey(0), num_envs)
    states = jax.vmap(lambda k: core.reset(params, bank, k))(keys)

    def rngs_for(seed):
        if engine == "fastpath":
            return jax.random.PRNGKey(seed)  # batch collector: one key
        return jax.random.split(jax.random.PRNGKey(seed), num_envs)

    if engine == "fastpath":
        # calibrate the compaction bucket K over the candidate list
        # (bench.py's self-calibration pattern: warm each candidate,
        # time one chunk, keep the winner for the timed run)
        rates = {}
        runs = {}
        for k in job_caps:
            runs[k] = make_run(k)
            ro, telem = runs[k](states, rngs_for(1), telem)
            jax.block_until_ready(ro.reward)  # compile + warm
            tc = time.perf_counter()
            ro, telem = runs[k](states, rngs_for(40 + k), telem)
            n = int(jax.block_until_ready(ro.valid).sum())
            rates[k] = n / (time.perf_counter() - tc)
            if len(job_caps) > 1:
                print(
                    f"# bench_decima: fastpath K={k}: "
                    f"{rates[k]:.1f} steps/s",
                    file=sys.stderr, flush=True,
                )
        job_bucket = max(rates, key=rates.get)
        run = runs[job_bucket]
    else:
        ro, telem = run(states, rngs_for(1), telem)
        jax.block_until_ready(ro.reward)  # compile + warm
    telem_snap = jax.device_get(telem) if TELEMETRY else None

    t0 = time.perf_counter()
    n_timed = 2
    total = 0
    for i in range(n_timed):
        ro, telem = run(states, rngs_for(2 + i), telem)
        total += int(jax.block_until_ready(ro.valid).sum())
    dt = time.perf_counter() - t0
    value = total / dt
    tag = f"_{compute_dtype}" if compute_dtype else ""
    eng_tag = {"flat": "_flat", "fastpath": "_fastpath"}.get(engine, "")
    # quantized-bank rows carry the layout in the metric name so the
    # f32 row can never be overwritten/confused by the A/B partner
    bank_tag = f"_bank{bank_dtype_label(bank)}" if bank_dtype else ""
    cfg = {
        "num_envs": num_envs,
        "engine": engine,
        # ISSUE 7 layout stamp: the bank's ACTUAL dur dtype + the obs
        # feature-bank dtype on every row
        "dtype": bank_dtype_label(bank),
        "obs_dtype": params.obs_dtype,
        # the compaction bucket this row ran with (0 = off) and the
        # calibration surface it was chosen from — part of EVERY row so
        # numbers are only compared at equal config
        "job_bucket": int(job_bucket),
        "job_cap_candidates": job_caps,
        "prng_impl": str(jax.config.jax_default_prng_impl),
        "backend": jax.default_backend(),
        "telemetry": TELEMETRY,
    }
    if engine == "fastpath":
        cfg |= {
            "single_eval": True,
            "fulfill_bulk": knobs["fulfill_bulk"],
            "bulk_events": knobs["bulk_events"],
            "bulk_cycles": knobs["bulk_cycles"],
            "bulk_fused": knobs["bulk_fused"],
        }
    if engine == "flat":
        cfg |= {"micro_per_decision": micro_per_dec} | knobs
    if engine == "fastpath":
        # the stamp must fit the WINNING bucket's program (the
        # calibration loop left sched.job_bucket at the last candidate)
        sched.job_bucket = int(job_bucket)
        bpol_fit = sched.flat_batch_policy()
    else:
        bpol_fit = None
    row = {
        "metric": f"decima_infer_steps_per_sec_{num_envs}envs{tag}"
                  f"{eng_tag}{bank_tag}",
        "value": round(value, 1),
        "unit": "steps/s",
        "vs_baseline": round(value / TARGET, 3),
        "analysis_clean": analysis_clean_stamp(),
        "config": cfg,
        "memory": _inference_mem_stamp(
            params, bank, engine, steps, pol, bpol_fit, knobs,
            micro_groups if engine == "flat" else None, states,
        ),
    }
    if TELEMETRY:
        row["telemetry"] = summarize(telem, prev=telem_snap)
    _emit_row(row)


def _latency_block(samples_ms: list[float], reps: int) -> dict:
    """The `latency` row's percentile block (PERF_ROUNDS.md round 13):
    per-decision wall-time percentiles over `reps` timed calls. Since
    round 14 this is the shared `obs.metrics.percentile_block` helper
    (exact numpy percentiles, identical keys/values to the r10 rows —
    the refactor must keep old and new artifacts comparable)."""
    from sparksched_tpu.obs.metrics import percentile_block

    return percentile_block(samples_ms, reps=reps)


def _on_chip_block() -> dict:
    """On-chip-only latency-row fields: allocator stats exist only on
    an accelerator backend, and the field is absent where they do not
    (a CPU row)."""
    from sparksched_tpu.obs.memory import device_memory_stats

    stats = device_memory_stats()
    return {} if stats is None else {"device_memory": stats}


# the serving benches' Decima architecture — ONE definition shared by
# `_serve_setup` (the scheduler the store compiles) and the online
# arm's learner trainer (ISSUE 14), which MUST build the same net or a
# publish would be rejected at `set_params`'s aval check (shape drift)
# or silently train a mismatched policy (same shapes, different
# activation). job_bucket 16 is the PR-3 CPU calibration winner.
SERVE_AGENT_KWARGS = {
    "embed_dim": 16,
    "gnn_mlp_kwargs": {
        "hid_dims": [32, 16],
        "act_cls": "LeakyReLU",
        "act_kwargs": {"negative_slope": 0.2},
    },
    "policy_mlp_kwargs": {"hid_dims": [64, 64], "act_cls": "Tanh"},
    "job_bucket": 16,
}


def _serve_setup():
    """(params, bank, sched) for the serving benches — the BASELINE.md
    config #3 env at the PR-3 CPU-calibrated compaction bucket, shared
    by `bench_serve_latency` and `bench_serve_scale` so the two row
    families measure the same store."""
    params = EnvParams(
        num_executors=10, max_jobs=50, max_stages=20, max_levels=20,
        moving_delay=2000.0, warmup_delay=1000.0, job_arrival_rate=4e-5,
        mean_time_limit=None,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    if bank.max_stages != params.max_stages:
        params = params.replace(
            max_stages=bank.max_stages, max_levels=bank.max_stages
        )
    sched = DecimaScheduler(
        num_executors=params.num_executors, **SERVE_AGENT_KWARGS
    )
    return params, bank, sched


def bench_serve_latency(
    capacity: int | None = None,
    max_batch: int | None = None,
    reps: int | None = None,
    artifact: str = "artifacts/serve_latency_r20.json",
) -> list[dict]:
    """Decision-serving latency (ISSUE 10): p50/p90/p99 per-decision
    wall time through the AOT session store (`sparksched_tpu/serve/`),
    batch=1 (unbatched donated program) vs batch=K (one compiled
    width-K call), plus the micro-batcher's bounded-linger sweep and
    the cold-start cost (AOT lower+compile + first dispatch). Each
    configuration prints one `latency` JSON row; the full set is also
    written to `artifact` with the protocol metadata. Percentiles are
    over per-call wall times (median-of-reps protocol: the timed
    window is `reps` sequential calls on a warm store, so p50 is the
    steady-state figure and p99 the scheduling-jitter tail)."""
    capacity = capacity if capacity is not None else int(
        os.environ.get("SERVE_BENCH_CAPACITY", 64)
    )
    max_batch = max_batch if max_batch is not None else int(
        os.environ.get("SERVE_BENCH_BATCH", 8)
    )
    reps = reps if reps is not None else int(
        os.environ.get("SERVE_BENCH_REPS", 150)
    )
    lingers = [
        float(x) for x in os.environ.get(
            "SERVE_BENCH_LINGER_MS", "0,2"
        ).split(",") if x.strip()
    ]
    from sparksched_tpu.obs.runlog import RunLog
    from sparksched_tpu.serve import MicroBatcher, SessionStore

    params, bank, sched = _serve_setup()
    runlog = RunLog.create("artifacts", name=None)
    t0 = time.perf_counter()
    store = SessionStore(
        params, bank, sched, capacity=capacity, max_batch=max_batch,
        deterministic=True, seed=0, runlog=runlog,
    )
    cold_start_s = time.perf_counter() - t0

    def fresh_sessions(n: int) -> list[int]:
        return [store.create(seed=1000 + i) for i in range(n)]

    sids = fresh_sessions(max_batch)
    base_cfg = {
        "capacity": capacity,
        "max_batch": max_batch,
        "engine": "serve",
        "deterministic": True,
        "donated": store.donate,
        "job_bucket": sched.job_bucket,
        "dtype": bank_dtype_label(bank),
        "obs_dtype": params.obs_dtype,
        "prng_impl": str(jax.config.jax_default_prng_impl),
        "backend": jax.default_backend(),
    }
    cold = {
        "cold_start_s": round(cold_start_s, 3),
        "compile_decide_s": round(store.compile_secs["decide"], 3),
        "compile_decide_batch_s": round(
            store.compile_secs["decide_batch"], 3
        ),
        "warmup_s": round(store.warmup_secs, 4),
    }
    rows: list[dict] = []

    def wall_split_block(ws0: dict, n_calls: int, st=None) -> dict:
        """ISSUE 15 satellite: the timed window's wall time split into
        `dispatch_wall` (issuing compiled calls — async, returns
        futures) vs `blocked_host_wall` (inside
        `block_until_ready`/`np.asarray` syncs), from the store's
        cumulative counters delta'd across the window. The r10
        percentile fields are untouched; this block sits NEXT TO them
        so pipeline overlap (a shrinking blocked share) is visible in
        the row schema."""
        st = store if st is None else st
        d_ms = (st.wall_split["dispatch_s"] - ws0["dispatch_s"]) * 1e3
        b_ms = (
            st.wall_split["blocked_host_s"] - ws0["blocked_host_s"]
        ) * 1e3
        return {
            "dispatch_wall_ms": round(d_ms, 3),
            "blocked_host_wall_ms": round(b_ms, 3),
            "dispatch_wall_ms_per_call": round(d_ms / n_calls, 4),
            "blocked_host_wall_ms_per_call": round(b_ms / n_calls, 4),
            "blocked_fraction": round(
                b_ms / max(d_ms + b_ms, 1e-9), 4
            ),
            "calls": n_calls,
        }

    def emit(metric: str, samples_ms: list[float], cfg_extra: dict,
             wall_split: dict | None = None,
             attribution: dict | None = None) -> None:
        from sparksched_tpu.obs.metrics import hist_summary

        lat = _latency_block(samples_ms, len(samples_ms)) | cold
        # round-14 satellite: the O(buckets) streaming-histogram block
        # NEXT TO the exact percentiles (same samples; the exact
        # p50/p90/p99 fields above are unchanged from the r10 schema)
        lat["hist"] = hist_summary(samples_ms)
        if wall_split is not None:
            lat["wall_split"] = wall_split
        if cfg_extra.get("batch", 1) > 1:
            lat["per_decision_p50_ms"] = round(
                lat["p50_ms"] / cfg_extra["batch"], 4
            )
        row = {
            "metric": metric,
            "value": lat["p50_ms"],
            "unit": "ms",
            "latency": lat,
            "analysis_clean": analysis_clean_stamp(),
            "config": base_cfg | cfg_extra,
            "on_chip": _on_chip_block(),
        }
        if attribution is not None:
            row["attribution"] = attribution
        rows.append(row)
        runlog.latency(lat, batch=cfg_extra.get("batch"), metric=metric)
        _emit_row(row)

    # --- batch=1: the unbatched donated AOT path (a dedicated
    # session, so an episode ending mid-window never touches the
    # batch set served below) ---
    one = store.create(seed=3000)
    samples = []
    ws0 = dict(store.wall_split)
    for i in range(reps):
        t1 = time.perf_counter()
        r = store.decide(one)
        samples.append((time.perf_counter() - t1) * 1e3)
        # rotate a finished OR quarantined session (a tripped health
        # mask means the NEXT decide would raise — on-chip, where
        # sentinels actually fire, the artifact must survive it)
        if r.done or r.health_mask:
            store.close(one)
            one = store.create(seed=4000 + i)
    store.close(one)
    ws_off = wall_split_block(ws0, reps)
    emit("serve_decide_latency_batch1", samples, {"batch": 1},
         wall_split=ws_off)

    # --- ISSUE 18: the record-path A/B at batch=1 — the same reps
    # window on a record-on store, once through the per-decision
    # path (`record=True`, every decide syncs its StoredObs payload
    # to the host) and once through the device-resident trajectory
    # ring (`ring=R`: decides append on-device, the host drains ONE
    # batched transfer every ring_drain decisions). The headline the
    # ring exists for is the `blocked_host_wall_record_*` family
    # emitted below: per-call host-blocked wall, record-off vs the
    # two record paths — the ring row must sit in the noise of the
    # record-off row. Both arms feed a real TrajectoryBuffer, so the
    # measured path is the online actor's, not a null sink.
    from sparksched_tpu.online.trajectory import TrajectoryBuffer

    ring_size = int(os.environ.get(
        "SERVE_BENCH_RING", 4 * max_batch
    ))
    rec_ws: dict[str, dict] = {}
    rec_ring_stats: dict[str, dict] = {}
    for label, extra in (
        ("legacy", {}),
        ("ring", {"ring": ring_size}),
    ):
        buf = TrajectoryBuffer(max_steps=16)
        t0r = time.perf_counter()
        st = SessionStore(
            params, bank, sched, capacity=capacity,
            max_batch=max_batch, deterministic=True, seed=0,
            runlog=runlog, record=True, collector=buf, **extra,
        )
        rec_cold_s = time.perf_counter() - t0r
        one = st.create(seed=3000)
        samples = []
        ws0 = dict(st.wall_split)
        for i in range(reps):
            t1 = time.perf_counter()
            r = st.decide(one)
            samples.append((time.perf_counter() - t1) * 1e3)
            if r.done or r.health_mask:
                st.close(one)
                one = st.create(seed=4000 + i)
        st.close(one)
        if getattr(st, "_ring_on", False):
            st.drain_ring(wait=True)
        rec_ws[label] = wall_split_block(ws0, reps, st=st)
        rec_ring_stats[label] = {
            k: int(st.stats[k]) for k in (
                "serve_ring_occupancy", "serve_ring_drains",
                "serve_ring_records", "serve_ring_dropped",
            )
        }
        emit(
            f"serve_decide_latency_batch1_record_{label}", samples,
            {
                "batch": 1, "record": True,
                "ring": extra.get("ring", 0),
                "ring_drain": getattr(st, "ring_drain", None)
                if extra else None,
                "record_cold_start_s": round(rec_cold_s, 3),
                "trajectories": dict(buf.stats),
                "ring_stats": rec_ring_stats[label],
            },
            wall_split=rec_ws[label],
        )

    # the ledger family: per-call blocked-host wall as its own rows,
    # so the cross-round trend (and the tier-1 round pin) reads the
    # record path's sync cost directly instead of digging through
    # wall_split blocks
    for metric, ws, cfg_extra in (
        ("blocked_host_wall_record_off", ws_off,
         {"batch": 1, "record": False}),
        ("blocked_host_wall_record_legacy", rec_ws["legacy"],
         {"batch": 1, "record": True, "ring": 0}),
        ("blocked_host_wall_record_on", rec_ws["ring"],
         {"batch": 1, "record": True, "ring": ring_size,
          "ring_stats": rec_ring_stats["ring"]}),
    ):
        row = {
            "metric": metric,
            "value": ws["blocked_host_wall_ms_per_call"],
            "unit": "ms",
            "wall_split": ws,
            "analysis_clean": analysis_clean_stamp(),
            "config": base_cfg | cfg_extra,
            "on_chip": _on_chip_block(),
        }
        rows.append(row)
        _emit_row(row)

    # --- batch=K: one compiled width-K call per timed rep ---
    samples = []
    ws0 = dict(store.wall_split)
    for i in range(reps):
        t1 = time.perf_counter()
        results = store.decide_batch(sids)
        samples.append((time.perf_counter() - t1) * 1e3)
        if any(r.done or r.health_mask for r in results):
            for s in sids:
                store.close(s)
            sids = fresh_sessions(max_batch)
    emit(
        f"serve_decide_latency_batch{max_batch}", samples,
        {"batch": max_batch},
        wall_split=wall_split_block(ws0, reps),
    )

    # --- bounded-linger sweep: one lone request through the batcher;
    # its latency is the linger window (waiting for co-riders that
    # never come) plus the flush's decision call — the worst case the
    # linger knob can add to a request ---
    for linger_ms in lingers:
        mb = MicroBatcher(store, linger_ms=linger_ms)
        lone = store.create(seed=5000)
        samples = []
        ws0 = dict(store.wall_split)
        for i in range(max(10, reps // 5)):
            tk = mb.submit(lone)
            while not tk.ready:
                mb.poll()
            samples.append(
                (time.perf_counter() - tk.submitted_at) * 1e3
            )
            # rotate a finished/failed/quarantined session so the
            # sweep never times a frozen lane (and a quarantine fails
            # one ticket, not the artifact)
            if (tk.result is None or tk.result.done
                    or tk.result.health_mask):
                store.close(lone)
                lone = store.create(seed=5100 + i)
        store.close(lone)
        emit(
            f"serve_batcher_latency_linger{linger_ms:g}ms", samples,
            {"batch": 1, "linger_ms": linger_ms, "front": "batcher"},
            wall_split=wall_split_block(ws0, len(samples)),
        )

    # --- ISSUE 20: attribution capture. A SEPARATE short window (the
    # ledger-pinned linger rows above stay untraced, their timing
    # untouched): the lone-request shape through a traced front
    # carrying the critical-path analyzer, emitting one row whose
    # `attribution` block decomposes the wall into segments
    # (ledger-indexed as serve_latency_attribution_seg_*_p99_ms) ---
    from sparksched_tpu.obs.critpath import CritPathAnalyzer
    from sparksched_tpu.obs.metrics import MetricsRegistry

    att_reg = MetricsRegistry()
    att_cp = CritPathAnalyzer(metrics=att_reg, window_s=float("inf"))
    store.metrics, store.trace = att_reg, True
    mb = MicroBatcher(store, linger_ms=0.0, metrics=att_reg,
                      trace=True, critpath=att_cp)
    lone = store.create(seed=6000)
    samples = []
    for i in range(max(10, reps // 5)):
        tk = mb.submit(lone)
        while not tk.ready:
            mb.poll()
        samples.append(
            (time.perf_counter() - tk.submitted_at) * 1e3
        )
        if (tk.result is None or tk.result.done
                or tk.result.health_mask):
            store.close(lone)
            lone = store.create(seed=6100 + i)
    store.close(lone)
    store.metrics, store.trace = None, False
    att_snap = att_cp.snapshot()
    att_hists = att_reg.snapshot()["hists"]
    emit(
        "serve_latency_attribution", samples,
        {"batch": 1, "front": "batcher", "attribution": True},
        attribution={
            "seg_p99_ms": {
                k.removeprefix("serve_seg_").removesuffix("_ms"):
                    v["p99"]
                for k, v in att_hists.items()
                if k.startswith("serve_seg_")
            },
            "dominant_tail_segment": att_snap.get(
                "dominant_tail_segment"
            ),
            "at_p50": att_snap.get("at_p50"),
            "at_p99": att_snap.get("at_p99"),
        },
    )

    os.makedirs(os.path.dirname(artifact) or ".", exist_ok=True)
    with open(artifact, "w") as fp:
        json.dump({
            "protocol": {
                "reps": reps,
                "timing": "per-call wall time on a warm store; "
                          "percentiles over reps sequential calls",
                "cold_start": "AOT lower+compile (both programs) + "
                              "first-dispatch warmup",
                "linger_sweep_ms": lingers,
                # ISSUE 18: the record-path A/B — same reps window on
                # record-on stores (per-decision vs device ring), the
                # blocked_host_wall_record_* rows are the per-call
                # host-blocked wall of each path
                "record_ab": {
                    "ring": ring_size,
                    "arms": ["off", "legacy", "ring"],
                    "blocked_host_wall_ms_per_call": {
                        "off": ws_off[
                            "blocked_host_wall_ms_per_call"],
                        "legacy": rec_ws["legacy"][
                            "blocked_host_wall_ms_per_call"],
                        "ring": rec_ws["ring"][
                            "blocked_host_wall_ms_per_call"],
                    },
                },
            },
            "rows": rows,
        }, fp, indent=1)
    runlog.close()
    print(f"# bench_decima: wrote {artifact} ({len(rows)} rows)",
          file=sys.stderr, flush=True)
    return rows


def _serve_obs_overhead(store, reps: int = 30) -> dict:
    """Instrumentation A/B on the serve path (ISSUE 11 acceptance bar:
    <= 5%): time `reps` warm full-batch flush windows through an
    UNinstrumented MicroBatcher vs a fully instrumented one (metrics +
    per-request tracing + runlog trace records), interleaved medians —
    the scripts_obs_demo.py protocol, so box-level drift hits both
    arms equally."""
    import tempfile

    from sparksched_tpu.obs.metrics import MetricsRegistry, interleaved_ab
    from sparksched_tpu.obs.runlog import RunLog
    from sparksched_tpu.serve import MicroBatcher

    def same_group_sessions(base: int) -> list[int]:
        # a full-batch flush is ONE compiled call and must live in one
        # slot group (ISSUE 15): over-create, keep max_batch sessions
        # of the first session's group, release the rest
        cand = [
            store.create(seed=base + i)
            for i in range(2 * store.max_batch)
        ]
        g0 = store.session_group(cand[0])
        keep = [
            s for s in cand if store.session_group(s) == g0
        ][: store.max_batch]
        for s in cand:
            if s not in keep:
                store.close(s)
        return keep

    sids = same_group_sessions(9000)
    rl = RunLog(
        os.path.join(tempfile.mkdtemp(prefix="serve_ab_"), "ab.jsonl")
    )

    def rotate(results):
        nonlocal sids
        if any(r.done or r.health_mask for r in results):
            for s in sids:
                store.close(s)
            sids = same_group_sessions(9500)

    def window(mb):
        t0 = time.perf_counter()
        tks = [mb.submit(s) for s in sids]  # full batch => auto-flush
        dt = time.perf_counter() - t0
        rotate([t.result for t in tks if t.result is not None])
        return dt

    def arm_off():
        store.metrics, store.trace = None, False
        return window(MicroBatcher(store, linger_ms=1e6))

    def arm_on():
        store.metrics, store.trace = MetricsRegistry(), True
        return window(MicroBatcher(
            store, linger_ms=1e6, metrics=store.metrics, runlog=rl,
            trace=True,
        ))

    t_off, t_on, pct = interleaved_ab(
        arm_off, arm_on, warmups=2, reps=max(5, reps)
    )
    rl.close()
    for s in sids:
        store.close(s)
    store.metrics, store.trace = None, False
    return {
        "off_ms": round(t_off * 1e3, 4),
        "on_ms": round(t_on * 1e3, 4),
        "overhead_pct": round(pct, 2),
        "passed": pct < 5.0,
        "reps": max(5, reps),
        "protocol": "interleaved medians over warm full-batch flush "
                    "windows (scripts_obs_demo.py protocol); on = "
                    "metrics registry + per-request trace spans + "
                    "runlog trace records",
    }


def bench_serve_scale(
    artifact: str = "artifacts/serve_scale_r20.json",
) -> list[dict]:
    """Serving at load (ISSUE 11/13): open-loop offered-load sweep
    over the AOT session store, reporting GOODPUT under a p99 SLO —
    replies within `slo_ms` of their SCHEDULED arrival per second of
    run — and the p99-vs-offered-load curve.

    Since round 15 this is an A/B bench over the two batching fronts:
    at every offered-load point the SAME seeded arrival schedule runs
    through the fixed-linger `MicroBatcher` (the r10/r11 front) and
    the `ContinuousBatcher` (ISSUE 13 — occupancy-driven, no linger
    timer), arms interleaved rep-by-rep per point so box-level drift
    hits both equally, medians compared (the PR-11 `interleaved_ab`
    protocol at run granularity). Each (point, front) pair emits one
    row — the median-goodput rep's full summary, with the per-rep
    goodput/p99 lists in its `ab` block — and the artifact's protocol
    carries the per-front SUSTAINED rate (the highest offered load
    whose median p99 met the SLO): the headline the continuous
    batcher exists to raise. Rows also stamp the hot-set capacity
    advice (`SessionStore.hot_set_advice` — how many device slots the
    HBM budget holds, the pager's sizing model). Arrival schedules
    are seeded and deterministic (serve/loadgen.py); latency is
    measured open-loop, so offered loads beyond capacity show the
    queueing tail closed-loop medians can never see.

    Since round 16 (ISSUE 14) the bench grows an ONLINE arm
    (`SERVE_SCALE_ONLINE=1`, the default): one extra point at
    `SERVE_SCALE_ONLINE_RPS` runs the full closed loop — a record-on
    store serving the seeded schedule while a background
    `OnlineLearner` drains served-decision trajectories through
    `ppo_update` and hot-swaps accepted versions in via the `ParamBus`
    (zero recompiles) — so the artifact reports goodput@SLO AND the
    reward trend under live learning, plus the record-on-vs-off
    serving overhead at the same offered load (interleaved
    run-granularity A/B against the bench's record-off store).

    Since round 18 (ISSUE 16) the bench grows a NETWORK arm
    (`SERVE_SCALE_NET=1`, the default): (a) a loopback A/B — the same
    store architecture served direct vs through the HTTP front over
    127.0.0.1 (`ServeClient` in `run_open_loop`'s client mode), arms
    interleaved rep-by-rep so the delta IS the wire; and (b) a replica
    sweep — goodput@SLO against a spawned N-process serve fleet behind
    the session-affinity router, N in `SERVE_SCALE_REPLICAS`. Latency
    still clocks from SCHEDULED arrival on every arm, so queue wait
    counts against the server on both sides of each pairing. The
    protocol block stamps `os.cpu_count()` — replica scaling is
    core-bound, and a single-core host is called out explicitly rather
    than letting a flat sweep masquerade as a router bottleneck."""
    offered = [
        float(x) for x in os.environ.get(
            "SERVE_SCALE_OFFERED", "12.5,25,50,100,200"
        ).split(",") if x.strip()
    ]
    n_req = int(os.environ.get("SERVE_SCALE_REQUESTS", 240))
    tenants = int(os.environ.get("SERVE_SCALE_TENANTS", 12))
    slo_ms = float(os.environ.get("SERVE_SCALE_SLO_MS", 200))
    linger_ms = float(os.environ.get("SERVE_SCALE_LINGER_MS", 2))
    capacity = int(os.environ.get("SERVE_SCALE_CAPACITY", 32))
    hot_env = os.environ.get("SERVE_SCALE_HOT_CAPACITY", "")
    hot_capacity = int(hot_env) if hot_env else None
    max_batch = int(os.environ.get("SERVE_SCALE_BATCH", 8))
    with_mmpp = os.environ.get("SERVE_SCALE_MMPP", "1") == "1"
    seed = int(os.environ.get("SERVE_SCALE_SEED", 11))
    # ISSUE 15: the round-17 default A/B isolates PIPELINING — the
    # synchronous continuous front (depth 1) vs the pipelined front
    # (depth D over G slot groups) on the SAME grouped store, so the
    # in-flight window is the only variable. `linger` remains runnable
    # for the r13-protocol three-way.
    fronts = [
        f.strip() for f in os.environ.get(
            "SERVE_SCALE_FRONTS", "continuous,pipelined"
        ).split(",") if f.strip()
    ]
    unknown_fronts = set(fronts) - {"linger", "continuous", "pipelined"}
    if unknown_fronts:
        # fail loudly (the serve-config contract): a typo'd front
        # would silently run the fallback arm twice and stamp the
        # paired A/B rows with a label that never ran
        raise ValueError(
            f"unknown SERVE_SCALE_FRONTS entr(y/ies) "
            f"{sorted(unknown_fronts)}; known: continuous, linger, "
            "pipelined"
        )
    ab_reps = int(os.environ.get("SERVE_SCALE_AB_REPS", 3))
    # CPU default: groups=1 (consecutive calls chain on the one donated
    # buffer, which the depth-2 window never waits on) — slot groups
    # buy true call concurrency only where device and host are
    # different silicon, so the chip stage (17) runs groups=4 while
    # the CPU A/B isolates the async dispatch/harvest split
    groups = int(os.environ.get("SERVE_SCALE_GROUPS", 1))
    depth = int(os.environ.get("SERVE_SCALE_DEPTH", max(2, groups)))
    harvester = os.environ.get("SERVE_SCALE_HARVESTER", "0") == "1"
    # ISSUE 16: the network arm (loopback A/B + replica-fleet sweep).
    # With it on, persist XLA compilations (config.py cache helper)
    # BEFORE the parent's stores build: every replica process then
    # boots by cache load instead of recompiling the serve programs —
    # the difference between a ~1 min and a ~10 s fleet spin-up.
    net_on = os.environ.get("SERVE_SCALE_NET", "1") == "1"
    if net_on:
        from sparksched_tpu.config import enable_compilation_cache

        enable_compilation_cache()

    from sparksched_tpu.obs.metrics import (
        MetricsRegistry,
        hist_summary,
        paired_ab_pct,
        percentile_block,
    )
    from sparksched_tpu.obs.runlog import RunLog
    from sparksched_tpu.serve import (
        ContinuousBatcher,
        MicroBatcher,
        SessionStore,
        generate_arrivals,
        run_open_loop,
    )

    params, bank, sched = _serve_setup()
    runlog = RunLog.create("artifacts", name=None)
    t0 = time.perf_counter()
    # the sync arms' store (and the obs-overhead / hot-set / online-A/B
    # subject): groups=1 — the linger and continuous rows ARE the
    # r11/r13 fronts, byte-for-byte. At the CPU default
    # (groups=1, no harvester) the pipelined arm SHARES this store, so
    # the A/B isolates the front (r13 pairing discipline); with
    # SERVE_SCALE_GROUPS>1 (the chip stage) it gets its own grouped
    # store — the slot-group layout is then part of the architecture
    # under test, compared at identical seeded schedules.
    store = SessionStore(
        params, bank, sched, capacity=capacity,
        hot_capacity=hot_capacity, max_batch=max_batch,
        deterministic=True, seed=0, runlog=runlog,
    )
    store_pipe = None
    if "pipelined" in fronts:
        if groups == 1 and not harvester:
            # same layout as the sync arms: share the store, so the
            # A/B isolates the FRONT (r13 pairing discipline)
            store_pipe = store
        else:
            store_pipe = SessionStore(
                params, bank, sched, capacity=capacity,
                hot_capacity=hot_capacity, groups=groups,
                harvester=harvester, max_batch=max_batch,
                deterministic=True, seed=0, runlog=runlog,
            )
    cold_start_s = time.perf_counter() - t0
    hot_set = store.hot_set_advice()

    def ring_block(st) -> dict:
        """ISSUE 18: the store's device-ring counters, stamped on
        every row so a record-on arm's drain cadence (and any overrun
        drops) travels with the goodput it produced. Record-off
        stores stamp zeros — the zero IS the claim that the arm never
        touched the ring path."""
        return {
            k: int(st.stats.get(k, 0)) for k in (
                "serve_ring_occupancy", "serve_ring_drains",
                "serve_ring_records", "serve_ring_dropped",
            )
        }

    base_cfg = {
        "capacity": capacity,
        "hot_capacity": store.hot_capacity,
        "max_batch": max_batch,
        "linger_ms": linger_ms,
        "tenants": tenants,
        "requests": n_req,
        "seed": seed,
        "engine": "serve",
        "deterministic": True,
        "job_bucket": sched.job_bucket,
        "dtype": bank_dtype_label(bank),
        "obs_dtype": params.obs_dtype,
        "prng_impl": str(jax.config.jax_default_prng_impl),
        "backend": jax.default_backend(),
    }
    rows: list[dict] = []
    points = [(r, "poisson") for r in offered]
    if with_mmpp and offered:
        points.append((offered[len(offered) // 2], "mmpp"))
    # per-front median p99 at each poisson rate, for the sustained-
    # under-SLO summary
    p99_med: dict[tuple[str, float], float] = {}

    def one_run(rate, process, front):
        """One open-loop run of the seeded schedule through `front`;
        returns (summary, samples, hist, metrics snapshot,
        attribution snapshot)."""
        from sparksched_tpu.obs.critpath import CritPathAnalyzer

        arrivals = generate_arrivals(
            rate, n_req, tenants, process=process, seed=seed
        )
        reg = MetricsRegistry()
        # ISSUE 20: the attribution plane rides every traced arm —
        # per-segment hists land in `reg`, the joint quantile mixes
        # in the snapshot (window disabled: the run IS the window)
        cp = CritPathAnalyzer(metrics=reg, window_s=float("inf"))
        st = store_pipe if front == "pipelined" else store
        st.metrics, st.trace = reg, True
        if front == "pipelined":
            b = ContinuousBatcher(
                st, depth=depth, metrics=reg, runlog=runlog,
                trace=True, critpath=cp,
            )
        elif front == "continuous":
            b = ContinuousBatcher(
                st, metrics=reg, runlog=runlog, trace=True,
                critpath=cp,
            )
        else:
            b = MicroBatcher(
                st, linger_ms=linger_ms, metrics=reg,
                runlog=runlog, trace=True, critpath=cp,
            )
        summary = run_open_loop(
            st, b, arrivals, slo_ms=slo_ms,
            session_seed=20_000 + int(rate),
        )
        st.metrics, st.trace = None, False
        samples = summary.pop("samples_ms")
        hist = summary.pop("hist")
        return summary, samples, hist, reg.snapshot(), cp.snapshot()

    for rate, process in points:
        # interleaved arms, rep-by-rep (the PR-11 interleaved_ab
        # protocol at run granularity): linger rep 1, continuous rep
        # 1, linger rep 2, ... so drift hits both fronts equally
        runs: dict[str, list] = {f: [] for f in fronts}
        for _rep in range(max(1, ab_reps)):
            for front in fronts:
                runs[front].append(one_run(rate, process, front))
        tag = "_mmpp" if process == "mmpp" else ""
        for front in fronts:
            reps = runs[front]
            goodputs = [r[0]["goodput_rps"] for r in reps]
            p99s = [
                percentile_block(r[1])["p99_ms"] for r in reps
            ]
            # the row is the MEDIAN-goodput rep's full summary
            order = sorted(range(len(reps)), key=goodputs.__getitem__)
            summary, samples, hist, snap, att = (
                reps[order[len(order) // 2]]
            )
            lat_block = percentile_block(samples)
            med_p99 = sorted(p99s)[len(p99s) // 2]
            if process == "poisson":
                p99_med[(front, rate)] = med_p99
            # linger rows keep the r11 metric names (directly
            # comparable at equal offered load); continuous adds _cb,
            # pipelined _pipe
            suffix = {
                "continuous": "_cb", "pipelined": "_pipe",
            }.get(front, "")
            row = {
                "metric": (
                    f"serve_scale_offered{rate:g}rps{tag}{suffix}"
                ),
                # the headline value IS goodput: SLO-satisfying
                # decisions/s (median rep)
                "value": summary["goodput_rps"],
                "unit": "decisions/s",
                "slo": {
                    "p99_slo_ms": slo_ms,
                    "p99_ms": lat_block["p99_ms"],
                    "p99_ms_median": med_p99,
                    "slo_met": med_p99 <= slo_ms,
                    "good": summary["good"],
                    "good_fraction": round(
                        summary["good"]
                        / max(summary["completed"], 1), 4
                    ),
                    "goodput_rps": summary["goodput_rps"],
                },
                # the paired-A/B block: per-rep values for both the
                # curve and the pairing key shared by the two fronts'
                # rows at this point
                "ab": {
                    "pair": f"offered{rate:g}rps{tag}",
                    "front": front,
                    "reps": len(reps),
                    "goodput_rps_reps": goodputs,
                    "p99_ms_reps": p99s,
                    "goodput_rps_median": sorted(goodputs)[
                        len(goodputs) // 2
                    ],
                },
                "open_loop": {
                    k: summary[k] for k in (
                        "requests", "front", "completed", "errors",
                        "makespan_s", "offered_rps", "achieved_rps",
                        "session_rotations", "capacity_rejections",
                    )
                },
                "latency": lat_block | {"hist": hist_summary(hist)},
                # the trace stamp: per-span latency summaries from
                # the instrumented front (queue wait / device compute
                # / scatter-back / total), one histogram each
                "trace": {
                    k: v for k, v in snap["hists"].items()
                    if k.startswith("serve_span_")
                },
                # ISSUE 20: the attribution stamp — windowed
                # per-segment p99s (ledger-indexed as
                # `<metric>_seg_<seg>_p99_ms`) plus the joint segment
                # mix at p50 vs p99 and the dominant tail segment
                "attribution": {
                    "seg_p99_ms": {
                        k.removeprefix("serve_seg_")
                         .removesuffix("_ms"): v["p99"]
                        for k, v in snap["hists"].items()
                        if k.startswith("serve_seg_")
                    },
                    "dominant_tail_segment": att.get(
                        "dominant_tail_segment"
                    ),
                    "at_p50": att.get("at_p50"),
                    "at_p99": att.get("at_p99"),
                },
                # the metrics stamp: admission/occupancy views +
                # counters (wait_ms is the linger wait under the
                # linger front, the queue wait under continuous)
                "metrics": {
                    "queue_depth": snap["hists"].get(
                        "serve_queue_depth"
                    ),
                    "batch_occupancy": snap["hists"].get(
                        "serve_batch_occupancy"
                    ),
                    "wait_ms": snap["hists"].get(
                        "serve_linger_wait_ms"
                    ) or snap["hists"].get("serve_queue_wait_ms"),
                    "flush_reasons": {
                        k.removeprefix("serve_flush_"): int(v)
                        for k, v in snap["counters"].items()
                        if k.startswith("serve_flush_")
                    },
                    "quarantines": int(
                        snap["counters"].get("serve_quarantines", 0)
                    ),
                    # store-side create() failures (one per rotation
                    # attempt) — request-level rejections live in
                    # open_loop.capacity_rejections; the two counters
                    # measure different events and are named apart
                    "store_create_rejections": int(
                        snap["counters"].get(
                            "serve_capacity_rejections", 0
                        )
                    ),
                    "rejected_requests": int(
                        snap["counters"].get(
                            "serve_requests_rejected", 0
                        )
                    ),
                    "page_ins": int(
                        snap["counters"].get("serve_page_ins", 0)
                    ),
                    "page_outs": int(
                        snap["counters"].get("serve_page_outs", 0)
                    ),
                },
                "ring": ring_block(
                    store_pipe if front == "pipelined" else store
                ),
                "analysis_clean": analysis_clean_stamp(),
                "config": base_cfg | {
                    "offered_rps": rate, "process": process,
                    "front": front,
                    # the arm's serve architecture (ISSUE 15): sync
                    # arms run the r13 single-group layout, the
                    # pipelined arm its G-group depth-D window
                    "groups": (
                        groups if front == "pipelined" else 1
                    ),
                    "pipeline_depth": (
                        depth if front == "pipelined" else 1
                    ),
                    "cold_start_s": round(cold_start_s, 3),
                },
                "on_chip": _on_chip_block(),
            }
            rows.append(row)
            runlog.metrics(snap, metric=row["metric"])
            _emit_row(row)

    # ---- the online arm (ISSUE 14): the closed serve->learn->serve
    # loop at one offered-load point — goodput@SLO + reward trend
    # under live learning, hot-swap accounting, and the record-on
    # serving-overhead A/B at the same offered load
    online_protocol = None
    if os.environ.get("SERVE_SCALE_ONLINE", "1") == "1":
        from sparksched_tpu.online import online_from_config

        on_rate = float(os.environ.get(
            "SERVE_SCALE_ONLINE_RPS",
            offered[len(offered) // 2] if offered else 25.0,
        ))
        # the learner's trainer builds the SAME net the serving
        # scheduler runs (the swap publishes into the compiled
        # programs) — one shared definition, never a copy
        agent_cfg = {"agent_cls": "DecimaScheduler"} | SERVE_AGENT_KWARGS
        reg = MetricsRegistry()
        # ISSUE 18: the record arm runs through the device-resident
        # trajectory ring by default — decides append on-device, the
        # host drains one batched transfer per cadence, so the online
        # loop's record cost is the ring drain, not a per-decision
        # sync. SERVE_SCALE_RING=0 restores the r16 per-decision path
        # (the before arm of the PERF_ROUNDS.md round-20 table).
        ring_size = int(os.environ.get(
            "SERVE_SCALE_RING", 8 * max_batch
        ))
        t0o = time.perf_counter()
        store_on = SessionStore(
            params, bank, sched, capacity=capacity,
            hot_capacity=hot_capacity, max_batch=max_batch,
            deterministic=True, seed=0, runlog=runlog, metrics=reg,
            record=True, ring=ring_size,
        )
        online_cold_s = time.perf_counter() - t0o
        buffer, learner, bus = online_from_config(
            {
                "max_steps": 16, "batch_trajectories": 4,
                "probation_decisions": 32,
                "max_quarantine_rate": 0.5,
            },
            store_on, agent_cfg, runlog=runlog, metrics=reg,
        )
        learner_compile_s = learner.warmup()
        # absorb first-dispatch glue + prime the trajectory buffer
        # outside the measured window
        warm = generate_arrivals(
            on_rate, max(2 * tenants, 24), tenants, seed=seed + 3
        )
        run_open_loop(
            store_on, ContinuousBatcher(store_on, metrics=reg), warm,
            slo_ms=slo_ms, session_seed=41_000, on_poll=bus.pump,
            keep_samples=False,
        )
        while learner.ready():
            learner.step()
        bus.pump()
        v0 = store_on.params_version
        swaps0 = store_on.stats["serve_param_swaps"]
        steps0 = learner.stats["learner_steps"]
        arrivals = generate_arrivals(
            on_rate, n_req, tenants, seed=seed
        )
        front_on = ContinuousBatcher(
            store_on, metrics=reg, runlog=runlog, trace=True
        )
        store_on.trace = True
        learner.start_background()
        try:
            summary = run_open_loop(
                store_on, front_on, arrivals, slo_ms=slo_ms,
                session_seed=42_000, on_poll=bus.pump,
            )
        finally:
            learner.stop()
            store_on.trace = False
        # snapshot the IN-WINDOW accounting BEFORE the drain pump: a
        # swap published at the window's tail but applied by the pump
        # below landed outside the measured traffic
        swaps_in_window = (
            store_on.stats["serve_param_swaps"] - swaps0
        )
        steps_in_window = learner.stats["learner_steps"] - steps0
        bus.pump()
        samples = summary.pop("samples_ms")
        hist_on = summary.pop("hist")
        lat_block = percentile_block(samples)

        # record-on vs record-off at the SAME offered load: the off
        # arm is the bench's record-off store, arms interleaved
        # rep-by-rep (run-granularity interleaved_ab), medians of the
        # per-rep mean latency compared. BOTH arms run bare — no
        # metrics, no trace, no collector — so the A/B isolates the
        # record PATH's serving cost (trajectory assembly is the
        # loop's cost, measured by the window above, not here)
        store.metrics, store.trace = None, False
        on_state = (store_on.metrics, store_on.collector)
        store_on.metrics, store_on.collector = None, None
        # pin BOTH arms to the SAME policy for the record A/B: the
        # online window just hot-swapped learned params into store_on,
        # and a different policy changes decision and drain costs —
        # this A/B isolates the record PATH's serving cost, not the
        # learner's behavioral effect (round-17 fix: with effective
        # learning the confound dwarfed the record cost)
        store_on.set_params(
            jax.device_get(store.model_params), mark_good=False,
            origin="record_ab_pin",
        )
        ab_sched = generate_arrivals(
            on_rate, max(n_req // 2, 60), tenants, seed=seed + 4
        )
        rec_runs: dict[str, list[float]] = {"off": [], "on": []}
        for rep in range(max(1, ab_reps)):
            arms = (("off", store), ("on", store_on))
            if rep % 2:
                arms = arms[::-1]  # cancel within-pair ordering bias
            for label, st in arms:
                s2 = run_open_loop(
                    st, ContinuousBatcher(st), ab_sched,
                    slo_ms=slo_ms, session_seed=43_000,
                )
                rec_runs[label].append(
                    percentile_block(s2["samples_ms"])["mean_ms"]
                )
        store_on.metrics, store_on.collector = on_state
        rec_med = {
            k: sorted(v)[len(v) // 2] for k, v in rec_runs.items()
        }
        # paired per-rep statistic: run-level reps are few and box
        # drift is monotone — pairing cancels it
        # (obs.metrics.paired_ab_pct)
        rec_pct = paired_ab_pct(rec_runs["off"], rec_runs["on"])
        reward_trend = [
            {
                "version": h.get("version"),
                "policy_loss": round(h["policy_loss"], 6),
                "traj_reward_mean": round(h["traj_reward_mean"], 2),
                "accepted": h["accepted"],
            }
            for h in learner.history
        ]
        online_block = {
            "hot_swaps": store_on.stats["serve_param_swaps"],
            "swaps_in_window": swaps_in_window,
            "params_version": {
                "start": v0, "end": store_on.params_version,
            },
            "rollbacks": store_on.stats["serve_param_rollbacks"],
            "learner_steps": learner.stats["learner_steps"],
            "learner_steps_in_window": steps_in_window,
            "learner_rejected": learner.stats["learner_rejected"],
            "reward_trend": reward_trend,
            "trajectories": dict(buffer.stats),
            "bus": dict(bus.stats),
        }
        row = {
            "metric": f"serve_scale_online{on_rate:g}rps",
            "value": summary["goodput_rps"],
            "unit": "decisions/s",
            "slo": {
                "p99_slo_ms": slo_ms,
                "p99_ms": lat_block["p99_ms"],
                "slo_met": lat_block["p99_ms"] <= slo_ms,
                "good": summary["good"],
                "goodput_rps": summary["goodput_rps"],
            },
            "open_loop": {
                k: summary[k] for k in (
                    "requests", "front", "completed", "errors",
                    "makespan_s", "offered_rps", "achieved_rps",
                    "session_rotations", "capacity_rejections",
                )
            },
            "latency": lat_block | {"hist": hist_summary(hist_on)},
            "online": online_block,
            "ring": ring_block(store_on),
            "record_overhead": {
                "open_loop_pct": round(rec_pct, 2),
                "mean_ms": {
                    "off": round(rec_med["off"], 3),
                    "on": round(rec_med["on"], 3),
                },
                "reps": rec_runs,
                "passed": rec_pct <= 5.0,
                "bar_pct": 5.0,
            },
            "analysis_clean": analysis_clean_stamp(),
            "config": base_cfg | {
                "offered_rps": on_rate, "process": "poisson",
                "front": "continuous", "record": True,
                "ring": ring_size,
                "ring_drain": store_on.ring_drain,
                "online_cold_start_s": round(online_cold_s, 3),
                "learner_compile_s": round(learner_compile_s, 3),
            },
            "on_chip": _on_chip_block(),
        }
        rows.append(row)
        runlog.metrics(reg.snapshot(), metric=row["metric"])
        _emit_row(row)
        online_protocol = {
            "loop": "record-on store + ContinuousBatcher serving the "
                    "seeded schedule; background OnlineLearner "
                    "(ppo_update, health gates on) publishes via "
                    "ParamBus; swaps applied between compiled calls "
                    "(run_open_loop on_poll) — zero recompiles by "
                    "construction (params are arguments of the AOT "
                    "programs; pinned in tests/test_online.py)",
            "offered_rps": on_rate,
            "record_ab": "record-on vs record-off store at the same "
                         "seeded offered load, arms interleaved "
                         "rep-by-rep, median per-rep mean latency; "
                         "since r20 the record arm runs the device "
                         "trajectory ring (ISSUE 18), so the "
                         "overhead is the batched drain, not a "
                         "per-decision sync",
            "record_overhead_pct": round(rec_pct, 2),
            "ring": {"size": ring_size,
                     "drain": store_on.ring_drain},
            "hot_swaps": online_block["hot_swaps"],
            "learner_steps": online_block["learner_steps"],
        }

    # ---- the network arm (ISSUE 16): the serving tier behind a real
    # socket. (a) loopback vs in-process — the SAME store architecture
    # served direct vs through the HTTP front over 127.0.0.1, arms
    # interleaved rep-by-rep (the PR-13 pairing discipline), so the
    # delta IS the wire: HTTP framing + JSON + the handler->pump
    # thread handoff. (b) the replica sweep — the same seeded schedule
    # against a spawned N-process fleet behind the session-affinity
    # router, one row per N. SERVE_SCALE_NET=0 skips, and nothing
    # network-side is imported (zero-cost-off).
    net_protocol = None
    if net_on:
        from sparksched_tpu.serve import (
            ReplicaSpec,
            Router,
            ServeClient,
            ServeServer,
        )

        net_rate = float(os.environ.get(
            "SERVE_SCALE_NET_RPS",
            offered[len(offered) // 2] if offered else 25.0,
        ))
        net_req = int(os.environ.get("SERVE_SCALE_NET_REQUESTS", n_req))
        replica_counts = [
            int(x) for x in os.environ.get(
                "SERVE_SCALE_REPLICAS", "1,2,4"
            ).split(",") if x.strip()
        ]
        fleet_capacity = int(os.environ.get(
            "SERVE_SCALE_FLEET_CAPACITY", 16
        ))
        fleet_batch = int(os.environ.get("SERVE_SCALE_FLEET_BATCH", 4))
        net_arrivals = generate_arrivals(
            net_rate, net_req, tenants, seed=seed + 7
        )

        def net_run(st, fr):
            s = run_open_loop(
                st, fr, net_arrivals, slo_ms=slo_ms,
                session_seed=50_000,
            )
            return s, s.pop("samples_ms"), s.pop("hist")

        def net_median(reps_l):
            """(median-goodput rep, lat block, med_p99, goodputs, p99s)
            — the sweep rows' median-rep protocol."""
            goodputs = [r[0]["goodput_rps"] for r in reps_l]
            p99s = [percentile_block(r[1])["p99_ms"] for r in reps_l]
            order = sorted(
                range(len(reps_l)), key=goodputs.__getitem__
            )
            s_med, samples, h = reps_l[order[len(order) // 2]]
            return (
                s_med, percentile_block(samples), h,
                sorted(p99s)[len(p99s) // 2], goodputs, p99s,
            )

        def net_row(metric, pair, arm, med, net_block, cfg_extra,
                    ring=None):
            s_med, lat, h, med_p99, goodputs, p99s = med
            return {
                "metric": metric,
                "value": s_med["goodput_rps"],
                "unit": "decisions/s",
                "slo": {
                    "p99_slo_ms": slo_ms,
                    "p99_ms": lat["p99_ms"],
                    "p99_ms_median": med_p99,
                    "slo_met": med_p99 <= slo_ms,
                    "good": s_med["good"],
                    "goodput_rps": s_med["goodput_rps"],
                },
                "ab": {
                    "pair": pair,
                    "front": arm,
                    "reps": len(goodputs),
                    "goodput_rps_reps": goodputs,
                    "p99_ms_reps": p99s,
                    "goodput_rps_median": sorted(goodputs)[
                        len(goodputs) // 2
                    ],
                },
                "open_loop": {
                    k: s_med[k] for k in (
                        "requests", "front", "completed", "errors",
                        "makespan_s", "offered_rps", "achieved_rps",
                        "session_rotations", "capacity_rejections",
                    )
                } | {"reconcile": s_med.get("reconcile")},
                "latency": lat | {"hist": hist_summary(h)},
                "net": net_block,
                "ring": ring if ring is not None
                else ring_block(store),
                "analysis_clean": analysis_clean_stamp(),
                "config": base_cfg | {
                    "offered_rps": net_rate, "process": "poisson",
                } | cfg_extra,
                "on_chip": _on_chip_block(),
            }

        # (a) loopback vs in-process. The loopback arm serves an
        # identically-built store (deterministic seed 0 — same params
        # by construction; the compile is a cache load) through
        # ServeServer; the direct arm is the bench's own store behind
        # a fresh continuous front.
        t0n = time.perf_counter()
        store_lb = SessionStore(
            params, bank, sched, capacity=capacity,
            hot_capacity=hot_capacity, max_batch=max_batch,
            deterministic=True, seed=0, runlog=runlog,
        )
        lb_cold_s = time.perf_counter() - t0n
        server = ServeServer(
            store_lb, ContinuousBatcher(store_lb), port=0,
            runlog=runlog,
        )
        server.start()
        # enough worker connections that the server can actually FILL
        # a width-K batch from concurrent decides (each outstanding
        # request occupies one keep-alive connection end-to-end)
        client = ServeClient(
            "127.0.0.1", server.port, workers=2 * max_batch,
        )
        ab_runs: dict[str, list] = {"direct": [], "loopback": []}
        try:
            for rep in range(max(1, ab_reps)):
                arms = (
                    ("direct", store, ContinuousBatcher(store)),
                    ("loopback", client, client),
                )
                if rep % 2:
                    arms = arms[::-1]  # cancel within-pair order bias
                for label, st, fr in arms:
                    ab_runs[label].append(net_run(st, fr))
        finally:
            client.stop()
            server.stop()
        meds = {k: net_median(v) for k, v in ab_runs.items()}
        # paired per-rep deltas (obs.metrics.paired_ab_pct): positive
        # = loopback worse (lower goodput / higher p99)
        wire_goodput_pct = paired_ab_pct(
            meds["loopback"][4], meds["direct"][4]
        )
        wire_p99_pct = paired_ab_pct(
            meds["direct"][5], meds["loopback"][5]
        )
        lb_block = {
            "tier": "loopback",
            "host": "127.0.0.1",
            "goodput_delta_pct": round(wire_goodput_pct, 2),
            "p99_delta_pct": round(wire_p99_pct, 2),
        }
        for label in ("direct", "loopback"):
            row = net_row(
                f"serve_scale_net{net_rate:g}rps_{label}",
                f"net{net_rate:g}rps", label, meds[label],
                lb_block | {"arm": label},
                {
                    "front": "continuous", "network": label != "direct",
                    "cold_start_s": round(
                        lb_cold_s if label == "loopback" else 0.0, 3
                    ),
                },
                ring=ring_block(
                    store_lb if label == "loopback" else store
                ),
            )
            rows.append(row)
            _emit_row(row)

        # (b) the replica sweep: client -> HTTP front -> affinity
        # router -> N spawned replica processes, each owning its own
        # donated store + persistent-cache AOT programs + pager. The
        # builder is this module's `_serve_setup` (spawn children
        # import `bench_decima` fresh; the __main__ bench gates keep
        # re-import side-effect-free), so every replica compiles the
        # SAME net at the SAME seed — bit-identical params fleet-wide.
        # This process has already used its device, and a chip belongs
        # to one process: spawned replicas cannot claim it too. How
        # replicas are laid out on chips is the benchmark's to decide.
        if replica_counts and jax.default_backend() != "cpu":
            raise RuntimeError(
                "bench_serve_scale's replica sweep spawns replica "
                "processes from a parent that holds the "
                f"{jax.default_backend()} device; it runs on a CPU "
                "backend only (SERVE_SCALE_REPLICAS= skips the sweep)"
            )
        spec = ReplicaSpec(
            builder="bench_decima:_serve_setup",
            serve_cfg={
                "capacity": fleet_capacity, "max_batch": fleet_batch,
                "deterministic": True, "seed": 0,
            },
        )
        sweep: dict[str, dict] = {}
        for n_rep in replica_counts:
            t0f = time.perf_counter()
            router = Router(spec, replicas=n_rep, runlog=runlog)
            boot_s = time.perf_counter() - t0f
            srv = ServeServer(router, router, port=0, runlog=runlog)
            srv.start()
            cl = ServeClient(
                "127.0.0.1", srv.port,
                workers=min(32, max(8, 2 * fleet_batch * n_rep)),
            )
            reps_f = []
            try:
                for _ in range(max(1, ab_reps)):
                    reps_f.append(net_run(cl, cl))
                fleet = router.fleet_stats()
            finally:
                cl.stop()
                srv.stop()
                router.stop()
            med = net_median(reps_f)
            fleet_block = {
                "tier": "fleet",
                "replicas": n_rep,
                "boot_s": round(boot_s, 3),
                "deaths": fleet["router_replica_deaths"],
                "decisions": fleet["serve_decisions"],
                "quarantines": fleet["serve_quarantines"],
            }
            sweep[str(n_rep)] = {
                "goodput_rps_median": med[0]["goodput_rps"],
                "p99_ms_median": med[3],
                "slo_met": med[3] <= slo_ms,
                "boot_s": round(boot_s, 3),
            }
            row = net_row(
                f"serve_scale_net{net_rate:g}rps_fleet{n_rep}",
                f"net_fleet{net_rate:g}rps", f"fleet{n_rep}", med,
                fleet_block,
                {
                    "front": "router", "network": True,
                    "replicas": n_rep,
                    "capacity": fleet_capacity,
                    "max_batch": fleet_batch,
                    "cold_start_s": round(boot_s, 3),
                },
                # fleet_stats sums replica stats, so the ring block
                # here is the FLEET's aggregate drain accounting
                ring={
                    k: int(fleet.get(k, 0)) for k in (
                        "serve_ring_occupancy", "serve_ring_drains",
                        "serve_ring_records", "serve_ring_dropped",
                    )
                },
            )
            rows.append(row)
            _emit_row(row)

        cores = os.cpu_count() or 1
        net_protocol = {
            "rate_rps": net_rate,
            "requests": net_req,
            "wire": "HTTP/1.1 keep-alive JSON over 127.0.0.1; latency "
                    "clocked from SCHEDULED arrival at the client; "
                    "server span offsets re-anchored at wire_submit "
                    "(obs/tracing.py SPAN_ORDER)",
            "loopback_ab": lb_block | {
                "goodput_rps_median": {
                    k: meds[k][0]["goodput_rps"] for k in meds
                },
                "p99_ms_median": {k: meds[k][3] for k in meds},
            },
            "replica_sweep": sweep,
            "fleet": {
                "builder": "bench_decima:_serve_setup",
                "capacity_per_replica": fleet_capacity,
                "max_batch": fleet_batch,
                "compile_cache": True,
            },
            "cpu_count": cores,
            # replica scaling is CORE-bound: N serve processes need N
            # cores to overlap device compute. Stamp the constraint so
            # a flat sweep on a small host reads as what it is.
            "single_core_note": None if cores >= 2 * max(
                replica_counts, default=1
            ) else (
                f"host has {cores} CPU core(s) for up to "
                f"{max(replica_counts, default=0)} replica processes: "
                "replicas time-share cores, so near-linear scaling "
                "cannot materialize here — the sweep measures the "
                "router/wire overhead floor, not the scale-out "
                "ceiling (run on a multi-core host for the headline)"
                " — the loopback A/B is skewed the same way: the wire "
                "tier's extra host work (JSON + thread handoffs) "
                "time-shares the one core the device compute runs on, "
                "so near-saturation goodput deltas overstate the wire "
                "cost vs a host with a free core for the front"
            ),
        }

    # the headline the A/B exists to measure: per front, the highest
    # offered (poisson) load whose MEDIAN p99 met the SLO
    sustained = {
        front: max(
            (r for r in offered
             if p99_med.get((front, r), float("inf")) <= slo_ms),
            default=0.0,
        )
        for front in fronts
    }
    overhead = _serve_obs_overhead(store)
    os.makedirs(os.path.dirname(artifact) or ".", exist_ok=True)
    with open(artifact, "w") as fp:
        json.dump({
            "protocol": {
                "slo_ms": slo_ms,
                "goodput": "replies within slo_ms of their SCHEDULED "
                           "arrival, per second of run (open-loop: "
                           "queue wait counts against the server)",
                "open_loop": "seeded deterministic arrival schedule "
                             "(serve/loadgen.py), never "
                             "back-pressured by response times",
                "ab": "paired fronts at the SAME seeded schedule per "
                      "point, arms interleaved rep-by-rep, medians "
                      "compared (PR-11 interleaved_ab protocol at "
                      "run granularity)",
                "fronts": fronts,
                "ab_reps": ab_reps,
                # ISSUE 15: the pipelined arm's architecture (its own
                # G-group store; the sync arms run the r13 layout, so
                # the A/B compares the two serve ARCHITECTURES at
                # identical seeded schedules)
                "pipeline": None if store_pipe is None else {
                    "groups": store_pipe.groups,
                    "depth": depth,
                    "harvester": harvester,
                    "inflight_peak": store_pipe.stats[
                        "serve_inflight_peak"
                    ],
                    "prefetches": store_pipe.stats[
                        "serve_prefetches"
                    ],
                },
                "sustained_rps_slo": sustained,
                # run-invariant store sizing (the pager's capacity
                # model): stamped ONCE here, not per row
                "hot_set": hot_set,
                "arrival_processes": sorted({p for _, p in points}),
                "requests_per_point": n_req,
                "offered_sweep_rps": offered,
                "obs_overhead": overhead,
                # ISSUE 14: the online arm's summary (None when
                # SERVE_SCALE_ONLINE=0)
                "online": online_protocol,
                # ISSUE 16: the network arm's summary — loopback wire
                # overhead + the replica-fleet sweep (None when
                # SERVE_SCALE_NET=0)
                "network": net_protocol,
            },
            "rows": rows,
        }, fp, indent=1)
    runlog.close()
    print(
        f"# bench_decima: wrote {artifact} ({len(rows)} rows; "
        f"sustained@SLO {sustained}; obs overhead "
        f"{overhead['overhead_pct']:+.2f}% "
        f"{'PASS' if overhead['passed'] else 'FAIL'} vs 5% bar)",
        file=sys.stderr, flush=True,
    )
    return rows


def bench_ppo(
    num_envs: int = 1024, rollout_steps: int = 256,
    compute_dtype: str | None = None, engine: str = "core",
) -> None:
    cfg_agent = {
        "agent_cls": "DecimaScheduler",
        "embed_dim": 16,
        "gnn_mlp_kwargs": {
            "hid_dims": [32, 16],
            "act_cls": "LeakyReLU",
            "act_kwargs": {"negative_slope": 0.2},
        },
        "policy_mlp_kwargs": {"hid_dims": [64, 64], "act_cls": "Tanh"},
        # bf16 matmuls with f32 params/optimizer: the same knob the
        # shipped config documents for training (README); the net is
        # shared by the rollout policy and evaluate_actions, so the
        # whole collect+update path runs MXU-native under it
        "compute_dtype": compute_dtype,
    }
    cfg_env = {
        "num_executors": 10,
        "job_arrival_cap": 50,
        "moving_delay": 2000.0,
        "job_arrival_rate": 4.0e-5,
        "warmup_delay": 1000.0,
    }
    # lane grid must cover num_envs EXACTLY or the metric name would
    # report more lanes than ran (the reduced-lane masquerade the
    # __main__ comment rules out)
    num_sequences = min(16, num_envs)
    assert num_envs % num_sequences == 0, (
        f"num_envs={num_envs} must be a multiple of {num_sequences}"
    )
    cfg_train = {
        "trainer_cls": "PPO",
        "num_iterations": 1,
        "num_sequences": num_sequences,
        "num_rollouts": num_envs // num_sequences,
        "seed": 0,
        "use_tensorboard": False,
        "num_epochs": 3,
        # minibatch = num_envs*rollout_steps/num_batches; features alone
        # are [minibatch, J, S, 5] f32 in the update, so keep minibatches
        # to a few thousand steps
        "num_batches": 64,
        "beta_discount": 5.0e-3,
        "opt_kwargs": {"lr": 3.0e-4},
        "max_grad_norm": 0.5,
        "rollout_steps": rollout_steps,
        # match the shipped flagship config (and bench.py's default);
        # BENCH_PRNG=threefry overrides, as in bench.py
        "fast_prng": os.environ.get("BENCH_PRNG", "rbg") == "rbg",
        "rollout_engine": engine,
    }
    if engine == "flat":
        knobs = _flat_knobs()
        cfg_train |= {
            "flat_micro_per_decision": float(
                os.environ.get("DEC_BENCH_FLAT_MICRO", 4.0)
            ),
            "flat_event_burst": knobs["event_burst"],
            "flat_bulk_events": knobs["bulk_events"],
            "flat_fulfill_bulk": knobs["fulfill_bulk"],
            "flat_bulk_cycles": knobs["bulk_cycles"],
        }
    trainer = PPO(
        cfg_agent, cfg_env, cfg_train,
        obs_cfg={"telemetry": TELEMETRY, "runlog": False},
    )
    state = trainer.init_state()

    def one_iter(state, i):
        ro, _, telem = trainer._collect_jit(
            state.params, state.iteration,
            jax.random.fold_in(state.rng, i), None,
        )
        state, stats = trainer._update_jit(state, ro)
        return state, ro, telem

    state, ro, _ = one_iter(state, 0)  # compile + warm
    jax.block_until_ready(state.params)

    t0 = time.perf_counter()
    n_timed = 2
    total = 0
    summaries = []
    for i in range(1, 1 + n_timed):
        state, ro, telem = one_iter(state, i)
        total += int(jax.block_until_ready(ro.valid).sum())
        if telem is not None:
            summaries.append(summarize(telem))
    dt = time.perf_counter() - t0
    value = total / dt
    tag = f"_{compute_dtype}" if compute_dtype else ""
    eng_tag = "_flat" if engine == "flat" else ""
    row = {
        "metric": f"ppo_train_steps_per_sec_{num_envs}envs{tag}{eng_tag}",
        "value": round(value, 1),
        "unit": "steps/s",
        "vs_baseline": round(value / TARGET, 3),
        "analysis_clean": analysis_clean_stamp(),
        "config": {
            "num_envs": num_envs,
            "rollout_steps": rollout_steps,
            "engine": engine,
            "dtype": bank_dtype_label(trainer.bank),
            "obs_dtype": trainer.params_env.obs_dtype,
            "job_bucket": int(cfg_agent.get("job_bucket", 0)),
            "single_eval": bool(trainer.flat_single_eval),
            "prng_impl": str(jax.config.jax_default_prng_impl),
            "backend": jax.default_backend(),
            "telemetry": TELEMETRY,
        },
        "memory": _registry_proxy_stamp(),
    }
    if summaries:
        row["telemetry"] = summaries[-1]
    _emit_row(row)


if __name__ == "__main__":
    from sparksched_tpu.config import (
        enable_compilation_cache,
        use_fast_prng,
    )

    enable_compilation_cache()
    if os.environ.get("BENCH_PRNG", "rbg") == "rbg":
        use_fast_prng()
    # lane counts are overridable for CPU-round artifacts (the metric
    # name embeds the lane count, so a reduced-lane run can never
    # masquerade as the chip-scale row); defaults are the BASELINE.md
    # config #3/#4 scales
    infer_envs = int(os.environ.get("DEC_BENCH_INFER_ENVS", 64))
    infer_steps = int(os.environ.get("DEC_BENCH_INFER_STEPS", 512))
    ppo_envs = int(os.environ.get("DEC_BENCH_PPO_ENVS", 1024))
    ppo_steps = int(os.environ.get("DEC_BENCH_PPO_STEPS", 256))
    # DEC_BENCH_INFER=0 / DEC_BENCH_PPO=0 skip whole sections (the
    # SERVE_BENCH idiom) so a time-boxed round can run just the slice
    # it is re-measuring
    if os.environ.get("DEC_BENCH_INFER", "1") == "1":
        bench_inference(num_envs=infer_envs, steps=infer_steps)
        bench_inference(
            num_envs=infer_envs, steps=infer_steps,
            compute_dtype="bfloat16",
        )
        bench_inference(
            num_envs=infer_envs, steps=infer_steps, engine="flat"
        )
        bench_inference(
            num_envs=infer_envs, steps=infer_steps,
            compute_dtype="bfloat16", engine="flat",
        )
        bench_inference(
            num_envs=infer_envs, steps=infer_steps, engine="fastpath"
        )
        bench_inference(
            num_envs=infer_envs, steps=infer_steps,
            compute_dtype="bfloat16", engine="fastpath",
        )
        # ISSUE 7 dtype sweep: the f32 fastpath row above vs the
        # quantized (int16 dur table, per-template scale) bank on the
        # SAME collector and knobs — the low-precision layout's
        # throughput effect as a recorded A/B. DEC_BENCH_BANK_DTYPE
        # overrides the swept layout.
        bench_inference(
            num_envs=infer_envs, steps=infer_steps, engine="fastpath",
            bank_dtype=os.environ.get("DEC_BENCH_BANK_DTYPE", "int16"),
        )
    if os.environ.get("DEC_BENCH_PPO", "1") == "1":
        bench_ppo(num_envs=ppo_envs, rollout_steps=ppo_steps)
        bench_ppo(
            num_envs=ppo_envs, rollout_steps=ppo_steps,
            compute_dtype="bfloat16",
        )
        bench_ppo(
            num_envs=ppo_envs, rollout_steps=ppo_steps, engine="flat"
        )
    # ISSUE 10: decision-serving latency rows (p50/p99, batch=1 vs
    # batch=K, cold start + linger sweep) through the AOT session
    # store; SERVE_BENCH=0 skips
    if os.environ.get("SERVE_BENCH", "1") == "1":
        bench_serve_latency()
    # ISSUE 11: open-loop goodput@SLO rows (offered-load sweep through
    # the seeded load generator + instrumented micro-batching front);
    # SERVE_SCALE_BENCH=0 skips
    if os.environ.get("SERVE_SCALE_BENCH", "1") == "1":
        bench_serve_scale()
    # ISSUE 17: the round's top-level summary artifact (the headline
    # bench series the perf ledger indexes)
    _write_bench_summary()
