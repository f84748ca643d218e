"""Headline benchmark: env decision-steps/sec with 1024 vmapped
environments (synthetic TPC-H-shaped workload bank) driven by the jitted
fair scheduler on one chip (BASELINE.md config #4 analog; north-star
target >= 50k env-steps/sec).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "steps/s", "vs_baseline": N/50000}

The reference has no published numbers (BASELINE.md); `vs_baseline` is
measured against the 50k steps/sec north-star target from the driver's
BASELINE.json.

Engine: the flat micro-step loop (env/flat_loop.py) — every lane advances
by one unit of work (decide / fulfill / event) per iteration, so no lane
pays the batch-max event count of the per-decision `core.step` while_loop
(the ~6x straggler tax measured in flat_loop.py's docstring). Two further
measured optimizations (probes on the v5e, 2026-07-30):

- bulk relaunch (`core._bulk_relaunch`): one EVENT micro-step consumes a
  whole run of task-relaunch events — the dominant event kind — instead
  of one, cutting micro-steps per decision several-fold;
- reset hoisting: `core.reset` (a full arrival-sequence resample) plus
  the fresh/old tree-select cost 2.7 of the 6.7 ms per 1024-lane
  micro-step when auto-reset runs inside the loop. Chunks run with
  auto_reset=False (done lanes freeze, episodes last thousands of
  micro-steps so the idle tail is <~2%) and done lanes are re-seeded
  between timed chunks by `reset_done_lanes`.
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from sparksched_tpu.config import EnvParams
from sparksched_tpu.env import core
from sparksched_tpu.env.flat_loop import init_loop_state, run_flat
from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros_like
from sparksched_tpu.schedulers.heuristics import round_robin_policy
from sparksched_tpu.workload import bank_dtype_label, make_workload_bank

import os

# lane count; overridable for off-chip smoke runs (the headline metric
# is only comparable at the default 1024)
NUM_ENVS = int(os.environ.get("BENCH_NUM_ENVS", 1024))


def _parse_mesh_dp() -> int:
    """`--mesh-dp N` CLI flag (wins) or BENCH_MESH_DP env var; 0 = no
    mesh (the single-device bench). dp=1 normalizes to 0 — the
    unsharded bench IS the 1-device configuration (mesh_from_config
    has the same contract), and mesh-only code paths (single-pass
    SUB_BATCH, the `_dpN` metric) must not trigger without sharding."""
    v = int(os.environ.get("BENCH_MESH_DP", "0") or 0)
    if "--mesh-dp" in sys.argv:
        i = sys.argv.index("--mesh-dp")
        try:
            v = int(sys.argv[i + 1])
        except (IndexError, ValueError):
            sys.exit("bench.py: --mesh-dp needs an integer argument")
    return 0 if v <= 1 else v


# dp-mesh scale-out (ISSUE 6): shard the lane axis over a 1-D dp mesh
# (parallel.py) and emit a row tagged `dp` with per-device lanes and
# per-device dec/s alongside the aggregate. `--mesh-dp N` needs N
# visible devices — real chips, or (BENCH_VIRTUAL_MESH=1, CI) a
# virtual N-device CPU backend the __main__ block bootstraps. Mesh
# rows are a separate metric name (`..._dpN`): sharded numbers must
# never masquerade as the single-chip headline.
MESH_DP = _parse_mesh_dp()
# lanes are processed in sub-batches of 512 via lax.map inside one jit —
# same program, bounded vector width. Overridable via the env var. When
# it is UNSET on an accelerator, main() tries the single-pass 1024-lane
# sub-batch first (it compiles for the v5e:
# tests/test_tpu_compile.py), keeps this default on any failure, and
# records which was used in the row.
_SB_ENV = os.environ.get("BENCH_SUB_BATCH")
SUB_BATCH = min(int(_SB_ENV) if _SB_ENV is not None else 512, NUM_ENVS)
# cascade length of the bulk-relaunch scan (core._bulk_relaunch); unset
# -> self-calibrate between the cascade (8) and the single-event path
# (0) with one short chunk each before the timed run, since the
# op-count-vs-step-count trade differs across backends
_BULK_ENV = os.environ.get("BENCH_BULK_EVENTS")
BULK_EVENTS = int(_BULK_ENV) if _BULK_ENV is not None else None
# fulfillment-prefix bulking in the flat loop (core._bulk_fulfill, run
# in the shared micro-step tail); unset -> calibrated alongside
# bulk_events
_FB_ENV = os.environ.get("BENCH_FULFILL_BULK")
FULFILL_BULK = bool(int(_FB_ENV)) if _FB_ENV is not None else None
# chained (relaunch + ready) pass pairs per micro-step
# (flat_loop._bulk_cycle_chain); unset -> calibrated
_BC_ENV = os.environ.get("BENCH_BULK_CYCLES")
BULK_CYCLES = int(_BC_ENV) if _BC_ENV is not None else None
# ISSUE 7: single fused bulk kernel (core._bulk_events_fused — mixed
# relaunch/arrival runs in exact queue order, one pass per cycle) vs
# the round-3/4 (relaunch cascade + arrival burst) pass pair.
# Step-exact either way (tests/test_flat_loop.py), so this is purely a
# dispatch-count A/B knob; BENCH_BULK_FUSED=0 runs the unfused pair.
BULK_FUSED = os.environ.get("BENCH_BULK_FUSED", "1") == "1"
# ISSUE 7 low-precision bank layout: BENCH_BANK_DTYPE in
# {int8,int16,bf16} re-encodes the workload bank's dur table via
# workload.quantize_bank (f32 accumulation at the single gather site);
# every row stamps config.dtype with the bank's actual dur dtype so
# the A/B is recorded, never inferred
BANK_DTYPE = os.environ.get("BENCH_BANK_DTYPE") or None
# keep each timed program short and accumulate across calls
MICRO_CHUNK = 256  # micro-steps per timed scan
assert NUM_ENVS % SUB_BATCH == 0, (
    f"BENCH_SUB_BATCH={SUB_BATCH} must divide {NUM_ENVS}"
)
# timed chunks; BENCH_NUM_CHUNKS raises it for small-lane A/Bs whose
# default window is seconds long (machine noise swamps a short window
# — the ISSUE-7 fusion A/B measured ±20% run-to-run at 8 lanes x 4
# chunks; the chunk count rides the row's config for comparability)
NUM_CHUNKS = int(os.environ.get("BENCH_NUM_CHUNKS", 4))
TARGET = 50_000.0  # steps/sec north-star (BASELINE.json)
# extra bulk_cycles values tried when BENCH_BULK_CYCLES is unset (the
# baseline candidate always runs bc=1); every candidate costs a warmup
# + calibration chunk at full lane count
_BC_CANDS = (2, 3)
# extra bulk_events (cascade scan length) values tried when
# BENCH_BULK_EVENTS is unset: round-5 session 1 measured a 2x swing
# between be=8 and be=0 on chip, so the scan length is a live knob —
# but only be∈{8,0} had ever been calibrated.
_BE_CANDS = (4, 16)
# on-device telemetry counters ride the micro-step scan carry and stamp
# the emitted row with micro-step composition + straggler ratio
# (sparksched_tpu/obs/telemetry.py) — a dozen scalar i32 adds against a
# multi-thousand-eqn micro-step (<5% measured on the CPU row; see
# scripts_obs_demo.py for the A/B). BENCH_TELEMETRY=0 turns it off.
TELEMETRY = os.environ.get("BENCH_TELEMETRY", "1") == "1"
# every row records whether the tree passes the static analyzer
# (sparksched_tpu/analysis: jaxpr audit + AST lint + pytree contracts)
# so perf rows from a dirty tree are self-identifying. Once per
# process, CPU-pinned subprocess (it can never claim the accelerator
# this bench holds); BENCH_ANALYSIS=0 stamps null, crash/timeout
# stamps false — semantics live in analysis_clean_stamp.
from sparksched_tpu.analysis import analysis_clean_stamp

# every row additionally carries a `memory` block (ISSUE 5): runtime
# allocator stats (mem_peak_bytes — null on backends without them) and
# the lane-fit prediction for the EXACT timed lane program at this
# row's calibrated knobs (obs/memory.py: two small vmapped traces +
# a per-buffer linear model — never compiles, never rides the timed
# window). BENCH_MEMFIT=0 skips the trace-time prediction.
from sparksched_tpu.obs.memory import (
    gb,
    lane_fit,
    memory_row_stamp,
)

MEMFIT = os.environ.get("BENCH_MEMFIT", "1") == "1"


def _fit_lane_callable(params, bank, bulk_events, fulfill_bulk,
                       bulk_cycles):
    """The per-lane program bench_chunk vmaps, rebuilt standalone for
    the memory pass (bench_chunk's own closure is trace-internal)."""
    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    def lane(ls, rng):
        return run_flat(
            params, bank, pol, rng, MICRO_CHUNK,
            auto_reset=False, compute_levels=False,
            event_bulk=bulk_events > 0,
            bulk_events=max(bulk_events, 1),
            fulfill_bulk=fulfill_bulk, bulk_cycles=bulk_cycles,
            loop_state=ls, bulk_fused=BULK_FUSED,
        )

    return lane


def _fit_lane_args(params, bank):
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda k: core.reset(params, bank, k), key)
    return (jax.eval_shape(init_loop_state, state), key)


def _memory_stamp(params, bank, bulk_events, fulfill_bulk, bulk_cycles,
                  mesh=None):
    if not MEMFIT:
        return memory_row_stamp()
    return memory_row_stamp(
        _fit_lane_callable(
            params, bank, bulk_events, fulfill_bulk, bulk_cycles
        ),
        _fit_lane_args(params, bank),
        candidates=tuple(sorted({SUB_BATCH, NUM_ENVS, 1024})),
        # dp mesh: candidates are global lane counts, the fit is per
        # SHARD against the per-chip budget (obs/memory.py lane_fit)
        mesh=mesh,
    )


def _predict_skip_cause(params, bank, bulk_events, fulfill_bulk,
                        bulk_cycles, mesh=None) -> str | None:
    """The memory pass's verdict on a failed calibration candidate: is
    this the single-buffer HBM blowup class (the round-5 19.4 GB OOM)
    at this sub-batch width, and which buffer dominates. Best-effort —
    a failed *prediction* must never take the bench down."""
    if not MEMFIT:
        return None
    try:
        fit = lane_fit(
            _fit_lane_callable(
                params, bank, bulk_events, fulfill_bulk, bulk_cycles
            ),
            _fit_lane_args(params, bank),
            candidates=(SUB_BATCH,),
            mesh=mesh,
        )
        c = fit["candidates"][0]
        top = c.get("top", {})
        verdict = (
            "predicts OOM" if not c["fits"]
            else "predicts fit (not a single-buffer HBM blowup)"
        )
        return (
            f"memory pass {verdict} at {SUB_BATCH} lanes: est "
            f"~{gb(c['est_peak_bytes'])} GB vs "
            f"{gb(fit['budget_bytes'])} GB budget; dominant buffer "
            f"{top.get('op')} {top.get('shape')}"
        )
    except Exception:
        return None


def _metric_suffix() -> str:
    """A CPU run never carries the device metric's name."""
    return "_cpu" if jax.default_backend() == "cpu" else ""


@partial(
    jax.jit, static_argnums=(0, 4, 5, 6), static_argnames=("sub_batch",)
)
def bench_chunk(params: EnvParams, bank, loop_states, rngs, bulk_events,
                fulfill_bulk, bulk_cycles=1, telem=None, *,
                sub_batch=None):
    """MICRO_CHUNK flat micro-steps per lane; returns updated loop
    states, the per-lane telemetry (or None), and the total decision
    count across the batch. `sub_batch` overrides the module-level
    SUB_BATCH (it must be an explicit static arg: the 1024-lane retry
    re-invokes with a different width, and a global read inside the
    traced body would silently reuse the first trace)."""
    track = telem is not None
    if sub_batch is None:
        sub_batch = SUB_BATCH

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    def lane(ls, rng, tm=None):
        return run_flat(
            params, bank, pol, rng, MICRO_CHUNK,
            auto_reset=False, compute_levels=False,
            event_bulk=bulk_events > 0,
            bulk_events=max(bulk_events, 1),
            fulfill_bulk=fulfill_bulk, bulk_cycles=bulk_cycles,
            loop_state=ls, telemetry=tm, bulk_fused=BULK_FUSED,
        )

    b = jax.tree_util.tree_leaves(rngs)[0].shape[0]
    sub = min(sub_batch, b)
    tree = (loop_states, rngs, telem) if track else (loop_states, rngs)
    group = jax.tree_util.tree_map(
        lambda a: a.reshape(b // sub, sub, *a.shape[1:]), tree
    )
    if track:
        out = lax.map(
            lambda sr: jax.vmap(lane)(sr[0], sr[1], sr[2]), group
        )
    else:
        out = lax.map(lambda sr: jax.vmap(lane)(sr[0], sr[1]), group)
    out = jax.tree_util.tree_map(
        lambda a: a.reshape(b, *a.shape[2:]), out
    )
    loop_states, telem = out if track else (out, None)
    return loop_states, telem, loop_states.decisions.sum()


@partial(jax.jit, static_argnums=(0,))
def reset_done_lanes(params: EnvParams, bank, loop_states, keys):
    """Re-seed finished lanes between timed chunks (reset hoisting: see
    module docstring). Counters persist; only env/loop mode restart."""
    fresh_env = jax.vmap(lambda k: core.reset(params, bank, k))(keys)
    fresh = jax.vmap(init_loop_state)(fresh_env)
    fresh = fresh.replace(
        decisions=loop_states.decisions,
        episodes=loop_states.episodes,
        bulked=loop_states.bulked,
    )
    done = (
        jax.vmap(lambda e: e.all_jobs_complete)(loop_states.env)
        | (loop_states.env.wall_time >= loop_states.env.time_limit)
    )
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(
            done.reshape(done.shape + (1,) * (a.ndim - 1)), a, b
        ),
        fresh,
        loop_states,
    )


def main() -> None:
    params = EnvParams(
        num_executors=10,
        max_jobs=50,
        max_stages=20,
        max_levels=20,
        moving_delay=2000.0,
        warmup_delay=1000.0,
        job_arrival_rate=4e-5,
        mean_time_limit=None,
    )
    bank = make_workload_bank(
        params.num_executors, params.max_stages, bank_dtype=BANK_DTYPE
    )
    if bank.max_stages != params.max_stages:
        params = params.replace(
            max_stages=bank.max_stages, max_levels=bank.max_stages
        )

    global SUB_BATCH

    # --- dp mesh (ISSUE 6): lane axis sharded over the devices ---------
    mesh = None
    if MESH_DP:
        from sparksched_tpu.parallel import make_mesh, shard_lanes

        assert NUM_ENVS % MESH_DP == 0, (
            f"BENCH_MESH_DP={MESH_DP} must divide {NUM_ENVS}"
        )
        mesh = make_mesh(MESH_DP)
        # single pass over the full lane stack: the lax.map sub-batch
        # reshape would fold the sharded lane axis into a leading trip
        # dimension and force resharding every map step (the sub-batch
        # fault workaround is a single-chip concern; per-device width
        # here is NUM_ENVS/dp, already below the fault boundary for
        # dp >= 2 at the headline 1024)
        SUB_BATCH = NUM_ENVS

    def shard(tree):
        return shard_lanes(tree, mesh) if mesh is not None else tree

    def lane_keys(seed: int):
        return shard(
            jax.random.split(jax.random.PRNGKey(seed), NUM_ENVS)
        )

    rng = jax.random.PRNGKey(0)
    reset_keys = jax.random.split(rng, NUM_ENVS)
    states = jax.vmap(lambda k: core.reset(params, bank, k))(reset_keys)
    loop_states = shard(jax.vmap(init_loop_state)(states))

    # --- sub-batch resolution (round-8 headroom retry) -----------------
    # With BENCH_SUB_BATCH unset and an accelerator answering, try the
    # single-pass 1024-lane sub-batch first: success halves the
    # lax.map trip count. ANY failure keeps the 512 default; the
    # emitted row records which was used (config.sub_batch) and the
    # retry outcome. CPU never probes.
    sub_batch_retry = None
    if (
        _SB_ENV is None
        and not MESH_DP  # mesh runs are single-pass already
        and jax.default_backend() != "cpu"
        and NUM_ENVS >= 1024
        and NUM_ENVS % 1024 == 0
    ):
        try:
            _, _, n = bench_chunk(
                params, bank, loop_states, lane_keys(50),
                8, True, 1, None, sub_batch=1024,
            )
            jax.block_until_ready(n)
        except Exception as err:
            sub_batch_retry = f"failed: {type(err).__name__}"
            print(
                f"# bench: sub-batch 1024 retry failed "
                f"({type(err).__name__}: {str(err)[:200]}); keeping "
                f"{SUB_BATCH}",
                file=sys.stderr, flush=True,
            )
        else:
            sub_batch_retry = "ok"
            SUB_BATCH = 1024
            print(
                "# bench: sub-batch 1024 retry succeeded; using 1024",
                file=sys.stderr, flush=True,
            )

    # warmup/compile (also warms every calibration candidate). A
    # candidate that fails to compile or run on this backend (e.g. an
    # HBM-exceeding allocation — the tiled-layout cost of a program
    # differs across backends) is dropped from calibration instead of
    # killing the bench; at least one candidate must survive.
    if (
        BULK_EVENTS is not None
        and FULFILL_BULK is not None
        and BULK_CYCLES is not None
    ):
        cands = [(BULK_EVENTS, FULFILL_BULK, BULK_CYCLES)]
    else:
        be = BULK_EVENTS if BULK_EVENTS is not None else 8
        fb = FULFILL_BULK if FULFILL_BULK is not None else True
        bc = BULK_CYCLES if BULK_CYCLES is not None else 1
        cands = [(be, fb, bc)]
        if BULK_CYCLES is None and be > 0:
            # bulk_cycles is a no-op with event bulking off
            cands += [(be, fb, c) for c in _BC_CANDS]
        if FULFILL_BULK is None:
            cands += [(be, False, bc)]
        if BULK_EVENTS is None:
            # alternate cascade lengths, then the no-bulk baseline,
            # holding any explicitly pinned knobs. The cascade-length
            # sweep is accelerator-only: on a CPU host every candidate
            # costs a full-lane warmup + chunk, and the CPU optimum
            # has been stable at be=8 across rounds.
            if jax.default_backend() != "cpu":
                cands += [(b, fb, bc) for b in _BE_CANDS]
            cands += [(0, fb, bc)]
        cands = list(dict.fromkeys(cands))
    telem = (
        shard(telemetry_zeros_like((NUM_ENVS,)))
        if TELEMETRY else None
    )

    skipped_candidates: list[dict] = []

    def warm_candidates(cands, loop_states, telem):
        keys = lane_keys(1)
        ok = []
        for i, (be, fb, bc) in enumerate(cands):
            try:
                ls_try, tm_try, n = bench_chunk(
                    params, bank, loop_states, keys, be, fb, bc, telem,
                    sub_batch=SUB_BATCH,
                )
                jax.block_until_ready(n)
            except Exception as err:
                # not a bare skip: ask the memory pass whether this is
                # the HBM-blowup failure class and which buffer — the
                # round-5 OOM's postmortem, available at skip time
                cause = _predict_skip_cause(
                    params, bank, be, fb, bc, mesh=mesh
                )
                print(
                    f"# bench: candidate bulk_events={be} "
                    f"fulfill_bulk={fb} bulk_cycles={bc} skipped at "
                    f"sub-batch {SUB_BATCH} "
                    f"({type(err).__name__}: {str(err)[:200]})"
                    + (f"; {cause}" if cause else ""),
                    file=sys.stderr, flush=True,
                )
                skipped_candidates.append({
                    "bulk_events": int(be), "fulfill_bulk": bool(fb),
                    "bulk_cycles": int(bc), "sub_batch": SUB_BATCH,
                    "error": type(err).__name__,
                    "mem_predicted": cause,
                })
            else:
                loop_states = ls_try
                telem = tm_try
                ok.append((be, fb, bc))
            keys = lane_keys(90 + i)
        return ok, loop_states, telem

    ok_cands, loop_states, telem = warm_candidates(
        cands, loop_states, telem
    )
    if len(ok_cands) < len(cands) and sub_batch_retry == "ok":
        # the 1024 promotion must not NARROW the calibration set: the
        # fault being retried is program-dependent, so a candidate that
        # faults only at the wider width deserves its 512-wide run —
        # demote and re-warm everything at the safe width instead of
        # silently calibrating over fewer engine configs
        SUB_BATCH = 512
        sub_batch_retry = "demoted: candidate failed at 1024"
        print(
            "# bench: demoting sub-batch to 512 (a calibration "
            "candidate failed at 1024); re-warming all candidates",
            file=sys.stderr, flush=True,
        )
        ok_cands, loop_states, telem = warm_candidates(
            cands, loop_states, telem
        )
    if not ok_cands:
        raise RuntimeError("bench: every engine configuration failed")
    cands = ok_cands
    if len(cands) > 1:
        rates = {}
        for i, (be, fb, bc) in enumerate(cands):
            # re-seed finished lanes before each candidate so all
            # measure the same live-lane precondition
            loop_states = reset_done_lanes(
                params, bank, loop_states, lane_keys(80 + i),
            )
            d0 = int(jax.block_until_ready(loop_states.decisions.sum()))
            kk = lane_keys(70 + i)
            tc = time.perf_counter()
            loop_states, telem, n = bench_chunk(
                params, bank, loop_states, kk, be, fb, bc, telem,
                sub_batch=SUB_BATCH,
            )
            d1 = int(jax.block_until_ready(n))
            rates[(be, fb, bc)] = (d1 - d0) / (time.perf_counter() - tc)
            print(
                f"# bench: candidate be={be} fb={int(fb)} bc={bc}: "
                f"{rates[(be, fb, bc)]:.0f} dec/s",
                file=sys.stderr, flush=True,
            )
        bulk_events, fulfill_bulk, bulk_cycles = max(rates, key=rates.get)
    else:
        bulk_events, fulfill_bulk, bulk_cycles = cands[0]
    # timed run starts from a freshly re-seeded lane population on both
    # the calibrated and the env-pinned paths
    loop_states = reset_done_lanes(
        params, bank, loop_states, lane_keys(101),
    )
    base = int(jax.block_until_ready(loop_states.decisions.sum()))
    # telemetry snapshot: the emitted summary covers the timed window
    # only, not the warmup/calibration chunks
    telem_snap = jax.device_get(telem) if TELEMETRY else None

    t0 = time.perf_counter()
    for i in range(NUM_CHUNKS):
        keys = lane_keys(2 + i)
        loop_states, telem, n = bench_chunk(
            params, bank, loop_states, keys, bulk_events, fulfill_bulk,
            bulk_cycles, telem, sub_batch=SUB_BATCH,
        )
        loop_states = reset_done_lanes(
            params, bank, loop_states, lane_keys(102 + i),
        )
        total = int(jax.block_until_ready(n))
    dt = time.perf_counter() - t0

    value = (total - base) / dt
    # the trailing config keys make every recorded BENCH_r*.json
    # self-describing (burst/bulk/PRNG defaults have changed across
    # rounds; numbers are only comparable at equal config). The lane
    # count is part of the metric name so an off-default smoke run can
    # never masquerade as the headline number.
    row = {
        "metric": (
            f"env_decision_steps_per_sec_{NUM_ENVS}envs_fair_"
            "synthetic_tpch"
            + (f"_dp{MESH_DP}" if MESH_DP else "")
            + _metric_suffix()
        ),
        "value": round(value, 1),
        "unit": "steps/s",
        "vs_baseline": round(value / TARGET, 3),
        "analysis_clean": analysis_clean_stamp(),
        "config": {
            "num_envs": NUM_ENVS,
            "num_chunks": NUM_CHUNKS,
            "sub_batch": SUB_BATCH,
            # None: pinned by env var / CPU / lane count not applicable;
            # "ok"/"failed: ...": the 1024-lane single-pass retry outcome
            "sub_batch_retry_1024": sub_batch_retry,
            "bulk_events": int(bulk_events),
            "fulfill_bulk": bool(fulfill_bulk),
            "bulk_cycles": int(bulk_cycles),
            # ISSUE 7: fused-bulk-kernel knob + the bank's dur-table
            # dtype ("f32"/"bf16"/"int8"/"int16") — rows are only
            # comparable at equal engine AND layout config
            "bulk_fused": BULK_FUSED,
            "dtype": bank_dtype_label(bank),
            "obs_dtype": params.obs_dtype,
            "calibrated": BULK_EVENTS is None
            or FULFILL_BULK is None
            or BULK_CYCLES is None,
            "prng_impl": str(jax.config.jax_default_prng_impl),
            "backend": jax.default_backend(),
            # rows are only comparable at equal config: the counters
            # ride the scan carry, so the flag is part of the config
            # (rounds <= 6 ran telemetry-free, i.e. telemetry: false)
            "telemetry": TELEMETRY,
        },
    }
    if MESH_DP:
        # the sharded row's own vocabulary: aggregate dec/s is `value`;
        # per-device dec/s and lanes make the row a scaling datum on
        # its own (MULTICHIP_r*.json carries these rows verbatim)
        row["config"]["dp"] = MESH_DP
        row["config"]["lanes_per_device"] = NUM_ENVS // MESH_DP
        row["per_device"] = {
            "dp": MESH_DP,
            "lanes": NUM_ENVS // MESH_DP,
            "steps_per_sec": round(value / MESH_DP, 1),
        }
    if skipped_candidates:
        # a row whose calibration silently dropped candidates is not
        # comparable with one that tried them all — the skip list (with
        # the memory pass's per-candidate verdict) rides the row
        row["config"]["skipped_candidates"] = skipped_candidates
    # runtime allocator stats + the lane-fit prediction for the exact
    # timed program at the calibrated knobs; computed AFTER the timed
    # window (the two small traces must not ride the measured chunks)
    row["memory"] = _memory_stamp(
        params, bank, bulk_events, fulfill_bulk, bulk_cycles, mesh=mesh
    )
    if TELEMETRY:
        # micro-step composition + straggler ratio over the timed
        # window, from the same module every bench row stamps from
        # (sparksched_tpu/obs/telemetry.py)
        row["telemetry"] = summarize(telem, prev=telem_snap)
    print(json.dumps(row))


if __name__ == "__main__":
    from sparksched_tpu.config import (
        enable_compilation_cache,
        use_fast_prng,
    )

    if MESH_DP > 1 and os.environ.get("BENCH_VIRTUAL_MESH") == "1":
        # CI / single-chip hosts: bootstrap a virtual MESH_DP-device
        # CPU backend (the same in-process flip tests/conftest.py
        # uses) so the sharded row is measurable without hardware —
        # the row stays honestly labeled via config.backend and the
        # _cpu metric suffix
        from __graft_entry__ import force_virtual_cpu_devices

        force_virtual_cpu_devices(MESH_DP)
    enable_compilation_cache()
    if os.environ.get("BENCH_PRNG", "rbg") == "rbg":
        use_fast_prng()
    main()
