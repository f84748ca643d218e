"""Memory pass: HBM-byte accounting, the bank-broadcast rule, and the
lane-fit advisor over the registered hot programs.

The round-5 flagship bench died in XLA allocation analysis with a
19.4 GB temp — a per-lane broadcast of the workload bank's duration
table (`f32[512,154,20,3,8,16]`) that XLA:CPU folds away, so no CPU
test, bench or calibration run could see it (PERF_ROUNDS.md "Round-3 on-chip
session 1"; fixed by commit 81e77fb). This pass makes that class of
failure a CPU-checkable CI failure, with the same shape as the eqn
budgets in `jaxpr_audit`:

Rules (ids used in the JSON report and the fixture tests):

- ``bank-broadcast``: no vmapped lane program (`observe`,
  `micro_step`, `decide_micro_step`, `drain_to_decision` — the
  registry programs that run under a lane vmap in production) may
  contain a lane-batched producer of a workload-bank-shaped array
  (`dur[T,S,3,L,K]`, `cnt[T,S,3,L]`, `adj[T,S,S]` with a leading lane
  dim). jax's cond/switch batching broadcasts closed-over operands
  when the predicate is lane-dependent, so a bank access inside a
  lane-dependent branch materializes a per-lane table copy — the
  exact invariant 81e77fb restored, checked on the JAXPR (before
  backend folding) so CPU CI sees what the TPU would allocate.
- ``mem-budget``: per-program `temp_total_bytes` (the tile-padded sum
  over every intermediate buffer of the UNBATCHED program at audit
  shapes — no liveness model, but stable and monotone in program
  growth) within the declarative `MEM_BUDGETS` bands below.

The report additionally carries, per program: the full trace-time
byte accounting (`obs.memory.jaxpr_memory_estimate` — args / outputs /
consts / temp-total / peak lower bound and a top-K largest-buffer
attribution naming shape + producing op), and for the lane programs a
lane-fit table (max lanes under the `TPU_HBM_BUDGET_BYTES` budget,
default 17.2 GB = the v5-lite part in PERF_ROUNDS.md).

Backend-true accounting (`compiled.memory_analysis()` after a real AOT
compile) is NOT part of the default pass — it is backend-dependent
(CPU folds, TPU pads) and compiling every registry program would roughly
double the gate's cost. `program_memory_accounting(compile=True)`
exposes it for the chip session (stage 11) and the CLI's
`--mem-compile` flag.

Re-pin procedure (same contract as jaxpr_audit.BUDGETS): run
`python -m sparksched_tpu.analysis` — the report's
`passes.memory.measured` block prints every program's measured
temp-total bytes. A deliberate change that moves a program's bytes
gets a new cap of ~1.35x the measured value IN THE SAME PR, with a
bench row justifying the growth (PERF_ROUNDS.md "Memory"). Bands are loose:
byte totals drift a few percent across jax versions as fusion
boundaries move; a band breach means structural allocation growth
(a new lane-batched table, a widened buffer), not noise.

Pinned 2026-08 (jax 0.4.37, threefry, CPU trace, tile-padded audit
shapes) — measured temp-total MB: observe 2.3, decima_score 153.6,
decima_batch_policy 169.2, ppo_update 269.6. Re-pinned 2026-08-03
for the ISSUE-7 fused bulk kernel, which SHRANK the engine programs:
micro_step 22.1 -> 16.1, drain_to_decision 16.2 -> 9.7,
flat_collect_batch 357.7 -> 329.8; decide_micro_step unchanged at
9.9 (its bulk phase is the mode-exclusive fulfill pass, deliberately
unfused). Re-pinned 2026-09-28 (PR 31): `decide_micro_step` lost its
`auto_reset` (a decide step cannot end an episode, so the reset
program it evaluated selected nothing): 9.9 -> 6.0, cap 14 -> 8;
`drain_to_decision(auto_reset=True)` re-seeds once after its loop and
no longer in the loop's body: 9.5 -> 10.0 as registered (one lane, no
lane axis: the re-seed unconditional; the loop's body took the sync
tail's freeze select), inside its cap. Re-pinned 2026-09-29 (PR 33):
`DecimaNet` sums a node's children's messages by a select and a reduce
over the child axis where it had a per-job matrix product, and a jaxpr
holds the select's [J,S,S,D] operand as a buffer of its own (at audit
shapes f32[4,20,20,20,16], 19.7 MB tile-padded, once a level step and
once more in the update's backward pass). The compiler fuses it into
the reduce, so this is the MODEL's growth, not the chip's: compiled
for the v5e at 128 lanes x 200 jobs the net's temporaries FELL, 331 ->
166 MB, and the gradient over 96 samples 3.69 -> 3.22 GB (PERF.md,
PR 33; tests/test_tpu_compile.py bounds the compiled collector and
update). Every program that evaluates the net moved, measured MB
before -> after, caps 1.35x the new value: decima_score 153.6 ->
361.4, decima_batch_policy 169.2 -> 377.0, ppo_update 270.2 -> 464.1,
ppo_update_health 270.5 -> 464.3, flat_collect_batch 366.2 -> 574.1,
flat_collect_batch_health 367.0 -> 574.8, serve_decide 58.9 -> 110.9,
serve_decide_record 59.2 -> 111.2, serve_decide_record_ring 59.3 ->
111.3, serve_decide_batch 362.3 -> 570.1, serve_decide_batch_sharded
366.1 -> 574.0, serve_decide_batch_group 361.4 -> 569.2,
serve_decide_batch_record 363.7 -> 571.5,
serve_decide_batch_record_ring 363.9 -> 571.8; the four engine
programs did not move. (The decima/ppo programs
carry a 4-lane batch in their audited shapes, and tile padding
inflates narrow minor dims — these are model numbers for regression
detection, not literal HBM footprints; the lane-fit table is the
footprint story.) Re-pinned 2026-10-04 (PR 50): the reset program
packs a stage's duration facts from the bank it is handed
(`sampling.pack_duration_facts`: a transpose of `cnt` to
i32[3,8,T,S], 1.97 MB tile-padded, a compare, a shift and a sum over
[32, T*S]; 3.7 MB in all, once a RESET, in no loop), and `EnvState`
has the leaf `duration_facts`, u32[J,S]. The two one-lane programs
that hold a reset moved, measured MB before -> after, caps 1.35x the
new value: micro_step 18.7 -> 22.4 (cap 22 -> 30), drain_to_decision
12.1 -> 16.0 (cap 14 -> 22); the batch programs moved by under 1%
inside their caps (serve_decide_batch 622.5 -> 627.7,
flat_collect_batch 627.2 -> 632.5, sweep_chunk 268.0 -> 277.1),
decide_micro_step 7.1 -> 7.0. The MODEL's growth again, not the
chip's: compiled for the v5e the sweep chunk's temporaries at 26,624
lanes FELL, 14.45 -> 14.04 GB (PERF.md, PR 50). Re-pinned 2026-10-05
(PR 51): the engine picks what it read of a lane's own state at one
index with the one-hot its writes use (`core._pick`), and a jaxpr
holds every select's [J,S] operand as a buffer of its own where an
indexed read held a scalar; the compiler fuses each into its reduce.
Measured MB before -> after: decide_micro_step 7.0 -> 9.2 (cap 8 ->
12), micro_step 22.4 -> 26.6, drain_to_decision 16.0 -> 18.8,
serve_decide 113.6 -> 118.5, flat_collect_batch 632.4 -> 651.7,
sweep_chunk 277.1 -> 296.4, serve_decide_batch 627.6 -> 646.9, all
inside their caps. The MODEL's growth once more: compiled for the v5e
the sweep chunk's temporaries at 26,624 lanes fell from 14.04 to 2.95
GB, the batched collector's from 323 to 160 MB and the flagship
collector's from 337 to 184 MB (the gathers' operands were relaid
whole; PERF.md, PR 51).
"""

from __future__ import annotations

import dataclasses
from typing import Any

from . import Violation
from .jaxpr_audit import (
    AUDIT_COLLECT_BATCH,
    BATCH_LANE_PROGRAMS,
    LANE_PROGRAMS,
    audit_setup,
    build_programs,
    flat_collect_batch_callable,
    lane_callables,
    program_callables,
)

# batch-width-parameterized builders for BATCH_LANE_PROGRAMS — the
# lane-fit advisor re-traces these at a second width to fit its
# per-lane byte model (keep in one-to-one sync with the tuple)
BATCH_PROGRAM_BUILDERS = {
    "flat_collect_batch": flat_collect_batch_callable,
}
assert set(BATCH_PROGRAM_BUILDERS) == set(BATCH_LANE_PROGRAMS)
from ..obs.memory import (
    TPU_HBM_BUDGET_BYTES,
    _iter_eqns,
    _trace_vmapped,
    aot_memory,
    aval_bytes,
    gb,
    jaxpr_memory_estimate,
    lane_fit,
)


@dataclasses.dataclass(frozen=True)
class MemBudget:
    """Per-program byte budget: `temp_hi` bounds the tile-padded sum of
    intermediate buffer bytes of the unbatched program at audit shapes
    (`obs.memory.jaxpr_memory_estimate`'s `temp_total_bytes`)."""

    temp_hi: int


MB = 10**6

# ---------------------------------------------------------------------------
# THE bytes budget table (single source of truth; see the module
# docstring for the re-pin procedure). Caps are ~1.35x the measured
# value, matching the eqn-budget band policy.
# ---------------------------------------------------------------------------

MEM_BUDGETS: dict[str, MemBudget] = {
    "observe": MemBudget(temp_hi=4 * MB),
    "micro_step": MemBudget(temp_hi=30 * MB),
    "decide_micro_step": MemBudget(temp_hi=12 * MB),
    "drain_to_decision": MemBudget(temp_hi=22 * MB),
    "decima_score": MemBudget(temp_hi=490 * MB),
    "decima_batch_policy": MemBudget(temp_hi=510 * MB),
    "ppo_update": MemBudget(temp_hi=627 * MB),
    # ISSUE 6: the single-eval batch collector the dp mesh shards,
    # audited at its native 4-lane batch (audit shapes are per-REPLICA:
    # under a dp mesh each device holds a 1/dp shard of every
    # lane-batched buffer, which is what the lane-fit advisor's `mesh`
    # mode models — these bytes bound the unsharded audit program)
    "flat_collect_batch": MemBudget(temp_hi=775 * MB),
    # ISSUE 9 `health:`-on variants (pinned 2026-08-03): the sentinels
    # are scalar reductions, so bytes barely move — ppo_update_health
    # 269.8 MB (vs 269.6 off), flat_collect_batch_health 330.6 MB (vs
    # 329.8). The byte budget pins that the sentinels stay reductions:
    # a health check that starts materializing per-lane tables would
    # breach this long before it OOMs a chip.
    "ppo_update_health": MemBudget(temp_hi=627 * MB),
    "flat_collect_batch_health": MemBudget(temp_hi=776 * MB),
    # ISSUE 10 serving programs (pinned 2026-08-04): serve_decide
    # 59.0 MB, serve_decide_batch 325.5 MB at the audit store/batch
    # shapes. The byte budget is the serving-latency analog of the
    # round-5 OOM lesson: a serve-path change that starts
    # materializing store-sized temporaries (the donation exists so
    # steady-state decisions allocate nothing store-shaped) breaches
    # this band long before it shows up as a p99 regression on-chip.
    "serve_decide": MemBudget(temp_hi=150 * MB),
    "serve_decide_batch": MemBudget(temp_hi=770 * MB),
    # ISSUE 13 sharded-store variant (pinned 2026-08-04): 329.3 MB vs
    # 325.5 unsharded — the sharding constraints add layout ops, not
    # buffers. The band pins that sharding the [C] axis never starts
    # materializing a gathered (unsharded) store copy: that would
    # roughly double the temp bytes and breach here on CPU before a
    # multi-chip window ever compiles it.
    "serve_decide_batch_sharded": MemBudget(temp_hi=775 * MB),
    # ISSUE 14 record-on serve variants (pinned 2026-08-04): 59.3 MB
    # / 326.7 MB vs 59.0 / 325.5 record-off — the StoredObs record is
    # a handful of [J,S] masks/counters per decision, ~0.4% bytes.
    # The band pins that recording stays a byproduct of the decision
    # already computed: a record path that re-materializes
    # observation-sized temporaries (a second observe pass, an
    # unmasked [J,S,S] adjacency copy) breaches here first. The
    # record-off programs re-measured byte-identical in the same PR
    # (the hot-swap params-as-argument refactor moved no bytes).
    "serve_decide_record": MemBudget(temp_hi=151 * MB),
    "serve_decide_batch_record": MemBudget(temp_hi=772 * MB),
    # ISSUE 15 group-shaped store program (pinned 2026-08-04):
    # 324.6 MB vs 325.5 at the full audit store — the temp bytes are
    # batch-axis-dominated (the width-K policy eval), so halving the
    # STORE axis moves almost nothing. The band pins that a grouped
    # lowering never starts materializing cross-group state (a
    # concatenated all-groups view would double here immediately).
    "serve_decide_batch_group": MemBudget(temp_hi=769 * MB),
    # ISSUE 18 ring-record serve variants (pinned 2026-08-07): 59.9 MB
    # / 327.3 MB vs 59.3 / 326.7 for the per-decision record programs —
    # the trajectory ring rides in the donated ARGS (one [R,...] RingRec
    # pytree, ~0.5 MB at the audit R), and the append is a single
    # masked scatter per leaf into that donated buffer, so temp bytes
    # barely move. The band pins that the ring append never starts
    # materializing a ring-sized temporary: a lowering that copies the
    # [R,...] ring to stage the append (instead of scattering in place)
    # would add the full ring bytes here and breach on CPU before a
    # record-on serve deploy ever pages it.
    "serve_decide_record_ring": MemBudget(temp_hi=151 * MB),
    "serve_decide_batch_record_ring": MemBudget(temp_hi=773 * MB),
    # PR 46: the sweep loop's chunk (4 lanes x 3 rows, fair policy,
    # health on), pinned 2026-10-02 at 268.3 MB: under half the batch
    # collector's (628.4 with health), whose net's width-K evaluation
    # and stored observations it lacks
    "sweep_chunk": MemBudget(temp_hi=362 * MB),
}

# lane counts the advisor sweeps (the bench's production range; 1024
# is the headline lane count, 512 the sub-batch the round-5 OOM hit)
LANE_FIT_CANDIDATES = (64, 128, 256, 512, 1024)
# lane counts the vmapped traces are built at: B=4 feeds the
# bank-broadcast scan, (2, 4) the advisor's linear model
AUDIT_LANES = (2, 4)


def bank_shapes(bank) -> dict[str, tuple[int, ...]]:
    """The workload-bank array shapes whose lane-batched materialization
    is the hazard (the same trio tests/test_vmap_memory.py greps for)."""
    return {
        "dur": tuple(bank.dur.shape),
        "cnt": tuple(bank.cnt.shape),
        "adj": tuple(bank.adj.shape),
    }


def check_bank_broadcast(name: str, closed, bank, lanes: int
                         ) -> list[Violation]:
    """Scan one VMAPPED program's jaxpr for equations producing a
    lane-batched bank-shaped array. Names the producing op and the
    would-be HBM cost at the headline lane count, so the report reads
    like the round-5 postmortem instead of a six-dim shape."""
    hazard = {
        (lanes,) + shape: table
        for table, shape in bank_shapes(bank).items()
    }
    found: list[Violation] = []
    seen: set[tuple] = set()
    for eqn in _iter_eqns(closed.jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            shape = tuple(getattr(aval, "shape", ()))
            if shape not in hazard:
                continue
            import jax

            key = (eqn.primitive.name, shape, str(aval.dtype))
            if key in seen:
                continue
            seen.add(key)
            at_1024 = aval_bytes(
                jax.ShapeDtypeStruct((1024,) + shape[1:], aval.dtype)
            )
            found.append(Violation(
                "memory", "bank-broadcast", name,
                f"lane-batched producer of the bank's {hazard[shape]} "
                f"table: {eqn.primitive.name} -> {aval.dtype}"
                f"{list(shape)} under a {lanes}-lane vmap "
                f"(~{gb(at_1024)} GB tile-padded at 1024 lanes) — a "
                "bank access moved inside a lane-dependent cond/switch "
                "branch; hoist it to the shared micro-step tail "
                "(commit 81e77fb pattern)",
            ))
    return found


def _lane_traces(names: tuple[str, ...] | None = None
                 ) -> dict[str, dict[int, Any]]:
    """Vmapped ClosedJaxprs of the lane programs at AUDIT_LANES —
    built once and shared between the bank-broadcast rule and the
    lane-fit advisor (each heavy trace costs seconds)."""
    out: dict[str, dict[int, Any]] = {}
    for name, (fn, args) in lane_callables().items():
        if names is not None and name not in names:
            continue
        out[name] = {
            b: _trace_vmapped(fn, args, b) for b in AUDIT_LANES
        }
    return out


def audit_memory(
    names: tuple[str, ...] | None = None,
    budget_bytes: int = TPU_HBM_BUDGET_BYTES,
) -> tuple[list[Violation], dict[str, Any]]:
    """Run the memory pass over the registry (or the `names` subset).
    Returns (violations, measured dict for the report): per-program
    byte accounting + budget verdicts, bank-broadcast scan of the
    vmapped lane programs, and the lane-fit table."""
    if names is not None:
        unknown = set(names) - set(MEM_BUDGETS)
        if unknown:
            raise ValueError(
                f"unknown program name(s) {sorted(unknown)} — the "
                "registry is the MEM_BUDGETS table's key set"
            )
    _, bank, _ = audit_setup()
    found: list[Violation] = []
    measured: dict[str, Any] = {}
    programs = build_programs(names)

    # -- unbatched accounting + the bytes budget ------------------------
    for name, closed in programs.items():
        est = jaxpr_memory_estimate(closed, tile_pad=True, top_k=3)
        budget = MEM_BUDGETS.get(name)
        measured[name] = {
            "temp_total_bytes": est["temp_total_bytes"],
            "temp_total_mb": round(est["temp_total_bytes"] / MB, 1),
            "args_bytes": est["args_bytes"],
            "out_bytes": est["out_bytes"],
            "const_bytes": est["const_bytes"],
            "peak_lower_bound_bytes": est["peak_lower_bound_bytes"],
            "largest": est["largest"],
        }
        if budget is None:
            found.append(Violation(
                "memory", "mem-budget", name,
                "program has no entry in the MEM_BUDGETS table",
            ))
        elif est["temp_total_bytes"] > budget.temp_hi:
            top = est["largest"][0] if est["largest"] else {}
            found.append(Violation(
                "memory", "mem-budget", name,
                f"temp-total {round(est['temp_total_bytes'] / MB, 1)}"
                f" MB > cap {round(budget.temp_hi / MB, 1)} MB "
                f"(largest buffer: {top.get('op')} "
                f"{top.get('shape')} = "
                f"{round(top.get('bytes', 0) / MB, 2)} MB) — "
                "structural allocation growth (or a stale cap); "
                "re-measure and re-pin in the same PR with a bench "
                "row justifying it",
            ))

    # -- vmapped lane programs: bank-broadcast + lane-fit ---------------
    lane_names = tuple(
        n for n in LANE_PROGRAMS if names is None or n in names
    )
    if lane_names:
        traces = _lane_traces(lane_names)
        callables = lane_callables()
        b_scan = max(AUDIT_LANES)
        lane_report: dict[str, Any] = {}
        for name in lane_names:
            found.extend(check_bank_broadcast(
                name, traces[name][b_scan], bank, b_scan
            ))
            fn, args = callables[name]
            fit = lane_fit(
                fn, args, candidates=LANE_FIT_CANDIDATES,
                budget_bytes=budget_bytes, base_lanes=AUDIT_LANES,
                traced=traces[name],
            )
            lane_report[name] = fit
            measured[name]["lane_fit"] = {
                "budget_gb": gb(budget_bytes),
                "max_lanes_fit": fit["max_lanes_fit"],
                "at_1024_gb": next(
                    (gb(r["est_peak_bytes"])
                     for r in fit["candidates"] if r["lanes"] == 1024),
                    None,
                ),
            }

    # -- batch programs (native lane axis): the sharded collectors ------
    # The single-eval collectors take the lane stack directly, so the
    # registry trace ALREADY carries the batch axis: the bank-broadcast
    # rule scans it as-traced (a lane-batched bank table here is the
    # same 19.4 GB class — and under a dp mesh it would materialize
    # per SHARD, i.e. the rule must see one replicated bank per
    # device, not a per-lane broadcast), and the lane-fit advisor fits
    # its model by re-tracing at a second batch width instead of
    # vmapping.
    for name in BATCH_LANE_PROGRAMS:
        if names is not None and name not in names:
            continue
        found.extend(check_bank_broadcast(
            name, programs[name], bank, AUDIT_COLLECT_BATCH
        ))

        def _tracer(b, _builder=BATCH_PROGRAM_BUILDERS[name]):
            import jax

            fn, args = _builder(batch=b)
            return jax.make_jaxpr(fn)(*args)

        fit = lane_fit(
            candidates=LANE_FIT_CANDIDATES, budget_bytes=budget_bytes,
            base_lanes=(2, AUDIT_COLLECT_BATCH),
            traced={AUDIT_COLLECT_BATCH: programs[name]},
            tracer=_tracer,
        )
        measured[name]["lane_fit"] = {
            "budget_gb": gb(budget_bytes),
            "max_lanes_fit": fit["max_lanes_fit"],
            "at_1024_gb": next(
                (gb(r["est_peak_bytes"])
                 for r in fit["candidates"] if r["lanes"] == 1024),
                None,
            ),
        }

    # -- serving batch programs (ISSUE 10/13): the bank-broadcast rule
    # on their native micro-batch axis. `serve/aot.py` vmaps
    # apply_and_drain over the K gathered sessions, so a bank access
    # slipping into a lane-dependent cond/switch branch would
    # materialize one bank copy per in-flight request — the same
    # 19.4 GB hazard class, caught here on CPU before a serving deploy
    # ever sees it. The dp-sharded variant is scanned too: under the
    # mesh a broadcast bank would materialize per SHARD, so the rule
    # must see one replicated bank, not a per-request (or per-device)
    # copy. (No lane-fit: the serve batch width is a latency knob
    # bounded by max_batch, not a throughput axis swept to HBM
    # capacity — the hot-set axis has its own advisor,
    # obs.memory.hot_set_fit.)
    for sname in ("serve_decide_batch", "serve_decide_batch_sharded",
                  "serve_decide_batch_group",
                  "serve_decide_batch_record_ring"):
        if names is not None and sname not in names:
            continue
        from ..serve.aot import SERVE_AUDIT_BATCH

        found.extend(check_bank_broadcast(
            sname, programs[sname], bank, SERVE_AUDIT_BATCH,
        ))
    return found, measured


_REGISTRY_FIT_CACHE: dict = {}


def registry_lane_fit(
    names: tuple[str, ...] = ("micro_step",),
    budget_bytes: int = TPU_HBM_BUDGET_BYTES,
) -> dict[str, Any]:
    """Memoized compact lane-fit of registry lane programs — the stamp
    bench rows use when their own collection program has no per-lane
    form (the single-eval batch collectors, the trainer's PPO jit): the
    registry micro-step/decide programs are the HBM-dominant inner loop
    every engine shares, so their fit is the honest proxy. Memoized per
    process because each program costs two heavy vmapped traces."""
    from ..obs.memory import lane_fit_summary

    key = (tuple(names), int(budget_bytes))
    if key not in _REGISTRY_FIT_CACHE:
        callables = lane_callables()
        _REGISTRY_FIT_CACHE[key] = {
            name: lane_fit_summary(lane_fit(
                *callables[name], candidates=LANE_FIT_CANDIDATES,
                budget_bytes=budget_bytes, base_lanes=AUDIT_LANES,
            ))
            for name in names
        }
    return _REGISTRY_FIT_CACHE[key]


def program_memory_accounting(
    names: tuple[str, ...] | None = None,
) -> dict[str, Any]:
    """Backend-true accounting: AOT lower + compile every registry
    program on the CURRENT backend and extract
    `compiled.memory_analysis()` (argument/output/temp/generated-code
    bytes). This is what chip-session stage 11 captures on the real
    TPU; on CPU the numbers are real but post-folding (the broadcast
    hazard is invisible here — that is the jaxpr rules' job). A
    program that fails to compile records the error string instead of
    killing the capture."""
    import jax

    out: dict[str, Any] = {"backend": jax.default_backend()}
    for name, (fn, args) in program_callables(names).items():
        mem = aot_memory(fn, *args)
        if mem is None:
            out[name] = {"error": "lower/compile/memory_analysis failed"}
        else:
            out[name] = mem
    return out
