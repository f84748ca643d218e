"""Jaxpr auditor: trace the registered hot programs and check each
rule-by-rule.

The decision row's cost on op-count-bound backends tracks jaxpr
equation counts (PERF_ROUNDS.md round-4 census), host callbacks serialize the
dispatch pipeline, f64/i64 leaves double memory traffic and poison
compile keys, and a data-dependent while-loop reappearing in a
pinned-loop-free program re-introduces the straggler tax the flat
engine exists to remove. Each of those is a silent, gradual failure —
this auditor makes them CI failures at the PR that introduces them.

Rules (ids used in the JSON report and the fixture tests):

- ``host-callback``: no callback primitives (`pure_callback`,
  `io_callback`, `debug_callback`, ...) anywhere in a hot program,
  outside the program's explicit `Budget.callback_allow` set (e.g. a
  telemetry io_callback, should one ever be threaded on-device).
- ``wide-dtype``: no f64/i64/u64/c128 avals anywhere — inputs,
  outputs, or any intermediate equation.
- ``loop-free``: programs pinned loop-free (`Budget.loop_free`)
  contain no `while`/`scan` primitives at any nesting depth.
- ``budget``: per-program equation/gather/scatter counts within the
  declarative `BUDGETS` table below.

Programs are traced with the AUDIT CONFIG shapes (10 executors,
20-job/20-stage caps — the same shapes tests/test_jaxpr_budget.py
pinned before the table moved here). Equation counts are
shape-independent, so small shapes trace fast and the budgets hold at
flagship scale; the Decima programs use the shipped agent architecture
(config/decima_tpch.yaml: embed 16, gnn [32,16], policy [64,64]) with
the compaction bucket scaled to the audit job cap so BOTH score
branches (compact + full-width fallback) are in the audited program.
Everything is traced via `jax.make_jaxpr`/`jax.eval_shape` over
ShapeDtypeStructs — nothing executes on a device except tiny parameter
init, so the audit is safe to run while a bench holds the accelerator
(the CLI pins JAX_PLATFORMS=cpu regardless).

Budgets were pinned under the default threefry PRNG (a key draw is
~60 eqns under threefry vs 1 under rbg, so the impl is part of the
measurement); the CLI never switches impls, and neither should a test
importing this module.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any, Callable

from . import Violation

WIDE_DTYPES = frozenset({"float64", "int64", "uint64", "complex128"})
LOOP_PRIMS = frozenset({"while", "scan"})
# primitives that read an operand by rows: what they cost follows the
# rows taken, not the operand
ROW_READ_PRIMS = frozenset({"gather", "dynamic_slice"})
# a select between two whole copies of a leaf hands it on: where
# nothing in the loop writes the leaf both sides are the loop's own
# input, and the compiler drops the select (the tail's freeze select of
# a one-lane program; tests/test_tpu_compile.py holds the compiled
# loop to no adjacency-sized result)
HAND_ON_PRIMS = frozenset({"select_n"})
# the adjacency [J,S,S] of one lane at the audit config (`audit_setup`)
AUDIT_ADJ_ELEMS = 20 * 20 * 20


@dataclasses.dataclass(frozen=True)
class Budget:
    """Per-program op budget. `eqn_*` bound total equations (including
    nested sub-jaxprs), `gather_hi`/`scatter_hi` bound the gather- and
    scatter-family primitive counts (they serialize on TPU, so growth
    there hurts more than its eqn share suggests). `loop_free` pins the
    program free of while/scan; `callback_allow` names callback
    primitives the program may legitimately contain (empty everywhere
    today — the telemetry counters are pure adds, not callbacks).
    `while_whole_read_elems` pins the program's `while` loops (bodies
    and predicates, nested ones too) free of any equation that reads
    an operand of at least that many elements whole: only a gather or
    a dynamic slice may touch one (PR 39: set to the adjacency's size
    on the one-lane programs that drain, whose loop body contracted
    the whole [J,S,S] adjacency once an iteration until then; on the
    TPU that one equation was a sixth of the body). None: not
    pinned."""

    eqn_lo: int
    eqn_hi: int
    gather_hi: int
    scatter_hi: int
    loop_free: bool = False
    callback_allow: frozenset = frozenset()
    while_whole_read_elems: int | None = None


# ---------------------------------------------------------------------------
# THE budget table (single source of truth; tests/test_jaxpr_budget.py is
# a thin wrapper over this).
#
# Re-pin procedure: run `python -m sparksched_tpu.analysis` — the report's
# `passes.jaxpr.measured` block prints every program's measured eqn /
# gather / scatter counts. A deliberate change that moves a count gets a
# new cap of ~1.35x the measured value (gather/scatter: measured + max(2,
# 35%)) IN THE SAME PR, with a bench row justifying the growth
# (PERF_ROUNDS.md "Static analysis"). Bands are deliberately loose: counts
# drift a few percent across jax versions; a band breach means
# structural growth, not noise.
#
# Pinned 2026-08 (jax 0.4.37, threefry, CPU trace) — measured eqns /
# gathers / scatters: observe 78/0/0 (identical before and after the
# implicit-dtype lint fixes, and to the tests/test_jaxpr_budget.py pin
# this table absorbed), decima_score 491/8/2, decima_batch_policy
# 733/13/2, ppo_update 2856/43/3 (re-measured 2860/43/3 after the
# ISSUE-6 fold_in minibatch-key derivation).
#
# Re-pinned 2026-08-03 for the ISSUE-7 fused bulk kernel
# (core._bulk_events_fused replaces the relaunch+ready pass pair;
# drain_to_decision additionally moved to the cheap existence-bit cond
# + unmasked body): the fusion SHRANK the audited programs —
# micro_step 4734/69/1 -> 4044/29/1, drain_to_decision 3374/45/1 ->
# 2539/5/1, flat_collect_batch 13407/216/18 -> 12513/190/18;
# decide_micro_step unchanged at 2729/28/1 (its bulk phase is the
# mode-exclusive fulfill pass, deliberately left unfused). Caps below
# tightened to ~1.35x the new measurements per the band policy; the
# fusion A/B bench rows live in PERF_ROUNDS.md round 11.
#
# Re-measured 2026-09-28 (jax 0.9.0, PR 28: the fused pass's fixed scan
# became an early-exit `while` whose body holds two unrolled steps, so
# every program that runs the pass counts one more step's equations
# and batched gathers; the loop's trip count, which is what the change
# cut, is not in a jaxpr): micro_step 3993/29/1 -> 4338/29/1,
# drain_to_decision 2500/5/1 -> 2845/5/1, flat_collect_batch
# 14437/190/18 -> 14866/206/18, flat_collect_batch_health 14675/190/20
# -> 15111/206/20, serve_decide 6265/33/65 -> 6610/33/65,
# serve_decide_batch 14756/251/65 -> 15185/267/65. Every count is
# inside its band, so no band moved; the chip rows are PERF.md, PR 28.
#
# Re-pinned 2026-09-28 (PR 31: the re-seed of a streaming lane left the
# micro-step tail of the row-structured programs). `decide_micro_step`
# has no `auto_reset` any more, a decide step cannot end an episode:
# 2711/28/1 -> 2470/24/0, now loop-free, caps 3700/40/3 -> 3350/33/2.
# `drain_to_decision(auto_reset=True)` re-seeds once, after its loop:
# 2845/5/1 -> 2960/5/1 as registered here (one lane and no lane axis,
# so the re-seed is unconditional; the reset program's equations moved
# from the loop's body to after it, and the body took the sync tail's
# freeze select), inside its band. What the change is for, a drain
# `while` that carries nothing the reset writes, is not a count of
# equations: tests/test_obs.py holds the streaming loop to the sync
# one. The decide step's dead key split went with its `rng`: the
# serve programs 7 and the collectors 10 equations fewer
# (serve_decide 6610 -> 6603, flat_collect_batch 14866 -> 14856), all
# inside their bands. The chip rows are PERF.md, PR 31.
#
# Re-measured 2026-09-29 (PR 33: `DecimaNet`'s level scan runs one step
# fewer, which is a trip count and no equation, and sums the children's
# messages by a select and a reduce where it had one `dot_general`;
# LeakyReLU is a `custom_jvp` call around one `max` where it was a
# compare and a select under a `jit`: one `net.apply` 208 -> 210
# equations, +2 for each net a program traces). Eqns before -> after,
# gathers and scatters unmoved, every count inside its band, so no band
# moved: decima_score 491 -> 495,
# decima_batch_policy 728 -> 732, ppo_update 2834 -> 2859,
# ppo_update_health 3183 -> 3208, flat_collect_batch 14856 -> 14860,
# flat_collect_batch_health 15109 -> 15113, serve_decide 6603 -> 6607,
# serve_decide_batch 15178 -> 15182 (the record, ring, group and
# sharded variants +4 each); observe, micro_step, decide_micro_step and
# drain_to_decision as they were. The chip rows are PERF.md, PR 33.
#
# Re-measured 2026-09-30 (PR 39: the fused pass refreshes
# `unsat_parent_count` from the state's packed parent sets, two
# compares, packs, `and`s and population counts where it had one
# `dot_general` over the adjacency; `EnvState` has one leaf more,
# `parent_sets`, packed at reset, so every select, conditional and
# store write over the state has one operand more). Eqns / gathers /
# scatters before -> after, every count inside its band, so no band
# moved: micro_step 4337/29/1 -> 4379/29/1, decide_micro_step
# 2470/24/0 -> 2474/24/0, drain_to_decision 2970/5/1 -> 3014/5/1,
# serve_decide 6621/33/65 -> 6666/33/66 (+0.7%; the one more scatter is
# the leaf's write back to the store, as on every serve program: 65 ->
# 66, the ring programs 86 -> 87), serve_decide_record 6643 -> 6688,
# serve_decide_record_ring 6771 -> 6816, serve_decide_batch
# 15196/267/65 -> 15378/268/66 (+1.2%; its group, record, ring and
# sharded variants the same +182 and one gather), flat_collect_batch
# 14874 -> 15050, flat_collect_batch_health 15143 -> 15319; observe,
# the net's and the update's as they were. New with it: the
# `while_whole_read_elems` pin on micro_step, drain_to_decision and
# serve_decide. The chip rows are PERF.md, PR 39.
#
# Re-pinned 2026-10-02 (PR 45: the three bulk passes read the frontier
# bits of their executors' destinations from the frontier packed over
# its stage axis, `core._frontier_at`, a pack, a one-hot select-reduce
# over [N,J] and a bit test where each had one gather of N elements
# out of [J,S]; the fused pass draws `[max_events + N, 2]` uniforms,
# a pair a step, and its step no longer selects its pair out of a row
# of N). Eqns / gathers before -> after: micro_step 4380/29 ->
# 4438/27, decide_micro_step 2474/24 -> 2510/23, drain_to_decision
# 3015/5 -> 3037/4, serve_decide 6666/33 -> 6724/31 (its record and
# ring variants the same +58 and -2), serve_decide_batch 15378/268 ->
# 15442/266 (its variants alike), flat_collect_batch 15050/206 ->
# 15114/204, flat_collect_batch_health 15319/206 -> 15383/204;
# scatters, observe, the net's and the update's as they were. Every
# count inside its band; the gather caps of the programs whose rule
# (measured x 1.35, at least measured + 2) now gives less than their
# cap are lowered to it: micro_step 40 -> 37, decide_micro_step 33 ->
# 32, drain_to_decision 8 -> 6, serve_decide and its record and ring
# variants 45 -> 42 (the batch programs' caps, pinned at a lower count
# than today's, are already under the rule's). What the change is for
# is no count: `row_gathers` over the pass's own jaxpr
# (tests/test_static_analysis.py). The chip rows are PERF.md, PR 45.
#
# Re-pinned 2026-10-03 (PR 47: the sampler computes its executor-level
# interval from `params.num_executors`, one chain of selects over the
# table's runs and three shifts and masks, `sampling.executor_interval`,
# where it gathered a row out of each of four `i32[N+1]` bank leaves;
# the bank has those four leaves fewer). Eqns / gathers before ->
# after, four gathers fewer for every sampler call a program traces:
# micro_step 4438/27 -> 4413/23, decide_micro_step 2510/23 -> 2497/19,
# drain_to_decision 3037/4 -> 3019/4 (one lane: its reads were dynamic
# slices), serve_decide 6724/31 -> 6693/27 (its record and ring
# variants the same -31 and -4), serve_decide_batch 15442/266 ->
# 15410/246 (its group, record, ring and sharded variants alike),
# flat_collect_batch 15114/204 -> 15082/184, flat_collect_batch_health
# 15383/204 -> 15351/184, sweep_chunk 14816/199 -> 14784/179; scatters,
# observe, the net's and the update's as they were. Every count inside
# its band; the gather caps follow the rule (measured x 1.35, at least
# measured + 2) down: micro_step 37 -> 32, decide_micro_step 32 -> 26,
# serve_decide and its record and ring variants 42 -> 37, the batch
# serve programs 339 -> 333 (the ring one 341 -> 334), the two
# collectors 257 -> 249, sweep_chunk 269 -> 242. What the change is for
# is no count: `reads_of_shape` over the pass's and the sampler's
# jaxprs (tests/test_static_analysis.py). The chip rows are PERF.md,
# PR 47.
#
# Re-pinned 2026-10-04 (PR 50: the sampler takes a stage's word of
# `EnvState.duration_facts`, a new u32[J,S] leaf packed at reset from
# the bank, `sampling.pack_duration_facts`, where it gathered from
# `bank.level_present` and `bank.max_present` and read three elements
# of `bank.cnt` and picked one; the fused pass's step picks the word,
# `rem[tj, ts]`, `jcnt[tj]` and `job_template[tj]` with the one-hots
# it builds for its updates). Eqns / gathers before -> after, for
# every sampler call a program traces four bank reads fewer and one
# read of the word more (in the fused pass's two steps four indexed
# reads of the lane's own state fewer besides), and in every program
# that holds a reset the pack and one gather of its rows: micro_step
# 4413/23 -> 4430/21, decide_micro_step 2497/19 -> 2502/16,
# drain_to_decision 3019/4 -> 3041/5 (one lane: the sampler's and the
# step's reads are dynamic slices, the reset's row gather is the one
# more), serve_decide 6693/27/66 -> 6710/24/67 (its record and ring
# variants the same +17, -3 and one scatter more: the leaf's write
# back to the store), serve_decide_batch 15410/246/66 -> 15529/224/67
# (its group, record, ring and sharded variants alike),
# flat_collect_batch 15082/184 -> 15195/161, flat_collect_batch_health
# 15351/184 -> 15464/161, sweep_chunk 14786/179 -> 14916/157; observe,
# the net's and the update's as they were. Every count inside its
# band; the gather caps follow the rule (measured x 1.35, at least
# measured + 2): micro_step 32 -> 28, decide_micro_step 26 -> 22,
# drain_to_decision 6 -> 7, serve_decide and its record and ring
# variants 37 -> 33, the batch serve programs 333 -> 303 (the ring one
# 334 -> 304), the two collectors 249 -> 218, sweep_chunk 242 -> 212.
# What the change is for is no count: `loop_row_reads` over the pass's
# jaxpr (tests/test_static_analysis.py). The chip rows are PERF.md,
# PR 50.
#
# Re-pinned 2026-10-05 (PR 51: what the pop, the handlers,
# `_resolve_action`, `_apply_action`, `_refresh_sat`, the decide step's
# commit and the FULFILL branch read of a lane's own state at ONE
# (job, stage), job, executor or slot they pick with the one-hot their
# masked writes use, `core._pick`, a select and a reduce where each was
# an indexed read; the adjacency's rows come off `parent_sets`). Under
# a lane `vmap` each of those reads was a gather, in a one-lane program
# a dynamic slice, so the gather counts fall in the batched programs
# alone. Eqns / gathers before (re-measured on the parent's checkout)
# -> after: micro_step 4378/21 -> 4147/21, decide_micro_step 2476/16 ->
# 2359/16, drain_to_decision 3002/5 -> 2852/5, serve_decide 6645/24 ->
# 6378/24 (its record and ring variants the same -267),
# serve_decide_batch 15464/224 -> 15044/108 (its group, record and
# sharded variants alike; the ring one 225 -> 109), flat_collect_batch
# 15130/161 -> 14710/45, flat_collect_batch_health 15399/161 ->
# 14979/45, sweep_chunk 14851/157 -> 14431/41; scatters,
# observe, the net's and the update's as they were; every eqn count
# inside its band. What is left in the collectors: `_bulk_fulfill`'s
# reads at a vector of candidates (ROADMAP S10), the bank's three a
# sampled duration, the net's, the reset's rows of the bank and the
# stores. The gather caps of the BATCHED programs are pinned at the
# measured count + 2, under this table's usual rule (x 1.35), so that
# the next indexed read of the state in these paths fails here and is
# looked at: the serve batch programs 303 -> 110 (the ring one 304 ->
# 111), the two collectors 218 -> 47, sweep_chunk 212 -> 43; the
# one-lane programs' caps stay where the rule puts them (28, 22, 7,
# 33). What the change is for is a count of the COMPILED drain body's
# gathers (tests/test_tpu_compile.py). The chip rows are PERF.md,
# PR 51.
# ---------------------------------------------------------------------------

BUDGETS: dict[str, Budget] = {
    # round 8 replaced observe's S-deep [J,S,S] fori_loop with the
    # state-maintained node_level cache: the program must stay loop-free
    # and within a small eqn band (migrated from test_jaxpr_budget.py)
    "observe": Budget(
        eqn_lo=20, eqn_hi=110, gather_hi=2, scatter_hi=2, loop_free=True,
    ),
    # one flat micro-step at the shipped bulk config (be=8,
    # fulfill_bulk, cycles=1, fused bulk kernel) — the engine's unit
    # of work (the while is the fused event run's early-exit loop, not
    # a decision loop)
    "micro_step": Budget(
        eqn_lo=2000, eqn_hi=5500, gather_hi=28, scatter_hi=3,
        while_whole_read_elems=AUDIT_ADJ_ELEMS,
    ),
    # the single-eval collectors' policy-bearing micro-step
    "decide_micro_step": Budget(
        eqn_lo=1000, eqn_hi=3350, gather_hi=22, scatter_hi=2,
        loop_free=True,
    ),
    # the single-eval collectors' non-policy drain (while-loop by
    # design: it runs until the lane is ready to DECIDE again; the
    # ISSUE-7 restructure keeps its cond to the event existence bit
    # and drops the per-iteration full-pytree rollback select)
    "drain_to_decision": Budget(
        eqn_lo=1200, eqn_hi=3450, gather_hi=7, scatter_hi=3,
        while_whole_read_elems=AUDIT_ADJ_ELEMS,
    ),
    # Decima stage/exec scores over a [B]-stacked feature set, both
    # compaction branches under the scalar cond (the scan is the
    # level-wise GNN message pass)
    "decima_score": Budget(
        eqn_lo=150, eqn_hi=670, gather_hi=12, scatter_hi=4,
    ),
    # score + per-lane masked sampling over a lane stack
    "decima_batch_policy": Budget(
        eqn_lo=250, eqn_hi=990, gather_hi=18, scatter_hi=4,
    ),
    # one PPO update (epochs x minibatches scan, remat'd GNN recompute)
    "ppo_update": Budget(
        eqn_lo=1000, eqn_hi=3900, gather_hi=60, scatter_hi=5,
    ),
    # the single-eval batch collector over a native [B] lane axis —
    # the program the dp mesh shards (ISSUE 6): decide + drain + ONE
    # Decima batch_policy per decision row inside a short scan, with
    # the per-decision buffer scatters. The jaxpr is dp-invariant
    # (sharding is applied at lowering, not tracing), which is exactly
    # what makes this CPU audit valid for the sharded configuration;
    # the HLO-level collective census lives in tests/test_parallel.py.
    "flat_collect_batch": Budget(
        eqn_lo=9000, eqn_hi=16900, gather_hi=47, scatter_hi=25,
    ),
    # ISSUE 9: the `health:`-on variants of the two production
    # programs. Pinned 2026-08-03 — ppo_update_health 3209/43/3 (the
    # grad sentinels + per-minibatch skip gate cost ~12% eqns, zero
    # extra gathers/scatters), flat_collect_batch_health 12734/190/20
    # (per-decision-row state sentinels ride the telemetry carry:
    # +1.8% eqns, +2 scatters from the conservation goldens). The
    # default-off programs above are byte-for-byte the PR-7 pins —
    # which is the acceptance bar: health off must change nothing.
    "ppo_update_health": Budget(
        eqn_lo=1000, eqn_hi=4350, gather_hi=60, scatter_hi=5,
    ),
    "flat_collect_batch_health": Budget(
        eqn_lo=9000, eqn_hi=17200, gather_hi=47, scatter_hi=27,
    ),
    # PR 46: the sweep loop's chunk under the fair heuristic, health
    # on (sweep.py): the collectors' decide and drain with the re-seed
    # and an episode's result in the drain, no net and no stored
    # observation in the row. Pinned 2026-10-02 at 14816/199/2 (4
    # lanes x 3 rows): as many equations as the batch collector's,
    # whose net and stores it lacks, because the reset program runs
    # inside the scan (the bank's gathers are its)
    "sweep_chunk": Budget(
        eqn_lo=9000, eqn_hi=16600, gather_hi=43, scatter_hi=4,
    ),
    # ISSUE 10: the AOT decision-serving programs (serve/aot.py),
    # pinned 2026-08-04 — serve_decide 6514/33/65, serve_decide_batch
    # 12853/251/65 (store capacity 8 / batch 4 at audit scale). The
    # high scatter count is structural: the store scatter-back writes
    # each of the ~50 LoopState leaves at the served slot(s) — one
    # dynamic-update per leaf, in-place under donation. The while is
    # `drain_to_decision` (the inter-decision drain, by design); the
    # scan is the GNN level pass + the bulk event kernel.
    "serve_decide": Budget(
        eqn_lo=3000, eqn_hi=8800, gather_hi=33, scatter_hi=88,
        while_whole_read_elems=AUDIT_ADJ_ELEMS,
    ),
    "serve_decide_batch": Budget(
        eqn_lo=6000, eqn_hi=17400, gather_hi=110, scatter_hi=88,
    ),
    # ISSUE 13: the dp-sharded store variant (serve/aot.py
    # `serve_decide_batch_fn(..., shard=...)`), pinned 2026-08-04 —
    # 12975/251/65: exactly the unsharded batch program plus one
    # sharding_constraint eqn per store leaf at entry and exit. The
    # constraint count is MESH-SIZE-INVARIANT (the mesh is a lowering
    # parameter, not an equation — measured identical at 1 and 8
    # devices), so the pin holds on the 1-device analysis CLI and the
    # 8-virtual-device test mesh alike; the unsharded programs above
    # re-measured byte-identical, which is the acceptance bar (shard
    # off must change nothing).
    "serve_decide_batch_sharded": Budget(
        eqn_lo=6000, eqn_hi=17500, gather_hi=110, scatter_hi=88,
    ),
    # ISSUE 14: the record-on serve variants (serve/aot.py
    # `record=True` — the online trajectory path's programs), pinned
    # 2026-08-04 — serve_decide_record 6520/33/65,
    # serve_decide_batch_record 12860/251/65: +6/+7 eqns over the
    # record-off programs (the StoredObs assembly is masked selects
    # over already-computed observation pieces; zero extra
    # gathers/scatters). Two things were re-measured in the same PR:
    # (a) the record-off programs above are BYTE-IDENTICAL to the
    # PR-10/13 pins, and (b) moving the model params from closure
    # constants to runtime arguments (the hot-swap refactor) changed
    # NO count on any serve program — params enter as invars, the
    # traced computation is the same.
    "serve_decide_record": Budget(
        eqn_lo=3000, eqn_hi=8810, gather_hi=33, scatter_hi=88,
    ),
    "serve_decide_batch_record": Budget(
        eqn_lo=6000, eqn_hi=17410, gather_hi=110, scatter_hi=88,
    ),
    # ISSUE 15: the GROUP-shaped serve program (the pipelined store's
    # [hot_capacity/groups] lowering — serve/aot.py
    # `serve_decide_batch_group`), pinned 2026-08-04 at 12853/251/65:
    # byte-identical counts to `serve_decide_batch`, which is the
    # acceptance bar — slot groups are host-side call routing, and a
    # "grouped" program that started diverging structurally from the
    # ungrouped one (extra copies, a gather over groups) would breach
    # here first. All pre-ISSUE-15 serve programs re-measured
    # byte-identical in the same PR (the take_slot/write_slot
    # refactor moved code, not equations).
    "serve_decide_batch_group": Budget(
        eqn_lo=6000, eqn_hi=17400, gather_hi=110, scatter_hi=88,
    ),
    # ISSUE 18: the ring-recording serve programs (serve/aot.py
    # `serve_decide_ring_fn` / `serve_decide_batch_ring_fn` — the
    # device-resident trajectory path), pinned 2026-08-07 —
    # serve_decide_record_ring 6648/33/86,
    # serve_decide_batch_record_ring 12996/252/86. The +21 scatters
    # over the record programs are structural: ring_append writes
    # each of the 21 RingRec leaves (12 decision scalars + the
    # StoredObs pieces) at the masked cursor position — one
    # dynamic-update per leaf, in-place under ring donation, with the
    # drop-mode lane for masked-off appends. +~130 eqns are the
    # cursor/offset arithmetic and the record assembly. Every
    # record-OFF and record-on-ring-OFF serve program above
    # re-measured BYTE-IDENTICAL in the same PR — the zero-cost-off
    # acceptance bar.
    "serve_decide_record_ring": Budget(
        eqn_lo=3000, eqn_hi=8980, gather_hi=33, scatter_hi=117,
    ),
    "serve_decide_batch_record_ring": Budget(
        eqn_lo=6000, eqn_hi=17550, gather_hi=111, scatter_hi=117,
    ),
}


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn) -> list:
    """The jaxprs an equation carries in its parameters (the branches
    of a conditional, a loop's body and predicate, a call's body)."""
    subs = []
    for v in eqn.params.values():
        for sub in v if isinstance(v, (list, tuple)) else [v]:
            if hasattr(sub, "jaxpr"):
                subs.append(sub.jaxpr)
            elif hasattr(sub, "eqns"):
                subs.append(sub)
    return subs


def iter_eqns(jaxpr):
    """Yield every equation including nested sub-jaxprs (cond/scan/while
    branches, closed calls, custom_* wrappers)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def whole_reads_in_while(jaxpr, elems: int, _in_loop: bool = False
                         ) -> list[str]:
    """The equations inside a `while` (its body or its predicate, at
    any depth) that read an operand of at least `elems` elements
    otherwise than by rows (`ROW_READ_PRIMS`) or to hand it on
    (`HAND_ON_PRIMS`), as `primitive(shape)`. An equation that only
    hands operands to sub-jaxprs (a conditional, a call, an inner
    loop) is looked into, not counted."""
    found = []
    for eqn in jaxpr.eqns:
        subs = _sub_jaxprs(eqn)
        if subs:
            inside = _in_loop or eqn.primitive.name == "while"
            for sub in subs:
                found += whole_reads_in_while(sub, elems, inside)
        elif _in_loop and eqn.primitive.name not in (
                ROW_READ_PRIMS | HAND_ON_PRIMS):
            found += [
                f"{eqn.primitive.name}{tuple(v.aval.shape)}"
                for v in eqn.invars
                if getattr(getattr(v, "aval", None), "size", 0) >= elems
            ]
    return found


def row_gathers(jaxpr, elems: int, rows: int) -> list[str]:
    """The `gather` equations, at any depth, that read an operand of
    at least `elems` elements at `rows` or more index rows (the
    indices' shape less its last axis), as `gather(operand <- rows)`:
    a grid of the state's size read once for every executor."""
    found = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "gather":
            continue
        operand, indices = (v.aval for v in eqn.invars[:2])
        n_rows = 1
        for d in indices.shape[:-1]:
            n_rows *= d
        if operand.size >= elems and n_rows >= rows:
            found.append(
                f"gather{tuple(operand.shape)} <- {tuple(indices.shape)}"
            )
    return found


def reads_of_shape(jaxpr, shape: tuple[int, ...]) -> list[str]:
    """The equations, at any depth, with an operand of exactly `shape`,
    as `primitive(shape)`: a gather from a table of that shape, a loop
    that carries it, a call that is handed it. With `(N + 1,)` it says
    whether a program reads a table indexed by an executor count
    (PR 47: the sampler's executor-level intervals are computed from
    `EnvParams.num_executors`, and the bank holds no such leaf)."""
    shape = tuple(shape)
    return [
        f"{eqn.primitive.name}{shape}"
        for eqn in iter_eqns(jaxpr)
        for v in eqn.invars
        if getattr(getattr(v, "aval", None), "shape", None) == shape
    ]


def loop_row_reads(jaxpr, operands, _in_loop: bool = False
                   ) -> list[str]:
    """The row reads (`ROW_READ_PRIMS`: a gather under `vmap`, a
    dynamic slice in one lane) inside a `while` (its body or its
    predicate, at any depth) from an operand that is one of
    `operands` by shape and dtype (arrays or shape structs: a bank's
    leaves), as `primitive(dtype[shape])`. With a bank's leaves it
    says what the loop reads of the bank, and how often (PR 50: the
    fused bulk pass's early-exit loop reads `cnt`, `dur` and
    `rough_duration` once a step and nothing of `level_present` or
    `max_present`, which the stage's word of `EnvState.duration_facts`
    holds)."""
    want = {(tuple(o.shape), str(o.dtype)) for o in operands}
    found = []
    for eqn in jaxpr.eqns:
        inside = _in_loop or eqn.primitive.name == "while"
        for sub in _sub_jaxprs(eqn):
            found += loop_row_reads(sub, operands, inside)
        if _in_loop and eqn.primitive.name in ROW_READ_PRIMS:
            aval = eqn.invars[0].aval
            if (tuple(aval.shape), str(aval.dtype)) in want:
                found.append(
                    f"{eqn.primitive.name}"
                    f"({aval.dtype}{list(aval.shape)})"
                )
    return found


def count_eqns(jaxpr) -> int:
    return sum(1 for _ in iter_eqns(jaxpr))


def primitive_counts(jaxpr) -> Counter:
    return Counter(e.primitive.name for e in iter_eqns(jaxpr))


def _gather_count(prims: Counter) -> int:
    return sum(n for p, n in prims.items() if p == "gather")


def _scatter_count(prims: Counter) -> int:
    return sum(n for p, n in prims.items() if p.startswith("scatter"))


def _iter_avals(jaxpr):
    for v in list(jaxpr.invars) + list(jaxpr.outvars) + list(
            jaxpr.constvars):
        yield getattr(v, "aval", None)
    for eqn in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            yield getattr(v, "aval", None)


def wide_dtype_avals(jaxpr) -> list[str]:
    found = []
    for aval in _iter_avals(jaxpr):
        dt = getattr(aval, "dtype", None)
        if dt is not None and str(dt) in WIDE_DTYPES:
            found.append(f"{dt}{tuple(getattr(aval, 'shape', ()))}")
    return found


def is_host_callback_prim(prim: str) -> bool:
    """Primitives that call back into the host: the `*callback*`
    family (`pure_callback`, `io_callback`, `debug_callback`), the
    legacy host_callback pair, and `debug_print`, which is what
    `jax.debug.print` lowers to since jax 0.9."""
    return "callback" in prim or prim in (
        "outside_call", "host_callback", "debug_print",
    )


def audit_closed_jaxpr(name: str, closed, budget: Budget
                       ) -> tuple[list[Violation], dict[str, Any]]:
    """Apply every jaxpr rule to one traced program. Returns the
    violations plus the measured counts (the re-pin surface)."""
    jaxpr = closed.jaxpr
    prims = primitive_counts(jaxpr)
    n_eqns = sum(prims.values())
    n_gather = _gather_count(prims)
    n_scatter = _scatter_count(prims)
    measured = {
        "eqns": n_eqns,
        "gathers": n_gather,
        "scatters": n_scatter,
        "loops": sorted(set(prims) & LOOP_PRIMS),
    }
    found: list[Violation] = []

    callbacks = {p for p in prims if is_host_callback_prim(p)}
    bad_cb = callbacks - set(budget.callback_allow)
    if bad_cb:
        found.append(Violation(
            "jaxpr", "host-callback", name,
            f"callback primitives {sorted(bad_cb)} present "
            f"({sum(prims[p] for p in bad_cb)} call sites) — host "
            "callbacks serialize the dispatch pipeline; allowlist "
            "explicitly in BUDGETS if deliberate",
        ))

    wide = wide_dtype_avals(jaxpr)
    if wide:
        found.append(Violation(
            "jaxpr", "wide-dtype", name,
            f"{len(wide)} f64/i64-family avals in the jaxpr (e.g. "
            f"{wide[:3]}) — a single wide leaf doubles memory traffic "
            "and recompiles every consumer",
        ))

    loops = set(prims) & LOOP_PRIMS
    if budget.loop_free and loops:
        found.append(Violation(
            "jaxpr", "loop-free", name,
            f"loop primitives {sorted(loops)} in a pinned-loop-free "
            "program — the data-dependent loop this pin exists to keep "
            "out came back",
        ))

    if budget.while_whole_read_elems is not None:
        whole = whole_reads_in_while(jaxpr, budget.while_whole_read_elems)
        if whole:
            found.append(Violation(
                "jaxpr", "loop-whole-read", name,
                f"{len(whole)} equations inside a `while` read an "
                f"operand of {budget.while_whole_read_elems} elements "
                f"or more whole (e.g. {whole[:3]}) — once an iteration, "
                "whatever the iteration does; read rows (a gather), or "
                "make what the loop needs of it once, outside",
            ))

    if not (budget.eqn_lo <= n_eqns <= budget.eqn_hi):
        found.append(Violation(
            "jaxpr", "budget", name,
            f"eqn count {n_eqns} outside [{budget.eqn_lo}, "
            f"{budget.eqn_hi}] — structural op growth (or a stale "
            "budget); re-measure and re-pin in the same PR with a "
            "bench row justifying it",
        ))
    if n_gather > budget.gather_hi:
        found.append(Violation(
            "jaxpr", "budget", name,
            f"gather count {n_gather} > {budget.gather_hi}",
        ))
    if n_scatter > budget.scatter_hi:
        found.append(Violation(
            "jaxpr", "budget", name,
            f"scatter count {n_scatter} > {budget.scatter_hi}",
        ))
    return found, measured


# ---------------------------------------------------------------------------
# audit config + program registry
# ---------------------------------------------------------------------------

_SETUP_CACHE: list = []


def audit_setup():
    """(params, bank, reset-state ShapeDtypeStruct pytree) under the
    audit config — shared with the contracts pass so both agree on
    shapes. The bank is real data (host numpy -> device constants);
    the state is abstract."""
    if _SETUP_CACHE:
        return _SETUP_CACHE[0]
    import jax

    from ..config import EnvParams
    from ..env import core
    from ..workload import make_workload_bank

    params = EnvParams(
        num_executors=10, max_jobs=20, max_stages=20, max_levels=20
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )
    assert params.max_jobs * params.max_stages**2 == AUDIT_ADJ_ELEMS
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda k: core.reset(params, bank, k), key)
    _SETUP_CACHE.append((params, bank, state))
    return _SETUP_CACHE[0]


def _shipped_agent_kwargs() -> dict[str, Any]:
    """The shipped Decima architecture (config/decima_tpch.yaml agent
    section). Hard-coded rather than YAML-loaded so the audit is
    self-contained; drift is caught by the budget band moving."""
    return {
        "embed_dim": 16,
        "gnn_mlp_kwargs": {
            "hid_dims": [32, 16],
            "act_cls": "LeakyReLU",
            "act_kwargs": {"negative_slope": 0.2},
        },
        "policy_mlp_kwargs": {"hid_dims": [64, 64], "act_cls": "Tanh"},
    }


def _batched(tree, b: int):
    import jax

    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((b,) + tuple(l.shape), l.dtype),
        tree,
    )


# per-lane programs of the registry: the ones that run under a lane
# vmap in production (bench.py / the trainer's collectors), and therefore
# the ones the memory pass lane-batches for the bank-broadcast rule
# and the lane-fit advisor
LANE_PROGRAMS = (
    "observe", "micro_step", "decide_micro_step", "drain_to_decision",
)

# batch programs: registry programs that take the lane axis NATIVELY
# (no outer vmap) — the single-eval collectors the dp mesh shards. The
# memory pass applies the bank-broadcast rule to their traced batch
# axis directly and drives the lane-fit advisor by re-tracing at each
# base batch width (`flat_collect_batch_callable(batch)`).
BATCH_LANE_PROGRAMS = ("flat_collect_batch",)

# lane/scan widths of the audited batch collector: 4 lanes x 3
# decision rows keeps the ~13k-eqn trace a few seconds while still
# containing every production phase (batch policy, decide, drain,
# scatter) — eqn counts are shape-independent, so the budgets hold at
# flagship scale
AUDIT_COLLECT_BATCH = 4
AUDIT_COLLECT_STEPS = 3


def flat_collect_batch_callable(
    batch: int = AUDIT_COLLECT_BATCH,
    health: bool = False,
) -> tuple[Callable, tuple]:
    """The single-eval flat sync collector over a native [batch] lane
    axis with the shipped Decima batch policy — the program
    `parallel:` mesh configs shard over dp
    (trainers/rollout.py:collect_flat_sync_batch; the async variant
    shares the same scan body). As (callable, abstract args); `batch`
    parameterizes the lane width so the memory pass can fit its
    per-lane byte model from two widths. With `health`, the in-JIT
    sentinels ride a telemetry carry — the `health:`-on production
    configuration, audited as `flat_collect_batch_health` so the
    sentinel cost stays inside its own eqn/byte budget."""
    import jax

    from ..obs.telemetry import telemetry_zeros_like
    from ..schedulers.decima import DecimaScheduler
    from ..trainers.rollout import collect_flat_sync_batch

    params, bank, state = audit_setup()
    # compaction bucket scaled to the audit job cap, as for the
    # decima_* programs, so BOTH score branches are in the audit
    sched = DecimaScheduler(
        num_executors=params.num_executors, job_bucket=8,
        **_shipped_agent_kwargs(),
    )
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    states_b = _batched(state, batch)
    telem = (
        jax.eval_shape(lambda: telemetry_zeros_like((batch,)))
        if health else None
    )

    def fn(s, r):
        return collect_flat_sync_batch(
            params, bank,
            lambda rr, oo: sched.batch_policy(rr, oo),
            r, AUDIT_COLLECT_STEPS, s, telem,
            event_bulk=True, bulk_events=8, fulfill_bulk=True,
            bulk_cycles=1, health=health,
        )

    return fn, (states_b, key)


def sweep_chunk_callable() -> tuple[Callable, tuple]:
    """The sweep loop's chunk (`sweep.py: sweep_chunk`) under the fair
    heuristic over a native lane axis, health sentinels on, as the cell
    `sweep_fair` runs it: observe, one policy evaluation, decide, the
    drain with its re-seed and the episode's result, the row's record.
    As (callable, abstract args), at the batch collector's audit
    widths."""
    import jax
    import jax.numpy as jnp

    from .. import sweep
    from ..env.flat_loop import init_loop_state
    from ..schedulers.heuristics import RoundRobinScheduler

    params, bank, state = audit_setup()
    sched = RoundRobinScheduler(params.num_executors)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    lanes = AUDIT_COLLECT_BATCH
    carry = jax.eval_shape(
        lambda s, k: sweep.SweepCarry(
            ls=jax.vmap(init_loop_state)(s), key=k,
            lane=jnp.zeros((lanes,), jnp.int32),
            decisions=jnp.zeros((lanes,), jnp.int32)),
        _batched(state, lanes), _batched(key, lanes))

    def fn(c, r):
        return sweep._chunk(
            params, bank, sched.batch_policy, c, r, AUDIT_COLLECT_STEPS)

    return fn, (carry, key)


def lane_callables() -> dict[str, tuple[Callable, tuple]]:
    """The per-lane registry programs as (callable, UNBATCHED abstract
    args) — shared by the unbatched jaxpr trace below and the memory
    pass's vmapped traces, so the two passes cannot audit different
    programs under the same name."""
    import jax
    import jax.numpy as jnp

    from ..env.flat_loop import (
        decide_micro_step,
        drain_to_decision,
        init_loop_state,
        micro_step,
    )
    from ..env.observe import observe
    from ..schedulers.heuristics import round_robin_policy

    params, bank, state = audit_setup()
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    ls = jax.eval_shape(init_loop_state, state)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    return {
        "observe": (lambda s: observe(params, s), (state,)),
        # the shipped bulk config: be=8, fulfill_bulk on, one cycle
        # (compute_levels=False as in bench.py's driving loop)
        "micro_step": (
            lambda l, r: micro_step(
                params, bank, pol, l, r, True, False, True, 8, True, 1
            ),
            (ls, key),
        ),
        "decide_micro_step": (
            lambda l, si, ne: decide_micro_step(
                params, bank, l, si, ne, True
            ),
            (ls, i32, i32),
        ),
        "drain_to_decision": (
            lambda l, r: drain_to_decision(
                params, bank, l, r, True, True, 8, 1
            ),
            (ls, key),
        ),
    }


_PROGRAMS_CACHE: dict = {}


def program_callables(names: tuple[str, ...] | None = None
                      ) -> dict[str, tuple[Callable, tuple]]:
    """Every registered hot program as (callable, abstract args) —
    the single registry behind the unbatched jaxpr traces (this pass),
    the memory pass's vmapped traces, and the chip session's on-device
    `memory_analysis()` capture."""
    import jax

    from ..env.observe import observe
    from ..schedulers.decima import DecimaScheduler

    params, bank, state = audit_setup()
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    want = set(names) if names is not None else None

    out: dict[str, tuple[Callable, tuple]] = {}
    for name, entry in lane_callables().items():
        if want is None or name in want:
            out[name] = entry

    if want is None or want & {"decima_score", "decima_batch_policy"}:
        # compaction bucket scaled to the audit job cap (flagship K=32
        # over a 200-job cap -> K=8 over 20) so the cond's BOTH
        # branches are in the audited program
        sched = DecimaScheduler(
            num_executors=params.num_executors, job_bucket=8,
            **_shipped_agent_kwargs(),
        )
        obs_b = jax.eval_shape(
            lambda s: jax.vmap(lambda x: observe(params, x))(s),
            _batched(state, 4),
        )
        feats_b = jax.eval_shape(
            lambda o: jax.vmap(sched.features)(o), obs_b
        )
        if want is None or "decima_score" in want:
            out["decima_score"] = (
                lambda f: sched.score(sched.params, f), (feats_b,)
            )
        if want is None or "decima_batch_policy" in want:
            out["decima_batch_policy"] = (
                lambda r, o: sched.batch_policy(r, o), (key, obs_b)
            )

    if want is None or want & {
        "serve_decide", "serve_decide_batch",
        "serve_decide_batch_sharded", "serve_decide_record",
        "serve_decide_batch_record", "serve_decide_batch_group",
        "serve_decide_record_ring", "serve_decide_batch_record_ring",
    }:
        # ISSUE 10/13: the AOT decision service's programs (serving
        # store capacity 8, micro-batch width 4 at audit scale; the
        # production programs differ only in buffer widths), plus the
        # dp-sharded store variant. Traced here exactly as
        # `serve/aot.py` lowers them, so the audited jaxpr IS the
        # compiled serving program.
        from ..serve.aot import serve_callables

        for name, entry in serve_callables().items():
            if want is None or name in want:
                out[name] = entry

    if want is None or "ppo_update" in want:
        out["ppo_update"] = ppo_update_callable()
    if want is None or "flat_collect_batch" in want:
        out["flat_collect_batch"] = flat_collect_batch_callable()
    # the `health:`-on variants (ISSUE 9): the sentinel-instrumented
    # production programs, budgeted separately so (a) the opt-in cost
    # is visible and capped, and (b) the default programs above prove
    # the off path is structurally unchanged
    if want is None or "ppo_update_health" in want:
        out["ppo_update_health"] = ppo_update_callable(health=True)
    if want is None or "flat_collect_batch_health" in want:
        out["flat_collect_batch_health"] = flat_collect_batch_callable(
            health=True
        )
    if want is None or "sweep_chunk" in want:
        out["sweep_chunk"] = sweep_chunk_callable()
    return out


def build_programs(names: tuple[str, ...] | None = None
                   ) -> dict[str, Any]:
    """Trace the registered hot programs; returns name -> ClosedJaxpr.
    Order is cheap-first. `names` restricts the registry (the thin
    test wrappers trace only what they pin). The full-registry result
    is memoized per process: the jaxpr and memory passes both consume
    it, and re-tracing ~15k equations for the second pass would double
    the gate's cost for identical jaxprs."""
    import jax

    if names is None and _PROGRAMS_CACHE:
        return dict(_PROGRAMS_CACHE)
    programs = {
        name: jax.make_jaxpr(fn)(*args)
        for name, (fn, args) in program_callables(names).items()
    }
    if names is None:
        _PROGRAMS_CACHE.update(programs)
    return programs


def _trace_ppo_update():
    import jax

    fn, args = ppo_update_callable()
    return jax.make_jaxpr(fn)(*args)


def ppo_update_callable(health: bool = False) -> tuple[Callable, tuple]:
    """One PPO update at a tiny audit scale (2 lanes, 16 decision
    steps), as (callable, abstract args). The rollout is abstract
    (`eval_shape` over `_collect`), so nothing episode-sized executes;
    tracing/lowering the callable then hits the real epochs x
    minibatches scan with the remat'd GNN recompute. With `health`,
    the update carries the in-JIT grad sentinels + minibatch skip gate
    (audited as `ppo_update_health`)."""
    import jax
    import jax.numpy as jnp

    from ..trainers.ppo import PPO

    agent_cfg = {"agent_cls": "DecimaScheduler"} | _shipped_agent_kwargs()
    env_cfg = {
        "num_executors": 5,
        "job_arrival_cap": 3,
        "moving_delay": 2000.0,
        "mean_time_limit": 2.0e7,
        "job_arrival_rate": 4.0e-5,
        "warmup_delay": 1000.0,
    }
    train_cfg = {
        "trainer_cls": "PPO",
        "num_iterations": 1,
        "num_sequences": 1,
        "num_rollouts": 2,
        "seed": 0,
        "use_tensorboard": False,
        "num_epochs": 1,
        "num_batches": 2,
        "beta_discount": 5.0e-3,
        "opt_kwargs": {"lr": 3.0e-4},
        "max_grad_norm": 0.5,
        "rollout_steps": 16,
        "checkpointing_freq": 10**9,
    }
    trainer = PPO(
        agent_cfg, env_cfg, train_cfg,
        health_cfg={"enabled": True} if health else None,
    )
    state = jax.eval_shape(trainer.init_state)
    it = jax.ShapeDtypeStruct((), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    ro, _, _ = jax.eval_shape(
        lambda p, i, r: trainer._collect(p, i, r, None),
        state.params, it, key,
    )
    return trainer._update, (state, ro)


def audit_all(names: tuple[str, ...] | None = None
              ) -> tuple[list[Violation], dict[str, Any]]:
    """Trace + audit every registered program (or the `names` subset).
    Returns (violations, measured-counts dict for the report)."""
    if names is not None:
        unknown = set(names) - set(BUDGETS)
        if unknown:
            raise ValueError(
                f"unknown program name(s) {sorted(unknown)} — the "
                "registry is the BUDGETS table's key set"
            )
    programs = build_programs(names)
    found: list[Violation] = []
    measured: dict[str, Any] = {}
    for name, closed in programs.items():
        if name not in BUDGETS:
            found.append(Violation(
                "jaxpr", "budget", name,
                "program has no entry in the BUDGETS table",
            ))
            continue
        vs, m = audit_closed_jaxpr(name, closed, BUDGETS[name])
        found.extend(vs)
        measured[name] = m
    return found, measured
