"""Flat micro-step execution engine.

`core.step` drives one decision at a time: the event loop between
decisions is a `lax.while_loop`, and under `jax.vmap` every lane pays the
*maximum* event count over the batch per decision (measured ~6x the mean
at 64 lanes — the straggler tax of lockstep scanning). This engine
flattens the whole simulation into identical micro-steps —

    DECIDE   one policy commitment (or round finish)
    FULFILL  one source-pool commitment fulfillment
    EVENT    one event pop + handling

— so every lane advances by one unit of work on every iteration and no
lane ever idles waiting for a straggler. Semantics are identical to the
`core.step` loop (same phase-split helpers, same ordering); the flat-vs-
step equivalence is asserted by tests/test_flat_loop.py. One difference
is by design: `core.step` looks at the episode's time limit where the
reference's StochasticTimeLimit wrapper does, back at a decision, so a
truncated episode's last step runs on to the first decision past the
limit; this engine looks after every event (`_lane_done`, the bulk
passes' `stop_at_limit`) and ends the episode on the first event at or
past the limit. Every decision, its time and every reward but a
truncated episode's last agree; that last span is a prefix of the core
path's (test_flat_collection_at_the_time_limit_ends_on_the_crossing_event).

`run_flat` over `micro_step` serves the bench/eval paths, where only
final states and decision counts matter. The trainers' collectors
(`trainers/rollout.py:collect_flat_sync_batch/_async_batch`) evaluate
the policy once per decision row themselves and drive the engine through
`decide_micro_step` and `drain_to_decision`, which also report the
row's reward, wall-clock advance and episode end.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from ..config import EnvParams
from ..obs.telemetry import add as _tm_add
from ..obs.tracing import annotate
from ..workload.bank import WorkloadBank
from .core import (
    RQ_NONE,
    _compute_jobtime,
    _rank_order,
    _onehot,
    _onehot2,
    _pick,
    _add_commitment,
    _apply_action,
    _bulk_events_fused,
    _bulk_fulfill,
    _bulk_ready,
    _bulk_relaunch,
    _commit_remaining,
    _fulfill_commitment_phase_a,
    _handle_executor_ready,
    _handle_job_arrival,
    _handle_task_finished,
    _has_pending_event,
    _move_idle_from_pool,
    _next_event,
    _resolve_action,
    find_schedulable,
)
from .observe import observe
from .state import (
    BIG_SEQ,
    EV_EXECUTOR_READY,
    EV_JOB_ARRIVAL,
    EV_TASK_FINISHED,
    EnvState,
)

_i32 = jnp.int32

M_DECIDE, M_FULFILL, M_EVENT = 0, 1, 2


class LoopState(struct.PyTreeNode):
    env: EnvState
    mode: jnp.ndarray  # i32 []
    fulfill_k: jnp.ndarray  # i32 []
    num_idle: jnp.ndarray  # i32 []
    exec_order: jnp.ndarray  # i32[N]
    slot_order: jnp.ndarray  # i32[N]
    decisions: jnp.ndarray  # i32 []; decision micro-steps taken
    episodes: jnp.ndarray  # i32 []; completed episodes
    bulked: jnp.ndarray  # i32 []; events consumed by bulk relaunches


def aux_action_fields(aux: dict, stage_idx: jnp.ndarray,
                      num_exec: jnp.ndarray, max_stages: int):
    """(lgprob, job_idx, num_exec_k) from a policy's aux dict, with the
    derivation fallbacks for policies that omit keys (heuristics report
    no job_idx; it derives from the flat padded node index
    stage_idx = job * max_stages + stage). Single source of truth for
    the reference collectors over `core.step` and the flat-engine
    collectors (both in `trainers/rollout.py`), so their recorded
    actions cannot drift apart."""
    lgprob = aux.get("lgprob", jnp.float32(0.0))
    job = aux.get(
        "job_idx", jnp.where(stage_idx >= 0, stage_idx // max_stages, 0)
    )
    k = aux.get("num_exec_k", num_exec - 1)
    return lgprob, job, k


def take_slot(store, i):
    """One session's `LoopState` gathered from a [C]-stacked store at a
    (possibly traced) slot index — the serve programs' gather
    (`serve/aot.py`) and the session pager's host-side page-out
    (`serve/session.py`) share this one definition, so the paged copy
    of a slot is by construction the same view the compiled program
    serves."""
    return jax.tree_util.tree_map(lambda a: a[i], store)


def write_slot(store, i, ls, drop: bool = False):
    """`take_slot`'s scatter partner: write one session's `LoopState`
    (or, with a vector index and [K]-stacked values, K sessions) back
    into a [C]-stacked store at slot index `i`. With `drop`,
    out-of-range indices drop instead of clamping (the batched serve
    program's padding-lane discipline). One definition shared by the
    serve programs' scatter-back (`serve/aot.py`) and the session
    store's slot writer / pager page-in (`serve/session.py`), so a
    paged or group-routed write is by construction the same update the
    compiled program performs."""
    kw = {"mode": "drop"} if drop else {}
    return jax.tree_util.tree_map(
        lambda s, v: s.at[i].set(v, **kw), store, ls
    )


class TrajRing(struct.PyTreeNode):
    """Device-resident trajectory ring (ISSUE 18): a [R]-stacked record
    pytree plus a monotone append cursor, living next to the session
    store and donated through the record-on serve programs.

    `cursor` counts TOTAL records ever appended (not the wrapped
    position): the host drains span `[drained, cursor)` and recovers the
    wrapped indices itself (`i % R`), so an overrun (more than R appends
    between drains) is detectable as `cursor - drained > R` instead of
    silently aliasing. `rec` is any [R, ...]-stacked record pytree — the
    serve layer stacks `RingRec` (serve/aot.py), but the append below is
    schema-agnostic."""

    cursor: jnp.ndarray  # i32 []; total records appended since init
    rec: Any  # [R, ...] record pytree


def ring_append(ring: TrajRing, recs, mask) -> TrajRing:
    """Masked in-JIT append into the ring: scalar `mask` appends one
    record, a [K] `mask` appends the masked subset of [K]-stacked
    records in order (exclusive-cumsum compaction), both via a single
    `mode="drop"` scatter — masked-off lanes target index R (out of
    range) and drop, so the traced program is branch-free and the
    donated ring updates in place. The wrap (`% R`) happens here, in
    the compiled program; the cursor advances by the number of records
    actually appended."""
    R = jax.tree_util.tree_leaves(ring.rec)[0].shape[0]
    if jnp.ndim(mask) == 0:
        n = mask.astype(_i32)
        idx = jnp.where(mask, ring.cursor % R, R)
    else:
        mi = mask.astype(_i32)
        n = mi.sum()
        offs = jnp.cumsum(mi) - mi  # exclusive cumsum: append order
        idx = jnp.where(mask, (ring.cursor + offs) % R, R)
    rec2 = jax.tree_util.tree_map(
        lambda s, v: s.at[idx].set(v, mode="drop"), ring.rec, recs
    )
    return TrajRing(cursor=ring.cursor + n, rec=rec2)


def init_loop_state(state: EnvState) -> LoopState:
    n = state.exec_job.shape[0]
    return LoopState(
        env=state,
        mode=_i32(M_DECIDE),
        fulfill_k=_i32(0),
        num_idle=_i32(0),
        exec_order=jnp.zeros(n, _i32),
        slot_order=jnp.zeros(n, _i32),
        decisions=_i32(0),
        episodes=_i32(0),
        bulked=_i32(0),
    )


def _pop_event(params: EnvParams, st: EnvState, enabled):
    """Pop + handle one event (core._resume_simulation body). Returns
    (state, req_kind, rj, rs, event_arg, quirk, popped, kind);
    a no-op (RQ_NONE, popped=False) when `enabled` is False or the
    queue is drained. `popped`/`kind` feed the telemetry counters."""
    has, t, kind, arg = _next_event(params, st)

    def pop(st: EnvState):
        st = st.replace(wall_time=t)
        quirk = st.source_job_id()
        st, rk, rj, rs = lax.switch(
            kind,
            [
                lambda st, a: _handle_job_arrival(st, a),
                lambda st, a: _handle_task_finished(st, a),
                lambda st, a: _handle_executor_ready(st, a),
            ],
            st,
            arg,
        )
        return st, rk, rj, rs, quirk

    def drained(st: EnvState):
        return st, _i32(RQ_NONE), _i32(-1), _i32(-1), _i32(-1)

    popped = enabled & has
    st, rk, rj, rs, quirk = lax.cond(popped, pop, drained, st)
    return st, rk, rj, rs, arg, quirk, popped, kind


def _bulk_cycle_chain(
    params: EnvParams,
    bank: WorkloadBank,
    env: EnvState,
    is_event: jnp.ndarray,
    bulk_events: int,
    bulk_cycles: int,
    bulk_fused: bool = True,
    lane_axis: str | None = None,
):
    """`bulk_cycles` chained bulk passes. With `bulk_fused` (the ISSUE-7
    default) each cycle is ONE `core._bulk_events_fused` kernel that
    consumes a mixed relaunch/arrival run in exact (time, seq) order —
    one early-exit loop (as many steps as the run is long; under vmap,
    as the longest run of the batch), one rng split, one merged state
    update per cycle; without it, each cycle is the round-3/4 (relaunch
    cascade + arrival burst) pass pair. The first cycle runs whenever
    the lane is in EVENT mode; each further cycle runs only while the
    sequential between-event
    tail would be a no-op — `num_committable() == 0` (round-ready flip
    and move_and_clear are gated on committable > 0) and the wall clock
    inside the episode limit (the freeze point) — so chaining is
    exactly the next micro-step's bulk phase minus its provably-no-op
    tail. Returns (env, events_consumed, relaunch_events, ready_events,
    scan_steps, lane_syncs) — relaunch and ready split the count by
    event kind, `scan_steps` is the steps the fused passes' loops
    needed for this lane and `lane_syncs` the reductions over
    `lane_axis` they made for the batch (both 0 from the unfused pair),
    all for the telemetry counters. `lane_axis` is handed to the fused
    pass."""
    nb = _i32(0)
    nb_rel = _i32(0)
    nb_rdy = _i32(0)
    steps = _i32(0)
    syncs = _i32(0)
    for i in range(bulk_cycles):
        on = is_event if i == 0 else (
            is_event
            & (env.num_committable() == 0)
            & (env.wall_time < env.time_limit)
        )
        if bulk_fused:
            env, nbi1, nbi2, si, yi = _bulk_events_fused(
                params, bank, env, on,
                stop_at_limit=True, max_events=bulk_events,
                lane_axis=lane_axis,
            )
            steps = steps + si
            syncs = syncs + yi
        else:
            env, nbi1 = _bulk_relaunch(
                params, bank, env, on,
                stop_at_limit=True, max_events=bulk_events,
            )
            # chain the arrival-burst pass; never past an episode-limit
            # crossing the cascade just committed (the freeze point)
            env, nbi2 = _bulk_ready(
                params, bank, env,
                on & (env.wall_time < env.time_limit),
                stop_at_limit=True,
            )
        nb = nb + nbi1 + nbi2
        nb_rel = nb_rel + nbi1
        nb_rdy = nb_rdy + nbi2
    return env, nb, nb_rel, nb_rdy, steps, syncs


def _lane_done(env: EnvState) -> jnp.ndarray:
    """Episode over: all jobs complete or the time limit was crossed."""
    return env.all_jobs_complete | (env.wall_time >= env.time_limit)


def _fused_pop_gate(env: EnvState, nb: jnp.ndarray) -> jnp.ndarray:
    """May this micro-step still pop the run-cutting event after its
    bulk passes consumed `nb` events? Always when nothing was bulked
    (the classic single-pop path — the previous micro-step's tail ran
    for real); after a bulk only when the skipped between-event tail is
    provably a no-op (see `_bulk_cycle_chain`)."""
    return (nb == 0) | (
        (env.num_committable() == 0)
        & (env.wall_time < env.time_limit)
    )


def _clear_round(st: EnvState) -> EnvState:
    return st.replace(
        source_valid=jnp.bool_(False),
        source_job=_i32(-1),
        source_stage=_i32(-1),
        stage_selected=jnp.zeros_like(st.stage_selected),
        round_ready=jnp.bool_(False),
        schedulable=jnp.zeros_like(st.schedulable),
    )


def _apply_decision(
    params: EnvParams, ls: LoopState, stage_idx: jnp.ndarray,
    num_exec: jnp.ndarray, fulfill_bulk: bool,
) -> LoopState:
    """core.step's front half for ONE precomputed policy decision on
    `ls.env`: commit (or round finish), fulfillment-phase setup, mode
    bookkeeping. Shared by `micro_step`'s DECIDE branch and the
    single-eval `decide_micro_step` so the two can never drift. The
    caller runs the shared `_finish_micro_step` tail."""
    st = ls.env
    n = st.exec_job.shape[0]
    s_cap = params.max_stages
    j, s = stage_idx // s_cap, stage_idx % s_cap
    # all-false for a `stage_idx` out of range, which `valid` rules out
    sel = _onehot2(params.max_jobs, s_cap, j, s)
    valid = (
        (stage_idx >= 0)
        & (stage_idx < params.num_nodes)
        & _pick(sel, st.schedulable)
    )

    def do_commit(stt: EnvState) -> EnvState:
        committable = stt.num_committable()
        nn = jnp.clip(num_exec, 1, committable)
        nn = jnp.minimum(nn, _pick(sel, stt.exec_demand))
        stt = _add_commitment(stt, nn, j, s)
        stt = stt.replace(stage_selected=stt.stage_selected | sel)
        return stt.replace(
            schedulable=find_schedulable(
                params, stt, stt.source_job_id()
            )
        )

    st = lax.cond(valid, do_commit, _commit_remaining, st)
    round_continues = (
        (st.num_committable() > 0) & st.schedulable.any()
    )

    def finish(st: EnvState):
        st = _commit_remaining(st)
        idle = st.source_pool_mask() & ~st.exec_executing
        num_idle = idle.sum().astype(_i32)
        exec_order = _rank_order(
            jnp.where(idle, jnp.arange(n, dtype=_i32), BIG_SEQ)
        )
        match = (
            st.cm_valid
            & (st.cm_src_job == st.source_job)
            & (st.cm_src_stage == st.source_stage)
        )
        slot_order = _rank_order(
            jnp.where(match, st.cm_seq, BIG_SEQ)
        )
        if fulfill_bulk:
            # the bulk pass samples durations, and bank accesses
            # must stay OUT of lane-dependent branches: batching a
            # cond instantiates branch constants as broadcast
            # outputs, materializing a per-lane copy of the bank's
            # [T,S,3,L,K] duration table (a 19 GB HBM allocation at
            # 512 lanes on the v5e). The pass runs unconditionally
            # in the shared tail (_finish_micro_step), gated by
            # mode — exactly like the relaunch cascade above the
            # switch — along with the complete/clear/mode step.
            return st, _i32(M_FULFILL), num_idle, exec_order, \
                slot_order, _i32(0)
        k0 = _i32(0)
        # phase already complete (empty): clear and go straight to
        # events — matching core.step, which clears only after
        # _fulfill_from_source returns (no leftover backup search
        # remains to observe stage_selected)
        complete = k0 >= num_idle
        st = lax.cond(complete, _clear_round, lambda x: x, st)
        mode = jnp.where(complete, M_EVENT, M_FULFILL)
        return st, mode.astype(_i32), num_idle, exec_order, \
            slot_order, k0

    def stay(st: EnvState):
        return (
            st, _i32(M_DECIDE), _i32(0), ls.exec_order,
            ls.slot_order, _i32(0),
        )

    st, mode, num_idle, eo, so, k0 = lax.cond(
        round_continues, stay, finish, st
    )
    return ls.replace(
        env=st,
        mode=mode,
        fulfill_k=k0,
        num_idle=num_idle,
        exec_order=eo,
        slot_order=so,
        decisions=ls.decisions + 1,
    )


def _fulfill_branch(ls: LoopState):
    """One commitment fulfillment (core._fulfill_from_source body, one k
    per micro-step). Returns (ls, rk, rj, rs, e, quirk, popped, kind) —
    the shared-tail argument tuple."""
    st = ls.env
    k = ls.fulfill_k
    # a lane past its last idle executor (`skip` below; k may be N)
    # picks executor 0 where the read clamped to the last entry, and
    # requests nothing either way
    ok = _onehot(ls.exec_order.shape[0], k)
    e = _pick(ok, ls.exec_order)
    quirk = st.source_job_id()

    def do(st: EnvState):
        return _fulfill_commitment_phase_a(st, e, _pick(ok, ls.slot_order))

    def skip(st: EnvState):
        return st, _i32(RQ_NONE), _i32(-1), _i32(-1)

    st, rk, rj, rs = lax.cond(k < ls.num_idle, do, skip, st)
    last = k + 1 >= ls.num_idle
    # round clearing is deferred to the shared tail (after this
    # fulfillment's resolve/apply), matching core.step which clears
    # only after _fulfill_from_source returns — the final executor's
    # backup-stage search must still see stage_selected
    mode = jnp.where(last, M_EVENT, M_FULFILL).astype(_i32)
    return ls.replace(env=st, mode=mode, fulfill_k=k + 1), rk, rj, rs, \
        e, quirk, jnp.bool_(False), _i32(0)


def _event_branch(params: EnvParams, ls: LoopState, nb: jnp.ndarray):
    """One event pop + handling (core._resume_simulation body) with the
    fused-pop gate over the `nb` events the bulk passes just consumed.
    Returns the shared-tail argument tuple."""
    st, rk, rj, rs, arg, quirk, popped, kind = _pop_event(
        params, ls.env, _fused_pop_gate(ls.env, nb)
    )
    return ls.replace(env=st), rk, rj, rs, arg, quirk, popped, kind


def micro_step(
    params: EnvParams,
    bank: WorkloadBank,
    policy_fn: Callable,
    ls: LoopState,
    rng: jax.Array,
    auto_reset: bool = True,
    compute_levels: bool = True,
    event_bulk: bool = True,
    bulk_events: int = 8,
    fulfill_bulk: bool = False,
    bulk_cycles: int = 1,
    reset_fn: Callable | None = None,
    telemetry=None,
    bulk_fused: bool = True,
) -> LoopState | tuple:
    """One unit of work for one lane (vmap over lanes). With
    `event_bulk`, an EVENT micro-step consumes a whole run of relaunch
    events via `core._bulk_relaunch` (hoisted above the mode switch —
    it samples task durations, and bank accesses must stay out of
    lane-dependent branches; see core's structural note), chains the
    arrival-burst pass, and then — new in round 4 — still pops the
    run-cutting event in the SAME micro-step ("fused pop") whenever the
    sequential engine's between-event tail is provably a no-op:
    `num_committable() == 0` (the tail's round-ready flip and
    move_and_clear are both gated on committable > 0, and the bulk
    passes stop BEFORE any point where they could raise it — a
    source-joining arrival ends `_bulk_ready`'s prefix) and the wall
    clock is inside the episode limit (the freeze point). `bulk_cycles`
    extra (relaunch + ready) pass pairs run first under the same gate,
    consuming alternating run/burst patterns that previously cost one
    micro-step per kind switch.

    With `fulfill_bulk`, a DECIDE micro-step that finishes a commitment
    round consumes the fulfillment phase's simple prefix in one
    `core._bulk_fulfill` pass (exactly `core.step`'s bulk path) and only
    the backup-scheduling leftovers take FULFILL micro-steps — removing
    the ~1 FULFILL step per decision the flat loop otherwise pays. Like
    the relaunch cascade, the pass's op count is charged to every lane
    on every micro-step under vmap (a batched `lax.switch` executes all
    branches), so the flag is calibration-gated in bench.py rather than
    assumed to win.

    `reset_fn`, when given, replaces the auto-reset draw: called as
    `reset_fn(key, episodes)` with the lane's completed-episode count.

    With `bulk_fused` (the ISSUE-7 default), the bulk phase is the
    single fused `core._bulk_events_fused` kernel — mixed
    relaunch/arrival runs in exact queue order, one pass — instead of
    the (relaunch cascade + arrival burst) pass pair; step-exact
    either way (tests/test_flat_loop.py pins fused vs unfused).

    With `telemetry` (an `obs.Telemetry`, static None check), the
    counters are advanced on live lanes — micro-step composition by
    entry mode, events consumed (`loop_iters`), pops by kind, bulk-pass
    consumption — and the call returns `(ls, telemetry)`. The None
    path threads nothing."""
    track = telemetry is not None
    k_pol, k_reset = jax.random.split(rng)
    ls0 = ls  # pre-bulk state: the freeze path must restore exactly this
    if event_bulk:
        env_b, nb, nb_rel, nb_rdy, nsteps, _ = _bulk_cycle_chain(
            params, bank, ls.env, ls.mode == M_EVENT, bulk_events,
            bulk_cycles, bulk_fused,
        )
        ls = ls.replace(env=env_b, bulked=ls.bulked + nb)
    else:
        nb = _i32(0)
        nb_rel = nb_rdy = nsteps = nb

    # ---- DECIDE: one commitment from the policy (core.step's front
    # half; the commit/round logic lives in the shared `_apply_decision`)
    def decide(ls: LoopState):
        obs = observe(params, ls.env, compute_levels)
        stage_idx, num_exec, _ = policy_fn(k_pol, obs)
        ls2 = _apply_decision(params, ls, stage_idx, num_exec, fulfill_bulk)
        return ls2, _i32(RQ_NONE), _i32(-1), _i32(-1), _i32(0), \
            ls2.env.source_job_id(), jnp.bool_(False), _i32(0)

    # ---- FULFILL: one commitment fulfillment (core._fulfill_from_source
    # body, one k per micro-step)
    def fulfill(ls: LoopState):
        return _fulfill_branch(ls)

    # ---- EVENT: one event pop + handling (core._resume_simulation
    # body). Fused pop: even after the bulk passes consumed events, the
    # run-cutting event they stopped at is popped in the same micro-step
    # when the skipped between-event tail is provably a no-op
    def event(ls: LoopState):
        return _event_branch(params, ls, nb)

    with annotate("env/micro_step"):
        ls2, rk, rj, rs, e, quirk, popped, ev_kind = lax.switch(
            ls.mode, [decide, fulfill, event], ls
        )
        out = _finish_micro_step(
            params, bank, ls0, ls2, rk, rj, rs, e, quirk, k_reset,
            auto_reset, fulfill_bulk=fulfill_bulk, reset_fn=reset_fn,
            telem=telemetry,
        )
    if track:
        out, telemetry = out
        # frozen lanes (auto_reset=False, episode already over at
        # entry) count nothing — the tail rolls their state back
        live = ~_lane_done(ls0.env)
        pop_live = popped & live
        telemetry = _tm_add(
            telemetry,
            decide_steps=(ls0.mode == M_DECIDE) & live,
            fulfill_steps=(ls0.mode == M_FULFILL) & live,
            event_steps=(ls0.mode == M_EVENT) & live,
            commit_rounds=(ls0.mode == M_DECIDE) & live
            & (ls2.mode != M_DECIDE),
            loop_iters=jnp.where(live, nb + popped.astype(_i32), 0),
            bulk_relaunch_events=jnp.where(live, nb_rel, 0),
            bulk_ready_events=jnp.where(live, nb_rdy, 0),
            bulk_passes=(nb > 0) & live,
            bulk_scan_steps=jnp.where(live, nsteps, 0),
            ev_job_arrival=pop_live & (ev_kind == EV_JOB_ARRIVAL),
            ev_task_finished=pop_live & (ev_kind == EV_TASK_FINISHED),
            ev_exec_ready=pop_live & (ev_kind == EV_EXECUTOR_READY),
        )
    return (out, telemetry) if track else out


def _finish_micro_step(
    params: EnvParams,
    bank: WorkloadBank,
    ls: LoopState,
    ls2: LoopState,
    rk: jnp.ndarray,
    rj: jnp.ndarray,
    rs: jnp.ndarray,
    e: jnp.ndarray,
    quirk: jnp.ndarray,
    k_reset: jax.Array | None,  # read under auto_reset only
    auto_reset: bool,
    fulfill_bulk: bool = False,
    record: bool = False,
    reset_fn: Callable | None = None,
    t_ref: jnp.ndarray | None = None,
    telem=None,
) -> LoopState | tuple:
    """Shared micro-step tail: move resolution/application, round clearing
    and readiness, episode end. `ls` is the pre-step state, `ls2` the
    state after the mode branch ran. With `record`, also returns the
    micro-step's `(reward, dt, reset)` triple, measured on the pre-reset
    state and zeroed for frozen lanes: `reward` is the negative job-time
    contribution (discount-referenced to the caller-carried `t_ref`, see
    `_compute_jobtime`), `dt` the wall-clock advance, and `reset`
    whether the episode ended during the micro-step. With `telem`,
    the bulk-fulfillment hit count is added (live lanes only) and the
    telemetry is returned as the trailing element.

    With `fulfill_bulk`, a DECIDE micro-step that just finished a
    commitment round (mode went DECIDE -> FULFILL) consumes the
    fulfillment phase's simple prefix here via `core._bulk_fulfill`,
    hoisted out of the decide branch so the duration table is never a
    lane-dependent cond operand (see the branch comment in
    `micro_step.decide.finish`). The pass is a strict state no-op
    (rng included) for lanes where the gate is off: every scatter in
    `_bulk_fulfill` is masked by its candidate prefix, which is empty
    at num_idle=0."""
    st = ls2.env

    if fulfill_bulk:
        want = (ls.mode == M_DECIDE) & (ls2.mode == M_FULFILL)
        ni = jnp.where(want, ls2.num_idle, 0)
        st, k0 = _bulk_fulfill(
            params, bank, st, ni, ls2.exec_order, ls2.slot_order
        )
        if telem is not None:
            live = ~_lane_done(ls.env)
            telem = _tm_add(
                telem, bulk_fulfill_hits=jnp.where(live, k0, 0)
            )
        # phase complete (empty, or fully consumed by the pass): clear
        # and go straight to events — matching core.step, which clears
        # only after _fulfill_from_source returns (no leftover backup
        # search remains to observe stage_selected)
        complete = want & (k0 >= ls2.num_idle)
        st = lax.cond(complete, _clear_round, lambda x: x, st)
        ls2 = ls2.replace(
            fulfill_k=jnp.where(want, k0, ls2.fulfill_k).astype(_i32),
            mode=jnp.where(complete, M_EVENT, ls2.mode).astype(_i32),
        )

    # shared move resolution + application (the only bank access)
    ak, tj, ts = _resolve_action(params, st, rk, e, rj, rs, quirk)
    st = _apply_action(params, bank, st, ak, e, tj, ts)

    # a FULFILL micro-step that consumed the round's last idle executor
    # clears the round here, after its resolve/apply (core.step ordering)
    fulfill_done = (ls.mode == M_FULFILL) & (
        ls2.fulfill_k >= ls2.num_idle
    )
    st = lax.cond(fulfill_done, _clear_round, lambda x: x, st)

    # post-event round-ready check (core._resume_simulation :tail), only
    # meaningful after EVENT micro-steps
    is_event = ls.mode == M_EVENT
    committable = st.num_committable()
    sched = find_schedulable(params, st, st.source_job_id())
    ready = is_event & (committable > 0) & sched.any()

    def set_ready(st: EnvState) -> EnvState:
        return st.replace(round_ready=jnp.bool_(True), schedulable=sched)

    def not_ready(st: EnvState) -> EnvState:
        def move_and_clear(st: EnvState) -> EnvState:
            idle = st.source_pool_mask() & ~st.exec_executing
            st = _move_idle_from_pool(
                st, st.source_job, st.source_stage, idle,
                _onehot(params.max_jobs, st.source_job),
            )
            return st.replace(
                source_valid=jnp.bool_(False),
                source_job=_i32(-1),
                source_stage=_i32(-1),
            )

        return lax.cond(
            is_event & (committable > 0), move_and_clear,
            lambda x: x, st,
        )

    st = lax.cond(ready, set_ready, not_ready, st)
    mode = jnp.where(ready, M_DECIDE, ls2.mode).astype(_i32)

    # episode end. The loops whose unit is the micro-step (`micro_step`
    # and so `run_flat`, and `drain_micro_step` called on its own)
    # re-seed here with auto_reset: the lane goes on in its NEXT
    # micro-step, so the reset program runs in every one
    # and the state is selected against it (unconditional, which keeps
    # the workload bank out of lane-dependent conditionals). With
    # auto_reset=False finished lanes freeze instead: tests, evals, the
    # decide step, and the body of `drain_to_decision`, whose unit is
    # the decision row and which re-seeds once, after its loop
    # (`_reseed_ended`)
    done = _lane_done(st)
    was_done = _lane_done(ls.env)
    if record:
        # reward/dt on the PRE-reset state (the reset select below would
        # lose the episode's final span); frozen lanes report zeros
        t_old = ls.env.wall_time
        jt = _compute_jobtime(
            params, st, t_old, ls.env.job_active, t_ref
        )
        rec_tail = (
            jnp.where(was_done, 0.0, -jt),
            jnp.where(was_done, 0.0, st.wall_time - t_old),
            done & ~was_done,
        )
    if auto_reset:
        # one whole name (obs/tracing.py): the reset program and the
        # select of the whole state, paid by every micro-step of these
        # loops
        with annotate("env/micro_step/reset"):
            # ls2.episodes is the pre-increment completed-episode count
            fresh = _fresh_episode(
                params, bank, k_reset, reset_fn, ls2.episodes
            )
            st = jax.tree_util.tree_map(
                lambda a, b: jnp.where(done, a, b), fresh, st
            )
            mode = jnp.where(done, M_DECIDE, mode).astype(_i32)
        if telem is not None:
            telem = _tm_add(
                telem, reseeds=done & ~was_done, reset_evals=1
            )
    else:
        st = jax.tree_util.tree_map(
            lambda a, b: jnp.where(was_done, a, b), ls.env, st
        )
        ls2 = ls2.replace(
            decisions=jnp.where(
                was_done, ls.decisions, ls2.decisions
            ).astype(_i32),
            bulked=jnp.where(
                was_done, ls.bulked, ls2.bulked
            ).astype(_i32),
        )
    out = ls2.replace(
        env=st,
        mode=mode,
        episodes=ls2.episodes + (done & ~was_done).astype(_i32),
    )
    ret = (out, rec_tail) if record else (out,)
    if telem is not None:
        ret = ret + (telem,)
    return ret[0] if len(ret) == 1 else ret


def decide_micro_step(
    params: EnvParams,
    bank: WorkloadBank,
    ls: LoopState,
    stage_idx: jnp.ndarray,
    num_exec: jnp.ndarray,
    fulfill_bulk: bool = False,
    t_ref: jnp.ndarray | None = None,
    telemetry=None,
) -> tuple:
    """One DECIDE-only micro-step driven by a PRECOMPUTED policy decision:
    lanes in M_DECIDE mode commit (or round-finish) via the shared
    `_apply_decision` + `_finish_micro_step` pair; other lanes no-op
    bit-exactly (their state must not advance). The single-eval flat
    collectors (`trainers/rollout.py:collect_flat_*_batch`) evaluate the
    policy ONCE per decision row at batch level and feed the outputs
    here, so the GNN appears exactly once per recorded decision instead
    of once per micro-step group. Returns
    `(ls, (decided, reward, dt, reset)[, telemetry])`; `decided` marks
    lanes that recorded a decision (live and in DECIDE mode at entry).

    The step draws nothing and cannot end an episode: it never advances
    the wall clock and finishes no task, and `_lane_done` reads those
    two. So it has no `auto_reset`: there is never an episode to
    re-seed here, `reset` is False for every lane that was live at
    entry, and a lane handed in with its episode over is frozen, as in
    a sync collection."""
    track = telemetry is not None
    with annotate("env/micro_step/decide"):
        is_dec = ls.mode == M_DECIDE
        # force the tail's mode-keyed logic to the DECIDE shape for every
        # lane: non-decide lanes' branch results are discarded by the
        # final select below
        ls0 = ls.replace(mode=_i32(M_DECIDE))
        ls2 = _apply_decision(params, ls0, stage_idx, num_exec, fulfill_bulk)
        mode2 = ls2.mode  # pre-tail mode: DECIDE -> non-DECIDE == round done
        out = _finish_micro_step(
            params, bank, ls0, ls2, _i32(RQ_NONE), _i32(-1), _i32(-1),
            _i32(0), ls2.env.source_job_id(), None, False,
            fulfill_bulk=fulfill_bulk, record=True, t_ref=t_ref,
            telem=telemetry,
        )
        if track:
            out_ls, (rw, dt, rs_), telemetry = out
        else:
            out_ls, (rw, dt, rs_) = out
        was_done = _lane_done(ls.env)
        decided = is_dec & ~was_done
        if track:
            telemetry = _tm_add(
                telemetry,
                decide_steps=decided,
                commit_rounds=decided & (mode2 != M_DECIDE),
            )
        final = jax.tree_util.tree_map(
            lambda a, b: jnp.where(is_dec, a, b), out_ls, ls
        )
        zero = jnp.float32(0.0)
        rec = (
            decided,
            jnp.where(is_dec, rw, zero),
            jnp.where(is_dec, dt, zero),
            is_dec & rs_,
        )
    return (final, rec, telemetry) if track else (final, rec)


def drain_micro_step(
    params: EnvParams,
    bank: WorkloadBank,
    ls: LoopState,
    rng: jax.Array,
    auto_reset: bool = True,
    event_bulk: bool = True,
    bulk_events: int = 8,
    bulk_cycles: int = 1,
    reset_fn: Callable | None = None,
    t_ref: jnp.ndarray | None = None,
    telemetry=None,
    bulk_fused: bool = True,
    masked: bool = True,
    lane_axis: str | None = None,
) -> tuple:
    """One NON-POLICY micro-step: FULFILL and EVENT lanes advance exactly
    as `micro_step`'s branches (bulk passes + fused pop included); DECIDE
    lanes no-op bit-exactly. Contains no observe/policy ops at all — the
    point of the single-eval restructure is that this program, not the
    policy-bearing one, runs between decisions. Returns
    `(ls, (reward, dt, reset)[, telemetry])`.

    `masked=False` skips the final full-pytree select that rolls
    DECIDE-mode lanes back — legal ONLY when the caller already
    guarantees every lane that reaches this step is non-DECIDE, which
    is exactly `drain_to_decision`'s while body: the vmapped
    while-loop's batching rule selects the whole carry against each
    lane's own cond, so the per-iteration ~50-leaf select here (adj is
    [J,S,S] per lane) was pure duplicated bandwidth on the drain's hot
    path (ISSUE 7 drain restructure). `lane_axis`: see
    `drain_to_decision`."""
    track = telemetry is not None
    active = ls.mode != M_DECIDE
    _, k_reset = jax.random.split(rng)
    ls0 = ls
    if event_bulk:
        env_b, nb, nb_rel, nb_rdy, nsteps, nsyncs = _bulk_cycle_chain(
            params, bank, ls.env, ls.mode == M_EVENT, bulk_events,
            bulk_cycles, bulk_fused, lane_axis,
        )
        ls = ls.replace(env=env_b, bulked=ls.bulked + nb)
    else:
        nb = _i32(0)
        nb_rel = nb_rdy = nsteps = nsyncs = nb

    def noop(ls: LoopState):
        return ls, _i32(RQ_NONE), _i32(-1), _i32(-1), _i32(0), \
            ls.env.source_job_id(), jnp.bool_(False), _i32(0)

    ls2, rk, rj, rs, e, quirk, popped, ev_kind = lax.switch(
        ls.mode,
        [noop, _fulfill_branch, lambda l: _event_branch(params, l, nb)],
        ls,
    )
    out = _finish_micro_step(
        params, bank, ls0, ls2, rk, rj, rs, e, quirk, k_reset,
        auto_reset, record=True, reset_fn=reset_fn, t_ref=t_ref,
        telem=telemetry,
    )
    if track:
        out_ls, (rw, dt, rs_), telemetry = out
    else:
        out_ls, (rw, dt, rs_) = out
    was_done = _lane_done(ls0.env)
    gate = active & ~was_done
    if track:
        pop_live = popped & gate
        telemetry = _tm_add(
            telemetry,
            fulfill_steps=(ls0.mode == M_FULFILL) & ~was_done,
            event_steps=(ls0.mode == M_EVENT) & ~was_done,
            loop_iters=jnp.where(gate, nb + popped.astype(_i32), 0),
            bulk_relaunch_events=jnp.where(gate, nb_rel, 0),
            bulk_ready_events=jnp.where(gate, nb_rdy, 0),
            bulk_passes=(nb > 0) & gate,
            bulk_scan_steps=jnp.where(gate, nsteps, 0),
            # a fact of the batch, whatever this lane holds: the
            # collector's row takes the longest-running lane's total
            lane_syncs=nsyncs,
            ev_job_arrival=pop_live & (ev_kind == EV_JOB_ARRIVAL),
            ev_task_finished=pop_live & (ev_kind == EV_TASK_FINISHED),
            ev_exec_ready=pop_live & (ev_kind == EV_EXECUTOR_READY),
        )
    if not masked:
        # drain-while body: the loop's own batched-cond carry select
        # already discards DECIDE lanes' outputs
        rec = (rw, dt, rs_)
        return (out_ls, rec, telemetry) if track else (out_ls, rec)
    final = jax.tree_util.tree_map(
        lambda a, b: jnp.where(active, a, b), out_ls, ls0
    )
    zero = jnp.float32(0.0)
    rec = (
        jnp.where(active, rw, zero),
        jnp.where(active, dt, zero),
        active & rs_,
    )
    return (final, rec, telemetry) if track else (final, rec)


def drain_to_decision(
    params: EnvParams,
    bank: WorkloadBank,
    ls: LoopState,
    rng: jax.Array,
    auto_reset: bool = True,
    event_bulk: bool = True,
    bulk_events: int = 8,
    bulk_cycles: int = 1,
    reset_fn: Callable | None = None,
    t_ref: jnp.ndarray | None = None,
    telemetry=None,
    bulk_fused: bool = True,
    lane_axis: str | None = None,
    result_fn: Callable | None = None,
) -> tuple:
    """Drain one lane's non-decision work — FULFILL leftovers and the
    whole inter-decision event run — until it is ready to DECIDE again
    (or its episode is over / its event queue is drained), accumulating
    the span's reward/dt/reset with `t_ref` as the discount reference.

    The batch collectors vmap this; under vmap the while-loop costs the
    longest drain among the lanes the `vmap` spans per decision row —
    but every iteration is
    pure env machinery (bulk passes + single pops), and the GNN runs
    exactly once per decision outside this loop. That is why the
    collectors span no more than a block of 128 lanes with one `vmap`
    of this function (`trainers/rollout.py`, `_DRAIN_BLOCK`): a batch of
    several blocks is drained block by block, so a lane waits for the
    slowest lane of its own block and a block whose lanes are all at a
    decision, or ended, pays one predicate (at 1024 lanes the whole
    batch's `while` ran 24 bodies for each one a lane needed; PERF.md
    section 6, PR 43). Which slice is the
    cheap one depends on the device: on the TPU v5e this loop is
    nearly three fifths of a decision row of 128 lanes and the GNN a
    sixth (PERF.md section 5, PR 39). The device time is under the
    scope `env/micro_step/drain`. A body of 128 lanes costs 1.0 ms
    there: 0.50 ms whatever the lanes hold (the pass's set-up and its
    merged state update, the one-hot counters of the consumed arrivals
    42 us of it; the pop; the shared tail; sixty-odd relayouts of
    [lanes,J,S] arrays at 8 to 10 us) and 26 us for each step of the
    fused bulk pass's early-exit loop, which runs as many steps as the
    longest run among the lanes (19 on average, of a budget of 58;
    PERF.md section 5, PR 45's count). Since PR 50 a step costs 8 us
    (an iteration of two 15.7 us at a job axis of 50 and 16.7 at 20,
    from 39: the step reads the bank for three elements and the lane's
    own state by the one-hots it builds, where it made nine gathers),
    so the loop was a quarter to a half of a body and the part spent
    whatever the lanes hold the larger one (276 of 384 us in
    `sweep_fair`, 168 of 318 in `decima_batch20`; PERF.md section 5,
    PR 50): compiled for the v5e, 71 of that part's 220 instructions
    were gathers, every indexed read of a lane's own state at one
    (job, stage), job, executor or slot, each with a relayout of its
    index column. Since PR 51 those reads are picks by the one-hots
    the masked writes use (`core._pick`) and the part is 120
    instructions, its three gathers the bank's for `_apply_action`'s
    duration: 45 us of a 130-us body in `sweep_fair` and 44 of 194 in
    `decima_batch20` (most of what went were relayouts of whole
    [lanes,J,S] grids into the layout the gathers' operands wanted,
    7 us each; PERF.md sections 5 and 6, PR 51), so the early-exit
    loop is two thirds to three quarters of a body now.
    Nothing in the body reads the [J,S,S] adjacency at all: the pass's
    refresh of the saturation caches counts on `EnvState.parent_sets`
    (until PR 39 a contraction over the adjacency, 181 us a body), and
    `_refresh_sat` and the released-stage handler read a stage's
    children and a job's adjacency off the same words (until PR 51 six
    row gathers), so the compiled loop does not carry the adjacency.
    `lane_axis`, the name the caller's `vmap` gave its lane axis, lets
    that loop end on one predicate for all the lanes of that `vmap`
    (the whole batch, or one block of it); a caller
    without one (a single lane) leaves it None.
    The ISSUE-7 restructure keeps that slice cheap two ways: the cond
    reduces to the existence bit of the next event (`_has_pending_event`
    — no argmin/kind chain), and the body runs `drain_micro_step` with
    `masked=False`, relying on the batched while-loop's own per-lane
    carry select instead of re-selecting the ~50-leaf LoopState every
    iteration. The per-lane iteration count is measured directly
    (`drain_iters` — its max/mean over lanes IS the drain's batch-max
    while tax). Returns `(ls, (reward, dt, reset)[, telemetry])`.

    The unit here is the decision row, not the micro-step: a lane whose
    episode ends leaves the loop at that body (the cond reads
    `_lane_done`) and nothing runs on it until the next row. So the
    body's tail never re-seeds, with or without `auto_reset`, and the
    loop is the same program in both modes: the leaves only a reset
    writes (the adjacency, the templates, the task counts) stay
    loop-invariant. With `auto_reset` the lane is re-seeded ONCE, after
    the loop (`_reseed_ended`): the reset program runs only in a row in
    which some lane under the caller's `vmap` ended (one predicate over
    `lane_axis`: the batch's, or the block's),
    at the ordinal the tail would have used. The state, the row's
    `(reward, dt, reset)` and the episode count are those of
    `drain_micro_step(auto_reset=True)` repeated until the lane is
    ready to decide (tests/test_trainers.py holds the two against each
    other a row at a time); with a `reset_fn` that ignores its key, as
    the streaming collector's does, bit for bit.

    `result_fn`, where a caller gives one, is read on the state the
    loop left, BEFORE the re-seed replaces an ended episode's: the one
    place an episode's result (`metrics.episode_result`: its average
    job completion time, the jobs it completed, its makespan) can be
    taken, beside `episodes_terminated`. `result_fn(ls.env)` then rides
    the span as its fourth element, `(reward, dt, reset, result)`, for
    every lane; it is an ended episode's where `reset` is set. Without
    one nothing is read and the program is what it was."""
    track = telemetry is not None
    zero = jnp.float32(0.0)

    def cond(c):
        ls = c[0]
        has = _has_pending_event(ls.env)
        # a drained queue with the episode still open cannot progress
        # without a new decision round — hand such a lane back to the
        # caller instead of spinning forever
        stuck = (ls.mode == M_EVENT) & ~has & ~ls.env.round_ready
        return (ls.mode != M_DECIDE) & ~_lane_done(ls.env) & ~stuck

    def body(c):
        if track:
            ls, k, rw, dt, rs, tm = c
            tm = _tm_add(tm, drain_iters=1)
        else:
            (ls, k, rw, dt, rs), tm = c, None
        k, sub = jax.random.split(k)
        out = drain_micro_step(
            params, bank, ls, sub, False, event_bulk, bulk_events,
            bulk_cycles, t_ref=t_ref, telemetry=tm,
            bulk_fused=bulk_fused, masked=False, lane_axis=lane_axis,
        )
        if track:
            ls, (r, d, re), tm = out
        else:
            ls, (r, d, re) = out
        c2 = (ls, k, rw + r, dt + d, rs | re)
        return c2 + (tm,) if track else c2

    c0 = (ls, rng, zero, zero, jnp.bool_(False))
    if track:
        c0 = c0 + (telemetry,)
    with annotate("env/micro_step/drain"):
        c = lax.while_loop(cond, body, c0)
        ls, rw, dt, rs = c[0], c[2], c[3], c[4]
        tm = c[5] if track else None
        if track and tm.counts_episodes:
            # an episode that ended in this drain with every job
            # complete ended by completion, not on a limit (read here,
            # before a re-seed replaces the state)
            tm = _tm_add(
                tm, episodes_terminated=rs & ls.env.all_jobs_complete
            )
        span = (rw, dt, rs)
        if result_fn is not None:
            span = span + (result_fn(ls.env),)
    if auto_reset:
        # one whole name (obs/tracing.py), beside `env/micro_step/drain`
        with annotate("env/micro_step/reset"):
            ls = _reseed_ended(
                params, bank, ls, rs, c[1], reset_fn, lane_axis
            )
            tm = _tm_add(tm, reseeds=rs)
    return (ls, span, tm) if track else (ls, span)


def _fresh_episode(
    params: EnvParams,
    bank: WorkloadBank,
    key: jax.Array,
    reset_fn: Callable | None,
    ordinal: jnp.ndarray,
) -> EnvState:
    """The state a re-seeded lane starts from: `reset_fn(key, ordinal)`
    where the caller gave one (`ordinal`, the lane's completed-episode
    count before this episode's end was counted, is the async
    collectors' group-shared reset-ordinal hook), else `core.reset`
    under `key`."""
    if reset_fn is not None:
        return reset_fn(key, ordinal)
    from . import core as _core

    return _core.reset(params, bank, key)


def _reseed_ended(
    params: EnvParams,
    bank: WorkloadBank,
    ls: LoopState,
    ended: jnp.ndarray,
    rng: jax.Array,
    reset_fn: Callable | None,
    lane_axis: str | None,
) -> LoopState:
    """Start a new episode on a lane whose episode `ended` in the drain
    just run: the fresh state, in DECIDE mode, everything else as the
    drain left it. The fresh state (`_fresh_episode`) is asked for at
    the episode count BEFORE the increment the tail made when the
    episode ended, as the per-micro-step reset of `_finish_micro_step`
    asks for it, under `rng`, what is left of the drain's key stream.

    The reset program runs under ONE predicate for the batch: `ended`
    reduced over `lane_axis`, the name the caller's `vmap` gave its lane
    axis, so under that `vmap` this stays a conditional and a row in
    which no lane ended pays nothing. The workload bank is an operand
    of the conditional, which is sound because the predicate is not
    lane-dependent. A per-lane predicate would become, under `vmap`, a
    select over both branches with the bank broadcast to every lane
    (the memory pass's `bank-broadcast` rule), so a caller without
    `lane_axis` gets the reset program and the select unconditionally,
    once a drain."""

    def reseed(ls: LoopState) -> LoopState:
        fresh = _fresh_episode(
            params, bank, rng, reset_fn, ls.episodes - 1
        )
        env = jax.tree_util.tree_map(
            lambda a, b: jnp.where(ended, a, b), fresh, ls.env
        )
        return ls.replace(
            env=env, mode=jnp.where(ended, M_DECIDE, ls.mode).astype(_i32)
        )

    if lane_axis is None:
        return reseed(ls)
    return lax.cond(lax.pmax(ended, lane_axis), reseed, lambda ls: ls, ls)


def apply_and_drain(
    params: EnvParams,
    bank: WorkloadBank,
    ls: LoopState,
    stage_idx: jnp.ndarray,
    num_exec: jnp.ndarray,
    rng: jax.Array,
    auto_reset: bool = False,
    event_bulk: bool = True,
    bulk_events: int = 8,
    fulfill_bulk: bool = True,
    bulk_cycles: int = 1,
    bulk_fused: bool = True,
    telemetry=None,
) -> tuple:
    """One PRECOMPUTED decision applied and drained to the next decision
    point, for ONE lane: `decide_micro_step` (commit or round-finish)
    followed by `drain_to_decision` (FULFILL leftovers + the whole
    inter-decision event run) — the serving-shaped unit of work the
    AOT decision service compiles (`sparksched_tpu/serve/`). It drives
    the same two primitives as the single-eval collectors' scan body
    (`trainers/rollout.py:_flat_collect_single_eval`), but is NOT that
    body: the collectors carry their discount reference across rows
    (an undecided lane keeps the previous decision's `t_ref`), while
    a served request always references the lane's wall time at entry —
    per-request accounting, there is no previous row to carry. The
    engine-level decision semantics shared with training are pinned by
    the decide/drain step-exactness tests, not by this wrapper.
    Returns `(ls, (decided, reward, dt, reset)[, telemetry])` —
    `reward`/`dt` accumulate over the decide step and the whole
    drain."""
    track = telemetry is not None
    # the first key is not used (the decide step draws nothing): the
    # drain keeps the second, so a served lane's stream is what it is
    _, k_drain = jax.random.split(rng)
    t_ref = ls.env.wall_time
    out = decide_micro_step(
        params, bank, ls, stage_idx, num_exec, fulfill_bulk,
        t_ref=t_ref, telemetry=telemetry,
    )
    if track:
        ls2, (decided, rw1, dt1, rs1), telemetry = out
    else:
        ls2, (decided, rw1, dt1, rs1) = out
    out = drain_to_decision(
        params, bank, ls2, k_drain, auto_reset, event_bulk,
        bulk_events, bulk_cycles, t_ref=t_ref, telemetry=telemetry,
        bulk_fused=bulk_fused,
    )
    if track:
        ls3, (rw2, dt2, rs2), telemetry = out
    else:
        ls3, (rw2, dt2, rs2) = out
    rec = (decided, rw1 + rw2, dt1 + dt2, rs1 | rs2)
    return (ls3, rec, telemetry) if track else (ls3, rec)


def run_flat(
    params: EnvParams,
    bank: WorkloadBank,
    policy_fn: Callable,
    rng: jax.Array,
    num_groups: int,
    state: EnvState | None = None,
    auto_reset: bool = True,
    compute_levels: bool = True,
    event_bulk: bool = True,
    bulk_events: int = 8,
    fulfill_bulk: bool = False,
    bulk_cycles: int = 1,
    loop_state: LoopState | None = None,
    telemetry=None,
    bulk_fused: bool = True,
) -> LoopState | tuple:
    """Scan `num_groups` micro-steps for one lane (vmap over lanes). Pass
    `loop_state`
    (instead of a freshly-reset `state`) to continue a previous run —
    bench chunks resume this way. With `telemetry` (an
    `obs.Telemetry`), the counters ride the scan carry and the call
    returns `(LoopState, Telemetry)`."""
    ls = init_loop_state(state) if loop_state is None else loop_state
    track = telemetry is not None

    def body(carry, _):
        if track:
            ls, k, tm = carry
        else:
            (ls, k), tm = carry, None
        k, sub = jax.random.split(k)
        out = micro_step(
            params, bank, policy_fn, ls, sub, auto_reset,
            compute_levels, event_bulk, bulk_events, fulfill_bulk,
            bulk_cycles, telemetry=tm, bulk_fused=bulk_fused,
        )
        ls, tm = out if track else (out, None)
        return ((ls, k, tm) if track else (ls, k)), None

    if track:
        (ls, _, telemetry), _ = lax.scan(
            body, (ls, rng, telemetry), None, length=num_groups
        )
        return ls, telemetry
    (ls, _), _ = lax.scan(body, (ls, rng), None, length=num_groups)
    return ls
