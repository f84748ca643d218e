"""The vectorized Spark scheduling simulator: pure reset/step functions.

Semantics mirror the reference `SparkSchedSimEnv`
(spark_sched_sim/spark_sched_sim.py) exactly — commitment rounds, executor
pools, backup scheduling, moving delays, wave-based task durations — but the
implementation is a branch-free-per-lane state machine over the SoA
`EnvState`, so `jax.vmap(step)` advances thousands of simulations per TPU
core and `lax.while_loop` replaces the Python event loop.

Action encoding: `stage_idx` is a *flat padded node index* j * max_stages + s
(or -1 for "no selection"), unlike the reference's index into the compacted
list of schedulable stages (spark_sched_sim.py:284). Adapters convert.
`num_exec` is 1-based like the raw reference env (1..num_executors).

Invalid actions (unschedulable stage, out-of-range executor counts) are
handled by clamping — selecting an unschedulable stage behaves like -1 and
executor counts are clipped to [1, num_committable] — where the reference
raises ValueError (:275-295). Under jit there is no raising; policies are
expected to respect the masks.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..config import EnvParams
from ..obs.telemetry import add as _tm_add
from ..workload.bank import WorkloadBank
from ..workload.sampling import (
    pack_duration_facts,
    sample_job_sequence,
    sample_task_duration,
)
from .state import (
    BIG_SEQ,
    EV_EXECUTOR_READY,
    EV_JOB_ARRIVAL,
    EV_TASK_FINISHED,
    INF,
    STAGE_SET_BITS,
    EnvState,
    empty_state,
    topo_levels,  # shared levels reduction (re-exported; observe/tests
    # and the golden property all use the single state.py copy)
)

_i32 = jnp.int32


def _onehot(n: int, e: jnp.ndarray) -> jnp.ndarray:
    return jnp.arange(n, dtype=_i32) == e


def _onehot2(j_cap: int, s_cap: int, j: jnp.ndarray, s: jnp.ndarray
             ) -> jnp.ndarray:
    """bool[j_cap, s_cap] mask selecting exactly (j, s); all-false when
    either index is out of range (e.g. -1 pool sentinels)."""
    return _onehot(j_cap, j)[:, None] & _onehot(s_cap, s)[None, :]


def _pick(oh: jnp.ndarray, x: jnp.ndarray, axis=None) -> jnp.ndarray:
    """`x` at the one position the mask `oh` marks, as a select-reduce:
    what `x[i]` reads where `oh` is `_onehot(n, i)` (`x[j, s]` under
    `_onehot2`; with `oh = oj[:, None]` and `axis=0`, the row `x[j]`),
    for every in-range index, whatever `x` holds (an `inf` included:
    nothing is multiplied). A flag is reduced by `any`, a number by a
    sum over zeros. An all-false mask (the one-hot of a -1 sentinel or
    of an index past the end) gives 0 / False where the indexed read
    wraps or clamps: every caller says why that value is masked.

    Why not the indexed read: under `jax.vmap` it is a gather, which
    the TPU serialises (1.1 to 2.2 us for 128 lanes, and a relayout of
    the index column beside it), while this fuses with the elementwise
    work around it; the one-hot is the one the caller's masked writes
    need anyway (PERF.md, PRs 50 and 51)."""
    if x.dtype == jnp.bool_:
        return (oh & x).any(axis)
    return jnp.where(oh, x, 0).sum(axis).astype(x.dtype)


# --------------------------------------------------------------------------
# schedulable-stage computation (reference :505-555)
# --------------------------------------------------------------------------


def find_schedulable(
    params: EnvParams, state: EnvState, source_job_id: jnp.ndarray
) -> jnp.ndarray:
    """bool[J,S]. A stage is schedulable iff its job passes the saturation
    filter (source job exempt), it is ready (unsaturated with all parents
    saturated), and it was not selected this round."""
    j_idx = jnp.arange(params.max_jobs, dtype=_i32)
    job_ok = state.job_active & (
        (j_idx == source_job_id)
        | (state.job_supply < params.num_executors)
    )
    # incremental caches replace the [J,S,S] reduction the reference's
    # Python version implies (stage_sat / unsat_parent_count are updated at
    # every demand mutation; golden recomputations checked in tests)
    sat = state.stage_sat
    ready = state.stage_exists & ~sat & (state.unsat_parent_count == 0)
    return job_ok[:, None] & ready & ~state.stage_selected


def _refresh_sat(state: EnvState, oj: jnp.ndarray, os_: jnp.ndarray,
                 enable: jnp.ndarray = True) -> EnvState:
    """Recompute saturation of stage (j,s) after a demand mutation and
    propagate the flip to its children's unsaturated-parent counts.
    The stage comes as the one-hots of its job and of its stage index
    (`oj` bool[J], `os_` bool[S]) that the caller built for its own
    writes; a caller whose job is the -1 sentinel hands an all-false
    `oj` and `enable=False`, and nothing changes.

    Written as masked whole-array selects rather than `.at[j, s]`
    scatters, and as picks (`_pick`) rather than `[j, s]` reads: under
    `jax.vmap` a batched scatter or gather is a serialized kernel,
    while broadcast+select fuses with the surrounding elementwise work.
    The stage's children are read off the packed parent sets of its
    job (`_children`), not off the [J,S,S] adjacency."""
    m2 = oj[:, None] & os_[None, :]
    new = _pick(m2, state.exec_demand) <= 0
    old = _pick(m2, state.stage_sat)
    # only existing stages count as unsaturated parents
    delta = jnp.where(
        enable & _pick(m2, state.stage_exists),
        new.astype(_i32) - old.astype(_i32),
        0,
    )
    children = _children(_job_parent_sets(state, oj), os_)
    return state.replace(
        stage_sat=jnp.where(m2 & enable, new, state.stage_sat),
        unsat_parent_count=state.unsat_parent_count
        - delta * (oj[:, None] & children[None, :]).astype(_i32),
    )


# --------------------------------------------------------------------------
# executor pool moves (reference executor_tracker + spark_sched_sim helpers)
# --------------------------------------------------------------------------


def _move_idle_from_pool(
    state: EnvState, pj: jnp.ndarray, ps: jnp.ndarray, mask: jnp.ndarray,
    opj: jnp.ndarray,
) -> EnvState:
    """_move_idle_executors (reference :745-782): no-op for the common pool
    and for unsaturated job pools; otherwise masked executors move to the
    common pool (job saturated — detaching them) or to the job pool (task
    reference intentionally retained, matching the reference's
    move_executor_to_pool which does not clear `executor.task`).
    `opj` is the caller's one-hot of `pj` over the jobs: all-false for
    the common pool's -1, where `sat` then reads False and is masked by
    `noop` (the indexed read took job 0's there, masked the same)."""
    sat = _pick(opj, state.job_saturated)
    noop = (pj < 0) | ((ps < 0) & ~sat)
    m = mask & ~noop
    to_common = m & sat
    return state.replace(
        exec_at_common=jnp.where(to_common, True, state.exec_at_common),
        exec_job=jnp.where(to_common, -1, state.exec_job),
        exec_stage=jnp.where(m, -1, state.exec_stage),
        exec_task_valid=jnp.where(
            to_common, False, state.exec_task_valid
        ),
    )


def _exec_location(state: EnvState, one_e: jnp.ndarray):
    """Pool key of the executor `one_e` marks (bool[N]): (-1,-1) for
    common; (job, stage|-1) else."""
    at_common = _pick(one_e, state.exec_at_common)
    pj = jnp.where(at_common, -1, _pick(one_e, state.exec_job))
    ps = jnp.where(at_common, -1, _pick(one_e, state.exec_stage))
    return pj, ps


# --------------------------------------------------------------------------
# task execution (reference :584-615)
#
# IMPORTANT STRUCTURAL CONSTRAINT: under `jax.vmap`, a `lax.cond`/`switch`
# with a lane-dependent predicate broadcasts EVERY operand — including
# closed-over constants like the workload bank's duration tables — across
# the batch (jax _cond_batching_rule: "we broadcast the input operands for
# simplicity"). At 1024+ lanes that materializes gigabytes. Therefore the
# event-loop machinery below is phase-split: conditional branches only
# touch `EnvState` and scalars, every event resolves to a small action
# descriptor (kind, executor, target stage), and the task-duration sample —
# the only bank access — happens UNCONDITIONALLY at loop-body top level,
# where it is an ordinary batched gather from the shared table.
# --------------------------------------------------------------------------

# move-request kinds produced by event phase-A handlers
RQ_NONE, RQ_START, RQ_MOVE = 0, 1, 2
# resolved action kinds consumed by _apply_action
A_NONE, A_START, A_SEND, A_IDLE, A_PARK = 0, 1, 2, 3, 4


# --------------------------------------------------------------------------
# backup scheduling (reference :784-845)
# --------------------------------------------------------------------------


def _find_backup_stage(params: EnvParams, state: EnvState,
                       own: jnp.ndarray, quirk_src: jnp.ndarray):
    """Greedy local-then-global search for a stage to absorb an executor
    (of job `own`, -1 for none)
    that arrived somewhere it is no longer needed. Reproduces the
    reference's `if not source_job_id` falsiness quirk (:521-522): when the
    executor's job id is 0, the saturation-filter exemption falls back to
    the tracker's source job *as it was when the reference would run this
    search* (`quirk_src` — phase-A handlers may update the tracked source
    before the search runs here)."""
    eff_src = jnp.where(own == 0, quirk_src, own)
    sched = find_schedulable(params, state, eff_src)
    j_cap, s_cap = sched.shape
    flat = sched.reshape(-1)
    pos = jnp.arange(j_cap * s_cap, dtype=_i32)
    job_of = pos // s_cap

    local = flat & (job_of == own)
    other = flat & (job_of != own)

    local_any = local.any()
    local_idx = jnp.argmax(local)
    other_any = other.any()
    other_idx = jnp.argmax(other)

    found = local_any | other_any
    idx = jnp.where(local_any, local_idx, other_idx)
    return found, idx // s_cap, idx % s_cap


# --------------------------------------------------------------------------
# executor -> stage movement resolution (reference :699-845)
# --------------------------------------------------------------------------


def _resolve_action(
    params: EnvParams, state: EnvState, req_kind: jnp.ndarray,
    e: jnp.ndarray, rj: jnp.ndarray, rs: jnp.ndarray,
    quirk_src: jnp.ndarray,
):
    """Resolve a phase-A move request into a concrete action. Pure mask
    arithmetic over the state; the reference's nested-branch version is
    _move_executor_to_stage (:784-845 saturated/backup layer) +
    _mets_inner send/start/park (:799-819)."""
    j = jnp.maximum(rj, 0)
    s = jnp.maximum(rs, 0)
    j_cap, s_cap = state.stage_remaining.shape
    # `e` may be a job's index (a job arrival's argument, a request of
    # RQ_NONE): clipped as `_apply_action` clips it, so that the pick
    # is the indexed read's clamp
    n = state.exec_job.shape[0]
    own = _pick(_onehot(n, jnp.clip(e, 0, n - 1)), state.exec_job)
    saturated = _pick(
        _onehot2(j_cap, s_cap, j, s), state.stage_remaining
    ) == 0
    found, bj, bs = _find_backup_stage(params, state, own, quirk_src)
    use_backup = saturated & found
    tj = jnp.where(use_backup, bj, j)
    ts = jnp.where(use_backup, bs, s)
    dead = saturated & ~found
    send = own != tj
    start = _pick(_onehot2(j_cap, s_cap, tj, ts), state.frontier)
    ak_move = jnp.where(
        dead, A_IDLE,
        jnp.where(send, A_SEND, jnp.where(start, A_START, A_PARK)),
    )
    ak = jnp.where(
        req_kind == RQ_MOVE, ak_move,
        jnp.where(req_kind == RQ_START, A_START, A_NONE),
    )
    tj = jnp.where(req_kind == RQ_MOVE, tj, j)
    ts = jnp.where(req_kind == RQ_MOVE, ts, s)
    return ak.astype(_i32), tj.astype(_i32), ts.astype(_i32)


def _apply_action(
    params: EnvParams, bank: WorkloadBank, state: EnvState,
    ak: jnp.ndarray, e: jnp.ndarray, tj: jnp.ndarray, ts: jnp.ndarray
) -> EnvState:
    """Apply a resolved action. The duration is sampled unconditionally
    here — the only bank access — so no conditional branch closes over the
    bank tables (see structural note above). The rng is advanced once per
    call regardless of the action kind.

    This is the hottest function in the engine (every micro-step and every
    event-loop iteration ends here), so instead of a `lax.switch` over
    START/SEND/IDLE/PARK branches full of `.at[e].set` scatters — under
    vmap every branch executes anyway and batched scatters serialize — the
    five action semantics (reference `_execute_next_task` :584-615,
    `_send_executor` :617-637, `_move_idle_executors` :745-782, park) are
    fused into one straight-line pass of masked whole-array selects, at
    most one update per state field."""
    rng, sub = jax.random.split(state.rng)
    n = state.exec_job.shape[0]
    j_cap, s_cap = state.stage_remaining.shape
    # the one-hots of this call's writes, which its reads pick with
    # too (`_pick`): (tj, ts) is in range, from `_resolve_action`
    one_e = _onehot(n, jnp.clip(e, 0, n - 1))
    oj = _onehot(j_cap, tj)
    os_ = _onehot(s_cap, ts)
    m2 = oj[:, None] & os_[None, :]

    num_local = (state.exec_job == tj).sum()
    dur = sample_task_duration(
        params, bank, jax.random.uniform(sub, (2,)),
        _pick(m2, state.duration_facts), _pick(oj, state.job_template),
        ts, num_local,
        _pick(one_e, state.exec_task_valid),
        _pick(one_e, state.exec_task_stage) == ts,
    )

    is_start = ak == A_START
    is_send = ak == A_SEND
    is_idle = ak == A_IDLE
    is_park = ak == A_PARK

    # IDLE = _move_idle_executors for the single executor e: no-op for the
    # common pool and unsaturated job pools; saturated job -> common pool
    # (at the common pool's -1 the pick reads False and `pj < 0` masks
    # it, as it masked job 0's under the indexed read)
    pj, ps = _exec_location(state, one_e)
    pool_sat = _pick(_onehot(j_cap, pj), state.job_saturated)
    idle_eff = is_idle & ~((pj < 0) | ((ps < 0) & ~pool_sat))
    idle_common = idle_eff & pool_sat

    # START/SEND bookkeeping read before any mutation
    seq = state.seq_counter
    old_job = _pick(one_e, state.exec_job)
    newly_saturated = is_start & (_pick(m2, state.stage_remaining) == 1)

    i32_ = lambda b: b.astype(_i32)  # noqa: E731
    m2_start = m2 & is_start

    state = state.replace(
        rng=rng,
        seq_counter=seq + i32_(is_start | is_send),
        # --- executor fields (single slot e) ---
        exec_stage=jnp.where(
            one_e & (is_start | is_send | idle_eff | is_park),
            jnp.where(is_start, ts, -1),
            state.exec_stage,
        ),
        exec_task_valid=jnp.where(
            one_e & (is_start | is_send | idle_common | is_park),
            is_start,
            state.exec_task_valid,
        ),
        exec_at_common=jnp.where(
            one_e & (is_send | idle_common),
            idle_common,
            state.exec_at_common,
        ),
        exec_job=jnp.where(
            one_e & (is_send | idle_common), -1, state.exec_job
        ),
        exec_moving=state.exec_moving | (one_e & is_send),
        exec_dst_job=jnp.where(one_e & is_send, tj, state.exec_dst_job),
        exec_dst_stage=jnp.where(
            one_e & is_send, ts, state.exec_dst_stage
        ),
        exec_arrive_time=jnp.where(
            one_e & is_send,
            state.wall_time + params.moving_delay,
            state.exec_arrive_time,
        ),
        exec_arrive_seq=jnp.where(
            one_e & is_send, seq, state.exec_arrive_seq
        ),
        exec_executing=state.exec_executing | (one_e & is_start),
        exec_task_stage=jnp.where(
            one_e & is_start, ts, state.exec_task_stage
        ),
        exec_finish_time=jnp.where(
            one_e & is_start,
            state.wall_time + dur,
            state.exec_finish_time,
        ),
        exec_finish_seq=jnp.where(
            one_e & is_start, seq, state.exec_finish_seq
        ),
        # --- job fields ---
        job_supply=state.job_supply
        + i32_(oj & is_send)
        - i32_(_onehot(j_cap, old_job) & is_send & (old_job >= 0)),
        job_saturated_stages=state.job_saturated_stages
        + i32_(oj & newly_saturated),
        # --- stage fields ---
        stage_remaining=state.stage_remaining - i32_(m2_start),
        stage_executing=state.stage_executing + i32_(m2_start),
        stage_duration=jnp.where(
            m2_start, dur, state.stage_duration
        ),
        moving_count=state.moving_count + i32_(m2 & is_send),
    )
    return _refresh_sat(state, oj, os_, enable=is_start | is_send)


# --------------------------------------------------------------------------
# commitments (reference executor_tracker.py:146-249)
# --------------------------------------------------------------------------


def _add_commitment(
    state: EnvState, n: jnp.ndarray, dj: jnp.ndarray, ds: jnp.ndarray
) -> EnvState:
    """Create n commitment slots from the current source pool to (dj, ds).
    Slots for an existing (src, dst) pair inherit its sequence number so
    `peek` preserves the reference's dict-insertion order."""
    src_j, src_s = state.source_job, state.source_stage
    match = (
        state.cm_valid
        & (state.cm_src_job == src_j)
        & (state.cm_src_stage == src_s)
        & (state.cm_dst_job == dj)
        & (state.cm_dst_stage == ds)
    )
    has_match = match.any()
    inherited = jnp.where(match, state.cm_seq, BIG_SEQ).min()
    seq = jnp.where(has_match, inherited, state.seq_counter)

    free = ~state.cm_valid
    take = free & (jnp.cumsum(free.astype(_i32)) <= n)

    j_cap, s_cap = state.commit_count.shape
    oj = _onehot(j_cap, dj)  # all-false when dj == -1
    os_ = _onehot(s_cap, ds)
    supply = state.job_supply + n * (oj & (dj != src_j)).astype(_i32)
    cc = state.commit_count + n * (oj[:, None] & os_[None, :]).astype(
        _i32
    )

    state = state.replace(
        seq_counter=state.seq_counter + jnp.where(has_match, 0, 1),
        job_supply=supply,
        commit_count=cc,
        cm_valid=state.cm_valid | take,
        cm_src_job=jnp.where(take, src_j, state.cm_src_job),
        cm_src_stage=jnp.where(take, src_s, state.cm_src_stage),
        cm_dst_job=jnp.where(take, dj, state.cm_dst_job),
        cm_dst_stage=jnp.where(take, ds, state.cm_dst_stage),
        cm_seq=jnp.where(take, seq, state.cm_seq),
    )
    return _refresh_sat(state, oj, os_, enable=dj >= 0)


def _commit_remaining(state: EnvState) -> EnvState:
    """reference :487-503 — commit uncommitted source executors to the
    common pool."""
    n = state.num_committable()
    return lax.cond(
        n > 0,
        lambda st: _add_commitment(st, n, _i32(-1), _i32(-1)),
        lambda st: st,
        state,
    )


def _peek_commitment(state: EnvState, pj: jnp.ndarray, ps: jnp.ndarray):
    """First outgoing commitment from pool (pj, ps) in insertion order
    (reference executor_tracker.py:175-181). Returns (exists, slot)."""
    match = (
        state.cm_valid
        & (state.cm_src_job == pj)
        & (state.cm_src_stage == ps)
    )
    key = jnp.where(match, state.cm_seq, BIG_SEQ)
    return match.any(), jnp.argmin(key)


def _fulfill_commitment_phase_a(
    state: EnvState, e: jnp.ndarray, slot: jnp.ndarray
):
    """reference :699-712 — consume one commitment slot with executor e.
    Pure bookkeeping + move request; the actual move is resolved/applied by
    the caller (see structural note above). Returns
    (state, req_kind, rj, rs)."""
    n = state.cm_valid.shape[0]
    j_cap, s_cap = state.commit_count.shape
    # `slot` is a slot's index (`_peek_commitment`'s argmin, an entry
    # of `slot_order`), so the picks are the indexed reads
    oslot = _onehot(n, slot)
    dj = _pick(oslot, state.cm_dst_job)
    ds = _pick(oslot, state.cm_dst_stage)
    sj = _pick(oslot, state.cm_src_job)
    oj = _onehot(j_cap, dj)  # all-false when dj == -1
    os_ = _onehot(s_cap, ds)
    m2 = oj[:, None] & os_[None, :]
    state = state.replace(
        cm_valid=state.cm_valid & ~oslot,
        job_supply=state.job_supply - (oj & (dj != sj)).astype(_i32),
        commit_count=state.commit_count - m2.astype(_i32),
    )
    state = _refresh_sat(state, oj, os_, enable=dj >= 0)

    def to_common(st: EnvState):
        one_e = _onehot(n, e)
        pj, ps = _exec_location(st, one_e)
        st = _move_idle_from_pool(st, pj, ps, one_e, _onehot(j_cap, pj))
        return st, _i32(RQ_NONE), _i32(-1), _i32(-1)

    def to_stage(st: EnvState):
        return st, _i32(RQ_MOVE), dj, ds

    return lax.cond(dj < 0, to_common, to_stage, state)


def _exec_scatter(sel):
    """Masked per-executor scatter helpers over a [candidate, executor]
    selection matrix in which every executor is selected at most once
    (shared by the bulk passes)."""

    def exset(base, cond, payload):
        msel = sel & cond[:, None]
        val = jnp.where(msel, payload[:, None], 0).sum(0)
        return jnp.where(msel.any(0), val.astype(base.dtype), base)

    def exflag(base, cond, value):
        return jnp.where((sel & cond[:, None]).any(0), value, base)

    return exset, exflag


def _bulk_fulfill(
    params: EnvParams, bank: WorkloadBank, state: EnvState,
    num_idle: jnp.ndarray, exec_order: jnp.ndarray,
    slot_order: jnp.ndarray,
):
    """Consume the maximal *simple* prefix of the fulfillment phase in
    one vectorized pass. Returns (state, m): candidates 0..m-1 of the
    (exec_order, slot_order) pairing are fully processed; the caller
    finishes the rest (backup-scheduling cases) on the one-at-a-time
    path.

    Each executor is fulfilled at most once per phase, so unlike the
    relaunch cascade there is no sequential generation structure: the
    only cross-candidate coupling is through per-stage/per-job counters
    (unlaunched-task counts, saturated-stage counts, executor-on-job
    counts), all reconstructible per candidate with N^2 prefix sums.
    A candidate is *simple* — its classification is static — iff its
    commitment targets the common pool (dj < 0) or its destination
    stage still has unlaunched tasks at its turn (rem0 minus earlier
    prefix starts > 0, the `_resolve_action` unsaturated case, which
    resolves to A_SEND / A_START / A_PARK by static facts: executor's
    job vs destination, destination frontier membership). The prefix
    stops at the first saturated-destination candidate, whose
    backup-stage search depends on the live saturation caches.

    Matches the sequential path bit-exactly except the rng stream
    (per-candidate pre-derived keys, as in `_bulk_relaunch`).
    """
    n = state.exec_job.shape[0]
    j_cap, s_cap = state.stage_remaining.shape
    pos = jnp.arange(n, dtype=_i32)

    e = exec_order
    slot = slot_order
    dj = state.cm_dst_job[slot]
    ds0 = state.cm_dst_stage[slot]
    sjs = state.cm_src_job[slot]
    ejob = state.exec_job[e]
    djc = jnp.clip(dj, 0, j_cap - 1)
    dsc = jnp.clip(ds0, 0, s_cap - 1)

    valid = pos < num_idle
    common_dst = dj < 0
    send0 = ~common_dst & (ejob != dj)
    frontier_k = _frontier_at(state, dj, ds0)
    start0 = ~common_dst & ~send0 & frontier_k
    park0 = ~common_dst & ~send0 & ~frontier_k

    flat = djc * s_cap + dsc
    stage_pair = (
        (flat[None, :] == flat[:, None])
        & ~common_dst[None, :]
        & ~common_dst[:, None]
    )
    earlier = pos[None, :] < pos[:, None]
    cum_starts = (earlier & stage_pair & start0[None, :]).sum(-1)
    rem0 = state.stage_remaining[djc, dsc]
    saturated = ~common_dst & (rem0 - cum_starts == 0)
    ok = valid & ~saturated
    prefix = (jnp.cumsum((~ok).astype(_i32)) == 0) & valid
    m = prefix.sum().astype(_i32)

    send = send0 & prefix
    start = start0 & prefix
    park = park0 & prefix
    common_k = common_dst & prefix

    # source-pool saturation at each candidate's turn: starts that
    # launch a destination stage's last task bump the destination job's
    # saturated-stage count, which a later dj<0 candidate's
    # _move_idle_from_pool reads for the SOURCE job
    src_j = state.source_job
    src_s = state.source_stage
    newly_exh = start & (rem0 - cum_starts == 1)
    exh_src_before = (
        earlier & (newly_exh & (dj == src_j))[None, :]
    ).sum(-1)
    src_jc = jnp.maximum(src_j, 0)
    src_sat_k = (
        state.job_saturated_stages[src_jc] + exh_src_before
    ) >= state.job_num_stages[src_jc]
    noop_move = (src_j < 0) | ((src_s < 0) & ~src_sat_k)
    to_common = common_k & ~noop_move & src_sat_k
    moved_any = common_k & ~noop_move  # to common OR up to the job pool

    # executor-on-destination-job count at each candidate's turn (the
    # duration model's executor-level input): earlier sends/common
    # moves detach executors from the source job
    leaver = (send | to_common) & (ejob >= 0)
    leavers_before = (earlier & leaver[None, :]).sum(-1)
    base_nl = (state.exec_job[None, :] == dj[:, None]).sum(-1)
    nl = base_nl - jnp.where(dj == src_j, leavers_before, 0)

    rng_next, sub = jax.random.split(state.rng)
    # one batched draw for the whole pass (rows were independently
    # keyed via per-row fold_in before; independent uniforms now — see
    # sample_task_duration's docstring for the round-5 measurement)
    us = jax.random.uniform(sub, (pos.shape[0], 2))
    tpl = state.job_template[djc]
    tv = state.exec_task_valid[e]
    ss_same = state.exec_task_stage[e] == ds0
    durs = jax.vmap(
        lambda u2, f_, tp, s_, nl_, tv_, sm_: sample_task_duration(
            params, bank, u2, f_, tp, s_, nl_, tv_, sm_,
        )
    )(us, state.duration_facts[djc, dsc], tpl, dsc, nl, tv, ss_same)

    inc = (start | send).astype(_i32)
    seq_k = state.seq_counter + (earlier & (inc[None, :] > 0)).sum(-1)
    n_inc = inc.sum()

    fin_k = state.wall_time + durs
    arr_k = jnp.full(
        (n,), state.wall_time + params.moving_delay, jnp.float32
    )

    # ---- per-executor scatters (each candidate's executor is unique)
    sel = prefix[:, None] & (e[:, None] == pos[None, :])  # [cand, exec]
    exset, exflag = _exec_scatter(sel)

    minus1 = jnp.full((n,), -1, _i32)
    exec_stage = exset(
        state.exec_stage, start | send | park | moved_any,
        jnp.where(start, ds0, minus1),
    )
    exec_task_valid = exflag(
        exflag(state.exec_task_valid, send | park | to_common, False),
        start, True,
    )
    exec_at_common = exflag(
        exflag(state.exec_at_common, send, False), to_common, True
    )
    exec_job = exset(state.exec_job, send | to_common, minus1)
    exec_moving = exflag(state.exec_moving, send, True)
    exec_dst_job = exset(state.exec_dst_job, send, dj)
    exec_dst_stage = exset(state.exec_dst_stage, send, ds0)
    exec_arrive_time = exset(state.exec_arrive_time, send, arr_k)
    exec_arrive_seq = exset(state.exec_arrive_seq, send, seq_k)
    exec_executing = exflag(state.exec_executing, start, True)
    exec_task_stage = exset(state.exec_task_stage, start, ds0)
    exec_finish_time = exset(state.exec_finish_time, start, fin_k)
    exec_finish_seq = exset(state.exec_finish_seq, start, seq_k)

    # ---- commitment slots (every prefix candidate consumes one)
    consumed = (
        prefix[:, None] & (slot[:, None] == pos[None, :])
    ).any(0)
    cm_valid = state.cm_valid & ~consumed

    # ---- per-stage counters (destination stages)
    oh_j = (
        (dj[:, None] == jnp.arange(j_cap, dtype=_i32)[None, :])
        & prefix[:, None]
        & ~common_dst[:, None]
    )  # [cand, J]
    oh_s = ds0[:, None] == jnp.arange(s_cap, dtype=_i32)[None, :]
    m3 = oh_j[:, :, None] & oh_s[:, None, :]  # [cand, J, S]
    cnt_start = (m3 & start[:, None, None]).sum(0).astype(_i32)
    cnt_send = (m3 & send[:, None, None]).sum(0).astype(_i32)
    cnt_slot = m3.sum(0).astype(_i32)
    stage_remaining = state.stage_remaining - cnt_start
    stage_executing = state.stage_executing + cnt_start
    moving_count = state.moving_count + cnt_send
    commit_count = state.commit_count - cnt_slot

    later = pos[None, :] > pos[:, None]
    is_last_start = start & ~(
        later & stage_pair & start[None, :]
    ).any(-1)
    dur_js = (
        (m3 & is_last_start[:, None, None]) * durs[:, None, None]
    ).sum(0)
    stage_duration = jnp.where(
        cnt_start > 0, dur_js, state.stage_duration
    )

    # ---- per-job counters
    job_supply = (
        state.job_supply
        - (oh_j & (dj != sjs)[:, None]).sum(0)  # slot consumption
        + (oh_j & send[:, None]).sum(0)  # arrivals in transit
        - _onehot(j_cap, src_jc).astype(_i32)
        * jnp.where(src_j >= 0, (send & (ejob >= 0)).sum(), 0)
    )
    job_saturated_stages = (
        state.job_saturated_stages
        + (oh_j & newly_exh[:, None]).sum(0).astype(_i32)
    )

    # ---- saturation-cache refresh for every touched destination stage
    aff = cnt_slot > 0
    demand = stage_remaining - moving_count - commit_count
    sat_new = demand <= 0
    is_rep = prefix & ~common_dst & ~(
        earlier & stage_pair
    ).any(-1)
    delta_k = jnp.where(
        is_rep & state.stage_exists[djc, dsc],
        sat_new[djc, dsc].astype(_i32)
        - state.stage_sat[djc, dsc].astype(_i32),
        0,
    )
    adj_row = state.adj[djc, dsc]  # [cand, S]
    unsat = state.unsat_parent_count - (
        oh_j[:, :, None]
        * (delta_k[:, None] * adj_row.astype(_i32))[:, None, :]
    ).sum(0)

    bulked = m > 0
    state = state.replace(
        rng=jnp.where(bulked, rng_next, state.rng),
        seq_counter=state.seq_counter + n_inc,
        exec_stage=exec_stage,
        exec_task_valid=exec_task_valid,
        exec_at_common=exec_at_common,
        exec_job=exec_job,
        exec_moving=exec_moving,
        exec_dst_job=exec_dst_job,
        exec_dst_stage=exec_dst_stage,
        exec_arrive_time=exec_arrive_time,
        exec_arrive_seq=exec_arrive_seq,
        exec_executing=exec_executing,
        exec_task_stage=exec_task_stage,
        exec_finish_time=exec_finish_time,
        exec_finish_seq=exec_finish_seq,
        cm_valid=cm_valid,
        stage_remaining=stage_remaining,
        stage_executing=stage_executing,
        moving_count=moving_count,
        commit_count=commit_count,
        stage_duration=stage_duration,
        job_supply=job_supply,
        job_saturated_stages=job_saturated_stages,
        stage_sat=jnp.where(aff, sat_new, state.stage_sat),
        unsat_parent_count=unsat,
    )
    return state, m


def _fulfill_from_source(
    params: EnvParams, bank: WorkloadBank, state: EnvState,
    active: jnp.ndarray, bulk: bool = True, telem=None
):
    """reference :730-743 — match the source pool's idle executors against
    its outstanding commitments, in commitment insertion order. `active`
    masks the whole call (used to fold the reference's round-finished
    branch into straight-line code). With `bulk`, the simple prefix of
    the phase is consumed in one `_bulk_fulfill` pass and only the
    backup-scheduling tail (usually empty) runs the per-candidate
    while-loop — under vmap the loop runs the batch-max LEFTOVER count
    instead of a fixed N iterations. With `telem` (an `obs.Telemetry`),
    returns `(state, telem)` with bulk hits and per-candidate
    fulfillments counted; the None path threads nothing."""
    track = telem is not None
    n = state.exec_job.shape[0]
    idle = state.source_pool_mask() & ~state.exec_executing
    num_idle = jnp.where(active, idle.sum(), 0)

    exec_order = _rank_order(
        jnp.where(idle, jnp.arange(n, dtype=_i32), BIG_SEQ)
    )
    match = (
        state.cm_valid
        & (state.cm_src_job == state.source_job)
        & (state.cm_src_stage == state.source_stage)
    )
    slot_order = _rank_order(jnp.where(match, state.cm_seq, BIG_SEQ))

    if bulk:
        state, k0 = _bulk_fulfill(
            params, bank, state, num_idle, exec_order, slot_order
        )
        if track:
            telem = _tm_add(telem, bulk_fulfill_hits=k0)
    else:
        k0 = _i32(0)

    def cond(carry):
        return carry[0] < num_idle

    def body(carry):
        if track:
            k, st, tm = carry
        else:
            k, st = carry
        ok = _onehot(n, k)  # k < num_idle <= n
        e = _pick(ok, exec_order)
        quirk_src = st.source_job_id()
        st, rk, rj, rs = _fulfill_commitment_phase_a(
            st, e, _pick(ok, slot_order)
        )
        ak, tj, ts = _resolve_action(
            params, st, rk, e, rj, rs, quirk_src
        )
        st = _apply_action(params, bank, st, ak, e, tj, ts)
        if track:
            return k + 1, st, _tm_add(tm, fulfill_steps=1)
        return k + 1, st

    if track:
        _, state, telem = lax.while_loop(
            cond, body, (k0, state, telem)
        )
        return state, telem
    _, state = lax.while_loop(cond, body, (k0, state))
    return state


# --------------------------------------------------------------------------
# node levels for the GNN (active-subgraph topological generations)
# --------------------------------------------------------------------------


def _job_topo_levels(active_s: jnp.ndarray, adj_s: jnp.ndarray
                     ) -> jnp.ndarray:
    """i32[S] topological generation of one job's active nodes in the
    masked [S,S] subgraph; padding = S. Single-job form of `topo_levels`,
    used by the incremental `state.node_level` maintenance — an S-bounded
    pass over one job instead of the [J,S,S] all-jobs reduction."""
    s_cap = active_s.shape[0]

    def body(_, lvl):
        cand = jnp.where(adj_s, lvl[:, None] + 1, 0).max(axis=0)
        return jnp.maximum(lvl, cand)

    lvl = lax.fori_loop(0, s_cap, body, jnp.zeros(active_s.shape, _i32))
    return jnp.where(active_s, lvl, s_cap)


def compute_node_levels(params: EnvParams, state: EnvState) -> jnp.ndarray:
    """Active-subgraph topological generations (completed stages and
    inactive jobs excluded — the same node set as the observation's
    `node_mask`, so an Observation rebuilt from a stored rollout step is
    bit-identical to the live one). Since round 8 this full [J,S,S]
    recomputation is the GOLDEN reference only: `observe` reads the
    state-maintained `node_level` cache, updated per stage completion
    (`_handle_task_finished`) with a single-job `_job_topo_levels` pass."""
    active = (
        state.job_active[:, None]
        & state.stage_exists
        & ~state.stage_completed
    )
    adj_act = state.adj & active[:, :, None] & active[:, None, :]
    return topo_levels(active, adj_act)


# --------------------------------------------------------------------------
# event handlers (reference :426-483)
# --------------------------------------------------------------------------


def _handle_job_arrival(state: EnvState, j: jnp.ndarray):
    state = state.replace(
        job_arrived=state.job_arrived
        | _onehot(state.job_arrived.shape[0], j)
    )
    has_common = state.exec_at_common.any()
    state = state.replace(
        source_valid=state.source_valid | has_common,
        source_job=jnp.where(has_common, -1, state.source_job),
        source_stage=jnp.where(has_common, -1, state.source_stage),
    )
    return state, _i32(RQ_NONE), _i32(-1), _i32(-1)


def _handle_executor_ready(state: EnvState, e: jnp.ndarray):
    n = state.exec_job.shape[0]
    j_cap, s_cap = state.moving_count.shape
    # under `vmap` every handler runs for every lane: where the event
    # is a job's arrival `e` is a job's index, an `e` past the last
    # executor picks 0 where the read clamped, and the switch discards
    # the branch either way
    one_e = _onehot(n, e)
    j = _pick(one_e, state.exec_dst_job)
    s = _pick(one_e, state.exec_dst_stage)
    oj = _onehot(j_cap, j)
    os_ = _onehot(s_cap, s)
    m2 = oj[:, None] & os_[None, :]
    state = state.replace(
        moving_count=state.moving_count - m2.astype(_i32),
        exec_moving=state.exec_moving & ~one_e,
        exec_arrive_time=jnp.where(one_e, INF, state.exec_arrive_time),
        exec_at_common=state.exec_at_common & ~one_e,
        exec_job=jnp.where(one_e, j, state.exec_job),
        exec_stage=jnp.where(one_e, -1, state.exec_stage),
    )
    state = _refresh_sat(state, oj, os_)
    return state, _i32(RQ_MOVE), j, s


def _handle_task_finished(state: EnvState, e: jnp.ndarray):
    n = state.exec_job.shape[0]
    j_cap, s_cap = state.stage_executing.shape
    # as in `_handle_executor_ready`: where this is not the event's
    # handler `e` may mark no executor, or one with no job (-1), the
    # picks below then read 0 / False where the reads clamped or
    # wrapped, and the switch discards the branch
    one_e = _onehot(n, e)
    j = _pick(one_e, state.exec_job)
    s = _pick(one_e, state.exec_task_stage)
    oj = _onehot(j_cap, j)
    os_ = _onehot(s_cap, s)
    m2 = oj[:, None] & os_[None, :]
    frontier_before = _pick(oj[:, None], state.frontier, axis=0)

    state = state.replace(
        stage_executing=state.stage_executing - m2.astype(_i32),
        stage_completed_tasks=state.stage_completed_tasks
        + m2.astype(_i32),
        exec_executing=state.exec_executing & ~one_e,
        exec_finish_time=jnp.where(one_e, INF, state.exec_finish_time),
    )

    def more_tasks(st: EnvState):
        return st, _i32(RQ_START), j, s

    def released(st: EnvState):
        stage_done = _pick(m2, st.stage_completed)
        # job j's adjacency, from its packed parent sets (a pick over
        # the jobs of a [J,S]-sized operand: nothing here reads the
        # [J,S,S] adjacency)
        adj_j = _unpack_parents(_job_parent_sets(st, oj), s_cap)
        # maintain the frontier cache: one fewer incomplete parent for
        # every child of a completed stage
        children = _pick(os_[:, None], adj_j, axis=0)
        st = st.replace(
            incomplete_parent_count=st.incomplete_parent_count
            - (stage_done & oj[:, None] & children[None, :]).astype(
                _i32
            )
        )
        # maintain the node-level cache: the completed stage leaves job
        # j's active subgraph, so recompute THAT job's row only (stage
        # completion is the sole mutation point — the bulk passes only
        # launch tasks and can never complete a stage)
        act_row = _pick(
            oj[:, None], st.stage_exists & ~st.stage_completed, axis=0
        )
        adj_row = adj_j & act_row[:, None] & act_row[None, :]
        lvl_row = _job_topo_levels(act_row, adj_row)
        st = st.replace(
            node_level=jnp.where(
                stage_done & oj[:, None], lvl_row[None, :],
                st.node_level,
            )
        )
        new_frontier = (
            _pick(oj[:, None], st.frontier, axis=0) & ~frontier_before
        )
        did_change = stage_done & new_frontier.any()
        job_done = _pick(oj, st.job_completed)

        def complete_job(st: EnvState) -> EnvState:
            pool = st.pool_member_mask(j, _i32(-1)) & ~st.exec_executing
            st = _move_idle_from_pool(st, j, _i32(-1), pool, oj)
            return st.replace(
                job_t_completed=jnp.where(
                    oj, st.wall_time, st.job_t_completed
                )
            )

        st = lax.cond(
            job_done & jnp.isinf(_pick(oj, st.job_t_completed)),
            complete_job, lambda s2: s2, st,
        )

        has_cm, slot = _peek_commitment(st, j, s)

        def fulfill(st: EnvState):
            return _fulfill_commitment_phase_a(st, e, slot)

        def no_cm(st: EnvState):
            st = st.replace(
                exec_task_valid=st.exec_task_valid & ~one_e
            )
            st = lax.cond(
                did_change,
                lambda s2: _move_idle_from_pool(s2, j, s, one_e, oj),
                lambda s2: s2,
                st,
            )
            return st, _i32(RQ_NONE), _i32(-1), _i32(-1)

        st, rk, rj, rs = lax.cond(has_cm, fulfill, no_cm, st)

        # _update_executor_source (reference :662-674)
        set_job_pool = did_change
        set_stage_pool = ~did_change & ~has_cm
        any_set = set_job_pool | set_stage_pool
        st = st.replace(
            source_valid=st.source_valid | any_set,
            source_job=jnp.where(any_set, j, st.source_job),
            source_stage=jnp.where(
                set_job_pool, -1,
                jnp.where(set_stage_pool, s, st.source_stage),
            ),
        )
        return st, rk, rj, rs

    return lax.cond(
        _pick(m2, state.stage_remaining) > 0, more_tasks, released, state
    )


# --------------------------------------------------------------------------
# event selection + simulation loop (reference :320-343 + event.py)
# --------------------------------------------------------------------------


def _next_event(params: EnvParams, state: EnvState):
    """Lexicographic (time, seq) argmin over all pending events."""
    t_job = jnp.where(state.job_arrived, INF, state.job_arrival_time)
    times = jnp.concatenate(
        [t_job, state.exec_finish_time, state.exec_arrive_time]
    )
    seqs = jnp.concatenate(
        [state.job_arrival_seq, state.exec_finish_seq,
         state.exec_arrive_seq]
    )
    tmin = times.min()
    has = jnp.isfinite(tmin)
    cand = times == tmin
    idx = jnp.argmin(jnp.where(cand, seqs, BIG_SEQ))
    j_cap = params.max_jobs
    n = params.num_executors
    kind = jnp.where(
        idx < j_cap,
        EV_JOB_ARRIVAL,
        jnp.where(idx < j_cap + n, EV_TASK_FINISHED, EV_EXECUTOR_READY),
    )
    arg = jnp.where(
        idx < j_cap,
        idx,
        jnp.where(idx < j_cap + n, idx - j_cap, idx - j_cap - n),
    )
    return has, tmin, kind, arg


def _has_pending_event(state: EnvState) -> jnp.ndarray:
    """Cheap existence bit of `_next_event` — drain/resume loop conds
    need only "is anything pending", not the (kind, arg) argmin chain
    (the ISSUE-7 cheap-cond restructure)."""
    t = jnp.minimum(
        jnp.where(state.job_arrived, INF, state.job_arrival_time).min(),
        jnp.minimum(
            state.exec_finish_time.min(), state.exec_arrive_time.min()
        ),
    )
    return jnp.isfinite(t)


def _rank_order(key: jnp.ndarray) -> jnp.ndarray:
    """Stable ascending order of `key` as an index array — the
    `jnp.argsort(..., stable=True)` contract (ties break by index) —
    via an N x N pairwise rank matrix instead of a sort primitive: for
    the engine's N-sized keys a batched sort kernel costs far more than
    these few elementwise reduces."""
    n = key.shape[0]
    pos = jnp.arange(n, dtype=_i32)
    lt = (key[None, :] < key[:, None]) | (
        (key[None, :] == key[:, None]) & (pos[None, :] < pos[:, None])
    )
    rank = lt.sum(-1)
    perm = rank[None, :] == pos[:, None]
    return jnp.where(perm, pos[None, :], 0).sum(-1).astype(_i32)


def _bulk_relaunch(
    params: EnvParams, bank: WorkloadBank, state: EnvState,
    enabled: jnp.ndarray, stop_at_limit: bool = False,
    max_events: int = 8,
):
    """Pop up to `max_events` consecutive *task relaunch* events in one
    pass. Returns (state, k) with k the number of events consumed (0
    when the next event is not a relaunch, the queue is drained, or
    `enabled` is False — callers fall back to the single-event path).

    A relaunch is a TASK_FINISHED event on a stage that still has
    unlaunched tasks at processing time (`stage_remaining > 0`): the
    executor immediately launches the stage's next task
    (`_handle_task_finished`'s more_tasks path resolving to A_START).
    These are by far the most common events (one per task, 100s per
    stage). Two facts make a whole run of them processable in one
    micro-step:

    - the source pool is always empty while events are being popped
      (`clear_round`/`move_and_clear` precede every pop), so
      `num_committable() == 0` and `round_ready` cannot flip mid-run
      even when a relaunch saturates a parent stage and readies its
      children; relaunches touch no pools, commitments, sources or
      frontiers;
    - an executor only ever relaunches on its OWN stage, so the whole
      cascade's evolving state is N-sized: per-executor pending
      (time, seq), a shared per-stage remaining-task view, launch
      counts, and the per-stage last duration.

    The cascade is replayed in EXACT sequential order by a bounded
    `lax.scan`: each step picks the lexicographic (time, seq) minimum
    pending finish — the same tie-break as `_next_event` — checks the
    handler's relaunch condition against the live remaining view, and
    relaunches with a pre-sampled duration and the exact sequential
    seq-counter value. Newly generated events participate in later
    steps, so ordering (including ties against competitors and among
    generated events) is bit-identical to the one-event path; only the
    rng STREAM differs (each potential event has its own pre-derived
    key), which the engine does not promise for stochastic banks.

    The scan stops at the first event that is not a relaunch — a
    non-finish event with an earlier (time, seq), or a finish on a
    stage whose unlaunched tasks the run exhausted — leaving it
    pending for the single-event path. With `stop_at_limit` (the flat
    engine's per-micro-step episode-end check) it also stops right
    after the first event at or past the episode time limit, where
    that engine freezes/resets. A run longer than `max_events`
    resumes on the next micro-step: the cascade state is always
    consistent.
    """
    n = state.exec_finish_time.shape[0]
    j_cap, s_cap = state.stage_remaining.shape
    pos = jnp.arange(n, dtype=_i32)

    # earliest non-finish competitor, lexicographic (time, seq)
    t_job = jnp.where(state.job_arrived, INF, state.job_arrival_time)
    jt = t_job.min()
    jseq = jnp.where(t_job == jt, state.job_arrival_seq, BIG_SEQ).min()
    at = state.exec_arrive_time.min()
    aseq = jnp.where(
        state.exec_arrive_time == at, state.exec_arrive_seq, BIG_SEQ
    ).min()
    t_star = jnp.minimum(jt, at)
    seq_star = jnp.minimum(
        jnp.where(jt == t_star, jseq, BIG_SEQ),
        jnp.where(at == t_star, aseq, BIG_SEQ),
    )

    # static per-executor facts for the whole cascade: stage identity,
    # same-stage sharing, job-local executor count (for the duration
    # model's executor-level interpolation)
    je = state.exec_job
    se = state.exec_task_stage
    executing = jnp.isfinite(state.exec_finish_time)
    jc = jnp.clip(je, 0, j_cap - 1)
    sc = jnp.clip(se, 0, s_cap - 1)
    same = (
        (je[:, None] == je[None, :])
        & (se[:, None] == se[None, :])
        & executing[:, None]
        & executing[None, :]
    )
    num_local = (je[None, :] == je[:, None]).sum(-1)
    tpl = state.job_template[jc]

    # pre-sampled durations: dur_table[i, e] is the draw consumed if
    # the i-th processed event belongs to executor e. Each (i, e) key
    # is independent and the selection of e at step i depends only on
    # draws from earlier steps, so the consumed draws are i.i.d. from
    # the correct per-stage distribution; unconsumed draws are
    # discarded. Deterministic banks (the parity fixtures) are
    # unaffected. rng advances once iff the bulk fires.
    rng_next, sub = jax.random.split(state.rng)
    # one batched draw for the whole table (per-row fold_in keys
    # before; independent uniforms now — sample_task_duration docstring)
    us = jax.random.uniform(sub, (max_events * n, 2))
    e_rep = jnp.tile(pos, max_events)
    facts = state.duration_facts[jc, sc]
    dur_table = jax.vmap(
        lambda u2, e: sample_task_duration(
            params, bank, u2, facts[e], tpl[e], sc[e], num_local[e],
            jnp.bool_(True), jnp.bool_(True),
        )
    )(us, e_rep).reshape(max_events, n)

    def step_fn(carry, dur_row):
        t_e, sq_e, rem_e, k_e, ldur_e, counter, wall, active, crossed \
            = carry
        tmin = t_e.min()
        has = jnp.isfinite(tmin)
        cand = t_e == tmin
        smin = jnp.where(cand, sq_e, BIG_SEQ).min()
        e_oh = cand & (sq_e == smin)  # unique among pending finishes
        before = (tmin < t_star) | ((tmin == t_star) & (smin < seq_star))
        rem_i = jnp.where(e_oh, rem_e, 0).sum()
        ok = active & has & before & (rem_i > 0)
        if stop_at_limit:
            ok = ok & ~crossed
            crossed = crossed | (ok & (tmin >= state.time_limit))
        srow = (e_oh[:, None] & same).any(0)  # e*'s same-stage row
        dur_i = jnp.where(e_oh, dur_row, 0.0).sum()
        t_e = jnp.where(ok & e_oh, tmin + dur_i, t_e)
        sq_e = jnp.where(ok & e_oh, counter, sq_e)
        rem_e = rem_e - (ok & srow).astype(_i32)
        k_e = k_e + (ok & e_oh).astype(_i32)
        ldur_e = jnp.where(ok & srow, dur_i, ldur_e)
        counter = counter + ok.astype(_i32)
        wall = jnp.where(ok, tmin, wall)
        active = active & ok  # sequential order: first rejection stops
        return (
            t_e, sq_e, rem_e, k_e, ldur_e, counter, wall, active,
            crossed,
        ), None

    carry0 = (
        state.exec_finish_time,
        state.exec_finish_seq,
        state.stage_remaining[jc, sc],
        jnp.zeros(n, _i32),
        jnp.zeros(n, jnp.float32),
        state.seq_counter,
        state.wall_time,
        jnp.asarray(enabled, bool),
        jnp.bool_(False),
    )
    (t_e, sq_e, rem_e, k_e, ldur_e, counter, wall, _, _), _ = lax.scan(
        step_fn, carry0, dur_table
    )
    k = k_e.sum()
    bulked = k > 0
    touched = k_e > 0

    # one representative executor per touched stage (same-stage views
    # are kept consistent by the scan, so any member would do; pick the
    # minimal index to scatter each stage exactly once)
    first_touched = jnp.where(same & touched[None, :], pos[None, :], n
                              ).min(-1)
    rep = touched & (pos == first_touched)

    # per-representative stage quantities (all [N]-sized + gathers)
    cnt_i = ((same & touched[None, :]) * k_e[None, :]).sum(-1)
    exhausted_i = rep & (rem_e == 0)
    demand_i = (
        rem_e - state.moving_count[jc, sc] - state.commit_count[jc, sc]
    )
    sat_new_i = demand_i <= 0
    delta_i = jnp.where(
        rep & state.stage_exists[jc, sc],
        sat_new_i.astype(_i32) - state.stage_sat[jc, sc].astype(_i32),
        0,
    )
    adj_row = state.adj[jc, sc]  # [N, S] children of each rep's stage

    # scatter into [J,S] through rep-masked payload reduces
    oh_j = je[:, None] == jnp.arange(j_cap, dtype=_i32)[None, :]
    oh_s = se[:, None] == jnp.arange(s_cap, dtype=_i32)[None, :]
    m = oh_j[:, :, None] & oh_s[:, None, :] & rep[:, None, None]
    cnt = (m * cnt_i[:, None, None]).sum(0)
    aff = cnt > 0
    dur_js = (m * ldur_e[:, None, None]).sum(0)
    sat_js = (m & sat_new_i[:, None, None]).any(0)
    unsat = state.unsat_parent_count - (
        oh_j[:, :, None]
        * (delta_i[:, None] * adj_row.astype(_i32))[:, None, :]
    ).sum(0)

    return state.replace(
        rng=jnp.where(bulked, rng_next, state.rng),
        wall_time=wall,
        seq_counter=counter,
        exec_finish_time=jnp.where(touched, t_e, state.exec_finish_time),
        exec_finish_seq=jnp.where(touched, sq_e, state.exec_finish_seq),
        stage_remaining=state.stage_remaining - cnt,
        stage_completed_tasks=state.stage_completed_tasks + cnt,
        stage_duration=jnp.where(aff, dur_js, state.stage_duration),
        job_saturated_stages=state.job_saturated_stages
        + (oh_j & exhausted_i[:, None]).sum(0).astype(_i32),
        stage_sat=jnp.where(aff, sat_js, state.stage_sat),
        unsat_parent_count=unsat,
    ), k


def _bulk_ready(
    params: EnvParams, bank: WorkloadBank, state: EnvState,
    enabled: jnp.ndarray, stop_at_limit: bool = False,
):
    """Consume the maximal run of consecutive EXECUTOR_READY events in
    one vectorized pass. Returns (state, k); callers fall back to the
    single-event path when k == 0.

    After a send-heavy commitment round, every sent executor arrives at
    the same `wall + moving_delay` with consecutive seqs — a burst of
    ready events the one-at-a-time loop pays one iteration each for.
    An arrival is *simple* (statically classifiable) like a fulfillment
    candidate: its handler attaches the executor to its destination job
    and resolves RQ_MOVE locally, so with the destination unsaturated
    at its turn (rem0 minus earlier prefix starts > 0) it is A_START
    iff the destination is on the frontier (static — no completions
    happen mid-run) else A_PARK. The prefix stops at the first
    saturated-destination arrival (backup search), at any earlier
    non-ready event (job arrivals and task finishes are competitors —
    symmetrically, `_bulk_relaunch` treats arrival events as
    competitors, so the two passes alternate cleanly), at a finish
    event GENERATED by an earlier prefix start, and right AFTER any
    arrival that joins the live source pool — such an arrival can
    raise `num_committable` above 0, and the sequential per-event tail
    (round_ready / move_and_clear) must run before the next event,
    which the caller's tail does when the joiner ends the pass.

    Matches the sequential path bit-exactly except the rng stream.
    """
    n = state.exec_job.shape[0]
    j_cap, s_cap = state.stage_remaining.shape
    pos = jnp.arange(n, dtype=_i32)

    # earliest non-ready competitor, lexicographic (time, seq)
    t_job = jnp.where(state.job_arrived, INF, state.job_arrival_time)
    jt = t_job.min()
    jseq = jnp.where(t_job == jt, state.job_arrival_seq, BIG_SEQ).min()
    ft = state.exec_finish_time.min()
    fseq = jnp.where(
        state.exec_finish_time == ft, state.exec_finish_seq, BIG_SEQ
    ).min()
    t_star = jnp.minimum(jt, ft)
    seq_star = jnp.minimum(
        jnp.where(jt == t_star, jseq, BIG_SEQ),
        jnp.where(ft == t_star, fseq, BIG_SEQ),
    )

    # arrivals in processing order
    gt = (
        state.exec_arrive_time[:, None] > state.exec_arrive_time[None, :]
    ) | (
        (state.exec_arrive_time[:, None]
         == state.exec_arrive_time[None, :])
        & (state.exec_arrive_seq[:, None] > state.exec_arrive_seq[None, :])
    )
    rank = gt.sum(-1)
    perm = rank[None, :] == pos[:, None]

    def by_pos(x):
        return jnp.where(perm, x[None, :], 0).sum(-1)

    to = jnp.where(perm, state.exec_arrive_time[None, :], INF).min(-1)
    so = by_pos(state.exec_arrive_seq)
    e = by_pos(pos)
    dj = by_pos(state.exec_dst_job)
    ds0 = by_pos(state.exec_dst_stage)
    djc = jnp.clip(dj, 0, j_cap - 1)
    dsc = jnp.clip(ds0, 0, s_cap - 1)

    frontier_k = _frontier_at(state, dj, ds0)
    flat = djc * s_cap + dsc
    earlier = pos[None, :] < pos[:, None]
    stage_pair = flat[None, :] == flat[:, None]
    # within a prefix nobody is saturated, so starts are static; the
    # per-candidate quantities below may count ALL earlier positions
    # rather than earlier prefix members — for an in-prefix candidate
    # the two coincide (the prefix is contiguous), and out-of-prefix
    # values are never consumed
    start0 = frontier_k
    cum_starts = (earlier & stage_pair & start0[None, :]).sum(-1)
    rem0 = state.stage_remaining[djc, dsc]
    saturated = rem0 - cum_starts == 0

    same_job = dj[None, :] == dj[:, None]
    base_nl = (state.exec_job[None, :] == dj[:, None]).sum(-1)
    # the arriving executor itself plus earlier arrivals to the same
    # job join the count the sequential `_apply_action` reads after
    # its handler ran
    nl = base_nl + (earlier & same_job).sum(-1) + 1

    rng_next, sub = jax.random.split(state.rng)
    # one batched draw for the whole pass (sample_task_duration
    # docstring has the round-5 measurement behind this form)
    us = jax.random.uniform(sub, (pos.shape[0], 2))
    tpl = state.job_template[djc]
    tv = state.exec_task_valid[jnp.clip(e, 0, n - 1)]
    ss_same = state.exec_task_stage[jnp.clip(e, 0, n - 1)] == ds0
    durs = jax.vmap(
        lambda u2, f_, tp, s_, nl_, tv_, sm_: sample_task_duration(
            params, bank, u2, f_, tp, s_, nl_, tv_, sm_,
        )
    )(us, state.duration_facts[djc, dsc], tpl, dsc, nl, tv, ss_same)
    fin_k = to + durs

    before_star = (to < t_star) | ((to == t_star) & (so < seq_star))
    # an earlier prefix start GENERATES a finish event; the sequential
    # loop pops it before any later-timed arrival (ties go to the
    # arrival — generated seqs exceed all pending ones), so the run
    # must stop there
    gen = jnp.where(start0, fin_k, INF)
    gen_before = jnp.concatenate(
        [jnp.full((1,), INF, jnp.float32), lax.cummin(gen)[:-1]]
    )
    # an arrival that joins the LIVE source pool can raise
    # num_committable above 0; the sequential per-event tail reacts
    # (round_ready or move_and_clear) before the next event, so such
    # an arrival must be the LAST one this pass consumes — the
    # caller's tail then runs exactly where the sequential one would
    joins_source = (
        state.source_valid
        & (dj == state.source_job)
        & jnp.where(
            start0, ds0 == state.source_stage, state.source_stage == -1
        )
    )
    joined_before = (
        jnp.concatenate(
            [jnp.zeros(1, bool), joins_source[:-1]]
        ).cumsum() > 0
    )
    ok = (
        jnp.isfinite(to)
        & before_star
        & ~saturated
        & (to <= gen_before)
        & ~joined_before
    )
    if stop_at_limit:
        crossed_before = (
            jnp.concatenate(
                [jnp.zeros(1, bool), (to >= state.time_limit)[:-1]]
            ).cumsum() > 0
        )
        ok &= ~crossed_before
    prefix = (jnp.cumsum((~ok).astype(_i32)) == 0) & jnp.asarray(
        enabled, bool
    )
    k = prefix.sum().astype(_i32)

    start = start0 & prefix
    park = ~start0 & prefix
    newly_exh = start & (rem0 - cum_starts == 1)

    inc = start.astype(_i32)
    seq_k = state.seq_counter + (earlier & start0[None, :]).sum(-1)

    # ---- per-executor scatters
    sel = prefix[:, None] & perm
    exset, exflag = _exec_scatter(sel)

    minus1 = jnp.full((n,), -1, _i32)
    arrived = prefix
    exec_moving = exflag(state.exec_moving, arrived, False)
    exec_arrive_time = exset(
        state.exec_arrive_time, arrived, jnp.full((n,), INF, jnp.float32)
    )
    exec_at_common = exflag(state.exec_at_common, arrived, False)
    exec_job = exset(state.exec_job, arrived, dj)
    exec_stage = exset(
        state.exec_stage, arrived, jnp.where(start, ds0, minus1)
    )
    exec_task_valid = exflag(
        exflag(state.exec_task_valid, park, False), start, True
    )
    exec_executing = exflag(state.exec_executing, start, True)
    exec_task_stage = exset(state.exec_task_stage, start, ds0)
    exec_finish_time = exset(state.exec_finish_time, start, fin_k)
    exec_finish_seq = exset(state.exec_finish_seq, start, seq_k)

    # ---- per-stage counters (every prefix arrival was counted moving)
    oh_j = (dj[:, None] == jnp.arange(j_cap, dtype=_i32)[None, :]) \
        & prefix[:, None]
    oh_s = ds0[:, None] == jnp.arange(s_cap, dtype=_i32)[None, :]
    m3 = oh_j[:, :, None] & oh_s[:, None, :]
    cnt_arr = m3.sum(0).astype(_i32)
    cnt_start = (m3 & start[:, None, None]).sum(0).astype(_i32)
    moving_count = state.moving_count - cnt_arr
    stage_remaining = state.stage_remaining - cnt_start
    stage_executing = state.stage_executing + cnt_start

    later = pos[None, :] > pos[:, None]
    is_last_start = start & ~(later & stage_pair & start[None, :]).any(-1)
    dur_js = (
        (m3 & is_last_start[:, None, None]) * durs[:, None, None]
    ).sum(0)
    stage_duration = jnp.where(
        cnt_start > 0, dur_js, state.stage_duration
    )
    job_saturated_stages = (
        state.job_saturated_stages
        + (oh_j & newly_exh[:, None]).sum(0).astype(_i32)
    )

    # ---- saturation-cache refresh for touched destination stages
    aff = cnt_arr > 0
    demand = stage_remaining - moving_count - state.commit_count
    sat_new = demand <= 0
    is_rep = prefix & ~(earlier & stage_pair).any(-1)
    delta_k = jnp.where(
        is_rep & state.stage_exists[djc, dsc],
        sat_new[djc, dsc].astype(_i32)
        - state.stage_sat[djc, dsc].astype(_i32),
        0,
    )
    adj_row = state.adj[djc, dsc]
    unsat = state.unsat_parent_count - (
        oh_j[:, :, None]
        * (delta_k[:, None] * adj_row.astype(_i32))[:, None, :]
    ).sum(0)

    bulked = k > 0
    wall = jnp.where(
        bulked, jnp.where(prefix, to, -INF).max(), state.wall_time
    )
    state = state.replace(
        rng=jnp.where(bulked, rng_next, state.rng),
        wall_time=wall,
        seq_counter=state.seq_counter + inc.sum(),
        exec_moving=exec_moving,
        exec_arrive_time=exec_arrive_time,
        exec_at_common=exec_at_common,
        exec_job=exec_job,
        exec_stage=exec_stage,
        exec_task_valid=exec_task_valid,
        exec_executing=exec_executing,
        exec_task_stage=exec_task_stage,
        exec_finish_time=exec_finish_time,
        exec_finish_seq=exec_finish_seq,
        moving_count=moving_count,
        stage_remaining=stage_remaining,
        stage_executing=stage_executing,
        stage_duration=stage_duration,
        job_saturated_stages=job_saturated_stages,
        stage_sat=jnp.where(aff, sat_new, state.stage_sat),
        unsat_parent_count=unsat,
    )
    return state, k


# Steps of the fused bulk pass between two tests of "is any lane still
# active". An iteration of the `while` costs its predicate (a reduce
# over the lanes and a scalar read, about 3 us on the TPU v5e) on top
# of the steps it runs (33 us each at 128 lanes), and a granule of g
# steps pays that once per g and rounds the batch's need up to a
# multiple of g. Measured on whole collections of 128 lanes x 800
# decisions: 15.20 s at 1, 15.10 s at 2, 15.35 s at 4, 15.63 s at 8,
# against 20.43 s for the fixed scan (PERF.md, PR 28).
_BULK_STEP_GRANULE = 2


def _steps_while_active(step_fn, carry0, us, lane_axis=None):
    """Run `step_fn(carry, u_row, in_budget)` over the rows of `us` in
    order, from `carry0`, for as long as the pass is active
    (`carry[-1]`) and rows are left: the early-exit form of
    `lax.scan(step_fn, carry0, us)`. A step on an inactive pass changes
    nothing, so the result is the scan's; the steps not run are the
    ones that could not have done anything.

    Under `jax.vmap` the loop runs until NO lane is active: the
    batch's largest need, not `len(us)`. With `lane_axis` (the name the
    caller's `vmap` gave its lane axis) the predicate is reduced over
    that axis, so it is the same for every lane and the loop selects
    nothing; without it each lane keeps its own predicate, and the
    batching rule of `while_loop` selects the carry against it after
    every iteration (redundant here, and harmless).

    Steps run in granules of `_BULK_STEP_GRANULE`; a granule's steps
    past the last row are gated off through `in_budget` (the row index
    is clamped by the slice, and nothing reads the row).

    Returns the carry and the number of times the loop's predicate was
    evaluated (its iterations and the one that ended it): with
    `lane_axis` each is one reduction over the lanes, across the chips
    of a dp mesh an all-reduce (`Telemetry.lane_syncs`); without it
    the count is the lane's own and counts no reduction."""
    length = us.shape[0]

    def cond(c):
        i, carry = c
        active = carry[-1]
        if lane_axis is not None:
            active = lax.pmax(active, lane_axis)
        return active & (i < length)

    def body(c):
        i, carry = c
        for j in range(_BULK_STEP_GRANULE):
            u_row = lax.dynamic_index_in_dim(us, i + j, keepdims=False)
            carry = step_fn(carry, u_row, i + j < length)
        return i + _BULK_STEP_GRANULE, carry

    i, carry = lax.while_loop(cond, body, (_i32(0), carry0))
    return carry, i // _BULK_STEP_GRANULE + 1


def _pack_stage_sets(member: jnp.ndarray) -> jnp.ndarray:
    """bool[J, S, ...] -> uint32[J, W, ...]: the stage axis as bit sets,
    stage `p` at bit `p % 32` of word `p // 32` (`STAGE_SET_BITS`;
    W = ceil(S / 32): one word up to 32 stages a job)."""
    j_cap, s_cap = member.shape[:2]
    rest = member.shape[2:]
    words = -(-s_cap // STAGE_SET_BITS)
    pad = [(0, 0), (0, words * STAGE_SET_BITS - s_cap)] + [(0, 0)] * len(rest)
    bits = jnp.pad(member, pad).reshape(j_cap, words, STAGE_SET_BITS, *rest)
    place = jnp.arange(STAGE_SET_BITS, dtype=jnp.uint32).reshape(
        (STAGE_SET_BITS,) + (1,) * len(rest)
    )
    # distinct bits, so the sum is the union
    return (bits.astype(jnp.uint32) << place).sum(2, dtype=jnp.uint32)


def pack_parents(adj: jnp.ndarray) -> jnp.ndarray:
    """The adjacency `bool[J,S,S]` (`adj[j,p,c]`: edge p -> c) as each
    stage's PARENT SET: `uint32[J,W,S]`, bit `p % 32` of `[j, p // 32,
    c]` set iff p is a parent of c. `EnvState.parent_sets` holds it: a
    function of `adj` alone, written where `adj` is (at reset)."""
    return _pack_stage_sets(adj)


def _job_parent_sets(state: EnvState, oj: jnp.ndarray) -> jnp.ndarray:
    """uint32[W,S]: the packed parent sets of the job `oj` marks
    (bool[J]; all zero where it marks none), picked over the job axis
    of `state.parent_sets`. What the drain body's fixed part reads of
    the adjacency it reads here: a [J,S]-sized operand, where
    `adj[j, s]` and `adj[j]` gather from the [J,S,S] one."""
    return _pick(oj[:, None, None], state.parent_sets, axis=0)


def _children(sets_j: jnp.ndarray, os_: jnp.ndarray) -> jnp.ndarray:
    """bool[S]: `adj[j, s, :]`, the children of the stage `os_` marks
    (bool[S]) in the job whose packed parent sets are `sets_j`: c is a
    child iff its parent set holds the stage's bit."""
    of_stage = _pack_stage_sets(os_[None, :])[0]  # [W]: the set {s}
    return ((sets_j & of_stage[:, None]) != 0).any(0)


def _unpack_parents(sets_j: jnp.ndarray, s_cap: int) -> jnp.ndarray:
    """bool[S,S]: `adj[j]` (`[p, c]`: edge p -> c) from the job's packed
    parent sets, `pack_parents` undone for one job."""
    place = jnp.arange(STAGE_SET_BITS, dtype=jnp.uint32)[None, :, None]
    bits = (sets_j[:, None, :] >> place) & 1  # [W,32,S]
    return bits.reshape(-1, sets_j.shape[-1])[:s_cap].astype(bool)


def _flipped_parents(state: EnvState, delta: jnp.ndarray) -> jnp.ndarray:
    """`sum_p delta[j,p] * adj[j,p,c]` for `delta` in {-1, 0, +1}, from
    the state's packed parent sets: the parents of (j,c) that turned
    saturated less those that turned unsaturated, two set
    intersections counted. Integer arithmetic, so equal to the
    contraction over `state.adj` for every input; and it reads [J,S]
    words where that reads [J,S,S] (tests/test_flat_loop.py keeps the
    contraction as the reference)."""
    def hits(flipped):  # bool[J,S] -> i32[J,S]
        of_job = _pack_stage_sets(flipped)[:, :, None]
        return lax.population_count(
            state.parent_sets & of_job
        ).sum(1).astype(_i32)

    return hits(delta > 0) - hits(delta < 0)


def _frontier_at(state: EnvState, dj: jnp.ndarray, ds: jnp.ndarray
                 ) -> jnp.ndarray:
    """bool[N]: `state.frontier[dj, ds]` for per-executor destinations
    (both clipped into range, as an index is), read by no dynamic
    index. The frontier is packed over its stage axis
    (`_pack_stage_sets`: uint32[J,W], one elementwise pass over the
    three [J,S] caches it is made of), each executor's job word picked
    by a one-hot over the job axis ([N,J]; a row holds one match, so
    the sum IS the word), and bit `ds % 32` of word `ds // 32` tested.
    Bits and integers, so equal to the gather for every input and any
    S. Why not the gather: on the TPU v5e it is serialised, 12 ns an
    element, 77 us a drain body of 128 lanes x 50 executors at the
    flagship cluster (PERF.md, PRs 39 and 45)."""
    j_cap, s_cap = state.stage_exists.shape
    djc = jnp.clip(dj, 0, j_cap - 1)
    dsc = jnp.clip(ds, 0, s_cap - 1)
    sets = _pack_stage_sets(state.frontier)  # [J,W]
    of_job = djc[:, None] == jnp.arange(j_cap, dtype=_i32)[None, :]
    words = jnp.where(of_job[:, :, None], sets[None], 0).sum(
        1, dtype=jnp.uint32)  # [N,W]
    at_word = lax.div(dsc, _i32(STAGE_SET_BITS))[:, None] == jnp.arange(
        sets.shape[1], dtype=_i32)[None, :]
    word = jnp.where(at_word, words, 0).sum(1, dtype=jnp.uint32)
    bit = lax.rem(dsc, _i32(STAGE_SET_BITS)).astype(jnp.uint32)
    return ((word >> bit) & 1).astype(bool)


def _bulk_events_fused(
    params: EnvParams, bank: WorkloadBank, state: EnvState,
    enabled: jnp.ndarray, stop_at_limit: bool = False,
    max_events: int = 8, lane_axis: str | None = None,
):
    """Consume one maximal run of *simple* events — task relaunches AND
    executor arrivals, interleaved in exact (time, seq) order — in a
    SINGLE bounded early-exit loop. Returns (state, k_rel, k_rdy,
    steps, syncs): events consumed by kind (both 0 when the next event
    is not simple, the queue is drained, or `enabled` is False), the
    steps of the loop this lane needed (those it entered active: one
    per event taken, plus the step that saw the run end unless a
    joining arrival or the budget ended it; 0 when not `enabled`) and
    the reductions over `lane_axis` the loop made for the batch (the
    evaluations of its predicate, the same in every lane; 0 without
    `lane_axis`).

    This fuses `_bulk_relaunch` + `_bulk_ready` into one kernel (ISSUE
    7): instead of a fixed relaunch-pass / arrival-pass order — which
    pays one micro-step per event-kind switch and two full pass-sized
    op chains per micro-step — every scan step picks the lexicographic
    (time, seq) minimum over ALL pending finishes and arrivals,
    classifies it against the live remaining-task view, and applies it.
    One rng split, one duration-sampling chain per consumed event (of
    the bank it reads three elements, the chosen bucket's count, the
    picked sample and the stage's rough duration; what no draw decides
    is the stage's word of `state.duration_facts`), and
    after the loop one merged state update: the [J,S] counters of the
    consumed arrivals as one-hot sums over the executors, and the
    saturation caches (`stage_sat`, `unsat_parent_count`) refreshed at
    the stages a launch or an arrival touched, the parents' flips
    counted on `state.parent_sets` (`_flipped_parents`), so that
    nothing here reads the [J,S,S] adjacency whole. Because events are
    processed in true queue order, the separate passes' cross-kind stop
    conditions (`_bulk_ready`'s generated-finish cutoff,
    `_bulk_relaunch` treating arrivals as competitors) dissolve: a
    finish event generated by an in-run arrival start simply
    participates in later steps, and mixed relaunch/arrival runs that
    previously cost one micro-step per kind switch are consumed in one
    pass.

    An event is *simple* iff its target stage still has unlaunched
    tasks at its turn (`rem > 0` on the live view):

    - a TASK_FINISHED on a stage with `rem > 0` relaunches (the
      `_handle_task_finished` more_tasks path resolving to A_START);
      `rem == 0` means the released-stage handler must run — stop;
    - an EXECUTOR_READY whose destination has `rem > 0` resolves
      locally to A_START (destination on the frontier — static during
      the run, no stage ever completes here) or A_PARK; `rem == 0`
      triggers the backup-stage search — stop.

    The run also stops before any job-arrival competitor, right after
    an arrival that joins the live source pool (it can raise
    `num_committable` above 0, and the sequential per-event tail must
    run before the next event), and — with `stop_at_limit` — right
    after the first event at or past the episode time limit.

    The fulfillment-phase bulk (`_bulk_fulfill`) stays a separate pass
    in the shared micro-step tail: fulfillment work only exists on
    DECIDE-mode lanes and event work only on EVENT-mode lanes, so the
    two passes are mode-exclusive per micro-step and fusing them would
    add op count without removing a dispatch.

    Cross-event coupling is tracked in the scan carry: the live
    per-stage remaining view `rem[J,S]` (launches of either kind
    decrement it), the live executors-per-job count (`jcnt[J]` — the
    duration model's executor-level input; arrivals attach mid-run),
    and each executor's CURRENT finish-event stage (`fj`/`fs` — an
    arrival start re-targets the executor's next finish to its
    destination stage, and that finish may itself relaunch within the
    same pass). The loop's budget is `max_events + N` steps: one full
    relaunch cascade plus a worst-case arrival burst, so a fused pass
    can always consume at least what the unfused pass pair could. It
    stops stepping as soon as the run is over (`_steps_while_active`):
    once `active` is false every update of a step is gated off, so the
    steps left out are no-ops and the result is that of all
    `max_events + N`. Under `jax.vmap` the device pays the largest need
    over the lanes of the batch (17 of the 58 steps a pass at 128 lanes
    of the flagship cluster, PERF.md section 5), not the budget;
    `lane_axis` names the caller's lane axis, if it has one.

    Matches the sequential path bit-exactly except the rng stream:
    one uniform table a pass, drawn before the loop at
    `[max_events + N, 2]`, the pair a step can consume (a step
    launches at most one task; a pair for every step AND executor is
    N times what a pass can read, and cost 62 us a drain body of 128
    lanes under threefry keys: PERF.md, PR 45)."""
    n = state.exec_job.shape[0]
    j_cap, s_cap = state.stage_remaining.shape
    pos = jnp.arange(n, dtype=_i32)
    length = max_events + n

    # job arrivals: the only competitor kind (never consumed here)
    t_job = jnp.where(state.job_arrived, INF, state.job_arrival_time)
    jt = t_job.min()
    jseq = jnp.where(t_job == jt, state.job_arrival_seq, BIG_SEQ).min()

    # static per-executor arrival facts (an arrival's destination and
    # wave inputs cannot change before it fires — the executor is
    # moving, so no other event touches it first)
    dj = state.exec_dst_job
    ds0 = state.exec_dst_stage
    djc = jnp.clip(dj, 0, j_cap - 1)
    dsc = jnp.clip(ds0, 0, s_cap - 1)
    frontier_a = _frontier_at(state, dj, ds0)
    tv_a = state.exec_task_valid
    ss_a = state.exec_task_stage == ds0
    sq_a = state.exec_arrive_seq
    joins_a = (
        state.source_valid
        & (dj == state.source_job)
        & jnp.where(
            frontier_a, ds0 == state.source_stage,
            state.source_stage == -1,
        )
    )

    rng_next, sub = jax.random.split(state.rng)
    # one draw for the whole pass, a pair a step: step i takes ONE
    # event and launches at most one task, which reads us[i] (which
    # event it is depends only on earlier rows, so the pairs consumed
    # are i.i.d.)
    us = jax.random.uniform(sub, (length, 2))

    jcnt0 = (
        state.exec_job[None, :] == jnp.arange(j_cap, dtype=_i32)[:, None]
    ).sum(-1).astype(_i32)

    def step_fn(carry, u2, in_budget):
        (t_f, sq_f, t_a, fj, fs, rem, jcnt, launch_t, dur_js, relc,
         arr_done, started, counter, wall, crossed, steps, active) = carry
        active = active & in_budget
        steps = steps + active.astype(_i32)

        # lexicographic (time, seq) minimum over finishes and arrivals
        ftmin = t_f.min()
        fcand = t_f == ftmin
        fsmin = jnp.where(fcand, sq_f, BIG_SEQ).min()
        atmin = t_a.min()
        acand = t_a == atmin
        asmin = jnp.where(acand, sq_a, BIG_SEQ).min()
        is_fin = (ftmin < atmin) | ((ftmin == atmin) & (fsmin < asmin))
        tmin = jnp.minimum(ftmin, atmin)
        smin = jnp.where(is_fin, fsmin, asmin)
        has = jnp.isfinite(tmin)
        before_job = (tmin < jt) | ((tmin == jt) & (smin < jseq))
        e_oh = jnp.where(
            is_fin, fcand & (sq_f == fsmin), acand & (sq_a == asmin)
        )

        # the winner's target stage on the LIVE views. What the step
        # reads of its target in the lane's own arrays (the stage's
        # remaining tasks and word of duration facts, the job's
        # executor count and template) it picks with the one-hots its
        # updates need anyway: under `vmap` an indexed read is a
        # gather, serialised on the TPU (1.5 to 2.2 us for 128 lanes
        # where a select-reduce fuses with its neighbours), and each
        # brought a relayout of its index column with it
        tj = jnp.where(is_fin, _pick(e_oh, fj), _pick(e_oh, djc))
        ts = jnp.where(is_fin, _pick(e_oh, fs), _pick(e_oh, dsc))
        oh_j = _onehot(j_cap, tj)
        oh2 = _onehot2(j_cap, s_cap, tj, ts)
        rem_t = _pick(oh2, rem)
        ok = active & has & before_job & (rem_t > 0)
        if stop_at_limit:
            ok = ok & ~crossed
            crossed = crossed | (ok & (tmin >= state.time_limit))
        start_a = _pick(e_oh, frontier_a)  # arrival-start vs park
        is_rel = ok & is_fin
        is_arr = ok & ~is_fin
        launch = is_rel | (is_arr & start_a)
        # an arrival that joins the live source pool ends the run
        # AFTER being consumed (the caller's tail then runs exactly
        # where the sequential loop's would)
        joins = is_arr & _pick(e_oh, joins_a)

        # duration for the launched task (relaunch: same-stage
        # continuation; arrival: the sequential wave inputs)
        # (an arrival counts itself among the job's executors)
        nl = _pick(oh_j, jcnt) + is_arr.astype(_i32)
        tv = jnp.where(is_fin, True, _pick(e_oh, tv_a))
        ss = jnp.where(is_fin, True, _pick(e_oh, ss_a))
        dur = sample_task_duration(
            params, bank, u2, _pick(oh2, state.duration_facts),
            _pick(oh_j, state.job_template), ts, nl, tv, ss,
        )

        t_f = jnp.where(launch & e_oh, tmin + dur, t_f)
        sq_f = jnp.where(launch & e_oh, counter, sq_f)
        t_a = jnp.where(is_arr & e_oh, INF, t_a)
        fj = jnp.where(is_arr & start_a & e_oh, tj, fj)
        fs = jnp.where(is_arr & start_a & e_oh, ts, fs)
        rem = rem - (launch & oh2).astype(_i32)
        jcnt = jcnt + (is_arr & oh_j).astype(_i32)
        launch_t = launch_t | (launch & oh2)
        dur_js = jnp.where(launch & oh2, dur, dur_js)
        relc = relc + (is_rel & oh2).astype(_i32)
        arr_done = arr_done | (is_arr & e_oh)
        started = started | (is_arr & start_a & e_oh)
        counter = counter + launch.astype(_i32)
        wall = jnp.where(ok, tmin, wall)
        active = active & ok & ~joins
        return (
            t_f, sq_f, t_a, fj, fs, rem, jcnt, launch_t, dur_js, relc,
            arr_done, started, counter, wall, crossed, steps, active,
        )

    jc = jnp.clip(state.exec_job, 0, j_cap - 1)
    sc = jnp.clip(state.exec_task_stage, 0, s_cap - 1)
    carry0 = (
        state.exec_finish_time,
        state.exec_finish_seq,
        state.exec_arrive_time,
        jc,
        sc,
        state.stage_remaining,
        jcnt0,
        jnp.zeros((j_cap, s_cap), bool),
        jnp.zeros((j_cap, s_cap), jnp.float32),
        jnp.zeros((j_cap, s_cap), _i32),
        jnp.zeros(n, bool),
        jnp.zeros(n, bool),
        state.seq_counter,
        state.wall_time,
        jnp.bool_(False),
        _i32(0),
        jnp.asarray(enabled, bool),
    )
    (t_f, sq_f, t_a, _, _, rem, _, launch_t, dur_js, relc, arr_done,
     started, counter, wall, _, steps, _), evals = _steps_while_active(
        step_fn, carry0, us, lane_axis
    )
    # the loop's predicate is a reduction over the lanes only where the
    # caller named its lane axis
    syncs = evals if lane_axis is not None else _i32(0)

    k_rel = relc.sum()
    k_rdy = arr_done.sum().astype(_i32)
    bulked = (k_rel + k_rdy) > 0

    # [J,S] scatters for the consumed arrivals (static destinations)
    oh_j = (dj[:, None] == jnp.arange(j_cap, dtype=_i32)[None, :]) \
        & arr_done[:, None]
    oh_s = ds0[:, None] == jnp.arange(s_cap, dtype=_i32)[None, :]
    m3 = oh_j[:, :, None] & oh_s[:, None, :]
    cnt_arr = m3.sum(0).astype(_i32)
    cnt_start = (m3 & started[:, None, None]).sum(0).astype(_i32)
    moving_count = state.moving_count - cnt_arr
    stage_executing = state.stage_executing + cnt_start

    # stages that launched down to zero transitioned to fully-launched
    # (launches are the only in-run decrements and require rem > 0)
    newly_exh = launch_t & (rem == 0)
    job_saturated_stages = (
        state.job_saturated_stages + newly_exh.sum(-1).astype(_i32)
    )

    # saturation-cache refresh over every touched stage: demand moved
    # wherever a launch or an arrival landed. `delta` is the flip of
    # each stage's saturation ([J,S], nearly always all zero: a stage
    # saturates once in its life, and un-saturates only when an
    # arrival parks); a child's unsaturated-parent count moves by the
    # flips among its parents, counted on the state's packed parent
    # sets (`_flipped_parents`) and not by a contraction over `adj`
    touched = launch_t | (cnt_arr > 0)
    demand = rem - moving_count - state.commit_count
    sat_new = demand <= 0
    delta = jnp.where(
        touched & state.stage_exists,
        sat_new.astype(_i32) - state.stage_sat.astype(_i32),
        0,
    )
    unsat = state.unsat_parent_count - _flipped_parents(state, delta)

    state = state.replace(
        rng=jnp.where(bulked, rng_next, state.rng),
        wall_time=wall,
        seq_counter=counter,
        exec_finish_time=t_f,
        exec_finish_seq=sq_f,
        exec_arrive_time=t_a,
        exec_moving=state.exec_moving & ~arr_done,
        exec_at_common=state.exec_at_common & ~arr_done,
        exec_job=jnp.where(arr_done, dj, state.exec_job),
        exec_stage=jnp.where(
            arr_done, jnp.where(started, ds0, -1), state.exec_stage
        ),
        exec_task_valid=jnp.where(
            arr_done, started, state.exec_task_valid
        ),
        exec_executing=state.exec_executing | started,
        exec_task_stage=jnp.where(
            started, ds0, state.exec_task_stage
        ),
        stage_remaining=rem,
        stage_completed_tasks=state.stage_completed_tasks + relc,
        stage_executing=stage_executing,
        moving_count=moving_count,
        stage_duration=jnp.where(launch_t, dur_js, state.stage_duration),
        job_saturated_stages=job_saturated_stages,
        stage_sat=jnp.where(touched, sat_new, state.stage_sat),
        unsat_parent_count=unsat,
    )
    return state, k_rel, k_rdy, steps, syncs


def _resume_simulation(
    params: EnvParams, bank: WorkloadBank, state: EnvState,
    active: jnp.ndarray, bulk: bool = True, bulk_events: int = 8,
    telem=None,
):
    """Pop events until there are new scheduling decisions to make or the
    queue drains (reference :320-343). `active` masks the whole loop.
    With `bulk`, each iteration first consumes a whole run of relaunch
    events via `_bulk_relaunch` plus the arrival-burst prefix, and then
    — fused pop, mirroring the flat engine — still pops the run-cutting
    event in the SAME iteration whenever the skipped between-event tail
    is provably a no-op: `num_committable() == 0` (the tail's
    round-ready flip and move_and_clear are both gated on
    committable > 0, and `_bulk_ready` ends its prefix at any arrival
    that could raise it). Under vmap the while loop costs the batch-max
    iteration count, so consuming bulk + cutter per iteration cuts the
    straggler tax for every lane.

    With `telem` (an `obs.Telemetry`), returns `(state, telem)` counting
    each lane's own iteration count (`loop_iters` — the while batching
    rule masks the carry for false-cond lanes, so the count is per-lane
    exact and max/mean over lanes IS the straggler tax), single pops by
    event kind, and bulk-pass consumption. None threads nothing."""
    track = telem is not None

    def cond(carry):
        st = carry[0] if track else carry
        has, _, _, _ = _next_event(params, st)
        return active & has & ~st.round_ready

    def body(carry):
        if track:
            st, tm = carry
        else:
            st, tm = carry, None
        if bulk:
            st, nb1 = _bulk_relaunch(
                params, bank, st, jnp.bool_(True),
                max_events=bulk_events,
            )
            st, nb2 = _bulk_ready(params, bank, st, jnp.bool_(True))
            single = ((nb1 + nb2) == 0) | (st.num_committable() == 0)
            if track:
                tm = _tm_add(
                    tm, bulk_relaunch_events=nb1, bulk_ready_events=nb2,
                    bulk_passes=(nb1 + nb2) > 0,
                )
        else:
            single = jnp.bool_(True)
        # `has` must re-gate the fused pop: the bulk passes above may
        # have consumed the queue's last events (e.g. a parked arrival)
        has, t, kind, arg = _next_event(params, st)
        if track:
            did_pop = single & has
            tm = _tm_add(
                tm,
                loop_iters=1,
                drain_iters=1,
                event_steps=did_pop,
                ev_job_arrival=did_pop & (kind == EV_JOB_ARRIVAL),
                ev_task_finished=did_pop & (kind == EV_TASK_FINISHED),
                ev_exec_ready=did_pop & (kind == EV_EXECUTOR_READY),
            )

        def pop(st: EnvState):
            st = st.replace(wall_time=t)
            quirk_src = st.source_job_id()
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
            return st, rk, rj, rs, quirk_src

        def nopop(st: EnvState):
            return st, _i32(RQ_NONE), _i32(-1), _i32(-1), _i32(-1)

        st, rk, rj, rs, quirk_src = lax.cond(single & has, pop, nopop, st)
        ak, tj, ts = _resolve_action(params, st, rk, arg, rj, rs, quirk_src)
        st = _apply_action(params, bank, st, ak, arg, tj, ts)
        committable = st.num_committable()
        sched = find_schedulable(params, st, st.source_job_id())
        ready = (committable > 0) & sched.any()

        def set_ready(st: EnvState) -> EnvState:
            return st.replace(
                round_ready=jnp.bool_(True), schedulable=sched
            )

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
                committable > 0, move_and_clear, lambda s2: s2, st
            )

        st = lax.cond(ready, set_ready, not_ready, st)
        return (st, tm) if track else st

    if track:
        return lax.while_loop(cond, body, (state, telem))
    return lax.while_loop(cond, body, state)


# --------------------------------------------------------------------------
# reward (reference :847-874)
# --------------------------------------------------------------------------


def _compute_jobtime(
    params: EnvParams, state: EnvState, t_old: jnp.ndarray,
    active_old: jnp.ndarray, t_ref: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Total (optionally beta-discounted) job-time over [t_old, wall_time].

    `t_ref` is the discount reference point; it defaults to `t_old` (the
    per-decision-step form `step` uses). The flat engine's trajectory
    recording accumulates job-time one micro-step at a time and passes the
    wall time of the round-finishing decision as `t_ref`, so the partial
    contributions telescope to exactly the single-span quantity `step`
    would have computed (exp(-b(x - t_ref)) factors cancel at interior
    interval boundaries; for beta == 0 the sum is plainly additive)."""
    t_new = state.wall_time
    m = active_old | state.job_active
    start = jnp.maximum(state.job_arrival_time, t_old)
    end = jnp.minimum(state.job_t_completed, t_new)
    if params.beta == 0.0:
        per = end - start
    else:
        ref = t_old if t_ref is None else t_ref
        b = params.beta * 1e-3
        per = jnp.exp(-b * (start - ref)) - jnp.exp(-b * (end - ref))
    total = jnp.where(m, per, 0.0).sum()
    if params.beta > 0.0:
        total = total / params.beta
    return jnp.where(t_new == t_old, 0.0, total)


# --------------------------------------------------------------------------
# public API: reset / step
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnums=0)
def reset(params: EnvParams, bank: WorkloadBank, rng: jax.Array) -> EnvState:
    """Sample a fresh episode (reference :127-186 + StochasticTimeLimit)."""
    return reset_pair(params, bank, rng, jax.random.fold_in(rng, 1))


@partial(jax.jit, static_argnums=0)
def reset_pair(
    params: EnvParams, bank: WorkloadBank, seq_rng: jax.Array,
    lane_rng: jax.Array
) -> EnvState:
    """Reset with separate keys for the job sequence / time limit
    (`seq_rng`) and the per-lane stochastic stream (`lane_rng`). Lanes that
    share `seq_rng` replay the same arrival sequence — the TPU analogue of
    the reference's `num_sequences x num_rollouts` worker seed layout
    (trainers/trainer.py:268-271), which the critic-free baseline relies
    on (trainers/utils/baselines.py:12-18)."""
    k_limit, k_seq = jax.random.split(seq_rng)
    k_state = lane_rng

    if params.mean_time_limit is None:
        time_limit = INF
    else:
        time_limit = (
            jax.random.exponential(k_limit) * params.mean_time_limit
        ).astype(jnp.float32)

    arrivals, templates, num_jobs, mask = sample_job_sequence(
        params, bank, k_seq, time_limit
    )
    return reset_from_sequence(
        params, bank, k_state, time_limit, arrivals, templates, num_jobs,
        mask,
    )


@partial(jax.jit, static_argnums=0)
def reset_from_sequence(
    params: EnvParams, bank: WorkloadBank, rng: jax.Array,
    time_limit: jnp.ndarray, arrivals: jnp.ndarray, templates: jnp.ndarray,
    num_jobs: jnp.ndarray, mask: jnp.ndarray
) -> EnvState:
    """Reset with an explicitly provided job sequence (for parity tests and
    replay; the reference takes its sequence from DataSampler.job_sequence
    at reset, spark_sched_sim.py:149-156)."""
    state = empty_state(params, rng)
    s_cap = params.max_stages
    ns = jnp.where(mask, bank.num_stages[templates], 0)
    exists = (jnp.arange(s_cap, dtype=_i32)[None, :] < ns[:, None])
    ntasks = jnp.where(exists, bank.num_tasks[templates], 0)
    rough = jnp.where(exists, bank.rough_duration[templates], 0.0)
    adj = bank.adj[templates] & exists[:, :, None] & exists[:, None, :]

    sat0 = ntasks <= 0  # padding rows and empty stages start saturated
    unsat0 = (
        (adj & (~sat0 & exists)[:, :, None]).sum(axis=1)
    ).astype(jnp.int32)
    ipc0 = adj.sum(axis=1).astype(jnp.int32)
    state = state.replace(
        stage_sat=sat0,
        unsat_parent_count=unsat0,
        incomplete_parent_count=ipc0,
        parent_sets=pack_parents(adj),
        duration_facts=pack_duration_facts(bank)[templates],
        node_level=topo_levels(exists, adj),
        time_limit=time_limit,
        seq_counter=num_jobs,
        job_template=templates,
        job_arrival_time=arrivals,
        job_arrival_seq=jnp.arange(params.max_jobs, dtype=_i32),
        job_num_stages=ns,
        num_jobs=num_jobs,
        stage_exists=exists,
        stage_num_tasks=ntasks,
        stage_remaining=ntasks,
        stage_duration=rough,
        adj=adj,
    )

    # _load_initial_jobs (reference :260-273): pop all t=0 arrivals
    t0 = mask & (arrivals == 0.0)
    state = state.replace(
        job_arrived=t0,
        # common pool holds all executors -> source = common pool
        source_valid=jnp.bool_(True),
        source_job=_i32(-1),
        source_stage=_i32(-1),
    )
    sched = find_schedulable(params, state, state.source_job_id())
    return state.replace(schedulable=sched, round_ready=jnp.bool_(True))


@partial(
    jax.jit, static_argnums=0, static_argnames=("bulk", "bulk_events")
)
def step(
    params: EnvParams, bank: WorkloadBank, state: EnvState,
    stage_idx: jnp.ndarray, num_exec: jnp.ndarray, *, bulk: bool = True,
    bulk_events: int = 8, telemetry=None
):
    """One decision step (reference :188-221). Returns
    (state, reward, terminated, truncated). `bulk=False` forces BOTH
    vectorized fast paths off — relaunch runs pop one event per
    iteration (`_bulk_relaunch`) and the fulfillment phase runs one
    candidate at a time (`_bulk_fulfill`) — for equivalence testing;
    the rng streams of the two modes differ (per-candidate pre-derived
    keys vs the sequential chain).

    With `telemetry` (an `obs.Telemetry`), returns a 5-tuple with the
    counters advanced — decisions/rounds on live lanes, event-loop
    iterations and event kinds (see `obs.telemetry` for semantics).
    The default None path is bit-identical to the pre-telemetry step
    and threads no extra carry."""
    track = telemetry is not None
    s_cap = params.max_stages
    j = stage_idx // s_cap
    s = stage_idx % s_cap
    # all-false for a `stage_idx` out of range, which `valid` rules out
    sel = _onehot2(params.max_jobs, s_cap, j, s)
    valid = (
        (stage_idx >= 0)
        & (stage_idx < params.num_nodes)
        & _pick(sel, state.schedulable)
    )
    if track:
        live = ~(state.terminated | state.truncated)

    def do_commit(st: EnvState) -> EnvState:
        committable = st.num_committable()
        n = jnp.clip(num_exec, 1, committable)
        # _adjust_num_executors
        n = jnp.minimum(n, _pick(sel, st.exec_demand))
        st = _add_commitment(st, n, j, s)
        st = st.replace(stage_selected=st.stage_selected | sel)
        sched = find_schedulable(params, st, st.source_job_id())
        return st.replace(schedulable=sched)

    state = lax.cond(valid, do_commit, _commit_remaining, state)

    round_continues = (state.num_committable() > 0) & state.schedulable.any()

    # The round-finished path below runs straight-line, masked by `active`,
    # instead of under lax.cond: its body reaches the workload bank (task
    # durations, via the event loop), and a lane-dependent cond would
    # broadcast the bank across the vmap batch (see structural note above).
    active = ~round_continues

    def commit_rest(st: EnvState) -> EnvState:
        return _commit_remaining(st)

    state = lax.cond(active, commit_rest, lambda st: st, state)
    if track:
        telemetry = _tm_add(
            telemetry,
            decide_steps=live,
            commit_rounds=active & live,
        )
        state, telemetry = _fulfill_from_source(
            params, bank, state, active, bulk=bulk, telem=telemetry
        )
    else:
        state = _fulfill_from_source(
            params, bank, state, active, bulk=bulk
        )

    def clear_round(st: EnvState) -> EnvState:
        return st.replace(
            source_valid=jnp.bool_(False),
            source_job=_i32(-1),
            source_stage=_i32(-1),
            stage_selected=jnp.zeros_like(st.stage_selected),
            round_ready=jnp.bool_(False),
            schedulable=jnp.zeros_like(st.schedulable),
        )

    state = lax.cond(active, clear_round, lambda st: st, state)
    t_old = state.wall_time
    active_old = state.job_active
    if track:
        state, telemetry = _resume_simulation(
            params, bank, state, active, bulk=bulk,
            bulk_events=bulk_events, telem=telemetry,
        )
    else:
        state = _resume_simulation(
            params, bank, state, active, bulk=bulk,
            bulk_events=bulk_events,
        )
    reward = jnp.where(
        active, -_compute_jobtime(params, state, t_old, active_old), 0.0
    )

    terminated = state.all_jobs_complete
    truncated = state.wall_time >= state.time_limit
    state = state.replace(terminated=terminated, truncated=truncated)
    if track:
        return state, reward, terminated, truncated, telemetry
    return state, reward, terminated, truncated
