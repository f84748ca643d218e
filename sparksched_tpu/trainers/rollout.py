"""On-device rollout collection.

The reference collects rollouts in `num_sequences x num_rollouts` separate
OS processes, each running a Python env + torch policy episode loop and
shipping pickled buffers over pipes (trainers/rollout_worker.py:49-206,
trainer.py:264-296). Here a rollout is one `lax.scan` of
policy∘env-step over T decision steps, vmapped over B environment lanes on
one chip (and sharded over the device mesh for more) — parameter scatter
and buffer gather disappear because learner and actors are one XLA program.

Both reference modes exist:
- sync (RolloutWorkerSync:132-157): one episode per lane per iteration;
  steps after episode end are masked out (`valid=False`).
- async (RolloutWorkerAsync:160-206): fixed sim-time budget per iteration;
  lanes persist across iterations and auto-reset mid-scan, recording reset
  steps.

One collector per mode is what the trainer runs, both over the flat
engine (env/flat_loop.py): `collect_flat_sync_batch` and
`collect_flat_async_batch`. `collect_sync` and `collect_async`, over
`core.step`, are the reference collectors the tests and `chip_smoke.py`
hold them to; the trainer does not reach them. A third caller of the
engine's two primitives, with any `Scheduler` in the row and no stored
observation, is the sweep loop (`sparksched_tpu/sweep.py`), which
shares `_by_blocks` and `_DRAIN_BLOCK` below.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from ..config import EnvParams
from ..env import core
from ..env.flat_loop import (
    M_DECIDE,
    LoopState,
    _lane_done,
    aux_action_fields,
    decide_micro_step,
    drain_to_decision,
    init_loop_state,
)
from ..env.health import reward_health, state_health
from ..env.observe import Observation, observe
from ..env.state import EnvState
from ..obs.telemetry import add as _tm_add
from ..obs.telemetry import orr as _tm_orr
from ..obs.tracing import annotate
from ..workload.bank import WorkloadBank

_i32 = jnp.int32


# The [J,S] node grids of a stored step are kept flat, padded to a
# multiple of the TPU's 128-wide lane tile. The TPU compiler lays an
# array out so that padding is least: a rollout leaf [B,T,200,20] or
# [B,T,4000] gets the TIME axis minor-most, and both the collector's
# per-step row scatter and the update's minibatch gather along T then
# go through whole-rollout relayout copies (at the flagship's 16 lanes x
# 9600 steps: 9.4 GB of temporaries in each program beside the 6.1 GB
# rollout, more than a 16 GB chip has). A last axis that is a multiple
# of 128 stays minor-most, rows are contiguous, and the copies are gone
# (0.4 GB and 3.5 GB of temporaries; tests/test_tpu_compile.py).
_ROW = 128


def _flat_grid(a: jnp.ndarray) -> jnp.ndarray:
    a = a.reshape(-1)
    return jnp.pad(a, (0, -a.shape[0] % _ROW))


def _row_scatter(scatter, buf, slot, rows):
    """`buf[b, slot[b]] <- rows[b]` for every lane b by ONE scatter over
    (lane, slot) pairs (`scatter`: `lax.scatter`, `_add` or `_max`); a
    slot out of bounds is dropped. The pairs ARE sorted and unique, and
    the scatter is not told so: `vmap` of a one-lane `.at[slot].set`
    tells it (one index is sorted), the TPU compiler then stores all
    lanes by one row scatter under a linear index with -1 for a
    dropped row, which is sorted no longer, and on the v5e that store
    LOST rows of the widest leaves at 1024 lanes x 640 rows, more of
    them the more lanes sat a row out (PERF.md, PR 42)."""
    pairs = jnp.stack(
        [lax.iota(_i32, buf.shape[0]), slot.astype(_i32)], axis=-1
    )
    return scatter(
        buf, pairs, rows,
        lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(1, rows.ndim)),
            inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1),
        ),
        indices_are_sorted=False, unique_indices=False,
        mode=lax.GatherScatterMode.FILL_OR_DROP,
    )


def _on_own_lanes(fn, lane_shard):
    """`fn` as it is or, on a dp mesh, run once a device over the lanes
    that device holds (`fn` takes and returns trees whose leaves lead
    with the lane axis, and works lane by lane). The partitioner does
    not see that the (lane, slot) pairs of `_row_scatter` stay within a
    lane, and would gather every update to every device; told so, the
    mesh adds nothing to the store. The drain runs so too where a
    device's share is whole blocks (`_DRAIN_BLOCK`): its loops then end
    on the device's own lanes and hold no collective. (`check_vma` is
    off because jax's check of varying axes trips over a reduction
    along a `vmap` axis name, the drain's `lax.pmax(..., "lanes")`;
    every leaf in and out is sharded over every mesh axis, so there is
    nothing for it to infer.)"""
    if lane_shard is None:
        return fn
    return jax.shard_map(
        fn, mesh=lane_shard.mesh, in_specs=lane_shard.spec,
        out_specs=lane_shard.spec, check_vma=False,
    )


# The lanes one drain `while` waits for. A vmapped `lax.while_loop` runs
# until the slowest of ITS lanes is done, so the collectors run the
# drain over blocks of this many lanes, one `while` a block
# (`_flat_collect_single_eval`). 128 is the TPU's lane tile: a block is
# a whole tile of the lane-minor layout the scan's carry has, a
# 128-lane batch is ONE block and compiles the program it always has,
# and so is a chip's share of 512 lanes on four. A constant, not a
# setting: which path a collection takes follows from its lane count
# and its mesh and from nothing else.
_DRAIN_BLOCK = 128


def _by_blocks(fn, block: int):
    """`fn(state, args) -> state`, over trees whose leaves lead with a
    lane axis of `block`, for a lane axis of any whole number of blocks:
    one block after another (a loop: ONE copy of `fn` in the program,
    whatever the number of blocks; never `vmap`, which would make the
    blocks one batch again). A block's lanes are sliced out of the
    full-width `state` and `args` and its new state written back in
    place, so every array keeps the shape, and with it the layout, it
    has outside the loop: stacked blocks (`lax.map`) came back in
    another layout at 512 lanes x 200 jobs, the sampler then summed
    its softmax in another order, and the mesh's log-probs no longer
    matched the one-chip program's to the last bit (PERF.md, PR 43)."""

    def over_blocks(state_and_args):
        state, args = state_and_args
        lanes = jax.tree_util.tree_leaves(state)[0].shape[0]
        if lanes == block:
            return fn(state, args)

        def one_block(i, state):
            at = i * block
            new = fn(*jax.tree_util.tree_map(
                lambda a: lax.dynamic_slice_in_dim(a, at, block),
                (state, args),
            ))
            return jax.tree_util.tree_map(
                lambda a, b: lax.dynamic_update_slice_in_dim(a, b, at, 0),
                state, new,
            )

        return lax.fori_loop(0, lanes // block, one_block, state)

    return over_blocks


class StoredObs(struct.PyTreeNode):
    """Minimal per-step observation record from which `Observation` (and so
    Decima features) can be rebuilt — the padded equivalent of the obs dicts
    the reference keeps in RolloutBuffer.obsns (rollout_worker.py:27-39).
    The [S,S] adjacency is *not* stored: it is reconstructed from the job's
    template id, which shrinks the rollout memory footprint by ~10x.
    F below is J*S rounded up to a multiple of 128 (`_flat_grid`)."""

    remaining: jnp.ndarray  # i32[F]
    duration: jnp.ndarray  # f32[F]
    schedulable: jnp.ndarray  # bool[F]
    node_mask: jnp.ndarray  # bool[F]
    job_mask: jnp.ndarray  # bool[J]
    job_template: jnp.ndarray  # i32[J]
    exec_supplies: jnp.ndarray  # i32[J]
    num_committable: jnp.ndarray  # i32 []
    source_job: jnp.ndarray  # i32 []


def store_obs(obs: Observation, state: EnvState) -> StoredObs:
    # `remaining` comes from the state, not `nodes[..., 0]`: the count
    # must stay exactly i32 even when `params.obs_dtype` narrows the
    # feature bank to bf16 (whose 8-bit mantissa rounds counts > 256);
    # `duration` deliberately inherits the bank's (possibly narrow)
    # dtype — it is the lane-scaled buffer the layout exists to halve
    return StoredObs(
        remaining=_flat_grid(
            jnp.where(obs.node_mask, state.stage_remaining, 0)
        ),
        duration=_flat_grid(obs.nodes[..., 1]),
        schedulable=_flat_grid(obs.schedulable),
        node_mask=_flat_grid(obs.node_mask),
        job_mask=obs.job_mask,
        job_template=state.job_template,
        exec_supplies=obs.exec_supplies,
        num_committable=obs.num_committable,
        source_job=obs.source_job,
    )


def stored_to_observation(bank: WorkloadBank, so: StoredObs) -> Observation:
    """Rebuild the padded Observation a stored step was taken from.

    `node_level` is recomputed from the reconstructed active-subgraph
    adjacency rather than stored: an i32[J,S] per step was ~30% of the
    rollout buffer at the flagship 200-job scale, and the S-deep level
    recursion is a small fraction of the GNN work the observation feeds."""
    j, s = so.job_mask.shape[0], bank.adj.shape[-1]
    so = so.replace(**{
        name: getattr(so, name)[: j * s].reshape(j, s)
        for name in ("remaining", "duration", "schedulable", "node_mask")
    })
    adj = (
        bank.adj[so.job_template]
        & so.node_mask[:, :, None]
        & so.node_mask[:, None, :]
    )
    nodes = jnp.stack(
        [
            so.remaining.astype(jnp.float32),
            # f32 accumulation at the use site: a bf16-recorded
            # duration upcasts losslessly here
            so.duration.astype(jnp.float32),
            so.schedulable.astype(jnp.float32),
        ],
        axis=-1,
    )
    return Observation(
        nodes=nodes,
        node_mask=so.node_mask,
        job_mask=so.job_mask,
        schedulable=so.schedulable,
        frontier=jnp.zeros_like(so.schedulable),  # not needed by any model
        adj=adj,
        node_level=core.topo_levels(so.node_mask, adj),
        exec_supplies=so.exec_supplies,
        num_committable=so.num_committable,
        source_job=so.source_job,
        wall_time=jnp.float32(0.0),
    )


class Rollout(struct.PyTreeNode):
    """One lane's fixed-length rollout (leading [T] axis on per-step
    fields; vmapped collection adds a [B] axis in front)."""

    obs: StoredObs  # [T, ...]
    stage_idx: jnp.ndarray  # i32[T] flat padded node index (-1 = none)
    job_idx: jnp.ndarray  # i32[T]
    num_exec_k: jnp.ndarray  # i32[T] 0-based exec choice (Decima) or n-1
    lgprob: jnp.ndarray  # f32[T]
    reward: jnp.ndarray  # f32[T]
    # wall_times[k] = time of obs k; wall_times[T] = final time
    # (reference rollout_worker.py:154-156 appends the last wall time)
    wall_times: jnp.ndarray  # f32[T+1]
    valid: jnp.ndarray  # bool[T]; step actually happened
    resets: jnp.ndarray  # bool[T]; async: env was reset after this step
    final_state: EnvState
    # async: the next reset ordinal for this lane (drives the group-shared
    # job-sequence key; reference rollout_worker.py:119-120). 0 for sync.
    final_reset_count: jnp.ndarray  # i32 []

    @property
    def num_steps(self) -> jnp.ndarray:
        return self.valid.sum()


# policy_fn(rng, obs) -> (stage_idx, num_exec_1based, aux) where aux is a
# dict containing at least {"lgprob", "job_idx", "num_exec_k"} for
# trainable policies; heuristics may return {}.
PolicyFn = Callable[[jax.Array, Observation], tuple]


def _aux_fields(aux: dict, stage_idx: jnp.ndarray, num_exec: jnp.ndarray,
                max_stages: int):
    # single source of truth shared with the flat engine's record path
    # (env/flat_loop.py:aux_action_fields) so the two collection paths'
    # recorded actions cannot drift apart
    return aux_action_fields(aux, stage_idx, num_exec, max_stages)


@partial(
    jax.jit, static_argnums=(0, 2, 4), static_argnames=("health",)
)
def collect_sync(
    params: EnvParams,
    bank: WorkloadBank,
    policy_fn: PolicyFn,
    rng: jax.Array,
    num_steps: int,
    state: EnvState,
    telemetry=None,
    health: bool = False,
) -> Rollout | tuple:
    """The reference collector over `core.step`, for one lane (vmap
    over lanes); `collect_flat_sync_batch` is what the trainer runs
    and is held to this one step by step (tests/test_flat_loop.py).
    One episode (from the given freshly-reset state), padded to
    `num_steps` decisions (reference RolloutWorkerSync.collect_rollout).
    With `telemetry` (an `obs.Telemetry`), engine counters ride the scan
    carry — rolled back on frozen (done) lanes — and the call returns
    `(Rollout, Telemetry)`. With `health` (static; requires telemetry),
    each live step additionally ORs the `env/health.py` sentinel mask
    over the post-step state + reward into `telemetry.health_mask`."""
    track = telemetry is not None
    if health and not track:
        raise ValueError("health=True requires a telemetry carry")

    def body(carry, _):
        if track:
            st, k, tm = carry
        else:
            (st, k), tm = carry, None
        k, k_pol = jax.random.split(k)
        obs = observe(params, st)
        done = st.terminated | st.truncated
        stage_idx, num_exec, aux = policy_fn(k_pol, obs)
        if track:
            nxt, reward, _, _, tm2 = core.step(
                params, bank, st, stage_idx, num_exec, telemetry=tm
            )
            tm = jax.tree_util.tree_map(
                lambda a, b: jnp.where(done, a, b), tm, tm2
            )
        else:
            nxt, reward, _, _ = core.step(
                params, bank, st, stage_idx, num_exec
            )
        if health:
            hm = state_health(nxt, prev=st) | reward_health(reward)
            tm = _tm_orr(tm, health_mask=jnp.where(done, 0, hm))
        nxt = jax.tree_util.tree_map(
            lambda a, b: jnp.where(done, a, b), st, nxt
        )
        lgprob, job, kk = _aux_fields(
            aux, stage_idx, num_exec, params.max_stages
        )
        rec = (
            store_obs(obs, st),
            jnp.where(done, -1, stage_idx),
            job,
            kk,
            jnp.where(done, 0.0, lgprob),
            jnp.where(done, 0.0, reward),
            st.wall_time,
            ~done,
        )
        return ((nxt, k, tm) if track else (nxt, k)), rec

    carry0 = (state, rng, telemetry) if track else (state, rng)
    carry, (obs, stage_idx, job, kk, lgprob, reward, wt, valid) = (
        lax.scan(body, carry0, None, length=num_steps)
    )
    final = carry[0]
    wall_times = jnp.concatenate([wt, final.wall_time[None]])
    ro = Rollout(
        obs=obs,
        stage_idx=stage_idx,
        job_idx=job,
        num_exec_k=kk,
        lgprob=lgprob,
        reward=reward,
        wall_times=wall_times,
        valid=valid,
        resets=jnp.zeros_like(valid),
        final_state=final,
        final_reset_count=jnp.int32(0),
    )
    return (ro, carry[2]) if track else ro


@partial(
    jax.jit, static_argnums=(0, 2, 4), static_argnames=("health",)
)
def collect_async(
    params: EnvParams,
    bank: WorkloadBank,
    policy_fn: PolicyFn,
    rng: jax.Array,
    num_steps: int,
    state: EnvState,
    rollout_duration: jnp.ndarray | float = jnp.inf,
    seq_base: jax.Array | None = None,
    lane_salt: jnp.ndarray | int = 0,
    reset_count: jnp.ndarray | int = 0,
    telemetry=None,
    health: bool = False,
) -> Rollout | tuple:
    """The reference streaming collector over `core.step`, for one lane
    (vmap over lanes); the trainer runs `collect_flat_async_batch`.
    Fixed sim-time budget with persistent envs and auto-reset (reference
    RolloutWorkerAsync.collect_rollout:171-206). `wall_times` are *elapsed*
    times within the iteration, continuing across resets. Steps after the
    budget is exhausted are masked. With `telemetry`, counters ride the
    scan carry (rolled back on budget-frozen lanes) and the call returns
    `(Rollout, Telemetry)`.

    Mid-scan resets draw the new episode from
    ``fold_in(seq_base, reset_count)`` — so lanes that share `seq_base`
    (a sequence group) replay identical job-arrival sequences at equal
    reset ordinals, which the grouped critic-free baseline relies on
    (reference ``base_seed + seed_step * reset_count``,
    rollout_worker.py:119-120, trainer.py:268-271). `lane_salt`
    de-correlates the per-lane stochastic stream within a group
    (core.reset_pair's seq/lane split). When `seq_base` is None (ad-hoc
    use outside a trainer), `rng` stands in for it."""
    track = telemetry is not None
    if health and not track:
        raise ValueError("health=True requires a telemetry carry")
    rollout_duration = jnp.float32(rollout_duration)
    if seq_base is None:
        seq_base = rng
    lane_salt = jnp.asarray(lane_salt, _i32)
    reset_count = jnp.asarray(reset_count, _i32)

    def body(carry, _):
        if track:
            st, k, elapsed, rc, tm = carry
        else:
            (st, k, elapsed, rc), tm = carry, None
        k, k_pol = jax.random.split(k)
        obs = observe(params, st)
        over = elapsed >= rollout_duration
        stage_idx, num_exec, aux = policy_fn(k_pol, obs)
        if track:
            nxt, reward, term, trunc, tm2 = core.step(
                params, bank, st, stage_idx, num_exec, telemetry=tm
            )
            tm = jax.tree_util.tree_map(
                lambda a, b: jnp.where(over, a, b), tm, tm2
            )
        else:
            nxt, reward, term, trunc = core.step(
                params, bank, st, stage_idx, num_exec
            )
        if health:
            # on the post-step, PRE-reset state (the reset select below
            # swaps in a fresh episode for done lanes)
            hm = state_health(nxt, prev=st) | reward_health(reward)
            tm = _tm_orr(tm, health_mask=jnp.where(over, 0, hm))
        new_elapsed = elapsed + (nxt.wall_time - st.wall_time)
        done = term | trunc

        # unconditional reset + tree-select rather than lax.cond: a
        # lane-dependent cond broadcasts the closed-over workload bank
        # across the vmap batch (see env/core.py structural note)
        seq_rng = jax.random.fold_in(seq_base, rc)
        fresh = core.reset_pair(
            params, bank, seq_rng, jax.random.fold_in(seq_rng, lane_salt)
        )
        did_reset = done & ~over
        nxt2 = jax.tree_util.tree_map(
            lambda a, b: jnp.where(did_reset, a, b), fresh, nxt
        )
        # budget exhausted: freeze the lane
        nxt2 = jax.tree_util.tree_map(
            lambda a, b: jnp.where(over, a, b), st, nxt2
        )
        new_elapsed = jnp.where(over, elapsed, new_elapsed)
        new_rc = rc + did_reset.astype(_i32)
        lgprob, job, kk = _aux_fields(
            aux, stage_idx, num_exec, params.max_stages
        )
        rec = (
            store_obs(obs, st),
            jnp.where(over, -1, stage_idx),
            job,
            kk,
            jnp.where(over, 0.0, lgprob),
            jnp.where(over, 0.0, reward),
            elapsed,
            ~over,
            did_reset,
        )
        carry = (
            (nxt2, k, new_elapsed, new_rc, tm)
            if track
            else (nxt2, k, new_elapsed, new_rc)
        )
        return carry, rec

    carry0 = (state, rng, jnp.float32(0.0), reset_count)
    if track:
        carry0 = carry0 + (telemetry,)
    carry, (
        obs, stage_idx, job, kk, lgprob, reward, wt, valid, resets
    ) = lax.scan(body, carry0, None, length=num_steps)
    final, elapsed, final_rc = carry[0], carry[2], carry[3]
    wall_times = jnp.concatenate([wt, elapsed[None]])
    ro = Rollout(
        obs=obs,
        stage_idx=stage_idx,
        job_idx=job,
        num_exec_k=kk,
        lgprob=lgprob,
        reward=reward,
        wall_times=wall_times,
        valid=valid,
        resets=resets,
        final_state=final,
        final_reset_count=final_rc,
    )
    return (ro, carry[4]) if track else ro


# ---------------------------------------------------------------------------
# flat-engine collection (env/flat_loop.py): the trainer's collectors
#
# The per-decision `core.step` scan above pays the straggler tax of a
# vmapped `lax.while_loop` that holds observe and the policy too. The
# collectors below drive the flat micro-step engine instead and scatter
# one record per decision into the same fixed-shape `Rollout` the
# trainers consume. The scan is decision-synchronous over the WHOLE lane
# batch, and ONE policy evaluation is both acted on and recorded per
# decision row:
#
#   scan iteration k == decision k:
#     observe -> batch_policy (ONE eval over the [B] lane stack, with
#     the Decima job-compaction cond at batch level) ->
#     vmap(decide_micro_step) (acts on + records the same outputs) ->
#     vmap(drain_to_decision) (non-policy micro-steps until every lane
#     is at its next decision)
#
# The drain is a batch-max while-loop between decisions, over the env
# machinery alone (bulk passes + pops); the GNN runs exactly once per
# decision (test-pinned by a counting-policy test in
# tests/test_flat_loop.py). On the TPU v5e that loop, not the GNN, is
# most of a decision row (PERF.md section 5). Collected quantities are
# step-exact vs the `core.step` path (tests/test_flat_loop.py parity
# test): actions, log-probs, the valid mask, per-decision wall times
# and rewards (the micro-step reward deltas telescope to `core.step`'s
# per-decision span quantity — see `core._compute_jobtime`'s `t_ref`
# note).
#
# Every operation of the scan body runs under one of the trace scopes
# `collect/observe`, `decima/features`, `decima/gnn`, `decima/sample`,
# `env/micro_step` (`decide`, `drain`), `collect/health`,
# `collect/freeze` and `collect/scatter` (key splits apart;
# tests/test_obs.py pins it), and with a telemetry carry the body
# counts its rows (`obs/telemetry.py`: `rows`, `rows_live`,
# `rows_full_width`, `drain_batch_iters`, `lane_syncs`, and per lane
# `rows_frozen`).
# In streaming mode (`auto_reset`) a lane whose episode ended in the
# row's drain is re-seeded once, after the drain's loop and under one
# predicate for the batch (`flat_loop._reseed_ended`, scope
# `env/micro_step/reset`); `reseeds` counts it for the lane and
# `reset_evals` the rows in which the reset program ran.
# ---------------------------------------------------------------------------


def _zero_stored(params: EnvParams) -> StoredObs:
    j, s = params.max_jobs, params.max_stages
    # duration mirrors the observation bank's dtype (params.obs_dtype):
    # the scan carry's buffer and the per-step `store_obs` record must
    # agree or the collection scan fails its carry dtype check
    dur_dt = (
        jnp.bfloat16 if params.obs_dtype == "bfloat16" else jnp.float32
    )
    f = _flat_grid(jnp.zeros((j, s), bool)).shape
    return StoredObs(
        remaining=jnp.zeros(f, _i32),
        duration=jnp.zeros(f, dur_dt),
        schedulable=jnp.zeros(f, bool),
        node_mask=jnp.zeros(f, bool),
        job_mask=jnp.zeros((j,), bool),
        job_template=jnp.zeros((j,), _i32),
        exec_supplies=jnp.zeros((j,), _i32),
        num_committable=_i32(0),
        source_job=_i32(-1),
    )


class _FlatBuf(struct.PyTreeNode):
    """Fixed-offset per-decision buffers the collection scan scatters
    into, carried through the scan: a lane's decision lands in its own
    slot `ndec`, so a row in which the lane did not decide stores
    nothing."""

    obs: StoredObs  # [B, T, ...]
    stage_idx: jnp.ndarray  # i32[B, T]
    job_idx: jnp.ndarray  # i32[B, T]
    num_exec_k: jnp.ndarray  # i32[B, T]
    lgprob: jnp.ndarray  # f32[B, T]
    reward: jnp.ndarray  # f32[B, T]
    walls: jnp.ndarray  # f32[B, T]
    resets: jnp.ndarray  # i32[B, T]


# batch policy: policy_fn(rng, obs_with_leading_B_axis) -> per-lane
# (stage_idx[B], num_exec[B], aux-of-[B]) from ONE evaluation — see
# DecimaScheduler.batch_policy / flat_batch_policy.
BatchPolicyFn = Callable[[jax.Array, Observation], tuple]


def _flat_collect_single_eval(
    params: EnvParams,
    bank: WorkloadBank,
    batch_policy_fn: BatchPolicyFn,
    rng: jax.Array,
    num_steps: int,
    ls: LoopState,  # [B]-batched
    auto_reset: bool,
    event_bulk: bool,
    bulk_events: int,
    fulfill_bulk: bool,
    bulk_cycles: int,
    reset_fns,  # None, or a per-lane factory: a lane's reset_args -> reset_fn
    rollout_duration,
    use_elapsed: bool,
    telemetry=None,
    lane_shard=None,
    bulk_fused: bool = True,
    health: bool = False,
    reset_args=None,  # [B]-leading tree for `reset_fns`; None: the lane index
):
    """Shared single-eval collection scan over the WHOLE lane batch
    (`ls` carries a leading [B] axis; no outer vmap). Exactly
    `num_steps` scan iterations, each producing at most one decision
    per lane; see the section comment above for the shape.

    The drain is the one part of a row that does NOT run over the whole
    batch where the batch is more than one block of `_DRAIN_BLOCK`
    lanes: lanes are independent, a vmapped `while` waits for the
    slowest of its lanes, so a device's lanes are drained a block at a
    time (`_by_blocks`), each block under its own `while` and its own
    named lane axis: the drain `while`'s predicate, the fused bulk
    pass's and the streaming re-seed's are then the BLOCK's, and a
    block whose lanes are all at a decision (or all ended) pays one
    predicate. The rule is the shape's: `B` a whole number of blocks
    and more than one or, on a dp mesh, a device's share whole blocks,
    where the drain then runs once a device over its own lanes
    (`_on_own_lanes`) and holds no collective. Any other lane count (a
    single block, fewer lanes, no multiple) drains the whole batch
    under one `while`, as every collection did before. Under threefry
    keys no stored bit depends on which lanes a lane waits for; under
    rbg keys a vmapped draw takes the FIRST lane's key (`parallel.py`),
    so a block draws from its own first lane, on a mesh as on one chip.
    What a blocked row still reduces over ALL lanes: `rows_live`'s
    `any`, the policy's full-width predicate and, streaming,
    `reset_evals`' `any`.

    `lane_shard` (a `NamedSharding` over the lane axis, parallel.py:
    `lane_sharding`) pins the scan's carry — the [B] `LoopState`, the
    [B,T] decision buffers and the per-lane telemetry — to the dp mesh
    via `with_sharding_constraint`, so the whole collection runs SPMD
    with the lane axis sharded end-to-end instead of leaving the carry
    layout to the partitioner's fallback (which can silently replicate
    the largest resident buffers of the program).

    With `telemetry`, each decision row also advances the five
    batch-level row counters (`rows`, `rows_live`, `rows_full_width`,
    `drain_batch_iters`, `lane_syncs`: `obs/telemetry.py`), once per
    row and outside the drain's `while`; `rows_full_width` reads the
    policy's
    `aux["full_width"]` and stays 0 for a policy that gives none.
    In a blocked row `drain_batch_iters` takes the bodies the lane's
    OWN block ran, and `lane_syncs` the row's reductions over all the
    lanes, not the blocks' own predicates.
    The per-lane `rows_frozen` counts the rows a lane sat out with its
    `rollout_duration` spent (0 without a budget).

    With `health` (static; requires telemetry), each decision row ORs
    the per-lane `env/health.py` sentinel mask over the post-drain
    state + the row's accumulated reward into
    `telemetry.health_mask`."""
    track = telemetry is not None
    if health and not track:
        raise ValueError("health=True requires a telemetry carry")
    T = num_steps
    B = ls.mode.shape[0]
    s_cap = params.max_stages
    zs = _zero_stored(params)
    buf0 = _FlatBuf(
        obs=jax.tree_util.tree_map(
            lambda a: jnp.zeros((B, T) + a.shape, a.dtype), zs
        ),
        stage_idx=jnp.zeros((B, T), _i32),
        job_idx=jnp.zeros((B, T), _i32),
        num_exec_k=jnp.zeros((B, T), _i32),
        lgprob=jnp.zeros((B, T), jnp.float32),
        reward=jnp.zeros((B, T), jnp.float32),
        walls=jnp.zeros((B, T), jnp.float32),
        resets=jnp.zeros((B, T), _i32),
    )
    if lane_shard is not None:
        from ..parallel import constrain_lanes

        ls = constrain_lanes(ls, lane_shard)
        buf0 = constrain_lanes(buf0, lane_shard)
        if track:
            telemetry = constrain_lanes(telemetry, lane_shard)
    if reset_args is None:
        reset_args = jnp.arange(B)
    dp = 1 if lane_shard is None else lane_shard.mesh.size
    blocked = B % (dp * _DRAIN_BLOCK) == 0 and B > _DRAIN_BLOCK
    # the reductions over ALL the lanes a row makes outside its drain:
    # `rows_live`'s `any`, streaming `reset_evals`' too; a policy with
    # two widths adds its predicate (`aux["full_width"]`), counted in
    # the body
    row_syncs = 2 if auto_reset else 1

    def v_decide(ls, si, ne, tm):
        def one(l, s_, n_, t_):
            return decide_micro_step(
                params, bank, l, s_, n_, fulfill_bulk, telemetry=t_
            )

        return jax.vmap(one)(ls, si, ne, tm)

    def drain_lanes(a):  # (ls, keys, reset_args, t_ref, tm) of some lanes
        def one(l, k_, ra, tr, t_):
            rf = None if reset_fns is None else reset_fns(ra)
            return drain_to_decision(
                params, bank, l, k_, auto_reset, event_bulk,
                bulk_events, bulk_cycles, reset_fn=rf, t_ref=tr,
                telemetry=t_, bulk_fused=bulk_fused, lane_axis="lanes",
            )

        # the lane axis has a name so that the fused bulk pass can end
        # its loop, and the re-seed after the drain be skipped, on one
        # predicate for all these lanes
        return jax.vmap(one, axis_name="lanes")(*a)

    def drain_block(state, args):
        """One block's drain, on its lanes of the state `v_drain`
        threads through the blocks: `LoopState` and telemetry go on,
        the span's `(reward, dt, reset)` is written and, with
        telemetry, the bodies the block's `while` ran (the most any
        lane of it needed), in every lane."""
        ls, _, tm, _ = state
        out = drain_lanes((ls,) + args + (tm,))
        if not track:
            return out + (None, None)
        ran = out[2].drain_iters - tm.drain_iters
        return out + (jnp.broadcast_to(ran.max(), ran.shape),)

    v_drain = drain_lanes
    if blocked:
        drain_blocks = _on_own_lanes(
            _by_blocks(drain_block, _DRAIN_BLOCK), lane_shard
        )

        def v_drain(a):
            ls, keys, ra, t_ref, tm = a
            zero = jnp.zeros((B,), jnp.float32)
            ran = jnp.zeros((B,), _i32) if track else None
            # the blocks' slices and the write-back are the drain's too
            with annotate("env/micro_step/drain"):
                return drain_blocks((
                    (ls, (zero, zero, zero > 0), tm, ran),
                    (keys, ra, t_ref),
                ))

    def body(carry, _):
        if track:
            ls, k, t_ref, elapsed, ndec, buf, tm = carry
        else:
            (ls, k, t_ref, elapsed, ndec, buf), tm = carry, None
        tm_frozen = tm
        # the third key is not used (the decide step draws nothing):
        # the policy and the drain keep the second and the fourth, so
        # every stream, and with it every rollout, is what it is
        k, k_pol, _, k_drain = jax.random.split(k, 4)
        env0 = ls.env
        wall0 = env0.wall_time  # [B]

        # THE policy evaluation of this decision row (batch-level: one
        # net application, compaction cond on a scalar predicate)
        with annotate("collect/observe"):
            obs = jax.vmap(lambda e: observe(params, e))(env0)
        stage_idx, num_exec, aux = batch_policy_fn(k_pol, obs)

        out = v_decide(ls, stage_idx, num_exec, tm)
        if track:
            ls2, (decided, rw1, dt1, rs1), tm = out
        else:
            ls2, (decided, rw1, dt1, rs1) = out
        with annotate("collect/freeze"):
            if rollout_duration is not None:
                over = elapsed >= rollout_duration
            else:
                over = jnp.zeros((B,), bool)
            # discount reference for the span this decision opens (the
            # decide micro-step itself never advances the wall clock)
            t_ref2 = jnp.where(decided & ~over, wall0, t_ref)
            if rollout_duration is not None:
                # a frozen lane's row is rolled back below whatever it
                # did, so it sits the drain out (a lane in DECIDE mode
                # does not enter the `while`): left to run, it repeats
                # the span after its last decision in every row, and
                # all 128 lanes wait for it (PERF.md, PR 30)
                ls2 = ls2.replace(
                    mode=jnp.where(over, M_DECIDE, ls2.mode)
                )

        out = v_drain(
            (ls2, jax.random.split(k_drain, B), reset_args, t_ref2, tm)
        )
        ls3, (rw2, dt2, rs2) = out[:2]
        if track:
            tm = out[2]
        with annotate("collect/freeze"):
            reward = rw1 + rw2
            dt = dt1 + dt2
            reset = rs1 | rs2
        if health:
            with annotate("collect/health"):
                hm = jax.vmap(state_health)(
                    ls3.env, env0, reset
                ) | reward_health(reward)

        with annotate("collect/freeze"):
            # frozen lanes (async budget exhausted): state untouched,
            # nothing recorded
            ls3 = jax.tree_util.tree_map(
                lambda a, b: jnp.where(
                    over.reshape(over.shape + (1,) * (a.ndim - 1)), a, b
                ),
                ls, ls3,
            )
            dec = decided & ~over
            if track:
                if blocked:
                    # the bodies the lane's own block ran; the
                    # predicates of its loops cross no block and are
                    # not counted
                    drained, drain_syncs = out[3], 0
                else:
                    # the bodies the vmapped drain `while` ran this row
                    # (every lane, frozen ones too, waits for the
                    # slowest) and the predicates of the fused passes
                    # in them: the lane that ran longest counted every
                    # one. One reduction over the lanes for both.
                    drained, pass_syncs = jnp.stack(
                        [tm.drain_iters - tm_frozen.drain_iters,
                         tm.lane_syncs - tm_frozen.lane_syncs], -1
                    ).max(0)
                    # the passes' predicates, the drain `while`'s (its
                    # bodies and the one that ended it), this maximum
                    # and, streaming, the re-seed's
                    # (`flat_loop._reseed_ended`)
                    drain_syncs = pass_syncs + drained + (
                        3 if auto_reset else 2
                    )
                tm = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(over, a, b), tm_frozen, tm
                ).replace(lane_syncs=tm_frozen.lane_syncs)
                # the row counters are facts of the batch: added after
                # the freeze, so every lane holds the same value
                tm = _tm_add(
                    tm, rows=1, rows_live=dec.any(),
                    rows_full_width=aux.get("full_width", False),
                    drain_batch_iters=drained, rows_frozen=over,
                    lane_syncs=drain_syncs + row_syncs
                    + ("full_width" in aux),
                )
                if tm.counts_episodes:
                    # a lane whose own episode was over at the row's
                    # start sits the row out (sync: nothing re-seeds
                    # it); the jobs a stored decision saw
                    tm = _tm_add(
                        tm,
                        rows_ended=jax.vmap(_lane_done)(env0) & ~over,
                        jobs_present_sum=jnp.where(
                            dec, obs.job_mask.sum(-1, dtype=_i32), 0
                        ),
                    )
                if auto_reset:
                    # the drain's re-seed ran iff some lane ended its
                    # episode there (a frozen lane sits the drain out,
                    # so `reset` is already without the frozen lanes)
                    tm = _tm_add(tm, reset_evals=reset.any())
            if health:
                tm = _tm_orr(tm, health_mask=jnp.where(over, 0, hm))
            zero = jnp.float32(0.0)
            reward = jnp.where(over, zero, reward)
            dt = jnp.where(over, zero, dt)
            reset = reset & ~over
            elapsed2 = elapsed + dt

        with annotate("collect/scatter"):
            lgprob, job, kk = aux_action_fields(
                aux, stage_idx, num_exec, s_cap
            )
            # heuristic batch policies may omit lgprob (scalar default);
            # the per-lane buffer scatters need a [B] leading axis
            lgprob = jnp.broadcast_to(
                jnp.asarray(lgprob, jnp.float32), stage_idx.shape
            )
            stored = jax.vmap(store_obs)(obs, env0)
            slot = jnp.where(dec & (ndec < T), ndec, T)
            ndec2 = ndec + dec.astype(_i32)
            # span rewards belong to the most recent decision's slot;
            # spans before a resumed lane's first decision drop
            rslot = jnp.where((ndec2 > 0) & (ndec2 <= T), ndec2 - 1, T)

            def store(a):
                buf, row, slot, rslot, reward, reset = a
                row = jax.tree_util.tree_map(
                    lambda b, v: _row_scatter(lax.scatter, b, slot, v),
                    {k: getattr(buf, k) for k in row}, row,
                )
                return buf.replace(
                    reward=_row_scatter(
                        lax.scatter_add, buf.reward, rslot, reward
                    ),
                    resets=_row_scatter(
                        lax.scatter_max, buf.resets, rslot, reset
                    ),
                    **row,
                )

            buf = _on_own_lanes(store, lane_shard)((
                buf,
                dict(
                    obs=stored, stage_idx=stage_idx, job_idx=job,
                    num_exec_k=kk, lgprob=lgprob,
                    walls=elapsed if use_elapsed else wall0,
                ),
                slot, rslot, reward, reset.astype(_i32),
            ))
        carry = (ls3, k, t_ref2, elapsed2, ndec2, buf)
        return (carry + (tm,) if track else carry), None

    carry0 = (
        ls, rng, ls.env.wall_time, jnp.zeros((B,), jnp.float32),
        jnp.zeros((B,), _i32), buf0,
    )
    if track:
        carry0 = carry0 + (telemetry,)
    carry, _ = lax.scan(body, carry0, None, length=T)
    ls, elapsed, ndec, buf = carry[0], carry[3], carry[4], carry[5]
    if track:
        telemetry = carry[6]

    valid = jnp.arange(T)[None, :] < jnp.minimum(ndec, T)[:, None]
    final_t = elapsed if use_elapsed else ls.env.wall_time
    walls = jnp.where(valid, buf.walls, final_t[:, None])
    ro = Rollout(
        obs=buf.obs,
        stage_idx=jnp.where(valid, buf.stage_idx, -1),
        job_idx=buf.job_idx,
        num_exec_k=buf.num_exec_k,
        lgprob=buf.lgprob,
        reward=buf.reward,
        wall_times=jnp.concatenate([walls, final_t[:, None]], axis=1),
        valid=valid,
        resets=buf.resets > 0,
        final_state=ls.env,
        final_reset_count=ls.episodes,
    )
    return (ro, ls, telemetry) if track else (ro, ls)


@partial(
    jax.jit, static_argnums=(0, 2, 4),
    static_argnames=(
        "event_bulk", "bulk_events", "fulfill_bulk", "bulk_cycles",
        "lane_shard", "bulk_fused", "health",
    ),
)
def collect_flat_sync_batch(
    params: EnvParams,
    bank: WorkloadBank,
    batch_policy_fn: BatchPolicyFn,
    rng: jax.Array,
    num_steps: int,
    states: EnvState,  # [B]-batched, freshly reset
    telemetry=None,
    *,
    event_bulk: bool = True,
    bulk_events: int = 8,
    fulfill_bulk: bool = True,
    bulk_cycles: int = 1,
    lane_shard=None,
    bulk_fused: bool = True,
    health: bool = False,
) -> Rollout | tuple:
    """The trainer's sync collector, the flat-engine equivalent of
    `vmap(collect_sync)`: one episode per lane from the given
    freshly-reset [B] states, exactly one policy evaluation per
    decision row (the scan length IS `num_steps`). With `telemetry` ([B]-leading), returns
    `(Rollout, Telemetry)`. `lane_shard` (static; a lane-axis
    `NamedSharding`) runs the collection SPMD over a dp mesh — see
    `_flat_collect_single_eval`. `health` (static) ORs the in-JIT
    sentinel mask into `telemetry.health_mask` per decision row."""
    ls = jax.vmap(init_loop_state)(states)
    out = _flat_collect_single_eval(
        params, bank, batch_policy_fn, rng, num_steps, ls,
        auto_reset=False, event_bulk=event_bulk,
        bulk_events=bulk_events, fulfill_bulk=fulfill_bulk,
        bulk_cycles=bulk_cycles, reset_fns=None, rollout_duration=None,
        use_elapsed=False, telemetry=telemetry, lane_shard=lane_shard,
        bulk_fused=bulk_fused, health=health,
    )
    return (out[0], out[2]) if telemetry is not None else out[0]


def _group_reset_fns(params, bank):
    """The streaming batch collector's per-lane factory of reset
    programs: `reset_fns((seq_base, reset_count, lane_salt))(key,
    episodes)` is that lane's episode at the group-shared ordinal
    `reset_count + episodes`. It ignores `key`: the fresh state is a
    function of the lane's sequence base, its ordinal and its salt
    alone, so it is the same wherever in a row it is evaluated. The
    three come a lane at a time (the collector maps them with the
    lanes), not as whole arrays under a lane index: a drain that runs
    over a block or a device's share of the lanes holds only its own."""

    def reset_fns(lane):
        seq_base, reset_count, lane_salt = lane

        def reset_fn(key, episodes):
            seq_rng = jax.random.fold_in(seq_base, reset_count + episodes)
            return core.reset_pair(
                params, bank, seq_rng,
                jax.random.fold_in(seq_rng, lane_salt),
            )

        return reset_fn

    return reset_fns


@partial(
    jax.jit, static_argnums=(0, 2, 4),
    static_argnames=(
        "event_bulk", "bulk_events", "fulfill_bulk", "bulk_cycles",
        "lane_shard", "bulk_fused", "health",
    ),
)
def collect_flat_async_batch(
    params: EnvParams,
    bank: WorkloadBank,
    batch_policy_fn: BatchPolicyFn,
    rng: jax.Array,
    num_steps: int,
    loop_states: LoopState,  # [B]-batched
    rollout_duration: jnp.ndarray | float = jnp.inf,
    seq_bases: jax.Array | None = None,  # [B] keys
    lane_salts: jnp.ndarray | int = 0,  # [B]
    reset_counts: jnp.ndarray | int = 0,  # [B]
    telemetry=None,
    *,
    event_bulk: bool = True,
    bulk_events: int = 8,
    fulfill_bulk: bool = True,
    bulk_cycles: int = 1,
    lane_shard=None,
    bulk_fused: bool = True,
    health: bool = False,
) -> tuple:
    """The trainer's streaming collector, the flat-engine equivalent
    of `vmap(collect_async)`: persistent [B] lanes, fixed sim-time
    budget, group-shared mid-scan reset sequences from
    `fold_in(seq_bases[i], reset_counts[i] + completed_episodes)`.
    Budget granularity is the decision row (the same as
    `collect_async`). Takes and returns the full `LoopState` (a
    budget-frozen lane may be mid-FULFILL/EVENT phase, which `EnvState`
    alone cannot represent). Returns `(Rollout, LoopState[,
    Telemetry])`. `lane_shard` (static) runs the collection SPMD over
    a dp mesh — see `_flat_collect_single_eval`; the returned
    `LoopState` carry stays lane-sharded, so the next iteration's
    collection starts from shards already resident on their devices."""
    rollout_duration = jnp.float32(rollout_duration)
    B = loop_states.mode.shape[0]
    if seq_bases is None:
        seq_bases = jax.random.split(rng, B)
    lane_salts = jnp.broadcast_to(
        jnp.asarray(lane_salts, _i32), (B,)
    )
    reset_counts = jnp.broadcast_to(
        jnp.asarray(reset_counts, _i32), (B,)
    )
    loop_states = loop_states.replace(episodes=jnp.zeros((B,), _i32))
    out = _flat_collect_single_eval(
        params, bank, batch_policy_fn, rng, num_steps, loop_states,
        auto_reset=True, event_bulk=event_bulk, bulk_events=bulk_events,
        fulfill_bulk=fulfill_bulk, bulk_cycles=bulk_cycles,
        reset_fns=_group_reset_fns(params, bank),
        rollout_duration=rollout_duration,
        use_elapsed=True, telemetry=telemetry, lane_shard=lane_shard,
        bulk_fused=bulk_fused, health=health,
        reset_args=(seq_bases, reset_counts, lane_salts),
    )
    ro, ls = out[0], out[1]
    ro = ro.replace(final_reset_count=reset_counts + ls.episodes)
    if telemetry is not None:
        return ro, ls, out[2]
    return ro, ls
