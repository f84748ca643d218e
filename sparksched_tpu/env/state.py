"""Struct-of-arrays environment state.

Replaces the reference's Python object graph (Job/Stage/Task/Executor +
ExecutorTracker dicts + heapq event queue; reference spark_sched_sim/
components/) with fixed-shape arrays so `jax.vmap` can run thousands of
environments and `lax.while_loop` can drive the event loop on-device.

Encoding conventions
--------------------
Pool keys (reference components/executor_tracker.py:4-10) become integer
pairs: job == -1 means the common pool ("general pool"); stage == -1 means a
job pool; (job >= 0, stage >= 0) is a stage pool. A separate validity flag
stands in for the `None` placeholder pool.

Events (reference components/event.py): instead of a heap, every pending
event lives in the array that naturally owns it — job arrival times [J],
per-executor task finish times [N], per-executor move arrival times [N] —
each with the sequence number it was "pushed" with. The next event is the
lexicographic argmin of (time, seq), which reproduces the reference heap's
exact FIFO tie-breaking (event.py:34-35).

Commitments (reference executor_tracker dict-of-dicts): a slot table of at
most `num_executors` rows. This bound is exact: the tracker enforces
supply >= demand per pool (executor_tracker.py:234-236) and pools partition
the executors, so the total outstanding commitment count never exceeds N.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..config import EnvParams

# event kinds, dispatch order matches reference handler registration
# (spark_sched_sim.py:68-72)
EV_JOB_ARRIVAL, EV_TASK_FINISHED, EV_EXECUTOR_READY = 0, 1, 2

# numpy scalars, not jnp: creating a jax array at import time would
# initialize the backend (and claim the TPU) on `import sparksched_tpu`;
# numpy dtypes carry through jnp ops identically
INF = np.float32(np.inf)
BIG_SEQ = np.int32(2**30)
# stages a word of a packed stage set holds (`EnvState.parent_sets`)
STAGE_SET_BITS = 32


def topo_levels(active: jnp.ndarray, adj_act: jnp.ndarray) -> jnp.ndarray:
    """i32[J,S] topological generation of each active node in the masked
    subgraph; padding = S. Matches nx.topological_generations on the
    observed dag batch (reference decima/utils.py:238-267). Lives here —
    the leaf module — so the env core, the observation path and the
    golden `node_level_golden` property all share ONE copy of the
    reduction (core re-exports it)."""
    from jax import lax

    s_cap = active.shape[1]

    def body(_, lvl):
        cand = jnp.where(adj_act, lvl[:, :, None] + 1, 0).max(axis=1)
        return jnp.maximum(lvl, cand)

    lvl = lax.fori_loop(
        0, s_cap, body, jnp.zeros(active.shape, jnp.int32)
    )
    return jnp.where(active, lvl, s_cap)


class EnvState(struct.PyTreeNode):
    # --- rng / time ---
    rng: jnp.ndarray
    wall_time: jnp.ndarray  # f32 []
    time_limit: jnp.ndarray  # f32 []; inf if no time limit
    seq_counter: jnp.ndarray  # i32 []; next event/commitment sequence number

    # --- episode flags ---
    round_ready: jnp.ndarray  # bool []; a scheduling round is in progress
    terminated: jnp.ndarray  # bool []
    truncated: jnp.ndarray  # bool []

    # --- jobs [J] ---
    job_template: jnp.ndarray  # i32[J]
    job_arrival_time: jnp.ndarray  # f32[J]; inf for padding slots
    job_arrival_seq: jnp.ndarray  # i32[J]
    job_arrived: jnp.ndarray  # bool[J]
    job_t_completed: jnp.ndarray  # f32[J]; inf until completed
    job_num_stages: jnp.ndarray  # i32[J]
    job_saturated_stages: jnp.ndarray  # i32[J] (reference job.py:41)
    job_supply: jnp.ndarray  # i32[J]; _total_executor_count, maintained with
    # the reference's exact increments (executor_tracker.py:146-231) —
    # including its staleness for saturated jobs whose idle executors moved
    # to the common pool without a decrement
    num_jobs: jnp.ndarray  # i32 []; actual arrivals this episode

    # --- stages [J,S] ---
    stage_exists: jnp.ndarray  # bool[J,S]
    stage_num_tasks: jnp.ndarray  # i32[J,S]
    stage_remaining: jnp.ndarray  # i32[J,S]
    stage_executing: jnp.ndarray  # i32[J,S]
    stage_completed_tasks: jnp.ndarray  # i32[J,S]
    stage_duration: jnp.ndarray  # f32[J,S]; most_recent_duration
    stage_selected: jnp.ndarray  # bool[J,S]; selected this scheduling round
    schedulable: jnp.ndarray  # bool[J,S]; saved schedulable set for round
    adj: jnp.ndarray  # bool[J,S,S]; adj[j,p,c] == True iff edge p->c

    # --- executors [N] ---
    exec_at_common: jnp.ndarray  # bool[N]
    exec_job: jnp.ndarray  # i32[N]; attached job, -1 = none (common/moving)
    exec_stage: jnp.ndarray  # i32[N]; stage pool residence, -1 = none
    exec_moving: jnp.ndarray  # bool[N]
    exec_dst_job: jnp.ndarray  # i32[N]
    exec_dst_stage: jnp.ndarray  # i32[N]
    exec_arrive_time: jnp.ndarray  # f32[N]; inf if not moving
    exec_arrive_seq: jnp.ndarray  # i32[N]
    exec_executing: jnp.ndarray  # bool[N]
    exec_task_valid: jnp.ndarray  # bool[N]; executor.task is not None
    exec_task_stage: jnp.ndarray  # i32[N]; stage of current/last task
    exec_finish_time: jnp.ndarray  # f32[N]; inf if not executing
    exec_finish_seq: jnp.ndarray  # i32[N]

    # --- incremental scheduling caches [J,S] ---
    # stage saturation and per-stage parent counts, maintained at the few
    # mutation points instead of recomputed via [J,S,S] reductions on every
    # find_schedulable/frontier access inside the event loop (the dominant
    # TPU cost before this; the golden recomputations remain as properties
    # for invariant tests)
    stage_sat: jnp.ndarray  # bool[J,S]; exec_demand <= 0
    unsat_parent_count: jnp.ndarray  # i32[J,S]; parents with ~sat & exists
    incomplete_parent_count: jnp.ndarray  # i32[J,S]; parents not completed
    # each stage's parents as a bit set: `adj` packed over its parent
    # axis (`core.pack_parents`; W = ceil(S / 32) words), written where
    # `adj` is, at reset, and read by the fused bulk pass, whose refresh
    # of `unsat_parent_count` counts flipped parents on it instead of
    # contracting the whole [J,S,S] adjacency in every drain body
    parent_sets: jnp.ndarray  # u32[J,W,S]; bit p%32 of [j,p//32,c] = adj[j,p,c]
    # what the duration sampler reads of a (job, stage) that no draw
    # decides, one word each (`sampling.pack_duration_facts` of the
    # bank, the rows of the episode's templates): bits 0 to 7 the
    # executor levels the stage has first-wave samples at, bits 8 to
    # 31 which of its 3 x 8 (wave, level) buckets hold a sample.
    # Written where `job_template` is, at reset; a fact of the
    # episode, so loop-invariant in every drain, where the fused bulk
    # pass's step picks its stage's word with the one-hot over [J,S]
    # it builds for its updates and reads no bank table for it
    duration_facts: jnp.ndarray  # u32[J,S]

    # --- incremental node-level cache [J,S] ---
    # per-job topological generations over the job's existing, incomplete
    # stages (padding = max_stages), maintained at the ONLY mutation point
    # that changes a job's active subgraph — stage completion in
    # `_handle_task_finished` (bulk passes never complete a stage) — by a
    # depth-bounded single-job [S,S] pass. Replaces the per-observation
    # S-deep [J,S,S] reduction (`compute_node_levels`, the documented most
    # expensive part of `observe`); job arrival/termination need no
    # recompute because the cache ignores `job_active` and the observation
    # masks with `node_mask`. Golden recomputation: `node_level_golden`.
    node_level: jnp.ndarray  # i32[J,S]

    # --- incremental executor-flow counters [J,S] ---
    # the reference maintains these as dicts (_num_commitments_to_stage /
    # _num_moving_to_stage, executor_tracker.py); recomputing them by
    # scatter on every find_schedulable call dominated the event loop on
    # TPU (scatters serialize), so they are first-class state updated at
    # the four mutation points (commit add/consume, send, arrival)
    commit_count: jnp.ndarray  # i32[J,S]
    moving_count: jnp.ndarray  # i32[J,S]

    # --- commitment slots [N] ---
    cm_valid: jnp.ndarray  # bool[N]
    cm_src_job: jnp.ndarray  # i32[N]
    cm_src_stage: jnp.ndarray  # i32[N]
    cm_dst_job: jnp.ndarray  # i32[N]; -1 = common pool destination
    cm_dst_stage: jnp.ndarray  # i32[N]
    cm_seq: jnp.ndarray  # i32[N]

    # --- executor source (reference executor_tracker _curr_source) ---
    source_valid: jnp.ndarray  # bool []
    source_job: jnp.ndarray  # i32 []; -1 = common pool
    source_stage: jnp.ndarray  # i32 []

    # ---------------- derived quantities ----------------

    @property
    def stage_completed(self) -> jnp.ndarray:
        """bool[J,S]; a stage is completed when all its tasks completed
        (reference components/stage.py:40)."""
        return self.stage_exists & (
            self.stage_completed_tasks >= self.stage_num_tasks
        )

    @property
    def job_completed(self) -> jnp.ndarray:
        """bool[J]; no incomplete stages remain (reference job.py:49-50)."""
        done = jnp.where(self.stage_exists, self.stage_completed, True)
        return self.job_arrived & done.all(axis=1)

    @property
    def job_active(self) -> jnp.ndarray:
        """bool[J]; arrived and not completed == membership of
        active_job_ids, which stays sorted by arrival order == job id."""
        return self.job_arrived & ~self.job_completed

    @property
    def job_saturated(self) -> jnp.ndarray:
        """bool[J] (reference job.py:53-54)."""
        return self.job_saturated_stages >= self.job_num_stages

    @property
    def frontier(self) -> jnp.ndarray:
        """bool[J,S]; incomplete stages whose parents all completed
        (reference job.py:24-26, maintained incrementally there AND here,
        via `incomplete_parent_count`). Identical to "no incoming edges in
        the active subgraph" computed by heuristic preprocessing
        (schedulers/heuristics/utils.py:5-14)."""
        return (
            self.stage_exists
            & ~self.stage_completed
            & (self.incomplete_parent_count == 0)
        )

    @property
    def frontier_golden(self) -> jnp.ndarray:
        """Recomputed frontier for invariant tests."""
        incomplete_parent = self.adj & ~self.stage_completed[:, :, None]
        blocked = incomplete_parent.any(axis=1)
        return self.stage_exists & ~self.stage_completed & ~blocked

    @property
    def node_level_golden(self) -> jnp.ndarray:
        """Recomputed per-job topological generations over existing,
        incomplete stages — the golden version of the incremental
        `node_level` field (the shared `topo_levels` reduction above)."""
        active = self.stage_exists & ~self.stage_completed
        adj_act = self.adj & active[:, :, None] & active[:, None, :]
        return topo_levels(active, adj_act)

    @property
    def commit_count_to_stage(self) -> jnp.ndarray:
        """i32[J,S]; slot-derived commitment counts — the slow golden
        version of the incremental `commit_count` field, kept for
        invariant checks in tests."""
        j_cap, s_cap = self.stage_exists.shape
        flat = jnp.zeros(j_cap * s_cap + 1, dtype=jnp.int32)
        idx = jnp.where(
            self.cm_valid & (self.cm_dst_job >= 0),
            self.cm_dst_job * s_cap + self.cm_dst_stage,
            j_cap * s_cap,
        )
        flat = flat.at[idx].add(1)
        return flat[:-1].reshape(j_cap, s_cap)

    @property
    def moving_count_to_stage(self) -> jnp.ndarray:
        """i32[J,S]; executor-derived moving counts — golden version of
        the incremental `moving_count` field, for invariant checks."""
        j_cap, s_cap = self.stage_exists.shape
        flat = jnp.zeros(j_cap * s_cap + 1, dtype=jnp.int32)
        idx = jnp.where(
            self.exec_moving,
            self.exec_dst_job * s_cap + self.exec_dst_stage,
            j_cap * s_cap,
        )
        flat = flat.at[idx].add(1)
        return flat[:-1].reshape(j_cap, s_cap)

    @property
    def exec_demand(self) -> jnp.ndarray:
        """i32[J,S]; remaining tasks minus (moving + committed) executors
        (reference spark_sched_sim.py:566-578). Can be negative."""
        return self.stage_remaining - (
            self.moving_count + self.commit_count
        )

    @property
    def stage_saturated(self) -> jnp.ndarray:
        """bool[J,S] (reference :580-582). Golden recomputation of the
        incremental `stage_sat` field."""
        return self.exec_demand <= 0

    @property
    def all_jobs_complete(self) -> jnp.ndarray:
        j = jnp.arange(self.job_arrived.shape[0], dtype=jnp.int32)
        return jnp.where(j < self.num_jobs, self.job_completed, True).all()

    # --- pools ---

    def pool_member_mask(self, job: jnp.ndarray, stage: jnp.ndarray
                         ) -> jnp.ndarray:
        """bool[N]; executors residing in pool (job, stage)."""
        common = self.exec_at_common
        at_job_pool = (self.exec_job == job) & (self.exec_stage == -1) & \
            ~self.exec_at_common & ~self.exec_moving
        at_stage_pool = (self.exec_job == job) & (self.exec_stage == stage)
        return jnp.where(
            job < 0, common, jnp.where(stage < 0, at_job_pool, at_stage_pool)
        )

    def source_pool_mask(self) -> jnp.ndarray:
        mask = self.pool_member_mask(self.source_job, self.source_stage)
        return jnp.where(self.source_valid, mask, False)

    def commitments_from_source(self) -> jnp.ndarray:
        """i32 []; total outgoing commitments from the source pool."""
        match = (
            self.cm_valid
            & (self.cm_src_job == self.source_job)
            & (self.cm_src_stage == self.source_stage)
        )
        return jnp.where(self.source_valid, match.sum(), 0).astype(jnp.int32)

    def num_committable(self) -> jnp.ndarray:
        """i32 []; source pool size minus its outgoing commitments
        (reference executor_tracker.py:105-111)."""
        return (
            self.source_pool_mask().sum().astype(jnp.int32)
            - self.commitments_from_source()
        )

    def source_job_id(self) -> jnp.ndarray:
        """i32 []; -1 when source is the common pool or cleared (the
        reference returns None in both cases, executor_tracker.py:98-102)."""
        return jnp.where(self.source_valid, self.source_job, -1)


def empty_state(params: EnvParams, rng: jax.Array) -> EnvState:
    """All-zero template state with the right shapes/dtypes."""
    j, s, n = params.max_jobs, params.max_stages, params.num_executors
    f32 = jnp.float32
    i32 = jnp.int32
    return EnvState(
        rng=rng,
        wall_time=f32(0),
        time_limit=INF,
        seq_counter=i32(0),
        round_ready=jnp.bool_(False),
        terminated=jnp.bool_(False),
        truncated=jnp.bool_(False),
        job_template=jnp.zeros(j, i32),
        job_arrival_time=jnp.full(j, INF, f32),
        job_arrival_seq=jnp.zeros(j, i32),
        job_arrived=jnp.zeros(j, bool),
        job_t_completed=jnp.full(j, INF, f32),
        job_num_stages=jnp.zeros(j, i32),
        job_saturated_stages=jnp.zeros(j, i32),
        job_supply=jnp.zeros(j, i32),
        num_jobs=i32(0),
        stage_exists=jnp.zeros((j, s), bool),
        stage_num_tasks=jnp.zeros((j, s), i32),
        stage_remaining=jnp.zeros((j, s), i32),
        stage_executing=jnp.zeros((j, s), i32),
        stage_completed_tasks=jnp.zeros((j, s), i32),
        stage_duration=jnp.zeros((j, s), f32),
        stage_selected=jnp.zeros((j, s), bool),
        schedulable=jnp.zeros((j, s), bool),
        adj=jnp.zeros((j, s, s), bool),
        exec_at_common=jnp.ones(n, bool),
        exec_job=jnp.full(n, -1, i32),
        exec_stage=jnp.full(n, -1, i32),
        exec_moving=jnp.zeros(n, bool),
        exec_dst_job=jnp.full(n, -1, i32),
        exec_dst_stage=jnp.full(n, -1, i32),
        exec_arrive_time=jnp.full(n, INF, f32),
        exec_arrive_seq=jnp.zeros(n, i32),
        exec_executing=jnp.zeros(n, bool),
        exec_task_valid=jnp.zeros(n, bool),
        exec_task_stage=jnp.full(n, -1, i32),
        exec_finish_time=jnp.full(n, INF, f32),
        exec_finish_seq=jnp.zeros(n, i32),
        stage_sat=jnp.ones((j, s), bool),
        unsat_parent_count=jnp.zeros((j, s), i32),
        incomplete_parent_count=jnp.zeros((j, s), i32),
        parent_sets=jnp.zeros(
            (j, -(-s // STAGE_SET_BITS), s), jnp.uint32
        ),
        duration_facts=jnp.zeros((j, s), jnp.uint32),
        node_level=jnp.full((j, s), s, i32),
        commit_count=jnp.zeros((j, s), i32),
        moving_count=jnp.zeros((j, s), i32),
        cm_valid=jnp.zeros(n, bool),
        cm_src_job=jnp.full(n, -1, i32),
        cm_src_stage=jnp.full(n, -1, i32),
        cm_dst_job=jnp.full(n, -1, i32),
        cm_dst_stage=jnp.full(n, -1, i32),
        cm_seq=jnp.zeros(n, i32),
        source_valid=jnp.bool_(False),
        source_job=i32(-1),
        source_stage=i32(-1),
    )
