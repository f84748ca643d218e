"""The sweep loop: any `Scheduler` over thousands of episodes on one
chip, ended lanes re-seeded inside the scan, an episode's result taken
where it ends.

A sweep scores a scheduler by per-episode average job completion time
over many job sequences (the Decima paper's Figure 9; upstream's README
claim against Spark's fair scheduler). It is the third caller of the two
primitives the trainer's collectors and the decision service share,
`flat_loop.decide_micro_step` and `flat_loop.drain_to_decision`, and
drains in blocks of `rollout._DRAIN_BLOCK` lanes through the same
`rollout._by_blocks`. What is its own:

- **a carry that goes in and comes out** (`SweepCarry`, every leaf
  leading with the lane axis): the lanes' `LoopState`, each lane's id
  and base key, and the decisions of its episode so far. `init` makes
  one of reset states drawn by the seed law or of states the caller
  made; a carry a chunk returned, and any concatenation of such
  carries along the lane axis (`concat`), is a valid carry: lanes are
  independent, so each goes on bit for bit as it would have alone.
- **a seed law**: episode `ordinal` of lane `id` is
  `core.reset(fold_in(fold_in(sweep key, id), ordinal))`, a job
  sequence of its own for every (lane, ordinal) and the same one
  whatever the number of lanes beside it. The law needs threefry keys
  (under rbg a vmapped draw takes its first lane's key:
  `parallel.py`), so `init` refuses another default implementation.
- **a record of a few words a decision** (`SweepRecord`, rows first):
  `valid`, the decision's time, job, stage and executor count, `reset`
  on the row an episode ends in and, there, the episode's result: its
  average job completion time, the jobs it completed, its makespan
  (`metrics.episode_result`, read by `drain_to_decision` before the
  re-seed) and its decisions; under a policy that states one, the
  decision's log-probability (`lgprob`).
- no engine switch: the bulk-pass constants below are the values the
  trainer passes at the flagship.

One decision row: observe the lanes, evaluate the policy over them
(`Scheduler.batch_policy`), apply `decide_micro_step`, drain to the
next decision; over more than one block of lanes the drain runs a block
of 128 lanes at a time, and with a net in the row (`DecimaScheduler`;
its weights an argument of the chunk, `sweep_chunk(..., weights)`) all
four do, in ONE loop over the blocks, so that the net is one copy in
the program and holds activations for a block only. Device
scopes: `collect/observe`, `sweep/policy`,
`env/micro_step/decide`, `env/micro_step/drain`, `env/micro_step/reset`,
`collect/health`, `sweep/record`. Host span: `sweep/chunk_call` around
every call of the compiled chunk (trace, lower and compile or load on a
first call, dispatch alone afterwards), `setup/sweep_init` around
`init`; jax's compile events land in `obs.tracing.RECORD` beside them.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax import lax

from .config import EnvParams, env_params_from_cfg
from .env import core
from .env.flat_loop import (
    LoopState,
    decide_micro_step,
    drain_to_decision,
    init_loop_state,
)
from .env.health import reward_health, state_health
from .env.observe import observe
from .env.state import EnvState
from .metrics import episode_result
from .obs.telemetry import add as _tm_add
from .obs.telemetry import orr as _tm_orr
from .obs.telemetry import summarize, telemetry_zeros_like
from .obs.tracing import annotate, span, spanned
from .schedulers.base import TrainableScheduler
from .trainers.rollout import _DRAIN_BLOCK, _by_blocks
from .workload.bank import WorkloadBank

_i32 = jnp.int32

# the flat engine's bulk passes, as `Trainer` passes them at the
# flagship (`trainers/trainer.py: flat_knobs`): constants, not settings
EVENT_BULK, BULK_EVENTS, FULFILL_BULK, BULK_CYCLES, BULK_FUSED = (
    True, 8, True, 1, True)


class SweepCarry(struct.PyTreeNode):
    """What a chunk is handed and hands back; every leaf leads with the
    lane axis. `ls.episodes`, the lane's completed episodes, is the
    ordinal of the episode it is in."""

    ls: LoopState
    lane: jnp.ndarray  # i32[B]; the lane's id under the seed law
    key: jax.Array  # [B] keys; fold_in(sweep key, lane)
    decisions: jnp.ndarray  # i32[B]; of the episode in progress

    @property
    def ordinal(self) -> jnp.ndarray:
        return self.ls.episodes


class SweepRecord(struct.PyTreeNode):
    """A chunk's decision rows, [rows, lanes]. The last four hold an
    episode's result on the row it ended in (`reset`), 0 elsewhere."""

    valid: jnp.ndarray  # bool; the lane decided in this row
    wall_time: jnp.ndarray  # f32; the decision's sim-time
    job: jnp.ndarray  # i32; -1: the policy chose no stage
    stage: jnp.ndarray  # i32
    num_exec: jnp.ndarray  # i32; as the policy gave it
    reset: jnp.ndarray  # bool; the episode ended after this decision
    ordinal: jnp.ndarray  # i32; the episode the row belongs to
    avg_jct: jnp.ndarray  # f32; ms
    jobs_completed: jnp.ndarray  # i32
    makespan: jnp.ndarray  # f32; ms
    decisions: jnp.ndarray  # i32
    # f32; the log-probability the policy states of its decision
    # (`aux["lgprob"]`); None, and no leaf, where it states none
    lgprob: jnp.ndarray | None = None


def lane_keys(key: jax.Array, lanes: jnp.ndarray) -> jax.Array:
    """The base keys of the lanes with ids `lanes` under the sweep key."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(lanes)


def episode_state(params: EnvParams, bank: WorkloadBank, lane_key: jax.Array,
                  ordinal: jnp.ndarray) -> EnvState:
    """The seed law: the reset state of a lane's episode `ordinal`."""
    return core.reset(params, bank, jax.random.fold_in(lane_key, ordinal))


@partial(jax.jit, static_argnums=0)
def _reset_lanes(params, bank, keys):
    return jax.vmap(
        lambda k: episode_state(params, bank, k, _i32(0)))(keys)


def init(params: EnvParams, bank: WorkloadBank, key: jax.Array,
         lanes: int | None = None, *, states: EnvState | None = None
         ) -> SweepCarry:
    """A carry of `lanes` reset states drawn by the seed law under the
    sweep key `key` (episode 0 of lanes 0 to `lanes` - 1), or of the
    [B]-leading `states` the caller made, which need not be reset
    states; a lane's later episodes follow the law either way."""
    if jax.config.jax_default_prng_impl != "threefry2x32":
        raise ValueError(
            "the sweep's seed law needs threefry keys; the default "
            f"implementation is {jax.config.jax_default_prng_impl!r}")
    with span("setup/sweep_init"):
        if lanes is None:
            lanes = jax.tree_util.tree_leaves(states)[0].shape[0]
        lane_ids = jnp.arange(lanes, dtype=_i32)
        keys = lane_keys(key, lane_ids)
        if states is None:
            states = _reset_lanes(params, bank, keys)
        return SweepCarry(
            ls=jax.vmap(init_loop_state)(states), lane=lane_ids, key=keys,
            decisions=jnp.zeros(lane_ids.shape, _i32))


def concat(carries: list[SweepCarry]) -> SweepCarry:
    """The carries' lanes side by side: a valid carry. Lanes that share
    an id run the same episodes from their next re-seed on, so give
    copies ids of their own (`carry.replace(lane=ids,
    key=lane_keys(key, ids))`)."""
    return jax.tree_util.tree_map(
        lambda *a: jnp.concatenate(a, axis=0), *carries)


def _chunk(params: EnvParams, bank: WorkloadBank, policy: Callable,
           carry: SweepCarry, rng: jax.Array, rows: int,
           weights: Any = None):
    """`rows` decision rows over every lane of `carry`: the new carry,
    the rows' `SweepRecord` and the chunk's `Telemetry` ([lanes]; the
    collectors' row counters, the episode counters and
    `episode_decisions_sum`, from zero; the in-JIT health sentinels of
    `env/health.py` ORed into its `health_mask` every row).
    `policy(rng, obs)` is a scheduler's `batch_policy`; only it reads
    `rng`: an episode's draws come from its own state.

    Where the lanes are more than one block of `_DRAIN_BLOCK` and whole
    blocks, the drain runs a block at a time. What runs beside it is
    chosen by ONE static fact, whether the policy comes with `weights`
    (a `TrainableScheduler`'s parameters, which `run` hands over):

    - without (a heuristic, a `vmap` of scalar work): observe, policy
      and decide once over all lanes, the drain alone block by block:
      the program `sweep_fair` has been measured with since PR 46, to
      the byte (`tests/test_sweep_decima.py` holds its lowered text).
      With a block's whole row inside the loop the chunk no longer
      loads beside that cell's 26,624 lanes (PERF.md, PR 49);
    - with (a net: `policy(keys, obs, weights)`, one key a lane, split
      from the row's key over ALL the lanes): ONE loop over the blocks
      whose body is a block's whole row (observe, policy, decide,
      drain), so that an observation and the net's activations exist
      for 128 lanes at a time, one copy of the net is in the program
      whatever the lane count, and no stored bit depends on the block
      layout. The weights are an ARGUMENT of the compiled program:
      another checkpoint of the same net sweeps through the program
      that is there.

    Any other lane count runs either row once over the whole batch. A
    policy whose `aux` states a log-probability (`lgprob`: a net's
    decision) has it recorded, and the nodes its decisions saw counted
    (`nodes_present_sum`); any other policy's record and telemetry hold
    no such leaf. Such a policy without `weights` is refused: as
    closure constants a net's parameters make every checkpoint a
    program of its own."""
    lanes = carry.lane.shape[0]
    s_cap = params.max_stages
    blocked = lanes % _DRAIN_BLOCK == 0 and lanes > _DRAIN_BLOCK
    width = _DRAIN_BLOCK if blocked else lanes
    net = weights is not None

    def observe_lanes(env):
        return jax.vmap(lambda e: observe(params, e))(env)

    # what the policy states of a decision, from its shapes alone
    scored = "lgprob" in jax.eval_shape(
        lambda k, env: policy(
            k, observe_lanes(env), *((weights,) if net else ()))[2],
        *jax.tree_util.tree_map(
            lambda a: a[:width], (carry.key, carry.ls.env)))
    if scored and not net:
        raise ValueError(
            "a policy that states a log-probability is a net: hand its "
            "parameters to the chunk as `weights`")

    def decide_lanes(ls, stage_idx, num_exec, tm):
        return jax.vmap(lambda l, s, n, t: decide_micro_step(
            params, bank, l, s, n, FULFILL_BULK, telemetry=t
        ))(ls, stage_idx, num_exec, tm)

    def drain_block(state, args):
        """The drain of the lanes one `while` spans (a block, or the
        whole batch): `LoopState` and telemetry go on; written in every
        lane are the span (with the episode's result), the bodies the
        `while` ran and whether it ran the reset program."""
        ls, _, tm, _, _ = state

        def one(l, k, lane_key, t):
            return drain_to_decision(
                params, bank, l, k, True, EVENT_BULK, BULK_EVENTS,
                BULK_CYCLES, telemetry=t, bulk_fused=BULK_FUSED,
                lane_axis="lanes", result_fn=episode_result,
                # the re-seed hands over the episodes completed BEFORE
                # the one that just ended: the new episode's ordinal
                # is one more than that
                reset_fn=lambda _, before: episode_state(
                    params, bank, lane_key, before + 1),
            )

        ls2, ended_span, tm2 = jax.vmap(one, axis_name="lanes")(
            ls, *args, tm)
        ran = tm2.drain_iters - tm.drain_iters
        return (ls2, ended_span, tm2,
                jnp.broadcast_to(ran.max(), ran.shape),
                jnp.broadcast_to(ended_span[2].any(), ran.shape))

    if blocked:
        over_blocks = _by_blocks(drain_block, _DRAIN_BLOCK)

        def drain(state_and_args):
            # the blocks' slices and the write-back are the drain's too
            with annotate("env/micro_step/drain"):
                return over_blocks(state_and_args)
    else:
        def drain(state_and_args):
            return drain_block(*state_and_args)

    zero = jnp.zeros((lanes,), jnp.float32)
    count0 = jnp.zeros((lanes,), _i32)
    span0 = (zero, zero, zero > 0, {
        "avg_jct": zero, "jobs_completed": count0, "makespan": zero})

    def net_row(state, args):
        """A net's decision row of the lanes `state` leads with (a
        block, or the whole batch): observe, policy, decide, then the
        drain under the lanes' own `while`. The lanes' `LoopState` and
        telemetry go on; the rest of `state` is written."""
        ls, tm = state["ls"], state["tm"]
        k_pol, k_drain, lane_key = args
        with annotate("collect/observe"):
            obs = observe_lanes(ls.env)
        with annotate("sweep/policy"):
            stage_idx, num_exec, aux = policy(k_pol, obs, weights)
        ls, (decided, rw1, _, _), tm = decide_lanes(
            ls, stage_idx, num_exec, tm)
        with annotate("sweep/record"):
            seen = {"jobs_present_sum": obs.job_mask}
            if scored:
                seen["nodes_present_sum"] = obs.node_mask.reshape(
                    obs.node_mask.shape[0], -1)
            tm = _tm_add(tm, **{
                name: jnp.where(decided, mask.sum(-1, dtype=_i32), 0)
                for name, mask in seen.items()})
        with annotate("env/micro_step/drain"):
            ls, span, tm, drained, paid = drain_block(
                (ls, None, tm, None, None), (k_drain, lane_key))
        return dict(
            ls=ls, tm=tm, stage_idx=stage_idx, num_exec=num_exec,
            decided=decided, rw1=rw1, span=span, drained=drained,
            paid=paid, **({"lgprob": aux["lgprob"]} if scored else {}))

    written = dict(
        stage_idx=count0, num_exec=count0, decided=zero > 0, rw1=zero,
        span=span0, drained=count0, paid=zero > 0,
        **({"lgprob": zero} if scored else {}))

    def body(c, _):
        carry, k, tm = c
        ls = carry.ls
        k, k_pol, k_drain = jax.random.split(k, 3)
        env0 = ls.env
        if net:
            # a block's whole row inside the loop over blocks, every
            # lane under a key of its own
            out = _by_blocks(net_row, width)((
                dict(written, ls=ls, tm=tm),
                (jax.random.split(k_pol, lanes),
                 jax.random.split(k_drain, lanes), carry.key)))
            ls3, tm2, stage_idx, num_exec, decided, rw1, drained, paid = (
                out[name] for name in (
                    "ls", "tm", "stage_idx", "num_exec", "decided", "rw1",
                    "drained", "paid"))
            rw2, _, ended, result = out["span"]
        else:
            # a heuristic is a `vmap` of scalar work: observe, policy
            # and decide once over all lanes, the drain block by block
            with annotate("collect/observe"):
                obs = observe_lanes(env0)
            with annotate("sweep/policy"):
                stage_idx, num_exec, _ = policy(k_pol, obs)
            ls2, (decided, rw1, _, _), tm1 = decide_lanes(
                ls, stage_idx, num_exec, tm)
            ls3, (rw2, _, ended, result), tm2, drained, paid = drain((
                (ls2, span0, tm1, count0, zero > 0),
                (jax.random.split(k_drain, lanes), carry.key),
            ))
        if blocked:
            # a block's loops end on the block's own predicates
            drain_syncs = 0
        else:
            # the fused passes' predicates (the lane that ran longest
            # counted every one), the `while`'s (its bodies and the
            # one that ended it), the maximum and the re-seed's
            drain_syncs = (tm2.lane_syncs - tm.lane_syncs).max() + (
                drained + 3)
        tm2 = tm2.replace(lane_syncs=tm.lane_syncs)
        with annotate("collect/health"):
            tm2 = _tm_orr(tm2, health_mask=jax.vmap(state_health)(
                ls3.env, env0, ended) | reward_health(rw1 + rw2))
        with annotate("sweep/record"):
            taken = carry.decisions + decided.astype(_i32)
            chose = stage_idx >= 0
            row = SweepRecord(
                valid=decided, wall_time=env0.wall_time,
                job=jnp.where(chose, stage_idx // s_cap, -1),
                stage=jnp.where(chose, stage_idx % s_cap, -1),
                num_exec=num_exec, reset=ended, ordinal=ls.episodes,
                decisions=jnp.where(ended, taken, 0),
                lgprob=out["lgprob"] if scored else None,
                **{name: jnp.where(ended, v, jnp.zeros_like(v))
                   for name, v in result.items()},
            )
            # the row counters are facts of the batch (`rows_live`'s
            # `any` is the one reduction over all lanes a blocked row
            # makes); `reset_evals` of the lane's own `while`
            counted = dict(
                rows=1, rows_live=decided.any(), drain_batch_iters=drained,
                lane_syncs=drain_syncs + 1, reset_evals=paid)
            if not net:  # a net's row counts what its blocks saw
                counted["jobs_present_sum"] = jnp.where(
                    decided, obs.job_mask.sum(-1, dtype=_i32), 0)
            tm2 = _tm_add(
                tm2, **counted, episode_decisions_sum=row.decisions)
        carry = carry.replace(
            ls=ls3, decisions=jnp.where(ended, 0, taken))
        return (carry, k, tm2), row

    tm0 = telemetry_zeros_like(
        (lanes,), episodes=True, results=True, nodes=scored)
    (carry, _, tm), record = lax.scan(
        body, (carry, rng, tm0), None, length=rows)
    return carry, record, tm


# `sweep_chunk(params, bank, policy, carry, rng, rows, weights=None)`:
# the one jitted chunk program (`_chunk`), under the host span
# `sweep/chunk_call`. `params`, `policy` and `rows` are static; the
# carry is not donated (a caller may keep the one it handed in)
sweep_chunk = spanned("sweep/chunk_call", jax.jit(
    _chunk, static_argnums=(0, 2, 5)))


def add_telemetry(total, tm):
    """Two chunks' telemetry as one: counters add, the health mask's
    bits OR (`total` None: `tm`)."""
    if total is None:
        return tm
    return jax.tree_util.tree_map(jnp.add, total, tm).replace(
        health_mask=total.health_mask | tm.health_mask)


def results_of(record: SweepRecord, lane: Any) -> dict[str, np.ndarray]:
    """The results of the episodes that ended in `record` (on the host),
    one entry an episode, in row order: `lane`, `ordinal`, `avg_jct`,
    `jobs_completed`, `makespan`, `decisions`."""
    rec = jax.device_get(record)
    row, col = np.nonzero(rec.reset)
    out = {"lane": np.asarray(lane)[col]}
    for name in ("ordinal", "avg_jct", "jobs_completed", "makespan",
                 "decisions"):
        out[name] = np.asarray(getattr(rec, name))[row, col]
    return out


def run(params: EnvParams, bank: WorkloadBank, scheduler, *, episodes: int,
        lanes: int | None = None, seed: int = 0, rows: int = 64,
        states: EnvState | None = None, policy: Callable | None = None,
        max_chunks: int | None = None) -> dict:
    """Sweeps `scheduler` (its `batch_policy`, or `policy` where given:
    a Decima scheduler's greedy `partial(batch_policy,
    deterministic=True)`; a `TrainableScheduler`'s is called with the
    scheduler's `params` third, an argument of the compiled chunk)
    over `episodes` episodes on `lanes` lanes: chunk after chunk of
    `rows` rows until the results of episodes 0 to `episodes` - 1 are
    in, episode e being ordinal `e // lanes` of lane `e % lanes` (a set
    fixed beforehand: the first results to come in would favour short
    episodes). `states`: the lanes' first episodes, made by the caller.
    Returns their results as arrays ordered by (ordinal, lane),
    `mean_avg_jct`, and what it took: `chunks`, `decisions_total` and the
    chunks' summed `telemetry`."""
    key_law, key_run = jax.random.split(jax.random.PRNGKey(seed))
    carry = init(params, bank, key_law, lanes, states=states)
    lanes = int(carry.lane.shape[0])
    policy = scheduler.batch_policy if policy is None else policy
    # a net's parameters go in as an argument of the chunk
    weights = (scheduler.params
               if isinstance(scheduler, TrainableScheduler) else None)
    per_lane = math.ceil(episodes / lanes)
    found: dict[tuple[int, int], dict] = {}
    chunks, decisions, telemetry = 0, 0, None
    while len(found) < episodes:
        if max_chunks is not None and chunks >= max_chunks:
            raise RuntimeError(
                f"{len(found)} of {episodes} episodes ended in "
                f"{chunks} chunks of {rows} rows")
        carry, record, tm = sweep_chunk(
            params, bank, policy, carry,
            jax.random.fold_in(key_run, chunks), rows, weights)
        chunks += 1
        telemetry = add_telemetry(telemetry, tm)
        decisions += int(record.valid.sum())
        res = results_of(record, carry.lane)
        for i, (lane, ordinal) in enumerate(zip(res["lane"], res["ordinal"])):
            if ordinal < per_lane and ordinal * lanes + lane < episodes:
                found[(int(ordinal), int(lane))] = {
                    name: col[i] for name, col in res.items()}
    rows = [found[k] for k in sorted(found)]
    out: dict = {name: np.asarray([r[name] for r in rows]) for name in res}
    return dict(out, mean_avg_jct=float(out["avg_jct"].mean()),
                chunks=chunks, decisions_total=decisions,
                telemetry=summarize(telemetry))


def from_config(cfg: dict):
    """`(params, bank, scheduler)` of a sweep's YAML (`env:` and
    `agent:` blocks as `train.py`'s), built as the trainer builds
    them: a Decima net's level scan is bounded by the bank's depth
    unless the `agent:` block states `num_levels`."""
    from .schedulers import make_scheduler
    from .workload import bank_depth, make_workload_bank

    env_cfg = cfg["env"]
    params = env_params_from_cfg(env_cfg)
    with span("setup/workload_bank"):
        bank = make_workload_bank(
            params.num_executors, params.max_stages,
            **{k: v for k, v in env_cfg.items()
               if k in ("data_dir", "bucket_size", "data_sampler_cls",
                        "bank_dtype")})
    if bank.max_stages != params.max_stages:
        params = params.replace(
            max_stages=bank.max_stages,
            max_levels=max(params.max_levels, bank.max_stages))
    with span("setup/scheduler_init"):
        scheduler = make_scheduler(
            {"num_levels": bank_depth(bank)} | cfg["agent"]
            | {"num_executors": params.num_executors})
    return params, bank, scheduler
