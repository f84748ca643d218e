"""On-device telemetry counters for both rollout engines.

A `Telemetry` is a tiny pytree of i32 scalars (one per lane when the
engine is vmapped) threaded through the hot loops as *pure adds inside
jit* — no host callbacks, no side effects, a handful of scalar ops per
iteration against loop bodies of thousands. Both engines take it as an
optional argument and are bit-identical no-ops when it is omitted
(`telemetry=None` skips the threading entirely, so the off path costs
zero).

Counter semantics per engine:

- `env/core.py` (per-decision `step`): `decide_steps` counts live step
  calls (one per policy commitment), `commit_rounds` finished rounds,
  `loop_iters` the `_resume_simulation` while-loop body iterations —
  under vmap the loop batching masks the carry for lanes whose cond is
  false, so each lane counts exactly ITS iteration count and the
  straggler tax (max/mean over lanes) is measured, not inferred.
  `event_steps` / `ev_*` count single event pops by kind;
  `bulk_relaunch_events` / `bulk_ready_events` the events consumed by
  the vectorized passes; `fulfill_steps` / `bulk_fulfill_hits` the
  one-at-a-time vs bulk-prefix fulfillments.
- `env/flat_loop.py` (micro-step engine): `decide_steps` /
  `fulfill_steps` / `event_steps` count live micro-steps by entry mode
  (the micro-step composition), `loop_iters` the events consumed per
  lane (pops + bulk passes) — the lane-imbalance quantity the flat
  engine absorbs without stalling. `bulk_scan_steps` counts the steps
  the fused bulk pass's early-exit loop needed for the lane
  (`core._bulk_events_fused`: one per event taken, plus the step that
  saw the run end; 0 for a pass that was not enabled), of a budget of
  `bulk_events + num_executors` a pass: how much of that budget the
  traffic uses. Under vmap the device runs the largest need over the
  lanes of each pass, not each lane's own.
- the single-eval batch collectors (`trainers/rollout.py`:
  `collect_flat_sync_batch`, `collect_flat_async_batch`) also set the
  four ROW counters, once per decision row of the scan and never inside
  the drain's `while`: `rows` (scan iterations), `rows_live` (rows in
  which some lane decided), `rows_full_width` (rows whose policy took
  its full-width branch, `DecimaScheduler.full_width`; 0 for a policy
  that reports none) and `drain_batch_iters` (the maximum over lanes of
  the row's `drain_iters` increment: the bodies the vmapped `while`
  really ran, which every lane pays for). They are facts of the batch,
  not of a lane, so every lane holds the same value; no other engine or
  collector touches them. The one exception: a collection of more than
  one block of lanes (`rollout._DRAIN_BLOCK`; whole blocks a device)
  drains block by block, each block under its own `while`, and a lane
  waits for the slowest lane of its OWN block only: `drain_batch_iters`
  is then the maximum over the lane's block, the same in every lane of
  a block and another from block to block. `summarize` sums it over
  the lanes (`row.drain_lane_iters_executed`: the bodies the device
  ran, a lane at a time) and gives the mean over the lanes as
  `row.drain_batch_iters`; with one block both are what they always
  were. A fifth, `lane_syncs`, counts the REDUCTIONS
  OVER THE LANE AXIS the row executed: every evaluation of the fused
  bulk pass's loop predicate (`core._steps_while_active` with a named
  lane axis: its iterations and the one that ended it, in every body
  of the drain), every evaluation of the drain `while`'s batched
  predicate (its bodies and one), and the row's own (the policy's
  full-width predicate, the maximum that gives `drain_batch_iters`,
  `rows_live`'s `any`; streaming adds `reset_evals`' `any` and the
  re-seed's predicate). On one chip each is a local reduction; on a
  dp mesh each is an all-reduce across the chips, on the critical path.
  Inside the drain's loop the lane that runs longest accumulates the
  passes' predicates in its own `lane_syncs` (one scalar of the carry);
  the row takes the maximum over lanes with `drain_batch_iters`', in
  the one reduction. `lane_syncs` counts reductions over ALL the lanes
  of the batch. A blocked row's loops end on predicates of a block,
  which cross no chip (on a mesh the blocked drain runs a device at a
  time, `rollout._on_own_lanes`) and are not counted: such a row
  counts `rows_live`'s `any`, the full-width predicate and, streaming,
  `reset_evals`' `any`, and nothing else.
- streaming (`auto_reset`): `reseeds` counts the lane's episodes that
  ended in the scan and were re-seeded, `reset_evals` the evaluations
  of the reset program (`reset_fn` / `core.reset` and the select of the
  state against it) the lane was part of. In the single-eval batch
  collector (PR 31) the program runs once a decision row, after the
  drain, and only in a row in which some unfrozen lane's episode ended
  (`flat_loop.drain_to_decision`): `reset_evals` is then a fact of the
  batch, +1 in EVERY lane for such a row, frozen lanes too, so
  `reset_evals_total / reseeds_total` is at most the number of lanes.
  In the loops whose unit is the micro-step
  (`flat_loop._finish_micro_step` under `micro_step` and
  `drain_micro_step`) the tail evaluates it in
  every micro-step the lane takes, whether or not an episode ended,
  and counts one each. `rows_frozen` (the batch collectors, once a
  row) counts the decision rows the lane sat out because its sim-time
  budget was spent (`over`); a frozen lane's other counters, `reseeds`
  included, stand still for the row. All three stay 0 in sync mode
  (`auto_reset=False`, no budget).
- episodes that end inside the scan (the batched-arrivals deployment,
  where every lane's does): three counters carried only where asked
  for (`telemetry_zeros_like(..., episodes=True)`; the trainer's
  `obs.episode_counters`). They are None otherwise, no leaf of the
  carry, and an engine adds to them only where they are there, so a
  program that does not ask traces as if they did not exist.
  `rows_ended` (the batch collectors, once a row) counts the decision
  rows a lane sat out because its OWN episode was over at the row's
  start, which only a sync lane can be (a streaming lane is re-seeded
  in the row its episode ends in); `episodes_terminated`
  (`flat_loop.drain_to_decision`) the lane's episodes that ended in a
  drain with every job complete, so by completion and not on a time
  limit (`reseeds` counts both kinds, in streaming mode only);
  `jobs_present_sum` (the batch collectors) the jobs in the lane's
  observation (`Observation.job_mask`), summed over its stored
  decisions: the backlog a decision sees. The sweep loop carries two
  more the same way: `episode_decisions_sum` (`results=True`), and
  under a policy that states a log-probability `nodes_present_sum`
  (`nodes=True`): the active nodes (`Observation.node_mask`) in the
  observation of each stored decision, the part of the net's padded
  job-by-stage grid that is real.

Cross-engine invariant (the parity test): on a deterministic workload
the two engines process the same trajectory, so `decide_steps`, the
per-kind event totals (single pops + the bulk pass attributable to that
kind) and the fulfillment totals (`fulfill_steps + bulk_fulfill_hits`)
agree exactly.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct

_i32 = jnp.int32


class Telemetry(struct.PyTreeNode):
    """Per-lane engine counters (i32 scalars; vmapped engines add a
    leading lane axis). See the module docstring for per-engine
    semantics."""

    decide_steps: jnp.ndarray  # policy commitments on live lanes
    fulfill_steps: jnp.ndarray  # one-at-a-time fulfillments
    event_steps: jnp.ndarray  # single event pops / EVENT micro-steps
    loop_iters: jnp.ndarray  # while-loop iters (core) / events (flat)
    ev_job_arrival: jnp.ndarray  # single pops by kind
    ev_task_finished: jnp.ndarray
    ev_exec_ready: jnp.ndarray
    bulk_relaunch_events: jnp.ndarray  # TASK_FINISHED via bulk passes
    bulk_ready_events: jnp.ndarray  # EXECUTOR_READY via bulk passes
    bulk_fulfill_hits: jnp.ndarray  # candidates via _bulk_fulfill
    commit_rounds: jnp.ndarray  # finished commitment rounds
    # --- per-phase while-iteration split (ISSUE 7) ---
    # bulk-phase iterations: micro-steps (flat) / resume-loop
    # iterations (core) whose bulk pass consumed >= 1 event — the
    # decide/fulfill/event phases' iteration counts are decide_steps /
    # fulfill_steps / event_steps; this completes the per-phase split
    bulk_passes: jnp.ndarray
    # steps the fused bulk passes' early-exit loops needed for this
    # lane (`core._bulk_events_fused`): of a budget of
    # `bulk_events + num_executors` a pass (flat engine only)
    bulk_scan_steps: jnp.ndarray
    # inter-decision while-loop body iterations: `drain_to_decision`
    # (flat single-eval path) / `_resume_simulation` (core). Max/mean
    # over lanes IS the measured batch-max drain tax.
    drain_iters: jnp.ndarray
    # --- decision-row counters of the single-eval batch collectors:
    # batch-level, the same value in every lane (module docstring) ---
    rows: jnp.ndarray  # scan iterations (decision rows)
    rows_live: jnp.ndarray  # rows in which some lane decided
    rows_full_width: jnp.ndarray  # rows scored at the full job width
    # sum over rows of the most drain iters a lane of the batch needed
    # (of the lane's own block where the drain runs block by block)
    drain_batch_iters: jnp.ndarray
    lane_syncs: jnp.ndarray  # reductions over the lane axis executed
    # --- streaming (auto_reset) collection: 0 in sync mode ---
    reseeds: jnp.ndarray  # episodes that ended in the scan, re-seeded
    # evaluations of the reset program: rows in which it ran (the batch
    # collector; every lane the same) or the lane's micro-steps
    reset_evals: jnp.ndarray
    rows_frozen: jnp.ndarray  # rows sat out with the budget spent
    # --- health sentinels (ISSUE 9) ---
    # i32 violation BITMASK (env/health.py bit table), OR-accumulated
    # via `orr` — not a counter. Stays 0 unless a collector runs with
    # `health=True` (the opt-in `health:` config block); the subtract/
    # summarize window math still works because bits only ever get set
    # (so a - prev == the window's newly-set bits).
    health_mask: jnp.ndarray
    # --- episodes that end inside the scan: None unless asked for
    # (module docstring), and then no leaf of the carry ---
    rows_ended: jnp.ndarray | None = None  # rows sat out, episode over
    episodes_terminated: jnp.ndarray | None = None  # ended by completion
    jobs_present_sum: jnp.ndarray | None = None  # jobs seen, over decisions
    # --- the sweep loop's: None unless asked for (module docstring) ---
    episode_decisions_sum: jnp.ndarray | None = None  # of ended episodes
    # the active nodes (`Observation.node_mask`) in the observation of
    # each stored decision: the share of a net's padded [J, S] grid
    # that is real (the sweep loop, under a policy that scores nodes)
    nodes_present_sum: jnp.ndarray | None = None

    @property
    def counts_episodes(self) -> bool:
        return self.rows_ended is not None


_EPISODE_COUNTERS = ("rows_ended", "episodes_terminated", "jobs_present_sum")
_RESULT_COUNTERS = ("episode_decisions_sum",)
_NODE_COUNTERS = ("nodes_present_sum",)


def _zeros(z, episodes: bool, results: bool = False,
           nodes: bool = False) -> Telemetry:
    return Telemetry(**{
        k: z for k in Telemetry.__dataclass_fields__
        if (episodes or k not in _EPISODE_COUNTERS)
        and (results or k not in _RESULT_COUNTERS)
        and (nodes or k not in _NODE_COUNTERS)
    })


def telemetry_zeros(episodes: bool = False) -> Telemetry:
    return _zeros(jnp.zeros((), _i32), episodes)


def telemetry_zeros_like(
    batch_shape: tuple[int, ...], episodes: bool = False,
    results: bool = False, nodes: bool = False,
) -> Telemetry:
    """Zeros with a leading batch shape on every counter — the starting
    value for vmapped engines (one counter set per lane). `episodes`:
    with the three counters of episodes that end inside the scan;
    `results`: with the sweep loop's `episode_decisions_sum`; `nodes`:
    with its `nodes_present_sum`."""
    return _zeros(jnp.zeros(batch_shape, _i32), episodes, results, nodes)


def _count(x) -> bool:
    """i32-cast helper for bool increments."""
    return x.astype(_i32) if hasattr(x, "astype") else _i32(x)


def add(tm: Telemetry | None, **deltas: Any) -> Telemetry | None:
    """`tm.replace(field=field + delta, ...)` with bool deltas cast to
    i32; passes None through so call sites stay one-liners."""
    if tm is None:
        return None
    return tm.replace(
        **{k: getattr(tm, k) + _count(v) for k, v in deltas.items()}
    )


def orr(tm: Telemetry | None, **masks: Any) -> Telemetry | None:
    """Bitwise-OR accumulation for the mask-valued fields
    (`health_mask`): `tm.replace(field=field | mask, ...)`; passes None
    through like `add`."""
    if tm is None:
        return None
    return tm.replace(
        **{k: getattr(tm, k) | _count(v) for k, v in masks.items()}
    )


# ---------------------------------------------------------------------------
# host-side summary (once per iteration / bench row)
# ---------------------------------------------------------------------------


def subtract(tm: Telemetry, prev) -> Telemetry:
    """Counter delta since a `jax.device_get` snapshot `prev` (numpy
    pytree) — bench windows report the timed span, not the warmup."""
    return jax.tree_util.tree_map(lambda a, b: a - b, tm, prev)


def summarize(tm: Telemetry, prev=None) -> dict[str, Any]:
    """Host-side summary dict of a (possibly vmapped) Telemetry.

    Reports totals pooled over lanes, the micro-step composition
    (decide/fulfill/event fractions), per-kind event totals including
    the bulk passes, events and micro-steps per decision, and the
    straggler ratio max/mean over lanes of `loop_iters` — for the core
    engine that is the measured while-loop straggler tax the flat
    engine exists to remove; for the flat engine it is the event-count
    imbalance absorbed without stalling. `prev` (a `jax.device_get`
    snapshot) windows the summary to the counts since the snapshot.
    """
    import numpy as np

    t = jax.device_get(tm)
    if prev is not None:
        t = subtract(t, prev)

    def tot(x) -> int:
        return int(np.sum(np.asarray(x)))

    decide = tot(t.decide_steps)
    fulfill = tot(t.fulfill_steps)
    event = tot(t.event_steps)
    micro = decide + fulfill + event
    li = np.asarray(t.loop_iters).ravel().astype(np.float64)
    lanes = int(li.size)
    mean_li = float(li.mean()) if lanes else 0.0
    straggler = float(li.max() / mean_li) if mean_li > 0 else 1.0

    events_by_kind = {
        "job_arrival": tot(t.ev_job_arrival),
        "task_finished": tot(t.ev_task_finished)
        + tot(t.bulk_relaunch_events),
        "executor_ready": tot(t.ev_exec_ready)
        + tot(t.bulk_ready_events),
    }
    events_total = sum(events_by_kind.values())
    frac = lambda n: round(n / micro, 4) if micro else 0.0  # noqa: E731
    per_dec = lambda n: round(n / decide, 3) if decide else 0.0  # noqa: E731
    di = np.asarray(t.drain_iters).ravel().astype(np.float64)
    mean_di = float(di.mean()) if lanes else 0.0
    drain_straggler = float(di.max() / mean_di) if mean_di > 0 else 1.0

    def batch(x) -> int:
        """A batch-level counter: every lane holds the same value, and
        the maximum is right for a window in which lanes differ."""
        x = np.asarray(x)
        return int(x.max()) if x.size else 0

    rows = batch(t.rows)
    # the reductions over the lane axis the rows executed (on a dp mesh,
    # all-reduces on the critical path); in the summary where rows were
    # counted (PERF.md section 7 says what keeps it from every summary)
    lane_syncs = {"lane_syncs": batch(t.lane_syncs)} if rows else {}
    # where the episode counters were carried: episodes that ended with
    # every job complete (not on a limit), the backlog a decision sees
    # (the mean number of jobs in its observation), and the lane-rows a
    # lane sat out with its own episode over
    episodes, rows_ended = {}, {}
    if t.counts_episodes:
        episodes = {
            "episodes_terminated_total": tot(t.episodes_terminated),
            "jobs_present_total": tot(t.jobs_present_sum),
            "jobs_present_per_decision": per_dec(tot(t.jobs_present_sum)),
        }
        rows_ended = {"lane_rows_ended": tot(t.rows_ended)}
    if t.episode_decisions_sum is not None:
        # the sweep loop: the decisions of the episodes that ended
        episodes["episode_decisions_total"] = tot(t.episode_decisions_sum)
    if t.nodes_present_sum is not None:
        episodes |= {
            "nodes_present_total": tot(t.nodes_present_sum),
            "nodes_present_per_decision": per_dec(tot(t.nodes_present_sum)),
        }
    scan_steps = tot(t.bulk_scan_steps)
    bulk_passes = tot(t.bulk_passes)
    # the bodies the device ran, a lane at a time: every lane runs what
    # the slowest lane of its `while` needs (the whole batch's, or its
    # own block's in a blocked collection: module docstring), so the
    # sum over the lanes; `drain_batch_iters` is a lane's mean
    drain_executed = tot(t.drain_batch_iters)
    drain_batch = drain_executed / lanes if lanes else 0.0
    if drain_batch == int(drain_batch):
        drain_batch = int(drain_batch)  # one block: the count itself
    hm = np.asarray(t.health_mask).ravel()
    health_mask = (
        int(np.bitwise_or.reduce(hm)) if hm.size else 0
    )
    from ..env.health import describe_mask  # host-side, no cycle

    return {
        "lanes": lanes,
        "decisions": decide,
        "commit_rounds": tot(t.commit_rounds),
        "micro_steps": micro,
        "composition": {
            "decide": frac(decide),
            "fulfill": frac(fulfill),
            "event": frac(event),
        },
        "events_by_kind": events_by_kind,
        "events_total": events_total,
        "events_per_decision": per_dec(events_total),
        "micro_per_decision": per_dec(micro),
        "bulk": {
            "relaunch_events": tot(t.bulk_relaunch_events),
            "ready_events": tot(t.bulk_ready_events),
            "fulfill_hits": tot(t.bulk_fulfill_hits),
        },
        "fulfillments": fulfill + tot(t.bulk_fulfill_hits),
        # per-phase while-iteration split (ISSUE 7): the engine's
        # iteration budget attributed to decide / fulfill / event /
        # bulk phases
        "phase_iters": {
            "decide": decide,
            "fulfill": fulfill,
            "event": event,
            "bulk": bulk_passes,
        },
        # how much of its step budget the fused bulk pass uses: the
        # steps the lanes' passes needed, and the mean over the passes
        # that took an event
        "bulk_scan_steps_total": scan_steps,
        "bulk_scan_steps_per_pass": (
            round(scan_steps / bulk_passes, 3) if bulk_passes else 0.0
        ),
        # streaming collection: episodes re-seeded in the scan, and
        # evaluations of the reset program (one per micro-step of a
        # lane while the reset is unconditional); 0 in sync mode
        "reseeds_total": tot(t.reseeds),
        "reset_evals_total": tot(t.reset_evals),
        **episodes,
        "drain_iters_mean": round(mean_di, 2),
        "drain_iters_max": int(di.max()) if lanes else 0,
        "drain_straggler_ratio": round(drain_straggler, 3),
        # the decision rows of the single-eval batch collectors (all
        # zero from any other engine): what the device ran, whole rows
        # and whole `while` bodies over every lane, beside what the
        # lanes needed (`decisions`, `drain_iters_total`)
        "row": {
            "rows": rows,
            "rows_live": batch(t.rows_live),
            "rows_full_width": batch(t.rows_full_width),
            "drain_batch_iters": drain_batch,
            **lane_syncs,
            "lane_rows": rows * lanes,
            "drain_lane_iters_executed": drain_executed,
            "drain_iters_total": tot(t.drain_iters),
            # lane-rows a lane sat out with its budget spent
            "lane_rows_frozen": tot(t.rows_frozen),
            **rows_ended,
        },
        # health sentinels (ISSUE 9): the pooled violation bitmask, its
        # decoded bit names, and how many lanes tripped anything —
        # all zero/empty unless a collector ran with health=True
        "health_mask": health_mask,
        "health_bits": describe_mask(health_mask),
        "unhealthy_lanes": int((hm != 0).sum()) if hm.size else 0,
        "loop_iters_mean": round(mean_li, 2),
        "loop_iters_max": int(li.max()) if lanes else 0,
        "straggler_ratio": round(straggler, 3),
    }
