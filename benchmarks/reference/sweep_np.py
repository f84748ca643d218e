"""The plain reference of the heuristic-sweep deployment
(`tpch_demo_10x50_fair`): numpy and the standard library only, nothing
of the program imported. Two parts.

**1. The guarantees of a sweep, from what one chunk stored**
(`check_sweep`): what decides `correct` on the chip beside the policy
and engine comparisons. A chunk's record is a dict of arrays, ROWS
first ([rows, lanes]):

    valid bool, wall_time f32 (the decision's sim-time inside its
    episode), job, stage, num_exec int, reset bool (the episode ended
    after this decision), ordinal int (the episode the row belongs to,
    counted a lane), and on a `reset` row the episode's result:
    avg_jct, jobs_completed, makespan, decisions

with, beside it, the lanes' ids, the ordinal and the job sequence
(arrival times and templates, [lanes, J]) each lane held when the chunk
returned, and the telemetry summary of the chunk.

**2. A closed-loop simulator of the deployment** (`simulate`): the event
heap of `stream_np` (`_Episode`) with a plain policy in the loop
(`fair_np.fair`), one lane, episode after episode. The independent
check of the engine's transitions under a bank of fixed durations: on
the CPU in the tests, on the chip in the cell's `verify`.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference.stream_np import _Episode

# ---------------------------------------------------------------------------
# 1. the guarantees of a sweep
# ---------------------------------------------------------------------------


def check_results(rec: dict, jobs: int) -> dict[str, int]:
    """(ii) An episode ends only with every job complete; its stored
    result is a finite average job completion time above 0 and no
    longer than its makespan, and at least as many decisions as jobs;
    a row that ends no episode stores nothing."""
    ends = np.asarray(rec["reset"], bool)
    avg = np.asarray(rec["avg_jct"], np.float64)
    span = np.asarray(rec["makespan"], np.float64)
    stored = ((avg != 0) | (span != 0) | (np.asarray(rec["decisions"]) != 0)
              | (np.asarray(rec["jobs_completed"]) != 0))
    sound = np.isfinite(avg) & (avg > 0) & (avg <= span)
    return {
        "sweep_ends_incomplete": int(
            (ends & (np.asarray(rec["jobs_completed"]) != jobs)).sum()),
        "sweep_results_unsound": int((ends & ~sound).sum()),
        "sweep_results_too_few_decisions": int(
            (ends & (np.asarray(rec["decisions"]) < jobs)).sum()),
        "sweep_results_off_an_end": int((stored & ~ends).sum()),
    }


def check_order(rec: dict, final_ordinal) -> dict[str, int]:
    """(iv) A `reset` flag sits on a valid row; a lane's valid rows are
    in time order inside an episode; the valid row after a flagged one
    is the next episode's first (ordinal one more, time 0), every other
    row keeps its ordinal; the ordinal the lane held when the chunk
    returned follows its last row."""
    valid = np.asarray(rec["valid"], bool)
    ends = np.asarray(rec["reset"], bool)
    t = np.asarray(rec["wall_time"], np.float64)
    ordinal = np.asarray(rec["ordinal"])
    step = ends.astype(ordinal.dtype)
    follows = ordinal[1:] == ordinal[:-1] + step[:-1]
    both = valid[1:] & valid[:-1]
    back = both & ~ends[:-1] & (t[1:] < t[:-1])
    fresh = both & ends[:-1] & (t[1:] != 0)
    return {
        "sweep_reset_on_an_idle_row": int((ends & ~valid).sum()),
        "sweep_time_runs_back": int(back.sum()),
        "sweep_new_episode_not_at_zero": int(fresh.sum()),
        "sweep_ordinal_breaks": int((~follows).sum()) + int(
            (np.asarray(final_ordinal) != ordinal[-1] + step[-1]).sum()),
    }


def check_episodes_own(lane, arrivals, templates, own=None
                       ) -> dict[str, int]:
    """(iii) Every lane has an id of its own, and the job sequences the
    lanes hold (arrival times and templates) are pairwise different:
    those of the lanes `own` marks (all of them without it; a caller
    that started lanes as copies of one another marks the lanes that
    have drawn an episode under their own id since)."""
    lane = np.asarray(lane)
    own = np.ones(len(lane), bool) if own is None else np.asarray(own, bool)
    seqs = {np.asarray(a).tobytes() + np.asarray(t).tobytes()
            for a, t, o in zip(arrivals, templates, own) if o}
    return {
        "sweep_lane_ids_shared": int(len(lane) - len(np.unique(lane))),
        "sweep_sequences_shared": int(own.sum() - len(seqs)),
    }


def check_counts(rec: dict, summary: dict | None) -> dict[str, int]:
    """(v) The program's counters against the record: decisions,
    re-seeds, ends by completion, and the decisions of the episodes
    that ended. A counter the summary lacks is left out."""
    if not summary:
        return {}
    ends = np.asarray(rec["reset"], bool)
    read = {
        "decisions": int(np.asarray(rec["valid"]).sum()),
        "reseeds_total": int(ends.sum()),
        "episodes_terminated_total": int(ends.sum()),
        "episode_decisions_total": int(np.asarray(rec["decisions"]).sum()),
    }
    return {f"sweep_{k}_gap": abs(summary[k] - v)
            for k, v in read.items() if k in summary}


def check_sweep(rec: dict, *, lane, final_ordinal, arrivals, templates,
                jobs: int, own=None, summary: dict | None = None
                ) -> dict[str, int]:
    """Guarantees (ii) to (v) on one chunk: every entry a count of
    violations (0 on a sound chunk)."""
    return {**check_results(rec, jobs), **check_order(rec, final_ordinal),
            **check_episodes_own(lane, arrivals, templates, own),
            **check_counts(rec, summary)}


# ---------------------------------------------------------------------------
# 2. the deployment, simulated
# ---------------------------------------------------------------------------


def policy_fields(ep: _Episode) -> dict:
    """What a heuristic reads of an episode's observation: `observe`'s
    fields and the frontier (the unfinished stages of active jobs whose
    every parent is finished)."""
    obs = ep.observe()
    frontier = np.zeros_like(obs["schedulable"])
    for j, job in enumerate(ep.jobs):
        if obs["job_mask"][j]:
            for s in range(job["ns"]):
                frontier[j, s] = ep.frontier(j, s)
    return {"schedulable": obs["schedulable"], "frontier": frontier,
            "job_mask": obs["job_mask"],
            "exec_supplies": obs["exec_supplies"],
            "num_committable": obs["num_committable"],
            "source_job": obs["source_job"]}


def episode_result(ep: _Episode, decisions: int) -> dict:
    """An ended episode's result: the mean of completion less arrival
    over its jobs (up to now for a job cut off by a time limit), the
    jobs complete, the makespan, its decisions."""
    now = float(ep.t)
    spans = [min(job["done_at"], now) - float(job["arrival"])
             for job in ep.jobs if job["arrived"]]
    return {"avg_jct": sum(spans) / max(len(spans), 1),
            "jobs_completed": sum(
                job["done_at"] <= now for job in ep.jobs if job["arrived"]),
            "makespan": now, "decisions": decisions}


def simulate(jobs: list[dict], bank_tables: dict, durations: dict, policy,
             rows: int, *, num_executors: int, max_jobs: int,
             max_stages: int, moving_delay: float,
             warmup_delay: float) -> list[dict]:
    """`rows` decisions of one lane under `policy`, episode `jobs[k]`
    after episode `jobs[k - 1]` (the arguments are `stream_np.replay`'s;
    `policy(**policy_fields)` gives `(flat stage index or -1,
    executors)`). One dict a decision: `time`, `job`, `stage` (-1, -1
    for no stage), `num_exec`, `ordinal` (k), `reset`, and on the row an
    episode ends in its `result`."""
    def episode(k):
        return _Episode(
            jobs[k], bank_tables, durations, num_executors=num_executors,
            max_jobs=max_jobs, max_stages=max_stages,
            moving_delay=moving_delay, warmup_delay=warmup_delay)

    ep, k, taken, out = episode(0), 0, 0, []
    while len(out) < rows:
        flat, num_exec = policy(**policy_fields(ep))
        stage = None if flat < 0 else divmod(int(flat), max_stages)
        row = {"time": float(ep.t), "num_exec": int(num_exec), "ordinal": k,
               "job": -1 if stage is None else stage[0],
               "stage": -1 if stage is None else stage[1], "reset": False}
        taken += 1
        if not ep.decide(stage, int(num_exec)):
            while not ep.round_ready and not ep.over() and ep.events:
                ep.pop_event()
            if ep.over():
                row["reset"], row["result"] = True, episode_result(ep, taken)
                k, taken = k + 1, 0
                if k < len(jobs):
                    ep = episode(k)
        out.append(row)
        if row["reset"] and k >= len(jobs):
            break
    return out
