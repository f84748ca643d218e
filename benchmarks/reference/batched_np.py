"""The plain reference of the batched-arrivals deployment
(`decima_tpch_50x20_batched`): numpy only, nothing of the program
imported. After the Decima paper's section 7.2 ("Batched arrivals": a
batch of jobs arrives together and runs to completion) and upstream
gym-sparksched's `_load_initial_jobs` (every arrival at t=0 is loaded
at reset). It holds what one collection stored to the guarantees the
configuration file states. A collection is a dict of arrays, lanes
first, a lane's rows in the order it decided:

    valid [B,T] bool; resets [B,T] bool (the episode ended in the span
    after this decision); job_mask [B,T,J] bool (the jobs in the
    decision's observation: arrived and not complete); row_has, a dict
    of [B,T] bool, one for each wide leaf of a stored observation
    (`node`: a node is active; `schedulable`: a stage can be scheduled;
    `remaining`: a stage has tasks left; `duration`: a stage has a
    duration): whether the row holds anything there; job_template
    [B,J] (of each lane's FIRST decision); and of the state the
    collection ended in: final_num_jobs [B], final_arrival_time [B,J],
    final_completed [B,J] bool (the job's completion time is finite)

`check_batched` returns counts of violations, each 0 on a sound
collection, and the share of lanes whose episode ended by completion:

- arrivals: a lane's first decision sees every job of its batch
  (`batch_jobs` of them, none complete yet), every job's arrival time is
  0, and no job enters a later observation that the row before lacked
  (a job only ever leaves: nothing arrives after t=0);
- completion: a lane whose episode ended is flagged once, on its last
  valid row, its valid rows are a prefix, and every job of it is
  complete in the final state; a lane that was not flagged used every
  row and still has a job to run (the scan cut it short);
- stored rows: EVERY valid row holds a job, an active node, a stage to
  schedule, tasks left and a duration (a decision is taken only where
  a stage can be scheduled; a row the store lost reads all zeros, and
  a sample of decisions finds it only where it falls on one);
- groups: the `rollouts_per_group` lanes of a sequence group hold the
  same batch (job templates, in order), and no two groups the same;
- counts, where the program's summary has the counters: the lanes
  flagged equal `episodes_terminated_total`, and the rows after a
  lane's end equal `row.lane_rows_ended`.
"""

from __future__ import annotations

import numpy as np


def check_batched(col: dict, *, batch_jobs: int, rollouts_per_group: int,
                  summary: dict | None = None) -> dict:
    valid = np.asarray(col["valid"], bool)
    resets = np.asarray(col["resets"], bool)
    mask = np.asarray(col["job_mask"], bool)
    lanes, rows = valid.shape
    n = valid.sum(axis=1)
    lane = np.arange(lanes)
    last = np.maximum(n - 1, 0)

    # arrivals: the whole batch at the first decision, none later
    present0 = mask[:, 0].sum(axis=1)
    both = valid[:, 1:] & valid[:, :-1]
    entered = (mask[:, 1:] & ~mask[:, :-1]).any(axis=2) & both
    arrival = np.asarray(col["final_arrival_time"], np.float64)
    in_batch = np.arange(arrival.shape[1])[None, :] < np.asarray(
        col["final_num_jobs"])[:, None]
    found = {
        "batched_first_row_short": int((present0 != batch_jobs).sum()),
        "batched_batch_size_off": int(
            (np.asarray(col["final_num_jobs"]) != batch_jobs).sum()),
        "batched_arrival_after_start": int(
            (in_batch & (arrival != 0.0)).sum()),
        "batched_job_entered_later": int(entered.sum()),
    }

    # completion: flagged once, on the last valid row, with every job done
    prefix = (valid == (np.arange(rows)[None, :] < n[:, None])).all(axis=1)
    flagged = resets.any(axis=1)
    flag_right = (resets.sum(axis=1) == 1) & resets[lane, last] & (n > 0)
    done = (np.asarray(col["final_completed"], bool) | ~in_batch).all(axis=1)
    found |= {
        "batched_valid_not_prefix": int((~prefix).sum()),
        "batched_end_flag_misplaced": int((flagged & ~flag_right).sum()),
        "batched_ended_unfinished": int((flagged & ~done).sum()),
        # no flag: the lane decided in every row and has work left
        "batched_unended_idle": int(
            (~flagged & ((n < rows) | done)).sum()),
    }

    # stored rows: no valid row is empty, in any of the wide leaves
    row_has = {"job": mask.any(axis=2)} | {
        k: np.asarray(v, bool) for k, v in col["row_has"].items()}
    found |= {f"batched_row_without_{k}": int((valid & ~v).sum())
              for k, v in row_has.items()}

    # groups: one batch a group, another in every other group
    tpl = np.asarray(col["job_template"])
    groups = tpl.reshape(lanes // rollouts_per_group, rollouts_per_group, -1)
    split = int((groups != groups[:, :1]).any(axis=(1, 2)).sum())
    distinct = len({g[0].tobytes() for g in groups})
    found |= {
        "batched_group_batch_split": split,
        "batched_batch_repeated": len(groups) - distinct,
    }

    # counts, where the program has the counters
    if summary is not None:
        if "episodes_terminated_total" in summary:
            found["batched_terminated_count_gap"] = int(
                summary["episodes_terminated_total"] - flagged.sum())
        if "lane_rows_ended" in summary.get("row", {}):
            found["batched_ended_rows_gap"] = int(
                summary["row"]["lane_rows_ended"]
                - (rows - n)[flagged].sum())
    found["episodes_terminated_share"] = float(
        (flagged & flag_right & done).mean())
    return found
