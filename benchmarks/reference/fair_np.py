"""The plain reference of the fair scheduler: numpy and the standard
library only, nothing of the program imported. Upstream gym-sparksched's
`RoundRobinScheduler(dynamic_partition=True)` as its Python reads
(`schedulers/heuristics/round_robin.py:14-49`, with `find_stage` of
`schedulers/heuristics/utils.py:17-37`), over the fields of a padded
observation instead of upstream's compacted one:

    schedulable [J,S] bool, frontier [J,S] bool (both only on the
    unfinished stages of active jobs), job_mask [J] bool (active jobs;
    job ids are in arrival order), exec_supplies [J] int,
    num_committable int, source_job int (the job that is releasing
    executors, -1 for the common pool or none)

- the per-job cap is `ceil(executors / active jobs)` (the whole cluster
  with `dynamic_partition` off: FIFO);
- first the source job: a schedulable stage of it takes every
  committable executor;
- then the active jobs in arrival order, the source job and every job
  at or over its cap passed over: the first with a schedulable stage
  takes `min(committable, cap - supply)`;
- within a job the first schedulable FRONTIER stage (no unfinished
  parent), else the first schedulable stage;
- no stage found: -1, with every committable executor.

`fair` returns `(stage, executors)`, `stage` the flat padded index
`job * S + stage` or -1. With the event heap of `stream_np`
(`sweep_np.simulate`) it makes a whole simulator of the deployment that
shares no line with the program.
"""

from __future__ import annotations

import math

import numpy as np


def find_stage(schedulable, frontier, job: int) -> int:
    """upstream utils.py:17-37: the job's first schedulable frontier
    stage, else its first schedulable stage, else -1."""
    found = -1
    for s in range(len(schedulable[job])):
        if not schedulable[job][s]:
            continue
        if frontier[job][s]:
            return s
        if found == -1:
            found = s
    return found


def fair(schedulable, frontier, job_mask, exec_supplies, num_committable,
         source_job, *, num_executors: int,
         dynamic_partition: bool = True) -> tuple[int, int]:
    """One decision of upstream's round-robin scheduler."""
    schedulable = np.asarray(schedulable, bool)
    frontier = np.asarray(frontier, bool)
    job_mask = np.asarray(job_mask, bool)
    supplies = np.asarray(exec_supplies)
    committable, source = int(num_committable), int(source_job)
    s_cap = schedulable.shape[1]
    active = [j for j in range(len(job_mask)) if job_mask[j]]
    cap = num_executors
    if dynamic_partition:
        cap = int(math.ceil(num_executors / max(1, len(active))))
    if source >= 0 and job_mask[source]:
        s = find_stage(schedulable, frontier, source)
        if s != -1:
            return source * s_cap + s, committable
    for j in active:
        if supplies[j] >= cap or j == source:
            continue
        s = find_stage(schedulable, frontier, j)
        if s == -1:
            continue
        return j * s_cap + s, min(committable, cap - int(supplies[j]))
    return -1, committable
