"""The plain reference of the trained-policy sweep deployment
(`tpch_demo_10x50_decima`): numpy and the standard library only, nothing
of the program imported.

Under a SAMPLED policy a plain simulator cannot choose the program's
actions (the draw is the program's), so the engine is held to the plain
event heap of `stream_np` (`_Episode`) under the decisions the program
RECORDED, as `stream_np.replay` does, with the two things a sweep needs
and `replay` lacks:

- a lane that can be taken up where it stopped: `Lane` keeps the episode
  in progress, its ordinal and its decisions so far between calls, and
  `copy` gives an independent lane in the same state (the stagger's
  copies of a source lane go on from the state the source had when they
  were placed);
- on the row an episode ends in, its result
  (`sweep_np.episode_result`: average job completion time, jobs
  completed, makespan, decisions), and the next job sequence after it.

The decision itself is the plain net's to check
(`decima_np.score_action`, through `benchmarks/logprob_check.py`).
"""

from __future__ import annotations

import copy

from benchmarks.reference import sweep_np
from benchmarks.reference.stream_np import _Episode


class Lane:
    """One lane of a sweep in plain Python containers: the job
    sequences of its episodes in order (`jobs[k]` is ordinal k, as
    `stream_np.replay` takes them) and the episode in progress."""

    def __init__(self, jobs: list[dict], bank_tables: dict, durations: dict,
                 **cluster) -> None:
        self.jobs, self.tables, self.durations = jobs, bank_tables, durations
        self.cluster = cluster
        self.ordinal, self.taken = 0, 0
        self.ep = self._episode(0)

    def _episode(self, k: int) -> _Episode:
        return _Episode(self.jobs[k], self.tables, self.durations,
                        **self.cluster)

    def copy(self) -> "Lane":
        """An independent lane in this lane's state (the job sequences
        and tables, which nothing writes, are shared)."""
        twin = copy.copy(self)
        twin.ep = copy.deepcopy(self.ep)
        return twin

    def replay(self, actions) -> list[dict]:
        """The lane under the recorded `actions`, one `(job, stage,
        executors)` a decision (`job` < 0: no stage chosen). One dict a
        decision: `time` (the episode's clock when it was taken),
        `ordinal`, `taken` (False where the simulator could not take
        the decision: a stage that is not schedulable there), `reset`
        and, on the row an episode ends in, its `result`. The lane
        stays where the last action left it."""
        rows = []
        for job, stage, num_exec in actions:
            chosen = None if job < 0 else (int(job), int(stage))
            row = {"time": float(self.ep.t), "ordinal": self.ordinal,
                   "taken": chosen is None or chosen in self.ep.schedulable,
                   "reset": False}
            self.taken += 1
            if not self.ep.decide(chosen, int(num_exec)):
                ep = self.ep
                while not ep.round_ready and not ep.over() and ep.events:
                    ep.pop_event()
                if ep.over():
                    row["reset"] = True
                    row["result"] = sweep_np.episode_result(ep, self.taken)
                    self.ordinal, self.taken = self.ordinal + 1, 0
                    self.ep = self._episode(self.ordinal)
            rows.append(row)
        return rows
