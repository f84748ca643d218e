"""The plain reference of the streaming deployment
(`decima_tpch_50x200_stream`): numpy and the standard library only,
nothing of the program imported. It follows upstream gym-sparksched's
`RolloutWorkerAsync` (trainers/rollout_worker.py:160-206) and
`spark_sched_sim.py`. Two parts.

**1. The guarantees of streaming, from what two consecutive collections
stored** (`check_stream`): what decides `correct` on the chip. A
collection is a dict of arrays, lanes first:

    valid [B,T] bool, wall_times [B,T+1] (ELAPSED sim-ms inside the
    collection; column T and every invalid column hold the lane's final
    elapsed time), resets [B,T] bool, final_reset_count [B],
    job_template [B,T,J]; of the earlier collection besides
    last_remaining and last_node_mask [B,F], the lane's last valid row
    (`last_valid_rows` takes them from whole arrays); of the later one
    `rows(lane)`, a function that gives the lane's stored `remaining`
    and `node_mask`, each [T,F] (a collection's are gigabytes: the
    caller hands them over a lane at a time)

Where the program's stated semantics (the docstrings of `collect_async`
and `collect_flat_async_batch`, which follow upstream's worker) fix a
detail, the checks follow them:

- a `resets` flag sits on the row of the lane's LAST decision before the
  episode ended ("env was reset after this step"), so the next stored row
  is the first of the new episode, and a flag on a collection's last
  valid row says that the NEXT collection starts a new episode;
- `final_reset_count` is the NEXT reset ordinal: the episode a lane is in
  when a collection starts has the ordinal handed in less one;
- the budget is checked once a decision row: a lane decides in every row
  until its elapsed time has reached `rollout_duration`, and the span
  after its last decision runs to its end, so a lane's final elapsed time
  may pass the budget by one span;
- `remaining` is stored as 0 outside `node_mask` (a job that has not
  arrived, a finished stage), so "never rises" is held where both rows
  have the node.

**2. A plain event-heap simulator of the scheduling semantics**
(`replay`), one lane, Python lists and a heap: the independent check of
the engine's transitions, for the CPU tests. On the chip the bank's task
durations are drawn at random, which a replay cannot mirror without the
program's rng, so there it stays a test.
"""

from __future__ import annotations

import heapq

import numpy as np

# ---------------------------------------------------------------------------
# 1. the guarantees of streaming
# ---------------------------------------------------------------------------


def _n_valid(col: dict) -> np.ndarray:
    return np.asarray(col["valid"]).sum(axis=1)


def last_valid_rows(valid, remaining, node_mask) -> dict:
    """`last_remaining` and `last_node_mask` of a collection, from its
    whole [B,T,F] arrays (row 0 for a lane with no valid row)."""
    last = np.maximum(np.asarray(valid).sum(axis=1) - 1, 0)
    lanes = np.arange(len(last))
    return {"last_remaining": np.asarray(remaining)[lanes, last],
            "last_node_mask": np.asarray(node_mask)[lanes, last]}


def handed_reset_count(col: dict) -> np.ndarray:
    """The reset ordinal each lane was handed when the collection began:
    its final one less the re-seeds flagged inside."""
    return (np.asarray(col["final_reset_count"])
            - np.asarray(col["resets"]).sum(axis=1))


def check_budget(col: dict, rollout_duration: float) -> dict[str, int]:
    """(i) Valid rows are a prefix; every valid row starts under the
    budget; a lane with unused rows ended at or past the budget."""
    valid = np.asarray(col["valid"])
    walls = np.asarray(col["wall_times"], np.float64)
    t = valid.shape[1]
    n = valid.sum(axis=1)
    prefix = np.arange(t)[None, :] < n[:, None]
    late = valid & (walls[:, :t] >= rollout_duration)
    unused = n < t
    return {
        "stream_valid_not_prefix": int((valid != prefix).any(axis=1).sum()),
        "stream_rows_past_budget": int(late.sum()),
        "stream_unused_rows_under_budget": int(
            (unused & (walls[:, t] < rollout_duration)).sum()),
        "stream_elapsed_not_monotone": int(
            (valid[:, 1:] & (np.diff(walls[:, :t], axis=1) < 0)).sum()),
    }


def _episode_of_row(resets: np.ndarray) -> np.ndarray:
    """For one lane, the number of re-seeds flagged BEFORE each row: the
    row's episode, counted from the one the collection began in."""
    return np.concatenate([[0], np.cumsum(resets)[:-1]]).astype(int)


def check_persistence(prev: dict, cur: dict) -> dict[str, int]:
    """(ii) `cur` starts where `prev` stopped."""
    ordinal = int((handed_reset_count(cur)
                   != np.asarray(prev["final_reset_count"])).sum())
    n_prev, n_cur = _n_valid(prev), _n_valid(cur)
    template_moved = remaining_rose = 0
    for lane in range(len(n_cur)):
        rem, mask = cur["rows"](lane)
        rem, mask = np.asarray(rem), np.asarray(mask)
        resets = np.asarray(cur["resets"][lane])
        episode = _episode_of_row(resets)[: n_cur[lane]]
        rem, mask = rem[: n_cur[lane]], mask[: n_cur[lane]]
        if n_prev[lane] and n_cur[lane]:
            last = n_prev[lane] - 1
            if not prev["resets"][lane][last]:
                # the same episode goes on across the boundary
                rem = np.concatenate(
                    [np.asarray(prev["last_remaining"][lane])[None], rem])
                mask = np.concatenate(
                    [np.asarray(prev["last_node_mask"][lane])[None], mask])
                episode = np.concatenate([[0], episode])
                template_moved += int(
                    (np.asarray(prev["job_template"][lane][last])
                     != np.asarray(cur["job_template"][lane][0])).any())
        same = episode[1:] == episode[:-1]
        both = mask[1:] & mask[:-1]
        rose = (rem[1:] > rem[:-1]) & both & same[:, None]
        remaining_rose += int(rose.any(axis=1).sum())
    return {
        "stream_reset_ordinal_not_handed_on": ordinal,
        "stream_template_moved_without_reseed": template_moved,
        "stream_remaining_rose_in_episode": remaining_rose,
    }


def episode_sequences(col: dict) -> list[tuple[int, int, bytes]]:
    """`(lane, reset ordinal, job-template vector)` for every episode of
    which the collection stored the first row it saw: the episode a lane
    began the collection in, and each one re-seeded inside it whose
    first decision was stored."""
    out = []
    handed = handed_reset_count(col)
    n = _n_valid(col)
    templates = col["job_template"]
    for lane in range(len(n)):
        if not n[lane]:
            continue
        starts = [0] + [int(r) + 1 for r in np.flatnonzero(
            np.asarray(col["resets"][lane])[: n[lane]])]
        for k, row in enumerate(starts):
            if row < n[lane]:
                vec = np.ascontiguousarray(templates[lane][row])
                out.append((lane, int(handed[lane]) - 1 + k, vec.tobytes()))
    return out


def check_groups(cols: list[dict], rollouts_per_group: int
                 ) -> dict[str, int]:
    """(iii) Over all the collections given: one job sequence for each
    (group, reset ordinal), and no sequence under two of them. Lanes
    `g * rollouts_per_group ...` are group g, as the trainer lays them
    out (upstream trainer.py:268-271)."""
    by_key: dict[tuple[int, int], set[bytes]] = {}
    for col in cols:
        for lane, ordinal, vec in episode_sequences(col):
            by_key.setdefault(
                (lane // rollouts_per_group, ordinal), set()).add(vec)
    split = sum(len(v) > 1 for v in by_key.values())
    keys_of: dict[bytes, set] = {}
    for key, vecs in by_key.items():
        for vec in vecs:
            keys_of.setdefault(vec, set()).add(key)
    shared = sum(len(k) > 1 for k in keys_of.values())
    return {"stream_group_sequence_split": split,
            "stream_sequence_repeated": shared,
            "stream_episodes_seen": len(by_key)}


def check_counts(col: dict, summary: dict | None) -> dict[str, int]:
    """(iv) The rows flagged in `resets` are the telemetry's re-seeds,
    where the program has that counter; the valid rows are its
    decisions; no health bit. A re-seed is flagged on a valid row."""
    valid, resets = np.asarray(col["valid"]), np.asarray(col["resets"])
    out = {"stream_reset_flag_on_unused_row": int((resets & ~valid).sum())}
    if summary is not None:
        out["stream_decisions_gap"] = int(
            valid.sum() - summary["decisions"])
        out["stream_health_mask"] = int(summary["health_mask"])
        if "reseeds_total" in summary:
            out["stream_reseeds_gap"] = int(
                resets.sum() - summary["reseeds_total"])
    return out


def check_stream(prev: dict, cur: dict, *, rollout_duration: float,
                 rollouts_per_group: int, summary: dict | None = None
                 ) -> dict[str, int]:
    """Every guarantee on two consecutive collections: the name of each
    check and the number of violations (`stream_episodes_seen` apart,
    which counts what the group check had to look at and has to be at
    least the number of groups)."""
    out: dict[str, int] = {}
    for col in (prev, cur):
        for k, v in check_budget(col, rollout_duration).items():
            out[k] = out.get(k, 0) + v
    out.update(check_persistence(prev, cur))
    out.update(check_groups([prev, cur], rollouts_per_group))
    out.update(check_counts(cur, summary))
    return out


# ---------------------------------------------------------------------------
# 2. a plain event-heap simulator of the scheduling semantics
# ---------------------------------------------------------------------------
# One lane, after upstream's `spark_sched_sim.py` and
# `components/executor_tracker.py`: executors live in pools (the common
# pool, a job's pool, a stage's pool); a decision commits executors of
# the current SOURCE pool to a stage; when a commitment round ends the
# commitments are fulfilled (an executor starts a task, is sent to
# another job and arrives `moving_delay` later, is parked in its job's
# pool, or falls back to a backup stage); then events are popped off a
# heap, each through its `_handle_*`, until executors are free and a
# stage is schedulable again. Task durations are DATA: per template and
# stage one value for an executor that was idle (`fresh`), one for an
# executor new to the stage (`first`), one for an executor repeating the
# stage (`rest`), None for an empty bucket, with upstream's fall-backs
# (tpch.py:75-106) and `warmup_delay` where a fresh executor has to take
# a first-wave value. The rest is the engine's documented behaviour:
# the episode ends at the first event at or past the time limit, times
# are float32 (the sum of a float32 time and a float32 duration).

COMMON = (-1, -1)
_f32 = np.float32
_INF = float("inf")


class _Episode:
    """One episode's state, in plain Python containers."""

    def __init__(self, seq: dict, tables: dict, durations: dict, *,
                 num_executors: int, max_jobs: int, max_stages: int,
                 moving_delay: float, warmup_delay: float) -> None:
        self.n, self.j_cap, self.s_cap = num_executors, max_jobs, max_stages
        self.moving_delay, self.warmup_delay = moving_delay, warmup_delay
        self.time_limit = _f32(seq["time_limit"])
        self.t = _f32(0.0)
        arrivals = list(seq["arrivals"])  # [(time, template)]
        self.num_jobs = len(arrivals)
        self.jobs = []
        for t_arr, tpl in arrivals:
            tab, dur = tables[tpl], durations[tpl]
            ns = len(tab["num_tasks"])
            self.jobs.append({
                "arrival": _f32(t_arr), "arrived": False, "done_at": _INF,
                "template": tpl, "ns": ns, "dur": dur,
                "adj": np.asarray(tab["adj"], bool)[:ns, :ns],
                "num_tasks": list(tab["num_tasks"]),
                "remaining": list(tab["num_tasks"]),
                "executing": [0] * ns, "completed": [0] * ns,
                "duration": [_f32(x) for x in tab["rough"]],
                "moving": [0] * ns, "committed": [0] * ns,
                "selected": [False] * ns, "supply": 0,
            })
        # executors: where each is and what it last ran
        self.pool = [COMMON] * num_executors  # None while moving
        self.job_of = [-1] * num_executors
        self.executing = [False] * num_executors
        self.task_valid = [False] * num_executors
        self.task_stage = [-1] * num_executors
        self.dst = [None] * num_executors
        self.commitments: list[dict] = []  # src, dst, seq; in seq order
        self.seq = self.num_jobs  # arrivals hold sequence numbers 0..
        self.events: list = []
        for j, job in enumerate(self.jobs):
            if job["arrival"] == 0.0:
                job["arrived"] = True  # upstream _load_initial_jobs
            else:
                heapq.heappush(
                    self.events, (job["arrival"], j, "arrival", j))
        self.source = COMMON  # None when cleared
        self.round_ready = True
        self.schedulable = self.find_schedulable(self.source_job_id())

    # -- derived quantities -------------------------------------------------

    def stage_done(self, j, s):
        job = self.jobs[j]
        return job["completed"][s] >= job["num_tasks"][s]

    def job_done(self, j):
        job = self.jobs[j]
        return job["arrived"] and all(
            self.stage_done(j, s) for s in range(job["ns"]))

    def job_active(self, j):
        return self.jobs[j]["arrived"] and not self.job_done(j)

    def job_saturated(self, j):
        return all(r == 0 for r in self.jobs[j]["remaining"])

    def demand(self, j, s):
        job = self.jobs[j]
        return job["remaining"][s] - job["moving"][s] - job["committed"][s]

    def saturated(self, j, s):
        return self.demand(j, s) <= 0

    def frontier(self, j, s):
        job = self.jobs[j]
        return not self.stage_done(j, s) and all(
            self.stage_done(j, p) for p in range(job["ns"])
            if job["adj"][p, s])

    def members(self, pool):
        return [e for e in range(self.n) if self.pool[e] == pool]

    def num_committable(self):
        if self.source is None:
            return 0
        out = sum(c["src"] == self.source for c in self.commitments)
        return len(self.members(self.source)) - out

    def source_job_id(self):
        return -1 if self.source is None else self.source[0]

    def all_done(self):
        return all(self.job_done(j) for j in range(self.num_jobs))

    def over(self):
        return self.all_done() or self.t >= self.time_limit

    def find_schedulable(self, source_job):
        """upstream :505-555: jobs that pass the saturation filter (the
        source job exempt), stages that are unsaturated with every
        parent saturated and were not selected this round."""
        out = set()
        for j, job in enumerate(self.jobs):
            if not self.job_active(j):
                continue
            if j != source_job and job["supply"] >= self.n:
                continue
            for s in range(job["ns"]):
                if self.saturated(j, s) or job["selected"][s]:
                    continue
                if all(self.saturated(j, p) for p in range(job["ns"])
                       if job["adj"][p, s]):
                    out.add((j, s))
        return out

    # -- observation --------------------------------------------------------

    def observe(self) -> dict:
        shape = (self.j_cap, self.s_cap)
        rem = np.zeros(shape, np.int32)
        dur = np.zeros(shape, np.float32)
        node = np.zeros(shape, bool)
        sched = np.zeros(shape, bool)
        job_mask = np.zeros(self.j_cap, bool)
        supplies = np.zeros(self.j_cap, np.int32)
        templates = np.zeros(self.j_cap, np.int32)
        for j, job in enumerate(self.jobs):
            templates[j] = job["template"]
            if not self.job_active(j):
                continue
            job_mask[j] = True
            supplies[j] = job["supply"]
            for s in range(job["ns"]):
                if self.stage_done(j, s):
                    continue
                node[j, s] = True
                rem[j, s] = job["remaining"][s]
                dur[j, s] = job["duration"][s]
                sched[j, s] = (j, s) in self.schedulable
        return {"remaining": rem, "duration": dur, "schedulable": sched,
                "node_mask": node, "job_mask": job_mask,
                "exec_supplies": supplies,
                "num_committable": self.num_committable(),
                "source_job": self.source_job_id(),
                "job_template": templates, "time": float(self.t)}

    # -- commitments (executor_tracker.py:146-184) ---------------------------

    def add_commitment(self, n, dst):
        src = self.source
        same = [c for c in self.commitments
                if c["src"] == src and c["dst"] == dst]
        if same:
            seq = same[0]["seq"]
        else:
            seq, self.seq = self.seq, self.seq + 1
        self.commitments += [
            {"src": src, "dst": dst, "seq": seq} for _ in range(n)]
        self.commitments.sort(key=lambda c: c["seq"])  # stable
        if dst[0] >= 0:
            if dst[0] != src[0]:
                self.jobs[dst[0]]["supply"] += n
            self.jobs[dst[0]]["committed"][dst[1]] += n

    def commit_remaining(self):
        n = self.num_committable()
        if n > 0:
            self.add_commitment(n, COMMON)

    def peek_commitment(self, pool):
        for c in self.commitments:  # insertion (sequence) order
            if c["src"] == pool:
                return c
        return None

    def fulfill_commitment(self, e, c, quirk):
        """upstream :699-712: executor `e` takes commitment `c`."""
        self.commitments.remove(c)
        dst, src = c["dst"], c["src"]
        if dst[0] < 0:
            self.move_idle_from_pool(self.pool[e], [e])
            return
        if dst[0] != src[0]:
            self.jobs[dst[0]]["supply"] -= 1
        self.jobs[dst[0]]["committed"][dst[1]] -= 1
        self.move_to_stage(e, dst, quirk)

    # -- executor moves (upstream :584-637, :745-845) ------------------------

    def move_idle_from_pool(self, pool, execs):
        """upstream _move_idle_executors: nothing for the common pool or
        an unsaturated job's pool; else to the job's pool, or on to the
        common pool if the job is saturated."""
        if pool is None or pool[0] < 0:
            return
        sat = self.job_saturated(pool[0])
        if pool[1] < 0 and not sat:
            return
        for e in execs:
            if sat:
                self.pool[e], self.job_of[e] = COMMON, -1
                self.task_valid[e] = False
            else:
                self.pool[e] = (pool[0], -1)

    def find_backup(self, e, quirk):
        """upstream :784-845, with its `if not source_job_id` quirk: an
        executor of job 0 searches with the tracker's source job."""
        own = self.job_of[e]
        sched = sorted(self.find_schedulable(quirk if own == 0 else own))
        local = [x for x in sched if x[0] == own]
        other = [x for x in sched if x[0] != own]
        return (local or other or [None])[0]

    def move_to_stage(self, e, stage, quirk):
        j, s = stage
        if self.jobs[j]["remaining"][s] == 0:  # nothing left to launch
            stage = self.find_backup(e, quirk)
            if stage is None:
                self.move_idle_from_pool(self.pool[e], [e])
                return
            j, s = stage
        if self.job_of[e] != j:
            self.send(e, j, s)
        elif self.frontier(j, s):
            self.start_task(e, j, s)
        else:  # parked in its job's pool until the stage is ready
            self.pool[e] = (j, -1)
            self.task_valid[e] = False

    def send(self, e, j, s):
        seq, self.seq = self.seq, self.seq + 1
        old = self.job_of[e]
        if old >= 0:
            self.jobs[old]["supply"] -= 1
        self.jobs[j]["supply"] += 1
        self.jobs[j]["moving"][s] += 1
        self.pool[e], self.job_of[e] = None, -1
        self.task_valid[e] = False
        self.dst[e] = (j, s)
        heapq.heappush(self.events, (
            _f32(self.t + _f32(self.moving_delay)), seq, "ready", e))

    def task_duration(self, e, j, s):
        """upstream tpch.py:75-106 over one value a bucket."""
        d = self.jobs[j]["dur"]
        fresh, first, rest = d["fresh"][s], d["first"][s], d["rest"][s]
        if not self.task_valid[e]:
            if fresh is not None:
                return _f32(fresh)
            return _f32(_f32(first) + _f32(self.warmup_delay))
        if self.task_stage[e] == s:  # upstream compares stage ids alone
            options = (rest, first, fresh)
        else:
            options = (first, fresh)
        return _f32(next(x for x in options if x is not None))

    def start_task(self, e, j, s):
        job = self.jobs[j]
        dur = self.task_duration(e, j, s)
        seq, self.seq = self.seq, self.seq + 1
        self.pool[e] = (j, s)
        self.executing[e], self.task_valid[e] = True, True
        self.task_stage[e] = s
        job["remaining"][s] -= 1
        job["executing"][s] += 1
        job["duration"][s] = dur
        heapq.heappush(
            self.events, (_f32(self.t + dur), seq, "finished", e))

    # -- a decision (upstream step :188-221, front half) ---------------------

    def decide(self, stage, num_exec):
        """One action of a commitment round; True when the round goes
        on at the same time."""
        if stage is not None and stage in self.schedulable:
            n = max(1, min(num_exec, self.num_committable()))
            n = min(n, self.demand(*stage))
            self.add_commitment(n, stage)
            self.jobs[stage[0]]["selected"][stage[1]] = True
            self.schedulable = self.find_schedulable(self.source_job_id())
        else:
            self.commit_remaining()
        if self.num_committable() > 0 and self.schedulable:
            return True
        self.commit_remaining()
        # the source pool's idle executors take its commitments in order
        idle = [e for e in self.members(self.source)
                if not self.executing[e]]
        mine = [c for c in self.commitments if c["src"] == self.source]
        for e, c in zip(idle, mine):
            self.fulfill_commitment(e, c, self.source_job_id())
        self.clear_round()
        return False

    def clear_round(self):
        self.source = None
        self.round_ready = False
        self.schedulable = set()
        for job in self.jobs:
            job["selected"] = [False] * job["ns"]

    # -- events (upstream :320-343, :426-483) --------------------------------

    def pop_event(self):
        t, _, kind, arg = heapq.heappop(self.events)
        self.t = t
        quirk = self.source_job_id()
        if kind == "arrival":
            self.jobs[arg]["arrived"] = True
            if self.members(COMMON):
                self.source = COMMON
        elif kind == "ready":
            e, (j, s) = arg, self.dst[arg]
            self.jobs[j]["moving"][s] -= 1
            self.pool[e], self.job_of[e] = (j, -1), j
            self.move_to_stage(e, (j, s), quirk)
        else:
            self.task_finished(arg, quirk)
        # upstream :332-343: a new round, or the source's idle
        # executors move on and the source is dropped
        committable = self.num_committable()
        sched = self.find_schedulable(self.source_job_id())
        if committable > 0 and sched:
            self.round_ready, self.schedulable = True, sched
        elif committable > 0:
            self.move_idle_from_pool(self.source, [
                e for e in self.members(self.source)
                if not self.executing[e]])
            self.source = None

    def task_finished(self, e, quirk):
        j, s = self.job_of[e], self.task_stage[e]
        job = self.jobs[j]
        before = {x for x in range(job["ns"]) if self.frontier(j, x)}
        job["executing"][s] -= 1
        job["completed"][s] += 1
        self.executing[e] = False
        if job["remaining"][s] > 0:
            self.start_task(e, j, s)  # the next task of the stage
            return
        # the stage has no task left to launch: the executor is released
        changed = self.stage_done(j, s) and bool(
            {x for x in range(job["ns"]) if self.frontier(j, x)} - before)
        if self.job_done(j) and job["done_at"] == _INF:
            self.move_idle_from_pool((j, -1), [
                x for x in self.members((j, -1)) if not self.executing[x]])
            job["done_at"] = float(self.t)
        c = self.peek_commitment((j, s))
        if c is not None:
            self.fulfill_commitment(e, c, quirk)
        else:
            self.task_valid[e] = False
            if changed:
                self.move_idle_from_pool((j, s), [e])
        # upstream _update_executor_source :662-674
        if changed:
            self.source = (j, -1)
        elif c is None:
            self.source = (j, s)

    def jobtime(self, t_old, beta):
        """upstream :847-874 over [t_old, now]: every job's overlap with
        the span, discounted from the span's start when beta > 0."""
        total = 0.0
        for job in self.jobs:
            if not job["arrived"]:
                continue
            start = max(float(job["arrival"]), t_old)
            end = min(job["done_at"], float(self.t))
            if end <= start:
                continue
            if beta == 0.0:
                total += end - start
            else:
                b = beta * 1e-3
                total += (np.exp(-b * (start - t_old))
                          - np.exp(-b * (end - t_old))) / beta
        return total


def replay(jobs: list[dict], bank_tables: dict, actions: list,
           durations: dict, *, num_executors: int, max_jobs: int,
           max_stages: int, moving_delay: float, warmup_delay: float,
           beta: float = 0.0) -> list[dict]:
    """Replays `actions` through the episodes `jobs` and returns one row
    per action: the observation the action was taken on, `time` (the
    episode's clock), `elapsed` (sim-time since the first row, over
    episode ends, as the streaming collector stores it), `reward` (less
    the job-time of the span from this decision to the next; 0 inside a
    commitment round) and `reset` (the episode ended in that span and
    the next of `jobs` began).

    `jobs[k]` is episode k: `{"arrivals": [(time, template), ...],
    "time_limit": t}`; `bank_tables[template]` holds `adj` [S,S],
    `num_tasks` [S] and `rough` [S] (the duration a stage shows before a
    task of it has run); `durations[template]` holds `fresh`, `first`
    and `rest`, each [S] with None for an empty bucket; an action is
    `(job, stage, num_exec)` or `(None, None, n)` for none."""
    def episode(k):
        return _Episode(
            jobs[k], bank_tables, durations, num_executors=num_executors,
            max_jobs=max_jobs, max_stages=max_stages,
            moving_delay=moving_delay, warmup_delay=warmup_delay)

    ep, k, elapsed, rows = episode(0), 0, 0.0, []
    for job, stage, num_exec in actions:
        row = ep.observe()
        row["elapsed"] = elapsed
        t_old = float(ep.t)
        going_on = ep.decide(None if job is None else (job, stage), num_exec)
        reward, reset = 0.0, False
        if not going_on:
            while not ep.round_ready and not ep.over() and ep.events:
                ep.pop_event()
            reward = -ep.jobtime(t_old, beta)
            elapsed += float(ep.t) - t_old
            if ep.over():
                reset, k = True, k + 1
                if k < len(jobs):
                    ep = episode(k)
        rows.append(dict(row, reward=reward, reset=reset))
        if reset and k >= len(jobs):
            break
    return rows
