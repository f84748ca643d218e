"""Workload template bank: DAG-job traces packed into device arrays.

The reference samples jobs from 22 TPC-H queries x 7 input sizes, loading
`adj_mat_*.npy` / `task_duration_*.npy` trace files per job and sampling task
durations from per-(stage, wave, executor-count-level) empirical lists
(reference: spark_sched_sim/data_samplers/tpch.py). That design — Python
dicts of variable-length lists consulted inside the event loop — cannot run
on a TPU.

Here every job *template* is packed once into fixed-shape arrays shared by
all environments:

- structure: `adj[T,S,S]`, `num_tasks[T,S]`, `num_stages[T]`, topological
  `node_level[T,S]` (precomputed for the GNN's level-wise message passing,
  replacing the per-observation nx.topological_generations of reference
  schedulers/decima/utils.py:238-267);
- durations: `dur[T,S,3,L,K]` buckets of K empirical samples per
  (stage, wave, executor-level), with counts `cnt[T,S,3,L]` and presence
  masks driving the same fallback chain as the reference's
  try/except sampling (tpch.py:75-106).

Sampling a duration on-device is then three element reads of the bank
(a bucket's count, the picked sample, the stage's rough duration) beside
one word of the lane's own state and two pre-drawn uniforms
(`sampling.sample_task_duration`) — no host round trip.
"""

from __future__ import annotations

import functools
import os.path as osp
from typing import Any

import numpy as np
from flax import struct
import jax.numpy as jnp

# executor-count levels at which the TPC-H traces record durations
# (reference tpch.py:238)
EXEC_LEVEL_VALUES = (5, 10, 20, 40, 50, 60, 80, 100)
NUM_EXEC_LEVELS = len(EXEC_LEVEL_VALUES)

# wave indices into the duration buckets
WAVE_FRESH, WAVE_FIRST, WAVE_REST = 0, 1, 2

NUM_QUERIES = 22
QUERY_SIZES = ("2g", "5g", "10g", "20g", "50g", "80g", "100g")


class WorkloadBank(struct.PyTreeNode):
    """Packed template bank. T templates, S stage slots, L executor levels,
    K duration samples per bucket. All arrays live on device and are shared
    (broadcast) across every vmapped environment lane."""

    # --- structure ---
    num_stages: jnp.ndarray  # i32[T]
    num_tasks: jnp.ndarray  # i32[T,S]
    adj: jnp.ndarray  # bool[T,S,S]; adj[t,p,c] == True iff edge p->c
    node_level: jnp.ndarray  # i32[T,S]; topological generation, S = padding
    rough_duration: jnp.ndarray  # f32[T,S]; mean duration over all buckets

    # --- durations ---
    dur: jnp.ndarray  # f32[T,S,3,L,K]
    cnt: jnp.ndarray  # i32[T,S,3,L]
    # `level_present` and `cnt > 0` are read at RESET only, packed one
    # word a (template, stage) into `EnvState.duration_facts`
    # (`sampling.pack_duration_facts`): no loop of the engine reads
    # either, nor `max_present`, which is the highest set bit of
    # `level_present` (0 where none) and is derived there from the word.
    # A bank rebuilt with `bank.replace(cnt=..., level_present=...)`
    # needs no other leaf brought in line
    level_present: jnp.ndarray  # bool[T,S,L]; key present in first_wave
    max_present: jnp.ndarray  # i32[T,S]; index of max present level

    # --- low-precision layout (ISSUE 7) ---
    # When `dur` carries an integer dtype (int8/int16 via
    # `quantize_bank`), `dur_scale` is the per-template f32[T]
    # LOG-domain dequantization scale:
    # duration = expm1(dur.astype(f32) * dur_scale[t]),
    # applied at the single use site (`sampling.sample_task_duration`)
    # so every accumulation stays f32. None for f32/bf16 banks.
    dur_scale: jnp.ndarray | None = None

    @property
    def num_templates(self) -> int:
        return self.num_stages.shape[0]

    @property
    def max_stages(self) -> int:
        return self.num_tasks.shape[1]

    @property
    def bucket_size(self) -> int:
        return self.dur.shape[-1]


def topological_levels(adj: np.ndarray, num_stages: int) -> np.ndarray:
    """Kahn's algorithm returning the topological generation index of each
    node (same grouping as nx.topological_generations). Padding slots get
    level == S."""
    s_cap = adj.shape[0]
    level = np.full(s_cap, s_cap, dtype=np.int32)
    indeg = adj[:num_stages, :num_stages].sum(axis=0)
    frontier = [int(i) for i in np.flatnonzero(indeg == 0)]
    cur = 0
    while frontier:
        nxt = []
        for u in frontier:
            level[u] = cur
            for v in np.flatnonzero(adj[u, :num_stages]):
                indeg[v] -= 1
                if indeg[v] == 0:
                    nxt.append(int(v))
        frontier = nxt
        cur += 1
    assert (level[:num_stages] < s_cap).all(), "adjacency has a cycle"
    return level


def bank_depth(bank: "WorkloadBank") -> int:
    """The topological generations of the bank's deepest DAG: one more
    than the largest `node_level` of a real node (padding slots hold
    `max_stages`). What bounds the Decima net's level scan
    (`DecimaNet.num_levels`): deeper levels update nothing."""
    level = np.asarray(bank.node_level)
    return int(np.max(np.where(level < bank.max_stages, level, -1))) + 1


def _executor_intervals(num_executors: int) -> np.ndarray:
    """Map num_local_executors -> (left, right) executor-level VALUES,
    reproducing the reference table exactly (tpch.py:237-262), including its
    behavior of leaving index `num_executors` zeroed when
    num_executors > max level (the presence fallback then kicks in)."""
    levels = list(EXEC_LEVEL_VALUES)
    cap = num_executors
    intervals = np.zeros((cap + 1, 2), dtype=np.int64)
    intervals[: levels[0] + 1] = levels[0]
    for i in range(len(levels) - 1):
        intervals[levels[i] + 1 : levels[i + 1]] = (levels[i], levels[i + 1])
        if levels[i + 1] > cap:
            break
        intervals[levels[i + 1]] = levels[i + 1]
    if cap > levels[-1]:
        intervals[levels[-1] + 1 : cap] = levels[-1]
    return intervals


def _to_idx(vals: np.ndarray) -> np.ndarray:
    """Level values -> indices into `EXEC_LEVEL_VALUES`; unknown values
    (e.g. the zeroed tail entry of the reference table) map to index 0:
    the presence fallback replaces them anyway."""
    idx = np.zeros_like(vals)
    for i, v in enumerate(EXEC_LEVEL_VALUES):
        idx[vals == v] = i
    return idx


@functools.lru_cache(maxsize=None)
def executor_interval_runs(
    num_executors: int,
) -> tuple[tuple[int, ...], tuple[tuple[int, int, int, int], ...]]:
    """`_executor_intervals(num_executors)` by its runs: the table is
    piecewise constant in num_local_executors (3 runs at 10 executors,
    9 at 50, at most 17 for any count), so it is the first
    num_local of each run (`starts`, starts[0] == 0) and the run's
    (left value, right value, left index, right index). A function of
    the executor count alone, so the sampler computes the lookup from
    these literals and no program reads an `[N+1]` table
    (`sampling.sample_executor_key`)."""
    itv = _executor_intervals(num_executors)
    table = np.concatenate([itv, _to_idx(itv)], axis=1)  # [N+1, 4]
    starts = np.flatnonzero(
        np.r_[True, (table[1:] != table[:-1]).any(axis=1)]
    )
    return (
        tuple(int(s) for s in starts),
        tuple(tuple(int(v) for v in table[s]) for s in starts),
    )


def _value_to_index() -> dict[int, int]:
    return {v: i for i, v in enumerate(EXEC_LEVEL_VALUES)}


def pack_bank(
    templates: list[dict[str, Any]],
    num_executors: int,
    max_stages: int,
    bucket_size: int,
    seed: int = 0,
) -> WorkloadBank:
    """Pack a list of host-side template dicts into a WorkloadBank.

    Each template dict has:
      adj: bool [s, s] numpy, parent->child
      num_tasks: int [s]
      durations: {stage_id: {wave_name: {level_value: list[float]}}}
        with wave_name in ('fresh_durations', 'first_wave', 'rest_wave').
        Levels present in 'first_wave' define the presence mask
        (reference tpch.py:228-231).

    The bank holds nothing that depends on `num_executors`: the sampler
    computes the executor-level interval from `EnvParams.num_executors`
    (`executor_interval_runs`). The argument stays so that a positional
    call means what it meant.
    """
    del num_executors
    rng = np.random.default_rng(seed)
    t_n = len(templates)
    s_cap = max_stages
    l_n = NUM_EXEC_LEVELS
    k = bucket_size

    num_stages = np.zeros(t_n, dtype=np.int32)
    num_tasks = np.zeros((t_n, s_cap), dtype=np.int32)
    adj = np.zeros((t_n, s_cap, s_cap), dtype=bool)
    node_level = np.full((t_n, s_cap), s_cap, dtype=np.int32)
    rough = np.zeros((t_n, s_cap), dtype=np.float32)
    dur = np.zeros((t_n, s_cap, 3, l_n, k), dtype=np.float32)
    cnt = np.zeros((t_n, s_cap, 3, l_n), dtype=np.int32)
    present = np.zeros((t_n, s_cap, l_n), dtype=bool)
    max_present = np.zeros((t_n, s_cap), dtype=np.int32)

    v2i = _value_to_index()
    wave_names = {"fresh_durations": WAVE_FRESH, "first_wave": WAVE_FIRST,
                  "rest_wave": WAVE_REST}

    for t, tpl in enumerate(templates):
        s_n = tpl["adj"].shape[0]
        assert s_n <= s_cap, f"template {t} has {s_n} stages > cap {s_cap}"
        num_stages[t] = s_n
        num_tasks[t, :s_n] = tpl["num_tasks"]
        adj[t, :s_n, :s_n] = tpl["adj"]
        node_level[t] = topological_levels(adj[t], s_n)

        for s in range(s_n):
            stage_data = tpl["durations"][s]
            all_durs: list[float] = []
            for wname, w in wave_names.items():
                for lv, samples in stage_data.get(wname, {}).items():
                    li = v2i[int(lv)]
                    samples = np.asarray(samples, dtype=np.float32)
                    all_durs.extend(samples.tolist())
                    if samples.size == 0:
                        continue
                    if samples.size > k:
                        samples = rng.choice(samples, size=k, replace=False)
                    n = samples.size
                    dur[t, s, w, li, :n] = samples
                    cnt[t, s, w, li] = n
            for lv in stage_data.get("first_wave", {}):
                present[t, s, v2i[int(lv)]] = True
            pres_idx = np.flatnonzero(present[t, s])
            max_present[t, s] = pres_idx.max() if pres_idx.size else 0
            rough[t, s] = float(np.mean(all_durs)) if all_durs else 1.0

    return WorkloadBank(
        num_stages=jnp.asarray(num_stages),
        num_tasks=jnp.asarray(num_tasks),
        adj=jnp.asarray(adj),
        node_level=jnp.asarray(node_level),
        rough_duration=jnp.asarray(rough),
        dur=jnp.asarray(dur),
        cnt=jnp.asarray(cnt),
        level_present=jnp.asarray(present),
        max_present=jnp.asarray(max_present),
    )


BANK_DTYPES = ("f32", "float32", "bf16", "bfloat16", "int8", "int16")


def bank_dtype_label(bank: WorkloadBank) -> str:
    """Short dtype tag of a bank's `dur` table for bench-row stamps
    ("f32", "bf16", "int8", "int16")."""
    name = str(bank.dur.dtype)
    return {"float32": "f32", "bfloat16": "bf16"}.get(name, name)


def quantize_bank(bank: WorkloadBank, dtype: str = "int16"
                  ) -> WorkloadBank:
    """Re-encode the bank's `dur[T,S,3,L,K]` table — by far its largest
    array — in a narrow dtype (ISSUE 7 low-precision bank layout).

    int8/int16: LOG-domain quantization with a per-template f32 scale
    (`q = rint(log1p(dur) / dur_scale[t])`, `dur_scale[t] =
    log1p(max(dur[t])) / intmax`). TPC-H durations are heavy-tailed
    (per-template maxima in the millions of ms against typical tasks
    of hundreds), so a LINEAR step of max/intmax would put ~50 ms of
    absolute error on every short task; the log code makes the error
    RELATIVE instead — bounded by expm1(dur_scale[t]/2), i.e. ~1.2e-4
    for int16 and ~6e-2 for int8, uniformly across the tail. The
    observe-path drift this buys is pinned by
    tests/test_workload_ingest.py's epsilon test.
    bfloat16: a plain cast (8-bit mantissa, no scale needed).

    Dequantization to f32 (`expm1(q * dur_scale[t])`) happens at the
    single gather site (`sampling.sample_task_duration`), so the env
    state, rewards and every accumulation stay f32; only the resident
    table and its gathers narrow. `rough_duration` ([T,S], vanishingly
    small next to the K-sample buckets) stays f32 — it is the
    empty-bucket fallback and feeds observations directly."""
    if dtype in ("f32", "float32"):
        return bank
    if dtype in ("bf16", "bfloat16"):
        return bank.replace(
            dur=bank.dur.astype(jnp.bfloat16), dur_scale=None
        )
    if dtype not in ("int8", "int16"):
        raise ValueError(
            f"unknown bank dtype {dtype!r} (have: {BANK_DTYPES})"
        )
    imax = 127 if dtype == "int8" else 32767
    # quantize in f64 on the host: an f32 log/division can land a
    # value epsilon-across a .5 step boundary and round one step off,
    # which would break the half-step error bound the epsilon test pins
    ldur = np.log1p(np.asarray(bank.dur, dtype=np.float64))
    t_max = ldur.reshape(ldur.shape[0], -1).max(axis=1)
    scale = np.where(t_max > 0, t_max / imax, 1.0)
    q = np.rint(ldur / scale[:, None, None, None, None])
    q = np.clip(q, 0, imax).astype(dtype)
    scale = scale.astype(np.float32)
    return bank.replace(
        dur=jnp.asarray(q), dur_scale=jnp.asarray(scale)
    )


def load_tpch_templates(data_dir: str = "data/tpch") -> list[dict[str, Any]]:
    """Load the real TPC-H traces (if present on disk) into host template
    dicts, applying the same preprocessing as the reference: fresh durations
    are removed from first_wave, and empty first-wave lists borrow the
    nearest lower executor level's (tpch.py:135-162)."""
    templates = []
    for size in QUERY_SIZES:
        for q in range(1, NUM_QUERIES + 1):
            qdir = osp.join(data_dir, size)
            adj = np.load(osp.join(qdir, f"adj_mat_{q}.npy"), allow_pickle=True)
            tdd = np.load(
                osp.join(qdir, f"task_duration_{q}.npy"), allow_pickle=True
            ).item()
            s_n = adj.shape[0]
            durations = {}
            ntasks = np.zeros(s_n, dtype=np.int64)
            for s in range(s_n):
                data = {k: {lv: list(v) for lv, v in d.items()}
                        for k, d in tdd[s].items()}
                e0 = next(iter(data["first_wave"]))
                ntasks[s] = len(data["first_wave"][e0]) + len(
                    data["rest_wave"][e0]
                )
                _preprocess_first_wave(data)
                durations[s] = data
            templates.append(
                {"adj": adj.astype(bool), "num_tasks": ntasks,
                 "durations": durations, "query_num": q, "query_size": size}
            )
    return templates


def _preprocess_first_wave(data: dict[str, Any]) -> None:
    """Remove fresh durations from first_wave lists, then fill empty lists
    from the nearest lower level (reference tpch.py:135-162)."""
    clean: dict[int, list[float]] = {}
    for e in data["first_wave"]:
        clean[e] = []
        fresh: dict[float, int] = {}
        for d in data["fresh_durations"].get(e, []):
            fresh[d] = fresh.get(d, 0) + 1
        for d in data["first_wave"][e]:
            if fresh.get(d, 0) > 0:
                fresh[d] -= 1
            else:
                clean[e].append(d)
    last: list[float] = []
    for e in sorted(clean.keys()):
        if len(clean[e]) == 0:
            clean[e] = last
        last = clean[e]
    data["first_wave"] = clean
