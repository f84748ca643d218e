"""Shared fixtures for golden parity tests: deterministic workloads
expressed both as a reference-env DataSampler and as a sparksched_tpu
workload bank.

The reference implementation (PUBLIC code under /root/reference) is imported
*at test time only* as a golden model; nothing from it ships in the
package."""

from __future__ import annotations

import os.path as osp
import sys
from typing import Any

import numpy as np

REFERENCE_PATH = "/root/reference"


def reference_available() -> bool:
    return osp.isdir(osp.join(REFERENCE_PATH, "spark_sched_sim"))


def _ensure_reference_on_path() -> None:
    if REFERENCE_PATH not in sys.path:
        sys.path.insert(0, REFERENCE_PATH)


# ---------------------------------------------------------------------------
# deterministic workload specs
# ---------------------------------------------------------------------------
# Each job: adjacency (parent->child), per-stage task counts, and three
# constant per-stage durations (fresh / first / rest wave). Durations are
# distinct integers to keep event times tie-free and exactly representable
# in float32.


def spec_chain() -> dict[str, Any]:
    """One job: 3-stage chain, small."""
    return {
        "arrivals": [0.0],
        "jobs": [
            {
                "adj": np.array(
                    [[0, 1, 0], [0, 0, 1], [0, 0, 0]], dtype=bool
                ),
                "num_tasks": [3, 2, 4],
                "fresh": [1013.0, 2017.0, 3023.0],
                "first": [509.0, 1021.0, 1531.0],
                "rest": [211.0, 421.0, 631.0],
            }
        ],
    }


def spec_diamond() -> dict[str, Any]:
    """One job: diamond DAG with a wide middle."""
    return {
        "arrivals": [0.0],
        "jobs": [
            {
                "adj": np.array(
                    [
                        [0, 1, 1, 0],
                        [0, 0, 0, 1],
                        [0, 0, 0, 1],
                        [0, 0, 0, 0],
                    ],
                    dtype=bool,
                ),
                "num_tasks": [2, 7, 5, 3],
                "fresh": [1511.0, 2503.0, 3511.0, 4517.0],
                "first": [701.0, 1201.0, 1709.0, 2203.0],
                "rest": [307.0, 601.0, 907.0, 1201.0],
            }
        ],
    }


def spec_multi_job(num_jobs: int = 5, seed: int = 7) -> dict[str, Any]:
    """Several staggered jobs with random-ish DAGs (deterministic seed),
    exercising moving delays, cross-job commitments and backup
    scheduling."""
    rng = np.random.default_rng(seed)
    arrivals = [0.0]
    for _ in range(num_jobs - 1):
        arrivals.append(arrivals[-1] + float(rng.integers(1000, 30000)))
    jobs = []
    for j in range(num_jobs):
        s_n = int(rng.integers(2, 7))
        adj = np.zeros((s_n, s_n), dtype=bool)
        for c in range(1, s_n):
            parents = rng.choice(c, size=min(c, int(rng.integers(1, 3))),
                                 replace=False)
            adj[parents, c] = True
        num_tasks = rng.integers(1, 9, size=s_n).tolist()
        base = rng.integers(100, 5000, size=s_n)
        jobs.append(
            {
                "adj": adj,
                "num_tasks": [int(x) for x in num_tasks],
                "fresh": [float(3 * b + 11) for b in base],
                "first": [float(2 * b + 7) for b in base],
                "rest": [float(b + 3) for b in base],
            }
        )
    return {"arrivals": arrivals, "jobs": jobs}


# ---------------------------------------------------------------------------
# reference-env side
# ---------------------------------------------------------------------------


def make_reference_env(spec: dict[str, Any], num_executors: int,
                       moving_delay: float = 2000.0):
    """Build the reference SparkSchedSimEnv driven by a deterministic
    sampler for `spec`."""
    _ensure_reference_on_path()
    import networkx as nx
    import spark_sched_sim.data_samplers as ds_mod
    from spark_sched_sim.components import Job, Stage
    from spark_sched_sim.data_samplers import DataSampler
    from spark_sched_sim.spark_sched_sim import SparkSchedSimEnv

    class FixedDataSampler(DataSampler):
        def __init__(self, **kwargs: Any) -> None:
            self.spec = kwargs["spec"]

        def reset(self, np_random: Any) -> None:
            self.np_random = np_random

        def job_sequence(self, max_time: float):
            seq = []
            for job_id, (t, jspec) in enumerate(
                zip(self.spec["arrivals"], self.spec["jobs"])
            ):
                if t >= max_time:
                    break
                stages = []
                for s, n in enumerate(jspec["num_tasks"]):
                    rough = (
                        jspec["fresh"][s] + jspec["first"][s]
                        + jspec["rest"][s]
                    ) / 3.0
                    stages.append(Stage(s, job_id, n, rough))
                dag = nx.from_numpy_array(
                    jspec["adj"].astype(int), create_using=nx.DiGraph
                )
                for _, _, d in dag.edges(data=True):
                    d.clear()
                seq.append((t, Job(job_id, stages, dag, t)))
            return seq

        def task_duration(self, job, stage, task, executor) -> float:
            jspec = self.spec["jobs"][stage.job_id]
            if executor.is_idle:
                return jspec["fresh"][stage.id_]
            if executor.task.stage_id == task.stage_id:
                return jspec["rest"][stage.id_]
            return jspec["first"][stage.id_]

    ds_mod.__dict__["FixedDataSampler"] = FixedDataSampler
    env_cfg = {
        "num_executors": num_executors,
        "moving_delay": moving_delay,
        "job_arrival_cap": len(spec["jobs"]),
        "data_sampler_cls": "FixedDataSampler",
        "spec": spec,
    }
    return SparkSchedSimEnv(env_cfg)


# ---------------------------------------------------------------------------
# sparksched_tpu side
# ---------------------------------------------------------------------------


def make_tpu_env_state(spec: dict[str, Any], num_executors: int,
                       moving_delay: float = 2000.0):
    """Build (params, bank, state) for the same spec, one template per
    job, injected arrival sequence."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env.core import reset_from_sequence
    from sparksched_tpu.workload.bank import EXEC_LEVEL_VALUES, pack_bank

    templates = []
    for jspec in spec["jobs"]:
        s_n = jspec["adj"].shape[0]
        durations = {}
        for s in range(s_n):
            durations[s] = {
                "fresh_durations": {
                    lv: [jspec["fresh"][s]] for lv in EXEC_LEVEL_VALUES
                },
                "first_wave": {
                    lv: [jspec["first"][s]] for lv in EXEC_LEVEL_VALUES
                },
                "rest_wave": {
                    lv: [jspec["rest"][s]] for lv in EXEC_LEVEL_VALUES
                },
            }
        templates.append(
            {"adj": jspec["adj"], "num_tasks": np.array(jspec["num_tasks"]),
             "durations": durations}
        )

    max_stages = max(t["adj"].shape[0] for t in templates)
    params = EnvParams(
        num_executors=num_executors,
        max_jobs=len(spec["jobs"]),
        max_stages=max_stages,
        max_levels=max_stages,
        moving_delay=moving_delay,
    )
    bank = pack_bank(templates, num_executors, max_stages, bucket_size=1)

    j_cap = params.max_jobs
    arrivals = np.full(j_cap, np.inf, dtype=np.float32)
    arrivals[: len(spec["arrivals"])] = spec["arrivals"]
    mask = np.isfinite(arrivals)
    state = reset_from_sequence(
        params, bank, jax.random.PRNGKey(0), jnp.float32(jnp.inf),
        jnp.asarray(arrivals), jnp.arange(j_cap, dtype=jnp.int32),
        jnp.int32(mask.sum()), jnp.asarray(mask),
    )
    return params, bank, state


def parent_sets_by_hand(adj: np.ndarray) -> np.ndarray:
    """`EnvState.parent_sets` recomputed from `adj[..., J, S, S]`, a bit
    at a time: bit p % 32 of word [j, p // 32, c] is adj[j, p, c]."""
    *lead, j_cap, s_cap, _ = adj.shape
    out = np.zeros((*lead, j_cap, -(-s_cap // 32), s_cap), np.uint32)
    for p in range(s_cap):
        out[..., p // 32, :] |= (
            adj[..., p, :].astype(np.uint32) << np.uint32(p % 32)
        )
    return out
