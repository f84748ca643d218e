"""Episode metrics (reference spark_sched_sim/metrics.py:4-23), computed
on-device from the SoA EnvState so they can be vmapped across thousands of
environment lanes and logged from the host once per iteration."""

from __future__ import annotations

import jax.numpy as jnp

from .env.state import EnvState


def job_durations(state: EnvState) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(durations[J], mask[J]) over arrived jobs: duration is
    min(t_completed, wall_time) - t_arrival (reference metrics.py:4-10)."""
    mask = state.job_arrived
    t_end = jnp.minimum(state.job_t_completed, state.wall_time)
    durations = jnp.where(mask, t_end - state.job_arrival_time, 0.0)
    return durations, mask


def avg_job_duration(state: EnvState) -> jnp.ndarray:
    d, m = job_durations(state)
    return d.sum() / jnp.maximum(m.sum(), 1)


def avg_num_jobs(state: EnvState) -> jnp.ndarray:
    """Time-average number of concurrent jobs = total job-time / wall time
    (reference metrics.py:17-18)."""
    d, _ = job_durations(state)
    return d.sum() / jnp.maximum(state.wall_time, 1e-9)


def num_completed_jobs(state: EnvState) -> jnp.ndarray:
    return (state.job_arrived & jnp.isfinite(state.job_t_completed)).sum()


def num_job_arrivals(state: EnvState) -> jnp.ndarray:
    return state.job_arrived.sum()


def episode_result(state: EnvState) -> dict[str, jnp.ndarray]:
    """What a sweep keeps of an episode, read on the state it ended in
    (`flat_loop.drain_to_decision`'s `result_fn`, before the re-seed):
    its average job completion time over the jobs that arrived (with
    every job complete, the mean of completion less arrival), the jobs
    it completed and its makespan."""
    return {
        "avg_jct": avg_job_duration(state),
        "jobs_completed": num_completed_jobs(state).astype(jnp.int32),
        "makespan": state.wall_time,
    }


PERCENTILE_QS = (25, 50, 75, 100)


def masked_percentiles(durations, mask, qs=PERCENTILE_QS):
    """Host-side percentiles over masked durations; one shared policy for
    single states and pooled vmapped batches."""
    import numpy as np

    d, m = np.asarray(durations).ravel(), np.asarray(mask).ravel()
    return np.percentile(d[m], list(qs)) if m.any() else np.zeros(len(qs))


def job_duration_percentiles(state: EnvState, qs=PERCENTILE_QS):
    """Percentiles over arrived jobs (reference metrics.py:21-23)."""
    return masked_percentiles(*job_durations(state), qs)
