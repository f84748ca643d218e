"""Static configuration for the vectorized simulator.

The reference drives everything from a YAML file with three sections
(`trainer`/`agent`/`env`; reference: config/decima_tpch.yaml, cfg_loader.py).
We keep that YAML shape for drop-in familiarity, but the environment's shape
caps must be static so XLA sees fixed shapes: `EnvParams` is a frozen,
hashable dataclass that is passed as a `static_argnum` to jitted functions.
"""

from __future__ import annotations

import dataclasses
from argparse import ArgumentDefaultsHelpFormatter, ArgumentParser
from typing import Any

import yaml


@dataclasses.dataclass(frozen=True)
class EnvParams:
    """Static environment parameters (all shape-determining fields).

    Mirrors the reference env config (spark_sched_sim/spark_sched_sim.py:34-57)
    plus the padding caps the reference does not need because it uses dynamic
    Python object graphs.
    """

    # number of simulated executors (reference spark_sched_sim.py:37)
    num_executors: int = 10

    # hard cap on job arrivals == padded job axis. The reference allows a
    # time-limit-only episode (spark_sched_sim.py:48); with fixed shapes a
    # cap is always required.
    max_jobs: int = 50

    # padded per-job stage axis (TPC-H DAGs have <= ~20 stages)
    max_stages: int = 20

    # cap on DAG depth for the level-wise GNN scan; topological depth of a
    # DAG with max_stages nodes is at most max_stages.
    max_levels: int = 20

    # time in ms for an executor to move between jobs (reference :40)
    moving_delay: float = 2000.0

    # warmup delay in ms added to some first-wave task durations
    # (reference data_samplers/tpch.py:38-43)
    warmup_delay: float = 1000.0

    # continuous discount factor for rewards (reference :42-44)
    beta: float = 0.0

    # Poisson job arrival rate (1/ms); inverse is mean inter-arrival time
    # (reference data_samplers/tpch.py:29-32)
    job_arrival_rate: float = 4.0e-5

    # jobs that arrive together at t=0 (the Decima paper's batched
    # arrivals, section 7.2; decima-sim's `--num_init_dags`): the first
    # `num_init_jobs` jobs of a sequence, every later gap still
    # Exponential(1/rate). 1 is the streaming sequence (one job at t=0).
    # A Python-level branch of `workload.sample_job_sequence`, so at 1
    # every program traces as it did.
    num_init_jobs: int = 1

    # mean of the exponential per-episode time limit (ms). None => no time
    # limit (episode ends when all `max_jobs` jobs complete).
    # (reference wrappers/stochastic_time_limit.py)
    mean_time_limit: float | None = None

    # track per-executor release history on-device for Gantt rendering
    # (reference components/executor.py:20-26). 0 disables.
    history_cap: int = 0

    # dtype of the observation feature bank (`Observation.nodes` and
    # the recorded per-decision `StoredObs.duration` buffers):
    # "float32" (default) or "bfloat16" (ISSUE 7 low-precision
    # observation layout — halves the lane-scaled rollout-obs bytes;
    # consumers accumulate in f32, drift pinned by the observe-path
    # epsilon test). Env dynamics and rewards are f32 either way.
    # Aliases f32/bf16 normalize; anything else raises — the layout
    # checks compare the exact canonical string, and a misspelled
    # value silently running f32 would stamp mislabeled bench rows.
    obs_dtype: str = "float32"

    def __post_init__(self) -> None:
        canon = {
            "float32": "float32", "f32": "float32",
            "bfloat16": "bfloat16", "bf16": "bfloat16",
        }.get(self.obs_dtype)
        if canon is None:
            raise ValueError(
                f"obs_dtype {self.obs_dtype!r} is not one of "
                "float32/f32/bfloat16/bf16"
            )
        object.__setattr__(self, "obs_dtype", canon)
        n0 = self.num_init_jobs
        if (isinstance(n0, bool) or int(n0) != n0
                or not 1 <= n0 <= self.max_jobs):
            raise ValueError(
                f"num_init_jobs {n0!r} is not a whole number from 1 to "
                f"max_jobs ({self.max_jobs})"
            )
        object.__setattr__(self, "num_init_jobs", int(n0))

    @property
    def num_nodes(self) -> int:
        return self.max_jobs * self.max_stages

    def replace(self, **kw: Any) -> "EnvParams":
        return dataclasses.replace(self, **kw)


def env_params_from_cfg(env_cfg: dict[str, Any]) -> EnvParams:
    """Build EnvParams from a reference-style `env:` config section.

    Field values are coerced to the declared int/float types: PyYAML 1.1
    parses exponent literals without a sign (``2.0e7``) as *strings*, and
    a string smuggled into a jitted computation fails deep inside XLA."""
    types = {f.name: f.type for f in dataclasses.fields(EnvParams)}
    kw: dict[str, Any] = {}
    for k, v in env_cfg.items():
        if k not in types:
            # skipped on purpose: an upstream `env:` block also holds
            # its sampler's and renderer's keys. So a key this program
            # does not know runs as if absent, and a benchmark cell's
            # driver checks for the field it needs itself
            # (benchmarks/drivers/collect_batched.py)
            continue
        if v is not None and types[k] != "str":
            if types[k] == "int" and float(v) != int(float(v)):
                raise ValueError(f"env.{k}: {v!r} is not a whole number")
            v = int(float(v)) if types[k] == "int" else float(v)
        kw[k] = v
    if "max_jobs" not in kw and "job_arrival_cap" in env_cfg:
        kw["max_jobs"] = int(env_cfg["job_arrival_cap"])
    if "mean_time_limit" in env_cfg and "job_arrival_cap" not in env_cfg:
        # time-limit-only episodes still need a padding cap
        kw.setdefault("max_jobs", 200)
    return EnvParams(**kw)


# ---------------------------------------------------------------------------
# runtime robustness blocks (ISSUE 9): the known key sets of the
# top-level `health:` and `chaos:` YAML sections. Declarative data here
# — the single source of truth for the YAML surface — consumed by the
# trainer (health recovery policy) and sparksched_tpu/chaos.py (fault
# injection), both of which fail loudly on an unknown key: a typo'd
# sentinel knob silently disabling recovery is exactly the class of
# quiet failure the health subsystem exists to remove.
# ---------------------------------------------------------------------------

HEALTH_KEYS = frozenset({
    "enabled",  # default True when the block is present
    "max_retries",  # rollback+retry budget per iteration (default 2)
    "backoff_seconds",  # exponential-backoff base (default 1.0)
    "checkpoint_every",  # atomic train-state write cadence (0 = end only)
    "keep",  # checkpoint generations kept for corrupt-file fallback
    "straggler_ratio_max",  # quarantine threshold (no retry)
})

SERVE_KEYS = frozenset({
    # ISSUE 10: the top-level `serve:` block — the AOT decision
    # service's surface (sparksched_tpu/serve/session.py:
    # store_from_config), validated with the same fail-loud contract
    "capacity",  # sessions the store admits (one live cluster per tenant)
    "max_batch",  # micro-batch width K (the batched AOT program's shape)
    "linger_ms",  # bounded linger window (the `front: linger` A/B partner)
    "deterministic",  # greedy serving (default True)
    "donate",  # donate the store buffer to the serve programs
    "seed",  # base key for session resets / sampling
    # ISSUE 11 instrumentation (default off, zero-cost off):
    "trace",  # per-request span stamps + runlog `trace` records
    "metrics",  # attach an obs.metrics.MetricsRegistry to the store
    # ISSUE 13: continuous batching + the sharded, host-paged store
    "front",  # batching front: continuous (default) | linger
    "hot_capacity",  # device slots; < capacity pages idle sessions to host
    "shard_dp",  # shard the device store over a dp mesh (N | "auto")
    # ISSUE 14: the online learning loop's serve-side knobs
    "record",  # compile the record-on programs (per-decision StoredObs)
    "pager_aware",  # continuous front: prefer hot sessions in batches
    # ISSUE 18: the device-resident trajectory ring (record-on only)
    "ring",  # ring depth R (records); 0 = per-decision record path
    "ring_drain",  # drain cadence in decisions (default: ring // 2)
    # ISSUE 15: pipelined serve execution
    "groups",  # independently-donated slot groups (in-flight width)
    "depth",  # `front: pipelined` in-flight window depth (default: groups)
    "harvester",  # background harvester thread for output materialization
    "prefetch",  # pipelined front: page predicted-next sessions ahead
    # ISSUE 16: the network serving tier (serve/server.py HTTP front +
    # serve/router.py replica fleet) — consumed by `server_from_config`,
    # ignored by `store_from_config` exactly like the `front:` knobs.
    # All default OFF: no `replicas`/`port` keys => the in-process
    # store, byte-identical to the r15 path (zero-cost-off).
    "host",  # HTTP front bind address (default 127.0.0.1)
    "port",  # HTTP front port (0 = OS-assigned ephemeral, reported back)
    "replicas",  # serve-fleet width (0/absent = in-process, no fleet)
    "quota_sessions",  # per-tenant live-session quota (0 = unlimited)
    "quota_inflight",  # per-tenant outstanding-decide quota (0 = unlimited)
    # ISSUE 17: the fleet observability plane (obs/fleet.py collector +
    # obs/slo.py burn-rate monitor) — consumed by `server_from_config`,
    # stripped before the store like the other network-layer keys.
    # Default OFF: no `collect` key => no collector, no scrape loop,
    # `/fleet` 404s (zero-cost-off).
    "collect",  # attach the fleet collector (scrapes ride the pump)
    "collect_period_s",  # scrape period (default 1.0 s)
    "slo",  # nested declarative SLO block (obs.slo.SLO_CONFIG_KEYS:
    #   p99_ms, goodput_floor_rps, quarantine_rate_max, max_staleness,
    #   windows, rollback_on, cooldown_s, min_events)
    # ISSUE 20: the tail-latency attribution plane — a front-level
    # knob like `front:`/`linger_ms` (consumed by `front_from_config`,
    # ignored by `store_from_config`). Defaults to the `trace` value:
    # traced serving gets attribution unless explicitly disabled.
    "attribution",  # critical-path analyzer + tail exemplars on the front
    "hostprof",  # role-attributed sampling profiler over the serve threads
})

ONLINE_KEYS = frozenset({
    # ISSUE 14: the top-level `online:` block — the serve->learn->serve
    # loop's surface (sparksched_tpu/online/: TrajectoryBuffer +
    # OnlineLearner + ParamBus, built by `online.online_from_config`),
    # validated with the same fail-loud contract as health:/serve:
    "enabled",  # default True when the block is present
    "max_trajectories",  # completed-trajectory buffer bound (FIFO evict)
    "max_steps",  # decisions per trajectory segment (the padded T)
    "batch_trajectories",  # trajectories per ppo_update (the padded B)
    "max_param_lag",  # off-policy guard: skip trajectories whose
    #   params-version lag exceeds this (PPO's ratio clip covers the rest)
    "min_decisions",  # drop segments shorter than this many decisions
    "swap_every",  # publish params every N accepted learner updates
    "probation_decisions",  # post-swap decisions watched before a swap
    #   is marked good (the rollback window)
    "max_quarantine_rate",  # rollback when the post-swap quarantine
    #   rate over the probation window exceeds this
    "learner",  # nested PPO-hyperparameter overrides for the learner's
    #   trainer (lr, num_epochs, num_batches, entropy_coeff, ...)
    "seed",
})

OBS_KEYS = frozenset({
    # the top-level `obs:` block (ISSUE 2; consumed by the trainer) —
    # validated since ISSUE 11 with the same fail-loud contract as
    # health:/chaos:/serve: (a typo'd observability knob silently
    # running blind is the quiet failure this subsystem removes)
    "runlog",  # true|false|path — the JSONL event-stream sink
    "telemetry",  # thread on-device engine counters per iteration
    "episode_counters",  # with telemetry: the counters of episodes
    #   that end inside the scan (obs/telemetry.py)
    "memory",  # per-iteration device-allocator sample (default True)
    "trace_iteration",  # capture a labeled device trace of iteration N
    "trace_dir",  # where that trace lands
    "runlog_max_bytes",  # size-cap + numbered-suffix runlog rotation
    "slo",  # declarative SLO block for non-serving loops (same nested
    #   surface as serve: slo — obs.slo.SLO_CONFIG_KEYS)
})

CHAOS_KEYS = frozenset({
    "seed",  # injection-index derivation seed
    "nan_grad",  # iterations: poison one recorded reward with NaN
    "bank_row",  # iterations: poison one recorded obs duration row
    "straggler",  # iterations: inflate one lane's loop_iters counter
    "oom",  # iterations: raise a simulated RESOURCE_EXHAUSTED
    "sigkill",  # iterations: SIGKILL the process mid-iteration
    "straggler_factor",  # loop_iters inflation factor (default 100)
})


def use_fast_prng() -> None:
    """Switch jax's default PRNG to the TPU-friendly ``rbg`` impl.

    The default threefry generator unrolls to ~60 scalar-heavy HLO ops
    per draw; the simulator's hot loop draws several keys per
    micro-step (reset keys, task-duration samples), so on an op-count
    bound engine the RNG alone is a measurable slice of every step
    (jaxpr census of the sampler while it drew its own keys:
    sample_task_duration was ~200 eqns, ~180 of them threefry; since
    its callers hand it pre-drawn uniforms it holds no draw, and since
    PR 50 it is 140 equations and three row reads of the bank in one
    lane, 167 and three gathers under `vmap`: the draws are the
    callers', one table a bulk pass). ``rbg`` lowers to a single XLA
    RngBitGenerator op.

    Trade-off: rbg's split/fold_in are statistically weaker than
    threefry's, which is irrelevant for workload sampling. Keys from
    the two impls are incompatible (uint32[4] vs uint32[2]), so a
    checkpointed rng resumes only under the impl that wrote it. Tests
    keep the default threefry."""
    import jax

    jax.config.update("jax_default_prng_impl", "rbg")


def enable_compilation_cache() -> None:
    """Persist XLA compilations across processes.

    One cache for every entry point. Where ``JAX_COMPILATION_CACHE_DIR``
    is set jax reads it itself and no directory is set in code; otherwise
    the cache is the git-ignored ``.jax_cache`` directory of this
    checkout (the path is part of the cache key, so it never moves)."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                ".jax_cache",
            ),
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def load(filename: str | None = None) -> dict[str, Any]:
    """Load a YAML experiment config (reference cfg_loader.py:5-13)."""
    if not filename:
        args = make_parser().parse_args()
        filename = args.filename
    with open(filename, "r") as stream:
        return yaml.safe_load(stream)


def make_parser() -> ArgumentParser:
    parser = ArgumentParser(
        description="sparksched_tpu experiment runner",
        formatter_class=ArgumentDefaultsHelpFormatter,
    )
    parser.add_argument(
        "-f",
        "--file",
        dest="filename",
        help="experiment definition file",
        metavar="FILE",
        required=True,
    )
    return parser
