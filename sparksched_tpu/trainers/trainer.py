"""Training orchestration (reference trainers/trainer.py:25-329).

The reference Trainer spawns `num_sequences x num_rollouts` worker
processes, scatters `state_dict`s over pipes, gathers pickled rollout
buffers, and trains on them with torch. Here the whole iteration —
vmapped env resets, scanned policy-in-the-loop rollouts, returns,
baselines and the policy update — is jitted XLA code; the host loop only
carries seeds, logging, best-model tracking and checkpoints.

Config surface mirrors the reference YAML (config/decima_tpch.yaml):
`trainer:` (num_iterations, num_sequences, num_rollouts, seed,
artifacts_dir, checkpointing_freq, use_tensorboard, beta_discount |
reward_buff_cap, rollout_duration -> async mode, opt_kwargs,
max_grad_norm, + PPO keys), `agent:`, `env:`. One new required cap:
`rollout_steps` — the static scan length (the reference's dynamic episode
lengths become masked fixed-shape rollouts).

Rollouts are collected by one collector per mode, both over the flat
micro-step engine (env/flat_loop.py): `collect_flat_sync_batch`, and
`collect_flat_async_batch` when `rollout_duration` is set
(trainers/rollout.py). The optional keys `flat_event_bulk` /
`flat_bulk_events` / `flat_fulfill_bulk` / `flat_bulk_cycles` /
`flat_bulk_fused` are the engine's bulk-pass knobs.

Multi-chip: a top-level `parallel:` YAML block (`dp: auto|N`) builds a
1-D dp mesh (parallel.py) and runs the whole iteration SPMD — rollout
lanes sharded over the mesh, parameters replicated, the update's
gradient/advantage reductions lowered to one all-reduce family per step
(the minibatch permutation is shard-aligned by construction, see
trainers/ppo.py). `num_sequences * num_rollouts` must divide evenly
over the mesh.
"""

from __future__ import annotations

import abc
import hashlib
import json
import os
import os.path as osp
import pathlib
import shutil
import time
from typing import Any

import jax
import jax.numpy as jnp
import optax
from flax import serialization, struct

from .. import metrics
from ..config import (
    HEALTH_KEYS,
    OBS_KEYS,
    EnvParams,
    env_params_from_cfg,
)
from ..env import core
from ..env.health import (
    H_OOM,
    H_STRAGGLER,
    RETRYABLE_MASK,
    describe_mask,
)
from ..obs import RunLog, emit
from ..obs.memory import device_memory_stats
from ..obs.telemetry import summarize, telemetry_zeros_like
from ..obs.tracing import annotate, mark, open_span, span, spanned
from ..schedulers import TrainableScheduler, make_scheduler
from ..workload import bank_depth, make_workload_bank
from .baselines import group_baselines
from .profiler import Profiler
from .returns import (
    AvgNumJobsBuffer,
    differential_returns,
    discounted_returns,
    step_dts,
)
from ..env.flat_loop import init_loop_state
from .rollout import (
    Rollout,
    collect_flat_async_batch,
    collect_flat_sync_batch,
)

CfgType = dict[str, Any]

# trainer keys that chose among collectors until PR 32 (MIGRATION.md)
REMOVED_TRAINER_KEYS = (
    "rollout_engine", "flat_single_eval", "flat_micro_per_decision",
    "flat_event_burst",
)


class TrainState(struct.PyTreeNode):
    params: Any
    opt_state: Any
    rng: jax.Array
    buf: AvgNumJobsBuffer | None  # differential-returns window, or None
    iteration: jnp.ndarray  # i32 []


def make_optimizer(train_cfg: CfgType) -> optax.GradientTransformation:
    """Adam + global-norm clipping (reference scheduler.py:37-54,
    decima_tpch.yaml:60-63). Optional `lr_anneal: {final, steps}`
    geometrically decays the learning rate over optimizer steps — a
    training-stability lever beyond the reference's fixed lr."""
    opt_cls = train_cfg.get("opt_cls", "Adam").lower()
    kwargs = dict(train_cfg.get("opt_kwargs") or {})
    lr = float(kwargs.pop("lr", 3e-4))
    anneal = train_cfg.get("lr_anneal")
    if anneal:
        final = float(anneal["final"])
        steps = int(anneal["steps"])
        lr = optax.exponential_decay(
            init_value=lr, transition_steps=steps,
            decay_rate=final / lr, end_value=final,
        )
    makers = {
        "adam": optax.adam,
        "adamw": optax.adamw,
        "sgd": optax.sgd,
        "rmsprop": optax.rmsprop,
    }
    if opt_cls not in makers:
        raise ValueError(f"unsupported optimizer {opt_cls!r}")
    tx = makers[opt_cls](lr, **kwargs)
    max_grad_norm = train_cfg.get("max_grad_norm")
    if max_grad_norm:
        tx = optax.chain(optax.clip_by_global_norm(max_grad_norm), tx)
    return tx


class Trainer(abc.ABC):
    """Base trainer; subclasses implement the jitted `_update`."""

    @span("setup/trainer_init")
    def __init__(self, agent_cfg: CfgType, env_cfg: CfgType,
                 train_cfg: CfgType, mesh=None,
                 obs_cfg: CfgType | None = None,
                 health_cfg: CfgType | None = None,
                 chaos_cfg: CfgType | None = None) -> None:
        # where this trainer's host spans begin in the process's record
        # (`make_trainer` moves it to its `setup/mesh`, the end of a
        # run past the run's spans): a runlog's backlog starts there
        self._spans_from: int = open_span().ordinal
        for key in REMOVED_TRAINER_KEYS:
            if key in train_cfg:
                raise ValueError(
                    f"trainer config key {key!r} was removed: the trainer "
                    "has one collector per mode, over the flat engine. "
                    "Delete the key; see MIGRATION.md (PR 32)"
                )
        # TPU-friendly rbg PRNG for the whole training program (the env
        # hot loop draws several keys per micro-step; see
        # config.use_fast_prng). Must run before any key is created.
        # An rng checkpointed under one impl resumes only under the
        # same impl (uint32[4] vs uint32[2] keys).
        if train_cfg.get("fast_prng", False):
            from ..config import use_fast_prng

            use_fast_prng()
        self.seed: int = train_cfg.get("seed", 42)
        self.num_iterations: int = train_cfg["num_iterations"]
        self.num_sequences: int = train_cfg["num_sequences"]
        self.num_rollouts: int = int(train_cfg["num_rollouts"])
        self.num_envs = self.num_sequences * self.num_rollouts

        self.artifacts_dir: str = train_cfg.get("artifacts_dir", "artifacts")
        self.use_tensorboard: bool = train_cfg.get("use_tensorboard", False)
        self.checkpointing_freq: int = train_cfg.get(
            "checkpointing_freq", 50
        )
        rd = train_cfg.get("rollout_duration")
        # YAML exponent literals without a sign ("2.0e7") arrive as strings
        self.rollout_duration = float(rd) if rd is not None else None

        # training-stability levers beyond the reference's fixed
        # hyperparameters (its README credits tuning for stability;
        # these make the schedule explicit and checkpoint-resumable):
        # entropy_anneal: {final, iterations} — geometric decay of the
        # entropy bonus from `entropy_coeff` to `final`;
        # fixed_sequences: true — train every iteration on the same
        # `num_sequences` job sequences instead of resampling (lower
        # gradient variance early in training).
        self.entropy_anneal = train_cfg.get("entropy_anneal")
        if self.entropy_anneal and "final" not in self.entropy_anneal:
            raise ValueError("entropy_anneal requires a 'final' value")
        if self.entropy_anneal and "iterations" not in self.entropy_anneal:
            # `num_iterations` counts iterations *per session* while
            # state.iteration is absolute across resumed sessions, so an
            # implicit horizon would silently pin the coefficient at
            # `final` for every session after the first
            raise ValueError(
                "entropy_anneal requires an explicit 'iterations' horizon "
                "(absolute iteration count, spanning resumed sessions)"
            )
        self.fixed_sequences = bool(train_cfg.get("fixed_sequences", False))
        if self.fixed_sequences and self.rollout_duration:
            # async lanes draw each mid-scan episode from
            # fold_in(seq_base, reset_count); only the initial reset
            # would be pinned, so the flag's guarantee cannot hold
            raise ValueError(
                "fixed_sequences is only supported in sync mode "
                "(remove rollout_duration)"
            )

        # per-iteration wall-time reporting + optional device trace of the
        # first iteration (the reference wraps every rollout in cProfile,
        # rollout_worker.py:103; host profiles are meaningless for jitted
        # programs, so this uses the jax.profiler-backed Profiler)
        self.profiling: bool = bool(train_cfg.get("profiling", False))
        self.profile_trace_dir = train_cfg.get("profile_trace_dir")

        # observability block (top-level `obs:` YAML section):
        #   runlog: true|false|path — JSONL event stream (spans, stats,
        #     telemetry summaries, JIT recompiles) under artifacts/
        #     (the default sink; TensorBoard stays a mirror)
        #   telemetry: true — thread engine counters through the rollout
        #     collectors and summarize once per iteration
        #   episode_counters: true — with `telemetry`, also carry the
        #     three counters of episodes that END inside the scan
        #     (obs/telemetry.py: rows a lane sat out after its end,
        #     episodes ended by completion, jobs a decision saw); off,
        #     they are no part of the collector's program
        #   memory: true (default) — sample the device allocator
        #     (`obs.memory.device_memory_stats`) once per iteration and
        #     emit a `memory` runlog record + mem_* scalars; a no-op on
        #     backends without allocator stats (CPU), so the default
        #     costs nothing off-chip
        #   trace_iteration: N — capture a labeled jax.profiler device
        #     trace of (absolute) iteration N's collect+update
        #   trace_dir: where that trace lands (default
        #     artifacts/trace)
        #   runlog_max_bytes: N — size-cap + numbered-suffix rotation
        #     of the runlog file (ISSUE 11; 0/absent = unbounded)
        oc = dict(obs_cfg or {})
        if set(oc) - OBS_KEYS:
            raise ValueError(
                "unknown obs: config key(s) "
                f"{sorted(set(oc) - OBS_KEYS)} — known keys: "
                f"{sorted(OBS_KEYS)}"
            )
        self.obs_runlog = oc.get("runlog", True)
        rmb = oc.get("runlog_max_bytes")
        self.obs_runlog_max_bytes = int(rmb) if rmb else None
        self.obs_telemetry: bool = bool(oc.get("telemetry", False))
        self.obs_episode_counters: bool = bool(
            oc.get("episode_counters", False)
        )
        self.obs_memory: bool = bool(oc.get("memory", True))
        ti = oc.get("trace_iteration")
        self.obs_trace_iteration = None if ti is None else int(ti)
        self.obs_trace_dir: str = oc.get(
            "trace_dir", osp.join(self.artifacts_dir, "trace")
        )
        self._runlog: RunLog | None = None

        # self-healing block (top-level `health:` YAML section, ISSUE 9):
        #   enabled: true (default when the block is present) — thread
        #     the in-JIT health sentinels through the rollout collectors
        #     and the PPO update, and turn on automatic recovery (skip
        #     the poisoned update in-JIT; on a tripped sentinel roll
        #     back to the last-good state, reseed the iteration rng, and
        #     retry with exponential backoff)
        #   max_retries: 2 — rollback+retry budget per iteration; an
        #     iteration still unhealthy past it raises (poisoned params
        #     must never train on)
        #   backoff_seconds: 1.0 — base of the exponential backoff
        #   checkpoint_every: N — atomically save the full train state
        #     every N iterations (0 = session end only), the preemption
        #     half: a SIGKILLed window resumes from the last write
        #   keep: 2 — checkpoint generations retained for the
        #     corrupt-file fallback in `load_train_state`
        #   straggler_ratio_max: float — quarantine (runlog `health`
        #     record, no retry) iterations whose measured while-loop
        #     straggler ratio exceeds this
        # Enabling health forces telemetry threading (the mask rides the
        # Telemetry carry) and disables the async-carry donation so a
        # rolled-back iteration can re-collect from the pre-iteration
        # lanes (one extra resident LoopState copy — the price of
        # rollback).
        hc = dict(health_cfg or {})
        if set(hc) - HEALTH_KEYS:
            raise ValueError(
                "unknown health: config key(s) "
                f"{sorted(set(hc) - HEALTH_KEYS)} — known keys: "
                f"{sorted(HEALTH_KEYS)}"
            )
        self.health_enabled: bool = bool(
            hc.get("enabled", health_cfg is not None)
        )
        self.health_max_retries: int = int(hc.get("max_retries", 2))
        self.health_backoff: float = float(hc.get("backoff_seconds", 1.0))
        self.health_checkpoint_every: int = int(
            hc.get("checkpoint_every", 0)
        )
        self.checkpoint_keep: int = int(hc.get("keep", 2))
        srm = hc.get("straggler_ratio_max")
        self.health_straggler_max = None if srm is None else float(srm)
        if self.health_enabled:
            self.obs_telemetry = True

        # deterministic fault injection (top-level `chaos:` YAML block;
        # sparksched_tpu/chaos.py) — drills the recovery paths above
        self._chaos = None
        if chaos_cfg:
            from ..chaos import ChaosMonkey

            self._chaos = ChaosMonkey(chaos_cfg)
            if self._chaos.any_scheduled() and not self.health_enabled:
                emit(
                    "[chaos] warning: chaos: faults scheduled without a "
                    "health: block — injections will NOT be detected or "
                    "recovered (this is only useful for negative tests)"
                )

        # exactly one returns mode (reference trainer.py:63-74)
        assert ("reward_buff_cap" in train_cfg) ^ (
            "beta_discount" in train_cfg
        ), "provide exactly one of reward_buff_cap / beta_discount"
        self.beta: float = float(train_cfg.get("beta_discount", 0.0))
        self.reward_buff_cap: int = int(
            train_cfg.get("reward_buff_cap", 0)
        )
        if self.beta:
            env_cfg = env_cfg | {"beta": self.beta}

        self.params_env: EnvParams = env_params_from_cfg(env_cfg)
        with span("setup/workload_bank"):
            self.bank = make_workload_bank(
                self.params_env.num_executors, self.params_env.max_stages,
                **{k: v for k, v in env_cfg.items()
                   if k in ("data_dir", "bucket_size", "data_sampler_cls",
                            "bank_dtype")},
            )
        if self.bank.max_stages != self.params_env.max_stages:
            self.params_env = self.params_env.replace(
                max_stages=self.bank.max_stages,
                max_levels=max(self.params_env.max_levels,
                               self.bank.max_stages),
            )

        # static rollout scan length
        self.rollout_steps: int = train_cfg.get(
            "rollout_steps", 48 * self.params_env.max_jobs
        )

        # bound the Decima level scan by the bank's true max DAG depth
        # (bit-identical — deeper levels are no-op updates — and the
        # dominant GNN cost scales with it; the synthetic bank is 6 deep
        # vs a 20-stage cap). An explicit agent num_levels wins.
        with span("setup/scheduler_init"):
            scheduler = make_scheduler(
                {"num_levels": bank_depth(self.bank)}
                | agent_cfg
                | {"num_executors": self.params_env.num_executors}
            )
        if not isinstance(scheduler, TrainableScheduler):
            raise ValueError(
                f"{type(scheduler).__name__} is not a TrainableScheduler"
            )
        self.scheduler: TrainableScheduler = scheduler
        # the flat engine's bulk-pass knobs, passed to both collectors.
        # fulfill_bulk: leftovers otherwise cost one drain iteration
        # each, and the pass's op count rides the decision row.
        # bulk_fused: one fused bulk kernel (mixed relaunch/arrival runs
        # in one pass) against the pass pair; step-exact either way
        self.flat_knobs = {
            "event_bulk": bool(train_cfg.get("flat_event_bulk", True)),
            "bulk_events": int(train_cfg.get("flat_bulk_events", 8)),
            "fulfill_bulk": bool(train_cfg.get("flat_fulfill_bulk", True)),
            "bulk_cycles": int(train_cfg.get("flat_bulk_cycles", 1)),
            "bulk_fused": bool(train_cfg.get("flat_bulk_fused", True)),
        }
        self.tx = make_optimizer(train_cfg)
        self.train_cfg = train_cfg
        self._env_states = None  # async mode: persistent lanes

        # SPMD over a device mesh: rollout lanes sharded along the dp axis,
        # parameters replicated; the update's cross-lane reductions lower to
        # XLA collectives (see parallel.py). The persistent async carry
        # (env_states, arg 3) is donated on both paths: the host never
        # reads it between iterations, and donation lets XLA alias the
        # lane-sharded LoopState buffers across iterations instead of
        # holding two copies of the largest resident state per device.
        self.mesh = mesh
        self._lane_sharding = None
        # health rollback needs the pre-iteration async carry to stay
        # valid after a (possibly poisoned) collect, so donation is off
        # under the health block (see the health: comment above)
        donate = () if self.health_enabled else (3,)
        if mesh is not None:
            from ..parallel import lane_sharding

            lanes = lane_sharding(mesh)
            assert self.num_envs % mesh.size == 0, (
                f"num_sequences*num_rollouts={self.num_envs} must divide "
                f"evenly over {mesh.size} devices"
            )
            self._lane_sharding = lanes
            # every _collect output is lane-leading: the Rollout, the
            # async (LoopState, reset_counts) carry, and the per-lane
            # Telemetry — shard them all, or the carry round-trips
            # through a replicated layout every iteration
            collect_jit = jax.jit(
                self._collect, out_shardings=(lanes, lanes, lanes),
                donate_argnums=donate,
            )
            update_jit = jax.jit(
                self._update, in_shardings=(None, lanes),
                out_shardings=None,
            )
        else:
            collect_jit = jax.jit(self._collect, donate_argnums=donate)
            update_jit = jax.jit(self._update)
        # the `jit` objects under a host span a call (obs/tracing.py):
        # seconds of `collect/call` past an iteration's first are a
        # re-trace, named and timed
        self._collect_jit = spanned("collect/call", collect_jit)
        self._update_jit = spanned("train/update_call", update_jit)

    # ------------------------------------------------------------------
    # device-side pieces
    # ------------------------------------------------------------------

    @span("setup/init_state")
    def init_state(self) -> TrainState:
        params = self.scheduler.params
        return TrainState(
            params=params,
            opt_state=self.tx.init(params),
            rng=jax.random.PRNGKey(self.seed),
            buf=(AvgNumJobsBuffer.create(self.reward_buff_cap)
                 if self.reward_buff_cap else None),
            iteration=jnp.zeros((), jnp.int32),
        )

    def _entropy_coeff_at(self, base: float, iteration: jnp.ndarray):
        """Entropy coefficient at `iteration` under the optional
        geometric anneal (jit-traceable)."""
        if not self.entropy_anneal or not base:
            return base
        final = float(self.entropy_anneal["final"])
        n = float(self.entropy_anneal["iterations"])
        frac = jnp.clip(iteration.astype(jnp.float32) / n, 0.0, 1.0)
        return base * (final / base) ** frac

    def _collect(self, model_params, iteration: jnp.ndarray,
                 rng: jax.Array, env_states) -> tuple[Rollout, Any, Any]:
        """One iteration's rollouts: one scan over the [B] lane batch.
        Seed layout mirrors the reference (trainer.py:268-271): lanes in
        the same sequence group share the job-sequence key, refreshed per
        reset. Returns `(rollout, env_states, telemetry)` — telemetry is
        a per-lane `obs.Telemetry` when `obs: telemetry` is on, else
        None."""
        p, bank = self.params_env, self.bank
        G, R = self.num_sequences, self.num_rollouts
        master = jax.random.PRNGKey(self.seed)
        telem0 = (
            telemetry_zeros_like(
                (G * R,), episodes=self.obs_episode_counters
            ) if self.obs_telemetry else None
        )
        if self.fixed_sequences:
            iteration = jnp.zeros_like(iteration)

        def seq_key(g, reset_count):
            return jax.random.fold_in(
                jax.random.fold_in(master, g), reset_count
            )

        g_ids = jnp.repeat(jnp.arange(G), R)
        r_ids = jnp.tile(jnp.arange(R), G)

        def fresh_states():
            # a scope of its own (obs/tracing.py): every lane's reset
            # program, once a collection in sync mode
            with annotate("collect/reset"):
                seq_rngs = jax.vmap(lambda g: seq_key(g, iteration))(g_ids)
                lane_rngs = jax.vmap(
                    lambda s, r: jax.random.fold_in(s, 1000 + r)
                )(seq_rngs, r_ids)
                return jax.vmap(
                    lambda s, l: core.reset_pair(p, bank, s, l)
                )(seq_rngs, lane_rngs)

        def batch_policy_fn(k, obs):
            return self.scheduler.batch_policy(k, obs, model_params)

        k_pol = jax.random.fold_in(rng, 7)
        # the collectors' return shape switches on the Python-level
        # None check of telem0 at trace time
        track = telem0 is not None
        if not self.rollout_duration:  # sync: fresh episode per iteration
            out = collect_flat_sync_batch(
                p, bank, batch_policy_fn, k_pol, self.rollout_steps,
                fresh_states(), telem0,
                lane_shard=self._lane_sharding,
                health=self.health_enabled, **self.flat_knobs,
            )
            ro, telem = out if track else (out, None)
            return ro, None, telem
        if env_states is None:
            states = jax.vmap(init_loop_state)(fresh_states())
            # the initial reset consumed ordinal `iteration`; the
            # next (mid-scan) reset of any lane is ordinal + 1
            reset_counts = jnp.full((G * R,), iteration + 1, jnp.int32)
        else:
            states, reset_counts = env_states
        seq_bases = jax.vmap(
            lambda g: jax.random.fold_in(master, g)
        )(g_ids)
        lane_salts = (1000 + r_ids).astype(jnp.int32)
        out = collect_flat_async_batch(
            p, bank, batch_policy_fn, k_pol, self.rollout_steps, states,
            self.rollout_duration, seq_bases, lane_salts, reset_counts,
            telem0, lane_shard=self._lane_sharding,
            health=self.health_enabled, **self.flat_knobs,
        )
        ro, loop_states, telem = out if track else (out + (None,))
        return ro, (loop_states, ro.final_reset_count), telem

    def _returns_and_baselines(self, state: TrainState, ro: Rollout):
        """Shared preprocessing (reference trainer.py:172-212)."""
        T = self.rollout_steps
        dts = step_dts(ro.wall_times)  # [B,T]
        if self.beta:
            returns = discounted_returns(ro.reward, dts, self.beta)
            buf = state.buf
            avg_num_jobs = None
        else:
            buf = state.buf.extend(dts, ro.reward, ro.valid)
            avg_num_jobs = buf.avg_num_jobs()
            returns = differential_returns(ro.reward, dts, avg_num_jobs)
        G, R = self.num_sequences, self.num_rollouts
        obs_times = ro.wall_times[:, :T]
        baselines = group_baselines(
            obs_times.reshape(G, R, T),
            returns.reshape(G, R, T),
            ro.valid.reshape(G, R, T),
        ).reshape(G * R, T)
        return returns, baselines, buf, avg_num_jobs

    @abc.abstractmethod
    def _update(self, state: TrainState, ro: Rollout):
        """One policy update from an iteration's rollouts. Returns
        (new TrainState, stats dict of scalars)."""

    # ------------------------------------------------------------------
    # host loop
    # ------------------------------------------------------------------

    def train(self, resume_from: str | None = None) -> TrainState:
        """Run `num_iterations` more iterations, optionally resuming a
        saved full train state (params + optimizer + returns window + RNG +
        iteration counter) — the resume capability the reference lacks
        (its checkpoints are model weights only, trainer.py:256-262)."""
        self._setup(fresh=resume_from is None)
        if resume_from:
            state = self.load_train_state(resume_from)
            emit(f"Resumed from {resume_from} at iteration "
                 f"{int(state.iteration)}.")
            if self._runlog is not None:
                self._runlog.write(
                    "resume", path=resume_from,
                    iteration=int(state.iteration),
                )
        else:
            state = self.init_state()
        best: dict[str, Any] | None = None
        start = int(state.iteration)
        sink = (
            self._runlog.span_event if self._runlog is not None else None
        )

        for i in range(start, start + self.num_iterations):
            # device trace: the obs-block iteration (absolute) wins; the
            # legacy profile_trace_dir traces the session's first
            # iteration's collect as before
            if i == self.obs_trace_iteration:
                trace_dir = self.obs_trace_dir
            elif i == start and self.profile_trace_dir:
                trace_dir = self.profile_trace_dir
            else:
                trace_dir = None
            trace_upd = (
                self.obs_trace_dir if i == self.obs_trace_iteration
                else None
            )
            # recovery loop (ISSUE 9): with `health:` off this runs the
            # iteration exactly once with the pre-health rng derivation;
            # with it on, a tripped sentinel rolls back to `last_good`
            # (the pre-iteration TrainState and async carry — donation
            # is off under health, so the carry stays valid), reseeds
            # the iteration rng, and retries under exponential backoff.
            last_good = state
            prev_env_states = self._env_states
            attempt = 0
            while True:
                rng_i = jax.random.fold_in(
                    jax.random.PRNGKey(self.seed), i
                )
                if attempt:
                    # reseeded retry: a fresh minibatch permutation and
                    # policy-sampling stream for the re-run
                    rng_i = jax.random.fold_in(rng_i, 90_000 + attempt)
                state = last_good.replace(rng=rng_i)
                try:
                    with Profiler(trace_dir, f"iter {i + 1} collect",
                                  quiet=not self.profiling,
                                  sink=sink) as p_col:
                        ro, env_states_new, telem = self._collect_jit(
                            state.params, state.iteration, state.rng,
                            prev_env_states,
                        )
                        jax.block_until_ready(ro.reward)
                    if self._chaos is not None:
                        ro, injected = self._chaos.poison_rollout(
                            ro, i, attempt
                        )
                        telem, inj2 = self._chaos.inflate_straggler(
                            telem, i, attempt
                        )
                        injected += inj2
                        if injected and self._runlog is not None:
                            self._runlog.write(
                                "chaos", iteration=i, attempt=attempt,
                                injected=injected,
                            )
                        self._chaos.maybe_sigkill(i)
                        self._chaos.maybe_raise_oom(i, attempt)
                    prev_params = state.params
                    with Profiler(trace_upd, f"iter {i + 1} update",
                                  quiet=not self.profiling,
                                  sink=sink) as p_upd:
                        state, stats = self._update_jit(state, ro)
                        jax.block_until_ready(state.params)
                except Exception as e:
                    if not (self.health_enabled
                            and "RESOURCE_EXHAUSTED" in str(e)):
                        raise
                    if not self._record_health_and_retry(
                        i, attempt, H_OOM, detail=str(e)[:300]
                    ):
                        raise
                    attempt += 1
                    continue
                tsum = summarize(telem) if telem is not None else None
                health_mask = 0
                if self.health_enabled:
                    if tsum is not None:
                        health_mask |= int(tsum.get("health_mask", 0))
                    hm_stat = stats.get("health_mask")
                    if hm_stat is not None:
                        health_mask |= int(hm_stat)
                    if (self.health_straggler_max is not None
                            and tsum is not None
                            and tsum["straggler_ratio"]
                            > self.health_straggler_max):
                        health_mask |= H_STRAGGLER
                if health_mask & RETRYABLE_MASK:
                    if not self._record_health_and_retry(
                        i, attempt, health_mask
                    ):
                        raise RuntimeError(
                            f"iteration {i + 1} still unhealthy "
                            f"({describe_mask(health_mask)}) after "
                            f"{attempt} retr"
                            f"{'y' if attempt == 1 else 'ies'} — "
                            "refusing to train on a poisoned state"
                        )
                    attempt += 1
                    continue
                if health_mask:  # non-retryable bits (straggler):
                    # quarantine the observation, keep the iteration
                    self._record_health(i, attempt, health_mask,
                                        action="quarantine")
                break
            self._env_states = env_states_new
            state = state.replace(iteration=state.iteration + 1)

            roll_stats = self._rollout_stats(ro)
            # the rollout is the largest thing on the device (6 GB at
            # the flagship's 16 lanes x 9600 steps): do not hold it
            # through the next iteration's collection
            del ro
            avg_num_jobs = float(
                stats.get("avg_num_jobs_est") or roll_stats["avg_num_jobs"]
            )

            if best is None or avg_num_jobs < best["avg_num_jobs"]:
                best = {
                    "iteration": i,
                    "avg_num_jobs": round(avg_num_jobs, 3),
                    "params": jax.device_get(prev_params),
                    "completed_job_count": int(
                        roll_stats["num_completed_jobs"]
                    ),
                }
            if (i + 1) % self.checkpointing_freq == 0:
                self._checkpoint(i, best, state)
                best = None

            host_stats = {
                k: float(v) for k, v in stats.items()
                if v is not None
                and k not in ("avg_num_jobs_est", "health_mask")
            }
            host_stats["collect_seconds"] = p_col.elapsed
            host_stats["update_seconds"] = p_upd.elapsed
            if self.health_enabled:
                host_stats["health_mask"] = float(health_mask)
                host_stats["health_retries"] = float(attempt)
            if tsum is not None:
                if self._runlog is not None:
                    self._runlog.telemetry(tsum, iteration=i)
                host_stats["straggler_ratio"] = tsum["straggler_ratio"]
                host_stats["micro_per_decision"] = tsum[
                    "micro_per_decision"
                ]
                host_stats["events_per_decision"] = tsum[
                    "events_per_decision"
                ]
            if self.obs_memory:
                # one host call per iteration, after the update sync —
                # outside the timed collect/update spans, so the sample
                # reads the iteration's peak without riding its clock
                mem = device_memory_stats()
                if mem is not None:
                    if self._runlog is not None:
                        self._runlog.memory(mem, iteration=i)
                    for src, dst in (
                        ("bytes_in_use", "mem_bytes_in_use"),
                        ("peak_bytes_in_use", "mem_peak_bytes"),
                    ):
                        if mem.get(src) is not None:
                            host_stats[dst] = mem[src]
            self._write_stats(i, host_stats | roll_stats)
            # preemption safety (ISSUE 9): an atomic full-train-state
            # write every N iterations, so a SIGKILLed window resumes
            # from the last completed iteration instead of the session
            # start (the end-of-session save in _cleanup never runs
            # under SIGKILL)
            if (self.health_enabled and self.health_checkpoint_every
                    and (i + 1) % self.health_checkpoint_every == 0):
                self.save_train_state(
                    state,
                    osp.join(self.artifacts_dir, "train_state.msgpack"),
                )
            emit(
                f"Iteration {i + 1} complete. Avg. # jobs: "
                f"{avg_num_jobs:.3f}"
            )
        self._cleanup(state)
        return state

    # ------------------------------------------------------------------
    # health recording / recovery policy (ISSUE 9)
    # ------------------------------------------------------------------

    def _record_health(self, i: int, attempt: int, mask: int,
                       action: str, **fields: Any) -> None:
        """One runlog `health` record (the quarantine marker): the raw
        bitmask, its decoded bit names, and what the trainer did about
        it."""
        bits = describe_mask(mask)
        if self._runlog is not None:
            self._runlog.health(
                mask, iteration=i, attempt=attempt, action=action,
                **fields,
            )
        emit(
            f"[health] iteration {i + 1} attempt {attempt}: "
            f"{bits or [hex(mask)]} -> {action}"
        )

    def _record_health_and_retry(self, i: int, attempt: int, mask: int,
                                 **fields: Any) -> bool:
        """Record a tripped sentinel and decide the retry: True means
        "rolled back, backoff slept, caller should re-run the
        iteration"; False means the retry budget is exhausted."""
        if attempt >= self.health_max_retries:
            self._record_health(i, attempt, mask, action="gave_up",
                                **fields)
            if self._runlog is not None:
                self._runlog.write(
                    "recovery", iteration=i, attempt=attempt,
                    action="gave_up", mask=int(mask),
                    bits=describe_mask(mask),
                )
            return False
        delay = self.health_backoff * (2.0 ** attempt)
        self._record_health(i, attempt, mask, action="rollback_retry",
                            backoff_seconds=round(delay, 3), **fields)
        if self._runlog is not None:
            self._runlog.write(
                "recovery", iteration=i, attempt=attempt,
                action="rollback_retry", mask=int(mask),
                bits=describe_mask(mask),
                backoff_seconds=round(delay, 3),
            )
        time.sleep(delay)
        return True

    # ------------------------------------------------------------------
    # stats / io
    # ------------------------------------------------------------------

    def _rollout_stats(self, ro: Rollout) -> dict[str, float]:
        fs = ro.final_state
        d, m = jax.vmap(metrics.job_durations)(fs)
        pcts = metrics.masked_percentiles(d, m)  # pooled across lanes
        pct_stats = {
            f"job_duration_p{q}": float(v)
            for q, v in zip(metrics.PERCENTILE_QS, pcts)
        }
        return pct_stats | {
            "avg_job_duration": float(
                jax.vmap(metrics.avg_job_duration)(fs).mean()
            ),
            "avg_num_jobs": float(
                jax.vmap(metrics.avg_num_jobs)(fs).mean()
            ),
            "num_completed_jobs": float(
                jax.vmap(metrics.num_completed_jobs)(fs).mean()
            ),
            "num_job_arrivals": float(
                jax.vmap(metrics.num_job_arrivals)(fs).mean()
            ),
            "episode_length": float(ro.valid.sum(-1).mean()),
        }

    def _setup(self, fresh: bool = True) -> None:
        pathlib.Path(self.artifacts_dir).mkdir(parents=True, exist_ok=True)
        self.checkpointing_dir = osp.join(self.artifacts_dir, "checkpoints")
        if fresh:
            shutil.rmtree(self.checkpointing_dir, ignore_errors=True)
        os.makedirs(self.checkpointing_dir, exist_ok=True)
        if self.obs_runlog and self._runlog is None:
            if isinstance(self.obs_runlog, str):
                self._runlog = RunLog(
                    self.obs_runlog,
                    max_bytes=self.obs_runlog_max_bytes,
                )
            else:
                self._runlog = RunLog.create(
                    self.artifacts_dir,
                    max_bytes=self.obs_runlog_max_bytes,
                )
            self._runlog.install_jit_hooks()
            self._runlog.write(
                "run_start",
                trainer=type(self).__name__,
                num_iterations=self.num_iterations,
                num_envs=self.num_envs,
                rollout_steps=self.rollout_steps,
                telemetry=self.obs_telemetry,
                memory=self.obs_memory,
                seed=self.seed,
            )
            self._runlog.follow_spans(self._spans_from)
        self._tb = None
        if self.use_tensorboard:
            # a heavy torch dependency in a JAX repo: degrade to the
            # JSONL runlog (the default sink) instead of crashing when
            # torch/tensorboard is absent
            try:
                from torch.utils.tensorboard import SummaryWriter
            except ImportError as e:
                emit(
                    "use_tensorboard: torch.utils.tensorboard is "
                    f"unavailable ({e}); stats go to the JSONL runlog "
                    "instead"
                    + (
                        f" ({self._runlog.path})"
                        if self._runlog is not None
                        else " (enable it via the obs: config block)"
                    )
                )
            else:
                self._tb = SummaryWriter(
                    osp.join(self.artifacts_dir, "tb")
                )

    def _cleanup(self, state: TrainState) -> None:
        if self._tb is not None:
            self._tb.close()
        # always leave a resumable final state behind (the reference cannot
        # resume: it only saves model weights, trainer.py:256-262)
        self.save_train_state(
            state, osp.join(self.artifacts_dir, "train_state.msgpack")
        )
        if self._runlog is not None:
            self._runlog.close(iteration=int(state.iteration))
            self._runlog = None
            self._spans_from = mark()
        emit("\nTraining complete.")

    def _checkpoint(self, i: int, best: dict[str, Any],
                    state: TrainState) -> None:
        d = osp.join(self.checkpointing_dir, f"{i + 1}")
        os.makedirs(d, exist_ok=True)
        with open(osp.join(d, "model.msgpack"), "wb") as fp:
            fp.write(serialization.to_bytes(best["params"]))
        meta = {k: v for k, v in best.items() if k != "params"}
        with open(osp.join(d, "state.json"), "w") as fp:
            json.dump(meta, fp)

    def save_train_state(self, state: TrainState, path: str,
                         keep: int | None = None) -> None:
        """Atomic, digest-stamped, keep-last-K train-state write
        (ISSUE 9 satellite): serialize, fsync a tmp file, rotate the
        previous generations (`path.1` = previous, `path.2` = the one
        before, up to `keep - 1` — state and meta move together), then
        `os.replace` into place. A kill at ANY point leaves either the
        old complete generation set or the new one; a torn write can
        only ever hit the tmp file, never a named generation."""
        keep = self.checkpoint_keep if keep is None else int(keep)
        data = serialization.to_bytes(jax.device_get(state))
        # the checkpointed rng key's layout depends on the PRNG impl
        # (threefry uint32[2] vs rbg uint32[4], config.use_fast_prng);
        # stamp the impl so a resume under the wrong `fast_prng` setting
        # fails with an error naming the flag instead of an opaque flax
        # shape mismatch. sha256 is the torn-write detector: a load
        # whose bytes don't match falls back to the previous generation.
        meta = {
            "prng_impl": str(jax.config.jax_default_prng_impl),
            "sha256": hashlib.sha256(data).hexdigest(),
            "iteration": int(state.iteration),
        }

        def fsync_write(target: str, payload: bytes | str,
                        mode: str) -> None:
            tmp = target + ".tmp"
            with open(tmp, mode) as fp:
                fp.write(payload)
                fp.flush()
                os.fsync(fp.fileno())
            os.replace(tmp, target)

        def intact(gen: str) -> bool:
            """Digest check of one on-disk generation; generations
            without a digest (legacy) pass."""
            meta_p = gen + ".meta.json"
            if not osp.exists(meta_p):
                return True
            try:
                with open(meta_p) as fp:
                    want = json.load(fp).get("sha256")
                if want is None:
                    return True
                with open(gen, "rb") as fp:
                    return hashlib.sha256(
                        fp.read()
                    ).hexdigest() == want
            except (OSError, ValueError):
                return False

        # rotate existing generations oldest-first (gen g -> g+1) —
        # but NEVER promote a torn generation over an intact one: after
        # a crash-recovery resume, `path` may be the very corrupt file
        # the loader fell back past, and rotating it onto `path.1`
        # would destroy the only good copy right before the (killable)
        # write below
        for g in range(keep - 1, 0, -1):
            src = path if g == 1 else f"{path}.{g - 1}"
            if not osp.exists(src):
                continue
            if not intact(src):
                emit(
                    f"[checkpoint] discarding torn generation {src} "
                    "instead of rotating it over an intact one"
                )
                os.remove(src)
                if osp.exists(src + ".meta.json"):
                    os.remove(src + ".meta.json")
                continue
            os.replace(src, f"{path}.{g}")
            if osp.exists(src + ".meta.json"):
                os.replace(
                    src + ".meta.json", f"{path}.{g}.meta.json"
                )
        fsync_write(path, data, "wb")
        fsync_write(path + ".meta.json", json.dumps(meta), "w")

    def load_train_state(self, path: str) -> TrainState:
        """Verified load with corrupt-file fallback (ISSUE 9): check
        the meta digest, deserialize, and on a torn/corrupt generation
        fall back to the previous one (`path.1`, `path.2`, ...),
        emitting + runlogging what was skipped. A PRNG-impl mismatch
        raises immediately — that is a config error on THIS process,
        not file corruption, and every generation shares it."""
        current = str(jax.config.jax_default_prng_impl)
        template = self.init_state()
        candidates = [path] + [
            f"{path}.{g}" for g in range(1, max(self.checkpoint_keep, 2))
        ]
        errors: list[str] = []
        for cand in candidates:
            if not osp.exists(cand):
                continue
            meta_path = cand + ".meta.json"
            digest = None
            if osp.exists(meta_path):
                with open(meta_path) as fp:
                    meta = json.load(fp)
                saved = meta.get("prng_impl", current)
                if saved != current:
                    raise ValueError(
                        f"train state {cand} was saved under PRNG impl "
                        f"{saved!r} but this process uses {current!r} — "
                        f"set `fast_prng: {saved == 'rbg'}` in the "
                        "trainer config (config.use_fast_prng switches "
                        "the impl) before resuming"
                    )
                digest = meta.get("sha256")
            with open(cand, "rb") as fp:
                data = fp.read()
            if digest is not None and (
                hashlib.sha256(data).hexdigest() != digest
            ):
                errors.append(f"{cand}: sha256 mismatch (torn write?)")
                continue
            try:
                restored = serialization.from_bytes(template, data)
            except (ValueError, KeyError) as e:
                errors.append(f"{cand}: {e}")
                continue
            if errors:
                emit(
                    f"[checkpoint] fell back to {cand} — skipped: "
                    + "; ".join(errors)
                )
                if self._runlog is not None:
                    self._runlog.write(
                        "recovery", action="checkpoint_fallback",
                        loaded=cand, skipped=errors,
                    )
            return restored
        raise ValueError(
            f"could not restore {path}: no intact generation among "
            f"{candidates} ({'; '.join(errors) or 'none found'}) — if "
            "the error is a shape mismatch on `rng`, the state was "
            "saved under a different PRNG impl (trainer config "
            "`fast_prng`)"
        )

    def _write_stats(self, i: int, stats: dict[str, float]) -> None:
        """Per-iteration scalars: runlog JSONL (default sink) + the
        TensorBoard mirror when enabled — identical keys/values."""
        if self._runlog is not None:
            self._runlog.scalars(i, stats)
        if self._tb is None:
            return
        for k, v in stats.items():
            self._tb.add_scalar(k, v, i)


def make_trainer(cfg: CfgType) -> Trainer:
    """String-keyed factory (reference trainers/__init__.py:7-13); the
    optional top-level `obs:` YAML section configures the observability
    block (runlog / telemetry / trace capture) and the optional
    `parallel:` section (`dp: auto|N`) shards rollout lanes over a
    device mesh — params replicated, `EnvState`/`Rollout`/`Telemetry`
    batch-sharded, the PPO update's reductions lowered to XLA
    collectives (parallel.py; config/decima_tpch_multichip.yaml is the
    worked example)."""
    from ..parallel import mesh_from_config
    from .ppo import PPO
    from .vpg import VPG

    registry = {"PPO": PPO, "VPG": VPG}
    name = cfg["trainer"]["trainer_cls"]
    if name not in registry:
        raise ValueError(f"'{name}' is not a valid trainer.")
    with span("setup/mesh") as first:
        mesh = mesh_from_config(cfg.get("parallel"))
    trainer = registry[name](
        cfg["agent"], cfg["env"], cfg["trainer"], mesh=mesh,
        obs_cfg=cfg.get("obs"),
        health_cfg=cfg.get("health"),
        chaos_cfg=cfg.get("chaos"),
    )
    trainer._spans_from = first.ordinal
    return trainer
