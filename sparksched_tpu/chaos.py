"""Deterministic fault injection (ISSUE 9 tentpole, part 3).

Every recovery path in the self-healing runtime must be exercisable in
CI, not just in production: a `chaos:` config block injects seeded,
reproducible faults at named iterations, and `scripts_chaos_drill.py`
drives the full matrix end-to-end. Injection sites are host-side
boundaries of the training loop (the collected rollout, the telemetry
summary, the inter-phase gap) so no traced program changes shape — the
faults *look* like what the sentinels exist to catch, without a second
compile of anything.

Fault classes (block keys; each an iteration list except `sigkill`):

- ``nan_grad: [i, ...]`` — poison one recorded reward with NaN. That
  lane's returns up to the poisoned step go NaN, every minibatch that
  holds one of those samples gets a NaN loss/grad, and the PPO in-JIT
  sentinel (trainers/ppo.py) must keep each such minibatch from the
  optimizer while the trainer rolls the whole update back and retries.
- ``bank_row: [i, ...]`` — poison a recorded observation's duration
  row with NaN (what a corrupted workload-bank row read produces
  downstream). Detected by the update sentinels via NaN features; the
  *state-level* detection of an actually-corrupt bank is exercised by
  `corrupt_bank` + a health-threaded collector in the drill.
- ``straggler: [i, ...]`` — inflate one lane's `loop_iters` telemetry
  counter so the straggler ratio blows past `health.straggler_ratio_max`.
  Detected and quarantined (a runlog `health` record), never retried.
- ``oom: [i, ...]`` — raise a simulated RESOURCE_EXHAUSTED between
  collect and update. The trainer's OOM catch must back off and retry.
- ``sigkill: [i, ...]`` — SIGKILL this process mid-iteration (after
  collect, before the update commits). The preemption-safety story:
  the atomic `health.checkpoint_every` train-state write from the
  previous iteration must resume the run bit-exactly.

All injections except sigkill fire only on `attempt == 0` — they model
*transient* faults, so a rollback+retry genuinely recovers. Indices
(which lane/step/row) derive from `seed` + the iteration, so a drill
re-run reproduces the exact same faults.
"""

from __future__ import annotations

import os
import signal
from typing import Any

import jax.numpy as jnp
import numpy as np

from .config import CHAOS_KEYS
from .obs.runlog import emit


def _iters(cfg: dict, key: str) -> frozenset:
    v = cfg.get(key) or ()
    if isinstance(v, int):
        v = (v,)
    return frozenset(int(x) for x in v)


class ChaosMonkey:
    """Seeded fault injector driven by a `chaos:` config block. All
    methods are cheap no-ops for iterations with nothing scheduled."""

    def __init__(self, cfg: dict[str, Any] | None) -> None:
        cfg = dict(cfg or {})
        unknown = set(cfg) - CHAOS_KEYS
        if unknown:
            raise ValueError(
                f"unknown chaos: config key(s) {sorted(unknown)} — "
                f"known keys: {sorted(CHAOS_KEYS)}"
            )
        self.seed = int(cfg.get("seed", 0))
        self.nan_grad = _iters(cfg, "nan_grad")
        self.bank_row = _iters(cfg, "bank_row")
        self.straggler = _iters(cfg, "straggler")
        self.oom = _iters(cfg, "oom")
        self.sigkill = _iters(cfg, "sigkill")
        self.straggler_factor = int(cfg.get("straggler_factor", 100))

    def _rng(self, iteration: int) -> np.random.Generator:
        return np.random.default_rng(
            self.seed * 1_000_003 + int(iteration)
        )

    def any_scheduled(self) -> bool:
        return bool(
            self.nan_grad | self.bank_row | self.straggler
            | self.oom | self.sigkill
        )

    # -- rollout poisoning (transient: attempt 0 only) --------------------

    def poison_rollout(self, ro, iteration: int, attempt: int):
        """Apply this iteration's rollout-level faults; returns
        `(rollout, [fault names injected])`."""
        injected: list[str] = []
        if attempt != 0:
            return ro, injected
        rng = self._rng(iteration)
        B, T = ro.reward.shape
        if iteration in self.nan_grad:
            b, t = int(rng.integers(B)), int(rng.integers(T))
            ro = ro.replace(
                reward=ro.reward.at[b, t].set(jnp.float32(jnp.nan))
            )
            injected.append("nan_grad")
        if iteration in self.bank_row:
            b, t = int(rng.integers(B)), int(rng.integers(T))
            j_cap, s_cap = ro.final_state.stage_remaining.shape[1:]
            j = int(rng.integers(j_cap))
            dur = ro.obs.duration  # [B, T, F], job j's stages in a row
            ro = ro.replace(obs=ro.obs.replace(
                duration=dur.at[b, t, j * s_cap:(j + 1) * s_cap].set(
                    jnp.asarray(jnp.nan, dur.dtype)
                )
            ))
            injected.append("bank_row")
        return ro, injected

    # -- telemetry inflation ----------------------------------------------

    def inflate_straggler(self, telem, iteration: int, attempt: int):
        """Multiply one lane's `loop_iters` so the summary's straggler
        ratio trips the configured threshold; returns
        `(telemetry, [fault names])`."""
        if telem is None or attempt != 0 or iteration not in self.straggler:
            return telem, []
        lanes = telem.loop_iters.shape[0]
        b = int(self._rng(iteration).integers(lanes))
        li = telem.loop_iters
        return telem.replace(
            loop_iters=li.at[b].set(
                (li[b] + 1) * self.straggler_factor
            )
        ), ["straggler"]

    # -- process-level faults ----------------------------------------------

    def maybe_raise_oom(self, iteration: int, attempt: int) -> None:
        if attempt == 0 and iteration in self.oom:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: simulated chaos OOM at iteration "
                f"{iteration} (chaos: oom)"
            )

    def maybe_sigkill(self, iteration: int) -> None:
        """SIGKILL the process mid-iteration — no teardown hook runs,
        exactly like a preempted chip window. Fires on every attempt
        (a kill is not retryable in-process by construction)."""
        if iteration in self.sigkill:
            emit(
                f"[chaos] SIGKILL at iteration {iteration} "
                "(simulated preemption)"
            )
            os.kill(os.getpid(), signal.SIGKILL)


def corrupt_bank(bank, seed: int = 0):
    """A workload bank with one seeded duration STAGE row (all
    templates/waves/levels of one stage index) overwritten with NaN —
    the state-level fault class (`bank_row`) for drills that drive a
    health-threaded collector directly: env dynamics sample a NaN task
    duration, the executor's finish time (and eventually the wall
    clock) goes NaN, and `env/health.py:state_health` must raise
    H_EXEC_CONSERVE / H_NONFINITE_TIME. Corrupting across templates
    (not one seeded template) guarantees a short drill episode actually
    reads a poisoned row."""
    if not np.issubdtype(np.asarray(bank.dur).dtype, np.floating):
        raise ValueError(
            "corrupt_bank needs a float dur table — quantized "
            "(int-coded) banks have no NaN representation to corrupt "
            "with; drill the default f32 bank instead"
        )
    del seed  # kept for API symmetry with the other injectors
    dur = np.asarray(bank.dur, dtype=np.float32).copy()
    # stage 0 exists in every template, so a short drill episode is
    # guaranteed to read a poisoned bucket
    dur[:, 0] = np.nan
    return bank.replace(dur=jnp.asarray(dur))
