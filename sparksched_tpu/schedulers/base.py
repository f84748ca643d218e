"""Scheduler interfaces (reference schedulers/scheduler.py:10-55).

Two calling conventions coexist:

- `schedule(obs) -> (action, info)`: host-side, one decision at a time —
  the reference's contract, kept for drop-in compatibility and debugging.
- `policy(rng, obs, ...) -> (stage_idx, num_exec, info)`: pure jittable
  function over the padded `Observation`, the TPU-native path used inside
  vmapped/scanned rollouts. `stage_idx` is a flat padded node index
  (job * max_stages + stage, or -1 for "no selection").
"""

from __future__ import annotations

import abc
from typing import Any

import jax


def keys_by_lane(rng: jax.Array, lanes: int) -> jax.Array:
    """One key a lane: `rng` split `lanes` ways where it is a single
    key, `rng` itself where it already leads with the lane axis (the
    sweep loop splits a row's key over all its lanes and hands a block
    of lanes its own slice, so a lane's draw does not depend on which
    lanes are evaluated beside it)."""
    typed = jax.dtypes.issubdtype(rng.dtype, jax.dtypes.prng_key)
    if rng.ndim == (0 if typed else 1):
        return jax.random.split(rng, lanes)
    return rng


class Scheduler(abc.ABC):
    """Interface for all schedulers (reference scheduler.py:10-18)."""

    name: str

    @abc.abstractmethod
    def schedule(self, obs: Any) -> tuple[dict[str, Any], dict[str, Any]]:
        """One decision from a single padded Observation. Returns
        ({"stage_idx": flat padded index | -1, "num_exec": int}, info)."""

    @abc.abstractmethod
    def policy(self, rng: jax.Array, obs: Any):
        """Pure jittable single-decision function; vmap/scan-safe."""

    def batch_policy(self, rng: jax.Array, obs: Any):
        """`policy` over a [B]-stacked Observation, a lane at a time
        under keys split from `rng` (or under `rng` itself where it is
        one key a lane: `keys_by_lane`): `(stage_idx[B], num_exec[B],
        aux)`, the form the sweep loop (`sparksched_tpu/sweep.py`) and
        the trainer's collectors call once a decision row. The
        heuristics' batch form is this `vmap` of what they have."""
        lanes = jax.tree_util.tree_leaves(obs)[0].shape[0]
        return jax.vmap(self.policy)(keys_by_lane(rng, lanes), obs)


class TrainableScheduler(Scheduler):
    """Interface for trainable schedulers (reference scheduler.py:21-55).

    The torch `nn.Module` + owned-optimizer design becomes functional:
    parameters are an explicit pytree, `evaluate_actions` is a pure function
    of (params, rollout arrays), and the optimizer lives with the trainer
    (optax), so `update_parameters` (reference :37-54) has no analogue here —
    gradient clipping and the update are part of the trainer's jitted step.
    """

    params: Any  # flax parameter pytree

    @abc.abstractmethod
    def evaluate_actions(self, params: Any, obsns: Any, actions: Any):
        """Log-probs and entropies of `actions` under `params`, batched over
        the rollout. Pure; differentiable wrt `params`."""

    @abc.abstractmethod
    def batch_policy(self, rng: jax.Array, obs: Any, params: Any = None):
        """`policy` over a [B]-stacked Observation from ONE evaluation:
        `(stage_idx[B], num_exec[B], aux)`. The trainer's collectors
        call it once per decision row (trainers/rollout.py)."""
