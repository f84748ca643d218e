"""Proximal Policy Optimization (reference trainers/ppo.py:39-138).

The epochs x shuffled-minibatches loop, clipped surrogate loss, per-batch
advantage standardization, entropy bonus and approx-KL early stop are all
inside one jitted `lax.scan` over minibatches, so the whole update is a
single XLA program. Three deliberate deviations from the reference, the
first two forced by static shapes, the third by SPMD sharding:

- minibatches are fixed-size slices of a padded permutation, so a batch's
  *effective* size varies slightly (masked means) instead of
  `len(dataset)//num_batches + 1`;
- the KL early stop zeroes out all subsequent updates in the scan instead
  of Python `break` — identical parameter trajectory, same wasted-compute
  tradeoff the reference makes when it keeps collecting after stopping;
- minibatches are drawn as per-lane permutations of the TIME axis (every
  minibatch contains all B lanes x a random T-slice) instead of one
  global permutation of the flattened B*T dataset. A global shuffle
  forces XLA to all-gather the whole rollout onto every device of a dp
  mesh (measured: per-device update FLOPs flat in dp); keeping the lane
  axis intact lets the minibatch gather, the GNN recompute and the
  gradient all shard 1/dp, with one psum per grad step — the same
  reduction structure as the loss means. Identical on a single device
  modulo minibatch composition (every step still appears exactly once
  per epoch; advantage standardization stays per-minibatch and global
  across lanes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from ..env.health import grad_health
from ..obs.tracing import annotate
from ..schedulers.decima import DecimaAction
from .rollout import Rollout, stored_to_observation
from .trainer import CfgType, Trainer, TrainState

EPS = 1e-8
# Samples per gradient chunk of the update (see `_update`). The flagship
# network keeps ~27 MB of activations alive per sample through the
# backward pass (exec head over [200 jobs, 50 executors, 64]), so a
# 16 GB chip holds a few hundred samples and the committed config's
# minibatch is 16 lanes x 960 steps. 96 is also the size at which the
# update was seen to reproduce the collector's log-probs on the v5e
# (PERF.md, PR 25).
CHUNK_SAMPLES = 96


def _masked_mean(x, w, n):
    return (x * w).sum() / n


class PPO(Trainer):
    def __init__(self, agent_cfg: CfgType, env_cfg: CfgType,
                 train_cfg: CfgType, mesh=None,
                 obs_cfg: CfgType | None = None,
                 health_cfg: CfgType | None = None,
                 chaos_cfg: CfgType | None = None) -> None:
        super().__init__(agent_cfg, env_cfg, train_cfg, mesh=mesh,
                         obs_cfg=obs_cfg, health_cfg=health_cfg,
                         chaos_cfg=chaos_cfg)
        self.entropy_coeff = train_cfg.get("entropy_coeff", 0.0)
        self.clip_range = train_cfg.get("clip_range", 0.2)
        self.target_kl = train_cfg.get("target_kl", 0.01)
        self.num_epochs = train_cfg.get("num_epochs", 10)
        self.num_batches = train_cfg.get("num_batches", 3)

    def _features(self, so):
        return jax.vmap(
            lambda s: self.scheduler.features(
                stored_to_observation(self.bank, s)
            )
        )(so)

    def _update(self, state: TrainState, ro: Rollout):
        returns, baselines, buf, avg_num_jobs = (
            self._returns_and_baselines(state, ro)
        )
        B, T = ro.reward.shape
        ent_coeff = self._entropy_coeff_at(
            self.entropy_coeff, state.iteration
        )
        actions = DecimaAction(
            stage_idx=ro.stage_idx,
            job_idx=ro.job_idx,
            num_exec=ro.num_exec_k,
        )  # [B,T]
        advantages = returns - baselines  # [B,T]
        old_lgprobs = ro.lgprob
        valid = ro.valid & (actions.stage_idx >= 0)

        # shuffled fixed-size minibatches (reference ppo.py:64-71),
        # shard-aligned: per-lane permutations of the time axis (see
        # module docstring). mb_idx[k] is i32[B, mbs] — lane b of
        # minibatch k takes steps mb_idx[k, b, :].
        nb = self.num_batches
        mbs = -(-T // nb)
        rng = jax.random.fold_in(state.rng, 13)
        # per-(epoch, lane) permutation keys via fold_in over a lane
        # iota — elementwise in the lane index, so each dp shard derives
        # its local lanes' keys from the replicated rng. The previous
        # vmap(split) derivation materialized one global [E*B] key strip
        # whose distribution onto lane shards lowered to
        # collective-permute chains (the resharding family the census
        # test forbids); fold_in keeps the update's collective set to
        # the reduction families alone.
        ep_keys = jax.random.split(rng, self.num_epochs)  # [E, 2]
        lane_keys = jax.vmap(
            lambda ek: jax.vmap(
                lambda b: jax.random.fold_in(ek, b)
            )(jnp.arange(B))
        )(ep_keys)  # [E, B]
        perms = jax.vmap(jax.vmap(lambda k: jax.random.permutation(k, T)))(
            lane_keys
        )  # [E, B, T]
        pad = nb * mbs - T
        perms = jnp.concatenate(
            [perms, jnp.zeros((self.num_epochs, B, pad), jnp.int32)],
            axis=-1,
        )
        # [E, B, nb, mbs] -> [E*nb, B, mbs]; ok masks by slot position
        # (identical across lanes and epochs: slots past T are padding)
        mb_idx = (
            perms.reshape(self.num_epochs, B, nb, mbs)
            .transpose(0, 2, 1, 3)
            .reshape(self.num_epochs * nb, B, mbs)
        )
        in_range = jnp.arange(nb * mbs) < T  # [nb*mbs]
        mb_ok = jnp.tile(
            in_range.reshape(nb, mbs), (self.num_epochs, 1)
        )

        def gather_t(a, idx):
            """a: [B, T, ...], idx: i32[B, m] -> [B, m, ...]."""
            return jax.vmap(lambda row, ii: row[ii])(a, idx)

        # A minibatch of more than CHUNK_SAMPLES samples is evaluated in
        # chunks of `cs` of its time slots (all B lanes each, so the
        # lane axis stays shard-aligned) and the chunks' gradients are
        # summed: the minibatch's loss is a masked sum over samples
        # divided by one count, so this is the same update up to the
        # order of the sums. What needs the whole minibatch and not the
        # network (the count, the advantage standardization) is done
        # once, outside the chunks.
        cs = max(
            d for d in range(1, mbs + 1)
            if mbs % d == 0 and (d == 1 or B * d <= CHUNK_SAMPLES)
        )
        nc = mbs // cs

        def chunk_loss(params, idx, w, adv, n):
            """One chunk's share of its minibatch's loss. idx, w, adv:
            [B, cs]; n: the minibatch's valid-sample count."""
            so = jax.tree_util.tree_map(
                lambda a: gather_t(a, idx).reshape(
                    B * idx.shape[1], *a.shape[2:]
                ),
                ro.obs,
            )
            feats = self._features(so)
            acts = jax.tree_util.tree_map(
                lambda a: gather_t(a, idx).reshape(-1), actions
            )
            lgprobs, entropies = self.scheduler.evaluate_actions(
                params, feats, acts
            )
            w, adv = w.reshape(-1), adv.reshape(-1)
            log_ratio = lgprobs - gather_t(old_lgprobs, idx).reshape(-1)
            ratio = jnp.exp(log_ratio)
            pl1 = adv * ratio
            pl2 = adv * jnp.clip(
                ratio, 1 - self.clip_range, 1 + self.clip_range
            )
            policy_loss = -_masked_mean(jnp.minimum(pl1, pl2), w, n)
            entropy_loss = -_masked_mean(entropies, w, n)
            loss = policy_loss + ent_coeff * entropy_loss
            kl = _masked_mean((ratio - 1) - log_ratio, w, n)
            return loss, {
                "policy_loss": policy_loss,
                "entropy_loss": entropy_loss,
                "kl": jax.lax.stop_gradient(kl),
            }

        chunk_grad = jax.value_and_grad(chunk_loss, has_aux=True)

        def grad_fn(params, idx, ok):
            """((loss, aux), grads) of one minibatch. idx: [B, mbs]."""
            w = (gather_t(valid, idx) & ok[None, :]).astype(jnp.float32)
            n = jnp.maximum(w.sum(), 1.0)
            adv = gather_t(advantages, idx)
            mean = _masked_mean(adv, w, n)
            var = ((adv - mean) ** 2 * w).sum() / jnp.maximum(n - 1, 1.0)
            adv = (adv - mean) / (jnp.sqrt(var) + EPS)
            if nc == 1:
                return chunk_grad(params, idx, w, adv, n)
            chunks = jax.tree_util.tree_map(
                lambda a: a.reshape(B, nc, cs).swapaxes(0, 1),
                (idx, w, adv),
            )  # [nc, B, cs]

            def add_chunk(total, chunk):
                return jax.tree_util.tree_map(
                    jnp.add, total, chunk_grad(params, *chunk, n)
                ), None

            zero = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype),
                jax.eval_shape(
                    chunk_grad, params, idx[:, :cs], w[:, :cs],
                    adv[:, :cs], n,
                ),
            )
            return jax.lax.scan(add_chunk, zero, chunks)[0]

        # in-JIT health sentinel (ISSUE 9, opt-in via the `health:`
        # block): a minibatch whose loss or gradients go non-finite is
        # SKIPPED on-device — exactly the KL-stop select pattern, so a
        # single NaN gradient can never reach the optimizer — and the
        # violation bits accumulate into a `health_mask` stat the
        # trainer's recovery loop reads. With health off the traced
        # program is bit-identical to the pre-health update (the
        # ppo_update budget pin).
        health = bool(getattr(self, "health_enabled", False))

        def body(carry, x):
            params, opt_state, stop, sums = carry
            idx, ok = x
            (loss_val, aux), grads = grad_fn(params, idx, ok)
            kl_bad = (
                (aux["kl"] > 1.5 * self.target_kl)
                if self.target_kl is not None
                else jnp.bool_(False)
            )
            do_update = ~stop & ~kl_bad
            if health:
                mb_mask = grad_health(loss=loss_val, grads=grads)
                do_update = do_update & (mb_mask == 0)
            updates, new_opt = self.tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            sel = lambda a, b: jnp.where(do_update, a, b)  # noqa: E731
            params = jax.tree_util.tree_map(sel, new_params, params)
            opt_state = jax.tree_util.tree_map(sel, new_opt, opt_state)
            computed = (~stop).astype(jnp.float32)
            new_sums = {
                "policy_loss": sums["policy_loss"]
                + computed * aux["policy_loss"],
                "entropy_loss": sums["entropy_loss"]
                + computed * aux["entropy_loss"],
                "kl": sums["kl"] + computed * aux["kl"],
                "count": sums["count"] + computed,
                # the first minibatch is evaluated at the collector's
                # own parameters: its KL is how far the update's
                # recomputed log-probs sit from the recorded ones
                "kl_first": jnp.where(
                    sums["count"] == 0, aux["kl"], sums["kl_first"]
                ),
                "applied": sums["applied"]
                + do_update.astype(jnp.float32),
            }
            if health:
                new_sums["health"] = sums["health"] | mb_mask
            return (params, opt_state, stop | kl_bad, new_sums), None

        zero = jnp.float32(0.0)
        sums0 = {"policy_loss": zero, "entropy_loss": zero, "kl": zero,
                 "count": zero, "kl_first": zero, "applied": zero}
        if health:
            sums0["health"] = jnp.int32(0)
        with annotate("train/ppo_update"):
            (params, opt_state, _, sums), _ = jax.lax.scan(
                body,
                (state.params, state.opt_state, jnp.bool_(False), sums0),
                (mb_idx, mb_ok),
            )
        n = jnp.maximum(sums["count"], 1.0)
        stats = {
            "policy_loss": jnp.abs(sums["policy_loss"] / n),
            "entropy": jnp.abs(sums["entropy_loss"] / n),
            "approx_kl_div": jnp.abs(sums["kl"] / n),
            "approx_kl_first": jnp.abs(sums["kl_first"]),
            "minibatches_applied": sums["applied"],
            "avg_num_jobs_est": avg_num_jobs,
        }
        if health:
            # post-update params check: the skip gate should make this
            # unreachable, but a pre-existing non-finite parameter (a
            # corrupt resume that slipped the digest) must still trip
            stats["health_mask"] = sums["health"] | grad_health(
                params=params
            )
        return state.replace(
            params=params, opt_state=opt_state, buf=buf
        ), stats
