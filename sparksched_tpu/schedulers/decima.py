"""Decima GNN policy, TPU-native (flax + padded graphs).

Semantics mirror the reference implementation
(schedulers/decima/scheduler.py:16-385, env_wrapper.py:36-162,
decima/utils.py) — same 5 normalized node features, the same DAGNN-style
*asynchronous level-wise* message passing leaf→root, the same dag/global
summaries and two autoregressive policy heads — but the ragged PyG graphs
become fixed-shape [max_jobs, max_stages] arrays with masks:

- the per-level masked sparse matmul (reference scheduler.py:219-232)
  becomes a dense per-job sum over the child axis (`[S,S] @ [S,D]`,
  written as a select and a reduce) inside a `lax.scan` over topological
  generations — fixed-shape array work instead of scatter/gather kernels;
- the edge-mask batches the reference caches per observation
  (env_wrapper.py:145-162) are replaced by the env-maintained per-node
  `node_level` array, so no host-side graph analysis happens at all;
- `collate_obsns` (decima/utils.py:118-231) disappears: training batches
  are plain `jnp.stack`s of identically-shaped observations.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from flax import struct

from ..env.observe import Observation
from ..obs.tracing import annotate
from .base import TrainableScheduler, keys_by_lane

NUM_NODE_FEATURES = 5  # reference env_wrapper.py:9
NUM_DAG_FEATURES = 3  # reference scheduler.py:34
# numpy scalar, not jnp: a jax array here would initialize the backend
# (and claim the TPU) on `import sparksched_tpu.schedulers` — see the
# matching note in env/state.py
NEG_INF = np.float32(-1e30)

_i32 = jnp.int32


# --------------------------------------------------------------------------
# features (reference DecimaObsWrapper, env_wrapper.py:69-143)
# --------------------------------------------------------------------------


class DecimaFeatures(struct.PyTreeNode):
    """Padded model inputs derived from a raw Observation."""

    x: jnp.ndarray  # f32[J,S,5] normalized node features
    node_mask: jnp.ndarray  # bool[J,S]
    job_mask: jnp.ndarray  # bool[J]
    stage_mask: jnp.ndarray  # bool[J,S]; schedulable stages
    exec_mask: jnp.ndarray  # bool[J,N]; allowed parallelism limits per job
    adj: jnp.ndarray  # bool[J,S,S] active-subgraph adjacency
    node_level: jnp.ndarray  # i32[J,S] topological generation


def build_features(
    obs: Observation,
    num_executors: int,
    num_tasks_scale: float = 200.0,
    work_scale: float = 1e5,
) -> DecimaFeatures:
    """The 5 normalized node features + masks (env_wrapper.py:110-143):
    commit-cap/N, ±1 source-job flag, exec-supply/N, tasks/200, work/1e5."""
    n = num_executors
    j_cap = obs.job_mask.shape[0]
    j_idx = jnp.arange(j_cap, dtype=_i32)

    supplies = obs.exec_supplies
    committable = obs.num_committable
    gap = jnp.maximum(n - supplies, 0)
    caps = jnp.minimum(gap, committable)
    is_src = (obs.source_job >= 0) & (j_idx == obs.source_job)
    caps = jnp.where(is_src, committable, caps)

    # f32 accumulation at the use site: under the low-precision
    # observation layout (params.obs_dtype = bf16) the feature bank
    # arrives narrow; the normalization arithmetic below must not run
    # in bf16, so each read upcasts first (lossless for bf16 inputs)
    remaining = obs.nodes[..., 0].astype(jnp.float32)
    duration = obs.nodes[..., 1].astype(jnp.float32)
    x = jnp.stack(
        [
            jnp.broadcast_to((caps / n)[:, None], remaining.shape),
            jnp.broadcast_to(
                jnp.where(is_src, 1.0, -1.0)[:, None], remaining.shape
            ),
            jnp.broadcast_to((supplies / n)[:, None], remaining.shape),
            remaining / num_tasks_scale,
            remaining * duration / work_scale,
        ],
        axis=-1,
    ).astype(jnp.float32)
    x = jnp.where(obs.node_mask[..., None], x, 0.0)

    exec_mask = (
        jnp.arange(n, dtype=_i32)[None, :] < caps[:, None]
    ) & obs.job_mask[
        :, None
    ]
    adj = obs.adj & obs.node_mask[:, :, None] & obs.node_mask[:, None, :]
    return DecimaFeatures(
        x=x,
        node_mask=obs.node_mask,
        job_mask=obs.job_mask,
        stage_mask=obs.schedulable,
        exec_mask=exec_mask,
        adj=adj,
        node_level=obs.node_level,
    )


# --------------------------------------------------------------------------
# active-job compaction (round-8 fast path)
#
# The reference only ever embeds the arrived, incomplete jobs (its PyG
# batch is built from live DAGs; scheduler.py:219-232), while the dense
# padded port pays the full [J,S,S]@[S,D] level sum over every padded
# job slot. These helpers gather the <=K active jobs into a width-K view,
# run the (shape-polymorphic) net at width K, and scatter the per-job
# scores back to the padded [J] layout before masked softmax — cutting
# GNN FLOPs and memory traffic by ~J/K at flagship shapes (J=200 cap,
# typically a few dozen live jobs). All per-job computations are
# independent except the global summary, which sums over job_mask only,
# so compact and full-width scores agree on every active job.
# --------------------------------------------------------------------------


def compact_features(
    f: DecimaFeatures, k: int
) -> tuple[DecimaFeatures, jnp.ndarray]:
    """Gather the first `k` active jobs of an unbatched [J,...] feature
    set into a width-k view. Returns (compact features, ids) where
    `ids[i]` is the padded job id behind compact row i (== j_cap for
    empty rows). Only meaningful when the number of active jobs is <= k;
    callers guard with the overflow cond in `DecimaScheduler.score`."""
    j_cap = f.job_mask.shape[0]
    # active ids are the smallest entries of this ascending sort, so
    # rows 0..num_active-1 are exactly the active jobs in id order
    ids = jnp.sort(
        jnp.where(f.job_mask, jnp.arange(j_cap, dtype=_i32), j_cap)
    )[:k]
    valid = ids < j_cap
    idx = jnp.minimum(ids, j_cap - 1)  # clamp gathers for empty rows
    vm = valid[:, None]
    node_mask = f.node_mask[idx] & vm
    return DecimaFeatures(
        x=jnp.where(node_mask[..., None], f.x[idx], 0.0),
        node_mask=node_mask,
        job_mask=valid,
        stage_mask=f.stage_mask[idx] & vm,
        exec_mask=f.exec_mask[idx] & vm,
        adj=f.adj[idx] & vm[:, :, None],
        node_level=f.node_level[idx],
    ), ids


def scatter_job_scores(
    stage_k: jnp.ndarray, exec_k: jnp.ndarray, ids: jnp.ndarray,
    j_cap: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter compact [k,S]/[k,N] scores back to the padded [J,S]/[J,N]
    layout (rows of inactive jobs are zero — the masked softmax never
    reads them). Empty compact rows carry ids == j_cap and drop."""
    stage = jnp.zeros(
        (j_cap,) + stage_k.shape[1:], stage_k.dtype
    ).at[ids].set(stage_k, mode="drop")
    execs = jnp.zeros(
        (j_cap,) + exec_k.shape[1:], exec_k.dtype
    ).at[ids].set(exec_k, mode="drop")
    return stage, execs


# --------------------------------------------------------------------------
# model (reference scheduler.py:142-385)
# --------------------------------------------------------------------------


def leaky_relu(slope: float) -> Callable:
    """LeakyReLU as one maximum, `max(x, slope * x)`: the values of
    `where(x >= 0, x, slope * x)` for a slope in [0, 1]. The TPU
    compiler makes the compare-and-select form's predicate an array of
    its own beside every Dense layer of the GNN (a fifth of a policy
    evaluation on a v5e, PERF.md PR 33). The derivative is stated, and
    is the select form's (1 at x >= 0, else the slope): a maximum's own
    rule splits a tie between its arguments, which every zero-padded
    node slot is, and keeps both of them for the backward pass."""
    if not 0.0 <= slope <= 1.0:
        raise ValueError(
            f"LeakyReLU negative_slope {slope!r} is not in [0, 1]"
        )

    @jax.custom_jvp
    def act(x):
        return jnp.maximum(x, slope * x)

    @act.defjvp
    def _(primals, tangents):
        (x,), (t,) = primals, tangents
        return act(x), jnp.where(x >= 0, t, slope * t)

    return act


def make_act(name: str, kwargs: Any = None) -> Callable:
    """Activation factory (reference utils.make_mlp's act_cls lookup).
    `kwargs` may be a dict or the hashable tuple-of-pairs form flax module
    fields require."""
    if isinstance(kwargs, tuple):
        kwargs = dict(kwargs)
    kwargs = kwargs or {}
    name = name.lower()
    if name in ("leakyrelu", "leaky_relu"):
        return leaky_relu(kwargs.get("negative_slope", 0.01))
    if name == "tanh":
        return jnp.tanh
    if name == "relu":
        return jax.nn.relu
    raise ValueError(f"unknown activation {name!r}")


class MLP(nn.Module):
    """Dense stack matching reference utils.make_mlp:45-64 (all biases
    start at zero per scheduler.py:66-69 `_reset_biases`).

    `dtype` is the *compute* dtype (params stay f32): bfloat16 keeps the
    matmuls on the MXU's native precision — the TPU analog of the
    reference's f32 torch path."""

    hid_dims: tuple[int, ...]
    out_dim: int
    act: Callable
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        for i, d in enumerate(self.hid_dims):
            x = self.act(
                nn.Dense(d, name=f"dense_{i}", dtype=self.dtype)(x)
            )
        return nn.Dense(
            self.out_dim, name=f"dense_{len(self.hid_dims)}",
            dtype=self.dtype,
        )(x)


class DecimaNet(nn.Module):
    """Encoder + both policy heads in one module.

    Returns masked stage scores [J,S] and exec scores for every job [J,N];
    the reference computes exec scores only for the selected job
    (scheduler.py:92), but computing all rows is one batched matmul here and
    removes the data-dependent gather from the autoregressive chain.
    """

    num_executors: int
    embed_dim: int = 16
    gnn_hid: tuple[int, ...] = (32, 16)
    policy_hid: tuple[int, ...] = (64, 64)
    gnn_act: str = "LeakyReLU"
    gnn_act_kwargs: Any = None
    policy_act: str = "Tanh"
    policy_act_kwargs: Any = None
    # compute dtype for all Dense layers + message aggregation; params
    # stay f32. "bfloat16" puts the matmuls on the MXU's native input
    # precision; scores are returned as f32 either way.
    compute_dtype: str | None = None
    # upper bound on topological depth: the number of generations a DAG
    # can have (0 = all s_cap). Levels past the deepest active node are
    # exact no-ops (the update mask is all-false), so bounding the scan
    # by the workload bank's true max DAG depth (5 for the synthetic
    # TPC-H bank vs s_cap = 20) is bit-identical and cuts the
    # GNN's dominant cost proportionally. The scan runs one step fewer
    # than there are generations: the deepest holds no parent.
    # The reference gets this for free from its per-observation edge
    # mask list (scheduler.py:219-232 iterates only realized levels).
    num_levels: int = 0

    def setup(self) -> None:
        # setup() (not @nn.compact) so the level loop can be an nn.scan
        # over a method; attribute names keep the param tree identical to
        # the round-1/2 checkpoints ("mlp_prep", "mlp_msg", ...).
        g_act = make_act(self.gnn_act, self.gnn_act_kwargs)
        self._p_act = make_act(self.policy_act, self.policy_act_kwargs)
        cdt = (
            jnp.dtype(self.compute_dtype) if self.compute_dtype else None
        )
        self._cdt = cdt
        d = self.embed_dim
        self.mlp_prep = MLP(self.gnn_hid, d, g_act, dtype=cdt)
        self.mlp_msg = MLP(self.gnn_hid, d, g_act, dtype=cdt)
        self.mlp_update = MLP(self.gnn_hid, d, g_act, dtype=cdt)
        self.mlp_dag = MLP(self.gnn_hid, d, g_act, dtype=cdt)
        self.mlp_glob = MLP(self.gnn_hid, d, g_act, dtype=cdt)
        self.mlp_stage = MLP(self.policy_hid, 1, self._p_act, dtype=cdt)
        self.mlp_exec = MLP(self.policy_hid, 1, self._p_act, dtype=cdt)

    def __call__(self, f: DecimaFeatures):
        d = self.embed_dim
        cdt = self._cdt

        # --- NodeEncoder (reference scheduler.py:173-241) ---
        # h[leaf] = update(prep(x)); h[p] = prep(x)[p] + update(sum_children
        # msg(h[c])), computed one topological generation at a time from the
        # deepest level up (reverse_flow=True, leaf-to-root).
        x = f.x.astype(cdt) if cdt is not None else f.x
        s_cap = x.shape[-2]
        h_init = self.mlp_prep(x)
        has_child = f.adj.any(axis=-1)
        h0 = jnp.where(has_child[..., None], 0.0, self.mlp_update(h_init))
        eye = jnp.eye(d, dtype=h_init.dtype)

        # one `nn.scan` step per topological generation, deepest first.
        # Weights are broadcast across levels (the reference reuses the
        # same msg/update MLPs each level, scheduler.py:219-232); scanning
        # instead of statically unrolling keeps the compiled program one
        # body regardless of s_cap — at the flagship 200-job scale the
        # unrolled chain dominated XLA compile time.
        #
        # The children's messages are summed by a select and a reduce
        # over the child axis, not by the per-job `[S,S] @ [S,D]`
        # product the sum is: the TPU compiler lays the Dense chain's
        # arrays with the batch's lane axis minor-most, and a batched
        # product of 25,600 tiny matrices wants the stage axis there, so
        # it copied mlp_msg's output into that layout and the sums back
        # out of it in every step (three quarters of a step on a v5e,
        # PERF.md PR 33). The sum keeps a product's operand precision
        # all the same: `msg @ eye` reads the messages as a product at
        # the default precision reads an operand (rounded to bfloat16
        # on a TPU, untouched on a CPU), so the scores differ from the
        # product's by the order of the sums at most.
        def level_step(mdl, h_node, lvl):
            msg = mdl.mlp_msg(h_node) @ eye
            agg = jnp.where(
                f.adj[..., None], msg[..., None, :, :], 0
            ).sum(axis=-2, dtype=jnp.float32)
            upd = (f.node_level == lvl) & has_child
            h_node = jnp.where(
                upd[..., None], h_init + mdl.mlp_update(agg), h_node
            )
            return h_node, None

        # a node of the deepest generation has no child (`node_level` is
        # the longest path from a source), so the scan starts one
        # generation above it: nl - 1 steps, none for a bank of sources
        nl = min(self.num_levels, s_cap) if self.num_levels else s_cap
        levels = jnp.arange(nl - 2, -1, -1, dtype=_i32)
        with annotate("decima/gnn/levels"):
            h_node, _ = nn.scan(
                level_step,
                variable_broadcast="params",
                split_rngs={"params": False},
            )(self, h0, levels)
        # reference fast path for an observation with no edges
        # (scheduler.py:205-207,236-241): plain prep(x), no update().
        # Reduced per ITEM (last 3 axes), not over leading batch dims:
        # a vmapped per-lane policy traces the unbatched reduction, so
        # the genuinely-batched callers (batch_policy / the single-eval
        # collectors) must do the same per-lane or the two paths'
        # scores diverge on edgeless observations sharing a batch with
        # edged ones.
        edgeless = ~f.adj.any(axis=(-3, -2, -1))
        h_node = jnp.where(
            edgeless[..., None, None, None], h_init, h_node
        )
        h_node = jnp.where(f.node_mask[..., None], h_node, 0.0)

        # --- DagEncoder (reference scheduler.py:244-257) ---
        z = self.mlp_dag(jnp.concatenate([x, h_node], axis=-1))
        h_dag = jnp.where(f.node_mask[..., None], z, 0.0).sum(axis=-2)

        # --- GlobalEncoder (reference scheduler.py:260-276) ---
        zg = self.mlp_glob(h_dag)
        h_glob = jnp.where(f.job_mask[..., None], zg, 0.0).sum(axis=-2)

        # --- StagePolicyNetwork (reference scheduler.py:279-320) ---
        j_cap = x.shape[-3]
        with annotate("decima/gnn/stage_head"):
            h_dag_rpt = jnp.broadcast_to(
                h_dag[..., :, None, :], (*x.shape[:-1], d)
            )
            h_glob_rpt = jnp.broadcast_to(
                h_glob[..., None, None, :], (*x.shape[:-1], d)
            )
            stage_in = jnp.concatenate(
                [x, h_node, h_dag_rpt, h_glob_rpt], axis=-1
            )
            stage_scores = self.mlp_stage(stage_in)[..., 0].astype(
                jnp.float32
            )

        # --- ExecPolicyNetwork (reference scheduler.py:323-385) ---
        # x_dag = first NUM_DAG_FEATURES features of each dag's first node;
        # features 0..2 are per-job constants so any active node works.
        with annotate("decima/gnn/exec_head"):
            first = jnp.argmax(f.node_mask, axis=-1)
            x_dag = jnp.take_along_axis(
                x, first[..., None, None], axis=-2
            )[..., 0, :NUM_DAG_FEATURES]
            n = self.num_executors
            k_frac = (jnp.arange(n, dtype=_i32) / n).astype(x.dtype)
            per_job = jnp.concatenate([x_dag, h_dag], axis=-1)
            exec_in = jnp.concatenate(
                [
                    jnp.broadcast_to(
                        per_job[..., :, None, :],
                        (*per_job.shape[:-1], n, per_job.shape[-1]),
                    ),
                    jnp.broadcast_to(
                        h_glob[..., None, None, :],
                        (*per_job.shape[:-1], n, d),
                    ),
                    jnp.broadcast_to(
                        k_frac[:, None], (*per_job.shape[:-1], n, 1)
                    ),
                ],
                axis=-1,
            )
            exec_scores = self.mlp_exec(exec_in)[..., 0].astype(
                jnp.float32
            )

        return stage_scores, exec_scores


# --------------------------------------------------------------------------
# masked sampling / evaluation (reference decima/utils.py:19-42)
# --------------------------------------------------------------------------


def masked_log_softmax(scores: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    logits = jnp.where(mask, scores, NEG_INF)
    return jax.nn.log_softmax(logits, axis=-1)


def masked_entropy(logp: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """-sum p·logp over masked entries (reference utils.evaluate:26-42)."""
    p = jnp.exp(logp)
    return -jnp.where(mask, p * logp, 0.0).sum(axis=-1)


class DecimaAction(struct.PyTreeNode):
    stage_idx: jnp.ndarray  # i32 flat padded node index (-1 = none)
    job_idx: jnp.ndarray  # i32 padded job id
    num_exec: jnp.ndarray  # i32 0-based parallelism choice k (env gets k+1)


def sample_action(
    rng: jax.Array,
    stage_scores: jnp.ndarray,
    exec_scores: jnp.ndarray,
    f: DecimaFeatures,
    deterministic: bool = False,
):
    """Autoregressive sample: stage via masked softmax over all schedulable
    nodes, then executor count conditioned on the stage's job (reference
    scheduler.py:81-99). Returns (DecimaAction, lgprob). With
    `deterministic` (static), both heads take the masked argmax instead of
    sampling (greedy eval / rng-free parity testing); `lgprob` is still the
    softmax log-probability of the chosen action."""
    j_cap, s_cap = f.stage_mask.shape
    k_stage, k_exec = jax.random.split(rng)

    flat_mask = f.stage_mask.reshape(-1)
    logp_stage = masked_log_softmax(stage_scores.reshape(-1), flat_mask)
    valid = flat_mask.any()
    stage_logits = jnp.where(flat_mask, stage_scores.reshape(-1), NEG_INF)
    stage_pick = (
        jnp.argmax(stage_logits)
        if deterministic
        else jax.random.categorical(k_stage, stage_logits)
    )
    stage_flat = jnp.where(valid, stage_pick, -1).astype(_i32)
    job = jnp.where(valid, stage_flat // s_cap, -1).astype(_i32)

    e_mask = f.exec_mask[jnp.maximum(job, 0)]
    logp_exec = masked_log_softmax(exec_scores[jnp.maximum(job, 0)], e_mask)
    exec_logits = jnp.where(
        e_mask, exec_scores[jnp.maximum(job, 0)], NEG_INF
    )
    exec_pick = (
        jnp.argmax(exec_logits)
        if deterministic
        else jax.random.categorical(k_exec, exec_logits)
    )
    k = jnp.where(e_mask.any(), exec_pick, 0).astype(_i32)

    lgprob = jnp.where(
        valid,
        logp_stage[jnp.maximum(stage_flat, 0)] + logp_exec[k],
        0.0,
    )
    return DecimaAction(stage_idx=stage_flat, job_idx=job, num_exec=k), lgprob


def evaluate_actions(
    stage_scores: jnp.ndarray,
    exec_scores: jnp.ndarray,
    f: DecimaFeatures,
    action: DecimaAction,
    num_executors: int,
):
    """Log-prob + normalized entropy of one stored action (reference
    scheduler.py:101-139). Batch by vmapping over leading axes."""
    s_cap = f.stage_mask.shape[-1]
    flat_mask = f.stage_mask.reshape(-1)
    logp_stage = masked_log_softmax(stage_scores.reshape(-1), flat_mask)
    e_mask = f.exec_mask[jnp.maximum(action.job_idx, 0)]
    logp_exec = masked_log_softmax(
        exec_scores[jnp.maximum(action.job_idx, 0)], e_mask
    )

    lgprob = (
        logp_stage[jnp.maximum(action.stage_idx, 0)]
        + logp_exec[action.num_exec]
    )
    ent = masked_entropy(logp_stage, flat_mask) + masked_entropy(
        logp_exec, e_mask
    )
    # entropy scale-normalization (reference scheduler.py:135-137)
    num_nodes = f.node_mask.sum()
    ent = ent / jnp.log(
        jnp.maximum(num_executors * num_nodes, 2).astype(jnp.float32)
    )
    valid = action.stage_idx >= 0
    return jnp.where(valid, lgprob, 0.0), jnp.where(valid, ent, 0.0)


# --------------------------------------------------------------------------
# scheduler plugin
# --------------------------------------------------------------------------


class DecimaScheduler(TrainableScheduler):
    """Trainable Decima scheduler (reference decima/scheduler.py:16-139).

    Holds the flax module and a parameter pytree; all heavy lifting is in
    the pure functions above so trainers can jit/vmap/grad them directly.
    """

    def __init__(
        self,
        num_executors: int,
        embed_dim: int = 16,
        gnn_mlp_kwargs: dict[str, Any] | None = None,
        policy_mlp_kwargs: dict[str, Any] | None = None,
        state_dict_path: str | None = None,
        seed: int = 42,
        num_tasks_scale: float = 200.0,
        work_scale: float = 1e5,
        compute_dtype: str | None = None,
        num_levels: int = 0,
        job_bucket: int = 0,
        **_: Any,
    ) -> None:
        self.name = "Decima"
        self.num_executors = int(num_executors)
        self.num_tasks_scale = num_tasks_scale
        self.work_scale = work_scale
        # active-job compaction bucket K (0 = off): `score` runs the GNN
        # at width K when every item has <= K active jobs, with a
        # scalar-predicate full-width fallback (see `score`'s docstring)
        self.job_bucket = int(job_bucket)
        gnn_mlp_kwargs = gnn_mlp_kwargs or {}
        policy_mlp_kwargs = policy_mlp_kwargs or {}
        self.net = DecimaNet(
            num_executors=self.num_executors,
            embed_dim=embed_dim,
            gnn_hid=tuple(gnn_mlp_kwargs.get("hid_dims", (32, 16))),
            policy_hid=tuple(policy_mlp_kwargs.get("hid_dims", (64, 64))),
            gnn_act=gnn_mlp_kwargs.get("act_cls", "LeakyReLU"),
            gnn_act_kwargs=_hashable(gnn_mlp_kwargs.get("act_kwargs")),
            policy_act=policy_mlp_kwargs.get("act_cls", "Tanh"),
            policy_act_kwargs=_hashable(policy_mlp_kwargs.get("act_kwargs")),
            compute_dtype=compute_dtype,
            num_levels=int(num_levels),
        )
        self.params = self.init_params(jax.random.PRNGKey(seed))
        if state_dict_path:
            self.name += f":{state_dict_path}"
            if state_dict_path.endswith(".pt"):
                self.params = load_torch_state_dict(
                    state_dict_path, self.params
                )
            else:  # flax msgpack checkpoint written by the Trainer
                from flax import serialization

                with open(state_dict_path, "rb") as fp:
                    self.params = serialization.from_bytes(
                        self.params, fp.read()
                    )
        self._rng = jax.random.PRNGKey(seed)

    # -- parameter init ---------------------------------------------------
    def init_params(self, rng: jax.Array):
        f = _dummy_features(self.num_executors)
        return self.net.init(rng, f)

    def features(self, obs: Observation) -> DecimaFeatures:
        return build_features(
            obs, self.num_executors, self.num_tasks_scale, self.work_scale
        )

    # -- scoring (compaction-aware) ----------------------------------------
    def full_width(self, f: DecimaFeatures):
        """The scalar predicate of `score`'s full-width fallback: some
        item of `f` (over all leading axes) holds more than `job_bucket`
        active jobs. None where the net has one width only (no bucket,
        or a bucket that covers the job cap). `batch_policy` reports it
        as `aux["full_width"]`, which the single-eval collectors count
        per decision row (`Telemetry.rows_full_width`)."""
        k = self.job_bucket
        if not k or k >= f.job_mask.shape[-1]:
            return None
        return (f.job_mask.sum(-1) > k).any()

    def score(self, params, f: DecimaFeatures):
        """Stage/exec scores for padded features `f` — unbatched [J,...]
        or with any number of leading batch axes. With `job_bucket` K > 0
        the <=K active jobs are gathered into a width-K view, the net
        runs at width K, and the scores scatter back to [J] (identical
        values on active jobs — per-job computations are independent and
        the global summary sums over job_mask only). The full-width
        fallback runs under a lax.cond whose predicate reduces over ALL
        leading axes to a scalar: batched callers (the single-eval flat
        collectors, bench) execute exactly one branch at runtime —
        unlike a per-lane cond, which jax's batching rule lowers to
        executing both branches for every lane."""
        overflow = self.full_width(f)
        if overflow is None:
            return self.net.apply(params, f)
        k = self.job_bucket
        j_cap = f.job_mask.shape[-1]

        def full(f):
            return self.net.apply(params, f)

        def compact(f):
            cf = partial(compact_features, k=k)
            sc = partial(scatter_job_scores, j_cap=j_cap)
            for _ in range(f.job_mask.ndim - 1):
                cf, sc = jax.vmap(cf), jax.vmap(sc)
            fk, ids = cf(f)
            ss, es = self.net.apply(params, fk)
            return sc(ss, es, ids)

        return jax.lax.cond(overflow, full, compact, f)

    # -- pure policy (vmap/scan-safe) -------------------------------------
    def policy(self, rng: jax.Array, obs: Observation, params=None,
               deterministic: bool = False):
        params = self.params if params is None else params
        with annotate("decima/features"):
            f = self.features(obs)
        with annotate("decima/gnn"):
            stage_scores, exec_scores = self.score(params, f)
        with annotate("decima/sample"):
            action, lgprob = sample_action(
                rng, stage_scores, exec_scores, f, deterministic
            )
            # env takes a 1-based executor count (reference
            # env_wrapper.py:33-34)
            num_exec = action.num_exec + 1
        return action.stage_idx, num_exec, {
            "lgprob": lgprob,
            "job_idx": action.job_idx,
            "num_exec_k": action.num_exec,
        }

    # -- batched policy (single GNN eval over a lane stack) ----------------
    def batch_policy(self, rng: jax.Array, obs: Observation, params=None,
                     deterministic: bool = False):
        """Policy over a [B]-leading Observation stack in ONE net
        evaluation, with the compaction cond at batch level (scalar
        predicate — one branch executes at runtime). `rng` is a single
        key, split per lane internally, or one key a lane
        (`base.keys_by_lane`). Returns per-lane
        (stage_idx[B], num_exec_1based[B], aux-of-[B]); where the net
        has two widths, aux also holds the row's scalar `full_width`
        (see `full_width`)."""
        params = self.params if params is None else params
        with annotate("decima/features"):
            f = jax.vmap(self.features)(obs)
        with annotate("decima/gnn"):
            stage_scores, exec_scores = self.score(params, f)
            wide = self.full_width(f)
        with annotate("decima/sample"):
            keys = keys_by_lane(rng, f.job_mask.shape[0])
            action, lgprob = jax.vmap(
                lambda r, ss, es, ff: sample_action(
                    r, ss, es, ff, deterministic
                )
            )(keys, stage_scores, exec_scores, f)
            num_exec = action.num_exec + 1
        aux = {
            "lgprob": lgprob,
            "job_idx": action.job_idx,
            "num_exec_k": action.num_exec,
        }
        if wide is not None:
            aux["full_width"] = wide
        return action.stage_idx, num_exec, aux

    # -- flat micro-step engine adapter ------------------------------------
    def flat_policy(self, params=None, deterministic: bool = False):
        """Bind this scheduler into a `policy_fn(rng, obs)` for the flat
        micro-step engine (`env/flat_loop.py`): the dense per-job GNN
        runs on the DECIDE branch's padded observation inside the
        micro-step scan, and the aux dict carries the log-prob/action
        decomposition the trajectory recorder stores. Pass explicit
        `params` (e.g. the live training parameters) to keep the returned
        closure jit/scan-safe across parameter updates."""
        p = self.params if params is None else params

        def policy_fn(rng, obs):
            return self.policy(rng, obs, p, deterministic)

        return policy_fn

    def flat_batch_policy(self, params=None, deterministic: bool = False):
        """Batched analog of `flat_policy` for the single-eval flat
        collectors (`trainers/rollout.py:collect_flat_sync_batch`): one
        `batch_policy` call per decision row over the whole lane stack,
        so the compaction cond stays scalar (see `score`)."""
        p = self.params if params is None else params

        def policy_fn(rng, obs):
            return self.batch_policy(rng, obs, p, deterministic)

        return policy_fn

    def serve_policies(self, params=None, deterministic: bool = True):
        """The `(policy_fn, batch_policy_fn)` pair with the parameters
        BOUND as closure constants — the pre-ISSUE-14 serving binding,
        kept for ad-hoc jit use. The AOT decision service compiles
        `serve_param_policies` instead (explicit-params signature), so
        weights stay a runtime argument and hot swap needs no
        recompile. Serving defaults to greedy (`deterministic=True`):
        a production decision is the argmax of both heads,
        rng-independent, so equal session states always serve equal
        decisions regardless of the request's batch placement."""
        p = self.params if params is None else params
        return (
            self.flat_policy(p, deterministic),
            self.flat_batch_policy(p, deterministic),
        )

    def serve_param_policies(self, deterministic: bool = True):
        """The `(policy_fn, batch_policy_fn)` pair the AOT decision
        service compiles since ISSUE 14, with the model parameters as
        the LEADING EXPLICIT ARGUMENT:
        `policy_fn(model_params, rng, obs)` /
        `batch_policy_fn(model_params, rng, obs)`. Both serve paths
        receive the same params value per call from the session store,
        so they cannot disagree on weights — and because params enter
        the compiled programs as ordinary arguments (not closure
        constants), a new parameter version swaps in with zero
        recompiles (the `ParamBus` hot-swap contract)."""
        return (
            lambda p, k, o: self.policy(k, o, p, deterministic),
            lambda p, k, o: self.batch_policy(k, o, p, deterministic),
        )

    # -- host-side single decision ----------------------------------------
    def schedule(self, obs: Observation):
        self._rng, sub = jax.random.split(self._rng)
        stage_idx, num_exec, info = jax.jit(self.policy)(sub, obs)
        return (
            {"stage_idx": int(stage_idx), "num_exec": int(num_exec)},
            {k: jax.device_get(v) for k, v in info.items()},
        )

    # -- training-time evaluation ------------------------------------------
    def evaluate_actions(self, params, feats: DecimaFeatures,
                         actions: DecimaAction):
        """Batched log-probs/entropies; `feats`/`actions` have leading batch
        axes (reference scheduler.py:101-139).

        The forward is rematerialized (`jax.checkpoint`): the unrolled
        S-level GNN would otherwise keep every level's activations alive
        for the backward pass across the whole minibatch — the memory
        wall at the flagship 200-job/20-stage scale. Remat trades one
        recomputed forward for ~S x less live activation memory."""

        def one(f, a):
            with annotate("decima/gnn"):
                stage_scores, exec_scores = jax.checkpoint(
                    lambda p, ff: self.net.apply(p, ff)
                )(params, f)
            return evaluate_actions(
                stage_scores, exec_scores, f, a, self.num_executors
            )

        return jax.vmap(one)(feats, actions)


def _hashable(obj):
    if isinstance(obj, dict):
        return tuple(sorted(obj.items()))
    return obj


def _dummy_features(num_executors: int) -> DecimaFeatures:
    j, s = 2, 3
    return DecimaFeatures(
        x=jnp.zeros((j, s, NUM_NODE_FEATURES), jnp.float32),
        node_mask=jnp.ones((j, s), bool),
        job_mask=jnp.ones((j,), bool),
        stage_mask=jnp.ones((j, s), bool),
        exec_mask=jnp.ones((j, num_executors), bool),
        adj=jnp.zeros((j, s, s), bool),
        node_level=jnp.zeros((j, s), _i32),
    )


# --------------------------------------------------------------------------
# torch checkpoint conversion (reference models/decima/model.pt)
# --------------------------------------------------------------------------

_TORCH_TO_FLAX = {
    "encoder.node_encoder.mlp_prep": "mlp_prep",
    "encoder.node_encoder.mlp_msg": "mlp_msg",
    "encoder.node_encoder.mlp_update": "mlp_update",
    "encoder.dag_encoder.mlp": "mlp_dag",
    "encoder.global_encoder.mlp": "mlp_glob",
    "stage_policy_network.mlp_score": "mlp_stage",
    "exec_policy_network.mlp_score": "mlp_exec",
}


def load_torch_state_dict(path: str, params):
    """Convert a reference torch checkpoint (scheduler.py:57-59) into this
    module's parameter pytree. Torch `Sequential` indices map to dense
    layer indices (Linear layers sit at even indices)."""
    import torch

    sd = torch.load(path, map_location="cpu", weights_only=True)
    out = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy
    flat = dict(out["params"])
    for tname, fname in _TORCH_TO_FLAX.items():
        dst = dict(flat[fname])
        seq_idxs = sorted(
            {
                int(k[len(tname) + 1:].split(".")[0])
                for k in sd
                if k.startswith(tname + ".")
            }
        )
        for li, si in enumerate(seq_idxs):
            w = np.asarray(sd[f"{tname}.{si}.weight"])
            b = np.asarray(sd[f"{tname}.{si}.bias"])
            dst[f"dense_{li}"] = {
                "kernel": jnp.asarray(w.T),
                "bias": jnp.asarray(b),
            }
        flat[fname] = dst
    return {"params": flat}
