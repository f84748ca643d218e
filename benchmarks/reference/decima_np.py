"""Plain numpy forward pass of the Decima policy on one padded
observation, and the log-probability it gives a stored action.

It follows the published description (Mao et al., "Learning Scheduling
Algorithms for Data Processing Clusters", and the upstream
`schedulers/decima/scheduler.py`): five normalised node features,
message passing from the leaves to the roots one topological generation
at a time, per-job and global summaries, a stage head over the
schedulable nodes and an executor head over the allowed parallelism
limits of the chosen job. It imports nothing of the program. It is
given arrays only: one observation and the weights to score it with
(flax's tree `{"params": {mlp: {dense_i: {kernel, bias}}}}` as plain
arrays), and works in float64 on the float32 values.

`matmul` says how a matrix product treats its operands. `"float32"` is
the plain reference: the values as they are. `"bf16_operands"` is the
configuration's STATED precision on a TPU, float32 at the default matmul
precision: both operands of every matrix product (the Dense layers and
the children's message sum) are rounded to bfloat16, products and sums
are exact, and everything between the products stays float32. A program
at the stated precision differs from that by the order of its sums
alone; one that also keeps its activations in bfloat16, the next
precision down, does not. `"bf16_compute"` is that next precision down,
the reference put in such a program's place: operands rounded as above,
and every value a layer hands on (a Dense layer's output before and
after its activation, the features, a message sum, a node's updated
embedding, the per-job and global summaries) rounded to bfloat16 too;
the scores' log-softmax stays exact. It is the yardstick a cell's
`correct` measures a program's gaps with: how far the lower precision
moves the SAME decisions under the SAME weights.

Departures from upstream, as the program makes them: graphs are padded
to [jobs, stages] and masked; the executor head is evaluated for every
job, not only the chosen one (the same numbers).
"""

from __future__ import annotations

import numpy as np

NEG_INF = -1e30
MLPS = ("mlp_prep", "mlp_msg", "mlp_update", "mlp_dag", "mlp_glob",
        "mlp_stage", "mlp_exec")


def _leaky(slope: float):
    return lambda v: np.where(v >= 0, v, slope * v)


def bf16(x: np.ndarray) -> np.ndarray:
    """`x` rounded to bfloat16 (nearest, ties to even), as float64."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


def _operand(x: np.ndarray, matmul: str) -> np.ndarray:
    if matmul == "float32":
        return np.asarray(x, np.float64)
    if matmul in ("bf16_operands", "bf16_compute"):
        return bf16(x)
    raise ValueError(f"unknown matmul treatment {matmul!r}")


def _stored(x: np.ndarray, matmul: str) -> np.ndarray:
    """A value a layer hands on: bfloat16 at the lower precision."""
    return bf16(x) if matmul == "bf16_compute" else x


def _mlp(tree: dict, x: np.ndarray, act, matmul: str) -> np.ndarray:
    n = len(tree)
    for i in range(n):
        d = tree[f"dense_{i}"]
        x = _stored(_operand(x, matmul) @ _operand(d["kernel"], matmul)
                    + np.asarray(d["bias"], np.float64), matmul)
        if i < n - 1:
            x = _stored(act(x), matmul)
    return x


def node_levels(node_mask: np.ndarray, adj: np.ndarray) -> np.ndarray:
    """Topological generation of every active node of the masked graph
    (roots 0, a child one more than its deepest parent)."""
    lvl = np.zeros(node_mask.shape, np.int64)
    for _ in range(node_mask.shape[-1]):
        cand = np.where(adj, lvl[..., :, None] + 1, 0).max(axis=-2)
        lvl = np.maximum(lvl, cand)
    return lvl


def obs_arrays(o) -> dict:
    """The dict `features` takes, from an object with the program's
    `Observation` fields as host arrays (`nodes[..., 0]` the remaining
    tasks, `nodes[..., 1]` the latest duration)."""
    return {"remaining": o.nodes[..., 0], "duration": o.nodes[..., 1],
            "schedulable": o.schedulable, "node_mask": o.node_mask,
            "job_mask": o.job_mask, "exec_supplies": o.exec_supplies,
            "num_committable": o.num_committable,
            "source_job": o.source_job, "adj": o.adj}


def features(obs: dict, num_executors: int, num_tasks_scale: float = 200.0,
             work_scale: float = 1e5) -> dict:
    """The model's inputs from a raw observation (dict of arrays:
    remaining, duration, schedulable, node_mask [J,S]; job_mask,
    exec_supplies [J]; adj [J,S,S]; num_committable, source_job)."""
    n = num_executors
    node_mask = np.asarray(obs["node_mask"], bool)
    job_mask = np.asarray(obs["job_mask"], bool)
    j_cap = job_mask.shape[0]
    supplies = np.asarray(obs["exec_supplies"], np.float64)
    committable = float(obs["num_committable"])
    source = int(obs["source_job"])
    caps = np.minimum(np.maximum(n - supplies, 0), committable)
    is_src = (np.arange(j_cap) == source) & (source >= 0)
    caps = np.where(is_src, committable, caps)
    remaining = np.asarray(obs["remaining"], np.float64)
    duration = np.asarray(obs["duration"], np.float64)
    ones = np.ones_like(remaining)
    x = np.stack([
        ones * (caps / n)[:, None],
        ones * np.where(is_src, 1.0, -1.0)[:, None],
        ones * (supplies / n)[:, None],
        remaining / num_tasks_scale,
        remaining * duration / work_scale,
    ], axis=-1)
    x = np.where(node_mask[..., None], x, 0.0)
    adj = (np.asarray(obs["adj"], bool)
           & node_mask[:, :, None] & node_mask[:, None, :])
    return {
        "x": x, "node_mask": node_mask, "job_mask": job_mask,
        "stage_mask": np.asarray(obs["schedulable"], bool) & node_mask,
        "exec_mask": (np.arange(n)[None, :] < caps[:, None])
        & job_mask[:, None],
        "adj": adj,
    }


def forward(weights: dict, f: dict, num_executors: int,
            gnn_slope: float = 0.2, matmul: str = "float32"
            ) -> tuple[np.ndarray, np.ndarray]:
    """Stage scores [J,S] and executor scores [J,N] (rows of inactive
    jobs are 0 and are never read through the masks)."""
    w = weights["params"]
    g_act, p_act = _leaky(gnn_slope), np.tanh

    def mlp(name: str, x: np.ndarray, act) -> np.ndarray:
        return _mlp(w[name], x, act, matmul)

    jobs = np.flatnonzero(f["job_mask"])
    j_cap, s_cap = f["node_mask"].shape
    stage = np.zeros((j_cap, s_cap))
    execs = np.zeros((j_cap, num_executors))
    if jobs.size == 0:
        return stage, execs
    x = _stored(f["x"][jobs], matmul)
    mask = f["node_mask"][jobs]
    adj = f["adj"][jobs].astype(np.float64)  # [parent, child]
    has_child = f["adj"][jobs].any(axis=-1)
    level = node_levels(mask, f["adj"][jobs])

    h_init = mlp("mlp_prep", x, g_act)
    if not f["adj"][jobs].any():
        h = h_init  # upstream's path for an observation with no edge
    else:
        h = np.where(has_child[..., None], 0.0,
                     mlp("mlp_update", h_init, g_act))
        for lvl in range(s_cap - 1, -1, -1):
            upd = (level == lvl) & has_child & mask
            if not upd.any():
                continue
            agg = _stored(
                adj @ _operand(mlp("mlp_msg", h, g_act), matmul), matmul)
            h = np.where(upd[..., None], _stored(
                h_init + mlp("mlp_update", agg, g_act), matmul), h)
    h = np.where(mask[..., None], h, 0.0)

    z = mlp("mlp_dag", np.concatenate([x, h], axis=-1), g_act)
    h_dag = _stored(np.where(mask[..., None], z, 0.0).sum(axis=-2), matmul)
    h_glob = _stored(mlp("mlp_glob", h_dag, g_act).sum(axis=0), matmul)

    d = h_dag.shape[-1]
    stage_in = np.concatenate([
        x, h,
        np.broadcast_to(h_dag[:, None, :], (*x.shape[:-1], d)),
        np.broadcast_to(h_glob, (*x.shape[:-1], d)),
    ], axis=-1)
    stage[jobs] = mlp("mlp_stage", stage_in, p_act)[..., 0]

    first = mask.argmax(axis=-1)
    x_dag = x[np.arange(jobs.size), first, :3]
    n = num_executors
    exec_in = np.concatenate([
        np.broadcast_to(
            np.concatenate([x_dag, h_dag], axis=-1)[:, None, :],
            (jobs.size, n, 3 + d)),
        np.broadcast_to(h_glob, (jobs.size, n, d)),
        np.broadcast_to((np.arange(n) / n)[None, :, None],
                        (jobs.size, n, 1)),
    ], axis=-1)
    execs[jobs] = mlp("mlp_exec", exec_in, p_act)[..., 0]
    return stage, execs


def _log_softmax(scores: np.ndarray, mask: np.ndarray) -> np.ndarray:
    logits = np.where(mask, scores, NEG_INF)
    m = logits.max()
    return logits - (m + np.log(np.exp(logits - m).sum()))


def action_log_probs(stage: np.ndarray, execs: np.ndarray, f: dict
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Log-probabilities of every flat stage index and, per job, of
    every executor count (masked entries about -1e30)."""
    lp_stage = _log_softmax(stage.reshape(-1), f["stage_mask"].reshape(-1))
    lp_exec = np.stack([
        _log_softmax(execs[j], f["exec_mask"][j])
        if f["exec_mask"][j].any() else np.full(execs.shape[1], NEG_INF)
        for j in range(execs.shape[0])
    ])
    return lp_stage, lp_exec


def score_action(weights: dict, obs: dict, stage_idx: int, num_exec_k: int,
                 num_executors: int, gnn_slope: float = 0.2,
                 num_tasks_scale: float = 200.0,
                 work_scale: float = 1e5, matmul: str = "float32") -> dict:
    """What the reference says of one stored decision: the action's
    log-probability, the reference's own greedy action, how far the
    stored action's log-probability lies below the greedy one's, and
    the margin between the two best greedy candidates of each head."""
    f = features(obs, num_executors, num_tasks_scale, work_scale)
    stage, execs = forward(weights, f, num_executors, gnn_slope, matmul)
    lp_stage, lp_exec = action_log_probs(stage, execs, f)
    s_cap = f["node_mask"].shape[1]
    job = stage_idx // s_cap
    lgprob = float(lp_stage[stage_idx] + lp_exec[job, num_exec_k])
    best_stage = int(lp_stage.argmax())
    best_job = best_stage // s_cap
    best_k = int(lp_exec[best_job].argmax())

    def margin(v: np.ndarray) -> float:
        top = np.sort(v[v > NEG_INF / 2])[::-1]
        return float(top[0] - top[1]) if top.size > 1 else float("inf")

    return {
        "lgprob": lgprob,
        "greedy": (best_stage, best_k),
        "below_best": float(
            (lp_stage[best_stage] - lp_stage[stage_idx])
            + (lp_exec[best_job, best_k] - lp_exec[job, num_exec_k])
            if job == best_job else
            lp_stage[best_stage] - lp_stage[stage_idx]),
        "margin": min(margin(lp_stage), margin(lp_exec[best_job])),
        "stage_scores": stage, "exec_scores": execs,
    }
