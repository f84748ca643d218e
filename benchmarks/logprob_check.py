"""The one comparison of a collector's stored log-probabilities with the
plain forward pass, for every driver whose cell collects with Decima.

A seeded sample of a rollout's valid stored decisions is scored by
`reference/decima_np.py` under the parameters that collected them: the
gap between the collector's recorded log-probability and the
reference's, for the recorded action on the recorded observation; the
reference once in plain float32 and once at the stated precision
(bfloat16 operands in every matrix product). What is compared is the
configuration file's to say (`limits`): the mean gap against the plain
reference (a gross fault: a wrong action, row or weight); against the
stated precision the mean gap and a high quantile of the gaps; and
either of those two as a SHARE of what the reference itself reads when
it is put in the program's place at the next precision down
(`bf16_compute`) on the same decisions. The gaps follow the scale of
the weights a seed draws and how peaked its policy is, by more than a
factor of ten over seeds, so that no limit on a gap parts sound runs
from runs computed in bfloat16 on every seed; the share does (PERF.md
section 4, PR 41). The widest gap is printed and NOT compared: the
largest of a few hundred draws of a rare rounding is a heavy tail that
a sound run's and a bfloat16 run's share.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import harness
from benchmarks.reference import decima_np

# the widest gap of the first few of the sample is printed too: what
# `decima_rollout` compared until PR 41, kept to count what it failed
FIRST = 64


RATIO = "_ratio"  # a limit of that ending asks for the lower precision


def wants_lower_precision(limits: dict) -> bool:
    """Whether the configuration compares a gap as a share of the lower
    precision's: only then is the reference run a third time."""
    return any(k.endswith(RATIO) for k in limits)


def logprob_gaps(trainer, params, ro, seed: int, conf: dict, sample: int,
                 lower_precision: bool = False) -> dict:
    """The gaps of `sample` valid decisions of the rollout `ro`, drawn
    from `seed`, arrays in the order drawn: `float32` and
    `bf16_operands`, the collector's recorded log-probability against
    the reference in plain float32 and at the stated precision; with
    `lower_precision` also `bf16_compute`, the REFERENCE at the next
    precision down against the reference at the stated one, on the same
    decisions under the same weights."""
    import jax

    valid = np.asarray(jax.device_get(ro.valid))
    lanes_t = np.argwhere(valid)
    rng = np.random.default_rng(seed)
    n = min(sample, len(lanes_t))
    pick = lanes_t[rng.choice(len(lanes_t), size=n, replace=False)]
    bi, ti = pick[:, 0], pick[:, 1]
    so, stage_idx, exec_k, lgprob = jax.device_get(jax.tree_util.tree_map(
        lambda a: a[bi, ti],
        (ro.obs, ro.stage_idx, ro.num_exec_k, ro.lgprob)))
    weights = jax.tree_util.tree_map(np.asarray, jax.device_get(params))
    adj_bank = np.asarray(trainer.bank.adj)
    j, s = so.job_mask.shape[1], adj_bank.shape[-1]
    gaps = {"float32": [], "bf16_operands": []}
    if lower_precision:
        gaps["bf16_compute"] = []
    for i in range(n):
        obs = {
            name: np.asarray(getattr(so, name)[i])[: j * s].reshape(j, s)
            for name in ("remaining", "duration", "schedulable",
                         "node_mask")}
        obs |= {"job_mask": so.job_mask[i],
                "exec_supplies": so.exec_supplies[i],
                "num_committable": so.num_committable[i],
                "source_job": so.source_job[i],
                "adj": adj_bank[np.asarray(so.job_template[i])]}
        ref = {matmul: decima_np.score_action(
            weights, obs, int(stage_idx[i]), int(exec_k[i]),
            trainer.params_env.num_executors,
            gnn_slope=conf["model"]["gnn_negative_slope"],
            matmul=matmul)["lgprob"] for matmul in gaps}
        for matmul, value in ref.items():
            against = (ref["bf16_operands"] if matmul == "bf16_compute"
                       else float(lgprob[i]))
            gaps[matmul].append(abs(against - value))
    return {k: np.asarray(v, dtype=float) for k, v in gaps.items()}


def gap_numbers(gaps: dict, q: float) -> dict:
    """Every number a configuration may compare, by its check's name:
    the mean gap against the plain float32 reference; against the
    reference at the stated precision the mean gap and the `q` quantile
    of the gaps; and, where the lower precision was run, each of those
    two as a share of what the lower precision reads on the same
    decisions (a sound program a small share, one computed in bfloat16
    about 1, whatever scale the seed's weights have). An empty sample
    gives no number (nan)."""
    def mean(g):
        return float(g.mean()) if g.size else float("nan")

    def quantile(g):
        return float(np.quantile(g, q)) if g.size else float("nan")

    def share(a, b):
        return a / b if b > 0 else float("nan")

    plain, stated = (np.asarray(gaps[k], dtype=float)
                     for k in ("float32", "bf16_operands"))
    out = {"logprob_gap_mean": mean(plain),
           "logprob_stated_gap_mean": mean(stated),
           "logprob_stated_gap_quantile": quantile(stated)}
    if "bf16_compute" in gaps:
        lower = np.asarray(gaps["bf16_compute"], dtype=float)
        out["logprob_stated_gap_mean" + RATIO] = share(
            out["logprob_stated_gap_mean"], mean(lower))
        out["logprob_stated_gap_quantile" + RATIO] = share(
            out["logprob_stated_gap_quantile"], quantile(lower))
    return out


def gap_checks(gaps: dict, limits: dict) -> list[dict]:
    """The sample's size, and each of `gap_numbers` that the
    configuration's `limits` holds a limit for, beside it. A number
    that is no number (an empty sample, a nan, a limit on a share with
    the lower precision not run) fails."""
    numbers = gap_numbers(gaps, float(limits["logprob_stated_gap_q"]))
    compared = [k for k in limits
                if k in numbers or k.endswith(RATIO)]
    return [harness.check(
        "logprob_sample", len(gaps["bf16_operands"]), 1, ">=")] + [
        harness.check(k, numbers.get(k, float("nan")), limits[k], "<=")
        for k in compared]


def widest(gaps: dict) -> dict:
    """What is printed and not compared: the widest gap against each
    reference, and of the first `FIRST` drawn."""
    out = {}
    for name, key in (("float32", "gap"), ("bf16_operands", "stated_gap")):
        g = np.asarray(gaps[name], dtype=float)
        out[f"{key}_max"] = float(g.max()) if g.size else None
        out[f"{key}_max_first_{FIRST}"] = (
            float(g[:FIRST].max()) if g.size else None)
    return out


def checks(trainer, params, ro, seed: int, conf: dict) -> list[dict]:
    """`limits.logprob_sample` seeded decisions of `ro` against the
    reference: the compared numbers as checks; every number, compared
    or not, the widest gaps and the reference's seconds on a line of
    their own."""
    limits = conf["limits"]
    t0 = time.perf_counter()
    gaps = logprob_gaps(trainer, params, ro, seed, conf,
                        int(limits["logprob_sample"]),
                        wants_lower_precision(limits))
    harness.say(logprob_reference=dict(
        gap_numbers(gaps, float(limits["logprob_stated_gap_q"])),
        **widest(gaps), sample=int(gaps["bf16_operands"].size),
        seconds=time.perf_counter() - t0))
    return gap_checks(gaps, limits)
