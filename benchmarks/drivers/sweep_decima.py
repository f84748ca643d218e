"""A TRAINED-POLICY sweep in steady state: `sweep_chunks`' cell with the
Decima net in every decision row of the program's sweep loop
(`sparksched_tpu/sweep.py`: `sweep_chunk`), the action SAMPLED once a
row and block of 128 lanes.

`stagger`, `warm_up`, `measure` and `close` are `sweep_chunks`', and so
is what `build` does (it ends at once, before jax is touched, on a
program without the configuration's `program_config` or without the
sweep); here `build` besides draws the net's weights from `--seed`
(`agent.seed`) and hands them to every call of the chunk as an ARGUMENT
(`sweep_chunk(..., weights)`), so that every seed runs one compiled
program.

`verify`, outside the window (the configuration's `guarantees`): the
counts of `sweep_chunks` on the last chunk and the carry it returned
(`reference/sweep_np.check_sweep`, each against 0; `health_mask`; the
window's finished episodes against a share of the lanes). THE POLICY: a
seeded sample of the lanes, some of EVERY block of 128, row 0 of the
last chunk: the recorded job, stage, executors and log-probability
against the plain net (`reference/decima_np.score_action`) on the
program's observation of the carry that chunk was handed, through
`benchmarks/logprob_check.py`, by a share as `decima_rollout` decides
(here the 0.95 quantile's, `logprob_stated_gap_quantile_ratio`). THE
ENGINE, through the executable the run timed: the window's carries are
let go, the stagger is made once more over the bank collapsed to fixed
durations (`sweep_chunks.fixed_durations`; the bank is an argument, so
nothing compiles, and that is checked) with the lanes left under the
SOURCE block's ids, and the timed program runs one chunk over it (the
device does both while the host scores the policy's sample). A sampled
policy does not "go on as the source lane does", so nothing is
simulated forward: for a few source lanes the plain event heap
(`reference/sweep_replay_np.Lane`) REPLAYS the decisions the source
block recorded, call after call from reset through the stagger, and
from the state it has after call `p` a copy of it replays the rows the
lane's copy in block `p` of the timed chunk recorded (a block in
`limits.engine_copy_stride`, and every block in which that copy's
episode ends: the plain heap takes 10 to 13 ms a decision at this
cluster). Held to it in every replayed row: the decision's time, that
the lane decided, that the simulator could take the decision (the stage
schedulable there), the end flag, the ordinal and, where an episode
ends, its result. THE OBSERVATION, which the policy check takes from
the program: after every call of the stagger the plain heap's own
observation of each source lane (`stream_np._Episode.observe`) against
the program's (`observe`, `store_obs`) of that lane's copy in the carry
the timed chunk was handed, field for field: what a lane at every phase
of an episode shows the net.
"""

from __future__ import annotations

import time
import types

import numpy as np

from benchmarks import harness, logprob_check
from benchmarks.drivers import sweep_chunks
from benchmarks.drivers.sweep_chunks import (  # noqa: F401
    BLOCK,
    HOST_SPANS,
    UNATTRIBUTED,
    close,
    fixed_durations,
    guarantee_checks,
    job_sequences,
    measure,
    record_arrays,
    results_differ,
    stagger,
    staggered,
    warm_up,
)
from benchmarks.reference import sweep_replay_np

ROW_FIELDS = 5  # valid, time, the decision taken, end flag, ordinal
RESULT_FIELDS = sweep_chunks.RESULT_FIELDS


class _WithWeights:
    """The program's sweep module, its `sweep_chunk` handed the net's
    weights as the argument after `rows`."""

    def __init__(self, sweep, weights) -> None:
        self._sweep, self._weights = sweep, weights

    def __getattr__(self, name: str):
        return getattr(self._sweep, name)

    def sweep_chunk(self, *args):
        return self._sweep.sweep_chunk(*args, self._weights)


def build(cell: dict, seed: int, *, seconds: float = 0.0,
          control: dict | None = None, trace: bool = False) -> dict:
    ctx = sweep_chunks.build(
        cell, seed, seconds=seconds, trace=trace, control=harness.merge(
            control or {}, {"agent": {"seed": harness.seed31(seed)}}))
    stated, net = cell["config_data"]["model"], ctx["scheduler"].net
    built = {"embed_dim": net.embed_dim, "gnn_hid_dims": list(net.gnn_hid),
             "policy_hid_dims": list(net.policy_hid)}
    if any(stated[k] != v for k, v in built.items()):
        raise SystemExit(
            f"the configuration states the net {stated}; the program's "
            f"{cell['config_data']['program_config']} builds {built}")
    ctx["module"] = ctx["sweep"]
    ctx["sweep"] = _WithWeights(ctx["sweep"], ctx["scheduler"].params)
    return ctx


# ---------------------------------------------------------------------------
# verify
# ---------------------------------------------------------------------------


def stored_observation(ctx: dict, env, lanes):
    """The program's observation of `lanes` of the stacked state `env`
    as a collector stores it (`observe`, `store_obs`), on the host."""
    import jax

    from sparksched_tpu.env.observe import observe
    from sparksched_tpu.trainers.rollout import store_obs

    params = ctx["params"]
    return jax.device_get(jax.jit(lambda env, at: jax.vmap(
        lambda e: store_obs(observe(params, e), e))(
        jax.tree_util.tree_map(lambda a: a[at], env)))(
        env, np.asarray(lanes)))


def sampled_lanes(seed: int, lanes: int, sample: int) -> np.ndarray:
    """`sample` seeded lanes of `lanes`, as many of one block of `BLOCK`
    as of another (to one): a row evaluates the policy a block at a
    time, and no block goes unsampled."""
    rng, blocks = np.random.default_rng(seed), lanes // BLOCK
    most = -(-sample // blocks)  # of one block
    picks = np.stack([rng.permutation(BLOCK)[:most]
                      for _ in range(blocks)], axis=1)
    return np.sort((picks + BLOCK * np.arange(blocks)).reshape(-1)[:sample])


def policy_sample(ctx: dict, rec: dict, lgprob):
    """`limits.logprob_sample` seeded lanes (`sampled_lanes`), row 0 of
    the chunk `rec` (with its recorded log-probabilities `lgprob`), on
    the program's observation of the carry the chunk was handed, on the
    host and laid out as `logprob_check` takes a rollout: one step a
    lane."""
    import jax

    params, conf = ctx["params"], ctx["cell"]["config_data"]
    lanes = sampled_lanes(ctx["seed"], ctx["lanes"], min(
        int(conf["limits"]["logprob_sample"]), ctx["lanes"]))
    stored = stored_observation(ctx, ctx["handed"].ls.env, lanes)
    job, stage = rec["job"][0, lanes], rec["stage"][0, lanes]
    return types.SimpleNamespace(
        valid=(rec["valid"][0, lanes] & (job >= 0))[:, None],
        obs=jax.tree_util.tree_map(lambda a: a[:, None], stored),
        stage_idx=(job * params.max_stages + stage)[:, None],
        num_exec_k=(rec["num_exec"][0, lanes] - 1)[:, None],
        lgprob=np.asarray(lgprob)[0, lanes][:, None])


def actions_of(rec: dict, lane: int):
    return zip(rec["job"][:, lane], rec["stage"][:, lane],
               rec["num_exec"][:, lane])


def rows_differ(rec: dict, lane: int, want: list[dict]) -> int:
    """The fields in which the rows of a lane's record part from the
    simulator's replay of them."""
    return sum(
        int(not rec["valid"][t, lane])
        + (abs(float(rec["wall_time"][t, lane]) - row["time"]) > 1e-3)
        + (not row["taken"])
        + (bool(rec["reset"][t, lane]) != row["reset"])
        + (int(rec["ordinal"][t, lane]) != row["ordinal"])
        for t, row in enumerate(want))


def observation_differs(stored, i: int, plain: dict) -> int:
    """The fields in which the program's stored observation of lane `i`
    parts from the plain simulator's: the node grids (tasks remaining,
    latest duration, the schedulable set, the active nodes), the jobs
    present, and of those their executors and templates, the executors
    that can be committed and the job they come from."""
    present = plain["job_mask"]
    cells = present.size * plain["node_mask"].shape[1]
    return sum(
        not np.array_equal(np.asarray(getattr(stored, name)[i])[
            :cells].reshape(plain[name].shape), plain[name])
        for name in ("remaining", "duration", "schedulable", "node_mask")
    ) + (not np.array_equal(stored.job_mask[i], present)) + sum(
        not np.array_equal(np.asarray(getattr(stored, name)[i])[present],
                           plain[name][present])
        for name in ("exec_supplies", "job_template")
    ) + sum(int(getattr(stored, name)[i]) != plain[name]
            for name in ("num_committable", "source_job"))


OBSERVATION_FIELDS = 9


def engine_run(ctx: dict) -> dict:
    """The device's part of the engine comparison, dispatched and not
    waited for: the stagger once more over the bank collapsed to fixed
    durations, its lanes under the source block's ids, and one chunk of
    the timed program over it."""
    from sparksched_tpu.workload import make_workload_bank

    sweep, params = ctx["module"], ctx["params"]
    fixed, tables, durations = fixed_durations(make_workload_bank(
        params.num_executors, params.max_stages,
        **{k: v for k, v in ctx["cfg"]["env"].items()
           if k in ("data_dir", "bucket_size", "data_sampler_cls")}))
    programs = sweep.sweep_chunk._cache_size()
    carry, _, recs, _ = staggered(ctx, fixed)
    keys = carry.key[:BLOCK]
    _, rec, tm = sweep_chunks._chunk(ctx, carry, ctx["rows"], bank=fixed)
    return {"bank": fixed, "tables": tables, "durations": durations,
            "programs": programs, "source": recs, "timed": rec, "tm": tm,
            "keys": keys, "handed": carry}


def engine_checks(ctx: dict, ran: dict) -> list[dict]:
    """(vii), and (ii)'s stored result, through the compiled programs
    the run timed (module docstring), on what `engine_run` left: of the
    source lanes whose first episode ended inside the stagger, the
    `limits.engine_source_lanes` whose copies end most episodes inside
    the timed chunk; of each, the copy in every
    `limits.engine_copy_stride`-th block and in every block in which
    the copy's episode ends, and what EVERY copy observes before its
    first row."""
    import jax

    sweep, params = ctx["module"], ctx["params"]
    limits = ctx["cell"]["config_data"]["limits"]
    source = [record_arrays(r) for r in jax.device_get(ran["source"])]
    timed = record_arrays(ran["timed"])
    compiled = sweep.sweep_chunk._cache_size() - ran["programs"]
    keys = np.asarray(ran["keys"])
    ends_in = np.add.reduce([r["reset"].sum(axis=0) for r in source])
    # [blocks, BLOCK]: the copy of source lane b in block p ends one
    copy_ends = timed["reset"].any(axis=0).reshape(len(source), BLOCK)
    ranked = sorted(np.flatnonzero(ends_in),
                    key=lambda b: (-int(copy_ends[:, b].sum()), b))
    chosen = [int(b) for b in ranked[:int(limits["engine_source_lanes"])]]
    stride = int(limits["engine_copy_stride"])
    # the program's observation of every copy of the chosen lanes
    seen = stored_observation(ctx, ran["handed"].ls.env, [
        p * BLOCK + b for b in chosen for p in range(len(source))])
    cluster = dict(
        num_executors=params.num_executors, max_jobs=params.max_jobs,
        max_stages=params.max_stages, moving_delay=params.moving_delay,
        warmup_delay=params.warmup_delay)
    t0 = time.perf_counter()
    differ = {"source": 0, "timed": 0}
    ends = {"source": 0, "timed": 0}
    rows = {"source": 0, "timed": 0}
    copies, worst, rel = 0, 0.0, float(limits["result_rel_tol"])
    observed = 0

    def held(name, got, lane, want):
        nonlocal worst
        unequal, n, gap = results_differ(got, lane, want, 0, rel)
        differ[name] += rows_differ(got, lane, want) + unequal
        ends[name] += n
        rows[name] += len(want)
        worst = max(worst, gap)

    for i, b in enumerate(chosen):
        plain = sweep_replay_np.Lane(job_sequences(
            sweep, params, ran["bank"], keys[b],
            range(int(ends_in[b]) + ctx["rows"] // params.max_jobs + 2)),
            ran["tables"], ran["durations"], **cluster)
        for p, got in enumerate(source):
            held("source", got, b, plain.replay(actions_of(got, b)))
            observed += observation_differs(
                seen, i * len(source) + p, plain.ep.observe())
            if p % stride == stride - 1 or copy_ends[p, b]:
                lane, copies = p * BLOCK + b, copies + 1
                held("timed", timed, lane, plain.copy().replay(
                    actions_of(timed, lane)))
    harness.say(
        engine_source_lanes=chosen, engine_copies=copies,
        engine_reference_s=time.perf_counter() - t0,
        engine_avg_jct_gap_max=worst,
        engine_ends_compared_source=ends["source"],
        engine_fields_compared_source=ROW_FIELDS * rows["source"]
        + RESULT_FIELDS * ends["source"],
        engine_fields_compared=ROW_FIELDS * rows["timed"]
        + RESULT_FIELDS * ends["timed"],
        engine_observation_fields_compared=OBSERVATION_FIELDS
        * len(chosen) * len(source))
    return [
        harness.check("engine_mismatches", differ["timed"], 0, "=="),
        harness.check("engine_mismatches_source", differ["source"], 0, "=="),
        harness.check("engine_observation_mismatches", observed, 0, "=="),
        harness.check("engine_ends_compared", ends["timed"],
                      int(limits["engine_ends_compared"]), ">="),
        harness.check("engine_programs_compiled", compiled, 0, "=="),
        harness.check("engine_health_mask",
                      sweep.summarize(ran["tm"])["health_mask"], 0, "=="),
    ]


def verify(ctx: dict, window: dict) -> list[dict]:
    import jax

    conf, mix = ctx["cell"]["config_data"], ctx["cell"]["mix"]
    telemetry = window["telemetry"]
    rec = record_arrays(ctx["last"][0])
    checks = [
        harness.check("chunks", len(window["scalars"]),
                      int(mix["min_chunks"]), ">="),
        harness.check("health_mask", max(
            (t["health_mask"] for t in telemetry), default=None), 0, "=="),
        harness.check("telemetry_decisions_gap", sum(
            t["decisions"] for t in telemetry)
            - window["samples"]["decisions"], 0, "=="),
        harness.check("idle_lanes", int(
            (rec["valid"].sum(axis=0) == 0).sum()), 0, "=="),
        harness.check("episodes_finished_share", sum(
            t["reseeds_total"] for t in telemetry) / ctx["lanes"],
            float(conf["limits"]["episodes_finished_share"]), ">="),
    ]
    checks += guarantee_checks(
        rec, ctx["carry"], ctx["staggered_ordinal"], telemetry[-1],
        ctx["params"].max_jobs)
    sample = policy_sample(ctx, rec, jax.device_get(ctx["last"][0].lgprob))
    # the window's carries go before the comparison's come; the device
    # runs the engine's stagger and chunk while the host scores the
    # sample with the plain net
    ctx["handed"] = ctx["carry"] = ctx["last"] = None
    ran = engine_run(ctx)
    checks += logprob_check.checks(
        types.SimpleNamespace(bank=ctx["bank"], params_env=ctx["params"]),
        ctx["scheduler"].params, sample, ctx["seed"], conf)
    return checks + engine_checks(ctx, ran)
