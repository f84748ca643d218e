"""A heuristic sweep in STEADY STATE: chunk after chunk of the program's
sweep loop (`sparksched_tpu/sweep.py`: `sweep_chunk`) over thousands of
lanes at every phase of an episode, a few of them ending, and re-seeded
inside the scan, in every row.

`build` first looks for the program's sweep (its configuration file and
the module): a program without them cannot run the cell, and the run
ends there, at once, naming what is missing, before jax is touched. Then
it builds cluster, bank and scheduler the way `sweep.py -f` does
(`sweep.from_config` of the program's own YAML, with the overrides the
configuration file and the traffic mix list); the sweep key, and with it
every job sequence, comes from `--seed`.

`warm_up` makes the timed carry by a PHASE-STAGGERED START, with nothing
but the program's `init`, `sweep_chunk` and a lane-wise placement: ONE
block of 128 lanes runs from reset through the mix's `episode_rows` (the
median episode in decision rows), in `lanes / 128` calls of
`ceil(episode_rows / (lanes / 128))` rows; the carry after each call is
placed side by side into the timed carry, every lane of which then gets
an id of its own: 128 episodes x `lanes / 128` phases, uniform over an
episode. A whole sweep (episodes many times the lanes) is in that state
for nine tenths of its time; from reset it would take minutes to reach.
The source block's own telemetry over those rows is the whole-episode
reading the window's is printed beside. Then the mix's warm-up chunks of
the timed shape.

`measure` runs whole chunks until `--seconds` are used up (at least the
mix's `min_chunks`); the end-to-end metric is the valid rows of all the
window's chunks over its wall time. The carry is not donated: the carry
the LAST chunk was handed is kept for `verify`.

`verify`, outside the window (the configuration's `guarantees`):
(ii) to (v) on the last chunk and the carry it returned, by the plain
numpy reading `reference/sweep_np.check_sweep`, counts of violations
held at 0; the window's finished episodes against a share of the lanes
(a stagger that did not work ends none); `health_mask` 0; (i) on EVERY
lane, row 0 of the last chunk (job, stage, executors) against
`reference/fair_np.fair`, on the host, on the observation of the carry
that chunk was handed, and once more the program's policy against it on
the carry the chunk returned. Then (vii) and the second half of (ii),
THROUGH THE TIMED EXECUTABLE: the window's carries are let go, the
stagger is made once more over a bank collapsed to one whole-number
value a bucket (`fixed_durations`; the bank is an argument of the
compiled programs, so nothing compiles, and that is checked), its
lanes left under the SOURCE block's ids, so that lane `p * 128 + b` is
source lane `b` after `(p + 1) * rows_per_call` rows and goes on as `b`
does; the timed program runs one chunk over that carry, and for a few
source lanes the plain simulator (`sweep_np.simulate` with `fair_np`,
run on the host while the device runs the chunk) gives every row from
reset through the stagger and the chunk: held to it, field for field
(time, job, stage, executors, the end flag, the ordinal, and on a row an
episode ends in its result: average job completion time, jobs
completed, makespan, decisions), are the source block's own rows (the
128-lane program, through the lanes' first episode end and the re-seed
after it) and the copies' rows in the timed chunk: every one of its
blocks of 128 lanes, at every phase of an episode, the ends and
re-seeds that fall into the chunk among them.
"""

from __future__ import annotations

import math
import os.path as osp
import time

import numpy as np

from benchmarks import harness
from benchmarks.reference import fair_np, sweep_np

HOST_SPANS = ("bench/chunk",)
UNATTRIBUTED = "sweep/host_gap"  # an idle gap under no host span
BLOCK = 128  # the stagger's source: one drain block
POLICY_FIELDS = ("schedulable", "frontier", "job_mask", "exec_supplies",
                 "num_committable", "source_job")
ENV_KEYS = ("num_executors", "job_arrival_cap", "job_arrival_rate",
            "moving_delay", "warmup_delay", "mean_time_limit")
# printed of the source block's whole episode beside the window's
READINGS = ("decisions", "reseeds_total", "jobs_present_per_decision",
            "micro_per_decision", "events_per_decision")


def build(cell: dict, seed: int, *, seconds: float = 0.0,
          control: dict | None = None, trace: bool = False) -> dict:
    conf, mix = cell["config_data"], cell["mix"]
    path = osp.join(harness.ROOT, conf["program_config"])
    module = osp.join(harness.ROOT, "sparksched_tpu", "sweep.py")
    for needed in (path, module):
        if not osp.exists(needed):
            raise SystemExit(
                f"this program has no {osp.relpath(needed, harness.ROOT)}: "
                f"it cannot run the sweep of the cell {cell['name']}")
    import jax

    from sparksched_tpu import config, sweep

    cfg = harness.merge(config.load(path), conf.get("overrides", {}))
    cfg = harness.merge(cfg, mix.get("overrides", {}))
    stated = {k: conf["env"][k] for k in ENV_KEYS}
    built = {k: cfg["env"].get(k) for k in ENV_KEYS}
    if any(v is not None and float(built[k]) != float(v) or
           (v is None) != (built[k] is None) for k, v in stated.items()):
        raise SystemExit(
            f"the configuration states the cluster {stated}; the program's "
            f"{conf['program_config']} gives {built}")
    if control:
        cfg = harness.merge(cfg, control)
    lanes, rows = int(mix["lanes"]), int(mix["rows_per_chunk"])
    if lanes % BLOCK:
        raise SystemExit(f"the mix's lanes ({lanes}) are no whole blocks "
                         f"of {BLOCK}")
    params, bank, scheduler = sweep.from_config(cfg)
    key_law, key_run = jax.random.split(harness.key_from_seed(seed))
    return {"cell": cell, "cfg": cfg, "sweep": sweep, "params": params,
            "bank": bank, "scheduler": scheduler,
            "policy": scheduler.batch_policy, "lanes": lanes, "rows": rows,
            "key_law": key_law, "key_run": key_run, "seed": seed,
            "calls": 0, "carry": None, "handed": None, "last": None,
            "stagger": None}


def _chunk(ctx: dict, carry, rows: int, bank=None):
    """One call of the program's compiled chunk, under the run's next
    key (only a sampling policy reads it)."""
    import jax

    ctx["calls"] += 1
    return ctx["sweep"].sweep_chunk(
        ctx["params"], ctx["bank"] if bank is None else bank, ctx["policy"],
        carry, jax.random.fold_in(ctx["key_run"], ctx["calls"]), rows)


def _place(full, block, at):
    from jax import lax, tree_util

    return tree_util.tree_map(
        lambda f, b: lax.dynamic_update_slice_in_dim(f, b, at, 0),
        full, block)


def staggered(ctx: dict, bank):
    """One block of 128 lanes over `bank` from reset through the mix's
    `episode_rows`, in `lanes / 128` calls of the program's chunk, the
    carry after call `p` placed at lanes `p * 128` onward, still under
    the source block's ids and keys. Returns that carry, the source
    block's summed telemetry, its records (one a call) and the rows of
    a call."""
    import jax
    import jax.numpy as jnp

    sweep, mix = ctx["sweep"], ctx["cell"]["mix"]
    phases = ctx["lanes"] // BLOCK
    per = math.ceil(int(mix["episode_rows"]) / phases)
    block = sweep.init(ctx["params"], bank, ctx["key_law"], BLOCK)
    full = jax.tree_util.tree_map(
        lambda a: jnp.zeros((ctx["lanes"],) + a.shape[1:], a.dtype), block)
    if "place" not in ctx:  # one program for every stagger of the run
        ctx["place"] = jax.jit(_place, donate_argnums=0)
    place = ctx["place"]
    total, recs = None, []
    for i in range(phases):
        block, rec, tm = _chunk(ctx, block, per, bank=bank)
        total = sweep.add_telemetry(total, tm)
        recs.append(rec)
        full = place(full, block, i * BLOCK)
    return full, total, recs, per


def stagger(ctx: dict):
    """The timed carry (module docstring) and what the source block
    counted on its way (its telemetry's summary, with the calls made
    and the rows of each)."""
    import jax.numpy as jnp

    sweep = ctx["sweep"]
    full, total, _, per = staggered(ctx, ctx["bank"])
    ids = jnp.arange(ctx["lanes"], dtype=jnp.int32)
    full = full.replace(lane=ids, key=sweep.lane_keys(ctx["key_law"], ids))
    return full, dict(sweep.summarize(total), rows_per_call=per,
                      calls=ctx["lanes"] // BLOCK)


def warm_up(ctx: dict) -> None:
    import jax

    ctx["carry"], ctx["stagger"] = stagger(ctx)
    ctx["staggered_ordinal"] = np.asarray(ctx["carry"].ordinal)
    for _ in range(int(ctx["cell"]["mix"]["warmup_chunks"])):
        ctx["carry"], rec, _ = _chunk(ctx, ctx["carry"], ctx["rows"])
        jax.block_until_ready(rec.valid)


def measure(ctx: dict, seconds: float, tracer) -> dict:
    import jax

    mix = ctx["cell"]["mix"]
    least = int(mix["min_chunks"])
    times, counted, telems = [], [], []
    tracing = harness.trace_for(
        tracer, float(mix.get("trace_start_s", 0)),
        float(mix.get("trace_seconds", 0)))
    t0 = time.perf_counter()
    while True:
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation(HOST_SPANS[0]):
            ctx["handed"] = ctx["carry"]
            ctx["carry"], rec, tm = _chunk(ctx, ctx["carry"], ctx["rows"])
            jax.block_until_ready(rec.valid)
        now = time.perf_counter()
        times.append(now - t1)
        counted.append(rec.valid)  # read after the window
        telems.append(tm)
        ctx["last"] = (rec, tm)
        if len(times) >= least and now - t0 >= seconds:
            break
    wall = now - t0
    per_chunk = float(np.median(times))
    trace = None
    if tracing is not None:
        tracing.join()
        trace = tracer.reduce()
        # scope times are per chunk: the trace covers part of one
        trace["units"] = trace["window_s"] / per_chunk
    counted = [int(np.asarray(v).sum()) for v in counted]
    summaries = [ctx["sweep"].summarize(tm) for tm in telems]
    decisions = sum(counted)
    whole, window = ctx["stagger"], _window_reading(summaries)
    harness.say(whole_episode={k: whole.get(k) for k in READINGS},
                window=window)
    return {
        "end_to_end": {mix["end_to_end"]: decisions / wall},
        "samples": {"chunks": len(times), "decisions": decisions,
                    "window_s": wall, "asked_s": seconds,
                    "lanes": ctx["lanes"], "rows_per_chunk": ctx["rows"],
                    "chunk_s_median": per_chunk,
                    "stagger": {k: whole[k] for k in (
                        "rows_per_call", "calls", "decisions",
                        "reseeds_total")},
                    "jobs_present_per_decision_whole_episode":
                        whole.get("jobs_present_per_decision"),
                    "jobs_present_per_decision_window":
                        window["jobs_present_per_decision"]},
        "attempted": len(times), "failed": 0,
        # a chunk is the unit of a compiled call, as a collection is the
        # collectors': the key the accepted readers know
        "scalars": [{"collect_seconds": s, "collection": i, "decisions": n}
                    for i, (s, n) in enumerate(zip(times, counted))],
        "telemetry": summaries,
        "trace": trace,
    }


def _window_reading(summaries: list[dict]) -> dict:
    """The window's chunks as one reading, beside the source block's."""
    tot = {k: sum(s[k] for s in summaries) for k in (
        "decisions", "reseeds_total", "jobs_present_total", "micro_steps",
        "events_total")}
    d = max(tot["decisions"], 1)
    return {"decisions": tot["decisions"],
            "reseeds_total": tot["reseeds_total"],
            "jobs_present_per_decision": tot["jobs_present_total"] / d,
            "micro_per_decision": tot["micro_steps"] / d,
            "events_per_decision": tot["events_total"] / d}


# ---------------------------------------------------------------------------
# verify
# ---------------------------------------------------------------------------


def record_arrays(rec) -> dict:
    """A chunk's record on the host, as the plain reading takes it."""
    import jax

    rec = jax.device_get(rec)
    return {k: np.asarray(getattr(rec, k)) for k in (
        "valid", "wall_time", "job", "stage", "num_exec", "reset",
        "ordinal", "avg_jct", "jobs_completed", "makespan", "decisions")}


def guarantee_checks(rec: dict, carry, staggered_ordinal, summary: dict,
                     jobs: int) -> list[dict]:
    """(ii) to (v) on one chunk and the carry it returned: every count of
    violations against 0. A lane's job sequence is its own once it has
    been re-seeded under its own id (the stagger's copies of a source
    lane share the episode they were copied in)."""
    import jax

    env = carry.ls.env
    lane, ordinal, arrivals, templates = jax.device_get(
        (carry.lane, carry.ordinal, env.job_arrival_time, env.job_template))
    found = sweep_np.check_sweep(
        rec, lane=lane, final_ordinal=ordinal, arrivals=arrivals,
        templates=templates, own=ordinal > staggered_ordinal, jobs=jobs,
        summary=summary)
    return [harness.check(k, v, 0, "==") for k, v in found.items()]


def policy_mismatches(ctx: dict, carry, decided=None) -> int:
    """(i) The lanes of `carry` on whose observation the plain fair
    policy gives another (stage, executors) than `decided` ([lanes]
    arrays `valid`, `stage index`, `executors` of the program's own
    row) or, without one, than the program's policy evaluated here."""
    import jax

    from sparksched_tpu.env.observe import observe

    params, sched = ctx["params"], ctx["scheduler"]
    if "fields" not in ctx:  # one program for both carries
        def fields(carry, key):
            obs = jax.vmap(lambda e: observe(params, e))(carry.ls.env)
            stage_idx, num_exec, _ = ctx["policy"](key, obs)
            return {f: getattr(obs, f) for f in POLICY_FIELDS}, (
                stage_idx, num_exec)

        ctx["fields"] = jax.jit(fields)
    obs, (stage_idx, num_exec) = jax.device_get(
        ctx["fields"](carry, ctx["key_run"]))
    valid = np.ones(len(stage_idx), bool)
    if decided is not None:
        valid, stage_idx, num_exec = decided
    wrong = 0
    for b in np.flatnonzero(valid):
        want = fair_np.fair(
            *(obs[f][b] for f in POLICY_FIELDS),
            num_executors=sched.num_executors,
            dynamic_partition=sched.dynamic_partition)
        wrong += want != (int(stage_idx[b]), int(num_exec[b]))
    return int(wrong)


def fixed_durations(bank) -> tuple:
    """`bank` collapsed to ONE whole-number duration a (template, stage,
    wave), the same at every executor level: the median of the bucket's
    samples over all levels, rounded (at least 1 ms); a stage without a
    first-wave sample takes its rough mean there, so that the plain
    simulator never lacks one. No draw can then change a duration, and
    every time is a sum of whole numbers and of the arrival times.
    Returns the bank, and the plain simulator's `tables` and `durations`
    of it (`stream_np.replay`'s arguments)."""
    import jax.numpy as jnp

    dur = np.asarray(bank.dur, np.float64)
    cnt = np.asarray(bank.cnt)
    ns = np.asarray(bank.num_stages)
    rough = np.asarray(bank.rough_duration)
    adj, num_tasks = np.asarray(bank.adj), np.asarray(bank.num_tasks)
    new_dur, new_cnt = np.zeros_like(dur), np.zeros_like(cnt)
    tables, durations = {}, {}
    for t in range(dur.shape[0]):
        waves: list[list] = [[], [], []]
        for s in range(int(ns[t])):
            for w in range(3):
                samples = np.concatenate([
                    dur[t, s, w, lv, :cnt[t, s, w, lv]]
                    for lv in range(dur.shape[3])])
                value = None
                if samples.size:
                    value = max(1.0, float(np.rint(np.median(samples))))
                elif w == 1:
                    value = max(1.0, float(np.rint(rough[t, s])))
                if value is not None:
                    new_dur[t, s, w], new_cnt[t, s, w] = value, 1
                waves[w].append(value)
        n = int(ns[t])
        tables[t] = {"adj": adj[t, :n, :n], "num_tasks": num_tasks[t, :n],
                     "rough": rough[t]}
        durations[t] = dict(zip(("fresh", "first", "rest"), waves))
    fixed = bank.replace(
        dur=jnp.asarray(new_dur, jnp.float32), cnt=jnp.asarray(new_cnt),
        level_present=jnp.asarray(
            np.broadcast_to(new_cnt[:, :, 1, :1] > 0,
                            bank.level_present.shape)),
        dur_scale=None)
    return fixed, tables, durations


def job_sequences(sweep, params, bank, lane_key, ordinals) -> list[dict]:
    """The job sequences the program's seed law gives a lane, as the
    plain simulator takes them (`stream_np.replay`'s `jobs`)."""
    import jax

    out = []
    for k in ordinals:
        st = jax.device_get(
            sweep.episode_state(params, bank, lane_key, np.int32(k)))
        n = int(st.num_jobs)
        out.append({"arrivals": list(zip(
            st.job_arrival_time[:n].tolist(), st.job_template[:n].tolist())),
            "time_limit": float(st.time_limit)})
    return out


def simulated_over(params, tables, durations, jobs: list[dict], rows: int,
                   dynamic_partition: bool = True) -> list[dict]:
    """`rows` decisions of a lane that runs the job sequences `jobs` one
    after another, by the plain simulator under the plain fair policy."""
    return sweep_np.simulate(
        jobs, tables, durations,
        lambda **obs: fair_np.fair(
            **obs, num_executors=params.num_executors,
            dynamic_partition=dynamic_partition),
        rows, num_executors=params.num_executors, max_jobs=params.max_jobs,
        max_stages=params.max_stages, moving_delay=params.moving_delay,
        warmup_delay=params.warmup_delay)


def simulated(sweep, params, bank, tables, durations, lane_key, rows: int,
              ordinals=range(2), dynamic_partition: bool = True
              ) -> list[dict]:
    """`rows` decisions of the lane with the base key `lane_key` by the
    plain simulator under the plain fair policy."""
    return simulated_over(
        params, tables, durations,
        job_sequences(sweep, params, bank, lane_key, ordinals), rows,
        dynamic_partition)


ROW_FIELDS = 7  # valid, time, job, stage, executors, end flag, ordinal
RESULT_FIELDS = 4  # average JCT, jobs completed, makespan, decisions


def rows_differ(rec: dict, lane: int, want: list[dict], start: int = 0
                ) -> int:
    """The fields in which the rows of a lane's record part from the
    simulator's rows `start` onward; a row the simulator lacks counts
    whole."""
    have = want[start:start + len(rec["valid"])]
    return ROW_FIELDS * (len(rec["valid"]) - len(have)) + sum(
        int(not rec["valid"][t, lane])
        + (abs(float(rec["wall_time"][t, lane]) - row["time"]) > 1e-3)
        + (int(rec["job"][t, lane]) != row["job"])
        + (int(rec["stage"][t, lane]) != row["stage"])
        + (int(rec["num_exec"][t, lane]) != row["num_exec"])
        + (bool(rec["reset"][t, lane]) != row["reset"])
        + (int(rec["ordinal"][t, lane]) != row["ordinal"])
        for t, row in enumerate(have))


def results_differ(rec: dict, lane: int, want: list[dict], start: int,
                   rel: float) -> tuple[int, int, float]:
    """On the rows (from `start` onward) in which the simulator ends an
    episode: the fields of the result the lane's record stores there
    that part from the simulator's (the counts exactly; the makespan
    and the average job completion time, float32 in the program,
    within `rel` of the simulator's), the ends compared, and the
    largest relative gap of an average job completion time."""
    differ, ends, worst = 0, 0, 0.0
    for t, row in enumerate(want[start:start + len(rec["valid"])]):
        if "result" not in row:
            continue
        res, ends = row["result"], ends + 1
        gaps = {k: abs(float(rec[k][t, lane]) - res[k]) / abs(res[k])
                for k in ("makespan", "avg_jct")}
        worst = max(worst, gaps["avg_jct"])
        differ += sum(int(rec[k][t, lane]) != res[k]
                      for k in ("jobs_completed", "decisions")) + sum(
            gap > rel for gap in gaps.values())
    return differ, ends, worst


def engine_checks(ctx: dict) -> list[dict]:
    """(vii), and (ii)'s stored result, through the compiled programs
    the run timed (module docstring): the stagger once more over the
    collapsed bank (encoded as the run's bank is: a lower-precision
    control re-encodes it), its lanes under the source block's ids, one
    chunk of the timed program over it, and `limits.engine_source_lanes`
    source lanes whose first episode ended inside the stagger against
    the plain simulator under the plain fair policy."""
    import jax

    from sparksched_tpu.workload import make_workload_bank, quantize_bank

    sweep, params = ctx["sweep"], ctx["params"]
    limits = ctx["cell"]["config_data"]["limits"]
    env_cfg = ctx["cfg"]["env"]
    plain = make_workload_bank(
        params.num_executors, params.max_stages,
        **{k: v for k, v in env_cfg.items()
           if k in ("data_dir", "bucket_size", "data_sampler_cls")})
    fixed, tables, durations = fixed_durations(plain)
    program_bank = fixed
    if env_cfg.get("bank_dtype"):
        program_bank = quantize_bank(fixed, env_cfg["bank_dtype"])
    programs = sweep.sweep_chunk._cache_size()
    carry, _, recs, per = staggered(ctx, program_bank)
    recs = [record_arrays(r) for r in jax.device_get(recs)]
    source = {k: np.concatenate([r[k] for r in recs]) for k in recs[0]}
    keys = np.asarray(carry.key[:BLOCK])
    ended = np.flatnonzero(source["reset"].any(axis=0))
    chosen = [int(b) for b in ended[:int(limits["engine_source_lanes"])]]
    jobs = {b: job_sequences(
        sweep, params, fixed, keys[b],
        range(int(source["reset"][:, b].sum())
              + ctx["rows"] // params.max_jobs + 2)) for b in chosen}
    # the device runs the chunk while the host simulates
    _, rec, tm = _chunk(ctx, carry, ctx["rows"], bank=program_bank)
    del carry
    t0 = time.perf_counter()
    want = {b: simulated_over(
        params, tables, durations, jobs[b], len(source["valid"]) + ctx["rows"],
        ctx["scheduler"].dynamic_partition) for b in chosen}
    reference_s = time.perf_counter() - t0
    timed = record_arrays(rec)
    compiled = sweep.sweep_chunk._cache_size() - programs
    rel = float(limits["result_rel_tol"])
    differ = {"source": 0, "timed": 0}
    ends = {"source": 0, "timed": 0}
    worst = 0.0
    for b in chosen:
        at = [("source", source, b, 0)] + [
            ("timed", timed, p * BLOCK + b, per * (p + 1))
            for p in range(len(recs))]
        for name, got, lane, start in at:
            unequal, n, gap = results_differ(
                got, lane, want[b], start, rel)
            differ[name] += rows_differ(got, lane, want[b], start) + unequal
            ends[name] += n
            worst = max(worst, gap)
    harness.say(
        engine_source_lanes=chosen, engine_reference_s=reference_s,
        engine_avg_jct_gap_max=worst,
        engine_ends_compared_source=ends["source"],
        engine_fields_compared_source=len(chosen) * ROW_FIELDS * len(
            source["valid"]) + RESULT_FIELDS * ends["source"],
        engine_fields_compared=len(chosen) * len(recs) * ROW_FIELDS * ctx[
            "rows"] + RESULT_FIELDS * ends["timed"])
    return [
        harness.check("engine_mismatches", differ["timed"], 0, "=="),
        harness.check("engine_mismatches_source", differ["source"], 0, "=="),
        harness.check("engine_ends_compared", ends["timed"],
                      int(limits["engine_ends_compared"]), ">="),
        harness.check("engine_programs_compiled", compiled, 0, "=="),
        harness.check("engine_health_mask",
                      sweep.summarize(tm)["health_mask"], 0, "=="),
    ]


def verify(ctx: dict, window: dict) -> list[dict]:
    conf, mix = ctx["cell"]["config_data"], ctx["cell"]["mix"]
    limits = conf["limits"]
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
            float(limits["episodes_finished_share"]), ">="),
    ]
    checks += guarantee_checks(
        rec, ctx["carry"], ctx["staggered_ordinal"], telemetry[-1],
        ctx["params"].max_jobs)
    s_cap = ctx["params"].max_stages
    row0 = (rec["valid"][0],
            np.where(rec["job"][0] >= 0,
                     rec["job"][0] * s_cap + rec["stage"][0], -1),
            rec["num_exec"][0])
    checks += [
        harness.check("policy_mismatches_recorded", policy_mismatches(
            ctx, ctx["handed"], row0), 0, "=="),
        harness.check("policy_mismatches_returned", policy_mismatches(
            ctx, ctx["carry"]), 0, "=="),
    ]
    # the window's carries go before the comparison's come: the timed
    # program's temporaries leave room for two carries of this size
    ctx["handed"] = ctx["carry"] = ctx["last"] = None
    return checks + engine_checks(ctx)


def close(ctx: dict) -> None:
    pass
