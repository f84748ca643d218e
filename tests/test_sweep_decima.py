"""The sweep loop (`sparksched_tpu/sweep.py`) with the Decima net in the
row, at 3 executors x 6 jobs over the fixed-duration bank of
`test_sweep.py`, 256 lanes in two blocks of 128, seeded random weights:

(a) the row evaluated block by block (observe, policy, decide and drain
    in ONE loop over the blocks) gives the decisions, log-probabilities,
    records and carry of the row evaluated over the whole batch, lane
    for lane, under the same per-lane keys;
(b) every recorded decision's `lgprob` is the plain net's
    (`decima_np.score_action`) on the observation it was taken on,
    within float32 rounding, and a net computed in bfloat16 is not;
(c) under fixed durations the rows' times, end flags, ordinals and
    results equal the plain event heap's REPLAY of the recorded
    decisions (`sweep_replay_np.Lane`), through an episode's end and its
    re-seed, and from a copy of the lane taken mid-way; a doctored
    decision does not replay;
(d) the scheduler `sweep.from_config` builds scans the bank's depth and
    gives the scores of the one that scans every level;
(e) the static facts: at 1,024 lanes of the deployment's 10 x 50 the
    chunk holds ONE net inside one loop over 8 blocks and no float32
    operand of all lanes' nodes under `sweep/policy`; a heuristic's
    record and telemetry hold no `lgprob` and no `nodes_present_sum`;
    the counter counts the active nodes of each decision's observation.
"""

import functools
import hashlib
import os.path as osp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import sweep_chunks, sweep_decima
from benchmarks.reference import decima_np, sweep_replay_np
from sparksched_tpu import config, sweep
from sparksched_tpu.analysis.jaxpr_audit import _sub_jaxprs, iter_eqns
from sparksched_tpu.config import EnvParams
from sparksched_tpu.env.observe import observe
from sparksched_tpu.schedulers import DecimaScheduler, RoundRobinScheduler
from sparksched_tpu.workload import bank_depth

from .test_stream_replay import EXECUTORS, JOBS, MOVING, WARMUP
from .test_stream_replay import _bank as fixed_bank
from .test_stream_replay import _templates as fixed_templates

ROOT = osp.dirname(osp.dirname(osp.abspath(__file__)))
LANES, ROWS, CHUNKS = 256, 16, 6
KEY, RUN = jax.random.PRNGKey(49), jax.random.PRNGKey(50)
SLOPE = 0.2
NET = {"gnn_mlp_kwargs": {"hid_dims": [32, 16], "act_cls": "LeakyReLU",
                          "act_kwargs": {"negative_slope": SLOPE}},
       "policy_mlp_kwargs": {"hid_dims": [64, 64], "act_cls": "Tanh"}}


@pytest.fixture(scope="module")
def small():
    """The 3 x 6 cluster over the fixed-duration bank: parameters, bank,
    a Decima scheduler of seeded random weights (its level scan bounded
    by the bank's depth) and the plain simulator's tables."""
    templates = fixed_templates()
    max_stages = max(len(t["num_tasks"]) for t in templates)
    params = EnvParams(
        num_executors=EXECUTORS, max_jobs=JOBS, max_stages=max_stages,
        max_levels=max_stages, moving_delay=MOVING, warmup_delay=WARMUP)
    bank = fixed_bank(templates, max_stages)
    rough = np.asarray(bank.rough_duration)
    tables = {t: {"adj": tpl["adj"], "num_tasks": tpl["num_tasks"],
                  "rough": rough[t]} for t, tpl in enumerate(templates)}
    durations = {t: {w: tpl[w] for w in ("fresh", "first", "rest")}
                 for t, tpl in enumerate(templates)}
    sched = DecimaScheduler(
        EXECUTORS, seed=7, num_levels=bank_depth(bank), **NET)
    return params, bank, sched, tables, durations


def chunks_of(small, chunk, carry):
    params, bank, sched, _, _ = small
    carries, recs, tms = [carry], [], []
    for i in range(CHUNKS):
        carry, rec, tm = chunk(
            params, bank, sched.batch_policy, carry,
            jax.random.fold_in(RUN, i), ROWS, sched.params)
        carries.append(carry)
        recs.append(jax.device_get(rec))
        tms.append(tm)
    return carries, recs, tms


@pytest.fixture(scope="module")
def swept(small):
    """`CHUNKS` chunks of `ROWS` rows over `LANES` lanes from reset, the
    row in two blocks: the carries (the first the one handed in), the
    records, the telemetry."""
    params, bank, _, _, _ = small
    return chunks_of(small, sweep.sweep_chunk,
                     sweep.init(params, bank, KEY, LANES))


def leaves_equal(a, b) -> bool:
    def host(x):
        if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            x = jax.random.key_data(x)
        return np.asarray(x)

    a, b = (jax.tree_util.tree_leaves(t) for t in (a, b))
    return len(a) == len(b) and all(
        np.array_equal(host(x), host(y)) for x, y in zip(a, b))


# -- (a) block by block is the whole batch ----------------------------------


def test_the_blocked_row_equals_the_whole_batch_row(small, swept,
                                                    monkeypatch):
    """`_DRAIN_BLOCK` at the lane count makes the same lanes one block:
    the row's body once over all of them. A function and `jit` of its
    own: the constant is no part of a `jit` key."""
    carries, recs, tms = swept
    monkeypatch.setattr(sweep, "_DRAIN_BLOCK", LANES)
    whole = jax.jit(lambda *a: sweep._chunk(*a), static_argnums=(0, 2, 5))
    carry, rec, tm = chunks_of(small, whole, carries[0])
    assert leaves_equal(rec[0], recs[0]) and leaves_equal(rec[-1], recs[-1])
    assert rec[0].lgprob.shape == (ROWS, LANES) and rec[0].valid.all()
    assert leaves_equal(carry[-1], carries[-1])
    # the lanes counted the same; only what a block's `while` ran (its
    # bodies, the reset program where a lane of the BLOCK ended) and
    # the reductions over all lanes differ
    a, b = sweep.summarize(tms[-1]), sweep.summarize(tm[-1])
    for s in (a, b):
        s.pop("reset_evals_total")
        for k in ("drain_batch_iters", "lane_syncs",
                  "drain_lane_iters_executed"):
            s["row"].pop(k)
    assert a == b and a["health_mask"] == 0
    assert a["nodes_present_total"] > a["jobs_present_total"] > 0


def test_a_lanes_draw_does_not_depend_on_the_lanes_beside_it(small, swept):
    """A carry of the first block alone, under the row's key split over
    ALL the lanes as the whole batch splits it, decides as those lanes
    do in the batch: `batch_policy` under one key a lane."""
    params, _, sched, _, _ = small
    carries, recs, _ = swept
    env = jax.tree_util.tree_map(lambda a: a[:128], carries[0].ls.env)
    obs = jax.vmap(lambda e: observe(params, e))(env)
    _, k_pol, _ = jax.random.split(jax.random.fold_in(RUN, 0), 3)
    keys = jax.random.split(k_pol, LANES)[:128]
    stage_idx, num_exec, aux = jax.jit(sched.batch_policy)(keys, obs)
    s_cap = params.max_stages
    assert np.array_equal(
        stage_idx, recs[0].job[0, :128] * s_cap + recs[0].stage[0, :128])
    assert np.array_equal(num_exec, recs[0].num_exec[0, :128])
    assert np.array_equal(aux["lgprob"], recs[0].lgprob[0, :128])
    # one key for the stack is split the same way
    one = jax.jit(sched.batch_policy)(k_pol, jax.vmap(
        lambda e: observe(params, e))(carries[0].ls.env))
    assert np.array_equal(one[0][:128], stage_idx)


# -- (b) the recorded decision against the plain net ------------------------


def score(small, obs, lane, stage_idx, k, matmul="float32"):
    params, _, sched, _, _ = small
    o = jax.tree_util.tree_map(lambda a: np.asarray(a[lane]), obs)
    return decima_np.score_action(
        jax.tree_util.tree_map(np.asarray, sched.params),
        decima_np.obs_arrays(o), int(stage_idx), int(k),
        params.num_executors, gnn_slope=SLOPE, matmul=matmul)["lgprob"]


def test_the_recorded_log_probability_is_the_plain_nets(small, swept):
    params, _, sched, _, _ = small
    carries, recs, _ = swept
    s_cap, lanes = params.max_stages, range(0, LANES, 8)
    gaps = []
    for c in (0, 3):  # from reset, and rows into the episodes
        obs = jax.device_get(jax.vmap(lambda e: observe(params, e))(
            carries[c].ls.env))
        rec = recs[c]
        gaps += [abs(float(rec.lgprob[0, b]) - score(
            small, obs, b, rec.job[0, b] * s_cap + rec.stage[0, b],
            rec.num_exec[0, b] - 1)) for b in lanes]
    assert max(gaps) < 2e-5, max(gaps)
    # the same weights computed in bfloat16: its own decisions' stated
    # log-probabilities part from the plain net's by a hundred times that
    low = DecimaScheduler(EXECUTORS, seed=7, compute_dtype="bfloat16",
                          num_levels=sched.net.num_levels, **NET)
    stage_idx, num_exec, aux = jax.device_get(
        jax.jit(low.batch_policy)(KEY, obs, sched.params))
    low_gaps = [abs(float(aux["lgprob"][b]) - score(
        small, obs, b, stage_idx[b], num_exec[b] - 1)) for b in lanes]
    assert np.mean(low_gaps) > 1e-3 > 50 * np.mean(gaps)


# -- (c) the engine under recorded decisions --------------------------------


def plain_lane(small, lane_key):
    params, bank, _, tables, durations = small
    return sweep_replay_np.Lane(
        sweep_chunks.job_sequences(sweep, params, bank, lane_key, range(6)),
        tables, durations, num_executors=params.num_executors,
        max_jobs=params.max_jobs, max_stages=params.max_stages,
        moving_delay=params.moving_delay, warmup_delay=params.warmup_delay)


def arrays(recs) -> dict:
    recs = [sweep_chunks.record_arrays(r) for r in recs]
    return {k: np.concatenate([r[k] for r in recs]) for k in recs[0]}


@pytest.mark.parametrize("lane", [0, 77, 130, 255])
def test_the_rows_equal_the_replay_of_the_recorded_decisions(
        small, swept, lane):
    carries, recs, _ = swept
    rec = arrays(recs)
    assert rec["reset"][:, lane].any()  # an end and a re-seed inside
    plain = plain_lane(small, np.asarray(carries[0].key[lane]))
    want = plain.replay(sweep_decima.actions_of(rec, lane))
    assert len(want) == CHUNKS * ROWS and all(r["taken"] for r in want)
    assert sweep_decima.rows_differ(rec, lane, want) == 0
    differ, ends, worst = sweep_chunks.results_differ(
        rec, lane, want, 0, 1e-5)
    assert differ == 0 and ends == rec["reset"][:, lane].sum() >= 1
    assert worst < 1e-6
    assert rec["ordinal"][-1, lane] == ends  # the re-seeded episode's rows


def test_a_copy_of_the_plain_lane_goes_on_from_where_it_was_taken(
        small, swept):
    carries, recs, _ = swept
    lane, at = 130, 2  # the copy after two chunks replays the other four
    plain = plain_lane(small, np.asarray(carries[0].key[lane]))
    plain.replay(sweep_decima.actions_of(arrays(recs[:at]), lane))
    twin, rest = plain.copy(), arrays(recs[at:])
    want = twin.replay(sweep_decima.actions_of(rest, lane))
    assert sweep_decima.rows_differ(rest, lane, want) == 0
    assert sweep_chunks.results_differ(rest, lane, want, 0, 1e-5)[:2] == (
        0, rest["reset"][:, lane].sum())
    # the lane the copy was taken from stayed where it was
    assert plain.ordinal == 0 and plain.taken == at * ROWS
    assert sweep_decima.rows_differ(rest, lane, plain.replay(
        sweep_decima.actions_of(rest, lane))) == 0


@pytest.mark.parametrize("lane", [0, 130])
def test_the_plain_lane_observes_what_the_program_observes(
        small, swept, lane):
    """After every chunk of the replay (an end and a re-seed among
    them) the plain heap's own observation is the program's of the
    carry that chunk returned, field for field; one flipped bit is one
    field."""
    params = small[0]
    carries, recs, _ = swept
    plain = plain_lane(small, np.asarray(carries[0].key[lane]))
    ctx = {"params": params}
    for carry, rec in zip(carries[1:], recs):
        plain.replay(sweep_decima.actions_of(
            sweep_chunks.record_arrays(rec), lane))
        stored = sweep_decima.stored_observation(
            ctx, carry.ls.env, [lane])
        assert sweep_decima.observation_differs(
            stored, 0, plain.ep.observe()) == 0
    assert stored.job_mask.any() and plain.ordinal >= 1
    off = stored.replace(schedulable=~np.asarray(stored.schedulable))
    assert sweep_decima.observation_differs(off, 0, plain.ep.observe()) == 1
    other = plain_lane(small, np.asarray(carries[0].key[lane + 1]))
    assert sweep_decima.observation_differs(
        stored, 0, other.ep.observe()) > 1


@pytest.mark.parametrize("fault", ["stage", "time", "result"])
def test_a_doctored_record_does_not_replay(small, swept, fault):
    carries, recs, _ = swept
    lane = 0
    rec = {k: v.copy() for k, v in arrays(recs).items()}
    end = int(np.flatnonzero(rec["reset"][:, lane])[0])
    if fault == "time":
        rec["wall_time"][5, lane] += 1.0
    elif fault == "result":
        rec["avg_jct"][end, lane] *= 1.001
    else:  # two decisions of a round the other way round
        for k in ("job", "stage", "num_exec"):
            rec[k][[4, 5], lane] = rec[k][[5, 4], lane]
    want = plain_lane(small, np.asarray(carries[0].key[lane])).replay(
        sweep_decima.actions_of(rec, lane))
    differ = sweep_decima.rows_differ(rec, lane, want) + (
        sweep_chunks.results_differ(rec, lane, want, 0, 1e-5)[0])
    assert differ > 0


# -- (d) the level scan bounded outside the trainer -------------------------


def scan_lengths(fn, *args) -> list[int]:
    return [e.params["length"]
            for e in iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "scan"]


def demo_cfg(**agent) -> dict:
    cfg = config.load(osp.join(ROOT, "config", "sweep_decima_demo.yaml"))
    cfg["env"] |= {"num_executors": 3, "job_arrival_cap": 6}
    cfg["agent"] |= agent
    return cfg


def test_from_config_bounds_the_level_scan_by_the_banks_depth():
    params, bank, sched = sweep.from_config(demo_cfg())
    depth = bank_depth(bank)
    assert 1 < depth < bank.max_stages and sched.net.num_levels == depth
    every = sweep.from_config(demo_cfg(num_levels=0))[2]  # stated: it wins
    assert every.net.num_levels == 0
    carry = sweep.init(params, bank, KEY, 8)
    for _ in range(3):
        carry, _, _ = sweep.sweep_chunk(
            params, bank, RoundRobinScheduler(3).batch_policy, carry, KEY, 8)
    f = jax.vmap(sched.features)(
        jax.vmap(lambda e: observe(params, e))(carry.ls.env))
    assert bool(f.adj.any())
    bounded, free = (s.net.apply(sched.params, f) for s in (sched, every))
    assert leaves_equal(bounded, free)
    lengths = [scan_lengths(lambda p, ff: s.net.apply(p, ff), sched.params,
                            f) for s in (sched, every)]
    assert lengths == [[depth - 1], [bank.max_stages - 1]]


# -- (e) the static facts ---------------------------------------------------


def scans_around(jaxpr, wanted, around=()):
    """The lengths of the scans around each equation `wanted` accepts,
    outermost first."""
    for eqn in jaxpr.eqns:
        if wanted(eqn):
            yield around
        inner = around + ((eqn.params["length"],)
                          if eqn.primitive.name == "scan" else ())
        for sub in _sub_jaxprs(eqn):
            yield from scans_around(sub, wanted, inner)


@functools.cache
def demo_chunk_jaxpr(lanes: int = 1024, rows: int = 4):
    """The chunk traced (not compiled) at the deployment's 10 x 50."""
    cfg = config.load(osp.join(ROOT, "config", "sweep_decima_demo.yaml"))
    params, bank, sched = sweep.from_config(cfg)
    carry = jax.eval_shape(lambda: sweep.init(params, bank, KEY, lanes))
    return params, bank, jax.make_jaxpr(
        lambda b, c, k, w: sweep._chunk(
            params, b, sched.batch_policy, c, k, rows, w))(
        bank, carry, KEY, sched.params).jaxpr


def test_one_net_inside_one_loop_over_the_blocks_at_1024_lanes():
    params, bank, jaxpr = demo_chunk_jaxpr()
    assert (params.num_executors, params.max_jobs) == (10, 50)

    def level_scan(eqn):
        return (eqn.primitive.name == "scan"
                and "decima/gnn/levels" in str(eqn.source_info.name_stack))

    # the rows' scan, the loop over 8 blocks, and in it the one net
    assert list(scans_around(jaxpr, level_scan)) == [(4, 8)]
    (levels,) = [e for e in iter_eqns(jaxpr) if level_scan(e)]
    assert levels.params["length"] == bank_depth(bank) - 1
    under_policy = [e for e in iter_eqns(jaxpr)
                    if "sweep/policy" in str(e.source_info.name_stack)]
    assert len(under_policy) > 200
    for scope in ("decima/features", "decima/gnn", "decima/sample"):
        inside = [e for e in iter_eqns(jaxpr)
                  if scope in str(e.source_info.name_stack)]
        assert inside and all(
            "sweep/policy" in str(e.source_info.name_stack) for e in inside)
    wide = [v.aval.shape for e in under_policy
            for v in list(e.invars) + list(e.outvars)
            if hasattr(v, "aval") and getattr(v.aval, "shape", ())[:1] == (
                1024,) and len(v.aval.shape) > 1]
    assert wide == []  # nothing of all lanes' nodes: a block's, 128 wide
    blocks = [v.aval.shape for e in under_policy for v in e.outvars
              if getattr(v.aval, "shape", ())[:3] == (128, 50, 20)]
    assert blocks


def test_a_heuristics_blocked_row_equals_its_whole_batch_row(monkeypatch):
    """Under the fair policy (no weights: the drain alone by blocks)
    two blocks of 128 lanes decide and drain as the whole batch does,
    lane for lane."""
    cfg = config.load(osp.join(ROOT, "config", "sweep_fair_demo.yaml"))
    cfg["env"] |= {"num_executors": 3, "job_arrival_cap": 6}
    params, bank, sched = sweep.from_config(cfg)
    carry = sweep.init(params, bank, KEY, LANES)
    blocked = sweep.sweep_chunk(
        params, bank, sched.batch_policy, carry, RUN, 2 * ROWS)
    monkeypatch.setattr(sweep, "_DRAIN_BLOCK", LANES)
    whole = jax.jit(lambda *a: sweep._chunk(*a), static_argnums=(0, 2, 5))(
        params, bank, sched.batch_policy, carry, RUN, 2 * ROWS)
    assert leaves_equal(blocked[:2], whole[:2])
    assert blocked[1].valid.all() and blocked[1].reset.any()


# sha256 of the lowered text of `sweep_chunk` under
# `config/sweep_fair_demo.yaml` at 1,024 lanes x 16 rows, taken by this
# function's lines. At the parent commit of PR 49 (73a56a0) it was
# 214f6dd2... of 1,643,269 characters, and PR 49's own tree gave the
# same: a heuristic's row is the one it had. A PR that changes the
# engine or a heuristic's row on purpose takes it again from its own
# tree and says so: PR 50 (the duration sampler reads the stage's word
# of `EnvState.duration_facts`) took 1a2764b3... of 1,642,076, and
# PR 51 (the engine picks what it read of a lane's state at one index
# with the one-hot its writes use, `core._pick`; the engine's change,
# the row's order as it was) took this one.
FAIR_CHUNK_AT_PARENT = (
    "0fcdf0e295e11877a528885d475b7cba91f378985f5f6400fb52586c7013460e")


def test_a_heuristics_chunk_lowers_to_the_parents_text():
    """`sweep_fair`'s program is the parent's to the byte: a policy
    that comes without weights keeps the row it had (observe, policy
    and decide once over all lanes, the drain block by block), since
    with a block's whole row in the loop the chunk does not load at
    that cell's 26,624 lanes (PERF.md, PR 49)."""
    params, bank, sched = sweep.from_config(
        config.load(osp.join(ROOT, "config", "sweep_fair_demo.yaml")))
    carry = jax.eval_shape(lambda: sweep.init(params, bank, KEY, 1024))
    text = sweep.sweep_chunk.lower(
        params, bank, sched.batch_policy, carry, KEY, 16).as_text()
    assert len(text) == 1535622
    assert hashlib.sha256(text.encode()).hexdigest() == FAIR_CHUNK_AT_PARENT


def test_a_net_without_its_weights_is_refused(small):
    """A policy that states a log-probability is a net, and its
    parameters are the chunk's argument: as closure constants every
    checkpoint would be a program of its own."""
    params, bank, sched, _, _ = small
    carry = jax.eval_shape(lambda: sweep.init(params, bank, KEY, 4))
    with pytest.raises(ValueError, match="weights"):
        jax.eval_shape(lambda b, c, k: sweep._chunk(
            params, b, sched.batch_policy, c, k, ROWS), bank, carry, KEY)


def test_a_heuristics_chunk_holds_no_log_probability_and_no_node_count():
    cfg = config.load(osp.join(ROOT, "config", "sweep_fair_demo.yaml"))
    cfg["env"] |= {"num_executors": 3, "job_arrival_cap": 6}
    params, bank, sched = sweep.from_config(cfg)
    carry = jax.eval_shape(lambda: sweep.init(params, bank, KEY, LANES))
    _, rec, tm = jax.eval_shape(
        lambda b, c, k: sweep._chunk(
            params, b, sched.batch_policy, c, k, ROWS), bank, carry, KEY)
    assert rec.lgprob is None and tm.nodes_present_sum is None
    assert len(jax.tree_util.tree_leaves(rec)) == 11
    assert tm.jobs_present_sum.shape == (LANES,)


def test_the_node_counter_counts_the_active_nodes_a_decision_saw(small):
    """Four lanes, a row a call: the call's `nodes_present_total` is the
    active nodes in the observations of the carry it was handed."""
    params, bank, sched, _, _ = small
    carry = sweep.init(params, bank, KEY, 4)
    for i in range(12):
        obs = jax.vmap(lambda e: observe(params, e))(carry.ls.env)
        carry, rec, tm = sweep.sweep_chunk(
            params, bank, sched.batch_policy, carry,
            jax.random.fold_in(RUN, i), 1, sched.params)
        summary = sweep.summarize(tm)
        assert rec.valid.all()
        assert summary["nodes_present_total"] == int(obs.node_mask.sum())
        assert summary["jobs_present_total"] == int(obs.job_mask.sum())
    assert summary["nodes_present_per_decision"] == pytest.approx(
        int(obs.node_mask.sum()) / 4, abs=1e-3)


# -- the normal path --------------------------------------------------------


def test_the_command_line_sweeps_the_demo_yaml_under_decima(
        tmp_path, capsys):
    """`python sweep.py -f config/sweep_decima_demo.yaml`, cut for the
    CPU (3 executors, 6 jobs, 256 lanes in two blocks): through
    `sweep.run`, the weights an argument, a mean average job completion
    time printed, `health_mask` 0, one line an episode written."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "sweep_cli", osp.join(ROOT, "sweep.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    out = str(tmp_path / "episodes.csv")
    cfg = demo_cfg()
    cfg["sweep"] |= {"episodes": 300, "lanes": 256, "rows_per_chunk": 16,
                     "out": out}
    res = cli.main(cfg)
    said = capsys.readouterr().out
    assert "Decima: mean avg job completion time" in said
    assert "over 300 episodes" in said and "health_mask 0" in said
    assert (res["jobs_completed"] == 6).all() and res["mean_avg_jct"] > 0
    assert res["telemetry"]["nodes_present_per_decision"] > 1
    assert len(open(out).read().splitlines()) == 301
