"""Static-analysis subsystem (sparksched_tpu/analysis): the tier-1
clean-tree run, a seeded-violation fixture per rule (every rule has a
pinned true positive — a rule that cannot fire is worse than no rule),
and the contract checker's runtime-assert mode around real episodes on
both engines."""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import textwrap

import pytest


# ---------------------------------------------------------------------------
# the analyzer is the CI gate: the shipped tree must be clean
# ---------------------------------------------------------------------------


def test_shipped_tree_is_analysis_clean():
    from sparksched_tpu.analysis import DEFAULT_PASSES, run_all
    from sparksched_tpu.analysis.jaxpr_audit import (
        BATCH_LANE_PROGRAMS,
        LANE_PROGRAMS,
    )

    report = run_all(DEFAULT_PASSES)
    assert report["clean"], "\n".join(
        f"[{v['passname']}/{v['rule']}] {v['where']}: {v['detail']}"
        for v in report["violations"]
    )
    # >= 8 rules across the passes is the subsystem's acceptance bar;
    # the registry traced every hot program — in BOTH registry passes
    # (the memory pass shares the unbatched traces via the cache, so
    # the two can never audit different programs under one name)
    all_programs = {
        "observe", "micro_step", "decide_micro_step",
        "drain_to_decision", "decima_score", "decima_batch_policy",
        "ppo_update", "flat_collect_batch",
        # ISSUE 9: the `health:`-on production programs, budgeted
        # separately so the sentinel cost is capped while the
        # default-off programs above pin that health off changes
        # nothing
        "ppo_update_health", "flat_collect_batch_health",
        # ISSUE 10: the AOT decision-serving programs (serve/aot.py),
        # audited exactly as the session store lowers them
        "serve_decide", "serve_decide_batch",
        # ISSUE 13: the dp-sharded store variant (the sharding
        # constraints are part of the traced program, so the audited
        # jaxpr IS the sharded configuration)
        "serve_decide_batch_sharded",
        # ISSUE 14: the record-on serve variants (the online loop's
        # actor path), budgeted separately so the recording cost is
        # capped while the record-off programs above pin that record
        # off changes nothing
        "serve_decide_record", "serve_decide_batch_record",
        # ISSUE 15: the group-shaped store program (the pipelined
        # store's [hot_capacity/groups] lowering) — pinned
        # count-identical to serve_decide_batch: slot groups are
        # host-side call routing, never traced structure
        "serve_decide_batch_group",
        # ISSUE 18: the ring-record serve variants (the zero-sync
        # record path) — the trajectory ring rides the donated args,
        # so the budgets cap the append at a masked scatter per
        # RingRec leaf while the record-off programs above pin that
        # ring off changes nothing
        "serve_decide_record_ring", "serve_decide_batch_record_ring",
        # PR 46: the sweep loop's chunk (sweep.py), the third caller
        # of the engine's decide and drain, with the re-seed and an
        # episode's result inside the scan
        "sweep_chunk",
    }
    assert set(report["passes"]["jaxpr"]["measured"]) == all_programs
    mem = report["passes"]["memory"]["measured"]
    assert set(mem) == all_programs
    # every lane program — vmapped AND native-batch (the sharded
    # single-eval collector, ISSUE 6) — carries a lane-fit verdict,
    # and the shipped (post-81e77fb) engine fits the full 1024-lane
    # production width under the default 17.2 GB budget
    for name in LANE_PROGRAMS:
        assert mem[name]["lane_fit"]["max_lanes_fit"] >= 1024, name
    # the Decima collector's verdict is the model's largest equation:
    # since PR 33 the select that feeds the children's message sum,
    # whose [B,J,S,S,D] operands a jaxpr holds as buffers (17.36 GB at
    # 1024 lanes, over by 1%) and the compiler fuses into the reduce
    # (tests/test_tpu_compile.py bounds the compiled net's temporaries)
    for name in BATCH_LANE_PROGRAMS:
        assert mem[name]["lane_fit"]["max_lanes_fit"] >= 512, name


def test_cli_json_and_exit_code():
    """The CLI contract: JSON on stdout, exit 0 on a clean tree. Runs
    the cheap passes only — the full jaxpr audit already runs
    in-process above, and a subprocess re-trace would double tier-1's
    trace bill for no new signal. The AST passes (lint, coverage,
    concurrency) are all cheap, so the gate runs all three."""
    r = subprocess.run(
        [sys.executable, "-m", "sparksched_tpu.analysis",
         "--passes", "lint,coverage,concurrency,contracts", "--quiet"],
        capture_output=True, timeout=600,
        cwd=pathlib.Path(__file__).resolve().parent.parent,
    )
    assert r.returncode == 0, r.stdout.decode() + r.stderr.decode()
    report = json.loads(r.stdout)
    assert report["clean"] is True and report["violations"] == []


# ---------------------------------------------------------------------------
# jaxpr rules: seeded violations
# ---------------------------------------------------------------------------


def _audit_one(fn, *args, **budget_kw):
    import jax

    from sparksched_tpu.analysis import jaxpr_audit

    budget = jaxpr_audit.Budget(**({
        "eqn_lo": 0, "eqn_hi": 10**6,
        "gather_hi": 10**6, "scatter_hi": 10**6,
    } | budget_kw))
    jx = jax.make_jaxpr(fn)(*args)
    return jaxpr_audit.audit_closed_jaxpr("fixture", jx, budget)


def _rules(violations):
    return {v.rule for v in violations}


def test_rule_host_callback_fires_and_allowlist_clears():
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.analysis import jaxpr_audit

    def printed(x):
        jax.debug.print("x={x}", x=x)  # `debug_print` since jax 0.9
        return x + 1

    def called(x):
        jax.debug.callback(lambda v: None, x)  # `debug_callback`
        return x + 1

    for bad, prim in ((printed, "debug_print"),
                      (called, "debug_callback")):
        jx = jax.make_jaxpr(bad)(jnp.float32(1.0))
        assert prim in jaxpr_audit.primitive_counts(jx.jaxpr), prim
        vs, measured = _audit_one(bad, jnp.float32(1.0))
        assert "host-callback" in _rules(vs), prim
        # the explicit allowlist (the telemetry-io_callback escape
        # hatch) clears exactly that rule, for exactly that primitive
        vs2, _ = _audit_one(
            bad, jnp.float32(1.0), callback_allow=frozenset({prim}),
        )
        assert "host-callback" not in _rules(vs2), prim
        vs3, _ = _audit_one(
            bad, jnp.float32(1.0),
            callback_allow=frozenset({"io_callback"}),
        )
        assert "host-callback" in _rules(vs3), prim


def test_rule_wide_dtype_fires():
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):
        vs, _ = _audit_one(
            lambda x: x.astype(jnp.float64) * 2.0, jnp.float32(1.0)
        )
    assert "wide-dtype" in _rules(vs)


def test_rule_loop_free_fires():
    import jax.numpy as jnp
    from jax import lax

    def scanny(x):
        return lax.scan(lambda c, _: (c + x, None), 0.0, None, length=4)[0]

    vs, _ = _audit_one(scanny, jnp.float32(1.0), loop_free=True)
    assert "loop-free" in _rules(vs)
    # the same program is fine when not pinned loop-free
    vs2, _ = _audit_one(scanny, jnp.float32(1.0))
    assert "loop-free" not in _rules(vs2)


def test_rule_budget_fires_on_eqn_and_gather_and_scatter():
    import jax.numpy as jnp

    def heavy(x):
        return (x * 2 + 1) * (x - 3)

    vs, measured = _audit_one(heavy, jnp.float32(1.0), eqn_hi=1)
    assert "budget" in _rules(vs) and measured["eqns"] > 1

    def gathery(x, idx):
        return x[idx]

    vs, measured = _audit_one(
        gathery, jnp.zeros(4, jnp.float32), jnp.zeros(2, jnp.int32),
        gather_hi=0,
    )
    assert "budget" in _rules(vs) and measured["gathers"] >= 1

    def scattery(x, idx):
        return x.at[idx].add(1.0)

    vs, measured = _audit_one(
        scattery, jnp.zeros(4, jnp.float32), jnp.zeros(2, jnp.int32),
        scatter_hi=0,
    )
    assert "budget" in _rules(vs) and measured["scatters"] >= 1


def test_rule_loop_whole_read_fires_and_row_reads_clear():
    """Inside a `while`, an equation that computes with a pinned-size
    operand whole is a violation; a row read of it (a dynamic slice, a
    gather), a select that hands it on, and the same equation OUTSIDE
    the loop are not; an inner call or conditional is looked into."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    big = jnp.zeros((6, 5, 5), bool)

    def loop(step):
        def fn(x):
            return lax.while_loop(
                lambda c: c[0] < 3,
                lambda c: (c[0] + 1, step(x, c[0], c[1])),
                (jnp.int32(0), jnp.int32(0)),
            )[1]
        return fn

    def whole(x, i, acc):
        return acc + x.sum()

    def whole_in_a_call(x, i, acc):
        return acc + jax.jit(lambda a: a.astype(jnp.int32).sum())(x)

    def whole_in_a_branch(x, i, acc):
        return lax.cond(
            i > 1, lambda a: acc + a.sum(), lambda a: acc, x
        )

    def by_rows(x, i, acc):
        row = lax.dynamic_index_in_dim(x, i, keepdims=False)
        return acc + row.sum() + x[i, i % 5].sum()

    def handed_on(x, i, acc):
        return acc + jnp.where(i > 1, x, x)[i, 0, 0]

    def outside(x):
        return loop(lambda x_, i, acc: acc + i)(x) + x.sum()

    for step in (whole, whole_in_a_call, whole_in_a_branch):
        vs, _ = _audit_one(loop(step), big, while_whole_read_elems=150)
        assert "loop-whole-read" in _rules(vs), step.__name__
        # a smaller operand than the pin is nobody's business
        vs, _ = _audit_one(loop(step), big, while_whole_read_elems=151)
        assert "loop-whole-read" not in _rules(vs), step.__name__
    for fn in (loop(by_rows), loop(handed_on), outside):
        vs, _ = _audit_one(fn, big, while_whole_read_elems=150)
        assert "loop-whole-read" not in _rules(vs), vs
    # not pinned, not looked for
    vs, _ = _audit_one(loop(whole), big)
    assert "loop-whole-read" not in _rules(vs)


def test_drain_loop_reads_the_adjacency_by_rows_only(monkeypatch):
    """What takes a counter's place for PR 39: no equation inside the
    `while` of `drain_to_decision` (the one-lane program the collectors
    vmap) and of `serve_decide` computes with an operand of the
    adjacency's size, J*S*S elements; the fused pass refreshes
    `unsat_parent_count` against the state's packed parent sets. With
    the refresh as it was, a contraction of the whole adjacency in
    every body, the rule names both programs."""
    import jax.numpy as jnp

    from sparksched_tpu.analysis import jaxpr_audit
    from sparksched_tpu.env import core

    names = ("drain_to_decision", "serve_decide", "micro_step")
    for name in names:
        assert (jaxpr_audit.BUDGETS[name].while_whole_read_elems
                == jaxpr_audit.AUDIT_ADJ_ELEMS)
    vs, measured = jaxpr_audit.audit_all(names=names)
    assert set(measured) == set(names) and not vs, vs

    monkeypatch.setattr(
        core, "_flipped_parents",
        lambda state, delta: jnp.einsum(
            "jp,jpc->jc", delta, state.adj.astype(jnp.int32)
        ),
    )
    vs, _ = jaxpr_audit.audit_all(names=names)
    assert {(v.rule, v.where) for v in vs} == {
        ("loop-whole-read", "drain_to_decision"),
        ("loop-whole-read", "serve_decide"),
    }, vs


def test_row_gathers_finds_a_grid_read_once_an_executor():
    """`jaxpr_audit.row_gathers`: a gather of a grid-sized operand at
    a row of indices an executor is found, at any depth; one element
    of the grid, a row for each executor out of a small operand, and
    a one-hot select-reduce over the same grid are not."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from sparksched_tpu.analysis import jaxpr_audit

    grid = jnp.zeros((6, 5), bool)
    dj, ds = jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32)

    def found(fn, *args):
        return jaxpr_audit.row_gathers(
            jax.make_jaxpr(fn)(*args).jaxpr, elems=30, rows=4
        )

    def per_executor(g, j, s):
        return g[j, s]

    def in_a_loop(g, j, s):
        return lax.fori_loop(
            0, 3, lambda i, acc: acc | g[j, s], jnp.zeros(4, bool)
        )

    def one_element(g, j, s):
        return g[j[0], s[0]]

    def small_operand(g, j, s):
        return g[0][s]

    def one_hot(g, j, s):
        pick = (j[:, None, None] == jnp.arange(6)[None, :, None]) & (
            s[:, None, None] == jnp.arange(5)[None, None, :]
        )
        return (pick & g[None]).any((1, 2))

    assert found(per_executor, grid, dj, ds) == [
        "gather(6, 5) <- (4, 2)"
    ]
    assert len(found(in_a_loop, grid, dj, ds)) == 1
    for fn in (one_element, small_operand, one_hot):
        assert not found(fn, grid, dj, ds), fn.__name__
    # under the thresholds, nobody's business
    jx = jax.make_jaxpr(per_executor)(grid, dj, ds).jaxpr
    assert not jaxpr_audit.row_gathers(jx, elems=31, rows=4)
    assert not jaxpr_audit.row_gathers(jx, elems=30, rows=5)


def test_fused_pass_sets_up_what_its_loop_can_read(monkeypatch):
    """What takes a counter's place for PR 45: the jaxpr of the fused
    bulk pass holds no gather that reads a [J,S]-sized operand once
    for every executor (the arrivals' frontier bits come from the
    packed frontier, `core._frontier_at`), and its one uniform draw is
    `[max_events + N, 2]`, a pair a step. With the lookup as it was,
    an element gather at the executors' destinations, the rule names
    it."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.analysis import jaxpr_audit
    from sparksched_tpu.env import core

    params, bank, state = jaxpr_audit.audit_setup()
    n = params.num_executors
    j_cap, s_cap = state.stage_remaining.shape
    max_events = 8

    def traced():
        return jax.make_jaxpr(lambda st: core._bulk_events_fused(
            params, bank, st, True, stop_at_limit=True,
            max_events=max_events,
        ))(state).jaxpr

    jaxpr = traced()
    assert not jaxpr_audit.row_gathers(jaxpr, j_cap * s_cap, n)
    draws = [
        e.params["shape"] for e in jaxpr_audit.iter_eqns(jaxpr)
        if e.primitive.name == "random_bits"
    ]
    assert draws == [(max_events + n, 2)], draws

    def gathered(state, dj, ds):
        return state.frontier[
            jnp.clip(dj, 0, j_cap - 1), jnp.clip(ds, 0, s_cap - 1)
        ]

    monkeypatch.setattr(core, "_frontier_at", gathered)
    assert jaxpr_audit.row_gathers(traced(), j_cap * s_cap, n) == [
        f"gather({j_cap}, {s_cap}) <- ({n}, 2)"
    ]


def test_no_program_reads_a_table_an_executor_count_long(monkeypatch):
    """What takes a counter's place for PR 47: the sampler's
    executor-level interval is computed from `params.num_executors`
    (`sampling.executor_interval`), so the jaxpr of the fused bulk
    pass, and of `sample_task_duration` alone, holds no equation with
    an operand of `num_executors + 1` elements: no gather from such a
    table, no loop that carries one, whether the bank is a constant of
    the program or an argument of it (the sweep's chunk). With the
    sampler that read the bank's four `i32[N+1]` tables
    (`tests/test_bulk_pass_setup.py` keeps it as the reference) the
    rule names four gathers a sampled duration."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.analysis import jaxpr_audit
    from sparksched_tpu.env import core
    from sparksched_tpu.workload import sampling

    from .test_bulk_pass_setup import table_reading_sampler

    params, bank, state = jaxpr_audit.audit_setup()
    n = params.num_executors
    assert not any(
        n + 1 in jnp.shape(leaf) for leaf in jax.tree_util.tree_leaves(bank)
    )

    def traced():
        # functions of its own for each call: `make_jaxpr` keeps what
        # it traced for a function it has seen
        def fused_pass(bank_, st):
            return core._bulk_events_fused(
                params, bank_, st, True, stop_at_limit=True, max_events=8
            )

        def durations(bank_, u2, nl):
            return jax.vmap(lambda k: sampling.sample_task_duration(
                params, bank_, u2, jnp.uint32(0x01010103), jnp.int32(3),
                jnp.int32(1), k, jnp.bool_(True), jnp.bool_(False),
            ))(nl)

        u2, nl = jnp.zeros(2), jnp.arange(7, dtype=jnp.int32)
        return [
            jax.make_jaxpr(lambda st: fused_pass(bank, st))(state).jaxpr,
            jax.make_jaxpr(fused_pass)(bank, state).jaxpr,
            jax.make_jaxpr(lambda *a: durations(bank, *a))(u2, nl).jaxpr,
            jax.make_jaxpr(durations)(bank, u2, nl).jaxpr,
        ]

    for jaxpr in traced():
        assert not jaxpr_audit.reads_of_shape(jaxpr, (n + 1,))
        assert jaxpr_audit.count_eqns(jaxpr) > 60

    reference = table_reading_sampler(n)
    monkeypatch.setattr(sampling, "sample_task_duration", reference)
    monkeypatch.setattr(core, "sample_task_duration", reference)
    with_tables = [
        jaxpr_audit.reads_of_shape(j, (n + 1,)) for j in traced()
    ]
    assert with_tables[2] == with_tables[3] == [f"gather({n + 1},)"] * 4
    # the pass samples in each of its loop's two unrolled steps (one
    # lane: a scalar index, so the four reads are dynamic slices)
    assert with_tables[0] == with_tables[1] == [
        f"dynamic_slice({n + 1},)"] * 8


def test_loop_row_reads_finds_a_table_read_inside_a_loop():
    """`jaxpr_audit.loop_row_reads`: a gather (under `vmap`) or a
    dynamic slice (one lane) from an operand of a named shape AND
    dtype is found inside a `while`, through a call, and not outside
    it; an operand of the same shape and another dtype is not named."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.analysis import jaxpr_audit

    table = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    other = jnp.ones((3, 4), jnp.float32)

    @jax.jit
    def read(i):
        return table[i % 3, i % 4] + other[i % 3, 0].astype(jnp.int32)

    def prog(i0):
        before = table[i0 % 3, 1]
        return jax.lax.while_loop(
            lambda c: c[0] < 5, lambda c: (c[0] + 1, c[1] + read(c[0])),
            (i0, before),
        )

    one = jax.make_jaxpr(prog)(jnp.int32(0)).jaxpr
    many = jax.make_jaxpr(jax.vmap(prog))(jnp.arange(4)).jaxpr
    for jaxpr, prim in ((one, "dynamic_slice"), (many, "gather")):
        assert jaxpr_audit.loop_row_reads(jaxpr, [table]) == [
            f"{prim}(int32[3, 4])"]
        assert jaxpr_audit.loop_row_reads(jaxpr, [other]) == [
            f"{prim}(float32[3, 4])"]
        assert len(jaxpr_audit.loop_row_reads(jaxpr, [table, other])) == 2
        assert not jaxpr_audit.loop_row_reads(
            jaxpr, [jnp.zeros((3, 4), jnp.uint32)])


def test_early_exit_loop_reads_three_bank_tables_a_step(monkeypatch):
    """What takes a counter's place for PR 50 (every sampled duration
    goes through it): the jaxpr of the fused bulk pass, one lane and
    under `vmap`, bank closed over and handed in, holds NO equation
    anywhere with an operand of `bank.level_present`'s shape, and in
    its early-exit loop no row read from an `int32[T,S]` operand
    (`bank.max_present`) and three row reads a step from the bank:
    `cnt`, `dur`, `rough_duration`, one element each (two steps an
    iteration: `_BULK_STEP_GRANULE`). What the sampler needs of a
    stage that no draw decides is the stage's word of
    `EnvState.duration_facts`, picked by the step's one-hot. With the
    table-reading sampler (`tests/test_bulk_pass_setup.py` keeps it)
    the rule names five reads a step, the two tables among them."""
    import jax

    from sparksched_tpu.analysis import jaxpr_audit
    from sparksched_tpu.env import core

    from .test_bulk_pass_setup import table_reading_sampler

    params, bank, state = jaxpr_audit.audit_setup()
    steps = core._BULK_STEP_GRANULE
    states = jaxpr_audit._batched(state, 3)
    leaves = jax.tree_util.tree_leaves(bank)
    present, highest = bank.level_present, bank.max_present
    t, s_cap = highest.shape
    assert present.shape == (t, s_cap, 8) and t != state.job_template.shape[0]

    def traced():
        def fused_pass(bank_, st):
            return core._bulk_events_fused(
                params, bank_, st, True, stop_at_limit=True, max_events=8
            )

        return {
            "closed over": jax.make_jaxpr(
                lambda st: fused_pass(bank, st))(state).jaxpr,
            "handed in": jax.make_jaxpr(fused_pass)(bank, state).jaxpr,
            "under vmap": jax.make_jaxpr(jax.vmap(
                fused_pass, in_axes=(None, 0)))(bank, states).jaxpr,
        }

    def table(reads, leaf):
        return [r for r in reads if r.endswith(
            f"({leaf.dtype}{list(leaf.shape)})")]

    for how, jaxpr in traced().items():
        assert not jaxpr_audit.reads_of_shape(jaxpr, present.shape), how
        reads = jaxpr_audit.loop_row_reads(jaxpr, leaves)
        assert len(reads) == 3 * steps, (how, reads)
        assert not table(reads, highest) and not table(reads, present)
        for leaf in (bank.cnt, bank.dur, bank.rough_duration):
            assert len(table(reads, leaf)) == steps, (how, reads)
        prim = "gather" if how == "under vmap" else "dynamic_slice"
        assert all(r.startswith(prim + "(") for r in reads), reads

    reference = table_reading_sampler(params.num_executors)
    monkeypatch.setattr(core, "sample_task_duration", reference)
    for how, jaxpr in traced().items():
        reads = jaxpr_audit.loop_row_reads(jaxpr, leaves)
        assert len(reads) == 5 * steps, (how, reads)
        assert len(table(reads, highest)) == steps
        assert len(table(reads, present)) == steps
        assert jaxpr_audit.reads_of_shape(jaxpr, present.shape)


def test_unknown_program_name_is_an_error():
    from sparksched_tpu.analysis import jaxpr_audit

    # a typo'd registry name must fail loudly, not silently audit
    # nothing — the registry and the budget table move together
    with pytest.raises(ValueError, match="not_a_program"):
        jaxpr_audit.audit_all(names=("not_a_program",))


# ---------------------------------------------------------------------------
# lint rules: seeded violations (fixture trees mirror the package layout
# — rule scoping keys on paths relative to the lint root)
# ---------------------------------------------------------------------------


def _lint_tree(tmp_path, files: dict[str, str]):
    from sparksched_tpu.analysis import lint

    root = tmp_path / "pkg"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return lint.lint_paths(root)


def test_rule_host_scalar_fires(tmp_path):
    vs = _lint_tree(tmp_path, {"env/bad.py": """\
        import numpy as np

        def f(x):
            a = x.item()
            b = np.asarray(x)
            c = float(x)
            d = int(x)
            return a, b, c, d
    """})
    got = [v for v in vs if v.rule == "host-scalar"]
    assert len(got) == 4, vs


def test_rule_host_scalar_respects_host_boundaries(tmp_path):
    vs = _lint_tree(tmp_path, {
        # the host adapter file is exempt by contract
        "env/gym_compat.py": "def f(x):\n    return x.item()\n",
        # host-boundary functions (config coercion, host decision API)
        "schedulers/ok.py": """\
            class S:
                def __init__(self, n):
                    self.n = int(n)

                def schedule(self, obs):
                    return int(obs)
        """,
        # the line-level pragma escape hatch
        "env/pragma.py": (
            "def f(x):\n"
            "    return x.item()  # analysis: allow(host-scalar)\n"
        ),
        # literals are not host pulls
        "env/lit.py": "def f():\n    return int(3), float('inf')\n",
    })
    assert [v for v in vs if v.rule == "host-scalar"] == []


def test_rule_host_sync_fires_and_exemptions_hold(tmp_path):
    vs = _lint_tree(tmp_path, {
        "trainers/bad.py": """\
            import jax

            def collect(x):
                jax.block_until_ready(x)
                return jax.device_get(x)
        """,
        # the sanctioned host loop: obs/ and the trainer host loop —
        # exemptions are path-qualified, so ONLY trainers/trainer.py's
        # train() is exempt (a `train` elsewhere still fires, below)
        "obs/fine.py": "import jax\n\ndef f(x):\n"
                       "    return jax.device_get(x)\n",
        "trainers/trainer.py": """\
            import jax

            def train(x):
                jax.block_until_ready(x)
                return jax.device_get(x)
        """,
        "env/loop.py": """\
            import jax

            def train(x):
                return jax.device_get(x)
        """,
        # the from-import form must not bypass the rule
        "trainers/bad2.py": """\
            from jax import device_get as dg

            def collect(x):
                return dg(x)
        """,
    })
    got = [v for v in vs if v.rule == "host-sync"]
    assert len(got) == 4 and all(
        "bad.py" in v.where or "bad2.py" in v.where
        or "env/loop.py" in v.where
        for v in got
    ), vs


def test_rule_implicit_dtype_fires(tmp_path):
    vs = _lint_tree(tmp_path, {"env/bad.py": """\
        import jax.numpy as jnp

        def f(n):
            a = jnp.zeros(n)
            b = jnp.ones((n, n))
            c = jnp.full((n,), 3.0)
            d = jnp.arange(n)
            # explicit forms (positional dtype slot or keyword) are fine
            e = jnp.zeros(n, jnp.int32)
            f_ = jnp.full((n,), 3.0, jnp.float32)
            g = jnp.arange(n, dtype=jnp.int32)
            h = jnp.zeros_like(a)
            return a, b, c, d, e, f_, g, h
    """, "env/aliased.py": """\
        from jax.numpy import zeros
        import jax.numpy as J

        def f(n):
            return zeros(n), J.ones(n)
    """})
    got = [v for v in vs if v.rule == "implicit-dtype"]
    assert len(got) == 6, vs


def test_rule_time_in_jit_fires(tmp_path):
    vs = _lint_tree(tmp_path, {
        "env/bad.py": "import time\n\ndef f():\n    return time.time()\n",
        # from-import and module-alias forms must not bypass the rule
        "env/bad2.py": (
            "from time import perf_counter\n\n"
            "def f():\n    return perf_counter()\n"
        ),
        "env/bad3.py": (
            "import time as t\n\ndef f():\n    return t.time()\n"
        ),
        # host modules may read the clock
        "trainers/fine.py": (
            "import time\n\ndef f():\n    return time.perf_counter()\n"
        ),
    })
    got = [v for v in vs if v.rule == "time-in-jit"]
    assert len(got) == 3 and all("env/bad" in v.where for v in got), vs


def test_rule_serve_host_sync_fires(tmp_path):
    """ISSUE 15: blocking syncs (`jax.device_get` /
    `block_until_ready` / eager `np.asarray`) in the serve pump hot
    path (serve/session.py) fire OUTSIDE the harvest/trace boundary,
    stay silent inside it (`_served`, `harvest`, `_materialize`,
    `_drain_writebacks` — the sanctioned functions), honor the
    line-level pragma escape, and do not apply to other serve files
    (loadgen is host-side by contract)."""
    vs = _lint_tree(tmp_path, {
        "serve/session.py": """\
            import jax
            import numpy as np

            def pump(store, out):
                jax.block_until_ready(out)       # violation
                a = np.asarray(out)              # violation
                b = jax.device_get(out)          # violation
                c = jax.device_get(out)  # analysis: allow(serve-host-sync)
                return a, b, c

            def harvest(out):
                return np.asarray(out)           # sanctioned

            def _served(call):
                import jax
                jax.block_until_ready(call)      # sanctioned
                return jax.device_get(call)      # sanctioned

            def _drain_writebacks(entry):
                return np.asarray(entry)         # sanctioned
        """,
        # other serve files are NOT in the pump scope
        "serve/loadgen.py": """\
            import jax

            def run(x):
                return jax.device_get(x)
        """,
    })
    got = [v for v in vs if v.rule == "serve-host-sync"]
    assert len(got) == 3 and all(
        "serve/session.py" in v.where for v in got
    ), vs
    # the generic host-sync rule stays exempt for these HOST_FILES
    assert [v for v in vs if v.rule == "host-sync"] == []


def test_rule_bare_print_fires(tmp_path):
    vs = _lint_tree(tmp_path, {
        "workload/bad.py": "print('hello')\n",
        "renderer.py": "print('renderer may print')\n",
        "obs/methods.py": "class A:\n    def print(self):\n        pass\n",
    })
    got = [v for v in vs if v.rule == "bare-print"]
    assert len(got) == 1 and "workload/bad.py" in got[0].where, vs


# ---------------------------------------------------------------------------
# contracts: seeded violations
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_env():
    import jax

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(
        num_executors=5, max_jobs=6, max_stages=6, max_levels=6,
        mean_time_limit=2.0e7,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )
    state = core.reset(params, bank, jax.random.PRNGKey(0))
    return params, bank, state


def test_contract_env_state_schema_fires(small_env):
    import jax.numpy as jnp

    from sparksched_tpu.analysis import contracts

    params, _, state = small_env
    assert contracts.check_env_state(state, params) == []

    bad_dtype = state.replace(
        wall_time=state.wall_time.astype(jnp.float16)
    )
    vs = contracts.check_env_state(bad_dtype, params)
    assert any(
        v.rule == "env-state-schema" and "wall_time" in v.where
        for v in vs
    )

    bad_shape = state.replace(
        job_supply=jnp.zeros(params.max_jobs + 1, jnp.int32)
    )
    vs = contracts.check_env_state(bad_shape, params)
    assert any("job_supply" in v.where for v in vs)

    with pytest.raises(AssertionError):
        contracts.assert_env_state(bad_dtype, params)


def test_contract_telemetry_schema_fires():
    import jax.numpy as jnp

    from sparksched_tpu.analysis import contracts
    from sparksched_tpu.obs.telemetry import telemetry_zeros

    tm = telemetry_zeros()
    assert contracts.check_telemetry(tm) == []
    bad = tm.replace(decide_steps=jnp.zeros((), jnp.float32))
    vs = contracts.check_telemetry(bad)
    assert vs and vs[0].rule == "telemetry-schema"

    # a counter widened to a vector (shape drift) must fire too — it
    # changes the scan carry's compile key on every consumer
    wide = tm.replace(ev_job_arrival=jnp.zeros(3, jnp.int32))
    vs = contracts.check_telemetry(wide)
    assert vs and "ev_job_arrival" in vs[0].where

    # vmapped telemetry: lane axes are fine past batch_ndim
    from sparksched_tpu.obs.telemetry import telemetry_zeros_like

    tb = telemetry_zeros_like((4,))
    assert contracts.check_telemetry(tb, batch_ndim=1) == []
    assert contracts.check_telemetry(tb) != []


def test_contract_trajectory_schema_fires():
    import jax

    from sparksched_tpu.analysis import contracts

    # a StoredObs whose duration drifted to f64 must fire
    dims = {"F": 128, "J": 4}
    rec = {
        k: jax.ShapeDtypeStruct(tuple(dims[d] for d in shape), dt)
        for k, (dt, shape) in contracts.STORED_OBS_SCHEMA.items()
    }
    assert contracts.check_fields(
        rec, contracts.STORED_OBS_SCHEMA, dims, "StoredObs"
    ) == []
    rec["duration"] = jax.ShapeDtypeStruct((128,), "float64")
    vs = contracts.check_fields(
        rec, contracts.STORED_OBS_SCHEMA, dims, "StoredObs"
    )
    assert vs and vs[0].rule == "trajectory-schema"

    # a leaf added without a schema update is itself a violation (the
    # f64-smuggled-into-the-rollout-buffer hazard must not hide behind
    # a schema-keyed projection)
    rec["duration"] = jax.ShapeDtypeStruct((128,), "float32")
    rec["value_est"] = jax.ShapeDtypeStruct((), "float64")
    vs = contracts.check_fields(
        rec, contracts.STORED_OBS_SCHEMA, dims, "StoredObs"
    )
    assert vs and "value_est" in vs[0].where, vs


def test_contract_step_invariance_fires(small_env):
    import jax.numpy as jnp

    from sparksched_tpu.analysis import contracts

    _, _, state = small_env
    before = contracts.spec_of(state)
    # an f32 drift on an i32 scalar (an i64 would need x64 enabled —
    # the astype silently truncates back to i32 on the shipped config)
    after = contracts.spec_of(
        state.replace(num_jobs=state.num_jobs.astype(jnp.float32))
    )
    vs = contracts.diff_spec(before, after, "EnvState")
    assert vs and vs[0].rule == "step-invariance"
    with pytest.raises(AssertionError):
        contracts.assert_same_spec(before, after)
    contracts.assert_same_spec(before, before)


# ---------------------------------------------------------------------------
# runtime-assert mode around real episodes (satellite): 500 flat-engine
# micro-steps and 500 core decision steps, EnvState/Telemetry pinned
# structure/dtype/shape-invariant at every step on both engines
# ---------------------------------------------------------------------------


def test_flat_engine_500_steps_contract_invariant(small_env):
    import jax

    from sparksched_tpu.analysis import contracts
    from sparksched_tpu.env.flat_loop import init_loop_state, micro_step
    from sparksched_tpu.obs.telemetry import telemetry_zeros
    from sparksched_tpu.schedulers.heuristics import round_robin_policy

    params, bank, state = small_env

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    @jax.jit
    def one(ls, key, tm):
        return micro_step(
            params, bank, pol, ls, key, True, True, True, 8, True, 1,
            telemetry=tm,
        )

    ls = init_loop_state(state)
    tm = telemetry_zeros()
    spec0 = contracts.spec_of(ls)
    tm_spec0 = contracts.spec_of(tm)
    key = jax.random.PRNGKey(1)
    for i in range(500):
        key, sub = jax.random.split(key)
        ls, tm = one(ls, sub, tm)
        # cheap metadata-only asserts — no device sync in the loop
        contracts.assert_same_spec(
            spec0, contracts.spec_of(ls), f"LoopState@{i}"
        )
        contracts.assert_same_spec(
            tm_spec0, contracts.spec_of(tm), f"Telemetry@{i}"
        )
        if i % 100 == 0:
            contracts.assert_env_state(ls.env, params)
    contracts.assert_env_state(ls.env, params)
    assert int(ls.decisions) > 0  # the episode actually progressed


def test_core_engine_500_steps_contract_invariant(small_env):
    import jax

    from sparksched_tpu.analysis import contracts
    from sparksched_tpu.env import core
    from sparksched_tpu.env.observe import observe
    from sparksched_tpu.obs.telemetry import telemetry_zeros
    from sparksched_tpu.schedulers.heuristics import round_robin_policy

    params, bank, state = small_env

    @jax.jit
    def one(st, key, tm):
        obs = observe(params, st)
        si, ne = round_robin_policy(obs, params.num_executors, True)
        st, reward, term, trunc, tm = core.step(
            params, bank, st, si, ne, telemetry=tm
        )
        # auto-reset on episode end so all 500 steps exercise live code
        fresh = core.reset(params, bank, jax.random.fold_in(key, 1))
        st = jax.tree_util.tree_map(
            lambda a, b: jax.numpy.where(term | trunc, a, b), fresh, st
        )
        return st, tm

    tm = telemetry_zeros()
    spec0 = contracts.spec_of(state)
    tm_spec0 = contracts.spec_of(tm)
    key = jax.random.PRNGKey(2)
    st = state
    for i in range(500):
        key, sub = jax.random.split(key)
        st, tm = one(st, sub, tm)
        contracts.assert_same_spec(
            spec0, contracts.spec_of(st), f"EnvState@{i}"
        )
        contracts.assert_same_spec(
            tm_spec0, contracts.spec_of(tm), f"Telemetry@{i}"
        )
        if i % 100 == 0:
            contracts.assert_env_state(st, params)
    contracts.assert_env_state(st, params)
    from sparksched_tpu.analysis.contracts import check_telemetry

    assert check_telemetry(tm) == []
    assert int(tm.decide_steps) > 0


# ---------------------------------------------------------------------------
# coverage rules: seeded violations (ISSUE 19 — every jit/AOT site is
# registered in the jaxpr-audit registry or explicitly waived)
# ---------------------------------------------------------------------------


def _coverage_tree(tmp_path, files: dict[str, str]):
    from sparksched_tpu.analysis import coverage

    root = tmp_path / "pkg"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return coverage.check_paths(root)


def test_rule_unregistered_jit_fires_and_pragma_clears(tmp_path):
    src = {"env/hot.py": """\
        import jax

        @jax.jit
        def fast(x):
            return x + 1

        def build():
            return jax.jit(lambda x: x * 2)
    """}
    vs = _coverage_tree(tmp_path, src)
    got = [v for v in vs if v.rule == "coverage-unregistered-jit"]
    # both forms: the decorator AND the call expression
    assert len(got) == 2
    assert {v.where for v in got} == {"env/hot.py:3", "env/hot.py:8"}
    vs2 = _coverage_tree(tmp_path, {"env/hot.py": """\
        import jax

        @jax.jit  # analysis: allow(coverage-unregistered-jit)
        def fast(x):
            return x + 1

        def build():
            return jax.jit(lambda x: x * 2)  # analysis: allow(coverage-unregistered-jit)
    """})
    assert _rules(vs2) == set()


def test_coverage_table_matches_shipped_tree():
    """Strict mode on the real package: zero unregistered sites, zero
    stale entries, and every registered program name exists in the
    jaxpr-audit BUDGETS (the three tables cannot drift apart)."""
    from sparksched_tpu.analysis import coverage

    assert coverage.check_package() == []
    assert coverage.last_scan_count() > 30


# ---------------------------------------------------------------------------
# concurrency rules: seeded violations (ISSUE 19 — fixture trees mirror
# the package layout; roles seed from the Thread spawn's name=)
# ---------------------------------------------------------------------------


def _conc_tree(tmp_path, files: dict[str, str]):
    from sparksched_tpu.analysis import concurrency

    root = tmp_path / "pkg"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return concurrency.check_paths(root)


def test_rule_nonowner_write_fires_and_pragma_clears(tmp_path):
    src = """\
        import threading

        class Store:
            def __init__(self):
                self.data = {}  # owner: serve-pump
                self._t = threading.Thread(
                    target=self._loop, name="online-learner"
                )

            def _loop(self):
                self.data["k"] = 1PRAGMA

            def pump(self):
                self.data["k"] = 2
    """
    vs = _conc_tree(
        tmp_path, {"serve/pump.py": src.replace("PRAGMA", "")})
    got = [v for v in vs if v.rule == "concurrency-nonowner-write"]
    # only the learner-thread write fires; the role-less method (main
    # is ownership-polymorphic) is fine
    assert [v.where for v in got] == ["serve/pump.py:11"]
    assert "online-learner" in got[0].detail
    vs2 = _conc_tree(tmp_path, {"serve/pump.py": src.replace(
        "PRAGMA",
        "  # analysis: allow(concurrency-nonowner-write)")})
    assert _rules(vs2) == set()


def test_rule_unlocked_shared_fires_and_pragma_clears(tmp_path):
    src = """\
        import threading

        class Buf:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []  # lock: _lock

            def add(self, x):
                with self._lock:
                    self.items.append(x)

            def bad(self):
                return len(self.items){pragma}
    """
    vs = _conc_tree(tmp_path, {"serve/buf.py": src.format(pragma="")})
    got = [v for v in vs if v.rule == "concurrency-unlocked-shared"]
    assert [v.where for v in got] == ["serve/buf.py:13"]
    vs2 = _conc_tree(tmp_path, {"serve/buf.py": src.format(
        pragma="  # analysis: allow(concurrency-unlocked-shared)")})
    assert _rules(vs2) == set()


def test_rule_lock_order_fires_and_pragma_clears(tmp_path):
    src = """\
        import threading

        class AB:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:{p1}
                        pass

            def two(self):
                with self._b:
                    with self._a:{p2}
                        pass
    """
    vs = _conc_tree(tmp_path, {"serve/ab.py": src.format(p1="", p2="")})
    got = [v for v in vs if v.rule == "concurrency-lock-order"]
    # the cycle is reported at each edge's acquisition site
    assert {v.where for v in got} == {"serve/ab.py:10", "serve/ab.py:15"}
    allow = "  # analysis: allow(concurrency-lock-order)"
    vs2 = _conc_tree(tmp_path, {"serve/ab.py": src.format(
        p1=allow, p2=allow)})
    assert _rules(vs2) == set()
    # waiving ONE edge leaves the other firing — the pragma is
    # per-site, never per-cycle
    vs3 = _conc_tree(tmp_path, {"serve/ab.py": src.format(
        p1=allow, p2="")})
    assert [v.where for v in vs3
            if v.rule == "concurrency-lock-order"] == ["serve/ab.py:15"]


def test_rule_blocking_under_lock_fires_and_pragma_clears(tmp_path):
    src = """\
        import queue
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()

            def bad(self):
                with self._lock:
                    return self._q.get(){pragma}

            def ok(self):
                with self._lock:
                    return self._q.get(timeout=1.0)
    """
    vs = _conc_tree(tmp_path, {"serve/w.py": src.format(pragma="")})
    got = [v for v in vs if v.rule == "concurrency-blocking-under-lock"]
    # the bounded get (timeout=) never fires
    assert [v.where for v in got] == ["serve/w.py:11"]
    vs2 = _conc_tree(tmp_path, {"serve/w.py": src.format(
        pragma="  # analysis: allow(concurrency-blocking-under-lock)")})
    assert _rules(vs2) == set()


def test_rule_pump_blocking_fires_and_pragma_clears(tmp_path):
    src = """\
        import threading

        import jax

        class Pump:
            def __init__(self):
                self._t = threading.Thread(
                    target=self._pump, name="serve-pump"
                )

            def _pump(self):
                jax.block_until_ready(1){pragma}
                self.harvest()

            def harvest(self):
                jax.block_until_ready(2)
    """
    vs = _conc_tree(tmp_path, {"serve/loop.py": src.format(pragma="")})
    got = [v for v in vs if v.rule == "concurrency-pump-blocking"]
    # only the sync OUTSIDE the harvest boundary fires: harvest() is a
    # sanctioned blocking stage even though the pump role reaches it
    assert [v.where for v in got] == ["serve/loop.py:12"]
    vs2 = _conc_tree(tmp_path, {"serve/loop.py": src.format(
        pragma="  # analysis: allow(concurrency-pump-blocking)")})
    assert _rules(vs2) == set()


def test_assert_placement_table_matches_code_and_runtime():
    """The three layers cannot drift: the static RUNTIME_ASSERT_SITES
    table, the assert_owner calls in source (strict scan fails on any
    mismatch, either direction), and the runtime role names."""
    from sparksched_tpu import ownership
    from sparksched_tpu.analysis import concurrency

    assert concurrency.check_package() == []
    assert concurrency.last_scan_count() > 30
    exp = concurrency.runtime_assert_expectations()
    assert len(exp) >= 15
    roles = {r for rs in exp.values() for r in rs}
    # every asserted role is a spawnable role the runtime knows; main
    # is ownership-polymorphic and never asserted
    assert roles <= set(concurrency.KNOWN_ROLES) - {"main"}
    assert ownership.ENV_FLAG == "SPARKSCHED_DEBUG_OWNERSHIP"
