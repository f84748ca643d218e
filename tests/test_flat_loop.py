"""Equivalence of the flat micro-step engine with the per-decision step
loop: same deterministic workload + fair policy must yield identical wall
times, decision counts and job completion times."""

from __future__ import annotations

import numpy as np
import pytest

from .reference_fixtures import (
    make_tpu_env_state,
    spec_diamond,
    spec_multi_job,
)


def _neq_ignoring_rng(sa, sb):
    """In-graph: any state field (rng excluded) differs between the
    engines. Used by the chunked equivalence scans to record the exact
    first-divergence step without per-step host transfers."""
    import jax
    import jax.numpy as jnp

    neq = jnp.bool_(False)
    for (pa, x), y in zip(
        jax.tree_util.tree_leaves_with_path(sa),
        jax.tree_util.tree_leaves(sb),
    ):
        if jax.tree_util.keystr(pa) == ".rng":
            continue
        neq = neq | jnp.any(x != y)
    return neq


# fast tier keeps the diamond fixture under BOTH fulfillment modes
# (False is the library default every non-bench caller uses; True is
# one of bench.py's self-calibration candidates); the multi-job fixture
# runs in the slow tier
@pytest.mark.parametrize("fulfill_bulk", [False, True])
@pytest.mark.parametrize(
    "spec_fn,num_exec",
    [
        (spec_diamond, 4),
        pytest.param(
            lambda: spec_multi_job(4, 11), 5, marks=pytest.mark.slow
        ),
    ],
)
def test_flat_loop_matches_step_loop(spec_fn, num_exec, fulfill_bulk):
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import run_flat
    from sparksched_tpu.env.observe import observe
    from sparksched_tpu.schedulers import round_robin_policy

    spec = spec_fn()
    params, bank, state0 = make_tpu_env_state(spec, num_exec)

    # step loop, advanced in jitted chunks with a done-freeze (the
    # per-call python loop made this one of the slowest fast-tier tests)
    @jax.jit
    def step_chunk(state, decisions):
        def body(carry, _):
            state, decisions = carry
            done = state.terminated
            obs = observe(params, state)
            si, ne = round_robin_policy(obs, num_exec, True)
            state2, _, _, _ = core.step(params, bank, state, si, ne)
            state = jax.tree_util.tree_map(
                lambda frozen, stepped: jnp.where(done, frozen, stepped),
                state, state2,
            )
            return (state, decisions + ~done), None

        return jax.lax.scan(body, (state, decisions), None, length=100)[0]

    state, decisions = state0, jnp.int32(0)
    for _ in range(40):
        state, decisions = step_chunk(state, decisions)
        if bool(state.terminated):
            break
    assert bool(state.terminated)
    decisions = int(decisions)

    # flat loop (frozen lanes at completion)
    def pol(rng, obs):
        si, ne = round_robin_policy(obs, num_exec, True)
        return si, ne, {}

    ls = jax.jit(
        lambda s, r: run_flat(
            params, bank, pol, r, 40 * decisions, s,
            auto_reset=False, fulfill_bulk=fulfill_bulk,
        )
    )(state0, jax.random.PRNGKey(0))

    assert int(ls.episodes) == 1
    assert int(ls.decisions) == decisions
    np.testing.assert_allclose(
        float(ls.env.wall_time), float(state.wall_time), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(ls.env.job_t_completed),
        np.asarray(state.job_t_completed), rtol=1e-6,
    )


def test_telemetry_parity_core_vs_flat():
    """Observability satellite: at a fixed seed on a deterministic
    workload, the two engines must report IDENTICAL DECIDE counts and
    per-kind event totals (single pops + the bulk pass attributable to
    that kind), plus matching fulfillment and commitment-round counts —
    the telemetry layer measures the same trajectory, so any skew is a
    counter bug, not engine noise. Extends the step-exact parity above
    from states to the obs.Telemetry counters."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import run_flat
    from sparksched_tpu.env.observe import observe
    from sparksched_tpu.obs import summarize, telemetry_zeros
    from sparksched_tpu.schedulers import round_robin_policy

    params, bank, s0 = make_tpu_env_state(spec_multi_job(4, 11), 5)

    @jax.jit
    def step_chunk(state, tm):
        def body(carry, _):
            st, tm = carry
            done = st.terminated
            obs = observe(params, st)
            si, ne = round_robin_policy(obs, 5, True)
            st2, _, _, _, tm2 = core.step(
                params, bank, st, si, ne, telemetry=tm
            )
            sel = lambda a, b: jnp.where(done, a, b)  # noqa: E731
            st = jax.tree_util.tree_map(sel, st, st2)
            tm = jax.tree_util.tree_map(sel, tm, tm2)
            return (st, tm), None

        return jax.lax.scan(body, (state, tm), None, length=100)[0]

    st, tm_core = s0, telemetry_zeros()
    for _ in range(40):
        st, tm_core = step_chunk(st, tm_core)
        if bool(st.terminated):
            break
    assert bool(st.terminated)
    sum_core = summarize(tm_core)

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, 5, True)
        return si, ne, {}

    ls, tm_flat = jax.jit(
        lambda s, r, t: run_flat(
            params, bank, pol, r, 4000, s, auto_reset=False,
            telemetry=t,
        )
    )(s0, jax.random.PRNGKey(0), telemetry_zeros())
    assert int(ls.episodes) == 1
    sum_flat = summarize(tm_flat)

    assert sum_core["decisions"] == sum_flat["decisions"] == int(
        ls.decisions
    )
    assert sum_core["events_by_kind"] == sum_flat["events_by_kind"]
    assert sum_core["fulfillments"] == sum_flat["fulfillments"]
    assert sum_core["commit_rounds"] == sum_flat["commit_rounds"]
    # the flat engine's raison d'être shows up in the counters: its
    # micro-step composition is defined (decide+fulfill+event == all
    # micro-steps) and the core loop measured its while iterations
    comp = sum_flat["composition"]
    # fractions are rounded to 4 decimals in summarize(), so the sum
    # carries up to 3 half-ulp rounding errors
    assert abs(
        comp["decide"] + comp["fulfill"] + comp["event"] - 1.0
    ) < 2e-4
    assert sum_core["loop_iters_mean"] > 0
    # ISSUE 7 per-phase split: single pops + productive bulk passes
    # describe the same trajectory on both engines, and the drain
    # iteration counter measures each engine's inter-decision loop
    assert sum_core["phase_iters"]["event"] > 0
    assert sum_flat["phase_iters"]["bulk"] > 0
    assert sum_core["phase_iters"]["bulk"] > 0
    # the decide phase IS the decision count on both engines; fulfill
    # PHASE iters are per-engine quantities (core fulfills via the bulk
    # prefix here -> 0 single steps; flat's default is one FULFILL
    # micro-step each) whose cross-engine invariant is the
    # `fulfillments` total asserted above
    assert sum_core["phase_iters"]["decide"] == (
        sum_flat["phase_iters"]["decide"]
    ) == sum_flat["decisions"]
    assert sum_flat["phase_iters"]["fulfill"] > 0
    # core's inter-decision while-loop is measured by drain_iters; the
    # flat run here never enters `drain_to_decision` (micro-step path),
    # so its drain counter stays zero by construction
    assert sum_core["drain_iters_mean"] > 0
    assert sum_flat["drain_iters_mean"] == 0
    # ISSUE 9 health-bitmask field: engines without health threading
    # report an all-zero mask and agree — the collector-level
    # health=True parity (clean episodes still zero, still agreeing)
    # is tests/test_health.py::test_health_mask_parity_core_vs_flat...
    assert sum_core["health_mask"] == sum_flat["health_mask"] == 0
    assert sum_core["health_bits"] == sum_flat["health_bits"] == []
    assert sum_flat["unhealthy_lanes"] == 0


@pytest.mark.slow
def test_bulk_relaunch_matches_sequential_event_loop():
    """core.step with bulk relaunch processing must produce bit-identical
    trajectories (modulo the rng field, whose stream legitimately
    differs) to the one-event-per-iteration loop on deterministic
    workloads — including the cascade case where a relaunch generates an
    event that precedes other pending finishes."""
    import jax

    from sparksched_tpu.env import core
    from sparksched_tpu.env.observe import observe
    from sparksched_tpu.schedulers import round_robin_policy

    import jax.numpy as jnp

    for spec_fn, n_exec in ((spec_diamond, 4), (lambda: spec_multi_job(4, 11), 5)):
        params, bank, s0 = make_tpu_env_state(spec_fn(), n_exec)

        # both engines advance inside one jitted chunked scan; a
        # per-step in-scan divergence tracker preserves the old host
        # loop's step-exact localization while the full tree compare
        # runs only at chunk boundaries
        @jax.jit
        def step_pair_chunk(sa, sb, done, div, base):
            def body(carry, i):
                sa, sb, done, div = carry
                obs = observe(params, sa)
                si, ne = round_robin_policy(obs, n_exec, True)
                sa2, _, term, _ = core.step(params, bank, sa, si, ne,
                                            bulk=True)
                sb2, _, _, _ = core.step(params, bank, sb, si, ne,
                                         bulk=False)
                sa, sb = jax.tree_util.tree_map(
                    lambda frozen, stepped: jnp.where(
                        done, frozen, stepped
                    ),
                    (sa, sb), (sa2, sb2),
                )
                div = jnp.where(
                    (div < 0) & _neq_ignoring_rng(sa, sb), base + i, div
                )
                done = done | term
                return (sa, sb, done, div), None

            return jax.lax.scan(
                body, (sa, sb, done, div), jnp.arange(100)
            )[0]

        sa = sb = s0
        done = jnp.bool_(False)
        div = jnp.int32(-1)
        for chunk in range(40):
            sa, sb, done, div = step_pair_chunk(
                sa, sb, done, div, jnp.int32(chunk * 100)
            )
            la = jax.tree_util.tree_leaves_with_path(sa)
            lb = jax.tree_util.tree_leaves(sb)
            for (pa, a), b in zip(la, lb):
                name = jax.tree_util.keystr(pa)
                if name == ".rng":
                    continue
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=(
                        f"chunk {chunk}, field {name}, first "
                        f"divergence at step {int(div)}"
                    ),
                )
            assert int(div) < 0, (
                f"transient divergence at step {int(div)}"
            )
            if bool(done):
                break
        assert bool(done)


@pytest.mark.slow
def test_bulk_stop_at_limit_matches_single_event_flat_loop():
    """The flat engine freezes at the first micro-step whose state
    crosses the episode time limit; a bulk pass must stop right after
    the first at-or-past-limit event so the frozen terminal state is
    identical to the single-event engine's. Swept over limits landing
    at arbitrary points mid-episode."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env.flat_loop import run_flat
    from sparksched_tpu.schedulers import round_robin_policy

    params, bank, s0 = make_tpu_env_state(spec_multi_job(4, 11), 5)

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, 5, True)
        return si, ne, {}

    for limit in (9000.0, 12503.0, 12504.0, 30000.0, 61111.0):
        st = s0.replace(time_limit=jnp.float32(limit))
        outs = []
        # bulk_cycles=3 stresses the chained-pass freeze gate (each
        # extra pass must refuse to run once the limit was crossed)
        for bulk, bc in ((True, 1), (True, 3), (False, 1)):
            ls = jax.jit(
                lambda s, r, b=bulk, c=bc: run_flat(
                    params, bank, pol, r, 4000, s,
                    auto_reset=False, event_bulk=b, bulk_cycles=c,
                )
            )(st, jax.random.PRNGKey(0))
            outs.append(ls)
        a, b = outs[0], outs[2]
        c3 = outs[1]
        la3 = jax.tree_util.tree_leaves_with_path(c3)
        for (pa, x), y in zip(la3, jax.tree_util.tree_leaves(b)):
            name = jax.tree_util.keystr(pa)
            if name in (".env.rng", ".bulked", ".mode"):
                continue
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"limit {limit} cycles=3, field {name}",
            )
        assert int(a.episodes) == 1, f"limit {limit}: episode did not end"
        assert int(a.decisions) == int(b.decisions), f"limit {limit}"
        la = jax.tree_util.tree_leaves_with_path(a)
        lb = jax.tree_util.tree_leaves(b)
        for (pa, x), y in zip(la, lb):
            name = jax.tree_util.keystr(pa)
            # rng streams legitimately differ; `bulked` counts by
            # construction; `mode` is dead state on a frozen lane (the
            # freeze path restores env and rolls back counters every
            # subsequent micro-step, and the engines reach the identical
            # terminal env via different micro-step sequences)
            if name in (".env.rng", ".bulked", ".mode"):
                continue
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"limit {limit}, field {name}",
            )


@pytest.mark.slow
def test_run_flat_loop_state_resume_matches_single_run():
    """Chunked runs resuming via `loop_state` (the bench pattern) must
    reach the same final state as one continuous run when the rng only
    feeds unused reset keys (deterministic policy, no auto-reset)."""
    import jax

    from sparksched_tpu.env.flat_loop import run_flat
    from sparksched_tpu.schedulers import round_robin_policy

    spec = spec_diamond()
    params, bank, state0 = make_tpu_env_state(spec, 4)

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, 4, True)
        return si, ne, {}

    whole = jax.jit(
        lambda s, r: run_flat(
            params, bank, pol, r, 120, s, auto_reset=False
        )
    )(state0, jax.random.PRNGKey(0))

    chunked = jax.jit(
        lambda s, r: run_flat(
            params, bank, pol, r, 60, s, auto_reset=False
        )
    )(state0, jax.random.PRNGKey(1))
    chunked = jax.jit(
        lambda ls, r: run_flat(
            params, bank, pol, r, 60, auto_reset=False, loop_state=ls
        )
    )(chunked, jax.random.PRNGKey(2))

    for a, b in zip(
        jax.tree_util.tree_leaves(whole), jax.tree_util.tree_leaves(chunked)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _decima_parity_fixture(monkeypatch):
    """Shared fixture for the Decima collection-parity tests: pins the
    duration sampler deterministic (the engines' rng STREAMS
    legitimately differ) and builds a greedy Decima scheduler, so every
    compared quantity is rng-independent."""
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.schedulers import DecimaScheduler
    from sparksched_tpu.workload import make_workload_bank

    def det_sampler(params, bank, rng, facts, template, stage, num_local,
                    task_valid, same_stage):
        base = bank.rough_duration[template, stage]
        return (
            base
            + jnp.where(task_valid & same_stage, 7.0, 131.0)
            + 17.0 * stage.astype(jnp.float32)
        )

    monkeypatch.setattr(core, "sample_task_duration", det_sampler)

    params = EnvParams(
        num_executors=5, max_jobs=6, max_stages=20, max_levels=20,
        moving_delay=700.0, warmup_delay=500.0, job_arrival_rate=4e-5,
        mean_time_limit=None, beta=5e-3,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )

    def make_sched(**kw):
        return DecimaScheduler(
            num_executors=params.num_executors, embed_dim=8,
            gnn_mlp_kwargs={"hid_dims": [16, 8], "act_cls": "LeakyReLU",
                            "act_kwargs": {"negative_slope": 0.2}},
            policy_mlp_kwargs={"hid_dims": [16, 16], "act_cls": "Tanh"},
            seed=7, **kw,
        )

    return params, bank, make_sched


def _assert_rollouts_match(ro_core, ro_flat, lane=None):
    """Step-exact comparison of an unbatched core Rollout against (one
    lane of) a possibly-batched flat Rollout."""
    import numpy as np_

    def a(x):
        return np_.asarray(x)

    def b(x):
        return np_.asarray(x)[lane] if lane is not None else np_.asarray(x)

    nv = int(a(ro_core.valid).sum())
    assert nv > 30, "fixture episode too short to be meaningful"
    np_.testing.assert_array_equal(a(ro_core.valid), b(ro_flat.valid))
    np_.testing.assert_array_equal(
        a(ro_core.stage_idx), b(ro_flat.stage_idx)
    )
    for name in ("job_idx", "num_exec_k"):
        np_.testing.assert_array_equal(
            a(getattr(ro_core, name))[:nv],
            b(getattr(ro_flat, name))[:nv],
            err_msg=name,
        )
    np_.testing.assert_allclose(
        a(ro_core.lgprob)[:nv], b(ro_flat.lgprob)[:nv],
        rtol=1e-5, atol=1e-6,
    )
    np_.testing.assert_allclose(
        a(ro_core.reward), b(ro_flat.reward), rtol=1e-4, atol=1e-4
    )
    np_.testing.assert_allclose(
        a(ro_core.wall_times), b(ro_flat.wall_times), rtol=1e-6
    )
    for name in ("remaining", "duration", "schedulable", "node_mask",
                 "job_mask", "job_template", "exec_supplies",
                 "num_committable", "source_job"):
        np_.testing.assert_array_equal(
            a(getattr(ro_core.obs, name))[:nv],
            b(getattr(ro_flat.obs, name))[:nv],
            err_msg=f"stored obs field {name}",
        )


def test_flat_collection_at_the_time_limit_ends_on_the_crossing_event(
    monkeypatch
):
    """The one place the flat engine's rollout leaves the `core.step`
    path's, pinned as a rule. `core.step` looks at the episode's time
    limit where the reference's StochasticTimeLimit wrapper does, back
    at a decision, so a truncated episode's last step runs on to the
    first decision past the limit. The flat engine looks after every
    event and freezes at the first one at or past the limit. Every
    decision, its time and every reward but the last agree; the last
    span of the flat engine is a prefix of the core path's."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env import core
    from sparksched_tpu.schedulers import round_robin_policy
    from sparksched_tpu.trainers.rollout import (
        collect_flat_sync_batch,
        collect_sync,
    )

    params, bank, _ = _decima_parity_fixture(monkeypatch)

    def fair(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    def fair_batch(rng, obs):
        si, ne, _ = jax.vmap(lambda o: fair(rng, o))(obs)
        return si, ne, {}

    T = 80
    free = core.reset(params, bank, jax.random.PRNGKey(3))
    times = np.asarray(collect_sync(
        params, bank, fair, jax.random.PRNGKey(0), T, free
    ).wall_times)
    k = 40
    assert times[k] < times[k + 1], "fixture: pick a strict time step"
    limit = float(times[k] + times[k + 1]) / 2
    s0 = free.replace(time_limit=jnp.float32(limit))
    ro_core = collect_sync(
        params, bank, fair, jax.random.PRNGKey(0), T, s0
    )
    # a batch of one lane, unstacked again for the comparison
    ro_flat = jax.tree_util.tree_map(
        lambda a: a[0],
        collect_flat_sync_batch(
            params, bank, fair_batch, jax.random.PRNGKey(1), T,
            jax.tree_util.tree_map(lambda a: a[None], s0),
        ),
    )
    nv = int(ro_core.valid.sum())
    assert nv == k + 1 and bool(ro_core.final_state.truncated)
    np.testing.assert_array_equal(
        np.asarray(ro_core.valid), np.asarray(ro_flat.valid)
    )
    for name in ("stage_idx", "job_idx", "num_exec_k"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ro_core, name))[:nv],
            np.asarray(getattr(ro_flat, name))[:nv], err_msg=name,
        )
    np.testing.assert_allclose(
        np.asarray(ro_core.wall_times)[:nv],
        np.asarray(ro_flat.wall_times)[:nv], rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(ro_core.reward)[: nv - 1],
        np.asarray(ro_flat.reward)[: nv - 1], rtol=1e-4, atol=1e-4,
    )
    end_core = float(ro_core.wall_times[nv])
    end_flat = float(ro_flat.wall_times[nv])
    assert end_core == pytest.approx(float(times[k + 1]), rel=1e-6)
    assert limit <= end_flat <= end_core * (1 + 1e-6)
    assert (
        float(ro_core.reward[nv - 1]) - 1e-3
        <= float(ro_flat.reward[nv - 1]) <= 0.0
    )


@pytest.mark.parametrize("job_bucket", [0, 3])
def test_single_eval_flat_collection_matches_core_step_path(
    monkeypatch, job_bucket
):
    """Round-8 tentpole parity: the single-eval batch collector
    (`collect_flat_sync_batch` — one batched policy evaluation per
    decision row, decide micro-step + drain-to-decision) must agree
    step-exactly with the per-decision `core.step` collection path at
    fixed seeds, with and without active-job compaction (job_bucket=3
    exercises the compact GNN on <=3-active rows AND the full-width
    fallback when more jobs are live)."""
    import jax

    from sparksched_tpu.env import core
    from sparksched_tpu.trainers.rollout import (
        collect_flat_sync_batch,
        collect_sync,
    )

    params, bank, make_sched = _decima_parity_fixture(monkeypatch)
    sched = make_sched(job_bucket=job_bucket)
    pol = sched.flat_policy(deterministic=True)
    bpol = sched.flat_batch_policy(deterministic=True)

    T = 160
    keys = [jax.random.PRNGKey(3), jax.random.PRNGKey(5)]
    states = [core.reset(params, bank, k) for k in keys]
    ro_cores = [
        collect_sync(params, bank, pol, jax.random.PRNGKey(0), T, s)
        for s in states
    ]
    batched = jax.tree_util.tree_map(
        lambda *a: jax.numpy.stack(a), *states
    )
    ro_flat = collect_flat_sync_batch(
        params, bank, bpol, jax.random.PRNGKey(1), T, batched,
        fulfill_bulk=True,
    )
    for lane, ro_core in enumerate(ro_cores):
        _assert_rollouts_match(ro_core, ro_flat, lane=lane)
        np.testing.assert_allclose(
            float(np.asarray(ro_core.final_state.wall_time)),
            float(np.asarray(ro_flat.final_state.wall_time)[lane]),
            rtol=1e-6,
        )


def test_single_eval_flat_collection_one_policy_eval_per_decide(
    monkeypatch,
):
    """Acceptance pin: flat single-eval collection performs EXACTLY one
    policy evaluation per recorded decision row. The counting wrapper
    bumps a host counter via io_callback on every actual execution of
    the policy program; with B lanes and T decisions per lane the batch
    collector must evaluate T times total (one batched eval per row) —
    the per-lane group collector measured ~2 per decision (PERF_ROUNDS.md
    round 6)."""
    import jax

    from sparksched_tpu.env import core
    from sparksched_tpu.trainers.rollout import collect_flat_sync_batch

    params, bank, make_sched = _decima_parity_fixture(monkeypatch)
    sched = make_sched()
    bpol = sched.flat_batch_policy(deterministic=True)

    calls = {"n": 0}

    def bump():
        calls["n"] += 1

    def counting_bpol(rng, obs):
        import jax.numpy as jnp

        out = bpol(rng, obs)
        # io_callback (not debug.callback): guaranteed to execute per
        # scan iteration, ordered against the policy outputs
        token = jax.experimental.io_callback(
            bump, None, ordered=False
        )
        del token
        return out

    T = 40  # well under the fixture episode's decision count
    keys = [jax.random.PRNGKey(3), jax.random.PRNGKey(5)]
    states = jax.tree_util.tree_map(
        lambda *a: jax.numpy.stack(a),
        *[core.reset(params, bank, k) for k in keys],
    )
    ro = collect_flat_sync_batch(
        params, bank, counting_bpol, jax.random.PRNGKey(1), T, states,
        fulfill_bulk=True,
    )
    jax.block_until_ready(ro.reward)
    per_lane = np.asarray(ro.valid).sum(axis=1)
    assert per_lane.tolist() == [T, T], per_lane
    # one batched evaluation per decision row — not ~2 per decision
    assert calls["n"] == T, (calls["n"], T)


# slow tier: the fast tier already pins the fused kernel two ways —
# fused-vs-core-sequential via test_bulk_paths_...'s run_flat section
# (bulk_fused defaults True) and direct fused-vs-unfused on the
# recorded single-eval path below; these whole-episode plain sweeps
# are the belt-and-braces run (tier-1 runs against a hard time budget)
@pytest.mark.slow
@pytest.mark.parametrize("moving_delay", [2000.0, 700.0])
def test_fused_bulk_pass_matches_unfused_plain(monkeypatch, moving_delay):
    """ISSUE 7 fused-kernel parity, plain (no recording): the flat
    engine with the single fused bulk kernel (`bulk_fused=True`,
    `core._bulk_events_fused` — mixed relaunch/arrival runs in exact
    queue order, one pass) must reach the SAME terminal state as the
    round-3/4 (relaunch cascade + arrival burst) pass pair at fixed
    seeds with a deterministic duration sampler. The engines take
    different micro-step sequences (the fused pass consumes mixed runs
    the pair splits across kind-switch micro-steps), so `bulked`/`mode`
    legitimately differ — everything else must agree bit-for-bit.
    moving_delay=700 forces dense interleavings of relaunch-generated
    finishes with arrival bursts, the regime where the two engines'
    pass boundaries differ most."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import run_flat
    from sparksched_tpu.schedulers import round_robin_policy
    from sparksched_tpu.workload import make_workload_bank

    def det_sampler(params, bank, rng, facts, template, stage, num_local,
                    task_valid, same_stage):
        base = bank.rough_duration[template, stage] * 0.05
        return (
            base
            + jnp.where(task_valid & same_stage, 7.0, 131.0)
            + 17.0 * stage.astype(jnp.float32)
            + 3.0 * num_local.astype(jnp.float32)
        )

    monkeypatch.setattr(core, "sample_task_duration", det_sampler)

    params = EnvParams(
        num_executors=6, max_jobs=12, max_stages=20, max_levels=20,
        moving_delay=moving_delay, warmup_delay=1000.0,
        job_arrival_rate=4e-5, mean_time_limit=None,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    for seed in (0, 3):
        s0 = core.reset(params, bank, jax.random.PRNGKey(seed))
        outs = {}
        for fused in (True, False):
            outs[fused] = jax.jit(
                lambda s, r, f=fused: run_flat(
                    params, bank, pol, r, 6000, s, auto_reset=False,
                    fulfill_bulk=True, bulk_fused=f,
                )
            )(s0, jax.random.PRNGKey(0))
        a, b = outs[True], outs[False]
        assert int(a.episodes) == int(b.episodes) == 1, f"seed {seed}"
        assert int(a.decisions) == int(b.decisions), f"seed {seed}"
        la = jax.tree_util.tree_leaves_with_path(a)
        lb = jax.tree_util.tree_leaves(b)
        for (pa, x), y in zip(la, lb):
            name = jax.tree_util.keystr(pa)
            # rng streams legitimately differ (one batched draw per
            # fused pass vs one per unfused pass); `bulked` counts
            # passes-by-construction; `mode` is dead state on a frozen
            # lane reached via different micro-step sequences
            if name in (".env.rng", ".bulked", ".mode"):
                continue
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"seed {seed}, field {name}",
            )


def test_fused_bulk_pass_matches_unfused_recorded(monkeypatch):
    """ISSUE 7 fused-kernel parity on a recorded rollout: the single-eval
    batch collector (decide micro-step + drain-to-decision — the path
    whose drain now runs the cheap-cond/`masked=False` body) must
    produce an IDENTICAL Rollout under `bulk_fused` on/off at fixed
    seeds — actions, log-probs, rewards, wall times, valid mask, and
    the stored observations the PPO update rebuilds features from."""
    import jax

    from sparksched_tpu.env import core
    from sparksched_tpu.trainers.rollout import collect_flat_sync_batch

    params, bank, make_sched = _decima_parity_fixture(monkeypatch)
    sched = make_sched()
    bpol = sched.flat_batch_policy(deterministic=True)

    T = 120
    keys = [jax.random.PRNGKey(3), jax.random.PRNGKey(5)]
    states = jax.tree_util.tree_map(
        lambda *a: jax.numpy.stack(a),
        *[core.reset(params, bank, k) for k in keys],
    )
    ros = {}
    for fused in (True, False):
        ros[fused] = collect_flat_sync_batch(
            params, bank, bpol, jax.random.PRNGKey(1), T, states,
            fulfill_bulk=True, bulk_fused=fused,
        )
    a, b = ros[True], ros[False]
    nv = int(np.asarray(a.valid).sum())
    assert nv > 30, "fixture episode too short to be meaningful"
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree_util.tree_leaves(b)
    for (pa, x), y in zip(la, lb):
        name = jax.tree_util.keystr(pa)
        # the final carried env's rng differs by stream construction
        if ".rng" in name:
            continue
        if name == ".reward":
            # per-decision rewards sum the SAME per-event terms in a
            # different partial-sum order (the fused pass consumes
            # runs the pair splits across micro-steps) — f32
            # associativity, not trajectory drift
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-3,
                err_msg=f"field {name}",
            )
            continue
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"field {name}"
        )


@pytest.mark.parametrize(
    "dur_scale,moving_delay",
    [
        # the default-delay sweep moved to the slow tier in round 11
        # (tier-1 time budget): the dense 0.02/700 interleaving regime
        # below is the strictly harder coverage and stays fast
        pytest.param(1.0, 2000.0, marks=pytest.mark.slow),
        # tiny durations + short moving delay force dense interleavings
        # of relaunch-generated finishes with arrival bursts (the
        # _bulk_ready generated-finish and source-join stop conditions)
        (0.02, 700.0),
    ],
)
def test_bulk_paths_match_sequential_on_synthetic_bank(
    monkeypatch, dur_scale, moving_delay
):
    """Randomized coverage beyond the hand-built fixtures: drive the
    synthetic TPC-H bank (50-job cap, rich DAG/task-count variety) with
    the duration sampler pinned to a deterministic table lookup, so the
    bulk fast paths (relaunch cascade + fulfillment prefix + arrival
    bursts) must match the fully sequential engine bit-for-bit over
    whole episodes."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.observe import observe
    from sparksched_tpu.schedulers import round_robin_policy
    from sparksched_tpu.workload import make_workload_bank

    def det_sampler(params, bank, rng, facts, template, stage, num_local,
                    task_valid, same_stage):
        base = bank.rough_duration[template, stage] * dur_scale
        # distinct per (stage-continuation kind) so wave logic still
        # shapes trajectories, but with no rng sensitivity
        return (
            base
            + jnp.where(task_valid & same_stage, 7.0, 131.0)
            + 17.0 * stage.astype(jnp.float32)
        )

    monkeypatch.setattr(core, "sample_task_duration", det_sampler)

    params = EnvParams(
        num_executors=6, max_jobs=12, max_stages=20, max_levels=20,
        moving_delay=moving_delay, warmup_delay=1000.0,
        job_arrival_rate=4e-5, mean_time_limit=None,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )

    # both engines advance inside ONE jitted chunked scan (the policy is
    # computed once per step from the bulk arm's state and applied to
    # both), with full-tree equality checked at every chunk boundary —
    # the same invariant as a per-step comparison, at a fraction of the
    # dispatch/host-transfer cost that made this the slowest test in the
    # fast tier
    CHUNK = 50

    @jax.jit
    def step_pair_chunk(sa, sb, done, div, base):
        def body(carry, i):
            sa, sb, done, div = carry
            obs = observe(params, sa)
            si, ne = round_robin_policy(obs, params.num_executors, True)
            sa2, _, term, _ = core.step(params, bank, sa, si, ne,
                                        bulk=True)
            sb2, _, _, _ = core.step(params, bank, sb, si, ne,
                                     bulk=False)
            sa, sb = jax.tree_util.tree_map(
                lambda frozen, stepped: jnp.where(done, frozen, stepped),
                (sa, sb), (sa2, sb2),
            )
            div = jnp.where(
                (div < 0) & _neq_ignoring_rng(sa, sb), base + i, div
            )
            done = done | term
            return (sa, sb, done, div), None

        (sa, sb, done, div), _ = jax.lax.scan(
            body, (sa, sb, done, div), jnp.arange(CHUNK)
        )
        return sa, sb, done, div

    for seed in (0, 3):
        sa = sb = core.reset(params, bank, jax.random.PRNGKey(seed))
        done = jnp.bool_(False)
        div = jnp.int32(-1)
        for chunk in range(1500 // CHUNK):
            sa, sb, done, div = step_pair_chunk(
                sa, sb, done, div, jnp.int32(chunk * CHUNK)
            )
            la = jax.tree_util.tree_leaves_with_path(sa)
            lb = jax.tree_util.tree_leaves(sb)
            for (pa, a), b in zip(la, lb):
                name = jax.tree_util.keystr(pa)
                if name == ".rng":
                    continue
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=(
                        f"seed {seed} chunk {chunk}, field {name}, "
                        f"first divergence at step {int(div)}"
                    ),
                )
            assert int(div) < 0, (
                f"seed {seed}: transient divergence at step {int(div)}"
            )
            if bool(done):
                break
        assert bool(done), f"seed {seed}: episode did not finish"

        # the flat micro-step engine (bench path) must land on the same
        # terminal state as the per-decision loop — with single-fulfill
        # micro-steps AND with the bulked fulfillment prefix
        from sparksched_tpu.env.flat_loop import run_flat

        def pol(rng, obs):
            si, ne = round_robin_policy(obs, params.num_executors, True)
            return si, ne, {}

        # bulk_cycles > 1 chains extra (relaunch + ready) pairs per
        # micro-step and exercises the round-4 fused pop (the default
        # engine pops the run-cutting event in the same micro-step)
        for fb, bc in ((False, 1), (True, 1), (True, 2), (True, 3)):
            ls = jax.jit(
                lambda s, r, fb=fb, bc=bc: run_flat(
                    params, bank, pol, r, 6000, s, auto_reset=False,
                    fulfill_bulk=fb, bulk_cycles=bc,
                )
            )(core.reset(params, bank, jax.random.PRNGKey(seed)),
              jax.random.PRNGKey(0))
            assert int(ls.episodes) == 1, (
                f"seed {seed} fb={fb} bc={bc}: flat episode open"
            )
            np.testing.assert_allclose(
                float(ls.env.wall_time), float(sa.wall_time), rtol=1e-6,
                err_msg=f"seed {seed} fb={fb} bc={bc}: flat wall_time",
            )
            np.testing.assert_allclose(
                np.asarray(ls.env.job_t_completed),
                np.asarray(sa.job_t_completed), rtol=1e-6,
                err_msg=(
                    f"seed {seed} fb={fb} bc={bc}: flat job "
                    "completion times"
                ),
            )


# ---------------------------------------------------------------------------
# PR 28: the fused bulk pass's early-exit loop against the fixed scan
# ---------------------------------------------------------------------------


def _fixed_scan(step_fn, carry0, us, lane_axis=None):
    """The oracle: the fixed-length scan the fused pass ran before its
    loop ended early, over the same `step_fn` (every row in budget);
    it evaluates no predicate."""
    import jax

    return jax.lax.scan(
        lambda c, u: (step_fn(c, u, True), None), carry0, us
    )[0], 0


@pytest.fixture(scope="module")
def bulk_pass_trail():
    """Mid-episode engine states for the pass to start from: every
    `LoopState` along 700 micro-steps of one dense episode (6 executors,
    short moving delay, the REAL duration sampler, so the uniform table
    is read and the rng stream compared), stacked on a leading axis;
    and `need`, the events the fixed scan takes from each under a
    budget no run reaches (0 where the lane is not in EVENT mode)."""
    import jax

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import (
        M_EVENT,
        init_loop_state,
        micro_step,
    )
    from sparksched_tpu.schedulers import round_robin_policy
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(
        num_executors=6, max_jobs=12, max_stages=20, max_levels=20,
        moving_delay=700.0, warmup_delay=1000.0,
        job_arrival_rate=4e-5, mean_time_limit=None,
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    @jax.jit
    def trail(s0, key):
        def body(ls, k):
            ls2 = micro_step(
                params, bank, pol, ls, k, auto_reset=False,
                fulfill_bulk=True,
            )
            return ls2, ls

        return jax.lax.scan(
            body, init_loop_state(s0), jax.random.split(key, 700)
        )[1]

    lss = trail(
        core.reset(params, bank, jax.random.PRNGKey(3)),
        jax.random.PRNGKey(0),
    )
    on = np.asarray(lss.mode) == M_EVENT
    _, k_rel, k_rdy, _ = _run_pass(
        params, bank, _fixed_scan, lss.env, on, max_events=40,
        how="vmap",
    )
    need = np.asarray(k_rel) + np.asarray(k_rdy)
    assert need.max() >= 9 and (need == 0).sum() > 50, np.bincount(need)
    return params, bank, lss, on, need


def _run_pass(params, bank, runner, envs, on, *, max_events, how,
              syncs=False):
    """`core._bulk_events_fused` over stacked states with `runner` as
    its step loop: `how` is "one" (a lane at a time, no vmap), "vmap"
    (per-lane predicate) or "vmap_named" (the lane axis named, so one
    predicate for the batch). Returns the pass's state, `k_rel`,
    `k_rdy` and steps, or with `syncs` its count of reductions over
    the lanes alone."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env import core

    axis = "lanes" if how == "vmap_named" else None

    def one(env, enabled):
        out = core._bulk_events_fused(
            params, bank, env, enabled, stop_at_limit=True,
            max_events=max_events, lane_axis=axis,
        )
        return out[4] if syncs else out[:4]

    saved = core._steps_while_active
    core._steps_while_active = runner
    try:
        on = jnp.asarray(on)
        if how == "one":
            fn = jax.jit(one)
            outs = [
                fn(jax.tree_util.tree_map(lambda a: a[i], envs), on[i])
                for i in range(on.shape[0])
            ]
            return jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *outs
            )
        return jax.jit(jax.vmap(one, axis_name=axis))(envs, on)
    finally:
        core._steps_while_active = saved


def _assert_same_pass(got, want, msg):
    import jax

    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got),
        jax.tree_util.tree_leaves(want),
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{msg}: {jax.tree_util.keystr(path)}",
        )


def _pick_lanes(on, need, wanted):
    """One lane index for each wanted need (the first that has it)."""
    return np.asarray(
        [int(np.flatnonzero(on & (need == k))[0]) for k in wanted]
    )


@pytest.mark.parametrize("how", ["one", "vmap", "vmap_named"])
def test_early_exit_pass_matches_fixed_scan(bulk_pass_trail, how):
    """The early-exit loop returns what the fixed scan over the same
    `step_fn` returns: every leaf of the state (rng included: the table
    is drawn whole before the loop and read at the step's index),
    `k_rel`, `k_rdy` and the steps needed; for one lane and under
    `vmap`, with lanes whose runs end at different steps, lanes not
    enabled, and lanes at their episode's time limit (which stop after
    the event that crosses it: at most two steps)."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.env import core

    params, bank, lss, on, need = bulk_pass_trail
    # runs of every length the trail has, then lanes that are not in
    # EVENT mode; for the vmapped cases, every tenth lane of the trail
    # as well, so one batch holds a mix of needs
    lengths = sorted(set(need[on].tolist()))
    idx = np.concatenate([
        _pick_lanes(on, need, lengths),
        np.flatnonzero(~on)[:3],
        np.arange(0, on.shape[0], 10) if how != "one" else [],
    ]).astype(int)
    envs = jax.tree_util.tree_map(lambda a: a[idx], lss.env)
    enabled = on[idx]
    # the last four enabled lanes with something to take stand at their
    # limit: the first event they take crosses it
    at_limit = np.flatnonzero(enabled & (need[idx] >= 2))[-4:]
    tl = np.asarray(envs.time_limit).copy()
    tl[at_limit] = np.asarray(envs.wall_time)[at_limit]
    envs = envs.replace(time_limit=jnp.asarray(tl))

    want = _run_pass(
        params, bank, _fixed_scan, envs, enabled, max_events=8, how=how
    )
    got = _run_pass(
        params, bank, core._steps_while_active, envs, enabled,
        max_events=8, how=how,
    )
    _assert_same_pass(got, want, how)

    _, k_rel, k_rdy, steps = got
    took = np.asarray(k_rel) + np.asarray(k_rdy)
    steps = np.asarray(steps)
    # the reductions over the lanes the loop made: with the lane axis
    # named, one for each granule the longest run of the batch needed
    # and the one that ended the loop, the same in every lane; none
    # for a loop that keeps each lane's own predicate
    got_syncs = np.asarray(_run_pass(
        params, bank, core._steps_while_active, envs, enabled,
        max_events=8, how=how, syncs=True,
    ))
    g = core._BULK_STEP_GRANULE
    if how == "vmap_named":
        assert (got_syncs == -(-int(steps.max()) // g) + 1).all()
        assert steps.max() > g
    else:
        assert (got_syncs == 0).all()
    assert (took[~enabled] == 0).all() and (steps[~enabled] == 0).all()
    assert (took[at_limit] == 1).all() and (steps[at_limit] == 2).all()
    assert len(set(took[enabled].tolist())) >= 6, took
    # a step per event taken, and the one that saw the run end (not
    # there when a joining arrival or the budget ended the run)
    extra = steps - took
    budget = 8 + params.num_executors
    assert ((extra == 0) | (extra == 1))[enabled].all()
    assert (extra[enabled & (took == budget)] == 0).all()
    ended = enabled & (took < budget)
    assert (extra[ended] == 1).sum() > ended.sum() // 2


@pytest.mark.parametrize("max_events", [1, 2, 3])
def test_early_exit_pass_keeps_the_budget(bulk_pass_trail, max_events):
    """`max_events + N` is still the budget, whatever the loop's
    granule: a run longer than it is cut where the scan cut it (at
    budgets of 7, 8 and 9 steps: 7 and 9 are no multiple of the
    granule, so the last granule's step past the budget must take
    nothing), and a run longer than `max_events` but inside the budget
    ends where it ends."""
    import jax

    from sparksched_tpu.env import core

    params, bank, lss, on, need = bulk_pass_trail
    budget = max_events + params.num_executors
    over = on & (need > budget)
    inside = on & (need > max_events) & (need < budget)
    assert over.sum() >= 1 and inside.sum() >= 2, (budget, need.max())
    idx = np.concatenate([
        np.flatnonzero(over)[:4], np.flatnonzero(on & (need == budget))[:2],
        np.flatnonzero(inside)[:4], np.flatnonzero(~on)[:2],
    ])
    envs = jax.tree_util.tree_map(lambda a: a[idx], lss.env)
    for how in ("vmap_named", "one"):
        want = _run_pass(
            params, bank, _fixed_scan, envs, on[idx],
            max_events=max_events, how=how,
        )
        got = _run_pass(
            params, bank, core._steps_while_active, envs, on[idx],
            max_events=max_events, how=how,
        )
        _assert_same_pass(got, want, f"budget {budget}, {how}")
        took = np.asarray(got[1]) + np.asarray(got[2])
        np.testing.assert_array_equal(
            took, np.minimum(need[idx], budget)
        )
        # a run cut by the budget has no step that saw it end
        np.testing.assert_array_equal(
            np.asarray(got[3])[need[idx] >= budget], budget
        )


def test_bulk_scan_steps_counter(bulk_pass_trail):
    """`Telemetry.bulk_scan_steps` is what the pass reports for a live
    lane that is not in DECIDE mode and 0 otherwise, through both
    micro-steps that run the bulk chain; `summarize` gives its total
    and its mean over the passes that took an event."""
    import jax

    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import (
        _lane_done,
        drain_micro_step,
        micro_step,
    )
    from sparksched_tpu.obs import summarize
    from sparksched_tpu.obs.telemetry import telemetry_zeros_like
    from sparksched_tpu.schedulers import round_robin_policy

    params, bank, lss, on, need = bulk_pass_trail
    idx = np.arange(0, on.shape[0], 7)
    ls = jax.tree_util.tree_map(lambda a: a[idx], lss)
    keys = jax.random.split(jax.random.PRNGKey(5), len(idx))
    tm0 = telemetry_zeros_like((len(idx),))
    want = np.asarray(_run_pass(
        params, bank, core._steps_while_active, ls.env, on[idx],
        max_events=8, how="vmap",
    )[3])
    assert (want[on[idx]] >= 1).all() and (want[~on[idx]] == 0).all()
    # the trail's last lanes are frozen after the episode's end: the
    # pass still looks at them, no counter moves
    done = np.asarray(jax.vmap(_lane_done)(ls.env))
    assert 0 < done.sum() < len(idx) // 2
    want = np.where(done, 0, want)
    took_any = (need[idx] > 0) & ~done

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    steps = {
        "drain": lambda l, k, t: drain_micro_step(
            params, bank, l, k, auto_reset=False, telemetry=t),
        "micro": lambda l, k, t: micro_step(
            params, bank, pol, l, k, auto_reset=False, telemetry=t),
    }
    for name, fn in steps.items():
        tm = jax.jit(jax.vmap(fn))(ls, keys, tm0)[-1]
        np.testing.assert_array_equal(
            np.asarray(tm.bulk_scan_steps), want, err_msg=name
        )
        s = summarize(tm)
        assert s["bulk_scan_steps_total"] == int(want.sum()), name
        assert s["phase_iters"]["bulk"] == int(took_any.sum()), name
        assert s["bulk_scan_steps_per_pass"] == round(
            want.sum() / took_any.sum(), 3
        ), name


# ---------------------------------------------------------------------
# PR 39: the pass's refresh of `unsat_parent_count` counts flipped
# parents on the state's packed parent sets (`EnvState.parent_sets`)
# where it contracted `delta` against the whole [J,S,S] adjacency. The
# contraction lives on here, as the reference.
# ---------------------------------------------------------------------


def _contracted_refresh(state, delta):
    """The refresh as it was until PR 39."""
    import jax.numpy as jnp

    return jnp.einsum("jp,jpc->jc", delta, state.adj.astype(jnp.int32))


def _run_pass_contracted(params, bank, envs, on, **kw):
    """`_run_pass` with the pass reading the adjacency itself, through
    the contraction."""
    from sparksched_tpu.env import core

    saved = core._flipped_parents
    core._flipped_parents = _contracted_refresh
    try:
        return _run_pass(
            params, bank, core._steps_while_active, envs, on, **kw
        )
    finally:
        core._flipped_parents = saved


def _golden_unsat(env):
    sat = np.asarray(env.stage_saturated)
    ex = np.asarray(env.stage_exists)
    return (np.asarray(env.adj) & (~sat & ex)[..., None]).sum(-2)


@pytest.mark.parametrize("how", ["one", "vmap_named"])
def test_packed_refresh_matches_contraction_mid_episode(
    bulk_pass_trail, how
):
    """Every leaf the pass writes, from the packed parent sets and from
    the contraction over the adjacency, on states along an episode: all
    700 of the trail under `vmap`, and one at a time (no batch axis)
    the lanes whose pass flips a stage's saturation, with some that
    flip none."""
    import jax

    from sparksched_tpu.env import core

    params, bank, lss, on, need = bulk_pass_trail
    want = _run_pass_contracted(
        params, bank, lss.env, on, max_events=8, how="vmap"
    )
    flips = (
        np.asarray(want[0].stage_sat) != np.asarray(lss.env.stage_sat)
    ).any((1, 2))
    # the trail's passes saturate stages (none un-saturates one: the
    # made-up states below do), and most flip none
    assert flips.sum() >= 10 and (~flips & on).sum() > 100
    if how == "one":
        idx = np.concatenate([
            np.flatnonzero(flips)[:12], np.flatnonzero(~flips & on)[:3],
        ])
        envs = jax.tree_util.tree_map(lambda a: a[idx], lss.env)
        want = jax.tree_util.tree_map(lambda a: a[idx], want)
        enabled = on[idx]
    else:
        envs, enabled = lss.env, on
    got = _run_pass(
        params, bank, core._steps_while_active, envs, enabled,
        max_events=8, how=how,
    )
    _assert_same_pass(got, want, how)
    np.testing.assert_array_equal(
        np.asarray(got[0].unsat_parent_count), _golden_unsat(got[0])
    )


def _made_up_pass_states():
    """Four hand-made states for the pass, one for each way it moves a
    stage's saturation. Two jobs of one diamond (0 -> 1, 0 -> 2,
    1 -> 3, 2 -> 3), four executors, both jobs arrived, the caches set
    to their recomputation. Returns (params, bank, stacked states,
    names)."""
    import jax
    import jax.numpy as jnp

    adj = np.zeros((4, 4), bool)
    adj[0, 1] = adj[0, 2] = adj[1, 3] = adj[2, 3] = True
    job = {"adj": adj, "num_tasks": [6, 6, 6, 6],
           "fresh": [900.0] * 4, "first": [700.0] * 4, "rest": [500.0] * 4}
    params, bank, base = make_tpu_env_state(
        {"arrivals": [0.0, 0.0], "jobs": [job, job]}, 4, moving_delay=700.0
    )
    inf = np.float32(np.inf)

    def build(remaining, moving, execs):
        """`execs`: per executor None (idle in the common pool),
        ("run", job, stage, t) or ("move", job, stage, t)."""
        e = {k: [] for k in (
            "job", "stage", "tstage", "tvalid", "executing", "fin",
            "moving", "arr", "dj", "ds", "common")}
        for x in execs:
            kind = x[0] if x else None
            e["job"].append(x[1] if kind == "run" else -1)
            e["stage"].append(x[2] if kind == "run" else -1)
            e["tstage"].append(x[2] if kind == "run" else -1)
            e["tvalid"].append(kind == "run")
            e["executing"].append(kind == "run")
            e["fin"].append(x[3] if kind == "run" else inf)
            e["moving"].append(kind == "move")
            e["arr"].append(x[3] if kind == "move" else inf)
            e["dj"].append(x[1] if kind == "move" else -1)
            e["ds"].append(x[2] if kind == "move" else -1)
            e["common"].append(kind is None)
        rem = jnp.asarray(remaining, jnp.int32)
        mov = jnp.asarray(moving, jnp.int32)
        st = base.replace(
            job_arrived=jnp.ones(2, bool), round_ready=jnp.bool_(False),
            stage_remaining=rem, moving_count=mov,
            stage_executing=jnp.zeros_like(rem).at[
                jnp.asarray([max(j, 0) for j in e["job"]]),
                jnp.asarray([max(s_, 0) for s_ in e["stage"]]),
            ].add(jnp.asarray(e["executing"], jnp.int32)),
            exec_job=jnp.asarray(e["job"], jnp.int32),
            exec_stage=jnp.asarray(e["stage"], jnp.int32),
            exec_task_stage=jnp.asarray(e["tstage"], jnp.int32),
            exec_task_valid=jnp.asarray(e["tvalid"]),
            exec_executing=jnp.asarray(e["executing"]),
            exec_finish_time=jnp.asarray(e["fin"], jnp.float32),
            exec_finish_seq=jnp.arange(10, 14, dtype=jnp.int32),
            exec_moving=jnp.asarray(e["moving"]),
            exec_arrive_time=jnp.asarray(e["arr"], jnp.float32),
            exec_arrive_seq=jnp.arange(20, 24, dtype=jnp.int32),
            exec_dst_job=jnp.asarray(e["dj"], jnp.int32),
            exec_dst_stage=jnp.asarray(e["ds"], jnp.int32),
            exec_at_common=jnp.asarray(e["common"]),
            seq_counter=jnp.int32(30),
        )
        st = st.replace(stage_sat=st.stage_saturated)
        return st.replace(
            unsat_parent_count=jnp.asarray(_golden_unsat(st), jnp.int32)
        )

    full = [[6, 6, 6, 6], [6, 6, 6, 6]]
    none = np.zeros((2, 4), int)
    states = {
        # two executors finish tasks of (0, 0), which has two left: the
        # second relaunch saturates it
        "shared_stage": build(
            [[2, 6, 6, 6], full[1]], none,
            [("run", 0, 0, 10.0), ("run", 0, 0, 20.0), None, None],
        ),
        # an executor arrives at (1, 0), on the frontier, and starts;
        # its finish is then (1, 0)'s, and relaunching there saturates
        # it: the flip lands where the arrival re-targeted the finish
        "retargeted_finish": build(
            [full[0], [2, 6, 6, 6]], [[0] * 4, [1, 0, 0, 0]],
            [("move", 1, 0, 5.0), None, None, None],
        ),
        # an executor arrives at (1, 2), whose parent is incomplete: it
        # parks, (1, 2)'s demand rises to 1 and the stage un-saturates
        "park_unsaturates": build(
            [full[0], [6, 6, 1, 6]], [[0] * 4, [0, 0, 1, 0]],
            [None, ("move", 1, 2, 5.0), None, None],
        ),
        # relaunches on stages with more tasks left than the pass has
        # steps: nothing flips
        "no_flip": build(
            [[40, 6, 6, 6], [40, 6, 6, 6]], none,
            [None, None, ("run", 1, 0, 10.0), ("run", 0, 0, 20.0)],
        ),
    }
    stacked = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *states.values()
    )
    return params, bank, stacked, list(states)


@pytest.mark.parametrize(
    "case",
    ["shared_stage", "retargeted_finish", "park_unsaturates", "no_flip"],
)
def test_packed_refresh_matches_contraction_made_up(case):
    """The four ways a pass moves (or leaves) a stage's saturation, on
    hand-made states: the pass from the packed parent sets is leaf-equal
    to the pass through the contraction, the caches equal their
    recomputation afterwards, and each state does what it was made
    for."""
    import jax

    from sparksched_tpu.env import core

    params, bank, stacked, names = _made_up_pass_states()
    i = names.index(case)
    envs = jax.tree_util.tree_map(lambda a: a[i:i + 1], stacked)
    on = np.ones(1, bool)
    want = _run_pass_contracted(
        params, bank, envs, on, max_events=8, how="vmap"
    )
    got = _run_pass(
        params, bank, core._steps_while_active, envs, on,
        max_events=8, how="vmap",
    )
    _assert_same_pass(got, want, case)
    env, k_rel, k_rdy, _ = jax.tree_util.tree_map(
        lambda a: np.asarray(a)[0], got
    )
    before = jax.tree_util.tree_map(lambda a: np.asarray(a)[0], envs)
    np.testing.assert_array_equal(env.stage_sat, env.stage_saturated)
    np.testing.assert_array_equal(
        env.unsat_parent_count, _golden_unsat(env)
    )
    sat_moved = env.stage_sat.astype(int) - before.stage_sat.astype(int)
    unsat_moved = env.unsat_parent_count - before.unsat_parent_count
    want_sat = np.zeros((2, 4), int)
    want_unsat = np.zeros((2, 4), int)
    if case == "shared_stage":
        assert (k_rel, k_rdy) == (2, 0)
        want_sat[0, 0], want_unsat[0, 1:3] = 1, -1
    elif case == "retargeted_finish":
        assert (k_rel, k_rdy) == (1, 1)
        want_sat[1, 0], want_unsat[1, 1:3] = 1, -1
    elif case == "park_unsaturates":
        assert (k_rel, k_rdy) == (0, 1)
        want_sat[1, 2], want_unsat[1, 3] = -1, 1
    else:
        assert k_rel >= 2 and k_rdy == 0
    np.testing.assert_array_equal(sat_moved, want_sat)
    np.testing.assert_array_equal(unsat_moved, want_unsat)


def test_collection_is_the_same_with_and_without_telemetry(monkeypatch):
    """The counters are pure adds beside the engine: a single-eval
    collection with `telemetry=None` is leaf-equal to one that carries
    them, `bulk_scan_steps` included, and the collection's counter is
    consistent with the events its bulk passes took."""
    import jax

    from sparksched_tpu.env import core
    from sparksched_tpu.obs import summarize
    from sparksched_tpu.obs.telemetry import telemetry_zeros_like
    from sparksched_tpu.trainers.rollout import collect_flat_sync_batch

    params, bank, make_sched = _decima_parity_fixture(monkeypatch)
    bpol = make_sched().flat_batch_policy(deterministic=True)
    keys = [jax.random.PRNGKey(3), jax.random.PRNGKey(5)]
    states = jax.tree_util.tree_map(
        lambda *a: jax.numpy.stack(a),
        *[core.reset(params, bank, k) for k in keys],
    )
    plain = collect_flat_sync_batch(
        params, bank, bpol, jax.random.PRNGKey(1), 60, states,
        fulfill_bulk=True,
    )
    counted, tm = collect_flat_sync_batch(
        params, bank, bpol, jax.random.PRNGKey(1), 60, states,
        telemetry_zeros_like((2,)), fulfill_bulk=True,
    )
    _assert_same_pass(plain, counted, "telemetry on against off")
    s = summarize(tm)
    bulk_events = s["bulk"]["relaunch_events"] + s["bulk"]["ready_events"]
    passes = s["phase_iters"]["bulk"]
    assert passes > 10
    # every pass that took events needed a step for each, and at most
    # one more; passes that took nothing needed one step each
    assert s["bulk_scan_steps_total"] >= bulk_events
    assert s["bulk_scan_steps_total"] <= bulk_events + s["phase_iters"][
        "event"]
    assert s["bulk_scan_steps_per_pass"] >= bulk_events / passes
