"""Memory pass (sparksched_tpu/analysis/memory + obs/memory): the
tile-padded size model, seeded bank-broadcast fixtures (the rule must
fire on a lane-batched bank producer and stay silent on the hoisted
form), the bytes-budget regression path (CLI rc != 0 naming program +
buffer), and the lane-fit advisor replaying the round-5 19.4 GB OOM
without a chip."""

from __future__ import annotations

import json

import pytest


@pytest.fixture(scope="module")
def bank():
    from sparksched_tpu.analysis.jaxpr_audit import audit_setup

    return audit_setup()[1]


# ---------------------------------------------------------------------------
# the tiled-layout size model
# ---------------------------------------------------------------------------


def test_aval_bytes_tile_padding():
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.obs.memory import aval_bytes

    a = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert aval_bytes(a, tile_pad=False) == 8 * 16 * 4
    # minor dim lane-padded 16 -> 128; second-minor 8 is already a
    # full f32 sublane (32 bytes / 4)
    assert aval_bytes(a) == 8 * 128 * 4
    # the round-5 temp: f32[512,154,20,3,8,16] = 2.4 GB dense but
    # 19.4 GB tile-padded — the 8x minor-dim inflation that put it
    # over the 17.2 GB part
    big = jax.ShapeDtypeStruct((512, 154, 20, 3, 8, 16), jnp.float32)
    assert round(aval_bytes(big, tile_pad=False) / 1e9, 1) == 2.4
    assert round(aval_bytes(big) / 1e9, 1) == 19.4


def test_aval_bytes_int8_minor_dim_padding():
    """ISSUE 7: narrower dtypes change the tiled-layout padding math.
    The sublane row count scales INVERSELY with itemsize (8 rows for
    4-byte dtypes, 16 for 2-byte, 32 for 1-byte), so at the workload
    bank's narrow [..., 8, 16] tail the padding exactly cancels the
    dtype width — an int8/int16 dur table is NOT smaller than f32
    under the tile model — while tile-aligned shapes keep the full
    width win. The lane-count headroom of the low-precision layout
    therefore comes from the lane-scaled bf16 observation buffers, not
    the resident bank (PERF_ROUNDS.md round 11)."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.obs.memory import aval_bytes

    # [8,16] tails: minor 16 -> 128 always; second-minor pads to the
    # 32-byte sublane, i.e. 8 rows f32 / 16 rows i16 / 32 rows i8 —
    # identical padded bytes across all three widths
    for dt, rows in ((jnp.float32, 8), (jnp.int16, 16), (jnp.int8, 32)):
        a = jax.ShapeDtypeStruct((8, 16), dt)
        assert aval_bytes(a) == rows * 128 * jnp.dtype(dt).itemsize
        assert aval_bytes(a) == 4096
    # ... and the bank's actual dur tail behaves the same way: the
    # tile-padded dur table is dtype-INVARIANT at (..., 8, 16)
    shapes = {}
    for dt in (jnp.float32, jnp.int16, jnp.int8):
        big = jax.ShapeDtypeStruct((154, 20, 3, 8, 16), dt)
        shapes[str(dt)] = aval_bytes(big)
    assert len(set(shapes.values())) == 1, shapes
    # tile-aligned shapes get the full dtype-width win (4x for int8)
    f = aval_bytes(jax.ShapeDtypeStruct((256, 256), jnp.float32))
    i = aval_bytes(jax.ShapeDtypeStruct((256, 256), jnp.int8))
    assert f == 4 * i
    # unpadded (linear-layout) bytes DO shrink 4x for the bank tail —
    # the honest statement of where int8 helps (host RAM, transfer)
    assert aval_bytes(
        jax.ShapeDtypeStruct((154, 20, 3, 8, 16), jnp.int8),
        tile_pad=False,
    ) * 4 == aval_bytes(
        jax.ShapeDtypeStruct((154, 20, 3, 8, 16), jnp.float32),
        tile_pad=False,
    )


# ---------------------------------------------------------------------------
# bank-broadcast rule: seeded violation + hoisted-form negative
# ---------------------------------------------------------------------------


def _lane_pred_struct():
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct((), jnp.float32)


def test_bank_broadcast_fires_on_lane_batched_producer(bank):
    import jax.numpy as jnp
    from jax import lax

    from sparksched_tpu.analysis.memory import check_bank_broadcast
    from sparksched_tpu.obs.memory import _trace_vmapped

    def bad(x):
        # the pre-81e77fb pattern: a bank table inside a lane-dependent
        # branch. cond's batching rule broadcasts the operands when the
        # predicate is lane-dependent, so the vmapped jaxpr contains a
        # per-lane copy of the dur table.
        return lax.cond(
            x > 0, lambda: bank.dur, lambda: jnp.zeros_like(bank.dur)
        ).sum()

    closed = _trace_vmapped(bad, (_lane_pred_struct(),), 4)
    vs = check_bank_broadcast("fixture", closed, bank, 4)
    assert vs, "the seeded lane-batched dur producer did not fire"
    assert all(v.rule == "bank-broadcast" for v in vs)
    # the report names the table and the hoist remedy, not a bare shape
    assert any("dur" in v.detail for v in vs)
    assert any("hoist" in v.detail for v in vs)


def test_bank_broadcast_clears_on_hoisted_form(bank):
    from jax import lax

    from sparksched_tpu.analysis.memory import check_bank_broadcast
    from sparksched_tpu.obs.memory import _trace_vmapped

    def good(x):
        # the 81e77fb fix pattern: the bank access is hoisted out of
        # the lane-dependent branch; the cond only carries scalars
        d = bank.dur.sum()
        return lax.cond(x > 0, lambda: d, lambda: d * 0.0)

    closed = _trace_vmapped(good, (_lane_pred_struct(),), 4)
    assert check_bank_broadcast("fixture", closed, bank, 4) == []


def test_bank_broadcast_rule_covers_quantized_bank(bank):
    """ISSUE 7: the bank-broadcast rule must keep working on the
    low-precision bank layout — the hazard SHAPES are dtype-blind, so a
    lane-batched producer of the int16 dur table fires exactly like the
    f32 one, and the hoisted micro-step stays clean when driven by a
    quantized bank."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from sparksched_tpu.analysis.jaxpr_audit import audit_setup
    from sparksched_tpu.analysis.memory import check_bank_broadcast
    from sparksched_tpu.obs.memory import _trace_vmapped
    from sparksched_tpu.workload import quantize_bank

    qbank = quantize_bank(bank, "int16")

    def bad(x):
        return lax.cond(
            x > 0, lambda: qbank.dur,
            lambda: jnp.zeros_like(qbank.dur),
        ).sum()

    closed = _trace_vmapped(bad, (_lane_pred_struct(),), 4)
    vs = check_bank_broadcast("fixture", closed, qbank, 4)
    assert vs and all(v.rule == "bank-broadcast" for v in vs)
    assert any("dur" in v.detail for v in vs)

    # the real engine on the quantized bank: hoisted, no violations
    # (this is the "bank-broadcast rule must pass on the quantized
    # bank" acceptance line — the per-template dur_scale gather at the
    # sampling site must not smuggle a table into a lane branch)
    from sparksched_tpu.env.flat_loop import init_loop_state, micro_step
    from sparksched_tpu.schedulers.heuristics import round_robin_policy

    params, _, state = audit_setup()

    def pol(rng, obs):
        si, ne = round_robin_policy(obs, params.num_executors, True)
        return si, ne, {}

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    ls = jax.eval_shape(init_loop_state, state)
    closed = _trace_vmapped(
        lambda l, r: micro_step(
            params, qbank, pol, l, r, True, False, True, 8, True, 1
        ),
        (ls, key), 4,
    )
    assert check_bank_broadcast("micro_step[int16]", closed, qbank,
                                4) == []


def test_lane_fit_quantized_layout_strictly_more_lanes():
    """ISSUE 7 acceptance: under the 17.2 GB per-chip budget the
    low-precision layout (int16 dur bank + bf16 observation features,
    `obs_dtype`) must fit STRICTLY more recording-collector lanes than
    the f32 layout. The win comes from the lane-scaled rollout-obs
    buffers (`StoredObs.duration` bf16 halves its bytes);
    the resident bank's tile-padded bytes are dtype-invariant at its
    [...,8,16] tail (see test_aval_bytes_int8_minor_dim_padding)."""
    import jax

    from sparksched_tpu.analysis.jaxpr_audit import _batched, audit_setup
    from sparksched_tpu.env import core
    from sparksched_tpu.obs.memory import TPU_HBM_BUDGET_BYTES, lane_fit
    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.trainers.rollout import collect_flat_sync_batch
    from sparksched_tpu.workload import quantize_bank

    params32, bank32, _ = audit_setup()
    params16 = params32.replace(obs_dtype="bfloat16")
    bank16 = quantize_bank(bank32, "int16")

    T = 768  # recorded decision rows: the [T,...] obs buffers are the
    # lane-scaled bytes the layout halves, so T sets the per-lane
    # slope — sized so the 17.2 GB crossing lands mid-candidate-range
    # (~1100 f32 lanes at audit shapes; the stored node grids are flat
    # [T,F] rows, which tile without padding, so a row costs a fifth
    # of the [T,J,S] grid this test was first sized for)

    def make_fit(params, bank):
        def bpol(rng, obs):
            si, ne = jax.vmap(lambda o: round_robin_policy(
                o, params.num_executors, True))(obs)
            return si, ne, {}

        key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        state = jax.eval_shape(
            lambda k: core.reset(params, bank, k), key
        )

        # the collector takes the lane axis itself: trace it at each
        # base width in place of a vmap over a per-lane program
        def tracer(lanes):
            return jax.make_jaxpr(
                lambda s, r: collect_flat_sync_batch(
                    params, bank, bpol, r, T, s)
            )(_batched(state, lanes), key)

        return lane_fit(
            tracer=tracer,
            candidates=tuple(range(256, 2049, 32)),
            budget_bytes=TPU_HBM_BUDGET_BYTES,
        )

    fit32 = make_fit(params32, bank32)
    fit16 = make_fit(params16, bank16)
    assert fit32["max_lanes_fit"] > 0
    assert fit16["max_lanes_fit"] > fit32["max_lanes_fit"], (
        f"quantized layout fits {fit16['max_lanes_fit']} lanes vs "
        f"f32 {fit32['max_lanes_fit']} — expected strictly more under "
        f"{TPU_HBM_BUDGET_BYTES / 1e9:.1f} GB"
    )


# ---------------------------------------------------------------------------
# bytes budget: regression fixture through the real CLI entry point
# ---------------------------------------------------------------------------


def test_mem_budget_breach_fails_with_named_buffer(monkeypatch, capsys):
    from sparksched_tpu.analysis import memory
    from sparksched_tpu.analysis.__main__ import main

    monkeypatch.setitem(
        memory.MEM_BUDGETS, "observe", memory.MemBudget(temp_hi=1)
    )
    rc = main(["--passes", "memory", "--programs", "observe"])
    assert rc != 0
    report = json.loads(capsys.readouterr().out)
    assert report["clean"] is False
    v = report["violations"][0]
    assert v["rule"] == "mem-budget" and v["where"] == "observe"
    # the report names the dominant buffer (op + shape), not a bare
    # byte count — the attribution requirement of the tentpole
    assert "largest buffer" in v["detail"]


def test_unknown_program_name_is_an_error():
    from sparksched_tpu.analysis.memory import audit_memory

    with pytest.raises(ValueError, match="not_a_program"):
        audit_memory(names=("not_a_program",))


def test_memory_pass_reports_accounting_and_lane_fit():
    from sparksched_tpu.analysis.memory import audit_memory

    vs, measured = audit_memory(names=("observe",))
    assert vs == []
    m = measured["observe"]
    for key in ("temp_total_bytes", "args_bytes", "out_bytes",
                "peak_lower_bound_bytes", "largest"):
        assert key in m
    assert m["largest"] and {"bytes", "shape", "op"} <= set(
        m["largest"][0]
    )
    # observe is a lane program: the advisor must report its fit, and
    # the tiny per-lane observation comfortably fits the full 1024-lane
    # production width under the default budget
    assert m["lane_fit"]["max_lanes_fit"] >= 1024


# ---------------------------------------------------------------------------
# lane-fit advisor: the round-5 incident, replayed on CPU
# ---------------------------------------------------------------------------


def test_lane_fit_replays_round5_oom(bank):
    import jax.numpy as jnp
    from jax import lax

    from sparksched_tpu.obs.memory import TPU_HBM_BUDGET_BYTES, lane_fit

    # the audit bank's dur table IS the incident table's shape
    assert tuple(bank.dur.shape) == (154, 20, 3, 8, 16)

    def pre_fix(x):
        # pre-81e77fb: _bulk_fulfill's dur gather inside the
        # lane-dependent decide branch
        return lax.cond(
            x > 0, lambda: bank.dur, lambda: jnp.zeros_like(bank.dur)
        ).sum()

    fit = lane_fit(
        pre_fix, (_lane_pred_struct(),), candidates=(64, 512, 1024),
        budget_bytes=TPU_HBM_BUDGET_BYTES,
    )
    by_lanes = {c["lanes"]: c for c in fit["candidates"]}
    # the regression the chip found: 512 lanes do NOT fit 17.2 GB
    assert not by_lanes[512]["fits"]
    assert fit["max_lanes_fit"] < 512
    # and the report names the offending table at its headline size:
    # the dominant buffer is the six-dim per-lane dur copy, 19.4 GB
    # tile-padded at 512 lanes (so est_peak is at least that)
    assert by_lanes[512]["est_peak_bytes"] >= 19.3e9
    top = by_lanes[512]["top"]
    assert "154,20,3,8,16" in top["shape"]

    def post_fix(x):
        # hoisted: the gather happens once, outside the branch
        d = bank.dur.sum()
        return lax.cond(x > 0, lambda: d, lambda: d * 0.0)

    fit2 = lane_fit(
        post_fix, (_lane_pred_struct(),), candidates=(512, 1024),
        budget_bytes=TPU_HBM_BUDGET_BYTES,
    )
    assert fit2["max_lanes_fit"] >= 1024


def test_lane_fit_linear_model_matches_direct_trace(bank):
    """The two-point linear model must agree with a direct trace at an
    off-base lane count (vmap batching is linear in lanes, so the fit
    is exact — a mismatch means the model mis-reads the jaxpr)."""
    import jax.numpy as jnp

    from sparksched_tpu.obs.memory import (
        _trace_vmapped,
        jaxpr_memory_estimate,
        lane_fit,
    )

    def fn(x):
        return (x * 2.0 + jnp.float32(1.0)).sum()

    args = (jnp.zeros((8, 16), jnp.float32),)
    fit = lane_fit(fn, args, candidates=(64,))
    direct = jaxpr_memory_estimate(_trace_vmapped(fn, args, 64))
    est = fit["candidates"][0]["est_peak_bytes"]
    assert est == direct["peak_lower_bound_bytes"]


# ---------------------------------------------------------------------------
# ISSUE 13: the hot-set capacity model behind the session pager
# ---------------------------------------------------------------------------


def test_hot_set_fit_monotone_in_hot_capacity():
    """`hot_set_fit` (the lane-fit advisor's serving analog) must be
    MONOTONE in hot capacity: estimated bytes nondecreasing, `fits`
    antitone, and `max_hot_fit` exactly the largest fitting candidate
    — the pager sizes the device store off these predictions, so a
    non-monotone model could report a larger hot set as cheaper than
    a smaller one. Also pins the fixed-cost shift (a bigger replicated
    bank never increases the fitting hot set) and the per-device dp
    mode (sharding the [H] axis over dp chips fits at least as many
    GLOBAL slots as one chip does)."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.obs.memory import hot_set_fit

    slot = {
        "env": jax.ShapeDtypeStruct((154, 20, 8), jnp.float32),
        "adj": jax.ShapeDtypeStruct((20, 20, 20), jnp.bool_),
        "mode": jax.ShapeDtypeStruct((), jnp.int32),
    }
    cands = (8, 16, 32, 64, 128, 256)
    budget = 2 * 10**9
    fit = hot_set_fit(slot, candidates=cands, budget_bytes=budget)
    ests = [c["est_bytes"] for c in fit["candidates"]]
    fits = [c["fits"] for c in fit["candidates"]]
    assert [c["hot"] for c in fit["candidates"]] == sorted(cands)
    assert ests == sorted(ests), "est bytes must be nondecreasing"
    # fits is a prefix: once a hot set misses the budget, every larger
    # one does too
    assert fits == sorted(fits, reverse=True)
    fitting = [c["hot"] for c in fit["candidates"] if c["fits"]]
    assert fit["max_hot_fit"] == (max(fitting) if fitting else 0)
    assert fit["slot_bytes"] > 0

    # fixed cost shifts the whole curve up — never down
    heavier = hot_set_fit(
        slot, candidates=cands, budget_bytes=budget,
        fixed_bytes=10**9,
    )
    for a, b in zip(fit["candidates"], heavier["candidates"]):
        assert b["est_bytes"] == a["est_bytes"] + 10**9
    assert heavier["max_hot_fit"] <= fit["max_hot_fit"]

    # dp mode: each chip holds ceil(H/dp) slots, so the same global
    # candidates cost per-device no more than single-chip
    dp2 = hot_set_fit(
        slot, candidates=cands, budget_bytes=budget, dp=2
    )
    for a, b in zip(fit["candidates"], dp2["candidates"]):
        assert b["hot_per_device"] == -(-a["hot"] // 2)
        assert b["est_bytes"] <= a["est_bytes"]
    assert dp2["max_hot_fit"] >= fit["max_hot_fit"]
