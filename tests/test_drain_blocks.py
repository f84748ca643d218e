"""The collectors' drain over blocks of lanes (PR 43,
`trainers/rollout.py`: `_DRAIN_BLOCK`, `_by_blocks`).

A vmapped `while` waits for the slowest of its lanes; lanes are
independent, so which lanes a lane waits for is no part of what it
stores. With the block patched small so that a CPU runs several blocks:
a blocked collection stores what the whole-batch one stores, bit for
bit under threefry keys, in all three modes; the counters say what the
device ran; and which path a collection takes follows from its lane
count alone.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparksched_tpu.trainers import rollout

BLOCK, LANES = 4, 8  # two blocks; a sequence group is a block's lanes
# what a blocked row leaves to the block: every other counter, leaf of
# the rollout and of the final state is the whole-batch collection's
BLOCK_COUNTERS = ("drain_batch_iters", "lane_syncs")


@contextlib.contextmanager
def drain_block(lanes: int):
    """`rollout._DRAIN_BLOCK` patched, and meanwhile both collectors as
    functions and `jit`s of their own, there and where the trainer
    imported them: the constant is no part of a `jit` key (which is the
    function and its arguments), so a collector traced under one value
    must not answer for another."""
    from sparksched_tpu.trainers import trainer

    def fresh(collector):
        @functools.wraps(collector)
        def own(*args, **kwargs):
            return collector(*args, **kwargs)

        return jax.jit(own, static_argnums=(0, 2, 4), static_argnames=(
            "event_bulk", "bulk_events", "fulfill_bulk", "bulk_cycles",
            "lane_shard", "bulk_fused", "health"))

    patch = pytest.MonkeyPatch()
    patch.setattr(rollout, "_DRAIN_BLOCK", lanes)
    for name in ("collect_flat_sync_batch", "collect_flat_async_batch"):
        own = fresh(getattr(rollout, name).__wrapped__)
        patch.setattr(rollout, name, own)
        patch.setattr(trainer, name, own)
    try:
        yield
    finally:
        patch.undo()


@functools.cache  # one policy object a mode: it is a static argument
def _cluster(batched: bool):
    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(
        num_executors=4, max_jobs=3, max_stages=20, max_levels=20,
        moving_delay=500.0, warmup_delay=200.0,
        **(dict(num_init_jobs=3, mean_time_limit=None) if batched else {}),
    )
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages
    )

    def bpol(rng, obs):
        si, ne = jax.vmap(
            lambda o: round_robin_policy(o, params.num_executors, True)
        )(obs)
        return si, ne, {}

    return params, bank, bpol


def _lanes():
    """A sequence base for each block's lanes and a salt a lane."""
    bases = jnp.repeat(
        jax.random.split(jax.random.PRNGKey(7), LANES // BLOCK), BLOCK, 0)
    return bases, 1000 + jnp.arange(LANES, dtype=jnp.int32)


def _fresh_states(params, bank):
    """Every lane at episode 0 of its group's sequence."""
    from sparksched_tpu.env import core

    def one(base, salt):
        seq = jax.random.fold_in(base, 0)
        return core.reset_pair(
            params, bank, seq, jax.random.fold_in(seq, salt))

    return jax.vmap(one)(*_lanes())


def _collect(mode: str, rows: int, states=None, carry=None):
    """One collection as the trainer makes it in `mode`; returns what is
    compared: `(rollout, final LoopState or None, telemetry)`."""
    from sparksched_tpu.env.flat_loop import init_loop_state
    from sparksched_tpu.obs.telemetry import telemetry_zeros_like

    params, bank, bpol = _cluster(mode == "batched")
    bases, salts = _lanes()
    if states is None:
        states = _fresh_states(params, bank)
    tm = telemetry_zeros_like((LANES,), episodes=mode == "batched")
    key = jax.random.PRNGKey(9)
    if mode != "stream":
        ro, tm = rollout.collect_flat_sync_batch(
            params, bank, bpol, key, rows, states, tm)
        return ro, None, tm
    ls, tm = carry or (jax.vmap(init_loop_state)(states), tm)
    return rollout.collect_flat_async_batch(
        params, bank, bpol, key, rows, ls, jnp.float32(2.0e7),
        seq_bases=bases, lane_salts=salts,
        reset_counts=jnp.ones((LANES,), jnp.int32), telemetry=tm)


def _unequal(a, b, but=()) -> list[str]:
    a, b = jax.device_get((a, b))
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    return [
        jax.tree_util.keystr(path)
        for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                                jax.tree_util.tree_leaves(b))
        if not any(name in jax.tree_util.keystr(path) for name in but)
        and not np.array_equal(np.asarray(x), np.asarray(y))
    ]


@pytest.fixture(scope="module")
def pairs():
    """Each mode's collection under one `while` for all the lanes and
    block by block, collected once for the tests below."""
    rows = {"sync": 40, "stream": 150, "batched": 60}
    with drain_block(4 * LANES):
        whole = {m: _collect(m, r) for m, r in rows.items()}
    with drain_block(BLOCK):
        blocked = {m: _collect(m, r) for m, r in rows.items()}
        # batched arrivals again with the first block's lanes already
        # at their episode's end (the same program: no new trace)
        mixed = jax.tree_util.tree_map(
            lambda e, f: jnp.concatenate([e[:BLOCK], f[BLOCK:]]),
            blocked["batched"][0].final_state,
            _fresh_states(*_cluster(True)[:2]))
        blocked["tail"] = _collect("batched", rows["batched"], states=mixed)
    return whole, blocked


@pytest.mark.parametrize("mode", ["sync", "stream", "batched"])
def test_a_blocked_collection_stores_what_the_whole_batch_one_stores(
    pairs, mode
):
    """Under threefry keys every leaf of the `Rollout`, of the final
    `LoopState` and every per-lane counter but the two that say how
    long a lane waited: in sync mode, in streaming mode with re-seeds
    inside the scan and a budget that freezes lanes, and under batched
    arrivals with lanes that end inside the scan."""
    from sparksched_tpu.obs.telemetry import summarize

    assert jax.config.jax_default_prng_impl == "threefry2x32"
    whole, blocked = pairs[0][mode], pairs[1][mode]
    assert _unequal(whole, blocked, but=BLOCK_COUNTERS) == []
    s, ro, tm = summarize(blocked[2]), blocked[0], blocked[2]
    assert s["decisions"] == int(np.asarray(ro.valid).sum()) > 4 * LANES
    if mode == "stream":
        assert s["reseeds_total"] >= LANES // 2  # re-seeds in the scan
        assert 0 < s["row"]["lane_rows_frozen"]  # and frozen lanes
        assert np.asarray(ro.resets).any()
    if mode == "batched":
        assert s["episodes_terminated_total"] == LANES  # all end inside
        assert s["row"]["lane_rows_ended"] > LANES
    # the lanes waited less: no lane for more bodies than under one
    # `while`, some block for fewer; and every lane of a block for as
    # many as the block's slowest
    b = np.asarray(tm.drain_batch_iters).reshape(-1, BLOCK)
    w = np.asarray(whole[2].drain_batch_iters)
    assert (b == b[:, :1]).all() and (w == w[0]).all()
    assert (b <= w[0]).all() and b.min() < w[0]
    assert (b[:, 0] >= np.asarray(tm.drain_iters).reshape(-1, BLOCK).max(1)
            ).all()
    row, row_w = s["row"], summarize(whole[2])["row"]
    assert row["drain_lane_iters_executed"] == int(b.sum())
    assert row["drain_batch_iters"] == b.sum() / LANES
    assert row_w["drain_lane_iters_executed"] == int(w[0]) * LANES
    assert row["drain_iters_total"] == row_w["drain_iters_total"]
    # the reductions over ALL the lanes a blocked row makes: `rows_live`
    # and, streaming, `reset_evals` (this policy has one width)
    assert row["lane_syncs"] == row["rows"] * (2 if mode == "stream" else 1)
    assert row_w["lane_syncs"] > 2 * row_w["drain_batch_iters"]


def test_a_block_whose_lanes_are_all_done_runs_no_body(pairs):
    """The padded tail: with the first block's lanes at their episode's
    end from row 0 and the second block's fresh, the first block's
    `while` never runs a body while the second's runs what it ran."""
    _, blocked = pairs
    tm, full = blocked["tail"][2], blocked["batched"][2]
    bodies = np.asarray(tm.drain_batch_iters)
    assert (bodies[:BLOCK] == 0).all() and (bodies[BLOCK:] > 0).all()
    assert (np.asarray(tm.decide_steps)[:BLOCK] == 0).all()
    np.testing.assert_array_equal(
        bodies[BLOCK:], np.asarray(full.drain_batch_iters)[BLOCK:])
    rows = int(np.asarray(tm.rows)[0])
    assert (np.asarray(tm.rows_ended)[:BLOCK] == rows).all()


def test_executed_bodies_against_a_count_made_by_hand():
    """A streaming collection a row at a time (collections of ONE row,
    the carry threaded through): in each row a block runs the bodies its
    slowest lane needs, which `drain_iters` gives lane by lane, so the
    bodies the device ran, a lane at a time, are the sum over rows and
    blocks of (the block's maximum x its lanes)."""
    from sparksched_tpu.obs.telemetry import summarize

    with drain_block(BLOCK):
        carry, by_hand, before = None, 0, np.zeros((LANES,), int)
        for _ in range(60):
            _, ls, tm = _collect("stream", 1, carry=carry)
            carry = (ls, tm)
            now = np.asarray(tm.drain_iters)
            by_hand += int(
                (now - before).reshape(-1, BLOCK).max(1).sum()) * BLOCK
            before = now
        row = summarize(tm)["row"]
    assert row["rows"] == 60 and by_hand > 60 * LANES
    assert row["drain_lane_iters_executed"] == by_hand
    assert row["drain_lane_iters_executed"] == int(
        np.asarray(tm.drain_batch_iters).sum())


def _scan_body(lanes: int, mode: str):
    """The scan body of the collector's jaxpr at `lanes` lanes."""
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import init_loop_state
    from sparksched_tpu.obs.telemetry import telemetry_zeros_like

    from .test_obs import _collection_scan_body

    params, bank, bpol = _cluster(False)
    states = jax.vmap(lambda k: core.reset(params, bank, k))(
        jax.random.split(jax.random.PRNGKey(3), lanes))
    steps = 5

    def collect(key, states, tm):
        if mode == "stream":
            return rollout.collect_flat_async_batch(
                params, bank, bpol, key, steps,
                jax.vmap(init_loop_state)(states), jnp.float32(1.0e6),
                telemetry=tm)
        return rollout.collect_flat_sync_batch(
            params, bank, bpol, key, steps, states, tm)

    return _collection_scan_body(jax.make_jaxpr(collect)(
        jax.random.PRNGKey(1), states, telemetry_zeros_like((lanes,))),
        steps)


DRAIN, RESET = "env/micro_step/drain", "env/micro_step/reset"


def _at_top(body, primitive: str, scope: str = DRAIN) -> list:
    return [e for e in body.eqns if e.primitive.name == primitive
            and scope in str(e.source_info.name_stack)]


def _carry_lanes(loop) -> set:
    """The leading extents of a vmapped `while`'s carry: its lanes."""
    consts = loop.params["cond_nconsts"] + loop.params["body_nconsts"]
    return {v.aval.shape[0] for v in loop.invars[consts:] if v.aval.shape}


@pytest.mark.parametrize("mode", ["sync", "stream"])
@pytest.mark.parametrize("lanes, blocks", [
    (BLOCK, 1), (BLOCK - 1, 0), (BLOCK + 2, 0), (2 * BLOCK + 1, 0),
    (2 * BLOCK, 2), (3 * BLOCK, 3),
])
def test_the_path_follows_from_the_lane_count(lanes, blocks, mode):
    """One block, fewer lanes than a block, or no whole number of
    blocks: the scan body holds the drain `while` itself, as it always
    did, and no loop around it (streaming: the re-seed's conditional
    beside it). A whole number of blocks greater than one: ONE loop
    over the blocks under the drain's scope, as long as there are
    blocks, and inside it the one `while` (and the one conditional): one
    copy of the drain, not one a block. The loop carries the arrays at
    their full width and a block is a slice of them."""
    with drain_block(BLOCK):
        body = _scan_body(lanes, mode)
    loops, whiles = _at_top(body, "scan"), _at_top(body, "while")
    conds = _at_top(body, "cond", RESET)
    if blocks < 2:
        assert not loops and len(whiles) == 1
        assert len(conds) == (mode == "stream")
        assert _carry_lanes(whiles[0]) == {lanes}
        return
    assert not whiles and not conds and len(loops) == 1
    assert loops[0].params["length"] == blocks
    inner = loops[0].params["jaxpr"].jaxpr
    assert len(_at_top(inner, "while")) == 1
    assert len(_at_top(inner, "cond", RESET)) == (mode == "stream")
    assert _carry_lanes(_at_top(inner, "while")[0]) == {BLOCK}
    # the blocks are sliced out of the full-width arrays and written
    # back inside that loop, so they are the drain's too and nothing
    # of the row falls under no scope for it
    assert {"dynamic_slice", "dynamic_update_slice"} <= {
        e.primitive.name for e in inner.eqns}
    bare = [e.primitive.name for e in body.eqns
            if not str(e.source_info.name_stack)]
    assert not {"scan", "dynamic_slice", "dynamic_update_slice"} & set(bare)
