"""The guarantees of streaming (`benchmarks/reference/stream_np.py`,
part 1) on two consecutive collections of the program's
`collect_flat_async_batch` at a tiny size: every check passes on what
the collector stored, and each fails on a doctored copy."""

import numpy as np
import pytest

from benchmarks.reference import stream_np

GROUPS, ROLLOUTS, STEPS, BUDGET = 2, 2, 90, 1.2e6


@pytest.fixture(scope="module")
def stream():
    """Collections 1 and 2 of four persistent lanes (two sequence
    groups of two) under a round-robin policy, as the trainer lays the
    keys out, and what it takes to run a doctored second collection."""
    import jax
    import jax.numpy as jnp

    from sparksched_tpu.config import EnvParams
    from sparksched_tpu.env import core
    from sparksched_tpu.env.flat_loop import init_loop_state
    from sparksched_tpu.obs.telemetry import summarize, telemetry_zeros_like
    from sparksched_tpu.schedulers.heuristics import round_robin_policy
    from sparksched_tpu.trainers.rollout import collect_flat_async_batch
    from sparksched_tpu.workload import make_workload_bank

    params = EnvParams(
        num_executors=4, max_jobs=3, max_stages=20, max_levels=20,
        moving_delay=500.0, warmup_delay=200.0, mean_time_limit=2.0e6)
    bank = make_workload_bank(params.num_executors, params.max_stages)
    params = params.replace(
        max_stages=bank.max_stages, max_levels=bank.max_stages)

    def bpol(rng, obs):
        si, ne = jax.vmap(
            lambda o: round_robin_policy(o, params.num_executors, True)
        )(obs)
        return si, ne, {}

    master = jax.random.PRNGKey(11)
    lanes = GROUPS * ROLLOUTS
    g_ids = jnp.repeat(jnp.arange(GROUPS), ROLLOUTS)
    salts = (1000 + jnp.tile(jnp.arange(ROLLOUTS), GROUPS)).astype(jnp.int32)
    bases = jax.vmap(lambda g: jax.random.fold_in(master, g))(g_ids)
    states = jax.vmap(lambda b, s: core.reset_pair(
        params, bank, jax.random.fold_in(b, 0),
        jax.random.fold_in(jax.random.fold_in(b, 0), s)))(bases, salts)
    ls0 = jax.vmap(init_loop_state)(states)

    def collect(i, ls, counts):
        ro, ls, tm = collect_flat_async_batch(
            params, bank, bpol, jax.random.fold_in(master, 100 + i), STEPS,
            ls, jnp.float32(BUDGET), bases, salts, counts,
            telemetry_zeros_like((lanes,)))
        ro = jax.device_get(ro)
        col = {"valid": ro.valid, "wall_times": ro.wall_times,
               "resets": ro.resets,
               "final_reset_count": ro.final_reset_count,
               "job_template": ro.obs.job_template,
               "remaining": ro.obs.remaining,
               "node_mask": ro.obs.node_mask}
        col |= stream_np.last_valid_rows(
            ro.valid, ro.obs.remaining, ro.obs.node_mask)
        col["rows"] = lambda lane, c=col: (
            c["remaining"][lane], c["node_mask"][lane])
        return col, ls, summarize(tm)

    ones = jnp.ones((lanes,), jnp.int32)
    c0, ls1, _ = collect(0, ls0, ones)
    c1, ls2, s1 = collect(1, ls1, jnp.asarray(c0["final_reset_count"]))
    c2, _, s2 = collect(2, ls2, jnp.asarray(c1["final_reset_count"]))
    return {"c1": c1, "c2": c2, "s2": s2, "ls0": ls0, "ls2": ls2,
            "collect": collect}


def _check(prev, cur, summary=None):
    return stream_np.check_stream(
        prev, cur, rollout_duration=BUDGET, rollouts_per_group=ROLLOUTS,
        summary=summary)


def _failed(found: dict) -> set:
    return {k for k, v in found.items()
            if v and k != "stream_episodes_seen"}


def _copy(col: dict) -> dict:
    out = {k: (np.array(v) if isinstance(v, np.ndarray) else v)
           for k, v in col.items()}
    out["rows"] = lambda lane: (out["remaining"][lane],
                                out["node_mask"][lane])
    return out


def test_the_collector_keeps_every_guarantee_of_streaming(stream):
    c1, c2 = stream["c1"], stream["c2"]
    found = _check(c1, c2, stream["s2"])
    assert _failed(found) == set(), found
    assert found["stream_episodes_seen"] >= GROUPS
    # the fixture works every path: re-seeds inside the scan, lanes that
    # end on the budget, a summary with the counters
    assert c2["resets"].sum() >= 2
    assert (c2["valid"].sum(axis=1) < STEPS).any()
    assert stream["s2"]["reseeds_total"] == int(c2["resets"].sum())
    assert set(found) == {
        "stream_valid_not_prefix", "stream_rows_past_budget",
        "stream_unused_rows_under_budget", "stream_elapsed_not_monotone",
        "stream_reset_ordinal_not_handed_on",
        "stream_template_moved_without_reseed",
        "stream_remaining_rose_in_episode", "stream_group_sequence_split",
        "stream_sequence_repeated", "stream_episodes_seen",
        "stream_reset_flag_on_unused_row", "stream_decisions_gap",
        "stream_health_mask", "stream_reseeds_gap"}


def test_a_summary_without_the_new_counter_leaves_its_check_out(stream):
    old = {k: v for k, v in stream["s2"].items() if k != "reseeds_total"}
    found = _check(stream["c1"], stream["c2"], old)
    assert "stream_reseeds_gap" not in found and _failed(found) == set()
    assert "stream_decisions_gap" not in _check(stream["c1"], stream["c2"])


def test_a_valid_row_past_the_budget_fails(stream):
    bad = _copy(stream["c2"])
    lane = int(np.argmax(bad["valid"].sum(axis=1)))
    row = int(bad["valid"][lane].sum()) - 1
    bad["wall_times"][lane, row] = BUDGET + 1.0
    assert "stream_rows_past_budget" in _failed(_check(stream["c1"], bad))
    # and a lane that stopped early with budget left
    bad = _copy(stream["c2"])
    lane = int(np.argmin(bad["valid"].sum(axis=1)))
    bad["wall_times"][lane, -1] = BUDGET * 0.5
    assert "stream_unused_rows_under_budget" in _failed(
        _check(stream["c1"], bad))


def test_a_lane_restarted_from_reset_between_collections_fails(stream):
    """Collection 2 of lane 0 taken from a run that began at the reset
    states: the lane did not go on from where collection 1 stopped."""
    import jax.numpy as jnp

    restarted, _, _ = stream["collect"](
        2, stream["ls0"], jnp.asarray(stream["c1"]["final_reset_count"]))
    bad = _copy(stream["c2"])
    for k in ("valid", "wall_times", "resets", "final_reset_count",
              "job_template", "remaining", "node_mask"):
        bad[k][0] = restarted[k][0]
    failed = _failed(_check(stream["c1"], bad))
    assert failed & {"stream_remaining_rose_in_episode",
                     "stream_template_moved_without_reseed"}, failed


def test_a_groups_reseed_ordinal_shifted_by_one_fails(stream):
    """Collection 2 run with the second group's reset ordinals one too
    low: its next re-seed replays the episode it is in."""
    import jax.numpy as jnp

    counts = np.array(stream["c1"]["final_reset_count"])
    counts[ROLLOUTS:] -= 1
    shifted, _, _ = stream["collect"](2, stream["ls2"], jnp.asarray(counts))
    assert shifted["resets"][ROLLOUTS:].any(), "the group never re-seeded"
    failed = _failed(_check(stream["c1"], shifted))
    assert "stream_reset_ordinal_not_handed_on" in failed
    assert "stream_sequence_repeated" in failed


def test_one_sequence_for_every_group_fails(stream):
    bad = _copy(stream["c2"])
    bad["job_template"][ROLLOUTS:] = bad["job_template"][:ROLLOUTS]
    assert "stream_sequence_repeated" in _failed(_check(stream["c1"], bad))
    bad = _copy(stream["c2"])
    bad["job_template"][1] = (bad["job_template"][1] + 1) % 7
    assert "stream_group_sequence_split" in _failed(
        _check(stream["c1"], bad))


def test_a_dropped_resets_flag_fails(stream):
    bad = _copy(stream["c2"])
    lane, row = np.argwhere(bad["resets"])[0]
    bad["resets"][lane, row] = False
    failed = _failed(_check(stream["c1"], bad, stream["s2"]))
    assert {"stream_reseeds_gap",
            "stream_reset_ordinal_not_handed_on"} <= failed
