"""The trainer's collector on a dp mesh: `collect_rollout`'s cell with
the lane axis sharded over the chips of one host, and one more
guarantee held: a lane's stored rollout does not depend on the mesh.

`build`, `warm_up`, `measure` and `close` are `collect_rollout`'s: the
configuration file's overrides lay `parallel.dp` over the program's
multi-chip configuration, so `make_trainer` builds the mesh and
`Trainer._collect_jit` is the sharded collector with its
`out_shardings`; the window is whole collections, the end-to-end metric
the valid decisions of all lanes over the window's wall time. `build`
first looks for the program's multi-chip configuration, as
`collect_stream` looks for its own: a program without it ends there.

`verify`, outside the window, on the window's LAST collection:

(a) the sentinels and counts of `collect_rollout`;
(b) a seeded sample of its stored decisions against the plain forward
    pass at the stated precision, by the mean gap and a high quantile
    of it (`benchmarks/logprob_check.py`, as every collector cell);
(c) the mesh guarantee. A second trainer, built from the same
    configuration with `parallel.dp: 1` and `rollout_steps: R` (the
    configuration's `limits.mesh_check_rows`), collects the same
    collection number under the same parameters and key on ONE chip,
    all lanes. The collector's scan is causal (row r reads nothing of a
    later row, and the key is split row by row), so its R rows are the
    first R rows of the sharded collection, and it is compared with the
    sharded rollout slot by slot, wherever the R-row run marks a slot
    valid (`mesh_gap`). The reference is built WITHOUT the `--control`:
    under `bf16_compute` the mesh's side is computed in bfloat16 and
    the one-chip side at the stated precision, a fault on one side,
    which (c) has to read as one.

    What the chip gives (PERF.md, PR 34): the two programs (512 lanes in
    one, 128 in each of four) are two compilations of the same float32
    arithmetic at the stated precision. A float32 intermediate that
    differs in its last bits between them, where it is rounded to
    bfloat16 as the next product's operand, now and then rounds the
    other way, and the log-prob of that evaluation moves by up to a few
    hundredths: the log-probs differ at about one slot in 800, and at no
    other. Every observation, action, time and reward was equal in
    every sound reading; but a sampled action is an argmax over scores,
    so a near tie can fall the other way and the lane's two histories
    part there. So the check holds, with no tolerance, what the mesh and
    the engine must keep: UP TO a lane's first slot at which the stored
    action differs, the observation and the time are equal at every
    slot, that slot included (the divergence is born in the sampling,
    not in the engine or in where a lane's data lives), and the reward
    and the reset flag at every slot before it. And three limits, each
    set between a sound reading and the one-sided fault's, both from
    the chip (the configuration's `limits.why`): the lanes whose
    actions part at all (`mesh_lanes_parted`), and over the slots
    before a parting the share whose log-probs differ at all
    (`mesh_lgprob_unequal_share`: rare in a sound run, nearly every
    slot under a fault of precision) and the mean gap
    (`mesh_lgprob_gap_mean`). The widest gap is printed and not
    compared: the largest of 50,000 draws of a rare rounding is a
    heavy tail whose sound range a fault's overlaps.

    One slot is left out of two leaves: a decision is stored at the
    lane's own slot `ndec`, and a span's reward and reset flag are
    added to the lane's LATEST slot, so rows after R can still add to
    the slot that is the lane's last at row R. (In a sync collection a
    lane that does not decide in a row is done or stuck and its span is
    empty, so what later rows add there is 0.0; the slot is left out
    for what the scatter can do, not for what it was seen to do.)
"""

from __future__ import annotations

import os.path as osp
import time

import numpy as np

from benchmarks import harness, logprob_check
from benchmarks.drivers import collect_rollout

HOST_SPANS = collect_rollout.HOST_SPANS
UNATTRIBUTED = collect_rollout.UNATTRIBUTED
# the per-decision leaves of a rollout: the stored action; what the
# lane saw before it (equal up to and at the slot where the actions
# part); what the span after it gave (equal before that slot; a later
# row may still add to the lane's latest slot); the log-prob
ACTION = ("stage_idx", "job_idx", "num_exec_k")
BEFORE_ACTION = ("obs", "wall_times")
AFTER_ACTION = ("reward", "resets")
LEAVES = ACTION + BEFORE_ACTION + AFTER_ACTION + ("lgprob",)


def build(cell: dict, seed: int, *, seconds: float = 0.0,
          control: dict | None = None, trace: bool = False) -> dict:
    conf = cell["config_data"]
    if not osp.exists(osp.join(harness.ROOT, conf["program_config"])):
        raise SystemExit(
            f"this program has no {conf['program_config']}: it cannot run "
            f"the mesh configuration of the cell {cell['name']}")
    ctx = collect_rollout.build(
        cell, seed, seconds=seconds, control=control, trace=trace)
    if ctx["trainer"].mesh is None:
        raise SystemExit(
            f"the cell {cell['name']} is a mesh's: its configuration "
            f"built a trainer without one")
    return ctx


warm_up = collect_rollout.warm_up
measure = collect_rollout.measure
close = collect_rollout.close


def verify(ctx: dict, window: dict) -> list[dict]:
    conf = ctx["cell"]["config_data"]
    limits = conf["limits"]
    params, ro = ctx["last"]
    checks = collect_rollout.sentinel_checks(ctx, window)
    checks += logprob_check.checks(
        ctx["trainer"], params, ro, ctx["seed"], conf)
    return checks + mesh_checks(
        ctx, window["scalars"][-1]["collection"],
        int(limits["mesh_check_rows"]), limits)


def _reference_build(ctx: dict, rows: int) -> dict:
    """The cell again with no mesh and `rows` rows: `collect_rollout`'s
    build with `parallel.dp: 1` laid over the mix, so its trainer is
    the unsharded collector on one device (under a directory of its
    own, which `close` removes)."""
    cell = ctx["cell"]
    mix = harness.merge(cell["mix"], {
        "rollout_steps": rows,
        "overrides": {"parallel": {"dp": 1},
                      "trainer": {"rollout_steps": rows}}})
    return collect_rollout.build(
        dict(cell, name=cell["name"] + ".one_chip", mix=mix), ctx["seed"])


class _CompileSeconds:
    """The seconds jax spent tracing, lowering and compiling (or
    loading from the cache) while armed, by the event's last name."""

    def __init__(self) -> None:
        import jax

        self.armed = True
        self.seconds: dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.armed:
            name = event.rsplit("/", 1)[-1]
            self.seconds[name] = self.seconds.get(name, 0.0) + float(duration)


def reference_rollout(ctx: dict, collection: int, rows: int) -> tuple:
    """Collection number `collection` again, `rows` rows of it on ONE
    device under the parameters and key of the sharded one: its
    per-decision leaves, moved to the mesh (a lane to the chip that
    holds the lane, so the comparison moves nothing of the 4.4 GB), and
    the seconds of building, of jax's tracing, lowering and compiling
    or loading, and of the call as a whole."""
    import jax
    import jax.numpy as jnp

    params = ctx["last"][0]
    t0 = time.perf_counter()
    reference = _reference_build(ctx, rows)
    t_built = time.perf_counter()
    one = jax.devices()[0]
    rng = jax.random.fold_in(harness.key_from_seed(ctx["seed"]), collection)
    compiling = _CompileSeconds()
    ro_ref = reference["trainer"]._collect_jit(
        jax.device_put(params, one), jnp.int32(collection), rng, None)[0]
    jax.block_until_ready(ro_ref.reward)
    compiling.armed = False
    close(reference)
    seconds = dict(compiling.seconds, build=t_built - t0,
                   collect=time.perf_counter() - t_built)
    leaves = jax.device_put(
        {k: getattr(ro_ref, k) for k in LEAVES + ("valid",)},
        ctx["trainer"]._lane_sharding)
    return leaves, seconds


def mesh_checks(ctx: dict, collection: int, rows: int,
                limits: dict) -> list[dict]:
    """(c) of the module docstring: collection number `collection`
    again, `rows` rows of it on one device, against the sharded rollout
    the window kept."""
    import jax

    ro = ctx["last"][1]
    ro_ref, seconds = reference_rollout(ctx, collection, rows)
    t0 = time.perf_counter()
    found = jax.device_get(jax.jit(mesh_gap)(
        ro_ref, {k: getattr(ro, k) for k in LEAVES + ("valid",)}))
    before = max(int(found["slots_before_parting"]), 1)
    per_lane = np.asarray(found["lgprob_unequal_per_lane"])
    chips = ctx["trainer"].mesh.devices.size
    harness.say(mesh_check={
        "rows": rows, "collection": collection,
        "slots": int(found["slots"]),
        "slots_before_parting": int(found["slots_before_parting"]),
        "first_parting_slot": int(found["first_parting_slot"]),
        "lgprob_unequal_slots": int(per_lane.sum()),
        "lgprob_unequal_lanes": int((per_lane > 0).sum()),
        "lgprob_unequal_by_chip": per_lane.reshape(chips, -1).sum(1).tolist(),
        "lgprob_gap_sum": float(found["lgprob_gap_sum"]),
        "lgprob_gap_max": float(found["lgprob_gap_max"]),
        "seconds": dict(seconds, compare=time.perf_counter() - t0)})
    return [
        harness.check("mesh_slots", int(found["slots"]), 1, ">="),
        harness.check("mesh_valid_lost", int(found["valid_lost"]), 0, "=="),
    ] + [harness.check(f"mesh_unequal.{k}", int(found["unequal"][k]), 0,
                       "==") for k in BEFORE_ACTION + AFTER_ACTION] + [
        harness.check("mesh_lanes_parted", int(found["lanes_parted"]),
                      int(limits["mesh_lanes_parted"]), "<="),
        harness.check("mesh_lgprob_unequal_share",
                      int(per_lane.sum()) / before,
                      float(limits["mesh_lgprob_unequal_share"]), "<="),
        harness.check("mesh_lgprob_gap_mean",
                      float(found["lgprob_gap_sum"]) / before,
                      float(limits["mesh_lgprob_gap_mean"]), "<=")]


def mesh_gap(ref: dict, ro: dict) -> dict:
    """The reference rollout `ref` (R rows) against the rollout `ro`
    (at least R rows), over the slots `ref` marks valid; `ref` and `ro`
    are dicts of `LEAVES` and `valid`, lane-leading; jit-able.

    A lane PARTS at its first slot at which a leaf of `ACTION` differs.
    `unequal[k]` counts the (lane, slot) pairs at which leaf `k` holds
    different bits: for `BEFORE_ACTION` up to and at the lane's parting
    slot, for `AFTER_ACTION` before it and but for the lane's last
    valid slot (which `ro`'s later rows may have added to); any of them
    is a fault of the engine or of where a lane's data lives. Also: the
    lanes that part and the first slot at which one does (-1: none),
    the slots compared and those before a parting, the valid slots of
    `ref` up to a parting that `ro` does not mark valid, and over the slots before a
    parting the widest and the summed gap of the log-probs and, lane by
    lane, the slots at which they differ at all."""
    import jax
    import jax.numpy as jnp

    valid = ref["valid"]
    rows = valid.shape[1]
    slot = jnp.arange(rows)[None, :]

    def differ(k):
        def one(a, b):
            d = a[:, :rows] != b[:, :rows]
            return d.reshape(d.shape[:2] + (-1,)).any(-1)

        return valid & jax.tree_util.tree_reduce(
            jnp.logical_or, jax.tree_util.tree_map(one, ref[k], ro[k]))

    parted = differ(ACTION[0])
    for k in ACTION[1:]:
        parted |= differ(k)
    at = jnp.where(parted, slot, rows).min(1, keepdims=True)  # [B, 1]
    before = valid & (slot < at)
    # the lane's last valid slot: valid, and the next one is not
    last = valid & ~jnp.pad(valid[:, 1:], ((0, 0), (0, 1)))
    unequal = {k: (differ(k) & (slot <= at)).sum() for k in BEFORE_ACTION}
    unequal |= {k: (differ(k) & before & ~last).sum() for k in AFTER_ACTION}
    gap = jnp.where(
        before, jnp.abs(ref["lgprob"] - ro["lgprob"][:, :rows]), 0.0)
    lanes_parted = (at < rows).sum()
    return {
        "unequal": unequal,
        "lanes_parted": lanes_parted,
        "first_parting_slot": jnp.where(lanes_parted > 0, at.min(), -1),
        "lgprob_gap_max": gap.max(),
        "lgprob_gap_sum": gap.sum(),
        "lgprob_unequal_per_lane": (gap > 0).sum(1),
        "slots": valid.sum(),
        "slots_before_parting": before.sum(),
        "valid_lost": (
            valid & ~ro["valid"][:, :rows] & (slot <= at)).sum(),
    }
