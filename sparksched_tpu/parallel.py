"""Multi-chip scale-out over a `jax.sharding.Mesh`.

The reference's only parallelism is process-level fan-out of rollout
workers glued with `mp.Pipe` (reference trainers/trainer.py:264-296).
The TPU-native equivalent has two layers:

- on-chip: `jax.vmap` already runs thousands of env lanes per core — that
  alone replaces the reference's N worker processes;
- across chips: the lane axis is sharded over a 1-D `dp` mesh axis with
  `NamedSharding(P("dp"))`. The PPO update's global minibatch
  permutation, advantage normalization and gradient reduction become XLA
  collectives (all-gather / psum) over ICI — no NCCL, no parameter
  scatter, no pickling. Multi-host works the same way: the mesh simply
  spans hosts and the same collectives ride DCN.

Rollout collection moves no lane's data to another chip, but it is NOT
free of cross-lane operations: the collector is one program under `jit`
with sharding constraints, so every reduction over the lane axis that
it makes outside a `shard_map` is an all-reduce of a scalar across the
chips, on the critical path. Two parts of a row run inside `shard_map`,
a device at a time over its own lanes (`rollout._on_own_lanes`): the
row store, and the drain where a device's share of the lanes is whole
blocks of `rollout._DRAIN_BLOCK` lanes (512 lanes on four chips: one
block of 128 a chip). The drain's loops then end on predicates of the
device's own blocks and hold NO collective; what such a row still
reduces over all the lanes is the last group below less the counters'
maximum and the re-seed's predicate. Where a device's share is not
whole blocks (16 lanes a device in the tests) the whole batch drains
under one `while`, and a decision row makes all of these
(`Telemetry.lane_syncs` counts them, `collector_collectives` below
holds the compiled program to them):

- the predicate of the fused bulk pass's early-exit loop, `lax.pmax`
  over the lanes, once an iteration of that loop in every body of the
  drain (`env/core.py: _steps_while_active`, PR 28);
- the batched predicate of the drain's vmapped `while`, an `any` over
  the lanes, once a body (`flat_loop.drain_to_decision`);
- once a row: the policy's full-width predicate
  (`DecimaScheduler.full_width`), the maximum over lanes that gives
  `drain_batch_iters`, `rows_live`'s `any`; streaming adds
  `reset_evals`' `any` and the re-seed's predicate
  (`flat_loop._reseed_ended`, PR 31).

Under rbg keys (`fast_prng`) jax draws a vmapped batch of random bits
from the FIRST lane's key, so each such draw (two in a drain body, one in
a decide step) is also a broadcast of that key from the chip that holds
lane 0: the partitioner lowers it to an all-reduce of the key's four
words. `lane_syncs` does not count those (they reduce nothing); the
trace does (PERF.md, PR 34). A drain that runs block by block draws a
block's bits from the BLOCK's first lane, on the mesh and on one chip
alike: under these keys the blocks are part of what a collection
stores, and a mesh's collection equals the one-chip collection of the
same lanes because both run the same blocks
(`tests/test_parallel.py`); the drain's two broadcasts a body are gone
with its collectives, the decide step's stays.
"""

from __future__ import annotations

import math
import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DP_AXIS = "dp"
HOST_AXIS = "host"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D data-parallel mesh over the first `n_devices` devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        assert len(devices) >= n_devices, (
            f"need {n_devices} devices, have {len(devices)}"
        )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (DP_AXIS,))


def make_host_device_mesh(
    n_hosts: int | None = None, devices_per_host: int | None = None,
    devices=None,
) -> Mesh:
    """2-D ("host", "dp") mesh for multi-host runs.

    Lanes shard over BOTH axes (`lane_sharding` spans every mesh axis),
    so rollout collection moves no lane's data (its scalar reductions
    over the lanes cross both axes: module docstring); the update's
    reductions become hierarchical collectives — XLA reduces along the
    fast "dp" (intra-host ICI) axis before the "host" (DCN) axis, which
    is exactly the hierarchy the reference's per-process workers + one
    learner lacked. Defaults follow jax's process topology
    (`jax.process_count()` x local device count); pass explicit factors
    to build a virtual multi-host mesh on a flat device list (tests)."""
    if devices is None:
        devices = jax.devices()
    if n_hosts is None:
        n_hosts = jax.process_count()
    if devices_per_host is None:
        devices_per_host = len(devices) // n_hosts
    need = n_hosts * devices_per_host
    assert len(devices) >= need, (
        f"need {need} devices, have {len(devices)}"
    )
    # jax.devices() order does not guarantee per-host contiguity on all
    # topologies; group by owning process first so each mesh row really
    # is one host's chips (otherwise "dp" reductions silently cross DCN
    # and the hierarchy claim above inverts)
    devices = sorted(devices, key=lambda d: (d.process_index, d.id))
    grid = np.array(devices[:need]).reshape(n_hosts, devices_per_host)
    if jax.process_count() > 1:
        for row in grid:
            assert len({d.process_index for d in row}) == 1, (
                "a host row mixes devices from different processes — "
                "pass explicit per-host `devices`"
            )
    return Mesh(grid, (HOST_AXIS, DP_AXIS))


def lane_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (env-lane) axis over every mesh axis (1-D dp
    meshes and 2-D host x device meshes alike)."""
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_lanes(tree, mesh: Mesh):
    """Place a [B, ...] pytree with its lane axis sharded over the mesh."""
    return jax.device_put(tree, lane_sharding(mesh))


def constrain_lanes(tree, sharding: NamedSharding):
    """`with_sharding_constraint` every leaf's leading (lane) axis —
    applied to the collection scan's carry buffers so XLA's SPMD
    partitioner keeps them lane-sharded instead of falling back to a
    replicated layout mid-scan (every leaf must carry a leading [B]
    axis; scalars like the scan's PRNG key stay outside the tree)."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.with_sharding_constraint(a, sharding), tree
    )


# ---------------------------------------------------------------------------
# config wiring: the `parallel:` YAML block
# ---------------------------------------------------------------------------


def mesh_from_config(cfg: dict[str, Any] | None) -> Mesh | None:
    """Resolve the top-level `parallel:` config block to a mesh.

    Contract (config/decima_tpch_multichip.yaml documents the YAML
    side): `dp: auto` takes every visible device; `dp: N` demands
    exactly N and fails loudly when the host has fewer (a silent
    single-chip fallback would report sharded dec/s that never
    sharded). A resolved dp of 1 returns None — the unsharded jit path
    is the same program without the sharding plumbing, and a 1-device
    mesh would only add layout bookkeeping."""
    if not cfg:
        return None
    dp = cfg.get("dp", "auto")
    if dp in ("auto", None):
        dp = len(jax.devices())
    dp = int(dp)
    if dp <= 1:
        return None
    return make_mesh(dp)


# ---------------------------------------------------------------------------
# collective census: the HLO-level contract of the sharded update
# ---------------------------------------------------------------------------

_COLLECTIVE_FAMILIES = (
    "all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
)
COLLECTIVE_RE = re.compile(rf"\b({_COLLECTIVE_FAMILIES})\b")

# what the shard-aligned update is ALLOWED to lower to: the gradient /
# advantage-normalization reductions (all-reduce), their occasional
# reduce-scatter re-association, and the small gathers of per-shard
# scalars (KL early-stop predicate, loss means)
EXPECTED_UPDATE_COLLECTIVES = frozenset(
    {"all-reduce", "all-gather", "reduce-scatter"}
)
# what it must NEVER contain: resharding families. An all-to-all or
# collective-permute in the update means the minibatch permutation
# stopped being shard-aligned (e.g. someone reintroduced a global
# B*T shuffle) and every grad step now pays a full rollout reshuffle
# over ICI/DCN — the regression tests/test_parallel.py's census pins.
FORBIDDEN_UPDATE_COLLECTIVES = frozenset(
    {"all-to-all", "collective-permute"}
)


# what the sharded COLLECTOR may lower to: all-reduces, each of a few
# words (the scalar reductions over the lane axis listed in the module
# docstring, the compiler's combinations of them, and the rbg key
# broadcasts). Anything else (all-gather, all-to-all,
# collective-permute, reduce-scatter), or an all-reduce of an array as
# long as the lane axis, moves or replicates lane-sized data.
EXPECTED_COLLECT_COLLECTIVES = frozenset({"all-reduce"})
# the widest all-reduce the collector may hold, in elements: a combined
# tuple of rbg keys (three keys of four words, the reset's draws)
COLLECT_ALL_REDUCE_MAX_ELEMENTS = 16

_COLLECTIVE_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (?P<shape>.*?) "
    rf"(?P<family>{_COLLECTIVE_FAMILIES})(?:-start)?\(",
    re.M,
)
_SHAPE_DIMS = re.compile(r"\w+\[([\d,]*)\]")


def collector_collectives(hlo_text: str) -> list[dict[str, Any]]:
    """The collective INSTRUCTIONS of an optimized-HLO dump (not the
    mentions `collective_census` counts: an operand named after its
    instruction is one): family, the elements of the result (summed
    over a tuple's parts) and the `op_name`, which holds the
    `named_scope` path."""
    out = []
    for m in _COLLECTIVE_INSTRUCTION.finditer(hlo_text):
        line = hlo_text[m.start():hlo_text.find("\n", m.start())]
        name = re.search(r'op_name="([^"]*)"', line)
        elements = sum(
            math.prod(int(d) for d in dims.split(",") if d)
            for dims in _SHAPE_DIMS.findall(m.group("shape"))
        )
        out.append({
            "family": m.group("family"), "elements": elements,
            "op_name": name.group(1) if name else "",
        })
    return out


def collector_violations(hlo_text: str) -> list[dict[str, Any]]:
    """The collectives of a compiled sharded collector that it may not
    hold: any family but all-reduce, and an all-reduce of more than
    `COLLECT_ALL_REDUCE_MAX_ELEMENTS` elements."""
    return [
        c for c in collector_collectives(hlo_text)
        if c["family"] not in EXPECTED_COLLECT_COLLECTIVES
        or c["elements"] > COLLECT_ALL_REDUCE_MAX_ELEMENTS
    ]


def compiled_flops(compiled) -> float:
    """Per-device FLOPs from an AOT-compiled program's cost analysis.
    `Compiled.cost_analysis()` returned a bare dict before jax 0.4.30ish
    and a one-element list of dicts after — accept both (the mesh
    accounting script and the dp-scaling test share this)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return float(ca.get("flops", 0.0))


def collective_census(hlo_text: str) -> dict[str, int]:
    """Count collective ops in an optimized-HLO dump, by family.
    Shared by the mesh-accounting script and the census test so the
    two cannot drift on what counts as a collective."""
    counts: dict[str, int] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts
