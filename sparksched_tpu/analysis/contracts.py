"""Pytree contract checker: declared dtype/shape schemas for the
pytrees that cross the jit boundary every iteration.

The recompile hazard this pass pins: XLA keys compiled executables on
the (structure, dtype, shape) signature of every input, so a field
that drifts — an f32 that becomes weak-f64 under a stray promotion, a
shape that silently follows a config change, a leaf added to
`EnvState` without a schema update — recompiles every consumer and
invalidates the budget table. Schemas here are *data*: the auditor
reads them (static verification via `jax.eval_shape` — nothing
executes), and tests run the cheap runtime-assert mode around real
episodes to pin that `reset`/`step`/`micro_step` never change a
field's structure, dtype, or shape mid-run.

Shape entries are dim tokens resolved against the `EnvParams` under
audit: ``J`` = max_jobs, ``S`` = max_stages, ``N`` = num_executors,
``*`` = any size (the rng key length is PRNG-impl-dependent:
threefry uint32[2] vs rbg uint32[4]).

Rules reported by `check_all` (all under pass "contracts"):

- ``env-state-schema``: `core.reset`'s output matches ENV_STATE_SCHEMA
  exactly — field set, dtypes, shapes (no unknown or missing leaves).
- ``telemetry-schema``: every `Telemetry` counter is an i32 scalar
  (vmapped engines prepend lane axes; the schema checks the trailing
  shape).
- ``trajectory-schema``: the collectors' `StoredObs` record matches
  its declared dtypes/shapes — an f64 smuggled into the rollout buffer doubles its
  footprint and poisons the update's compile key.
- ``step-invariance``: `core.step` and flat `micro_step` return an
  `EnvState` with the *identical* spec as their input (via eval_shape;
  the recompile hazard directly).
"""

from __future__ import annotations

from typing import Any

from . import Violation

SCHEMA_NAMES = (
    "EnvState", "Telemetry", "StoredObs",
)

# --- schemas (declarative data) -------------------------------------------

ENV_STATE_SCHEMA: dict[str, tuple[str, tuple]] = {
    "rng": ("uint32", ("*",)),
    "wall_time": ("float32", ()),
    "time_limit": ("float32", ()),
    "seq_counter": ("int32", ()),
    "round_ready": ("bool", ()),
    "terminated": ("bool", ()),
    "truncated": ("bool", ()),
    "job_template": ("int32", ("J",)),
    "job_arrival_time": ("float32", ("J",)),
    "job_arrival_seq": ("int32", ("J",)),
    "job_arrived": ("bool", ("J",)),
    "job_t_completed": ("float32", ("J",)),
    "job_num_stages": ("int32", ("J",)),
    "job_saturated_stages": ("int32", ("J",)),
    "job_supply": ("int32", ("J",)),
    "num_jobs": ("int32", ()),
    "stage_exists": ("bool", ("J", "S")),
    "stage_num_tasks": ("int32", ("J", "S")),
    "stage_remaining": ("int32", ("J", "S")),
    "stage_executing": ("int32", ("J", "S")),
    "stage_completed_tasks": ("int32", ("J", "S")),
    "stage_duration": ("float32", ("J", "S")),
    "stage_selected": ("bool", ("J", "S")),
    "schedulable": ("bool", ("J", "S")),
    "adj": ("bool", ("J", "S", "S")),
    "exec_at_common": ("bool", ("N",)),
    "exec_job": ("int32", ("N",)),
    "exec_stage": ("int32", ("N",)),
    "exec_moving": ("bool", ("N",)),
    "exec_dst_job": ("int32", ("N",)),
    "exec_dst_stage": ("int32", ("N",)),
    "exec_arrive_time": ("float32", ("N",)),
    "exec_arrive_seq": ("int32", ("N",)),
    "exec_executing": ("bool", ("N",)),
    "exec_task_valid": ("bool", ("N",)),
    "exec_task_stage": ("int32", ("N",)),
    "exec_finish_time": ("float32", ("N",)),
    "exec_finish_seq": ("int32", ("N",)),
    "stage_sat": ("bool", ("J", "S")),
    "unsat_parent_count": ("int32", ("J", "S")),
    "incomplete_parent_count": ("int32", ("J", "S")),
    "parent_sets": ("uint32", ("J", "W", "S")),
    "duration_facts": ("uint32", ("J", "S")),
    "node_level": ("int32", ("J", "S")),
    "commit_count": ("int32", ("J", "S")),
    "moving_count": ("int32", ("J", "S")),
    "cm_valid": ("bool", ("N",)),
    "cm_src_job": ("int32", ("N",)),
    "cm_src_stage": ("int32", ("N",)),
    "cm_dst_job": ("int32", ("N",)),
    "cm_dst_stage": ("int32", ("N",)),
    "cm_seq": ("int32", ("N",)),
    "source_valid": ("bool", ()),
    "source_job": ("int32", ()),
    "source_stage": ("int32", ()),
}

# every engine counter is an i32 scalar per lane (telemetry.py)
TELEMETRY_SCHEMA_DTYPE = "int32"

STORED_OBS_SCHEMA: dict[str, tuple[str, tuple]] = {
    "remaining": ("int32", ("F",)),
    # the audited layout; `env: {obs_dtype: bfloat16}` configs narrow
    # this leaf to bf16 (ISSUE 7) — the audit always runs the default
    # f32 params, so the pin holds for CI while the low-precision
    # layout stays an explicit per-config opt-in
    "duration": ("float32", ("F",)),
    "schedulable": ("bool", ("F",)),
    "node_mask": ("bool", ("F",)),
    "job_mask": ("bool", ("J",)),
    "job_template": ("int32", ("J",)),
    "exec_supplies": ("int32", ("J",)),
    "num_committable": ("int32", ()),
    "source_job": ("int32", ()),
}


# --- core machinery --------------------------------------------------------


def dims_from_params(params) -> dict[str, int]:
    from ..env.state import STAGE_SET_BITS

    return {
        "J": params.max_jobs,
        "S": params.max_stages,
        "N": params.num_executors,
        # words of a packed stage set (`core.pack_parents`)
        "W": -(-params.max_stages // STAGE_SET_BITS),
        # a stored step's flat [J,S] node grid, padded to whole
        # 128-wide rows (trainers/rollout.py:_flat_grid)
        "F": -(-params.max_jobs * params.max_stages // 128) * 128,
    }


def _shape_matches(shape: tuple, spec: tuple, dims: dict[str, int]) -> bool:
    if len(shape) != len(spec):
        return False
    for got, want in zip(shape, spec):
        if want == "*":
            continue
        if got != dims.get(want, want):
            return False
    return True


def check_fields(
    obj: Any,
    schema: dict[str, tuple[str, tuple]],
    dims: dict[str, int],
    where: str,
    batch_ndim: int = 0,
) -> list[Violation]:
    """Check a dataclass-style pytree (concrete arrays OR
    ShapeDtypeStructs — anything with .dtype/.shape) against a schema.
    `batch_ndim` leading axes are ignored on every leaf (vmapped/
    scanned containers). Reports unknown fields too: a leaf added
    without a schema update is itself a contract violation."""
    found: list[Violation] = []
    if isinstance(obj, dict):
        names = set(obj)
        get = obj.__getitem__
    else:
        fields = getattr(obj, "__dataclass_fields__", None)
        names = set(fields) if fields is not None else set(vars(obj))
        get = lambda n: getattr(obj, n)  # noqa: E731
    for name in sorted(names - set(schema)):
        found.append(Violation(
            "contracts", "env-state-schema" if "EnvState" in where
            else "trajectory-schema",
            f"{where}.{name}",
            "field missing from the declared schema — declare its "
            "dtype/shape in analysis/contracts.py",
        ))
    for name, (dtype, shape) in schema.items():
        if name not in names:
            found.append(Violation(
                "contracts", "env-state-schema" if "EnvState" in where
                else "trajectory-schema",
                f"{where}.{name}", "declared field missing from pytree",
            ))
            continue
        leaf = get(name)
        got_dt = str(leaf.dtype)
        got_shape = tuple(leaf.shape)[batch_ndim:]
        if got_dt != dtype:
            found.append(Violation(
                "contracts", "env-state-schema" if "EnvState" in where
                else "trajectory-schema",
                f"{where}.{name}",
                f"dtype {got_dt}, schema says {dtype}",
            ))
        if not _shape_matches(got_shape, shape, dims):
            found.append(Violation(
                "contracts", "env-state-schema" if "EnvState" in where
                else "trajectory-schema",
                f"{where}.{name}",
                f"shape {got_shape}, schema says {shape} with {dims}",
            ))
    return found


def check_env_state(state, params, where: str = "EnvState",
                    batch_ndim: int = 0) -> list[Violation]:
    return check_fields(
        state, ENV_STATE_SCHEMA, dims_from_params(params), where,
        batch_ndim,
    )


def check_telemetry(tm, where: str = "Telemetry",
                    batch_ndim: int = 0) -> list[Violation]:
    """Every counter must be an i32 SCALAR past the `batch_ndim`
    leading lane axes a vmapped engine prepends — a counter silently
    widened to a vector changes the scan carry's compile key on every
    consumer."""
    found: list[Violation] = []
    for name in tm.__dataclass_fields__:
        leaf = getattr(tm, name)
        if leaf is None:  # a counter that was not asked for
            continue
        if str(leaf.dtype) != TELEMETRY_SCHEMA_DTYPE:
            found.append(Violation(
                "contracts", "telemetry-schema", f"{where}.{name}",
                f"dtype {leaf.dtype}, every counter must be "
                f"{TELEMETRY_SCHEMA_DTYPE}",
            ))
        trailing = tuple(leaf.shape)[batch_ndim:]
        if trailing != ():
            found.append(Violation(
                "contracts", "telemetry-schema", f"{where}.{name}",
                f"trailing shape {trailing}, every counter must be a "
                "scalar past the lane axes",
            ))
    return found


# --- runtime-assert mode ---------------------------------------------------


def spec_of(tree) -> list[tuple[str, str, tuple]]:
    """Flat (path, dtype, shape) signature of a pytree — the exact
    quantity XLA keys compiled executables on. Host-side and cheap
    (reads metadata only, no device sync)."""
    import jax

    leaves = jax.tree_util.tree_leaves_with_path(tree)
    return [
        (jax.tree_util.keystr(path), str(leaf.dtype), tuple(leaf.shape))
        for path, leaf in leaves
        if hasattr(leaf, "dtype")
    ]


def diff_spec(before, after, where: str = "pytree") -> list[Violation]:
    """Spec difference between two snapshots of the same logical pytree
    — the runtime-assert core: any entry here would force a recompile."""
    b = {p: (d, s) for p, d, s in before}
    a = {p: (d, s) for p, d, s in after}
    found: list[Violation] = []
    for p in sorted(set(b) - set(a)):
        found.append(Violation(
            "contracts", "step-invariance", f"{where}{p}",
            "leaf disappeared across a step",
        ))
    for p in sorted(set(a) - set(b)):
        found.append(Violation(
            "contracts", "step-invariance", f"{where}{p}",
            "leaf appeared across a step",
        ))
    for p in sorted(set(a) & set(b)):
        if a[p] != b[p]:
            found.append(Violation(
                "contracts", "step-invariance", f"{where}{p}",
                f"{b[p]} -> {a[p]} across a step (recompile hazard)",
            ))
    return found


def assert_env_state(state, params, where: str = "EnvState",
                     batch_ndim: int = 0) -> None:
    """Runtime-assert mode: raise AssertionError listing every schema
    violation on a concrete state. Cheap (metadata only) — tests wrap
    episodes with it."""
    vs = check_env_state(state, params, where, batch_ndim)
    assert not vs, "\n".join(map(str, vs))


def assert_same_spec(before, after, where: str = "pytree") -> None:
    vs = diff_spec(before, after, where)
    assert not vs, "\n".join(map(str, vs))


# --- static verification (the auditor's contracts pass) --------------------


def check_all() -> list[Violation]:
    """Static contract verification under `jax.eval_shape` — nothing
    executes, so this pass is cheap and backend-independent. Uses the
    shared audit config from `jaxpr_audit` so the two passes agree on
    shapes."""
    import jax
    import jax.numpy as jnp

    from ..env import core
    from ..env.flat_loop import init_loop_state, micro_step
    from ..obs.telemetry import telemetry_zeros
    from .jaxpr_audit import audit_setup

    params, bank, state_sds = audit_setup()
    dims = dims_from_params(params)
    found: list[Violation] = []

    # env-state-schema: reset's output
    found.extend(check_env_state(state_sds, params, "reset->EnvState"))

    # telemetry-schema
    found.extend(check_telemetry(telemetry_zeros()))

    # step-invariance: core.step output state spec == input spec
    def run_step(s, si, ne, tm):
        out = core.step(params, bank, s, si, ne, telemetry=tm)
        return out[0], out[4]

    si = jax.ShapeDtypeStruct((), jnp.int32)
    tm0 = telemetry_zeros()
    out_state, out_tm = jax.eval_shape(run_step, state_sds, si, si, tm0)
    found.extend(diff_spec(
        spec_of(state_sds), spec_of(out_state), "core.step(EnvState)"
    ))
    found.extend(diff_spec(
        spec_of(tm0), spec_of(out_tm), "core.step(Telemetry)"
    ))

    # step-invariance: flat micro_step
    def pol(rng, obs):
        from ..schedulers.heuristics import round_robin_policy

        s_idx, ne = round_robin_policy(obs, params.num_executors, True)
        return s_idx, ne, {}

    def run_micro(ls, r):
        return micro_step(
            params, bank, pol, ls, r, True, True, True, 8, True, 1
        )

    ls0 = jax.eval_shape(init_loop_state, state_sds)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    ls1 = jax.eval_shape(run_micro, ls0, key)
    found.extend(diff_spec(
        spec_of(ls0), spec_of(ls1), "micro_step(LoopState)"
    ))
    # trajectory-schema: the collectors' stored-observation record
    from ..env.observe import observe
    from ..trainers.rollout import store_obs

    so = jax.eval_shape(
        lambda s: store_obs(observe(params, s), s), state_sds
    )
    found.extend(check_fields(so, STORED_OBS_SCHEMA, dims, "StoredObs"))
    return found
