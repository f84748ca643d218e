"""HBM memory observability: per-program byte accounting, tiled-layout
size estimation, and the lane-fit advisor.

Motivation (PERF_ROUNDS.md "Round-3 on-chip session 1"): the round-5 flagship
bench died in XLA allocation analysis with a 19.4 GB temp
(`f32[512,154,20,3,8,16]`, a per-lane broadcast of the workload bank's
duration table) that no CPU run could see — XLA:CPU folds the
identity-select away, so tests, benches and calibration were all blind
until the chip window opened. This module makes memory a first-class
observable on three layers:

- **compile-time accounting** (`aot_memory`, `compiled_memory`): AOT
  lower/compile a program and extract `compiled.memory_analysis()`
  (argument / output / temp / generated-code bytes). Backend-true but
  backend-dependent: XLA:CPU folds the broadcast the v5e chokes on, so
  these numbers answer "what did THIS backend allocate", not "is the
  program lane-safe".
- **trace-time estimation** (`jaxpr_memory_estimate`,
  `largest_buffers`, `aval_bytes`): walk a ClosedJaxpr BEFORE backend
  folding and size every intermediate under the TPU tiled-layout model
  (minor dim padded to the 128 lane, second-minor to the 32-byte
  sublane — the 16->128 padding that turned a 2.4 GB table into
  19.4 GB). Backend-independent, so a CPU gate can veto a TPU OOM.
- **the lane-fit advisor** (`lane_fit`): trace `vmap(fn)` at two small
  lane counts, fit a per-buffer linear model bytes(B) = a + b*B, and
  evaluate any candidate lane count against an HBM budget in O(1) —
  the question bench calibration used to answer by crashing. With
  `mesh`, the budget is per DEVICE: candidates stay global lane
  counts, each evaluated at its ceil(lanes/dp) shard width against
  17.2 GB/chip — "max lanes per shard", the multi-chip scale-out's
  memory question. The
  estimate is a *lower bound* (largest single-equation working set +
  arguments + outputs + constants; real peaks add allocator slack), so
  "does not fit" is trustworthy and "fits" means "no single buffer
  blowup" — exactly the failure class the round-5 incident is in.
- **runtime telemetry** (`device_memory_stats`): `bytes_in_use` /
  `peak_bytes_in_use` from the backend allocator, for stamping bench
  rows and trainer iterations (None on backends without allocator
  stats, e.g. CPU — callers must treat the fields as optional).

`TPU_HBM_BUDGET_BYTES` defaults to the v5-lite number in PERF_ROUNDS.md
(17.2 GB decimal); override per call for other parts.
"""

from __future__ import annotations

from typing import Any, Callable, Iterator

# the v5-lite HBM the round-5 OOM ran into (PERF_ROUNDS.md: 19.4 GB > 17.2 GB)
TPU_HBM_BUDGET_BYTES = int(17.2e9)

# TPU tiled layout: minor dim padded to the 128-wide lane, second-minor
# to the 32-byte sublane (8 rows for 4-byte dtypes, 16 for 2-byte, 32
# for 1-byte) — the padding model behind the 16->128 (8x) inflation of
# the round-5 temp
_TPU_LANE = 128
_TPU_SUBLANE_BYTES = 32


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _itemsize(dtype) -> int:
    import numpy as np

    try:
        return int(np.dtype(dtype).itemsize)
    except TypeError:
        # extended dtypes (typed PRNG keys): size of the uint32 block
        # behind one key ((2,) for threefry, (4,) for rbg)
        ks = getattr(getattr(dtype, "_impl", None), "key_shape", None)
        if ks is None:
            return 0
        n = 4
        for d in ks:
            n *= int(d)
        return n


def aval_bytes(aval: Any, tile_pad: bool = True) -> int:
    """Bytes of one abstract value; `tile_pad` applies the TPU tiled
    layout model (the default — this module exists to predict HBM)."""
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    itemsize = _itemsize(dtype)
    shape = tuple(int(d) for d in getattr(aval, "shape", ()))
    if not shape:
        return itemsize
    if not tile_pad:
        n = 1
        for d in shape:
            n *= d
        return n * itemsize
    padded = list(shape)
    padded[-1] = _ceil_to(padded[-1], _TPU_LANE)
    if len(padded) >= 2:
        padded[-2] = _ceil_to(
            padded[-2], max(1, _TPU_SUBLANE_BYTES // itemsize)
        )
    n = 1
    for d in padded:
        n *= d
    return n * itemsize


def _aval_desc(aval: Any) -> str:
    import numpy as np

    try:
        name = np.dtype(aval.dtype).name
    except TypeError:
        name = str(aval.dtype)
    short = {"float32": "f32", "float64": "f64", "int32": "i32",
             "int64": "i64", "bool": "bool", "bfloat16": "bf16",
             "uint32": "u32", "float16": "f16", "int8": "i8",
             "uint8": "u8"}.get(name, name)
    return f"{short}[{','.join(str(d) for d in aval.shape)}]"


def _iter_eqns(jaxpr) -> Iterator:
    """Every equation including nested sub-jaxprs (cond branches, scan
    bodies, closed calls) — a huge temp inside a scan body is live."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if hasattr(sub, "jaxpr"):
                    yield from _iter_eqns(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    yield from _iter_eqns(sub)


def _eqn_working_set(eqn, tile_pad: bool) -> int:
    """Bytes simultaneously live while one equation executes: its unique
    input and output buffers. A lower bound on the program's peak."""
    seen: set[int] = set()
    total = 0
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is None or id(v) in seen:  # skip Literals / dupes
            continue
        seen.add(id(v))
        total += aval_bytes(aval, tile_pad)
    return total


def largest_buffers(closed, k: int = 5, tile_pad: bool = True
                    ) -> list[dict[str, Any]]:
    """Top-K largest intermediate buffers with their producing op — the
    attribution that names the offending table instead of a bare
    six-dim shape. Deduped by (shape, dtype, primitive)."""
    best: dict[tuple, dict[str, Any]] = {}
    for eqn in _iter_eqns(closed.jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is None or not getattr(aval, "shape", ()):
                continue
            key = (tuple(aval.shape), str(aval.dtype),
                   eqn.primitive.name)
            if key in best:
                best[key]["count"] += 1
                continue
            best[key] = {
                "bytes": aval_bytes(aval, tile_pad),
                "shape": _aval_desc(aval),
                "op": eqn.primitive.name,
                "count": 1,
            }
    return sorted(
        best.values(), key=lambda d: d["bytes"], reverse=True
    )[:k]


def jaxpr_memory_estimate(closed, tile_pad: bool = True, top_k: int = 5
                          ) -> dict[str, Any]:
    """Backend-independent byte accounting of one traced program:
    argument/output/constant bytes, the total across intermediate
    buffers (`temp_total_bytes` — the budget-table metric: no liveness
    model, but stable and monotone in program growth), the largest
    single-equation working set, and a peak lower bound."""
    jaxpr = closed.jaxpr
    args = sum(aval_bytes(v.aval, tile_pad) for v in jaxpr.invars)
    outs = sum(aval_bytes(v.aval, tile_pad) for v in jaxpr.outvars)
    consts = sum(aval_bytes(v.aval, tile_pad) for v in jaxpr.constvars)
    temp_total = 0
    max_ws = 0
    for eqn in _iter_eqns(jaxpr):
        temp_total += sum(
            aval_bytes(v.aval, tile_pad) for v in eqn.outvars
        )
        ws = _eqn_working_set(eqn, tile_pad)
        if ws > max_ws:
            max_ws = ws
    return {
        "args_bytes": args,
        "out_bytes": outs,
        "const_bytes": consts,
        "temp_total_bytes": temp_total,
        "max_working_set_bytes": max_ws,
        # resident state + the widest single step: what must fit at once
        "peak_lower_bound_bytes": args + outs + consts + max_ws,
        "largest": largest_buffers(closed, k=top_k, tile_pad=tile_pad),
    }


# ---------------------------------------------------------------------------
# compile-time accounting (backend-true)
# ---------------------------------------------------------------------------

_MEM_ANALYSIS_FIELDS = (
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "alias_size_in_bytes",
    "generated_code_size_in_bytes",
)


def compiled_memory(compiled) -> dict[str, int] | None:
    """`compiled.memory_analysis()` as a plain dict (None when the
    backend does not implement it)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for f in _MEM_ANALYSIS_FIELDS:
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    return out or None


def aot_memory(fn: Callable, *args, **kwargs) -> dict[str, Any] | None:
    """AOT lower + compile `fn` at the argument shapes and return the
    backend's memory analysis (plus which backend produced it). Returns
    None when lowering/compilation fails — callers log, not crash: a
    failed *accounting* compile must never take a bench down."""
    import jax

    try:
        compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    except Exception:
        return None
    mem = compiled_memory(compiled)
    if mem is None:
        return None
    return {"backend": jax.default_backend()} | mem


def device_memory_stats(device=None) -> dict[str, int] | None:
    """Allocator stats (`bytes_in_use`, `peak_bytes_in_use`, ...) for
    one device; None on backends without them (CPU) — runtime memory
    fields are optional everywhere they are stamped."""
    import jax

    try:
        if device is None:
            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return {k: int(v) for k, v in stats.items()
            if isinstance(v, (int, float))}


# ---------------------------------------------------------------------------
# the lane-fit advisor
# ---------------------------------------------------------------------------


def _batched_struct(tree, b: int):
    import jax

    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((b,) + tuple(l.shape), l.dtype),
        tree,
    )


def _trace_vmapped(fn: Callable, example_args: tuple, lanes: int):
    import jax

    batched = tuple(_batched_struct(a, lanes) for a in example_args)
    return jax.make_jaxpr(jax.vmap(fn))(*batched)


def _linear_fit(y1: int, y2: int, b1: int, b2: int
                ) -> tuple[float, float]:
    slope = (y2 - y1) / float(b2 - b1)
    return y1 - slope * b1, slope


def _mesh_dp(mesh) -> int:
    """Device count of a `mesh` argument: a Mesh, an int, or None."""
    if mesh is None:
        return 1
    if isinstance(mesh, int):
        return max(1, mesh)
    return max(1, int(getattr(mesh, "size", 1)))


def lane_fit(
    fn: Callable | None = None,
    example_args: tuple | None = None,
    candidates: tuple[int, ...] = (64, 128, 256, 512, 1024),
    budget_bytes: int = TPU_HBM_BUDGET_BYTES,
    tile_pad: bool = True,
    base_lanes: tuple[int, int] = (2, 4),
    traced: dict[int, Any] | None = None,
    tracer: Callable[[int], Any] | None = None,
    mesh=None,
) -> dict[str, Any]:
    """Sweep vmap lane counts against an HBM budget without compiling.

    `fn` is the per-lane program, `example_args` its UNBATCHED abstract
    arguments (ShapeDtypeStructs or arrays). The program is traced at
    the two `base_lanes` counts only; every buffer's bytes are fitted
    as a + b*lanes from the pair (exact for vmap's linear batching),
    then each candidate is evaluated in O(1). `traced` optionally
    provides pre-built `{lanes: ClosedJaxpr}` traces to share with
    other passes; `tracer` (lanes -> ClosedJaxpr) replaces the default
    `vmap(fn)` trace for programs that take the lane axis directly
    (e.g. the single-eval batch collectors).

    `mesh` (a `jax.sharding.Mesh`, or a bare device count) makes the
    budget PER DEVICE: candidates stay GLOBAL lane counts, but each is
    evaluated at its per-shard width ceil(lanes/dp) against
    `budget_bytes` per chip — the lane axis is batch-sharded under the
    dp mesh (parallel.py:lane_sharding), so the buffers that grow with
    lanes live ceil(B/dp) wide on every device while the bank/params
    stay replicated (the `a` intercept of each buffer's linear model).
    `max_lanes_fit` then answers "how many GLOBAL lanes fit this mesh",
    and each candidate row carries `lanes_per_device`.

    Returns `{budget_bytes, base_lanes, max_lanes_fit,
    candidates: [{lanes, est_peak_bytes, fits, top: {...}}]}` —
    `top` names the dominant buffer (shape at that lane count +
    producing op), so an over-budget row reads "select_n
    f32[512,154,20,3,8,16] = 19.4 GB", not a bare number."""
    dp = _mesh_dp(mesh)
    if tracer is None:
        assert fn is not None and example_args is not None
        tracer = lambda b: _trace_vmapped(fn, example_args, b)  # noqa: E731
    b1, b2 = base_lanes
    assert b1 != b2
    traced = dict(traced or {})
    for b in (b1, b2):
        if b not in traced:
            traced[b] = tracer(b)
    jx1, jx2 = traced[b1], traced[b2]

    def _rows(closed):
        rows = []
        for eqn in _iter_eqns(closed.jaxpr):
            rows.append((
                eqn.primitive.name,
                _eqn_working_set(eqn, tile_pad),
                eqn,
            ))
        return rows

    rows1, rows2 = _rows(jx1), _rows(jx2)
    aligned = len(rows1) == len(rows2) and all(
        a[0] == b[0] for a, b in zip(rows1, rows2)
    )
    if not aligned:
        # the two traces disagree structurally (shape-dependent Python
        # control flow in fn): fall back to tracing every candidate
        return _lane_fit_direct(
            tracer, candidates, budget_bytes, tile_pad, dp
        )

    ws_models = [
        _linear_fit(a[1], b[1], b1, b2) for a, b in zip(rows1, rows2)
    ]

    def _sum_model(vars1, vars2):
        y1 = sum(aval_bytes(v.aval, tile_pad) for v in vars1)
        y2 = sum(aval_bytes(v.aval, tile_pad) for v in vars2)
        return _linear_fit(y1, y2, b1, b2)

    arg_m = _sum_model(jx1.jaxpr.invars, jx2.jaxpr.invars)
    out_m = _sum_model(jx1.jaxpr.outvars, jx2.jaxpr.outvars)
    con_m = _sum_model(jx1.jaxpr.constvars, jx2.jaxpr.constvars)

    def _top_desc(i: int, lanes: int) -> dict[str, Any]:
        import numpy as np

        eqn = rows2[i][2]
        best = max(
            (v for v in eqn.outvars if getattr(v, "aval", None)
             is not None),
            key=lambda v: aval_bytes(v.aval, tile_pad),
            default=None,
        )
        if best is None:
            return {"op": eqn.primitive.name}
        shape = list(best.aval.shape)
        if shape and shape[0] == b2:  # lane-batched: show at `lanes`
            shape[0] = lanes
        scaled = jax_shape_struct(tuple(shape), np.dtype(best.aval.dtype))
        return {
            "op": eqn.primitive.name,
            "shape": f"{_aval_desc(best.aval).split('[')[0]}"
                     f"[{','.join(str(d) for d in shape)}]",
            "bytes": aval_bytes(scaled, tile_pad),
        }

    out_rows = []
    max_fit = 0
    for lanes in sorted(candidates):
        # per-device width: the model is linear in the LANE dimension of
        # the traced program, and under a dp mesh each device holds a
        # ceil(lanes/dp)-wide shard of every lane-batched buffer
        shard = -(-lanes // dp)
        fixed = (arg_m[0] + out_m[0] + con_m[0]
                 + (arg_m[1] + out_m[1] + con_m[1]) * shard)
        ws_vals = [a + b * shard for a, b in ws_models]
        i_top = max(range(len(ws_vals)), key=ws_vals.__getitem__)
        est = int(fixed + ws_vals[i_top])
        fits = est <= budget_bytes
        if fits:
            max_fit = max(max_fit, lanes)
        top = _top_desc(i_top, shard)
        top["working_set_bytes"] = int(ws_vals[i_top])
        row = {
            "lanes": lanes,
            "est_peak_bytes": est,
            "fits": fits,
            "top": top,
        }
        if dp > 1:
            row["lanes_per_device"] = shard
        out_rows.append(row)
    out = {
        "budget_bytes": int(budget_bytes),
        "base_lanes": list(base_lanes),
        "max_lanes_fit": max_fit,
        "candidates": out_rows,
    }
    if dp > 1:
        out["dp"] = dp
    return out


def _lane_fit_direct(tracer, candidates, budget_bytes,
                     tile_pad, dp: int = 1) -> dict[str, Any]:
    """Fallback: one trace per candidate (used only when the two-point
    linear model cannot align its traces). Under a dp mesh the trace
    runs at the candidate's per-shard width."""
    out_rows = []
    max_fit = 0
    for lanes in sorted(candidates):
        shard = -(-lanes // dp)
        jx = tracer(shard)
        est = jaxpr_memory_estimate(jx, tile_pad, top_k=1)
        peak = est["peak_lower_bound_bytes"]
        fits = peak <= budget_bytes
        if fits:
            max_fit = max(max_fit, lanes)
        top = dict(est["largest"][0]) if est["largest"] else {}
        row = {
            "lanes": lanes,
            "est_peak_bytes": int(peak),
            "fits": fits,
            "top": top,
        }
        if dp > 1:
            row["lanes_per_device"] = shard
        out_rows.append(row)
    out = {
        "budget_bytes": int(budget_bytes),
        "base_lanes": [],
        "max_lanes_fit": max_fit,
        "candidates": out_rows,
    }
    if dp > 1:
        out["dp"] = dp
    return out


def jax_shape_struct(shape: tuple, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def hot_set_fit(
    slot_tree,
    candidates: tuple[int, ...] = (64, 128, 256, 512, 1024, 2048),
    budget_bytes: int = TPU_HBM_BUDGET_BYTES,
    fixed_bytes: int = 0,
    tile_pad: bool = True,
    dp: int = 1,
) -> dict[str, Any]:
    """Hot-set capacity model for the paged session store (ISSUE 13) —
    the lane-fit advisor's serving analog.

    `slot_tree` is ONE session's slot as abstract leaves (arrays or
    ShapeDtypeStructs — the `LoopState` one `SessionStore` slot holds).
    The store's HBM cost is linear in the HOT capacity H: the
    [H]-stacked slot store is the only store-sized buffer the donated
    serve programs keep resident, so bytes(H) = fixed + store(H),
    where store(H) is evaluated EXACTLY per candidate (every slot leaf
    sized at leading dim H under the TPU tiled-layout model — no
    fitting, and monotone in H by construction, which the pager test
    pins). `fixed_bytes` carries the replicated constants (the
    workload bank, params) plus whatever working-set allowance the
    caller budgets for the serve program itself.

    With `dp` > 1 (the sharded store), candidates stay GLOBAL hot
    capacities but each is evaluated at its per-device shard width
    ceil(H/dp) against a per-chip budget, mirroring `lane_fit`'s mesh
    mode — the store's leading axis is `P('dp')`-sharded while the
    bank stays replicated (the fixed term).

    Returns `{budget_bytes, fixed_bytes, slot_bytes, max_hot_fit,
    candidates: [{hot, est_bytes, fits[, hot_per_device]}]}` —
    `slot_bytes` is the marginal PER-DEVICE cost of one more GLOBAL
    slot at large H (the est_bytes slope in global H, i.e. already
    divided by dp), so "how many more global sessions fit the
    per-chip budget" is one division away under any mesh."""
    leaves = [
        (tuple(int(d) for d in getattr(a, "shape", ())),
         getattr(a, "dtype", None))
        for a in _tree_leaves(slot_tree)
    ]

    def store_bytes(h: int) -> int:
        return sum(
            aval_bytes(jax_shape_struct((h,) + shape, dtype), tile_pad)
            for shape, dtype in leaves
            if dtype is not None
        )

    dp = max(1, int(dp))
    rows = []
    max_fit = 0
    for h in sorted(int(c) for c in candidates):
        shard = -(-h // dp)
        est = int(fixed_bytes) + store_bytes(shard)
        fits = est <= budget_bytes
        if fits:
            max_fit = max(max_fit, h)
        row = {"hot": h, "est_bytes": est, "fits": fits}
        if dp > 1:
            row["hot_per_device"] = shard
        rows.append(row)
    out = {
        "budget_bytes": int(budget_bytes),
        "fixed_bytes": int(fixed_bytes),
        # marginal bytes of one more GLOBAL slot (the large-H slope
        # of est_bytes, where per-leaf tile padding has amortized) —
        # computed at per-device shard widths so the division against
        # the per-chip budget yields GLOBAL sessions under any dp
        "slot_bytes": (
            store_bytes(-(-2048 // dp)) - store_bytes(-(-1024 // dp))
        ) // 1024,
        "max_hot_fit": max_fit,
        "candidates": rows,
    }
    if dp > 1:
        out["dp"] = dp
    return out


def _tree_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def gb(n: int | float) -> float:
    """Decimal GB, the unit PERF_ROUNDS.md and the budget table speak."""
    return round(float(n) / 1e9, 2)


def lane_fit_summary(fit: dict[str, Any]) -> dict[str, Any]:
    """Compact per-row form of a `lane_fit` report — what bench rows
    carry (the full candidate table with buffer attributions lives in
    the analysis report)."""
    worst = fit["candidates"][-1] if fit["candidates"] else {}
    top = worst.get("top", {})
    out = {
        "budget_gb": gb(fit["budget_bytes"]),
        "max_lanes_fit": fit["max_lanes_fit"],
        "candidates": [
            {
                "lanes": c["lanes"], "est_gb": gb(c["est_peak_bytes"]),
                "fits": c["fits"],
            }
            | (
                {"lanes_per_device": c["lanes_per_device"]}
                if "lanes_per_device" in c else {}
            )
            for c in fit["candidates"]
        ],
        "top": {k: top.get(k) for k in ("op", "shape") if k in top},
    }
    if "dp" in fit:
        # per-device budget: est_gb rows above are bytes PER CHIP at
        # each global lane count sharded dp ways
        out["dp"] = fit["dp"]
    return out


def memory_row_stamp(
    lane_fn: Callable | None = None,
    example_args: tuple | None = None,
    candidates: tuple[int, ...] = (512, 1024),
    budget_bytes: int = TPU_HBM_BUDGET_BYTES,
    tracer: Callable[[int], Any] | None = None,
    program: str | None = None,
    mesh=None,
) -> dict[str, Any]:
    """Best-effort `memory` block for a bench row: runtime allocator
    stats (null on backends without them — CPU) plus, when a lane
    program (or `tracer`) is given, the compact lane-fit prediction.
    With `mesh` (or a device count), the prediction is per shard
    against a per-chip budget — what a dp-sharded bench row must stamp
    (global lanes, per-device bytes). Never raises — a failed
    *accounting* step must never take a bench row down; failures land
    as a `lane_fit: {error}` field instead."""
    stats = device_memory_stats() or {}
    out: dict[str, Any] = {
        "mem_peak_bytes": stats.get("peak_bytes_in_use"),
        "mem_bytes_in_use": stats.get("bytes_in_use"),
    }
    if program is not None:
        out["program"] = program
    if lane_fn is not None or tracer is not None:
        try:
            out["lane_fit"] = lane_fit_summary(lane_fit(
                lane_fn, example_args, candidates=candidates,
                budget_bytes=budget_bytes, tracer=tracer, mesh=mesh,
            ))
        except Exception as e:
            out["lane_fit"] = {
                "error": f"{type(e).__name__}: {str(e)[:160]}"
            }
    return out
