"""AST lint over `sparksched_tpu/` source: repo rules that keep host
Python out of the traced hot path.

Rules (ids are what `# analysis: allow(<rule>)` pragmas and the JSON
report use):

- ``host-scalar``: no `.item()` / `np.asarray(...)` / `float(...)` /
  `int(...)` on non-constant values inside the fully-traced modules
  (`env/`, `schedulers/`). Each of these forces a device->host
  transfer when it touches a traced value — inside jit it is a trace
  error at best, a silent sync at worst.
- ``host-sync``: no `jax.device_get` / `block_until_ready` outside the
  sanctioned host-side code (`obs/`, the host adapters, and the
  trainer host loop — see `HOST_SYNC_EXEMPT_FUNCS`). Collection and
  update code must stay asynchronous; a stray sync serializes the
  dispatch pipeline.
- ``implicit-dtype``: `jnp.zeros/ones/full/arange` in the hot modules
  must pass an explicit dtype (keyword or the positional dtype slot).
  Implicit dtypes follow the x64 flag — the same constructor that
  builds i32/f32 on the shipped config silently builds i64/f64 under
  `JAX_ENABLE_X64`, and a single wide leaf recompiles every consumer.
- ``time-in-jit``: no `time.time()`-family reads in the fully-traced
  modules. A wall-clock read inside a jitted body is evaluated once at
  trace time and baked in as a constant — timing belongs to the host
  loop (`trainers/profiler.py`, `obs/runlog.py`).
- ``bare-print``: no bare `print(` anywhere in the package outside
  `renderer.py` (moved here from tests/test_obs.py) — host-loop output
  goes through `obs.runlog` (`emit` / the JSONL sink) so it stays
  machine-readable and console-consistent.
- ``serve-host-sync`` (ISSUE 15): in the serve pump hot path
  (`serve/session.py`), a blocking host sync — `jax.device_get`,
  `block_until_ready`, or an eager `np.asarray` on a device array —
  is a violation OUTSIDE the harvest/trace boundary
  (`SERVE_HARVEST_FUNCS`). The pipelined front exists because one
  stray sync in dispatch/admission serializes the whole in-flight
  window; this rule makes that regression a CI failure instead of a
  p99 surprise. The file is a HOST_FILE (the generic host-sync rule
  deliberately exempts it — handing back concrete decisions IS its
  product), so this rule is the narrow replacement: syncs may live in
  the harvest stage and the trace stamps, nowhere else. ISSUE 19
  generalizes this interprocedurally: `concurrency-pump-blocking`
  (analysis/concurrency.py) follows the serve-pump ROLE through the
  call graph package-wide, so a sync buried two calls deep or in a
  different module is caught too; this rule stays as the cheap
  file-scoped first line.

Scoping is declarative data below. Known-host-side code is exempted
there (visible in one place), and a line-level escape hatch exists for
deliberate boundary crossings: append ``# analysis: allow(<rule>)`` to
the offending line.
"""

from __future__ import annotations

import ast
import pathlib
import re
from typing import Iterator

from . import Violation

# --- rule scoping (declarative) -------------------------------------------

# fully-traced modules: everything in these package subdirs executes
# (or may execute) under jit/vmap/scan
HOT_DIRS = ("env", "schedulers")

# host adapters by contract — they exist to bridge device pytrees to
# host consumers, so host-scalar/host-sync/time rules do not apply.
# serve/session.py is the decision-serving request/response boundary
# (ISSUE 10): its device_get/block_until_ready ARE the product — the
# caller is handed a concrete decision — and its traced code lives in
# serve/aot.py + env/, which the jaxpr rules audit directly.
# serve/loadgen.py (ISSUE 11) is host-side by definition — an
# open-loop load generator IS a wall-clock consumer
# (time.perf_counter is its measurement instrument, not a trace
# hazard); obs/metrics.py needs no entry here because obs/ is already
# a sync-exempt host dir, but both are named so the scoping decision
# is visible in one place.
HOST_FILES = frozenset({
    "renderer.py", "env/gym_compat.py", "serve/session.py",
    "serve/loadgen.py",
    # ISSUE 14: the online loop's host-side modules — trajectory
    # assembly consumes concrete ServeResults (device_get is the
    # product, as in serve/session.py), the learner's host loop syncs
    # on update completion exactly like trainers/trainer.py's, and
    # the bus is pure host bookkeeping; their traced code is the
    # registry-audited serve/ppo programs, not these files
    "online/__init__.py", "online/trajectory.py",
    "online/learner.py", "online/bus.py",
    # ISSUE 16: the network tier's request/response boundary — the
    # HTTP front and the replica router are host bookkeeping end to
    # end (sockets, pipes, wall-clock timeouts ARE the product);
    # their traced code is the same registry-audited serve programs,
    # built per-replica through store_from_config. Jaxpr-exempt but
    # still AST-linted (bare-print etc. apply).
    "serve/server.py", "serve/router.py",
    # ISSUE 17: the fleet observability plane — scrape loops, burn-
    # rate window arithmetic, and artifact-JSON indexing are host
    # bookkeeping by definition (wall clocks and files ARE the
    # product); nothing in them traces. Already under the obs/
    # sync-exempt dir; named here so the host scoping is explicit.
    "obs/fleet.py", "obs/slo.py", "obs/ledger.py",
    # ISSUE 20: the tail-attribution plane — span arithmetic over
    # perf_counter stamps and a wall-clock sampling profiler are host
    # instruments by definition (the clock IS the measurement).
    # Already under the obs/ sync-exempt dir; named for visibility.
    "obs/critpath.py", "obs/hostprof.py",
})

# host-side entry points inside otherwise-hot modules, PATH-QUALIFIED
# (a bare-name exemption would let any function named `schedule` in a
# hot module disable the rules): constructor config coercion, the
# one-decision host API, torch checkpoint IO
HOST_BOUNDARY_FUNCS: dict[str, tuple[str, ...]] = {
    "__init__": ("schedulers/",),
    "schedule": ("schedulers/",),
    "load_torch_state_dict": ("schedulers/decima.py",),
}

# the sanctioned synchronous host loop, path-qualified like the above:
# the trainer's per-iteration timing fences and checkpoint
# serialization, and the scheduler's host-side single-decision API
HOST_SYNC_EXEMPT_DIRS = ("obs",)
HOST_SYNC_EXEMPT_FUNCS: dict[str, tuple[str, ...]] = {
    "train": ("trainers/trainer.py",),
    "save_train_state": ("trainers/trainer.py",),
    "_checkpoint": ("trainers/trainer.py",),
    "_cleanup": ("trainers/trainer.py",),
    "schedule": ("schedulers/",),
    # the sweep's host loop reads the episodes' results between chunks
    "run": ("sweep.py",),
    "results_of": ("sweep.py",),
}

# serve-host-sync (ISSUE 15) scoping: the serve pump hot path, and the
# functions forming its sanctioned harvest/trace boundary — the ONLY
# places in those files where a blocking device sync
# (device_get / block_until_ready / eager np.asarray on device
# buffers) is allowed. Everything else in the file is
# dispatch/admission code the pipelined front needs sync-free.
SERVE_PUMP_FILES = frozenset({"serve/session.py"})
SERVE_HARVEST_FUNCS = frozenset({
    # the synchronous serve path's materialization (it IS a harvest)
    "_served",
    # the pipelined harvest stage (pop_ready = the device half,
    # finalize_call = the host half) + the background harvester
    "harvest", "pop_ready", "finalize_call", "_materialize",
    "_harvester_loop",
    # the deferred page-out drain (the non-blocking pager's tail)
    "_drain_writebacks",
})


def _func_exempt(relpath: str, func_stack: list[str],
                 table: dict[str, tuple[str, ...]]) -> bool:
    return any(
        f in table and any(relpath.startswith(p) for p in table[f])
        for f in func_stack
    )

_JNP_CTORS = {
    # constructor -> index of the positional dtype slot
    "zeros": 1,
    "ones": 1,
    "full": 2,
    "arange": 3,
}
_TIME_FNS = frozenset({
    "time", "perf_counter", "monotonic", "process_time", "time_ns",
    "perf_counter_ns", "monotonic_ns",
})

_PRAGMA_RE = re.compile(r"#\s*analysis:\s*allow\(([a-z\-_, ]+)\)")

_last_scan_count = 0


def last_scan_count() -> int:
    return _last_scan_count


def _pragmas(source: str) -> dict[int, set[str]]:
    """lineno -> set of rule ids allowed on that line."""
    out: dict[int, set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _PRAGMA_RE.search(line)
        if m:
            out[i] = {r.strip() for r in m.group(1).split(",")}
    return out


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of a call target ('jax.device_get')."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _import_table(tree: ast.AST) -> dict[str, str]:
    """Local name -> canonical dotted path, from every import form, so
    rules match on canonical names and cannot be bypassed by aliasing
    (`import time as t`, `from jax.numpy import zeros as z`,
    `import jax.numpy as J`, ...)."""
    table: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    table[a.asname] = a.name
                else:
                    # `import jax.numpy` binds `jax`; dotted call
                    # sites resolve through the first segment
                    top = a.name.split(".")[0]
                    table[top] = top
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                if a.name != "*":
                    table[a.asname or a.name] = \
                        f"{node.module}.{a.name}"
    return table


class _Linter(ast.NodeVisitor):
    def __init__(self, relpath: str, source: str,
                 tree: ast.AST) -> None:
        self.relpath = relpath
        self.pragmas = _pragmas(source)
        self.func_stack: list[str] = []
        self.found: list[Violation] = []
        self.imports = _import_table(tree)

        top = relpath.split("/")[0]
        self.in_hot = top in HOT_DIRS and relpath not in HOST_FILES
        self.host_file = relpath in HOST_FILES
        self.sync_exempt_file = (
            top in HOST_SYNC_EXEMPT_DIRS or self.host_file
        )
        self.serve_pump = relpath in SERVE_PUMP_FILES
        self.print_exempt = relpath == "renderer.py"

    # -- helpers ------------------------------------------------------

    def _emit(self, rule: str, node: ast.AST, detail: str) -> None:
        line = getattr(node, "lineno", 0)
        if rule in self.pragmas.get(line, ()):  # line-level escape hatch
            return
        self.found.append(Violation(
            "lint", rule, f"{self.relpath}:{line}", detail
        ))

    def _in_host_boundary(self) -> bool:
        return _func_exempt(
            self.relpath, self.func_stack, HOST_BOUNDARY_FUNCS
        )

    def _sync_exempt(self) -> bool:
        return self.sync_exempt_file or _func_exempt(
            self.relpath, self.func_stack, HOST_SYNC_EXEMPT_FUNCS
        )

    def _canonical(self, fn: ast.AST) -> str:
        """Import-resolved dotted name of a call target: `t.time` under
        `import time as t` -> "time.time"; `z` under `from jax.numpy
        import zeros as z` -> "jax.numpy.zeros"."""
        name = _dotted(fn)
        if not name:
            return ""
        head, _, rest = name.partition(".")
        resolved = self.imports.get(head, head)
        return f"{resolved}.{rest}" if rest else resolved

    # -- traversal ----------------------------------------------------

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.func_stack.append(node.name)
        self.generic_visit(node)
        self.func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Call(self, node: ast.Call) -> None:
        self._check_call(node)
        self.generic_visit(node)

    def _check_call(self, node: ast.Call) -> None:
        fn = node.func
        name = self._canonical(fn)
        mod, _, leaf = name.rpartition(".")

        # bare-print (whole package minus renderer.py)
        if isinstance(fn, ast.Name) and fn.id == "print":
            if not self.print_exempt:
                self._emit(
                    "bare-print", node,
                    "bare print( call — use obs.runlog.emit or the "
                    "JSONL runlog",
                )
            return

        # host-sync (package-wide minus the sanctioned host loop)
        is_sync_call = (
            name in ("jax.device_get", "jax.block_until_ready")
            or (isinstance(fn, ast.Attribute)
                and fn.attr == "block_until_ready")
        )
        if is_sync_call and not self._sync_exempt():
            self._emit(
                "host-sync", node,
                f"{name}() outside obs//bench — a device sync in "
                "collection/update code serializes dispatch",
            )

        # serve-host-sync (ISSUE 15): blocking syncs in the serve pump
        # hot path are confined to the harvest/trace boundary — a
        # stray one in dispatch/admission code serializes the whole
        # in-flight window
        if self.serve_pump and (
            is_sync_call or name == "numpy.asarray"
        ) and not any(
            f in SERVE_HARVEST_FUNCS for f in self.func_stack
        ):
            self._emit(
                "serve-host-sync", node,
                f"{name or 'block_until_ready'}() in the serve pump "
                "hot path outside the harvest/trace boundary "
                "(SERVE_HARVEST_FUNCS) — a blocking sync here "
                "serializes the pipelined in-flight window",
            )

        if not self.in_hot:
            return

        # implicit-dtype (hot modules; jnp.* and any aliased or
        # from-imported form of the jax.numpy constructors)
        if mod == "jax.numpy" and leaf in _JNP_CTORS:
            has_kw = any(kw.arg == "dtype" for kw in node.keywords)
            has_pos = len(node.args) > _JNP_CTORS[leaf]
            if not (has_kw or has_pos):
                self._emit(
                    "implicit-dtype", node,
                    f"jnp.{leaf}(...) without an explicit dtype — "
                    "implicit dtypes follow the x64 flag",
                )

        # time-in-jit (hot modules; any import form of the clock fns)
        if mod == "time" and leaf in _TIME_FNS:
            self._emit(
                "time-in-jit", node,
                f"time.{leaf}() in a traced module — evaluated once "
                "at trace time, constant thereafter",
            )

        # host-scalar (hot modules, outside host-boundary functions)
        if self._in_host_boundary():
            return
        if isinstance(fn, ast.Attribute) and fn.attr == "item" \
                and not node.args:
            self._emit(
                "host-scalar", node,
                ".item() in a traced module forces a device->host "
                "transfer",
            )
        elif name == "numpy.asarray":
            self._emit(
                "host-scalar", node,
                "np.asarray() on a (possibly traced) value — use "
                "jnp.asarray or move to a host adapter",
            )
        elif (
            isinstance(fn, ast.Name)
            and fn.id in ("float", "int")
            and node.args
            and not isinstance(node.args[0], ast.Constant)
        ):
            self._emit(
                "host-scalar", node,
                f"{fn.id}(...) on a non-constant in a traced module — "
                "a silent sync on concrete values, a trace error under "
                "jit",
            )


def lint_file(path: pathlib.Path, relpath: str) -> list[Violation]:
    source = path.read_text()
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Violation("lint", "syntax", relpath, str(e))]
    linter = _Linter(relpath, source, tree)
    linter.visit(tree)
    return linter.found


def iter_package_files(root: pathlib.Path) -> Iterator[
        tuple[pathlib.Path, str]]:
    for path in sorted(root.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        rel = path.relative_to(root).as_posix()
        if rel.startswith("analysis/"):
            # the analyzer itself is host-side tooling
            continue
        yield path, rel


def lint_paths(root: pathlib.Path) -> list[Violation]:
    """Lint every .py under `root`, with rule scoping keyed on paths
    RELATIVE to `root` (so a fixture tree mirroring the package layout
    — env/..., schedulers/..., obs/... — gets the same treatment)."""
    global _last_scan_count
    found: list[Violation] = []
    n = 0
    for path, rel in iter_package_files(root):
        n += 1
        found.extend(lint_file(path, rel))
    _last_scan_count = n
    return found


def lint_package() -> list[Violation]:
    import sparksched_tpu

    return lint_paths(pathlib.Path(sparksched_tpu.__file__).parent)
