"""Registry-coverage pass (ISSUE 19 satellite): every `jax.jit` /
AOT-lowered callable in the package must be accounted for in the
jaxpr-audit program registry or carry an explicit waiver.

The jaxpr/memory passes only audit programs someone REGISTERED in
`jaxpr_audit.BUDGETS` — a new jit site that nobody registers is a
silent gap: it ships untraced, unbudgeted, and surfaces rounds later
as a bench slump. This pass closes the gap structurally: it finds
every jit/AOT site in the source (call forms `jax.jit(...)`,
decorator forms `@jax.jit` / `@partial(jax.jit, ...)`, and
`aot_compile(...)` lowering sites) and requires each to appear in the
declarative `COVERAGE` table below, mapped either to the audited
program(s) it produces or to a waiver with a reason.

Rules:

- ``coverage-unregistered-jit``: a jit/AOT site with no COVERAGE
  entry (and no `# analysis: allow(coverage-unregistered-jit)`
  pragma). Register the program in `jaxpr_audit.BUDGETS` + here, or
  waive it with the reason.
- ``coverage-stale-entry``: a COVERAGE entry whose site no longer
  exists — the table must shrink with the code (package scan only).
- ``coverage-unknown-program``: a COVERAGE entry naming a program
  that is not a `jaxpr_audit.BUDGETS` key — a typo'd or unregistered
  mapping is itself a gap.

Sites are keyed `(relative path, enclosing qualname)` — stable across
line churn, specific enough that a NEW jit site in an already-listed
function still needs a table touch only when it lands in a new scope.
"""

from __future__ import annotations

import ast
import pathlib
from typing import Any

from . import Violation
from .lint import _import_table, _pragmas, iter_package_files

# site -> ("program", (budget keys...)) or ("waiver", reason)
COVERAGE: dict[tuple[str, str], tuple[str, Any]] = {
    # the generic AOT lowering entry: every serve program goes through
    # it; the concrete programs are registered per budget key
    ("serve/aot.py", "aot_compile"): ("program", (
        "serve_decide", "serve_decide_batch",
        "serve_decide_batch_sharded", "serve_decide_batch_group",
        "serve_decide_record", "serve_decide_batch_record",
        "serve_decide_record_ring", "serve_decide_batch_record_ring",
    )),
    # session-store construction lowers the serve programs (the
    # aot_compile call sites) and jits the slot-copy helpers
    # (_reset1/_write_slot/_take1/_ring_take — pure dynamic-slice
    # plumbing, covered by the serve programs' scatter budgets)
    ("serve/session.py", "SessionStore.__init__"): ("program", (
        "serve_decide", "serve_decide_batch",
        "serve_decide_batch_sharded", "serve_decide_batch_group",
        "serve_decide_record", "serve_decide_batch_record",
        "serve_decide_record_ring", "serve_decide_batch_record_ring",
    )),
    # tooling, not a hot program: the memory pass's own compile probe
    ("obs/memory.py", "aot_memory"): ("waiver",
        "analysis tooling: compiles the PROBED program, is not one"),
    # host-API convenience wrapper; the underlying policy programs are
    # audited as decima_score/decima_batch_policy
    ("schedulers/decima.py", "DecimaScheduler.schedule"): ("waiver",
        "host convenience API; the policy it jits is audited as "
        "decima_score/decima_batch_policy"),
    # baseline heuristics: cold-path comparison schedulers, not part
    # of the training/serving hot loop
    ("schedulers/heuristics.py", "round_robin_policy"): ("waiver",
        "baseline comparison scheduler, cold path"),
    ("schedulers/heuristics.py", "random_policy"): ("waiver",
        "baseline comparison scheduler, cold path"),
    ("env/observe.py", "observe"): ("program", ("observe",)),
    # episode initialization: traced once per reset, audited inside
    # the collector programs that inline it
    ("env/core.py", "reset"): ("waiver",
        "episode init, cold path; inlined into the audited "
        "collectors"),
    ("env/core.py", "reset_pair"): ("waiver",
        "episode init, cold path; inlined into the audited "
        "collectors"),
    ("env/core.py", "reset_from_sequence"): ("waiver",
        "episode init, cold path; inlined into the audited "
        "collectors"),
    ("env/core.py", "step"): ("program", (
        "micro_step", "decide_micro_step", "drain_to_decision",
    )),
    # gym-API compatibility shim: external-interface path,
    # perf-audited only through the native collectors
    ("env/gym_compat.py", "SparkSchedSimVectorEnv.__init__"): (
        "waiver", "gym-API compatibility shim"),
    ("env/gym_compat.py", "observe_batch"): ("waiver",
        "gym-API compatibility shim (batched observe helper)"),
    # the production collector program (batch axis) and its health
    # variant
    ("trainers/rollout.py", "collect_flat_sync_batch"): ("program", (
        "flat_collect_batch", "flat_collect_batch_health",
    )),
    ("trainers/rollout.py", "collect_flat_async_batch"): ("program", (
        "flat_collect_batch",
    )),
    # the reference collectors over core.step: what the tests and
    # chip_smoke.py hold the production collectors to; the trainer
    # does not reach them
    # the sweep loop (PR 46): the chunk is audited under its own name;
    # `init`'s reset of every lane is the reset program the env/core.py
    # entries above waive
    ("sweep.py", "<module>"): ("program", ("sweep_chunk",)),
    ("sweep.py", "_reset_lanes"): ("waiver",
        "core.reset over the lanes, once a sweep, before the first chunk"),
    ("trainers/rollout.py", "collect_sync"): ("waiver",
        "reference collector over core.step, parity-test path"),
    ("trainers/rollout.py", "collect_async"): ("waiver",
        "reference collector over core.step, parity-test path"),
    # Trainer.__init__ jits the collect/update pair; the update is
    # audited as ppo_update (+_health), the collect as
    # flat_collect_batch through the rollout entries above
    ("trainers/trainer.py", "Trainer.__init__"): ("program", (
        "ppo_update", "ppo_update_health", "flat_collect_batch",
    )),
}

_last_scan_count = 0


def last_scan_count() -> int:
    return _last_scan_count


def _canonical(imports: dict[str, str], node: ast.AST) -> str:
    from .lint import _dotted

    name = _dotted(node)
    if not name:
        return ""
    head, _, rest = name.partition(".")
    head = imports.get(head, head)
    return f"{head}.{rest}" if rest else head


def _is_jit_expr(imports: dict[str, str], node: ast.AST) -> bool:
    """jax.jit referenced bare (decorator) or called."""
    if isinstance(node, ast.Call):
        node = node.func
    return _canonical(imports, node) == "jax.jit"


class _SiteFinder(ast.NodeVisitor):
    def __init__(self, relpath: str, imports: dict[str, str]) -> None:
        self.relpath = relpath
        self.imports = imports
        self.stack: list[str] = []
        self.sites: list[tuple[str, int, str]] = []  # qualname, line

    def _qual(self) -> str:
        return ".".join(self.stack) if self.stack else "<module>"

    def _record(self, lineno: int, what: str) -> None:
        self.sites.append((self._qual(), lineno, what))

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    def _visit_func(self, node) -> None:
        for dec in node.decorator_list:
            # site lineno is the DECORATOR's line, so an
            # `# analysis: allow(...)` pragma sits where the reader
            # sees the jit, not on the def below it
            if _is_jit_expr(self.imports, dec):
                self.stack.append(node.name)
                self._record(dec.lineno, "@jax.jit")
                self.stack.pop()
            elif (isinstance(dec, ast.Call)
                    and _canonical(self.imports, dec.func)
                    in ("functools.partial", "partial")
                    and dec.args
                    and _is_jit_expr(self.imports, dec.args[0])):
                self.stack.append(node.name)
                self._record(dec.lineno, "@partial(jax.jit, ...)")
                self.stack.pop()
        self.stack.append(node.name)
        for stmt in node.body:
            self.visit(stmt)
        self.stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_Call(self, node: ast.Call) -> None:
        canon = _canonical(self.imports, node.func)
        if canon == "jax.jit":
            self._record(node.lineno, "jax.jit(...)")
        elif (canon.endswith("aot_compile")
                and self.relpath != "serve/aot.py"):
            # lowering call sites outside the definition module
            self._record(node.lineno, "aot_compile(...)")
        self.generic_visit(node)


def _collapse_qual(qual: str) -> str:
    """Nested defs fold onto their outermost enclosing scope: the
    table keys on where the site LIVES, not closure depth."""
    parts = qual.split(".")
    return ".".join(parts[:2]) if len(parts) > 2 else qual


def check_paths(root: pathlib.Path,
                strict: bool = False) -> list[Violation]:
    global _last_scan_count
    found: list[Violation] = []
    seen: set[tuple[str, str]] = set()
    n = 0
    for path, rel in iter_package_files(root):
        n += 1
        source = path.read_text()
        try:
            tree = ast.parse(source)
        except SyntaxError as e:
            found.append(Violation("coverage", "syntax", rel, str(e)))
            continue
        pragmas = _pragmas(source)
        finder = _SiteFinder(rel, _import_table(tree))
        finder.visit(tree)
        for qual, lineno, what in finder.sites:
            key = (rel, _collapse_qual(qual))
            seen.add(key)
            if key in COVERAGE:
                continue
            if "coverage-unregistered-jit" in pragmas.get(lineno,
                                                          set()):
                continue
            found.append(Violation(
                "coverage", "coverage-unregistered-jit",
                f"{rel}:{lineno}",
                f"{what} in {qual} is not in the COVERAGE table: "
                f"register the program in jaxpr_audit.BUDGETS and map "
                f"it here, or add a waiver with the reason"))
    _last_scan_count = n
    if strict:
        from .jaxpr_audit import BUDGETS

        for key, (kind, data) in COVERAGE.items():
            if key not in seen:
                found.append(Violation(
                    "coverage", "coverage-stale-entry",
                    f"{key[0]}:{key[1]}",
                    f"COVERAGE lists this {kind} entry but no jit/AOT "
                    f"site exists there anymore"))
            if kind == "program":
                for name in data:
                    if name not in BUDGETS:
                        found.append(Violation(
                            "coverage", "coverage-unknown-program",
                            f"{key[0]}:{key[1]}",
                            f"mapped program {name!r} is not a "
                            f"jaxpr_audit.BUDGETS key"))
    return found


def check_package() -> list[Violation]:
    import sparksched_tpu

    root = pathlib.Path(sparksched_tpu.__file__).parent
    return check_paths(root, strict=True)
