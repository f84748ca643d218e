"""Static-analysis subsystem: the single source of truth for "is this
program still TPU-shaped".

The repo's hot loop must stay XLA-friendly (ROADMAP north star: as fast
as the hardware allows), but nothing in Python stops a stray host
callback, an f64 promotion, or a data-dependent while-loop from landing
in a hot program and surfacing rounds later as a bench slump. This
package makes TPU-hostility a CI failure, via three passes:

- `jaxpr_audit`: traces the registered hot programs (`observe`,
  `micro_step`, `decide_micro_step`, `drain_to_decision`,
  `DecimaScheduler.score`/`batch_policy`, `ppo_update`,
  `flat_collect_batch`, the `health:`-instrumented
  `ppo_update_health`/`flat_collect_batch_health` variants, plus the
  AOT serving programs `serve_decide`/`serve_decide_batch`) with
  audit-config shapes and checks each jaxpr rule-by-rule — no host
  callbacks outside an explicit allowlist, no f64/i64 anywhere,
  loop-free programs stay free of `while`/`scan`, and per-program
  eqn/gather/scatter budgets from ONE declarative table (migrated out
  of tests/test_jaxpr_budget.py).
- `lint`: AST rules over `sparksched_tpu/` source — host-scalar pulls
  (`.item()`/`float()`/`int()`/`np.asarray`) in traced modules, host
  syncs (`jax.device_get`/`block_until_ready`) outside the sanctioned
  host loop, implicit-dtype array constructors in hot modules,
  `time.*` reads in traced modules, and the generalized no-bare-print
  rule (moved here from tests/test_obs.py).
- `contracts`: declared dtype/shape schemas for `EnvState`,
  `Telemetry` and trajectory records, verified statically (the
  schemas are data the auditor reads via `jax.eval_shape`) plus a
  cheap runtime-assert mode tests use to pin that reset/step never
  drift structure, dtype, or shape (the recompile hazard).
- `coverage`: registry coverage — every `jax.jit`/AOT site in the
  package must map to a registered jaxpr-audit program or carry an
  explicit waiver (`coverage.COVERAGE`), closing the silent-gap
  failure mode as the program surface grows.
- `concurrency`: host-thread ownership + lock discipline over the
  serve/online stack — a thread-role call graph seeded at every
  `threading.Thread` spawn site, a declarative attribute OWNERSHIP
  table, non-owner-write / unlocked-shared / lock-order /
  blocking-under-lock / pump-blocking rules, and cross-validation of
  the runtime `assert_owner` placements (`sparksched_tpu.ownership`).
- `memory`: HBM-byte observability (ISSUE 5 tentpole) — per-program
  trace-time byte accounting under the TPU tiled-layout model, the
  `bank-broadcast` rule (no vmapped lane program may contain a
  lane-batched producer of a workload-bank-shaped array — the 19.4 GB
  round-5 OOM, checkable on CPU before backend folding), a
  declarative temp-bytes budget table, and the lane-fit advisor (max
  vmap lanes per program under a 17.2 GB HBM budget).

`python -m sparksched_tpu.analysis` runs all passes, prints a JSON
report, and exits non-zero on any violation. Budgets and rule scoping
are declarative data in the respective modules; see
`jaxpr_audit.BUDGETS` and `memory.MEM_BUDGETS` for the re-pin
procedures.
"""

from __future__ import annotations

import dataclasses
from typing import Any

__all__ = [
    "Violation",
    "run_all",
    "clean_in_subprocess",
    "analysis_clean_stamp",
]


@dataclasses.dataclass(frozen=True)
class Violation:
    """One rule violation. `passname` is the pass that found it
    (jaxpr | lint | contracts), `rule` the rule id, `where` the
    program/file/pytree location, `detail` a human-readable message."""

    passname: str
    rule: str
    where: str
    detail: str

    def to_dict(self) -> dict[str, str]:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return f"[{self.passname}/{self.rule}] {self.where}: {self.detail}"


DEFAULT_PASSES = ("lint", "coverage", "concurrency", "contracts",
                  "jaxpr", "memory")


def run_all(passes: tuple[str, ...] = DEFAULT_PASSES,
            programs: tuple[str, ...] | None = None,
            ) -> dict[str, Any]:
    """Run the selected passes and return the JSON-able report dict.

    Pass order is cheap-first (lint is pure AST, contracts is
    `eval_shape`-only, the jaxpr audit traces every registered hot
    program, the memory pass additionally traces the VMAPPED lane
    programs — it reuses the jaxpr pass's unbatched traces via the
    registry cache, so running both costs one set of traces plus the
    vmapped ones) so a dirty tree fails fast. `programs` restricts the
    jaxpr/memory registries (the lint/contracts passes ignore it). The
    heavy imports happen here, not at module import, so `from
    sparksched_tpu import analysis` stays light for the bench stamp
    helper."""
    report: dict[str, Any] = {"passes": {}, "violations": []}
    all_violations: list[Violation] = []
    for p in passes:
        if p == "lint":
            from . import lint

            vs = lint.lint_package()
            extra: dict[str, Any] = {"files_scanned": lint.last_scan_count()}
        elif p == "coverage":
            from . import coverage

            vs = coverage.check_package()
            extra = {"files_scanned": coverage.last_scan_count(),
                     "sites_registered": len(coverage.COVERAGE)}
        elif p == "concurrency":
            from . import concurrency

            vs = concurrency.check_package()
            extra = {"files_scanned": concurrency.last_scan_count()}
        elif p == "contracts":
            from . import contracts

            vs = contracts.check_all()
            extra = {"schemas": contracts.SCHEMA_NAMES}
        elif p == "jaxpr":
            from . import jaxpr_audit

            vs, measured = jaxpr_audit.audit_all(names=programs)
            extra = {"measured": measured}
        elif p == "memory":
            from . import memory

            vs, measured = memory.audit_memory(names=programs)
            extra = {"measured": measured}
        else:
            raise ValueError(f"unknown pass {p!r}")
        report["passes"][p] = extra | {
            "violations": [v.to_dict() for v in vs],
        }
        all_violations.extend(vs)
    report["violations"] = [v.to_dict() for v in all_violations]
    report["violation_count"] = len(all_violations)
    report["clean"] = not all_violations
    return report


def run_cli_subprocess(timeout: float = 900.0, quiet: bool = True):
    """Spawn the full analyzer CLI in a CPU-pinned subprocess — THE
    shared runner for every out-of-process gate, so invocation, env
    pinning and timeout semantics cannot diverge between them.

    A subprocess so the analyzer never pollutes the parent's jit
    caches; CPU-pinned because tracing is backend-independent and a
    chip belongs to one process (the parent bench keeps it). Returns
    the CompletedProcess, or None when the spawn failed or timed
    out."""
    import os
    import subprocess
    import sys

    env = os.environ | {"JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, "-m", "sparksched_tpu.analysis"]
    if quiet:
        cmd.append("--quiet")
    try:
        return subprocess.run(
            cmd, env=env, timeout=timeout, capture_output=True
        )
    except Exception:
        return None


def clean_in_subprocess(timeout: float = 900.0) -> bool:
    """True iff the tree is analysis-clean. Any failure — timeout,
    crash, violations — is False: a perf row that cannot prove the
    tree is clean must identify itself as dirty."""
    r = run_cli_subprocess(timeout)
    return r is not None and r.returncode == 0


_STAMP_CACHE: list = []


def analysis_clean_stamp() -> bool | None:
    """The bench-row `analysis_clean` value, memoized per process
    (a bench may emit several rows per run; the tree cannot change
    between them). `BENCH_ANALYSIS=0` skips the run and stamps null —
    an explicit opt-out, distinct from False which means the analyzer
    found violations, crashed, or timed out."""
    import os

    if os.environ.get("BENCH_ANALYSIS", "1") != "1":
        return None
    if not _STAMP_CACHE:
        _STAMP_CACHE.append(clean_in_subprocess())
    return _STAMP_CACHE[0]
