"""`python -m sparksched_tpu.analysis` — run every static-analysis pass,
print a JSON report, exit non-zero on any violation.

Flags:
  --passes lint,coverage,concurrency,contracts,jaxpr,memory
                                  subset to run (default: all,
                                  cheap-first); `--passes memory` runs
                                  the HBM memory pass alone,
                                  `--passes concurrency` the host
                                  thread-ownership pass alone
  --quiet                         violations-only JSON (no measured
                                  counts) — the bench stamp subprocess
                                  uses this
  --programs observe,micro_step   registry subset for the jaxpr/memory
                                  passes (default: all 8; unknown names
                                  are an error)
  --mem-compile                   additionally AOT-compile every
                                  registry program on the current
                                  backend and report
                                  compiled.memory_analysis() (backend-
                                  true bytes; roughly doubles runtime)
Exit code 0 == analysis-clean tree.

JAX_PLATFORMS defaults to cpu (tracing is backend-independent, and the
audit must never claim an accelerator a bench session holds — PERF_ROUNDS.md
operational rules); an explicit JAX_PLATFORMS in the environment wins.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparksched_tpu.analysis",
        description="TPU-hostility static analysis (jaxpr audit + AST "
        "lint + pytree contracts)",
    )
    ap.add_argument(
        "--passes",
        default="lint,coverage,concurrency,contracts,jaxpr,memory",
        help="comma-separated subset of lint,coverage,concurrency,"
        "contracts,jaxpr,memory",
    )
    ap.add_argument(
        "--quiet", action="store_true",
        help="violations-only JSON (omit measured counts)",
    )
    ap.add_argument(
        "--programs", default=None,
        help="comma-separated registry subset for the jaxpr/memory "
        "passes (default: every registered hot program)",
    )
    ap.add_argument(
        "--mem-compile", action="store_true",
        help="AOT-compile the registry and report backend-true "
        "memory_analysis() bytes (chip session stage 11 uses this "
        "on-device; the default stays trace-only and CPU-pinned)",
    )
    args = ap.parse_args(argv)

    # pin the backend BEFORE jax initializes (run_all imports it)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from . import run_all

    passes = tuple(p for p in args.passes.split(",") if p)
    programs = (
        tuple(p for p in args.programs.split(",") if p)
        if args.programs else None
    )
    report = run_all(passes, programs=programs)
    if args.mem_compile:
        from .memory import program_memory_accounting

        report["mem_compile"] = program_memory_accounting(programs)
    if args.quiet:
        report = {
            "clean": report["clean"],
            "violation_count": report["violation_count"],
            "violations": report["violations"],
        }
    json.dump(report, sys.stdout, indent=1)
    sys.stdout.write("\n")
    return 0 if report["clean"] else 1


if __name__ == "__main__":
    sys.exit(main())
