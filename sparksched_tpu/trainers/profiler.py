"""Profiling utilities (reference trainers/utils/profiler.py:7-30).

The reference wraps every rollout in a cProfile context manager printing
top-N cumulative stats. Host-side Python profiling is meaningless for a
jitted program, so `Profiler` keeps the same context-manager interface but
reports wall time (through `obs.tracing.span`, the process's one host
timer: the block is in its record under `label`, and on the host track
of a running profiler) and, when a trace directory is given, captures a
`jax.profiler` device trace viewable in TensorBoard / Perfetto (phases
are labeled via `obs.tracing.annotate` scopes; the module docstring of
`obs/tracing.py` lists them, and `benchmarks/trace_reduce.py` turns
such a trace into device seconds per scope)."""

from __future__ import annotations

from ..obs.runlog import emit
from ..obs.tracing import span


class Profiler:
    """Context manager timing a block (and optionally tracing the devices).

    >>> with Profiler() as p:
    ...     rollout = collect(...)
    >>> p.elapsed  # seconds

    `sink(label, elapsed)` replaces the default stdout report — the
    trainer routes it into the JSONL runlog. The device trace is stopped
    in a try/finally: an exception inside a traced block (or inside the
    report itself) must not leave jax's process-global tracer running,
    which would poison the next capture with a "profiler already active"
    error."""

    def __init__(self, trace_dir: str | None = None,
                 label: str = "block", quiet: bool = False,
                 sink=None) -> None:
        self.trace_dir = trace_dir
        self.label = label
        self.quiet = quiet
        self.sink = sink
        self.elapsed = 0.0
        self._tracing = False

    def __enter__(self) -> "Profiler":
        if self.trace_dir:
            import jax

            jax.profiler.start_trace(self.trace_dir)
            self._tracing = True
        # a sink reports the span itself: not announced a second time
        self._span = span(self.label, announce=self.sink is None)
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> None:
        try:
            self._span.__exit__(exc_type, exc_val, exc_tb)
            self.elapsed = self._span.elapsed
            # the sink (runlog) always receives the span; `quiet` only
            # silences the console echo
            if self.sink is not None:
                self.sink(self.label, self.elapsed)
            if not self.quiet:
                emit(f"[profiler] {self.label}: {self.elapsed:.3f}s")
        finally:
            if self._tracing:
                self._tracing = False
                import jax

                jax.profiler.stop_trace()
