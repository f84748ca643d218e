"""Observability subsystem: on-device telemetry counters, structured
JSONL run logs, and legible device traces.

Three parts (ISSUE 2 tentpole), each usable on its own:

- `telemetry`: a small integer `Telemetry` pytree threaded (optionally)
  through `env/core.py`'s per-decision event loop and
  `env/flat_loop.py`'s micro-step engine — pure i32 adds inside jit,
  summarized on host once per iteration (`summarize`). Counts per-lane
  step types (DECIDE / FULFILL / EVENT), event pops by kind, bulk-pass
  consumption, fulfillments and commitment rounds, and the while-loop
  iteration counts from which the straggler ratio (max/mean over lanes)
  is *measured* rather than inferred from A/B steps/s pairs.
- `runlog`: a JSONL event stream per run under `artifacts/` — timed
  spans, telemetry summaries, per-iteration training stats, and JIT
  recompile events via `jax.monitoring` hooks. The default sink the
  trainer writes to (TensorBoard stays available as a mirror).
- `tracing`: named `annotate(...)` scopes (jax.named_scope +
  jax.profiler.TraceAnnotation) around the GNN eval, the env
  micro-step, the collection scatter and the PPO update, so a captured
  Perfetto trace carries those phase labels; and `span(...)`, the
  process's one host timer (PR 44): the trainer's start-up and every
  call of its compiled programs, with jax's own trace, lower, compile
  and cache events beside them, in one bounded record that the runlog
  and the benchmark's `setup.*` metrics read.
- `memory`: HBM byte accounting (ISSUE 5 tentpole) — compile-time
  `memory_analysis()` extraction, trace-time buffer sizing under the
  TPU tiled-layout model, the lane-fit advisor (max vmap lanes under
  an HBM budget), and runtime `device_memory_stats()` for stamping
  bench rows and trainer iterations.
- `metrics`: streaming serving metrics (ISSUE 11) — log-bucketed
  mergeable histograms (p50..p999 in O(buckets) memory, so
  million-request open-loop runs never retain samples) and a
  counter/gauge/histogram `MetricsRegistry` with Prometheus-text and
  runlog-JSONL exporters; `tracing` additionally carries the
  per-request `RequestTrace` span clock the serving front stamps
  (submit -> batch_admit -> dispatch -> device_compute ->
  scatter_back -> reply, the runlog `trace` record kind).
- `fleet` / `slo` / `ledger`: the fleet observability plane
  (ISSUE 17) — per-replica labeled scrape collector + windowed
  scoreboard (`FleetCollector`, the `/fleet` endpoint and
  `python -m sparksched_tpu.obs.fleet` CLI), declarative SLOs under
  multi-window burn-rate alerting with optional ParamBus rollback
  (`SLOMonitor`, the `alert` record kind) plus the online-loop depth
  probe (`OnlineLoopProbe`), and the cross-round perf-regression
  ledger over `artifacts/*.json` + `BENCH_*.json`
  (`python -m sparksched_tpu.obs.ledger`, the tier-1 gate).
"""

from .memory import device_memory_stats, lane_fit  # noqa: F401
from .metrics import (  # noqa: F401
    MetricsRegistry,
    StreamingHistogram,
    hist_summary,
    percentile_block,
)
from .runlog import RunLog, emit  # noqa: F401
from .slo import (  # noqa: F401
    OnlineLoopProbe,
    SLOMonitor,
    SLOSpec,
    slo_from_config,
)
from .telemetry import Telemetry, summarize, telemetry_zeros  # noqa: F401
from .tracing import RequestTrace, annotate, span  # noqa: F401

# PEP 562 lazy imports for the submodules that double as CLIs
# (`python -m sparksched_tpu.obs.{fleet,ledger}`) or that only the
# serving/attribution path needs: importing them eagerly here put the
# module object in sys.modules before runpy re-imported it, tripping
# the "found in sys.modules after import of package" RuntimeWarning
# (ISSUE 20 satellite). Consumers import these symbols or the
# submodules directly; both resolve identically through __getattr__.
_LAZY = {
    "FleetCollector": ("fleet", "FleetCollector"),
    "labeled_prometheus": ("fleet", "labeled_prometheus"),
    "Ledger": ("ledger", "Ledger"),
    "CritPathAnalyzer": ("critpath", "CritPathAnalyzer"),
    "SegmentProfile": ("critpath", "SegmentProfile"),
    "decompose": ("critpath", "decompose"),
    "HostProfiler": ("hostprof", "HostProfiler"),
}


def __getattr__(name: str):
    try:
        mod_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}"
        ) from None
    import importlib

    return getattr(
        importlib.import_module(f".{mod_name}", __name__), attr
    )


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
